"""Model FLOPs utilisation of the device while it is busy: the forward and
backward FLOPs a step needs on one chip (``benchmark/flops/<family>.py``
per image, no recomputation, times the chip's images per step) over the
device's busy seconds per traced step (the union of its operation
intervals, per chip and averaged: device_trace) over the chip's bf16 peak
(``benchmark/device.py``). It takes nothing from the host's clock: idle
time between operations is ``device.idle_share.train``'s, not this
metric's. Read on a chip of the peaks table only; no ``min``."""

from benchmark import device


def read(record, trace):
    steps = record["spans"].get("traced_steps")
    if trace is None or not steps or record["device"]["platform"] != "tpu":
        return None
    counters = record["counters"]
    flops_per_step = counters["train_flops_per_image"] * counters["images_per_step_per_chip"]
    peak = device.peaks(record["device"]["kind"])["bf16_flops_per_s"]
    return 100.0 * flops_per_step / (trace["busy_s"] / steps) / peak
