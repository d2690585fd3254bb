"""Percent of its roofline the latent attention kernel reaches: the least
seconds the chip could take for the Mosaic kernel calls under a latent
attention block's scope (a query/key head of 192 beside a value head of
128), over the seconds they took (device_trace; the calls are the
``tpu_custom_call`` instructions of the compiled step, which the driver lists
as ``kernel_calls``).

The least seconds of a call is the larger of its FLOPs over the chip's bf16
peak and its HBM bytes over the chip's bandwidth (``benchmark/device.py``),
with the counts of ``benchmark/flops/<family>.py``: the logits at the
query/key head and the weighted sum at the value head over the visible
pairs, each operand and result once at its own head size. A forward call
(first run or recomputed under remat) counts one forward; the backward's
kernels (dq; dk and dv) count one backward between them. Work beyond that
(masked pairs, lanes a head is padded to, the second recomputation of the
logits) is in the seconds and not in the count, so the share cannot pass
100%. Nothing to read where the step holds no such call, or the family's
FLOP file counts no such kernel."""

import importlib

from benchmark import device

BLOCK = "LatentSelfAttentionBlock"
BACKWARD_KERNELS = 2  # the blocked backward is two calls: dq, and dk with dv


def read(record, trace):
    calls, steps = record.get("kernel_calls"), record["spans"].get("traced_steps")
    config = record.get("config") or {}
    if trace is None or not calls or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None
    attention = {name: scope for name, scope in calls.items() if BLOCK in scope}
    seconds = sum(trace["op_seconds"].get(name, 0.0) for name in attention)
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    if not seconds or not hasattr(counts, "attention_floor_seconds"):
        return None
    backward = sum(
        1 for scope in attention.values()
        if "transpose(" in scope and "rematted_computation" not in scope
    )
    forward = len(attention) - backward
    peaks = device.peaks(record["device"]["kind"])
    floor = counts.attention_floor_seconds(
        config, record["counters"]["images_per_step_per_chip"],
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"],
    )
    least = steps * (forward * floor["forward"] + backward / BACKWARD_KERNELS * floor["backward"])
    return 100.0 * least / seconds
