"""Percent of its roofline the short convolution's core reaches: the least
seconds the chip could take for ``C * conv(B * x~)`` over the seconds of the
operations under an ``sconv/core`` scope (device_trace joined with the
compiled step's ``op_name`` scopes), whoever implements the core: XLA's
fusions, or a kernel.

The least seconds are ``benchmark/flops/<family>.py``'s count of the core's
bytes over the chip's bandwidth (``benchmark/device.py``; the FLOPs are two
orders below them): forward three arrays of ``[tokens, D]`` in and one out in
the compute dtype, one more forward where the trace holds recomputed
``sconv/core`` operations, backward four in and three out; every
short-convolution layer of the cut. What an implementation moves beyond that
(a padded copy a tap, float32 intermediates through HBM, the RMS the block
reports, which the scope holds too) is in the seconds and not in the count, so
the share cannot pass 100%. Nothing to read where no operation lies under
such a scope, or the family's file counts no such core."""

import importlib

from benchmark import device, stepscopes

SCOPE, RECOMPUTED = ("sconv", "core"), "rematted_computation"


def in_core(labels) -> bool:
    return any(pair == SCOPE for pair in zip(labels, labels[1:]))


def read(record, trace):
    scopes, steps = record.get("hlo_scopes"), record["spans"].get("traced_steps")
    config = record.get("config") or {}
    if trace is None or not scopes or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None
    ours = {name: stepscopes.scopes_of(scopes.get(name, "")) for name in trace["op_seconds"]}
    ours = {name: labels for name, labels in ours.items() if in_core(labels)}
    seconds = sum(trace["op_seconds"][name] for name in ours)
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    if not seconds or not hasattr(counts, "short_conv_floor_seconds"):
        return None
    tokens = record["counters"]["images_per_step_per_chip"] * config["sequence_length"]
    recomputed = any(RECOMPUTED in labels for labels in ours.values())
    peaks = device.peaks(record["device"]["kind"])
    least = steps * counts.short_conv_floor_seconds(
        config, tokens, recomputed, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds
