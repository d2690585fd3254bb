"""Percent of its roofline the banded flash kernel reaches: the least seconds
the chip could take for the window layers' cores over the seconds of the
Mosaic kernel calls under an ``attn/window`` scope (device_trace; the calls
are the ``tpu_custom_call`` instructions of the compiled step, which the
driver lists as ``kernel_calls``).

The least seconds of a call is the larger of its FLOPs over the chip's bf16
peak and its HBM bytes over the chip's bandwidth (``benchmark/device.py``),
with the counts of ``benchmark/flops/<family>.py``'s
``window_attention_floor_seconds``: THE BAND'S pairs, ``sum_i min(i + 1,
window)`` a query head (not the triangle's), ``4 D`` FLOP a pair forward and
2.5 times that backward; q and o at the layer's query heads, k and v at the
key/value heads, once each. A forward call (first run or recomputed under
remat) counts one forward floor; a backward call (``transpose(`` in its scope,
not recomputed) one backward floor: the backward is one kernel a layer. Work
beyond the count (the masked pairs of the blocks an edge crosses, skipped
cells' grid steps, a group's dk and dv written a query head) is in the seconds
and not in the count, so the share cannot pass 100%: a kernel that swept the
whole triangle would read low, not high. Nothing to read where the step holds
no such call (another family, or a program that does not name the scope), or
the family's FLOP file counts no such kernel."""

import importlib

from benchmark import device, stepscopes

SCOPE, FLOOR = ("attn", "window"), "window_attention_floor_seconds"


def read(record, trace, scope=SCOPE, floor_name=FLOOR):
    calls, steps = record.get("kernel_calls"), record["spans"].get("traced_steps")
    config = record.get("config") or {}
    if trace is None or not calls or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None

    def in_core(op_name: str) -> bool:
        labels = stepscopes.scopes_of(op_name)
        return any(pair == scope for pair in zip(labels, labels[1:]))

    ours = {name: op_name for name, op_name in calls.items() if in_core(op_name)}
    seconds = sum(trace["op_seconds"].get(name, 0.0) for name in ours)
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    if not seconds or not hasattr(counts, floor_name):
        return None
    backward = sum(1 for s in ours.values() if "transpose(" in s and "rematted_computation" not in s)
    forward = len(ours) - backward
    peaks = device.peaks(record["device"]["kind"])
    floor = getattr(counts, floor_name)(
        config, record["counters"]["images_per_step_per_chip"],
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"],
    )
    least = steps * (forward * floor["forward"] + backward * floor["backward"])
    return 100.0 * least / seconds
