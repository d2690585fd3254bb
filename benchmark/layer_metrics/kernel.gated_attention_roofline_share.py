"""Percent of its roofline the gated grouped-query attention kernel reaches:
the least seconds the chip could take for the Mosaic kernel calls under a
gated attention block's scope (16 query heads on 2 key/value heads of 256)
over the seconds they took (device_trace; the calls are the
``tpu_custom_call`` instructions of the compiled step, which the driver lists
as ``kernel_calls``).

The least seconds of a call is the larger of its FLOPs over the chip's bf16
peak and its HBM bytes over the chip's bandwidth (``benchmark/device.py``),
with the counts of ``benchmark/flops/<family>.py``: ``4 D S (S + 1) / 2`` a
query head forward and 2.5 times that backward; q and o at the query heads, k
and v at the key/value heads, once each. A forward call (first run or
recomputed under remat) counts one forward. The backward counts ONE backward
an application of the block, however many calls carry it (one since the
backward is one kernel, two before): an application is a forward call that is
neither recomputed nor part of the transpose. Work beyond the count (masked
pairs of the diagonal's tiles, the group's dk and dv written a query head and
summed after the call) is in the seconds and not in the count, so the share
cannot pass 100%. Nothing to read where the step holds no such call, or the
family's FLOP file counts no such kernel."""

import importlib

from benchmark import device

BLOCK = "GatedSelfAttentionBlock"


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
    if not seconds or not hasattr(counts, "attention_floor_seconds") or "head_dim" not in config:
        return None
    backward = [s for s in attention.values() if "transpose(" in s and "rematted_computation" not in s]
    forward = len(attention) - len(backward)
    applications = sum(1 for s in attention.values() if "transpose(" not in s and "rematted_computation" not in s)
    peaks = device.peaks(record["device"]["kind"])
    floor = counts.attention_floor_seconds(
        config, record["counters"]["images_per_step_per_chip"],
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"],
    )
    least = steps * (forward * floor["forward"] + (applications if backward else 0) * floor["backward"])
    return 100.0 * least / seconds
