"""Percent of its roofline the grouped-query attention of heads narrower than
a lane tile reaches (32 query heads of 64 on 8 key/value heads): the least
seconds the chip could take for the REAL causal work over the seconds of the
whole attention core: the Mosaic kernel calls under a
``GatedSelfAttentionBlock`` scope (the ``tpu_custom_call`` instructions of the
compiled step, which the driver lists as ``kernel_calls``) AND the copies XLA
builds around them under the same block, beside its projections (device_trace
joined with the compiled step's ``op_name`` scopes). The padding of the head
to 128 lanes and k and v repeated to the query heads are in the seconds and
not in the count: they show as lost share.

The least seconds of a call is the larger of its FLOPs over the chip's bf16
peak and its HBM bytes over the chip's bandwidth (``benchmark/device.py``),
with the counts of ``benchmark/flops/<family>.py``: ``4 D S (S + 1) / 2`` a
query head forward at the real ``D``, 2.5 times that backward; q and o at the
query heads, k and v at the key/value heads, once each. A forward call (first
run or recomputed under remat) counts one forward; the backward counts ONE
whole backward an application of the block (an application is a forward call
that is neither recomputed nor part of the transpose), which is one for each
backward call where the backward is one kernel (the accepted readers of PR 26
and PR 30 count half a backward a call, PERF.md section 7). The share cannot
pass 100%. Nothing to read where the step holds no such call, the family's
FLOP file counts no such kernel, or the head is a whole lane tile (another
reader's)."""

import importlib

from benchmark import device, stepscopes

BLOCK = "GatedSelfAttentionBlock"
PROJECTIONS = ("to_qkv", "to_out")


def in_core(op_name: str) -> bool:
    labels = stepscopes.scopes_of(op_name)
    return any(label.startswith(BLOCK) for label in labels) and not any(p in labels for p in PROJECTIONS)


def read(record, trace):
    calls, scopes = record.get("kernel_calls"), record.get("hlo_scopes")
    steps, config = record["spans"].get("traced_steps"), record.get("config") or {}
    if trace is None or not calls or not scopes or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    if not hasattr(counts, "attention_floor_seconds") or not hasattr(counts, "head_dim"):
        return None
    if counts.head_dim(config) % 128 == 0:
        return None
    attention = {name: scope for name, scope in calls.items() if BLOCK in scope}
    seconds = sum(s for name, s in trace["op_seconds"].items() if in_core(scopes.get(name, "")))
    if not attention or not seconds:
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
