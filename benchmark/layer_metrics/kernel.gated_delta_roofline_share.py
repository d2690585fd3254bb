"""Percent of its roofline the gated delta rule reaches: the least seconds the
chip could take for the rule over the seconds of the operations under a
``gdn/rule`` scope (device_trace joined with the compiled step's ``op_name``
scopes), whatever implements it: XLA's scan and fusions, or a kernel.

The least seconds are ``benchmark/flops/<family>.py``'s count of the chunked
algorithm at a chunk of 64, the same for every implementation: per token and
value head 180,224 FLOP forward and each operand once (q, k at the key heads,
v and o at the value heads in the compute dtype, g and beta float32); one
more forward where the trace holds recomputed ``gdn/rule`` operations; twice
the forward for the backward; a pass the larger of its FLOPs over the chip's
bf16 peak and its bytes over its bandwidth (``benchmark/device.py``); every
delta-rule layer of the cut. What an implementation does beyond that (the
triangular system, float32 passes, intermediates through HBM, the
normalisation of q and k and the gates, which the scope holds too) is in the
seconds and not in the count, so the share cannot pass 100%. Nothing to read
where no operation lies under such a scope, or the family's file counts no
such rule."""

import importlib

from benchmark import device, stepscopes

SCOPE, RECOMPUTED = ("gdn", "rule"), "rematted_computation"


def in_rule(labels) -> bool:
    return any(pair == SCOPE for pair in zip(labels, labels[1:]))


def read(record, trace):
    scopes, steps = record.get("hlo_scopes"), record["spans"].get("traced_steps")
    config = record.get("config") or {}
    if trace is None or not scopes or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None
    ours = {name: stepscopes.scopes_of(scopes.get(name, "")) for name in trace["op_seconds"]}
    ours = {name: labels for name, labels in ours.items() if in_rule(labels)}
    seconds = sum(trace["op_seconds"][name] for name in ours)
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    if not seconds or not hasattr(counts, "gated_delta_floor_seconds"):
        return None
    tokens = record["counters"]["images_per_step_per_chip"] * config["sequence_length"]
    recomputed = any(RECOMPUTED in labels for labels in ours.values())
    peaks = device.peaks(record["device"]["kind"])
    least = steps * counts.gated_delta_floor_seconds(
        config, tokens, recomputed, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds
