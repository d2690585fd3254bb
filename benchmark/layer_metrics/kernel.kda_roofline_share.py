"""Percent of its roofline the vector-decay delta rule reaches: the least
seconds the chip could take for the rule over the seconds of the operations
under a ``kda/rule`` scope (device_trace joined with the compiled step's
``op_name`` scopes), whatever implements it: XLA's program and scans, or a
kernel.

The least seconds are ``benchmark/flops/<family>.py``'s
``kda_rule_floor_seconds``: the chunked algorithm's matrix work at a chunk of
64 (163,840 FLOP a token and head forward) and each operand once (q, k, v and
o in the compute dtype, g a float32 a key lane, beta a float32 a head); one
more forward where the trace holds recomputed ``kda/rule`` operations; twice
the forward for the backward; a pass the larger of its FLOPs over the chip's
bf16 peak and its bytes over its bandwidth (``benchmark/device.py``); every
KDA layer of the cut. What an implementation does beyond that (the triangular
system, float32 passes, intermediates through HBM, the normalisation of q and
k and the gates, which the scope holds too) is in the seconds and not in the
count, so the share cannot pass 100%.

The rule's scans are ``while`` instructions: the trace holds an event for the
loop, which spans its body, and events for the body's operations. The loop's
own event is left out here (an instruction named ``while`` or ``conditional``),
so that a scan's seconds count once, by its body (ROADMAP B5: the accepted
scalar-rule reader sums both). Nothing to read where no operation lies under
such a scope, or the family's file counts no such rule."""

import importlib

from benchmark import device, loop_events, stepscopes

SCOPE, RECOMPUTED = ("kda", "rule"), "rematted_computation"


def in_rule(labels) -> bool:
    return any(pair == SCOPE for pair in zip(labels, labels[1:]))


def read(record, trace):
    scopes, steps = record.get("hlo_scopes"), record["spans"].get("traced_steps")
    config = record.get("config") or {}
    if trace is None or not scopes or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None
    ours = {name: stepscopes.scopes_of(scopes.get(name, "")) for name in loop_events.once(trace["op_seconds"])}
    ours = {name: labels for name, labels in ours.items() if in_rule(labels)}
    seconds = sum(trace["op_seconds"][name] for name in ours)
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    if not seconds or not hasattr(counts, "kda_rule_floor_seconds"):
        return None
    tokens = record["counters"]["images_per_step_per_chip"] * config["sequence_length"]
    recomputed = any(RECOMPUTED in labels for labels in ours.values())
    peaks = device.peaks(record["device"]["kind"])
    least = steps * counts.kda_rule_floor_seconds(
        config, tokens, recomputed, peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"]
    )
    return 100.0 * least / seconds
