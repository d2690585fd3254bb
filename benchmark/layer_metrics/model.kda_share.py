"""Percent of the device's operation time in the Kimi delta attention blocks:
every operation under a ``KDABlock`` scope, the six input projections and the
output merge (``to_qkv``, ``to_out``), the three convolutions (``kda/conv``),
the normalisation, the gates and the rule (``kda/rule``) and the gated norm
(``kda/gate_norm``), forward, recomputed and backward (device_trace joined
with the compiled step's ``op_name`` scopes). What the vector-decay mixer
costs a training step, five layers of six.

A ``while`` or ``conditional`` event spans its body, whose operations are
events of their own: such an event is left out of both sums, so that the
rule's scans (and the expert layers' overflow loops in the total) count once
(ROADMAP B5). Nothing to read where no operation lies under such a block: a
model without one."""

from benchmark import loop_events, stepscopes

BLOCK = "KDABlock"


def in_block(op_name: str) -> bool:
    return any(label.startswith(BLOCK) for label in stepscopes.scopes_of(op_name))


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    seconds = loop_events.once(trace["op_seconds"])
    ours = sum(s for name, s in seconds.items() if in_block(scopes.get(name, "")))
    if not ours:
        return None
    return 100.0 * ours / sum(seconds.values())
