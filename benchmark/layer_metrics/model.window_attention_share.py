"""Percent of the device's operation time in the sliding-window attention
cores: every operation under an ``attn/window`` scope, forward, recomputed and
backward, the kernel calls and whatever XLA builds around them inside the
scope (device_trace joined with the compiled step's ``op_name`` scopes). The
projections, the norms, the rotary and the gate lie outside it (``to_qkv``,
``to_out``). What the band costs a training step, three layers of five.

A ``while`` or ``conditional`` event spans its body, whose operations are
events of their own: such an event is left out of both sums, so that the
expert layers' overflow loops count once in the total (ROADMAP B5). Nothing to
read where no operation lies under such a scope: a model without a window
layer, or a program that does not name the scope."""

from benchmark import loop_events, stepscopes

SCOPE = ("attn", "window")


def in_core(op_name: str, scope=SCOPE) -> bool:
    labels = stepscopes.scopes_of(op_name)
    return any(pair == scope for pair in zip(labels, labels[1:]))


def read(record, trace, scope=SCOPE):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    seconds = loop_events.once(trace["op_seconds"])
    ours = sum(s for name, s in seconds.items() if in_core(scopes.get(name, ""), scope))
    if not ours:
        return None
    return 100.0 * ours / sum(seconds.values())
