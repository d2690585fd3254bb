"""Percent of the device's operation time under the expert layers' ``route``
scopes: the router's matmul and sigmoid, the group-limited selection (the
``route/groups`` step inside: a top-2 a group, a top-k over the groups, the
mask), the top-k over the experts, the weights' normalisation, the counts and
the balance term, forward, recomputed and backward (device_trace joined with
the compiled step's ``op_name`` scopes). What choosing 8 of 512 experts in 4
of 8 groups costs a step, beside the dispatch, the matmuls and the sum that
``model.moe_dispatch_share`` and ``model.moe_routed_share`` read.

A ``while`` or ``conditional`` event spans its body: it is left out of both
sums (ROADMAP B5). Nothing to read where the router picks inside no groups
(no operation lies under a ``route/groups`` scope): the accepted expert
families' cells."""

from benchmark import loop_events, stepscopes

LAYER, ROUTE, GROUPS = "moe", "route", "groups"


def under_route(op_name: str) -> bool:
    labels = stepscopes.scopes_of(op_name)
    return any(a == LAYER and b == ROUTE for a, b in zip(labels, labels[1:]))


def under_groups(op_name: str) -> bool:
    labels = stepscopes.scopes_of(op_name)
    return any(a == ROUTE and b == GROUPS for a, b in zip(labels, labels[1:]))


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    seconds = loop_events.once(trace["op_seconds"])
    if not any(under_groups(scopes.get(name, "")) for name in seconds):
        return None
    return 100.0 * sum(s for name, s in seconds.items() if under_route(scopes.get(name, ""))) / sum(seconds.values())
