"""Percent of the device's operation time in the routed half of the expert
layers: everything under an expert layer's ``route``, ``dispatch``,
``experts`` or ``combine`` scope (scores, top-k and the balance terms; the
sort and the gather of rows; the grouped matmuls; the gather back and the
weighted sum), forward, recomputed and backward, the multi-token-prediction
module's layer included (device_trace joined with the compiled step's
``op_name`` scopes). The shared expert is not in it: a dense model pays that
too. Nothing to read where no operation lies under an expert layer."""

from benchmark import stepscopes, tracered

LAYER = "moe"
ROUTED = ("route", "dispatch", "experts", "combine")


def under(op_name: str, parts) -> bool:
    """Some ``moe`` label of ``op_name`` is followed by one of ``parts``."""
    labels = stepscopes.scopes_of(op_name)
    return any(a == LAYER and b in parts for a, b in zip(labels, labels[1:]))


def share_under(record, trace, parts):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    if not any(under(scopes.get(name, ""), ROUTED) for name in trace["op_seconds"]):
        return None
    return tracered.share_by_scope(trace, scopes, lambda op_name: under(op_name, parts))


def read(record, trace):
    return share_under(record, trace, ROUTED)
