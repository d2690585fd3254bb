"""Percent of the device's operation time in the multi-token-prediction
module: everything under the model's ``mtp`` scope (the input projection, the
module's own expert layer, its final norm, its pass through the head and its
cross-entropy), forward, recomputed and backward (device_trace joined with
the compiled step's ``op_name`` scopes). What predicting a second token costs
a training step. Nothing to read where no operation carries an ``mtp``
scope."""

from benchmark import stepscopes, tracered

LABEL = "mtp"


def in_mtp(op_name: str) -> bool:
    return LABEL in stepscopes.scopes_of(op_name)


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    if not any(in_mtp(scopes.get(name, "")) for name in trace["op_seconds"]):
        return None
    return tracered.share_by_scope(trace, scopes, in_mtp)
