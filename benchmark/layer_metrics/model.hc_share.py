"""Percent of the device's operation time in the hyper-connections: every
operation under an ``hc`` scope (``hc/pre``: the RMS over a token's streams,
the projection onto the maps, the gates, the weighted stream sum;
``hc/sinkhorn``: the projection onto the doubly stochastic matrices;
``hc/post``: the streams mixed and the sublayer's result added), forward,
recomputed and backward (device_trace joined with the compiled step's
``op_name`` scopes). What the residual path costs a training step beside the
sublayers it connects. Nothing to read where no operation carries an ``hc``
scope: a model whose residual is one array."""

from benchmark import stepscopes, tracered

LABEL = "hc"


def in_hc(op_name: str) -> bool:
    return LABEL in stepscopes.scopes_of(op_name)


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    if not any(in_hc(scopes.get(name, "")) for name in trace["op_seconds"]):
        return None
    return tracered.share_by_scope(trace, scopes, in_hc)
