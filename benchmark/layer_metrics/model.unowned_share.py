"""Percent of the device's operation time that the scope shares cannot
see: operations with no ``op_name`` (the compiler's own copies) or one
that names no scope, neither a flax module's path nor one of the step's
four (``preprocess``, ``loss``, ``optimizer``, ``metrics``): device_trace
joined with the compiled step's ``op_name`` scopes
(``benchmark/stepscopes.py``)."""

from benchmark import stepscopes, tracered


def read(record, trace):
    if trace is None or not record.get("hlo_scopes"):
        return None
    return tracered.share_by_scope(trace, record["hlo_scopes"], stepscopes.unowned)
