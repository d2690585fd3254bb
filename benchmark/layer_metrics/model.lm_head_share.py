"""Percent of the device's operation time in the output heads and the token
loss of a looped language model: everything under the model's ``lm_head``
scope (each pass's ``[tokens, vocabulary]`` matmul and its cross-entropy,
forward, recomputed and backward) or the step's ``loss`` scope
(device_trace joined with the compiled step's ``op_name`` scopes). What the
loop multiplies by its passes and a plain decoder pays once. Nothing to
read where no operation carries an ``lm_head`` scope."""

from benchmark import stepscopes, tracered


def in_head_or_loss(op_name: str) -> bool:
    labels = stepscopes.scopes_of(op_name)
    return "lm_head" in labels or "loss" in labels


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    if not any("lm_head" in stepscopes.scopes_of(scopes.get(name, "")) for name in trace["op_seconds"]):
        return None
    return tracered.share_by_scope(trace, scopes, in_head_or_loss)
