"""Percent of the device's operation time in the attention core: the QK^T
and PV einsums and the softmax between them, forward and backward
(device_trace joined with the compiled step's ``op_name`` scopes). The core
is everything under a ``SelfAttentionBlock`` scope that is not one of its
projections (``to_qkv``, ``to_out``)."""

from benchmark import tracered


def in_attention_core(scope: str) -> bool:
    return "SelfAttentionBlock" in scope and "to_qkv" not in scope and "to_out" not in scope


def read(record, trace):
    if trace is None or not record.get("hlo_scopes"):
        return None
    return tracered.share_by_scope(trace, record["hlo_scopes"], in_attention_core)
