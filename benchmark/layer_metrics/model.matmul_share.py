"""Percent of the device's operation time in the weight matmuls of the
blocks: the Q/K/V and output projections and the two MLP layers, forward
and backward, with what the compiler fused onto them (device_trace joined
with the compiled step's ``op_name`` scopes)."""

from benchmark import tracered

MARKS = ("to_qkv", "to_out", "/fc1/", "/fc2/")


def in_weight_matmul(scope: str) -> bool:
    return any(mark in scope for mark in MARKS)


def read(record, trace):
    if trace is None or not record.get("hlo_scopes"):
        return None
    return tracered.share_by_scope(trace, record["hlo_scopes"], in_weight_matmul)
