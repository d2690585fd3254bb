"""Percent of the device's operation time under a ``ShortConvBlock`` scope:
the double-gated short convolution whole, its input and output projections
(``to_qkv``, ``to_out``) and the two gates with the convolution between them
(``sconv/core``), forward, recomputed and backward (device_trace joined with
the compiled step's ``op_name`` scopes). What the token mixer that is neither
attention nor a recurrence costs a training step. Nothing to read where no
operation lies under such a block: a model without one."""

from benchmark import stepscopes, tracered

BLOCK = "ShortConvBlock"


def in_block(op_name: str) -> bool:
    return any(label.startswith(BLOCK) for label in stepscopes.scopes_of(op_name))


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    if not any(in_block(scopes.get(name, "")) for name in trace["op_seconds"]):
        return None
    return tracered.share_by_scope(trace, scopes, in_block)
