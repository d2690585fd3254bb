"""Percent of the device's operation time in the gated delta-rule blocks
beside their projections: every operation under a ``GatedDeltaNetBlock``
scope that is not one of its weight matmuls (``to_qkv``, ``to_out``): the
causal convolution and its SiLU (``gdn/conv``), the normalisation of q and k,
the gates and the rule itself (``gdn/rule``), the gated norm
(``gdn/gate_norm``), forward, recomputed and backward (device_trace joined
with the compiled step's ``op_name`` scopes). What carrying a state along the
sequence costs a training step. Nothing to read where no operation lies under
such a block: a model without one."""

from benchmark import stepscopes, tracered

BLOCK = "GatedDeltaNetBlock"
PROJECTIONS = ("to_qkv", "to_out")


def in_block(op_name: str) -> bool:
    return any(label.startswith(BLOCK) for label in stepscopes.scopes_of(op_name))


def beside_the_projections(op_name: str) -> bool:
    labels = stepscopes.scopes_of(op_name)
    return any(label.startswith(BLOCK) for label in labels) and not any(p in labels for p in PROJECTIONS)


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    if not any(in_block(scopes.get(name, "")) for name in trace["op_seconds"]):
        return None
    return tracered.share_by_scope(trace, scopes, beside_the_projections)
