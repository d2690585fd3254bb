"""Percent of the device's operation time spent computing forward values a
second time: operations whose ``op_name`` holds a ``rematted_computation``
component, which ``jax.checkpoint`` (flax's ``nn.remat``) gives every
operation it runs again in the backward pass, the looped stack's and the
heads' alike (device_trace joined with the compiled step's ``op_name``
scopes). What a step pays for the activations it did not keep; ``device.mfu``
counts none of it. Nothing to read where no operation carries the label: a
step that rematerialises nothing, or a record without scopes."""

from benchmark import stepscopes, tracered

LABEL = "rematted_computation"


def recomputed(op_name: str) -> bool:
    return LABEL in stepscopes.scopes_of(op_name)


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    if not any(recomputed(scopes.get(name, "")) for name in trace["op_seconds"]):
        return None
    return tracered.share_by_scope(trace, scopes, recomputed)
