"""Percent of the attention core's device time spent in operations whose
``op_name`` ends in ``pallas_call``: of what ``model.attention_share``
counts (its predicate, loaded from its file), the part a Pallas kernel
took over (device_trace joined with the compiled step's ``op_name``
scopes). The copies XLA puts at the call's operands and results to change
their layout carry the call's name and count with it; PERF.md section 5
splits them off. 0 where ``auto`` resolves to the dense path; nothing to
read where no operation belongs to an attention core."""

import importlib.util
import os

_SIBLING = os.path.join(os.path.dirname(os.path.abspath(__file__)), "model.attention_share.py")
_spec = importlib.util.spec_from_file_location("layer_metric_model_attention_share", _SIBLING)
_attention_share = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_attention_share)
in_attention_core = _attention_share.in_attention_core


def from_a_kernel(scope: str) -> bool:
    """Every instruction the compiler fused into the operation came from a
    ``pallas_call`` (it joins their names with ``;``)."""
    return all(one.rsplit("/", 1)[-1] == "pallas_call" for one in scope.split(";"))


def read(record, trace):
    scopes = record.get("hlo_scopes")
    if trace is None or not scopes:
        return None
    core = kernel = 0.0
    for instruction, seconds in trace["op_seconds"].items():
        scope = scopes.get(instruction, "")
        if in_attention_core(scope):
            core += seconds
            if from_a_kernel(scope):
                kernel += seconds
    return 100.0 * kernel / core if core else None
