"""Percent of the device's operation time that routing costs beside the
matmuls it feeds: everything under an expert layer's ``route``, ``dispatch``
or ``combine`` scope (``model.moe_routed_share`` without ``experts``),
forward, recomputed and backward (device_trace joined with the compiled
step's ``op_name`` scopes). One chip's share of an expert-parallel layer
runs no exchange: this is the sort, the gathers and the router alone.
Nothing to read where no operation lies under an expert layer."""

import importlib.util
import os

_SIBLING = os.path.join(os.path.dirname(os.path.abspath(__file__)), "model.moe_routed_share.py")
_spec = importlib.util.spec_from_file_location("layer_metric_model_moe_routed_share", _SIBLING)
_routed = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_routed)

AROUND_THE_MATMULS = ("route", "dispatch", "combine")


def read(record, trace):
    return _routed.share_under(record, trace, AROUND_THE_MATMULS)
