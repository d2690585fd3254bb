"""Percent of the device's operation time in the full (causal, whole-prefix)
attention cores of a decoder that names its two kinds of softmax layer: every
operation under an ``attn/full`` scope, forward, recomputed and backward
(device_trace joined with the compiled step's ``op_name`` scopes);
``model.window_attention_share``'s sum at the other scope. Nothing to read
where no operation lies under such a scope."""

import importlib.util
import os

_SIBLING = os.path.join(os.path.dirname(os.path.abspath(__file__)), "model.window_attention_share.py")
_spec = importlib.util.spec_from_file_location("layer_metric_model_window_attention_share", _SIBLING)
_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_window)

SCOPE = ("attn", "full")


def read(record, trace):
    return _window.read(record, trace, SCOPE)
