"""Percent of its roofline the causal flash kernel reaches in the full layers
of a decoder that names its two kinds of softmax layer: the least seconds the
chip could take for those layers' cores over the seconds of the Mosaic kernel
calls under an ``attn/full`` scope (device_trace and the driver's
``kernel_calls``). ``kernel.window_attention_roofline_share``'s reading at the
other scope, against ``full_attention_floor_seconds`` of
``benchmark/flops/<family>.py``: the triangle's pairs at the full layer's head
count. It cannot pass 100%. Nothing to read where the step holds no such call
or the family's FLOP file counts no such kernel."""

import importlib.util
import os

_SIBLING = os.path.join(os.path.dirname(os.path.abspath(__file__)), "kernel.window_attention_roofline_share.py")
_spec = importlib.util.spec_from_file_location("layer_metric_kernel_window_attention_roofline_share", _SIBLING)
_window = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_window)


def read(record, trace):
    return _window.read(record, trace, ("attn", "full"), "full_attention_floor_seconds")
