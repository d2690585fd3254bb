"""What an operation's ``op_name`` says of who owns it.

The compiled step's metadata gives every instruction the name stack it was
traced under: ``jit(_train_step_impl)/jit(main)/transpose(jvp(ViT))/
Encoder_0/block_3/FFBlock_0/fc1/dot_general``. A flax module contributes
its name, ``jax.named_scope`` its label; transforms wrap a component
(``jvp(loss)``), jitted functions add their own (``jit(clip)``), and the
last component is the primitive. ``Trainer._train_step_impl`` names the
four stretches of the step that no module owns: :data:`STEP_SCOPES`.
"""

from __future__ import annotations

import re

STEP_SCOPES = ("preprocess", "loss", "optimizer", "metrics")
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_FUNCTIONS = ("jit", "pjit")  # a function's name is not a scope


def scopes_of(op_name: str) -> list:
    """The scope labels of an ``op_name``: its components without the
    primitive at the end, transforms unwrapped (``transpose(jvp(loss))`` ->
    ``loss``), jitted functions' names and empty wrappers (``jvp()``) left
    out. The compiler joins the names of fused instructions with ``;``:
    the labels of all of them."""
    out = []
    for one in op_name.split(";"):
        for part in one.split("/")[:-1]:
            while (wrapped := _WRAPPED.match(part)) and wrapped.group(1) not in _FUNCTIONS:
                part = wrapped.group(2)
            if part and not _WRAPPED.match(part):
                out.append(part)
    return out


def in_step_scope(label: str):
    return lambda op_name: label in scopes_of(op_name)


def unowned(op_name: str) -> bool:
    """No ``op_name`` at all (the compiler's own copies), or one that
    names no scope: neither a module's path nor a step scope."""
    return not scopes_of(op_name)
