"""Shared PEP 562 lazy re-export machinery for the package ``__init__``s.

Four subpackages (:mod:`sav_tpu.utils`, :mod:`sav_tpu.obs`,
:mod:`sav_tpu.data`, :mod:`sav_tpu.train`) carry the same import
contract: their stdlib-only submodules (``device_check``, ``manifest``,
``synthetic``, ``supervisor`` ...) must be importable without dragging
``jax``/TF into the process — the elasticity supervisor and the serve
pool are parents of on-chip children, and a parent that touched the
backend would hold the chip against them. One factory instead of
four hand-copied ``__getattr__``/``__dir__`` bodies keeps the contract's
implementation in one place.

Every import resolved here is a phase span of the process timeline
(``sav:startup/import:<module>``, :mod:`sav_tpu.obs.spans`): the seam
through which the package's heavy modules are first loaded is where
start-up's import seconds are measured. Outermost only: a lazy import made
while another resolves is part of that one's span. While it resolves the
span counts as open on its thread, so what a module compiles as it is
imported is put down to its import (``obs/compile_log.py``).

Stdlib-only, and importing it only executes ``sav_tpu/__init__``'s
docstring — free on every path.
"""

from __future__ import annotations

import importlib
import sys
import threading
import time
from typing import Iterable

_resolving = threading.local()


def _import(target: str):
    """``import_module``; a module's first import, if no other lazy import
    is resolving on this thread, is a phase span of the timeline. Timed
    with a bare clock pair: nothing else runs before or around the import."""
    if target in sys.modules or getattr(_resolving, "active", False):
        return importlib.import_module(target)
    # Stdlib-only, like this module; by its full name, so that the package's
    # own lazy ``__getattr__`` is not what resolves it.
    from sav_tpu.obs.spans import pop_open, push_open, record_phase

    _resolving.active = True
    push_open("startup/import:" + target)
    start = time.perf_counter()
    try:
        return importlib.import_module(target)
    finally:
        end = time.perf_counter()
        pop_open()
        _resolving.active = False
        record_phase("startup/import:" + target, start, end)


def install_lazy_exports(
    namespace: dict, exports: dict, submodules: Iterable[str] = ()
):
    """Build a package's lazy ``(__getattr__, __dir__)`` pair.

    Args:
      namespace: the package ``__init__``'s ``globals()`` — resolved
        names are cached into it so each import happens once.
      exports: re-export name -> defining module (``"TrainConfig":
        "sav_tpu.train.config"``).
      submodules: names that resolve to the submodule itself (keeps
        ``sav_tpu.utils.metrics``-after-``import sav_tpu.utils`` working
        the way eager imports used to bind them).

    Usage in an ``__init__.py``::

        _EXPORTS = {...}
        __all__ = list(_EXPORTS)
        __getattr__, __dir__ = install_lazy_exports(
            globals(), _EXPORTS, {"submodule", ...}
        )
    """
    package = namespace["__name__"]
    submodules = frozenset(submodules)

    def __getattr__(name: str):
        if name in submodules:
            module = _import(f"{package}.{name}")
            namespace[name] = module
            return module
        target = exports.get(name)
        if target is None:
            raise AttributeError(
                f"module {package!r} has no attribute {name!r}"
            )
        value = getattr(_import(target), name)
        namespace[name] = value
        return value

    def __dir__():
        return sorted(set(namespace) | set(exports) | submodules)

    return __getattr__, __dir__
