"""Persistent XLA compilation cache — the one rule for where it lives.

JAX's persistent cache, keyed on (HLO, compile options, backend version),
turns a repeated compile into a disk read; the directory is part of what
makes a cache findable, so it must not move between runs. The rule, used
by the trainer, the serve engine, ``bench.py``, the tools and
``chip_smoke.py``:

1. ``JAX_COMPILATION_CACHE_DIR`` set: JAX placed the cache there itself
   when it was imported. No code sets another directory.
2. Otherwise an explicit override (``--compilation-cache-dir`` /
   ``TrainConfig.compilation_cache_dir`` /
   ``ServeConfig.compilation_cache_dir``) — it loses to the variable.
3. Otherwise, on a TPU, :data:`DEFAULT_CACHE_DIR`: one fixed directory
   inside the checkout, ignored by git. On the CPU the cache stays off
   unless 1 or 2 asked for it (tests and rehearsals compile small
   programs and must not share state through the disk).

Must run before the first compilation it should cover (Trainer and
ServeEngine apply it in ``__init__``, before any jit dispatch).
"""

from __future__ import annotations

import os
from typing import Optional

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"

#: Fixed in-checkout location (listed in .gitignore). Never derived from a
#: temporary name, a process id or the time.
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ),
    ".jax_cache",
)


def _reset_cache_singleton() -> None:
    # jax freezes the cache's on/off decision and directory at the
    # process's FIRST compilation. A Trainer or ServeEngine built after
    # any earlier jit dispatch (a warm-up, another engine, an earlier
    # test that used a different directory) would silently keep the old
    # decision. This is the one place that drops the frozen object.
    from jax.experimental.compilation_cache import compilation_cache

    compilation_cache.reset_cache()


def _set_cache_dir(cache_dir: Optional[str]) -> None:
    # The single place that sets jax's cache directory. Where the
    # variable is set jax read it at import, and it wins.
    import jax

    if not os.environ.get(CACHE_DIR_ENV):
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    _reset_cache_singleton()


def resolve_cache_dir(override: Optional[str] = None) -> Optional[str]:
    """Directory the rule above picks, or None when the cache stays off."""
    env = os.environ.get(CACHE_DIR_ENV)
    if env:
        return env
    if override:
        return override
    import jax

    if jax.default_backend() == "tpu":
        return DEFAULT_CACHE_DIR
    return None


def enable_persistent_cache(
    override: Optional[str] = None,
    *,
    min_compile_time_secs: Optional[float] = None,
) -> Optional[str]:
    """Apply the rule; return the cache directory in use (None = off).

    ``min_compile_time_secs``: only persist compilations slower than this
    (None keeps jax's default, 1 s; the serve engine passes 0.0 so every
    bucket executable comes back from disk on a warm start).
    """
    import jax

    cache_dir = resolve_cache_dir(override)
    if cache_dir is None:
        return None
    os.makedirs(cache_dir, exist_ok=True)
    if min_compile_time_secs is not None:
        jax.config.update(
            "jax_persistent_cache_min_compile_time_secs",
            float(min_compile_time_secs),
        )
    _set_cache_dir(cache_dir)
    return cache_dir


def disable_persistent_cache() -> None:
    """Turn the persistent cache fully off — the inverse of
    :func:`enable_persistent_cache` for callers that enable it
    temporarily (tests). Clearing the directory alone is not enough: the
    live cache object keeps serving the old one, so later identical
    programs come back as deserialized executables from a path the caller
    believes is disabled. Where ``JAX_COMPILATION_CACHE_DIR`` is set the
    cache stays where the variable put it."""
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    _set_cache_dir(None)
