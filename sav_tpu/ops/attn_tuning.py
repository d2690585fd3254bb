"""Shape→config cache for the attention dispatcher.

``tools/attn_tune.py`` sweeps (block_q, block_kv, block_b) per shape
across the xla / fused / flash backends on the live chip and emits a
JSON cache; this module is the *consumer* side: the ``auto`` dispatcher
(:func:`sav_tpu.ops.attention.resolve_attention_backend`) looks the
traced shape up here to pick the measured-winner backend and block
config instead of a hand-picked one.

Promotion is evidence-gated by construction: without a measured cache
entry the short-sequence band stays on XLA, and a fused/flash entry only
exists where a chip run confirmed the win, in the kernel's own time and
in the whole train step. The checked-in default cache
(``attn_tune_cache.json`` next to this module) carries the v5e
measurements of PERF.md §6 (PR 25): ``fused`` at L 197, D 64, H 6 and
12. Point ``SAV_ATTN_TUNE_CACHE`` / :func:`set_cache_path` /
``TrainConfig.attention_tune_cache`` at a fresh sweep to override.

Everything here runs at TRACE time only (the lookup is keyed on static
shapes) — no host work ever lands in the jitted hot path, and the file
is read once per (path, mtime) per process.

Cache schema (version 1)::

    {
      "version": 1,
      "device": "TPU v5e",
      "entries": {
        "<key>": {"backend": "xla"|"fused"|"pallas",
                   "block_q": int|null, "block_kv": int|null,
                   "block_b": int|null,
                   "fwd_ms": float|null, "fwd_bwd_ms": float|null,
                   "min_batch": int (optional),
                   "source": "<tool / PERF.md section>"}
      },
      "infeasible": {
        "<key>": [{"backend": ..., "block_q": ..., "block_kv": ...,
                    "block_b": ..., "error": "<Mosaic message>",
                    "backward": "one_kernel"|"two_kernels" (optional: the
                    flash backward the record was compiled with)}]
      }
    }

Keys come from :func:`shape_key` (a causal core's end in ``.causal``: it
does half the work of its shape unmasked and is measured apart; a banded
core's in ``.causal.window<W>``: it does a band's work, and a causal
entry's blocks were chosen for a triangle's); a lookup
tries the exact batch first,
then the batch-wildcard key (``B*``) so one measured model-zoo shape
covers every batch size that shares its sequence geometry — from
``min_batch`` up, where the entry has one: the smallest batch the verdict
was measured at (below it XLA keeps the dense tensors on chip and a
kernel's win does not carry over; such a shape reads as never swept).
"""

from __future__ import annotations

import json
import os
import threading
from typing import Optional

import jax.numpy as jnp

CACHE_VERSION = 1
ENV_VAR = "SAV_ATTN_TUNE_CACHE"
DEFAULT_CACHE_PATH = os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "attn_tune_cache.json"
)

_BACKENDS = ("xla", "fused", "pallas")

_lock = threading.Lock()
_cache_path_override: Optional[str] = None
# (path, mtime) -> parsed cache dict; misses/IO errors memoize as {}.
_loaded: dict = {}


def shape_key(
    batch, q_len: int, kv_len: int, heads: int, dim: int, dtype="bfloat16",
    causal: bool = False, value_dim: Optional[int] = None, window: Optional[int] = None,
) -> str:
    """Canonical cache key. ``batch`` may be ``'*'`` for the wildcard; a
    causal core does half the work of the same shape unmasked and has
    entries of its own (``....causal``), a banded one a band's
    (``....causal.window512``); a value head of another size than the
    query's is part of the head's name (``D192v128``)."""
    dt = jnp.dtype(dtype).name
    head = f"D{dim}" if value_dim in (None, dim) else f"D{dim}v{value_dim}"
    key = f"B{batch}.Lq{q_len}.Lkv{kv_len}.H{heads}.{head}.{dt}"
    if window is not None:
        return f"{key}.causal.window{window}"
    return key + ".causal" if causal else key


def set_cache_path(path: Optional[str]) -> None:
    """Process-wide cache-path override (trace-time state only; wired from
    ``TrainConfig.attention_tune_cache`` / ``bench.py --attn-tune-cache``).
    ``None`` restores the env-var / default resolution."""
    global _cache_path_override
    with _lock:
        _cache_path_override = path


def get_cache_path() -> str:
    with _lock:
        if _cache_path_override is not None:
            return _cache_path_override
    return os.environ.get(ENV_VAR, DEFAULT_CACHE_PATH)


def load_cache(path: Optional[str] = None) -> dict:
    """Parsed cache (``{}`` when the file is missing/invalid — a broken
    cache degrades to the static dispatch rule, never to a crash)."""
    path = path or get_cache_path()
    try:
        mtime = os.stat(path).st_mtime_ns
    except OSError:
        return {}
    key = (path, mtime)
    with _lock:
        if key in _loaded:
            return _loaded[key]
    try:
        with open(path) as f:
            cache = json.load(f)
        if not isinstance(cache, dict) or cache.get("version") != CACHE_VERSION:
            cache = {}
    except (OSError, ValueError):
        cache = {}
    with _lock:
        _loaded.clear()  # one live file per process is plenty
        _loaded[key] = cache
    return cache


def lookup(
    batch: int,
    q_len: int,
    kv_len: int,
    heads: int,
    dim: int,
    dtype="bfloat16",
    *,
    causal: bool = False,
    value_dim: Optional[int] = None,
    window: Optional[int] = None,
    path: Optional[str] = None,
) -> Optional[dict]:
    """Measured entry for a shape (exact batch, then batch-wildcard);
    ``None`` when the shape has never been swept. Entries with an unknown
    backend name are ignored rather than dispatched on."""
    entries = load_cache(path).get("entries", {})
    for b in (batch, "*"):
        entry = entries.get(shape_key(b, q_len, kv_len, heads, dim, dtype, causal, value_dim, window))
        if not isinstance(entry, dict) or entry.get("backend") not in _BACKENDS:
            continue
        if b == "*" and batch < entry.get("min_batch", 0):
            continue
        return entry
    return None


def block_config(entry: Optional[dict]) -> Optional[dict]:
    """The (block_q, block_kv, block_b) triple of a cache entry, with
    Nones dropped — the kwargs shape the kernels accept."""
    if not entry:
        return None
    cfg = {
        k: entry[k]
        for k in ("block_q", "block_kv", "block_b")
        if entry.get(k) is not None
    }
    return cfg or None


def write_cache(
    path: str,
    entries: dict,
    infeasible: Optional[dict] = None,
    *,
    device: Optional[str] = None,
    merge: bool = False,
) -> dict:
    """Write (or merge into) a cache file; returns the written dict.
    ``merge=True`` folds the new entries/infeasible records over an
    existing file's, so per-shape sweeps accumulate into one table."""
    cache = {"version": CACHE_VERSION, "entries": {}, "infeasible": {}}
    if merge and os.path.exists(path):
        old = load_cache(path)
        cache["entries"].update(old.get("entries", {}))
        cache["infeasible"].update(old.get("infeasible", {}))
        if old.get("device"):
            cache["device"] = old["device"]
    if device:
        cache["device"] = device
    cache["entries"].update(entries)
    for k, v in (infeasible or {}).items():
        cache["infeasible"].setdefault(k, [])
        cache["infeasible"][k].extend(v)
    tmp = f"{path}.tmp.{os.getpid()}"
    os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
    with open(tmp, "w") as f:
        json.dump(cache, f, indent=2, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return cache
