"""The comparison that decides ``correct``.

Each number compared has a limit of its own; ``correct`` is true when every
number is finite and within its limit. PERF.md gives, for each limit, the
readings it was set from.
"""

from __future__ import annotations

import math

import numpy as np


def worst_leaf_norm_gap(program_norms, reference_norms) -> float:
    """Largest, over the leaves, gap between the program's norm of a leaf
    and the reference's, against the reference's norm of that leaf or of
    its median leaf, whichever is larger (some gradients are all but zero)."""
    p = np.asarray(program_norms, np.float64)
    r = np.asarray(reference_norms, np.float64)
    if p.shape != r.shape:
        raise ValueError(f"{p.shape} program leaves against {r.shape} reference leaves")
    scale = np.maximum(r, np.median(r))
    return float(np.max(np.abs(p - r) / scale))


def relative_gap(program: float, reference: float) -> float:
    return abs(program - reference) / abs(reference)


def decide(numbers: dict, limits: dict) -> tuple[bool, list]:
    """``(correct, rows)``; a row is one number beside its limit. A number
    with no limit in ``limits`` is an error: nothing is compared unheld."""
    rows = []
    for name, value in numbers.items():
        if name not in limits:
            raise KeyError(f"no limit for the compared number {name!r}")
        limit = limits[name]
        ok = math.isfinite(value) and value <= limit
        rows.append({"check": name, "value": value, "limit": limit, "ok": ok})
    return all(row["ok"] for row in rows), rows
