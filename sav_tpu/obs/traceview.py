"""Trace intelligence — machine-read the profiles the run already captures.

The capture layer (``TrainConfig.profile_dir``, ``obs/autoprof.py``,
``tools/profile_step.py``) writes ``jax.profiler`` chrome-trace files that
until now only a human in TensorBoard could read; every optimization in
PERF.md (the 70% attention tax, the fused-kernel promotion) came from
hand-reading them. This module is the machine version of that read:

- :func:`load_trace` / :func:`device_op_times` — parse the
  ``*.trace.json.gz`` chrome-trace export and sum complete-event ("X")
  durations per HLO op on the *device* planes. TPU traces carry device
  processes (``"TPU"`` in the process name); CPU-backend traces — what
  autoprof's tier-1 e2e actually captures — have no device plane at all,
  but their XLA execution threads tag op events with an ``hlo_op`` arg,
  so the selector falls back to exactly those events and the parser is
  exercisable without an accelerator.
- :func:`count_steps` — per-step segmentation via the module-execution /
  pjit step markers (top-level occurrences only: the markers nest).
- :func:`parse_hlo_op_index` — map HLO instruction names (what the trace
  calls an op, e.g. ``multiply_reduce_fusion.16``) to their
  ``metadata={op_name="..."}`` scope paths from the compiled
  executable's HLO text. Flax threads module names through those scopes
  (``Encoder_0/block_1/FFBlock_0/fc1/dot_general``), and the path roots
  are the same top-level parameter-tree groups
  ``obs/diagnostics._group_of`` / ``obs/costs.py`` key on.
- :func:`attribute` / :func:`summarize` — fold per-op time through the
  scope index into the cost model's component vocabulary
  (``patch_embed`` / ``attention_proj`` / ``attention_qkav`` / ``ffn`` /
  ``head`` / ``other``) and layer groups, so every trace renders as a
  *measured* ``flops/<comp>_frac``-shaped table next to the cost
  model's *predicted* one — with per-component deltas and a
  disagreement flag (:func:`compare`) when measured time attribution
  diverges from predicted FLOPs attribution beyond a pinned tolerance.
  Measured fractions are time, predicted are FLOPs; on a roofline-bound
  step they should agree, and a large delta is exactly the finding
  (e.g. the dense-softmax HBM tax made attention's time share double
  its FLOPs share — PERF.md §3).

Deliberately **stdlib-only** (no jax, no numpy): ``tools/trace_report.py``
and ``tools/run_report.py`` run this against rsynced logs on a laptop,
and the backend-unreachable post-mortem must never import jax. The
component marker tables are mirrored from ``obs/costs.py`` (which imports
jax transitively); ``tests/test_traceview.py`` pins the two vocabularies
equal.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
import re
from typing import Iterable, Optional

# Component vocabulary — MUST stay equal to obs/costs.py's COMP_* values
# (test_traceview.py pins this; costs imports jax transitively via
# diagnostics, so the names are mirrored rather than imported).
COMP_PATCH_EMBED = "patch_embed"
COMP_ATTN_PROJ = "attention_proj"
COMP_ATTN_QKAV = "attention_qkav"
COMP_FFN = "ffn"
COMP_HEAD = "head"
COMP_OTHER = "other"
COMPONENTS = (
    COMP_PATCH_EMBED, COMP_ATTN_PROJ, COMP_ATTN_QKAV, COMP_FFN, COMP_HEAD,
    COMP_OTHER,
)

# Scope-segment markers (lowercase substring match). The attention set
# splits into the projections (named qkv/out submodules — the parameter
# matmuls costs.py books as attention_proj) vs the parameter-free core
# (QK^T/AV einsums, softmax — attention_qkav); a segment naming an
# attention *module* without a projection submodule below it is core.
_ATTN_MODULE_MARKERS = (
    "attention", "attn", "selfattention", "talkingheads", "classattention",
)
_ATTN_PROJ_MARKERS = (
    "to_qkv", "to_out", "to_q", "to_kv", "to_v", "query", "key", "value",
    "proj_q", "proj_k", "proj_v", "out_proj",
)
_FFN_MARKERS = ("ffblock", "feedforward", "mlp", "fc1", "fc2", "moeff")
_PATCH_MARKERS = ("patchembed", "patch_embed", "stem", "conv_stem")
_HEAD_MARKERS = ("head",)

# Default measured-vs-predicted disagreement tolerance: absolute gap in
# attribution fraction. 0.15 = fifteen points of step share — big enough
# that FLOPs-vs-time skew on healthy steps (softmax/norms cost time but
# ~no FLOPs) stays quiet, small enough that a dense-softmax-sized tax
# (PERF.md §3 measured attention at ~70% time vs ~35% FLOPs) flags.
DISAGREEMENT_TOLERANCE = 0.15

# A transform wrapper segment in an HLO metadata op_name path:
# jit(main), jvp(ViT), transpose(jvp(ViT)), checkpoint(...), vmap(...).
_TRANSFORM_RE = re.compile(r"^[\w.\-]+\(.*\)$")

# One HLO instruction line with metadata: captures the instruction name
# (the trace's op name) and its op_name scope path.
_HLO_METADATA_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<instr>[\w.\-]+)\s*=\s*.*"
    r"metadata=\{[^}]*op_name=\"(?P<op_name>[^\"]+)\"",
)


# ------------------------------------------------------------------ loading


def find_traces(root: str) -> list[str]:
    """``*.trace.json.gz`` files under ``root`` (a profile dir, an
    autoprof capture dir, or a log dir), oldest → newest by mtime."""
    if os.path.isfile(root):
        return [root]
    pattern = os.path.join(root, "**", "*.trace.json.gz")
    return sorted(glob.glob(pattern, recursive=True), key=os.path.getmtime)


def load_trace(path: str) -> list[dict]:
    """The ``traceEvents`` list of one chrome-trace file (.json or
    .json.gz)."""
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rt") as f:
        doc = json.load(f)
    # Chrome's JSON Array Format is a bare list of events; the Object
    # Format wraps them in {"traceEvents": [...]}.
    events = doc if isinstance(doc, list) else doc.get("traceEvents", [])
    return [e for e in events if isinstance(e, dict)]


# ----------------------------------------------------------- device planes


def _process_names(events: Iterable[dict]) -> dict:
    names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            names[e.get("pid")] = (e.get("args") or {}).get("name", "")
    return names


def _thread_names(events: Iterable[dict]) -> dict:
    names = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "thread_name":
            names[(e.get("pid"), e.get("tid"))] = (
                (e.get("args") or {}).get("name", "")
            )
    return names


# Device-process threads that are NOT the per-op plane: the xprof
# chrome export puts "XLA Modules" (one event spanning the whole module
# execution) and "Steps" rows under the same device pid as the op rows
# — summing them would double/triple-count every op's time and pin
# idle_frac at 0 on real TPU traces.
def _is_aggregate_thread(name: str) -> bool:
    low = name.strip().lower()
    return "module" in low or low == "steps" or low.startswith("step ")


def device_events(events: list[dict]) -> tuple[list[dict], str]:
    """The device-plane complete events and which selector matched.

    TPU first: the ``"X"`` events on processes whose name contains
    ``"TPU"`` — restricted to the per-op rows: threads named
    ``XLA Ops...`` when present, otherwise everything except the
    aggregate ``XLA Modules``/``Steps`` rows (whose events span whole
    steps and would double-count every op under them). CPU fallback:
    the CPU backend emits no device process, but its XLA execution
    threads tag each op event with an ``hlo_op`` arg — select those, so
    tier-1 CPU captures parse to real totals instead of the empty dict
    the old ``"TPU" in process_name`` selector produced.
    Returns ``(events, "tpu" | "cpu-hlo-op" | "none")``.
    """
    names = _process_names(events)
    tpu_pids = {pid for pid, name in names.items() if "TPU" in name}
    if tpu_pids:
        threads = _thread_names(events)
        tpu_x = [
            e for e in events
            if e.get("ph") == "X" and e.get("pid") in tpu_pids
        ]
        op_tids = {
            key for key, name in threads.items()
            if key[0] in tpu_pids and "xla ops" in name.lower()
        }
        if op_tids:
            picked = [
                e for e in tpu_x
                if (e.get("pid"), e.get("tid")) in op_tids
            ]
        else:
            picked = [
                e for e in tpu_x
                if not _is_aggregate_thread(
                    threads.get((e.get("pid"), e.get("tid")), "")
                )
            ]
        if picked:
            return picked, "tpu"
    picked = [
        e for e in events
        if e.get("ph") == "X" and "hlo_op" in (e.get("args") or {})
    ]
    return picked, ("cpu-hlo-op" if picked else "none")


def _op_name(event: dict) -> str:
    args = event.get("args") or {}
    return args.get("hlo_op") or event.get("name", "")


def device_op_times(
    events: list[dict],
) -> tuple[dict[str, float], dict[str, int], str]:
    """Per-op total duration (ms) and event counts on the device planes.

    Keys are HLO op (instruction) names — ``hlo_op`` when tagged, the
    event name otherwise (TPU planes name events by instruction
    already). Returns ``(totals_ms, counts, selector)``.
    """
    picked, selector = device_events(events)
    totals: dict[str, float] = {}
    counts: dict[str, int] = {}
    for e in picked:
        name = _op_name(e)
        if not name:
            continue
        totals[name] = totals.get(name, 0.0) + float(e.get("dur", 0)) / 1e3
        counts[name] = counts.get(name, 0) + 1
    return totals, counts, selector


def span_and_busy_ms(events: list[dict]) -> tuple[float, float]:
    """(wall span, summed busy time) of the device planes in ms.

    Busy can exceed span when ops run on parallel device threads (the
    CPU backend's intra-op pool); idle accounting clamps at zero.
    """
    picked, _ = device_events(events)
    if not picked:
        return 0.0, 0.0
    start = min(float(e.get("ts", 0.0)) for e in picked)
    end = max(float(e.get("ts", 0.0)) + float(e.get("dur", 0.0))
              for e in picked)
    busy = sum(float(e.get("dur", 0.0)) for e in picked)
    return (end - start) / 1e3, busy / 1e3


# ------------------------------------------------------------------- steps

# Step markers, in preference order: a train-step pjit dispatch (named,
# so an eval pass or a bench probe in the same window cannot inflate the
# count), then module executions, then any pjit dispatch. Names nest
# (the dispatch TraceMe re-enters), so only top-level occurrences count.
_STEP_MARKER_RES = (
    re.compile(r"^PjitFunction\(.*train.*\)$"),
    re.compile(r"^jit_?_?.*train.*"),
    re.compile(r"^TfrtCpuExecutable::ExecuteHelper$"),
    re.compile(r"^PjitFunction\(.*\)$"),
)


def _top_level_count(events: list[dict]) -> int:
    """Occurrences of same-named events that are not nested inside a
    previous occurrence (the profiler emits one TraceMe per frame, so a
    re-entrant marker shows up twice at the same wall instant)."""
    spans = sorted(
        (float(e.get("ts", 0.0)), float(e.get("dur", 0.0))) for e in events
    )
    count = 0
    horizon = float("-inf")
    for ts, dur in spans:
        if ts >= horizon:
            count += 1
            horizon = ts + dur
    return count


def count_steps(events: list[dict]) -> Optional[int]:
    """Number of training steps the capture covers, from the step
    markers; None when nothing matched (caller may know the count from
    its own capture window — autoprof does)."""
    by_name: dict[str, list[dict]] = {}
    for e in events:
        if e.get("ph") == "X" and isinstance(e.get("name"), str):
            by_name.setdefault(e["name"], []).append(e)
    for marker in _STEP_MARKER_RES:
        candidates = [
            evs for name, evs in by_name.items() if marker.match(name)
        ]
        if candidates:
            # The most frequent matching name is the per-step one.
            best = max(candidates, key=len)
            n = _top_level_count(best)
            if n > 0:
                return n
    return None


# ------------------------------------------------------------ HLO op index


def parse_hlo_op_index(hlo_text: str) -> dict[str, str]:
    """``{instruction_name: metadata op_name scope}`` from post-
    optimization HLO text (``compiled.as_text()``).

    The trace's op names are instruction names (``dot.19``,
    ``multiply_reduce_fusion.16``); the metadata ``op_name`` is the
    jax name-stack path (``jit(step)/jvp(ViT)/Encoder_0/block_1/...``)
    that carries the flax module scopes. Fusions inherit their root
    instruction's metadata, which is exactly the right attribution.
    """
    index: dict[str, str] = {}
    for line in hlo_text.splitlines():
        if "metadata=" not in line or "op_name=" not in line:
            continue
        m = _HLO_METADATA_RE.match(line)
        if m:
            index.setdefault(m.group("instr"), m.group("op_name"))
    return index


def scope_segments(op_name: str) -> list[str]:
    """Module-path segments of a metadata op_name, transform wrappers
    (``jit(...)``, ``jvp(Model)``, ``transpose(jvp(Model))``) stripped."""
    return [
        seg for seg in op_name.split("/")
        if seg and not _TRANSFORM_RE.match(seg)
    ]


def is_backward(op_name: str) -> bool:
    """True when the op belongs to the backward pass (jax marks the
    transposed computation with a ``transpose(...)`` wrapper segment)."""
    return "transpose(" in op_name


def component_of_scope(op_name: str) -> str:
    """Map a metadata op_name scope onto the cost model's component
    vocabulary (the keys of ``StepCost.attribution``)."""
    segments = scope_segments(op_name)
    joined = "/".join(segments).lower()
    if not segments:
        return COMP_OTHER
    if any(m in joined for m in _PATCH_MARKERS):
        return COMP_PATCH_EMBED
    if any(m in joined for m in _ATTN_MODULE_MARKERS):
        if any(m in joined for m in _ATTN_PROJ_MARKERS):
            return COMP_ATTN_PROJ
        return COMP_ATTN_QKAV
    if any(m in joined for m in _FFN_MARKERS):
        return COMP_FFN
    if any(seg.lower().startswith(m) for seg in segments
           for m in _HEAD_MARKERS):
        return COMP_HEAD
    return COMP_OTHER


def group_of_scope(op_name: str) -> str:
    """Top-level module segment — the same layer-group key
    ``obs/diagnostics._group_of`` derives from the parameter tree
    (``Encoder_0``, ``PatchEmbedBlock_0``, ``head``, ...).

    A module scope always has at least two segments (module path + the
    primitive, e.g. ``Encoder_0/block_0/.../dot_general``); a
    single-segment scope is a bare top-level primitive — step glue, a
    donation copy — and belongs to ``other``, not to a fake group named
    after the primitive. The train step's own named scopes
    (``preprocess``, ``loss``, ``optimizer``, ``metrics``) are groups of
    their own, beside the modules'.
    """
    segments = scope_segments(op_name)
    return segments[0] if len(segments) >= 2 else COMP_OTHER


# ----------------------------------------------------------- op-name kinds

# HLO op-name buckets for traces WITHOUT a scope index (the offline case
# where only the trace file survived). Coarser than components — op names
# alone cannot tell attention from FFN — but they still rank softmax /
# transpose / dot time, which is how PERF.md's §3 profile was read.
OP_KINDS = (
    "softmax", "dot/conv", "transpose", "copy/layout", "collective",
    "fusion(other)", "other",
)


def op_kind(name: str) -> str:
    n = name.lower()
    if "softmax" in n:
        return "softmax"
    if "transpose" in n:
        return "transpose"
    if "dot" in n or "conv" in n or "einsum" in n:
        return "dot/conv"
    if "copy" in n or "bitcast" in n:
        return "copy/layout"
    if "all-reduce" in n or "all-gather" in n or "reduce-scatter" in n \
            or "collective" in n or "ppermute" in n or "all-to-all" in n:
        return "collective"
    if "fusion" in n:
        return "fusion(other)"
    return "other"


# ------------------------------------------------------------- attribution


def attribute(
    totals_ms: dict[str, float],
    op_index: Optional[dict[str, str]] = None,
) -> dict:
    """Fold per-op time into components / layer groups / op kinds.

    With an ``op_index`` (scope metadata), components and groups are
    exact; without one, every op lands in the kind buckets only and
    ``indexed_frac`` is 0. Ops the index does not know stay honest in
    ``unattributed_ms`` instead of silently padding ``other``.
    """
    components = {c: 0.0 for c in COMPONENTS}
    groups: dict[str, float] = {}
    kinds: dict[str, float] = {}
    fwd = bwd = 0.0
    unattributed = 0.0
    total = 0.0
    for name, ms in totals_ms.items():
        total += ms
        kinds[op_kind(name)] = kinds.get(op_kind(name), 0.0) + ms
        scope = (op_index or {}).get(name)
        if scope is None:
            unattributed += ms
            continue
        components[component_of_scope(scope)] += ms
        group = group_of_scope(scope)
        groups[group] = groups.get(group, 0.0) + ms
        if is_backward(scope):
            bwd += ms
        else:
            fwd += ms
    indexed = total - unattributed
    return {
        "total_ms": total,
        "indexed_ms": indexed,
        "unattributed_ms": unattributed,
        "indexed_frac": (indexed / total) if total else 0.0,
        "components_ms": components,
        "components_frac": {
            c: (v / indexed if indexed else 0.0)
            for c, v in components.items()
        },
        "groups_ms": dict(sorted(groups.items())),
        "groups_frac": {
            g: (v / indexed if indexed else 0.0)
            for g, v in sorted(groups.items())
        },
        "kinds_ms": dict(sorted(kinds.items(), key=lambda kv: -kv[1])),
        "fwd_ms": fwd,
        "bwd_ms": bwd,
    }


def attention_core_frac(attribution: dict) -> Optional[float]:
    """The measured attention-core share (``attention_qkav`` time over
    indexed time) — the number the regression sentinel gates on so a
    perf change is attributable to *where* time went. None when the
    trace had no scope index (an unindexed share is not a measurement).
    """
    if not attribution.get("indexed_ms"):
        return None
    return attribution["components_frac"].get(COMP_ATTN_QKAV, 0.0)


def compare(
    measured_frac: dict[str, float],
    predicted_frac: dict[str, float],
    *,
    tolerance: float = DISAGREEMENT_TOLERANCE,
) -> dict:
    """Measured (time) vs predicted (FLOPs) attribution, per component.

    Rows carry the delta; components whose absolute gap exceeds
    ``tolerance`` are flagged, and the summary-level ``disagrees`` bit
    is the falsifiability link ROADMAP items 1/3 hinge on: when the
    cost model's picture of a step stops matching the measured one,
    autotuning over that model is guessing again.
    """
    rows = []
    disagrees = []
    for comp in sorted(set(measured_frac) | set(predicted_frac)):
        measured = float(measured_frac.get(comp, 0.0))
        predicted = float(predicted_frac.get(comp, 0.0))
        delta = measured - predicted
        flagged = abs(delta) > tolerance
        if flagged:
            disagrees.append(comp)
        rows.append({
            "component": comp,
            "measured_frac": round(measured, 4),
            "predicted_frac": round(predicted, 4),
            "delta": round(delta, 4),
            "flagged": flagged,
        })
    return {
        "tolerance": tolerance,
        "rows": rows,
        "disagrees": disagrees,
    }


# --------------------------------------------------------- request planes


def request_spans(events: list[dict]) -> dict[int, dict]:
    """Per-request serve span timelines from a chrome trace.

    The serving telemetry layer (``sav_tpu/serve/telemetry.py``) exports
    its span ring as complete events tagged with a ``request`` arg (one
    row per request, one event per lifecycle interval). This reads them
    back — the request-timeline twin of :func:`device_op_times`, so
    ``tools/trace_report.py`` renders request traces with the machinery
    that reads device profiles. Returns ``{request_id: {"stages":
    [(name, start_ms, dur_ms)...], "total_ms", "dominant_stage",
    "bucket", "deadline_ms", "overrun_ms"}}`` (empty when the trace has
    no request plane).
    """
    out: dict[int, dict] = {}
    for e in events:
        args = e.get("args") or {}
        if e.get("ph") != "X" or "request" not in args:
            continue
        rid = args["request"]
        view = out.setdefault(rid, {
            "stages": [],
            "total_ms": 0.0,
            "bucket": args.get("bucket"),
            "deadline_ms": args.get("deadline_ms"),
            "overrun_ms": args.get("overrun_ms"),
        })
        dur_ms = float(e.get("dur", 0.0)) / 1e3
        view["stages"].append(
            (e.get("name", "?"), float(e.get("ts", 0.0)) / 1e3, dur_ms)
        )
        view["total_ms"] += dur_ms
    for view in out.values():
        view["stages"].sort(key=lambda s: s[1])
        view["total_ms"] = round(view["total_ms"], 3)
        view["dominant_stage"] = (
            max(view["stages"], key=lambda s: s[2])[0]
            if view["stages"] else None
        )
    return out


# ----------------------------------------------------------- fleet merge

#: The merged fleet-walk vocabulary (ISSUE 16) — one contiguous
#: router→replica→router chain per request. ``depad`` covers the
#: replica's whole post-device tail (depad + deliver); a request whose
#: replica export is missing degrades to the router-only chain
#: (``replica_wait`` stays opaque) — never dropped.
FLEET_STAGES = (
    "router_queue",    # router: admit -> route_selected
    "route",           # router: route_selected -> connect
    "transport_send",  # connect -> replica admission (clock-shifted)
    "replica_queue",   # replica: submit -> device dispatch
    "device",          # replica: the batch step itself
    "depad",           # replica: device done -> reply written
    "transport_reply", # replica done (shifted) -> router completed
)

#: Router-only degradation chain: the replica decomposition collapses
#: into the opaque ``replica_wait`` span the router measured itself.
FLEET_STAGES_ROUTER_ONLY = (
    "router_queue", "route", "transport_send", "replica_wait",
    "transport_reply",
)

FLEET_TRACE_SCHEMA = 1


def _span_bounds(events: list[dict]) -> dict:
    """Per-request interval bounds from one export's chrome events:
    ``{rid: {"at": {name: (start_us, end_us)}, "args": {...}}}`` (first
    occurrence of a name wins, matching ``intervals()``' first-stamp
    rule)."""
    out: dict = {}
    for e in events:
        args = e.get("args") or {}
        if e.get("ph") != "X" or "request" not in args:
            continue
        rid = args["request"]
        view = out.setdefault(rid, {"at": {}, "args": {}})
        name = e.get("name", "?")
        ts = float(e.get("ts", 0.0))
        if name not in view["at"]:
            view["at"][name] = (ts, ts + float(e.get("dur", 0.0)))
        for k, v in args.items():
            if k != "request" and v is not None:
                view["args"].setdefault(k, v)
    return out


def _replica_boundaries(at: dict) -> Optional[dict]:
    """The four replica instants the merge needs, from the replica's
    interval bounds (its own clock, µs): ``submit`` (admission start),
    ``dispatched`` / ``executed`` (device bounds), ``completed`` (end
    of the last present tail interval). None when the export lacks the
    device span — a torn record degrades to router-only."""
    if "admission" not in at or "device" not in at:
        return None
    completed = at["device"][1]
    for tail in ("depad", "deliver"):
        if tail in at:
            completed = max(completed, at[tail][1])
    return {
        "submit": at["admission"][0],
        "dispatched": at["device"][0],
        "executed": at["device"][1],
        "completed": completed,
    }


def _estimate_offset(pairs: list[tuple]) -> Optional[dict]:
    """Per-replica clock offset (replica clock + offset = router clock)
    from ``(sent, reply, r_submit, r_completed)`` handshake tuples (µs).

    Causality bounds each request: the replica admitted AFTER the router
    sent (``offset >= sent - r_submit``) and the router saw the reply
    AFTER the replica finished (``offset <= reply - r_completed``).
    Intersecting all requests' bounds gives an interval; its midpoint is
    the estimate and its half-width the HONEST skew bound stamped into
    the merged output. An empty intersection (stamp jitter beyond the
    physics) falls back to the median of per-request midpoints with the
    violation size as the bound.
    """
    lbs = [s - rs for s, _, rs, _ in pairs]
    ubs = [r - rc for _, r, _, rc in pairs]
    if not lbs:
        return None
    lb, ub = max(lbs), min(ubs)
    if lb <= ub:
        return {
            "offset_us": (lb + ub) / 2.0,
            "skew_us": (ub - lb) / 2.0,
            "pairs": len(pairs),
        }
    mids = sorted(
        ((s - rs) + (r - rc)) / 2.0 for s, r, rs, rc in pairs
    )
    return {
        "offset_us": mids[len(mids) // 2],
        "skew_us": (lb - ub) / 2.0,
        "pairs": len(pairs),
    }


def fleet_request_spans(log_dir: str) -> dict:
    """The offline fleet-trace joiner (ISSUE 16 tentpole, part 2).

    Reads the router's span-ring export
    (``serve_traces/requests_router.trace.json.gz``) plus every replica
    export (``serve_traces/requests_proc<i>.trace.json.gz``), estimates
    each replica's clock offset from the per-request handshake pairs
    (:func:`_estimate_offset` — bounded-skew midpoint), and merges each
    request into ONE contiguous router→replica→router chain in the
    :data:`FLEET_STAGES` vocabulary. Requests whose replica record is
    missing or torn keep the router-only chain
    (:data:`FLEET_STAGES_ROUTER_ONLY`, ``router_only=True``) — a
    request is NEVER dropped for a lost replica export.

    Returns ``{"schema", "router_export", "replicas": {proc:
    {"offset_ms", "skew_ms", "pairs"}}, "requests": {rid: {...}}}`` —
    empty ``requests`` when there is no router export. Stdlib-only like
    the rest of this module: runs against rsynced logs on a laptop.
    """
    out: dict = {
        "schema": FLEET_TRACE_SCHEMA,
        "router_export": None,
        "replicas": {},
        "requests": {},
    }
    router_path = os.path.join(
        log_dir, "serve_traces", "requests_router.trace.json.gz"
    )
    if not os.path.isfile(router_path):
        return out
    try:
        router = _span_bounds(load_trace(router_path))
    except (OSError, json.JSONDecodeError, EOFError):
        return out
    out["router_export"] = router_path
    # Replica exports: proc index from the filename; a torn file is a
    # degraded (router-only) merge for its requests, not a failure.
    replica: dict[int, dict] = {}
    for path in sorted(glob.glob(os.path.join(
        log_dir, "serve_traces", "requests_proc*.trace.json.gz"
    ))):
        m = re.search(r"requests_proc(\d+)\.trace\.json\.gz$",
                      os.path.basename(path))
        if not m:
            continue
        try:
            replica[int(m.group(1))] = _span_bounds(load_trace(path))
        except (OSError, json.JSONDecodeError, EOFError):
            continue
    # Clock offsets: pair each completed router record with its final
    # replica's record (args["rank"] names the replica that replied).
    offsets: dict[int, Optional[dict]] = {}
    for proc, bounds in sorted(replica.items()):
        pairs = []
        for rid, rview in router.items():
            if rview["args"].get("rank") != proc:
                continue
            if rview["args"].get("outcome") not in (None, "completed"):
                continue
            at = rview["at"]
            if "replica_wait" not in at:
                continue
            rep = bounds.get(rid)
            rb = _replica_boundaries(rep["at"]) if rep else None
            if rb is None:
                continue
            sent, reply = at["replica_wait"]
            pairs.append((sent, reply, rb["submit"], rb["completed"]))
        est = _estimate_offset(pairs)
        offsets[proc] = est
        if est is not None:
            out["replicas"][proc] = {
                "offset_ms": round(est["offset_us"] / 1e3, 3),
                "skew_ms": round(est["skew_us"] / 1e3, 3),
                "pairs": est["pairs"],
            }
    # Merge each router record.
    for rid, rview in sorted(router.items(), key=lambda kv: str(kv[0])):
        at = rview["at"]
        args = rview["args"]
        rank = args.get("rank")
        if "router_queue" not in at or "replica_wait" not in at:
            # Shed/failed before the exchange: no cross-process walk to
            # merge, but NEVER drop the request — keep whatever router
            # spans exist (admission, maybe router_queue/route).
            stages = sorted(
                ((name, round(b[0] / 1e3, 3),
                  round((b[1] - b[0]) / 1e3, 3))
                 for name, b in at.items()),
                key=lambda s: s[1],
            )
            out["requests"][rid] = {
                "rank": rank,
                "outcome": args.get("outcome"),
                "deadline_ms": args.get("deadline_ms"),
                "overrun_ms": args.get("overrun_ms"),
                "router_only": True,
                "skew_ms": None,
                "stages": stages,
                "total_ms": round(
                    (max(b[1] for b in at.values())
                     - min(b[0] for b in at.values())) / 1e3, 3
                ) if at else 0.0,
                "dominant_stage": (
                    max(stages, key=lambda s: s[2])[0] if stages else None
                ),
            }
            continue
        admit = at["router_queue"][0]
        selected = at["router_queue"][1]
        connect = at["route"][1] if "route" in at else selected
        sent, reply = at["replica_wait"]
        completed = (
            at["deliver"][1] if "deliver" in at else reply
        )
        est = offsets.get(rank) if rank is not None else None
        rep = replica.get(rank, {}).get(rid) if rank is not None else None
        rb = _replica_boundaries(rep["at"]) if rep else None
        entry = {
            "rank": rank,
            "outcome": args.get("outcome"),
            "deadline_ms": args.get("deadline_ms"),
            "overrun_ms": args.get("overrun_ms"),
            "router_only": rb is None or est is None,
            "skew_ms": (
                round(est["skew_us"] / 1e3, 3) if est is not None else None
            ),
        }
        if rb is None or est is None:
            cuts = [admit, selected, connect, sent, reply, completed]
            names = FLEET_STAGES_ROUTER_ONLY
        else:
            off = est["offset_us"]
            cuts = [admit, selected, connect,
                    rb["submit"] + off, rb["dispatched"] + off,
                    rb["executed"] + off, rb["completed"] + off,
                    completed]
            names = FLEET_STAGES
        # Contiguity by construction: clamp each boundary to the one
        # before it (a ±skew shift may nudge a replica instant past its
        # neighbour; the chain must stay monotone).
        for i in range(1, len(cuts)):
            cuts[i] = max(cuts[i], cuts[i - 1])
        stages = [
            (name, round(cuts[i] / 1e3, 3),
             round((cuts[i + 1] - cuts[i]) / 1e3, 3))
            for i, name in enumerate(names)
        ]
        entry["stages"] = stages
        entry["total_ms"] = round((cuts[-1] - cuts[0]) / 1e3, 3)
        entry["dominant_stage"] = (
            max(stages, key=lambda s: s[2])[0] if stages else None
        )
        out["requests"][rid] = entry
    return out


def write_fleet_trace(log_dir: str) -> Optional[str]:
    """Persist the merged fleet walk as ONE chrome trace —
    ``serve_traces/fleet.trace.json.gz`` — readable by every existing
    trace consumer (``trace_report``, :func:`request_spans`). Returns
    the path, or None when there was nothing to merge (telemetry
    discipline: never raises)."""
    merged = fleet_request_spans(log_dir)
    if not merged["requests"]:
        return None
    events = [{
        "ph": "M", "pid": 1, "name": "process_name",
        "args": {"name": "Fleet Requests"},
    }]
    for rid, entry in merged["requests"].items():
        for name, start_ms, dur_ms in entry["stages"]:
            events.append({
                "ph": "X", "pid": 1, "tid": rid, "name": name,
                "ts": round(start_ms * 1e3, 1),
                "dur": round(dur_ms * 1e3, 1),
                "args": {
                    "request": rid,
                    "rank": entry["rank"],
                    "outcome": entry["outcome"],
                    "router_only": entry["router_only"],
                    "skew_ms": entry["skew_ms"],
                    "deadline_ms": entry["deadline_ms"],
                    "overrun_ms": entry["overrun_ms"],
                },
            })
    path = os.path.join(log_dir, "serve_traces", "fleet.trace.json.gz")
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        tmp = f"{path}.tmp.{os.getpid()}"
        with gzip.open(tmp, "wt") as f:
            json.dump({"traceEvents": events}, f)
        os.replace(tmp, path)
        return path
    except OSError:
        return None


def write_fleet_exemplars(
    log_dir: str, *, max_exemplars: int = 8
) -> list[str]:
    """Dump the slowest merged requests as fleet exemplars —
    ``serve_traces/slow_fleet_<seq>_req<rid>.json`` with the full
    cross-process walk — under the PR-11 budget discipline (a bounded
    count, slowest first; ``telemetry.find_exemplars``' ``slow_*.json``
    glob picks them up next to the replica-local ones)."""
    merged = fleet_request_spans(log_dir)
    ranked = sorted(
        merged["requests"].items(),
        key=lambda kv: kv[1]["total_ms"], reverse=True,
    )[:max(int(max_exemplars), 0)]
    written = []
    for seq, (rid, entry) in enumerate(ranked):
        doc = {
            "fleet": True,
            "rid": rid,
            "latency_ms": entry["total_ms"],
            "deadline_ms": entry["deadline_ms"],
            "overrun_ms": entry["overrun_ms"],
            "rank": entry["rank"],
            "outcome": entry["outcome"],
            "router_only": entry["router_only"],
            "skew_ms": entry["skew_ms"],
            "dominant_stage": entry["dominant_stage"],
            "stages_ms": {
                name: dur for name, _, dur in entry["stages"]
            },
            "walk": [list(s) for s in entry["stages"]],
        }
        safe_rid = re.sub(r"[^\w.\-]", "_", str(rid))
        path = os.path.join(
            log_dir, "serve_traces",
            f"slow_fleet_{seq:04d}_req{safe_rid}.json",
        )
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "w") as f:
                json.dump(doc, f, indent=2, default=str)
            os.replace(tmp, path)
            written.append(path)
        except OSError:
            continue
    return written


# --------------------------------------------------------------- summaries

TRACEVIEW_SCHEMA = 1


def summarize(
    trace_path: str,
    *,
    op_index: Optional[dict[str, str]] = None,
    predicted: Optional[dict[str, float]] = None,
    steps: Optional[int] = None,
    tolerance: float = DISAGREEMENT_TOLERANCE,
    top_ops: int = 10,
    events: Optional[list[dict]] = None,
) -> dict:
    """One trace file → the machine-readable summary every consumer
    renders (autoprof sidecars, ``tools/trace_report.py``,
    ``run_report.py --trace``, bench's JSON line). Pass ``events`` when
    the trace is already loaded (a real capture gunzips+parses tens of
    MB — callers that also need the raw events must not pay it twice).
    """
    if events is None:
        events = load_trace(trace_path)
    totals, counts, selector = device_op_times(events)
    span_ms, busy_ms = span_and_busy_ms(events)
    n_steps = steps if steps is not None else count_steps(events)
    attribution = attribute(totals, op_index)
    summary = {
        "schema": TRACEVIEW_SCHEMA,
        "trace": trace_path,
        "device_selector": selector,
        "num_ops": len(totals),
        "steps": n_steps,
        "span_ms": round(span_ms, 3),
        "busy_ms": round(busy_ms, 3),
        # Device-plane gap share of the captured span: host stalls,
        # input waits, dispatch bubbles. Parallel device threads can
        # push busy past span (CPU's intra-op pool) — clamp, don't lie.
        "idle_frac": round(max(0.0, 1.0 - busy_ms / span_ms), 4)
        if span_ms > 0 else None,
        "total_ms": round(attribution["total_ms"], 3),
        "per_step_ms": round(attribution["total_ms"] / n_steps, 3)
        if n_steps else None,
        "indexed_frac": round(attribution["indexed_frac"], 4),
        "components_frac": {
            k: round(v, 4)
            for k, v in attribution["components_frac"].items()
        },
        "groups_frac": {
            k: round(v, 4) for k, v in attribution["groups_frac"].items()
        },
        "kinds_ms": {
            k: round(v, 3) for k, v in attribution["kinds_ms"].items()
        },
        "fwd_ms": round(attribution["fwd_ms"], 3),
        "bwd_ms": round(attribution["bwd_ms"], 3),
        "attention_core_frac": (
            round(attention_core_frac(attribution), 6)
            if attention_core_frac(attribution) is not None else None
        ),
        "top_ops": [
            {
                "op": name,
                "ms": round(ms, 3),
                "count": counts.get(name, 0),
                "kind": op_kind(name),
                **(
                    {"scope": op_index[name]}
                    if op_index and name in op_index else {}
                ),
            }
            for name, ms in sorted(
                totals.items(), key=lambda kv: -kv[1]
            )[:top_ops]
        ],
    }
    if predicted is not None and attribution["indexed_ms"]:
        summary["vs_predicted"] = compare(
            attribution["components_frac"], predicted, tolerance=tolerance
        )
    return summary


def save_op_index(path: str, op_index: dict[str, str]) -> Optional[str]:
    """Persist an op index next to a capture (``op_index.json``) so the
    offline tools can attribute without the live executable. Telemetry:
    returns None instead of raising on I/O failure."""
    try:
        tmp = f"{path}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(op_index, f)
        os.replace(tmp, path)
        return path
    except OSError:
        return None


def load_op_index(root: str) -> Optional[dict[str, str]]:
    """Find and load an ``op_index.json`` for a trace: next to the trace
    file, in the capture dir, or any parent up to (and including) the
    log dir's ``autoprof/``. None when absent or unreadable."""
    if os.path.isfile(root):
        root = os.path.dirname(root)
    probe = root
    for _ in range(6):
        candidate = os.path.join(probe, "op_index.json")
        if os.path.exists(candidate):
            try:
                with open(candidate) as f:
                    doc = json.load(f)
                if isinstance(doc, dict):
                    return {str(k): str(v) for k, v in doc.items()}
            except (OSError, json.JSONDecodeError):
                return None
        parent = os.path.dirname(probe)
        if parent == probe:
            break
        probe = parent
    return None
