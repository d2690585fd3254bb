#!/usr/bin/env python
"""Mesh/layout autotuner: enumerate mesh shapes × layouts × microbatch
sizes for the device count at hand, rank every candidate with the cost
model, *measure* the top-K on the live backend, and emit the winner as a
layout preset (``sav_tpu/parallel/layout.py`` JSON) that
``train.py --layout-preset`` and ``ServeConfig.layout_preset`` consume.

Three stages, each recorded in the report so the decision is auditable:

1. **Enumerate + rank.** Candidates are SpecLayouts over the axis
   factorizations of the device count (pure DP, 1D TP over ``model``,
   2D TP over ``x,y``, FSDP — ``--arms`` picks the subset) crossed with
   the ``--grad-accum`` ladder (microbatch = global batch / accum).
   Feasibility is checked against the REAL param tree: every dim a
   layout's spec shards must divide its axis size product, the
   microbatch must divide the batch-axis product. Infeasible candidates
   are recorded with the reason, never silently dropped, and never fatal.
   Ranking is predicted step time = analytic compute
   (``sav_tpu.obs.costs.analytic_train_step_cost`` over the peak-FLOPs
   table) + a per-arm collective-traffic estimate over an ICI bandwidth
   figure. The estimate is a RANKING heuristic — the per-term breakdown
   lands in the report, and the measured pass is the authority.

2. **Measure top-K** with the Trap-1/2/3 methodology of
   ``tools/attn_tune.py`` / docs/benchmarking.md, adapted to a full
   train step: the timed program is a jitted ``lax.scan`` whose carry is
   the *parameter tree itself* — each iteration takes grads and applies
   an SGD update, so the primal rides the carry (Trap 1: nothing can
   hoist out of the scan) and the backward matmuls feed the update that
   feeds the next iteration (Trap 2: the algebraic simplifier cannot
   collapse them). Candidates compile up front (a compile failure is
   recorded infeasible with the error, and the sweep continues), timing
   windows interleave round-robin with a rotated start order, and
   per-candidate minima are reported (Trap 3 — windows on a shared host
   vary).

3. **Cross-check** (``--trace``): the winner's loop is captured under
   ``jax.profiler.trace``, machine-read through ``sav_tpu/obs/traceview``
   with the op index parsed from the loop's own HLO metadata, and the
   measured per-component time attribution is compared against the cost
   model's predicted FLOPs attribution (``traceview.compare``).
   Disagreements are FLAGGED in the report and stamped into the preset's
   provenance — never silently trusted: when the cost model's picture of
   a step stops matching the measured one, ranking over it is guessing
   again (docs/perf_accounting.md).

The measured step is a self-contained fwd+bwd+SGD over the real model
(``is_training=False`` apply: no dropout streams, BatchNorm families read
their init stats) rather than the full ``Trainer`` step — optimizer
element-wise ops are layout-invariant, and the matmuls + collectives the
layout decision hinges on are identical. The emitted preset then rides
the REAL trainer end-to-end (an on-chip round that has not been run yet)
before the sentinel ever sees it.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
from typing import Any, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _REPO_ROOT)

MESH_TUNE_SCHEMA = 1

# ICI bandwidth figure for the collective-traffic term (bytes/s per
# chip, all links). A ranking constant, not a measurement: ~9e10 is the
# v4/v5p neighborhood; override with --ici-gbps when the fabric is
# known. CPU runs get a deterministic fake (labeled, like the cpu-fake
# peak in obs/costs.py) so the ranking pipeline is assertable in tier-1.
DEFAULT_ICI_BYTES_PER_S = 9.0e10
CPU_FAKE_ICI_BYTES_PER_S = 1.0e10

ARMS = ("dp", "tp", "2d", "fsdp")


# ------------------------------------------------------------- enumeration


def _divisors(n: int) -> list[int]:
    return [d for d in range(1, n + 1) if n % d == 0]


def enumerate_layouts(n_devices: int, arms: list[str]) -> list:
    """Candidate SpecLayouts over the axis factorizations of
    ``n_devices``. Every axis is sized explicitly (no -1): a candidate
    states exactly the mesh it is measured on."""
    from sav_tpu.parallel.layout import layout_from_mesh_axes

    out = []

    def add(axes: dict, name: str):
        layout = layout_from_mesh_axes(axes, name=name)
        out.append(dataclasses.replace(layout, source="mesh-tune"))

    if "dp" in arms:
        add({"data": n_devices}, "dp")
    if "tp" in arms:
        for t in _divisors(n_devices):
            if t > 1:
                add({"data": n_devices // t, "model": t}, f"tp{t}")
    if "2d" in arms:
        for x in _divisors(n_devices):
            if x <= 1:
                continue
            for y in _divisors(n_devices // x):
                if y > 1:
                    add(
                        {"data": n_devices // (x * y), "x": x, "y": y},
                        f"2d{x}x{y}",
                    )
    if "fsdp" in arms:
        for f in _divisors(n_devices):
            if f > 1:
                add({"data": n_devices // f, "fsdp": f}, f"fsdp{f}")
    return out


def check_feasible(
    layout, abstract_params, *, global_batch: int, grad_accum: int
) -> Optional[str]:
    """Reason the candidate cannot run, or None.

    Divisibility is checked against the REAL param tree: every dim a
    spec entry shards must divide the product of its axis sizes (the
    partitioner would otherwise pad or reject), and the microbatch must
    divide the batch-axis product. FSDP augmentation is exempt — its
    divisibility-aware rule falls back per-leaf by construction.
    """
    import numpy as np

    import jax

    from jax.sharding import PartitionSpec as P

    sizes = layout.axis_dict()
    if global_batch % grad_accum:
        return f"global batch {global_batch} not divisible by accum {grad_accum}"
    micro = global_batch // grad_accum
    group = int(np.prod([sizes[a] for a in layout.batch_axes()] or [1]))
    if micro % group:
        return f"microbatch {micro} not divisible by batch-axis product {group}"

    def axes_size(entry) -> int:
        names = entry if isinstance(entry, tuple) else (entry,)
        return int(np.prod([sizes[a] for a in names]))

    specs = layout.param_specs(abstract_params)
    flat_p = jax.tree_util.tree_flatten_with_path(abstract_params)[0]
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(flat_p, flat_s):
        for i, entry in enumerate(spec):
            if entry is None:
                continue
            size = axes_size(entry)
            if leaf.shape[i] % size:
                name = "/".join(str(getattr(k, "key", k)) for k in path)
                return (
                    f"param {name} dim {i} ({leaf.shape[i]}) not divisible "
                    f"by {entry!r}={size}"
                )
    if layout.tp_feature_axis:
        # Activations [B, L, D] carry D over the feature axis.
        embed = _embed_dim(abstract_params)
        y = sizes[layout.tp_feature_axis]
        if embed and embed % y:
            return f"embed dim {embed} not divisible by feature axis {y}"
    return None


def _embed_dim(abstract_params) -> Optional[int]:
    """Model feature dim from the param tree (first qkv/fc1 kernel's
    leading dim) — the activation-spec divisibility check's D."""
    import jax

    for path, leaf in jax.tree_util.tree_flatten_with_path(abstract_params)[0]:
        joined = "/".join(str(getattr(k, "key", k)) for k in path)
        if joined.endswith(("to_qkv/kernel", "to_q/kernel", "fc1/kernel")):
            return int(leaf.shape[0])
    return None


# ---------------------------------------------------------------- ranking


def resolve_ici_bytes_per_s(override: Optional[float] = None) -> tuple[float, str]:
    if override:
        return float(override), "override"
    import jax

    if jax.devices()[0].platform == "cpu":
        return CPU_FAKE_ICI_BYTES_PER_S, "cpu-fake"
    return DEFAULT_ICI_BYTES_PER_S, "default-estimate"


def predict_step_time(
    layout,
    cost,
    abstract_params,
    *,
    global_batch: int,
    grad_accum: int,
    num_layers: int,
    peak_flops: Optional[float],
    ici_bytes_per_s: float,
    dot_dtype: Optional[str] = None,
) -> dict:
    """Predicted optimizer-step seconds = compute + collective traffic.

    Compute is the analytic cost model's per-device FLOPs over the peak.
    The collective terms (2·(n−1)/n ring AllReduce per TP block output
    and its backward mirror, all-gather/reduce-scatter pairs on the 2D
    feature axis, per-microbatch FSDP param gathers + one grad
    reduce-scatter, one DP gradient AllReduce per optimizer step) are
    first-order traffic/bandwidth estimates — a RANKING signal whose
    breakdown is recorded so a wrong rank is attributable, not a
    roofline claim. The measured pass is the authority.
    """
    import numpy as np

    import jax

    sizes = layout.axis_dict()
    micro = global_batch // grad_accum
    param_bytes = 0.0
    for leaf in jax.tree.leaves(abstract_params):
        param_bytes += float(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize

    from sav_tpu.obs.costs import dot_dtype_bytes

    embed = _embed_dim(abstract_params) or 0
    tokens = cost.num_tokens
    # Activations ring at the dot dtype's width (obs/costs.py dtype
    # axis, ISSUE 17): 2 B/elt for the bf16 default, 1 under --dot-dtype
    # int8 — the int8 arm halves the TP collective volume along with
    # doubling the peak, which is exactly why it re-ranks layouts.
    act_bytes = micro * tokens * embed * float(dot_dtype_bytes(dot_dtype, 2))

    def ring(n: int) -> float:
        return 2.0 * (n - 1) / n if n > 1 else 0.0

    terms: dict[str, float] = {}
    d = sizes.get(layout.data_axis, 1)
    if d > 1:
        # One gradient AllReduce per optimizer step (accum sums locally).
        terms["dp_grad_allreduce"] = ring(d) * param_bytes / ici_bytes_per_s
    if layout.fsdp_axis:
        f = sizes[layout.fsdp_axis]
        # Param all-gathers every microbatch (fwd + bwd), grads
        # reduce-scattered once per optimizer step.
        terms["fsdp_param_allgather"] = (
            grad_accum * 2.0 * ring(f) / 2.0 * param_bytes / ici_bytes_per_s
        )
        terms["fsdp_grad_reduce_scatter"] = (
            ring(f) / 2.0 * param_bytes / ici_bytes_per_s
        )
    if layout.tp_heads_axis:
        t = sizes[layout.tp_heads_axis]
        # Two block-output AllReduces per layer (attn out, MLP out),
        # mirrored in the backward: 4 × per-microbatch activation rings.
        terms["tp_block_allreduce"] = (
            grad_accum * num_layers * 4.0 * ring(t) * act_bytes / ici_bytes_per_s
        )
    if layout.tp_feature_axis:
        y = sizes[layout.tp_feature_axis]
        # All-gather/reduce-scatter pairs as activations enter/leave each
        # projection on the 2D feature axis (half the ring volume each).
        terms["tp2d_feature_gather_scatter"] = (
            grad_accum * num_layers * 4.0 * ring(y) / 2.0 * act_bytes
            / ici_bytes_per_s
        )

    # cost.flops is the per-device share of the FULL global batch —
    # accumulation splits it across microbatch steps without changing
    # the optimizer-step total.
    compute_s = cost.flops / peak_flops if peak_flops else float("inf")
    comm_s = sum(terms.values())
    return {
        "compute_s": compute_s,
        "comm_s": comm_s,
        "total_s": compute_s + comm_s,
        "comm_terms": {k: round(v, 6) for k, v in sorted(terms.items())},
    }


# ------------------------------------------------------------ measurement


def build_step_loop(model, params, aux_vars, batch, *, iters: int):
    """The Trap-pinned timing program: jitted scan threading the PARAM
    TREE through the carry — grads feed an SGD update that feeds the next
    iteration, so the primal rides the carry (Trap 1) and every backward
    matmul is carry-reachable (Trap 2). Returns (run, lowered): ``run()``
    executes one compiled window and blocks; ``lowered`` carries the HLO
    for the op index + XLA cost analysis."""
    import jax
    import jax.numpy as jnp

    images, labels = batch["images"], batch["labels"]

    def loss_fn(p, images, labels):
        out = model.apply({"params": p, **aux_vars}, images, is_training=False)
        logits = out[0] if isinstance(out, tuple) else out
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
        return -jnp.mean(jnp.sum(onehot * logp, axis=-1))

    def body(p, _):
        loss, grads = jax.value_and_grad(loss_fn)(p, images, labels)
        new_p = jax.tree.map(
            lambda a, g: a - jnp.asarray(1e-4, a.dtype) * g.astype(a.dtype),
            p,
            grads,
        )
        return new_p, loss

    def loop(p):
        final, losses = jax.lax.scan(body, p, None, length=iters)
        return losses[-1]

    lowered = jax.jit(loop).lower(params)
    compiled = lowered.compile()
    jax.device_get(compiled(params))  # warm (and surface backend errors)
    return (lambda: jax.device_get(compiled(params))), lowered, compiled


def _init_variables(model, image_size: int):
    """Jit-materialized model variables (one fresh compile per candidate
    by design — every candidate is a different model/mesh pairing)."""
    import jax

    return jax.jit(
        lambda: model.init(
            {"params": jax.random.PRNGKey(0)},
            jax.numpy.zeros((1, image_size, image_size, 3)),
            is_training=False,
        )
    )()


def _make_batch(blayout, *, micro: int, image_size: int, num_classes: int):
    import numpy as np

    import jax

    rng = np.random.default_rng(0)
    images = rng.standard_normal(
        (micro, image_size, image_size, 3), dtype=np.float32
    )
    labels = rng.integers(0, num_classes, size=(micro,), dtype=np.int64)
    sh = blayout.batch_sharding()
    return {
        "images": jax.device_put(images, sh),
        "labels": jax.device_put(labels.astype(np.int32), sh),
    }


def measure_candidates(
    candidates: list[dict],
    *,
    model_name: str,
    num_classes: int,
    image_size: int,
    model_overrides: dict,
    global_batch: int,
    iters: int,
    rounds: int,
    devices,
    log=print,
) -> None:
    """Compile + time each top-K candidate in place (adds
    ``measured_ms_per_step`` or flips to infeasible with the compile
    error). Round-robin interleave with rotated start; per-candidate
    minima (Trap 3)."""
    import jax

    from sav_tpu.models import create_model
    from sav_tpu.parallel.layout import BoundLayout

    runs = []
    for cand in candidates:
        layout = cand["_layout"]
        micro = global_batch // cand["grad_accum"]
        try:
            mesh = layout.create_mesh(devices=devices)
            blayout = BoundLayout(layout, mesh)
            model = create_model(
                model_name,
                num_classes=num_classes,
                layout=(blayout if layout.tp_feature_axis else None),
                **model_overrides,
            )
            variables = _init_variables(model, image_size)
            params = variables.pop("params")
            params = jax.tree.map(
                jax.device_put, params, blayout.param_shardings(params)
            )
            aux_vars = jax.device_get(variables)  # batch_stats etc. (tiny)
            batch = _make_batch(
                blayout, micro=micro, image_size=image_size,
                num_classes=num_classes,
            )
            run, lowered, compiled = build_step_loop(
                model, params, aux_vars, batch, iters=iters
            )
        except Exception as e:  # noqa: BLE001 — a bad candidate must not kill the sweep
            cand["feasible"] = False
            cand["reason"] = f"compile/build: {type(e).__name__}: {e}"[:300]
            log(f"  {cand['name']:14s} INFEASIBLE ({type(e).__name__})")
            continue
        cand["_run"] = run
        cand["_lowered"] = lowered
        cand["_compiled"] = compiled
        runs.append(cand)

    best = {id(c): float("inf") for c in runs}
    for r in range(rounds if runs else 0):
        rotated = runs[r % len(runs):] + runs[: r % len(runs)]
        for cand in rotated:
            t0 = time.perf_counter()
            cand["_run"]()
            ms = (time.perf_counter() - t0) / iters * 1e3
            best[id(cand)] = min(best[id(cand)], ms)
    for cand in runs:
        cand["measured_ms_per_step"] = round(best[id(cand)], 3)
        # The comparable number: an optimizer step is grad_accum
        # microbatch steps (candidates at different accums must not be
        # compared per-microbatch).
        cand["measured_ms_per_opt_step"] = round(
            best[id(cand)] * cand["grad_accum"], 3
        )
        log(
            f"  {cand['name']:14s} accum={cand['grad_accum']} "
            f"{cand['measured_ms_per_step']:10.3f} ms/microbatch step "
            f"({cand['measured_ms_per_opt_step']:.3f} ms/opt step)"
        )


def trace_cross_check(winner: dict, cost, trace_dir: str, *, log=print) -> dict:
    """Capture the winner's timed loop under ``jax.profiler.trace`` and
    compare measured time attribution vs the cost model's predicted
    FLOPs attribution. Best-effort by design — a backend without device
    planes reports ``available: False`` rather than failing the sweep —
    but a disagreement is always flagged, never swallowed."""
    import jax

    from sav_tpu.obs import traceview

    try:
        with jax.profiler.trace(trace_dir):
            winner["_run"]()
        traces = traceview.find_traces(trace_dir)
        if not traces:
            return {"available": False, "reason": "no trace captured"}
        # Instruction names must match the EXECUTED program's: index the
        # optimized (compiled) HLO, falling back to the lowered text on
        # backends whose compiled.as_text() is unavailable.
        try:
            hlo_text = winner["_compiled"].as_text()
        except Exception:  # noqa: BLE001
            hlo_text = winner["_lowered"].as_text()
        op_index = traceview.parse_hlo_op_index(hlo_text)
        traceview.save_op_index(
            os.path.join(os.path.dirname(traces[-1]), "op_index.json"),
            op_index,
        )
        summary = traceview.summarize(
            traces[-1], op_index=op_index, predicted=cost.attribution
        )
    except Exception as e:  # noqa: BLE001 — cross-check must not kill the sweep
        return {"available": False, "reason": f"{type(e).__name__}: {e}"[:300]}
    if not summary.get("num_ops"):
        return {"available": False, "reason": "no device ops in trace"}
    vs = summary.get("vs_predicted")
    if not vs:
        # summarize only compares when some op time is INDEXED through
        # the HLO metadata — an unindexed capture is "no measurement",
        # never a clean bill of health.
        return {
            "available": False,
            "reason": "no indexed device ops (op index did not match the "
            "capture) — measured-vs-predicted not comparable",
            "trace": traces[-1],
            "indexed_frac": summary.get("indexed_frac"),
        }
    disagrees = vs.get("disagrees") or []
    for comp in disagrees:
        log(
            f"  DISAGREEMENT: measured time share of {comp!r} diverges "
            "from predicted FLOPs share beyond tolerance — the ranking "
            "over this model is suspect (see report.trace_check)"
        )
    return {
        "available": True,
        "trace": traces[-1],
        "indexed_frac": summary.get("indexed_frac"),
        "vs_predicted": vs,
        "disagrees": disagrees,
        "measured_components_frac": summary.get("components_frac"),
    }


# ------------------------------------------------------------------- main


def run(args, log=print) -> dict:
    import jax

    from sav_tpu.models import create_model
    from sav_tpu.obs.costs import analytic_train_step_cost, resolve_peak_flops
    from sav_tpu.parallel.layout import save_layout_preset

    n_devices = args.devices or len(jax.devices())
    devices = jax.devices()[:n_devices]
    if len(devices) < n_devices:
        raise SystemExit(
            f"mesh_tune: need {n_devices} devices, have {len(jax.devices())}"
        )
    overrides = json.loads(args.model_overrides) if args.model_overrides else {}
    model = create_model(args.model, num_classes=args.num_classes, **overrides)
    abstract = jax.eval_shape(
        lambda x: model.init(
            {"params": jax.random.PRNGKey(0)}, x, is_training=False
        ),
        jax.ShapeDtypeStruct(
            (1, args.image_size, args.image_size, 3), jax.numpy.float32
        ),
    )["params"]
    num_layers = int(
        overrides.get("num_layers")
        or getattr(model, "num_layers", None)
        or 12
    )
    peak_flops, peak_source = resolve_peak_flops(
        args.peak_flops, devices, dot_dtype=args.dot_dtype
    )
    ici, ici_source = resolve_ici_bytes_per_s(args.ici_gbps and args.ici_gbps * 1e9)
    arms = [a.strip() for a in args.arms.split(",") if a.strip()]
    bad = set(arms) - set(ARMS)
    if bad:
        raise SystemExit(f"mesh_tune: unknown arms {sorted(bad)} (have {ARMS})")
    accums = [int(x) for x in args.grad_accum.split(",")]

    # The analytic cost is layout-independent (total work is fixed; the
    # per-device share divides by the device count either way) — computed
    # once, attached to every candidate for the trace cross-check.
    cost = analytic_train_step_cost(
        abstract,
        batch_size=args.global_batch,
        image_size=args.image_size,
        n_devices=n_devices,
    )
    candidates: list[dict] = []
    for layout in enumerate_layouts(n_devices, arms):
        for accum in accums:
            cand = {
                "name": layout.name,
                "mesh_axes": layout.axis_dict(),
                "grad_accum": accum,
                "_layout": layout,
                "_cost": cost,
            }
            reason = check_feasible(
                layout, abstract, global_batch=args.global_batch,
                grad_accum=accum,
            )
            if reason is not None:
                cand.update(feasible=False, reason=reason)
                candidates.append(cand)
                continue
            cand.update(
                feasible=True,
                predicted=predict_step_time(
                    layout, cost, abstract,
                    global_batch=args.global_batch, grad_accum=accum,
                    num_layers=num_layers, peak_flops=peak_flops,
                    ici_bytes_per_s=ici,
                    dot_dtype=args.dot_dtype,
                ),
            )
            candidates.append(cand)

    feasible = [c for c in candidates if c["feasible"]]
    feasible.sort(key=lambda c: c["predicted"]["total_s"])
    log(
        f"mesh_tune: {len(candidates)} candidates over {n_devices} devices "
        f"({len(feasible)} feasible), measuring top {args.top_k}"
    )
    for c in candidates:
        if c["feasible"]:
            p = c["predicted"]
            log(
                f"  {c['name']:14s} accum={c['grad_accum']} predicted "
                f"{p['total_s'] * 1e3:9.3f} ms/opt-step "
                f"(compute {p['compute_s'] * 1e3:.3f} + comm "
                f"{p['comm_s'] * 1e3:.3f})"
            )
        else:
            log(f"  {c['name']:14s} accum={c['grad_accum']} INFEASIBLE: "
                f"{c['reason']}")

    top = feasible[: args.top_k]
    measure_candidates(
        top,
        model_name=args.model, num_classes=args.num_classes,
        image_size=args.image_size, model_overrides=overrides,
        global_batch=args.global_batch, iters=args.iters,
        rounds=args.rounds, devices=devices, log=log,
    )
    measured = [c for c in top if c.get("measured_ms_per_step") is not None]
    winner = min(
        measured, key=lambda c: c["measured_ms_per_opt_step"], default=None
    )

    trace_check = None
    if winner is not None and args.trace:
        trace_check = trace_cross_check(
            winner, winner["_cost"], args.trace, log=log
        )

    device_kind = getattr(devices[0], "device_kind", devices[0].platform)
    report = {
        "schema": MESH_TUNE_SCHEMA,
        "kind": "mesh-tune-report",
        "model": args.model,
        "n_devices": n_devices,
        "device_kind": str(device_kind),
        "global_batch": args.global_batch,
        "peak_flops": peak_flops,
        "peak_source": peak_source,
        "dot_dtype": args.dot_dtype,
        "ici_bytes_per_s": ici,
        "ici_source": ici_source,
        "candidates": [
            {k: v for k, v in c.items() if not k.startswith("_")}
            for c in candidates
        ],
        "winner": (
            {k: v for k, v in winner.items() if not k.startswith("_")}
            if winner is not None else None
        ),
        "trace_check": trace_check,
    }
    if args.report:
        os.makedirs(
            os.path.dirname(os.path.abspath(args.report)), exist_ok=True
        )
        tmp = f"{args.report}.tmp.{os.getpid()}"
        with open(tmp, "w") as f:
            json.dump(report, f, indent=2, default=str)
        os.replace(tmp, args.report)

    if winner is None:
        log("mesh_tune: no candidate survived measurement — no preset emitted")
        return report

    provenance = {
        "tool": "tools/mesh_tune.py",
        "device_kind": str(device_kind),
        "n_devices": n_devices,
        "model": args.model,
        "global_batch": args.global_batch,
        "measured_ms_per_step": winner["measured_ms_per_step"],
        "measured_ms_per_opt_step": winner["measured_ms_per_opt_step"],
        "predicted_ms_per_opt_step": round(
            winner["predicted"]["total_s"] * 1e3, 3
        ),
        "methodology": (
            f"trap-pinned scan, min of {args.rounds}x{args.iters} "
            "round-robin"
        ),
        "peak_source": peak_source,
        "ici_source": ici_source,
    }
    if trace_check is not None:
        provenance["trace_disagreements"] = trace_check.get("disagrees") or (
            [] if trace_check.get("available") else ["(trace unavailable)"]
        )
    save_layout_preset(
        args.out, winner["_layout"],
        grad_accum_steps=winner["grad_accum"], provenance=provenance,
    )
    log(
        f"mesh_tune: winner {winner['name']} accum={winner['grad_accum']} "
        f"({winner['measured_ms_per_opt_step']} ms/opt-step measured) "
        f"-> {args.out}"
    )
    return report


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--model", default="deit_s_patch16")
    p.add_argument("--num-classes", type=int, default=1000)
    p.add_argument("--image-size", type=int, default=224)
    p.add_argument(
        "--model-overrides", default=None,
        help='JSON hyperparameter overrides (e.g. \'{"num_layers": 2}\')',
    )
    p.add_argument("--global-batch", type=int, default=256)
    p.add_argument(
        "--devices", type=int, default=None,
        help="device count to tune for (default: all visible)",
    )
    p.add_argument(
        "--arms", default="dp,tp,2d,fsdp",
        help=f"comma subset of {','.join(ARMS)}",
    )
    p.add_argument(
        "--grad-accum", default="1",
        help="comma ladder of grad-accum steps (microbatch = global/accum)",
    )
    p.add_argument("--top-k", type=int, default=3)
    p.add_argument("--iters", type=int, default=8,
                   help="scan length of one timing window")
    p.add_argument("--rounds", type=int, default=3,
                   help="round-robin windows per candidate (minima reported)")
    p.add_argument("--peak-flops", type=float, default=None)
    p.add_argument(
        "--dot-dtype", default=None, choices=["bf16", "f32", "int8"],
        help="dtype the projection/FFN dots run in (obs/costs.py dtype "
        "axis): 'int8' ranks layouts for the quantized arm — 2x the "
        "bf16 peak FLOP/s and half the activation bytes in the TP "
        "collective terms (docs/quantization.md). Default: the bf16 "
        "accounting, unchanged.",
    )
    p.add_argument(
        "--ici-gbps", type=float, default=None,
        help="ICI bandwidth override, GB/s per chip (default: "
        f"{DEFAULT_ICI_BYTES_PER_S / 1e9:.0f} estimate; cpu-fake on CPU)",
    )
    p.add_argument(
        "--trace", default=None,
        help="capture the winner's loop here and cross-check measured vs "
        "predicted attribution (flagged in report + preset provenance)",
    )
    p.add_argument(
        "--out", default=".tpu_results/layout_preset.json",
        help="winner preset path (train.py --layout-preset consumes it)",
    )
    p.add_argument(
        "--report", default=".tpu_results/mesh_tune_report.json",
        help="full sweep report (every candidate, predictions, reasons)",
    )
    args = p.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print(
            "mesh_tune: WARNING — backend is "
            f"{jax.default_backend()!r}; timings are NOT chip-meaningful "
            "(the emitted preset should not be promoted to training runs)",
            file=sys.stderr,
        )
    run(args)
    return 0


if __name__ == "__main__":
    sys.exit(main())
