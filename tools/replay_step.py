#!/usr/bin/env python
"""Deterministically re-execute recorded steps from an incident bundle.

The flight recorder (``sav_tpu/obs/recorder.py``, train.py ``--record``)
dumps ``<log_dir>/incidents/step_<N>/`` on a nonfinite/spike/hang/crash
incident: the ring index, the raw host batches of the last steps, the rng
derivation recipe, and a pre-step ``TrainState`` snapshot saved through
the normal checkpoint machinery. This tool closes the loop — the NaN
that killed a multi-hour run becomes a deterministic, seconds-long
reproduction:

1. **as-recorded** — rebuild the exact trainer from the bundle's config
   (diagnostics forced on), restore the snapshot, and replay steps
   ``snapshot+1 .. incident``. Replayed step metrics are compared
   **bit-exactly** against the metrics the run logged (same program, same
   inputs, same backend ⇒ same bits), and the first step whose metrics go
   nonfinite is identified, along with the first layer *group* whose
   gradients go nonfinite — the same ``_group_of`` naming as the
   ``grad_norm/<group>`` diagnostics and ``flops/<group>`` cost gauges,
   so provenance lines up with the dashboards.
2. **checkify** — re-run the first bad step under
   ``jax.experimental.checkify`` NaN checks (``utils/debug.py``): the
   error names the first failing *primitive* and its source line.
3. **f32 recompute** — replay the same steps with ``compute_dtype``
   forced to float32: still-nonfinite means a genuine divergence (bad
   batch / lr spike), finite-in-f32 means bf16 range/precision is the
   culprit.

The verdict is written back into the bundle as ``replay_verdict.json``
(rendered by ``tools/run_report.py --incidents``).

Usage:
  python tools/replay_step.py runs/deit/incidents/step_00001234
  python tools/replay_step.py <bundle> --json --no-escalate
  python tools/replay_step.py <bundle> --platform cpu   # triage off-chip

Exit codes: 0 = replay ran (verdict written), 2 = usage/bundle error.
Note: the bundle's mesh axes must divide the replay host's device count
(a CPU replay of an 8-chip run wants the same
``--xla_force_host_platform_device_count`` the tests use).
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _REPO_ROOT)

VERDICT_SCHEMA = 1


def load_incident(bundle: str) -> dict:
    path = os.path.join(bundle, "incident.json")
    with open(path) as f:
        doc = json.load(f)
    if not isinstance(doc, dict) or "step" not in doc:
        raise ValueError(f"{path}: not an incident record")
    return doc


def _entry_for(doc: dict, step: int) -> dict:
    for entry in doc.get("ring", []):
        if entry.get("step") == step:
            return entry
    return {}


def build_trainer(config: dict, *, compute_dtype=None):
    """Trainer rebuilt from the bundle's serialized TrainConfig.

    Side-effectful knobs are neutralized: no checkpointer (the replay
    must never touch the original run's checkpoints), no recorder (a
    replay of an incident must not record incidents), no compile cache.
    """
    import dataclasses

    from sav_tpu.train import TrainConfig, Trainer

    cfg = TrainConfig(**config)
    cfg = dataclasses.replace(
        cfg,
        checkpoint_dir=None,
        log_dir=None,
        record=False,
        sanitize=False,
        watchdog_secs=None,
        profile_dir=None,
        compilation_cache_dir=None,
        diagnostics=True,  # per-group grad norms drive the provenance
        **(
            {"compute_dtype": compute_dtype}
            if compute_dtype is not None else {}
        ),
    )
    return Trainer(cfg)


def restore_snapshot(trainer, bundle: str):
    from sav_tpu.train.checkpoint import Checkpointer

    template = trainer.init_state()
    ckpt = Checkpointer(os.path.join(bundle, "state"), read_only=True)
    try:
        state = ckpt.restore_latest(template)
    finally:
        ckpt.close()
    if state is None:
        raise ValueError(f"{bundle}/state holds no snapshot")
    return state


def _first_group_order(params) -> list:
    """Top-level parameter-tree groups in insertion (≈ model depth) order,
    matching diagnostics' ``_group_of`` naming."""
    try:
        return list(params.keys())
    except AttributeError:
        return []


def _nonfinite_groups(host_metrics: dict, order: list) -> list:
    """Layer groups whose grad norms went nonfinite, in model order."""
    bad = {
        k[len("grad_norm/"):]
        for k, v in host_metrics.items()
        if k.startswith("grad_norm/") and not math.isfinite(v)
    }
    ordered = [g for g in order if g in bad]
    return ordered + sorted(bad - set(ordered))


def replay(
    trainer, state, doc: dict, bundle: str, steps: list
) -> tuple[list, object]:
    """Replay ``steps`` in order; returns (per-step records, final state).

    Each record: {step, metrics (host floats), nonfinite: bool,
    bad_groups, recorded, match}.
    """
    import jax

    from sav_tpu.obs.recorder import device_metric_items, load_bundle_batch

    rng = jax.random.fold_in(
        jax.random.PRNGKey(doc["config"]["seed"]), 1
    )
    order = _first_group_order(state.params)
    records = []
    for step in steps:
        entry = _entry_for(doc, step)
        dtypes = (entry.get("batch") or {}).get("dtypes", {})
        batch = load_bundle_batch(bundle, step, dtypes)
        placed = trainer.shard_batch(batch)
        state, metrics = trainer.train_step_placed(state, placed, rng)
        host = {
            k: float(v) for k, v in jax.device_get(metrics).items()
        }
        device_items = device_metric_items(host)
        nonfinite = any(not math.isfinite(v) for _, v in device_items)
        record = {
            "step": step,
            "metrics": host,
            "nonfinite": nonfinite,
            "bad_groups": _nonfinite_groups(host, order),
        }
        recorded = entry.get("metrics")
        if recorded:
            mismatches = []
            for key, want in device_metric_items(recorded):
                got = host.get(key)
                if got is None:
                    continue  # replay forces diagnostics on; extra keys ok
                same = got == want or (
                    math.isnan(got) and math.isnan(want)
                )
                if not same:
                    mismatches.append(
                        {"key": key, "recorded": want, "replayed": got}
                    )
            record["compared"] = True
            record["match"] = not mismatches
            record["mismatches"] = mismatches
        else:
            record["compared"] = False
        records.append(record)
    return records, state


def checkify_probe(trainer, state, doc: dict, bundle: str, step: int):
    """Escalation rung 2: the first bad step under checkify nan_checks —
    the raised error names the first failing primitive + source line."""
    import jax

    from sav_tpu.obs.recorder import load_bundle_batch
    from sav_tpu.utils.debug import checkify_step

    entry = _entry_for(doc, step)
    dtypes = (entry.get("batch") or {}).get("dtypes", {})
    batch = load_bundle_batch(bundle, step, dtypes)
    placed = trainer.shard_batch(batch)
    rng = jax.random.fold_in(
        jax.random.PRNGKey(doc["config"]["seed"]), 1
    )
    checked = checkify_step(trainer._train_step_impl)
    try:
        checked(state, placed, rng)
    except Exception as e:  # checkify throws ValueError/JaxRuntimeError
        message = str(e)
        return {
            "error_type": type(e).__name__,
            # First line carries "nan generated by primitive <p> at <src>".
            "first_error": message.strip().splitlines()[0][:500],
        }
    return None


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("bundle", help="incident bundle directory")
    parser.add_argument(
        "--json", action="store_true", help="machine-readable verdict"
    )
    parser.add_argument(
        "--no-escalate", action="store_true",
        help="as-recorded replay only (skip checkify + f32 recompute)",
    )
    parser.add_argument(
        "--no-write", action="store_true",
        help="do not write replay_verdict.json back into the bundle",
    )
    parser.add_argument(
        "--platform", choices=["auto", "cpu"], default="auto",
        help="'cpu' pins JAX to host CPU before backend init — replay an "
        "accelerator incident on a workstation",
    )
    args = parser.parse_args(argv)

    try:
        doc = load_incident(args.bundle)
    except (OSError, ValueError, json.JSONDecodeError) as e:
        print(f"replay: cannot read bundle: {e}", file=sys.stderr)
        return 2
    if not doc.get("replayable"):
        print(
            "replay: bundle is not replayable (no snapshot + contiguous "
            "batches — an eval-only or budget-truncated dump)",
            file=sys.stderr,
        )
        return 2
    config = doc.get("config")
    if not config:
        print("replay: bundle carries no config", file=sys.stderr)
        return 2

    import jax

    if args.platform == "cpu":
        jax.config.update("jax_platforms", "cpu")

    snap_step = doc["snapshot_step"]
    incident_step = doc["step"]
    batch_steps = set(doc.get("batch_steps") or [])
    steps = [
        s for s in range(snap_step + 1, incident_step + 1)
        if s in batch_steps
    ]
    if not steps:
        print("replay: no replayable steps in bundle", file=sys.stderr)
        return 2

    trainer = build_trainer(config)
    state = restore_snapshot(trainer, args.bundle)
    records, _ = replay(trainer, state, doc, args.bundle, steps)

    first_bad = next((r for r in records if r["nonfinite"]), None)
    compared = [r for r in records if r["compared"]]
    verdict = {
        "schema": VERDICT_SCHEMA,
        "bundle": args.bundle,
        "trigger": doc.get("trigger"),
        "snapshot_step": snap_step,
        "replayed_steps": steps,
        "metrics_match": bool(compared) and all(
            r["match"] for r in compared
        ),
        "steps_compared": len(compared),
        "mismatches": [
            {"step": r["step"], "mismatches": r["mismatches"]}
            for r in compared if not r["match"]
        ],
        "first_bad_step": first_bad["step"] if first_bad else None,
        "first_bad_group": (
            first_bad["bad_groups"][0]
            if first_bad and first_bad["bad_groups"] else None
        ),
        "bad_groups": first_bad["bad_groups"] if first_bad else [],
        "checkify": None,
        "f32": None,
    }

    if first_bad is not None and not args.no_escalate:
        # Rung 2: checkify needs the state JUST BEFORE the bad step —
        # replay donated the buffers, so restore and advance again.
        pre_state = restore_snapshot(trainer, args.bundle)
        before = [s for s in steps if s < first_bad["step"]]
        if before:
            _, pre_state = replay(
                trainer, pre_state, doc, args.bundle, before
            )
        verdict["checkify"] = checkify_probe(
            trainer, pre_state, doc, args.bundle, first_bad["step"]
        )
        # Rung 3: same steps, f32 compute — finite here means bf16
        # range/precision, still-nonfinite means a genuine divergence.
        if config.get("compute_dtype") != "float32":
            f32_trainer = build_trainer(config, compute_dtype="float32")
            f32_state = restore_snapshot(f32_trainer, args.bundle)
            f32_records, _ = replay(
                f32_trainer, f32_state, doc, args.bundle, steps
            )
            verdict["f32"] = {
                "ran": True,
                "finite": not any(r["nonfinite"] for r in f32_records),
                "first_bad_step": next(
                    (r["step"] for r in f32_records if r["nonfinite"]), None
                ),
            }
        else:
            verdict["f32"] = {"ran": False, "reason": "already float32"}

    if not args.no_write:
        tmp = os.path.join(args.bundle, "replay_verdict.json.tmp")
        with open(tmp, "w") as f:
            json.dump(verdict, f, indent=2)
        os.replace(tmp, os.path.join(args.bundle, "replay_verdict.json"))

    if args.json:
        print(json.dumps(verdict, indent=2))
    else:
        print(
            f"replay: {len(steps)} steps from snapshot {snap_step} "
            f"(trigger {doc.get('trigger')})"
        )
        if compared:
            status = "BIT-EXACT" if verdict["metrics_match"] else "MISMATCH"
            print(
                f"  recorded-metrics comparison: {status} "
                f"({len(compared)} steps)"
            )
        if first_bad is None:
            print("  no nonfinite step reproduced in the replayed window")
        else:
            print(
                f"  first nonfinite step: {first_bad['step']} — first bad "
                f"layer group: {verdict['first_bad_group']} "
                f"(all: {', '.join(verdict['bad_groups']) or 'none'})"
            )
            if verdict["checkify"]:
                print(f"  checkify: {verdict['checkify']['first_error']}")
            if verdict["f32"] and verdict["f32"].get("ran"):
                outcome = (
                    "finite in f32 — bf16 range/precision is implicated"
                    if verdict["f32"]["finite"]
                    else "still nonfinite in f32 — genuine divergence "
                    "(batch / lr), not dtype"
                )
                print(f"  f32 recompute: {outcome}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
