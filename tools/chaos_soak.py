#!/usr/bin/env python
"""Chaos soak — prove elastic training survives what kills real runs.

Runs a real child ``train.py`` under the elasticity supervisor
(:mod:`sav_tpu.train.supervisor`) and injects the three production
failure shapes at chosen (or seeded-random) steps:

  - **SIGKILL** — a preemption: the process dies with no warning, no
    finally blocks, no manifest finalize (the ``backend_unreachable``
    shape that killed bench rounds 3 and 5, minus the probe's courtesy).
  - **hang** — the data stream stalls forever at a step; the child's own
    watchdog converts it into the exit-4 contract (fired once per soak —
    a hang models a transient infra fault, it has no data-level cure).
  - **NaN** — a poisoned batch at a known step; ``--debug-nans`` kills
    the child with outcome ``nonfinite``, the flight recorder dumps the
    batch, and the supervisor's rewind-and-skip must cure it on restart.

The soak then **verifies** the chain end to end (the ROADMAP item-4
goodput proof, CPU-scaled):

  1. the supervisor manifest chain is structurally sound, final outcome
     ok, and its goodput accounting covers ≥ ``--min-accounted`` of the
     supervisor's wall time (attempt walls + backoff — nothing vanishes);
  2. every injected fault shows up as exactly one restart with the right
     reason (``killed:SIGKILL`` / ``hang`` / ``nonfinite``);
  3. resume is **step-exact**: each restarted attempt's manifest carries
     the blake2b fingerprint of the first batch it trained on
     (``notes.resume``), and this harness recomputes the same position's
     batch from the counter-based synthetic stream and matches it;
  4. the planted-NaN batch is skipped **exactly once** (the chain's skip
     ledger and the resumed attempt's ``notes.rewind_skip`` agree, and
     no later attempt skips again);
  5. the loss curve is **continued**, not restarted: an uninterrupted
     reference run (same seed, with ``--skip-steps`` for the planted
     NaN) must agree with the soaked run's logged losses at every common
     step within ``--loss-tol`` (0 = bit-equal — float32 CPU children
     are deterministic through checkpoint round-trips).

CPU smoke (tier-1 runs a scaled version of exactly this):

  python tools/chaos_soak.py --log-dir /tmp/soak --platform cpu \\
      --steps 60 --kill-at-steps 12,28 --nan-at-step 40

On-chip soak (not yet run): seeded-random kills over a long
run, ``--loss-tol`` loosened for bf16, the sentinel gating
``goodput_frac`` from the supervisor manifest afterwards.

The harness itself NEVER imports jax — it is the parent of on-chip
children, and a parent that touched the backend would hold the chip
against them (numpy loads lazily for the batch fingerprints).
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import sys
import threading
import time

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO_ROOT not in sys.path:  # runnable as a script from anywhere
    sys.path.insert(0, _REPO_ROOT)

from sav_tpu.train.supervisor import (  # noqa: E402
    Supervisor,
    load_chain,
    read_attempt_heartbeats,
    resume_schedule_position,
    verify_chain,
)

EXIT_CLEAN, EXIT_FAILED, EXIT_USAGE = 0, 1, 2


def _child_argv(args, *, log_dir, ckpt_dir, skip_steps=None) -> list:
    argv = [
        sys.executable,
        os.path.join(_REPO_ROOT, "train.py"),
        "--preset", args.preset,
        "--synth-data",
        "--platform", args.platform,
        "--steps", str(args.steps),
        "--batch-size", str(args.batch_size),
        "--seed", str(args.seed),
        "-c", ckpt_dir,
        "--log-dir", log_dir,
        "--checkpoint-every-steps", str(args.checkpoint_every_steps),
        "--record",
        "--debug-nans",
    ]
    if args.hang_at_step is not None:
        argv += ["--watchdog-secs", str(args.watchdog_secs)]
    if args.compilation_cache_dir:
        argv += ["--compilation-cache-dir", args.compilation_cache_dir]
    if skip_steps:
        argv += ["--skip-steps", ",".join(map(str, sorted(skip_steps)))]
    argv += list(args.child_arg or [])
    return argv


class _Killer(threading.Thread):
    """SIGKILLs the current child when its heartbeat step reaches each
    target — the preemption injector. Reads the per-attempt heartbeat
    stream (flushed per line, pid-tagged) rather than guessing by time,
    so kills land at reproducible steps."""

    def __init__(self, targets: list, log_dir: str, poll_s: float = 0.2):
        super().__init__(name="chaos-killer", daemon=True)
        self.targets = sorted(targets)
        self.log_dir = log_dir
        self.poll_s = poll_s
        self.kills: list = []
        self._lock = threading.Lock()
        self._child = None
        self._stop = threading.Event()

    def on_spawn(self, attempt: int, popen) -> None:
        with self._lock:
            self._child = popen

    def stop(self) -> None:
        self._stop.set()

    def run(self) -> None:
        while self.targets and not self._stop.is_set():
            with self._lock:
                child = self._child
            if child is None or child.poll() is not None:
                time.sleep(self.poll_s)
                continue
            beats = read_attempt_heartbeats(self.log_dir, child.pid)
            step = beats[-1]["step"] if beats else None
            if step is not None and step >= self.targets[0]:
                target = self.targets.pop(0)
                try:
                    os.kill(child.pid, signal.SIGKILL)
                    self.kills.append({"target_step": target, "at_step": step})
                except ProcessLookupError:
                    pass  # it died on its own first; the chain will say why
            time.sleep(self.poll_s)


def _load_metrics_losses(log_dir: str) -> dict:
    """step → loss from metrics.jsonl; attempts append to one file, so
    the LAST occurrence per step wins (the value that survived)."""
    losses: dict = {}
    path = os.path.join(log_dir, "metrics.jsonl")
    try:
        with open(path) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    rec = json.loads(line)
                except json.JSONDecodeError:
                    continue
                if isinstance(rec.get("loss"), (int, float)):
                    losses[int(rec["step"])] = float(rec["loss"])
    except OSError:
        pass
    return losses


def _attempt_manifest(log_dir: str, rel: str) -> dict:
    try:
        with open(os.path.join(log_dir, rel)) as f:
            return json.load(f)
    except (OSError, json.JSONDecodeError, TypeError):
        return {}


def verify_soak(args, chain: dict, killer_kills: list) -> tuple:
    """(problems, summary) — the data-level half of the proof on top of
    :func:`verify_chain`'s structural half."""
    from sav_tpu.obs.recorder import batch_fingerprint  # lazy: numpy
    from sav_tpu.data.synthetic import synth_batch  # numpy-only
    from sav_tpu.train import get_preset

    preset = get_preset(args.preset)
    expected_attempts = None
    if not args.random_kills:
        expected_attempts = (
            1
            + len(args.kills)
            + (1 if args.nan_at_step is not None else 0)
            + (1 if args.hang_at_step is not None else 0)
        )
    problems = verify_chain(
        chain,
        min_accounted=args.min_accounted,
        expect_attempts=expected_attempts,
    )
    notes = (chain.get("notes") or {}).get("chain") or {}
    attempts = notes.get("attempts") or []
    reasons = [a.get("restart_reason") for a in attempts[:-1]]

    # 2. every injected fault → one restart with the right reason
    n_sigkill = sum(1 for r in reasons if r == "killed:SIGKILL")
    if n_sigkill != len(killer_kills):
        problems.append(
            f"{len(killer_kills)} SIGKILLs injected but {n_sigkill} "
            "killed:SIGKILL restarts in the chain"
        )
    if args.nan_at_step is not None and reasons.count("nonfinite") != 1:
        problems.append(
            f"planted NaN should cause exactly 1 nonfinite restart, chain "
            f"has {reasons.count('nonfinite')}"
        )
    if args.hang_at_step is not None and reasons.count("hang") != 1:
        problems.append(
            f"injected hang should cause exactly 1 exit-4 restart, chain "
            f"has {reasons.count('hang')}"
        )

    # 4. NaN batch skipped exactly once
    skipped = notes.get("skipped_steps") or []
    skip_attempts = []
    for a in attempts:
        doc = _attempt_manifest(args.log_dir, a.get("manifest") or "")
        rs = (doc.get("notes") or {}).get("rewind_skip")
        if rs:
            skip_attempts.append((a.get("attempt"), rs))
    if args.nan_at_step is not None:
        if skipped != [args.nan_at_step]:
            problems.append(
                f"chain skip ledger is {skipped}, expected "
                f"[{args.nan_at_step}]"
            )
        if len(skip_attempts) != 1:
            problems.append(
                f"{len(skip_attempts)} attempts applied a rewind-skip, "
                "expected exactly 1"
            )
        elif skip_attempts[0][1].get("steps") != [args.nan_at_step]:
            problems.append(
                f"resumed attempt skipped {skip_attempts[0][1].get('steps')}"
                f", expected [{args.nan_at_step}]"
            )
    elif skip_attempts or skipped:
        problems.append(f"unexpected rewind-skips: {skipped}")

    # 3. step-exact resume: recompute each restart's first batch hash
    hash_checks = 0
    for a in attempts[1:]:
        doc = _attempt_manifest(args.log_dir, a.get("manifest") or "")
        resume = (doc.get("notes") or {}).get("resume") or {}
        got = resume.get("next_batch_hash")
        resumed_from = a.get("resumed_from_step")
        if got is None or resumed_from is None:
            problems.append(
                f"attempt {a.get('attempt')} has no resume fingerprint "
                "(notes.resume.next_batch_hash)"
            )
            continue
        if resume.get("from_step") != resumed_from:
            problems.append(
                f"attempt {a.get('attempt')} resumed from "
                f"{resume.get('from_step')} but the chain says "
                f"{resumed_from}"
            )
        # The same shift math train.py used to rebuild the stream: the
        # first consumed batch is the next unskipped ORIGINAL position
        # after the (skip-shifted) position of the restored step.
        pos = resume_schedule_position(
            resumed_from + 1, a.get("skip_steps") or []
        )
        if pos == args.nan_at_step:
            continue  # the poisoned position hashes as poisoned; skip
        expected = batch_fingerprint(synth_batch(
            seed=args.seed,
            position=pos,
            batch_size=args.batch_size,
            image_size=preset.image_size,
            num_classes=preset.num_classes,
        ))["hash"]
        if got != expected:
            problems.append(
                f"attempt {a.get('attempt')} resumed at step "
                f"{resumed_from} with batch hash {got[:12]}… but the "
                f"uninterrupted schedule's position-{pos} batch is "
                f"{expected[:12]}… — resume is NOT step-exact"
            )
        else:
            hash_checks += 1

    # 5. loss continuity against the uninterrupted reference
    loss_summary = None
    if args.reference:
        soak = _load_metrics_losses(args.log_dir)
        ref = _load_metrics_losses(args.ref_dir)
        common = sorted(set(soak) & set(ref))
        if len(common) < 3:
            problems.append(
                f"only {len(common)} common logged steps between soak and "
                "reference — cannot prove loss continuity"
            )
        else:
            diffs = [abs(soak[s] - ref[s]) for s in common]
            worst = max(diffs)
            if worst > args.loss_tol:
                at = common[diffs.index(worst)]
                problems.append(
                    f"loss diverges from the uninterrupted reference: "
                    f"|Δ|={worst:g} at step {at} (tol {args.loss_tol:g})"
                )
            loss_summary = {
                "common_steps": len(common),
                "max_abs_diff": worst,
                "final_step": common[-1],
            }

    metrics = chain.get("metrics") or {}
    summary = {
        "attempts": len(attempts),
        "restart_reasons": reasons,
        "kills_injected": killer_kills,
        "skipped_steps": skipped,
        "resume_hash_checks": hash_checks,
        "goodput_frac": metrics.get("goodput_frac"),
        "accounted_frac": metrics.get("accounted_frac"),
        "lost_s": metrics.get("goodput/lost_s"),
        "loss_continuity": loss_summary,
        "verified": not problems,
        "problems": problems,
    }
    return problems, summary


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    parser.add_argument("--log-dir", required=True)
    parser.add_argument(
        "--ckpt-dir", default=None,
        help="child checkpoint dir (default <log-dir>/ckpt)",
    )
    parser.add_argument("--steps", type=int, default=60)
    parser.add_argument(
        "--kill-at-steps", default="12,28",
        help="comma-separated heartbeat steps at which to SIGKILL the "
        "child ('' disables)",
    )
    parser.add_argument(
        "--random-kills", type=int, default=0,
        help="instead of --kill-at-steps: N kills at seeded-random steps "
        "in [--kill-min, --kill-max] (the on-chip soak mode)",
    )
    parser.add_argument("--kill-min", type=int, default=10)
    parser.add_argument("--kill-max", type=int, default=None)
    parser.add_argument("--chaos-seed", type=int, default=0)
    parser.add_argument(
        "--nan-at-step", type=int, default=None,
        help="poison the batch at this schedule step with NaN (the "
        "rewind-and-skip proof)",
    )
    parser.add_argument(
        "--hang-at-step", type=int, default=None,
        help="stall the data stream at this step, once per soak (the "
        "watchdog exit-4 leg); requires a finite --watchdog-secs",
    )
    parser.add_argument("--watchdog-secs", type=float, default=60.0)
    parser.add_argument("--preset", default="elastic_smoke")
    parser.add_argument("--batch-size", type=int, default=8)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--platform", choices=["auto", "cpu"], default="cpu")
    parser.add_argument("--checkpoint-every-steps", type=int, default=5)
    parser.add_argument("--max-restarts", type=int, default=8)
    parser.add_argument("--backoff", type=float, default=0.25)
    parser.add_argument("--compilation-cache-dir", default=None)
    parser.add_argument(
        "--reference", action=argparse.BooleanOptionalAction, default=True,
        help="also run an uninterrupted reference child and require the "
        "soaked loss curve to match it at common steps (--no-reference "
        "for week-long soaks)",
    )
    parser.add_argument(
        "--loss-tol", type=float, default=0.0,
        help="max |loss difference| vs the reference (0 = bit-equal; "
        "loosen for bf16/on-chip nondeterminism)",
    )
    parser.add_argument("--min-accounted", type=float, default=0.99)
    parser.add_argument(
        "--child-arg", action="append", default=[],
        help="extra raw argument appended to every child command "
        "(repeatable)",
    )
    parser.add_argument(
        "--lockwatch", action="store_true",
        help="run the soak harness under the runtime lock sanitizer "
        "(sav_tpu.analysis.lockwatch): the supervisor's and killer's "
        "locks are tracked, the observed acquisition graph lands in "
        "<log-dir>/lockwatch.json, and any observed lock-order "
        "inversion fails the soak",
    )
    parser.add_argument("--json", action="store_true")
    args = parser.parse_args(argv)

    args.kills = [
        int(s) for s in str(args.kill_at_steps).split(",") if s.strip()
    ]
    if args.random_kills:
        rng = random.Random(args.chaos_seed)
        hi = args.kill_max or max(args.steps - 10, args.kill_min + 1)
        args.kills = sorted(
            rng.randint(args.kill_min, hi) for _ in range(args.random_kills)
        )
    if args.hang_at_step is not None and not args.watchdog_secs:
        print("chaos_soak: --hang-at-step needs --watchdog-secs",
              file=sys.stderr)
        return EXIT_USAGE
    for fault, name in ((args.nan_at_step, "--nan-at-step"),
                        (args.hang_at_step, "--hang-at-step")):
        if fault is not None and not 1 <= fault <= args.steps:
            print(f"chaos_soak: {name} {fault} outside 1..{args.steps}",
                  file=sys.stderr)
            return EXIT_USAGE
    args.ckpt_dir = args.ckpt_dir or os.path.join(args.log_dir, "ckpt")
    args.ref_dir = os.path.join(args.log_dir, "reference")
    os.makedirs(args.log_dir, exist_ok=True)

    chaos_env = {}
    if args.nan_at_step is not None:
        chaos_env["SAV_CHAOS_NAN_STEP"] = str(args.nan_at_step)
    if args.hang_at_step is not None:
        chaos_env["SAV_CHAOS_HANG_STEP"] = str(args.hang_at_step)
        chaos_env["SAV_CHAOS_ONCE_DIR"] = args.log_dir

    watch = None
    watch_ctx = None
    if args.lockwatch:
        # Arm BEFORE constructing the killer/supervisor — only locks
        # built inside the patch window are tracked. The killer's lock
        # lives in this module; the supervisor's in its own.
        from sav_tpu.analysis.lockwatch import LockWatch
        from sav_tpu.train import supervisor as _supervisor_mod

        watch = LockWatch()
        watch_ctx = watch.patch(_supervisor_mod, sys.modules[__name__])
        watch_ctx.__enter__()

    killer = _Killer(args.kills, args.log_dir)
    supervisor = Supervisor(
        _child_argv(args, log_dir=args.log_dir, ckpt_dir=args.ckpt_dir),
        log_dir=args.log_dir,
        checkpoint_dir=args.ckpt_dir,
        max_restarts=args.max_restarts,
        backoff_base_s=args.backoff,
        backoff_max_s=max(args.backoff * 8, args.backoff),
        capture=True,
        on_spawn=killer.on_spawn,
        env=chaos_env,
    )
    print(
        f"chaos_soak: {args.steps} steps, kills at {args.kills}, "
        f"nan at {args.nan_at_step}, hang at {args.hang_at_step} -> "
        f"{args.log_dir}",
        file=sys.stderr,
    )
    killer.start()
    rc = supervisor.run()
    killer.stop()
    if watch is not None:
        watch_ctx.__exit__(None, None, None)
    if rc != 0:
        print(f"chaos_soak: supervised chain FAILED (rc {rc})",
              file=sys.stderr)

    if args.reference:
        # The uninterrupted twin: same seed/steps, no supervisor, no
        # chaos env — plus the same --skip-steps the rewind applied, so
        # both runs trained on the identical example sequence.
        import subprocess

        os.makedirs(args.ref_dir, exist_ok=True)
        skip = {args.nan_at_step} if args.nan_at_step is not None else None
        ref_argv = _child_argv(
            args,
            log_dir=args.ref_dir,
            ckpt_dir=os.path.join(args.ref_dir, "ckpt"),
            skip_steps=skip,
        )
        with open(os.path.join(args.ref_dir, "child.out"), "w") as out:
            ref_rc = subprocess.run(
                ref_argv, stdout=out, stderr=subprocess.STDOUT,
            ).returncode
        if ref_rc != 0:
            print(
                f"chaos_soak: reference run failed (rc {ref_rc}) — "
                "continuity not provable",
                file=sys.stderr,
            )

    chain = load_chain(args.log_dir)
    if chain is None:
        print("chaos_soak: no supervisor.json written", file=sys.stderr)
        return EXIT_FAILED
    problems, summary = verify_soak(args, chain, killer.kills)
    if watch is not None:
        lw = watch.write(os.path.join(args.log_dir, "lockwatch.json"))
        summary["lockwatch"] = {
            "locks": len(lw["locks"]),
            "edges": len(lw["edges"]),
            "cycles": lw["cycles"],
        }
        if lw["cycles"]:
            problems.append(
                "lockwatch observed lock-order inversion(s): "
                + "; ".join(" -> ".join(c) for c in lw["cycles"])
            )
            summary["verified"] = False
    if rc != 0:
        problems.insert(0, f"supervised chain exit code {rc}")
        summary["verified"] = False
        summary["problems"] = problems
    if args.json:
        print(json.dumps(summary, indent=2))
    else:
        print(
            f"chaos_soak: {summary['attempts']} attempts, restarts: "
            f"{summary['restart_reasons']}, goodput "
            f"{summary['goodput_frac']}, accounted "
            f"{summary['accounted_frac']}"
        )
        if summary["loss_continuity"]:
            lc = summary["loss_continuity"]
            print(
                f"  loss continuity: {lc['common_steps']} common steps, "
                f"max |Δ| {lc['max_abs_diff']:g}"
            )
        for p in problems:
            print(f"  PROBLEM: {p}")
        print("  VERIFIED" if not problems else "  NOT VERIFIED")
    return EXIT_CLEAN if not problems else EXIT_FAILED


if __name__ == "__main__":
    sys.exit(main())
