"""Elastic-training layer (ISSUE 9): supervised restarts, step-granular
checkpoints, rewind-and-skip, torn-save defenses, and the chaos-soak
goodput proof.

Three tiers: stdlib-fast units on the supervisor's pure helpers; trainer
integration on the 8-device CPU mesh (cadence saves, opt-layout
auto-detection, torn-newest fallback); and REAL-child e2e — a SIGKILLed
``train.py`` resumed step-exact (recorder batch-hash match, bit-equal
re-logged loss windows), and a tier-1-scaled ``tools/chaos_soak.py`` run
(2 injected SIGKILLs + 1 planted NaN) whose manifest chain must verify:
≥99% goodput accounting, step-exact resumes, the NaN batch skipped
exactly once, and a loss curve bit-continued against an uninterrupted
reference.
"""

import json
import os
import signal
import subprocess
import sys
import time
from io import StringIO

import numpy as np
import pytest

from sav_tpu.data.synthetic import synth_batch, synth_resumable_iterator
from sav_tpu.obs.recorder import batch_fingerprint
from sav_tpu.train.supervisor import (
    Supervisor,
    chaos_wrap,
    classify_exit,
    latest_checkpoint_step,
    load_chain,
    newest_incident,
    parse_skip_steps,
    resume_schedule_position,
    skip_step_batches,
    strip_supervisor_flags,
    verify_chain,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TRAIN_PY = os.path.join(ROOT, "train.py")


# ------------------------------------------------------------ pure helpers


def test_strip_supervisor_flags_both_spellings():
    argv = [
        "--supervise", "-m", "x", "--max-restarts", "3",
        "--restart-backoff=2.5", "--steps", "5", "--max-restarts=9",
    ]
    assert strip_supervisor_flags(argv) == ["-m", "x", "--steps", "5"]
    # train.py --supervise strips the user's --skip-steps too (it seeds
    # the supervisor's cumulative ledger instead — two --skip-steps on
    # the child would collapse to click's last-value-wins).
    argv = ["--skip-steps", "5,9", "--steps", "5", "--skip-steps=7"]
    assert strip_supervisor_flags(
        argv, extra_value_flags=("--skip-steps",)
    ) == ["--steps", "5"]


def test_resume_schedule_position():
    assert resume_schedule_position(4, {5}) == 4
    assert resume_schedule_position(5, {5}) == 6
    assert resume_schedule_position(10, {5}) == 11
    assert resume_schedule_position(10, {5, 6}) == 12
    assert resume_schedule_position(5, {5, 6}) == 7
    assert resume_schedule_position(0, set()) == 0


def test_skip_shift_survives_later_restart():
    """THE rewind-and-skip resume contract: once position p was dropped,
    step s >= p consumes a later original batch — a restart resuming
    past the skip must rebuild the stream from the SHIFTED position (and
    one resuming before it must re-arm the skip), reproducing the
    uninterrupted skip-applied schedule exactly."""
    import itertools

    def stream(start_pos):  # original-schedule positions as the batches
        return iter(range(start_pos + 1, 100))

    skips = {5}
    full = list(itertools.islice(skip_step_batches(stream(0), skips), 20))
    assert full[:6] == [1, 2, 3, 4, 6, 7]  # position 5 dropped, shifted

    for r in (10, 3, 5):  # resume after / before / exactly at the skip
        start_pos = resume_schedule_position(r, skips)
        remaining = {p for p in skips if p > start_pos}
        resumed = list(itertools.islice(
            skip_step_batches(
                stream(start_pos), remaining, start_step=start_pos
            ),
            20 - r,
        ))
        assert resumed == full[r:], f"resume at step {r} desynced"


def test_supervisor_passes_cumulative_skips(tmp_path):
    """The skip set rides EVERY attempt's argv (initial user skips
    included), not just the one after the incident — the schedule shift
    must survive later restarts."""
    out = tmp_path / "argv.json"
    child = _fake_child(
        "import sys, json\n"
        "json.dump(sys.argv[1:], open(sys.argv[1], 'w'))\n",
        str(out),
    )
    sup = Supervisor(
        child, log_dir=str(tmp_path), checkpoint_dir=None,
        skip_steps={9, 5},
    )
    assert sup.run() == 0
    assert json.loads(out.read_text())[-2:] == ["--skip-steps", "5,9"]
    attempts = load_chain(str(tmp_path))["notes"]["chain"]["attempts"]
    assert attempts[0]["skip_steps"] == [5, 9]


def test_parse_skip_steps():
    assert parse_skip_steps(None) == set()
    assert parse_skip_steps("") == set()
    assert parse_skip_steps("3, 5,3") == {3, 5}
    with pytest.raises(ValueError):
        parse_skip_steps("3,x")
    with pytest.raises(ValueError):
        parse_skip_steps("0")


def test_skip_step_batches_semantics():
    """Positions are uninterrupted-schedule steps: consecutive skips drop
    consecutive ORIGINAL batches (no off-by-one re-anchoring), each at
    most once, and on_skip sees the dropped batch."""
    batches = [{"i": i} for i in range(1, 7)]
    dropped = []
    out = list(skip_step_batches(
        iter(batches), {2, 3}, on_skip=lambda pos, b: dropped.append((pos, b["i"]))
    ))
    assert [b["i"] for b in out] == [1, 4, 5, 6]
    assert dropped == [(2, 2), (3, 3)]
    # Resumed stream: start_step anchors the counter.
    out = list(skip_step_batches(
        iter([{"i": 11}, {"i": 12}, {"i": 13}]), {12}, start_step=10
    ))
    assert [b["i"] for b in out] == [11, 13]
    # Skip of the final batch: the stream just ends.
    out = list(skip_step_batches(iter([{"i": 1}]), {1}))
    assert out == []


def test_chaos_wrap_noop_without_env():
    it = iter([{"images": np.ones(3)}])
    assert chaos_wrap(it, start_step=0, env={}) is it


def test_chaos_wrap_nan_and_hang_once(tmp_path):
    def stream():
        while True:
            yield {"images": np.ones((2, 2), np.float32)}

    env = {"SAV_CHAOS_NAN_STEP": "2"}
    it = chaos_wrap(stream(), start_step=0, env=env)
    first, second, third = next(it), next(it), next(it)
    assert not np.isnan(first["images"]).any()
    assert np.isnan(second["images"]).all()
    assert not np.isnan(third["images"]).any()
    # Resumed stream re-injects at the same schedule position (the skip
    # wrapper outside is what cures it).
    it = chaos_wrap(stream(), start_step=1, env=env)
    assert np.isnan(next(it)["images"]).all()
    # Hang: once-per-chain via the marker dir, and measured in wall time.
    env = {
        "SAV_CHAOS_HANG_STEP": "1",
        "SAV_CHAOS_HANG_SECS": "0.2",
        "SAV_CHAOS_ONCE_DIR": str(tmp_path),
    }
    t0 = time.perf_counter()
    next(chaos_wrap(stream(), start_step=0, env=env))
    assert time.perf_counter() - t0 >= 0.2
    t0 = time.perf_counter()
    next(chaos_wrap(stream(), start_step=0, env=env))  # marker: no hang
    assert time.perf_counter() - t0 < 0.1


def test_synth_batch_is_counter_based():
    """The batch is a pure function of (seed, position) — resumable by
    construction, and an external verifier recomputes any position."""
    a = synth_batch(seed=7, position=5, batch_size=4)
    b = synth_batch(seed=7, position=5, batch_size=4)
    assert batch_fingerprint(a)["hash"] == batch_fingerprint(b)["hash"]
    c = synth_batch(seed=7, position=6, batch_size=4)
    assert batch_fingerprint(a)["hash"] != batch_fingerprint(c)["hash"]
    # A resumed iterator IS the uninterrupted schedule from that point.
    resumed = next(synth_resumable_iterator(seed=7, start_step=4, batch_size=4))
    assert batch_fingerprint(resumed)["hash"] == batch_fingerprint(a)["hash"]


def test_latest_checkpoint_step(tmp_path):
    assert latest_checkpoint_step(None) is None
    assert latest_checkpoint_step(str(tmp_path / "missing")) is None
    for name in ("4", "12", "7.orbax-checkpoint-tmp-123", "notastep"):
        (tmp_path / name).mkdir()
    assert latest_checkpoint_step(str(tmp_path)) == 12


def test_classify_exit():
    assert classify_exit(0, None) == "ok"
    assert classify_exit(3, None) == "backend_unreachable"
    assert classify_exit(4, None) == "hang"
    assert classify_exit(2, None) == "usage_error"
    assert classify_exit(-9, None) == "killed:SIGKILL"
    assert classify_exit(1, "nonfinite") == "nonfinite"
    # A SIGKILLed child's manifest is stranded at 'running' — meaningless;
    # the signal is the fact.
    assert classify_exit(-9, "running") == "killed:SIGKILL"
    assert classify_exit(1, None) == "crash:rc=1"


def test_newest_incident(tmp_path):
    assert newest_incident(str(tmp_path)) is None
    root = tmp_path / "incidents"
    for step, t in ((5, 1.0), (9, 2.0)):
        d = root / f"step_{step:08d}"
        d.mkdir(parents=True)
        (d / "incident.json").write_text(json.dumps(
            {"step": step, "trigger": "nonfinite", "created_unix": t}
        ))
    (root / "memdump_00000012").mkdir()  # no step context: skipped
    doc = newest_incident(str(tmp_path))
    assert doc["step"] == 9 and doc["path"].endswith("step_00000009")


# -------------------------------------------------- supervisor (fake kids)


def _fake_child(script: str, *args) -> list:
    return [sys.executable, "-c", script, *args]


def _run_supervisor(tmp_path, child, **kwargs):
    sleeps = []
    sup = Supervisor(
        child,
        log_dir=str(tmp_path),
        checkpoint_dir=str(tmp_path / "ckpt"),
        sleep=sleeps.append,
        **kwargs,
    )
    rc = sup.run()
    return sup, rc, sleeps


def test_supervisor_restarts_until_success(tmp_path):
    """Exit-3 children restart with exponential backoff; the chain ends
    ok, every restart carries a reason, and the goodput metrics ride the
    supervisor manifest (a plain RunManifest the sentinel can read).

    The chain's accounting is checked on a clock the test owns (the
    supervisor's ``clock``/``sleep``/``on_spawn`` seams): an attempt lasts
    the 10 s ``on_spawn`` advances it by, a back-off as long as it was
    asked to sleep, and each read of the clock costs a millisecond of
    bookkeeping. What the supervisor decides (reasons, the back-off
    sequence, what it books as lost and as back-off) is then exact, however
    slowly a shared CPU starts the three child processes."""
    counter = tmp_path / "n"
    counter.write_text("2")
    child = _fake_child(
        "import sys\n"
        "p = sys.argv[1]\n"
        "n = int(open(p).read())\n"
        "open(p, 'w').write(str(n - 1))\n"
        "sys.exit(3 if n > 0 else 0)\n",
        str(counter),
    )
    now = [1_000_000.0]
    sleeps = []

    def clock():
        now[0] += 0.001
        return now[0]

    def sleep(seconds):
        sleeps.append(seconds)
        now[0] += seconds

    def on_spawn(attempt, process):
        now[0] += 10.0

    sup = Supervisor(
        child, log_dir=str(tmp_path), checkpoint_dir=str(tmp_path / "ckpt"),
        max_restarts=5, backoff_base_s=0.5,
        sleep=sleep, clock=clock, on_spawn=on_spawn,
    )
    assert sup.run() == 0
    assert sleeps == [0.5, 1.0]  # deterministic exponential backoff
    doc = load_chain(str(tmp_path))
    assert doc["outcome"] == "ok" and doc["kind"] == "supervisor"
    chain = doc["notes"]["chain"]
    attempts = chain["attempts"]
    assert [a["restart_reason"] for a in attempts] == [
        "backend_unreachable", "backend_unreachable", None,
    ]
    assert [a["exit_code"] for a in attempts] == [3, 3, 0]
    # Each attempt is its 10 s and the clock reads around it; a child that
    # died before its first heartbeat is lost whole, the last loses nothing.
    assert [a["wall_s"] for a in attempts] == pytest.approx([10.0] * 3, abs=0.01)
    assert [a["lost_s"] for a in attempts] == pytest.approx(
        [10.0, 10.0, 0.0], abs=0.01
    )
    metrics = doc["metrics"]
    assert metrics["attempts"] == 3.0
    assert metrics["goodput/backoff_s"] == pytest.approx(1.5, abs=0.01)
    assert metrics["goodput/lost_s"] == pytest.approx(20.0, abs=0.02)
    goodput = chain["goodput"]
    assert goodput["attempts_wall_s"] == pytest.approx(30.0, abs=0.03)
    # 30 s of attempts and 1.5 s of back-off in a wall of 31.5 s and the
    # bookkeeping's reads: the production criterion (99%) holds exactly.
    assert goodput["wall_s"] == pytest.approx(31.5, abs=0.2)
    assert metrics["goodput_frac"] == pytest.approx(10.0 / 31.5, abs=0.005)
    assert verify_chain(doc, min_accounted=0.99, expect_attempts=3) == []
    # The sentinel reads it natively: goodput_frac surfaces as a metric.
    from sav_tpu.obs.manifest import normalize_run_record

    rec = normalize_run_record(doc, label="supervisor.json")
    assert rec.ok and "goodput_frac" in rec.metrics


def test_supervisor_usage_error_is_terminal(tmp_path):
    sup, rc, sleeps = _run_supervisor(
        tmp_path, _fake_child("import sys; sys.exit(2)"), max_restarts=5
    )
    assert rc == 2 and sleeps == []
    doc = load_chain(str(tmp_path))
    assert doc["outcome"] == "error"
    assert len(doc["notes"]["chain"]["attempts"]) == 1


def test_supervisor_budget_exhaustion(tmp_path):
    sup, rc, sleeps = _run_supervisor(
        tmp_path, _fake_child("import sys; sys.exit(7)"),
        max_restarts=2, backoff_base_s=0.1,
    )
    assert rc == 7 and len(sleeps) == 2
    doc = load_chain(str(tmp_path))
    assert doc["outcome"] == "error"
    assert "budget exhausted" in doc["error"]
    assert len(doc["notes"]["chain"]["attempts"]) == 3
    assert verify_chain(doc)  # a failed chain must NOT verify


def test_supervisor_classifies_signal_kills(tmp_path):
    counter = tmp_path / "n"
    counter.write_text("1")
    child = _fake_child(
        "import os, sys, signal\n"
        "p = sys.argv[1]\n"
        "n = int(open(p).read())\n"
        "open(p, 'w').write(str(n - 1))\n"
        "if n > 0:\n"
        "    os.kill(os.getpid(), signal.SIGKILL)\n",
        str(counter),
    )
    sup, rc, _ = _run_supervisor(
        tmp_path, child, max_restarts=2, backoff_base_s=0.05
    )
    assert rc == 0
    attempts = load_chain(str(tmp_path))["notes"]["chain"]["attempts"]
    assert attempts[0]["restart_reason"] == "killed:SIGKILL"
    assert attempts[0]["exit_code"] == -9


def test_decide_skip_ignores_stale_incident(tmp_path):
    """A leftover incident bundle from an earlier run sharing the log
    dir must not arm a rewind-and-skip: skipping its (good) batch would
    shift the schedule while the real bad batch replays forever."""
    sup = Supervisor(
        ["true"], log_dir=str(tmp_path), checkpoint_dir=None
    )
    d = tmp_path / "incidents" / "step_00000025"
    d.mkdir(parents=True)
    stale_t = time.time() - 3600.0
    (d / "incident.json").write_text(json.dumps(
        {"step": 25, "trigger": "nonfinite", "created_unix": stale_t}
    ))
    # Attempt started NOW: the hour-old bundle is stale — no skip.
    assert sup._decide_skip("nonfinite", time.time() - 5.0) == []
    assert sup.skipped_steps == set()
    # A bundle created during the attempt IS the decision source.
    (d / "incident.json").write_text(json.dumps(
        {"step": 25, "trigger": "nonfinite", "created_unix": time.time()}
    ))
    assert sup._decide_skip("nonfinite", time.time() - 5.0) == [25]
    assert sup.skipped_steps == {25}
    # ...and once per chain: a second nonfinite at the same step does
    # not re-arm it.
    assert sup._decide_skip("nonfinite", time.time() - 5.0) == []


def test_verify_chain_flags_low_accounting():
    doc = {
        "outcome": "ok",
        "metrics": {"goodput_frac": 0.5, "accounted_frac": 0.5},
        "notes": {"chain": {"attempts": [
            {"attempt": 1, "restart_reason": "hang", "exit_code": 4},
            {"attempt": 2, "restart_reason": None, "exit_code": 0},
        ]}},
    }
    problems = verify_chain(doc, min_accounted=0.99)
    assert any("accounting" in p for p in problems)
    doc["metrics"]["accounted_frac"] = 0.995
    assert verify_chain(doc, min_accounted=0.99) == []
    assert verify_chain(doc, expect_attempts=3)  # wrong attempt count


# ------------------------------------------------- trainer-level integration


def _smoke_config(tmp_path, **overrides):
    from sav_tpu.train import TrainConfig

    base = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=8,
        num_train_images=8 * 1000,  # long epoch: cadence saves, not epoch
        num_epochs=1,
        warmup_epochs=0,
        base_lr=1e-3,
        lr_scaling_divisor=8,
        transpose_images=False,
        log_every_steps=2,
        checkpoint_dir=str(tmp_path / "ckpt"),
        seed=0,
    )
    base.update(overrides)
    return TrainConfig(**base)


def _trainer(config):
    import jax.numpy as jnp

    from sav_tpu.models import create_model
    from sav_tpu.train import Trainer

    model = create_model(
        config.model_name, num_classes=config.num_classes,
        dtype=jnp.float32, num_layers=2, embed_dim=64, num_heads=4,
    )
    return Trainer(config, model=model)


def _synth_iter(start_step=0):
    return synth_resumable_iterator(
        seed=0, start_step=start_step, batch_size=8, image_size=32,
        num_classes=10,
    )


def test_step_cadence_layout_probe_and_torn_fallback(tmp_path, devices, caplog):
    """One fit, three elasticity contracts: (a) checkpoint_every_steps
    counts steps SINCE THE LAST SAVE, quantized up to the next log
    boundary (N=3 with log_every=2 saves at 4 and 8 — a step-number
    modulo would misalign to lcm(3,2)=6 and save at 6 only) + writes the
    resume stamp; (b) a fresh trainer restores the per-leaf checkpoint and
    its probe has nothing to say (the mirror image, a flat checkpoint, is
    the next test); (c) when the newest step is torn,
    restore falls back to the previous committed one."""
    import jax

    cfg = _smoke_config(tmp_path, checkpoint_every_steps=3)
    tr = _trainer(cfg)
    state, _ = tr.fit(_synth_iter(), num_steps=10)
    assert tr.checkpointer.all_steps() == [4, 8, 10]
    stamp = json.load(open(tmp_path / "ckpt" / "resume.json"))
    assert stamp["step"] == 10 and stamp["step_in_epoch"] == 10
    assert stamp["feeder_position"] == 10 and "fold_in" in str(stamp["rng"])
    assert tr.checkpointer.opt_layout() == {"fused": False, "ema": False}
    tr.checkpointer.close()

    # (b) the probe passes the layout every trainer builds, silently.
    tr2 = _trainer(_smoke_config(tmp_path, checkpoint_every_steps=3))
    with caplog.at_level("WARNING"):
        st = tr2.restore_or_init()
    assert int(jax.device_get(st.step)) == 10
    assert "checkpoint" not in caplog.text
    # The restored optimizer state actually steps.
    rng = jax.random.fold_in(jax.random.PRNGKey(0), 1)
    st2, m = tr2.train_step(st, next(_synth_iter(10)), rng)
    assert np.isfinite(float(jax.device_get(m["loss"])))
    tr2.checkpointer.close()

    # (c) torn newest: gut step 10's payload; restore falls back to 8.
    import shutil

    step_dir = tmp_path / "ckpt" / "10"
    for child in step_dir.iterdir():
        shutil.rmtree(child) if child.is_dir() else child.unlink()
    tr3 = _trainer(_smoke_config(tmp_path, checkpoint_every_steps=3))
    st3 = tr3.restore_or_init()
    assert int(jax.device_get(st3.step)) == 8
    tr3.checkpointer.close()


def test_flat_checkpoint_is_refused_by_name(tmp_path, devices):
    """The mirror image of (b) above: a checkpoint whose Adam moments are
    one flat vector each (what ``fused_optimizer=True`` wrote, and auto on
    a pure-data mesh before PR 29) is refused before any template is
    built, by an error that names the layout and the last commit that
    reads it; a fit's manifest says ``per_leaf``, the one layout there is."""
    from sav_tpu.obs.manifest import RunManifest
    from sav_tpu.train.optimizer import make_optimizer

    tr = _trainer(_smoke_config(tmp_path))
    state = tr.init_state()
    flat = make_optimizer(tr.schedule, fused=True).init(state.params)
    tr.checkpointer.save(2, state.replace(step=state.step + 2, opt_state=flat))
    tr.checkpointer.wait()
    assert tr.checkpointer.opt_layout() == {"fused": True, "ema": False}
    tr.checkpointer.close()

    tr2 = _trainer(_smoke_config(tmp_path))
    with pytest.raises(ValueError, match="flat Adam moments.*e12e221"):
        tr2.restore_or_init()
    with pytest.raises(ValueError, match="per-leaf layout only"):
        tr2.fit(_synth_iter(), num_steps=4)  # fit restores: refused too
    tr2.checkpointer.close()

    manifest = RunManifest(str(tmp_path / "manifest.json"), kind="train")
    manifest.begin()
    tr3 = _trainer(_smoke_config(tmp_path, checkpoint_dir=None))
    tr3.fit(_synth_iter(), num_steps=2, manifest=manifest)
    notes = json.load(open(tmp_path / "manifest.json"))["notes"]
    assert notes["layout"]["optimizer_layout"] == "per_leaf"


def test_secs_cadence_dedupe_and_crash_drain(tmp_path, devices):
    """checkpoint_every_secs=0 saves at every log boundary without
    double-saving a step the epoch/step cadence already took, and
    fit()'s finally drains in-flight saves (bounded wait) on the crash
    path too."""
    from sav_tpu.train.checkpoint import Checkpointer

    calls = {"save": [], "wait": 0}

    class SpyCheckpointer(Checkpointer):
        def save(self, step, state):
            calls["save"].append(step)
            super().save(step, state)

        def wait(self, timeout_s=None):
            calls["wait"] += 1
            return super().wait(timeout_s=timeout_s)

    cfg = _smoke_config(tmp_path, checkpoint_every_secs=0.0)
    from sav_tpu.train import Trainer  # noqa: F401  (import surface)

    tr = _trainer(cfg)
    tr.checkpointer = SpyCheckpointer(cfg.checkpoint_dir)
    tr.fit(_synth_iter(), num_steps=6)
    # Log boundary every 2 steps → saves at 2, 4, 6; the final-step save
    # is deduped (6 was already saved by the cadence), no step repeats.
    assert calls["save"] == [2, 4, 6]
    assert calls["wait"] >= 1
    calls["save"].clear()
    calls["wait"] = 0

    # Crash path: the iterator explodes mid-run; the finally must still
    # drain the checkpointer so the step-2 save commits.
    def exploding():
        it = _synth_iter(6)
        for i, batch in enumerate(it):
            if i == 3:
                raise RuntimeError("boom")
            yield batch

    cfg2 = _smoke_config(tmp_path, checkpoint_every_secs=0.0)
    tr2 = _trainer(cfg2)
    tr2.checkpointer = SpyCheckpointer(cfg2.checkpoint_dir)
    with pytest.raises(RuntimeError, match="boom"):
        tr2.fit(exploding(), num_steps=20)
    assert calls["wait"] >= 1
    assert set(calls["save"]) <= {8}  # only log-boundary saves happened
    tr2.checkpointer.close()


def test_checkpointer_bounded_wait_times_out():
    from sav_tpu.train.checkpoint import Checkpointer

    ckpt = Checkpointer.__new__(Checkpointer)  # no orbax manager needed

    class _StuckMgr:
        def wait_until_finished(self):
            time.sleep(10.0)

    ckpt._mgr = _StuckMgr()
    t0 = time.perf_counter()
    assert ckpt.wait(timeout_s=0.2) is False
    assert time.perf_counter() - t0 < 2.0


def test_detect_opt_layout_paths():
    from sav_tpu.train.checkpoint import detect_opt_layout

    per_leaf = [("opt_state", "1", "mu", "dense", "kernel"),
                ("opt_state", "1", "nu", "dense", "kernel")]
    flat = [("opt_state", "1", "0", "mu"), ("opt_state", "1", "0", "nu")]
    ema = flat + [("opt_state", "3", "ema", "dense", "kernel")]
    assert detect_opt_layout(per_leaf) == {"fused": False, "ema": False}
    assert detect_opt_layout(flat) == {"fused": True, "ema": False}
    assert detect_opt_layout(ema) == {"fused": True, "ema": True}
    assert detect_opt_layout([("opt_state", "0")])["fused"] is None


def test_watchdog_drains_checkpointer_before_exit():
    """The exit-4 path waits (bounded) for in-flight async saves before
    os._exit abandons them — and a wedged checkpointer cannot stall the
    guaranteed-exit contract."""
    from sav_tpu.obs.watchdog import HangWatchdog

    events = []

    class _Ckpt:
        def wait(self, timeout_s=None):
            events.append(("wait", timeout_s))
            return True

    wd = HangWatchdog(
        0.2, poll_s=0.05, checkpointer=_Ckpt(), stream=StringIO(),
        exit_fn=lambda code: events.append(("exit", code)),
    )
    wd.start()
    assert wd.fired.wait(5.0)
    wd.stop()
    assert events[0][0] == "wait" and events[0][1] is not None
    assert events[-1] == ("exit", 4)


# ----------------------------------------------------------- real-child e2e


def _child_cmd(tmp_path, steps=20, extra=()):
    return [
        sys.executable, TRAIN_PY,
        "--preset", "elastic_smoke", "--synth-data", "--platform", "cpu",
        "--steps", str(steps), "--seed", "0",
        "-c", str(tmp_path / "ckpt"), "--log-dir", str(tmp_path),
        "--checkpoint-every-steps", "4",
        *extra,
    ]


def _wait_for(predicate, timeout_s, what):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if predicate():
            return
        time.sleep(0.2)
    raise AssertionError(f"timed out waiting for {what}")


def _heartbeat_step(log_dir, pid):
    from sav_tpu.train.supervisor import read_attempt_heartbeats

    beats = read_attempt_heartbeats(str(log_dir), pid)
    return beats[-1]["step"] if beats else None


def _metrics_lines(log_dir):
    out = []
    with open(os.path.join(str(log_dir), "metrics.jsonl")) as f:
        for line in f:
            line = line.strip()
            if line:
                try:
                    out.append(json.loads(line))
                except json.JSONDecodeError:
                    pass
    return out


def test_sigkill_resume_is_step_exact(tmp_path):
    """Kill a real training child mid-epoch; the rerun must resume from
    the committed checkpoint with the SAME rng recipe and the SAME next
    batch (recorder blake2b fingerprint vs the recomputed uninterrupted
    schedule), and the re-logged overlap windows must reproduce the
    killed run's losses bit-for-bit."""
    child = subprocess.Popen(
        _child_cmd(tmp_path), stdout=subprocess.DEVNULL,
        stderr=subprocess.DEVNULL,
    )
    try:
        _wait_for(
            lambda: (latest_checkpoint_step(str(tmp_path / "ckpt")) or 0) >= 4
            and (_heartbeat_step(tmp_path, child.pid) or 0) >= 10,
            timeout_s=180,
            what="a committed checkpoint and step >= 10",
        )
        os.kill(child.pid, signal.SIGKILL)
    finally:
        child.wait()
    assert child.returncode == -9
    resumed_from = latest_checkpoint_step(str(tmp_path / "ckpt"))
    assert resumed_from and resumed_from >= 4
    killed_losses = {
        int(r["step"]): r["loss"] for r in _metrics_lines(tmp_path)
        if "loss" in r
    }
    assert killed_losses, "the killed run logged no windows"

    rerun = subprocess.run(
        _child_cmd(tmp_path), capture_output=True, text=True, timeout=300
    )
    assert rerun.returncode == 0, rerun.stderr[-2000:]
    manifest = json.load(open(tmp_path / "manifest.json"))
    assert manifest["outcome"] == "ok"
    resume = manifest["notes"]["resume"]
    # Step-exact: resumed from a committed checkpoint, not epoch 0...
    assert resume["from_step"] >= resumed_from > 0
    assert "fold_in" in resume["rng"]  # same rng: derivation is (seed, step)
    # ...and the first batch is the uninterrupted schedule's, bit-for-bit.
    expected = batch_fingerprint(synth_batch(
        seed=0, position=resume["from_step"] + 1, batch_size=8,
        image_size=32, num_classes=10,
    ))["hash"]
    assert resume["next_batch_hash"] == expected

    # Loss continues: windows logged by BOTH runs (between the resume
    # point and the kill) must agree exactly — same state, same batches,
    # same rng. metrics.jsonl appends, so later lines are the rerun's.
    all_lines = _metrics_lines(tmp_path)
    rerun_losses = {}
    for r in all_lines:
        if "loss" in r:
            rerun_losses[int(r["step"])] = r["loss"]  # last occurrence wins
    overlap = [
        s for s in killed_losses
        if s > resume["from_step"] and s in rerun_losses
    ]
    assert overlap, "no overlap windows — kill/checkpoint cadence broken"
    for s in overlap:
        assert rerun_losses[s] == killed_losses[s], (
            f"loss at step {s} not bit-continued"
        )
    assert max(rerun_losses) == 20  # ran to completion


def test_chaos_soak_smoke_two_kills_one_nan(tmp_path):
    """The acceptance-criteria soak, CPU-scaled: 2 injected SIGKILLs + 1
    planted NaN in one supervised run. The harness itself verifies the
    chain (≥99% accounting, step-exact resume hashes, NaN skipped
    exactly once, loss bit-continued vs an uninterrupted reference);
    this test asserts the verification PASSED and the render tools read
    the chain."""
    proc = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "tools", "chaos_soak.py"),
            "--log-dir", str(tmp_path),
            "--steps", "24",
            "--kill-at-steps", "6,14",
            "--nan-at-step", "18",
            "--checkpoint-every-steps", "4",
            "--backoff", "0.2",
            "--json",
        ],
        capture_output=True, text=True, timeout=420,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr[-2000:]
    summary = json.loads(proc.stdout)
    assert summary["verified"], summary["problems"]
    assert summary["attempts"] == 4  # 1 + 2 kills + 1 nonfinite restart
    assert summary["restart_reasons"].count("killed:SIGKILL") == 2
    assert summary["restart_reasons"].count("nonfinite") == 1
    assert summary["skipped_steps"] == [18]
    assert summary["accounted_frac"] >= 0.99
    assert 0.0 < summary["goodput_frac"] < 1.0
    assert summary["resume_hash_checks"] >= 2
    assert summary["loss_continuity"]["max_abs_diff"] == 0.0
    assert summary["loss_continuity"]["final_step"] == 24

    # The chain renders through run_report (--chain auto-detects) and
    # fleet_status folds the supervisor headline into the fleet view.
    report = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_report.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert report.returncode == 0
    assert "Supervisor chain: 4 attempt(s), outcome=ok" in report.stdout
    assert "rewind-and-skip decided here: step(s) [18]" in report.stdout
    assert "skip set armed: step(s) [18]" in report.stdout
    assert "killed:SIGKILL" in report.stdout
    fleet = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "fleet_status.py"),
         str(tmp_path)],
        capture_output=True, text=True, timeout=60,
    )
    assert fleet.returncode == 0
    assert "Supervisor chain: 4 attempt(s)" in fleet.stdout

    # Single-attempt degradation: the reference run inside the soak dir
    # was never supervised — run_report must degrade gracefully there.
    ref = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tools", "run_report.py"),
         str(tmp_path / "reference"), "--chain"],
        capture_output=True, text=True, timeout=60,
    )
    assert ref.returncode == 0
    assert "no supervisor chain" in ref.stdout


# ------------------------------------------------------- CLI + sentinel


def test_supervise_requires_checkpoint_dir():
    proc = subprocess.run(
        [sys.executable, TRAIN_PY, "--supervise", "--synth-data",
         "--platform", "cpu", "--steps", "2"],
        capture_output=True, text=True, timeout=60,
    )
    assert proc.returncode == 2
    assert "needs -c" in proc.stderr


def test_sentinel_gates_goodput_frac(tmp_path):
    """regression_sentinel scores the supervisor chain's goodput_frac
    (higher-better): a collapse past the MAD gate regresses; healthy
    history stays clean; unsupervised records are skipped, not
    zero-filled."""
    from sav_tpu.obs.manifest import MANIFEST_SCHEMA

    def write(name, gf):
        doc = {
            "schema": MANIFEST_SCHEMA, "kind": "supervisor",
            "outcome": "ok", "metrics": {"goodput_frac": gf},
            "notes": {}, "error": None,
        }
        (tmp_path / name).write_text(json.dumps(doc))

    sentinel = os.path.join(ROOT, "tools", "regression_sentinel.py")
    write("r1.json", 0.991)
    write("r2.json", 0.993)
    write("r3.json", 0.992)
    clean = subprocess.run(
        [sys.executable, sentinel, "--metric", "goodput_frac", "--",
         str(tmp_path / "r1.json"), str(tmp_path / "r2.json"),
         str(tmp_path / "r3.json")],
        capture_output=True, text=True, timeout=60,
    )
    assert clean.returncode == 0, clean.stdout + clean.stderr
    write("r4.json", 0.62)  # preemptions started eating real wall time
    flagged = subprocess.run(
        [sys.executable, sentinel, "--metric", "goodput_frac", "--json",
         "--", str(tmp_path / "r1.json"), str(tmp_path / "r2.json"),
         str(tmp_path / "r3.json"), str(tmp_path / "r4.json")],
        capture_output=True, text=True, timeout=60,
    )
    assert flagged.returncode == 1
    payload = json.loads(flagged.stdout)
    verdicts = {v["metric"]: v for v in payload["verdicts"]}
    assert verdicts["goodput_frac"]["regressed"]
