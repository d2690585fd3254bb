"""Run manifests (ISSUE 4): lifecycle, outcome taxonomy, exception
classification, atomic/idempotent finalize semantics, and the crash-path
integrations — the hang watchdog finalizes ``outcome: "hang"`` before
exit 4, the device check finalizes ``backend_unreachable`` before exit
3, and bench.py's give-up path emits a final parseable JSON line."""

import io
import json
import os
import threading

import pytest

from sav_tpu.obs.manifest import (
    OUTCOMES,
    RunManifest,
    classify_exception,
    environment_fingerprint,
)


def _manifest(tmp_path, **kwargs):
    kwargs.setdefault("kind", "train")
    return RunManifest(str(tmp_path / "manifest.json"), **kwargs)


# ------------------------------------------------------------- lifecycle


def test_begin_writes_running_record_with_fingerprint(tmp_path):
    m = _manifest(tmp_path, argv=["--steps", "4"])
    path = m.begin()
    assert path == m.path and os.path.exists(path)
    doc = RunManifest.load(path)
    assert doc["outcome"] == "running"
    assert doc["kind"] == "train"
    assert doc["argv"] == ["--steps", "4"]
    env = doc["env"]
    assert env["python"] and env["hostname"]
    # The repo is a git checkout; the fingerprint must carry the sha.
    assert env["git_sha"] and len(env["git_sha"]) == 40


def test_fingerprint_never_inits_jax_devices():
    """The unreachable-backend path is exactly where the fingerprint must
    still work — it may read jax.__version__ but never touch devices
    (the device check has not run yet). Guard: the function is callable
    and returns without accelerator facts."""
    env = environment_fingerprint()
    assert "device_kind" not in env and "n_devices" not in env


def test_notes_and_metrics_accrete(tmp_path):
    m = _manifest(tmp_path)
    m.begin()
    m.note("cost_model", {"source": "analytic"})
    m.set_metrics({"goodput/mfu": 0.4})
    m.set_metrics({"goodput/wall_s": 10.0})
    doc = RunManifest.load(m.path)
    assert doc["notes"]["cost_model"] == {"source": "analytic"}
    assert doc["metrics"] == {"goodput/mfu": 0.4, "goodput/wall_s": 10.0}


def test_finalize_is_first_wins(tmp_path):
    """The watchdog thread and a crashing main thread can both reach
    finalize; the first outcome must stick (a late 'error' cannot
    overwrite 'hang')."""
    m = _manifest(tmp_path)
    m.begin()
    assert m.finalize("hang", exit_code=4) is True
    assert m.finalize("error", error="late") is False
    doc = RunManifest.load(m.path)
    assert doc["outcome"] == "hang"
    assert doc["exit_code"] == 4
    assert doc["error"] is None
    assert doc["finalized_unix"] is not None


def test_finalize_rejects_unknown_outcome(tmp_path):
    m = _manifest(tmp_path)
    with pytest.raises(ValueError):
        m.finalize("exploded")


def test_move_to_rehomes_the_file(tmp_path):
    m = _manifest(tmp_path)
    m.begin()
    old = m.path
    new = str(tmp_path / "resolved" / "manifest.json")
    m.move_to(new)
    m.finalize("ok")
    assert not os.path.exists(old)
    assert RunManifest.load(new)["outcome"] == "ok"


def test_disabled_manifest_stops_writing(tmp_path):
    m = _manifest(tmp_path)
    m.begin()
    m.disable()
    m.finalize("error", error="from process 3")
    # The on-disk record keeps process 0's view ('running' here).
    assert RunManifest.load(m.path)["outcome"] == "running"


def test_write_failure_never_raises(tmp_path):
    m = RunManifest(
        str(tmp_path / "dir_as_file"), kind="bench"
    )
    os.makedirs(str(tmp_path / "dir_as_file"))  # open() will fail
    assert m.begin() is None
    assert m.finalize("ok") is True  # state updates even if I/O fails


def test_concurrent_finalize_single_winner(tmp_path):
    m = _manifest(tmp_path)
    m.begin()
    wins = []
    barrier = threading.Barrier(8)

    def race(outcome):
        barrier.wait()
        if m.finalize(outcome):
            wins.append(outcome)

    threads = [
        threading.Thread(target=race, args=(o,))
        for o in ("hang", "error", "ok", "oom") * 2
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(wins) == 1
    assert RunManifest.load(m.path)["outcome"] == wins[0]


# --------------------------------------------------------- classification


def test_classify_exception_taxonomy():
    # The executable's refusal of a drifted batch is a plain error: there
    # is no outcome of its own for it.
    assert classify_exception(
        TypeError("Argument types differ from the types for which this "
                  "computation was compiled.")
    ) == "error"
    assert "retrace" not in OUTCOMES
    assert classify_exception(
        RuntimeError("RESOURCE_EXHAUSTED: Out of memory allocating ...")
    ) == "oom"
    assert classify_exception(MemoryError()) == "oom"
    assert classify_exception(ValueError("bad shape")) == "error"
    # NaN/Inf deaths are their own outcome (ISSUE 5): the trainer's
    # debug_nans assert, any "non-finite" message, and checkify's
    # nan_checks error all classify as nonfinite — never as plain error,
    # so the sentinel can list them as scored-never without scraping text.
    assert classify_exception(
        FloatingPointError("non-finite values in metrics at step 7")
    ) == "nonfinite"
    assert classify_exception(
        RuntimeError("non-finite values in eval metrics: ['eval_loss']")
    ) == "nonfinite"
    assert classify_exception(
        ValueError("nan generated by primitive: sub.")
    ) == "nonfinite"
    for outcome in ("oom", "nonfinite", "error"):
        assert outcome in OUTCOMES


# -------------------------------------------- crash-path integrations


def test_watchdog_fire_finalizes_hang_before_exit(tmp_path):
    """ISSUE 4 crash-path criterion: HangWatchdog._fire finalizes the
    manifest with outcome 'hang' BEFORE exiting 4 (os._exit skips every
    finally, so firing is the record's only chance)."""
    from sav_tpu.obs.goodput import GoodputLedger
    from sav_tpu.obs.watchdog import WATCHDOG_EXIT_CODE, HangWatchdog

    m = _manifest(tmp_path)
    m.begin()
    ledger = GoodputLedger()
    ledger.note_window(2, 0.5)
    observed = {}

    def exit_fn(code):
        # Order proof: at exit time the on-disk record must already say
        # 'hang' — read it inside the fake exit.
        observed["code"] = code
        observed["doc"] = RunManifest.load(m.path)

    watchdog = HangWatchdog(
        0.2, ledger=ledger, manifest=m, tag="mf-watchdog",
        exit_fn=exit_fn, stream=io.StringIO(), poll_s=0.05,
    )
    watchdog.start()
    try:
        assert watchdog.fired.wait(timeout=5.0), "watchdog never fired"
    finally:
        watchdog.stop()
    assert observed["code"] == WATCHDOG_EXIT_CODE
    doc = observed["doc"]
    assert doc["outcome"] == "hang"
    assert doc["exit_code"] == WATCHDOG_EXIT_CODE
    assert "no step completed" in doc["error"]
    # The goodput ledger's view rides along (partial-run telemetry).
    assert doc["metrics"]["goodput/step_s"] > 0


def test_bench_abort_emits_parseable_json_line(tmp_path, capsys):
    """The give-up path ends with one parseable stdout JSON line carrying
    the outcome + what the device check found + the manifest pointer (no
    prose-only stderr / parsed: null records). The finalize half of the
    contract is pinned in tests/test_device_check.py."""
    import argparse

    import bench
    from sav_tpu.utils.device_check import BackendUnreachableError

    m = RunManifest(str(tmp_path / "manifest.json"), kind="bench")
    m.begin()
    args = argparse.Namespace(model="deit_s_patch16", batch_size=256)
    error = BackendUnreachableError("expected a TPU, found platform 'cpu'")
    rc = bench._abort_backend_unreachable(args, m, error)
    assert rc == 3  # the device_check abort contract is preserved
    captured = capsys.readouterr()
    lines = captured.out.strip().splitlines()
    assert len(lines) == 1
    record = json.loads(lines[-1])
    assert record["outcome"] == "backend_unreachable"
    assert record["value"] is None
    assert "expected a TPU" in record["device_check"]["error"]
    assert record["manifest"] == m.path
    # The stderr abort line wrapper scripts grep for.
    assert captured.err.startswith(
        "bench: accelerator backend unreachable: expected a TPU"
    )
    doc = RunManifest.load(m.path)
    assert doc["outcome"] == "backend_unreachable"
    assert doc["exit_code"] == 3
