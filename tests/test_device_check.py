"""The in-process device check (sav_tpu/utils/device_check.py) and its
exit-3 / ``backend_unreachable`` contract.

The suite itself runs with ``jax_platforms`` pinned to ``cpu``
(tests/conftest.py), which is the "CPU asked for in so many words" case;
the other cases fake what jax reports.
"""

import json
import os
import subprocess
import sys

import pytest

import sav_tpu.utils.device_check as dc
from sav_tpu.obs.manifest import RunManifest


class _Device:
    def __init__(self, platform, kind="fake"):
        self.platform = platform
        self.device_kind = kind


def _fake_jax(monkeypatch, *, platform=None, platforms_config=None,
              error=None):
    import jax

    def devices():
        if error is not None:
            raise error
        return [_Device(platform)]

    monkeypatch.setattr(jax, "devices", devices)
    monkeypatch.setattr(dc, "cpu_requested", lambda: platforms_config == "cpu")


def test_module_import_stays_off_jax():
    # The serve pool's parent imports the exception from here and must
    # never touch the backend: a parent that did would hold the chip.
    code = (
        "import sys; import sav_tpu.utils.device_check; "
        "import sav_tpu.serve.fleet; print('jax' in sys.modules)"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        check=True,
    )
    assert out.stdout.strip() == "False"


def test_cpu_requested_reads_the_pinned_config():
    # conftest pinned jax_platforms=cpu through jax.config.
    assert dc.cpu_requested()


@pytest.mark.parametrize(
    "value,expected",
    [("cpu", True), (" CPU ", True), ("", False), (None, False),
     ("tpu", False), ("tpu,cpu", False)],
)
def test_cpu_requested_only_for_cpu_alone(monkeypatch, value, expected):
    import jax

    monkeypatch.setattr(
        type(jax.config), "jax_platforms", property(lambda self: value)
    )
    assert dc.cpu_requested() is expected


def test_check_accepts_the_suite_own_cpu():
    assert dc.check_accelerator() == "cpu"


def test_check_accepts_tpu(monkeypatch):
    _fake_jax(monkeypatch, platform="tpu")
    assert dc.check_accelerator() == "tpu"


def test_cpu_nobody_asked_for_is_unreachable(monkeypatch):
    # The silent fallback: jax found no chip and came up on the CPU.
    _fake_jax(monkeypatch, platform="cpu", platforms_config=None)
    with pytest.raises(dc.BackendUnreachableError, match="expected a TPU"):
        dc.check_accelerator()


def test_other_accelerator_is_unreachable_even_when_cpu_requested(
    monkeypatch
):
    _fake_jax(monkeypatch, platform="gpu", platforms_config="cpu")
    with pytest.raises(dc.BackendUnreachableError):
        dc.check_accelerator()


def test_backend_init_failure_is_unreachable(monkeypatch):
    # A chip held by another process: jax.devices() raises RuntimeError.
    _fake_jax(
        monkeypatch,
        error=RuntimeError("Unable to initialize backend 'tpu': in use"),
    )
    with pytest.raises(dc.BackendUnreachableError, match="in use"):
        dc.check_accelerator()


def test_require_accelerator_returns_platform_when_present(monkeypatch):
    _fake_jax(monkeypatch, platform="tpu")
    assert dc.require_accelerator("test") == "tpu"


def test_require_accelerator_abort_contract(monkeypatch, capsys):
    _fake_jax(monkeypatch, platform="cpu", platforms_config=None)
    with pytest.raises(SystemExit) as exc:
        dc.require_accelerator("train")
    assert exc.value.code == dc.EXIT_BACKEND_UNREACHABLE == 3
    err = capsys.readouterr().err
    # The abort line wrapper scripts grep for.
    assert err.startswith("train: accelerator backend unreachable: ")
    assert err.rstrip().endswith("; aborting")


def test_require_accelerator_finalizes_backend_unreachable(
    tmp_path, monkeypatch
):
    m = RunManifest(str(tmp_path / "manifest.json"), kind="train")
    m.begin()
    _fake_jax(monkeypatch, platform="cpu", platforms_config=None)
    with pytest.raises(SystemExit) as exc:
        dc.require_accelerator("test", manifest=m)
    assert exc.value.code == 3
    doc = RunManifest.load(m.path)
    assert doc["outcome"] == "backend_unreachable"
    assert doc["exit_code"] == 3
    assert "expected a TPU" in doc["notes"]["device_check"]["error"]


def test_exit_code_matches_the_supervisor_contract():
    from sav_tpu.train.supervisor import EXIT_BACKEND, classify_exit

    assert dc.EXIT_BACKEND_UNREACHABLE == EXIT_BACKEND
    assert classify_exit(EXIT_BACKEND, None) == "backend_unreachable"


def _run_cli(argv):
    """An entry point in a child with NO platform request in its
    environment — jax then finds no chip here and lands on the CPU by
    itself, which is exactly the silent fallback."""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["TPU_LOG_DIR"] = "disabled"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return subprocess.run(
        [sys.executable] + argv, capture_output=True, text=True, env=env,
        cwd=root, timeout=300,
    )


@pytest.mark.parametrize(
    "entry",
    ["train", "bench", "serve_bench"],
)
def test_entry_points_exit_3_without_a_tpu(tmp_path, entry):
    manifest = tmp_path / "manifest.json"
    argv = {
        "train": [
            "train.py", "--synth-data", "-m", "vit_ti_patch16",
            "--image-size", "32", "--steps", "1",
            "--log-dir", str(tmp_path),
        ],
        "bench": ["bench.py", "--manifest", str(manifest)],
        "serve_bench": [
            "tools/serve_bench.py", "--manifest", str(manifest),
        ],
    }[entry]
    proc = _run_cli(argv)
    assert proc.returncode == 3, proc.stderr[-2000:]
    assert "accelerator backend unreachable" in proc.stderr
    doc = json.loads(manifest.read_text())
    assert doc["outcome"] == "backend_unreachable"
    assert doc["exit_code"] == 3
    if entry != "train":
        # bench.py / serve_bench.py still end in ONE parseable stdout line.
        record = json.loads(proc.stdout.strip().splitlines()[-1])
        assert record["outcome"] == "backend_unreachable"
        assert record["manifest"] == str(manifest)


def test_train_platform_cpu_flag_is_a_request(tmp_path):
    proc = _run_cli(
        [
            "train.py", "--platform", "cpu", "--synth-data",
            "-m", "vit_ti_patch16", "--image-size", "32", "--steps", "1",
            "--batch-size", "8", "--log-dir", str(tmp_path),
        ],
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    doc = json.loads((tmp_path / "manifest.json").read_text())
    assert doc["outcome"] == "ok"


def test_serve_pool_fails_promptly_when_a_replica_finds_no_chip(tmp_path):
    """Two replicas, one chip: the replica that cannot claim one exits 3;
    the serve-mode supervisor treats that as terminal (no restart budget
    burnt) and the pool raises at once, saying why."""
    import time

    from sav_tpu.serve.fleet import ReplicaPool

    pool = ReplicaPool(
        replicas=2,
        child_argv_fn=lambda rank: [sys.executable, "-c", "import sys; sys.exit(3)"],
        log_dir=str(tmp_path),
        max_restarts=4,
        backoff_base_s=5.0,
    )
    t0 = time.monotonic()
    with pool:
        with pytest.raises(dc.BackendUnreachableError, match="one process"):
            pool.wait_ready(60.0)
    assert time.monotonic() - t0 < 30.0
    status = pool.status()
    assert status["restarts"] == 0
    assert status["ranks"]["0"]["exit_code"] in (3, None)


def test_training_supervisor_still_restarts_exit_3(tmp_path):
    """Exit 3 stays a restartable outcome for TRAINING chains (a chip
    lost to preemption can come back); only serve replicas treat it as
    terminal."""
    from sav_tpu.train.supervisor import Supervisor

    sup = Supervisor(
        [sys.executable, "-c", "import sys; sys.exit(3)"],
        log_dir=str(tmp_path), checkpoint_dir=None,
        max_restarts=1, backoff_base_s=0.01,
    )
    assert sup.run() == 3
    assert len(sup.attempts) == 2
    assert sup.attempts[0]["restart_reason"] == "backend_unreachable"
