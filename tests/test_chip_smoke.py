"""CPU rehearsal of chip_smoke.py: its phase functions at a tiny size, so
a later change cannot break the script unseen.

The device check is told to accept the CPU here, by the test — the script
has no option for it: run by hand on the CPU it must fail. A rehearsal
shows the paths, arguments and control flow; it is not a chip run.
"""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = dict(
    chip_smoke.FULL_SIZE,
    model="vit_ti_patch16",
    image_size=32,
    num_classes=10,
    batch_size=32,
    first_steps=2,
    total_steps=12,
    learning_rate=0.016,
    requests=12,
    max_batch=4,
    rate=0.0,
    deadline_ms=60000.0,
    kernel_batch=4,
)


def _phase_lines(capsys) -> dict:
    lines = {}
    for line in capsys.readouterr().out.strip().splitlines():
        doc = json.loads(line)  # every stdout line of a phase is JSON
        lines[doc["phase"]] = doc
    return lines


def test_full_size_is_the_flagship_at_published_width():
    assert chip_smoke.FULL_SIZE["model"] == "deit_s_patch16"
    assert chip_smoke.FULL_SIZE["image_size"] == 224
    assert chip_smoke.FULL_SIZE["num_classes"] == 1000
    assert chip_smoke.FULL_SIZE["batch_size"] == 256
    assert chip_smoke.FULL_SIZE["kernel_batch"] == 256


def test_train_then_serve_phases_at_tiny_size(tmp_path, capsys):
    ckpt_dir = chip_smoke.phase_train(TINY, str(tmp_path))
    chip_smoke.phase_serve(TINY, str(tmp_path), ckpt_dir)
    lines = _phase_lines(capsys)
    train = lines["train"]
    assert train["checkpoints"] == [2, 12]
    assert train["loss_second_run"] < train["loss_first_run"]
    assert [r["resumed_from"] for r in train["runs"]] == [0, 2]
    serve = lines["serve"]
    assert serve["requests"] == TINY["requests"] and serve["rejected"] == 0
    assert serve["logits_absmax"] > 0.0


def test_kernels_phase_at_tiny_size(capsys):
    # Interpret mode lowers to plain HLO: no tpu_custom_call to expect.
    chip_smoke.phase_kernels(TINY, expect_custom_call=False)
    kernels = _phase_lines(capsys)["kernels"]
    for backend in ("fused", "pallas"):
        assert kernels["backends"][backend]["logits_rel_err"] <= 0.03
        assert kernels["backends"][backend]["grad_rel_err"] <= 0.15


def test_sharded_train_phase_on_four_virtual_devices(devices, monkeypatch, capsys):
    import jax

    # The phase takes jax.devices() as the host's chips: hand it four of
    # the suite's eight virtual CPU devices. Six heads divide the model
    # axis (vit_ti has three).
    monkeypatch.setattr(jax, "devices", lambda *a: devices[:4])
    chip_smoke.phase_sharded_train(dict(TINY, model="vit_s_patch32"))
    line = _phase_lines(capsys)["sharded_train"]
    assert max(line["loss_rel_err"]) <= chip_smoke.LOSS_RTOL
    assert line["all_reduces_in_compiled_step"] > 0
    assert len(set(line["tp_shard_sizes"].values())) == 1
    total = line["opt_state_bytes_total"]
    assert all(0 < n < total for n in line["opt_state_bytes_per_device"].values())


def test_run_smoke_refuses_anything_but_a_tpu(tmp_path):
    with pytest.raises(chip_smoke.SmokeFailure, match="expected a TPU"):
        chip_smoke.run_smoke(1, TINY, str(tmp_path / "work"))
    assert not (tmp_path / "work").exists()


@pytest.mark.parametrize("extra", [[], ["--chips", "4"]])
def test_script_fails_on_the_cpu_and_prints_no_verdict(extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")] + extra,
        capture_output=True, text=True, env=env, cwd=ROOT, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "expected a TPU" in proc.stderr


def test_script_alone_without_the_program_fails(tmp_path):
    import shutil

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    env = {
        k: v for k, v in os.environ.items() if k != "PYTHONPATH"
    }
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "chip_smoke.py"],
        capture_output=True, text=True, env=env, cwd=tmp_path, timeout=300,
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
