"""tools/mesh_tune.py end-to-end on the CPU mesh: candidates
enumerated + ranked, infeasible configs recorded (never fatal), top-K
measured with the Trap-pinned scan loop, a preset emitted — and the
preset consumed by the trainer, closing the ISSUE-13 loop on CPU before
an on-chip round (not yet run) proves it at chip step times."""

import importlib.util
import json
import os
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def mesh_tune():
    return _load_tool("mesh_tune")


# ------------------------------------------------------------- enumeration


def test_enumerate_layouts_covers_the_arms(mesh_tune):
    layouts = mesh_tune.enumerate_layouts(8, ["dp", "tp", "2d", "fsdp"])
    names = {l.name for l in layouts}
    assert "dp" in names
    assert {"tp2", "tp4", "tp8"} <= names
    assert {"2d2x2", "2d2x4", "2d4x2"} <= names
    assert {"fsdp2", "fsdp4", "fsdp8"} <= names
    # Every candidate states a fully explicit mesh over exactly 8 devices.
    for layout in layouts:
        sizes = list(layout.axis_dict().values())
        assert -1 not in sizes
        assert int(np.prod(sizes)) == 8


def test_check_feasible_divisibility(mesh_tune):
    from sav_tpu.parallel.layout import layout_from_mesh_axes

    params = {
        "to_qkv": {
            "kernel": jax.ShapeDtypeStruct((64, 3, 4, 16), jax.numpy.float32)
        }
    }
    tp8 = layout_from_mesh_axes({"data": 1, "model": 8}, name="tp8")
    reason = mesh_tune.check_feasible(
        tp8, params, global_batch=8, grad_accum=1
    )
    assert reason is not None and "not divisible" in reason
    tp4 = layout_from_mesh_axes({"data": 2, "model": 4}, name="tp4")
    assert (
        mesh_tune.check_feasible(tp4, params, global_batch=8, grad_accum=1)
        is None
    )
    # Microbatch must divide the batch-axis product (6/2 = 3 over data=2).
    assert "microbatch" in mesh_tune.check_feasible(
        tp4, params, global_batch=6, grad_accum=2
    )


def test_predict_step_time_dot_dtype_axis(mesh_tune):
    """ISSUE 17 (docs/quantization.md): --dot-dtype int8 prices the
    quantized arm — the caller resolves a 2x peak (halving the compute
    term, passed doubled here exactly as run() does) and int8
    activations halve the TP collective-traffic term relative to the
    bf16 default, so the int8 prediction must be strictly faster on a
    TP layout."""
    import types

    from sav_tpu.parallel.layout import layout_from_mesh_axes

    params = {
        "to_qkv": {
            "kernel": jax.ShapeDtypeStruct((64, 3, 4, 16), jax.numpy.float32)
        },
        "pos_embedding": {
            "pos_embedding": jax.ShapeDtypeStruct(
                (1, 65, 64), jax.numpy.float32
            )
        },
    }
    cost = types.SimpleNamespace(flops=1e12, num_tokens=65)
    # Pure TP (data=1): no dp gradient AllReduce term, so ALL collective
    # traffic is activation-sized and the dtype ratio is exact.
    tp4 = layout_from_mesh_axes({"data": 1, "model": 4}, name="tp4")
    kwargs = dict(
        global_batch=32, grad_accum=1, num_layers=2,
        ici_bytes_per_s=1e9,
    )
    bf16 = mesh_tune.predict_step_time(
        tp4, cost, params, peak_flops=1e12, dot_dtype=None, **kwargs
    )
    int8 = mesh_tune.predict_step_time(
        tp4, cost, params, peak_flops=2e12, dot_dtype="int8", **kwargs
    )
    assert int8["total_s"] < bf16["total_s"]
    assert int8["compute_s"] == pytest.approx(bf16["compute_s"] / 2)
    assert int8["comm_s"] == pytest.approx(bf16["comm_s"] / 2)
    assert "tp_block_allreduce" in int8["comm_terms"]
    # f32 doubles the activation bytes instead (collectives get slower).
    f32 = mesh_tune.predict_step_time(
        tp4, cost, params, peak_flops=1e12, dot_dtype="f32", **kwargs
    )
    assert f32["total_s"] > bf16["total_s"]


# -------------------------------------------------------------------- e2e


@pytest.fixture(scope="module")
def sweep(mesh_tune, tmp_path_factory):
    """One tiny sweep shared by the e2e assertions (compiles are the
    cost; ~2 candidates measured)."""
    tmp = tmp_path_factory.mktemp("mesh_tune")
    out = str(tmp / "preset.json")
    report_path = str(tmp / "report.json")
    import argparse

    ns = argparse.Namespace(
        model="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        model_overrides='{"num_layers": 2, "embed_dim": 64, "num_heads": 4}',
        global_batch=32,
        devices=8,
        arms="dp,tp,2d,fsdp",
        grad_accum="1,2",
        top_k=2,
        iters=2,
        rounds=2,
        peak_flops=None,
        dot_dtype=None,
        ici_gbps=None,
        trace=str(tmp / "trace"),
        out=out,
        report=report_path,
    )
    lines = []
    report = mesh_tune.run(ns, log=lines.append)
    return {
        "report": report,
        "out": out,
        "report_path": report_path,
        "lines": lines,
    }


def test_sweep_ranks_and_records_infeasible(sweep):
    report = sweep["report"]
    cands = report["candidates"]
    assert len(cands) >= 10
    # tp8 cannot shard 4 heads — recorded with the reason, not dropped.
    tp8 = [c for c in cands if c["name"] == "tp8"]
    assert tp8 and all(not c["feasible"] for c in tp8)
    assert all("not divisible" in c["reason"] for c in tp8)
    # Every feasible candidate carries the prediction breakdown.
    for c in cands:
        if c["feasible"]:
            assert set(c["predicted"]) >= {"compute_s", "comm_s", "total_s"}
    # Ranking provenance: peak + ICI sources are labeled (cpu-fake here).
    assert report["peak_source"] == "cpu-fake"
    assert report["ici_source"] == "cpu-fake"


def test_sweep_measures_topk_and_emits_winner(sweep):
    report = sweep["report"]
    measured = [
        c for c in report["candidates"]
        if c.get("measured_ms_per_step") is not None
    ]
    assert len(measured) == 2  # top_k
    winner = report["winner"]
    assert winner is not None
    # Candidates at different accums compare per OPTIMIZER step.
    assert winner["measured_ms_per_opt_step"] == min(
        c["measured_ms_per_opt_step"] for c in measured
    )
    # The report file is valid JSON with the same shape.
    with open(sweep["report_path"]) as f:
        on_disk = json.load(f)
    assert on_disk["kind"] == "mesh-tune-report"
    assert on_disk["winner"]["name"] == winner["name"]


def test_sweep_trace_check_is_honest(sweep):
    """The cross-check either compares (and lists disagreements) or says
    it could not — an unindexed capture is never a clean bill."""
    check = sweep["report"]["trace_check"]
    assert check is not None
    if check["available"]:
        assert "vs_predicted" in check
        assert isinstance(check["disagrees"], list)
    else:
        assert check["reason"]


def test_emitted_preset_drives_the_trainer(sweep):
    """The winner preset rides TrainConfig.layout_preset end-to-end:
    mesh from the preset, one finite train step, provenance stamped."""
    from sav_tpu.data import synthetic_data_iterator
    from sav_tpu.parallel.layout import load_layout_preset
    from sav_tpu.train import TrainConfig, Trainer

    layout, doc = load_layout_preset(sweep["out"])
    assert doc["provenance"]["tool"] == "tools/mesh_tune.py"
    assert "measured_ms_per_step" in doc["provenance"]
    config = TrainConfig(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=32,
        num_train_images=64,
        num_epochs=1,
        warmup_epochs=1,
        transpose_images=False,
        layout_preset=sweep["out"],
        grad_accum_steps=doc.get("grad_accum_steps", 1),
        model_overrides=dict(num_layers=2, embed_dim=64, num_heads=4),
        seed=0,
    )
    trainer = Trainer(config)
    assert trainer.layout.name == sweep["report"]["winner"]["name"]
    assert trainer.layout.source == f"preset:{sweep['out']}"
    state = trainer.init_state()
    batch = next(
        synthetic_data_iterator(batch_size=32, image_size=32, num_classes=10)
    )
    state, metrics = trainer.train_step(state, batch, jax.random.PRNGKey(0))
    assert np.isfinite(float(jax.device_get(metrics["loss"])))
