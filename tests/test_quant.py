"""Int8 quantized matmuls (ISSUE 17, docs/quantization.md).

Op tier: per-channel symmetric quantization round-trips within half a
step, all-zero channels never divide by zero, stochastic rounding is
unbiased, and the STE dot's forward/backward track the float dot.

Module tier: QuantDenseGeneral's QAT arm initializes byte-identically
to the flax layer it replaces (quant checkpoints stay byte-compatible
with the bf16 arm), and the QAT forward is BIT-identical to the serving
forward after ``quantize_params`` — what trains is what serves.

Training tier: a CPU fit with ``quant="int8"`` moves the loss through
the STE + stochastic-rounding step; the pipeline arm refuses to compose.

Serving tier: the quant engine's logits track a float engine on the
same trained weights (top-1 agreement), the startup report carries the
HBM-density proof, the full-depth ratio clears the ≤0.6 gate (pure
eval_shape math — kernels dominate at depth), the manifest/bench-line
metrics land under the isolated ``quant_*`` sentinel names, and the
heartbeat dtype stamp survives to ``fleet/proc_0.jsonl``.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import linen as nn

from sav_tpu.ops.quant import (
    QuantDenseGeneral,
    int8_serve_dot,
    int8_ste_dot,
    quantize_channelwise,
    quantize_params,
    quantize_stochastic,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


# --------------------------------------------------------------- op tier


def test_quantize_channelwise_round_trip_and_zero_channels():
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.standard_normal((6, 8)), jnp.float32)
    a = a.at[:, 3].set(0.0)  # one all-zero output channel
    q, scale = quantize_channelwise(a, contract_axes=(0,))
    assert q.dtype == jnp.int8
    assert scale.shape == (1, 8)
    # Symmetric restricted range: -128 never appears.
    assert int(jnp.min(q)) >= -127 and int(jnp.max(q)) <= 127
    # Round-to-nearest: every element within half a quantization step.
    err = jnp.abs(q.astype(jnp.float32) * scale - a)
    assert float(jnp.max(err / scale)) <= 0.5 + 1e-6
    # The zero channel: scale 1.0 (not 0/0), q exactly 0.
    assert float(scale[0, 3]) == 1.0
    assert int(jnp.abs(q[:, 3]).sum()) == 0
    # Per-channel, not per-tensor: a huge outlier in channel 0 must not
    # crush channel 1's resolution.
    b = jnp.asarray([[1000.0, 0.5], [500.0, -0.25]], jnp.float32)
    _, sb = quantize_channelwise(b, contract_axes=(0,))
    assert float(sb[0, 1]) == pytest.approx(0.5 / 127.0)


def test_quantize_stochastic_is_unbiased():
    # amax 1.0 -> scale 1/127; 0.35/scale = 44.45 sits BETWEEN int8
    # steps: round-to-nearest always picks 44, stochastic rounding must
    # average to the true value (floor(44.45 + u) is 45 w.p. 0.45).
    a = jnp.asarray([[1.0], [0.35]], jnp.float32)
    keys = jax.random.split(jax.random.PRNGKey(17), 2048)

    def deq(key):
        q, s = quantize_stochastic(a, (0,), key)
        return (q.astype(jnp.float32) * s)[1, 0]

    vals = jax.vmap(deq)(keys)
    # E[q*s] = a (AQT unbiasedness); the empirical mean over 2048 draws
    # sits within a few standard errors of the true value.
    assert float(vals.mean()) == pytest.approx(0.35, rel=0.02)
    # And it genuinely rounds both ways (not a constant).
    assert float(vals.std()) > 0.0


def test_int8_ste_dot_tracks_float_forward_and_backward():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((4, 16)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((16, 8)), jnp.float32)
    key = jax.random.key_data(jax.random.PRNGKey(3))

    out = int8_ste_dot(x, w, key, 1)
    ref = x @ w
    # int8 resolution on unit-normal data: ~1% relative error envelope.
    assert float(jnp.max(jnp.abs(out - ref))) < 0.05 * float(
        jnp.max(jnp.abs(ref))
    )

    def loss(x, w):
        return jnp.sum(jnp.sin(int8_ste_dot(x, w, key, 1)))

    def loss_ref(x, w):
        return jnp.sum(jnp.sin(x @ w))

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    rx, rw = jax.grad(loss_ref, argnums=(0, 1))(x, w)
    assert gx.shape == x.shape and gw.shape == w.shape
    # STE gradients are quantized estimates of the float gradients —
    # same direction, few-percent magnitude error.
    for g, r in ((gx, rx), (gw, rw)):
        cos = jnp.sum(g * r) / (
            jnp.linalg.norm(g) * jnp.linalg.norm(r) + 1e-12
        )
        assert float(cos) > 0.99


def test_int8_ste_dot_multi_axis_contraction():
    # The DenseGeneral shape: x [B, L, D] against w [D, H, Dh].
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 5, 12)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((12, 3, 4)), jnp.float32)
    key = jax.random.key_data(jax.random.PRNGKey(4))
    out = int8_ste_dot(x, w, key, 1)
    ref = jnp.einsum("bld,dhk->blhk", x, w)
    assert out.shape == (2, 5, 3, 4)
    assert float(jnp.max(jnp.abs(out - ref))) < 0.05 * float(
        jnp.max(jnp.abs(ref))
    )
    # And two contracted axes (the folded [H, Dh] -> D output proj).
    x2 = jnp.asarray(rng.standard_normal((2, 5, 3, 4)), jnp.float32)
    w2 = jnp.asarray(rng.standard_normal((3, 4, 12)), jnp.float32)
    out2 = int8_ste_dot(x2, w2, key, 2)
    ref2 = jnp.einsum("blhk,hkd->bld", x2, w2)
    assert float(jnp.max(jnp.abs(out2 - ref2))) < 0.05 * float(
        jnp.max(jnp.abs(ref2))
    )


# ----------------------------------------------------------- module tier


def test_quant_dense_init_is_byte_identical_to_flax():
    """The QAT arm declares the SAME float params as the layer it
    replaces: identical tree paths, shapes, and init bytes — a quant
    checkpoint restores into the bf16 arm and vice versa."""
    x = jnp.zeros((2, 7, 16), jnp.float32)
    rng = jax.random.PRNGKey(0)
    ref = nn.DenseGeneral(features=(4, 8), axis=-1).init(rng, x)["params"]
    got = QuantDenseGeneral(features=(4, 8), mode="int8").init(
        {"params": rng}, x
    )["params"]
    assert jax.tree.structure(ref) == jax.tree.structure(got)
    for r, g in zip(jax.tree.leaves(ref), jax.tree.leaves(got)):
        np.testing.assert_array_equal(np.asarray(r), np.asarray(g))
    # Scalar-features twin of nn.Dense too.
    ref_d = nn.Dense(features=8).init(rng, x)["params"]
    got_d = QuantDenseGeneral(features=8, mode="int8").init(
        {"params": rng}, x
    )["params"]
    np.testing.assert_array_equal(
        np.asarray(ref_d["kernel"]), np.asarray(got_d["kernel"])
    )


def test_quant_dense_rejects_non_trailing_axis():
    x = jnp.zeros((2, 7, 16), jnp.float32)
    with pytest.raises(ValueError, match="trailing axes only"):
        QuantDenseGeneral(features=4, axis=1).init(
            {"params": jax.random.PRNGKey(0)}, x
        )


def test_qat_forward_is_bit_identical_to_serve_forward():
    """The parity gate: mode="int8" (training forward, round-to-nearest
    weights quantized on the fly) and mode="int8_serve" (pre-quantized
    kernels via quantize_params) must produce BIT-identical outputs —
    what the QAT arm trained is exactly what the serving arm runs."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((3, 16)), jnp.float32)
    key = jax.random.PRNGKey(7)
    qat = QuantDenseGeneral(features=(2, 4), mode="int8")
    float_params = qat.init({"params": key}, x)["params"]
    serve = QuantDenseGeneral(features=(2, 4), mode="int8_serve")
    template = jax.eval_shape(
        lambda: serve.init({"params": key}, x)
    )["params"]
    served_params = quantize_params(float_params, template)
    assert served_params["kernel"].dtype == jnp.int8
    assert served_params["scale"].shape == template["scale"].shape
    out_qat = qat.apply({"params": float_params}, x)
    out_serve = serve.apply({"params": served_params}, x)
    np.testing.assert_array_equal(np.asarray(out_qat), np.asarray(out_serve))


def test_quantize_params_casts_non_kernel_leaves_to_template_dtype():
    params = {
        "proj": {
            "kernel": jnp.ones((4, 2), jnp.float32) * 0.5,
            "bias": jnp.ones((2,), jnp.float32),
        },
        "norm": {"scale": jnp.ones((4,), jnp.float32)},
    }
    template = {
        "proj": {
            "kernel": jax.ShapeDtypeStruct((4, 2), jnp.int8),
            "scale": jax.ShapeDtypeStruct((2,), jnp.float32),
            "bias": jax.ShapeDtypeStruct((2,), jnp.bfloat16),
        },
        "norm": {"scale": jax.ShapeDtypeStruct((4,), jnp.bfloat16)},
    }
    out = quantize_params(params, template)
    assert out["proj"]["kernel"].dtype == jnp.int8
    assert int(out["proj"]["kernel"][0, 0]) == 127  # 0.5/(0.5/127)
    assert out["proj"]["scale"].shape == (2,)
    assert out["proj"]["bias"].dtype == jnp.bfloat16
    # norm/scale is NOT a quantized pair (no int8 kernel sibling): cast
    # only, never quantized.
    assert out["norm"]["scale"].dtype == jnp.bfloat16


def test_int8_serve_dot_matches_manual_dequant():
    rng = np.random.default_rng(6)
    x = jnp.asarray(rng.standard_normal((3, 8)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((8, 5)), jnp.float32)
    qw, sw = quantize_channelwise(w, (0,))
    out = int8_serve_dot(x, qw, sw.reshape(5), 1)
    qx, sx = quantize_channelwise(x, (1,))
    ref = (
        (qx.astype(jnp.int32) @ qw.astype(jnp.int32)).astype(jnp.float32)
        * sx
        * sw
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)


# --------------------------------------------------------- training tier


def test_trainer_quant_fit_moves_the_loss(tmp_path, devices):
    """The QAT arm end-to-end on CPU: --quant int8 threads the "quant"
    rng stream through the STE step and the loss moves under synthetic
    data — the whole fwd/bwd graph runs through int8 contractions."""
    from sav_tpu.data import synthetic_data_iterator
    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        compute_dtype="float32", global_batch_size=8, num_train_images=64,
        num_epochs=1, warmup_epochs=1, lr_scaling_divisor=8,
        transpose_images=False, log_every_steps=2, log_dir=str(tmp_path),
        model_overrides=dict(num_layers=2, embed_dim=64, num_heads=4),
        quant="int8", seed=0,
    )
    trainer = Trainer(config)
    assert getattr(trainer.model, "quant", None) == "int8"
    data = synthetic_data_iterator(
        batch_size=8, image_size=32, num_classes=10
    )
    _, history = trainer.fit(data, num_steps=8)
    losses = [float(m["loss"]) for m in history if "loss" in m]
    assert losses and all(np.isfinite(losses))
    # Synthetic labels are learnable: 8 STE steps must make progress.
    assert losses[-1] < losses[0]


def test_quant_refuses_pipeline_parallel():
    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        global_batch_size=8, num_train_images=64, num_epochs=1,
        model_overrides=dict(num_layers=2, embed_dim=64, num_heads=4),
        pipeline_parallel=2, quant="int8", seed=0,
    )
    with pytest.raises(ValueError, match="does not compose"):
        Trainer(config)


def test_trainer_rejects_mismatched_external_model_quant():
    from sav_tpu.models import create_model
    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        global_batch_size=8, num_train_images=64, num_epochs=1,
        quant="int8", seed=0,
    )
    model = create_model(
        "vit_ti_patch16", num_classes=10, dtype=jnp.float32,
        num_layers=2, embed_dim=64, num_heads=4,
    )
    with pytest.raises(ValueError, match="externally"):
        Trainer(config, model=model)


# ---------------------------------------------------------- serving tier


def _serve_config(**overrides):
    from sav_tpu.serve.engine import ServeConfig

    base = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        model_overrides={"num_layers": 1},
        buckets=[1, 2],
        max_queue=128,
        deadline_ms=2000.0,
    )
    base.update(overrides)
    return ServeConfig(**base)


def _noisy_params(config):
    """A float param tree with nonzero weights everywhere — fresh inits
    zero most projections, which would make the parity check vacuous."""
    from sav_tpu.models import create_model

    model = create_model(
        config.model_name, num_classes=config.num_classes,
        dtype=jnp.float32, **(config.model_overrides or {}),
    )
    s = config.image_size
    params = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((1, s, s, 3), jnp.float32), is_training=False,
    )["params"]
    leaves, treedef = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(42), len(leaves))
    noisy = [
        p + jax.random.normal(k, p.shape, p.dtype) * 0.02
        for p, k in zip(leaves, keys)
    ]
    return jax.tree.unflatten(treedef, noisy)


def test_quant_engine_parity_report_and_heartbeat_stamp(tmp_path, devices):
    """One engine pair on the same trained weights: the int8 arm must
    (a) agree with the float arm on top-1 within an int8-resolution
    logit envelope, (b) carry the HBM-density proof + int8 dtype in the
    startup report, (c) stamp serve/quant_weights into stop() metrics,
    and (d) leave an int8 dtype-stamped heartbeat in fleet/proc_0.jsonl
    (what serve_status/fleet_status render)."""
    from sav_tpu.serve.engine import ServeEngine

    params = _noisy_params(_serve_config())
    rng = np.random.default_rng(9)
    images = [
        rng.integers(0, 256, (32, 32, 3), dtype=np.uint8) for _ in range(4)
    ]

    float_engine = ServeEngine(_serve_config(), params=params)
    with float_engine:
        float_rows = [
            float_engine.submit(img).result(timeout=60.0) for img in images
        ]
    float_engine.stop()

    quant_engine = ServeEngine(
        _serve_config(quant_weights=True, log_dir=str(tmp_path)),
        params=params,
    )
    report = quant_engine.startup_report
    with quant_engine:
        quant_rows = [
            quant_engine.submit(img).result(timeout=60.0) for img in images
        ]

    # (a) numerics: same top-1, logits within the int8 envelope.
    for f, q in zip(float_rows, quant_rows):
        f, q = np.asarray(f), np.asarray(q)
        assert int(f.argmax()) == int(q.argmax())
        scale = max(float(np.abs(f).max()), 1e-6)
        assert float(np.abs(f - q).max()) <= 0.1 * scale

    # (b) the startup report: dtype stamp + the HBM-density proof.
    assert report["dtype"] == "int8"
    quant = report["quant"]
    assert quant["weights_dtype"] == "int8"
    assert quant["param_bytes_serving"] < quant["param_bytes_bf16_equiv"]
    assert 0.0 < quant["param_bytes_ratio"] < 1.0
    assert set(report["bucket_hbm_bytes"]) == {"1", "2"}

    # (c) the finalized manifest: the flat serve/quant_weights marker
    # (what _manifest_metrics keys the quant_* remap on) plus the
    # notes.quant arm stamp.
    from sav_tpu.obs.manifest import RunManifest

    manifests = [
        os.path.join(str(tmp_path), f)
        for f in os.listdir(str(tmp_path))
        if f.startswith("manifest-serve-")
    ]
    assert len(manifests) == 1
    doc = RunManifest.load(manifests[0])
    assert doc["outcome"] == "ok"
    assert doc["metrics"]["serve/quant_weights"] == 1.0
    assert doc["notes"]["quant"]["weights"] == "int8"

    # (d) the fleet heartbeat dtype stamp (telemetry close() emits a
    # final beat, so even a short-lived engine leaves one).
    beats_path = os.path.join(str(tmp_path), "fleet", "proc_0.jsonl")
    with open(beats_path) as f:
        beats = [json.loads(line) for line in f if line.strip()]
    assert any(b.get("dtype") == "int8" for b in beats)


def test_quant_engine_refuses_external_model():
    from sav_tpu.models import create_model
    from sav_tpu.serve.engine import ServeEngine

    model = create_model(
        "vit_ti_patch16", num_classes=10, dtype=jnp.float32, num_layers=1,
    )
    with pytest.raises(ValueError, match="quant_weights"):
        ServeEngine(_serve_config(quant_weights=True), model=model)


def test_full_depth_hbm_ratio_clears_the_gate():
    """The ≤0.6 acceptance gate, as pure eval_shape math (no training,
    no compile): at real depth the int8 kernels dominate the param
    bytes and the serving tree weighs ≤0.6× its bf16 equivalent. The
    shallow smoke models do NOT clear this (conv-embed tables dominate
    at depth 1-2) — depth is what the gate speaks to; the full-size
    model's proof on the chip has not been run."""
    from sav_tpu.models import create_model

    kwargs = dict(
        num_classes=1000, dtype=jnp.float32, num_layers=6,
    )
    x = jnp.zeros((1, 64, 64, 3), jnp.float32)
    rng = {"params": jax.random.PRNGKey(0)}

    float_tree = jax.eval_shape(
        lambda: create_model("vit_ti_patch16", **kwargs).init(
            rng, x, is_training=False
        )
    )["params"]
    serve_tree = jax.eval_shape(
        lambda: create_model(
            "vit_ti_patch16", quant="int8_serve", **kwargs
        ).init(rng, x, is_training=False)
    )["params"]

    bf16_equiv = sum(int(l.size) * 2 for l in jax.tree.leaves(float_tree))
    serving = sum(
        int(l.size) * jnp.dtype(l.dtype).itemsize
        for l in jax.tree.leaves(serve_tree)
    )
    ratio = serving / bf16_equiv
    assert ratio <= 0.6, f"HBM density gate failed: {ratio:.4f}"
    # The trees differ ONLY by the kernel/scale pairs: same top-level
    # structure, so SpecLayout rules keyed on names still apply.
    assert set(float_tree) == set(serve_tree)


# ----------------------------------------- sentinel + harness isolation


def test_manifest_metrics_isolate_quant_records():
    from sav_tpu.obs.manifest import _bench_line_metrics, _manifest_metrics

    line = {
        "p99_latency_ms": 26.0, "serve_throughput": 330.0,
        "slo_hit_frac": 0.99,
    }
    plain = _bench_line_metrics(dict(line))
    assert plain["p99_latency_ms"] == 26.0
    assert "quant_p99_latency_ms" not in plain
    quant = _bench_line_metrics(dict(line, quant="int8"))
    assert quant["quant_p99_latency_ms"] == 26.0
    assert quant["quant_serve_throughput"] == 330.0
    assert quant["quant_slo_hit_frac"] == 0.99
    assert "p99_latency_ms" not in quant

    metrics = {
        "serve/p99_latency_ms": 26.0, "serve/throughput_rps": 330.0,
        "serve/slo_hit_frac": 0.99,
    }
    assert _manifest_metrics(dict(metrics))["p99_latency_ms"] == 26.0
    remapped = _manifest_metrics(dict(metrics, **{"serve/quant_weights": 1.0}))
    assert remapped["quant_p99_latency_ms"] == 26.0
    assert remapped["quant_serve_throughput"] == 330.0
    assert "serve_throughput" not in remapped


def test_serve_bench_quant_does_not_compose_with_replicas(capsys):
    serve_bench = _load_tool("serve_bench")
    with pytest.raises(SystemExit) as exit_info:
        serve_bench.main(["--quant-weights", "--replicas", "2"])
    assert exit_info.value.code == 2
    assert "single-engine A/B arm" in capsys.readouterr().err


def test_zoo_quant_serve_check_all_seven_families_on_cpu(capsys):
    """Every family's int8 serving program builds and runs finite on
    CPU under the smoke shrink (the full-size on-chip sweep has not
    been run)."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import zoo_tpu_check
    finally:
        sys.path.pop(0)
    argv = sys.argv
    sys.argv = ["zoo_tpu_check.py", "--serve", "--smoke", "--quant-weights"]
    try:
        with pytest.raises(SystemExit) as exit_info:
            zoo_tpu_check.main()
    finally:
        sys.argv = argv
    out = capsys.readouterr().out
    assert exit_info.value.code == 0
    assert out.count("OK  serve:int8") == 7
    assert "ALL SERVABLE" in out
