"""Cost model (ISSUE 4): peak resolution, the analytic per-group FLOPs
walk, the XLA-upgrade path, and the end-to-end trainer integration — a
CPU fit() reports goodput/mfu + per-group attribution gauges and lands
them in the run manifest."""

import jax
import jax.numpy as jnp
import pytest

from sav_tpu.obs.costs import (
    CPU_FAKE_PEAK_FLOPS,
    TRAIN_STEP_MULTIPLIER,
    analytic_train_step_cost,
    infer_num_tokens,
    publish_cost_gauges,
    publish_mfu_gauges,
    resolve_peak_flops,
    train_step_cost,
)
from sav_tpu.obs.goodput import GoodputLedger


@pytest.fixture(scope="module")
def vit_params():
    from sav_tpu.models import create_model

    model = create_model(
        "vit_ti_patch16", num_classes=10, dtype=jnp.float32,
        num_layers=2, embed_dim=64, num_heads=4,
    )
    variables = model.init(
        {"params": jax.random.PRNGKey(0)},
        jnp.zeros((2, 32, 32, 3)), is_training=False,
    )
    return model, variables["params"]


# --------------------------------------------------------- peak resolution


def test_peak_resolution_order():
    # Override beats everything; the CPU this suite asked for
    # (tests/conftest.py pins jax_platforms=cpu) gets the deterministic
    # fake — labeled, so it can never masquerade as a hardware number.
    assert resolve_peak_flops(5e12) == (5e12, "override")
    peak, source = resolve_peak_flops()
    assert source == "cpu-fake"
    assert peak == CPU_FAKE_PEAK_FLOPS


def test_cpu_fake_only_where_the_cpu_was_asked_for(monkeypatch):
    """A CPU that jax fell back to by itself gets no made-up peak: the
    lookup fails like any device the table does not know."""
    import jax

    from sav_tpu.obs import costs
    from sav_tpu.utils.flops import UnknownDeviceKindError

    monkeypatch.setattr(costs, "cpu_requested", lambda: False)
    with pytest.raises(UnknownDeviceKindError, match="cpu"):
        resolve_peak_flops(None, devices=jax.devices()[:1])
    # An explicit override still works anywhere.
    assert resolve_peak_flops(2e12, devices=jax.devices()[:1]) == (
        2e12, "override",
    )


def test_device_table_matches_kind_exactly_and_carries_sources():
    from sav_tpu.utils.flops import (
        PEAK_FLOPS_PER_CHIP,
        UnknownDeviceKindError,
        per_chip_peak_flops,
    )

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v5 lite"  # what a v5e reports

    peak, source = resolve_peak_flops(None, devices=[FakeDevice()])
    assert (peak, source) == (197e12, "device-table")
    assert per_chip_peak_flops([FakeDevice()]) == (
        197e12, "Google Cloud documentation, TPU v5e",
    )
    # Every entry names where its number comes from.
    assert all(
        isinstance(p, float) and src for p, src in PEAK_FLOPS_PER_CHIP.values()
    )

    # An unknown accelerator is an error, not a default — and kinds that
    # merely CONTAIN a known one ("v4", "v6" as substrings) do not match.
    for kind in ("TPU v99", "TPU v5 lite pod", "NVIDIA v4", "tpu v5 lite", ""):
        class Unknown:
            platform = "tpu"
            device_kind = kind

        with pytest.raises(UnknownDeviceKindError, match="no peak FLOP/s"):
            resolve_peak_flops(None, devices=[Unknown()])
        with pytest.raises(UnknownDeviceKindError):
            per_chip_peak_flops([Unknown()])


def test_dot_dtype_axis_scales_peak_and_tags_source():
    """ISSUE 17: the int8 arm's roofline denominator is 2x the bf16
    table entry (the MXU's native int8 path) and the source string is
    tagged ':int8' so a doubled peak can never masquerade as the bf16
    one. bf16/f32 are the identity (untagged); explicit overrides are
    taken verbatim — the operator's number is never scaled."""
    from sav_tpu.obs.costs import dot_dtype_bytes

    class FakeDevice:
        platform = "tpu"
        device_kind = "TPU v5 lite"

    devices = [FakeDevice()]
    assert resolve_peak_flops(None, devices=devices, dot_dtype="int8") == (
        2 * 197e12, "device-table:int8",
    )
    assert resolve_peak_flops(None, devices=devices, dot_dtype="bf16") == (
        197e12, "device-table",
    )
    # CPU fake doubles too, still labeled fake (+ the dtype tag).
    assert resolve_peak_flops(None, dot_dtype="int8") == (
        2 * CPU_FAKE_PEAK_FLOPS, "cpu-fake:int8",
    )
    assert resolve_peak_flops(5e12, dot_dtype="int8") == (5e12, "override")
    # The activation-traffic side of the axis.
    assert dot_dtype_bytes("int8") == 1
    assert dot_dtype_bytes("bf16") == 2
    assert dot_dtype_bytes("f32") == 4
    assert dot_dtype_bytes(None) == 2  # the historical bf16 default


# ----------------------------------------------------------- analytic walk


def test_token_inference_prefers_pos_embed_table(vit_params):
    _, params = vit_params
    # 32px / 16px patches = 2x2 grid + CLS = 5, stated by the pos_embed
    # table directly.
    assert infer_num_tokens(params, 32) == 5


def test_analytic_cost_attribution_sums_to_one(vit_params):
    _, params = vit_params
    cost = analytic_train_step_cost(
        params, batch_size=16, image_size=32, n_devices=1
    )
    assert cost.source == "analytic"
    assert cost.flops > 0
    assert sum(cost.attribution.values()) == pytest.approx(1.0)
    assert sum(cost.groups.values()) == pytest.approx(1.0)
    # Every named component of a ViT shows up, QK/AV included (the
    # parameter-free einsums a parameter-bytes count would miss).
    for comp in (
        "patch_embed", "attention_proj", "attention_qkav", "ffn", "head",
    ):
        assert cost.attribution.get(comp, 0.0) > 0.0, comp
    # Group naming matches diagnostics' grad_norm/<group> vocabulary.
    assert "Encoder_0" in cost.groups and "head" in cost.groups


def test_analytic_cost_scales_linearly_with_batch_and_devices(vit_params):
    _, params = vit_params
    one = analytic_train_step_cost(params, batch_size=8, image_size=32)
    two = analytic_train_step_cost(params, batch_size=16, image_size=32)
    assert two.flops == pytest.approx(2 * one.flops)
    sharded = analytic_train_step_cost(
        params, batch_size=16, image_size=32, n_devices=8
    )
    assert sharded.flops == pytest.approx(two.flops / 8)


def test_training_multiplier_applies(vit_params):
    _, params = vit_params
    train = analytic_train_step_cost(params, batch_size=8, image_size=32)
    infer = analytic_train_step_cost(
        params, batch_size=8, image_size=32, training=False
    )
    assert train.flops == pytest.approx(TRAIN_STEP_MULTIPLIER * infer.flops)


def test_analytic_total_tracks_xla_cost_analysis(vit_params):
    """The fallback must be in the right ballpark of XLA's exact count on
    a real fwd+bwd graph (within 2x either way — it is an estimate, but a
    wrong-order-of-magnitude one would poison every MFU it feeds)."""
    model, params = vit_params

    def loss_fn(p, x):
        return (model.apply({"params": p}, x, is_training=False) ** 2).mean()

    compiled = jax.jit(jax.value_and_grad(loss_fn)).lower(
        params, jnp.zeros((16, 32, 32, 3))
    ).compile()
    cost = train_step_cost(
        params, batch_size=16, image_size=32, compiled=compiled
    )
    assert cost.source == "xla-cost-analysis"
    analytic = analytic_train_step_cost(params, batch_size=16, image_size=32)
    assert cost.flops == pytest.approx(analytic.flops, rel=1.0)
    # Attribution stays analytic even when the total is XLA's.
    assert cost.attribution == analytic.attribution


def test_gauges_vocabulary(vit_params):
    _, params = vit_params
    ledger = GoodputLedger()
    cost = analytic_train_step_cost(params, batch_size=8, image_size=32)
    publish_cost_gauges(
        ledger, cost, peak_flops=CPU_FAKE_PEAK_FLOPS, peak_source="cpu-fake"
    )
    mfu = publish_mfu_gauges(
        ledger, step_flops=cost.flops, peak_flops=CPU_FAKE_PEAK_FLOPS,
        steps=10, step_seconds=2.0,
    )
    flat = ledger.flat_metrics()
    assert flat["goodput/mfu"] == pytest.approx(mfu, abs=1e-6)  # 6dp rounding
    assert flat["goodput/flops_per_s"] == pytest.approx(cost.flops * 5)
    assert flat["goodput/peak_flops_is_fake"] == 1.0
    assert flat["goodput/flops/ffn_frac"] > 0
    # Unreportable cases return None and publish no mfu gauge.
    empty = GoodputLedger()
    assert publish_mfu_gauges(
        empty, step_flops=0.0, peak_flops=1e12, steps=5, step_seconds=1.0
    ) is None
    assert "goodput/mfu" not in empty.flat_metrics()


# ----------------------------------------------------- trainer integration


def test_fit_reports_mfu_and_attribution_in_goodput_and_manifest(
    tmp_path, devices
):
    """ISSUE 4 acceptance: a CPU fit() produces goodput/mfu, per-group
    FLOPs attribution, and a manifest carrying both."""
    from sav_tpu.data import fake_data_iterator
    from sav_tpu.models import create_model
    from sav_tpu.obs.manifest import RunManifest
    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        compute_dtype="float32", global_batch_size=8, num_train_images=32,
        num_epochs=1, warmup_epochs=1, lr_scaling_divisor=8,
        transpose_images=False, log_every_steps=2, log_dir=str(tmp_path),
        seed=0,
    )
    model = create_model(
        config.model_name, num_classes=10, dtype=jnp.float32,
        num_layers=2, embed_dim=64, num_heads=4,
    )
    trainer = Trainer(config, model=model)
    manifest = RunManifest(str(tmp_path / "manifest.json"), kind="train")
    manifest.begin()
    data = fake_data_iterator(batch_size=8, image_size=32, num_classes=10)
    _, history = trainer.fit(data, num_steps=4, manifest=manifest)
    manifest.finalize("ok", exit_code=0)

    gauges = trainer.last_goodput["gauges"]
    assert 0.0 < gauges["mfu"] < 1.0
    assert gauges["peak_flops_is_fake"] == 1.0
    assert gauges["flops/ffn_frac"] > 0
    # Per-window mfu rides the logged step metrics too.
    assert any("mfu" in m for m in history if "loss" in m)

    doc = RunManifest.load(manifest.path)
    assert doc["outcome"] == "ok"
    assert 0.0 < doc["metrics"]["goodput/mfu"] < 1.0
    attrib = [k for k in doc["metrics"] if k.startswith("goodput/flops/")]
    assert len(attrib) >= 5
    note = doc["notes"]["cost_model"]
    assert note["source"] == "xla-cost-analysis"  # the compiled step's own count
    assert note["peak_flops_source"] == "cpu-fake"
    assert doc["notes"]["backend"]["platform"] == "cpu"


def test_fit_crash_path_still_lands_cost_metrics_in_manifest(
    tmp_path, devices
):
    """A mid-run exception must leave a manifest that says where the
    FLOPs were going — fit()'s finally publishes before unwinding."""
    from sav_tpu.models import create_model
    from sav_tpu.obs.manifest import RunManifest, classify_exception
    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        compute_dtype="float32", global_batch_size=8, num_train_images=32,
        num_epochs=1, warmup_epochs=1, lr_scaling_divisor=8,
        transpose_images=False, log_every_steps=2, log_dir=str(tmp_path),
        async_feed=False, seed=0,
    )
    model = create_model(
        config.model_name, num_classes=10, dtype=jnp.float32,
        num_layers=2, embed_dim=64, num_heads=4,
    )
    trainer = Trainer(config, model=model)
    manifest = RunManifest(str(tmp_path / "manifest.json"), kind="train")
    manifest.begin()

    def poisoned():
        import numpy as np

        rng = np.random.default_rng(0)
        yield {
            "images": rng.standard_normal((8, 32, 32, 3)).astype("float32"),
            "labels": rng.integers(0, 10, (8,), "int32"),
        }
        raise RuntimeError("data source died")

    with pytest.raises(RuntimeError, match="data source died"):
        try:
            trainer.fit(poisoned(), num_steps=4, manifest=manifest)
        except BaseException as e:
            manifest.finalize(classify_exception(e), error=repr(e))
            raise
    doc = RunManifest.load(manifest.path)
    assert doc["outcome"] == "error"
    assert doc["metrics"]["goodput/flops/ffn_frac"] > 0
    # One step ran before the source died: the note is the compiled step's.
    assert doc["notes"]["cost_model"]["source"] == "xla-cost-analysis"
