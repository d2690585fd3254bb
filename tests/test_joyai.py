"""The JoyAI-LLM-Flash family (latent attention, routed and shared experts,
multi-token prediction) against its plain float32 reference, at toy sizes on
the CPU: hidden 64, 3 layers (one dense), 2 heads of 24 / 16, 16 experts of
which 4 a token, vocabulary 97, 32 positions: the model, the MTP task through
``Trainer.fit`` and the selection bias as state. The layers it brought are in
``test_joyai_layers.py``.

Tolerances. Program and reference both compute in float32 here, in
different orders (a sort and grouped matmuls against a loop over experts,
a blocked cross-entropy), so ``TIGHT`` = 2e-5 of the compared tensor's
largest entry, as ``test_ouro.py``. A routing decision is discrete: the
seeds here leave the margin between the 4th and the 5th score above 1e-5
at every token, which ``test_the_seeded_routing_has_a_margin`` holds."""

import gc
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import weights  # noqa: E402
from benchmark.reference import joyai as reference  # noqa: E402
from sav_tpu.models import create_model, model_task  # noqa: E402
from sav_tpu.train.tasks import TASKS, LoopedTokenPrediction, TokenPrediction, mtp_lm_loss  # noqa: E402

TIGHT = 2e-5
VOCAB, SEQ, BATCH, EXPERTS, TOP_K = 97, 32, 2, 16, 4
LAMBDA, ALPHA, GAMMA = 0.3, 1e-4, 1e-3
SIZES = dict(
    embed_dim=64, num_layers=3, num_heads=2, q_rank=48, kv_rank=32, nope_ch=16, rope_ch=8, v_ch=16,
    mlp_ch=96, expert_ch=32, num_experts=EXPERTS, top_k=TOP_K, loss_block_tokens=16,
)


def model_file(held=(0, EXPERTS)):
    """What ``benchmark/configs/joyai_llm_flash.json`` holds, at the toy sizes."""
    return {
        "hidden_size": 64, "num_layers": 3, "num_attention_heads": 2, "q_lora_rank": 48,
        "kv_lora_rank": 32, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
        "intermediate_size": 96, "moe_intermediate_size": 32, "n_shared_experts": 1,
        "n_routed_experts_published": EXPERTS, "expert_offset": held[0], "n_routed_experts": held[1],
        "num_experts_per_tok": TOP_K, "routed_scaling_factor": 2.5, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 1, "rope_theta": 32000000, "rms_norm_eps": 1e-6,
        "vocab_size": VOCAB,
        "recipe": {"mtp_lambda": LAMBDA, "balance_alpha": ALPHA, "bias_update_rate": GAMMA},
    }


def build(dtype=jnp.float32, **overrides):
    return create_model("joyai_llm_flash", num_classes=VOCAB, dtype=dtype, **{**SIZES, **overrides})


def draw(model, tokens, seed=11):
    abstract = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens[:, :-1], is_training=False)
    )["params"]
    return weights.draw_params(abstract, seed)


@pytest.fixture(scope="module", autouse=True)
def _leave_no_live_buffers():
    """The jitted closures here hold their constants in jax's caches; tests
    that rank the process's live buffers (``test_memdump.py``) may share this
    worker."""
    yield
    jax.clear_caches()
    gc.collect()


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (BATCH, SEQ + 1), 0, VOCAB, jnp.int32)


@pytest.fixture(scope="module")
def params(tokens):
    return draw(build(), tokens)


def close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) <= tol * float(np.max(np.abs(want)))


def program_terms(model, params, tokens, bias=None):
    rows = SIZES["num_layers"] - 1 + 1
    bias = jnp.zeros((rows, EXPERTS)) if bias is None else bias
    out, state = model.apply(
        {"params": params, "batch_stats": {"select_bias": bias}}, tokens[:, :-1],
        is_training=True, targets=tokens[:, 1:], mutable=["batch_stats", "losses"],
    )
    balance = sum(jnp.sum(x) for x in jax.tree.leaves(state["losses"]))
    return out, balance, state["batch_stats"]["select_bias"]


def program_loss(model, params, tokens):
    out, balance, _ = program_terms(model, params, tokens)
    return mtp_lm_loss(out["ce"], out["ce_mtp"], LAMBDA)[0] + ALPHA * balance


def reference_loss(params, tokens, model=None):
    model = model or model_file()
    bias = reference.initial_bias(model)
    with jax.default_matmul_precision("highest"):
        return sum(reference.sequence_loss(params, bias, row, model, len(tokens))[0] for row in tokens)


# ------------------------------------------------ program against reference


def test_registry_names_the_mtp_task_and_the_layout_is_the_configurations(params):
    assert model_task("joyai_llm_flash") == "tokens_mtp" and model_task("ouro_2_6b") == "tokens"
    assert issubclass(TASKS["tokens_mtp"], TokenPrediction) and issubclass(LoopedTokenPrediction, TokenPrediction)
    for shared in ("dummy_input", "rows", "batch_dim", "prepare", "apply_kwargs"):
        assert shared not in vars(TASKS["tokens_mtp"]) and shared not in vars(LoopedTokenPrediction)
    reference.check_layout(params, model_file())
    with pytest.raises(ValueError, match="is not the configuration's"):
        reference.check_layout(params, model_file(held=(0, 4)))


def test_the_seeded_routing_has_a_margin(params, tokens):
    """Top-k is discrete: the comparisons below mean something only while no
    token's k-th and (k+1)-th scores are within rounding of each other."""
    model = model_file()
    h = params["embed"]["embedding"][tokens[0, :-1]]
    with jax.default_matmul_precision("highest"):
        h, _, _ = reference.layer(h, params["layer_0"], None, model)
        x = reference.rms_norm(h, params["layer_1"]["ffn_norm"], 1e-6)
        scores, _, _ = reference.route(x, params["layer_1"]["moe"], 0.0, model)
    ranked = jnp.sort(scores, axis=-1)[:, ::-1]
    assert float(jnp.min(ranked[:, TOP_K - 1] - ranked[:, TOP_K])) > 1e-5


def test_main_logits_match_the_reference(params, tokens):
    out = build().apply(
        {"params": params, "batch_stats": {"select_bias": jnp.zeros((3, EXPERTS))}},
        tokens[:, :-1], is_training=False,
    )
    assert out["logits"].shape == (BATCH, SEQ, VOCAB)
    model = model_file()
    with jax.default_matmul_precision("highest"):
        for b in range(BATCH):
            h = params["embed"]["embedding"][tokens[b, :-1]]
            for i in range(3):
                h, _, _ = reference.layer(h, params[f"layer_{i}"], jnp.zeros((EXPERTS,)) if i else None, model)
            want = reference.rms_norm(h, params["final_norm"], 1e-6) @ params["lm_head"]["kernel"]
            assert close(out["logits"][b], want)


@pytest.fixture(scope="module")
def reference_loss_and_grad(params, tokens):
    return jax.jit(jax.value_and_grad(reference_loss))(params, tokens)


@pytest.mark.parametrize("remat", [False, True])
def test_loss_and_gradient_match_the_reference(params, tokens, reference_loss_and_grad, remat):
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(build(remat=remat), p, tokens)))(params)
    want_loss, want = reference_loss_and_grad
    assert abs(float(loss) - float(want_loss)) <= TIGHT * float(want_loss)
    flat, want_flat = jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)
    scale = max(float(jnp.max(jnp.abs(w))) for w in want_flat)
    for (path, got), w in zip(flat, want_flat):
        assert float(jnp.max(jnp.abs(got - w))) <= TIGHT * scale, jax.tree_util.keystr(path)


def test_every_leaf_takes_a_gradient_and_the_mtp_terms_are_the_references(params, tokens):
    out, _, _ = program_terms(build(), params, tokens)
    model = model_file()
    with jax.default_matmul_precision("highest"):
        for b in range(BATCH):
            ce, ce_mtp, _, counts = reference.sequence_terms(params, reference.initial_bias(model), tokens[b], model)
            assert close(out["ce"][b], ce) and close(out["ce_mtp"][b, :-1], ce_mtp)
            assert np.array_equal(np.asarray(out["moe_counts"][b]), np.asarray(counts))
    _, want = jax.jit(jax.value_and_grad(reference_loss))(params, tokens)
    assert all(float(jnp.max(jnp.abs(g))) > 0 for g in jax.tree.leaves(want))


def test_the_last_positions_mtp_term_is_exactly_zero_in_value_and_gradient(params, tokens):
    model = build()

    def mtp_terms(p, targets):
        out, _ = model.apply(
            {"params": p, "batch_stats": {"select_bias": jnp.zeros((3, EXPERTS))}}, tokens[:, :-1],
            is_training=True, targets=targets, mutable=["losses"],
        )
        return out["ce_mtp"]

    targets = tokens[:, 1:]
    ce_mtp = mtp_terms(params, targets)
    assert np.all(np.asarray(ce_mtp[:, -1]) == 0.0) and np.all(np.asarray(ce_mtp[:, :-1]) > 0.0)
    grads = jax.grad(lambda p: jnp.sum(mtp_terms(p, targets)[:, -1]))(params)
    assert all(np.all(np.asarray(g) == 0.0) for g in jax.tree.leaves(grads))
    # The module's input at position i is token i + 1: another last target
    # changes the last position's input and nothing that is scored.
    other = targets.at[:, -1].set((targets[:, -1] + 1) % VOCAB)
    assert np.array_equal(np.asarray(mtp_terms(params, other)[:, :-2]), np.asarray(ce_mtp[:, :-2]))


# ------------------------------------------------- the task through the trainer


def _trainer(compute_dtype="float32", held=(4, 8), **overrides):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    cfg = TrainConfig(
        model_name="joyai_llm_flash", num_classes=VOCAB, compute_dtype=compute_dtype,
        global_batch_size=BATCH, model_overrides={**SIZES, "remat": True, "experts_held": list(held)},
        label_smoothing=0.0, warmup_epochs=0, base_lr=3e-4, lr_scaling_divisor=BATCH,
        weight_decay=0.1, aux_loss_weight=ALPHA, log_every_steps=1, fleet=False,
        transpose_images=False, **overrides,
    )
    return Trainer(cfg, mesh=create_mesh({"data": 1}, devices=jax.devices()[:1]))


@pytest.fixture(scope="module")
def batches():
    return [
        jax.random.randint(jax.random.PRNGKey(20 + i), (BATCH, SEQ + 1), 0, VOCAB, jnp.int32)
        for i in range(3)
    ]


@pytest.fixture(scope="module")
def held_params(tokens):
    return draw(build(experts_held=(4, 8)), tokens)


@pytest.fixture(scope="module")
def fitted(held_params, batches):
    trainer = _trainer()
    state = trainer.init_state(0).replace(params=jax.tree.map(jnp.array, held_params))
    assert float(jnp.max(jnp.abs(state.batch_stats["select_bias"]))) == 0.0
    state, history = trainer.fit(iter({"tokens": np.asarray(b)} for b in batches), num_steps=3, state=state)
    return trainer, state, [h for h in history if "loss" in h]


@pytest.fixture(scope="module")
def reference_steps(held_params, batches):
    trainer = _trainer()
    hp = {k: getattr(trainer.config, k) for k in (
        "base_lr", "global_batch_size", "lr_scaling_divisor", "num_train_images", "warmup_epochs",
        "num_epochs", "end_lr", "weight_decay", "clip_grad_norm",
    )}
    return reference.follow_steps(held_params, batches, hp, model_file(held=(4, 8)))


def test_fit_runs_the_mtp_task_and_three_updates_match_the_reference(held_params, fitted, reference_steps):
    _, state, logged = fitted
    assert len(logged) == 3 and int(state.step) == 3
    for m, want in zip(logged, reference_steps["losses"]):
        assert abs(m["loss"] - want) <= TIGHT * want
        assert m["tokens"] == BATCH * SEQ
        assert m["loss"] == pytest.approx(m["loss_main"] + LAMBDA * m["loss_mtp"] + ALPHA * m["aux_loss"], rel=1e-5)
        assert 0.3 < m["moe_held_share"] < 0.7 and m["moe_load_max_over_mean"] >= 1.0
        assert m["moe_overflow_share"] == 0.0 and 0.3 < m["moe_rows_over_bound"] < 0.7
    assert logged[-1]["moe_bias_abs_max"] == pytest.approx(3 * GAMMA, rel=1e-5)
    change = [np.asarray(a) - np.asarray(b) for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(held_params))]
    scale = max(float(np.max(np.abs(c))) for c in reference_steps["change"])
    assert scale > 1e-4  # the weights moved
    for got, want in zip(change, reference_steps["change"]):
        assert float(np.max(np.abs(got - want))) <= 2e-3 * scale


@pytest.fixture(scope="module")
def long_sequences():
    """512 tokens x 4 routings a layer with experts 4..7 of 16 held: the routed
    buffers hold 1,024 of the 2,048 rows (at ``SEQ`` one tile holds them all)."""
    held, batch = (4, 4), jax.random.randint(jax.random.PRNGKey(30), (BATCH, 257), 0, VOCAB, jnp.int32)
    return _trainer(held=held), draw(build(experts_held=held), batch), batch, held


@pytest.mark.parametrize("routing", ["seeded", "every_token_onto_the_held_experts"])
def test_fit_logs_how_often_the_overflow_pass_ran_and_drops_nothing(long_sequences, routing):
    trainer, held_params, batch, held = long_sequences
    onto_held = (np.arange(EXPERTS) >= held[0]) & (np.arange(EXPERTS) < sum(held))
    bias = np.zeros((3, EXPERTS), np.float32) + (10.0 * onto_held if routing != "seeded" else 0.0)
    state = trainer.init_state(0).replace(params=jax.tree.map(jnp.array, held_params))
    state = state.replace(batch_stats={"select_bias": jnp.array(bias)})  # the step donates its state
    _, history = trainer.fit(iter([{"tokens": np.asarray(batch)}]), num_steps=1, state=state)
    (m,) = [h for h in history if "loss" in h]
    with jax.default_matmul_precision("highest"):
        want = float(sum(reference.sequence_loss(held_params, bias, row, model_file(held), BATCH)[0] for row in batch))
    assert abs(m["loss"] - want) <= TIGHT * want  # exact at either routing: no token is dropped
    if routing == "seeded":
        assert m["moe_overflow_share"] == 0.0 and 0.3 < m["moe_rows_over_bound"] < 0.7
        assert 0.15 < m["moe_held_share"] < 0.35
    else:
        # Two routed layers and the module's: all three took the overflow
        # pass, each with all 2,048 routings on the four held experts.
        assert m["moe_overflow_share"] == 1.0 and m["moe_rows_over_bound"] == 2.0
        assert m["moe_held_share"] == 1.0


def test_the_selection_bias_after_three_steps_is_the_references(fitted, reference_steps):
    _, state, _ = fitted
    bias = np.asarray(state.batch_stats["select_bias"])
    assert bias.shape == (3, EXPERTS) and np.array_equal(bias, reference_steps["select_bias"])
    assert set(np.round(np.abs(bias) / GAMMA).astype(int).ravel()) <= {0, 1, 2, 3} and np.any(bias != 0)


def test_the_selection_bias_takes_no_gradient_no_decay_and_survives_a_checkpoint(fitted, tmp_path):
    trainer, state, _ = fitted
    # Not a parameter: the optimizer has never seen it.
    assert not any("select_bias" in jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(state.params))
    assert not any("select_bias" in jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(state.opt_state))
    # No gradient reaches it through the selection.
    tokens = jnp.zeros((BATCH, SEQ + 1), jnp.int32).at[:, ::3].set(5)

    def loss(bias):
        out, _ = trainer.model.apply(
            {"params": state.params, "batch_stats": {"select_bias": bias}}, tokens[:, :-1],
            is_training=True, targets=tokens[:, 1:], mutable=["losses"],
        )
        return jnp.mean(out["ce"]) + jnp.sum(out["ce_mtp"])

    assert float(jnp.max(jnp.abs(jax.grad(loss)(state.batch_stats["select_bias"])))) == 0.0
    # An eval step reads it and leaves it alone.
    before = np.asarray(state.batch_stats["select_bias"])
    trainer.eval_step(state, trainer.shard_batch({"tokens": np.asarray(tokens)}))
    assert np.array_equal(before, np.asarray(state.batch_stats["select_bias"]))
    # Saved and restored with the state.
    from sav_tpu.train.checkpoint import Checkpointer

    saver = Checkpointer(str(tmp_path / "ckpt"))
    saver.save(3, state)
    saver.wait()
    fresh = trainer.init_state(1)
    assert float(jnp.max(jnp.abs(fresh.batch_stats["select_bias"]))) == 0.0
    restored = saver.restore_latest(fresh)
    assert np.array_equal(np.asarray(restored.batch_stats["select_bias"]), before)


def test_bfloat16_fails_the_float32_tolerances(params, tokens, reference_loss_and_grad):
    """The control: the same program a precision lower misses the limits the
    float32 program meets."""
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(build(jnp.bfloat16), p, tokens)))(params)
    want_loss, want = reference_loss_and_grad
    assert abs(float(loss) - float(want_loss)) > 10 * TIGHT * float(want_loss)
    assert not close(grads["lm_head"]["kernel"], want["lm_head"]["kernel"], 100 * TIGHT)
