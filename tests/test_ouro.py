"""The Ouro family (looped causal language model) against its plain float32
reference, at toy sizes on the CPU: hidden 64, 2 layers, 4 passes,
vocabulary 256, 32 positions. Also the pieces it brought: the causal mask on
the dense path and in the flash kernel (interpret mode), rotate-halves
rotary at base 1e6, the exit distribution and its loss, the token task
through ``Trainer.fit``.

Tolerances. Program and reference both compute in float32 here, the
reference with ``highest`` matmuls, in different orders (fused QKV slices,
log-space exit distribution, a blocked cross-entropy): they agree to a few
float32 roundings through 8 layer applications, so ``TIGHT`` = 2e-5 of the
compared tensor's largest entry. The same program in bfloat16 (the control:
the nearest precision below) misses every one of them by two orders, which
``test_bfloat16_fails_the_float32_tolerances`` shows."""

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
from benchmark.reference import ouro as reference  # noqa: E402
from sav_tpu.models import create_model, model_task  # noqa: E402
from sav_tpu.ops.attention import (  # noqa: E402
    dot_product_attention,
    resolve_attention_backend,
    xla_attention,
)
from sav_tpu.ops.flash_attention import flash_attention  # noqa: E402
from sav_tpu.ops.rotary import apply_rotary_half, half_split_tables  # noqa: E402
from sav_tpu.train.tasks import exit_distribution, looped_lm_loss  # noqa: E402

TIGHT = 2e-5
SIZES = dict(embed_dim=64, num_layers=2, num_heads=4, head_ch=16, mlp_ch=96, loss_block_tokens=16)
MODEL = {"total_ut_steps": 4, "rope_theta": 1e6, "rms_norm_eps": 1e-6}
VOCAB, SEQ, BATCH, BETA = 256, 32, 2, 0.1


def build(dtype=jnp.float32, **overrides):
    return create_model("ouro_2_6b", num_classes=VOCAB, dtype=dtype, **{**SIZES, **overrides})


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(3), (BATCH, SEQ + 1), 0, VOCAB, jnp.int32)


@pytest.fixture(scope="module")
def params(tokens):
    abstract = jax.eval_shape(
        lambda: build().init({"params": jax.random.PRNGKey(0)}, tokens[:, :-1], is_training=False)
    )["params"]
    return weights.draw_params(abstract, 11)


def close(got, want, tol=TIGHT):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want))) <= tol * float(np.max(np.abs(want)))


def program_loss(model, params, tokens):
    out = model.apply({"params": params}, tokens[:, :-1], is_training=True, targets=tokens[:, 1:])
    return looped_lm_loss(out["ce"], out["exit_logit"], BETA)[0]


def reference_loss(params, tokens):
    with jax.default_matmul_precision("highest"):
        total = sum(reference.sequence_loss_sum(params, row, MODEL, BETA) for row in tokens)
    return total / (tokens.shape[0] * (tokens.shape[1] - 1))


# ------------------------------------------------ program against reference


def test_registry_names_the_token_task_and_holds_each_layer_once(params):
    assert model_task("ouro_2_6b") == "tokens" and model_task("deit_s_patch16") == "image"
    stack = params["ut_loop"]
    assert sorted(k for k in stack if k.startswith("layer_")) == ["layer_0", "layer_1"]
    reference.check_layout(params, {
        "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16, "intermediate_size": 96,
        "vocab_size": VOCAB, "num_layers": 2,
    })
    with pytest.raises(ValueError, match="is not the configuration's"):
        reference.check_layout(params, {
            "hidden_size": 64, "num_attention_heads": 4, "head_dim": 16, "intermediate_size": 96,
            "vocab_size": VOCAB, "num_layers": 8,
        })


@pytest.mark.parametrize("ut_pass", range(4))
def test_each_passes_logits_and_gate_match_the_reference(params, tokens, ut_pass):
    out = build().apply({"params": params}, tokens[:, :-1], is_training=False)
    logits, lam = reference.make_forward(MODEL)(params, tokens[:, :-1])
    assert out["logits"].shape == (BATCH, 4, SEQ, VOCAB)
    assert close(out["logits"][:, ut_pass], logits[:, ut_pass])
    assert close(jax.nn.sigmoid(out["exit_logit"][..., ut_pass]), lam[:, ut_pass])


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


def test_remat_changes_no_loss_and_no_gradient_leaf(params, tokens):
    """What is kept and what is computed again is no part of the mathematics:
    the same float32 operations on the same operands."""
    (loss, grads), (want_loss, want) = (
        jax.jit(jax.value_and_grad(lambda p: program_loss(build(remat=remat), p, tokens)))(params)
        for remat in (True, False)
    )
    assert abs(float(loss) - float(want_loss)) <= 1e-6 * float(want_loss)
    for (path, got), w in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(want)):
        assert close(got, w, 1e-6), jax.tree_util.keystr(path)


# One layer application's backward pass, read from its jaxpr: the equation
# flax's nn.remat leaves there holds the recomputed forward and the backward.
B, L, C, H, D, M = 2, 24, 64, 4, 8, 96
# A forward matmul by its operands' shapes (the backward's have others).
FORWARD_MATMULS = {
    "to_qkv": ((B, L, C), (C, H, D)),
    "to_out": ((B, L, H, D), (H, D, C)),
    "fc1": ((B, L, C), (C, M)),  # gate and up
    "fc2": ((B, L, M), (M, C)),
}


def _subjaxprs(eqn):
    for value in eqn.params.values():
        inner = getattr(value, "jaxpr", value)
        if hasattr(inner, "eqns"):
            yield inner


def _count(jaxpr, counts):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general":
            shapes = tuple(v.aval.shape for v in eqn.invars)
            for name, want in FORWARD_MATMULS.items():
                counts[name] += shapes == want
        counts[eqn.primitive.name] += 1
        for inner in _subjaxprs(eqn):
            _count(inner, counts)
    return counts


def _recomputed_in_one_layer_application(kept, monkeypatch, backend="xla"):
    """Counts over the remat equation of ``grad(LoopedStack)``'s jaxpr with
    ``kept`` as the stack's policy."""
    from collections import Counter

    from sav_tpu.models import ouro

    monkeypatch.setattr(ouro, "KEPT_UNDER_REMAT", kept)
    stack = ouro.LoopedStack(
        num_layers=1, num_heads=H, head_ch=D, mlp_ch=M, rope_theta=1e6, norm_eps=1e-6, remat=True,
        backend=backend,
    )
    x = jnp.ones((B, L, C))
    variables = jax.eval_shape(lambda: stack.init(jax.random.PRNGKey(0), x))
    jaxpr = jax.make_jaxpr(jax.grad(lambda v, x: jnp.sum(stack.apply(v, x))))(variables, x).jaxpr
    remat = [e for e in jaxpr.eqns if e.primitive.name in ("remat2", "checkpoint", "remat")]
    assert len(remat) == 1
    return _count(next(_subjaxprs(remat[0])), Counter())


def test_the_policy_keeps_the_named_projections_and_recomputes_the_rest(monkeypatch):
    from sav_tpu.models.ouro import KEPT_UNDER_REMAT

    assert set(KEPT_UNDER_REMAT) == {"attn_qkv", "flash_out", "flash_lse", "ffn_gate", "ffn_up", "ffn_out"}
    kept = _recomputed_in_one_layer_application(KEPT_UNDER_REMAT, monkeypatch)
    whole = _recomputed_in_one_layer_application((), monkeypatch)
    # Whole-block remat runs every forward matmul of the layer a second time ...
    assert [whole[k] for k in ("to_qkv", "to_out", "fc1", "fc2")] == [3, 1, 2, 1]
    # ... the policy none of the named ones; to_out, whose output is tagged
    # (attn_out) but not listed, runs again.
    assert [kept[k] for k in ("to_qkv", "to_out", "fc1", "fc2")] == [0, 1, 0, 0]
    assert whole["dot_general"] - kept["dot_general"] == 6
    everything = _recomputed_in_one_layer_application(KEPT_UNDER_REMAT + ("attn_out",), monkeypatch)
    assert everything["to_out"] == 0
    # Both compute the four norms and the SiLU again: it is still remat per
    # layer application.
    assert kept["rsqrt"] == whole["rsqrt"] == 4
    assert kept["logistic"] == whole["logistic"] == 1


@pytest.mark.parametrize("name,matmul,runs", [
    ("attn_qkv", "to_qkv", 3), ("ffn_gate", "fc1", 1), ("ffn_up", "fc1", 1), ("ffn_out", "fc2", 1),
])
def test_a_name_the_policy_does_not_list_is_recomputed(name, matmul, runs, monkeypatch):
    from sav_tpu.models.ouro import KEPT_UNDER_REMAT

    counts = _recomputed_in_one_layer_application(
        tuple(n for n in KEPT_UNDER_REMAT if n != name), monkeypatch
    )
    assert {k: counts[k] for k in FORWARD_MATMULS} == {"to_qkv": 0, "to_out": 1, "fc1": 0, "fc2": 0, matmul: runs}


@pytest.mark.parametrize("policy", ["kept", "whole"])
def test_the_flash_forward_runs_once_under_the_policy(policy, monkeypatch):
    from sav_tpu.models.ouro import KEPT_UNDER_REMAT

    counts = _recomputed_in_one_layer_application(
        KEPT_UNDER_REMAT if policy == "kept" else (), monkeypatch, backend="pallas"
    )
    # The one backward call; whole-block remat runs the forward kernel before it.
    assert counts["pallas_call"] == (1 if policy == "kept" else 2)
    assert counts["to_qkv"] == (0 if policy == "kept" else 3)


def test_blocked_cross_entropy_is_the_full_logits_cross_entropy(params, tokens):
    model = build()
    full = model.apply({"params": params}, tokens[:, :-1], is_training=False)["logits"]
    blocked = model.apply(
        {"params": params}, tokens[:, :-1], is_training=False, targets=tokens[:, 1:]
    )["ce"]
    want = -jnp.take_along_axis(
        jax.nn.log_softmax(full, axis=-1), tokens[:, None, 1:, None], axis=-1
    )[..., 0]
    assert close(blocked, jnp.moveaxis(want, 1, -1), 1e-6)


def test_a_layers_gradient_is_the_sum_over_its_four_uses(params, tokens):
    """Unroll the loop over four copies of the stack: the shared layer's
    gradient is the sum of the copies' gradients."""
    row = tokens[0]

    def unrolled_loss(stacks):
        h = params["embed"]["embedding"][row[:-1]]
        hs = []
        for stack in stacks:
            for i in range(2):
                h = reference.layer(h, stack[f"layer_{i}"], 1e6, 1e-6)
            h = reference.rms_norm(h, stack["final_norm"], 1e-6)
            hs.append(h)
        ce = jnp.stack([
            reference._pass_cross_entropy(params["lm_head"]["kernel"], h, row[1:]) for h in hs
        ])
        p = reference.exit_distribution(jnp.stack([reference.gate_probability(params, h) for h in hs]))
        return jnp.mean(jnp.sum(p * ce, axis=0) + BETA * jnp.sum(p * jnp.log(p), axis=0))

    with jax.default_matmul_precision("highest"):
        per_use = jax.jit(jax.grad(unrolled_loss))([params["ut_loop"]] * 4)
    summed = jax.tree.map(lambda *g: sum(g), *per_use)
    shared = jax.jit(jax.grad(lambda p: program_loss(build(), p, row[None])))(params)["ut_loop"]
    scale = max(float(jnp.max(jnp.abs(g))) for g in jax.tree.leaves(summed))
    for got, want, one in zip(jax.tree.leaves(shared), jax.tree.leaves(summed), jax.tree.leaves(per_use[0])):
        assert float(jnp.max(jnp.abs(got - want))) <= TIGHT * scale
        # ... and no single use accounts for it.
        assert float(jnp.max(jnp.abs(one - want))) > 100 * TIGHT * float(jnp.max(jnp.abs(want)))


def _trainer(compute_dtype, **overrides):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    cfg = TrainConfig(
        model_name="ouro_2_6b", num_classes=VOCAB, compute_dtype=compute_dtype,
        global_batch_size=BATCH, model_overrides={**SIZES, "remat": True},
        label_smoothing=0.0, warmup_epochs=0, base_lr=3e-4, lr_scaling_divisor=BATCH,
        weight_decay=0.1, log_every_steps=1, fleet=False, transpose_images=False, **overrides,
    )
    return Trainer(cfg, mesh=create_mesh({"data": 1}, devices=jax.devices()[:1]))


def _three_steps(trainer, params, batches):
    state = trainer.init_state(0).replace(params=jax.tree.map(jnp.array, params))
    state, history = trainer.fit(iter({"tokens": np.asarray(b)} for b in batches), num_steps=3, state=state)
    return state, [h for h in history if "loss" in h]


@pytest.fixture(scope="module")
def batches():
    return [
        jax.random.randint(jax.random.PRNGKey(20 + i), (BATCH, SEQ + 1), 0, VOCAB, jnp.int32)
        for i in range(3)
    ]


@pytest.fixture(scope="module")
def reference_steps(params, batches):
    trainer = _trainer("float32")
    hp = {k: getattr(trainer.config, k) for k in (
        "base_lr", "global_batch_size", "lr_scaling_divisor", "num_train_images", "warmup_epochs",
        "num_epochs", "end_lr", "weight_decay", "clip_grad_norm",
    )}
    return reference.follow_steps(params, batches, {**hp, "entropy_weight": BETA}, MODEL)


def test_fit_runs_the_token_task_and_three_updates_match_the_reference(params, batches, reference_steps):
    state, logged = _three_steps(_trainer("float32"), params, batches)
    assert len(logged) == 3 and int(state.step) == 3
    for m, want in zip(logged, reference_steps["losses"]):
        assert abs(m["loss"] - want) <= TIGHT * want
        assert m["tokens"] == BATCH * SEQ
        assert {"loss_ut1", "loss_ut4", "exit_p1", "exit_p4", "exit_entropy", "grad_norm"} <= set(m)
        assert sum(m[f"exit_p{t}"] for t in (1, 2, 3, 4)) == pytest.approx(1.0, abs=1e-5)
    change = [np.asarray(a) - np.asarray(b) for a, b in zip(jax.tree.leaves(state.params), jax.tree.leaves(params))]
    # An update is lr x a ratio of moments: a rounding of the gradient moves
    # it by more than its own size near g = 0, so the updates are held to
    # 2e-3 of the largest (the bf16 control misses by 0.1).
    scale = max(float(np.max(np.abs(c))) for c in reference_steps["change"])
    assert scale > 1e-4  # the weights moved
    for got, want in zip(change, reference_steps["change"]):
        assert float(np.max(np.abs(got - want))) <= 2e-3 * scale


def test_bfloat16_fails_the_float32_tolerances(params, tokens, reference_loss_and_grad):
    """The control: the same program a precision lower misses the limits the
    float32 program meets, by two orders."""
    out = build(jnp.bfloat16).apply({"params": params}, tokens[:, :-1], is_training=False)
    logits, _ = reference.make_forward(MODEL)(params, tokens[:, :-1])
    assert not close(out["logits"][:, 3], logits[:, 3], 100 * TIGHT)
    loss, grads = jax.jit(jax.value_and_grad(lambda p: program_loss(build(jnp.bfloat16), p, tokens)))(params)
    want_loss, want = reference_loss_and_grad
    assert abs(float(loss) - float(want_loss)) > 10 * TIGHT * float(want_loss)
    assert not close(grads["lm_head"]["kernel"], want["lm_head"]["kernel"], 100 * TIGHT)


# ------------------------------------------------------------ causal attention


def _qkv(shape, seed=0):
    return [jax.random.normal(k, shape, jnp.float32) for k in jax.random.split(jax.random.PRNGKey(seed), 4)]


def _masked_reference(q, k, v):
    length, dim = q.shape[1], q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * dim**-0.5
    s = jnp.where(jnp.tril(jnp.ones((length, length), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v)


CAUSAL_CORES = {
    "dense": lambda q, k, v: xla_attention(q, k, v, causal=True),
    "dispatcher-xla": lambda q, k, v: dot_product_attention(q, k, v, backend="xla", causal=True, logits_dtype=jnp.float32),
    "dispatcher-pallas": lambda q, k, v: dot_product_attention(q, k, v, backend="pallas", causal=True),
    "flash-square-blocks": lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=32, block_kv=32),
    "flash-wide-kv": lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=16, block_kv=64, block_b=2),
    "flash-tall-q": lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=64, block_kv=16, block_b=1),
    "flash-padded-length": lambda q, k, v: flash_attention(q, k, v, causal=True, block_q=32, block_kv=32),
}


@pytest.mark.parametrize("core", sorted(CAUSAL_CORES))
def test_causal_core_forward_and_gradients(core):
    length = 88 if core == "flash-padded-length" else 96
    q, k, v, g = _qkv((2, length, 2, 32))
    fn = CAUSAL_CORES[core]
    assert close(fn(q, k, v), _masked_reference(q, k, v), 1e-5)
    got = jax.grad(lambda *a: jnp.sum(fn(*a) * g), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(lambda *a: jnp.sum(_masked_reference(*a) * g), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(got, want):
        assert close(a, b, 1e-5)


def test_causal_flash_with_a_bias_masks_in_the_dense_backward():
    q, k, v, g = _qkv((1, 64, 2, 32), seed=5)
    bias = jax.random.normal(jax.random.PRNGKey(9), (1, 2, 64, 64))

    def want_fn(q, k, v, bias):
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * 32**-0.5 + bias
        s = jnp.where(jnp.tril(jnp.ones((64, 64), bool)), s, -jnp.inf)
        return jnp.sum(jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1), v) * g)

    got = jax.grad(lambda q, k, v, b: jnp.sum(flash_attention(q, k, v, b, causal=True, block_q=32, block_kv=32) * g),
                   argnums=(0, 1, 2, 3))(q, k, v, bias)
    want = jax.grad(want_fn, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, b in zip(got, want):
        # the biased backward runs its matmuls in the operands' dtype, float32 here
        assert close(a, b, 1e-5)


def test_causal_refusals():
    q, k, v, _ = _qkv((1, 32, 2, 16))
    with pytest.raises(ValueError, match="no causal arm"):
        dot_product_attention(q, k, v, backend="fused", causal=True)
    with pytest.raises(ValueError, match="self-attention"):
        xla_attention(q, k[:, :16], v[:, :16], causal=True)
    with pytest.raises(ValueError, match="self-attention"):
        flash_attention(q, k[:, :16], v[:, :16], causal=True)
    with pytest.raises(ValueError, match="does not divide"):
        flash_attention(q, k, v, causal=True, block_b=3)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_a_later_token_leaves_earlier_logits_bit_equal(params, tokens, backend):
    # One layer, two passes: the interpreted kernel is slow.
    model = build(backend=backend, num_layers=1, ut_steps=2)
    inputs = tokens[:1, :-1]
    j = 19
    changed = inputs.at[:, j].set((inputs[:, j] + 7) % VOCAB)
    a = model.apply({"params": params}, inputs, is_training=False)
    b = model.apply({"params": params}, changed, is_training=False)
    assert np.array_equal(np.asarray(a["logits"][:, :, :j]), np.asarray(b["logits"][:, :, :j]))
    assert np.array_equal(np.asarray(a["exit_logit"][:, :j]), np.asarray(b["exit_logit"][:, :j]))
    assert not np.array_equal(np.asarray(a["logits"][:, :, j:]), np.asarray(b["logits"][:, :, j:]))


RESOLUTIONS = [
    # (batch, L, heads, dim, causal, devices) -> backend: today's shapes keep
    # theirs; a causal core has entries of its own and is never fused.
    ((256, 197, 6, 64, False, 1), "fused"),
    ((128, 197, 12, 64, False, 1), "fused"),
    ((96, 197, 6, 64, False, 1), "xla"),
    ((256, 197, 6, 64, False, 4), "xla"),
    ((256, 197, 6, 64, True, 1), "xla"),
    ((32, 785, 6, 64, False, 1), "xla"),
    ((2, 4096, 16, 128, True, 1), "pallas"),
    ((2, 4096, 16, 128, False, 1), "pallas"),
    ((1, 2048, 16, 128, True, 1), "xla"),
]


@pytest.mark.parametrize("shape,backend", RESOLUTIONS, ids=[str(s) for s, _ in RESOLUTIONS])
def test_auto_resolution_with_and_without_the_mask(shape, backend):
    batch, length, heads, dim, causal, devices = shape
    got = resolve_attention_backend(
        batch, length, length, heads, dim, on_tpu=True, num_devices=devices, causal=causal
    )
    assert got.backend == backend


def test_the_cells_shape_has_a_measured_causal_entry():
    from sav_tpu.ops import attn_tuning

    assert attn_tuning.shape_key(2, 4096, 4096, 16, 128, "bfloat16", True).endswith(".bfloat16.causal")
    entry = attn_tuning.lookup(2, 4096, 4096, 16, 128, "bfloat16", causal=True)
    assert entry and entry["backend"] == "pallas" and entry["fwd_bwd_ms"] > 0
    assert attn_tuning.lookup(2, 4096, 4096, 16, 128, "bfloat16") is None
    got = resolve_attention_backend(2, 4096, 4096, 16, 128, on_tpu=True, causal=True)
    assert got.block_config == attn_tuning.block_config(entry)


# -------------------------------------------------------------------- rotary


def test_rotate_halves_rotary_is_a_rotation_of_lane_pairs_at_base_1e6():
    length, dim = 40, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (1, length, 2, dim))
    y = np.asarray(apply_rotary_half(x, half_split_tables(length, dim, 1e6)), np.float64)
    x = np.asarray(x, np.float64)
    freq = 1e6 ** (-np.arange(0, dim, 2) / dim)
    turn = np.exp(1j * np.arange(length)[:, None] * freq[None, :])[None, :, None, :]
    want = (x[..., : dim // 2] + 1j * x[..., dim // 2:]) * turn
    np.testing.assert_allclose(y[..., : dim // 2], want.real, atol=1e-5)
    np.testing.assert_allclose(y[..., dim // 2:], want.imag, atol=1e-5)
    # position 0 is left alone, and the reference's rotation is the same one
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-7)
    np.testing.assert_allclose(y[0], np.asarray(reference.rotate(jnp.asarray(x[0], jnp.float32), 1e6)), atol=1e-5)


def test_rotate_halves_rotary_keeps_float32_angles_under_bfloat16():
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 4096, 1, 128))
    tables = half_split_tables(4096, 128, 1e6)
    low = apply_rotary_half(x.astype(jnp.bfloat16), tables)
    assert low.dtype == jnp.bfloat16
    err = np.abs(np.asarray(low, np.float32) - np.asarray(apply_rotary_half(x, tables)))
    assert float(err.max()) < 0.05  # one bf16 rounding of an O(4) value, at every position


# ------------------------------------------------------- exit distribution, loss


def test_exit_distribution_sums_to_one_and_matches_the_products():
    logit = jax.random.normal(jax.random.PRNGKey(4), (3, 5, 4)) * 3.0
    p, log_p = exit_distribution(logit)
    np.testing.assert_allclose(np.asarray(jnp.sum(p, axis=-1)), 1.0, atol=1e-6)
    lam = np.asarray(jax.nn.sigmoid(logit), np.float64)
    want = np.stack([
        lam[..., 0], lam[..., 1] * (1 - lam[..., 0]),
        lam[..., 2] * (1 - lam[..., 0]) * (1 - lam[..., 1]),
        (1 - lam[..., 0]) * (1 - lam[..., 1]) * (1 - lam[..., 2]),
    ], axis=-1)
    np.testing.assert_allclose(np.asarray(p), want, atol=1e-6)
    np.testing.assert_allclose(np.asarray(jnp.exp(log_p)), want, atol=1e-6)
    np.testing.assert_allclose(
        np.asarray(p), np.moveaxis(np.asarray(reference.exit_distribution(jnp.moveaxis(jax.nn.sigmoid(logit), -1, 0))), 0, -1),
        atol=1e-6,
    )


def test_looped_loss_matches_a_hand_written_case():
    # Two positions, two passes. Gates at logit 0 leave (1/2, 1/2);
    # a gate at logit ln 3 leaves (3/4, 1/4).
    ce = jnp.asarray([[2.0, 1.0], [4.0, 0.5]])
    logit = jnp.asarray([[0.0, 9.0], [np.log(3.0), -9.0]])
    loss, p, entropy = looped_lm_loss(ce, logit, 0.1)
    h0 = np.log(2.0)
    h1 = -(0.75 * np.log(0.75) + 0.25 * np.log(0.25))
    want = ((0.5 * 2.0 + 0.5 * 1.0 - 0.1 * h0) + (0.75 * 4.0 + 0.25 * 0.5 - 0.1 * h1)) / 2
    assert float(loss) == pytest.approx(want, rel=1e-6)
    np.testing.assert_allclose(np.asarray(p), [[0.5, 0.5], [0.75, 0.25]], atol=1e-6)
    np.testing.assert_allclose(np.asarray(entropy), [h0, h1], atol=1e-6)


def test_token_task_refuses_the_image_recipes_switches():
    from sav_tpu.train import TrainConfig
    from sav_tpu.train.tasks import make_task

    with pytest.raises(ValueError, match="label_smoothing=0"):
        make_task("tokens", TrainConfig(model_name="ouro_2_6b"), jnp.bfloat16)
    with pytest.raises(ValueError, match="unknown task"):
        make_task("audio", TrainConfig(), jnp.bfloat16)
