"""The plain reference against the program, both in float32, on seeded
weights at a toy size: forward, loss and gradient, and three AdamW updates
against the program's optimizer chain."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import weights
from benchmark.reference import vit as reference

HP = {
    "base_lr": 5e-4, "global_batch_size": 8, "lr_scaling_divisor": 512,
    "num_train_images": 1_281_167, "warmup_epochs": 0, "num_epochs": 300,
    "end_lr": 1e-5, "weight_decay": 0.05, "clip_grad_norm": 1.0, "label_smoothing": 0.1,
}


@pytest.fixture(scope="module")
def toy():
    from sav_tpu.models import create_model

    model = create_model("deit_s_patch16", num_classes=10, dtype=jnp.float32,
                         embed_dim=32, num_layers=2, num_heads=2)
    shapes = jax.eval_shape(
        lambda r: model.init({"params": r}, jnp.zeros((2, 32, 32, 3)), is_training=False),
        jax.random.PRNGKey(0),
    )["params"]
    params = weights.draw_params(shapes, 2**31 + 5)
    batches = weights.draw_batches(2**31 + 5, 3, 8, 32, 10)
    return model, params, batches


def program_loss(model, params, images, labels):
    from sav_tpu.ops import preprocess
    from sav_tpu.utils.metrics import cross_entropy
    import optax

    logits = model.apply({"params": params}, preprocess.normalize_images(images, jnp.float32),
                         is_training=True, rngs={"dropout": jax.random.PRNGKey(0),
                                                 "stochastic_depth": jax.random.PRNGKey(1)})
    probs = optax.smooth_labels(jax.nn.one_hot(labels, 10), 0.1)
    return cross_entropy(logits, probs)


def test_every_leaf_is_drawn_and_seeds_differ(toy):
    _, params, _ = toy
    assert all(float(jnp.abs(x).max()) > 0 for x in jax.tree.leaves(params))
    again = weights.draw_params(params, 2**31 + 5)
    other = weights.draw_params(params, 2**31 + 6)
    assert all(np.array_equal(a, b) for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(again)))
    assert not np.array_equal(params["head"]["kernel"], other["head"]["kernel"])


def test_forward_agrees(toy):
    from sav_tpu.ops import preprocess

    model, params, batches = toy
    images = batches[0][0]
    program = model.apply({"params": params}, preprocess.normalize_images(images, jnp.float32),
                          is_training=False)
    ours = reference.make_forward(4)(params, images)
    assert float(jnp.abs(ours).max()) > 0.5
    np.testing.assert_allclose(ours, program, atol=2e-5)


def test_loss_and_gradient_agree(toy):
    model, params, batches = toy
    images, labels = batches[0]
    loss, grads = reference.make_loss_and_grad(0.1, 4)(params, images, labels)
    want_loss, want_grads = jax.value_and_grad(lambda p: program_loss(model, p, images, labels))(params)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    for ours, theirs in zip(jax.tree.leaves(grads), jax.tree.leaves(want_grads)):
        np.testing.assert_allclose(ours, theirs, atol=1e-5 * float(jnp.abs(theirs).max()) + 1e-8)


def test_three_updates_agree_with_the_programs_optimizer(toy):
    from sav_tpu.train.optimizer import make_optimizer, warmup_cosine_schedule
    import optax

    model, params, batches = toy
    schedule = warmup_cosine_schedule(
        HP["base_lr"] * HP["global_batch_size"] / HP["lr_scaling_divisor"],
        steps_per_epoch=HP["num_train_images"] // HP["global_batch_size"],
        warmup_epochs=0, num_epochs=300, end_lr=1e-5,
    )
    assert [reference.learning_rate(c, HP) for c in range(3)] == pytest.approx(
        [float(schedule(c)) for c in range(3)], rel=1e-6)
    tx = make_optimizer(schedule, weight_decay=0.05, clip_grad_norm=1.0, fused=True)
    theirs, opt_state = params, tx.init(params)
    for images, labels in batches:
        grads = jax.grad(lambda p: program_loss(model, p, images, labels))(theirs)
        updates, opt_state = tx.update(grads, opt_state, theirs)
        theirs = optax.apply_updates(theirs, updates)
    ours = reference.follow_steps(params, batches, HP, 4)
    moved = 0.0
    for start, a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ours["params"]), jax.tree.leaves(theirs)):
        change = float(jnp.abs(b - start).max())
        moved = max(moved, change)
        np.testing.assert_allclose(a - start, b - start, atol=2e-3 * change + 1e-9)
    assert moved > 0
