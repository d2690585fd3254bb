"""Token-training cells: ``Trainer.fit`` on batches of ids that are already
on the device.

``train_fit``'s protocol with a token pool in place of the image pool: one
``Trainer`` and one state from ``--seed`` go through three ``fit`` calls of
one step each (the steps the reference follows), then one ``fit`` call for
warm-up and the measured window (``train_fit.Window`` is its ``log_fn``).
After the window the state is freed and the configuration's float32
reference follows the same three steps from the same seeded weights and
batches. The comparison, the clock and the record's keys are
``train_fit``'s; what differs is what a batch is.

A batch is ``per_chip_batch x chips`` sequences of ``sequence_length + 1``
int32 ids, uniform over the vocabulary, drawn on the device from
``--seed``. The record counts *sequences* where ``train_fit`` counts images
(``images``, ``images_per_step_per_chip``, ``train_flops_per_image``), so
the end-to-end rate is sequences per second per chip, and adds ``tokens``
(predicted positions in the window).
"""

from __future__ import annotations

import importlib
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import compare, tracered, weights
from benchmark.drivers.train_fit import (
    CompileCounter,
    PoolFeed,
    Window,
    _host_leaves,
    _leaf_norms,
    _phases,
    _relative_difference,
    family,
    first_gradient,
)

KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'


def train_config(cell: dict, seed: int, overrides: dict):
    from sav_tpu.train import TrainConfig

    config, mix = cell["config"], cell["mix"]
    fields = {
        "model_name": config["model_name"],
        "num_classes": config["vocab_size"],
        "compute_dtype": config["compute_dtype"],
        "global_batch_size": config["train"]["per_chip_batch"] * cell["chips"],
        "seed": seed & 0x7FFFFFFF,
        "model_overrides": {
            "num_layers": config["num_layers"],
            "remat": config["train"]["remat"],
            # Toy widths for the tests under benchmark/tests; no published
            # configuration has the key.
            **config.get("model_overrides", {}),
        },
        **mix["train_config"],
        **overrides,
    }
    return TrainConfig(**fields)


def draw_pool(cell: dict, cfg, seed: int) -> list:
    """``pool_batches`` batches of ids ``[B, S + 1]``, on the device, in one call."""
    count, config = cell["mix"]["pool_batches"], cell["config"]
    shape = (count, cfg.global_batch_size, config["sequence_length"] + 1)

    def draw(key):
        return jax.random.randint(
            jax.random.fold_in(key, 0x746F), shape, 0, config["vocab_size"], jnp.int32
        )

    tokens = jax.jit(draw)(weights.seed_key(seed))
    return [tokens[i] for i in range(count)]


def build(cell: dict, seed: int, overrides: dict, mark=lambda label: None):
    """The one trainer, its seeded state and the placed pool."""
    from sav_tpu.train import Trainer

    cfg = train_config(cell, seed, overrides)
    mark("program_imported")
    trainer = Trainer(cfg)
    mark("trainer_built")
    state = jax.block_until_ready(trainer.init_state(cfg.seed))
    mark("state_initialised")
    family(cell).check_layout(state.params, cell["config"])
    shardings = jax.tree.map(lambda x: x.sharding, state.params)
    state = state.replace(params=weights.draw_params(state.params, seed, shardings))
    jax.block_until_ready(state.params)
    mark("weights_drawn")
    pool = [trainer.shard_batch({"tokens": t}) for t in draw_pool(cell, cfg, seed)]
    jax.block_until_ready(pool)
    mark("pool_placed")
    return trainer, state, pool


def reference_side(cell: dict, cfg, seed: int, abstract_params) -> dict:
    """The reference over the followed steps, from the same seed: weights
    and batches are drawn again, now that the program's are freed."""
    hp = {k: getattr(cfg, k) for k in (
        "base_lr", "global_batch_size", "lr_scaling_divisor", "num_train_images",
        "warmup_epochs", "num_epochs", "end_lr", "weight_decay", "clip_grad_norm",
    )}
    hp["entropy_weight"] = cell["config"]["recipe"]["entropy_weight"]
    params0 = weights.draw_params(abstract_params, seed)
    batches = draw_pool(cell, cfg, seed)[: cell["mix"]["followed_steps"]]
    return family(cell).follow_steps(params0, batches, hp, cell["config"])


def kernel_calls(hlo_text: str) -> dict:
    """Instruction name -> ``op_name`` of every Mosaic kernel call in a
    compiled module's text."""
    calls = {}
    for line in hlo_text.splitlines():
        if KERNEL_TARGET in line:
            found = tracered._INSTRUCTION.match(line)
            scope = tracered._OP_NAME.search(line)
            if found:
                calls[found.group(1)] = scope.group(1) if scope else ""
    return calls


def run(cell: dict, seed: int, seconds: float, tracer, overrides: dict) -> dict:
    with CompileCounter() as compiles:
        return _run(cell, seed, seconds, tracer, overrides, compiles)


def _run(cell: dict, seed: int, seconds: float, tracer, overrides: dict, compiles) -> dict:
    mix, config = cell["mix"], cell["config"]
    marks = [("start", time.perf_counter())]

    def mark(label: str) -> None:
        marks.append((label, time.perf_counter()))

    trainer, state, pool = build(cell, seed, overrides, mark)
    cfg = trainer.config
    followed = mix["followed_steps"]
    if followed >= len(pool):
        raise ValueError("the pool must hold more batches than the steps the reference follows")
    # fit donates the state it is given: the seeded weights are kept on the
    # host, for the parameters' change after the followed steps.
    start_params = _host_leaves(state.params)
    mark("start_weights_on_host")

    # 1. The followed steps, one fit call each.
    step_losses, first_grad = [], None
    for k in range(followed):
        state, history = trainer.fit(
            PoolFeed(pool, k, threading.Event()), num_steps=k + 1, state=state
        )
        step_losses.append(float(next(h["loss"] for h in history if "loss" in h)))
        if k == 0:
            first_grad = first_gradient(state.opt_state, state.params)
        mark(f"fit_step{k + 1}")
    change = [after - before for after, before in zip(_host_leaves(state.params), start_params)]
    del start_params
    mark("change_on_host")
    # The step program's own account of itself (see train_fit: the process's
    # compilation cache answers, no second compile).
    compiled = trainer.compile_train_step(state, pool[0], jax.random.PRNGKey(0))
    resident_bytes = max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.local_devices())
    step_temp_bytes = compiled.memory_analysis().temp_size_in_bytes
    hlo_scopes = calls = None
    if tracer is not None:
        text = compiled.as_text()
        hlo_scopes, calls = tracered.scopes_of_hlo(text), kernel_calls(text)
        del text
    del compiled
    mark("step_program_read")

    # 2. Warm-up and the window, one fit call.
    stop = threading.Event()
    window = Window(mix, seconds, tracer, stop, compiles)
    state, _ = trainer.fit(
        PoolFeed(pool, followed, stop), num_steps=10**9, state=state, log_fn=window
    )
    jax.block_until_ready(state)
    marks.append(("window_opened", window.opened_t))
    mark("fit_returned")
    compiles_in_window = compiles.count - window.compiles_at_open
    if len(window.boundaries) < 2:
        raise RuntimeError(f"the window saw {len(window.boundaries)} log boundaries; it needs two")
    (step0, t0), (step1, t1) = window.boundaries[0], window.boundaries[-1]
    sequences = (step1 - step0) * cfg.global_batch_size
    per_step = [
        (tb - ta) / (sb - sa)
        for (sa, ta), (sb, tb) in zip(window.boundaries, window.boundaries[1:])
    ]
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
    del state, pool

    # 3. The reference, after the program's state is freed; its time is
    # reported apart and is no part of setup_s.
    ref_t0 = time.perf_counter()
    ref = reference_side(cell, cfg, seed, abstract)
    reference_s = time.perf_counter() - ref_t0

    numbers = {
        f"loss_gap.step{k + 1}": compare.relative_gap(step_losses[k], ref["losses"][k])
        for k in range(followed)
    }
    numbers["first_grad_norm_gap"] = compare.worst_leaf_norm_gap(
        _leaf_norms(first_grad), _leaf_norms(ref["first_grad"]))
    numbers["update_norm_gap"] = compare.worst_leaf_norm_gap(
        _leaf_norms(change), _leaf_norms(ref["change"]))
    numbers["first_grad_rel_diff"] = _relative_difference(first_grad, ref["first_grad"])
    numbers["update_rel_diff"] = _relative_difference(change, ref["change"])
    numbers["nonfinite_losses"] = float(sum(not math.isfinite(x) for x in window.losses + step_losses))
    numbers["compiles_in_window"] = float(compiles_in_window)

    traced_steps, traced_window_s = window.traced or (None, None)
    flops = importlib.import_module("benchmark.flops." + config["flops"])
    return {
        "attempted": step1 - step0,
        "failed": int(numbers["nonfinite_losses"]),
        "window_opened_t": window.opened_t,
        "reference_s": reference_s,
        "memory": {
            "resident_bytes": int(resident_bytes),
            "step_temp_bytes": int(step_temp_bytes),
        },
        "hlo_scopes": hlo_scopes,
        "kernel_calls": calls,
        "config": config,
        "phases_s": _phases(marks),
        "end_to_end": {"train_img_s_chip": sequences / (t1 - t0) / cell["chips"]},
        "numbers": numbers,
        "traced_window_s": traced_window_s,
        "spans": {
            "log_window_step_s": per_step,
            "traced_steps": traced_steps,
        },
        "counters": {
            "images": sequences,
            "images_per_step_per_chip": cfg.global_batch_size // cell["chips"],
            "train_flops_per_image": flops.train_flops_per_image(config),
            "tokens": sequences * config["sequence_length"],
        },
    }
