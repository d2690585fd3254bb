"""Training cells: ``Trainer.fit`` on batches that are already on the device.

One ``Trainer`` is built from the cell's files and one state from
``--seed`` (every leaf drawn by ``benchmark/weights.py``). That same
trainer and state lineage go through:

1. three ``fit`` calls of one step each, on three different batches of the
   pool: the steps the reference follows. ``fit`` has no per-step hook and
   compiles its step inside each call, so a step whose loss and state the
   harness must read is a call of its own (PERF.md, Open questions);
2. one ``fit`` call for everything else: ``warmup_log_windows`` log
   windows of warm-up, then the measured window, until the feed stops.

``fit`` calls ``log_fn`` after its own log-boundary ``device_get``, so the
callback's clock readings are synced with the device. The rate is the
images between the window's first and last boundary over the time between
them, over the chips.

With ``--trace 1`` the profiler runs over the first ``trace_log_windows``
log windows after the warm-up, started and stopped from the callback, at a
drained device; the rate window opens when it has stopped.
"""

from __future__ import annotations

import importlib
import math
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import compare, tracered, weights

ADAM_B1 = 0.9  # optax.scale_by_adam's default, which the recipe keeps
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class PoolFeed:
    """Cycles the placed pool from ``start``; ends when ``stop`` is set.
    ``fit`` pulls it from its feeder thread, a few batches ahead."""

    def __init__(self, pool: list, start: int, stop: threading.Event):
        self.pool, self.i, self.stop = pool, start, stop

    def __iter__(self):
        return self

    def __next__(self):
        if self.stop.is_set():
            raise StopIteration
        batch = self.pool[self.i % len(self.pool)]
        self.i += 1
        return batch


class Window:
    """The ``log_fn`` of the long ``fit`` call: warm-up, trace, rate window."""

    def __init__(self, mix: dict, seconds: float, tracer, stop: threading.Event, compiles):
        self.mix, self.seconds, self.tracer, self.stop = mix, seconds, tracer, stop
        self.compiles = compiles
        self.phase = "warmup"
        self.seen = 0
        self.losses = []
        self.boundaries = []  # (step, clock) of the rate window
        self.opened_t = None  # warm-up over: the measured time starts
        self.trace_from = None  # (step, clock) when the profiler had started
        self.traced = None  # (steps, seconds) the profiler covered
        self.compiles_at_open = None

    def __call__(self, m: dict) -> None:
        if "loss" not in m:
            return  # fit's closing goodput record
        now = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench:log_boundary"):
            self._boundary(int(m["step"]), float(m["loss"]), now)

    def _open_rate_window(self, step: int) -> None:
        self.phase = "window"
        self.boundaries.append((step, time.perf_counter()))

    def _boundary(self, step: int, loss: float, now: float) -> None:
        self.losses.append(loss)
        if self.phase == "warmup":
            self.seen += 1
            if self.seen < self.mix["warmup_log_windows"]:
                return
            self.opened_t = now
            self.compiles_at_open = self.compiles.count
            if self.tracer is None:
                self._open_rate_window(step)
            else:
                self.tracer.start()
                self.phase = "trace"
                self.trace_from = (step, time.perf_counter())
        elif self.phase == "trace":
            steps = step - self.trace_from[0]
            if steps >= self.mix["trace_log_windows"] * self.mix["train_config"]["log_every_steps"]:
                self.traced = (steps, now - self.trace_from[1])
                self.tracer.stop()
                self._open_rate_window(step)
        elif self.phase == "window":
            self.boundaries.append((step, now))
            last = now - self.boundaries[-2][1]
            if now - self.opened_t >= self.seconds - last / 2:
                self.phase = "done"
                self.stop.set()


class CompileCounter:
    """Counts the backend's compilations while the ``with`` block runs."""

    def __init__(self):
        self.count = 0

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._on_event)


def train_config(cell: dict, seed: int, overrides: dict):
    from sav_tpu.train import TrainConfig

    config, mix = cell["config"], cell["mix"]
    fields = {
        "model_name": config["model_name"],
        "num_classes": config["num_classes"],
        "image_size": config["image_size"],
        "compute_dtype": config["compute_dtype"],
        "global_batch_size": config["train"]["per_chip_batch"] * cell["chips"],
        "seed": seed & 0x7FFFFFFF,
        # Toy sizes for the tests under benchmark/tests; no published
        # configuration has the key.
        "model_overrides": config.get("model_overrides"),
        **mix["train_config"],
        **overrides,
    }
    return TrainConfig(**fields)


def _host_leaves(tree) -> list:
    return [np.asarray(x, np.float32) for x in jax.device_get(jax.tree.leaves(tree))]


def _leaf_norms(leaves: list) -> np.ndarray:
    return np.asarray([np.linalg.norm(x.astype(np.float64).ravel()) for x in leaves])


def _relative_difference(program: list, reference: list) -> float:
    """Norm of the difference over the reference's norm, all leaves as one vector."""
    diff = sum(float(np.sum(np.square(p.astype(np.float64) - r))) for p, r in zip(program, reference))
    size = sum(float(np.sum(np.square(r.astype(np.float64)))) for r in reference)
    return math.sqrt(diff / size)


def first_gradient(opt_state, params) -> list:
    """The gradient the optimizer's moments got, leaf by leaf on the host,
    from Adam's first moment after one update: ``mu = (1 - b1) g``. The
    recipe keeps the moments on one flat vector where the mesh is
    data-parallel; it is cut back into leaves in the tree's own order."""
    adam = [
        s for s in jax.tree.leaves(opt_state, is_leaf=lambda s: isinstance(s, optax.ScaleByAdamState))
        if isinstance(s, optax.ScaleByAdamState)
    ]
    if len(adam) != 1:
        raise ValueError(f"expected one Adam state in the optimizer's, found {len(adam)}")
    mu = adam[0].mu
    leaves = jax.tree.leaves(params)
    if isinstance(mu, jax.Array):
        flat = np.asarray(jax.device_get(mu), np.float32)
        bounds = np.cumsum([0] + [leaf.size for leaf in leaves])
        mu = [flat[a:b].reshape(leaf.shape) for a, b, leaf in zip(bounds, bounds[1:], leaves)]
    else:
        mu = _host_leaves(mu)
    return [m / (1.0 - ADAM_B1) for m in mu]


def _phases(marks: list) -> dict:
    """Seconds from each mark to the next, named by the later one."""
    ordered = sorted(marks, key=lambda m: m[1])
    return {b[0]: b[1] - a[1] for a, b in zip(ordered, ordered[1:])}


def family(cell: dict):
    """The plain reference of the configuration's family, found by the name
    the configuration's file gives (``reference``)."""
    return importlib.import_module("benchmark.reference." + cell["config"]["reference"])


def build(cell: dict, seed: int, overrides: dict, mark=lambda label: None):
    """The one trainer, its seeded state and the placed pool. ``mark`` is
    told when each part is done on the device, so that set-up's seconds can
    be read part by part (``phases_s``)."""
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

    def place(images, labels):
        if cfg.transpose_images:
            images = jnp.transpose(images, (1, 2, 3, 0))  # NHWC -> HWCN
        return trainer.shard_batch({"images": images, "labels": labels})

    pool = [place(*b) for b in draw_pool(cell, cfg, seed)]
    jax.block_until_ready(pool)
    mark("pool_placed")
    return trainer, state, pool


def draw_pool(cell: dict, cfg, seed: int) -> list:
    return weights.draw_batches(
        seed, cell["mix"]["pool_batches"], cfg.global_batch_size, cfg.image_size, cfg.num_classes
    )


def reference_side(cell: dict, cfg, seed: int, abstract_params) -> dict:
    """The reference over the followed steps, from the same seed: weights
    and batches are drawn again, now that the program's are freed."""
    mix = cell["mix"]
    hp = {k: getattr(cfg, k) for k in (
        "base_lr", "global_batch_size", "lr_scaling_divisor", "num_train_images",
        "warmup_epochs", "num_epochs", "end_lr", "weight_decay", "clip_grad_norm",
        "label_smoothing",
    )}
    rows_per_block, row_sharding, everywhere = mix["reference_rows_per_block"], None, None
    if cell["chips"] > 1:
        # The same plain reference, a block's rows laid over the chips.
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("rows",))
        row_sharding = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec("rows"))
        everywhere = jax.tree.map(
            lambda _: jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()), abstract_params
        )
        rows_per_block *= cell["chips"]
    params0 = weights.draw_params(abstract_params, seed, everywhere)
    batches = draw_pool(cell, cfg, seed)[: mix["followed_steps"]]
    ref = family(cell).follow_steps(params0, batches, hp, rows_per_block, row_sharding)
    return {
        "losses": ref["losses"],
        "first_grad": _host_leaves(ref["first_grad"]),
        "change": _host_leaves(jax.tree.map(jnp.subtract, ref["params"], params0)),
    }


def run(cell: dict, seed: int, seconds: float, tracer, overrides: dict) -> dict:
    with CompileCounter() as compiles:
        return _run(cell, seed, seconds, tracer, overrides, compiles)


def _run(cell: dict, seed: int, seconds: float, tracer, overrides: dict, compiles) -> dict:
    mix = cell["mix"]
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
    # The step program's own account of itself. fit keeps its executable to
    # itself; the trainer's public AOT entry lowers the same program from
    # the same arguments, and the process's in-memory compilation cache,
    # which fit's own compile filled, answers it: no second compile.
    compiled = trainer.compile_train_step(state, pool[0], jax.random.PRNGKey(0))
    resident_bytes = max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in jax.local_devices())
    step_temp_bytes = compiled.memory_analysis().temp_size_in_bytes
    hlo_scopes = tracered.scopes_of_hlo(compiled.as_text()) if tracer is not None else None
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
    images = (step1 - step0) * cfg.global_batch_size
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
    return {
        "attempted": step1 - step0,
        "failed": int(numbers["nonfinite_losses"]),
        "window_opened_t": window.opened_t,
        "reference_s": reference_s,
        "memory": {
            # Allocator's reading of the buffers held while the step runs,
            # and the compiler's count of that step's temporaries.
            "resident_bytes": int(resident_bytes),
            "step_temp_bytes": int(step_temp_bytes),
        },
        "hlo_scopes": hlo_scopes,
        "phases_s": _phases(marks),
        "end_to_end": {"train_img_s_chip": images / (t1 - t0) / cell["chips"]},
        "numbers": numbers,
        "traced_window_s": traced_window_s,
        "spans": {
            "log_window_step_s": per_step,
            "traced_steps": traced_steps,
        },
        "counters": {
            "images": images,
            "images_per_step_per_chip": cfg.global_batch_size // cell["chips"],
            "train_flops_per_image": importlib.import_module(
                "benchmark.flops." + cell["config"]["flops"]
            ).train_flops_per_image(cell["config"]),
        },
    }
