#!/usr/bin/env python
"""Chip smoke: the main path, once, on the TPU, through the normal entry points.

    python chip_smoke.py            # one chip: train -> serve -> kernels
    python chip_smoke.py --chips 4  # four chips: the sharded trainer only

ONE process runs every phase, one after another: a chip belongs to one
process at a time, so nothing here starts a child, and no phase hands the
chip to another process.

Default (one chip), at the flagship's published width — ``deit_s_patch16``,
224x224, 1000 classes, bf16, global batch 256, no depth or width override:

- ``train``: ``train.py``'s own path (``main`` -> ``_run`` -> ``TrainConfig``
  -> ``Trainer.fit``) on ``--synth-data``, twice: a first run of a few
  steps that writes a checkpoint, then the same command with more steps,
  which restores that checkpoint and trains on. Losses are finite and the
  second run ends lower than the first.
- ``serve``: ``tools/serve_bench.py``'s own path (``main`` -> ``run`` ->
  ``ServeConfig`` -> ``ServeEngine``) on that checkpoint: the bucket ladder
  compiled ahead of time, a few dozen requests, every one answered, logits
  finite and not all zero (a fresh head is zero-initialised, a trained one
  is not).
- ``kernels``: the same DeiT-S forward and backward with ``backend="xla"``,
  ``"fused"`` and ``"pallas"`` on one randomised-head parameter set: logits
  and one gradient leaf agree to the bf16 tolerance of the kernel tests,
  and the lowered text of the kernel arms contains ``tpu_custom_call``.

``--chips 4`` runs the path that exists only across chips and what it is
compared with, and no other phase: three full-width DeiT-S train steps on
a ``{"data": 2, "model": 2}`` mesh against the same three steps on a
one-device mesh.

Every phase prints one JSON line naming it (device kind, compile seconds,
what the backend compiled and what the compile cache answered, step time,
HBM statistics, served p50, agreement errors). None of those numbers is a benchmark result. The last
line of stdout is the verdict, and only on success:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any failed check, any exception, or a platform other than ``tpu`` ends the
script with a non-zero exit code and no such line.
"""

from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import time

REPO_ROOT = os.path.dirname(os.path.abspath(__file__))

#: The flagship at its published width (bench.py / BASELINE.json).
FULL_SIZE = {
    "model": "deit_s_patch16",
    "image_size": 224,
    "num_classes": 1000,
    "batch_size": 256,
    "first_steps": 4,
    "total_steps": 24,
    # base LR; train.py scales it by batch/512. No warm-up, so that the
    # loss moves within the smoke's few steps.
    "learning_rate": 2e-3,
    "requests": 48,
    "max_batch": 8,
    "rate": 200.0,
    "deadline_ms": 2000.0,
    "kernel_batch": 256,
    "sharded_steps": 3,
}

# bf16 agreement bounds — the kernel tests' bf16 tolerances
# (tests/test_flash_attention.py, tests/test_fused_attention.py:
# atol = rtol = 3e-2 forward, 0.15 gradients), with atol in units of the
# reference tensor's largest magnitude: |a - b| <= tol * (max|b| + |b|).
LOGITS_TOL = 3e-2
GRAD_TOL = 0.15
LOSS_RTOL = 3e-2


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(condition: bool, message: str) -> None:
    if not condition:
        raise SmokeFailure(message)


def emit(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}), flush=True)


def require_tpu(expected_count: int) -> dict:
    """The device as jax reports it; anything but ``expected_count`` TPU
    chips fails the smoke."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    check(
        device["platform"] == "tpu",
        f"expected a TPU, found {device}: the smoke does not run on "
        "anything else",
    )
    check(
        device["count"] == expected_count,
        f"expected {expected_count} chip(s), found {device['count']}",
    )
    return device


def _hbm_stats():
    """``memory_stats()`` of the device that has used the most memory
    (None where the backend keeps none, as the CPU does)."""
    import jax

    stats = [d.memory_stats() for d in jax.local_devices()]
    stats = [s for s in stats if s and "peak_bytes_in_use" in s]
    if not stats:
        return None
    top = max(stats, key=lambda s: s["peak_bytes_in_use"])
    keys = ("bytes_in_use", "peak_bytes_in_use", "largest_alloc_size",
            "bytes_limit")
    return {k: top[k] for k in keys if k in top}


def _cache_dir():
    from sav_tpu.utils.compile_cache import resolve_cache_dir

    return resolve_cache_dir()


def _compiles(since: float, until=None) -> dict:
    """What the process traced, compiled and loaded from the cache between
    two readings of ``time.perf_counter``, from its compile log."""
    from sav_tpu.obs import compile_log

    summary = compile_log.summary(since, until)
    return {
        key: round(summary[key], 2) if key.endswith("_s") else summary[key]
        for key in ("trace_lower_s", "backend_compile_s", "cache_load_s",
                    "cache_hits", "cache_misses", "cache_off")
    }


def _read_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _loss_records(log_dir: str) -> list:
    records = []
    with open(os.path.join(log_dir, "metrics.jsonl")) as f:
        for line in f:
            doc = json.loads(line)
            if "loss" in doc:
                records.append(doc)
    return records


# ------------------------------------------------------------------ train


def phase_train(size: dict, workdir: str) -> str:
    """train.py's own path, twice (fresh, then resumed). Returns the
    checkpoint directory."""
    import numpy as np

    import train as train_cli

    ckpt_dir = os.path.join(workdir, "ckpt")
    log_dir = os.path.join(workdir, "train")
    runs = []
    for steps in (size["first_steps"], size["total_steps"]):
        argv = [
            "--synth-data",
            "-m", size["model"],
            "--image-size", str(size["image_size"]),
            "--num-classes", str(size["num_classes"]),
            "--batch-size", str(size["batch_size"]),
            "--dtype", "bfloat16",
            "--learning-rate", str(size["learning_rate"]),
            "--warmup-epochs", "0",
            "--steps", str(steps),
            "-c", ckpt_dir,
            "--log-dir", log_dir,
        ]
        t0 = time.perf_counter()
        # train.py prints its own records on stdout; the smoke's stdout
        # carries only its phase lines, so those go to stderr.
        with contextlib.redirect_stdout(sys.stderr):
            train_cli.main.main(args=argv, standalone_mode=False)
        wall_s = time.perf_counter() - t0
        manifest = _read_json(os.path.join(log_dir, "manifest.json"))
        check(
            manifest.get("outcome") == "ok",
            f"train manifest outcome {manifest.get('outcome')!r}: "
            f"{manifest.get('error')}",
        )
        metrics = manifest.get("metrics") or {}
        last = _loss_records(log_dir)[-1]
        check(last["step"] == steps, f"last logged step {last['step']} != {steps}")
        runs.append({
            "steps_target": steps,
            "resumed_from": (manifest["notes"].get("resume") or {}).get(
                "from_step"
            ),
            "loss": last["loss"],
            # End to end over the last log window, input included: the
            # host makes each synthetic batch with numpy, which bounds it.
            "images_per_sec": last.get("images_per_sec"),
            "wall_s": round(wall_s, 2),
            "compile_s": metrics.get("goodput/compile_s"),
            "input_wait_s": metrics.get("goodput/input_wait_s"),
            "compiles": _compiles(t0),
        })
    first, second = runs
    check(first["resumed_from"] == 0, f"first run resumed from {first['resumed_from']}")
    check(
        second["resumed_from"] == size["first_steps"],
        f"second run resumed from {second['resumed_from']}, expected the "
        f"first run's checkpoint at step {size['first_steps']}",
    )
    losses = [first["loss"], second["loss"]]
    check(bool(np.all(np.isfinite(losses))), f"non-finite loss: {losses}")
    check(
        second["loss"] < first["loss"],
        f"loss did not fall: {first['loss']} at step {size['first_steps']} "
        f"-> {second['loss']} at step {size['total_steps']}",
    )
    saved = sorted(
        int(d) for d in os.listdir(ckpt_dir) if d.isdigit()
    )
    check(
        size["total_steps"] in saved,
        f"no checkpoint at step {size['total_steps']} in {ckpt_dir}: {saved}",
    )
    emit(
        "train",
        model=size["model"],
        image_size=size["image_size"],
        batch_size=size["batch_size"],
        dtype="bfloat16",
        loss_first_run=first["loss"],
        loss_second_run=second["loss"],
        checkpoints=saved,
        cache_dir=_cache_dir(),
        runs=runs,
        hbm=_hbm_stats(),
    )
    return ckpt_dir


# ------------------------------------------------------------------ serve


def phase_serve(size: dict, workdir: str, ckpt_dir: str) -> None:
    """tools/serve_bench.py's own path on the trained checkpoint."""
    sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))
    import serve_bench

    serve_dir = os.path.join(workdir, "serve")
    argv = [
        "--model", size["model"],
        "--image-size", str(size["image_size"]),
        "--num-classes", str(size["num_classes"]),
        "--checkpoint", ckpt_dir,
        "--requests", str(size["requests"]),
        "--max-batch", str(size["max_batch"]),
        "--rate", str(size["rate"]),
        "--deadline-ms", str(size["deadline_ms"]),
        "--manifest", os.path.join(serve_dir, "manifest.json"),
    ]
    t0 = time.perf_counter()
    captured = io.StringIO()
    with contextlib.redirect_stdout(captured):
        rc = serve_bench.main(argv)
    sys.stderr.write(captured.getvalue())
    check(rc == 0, f"serve_bench exit code {rc}")
    line = json.loads(captured.getvalue().strip().splitlines()[-1])
    startup = line["startup"]
    check(line["outcome"] == "ok", f"serve outcome {line['outcome']!r}")
    check(
        startup["params_source"].startswith("checkpoint:"),
        f"served params came from {startup['params_source']!r}, not the "
        "checkpoint",
    )
    check(
        line["requests"] == size["requests"] and line["rejected"] == 0,
        f"{line['requests']} of {size['requests']} requests answered, "
        f"{line['rejected']} rejected",
    )
    check(line["logits_finite"], "served logits are not finite")
    check(
        line["logits_absmax"] > 0.0,
        "served logits are all zero: the head is still the fresh "
        "zero-initialised one, not the trained checkpoint's",
    )
    manifest = _read_json(line["manifest"])
    check(
        manifest.get("outcome") == "ok",
        f"serve manifest outcome {manifest.get('outcome')!r}",
    )
    emit(
        "serve",
        model=size["model"],
        buckets=startup["buckets"],
        requests=line["requests"],
        rejected=line["rejected"],
        deadline_overruns=line["deadline_overruns"],
        p50_latency_ms=line["p50_latency_ms"],
        p99_latency_ms=line["p99_latency_ms"],
        logits_absmax=line["logits_absmax"],
        compile_s=startup["compile_s"],
        compiled_from_scratch=startup["compiled_from_scratch"],
        cache_hits=startup["cache_hits"],
        compiles=_compiles(t0),
        bucket_hbm_bytes=startup["bucket_hbm_bytes"],
        hbm=_hbm_stats(),
    )


# ---------------------------------------------------------------- kernels


def _agreement(a, b, tol: float) -> tuple[float, bool]:
    """(max |a - b| / max |b|, whether a agrees with b to ``tol``)."""
    import numpy as np

    a = np.asarray(a, np.float32)
    b = np.asarray(b, np.float32)
    scale = max(float(np.abs(b).max()), 1e-12)
    ok = bool(np.allclose(a, b, atol=tol * scale, rtol=tol))
    return float(np.abs(a - b).max() / scale), ok


def phase_kernels(size: dict, *, expect_custom_call: bool = True) -> None:
    """DeiT-S forward + backward on the three attention backends.

    ``expect_custom_call`` is what the CPU rehearsal switches off: in
    interpret mode a kernel lowers to plain HLO, not ``tpu_custom_call``.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sav_tpu.models import create_model

    batch, s = size["kernel_batch"], size["image_size"]
    x = jax.random.normal(
        jax.random.PRNGKey(0), (batch, s, s, 3), jnp.bfloat16
    )
    cotangent = jax.random.normal(
        jax.random.PRNGKey(3), (batch, size["num_classes"]), jnp.float32
    )

    def build(backend):
        return create_model(
            size["model"], num_classes=size["num_classes"],
            dtype=jnp.bfloat16, backend=backend,
        )

    # One parameter set for all three arms (the backend changes no
    # parameter); the zero-initialised head is randomised, or every
    # logit would be zero and the comparison vacuous.
    params = jax.jit(
        lambda rng: build("xla").init(
            {"params": rng}, jnp.zeros((2, s, s, 3), jnp.bfloat16),
            is_training=False,
        )["params"]
    )(jax.random.PRNGKey(1))
    params = dict(params)
    params["head"] = {
        "kernel": 0.05 * jax.random.normal(
            jax.random.PRNGKey(2), params["head"]["kernel"].shape
        ),
        "bias": jnp.zeros_like(params["head"]["bias"]),
    }

    def leaf_of(grads):
        # The first encoder block's fused qkv projection: upstream of
        # every attention layer's backward.
        flat = jax.tree_util.tree_flatten_with_path(grads)[0]
        for path, leaf in flat:
            name = "/".join(str(getattr(k, "key", k)) for k in path)
            if name.endswith("kernel") and "qkv" in name:
                return name, leaf
        raise SmokeFailure("no qkv kernel leaf in the gradient tree")

    results = {}
    report = {}
    for backend in ("xla", "fused", "pallas"):
        model = build(backend)

        # The batch and the cotangent are arguments, not closed-over
        # arrays: a closed-over array is baked into the executable as a
        # constant (77 MB of images here) and into its cache entry.
        def loss_fn(p, images, cotangent, model=model):
            logits = model.apply({"params": p}, images, is_training=False)
            logits = logits.astype(jnp.float32)
            return jnp.mean(logits * cotangent), logits

        step = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))
        compile_t0 = time.perf_counter()
        lowered = step.lower(params, x, cotangent)
        custom_calls = lowered.as_text().count("tpu_custom_call")
        compiled = lowered.compile()
        compile_s = time.perf_counter() - compile_t0
        (_, logits), grads = compiled(params, x, cotangent)
        leaf_name, leaf = leaf_of(grads)
        results[backend] = (np.asarray(logits), np.asarray(leaf))
        check(
            bool(np.all(np.isfinite(results[backend][0]))),
            f"{backend}: non-finite logits",
        )
        check(
            bool(np.all(np.isfinite(results[backend][1]))),
            f"{backend}: non-finite gradient in {leaf_name}",
        )
        t0 = time.perf_counter()
        jax.block_until_ready(compiled(params, x, cotangent))
        report[backend] = {
            "tpu_custom_calls": custom_calls,
            "compile_s": round(compile_s, 2),
            "fwd_bwd_ms": round((time.perf_counter() - t0) * 1e3, 2),
            "compiles": _compiles(compile_t0),
        }
    check(
        float(np.abs(results["xla"][0]).max()) > 0.0,
        "reference logits are all zero",
    )
    for backend in ("fused", "pallas"):
        logits_err, logits_ok = _agreement(
            results[backend][0], results["xla"][0], LOGITS_TOL
        )
        grad_err, grad_ok = _agreement(
            results[backend][1], results["xla"][1], GRAD_TOL
        )
        report[backend]["logits_rel_err"] = logits_err
        report[backend]["grad_rel_err"] = grad_err
        check(
            logits_ok,
            f"{backend} logits differ from xla by {logits_err:.4g} of the "
            f"largest logit (tolerance {LOGITS_TOL})",
        )
        check(
            grad_ok,
            f"{backend} {leaf_name} gradient differs from xla by "
            f"{grad_err:.4g} of its largest entry (tolerance {GRAD_TOL})",
        )
        if expect_custom_call:
            check(
                report[backend]["tpu_custom_calls"] > 0,
                f"{backend}: no tpu_custom_call in the lowered text — the "
                "kernel did not reach the chip's compiler",
            )
    check(
        report["xla"]["tpu_custom_calls"] == 0,
        "the xla arm contains a tpu_custom_call: it is no reference",
    )
    emit(
        "kernels",
        model=size["model"],
        batch_size=batch,
        grad_leaf=leaf_name,
        logits_tol=LOGITS_TOL,
        grad_tol=GRAD_TOL,
        backends=report,
        hbm=_hbm_stats(),
    )


# ---------------------------------------------------------- sharded train


def phase_sharded_train(size: dict) -> None:
    """Three train steps on a data x model mesh over four devices against
    the same three steps on one device."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from sav_tpu.data.synthetic import synth_batch
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    devices = jax.devices()
    check(len(devices) == 4, f"need four devices, found {len(devices)}")

    def make_trainer(mesh_axes, mesh=None):
        config = TrainConfig(
            model_name=size["model"],
            num_classes=size["num_classes"],
            image_size=size["image_size"],
            compute_dtype="bfloat16",
            global_batch_size=size["batch_size"],
            base_lr=size["learning_rate"],
            warmup_epochs=0,
            transpose_images=False,
            mesh_axes=mesh_axes,
            seed=0,
        )
        return Trainer(config, mesh=mesh)

    batches = [
        synth_batch(
            seed=0, position=i + 1, batch_size=size["batch_size"],
            image_size=size["image_size"], num_classes=size["num_classes"],
        )
        for i in range(size["sharded_steps"])
    ]
    head_kernel = None

    def run(trainer):
        nonlocal head_kernel
        state = trainer.init_state(seed=0)
        # Same reason as in the kernels phase: with the fresh zero head
        # the first losses are ln(num_classes) whatever the trunk does.
        old = state.params["head"]["kernel"]
        if head_kernel is None:
            head_kernel = 0.05 * np.asarray(
                jax.random.normal(jax.random.PRNGKey(2), old.shape),
                np.float32,
            )
        params = dict(state.params)
        params["head"] = dict(
            params["head"],
            kernel=jax.device_put(head_kernel.astype(old.dtype), old.sharding),
        )
        state = state.replace(params=params)
        rng = jax.random.PRNGKey(7)
        placed = trainer.shard_batch(batches[0])
        t0 = time.perf_counter()
        step = trainer.compile_train_step(state, placed, rng)
        compile_s = time.perf_counter() - t0
        losses = []
        for i, batch in enumerate(batches):
            state, metrics = step(
                state, trainer.shard_batch(batch), jax.random.fold_in(rng, i)
            )
            losses.append(float(jax.device_get(metrics["loss"])))
        return state, step, losses, compile_s

    sharded_t0 = time.perf_counter()
    sharded_trainer = make_trainer({"data": 2, "model": 2})
    state, step, sharded_losses, sharded_compile_s = run(sharded_trainer)
    text = step.as_text()
    all_reduces = text.count("all-reduce")
    check(all_reduces > 0, "no all-reduce in the compiled sharded step")

    # A tensor-parallel kernel: half of it on every device.
    def spec_has_model(leaf):
        return "model" in jax.tree.leaves(tuple(leaf.sharding.spec))

    flat = jax.tree_util.tree_flatten_with_path(state.params)[0]
    tp_name, tp_leaf = next(
        (
            ("/".join(str(getattr(k, "key", k)) for k in path), leaf)
            for path, leaf in flat
            if leaf.ndim == 2 and spec_has_model(leaf)
        ),
        (None, None),
    )
    check(tp_leaf is not None, "no parameter is sharded over the model axis")
    shard_sizes = {
        shard.device.id: int(np.prod(shard.data.shape))
        for shard in tp_leaf.addressable_shards
    }
    check(
        len(shard_sizes) == 4
        and all(n * 2 == tp_leaf.size for n in shard_sizes.values()),
        f"{tp_name} {tp_leaf.shape}: shard sizes {shard_sizes}, expected "
        f"{tp_leaf.size // 2} on each of four devices",
    )

    # No device holds the whole optimizer state.
    opt_leaves = [
        leaf for leaf in jax.tree.leaves(state.opt_state)
        if hasattr(leaf, "addressable_shards")
    ]
    opt_total = sum(leaf.size * leaf.dtype.itemsize for leaf in opt_leaves)
    opt_per_device = {d.id: 0 for d in devices}
    for leaf in opt_leaves:
        for shard in leaf.addressable_shards:
            opt_per_device[shard.device.id] += (
                int(np.prod(shard.data.shape)) * leaf.dtype.itemsize
            )
    check(
        all(0 < n < opt_total for n in opt_per_device.values()),
        f"optimizer state bytes per device {opt_per_device} of {opt_total}: "
        "some device holds all of it, or none",
    )
    memory = {
        d.id: (d.memory_stats() or {}).get("bytes_in_use") for d in devices
    }
    if devices[0].platform == "tpu":
        check(
            all(memory[d.id] for d in devices),
            f"bytes_in_use per device {memory}: a device holds nothing",
        )
    single_t0 = time.perf_counter()
    del state, step

    single_trainer = make_trainer(
        None, mesh=create_mesh({"data": 1}, devices=devices[:1])
    )
    _, _, single_losses, single_compile_s = run(single_trainer)
    check(
        bool(np.all(np.isfinite(sharded_losses + single_losses))),
        f"non-finite loss: {sharded_losses} / {single_losses}",
    )
    check(
        len(set(single_losses)) > 1,
        f"the one-device losses do not move ({single_losses}): the "
        "comparison would be vacuous",
    )
    loss_rel_err = [
        abs(a - b) / abs(b) for a, b in zip(sharded_losses, single_losses)
    ]
    check(
        max(loss_rel_err) <= LOSS_RTOL,
        f"sharded losses {sharded_losses} differ from one-device losses "
        f"{single_losses} by {max(loss_rel_err):.4g} > {LOSS_RTOL}",
    )
    emit(
        "sharded_train",
        model=size["model"],
        batch_size=size["batch_size"],
        mesh={"data": 2, "model": 2},
        steps=size["sharded_steps"],
        sharded_losses=sharded_losses,
        single_device_losses=single_losses,
        loss_rel_err=loss_rel_err,
        loss_rtol=LOSS_RTOL,
        all_reduces_in_compiled_step=all_reduces,
        tp_leaf=tp_name,
        tp_leaf_shape=list(tp_leaf.shape),
        tp_shard_sizes=shard_sizes,
        opt_state_bytes_total=opt_total,
        opt_state_bytes_per_device=opt_per_device,
        bytes_in_use_per_device=memory,
        sharded_compile_s=round(sharded_compile_s, 2),
        single_compile_s=round(single_compile_s, 2),
        compiles_sharded=_compiles(sharded_t0, single_t0),
        compiles_single=_compiles(single_t0),
        hbm=_hbm_stats(),
    )


# ------------------------------------------------------------------- main


def run_smoke(chips: int, size: dict, workdir: str) -> dict:
    """Every phase for ``chips``; returns the device for the verdict."""
    device = require_tpu(chips)
    from sav_tpu.obs import compile_log
    from sav_tpu.utils.compile_cache import enable_persistent_cache

    # Before the first compile of the process. The Trainer and the serve
    # engine apply the same rule and start the compile log themselves; the
    # kernels phase compiles outside both.
    enable_persistent_cache()
    compile_log.listen()
    emit("device", **device, cache_dir=_cache_dir())
    shutil.rmtree(workdir, ignore_errors=True)
    os.makedirs(workdir)
    if chips == 4:
        phase_sharded_train(size)
    else:
        ckpt_dir = phase_train(size, workdir)
        phase_serve(size, workdir, ckpt_dir)
        phase_kernels(size)
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="1 (default): train, serve and kernels on one chip. 4: the "
        "sharded trainer against one device, and no other phase.",
    )
    args = parser.parse_args(argv)
    t0 = time.perf_counter()
    try:
        device = run_smoke(
            args.chips, FULL_SIZE,
            os.path.join(REPO_ROOT, "runs", "chip_smoke"),
        )
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit("done", wall_s=round(time.perf_counter() - t0, 1))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
