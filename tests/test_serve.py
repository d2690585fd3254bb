"""Serving engine (sav_tpu/serve/) — ISSUE 10.

Unit tier (stdlib-only, no jax): the bucket ladder, the latency ledger's
percentiles/waste accounting, and the DynamicBatcher's deadline policy
under an injectable fake clock — the drain waits while the earliest
admitted deadline still has slack for the bucket's step, ships promptly
once it does not, and never dispatches later than
``earliest_deadline - est_step(bucket)`` (the invariant that bounds any
overrun to at most one bucket's actual step time).

Engine tier (tiny ViT on CPU): end-to-end serving correctness (results
match the model, padded rows masked to zero), the overlap-ordering
proof that batch N+1 is PLACED while batch N executes (the
tests/test_feeder.py technique, through the engine's instrumented
hooks), the dynamic-batching throughput proof against the batch-size-1
ladder, params-only checkpoint restore (both optimizer layouts, EMA,
opt_state never requested), the serving manifest -> sentinel loop
(fixture-pinned both directions), the uint8 wire-format parity against
the training loader's eval preprocessing, the zoo ``--serve`` check for
all seven families, and the warm-compile-cache restart proof (second
serve_bench process compiles 0 executables from scratch).
"""

import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

from sav_tpu.serve.batcher import (
    DynamicBatcher,
    QueueFullError,
    ServeClosedError,
    ServeFuture,
)
from sav_tpu.serve.bucketing import BucketLadder, default_ladder, padding_waste
from sav_tpu.serve.latency import LatencyLedger, percentile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join(os.path.dirname(__file__), "sentinel_fixtures")


# ------------------------------------------------------------- unit tier


def test_bucket_ladder_lookups():
    ladder = BucketLadder([4, 1, 8, 2])
    assert ladder.buckets == (1, 2, 4, 8)
    assert ladder.max_batch == 8
    assert ladder.bucket_for(1) == 1
    assert ladder.bucket_for(3) == 4
    assert ladder.bucket_for(8) == 8
    assert ladder.largest_fillable(7) == 4
    assert ladder.largest_fillable(1) == 1
    with pytest.raises(ValueError, match="exceeds the top bucket"):
        ladder.bucket_for(9)
    with pytest.raises(ValueError, match="at least one request"):
        ladder.bucket_for(0)
    with pytest.raises(ValueError, match="at least one rung"):
        BucketLadder([])
    with pytest.raises(ValueError, match=">= 1"):
        BucketLadder([0, 2])


def test_default_ladder_is_pow2_and_reaches_max():
    assert default_ladder(8) == [1, 2, 4, 8]
    assert default_ladder(1) == [1]
    # A non-power-of-two max is still a rung: configured capacity is
    # reachable.
    assert default_ladder(6) == [1, 2, 4, 6]
    assert padding_waste(3, 4) == 0.25
    assert padding_waste(4, 4) == 0.0
    with pytest.raises(ValueError):
        padding_waste(5, 4)


def test_percentile_interpolation():
    series = sorted([10.0, 20.0, 30.0, 40.0])
    assert percentile(series, 50.0) == 25.0
    assert percentile(series, 0.0) == 10.0
    assert percentile(series, 100.0) == 40.0
    assert percentile([7.0], 99.0) == 7.0
    with pytest.raises(ValueError):
        percentile([], 50.0)
    with pytest.raises(ValueError):
        percentile([1.0], 101.0)


def test_latency_ledger_summary_accounting():
    t = [0.0]
    ledger = LatencyLedger(clock=lambda: t[0])
    ledger.start()
    t[0] = 1.0
    ledger.observe_batch(
        bucket=4, latencies_s=[0.010, 0.020, 0.030],
        overruns_s=[-0.05, -0.04, 0.002], queue_depth=5, step_s=0.008,
    )
    t[0] = 2.0
    ledger.observe_batch(
        bucket=1, latencies_s=[0.040], overruns_s=[-0.1],
        queue_depth=0, step_s=0.004,
    )
    ledger.observe_rejected(2)
    s = ledger.summary()
    assert s["requests"] == 4
    assert s["batches"] == 2
    assert s["rejected"] == 2
    # 4 real rows over 4+1=5 padded rows -> 1/5 waste.
    assert s["padding_waste_frac"] == 0.2
    assert s["bucket_occupancy"]["4"] == {"batches": 1, "fill": 0.75}
    assert s["queue_depth_max"] == 5
    assert s["deadline_overruns"] == 1
    assert s["deadline_overrun_max_ms"] == 2.0
    assert s["latency_ms"]["p50"] == 25.0
    assert s["wall_s"] == 2.0  # start() .. last observe
    assert s["throughput_rps"] == 2.0
    flat = ledger.flat_metrics()
    assert flat["serve/p99_latency_ms"] == s["latency_ms"]["p99"]
    assert flat["serve/throughput_rps"] == 2.0


class FakeClock:
    """Manually advanced monotonic clock for deterministic drain tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _drain_in_thread(batcher):
    out = {}

    def drain():
        out["formed"] = batcher.next_batch()

    thread = threading.Thread(target=drain, daemon=True)
    thread.start()
    return thread, out


def test_batcher_hot_queue_fills_largest_bucket_immediately():
    clock = FakeClock()
    batcher = DynamicBatcher(
        BucketLadder([1, 2, 4]), step_time_fn=lambda b: 0.01,
        default_deadline_s=1.0, clock=clock,
    )
    for _ in range(6):
        batcher.submit("x")
    formed = batcher.next_batch()
    # 6 waiting -> grab the top bucket's worth outright, no deadline wait.
    assert formed.bucket == 4
    assert len(formed.requests) == 4
    assert formed.queue_depth == 2
    batcher.close()


def test_batcher_waits_while_slack_remains_then_ships_partial():
    clock = FakeClock()
    batcher = DynamicBatcher(
        BucketLadder([1, 2, 4]), step_time_fn=lambda b: 0.2,
        default_deadline_s=10.0, clock=clock,
    )
    batcher.submit("lonely")
    thread, out = _drain_in_thread(batcher)
    # Fake time is frozen with 9.8s of slack: the drain must NOT ship.
    thread.join(timeout=0.4)
    assert thread.is_alive(), "shipped a partial batch with slack remaining"
    # Advance past deadline - est_step: ships promptly, padded bucket 1.
    clock.advance(9.85)
    thread.join(timeout=2.0)
    assert not thread.is_alive()
    formed = out["formed"]
    assert formed.bucket == 1
    assert len(formed.requests) == 1
    batcher.close()


def test_batcher_deadline_dispatch_bound_pinned():
    """The overrun bound: every batch is dispatched no later than
    ``earliest_deadline - est_step(bucket)`` (+ the drain's poll
    granularity in fake time: one poll wakes per real POLL_S, and the
    test advances fake time in sub-slack steps). Completion therefore
    exceeds the earliest deadline by at most the bucket's ACTUAL step
    time — the 'one bucket step' guarantee docs/serving.md states."""
    clock = FakeClock()
    est = {1: 0.1, 2: 0.15, 4: 0.2}
    batcher = DynamicBatcher(
        BucketLadder([1, 2, 4]), step_time_fn=lambda b: est[b],
        default_deadline_s=5.0, clock=clock,
    )
    batcher.submit("a")
    clock.advance(1.0)
    batcher.submit("b", deadline_s=2.5)  # earliest absolute deadline: 3.5
    thread, out = _drain_in_thread(batcher)
    thread.join(timeout=0.4)
    assert thread.is_alive()  # slack remains at t=1.0
    # Jump near the bound (still slack), then step fake time across it;
    # the drain must ship at the first poll where
    # now >= earliest_deadline - est_step(bucket_for(2)) = 3.35.
    clock.advance(2.25)  # t = 3.25, 0.1 of slack left
    thread.join(timeout=0.3)
    assert thread.is_alive(), "shipped with slack remaining"
    while thread.is_alive() and clock.t < 10.0:
        clock.advance(0.05)
        thread.join(timeout=0.15)
    formed = out["formed"]
    assert formed is not None
    assert formed.bucket == 2
    earliest = min(r.deadline_t for r in formed.requests)
    assert earliest == pytest.approx(3.5)
    # Dispatched at-or-after the bound was crossed, within one fake step
    # of it — never later (the pinned guarantee), never earlier than the
    # slack allowed (the previous test).
    bound = earliest - est[2]
    assert bound <= formed.formed_t <= bound + 0.1 + 1e-9
    batcher.close()


def test_batcher_bounded_queue_rejects_and_counts():
    batcher = DynamicBatcher(
        BucketLadder([1, 2]), step_time_fn=lambda b: 0.0, max_queue=2,
    )
    batcher.submit("a")
    batcher.submit("b")
    with pytest.raises(QueueFullError, match="capacity"):
        batcher.submit("c")
    assert batcher.stats() == {
        "submitted": 2, "rejected": 1, "shed_infeasible": 0,
        "inflight": 0, "queued": 2,
    }
    batcher.close()


def test_batcher_sheds_deadline_infeasible_at_admission():
    """The overload half of the deadline guarantee: a request whose
    projected dispatch wait (in-flight + queued-ahead batches, one
    top-bucket step each) already exceeds its deadline is shed at
    submit — serving it would be a guaranteed miss. Projection math
    pinned: max bucket 2, est 0.1s/batch, deadline 0.25s admits 4
    queued (ceil(k/2)*0.1 <= 0.25) and sheds the 5th."""
    from sav_tpu.serve.batcher import DeadlineInfeasibleError

    clock = FakeClock()
    batcher = DynamicBatcher(
        BucketLadder([1, 2]), step_time_fn=lambda b: 0.1,
        default_deadline_s=0.25, clock=clock,
    )
    for tag in ("a", "b", "c", "d"):
        batcher.submit(tag)  # batches ahead: 1,1,2,2 -> <= 0.2s wait
    with pytest.raises(DeadlineInfeasibleError, match="shedding"):
        batcher.submit("e")  # 3 batches ahead -> 0.3s > 0.25s deadline
    # A roomier per-request deadline is still admitted.
    batcher.submit("e", deadline_s=1.0)
    # In-flight batches count as wait ahead too: drain one batch (NOT
    # completed) and the projection for a fresh 0.25s request now sees
    # inflight=1 + its own batch.
    formed = batcher.next_batch()
    assert formed.bucket == 2
    assert batcher.stats()["inflight"] == 1
    with pytest.raises(DeadlineInfeasibleError):
        batcher.submit("f", deadline_s=0.25)  # (1+2)*0.1 > 0.25
    batcher.mark_completed()
    assert batcher.stats()["inflight"] == 0
    stats = batcher.stats()
    assert stats["shed_infeasible"] == 2
    assert stats["rejected"] == 2
    # A deadline shorter than ONE bucket step is unserveable by
    # construction: always shed, even on an idle batcher.
    idle = DynamicBatcher(
        BucketLadder([1]), step_time_fn=lambda b: 0.2,
    )
    with pytest.raises(DeadlineInfeasibleError):
        idle.submit("x", deadline_s=0.1)
    idle.close()
    batcher.close()


def test_batcher_close_fails_queued_and_stops_admission():
    batcher = DynamicBatcher(
        BucketLadder([4]), step_time_fn=lambda b: 0.0,
    )
    future = batcher.submit("a")
    batcher.close()
    with pytest.raises(ServeClosedError):
        future.result(timeout=1.0)
    with pytest.raises(ServeClosedError):
        batcher.submit("b")
    # Drained-and-closed: next_batch reports the end of the stream.
    assert batcher.next_batch() is None


def test_future_result_timeout_and_set_once():
    future = ServeFuture()
    with pytest.raises(TimeoutError):
        future.result(timeout=0.05)
    future.set_result(41)
    assert future.result(timeout=0.1) == 41
    assert future.done()


# ------------------------------------------------------------ engine tier


def _tiny_config(**overrides):
    from sav_tpu.serve.engine import ServeConfig

    base = dict(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        model_overrides={"num_layers": 1},
        buckets=[1, 2, 4],
        max_queue=128,
        deadline_ms=2000.0,
    )
    base.update(overrides)
    return ServeConfig(**base)


def _requests(n, image_size=32, seed=0):
    rng = np.random.default_rng(seed)
    return [
        rng.integers(0, 256, (image_size, image_size, 3), dtype=np.uint8)
        for _ in range(n)
    ]


def test_engine_serves_correct_results_and_masks_padding(tmp_path):
    import jax
    import jax.numpy as jnp

    from sav_tpu.ops.preprocess import normalize_images
    from sav_tpu.serve.engine import ServeEngine

    # deadline 300ms: with 3 requests against bucket 4 the drain waits
    # out the slack for a 4th, then ships padded — the wait is the test's
    # only idle time, so keep the budget short.
    engine = ServeEngine(
        _tiny_config(buckets=[1, 4], deadline_ms=300.0, log_dir=str(tmp_path))
    )
    images = _requests(3)
    with engine:
        # 3 requests flood into bucket 4 (one padded row).
        futures = [engine.submit(img) for img in images]
        rows = [f.result(timeout=30.0) for f in futures]
    assert all(r.shape == (10,) for r in rows)
    assert all(np.isfinite(r).all() for r in rows)
    # Results match a direct (non-AOT) apply of the same model+params on
    # the same uint8 wire bytes (bf16 compute: loose-ish tolerance).
    x = normalize_images(
        jnp.asarray(np.stack(images)), engine.compute_dtype
    )
    expected = np.asarray(
        engine.model.apply(
            {"params": engine._params}, x, is_training=False
        ).astype(jnp.float32)
    )
    np.testing.assert_allclose(np.stack(rows), expected, rtol=0.05, atol=0.05)
    # The validity mask zeroes padded rows in the program itself. The
    # fresh init's head is zero-init (vacuous logits), so randomize it —
    # otherwise "masked to zero" is indistinguishable from "all zero".
    params = dict(engine._params)
    params["head"] = dict(params["head"])
    params["head"]["kernel"] = 0.02 * np.asarray(
        jax.random.normal(
            jax.random.PRNGKey(2), engine._params["head"]["kernel"].shape
        )
    )
    placed = engine._place_host_batch(
        np.stack(images + [np.zeros_like(images[0])]),
        np.array([1, 1, 0, 0], np.float32),
    )
    direct = engine._executables[4](params, engine._batch_stats, placed)
    out = np.asarray(direct["logits"])
    assert np.all(out[2:] == 0.0)
    assert np.any(out[:2] != 0.0)
    # The quality digest leaves (ISSUE 20) ride the same program and
    # the same validity mask: padded rows digest to zero.
    assert np.all(np.asarray(direct["margin"])[2:] == 0.0)
    assert np.all(np.asarray(direct["top1"])[2:] == 0)
    assert np.all(np.asarray(direct["entropy"])[2:] == 0.0)
    summary = engine.stop()
    assert summary["requests"] == 3
    assert summary["bucket_occupancy"]["4"]["batches"] == 1
    assert summary["padding_waste_frac"] == pytest.approx(0.25)
    # --- the finalized serving manifest, and its sentinel view ----------
    from sav_tpu.obs.manifest import normalize_run_record

    manifests = [f for f in os.listdir(tmp_path) if f.startswith("manifest")]
    assert len(manifests) == 1
    with open(os.path.join(tmp_path, manifests[0])) as f:
        data = json.load(f)
    assert data["kind"] == "serve"
    assert data["outcome"] == "ok"
    assert data["metrics"]["serve/requests"] == 3.0
    assert data["metrics"]["serve/p99_latency_ms"] > 0
    assert data["metrics"]["serve/throughput_rps"] > 0
    assert data["notes"]["serve_startup"]["buckets"] == [1, 4]
    assert "padding_waste_frac" in data["notes"]["serve_summary"]
    record = normalize_run_record(data, label="serve")
    assert record.ok
    assert record.metrics["p99_latency_ms"] == (
        data["metrics"]["serve/p99_latency_ms"]
    )
    assert record.metrics["serve_throughput"] == (
        data["metrics"]["serve/throughput_rps"]
    )
    assert "throughput" not in record.metrics  # img/s stays training-only


def test_engine_exit_on_exception_never_finalizes_ok(tmp_path):
    """A driver dying mid-serve must not ship an 'ok' serving record
    built from the few requests that happened to finish — finalize is
    first-wins, so if the context manager stamped 'ok' here, no later
    error finalize could correct it and the sentinel would score the
    broken run as a healthy p99 baseline."""
    from sav_tpu.serve.engine import ServeEngine

    engine = ServeEngine(
        _tiny_config(buckets=[1], log_dir=str(tmp_path))
    )
    with pytest.raises(RuntimeError, match="driver died"):
        with engine:
            engine.submit(_requests(1)[0]).result(timeout=30.0)
            raise RuntimeError("driver died mid-serve")
    manifests = [f for f in os.listdir(tmp_path) if f.startswith("manifest")]
    with open(os.path.join(tmp_path, manifests[0])) as f:
        data = json.load(f)
    assert data["outcome"] == "error"
    assert "driver died" in data["error"]
    # The partial measurements still ride along for the post-mortem —
    # but under a non-ok outcome the sentinel never scores them.
    assert data["metrics"]["serve/requests"] == 1.0


def test_engine_overlap_place_of_next_batch_during_execution():
    """The acceptance ordering proof (tests/test_feeder.py technique):
    with the device loop still 'executing' batch N (execute_hook holds
    it), the feeder worker must already have ISSUED the placement of
    batch N+1 — a serial loop would not touch it until N completed."""
    from sav_tpu.serve.engine import ServeEngine

    place_times = []
    executing = threading.Event()
    release = threading.Event()

    def place_hook(formed):
        place_times.append((time.monotonic(), len(formed.requests)))

    def execute_hook(formed):
        if not executing.is_set():
            executing.set()
            release.wait(timeout=10.0)  # hold batch 0 'on device'

    engine = ServeEngine(
        _tiny_config(buckets=[4]), place_hook=place_hook,
        execute_hook=execute_hook,
    )
    images = _requests(8)
    with engine:
        futures = [engine.submit(img) for img in images]
        assert executing.wait(timeout=10.0)
        # Batch 0 is executing; wait for the worker to place batch 1.
        deadline = time.monotonic() + 10.0
        while len(place_times) < 2 and time.monotonic() < deadline:
            time.sleep(0.01)
        n_placed_during_execution = len(place_times)
        release.set()
        for f in futures:
            f.result(timeout=30.0)
    assert n_placed_during_execution >= 2, (
        "placement of batch N+1 was not issued while batch N executed"
    )


def test_engine_dynamic_batching_beats_batch_size_1():
    """The throughput half of the acceptance criterion, as what the
    batcher decides and not as which of two floods a shared CPU finished
    sooner: under the same 48-request flood the deadline-aware bucketed
    ladder runs 6 device steps (eights, every one full: with the deadline
    far off the drain lingers until its largest bucket fills) where the
    ladder-[1] baseline runs 48, and no admitted request overran its
    deadline by more than one bucket's measured step time."""
    from sav_tpu.serve.engine import ServeEngine

    n = 48
    results = {}
    for label, buckets in (("batched", [1, 8]), ("bs1", [1])):
        # Deadline sized so the admission projection admits the whole
        # flood even against the bs1 arm's 48-batch backlog (the
        # shedding path has its own deterministic test below), and so
        # that no worker, however loaded, pushes the drain to ship a
        # partial bucket before the flood is in.
        engine = ServeEngine(
            _tiny_config(buckets=buckets, max_queue=256, deadline_ms=60000.0)
        )
        with engine:
            futures = [engine.submit(img) for img in _requests(n)]
            for f in futures:
                f.result(timeout=120.0)
        summary = engine.stop()
        assert summary["requests"] == n
        # One bucket's step time is the pinned overrun bound; the EMA
        # estimate tracks the actual, so allow scheduler slop on top.
        max_step_ms = max(engine._step_est.values()) * 1e3
        assert summary["deadline_overrun_max_ms"] <= max_step_ms + 250.0
        results[label] = summary
    assert results["bs1"]["bucket_occupancy"] == {
        "1": {"batches": n, "fill": 1.0}
    }
    assert results["batched"]["bucket_occupancy"] == {
        "8": {"batches": n // 8, "fill": 1.0}
    }
    assert results["batched"]["padding_waste_frac"] == 0.0


def test_engine_admission_validation_and_lifecycle():
    """One engine, three contracts. Admission shed, deterministically:
    hold the feeder worker inside the FIRST batch's placement
    (place_hook blocks on its thread) so the drain stops pulling; with
    max_queue=1 the next submit is admitted and the one after that must
    reject — and the ledger counts it. Plus the submit validation
    errors and the not-started/stopped lifecycle errors."""
    from sav_tpu.serve.engine import ServeEngine

    placing = threading.Event()
    release = threading.Event()

    def place_hook(formed):
        placing.set()
        release.wait(timeout=10.0)

    engine = ServeEngine(
        _tiny_config(max_queue=1, buckets=[1]), place_hook=place_hook
    )
    images = _requests(4)
    with pytest.raises(ServeClosedError, match="start"):
        engine.submit(images[0])
    with engine:
        with pytest.raises(ValueError, match="uint8"):
            engine.submit(np.zeros((32, 32, 3), np.float32))
        with pytest.raises(ValueError, match="32, 32, 3"):
            engine.submit(np.zeros((16, 16, 3), np.uint8))
        with pytest.raises(ValueError, match="deadline_s"):
            engine.submit(images[0], deadline_ms=0.0)
        futures = [engine.submit(images[0])]
        # The drain grabs request 0 (ladder [1] ships singles outright)
        # and the worker blocks inside its placement.
        assert placing.wait(timeout=10.0)
        deadline = time.monotonic() + 10.0
        while engine._batcher.stats()["queued"] and time.monotonic() < deadline:
            time.sleep(0.01)
        futures.append(engine.submit(images[1]))  # fills the queue (1)
        with pytest.raises(QueueFullError):
            engine.submit(images[2])
        release.set()
        for f in futures:
            f.result(timeout=30.0)
    assert engine.stop()["rejected"] == 1
    with pytest.raises(ServeClosedError):
        engine.submit(images[0])


def test_engine_rejects_buckets_that_do_not_shard(devices):
    import jax

    from sav_tpu.parallel.mesh import create_mesh
    from sav_tpu.serve.engine import ServeEngine

    mesh = create_mesh({"data": 8}, devices=jax.devices())
    with pytest.raises(ValueError, match="do not divide the mesh"):
        ServeEngine(_tiny_config(buckets=[1, 2, 8]), mesh=mesh)


# -------------------------------------------- params-only restore + serve


def _tiny_train_config(tmpdir, **overrides):
    from sav_tpu.train.config import TrainConfig

    base = dict(
        model_name="vit_ti_patch16", num_classes=10, image_size=32,
        model_overrides={"num_layers": 1}, global_batch_size=8,
        num_train_images=64, num_epochs=1, checkpoint_dir=str(tmpdir),
        fleet=False,
    )
    base.update(overrides)
    return TrainConfig(**base)


@pytest.mark.parametrize(
    "layout",
    ["fused", "per_leaf", "per_leaf_ema"],
)
def test_restore_params_only_accepts_every_opt_layout(tmp_path, layout):
    """The satellite contract: params-only restore never touches
    opt_state, so flat-buffer (a file from a trainer up to PR 41),
    per-leaf, and EMA-carrying checkpoints all restore — without
    requesting a single opt_state leaf from orbax."""
    import jax

    from sav_tpu.train.checkpoint import Checkpointer
    from sav_tpu.train.optimizer import make_optimizer
    from sav_tpu.train.trainer import Trainer

    cfg = _tiny_train_config(
        tmp_path,
        ema_decay=0.99 if layout == "per_leaf_ema" else None,
    )
    trainer = Trainer(cfg)
    state = trainer.init_state()
    if layout == "fused":
        state = state.replace(
            opt_state=make_optimizer(trainer.schedule, fused=True).init(state.params)
        )
    trainer.checkpointer.save(0, state)
    trainer.checkpointer.wait()
    reader = Checkpointer(str(tmp_path), read_only=True)
    try:
        probed = reader.opt_layout()
        assert probed.get("fused") is (layout == "fused")
        assert probed.get("ema") is (layout == "per_leaf_ema")
        template = {
            "params": state.params,
            "batch_stats": state.batch_stats,
            "step": state.step,
        }
        restored = reader.restore_params_only(template)
    finally:
        reader.close()
    assert sorted(restored.keys()) == ["batch_stats", "params", "step"]
    assert jax.tree.all(
        jax.tree.map(
            lambda a, b: np.array_equal(np.asarray(a), np.asarray(b)),
            restored["params"], state.params,
        )
    )


def test_restore_params_only_empty_dir_returns_none(tmp_path):
    from sav_tpu.train.checkpoint import Checkpointer

    ckpt = Checkpointer(str(tmp_path))
    try:
        assert ckpt.restore_params_only({"params": {}}) is None
    finally:
        ckpt.close()


def test_engine_serves_training_checkpoint_params_only(tmp_path):
    """End to end: a training checkpoint (full TrainState incl. Adam
    moments) serves through the engine's params-only restore, and the
    served logits match the checkpointed weights."""
    import jax
    import jax.numpy as jnp

    from sav_tpu.ops.preprocess import normalize_images
    from sav_tpu.serve.engine import ServeEngine
    from sav_tpu.train.trainer import Trainer

    trainer = Trainer(_tiny_train_config(tmp_path))
    state = trainer.init_state()
    trainer.checkpointer.save(0, state)
    trainer.checkpointer.wait()
    engine = ServeEngine(_tiny_config(checkpoint_dir=str(tmp_path)))
    assert engine.startup_report["params_source"].startswith("checkpoint:")
    image = _requests(1)[0]
    with engine:
        row = engine.submit(image).result(timeout=30.0)
    x = normalize_images(jnp.asarray(image[None]), engine.compute_dtype)
    expected = np.asarray(
        trainer.model.apply(
            {"params": state.params}, x, is_training=False
        ).astype(jnp.float32)
    )[0]
    np.testing.assert_allclose(row, expected, rtol=0.05, atol=0.05)


# ------------------------------------------------- manifest + sentinel


def test_sentinel_scores_serve_fixtures_both_directions(capsys):
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import regression_sentinel as sentinel
    finally:
        sys.path.pop(0)
    assert sentinel.main([os.path.join(FIXTURES, "serve_clean")]) == 0
    clean_out = capsys.readouterr().out
    assert "ok      p99_latency_ms" in clean_out
    assert "ok      serve_throughput" in clean_out
    assert sentinel.main(
        ["--json", os.path.join(FIXTURES, "serve_regressed")]
    ) == 1
    report = json.loads(capsys.readouterr().out)
    flagged = {v["metric"] for v in report["verdicts"] if v["regressed"]}
    assert flagged == {"p99_latency_ms", "serve_throughput"}


def test_sentinel_skips_records_without_serving_metrics():
    """The attention_core_frac presence contract, for serving: training
    records are skipped (not zero-filled) for the serve metrics, and a
    training candidate after serving history is not scorable."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        from regression_sentinel import judge_metric
    finally:
        sys.path.pop(0)
    from sav_tpu.obs.manifest import normalize_run_record

    def serve_line(p99, rps, i):
        return normalize_run_record(
            {"outcome": "ok", "p99_latency_ms": p99, "serve_throughput": rps},
            label=f"s{i}", index=i,
        )

    def train_line(i):
        return normalize_run_record(
            {"value": 1800.0, "unit": "img/s/chip"}, label=f"t{i}", index=i,
        )

    history = [train_line(0), serve_line(21.0, 400.0, 1),
               serve_line(22.0, 410.0, 2), serve_line(21.5, 395.0, 3),
               serve_line(21.2, 402.0, 4)]
    verdict = judge_metric(
        history, "p99_latency_ms", k=3.5, rel_floor=0.05, min_history=2
    )
    assert verdict is not None and not verdict.regressed
    # Training-only history: nothing to score, never zero-filled.
    assert judge_metric(
        [train_line(i) for i in range(5)], "p99_latency_ms",
        k=3.5, rel_floor=0.05, min_history=2,
    ) is None
    # Newest record is a training bench: scoring would re-judge a stale
    # serving record as "the candidate" — not scorable.
    assert judge_metric(
        history + [train_line(5)], "p99_latency_ms",
        k=3.5, rel_floor=0.05, min_history=2,
    ) is None


# ---------------------------------------------------- zoo serve check


def test_zoo_serve_check_all_seven_families_on_cpu(capsys):
    """The acceptance criterion: every model family AOT-lowers +
    compiles + runs the serving program (smallest bucket) on CPU."""
    sys.path.insert(0, os.path.join(ROOT, "tools"))
    try:
        import zoo_tpu_check
    finally:
        sys.path.pop(0)
    argv = sys.argv
    sys.argv = ["zoo_tpu_check.py", "--serve", "--smoke"]
    try:
        with pytest.raises(SystemExit) as exit_info:
            zoo_tpu_check.main()
    finally:
        sys.argv = argv
    assert exit_info.value.code == 0
    out = capsys.readouterr().out
    assert out.count("OK  serve") == 7
    assert "ALL SERVABLE" in out
    families = ["vit_ti_patch16", "botnet_t3", "tnt_s_patch16", "ceit_t",
                "cait_xxs_24", "cvt-13", "mixer_s_patch16"]
    for family in families:
        assert f"OK  serve {family}" in out


# --------------------------------- serve_bench + warm compile cache proof


def _run_serve_bench(tmp_path, tag, cache_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    manifest = str(tmp_path / f"manifest-{tag}.json")
    proc = subprocess.run(
        [
            sys.executable, os.path.join(ROOT, "tools", "serve_bench.py"),
            "--model", "vit_ti_patch16", "--num-classes", "10",
            "--image-size", "32",
            "--model-overrides", '{"num_layers": 1}',
            "--buckets", "1,2", "--requests", "12", "--deadline-ms", "2000",
            "--compilation-cache-dir", str(cache_dir),
            "--manifest", manifest,
        ],
        capture_output=True, text=True, timeout=420, cwd=ROOT, env=env,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    return line, manifest


def test_serve_bench_line_and_warm_cache_restart(tmp_path):
    """Two REAL serve_bench processes sharing a persistent compile
    cache. The first (cold) start compiles every bucket from scratch;
    the second (warm) start compiles ZERO from scratch — every
    executable is a cache hit, which is what makes an engine restart
    milliseconds of compile instead of minutes. Also pins the
    serve_bench JSON line contract and its finalized manifest."""
    cache_dir = tmp_path / "xla_cache"
    cold, cold_manifest = _run_serve_bench(tmp_path, "cold", cache_dir)
    warm, warm_manifest = _run_serve_bench(tmp_path, "warm", cache_dir)
    # --- the parseable-line acceptance contract -------------------------
    for line in (cold, warm):
        assert line["outcome"] == "ok"
        assert line["requests"] == 12
        for key in ("p50_latency_ms", "p95_latency_ms", "p99_latency_ms",
                    "serve_throughput"):
            assert isinstance(line[key], (int, float)) and line[key] > 0
        assert line["padding_waste_frac"] >= 0.0
        assert line["bucket_occupancy"]  # per-bucket batches + fill
        assert line["queue_depth_avg"] >= 0.0
        assert line["deadline_overruns"] == 0
    # --- warm-restart proof: cache-hit counts asserted ------------------
    assert cold["startup"]["compiled_from_scratch"] == 2
    assert cold["startup"]["cache_hits"] == 0
    assert warm["startup"]["compiled_from_scratch"] == 0
    assert warm["startup"]["cache_hits"] == 2
    # --- r11 telemetry rides the line: SLO + the telemetry block --------
    for line in (cold, warm):
        assert line["slo_hit_frac"] == 1.0  # every request met its budget
        assert line["burn_rate"] == 0.0
        assert line["telemetry"]["exemplars"] == 0
    # --- backed by a finalized manifest the sentinel can score ----------
    with open(warm_manifest) as f:
        manifest = json.load(f)
    assert manifest["kind"] == "serve"
    assert manifest["outcome"] == "ok"
    assert manifest["metrics"]["serve/p99_latency_ms"] == (
        warm["p99_latency_ms"]
    )
    assert manifest["metrics"]["serve/compiled_from_scratch"] == 0.0
    assert manifest["metrics"]["serve/slo_hit_frac"] == 1.0
    assert manifest["notes"]["serve_telemetry"]["slo"]["target"] == 0.99


# -------------------------------------------------- preprocess parity


def test_preprocess_request_validation():
    from sav_tpu.serve.preprocess import preprocess_request

    with pytest.raises(ValueError, match=r"\[H, W, 3\]"):
        preprocess_request(np.zeros((32, 32), np.uint8), 32)
    with pytest.raises(ValueError, match="uint8"):
        preprocess_request(np.zeros((64, 64, 3), np.float32), 32)
    out = preprocess_request(
        np.random.default_rng(0).integers(0, 256, (90, 70, 3), np.uint8), 48
    )
    assert out.shape == (48, 48, 3)
    assert out.dtype == np.uint8


def test_preprocess_request_matches_training_eval_loader():
    """Parity against the training loader's eval path: the SAME decoded
    pixels through pipeline.py's crop_resize (TF) and through the
    numpy request path agree within one uint8 level (TF's bicubic
    quantizes the sample fraction through a 1024-bin table; the
    residual is float-order noise at the truncating cast)."""
    tf = pytest.importorskip("tensorflow")
    from sav_tpu.data.pipeline import _eval_preprocess
    from sav_tpu.serve.preprocess import preprocess_request

    rng = np.random.default_rng(7)
    for (h, w, size) in [(300, 451, 224), (97, 131, 48), (64, 64, 48)]:
        raw = rng.integers(0, 256, (h, w, 3), dtype=np.uint8)
        jpeg = tf.io.encode_jpeg(raw, quality=100).numpy()
        decoded = tf.io.decode_jpeg(jpeg, channels=3).numpy()
        tf_out = _eval_preprocess(jpeg, size, "crop_resize").numpy()
        np_out = preprocess_request(decoded, size)
        diff = np.abs(tf_out.astype(int) - np_out.astype(int))
        assert diff.max() <= 1, (h, w, size, diff.max())
        assert diff.mean() < 0.1


def test_uint8_wire_normalize_matches_training_host_path():
    """The wire stays uint8 end to end: device-side normalization of the
    uint8 request equals the training host pipeline's normalize of the
    float image — bit-for-bit in f32."""
    import jax.numpy as jnp

    from sav_tpu.data.constants import MEAN_RGB, STDDEV_RGB
    from sav_tpu.ops.preprocess import normalize_images

    wire = np.random.default_rng(3).integers(
        0, 256, (2, 32, 32, 3), dtype=np.uint8
    )
    device_side = np.asarray(normalize_images(jnp.asarray(wire), jnp.float32))
    host_side = (
        wire.astype(np.float32) - np.asarray(MEAN_RGB, np.float32)
    ) / np.asarray(STDDEV_RGB, np.float32)
    np.testing.assert_array_equal(device_side, host_side)
