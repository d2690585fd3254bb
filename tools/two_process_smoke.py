#!/usr/bin/env python
"""Two-process distributed smoke: multi-process init → mesh → dp/tp/sp/pp/ep/fsdp steps.

VERDICT r3 item 8: nothing had ever *executed* the multi-process bring-up
path (``distributed_init`` → ``jax.distributed.initialize`` → one global
mesh spanning two processes' devices), even on CPU. This script is that
evidence — the CPU stand-in for the reference's implicit multi-host TPU-VM
SPMD (input_pipeline.py:102, train.py:96):

- the parent spawns 2 worker processes (rank 0 hosts the coordinator);
- each worker runs ``jax.distributed.initialize(coordinator, 2, rank)``
  via :func:`sav_tpu.parallel.distributed_init`, sees 4 global devices
  (2 local CPU devices each), builds one ``data=4`` mesh across both
  processes, and runs ONE DP train step through the real ``Trainer``
  (``shard_batch`` assembles the global batch from per-host shards via
  ``jax.make_array_from_process_local_data``);
- both workers print their loss; the parent asserts the two agree
  bit-for-bit (the gradient AllReduce crossed the process boundary) and
  that a second step decreases the loss.

``--mode tp`` (round 5) goes further: the mesh is laid out so the
``model`` axis itself SPANS the process boundary (device array
transposed: each model-parallel pair has one device in each process), so
the tensor-parallel activation psums — not just the gradient AllReduce —
cross processes. The parent additionally runs the same config
single-process on an identically-shaped ``data=2 × model=2`` mesh and
asserts the loss sequence is bit-for-bit identical: device placement
changes the transport (cross-process collectives vs shared memory), never
the numerics.

``--mode sp`` is the same transposed layout on the ``seq`` axis: the
ring's K/V ppermute hops cross processes (ring attention multi-host).
``--mode fleet`` (round 7) exercises the fleet-telemetry layer
(sav_tpu/obs/fleet.py, docs/fleet.md) under REAL multi-process: two
worker processes each run a short ``Trainer.fit`` over ONE shared log
dir with an injected input-side delay on rank 1 (the straggler); the
parent asserts both processes heartbeat into ``fleet/proc_<i>.jsonl``,
the merged fleet manifest was written exactly once (fleet process 0),
and the offline aggregation (``tools/fleet_status.py --json``) ranks
the injected-delay process as the straggler. Fleet identity comes from
the ``SAV_FLEET_PROC``/``SAV_FLEET_PROCS`` override — the documented
seam for fleets not coordinated through ``jax.distributed`` — because
this leg targets the telemetry layer, which is transport-agnostic by
design (the dp/tp/... modes own the collective-transport proof, and
CPU backends without multiprocess computation support must still be
able to smoke the fleet layer).
``--mode pp`` puts the ``pipe`` axis across processes: the GPipe
stage-boundary activation ppermutes ride the cross-process transport.
``--mode ep`` swaps in the MoE ViT with the ``expert`` axis across
processes (router dispatch/combine all-to-alls). ``--mode fsdp`` shards
parameters over a cross-process ``fsdp`` axis (ZeRO-3 all-gathers +
reduce-scatters); its single-reference comparison is tolerance-based —
4-way gradient reductions pick up last-ulp reduction-order differences
when placement reorders the devices.

Run: ``python tools/two_process_smoke.py`` (CPU; runs all six modes —
dp, tp, sp, pp, ep, fsdp; ``--mode X`` for one). Committed output:
evidence/two_process_smoke.txt.
"""

from __future__ import annotations

import os
import socket
import subprocess
import sys

GLOBAL_BATCH = 8
N_LOCAL_DEVICES = 2
NUM_PROCESSES = 2


# mode → the mesh axis that joins 'data' (None = pure DP). In every
# non-dp mode the worker mesh is transposed so that axis SPANS the
# process boundary.
MODE_AXIS = {"dp": None, "tp": "model", "sp": "seq", "pp": "pipe",
             "ep": "expert", "fsdp": "fsdp"}


def _config(mode: str):
    from sav_tpu.train import TrainConfig

    overrides = dict(num_layers=2, embed_dim=64, num_heads=4)
    extra = {}
    if mode == "fsdp":
        # Big enough that the MLP kernels clear param_shardings'
        # fsdp_min_elements (2^16) and actually shard over 'fsdp' — the
        # whole point is cross-process all-gathers on real parameters.
        overrides["embed_dim"] = 256
    if mode == "sp":
        # 32² at patch 8 → 17 tokens: odd length exercises the ring's
        # pad-and-mask path across the process boundary.
        overrides["patch_shape"] = (8, 8)
    if mode == "pp":
        # 2 stages x 1 encoder layer, 2 microbatches of 2 per data shard:
        # the GPipe stage-boundary ppermute crosses the process boundary.
        extra = dict(pipeline_parallel=2, pipeline_microbatches=2)
    return TrainConfig(
        # ep swaps in the MoE ViT (8 experts over expert=2): the router's
        # dispatch/combine all-to-alls cross the process boundary.
        model_name="vit_moe_s_patch16_e8" if mode == "ep" else "vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=GLOBAL_BATCH,
        num_train_images=GLOBAL_BATCH * 4,
        num_epochs=2,
        warmup_epochs=1,
        base_lr=0.05,  # LR auto-scales by batch/512; keep the step visible
        transpose_images=False,
        model_overrides=overrides,
        seed=0,
        # No mesh_axes override: every tp/sp call site passes an explicit
        # Mesh to Trainer (which then ignores config.mesh_axes) — a second
        # copy of the shape here could silently drift from the real layout.
        sequence_parallel="ring" if mode == "sp" else None,
        **extra,
    )


def _global_batch():
    import numpy as np

    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, (GLOBAL_BATCH,))
    images = (
        labels[:, None, None, None] * 20 + rng.normal(0, 8, (GLOBAL_BATCH, 32, 32, 3))
    ).astype(np.float32) / 127.5 - 1.0
    return images, labels


def _run_steps(trainer, batch, tag: str, presharded: bool = False) -> None:
    import jax

    state = trainer.init_state(0)
    step = trainer._train_step if presharded else trainer.train_step
    losses = []
    # Several steps: warmup LR is 0 at step 0 (nothing moves), so proving
    # the cross-process update path needs the ramp to kick in.
    for i in range(6):
        state, metrics = step(state, batch, jax.random.PRNGKey(i))
        losses.append(float(jax.device_get(metrics["loss"])))
    print("%s LOSS %s" % (tag, " ".join(f"{l:.9f}" for l in losses)), flush=True)


def _make_global(x, sharding):
    """Assemble a global array from exact per-device shards.

    ``shard_batch``'s per-host path assumes each process's rows are one
    contiguous block; the transposed-fsdp mesh gives each process two
    NON-contiguous batch quarters, so place every local device's slice
    explicitly (the sharding's own indices map is the ground truth).
    """
    import jax

    arrs = []
    idx_map = sharding.addressable_devices_indices_map(x.shape)
    for d, idx in idx_map.items():
        arrs.append(jax.device_put(x[idx], d))
    return jax.make_array_from_single_device_arrays(x.shape, sharding, arrs)


def single_reference(mode: str) -> None:
    """Single-process reference: same data=2 x <axis>=2 shape, local devices."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    n = NUM_PROCESSES * N_LOCAL_DEVICES
    devs = np.asarray(jax.devices()[:n]).reshape(NUM_PROCESSES, N_LOCAL_DEVICES)
    from sav_tpu.train import Trainer

    trainer = Trainer(
        _config(mode), mesh=Mesh(devs, ("data", MODE_AXIS[mode]))
    )
    images, labels = _global_batch()
    _run_steps(
        trainer, {"images": images, "labels": labels.astype(np.int32)}, "SINGLE"
    )


FLEET_STEPS = 8
FLEET_DELAY_S = 0.25  # rank 1's injected per-step input delay


def fleet_worker(rank: int, log_dir: str) -> None:
    """One fleet-mode worker: a short real fit() with heartbeats on and
    an injected input-side delay on rank 1 — the straggler pattern the
    aggregator must attribute (the delay lands in rank 1's input_wait
    bucket and stretches its heartbeat intervals). Identity comes from
    SAV_FLEET_PROC/_PROCS set by the parent; the workers are otherwise
    independent single-process fits sharing one log dir."""
    import time as _time

    import jax
    import numpy as np

    from sav_tpu.train import TrainConfig, Trainer

    config = TrainConfig(
        model_name="vit_ti_patch16",
        num_classes=10,
        image_size=32,
        compute_dtype="float32",
        global_batch_size=GLOBAL_BATCH,
        num_train_images=GLOBAL_BATCH * FLEET_STEPS,
        num_epochs=1,
        warmup_epochs=0,
        base_lr=1e-3,
        transpose_images=False,
        model_overrides=dict(num_layers=2, embed_dim=64, num_heads=4),
        log_every_steps=1,
        log_dir=log_dir,
        fleet=True,
        seed=0,
    )
    trainer = Trainer(config)

    images, labels = _global_batch()

    def batches():
        for step in range(FLEET_STEPS):
            if rank == 1:
                _time.sleep(FLEET_DELAY_S)  # the injected straggler
            yield {
                "images": images,
                "labels": labels.astype(np.int32),
            }

    state, history = trainer.fit(batches(), num_steps=FLEET_STEPS)
    steps = int(jax.device_get(state.step))
    print(f"RANK {rank} FLEETSTEPS {steps}", flush=True)


def _run_fleet() -> int:
    import glob
    import json
    import tempfile

    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    base_env = dict(os.environ)
    base_env["PYTHONPATH"] = repo_root + (
        os.pathsep + base_env["PYTHONPATH"]
        if base_env.get("PYTHONPATH") else ""
    )
    base_env["JAX_PLATFORMS"] = "cpu"
    base_env["XLA_FLAGS"] = (
        base_env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={N_LOCAL_DEVICES}"
    )
    log_dir = tempfile.mkdtemp(prefix="sav_fleet_smoke_")
    base_env["SMOKE_FLEET_LOG_DIR"] = log_dir

    print("=== mode fleet ===", flush=True)
    procs = []
    for r in range(NUM_PROCESSES):
        env = dict(base_env)
        # The documented non-jax.distributed fleet identity seam
        # (sav_tpu/obs/fleet.py resolve_identity).
        env["SAV_FLEET_PROC"] = str(r)
        env["SAV_FLEET_PROCS"] = str(NUM_PROCESSES)
        procs.append(
            subprocess.Popen(
                [sys.executable, __file__, "--rank", str(r),
                 "--mode", "fleet"],
                env=env,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                text=True,
            )
        )
    outs = []
    ok = True
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            ok = False
        outs.append(out)
        print(f"--- rank {r} (rc={p.returncode}) ---\n{out}")
        ok = ok and p.returncode == 0
    all_out = "\n".join(outs)
    if not ok:
        print("FAIL: fleet workers did not complete")
        return 1
    done = [
        line for line in all_out.splitlines() if "FLEETSTEPS" in line
    ]
    if len(done) != NUM_PROCESSES:
        print(f"FAIL: expected {NUM_PROCESSES} completion lines: {done}")
        return 1

    # 1. Both processes heartbeated into their own streams.
    for r in range(NUM_PROCESSES):
        path = os.path.join(log_dir, "fleet", f"proc_{r}.jsonl")
        if not os.path.exists(path):
            print(f"FAIL: no heartbeat stream {path}")
            return 1
        with open(path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        beats = [ln for ln in lines if ln.get("kind") == "hb"]
        finals = [ln for ln in lines if ln.get("kind") == "final"]
        if len(beats) < FLEET_STEPS or len(finals) != 1:
            print(
                f"FAIL: proc {r} stream malformed: {len(beats)} beats, "
                f"{len(finals)} finals"
            )
            return 1
        if any(b.get("proc") != r for b in beats):
            print(f"FAIL: proc {r} stream carries wrong proc ids")
            return 1

    # 2. The merged fleet manifest was written exactly once (process 0).
    manifests = glob.glob(os.path.join(log_dir, "fleet", "fleet*.json"))
    if len(manifests) != 1:
        print(f"FAIL: expected exactly one merged fleet manifest: "
              f"{manifests}")
        return 1

    # 3. Offline aggregation (through the CLI) names the injected-delay
    # process as the straggler.
    proc = subprocess.run(
        [
            sys.executable,
            os.path.join(repo_root, "tools", "fleet_status.py"),
            "--json", log_dir,
        ],
        capture_output=True, text=True, timeout=120,
    )
    if proc.returncode != 0:
        print(f"FAIL: fleet_status failed: {proc.stderr}")
        return 1
    summary = json.loads(proc.stdout)
    straggler = (summary.get("straggler") or {}).get("straggler")
    if straggler != 1:
        print(
            "FAIL: straggler ranking did not name the injected-delay "
            f"process: {json.dumps(summary.get('straggler'), indent=2)}"
        )
        return 1
    print(
        f"AGREE: fleet mode — both processes heartbeated ({FLEET_STEPS}+ "
        "beats each), one merged fleet manifest, and the offline "
        "aggregation ranked the injected-delay process (rank 1, "
        f"+{FLEET_DELAY_S}s/step input stall) as the straggler"
    )
    return 0


def worker(rank: int, coordinator: str, mode: str) -> None:
    from sav_tpu.parallel import distributed_init

    distributed_init(coordinator, NUM_PROCESSES, rank)

    import jax
    import numpy as np

    assert jax.process_count() == NUM_PROCESSES, jax.process_count()
    n_global = NUM_PROCESSES * N_LOCAL_DEVICES
    assert len(jax.devices()) == n_global, jax.devices()

    from sav_tpu.train import Trainer

    config = _config(mode)
    axis = MODE_AXIS[mode]
    if axis is not None:
        from jax.sharding import Mesh

        # Transposed layout: jax.devices() orders [p0d0, p0d1, p1d0, p1d1];
        # reshape(2, 2).T puts one device from EACH process in every
        # model/seq-axis pair, so the TP activation psums (or the ring's
        # kv ppermute hops) cross the process boundary — the whole point.
        devs = np.asarray(jax.devices()).reshape(NUM_PROCESSES, N_LOCAL_DEVICES).T
        trainer = Trainer(config, mesh=Mesh(devs, ("data", axis)))
    else:
        trainer = Trainer(config)
    mesh = trainer.mesh
    assert mesh.devices.size == n_global, mesh

    # Every process derives the SAME global batch from the seed. DP mode
    # keeps its half — exactly the data pipeline's per-host sharding
    # contract (sav_tpu/data/pipeline.py process_index/count). TP mode's
    # transposed mesh puts one device of EVERY data group in each process,
    # so each process's addressable portion is the full batch.
    images, labels = _global_batch()
    if mode == "fsdp":
        # The batch shards over (data, fsdp); under the transposed mesh each
        # process owns two non-contiguous quarters — place shards explicitly.
        from sav_tpu.parallel import batch_sharding

        sh = batch_sharding(mesh)
        batch = {
            "images": _make_global(images, sh),
            "labels": _make_global(labels.astype(np.int32), sh),
        }
        _run_steps(trainer, batch, "RANK %d" % rank, presharded=True)
        jax.distributed.shutdown()
        return
    if MODE_AXIS[mode] is not None:
        batch = {"images": images, "labels": labels.astype(np.int32)}
    else:
        per_host = GLOBAL_BATCH // NUM_PROCESSES
        sl = slice(rank * per_host, (rank + 1) * per_host)
        batch = {"images": images[sl], "labels": labels[sl].astype(np.int32)}

    _run_steps(trainer, batch, "RANK %d" % rank)
    jax.distributed.shutdown()


def main() -> int:
    mode = "dp"
    if "--mode" in sys.argv:
        mode = sys.argv[sys.argv.index("--mode") + 1]
        if mode not in MODE_AXIS and mode != "fleet":
            print(
                f"unknown --mode {mode!r}; known: "
                f"{sorted(MODE_AXIS) + ['fleet']}",
                file=sys.stderr,
            )
            return 2
    if "--single" in sys.argv:
        if mode == "fleet" or MODE_AXIS[mode] is None:
            print("--single needs --mode tp|sp|pp|ep|fsdp (dp/fleet have "
                  "no reference run)",
                  file=sys.stderr)
            return 2
        single_reference(mode)
        return 0
    if "--rank" in sys.argv:
        rank = int(sys.argv[sys.argv.index("--rank") + 1])
        if mode == "fleet":
            fleet_worker(rank, os.environ["SMOKE_FLEET_LOG_DIR"])
        else:
            worker(rank, os.environ["SMOKE_COORDINATOR"], mode)
        return 0
    if "--mode" in sys.argv:
        modes = [mode]
    else:
        modes = ["dp", "tp", "sp", "pp", "ep", "fsdp", "fleet"]
    for m in modes:
        # bind-then-close port picking races other processes on the host; one
        # retry with a fresh port covers the TOCTOU without masking real bugs
        # (only rendezvous-setup errors trigger it).
        rc = _run_fleet() if m == "fleet" else _run_once(m)
        if rc == 2:
            print("retrying once with a fresh coordinator port", flush=True)
            rc = _run_fleet() if m == "fleet" else _run_once(m)
        if rc != 0:
            return rc
    return 0


def _run_once(mode: str = "dp") -> int:
    with socket.socket() as s:  # pick a free coordinator port
        s.bind(("127.0.0.1", 0))
        port = s.getsockname()[1]

    env = dict(os.environ)
    repo_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo_root + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    # The workers run on virtual CPU devices.
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "")
        + f" --xla_force_host_platform_device_count={N_LOCAL_DEVICES}"
    )
    env["SMOKE_COORDINATOR"] = f"127.0.0.1:{port}"

    print(f"=== mode {mode} ===", flush=True)
    procs = [
        subprocess.Popen(
            [sys.executable, __file__, "--rank", str(r), "--mode", mode],
            env=env,
            stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT,
            text=True,
        )
        for r in range(NUM_PROCESSES)
    ]
    outs = []
    ok = True
    for r, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=600)
        except subprocess.TimeoutExpired:
            p.kill()
            out, _ = p.communicate()
            ok = False
        outs.append(out)
        print(f"--- rank {r} (rc={p.returncode}) ---\n{out}")
        ok = ok and p.returncode == 0

    losses = {}
    for out in outs:
        for line in out.splitlines():
            if line.startswith("RANK"):
                parts = line.split()
                losses[int(parts[1])] = tuple(float(x) for x in parts[3:])
    if not ok or len(losses) != NUM_PROCESSES:
        all_out = "\n".join(outs)
        if "Address already in use" in all_out or (
            "Failed to connect to coordinator" in all_out
        ):
            print("FAIL: coordinator port rendezvous failed (port race)")
            return 2
        print("FAIL: workers did not complete")
        return 1
    if losses[0] != losses[1]:
        print(f"FAIL: processes disagree on the loss: {losses}")
        return 1
    seq = losses[0]
    if not (seq[-1] < seq[0]):
        print(f"FAIL: loss did not decrease over the {mode} steps: {seq}")
        return 1
    if MODE_AXIS[mode] is not None:
        # Single-process reference on an identically-shaped mesh: placement
        # (cross-process vs shared-memory collectives) must not change bits.
        env_s = dict(env)
        # Rebuild from the ORIGINAL environment (not the workers' copy):
        # string surgery on the appended flag risks mangling user flags.
        env_s["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count="
            f"{NUM_PROCESSES * N_LOCAL_DEVICES}"
        )
        env_s.pop("SMOKE_COORDINATOR")
        proc = subprocess.run(
            [sys.executable, __file__, "--single", "--mode", mode],
            env=env_s, capture_output=True, text=True, timeout=900,
        )
        print(f"--- single-process reference (rc={proc.returncode}) ---")
        print(proc.stdout)
        single = None
        for line in proc.stdout.splitlines():
            if line.startswith("SINGLE"):
                single = tuple(float(x) for x in line.split()[2:])
        if proc.returncode != 0 or single is None:
            print(proc.stderr)
            print(f"FAIL: single-process {mode} reference did not complete")
            return 1
        delta = max(
            (abs(a - b) for a, b in zip(single, seq)), default=float("inf")
        )
        # tp/sp/pp/ep keep the EXACT invariant (their cross-placement
        # reductions are 2-way, and two-term addition is order-free);
        # only fsdp's 4-way data x fsdp gradient reduction earns a
        # last-ulp tolerance.
        tol = 5e-6 if mode == "fsdp" else 0.0
        if len(single) != len(seq) or delta > tol:
            print(
                f"FAIL: cross-process {mode} losses differ from "
                f"single-process placement: {seq} vs {single}"
            )
            return 1
        # 2-way reductions are placement-invariant bit-for-bit (two-term
        # addition is commutative); meshes that reduce gradients over BOTH
        # axes (fsdp: data x fsdp = 4 summands) may differ in the last ulps
        # because the collective's reduction order follows device order,
        # which is exactly what the transposed placement changes.
        fidelity = (
            "bit-for-bit"
            if single == seq
            else f"max |Δloss| {delta:.1e} (4-way reduction-order rounding)"
        )
        what = {
            "tp": "activation psums",
            "sp": "ring kv ppermute hops",
            "pp": "GPipe stage-boundary ppermutes",
            "ep": "MoE dispatch/combine all-to-alls",
            "fsdp": "ZeRO-3 param all-gathers + grad reduce-scatters",
        }[mode]
        print(
            f"AGREE: {mode} losses {seq[0]:.9f} -> {seq[-1]:.9f} bit-for-bit "
            f"across ranks, {fidelity} vs the single-process mesh — the "
            f"{MODE_AXIS[mode]} axis spans the process boundary ({what} "
            "over the cross-process transport)"
        )
        return 0
    print(
        f"AGREE: both processes computed losses {seq[0]:.9f} -> {seq[-1]:.9f} "
        f"bit-for-bit (one {NUM_PROCESSES}-process data-parallel mesh, "
        "gradient AllReduce across the process boundary)"
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
