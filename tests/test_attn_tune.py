"""The attention autotuner (tools/attn_tune.py) and the measurement
methodology it inherits (docs/benchmarking.md Traps 1–3), pinned in
tier-1 so the protocol cannot silently regress:

- a bad/infeasible kernel config must be RECORDED and skipped, never kill
  the sweep (the flash_sweep failure mode this tool replaced);
- the emitted cache must be the exact schema the dispatcher consumes;
- the timing loops must thread both the primal and the cotangent through
  the scan carry — asserted structurally on the jaxpr: every matmul in
  the scan body must be reachable from the carry, i.e. not hoistable;
- ab_step's full-step timing loop must thread the train state.
"""

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from sav_tpu.ops import attn_tuning

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(ROOT, "tools", f"{name}.py")
    )
    module = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = module
    spec.loader.exec_module(module)
    return module


@pytest.fixture(scope="module")
def attn_tune():
    return _load_tool("attn_tune")


# ----------------------------------------------------- sweep machinery


def test_sweep_records_infeasible_and_continues(attn_tune, monkeypatch):
    """A config whose compile raises (the Mosaic VMEM failure mode) is
    recorded as infeasible with the error message; the rest of the sweep
    still measures and a winner is still picked."""
    fumod = sys.modules["sav_tpu.ops.fused_attention"]
    real = fumod.fused_attention

    def failing(q, k, v, *a, **kw):
        if kw.get("block_b") == 2:
            raise RuntimeError("Mosaic: VMEM over budget (simulated)")
        return real(q, k, v, *a, **kw)

    monkeypatch.setattr(attn_tune.fumod, "fused_attention", failing)
    results, infeasible = attn_tune.sweep_shape(
        (2, 50, 50, 2, 16),
        blocks=[(64, 64)], block_bs=[1, 2], backends=["xla", "fused"],
        iters=2, rounds=1, bwd=False, log=lambda *_: None,
    )
    assert [r["name"] for r in results] == ["xla", "fused bq=64 bb=1"]
    assert len(infeasible) == 1
    assert infeasible[0]["block_b"] == 2
    assert "VMEM over budget (simulated)" in infeasible[0]["error"]
    winner = attn_tune.pick_winner(results, bwd=False)
    assert winner is not None


def test_sweep_all_infeasible_records_instead_of_crashing(attn_tune, monkeypatch):
    """Every candidate failing must yield (no winner, all recorded) — not
    a ZeroDivisionError out of the empty timing rotation."""

    def always_fail(*a, **kw):
        raise RuntimeError("Mosaic: simulated reject")

    monkeypatch.setattr(attn_tune.fumod, "fused_attention", always_fail)
    results, infeasible = attn_tune.sweep_shape(
        (2, 50, 50, 2, 16),
        blocks=[(64, 64)], block_bs=[1], backends=["fused"],
        iters=2, rounds=1, bwd=False, log=lambda *_: None,
    )
    assert results == []
    assert len(infeasible) == 1
    assert attn_tune.pick_winner(results, bwd=False) is None


def test_sweep_pins_block_b_through_backward_trace(attn_tune, monkeypatch):
    """The swept block_b must still be pinned when the flash BACKWARD
    traces — jax.vjp's bwd rule fires after the forward call returns, so
    a pin scoped to the forward call alone would silently time every
    'bb=N' row with the default-block_b backward."""
    flmod = attn_tune.flmod
    observed = []
    real_bwd = flmod._flash_backward_pallas

    def spy(*a, **kw):
        # 999 divides none of (8, 4, 2): the unpinned picker returns 1,
        # the pinned one returns the swept value regardless of bh.
        observed.append(flmod._pick_block_b(999))
        return real_bwd(*a, **kw)

    monkeypatch.setattr(flmod, "_flash_backward_pallas", spy)
    attn_tune.sweep_shape(
        (2, 24, 24, 2, 16),
        blocks=[(16, 16)], block_bs=[4], backends=["pallas"],
        iters=2, rounds=1, bwd=True, log=lambda *_: None,
    )
    assert observed, "backward never traced"
    assert all(v == 4 for v in observed), observed


def test_sweep_times_both_backward_forms_and_names_them(attn_tune, monkeypatch):
    """A pallas configuration is timed once with the backward its own rule
    picks and once with the two kernels, the form pinned while the backward
    traces; each row and each record says which it ran."""
    flmod = attn_tune.flmod
    budgets = []
    real_bwd = flmod._flash_backward_pallas

    def spy(*a, **kw):
        budgets.append(flmod.ONE_KERNEL_VMEM_BUDGET)
        return real_bwd(*a, **kw)

    monkeypatch.setattr(flmod, "_flash_backward_pallas", spy)
    budget = flmod.ONE_KERNEL_VMEM_BUDGET
    results, infeasible = attn_tune.sweep_shape(
        (2, 24, 24, 2, 16),
        blocks=[(16, 16)], block_bs=[1], backends=["pallas"],
        iters=2, rounds=1, bwd=True, log=lambda *_: None,
    )
    assert not infeasible
    assert [r["config"]["backward"] for r in results] == ["one_kernel", "two_kernels"]
    assert [r["name"].rsplit("bwd=", 1)[1] for r in results] == ["one_kernel", "two_kernels"]
    assert budgets == [budget, 0] and flmod.ONE_KERNEL_VMEM_BUDGET == budget


def test_sweep_of_a_window_times_both_bands_on_grouped_heads(attn_tune, monkeypatch):
    """``--kv-heads`` and ``--window``: a pair of blocks is timed in the form
    the band's rule gives it and, where that is the resident pair, in the
    causal kernels' banded arm too, pinned while both directions trace; the
    operands held with the sequence on the lanes or in their default order."""
    flmod = attn_tune.flmod
    bounds = []
    real = flmod.band_form

    def spy(*a, **kw):
        bounds.append(flmod.BAND_MAX_UNROLLED_TILES)
        return real(*a, **kw)

    monkeypatch.setattr(flmod, "band_form", spy)
    bound = flmod.BAND_MAX_UNROLLED_TILES
    for on_lanes in (True, False):
        results, infeasible = attn_tune.sweep_shape(
            (1, 256, 256, 4, 128), blocks=[(128, 128)], block_bs=[1], backends=["pallas"], iters=1, rounds=1,
            bwd=True, log=lambda *_: None, causal=True, kv_heads=2, window=100, sequence_on_lanes=on_lanes,
            dtype=jnp.float32,
        )
        assert not infeasible
        assert [r["config"].get("band") for r in results] == ["resident", "skipped_cells", "skipped_cells"]
        assert results[0]["config"]["block_b"] is None and results[0]["name"].endswith("band=resident")
        assert all(r["fwd_bwd_ms"] is not None for r in results)
    assert -1 in bounds and bound in bounds and flmod.BAND_MAX_UNROLLED_TILES == bound


def test_a_window_sweep_writes_the_banded_key_and_names_its_command(attn_tune, tmp_path):
    out = tmp_path / "cache.json"
    attn_tune.main([
        "--shapes", "1,256,4,128", "--kv-heads", "2", "--window", "100", "--causal", "--backends", "pallas",
        "--blocks", "128,128", "--block-b", "1", "--iters", "1", "--rounds", "1", "--fwd-only",
        "--sequence-on-lanes", "--dtype", "float32", "--out", str(out),
    ])
    entries = json.loads(out.read_text())["entries"]
    key = "B*.Lq256.Lkv256.H4.D128.float32.causal.window100"
    assert set(entries) == {key, key.replace("B*", "B1")}
    assert "tools/attn_tune.py --kv-heads 2 --window 100 --sequence-on-lanes" in entries[key]["source"]
    # A window no shorter than the sequence is the causal core's key.
    attn_tune.main([
        "--shapes", "1,256,4,128", "--window", "256", "--causal", "--backends", "xla", "--iters", "1",
        "--rounds", "1", "--fwd-only", "--dtype", "float32", "--out", str(out),
    ])
    assert set(json.loads(out.read_text())["entries"]) == {"B*.Lq256.Lkv256.H4.D128.float32.causal",
                                                           "B1.Lq256.Lkv256.H4.D128.float32.causal"}


def test_on_lanes_holds_operands_where_a_projection_leaves_them(attn_tune):
    """Operands held ``[B, H, D, L]`` are the ``[B, L, H, D]`` ones to the
    function, and the transposes are traced inside it (two that cancel at
    the kernels' own ``[B, H·D, L]`` view), not made ahead of it."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(key, (1, 64, h, 16)) for key, h in zip(ks, (4, 2, 2)))
    fn = lambda q, k, v: attn_tune.att.xla_attention(q, k, v, causal=True, window=20)
    held = [jnp.transpose(x, (0, 2, 3, 1)) for x in (q, k, v)]
    np.testing.assert_allclose(np.asarray(attn_tune.on_lanes(fn)(*held)), np.asarray(fn(q, k, v)), atol=1e-6)
    names = [eqn.primitive.name for eqn in jax.make_jaxpr(attn_tune.on_lanes(fn))(*held).jaxpr.eqns]
    assert names[:3] == ["transpose"] * 3


def test_sweep_precheck_skips_over_budget_without_compiling(attn_tune):
    """Configs the VMEM estimator rules out are recorded infeasible
    without paying a compile (block_b=8 at a deliberately fat shape)."""
    specs = list(attn_tune.variant_specs(
        8, 197, 197, 6, 64,
        blocks=[(256, 256)], block_bs=[8], backends=["fused"], itemsize=2,
    ))
    assert len(specs) == 1
    name, backend, cfg, build = specs[0]
    assert backend == "fused" and build is None  # estimator said no


def test_emitted_cache_is_dispatcher_consumable(attn_tune, tmp_path):
    """End to end on CPU: sweep → write_cache → attn_tuning.lookup →
    resolve_attention_backend consults the new entry (and the infeasible
    record survives the merge)."""
    out = str(tmp_path / "cache.json")
    rc = attn_tune.main([
        "--shapes", "2,50,2,16", "--blocks", "64,64", "--block-b", "1",
        "--backends", "xla,fused", "--iters", "2", "--rounds", "1",
        "--fwd-only", "--out", out,
    ])
    assert rc == 0
    cache = json.load(open(out))
    assert cache["version"] == attn_tuning.CACHE_VERSION
    key = attn_tuning.shape_key(2, 50, 50, 2, 16)
    star = attn_tuning.shape_key("*", 50, 50, 2, 16)
    assert key in cache["entries"] and star in cache["entries"]
    entry = cache["entries"][key]
    assert entry["backend"] in ("xla", "fused", "pallas")
    assert entry["fwd_ms"] > 0
    # Merge keeps prior entries and accumulates infeasible records.
    attn_tuning.write_cache(
        out,
        {"B9.Lq9.Lkv9.H9.D9.bfloat16": {"backend": "xla", "source": "x"}},
        {key: [{"backend": "pallas", "block_b": 16, "error": "VMEM"}]},
        merge=True,
    )
    merged = json.load(open(out))
    assert key in merged["entries"]  # survived the merge
    assert merged["infeasible"][key][0]["block_b"] == 16
    # The dispatcher consults it.
    attn_tuning.set_cache_path(out)
    try:
        assert attn_tuning.lookup(2, 50, 50, 2, 16) == entry
    finally:
        attn_tuning.set_cache_path(None)


def test_winner_prefers_fwd_bwd_metric(attn_tune):
    results = [
        {"name": "a", "backend": "xla", "config": None,
         "fwd_ms": 1.0, "fwd_bwd_ms": 9.0},
        {"name": "b", "backend": "fused",
         "config": {"block_q": 64, "block_kv": None, "block_b": 2},
         "fwd_ms": 2.0, "fwd_bwd_ms": 3.0},
    ]
    assert attn_tune.pick_winner(results, bwd=True)["name"] == "b"
    assert attn_tune.pick_winner(results, bwd=False)["name"] == "a"
    entry = attn_tune.winner_entry(attn_tune.pick_winner(results, bwd=True), "src")
    assert entry == {
        "backend": "fused", "block_q": 64, "block_kv": None, "block_b": 2,
        "fwd_ms": 2.0, "fwd_bwd_ms": 3.0, "source": "src",
    }


# --------------------------------------- methodology pins (Traps 1 & 2)


def _subjaxprs(eqn):
    for v in eqn.params.values():
        vals = v if isinstance(v, (tuple, list)) else (v,)
        for x in vals:
            if isinstance(x, jax.extend.core.ClosedJaxpr):
                yield x.jaxpr
            elif isinstance(x, jax.extend.core.Jaxpr):
                yield x


def _check_dots_carry_fed(jaxpr, seeds):
    """Walk a jaxpr with `seeds` (carry-derived invars) marked reachable;
    return (num_dots, num_carry_fed_dots), descending into sub-jaxprs with
    positional invar mapping where it lines up."""
    reachable = set(map(id, seeds))
    dots = fed = 0
    for eqn in jaxpr.eqns:
        ins_reach = [
            not hasattr(v, "val") and id(v) in reachable for v in eqn.invars
        ]
        if eqn.primitive.name in ("dot_general", "pjit") or list(
            _subjaxprs(eqn)
        ):
            if eqn.primitive.name == "dot_general":
                dots += 1
                fed += any(ins_reach)
            for sub in _subjaxprs(eqn):
                if len(sub.invars) == len(eqn.invars):
                    sub_seeds = [
                        sv for sv, r in zip(sub.invars, ins_reach) if r
                    ]
                elif any(ins_reach):
                    sub_seeds = list(sub.invars)  # conservative
                else:
                    sub_seeds = []
                d, f = _check_dots_carry_fed(sub, sub_seeds)
                dots += d
                fed += f
        elif eqn.primitive.name == "dot_general":
            dots += 1
            fed += any(ins_reach)
        if any(ins_reach):
            reachable.update(id(v) for v in eqn.outvars)
    return dots, fed


def _scan_carry_dot_stats(fn, *args):
    """For every scan in fn's jaxpr: (dots, carry-fed dots) inside the
    scan body, seeding reachability from the carry invars only."""
    jaxpr = jax.make_jaxpr(fn)(*args)

    stats = []

    def visit(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "scan":
                body = eqn.params["jaxpr"].jaxpr
                nc, ncar = eqn.params["num_consts"], eqn.params["num_carry"]
                carry = body.invars[nc:nc + ncar]
                stats.append(_check_dots_carry_fed(body, carry))
            else:
                for sub in _subjaxprs(eqn):
                    visit(sub)

    visit(jaxpr.jaxpr)
    return stats


def _qkv(l=24, d=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    return tuple(jax.random.normal(kk, (2, l, 2, d)) for kk in ks)


def test_timing_loop_threads_primal_through_carry(attn_tune):
    """Trap 1 pin: in the fwd timing loop's scan body, EVERY matmul is
    reachable from the carry — nothing is loop-invariant-hoistable."""
    from sav_tpu.ops.attention import xla_attention

    q, k, v = _qkv()
    loop = attn_tune.timing_loop(lambda q, k, v: xla_attention(q, k, v), 3)
    stats = _scan_carry_dot_stats(loop, q, k, v)
    assert stats, "timing loop lost its scan"
    for dots, fed in stats:
        assert dots > 0
        assert fed == dots, f"{dots - fed} hoistable matmuls in timing scan"


def test_grad_loop_threads_primal_and_cotangent(attn_tune):
    """Traps 1+2 pin: the fwd+bwd loop's backward matmuls (dP = g·Vᵀ and
    friends) must also be carry-fed — a trivial/loop-invariant cotangent
    would let the simplifier collapse them (docs/benchmarking.md)."""
    from sav_tpu.ops.attention import xla_attention

    q, k, v = _qkv()
    cot = jax.random.normal(jax.random.PRNGKey(1), q.shape)
    wrapped = attn_tune.grad_wrap(lambda q, k, v: xla_attention(q, k, v), cot)
    loop = attn_tune.timing_loop(wrapped, 3)
    stats = _scan_carry_dot_stats(loop, q, k, v)
    assert stats, "grad timing loop lost its scan"
    # The fwd+bwd body has strictly more matmuls than the fwd-only body
    # (the backward's transpose-dots), and every one is carry-fed.
    fwd_dots = _scan_carry_dot_stats(
        attn_tune.timing_loop(lambda q, k, v: xla_attention(q, k, v), 3),
        q, k, v,
    )[0][0]
    for dots, fed in stats:
        assert dots > fwd_dots, "backward matmuls missing from the loop"
        assert fed == dots, f"{dots - fed} hoistable matmuls in grad scan"


def test_methodology_pin_catches_hoistable_loop(attn_tune):
    """The pin itself must fail a Trap-1 regression: a loop that does NOT
    thread the primal (constant operands every iteration) shows
    non-carry-fed matmuls."""
    from sav_tpu.ops.attention import xla_attention

    q, k, v = _qkv()

    @jax.jit
    def bad_loop(q, k, v):
        def body(carry, _):
            out = xla_attention(q, k, v)  # loop-invariant: hoistable
            return carry + jnp.sum(out.astype(jnp.float32)) * 1e-30, None

        tot, _ = jax.lax.scan(body, jnp.float32(0), None, length=3)
        return tot

    stats = _scan_carry_dot_stats(bad_loop, q, k, v)
    assert stats
    dots, fed = stats[0]
    assert dots > 0 and fed < dots, (
        "reachability check failed to flag a hoistable timing loop"
    )


def test_ab_step_time_steps_threads_state():
    """ab_step's full-step timing loop must thread the train state through
    the python loop (call N receives call N-1's output) — re-stepping a
    constant state would let XLA serve every step from one result."""
    ab_step = _load_tool("ab_step")

    received = []

    class FakeTrainer:
        def init_state(self, seed=0):
            return jnp.float32(0)

        def shard_batch(self, b):
            return b

        def _train_step(self, state, batch, rng):
            received.append(float(state))
            return state + 1, {"loss": jnp.float32(0)}

    best, med = ab_step.time_steps(
        FakeTrainer(), batch={}, warmup=1, windows=2, steps=3
    )
    assert best >= 0 and med >= 0
    assert received == list(map(float, range(len(received)))), (
        "time_steps must thread state through consecutive steps"
    )
