"""Demonstrate the flash kernel's memory win at long sequence length.

PERF.md's honest conclusion from the zoo-shape A/Bs is that the fused
Pallas flash kernel loses to XLA's dense attention on *speed* at vision
sequence lengths (~200 tokens) and earns its keep on *memory*: dense
attention materializes [B, H, L, L] logits (O(L^2) HBM), flash streams
K/V blocks through VMEM (O(L*D + H*L) HBM). This script turns that claim
into a measurement (VERDICT r4 item 8):

  1. picks a long-sequence shape whose dense logits tensor alone exceeds
     the chip's HBM (v5e: 16 GB) so XLA *cannot* run it,
  2. confirms dense attention fails with RESOURCE_EXHAUSTED at that shape,
  3. runs flash_attention forward AND backward at the same shape and
     reports wall time + tokens/s,
  4. optionally (``--ring``) runs the ring-attention path over a
     1-device mesh (the degenerate ring) to show the SP composition also
     executes.

Semantics being scaled: plain softmax(QK^T/sqrt(d))V self-attention —
the same op as /root/reference/models/layers/attentions.py dot-product
attention, at sequence lengths the reference's dense einsum cannot reach.

Usage (real TPU; CPU would "run" dense fine out of swap and prove nothing):
  python tools/flash_memory_win.py
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp


def human_gb(n_bytes: float) -> str:
    return f"{n_bytes / 2**30:.1f} GiB"


def dense_attention(q, k, v, scale):
    s = jnp.einsum("blhd,bmhd->bhlm", q, k, preferred_element_type=jnp.float32)
    p = jax.nn.softmax(s * scale, axis=-1).astype(v.dtype)
    return jnp.einsum("bhlm,bmhd->blhd", p, v)


def main() -> int:
    parser = argparse.ArgumentParser()
    parser.add_argument("--batch", type=int, default=4)
    parser.add_argument("--seq-len", type=int, default=16384)
    parser.add_argument("--heads", type=int, default=8)
    parser.add_argument("--head-dim", type=int, default=64)
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument("--skip-dense", action="store_true",
                        help="skip the dense-OOM proof (e.g. repeat timing runs)")
    parser.add_argument("--ring", action="store_true",
                        help="also run the (1-device) ring attention path")
    args = parser.parse_args()

    from sav_tpu.ops.flash_attention import flash_attention

    dev = jax.devices()[0]
    b, l, h, d = args.batch, args.seq_len, args.heads, args.head_dim
    # f32 softmax logits are what XLA materializes for a stable softmax.
    dense_logits_bytes = b * h * l * l * 4
    print(f"device: {dev.platform} {getattr(dev, 'device_kind', '?')}")
    print(f"shape: B={b} L={l} H={h} D={d}  "
          f"dense [B,H,L,L] f32 logits = {human_gb(dense_logits_bytes)} "
          f"(v5e HBM: 16 GiB)")

    key = jax.random.PRNGKey(0)
    kq, kk, kv, kg = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, l, h, d), jnp.bfloat16)
    k = jax.random.normal(kk, (b, l, h, d), jnp.bfloat16)
    v = jax.random.normal(kv, (b, l, h, d), jnp.bfloat16)
    scale = d ** -0.5

    # --- 1. dense attention must OOM -------------------------------------
    if not args.skip_dense:
        t0 = time.time()
        try:
            out = jax.jit(dense_attention, static_argnums=3)(q, k, v, scale)
            jax.device_get(out.astype(jnp.float32).sum())
            print(f"dense: UNEXPECTEDLY SUCCEEDED in {time.time()-t0:.0f}s "
                  "— shape not big enough to prove the memory claim")
            return 2
        except Exception as e:  # XlaRuntimeError: RESOURCE_EXHAUSTED
            name = type(e).__name__
            msg = str(e).splitlines()[0][:160]
            if "RESOURCE_EXHAUSTED" not in str(e) and "Out of memory" not in str(e):
                # A compile/driver/transfer failure is NOT the memory proof —
                # don't memorialize a false positive in evidence/.
                print(f"dense: failed for an UNEXPECTED reason after "
                      f"{time.time()-t0:.0f}s ({name}: {msg}) — rerun needed")
                return 3
            print(f"dense: OOMed as expected after {time.time()-t0:.0f}s "
                  f"({name}: {msg})")

    # --- 2. flash fwd + bwd at the same shape -----------------------------
    def loss(q, k, v):
        return flash_attention(q, k, v).astype(jnp.float32).sum()

    step = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    t0 = time.time()
    grads = step(q, k, v)
    sync = jax.device_get(grads[0].astype(jnp.float32)[0, 0, 0, :2])
    compile_s = time.time() - t0
    print(f"flash fwd+bwd: compiled+ran in {compile_s:.0f}s "
          f"(grad sample {sync.tolist()})")

    times = []
    for _ in range(args.steps):
        t0 = time.time()
        grads = step(q, k, v)
        jax.device_get(grads[0].astype(jnp.float32)[0, 0, 0, 0])
        times.append(time.time() - t0)
    best = min(times)
    toks = b * l / best
    print(f"flash fwd+bwd steady state: {best*1e3:.0f} ms "
          f"({toks:,.0f} tok/s, {args.steps} reps)")

    # --- 3. optional ring composition ------------------------------------
    if args.ring:
        from jax.sharding import Mesh
        import numpy as np
        from sav_tpu.parallel.ring_attention import ring_attention

        # backend='pallas' is the long-context configuration: each ring step
        # runs the flash kernel, so nothing O(L_loc^2) exists on any device.
        # (The 'xla' backend's dense per-block logits would re-OOM here on a
        # 1-device mesh — that dense path is the numerics reference for
        # short sequences, not the long-context one.)
        mesh = Mesh(np.array(jax.devices()[:1]), ("seq",))
        t0 = time.time()
        out = ring_attention(q, k, v, mesh=mesh, seq_axis="seq",
                             backend="pallas")
        jax.device_get(out.astype(jnp.float32)[0, 0, 0, 0])
        print(f"ring[pallas] (1-device degenerate) fwd: {time.time()-t0:.0f}s")

    print("MEMORY WIN PROVEN" if not args.skip_dense else "flash timing done")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
