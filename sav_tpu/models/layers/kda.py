"""The Kimi delta attention block: the delta rule with a decay a key lane
(arXiv:2510.26692, section 3), as a hybrid decoder interleaves it with latent
attention.

For ``H`` heads of ``d_k`` keys and ``d_v`` values (one key head a value head,
no grouping)::

    q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v))   # causal, depthwise, width 4, no bias
    a = x W_f                                                           # [H d_k], full rank
    beta = sigmoid(x W_b)                                               # [H]
    o = gated_delta_rule_from_raw(q, k, v, a, beta)  # sav_tpu/ops/gated_delta.py, whose rule_operands computes
    #   q = l2norm(q) d_k^-0.5;  k = l2norm(k)                          # per head, float32; no rotary
    #   g = lower_bound sigmoid(exp(A_log_h) (a + dt_bias))             # the SAFE gate: g in (lower_bound, 0) a lane
    y = W_o (RMSNorm_{d_v}(o) w sigmoid(x W_g))                         # per head; w is plain

The six input projections are matrices of their own (``q``, ``k``, ``v``,
``f``, ``g``, ``b`` under ``to_qkv``): the gates are full rank and nothing is
fused by key head. ``lower_bound`` (the public config's ``kda_lower_bound``,
-5) is what bounds the chunked rule's exponents: the paper's own gate,
``-exp(A_log) softplus(a + dt_bias)``, is unbounded below and is not built
here (``ops/gated_delta.py`` says what it would take). The rule's operands
(the normalisation, the gate and its running sum inside a chunk, read from the
flat arrays where they lie and written chunk-major) and its state-free part
are one Mosaic call a direction each on a TPU at heads of whole lane tiles and
an even number of chunks (``ops/gated_delta.py::rule_form``, ``decay: vector``
and ``operands: kernel`` in the dispatch log), XLA's program elsewhere.

Scopes, for the readers of a trace: ``to_qkv`` and ``to_out`` hold the weight
matmuls; the work between them lies under ``kda/conv`` (the three convolutions
with their SiLU), ``kda/rule`` (the normalisation of q and k, the gates and
the rule) and ``kda/gate_norm``. The module's name holds no
``SelfAttentionBlock``: the attention readers pass it by.
"""

from __future__ import annotations

import functools
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from sav_tpu.models.layers.causal_conv import KERNEL_INIT, causal_conv_silu
from sav_tpu.models.layers.feedforward import _bias_free_dense
from sav_tpu.models.layers.gated_delta import _GatedNorm, _decay_rates
from sav_tpu.ops.gated_delta import CHUNK, gated_delta_rule_from_raw
from sav_tpu.ops.quant import QuantDenseGeneral

Dtype = Any


class _InputProj(nn.Module):
    """``x -> (q, k, v [.., H d_k | H d_v], f [.., H d_k], g [.., H d_v], b [.., H])``."""

    heads: int
    key_ch: int
    value_ch: int
    quant: Optional[str]
    dtype: Dtype

    @nn.compact
    def __call__(self, x: jax.Array):
        dense = _bias_free_dense(self.quant, self.dtype)
        keys, values = self.heads * self.key_ch, self.heads * self.value_ch
        widths = {"q": keys, "k": keys, "v": values, "f": keys, "g": values, "b": self.heads}
        return tuple(dense(width, name=name)(x) for name, width in widths.items())


class _CausalConvs(nn.Module):
    """:func:`causal_conv_silu` over q, k and v, an array and a ``[W, C]``
    kernel each (no bias)."""

    width: int

    @nn.compact
    def __call__(self, *arrays):
        names = ("q_kernel", "k_kernel", "v_kernel")
        return tuple(
            causal_conv_silu(x, self.param(name, KERNEL_INIT, (self.width, x.shape[-1])))
            for name, x in zip(names, arrays)
        )


def _gate_offsets(lower_bound: float):
    """``dt_bias`` at the start: the paper's ``dt`` (log-uniform in [1e-3,
    1e-1]) through the inverse of the gate in force, ``|lower_bound|
    sigmoid(.)`` in softplus's place: at ``A_log`` 0 and ``a`` 0 a lane decays
    by ``exp(-dt)`` a token."""
    def init(key, shape):
        dt = jnp.exp(jax.random.uniform(key, shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
        share = dt / abs(lower_bound)
        return jnp.log(share) - jnp.log1p(-share)

    return init


class KDABlock(nn.Module):
    """See the module docstring. Returns ``(y, stats)``; ``stats`` holds the
    smallest ``g`` of the call (``decay_min``: how near ``lower_bound`` the
    gate runs) and the largest RMS of any head's final state
    (``state_rms_max``), float32 scalars without a gradient."""

    heads: int
    key_ch: int
    value_ch: int
    conv_width: int = 4
    lower_bound: float = -5.0
    norm_eps: float = 1e-6
    chunk: int = CHUNK
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array):
        batch, seq, _ = inputs.shape
        q, k, v, a, z, b = _InputProj(
            self.heads, self.key_ch, self.value_ch, self.quant, self.dtype, name="to_qkv"
        )(inputs)
        a, z = (checkpoint_name(t, "kda_gates") for t in (a, z))
        a_log = self.param("A_log", _decay_rates, (self.heads,))
        dt_bias = self.param("dt_bias", _gate_offsets(self.lower_bound), (self.heads * self.key_ch,))

        def by_head(t, width):
            return t.reshape(batch, seq, self.heads, width)

        with jax.named_scope("kda/conv"):
            q, k, v = (checkpoint_name(t, "kda_conv") for t in _CausalConvs(self.conv_width, name="conv")(q, k, v))
        with jax.named_scope("kda/rule"):
            out, state, least = gated_delta_rule_from_raw(
                by_head(q, self.key_ch), by_head(k, self.key_ch), by_head(v, self.value_ch), by_head(a, self.key_ch),
                jax.nn.sigmoid(b.astype(jnp.float32)), self.chunk,
                a_log=a_log, dt_bias=dt_bias, lower_bound=self.lower_bound,
            )
            out = checkpoint_name(out, "kda_out")
            stats = jax.lax.stop_gradient({
                "decay_min": least,
                "state_rms_max": jnp.sqrt(jnp.max(jnp.mean(jnp.square(state), axis=(-2, -1)))),
            })
        with jax.named_scope("kda/gate_norm"):
            gate_norm = _GatedNorm(self.norm_eps, self.dtype, jax.nn.sigmoid, name="gate_norm")
            out = gate_norm(out, by_head(z, self.value_ch))
        dense = functools.partial(QuantDenseGeneral, mode=self.quant) if self.quant else nn.DenseGeneral
        out = dense(
            features=inputs.shape[-1], axis=(-2, -1), use_bias=False, dtype=self.dtype, name="to_out"
        )(out)
        return checkpoint_name(out, "attn_out"), stats
