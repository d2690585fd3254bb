"""Ouro — a looped (weight-shared) causal language model.

"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741;
sizes of ``Ouro-2.6B`` from its public ``config.json``. One stack of
sandwich-normed decoder layers is applied ``ut_steps`` times with the same
parameters (the parameter tree holds ``num_layers`` layers, never
``ut_steps`` times as many); after every pass a final RMSNorm, the untied
head and a per-token exit gate::

    h = E[tokens]
    for t in 1..T:
        for each layer:  h = h + RMSNorm(Attn(RMSNorm(h)));  h = h + RMSNorm(MLP(RMSNorm(h)))
        h = RMSNorm_f(h)            # h_t; the normed h enters pass t + 1
        z_t = W_head h_t            # logits of pass t
        g_t = w_g . h_t + b_g       # exit gate's logit; lambda_t = sigmoid(g_t)

``Attn``: Q, K, V, out without bias, rotary on the whole head with the
rotate-halves pairing at ``rope_theta``, causal softmax. ``MLP``: SwiGLU.
Training never exits early: every pass runs on every token, and the loss
(:func:`sav_tpu.train.tasks.looped_lm_loss`) weights the passes' losses by
the exit distribution the gates give.

Scopes, for the readers of a trace: the stack is ``ut_loop`` (one module,
called ``ut_steps`` times), the head ``lm_head``, the gate ``exit_gate``;
inside a layer the attention block is a ``SelfAttentionBlock`` with
``to_qkv`` / ``to_out`` and the MLP's matmuls are ``fc1`` / ``fc2``, as in
the vision zoo.
"""

from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from sav_tpu.models.layers import GatedFFBlock, RMSNorm, SelfAttentionBlock

Dtype = Any


class DecoderBlock(nn.Module):
    """Sandwich-normed decoder layer: a norm before and after each branch."""

    num_heads: int
    head_ch: int
    mlp_ch: int
    rope_theta: float
    norm_eps: float
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, inputs: jax.Array) -> jax.Array:
        def norm(name):
            return RMSNorm(eps=self.norm_eps, dtype=self.dtype, name=name)

        a = SelfAttentionBlock(
            num_heads=self.num_heads,
            head_ch=self.head_ch,
            use_rotary=True,
            rotary_half_base=self.rope_theta,
            causal=True,
            backend=self.backend,
            logits_dtype=self.logits_dtype,
            quant=self.quant,
            dtype=self.dtype,
        )(norm("attn_norm_in")(inputs), False)
        x = inputs + norm("attn_norm_out")(a)
        m = GatedFFBlock(hidden_ch=self.mlp_ch, quant=self.quant, dtype=self.dtype)(
            norm("mlp_norm_in")(x)
        )
        return x + norm("mlp_norm_out")(m)


# What the backward pass of a rematerialised layer application finds kept
# (``jax.ad_checkpoint.checkpoint_name`` tags in the layers and in the flash
# kernel's forward rule): the outputs of the projections and of the attention
# kernel, whose recomputation is a matmul or a kernel call each. The norms,
# SiLU and the gate's product, the residual adds and the casts are computed
# again from them. Chosen by measurement on a v5e at Ouro-2.6B's widths and
# 2 x 4,096 tokens, dearest first by milliseconds a byte, to fit 14.5 GB
# (PERF.md section 7): ``attn_out`` is tagged too and left out, 40 MB a layer
# application for the cheapest of the matmuls.
KEPT_UNDER_REMAT = ("attn_qkv", "flash_out", "flash_lse", "ffn_gate", "ffn_up", "ffn_out")


class LoopedStack(nn.Module):
    """``num_layers`` decoder layers and the final norm: one pass of the loop."""

    num_layers: int
    num_heads: int
    head_ch: int
    mlp_ch: int
    rope_theta: float
    norm_eps: float
    # Rematerialise each layer application in the backward pass, but for
    # KEPT_UNDER_REMAT; False keeps everything.
    remat: bool = False
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(self, x: jax.Array) -> jax.Array:
        block_cls = DecoderBlock
        if self.remat:
            kept = jax.checkpoint_policies.save_only_these_names(*KEPT_UNDER_REMAT)
            block_cls = nn.remat(DecoderBlock, policy=kept)
        for i in range(self.num_layers):
            x = block_cls(
                num_heads=self.num_heads,
                head_ch=self.head_ch,
                mlp_ch=self.mlp_ch,
                rope_theta=self.rope_theta,
                norm_eps=self.norm_eps,
                backend=self.backend,
                logits_dtype=self.logits_dtype,
                quant=self.quant,
                dtype=self.dtype,
                name=f"layer_{i}",
            )(x)
        return RMSNorm(eps=self.norm_eps, dtype=self.dtype, name="final_norm")(x)


class LMHead(nn.Module):
    """The output matrix ``[D, V]``: its own leaf (untied), or the transpose
    of the embedding's ``table [V, D]`` where a caller hands that over (tied:
    the module then has no leaf, and the table's gradient is the sum of the
    embedding's and the head's). With ``targets`` it returns each
    position's cross-entropy and never holds more than ``block_tokens`` rows
    of float32 logits: the rows go through in blocks, each under
    ``jax.checkpoint``, so the backward pass recomputes a block's logits
    where it needs them."""

    vocab_size: int
    block_tokens: int
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self, h: jax.Array, targets: Optional[jax.Array] = None, table: Optional[jax.Array] = None
    ) -> jax.Array:
        if table is None:
            kernel = self.param("kernel", nn.initializers.lecun_normal(), (h.shape[-1], self.vocab_size))
        else:
            kernel = table.T
        kernel = kernel.astype(self.dtype)
        if targets is None:
            return jnp.dot(h, kernel, preferred_element_type=jnp.float32)

        @jax.checkpoint
        def block_ce(h_blk, t_blk):
            logits = jnp.dot(h_blk, kernel, preferred_element_type=jnp.float32)
            classes = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
            picked = jnp.sum(jnp.where(classes == t_blk[:, None], logits, 0.0), axis=-1)
            return jax.nn.logsumexp(logits, axis=-1) - picked

        rows = h.shape[0] * h.shape[1]
        block = min(self.block_tokens, rows)
        if rows % block:
            raise ValueError(f"{rows} positions are not a multiple of the loss block {block}")
        # A Python loop, not lax.map: a while loop's own event in a device
        # trace spans its body's, and the time would be counted twice. The
        # barrier makes block i wait for block i - 1 (and, transposed, its
        # gradient for block i + 1's), so that one block's logits are alive
        # at a time as they would be in a loop.
        h_rows, t_rows = h.reshape(rows, h.shape[-1]), targets.reshape(rows)
        ces = []
        for i in range(0, rows, block):
            h_blk = h_rows[i:i + block]
            if ces:
                h_blk, ces[-1] = jax.lax.optimization_barrier((h_blk, ces[-1]))
            ces.append(block_ce(h_blk, t_rows[i:i + block]))
        ce = jnp.concatenate(ces)
        return ce.reshape(targets.shape)


class OuroLM(nn.Module):
    """tokens ``[B, S]`` int32 -> per pass, batch-leading:

    - without ``targets``: ``{"logits": [B, T, S, V], "exit_logit": [B, S, T]}``
      (float32; ``T`` = ``ut_steps``);
    - with ``targets`` ``[B, S]``: ``{"ce": [B, S, T], "exit_logit": [B, S, T]}``,
      the cross-entropy of every pass at every position without the full
      logits (see :class:`LMHead`).
    """

    num_classes: int  # the vocabulary
    embed_dim: int
    num_layers: int
    num_heads: int
    head_ch: int
    mlp_ch: int
    ut_steps: int
    rope_theta: float = 1e6
    norm_eps: float = 1e-6
    loss_block_tokens: int = 2048
    remat: bool = False
    backend: Optional[str] = None
    logits_dtype: Optional[Dtype] = None
    # int8 arm: the layers' projections and MLP; embedding, head and gate
    # stay in ``dtype``.
    quant: Optional[str] = None
    dtype: Dtype = jnp.float32

    @nn.compact
    def __call__(
        self, tokens: jax.Array, is_training: bool, targets: Optional[jax.Array] = None
    ) -> dict:
        del is_training  # no dropout, no stochastic depth
        h = nn.Embed(self.num_classes, self.embed_dim, dtype=self.dtype, name="embed")(tokens)
        stack = LoopedStack(
            num_layers=self.num_layers,
            num_heads=self.num_heads,
            head_ch=self.head_ch,
            mlp_ch=self.mlp_ch,
            rope_theta=self.rope_theta,
            norm_eps=self.norm_eps,
            remat=self.remat,
            backend=self.backend,
            logits_dtype=self.logits_dtype,
            quant=self.quant,
            dtype=self.dtype,
            name="ut_loop",
        )
        head = LMHead(self.num_classes, self.loss_block_tokens, dtype=self.dtype, name="lm_head")
        gate = nn.Dense(1, dtype=self.dtype, name="exit_gate")
        per_pass, exit_logits = [], []
        for _ in range(self.ut_steps):
            h = stack(h)
            per_pass.append(head(h, targets))
            exit_logits.append(gate(h)[..., 0].astype(jnp.float32))
        exit_logit = jnp.stack(exit_logits, axis=-1)
        if targets is None:
            return {"logits": jnp.stack(per_pass, axis=1), "exit_logit": exit_logit}
        return {"ce": jnp.stack(per_pass, axis=-1), "exit_logit": exit_logit}
