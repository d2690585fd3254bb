"""Ask the TPU's compiler, without a TPU: the kernels and the main-path
programs compile for a described ``v5e:2x2`` chip with ``interpret=False``.

Interpret-mode tests cannot see what the chip's compiler refuses — a slice
not aligned to the tiling, more fast memory than a kernel may use, a
program that does not fit 16 GB. These compiles can, at no chip time. A
compile that passes is not a chip run and says nothing about results or
speed (``chip_smoke.py`` is the chip run).

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU's library, every xdist worker
imports every test file, and a module that touched the topology while it
was imported would make the workers disagree about what to collect. The
compiles run in this test's own process, all in this one file, with the
persistent compile cache off (a described-device entry cannot be read
back without a chip and would only produce warnings).
"""

import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P, SingleDeviceSharding

HBM_BYTES = 16 * 2**30  # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — any failure to describe is a skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    # The suite compiles its CPU programs with most optimizations off
    # (conftest.py); the TPU's compiler is asked at its own settings.
    unoptimized = jax.config.read("jax_disable_most_optimizations")
    jax.config.update("jax_disable_most_optimizations", False)
    yield desc
    jax.config.update("jax_disable_most_optimizations", unoptimized)
    jax.config.update("jax_enable_compilation_cache", enabled)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture()
def compiled_kernels(monkeypatch):
    """Whole programs pick interpret mode from the backend's name, and the
    backend here is the CPU: steer them to the compiled kernel from the
    test (the program has no option for it, on purpose)."""
    from sav_tpu.ops import _backend

    monkeypatch.setattr(_backend, "default_interpret", lambda: False)


def _bytes_on_device(compiled) -> int:
    m = compiled.memory_analysis()
    return (
        m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes
    )


# What a scope of a compiled step moves, read from the step's text: the
# entry computation's instructions whose ``op_name`` lies under it (a fusion
# carries its root's).
_ITEMSIZE = {"f64": 8, "s64": 8, "u64": 8, "f32": 4, "s32": 4, "u32": 4,
             "bf16": 2, "f16": 2, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1}
_ARRAY = re.compile(r"\b(%s)\[([0-9,]*)\]" % "|".join(_ITEMSIZE))
_MOVES_NOTHING = ("get-tuple-element", "bitcast", "tuple", "parameter", "constant")


def _shape_bytes(shape: str) -> int:
    return sum(
        _ITEMSIZE[dtype] * int(np.prod([int(d) for d in dims.split(",") if d]))
        for dtype, dims in _ARRAY.findall(shape)
    )


def _closing(text: str, start: int) -> int:
    depth = 0
    for i in range(start, len(text)):
        depth += (text[i] == "(") - (text[i] == ")")
        if depth == 0:
            return i
    raise ValueError(text[:200])


def _entry_scope(hlo_text: str, in_scope) -> list:
    """``(opcode, output bytes, output and operand bytes)`` of every
    entry-computation instruction whose ``op_name`` satisfies ``in_scope``
    and that moves something."""
    lines = hlo_text.splitlines()
    entry = next(i for i, line in enumerate(lines) if line.startswith("ENTRY "))
    sizes, found = {}, []
    for line in lines[entry + 1:]:
        if line.startswith("}"):
            break
        head = re.match(r"\s*(?:ROOT )?%(\S+) = ", line)
        if not head:
            continue
        rest = line[head.end():]
        if rest.startswith("("):  # a tuple's shape
            end = _closing(rest, 0)
            shape, rest = rest[: end + 1], rest[end + 2:]
        else:
            shape, _, rest = rest.partition(" ")
        paren = rest.index("(")
        end = _closing(rest, paren)
        opcode, operands = rest[:paren], re.findall(r"%([\w.\-]+)", rest[paren: end + 1])
        sizes[head.group(1)] = _shape_bytes(shape)
        scope = re.search(r'op_name="([^"]*)"', rest[end + 1:])
        if scope and in_scope(scope.group(1)) and opcode not in _MOVES_NOTHING:
            out = sizes[head.group(1)]
            found.append((opcode, out, out + sum(sizes.get(o, 0) for o in operands)))
    return found


def _optimizer_scope(hlo_text: str) -> list:
    """``(opcode, bytes)`` of every entry-computation instruction under the
    ``optimizer`` scope that moves something, each charged its operands and
    its output whole."""
    return [(opcode, moved) for opcode, _, moved in _entry_scope(hlo_text, lambda scope: "/optimizer/" in scope)]


def _assert_one_pass_optimizer(compiled, params) -> None:
    """The AdamW update is one pass over each parameter: no data movement
    under the scope (the flat layout's ravel, concatenate and split are
    ``reshape``, ``concatenate`` and ``copy`` there), and at most 8 times
    the parameters' bytes moved: gradient, two moments and the parameter
    read, the moments and the parameter written, and the norm's read."""
    scope = _optimizer_scope(compiled.as_text())
    opcodes = {opcode for opcode, _ in scope}
    assert "fusion" in opcodes  # the scope was found
    assert not opcodes & {"concatenate", "reshape", "copy", "slice"}, sorted(opcodes)
    param_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    passes = sum(moved for _, moved in scope) / param_bytes
    assert passes <= 8, passes


# ------------------------------------------------------------- the kernels

# (name, kernel, [B, L, H, D]) at the shapes the zoo uses, plus one
# long-sequence shape the flash kernel exists for.
KERNEL_CASES = [
    ("flash-deit_s", "flash", (256, 197, 6, 64)),
    ("fused-deit_s", "fused", (256, 197, 6, 64)),
    ("flash-long", "flash", (8, 4096, 6, 64)),
    ("talking_heads-cait_xxs24", "talking_heads", (256, 196, 4, 48)),
    ("flash-tnt_outer", "flash", (64, 785, 6, 64)),
    ("fused-tnt_outer", "fused", (64, 785, 6, 64)),
    ("flash-tnt_inner", "flash", (12544, 17, 4, 6)),
    ("fused-tnt_inner", "fused", (12544, 17, 4, 6)),
    ("relative_position-botnet_14x14", "botnet", (64, 196, 4, 128)),
    # The looped language model's core: causal, head size 128, at the
    # default blocks and at the tune cache's measured ones.
    ("flash_causal-ouro_4k", "flash_causal", (2, 4096, 16, 128)),
    ("flash_causal_tuned-ouro_4k", "flash_causal_tuned", (2, 4096, 16, 128)),
    # Latent attention: a query/key head of 192 beside a value head of 128.
    ("flash_latent_tuned-joyai_4k", "flash_latent_tuned", (2, 4096, 32, 192, 128)),
    # Grouped key/value heads at head size 256: 16 query heads on 2 (the
    # hybrid decoder's full-attention layer), in place, the group's head
    # found through the block index. The fifth number is the key/value heads.
    ("flash_grouped-qwen3_next_4k", "flash_grouped", (4, 4096, 16, 256, 2)),
    # Heads narrower than a lane tile on a quarter as many key/value heads, at
    # the longest sequence a cell has: 32 query heads of 64 on 8 (the
    # convolution-attention hybrid's softmax layer). Head-major: the lanes
    # padded to 128, k and v repeated to the query heads.
    ("flash_grouped-lfm2_8k", "flash_grouped", (4, 8192, 32, 64, 8)),
    # The gated delta rule at the hybrid decoder's cell: q and k [B, L, H_k,
    # d_k], the fifth number the value heads; chunks of 64, the state-free
    # part in its two kernels (one a direction), eight chunks a grid step.
    ("gated_delta-qwen3_next_4k", "gated_delta", (4, 4096, 16, 128, 32)),
    # The same rule with a decay a key lane at the vector-decay hybrid's cell:
    # one key head a value head, two chunks of a head side by side a trip,
    # sixteen chunks a grid step.
    ("gated_delta_by_lane-ling_4k", "gated_delta_by_lane", (2, 4096, 32, 128)),
    # The causal depthwise convolution's kernels at the two cells that run
    # them, [B, S, C] and the taps: fused with a SiLU over q, k and v joined
    # (the hybrid decoder), between two gates on the thirds of the input
    # projection's [B, S, 3 C] (the convolution-attention hybrid).
    ("causal_conv_silu-qwen3_next_4k", "conv_silu", (4, 4096, 8192, 4)),
    # ... and as that cell runs it: on the projection [B, S, 16 (q 128 | k 128 | v 256 | z 256)] where it lies.
    ("causal_conv_silu_by_key_head-qwen3_next_4k", "conv_key_head", (4, 4096, 8192, 4)),
    ("gated_causal_conv-lfm2_8k", "conv_gated", (4, 8192, 2048, 3)),
]


def _rule_from_raw(prepare, form):
    """The delta rule as a TPU runs it from a block's own arrays, the
    kernels compiled (``interpret=False``: on this backend the rule's own
    entry would pick XLA's programs, or the interpreter): the operands' two
    calls at ``form``'s tile (a scalar decay's ``g`` summed by XLA), the
    state-free part's two, the scan."""
    from sav_tpu.ops import gated_delta

    prepare = functools.partial(prepare, tile=form["chunk_tile"], interpret=False)

    def rule(q, k, v, gate, beta):
        by_lane = isinstance(gate, tuple)
        for_prepare, for_scan, _ = gated_delta._operands_in_vmem(
            q, k, gate if by_lane else None, gated_delta.CHUNK, -5.0, form["operands_tile"], False)
        if not by_lane:
            gamma = gated_delta._summed_by_chunk(gate, gated_delta.CHUNK)
            for_prepare, for_scan = for_prepare[:2] + (gamma,), for_scan[:2] + (gamma,)
        return gated_delta._chunked(prepare, for_prepare, for_scan, v, beta)[0]

    return rule


def _kernel_fn_and_args(kernel, shape, sharding):
    from sav_tpu.ops.flash_attention import (
        flash_attention,
        flash_botnet_attention,
    )
    from sav_tpu.ops.fused_attention import fused_attention
    from sav_tpu.ops.talking_heads import flash_talking_heads_attention

    def spec(s, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct(s, dtype, sharding=sharding)

    qkv = (spec(shape[:4]),) * 3
    heads, dim = shape[2], shape[3]
    if kernel == "flash":
        return (lambda q, k, v: flash_attention(q, k, v, interpret=False)), qkv
    if kernel == "flash_causal":
        return (lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False)), qkv
    if kernel == "flash_causal_tuned":
        from sav_tpu.ops import attn_tuning

        blocks = attn_tuning.block_config(
            attn_tuning.lookup(*shape[:2], shape[1], *shape[2:], causal=True)
        )
        return (
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False, **blocks)
        ), qkv
    if kernel == "flash_latent_tuned":
        from sav_tpu.ops import attn_tuning

        blocks = attn_tuning.block_config(attn_tuning.lookup(
            *shape[:2], shape[1], *shape[2:4], causal=True, value_dim=shape[4]
        ))
        value = spec(shape[:3] + (shape[4],))
        return (
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False, **blocks)
        ), qkv[:2] + (value,)
    if kernel == "flash_grouped":
        from sav_tpu.ops.flash_attention import layout_form

        from sav_tpu.ops import attn_tuning

        blocks = attn_tuning.block_config(attn_tuning.lookup(*shape[:2], shape[1], heads, dim, causal=True)) or {}
        # What the dispatch log will name: in place where the head is whole lane tiles.
        layout = "in_place" if dim % 128 == 0 else "head_major"
        assert layout_form(shape[1], shape[1], dim, dim, batch_heads=shape[0] * heads, **blocks) == layout
        few = spec(shape[:2] + (shape[4], dim))
        return (
            lambda q, k, v: flash_attention(q, k, v, causal=True, interpret=False, **blocks)
        ), (qkv[0], few, few)
    if kernel == "gated_delta":
        from sav_tpu.ops import gated_delta

        form = gated_delta.rule_form(shape[1] // gated_delta.CHUNK, gated_delta.CHUNK, dim, shape[4] // heads, on_tpu=True)
        assert form == {"rule": "kernel", "chunk_tile": 8, "operands": "kernel", "operands_tile": 16}
        gates = spec(shape[:2] + (shape[4],), jnp.float32)
        return _rule_from_raw(gated_delta._prepare_in_vmem, form), (
            qkv[0], qkv[1], spec(shape[:2] + (shape[4], dim)), gates, gates)
    if kernel == "gated_delta_by_lane":
        from sav_tpu.ops import gated_delta

        form = gated_delta.rule_form(shape[1] // gated_delta.CHUNK, gated_delta.CHUNK, dim, 1, by_lane=True, on_tpu=True)
        assert form == {"rule": "kernel", "decay": "vector", "chunk_tile": 16, "operands": "kernel", "operands_tile": 16}
        rule = _rule_from_raw(gated_delta._prepare_by_lane_in_vmem, form)
        return (
            lambda q, k, v, a, beta, a_log, dt_bias: rule(q, k, v, (a, a_log, dt_bias), beta)
        ), qkv + (qkv[0], spec(shape[:3], jnp.float32), spec((heads,), jnp.float32), spec((heads * dim,), jnp.float32))
    if kernel in ("conv_silu", "conv_gated", "conv_key_head"):
        from sav_tpu.models.layers import causal_conv as forms
        from sav_tpu.ops.causal_conv import conv_form

        batch, seq, channels, width = shape
        if kernel == "conv_key_head":
            key_heads, key_ch, value_ch = 16, 128, 256
            form = conv_form(seq, channels, width, jnp.bfloat16, key_head=(key_ch, value_ch), on_tpu=True)
            assert form == {"conv": "kernel", "block_s": 1024, "block_c": 512, "reads": "in_place"}
            return (
                lambda qkvz, taps: jnp.concatenate(forms._conv_silu_of_key_heads(
                    qkvz, taps, key_heads, key_ch, value_ch, form["block_s"], False), axis=-1)
            ), (spec((batch, seq, channels + key_heads * value_ch)), spec((width, channels), jnp.float32))
        form = conv_form(seq, channels, width, jnp.bfloat16, on_tpu=True)
        assert form == {"conv": "kernel", "block_s": 1024, "block_c": 512}
        in_vmem, operand = (
            (forms._conv_silu_in_vmem, channels) if kernel == "conv_silu" else (forms._gated_conv_in_vmem, 3 * channels)
        )
        return (
            lambda x, taps: in_vmem(x, taps, form["block_s"], form["block_c"], False)
        ), (spec((batch, seq, operand)), spec((width, channels), jnp.float32))
    if kernel == "fused":
        return (lambda q, k, v: fused_attention(q, k, v, interpret=False)), qkv
    if kernel == "talking_heads":
        mix = spec((heads, heads), jnp.float32)
        return (
            lambda q, k, v, w_pre, w_post: flash_talking_heads_attention(
                q, k, v, w_pre, w_post, interpret=False
            )
        ), qkv + (mix, mix)
    if kernel == "botnet":
        side = int(round(shape[1] ** 0.5))
        rel = spec((2 * side - 1, dim), jnp.float32)
        return (
            lambda q, k, v, rel_h, rel_w: flash_botnet_attention(
                q, k, v, rel_h, rel_w, side, side, interpret=False
            )
        ), qkv + (rel, rel)
    raise ValueError(kernel)


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize(
    "kernel,shape",
    [(kernel, shape) for _, kernel, shape in KERNEL_CASES],
    ids=[name for name, _, _ in KERNEL_CASES],
)
def test_kernel_compiles_for_v5e(one_chip, kernel, shape, direction):
    fn, args = _kernel_fn_and_args(kernel, shape, one_chip)
    if direction == "backward":
        forward = fn

        def fn(*a):
            return jax.grad(
                lambda *b: forward(*b).astype(jnp.float32).sum(),
                argnums=tuple(range(len(a))),
            )(*a)

    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _bytes_on_device(compiled) < HBM_BYTES


@pytest.mark.parametrize("kernel,shape", [case[1:] for case in KERNEL_CASES if case[1].startswith("gated_delta")],
                         ids=[case[0] for case in KERNEL_CASES if case[1].startswith("gated_delta")])
def test_the_delta_rule_is_four_calls_and_the_scan_from_a_blocks_arrays(one_chip, kernel, shape):
    """Forward and backward in one program: the operands' call and the
    state-free part's, a direction each, and with a decay a key lane no
    running sum of XLA's (``reduce-window``) on the 134 MB of ``g``."""
    fn, args = _kernel_fn_and_args(kernel, shape, one_chip)
    both = lambda *a: jax.value_and_grad(lambda *b: fn(*b).astype(jnp.float32).sum(), argnums=tuple(range(len(a))))(*a)
    text = jax.jit(both).lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 4
    for name in ("_operands_forward", "_operands_backward"):
        assert sum(name in line for line in text.splitlines() if "tpu_custom_call" in line) == 1, name
    assert ("reduce-window" in text) == (kernel == "gated_delta")


def _kernel_vmem(compiled, which: str, field: str = "size") -> list:
    """Bytes of scoped VMEM each Mosaic call of a compiled program was
    given (``scoped_memory_configs``: none where the call sets no limit of
    its own) or used (``used_scoped_memory_configs``); ``field`` ``offset``:
    where the call's share starts (XLA's own scoped buffers lie under it,
    and ``used`` counts from 0)."""
    pattern = re.compile(r'"%s":\[\{"memory_space":"1","offset":"(?P<offset>\d+)","size":"(?P<size>\d+)"' % which)
    calls = [
        line for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
    ]
    return [int(found.group(field)) if (found := pattern.search(line)) else None for line in calls]


@pytest.mark.parametrize("kernel,shape", [case[1:] for case in KERNEL_CASES if case[1].startswith("conv_")],
                         ids=[case[0] for case in KERNEL_CASES if case[1].startswith("conv_")])
def test_causal_conv_kernels_stay_within_their_vmem_limit(one_chip, kernel, shape):
    """Forward and backward in one program: each Mosaic call is given the
    module's limit and uses less than half of it (the gated backward, the
    largest, holds four blocks of 1,024 x 512 twice and stages three twice)."""
    from sav_tpu.ops import causal_conv

    fn, args = _kernel_fn_and_args(kernel, shape, one_chip)
    both = lambda *a: jax.value_and_grad(lambda *b: fn(*b).astype(jnp.float32).sum(), argnums=(0, 1))(*a)
    compiled = jax.jit(both).lower(*args).compile()
    given, used = (_kernel_vmem(compiled, which) for which in ("scoped_memory_configs", "used_scoped_memory_configs"))
    assert given == [causal_conv._VMEM_LIMIT] * 2
    assert all(0 < one <= causal_conv._VMEM_LIMIT // 2 for one in used), used


# (name, [B, L, H, D(, Dv)], flash_attention's blocks, the forms the rules pick)
BACKWARD_FORM_CASES = [
    ("ouro_4k", (2, 4096, 16, 128), dict(block_q=1024, block_kv=1024, block_b=1), "one_kernel", "in_place"),
    # A tile the sweep also reads: its forward's working set is over Mosaic's
    # default 16 MiB in either layout's transposes, so both in-place calls
    # carry the limit.
    ("ouro_4k_2048x1024", (2, 4096, 16, 128), dict(block_q=2048, block_kv=1024, block_b=1), "one_kernel", "in_place"),
    ("joyai_4k", (2, 4096, 32, 192, 128), dict(block_q=1024, block_kv=1024, block_b=1), "one_kernel", "head_major"),
    ("deit_s", (256, 197, 6, 64), {}, "one_kernel", "head_major"),
    # A ring shard's length at the default blocks: block_b 4 whole float32
    # dq of 16,384 rows do not fit beside their tiles.
    ("ring_shard_16k", (2, 16384, 6, 64), {}, "two_kernels", "head_major"),
]


@pytest.mark.parametrize(
    "shape,blocks,form,layout", [case[1:] for case in BACKWARD_FORM_CASES],
    ids=[case[0] for case in BACKWARD_FORM_CASES],
)
def test_flash_backward_compiles_in_the_form_its_rule_picks(one_chip, shape, blocks, form, layout):
    """The blocked backward alone, through the head-major internals that
    the ring path also calls by name: one Mosaic call under its own VMEM
    limit where the rule says the resident dq fits, within the rule's
    estimate of what it uses and with the gradients in the operands'
    buffers; else the two calls under the default limit. Where the layout
    rule runs the shape in place, that form's one call as well, on the
    caller's own arrays, under the same limit and within the same estimate."""
    import importlib

    flmod = importlib.import_module("sav_tpu.ops.flash_attention")
    batch, length, heads, dim = shape[:4]
    dim_v = shape[4] if len(shape) == 5 else dim
    sizes = (length, length, dim, dim_v)
    assert form == flmod.backward_form(*sizes, batch_heads=batch * heads, **blocks)
    assert layout == flmod.layout_form(*sizes, batch_heads=batch * heads, **blocks)

    def spec(d, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((batch, length, heads, d), dtype, sharding=one_chip)

    block_q = min(blocks.get("block_q", 256), -(-length // 16) * 16)
    length_p = -(-length // block_q) * block_q
    lse = jax.ShapeDtypeStruct((batch * heads, length_p, 128), jnp.float32, sharding=one_chip)
    call = (dim ** -0.5, blocks.get("block_q", 256), blocks.get("block_kv", 256), False)

    def backward(q, k, v, out, g, lse):
        return flmod._flash_backward_pallas(q, k, v, out, lse, g, *call, causal=True, block_b=blocks.get("block_b"))

    compiled = jax.jit(backward).lower(
        spec(dim), spec(dim), spec(dim_v), spec(dim_v), spec(dim_v), lse
    ).compile()
    limits, used = _kernel_vmem(compiled, "scoped_memory_configs"), _kernel_vmem(compiled, "used_scoped_memory_configs")
    if form == "two_kernels":
        assert limits == [None, None] and max(used) <= 16 * 2**20
        return
    assert limits == [flmod._ONE_KERNEL_VMEM_LIMIT]
    # dq, dk, dv are written over the padded q, k, v: no HBM of their own.
    aliased = "output_to_operand_aliasing={{0}: (0, {}), {1}: (1, {}), {2}: (2, {})}"
    assert aliased in compiled.as_text()
    estimate = flmod.one_kernel_backward_vmem_bytes(
        length_p, flmod._pad_head(dim), flmod._pad_head(dim_v), block_q=block_q,
        block_kv=min(blocks.get("block_kv", 256), length_p),
        block_b=flmod._resolve_block_b(blocks.get("block_b"), batch * heads),
    )
    assert 0.45 * estimate <= used[0] <= estimate <= flmod.ONE_KERNEL_VMEM_BUDGET
    if layout == "head_major":
        return

    def in_place(q, k, v, out, g, lse):
        return flmod._in_place_backward(q, k, v, out, lse, g, *call, causal=True)

    def in_place_forward(q, k, v):
        return flmod._in_place_forward(q, k, v, *call, True, causal=True)

    def own_vmem(compiled):
        """What Mosaic used of the one call's share: where XLA keeps scoped
        buffers of its own they lie under the call's."""
        assert _kernel_vmem(compiled, "scoped_memory_configs") == [flmod._ONE_KERNEL_VMEM_LIMIT]
        return _kernel_vmem(compiled, "used_scoped_memory_configs")[0] - _kernel_vmem(compiled, "scoped_memory_configs", "offset")[0]

    flat_out = jax.ShapeDtypeStruct((batch, length, heads * dim_v), jnp.bfloat16, sharding=one_chip)
    rows = jax.ShapeDtypeStruct((batch, heads, 1, length), jnp.float32, sharding=one_chip)
    compiled = jax.jit(in_place).lower(spec(dim), spec(dim), spec(dim_v), flat_out, spec(dim_v), rows).compile()
    assert f"f32[{batch * heads},{length_p},128]" not in compiled.as_text()
    in_place_used = own_vmem(compiled)
    assert 0.45 * estimate <= in_place_used <= estimate
    # The forward holds the same tiles and no resident dq: the backward's
    # estimate bounds it, which is why the layout rule reads that one alone.
    forward_used = own_vmem(jax.jit(in_place_forward).lower(spec(dim), spec(dim), spec(dim_v)).compile())
    assert forward_used <= in_place_used
    print(
        f"Mosaic VMEM used at {shape} {blocks}: head-major backward {used[0] / 2**20:.1f} MiB, "
        f"in place backward {in_place_used / 2**20:.1f} MiB, forward {forward_used / 2**20:.1f} MiB"
    )


def _pallas_grids(jaxpr) -> list:
    """The grid of every ``pallas_call`` of a traced program, in order."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(tuple(eqn.params["grid_mapping"].grid))
        for value in eqn.params.values():
            inner = getattr(value, "jaxpr", value)
            if hasattr(inner, "eqns"):
                grids.extend(_pallas_grids(inner))
    return grids


# (name, [B, L, H, D], key/value heads, window, layout): the window/full hybrid
# cell's two cores, and a causal cell's core (Ouro's) for what a window must not change.
BAND_CASES = [
    ("laguna_window", (1, 4096, 72, 128), 8, 512, "in_place"),
    ("laguna_full", (1, 4096, 48, 128), 8, None, "in_place"),
    ("ouro_causal", (2, 4096, 16, 128), 16, None, "in_place"),
]


@pytest.mark.parametrize(
    "shape,kv_heads,window,layout", [case[1:] for case in BAND_CASES], ids=[case[0] for case in BAND_CASES]
)
def test_banded_and_causal_cores_compile_at_the_cells_shapes(one_chip, shape, kv_heads, window, layout):
    """Forward and backward of ``flash_attention`` at the blocks the tune
    cache gives the shape (a banded core reads its own entries), compiled for
    a described v5e: two Mosaic calls, the forward and the one-kernel
    backward: of a causal core on the full causal grid, of the banded core
    (the resident pair at this shape) on a grid that is the band, a q block
    and key/value head a cell. Without a window the program is the one a
    window no shorter than the sequence builds: the same calls on the same
    grid, told nothing of a window."""
    import importlib

    from sav_tpu.ops import attn_tuning

    flmod = importlib.import_module("sav_tpu.ops.flash_attention")
    batch, length, heads, dim = shape
    entry = attn_tuning.lookup(batch, length, length, heads, dim, causal=True, window=window)
    blocks = attn_tuning.block_config(entry)
    assert blocks and entry["backend"] == "pallas", "the cell's core shapes have measured entries"
    sizes = dict(batch_heads=batch * heads, **blocks)
    band = flmod.band_form(
        length, length, dim, dim, heads=heads, kv_heads=kv_heads, window=window,
        **{k: v for k, v in blocks.items() if k != "block_b"},
    )
    assert band == (None if window is None else "resident")
    if band is None:
        assert flmod.layout_form(length, length, dim, dim, **sizes) == layout
        assert flmod.backward_form(length, length, dim, dim, **sizes) == "one_kernel"

    def spec(h):
        return jax.ShapeDtypeStruct((batch, length, h, dim), jnp.bfloat16, sharding=one_chip)

    def both(window):
        def loss(q, k, v):
            out = flmod.flash_attention(q, k, v, causal=True, window=window, interpret=False, **blocks)
            return jnp.sum(out.astype(jnp.float32))
        return jax.value_and_grad(loss, argnums=(0, 1, 2))

    args = (spec(heads), spec(kv_heads), spec(kv_heads))
    q_blocks, kv_blocks = length // blocks["block_q"], length // blocks["block_kv"]
    if band == "resident":  # a q block and key/value head a cell, the ring's flush cells after the last
        resident = flmod.band_resident_blocks(length, blocks["block_q"], window)
        grids = [(batch, kv_heads, q_blocks), (batch, kv_heads, q_blocks + resident - 1)]
    elif layout == "in_place":  # a head a cell
        grids = [(batch, heads, q_blocks, kv_blocks), (batch, heads, kv_blocks, q_blocks)]
    else:  # block_b slices a cell
        slices = batch * heads // blocks["block_b"]
        grids = [(slices, q_blocks, kv_blocks), (slices, kv_blocks, q_blocks)]
    assert _pallas_grids(jax.make_jaxpr(both(window))(*args).jaxpr) == grids
    compiled = jax.jit(both(window)).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2
    assert _bytes_on_device(compiled) < HBM_BYTES
    counts = flmod.visited_blocks(length, length, window=window, **{k: v for k, v in blocks.items() if k != "block_b"})
    if window is not None:
        assert counts["kv_blocks_visited"] < counts["kv_blocks_causal"] <= q_blocks * kv_blocks
        assert _kernel_vmem(compiled, "scoped_memory_configs") == [flmod._ONE_KERNEL_VMEM_LIMIT] * 2
        used = [
            total - offset for total, offset in zip(
                _kernel_vmem(compiled, "used_scoped_memory_configs"), _kernel_vmem(compiled, "scoped_memory_configs", "offset"))
        ]
        estimate = flmod.band_vmem_bytes(heads // kv_heads, dim, dim, block=blocks["block_q"], resident=resident)
        print(f"Mosaic VMEM used by the resident pair at {shape} {blocks}: forward {used[0] / 2**20:.1f} MiB of an "
              f"estimated {estimate['forward'] / 2**20:.1f}, backward {used[1] / 2**20:.1f} of {estimate['backward'] / 2**20:.1f}")
        # The rule's estimates bound what Mosaic takes, and the budget what the limit leaves.
        assert used[0] <= estimate["forward"] <= flmod.ONE_KERNEL_VMEM_BUDGET
        assert used[1] <= estimate["backward"] <= flmod.ONE_KERNEL_VMEM_BUDGET
        return
    assert counts["kv_blocks_visited"] == counts["kv_blocks_causal"]
    # The causal program of before: a window that hides nothing builds it, letter for letter.
    text = str(jax.make_jaxpr(both(None))(*args))
    assert "window" not in text and "cases" not in text
    assert str(jax.make_jaxpr(both(length))(*args)) == text


@pytest.mark.parametrize("block", [128, 256, 512])
def test_the_resident_pair_compiles_at_each_block(one_chip, block):
    """The resident pair at the cell's window shape at each block its sweep
    times (five, three and two kv blocks a cell): one call a direction."""
    import importlib

    flmod = importlib.import_module("sav_tpu.ops.flash_attention")
    blocks = dict(block_q=block, block_kv=block)
    assert flmod.band_form(4096, 4096, 128, 128, heads=72, kv_heads=8, window=512, **blocks) == "resident"

    def spec(h):
        return jax.ShapeDtypeStruct((1, 4096, h, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flmod.flash_attention(q, k, v, causal=True, window=512, interpret=False, **blocks)
        return jnp.sum(out.astype(jnp.float32))

    both = jax.value_and_grad(loss, argnums=(0, 1, 2))
    args = (spec(72), spec(8), spec(8))
    resident = flmod.band_resident_blocks(4096, block, 512)
    assert _pallas_grids(jax.make_jaxpr(both)(*args).jaxpr) == [(1, 8, 4096 // block), (1, 8, 4096 // block + resident - 1)]
    compiled = jax.jit(both).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def test_the_banded_core_compiles_in_place_too(one_chip, monkeypatch):
    """The causal kernels' banded arm in place (what a shape the resident
    pair does not take runs where its heads are whole lane tiles; here the
    cell's window shape with the resident pair's rule pinned shut) at
    512-row blocks: a head a cell through the index maps, 15 of 36 cells
    with work."""
    import importlib

    flmod = importlib.import_module("sav_tpu.ops.flash_attention")
    monkeypatch.setattr(flmod, "BAND_MAX_UNROLLED_TILES", -1)
    blocks = dict(block_q=512, block_kv=512, block_b=1)
    assert flmod.layout_form(4096, 4096, 128, 128, batch_heads=72, **blocks) == "in_place"

    def spec(h):
        return jax.ShapeDtypeStruct((1, 4096, h, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flmod.flash_attention(q, k, v, causal=True, window=512, interpret=False, **blocks)
        return jnp.sum(out.astype(jnp.float32))

    both = jax.value_and_grad(loss, argnums=(0, 1, 2))
    args = (spec(72), spec(8), spec(8))
    assert _pallas_grids(jax.make_jaxpr(both)(*args).jaxpr) == [(1, 72, 8, 8), (1, 72, 8, 8)]
    compiled = jax.jit(both).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2
    assert flmod.visited_blocks(4096, 4096, window=512, block_q=512, block_kv=512) == {
        "kv_blocks_visited": 15, "kv_blocks_causal": 36}


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_ring_attention_compiles_for_four_chips(topo, backend, direction):
    """The sequence-parallel ring over the 2x2 host at a length the dense
    path cannot hold (L 16384): the kernel inside ``shard_map`` and the
    ``ppermute`` ring both reach the compiler."""
    from jax.sharding import Mesh

    from sav_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(np.asarray(topo.devices).reshape(1, 4), ("data", "seq"))
    qkv = jax.ShapeDtypeStruct(
        (2, 16384, 6, 64), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "seq", None, None)),
    )

    def fn(q, k, v):
        return ring_attention(
            q, k, v, mesh=mesh, backend=backend,
            interpret=False if backend == "pallas" else None,
        )

    if direction == "backward":
        forward = fn

        def fn(q, k, v):
            return jax.grad(
                lambda *a: forward(*a).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )(q, k, v)

    text = jax.jit(fn).lower(qkv, qkv, qkv).compile().as_text()
    assert "collective-permute" in text
    assert ("tpu_custom_call" in text) == (backend == "pallas")


# ------------------------------------------------- the main-path programs


def _abstract_train_args(trainer, batch_size, image_size, mesh):
    abstract = jax.eval_shape(trainer.init_state)
    shardings = trainer._blayout.param_shardings(abstract)
    state = jax.tree.map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        abstract, shardings,
    )
    batch_sharding = trainer._blayout.batch_sharding()
    batch = {
        "images": jax.ShapeDtypeStruct(
            (batch_size, image_size, image_size, 3), jnp.float32,
            sharding=batch_sharding,
        ),
        "labels": jax.ShapeDtypeStruct(
            (batch_size,), jnp.int32, sharding=batch_sharding
        ),
    }
    rng = jax.ShapeDtypeStruct(
        (2,), jnp.uint32, sharding=NamedSharding(mesh, P())
    )
    return state, batch, rng


def _deit_s_trainer(mesh_axes, devices, backend=None):
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    mesh = create_mesh(mesh_axes, devices=devices)
    config = TrainConfig(
        model_name="deit_s_patch16",
        num_classes=1000,
        image_size=224,
        compute_dtype="bfloat16",
        global_batch_size=256,
        transpose_images=False,
        attention_backend=backend,
        seed=0,
    )
    return Trainer(config, mesh=mesh), mesh


# One compile a step program, whichever test of it runs first.
_STEPS = {}


def _deit_s_step(topo, backend):
    if ("deit_s", backend) not in _STEPS:
        trainer, mesh = _deit_s_trainer({"data": 1}, topo.devices[:1], backend)
        state, batch, rng = _abstract_train_args(trainer, 256, 224, mesh)
        _STEPS["deit_s", backend] = (
            trainer.compile_train_step(state, batch, rng), state.params
        )
    return _STEPS["deit_s", backend]


@pytest.mark.parametrize("backend", [None, "fused", "pallas"])
def test_deit_s_train_step_compiles_and_fits_one_chip(
    topo, compiled_kernels, backend
):
    """chip_smoke.py's train phase: the whole DeiT-S step at batch 256."""
    compiled, _ = _deit_s_step(topo, backend)
    assert _bytes_on_device(compiled) < HBM_BYTES
    has_kernel = "tpu_custom_call" in compiled.as_text()
    assert has_kernel == (backend is not None)


def test_deit_s_optimizer_is_one_pass_over_each_parameter(topo, compiled_kernels):
    """128 leaves, 76 of them vectors: the per-leaf update did not become
    copies or second passes at the size where leaves are many and small."""
    _assert_one_pass_optimizer(*_deit_s_step(topo, None))


def _looped_lm_step(topo, monkeypatch):
    """The token task's step at the benchmark cell's widths, length and
    batch, one layer of its four (a quarter of the compile)."""
    if "looped_lm" in _STEPS:
        return _STEPS["looped_lm"]
    from sav_tpu.ops import attention
    from sav_tpu.parallel import create_mesh
    from sav_tpu.train import TrainConfig, Trainer

    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    mesh = create_mesh({"data": 1}, devices=topo.devices[:1])
    config = TrainConfig(
        model_name="ouro_2_6b", num_classes=49152, compute_dtype="bfloat16",
        global_batch_size=2, label_smoothing=0.0, transpose_images=False,
        model_overrides={"num_layers": 1, "remat": True}, seed=0,
    )
    trainer = Trainer(config, mesh=mesh)
    abstract = jax.eval_shape(trainer.init_state)
    replicated = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated), abstract
    )
    batch = {"tokens": jax.ShapeDtypeStruct(
        (2, 4097), jnp.int32, sharding=trainer._blayout.batch_sharding()
    )}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    _STEPS["looped_lm"] = (trainer.compile_train_step(state, batch, rng), state.params)
    return _STEPS["looped_lm"]


def test_looped_lm_train_step_compiles_and_fits_one_chip(topo, compiled_kernels, monkeypatch):
    """Every pass's causal core is the flash kernel, forward and backward,
    and the remat policy keeps what would make the backward run the forward
    kernel again."""
    compiled, _ = _looped_lm_step(topo, monkeypatch)
    assert _bytes_on_device(compiled) < HBM_BYTES
    text = compiled.as_text()
    # 4 passes x (forward, backward): no recomputed forward
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    assert len(calls) == 8
    assert not any("rematted_computation" in line for line in calls)
    assert "rematted_computation" in text  # the norms and the gated product are computed again
    assert " while(" not in text  # a loop's event would count its body twice in a trace
    # The calls run in place: q, k, v and dO read with the sequence on the
    # lanes, the outputs written [B, L, H·D], the logsumexp a row, and no
    # head-major array or 128-lane float32 tile anywhere in the step.
    assert all("bf16[2,2048,4096]{2,1,0}, bf16[2,2048,4096]{2,1,0}" in line for line in calls)
    assert all("bf16[2,4096,2048]" in line for line in calls) and "f32[2,16,1,4096]" in text
    assert not re.search(r"(bf16|f32)\[32,4096,128\]|bf16\[2,16,4096,128\]", text)
    # And XLA moves nothing beside them: the matmuls and the rotary write q,
    # k, v and dO in the order the calls read (a transpose answered with a
    # layout), and read the calls' outputs as they are written. No copy,
    # transpose or pad of any size is left under the core's scope, where the
    # head-major form had eleven transposes of q's 33.5 MB and two 67 MB
    # broadcasts an application.
    in_core = lambda scope: "SelfAttentionBlock" in scope and "to_qkv" not in scope and "to_out" not in scope
    moved = [(opcode, out) for opcode, out, _ in _entry_scope(text, in_core) if opcode in ("copy", "transpose", "pad")]
    assert not moved, moved


def test_looped_lm_optimizer_is_one_pass_over_each_parameter(topo, compiled_kernels, monkeypatch):
    """Leaves of up to 403 MB (the embedding, the head), and gradients that
    are sums over four passes: each sum is taken inside the leaf's update."""
    _assert_one_pass_optimizer(*_looped_lm_step(topo, monkeypatch))


# The four expert cells' sums: (rows of the routed buffer, width, tokens, experts held).
SUM_SHAPES = {"lfm2": (32768, 2048, 32768, 8), "xing": (8192, 3584, 8192, 8),
              "qwen3next": (20480, 2048, 16384, 32), "joyai": (8192, 2048, 8192, 16)}


@pytest.mark.parametrize("rows_dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("cell", sorted(SUM_SHAPES))
def test_the_sum_of_rows_by_token_compiles_at_the_expert_cells_shapes(one_chip, cell, rows_dtype):
    """``ops/rows_to_tokens.py`` at each expert cell's ``[C, D] -> [T, D]``,
    for both callers (a weight a row and a float32 result: ``moe/combine``;
    neither: ``moe/dispatch``'s backward), inside the VMEM it asks for: one
    Mosaic call, the buffer left in HBM, no scatter."""
    from sav_tpu.ops import rows_to_tokens as sums

    count, dim, tokens, held = SUM_SHAPES[cell]
    form = sums.sum_form(count, tokens, dim, held, rows_dtype, on_tpu=True)
    assert form == {"sum": "kernel", "tile": 512, "unit": 16}
    at = lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    for weight, result in ((at((count,), jnp.float32), jnp.float32), (None, jnp.dtype(rows_dtype))):
        call = functools.partial(sums.rows_to_tokens, tokens=tokens, dtype=result, tile=form["tile"], unit=form["unit"],
                                 interpret=False)
        compiled = jax.jit(call).lower(
            at((count, dim), rows_dtype), weight, at((count,), jnp.int32), at((count,), jnp.bool_), at((held,), jnp.int32)
        ).compile()
        text = compiled.as_text()
        assert text.count('custom_call_target="tpu_custom_call"') == 1 and "scatter" not in text
        # What the call holds in VMEM: two staging slots, a float32 copy of one, the sum, the result twice.
        held_in_vmem = (512 + 16) * dim * (2 * jnp.dtype(rows_dtype).itemsize + 4 * (rows_dtype != "float32")) \
            + 512 * dim * (4 + 2 * jnp.dtype(result).itemsize)
        assert held_in_vmem < 0.7 * sums._VMEM_LIMIT
        assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20  # a few thousand integers beside the call


def test_a_sixteenth_of_the_experts_held_compiles_with_bounded_buffers(one_chip, monkeypatch):
    """The expert layer at the expert cell's shapes (8,192 tokens x 8 of 256
    experts, 16 held), forward and backward: the grouped matmuls and the sums
    of rows by token are Mosaic calls on the 8,192 rows of the bound, nothing
    has the 65,536 rows of every routing, no scatter of rows is left, and the
    overflow pass is one loop each way, whose body holds the same calls."""
    from sav_tpu.models.layers import SparseMoEBlock
    from sav_tpu.models.layers.moe import routed_row_bound
    from sav_tpu.ops import _backend, attention

    monkeypatch.setattr(_backend, "default_interpret", lambda: False)
    monkeypatch.setattr(attention, "_on_tpu", lambda: True)
    assert routed_row_bound(8192 * 8, 16, 256) == 8192
    layer = SparseMoEBlock(
        num_experts=256, top_k=8, hidden_ch=768, routed_scale=2.5, experts_held=(0, 16), dtype=jnp.bfloat16
    )
    x = jax.ShapeDtypeStruct((2, 4096, 2048), jnp.bfloat16, sharding=one_chip)
    bias = jax.ShapeDtypeStruct((256,), jnp.float32, sharding=one_chip)
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(lambda: layer.init(jax.random.PRNGKey(0), jnp.zeros((1, 8, 2048), jnp.bfloat16), jnp.zeros((256,)))),
    )

    def loss(params, x, bias):
        return jnp.sum(layer.apply(params, x, bias)[0].astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, (0, 1))).lower(params, x, bias).compile()
    text = compiled.as_text()
    calls = [line for line in text.splitlines() if 'custom_call_target="tpu_custom_call"' in line]
    common = [line for line in calls if "/moe/overflow/" not in line and "/overflow/while" not in line]
    # gate, up, down: forward, and two transposes each; the sum back to tokens under ``combine``
    # (forward) and under ``dispatch``'s transpose (backward: the gather's cotangent rows).
    matmuls = [line for line in common if "/experts/fc" in line]
    sums = [line for line in common if "rows_to_tokens" in line]
    assert len(matmuls) == 9 and len(sums) == 2 and len(common) == 11
    assert sum("jvp(" in line and "/combine/" in line for line in sums) == 1
    assert sum("transpose(jvp(" in line and "/dispatch/" in line for line in sums) == 1
    # The loop's: three matmuls and the sum under ``combine`` in the forward loop; in the backward loop the
    # three matmuls again, their six transposes and the sum under ``dispatch`` (nobody reads the forward's
    # sum there). They are named as the common pass's, under the layer's label opened again inside the body.
    looped = set(calls) - set(common)
    assert len(calls) - len(common) == 14 and all("overflow/while/body/SparseMoEBlock/" in line for line in looped)
    looped_sums = [line for line in looped if "rows_to_tokens" in line]
    assert len(looped_sums) == 2
    assert sum(bool(re.search(r"[/(]combine\)*/jit\(rows_to_tokens\)", line)) for line in looped_sums) == 1
    assert sum(bool(re.search(r"[/(]dispatch\)*/jit\(rows_to_tokens\)", line)) for line in looped_sums) == 1
    assert all(re.search(r"experts\)*/fc[12]/", line) for line in looped - set(looped_sums))
    assert "[8192,2048]" in text and "[65536,2048]" not in text and "[8192,8,2048]" not in text
    assert " conditional(" not in text
    # No scatter of [.., 2048] rows is left anywhere: the only sums by token are the calls. What is left
    # under ``combine`` is the transpose of ``take(weights, routing)``: 8,192 floats into the 65,536 weights.
    scatters = [line for line in text.splitlines() if " scatter(" in line]
    assert not [line for line in scatters if ",2048]" in line.split(" scatter(")[0]], scatters
    in_scope = [line for line in scatters if re.search(r"[/(](combine|dispatch)\)*/", line)]
    assert in_scope and all("= f32[65536]{" in line and "jit(_take)/scatter-add" in line for line in in_scope), in_scope
    assert compiled.memory_analysis().temp_size_in_bytes < 1.5e9


def test_deit_s_sharded_train_step_compiles_for_four_chips(topo):
    """chip_smoke.py --chips 4: data x model over the 2x2 host."""
    trainer, mesh = _deit_s_trainer({"data": 2, "model": 2}, topo.devices)
    state, batch, rng = _abstract_train_args(trainer, 256, 224, mesh)
    compiled = trainer.compile_train_step(state, batch, rng)
    assert "all-reduce" in compiled.as_text()
    # Per-device bytes: less than the one-chip step's, and a kernel of
    # the tensor-parallel FFN is split over the model axis.
    assert _bytes_on_device(compiled) < HBM_BYTES // 2
    fc1 = state.params["Encoder_0"]["block_0"]["FFBlock_0"]["fc1"]["kernel"]
    assert "model" in jax.tree.leaves(tuple(fc1.sharding.spec))


def test_largest_serve_bucket_compiles_and_fits_one_chip(one_chip):
    """chip_smoke.py's serve phase: the engine's program at bucket 8."""
    from sav_tpu.models import create_model
    from sav_tpu.serve.engine import build_infer_fn
    from sav_tpu.serve.quality import digested_infer_fn

    model = create_model(
        "deit_s_patch16", num_classes=1000, dtype=jnp.bfloat16
    )
    params = jax.eval_shape(
        lambda rng: model.init(
            {"params": rng}, jnp.zeros((2, 224, 224, 3), jnp.bfloat16),
            is_training=False,
        )["params"],
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        params,
    )
    batch = {
        "images": jax.ShapeDtypeStruct(
            (8, 224, 224, 3), jnp.uint8, sharding=one_chip
        ),
        "valid": jax.ShapeDtypeStruct((8,), jnp.float32, sharding=one_chip),
    }
    infer = jax.jit(digested_infer_fn(build_infer_fn(model, jnp.bfloat16)))
    compiled = infer.lower(params, {}, batch).compile()
    assert _bytes_on_device(compiled) < HBM_BYTES


def test_described_device_kind_is_in_the_peak_table(topo):
    """The v5e reports ``TPU v5 lite``; the peak table matches it exactly."""
    from sav_tpu.utils.flops import per_chip_peak_flops

    peak, source = per_chip_peak_flops(topo.devices[:1])
    assert topo.devices[0].device_kind == "TPU v5 lite"
    assert peak == 197e12 and "v5e" in source
    assert np.isfinite(peak)
