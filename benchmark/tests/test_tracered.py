"""The trace reduction, on a small trace recorded on a v5e (three calls of
a two-matmul program, each inside a ``bench:fixture_call`` span)."""

import os

import pytest

from benchmark import tracered

from conftest import FIXTURES

XPLANE = os.path.join(FIXTURES, "tiny_tpu.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tracered.reduce(XPLANE)


def test_device_operations_are_found_by_their_short_names(reduced):
    assert reduced["chips"] == 1
    names = set(reduced["op_seconds"])
    assert {"fusion.5", "fusion.1", "fusion", "copy"} <= names
    assert all(" " not in n and not n.startswith("%") for n in names)


def test_busy_is_the_union_of_the_operations(reduced):
    total = sum(reduced["op_seconds"].values())
    assert 0 < reduced["busy_s"] <= total
    assert reduced["busy_s"] == pytest.approx(17.446e-6, rel=1e-3)


def test_gaps_go_to_the_harness_span_that_overlaps_them(reduced):
    assert reduced["idle_gaps"]["bench:fixture_call"] > 10 * reduced["busy_s"]


def test_a_trace_without_device_operations_is_an_error(tmp_path):
    import jax
    import jax.numpy as jnp

    tracer = tracered.Tracer(str(tmp_path))
    tracer.start()
    jax.jit(lambda x: x + 1)(jnp.ones(4)).block_until_ready()
    tracer.stop()
    with pytest.raises(tracered.TraceError):
        tracered.reduce(tracered.find_xplane(str(tmp_path)))


HLO = """
ENTRY %main {
  %fusion.7 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fc, metadata={op_name="jit(step)/jvp(ViT)/Encoder_0/block_3/SelfAttentionBlock_0/SelfAttentionBlock_0/sub" source_file="x.py"}
  %fusion.8 = bf16[8,8]{1,0} fusion(%a), kind=kOutput, calls=%fd, metadata={op_name="jit(step)/jvp(ViT)/Encoder_0/block_4/FFBlock_0/fc1/dot_general"}
  ROOT %copy.1 = bf16[8,8]{1,0} copy(%fusion.8)
}
"""


def test_scopes_come_from_the_compiled_text():
    scopes = tracered.scopes_of_hlo(HLO)
    assert scopes["fusion.7"].endswith("SelfAttentionBlock_0/sub")
    assert scopes["copy.1"] == ""
    trace = {"op_seconds": {"fusion.7": 3.0, "fusion.8": 1.0, "copy.1": 1.0}}
    assert tracered.share_by_scope(trace, scopes, lambda s: "SelfAttentionBlock" in s) == pytest.approx(60.0)
    folded = tracered.by_scope(trace["op_seconds"], scopes)
    assert folded["jvp(ViT)/Encoder_0/block_*/FFBlock_0/fc1/dot_general"] == 1.0
    assert folded["copy.1"] == 1.0
