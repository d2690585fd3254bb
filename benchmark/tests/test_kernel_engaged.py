"""``kernel.attention_engaged_share``: on a hand-made table where the
share is known, and through ``run_cell`` on the trace recorded on a v5e
(``fixtures/fit_v5e.xplane.pb``), whose program holds no ``pallas_call``."""

import os
import time

import pytest

from benchmark import run as harness, tracered

from conftest import FIXTURES

STEP = "jit(_train_step_impl)/jit(main)/"
BLOCK = "Encoder_0/block_3/SelfAttentionBlock_0/SelfAttentionBlock_0/"
SCOPES = {
    # instruction -> op_name, as tracered.scopes_of_hlo gives them
    "SelfAttentionBlock_0.24": STEP + "jvp(ViT)/" + BLOCK + "pallas_call",
    "SelfAttentionBlock_0.36": STEP + "transpose(jvp(ViT))/" + BLOCK + "pallas_call",
    # XLA's relayout of the kernel's output carries the kernel's name and counts with it
    "copy.736": STEP + "jvp(ViT)/" + BLOCK + "pallas_call",
    "fusion.9": STEP + "transpose(jvp(ViT))/" + BLOCK + "reshape;" + STEP + "transpose(jvp(ViT))/" + BLOCK + "pallas_call",
    "fusion.1": STEP + "jvp(ViT)/" + BLOCK + "exp",
    "fusion.2": STEP + "jvp(ViT)/" + BLOCK + "to_qkv/...i,ihd->...hd/dot_general",
    "fusion.3": STEP + "transpose(jvp(ViT))/Encoder_0/block_3/FFBlock_0/fc1/dot_general",
    "pallas_call.5": STEP + "optimizer/pallas_call",  # a kernel, but no attention core
    "copy.1": "",
}
OP_SECONDS = {
    "SelfAttentionBlock_0.24": 2.0, "SelfAttentionBlock_0.36": 4.0, "copy.736": 1.0, "fusion.9": 0.5,
    "fusion.1": 0.5, "fusion.2": 10.0, "fusion.3": 10.0, "pallas_call.5": 3.0, "copy.1": 1.0,
    "not-in-the-text.1": 6.0,
}


@pytest.fixture(scope="module")
def read():
    return harness.load_reader("kernel.attention_engaged_share")


def test_engaged_share_is_the_kernel_calls_part_of_the_core(read):
    # Core: the two calls 6.0, the copy under their name 1.0, two fusions
    # with other names in them 1.0; the projections, the MLP and the
    # optimizer's kernel are no part of it.
    assert read({"hlo_scopes": SCOPES}, {"op_seconds": OP_SECONDS}) == pytest.approx(100 * 7.0 / 8.0)


def test_engaged_share_is_zero_on_the_dense_path_and_nothing_without_a_core(read):
    dense = {k: v.replace("pallas_call", "exp") for k, v in SCOPES.items()}
    assert read({"hlo_scopes": dense}, {"op_seconds": OP_SECONDS}) == 0.0
    no_core = {k: v for k, v in SCOPES.items() if "SelfAttentionBlock" not in v}
    assert read({"hlo_scopes": no_core}, {"op_seconds": OP_SECONDS}) is None
    assert read({"hlo_scopes": SCOPES}, None) is None
    assert read({"hlo_scopes": None}, {"op_seconds": OP_SECONDS}) is None
    assert read({}, {"op_seconds": OP_SECONDS}) is None


def test_the_predicate_is_attention_shares_own(read):
    core = harness.load_reader("model.attention_share").__globals__["in_attention_core"]
    ours = read.__globals__["in_attention_core"]
    for scope in SCOPES.values():
        assert ours(scope) == core(scope)


def test_recorded_v5e_trace_holds_no_kernel_call(toy_bench, toy_cell, monkeypatch):
    # As test_drivers does: the CPU's trace has no device plane, so the
    # reduction of the recorded chip trace stands in for it.
    recorded = tracered.reduce(os.path.join(FIXTURES, "fit_v5e.xplane.pb"))
    monkeypatch.setattr(tracered, "reduce", lambda path: recorded)
    line = harness.run_cell(toy_bench, toy_cell, 3, 0.3, True, process_t0=time.perf_counter())
    assert line["metrics"]["kernel.attention_engaged_share"] == {"value": 0.0, "unit": "%"}
    assert line["metrics"]["model.attention_share"]["value"] > 0
