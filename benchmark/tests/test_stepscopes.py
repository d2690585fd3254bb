"""The scope readers on hand-made tables: what an ``op_name`` owns, the
optimizer's share and the unowned share of a step's device time, and the
two start-up readers on the process timeline."""

import pytest

from benchmark import run as harness, stepscopes

STEP = "jit(_train_step_impl)/jit(main)/"
SCOPES = {
    # instruction -> op_name, as tracered.scopes_of_hlo gives them
    "fusion.1": STEP + "transpose(jvp(ViT))/Encoder_0/block_3/FFBlock_0/fc1/dot_general",
    "fusion.2": STEP + "jvp(ViT)/Encoder_0/block_3/SelfAttentionBlock_0/SelfAttentionBlock_0/exp",
    "fusion.3": STEP + "optimizer/jit(clip)/min",
    "fusion.4": STEP + "optimizer/mul",
    "fusion.5": STEP + "transpose(jvp(loss))/jit(log_softmax)/sub",
    "fusion.6": STEP + "metrics/top_k",
    "fusion.7": STEP + "preprocess/jit(_one_hot)/eq",
    "fusion.8": STEP + "jvp(jit(log_softmax))/sub",  # a function's name is no scope
    "fusion.9": STEP + "transpose(jvp())/mul",
    "copy.1": "",
    "reduce.1": "reduce_sum",
    # the compiler joins fused instructions' names
    "fusion.10": STEP + "transpose(jvp())/broadcast_in_dim;" + STEP + "optimizer/add",
}
OP_SECONDS = {
    "fusion.1": 40.0, "fusion.2": 40.0, "fusion.3": 1.0, "fusion.4": 2.0, "fusion.5": 1.0,
    "fusion.6": 0.5, "fusion.7": 0.5, "fusion.8": 1.0, "fusion.9": 1.0, "copy.1": 5.0,
    "reduce.1": 1.0, "fusion.10": 1.0, "not-in-the-text.1": 6.0,
}


@pytest.mark.parametrize("op_name, labels", [
    (SCOPES["fusion.1"], ["ViT", "Encoder_0", "block_3", "FFBlock_0", "fc1"]),
    (SCOPES["fusion.3"], ["optimizer"]),
    (SCOPES["fusion.5"], ["loss"]),
    (SCOPES["fusion.8"], []),
    (SCOPES["fusion.9"], []),
    (SCOPES["fusion.10"], ["optimizer"]),
    ("", []),
    ("reduce_sum", []),
    ("state.params['head']['kernel']", []),
])
def test_scope_labels_of_an_op_name(op_name, labels):
    assert stepscopes.scopes_of(op_name) == labels
    assert stepscopes.unowned(op_name) is (not labels)


def test_every_step_scope_is_told_from_the_others():
    for label, instruction in zip(stepscopes.STEP_SCOPES, ["fusion.7", "fusion.5", "fusion.3", "fusion.6"]):
        accepts = stepscopes.in_step_scope(label)
        assert accepts(SCOPES[instruction])
        assert not any(accepts(s) for name, s in SCOPES.items()
                       if name not in (instruction, "fusion.4", "fusion.10", "fusion.3"))


def test_optimizer_share_is_the_scopes_share_of_operation_time():
    read = harness.load_reader("trainer.optimizer_share")
    trace = {"op_seconds": OP_SECONDS}
    assert read({"hlo_scopes": SCOPES}, trace) == pytest.approx(100 * 4.0 / 100.0)
    assert read({"hlo_scopes": SCOPES}, None) is None
    assert read({"hlo_scopes": None}, trace) is None
    # The parent's step names no such scope: nothing to read, not 0%.
    parent = {k: v.replace("optimizer/", "") for k, v in SCOPES.items()}
    assert read({"hlo_scopes": parent}, trace) is None


def test_unowned_share_counts_what_no_scope_names():
    read = harness.load_reader("model.unowned_share")
    trace = {"op_seconds": OP_SECONDS}
    # jit-only names 1 + 1, the copy 5, the bare reduce 1, the instruction
    # the text does not hold 6.
    assert read({"hlo_scopes": SCOPES}, trace) == pytest.approx(14.0)
    assert read({"hlo_scopes": {}}, trace) is None and read({"hlo_scopes": SCOPES}, None) is None
    parent = {k: v.replace("optimizer/", "").replace("metrics/", "") for k, v in SCOPES.items()}
    assert read({"hlo_scopes": parent}, trace) == pytest.approx(14.0 + 4.0 + 0.5)


def test_the_four_shares_partition_no_more_than_the_whole():
    trace = {"op_seconds": OP_SECONDS}
    record = {"hlo_scopes": SCOPES}
    shares = [harness.load_reader(name)(record, trace) for name in (
        "model.attention_share", "model.matmul_share", "trainer.optimizer_share", "model.unowned_share")]
    assert sum(shares) == pytest.approx(40 + 40 + 4 + 14) and sum(shares) <= 100.0


def test_startup_readers_read_the_process_timeline(monkeypatch):
    from sav_tpu.obs import spans

    entries = [
        ("sav:startup/import:sav_tpu.train.config", 10.0, 10.5),
        ("sav:startup/import:sav_tpu.train.trainer", 10.5, 36.0),
        ("sav:trainer/init", 36.0, 36.1),
        ("sav:fit/compile", 40.0, 44.5),
        ("sav:fit/compile", 50.0, 50.2),
    ]
    monkeypatch.setattr(spans, "timeline", lambda: list(entries))
    assert harness.load_reader("startup.import_s")({}, None) == pytest.approx(26.0)
    assert harness.load_reader("startup.first_step_s")({}, None) == pytest.approx(4.5)
    monkeypatch.setattr(spans, "timeline", lambda: entries[2:3])
    assert harness.load_reader("startup.import_s")({}, None) is None
    assert harness.load_reader("startup.first_step_s")({}, None) is None


def test_startup_readers_find_nothing_in_a_program_without_a_timeline(monkeypatch):
    from sav_tpu.obs import spans

    monkeypatch.delattr(spans, "timeline")  # the parent's spans.py has none
    assert harness.load_reader("startup.import_s")({}, None) is None
    assert harness.load_reader("startup.first_step_s")({}, None) is None
