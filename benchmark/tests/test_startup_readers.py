"""The five ``startup.*`` readers of the program's compile log, on a planted
log and timeline: the sums, the floor of a second, the cut at the window's
opening, ``init_state``'s self time; nothing and no exception where the
program keeps no log (the parent of the PR that added it); and the account
of ``benchmark/tools/startup_account.py`` adding up."""

import importlib.util
import os
import sys

import pytest

from benchmark import hostspans, run as harness

from conftest import ROOT

READERS = ("startup.trace_lower_s", "startup.compile_s", "startup.cache_load_s",
           "startup.cache_misses", "startup.init_state_s")
OPENED = 100.0


def _record(kind, start, end, *, cause=None, cache=None, fun_name="f", thread=1):
    found = {"kind": kind, "fun_name": fun_name, "start": start, "end": end, "thread": thread, "cause": cause}
    if kind == "backend":
        found.update(cache=cache, retrieval_s=0.0)
    return found


@pytest.fixture()
def planted(monkeypatch):
    """A run that loaded its step (20 s), compiled its initialiser (8 s)
    and a small draw (0.4 s), and compiled the reference after the window."""
    from sav_tpu.obs import compile_log, spans

    log = compile_log.CompileLog()
    init = "sav:trainer/init_state"
    for record in (
        _record("trace", 10.0, 12.0, cause=init, fun_name="init_fn"),
        _record("lower", 12.0, 13.0, cause=init, fun_name="jit(init_fn)"),
        _record("backend", 13.0, 21.0, cause=init, cache="miss", fun_name="jit(init_fn)"),
        _record("trace", 30.0, 30.5, fun_name="draw"),
        _record("backend", 30.5, 30.9, cache="off", fun_name="jit(draw)"),
        _record("trace", 40.0, 44.0, cause="sav:fit/compile", fun_name="step"),
        _record("backend", 45.0, 65.0, cause="sav:fit/compile", cache="hit", fun_name="jit(step)"),
        # After the window: the reference's.
        _record("trace", 120.0, 121.0, fun_name="reference"),
        _record("backend", 121.0, 181.0, cache="miss", fun_name="jit(reference)"),
    ):
        log.add(record)
    monkeypatch.setattr(compile_log, "_LOG", log)
    monkeypatch.setattr(spans, "timeline", lambda: [
        ("sav:startup/import:sav_tpu.train.trainer", 5.0, 8.0),
        ("sav:trainer/init", 8.0, 9.0),
        (init, 9.5, 24.5),
        ("sav:fit/compile", 39.0, 66.0),
        (init, 130.0, 140.0),  # after the window: not start-up's
    ])
    return log


def _read(name, record):
    return harness.load_reader(name)(record, None)


def test_the_readers_sum_what_ended_before_the_window(planted):
    record = {"window_opened_t": OPENED}
    assert _read("startup.trace_lower_s", record) == pytest.approx(2.0 + 1.0 + 0.5 + 4.0)
    assert _read("startup.compile_s", record) == pytest.approx(8.0 + 0.4)
    assert _read("startup.cache_load_s", record) == pytest.approx(20.0)
    # The draw's 0.4 s is in compile_s and under the floor of a second.
    assert _read("startup.cache_misses", record) == 1
    # 15 s of span less the 11 s its trace, lowering and compile took.
    assert _read("startup.init_state_s", record) == pytest.approx(15.0 - 11.0)


def test_the_cut_moves_with_the_windows_opening(planted):
    late = {"window_opened_t": 200.0}
    assert _read("startup.compile_s", late) == pytest.approx(8.0 + 0.4 + 60.0)
    assert _read("startup.cache_misses", late) == 2
    assert _read("startup.init_state_s", late) == pytest.approx(25.0 - 11.0)
    early = {"window_opened_t": 22.0}
    assert _read("startup.cache_load_s", early) == 0.0
    assert _read("startup.init_state_s", early) is None  # the span had not closed
    assert all(_read(name, {"window_opened_t": 1.0}) is None for name in READERS)


def test_dropped_records_are_not_summed_over(planted, capsys):
    planted.dropped = 3
    assert all(_read(name, {"window_opened_t": OPENED}) is None for name in READERS)
    assert "dropped 3 records" in capsys.readouterr().err


def test_a_program_without_a_compile_log_gives_nothing_and_raises_nothing(monkeypatch):
    # The parent's sav_tpu.obs has no such module: the import fails.
    import sav_tpu.obs

    monkeypatch.setitem(sys.modules, "sav_tpu.obs.compile_log", None)
    monkeypatch.delattr(sav_tpu.obs, "compile_log", raising=False)
    with pytest.raises(ImportError):
        from sav_tpu.obs import compile_log  # noqa: F401
    assert all(_read(name, {"window_opened_t": OPENED}) is None for name in READERS)


def test_a_program_without_a_timeline_gives_no_init_state(planted, monkeypatch):
    monkeypatch.setattr(hostspans, "program_timeline", lambda: [])
    assert _read("startup.init_state_s", {"window_opened_t": OPENED}) is None
    assert _read("startup.trace_lower_s", {"window_opened_t": OPENED}) is not None


def test_the_five_are_entered_for_every_cell_and_move_setup_s(bench):
    cells = [w["name"] for w in bench["workloads"]]
    entered = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert entered[name]["workloads"] == cells and entered[name]["moves"] == "setup_s"
        assert entered[name]["layer"] == "trainer"
    assert entered["startup.cache_misses"]["source"] == "program_counter"
    assert [m["name"] for m in bench["per_layer"]][-5:] == list(READERS)


def test_a_traced_toy_run_prints_the_five(toy_bench, toy_cell, monkeypatch):
    from benchmark import tracered
    from conftest import FIXTURES

    recorded = tracered.reduce(os.path.join(FIXTURES, "tiny_tpu.xplane.pb"))
    monkeypatch.setattr(tracered, "reduce", lambda path: recorded)
    for metric in toy_bench["per_layer"]:
        if metric["name"] in READERS:
            metric["workloads"].append(toy_cell["name"])
    import time

    line = harness.run_cell(toy_bench, toy_cell, 2**31 + 11, 0.3, True, process_t0=time.perf_counter())
    values = {name: line["metrics"][name]["value"] for name in READERS}
    assert values["startup.trace_lower_s"] > 0.0 and values["startup.compile_s"] > 0.0
    assert values["startup.init_state_s"] > 0.0
    assert line["metrics"]["startup.cache_misses"]["unit"] == "compiles"


# --------------------------------------------------------- the account's tool


def _tool():
    path = os.path.join(ROOT, "benchmark", "tools", "startup_account.py")
    spec = importlib.util.spec_from_file_location("startup_account", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_every_moment_goes_to_the_innermost_interval():
    rows = _tool().self_seconds([
        ("phase", 0.0, 10.0), ("span", 1.0, 8.0), ("compile", 2.0, 5.0), ("load", 6.0, 7.0),
        ("outlasts", 7.5, 9.0), ("next", 10.0, 12.0),
    ])
    assert rows == {"phase": 3.0, "span": 2.5, "compile": 3.0, "load": 1.0, "outlasts": 0.5, "next": 2.0}
    assert sum(rows.values()) == 12.0


def test_the_account_adds_setup_up(planted):
    from sav_tpu.obs import spans

    # The marks at 2, 8, 9.2, 26, 70 and the window's opening at 100.
    phases = {"program_imported": 6.0, "trainer_built": 1.2, "state_initialised": 16.8,
              "fit_step1": 44.0, "window_opened": 30.0, "fit_returned": 20.0}
    found = _tool().account(0.0, OPENED, phases, spans.timeline(), planted.log(until=OPENED))
    rows = found["rows"]
    assert found["setup_s"] == OPENED and found["remainder_s"] == pytest.approx(0.0)
    assert rows["harness:before_first_mark"] == pytest.approx(2.0)
    assert rows["sav:startup/import:*"] == pytest.approx(3.0)
    assert rows["compile caused by sav:trainer/init_state"] == pytest.approx(8.0)
    assert rows["trace_lower caused by sav:trainer/init_state"] == pytest.approx(3.0)
    assert rows["sav:trainer/init_state"] == pytest.approx(4.0)
    assert rows["cache_load caused by sav:fit/compile"] == pytest.approx(20.0)
    assert rows["sav:fit/compile"] == pytest.approx(27.0 - 24.0)
    assert rows["compile caused by None"] == pytest.approx(0.4)
    assert rows["harness:state_initialised"] == pytest.approx(16.8 - 15.0)
    assert rows["harness:window_opened"] == pytest.approx(30.0)
    assert "harness:fit_returned" not in rows
