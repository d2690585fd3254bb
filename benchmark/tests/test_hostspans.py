"""The host-span reduction on a trace recorded on a v5e
(``fixtures/fit_v5e.xplane.pb``: the toy cell through ``run_cell`` with the
profiler on, two traced log windows of two steps; recorded and cut down by
``tools/record_fit_trace.py``), and the two readers that use it."""

import os
import shutil

import pytest

from benchmark import hostspans, run as harness, tracered

from conftest import FIXTURES

XPLANE = os.path.join(FIXTURES, "fit_v5e.xplane.pb")
GAP_MS = 1.046559  # the one drained boundary's device gap in the recording


@pytest.fixture(scope="module")
def found():
    return hostspans.read(XPLANE)


def test_the_programs_spans_are_read_from_both_threads(found):
    count = {}
    for _, _, name, _ in found["spans"]:
        count[name] = count.get(name, 0) + 1
    # Four steps in the traced window; the boundary that started the
    # profiler and the one that stopped it are cut off, the stopping one
    # after its log_sync and log_host.
    assert count == {
        "sav:fit/batch_wait": 4, "sav:fit/dispatch": 4, "sav:fit/run_ahead_wait": 2,
        "sav:fit/log_boundary": 1, "sav:fit/log_sync": 2, "sav:fit/log_host": 2, "sav:fit/log_fn": 1,
        "sav:feeder/fetch": 4, "sav:feeder/place": 4,
    }
    threads = {name.split("/")[0]: thread for _, _, name, thread in found["spans"]}
    assert threads["sav:fit"] != threads["sav:feeder"]
    assert all(start <= end for start, end, _, _ in found["spans"])


def test_the_drained_gap_goes_to_the_log_boundary(found):
    ((boundary, gaps),) = hostspans.boundary_gaps(found)
    assert boundary[2] == "sav:fit/log_boundary" and len(gaps) == 1
    (gap,) = gaps
    # The device ran dry inside log_sync and started again after the
    # boundary had closed, under the next step's dispatch.
    assert boundary[0] < gap[0] < boundary[1] < gap[1]
    assert hostspans.gap_ms_per_boundary(found) == pytest.approx(GAP_MS)
    # The steps' own gaps (this toy is host-bound: 6.9 ms of gaps) are not
    # the boundary's.
    assert len(found["gaps"]) > 1000
    assert sum(g[1] - g[0] for g in found["gaps"]) * 1e-6 > 6 * GAP_MS


def test_the_gap_is_the_one_the_harness_span_took(found):
    # Acceptance: the program's number agrees with bench:log_boundary's
    # whole-gap attribution in the same trace.
    reduced = tracered.reduce(XPLANE)
    assert hostspans.gap_ms_per_boundary(found) == pytest.approx(
        reduced["idle_gaps"]["bench:log_boundary"] * 1e3, rel=1e-6)


def test_the_gap_is_split_among_the_boundarys_children_and_the_next_step(found):
    split = {name: ns * 1e-6 for name, ns in hostspans.split_of_gaps(found).items()}
    assert sum(split.values()) == pytest.approx(GAP_MS)
    assert {"sav:fit/log_sync", "sav:fit/log_host", "sav:fit/log_fn",
            "sav:fit/batch_wait", "sav:fit/dispatch", "unspanned"} <= set(split)
    # Children first: log_boundary itself keeps only what no child covers.
    assert split["sav:fit/log_boundary"] < split["sav:fit/log_host"]
    assert split["sav:fit/dispatch"] == pytest.approx(0.801902)
    assert split["sav:fit/log_sync"] == pytest.approx(0.077057)
    assert split["sav:fit/log_host"] == pytest.approx(0.0599)
    assert split["sav:fit/log_fn"] == pytest.approx(0.017131)
    assert "sav:feeder/place" not in split  # another thread's spans own none of it


def test_waits_are_the_three_waiting_spans(found):
    waited = hostspans.wait_seconds(found)
    assert waited == pytest.approx(0.000232799 + 0.00017681 + 0.004629671)


def _lay_trace(monkeypatch, tmp_path, source):
    root = tmp_path / "profile"
    target = root / "cell" / "plugins" / "profile" / "2026_01_01"
    target.mkdir(parents=True)
    shutil.copy(source, target / "host.xplane.pb")
    monkeypatch.setattr(hostspans, "PROFILE_ROOT", str(root))
    return root


def test_readers_take_the_runs_newest_trace(monkeypatch, tmp_path):
    root = _lay_trace(monkeypatch, tmp_path, XPLANE)
    older = root / "other_cell" / "plugins" / "profile" / "2025_01_01"
    older.mkdir(parents=True)
    shutil.copy(os.path.join(FIXTURES, "tiny_tpu.xplane.pb"), older / "host.xplane.pb")
    os.utime(older / "host.xplane.pb", (1, 1))
    trace = tracered.reduce(XPLANE)
    record = {"traced_window_s": 0.00909065}  # the recording's window, by the harness's clock
    assert harness.load_reader("trainer.boundary_gap_ms")(record, trace) == pytest.approx(GAP_MS)
    busy = harness.load_reader("trainer.host_busy_share")(record, trace)
    assert busy == pytest.approx(100 * (1 - 0.00503928 / 0.00909065))
    assert 0 < busy < 100


def test_readers_report_nothing_without_spans_or_trace(monkeypatch, tmp_path):
    readers = [harness.load_reader(n) for n in ("trainer.boundary_gap_ms", "trainer.host_busy_share")]
    record = {"traced_window_s": 1.0}
    # The parent's trace: device operations and the harness's span, no sav: span.
    _lay_trace(monkeypatch, tmp_path, os.path.join(FIXTURES, "tiny_tpu.xplane.pb"))
    trace = tracered.reduce(os.path.join(FIXTURES, "tiny_tpu.xplane.pb"))
    assert [read(record, trace) for read in readers] == [None, None]
    assert [read(record, None) for read in readers] == [None, None]
    monkeypatch.setattr(hostspans, "PROFILE_ROOT", str(tmp_path / "nothing_here"))
    assert [read(record, trace) for read in readers] == [None, None]
    assert hostspans.of_this_run() is None


def test_spans_without_a_whole_boundary_give_no_gap():
    found = {"spans": [(0.0, 5.0, "sav:fit/dispatch", 0)], "gaps": [(1.0, 2.0)]}
    assert hostspans.gap_ms_per_boundary(found) is None
    assert hostspans.split_of_gaps(found) == {}


def test_a_gap_while_steps_were_still_queued_is_not_the_boundarys():
    found = {
        "spans": sorted([
            (0.0, 100.0, "sav:fit/log_boundary", 0), (1.0, 60.0, "sav:fit/log_sync", 0),
            (61.0, 70.0, "sav:fit/log_host", 0), (110.0, 130.0, "sav:fit/dispatch", 0),
        ]),
        # 20-22: between two queued steps, inside log_sync; 55-140: the drain.
        "gaps": [(20.0, 22.0), (55.0, 140.0)],
    }
    ((_, gaps),) = hostspans.boundary_gaps(found)
    assert gaps == [(55.0, 140.0)]
    split = hostspans.split_of_gaps(found)
    assert split == {
        "sav:fit/log_sync": 5.0, "sav:fit/log_boundary": 1.0 + 30.0, "sav:fit/log_host": 9.0,
        "unspanned": 10.0 + 10.0, "sav:fit/dispatch": 20.0,
    }


def test_planes_apart_still_give_the_boundary_its_gap():
    spans = sorted([
        (0.0, 100e6, "sav:fit/log_boundary", 0), (1e6, 60e6, "sav:fit/log_sync", 0),
        (61e6, 70e6, "sav:fit/log_host", 0), (110e6, 130e6, "sav:fit/dispatch", 0),
    ])
    # The device's plane 8 ms late: no gap holds log_sync's end (60 ms), the
    # 3 ms drain lies after it, the steps' own gaps are 0.05 ms.
    found = {"spans": spans, "gaps": [(25e6, 25.05e6), (66e6, 69e6), (130e6, 130.05e6)]}
    assert not hostspans.aligned(found)
    ((_, gaps),) = hostspans.boundary_gaps(found)
    assert gaps == [(66e6, 69e6)]
    assert hostspans.gap_ms_per_boundary(found) == pytest.approx(3.0)
    # Beyond the tolerance nothing is guessed.
    far = {"spans": spans, "gaps": [(25e6, 25.05e6), (150e6, 153e6)]}
    assert hostspans.gap_ms_per_boundary(far) == 0.0
    assert hostspans.aligned({"spans": spans, "gaps": [(58e6, 112e6)]})


def test_the_command_prints_a_traces_account(capsys):
    assert hostspans.main([XPLANE]) == 0
    import json

    account = json.loads(capsys.readouterr().out)
    assert account["boundaries"] == 1 and account["boundary_gap_ms"] == pytest.approx(GAP_MS)
    assert account["planes_aligned"] is True and account["boundaries_ms"][0]["start"] > 0
    assert account["largest_gaps_ms"][0]["length"] >= account["largest_gaps_ms"][1]["length"]
    assert account["spans"]["sav:fit/dispatch"]["count"] == 4
    assert list(account["gap_split_ms"])[0] == "sav:fit/dispatch"  # largest first
