"""Hang watchdog (sav_tpu/obs/watchdog.py): a stalled step triggers the
stack dump + labeled exit code; a normally-beating run never fires.
The exit function is injected so the suite survives the 'abort'."""

import io
import threading
import time

import pytest

from sav_tpu.obs.goodput import GoodputLedger
from sav_tpu.obs.watchdog import WATCHDOG_EXIT_CODE, HangWatchdog, dump_all_stacks


class FakeExit:
    def __init__(self):
        self.codes = []
        self.called = threading.Event()

    def __call__(self, code):
        self.codes.append(code)
        self.called.set()


def test_exit_code_contract_distinct_from_device_check():
    # device_check aborts startup with 3; the watchdog owns 4. Wrapper
    # scripts key on both — pin the constant.
    assert WATCHDOG_EXIT_CODE == 4


def test_stalled_step_fires_with_stacks_and_labeled_exit():
    exit_fn = FakeExit()
    stream = io.StringIO()
    ledger = GoodputLedger()
    with ledger.measure("step"):
        pass
    watchdog = HangWatchdog(
        0.2, ledger=ledger, tag="test-watchdog", exit_fn=exit_fn,
        stream=stream, poll_s=0.05,
    )
    watchdog.start()
    try:
        # A deliberately-stalled step: never beat.
        assert exit_fn.called.wait(timeout=5.0), "watchdog never fired"
    finally:
        watchdog.stop()
    assert exit_fn.codes == [WATCHDOG_EXIT_CODE]
    output = stream.getvalue()
    assert "test-watchdog: HANG" in output
    assert f"exit {WATCHDOG_EXIT_CODE}" in output
    # The stack dump must include this (the stalled main) thread's frames.
    assert "stack of MainThread" in output
    assert "test_stalled_step_fires" in output
    # ... and the goodput ledger snapshot.
    assert "goodput ledger at hang" in output
    assert '"buckets_s"' in output


def test_no_false_fire_on_normal_run():
    exit_fn = FakeExit()
    watchdog = HangWatchdog(
        0.3, tag="test-watchdog", exit_fn=exit_fn, poll_s=0.05
    )
    watchdog.start()
    try:
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            watchdog.beat()  # a healthy step loop
            time.sleep(0.05)
    finally:
        watchdog.stop()
    assert not exit_fn.called.is_set()
    assert not watchdog.fired.is_set()


def test_stop_disarms_before_deadline():
    exit_fn = FakeExit()
    watchdog = HangWatchdog(
        0.2, exit_fn=exit_fn, poll_s=0.02
    ).start()
    watchdog.stop()
    time.sleep(0.4)
    assert not exit_fn.called.is_set()


def test_context_manager_protocol():
    exit_fn = FakeExit()
    with HangWatchdog(5.0, exit_fn=exit_fn) as watchdog:
        watchdog.beat()
    assert not exit_fn.called.is_set()


def test_rejects_nonpositive_deadline():
    with pytest.raises(ValueError):
        HangWatchdog(0.0)


# ------------------------------------------------- two-stage (soft) stage


def test_soft_stage_fires_once_then_hard_stage_aborts():
    """ISSUE 7: the soft (warning) stage dumps stacks + calls on_soft
    while the run continues; only the hard deadline keeps exit 4."""
    exit_fn = FakeExit()
    stream = io.StringIO()
    soft_calls = []
    watchdog = HangWatchdog(
        0.6, tag="test-watchdog", exit_fn=exit_fn, stream=stream,
        poll_s=0.05, soft_deadline_s=0.15,
        on_soft=soft_calls.append,
    )
    watchdog.start()
    try:
        assert watchdog.soft_fired.wait(timeout=5.0), "soft never fired"
        # Soft fired; the process is still alive (no exit yet).
        assert not exit_fn.called.is_set()
        assert exit_fn.called.wait(timeout=5.0), "hard stage never fired"
    finally:
        watchdog.stop()
    assert exit_fn.codes == [WATCHDOG_EXIT_CODE]
    assert watchdog.soft_count == 1  # once per silent episode, not per poll
    assert len(soft_calls) == 1 and soft_calls[0] >= 0.15
    output = stream.getvalue()
    assert "test-watchdog: SOFT" in output
    assert "run continues" in output
    assert "stack of MainThread" in output
    # The hard stage's contract is unchanged.
    assert "test-watchdog: HANG" in output


def test_soft_stage_rearms_after_a_beat():
    exit_fn = FakeExit()
    stream = io.StringIO()
    soft_calls = []
    watchdog = HangWatchdog(
        10.0, tag="test-watchdog", exit_fn=exit_fn, stream=stream,
        poll_s=0.03, soft_deadline_s=0.15, on_soft=soft_calls.append,
    )
    watchdog.start()
    try:
        assert watchdog.soft_fired.wait(timeout=5.0)
        watchdog.beat()  # the stall resolved: episode over
        watchdog.soft_fired.clear()
        assert watchdog.soft_fired.wait(timeout=5.0), (
            "soft stage did not re-arm for the second stall episode"
        )
    finally:
        watchdog.stop()
    assert watchdog.soft_count == 2
    assert not exit_fn.called.is_set()


def test_soft_callback_blocking_does_not_block_hard_stage():
    """The soft dump writes to the very log dir whose filesystem may BE
    the stall's cause: a callback that never returns must be abandoned
    after dump_timeout_s so the hard exit-4 contract survives."""
    exit_fn = FakeExit()
    stream = io.StringIO()
    wedged = threading.Event()

    def wedged_soft(silent_s):
        wedged.wait(60.0)  # a write to a hung FS never returns

    watchdog = HangWatchdog(
        1.2, tag="test-watchdog", exit_fn=exit_fn, stream=stream,
        poll_s=0.05, soft_deadline_s=0.2, on_soft=wedged_soft,
        dump_timeout_s=0.2,
    )
    watchdog.start()
    try:
        assert exit_fn.called.wait(timeout=10.0), (
            "hard stage never fired — the wedged soft callback blocked "
            "the monitor thread"
        )
    finally:
        wedged.set()
        watchdog.stop()
    assert exit_fn.codes == [WATCHDOG_EXIT_CODE]
    assert "soft-stage dump still blocked" in stream.getvalue()


def test_soft_callback_failure_does_not_block_hard_stage():
    exit_fn = FakeExit()
    stream = io.StringIO()

    def bad_soft(silent_s):
        raise RuntimeError("snapshot disk full")

    watchdog = HangWatchdog(
        0.4, tag="test-watchdog", exit_fn=exit_fn, stream=stream,
        poll_s=0.05, soft_deadline_s=0.1, on_soft=bad_soft,
    )
    watchdog.start()
    try:
        assert exit_fn.called.wait(timeout=5.0)
    finally:
        watchdog.stop()
    assert exit_fn.codes == [WATCHDOG_EXIT_CODE]
    assert "on_soft failed" in stream.getvalue()


def test_soft_deadline_must_be_below_hard():
    with pytest.raises(ValueError):
        HangWatchdog(1.0, soft_deadline_s=1.0)
    with pytest.raises(ValueError):
        HangWatchdog(1.0, soft_deadline_s=0.0)
    # None disables the stage entirely.
    exit_fn = FakeExit()
    watchdog = HangWatchdog(5.0, exit_fn=exit_fn, soft_deadline_s=None)
    assert watchdog.soft_deadline_s is None


def test_dump_all_stacks_lists_live_threads():
    stream = io.StringIO()
    barrier = threading.Event()
    release = threading.Event()

    def parked():
        barrier.set()
        release.wait(5.0)

    t = threading.Thread(target=parked, name="parked-thread")
    t.start()
    try:
        assert barrier.wait(5.0)
        dump_all_stacks(stream)
    finally:
        release.set()
        t.join()
    output = stream.getvalue()
    assert "parked-thread" in output
    assert "MainThread" in output
