"""Percent of the traced window in which ``fit``'s thread waits for
nothing: it is in none of ``sav:fit/batch_wait`` (the feeder),
``sav:fit/run_ahead_wait`` (the step ``feed_depth + 1`` back) and
``sav:fit/log_sync`` (the boundary's ``device_get``), so what is left is
the host's own work per step over the step: dispatch, bookkeeping, the log
boundary's host side (program_span, ``benchmark/hostspans.py``). At 100%
the host sets the pace and the device waits for it."""

from benchmark import hostspans


def read(record, trace):
    window = record.get("traced_window_s")
    found = hostspans.of_this_run() if trace is not None and window else None
    if not found:
        return None
    return 100.0 * (1.0 - hostspans.wait_seconds(found) / window)
