"""Share of the traced window in which no operation ran on the device:
1 - (union of device operation intervals) / (traced window), per chip and
averaged (device_trace). The traced window runs between two synced log
boundaries of ``fit``, so the device is drained at both ends.

Read at the cell's own log cadence, which the mix states: the train mixes
log every 10 steps, and ``fit`` drains the device for about 3 ms at each
boundary, so the share holds that pause once in ten steps. A ``fit`` at the
program's default of 100 steps shows it a tenth as often (PERF.md,
section 3)."""


def read(record, trace):
    window = record.get("traced_window_s")
    if trace is None or not window:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / window)
