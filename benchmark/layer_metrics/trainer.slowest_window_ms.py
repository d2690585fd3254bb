"""Milliseconds per step of the slowest log window of the rate window, from
the harness's clock at ``fit``'s synced log boundaries (host_clock): what
``fit``'s own host work (feeder, log sync, telemetry) adds at its worst.
A log window is the mix's ``log_every_steps`` (10 in the train mixes,
against the program's default of 100), so the boundary's own pause is a
tenth of each window here and a hundredth in a default ``fit``."""


def read(record, trace):
    steps = record["spans"].get("log_window_step_s")
    return max(steps) * 1e3 if steps else None
