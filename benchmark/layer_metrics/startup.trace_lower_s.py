"""Seconds jax spent tracing and lowering before the window opened, whoever
asked: the program's compile log's ``trace`` and ``lower`` records, each
instant of a thread counted once (program_span; ``benchmark/startuplog.py``).
The same on a warm cache and an empty one. Nothing to read where the program
keeps no compile log."""

from benchmark import startuplog


def read(record, trace):
    summary = startuplog.before_window(record)
    return summary["trace_lower_s"] if summary else None
