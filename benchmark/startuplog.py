"""The program's compile log, read for the ``startup.*`` readers.

``sav_tpu/obs/compile_log.py`` keeps one record for each trace, lowering
and backend compile jax finished in the process: its seconds on
``time.perf_counter`` (the clock of ``record["window_opened_t"]`` and of the
program's timeline), whether the persistent cache answered it, and the
phase span that caused it. :func:`before_window` is its own summary of the
records that ended before the window opened. A program that keeps no such
log (the parent of the PR that added it) gives None, and the readers then
report nothing.
"""

from __future__ import annotations

import sys


def before_window(record: dict):
    opened = record.get("window_opened_t")
    if opened is None:
        return None
    try:
        from sav_tpu.obs import compile_log
    except ImportError:
        return None
    summary = compile_log.summary(until=opened)
    if summary["dropped"]:
        # The oldest records are start-up's: a sum over what is left would
        # read low and say nothing of it.
        print(f"startuplog.py: the compile log dropped {summary['dropped']} records", file=sys.stderr)
        return None
    return summary if summary["records"] else None
