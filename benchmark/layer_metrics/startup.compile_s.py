"""Seconds the backend compiled before the window opened: the program's
compile log's ``backend`` records that the persistent cache did not answer
(``miss``, or ``off`` where it is not asked) (program_span;
``benchmark/startuplog.py``). Under a second on a warm cache; minutes where
the step compiles. Nothing to read where the program keeps no compile log."""

from benchmark import startuplog


def read(record, trace):
    summary = startuplog.before_window(record)
    return summary["backend_compile_s"] if summary else None
