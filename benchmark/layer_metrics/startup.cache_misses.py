"""Programs the backend compiled before the window opened that the
persistent cache should have held: the program's compile log's ``backend``
records with ``cache`` ``miss`` or ``off`` of at least a second, jax's own
floor for what is worth an entry (program_counter;
``benchmark/startuplog.py``). 0 on a warm cache: the number that tells a
run that compiled from one that loaded, whatever ``setup_s`` reads. Nothing
to read where the program keeps no compile log."""

from benchmark import startuplog


def read(record, trace):
    summary = startuplog.before_window(record)
    return summary["slow_compiles"] if summary else None
