"""Seconds of compile requests the persistent cache answered before the
window opened: read, decompress, deserialise and load of each executable,
the program's compile log's ``backend`` records with ``cache`` ``hit``
(program_span; ``benchmark/startuplog.py``). What a warm start still pays
for its programs. Nothing to read where the program keeps no compile log."""

from benchmark import startuplog


def read(record, trace):
    summary = startuplog.before_window(record)
    return summary["cache_load_s"] if summary else None
