"""Seconds the program's lazy imports took: the sum of the process
timeline's ``sav:startup/import:*`` spans, which ``sav_tpu/_lazy.py``
records for the outermost import it resolves (program_span; the timeline
is read in the program's own process). The largest part of ``setup_s``.
Nothing to read where the program keeps no timeline."""

from benchmark import hostspans


def read(record, trace):
    seconds = [
        end - start for name, start, end in hostspans.program_timeline()
        if name.startswith("sav:startup/import:")
    ]
    return sum(seconds) if seconds else None
