"""Seconds of the first ``fit``'s ``sav:fit/compile`` span in the process
timeline: the train step read from the compile cache, or compiled
(program_span; ``Trainer.fit`` records it around the ahead-of-time compile,
or around the first dispatch where it compiles there)."""

from benchmark import hostspans


def read(record, trace):
    compiles = [end - start for name, start, end in hostspans.program_timeline() if name == "sav:fit/compile"]
    return compiles[0] if compiles else None
