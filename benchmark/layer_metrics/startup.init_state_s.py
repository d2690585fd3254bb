"""Self seconds of ``Trainer.init_state`` before the window opened: the
timeline's ``sav:trainer/init_state`` spans less what the compile log puts
down to them (the initialiser's trace, lowering and compile or cache load,
which ``startup.trace_lower_s``, ``startup.compile_s`` and
``startup.cache_load_s`` hold), so that the start-up metrics add without
overlap: the initialiser's run on the device, the shardings, the dispatch
(program_span; ``benchmark/hostspans.py::program_timeline``,
``benchmark/startuplog.py``). Nothing to read where the program keeps no
timeline or no compile log."""

from benchmark import hostspans, startuplog

SPAN = "sav:trainer/init_state"


def read(record, trace):
    summary = startuplog.before_window(record)
    spans = [
        end - start for name, start, end in hostspans.program_timeline()
        if name == SPAN and end <= record["window_opened_t"]
    ] if summary else []
    if not spans:
        return None
    caused = summary["by_cause"].get(SPAN, {})
    return sum(spans) - sum(caused.get(k, 0.0) for k in ("trace_lower_s", "backend_compile_s", "cache_load_s"))
