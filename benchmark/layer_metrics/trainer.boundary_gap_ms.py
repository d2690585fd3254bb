"""Device idle milliseconds per log boundary of ``fit``: the first chip's
gaps that overlap a ``sav:fit/log_boundary`` span from its ``device_get``'s
return on, over the boundaries wholly inside the traced window
(program_span: the program's own ``TraceAnnotation`` spans in the run's
trace, ``benchmark/hostspans.py``). What the device waits while the host
converts the metrics, books its ledger, calls ``log_fn`` and comes round to
the next dispatch. Per boundary, so the number holds at any log cadence;
``device.idle_share.train`` shows it once in the mix's ten steps. Where
the trace's host and device planes lie apart, the longest gap near the
boundary is taken (``hostspans.boundary_gaps``)."""

from benchmark import hostspans


def read(record, trace):
    found = hostspans.of_this_run() if trace is not None else None
    return hostspans.gap_ms_per_boundary(found) if found else None
