"""Device milliseconds per step in all-reduce operations, by the HLO
operation names the trace prints (device_trace), per chip and averaged.
Nothing to read where the step has no all-reduce (one chip)."""


def read(record, trace):
    steps = record["spans"].get("traced_steps")
    if trace is None or not steps:
        return None
    seconds = [s for name, s in trace["op_seconds"].items() if name.startswith("all-reduce")]
    return sum(seconds) / steps * 1e3 if seconds else None
