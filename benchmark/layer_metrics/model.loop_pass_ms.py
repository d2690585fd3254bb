"""Device milliseconds one pass of the looped stack takes: the operation
time under the model's ``ut_loop`` scope (the shared layers and the final
norm, every pass, forward, recomputed and backward) per traced step, over
the configuration's ``total_ut_steps`` (device_trace joined with the
compiled step's ``op_name`` scopes). Nothing to read where no operation
carries the scope, or the record names no configuration."""

from benchmark import stepscopes


def read(record, trace):
    scopes, steps = record.get("hlo_scopes"), record["spans"].get("traced_steps")
    passes = (record.get("config") or {}).get("total_ut_steps")
    if trace is None or not scopes or not steps or not passes:
        return None
    seconds = sum(
        s for name, s in trace["op_seconds"].items()
        if "ut_loop" in stepscopes.scopes_of(scopes.get(name, ""))
    )
    return 1e3 * seconds / steps / passes if seconds else None
