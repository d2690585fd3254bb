"""Percent of its stream roofline the residual path reaches: the least seconds
the chip could take to move the hyper-connections' bytes over the seconds of
the operations under an ``hc`` scope (``hc/pre``, ``hc/sinkhorn``, ``hc/post``;
device_trace joined with the compiled step's ``op_name`` scopes), whatever
implements them: XLA's fusions or a kernel.

The bytes are ``benchmark/flops/<family>.py``'s count, the same for every
implementation: per sublayer application and token the streams read once, the
sublayer's result read once and the streams written once, in the compute
dtype; once more where the trace holds recomputed ``hc`` operations; twice
that for the backward; over the chip's HBM bandwidth (``benchmark/device.py``).
Bytes an implementation moves beyond that are in the seconds and not in the
count, so the share cannot pass 100%. Nothing to read where no operation lies
under such a scope, or the family's file counts no such bytes."""

import importlib

from benchmark import device, stepscopes

LABEL, RECOMPUTED = "hc", "rematted_computation"


def read(record, trace):
    scopes, steps = record.get("hlo_scopes"), record["spans"].get("traced_steps")
    config = record.get("config") or {}
    if trace is None or not scopes or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None
    ours = {
        name: stepscopes.scopes_of(scopes.get(name, "")) for name in trace["op_seconds"]
    }
    ours = {name: labels for name, labels in ours.items() if LABEL in labels}
    seconds = sum(trace["op_seconds"][name] for name in ours)
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    if not seconds or not hasattr(counts, "hc_stream_floor_seconds"):
        return None
    tokens = record["counters"]["images_per_step_per_chip"] * config["sequence_length"]
    recomputed = any(RECOMPUTED in labels for labels in ours.values())
    bandwidth = device.peaks(record["device"]["kind"])["hbm_bytes_per_s"]
    least = steps * counts.hc_stream_floor_seconds(config, tokens, recomputed, bandwidth)
    return 100.0 * least / seconds
