"""Percent of their roofline the routed experts' grouped matmuls reach: the
least seconds the chip could take for them over the seconds of the
operations under an expert layer's ``experts/fc1`` and ``experts/fc2`` scopes
(device_trace joined with the compiled step's ``op_name`` scopes), whether
those are XLA's ragged dot or a Mosaic kernel, with what the compiler fused
onto them.

The least seconds of one routed layer application is, forward and backward
each, the larger of the FLOPs over the chip's bf16 peak and the HBM bytes
over its bandwidth (``benchmark/device.py``), from
``benchmark/flops/<family>.py`` at the EXPECTED routings on the experts
held: ``tokens x k x held / published`` (the record carries no count of the
step's own; at the seeded weights the program's ``moe_held_share`` reads the
expectation to a few percent). Every routed layer of the cut and the
multi-token-prediction module's counts one forward, one more where the
operations are recomputed under remat, and one backward. Rows that belong to
no held expert are in the seconds and not in the count. Nothing to read where
no operation lies under such a scope."""

import importlib

from benchmark import device, stepscopes

LABEL = "rematted_computation"


def in_grouped_matmul(op_name: str) -> bool:
    labels = stepscopes.scopes_of(op_name)
    return any(
        a == "moe" and b == "experts" and c in ("fc1", "fc2")
        for a, b, c in zip(labels, labels[1:], labels[2:])
    )


def read(record, trace):
    scopes, steps = record.get("hlo_scopes"), record["spans"].get("traced_steps")
    config = record.get("config") or {}
    if trace is None or not scopes or not steps or "flops" not in config:
        return None
    if record["device"]["platform"] != "tpu":
        return None
    ours = {name: scopes[name] for name in trace["op_seconds"] if in_grouped_matmul(scopes.get(name, ""))}
    seconds = sum(trace["op_seconds"][name] for name in ours)
    if not seconds:
        return None
    counts = importlib.import_module("benchmark.flops." + config["flops"])
    tokens = record["counters"]["images_per_step_per_chip"] * config["sequence_length"]
    peaks = device.peaks(record["device"]["kind"])
    floor = counts.grouped_matmul_floor_seconds(
        config, tokens * counts.held_routings_per_token(config),
        peaks["bf16_flops_per_s"], peaks["hbm_bytes_per_s"],
    )
    layers = config["num_layers"] - config["first_k_dense_replace"] + config["num_nextn_predict_layers"]
    forwards = 2 if any(LABEL in stepscopes.scopes_of(scope) for scope in ours.values()) else 1
    least = steps * layers * (forwards * floor["forward"] + floor["backward"])
    return 100.0 * least / seconds
