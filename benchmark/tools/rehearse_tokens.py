#!/usr/bin/env python3
"""Rehearsal without a chip, for a token-training cell: compile its step for
a described ``v5e:2x2`` and print what the TPU's compiler says of it.

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_tokens.py --workload <cell> [--set key=json ...]

``rehearse.py`` for the cells of ``drivers/train_tokens_fit.py`` (that tool
names ``train_fit`` and an image batch). Nothing runs: no time, no result.
It shows whether the step compiles and fits (``memory_analysis`` bytes per
device), how many Mosaic kernel calls it holds and under which scopes. The
HLO text goes to ``benchmark/out/rehearse/<cell>.hlo.txt``. ``--set`` puts a
``TrainConfig`` field over the mix's (``--set attention_backend='"xla"'``).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from collections import Counter

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(HERE, "tools")]
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--set", action="append", default=[], metavar="KEY=JSON")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import run as harness, schema, tracered
    from benchmark.drivers import train_tokens_fit
    from sav_tpu.ops import _backend, attention
    from sav_tpu.train import Trainer

    attention._on_tpu = lambda: True
    _backend.default_interpret = lambda: False
    bench = schema.load(ROOT)
    cell = harness.load_cell(bench, args.workload)
    overrides = {k: json.loads(v) for k, v in (item.split("=", 1) for item in args.set)}
    cfg = train_tokens_fit.train_config(cell, 0, overrides)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[: cell["chips"]]), ("data",))
    trainer = Trainer(cfg, mesh=mesh)

    replicated = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(trainer.init_state),
    )
    b, s = cfg.global_batch_size, cell["config"]["sequence_length"]
    batch = {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32, sharding=NamedSharding(mesh, P("data")))}
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    compiled = trainer.compile_train_step(state, batch, rng)
    m = compiled.memory_analysis()
    text = compiled.as_text()
    out = os.path.join(HERE, "out", "rehearse")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.workload + ".hlo.txt"), "w") as f:
        f.write(text)
    calls = train_tokens_fit.kernel_calls(text)
    print(json.dumps({
        "workload": args.workload,
        "described": "v5e:2x2",
        "chips": cell["chips"],
        "global_batch": b,
        "argument_bytes": m.argument_size_in_bytes,
        "output_bytes": m.output_size_in_bytes,
        "alias_bytes": m.alias_size_in_bytes,
        "temp_bytes": m.temp_size_in_bytes,
        "bytes_per_device": m.argument_size_in_bytes + m.output_size_in_bytes
        + m.temp_size_in_bytes - m.alias_size_in_bytes,
        "all_reduce": text.count(" all-reduce("),
        "tpu_custom_call": len(calls),
        "kernel_scopes": Counter(
            tracered._LAYER_INDEX.sub(r"\1_*", scope.split("/", 2)[-1]) for scope in calls.values()
        ),
        "attention_dispatch": attention.snapshot_dispatch_log(),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
