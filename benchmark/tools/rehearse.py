#!/usr/bin/env python3
"""Rehearsal without a chip: compile a train cell's step for a described
``v5e:2x2`` and print what the TPU's compiler says of it.

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse.py --workload <cell> [--per-chip-batch N]

The program picks its attention backend and its kernels' mode from the live
backend's name, which is the CPU here; the tool steers both to what a TPU
would see, as ``tests/test_tpu_compile.py`` does (without that, the first
four-chip run of PR 23 met a kernel the rehearsal had not).

Nothing runs, so this gives no time and no result: it shows whether the
step compiles and fits (``memory_analysis`` bytes per device), which
collectives the compiler put in and whether a kernel is there. A compile
that passes is not a chip run. The HLO text goes to
``benchmark/out/rehearse/<cell>.hlo.txt``.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(HERE, "tools")]
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--per-chip-batch", type=int, default=None,
                        help="try another batch than the configuration's")
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from benchmark import run as harness, schema
    from benchmark.drivers import train_fit
    from sav_tpu.ops import _backend, attention
    from sav_tpu.train import Trainer

    attention._on_tpu = lambda: True
    _backend.default_interpret = lambda: False
    bench = schema.load(ROOT)
    cell = harness.load_cell(bench, args.workload)
    if args.per_chip_batch:
        cell["config"]["train"]["per_chip_batch"] = args.per_chip_batch
    cfg = train_fit.train_config(cell, 0, {})
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    mesh = Mesh(np.asarray(topo.devices[: cell["chips"]]), ("data",))
    trainer = Trainer(cfg, mesh=mesh)

    replicated = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=replicated),
        jax.eval_shape(trainer.init_state),
    )
    b, s = cfg.global_batch_size, cfg.image_size
    batch = {
        "images": jax.ShapeDtypeStruct((s, s, 3, b), jnp.uint8,
                                       sharding=NamedSharding(mesh, P(None, None, None, "data"))),
        "labels": jax.ShapeDtypeStruct((b,), jnp.int32, sharding=NamedSharding(mesh, P("data"))),
    }
    rng = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=replicated)
    compiled = trainer.compile_train_step(state, batch, rng)
    m = compiled.memory_analysis()
    text = compiled.as_text()
    out = os.path.join(HERE, "out", "rehearse")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, args.workload + ".hlo.txt"), "w") as f:
        f.write(text)
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
        "all_reduce_start": text.count(" all-reduce-start("),
        "tpu_custom_call": text.count("tpu_custom_call"),
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
