#!/usr/bin/env python3
"""Rehearsal without a chip: what a ``ServeEngine`` holds on one chip.

    JAX_PLATFORMS=cpu python3 benchmark/tools/rehearse_serve.py --config <name> [--buckets 8 32 256]
        [--model-name <registry name>] [--image-size N]

Compiles the engine's own inference program (``build_infer_fn`` under
``digested_infer_fn``, as ``ServeEngine`` jits it) for a described
``v5e:2x2`` at each bucket size, from the configuration's file, and prints
the bytes ``memory_analysis`` counts on the device: the resident float32
weights (the program's arguments) plus the bucket's temporaries. This is
the figure behind PERF.md's statement that no serve cell of these
configurations reaches the contract's floor of a quarter of the chip's
memory. ``--model-name`` and ``--image-size`` size a deployment that is not
a configuration yet (a larger model of the registry, a finer resolution)
with the named configuration's other keys. Nothing runs: no time, no
result, and not a chip run.
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
    parser.add_argument("--config", required=True, help="a configuration's name in BENCHMARK.json")
    parser.add_argument("--buckets", type=int, nargs="+", default=[32])
    parser.add_argument("--model-name", default=None, help="another model of the program's registry")
    parser.add_argument("--image-size", type=int, default=None)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from benchmark import schema
    from sav_tpu.models import create_model
    from sav_tpu.serve.engine import build_infer_fn
    from sav_tpu.serve.quality import digested_infer_fn

    bench = schema.load(ROOT)
    entry = next(c for c in bench["configs"] if c["name"] == args.config)
    with open(os.path.join(ROOT, entry["file"])) as f:
        config = json.load(f)
    dtype = jnp.dtype(config["compute_dtype"])
    size = args.image_size or config["image_size"]
    model_name = args.model_name or config["model_name"]
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    model = create_model(model_name, num_classes=config["num_classes"], dtype=dtype)
    params = jax.eval_shape(
        lambda rng: model.init(
            {"params": rng}, jnp.zeros((2, size, size, 3), dtype), is_training=False
        )["params"],
        jax.random.PRNGKey(0),
    )
    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), params
    )
    infer = jax.jit(digested_infer_fn(build_infer_fn(model, dtype)))
    for bucket in args.buckets:
        batch = {
            "images": jax.ShapeDtypeStruct((bucket, size, size, 3), jnp.uint8, sharding=one_chip),
            "valid": jax.ShapeDtypeStruct((bucket,), jnp.float32, sharding=one_chip),
        }
        m = infer.lower(params, {}, batch).compile().memory_analysis()
        print(json.dumps({
            "config": args.config,
            "model_name": model_name,
            "image_size": size,
            "described": "v5e:2x2, one chip",
            "bucket": bucket,
            "argument_bytes": m.argument_size_in_bytes,
            "output_bytes": m.output_size_in_bytes,
            "temp_bytes": m.temp_size_in_bytes,
            "bytes_on_device": m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes,
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
