#!/usr/bin/env python3
"""Run a token cell's plain reference alone, at the cell's own size, and print
the most the device held while it ran.

    python3 benchmark/tools/reference_peak.py --workload <cell> --seed <n>

One process that builds no trainer: the layout comes from ``jax.eval_shape``
of the program's model, the weights and the batches from the seed as the
driver draws them, and ``follow_steps`` runs over the followed steps. Prints
``peak_bytes_in_use`` of the device afterwards (the reference's own peak: the
program never ran here), the losses and the seconds. For a reference whose
state is most of a chip: does it fit, and by how much?
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(HERE, "tools")]
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    args = parser.parse_args(argv)

    import jax
    import jax.numpy as jnp

    from benchmark import device, run as harness, schema
    from benchmark.drivers import train_tokens_fit
    from sav_tpu.models import create_model

    bench = schema.load(ROOT)
    cell = harness.load_cell(bench, args.workload)
    device.require_chips(cell["chips"])
    harness.place_compile_cache()
    cfg = train_tokens_fit.train_config(cell, args.seed, {})
    model = create_model(cfg.model_name, num_classes=cfg.num_classes, dtype=jnp.bfloat16, **cfg.model_overrides)
    tokens = jnp.zeros((1, 16), jnp.int32)
    abstract = jax.eval_shape(
        lambda: model.init({"params": jax.random.PRNGKey(0)}, tokens, is_training=False)
    )["params"]
    t0 = time.perf_counter()
    ref = train_tokens_fit.reference_side(cell, cfg, args.seed, abstract)
    stats = jax.local_devices()[0].memory_stats() or {}
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "reference_s": time.perf_counter() - t0,
        "losses": ref["losses"], "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
        "bytes_limit": stats.get("bytes_limit"), "device": device.describe(),
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
