#!/usr/bin/env python3
"""How often the program and the float32 reference pick different experts,
and how large the routed branch is beside the shared expert, for a cell of
the expert family.

    python3 benchmark/tools/routing_agreement.py --workload joyai.train_ep16_4k --seed <n>

Top-k of 256 scores is discrete: a bfloat16 hidden state flips some
selections against the float32 reference, and the selection-bias update
feeds a flip forward. The cell's comparison hands neither side the other's
routing, so its limits have to leave room for the flips; this tool counts
them. One process, on the cell's chip:

1. the program's forward (the cell's ``TrainConfig``, no remat) on the first
   pool batch at the seeded weights, every router's selections captured;
2. ``Trainer.fit`` for ``--steps - 1`` updates, then the same forward on the
   next batch with the trained weights and the stepped bias;
3. the reference's selections at the same two points (``follow_steps`` for
   the updates), and at the seeded weights the norm of the routed experts'
   part of a layer's output over the shared expert's.

Prints, a routed layer (the module's last): the share of (token, slot)
selections of the program that the reference did not make. It also prints the parameters' change after the followed updates against the
reference's, by group of leaves. The benchmark's own runs never run this;
PERF.md quotes its output.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != os.path.join(HERE, "tools")]
sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--steps", type=int, default=3)
    parser.add_argument("--no-chip", action="store_true", help="a rehearsal on the CPU at a toy size")
    args = parser.parse_args(argv)

    import threading

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import device, run as harness, schema, weights
    from benchmark.drivers import train_tokens_fit as driver
    from benchmark.drivers.train_fit import PoolFeed
    from sav_tpu.models.layers import moe

    bench = schema.load(ROOT)
    cell = harness.load_cell(bench, args.workload)
    if not args.no_chip:
        device.require_chips(cell["chips"])
        harness.place_compile_cache()
    config, reference = cell["config"], driver.family(cell)
    trainer, state, pool = driver.build(cell, args.seed, {})
    forward_model = trainer.model.clone(remat=False)

    @jax.jit
    def program_selections(params, batch_stats, tokens):
        _, captured = forward_model.apply(
            {"params": params, "batch_stats": batch_stats}, tokens[:, :-1], is_training=False,
            targets=tokens[:, 1:], mutable=["intermediates", "losses"],
            capture_intermediates=lambda module, _: isinstance(module, moe._Router),
        )
        found = jax.tree_util.tree_leaves_with_path(captured["intermediates"])
        # a router returns (scores, chosen, weights): keep the int32 leaf, in the model's order
        return [leaf for path, leaf in found if leaf.dtype == jnp.int32]

    def reference_selections(params, bias, tokens):
        """Per routed layer ``[B, S, k]``, and layer 1's routed / shared norms."""
        eps = config["rms_norm_eps"]

        @jax.jit
        def one(params, bias, row):
            with jax.default_matmul_precision("highest"):
                inputs, targets = row[:-1], row[1:]
                table = params["embed"]["embedding"]
                h, picked, ratio, r = table[inputs], [], None, 0

                def routed_input(h, p):
                    mid = h + reference.latent_attention(
                        reference.rms_norm(h, p["attn_norm"], eps), p["LatentSelfAttentionBlock_0"], config)
                    return reference.rms_norm(mid, p["ffn_norm"], eps)

                for i in range(config["num_layers"]):
                    p = params[f"layer_{i}"]
                    if "moe" in p:
                        x = routed_input(h, p)
                        picked.append(reference.route(x, p["moe"], bias[r], config)[1])
                        if ratio is None:
                            shared = reference.mlp(x, p["moe"]["shared"])
                            both = reference.expert_layer(x, p["moe"], bias[r], config)[0]
                            ratio = jnp.linalg.norm(both - shared) / jnp.linalg.norm(shared)
                    h, _, _ = reference.layer(h, p, bias[r] if "moe" in p else None, config)
                    r += "moe" in p
                mtp = params["mtp"]
                both = jnp.concatenate([
                    reference.rms_norm(h, mtp["h_norm"], eps),
                    reference.rms_norm(table[targets], mtp["e_norm"], eps)], axis=-1)
                x = routed_input(both @ mtp["eh_proj"]["kernel"], mtp["layer"])
                picked.append(reference.route(x, mtp["layer"]["moe"], bias[r], config)[1])
                return picked, ratio

        rows = [one(params, bias, row) for row in tokens]
        layers = [jnp.stack([r[0][i] for r in rows]) for i in range(len(rows[0][0]))]
        return layers, float(np.mean([float(r[1]) for r in rows]))

    def disagreement(program, ref) -> list:
        out = []
        for ours, theirs in zip(program, ref):
            ours = np.asarray(ours).reshape(-1, ours.shape[-1])
            theirs = np.asarray(theirs).reshape(-1, theirs.shape[-1])
            same = (ours[:, :, None] == theirs[:, None, :]).any(axis=-1)
            out.append(float(1.0 - same.mean()))
        return out

    report = {"workload": args.workload, "seed": args.seed}
    first = np.asarray(jax.device_get(pool[0]["tokens"]))
    program_first = jax.device_get(program_selections(state.params, state.batch_stats, pool[0]["tokens"]))
    for k in range(args.steps - 1):
        state, _ = trainer.fit(PoolFeed(pool, k, threading.Event()), num_steps=k + 1, state=state)
    later = np.asarray(jax.device_get(pool[args.steps - 1]["tokens"]))
    program_later = jax.device_get(program_selections(state.params, state.batch_stats, pool[args.steps - 1]["tokens"]))
    program_bias = np.asarray(jax.device_get(state.batch_stats["select_bias"]))
    program_params = [np.asarray(x) for x in jax.device_get(jax.tree.leaves(state.params))]
    abstract = jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), state.params)
    cfg = trainer.config
    del state, pool, trainer

    params0 = weights.draw_params(abstract, args.seed)
    bias0 = reference.initial_bias(config)
    ref_first, ratio = reference_selections(params0, bias0, jnp.asarray(first))
    report["routed_over_shared_output_norm.layer_1"] = ratio
    report["selections_differ.step1"] = disagreement(program_first, ref_first)
    hp = {k: getattr(cfg, k) for k in (
        "base_lr", "global_batch_size", "lr_scaling_divisor", "num_train_images",
        "warmup_epochs", "num_epochs", "end_lr", "weight_decay", "clip_grad_norm",
    )}
    batches = driver.draw_pool(cell, cfg, args.seed)[: args.steps - 1]
    followed = reference.follow_steps(params0, batches, hp, config)
    flat, treedef = jax.tree_util.tree_flatten(params0)
    trained = jax.tree_util.tree_unflatten(treedef, [p + c for p, c in zip(flat, followed["change"])])
    ref_later, _ = reference_selections(trained, jnp.asarray(followed["select_bias"]), jnp.asarray(later))
    report[f"selections_differ.step{args.steps}"] = disagreement(program_later, ref_later)
    # The parameters' change after the followed updates, as the cell compares it
    # (norm of the difference over the reference's norm), by group of leaves:
    # what the flips cost the routed experts' leaves beside the rest.
    paths = [jax.tree_util.keystr(p) for p, _ in jax.tree_util.tree_leaves_with_path(abstract)]
    start = [np.asarray(x) for x in jax.device_get(flat)]
    groups = {"routed_experts": lambda path: "experts" in path, "router": lambda path: "route" in path,
              "embedding_and_head": lambda path: "embed" in path or "lm_head" in path}
    sums = {name: [0.0, 0.0, 0] for name in (*groups, "everything_else", "all")}
    for path, ours, theirs, before in zip(paths, program_params, followed["change"], start):
        diff = float(np.sum(np.square((ours - before).astype(np.float64) - theirs)))
        size = float(np.sum(np.square(theirs.astype(np.float64))))
        name = next((n for n, belongs in groups.items() if belongs(path)), "everything_else")
        for key in (name, "all"):
            sums[key][0] += diff
            sums[key][1] += size
            sums[key][2] += theirs.size
    report[f"update_rel_diff_after_{args.steps - 1}_updates"] = {
        name: {"rel_diff": (d / n) ** 0.5 if n else None, "parameters": count} for name, (d, n, count) in sums.items()
    }
    report["select_bias_entries_that_differ"] = float(np.mean(program_bias != followed["select_bias"]))
    report["select_bias_abs_max"] = float(np.max(np.abs(program_bias)))
    print(json.dumps(report), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
