#!/usr/bin/env python3
"""How a cell's seeded tokens route: rows per HELD expert (min, mean, max,
total) in every expert layer, for the first step's row and the seed's weights
as the run makes them, through the program's own forward pass at the cell's
size and precision (each expert layer's input is captured and put through the
layer's own ``sigmoid_topk_routing``).

    python3 benchmark/tools/routing_census.py --workload trinity-mini.train-8k --seed 1

Runs where the program runs (on the chip through the chip tool: the counts
depend on the bfloat16 forward pass).  Prints one JSON line that names the
device.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    sys.path.insert(0, ROOT)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmark import harness
    from benchmark.runners import train

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    args = ap.parse_args(argv)
    loaded = harness.load_cell(ROOT, args.workload)
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    fam = harness.load_module(ROOT, "families", cfg["family"])
    harness.place_compile_cache(ROOT)
    import apex_tpu.amp as amp
    from apex_tpu.parallel.moe import sigmoid_topk_routing

    job = train.Job(cfg, traffic, fam, 1)
    amp_ = amp.initialize(cfg["precision"]["opt_level"])
    pcfg = fam.program_config(cfg, amp_.policy.compute_dtype)
    model = fam.program_model(pcfg)
    lo, hi = pcfg.experts_held

    @jax.jit
    def census(w_key, ids):
        params = amp_.cast_model(fam.to_program(job.make_weights(w_key), cfg))
        _, state = model.apply({"params": params}, ids, deterministic=True,
                               capture_intermediates=lambda m, _: m.name == "pre_mlp_norm")
        out = {}
        for name, layer in sorted(state["intermediates"].items()):
            if "moe" not in params[name]:
                continue
            x = layer["pre_mlp_norm"]["__call__"][0]
            x = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
            moe = params[name]["moe"]
            logits = jnp.matmul(x, moe["router"].astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            sel, _ = sigmoid_topk_routing(
                logits, moe["expert_bias"], pcfg.num_experts_per_tok,
                pcfg.route_norm, pcfg.route_scale)
            out[name] = jnp.bincount(sel.reshape(-1), length=pcfg.num_experts)
        return out

    ids, _ = job.batches(args.seed, 0)
    counts = jax.device_get(census(job.keys(args.seed)[0], ids[0]))
    dev = jax.devices()[0]
    line = {"workload": args.workload, "seed": args.seed,
            "device": f"{dev.platform} {dev.device_kind}",
            "tokens": int(ids[0].size), "held": [lo, hi], "layers": {}}
    for name, c in counts.items():
        mine = np.asarray(c)[lo:hi]
        line["layers"][name] = {
            "rows_held_min": int(mine.min()), "rows_held_mean": float(mine.mean()),
            "rows_held_max": int(mine.max()), "rows_held_total": int(mine.sum()),
            "rows_all_experts_max": int(np.asarray(c).max())}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
