#!/usr/bin/env python3
"""How a cell's seeded tokens route where THE ROUTER READS THE BLOCK'S INPUT
(``smallthinker``): rows per HELD expert (min, mean, max, total) in every
layer and over the step, for the first step's row and the seed's weights as
the run makes them, through the program's own forward pass at the cell's size
and precision.  ``tools/routing_census.py`` captures a module named
``pre_mlp_norm`` and calls the sigmoid routing; here each LAYER's input is
captured (the embedding's output, then each layer's) and put through the
family's own published routing (``fam.reference.routing``).

    python3 benchmark/tools/routing_census_block_input.py \\
        --workload smallthinker.train-16k --seeds 1 2 3

Runs where the program runs (on the chip through the chip tool: the counts
depend on the bfloat16 forward pass).  Prints one JSON line a seed that names
the device.
"""
import argparse
import dataclasses
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
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    loaded = harness.load_cell(ROOT, args.workload)
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    fam = harness.load_module(ROOT, "families", cfg["family"])
    harness.place_compile_cache(ROOT)
    import apex_tpu.amp as amp

    job = train.Job(cfg, traffic, fam, 1)
    amp_ = amp.initialize(cfg["precision"]["opt_level"])
    rcfg = fam.reference_config(cfg)
    # a recomputed block hands out no intermediates: the forward pass alone
    pcfg = dataclasses.replace(
        fam.program_config(cfg, amp_.policy.compute_dtype), remat_policy="none")
    model = fam.program_model(pcfg)
    lo, hi = pcfg.experts_held
    layers = [f"layer_{i}" for i in range(pcfg.num_layers)]

    @jax.jit
    def census(w_key, ids):
        params = amp_.cast_model(fam.to_program(job.make_weights(w_key), cfg))
        _, state = model.apply(
            {"params": params}, ids, deterministic=True,
            capture_intermediates=lambda m, _: m.name in layers)
        x = params["embed"]["embedding"][ids].astype(amp_.policy.compute_dtype)
        out = {}
        for name in layers:
            logits = jnp.matmul(
                x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                params[name]["moe"]["router"].astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            sel, _ = fam.reference.routing(logits, rcfg)
            out[name] = jnp.bincount(sel.reshape(-1), length=pcfg.num_experts)
            x = state["intermediates"][name]["__call__"][0]
        return out

    dev = jax.devices()[0]
    for seed in args.seeds:
        ids, _ = job.batches(seed, 0)
        counts = jax.device_get(census(job.keys(seed)[0], ids[0]))
        line = {"workload": args.workload, "seed": seed,
                "device": f"{dev.platform} {dev.device_kind}",
                "tokens": int(ids[0].size), "held": [lo, hi], "layers": {}}
        for name, c in counts.items():
            c = np.asarray(c)
            mine = c[lo:hi]
            line["layers"][name] = {
                "rows_held_min": int(mine.min()), "rows_held_mean": float(mine.mean()),
                "rows_held_max": int(mine.max()), "rows_held_total": int(mine.sum()),
                "rows_all_experts_min": int(c.min()),
                "rows_all_experts_max": int(c.max())}
        line["rows_held_a_step"] = sum(
            v["rows_held_total"] for v in line["layers"].values())
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
