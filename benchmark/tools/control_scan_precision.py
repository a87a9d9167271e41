#!/usr/bin/env python3
"""A second control for a cell of the ``granitemoehybrid`` family, read on
the chip in ONE process: the state-space scan computed in a LOWER precision
than the configuration states, in the program's place.  ``tools/control.py``'s
control (AMP O3) drops the float32 master weights and leaves the scan as it
is; this one keeps AMP as the configuration has it and holds what ``precision``
says the scan keeps in float32 — ``dt``, the log-decays and their running sum,
the decay factors, the states and every sum — in bfloat16 (a product
accumulates in float32 and is rounded on the way out, as the MXU does it).
It reads the cell's first window on each seed against the plain reference,
beside the limits the traffic file has.  The benchmark's own runs never run
this.

    python3 benchmark/tools/control_scan_precision.py \\
        --workload granite-h.train-8k --seeds 11 22

Prints one ``READING`` line a seed and, for each limit, the control's
readings beside it; exits 1 where no limit refuses the control on every seed
(a run is refused by a reading ABOVE a limit; ``fails 3x`` marks those that
clear it three times over, ``tools/control.py``'s own rule).
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
FAMILY = "granitemoehybrid"


def scan_in_bfloat16(x, dt, A, B, C, D, *, chunk=256, dtype="bfloat16"):
    """``apex_tpu.ops.ssd.ssd_scan``'s contract (one group of B and C, whole
    chunks) by the chunked form with every tensor in bfloat16: ``l`` is summed
    in float32 and rounded, each product rounds its result, the states are
    chained in bfloat16.  Differentiated by JAX.  (``dtype`` is for the test
    that holds the same lines in float32 to the token recurrence.)"""
    import jax
    import jax.numpy as jnp

    bf = jnp.dtype(dtype)
    b, s, h, p = x.shape
    if B.shape[2] != 1 or s % chunk:
        raise ValueError(f"one group and whole chunks: got B {B.shape}, "
                         f"{s} tokens in chunks of {chunk}")
    nc = s // chunk
    chunks = lambda t: t.astype(bf).reshape((b, nc, chunk) + t.shape[2:])
    xc, dtc, bc, cc = chunks(x), chunks(dt), chunks(B[:, :, 0]), chunks(C[:, :, 0])
    A, D = A.astype(bf), D.astype(bf)
    l = jnp.cumsum((dtc * A).astype(jnp.float32), axis=2).astype(bf)  # (b, nc, Q, H)
    xdt = xc * dtc[..., None]
    cb = jnp.einsum("bcin,bcjn->bcij", cc, bc)
    lh = jnp.moveaxis(l, 3, 2)                                   # (b, nc, H, Q)
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = jnp.where(lower, jnp.exp(jnp.minimum(
        lh[..., :, None] - lh[..., None, :], 0)), 0).astype(bf)
    intra = jnp.einsum("bchij,bcjhp->bcihp", cb[:, :, None] * decay, xdt)
    last = l[:, :, -1:, :]
    to_end = jnp.exp(jnp.minimum(last - l, 0))
    grown = jnp.einsum("bcjhp,bcjn->bchpn", xdt * to_end[..., None], bc)

    def chain(state, inp):
        grown_c, last_c = inp
        return state * jnp.exp(last_c)[..., None, None] + grown_c, state

    _, starts = jax.lax.scan(
        chain, jnp.zeros((b, h, p, B.shape[3]), bf),
        (jnp.moveaxis(grown, 1, 0), jnp.moveaxis(last[:, :, 0], 1, 0)))
    inter = jnp.einsum("bcin,bchpn->bcihp", cc, jnp.moveaxis(starts, 0, 1))
    o = intra + jnp.exp(l)[..., None] * inter + D[:, None] * xc
    return o.reshape(b, s, h, p).astype(x.dtype)


def main(argv=None, root: str = ROOT) -> int:
    from benchmark import harness

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    loaded = harness.load_cell(root, args.workload)
    cfg, traffic = loaded["cfg"], loaded["traffic"]
    if cfg["family"] != FAMILY:
        ap.error(f"a cell of the {FAMILY} family: {args.workload} is of "
                 f"{cfg['family']}")
    chips = int(loaded["cell"]["chips"])
    device = harness.tpu_or_exit(chips)
    harness.place_compile_cache(root)
    tag = f"[{device['platform']} {device['kind']} x{device['count']}]"
    fam = harness.load_module(root, "families", cfg["family"])
    train = harness.load_module(root, "runners", traffic["kind"])
    job = train.Job(cfg, traffic, fam, chips)

    want = {}
    for seed in args.seeds:
        want[seed] = job.reference(seed)
        print(f"{tag} reference seed {seed}: losses {want[seed]['losses']}",
              flush=True)

    import apex_tpu.models.granite_hybrid as program

    kept, program.ssd_scan = program.ssd_scan, scan_in_bfloat16
    readings = {}
    try:                        # the scan is looked up when the window is traced
        driver, make_carry = job.program(cfg["precision"]["opt_level"])
        for seed in args.seeds:
            carry, got = job.first_window(driver, make_carry(seed), seed)
            del carry
            compared = train.compare(got, want[seed], fam)
            row = {k: v for k, (v, _) in compared.items()}
            for name, value in row.items():
                readings.setdefault(name, []).append(value)
            print(f"{tag} READING " + json.dumps(
                {"variant": "control_scan_bfloat16", "opt_level":
                 cfg["precision"]["opt_level"], "seed": seed, **row,
                 "losses": got["losses"], "reference_losses":
                 want[seed]["losses"], "first_grad_norm":
                 got["first_grad_norm"], "reference_first_grad_norm":
                 want[seed]["first_grad_norm"],
                 "what": {k: w for k, (_, w) in compared.items()}}),
                flush=True)
    finally:
        program.ssd_scan = kept

    refused = []
    for name, values in readings.items():
        limit = traffic["limits"][name]
        line = (f"{name}: control {min(values):.3g}..{max(values):.3g} "
                f"({len(values)} seeds), limit {limit:.3g}")
        if min(values) > limit:
            refused.append(name)
            line += (": refused on every seed"
                     + (", fails 3x" if min(values) >= 3 * limit else ""))
        print(f"{tag} {line}", flush=True)
    if not refused:
        print(f"{tag} the control passes every limit on some seed: no limit "
              f"holds the scan's precision", flush=True)
        return 1
    return 0


if __name__ == "__main__":
    sys.path.insert(0, ROOT)
    sys.exit(main())
