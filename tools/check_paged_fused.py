"""Live-TPU probe for the default-off fused paged-attention kernel.

The ROADMAP carried-risk rule: every new Pallas serving kernel defaults
off until a live-TPU session runs it.  ``paged_fused`` is the fused
serving read (page-table gather + int8 dequant + attention in one
kernel, `APEX_TPU_PAGED_FUSED`).  Checked: Mosaic-compiled kernel vs the
jitted materializing reference across dtype (fp32 / bf16 / int8 pages) x
masked (tree-verify block) x T (decode / spec-verify widths).  Tier-1
pins BITWISE parity in interpret mode; on hardware the compiled Mosaic
program may legally differ from XLA's fusion by float reassociation, so
this probe gates on a few-ulp tolerance and reports the max deviation
per grid point.

(The script once also probed an aliased-HBM dq accumulation of the flash
backward; that route is gone — the backward keeps what it accumulates in
VMEM, ``apex_flash_bwd_sweep`` — and its probe with it.)

Run on the TPU machine:

    python tools/check_paged_fused.py
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp
import numpy as np

import apex_tpu.ops.attention as attn

REPEATS = 5


# -- paged_fused: the ISSUE 20 fused serving read -----------------------

def check_paged_fused() -> int:
    rng = np.random.RandomState(1)
    fails = 0
    b, h, d, page_len, n_pages_per = 2, 4, 64, 128, 4
    num_pages = 1 + b * n_pages_per
    s_total = n_pages_per * page_len

    def mk(shape, dtype=np.float32):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3,
                           dtype)

    table = np.zeros((b, n_pages_per), np.int32)
    table[0] = np.arange(1, 1 + n_pages_per)
    table[1] = np.arange(1 + n_pages_per, 1 + 2 * n_pages_per)
    table = jnp.asarray(table)
    lengths = jnp.asarray([s_total - 7, s_total // 2], jnp.int32)

    for dtype in ("fp32", "bf16", "int8"):
        pool = mk((num_pages, h, page_len, d))
        pool_v = mk((num_pages, h, page_len, d))
        ksc = vsc = None
        if dtype == "bf16":
            pool, pool_v = pool.astype(jnp.bfloat16), pool_v.astype(
                jnp.bfloat16)
        elif dtype == "int8":
            pool, ksc = attn.quantize_kv(pool)
            pool_v, vsc = attn.quantize_kv(pool_v)
        for t, masked in ((1, False), (4, False), (5, True)):
            q = mk((b, h, t, d))
            kn = mk((b, h, t, d))
            vn = mk((b, h, t, d))
            positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)
            bm = None
            if masked:
                # the tree-verify shape: root + two 2-deep branches
                bv = [-1, 0, 0, 1, 1]
                bm = jnp.asarray(
                    [[bv[k_] < 0 or bv[k_] == bv[q_] for k_ in range(t)]
                     for q_ in range(t)])
            kw = dict(positions=positions, pool_k=pool, pool_v=pool_v,
                      page_table=table, cache_lengths=lengths,
                      pool_k_scale=ksc, pool_v_scale=vsc, block_mask=bm)
            ref = jax.jit(
                lambda q, kn, vn: attn.paged_cached_attention(
                    q, kn, vn, use_fused=False, **kw)
            )(q, kn, vn)
            for rep in range(REPEATS):
                got = jax.jit(
                    lambda q, kn, vn: attn.paged_fused_attention(
                        q, kn, vn, **kw)
                )(q, kn, vn)
                a = np.asarray(got, np.float32)
                r = np.asarray(ref, np.float32)
                tol = 1e-5 if dtype == "fp32" else 1e-2
                if not np.allclose(a, r, atol=tol, rtol=tol):
                    fails += 1
                    print(f"FAIL {dtype} t={t} masked={masked} rep={rep}: "
                          f"max|diff|={np.abs(a - r).max():.4g}")
                    break
            else:
                print(f"ok    {dtype} t={t} masked={masked} "
                      f"max|diff|={np.abs(np.asarray(got, np.float32) - r).max():.3g} "
                      f"({REPEATS} reps)")
    return fails


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--allow-cpu", action="store_true",
                    help="skip the TPU-backend assertion (smoke runs "
                    "the interpret path; NOT a hardware validation)")
    args = ap.parse_args(argv)
    if not args.allow_cpu:
        assert jax.default_backend() == "tpu", (
            f"backend is {jax.default_backend()!r} — this probe "
            "validates Mosaic lowering on real TPU (use --allow-cpu "
            "for an interpret-mode smoke only)")
    fails = check_paged_fused()
    print(f"{'PASS' if fails == 0 else 'FAIL'} paged_fused"
          f"{'' if fails == 0 else f' ({fails} failures)'}")
    return 1 if fails else 0


if __name__ == "__main__":
    sys.exit(main())
