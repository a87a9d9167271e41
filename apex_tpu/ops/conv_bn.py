"""Fused 1x1-conv + BatchNorm Pallas kernels — the RN50 HBM-diet path.

ref context: apex frames O3+keep_batchnorm_fp32 as RN50's speed-of-light
(examples/imagenet/README.md:74-86) and ships NHWC BN with fused
relu/add epilogues (apex/contrib/csrc/groupbn/, csrc/welford.cu
batchnorm_add_relu) because BN's extra memory passes around every conv
are the bottleneck.  On v5e the profile is the same (PERF.md: the RN50
step is HBM-bound on the BN/elementwise chain, not the convs), and the
#1 remedy named there is exactly this fusion.

A 1x1 convolution in NHWC is a matmul over (N*H*W, C) — RN50 bottleneck
blocks are 2/3rds 1x1 convs (conv1, conv3, downsample).  Two kernels:

- :func:`matmul_stats` — ``y = x @ w`` that ALSO writes per-column
  ``(sum(y), sum(y^2))`` as an in-register epilogue while the output
  block is still in VMEM.  Kills the separate BN-stats read pass over
  the conv output (1 full activation pass per BN layer).
- :func:`bn_relu_matmul` — ``z = relu((y - mean) * rstd * gamma + beta)
  @ w`` with the normalize+relu applied to each LHS block in-register
  between the DMA and the MXU dot.  Kills the normalize write AND the
  next conv's re-read of the normalized tensor (2 passes per BN layer).
  Optionally emits the stats epilogue for ITS output too.

Backward is plain jnp inside a ``custom_vjp``: the backward pass is two
matmuls (dw, dx) plus elementwise recompute of the normalized LHS — XLA
fuses the recompute into the dw matmul's operand read, which is already
memory-optimal, so Pallas buys nothing there.  Residuals are only the
original inputs (no normalized copies are ever materialized anywhere).

SyncBatchNorm composition: stats come back as (sum, sqsum, count-free)
partials — psum them over the data axis exactly like
``parallel.sync_batchnorm._bn_stats`` does, then feed (mean, rstd) to
the next ``bn_relu_matmul``.

These kernels are NOT wired into models/resnet.py: the measured attempt
(round 3, on another machine; the script and its record are in git
history before PR 28) landed at
~parity with XLA's own fusion at RN50 shapes on v5e, so the model keeps
the plain XLA path.  The kernels stay as tested library building blocks
for K-wide memory-bound matmul chains.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._common import pallas_call as _pallas_call
from jax.experimental.pallas import tpu as pltpu

_LANE = 128

# default tiles: (256, 512, 512) keeps lhs+rhs+acc well under VMEM while
# the MXU sees full 128x128 systolic tiles
DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 512
DEFAULT_BLOCK_K = 512


from apex_tpu.ops._common import auto_block as _blk  # shared heuristic


def _shapes_ok(m: int, k: int, n: int) -> bool:
    return m % _LANE == 0 and k % _LANE == 0 and n % _LANE == 0


def _check_forced(use_pallas, m, k, n, bm, bk, bn):
    """Explicit ``use_pallas=True`` with dims the resolved blocks cannot
    tile would yield a zero-iteration grid (silently unwritten output) —
    reject it instead of returning garbage."""
    if use_pallas and (m % bm or k % bk or n % bn):
        raise ValueError(
            f"use_pallas=True but shapes ({m}, {k}) x ({k}, {n}) are not "
            f"divisible by the resolved blocks (bm={bm}, bk={bk}, bn={bn}); "
            "pass use_pallas=None to auto-fall-back to the jnp path"
        )


# ---------------------------------------------------------------------------
# kernel bodies
# ---------------------------------------------------------------------------

def _matmul_stats_kernel(
    x_ref, w_ref, y_ref, s_ref, ss_ref, acc_scr, s_scr, ss_scr,
    *, nm: int, nk: int,
):
    mi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init_acc():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((mi == 0) & (ki == 0))
    def _init_stats():
        s_scr[:] = jnp.zeros_like(s_scr)
        ss_scr[:] = jnp.zeros_like(ss_scr)

    acc_scr[:] += jax.lax.dot_general(
        x_ref[...], w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _epilogue():
        yc = acc_scr[:].astype(y_ref.dtype)
        y_ref[...] = yc
        # stats epilogue while the block is still in VMEM — no extra
        # HBM read; computed from the STORED (cast) values so the
        # stats describe exactly the tensor the next layer reads
        y = yc.astype(jnp.float32)
        s_scr[:] += jnp.sum(y, axis=0, keepdims=True)
        ss_scr[:] += jnp.sum(y * y, axis=0, keepdims=True)
        @pl.when(mi == nm - 1)
        def _write_stats():
            s_ref[...] = s_scr[:]
            ss_ref[...] = ss_scr[:]


def _bn_relu_matmul_kernel(
    x_ref, mean_ref, rstd_ref, gamma_ref, beta_ref, w_ref,
    y_ref, s_ref, ss_ref, acc_scr, s_scr, ss_scr,
    *, nm: int, nk: int, relu: bool,
):
    mi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init_acc():
        acc_scr[:] = jnp.zeros_like(acc_scr)

    @pl.when((mi == 0) & (ki == 0))
    def _init_stats():
        s_scr[:] = jnp.zeros_like(s_scr)
        ss_scr[:] = jnp.zeros_like(ss_scr)

    # normalize+activation applied to the LHS block in-register, between
    # the DMA and the MXU dot — the normalized tensor never exists in HBM
    x = x_ref[...].astype(jnp.float32)
    x = (x - mean_ref[...]) * (rstd_ref[...] * gamma_ref[...]) + beta_ref[...]
    if relu:
        x = jnp.maximum(x, 0.0)
    acc_scr[:] += jax.lax.dot_general(
        x.astype(w_ref.dtype), w_ref[...], (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(ki == nk - 1)
    def _epilogue():
        yc = acc_scr[:].astype(y_ref.dtype)
        y_ref[...] = yc
        y = yc.astype(jnp.float32)  # stats of the STORED values
        s_scr[:] += jnp.sum(y, axis=0, keepdims=True)
        ss_scr[:] += jnp.sum(y * y, axis=0, keepdims=True)
        @pl.when(mi == nm - 1)
        def _write_stats():
            s_ref[...] = s_scr[:]
            ss_ref[...] = ss_scr[:]


# ---------------------------------------------------------------------------
# pallas_call plumbing (shared grid: (n_blocks, m_blocks, k_blocks) — n
# OUTER so the stats accumulator for an n-block sees its m-blocks
# consecutively; k inner for the dot accumulation)
# ---------------------------------------------------------------------------

def _grid_specs(m, k, n, bm, bk, bn):
    nm, nk, nn = m // bm, k // bk, n // bn
    x_spec = pl.BlockSpec((bm, bk), lambda j, i, t: (i, t))
    w_spec = pl.BlockSpec((bk, bn), lambda j, i, t: (t, j))
    y_spec = pl.BlockSpec((bm, bn), lambda j, i, t: (i, j))
    stat_spec = pl.BlockSpec((1, bn), lambda j, i, t: (0, j))
    kparam_spec = pl.BlockSpec((1, bk), lambda j, i, t: (0, t))
    return (nn, nm, nk), x_spec, w_spec, y_spec, stat_spec, kparam_spec


def _matmul_stats_fwd(x, w, bm, bn, bk):
    m, k = x.shape
    n = w.shape[1]
    grid, x_spec, w_spec, y_spec, stat_spec, _ = _grid_specs(
        m, k, n, bm, bk, bn
    )
    nn, nm, nk = grid
    y, s, ss = _pallas_call(
        functools.partial(_matmul_stats_kernel, nm=nm, nk=nk),
        name="apex_conv_bn_matmul_stats",
        grid=grid,
        in_specs=[x_spec, w_spec],
        out_specs=[y_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
    )(x, w)
    return y, s[0], ss[0]


def _bn_relu_matmul_fwd(x, mean, rstd, gamma, beta, w, bm, bn, bk, relu):
    m, k = x.shape
    n = w.shape[1]
    grid, x_spec, w_spec, y_spec, stat_spec, kparam_spec = _grid_specs(
        m, k, n, bm, bk, bn
    )
    nn, nm, nk = grid
    row = lambda v: v.astype(jnp.float32).reshape(1, k)
    y, s, ss = _pallas_call(
        functools.partial(
            _bn_relu_matmul_kernel, nm=nm, nk=nk, relu=relu,
        ),
        name="apex_conv_bn_relu_matmul",
        grid=grid,
        in_specs=[x_spec, kparam_spec, kparam_spec, kparam_spec,
                  kparam_spec, w_spec],
        out_specs=[y_spec, stat_spec, stat_spec],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), x.dtype),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
            jax.ShapeDtypeStruct((1, n), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bm, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
    )(x, row(mean), row(rstd), row(gamma), row(beta), w)
    return y, s[0], ss[0]


# ---------------------------------------------------------------------------
# custom_vjp wrappers (jnp backward: XLA fuses the recompute into the
# backward matmuls' operand reads — already memory-optimal)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _matmul_stats(x, w, bm, bn, bk, use_pallas):
    # stats are ALWAYS computed at this layer (their epilogue cost is two
    # (1, N) vectors); the public API decides whether to return them —
    # so kernel and fallback agree and the bwd fold is unconditional
    if not use_pallas:
        y = (x.astype(jnp.float32) @ w.astype(jnp.float32)).astype(x.dtype)
        y32 = y.astype(jnp.float32)
        return y, jnp.sum(y32, axis=0), jnp.sum(y32 * y32, axis=0)
    return _matmul_stats_fwd(x, w, bm, bn, bk)


def _matmul_stats_fwd_rule(x, w, bm, bn, bk, use_pallas):
    out = _matmul_stats(x, w, bm, bn, bk, use_pallas)
    return out, (x, w, out[0])


def _matmul_stats_bwd_rule(bm, bn, bk, use_pallas, res, cts):
    x, w, y = res
    dy, ds, dss = cts
    # stats cotangents fold into dy: d(sum y)/dy = 1, d(sum y^2)/dy = 2y
    dy32 = (dy.astype(jnp.float32) + ds[None, :]
            + 2.0 * y.astype(jnp.float32) * dss[None, :])
    dx = (dy32 @ w.astype(jnp.float32).T).astype(x.dtype)
    dw = (x.astype(jnp.float32).T @ dy32).astype(w.dtype)
    return dx, dw


_matmul_stats.defvjp(_matmul_stats_fwd_rule, _matmul_stats_bwd_rule)


def _bn_lhs(x, mean, rstd, gamma, beta, relu):
    # params cast to fp32 BEFORE the product — matches the Pallas kernel,
    # which receives fp32-cast rows (see _bn_relu_matmul_fwd's `row`)
    x32 = x.astype(jnp.float32)
    scale = rstd.astype(jnp.float32) * gamma.astype(jnp.float32)
    a = (x32 - mean.astype(jnp.float32)) * scale + beta.astype(jnp.float32)
    return jnp.maximum(a, 0.0) if relu else a


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9, 10))
def _bn_relu_matmul(x, mean, rstd, gamma, beta, w, bm, bn, bk, relu,
                    use_pallas):
    if not use_pallas:
        a = _bn_lhs(x, mean, rstd, gamma, beta, relu)
        y = (a @ w.astype(jnp.float32)).astype(x.dtype)
        y32 = y.astype(jnp.float32)
        return y, jnp.sum(y32, axis=0), jnp.sum(y32 * y32, axis=0)
    return _bn_relu_matmul_fwd(x, mean, rstd, gamma, beta, w, bm, bn, bk,
                               relu)


def _bn_relu_matmul_fwd_rule(x, mean, rstd, gamma, beta, w, bm, bn, bk,
                             relu, use_pallas):
    out = _bn_relu_matmul(x, mean, rstd, gamma, beta, w, bm, bn, bk, relu,
                          use_pallas)
    return out, (x, mean, rstd, gamma, beta, w, out[0])


def _bn_relu_matmul_bwd_rule(bm, bn, bk, relu, use_pallas, res, cts):
    x, mean, rstd, gamma, beta, w, y = res
    dy, ds, dss = cts
    dy32 = (dy.astype(jnp.float32) + ds[None, :]
            + 2.0 * y.astype(jnp.float32) * dss[None, :])
    w32 = w.astype(jnp.float32)
    a = _bn_lhs(x, mean, rstd, gamma, beta, relu)  # recompute; XLA fuses
    da = dy32 @ w32.T
    dw = (a.T @ dy32).astype(w.dtype)
    if relu:
        da = jnp.where(a > 0.0, da, 0.0)
    rstd32 = rstd.astype(jnp.float32)
    gamma32 = gamma.astype(jnp.float32)
    g32 = rstd32 * gamma32
    x32 = x.astype(jnp.float32)
    xc = x32 - mean.astype(jnp.float32)
    dx = (da * g32).astype(x.dtype)
    # cotangents must match the primal dtypes (bf16 BN params get bf16 grads)
    dmean = (-jnp.sum(da, axis=0) * g32).astype(mean.dtype)
    drstd = (jnp.sum(da * xc, axis=0) * gamma32).astype(rstd.dtype)
    dgamma = (jnp.sum(da * xc, axis=0) * rstd32).astype(gamma.dtype)
    dbeta = jnp.sum(da, axis=0).astype(beta.dtype)
    return dx, dmean, drstd, dgamma, dbeta, dw


_bn_relu_matmul.defvjp(_bn_relu_matmul_fwd_rule, _bn_relu_matmul_bwd_rule)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def matmul_stats(
    x: jax.Array,
    w: jax.Array,
    *,
    with_stats: bool = True,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    use_pallas: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``y = x @ w`` plus per-column (sum, sqsum) stats epilogue.

    x: (M, K); w: (K, N).  Returns (y (M, N), sum (N,), sqsum (N,)) with
    stats in fp32 of the STORED y (cast to x.dtype first — so the stats
    describe exactly the tensor the next layer reads, as the reference's
    Welford kernels do).  Divide by M (psum'd for SyncBN) for moments.
    ``with_stats=False`` returns just y (the stats epilogue costs two
    (N,) vectors either way; the flag only picks the return arity).
    """
    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = _blk(m, block_m), _blk(n, block_n), _blk(k, block_k)
    if use_pallas is None:
        from apex_tpu.ops._common import pallas_default

        use_pallas = pallas_default(_shapes_ok(m, k, n))
    else:
        _check_forced(use_pallas, m, k, n, bm, bk, bn)
    out = _matmul_stats(x, w, bm, bn, bk, bool(use_pallas))
    return out if with_stats else out[0]


def bn_relu_matmul(
    x: jax.Array,
    mean: jax.Array,
    rstd: jax.Array,
    gamma: jax.Array,
    beta: jax.Array,
    w: jax.Array,
    *,
    relu: bool = True,
    with_stats: bool = True,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    use_pallas: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``z = relu(bn(x)) @ w`` with the normalize in the LHS load path.

    x: (M, K); per-channel (K,) mean/rstd/gamma/beta; w: (K, N).  The
    normalized activation never touches HBM.  Returns (z, sum, sqsum)
    like :func:`matmul_stats` (just z with ``with_stats=False``).
    """
    m, k = x.shape
    n = w.shape[1]
    bm, bn, bk = _blk(m, block_m), _blk(n, block_n), _blk(k, block_k)
    if use_pallas is None:
        from apex_tpu.ops._common import pallas_default

        use_pallas = pallas_default(_shapes_ok(m, k, n))
    else:
        _check_forced(use_pallas, m, k, n, bm, bk, bn)
    out = _bn_relu_matmul(x, mean, rstd, gamma, beta, w, bm, bn, bk,
                          bool(relu), bool(use_pallas))
    return out if with_stats else out[0]


# ---------------------------------------------------------------------------
# dual-output matmul backward (r4 RN50 experiment)
# ---------------------------------------------------------------------------

def _matmul_bwd_dual_kernel(dy_ref, x_ref, w_ref, dx_ref, dw_ref, dw_scr,
                            *, nm: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_scr[:] = jnp.zeros_like(dw_scr)

    dy = dy_ref[...]
    dx_ref[...] = jax.lax.dot_general(
        dy, w_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    ).astype(dx_ref.dtype)
    dw_scr[:] += jax.lax.dot_general(
        x_ref[...], dy, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(i == nm - 1)
    def _finalize():
        dw_ref[...] = dw_scr[:].astype(dw_ref.dtype)


def matmul_bwd_dual(
    x: jax.Array,
    dy: jax.Array,
    w: jax.Array,
    *,
    block_m: int = 512,
) -> Tuple[jax.Array, jax.Array]:
    """Both cotangents of ``y = x @ w`` from ONE pass over (x, dy).

    dx = dy @ w^T and dw = x^T @ dy share their big operand reads; XLA
    schedules them as two GEMMs that each re-read dy (and read x/w
    separately), so at memory-bound backward-conv shapes (RN50 stage1/2
    1x1 convs, PERF.md r3 profile rows at 15-40 TF/s) the fused pass
    saves up to ~30% of the HBM traffic: read x + dy + w once, write
    dx + dw.  dw accumulates in VMEM fp32 across the M-block grid
    (sequential), dx streams out per block.

    Returns ``(dx, dw)`` with dx in ``x.dtype`` but dw ALWAYS fp32 (the
    VMEM accumulator's dtype — a weight-gradient is normally consumed by
    an fp32 optimizer/master-weight path); a caller wiring this into a
    custom VJP must cast dw to ``w.dtype`` itself if its cotangent
    contract requires it.

    x: (M, K); dy: (M, N); w: (K, N) with K, N small enough that a
    (K, N) fp32 scratch fits VMEM (1x1-conv channel dims).  ``block_m``
    is clamped to gcd(M, block_m) so the grid always covers every row
    (a non-dividing block would silently leave dx/dw tails unwritten);
    M must keep that gcd a multiple of 8.
    """
    import math

    m, k = x.shape
    n = w.shape[1]
    block_m = math.gcd(m, block_m)
    if block_m % 8:
        raise ValueError(
            f"M={m} has no block divisor compatible with TPU sublanes "
            f"(gcd with the requested block is {block_m}, not a multiple "
            "of 8)"
        )
    nm = m // block_m
    dx, dw = _pallas_call(
        functools.partial(_matmul_bwd_dual_kernel, nm=nm),
        name="apex_conv_bn_matmul_bwd",
        grid=(nm,),
        in_specs=[
            pl.BlockSpec((block_m, n), lambda i: (i, 0)),
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_m, k), lambda i: (i, 0)),
            pl.BlockSpec((k, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), x.dtype),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((k, n), jnp.float32)],
    )(dy, x, w)
    return dx, dw
