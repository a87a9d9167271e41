"""Fused LayerNorm — Pallas TPU kernel with custom VJP + pure-jnp reference.

ref: csrc/layer_norm_cuda.cpp + csrc/layer_norm_cuda_kernel.cu (Welford-based
LN returning (out, mean, invvar); backward HostLayerNormGradient with
two-pass gamma/beta grads) and apex/normalization/fused_layer_norm.py.

Design (TPU-first, not a port):
- Forward: one VMEM pass per row-block; mean/var reduced in fp32 on the VPU,
  normalize + affine fused in the same pass.  The CUDA kernel's Welford
  update is a serial-thread trick; on TPU a vectorized mean/mean-of-squares
  in fp32 is exact enough (tested to 1e-6 vs fp64 numpy) and maps to the VPU.
- Backward: memory-efficient flash-style — stats are *recomputed* from x in
  the backward kernel instead of stored, so the residual is just (x, gamma).
  dgamma/dbeta are XLA reductions over the row axis (the reference's
  two-pass part-size-32 scheme is a CUDA-occupancy artifact; XLA's column
  reduction is already optimal on TPU).
- Rows are processed in blocks of ``block_rows``; inputs with a trailing dim
  not divisible by 128 (the TPU lane width) fall back to the jnp reference —
  same math, still fused by XLA.

Public API:
    layer_norm(x, weight, bias, eps)          — differentiable, picks kernel
    layer_norm_ref(...)                        — pure-jnp reference
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._common import pallas_call as _pallas_call, pad_rows as _pad_rows
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_ROWS = 256



_LANE = 128

# r5: compute dgamma/dbeta as an EPILOGUE of the Pallas dx pass (the
# row-sum accumulator rides the same VMEM residency as the dx math — the
# lamb_stage1 trick without its fatal flaw, because the dx pass is already
# a custom call reading x/dy: no new fusion boundary).  Replaces the XLA
# column reductions, which re-read x AND dy and recompute mean/var/xhat
# (part of the 7.5 ms reduce_sum scope in the r4 BERT profile).  The env
# override makes the end-to-end A/B a subprocess flag flip
# (APEX_TPU_LN_FUSED_DGAMMA=0 restores the r4 path).  Ref capability: the
# two-pass gamma/beta grads of layer_norm_cuda_kernel.cu:701-807.
import os as _os

_FUSED_DGAMMA = _os.environ.get("APEX_TPU_LN_FUSED_DGAMMA", "1") != "0"


def fused_dgamma_active() -> bool:
    """True when the fused dgamma/dbeta epilogue is enabled (the
    ``APEX_TPU_LN_FUSED_DGAMMA`` switch alone: a compiler refusal of the
    epilogue raises, it never degrades to the XLA reduction) — benchmark
    artifacts record this next to their numbers."""
    return _FUSED_DGAMMA


# ---------------------------------------------------------------------------
# Pure-jnp reference (the "Python fallback" every kernel must have — SURVEY §1)
# ---------------------------------------------------------------------------

def layer_norm_ref(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
) -> jax.Array:
    """LayerNorm over the last axis, stats in fp32, output in x.dtype."""
    x32 = x.astype(jnp.float32)
    mean = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True) - jnp.square(mean)
    y = (x32 - mean) * jax.lax.rsqrt(var + eps)
    if weight is not None:
        y = y * weight.astype(jnp.float32)
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, eps: float, affine: bool):
    x = x_ref[:].astype(jnp.float32)
    n = x.shape[-1]
    mean = jnp.sum(x, axis=-1, keepdims=True) / n
    var = jnp.sum(x * x, axis=-1, keepdims=True) / n - mean * mean
    rstd = jax.lax.rsqrt(var + eps)
    y = (x - mean) * rstd
    if affine:
        y = y * w_ref[:].astype(jnp.float32) + b_ref[:].astype(jnp.float32)
    o_ref[:] = y.astype(o_ref.dtype)


def _ln_dx_math(x_ref, w_ref, dy_ref, *, eps: float, affine: bool):
    """The ONE dx recompute shared by both backward kernels (the fused-
    dgamma path and the APEX_TPU_LN_FUSED_DGAMMA=0 fallback must never
    drift).  Returns (dx, xhat, dy32) in fp32."""
    x = x_ref[:].astype(jnp.float32)
    dy = dy_ref[:].astype(jnp.float32)
    n = x.shape[-1]
    mean = jnp.sum(x, axis=-1, keepdims=True) / n
    var = jnp.sum(x * x, axis=-1, keepdims=True) / n - mean * mean
    rstd = jax.lax.rsqrt(var + eps)
    xhat = (x - mean) * rstd
    dxhat = dy * w_ref[:].astype(jnp.float32) if affine else dy
    m1 = jnp.sum(dxhat, axis=-1, keepdims=True) / n
    m2 = jnp.sum(dxhat * xhat, axis=-1, keepdims=True) / n
    dx = rstd * (dxhat - m1 - xhat * m2)
    return dx, xhat, dy


def _ln_bwd_dx_kernel(x_ref, w_ref, dy_ref, dx_ref, *, eps: float, affine: bool):
    """dx for one row-block; recomputes mean/rstd from x (memory-efficient)."""
    dx, _, _ = _ln_dx_math(x_ref, w_ref, dy_ref, eps=eps, affine=affine)
    dx_ref[:] = dx.astype(dx_ref.dtype)


def _ln_bwd_dx_dwdb_kernel(x_ref, w_ref, dy_ref, dx_ref, acc_ref,
                           *, eps: float, affine: bool, rows: int,
                           block_rows: int):
    """dx plus the dgamma/dbeta row-sum epilogue (see _FUSED_DGAMMA).

    ``acc_ref`` is an (8, n) fp32 block with a CONSTANT index map: it
    stays VMEM-resident across the (sequential) row-block grid and
    flushes once — sublane 0 accumulates sum(dy * xhat), sublane 1
    sum(dy).  Padded tail rows are masked out of the sums explicitly:
    their xhat is garbage (NaN at eps=0 — all-zero rows give rstd=inf),
    and 0 * NaN would poison the accumulator (pad_rows' contract says
    kernels must not reduce across padded rows unguarded).
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)

    dx, xhat, dy = _ln_dx_math(x_ref, w_ref, dy_ref, eps=eps, affine=affine)
    dx_ref[:] = dx.astype(dx_ref.dtype)
    row = i * block_rows + jax.lax.broadcasted_iota(jnp.int32, dy.shape, 0)
    valid = row < rows
    dw_b = jnp.sum(jnp.where(valid, dy * xhat, 0.0), axis=0, keepdims=True)
    db_b = jnp.sum(jnp.where(valid, dy, 0.0), axis=0, keepdims=True)
    lane = jax.lax.broadcasted_iota(jnp.int32, acc_ref.shape, 0)
    acc_ref[:] += jnp.where(
        lane == 0, jnp.broadcast_to(dw_b, acc_ref.shape),
        jnp.where(lane == 1, jnp.broadcast_to(db_b, acc_ref.shape), 0.0),
    )


def _pallas_ok(n: int) -> bool:
    return n % _LANE == 0





def _ln_fwd_pallas(x2, weight, bias, eps, block_rows):
    affine = weight is not None
    n = x2.shape[-1]
    xp, m = _pad_rows(x2, block_rows)
    grid = (xp.shape[0] // block_rows,)
    w = (weight if affine else jnp.zeros((n,), x2.dtype)).reshape(1, n)
    b = (bias if bias is not None else jnp.zeros((n,), w.dtype)).reshape(1, n)
    out = _pallas_call(
        functools.partial(_ln_fwd_kernel, eps=eps, affine=affine),
        name="apex_ln_fwd",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x2.dtype),
    )(xp, w, b)
    return out[:m]


def _ln_bwd_dx_pallas(x2, weight, dy2, eps, block_rows):
    affine = weight is not None
    n = x2.shape[-1]
    xp, m = _pad_rows(x2, block_rows)
    dyp, _ = _pad_rows(dy2, block_rows)
    grid = (xp.shape[0] // block_rows,)
    w = (weight if affine else jnp.zeros((n,), x2.dtype)).reshape(1, n)
    dx = _pallas_call(
        functools.partial(_ln_bwd_dx_kernel, eps=eps, affine=affine),
        name="apex_ln_bwd_dx",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct(xp.shape, x2.dtype),
    )(xp, w, dyp)
    return dx[:m]


def _ln_bwd_dx_dwdb_pallas(x2, weight, dy2, eps, block_rows):
    """dx + (dgamma, dbeta) from ONE pass over (x, dy) — see _FUSED_DGAMMA."""
    affine = weight is not None
    n = x2.shape[-1]
    xp, m = _pad_rows(x2, block_rows)
    dyp, _ = _pad_rows(dy2, block_rows)
    grid = (xp.shape[0] // block_rows,)
    w = (weight if affine else jnp.zeros((n,), x2.dtype)).reshape(1, n)
    dx, acc = _pallas_call(
        functools.partial(_ln_bwd_dx_dwdb_kernel, eps=eps, affine=affine,
                          rows=m, block_rows=block_rows),
        name="apex_ln_bwd_dx_dwdb",
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((1, n), lambda i: (0, 0)),
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_rows, n), lambda i: (i, 0)),
            pl.BlockSpec((8, n), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(xp.shape, x2.dtype),
            jax.ShapeDtypeStruct((8, n), jnp.float32),
        ],
    )(xp, w, dyp)
    return dx[:m], acc[0], acc[1]


# ---------------------------------------------------------------------------
# custom_vjp wiring
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _layer_norm(x2, weight, bias, eps, block_rows, use_pallas):
    if use_pallas:
        return _ln_fwd_pallas(x2, weight, bias, eps, block_rows)
    return layer_norm_ref(x2, weight, bias, eps)


def _ln_fwd_rule(x2, weight, bias, eps, block_rows, use_pallas):
    out = _layer_norm(x2, weight, bias, eps, block_rows, use_pallas)
    return out, (x2, weight, bias)


def _ln_bwd_rule(eps, block_rows, use_pallas, res, dy):
    x2, weight, bias = res
    affine = weight is not None
    x32 = x2.astype(jnp.float32)
    dy32 = dy.astype(jnp.float32)
    if use_pallas and affine and _FUSED_DGAMMA:
        # one pass over (x, dy): dx plus the dgamma/dbeta row sums as an
        # in-kernel epilogue (no XLA column-reduction re-read of x/dy)
        dx, dw32, db32 = _ln_bwd_dx_dwdb_pallas(x2, weight, dy, eps,
                                                block_rows)
        dw = dw32.astype(weight.dtype)
        db = db32.astype(bias.dtype) if bias is not None else None
        return dx, dw, db
    if use_pallas:
        dx = _ln_bwd_dx_pallas(x2, weight, dy, eps, block_rows)
    else:
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True) - jnp.square(mean)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (x32 - mean) * rstd
        dxhat = dy32 * weight.astype(jnp.float32) if affine else dy32
        n = x2.shape[-1]
        m1 = jnp.sum(dxhat, axis=-1, keepdims=True) / n
        m2 = jnp.sum(dxhat * xhat, axis=-1, keepdims=True) / n
        dx = (rstd * (dxhat - m1 - xhat * m2)).astype(x2.dtype)
    if affine:
        # dgamma/dbeta: column reductions over all rows — XLA's reduction is
        # optimal here (ref does a two-pass part-buffer scheme for occupancy)
        mean = jnp.mean(x32, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True) - jnp.square(mean)
        rstd = jax.lax.rsqrt(var + eps)
        xhat = (x32 - mean) * rstd
        dw = jnp.sum(dy32 * xhat, axis=0).astype(weight.dtype)
        db = jnp.sum(dy32, axis=0).astype(bias.dtype) if bias is not None else None
    else:
        dw = None
        db = None
    return dx, dw, db


_layer_norm.defvjp(_ln_fwd_rule, _ln_bwd_rule)


def layer_norm(
    x: jax.Array,
    weight: Optional[jax.Array] = None,
    bias: Optional[jax.Array] = None,
    eps: float = 1e-5,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Fused LayerNorm over the last axis with custom VJP.

    Accepts any leading shape; ``weight``/``bias`` must match the last axis
    (or both be None for the non-affine variant, ref
    fused_layer_norm.py:39-62).  ``use_pallas=None`` auto-selects: the Pallas
    kernel when the trailing dim is lane-aligned and the platform is TPU,
    else the jnp reference (identical math — the L1-style parity tests
    assert this).
    """
    n = x.shape[-1]
    if use_pallas is None:
        from apex_tpu.ops._common import pallas_default

        use_pallas = pallas_default(_pallas_ok(n))
    # Normalize one-sided affine to a full (weight, bias) pair so the kernel
    # path (which keys "affine" off weight) and the jnp reference agree; the
    # substituted identity is a constant, so no spurious grads flow.
    if weight is None and bias is not None:
        weight = jnp.ones((n,), dtype=bias.dtype)
    elif bias is None and weight is not None:
        bias = jnp.zeros((n,), dtype=weight.dtype)
    lead = x.shape[:-1]
    x2 = x.reshape((-1, n))
    out = _layer_norm(x2, weight, bias, eps, block_rows, bool(use_pallas))
    return out.reshape(lead + (n,))
