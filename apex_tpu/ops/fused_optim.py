"""Pallas multi-tensor optimizer stages — norms fused into the update pass.

ref: csrc/multi_tensor_lamb.cu:332-413 (one launch runs LAMBStage1 over
every tensor) and csrc/multi_tensor_l2norm_kernel.cu.  The reference
needs chained norm launches BEFORE the trust-ratio apply: multi_tensor_
l2norm for the global grad norm, LAMBStage1, another l2norm pair for the
per-tensor param/update norms, LAMBStage2.  The TPU profile (PERF.md r3
"BERT-large measured profile") shows the same structure materializing as
~8.7 ms of separate reduce_sum chains over 330M fp32 values — XLA does
not fuse a reduction consumed by a later pass into the update loop that
produces its operand.

This module moves those reductions INTO the Pallas update pass:
:func:`lamb_stage1` reads (g, p, m, v) once and emits (m_new, v_new)
plus the per-tensor ``sum(p^2)`` / ``sum(u^2)`` as an in-register
epilogue of the same memory pass — the two per-tensor norm passes
disappear.  The trust-ratio apply then recomputes ``u`` from
(m_new, v_new, p) as a plain XLA elementwise pass (recompute instead of
materializing ``u``: writing u would add a 1.3 GB fp32 buffer per
330M-param model, and the recompute reads the same three arrays the
apply needs anyway).

Layout: each leaf is viewed as (size//128, 128) rows; the grid walks
row-chunks, the final ragged chunk is handled with an in-kernel row mask
(Pallas drops out-of-bounds writes; masked rows are excluded from the
norm sums) — no jnp.pad copy pass, per the r3 measurement discipline.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import pallas_call as _pallas_call

# rows per grid step: 4 in + 2 out fp32 blocks of (512, 128) = 1.5 MB,
# ~3 MB with double buffering — small enough to coexist with anything
DEFAULT_BLOCK_ROWS = 512

# leaves below this element count stay on the jnp path (their norm
# reductions are trivially cheap; a kernel launch per tiny bias would
# cost more than it saves)
MIN_PALLAS_SIZE = 1 << 16


def _lamb_stage1_kernel(
    scal_ref, g_ref, p_ref, m_ref, v_ref,
    m_out, v_out, sums_ref,
    *, rows: int, block_rows: int,
    b1: float, b2: float, eps: float, wd: float, adam_w: bool,
):
    """One row-chunk of LAMB stage 1 + the fused norm epilogue.

    scal_ref (SMEM f32[4]) = [combined grad scale (1/clip, with the AMP
    1/loss_scale folded in when amp-fused), bias_corr1, bias_corr2,
    skip flag] — the traced scalars.  skip > 0 (an AMP overflow step)
    writes m/v back UNCHANGED from the values already in VMEM — the
    where-gate costs no extra memory pass, unlike gating outside the
    kernel.  Hyperparameters are compile-time constants.
    """
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        sums_ref[...] = jnp.zeros_like(sums_ref)

    g = g_ref[...].astype(jnp.float32) * scal_ref[0]
    p = p_ref[...].astype(jnp.float32)
    if not adam_w and wd != 0.0:
        g = g + wd * p
    skip = scal_ref[3] > 0.0
    m = jnp.where(skip, m_ref[...], b1 * m_ref[...] + (1.0 - b1) * g)
    v = jnp.where(skip, v_ref[...],
                  b2 * v_ref[...] + (1.0 - b2) * g * g)
    m_out[...] = m
    v_out[...] = v
    u = (m / scal_ref[1]) / (jnp.sqrt(v / scal_ref[2]) + eps)
    if adam_w and wd != 0.0:
        u = u + wd * p
    # ragged final chunk: rows past the true extent hold garbage reads —
    # exclude them from the norm sums (their m/v writes are dropped by
    # Pallas's out-of-bounds masking)
    row = i * block_rows + jax.lax.broadcasted_iota(
        jnp.int32, g_ref.shape, 0
    )
    valid = row < rows
    psum = jnp.sum(jnp.where(valid, p * p, 0.0))
    usum = jnp.sum(jnp.where(valid, u * u, 0.0))
    # the sums block has a constant index map: it stays resident in VMEM
    # across the (sequential) grid and flushes once — lanes 0/1 hold the
    # running sum(p^2)/sum(u^2)
    lane = jax.lax.broadcasted_iota(jnp.int32, sums_ref.shape, 1)
    sums_ref[...] += jnp.where(
        lane == 0, psum, jnp.where(lane == 1, usum, 0.0)
    )


def lamb_stage1(
    g: jax.Array,
    p: jax.Array,
    m: jax.Array,
    v: jax.Array,
    clip_inv: jax.Array,
    bc1: jax.Array,
    bc2: jax.Array,
    *,
    b1: float,
    b2: float,
    eps: float,
    wd: float,
    adam_w: bool,
    skip=None,
    block_rows: int = DEFAULT_BLOCK_ROWS,
) -> Tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """Fused LAMB stage 1 for one leaf: returns (m_new, v_new, sum_p2,
    sum_u2) from ONE pass over (g, p, m, v).

    Shapes are arbitrary with ``size % 1024 == 0`` (the (rows, 128) view
    keeps sublane alignment); m/v must be fp32.  The caller computes the
    trust ratio from the sums and applies the update elementwise.
    ``skip`` (traced bool, the AMP found_inf) makes the pass write m/v
    back unchanged — the overflow-step gate, in-register.
    """
    shape = g.shape
    size = g.size
    if size % 1024:
        raise ValueError(
            f"lamb_stage1 needs size % 1024 == 0 (got {size}: the "
            "(rows, 128) view must keep rows a multiple of 8 for TPU "
            "sublane alignment) — gate callers with lamb_leaf_ok"
        )
    if m.dtype != jnp.float32 or v.dtype != jnp.float32:
        raise ValueError(
            f"lamb_stage1 needs fp32 m/v (got m={m.dtype}, v={v.dtype}): "
            "the kernel accumulates moments in fp32 in place"
        )
    rows = size // 128
    g2 = g.reshape(rows, 128)
    p2 = p.reshape(rows, 128)
    m2 = m.reshape(rows, 128)
    v2 = v.reshape(rows, 128)
    scal = jnp.stack([
        jnp.asarray(clip_inv, jnp.float32).reshape(()),
        jnp.asarray(bc1, jnp.float32).reshape(()),
        jnp.asarray(bc2, jnp.float32).reshape(()),
        (jnp.zeros((), jnp.float32) if skip is None
         else jnp.asarray(skip, jnp.float32).reshape(())),
    ])
    br = min(block_rows, rows)
    ngrid = pl.cdiv(rows, br)
    row_spec = pl.BlockSpec((br, 128), lambda i: (i, 0))
    m_new, v_new, sums = _pallas_call(
        functools.partial(
            _lamb_stage1_kernel, rows=rows, block_rows=br,
            b1=b1, b2=b2, eps=eps, wd=wd, adam_w=adam_w,
        ),
        name="apex_lamb_stage1",
        grid=(ngrid,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            row_spec, row_spec, row_spec, row_spec,
        ],
        out_specs=[
            row_spec, row_spec,
            pl.BlockSpec((8, 128), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((rows, 128), jnp.float32),
            jax.ShapeDtypeStruct((rows, 128), jnp.float32),
            jax.ShapeDtypeStruct((8, 128), jnp.float32),
        ],
    )(scal, g2, p2, m2, v2)
    return (
        m_new.reshape(shape),
        v_new.reshape(shape),
        sums[0, 0],
        sums[0, 1],
    )


def lamb_leaf_ok(x: jax.Array) -> bool:
    """Shape gate for the Pallas leaf path (see :func:`lamb_stage1`)."""
    return x.size % 1024 == 0 and x.size >= MIN_PALLAS_SIZE


def lamb_kernel_enabled(explicit: Optional[bool]) -> bool:
    """Resolve fused_lamb's ``use_pallas``.

    Unlike every other kernel's auto-gate, the default here is OFF even
    on TPU: the r4 end-to-end A/B measured the kernel ~10% slower in the
    BERT step (the pallas_call boundary materializes the unscaled master
    grads and blocks XLA from fusing the AMP where-gates into the update
    loops — PERF.md r4 "Pallas LAMB").  ``force_pallas(True)`` (the L1
    harness's extensions-on switch) still opts in.
    """
    if explicit is not None:
        return explicit
    from apex_tpu.ops import _common

    return _common._FORCE_PALLAS is True
