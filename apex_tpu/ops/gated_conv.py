"""The gated short convolution — ``C * conv(B * X)`` over a sequence — as
two Pallas TPU kernels that read the three gates out of the projection's
output where they lie, and a ``jax.numpy`` path.

The sequence mixer of a convolution layer of ``models/lfm2.py``: the layer's
input projection hands over ONE array ``bcx`` laid out ``[B | C | X]``, each
third ``d`` wide, and the mixer is::

    u_t = B_t * X_t
    c_t = sum_{j < K} w[:, j] * u_{t - (K-1) + j}      depthwise, causal,
                                                       zeros before the row's start
    y_t = C_t * c_t

— no bias, no activation: a gate in front of a ``K``-tap depthwise
convolution and a gate behind it.  ``u``, the taps and the second gate are
float32 multiply-adds with ONE rounding, at the output (as
``ops/gated_delta.py::split_conv_qkvz``'s are).  No reference counterpart.

**On the TPU the mixer is two kernels** (:func:`gated_short_conv`).
``apex_gated_conv_fwd`` (grid (lane blocks, rows of the batch, row blocks —
walked in order)) reads a block of rows x a block of lanes of B, of C and of
X through three BlockSpecs on ``bcx`` itself (the thirds start ``d`` lanes
apart: whole blocks), keeps the ``K - 1`` preceding rows of ``u`` in VMEM
from the block before (zeros at every row's start) and writes the one
output.  ``apex_gated_conv_bwd`` (grid (rows of the batch, row blocks —
walked from the LAST), blocks the projection's whole width) makes ``u`` and
``c`` again, ``dC = dy * c``, ``dc = dy * C``, the taps the other way
(``du``), ``dB = du * X``, ``dX = du * B``, ``dw`` summed in float32 over the
blocks, and writes the PROJECTION's gradient where it lies, ``[dB | dC | dX]``
side by side.  What crosses HBM is the operator's input, output and their
gradients in the compute dtype — forward ``4 S d`` elements, backward ``7 S
d`` and a block edge's 16 rows: no split copy of a third, no float32 and no
padded array.  Under XLA the same arithmetic is a padded float32 copy of
``u``, ``K`` shifted passes and two gating passes, each through HBM
(``ops/gated_delta.py::causal_conv1d_silu``'s docstring and PERF.md section
6, PR 33, have what that cost the delta net's convolution).

The halo a block carries over its edge and the taps' multiply-adds are
``ops/gated_delta.py``'s (the other short convolution: SiLU behind it, no
gates, the delta net's per-key-head layout), imported, not copied; the blocks'
sizes are this layout's own.

**Off the TPU, and as the kernels' oracle,** :func:`gated_short_conv_ref`:
the thirds split, ``K`` shifted multiply-adds on a zero-padded float32 row.
The same path takes the shapes :func:`supported` refuses.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import (auto_block, pallas_call as _pallas_call,
                                  pallas_default)
from apex_tpu.ops.gated_delta import (_HALO, _HALO_ROWS, _taps, _trace_key)

__all__ = ["gated_short_conv", "gated_short_conv_ref", "supported"]

#: rows of the sequence a grid step of the forward kernel takes, at most (a
#: power of two; the largest that divides S is taken), and the lanes
_FWD_ROWS = 512
_FWD_LANES = 512
#: rows a grid step of the backward kernel takes, at most: its blocks are
#: the projection's whole width, three times ``d``, in and out
_BWD_ROWS = 128
#: lanes of a block the backward kernel works through at a time
_BWD_LANES = 256
#: elements of a piece, the rows x lanes the kernels work through
#: straight-line: what lives between a piece's loads and its stores stays in
#: registers (32 float32 vregs an array)
_PIECE = 32 * 1024


def gated_short_conv_ref(bcx, w):
    """:func:`gated_short_conv` in plain ``jax.numpy``, differentiated by
    JAX: the thirds split, ``u`` padded with ``K - 1`` zero rows in float32,
    ``K`` shifted multiply-adds, the second gate, one rounding."""
    k = w.shape[-1]
    s = bcx.shape[1]
    b, c, x = (t.astype(jnp.float32) for t in jnp.split(bcx, 3, axis=-1))
    u = jnp.pad(b * x, ((0, 0), (k - 1, 0), (0, 0)))
    w32 = w.astype(jnp.float32)
    conv = sum(u[:, j:j + s] * w32[:, j] for j in range(k))
    return (c * conv).astype(bcx.dtype)


def _tile(s: int, d: int):
    """``(forward rows, piece rows, lanes, backward rows, piece rows,
    lanes)`` for sequences of ``s`` tokens and thirds ``d`` wide."""
    out = ()
    for rows, lanes in ((_FWD_ROWS, _FWD_LANES), (_BWD_ROWS, _BWD_LANES)):
        rows, lanes = auto_block(s, rows, 1), auto_block(d, lanes, 1)
        out += (rows, min(rows, max(_PIECE // lanes, 8)), lanes)
    return out


def supported(s: int, d: int, taps: int) -> bool:
    """Whether the kernels take these shapes: thirds of whole 128-lane
    tiles, whole row blocks of at least a 16-bit tile, and taps that reach
    no further back than one sublane tile."""
    tile = _tile(s, d)
    return (d % 128 == 0 and min(tile[0], tile[3]) >= _HALO_ROWS
            and 1 <= taps <= _HALO + 1)


def _fwd_kernel(b_ref, c_ref, x_ref, w_ref, o_ref, us, *, size):
    """Grid (lane blocks, rows of the batch, row blocks — walked in order),
    ``size`` rows worked through at a time: the blocks of B, C and X, w's
    (K, lanes), the output block, and a float32 scratch (HALO + rows, lanes)
    for ``u`` whose first HALO rows carry the previous block's last ones."""
    f32 = jnp.float32
    rows, k = b_ref.shape[1], w_ref.shape[0]

    @pl.when(pl.program_id(2) == 0)     # zeros before the start of EVERY row
    def _():
        us[0:_HALO, :] = jnp.zeros((_HALO, us.shape[1]), f32)

    for start in range(0, rows, size):
        piece = slice(start, start + size)
        us[_HALO + start:_HALO + start + size, :] = (
            b_ref[0, piece, :].astype(f32) * x_ref[0, piece, :].astype(f32))
        c = _taps(w_ref, slice(None), lambda j: us[pl.ds(
            _HALO - (k - 1) + j + start, size), :])
        o_ref[0, piece, :] = (c_ref[0, piece, :].astype(f32) * c).astype(
            o_ref.dtype)
    us[0:_HALO, :] = us[rows:rows + _HALO, :]


def _bwd_kernel(bcx_ref, hb_ref, hx_ref, dy_ref, w_ref, dx_ref, dw_ref,
                us, gs, *, size, lanes):
    """Grid (rows of the batch, row blocks — walked from the LAST), ``size``
    rows x ``lanes`` lanes worked through at a time: a block of rows of
    ``bcx`` at its whole width, the HALO_ROWS preceding rows of B and of X,
    the block of dy, w (K, d), then the block of the projection's gradient
    ``[dB | dC | dX]``, dw (K, d) — resident over the whole grid and summed
    into —, a float32 scratch (HALO + rows, lanes) for ``u`` and one (rows +
    HALO, d) for ``dc = dy * C`` whose last HALO rows carry the following
    block's first ones."""
    f32 = jnp.float32
    rows, d = dy_ref.shape[1], dy_ref.shape[2]
    k = w_ref.shape[0]
    last_block = pl.program_id(1) == 0          # the walk's first step
    first_block = pl.program_id(1) == pl.num_programs(1) - 1

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, last_block))
    def _():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    @pl.when(last_block)        # nothing follows a row's last token
    def _():
        gs[rows:rows + _HALO, :] = jnp.zeros((_HALO, d), f32)

    edge = slice(_HALO_ROWS - _HALO, _HALO_ROWS)
    for lo in range(0, d, lanes):
        at = lambda third: slice(third * d + lo, third * d + lo + lanes)
        cols = at(0)
        halo = hb_ref[0, edge, cols].astype(f32) * hx_ref[0, edge, cols].astype(f32)
        us[0:_HALO, :] = jnp.where(first_block, 0.0, halo)
        for start in range(0, rows, size):
            piece = slice(start, start + size)
            us[_HALO + start:_HALO + start + size, :] = (
                bcx_ref[0, piece, at(0)].astype(f32)
                * bcx_ref[0, piece, at(2)].astype(f32))
        dw = [jnp.zeros((1, lanes), f32)] * k
        for start in reversed(range(0, rows, size)):
            piece = slice(start, start + size)
            window = [us[pl.ds(_HALO - (k - 1) + j + start, size), :]
                      for j in range(k)]
            dy = dy_ref[0, piece, cols].astype(f32)
            dx_ref[0, piece, at(1)] = (
                dy * _taps(w_ref, cols, lambda j: window[j])).astype(dx_ref.dtype)
            g = dy * bcx_ref[0, piece, at(1)].astype(f32)
            gs[piece, cols] = g
            dw = [acc + jnp.sum(g * window[j], axis=0, keepdims=True)
                  for j, acc in enumerate(dw)]
            # the taps the other way: row t's u fed outputs t .. t + K-1
            du = _taps(w_ref, cols, lambda j: gs[pl.ds(
                start + (k - 1) - j, size), cols])
            dx_ref[0, piece, at(0)] = (
                du * bcx_ref[0, piece, at(2)].astype(f32)).astype(dx_ref.dtype)
            dx_ref[0, piece, at(2)] = (
                du * bcx_ref[0, piece, at(0)].astype(f32)).astype(dx_ref.dtype)
        for j in range(k):
            dw_ref[j:j + 1, cols] += dw[j]
        gs[rows:rows + _HALO, cols] = gs[0:_HALO, cols]


def _fwd_pallas(bcx, w, tile):
    b, s, width = bcx.shape
    d, taps = width // 3, w.shape[1]
    rows, size, lanes = tile[:3]
    per = d // lanes                    # lane blocks a third
    third = lambda n: pl.BlockSpec(
        (1, rows, lanes), lambda l, r, i: (r, i, n * per + l))
    return _pallas_call(
        functools.partial(_fwd_kernel, size=size),
        name="apex_gated_conv_fwd", grid=(per, b, s // rows),
        in_specs=[third(0), third(1), third(2),
                  pl.BlockSpec((taps, lanes), lambda l, r, i: (0, l))],
        out_specs=third(0),
        out_shape=jax.ShapeDtypeStruct((b, s, d), bcx.dtype),
        scratch_shapes=[pltpu.VMEM((_HALO + rows, lanes), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(bcx, bcx, bcx, w.astype(jnp.float32).T)


def _bwd_pallas(bcx, w, dy, tile):
    """``(d bcx, dw)``: the projection's gradient written where it lies."""
    b, s, width = bcx.shape
    d, taps = width // 3, w.shape[1]
    rows, size, lanes = tile[3:]
    n, per = s // rows, rows // _HALO_ROWS
    block = lambda i: n - 1 - i
    halo = lambda third: pl.BlockSpec(
        (1, _HALO_ROWS, d),
        lambda r, i: (r, jnp.maximum(block(i) * per - 1, 0), third))
    whole = lambda cols: pl.BlockSpec((1, rows, cols),
                                      lambda r, i: (r, block(i), 0))
    weights = pl.BlockSpec((taps, d), lambda r, i: (0, 0))
    dx, dw = _pallas_call(
        functools.partial(_bwd_kernel, size=size, lanes=lanes),
        name="apex_gated_conv_bwd", grid=(b, n),
        in_specs=[whole(width), halo(0), halo(2), whole(d), weights],
        out_specs=[whole(width), weights],
        out_shape=[jax.ShapeDtypeStruct(bcx.shape, bcx.dtype),
                   jax.ShapeDtypeStruct((taps, d), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((_HALO + rows, lanes), jnp.float32),
                        pltpu.VMEM((rows + _HALO, d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
    )(bcx, bcx, bcx, dy, w.astype(jnp.float32).T)
    return dx, dw.T.astype(w.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _kernels(bcx, w, tile):
    return _fwd_pallas(bcx, w, tile)


def _kernels_fwd(bcx, w, tile):
    return _kernels(bcx, w, tile), (bcx, w)


def _kernels_bwd(tile, res, dy):
    return _bwd_pallas(*res, dy, tile)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# Called through jit, as the delta net's convolution: a model's layers share
# one trace.
@functools.partial(jax.jit, static_argnums=(2, 3))
def _jit(bcx, w, tile, trace_key):
    del trace_key
    return _kernels(bcx, w, tile) if tile else gated_short_conv_ref(bcx, w)


def gated_short_conv(bcx, w, *, use_pallas: Optional[bool] = None):
    """``C * conv(B * X)``: the gated short convolution of a projection's
    output, read where it lies.

    ``bcx`` (B, S, 3 d) laid out ``[B | C | X]``, ``w`` (d, K) the depthwise
    taps (``y_t`` reads ``u_{t-K+1} .. u_t`` through ``w[:, 0] .. w[:,
    K-1]``; zeros before the row's start).  Returns (B, S, d) in ``bcx``'s
    dtype — float32 arithmetic, one rounding at the output.  Differentiable
    in ``bcx`` and ``w``.

    On the TPU, where the shapes tile (:func:`supported`), the two kernels of
    the module docstring; else :func:`gated_short_conv_ref`.  The gauge
    ``gated_conv.kernel`` says which was traced."""
    if bcx.ndim != 3 or bcx.shape[2] != 3 * w.shape[0]:
        raise ValueError(f"bcx {bcx.shape} is not (B, S, 3 d) for taps "
                         f"{w.shape} (d, K)")
    s, d, taps = bcx.shape[1], w.shape[0], w.shape[1]
    ok = supported(s, d, taps)
    if use_pallas is None:
        use_pallas = pallas_default(ok)
    elif use_pallas and not ok:
        raise ValueError(f"the gated convolution's kernels want thirds of "
                         f"128 lanes, rows in blocks of {_HALO_ROWS} and at "
                         f"most {_HALO + 1} taps: got {bcx.shape}, {w.shape}")
    from apex_tpu import obs

    obs.default_registry().gauge("gated_conv.kernel").set(int(use_pallas))
    return _jit(bcx, w, _tile(s, d) if use_pallas else None, _trace_key())
