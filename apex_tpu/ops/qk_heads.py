"""q, k and v from a projection's output to the flash kernels in one pass:
the per-head RMS norm, the rotation by position and the way to heads-major
as two Pallas TPU kernels that read ``qkv`` / ``qkvg`` where it lies.

Between an attention block's ``[q | k | v (| rest)]`` projection and
``flash_attention`` the decoder families do, a head ``D`` wide at a time::

    q, k = RMS(q) * w_q, RMS(k) * w_k      # where the family norms them
    q, k = rotary(q), rotary(k)            # where the layer rotates
    q, k, v -> (B, heads, S, D)            # as the flash kernels take them

— no arithmetic to speak of, and under XLA four to six passes over q and k
in float32 (a slice, a transposition, a norm that relayouts heads-major q, a
``rotate_half`` built from a negate and a concatenation, ``cos`` / ``sin``
made anew for q and for k), all of it run again by a recomputed block.

**On the TPU it is two kernels** (:func:`qkv_heads`), for heads of ONE LANE
TILE (``D = 128``): a head's columns of the projection's output are then a
``(rows, 128)`` block a kernel can pick by a lane offset, and its rows a
heads-major block as they stand.  ``apex_qk_heads_fwd`` (grid (rows of the
batch, row blocks)) takes a block of rows of ``x`` (B, S, W) at the width of
q, of k and of v, walks the heads ON THE CHIP (one copy of the body for q and
one for k, whatever their number) and for each does in VMEM, in this order
and as the caller gives it: the norm over the head's 128 numbers with the
learned gain (float32; ROUNDED to ``x``'s dtype, as ``RMSNorm`` rounds), the
rotation (float32: ``y cos + roll(y, 64) sin`` with ``rotate_half``'s sign
folded into ``sin``; one rounding out), and writes q (B, H, S, D), k and v (B,
H_kv, S, D).  One read and one write in ``x``'s dtype; v is a copy; columns
past v (Trinity's output gate) are not touched — they are handed back as
XLA's slice, which the gate's own fusion absorbs.  ``cos`` and ``sin`` are
TABLES (S, D) float32 the caller makes once with ``rotary``'s expressions:
the kernel reads a row block of each and computes no angle itself.

``apex_qk_heads_bwd`` reads ``dq``, ``dk``, ``dv`` heads-major as the flash
backward leaves them, undoes the rotation (its transpose: ``d cos + roll(d
sin, 64)``), takes the norm's gradient from ``x`` itself — the kernel's own
input, there again in a recomputed forward; no normed copy is kept — and
writes the PROJECTION's gradient as one (B, S, W) array where the two
gradient products read it, ``[dq | dk | dv | d rest]`` side by side, the
rest's cotangent handed through.  The gains' gradients are summed in float32
over rows and heads, eight sublanes of partial sums a gain that the wrapper
adds up.

Everything else — heads of 64 (two a lane tile), a rotation over part of a
head, a zero-centred gain — is the callers' composed path
(``models/decoder.py::qkv_heads`` chooses, by :func:`supported`); that path
is also these kernels' oracle (``tests/test_qk_heads.py``).
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import auto_block, pallas_call as _pallas_call
from apex_tpu.ops.gated_delta import _trace_key

__all__ = ["qkv_heads", "supported"]

_LANES = 128
#: rows of the sequence a grid step takes, at most (a power of two; the
#: largest that divides S is taken), in both kernels; a head's (rows, 128)
#: is worked through straight-line — 32 float32 vregs an array, more than the
#: registers hold, but the norm's chain (a sum across the lanes, rsqrt and its
#: Newton step) is long and the compiler overlaps more of it the more rows it
#: sees: on the chip Trinity's window layer's forward took 1254 / 855 / 649 us
#: a call in pieces of 64 / 128 / 256 rows, its backward 1132 / 783 / 702,
#: whatever the block held them (PERF.md section 5, PR 49)
_ROWS = 256
#: least rows of a block: a 16-bit sublane tile
_MIN_ROWS = 16
#: what a kernel may hold in VMEM beyond its double-buffered blocks
_VMEM_SLACK = 4 * 1024 * 1024


class _Call(NamedTuple):
    """What is static in a call: the query and key/value heads, the norm's
    eps (None: no norm), whether q and k are rotated, and the rows of a
    block."""
    hq: int
    hk: int
    eps: Optional[float]
    rotate: bool
    rows: int


def supported(s: int, hq: int, hk: int, hd: int,
              rot: Optional[int] = None) -> bool:
    """Whether the kernels take these shapes: heads of one 128-lane tile,
    rotated whole or not at all (``rot``: the rotated dims, None for the
    whole head), query heads in whole groups a key head (so k's and v's
    columns start a whole number of their own widths in), and sequences of
    whole row blocks of at least a 16-bit tile."""
    return (hd == _LANES and rot in (None, hd) and hk > 0 and hq % hk == 0
            and s % _MIN_ROWS == 0)


def _each_head(n: int, head):
    """``head(index, first lane)`` for ``n`` heads, a loop on the chip: one
    copy of the body to trace, lower and compile whatever ``n``."""
    def body(h, carry):
        head(h, pl.multiple_of(h * _LANES, _LANES))
        return carry

    jax.lax.fori_loop(0, n, body, 0)


def _roll(t):
    """``rotate_half`` without its sign: the two halves of a head swapped."""
    return pltpu.roll(t, _LANES // 2, 1)


def _mean(t):
    """Over a head's 128 numbers — a sum times a power of two, exactly the
    quotient (left a quotient, the kernel makes 1 / 128 by Newton's steps)."""
    return jnp.sum(t, axis=-1, keepdims=True) * (1.0 / _LANES)


def _rms(x32, eps):
    return jax.lax.rsqrt(_mean(x32 * x32) + eps)


def _fwd_kernel(*refs, call: _Call):
    """Grid (rows of the batch, row blocks).  ``refs``: a block of rows of
    ``x`` at q's columns, at k's and at v's; the two gains (1, 128) float32
    where there is a norm; ``cos`` and the signed ``sin`` (rows, 128) float32
    where there is a rotation; then the blocks of q (1, H, rows, 128), k and
    v."""
    f32 = jnp.float32
    xq_ref, xk_ref, xv_ref, *refs = refs
    gq_ref = gk_ref = None
    if call.eps is not None:
        gq_ref, gk_ref, *refs = refs
    if call.rotate:
        cos_ref, sin_ref, *refs = refs
    q_ref, k_ref, v_ref = refs

    def part(x_ref, o_ref, gain_ref, n):
        def head(h, lane):
            y = x_ref[0, :, pl.ds(lane, _LANES)]
            if call.eps is not None:
                y32 = y.astype(f32)
                y = (y32 * _rms(y32, call.eps) * gain_ref[...]).astype(
                    y.dtype)
            if call.rotate:
                y32 = y.astype(f32)
                y = (y32 * cos_ref[...]
                     + _roll(y32) * sin_ref[...]).astype(y.dtype)
            o_ref[0, h] = y

        _each_head(n, head)

    part(xq_ref, q_ref, gq_ref, call.hq)
    part(xk_ref, k_ref, gk_ref, call.hk)

    def copy(h, lane):
        v_ref[0, h] = xv_ref[0, :, pl.ds(lane, _LANES)]

    _each_head(call.hk, copy)


def _bwd_kernel(*refs, call: _Call, rest: int):
    """Grid (rows of the batch, row blocks).  ``refs``: where there is a
    norm, a block of rows of ``x`` at q's columns and at k's and the two
    gains; ``cos`` and the signed ``sin`` where there is a rotation; the
    blocks of dq (1, H, rows, 128), dk and dv; the rest's cotangent (1, rows,
    ``rest``) where the projection is wider than q | k | v; then the block of
    the projection's gradient at its whole width and, where there is a norm,
    the gains' gradients (8, 128) float32 — resident over the whole grid and
    summed into."""
    f32 = jnp.float32
    norm = call.eps is not None
    xq_ref = xk_ref = gq_ref = gk_ref = dgq_ref = dgk_ref = None
    if norm:
        xq_ref, xk_ref, gq_ref, gk_ref, *refs = refs
    if call.rotate:
        cos_ref, sin_ref, *refs = refs
    dq_ref, dk_ref, dv_ref, *refs = refs
    if rest:
        drest_ref, *refs = refs
    dx_ref, *refs = refs

    if norm:
        dgq_ref, dgk_ref = refs

        @pl.when(jnp.logical_and(pl.program_id(0) == 0,
                                 pl.program_id(1) == 0))
        def _():
            dgq_ref[...] = jnp.zeros_like(dgq_ref)
            dgk_ref[...] = jnp.zeros_like(dgk_ref)

    def part(first, x_ref, do_ref, gain_ref, dgain_ref, n):
        def head(h, lane):
            d = do_ref[0, h].astype(f32)
            if call.rotate:
                d = d * cos_ref[...] + _roll(d * sin_ref[...])
            if norm:
                x32 = x_ref[0, :, pl.ds(lane, _LANES)].astype(f32)
                r = _rms(x32, call.eps)
                normed = x32 * r
                dgain_ref[...] += jnp.sum(
                    (d * normed).reshape(call.rows // 8, 8, _LANES), axis=0)
                d = d * gain_ref[...]
                d = r * (d - normed * _mean(d * normed))
            dx_ref[0, :, pl.ds(first + lane, _LANES)] = d.astype(
                dx_ref.dtype)

        _each_head(n, head)

    part(0, xq_ref, dq_ref, gq_ref, dgq_ref, call.hq)
    part(call.hq * _LANES, xk_ref, dk_ref, gk_ref, dgk_ref, call.hk)
    v0 = (call.hq + call.hk) * _LANES

    def copy(h, lane):
        dx_ref[0, :, pl.ds(v0 + lane, _LANES)] = dv_ref[0, h]

    _each_head(call.hk, copy)
    if rest:
        dx_ref[0, :, v0 + call.hk * _LANES:] = drest_ref[0]


def _vmem(*blocks):
    """The limit for a kernel with these (shape, dtype) blocks, each held
    twice (the pipeline's two buffers)."""
    return _VMEM_SLACK + 2 * sum(
        math.prod(shape) * jnp.dtype(dt).itemsize for shape, dt in blocks)


def _blocks(rows: int):
    """BlockSpecs of ``rows`` rows shared by the two kernels: ``x`` at the
    columns of a part ``n`` heads wide starting ``first`` heads in (q's heads
    are a multiple of k's, so every part starts a whole number of its own
    widths in), a gain, a table's row block, and a heads-major block of ``n``
    heads."""
    def cols(first, n):
        return pl.BlockSpec((1, rows, n * _LANES),
                            lambda bi, ri: (bi, ri, first // n))

    gain = pl.BlockSpec((1, _LANES), lambda bi, ri: (0, 0))
    table = pl.BlockSpec((rows, _LANES), lambda bi, ri: (ri, 0))

    def heads(n):
        return pl.BlockSpec((1, n, rows, _LANES),
                            lambda bi, ri: (bi, 0, ri, 0))

    return cols, gain, table, heads


def _gain_rows(gains):
    return [g.astype(jnp.float32).reshape(1, _LANES) for g in gains]


def _fwd_pallas(x, gains, tables, call: _Call):
    b, s, _ = x.shape
    hq, hk, rows = call.hq, call.hk, call.rows
    cols, gain, table, heads = _blocks(rows)
    in_specs = [cols(0, hq), cols(hq, hk), cols(hq + hk, hk)]
    args = [x, x, x]
    if call.eps is not None:
        in_specs += [gain, gain]
        args += _gain_rows(gains)
    if call.rotate:
        in_specs += [table, table]
        args += list(tables)
    out = lambda n: jax.ShapeDtypeStruct((b, n, s, _LANES), x.dtype)
    block = lambda n: ((rows, n * _LANES), x.dtype)
    return _pallas_call(
        functools.partial(_fwd_kernel, call=call),
        name="apex_qk_heads_fwd", grid=(b, s // rows),
        in_specs=in_specs, out_specs=[heads(hq), heads(hk), heads(hk)],
        out_shape=[out(hq), out(hk), out(hk)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_vmem(
                *(2 * [block(hq), block(hk), block(hk)]),
                *(2 * [((rows, _LANES), jnp.float32)]))),
    )(*args)


def _bwd_pallas(x, gains, tables, cts, call: _Call):
    """``(dx, (dgain_q, dgain_k) or None)`` for the cotangents ``cts`` = (dq,
    dk, dv, d rest or None)."""
    b, s, width = x.shape
    hq, hk, rows = call.hq, call.hk, call.rows
    norm = call.eps is not None
    rest = width - (hq + 2 * hk) * _LANES
    cols, gain, table, heads = _blocks(rows)
    in_specs, args = [], []
    if norm:
        in_specs += [cols(0, hq), cols(hq, hk), gain, gain]
        args += [x, x] + _gain_rows(gains)
    if call.rotate:
        in_specs += [table, table]
        args += list(tables)
    in_specs += [heads(hq), heads(hk), heads(hk)]
    args += list(cts[:3])
    whole = lambda n: pl.BlockSpec((1, rows, n), lambda bi, ri: (bi, ri, 0))
    if rest:
        in_specs.append(whole(rest))
        args.append(cts[3])
    out_specs = [whole(width)]
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype)]
    if norm:
        sums = pl.BlockSpec((8, _LANES), lambda bi, ri: (0, 0))
        out_specs += [sums, sums]
        out_shape += 2 * [jax.ShapeDtypeStruct((8, _LANES), jnp.float32)]
    dx, *dgains = _pallas_call(
        functools.partial(_bwd_kernel, call=call, rest=rest),
        name="apex_qk_heads_bwd", grid=(b, s // rows),
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            # the gains' sums stay in VMEM from the first step to the last
            dimension_semantics=2 * ("arbitrary" if norm else "parallel",),
            vmem_limit_bytes=_vmem(
                ((rows, 2 * width + (hq + hk) * _LANES * norm), x.dtype),
                *(2 * [((rows, _LANES), jnp.float32)]))),
    )(*args)
    if not norm:
        return dx, None
    return dx, tuple(d.sum(0).astype(g.dtype) for d, g in zip(dgains, gains))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _kernels(x, gains, tables, call):
    used = (call.hq + 2 * call.hk) * _LANES
    # the columns past v are XLA's slice: their consumer's fusion reads them
    rest = x[..., used:] if x.shape[-1] > used else None
    return (*_fwd_pallas(x, gains, tables, call), rest)


def _kernels_fwd(x, gains, tables, call):
    return _kernels(x, gains, tables, call), (x, gains, tables)


def _kernels_bwd(call, res, cts):
    x, gains, tables = res
    dx, dgains = _bwd_pallas(x, gains, tables, cts, call)
    # the tables are functions of the position alone
    return dx, dgains, jax.tree_util.tree_map(jnp.zeros_like, tables)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# Called through jit, as the other operators: a model's layers share one
# trace.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _jit(x, gains, tables, call, trace_key):
    del trace_key
    if tables is not None:
        cos, sin = tables
        # rotate_half's sign: the first half of a head takes MINUS the second
        first = jax.lax.broadcasted_iota(jnp.int32, sin.shape, 1) < _LANES // 2
        tables = cos, jnp.where(first, -sin, sin)
    return _kernels(x, gains, tables, call)


def qkv_heads(x, hq: int, hk: int, *, gains=None, eps: Optional[float] = None,
              tables=None):
    """``(q, k, v, rest)`` heads-major from a projection's output, normed and
    rotated on the way: the two kernels of the module docstring.

    ``x`` (B, S, W) laid out ``[q | k | v | rest]``, ``hq`` query and ``hk``
    key/value heads of 128 (``hq`` a multiple of ``hk``), ``rest`` whatever
    is left of W — returned as it is, (B, S, W - (hq + 2 hk) 128), or None.
    ``gains``: ``(w_q, w_k)``, (128,) each, for an RMS norm with ``eps`` over
    each head of q and of k, rounded to ``x``'s dtype; None: no norm.
    ``tables``: ``(cos, sin)``, (S, 128) float32 each as ``rotary`` makes
    them (both halves of a head filled), for a rotation of q and k over the
    whole head; None: none.  Returns q (B, hq, S, 128), k and v (B, hk, S,
    128) in ``x``'s dtype.  Differentiable in ``x`` and the gains.

    Raises ValueError for the shapes :func:`supported` refuses: the caller
    chooses (``models/decoder.py::qkv_heads``)."""
    if x.ndim != 3 or x.shape[2] < (hq + 2 * hk) * _LANES:
        raise ValueError(f"x {x.shape} is not (B, S, W >= {hq} + 2 * {hk} "
                         f"heads of {_LANES})")
    s = x.shape[1]
    if not supported(s, hq, hk, _LANES):
        raise ValueError(f"the kernels want {hq} query heads in whole groups "
                         f"of {hk} and whole blocks of {_MIN_ROWS} rows: got "
                         f"{x.shape}")
    if (gains is None) != (eps is None):
        raise ValueError("a norm wants both its gains and its eps")
    call = _Call(hq, hk, None if eps is None else float(eps),
                 tables is not None, auto_block(s, _ROWS, _MIN_ROWS))
    return _jit(x, gains, tables, call, _trace_key())
