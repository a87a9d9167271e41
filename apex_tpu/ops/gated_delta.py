"""The gated delta rule over a sequence, by chunks — two Pallas TPU kernels
that hold everything between q, k, v, g, beta and o in VMEM, and a ``lax.scan``
path — and the short causal depthwise convolution in front of it — two more
kernels that read q, k, v out of the projection's output where they lie, and
a ``jax.numpy`` path; the same two kernels, under another layout and with a
bias, are the convolution in front of ``ops/ssd.py``'s state-space scan.

The first sequential operator of ``ops/``: a linear-attention layer
(``models/qwen3_next.py``'s gated delta net) keeps, per head, a float32
state ``S`` of shape ``(d_k, d_v)`` and walks the sequence::

    S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;  o_t = S^T q_t

with ``g_t <= 0`` a log-decay and ``beta_t`` in (0, 1).  No reference
counterpart (apex has no recurrent attention).

**The chunked (WY) form.**  Inside a chunk of ``C`` tokens that starts from
state ``S_0``, with ``G_i`` the running sum of ``g`` over the chunk, the
corrected values ``u_i = beta_i r_i`` solve one unit-lower-triangular system

    (I + A) U = beta (V - diag(exp G) K S_0),   A_ij = beta_i exp(G_i - G_j) k_i.k_j  (j < i)

so with ``T = (I + A)^-1``, ``W = T (beta exp(G) K)`` and ``U0 = T (beta V)``::

    U  = U0 - W S_0
    O  = (exp(G) Q) S_0 + (M . Q K^T) U,        M_ij = exp(G_i - G_j)  (j <= i)
    S' = exp(G_C) S_0 + (exp(G_C - G) K)^T U

Everything but those three lines is local to a chunk; the three lines touch
the state by three ``(C, d_k) x (d_k, d_v)``-shaped products and chain the
chunks.

**On the TPU the whole rule is two kernels.**  ``apex_gdn_fwd`` (grid (rows,
head groups, chunks), the float32 states of a group's value heads in VMEM
scratch) reads a chunk's q and k at their KEY head, v, ``beta`` and the
running sum ``G`` — q, k, v as blocks of the arrays the model has, ``(B, S,
heads x d)`` in its compute dtype, no transposed and no float32 copy, a value
head ``h`` reading key head ``h // r`` — makes the decays, ``K K^T``, ``A``,
``T`` (the doubling steps of :func:`_tri_inverse` on 64 x 64 tiles), ``Q K^T``
in VMEM, runs the three lines and writes o, the state at each chunk's START
(``B x chunks x heads x d_k x d_v`` float32, never a state per token) and
``T`` (in the operands' dtype: a product is all that ever reads it).
``apex_gdn_bwd`` walks the chunks from the last with ``dS`` in scratch: from
q, k, v, ``beta``, ``G``, the chunk's starting state, ``T`` and ``do`` it makes
the forward's values again, transposes the three lines, and carries on —
still in VMEM — through ``T`` (``dA = -T^T dT T^T``), ``A``, ``Q K^T`` and
every decay to dq, dk (summed over a key head's value heads), dv, dbeta and
dG.  So the rule's HBM traffic is its inputs, its outputs and one state and
one ``T`` a chunk: what ``_chunk_local`` writes for the scan path — W, U0,
Qg, Kd, P, float32 arrays of q's size — never exists (PERF.md section 6, PR
31: 1.8 + 2.3 ms a layer against 10.5 + 12.2).  XLA keeps the (B, S, H_v)
arrays: ``G`` is ``jnp.cumsum`` of ``g`` inside each chunk, laid out twice (a
kernel reads a head's column (C, 1) from one and its row (1, C) from the
other), and dg is dG summed back over the chunk — 1 MB each.  Both kernels
are written a LINE of the arithmetic at a time over the heads of a grid
step, because a head's products wait on each other.

**The convolution in front of the rule is two kernels more**
(:func:`split_conv_qkvz`).  The model's projection hands q, k, v and the
output gate z over in ONE array laid out per key head ``[q d_k | k d_k | v
r d_v | z r d_v]``; every part starts a whole number of 128-lane tiles in, so
``apex_conv1d_fwd`` reads a block of rows of one key head's q, k and v
columns through BlockSpecs on that array itself, does the ``K`` taps and the
SiLU in float32 in VMEM (the ``K - 1`` rows before a block carried over from
the block before, zeros at every row's start) and writes q, k, v contiguous
over their heads, as the rule's kernels read them.  ``apex_conv1d_bwd``
walks the row blocks from the last and writes the projection's gradient in
the projection's own layout.  What crosses HBM under the model's scope
``gdn_conv`` is the operator's input and output in the compute dtype and
z's copy: the concatenated ``[q | k | v]`` array, its padded float32 copy
and the ``K`` shifted float32 passes that :func:`causal_conv1d_silu` costs on
the chip (29.96 ms a step of ``qwen3-next.train-8k`` for 3.4 ms of bytes,
PERF.md section 6, PR 33) exist only off the TPU.

**The same two kernels are a Mamba-2 layer's convolution**
(``ops/ssd.py::split_conv_xbc``, ``models/granite_hybrid.py``): the kernels,
their BlockSpecs and the ``jax.numpy`` path are written against a
:class:`ConvLayout` — groups of columns, the parts of a group that are read,
the outputs they are written to, what a group hands through — and know no
model.  The delta net's layout is a group a key head; the state-space
layer's ``[z | x | B | C | dt]`` is ONE group whose x, B and C are a part
and an output each, z and dt handed through, with a bias row beside the
taps (``None`` for the delta net: no operand, no add).  A part wider than a
lane tile is worked through a tile at a time.

(The library's OTHER short convolution — a gate in front and a gate behind,
no SiLU, a projection laid out in thirds — is ``ops/gated_conv.py``, which
imports this one's halo and taps.)

**Off the TPU, and as the kernels' oracle,** what is local to a chunk is
computed for all chunks at once by batched products (:func:`_chunk_local`,
plain ``jax.numpy``, differentiated by JAX; ``T`` by :func:`tri_inverse`) and
the chunks are chained by ``lax.scan`` (:func:`_chain`, a ``custom_vjp``
whose forward and backward each walk the chunks once), in float32, q and k
repeated to the value heads.  The same path takes the shapes
:func:`supported` refuses.

**Precision.**  Float32 arithmetic with the products at default precision:
on the TPU one bfloat16 rounding of each operand, float32 accumulation.  In
the kernels an operand is cast to v's dtype — the model's compute dtype; q
and k are cast to it on the way in — where it enters a product and nowhere
else; ``S``, ``dS``, ``G``, every decay and ``T``'s entries between
the doubling steps stay float32.  With float32 inputs (the tests, in
interpret mode) the kernels compute in float32 throughout.

**The trap.**  A head's log-decay reaches -21 a token (``A_log = log 16``,
``softplus`` of a large ``a``), -1300 over a chunk of 64.  Every decay here
is built as ``exp(G_i - G_j)`` for ``i >= j`` ONLY — a difference of the
running sum that is never positive, masked BEFORE the exponential.  Factored
as ``exp(G_i) * exp(-G_j)`` the second factor overflows float32 inside one
chunk (``tests/test_ops_gated_delta.py`` runs the strongest decay, through
the scan path and through both kernels).

**What a decay a key CHANNEL changes** (Kimi Delta Attention: ``g`` (B, S, H,
d_k), ``ops/kda.py``).  Here the decay is a scalar a pair of tokens, so ``A``
is ``(K K^T) . M``: one product, then a (C, C) mask of decays.  With a vector
decay it sits inside the sum over the channels — ``A_ij = beta_i sum_d k_id
k_jd exp(G_id - G_jd)`` — and ``A`` is no product of ``K`` with itself; made
one, it needs exactly the factored form above, ``k_i exp(G_i - G_ref)``
against ``k_j exp(G_ref - G_j)``, safe only while ``G_ref`` lies between
``j`` and ``i``.  That rule therefore has kernels of its own (sub-blocks of a
chunk, a reference row a sub-block, the diagonal sub-blocks channel by channel
on the VPU) and these stay as they are; it imports this file's triangular
inverse, product helpers, small layouts and convolution.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import pallas_call as _pallas_call, pallas_default
from apex_tpu.remat import GDN_OUT, GDN_STATES, GDN_TRI

__all__ = ["gated_delta_rule", "gated_delta_rule_recurrent",
           "causal_conv1d_silu", "split_conv_qkvz", "conv_columns",
           "ConvLayout", "tri_inverse", "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 64
#: value heads a grid step of the kernels takes together, at most: a step's
#: work is small and its fixed cost is not, and the heads' dependent
#: products hide each other's latency
_HEADS_PER_STEP = 8


# ---------------------------------------------------------------------------
# the short convolution: in front of the rule, and of the state-space scan
# ---------------------------------------------------------------------------

def causal_conv1d_silu(x, w, bias=None):
    """Depthwise causal convolution over the sequence, an optional bias,
    then SiLU.

    ``x`` (B, S, channels), ``w`` (channels, K), ``bias`` (channels,) or
    None: ``y_t = silu(sum_j w[:, j] * x_{t - (K-1) + j} + bias)`` with zeros
    before the row's start.  ``K`` shifted multiply-adds in float32 (a
    ``K``-tap depthwise convolution has no use for the MXU); ``x``'s dtype
    out.  The path off the TPU and the oracle of the kernels below: on the
    chip XLA makes a padded float32 copy of ``x`` and ``K`` shifted float32
    passes of this, not one fused pass (PERF.md section 6, PR 33 and PR
    44)."""
    k = w.shape[-1]
    s = x.shape[1]
    x32 = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w32 = w.astype(jnp.float32)
    y = sum(x32[:, j:j + s] * w32[:, j] for j in range(k))
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)


class ConvLayout(NamedTuple):
    """Where a projection's output (B, S, ``groups x stride``) keeps what the
    convolution reads, as data: the kernels, their BlockSpecs and the
    ``jax.numpy`` path are written against this and know no model.

    A GROUP is ``stride`` columns (the delta net: a key head's ``[q | k | v |
    z]``; a Mamba-2 layer: the whole ``[z | x | B | C | dt]``, one group) and
    a step of the kernels' grid.  ``parts``: the column blocks of a group the
    convolution reads, each ``(first column, width, output, first lane in
    that output's block)`` and read through a BlockSpec of its own width —
    so a part starts a whole number of its widths in, in every group.
    ``outs``: a group's width of each OUTPUT array (B, S, ``groups x
    width``); an output's parts lie side by side in the group, and the
    convolution's channels run over the outputs in order, each over all
    groups.  ``passed``: ``(first column, width)`` of what a group hands
    through untouched, returned behind the outputs; the outputs' columns and
    these are the whole group."""
    groups: int
    stride: int
    parts: Tuple[Tuple[int, int, int, int], ...]
    outs: Tuple[int, ...]
    passed: Tuple[Tuple[int, int], ...]

    def cut(self, proj, first: int, width: int):
        """Columns ``first .. first + width`` of every group, side by side:
        lane-aligned column slices, a copy of those bytes alone, which XLA
        fuses into their reader.  (Cut through a (B, S, groups, stride)
        reshape the chip first makes a relayout copy of the WHOLE
        projection output, PERF.md section 6, PR 33: 0.61 ms a pass.)"""
        at = [g * self.stride + first for g in range(self.groups)]
        return jnp.concatenate([proj[:, :, c:c + width] for c in at], axis=-1)

    def first_of(self, out: int) -> int:
        return min(first for first, _, o, _ in self.parts if o == out)


def _conv_dims(width: int, hk: int, dk: int, dv: int):
    """``(per-head width P, value heads a key head r)`` of a projection
    output ``width`` wide laid out per key head ``[q d_k | k d_k | v r d_v |
    z r d_v]``."""
    p = width // hk
    r = (p - 2 * dk) // (2 * dv)
    if hk * p != width or r < 1 or 2 * dk + 2 * r * dv != p:
        raise ValueError(f"a width of {width} is not {hk} key heads of "
                         f"[q {dk} | k {dk} | v r x {dv} | z r x {dv}]")
    return p, r


def _qkvz_layout(width: int, hk: int, dk: int, dv: int) -> ConvLayout:
    """The delta net's: a group a key head, q, k and its ``r`` value heads
    each a part (any ``r`` keeps every part a whole number of its widths
    in), v's written side by side into one block of ``r d_v``; z handed
    through."""
    p, r = _conv_dims(width, hk, dk, dv)
    return ConvLayout(
        hk, p, ((0, dk, 0, 0), (dk, dk, 1, 0))
        + tuple((2 * dk + j * dv, dv, 2, j * dv) for j in range(r)),
        (dk, dk, r * dv), ((2 * dk + r * dv, r * dv),))


def _conv_xla(proj, w, bias, lay: ConvLayout):
    """The convolution of ``proj``'s columns in plain ``jax.numpy``: a
    group's columns split into the outputs' and what is handed through
    (together they are the whole group), each contiguous over the groups
    (XLA's strided copies), the outputs' concatenated in the convolution's
    channel order, :func:`causal_conv1d_silu`, split again."""
    b, s, _ = proj.shape
    cols = [(lay.first_of(o), width) for o, width in enumerate(lay.outs)]
    cols += lay.passed
    order = sorted(range(len(cols)), key=lambda i: cols[i][0])
    ends = np.cumsum([cols[i][1] for i in order])[:-1].tolist()
    pieces = jnp.split(proj.reshape(b, s, lay.groups, lay.stride), ends,
                       axis=-1)
    cut = [pieces[order.index(i)].reshape(b, s, -1) for i in range(len(cols))]
    outs, passed = cut[:len(lay.outs)], cut[len(lay.outs):]
    mixed = causal_conv1d_silu(jnp.concatenate(outs, axis=-1), w, bias)
    ends = np.cumsum([t.shape[-1] for t in outs])[:-1].tolist()
    return (*jnp.split(mixed, ends, axis=-1), *passed)


#: rows of the sequence a grid step of the convolution's kernels takes, at
#: most (a power of two; the largest that divides S and fits
#: :data:`_CONV_VMEM` is taken): a step pays for its rows and operands, so
#: few large blocks (PERF.md section 6, PR 25)
_CONV_ROWS = 1024
#: rows of a block the kernels work through at a time, straight-line, a lane
#: tile wide: what lives between a piece's loads and its store stays in
#: registers
_CONV_PIECE = 256
#: lanes of a tile: the width of a piece
_LANES = 128
#: rows of float32 kept in front of (forward) or behind (backward) a block
#: in VMEM for the taps that reach over its edge: one sublane tile
_HALO = 8
#: rows of the block of preceding inputs the backward kernel reads through
#: a BlockSpec of its own: one tile of a 16-bit array
_HALO_ROWS = 16
#: bytes of VMEM a grid step's blocks (twice: the next step's are in flight)
#: and scratch may take; the kernels' limit, where they ask one, is twice this
_CONV_VMEM = 32 * 1024 * 1024


def _conv_vmem(lay: ConvLayout, rows: int, itemsize: int) -> int:
    """Bytes of VMEM the backward kernel — the larger — takes at ``rows``
    rows a block: the parts, the outputs' cotangents, what is handed through
    and the group's whole gradient, twice, and a float32 row a part's lane
    for ``dy silu'(y)``."""
    read = sum(width for _, width, _, _ in lay.parts)
    moved = read + sum(lay.outs) + sum(w for _, w in lay.passed) + lay.stride
    return rows * (2 * itemsize * moved + 4 * (read + _LANES))


def _conv_tile(s: int, lay: ConvLayout, taps: int, itemsize: int):
    """``(rows of a block, rows of a piece)`` for a sequence of ``s``
    tokens — the largest power of two up to :data:`_CONV_ROWS` that divides
    it and fits :data:`_CONV_VMEM`, worked through :data:`_CONV_PIECE` rows
    at a time — or None where the kernels do not take the shapes: parts of
    whole 128-lane tiles, each a whole number of its own widths in, whole
    row blocks of at least a 16-bit tile, and taps that reach no further
    back than one sublane tile."""
    rows = _CONV_ROWS
    while rows > 1 and (s % rows or _conv_vmem(lay, rows, itemsize)
                        > _CONV_VMEM):
        rows //= 2
    tiles = all(width % _LANES == 0 and (g * lay.stride + first) % width == 0
                for first, width, _, _ in lay.parts
                for g in range(lay.groups))
    if tiles and rows >= _HALO_ROWS and 1 <= taps <= _HALO + 1:
        return rows, min(rows, _CONV_PIECE)
    return None


def conv_supported(s: int, dk: int, dv: int, r: int, taps: int) -> bool:
    """Whether the convolution's kernels take the delta net's shapes: head
    sizes of whole 128-lane tiles, every part of the per-key-head layout ``[q
    d_k | k d_k | v r d_v | z r d_v]`` starting a whole number of its own
    widths in (equal head sizes always do), whole row blocks of at least a
    16-bit tile, and taps that reach no further back than one sublane
    tile."""
    # two key heads: the second's parts start a head's whole width in
    lay = _qkvz_layout(2 * (2 * dk + 2 * r * dv), 2, dk, dv)
    return _conv_tile(s, lay, taps, 2) is not None


def _taps(w_ref, lanes, window):
    """``sum_j w[j] * window(j)``: the ``K`` multiply-adds of one piece,
    float32; ``window(j)`` the rows tap ``j`` reads."""
    acc = None
    for j in range(w_ref.shape[0]):
        term = w_ref[j:j + 1, lanes] * window(j)
        acc = term if acc is None else acc + term
    return acc


def _refs(refs, *counts):
    """``refs`` cut into runs of ``counts``, and what is left."""
    out = []
    for n in counts:
        out.append(refs[:n])
        refs = refs[n:]
    return (*out, refs)


def _lane_tiles(width: int, piece):
    """``piece(first lane)`` for every lane tile of a part ``width`` lanes
    wide: straight-line where the part is one tile, else a loop ON THE CHIP
    over the tiles — one copy of the kernel's body to trace, lower and
    compile whatever the part's width (34 copies for the 4352 ``xBC``
    channels cost every process 4.4 s of set-up, PERF.md section 6, PR
    44)."""
    if width == _LANES:
        return piece(0)

    def tile(i, carry):
        piece(pl.multiple_of(i * _LANES, _LANES))
        return carry

    jax.lax.fori_loop(0, width // _LANES, tile, 0)


def _conv_fwd_kernel(*refs, lay: ConvLayout, bias: bool, size: int):
    """Grid (groups, rows of the batch, row blocks — walked in order),
    ``size`` rows x a lane tile worked through at a time: ``refs`` = the
    parts' input blocks, w's blocks (K, width) an output, with ``bias`` the
    bias's (1, width), the output blocks, a float32 scratch (HALO + rows,
    width) a part whose first HALO rows carry the previous block's last
    ones."""
    n, outs = len(lay.parts), len(lay.outs)
    x_refs, w_refs, b_refs, o_refs, xs_refs = _refs(
        refs, n, outs, outs * bias, outs)
    f32 = jnp.float32
    rows = x_refs[0].shape[1]
    first = pl.program_id(2) == 0
    for x_ref, xs, (_, width, out, lane) in zip(x_refs, xs_refs, lay.parts):
        w_ref, o_ref = w_refs[out], o_refs[out]
        k = w_ref.shape[0]

        @pl.when(first)         # zeros before the start of EVERY row
        def _():
            xs[0:_HALO, :] = jnp.zeros((_HALO, width), f32)

        def piece(lo):
            cols, lanes = pl.ds(lo, _LANES), pl.ds(lane + lo, _LANES)
            for start in range(0, rows, size):
                xs[_HALO + start:_HALO + start + size, cols] = x_ref[
                    0, start:start + size, cols].astype(f32)
                y = _taps(w_ref, lanes, lambda j: xs[pl.ds(
                    _HALO - (k - 1) + j + start, size), cols])
                if bias:
                    y = y + b_refs[out][:, lanes]
                o_ref[0, start:start + size, lanes] = (
                    y * jax.nn.sigmoid(y)).astype(o_ref.dtype)

        _lane_tiles(width, piece)
        xs[0:_HALO, :] = xs[rows:rows + _HALO, :]


def _conv_bwd_kernel(*refs, lay: ConvLayout, bias: bool, size: int):
    """Grid (groups, rows of the batch, row blocks — walked from the LAST):
    ``refs`` = the parts' input blocks, their blocks of preceding rows, the
    blocks of the outputs' cotangents and of what was handed through, w's
    blocks (and the bias's), then the group's block of the projection's
    gradient (the parts' gradients and what was handed through side by side,
    where they lie), dw's (K, width) blocks an output (and the bias's (1,
    width)) — resident over a group's whole walk and summed into —, a float32
    scratch (HALO + rows, a lane tile) for the inputs, and one (rows + HALO,
    width) a part for ``dy silu'(y)`` whose last HALO rows carry the
    following block's first ones."""
    n, outs = len(lay.parts), len(lay.outs)
    (x_refs, h_refs, dy_refs, dp_refs, w_refs, b_refs, (dx_ref,), dw_refs,
     db_refs, (xs,), gs_refs) = _refs(
        refs, n, n, outs, len(lay.passed), outs, outs * bias, 1, outs,
        outs * bias, 1)
    f32 = jnp.float32
    rows = x_refs[0].shape[1]
    last_block = pl.program_id(2) == 0          # the walk's first step
    first_block = pl.program_id(2) == pl.num_programs(2) - 1

    @pl.when(jnp.logical_and(pl.program_id(1) == 0, last_block))
    def _():
        for ref in (*dw_refs, *db_refs):
            ref[...] = jnp.zeros_like(ref)

    for x_ref, h_ref, gs, (at, width, out, lane) in zip(x_refs, h_refs,
                                                        gs_refs, lay.parts):
        dy_ref, w_ref, dw_ref = dy_refs[out], w_refs[out], dw_refs[out]
        k = w_ref.shape[0]

        @pl.when(last_block)    # nothing follows a row's last token
        def _():
            gs[rows:rows + _HALO, :] = jnp.zeros((_HALO, width), f32)

        def piece(lo):
            cols, lanes = pl.ds(lo, _LANES), pl.ds(lane + lo, _LANES)
            halo = h_ref[0, :, cols].astype(f32)[h_ref.shape[1] - _HALO:]
            xs[0:_HALO, :] = jnp.where(first_block, 0.0, halo)
            for start in range(0, rows, size):
                xs[_HALO + start:_HALO + start + size, :] = x_ref[
                    0, start:start + size, cols].astype(f32)
            dw = [jnp.zeros((1, _LANES), f32)] * (k + bias)
            for start in reversed(range(0, rows, size)):
                window = [xs[pl.ds(_HALO - (k - 1) + j + start, size), :]
                          for j in range(k)]
                y = _taps(w_ref, lanes, lambda j: window[j])
                if bias:
                    y = y + b_refs[out][:, lanes]
                sig = jax.nn.sigmoid(y)
                g = (dy_ref[0, start:start + size, lanes].astype(f32)
                     * (sig * (1.0 + y * (1.0 - sig))))
                gs[start:start + size, cols] = g
                terms = [g * t for t in window] + [g] * bias
                dw = [acc + jnp.sum(t, axis=0, keepdims=True)
                      for acc, t in zip(dw, terms)]
                # the taps the other way: row t's input fed outputs t .. t + K-1
                dx = _taps(w_ref, lanes, lambda j: gs[pl.ds(
                    start + (k - 1) - j, size), cols])
                dx_ref[0, start:start + size, pl.ds(at + lo, _LANES)] = (
                    dx.astype(dx_ref.dtype))
            for j in range(k):
                dw_ref[j:j + 1, lanes] += dw[j]
            if bias:
                db_refs[out][:, lanes] += dw[k]

        _lane_tiles(width, piece)
        gs[rows:rows + _HALO, :] = gs[0:_HALO, :]
    for (at, width), dp_ref in zip(lay.passed, dp_refs):
        dx_ref[0, :, at:at + width] = dp_ref[0]


def _conv_specs(lay: ConvLayout, rows: int, taps: int, block_of):
    """BlockSpecs over a grid (groups, rows of the batch, row blocks), step
    ``i`` of the last axis taking row block ``block_of(i)``: ``(the parts'
    blocks of the projection's output, their blocks of the HALO_ROWS
    preceding rows, the outputs' blocks, the blocks of what is handed
    through, w's blocks, the bias's, a group's whole block of the
    projection's output)``."""
    def rows_of(width, col):
        return pl.BlockSpec((1, rows, width),
                            lambda g, b, i: (b, block_of(i), col(g)))

    def halo_of(width, col):
        per = rows // _HALO_ROWS
        return pl.BlockSpec(
            (1, _HALO_ROWS, width),
            lambda g, b, i: (b, jnp.maximum(block_of(i) * per - 1, 0), col(g)))

    # a part's first column in the projection's output, in blocks of its width
    cols = [(lambda g, c=first, w=width: (g * lay.stride + c) // w)
            for first, width, _, _ in lay.parts]
    widths = [width for _, width, _, _ in lay.parts]
    group = lambda g: g
    per_out = lambda n: [pl.BlockSpec((n, width), lambda g, b, i: (0, g))
                         for width in lay.outs]
    return ([rows_of(w, c) for w, c in zip(widths, cols)],
            [halo_of(w, c) for w, c in zip(widths, cols)],
            [rows_of(width, group) for width in lay.outs],
            [rows_of(width, group) for _, width in lay.passed],
            per_out(taps), per_out(1), rows_of(lay.stride, group))


def _conv_weights(w, bias, lay: ConvLayout):
    """``w`` (channels, K) — its channels over the outputs in order, each
    over all groups — as float32 arrays (K, groups x width) an output: a tap
    is a row of lanes (32 K values); then the bias's (1, groups x width),
    where there is one."""
    ends = np.cumsum([lay.groups * width for width in lay.outs])[:-1].tolist()
    rows = [w.astype(jnp.float32).T]
    if bias is not None:
        rows.append(bias.astype(jnp.float32)[None, :])
    return [part for t in rows for part in jnp.split(t, ends, axis=1)]


def _conv_params(proj, lay: ConvLayout, rows: int, *semantics):
    """The grid's semantics, and a VMEM limit of their own for blocks past
    what the compiler grants a kernel unasked (16 MiB on a v5e)."""
    past = _conv_vmem(lay, rows, proj.dtype.itemsize) > 12 * 1024 * 1024
    return pltpu.CompilerParams(
        dimension_semantics=semantics,
        vmem_limit_bytes=2 * _CONV_VMEM if past else None)


def _conv_fwd_pallas(proj, w, bias, lay: ConvLayout, tile):
    b, s, _ = proj.shape
    rows, piece = tile
    taps = w.shape[1]
    x_specs, _, out_specs, _, w_specs, b_specs, _ = _conv_specs(
        lay, rows, taps, lambda i: i)
    has_bias = bias is not None
    return _pallas_call(
        functools.partial(_conv_fwd_kernel, lay=lay, bias=has_bias,
                          size=piece),
        name="apex_conv1d_fwd", grid=(lay.groups, b, s // rows),
        in_specs=[*x_specs, *w_specs, *(b_specs if has_bias else [])],
        out_specs=out_specs,
        out_shape=[jax.ShapeDtypeStruct((b, s, lay.groups * width), proj.dtype)
                   for width in lay.outs],
        scratch_shapes=[pltpu.VMEM((_HALO + rows, width), jnp.float32)
                        for _, width, _, _ in lay.parts],
        compiler_params=_conv_params(proj, lay, rows, "parallel", "parallel",
                                     "arbitrary"),
    )(*[proj] * len(lay.parts), *_conv_weights(w, bias, lay))


def _conv_bwd_pallas(proj, w, bias, dys, lay: ConvLayout, tile):
    """``(d proj, dw, dbias)``: the projection's gradient written where it
    lies, a group's parts and what it handed through side by side."""
    b, s, _ = proj.shape
    n_parts, n_outs = len(lay.parts), len(lay.outs)
    taps = w.shape[1]
    rows, piece = tile
    n = s // rows
    x_specs, halo_specs, dy_specs, dp_specs, w_specs, b_specs, dx_spec = \
        _conv_specs(lay, rows, taps, lambda i: n - 1 - i)
    has_bias = bias is not None
    weights = _conv_weights(w, bias, lay)
    small = [*w_specs, *(b_specs if has_bias else [])]
    dx, *dwb = _pallas_call(
        functools.partial(_conv_bwd_kernel, lay=lay, bias=has_bias,
                          size=piece),
        name="apex_conv1d_bwd", grid=(lay.groups, b, n),
        in_specs=[*x_specs, *halo_specs, *dy_specs, *dp_specs, *small],
        out_specs=[dx_spec, *small],
        out_shape=[jax.ShapeDtypeStruct(proj.shape, proj.dtype),
                   *(jax.ShapeDtypeStruct(x.shape, jnp.float32)
                     for x in weights)],
        scratch_shapes=[pltpu.VMEM((_HALO + rows, _LANES), jnp.float32)]
        + [pltpu.VMEM((rows + _HALO, width), jnp.float32)
           for _, width, _, _ in lay.parts],
        compiler_params=_conv_params(proj, lay, rows, "parallel", "arbitrary",
                                     "arbitrary"),
    )(*[proj] * (2 * n_parts), *dys, *weights)
    dw = jnp.concatenate(dwb[:n_outs], axis=1).T.astype(w.dtype)
    if not has_bias:
        return dx, dw, None
    return dx, dw, jnp.concatenate(dwb[n_outs:], axis=1)[0].astype(bias.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _conv_kernels(proj, w, bias, lay, tile):
    return (*_conv_fwd_pallas(proj, w, bias, lay, tile),
            *(lay.cut(proj, *cols) for cols in lay.passed))


def _conv_kernels_fwd(proj, w, bias, lay, tile):
    return _conv_kernels(proj, w, bias, lay, tile), (proj, w, bias)


def _conv_kernels_bwd(lay, tile, res, dys):
    return _conv_bwd_pallas(*res, dys, lay, tile)


_conv_kernels.defvjp(_conv_kernels_fwd, _conv_kernels_bwd)


# Called through jit, as the rule below: a model's layers share one trace.
@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _conv_jit(proj, w, bias, lay, tile, trace_key):
    del trace_key
    if tile:
        return _conv_kernels(proj, w, bias, lay, tile)
    return _conv_xla(proj, w, bias, lay)


def conv_columns(proj, w, bias, lay: ConvLayout, use_pallas: Optional[bool],
                 gauge: str):
    """The short convolution of ``proj``'s columns as ``lay`` describes
    them: ``(the outputs, what is handed through)``, a tuple — through the
    kernels on the TPU where the shapes tile (:func:`_conv_tile`), else in
    ``jax.numpy``; the gauge ``gauge`` says which was traced.  What the
    public operators share: :func:`split_conv_qkvz` and
    ``ops/ssd.py::split_conv_xbc`` each describe a layout and call this."""
    channels = lay.groups * sum(lay.outs)
    if w.shape[0] != channels or (bias is not None
                                  and bias.shape != (channels,)):
        raise ValueError(f"w {w.shape} and bias "
                         f"{None if bias is None else bias.shape} are not "
                         f"the convolution's {channels} channels")
    tile = _conv_tile(proj.shape[1], lay, w.shape[1], proj.dtype.itemsize)
    if use_pallas is None:
        use_pallas = pallas_default(tile is not None)
    elif use_pallas and tile is None:
        raise ValueError(f"the convolution's kernels want parts of whole "
                         f"tiles of 128 lanes, each a whole number of its "
                         f"widths in, rows in blocks of {_HALO_ROWS} and at "
                         f"most {_HALO + 1} taps: got {proj.shape}, "
                         f"{w.shape}, {lay}")
    from apex_tpu import obs

    obs.default_registry().gauge(gauge).set(int(use_pallas))
    return _conv_jit(proj, w, bias, lay, tile if use_pallas else None,
                     _trace_key())


def split_conv_qkvz(qkvz, w, *, key_heads: int, key_dim: int,
                    value_dim: int, use_pallas: Optional[bool] = None):
    """``in_proj_qkvz``'s output cut into its four parts, q, k and v through
    the delta net's short convolution on the way — read where they lie.

    ``qkvz`` (B, S, H_k (2 d_k + 2 r d_v)) laid out per KEY head ``[q d_k |
    k d_k | v r d_v | z r d_v]``, ``w`` (2 H_k d_k + H_v d_v, K) with its
    channels in the order ``[q | k | v]`` over all heads.  Returns ``(q (B,
    S, H_k d_k), k, v (B, S, H_v d_v), z)`` in ``qkvz``'s dtype, each
    contiguous over its heads; q, k, v are :func:`causal_conv1d_silu` of the
    three taken together — float32 taps and SiLU, one rounding at the output
    —, z is a copy.  Differentiable in ``qkvz`` and ``w``.

    On the TPU, where the shapes tile (:func:`conv_supported`), two kernels.
    ``apex_conv1d_fwd`` takes a block of rows x one key head's q, k and v
    columns through BlockSpecs on ``qkvz`` itself (z is not read: its copy
    stays XLA's), keeps the ``K - 1`` preceding rows in VMEM from the block
    before (zeros at every row's start) and writes q, k, v.
    ``apex_conv1d_bwd`` walks the row blocks from the last: ``y`` again,
    ``dy silu'(y)``, the taps the other way (dx), dw summed in float32 over
    the blocks, and writes the PROJECTION's gradient where it lies, a key
    head's ``[dq | dk | dv | dz]`` side by side — dz handed through, because
    XLA's interleave of the four is three relayout passes over an array of
    ``qkvz``'s size (PERF.md section 6, PR 33).  No concatenated, no float32
    and no padded array crosses HBM.  Else the same in ``jax.numpy``
    (:func:`_conv_xla`).  The gauge ``gdn.conv_kernel`` says which was
    traced."""
    lay = _qkvz_layout(qkvz.shape[2], key_heads, key_dim, value_dim)
    return conv_columns(qkvz, w, None, lay, use_pallas, "gdn.conv_kernel")


# ---------------------------------------------------------------------------
# the token recurrence: the definition, and the oracle of the tests
# ---------------------------------------------------------------------------

def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The rule token by token (``lax.scan`` over the sequence), float32 at
    ``highest`` precision: the definition the chunked form is held to.
    Shapes as :func:`gated_delta_rule`."""
    hi = jax.lax.Precision.HIGHEST
    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x                   # (B, H, d) / (B, H)
        state = state * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * r,
                                   precision=hi)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)

    b, _, h, dk = q.shape
    init = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, init, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


# ---------------------------------------------------------------------------
# (I + A)^-1 for a strictly lower-triangular A
# ---------------------------------------------------------------------------

def _tri_inverse(a):
    """Doubling over the diagonal blocks: with ``T`` the inverses of the
    diagonal blocks of size ``b`` (block diagonal), those of size ``2 b``
    are ``T - T E T`` where ``E`` holds ``A``'s entries in the lower-left
    quarter of each ``2 b`` block (from ``T = I`` the first step is ``I -
    E``).  log2(C) - 1 steps of two C x C products: no loop over rows,
    nothing that is not a matrix product."""
    c = a.shape[-1]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    quarter = lambda b: ((row // (2 * b) == col // (2 * b))
                         & (row // b > col // b))
    t = jnp.eye(c, dtype=a.dtype) - jnp.where(quarter(1), a, 0.0)
    b = 2
    while b < c:
        t = t - t @ jnp.where(quarter(b), a, 0.0) @ t
        b *= 2
    return t


@jax.custom_vjp
def tri_inverse(a):
    """``(I + A)^-1`` over the last two axes; ``A`` (..., C, C) strictly
    lower triangular (entries on and above the diagonal are NOT read as
    zero: hand over zeros), ``C`` a power of two.  The gradient is ``-T^T
    dT T^T`` on the strict lower triangle: two products, and ``T`` the
    only residual."""
    return _tri_inverse(a)


def _tri_inverse_fwd(a):
    # declared to the block-recomputing policies (apex_tpu.remat): ten
    # dependent products to make again, one C x C matrix a chunk to hold
    t = checkpoint_name(_tri_inverse(a), GDN_TRI)
    return t, t


def _tri_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    c = t.shape[-1]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    return (jnp.where(strict, -(tt @ dt @ tt), 0.0),)


tri_inverse.defvjp(_tri_inverse_fwd, _tri_inverse_bwd)


# ---------------------------------------------------------------------------
# what is local to a chunk
# ---------------------------------------------------------------------------

def _chunk_local(q, k, v, g, beta):
    """``(W, U0, Qg, P, Kd, c)`` of every chunk at once.  ``q``, ``k``
    (N, BH, C, d_k), ``v`` (N, BH, C, d_v), ``g``, ``beta`` (N, BH, C), all
    float32.  Every decay is ``exp`` of a masked non-positive difference."""
    c = q.shape[2]
    big_g = jnp.cumsum(g, axis=-1)
    lower = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    diff = big_g[..., :, None] - big_g[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))       # M, diagonal 1
    kk = jnp.einsum("nhid,nhjd->nhij", k, k)
    a = jnp.where(strict, beta[..., :, None] * decay * kk, 0.0)
    t = tri_inverse(a)
    gamma = jnp.exp(big_g)
    w = jnp.einsum("nhij,nhjd->nhid", t, (beta * gamma)[..., None] * k)
    u0 = jnp.einsum("nhij,nhjd->nhid", t, beta[..., None] * v)
    qg = gamma[..., None] * q
    p = decay * jnp.einsum("nhid,nhjd->nhij", q, k)
    to_end = jnp.exp(big_g[..., -1:] - big_g)
    return w, u0, qg, p, to_end[..., None] * k, jnp.exp(big_g[..., -1])


# ---------------------------------------------------------------------------
# the chain over the chunks (lax.scan): the path off the TPU, the oracle
# ---------------------------------------------------------------------------

def _chain_fwd_scan(w, u0, qg, p, kd, c):
    """``(O (N, BH, C, d_v), the state at each chunk's start (N, BH, d_k,
    d_v))``."""
    def body(s, x):
        w_, u0_, qg_, p_, kd_, c_ = x
        u = u0_ - w_ @ s
        o = qg_ @ s + p_ @ u
        return (c_[:, None, None] * s + jnp.swapaxes(kd_, -1, -2) @ u,
                (o, s))

    init = jnp.zeros((w.shape[1], w.shape[3], u0.shape[3]), jnp.float32)
    _, (o, states) = jax.lax.scan(body, init, (w, u0, qg, p, kd, c))
    return o, states


def _chain_bwd_scan(w, u0, qg, p, kd, c, states, do):
    tr = lambda t: jnp.swapaxes(t, -1, -2)

    def body(ds_next, x):
        w_, u0_, qg_, p_, kd_, c_, s, do_ = x
        u = u0_ - w_ @ s
        du = tr(p_) @ do_ + kd_ @ ds_next
        ds = tr(qg_) @ do_ + c_[:, None, None] * ds_next - tr(w_) @ du
        return ds, (-du @ tr(s), du, do_ @ tr(s), do_ @ tr(u),
                    u @ tr(ds_next), jnp.sum(s * ds_next, axis=(-1, -2)))

    _, grads = jax.lax.scan(body, jnp.zeros_like(states[0]),
                            (w, u0, qg, p, kd, c, states, do), reverse=True)
    return grads


# ---------------------------------------------------------------------------
# the scan path's chain, differentiable
# ---------------------------------------------------------------------------

@jax.custom_vjp
def _chain(w, u0, qg, p, kd, c):
    """``O`` (N, BH, C, d_v) float32 of the chunks chained from a zero
    state (the three lines of the module's docstring)."""
    return _chain_fwd_scan(w, u0, qg, p, kd, c)[0]


def _chain_fwd_rule(w, u0, qg, p, kd, c):
    o, states = _chain_fwd_scan(w, u0, qg, p, kd, c)
    # declared to the block-recomputing policies (apex_tpu.remat), as the
    # flash kernel declares its output: where a policy keeps these names
    # the backward pass does not walk the chunks forward a second time
    o = checkpoint_name(o, GDN_OUT)
    states = checkpoint_name(states, GDN_STATES)
    return o, (w, u0, qg, p, kd, c, states)


def _chain_bwd_rule(res, do):
    return _chain_bwd_scan(*res, do.astype(jnp.float32))


_chain.defvjp(_chain_fwd_rule, _chain_bwd_rule)


# ---------------------------------------------------------------------------
# the rule in kernels: what is local to a chunk made in VMEM
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _heads_per_step(hk: int, hv: int):
    """``(key heads, value heads)`` a grid step of the kernels takes: whole
    key heads, each with its ``hv // hk`` value heads, the most that divide
    ``hk`` and stay within :data:`_HEADS_PER_STEP` value heads (one key
    head where its value heads alone are more)."""
    r = hv // hk
    kb = max((n for n in range(1, hk + 1)
              if hk % n == 0 and n * r <= _HEADS_PER_STEP), default=1)
    return kb, kb * r


def _chunk_masks(c):
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    return row, col


def _decays(gc, gr, row, col):
    """``(M, gamma, delta, gamma_C)`` of one head from its running sum as a
    column ``gc`` (C, 1) and as a row ``gr`` (1, C): ``M_ij = exp(G_i -
    G_j)`` for ``j <= i`` — the difference clamped at 0 BEFORE the
    exponential, so the upper triangle's positive differences never reach
    it —, ``exp(G)``, ``exp(G_C - G)`` and ``exp(G_C)``."""
    c = gc.shape[0]
    eye = (row == col).astype(jnp.float32)
    decay = jnp.where(row > col, jnp.exp(jnp.minimum(gc - gr, 0.0)), eye)
    last = gc[c - 1:c, :]
    return decay, jnp.exp(gc), jnp.exp(jnp.minimum(last - gc, 0.0)), \
        jnp.exp(last)


def _tri_inverse_tiles(tiles, row, col, mx):
    """:func:`_tri_inverse` on (C, C) tiles inside a kernel, the tiles'
    doubling steps side by side: a step's two products wait on each other,
    those of different tiles do not.  ``T`` stays float32 between the
    steps; the products take ``mx``'s operands."""
    c = tiles[0].shape[0]
    quarter = lambda s: (((row >> (s + 1)) == (col >> (s + 1)))
                         & ((row >> s) > (col >> s)))
    eye = (row == col).astype(jnp.float32)
    ts = [eye - jnp.where(quarter(0), a, 0.0) for a in tiles]
    s = 1
    while (1 << s) < c:
        tes = [mx(_dot(mx(t), mx(jnp.where(quarter(s), a, 0.0)), _NN))
               for t, a in zip(ts, tiles)]
        ts = [t - _dot(te, mx(t), _NN) for t, te in zip(ts, tes)]
        s += 1
    return ts


def _step_heads(q_ref, k_ref, gc_ref, gr_ref, bc_ref, dk, r, heads):
    """What both kernels make first, for every value head of the grid step
    (lists by head; a key head's entries shared by its ``r`` value heads):
    ``q``, ``k`` (C, d_k), ``K K^T``, ``Q K^T``, the decays and ``beta``
    (C, 1)."""
    c = q_ref.shape[1]
    row, col = _chunk_masks(c)
    g_cols, g_rows, betas = gc_ref[0, 0], gr_ref[0, 0, 0], bc_ref[0, 0]
    q = [q_ref[0, :, i * dk:(i + 1) * dk] for i in range(heads // r)]
    k = [k_ref[0, :, i * dk:(i + 1) * dk] for i in range(heads // r)]
    kk = [_dot(x, x, _NT) for x in k]
    qk = [_dot(x, y, _NT) for x, y in zip(q, k)]
    of_value_head = lambda xs: [xs[h // r] for h in range(heads)]
    decays = [_decays(g_cols[:, h:h + 1], g_rows[h:h + 1, :], row, col)
              for h in range(heads)]
    return (row, col, *map(of_value_head, (q, k, kk, qk)), *zip(*decays),
            [betas[:, h:h + 1] for h in range(heads)])


def _each(fn, *lists):
    return [fn(*xs) for xs in zip(*lists)]


# Both kernels are written a LINE of the arithmetic at a time over all heads
# of the grid step, not a head at a time: a head's products wait on each
# other (ten in a row in the inverse, four from ``K S`` to the new state)
# and a 64-row product alone leaves the MXU idle for most of its latency —
# side by side the heads' chains fill it (one layer at qwen3-next's shape,
# forward / with gradients: 1.83 / 4.15 ms against 5.50 / 9.41 a head at a
# time, PERF.md section 5, PR 31).

def _rule_fwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, bc_ref,
                     o_ref, s_ref, t_ref, state, *, key_heads: int, r: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    f32 = jnp.float32
    dk, dv = state.shape[1:]
    heads = key_heads * r
    mx = lambda x: x.astype(q_ref.dtype)    # an operand, as it enters a product
    (row, col, q, k, kk, qk, decay, gamma, delta, gamma_c,
     beta) = _step_heads(q_ref, k_ref, gc_ref, gr_ref, bc_ref, dk, r, heads)
    t = _tri_inverse_tiles(
        _each(lambda b, m, x: jnp.where(row > col, b * m * x, 0.0),
              beta, decay, kk), row, col, mx)
    s = [state[h] for h in range(heads)]
    sm = _each(mx, s)
    v = [v_ref[0, :, h * dv:(h + 1) * dv].astype(f32) for h in range(heads)]
    ks = _each(lambda x, y: _dot(x, y, _NN), k, sm)
    qs = _each(lambda x, y: _dot(x, y, _NN), q, sm)
    rhs = _each(lambda b, x, g, y: mx(b * (x - g * y)), beta, v, gamma, ks)
    u = _each(lambda x, y: mx(_dot(mx(x), y, _NN)), t, rhs)
    o = _each(lambda g, x, m, y, z: g * x + _dot(mx(m * y), z, _NN),
              gamma, qs, decay, qk, u)
    new = _each(lambda g, x, d, y, z: (g + jnp.zeros((1, dv), f32)) * x
                + _dot(mx(d * y.astype(f32)), z, _TN),
                gamma_c, s, delta, k, u)
    for h in range(heads):
        s_ref[0, 0, h] = s[h]
        t_ref[0, 0, h] = mx(t[h])
        o_ref[0, :, h * dv:(h + 1) * dv] = o[h].astype(o_ref.dtype)
        state[h] = new[h]


def _rule_bwd_kernel(q_ref, k_ref, v_ref, gc_ref, gr_ref, bc_ref, s_ref,
                     t_ref, do_ref, dq_ref, dk_ref, dv_ref, dgc_ref, dgr_ref,
                     dbc_ref, dstate, *, key_heads: int, r: int):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    f32 = jnp.float32
    c = q_ref.shape[1]
    dk, dv = dstate.shape[1:]
    heads = key_heads * r
    mx = lambda x: x.astype(q_ref.dtype)
    rows = lambda x: jnp.sum(x, axis=1, keepdims=True)
    dot = lambda dims: lambda x, y: _dot(x, y, dims)
    (row, col, q, k, kk, qk, decay, gamma, delta, gamma_c,
     beta) = _step_heads(q_ref, k_ref, gc_ref, gr_ref, bc_ref, dk, r, heads)
    strict = row > col
    is_last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    k32 = _each(lambda x: x.astype(f32), k)
    s = [s_ref[0, 0, h] for h in range(heads)]
    ds_next = [dstate[h] for h in range(heads)]
    t = [t_ref[0, 0, h] for h in range(heads)]
    do = [do_ref[0, :, h * dv:(h + 1) * dv] for h in range(heads)]
    do32 = _each(lambda x: x.astype(f32), do)
    sm, dsm = _each(mx, s), _each(mx, ds_next)
    # the forward's values, made again
    ks, qs = _each(dot(_NN), k, sm), _each(dot(_NN), q, sm)
    resid = [v_ref[0, :, h * dv:(h + 1) * dv].astype(f32) - gamma[h] * ks[h]
             for h in range(heads)]
    rhs = _each(lambda b, x: mx(b * x), beta, resid)
    u = _each(lambda x, y: mx(_dot(x, y, _NN)), t, rhs)
    # the three lines that touch the state
    du = _each(lambda m, x, y, d, z, w: mx(_dot(mx(m * x), y, _TN)
                                           + _dot(mx(d * z), w, _NN)),
               decay, qk, do, delta, k32, dsm)
    dp = _each(dot(_NT), do, u)
    dkd = _each(dot(_NT), u, dsm)
    # U = T (beta (V - gamma K S)),  T = (I + A)^-1:  dA = -T^T dT T^T
    dr = _each(dot(_TN), t, du)
    dtri = _each(lambda x, y, z: mx(_dot(mx(_dot(x, y, _NT)), z, _NT)),
                 du, rhs, t)
    da = _each(lambda x, y: jnp.where(strict, -_dot(x, y, _TN), 0.0), t, dtri)
    dks = _each(lambda b, g, x: mx(-(b * g) * x), beta, gamma, dr)
    dqs = _each(lambda g, x: mx(g * x), gamma, do32)
    new = _each(lambda g, x, y, z, w, a: (g + jnp.zeros((1, dv), f32)) * x
                + _dot(y, z, _TN) + _dot(w, a, _TN),
                gamma_c, ds_next, q, dqs, k, dks)
    dqk = _each(lambda m, x: mx(m * x), decay, dp)
    dkk = _each(lambda x, b, m: mx(x * b * m), da, beta, decay)
    dq = _each(lambda x, y, z, w: _dot(x, y, _NT) + _dot(z, w, _NN),
               dqs, sm, dqk, k)
    dk_ = _each(lambda x, y, z, w, a, b, d, e: _dot(x, y, _NT)
                + _dot(z, w, _TN) + _dot(a, b, _NN) + _dot(a, b, _TN) + d * e,
                dks, sm, dqk, q, dkk, k, delta, dkd)
    # beta, and the running sum through every decay
    e = _each(lambda x, y, a, b, z, m: (x * y + a * b * z) * m,
              dp, qk, da, beta, kk, decay)
    d_delta = _each(lambda x, y, d: rows(x * y) * d, dkd, k32, delta)
    for h in range(heads):
        dstate[h] = new[h]
        dv_ref[0, :, h * dv:(h + 1) * dv] = (beta[h] * dr[h]).astype(
            dv_ref.dtype)
        dbc_ref[0, 0, :, h:h + 1] = (rows(dr[h] * resid[h])
                                     + rows(da[h] * decay[h] * kk[h]))
        at_end = (jnp.sum(d_delta[h], axis=0, keepdims=True) + gamma_c[h]
                  * jnp.sum(rows(s[h] * ds_next[h]), axis=0, keepdims=True))
        dgc_ref[0, 0, :, h:h + 1] = (
            rows(e[h]) + gamma[h] * (rows(do32[h] * qs[h])
                                     - beta[h] * rows(dr[h] * ks[h]))
            - d_delta[h] + jnp.where(is_last, at_end, 0.0))
        dgr_ref[0, 0, 0, h:h + 1, :] = -jnp.sum(e[h], axis=0, keepdims=True)
    for i in range(key_heads):      # a key head's r value heads, summed
        dq_ref[0, :, i * dk:(i + 1) * dk] = sum(
            dq[i * r:(i + 1) * r]).astype(dq_ref.dtype)
        dk_ref[0, :, i * dk:(i + 1) * dk] = sum(
            dk_[i * r:(i + 1) * r]).astype(dk_ref.dtype)


def _small_layouts(x, n, hb):
    """``x`` (B, N C, H_v) as float32 columns (B, H_v / hb, N C, hb) and
    rows (B, H_v / hb, N, hb, C): the two shapes a kernel reads a head's
    (C, 1) and (1, C) from (1 MB arrays, XLA's to lay out)."""
    b, s, hv = x.shape
    x = x.astype(jnp.float32).reshape(b, n, s // n, hv // hb, hb)
    return (x.transpose(0, 3, 1, 2, 4).reshape(b, hv // hb, s, hb),
            x.transpose(0, 3, 1, 4, 2))


def _small_inputs(g, beta, n, hb):
    """``(G as columns, G as rows, beta as columns)`` for the kernels, ``G``
    each chunk's running sum of ``g``: ``jnp.cumsum`` over the (B, S, H_v)
    array, the scan path's own — a prefix SHARED by ``G_i`` and ``G_j``
    keeps their difference's roundoff to the additions between them, and
    both layouts hold the same values.  (In the kernel a sum along sublanes
    and lanes alike is a product with a triangle of ones: every ``G_i`` a
    sum of its own, 3-5 times the scan path's error against float64 at
    ``|G|`` ~ 80.)"""
    b, s, hv = g.shape
    big_g = jnp.cumsum(g.astype(jnp.float32).reshape(b, n, s // n, hv), axis=2)
    return (*_small_layouts(big_g.reshape(b, s, hv), n, hb),
            _small_layouts(beta, n, hb)[0])


def _blocks(c, kb, hb, dk, dv, chunk_of):
    """The kernels' BlockSpecs over grid (rows, head groups, chunks), grid
    step ``i`` walking chunk ``chunk_of(i)``: ``(q | k at kb key heads, v |
    o | do at hb value heads — blocks of the (B, S, heads x d) arrays —, a
    (B, S, H_v) array as columns, as rows, the states, the T's)``."""
    wide = lambda heads, d: pl.BlockSpec(
        (1, c, heads * d), lambda b, h, i: (b, chunk_of(i), h))
    per_head = lambda *tile: pl.BlockSpec(
        (1, 1, hb) + tile, lambda b, h, i: (b, chunk_of(i), h, 0, 0))
    return (wide(kb, dk), wide(hb, dv),
            pl.BlockSpec((1, 1, c, hb), lambda b, h, i: (b, h, chunk_of(i), 0)),
            pl.BlockSpec((1, 1, 1, hb, c),
                         lambda b, h, i: (b, h, chunk_of(i), 0, 0)),
            per_head(dk, dv), per_head(c, c))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"))


def _rule_fwd_pallas(q, k, v, g, beta, chunk):
    """``q``, ``k`` (B, S, H_k, d_k), ``v`` (B, S, H_v, d_v), all in v's
    dtype, ``g``, ``beta`` (B, S, H_v), ``S`` whole chunks.  ``(o (B, S, H_v,
    d_v) in v's dtype, the state at each chunk's start (B, N, H_v, d_k, d_v)
    float32, each chunk's T (B, N, H_v, C, C) in v's dtype)``."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n, c = s // chunk, chunk
    kb, hb = _heads_per_step(hk, hv)
    keys, values, cols, rows, states, tri = _blocks(
        c, kb, hb, dk, dv, lambda i: i)
    o, states, tri = _pallas_call(
        functools.partial(_rule_fwd_kernel, key_heads=kb, r=hv // hk),
        name="apex_gdn_fwd", grid=(b, hk // kb, n),
        in_specs=[keys, keys, values, cols, rows, cols],
        out_specs=[values, states, tri],
        out_shape=[jax.ShapeDtypeStruct((b, s, hv * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, n, hv, dk, dv), jnp.float32),
                   jax.ShapeDtypeStruct((b, n, hv, c, c), v.dtype)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), *_small_inputs(g, beta, n, hb))
    return o.reshape(b, s, hv, dv), states, tri


def _rule_bwd_pallas(q, k, v, g, beta, states, tri, do, chunk):
    """The gradients of :func:`_rule_fwd_pallas`'s ``o`` in its five inputs,
    the chunks walked from the last."""
    b, s, hk, dk = q.shape
    hv, dv = v.shape[2:]
    n, c = s // chunk, chunk
    kb, hb = _heads_per_step(hk, hv)
    keys, values, cols, rows, per_state, per_tri = _blocks(
        c, kb, hb, dk, dv, lambda i: n - 1 - i)
    small = _small_inputs(g, beta, n, hb)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
    dq, dk_, dv_, dg_cols, dg_rows, dbeta_cols = _pallas_call(
        functools.partial(_rule_bwd_kernel, key_heads=kb, r=hv // hk),
        name="apex_gdn_bwd", grid=(b, hk // kb, n),
        in_specs=[keys, keys, values, cols, rows, cols, per_state, per_tri,
                  values],
        out_specs=[keys, keys, values, cols, rows, cols],
        out_shape=[jax.ShapeDtypeStruct((b, s, hk * dk), q.dtype),
                   jax.ShapeDtypeStruct((b, s, hk * dk), k.dtype),
                   jax.ShapeDtypeStruct((b, s, hv * dv), v.dtype),
                   *map(like, small)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
    )(q.reshape(b, s, hk * dk), k.reshape(b, s, hk * dk),
      v.reshape(b, s, hv * dv), *small, states, tri,
      do.reshape(b, s, hv * dv))
    from_cols = lambda x: x.reshape(b, hv // hb, n, c, hb).transpose(
        0, 2, 3, 1, 4).reshape(b, s, hv)
    from_rows = lambda x: x.transpose(0, 2, 4, 1, 3).reshape(b, s, hv)
    # dG, the gradient of a chunk's running sum, summed back over the tokens
    # that follow in the chunk (1 MB, XLA's)
    big_dg = (from_cols(dg_cols) + from_rows(dg_rows)).reshape(b, n, c, hv)
    dg = jnp.flip(jnp.cumsum(jnp.flip(big_dg, 2), axis=2), 2)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(b, s, hv).astype(g.dtype),
            from_cols(dbeta_cols).astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def _rule_kernels(q, k, v, g, beta, chunk: int):
    return _rule_fwd_pallas(q, k, v, g, beta, chunk)[0]


def _rule_kernels_fwd(q, k, v, g, beta, chunk):
    o, states, tri = _rule_fwd_pallas(q, k, v, g, beta, chunk)
    # declared to the block-recomputing policies (apex_tpu.remat), as the
    # flash kernel declares its output: where a policy keeps these names
    # the recomputed block's forward rule is dead code
    o = checkpoint_name(o, GDN_OUT)
    states = checkpoint_name(states, GDN_STATES)
    tri = checkpoint_name(tri, GDN_TRI)
    return o, (q, k, v, g, beta, states, tri)


def _rule_kernels_bwd(chunk, res, do):
    return _rule_bwd_pallas(*res, do, chunk)


_rule_kernels.defvjp(_rule_kernels_fwd, _rule_kernels_bwd)


def supported(chunk: int, dk: int, dv: int) -> bool:
    """Whether the kernels take these shapes: blocks whose last two axes
    are the arrays' own, lanes of 128."""
    return chunk % 8 == 0 and dk % 128 == 0 and dv % 128 == 0


def _trace_key():
    return jax.default_backend()


# Called through jit so that a model's linear-attention layers — every one
# the same call — share ONE trace and ONE lowering (PERF.md section 6, PR 25
# and PR 27: kernels traced once a layer doubled warm set-up).
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _rule_jit(q, k, v, g, beta, chunk, kernels, trace_key):
    del trace_key
    b, s, hk, dk = q.shape
    h, dv = v.shape[2:]
    pad = (-s) % chunk
    n = (s + pad) // chunk
    # the padding tokens (zero k, beta, g) leave state and outputs as they are
    padded = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    if kernels:
        # the products' operands are v's dtype: q and k are rounded to it
        # once, here (a cast their producer absorbs), not in every product
        args = (q.astype(v.dtype), k.astype(v.dtype), v, g, beta)
        return _rule_kernels(*(map(padded, args) if pad else args),
                             chunk)[:, :s]

    def chunks(t):
        """(B, S, H, ...) -> (N, B H, C, ...), float32."""
        t = padded(t.astype(jnp.float32))
        t = t.reshape((b, n, chunk, h) + t.shape[3:])
        t = jnp.moveaxis(jnp.moveaxis(t, 3, 1), 2, 0)      # (N, B, H, C, ...)
        return t.reshape((n, b * h, chunk) + t.shape[4:])

    if hk != h:                   # each value head beside its key head's q, k
        q, k = (jnp.repeat(t, h // hk, axis=2) for t in (q, k))
    local = _chunk_local(*map(chunks, (q, k, v, g, beta)))
    o = _chain(*local)                                      # (N, BH, C, dv)
    o = o.reshape(n, b, h, chunk, dv).transpose(1, 0, 3, 2, 4)
    return o.reshape(b, n * chunk, h, dv)[:, :s].astype(v.dtype)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                     use_pallas: Optional[bool] = None):
    """The gated delta rule over every row of a batch, by chunks.

    ``q``, ``k`` (B, S, H_k, d_k) — already normalised and scaled as the
    model wants them —, ``v`` (B, S, H, d_v) with ``H`` a multiple of
    ``H_k`` (value head ``h`` reads key head ``h // (H / H_k)``), ``g`` (B,
    S, H) the log-decay (<= 0), ``beta`` (B, S, H).  Returns (B, S, H, d_v)
    in ``v``'s dtype; the arithmetic is float32, the products at JAX's
    default precision (on the TPU the MXU's bfloat16 pass with float32
    accumulation, as the model's other products): an operand is rounded
    once, where it enters a product, and the state, the running sums, the
    decays and ``T`` stay float32 between products.  Each row starts from a
    zero state; ``S`` need not be whole chunks.  Differentiable in all five.

    On the TPU, where the shapes tile (:func:`supported`), the whole rule
    runs in the kernels ``apex_gdn_fwd`` / ``apex_gdn_bwd``, whose products
    take their operands in ``v``'s dtype (q and k are cast to it on the way
    in); else by ``_chunk_local`` and ``lax.scan`` in float32.  The gauges
    ``gdn.kernels`` and ``gdn.local_in_kernel`` say which was traced (both 1
    for the kernels: they make what is local to a chunk themselves), beside
    ``gdn.chunk``, ``gdn.chunks_per_row`` and ``gdn.value_heads``."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    if v.shape[2] % q.shape[2] or k.shape != q.shape:
        raise ValueError(f"value heads must be a multiple of the key heads "
                         f"q and k share: got {q.shape}, {k.shape}, {v.shape}")
    ok = supported(chunk, q.shape[-1], v.shape[-1])
    if use_pallas is None:
        use_pallas = pallas_default(ok)
    elif use_pallas and not ok:
        raise ValueError(f"the kernels want head sizes of 128 lanes and "
                         f"chunks of 8 rows: got {q.shape}, {v.shape}, {chunk}")
    from apex_tpu import obs

    reg = obs.default_registry()
    reg.gauge("gdn.chunk").set(chunk)
    reg.gauge("gdn.chunks_per_row").set(-(-q.shape[1] // chunk))
    reg.gauge("gdn.value_heads").set(v.shape[2])
    reg.gauge("gdn.kernels").set(int(use_pallas))
    reg.gauge("gdn.local_in_kernel").set(int(use_pallas))
    return _rule_jit(q, k, v, g, beta, chunk, bool(use_pallas), _trace_key())
