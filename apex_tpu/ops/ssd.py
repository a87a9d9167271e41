"""The state-space scan of a Mamba-2 layer (SSD, "state-space duality") over
a sequence, by chunks — two Pallas TPU kernels that hold every (chunk, chunk)
tensor in VMEM, a ``jax.numpy`` chunked path, and the token recurrence as the
oracle — and the short causal depthwise convolution with a bias in front of
it, read out of the layer's projection where it lies
(:func:`split_conv_xbc`: ``ops/gated_delta.py``'s convolution kernels under
this layer's layout).

The second sequential operator of ``ops/`` (the first is
``ops/gated_delta.py``'s delta rule): a state-space layer
(``models/granite_hybrid.py::Mamba2Mixer``) keeps, per head, a float32 state
``S`` of shape ``(P, N)`` — ``P`` the head's channels, ``N`` the state size —
and walks the sequence::

    a_t = dt_t * A                      # the log-decay, <= 0 (A < 0, dt > 0)
    S_t = exp(a_t) S_{t-1} + dt_t * x_t B_t^T
    o_t = S_t C_t + D * x_t

``B_t``, ``C_t`` (N,) are shared by the heads of a group (``G`` groups; the
kernels take ``G = 1``), ``dt_t`` is the step size after its softplus, ``A``
and ``D`` one scalar a head.  No ``beta``, no triangular inverse: the decay is
a scalar a head a token.  No reference counterpart (apex has no state-space
layer).

**The chunked form.**  Inside a chunk of ``Q`` tokens that starts from state
``S_0``, with ``l_i`` the running sum of ``a`` over the chunk (inclusive)::

    O  = (L . C B^T) (dt x) + diag(exp l) C S_0^T + D x,     L_ij = exp(l_i - l_j)  (j <= i)
    S' = exp(l_Q) S_0 + ((exp(l_Q - l) dt x)^T B)

``C B^T`` (Q, Q) is ONE product a chunk for all the heads of a group; the
decay matrix ``L`` is a head's own.  Everything but the two terms in ``S_0``
is local to a chunk; they chain the chunks.

**On the TPU the scan is two kernels.**  ``apex_ssd_fwd`` (grid (rows of the
batch, chunks — walked in order, head groups)) reads a chunk's x, B and C as
blocks of the arrays the model has — ``(B, S, heads x P)`` and ``(B, S, N)``
in its compute dtype —, dt and the running sum ``l`` as float32 columns and
rows, makes ``C B^T`` once a chunk (the head groups are the grid's innermost
axis: B and C are read once a chunk, the product kept in VMEM scratch),
every head's ``L`` and ``L . C B^T`` in VMEM, and writes o and the state at
each chunk's START (``B x chunks x heads`` states of ``N x P`` float32,
never a state per token); the states of ALL the heads live in VMEM scratch
between a chunk and the next (2 MB at 64 heads of 64 x 128).  ``apex_ssd_bwd``
walks the chunks from the last with ``dS`` in scratch: from x, dt, ``l``, B,
C, the chunk's starting state and ``do`` it makes what is local to a chunk
again and writes dx, dB and dC (summed over the heads in float32 scratch,
``sum_h dM_h . L_h`` first and ONE product with B and with C a chunk), and —
as float32 columns and rows a head — the gradients of ``l``, of ``dt`` where
it multiplies ``x``, and of ``D``.  So the scan's HBM traffic is its inputs,
its outputs, their gradients and one state a chunk a head: the float32
``(chunks, heads, Q, Q)`` decay matrix the chunked form costs under XLA (537
MB a layer a pass at 8192 tokens, 64 heads, Q = 256) never exists.  XLA keeps
the (B, S, H) arrays: ``l`` is ``jnp.cumsum`` of ``dt * A`` inside each
chunk, laid out twice (a kernel reads a head's column (Q, 1) from one and its
row (1, Q) from the other), and the gradient of ``l`` is summed back over the
chunk into dt's and A's — 2 MB each.

**Heads side by side.**  A state is kept TRANSPOSED, ``(N, P)``, and the
states of a grid step's heads side by side along the lanes, ``(N, heads x
P)``: ``C S_0^T`` and ``B^T (…)`` of all those heads are then one product
each a 128-lane tile.  A head of 64 channels is half a lane tile: the
products a head has to itself (``(L . C B^T) (dt x)``: its own (Q, Q) matrix)
are made a TILE of the lanes wide — both heads' channels — and each head
keeps its half: the MXU pass is 128 wide either way.

**Off the TPU, and as the kernels' oracle,** :func:`ssd_recurrent` (the
token recurrence by ``lax.scan``, float32) and the chunked form in
``jax.numpy`` (batched products over all chunks, the chunks chained by
``lax.scan``; differentiated by JAX).  The same path takes the shapes
:func:`supported` refuses.

**Precision.**  Float32 arithmetic with the products at default precision: in
the kernels an operand is cast to x's dtype — the model's compute dtype —
where it enters a product and nowhere else; ``dt``, ``l``, every decay, the
states, ``dS`` and the accumulators stay float32.  With float32 inputs (the
tests, in interpret mode) the kernels compute in float32 throughout.

**The trap** is ``ops/gated_delta.py``'s: a head's log-decay reaches -16 a
token (``A = -16``, ``dt`` 1), -4000 over a chunk of 256.  Every decay is
``exp(l_i - l_j)`` for ``i >= j`` only, the difference clamped at 0 BEFORE
the exponential; factored as ``exp(l_i) * exp(-l_j)`` the second factor
overflows inside one chunk.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import pallas_call as _pallas_call, pallas_default
from apex_tpu.ops.gated_delta import (_NN, _NT, _TN, ConvLayout, _dot,
                                      _trace_key, conv_columns)

__all__ = ["ssd_scan", "ssd_recurrent", "split_conv_xbc", "supported",
           "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 256
#: heads a grid step of the kernels takes together, at most: their states
#: side by side are the (N, heads x P) operand of the products with B and C
_HEADS_PER_STEP = 8
#: lanes of a tile: the width the kernels work through at a time
_LANES = 128


def _xbc_layout(d_inner: int, d_bc: int, heads: int) -> ConvLayout:
    """``in_proj``'s output ``[z d_in | x d_in | B | C | dt H]`` as ONE group:
    x, B and C each a part and an output of its own (x starts ``d_in`` in,
    one block of its width; B and C whole blocks of ``G N`` where ``2 d_in``
    is), z and dt handed through."""
    return ConvLayout(
        1, 2 * d_inner + 2 * d_bc + heads,
        ((d_inner, d_inner, 0, 0), (2 * d_inner, d_bc, 1, 0),
         (2 * d_inner + d_bc, d_bc, 2, 0)),
        (d_inner, d_bc, d_bc),
        ((0, d_inner), (2 * d_inner + 2 * d_bc, heads)))


def split_conv_xbc(zxbcdt, w, bias, *, d_inner: int, d_bc: int,
                   use_pallas: Optional[bool] = None):
    """A Mamba-2 layer's ``in_proj`` output cut into its five parts, x, B and
    C through the layer's short convolution on the way — read where they lie.

    ``zxbcdt`` (b, s, 2 d_in + 2 G N + H) laid out ``[z d_in | x d_in | B G N
    | C G N | dt H]``, ``w`` (d_in + 2 G N, K) and ``bias`` (d_in + 2 G N,)
    with their channels in the order ``[x | B | C]``; ``d_bc`` is ``G N``.
    Returns ``(z, x (b, s, d_in), B (b, s, G N), C, dt (b, s, H))`` in
    ``zxbcdt``'s dtype; x, B, C are ``causal_conv1d_silu`` of the three taken
    together with the bias — float32 taps, bias and SiLU, one rounding at the
    output —, z and dt are copies.  Differentiable in ``zxbcdt``, ``w`` and
    ``bias``.

    On the TPU, where the shapes tile (x, B and C of whole 128-lane tiles,
    ``2 d_in`` a multiple of ``G N``, rows in blocks of 16, at most 9 taps),
    ``ops/gated_delta.py``'s two kernels under this layout: ``apex_conv1d_fwd``
    takes a block of rows x the 4352 ``xBC`` columns through three BlockSpecs
    on ``zxbcdt`` itself (z and dt are not read), a lane tile at a time, and
    writes x, B and C as the three arrays the scan's kernels take;
    ``apex_conv1d_bwd`` walks the row blocks from the last and writes the
    projection's whole gradient ``[dz | dx | dB | dC | ddt]`` where it lies
    — dz and ddt handed through —, dw and dbias summed in float32.  No
    concatenated, no float32 and no padded array crosses HBM (under XLA the
    same arithmetic is a padded float32 copy and ``K`` shifted float32
    passes: 49.6 ms a step of ``granite-h.train-8k`` for 6 ms of bytes,
    PERF.md section 6, PR 44).  Else the same in ``jax.numpy``.  The gauge
    ``ssd.conv_kernel`` says which was traced."""
    heads = zxbcdt.shape[-1] - 2 * (d_inner + d_bc)
    if zxbcdt.ndim != 3 or heads < 0:
        raise ValueError(f"zxbcdt {zxbcdt.shape} is not (b, s, [z {d_inner} | "
                         f"x {d_inner} | B {d_bc} | C {d_bc} | dt])")
    lay = _xbc_layout(d_inner, d_bc, heads)
    x, bm, cm, z, dt = conv_columns(zxbcdt, w, bias, lay, use_pallas,
                                    "ssd.conv_kernel")
    return z, x, bm, cm, dt


def ssd_recurrent(x, dt, A, B, C, D):
    """The recurrence token by token, float32: the oracle.

    ``x`` (b, s, H, P), ``dt`` (b, s, H) after its softplus, ``A`` (H,)
    negative, ``B``, ``C`` (b, s, G, N) — head ``h`` reads group ``h // (H /
    G)`` —, ``D`` (H,).  Returns (b, s, H, P) float32; each row starts from a
    zero state.  Differentiated it keeps a state a token (17 GB at 8192
    tokens of 64 heads of 64 x 128): for small sizes."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    A, D = A.astype(f32), D.astype(f32)
    B, C = (jnp.repeat(t, h // g, axis=2) for t in (B, C))      # (b, s, H, N)
    hi = jax.lax.Precision.HIGHEST

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(dt_t * A)                                # (b, H)
        state = (state * decay[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        o_t = jnp.einsum("bhpn,bhn->bhp", state, c_t, precision=hi)
        return state, o_t + D[None, :, None] * x_t

    seq_first = lambda t: jnp.moveaxis(t, 1, 0)
    _, o = jax.lax.scan(token, jnp.zeros((b, h, p, n), f32),
                        tuple(map(seq_first, (x, dt, B, C))))
    return jnp.moveaxis(o, 0, 1)


def _decay_tile(lower, l_col, l_row):
    """``L_ij = exp(l_i - l_j)`` for ``j <= i``, else 0: the difference
    clamped at 0 before the exponential."""
    return jnp.where(lower, jnp.exp(jnp.minimum(l_col - l_row, 0.0)), 0.0)


def _chunked(x, dt, A, B, C, D, chunk):
    """The chunked form in ``jax.numpy``, float32, on whole chunks: batched
    products over all chunks, the chunks chained by ``lax.scan``."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    g, n = B.shape[2:]
    nc = s // chunk
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    A, D = A.astype(f32), D.astype(f32)
    chunks = lambda t: t.reshape((b, nc, chunk) + t.shape[2:])
    xc, dtc, bc, cc = map(chunks, (x, dt, B, C))
    l = jnp.cumsum(dtc * A, axis=2)                              # (b, nc, Q, H)
    xdt = xc * dtc[..., None]
    per = h // g
    grouped = lambda t: t.reshape(t.shape[:3] + (g, per) + t.shape[4:])
    cb = jnp.einsum("bcign,bcjgn->bcgij", cc, bc)                # (b, nc, G, Q, Q)
    lh = jnp.moveaxis(l, 3, 2)                                   # (b, nc, H, Q)
    decay = _decay_tile(jnp.tril(jnp.ones((chunk, chunk), bool)),
                        lh[..., :, None], lh[..., None, :])
    decay = decay.reshape(b, nc, g, per, chunk, chunk)
    intra = jnp.einsum("bcgij,bcgkij,bcjgkp->bcigkp", cb, decay,
                       grouped(xdt)).reshape(b, nc, chunk, h, p)
    last = l[:, :, -1:, :]                                       # (b, nc, 1, H)
    to_end = jnp.exp(jnp.minimum(last - l, 0.0))
    # each chunk's own contribution to the state at its end: (b, nc, H, P, N)
    grown = jnp.einsum("bcjgkp,bcjgn->bcgkpn",
                       grouped(xdt * to_end[..., None]), bc
                       ).reshape(b, nc, h, p, n)

    def chain(state, inp):
        grown_c, last_c = inp
        new = state * jnp.exp(last_c)[..., None, None] + grown_c
        return new, state

    _, starts = jax.lax.scan(
        chain, jnp.zeros((b, h, p, n), f32),
        (jnp.moveaxis(grown, 1, 0), jnp.moveaxis(last[:, :, 0], 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                          # (b, nc, H, P, N)
    inter = jnp.einsum("bcign,bcgkpn->bcigkp", cc,
                       starts.reshape(b, nc, g, per, p, n)
                       ).reshape(b, nc, chunk, h, p)
    o = intra + jnp.exp(l)[..., None] * inter + D[:, None] * xc
    return o.reshape(b, s, h, p)


# -- the kernels --------------------------------------------------------------

def _heads_per_step(h: int, p: int) -> int:
    """Heads a grid step takes: whole lane tiles' worth, the most that divide
    ``h`` within :data:`_HEADS_PER_STEP`."""
    per = _LANES // p
    return max((n for n in range(per, min(h, _HEADS_PER_STEP) + 1, per)
                if h % n == 0), default=0)


def supported(chunk: int, heads: int, p: int, n: int, groups: int = 1) -> bool:
    """Whether the kernels take these shapes: one group of B and C, heads
    whose channels fill lane tiles side by side, whole tiles of a 16-bit
    array a chunk."""
    return (groups == 1 and chunk % 16 == 0 and p <= _LANES
            and _LANES % p == 0 and n % 8 == 0
            and _heads_per_step(heads, p) > 0)


def _pick(lane, p, vals):
    """The value of each lane's head: ``vals[k]`` — (rows, 1) or (1, 1) —
    where the lane lies in the tile's ``k``-th head of ``p`` lanes."""
    out = vals[-1]
    for k in range(len(vals) - 2, -1, -1):
        out = jnp.where(lane < (k + 1) * p, vals[k], out)
    return out


def _own(lane, p, k, per):
    """The lanes of the tile's ``k``-th head (None: the whole tile is one
    head's)."""
    if per == 1:
        return None
    return jnp.logical_and(lane >= k * p, lane < (k + 1) * p)


def _head_sum(lane, p, k, per, a):
    """The sum of ``a`` (rows, lanes) over head ``k``'s lanes: (rows, 1)."""
    own = _own(lane, p, k, per)
    a = a if own is None else jnp.where(own, a, 0.0)
    return jnp.sum(a, axis=1, keepdims=True)


def _fwd_kernel(x_ref, dt_ref, lc_ref, lr_ref, b_ref, c_ref, d_ref,
                o_ref, s_ref, state, cb, *, p: int):
    """Grid (rows of the batch, chunks in order, head groups).  A chunk of x
    (Q, heads x P), dt and ``l`` as columns (Q, heads), ``l`` as rows (heads,
    Q), B and C (Q, N), D a lane a channel; o, the group's states at the
    chunk's start (N, heads x P); scratch: every group's states, ``C B^T``."""
    f32 = jnp.float32
    ci, j = pl.program_id(1), pl.program_id(2)
    q, width = x_ref.shape[1], x_ref.shape[2]
    per = _LANES // p
    mx = lambda t: t.astype(x_ref.dtype)    # an operand, as it enters a product

    @pl.when(ci == 0)
    def _():
        state[j] = jnp.zeros(state.shape[1:], f32)

    @pl.when(j == 0)
    def _():
        cb[...] = _dot(c_ref[0], b_ref[0], _NT)

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = row >= col
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    lcs, lrs, dts = lc_ref[0, 0], lr_ref[0, 0, 0], dt_ref[0, 0]
    last = lcs[q - 1:q, :]
    # exp(l), exp(l_Q - l) and exp(l_Q) of every head of the step, once
    grown, left, whole = (jnp.exp(lcs), jnp.exp(jnp.minimum(last - lcs, 0.0)),
                          jnp.exp(last))
    cmat, bmat = c_ref[0], b_ref[0]
    s_prev = state[j]
    s_ref[0, 0, 0] = s_prev
    for t in range(width // _LANES):
        sl = slice(t * _LANES, (t + 1) * _LANES)
        heads = range(t * per, (t + 1) * per)
        cols = lambda a: _pick(lane, p, [a[:, h:h + 1] for h in heads])
        x = x_ref[0, :, sl].astype(f32)
        xdt32 = x * cols(dts)
        xdt = mx(xdt32)
        intra = None
        for k, h in enumerate(heads):
            m = mx(cb[...] * _decay_tile(lower, lcs[:, h:h + 1],
                                         lrs[h:h + 1, :]))
            prod = _dot(m, xdt, _NN)
            own = _own(lane, p, k, per)
            intra = prod if intra is None else jnp.where(own, prod, intra)
        s_tile = s_prev[:, sl]
        inter = _dot(cmat, mx(s_tile), _NN)
        o = intra + cols(grown) * inter + d_ref[:, sl] * x
        o_ref[0, :, sl] = o.astype(o_ref.dtype)
        state[j, :, sl] = (cols(whole) * s_tile
                           + _dot(bmat, mx(cols(left) * xdt32), _TN))


def _bwd_kernel(x_ref, dt_ref, lc_ref, lr_ref, b_ref, c_ref, d_ref, s_ref,
                do_ref, dx_ref, db_ref, dc_ref, dlc_ref, dlr_ref, ddt_ref,
                dd_ref, dstate, cb, dcb, db_acc, dc_acc, *, p: int):
    """Grid (rows of the batch, chunks from the LAST, head groups).  The
    forward's inputs, the group's states at the chunk's start and do; dx, dB
    and dC (written at the chunk's last head group), and as columns (Q,
    heads) the gradient of ``l`` (its row part as rows), of dt where it
    multiplies x, and of D a token.  Scratch: every group's ``dS``, ``C
    B^T``, ``sum_h dM_h . L_h``, dB and dC in float32."""
    f32 = jnp.float32
    i, j = pl.program_id(1), pl.program_id(2)
    q, width = x_ref.shape[1], x_ref.shape[2]
    per = _LANES // p
    mx = lambda t: t.astype(x_ref.dtype)

    @pl.when(i == 0)            # nothing follows a row's last chunk
    def _():
        dstate[j] = jnp.zeros(dstate.shape[1:], f32)

    @pl.when(j == 0)
    def _():
        cb[...] = _dot(c_ref[0], b_ref[0], _NT)
        dcb[...] = jnp.zeros_like(dcb)
        db_acc[...] = jnp.zeros_like(db_acc)
        dc_acc[...] = jnp.zeros_like(dc_acc)

    row = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    lower = row >= col
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)
    is_last = jax.lax.broadcasted_iota(jnp.int32, (q, 1), 0) == q - 1
    lcs, lrs, dts = lc_ref[0, 0], lr_ref[0, 0, 0], dt_ref[0, 0]
    last = lcs[q - 1:q, :]
    grown, left, whole = (jnp.exp(lcs), jnp.exp(jnp.minimum(last - lcs, 0.0)),
                          jnp.exp(last))
    cmat, bmat = c_ref[0], b_ref[0]
    s_prev, ds_new = s_ref[0, 0, 0], dstate[j]
    for t in range(width // _LANES):
        sl = slice(t * _LANES, (t + 1) * _LANES)
        heads = range(t * per, (t + 1) * per)
        cols = lambda a: _pick(lane, p, [a[:, h:h + 1] for h in heads])
        x = x_ref[0, :, sl].astype(f32)
        dy = do_ref[0, :, sl]
        dy32 = dy.astype(f32)
        dt_t = cols(dts)
        xdt32 = x * dt_t
        xdt = mx(xdt32)
        s_tile, ds_tile = s_prev[:, sl], ds_new[:, sl]
        sm, dsm = mx(s_tile), mx(ds_tile)
        # the two terms in the chunk's starting state, the other way
        gamma, gamma_end, to_end = cols(grown), cols(whole), cols(left)
        inter = _dot(cmat, sm, _NN)
        dg = mx(gamma * dy32)
        dc_acc[...] += _dot(dg, sm, _NT)
        dstate[j, :, sl] = gamma_end * ds_tile + _dot(cmat, dg, _TN)
        dw = _dot(bmat, dsm, _NN)
        db_acc[...] += _dot(mx(to_end * xdt32), dsm, _NT)
        dxdt = to_end * dw
        # what a head has to itself
        intra, e_rows = None, []
        for k, h in enumerate(heads):
            own = _own(lane, p, k, per)
            decay = _decay_tile(lower, lcs[:, h:h + 1], lrs[h:h + 1, :])
            m32 = cb[...] * decay
            dm = _dot(dy if own is None else jnp.where(own, dy, 0), xdt, _NT)
            e = dm * m32
            e_rows.append(jnp.sum(e, axis=1, keepdims=True))
            dlr_ref[0, 0, 0, h:h + 1, :] = -jnp.sum(e, axis=0, keepdims=True)
            dcb[...] += dm * decay
            prod = _dot(mx(m32), dy, _TN)
            intra = prod if intra is None else jnp.where(own, prod, intra)
        dxdt = dxdt + intra
        dx_ref[0, :, sl] = (dt_t * dxdt + d_ref[:, sl] * dy32).astype(
            dx_ref.dtype)
        of_head = lambda a, k: _head_sum(lane, p, k, per, a)
        d_gamma, d_to_end = dy32 * inter * gamma, dw * xdt32 * to_end
        d_dt, d_d = dxdt * x, dy32 * x
        state_dot = jnp.sum(ds_tile * s_tile, axis=0, keepdims=True)
        for k, h in enumerate(heads):
            back = of_head(d_to_end, k)
            at_end = (jnp.sum(back, axis=0, keepdims=True)
                      + whole[:, h:h + 1] * of_head(state_dot, k))
            dlc_ref[0, 0, :, h:h + 1] = (
                e_rows[k] + of_head(d_gamma, k) - back
                + jnp.where(is_last, at_end, 0.0))
            ddt_ref[0, 0, :, h:h + 1] = of_head(d_dt, k)
            dd_ref[0, 0, :, h:h + 1] = of_head(d_d, k)

    @pl.when(j == pl.num_programs(2) - 1)
    def _():
        total = mx(dcb[...])
        dc_ref[0] = (dc_acc[...] + _dot(total, bmat, _NN)).astype(dc_ref.dtype)
        db_ref[0] = (db_acc[...] + _dot(total, cmat, _TN)).astype(db_ref.dtype)


def _small(dt, A, n: int, hb: int):
    """``(dt as columns, l as columns, l as rows)`` for the kernels, float32,
    ``l`` each chunk's running sum of ``dt * A``: ``jnp.cumsum`` over the (B,
    S, H) array — both layouts hold the same values.  Columns (B, H / hb, S,
    hb), rows (B, H / hb, N, hb, Q)."""
    b, s, h = dt.shape
    dt = dt.astype(jnp.float32)
    l = jnp.cumsum((dt * A.astype(jnp.float32)).reshape(b, n, s // n, h),
                   axis=2)
    cols = lambda t: t.reshape(b, s, h // hb, hb).transpose(0, 2, 1, 3)
    rows = l.reshape(b, n, s // n, h // hb, hb).transpose(0, 3, 1, 4, 2)
    return cols(dt), cols(l.reshape(b, s, h)), rows


def _specs(q: int, hb: int, p: int, n: int, chunk_of):
    """The kernels' BlockSpecs over grid (rows, chunks, head groups), grid
    step ``i`` walking chunk ``chunk_of(i)``: ``(x | o | do, a column array,
    the row array, B | C, D's lanes, the states)``."""
    width = hb * p
    return (
        pl.BlockSpec((1, q, width), lambda r, i, j: (r, chunk_of(i), j)),
        pl.BlockSpec((1, 1, q, hb), lambda r, i, j: (r, j, chunk_of(i), 0)),
        pl.BlockSpec((1, 1, 1, hb, q),
                     lambda r, i, j: (r, j, chunk_of(i), 0, 0)),
        pl.BlockSpec((1, q, n), lambda r, i, j: (r, chunk_of(i), 0)),
        pl.BlockSpec((1, width), lambda r, i, j: (0, j)),
        pl.BlockSpec((1, 1, 1, n, width),
                     lambda r, i, j: (r, chunk_of(i), j, 0, 0)))


_COMPILER_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary", "arbitrary"),
    vmem_limit_bytes=64 * 1024 * 1024)


def _d_lanes(D, p: int):
    return jnp.repeat(D.astype(jnp.float32), p)[None, :]


def _fwd_pallas(x, dt, A, B, C, D, chunk):
    """``x`` (b, S, H x P), ``dt`` (b, S, H), ``B``, ``C`` (b, S, N), ``S``
    whole chunks.  ``(o like x, the state at each chunk's start (b, chunks, H
    / hb, N, hb x P) float32)``."""
    b, s, hp = x.shape
    h, n = dt.shape[2], B.shape[2]
    p, nc = hp // h, s // chunk
    hb = _heads_per_step(h, p)
    wide, cols, rows, shared, lanes, states = _specs(chunk, hb, p, n,
                                                     lambda i: i)
    return _pallas_call(
        functools.partial(_fwd_kernel, p=p),
        name="apex_ssd_fwd", grid=(b, nc, h // hb),
        in_specs=[wide, cols, cols, rows, shared, shared, lanes],
        out_specs=[wide, states],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, h // hb, n, hb * p),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((h // hb, n, hb * p), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
    )(x, *_small(dt, A, nc, hb), B, C, _d_lanes(D, p))


def _bwd_pallas(x, dt, A, B, C, D, states, do, chunk):
    """The gradients of :func:`_fwd_pallas`'s ``o`` in its six inputs, the
    chunks walked from the last."""
    f32 = jnp.float32
    b, s, hp = x.shape
    h, n = dt.shape[2], B.shape[2]
    p, nc = hp // h, s // chunk
    hb = _heads_per_step(h, p)
    groups = h // hb
    wide, cols, rows, shared, lanes, per_state = _specs(
        chunk, hb, p, n, lambda i: nc - 1 - i)
    small = _small(dt, A, nc, hb)
    col_shape = jax.ShapeDtypeStruct(small[0].shape, f32)
    dx, db, dc, dl_cols, dl_rows, ddt_cols, dd_cols = _pallas_call(
        functools.partial(_bwd_kernel, p=p),
        name="apex_ssd_bwd", grid=(b, nc, groups),
        in_specs=[wide, cols, cols, rows, shared, shared, lanes, per_state,
                  wide],
        out_specs=[wide, shared, shared, cols, rows, cols, cols],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(B.shape, B.dtype),
                   jax.ShapeDtypeStruct(C.shape, C.dtype),
                   col_shape, jax.ShapeDtypeStruct(small[2].shape, f32),
                   col_shape, col_shape],
        scratch_shapes=[pltpu.VMEM((groups, n, hb * p), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, chunk), f32),
                        pltpu.VMEM((chunk, n), f32),
                        pltpu.VMEM((chunk, n), f32)],
        compiler_params=_COMPILER_PARAMS,
    )(x, *small, B, C, _d_lanes(D, p), states, do)
    from_cols = lambda t: t.transpose(0, 2, 1, 3).reshape(b, s, h)
    from_rows = lambda t: t.transpose(0, 2, 4, 1, 3).reshape(b, s, h)
    # dl, the gradient of a chunk's running sum, summed back over the tokens
    # that follow in the chunk: the gradient of a = dt * A (2 MB, XLA's)
    dl = (from_cols(dl_cols) + from_rows(dl_rows)).reshape(b, nc, chunk, h)
    da = jnp.flip(jnp.cumsum(jnp.flip(dl, 2), axis=2), 2).reshape(b, s, h)
    dt32, a32 = dt.astype(f32), A.astype(f32)
    return (dx, (from_cols(ddt_cols) + da * a32).astype(dt.dtype),
            jnp.sum(da * dt32, axis=(0, 1)).astype(A.dtype), db, dc,
            jnp.sum(from_cols(dd_cols), axis=(0, 1)).astype(D.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _kernels(x, dt, A, B, C, D, chunk: int):
    return _fwd_pallas(x, dt, A, B, C, D, chunk)[0]


def _kernels_fwd(x, dt, A, B, C, D, chunk):
    o, states = _fwd_pallas(x, dt, A, B, C, D, chunk)
    return o, (x, dt, A, B, C, D, states)


def _kernels_bwd(chunk, res, do):
    return _bwd_pallas(*res, do, chunk)


_kernels.defvjp(_kernels_fwd, _kernels_bwd)


# Called through jit so that a model's state-space layers — every one the
# same call — share ONE trace and ONE lowering (as the delta rule's).
@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _jit(x, dt, A, B, C, D, chunk, kernels, trace_key):
    del trace_key
    b, s, h, p = x.shape
    pad = (-s) % chunk
    # the padding tokens (dt 0: no decay, nothing added) leave the state as
    # it is; their outputs are cut off
    padded = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    if pad:
        x, dt, B, C = map(padded, (x, dt, B, C))
    if kernels:
        o = _kernels(x.reshape(b, s + pad, h * p), dt,
                     A, B[:, :, 0].astype(x.dtype), C[:, :, 0].astype(x.dtype),
                     D, chunk).reshape(x.shape)
    else:
        o = _chunked(x, dt, A, B, C, D, chunk).astype(x.dtype)
    return o[:, :s]


def ssd_scan(x, dt, A, B, C, D, *, chunk: int = DEFAULT_CHUNK,
             use_pallas: Optional[bool] = None):
    """The state-space scan over every row of a batch, by chunks.

    ``x`` (b, s, H, P) in the compute dtype, ``dt`` (b, s, H) float32 after
    its softplus, ``A`` (H,) negative, ``B``, ``C`` (b, s, G, N) with ``H`` a
    multiple of ``G`` (head ``h`` reads group ``h // (H / G)``), ``D`` (H,).
    Returns ``o`` (b, s, H, P) in ``x``'s dtype: ``o_t = S_t C_t + D x_t``,
    ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``, each row from a zero
    state; ``s`` need not be whole chunks (the row is padded with tokens of
    ``dt`` 0).  Differentiable in all six.

    On the TPU, where the shapes tile (:func:`supported`: one group, heads
    that fill lane tiles), the two kernels ``apex_ssd_fwd`` / ``apex_ssd_bwd``,
    whose products take their operands in ``x``'s dtype (B and C are cast to
    it on the way in); else the chunked form in ``jax.numpy``, float32.  The
    gauge ``ssd.kernel`` says which was traced."""
    if x.ndim != 4 or dt.shape != x.shape[:3] or B.shape != C.shape \
            or B.shape[:2] != x.shape[:2] or x.shape[2] % B.shape[2]:
        raise ValueError(f"x (b, s, H, P), dt (b, s, H), B and C (b, s, G, N) "
                         f"with H a multiple of G: got {x.shape}, {dt.shape}, "
                         f"{B.shape}, {C.shape}")
    if chunk <= 0 or chunk % 8:
        raise ValueError(f"chunk must be a positive multiple of 8, got {chunk}")
    ok = supported(chunk, x.shape[2], x.shape[3], B.shape[3], B.shape[2])
    if use_pallas is None:
        use_pallas = pallas_default(ok)
    elif use_pallas and not ok:
        raise ValueError(f"the scan's kernels want one group of B and C, "
                         f"heads that fill 128-lane tiles and chunks of 16 "
                         f"rows: got {x.shape}, {B.shape}, chunk {chunk}")
    from apex_tpu import obs

    obs.default_registry().gauge("ssd.kernel").set(int(use_pallas))
    return _jit(x, dt, A, B, C, D, chunk, bool(use_pallas), _trace_key())
