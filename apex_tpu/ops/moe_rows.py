"""Row movement of the dropless expert layer — Pallas TPU kernels.

``parallel/moe.py::ExpertShardMLP`` sorts its token-slots by expert into a
row buffer sized for the worst case (``ops/grouped_mm.py``'s tile-aligned
layout).  About an eighth of that buffer holds a token, and a token holds
about one of its ``k`` slots; a row gather by XLA (``jnp.take``) pays for
every row of the buffer and every slot all the same.  The kernels here move
only what is live, one DMA a row, so their cost follows the routing.

Records.  Mosaic moves whole (8, 128) tiles: one row of a tiled (n, d) array
— 128 lanes of one sublane in each of ``d / 128`` tiles — cannot be the
source of a DMA.  A source is therefore handed over as RECORDS, float32
``(n, d / 128, 128)``: row ``r`` is the ``d / 128`` sublanes of record ``r``,
contiguous, exactly the row's values (bfloat16 widens without rounding).
:func:`records` makes them of a token-major array (XLA, every row: there
every row is live); ``apex_moe_records`` (:func:`live_records`) of the row
buffer, live tiles only.  In VMEM a kernel turns records back into rows
with sublane-strided loads.

Any ``d`` that is a multiple of 128 goes through.  Where ``d / 128`` is a
multiple of 8 (2048: 16) a record is whole tiles.  Where it is not (2560:
20) the tiled layout itself pads it: the last two dimensions of the record
array are tiled (8, 128), so in HBM a record lies on ``_stride(d / 128)``
sublanes (24), the next whole tile count, and a kernel's VMEM buffer gives
every record that many; a DMA moves the record's own ``d / 128`` sublanes
to the start of its place, the loads step by the stride and read lane tile
``l < d / 128`` only, so the padding is never read.  The shapes say all of
it: no argument tells the kernels apart.

- ``apex_moe_gather`` (:func:`gather_rows`), row-major: ``out[r] =
  src[idx[r]]`` for the rows of the buffer that hold a token, times the
  row's weight where weights are given (a scalar from SMEM times the
  record; product in float32, then cast).  Grid over the buffer's tiles;
  the tile's indices arrive as an SMEM block, the records stay in HBM.  A
  tile past ``layout.tiles_used`` starts no DMA, names the last live tile's
  blocks and is NEVER WRITTEN: its rows are undefined.  The rows of a live
  tile past its ``tile_valid`` are zeros.
- ``apex_moe_combine`` (:func:`combine_rows`), token-major: ``out[t] =
  sum_j rows[slot_row[t, j]] (* weights[t, j])`` over the HELD slots
  (``slot_row < capacity``) only, in slot order, in float32.  Grid over
  blocks of tokens.  A step does not ask each of its ``block * k`` slots
  whether it is held (a scalar loop that cost 1.0 ms of a 1.6 ms call at
  the cell's shape, seven eighths of it on slots that are not): a group's
  rows are in token order, so the block's held slots are one range of rows
  in each group (``starts``), and each such row names its slot
  (``row_slot``, whole in SMEM).  A slot that is not held starts no DMA
  and adds zero.
- ``apex_moe_combine_dw`` (:func:`slot_dots`), the same body: ``out[t, j] =
  rows[slot_row[t, j]] . g[t]`` for the held slots, zero for the others.

The ``jnp.take`` functions of ``parallel/moe.py`` compute the same off the
TPU and are the tests' oracle.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import pallas_call as _pallas_call
from apex_tpu.ops.grouped_mm import GroupLayout

__all__ = ["records", "live_records", "gather_rows", "combine_rows",
           "slot_dots", "supported", "combine_block"]

_LANES = 128
_COMBINE_BUFFER_BYTES = 8 << 20     # the held records of a block of tokens
_VMEM_LIMIT_BYTES = 32 << 20


def _sublanes(*dtypes) -> int:
    """Rows of the widest second-minor tiling among ``dtypes``: 8 of 32
    bits, 16 of 16."""
    return max(32 // jnp.dtype(t).itemsize for t in dtypes)


def _stride(lanes: int) -> int:
    """Sublanes from one record to the next where records of ``lanes``
    sublanes lie tiled (8, 128): ``lanes`` rounded up to whole tiles."""
    return -(-lanes // 8) * 8


def supported(tokens: int, k: int, d: int, tile_rows: int, dtype) -> bool:
    """Can ``tokens`` rows of ``d`` elements of ``dtype``, ``k`` slots each,
    and a buffer in tiles of ``tile_rows`` go through the kernels?  A row
    has to be whole 128-lane sublanes; a record that is not whole (8, 128)
    tiles is padded to them by its layout (module docstring)."""
    return (d % _LANES == 0
            and jnp.dtype(dtype).itemsize in (2, 4)
            and tile_rows % _sublanes(dtype) == 0
            and combine_block(tokens, k, d) is not None)


def _shared(*static: str):
    """Call ``fn`` through ``jax.jit``: the layers of a model, every one the
    same call, share ONE trace and ONE lowering of its kernel (traced once a
    layer and a pass the kernels added 20 s to the cell's warm set-up,
    PERF.md section 6, PR 27; XLA inlines the call).  The backend decides
    between Mosaic and the interpreter when the call is traced, so it is
    part of the key."""
    def share(fn):
        jitted = jax.jit(lambda backend, *a, **kw: fn(*a, **kw),
                         static_argnums=0, static_argnames=static)
        return functools.wraps(fn)(
            lambda *a, **kw: jitted(jax.default_backend(), *a, **kw))
    return share


def records(x):
    """``(n, d)`` -> float32 ``(n, d / 128, 128)``: every row a record."""
    n, d = x.shape
    return x.astype(jnp.float32).reshape(n, d // _LANES, _LANES)


def _smem_blocks(idx, block: int):
    """``idx`` as int32 (blocks, 1, block): a grid step's scalars are one
    SMEM block whose last two dimensions are the array's own."""
    return idx.astype(jnp.int32).reshape(-1, 1, block)


def _rows_of(buf, first, count: int, lane_tile: int, stride: int):
    """``(count, 128)``: lane tile ``lane_tile`` of the ``count`` records
    from ``first`` on in ``buf`` ((records * stride, 128))."""
    return buf[pl.ds(first * stride + lane_tile, count, stride=stride), :]


def _records_kernel(used_ref, x_ref, o_ref, *, chunk: int):
    rows, d = x_ref.shape
    lanes = d // _LANES

    @pl.when(pl.program_id(0) < used_ref[0])
    def _live_tile():
        def piece(p, c):
            r0 = pl.multiple_of(p * chunk, chunk)
            for l in range(lanes):
                o_ref[pl.ds(r0, chunk), l, :] = x_ref[
                    pl.ds(r0, chunk), l * _LANES:(l + 1) * _LANES
                ].astype(jnp.float32)
            return c

        jax.lax.fori_loop(0, rows // chunk, piece, 0)


@_shared("tile_rows")
def live_records(rows, layout: GroupLayout, *, tile_rows: int):
    """The records of the row buffer ``rows`` (capacity, d): the tiles that
    belong to a group are copied whole, the others are not written."""
    capacity, d = rows.shape
    lanes = d // _LANES
    tile = lambda i, used: (jnp.minimum(i, used[0] - 1), 0)
    return _pallas_call(
        functools.partial(_records_kernel, chunk=_sublanes(rows.dtype)),
        name="apex_moe_records",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(capacity // tile_rows,),
            in_specs=[pl.BlockSpec((tile_rows, d), tile)],
            # a block of whole records: the layout pads each to whole tiles
            out_specs=pl.BlockSpec((tile_rows, lanes, _LANES),
                                   lambda i, used: (*tile(i, used), 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((capacity, lanes, _LANES),
                                       jnp.float32),
    )(layout.tiles_used, rows)


def _record_copies(src_ref, buf, sem):
    """``copy(at, r, record)``: the DMA of one record into the ``r``-th
    place of ``buf[at]``, whose places are ``_stride`` sublanes apart."""
    lanes = src_ref.shape[1]
    stride = _stride(lanes)

    def copy(at, r, record):
        return pltpu.make_async_copy(
            src_ref.at[record],
            buf.at[(*at, pl.ds(pl.multiple_of(r * stride, stride), lanes),
                    slice(None))], sem)

    return copy


def _gather_kernel(valid_ref, used_ref, *rest, weighted: bool, chunk: int):
    if weighted:
        w_ref, idx_ref, slot_ref, src_ref, o_ref, buf, sem = rest
    else:
        idx_ref, src_ref, o_ref, buf, sem = rest
    i = pl.program_id(0)
    valid = valid_ref[i]
    lanes = src_ref.shape[1]
    stride = _stride(lanes)
    copy = _record_copies(src_ref, buf, sem)

    @pl.when(i < used_ref[0])
    def _live_tile():
        def start(r, c):
            copy((), r, idx_ref[0, 0, r]).start()
            return c

        def wait(r, c):
            copy((), 0, 0).wait()
            return c

        def scale(r, c):            # a record's place is whole vregs
            at = pl.ds(pl.multiple_of(r * stride, stride), stride)
            buf[at, :] = buf[at, :] * w_ref[slot_ref[0, 0, r]]
            return c

        jax.lax.fori_loop(0, valid, start, 0)
        jax.lax.fori_loop(0, valid, wait, 0)
        if weighted:
            jax.lax.fori_loop(0, valid, scale, 0)

        def piece(p, c):
            r0 = pl.multiple_of(p * chunk, chunk)
            rows = pl.ds(r0, chunk)
            row = r0 + jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0)
            for l in range(lanes):
                o_ref[rows, l * _LANES:(l + 1) * _LANES] = jnp.where(
                    row < valid, _rows_of(buf, r0, chunk, l, stride), 0.0
                ).astype(o_ref.dtype)
            return c

        jax.lax.fori_loop(0, o_ref.shape[0] // chunk, piece, 0)


@_shared("tile_rows", "out_dtype")
def gather_rows(src, idx, layout: GroupLayout, *, tile_rows: int,
                out_dtype, weights=None, weight_index=None):
    """``out[r] = src[idx[r]]`` (``* weights[weight_index[r]]``, in float32)
    for the rows of a buffer laid out by ``layout`` that hold a row; zeros on
    a live tile's other rows; the tiles past ``layout.tiles_used`` are not
    written.

    ``src`` records (n, d / 128, 128), ``d`` any multiple of 128, ``idx``
    and ``weight_index`` (capacity,) int32, ``weights`` (m,) -> (capacity, d) ``out_dtype``.
    Only the first ``tile_valid`` indices of a tile are read."""
    _, lanes, _ = src.shape
    capacity = idx.shape[0]
    weighted = weights is not None
    tile = lambda i, *prefetch: (jnp.minimum(i, prefetch[1][0] - 1), 0)
    scalars = pl.BlockSpec(
        (1, 1, tile_rows), lambda i, *prefetch: (*tile(i, *prefetch), 0),
        memory_space=pltpu.SMEM)
    prefetch = [layout.tile_valid, layout.tiles_used]
    args, specs = [_smem_blocks(idx, tile_rows)], [scalars]
    if weighted:
        prefetch.append(weights.astype(jnp.float32))
        args.append(_smem_blocks(weight_index, tile_rows))
        specs.append(scalars)
    return _pallas_call(
        functools.partial(_gather_kernel, weighted=weighted,
                          chunk=_sublanes(out_dtype)),
        name="apex_moe_gather",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(prefetch),
            grid=(capacity // tile_rows,),
            in_specs=specs + [pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tile_rows, lanes * _LANES), tile),
            scratch_shapes=[
                pltpu.VMEM((tile_rows * _stride(lanes), _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((capacity, lanes * _LANES), out_dtype),
    )(*prefetch, *args, src)


def _divmod(x, k: int):
    """``(x // k, x % k)`` of a non-negative scalar; shifts where ``k`` is a
    power of two."""
    if k & (k - 1) == 0:
        return x >> (k.bit_length() - 1), x & (k - 1)
    return jax.lax.div(x, k), jax.lax.rem(x, k)


def _combine_kernel(row_slot_ref, starts_ref, src_ref, slot_ref, *rest,
                    weighted: bool, dots: bool, chunk: int):
    extra, (o_ref, buf, sem) = rest[:-3], rest[-3:]
    capacity, lanes, _ = src_ref.shape
    stride = _stride(lanes)
    k = buf.shape[0]
    tokens = o_ref.shape[0]
    b = pl.program_id(0)
    copy = _record_copies(src_ref, buf, sem)

    # the block's held slots, found from the ROWS' side: a group's rows are
    # in token order, so those of this block's tokens are one range of it
    def group(e, n):
        first, past = starts_ref[b, e], starts_ref[b + 1, e]

        def start(r, c):
            token, j = _divmod(row_slot_ref[r], k)
            copy((j,), token - b * tokens, r).start()
            return c

        jax.lax.fori_loop(first, past, start, 0)
        return n + past - first

    def wait(r, c):
        copy((0,), 0, 0).wait()
        return c

    started = jax.lax.fori_loop(0, starts_ref.shape[1], group, jnp.int32(0))
    jax.lax.fori_loop(0, started, wait, 0)

    def piece(p, c):
        t0 = pl.multiple_of(p * chunk, chunk)
        rows = pl.ds(t0, chunk)
        held = slot_ref[rows, :] < capacity                    # (chunk, k)
        held = [held[:, j:j + 1] for j in range(k)]
        value = lambda j, l: jnp.where(
            held[j], _rows_of(buf.at[j], t0, chunk, l, stride), 0.0)
        if dots:
            g_ref, = extra
            for j in range(k):
                acc = 0.0
                for l in range(lanes):
                    acc = acc + value(j, l) * g_ref[
                        rows, l * _LANES:(l + 1) * _LANES].astype(jnp.float32)
                o_ref[rows, j:j + 1] = jnp.sum(acc, axis=-1, keepdims=True)
            return c
        if weighted:
            w = extra[0][rows, :]
            w = [w[:, j:j + 1] for j in range(k)]
        for l in range(lanes):
            acc = None
            for j in range(k):
                val = value(j, l) * w[j] if weighted else value(j, l)
                acc = val if acc is None else acc + val
            o_ref[rows, l * _LANES:(l + 1) * _LANES] = acc.astype(o_ref.dtype)
        return c

    jax.lax.fori_loop(0, tokens // chunk, piece, 0)


def combine_block(tokens: int, k: int, d: int) -> Optional[int]:
    """Tokens of a grid step of the combine: the largest power of two, from
    16 on, that divides ``tokens`` and whose ``k`` records a token, each on
    its whole tiles, fit the buffer; None where 16 does not divide."""
    block, b = None, 16
    record_bytes = _stride(d // _LANES) * _LANES * 4
    while tokens % b == 0 and k * b * record_bytes <= _COMBINE_BUFFER_BYTES:
        block, b = b, 2 * b
    return block


def _combine(src, slot_row, row_slot, starts, extra, out_dtype, dots: bool):
    """The combine's call: ``extra`` is ``g`` where ``dots``, else the
    slots' weights or None."""
    _, lanes, _ = src.shape
    tokens, k = slot_row.shape
    block = tokens // (starts.shape[0] - 1)
    per_token = lambda width: pl.BlockSpec(
        (block, width), lambda i, row_slot, starts: (i, 0))
    args, specs = [src, slot_row.astype(jnp.int32)], [
        pl.BlockSpec(memory_space=pl.ANY), per_token(k)]
    if extra is not None:
        args.append(extra)
        specs.append(per_token(extra.shape[1]))
    width = k if dots else lanes * _LANES
    return _pallas_call(
        functools.partial(
            _combine_kernel, weighted=extra is not None and not dots,
            dots=dots,
            chunk=_sublanes(out_dtype, *((extra.dtype,) if dots else ()))),
        name="apex_moe_combine_dw" if dots else "apex_moe_combine",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(tokens // block,),
            in_specs=specs,
            out_specs=per_token(width),
            scratch_shapes=[
                pltpu.VMEM((k, block * _stride(lanes), _LANES), jnp.float32),
                pltpu.SemaphoreType.DMA(())],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, width), out_dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
    )(row_slot.astype(jnp.int32), starts.astype(jnp.int32), *args)


@_shared("out_dtype")
def combine_rows(src, slot_row, row_slot, starts, *, weights=None,
                 out_dtype=jnp.float32):
    """``(T, d)``: per token the sum, in slot order and in float32, of the
    records ``src[slot_row[t, j]]`` of its held slots (``slot_row <
    src.shape[0]``), each times ``weights[t, j]`` where given.  No other
    record of ``src`` (capacity, d / 128, 128) is read.

    A grid step takes ``combine_block`` tokens and finds their held slots
    from the rows' side, which costs by the held slot and not by the slot:
    ``row_slot`` (capacity,) is the flat slot ``t * k + j`` of each live
    row, and rows ``starts[b, e] .. starts[b + 1, e]`` are those of group
    ``e`` whose token lies in block ``b`` (``starts`` (blocks + 1,
    groups); a group's rows are in token order)."""
    if weights is not None:
        weights = weights.astype(jnp.float32)
    return _combine(src, slot_row, row_slot, starts, weights,
                    jnp.dtype(out_dtype), dots=False)


@_shared()
def slot_dots(src, slot_row, row_slot, starts, g):
    """``(T, k)`` float32: ``src[slot_row[t, j]] . g[t]`` over the features
    for the held slots, zero for the others (arguments as
    :func:`combine_rows`)."""
    return _combine(src, slot_row, row_slot, starts, g,
                    jnp.dtype(jnp.float32), dots=True)
