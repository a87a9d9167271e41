"""Grouped matrix product — Pallas TPU kernels + ``jax.lax.ragged_dot`` path.

The expert layer's product (``parallel/moe.py::ExpertShardMLP``): rows that
were sorted by the expert they go to, each group of rows times its own
expert's matrix.  No reference counterpart (apex has no MoE).

Layout.  The rows live in a buffer of static size, in groups laid out TILE
ALIGNED: group ``e`` starts on a multiple of ``tile_rows`` and owns
``max(1, ceil(size_e / tile_rows))`` whole tiles, its ``size_e`` rows first
(:func:`group_layout`).  A tile therefore belongs to ONE group, the kernels
are plain tiled matmuls whose weight block is picked by a prefetched
per-tile group id, and nothing has to be masked across a group boundary.
The buffer is sized for the worst case (:func:`rows_capacity`); the tiles
past the last group hold no rows, and the kernels' row axis ENDS at
``layout.tiles_used``, a device value (a dynamic grid bound): no step runs
for them, nothing is fetched or written, and a call costs what the routing
made live.  Those tiles are UNDEFINED in the output and in the input
gradient, as they may be in ``x``; a live tile's other rows read as zero.

- ``apex_gmm``: ``out[rows of e] = x[rows of e] @ w[e]`` (and, with the
  weight block transposed, the input gradient ``dout @ w[e].T``).  Grid
  (column tiles, live row tiles), the contraction whole: consecutive row
  tiles of one group name the same weight block, which is fetched once.
- ``apex_gmm_dw``: ``dw[e] = x[rows of e].T @ dout[rows of e]``; grid
  (k tiles, n tiles, live row tiles), row tiles innermost, float32
  accumulator in VMEM written when the group's last tile is done.  A group
  without rows owns one tile of zero valid rows: its ``dw`` is zeros.

Off the TPU (and as the kernels' test oracle) the same layout goes through
``jax.lax.ragged_dot`` with the groups' padded sizes, zeros off the groups.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import (auto_block, pallas_call as _pallas_call,
                                  pallas_default)

__all__ = ["grouped_matmul", "grouped_matmul_ref", "group_layout",
           "rows_capacity", "GroupLayout", "DEFAULT_TILE_ROWS"]

#: rows of a tile: a group's start is aligned to it, so a group wastes at
#: most one tile less a row
DEFAULT_TILE_ROWS = 256
_BLOCK_N = 512          # apex_gmm: output columns of a step
_BLOCK_DW = 512         # apex_gmm_dw: both sides of an accumulator tile


def rows_capacity(max_rows: int, groups: int,
                  tile_rows: int = DEFAULT_TILE_ROWS) -> int:
    """Rows of a buffer that holds ANY split of up to ``max_rows`` rows
    into ``groups`` tile-aligned groups: every group may waste up to a
    tile."""
    return (-(-max_rows // tile_rows) + groups) * tile_rows


class GroupLayout(NamedTuple):
    """Where the groups lie in the row buffer (all int32 arrays)."""
    row_start: jax.Array     # (groups,) first row of each group
    tile_group: jax.Array    # (tiles,) group of each tile (past the end: last)
    tile_valid: jax.Array    # (tiles,) rows of the tile that hold a row
    tiles_used: jax.Array    # (1,) tiles that belong to a group


def group_layout(group_sizes, capacity: int,
                 tile_rows: int = DEFAULT_TILE_ROWS) -> GroupLayout:
    """The tile-aligned layout of ``group_sizes`` rows in a buffer of
    ``capacity`` rows (a multiple of ``tile_rows``, at least
    :func:`rows_capacity` of the sizes' sum)."""
    if capacity % tile_rows:
        raise ValueError(f"capacity {capacity} is not whole tiles of {tile_rows}")
    sizes = group_sizes.astype(jnp.int32)
    tiles_of = jnp.maximum((sizes + tile_rows - 1) // tile_rows, 1)
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    tile = jnp.arange(capacity // tile_rows, dtype=jnp.int32)
    group = jnp.minimum(
        jnp.searchsorted(tile_end, tile, side="right").astype(jnp.int32),
        sizes.shape[0] - 1)
    valid = jnp.clip(sizes[group] - (tile - tile_start[group]) * tile_rows,
                     0, tile_rows)
    valid = jnp.where(tile < tile_end[-1], valid, 0)
    return GroupLayout(tile_start * tile_rows, group, valid.astype(jnp.int32),
                       tile_end[-1:].astype(jnp.int32))


def _row_mask(layout: GroupLayout, tile_rows: int):
    """(rows,) bool: the rows that belong to a group."""
    within = jnp.arange(tile_rows, dtype=jnp.int32)[None, :]
    return (within < layout.tile_valid[:, None]).reshape(-1)


def grouped_matmul_ref(x, w, layout: GroupLayout, tile_rows: int):
    """The same product through ``jax.lax.ragged_dot``: whole tiles as the
    groups' sizes, then zeros on the rows outside every group."""
    tiles = layout.tile_group.shape[0]
    used = jnp.arange(tiles, dtype=jnp.int32) < layout.tiles_used[0]
    padded = tile_rows * jax.ops.segment_sum(
        used.astype(jnp.int32), layout.tile_group, num_segments=w.shape[0])
    keep = _row_mask(layout, tile_rows)[:, None]
    out = jax.lax.ragged_dot(jnp.where(keep, x, 0), w, padded,
                             preferred_element_type=jnp.float32)
    return jnp.where(keep, out, 0).astype(x.dtype)


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _gmm_kernel(group_ref, valid_ref, x_ref, w_ref, o_ref, *, transpose_w):
    valid = valid_ref[pl.program_id(1)]

    @pl.when(valid > 0)
    def _rows():
        acc = jax.lax.dot_general(
            x_ref[...], w_ref[0],
            (((1,), (1 if transpose_w else 0,)), ((), ())),
            preferred_element_type=jnp.float32)
        row = jax.lax.broadcasted_iota(jnp.int32, acc.shape, 0)
        o_ref[...] = jnp.where(row < valid, acc, 0.0).astype(o_ref.dtype)

    @pl.when(valid == 0)
    def _no_rows():
        o_ref[...] = jnp.zeros_like(o_ref)


def _gmm_dw_kernel(group_ref, valid_ref, x_ref, g_ref, o_ref, acc_ref):
    m = pl.program_id(2)
    used = pl.num_programs(2)
    group = group_ref[m]
    first = (m == 0) | (group_ref[jnp.maximum(m - 1, 0)] != group)
    last = (m == used - 1) | (group_ref[jnp.minimum(m + 1, used - 1)] != group)
    valid = valid_ref[m]

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid > 0)
    def _rows():
        x = x_ref[...]
        row = jax.lax.broadcasted_iota(jnp.int32, x.shape, 0)
        x = jnp.where(row < valid, x, jnp.zeros_like(x))
        acc_ref[...] += jax.lax.dot_general(
            x, g_ref[...], (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(last)
    def _write():
        o_ref[0] = acc_ref[...].astype(o_ref.dtype)


def _live_tiles(layout: GroupLayout, tiles: int):
    """The row axis' dynamic bound: the tiles of a group, within the buffer."""
    return jnp.minimum(layout.tiles_used[0], tiles)


def _gmm_pallas(x, w, layout: GroupLayout, tile_rows: int, transpose_w: bool):
    rows, c = x.shape
    n = w.shape[1] if transpose_w else w.shape[2]
    bn = auto_block(n, _BLOCK_N)
    if transpose_w:
        w_spec = pl.BlockSpec((1, bn, c), lambda j, i, grp, _: (grp[i], j, 0))
    else:
        w_spec = pl.BlockSpec((1, c, bn), lambda j, i, grp, _: (grp[i], 0, j))
    return _pallas_call(
        functools.partial(_gmm_kernel, transpose_w=transpose_w),
        name="apex_gmm",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(n // bn, _live_tiles(layout, rows // tile_rows)),
            in_specs=[pl.BlockSpec((tile_rows, c),
                                   lambda j, i, grp, _: (i, 0)), w_spec],
            out_specs=pl.BlockSpec((tile_rows, bn),
                                   lambda j, i, grp, _: (i, j)),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
    )(layout.tile_group, layout.tile_valid, x, w)


def _gmm_dw_pallas(x, g, layout: GroupLayout, tile_rows, groups, dtype):
    (rows, k), n = x.shape, g.shape[1]
    bk, bn = auto_block(k, _BLOCK_DW), auto_block(n, _BLOCK_DW)
    return _pallas_call(
        _gmm_dw_kernel,
        name="apex_gmm_dw",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(k // bk, n // bn,
                  _live_tiles(layout, rows // tile_rows)),
            in_specs=[pl.BlockSpec((tile_rows, bk),
                                   lambda a, b, i, grp, _: (i, a)),
                      pl.BlockSpec((tile_rows, bn),
                                   lambda a, b, i, grp, _: (i, b))],
            out_specs=pl.BlockSpec(
                (1, bk, bn), lambda a, b, i, grp, _: (grp[i], a, b)),
            scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dtype),
    )(layout.tile_group, layout.tile_valid, x, g)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _gmm(x, w, layout, tile_rows):
    return _gmm_pallas(x, w, layout, tile_rows, transpose_w=False)


def _gmm_fwd_rule(x, w, layout, tile_rows):
    return _gmm(x, w, layout, tile_rows), (x, w, layout)


def _gmm_bwd_rule(tile_rows, res, g):
    x, w, layout = res
    dx = _gmm_pallas(g, w, layout, tile_rows, transpose_w=True)
    dw = _gmm_dw_pallas(x, g, layout, tile_rows, w.shape[0], w.dtype)
    no_grad = jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), layout)
    return dx, dw, no_grad


_gmm.defvjp(_gmm_fwd_rule, _gmm_bwd_rule)


def grouped_matmul(x, w, layout: GroupLayout, *,
                   tile_rows: int = DEFAULT_TILE_ROWS,
                   use_pallas: Optional[bool] = None):
    """``out[r] = x[r] @ w[group of r]`` for the rows of a buffer laid out
    by :func:`group_layout`; zeros on a live tile's rows outside its group.
    The tiles past ``layout.tiles_used`` are never read, and on the kernel
    path never written: undefined in the output and in ``x``'s gradient
    (``ragged_dot`` leaves zeros there).

    ``x`` (rows, c), ``w`` (groups, c, n) -> (rows, n), in ``x``'s dtype
    with float32 accumulation.  Differentiable in ``x`` and ``w``.  The
    Pallas kernels run on the TPU where the shapes tile (lane dimension
    128, ``tile_rows`` a multiple of 8); elsewhere ``jax.lax.ragged_dot``
    computes the same."""
    rows, c = x.shape
    if w.shape[1] != c or rows != layout.tile_group.shape[0] * tile_rows:
        raise ValueError(f"x {x.shape}, w {w.shape} and a layout of "
                         f"{layout.tile_group.shape[0]} tiles of {tile_rows} "
                         f"rows do not fit together")
    tiles = c % 128 == 0 and w.shape[2] % 128 == 0 and tile_rows % 8 == 0
    if use_pallas is None:
        use_pallas = pallas_default(tiles)
    elif use_pallas and not tiles:
        raise ValueError(f"the kernels want lane dimensions of 128 and rows "
                         f"of 8: got x {x.shape}, w {w.shape}, {tile_rows}")
    from apex_tpu import obs

    reg = obs.default_registry()
    reg.gauge("ops.gmm.rows_capacity").set_max(rows)
    reg.gauge("ops.gmm.tile_rows").set(tile_rows)
    reg.gauge("ops.gmm.live_tiles_only").set(int(use_pallas))
    w = w.astype(x.dtype)
    if not use_pallas:
        return grouped_matmul_ref(x, w, layout, tile_rows)
    return _gmm(x, w, layout, tile_rows)
