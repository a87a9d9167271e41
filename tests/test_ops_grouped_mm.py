"""Grouped matrix product (``ops/grouped_mm.py``): the Pallas kernels
``apex_gmm`` / ``apex_gmm_dw`` in interpret mode against
``jax.lax.ragged_dot`` and against a plain loop over the groups."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import grouped_mm as gmm

CASES = {
    "mixed": ([5, 0, 1, 40], 16, 128, 256, None),
    "empty_but_one": ([0, 0, 64, 0], 16, 128, 128, 64),
    "all_rows_in_first": ([64, 0, 0, 0], 16, 256, 128, 64),
    "single_rows": ([1, 1, 1, 1], 8, 128, 128, 32),
    "all_empty": ([0, 0, 0], 8, 128, 128, 16),
    "ragged_eight": ([17, 16, 15, 0, 33, 2, 1, 100], 32, 128, 640, 400),
    # the expert layer's worst-case buffer: the live rows an eighth and a
    # sixteenth of what it is sized for, and no live row at all
    "worst_case_eighth": ([9, 0, 30, 25], 16, 128, 256, 512),
    "worst_case_sixteenth": ([1, 13, 0, 2, 0, 11, 5, 0], 8, 256, 128, 512),
    "worst_case_all_empty": ([0, 0, 0, 0], 16, 128, 128, 256),
}


def _live_rows(layout, tile):
    """(rows,) bool: the tiles that belong to a group.  The kernels' row
    axis ends there: what lies past it is undefined in their outputs."""
    tiles = layout.tile_group.shape[0]
    return np.repeat(np.arange(tiles) < int(layout.tiles_used[0]), tile)


def _case(name):
    """The dead tiles of ``x`` and of the cotangent hold NaN: whatever read
    them would carry it into an output."""
    sizes, tile, c, n, max_rows = CASES[name]
    sizes = jnp.asarray(sizes, jnp.int32)
    cap = gmm.rows_capacity(max_rows or int(sizes.sum()), sizes.shape[0], tile)
    layout = gmm.group_layout(sizes, cap, tile)
    live = _live_rows(layout, tile)[:, None]
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jnp.where(live, jax.random.normal(k[0], (cap, c)), jnp.nan)
    w = jax.random.normal(k[1], (sizes.shape[0], c, n))
    cot = jnp.where(live, jax.random.normal(k[2], (cap, n)), jnp.nan)
    return sizes, tile, layout, x, w, cot


def _by_loop(sizes, layout, x, w):
    """Each group's rows times its own matrix; zeros elsewhere."""
    start = np.asarray(layout.row_start)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    for e, s in enumerate(np.asarray(sizes)):
        rows = slice(start[e], start[e] + s)
        out[rows] = np.asarray(x)[rows] @ np.asarray(w)[e]
    return out


def _out_and_grads(x, w, layout, tile, cot, use_pallas):
    out, vjp = jax.vjp(lambda x, w: gmm.grouped_matmul(
        x, w, layout, tile_rows=tile, use_pallas=use_pallas), x, w)
    return (out,) + vjp(cot)


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernels_match_ragged_dot_and_the_loop(name):
    sizes, tile, layout, x, w, cot = _case(name)
    live = _live_rows(layout, tile)
    if name.startswith("worst_case"):
        assert int(sizes.sum()) * 8 <= CASES[name][4] and live.mean() < 0.25
    out_k, dx_k, dw_k = map(np.asarray, _out_and_grads(
        x, w, layout, tile, cot, True))
    out_r, dx_r, dw_r = map(np.asarray, _out_and_grads(
        x, w, layout, tile, cot, False))
    # on the live tiles, and all of dw: no NaN came in from the dead ones
    for got in (out_k[live], dx_k[live], dw_k, out_r, dx_r, dw_r):
        assert np.isfinite(got).all()
    np.testing.assert_allclose(out_k[live], _by_loop(sizes, layout, x, w)[live],
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(out_k[live], out_r[live], atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(dx_k[live], dx_r[live], atol=2e-4, rtol=1e-4)
    np.testing.assert_allclose(dw_k, dw_r, atol=2e-4, rtol=1e-4)
    # a group without rows has no weight gradient; a live tile's rows
    # outside its group get no input gradient.  (ragged_dot, the oracle,
    # leaves zeros on the dead tiles too; the kernels never write them.)
    empty = np.asarray(sizes) == 0
    assert not dw_k[empty].any()
    outside = ~np.asarray(gmm._row_mask(layout, tile))
    assert not dx_k[outside & live].any()
    assert not out_k[outside & live].any()
    assert not dx_r[outside].any() and not out_r[outside].any()


def _pallas_calls(jaxpr):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _pallas_calls(sub)


def test_the_row_axis_is_a_dynamic_grid_bound():
    """Forward, dx and dw: the row-tile axis of every traced kernel ends at
    a device value (``layout.tiles_used``), not at the buffer's tile count,
    and it is the innermost axis."""
    sizes, tile, layout, x, w, cot = _case("worst_case_eighth")
    jaxpr = jax.make_jaxpr(lambda x, w, layout: _out_and_grads(
        x, w, layout, tile, cot, True))(x, w, layout)
    calls = list(_pallas_calls(jaxpr.jaxpr))
    assert sorted(c.params["name"] for c in calls) == [
        "apex_gmm", "apex_gmm", "apex_gmm_dw"]
    for call in calls:
        mapping = call.params["grid_mapping"]
        assert mapping.num_dynamic_grid_bounds == 1
        assert all(isinstance(d, int) for d in mapping.grid[:-1])
        assert not isinstance(mapping.grid[-1], int)


def test_layout_is_tile_aligned_and_fits_the_worst_case():
    tile = 16
    for sizes in ([0, 0, 0, 0], [64, 0, 0, 0], [15, 17, 1, 31], [16] * 4):
        z = jnp.asarray(sizes, jnp.int32)
        cap = gmm.rows_capacity(64, 4, tile)
        lay = gmm.group_layout(z, cap, tile)
        start = np.asarray(lay.row_start)
        assert (start % tile == 0).all()
        ends = start + np.maximum(np.asarray(sizes), 1)
        assert (ends[:-1] <= start[1:]).all() and ends[-1] <= cap
        valid = np.asarray(lay.tile_valid)
        assert valid.sum() == sum(sizes)
        used = int(lay.tiles_used[0])
        assert not valid[used:].any()
        # a tile belongs to one group, and the groups ascend
        assert (np.diff(np.asarray(lay.tile_group)) >= 0).all()


def test_bad_shapes_raise():
    lay = gmm.group_layout(jnp.asarray([4, 4], jnp.int32), 32, 16)
    with pytest.raises(ValueError, match="fit together"):
        gmm.grouped_matmul(jnp.zeros((48, 128)), jnp.zeros((2, 128, 128)),
                           lay, tile_rows=16)
    with pytest.raises(ValueError, match="whole tiles"):
        gmm.group_layout(jnp.asarray([4], jnp.int32), 30, 16)


def test_counters_are_set_when_traced():
    from apex_tpu import obs

    sizes, tile, layout, x, w, _ = _case("mixed")
    gmm.grouped_matmul(x, w, layout, tile_rows=tile, use_pallas=False)
    reg = obs.default_registry()
    assert reg.get("ops.gmm.tile_rows").value == tile
    assert reg.get("ops.gmm.rows_capacity").max >= x.shape[0]
    # the gauge says whether the path traced last walks the live tiles only
    assert reg.get("ops.gmm.live_tiles_only").value == 0
    gmm.grouped_matmul(x, w, layout, tile_rows=tile, use_pallas=True)
    assert reg.get("ops.gmm.live_tiles_only").value == 1
