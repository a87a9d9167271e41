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
}


def _case(name):
    sizes, tile, c, n, max_rows = CASES[name]
    sizes = jnp.asarray(sizes, jnp.int32)
    cap = gmm.rows_capacity(max_rows or int(sizes.sum()), sizes.shape[0], tile)
    layout = gmm.group_layout(sizes, cap, tile)
    k = jax.random.split(jax.random.PRNGKey(1), 3)
    x = jax.random.normal(k[0], (cap, c))
    w = jax.random.normal(k[1], (sizes.shape[0], c, n))
    cot = jax.random.normal(k[2], (cap, n))
    return sizes, tile, layout, x, w, cot


def _by_loop(sizes, layout, x, w):
    """Each group's rows times its own matrix; zeros elsewhere."""
    start = np.asarray(layout.row_start)
    out = np.zeros((x.shape[0], w.shape[2]), np.float32)
    for e, s in enumerate(np.asarray(sizes)):
        rows = slice(start[e], start[e] + s)
        out[rows] = np.asarray(x)[rows] @ np.asarray(w)[e]
    return out


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernels_match_ragged_dot_and_the_loop(name):
    sizes, tile, layout, x, w, cot = _case(name)

    def loss(x, w, use_pallas):
        out = gmm.grouped_matmul(x, w, layout, tile_rows=tile,
                                 use_pallas=use_pallas)
        return jnp.sum(out * cot), out

    (_, out_k), g_k = jax.value_and_grad(loss, (0, 1), has_aux=True)(x, w, True)
    (_, out_r), g_r = jax.value_and_grad(loss, (0, 1), has_aux=True)(x, w, False)
    np.testing.assert_allclose(out_k, _by_loop(sizes, layout, x, w),
                               atol=1e-4, rtol=1e-4)
    np.testing.assert_allclose(out_k, out_r, atol=1e-4, rtol=1e-4)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(a, b, atol=2e-4, rtol=1e-4)
    # a group without rows has no weight gradient; rows outside every
    # group get no input gradient
    empty = np.asarray(sizes) == 0
    assert not np.asarray(g_k[1])[empty].any()
    outside = ~np.asarray(gmm._row_mask(layout, tile))
    assert not np.asarray(g_k[0])[outside].any()
    assert not np.asarray(out_k)[outside].any()


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
