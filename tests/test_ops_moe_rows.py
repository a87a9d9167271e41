"""The expert layer's row movement (``ops/moe_rows.py``: ``apex_moe_records``,
``apex_moe_gather``, ``apex_moe_combine``, ``apex_moe_combine_dw``) in
interpret mode against the ``jnp.take`` path of ``parallel/moe.py``, which
stays the path off the TPU: values, gradients, and that nothing reads a row
that holds no token."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import grouped_mm as gmm
from apex_tpu.ops import moe_rows
from apex_tpu.ops._common import force_pallas
from apex_tpu.parallel import moe

T, K, D, TILE, ROUTED_OVER, HELD = 32, 4, 256, 16, 16, (4, 8)
F32, BF16 = jnp.float32, jnp.bfloat16

# Hidden sizes whose records are not whole (8, 128) tiles — 2560 is 20
# sublanes a record (SmallThinker's), 384 is 3 — beside one whose records
# are (1024: 8).  D itself, 256, is 2 sublanes a record.
WIDTHS = [1024, 2560, 384]


def _selection(routing: str):
    """(T, K) expert ids, distinct within a token."""
    t = np.arange(T)[:, None]
    j = np.arange(K)[None, :]
    if routing == "even":            # one held expert a token, each as often
        sel = (t + 4 * j) % ROUTED_OVER
    elif routing == "worst":         # every slot of every token is held
        sel = np.tile(np.arange(*HELD)[None], (T, 1))
    elif routing == "empty_expert":  # held expert 5 is picked by nobody
        sel = np.where((t + 3 * j) % ROUTED_OVER == 5, 12, (t + 3 * j) % ROUTED_OVER)
    elif routing == "all_or_none":   # even tokens hold all k, odd tokens none
        sel = np.where(t % 2 == 0, HELD[0] + j, j)
    else:
        raise KeyError(routing)
    return jnp.asarray(sel, jnp.int32)


ROUTINGS = ["even", "worst", "empty_expert", "all_or_none"]


def _routing(name: str, d: int = D):
    held = HELD[1] - HELD[0]
    return moe._route(
        _selection(name), HELD, gmm.rows_capacity(T * min(K, held), held, TILE),
        TILE, moe_rows.combine_block(T, K, d))


def _rand(seed, shape, dtype=F32):
    return jax.random.normal(jax.random.PRNGKey(seed), shape).astype(dtype)


def _live(routing):
    """(rows that hold a token, rows of the tiles that belong to a group)."""
    holds = np.asarray(gmm._row_mask(routing.layout, TILE))
    in_use = np.arange(holds.size) < int(routing.layout.tiles_used[0]) * TILE
    return holds, in_use


def _poisoned(rows, routing):
    """``rows`` with NaN wherever no token is held; the same with zeros."""
    holds = _live(routing)[0][:, None]
    return (jnp.where(holds, rows, jnp.nan).astype(rows.dtype),
            jnp.where(holds, rows, 0).astype(rows.dtype))


def test_the_cases_are_what_they_say():
    held = {n: np.asarray(_routing(n).slot_row) < _routing(n).row_slot.size
            for n in ROUTINGS}
    assert (held["even"].sum(-1) == 1).all()
    assert held["worst"].all()
    sizes = np.bincount(np.asarray(_selection("empty_expert")).ravel(),
                        minlength=ROUTED_OVER)[HELD[0]:HELD[1]]
    assert sizes[1] == 0 and (np.delete(sizes, 1) > 0).all()
    assert (held["all_or_none"].sum(-1) == np.where(np.arange(T) % 2, 0, K)).all()


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", ROUTINGS)
def test_gather_is_the_take_on_every_live_tile(name, dtype):
    """Row for row, weighted (product in float32, then cast) and not; the
    rows of a live tile that hold no token are zeros as the take's are."""
    r = _routing(name)
    _, in_use = _live(r)
    x = _rand(1, (T, D), dtype)
    got = moe_rows.gather_rows(moe_rows.records(x), r.row_token, r.layout,
                               tile_rows=TILE, out_dtype=dtype)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32)[in_use],
        np.asarray(moe._take_rows(x, r.row_token), np.float32)[in_use])

    g = _rand(2, (T, D))
    weights = jax.random.uniform(jax.random.PRNGKey(3), (T * K,))
    got = moe_rows.gather_rows(moe_rows.records(g), r.row_token, r.layout,
                               tile_rows=TILE, out_dtype=dtype,
                               weights=weights, weight_index=r.row_slot)
    want = (moe._take_rows(g, r.row_token)
            * moe._take_rows(weights, r.row_slot)[:, None]).astype(dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[in_use],
                                  np.asarray(want, np.float32)[in_use])


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("name", ROUTINGS)
def test_combine_sums_in_slot_order_and_reads_held_rows_only(name, dtype):
    """The oracle's float32 values EXACTLY (same order, same products), with
    every row that holds no token poisoned: a not-held slot adds zero."""
    r = _routing(name)
    rows, clean = _poisoned(_rand(4, (r.row_slot.size, D), dtype), r)
    w = jax.random.uniform(jax.random.PRNGKey(5), (T, K))
    records = moe_rows.live_records(rows, r.layout, tile_rows=TILE)
    _, in_use = _live(r)
    np.testing.assert_array_equal(          # a record is its row, widened
        np.asarray(records).reshape(-1, D)[in_use],
        np.asarray(rows, np.float32)[in_use])

    got = moe_rows.combine_rows(records, r.slot_row, r.row_slot, r.starts,
                                weights=w)
    want = jax.jit(moe._sum_slots)(clean, r.slot_row, w)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

    got = moe_rows.combine_rows(records, r.slot_row, r.row_slot, r.starts,
                                out_dtype=dtype)
    want = jax.jit(moe._sum_slots)(clean, r.slot_row).astype(dtype)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(want, np.float32))


@pytest.mark.parametrize("name", ROUTINGS)
def test_slot_dots_are_the_weights_gradient(name):
    r = _routing(name)
    rows, clean = _poisoned(_rand(6, (r.row_slot.size, D), BF16), r)
    g = _rand(7, (T, D))
    got = moe_rows.slot_dots(
        moe_rows.live_records(rows, r.layout, tile_rows=TILE), r.slot_row,
        r.row_slot, r.starts, g)
    want = jnp.stack([
        jnp.sum(g * moe._take_rows(clean, r.slot_row[:, j]).astype(F32), -1)
        for j in range(K)], -1)
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    held = np.asarray(r.slot_row) < r.row_slot.size
    assert (np.asarray(got)[~held] == 0).all()


@pytest.mark.parametrize("name", ROUTINGS)
def test_both_movements_and_their_gradients_match_the_take_path(name):
    """``_rows_from_tokens`` -> something an expert might do to the live
    rows -> ``_tokens_from_rows``: value and the gradients in ``x`` and the
    weights, kernels against takes."""
    r = _routing(name)
    holds = jnp.asarray(_live(r)[0])[:, None]
    x, w, cot = _rand(8, (T, D)), _rand(9, (T, K)), _rand(10, (T, D))

    def loss(x, w, tile_rows):
        rows = moe._rows_from_tokens(x, r, tile_rows)
        rows = jnp.where(holds, jnp.tanh(rows) * 1.5, 0.0)
        return jnp.sum(moe._tokens_from_rows(rows, w, r, tile_rows) * cot)

    want, want_grads = jax.value_and_grad(loss, (0, 1))(x, w, None)
    got, got_grads = jax.value_and_grad(loss, (0, 1))(x, w, TILE)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert np.asarray(b).any() and np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("d", WIDTHS)
@pytest.mark.parametrize("name", ["even", "empty_expert"])
def test_every_kernel_at_widths_off_the_whole_tile(name, d):
    """gather (plain and weighted), records, combine (weighted and not) and
    the slots' dots at each of ``WIDTHS`` against the takes, value for
    value, with the rows that hold no token poisoned."""
    r = _routing(name, d)
    _, in_use = _live(r)
    x = _rand(20, (T, d), BF16)
    got = moe_rows.gather_rows(moe_rows.records(x), r.row_token, r.layout,
                               tile_rows=TILE, out_dtype=BF16)
    assert got.shape == (r.row_slot.size, d)
    np.testing.assert_array_equal(
        np.asarray(got, np.float32)[in_use],
        np.asarray(moe._take_rows(x, r.row_token), np.float32)[in_use])
    g, weights = _rand(21, (T, d)), jax.random.uniform(
        jax.random.PRNGKey(22), (T * K,))
    got = moe_rows.gather_rows(moe_rows.records(g), r.row_token, r.layout,
                               tile_rows=TILE, out_dtype=BF16,
                               weights=weights, weight_index=r.row_slot)
    want = (moe._take_rows(g, r.row_token)
            * moe._take_rows(weights, r.row_slot)[:, None]).astype(BF16)
    np.testing.assert_array_equal(np.asarray(got, np.float32)[in_use],
                                  np.asarray(want, np.float32)[in_use])

    rows, clean = _poisoned(_rand(23, (r.row_slot.size, d), BF16), r)
    records = moe_rows.live_records(rows, r.layout, tile_rows=TILE)
    assert records.shape == (r.row_slot.size, d // 128, 128)
    np.testing.assert_array_equal(
        np.asarray(records).reshape(-1, d)[in_use],
        np.asarray(rows, np.float32)[in_use])
    w = weights.reshape(T, K)
    np.testing.assert_array_equal(
        np.asarray(moe_rows.combine_rows(records, r.slot_row, r.row_slot,
                                         r.starts, weights=w)),
        np.asarray(jax.jit(moe._sum_slots)(clean, r.slot_row, w)))
    np.testing.assert_array_equal(
        np.asarray(moe_rows.combine_rows(records, r.slot_row, r.row_slot,
                                         r.starts, out_dtype=BF16), np.float32),
        np.asarray(jax.jit(moe._sum_slots)(clean, r.slot_row).astype(BF16),
                   np.float32))
    got = moe_rows.slot_dots(records, r.slot_row, r.row_slot, r.starts, g)
    want = jnp.stack([
        jnp.sum(g * moe._take_rows(clean, r.slot_row[:, j]).astype(F32), -1)
        for j in range(K)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-4)
    assert (np.asarray(got)[np.asarray(r.slot_row) >= r.row_slot.size]
            == 0).all()


@pytest.mark.parametrize("d", WIDTHS)
def test_movements_and_gradients_match_the_takes_at_other_widths(d):
    """As the test above at ``D``, through the two ``custom_vjp``s."""
    r = _routing("even", d)
    holds = jnp.asarray(_live(r)[0])[:, None]
    x, w, cot = _rand(24, (T, d)), _rand(25, (T, K)), _rand(26, (T, d))

    def loss(x, w, tile_rows):
        rows = moe._rows_from_tokens(x, r, tile_rows)
        rows = jnp.where(holds, jnp.tanh(rows) * 1.5, 0.0)
        return jnp.sum(moe._tokens_from_rows(rows, w, r, tile_rows) * cot)

    want, want_grads = jax.value_and_grad(loss, (0, 1))(x, w, None)
    got, got_grads = jax.value_and_grad(loss, (0, 1))(x, w, TILE)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_grads, want_grads):
        assert np.asarray(b).any() and np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def _layer(**kw):
    return moe.ExpertShardMLP(
        num_experts=ROUTED_OVER, experts_held=HELD, d_ff=128, k=K,
        shared_d_ff=128, route_scale=2.0, tile_rows=TILE, **kw)


@pytest.mark.parametrize("bias", ["none", "worst", "empty_expert"])
def test_rows_without_a_token_are_never_read(monkeypatch, bias):
    """The kernel path leaves the tiles past the live ones undefined.  Fill
    them with NaN — in the gathered rows, in their gradient, in the records:
    the layer's output and every gradient stay finite and do not change, and
    they are the take path's."""
    from apex_tpu import obs

    layer = _layer()
    x, cot = _rand(11, (T, D)), _rand(12, (T, D))
    params = layer.init(jax.random.PRNGKey(0), x)["params"]
    params["expert_bias"] = {
        "none": jnp.zeros((ROUTED_OVER,)),
        "worst": jnp.zeros((ROUTED_OVER,)).at[HELD[0]:HELD[1]].set(10.0),
        "empty_expert": jnp.zeros((ROUTED_OVER,)).at[5].set(-10.0),
    }[bias]

    def run():
        loss = lambda p, x: jnp.sum(layer.apply({"params": p}, x) * cot)
        return jax.value_and_grad(loss, (0, 1))(params, x)

    with force_pallas(False):
        want = run()
    assert obs.default_registry().get("moe.dispatch.kernels").value == 0
    with force_pallas(True):
        plain = run()
        assert obs.default_registry().get("moe.dispatch.kernels").value == 1

        real_gather, real_records = moe_rows.gather_rows, moe_rows.live_records

        def dead(layout, rows):
            return (jnp.arange(rows) // TILE >= layout.tiles_used[0])[:, None]

        def gather(src, idx, layout, **kw):
            out = real_gather(src, idx, layout, **kw)
            return jnp.where(dead(layout, out.shape[0]), jnp.nan, out
                             ).astype(out.dtype)

        def records(rows, layout, **kw):
            out = real_records(rows, layout, **kw)
            return jnp.where(dead(layout, out.shape[0])[..., None], jnp.nan, out)

        monkeypatch.setattr(moe_rows, "gather_rows", gather)
        monkeypatch.setattr(moe_rows, "live_records", records)
        poisoned = run()

    flat = lambda t: jax.tree_util.tree_leaves(t)
    for a, b, c in zip(flat(poisoned), flat(plain), flat(want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        np.testing.assert_allclose(a, c, rtol=2e-4, atol=1e-5)


def test_gauges_say_what_was_traced():
    from apex_tpu import obs

    layer = _layer()
    x = jnp.zeros((T, D))
    with force_pallas(True):
        layer.init(jax.random.PRNGKey(0), x)
    reg = obs.default_registry()
    held = HELD[1] - HELD[0]
    assert reg.get("moe.dispatch.rows_capacity").value == \
        gmm.rows_capacity(T * K, held, TILE)
    assert reg.get("moe.dispatch.slots").value == T * K
    assert reg.get("moe.dispatch.kernels").value == 1
    # shapes the kernels cannot tile fall back, and say so
    with force_pallas(True):
        _layer().init(jax.random.PRNGKey(0), jnp.zeros((T, 96)))
    assert reg.get("moe.dispatch.kernels").value == 0


def test_supported_follows_the_tiling(monkeypatch):
    assert moe_rows.supported(64, 4, 256, 16, F32)
    # on the TPU as off it: a record need not be whole (8, 128) tiles
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert moe_rows.supported(16384, 6, 2560, 256, BF16)
    assert moe_rows.supported(8192, 8, 2048, 256, BF16)
    assert moe_rows.combine_block(16384, 6, 2560) == 64   # 24 sublanes each
    assert not moe_rows.supported(64, 4, 192, 16, F32)       # lanes
    assert not moe_rows.supported(64, 4, 256, 8, BF16)       # packed rows
    assert not moe_rows.supported(24, 4, 256, 16, F32)       # token blocks
    assert moe_rows.combine_block(8192, 8, 2048) == 128
    assert moe_rows.combine_block(96, 4, 128) == 32
