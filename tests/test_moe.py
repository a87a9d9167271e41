"""Expert-parallel MoE vs single-device routing math vs the dense no-drop
reference, forward and gradients, on a (data=2, expert=4) CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from apex_tpu.parallel.mesh import shard_map_compat as shard_map
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.moe import MoEMLP, moe_mlp_ref, top_k_routing

N_EXP_DEV = 4  # expert-axis size
N_DATA = 2
E, D, D_FF = 8, 16, 32
T_LOCAL = 24  # tokens per data shard


@pytest.fixture
def mesh2x4():
    devices = np.array(jax.devices()[:8]).reshape(N_DATA, N_EXP_DEV)
    return Mesh(devices, axis_names=("data", "expert"))


def _params(rng):
    return {
        "router": jnp.asarray(rng.randn(D, E).astype(np.float32) * 0.3),
        "wi": jnp.asarray(rng.randn(E, D, D_FF).astype(np.float32) * 0.2),
        "wo": jnp.asarray(rng.randn(E, D_FF, D).astype(np.float32) * 0.2),
    }


def _x(rng):
    return jnp.asarray(
        rng.randn(N_DATA * T_LOCAL, D).astype(np.float32) * 0.5
    )


def _run_ep(mesh, x, params, k=2, capacity_factor=2.0):
    """Expert-parallel: experts sharded over the expert axis, tokens over
    the data axis (replicated over expert — each expert group serves its
    data shard)."""
    moe = MoEMLP(num_experts=E, d_ff=D_FF, num_partitions=N_EXP_DEV,
                 k=k, capacity_factor=capacity_factor)

    def fn(x, router, wi, wo):
        y, aux = moe.apply(
            {"params": {"router": router, "wi": wi, "wo": wo}}, x
        )
        return y, aux[None]  # aux varies over the data axis

    f = shard_map(
        fn, mesh=mesh,
        in_specs=(P("data"), P(), P("expert"), P("expert")),
        out_specs=(P("data"), P("data")),
        check_vma=False,
    )
    return f(x, params["router"], params["wi"], params["wo"])


def _run_single(x, params, k=2, capacity_factor=2.0):
    """Same routing math, one device, per data shard (identical local
    token count, hence identical capacity)."""
    moe = MoEMLP(num_experts=E, d_ff=D_FF, num_partitions=1, k=k,
                 capacity_factor=capacity_factor)
    outs, auxes = [], []
    for i in range(N_DATA):
        y, aux = moe.apply(
            {"params": params}, x[i * T_LOCAL:(i + 1) * T_LOCAL]
        )
        outs.append(y)
        auxes.append(aux)
    return jnp.concatenate(outs, axis=0), jnp.stack(auxes)


class TestRouting:
    def test_capacity_drops_overflow(self, rng):
        logits = jnp.asarray(rng.randn(16, 4).astype(np.float32))
        dispatch, combine, aux = top_k_routing(logits, k=2, capacity=3)
        # no expert receives more than `capacity` tokens
        per_expert = np.asarray(jnp.sum(dispatch, axis=(0, 2)))
        assert (per_expert <= 3).all()
        # each buffer slot is claimed at most once
        slots = np.asarray(jnp.sum(dispatch, axis=0))
        assert (slots <= 1.0 + 1e-6).all()
        assert np.isfinite(float(aux))

    def test_no_drops_with_ample_capacity(self, rng):
        t, e, k = 12, 4, 2
        logits = jnp.asarray(rng.randn(t, e).astype(np.float32))
        dispatch, _, _ = top_k_routing(logits, k=k, capacity=t * k)
        assert float(jnp.sum(dispatch)) == pytest.approx(t * k)


class TestForward:
    @pytest.mark.parametrize("capacity_factor", [2.0, 0.5])
    def test_ep_matches_single_device(self, mesh2x4, rng, capacity_factor):
        """All-to-all dispatch is semantics-preserving for ANY capacity
        (including one that drops tokens)."""
        x, params = _x(rng), _params(rng)
        got, aux_ep = _run_ep(mesh2x4, x, params,
                              capacity_factor=capacity_factor)
        want, aux_1 = _run_single(x, params,
                                  capacity_factor=capacity_factor)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(np.asarray(aux_ep), np.asarray(aux_1),
                                   rtol=1e-6)

    def test_matches_dense_reference_when_nothing_drops(self, rng):
        """With ample capacity the routed layer == dense top-k mixture."""
        x, params = _x(rng), _params(rng)
        x0 = x[:T_LOCAL]
        moe = MoEMLP(num_experts=E, d_ff=D_FF, num_partitions=1, k=2,
                     capacity_factor=float(E))  # C >= k*T/E * E = k*T
        y, _ = moe.apply({"params": params}, x0)
        want = moe_mlp_ref(x0, params, num_experts=E, k=2)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   atol=1e-5, rtol=1e-5)


class TestBackward:
    def test_ep_grads_match_single_device(self, mesh2x4, rng):
        x, params = _x(rng), _params(rng)

        def loss_ep(params):
            y, aux = _run_ep(mesh2x4, x, params)
            return jnp.sum(y ** 2) + 0.01 * jnp.sum(aux)

        def loss_1(params):
            y, aux = _run_single(x, params)
            return jnp.sum(y ** 2) + 0.01 * jnp.sum(aux)

        g_ep = jax.grad(loss_ep)(params)
        g_1 = jax.grad(loss_1)(params)
        for key in params:
            np.testing.assert_allclose(
                np.asarray(g_ep[key]), np.asarray(g_1[key]),
                atol=1e-4, rtol=1e-4, err_msg=key,
            )


# -- ExpertShardMLP: where the scores come from, and the gated unit ---------

from apex_tpu.parallel.moe import ExpertShardMLP, softmax_topk_routing  # noqa: E402


def _shard_layer(**kw):
    return ExpertShardMLP(num_experts=8, experts_held=(2, 6), d_ff=32, k=3,
                          score_func="softmax", tile_rows=8, **kw)


def _shard_oracle(x, scored, params, act):
    """Every held expert on every token in plain ``jnp``, weighted by the
    renormalised softmax of ``scored``'s logits (zero where not picked)."""
    logits = jnp.matmul(scored, params["router"], precision="highest")
    sel, w = softmax_topk_routing(logits, 3, True)
    out = jnp.zeros_like(x)
    for j, e in enumerate(range(2, 6)):
        gate, up = jnp.split(x @ params["wi"][j], 2, axis=-1)
        weight = jnp.sum(jnp.where(sel == e, w, 0.0), axis=-1)
        out = out + weight[:, None] * ((act(gate) * up) @ params["wo"][j])
    return out


@pytest.mark.parametrize("unit", ["silu", "relu"])
@pytest.mark.parametrize("early", [False, True], ids=["scores_x", "router_input"])
def test_expert_shard_routes_on_router_input_and_gates_by_unit_func(unit, early):
    """Value and gradients (the experts' input, the scored stream, every
    parameter) against the oracle: with ``router_input`` the selection and
    the weights follow THAT stream and the experts still consume ``x``."""
    layer = _shard_layer(unit_func=unit)
    kx, kr, kp, kc = jax.random.split(jax.random.PRNGKey(0), 4)
    x = jax.random.normal(kx, (48, 16))
    scored = 2.0 * jax.random.normal(kr, (48, 16)) if early else None
    params = layer.init(kp, x)["params"]
    assert set(params) == {"router", "wi", "wo"}
    cot = jax.random.normal(kc, (48, 16))
    act = {"silu": jax.nn.silu, "relu": jax.nn.relu}[unit]

    def got(p, x, r):
        return jnp.sum(layer.apply({"params": p}, x, router_input=r) * cot)

    def want(p, x, r):
        return jnp.sum(_shard_oracle(x, x if r is None else r, p, act) * cot)

    argnums = (0, 1, 2) if early else (0, 1)
    a, ga = jax.value_and_grad(got, argnums)(params, x, scored)
    b, gb = jax.value_and_grad(want, argnums)(params, x, scored)
    np.testing.assert_allclose(a, b, rtol=1e-5)
    for u, v in zip(jax.tree_util.tree_leaves(ga), jax.tree_util.tree_leaves(gb)):
        assert np.asarray(v).any()
        np.testing.assert_allclose(u, v, rtol=1e-4, atol=1e-5)
    if early:       # and it IS another function than scoring x
        assert abs(float(got(params, x, None)) - float(a)) > 1e-3


def test_expert_shard_refuses_what_it_does_not_know():
    x = jnp.zeros((16, 16))
    with pytest.raises(ValueError, match="unit_func"):
        _shard_layer(unit_func="gelu").init(jax.random.PRNGKey(0), x)
    layer = _shard_layer()
    params = layer.init(jax.random.PRNGKey(0), x)
    with pytest.raises(ValueError, match="router_input"):
        layer.apply(params, x, router_input=jnp.zeros((8, 16)))


# sha256 of str(jaxpr) of the loss gradient of the three sparse models that
# call ExpertShardMLP with its defaults (no router_input, the silu unit), on
# the jnp.take path: an edit that means to leave their programs alone must
# not change them by a byte.  Taken anew at PR 40, which did mean to — the
# plan's names stand in every jaxpr, the picked weights are a masked sum and
# a row finds its slot without a gather (what held across that edit are the
# values: the tests of the plan's tables and of the weights, below) — and at
# PR 45, which put ONE equation into every SwiGLU: ``name`` on gate_up's
# output (``remat.MLP_GATE_UP``), in a dense leading layer and in every
# shared expert.  What held across that edit: with that one name taken out
# again the texts are PR 40's, and loss and gradients are the same to the
# bit either way (the test after this one).
_SPARSE_JAXPR_SHA256 = {
    "afmoe": "ee1bd0432f87417a",
    "qwen3_next": "7ba222639c19d33e",
    "deepseek_v3": "6938a9ba43f8fab9",
}
_SPARSE_JAXPR_SHA256_AT_PR_40 = {
    "afmoe": "5e3b04254f5420d8",
    "qwen3_next": "4ffd06c5fe154864",
    "deepseek_v3": "a512d0534c7f5cb2",
}


def _sha(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _sparse_model(name):
    import apex_tpu.models as models

    cls, cfg = {
        "afmoe": (models.AfmoeLM, models.AfmoeConfig),
        "qwen3_next": (models.Qwen3NextLM, models.Qwen3NextConfig),
        "deepseek_v3": (models.DeepseekV3LM, models.DeepseekV3Config),
    }[name]
    return cls(cfg.tiny(compute_dtype=jnp.float32))


@pytest.mark.parametrize("name", sorted(_SPARSE_JAXPR_SHA256))
def test_defaults_leave_the_sparse_models_the_text_they_had(name):
    from apex_tpu.ops._common import force_pallas

    model = _sparse_model(name)
    ids = jnp.zeros((1, 64), jnp.int32)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    with force_pallas(False):
        text = str(jax.make_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, ids, labels=ids)[1]))(params))
    assert "moe_router" in str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, ids))(params).pretty_print(name_stack=True))
    assert _sha(text) == _SPARSE_JAXPR_SHA256[name]


@pytest.mark.parametrize("name", sorted(_SPARSE_JAXPR_SHA256))
def test_gate_ups_name_is_all_that_changed_the_sparse_models(monkeypatch,
                                                             name):
    """With ``gate_up``'s name taken out of ``SwiGLU`` the three models'
    gradient jaxprs are the texts pinned at PR 40, and with it in the loss
    and every gradient leaf are the same to the bit."""
    from jax.ad_checkpoint import checkpoint_name

    from apex_tpu import remat
    from apex_tpu.ops._common import force_pallas
    from apex_tpu.parallel import moe

    model = _sparse_model(name)
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, 64), 0, 250)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    both = lambda: jax.jit(jax.value_and_grad(      # traced anew a call
        lambda p: model.apply({"params": p}, ids, labels=ids)[1]))
    with force_pallas(False):
        named = both()(params)
        monkeypatch.setattr(
            moe, "checkpoint_name", lambda x, name: x
            if name == remat.MLP_GATE_UP else checkpoint_name(x, name))
        bare = both()(params)
        zeros = jnp.zeros_like(ids)     # the ids of the pinned texts
        text = str(jax.make_jaxpr(jax.grad(lambda p: model.apply(
            {"params": p}, zeros, labels=zeros)[1]))(params))
    assert _sha(text) == _SPARSE_JAXPR_SHA256_AT_PR_40[name]
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(bare), strict=True):
        assert a.dtype == b.dtype and jnp.array_equal(a, b)


@pytest.mark.parametrize("d", [2048, 2560])
def test_row_movement_is_one_form_at_whole_tile_records_and_off_them(d):
    """Tokens -> rows -> tokens through ops/moe_rows.py at a hidden size
    whose records are whole (8, 128) tiles (2048: 16 sublanes) and at one
    whose records are not (2560: 20): the same four kernels, the records a
    (rows, d / 128, 128) array in both, value and gradients the jnp.take
    path's."""
    from apex_tpu.ops import grouped_mm as gmm
    from apex_tpu.ops import moe_rows
    from apex_tpu.ops._common import force_pallas
    from apex_tpu.parallel import moe

    t, k, tile, held = 64, 4, 16, (0, 4)
    cap = gmm.rows_capacity(t * k, 4, tile)
    key = jax.random.split(jax.random.PRNGKey(d), 3)
    x = jax.random.normal(key[0], (t, d)).astype(jnp.bfloat16)
    w = jax.nn.softmax(jax.random.normal(key[1], (t, k)))
    sel = jnp.argsort(jax.random.uniform(key[2], (t, 16)), axis=-1)[:, :k]

    def loss(x, w, block):      # block None: the jnp.take path
        rows_tile = None if block is None else tile
        routing = moe._route(sel.astype(jnp.int32), held, cap, tile, block)
        rows = moe._rows_from_tokens(x, routing, rows_tile)
        return jnp.sum(moe._tokens_from_rows(rows, w, routing, rows_tile)
                       .astype(jnp.float32) ** 2)

    both = jax.value_and_grad(loss, (0, 1))
    with force_pallas(True):
        block = moe_rows.combine_block(t, k, d)
        text = str(jax.make_jaxpr(lambda x, w: both(x, w, block))(x, w))
        got = both(x, w, block)
    for kernel in ("apex_moe_records", "apex_moe_gather", "apex_moe_combine",
                   "apex_moe_combine_dw"):
        assert kernel in text
    assert f"f32[{cap},{d // 128},128]" in text
    want = both(x, w, None)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=2e-2, atol=1e-3)


@pytest.mark.parametrize("unit", ["silu", "relu"])
def test_whole_layer_on_the_kernels_never_reads_the_dead_tail(unit):
    """``ExpertShardMLP`` holding an eighth of the experts, so that its
    worst-case row buffer is >= 8x the rows the routing makes live, through
    every kernel (row movement and grouped products, interpreted: a block no
    grid step wrote reads NaN, a tripwire for any reader of the tiles past
    ``layout.tiles_used``) against the ``jnp.take`` / ``ragged_dot`` path:
    loss, dx and every parameter's gradient equal and finite."""
    from apex_tpu.ops import grouped_mm as gmm
    from apex_tpu.ops._common import force_pallas

    t, d, k, tile = 64, 128, 4, 16
    layer = ExpertShardMLP(num_experts=32, experts_held=(4, 8), d_ff=128, k=k,
                           score_func="softmax", unit_func=unit, tile_rows=tile)
    kx, kp, kc = jax.random.split(jax.random.PRNGKey(38), 3)
    x = jax.random.normal(kx, (t, d))
    params = layer.init(kp, x)["params"]
    cot = jax.random.normal(kc, (t, d))
    sel = jax.lax.top_k(jnp.matmul(x, params["router"], precision="highest"), k)[1]
    live_rows = int(jnp.sum((sel >= 4) & (sel < 8)))
    assert 0 < 8 * live_rows <= gmm.rows_capacity(t * k, 4, tile)

    loss = jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply({"params": p}, x) * cot), (0, 1))
    with force_pallas(True):
        text = str(jax.make_jaxpr(loss)(params, x))
        got = loss(params, x)
    for kernel in ("apex_gmm", "apex_gmm_dw", "apex_moe_records",
                   "apex_moe_gather", "apex_moe_combine"):
        assert kernel in text
    with force_pallas(False):
        want = loss(params, x)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert np.isfinite(a).all() and np.asarray(b).any()
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-5)


# -- the routing plan: its tables against a plain loop over the slots --------

def _plan_oracle(sel, held, capacity, tile, block):
    """``_route``'s tables by a plain NumPy loop over the slots and the rows
    (``ops/grouped_mm.py::GroupLayout``'s four arrays, ``slot_row``,
    ``row_slot``, ``row_token``, ``_block_starts``)."""
    t, k = sel.shape
    lo, hi = held
    sizes = [int(np.sum(sel == e)) for e in range(lo, hi)]
    tiles_of = [max(1, -(-z // tile)) for z in sizes]
    row_start = [tile * sum(tiles_of[:g]) for g in range(hi - lo)]
    tile_group, tile_valid = [], []
    for g, (z, tiles) in enumerate(zip(sizes, tiles_of)):
        tile_group += [g] * tiles
        tile_valid += [min(tile, max(0, z - i * tile)) for i in range(tiles)]
    dead = capacity // tile - len(tile_group)
    tile_group += [hi - lo - 1] * dead
    tile_valid += [0] * dead
    slot_row = np.full((t, k), capacity, np.int32)
    row_slot = np.full(capacity, t * k, np.int32)
    row_token = np.full(capacity, t, np.int32)
    starts = np.zeros((t // block + 1, hi - lo), np.int32)
    taken = [0] * (hi - lo)
    for token in range(t):
        if token % block == 0:
            starts[token // block] = np.add(row_start, taken)
        for j in range(k):
            g = int(sel[token, j]) - lo
            if 0 <= g < hi - lo:
                row = row_start[g] + taken[g]
                taken[g] += 1
                slot_row[token, j] = row
                row_slot[row], row_token[row] = token * k + j, token
    starts[-1] = np.add(row_start, taken)
    return [np.asarray(a, np.int32) for a in (
        row_start, tile_group, tile_valid, [sum(tiles_of)])] + [
            slot_row, row_slot, row_token, starts]


def _random_sel(seed, t, k, e):
    rng = np.random.RandomState(seed)
    return np.stack([rng.permutation(e)[:k] for _ in range(t)]).astype(np.int32)


def _one_expert_ends_on_a_tile(t=32, k=2, e=8):
    """Expert 3 gets exactly two tiles of 8 rows, expert 2 none."""
    sel = np.stack([np.full(t, 7), np.full(t, 6)], axis=1).astype(np.int32)
    sel[:16, 0] = 3
    sel[16:24, 1] = 4
    return sel


PLAN_SELS = {
    "random_quarter_held": (_random_sel(0, 64, 4, 16), (4, 8)),
    "random_all_held": (_random_sel(1, 32, 3, 8), (0, 8)),
    "random_k_over_held": (_random_sel(2, 32, 8, 16), (5, 9)),
    "an_expert_with_no_row_one_ending_on_a_tile": (
        _one_expert_ends_on_a_tile(), (2, 6)),
    "every_slot_on_one_held_expert": (np.full((32, 1), 5, np.int32), (4, 8)),
    "no_held_slot_at_all": (_random_sel(3, 32, 4, 8) % 4, (4, 8)),
}


@pytest.mark.parametrize("name", sorted(PLAN_SELS))
def test_routing_plan_tables_are_the_plain_loops(name):
    """``shard_dispatch``'s and ``_block_starts``' tables, every leaf of
    ``_Routing``, equal to the bit to a loop over the slots — on random
    selections and where a lookup by sum could slip: an empty group, a group
    that ends exactly on a tile, all slots on one expert, none held."""
    from apex_tpu.ops import grouped_mm as gmm
    from apex_tpu.parallel import moe

    sel, held = PLAN_SELS[name]
    (t, k), tile, block = sel.shape, 8, 16
    capacity = gmm.rows_capacity(
        t * min(k, held[1] - held[0]), held[1] - held[0], tile)
    got = jax.tree_util.tree_leaves(jax.jit(
        lambda s: moe._route(s, held, capacity, tile, block))(jnp.asarray(sel)))
    want = _plan_oracle(sel, held, capacity, tile, block)
    assert len(got) == len(want) == 8
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(np.asarray(a), b)
    if name.startswith("an_expert"):
        assert want[2].tolist()[:4] == [0, 8, 8, 8]     # none; whole tiles
    live = int(np.sum((sel >= held[0]) & (sel < held[1])))
    assert int(np.sum(want[5] < t * k)) == live
    assert (live == 0) == name.startswith("no_held")


@pytest.mark.parametrize("route_norm", [False, True], ids=["raw", "normed"])
def test_sigmoid_weights_are_take_along_axis_to_the_bit(route_norm):
    """The picked scores as a masked sum over the experts: the float32 a
    gather reads, to the bit, under a selection bias that is NOT zero (so
    ``top_k``'s own values, scores plus bias, would not do), and the gradient
    of the logits equal too — a masked broadcast where the gather's was a
    scatter."""
    from apex_tpu.parallel.moe import sigmoid_topk_routing

    kl, kb, kc = jax.random.split(jax.random.PRNGKey(7), 3)
    logits = 2.0 * jax.random.normal(kl, (96, 32))
    bias = 0.5 * jax.random.normal(kb, (32,))
    cot = jax.random.normal(kc, (96, 5))

    def gathered(logits):
        scores = jax.nn.sigmoid(logits)
        _, sel = jax.lax.top_k(scores + bias, 5)
        w = jnp.take_along_axis(scores, sel, axis=-1)
        if route_norm:
            w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
        return sel.astype(jnp.int32), w * 2.5

    sel, w = sigmoid_topk_routing(logits, bias, 5, route_norm, 2.5)
    sel_g, w_g = gathered(logits)
    assert not jnp.array_equal(sel, jax.lax.top_k(logits, 5)[1])  # bias steers
    assert sel.dtype == sel_g.dtype and jnp.array_equal(sel, sel_g)
    assert w.dtype == w_g.dtype and jnp.array_equal(w, w_g)
    grad = jax.grad(lambda lg: jnp.sum(
        sigmoid_topk_routing(lg, bias, 5, route_norm, 2.5)[1] * cot))(logits)
    grad_g = jax.grad(lambda lg: jnp.sum(gathered(lg)[1] * cot))(logits)
    assert jnp.any(grad != 0) and jnp.array_equal(grad, grad_g)


def test_softmax_weights_are_top_ks_own_to_the_bit():
    """... and the picked probabilities are ``top_k``'s values, gradient too."""
    kl, kc = jax.random.split(jax.random.PRNGKey(8))
    logits = 2.0 * jax.random.normal(kl, (96, 32))
    cot = jax.random.normal(kc, (96, 5))

    def gathered(logits):
        w, sel = jax.lax.top_k(jax.nn.softmax(logits, -1), 5)
        return sel.astype(jnp.int32), w / jnp.sum(w, axis=-1, keepdims=True)

    for a, b in zip(softmax_topk_routing(logits, 5, True), gathered(logits)):
        assert a.dtype == b.dtype and jnp.array_equal(a, b)
    grad, grad_g = (jax.grad(lambda lg: jnp.sum(fn(lg)[1] * cot))(logits)
                    for fn in (lambda lg: softmax_topk_routing(lg, 5, True),
                               gathered))
    assert jnp.any(grad != 0) and jnp.array_equal(grad, grad_g)
