"""``ops/gated_delta.py``: the chunked gated delta rule against the token
recurrence it is defined by (outputs and every gradient; the kernels — which
make what is local to a chunk themselves — in interpret mode, and the
``lax.scan`` path), q and k at fewer heads than v, bfloat16 operands, the
decays at their strongest, the triangular inverse, and the causal
convolution in front of the rule — its XLA form, and the two kernels that
read q, k, v out of the projection's output where they lie."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import gated_delta as gd
from apex_tpu.ops._common import force_pallas


def inputs(seed=0, b=2, s=40, h=3, dk=128, dv=128, a_max=16.0, beta_shift=0.0,
           r=1):
    """q, k normalised as the model hands them over, at ``h`` key heads; v,
    g, beta at ``h * r`` value heads; value head 0 decays at ``a_max`` (the
    strongest ``A = exp(A_log)`` the initialisation draws), the others at 1
    and 0.01 in turn."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = l2(jax.random.normal(ks[0], (b, s, h, dk))) * dk ** -0.5
    k = l2(jax.random.normal(ks[1], (b, s, h, dk)))
    h = h * r
    v = jax.random.normal(ks[2], (b, s, h, dv))
    a = 3.0 * jax.random.normal(ks[3], (b, s, h))
    rates = jnp.resize(jnp.array([a_max, 1.0, 0.01]), (h,))
    g = -rates * jax.nn.softplus(a + 1.0)
    beta = jax.nn.sigmoid(4.0 * jax.random.normal(ks[4], (b, s, h)) + beta_shift)
    return q, k, v, g, beta


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


CASES = {
    "strongest_decay": dict(a_max=16.0),
    "beta_near_0": dict(beta_shift=-12.0),
    "beta_near_1": dict(beta_shift=12.0),
    "not_whole_chunks": dict(s=37),
    "one_short_chunk": dict(s=5),
    # q and k at FEWER heads than v: two value heads read each key head, and
    # a key head's gradient is the sum over them (2 x 2, and 1 x 4: a grid
    # step of one key head)
    "two_value_heads_a_key_head": dict(h=2, r=2),
    "four_value_heads_a_key_head": dict(h=1, r=4, s=37),
}


def recurrent(q, k, v, g, beta):
    """The recurrence with each value head beside its key head's q and k."""
    r = v.shape[2] // q.shape[2]
    return gd.gated_delta_rule_recurrent(
        jnp.repeat(q, r, axis=2), jnp.repeat(k, r, axis=2), v, g, beta)


@pytest.mark.parametrize("kernels", [False, True], ids=["scan", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_rule_matches_the_recurrence(case, kernels):
    """Outputs and all five gradients, chunks of 16."""
    args = inputs(**CASES[case])
    want = recurrent(*args)
    ct = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = lambda fn: jax.grad(lambda *a: jnp.sum(fn(*a) * ct),
                                argnums=(0, 1, 2, 3, 4))(*args)
    chunked = lambda *a: gd.gated_delta_rule(*a, chunk=16)
    with force_pallas(kernels):
        got = chunked(*args)
        got_grads = grads(chunked)
    assert gap(got, want) < 1e-5
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got_grads,
                          grads(recurrent)):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        assert gap(a, b) < 1e-4, name


@pytest.mark.parametrize("case", ["strongest_decay", "not_whole_chunks",
                                  "two_value_heads_a_key_head"])
def test_kernels_with_bfloat16_operands(case):
    """bfloat16 q, k, v as the model hands them over: the kernels' products
    take bfloat16 operands and accumulate in float32, everything between
    products stays float32.  Held to the float32 recurrence on the SAME
    bfloat16 values at tolerances of their own — 2e-2 of the largest value,
    outputs and gradients: an operand's rounding is 2^-9 = 2e-3 relative,
    some ten roundings lie between an input and a gradient, and the outputs
    and the gradients of q, k, v are themselves rounded to bfloat16."""
    q, k, v, g, beta = inputs(**CASES[case])
    q, k, v = (t.astype(jnp.bfloat16) for t in (q, k, v))
    f32 = lambda t: t.astype(jnp.float32)
    want = recurrent(f32(q), f32(k), f32(v), g, beta)
    ct = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    grads = lambda fn, *a: jax.grad(
        lambda *a: jnp.sum(f32(fn(*a)) * ct), argnums=(0, 1, 2, 3, 4))(*a)
    chunked = lambda *a: gd.gated_delta_rule(*a, chunk=16)
    with force_pallas(True):
        got = chunked(q, k, v, g, beta)
        got_grads = grads(chunked, q, k, v, g, beta)
    assert got.dtype == jnp.bfloat16 and gap(f32(got), want) < 2e-2
    assert [x.dtype for x in got_grads] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    for name, a, b in zip(("q", "k", "v", "g", "beta"), got_grads,
                          grads(recurrent, f32(q), f32(k), f32(v), g, beta)):
        assert bool(jnp.isfinite(f32(a)).all()), name
        assert gap(f32(a), b) < 2e-2, name


@pytest.mark.parametrize("kernels,r", [(False, 1), (True, 1), (True, 2)],
                         ids=["scan", "pallas", "pallas_two_a_key_head"])
def test_a_whole_chunk_at_the_strongest_decay_stays_finite(kernels, r):
    """-21 a token, -1300 over a chunk of 64: ``exp(G_i) * exp(-G_j)`` would
    overflow float32 inside the chunk; ``exp(G_i - G_j)`` for i >= j does
    not — in the scan path and in both kernels, forward and backward — and
    the result is still the recurrence's."""
    q, k, v, g, beta = inputs(b=1, s=128, h=2 // r, r=r)
    g = jnp.full_like(g, -21.0).at[..., 1].set(-0.5)
    assert float(jnp.sum(g[0, :64, 0])) < -1300
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(1300.0)))
    loss = lambda fn: lambda *a: jnp.sum(jnp.square(fn(*a)))
    with force_pallas(kernels):
        got = gd.gated_delta_rule(q, k, v, g, beta, chunk=64)
        grads = jax.grad(loss(lambda *a: gd.gated_delta_rule(*a, chunk=64)),
                         argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    assert bool(jnp.isfinite(got).all())
    assert all(bool(jnp.isfinite(x).all()) for x in grads)
    assert gap(got, recurrent(q, k, v, g, beta)) < 1e-5
    want = jax.grad(loss(recurrent), argnums=(0, 1, 2, 3, 4))(q, k, v, g, beta)
    for a, b in zip(grads, want):
        assert gap(a, b) < 1e-4


def test_kernels_and_scan_path_agree():
    """``apex_gdn_fwd`` / ``apex_gdn_bwd`` (interpret mode) against the scan
    path's pieces on the same inputs: outputs, the state at each chunk's
    start and each chunk's ``T`` — what the forward kernel hands the
    backward —, and all five gradients from those residuals."""
    b, s, h, c = 2, 48, 2, 16
    q, k, v, g, beta = inputs(b=b, s=s, h=h, a_max=4.0)
    n = s // c
    chunks = lambda t: jnp.moveaxis(                  # (N, B H, C, ...)
        t.reshape((b, n, c, h) + t.shape[3:]), 3, 2).swapaxes(0, 1).reshape(
            (n, b * h, c) + t.shape[3:])
    local = gd._chunk_local(*map(chunks, (q, k, v, g, beta)))
    o_s, st_s = gd._chain_fwd_scan(*local)
    o_k, st_k, tri_k = gd._rule_fwd_pallas(q, k, v, g, beta, c)
    heads_last = lambda t: t.reshape((n, b, h) + t.shape[2:]).swapaxes(0, 1)
    assert gap(o_k, jnp.moveaxis(heads_last(o_s), 2, 3).reshape(o_k.shape)) < 1e-5
    assert gap(st_k, heads_last(st_s)) < 1e-5
    assert float(jnp.max(jnp.abs(st_k[:, 0]))) == 0.0       # a zero start
    a = jnp.tril(beta_decay_kk(*map(chunks, (k, g, beta))), -1)
    assert gap(tri_k, heads_last(gd.tri_inverse(a))) < 1e-5
    do = jax.random.normal(jax.random.PRNGKey(6), o_k.shape)
    scan = lambda *x: gd.gated_delta_rule(*x, chunk=c, use_pallas=False)
    for got, want in zip(
            gd._rule_bwd_pallas(q, k, v, g, beta, st_k, tri_k, do, c),
            jax.vjp(scan, q, k, v, g, beta)[1](do)):
        assert got.shape == want.shape and gap(got, want) < 1e-5


def beta_decay_kk(k, g, beta):
    """``beta_i exp(G_i - G_j) k_i . k_j`` of every chunk, (N, BH, C, C)."""
    big_g = jnp.cumsum(g, axis=-1)
    diff = big_g[..., :, None] - big_g[..., None, :]
    return (beta[..., None] * jnp.exp(jnp.minimum(diff, 0.0))
            * jnp.einsum("nhid,nhjd->nhij", k, k))


def test_triangular_inverse_and_its_gradient():
    a = jnp.tril(jax.random.normal(jax.random.PRNGKey(3), (2, 3, 16, 16)), -1)
    t = gd.tri_inverse(a)
    eye = jnp.eye(16)
    np.testing.assert_allclose(t @ (eye + a), jnp.broadcast_to(eye, a.shape),
                               atol=2e-4)
    ct = jax.random.normal(jax.random.PRNGKey(4), a.shape)
    got = jax.grad(lambda x: jnp.sum(gd.tri_inverse(x) * ct))(a)
    want = jax.grad(lambda x: jnp.sum(
        jnp.linalg.inv(eye + jnp.tril(x, -1)) * ct))(a)
    assert gap(got, want) < 1e-4
    assert float(jnp.max(jnp.abs(jnp.triu(got)))) == 0.0


def test_convolution_is_causal_from_the_rows_start():
    """Against the sum written out position by position: zeros before the
    row's start, no tap reaches forward, rows do not see each other."""
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (2, 9, 6)))
    w = np.asarray(jax.random.normal(jax.random.PRNGKey(6), (6, 4)))
    want = np.zeros_like(x)
    for t in range(9):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += w[:, j] * x[:, t - 3 + j]
    want = want / (1.0 + np.exp(-want))                       # SiLU
    got = gd.causal_conv1d_silu(jnp.asarray(x), jnp.asarray(w))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    # the first output sees the first input through the LAST tap alone
    np.testing.assert_allclose(
        got[:, 0], jax.nn.silu(x[:, 0] * w[:, 3]), rtol=1e-5, atol=1e-6)
    later = x.copy()
    later[:, 5:] += 1.0
    np.testing.assert_array_equal(
        gd.causal_conv1d_silu(jnp.asarray(later), jnp.asarray(w))[:, :5], got[:, :5])
    assert gd.causal_conv1d_silu(jnp.asarray(x, jnp.bfloat16),
                                 jnp.asarray(w)).dtype == jnp.bfloat16


def conv_inputs(dtype=jnp.float32, r=2, b=2, s=96, hk=2, d=128, taps=4):
    """``in_proj_qkvz``'s output laid out per key head ``[q | k | v | z]``,
    the convolution's weights in the channel order ``[q | k | v]``, and a
    cotangent for each of q, k, v, z."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    width = hk * (2 * d + 2 * r * d)
    x = jax.random.normal(ks[0], (b, s, width)).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (2 * hk * d + hk * r * d, taps))
    cts = [jax.random.normal(k_, (b, s, n)).astype(dtype) for k_, n in
           zip(ks[2:], (hk * d, hk * d, hk * r * d, hk * r * d))]
    return x, w, cts, dict(key_heads=hk, key_dim=d, value_dim=d)


def conv_oracle(x, w, key_heads, key_dim, value_dim):
    """``causal_conv1d_silu`` fed the split-and-concatenated input, as the
    model called it before the kernels; z cut out beside."""
    b, s, width = x.shape
    hk, d = key_heads, key_dim
    per_head = width // hk
    rd = (per_head - 2 * d) // 2
    q, k, v, z = jnp.split(x.reshape(b, s, hk, per_head),
                           [d, 2 * d, 2 * d + rd], axis=-1)
    flat = lambda t: t.reshape(b, s, -1)
    mixed = gd.causal_conv1d_silu(
        jnp.concatenate([flat(q), flat(k), flat(v)], axis=-1), w)
    return (*jnp.split(mixed, [hk * d, 2 * hk * d], axis=-1), flat(z))


@pytest.fixture
def small_conv_tiles(monkeypatch):
    """Row blocks of 32 worked through 16 rows at a time: a sequence of 96
    is three blocks, so the taps cross block and piece boundaries."""
    monkeypatch.setattr(gd, "_CONV_ROWS", 32)
    monkeypatch.setattr(gd, "_CONV_PIECE", 16)


@pytest.mark.parametrize("r", [1, 2])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_conv_kernels_match_the_xla_form(dtype, r, small_conv_tiles):
    """q, k, v, z, the gradient in the projection's own layout and dw, a
    batch of two rows of three row blocks each."""
    x, w, cts, dims = conv_inputs(dtype, r)
    f32 = lambda t: t.astype(jnp.float32)

    def both(fn):       # the four parts and the two gradients, one program
        def loss(x, w):
            outs = fn(x, w, **dims)
            return sum(jnp.sum(f32(o) * f32(c)) for o, c in zip(outs, cts)), outs
        grads, outs = jax.jit(jax.grad(loss, argnums=(0, 1), has_aux=True))(x, w)
        return outs, grads

    kernels = lambda x, w, **kw: gd.split_conv_qkvz(x, w, use_pallas=True, **kw)
    (got, (dx, dw)), (want, (dx_want, dw_want)) = both(kernels), both(conv_oracle)
    one_ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
    for name, a, b in zip("qkvz", got, want):
        assert a.shape == b.shape and a.dtype == dtype, name
        assert gap(f32(a), f32(b)) <= one_ulp, name
    np.testing.assert_array_equal(got[3], want[3])          # z is a copy
    assert dx.shape == x.shape and dx.dtype == dtype
    assert dw.shape == w.shape and dw.dtype == jnp.float32
    assert gap(f32(dx), f32(dx_want)) <= 2 * one_ulp
    assert gap(dw, dw_want) <= 1e-5      # float32 sums in another order


def test_conv_kernels_start_every_row_of_the_batch_from_zeros(
        small_conv_tiles):
    """The second row's first outputs must not see the first row's tail:
    each row alone gives the same bits as the two together."""
    x, w, cts, dims = conv_inputs(jnp.bfloat16)
    @jax.jit
    def run(x, ct):     # the four parts and v's gradient, one program
        def loss(x):
            outs = gd.split_conv_qkvz(x, w, use_pallas=True, **dims)
            return jnp.sum(outs[2].astype(jnp.float32) * ct), outs
        return jax.grad(loss, has_aux=True)(x)

    ct = cts[2].astype(jnp.float32)
    dx_both, both = run(x, ct)
    for row in (0, 1):
        dx_alone, alone = run(x[row:row + 1], ct[row:row + 1])
        for a, b in zip(alone, both):
            np.testing.assert_array_equal(a[0], b[row])
        np.testing.assert_array_equal(dx_alone[0], dx_both[row])


def test_conv_kernels_are_causal_across_row_blocks(small_conv_tiles):
    """A changed later token leaves every earlier output bit-equal, and
    reaches exactly the K outputs from its own position on — across the
    boundary of a row block (token 63 feeds outputs 63..66)."""
    x, w, _, dims = conv_inputs(jnp.float32, b=1)
    base = gd.split_conv_qkvz(x, w, use_pallas=True, **dims)
    later = gd.split_conv_qkvz(x.at[:, 63].add(1.0), w, use_pallas=True,
                               **dims)
    for a, b in zip(base[:3], later[:3]):
        np.testing.assert_array_equal(a[:, :63], b[:, :63])
        np.testing.assert_array_equal(a[:, 67:], b[:, 67:])
        assert bool(jnp.all(jnp.any(a[:, 63:67] != b[:, 63:67], axis=-1)))


def test_conv_takes_the_xla_form_where_the_shapes_do_not_tile():
    from apex_tpu import obs

    gauge = lambda: obs.default_registry().get("gdn.conv_kernel").value
    x, w, _, dims = conv_inputs(s=40)        # 40 rows: no block of 16
    assert not gd.conv_supported(40, 128, 128, 2, 4)
    assert gd.conv_supported(96, 128, 128, 2, 4)
    assert not gd.conv_supported(96, 64, 128, 2, 4)      # half a lane tile
    assert not gd.conv_supported(96, 128, 128, 2, 10)    # taps past the halo
    with force_pallas(True):
        got = gd.split_conv_qkvz(x, w, **dims)
    assert gauge() == 0
    for a, b in zip(got, conv_oracle(x, w, **dims)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="128 lanes"):
        gd.split_conv_qkvz(x, w, use_pallas=True, **dims)
    with pytest.raises(ValueError, match="key heads of"):
        gd.split_conv_qkvz(x[..., :-128], w, **dims)
    with pytest.raises(ValueError, match="channels"):
        gd.split_conv_qkvz(x, w[:-1], **dims)
    x, w, _, dims = conv_inputs(s=48)
    with force_pallas(True):
        gd.split_conv_qkvz(x, w, **dims)
    assert gauge() == 1
    gd.split_conv_qkvz(x, w, **dims)         # off the TPU: the XLA form
    assert gauge() == 0


def test_rule_refuses_what_it_cannot_tile_and_sets_its_gauges():
    from apex_tpu import obs

    q, k, v, g, beta = inputs(b=1, s=40, h=2)
    with pytest.raises(ValueError, match="power of two"):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=24)
    with pytest.raises(ValueError, match="128 lanes"):
        gd.gated_delta_rule(q[..., :64], k[..., :64], v, g, beta,
                            use_pallas=True)
    assert gd.supported(64, 128, 128) and not gd.supported(64, 64, 128)
    gd.gated_delta_rule(q, k, v, g, beta, chunk=16)
    reg = obs.default_registry()
    assert reg.get("gdn.chunk").value == 16
    assert reg.get("gdn.chunks_per_row").value == 3
    assert reg.get("gdn.value_heads").value == 2
    assert reg.get("gdn.kernels").value == 0
    assert reg.get("gdn.local_in_kernel").value == 0
    with force_pallas(True):
        gd.gated_delta_rule(q, k, v, g, beta, chunk=16)
    assert reg.get("gdn.kernels").value == 1
    assert reg.get("gdn.local_in_kernel").value == 1
    with pytest.raises(ValueError, match="multiple of the key heads"):
        gd.gated_delta_rule(q, k, jnp.concatenate([v, v[:, :, :1]], 2), g, beta)
    assert gd.gated_delta_rule(q.astype(jnp.bfloat16), k, v.astype(jnp.bfloat16),
                               g, beta).dtype == jnp.bfloat16


def test_kernel_names_keep_clear_of_the_other_families():
    from apex_tpu.ops._common import KERNEL_NAMES

    ours = [n for n in KERNEL_NAMES if n.startswith("apex_gdn_")]
    assert sorted(ours) == ["apex_gdn_bwd", "apex_gdn_fwd"]
    for name in ours:
        assert not any(f in name for f in
                       ("apex_gmm", "apex_flash", "apex_ln_", "apex_xent_"))


def test_conv_kernel_names_keep_clear_of_every_family_readers_match():
    from apex_tpu.ops._common import KERNEL_NAMES

    ours = [n for n in KERNEL_NAMES if n.startswith("apex_conv1d_")]
    assert sorted(ours) == ["apex_conv1d_bwd", "apex_conv1d_fwd"]
    for name in ours:
        assert not any(f in name for f in (
            "apex_gmm", "apex_flash", "apex_ln_", "apex_xent_", "apex_gdn_"))
