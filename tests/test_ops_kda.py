"""``ops/kda.py``: the chunked delta rule with a decay a key channel against
the token recurrence it is defined by (outputs and every gradient; the kernels
in interpret mode, and the ``lax.scan`` path), the decays at their strongest
and spread over five decades, the l2 norm of q and k made inside the rule, a
chunk that does not divide the row, a decay constant over
the channels against ``ops/gated_delta.py``'s scalar rule, and the convolution
in front of it under this mixer's layout."""
import jax
import jax.numpy as jnp
import pytest

from apex_tpu.ops import gated_delta as gd
from apex_tpu.ops import kda
from apex_tpu.ops._common import KERNEL_NAMES, force_pallas


def inputs(seed=0, b=2, s=40, h=3, d=128, a_max=16.0, beta_shift=0.0,
           dtype=jnp.float32):
    """q, k normalised as the model hands them over; head 0 decays at
    ``a_max`` (the strongest ``A = exp(A_log)`` the initialisation draws:
    about -21 a token a channel under a large gate), the others at 1 and
    0.01 in turn; every channel its own gate."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + 1e-6)
    q = l2(jax.random.normal(ks[0], (b, s, h, d))) * d ** -0.5
    k = l2(jax.random.normal(ks[1], (b, s, h, d)))
    v = jax.random.normal(ks[2], (b, s, h, d))
    a = 3.0 * jax.random.normal(ks[3], (b, s, h, d))
    rates = jnp.resize(jnp.array([a_max, 1.0, 0.01]), (h,))[:, None]
    g = -rates * jax.nn.softplus(a + 1.0)
    beta = jax.nn.sigmoid(4.0 * jax.random.normal(ks[4], (b, s, h)) + beta_shift)
    return q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta


def gap(a, b):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def grads_of(fn, args, ct):
    return jax.grad(lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * ct),
                    argnums=(0, 1, 2, 3, 4))(*args)


NAMES = ("q", "k", "v", "g", "beta")
# ONE shape, so that the cases share their compiles (the suite's clock is
# tight): a row of 100 tokens at the model's chunks of 64 — a whole chunk of
# four sub-blocks (the VPU's passes, and the products of a sub-block against
# the tokens before it), then a chunk that the row does NOT fill, its state
# handed on from the first.
SHAPE = dict(b=1, s=100, chunk=64)
CASES = {
    "strongest_decay": dict(SHAPE),
    "beta_near_0": dict(SHAPE, beta_shift=-12.0),
    "beta_near_1": dict(SHAPE, beta_shift=12.0),
    # every channel of every head at -21 a token over a whole chunk (-1300
    # by its end): a factored decay would overflow float32
    "whole_chunk_at_minus_21": dict(SHAPE, g_all=-21.0),
    # q and k as the convolution leaves them (norms far from 1): the rule
    # normalises them — inside the kernels, where they run — and its dq and
    # dk are the gradients in the RAW q and k
    "norm_inside_the_rule": dict(SHAPE, qk_norm=(1e-6, 128 ** -0.5)),
    # a token's decay anywhere from -1e-4 to -21, channel by channel: the
    # running sums reach hundreds while neighbours differ by 1e-4, and the
    # decays read their DIFFERENCES
    "g_from_1e-4_to_21_a_token": dict(SHAPE, g_span=(1e-4, 21.0)),
}
NORM_GAUGE = "kda.qk_norm_in_kernel"


@pytest.mark.parametrize("kernels", [False, True], ids=["scan", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_chunked_rule_matches_the_recurrence(case, kernels):
    """Outputs and all five gradients, float32; no exponent that is built is
    positive, so everything stays finite at the strongest decay.  Where the
    rule normalises q and k itself, the kernels also against ``l2`` in
    ``jax.numpy`` in front of the call that does not; where ``g`` spans five
    decades, o and dg also against the scan path at float32's own size: the
    kernels' running sums carry all of g's bits."""
    from apex_tpu import obs

    kw = dict(CASES[case])
    chunk, g_all, g_span, qk_norm = (kw.pop(name, None) for name in (
        "chunk", "g_all", "g_span", "qk_norm"))
    q, k, v, g, beta = inputs(**kw)
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    if g_all is not None:
        g = jnp.full_like(g, g_all)
    if g_span is not None:
        lo, hi = map(jnp.log, g_span)
        g = -jnp.exp(jax.random.uniform(keys[0], g.shape, minval=lo, maxval=hi))
    normed = lambda q, k: (q, k)
    if qk_norm is not None:
        eps, scale = qk_norm
        l2 = lambda x: x * jax.lax.rsqrt(jnp.sum(x * x, -1, keepdims=True) + eps)
        normed = lambda q, k: (l2(q) * scale, l2(k))
        q = 3.0 * jax.random.normal(keys[1], q.shape)
        k = 0.5 * jax.random.normal(keys[2], k.shape)
    args = (q, k, v, g, beta)
    recurrence = lambda q, k, *rest: kda.kda_rule_recurrent(*normed(q, k), *rest)
    want = recurrence(*args)
    ct = jax.random.normal(jax.random.PRNGKey(9), want.shape)
    chunked = lambda *a: kda.kda_rule(*a, chunk=chunk, qk_norm=qk_norm)
    with force_pallas(kernels):
        got = chunked(*args)
        assert obs.default_registry().get(NORM_GAUGE).value == int(
            kernels and qk_norm is not None)
        got_grads = grads_of(chunked, args, ct)
    assert gap(got, want) < 2e-5
    for name, a, b in zip(NAMES, got_grads, grads_of(recurrence, args, ct)):
        assert a.shape == b.shape and bool(jnp.isfinite(a).all()), name
        # (with every decay exp(-21) g's gradient is ~1e-10, the float32
        # roundoff of the O(1e-3) terms it is the difference of: finite)
        if not (name == "g" and g_all is not None):
            assert gap(a, b) < 1e-4, name
    if not kernels:
        return
    if qk_norm is not None:
        outside = lambda q, k, *rest: kda.kda_rule(
            *normed(q, k), *rest, chunk=chunk, use_pallas=True)
        assert gap(got, outside(*args)) < 1e-6
        assert obs.default_registry().get(NORM_GAUGE).value == 0
        for name, a, b in zip(NAMES, got_grads, grads_of(outside, args, ct)):
            assert gap(a, b) < 2e-6, name
    if g_span is not None:
        scan = lambda *a: kda.kda_rule(*a, chunk=chunk, use_pallas=False)
        assert gap(got, scan(*args)) < 1e-5
        assert gap(got_grads[3], grads_of(scan, args, ct)[3]) < 1e-5


def test_a_decay_constant_over_the_channels_is_the_scalar_rule():
    """With ``g`` the same for every key channel of a head the rule IS
    ``gated_delta``'s, values and gradients (g's summed over the channels):
    this file's kernels against that file's scan path."""
    q, k, v, g, beta = inputs(b=1, s=100)
    g1 = g[..., 0]
    wide = lambda g1: jnp.broadcast_to(g1[..., None], g.shape)
    vector = lambda q, k, v, g1, beta: kda.kda_rule(
        q, k, v, wide(g1), beta, use_pallas=True)
    scalar = lambda *a: gd.gated_delta_rule(*a, use_pallas=False)
    args = (q, k, v, g1, beta)
    ct = jax.random.normal(jax.random.PRNGKey(3), v.shape)
    got, got_grads = vector(*args), grads_of(vector, args, ct)
    want, want_grads = scalar(*args), grads_of(scalar, args, ct)
    assert gap(got, want) < 2e-5
    for name, a, b in zip(NAMES, got_grads, want_grads):
        assert gap(a, b) < 1e-4, name


def test_rule_refuses_what_it_cannot_tile_and_sets_its_gauge():
    from apex_tpu import obs

    q, k, v, g, beta = inputs(s=16, d=64)
    assert not kda.supported(64, 64, 64) and kda.supported(64, 128, 128)
    assert not kda.supported(8, 128, 128)
    with pytest.raises(ValueError, match="128 lanes"):
        kda.kda_rule(q, k, v, g, beta, use_pallas=True)
    with pytest.raises(ValueError, match="power of two"):
        kda.kda_rule(q, k, v, g, beta, chunk=24)
    with pytest.raises(ValueError, match="share"):
        kda.kda_rule(q, k, v, g[..., :1], beta)
    # heads of 64 take the scan, whatever the backend
    with force_pallas(True):
        got = kda.kda_rule(q, k, v, g, beta, chunk=16)
        assert obs.default_registry().get("kda.kernels").value == 0
        jax.eval_shape(lambda *a: kda.kda_rule(*a, chunk=16), *inputs(s=16))
        assert obs.default_registry().get("kda.kernels").value == 1
    assert gap(got, kda.kda_rule_recurrent(q, k, v, g, beta)) < 1e-5


def test_kernel_names_keep_clear_of_the_other_families():
    """``apex_kda`` is matched by no other family's reader and matches none:
    a trace reader that asks for ``apex_gdn`` does not count these."""
    mine = [n for n in KERNEL_NAMES if "kda" in n]
    assert mine == ["apex_kda_fwd", "apex_kda_bwd"]
    others = [n for n in KERNEL_NAMES if n not in mine]
    assert not any(m in o or o in m for m in mine for o in others)


@pytest.mark.parametrize("kernels", [False, True], ids=["xla", "pallas"])
def test_convolution_reads_a_fused_projection_laid_out_per_head(kernels):
    """``split_conv_qkv`` on ``[q | k | v]`` a head against the plain
    convolution of each part, values and both gradients."""
    b, s, h, d, taps = 2, 64, 2, 128, 4
    qkv = jax.random.normal(jax.random.PRNGKey(0), (b, s, 3 * h * d))
    w = 0.3 * jax.random.normal(jax.random.PRNGKey(1), (3 * h * d, taps))

    def plain(qkv, w):
        parts = qkv.reshape(b, s, h, 3, d)
        return tuple(gd.causal_conv1d_silu(
            parts[:, :, :, i].reshape(b, s, h * d),
            w[i * h * d:(i + 1) * h * d]) for i in range(3))

    fused = lambda qkv, w: kda.split_conv_qkv(qkv, w, heads=h, head_dim=d)
    total = lambda fn: lambda *a: sum(
        jnp.sum(o * (i + 1.0)) for i, o in enumerate(fn(*a)))
    with force_pallas(kernels):
        got = fused(qkv, w)
        got_grads = jax.grad(total(fused), argnums=(0, 1))(qkv, w)
    for a, b_ in zip(got, plain(qkv, w)):
        assert gap(a, b_) < 1e-5
    for a, b_ in zip(got_grads, jax.grad(total(plain), argnums=(0, 1))(qkv, w)):
        assert gap(a, b_) < 1e-4
    with pytest.raises(ValueError, match="heads"):
        kda.split_conv_qkv(qkv[..., :-1], w, heads=h, head_dim=d)
