"""The state-space scan (``ops/ssd.py``): the chunked ``jax.numpy`` path and
both Pallas kernels (interpret mode) against the token recurrence
``ssd_recurrent`` — forward and all six gradients, at the published chunk and
smaller ones, several chunks a row, heads of 64 channels side by side in a
lane tile and heads of 128 alone in one, B and C shared by the heads of a
group, a row whose decays underflow, rows that are not whole chunks, and
causality."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops import ssd
from apex_tpu.ops._common import KERNEL_NAMES, force_pallas
from apex_tpu.ops.gated_delta import causal_conv1d_silu


@pytest.fixture(autouse=True)
def _float32_products():
    with jax.default_matmul_precision("highest"):
        yield


def inputs(seed, b, s, h, p, n, g=1, decay=1.0, dtype=jnp.float32):
    """Seeded inputs in the cell's ranges: ``dt`` a softplus around a step
    size log-uniform in [1e-3, 1e-1] (times ``decay``), ``A`` in -[1, 16]."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    step = jnp.exp(jax.random.uniform(ks[0], (h,), jnp.float32,
                                      jnp.log(1e-3), jnp.log(1e-1)))
    dt = decay * jax.nn.softplus(
        0.5 * jax.random.normal(ks[1], (b, s, h)) + jnp.log(jnp.expm1(step)))
    x = jax.random.normal(ks[2], (b, s, h, p)).astype(dtype)
    a = -jax.random.uniform(ks[3], (h,), jnp.float32, 1.0, 16.0)
    bm = (0.5 * jax.random.normal(ks[4], (b, s, g, n))).astype(dtype)
    cm = (0.5 * jax.random.normal(ks[5], (b, s, g, n))).astype(dtype)
    d = 1.0 + 0.3 * jax.random.normal(ks[6], (h,))
    cot = jax.random.normal(ks[7], (b, s, h, p))
    return (x, dt, a, bm, cm, d), cot


def rel_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-30))


def both(fn, args, cot):
    """``(o, the six gradients of sum(o * cot))`` of ``fn``, one program."""
    def loss(*a):
        o = fn(*a)
        return jnp.sum(o.astype(jnp.float32) * cot), o
    grads, o = jax.jit(jax.grad(loss, argnums=tuple(range(6)), has_aux=True))(
        *args)
    return o, grads


NAMES = ("dx", "ddt", "dA", "dB", "dC", "dD")
CASES = {
    # name: (b, s, H, P, N, chunk)
    "published_chunk_two_lane_tiles": (1, 512, 4, 64, 128, 256),
    "small_chunk_four_chunks_two_rows": (2, 256, 4, 64, 128, 64),
    "two_head_groups_of_the_grid": (1, 128, 16, 64, 32, 64),
    "heads_of_a_whole_lane_tile": (1, 128, 2, 128, 32, 64),
}


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "pallas"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_scan_matches_the_recurrence_forward_and_all_six_gradients(case, kernels):
    """float32 on both sides, two derivations of the same sums: 1e-4 against
    each array's largest element is summation order (B and C of ONE group
    shared by every head: their gradients are sums over the heads)."""
    b, s, h, p, n, chunk = CASES[case]
    args, cot = inputs(sorted(CASES).index(case), b, s, h, p, n)
    assert ssd.supported(chunk, h, p, n)
    want_o, want = both(ssd.ssd_recurrent, args, cot)
    got_o, got = both(lambda *a: ssd.ssd_scan(*a, chunk=chunk,
                                              use_pallas=kernels), args, cot)
    assert got_o.shape == (b, s, h, p) and got_o.dtype == jnp.float32
    assert rel_gap(got_o, want_o) < 1e-4
    for name, g, w in zip(NAMES, got, want):
        assert g.shape == w.shape and g.dtype == w.dtype, name
        assert float(jnp.max(jnp.abs(w))) > 0, name
        assert rel_gap(g, w) < 1e-4, name


def test_kernels_were_traced_and_are_named():
    from apex_tpu import obs

    args, _ = inputs(1, 1, 64, 4, 64, 32)
    with force_pallas(True):
        text = str(jax.make_jaxpr(jax.grad(lambda *a: jnp.sum(
            ssd.ssd_scan(*a, chunk=32)), argnums=(0, 1)))(*args))
    assert obs.default_registry().get("ssd.kernel").value == 1
    assert "apex_ssd_fwd" in text and "apex_ssd_bwd" in text
    ours = [n for n in KERNEL_NAMES if n.startswith("apex_ssd_")]
    assert ours == ["apex_ssd_fwd", "apex_ssd_bwd"]
    # no family prefix a trace reader matches is part of the names
    for prefix in ("apex_flash_fwd", "apex_flash_bwd", "apex_ln_", "apex_xent_",
                   "apex_gmm", "apex_gdn_", "apex_conv1d_", "apex_gated_conv_"):
        assert not any(prefix in n for n in ours)
    with force_pallas(False):
        ssd.ssd_scan(*args, chunk=32)
    assert obs.default_registry().get("ssd.kernel").value == 0


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "pallas"])
def test_bfloat16_at_the_edge_stays_close(kernels):
    """x, B, C and o bfloat16 at the kernels' edge, everything between them
    float32: against the float32 recurrence on the SAME (rounded) inputs the
    output's gap is one rounding of o and, in the kernels, of the products'
    operands — 2e-2 of the largest element."""
    args, cot = inputs(2, 1, 256, 4, 64, 128, dtype=jnp.bfloat16)
    want_o, want = both(ssd.ssd_recurrent, args, cot)
    got_o, got = both(lambda *a: ssd.ssd_scan(*a, chunk=128,
                                              use_pallas=kernels), args, cot)
    assert got_o.dtype == jnp.bfloat16 and got[0].dtype == jnp.bfloat16
    assert got[1].dtype == jnp.float32 and got[3].dtype == jnp.bfloat16
    assert rel_gap(got_o, want_o) < 2e-2
    for name, g, w in zip(NAMES, got, want):
        assert rel_gap(g, w) < 3e-2, name


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "pallas"])
def test_decays_that_underflow_give_no_nan_and_no_inf(kernels):
    """A row whose log-decay reaches -300 and further inside a chunk (``dt``
    a hundred times the cell's): every decay is ``exp`` of a difference that is
    never positive, so nothing overflows; the recurrence agrees on the
    output and on the gradients that do not cancel (dA and ddt are sums of
    terms of both signs ~1e3 times their total: 2e-2)."""
    args, cot = inputs(3, 1, 256, 4, 64, 32, decay=100.0)
    x, dt, a = args[:3]
    assert float(jnp.min(jnp.sum((dt * a).reshape(1, 2, 128, 4), axis=2))) < -300
    want_o, want = both(ssd.ssd_recurrent, args, cot)
    got_o, got = both(lambda *t: ssd.ssd_scan(*t, chunk=128,
                                              use_pallas=kernels), args, cot)
    for t in (got_o, *got):
        assert bool(jnp.all(jnp.isfinite(t)))
    assert rel_gap(got_o, want_o) < 1e-4
    for name, g, w in zip(NAMES, got, want):
        assert rel_gap(g, w) < (2e-2 if name in ("ddt", "dA") else 1e-4), name


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "pallas"])
def test_a_row_that_is_not_whole_chunks_is_padded(kernels):
    """200 tokens at chunks of 64: the row is padded with tokens of ``dt`` 0
    (no decay, nothing added to the state) and their outputs cut off."""
    args, cot = inputs(4, 2, 200, 4, 64, 32)
    want_o, want = both(ssd.ssd_recurrent, args, cot)
    got_o, got = both(lambda *a: ssd.ssd_scan(*a, chunk=64,
                                              use_pallas=kernels), args, cot)
    assert got_o.shape == (2, 200, 4, 64)
    assert rel_gap(got_o, want_o) < 1e-4
    for name, g, w in zip(NAMES, got, want):
        assert rel_gap(g, w) < 1e-4, name


def test_what_the_kernels_refuse_takes_the_jnp_path():
    """Two groups of B and C, or heads that do not fill lane tiles: the
    kernels say no, ``use_pallas=True`` is an error and the default is the
    chunked ``jax.numpy`` form, which agrees with the recurrence."""
    assert not ssd.supported(64, 4, 32, 16, groups=2)
    assert not ssd.supported(64, 3, 64, 32)         # an odd head beside none
    assert not ssd.supported(64, 4, 48, 32)         # 48 channels: no lane tile
    assert not ssd.supported(60, 4, 64, 32)
    assert ssd.supported(256, 64, 64, 128)          # the cell's
    args, cot = inputs(5, 1, 128, 4, 32, 16, g=2)
    with pytest.raises(ValueError, match="one group"):
        ssd.ssd_scan(*args, chunk=64, use_pallas=True)
    with force_pallas(True):        # the default asks supported() first
        got_o, got = both(lambda *a: ssd.ssd_scan(*a, chunk=64), args, cot)
    want_o, want = both(ssd.ssd_recurrent, args, cot)
    assert rel_gap(got_o, want_o) < 1e-4
    for name, g, w in zip(NAMES, got, want):
        assert rel_gap(g, w) < 1e-4, name
    with pytest.raises(ValueError, match="multiple of G"):
        ssd.ssd_scan(args[0], args[1], args[2], args[3][:, :, :1].repeat(3, 2),
                     args[4][:, :, :1].repeat(3, 2), args[5])
    with pytest.raises(ValueError, match="chunk"):
        ssd.ssd_scan(*args, chunk=12)


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "pallas"])
def test_later_tokens_do_not_move_earlier_outputs(kernels):
    """CAUSAL across chunks: changed x, dt, B and C from token t on leave the
    outputs before t bit-equal and move the output at t — and a changed token
    BEFORE t reaches t through the state carried over two chunk edges."""
    args, _ = inputs(6, 1, 256, 4, 64, 32)
    scan = jax.jit(lambda *a: ssd.ssd_scan(*a, chunk=64, use_pallas=kernels))
    base = scan(*args)
    t = 150
    x, dt, a, bm, cm, d = args
    later = (x.at[:, t:].add(1.0), dt.at[:, t:].multiply(1.5), a,
             bm.at[:, t:].add(0.5), cm.at[:, t:].add(0.5), d)
    moved = scan(*later)
    np.testing.assert_array_equal(base[:, :t], moved[:, :t])
    assert rel_gap(moved[:, t], base[:, t]) > 1e-3
    earlier = scan(x.at[:, 10].add(5.0), dt, a, bm, cm, d)
    np.testing.assert_array_equal(base[:, :10], earlier[:, :10])
    assert float(jnp.max(jnp.abs(earlier[:, t] - base[:, t]))) > 0


def test_conv_with_bias_and_silu_is_the_shifted_sum():
    """``y_t = silu(sum_j w[:, j] x_{t-(K-1)+j} + b)``, zeros before the row's
    start: ``ops/gated_delta.py``'s convolution with its optional bias; no
    bias is a zero bias."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    x = jax.random.normal(ks[0], (2, 40, 24))
    w = jax.random.normal(ks[1], (24, 4))
    b = jax.random.normal(ks[2], (24,))
    got = causal_conv1d_silu(x, w, b)
    want = np.zeros((2, 40, 24), np.float32)
    xs, ws = np.asarray(x), np.asarray(w)
    for t in range(40):
        for j in range(4):
            if t - 3 + j >= 0:
                want[:, t] += ws[:, j] * xs[:, t - 3 + j]
    want = jax.nn.silu(want + np.asarray(b))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        causal_conv1d_silu(x, w, jnp.zeros((24,))),
        causal_conv1d_silu(x, w), rtol=1e-6, atol=1e-7)
    assert causal_conv1d_silu(x.astype(jnp.bfloat16), w, b).dtype \
        == jnp.bfloat16


# -- the convolution in front of the scan, read out of in_proj's output -------

D_IN, D_BC, HEADS = 128, 128, 2     # [z 128 | x 128 | B 128 | C 128 | dt 2]
XBC = slice(D_IN, 2 * D_IN + 2 * D_BC)


def conv_inputs(dtype=jnp.float32, b=2, s=96, d_bc=D_BC):
    """``in_proj``'s output ``[z | x | B | C | dt]``, the taps and a
    NON-ZERO bias over the ``xBC`` channels, a cotangent of the output's
    shape (the five parts side by side in the projection's own order)."""
    ks = jax.random.split(jax.random.PRNGKey(44), 4)
    width = 2 * D_IN + 2 * d_bc + HEADS
    return (jax.random.normal(ks[0], (b, s, width)).astype(dtype),
            0.5 * jax.random.normal(ks[1], (D_IN + 2 * d_bc, 4)),
            0.5 * jax.random.normal(ks[2], (D_IN + 2 * d_bc,)),
            jax.random.normal(ks[3], (b, s, width)).astype(dtype))


def conv_oracle(zxbcdt, w, bias):
    """``causal_conv1d_silu`` over the ``xBC`` columns cut out, as the model
    called it before the kernels; z and dt beside."""
    mixed = causal_conv1d_silu(zxbcdt[..., XBC], w, bias)
    return jnp.concatenate([zxbcdt[..., :D_IN], mixed,
                            zxbcdt[..., XBC.stop:]], axis=-1)


def conv_kernels(zxbcdt, w, bias, **kw):
    return jnp.concatenate(ssd.split_conv_xbc(
        zxbcdt, w, bias, d_inner=D_IN, d_bc=D_BC, use_pallas=True, **kw),
        axis=-1)


def conv_both(fn, x, w, bias, cot):
    """``(the output, the gradients of sum(out * cot) in the projection's
    output, the taps and the bias)``."""
    def loss(x, w, bias):
        out = fn(x, w, bias)
        return jnp.sum(out.astype(jnp.float32) * cot.astype(jnp.float32)), out
    grads, out = jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(
        x, w, bias)
    return (out, *grads)


@pytest.fixture(scope="module")
def conv_runs():
    """Both paths once a dtype, at row blocks of 32 worked through 16 rows at
    a time: a sequence of 96 is three blocks, so the taps cross block and
    piece edges, in a batch of two rows."""
    from apex_tpu.ops import gated_delta as gd

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(gd, "_CONV_ROWS", 32)
        patch.setattr(gd, "_CONV_PIECE", 16)
        return {dtype: (conv_both(conv_kernels, *conv_inputs(dtype)),
                        conv_both(conv_oracle, *conv_inputs(dtype)))
                for dtype in (jnp.float32, jnp.bfloat16)}


RESULTS = ("forward", "dx", "dw", "dbias")       # conv_both's, in order


@pytest.mark.parametrize("what", RESULTS)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_xbc_conv_kernels_match_the_jnp_form(conv_runs, dtype, what):
    """x, B and C at a column offset with a bias; the gradient of the
    projection's WHOLE output where it lies, dw and dbias summed over both
    rows of the batch and three row blocks each."""
    got, want = (side[RESULTS.index(what)] for side in conv_runs[dtype])
    assert got.shape == want.shape and got.dtype == want.dtype
    one_ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
    if what in ("dw", "dbias"):     # float32 sums in another order
        assert got.dtype == jnp.float32
        assert rel_gap(got, want) <= 1e-5
    else:
        assert got.dtype == dtype
        assert rel_gap(got[..., XBC], want[..., XBC]) <= 2 * one_ulp
        # the second row of the batch starts from zeros, not the first's tail
        assert rel_gap(got[1, :4, XBC], want[1, :4, XBC]) <= 2 * one_ulp


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_xbc_conv_kernels_leave_z_and_dt_and_their_gradient_alone(
        conv_runs, dtype):
    """The columns outside the range come back as copies, and their
    cotangents pass through the backward kernel to where they lie, bit for
    bit."""
    x, _, _, cot = conv_inputs(dtype)
    (out, dx, *_), _ = conv_runs[dtype]
    for cols in (slice(0, D_IN), slice(XBC.stop, None)):
        np.testing.assert_array_equal(out[..., cols], x[..., cols])
        np.testing.assert_array_equal(dx[..., cols], cot[..., cols])


def test_xbc_conv_kernels_are_causal_across_row_blocks(monkeypatch):
    """A changed later token leaves every earlier output bit-equal and
    reaches exactly the K outputs from its own position on — across the
    edge of a row block (token 63 feeds outputs 63..66) — and moves nothing
    outside the range; without a bias the kernels are the biased ones at a
    zero bias."""
    from apex_tpu.ops import gated_delta as gd

    monkeypatch.setattr(gd, "_CONV_ROWS", 32)
    monkeypatch.setattr(gd, "_CONV_PIECE", 16)
    x, w, bias, _ = conv_inputs(b=1)
    base = conv_kernels(x, w, bias)
    later = conv_kernels(x.at[:, 63, XBC].add(1.0), w, bias)
    np.testing.assert_array_equal(base[:, :63], later[:, :63])
    np.testing.assert_array_equal(base[:, 67:], later[:, 67:])
    assert bool(jnp.all(base[:, 63:67, XBC] != later[:, 63:67, XBC]))
    np.testing.assert_array_equal(base[:, 63:67, :D_IN], later[:, 63:67, :D_IN])
    np.testing.assert_array_equal(conv_kernels(x, w, None),
                                  conv_kernels(x, w, jnp.zeros_like(bias)))


def test_xbc_conv_takes_the_jnp_form_where_the_shapes_do_not_tile():
    from apex_tpu import obs

    gauge = lambda: obs.default_registry().get("ssd.conv_kernel").value
    split = lambda x, w, bias, d_bc=D_BC, **kw: ssd.split_conv_xbc(
        x, w, bias, d_inner=D_IN, d_bc=d_bc, **kw)
    x, w, bias, _ = conv_inputs(s=40)           # 40 rows: no block of 16
    with force_pallas(True):
        got = split(x, w, bias)
    assert gauge() == 0
    np.testing.assert_allclose(jnp.concatenate(got, axis=-1),
                               conv_oracle(x, w, bias), rtol=1e-5, atol=1e-6)
    with pytest.raises(ValueError, match="128 lanes"):
        split(x, w, bias, use_pallas=True)
    half = conv_inputs(s=48, d_bc=64)[:3]       # B and C of half a lane tile
    with pytest.raises(ValueError, match="128 lanes"):
        split(*half, d_bc=64, use_pallas=True)
    with force_pallas(True):
        z, xs, bm, cm, dt = split(*half, d_bc=64)
    assert gauge() == 0
    assert [t.shape[-1] for t in (z, xs, bm, cm, dt)] == [128, 128, 64, 64, 2]
    with pytest.raises(ValueError, match="channels"):
        split(x, w[:-1], bias)
    with pytest.raises(ValueError, match="channels"):
        split(x, w, bias[:-1])
    with pytest.raises(ValueError, match="is not"):
        ssd.split_conv_xbc(x, w, bias, d_inner=D_IN + 128, d_bc=D_BC)
    x, w, bias, _ = conv_inputs(s=48)
    with force_pallas(True):
        split(x, w, bias)
    assert gauge() == 1
    split(x, w, bias)                           # off the TPU: the jnp form
    assert gauge() == 0
