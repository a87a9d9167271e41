"""The gated short convolution ``C * conv(B * X)`` (``ops/gated_conv.py``):
its ``jax.numpy`` form against three shifted passes written out here, and the
two kernels (interpret mode) against that form — forward and every gradient,
rows that cross block and piece edges, every row of a batch from zeros."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import obs
from apex_tpu.ops import gated_conv as gc
from apex_tpu.ops._common import force_pallas


def gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))


def inputs(dtype=jnp.float32, b=2, s=96, d=256, taps=3):
    """``W_in``'s output laid out ``[B | C | X]``, the taps, a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    bcx = jax.random.normal(ks[0], (b, s, 3 * d)).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (d, taps))
    ct = jax.random.normal(ks[2], (b, s, d)).astype(dtype)
    return bcx, w, ct


def oracle(bcx, w):
    """The definition, a shifted pass a tap: ``y_t = C_t * sum_j w[:, j]
    (B X)_{t-(K-1)+j}``, rows before the start are zeros."""
    d, k = w.shape
    f32 = lambda t: np.asarray(t, np.float32)
    gate_in, gate_out, x = f32(bcx[..., :d]), f32(bcx[..., d:2 * d]), f32(bcx[..., 2 * d:])
    u = gate_in * x
    conv = np.zeros_like(u)
    for j in range(k):
        back = k - 1 - j
        conv[:, back:] += u[:, :u.shape[1] - back] * f32(w)[:, j]
    return gate_out * conv


@pytest.fixture
def small_tiles(monkeypatch):
    """Row blocks of 32 worked through 16 rows x 128 lanes at a time: a
    sequence of 96 is three blocks of two pieces and a third of 256 lanes two
    lane blocks, so the taps cross block and piece boundaries."""
    for name, value in (("_FWD_ROWS", 32), ("_BWD_ROWS", 32),
                        ("_FWD_LANES", 128), ("_BWD_LANES", 128),
                        ("_PIECE", 16 * 128)):
        monkeypatch.setattr(gc, name, value)


@pytest.mark.parametrize("taps", [3, 4, 1])
def test_the_jnp_form_is_the_definition(taps):
    bcx, w, _ = inputs(taps=taps)
    got = gc.gated_short_conv(bcx, w, use_pallas=False)
    assert got.shape == (2, 96, 256) and got.dtype == jnp.float32
    assert gap(got, oracle(bcx, w)) <= 1e-6
    half = gc.gated_short_conv(bcx.astype(jnp.bfloat16), w, use_pallas=False)
    assert half.dtype == jnp.bfloat16        # one rounding, at the output
    assert gap(half, oracle(bcx.astype(jnp.bfloat16), w)) <= 2.0 ** -8


def _grads(fn, bcx, w, ct):
    f32 = lambda t: t.astype(jnp.float32)
    return jax.grad(lambda bcx, w: jnp.sum(f32(fn(bcx, w)) * f32(ct)),
                    argnums=(0, 1))(bcx, w)


@pytest.mark.parametrize("taps", [3, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_kernels_match_the_jnp_form(dtype, taps, small_tiles):
    """The output, the projection's gradient ``[dB | dC | dX]`` where it
    lies and the taps' gradient, a batch of two rows of three row blocks."""
    bcx, w, ct = inputs(dtype, taps=taps)
    assert gc._tile(96, 256) == (32, 16, 128, 32, 16, 128)
    kernels = lambda bcx, w: gc.gated_short_conv(bcx, w, use_pallas=True)
    got, want = kernels(bcx, w), gc.gated_short_conv_ref(bcx, w)
    assert obs.default_registry().get("gated_conv.kernel").value == 1
    one_ulp = 2.0 ** -8 if dtype == jnp.bfloat16 else 1e-6
    assert got.shape == want.shape and got.dtype == dtype
    assert gap(got, want) <= one_ulp
    (dx, dw), (dx_want, dw_want) = (_grads(f, bcx, w, ct)
                                    for f in (kernels, gc.gated_short_conv_ref))
    assert dx.shape == bcx.shape and dx.dtype == dtype
    assert dw.shape == w.shape and dw.dtype == jnp.float32
    for third, name in enumerate(("dB", "dC", "dX")):
        cols = slice(third * 256, (third + 1) * 256)
        assert gap(dx[..., cols], dx_want[..., cols]) <= 2 * one_ulp, name
    assert gap(dw, dw_want) <= 1e-5      # float32 sums in another order


def test_kernels_start_every_row_of_the_batch_from_zeros(small_tiles):
    """The second row's first outputs must not see the first row's tail, nor
    its gradient the first row's: each row alone gives the same bits as the
    two together."""
    bcx, w, ct = inputs(jnp.bfloat16)
    kernels = lambda bcx, w: gc.gated_short_conv(bcx, w, use_pallas=True)
    both, (dx_both, _) = kernels(bcx, w), _grads(kernels, bcx, w, ct)
    for row in (0, 1):
        one = slice(row, row + 1)
        np.testing.assert_array_equal(kernels(bcx[one], w)[0], both[row])
        np.testing.assert_array_equal(
            _grads(kernels, bcx[one], w, ct[one])[0][0], dx_both[row])


def test_kernels_are_causal_across_row_blocks(small_tiles):
    """A changed later token leaves every earlier output bit-equal and
    reaches exactly the K outputs from its own position on — across the
    boundary of a row block (token 63's X feeds outputs 63..65); its C gates
    its own output alone."""
    bcx, w, _ = inputs(jnp.float32, b=1)
    kernels = lambda t: gc.gated_short_conv(t, w, use_pallas=True)
    base = kernels(bcx)
    later = kernels(bcx.at[:, 63, 2 * 256:].add(1.0))          # X of token 63
    np.testing.assert_array_equal(base[:, :63], later[:, :63])
    np.testing.assert_array_equal(base[:, 66:], later[:, 66:])
    assert all(float(jnp.max(jnp.abs(base[:, t] - later[:, t]))) > 0
               for t in (63, 64, 65))
    gated = kernels(bcx.at[:, 63, 256:2 * 256].add(1.0))       # C of token 63
    changed = np.flatnonzero(np.any(np.asarray(base != gated)[0], axis=-1))
    np.testing.assert_array_equal(changed, [63])


def test_the_jnp_form_where_the_shapes_do_not_tile():
    """Off the TPU nothing asks for the kernels; shapes they refuse take the
    ``jax.numpy`` form, and asking for the kernels there is an error."""
    gauge = lambda: obs.default_registry().get("gated_conv.kernel").value
    assert gc.supported(16384, 2048, 3) and gc.supported(96, 128, 9)
    assert not gc.supported(40, 128, 3)          # 40 rows: no block of 16
    assert not gc.supported(96, 192, 3)          # a third of 1.5 lane tiles
    assert not gc.supported(96, 128, 10)         # taps past the halo
    bcx, w, _ = inputs(s=40, d=128)
    with force_pallas(True):
        got = gc.gated_short_conv(bcx, w)
    assert gauge() == 0 and gap(got, oracle(bcx, w)) <= 1e-6
    gc.gated_short_conv(*inputs(d=128)[:2])
    assert gauge() == 0                          # the CPU: the jnp form
    with pytest.raises(ValueError, match="128 lanes"):
        gc.gated_short_conv(bcx, w, use_pallas=True)
    with pytest.raises(ValueError, match="3 d"):
        gc.gated_short_conv(bcx[..., :-128], w)
