"""Pallas LayerNorm kernel vs jnp reference — the L1-style parity harness
(ref tests/L1/common/run_test.sh: native impl must match Python build under
identical inputs; tests/L0/run_fused_layer_norm/test_fused_layer_norm.py).

On CPU the kernel runs in Pallas interpreter mode; same math, same asserts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.ops.layer_norm import layer_norm, layer_norm_ref

TOL = 1e-5


@pytest.mark.parametrize("shape", [(64, 256), (3, 40, 128), (257, 384)])
@pytest.mark.parametrize("affine", [True, False])
def test_kernel_matches_ref_fwd(rng, shape, affine):
    x = jnp.asarray(rng.randn(*shape).astype(np.float32))
    n = shape[-1]
    w = jnp.asarray(rng.randn(n).astype(np.float32)) if affine else None
    b = jnp.asarray(rng.randn(n).astype(np.float32)) if affine else None
    out_k = layer_norm(x, w, b, use_pallas=True)
    out_r = layer_norm_ref(x, w, b)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=TOL)


def test_kernel_matches_ref_grads(rng):
    x = jnp.asarray(rng.randn(96, 256).astype(np.float32))
    w = jnp.asarray(rng.randn(256).astype(np.float32))
    b = jnp.asarray(rng.randn(256).astype(np.float32))

    def lk(x, w, b):
        return jnp.sum(jnp.square(layer_norm(x, w, b, use_pallas=True)))

    def lr(x, w, b):
        return jnp.sum(jnp.square(layer_norm_ref(x, w, b)))

    gk = jax.grad(lk, argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(lr, argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=1e-3, rtol=1e-4)


def test_matches_numpy_fp64(rng):
    """Stats-in-fp32 accuracy vs a float64 numpy LayerNorm."""
    x = rng.randn(128, 256).astype(np.float32)
    mean = x.astype(np.float64).mean(-1, keepdims=True)
    var = x.astype(np.float64).var(-1, keepdims=True)
    want = (x - mean) / np.sqrt(var + 1e-5)
    got = layer_norm(jnp.asarray(x), use_pallas=True)
    np.testing.assert_allclose(np.asarray(got), want, atol=1e-4)


def test_bf16_input(rng):
    x = jnp.asarray(rng.randn(64, 256), dtype=jnp.bfloat16)
    out = layer_norm(x, use_pallas=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(layer_norm_ref(x), np.float32),
        atol=1e-2,
    )


class TestModule:
    def test_affine_module(self, rng):
        m = FusedLayerNorm(normalized_shape=128)
        x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
        params = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(params, x)
        assert out.shape == x.shape
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(layer_norm_ref(x)), atol=1e-5
        )

    def test_multidim_normalized_shape(self, rng):
        m = FusedLayerNorm(normalized_shape=(4, 32))
        x = jnp.asarray(rng.randn(6, 4, 32).astype(np.float32))
        params = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(params, x)
        assert out.shape == x.shape
        # normalizes over the flattened trailing 128 elements
        np.testing.assert_allclose(
            np.asarray(out).reshape(6, -1).mean(-1), 0.0, atol=1e-5
        )

    def test_no_affine(self, rng):
        m = FusedLayerNorm(normalized_shape=128, elementwise_affine=False)
        x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
        params = m.init(jax.random.PRNGKey(0), x)
        assert not jax.tree_util.tree_leaves(params)  # no learned params
        m.apply(params, x)

    def test_shape_mismatch_raises(self, rng):
        m = FusedLayerNorm(normalized_shape=64)
        x = jnp.asarray(rng.randn(8, 128).astype(np.float32))
        with pytest.raises(ValueError, match="normalized_shape"):
            m.init(jax.random.PRNGKey(0), x)


def test_fused_dgamma_ragged_rows_eps0(rng):
    """Padded tail rows must be masked out of the dgamma/dbeta epilogue:
    at eps=0 an all-zero padded row has rstd=inf and xhat=NaN, and an
    unguarded sum would poison the whole accumulator (r5 regression)."""
    from apex_tpu.ops._common import force_pallas
    from apex_tpu.ops.layer_norm import layer_norm, layer_norm_ref

    n = 128
    x = jnp.asarray(rng.randn(257, n).astype(np.float32))  # ragged vs 256
    w = jnp.asarray(rng.randn(n).astype(np.float32))
    b = jnp.asarray(rng.randn(n).astype(np.float32))
    dy = jnp.asarray(rng.randn(257, n).astype(np.float32))

    def loss(fn):
        return lambda x, w, b: jnp.sum(fn(x, w, b) * dy)

    with force_pallas(True):
        gk = jax.grad(
            loss(lambda x, w, b: layer_norm(x, w, b, eps=0.0)),
            argnums=(0, 1, 2),
        )(x, w, b)
    gr = jax.grad(
        loss(lambda x, w, b: layer_norm_ref(x, w, b, eps=0.0)),
        argnums=(0, 1, 2),
    )(x, w, b)
    for a, r, name in zip(gk, gr, ("dx", "dgamma", "dbeta")):
        assert np.isfinite(np.asarray(a)).all(), name
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(r), atol=2e-4, rtol=1e-4, err_msg=name
        )


def test_fused_dgamma_env_kill_switch(rng, monkeypatch):
    """APEX_TPU_LN_FUSED_DGAMMA=0 pins the XLA-reduction backward: the
    epilogue kernel is never reached (the flag is the only gate — there
    is no compile probe behind it) and fused_dgamma_active() says so."""
    import importlib

    from apex_tpu.ops._common import force_pallas

    # module via importlib: the ops package rebinds `layer_norm` to the
    # function, so `import apex_tpu.ops.layer_norm as ln` gets the wrong
    # object
    ln = importlib.import_module("apex_tpu.ops.layer_norm")

    def boom(*a, **k):
        raise AssertionError("epilogue kernel reached with the switch off")

    monkeypatch.setattr(ln, "_FUSED_DGAMMA", False)
    monkeypatch.setattr(ln, "_ln_bwd_dx_dwdb_pallas", boom)
    assert not ln.fused_dgamma_active()

    n = 128
    x = jnp.asarray(rng.randn(64, n).astype(np.float32))
    w = jnp.asarray(rng.randn(n).astype(np.float32))
    b = jnp.asarray(rng.randn(n).astype(np.float32))

    def loss(fn):
        return lambda x, w, b: jnp.sum(jnp.square(fn(x, w, b)))

    with force_pallas(True):
        gk = jax.grad(loss(ln.layer_norm), argnums=(0, 1, 2))(x, w, b)
    gr = jax.grad(loss(layer_norm_ref), argnums=(0, 1, 2))(x, w, b)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=2e-4, rtol=1e-4)
