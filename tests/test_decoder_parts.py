"""The parts the sparse decoder families share (``models/decoder.py``): what
no family's own reference comparison holds — that the one rotation and the
one norm are the two forms they replaced, the masked loss's edge, and that
each family's LM is the shared shell with no other family's module behind
it — and the two hooks a family may state beside ``embed_scale``: a scale of
the attention scores that is not ``d ** -0.5`` and a divisor of the logits."""
import ast
import importlib
import inspect

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.models.decoder import (DecoderLM, RMSNorm, causal_attention,
                                     masked_token_mean_loss, rotary)
from apex_tpu.ops.softmax_xentropy import softmax_cross_entropy

FAMILIES = {
    "afmoe": ("AfmoeConfig", "AfmoeLM"),
    "deepseek_v3": ("DeepseekV3Config", "DeepseekV3LM"),
    "qwen3_next": ("Qwen3NextConfig", "Qwen3NextLM"),
    "smallthinker": ("SmallThinkerConfig", "SmallThinkerLM"),
    "lfm2": ("Lfm2Config", "Lfm2LM"),
    "granite_hybrid": ("GraniteHybridConfig", "GraniteHybridLM"),
}


def _bits(a):
    return np.asarray(a).view(np.uint32 if a.dtype == jnp.float32
                              else np.uint16)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rotary_whole_head_is_rot_equal_to_the_head(dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 40, 64), dtype)
    np.testing.assert_array_equal(_bits(rotary(x, 1e4)),
                                  _bits(rotary(x, 1e4, rot=64)))
    # and it turns: position 0 stays, later positions move
    y = rotary(x, 1e4)
    np.testing.assert_array_equal(_bits(y[..., 0, :]), _bits(x[..., 0, :]))
    assert np.any(_bits(y[..., 1:, :]) != _bits(x[..., 1:, :]))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_partial_rotary_leaves_the_tail_bit_for_bit(dtype):
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 3, 40, 64), dtype)
    y = rotary(x, 1e7, rot=16)
    assert y.shape == x.shape and y.dtype == x.dtype
    np.testing.assert_array_equal(_bits(y[..., 16:]), _bits(x[..., 16:]))
    # the rotated part is the whole-head rotation of those dims alone
    np.testing.assert_array_equal(_bits(y[..., :16]),
                                  _bits(rotary(x[..., :16], 1e7)))


def test_zero_centred_norm_at_zero_is_the_plain_norm_at_one():
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 24, 128)) * 3.0
    plain, centred = RMSNorm(1e-6), RMSNorm(1e-6, zero_centred=True)
    p = plain.init(jax.random.PRNGKey(0), x)
    c = centred.init(jax.random.PRNGKey(0), x)
    # they initialise to the gains that make them one function
    np.testing.assert_array_equal(np.asarray(p["params"]["scale"]), 1.0)
    np.testing.assert_array_equal(np.asarray(c["params"]["scale"]), 0.0)
    np.testing.assert_array_equal(_bits(plain.apply(p, x)),
                                  _bits(centred.apply(c, x)))
    # and off the initial value the one adds 1 to what the other holds
    w = jax.random.normal(jax.random.PRNGKey(3), (128,)) * 0.1
    np.testing.assert_array_equal(
        _bits(plain.apply({"params": {"scale": 1.0 + w}}, x)),
        _bits(centred.apply({"params": {"scale": w}}, x)))


def test_masked_loss_is_the_mean_over_the_predicted_tokens():
    logits = jax.random.normal(jax.random.PRNGKey(4), (3, 16, 50)) * 2.0
    labels = jax.random.randint(jax.random.PRNGKey(5), (3, 16), 0, 50)
    labels = labels.at[0, 5:].set(-100).at[2].set(-100)
    valid = np.asarray(labels) >= 0
    per_tok = np.asarray(softmax_cross_entropy(logits, jnp.maximum(labels, 0)))
    got = masked_token_mean_loss(logits, labels, jnp.float32)
    np.testing.assert_allclose(float(got), per_tok[valid].mean(), rtol=1e-6)
    # what sits under an ignored label moves nothing
    noisy = jnp.where(valid[..., None], logits, logits + 100.0)
    assert float(masked_token_mean_loss(noisy, labels, jnp.float32)) \
        == float(got)
    # the logits go into the loss in the dtype named
    want = np.asarray(softmax_cross_entropy(
        logits.astype(jnp.bfloat16), jnp.maximum(labels, 0)))[valid].mean()
    np.testing.assert_allclose(
        float(masked_token_mean_loss(logits, labels, jnp.bfloat16)), want,
        rtol=1e-6)


def test_masked_loss_of_no_predicted_token_is_zero_not_nan():
    logits = jax.random.normal(jax.random.PRNGKey(6), (1, 8, 50))
    labels = jnp.full((1, 8), -100, jnp.int32)
    loss, grad = jax.value_and_grad(masked_token_mean_loss)(
        logits, labels, jnp.float32)
    assert float(loss) == 0.0
    assert not np.asarray(grad).any()


@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_a_family_is_the_shared_shell_and_its_own_module(family):
    module = importlib.import_module(f"apex_tpu.models.{family}")
    cfg_cls, lm = (getattr(module, n) for n in FAMILIES[family])
    assert issubclass(lm, DecoderLM)
    # the shell is written once: a family overrides neither half of it
    assert "setup" not in vars(lm) and "__call__" not in vars(lm)

    cfg = cfg_cls.tiny()
    ids = jnp.zeros((1, 128), jnp.int32)
    shapes = jax.eval_shape(
        lambda: lm(cfg).init(jax.random.PRNGKey(0), ids))["params"]
    want = {"embed", "norm_f", *(f"layer_{i}" for i in range(cfg.num_layers))}
    if not lm.tied_head:
        want.add("head")
    assert set(shapes) == want
    assert set(shapes["norm_f"]) == {"scale"}

    # no family's module imports another's: the source's import lines say
    # (models/__init__.py imports them all, so sys.modules cannot)
    imported = set()
    for node in ast.walk(ast.parse(inspect.getsource(module))):
        if isinstance(node, ast.ImportFrom) and node.module:
            imported.add(node.module)
            imported.update(f"{node.module}.{a.name}" for a in node.names)
        elif isinstance(node, ast.Import):
            imported.update(a.name for a in node.names)
    others = {f"apex_tpu.models.{f}" for f in FAMILIES if f != family}
    assert not imported & others, imported & others


def _plain_attention(q, k, v, scale):
    group = q.shape[1] // k.shape[1]
    k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    n = q.shape[2]
    s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v)


@pytest.mark.parametrize("scale", [None, 1 / 64, 0.5])
@pytest.mark.parametrize("kernels", [False, True], ids=["off_tpu", "pallas"])
def test_causal_attention_takes_a_scale_of_its_own(scale, kernels):
    """``scale=None`` is ``d ** -0.5`` (the program every family had); a
    stated scale multiplies the scores instead — 1/64 at a head of 64 is not
    1/8 — through the kernels and off them, at four query heads a key/value
    head."""
    from apex_tpu.ops._common import force_pallas

    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (1, 4, 128, 64))
    k, v = (jax.random.normal(key, (1, 1, 128, 64)) for key in ks[1:])
    with force_pallas(kernels), jax.default_matmul_precision("highest"):
        got = causal_attention(q, k, v, scale=scale)
        default = causal_attention(q, k, v)
        want = _plain_attention(q, k, v, 64 ** -0.5 if scale is None else scale)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    if scale is None:
        np.testing.assert_array_equal(np.asarray(got), np.asarray(default))
    else:
        assert float(jnp.max(jnp.abs(got - default))) > 1e-2
    text = str(jax.make_jaxpr(lambda *a: causal_attention(*a, scale=scale))(
        q, k, v).pretty_print(name_stack=True))
    assert "attn_full" in text and "attn_window" not in text


class _Block(nn.Module):        # the plainest block: what comes in goes out
    cfg: object
    index: int

    def __call__(self, x, deterministic=True):
        return x


def _shell(divisor, scale):
    import types

    class LM(DecoderLM):
        layer_cls = _Block
        tied_head = True

        @staticmethod
        def embed_scale(cfg):
            return scale

        @staticmethod
        def logits_divisor(cfg):
            return divisor

    cfg = types.SimpleNamespace(
        vocab_size=64, hidden_size=32, num_layers=1, rms_norm_eps=1e-5,
        initializer_range=0.02, remat_policy="none", compute_dtype=jnp.float32)
    return LM(cfg)


@pytest.mark.parametrize("divisor", [None, 8.0, 0.5])
def test_logits_divisor_divides_the_float32_logits(divisor):
    """The hook's default is nothing; a stated divisor divides the logits —
    and so the loss sees the divided ones: the shell with a divisor gives the
    plain shell's logits over it, bit for bit where it is a power of two."""
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    plain, scaled = _shell(None, None), _shell(divisor, None)
    assert DecoderLM.logits_divisor(plain.cfg) is None
    params = plain.init(jax.random.PRNGKey(0), ids)["params"]
    base = plain.apply({"params": params}, ids)
    got, loss = scaled.apply({"params": params}, ids, labels=ids)
    assert got.dtype == jnp.float32
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(base if divisor is None else base / divisor))
    want = masked_token_mean_loss(got, ids, jnp.float32)
    assert float(loss) == pytest.approx(float(want), rel=1e-6)
    if divisor is not None:
        _, plain_loss = plain.apply({"params": params}, ids, labels=ids)
        assert abs(float(loss) - float(plain_loss)) > 1e-6
