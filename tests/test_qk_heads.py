"""q, k and v from a fused projection's output to heads-major
(``models/decoder.py::qkv_heads``): the kernel pair of ``ops/qk_heads.py``
(interpret mode) against the composed path — ``jnp.split``, ``split_heads``,
``RMSNorm``, ``rotary`` — and the choice between the two by shape."""
import os
import sys

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu import obs  # noqa: E402
from apex_tpu.models import decoder  # noqa: E402
from apex_tpu.ops import qk_heads  # noqa: E402
from apex_tpu.ops._common import force_pallas  # noqa: E402


class Heads(nn.Module):
    """A block's four lines and nothing else."""

    hq: int
    hk: int
    hd: int
    kw: dict

    @nn.compact
    def __call__(self, x):
        return decoder.qkv_heads(x, self.hq, self.hk, self.hd, **self.kw)


def rel_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))


def _both_paths(model, x, seed=0):
    """``{kernels: (outputs, (d params, dx))}`` for one set of parameters
    (the norms' gains moved off 1) and one set of cotangents."""
    with force_pallas(False):
        params = model.init(jax.random.PRNGKey(seed), x).get("params", {})
    params = jax.tree_util.tree_map(
        lambda p: p + 0.1 * jax.random.normal(jax.random.PRNGKey(seed + 1),
                                              p.shape), params)
    shapes = [o.shape for o in jax.eval_shape(
        lambda: model.apply({"params": params}, x)) if o is not None]
    cots = [jax.random.normal(jax.random.PRNGKey(10 + i), shape, jnp.float32)
            for i, shape in enumerate(shapes)]

    def run(kernels):
        def loss(p, x):
            with force_pallas(kernels):
                # the tree is the same on both paths
                assert jax.tree_util.tree_structure(model.init(
                    jax.random.PRNGKey(0), x).get("params", {})
                ) == jax.tree_util.tree_structure(params)
                outs = model.apply({"params": p}, x)
            outs = [o for o in outs if o is not None]
            return sum(jnp.sum(o.astype(jnp.float32) * c)
                       for o, c in zip(outs, cots)), outs

        (_, outs), grads = jax.jit(jax.value_and_grad(
            loss, (0, 1), has_aux=True))(params, x)
        return outs, grads

    return {kernels: run(kernels) for kernels in (False, True)}


@pytest.fixture
def small_blocks(monkeypatch):
    """Row blocks of 32 in both kernels: 32 tokens are one block, 64 two."""
    monkeypatch.setattr(qk_heads, "_ROWS", 32)


@pytest.mark.parametrize("blocks", [1, 2], ids=["one_block", "two_blocks"])
@pytest.mark.parametrize("group", [7, 8], ids=["groups_of_7", "groups_of_8"])
@pytest.mark.parametrize("rotated", [False, True], ids=["still", "rotated"])
@pytest.mark.parametrize("normed", [False, True], ids=["raw", "normed"])
def test_the_kernel_pair_is_the_composed_path(small_blocks, normed, rotated,
                                              group, blocks):
    """q, k, v (and an output gate's columns behind them, in the groups of
    8) to one bfloat16 ulp, the projection's and the gains' gradients as
    close as one rounding of the composed path's cotangent leaves them; in
    float32 at the reference tests' 2e-4."""
    hk, hd = 2, 128
    hq = group * hk
    width = (hq + 2 * hk + (hq if group == 8 else 0)) * hd
    model = Heads(hq, hk, hd, dict(
        norm_eps=1e-5 if normed else None,
        theta=1.5e6 if rotated else None))
    x32 = jax.random.normal(jax.random.PRNGKey(3), (2, 32 * blocks, width))
    before = obs.default_registry().counter("ops.qk_heads.kernel").value

    got = _both_paths(model, x32.astype(jnp.bfloat16))
    (want_outs, want_grads), (outs, grads) = got[False], got[True]
    assert len(outs) == 3 + (group == 8)
    for o, w in zip(outs, want_outs):
        assert o.dtype == jnp.bfloat16 and o.shape == w.shape
        o, w = np.asarray(o, np.float32), np.asarray(w, np.float32)
        assert (np.abs(o - w) <= 2.0 ** -7 * np.maximum(np.abs(o), np.abs(w))
                ).all()
    for g, w in zip(jax.tree_util.tree_leaves(grads),
                    jax.tree_util.tree_leaves(want_grads)):
        assert g.dtype == w.dtype and rel_gap(g, w) < 2e-2

    got = _both_paths(model, x32)
    for g, w in zip(jax.tree_util.tree_leaves(got[True]),
                    jax.tree_util.tree_leaves(got[False])):
        assert g.dtype == w.dtype and rel_gap(g, w) < 2e-4
    assert set(got[True][1][0]) == ({"q_norm", "k_norm"} if normed else set())
    assert obs.default_registry().counter(
        "ops.qk_heads.kernel").value > before


@pytest.mark.parametrize("case,hd,kw,kernels", [
    ("heads_of_64", 64, dict(norm_eps=1e-5, theta=1e4), False),
    ("partial_rotation", 128, dict(norm_eps=1e-6, theta=1e7, rot=32), False),
    ("zero_centred_gain", 128,
     dict(norm_eps=1e-6, zero_centred=True, theta=1e4), False),
    ("normed_and_rotated", 128, dict(norm_eps=1e-5, theta=1e4), True),
    ("neither", 128, dict(), True),
])
def test_the_path_is_chosen_by_shape(case, hd, kw, kernels):
    """Heads of one lane tile rotated whole or not at all take the kernels
    (where the backend does: the TPU, or ``force_pallas``); heads of 64, a
    rotation over part of a head and a zero-centred gain lower with no
    ``apex_qk_heads_*`` call — and off the TPU so does everything."""
    hq, hk = 4, 2
    model = Heads(hq, hk, hd, kw)
    x = jnp.zeros((1, 64, (hq + 2 * hk) * hd), jnp.bfloat16)
    params = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0), x))
    reg = obs.default_registry()
    count = lambda: {name: reg.counter("ops.qk_heads." + name).value
                     for name in ("kernel", "composed")}

    def jaxpr():
        return str(jax.make_jaxpr(lambda p: model.apply(p, x))(params))

    before = count()
    with force_pallas(True):
        text = jaxpr()
    after = count()
    assert ("apex_qk_heads_fwd" in text) == kernels
    assert after["kernel"] - before["kernel"] == int(kernels)
    assert after["composed"] - before["composed"] == int(not kernels)
    # the same parameters either way, where the norms would put them
    assert set(params.get("params", {})) == (
        {"q_norm", "k_norm"} if "norm_eps" in kw else set())
    # and with nothing forced, off the TPU: composed whatever the shape
    assert "apex_qk_heads" not in jaxpr()
    assert count()["composed"] == after["composed"] + 1


def test_shapes_the_kernels_refuse_raise_when_asked_for_by_name():
    x = jnp.zeros((1, 64, 8 * 128), jnp.bfloat16)
    with pytest.raises(ValueError, match="whole groups"):
        qk_heads.qkv_heads(x, 3, 2)
    with pytest.raises(ValueError, match="is not"):
        qk_heads.qkv_heads(x, 8, 2)             # wants 12 heads' columns
    with pytest.raises(ValueError, match="eps"):
        qk_heads.qkv_heads(x, 4, 2, eps=1e-5)
    assert not qk_heads.supported(60, 4, 2, 128)
    assert not qk_heads.supported(64, 4, 2, 128, rot=64)
    assert qk_heads.supported(64, 4, 2, 128, rot=128)


@pytest.mark.parametrize("family", ["afmoe", "smallthinker"])
def test_a_family_at_heads_of_128_is_its_reference_through_the_kernels(family):
    """The two families' blocks at a head size the kernels take, under
    ``full_block`` (the pair's forward runs in the forward and again in the
    recomputed one, its backward reads the recomputed projection): logits,
    loss and every leaf's gradient against the benchmark's plain reference,
    as ``tests/test_afmoe.py`` and ``tests/test_smallthinker.py`` hold them
    at heads of 64 on the composed path."""
    import importlib

    tiny = importlib.import_module("test_" + family)
    fam, ref = tiny.fam, tiny.ref
    cfg = tiny.tiny_cfg(remat_policy="full_block")
    cfg["head_dim"] = 128
    rcfg, w = tiny.seeded(cfg)
    ids, labels = tiny.batch()
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)

    def program_loss(p):
        return model.apply({"params": p}, ids, labels=labels,
                           deterministic=False)[1]

    def reference_loss(w):
        return (jnp.sum(ref.loss_rows(w, (ids, labels), rcfg))
                / jnp.sum(labels >= 0))

    count = obs.default_registry().counter("ops.qk_heads.kernel")
    before = count.value
    with force_pallas(True):
        logits = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
        loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    # every layer's call site, in each of the two programs
    assert count.value - before == 2 * cfg["num_hidden_layers"]
    want_logits = jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)
    assert rel_gap(logits, want_logits) < 1e-5
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(w)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = fam.from_program(grads, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 2e-4, name
