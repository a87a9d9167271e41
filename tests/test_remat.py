"""What the block-recomputing policies keep (``apex_tpu/remat.py``): the
residuals a kernel declares — the flash forward's output and log-sum-exp
— beside the block's input, so that the backward pass does not run the
attention forward a second time; an expert layer's routing plan, so that
it is made once a step; and a gated MLP's first product, ``gate_up``'s
output, so that the dearest product of a dense block is made once a step."""
import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest
from jax._src.ad_checkpoint import saved_residuals  # not exported by jax 0.9

from apex_tpu import obs, remat
from apex_tpu.analysis.precision import _sub_jaxprs
from apex_tpu.models import GPTConfig, GPTLM
from apex_tpu.models.afmoe import FULL, WINDOW, AfmoeConfig, AfmoeLM
from apex_tpu.ops import attention
from apex_tpu.ops._common import force_pallas

ROWS, SEQ = 2, 128


def tiny_lm(family, policy, layers):
    """(loss of the parameters, parameters, (batch*heads, key/value
    batch*heads, head size, hidden)) of a tiny model of ``layers`` blocks,
    float32, the kernels taken (interpret mode) where the caller forces
    them."""
    if family == "granite":
        from apex_tpu.models.granite_hybrid import (
            ATTENTION, MAMBA, GraniteHybridConfig, GraniteHybridLM)

        cfg = GraniteHybridConfig.tiny(
            compute_dtype=jnp.float32, remat_policy=policy,
            layer_types=(MAMBA, ATTENTION, MAMBA)[:layers])
        model = GraniteHybridLM(cfg)
        heads, kv_heads, head = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    elif family == "gpt":
        cfg = dataclasses.replace(
            GPTConfig.tiny(compute_dtype=jnp.float32, remat_policy=policy),
            num_layers=layers)
        model = GPTLM(cfg)
        heads = kv_heads = cfg.num_heads
        head = cfg.hidden_size // cfg.num_heads
    else:
        cfg = AfmoeConfig.tiny(
            compute_dtype=jnp.float32, remat_policy=policy,
            layer_types=(WINDOW, WINDOW, FULL)[:layers])
        model = AfmoeLM(cfg)
        heads, kv_heads, head = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    ids = jax.random.randint(jax.random.PRNGKey(1), (ROWS, SEQ), 0, 250)
    params = model.init(jax.random.PRNGKey(0), ids, labels=ids)

    def loss(p):
        return model.apply(p, ids, ids)[1]

    return loss, params, (ROWS * heads, ROWS * kv_heads, head,
                          cfg.hidden_size)


def kept(loss, params):
    """Shapes and dtypes of what the backward pass is handed, the
    parameters themselves left out."""
    return collections.Counter(
        (a.shape, str(a.dtype)) for a, why in saved_residuals(loss, params)
        if "from the argument" not in why)


def flash_forward_calls(fn, *args, kernel="apex_flash_fwd"):
    """``apex_flash_fwd`` kernels (or another ``kernel``) in the jaxpr of
    ``fn``, the jaxprs of ``remat``, ``pjit`` and the rest walked."""

    def count(jaxpr):
        n = 0
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                n += eqn.params["name"] == kernel
            else:
                n += sum(count(inner) for inner in _sub_jaxprs(eqn.params))
        return n

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


def products(fn, *args, shapes=None):
    """``dot_general`` equations in the jaxpr of ``fn`` — those whose result
    has one of ``shapes``, where given — every nested jaxpr walked."""

    def count(jaxpr):
        return sum(
            shapes is None or eqn.outvars[0].aval.shape in shapes
            if eqn.primitive.name == "dot_general" else
            sum(count(inner) for inner in _sub_jaxprs(eqn.params))
            for eqn in jaxpr.eqns)

    return count(jax.make_jaxpr(fn)(*args).jaxpr)


@pytest.mark.parametrize("family,layers", [("gpt", 2), ("afmoe", 3)])
def test_full_block_keeps_input_out_and_lse_a_layer(family, layers):
    """One block more keeps three arrays more: its input, the flash
    kernel's output and the (bh, sq) float32 lse — no second array of
    q's shape, no k3 / v3, no 128-lane lse buffer — and, where it holds
    an expert layer, the nine integer tables of its routing plan and its
    shared expert's ``gate_up`` output."""
    with force_pallas(True):
        more = kept(*tiny_lm(family, "full_block", layers)[:2])
        loss, params, (bh, bh_kv, d, hidden) = tiny_lm(
            family, "full_block", layers - 1)
        fewer = kept(loss, params)
    assert not fewer - more
    plan = collections.Counter()
    if family == "afmoe":       # and its expert layer's routing plan, int32
        from apex_tpu.ops import grouped_mm, moe_rows

        tokens, k, held = ROWS * SEQ, 4, 4
        rows = grouped_mm.rows_capacity(tokens * k, held)
        tiles = rows // grouped_mm.DEFAULT_TILE_ROWS
        blocks = tokens // moe_rows.combine_block(tokens, k, hidden)
        plan = collections.Counter({
            ((tokens, k), "int32"): 2,          # sel, slot_row
            ((rows,), "int32"): 2,              # row_slot, row_token
            ((tiles,), "int32"): 2,             # tile_group, tile_valid
            ((held,), "int32"): 1, ((1,), "int32"): 1,  # row_start, tiles_used
            ((blocks + 1, held), "int32"): 1,   # _block_starts
            ((tokens, 2 * 128), "float32"): 1,  # the shared expert's gate_up
        })
    assert more - fewer == plan + collections.Counter({
        ((ROWS, SEQ, hidden), "float32"): 1,
        ((bh, SEQ, d), "float32"): 1,
        ((bh, SEQ), "float32"): 1,
    })
    assert more[((bh, SEQ, d), "float32")] == layers
    assert more[((bh, SEQ), "float32")] == layers
    assert not more[((bh, SEQ, 128), "float32")]
    if bh_kv != bh:
        assert not more[((bh_kv, SEQ, d), "float32")]


@pytest.mark.parametrize("policy", ["none", "dots_saveable", "full_block"])
@pytest.mark.parametrize("family,layers", [("gpt", 2), ("afmoe", 3)])
def test_the_flash_forward_runs_once_a_layer(family, layers, policy):
    """The gradient's jaxpr holds one ``apex_flash_fwd`` a layer under
    every policy: the recomputed block finds ``out`` and ``lse`` kept."""
    with force_pallas(True):
        loss, params, _ = tiny_lm(family, policy, layers)
        assert flash_forward_calls(jax.grad(loss), params) == layers


def test_policy_without_the_names_runs_the_forward_twice(monkeypatch):
    """The witness that the test above can fail: a policy that keeps no
    name makes the forward kernel again in the backward pass."""
    monkeypatch.setattr(remat, "KEPT_RESIDUAL_NAMES", ())
    with force_pallas(True):
        loss, params, _ = tiny_lm("gpt", "full_block", 2)
        assert flash_forward_calls(jax.grad(loss), params) == 4


@pytest.mark.parametrize("keep,walks", [(True, 3), (False, 6)],
                         ids=["names_kept", "names_dropped"])
def test_the_delta_rules_chain_walks_forward_once_a_layer(monkeypatch, keep,
                                                          walks):
    """A gated-delta-net block under ``full_block`` finds the chain's
    output and chunk states kept (``apex_gdn_out``, ``apex_gdn_states``):
    one ``apex_gdn_fwd`` a linear-attention layer in the gradient, two
    where a policy keeps no name; one ``apex_gdn_bwd`` either way."""
    from apex_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextLM

    if not keep:
        monkeypatch.setattr(remat, "KEPT_RESIDUAL_NAMES", ())
    model = Qwen3NextLM(Qwen3NextConfig.tiny(
        compute_dtype=jnp.float32, remat_policy="full_block"))
    ids = jax.random.randint(jax.random.PRNGKey(1), (1, SEQ), 0, 250)
    with force_pallas(True):
        params = model.init(jax.random.PRNGKey(0), ids, labels=ids)
        grad = jax.grad(lambda p: model.apply(p, ids, ids)[1])
        assert flash_forward_calls(grad, params, kernel="apex_gdn_fwd") == walks
        assert flash_forward_calls(grad, params, kernel="apex_gdn_bwd") == 3
        assert flash_forward_calls(grad, params) == (1 if keep else 2)


def test_the_triangular_inverse_is_not_made_again(monkeypatch):
    """``apex_gdn_tri`` kept: the recomputed block finds each chunk's ``(I +
    A)^-1`` and leaves out its doubling steps — at chunks of 64, five steps
    of two products in each of the three linear-attention layers."""
    from apex_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextLM

    def made():
        model = Qwen3NextLM(Qwen3NextConfig.tiny(
            compute_dtype=jnp.float32, remat_policy="full_block"))
        ids = jax.random.randint(jax.random.PRNGKey(1), (1, SEQ), 0, 250)
        params = model.init(jax.random.PRNGKey(0), ids, labels=ids)
        return products(jax.grad(lambda p: model.apply(p, ids, ids)[1]),
                        params)

    kept = made()
    monkeypatch.setattr(remat, "KEPT_RESIDUAL_NAMES", tuple(
        n for n in remat.KEPT_RESIDUAL_NAMES if n != remat.GDN_TRI))
    assert made() - kept == 3 * 5 * 2


def test_names_lower_to_nothing_outside_a_checkpoint(monkeypatch):
    """``flash_attention``'s gradient with no ``jax.checkpoint`` around it
    lowers to the text it has with the names taken out of the forward
    rule: the cells that recompute nothing compile the program they had.
    (The private function's symbol bears a counter, which is no part of
    the program.)"""
    q = jax.random.normal(jax.random.PRNGKey(0), (2, 2, 256, 64))

    def lowered():
        attention._flash_jit.clear_cache()
        grad = jax.grad(lambda q: jnp.sum(attention.flash_attention(
            q, q, q, causal=True, use_pallas=True)))
        return re.sub(r"@_flash_jit_\d+", "@_flash_jit",
                      jax.jit(grad).lower(q).as_text())

    named = lowered()
    monkeypatch.setattr(attention, "checkpoint_name", lambda x, name: x)
    try:
        assert lowered() == named
    finally:
        attention._flash_jit.clear_cache()
    assert remat.FLASH_OUT not in named and remat.FLASH_LSE not in named


@pytest.mark.parametrize("policy", ["dots_saveable", "full_block"])
def test_gauge_counts_the_names_a_policy_keeps(policy):
    gauge = obs.default_registry().gauge("remat.kept_names")
    gauge.set(0)
    assert remat.checkpoint_policy("none") is None
    assert gauge.value == 0
    assert remat.checkpoint_policy(policy) is not None
    assert gauge.value == len(remat.KEPT_RESIDUAL_NAMES) == 12


# -- a gated MLP's first product: made once a step ---------------------------

def _gate_up_shapes(family):
    """The shape of ``gate_up``'s output in every ``SwiGLU`` of the tiny
    three-block model of ``family``: Granite's three dense MLPs; Trinity's
    dense leading layer and its two shared experts (which see tokens x d)."""
    if family == "granite":
        return [(ROWS, SEQ, 2 * 256)] * 3
    return [(ROWS, SEQ, 2 * 256)] + [(ROWS * SEQ, 2 * 128)] * 2


def _without_gate_up(monkeypatch):
    monkeypatch.setattr(remat, "KEPT_RESIDUAL_NAMES", tuple(
        n for n in remat.KEPT_RESIDUAL_NAMES if n != remat.MLP_GATE_UP))


@pytest.mark.parametrize("policy", ["none", "dots_saveable", "full_block"])
@pytest.mark.parametrize("family", ["granite", "afmoe"])
def test_gate_up_is_made_once_a_swiglu(family, policy):
    """The gradient's jaxpr holds ONE product of ``gate_up``'s output shape a
    ``SwiGLU`` under every policy (the two gradient products have the
    input's and the weight's shapes): a recomputed block finds the output
    kept (``apex_mlp_gate_up``), in a dense MLP and in a shared expert."""
    shapes = _gate_up_shapes(family)
    with force_pallas(False):
        loss, params, _ = tiny_lm(family, policy, 3)
        assert products(jax.grad(loss), params,
                        shapes=set(shapes)) == len(shapes)


@pytest.mark.parametrize("family", ["granite", "afmoe"])
def test_full_block_without_the_name_makes_gate_up_twice(monkeypatch, family):
    """The witness that the test above can fail: ``full_block`` without the
    name runs every ``gate_up`` product again in the backward pass;
    ``dots_saveable`` keeps a dot's output whatever it is called."""
    _without_gate_up(monkeypatch)
    shapes = _gate_up_shapes(family)
    with force_pallas(False):
        loss, params, _ = tiny_lm(family, "full_block", 3)
        assert products(jax.grad(loss), params,
                        shapes=set(shapes)) == 2 * len(shapes)
        loss, params, _ = tiny_lm(family, "dots_saveable", 3)
        assert products(jax.grad(loss), params,
                        shapes=set(shapes)) == len(shapes)


@pytest.mark.parametrize("family", ["granite", "afmoe"])
def test_full_block_keeps_one_gate_up_array_a_swiglu(monkeypatch, family):
    """What the name adds to ``full_block``'s residuals is exactly one
    ``(..., 2 d_ff)`` array a ``SwiGLU`` — not the gate's and the up's halves
    apart, not their product, nothing of ``down``'s."""
    with force_pallas(False):
        named = kept(*tiny_lm(family, "full_block", 3)[:2])
        _without_gate_up(monkeypatch)
        bare = kept(*tiny_lm(family, "full_block", 3)[:2])
    assert not bare - named
    assert named - bare == collections.Counter(
        (shape, "float32") for shape in _gate_up_shapes(family))


def test_gate_ups_name_lowers_to_nothing_outside_a_checkpoint(monkeypatch):
    """A ``SwiGLU`` with no ``jax.checkpoint`` around it lowers to a text that
    does not bear the name, and its value and gradients are the unnamed
    layer's to the bit: ``remat_policy`` ``none`` and every caller outside
    the model zoo compile what they did."""
    from apex_tpu.parallel import moe

    layer = moe.SwiGLU(256)
    x = jax.random.normal(jax.random.PRNGKey(45), (ROWS, SEQ, 128))
    params = layer.init(jax.random.PRNGKey(0), x)
    both = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply(p, x) ** 2), (0, 1)))
    text = both.lower(params, x).as_text()
    assert remat.MLP_GATE_UP not in text
    named = both(params, x)
    monkeypatch.setattr(moe, "checkpoint_name", lambda x, name: x)
    unnamed = jax.jit(jax.value_and_grad(
        lambda p, x: jnp.sum(layer.apply(p, x) ** 2), (0, 1)))
    assert unnamed.lower(params, x).as_text() == text
    for a, b in zip(jax.tree_util.tree_leaves(named),
                    jax.tree_util.tree_leaves(unnamed(params, x)),
                    strict=True):
        assert jnp.array_equal(a, b)
    assert all(jnp.any(g != 0) for g in jax.tree_util.tree_leaves(named[1]))


# -- an expert layer's routing plan: made once a step ------------------------

PLAN_MAKERS = ("top_k", "sort", "cumsum")


def _expert_layer(policy, score, kernels):
    from apex_tpu.parallel.moe import ExpertShardMLP

    return remat.remat_module(ExpertShardMLP, policy)(
        num_experts=16, experts_held=(4, 8), d_ff=128, k=4, score_func=score,
        tile_rows=16 if kernels else 8)


def _expert_loss(policy, score, early, kernels=False):
    """(loss of (parameters, x, router_input), its arguments) of one
    ``ExpertShardMLP`` under ``policy``; a selection bias that is not zero."""
    kx, kr, kp, kc, kb = jax.random.split(jax.random.PRNGKey(40), 5)
    x = jax.random.normal(kx, (64, 128))
    scored = 2.0 * jax.random.normal(kr, (64, 128)) if early else None
    cot = jax.random.normal(kc, (64, 128))
    with force_pallas(kernels):
        params = _expert_layer("none", score, kernels).init(kp, x)["params"]
    if score == "sigmoid":
        params = dict(params, expert_bias=0.3 * jax.random.normal(kb, (16,)))

    def loss(p, x, scored):
        with force_pallas(kernels):
            return jnp.sum(_expert_layer(policy, score, kernels).apply(
                {"params": p}, x, scored) * cot)

    return loss, (params, x, scored)


def _makings(loss, args):
    """How often each of the plan's dear primitives stands in the jaxpr of
    the loss's gradient, every nested jaxpr walked."""
    found = collections.Counter()

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in PLAN_MAKERS:
                found[eqn.primitive.name] += 1
            for inner in _sub_jaxprs(eqn.params):
                walk(inner)

    walk(jax.make_jaxpr(jax.grad(loss, (0, 1)))(*args).jaxpr)
    return found


PLAN_CASES = [
    pytest.param(score, early, policy, False,
                 id=f"{score}-{'router_input' if early else 'scores_x'}-{policy}")
    for score in ("sigmoid", "softmax") for early in (False, True)
    for policy in ("full_block", "dots_saveable")
] + [pytest.param("softmax", True, "full_block", True,
                  id="softmax-router_input-full_block-kernels"),
     pytest.param("sigmoid", False, "dots_saveable", True,
                  id="sigmoid-scores_x-dots_saveable-kernels")]


@pytest.mark.parametrize("score,early,policy,kernels", PLAN_CASES)
def test_the_routing_plan_is_made_once_a_layer(monkeypatch, score, early,
                                               policy, kernels):
    """A recomputed expert layer reads ``sel`` and ``_Routing``'s tables kept
    (``apex_moe_sel``, ``apex_moe_plan``): ONE ``top_k``, one ``argsort`` and
    one set of running counts in the gradient's jaxpr, as many as with no
    recomputation — and two of each where a policy keeps neither name.  The
    loss and every gradient leaf are the same to the bit all three ways."""
    once = _makings(*_expert_loss("none", score, early, kernels))
    assert once["top_k"] == once["sort"] == 1 and once["cumsum"] >= 3
    loss, args = _expert_loss(policy, score, early, kernels)
    both = jax.value_and_grad(loss, (0, 1, 2) if early else (0, 1))
    assert _makings(loss, args) == once
    kept = both(*args)

    monkeypatch.setattr(remat, "KEPT_RESIDUAL_NAMES", tuple(
        n for n in remat.KEPT_RESIDUAL_NAMES
        if n not in (remat.MOE_SEL, remat.MOE_PLAN)))
    loss, args = _expert_loss(policy, score, early, kernels)
    assert _makings(loss, args) == {m: 2 * once[m] for m in PLAN_MAKERS}
    dropped = jax.value_and_grad(loss, (0, 1, 2) if early else (0, 1))(*args)
    plain = jax.value_and_grad(
        _expert_loss("none", score, early, kernels)[0],
        (0, 1, 2) if early else (0, 1))(*args)
    for other in (dropped, plain):
        for a, b in zip(jax.tree_util.tree_leaves(kept),
                        jax.tree_util.tree_leaves(other), strict=True):
            assert a.dtype == b.dtype and jnp.array_equal(a, b)
    kept[1][0].pop("expert_bias", None)     # it steers the selection only
    assert all(jnp.any(g != 0) for g in jax.tree_util.tree_leaves(kept[1]))


def test_the_plans_names_lower_to_nothing_outside_a_checkpoint():
    """With no ``jax.checkpoint`` around it the layer's gradient lowers to a
    text that bears neither name: ``remat_policy`` ``none`` and every caller
    outside the model zoo compile what they would without them."""
    loss, args = _expert_loss("none", "sigmoid", False)
    text = jax.jit(jax.grad(loss)).lower(*args).as_text()
    assert "top_k" in text
    assert remat.MOE_SEL not in text and remat.MOE_PLAN not in text
