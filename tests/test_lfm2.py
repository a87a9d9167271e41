"""The ``lfm2_moe`` decoder (``models/lfm2.py``: a gated short convolution in
place of attention in most layers, grouped-query attention with normed and
rotated queries and keys in the others, a dense or a routed feed-forward by
layer index, bias-steered sigmoid experts with none shared, the head tied to
the embedding) against the benchmark's plain reference
(``benchmark/reference/lfm2_moe.py``) at tiny widths."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from apex_tpu.ops._common import force_pallas  # noqa: E402
from apex_tpu.parallel.moe import ExpertShardMLP  # noqa: E402
from benchmark.families import lfm2_moe as fam  # noqa: E402
from benchmark.reference import lfm2_moe as ref  # noqa: E402

KINDS = ["conv", "full_attention", "conv"]


def tiny_cfg(held=(4, 8), routed_over=16, k=4, kinds=KINDS, dense=1,
             **assumed):
    """A dense convolution layer, an expert attention layer with two query
    heads of 64 on one key/value head (the family reads a head's size as the
    hidden size over the query heads), an expert convolution layer; a strict
    subset of the experts held."""
    return {
        "hidden_size": 128, "num_hidden_layers": len(kinds),
        "layer_types": list(kinds), "num_dense_layers": dense,
        "conv_L_cache": 3, "conv_bias": False, "num_attention_heads": 2,
        "num_key_value_heads": 1, "intermediate_size": 256,
        "moe_intermediate_size": 128, "num_experts": held[1] - held[0],
        "num_experts_per_tok": k, "norm_topk_prob": True, "norm_eps": 1e-5,
        "routed_scaling_factor": 1, "use_expert_bias": True,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "vocab_size": 250,
        "published": {"num_experts": routed_over},
        "assumed": {"padded_vocab_size": 256, "experts_held": list(held),
                    "tie_word_embeddings": True, "route_norm_eps": 1e-20,
                    "initializer_range": 0.02, **assumed},
    }


def seeded(cfg, seed=0):
    """Reference weights with the norm scales moved off their initial 1, the
    selection bias off 0 and the routers, queries, keys and the
    convolution's taps and projection widened (at N(0, 0.02) and hidden 128
    the scores, the router's logits and the gates hardly leave 0: a missing
    rotation, a tap in the wrong order or a bias that entered the weights
    would hide in the flatness), so that each is seen to matter."""
    rcfg = fam.reference_config(cfg)
    w = ref.init_params(jax.random.PRNGKey(seed), rcfg)
    for i, name in enumerate(sorted(w)):
        noise = lambda: jax.random.normal(jax.random.PRNGKey(1000 + i),
                                          w[name].shape)
        if "norm" in name:
            w[name] = w[name] + 0.1 * noise()
        if name.endswith("moe.expert_bias"):
            w[name] = 0.05 * noise()
        if name.endswith(("attn.w_q", "attn.w_k", "conv.w_in")):
            w[name] = 8.0 * w[name]
        if name.endswith("conv.taps"):
            w[name] = 25.0 * w[name]
        if name.endswith("moe.router"):
            w[name] = 40.0 * w[name]
    return rcfg, w


def batch(rows=2, seq=128, vocab=250):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=-1)
    return ids, labels


def rel_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))


def reference_loss(w, ids, labels, rcfg):
    return jnp.sum(ref.loss_rows(w, (ids, labels), rcfg)) / jnp.sum(labels >= 0)


def reference_loss_and_grads(w, ids, labels, rcfg):
    """One program, not an eager walk of the reference's primitives."""
    return jax.jit(jax.value_and_grad(
        lambda w: reference_loss(w, ids, labels, rcfg)))(w)


@pytest.mark.parametrize("kernels", [False, True], ids=["off_tpu", "pallas"])
@pytest.mark.parametrize("remat", ["none", "full_block"])
def test_float32_matches_the_reference_leaf_by_leaf(kernels, remat):
    """Logits, loss and every leaf's gradient; with the Pallas kernels
    (interpret mode: the gated convolution's two, flash attention at a group
    of two, the grouped products, the row movement) and with their off-TPU
    paths; with per-block recomputation.  float32 on both sides, two
    derivations of the same sums: 1e-5 on the loss, 1e-4 on the logits and
    1e-3 on a leaf's gradient (against its largest element) are summation
    order, as in the other sparse families' tests."""
    from apex_tpu import obs

    cfg = tiny_cfg(remat_policy=remat)
    rcfg, w = seeded(cfg)
    ids, labels = batch()
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)

    def program_loss(p):
        return model.apply({"params": p}, ids, labels=labels,
                           deterministic=False)[1]

    with force_pallas(kernels):
        logits = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
        loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    reg = obs.default_registry()
    assert reg.get("moe.dispatch.kernels").value == kernels
    assert reg.get("gated_conv.kernel").value == kernels
    assert rel_gap(logits, jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)) < 1e-4
    want_loss, want = reference_loss_and_grads(w, ids, labels, rcfg)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = fam.from_program(grads, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 1e-3, name
    # every leaf reached but the selection bias, which steers a choice
    assert all(np.asarray(g).any() != name.endswith("expert_bias")
               for name, g in got.items())


def test_o2_stays_close_to_the_reference():
    """AMP O2 (bfloat16 compute, float32 masters) through AmpOptimizer's
    cast, as the benchmark's runner calls the model.  bfloat16 keeps 8 bits:
    a loss within 5e-3 and a gradient norm within 5% of the float32
    reference's are its rounding over the layers, the bounds the other
    sparse families' O2 tests hold."""
    import apex_tpu.amp as amp

    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    ids, labels = batch()
    amp_ = amp.initialize("O2")
    model = fam.program_model(fam.program_config(cfg, amp_.policy.compute_dtype))
    masters = fam.to_program(w, cfg)

    def program_loss(p):
        return model.apply({"params": amp_.cast_model(p)}, ids, labels=labels,
                           deterministic=False)[1]

    loss, grads = jax.jit(jax.value_and_grad(program_loss))(masters)
    want_loss, want = reference_loss_and_grads(w, ids, labels, rcfg)
    assert abs(float(loss) - float(want_loss)) < 5e-3 * float(want_loss)
    got = fam.from_program(grads, cfg)
    norm = lambda t: float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                                        for x in t.values())))
    assert abs(norm(got) - norm(want)) < 0.05 * norm(want)
    assert all(g.dtype == jnp.float32 for g in got.values())


def _layer(cfg, held):
    return ExpertShardMLP(
        num_experts=cfg["published"]["num_experts"], experts_held=held,
        d_ff=cfg["moe_intermediate_size"], k=cfg["num_experts_per_tok"],
        route_norm=True, route_scale=1.0, score_func="sigmoid", tile_rows=8)


def _layer_weights(w, layer=1):
    h = f"layers.{layer}."
    return {k[len(h):]: v for k, v in w.items() if k.startswith(h)}


def test_the_shares_add_up():
    """The parts all eight shares of a 16-expert layer give (two experts
    each; there is no shared expert to count once) are the uncut layer of the
    reference: an 8-way deployment's chips together compute the model.  One
    share alone is not it, and the selection bias moves the selection."""
    whole = tiny_cfg(held=(0, 16))
    rcfg, w = seeded(whole)
    lw = _layer_weights(w)
    z = jax.random.normal(jax.random.PRNGKey(3), (96, 128))
    uncut = ref.routed(z, lw, rcfg)

    tree = fam.to_program(w, whole)["layer_1"]["moe"]
    assert set(tree) == {"router", "expert_bias", "wi", "wo"}   # none shared
    total = jnp.zeros_like(z)
    for share in range(8):
        lo, hi = 2 * share, 2 * share + 2
        params = {"router": tree["router"], "expert_bias": tree["expert_bias"],
                  "wi": tree["wi"][lo:hi], "wo": tree["wo"][lo:hi]}
        part = _layer(whole, (lo, hi)).apply({"params": params}, z)
        assert float(jnp.max(jnp.abs(part))) > 0
        assert rel_gap(part, uncut) > 1e-2
        total = total + part
    assert rel_gap(total, uncut) < 1e-5
    unsteered = ref.routed(z, {**lw, "moe.expert_bias":
                               jnp.zeros_like(lw["moe.expert_bias"])}, rcfg)
    assert rel_gap(unsteered, uncut) > 1e-3


def test_tied_heads_gradient_is_the_lookups_plus_the_products():
    """The embedding is read twice, by the lookup and — transposed — by the
    head, and its gradient is the sum of the two: the head's part alone (the
    lookup's table held apart from the head's) plus the lookup's part alone
    (the other way round) is what the tied model gets, leaf for leaf in
    program and reference."""
    cfg = tiny_cfg(kinds=["conv"], dense=0)     # one expert conv layer
    rcfg, w = seeded(cfg)
    ids, labels = batch(rows=1, seq=64)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)
    loss = lambda p: model.apply({"params": p}, ids, labels=labels)[1]
    tied = jax.jit(jax.grad(loss))(params)["embed"]["embedding"]

    def untied(lookup, head):
        x = ref.hidden({**w, "embed": lookup}, ids, rcfg)
        logits = ref.head({**w, "embed": head}, x, rcfg)
        return jnp.sum(ref.C.row_loss_sums(logits, labels)) / jnp.sum(labels >= 0)

    d_lookup, d_head = jax.jit(jax.grad(untied, argnums=(0, 1)))(
        w["embed"], w["embed"])
    assert float(jnp.max(jnp.abs(d_lookup))) > 0 and float(jnp.max(jnp.abs(d_head))) > 0
    assert rel_gap(d_lookup, d_head) > 0.5          # two different gradients
    assert rel_gap(tied, d_lookup + d_head) < 1e-3
    # rows no token looked up move by the head alone
    unseen = np.setdiff1d(np.arange(256), np.asarray(ids))
    assert len(unseen) and rel_gap(tied[unseen], d_head[unseen]) < 1e-3
    assert "head" not in params and "head" not in w


def test_a_conv_layers_output_does_not_move_when_later_tokens_do():
    """CAUSAL, and only ``K`` tokens deep: changed tokens from position t on
    leave a conv-only model's logits before t bit-equal, move the logits at
    t, and — the other way — position t's logits read exactly the ``L (K -
    1) + 1`` tokens up to t (two conv layers, three taps: five)."""
    cfg = tiny_cfg(kinds=["conv", "conv"], dense=1)
    rcfg, w = seeded(cfg)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)
    ids, _ = batch(rows=1)
    t = 70
    later = ids.at[:, t:].set((ids[:, t:] + 1) % 250)
    for kernels in (False, True):
        with force_pallas(kernels):
            base, moved = (model.apply({"params": params}, x) for x in (ids, later))
        np.testing.assert_array_equal(base[:, :t], moved[:, :t])
        assert rel_gap(moved[:, t], base[:, t]) > 1e-3
        # tokens further back than the two layers' reach do not matter
        far = ids.at[:, :t - 4].set((ids[:, :t - 4] + 1) % 250)
        with force_pallas(kernels):
            near = model.apply({"params": params}, far)
        np.testing.assert_allclose(near[:, t], base[:, t], rtol=1e-5, atol=1e-6)
        assert rel_gap(near[:, t - 5], base[:, t - 5]) > 1e-3
    want = ref.logits(w, later, rcfg)
    assert rel_gap(moved, want) < 1e-4


def test_weights_round_trip_through_the_programs_layouts():
    """``to_program`` fuses q, k and v, gate and up, and stacks the experts;
    ``from_program`` gives every reference leaf back; no head leaf on either
    side."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    tree = fam.to_program(w, cfg)
    back = fam.from_program(tree, cfg)
    assert sorted(back) == sorted(w)
    assert all((np.asarray(back[k]) == np.asarray(w[k])).all() for k in w)
    assert set(tree) == {"embed", "norm_f", "layer_0", "layer_1", "layer_2"}
    conv = tree["layer_0"]["conv"]
    assert set(conv) == {"in_proj", "taps", "out_proj"}
    assert conv["in_proj"]["kernel"].shape == (128, 384)
    assert conv["taps"].shape == (128, 3)
    np.testing.assert_array_equal(conv["taps"], w["layers.0.conv.taps"])
    assert set(tree["layer_0"]) == {"operator_norm", "pre_mlp_norm", "conv", "mlp"}
    qkv = np.asarray(tree["layer_1"]["qkv"]["kernel"])
    assert qkv.shape == (128, (2 + 1 + 1) * 64)
    np.testing.assert_array_equal(qkv[:, :128], w["layers.1.attn.w_q"])
    np.testing.assert_array_equal(qkv[:, 192:], w["layers.1.attn.w_v"])
    assert set(tree["layer_1"]) == {"operator_norm", "pre_mlp_norm", "qkv",
                                    "q_norm", "k_norm", "o_proj", "moe"}
    moe = tree["layer_2"]["moe"]
    assert moe["wi"].shape == (4, 128, 256) and moe["wo"].shape == (4, 128, 128)
    seen = fam.views(w)
    assert seen["layers.1.moe.experts.w_gate"].shape == (4, 128, 128)
    assert not any(".experts.4." in k for k in seen)


def test_model_is_called_as_gptlm_is():
    """``apply(ids)`` -> logits; with labels -> (logits, loss); blocks are
    ``layer_<i>``; the scopes the readers look for are in the jaxpr; the
    names ``benchmark/tools/routing_census.py`` captures by are there; what
    the family's program does not do is refused."""
    from apex_tpu.models import Lfm2Config, Lfm2LM

    cfg = Lfm2Config.tiny()
    assert cfg.layer_types == ("conv", "full_attention", "conv")
    assert cfg.num_heads // cfg.num_kv_heads == 4 and cfg.num_dense_layers == 1
    assert cfg.experts_held[1] - cfg.experts_held[0] < cfg.num_experts
    for name in ("num_experts", "num_experts_per_tok", "route_norm",
                 "route_scale", "experts_held"):
        assert hasattr(cfg, name), name
    model = Lfm2LM(cfg)
    ids, labels = batch(rows=1, vocab=cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"embed", "norm_f", "layer_0", "layer_1", "layer_2"}
    assert set(params["layer_0"]) == {"operator_norm", "pre_mlp_norm", "conv", "mlp"}
    assert set(params["layer_2"]) == {"operator_norm", "pre_mlp_norm", "conv", "moe"}
    assert set(params["layer_1"]["moe"]) == {"router", "expert_bias", "wi", "wo"}
    assert params["layer_1"]["qkv"]["kernel"].shape == (128, (8 + 2 + 2) * 64)
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (1, 128, cfg.vocab_size) and logits.dtype == jnp.float32
    _, loss = model.apply({"params": params}, ids, labels=labels,
                          deterministic=False)
    assert loss.shape == () and np.isfinite(float(loss))
    text = str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, ids, labels=labels)[1])(params).pretty_print(
            name_stack=True))
    for scope in ("embed", "conv_proj", "conv_mix", "conv_out", "attn_full",
                  "dense_ffn", "moe_router", "moe_dispatch", "moe_experts",
                  "lm_head", "lm_loss", "layer_2"):
        assert scope in text, scope
    assert "moe_shared" not in text
    _, state = model.apply({"params": params}, ids, capture_intermediates=(
        lambda m, _: m.name == "pre_mlp_norm"))
    assert set(state["intermediates"]) == {"layer_0", "layer_1", "layer_2"}
    with pytest.raises(ValueError, match="layer type"):
        Lfm2LM(Lfm2Config.tiny(layer_types=("conv", "sliding_attention"))).init(
            jax.random.PRNGKey(0), ids)
    for key, value, match in (
            ("layer_types", ["conv"] * 2, "num_hidden_layers"),
            ("layer_types", ["conv", "sliding_attention", "conv"], "only"),
            ("num_dense_layers", 4, "num_dense_layers"),
            ("conv_bias", True, "bias"),
            ("use_expert_bias", False, "selection bias")):
        with pytest.raises(ValueError, match=match):
            fam.program_config({**tiny_cfg(), key: value}, jnp.float32)
    for key, value, match in (("tie_word_embeddings", False, "tied"),
                              ("route_norm_eps", 1e-6, "eps")):
        with pytest.raises(ValueError, match=match):
            fam.program_config(tiny_cfg(**{key: value}), jnp.float32)
