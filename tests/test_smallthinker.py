"""The ``smallthinker`` decoder (``models/smallthinker.py``: a router that reads
the block's input ahead of attention, ReGLU experts with no shared one, a full
layer without positions before rotary window layers, several query heads a
key/value head) against the benchmark's plain reference
(``benchmark/reference/smallthinker.py``, which routes in the published order)
at tiny widths."""
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
from apex_tpu.parallel.moe import ExpertShardMLP, softmax_topk_routing  # noqa: E402
from benchmark.families import smallthinker as fam  # noqa: E402
from benchmark.reference import smallthinker as ref  # noqa: E402


def tiny_cfg(held=(4, 8), routed_over=16, k=4, layers=2, **assumed):
    """The full layer first, three query heads a key/value head, a window
    shorter than a row, a strict subset of the experts held."""
    layout = [0] + [1] * (layers - 1)
    return {
        "hidden_size": 128, "num_hidden_layers": layers,
        "rope_layout": layout, "sliding_window_layout": layout,
        "layer_types": ["full_attention"] + ["sliding_attention"] * (layers - 1),
        "num_dense_layers": 0, "num_attention_heads": 6,
        "num_key_value_heads": 2, "head_dim": 64, "sliding_window_size": 48,
        "sliding_window": 48, "rope_theta": 1500000, "rope_scaling": None,
        "moe_ffn_hidden_size": 128, "moe_num_primary_experts": held[1] - held[0],
        "moe_num_active_primary_experts": k,
        "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "vocab_size": 250,
        "published": {"moe_num_primary_experts": routed_over},
        "assumed": {"padded_vocab_size": 256, "experts_held": list(held),
                    "initializer_range": 0.02, **assumed},
    }


def seeded(cfg, seed=0):
    """Reference weights with the norm scales moved off their initial 1 and
    the routers, queries and keys widened (at N(0, 0.02) and hidden 128 the
    scores and the router's logits hardly leave 0: a wrong window, a missing
    rotation or a router fed the wrong stream would hide in the flatness), so
    that each is seen to matter."""
    rcfg = fam.reference_config(cfg)
    w = ref.init_params(jax.random.PRNGKey(seed), rcfg)
    for i, name in enumerate(sorted(w)):
        if "norm" in name:
            w[name] = w[name] + 0.1 * jax.random.normal(
                jax.random.PRNGKey(1000 + i), w[name].shape)
        if name.endswith(("attn.w_q", "attn.w_k")):
            w[name] = 8.0 * w[name]
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


@pytest.mark.parametrize("kernels", [False, True], ids=["off_tpu", "pallas"])
@pytest.mark.parametrize("remat", ["none", "full_block"])
def test_float32_matches_the_reference_leaf_by_leaf(kernels, remat):
    """Logits, loss and every leaf's gradient; with the Pallas kernels
    (interpret mode: flash attention at a group of three with a window, the
    grouped products, the row movement at one 128-lane sublane a record) and
    with their off-TPU paths; with per-block recomputation.  float32 on both
    sides, two derivations of the same sums: 1e-5 on the loss, 1e-4 on the
    logits and 1e-3 on a leaf's gradient (against its largest element) are
    summation order, as in the other sparse families' tests."""
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
        # (each side ONE compiled program: op by op these cost the suite
        # minutes)
        logits = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
        loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    assert obs.default_registry().get("moe.dispatch.kernels").value == kernels
    assert rel_gap(logits, jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)) < 1e-4
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: reference_loss(w, ids, labels, rcfg)))(w)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = fam.from_program(grads, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 1e-3, name
    assert all(np.asarray(g).any() for g in got.values())   # every leaf reached


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
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: reference_loss(w, ids, labels, rcfg)))(w)
    assert abs(float(loss) - float(want_loss)) < 5e-3 * float(want_loss)
    got = fam.from_program(grads, cfg)
    norm = lambda t: float(jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                                        for x in t.values())))
    assert abs(norm(got) - norm(want)) < 0.05 * norm(want)
    assert all(g.dtype == jnp.float32 for g in got.values())


def _layer(cfg, held):
    return ExpertShardMLP(
        num_experts=cfg["published"]["moe_num_primary_experts"],
        experts_held=held, d_ff=cfg["moe_ffn_hidden_size"],
        k=cfg["moe_num_active_primary_experts"], route_norm=True,
        score_func="softmax", unit_func="relu", tile_rows=8)


def _layer_weights(w, layer=1):
    h = f"layers.{layer}."
    return {k[len(h):]: v for k, v in w.items() if k.startswith(h)}


def test_the_shares_add_up():
    """The parts all eight shares of a 16-expert layer give (two experts
    each; there is no shared expert to count once) are the uncut layer of the
    reference: an 8-way deployment's chips together compute the model.  The
    router scores one stream and the experts take another, as in the block."""
    whole = tiny_cfg(held=(0, 16))
    rcfg, w = seeded(whole)
    lw = _layer_weights(w)
    x_in, u = (jax.random.normal(jax.random.PRNGKey(s), (96, 128))
               for s in (3, 4))
    uncut = ref.routed(u, ref.C.mm(x_in, lw["moe.router"]), lw, rcfg)

    tree = fam.to_program(w, whole)["layer_1"]["moe"]
    total = jnp.zeros_like(u)
    for share in range(8):
        lo, hi = 2 * share, 2 * share + 2
        params = {"router": tree["router"], "wi": tree["wi"][lo:hi],
                  "wo": tree["wo"][lo:hi]}
        part = _layer(whole, (lo, hi)).apply({"params": params}, u,
                                             router_input=x_in)
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    assert rel_gap(total, uncut) < 1e-5
    # scored from the experts' own input the layer is another function
    same = sum(_layer(whole, (2 * s, 2 * s + 2)).apply(
        {"params": {"router": tree["router"], "wi": tree["wi"][2 * s:2 * s + 2],
                    "wo": tree["wo"][2 * s:2 * s + 2]}}, u) for s in range(8))
    assert rel_gap(same, uncut) > 1e-2


def _selection(model, params, ids, layer):
    """The experts layer ``layer`` picks for every token."""
    _, state = model.apply(
        {"params": params}, ids, capture_intermediates=lambda m, _: (
            m.name in (f"layer_{layer}", f"layer_{layer - 1}")))
    found = state["intermediates"]
    # the block's input: the block before's output (layer >= 1)
    x = found[f"layer_{layer - 1}"]["__call__"][0]
    logits = jnp.matmul(x.reshape(-1, x.shape[-1]).astype(jnp.float32),
                        params[f"layer_{layer}"]["moe"]["router"],
                        precision=jax.lax.Precision.HIGHEST)
    return np.asarray(jax.lax.top_k(logits, 4)[1])


def test_routing_of_a_block_does_not_move_when_its_attention_weights_do():
    """THE EARLY ROUTER: block 1's selection is a function of block 1's
    INPUT, so new attention weights in block 1 change its output (and block
    2's routing) and leave its own routing where it was.  Both sides: the
    reference's block gives the same logits, and a router fed the
    post-attention stream (the other sparse models' place) would have moved."""
    cfg = tiny_cfg(layers=3)
    rcfg, w = seeded(cfg)
    ids, _ = batch(rows=1)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    moved = dict(w)
    for name in ("attn.w_q", "attn.w_k", "attn.w_v", "attn.w_o"):
        moved["layers.1." + name] = 3.0 * w["layers.1." + name][::-1]
    p0, p1 = fam.to_program(w, cfg), fam.to_program(moved, cfg)
    out0, out1 = (model.apply({"params": p}, ids) for p in (p0, p1))
    assert rel_gap(out1, out0) > 1e-2                   # the block did change
    np.testing.assert_array_equal(_selection(model, p0, ids, 1),
                                  _selection(model, p1, ids, 1))
    assert (_selection(model, p0, ids, 2) != _selection(model, p1, ids, 2)).any()

    # the layer's jaxpr: nothing under moe_router depends on the flash call
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 128, 128))
    layer = type(model)(model.cfg).bind({"params": p0}).layers[1]
    jaxpr = jax.make_jaxpr(lambda t: layer(t))(x)
    text = str(jaxpr.pretty_print(name_stack=True))
    assert text.index("moe_router") > 0
    tainted, router_reads_attention = set(), False
    for eqn in jaxpr.jaxpr.eqns:
        reads = any(str(v) in tainted for v in eqn.invars)
        under = str(eqn.source_info.name_stack)
        if "attn_" in under or reads:
            tainted.update(str(v) for v in eqn.outvars)
        if "moe_router" in under and reads:
            router_reads_attention = True
    assert tainted and not router_reads_attention

    # the reference's block routes on the same stream
    lw = _layer_weights(w)
    x2 = x.reshape(-1, 128)
    sel_ref, _ = ref.routing(ref.C.mm(x2, lw["moe.router"]), rcfg)
    sel_prog, _ = softmax_topk_routing(
        jnp.matmul(x2, lw["moe.router"], precision="highest"), 4, True)
    np.testing.assert_array_equal(np.asarray(sel_ref), np.asarray(sel_prog))


@pytest.mark.parametrize("k,experts", [(6, 64), (4, 16), (1, 8)])
def test_top_k_then_softmax_is_the_renormalised_softmax_over_all(k, experts):
    """The published order (pick the k largest logits, softmax over the
    picked) and the program's (softmax over all, pick the k largest,
    renormalise) select the same experts and give the same weights:
    ``exp(l_i) / sum_picked exp(l_j)``."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(k), (512, experts))
    cfg = {"moe_num_active_primary_experts": k,
           "moe_primary_router_apply_softmax": True, "norm_topk_prob": True}
    sel_ref, w_ref = ref.routing(logits, cfg)
    sel, w = softmax_topk_routing(logits, k, True)
    np.testing.assert_array_equal(np.asarray(sel), np.asarray(sel_ref))
    np.testing.assert_allclose(w, w_ref, rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(jnp.sum(w, -1), 1.0, rtol=1e-6)


@pytest.mark.parametrize("embed_std", [None, 1.0])
def test_seeded_weights_take_the_embeddings_own_scale(embed_std):
    """``assumed.embedding_initializer_range`` scales the embedding's rows
    and nothing else; without the key every matrix has ``initializer_range``
    (the router reads the un-normed stream, so what the embedding's scale is
    beside the branches' decides how seeded tokens route)."""
    extra = {} if embed_std is None else {"embedding_initializer_range": embed_std}
    rcfg = fam.reference_config(tiny_cfg(**extra))
    w = ref.init_params(jax.random.PRNGKey(3), rcfg)
    plain = ref.init_params(jax.random.PRNGKey(3),
                            fam.reference_config(tiny_cfg()))
    std = lambda a: float(jnp.std(a))
    assert std(w["embed"]) == pytest.approx(embed_std or 0.02, rel=0.05)
    for name in w:
        if name != "embed":
            np.testing.assert_array_equal(w[name], plain[name])
    np.testing.assert_allclose(w["embed"], plain["embed"] * (embed_std or 0.02) / 0.02,
                               rtol=1e-6)


def test_weights_round_trip_through_the_programs_layouts():
    """``to_program`` fuses q, k and v, gate and up, and stacks the experts;
    ``from_program`` gives every reference leaf back."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    tree = fam.to_program(w, cfg)
    back = fam.from_program(tree, cfg)
    assert sorted(back) == sorted(w)
    assert all((np.asarray(back[k]) == np.asarray(w[k])).all() for k in w)
    qkv = np.asarray(tree["layer_1"]["qkv"]["kernel"])
    assert qkv.shape == (128, (6 + 2 + 2) * 64)
    np.testing.assert_array_equal(qkv[:, :384], w["layers.1.attn.w_q"])
    np.testing.assert_array_equal(qkv[:, 512:], w["layers.1.attn.w_v"])
    moe = tree["layer_0"]["moe"]
    assert set(moe) == {"router", "wi", "wo"}           # no bias, no shared
    assert moe["wi"].shape == (4, 128, 256) and moe["wo"].shape == (4, 128, 128)
    seen = fam.views(w)
    assert seen["layers.1.moe.experts.w_gate"].shape == (4, 128, 128)
    assert not any(".experts.4." in k for k in seen)


def test_model_is_called_as_gptlm_is():
    """``apply(ids)`` -> logits; with labels -> (logits, loss); blocks are
    ``layer_<i>``; the scopes the readers look for are in the jaxpr; what
    the family's program does not do is refused."""
    from apex_tpu.models import SmallThinkerConfig, SmallThinkerLM

    cfg = SmallThinkerConfig.tiny()
    assert cfg.sliding_window_layout[0] == 0 and cfg.rope_layout[0] == 0
    assert cfg.num_heads // cfg.num_kv_heads == 3
    assert cfg.experts_held[1] - cfg.experts_held[0] < cfg.num_experts
    model = SmallThinkerLM(cfg)
    ids, labels = batch(rows=1, vocab=cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert {f"layer_{i}" for i in range(3)} <= set(params)
    layer = params["layer_1"]
    assert set(layer) == {"input_norm", "post_attn_norm", "qkv", "o_proj", "moe"}
    assert layer["qkv"]["kernel"].shape == (128, (6 + 2 + 2) * 64)
    assert layer["o_proj"]["kernel"].shape == (6 * 64, 128)
    assert set(layer["moe"]) == {"router", "wi", "wo"}
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (1, 128, cfg.vocab_size) and logits.dtype == jnp.float32
    _, loss = model.apply({"params": params}, ids, labels=labels,
                          deterministic=False)
    assert loss.shape == () and np.isfinite(float(loss))
    text = str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, ids, labels=labels)[1])(params).pretty_print(
            name_stack=True))
    for scope in ("embed", "attn_full", "attn_window", "moe_router",
                  "moe_dispatch", "moe_experts", "lm_head", "lm_loss",
                  "layer_2"):
        assert scope in text, scope
    assert "moe_shared" not in text
    with pytest.raises(ValueError, match="rope_layout"):
        SmallThinkerLM(SmallThinkerConfig.tiny(rope_layout=(0, 1))).init(
            jax.random.PRNGKey(0), ids)
    for key, value, match in (
            ("layer_types", ["sliding_attention"] * 2, "layer_types"),
            ("sliding_window", 4096, "sliding_window"),
            ("num_dense_layers", 1, "num_dense_layers"),
            ("moe_primary_router_apply_softmax", False, "softmax")):
        with pytest.raises(ValueError, match=match):
            fam.program_config({**tiny_cfg(), key: value}, jnp.float32)
