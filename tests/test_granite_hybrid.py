"""The ``granitemoehybrid`` decoder (``models/granite_hybrid.py``: a Mamba-2
state-space layer in place of attention in most layers, grouped-query
attention without positions at a published scale in the others, a dense
SwiGLU in every layer, four multipliers, the head tied to the embedding)
against the benchmark's plain reference
(``benchmark/reference/granitemoehybrid.py``: the token recurrence) at tiny
widths."""
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
from benchmark.families import granitemoehybrid as fam  # noqa: E402
from benchmark.reference import granitemoehybrid as ref  # noqa: E402

KINDS = ["mamba", "attention", "mamba"]


def tiny_cfg(kinds=KINDS, vocab=256, published=None, **over):
    """Two mamba layers of four heads of 64 channels (two lane tiles side by
    side), a state of 32, chunks of 32 — four chunks a 128-token row —, around
    an attention layer with two query heads of 64 on one key/value head (the
    family reads a head's size as the hidden size over the query heads)."""
    assumed = {"padded_vocab_size": vocab, "tie_word_embeddings": True,
               "initializer_range": 0.02}
    assumed.update(over.pop("assumed", {}))
    cfg = {
        "hidden_size": 128, "num_hidden_layers": len(kinds),
        "layer_types": list(kinds), "attention_bias": False,
        "attention_multiplier": 0.015625, "embedding_multiplier": 12,
        "hidden_act": "silu", "intermediate_size": 256,
        "shared_intermediate_size": 256, "logits_scaling": 8,
        "mamba_chunk_size": 32, "mamba_conv_bias": True, "mamba_d_conv": 4,
        "mamba_d_head": 64, "mamba_d_state": 32, "mamba_expand": 2,
        "mamba_n_groups": 1, "mamba_n_heads": 4, "mamba_proj_bias": False,
        "normalization_function": "rmsnorm", "num_attention_heads": 2,
        "num_key_value_heads": 1, "num_experts_per_tok": 0,
        "num_local_experts": 0, "position_embedding_type": "nope",
        "residual_multiplier": 0.22, "rms_norm_eps": 1e-5,
        "tie_word_embeddings": True, "vocab_size": vocab,
        "published": {"layer_types": list(published or kinds) + ["mamba"]},
        "assumed": assumed,
    }
    cfg.update(over)
    return cfg


def seeded(cfg, seed=0):
    """Reference weights with the norm scales, the convolution's bias and D
    moved off their initial values and the projections, queries, keys and
    taps widened (at N(0, 0.02) and hidden 128 the scores, the gates and the
    convolution hardly leave 0: a missing multiplier, a tap in the wrong order
    or a bias left out would hide in the flatness), so that each is seen to
    matter."""
    rcfg = fam.reference_config(cfg)
    w = ref.init_params(jax.random.PRNGKey(seed), rcfg)
    for i, name in enumerate(sorted(w)):
        noise = lambda: jax.random.normal(jax.random.PRNGKey(1000 + i),
                                          w[name].shape)
        if "norm" in name or name.endswith("mamba.D"):
            w[name] = w[name] + 0.1 * noise()
        if name.endswith("mamba.conv_b"):
            w[name] = 0.3 * noise()
        if name.endswith(("attn.w_q", "attn.w_k")):
            w[name] = 10.0 * w[name]        # the scores' scale is 1/64
        if name.endswith(("attn.w_v", "attn.w_o")):
            w[name] = 8.0 * w[name]
        if name.endswith("mamba.w_in"):
            w[name] = 8.0 * w[name]
        if name.endswith("mamba.conv_w"):
            w[name] = 15.0 * w[name]
    return rcfg, w


def batch(rows=2, seq=128, vocab=256):
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
    (interpret mode: the scan's two over four chunks a row, flash attention
    at a group of two and a scale of 1/64) and with their off-TPU paths; with
    per-block recomputation.  The reference walks the TOKEN RECURRENCE, the
    program the chunked form: float32 on both sides, two derivations of the
    same sums — 1e-5 on the loss, 1e-4 on the logits and 1e-3 on a leaf's
    gradient (against its largest element) are summation order, as in the
    other families' tests."""
    from apex_tpu import obs

    cfg = tiny_cfg(assumed={"remat_policy": remat})
    rcfg, w = seeded(cfg)
    ids, labels = batch()
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    assert model.cfg.remat_policy == remat
    params = fam.to_program(w, cfg)

    def program_loss(p):    # one program: the logits beside the loss
        return model.apply({"params": p}, ids, labels=labels,
                           deterministic=False)[::-1]

    with force_pallas(kernels), jax.default_matmul_precision("highest"):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            program_loss, has_aux=True))(params)
    assert obs.default_registry().get("ssd.kernel").value == kernels
    assert rel_gap(logits, jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)) < 1e-4
    want_loss, want = reference_loss_and_grads(w, ids, labels, rcfg)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = fam.from_program(grads, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 1e-3, name
    # every leaf is reached: no gradient is identically zero
    assert all(np.asarray(g).any() for g in got.values())
    assert fam.ZERO_GRADIENT_SUFFIX is None


def test_o2_stays_close_to_the_reference():
    """AMP O2 (bfloat16 compute, float32 masters) through AmpOptimizer's
    cast, as the benchmark's runner calls the model.  bfloat16 keeps 8 bits:
    a loss within 5e-3 and a gradient norm within 5% of the float32
    reference's are its rounding over the layers, the bounds the other
    families' O2 tests hold."""
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


@pytest.mark.parametrize("name", ["embedding_multiplier", "attention_multiplier",
                                  "residual_multiplier", "logits_scaling"])
def test_each_multiplier_has_teeth(name):
    """The program under a configuration with one multiplier at 1 disagrees
    with the reference under the published one — by far more than any
    rounding — and agrees with the reference under the same change: each of
    the four is read, and read where the equations put it."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    ids, _ = batch(rows=1)
    params = fam.to_program(w, cfg)
    changed = {**cfg, name: 1}
    model = fam.program_model(fam.program_config(changed, jnp.float32))
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    published = jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)
    same = jax.jit(lambda w: ref.logits(w, ids, fam.reference_config(changed)))(w)
    assert rel_gap(got, published) > 5e-2
    assert rel_gap(got, same) < 1e-4


def test_attention_is_at_scale_one_sixty_fourth_and_has_no_positions():
    """The attention layer alone: the scores are multiplied by 1/64, not by
    1/8 = 64 ** -0.5 (the default the other families run), and nothing turns
    with position — a token's logits depend on WHICH tokens stand before it,
    not on where: swapping two earlier tokens of a one-layer attention-only
    model leaves a later position's logits as they were (a rotation would
    move them), and moves those of the positions between the two."""
    cfg = tiny_cfg(kinds=["attention"])
    rcfg, w = seeded(cfg)
    ids, _ = batch(rows=1)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)
    text = str(jax.make_jaxpr(lambda p: model.apply({"params": p}, ids))(params))
    assert "cos" not in text and "sin" not in text
    for kernels in (False, True):
        with force_pallas(kernels), jax.default_matmul_precision("highest"):
            base = model.apply({"params": params}, ids)
            swapped = model.apply({"params": params},
                                  ids.at[0, 3].set(ids[0, 9]).at[0, 9].set(ids[0, 3]))
        np.testing.assert_allclose(swapped[:, 20:], base[:, 20:],
                                   rtol=1e-4, atol=1e-5)
        assert rel_gap(swapped[:, 4:9], base[:, 4:9]) > 1e-3
        assert rel_gap(base, ref.logits(w, ids, rcfg)) < 1e-4
    eighth = ref.logits(w, ids, {**rcfg, "attention_multiplier": 64 ** -0.5})
    assert rel_gap(base, eighth) > 1e-2


def test_tied_heads_gradient_is_the_lookups_plus_the_products():
    """The embedding is read twice, by the lookup and — transposed — by the
    head, and its gradient is the sum of the two: the head's part alone (the
    lookup's table held apart from the head's) plus the lookup's part alone
    (the other way round) is what the tied model gets, leaf for leaf in
    program and reference."""
    cfg = tiny_cfg(kinds=["mamba"])
    rcfg, w = seeded(cfg)
    ids, labels = batch(rows=1, seq=64)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)
    loss = lambda p: model.apply({"params": p}, ids, labels=labels)[1]
    with jax.default_matmul_precision("highest"):
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


def test_a_sliced_vocabularys_logits_are_the_full_models_columns():
    """An eighth of the vocabulary is a smaller vocabulary: the model over
    the first 64 rows of a 512-row embedding gives, on ids below 64, the full
    model's logits at columns 0..63 — the slice is of the embedding's rows and
    so of the tied head's columns, and nothing else knows the vocabulary."""
    full_cfg = tiny_cfg(vocab=512)
    rcfg, w = seeded(full_cfg)
    ids, _ = batch(rows=1, vocab=64)
    sliced_cfg = tiny_cfg(vocab=64)
    sliced_w = {**w, "embed": w["embed"][:64]}
    full = fam.program_model(fam.program_config(full_cfg, jnp.float32))
    sliced = fam.program_model(fam.program_config(sliced_cfg, jnp.float32))
    with jax.default_matmul_precision("highest"):
        want = full.apply({"params": fam.to_program(w, full_cfg)}, ids)
        got = sliced.apply({"params": fam.to_program(sliced_w, sliced_cfg)}, ids)
    assert got.shape == (1, 128, 64) and want.shape == (1, 128, 512)
    np.testing.assert_allclose(got, want[..., :64], rtol=1e-5, atol=1e-6)
    assert rel_gap(got, ref.logits(sliced_w, ids,
                                   fam.reference_config(sliced_cfg))) < 1e-4


def test_a_mamba_layers_output_does_not_move_when_later_tokens_do():
    """CAUSAL through the convolution, the scan's chunks and the carried
    state: changed tokens from position t on leave a mamba-only model's logits
    before t bit-equal and move the logits at t; a changed token far before t
    still reaches t (through the state, over three chunk edges)."""
    cfg = tiny_cfg(kinds=["mamba", "mamba"])
    rcfg, w = seeded(cfg)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)
    ids, _ = batch(rows=1)
    t = 101
    later = ids.at[:, t:].set((ids[:, t:] + 1) % 256)
    early = ids.at[:, 2].set((ids[:, 2] + 1) % 256)
    for kernels in (False, True):
        with force_pallas(kernels), jax.default_matmul_precision("highest"):
            base, moved, reached = (model.apply({"params": params}, x)
                                    for x in (ids, later, early))
        np.testing.assert_array_equal(base[:, :t], moved[:, :t])
        assert rel_gap(moved[:, t], base[:, t]) > 1e-3
        assert float(jnp.max(jnp.abs(reached[:, t] - base[:, t]))) > 0
        assert rel_gap(moved, ref.logits(w, later, rcfg)) < 1e-4


def test_weights_round_trip_through_the_programs_layouts():
    """``to_program`` fuses q, k and v and gate and up; ``from_program``
    gives every reference leaf back; no head leaf on either side."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    tree = fam.to_program(w, cfg)
    back = fam.from_program(tree, cfg)
    assert sorted(back) == sorted(w)
    assert all((np.asarray(back[k]) == np.asarray(w[k])).all() for k in w)
    assert set(tree) == {"embed", "norm_f", "layer_0", "layer_1", "layer_2"}
    assert set(tree["layer_0"]) == {"input_norm", "post_norm", "mamba", "mlp"}
    mamba = tree["layer_0"]["mamba"]
    assert set(mamba) == {"in_proj", "conv_taps", "conv_bias", "dt_bias",
                          "A_log", "D", "norm", "out_proj"}
    # [z 256 | x 256 | B 32 | C 32 | dt 4]
    assert mamba["in_proj"]["kernel"].shape == (128, 580)
    assert mamba["conv_taps"].shape == (320, 4)
    assert mamba["out_proj"]["kernel"].shape == (256, 128)
    np.testing.assert_array_equal(mamba["conv_taps"], w["layers.0.mamba.conv_w"])
    assert set(tree["layer_1"]) == {"input_norm", "post_norm", "qkv", "o_proj",
                                    "mlp"}
    qkv = np.asarray(tree["layer_1"]["qkv"]["kernel"])
    assert qkv.shape == (128, (2 + 1 + 1) * 64)
    np.testing.assert_array_equal(qkv[:, :128], w["layers.1.attn.w_q"])
    np.testing.assert_array_equal(qkv[:, 192:], w["layers.1.attn.w_v"])
    gate_up = np.asarray(tree["layer_2"]["mlp"]["gate_up"]["kernel"])
    np.testing.assert_array_equal(gate_up[:, :256], w["layers.2.mlp.w_gate"])
    assert fam.views(w) is w
    # the reference draws the public Mamba-2 initial values
    a = np.exp(np.asarray(w["layers.0.mamba.A_log"]))
    assert ((a >= 1) & (a <= 16)).all()
    fresh = ref.init_params(jax.random.PRNGKey(0), rcfg)
    dt = np.asarray(jax.nn.softplus(fresh["layers.0.mamba.dt_bias"]))
    assert ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
    assert not np.asarray(fresh["layers.0.mamba.conv_b"]).any()
    assert (np.asarray(fresh["layers.0.mamba.D"]) == 1).all()


def test_the_family_refuses_what_it_does_not_build():
    """A file whose ``layer_types``, ``num_hidden_layers`` and
    ``published.layer_types`` disagree, or that asks for experts, a bias the
    family has not, positions, or an untied head."""
    fam.program_config(tiny_cfg(), jnp.float32)
    for over, match in (
            ({"num_hidden_layers": 2}, "num_hidden_layers"),
            ({"layer_types": ["mamba", "mamba", "attention"]}, "published"),
            ({"layer_types": ["mamba", "full_attention", "mamba"]}, "only"),
            ({"num_local_experts": 8}, "expert"),
            ({"num_experts_per_tok": 2}, "expert"),
            ({"shared_intermediate_size": 128}, "shared_intermediate_size"),
            ({"mamba_proj_bias": True}, "bias"),
            ({"attention_bias": True}, "bias"),
            ({"mamba_conv_bias": False}, "bias"),
            ({"mamba_expand": 4}, "mamba_expand"),
            ({"position_embedding_type": "rope"}, "positions"),
            ({"hidden_act": "gelu"}, "silu"),
            ({"tie_word_embeddings": False}, "tied")):
        with pytest.raises(ValueError, match=match):
            fam.program_config(tiny_cfg(**over), jnp.float32)
    with pytest.raises(ValueError, match="tied"):
        fam.reference_config(tiny_cfg(assumed={"tie_word_embeddings": False}))


def test_model_is_called_as_gptlm_is():
    """``apply(ids)`` -> logits; with labels -> (logits, loss); blocks are
    ``layer_<i>``; the scopes the readers look for are in the jaxpr; the
    model's own initial values are the public Mamba-2 code's; what the
    model does not build is refused."""
    from apex_tpu.models import GraniteHybridConfig, GraniteHybridLM

    cfg = GraniteHybridConfig.tiny()
    assert cfg.layer_types == ("mamba", "attention", "mamba")
    assert cfg.num_heads // cfg.num_kv_heads == 4 and cfg.head_dim == 16
    assert cfg.mamba_d_inner == 256 and cfg.num_layers == 3
    full = GraniteHybridConfig()
    assert full.layer_types.count("mamba") == 9 and full.layer_types[5] == "attention"
    assert (full.mamba_d_inner, full.head_dim, full.vocab_size) == (4096, 64, 12544)
    model = GraniteHybridLM(cfg)
    ids, labels = batch(rows=1, vocab=cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    assert set(params) == {"embed", "norm_f", "layer_0", "layer_1", "layer_2"}
    assert set(params["layer_0"]) == {"input_norm", "post_norm", "mamba", "mlp"}
    assert set(params["layer_1"]) == {"input_norm", "post_norm", "qkv",
                                      "o_proj", "mlp"}
    mamba = params["layer_2"]["mamba"]
    a = np.exp(np.asarray(mamba["A_log"]))
    dt = np.asarray(jax.nn.softplus(mamba["dt_bias"]))
    assert ((a >= 1) & (a <= 16)).all()
    assert ((dt >= 1e-3 * 0.999) & (dt <= 1e-1 * 1.001)).all()
    assert (np.asarray(mamba["D"]) == 1).all()
    assert not np.asarray(mamba["conv_bias"]).any()
    logits = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
    assert logits.shape == (1, 128, cfg.vocab_size) and logits.dtype == jnp.float32
    _, loss = jax.jit(lambda p: model.apply(
        {"params": p}, ids, labels=labels, deterministic=False))(params)
    assert loss.shape == () and np.isfinite(float(loss))
    text = str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, ids, labels=labels)[1])(params).pretty_print(
            name_stack=True))
    for scope in ("embed", "ssm_proj", "ssm_conv", "ssm_scan", "ssm_out",
                  "attn_full", "dense_ffn", "lm_head", "lm_loss", "layer_2"):
        assert scope in text, scope
    assert "moe_" not in text and "attn_window" not in text
    with pytest.raises(ValueError, match="layer type"):
        GraniteHybridLM(GraniteHybridConfig.tiny(
            layer_types=("mamba", "conv"))).init(jax.random.PRNGKey(0), ids)
    with pytest.raises(ValueError, match="groups"):
        GraniteHybridLM(GraniteHybridConfig.tiny(mamba_n_groups=3)).init(
            jax.random.PRNGKey(0), ids)


def test_conv_kernels_leave_logits_and_gradients_where_the_jnp_form_has_them(
        monkeypatch):
    """A tiny model whose ``xBC`` columns tile (a state of 128: x 256 wide at
    column 256, B and C a lane tile each) with the convolution's two kernels
    forced on (interpret mode; rows of 64 tokens in two row blocks, the
    forward run again under ``full_block``) and everything else as off the
    TPU, against the same model on the ``jax.numpy`` form: the logits, the
    loss and every leaf's gradient, the bias's among them."""
    import functools

    from apex_tpu import obs
    from apex_tpu.models import granite_hybrid as program
    from apex_tpu.ops import gated_delta as gd

    monkeypatch.setattr(gd, "_CONV_ROWS", 32)
    cfg = program.GraniteHybridConfig.tiny(
        layer_types=("mamba", "mamba"), mamba_d_state=128,
        remat_policy="full_block", compute_dtype=jnp.float32)
    model = program.GraniteHybridLM(cfg)
    ids, labels = batch(seq=64, vocab=cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    # the seeded bias is 0: one that the gradient and the outputs feel
    for layer in ("layer_0", "layer_1"):
        params[layer]["mamba"]["conv_bias"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(2), params[layer]["mamba"]["conv_bias"].shape)

    def run(p):
        (loss, logits), grads = jax.jit(jax.value_and_grad(
            lambda p: model.apply({"params": p}, ids, labels=labels,
                                  deterministic=False)[::-1], has_aux=True))(p)
        return logits, loss, grads

    gauge = lambda: obs.default_registry().get("ssd.conv_kernel").value
    want_logits, want_loss, want = run(params)
    assert gauge() == 0
    monkeypatch.setattr(program, "split_conv_xbc", functools.partial(
        program.split_conv_xbc, use_pallas=True))
    logits, loss, got = run(params)
    assert gauge() == 1
    assert rel_gap(logits, want_logits) < 1e-4
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, a), (_, b) in zip(flat(got), flat(want)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert rel_gap(a, b) < 1e-3, jax.tree_util.keystr(path)
    assert float(jnp.max(jnp.abs(got["layer_0"]["mamba"]["conv_bias"]))) > 0
