"""The ``deepseek_v3`` decoder (``models/deepseek_v3.py``: multi-head latent
attention through the flash kernels at a value head size of its own, a leading
dense layer, sigmoid-routed experts under a selection bias beside shared ones)
against the benchmark's plain reference (``benchmark/reference/deepseek_v3.py``,
whose rotation pairs adjacent dims) at tiny widths."""
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
from benchmark.families import deepseek_v3 as fam  # noqa: E402
from benchmark.reference import deepseek_v3 as ref  # noqa: E402


def tiny_cfg(held=(4, 8), routed_over=16, k=4, **assumed):
    """Values narrower than keys (128 = 96 + 32 against 64), fewer rotary
    dims than the rest, one leading dense layer, a strict subset held."""
    n = held[1] - held[0]
    return {
        "hidden_size": 128, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_dense_layers": 1, "num_attention_heads": 4,
        "qk_nope_head_dim": 96, "qk_rope_head_dim": 32, "v_head_dim": 64,
        "kv_lora_rank": 64, "q_lora_rank": None, "rope_theta": 50000,
        "intermediate_size": 256, "moe_intermediate_size": 128,
        "n_routed_experts": n, "num_experts_per_tok": k,
        "n_shared_experts": 2, "norm_topk_prob": True,
        "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
        "n_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "scoring_func": "sigmoid", "topk_method": "noaux_tc",
        "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
        "vocab_size": 250,
        "published": {"n_routed_experts": routed_over},
        "assumed": {"padded_vocab_size": 256, "experts_held": list(held),
                    "initializer_range": 0.02, "latent_norm_eps": 1e-6,
                    **assumed},
    }


def seeded(cfg, seed=0):
    """Reference weights with the norm scales and the selection bias moved
    off their initial 1 and 0 and the rotary key's and the queries' columns
    widened (at N(0, 0.02) and hidden 128 the scores hardly leave 0, and a
    wrong rotation would hide in the softmax's flatness), so that each is
    seen to matter."""
    rcfg = fam.reference_config(cfg)
    w = ref.init_params(jax.random.PRNGKey(seed), rcfg)
    for i, name in enumerate(sorted(w)):
        if "norm" in name or name.endswith("expert_bias"):
            # (the scores spread by ~0.05: a larger bias would starve experts)
            step = 0.01 if name.endswith("expert_bias") else 0.1
            w[name] = w[name] + step * jax.random.normal(
                jax.random.PRNGKey(1000 + i), w[name].shape)
        if name.endswith(("attn.w_q", "attn.w_dkv", "attn.w_ukv")):
            w[name] = 8.0 * w[name]
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
@pytest.mark.parametrize("remat", ["none", "dots_saveable", "full_block"])
def test_float32_matches_the_reference_leaf_by_leaf(kernels, remat):
    """Logits, loss and every leaf's gradient; with the Pallas kernels
    (interpret mode: flash attention at 128-wide keys against 64-wide values,
    the grouped products, the row movement) and with their off-TPU paths;
    with per-block recomputation.  float32 on both sides, two derivations of
    the same sums: 1e-5 on the loss, 1e-4 on the logits and 1e-3 on a leaf's
    gradient (against its largest element) are summation order, as in the
    other two sparse families' tests."""
    from apex_tpu import obs

    cfg = tiny_cfg(remat_policy=remat)
    rcfg, w = seeded(cfg)
    ids, labels = batch()
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)

    def program_loss(p):
        return model.apply({"params": p}, ids, labels=labels,
                           deterministic=False)[1]

    # (each side ONE compiled program: op by op these cost the suite minutes)
    with force_pallas(kernels):
        logits = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
        loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    reg = obs.default_registry()
    assert reg.get("moe.dispatch.kernels").value == kernels
    assert rel_gap(logits, jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)) < 1e-4
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: reference_loss(w, ids, labels, rcfg)))(w)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = fam.from_program(grads, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 1e-3, name
    # every leaf is reached but the selection bias, which only selects
    assert all(np.asarray(g).any() for n, g in got.items()
               if not n.endswith("expert_bias"))


def test_o2_stays_close_to_the_reference():
    """AMP O2 (bfloat16 compute, float32 masters) through AmpOptimizer's
    cast, as the benchmark's runner calls the model.  bfloat16 keeps 8 bits:
    a loss within 5e-3 and a gradient norm within 5% of the float32
    reference's are its rounding over three layers, the bounds the other two
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


def _layer(cfg, held, shared=True):
    return ExpertShardMLP(
        num_experts=cfg["published"]["n_routed_experts"], experts_held=held,
        d_ff=cfg["moe_intermediate_size"], k=cfg["num_experts_per_tok"],
        shared_d_ff=(cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
                     if shared else 0),
        route_norm=True, route_scale=cfg["routed_scaling_factor"], tile_rows=8)


def _layer_weights(w, layer=1):
    h = f"layers.{layer}."
    return {k[len(h):]: v for k, v in w.items() if k.startswith(h)}


def test_the_shares_add_up():
    """The routed parts of all eight shares of a 16-expert layer (two experts
    each) plus the shared experts, counted once, are the uncut layer of the
    reference: an 8-way deployment's chips together compute the model."""
    whole = tiny_cfg(held=(0, 16))
    rcfg, w = seeded(whole)
    lw = _layer_weights(w)
    x = jax.random.normal(jax.random.PRNGKey(3), (96, 128))
    uncut = ref.feed_forward(x, lw, rcfg)

    tree = fam.to_program(w, whole)["layer_1"]["moe"]
    total = jnp.zeros_like(x)
    for share in range(8):
        lo, hi = 2 * share, 2 * share + 2
        params = {"router": tree["router"], "expert_bias": tree["expert_bias"],
                  "wi": tree["wi"][lo:hi], "wo": tree["wo"][lo:hi]}
        part = _layer(whole, (lo, hi), shared=False).apply({"params": params}, x)
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    assert rel_gap(total + ref.shared(x, lw), uncut) < 1e-5
    # and one share WITH its shared experts is its routed part plus that
    lo, hi = 4, 6
    params = {"router": tree["router"], "expert_bias": tree["expert_bias"],
              "wi": tree["wi"][lo:hi], "wo": tree["wo"][lo:hi],
              "shared": tree["shared"]}
    cut = {**rcfg, "experts_held": [lo, hi]}
    cut_w = {k: v for k, v in lw.items()
             if not k.startswith("moe.experts.")
             or lo <= int(k.split(".")[2]) < hi}
    assert rel_gap(_layer(whole, (lo, hi)).apply({"params": params}, x),
                   ref.feed_forward(x, cut_w, cut)) < 1e-5


def test_shared_rotary_key_gradient_is_the_sum_over_heads():
    """ONE rotary key serves every head: its gradient through the mixer is
    the sum over heads of the gradient each head's own copy would get."""
    from apex_tpu.models.decoder import rotary
    from apex_tpu.ops.attention import attention_ref

    b, h, s, dn, dr, dv = 1, 4, 64, 96, 32, 64
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    q = jax.random.normal(ks[0], (b, h, s, dn + dr))
    k_nope = jax.random.normal(ks[1], (b, h, s, dn))
    v = jax.random.normal(ks[2], (b, h, s, dv))
    k_pe = jax.random.normal(ks[3], (b, 1, s, dr))
    do = jax.random.normal(ks[4], (b, h, s, dv))

    def loss(k_pe_heads):           # (b, h or 1, s, dr)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(
            rotary(k_pe_heads, 50000.0), (b, h, s, dr))], axis=-1)
        return jnp.sum(attention_ref(q, k, v, causal=True) * do)

    shared = jax.jit(jax.grad(loss))(k_pe)
    per_head = jax.jit(jax.grad(loss))(jnp.broadcast_to(k_pe, (b, h, s, dr)))
    assert shared.shape == (b, 1, s, dr) and per_head.shape == (b, h, s, dr)
    assert float(jnp.max(jnp.abs(per_head[:, 0] - per_head[:, 1]))) > 1e-3
    np.testing.assert_allclose(shared[:, 0], per_head.sum(axis=1),
                               rtol=1e-5, atol=1e-5)

    # and through the model's own mixer: the down-projection's rotary columns
    # get the gradient the reference's single shared key gets
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    ids, labels = batch(rows=1, seq=64)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    grads = jax.jit(jax.grad(lambda p: model.apply(
        {"params": p}, ids, labels=labels)[1]))(fam.to_program(w, cfg))
    want = jax.jit(jax.grad(
        lambda w: reference_loss(w, ids, labels, rcfg)))(w)
    got = fam.from_program(grads, cfg)["layers.1.attn.w_dkv"][:, 64:]
    assert float(jnp.max(jnp.abs(got))) > 0
    assert rel_gap(got, want["layers.1.attn.w_dkv"][:, 64:]) < 1e-3


def test_rotating_halves_of_deinterleaved_columns_is_rotating_adjacent_pairs():
    """The program rotates the two halves of the rotary slice and keeps its
    columns de-interleaved; the reference rotates adjacent pairs in place.
    The rotated slices are permutations of each other, so q . k is the same
    number — and NOT the same as rotating halves of the published order."""
    from apex_tpu.models.decoder import rotary

    x = jax.random.normal(jax.random.PRNGKey(8), (2, 3, 40, 32))
    y = jax.random.normal(jax.random.PRNGKey(9), (2, 3, 40, 32))
    perm = np.concatenate([np.arange(0, 32, 2), np.arange(1, 32, 2)])
    pos = jnp.arange(40)
    want_x, want_y = (ref.rotary_pairs(t, 50000.0, pos) for t in (x, y))
    got_x, got_y = (rotary(t[..., perm], 50000.0) for t in (x, y))
    assert rel_gap(got_x, want_x[..., perm]) < 1e-6
    scores = lambda a, b: jnp.einsum("bhqd,bhkd->bhqk", a, b)
    assert rel_gap(scores(got_x, got_y), scores(want_x, want_y)) < 1e-5
    assert rel_gap(scores(rotary(x, 50000.0), rotary(y, 50000.0)),
                   scores(want_x, want_y)) > 1e-2
    np.testing.assert_array_equal(want_x[..., 0, :], x[..., 0, :])  # position 0
    np.testing.assert_allclose(jnp.linalg.norm(want_x, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)


def test_weights_round_trip_through_the_programs_layouts():
    """``to_program`` de-interleaves the rotary columns, fuses gate and up
    and stacks the experts; ``from_program`` gives every reference leaf
    back."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    tree = fam.to_program(w, cfg)
    back = fam.from_program(tree, cfg)
    assert sorted(back) == sorted(w)
    assert all((np.asarray(back[k]) == np.asarray(w[k])).all() for k in w)
    q = np.asarray(tree["layer_1"]["attn"]["q_proj"]["kernel"]).reshape(128, 4, 128)
    ref_q = np.asarray(w["layers.1.attn.w_q"]).reshape(128, 4, 128)
    np.testing.assert_array_equal(q[:, :, :96], ref_q[:, :, :96])   # nope: as is
    np.testing.assert_array_equal(q[:, 2, 96:112], ref_q[:, 2, 96::2])  # evens
    np.testing.assert_array_equal(q[:, 2, 112:], ref_q[:, 2, 97::2])    # odds
    dkv = np.asarray(tree["layer_1"]["attn"]["kv_a_proj"]["kernel"])
    assert dkv.shape == (128, 64 + 32)
    np.testing.assert_array_equal(dkv[:, :64], w["layers.1.attn.w_dkv"][:, :64])
    np.testing.assert_array_equal(dkv[:, 64:80], w["layers.1.attn.w_dkv"][:, 64::2])
    assert "mlp" in tree["layer_0"] and "moe" not in tree["layer_0"]
    assert tree["layer_1"]["moe"]["wi"].shape == (4, 128, 256)
    assert tree["layer_1"]["moe"]["shared"]["gate_up"]["kernel"].shape == (128, 512)
    seen = fam.views(w)
    assert seen["layers.1.moe.experts.w_gate"].shape == (4, 128, 128)
    assert not any(".experts.4." in k for k in seen)


def test_model_is_called_as_gptlm_is():
    """``apply(ids)`` -> logits; with labels -> (logits, loss); blocks are
    ``layer_<i>``; the scopes the readers look for are in the jaxpr; what
    the family's program does not do is refused."""
    from apex_tpu.models import DeepseekV3Config, DeepseekV3LM

    cfg = DeepseekV3Config.tiny()
    assert cfg.qk_nope_head_dim + cfg.qk_rope_head_dim == 128 != cfg.v_head_dim
    assert cfg.qk_rope_head_dim < cfg.qk_nope_head_dim
    assert cfg.experts_held[1] - cfg.experts_held[0] < cfg.n_routed_experts
    model = DeepseekV3LM(cfg)
    ids, labels = batch(rows=1, vocab=cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    assert {f"layer_{i}" for i in range(3)} <= set(params)
    assert "mlp" in params["layer_0"] and "moe" in params["layer_2"]
    attn = params["layer_1"]["attn"]
    assert attn["q_proj"]["kernel"].shape == (128, 4 * 128)
    assert attn["kv_a_proj"]["kernel"].shape == (128, 64 + 32)
    assert attn["kv_b_proj"]["kernel"].shape == (64, 4 * (96 + 64))
    assert attn["o_proj"]["kernel"].shape == (4 * 64, 128)
    logits, loss = jax.jit(lambda p: model.apply(
        {"params": p}, ids, labels=labels, deterministic=False))(params)
    assert logits.shape == (1, 128, cfg.vocab_size) and logits.dtype == jnp.float32
    assert loss.shape == () and np.isfinite(float(loss))
    text = str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, ids, labels=labels)[1])(params).pretty_print(
            name_stack=True))
    for scope in ("mla_proj", "attn_full", "mla_out", "moe_router",
                  "moe_dispatch", "moe_experts", "moe_shared", "lm_head",
                  "lm_loss", "layer_2"):
        assert scope in text, scope
    with pytest.raises(ValueError, match="whole pairs"):
        DeepseekV3LM(DeepseekV3Config.tiny(qk_rope_head_dim=31)).init(
            jax.random.PRNGKey(0), ids)
    with pytest.raises(ValueError, match="low-rank"):
        fam.program_config({**tiny_cfg(), "q_lora_rank": 1536}, jnp.float32)
    with pytest.raises(ValueError, match="group-limited"):
        fam.reference_config({**tiny_cfg(), "n_group": 8})
