"""The ``qwen3_next`` decoder (``models/qwen3_next.py``: gated delta net,
gated full attention, softmax-routed experts beside a gated shared one)
against the benchmark's plain reference (``benchmark/reference/qwen3_next.py``,
whose delta rule is the token recurrence) at tiny widths."""
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
from apex_tpu.parallel.moe import (ExpertShardMLP, shard_dispatch,  # noqa: E402
                                   softmax_topk_routing)
from benchmark.families import qwen3_next as fam  # noqa: E402
from benchmark.reference import qwen3_next as ref  # noqa: E402


def tiny_cfg(held=(4, 8), routed_over=16, k=4, **assumed):
    return {
        "hidden_size": 128, "num_hidden_layers": 4, "full_attention_interval": 4,
        "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
        "partial_rotary_factor": 0.25, "rope_theta": 10000000,
        "linear_num_key_heads": 1, "linear_num_value_heads": 2,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "moe_intermediate_size": 128,
        "shared_expert_intermediate_size": 128,
        "num_experts": held[1] - held[0], "num_experts_per_tok": k,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6, "vocab_size": 250,
        "mlp_only_layers": [], "decoder_sparse_step": 1,
        "published": {"num_experts": routed_over},
        "assumed": {"padded_vocab_size": 256, "experts_held": list(held),
                    "initializer_range": 0.02, **assumed},
    }


def seeded(cfg, seed=0):
    """Reference weights with the norms moved off their initial 0 and 1, the
    router sharpened and the delta net's projections widened (at N(0, 0.02)
    and hidden 128 the decays hardly move and ``A_log``'s gradient is
    rounding noise), so that each is seen to matter."""
    rcfg = fam.reference_config(cfg)
    w = ref.init_params(jax.random.PRNGKey(seed), rcfg)
    for i, name in enumerate(sorted(w)):
        if "norm" in name or name.endswith("dt_bias"):
            w[name] = w[name] + 0.1 * jax.random.normal(
                jax.random.PRNGKey(1000 + i), w[name].shape)
        if name.endswith("moe.router"):
            w[name] = 20.0 * w[name]
        if name.endswith(("gdn.w_qkvz", "gdn.w_ba")):
            w[name] = 10.0 * w[name]
    return rcfg, w


def batch(rows=2, seq=136, vocab=250):
    """136 tokens: two chunks of the rule's 64 and an eighth of a third."""
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
    (interpret mode: the delta rule's chain, the grouped products, the row
    movement) and with their off-TPU paths; with per-block recomputation."""
    from apex_tpu import obs

    cfg = tiny_cfg(remat_policy=remat)
    rcfg, w = seeded(cfg)
    ids, labels = batch(seq=128 if kernels else 136)  # flash wants whole blocks
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
    reg = obs.default_registry()
    assert reg.get("moe.dispatch.kernels").value == kernels
    assert reg.get("gdn.kernels").value == kernels
    assert reg.get("gdn.conv_kernel").value == kernels     # 128 rows tile
    # float32 both sides, two derivations: the chunked algebra (a triangular
    # inverse, differences of products) leaves 1e-4 where the recurrence and
    # the other layers leave 1e-5
    assert rel_gap(logits, jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)) < 2e-4
    want_loss, want = jax.jit(jax.value_and_grad(
        lambda w: reference_loss(w, ids, labels, rcfg)))(w)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = fam.from_program(grads, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 1e-3, name
    assert all(np.asarray(g).any() for g in got.values())


def test_o2_stays_close_to_the_reference():
    """AMP O2 (bfloat16 compute, float32 masters) through AmpOptimizer's
    cast, as the benchmark's runner calls the model."""
    import apex_tpu.amp as amp

    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    # a chunk and an eighth: at `seeded`'s sharpened decays single tokens of
    # longer rows carry the gradient, and bfloat16 moves it by percents
    ids, labels = batch(seq=72)
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
        num_experts=cfg["published"]["num_experts"], experts_held=held,
        d_ff=cfg["moe_intermediate_size"], k=cfg["num_experts_per_tok"],
        shared_d_ff=cfg["shared_expert_intermediate_size"] if shared else 0,
        route_norm=True, score_func="softmax", shared_gate=shared, tile_rows=8)


def _layer_weights(w, layer=1):
    h = f"layers.{layer}."
    return {k[len(h):]: v for k, v in w.items() if k.startswith(h)}


def test_the_shares_add_up():
    """The 4 shares of a 16-expert layer's routed parts plus the GATED
    shared expert, counted once, are the uncut layer of the reference."""
    whole = tiny_cfg(held=(0, 16))
    rcfg, w = seeded(whole)
    lw = _layer_weights(w)
    x = jax.random.normal(jax.random.PRNGKey(3), (96, 128))
    uncut = ref.feed_forward(x, lw, rcfg)

    tree = fam.to_program(w, whole)["layer_1"]["moe"]
    total = jnp.zeros_like(x)
    for share in range(4):
        lo, hi = 4 * share, 4 * share + 4
        params = {"router": tree["router"], "wi": tree["wi"][lo:hi],
                  "wo": tree["wo"][lo:hi]}
        part = _layer(whole, (lo, hi), shared=False).apply({"params": params}, x)
        assert float(jnp.max(jnp.abs(part))) > 0
        total = total + part
    assert rel_gap(total + ref.shared(x, lw), uncut) < 1e-5
    # and one share WITH its gated shared expert is its routed part plus that
    lo, hi = 4, 8
    params = {"router": tree["router"], "wi": tree["wi"][lo:hi],
              "wo": tree["wo"][lo:hi], "shared": tree["shared"],
              "shared_gate": tree["shared_gate"]}
    cut = {**rcfg, "experts_held": [lo, hi]}
    cut_w = {k: v for k, v in lw.items()
             if not k.startswith("moe.experts.")
             or lo <= int(k.split(".")[2]) < hi}
    assert rel_gap(_layer(whole, (lo, hi)).apply({"params": params}, x),
                   ref.feed_forward(x, cut_w, cut)) < 1e-5


def test_softmax_routing_drops_no_token_in_the_worst_case():
    """Every token picks the SAME held experts (a router that only sees a
    constant feature): the buffer is full to its last row and the result
    is still the reference's."""
    cfg = tiny_cfg(held=(4, 8), k=4)
    rcfg, w = seeded(cfg)
    lw = _layer_weights(w)
    router = jnp.zeros((128, 16)).at[0, 4:8].set(50.0)
    lw["moe.router"] = router
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 128)).at[:, 0].set(1.0)
    tree = fam.to_program(w, cfg)["layer_1"]["moe"]
    got = _layer(cfg, (4, 8)).apply({"params": {**tree, "router": router}}, x)
    assert rel_gap(got, ref.feed_forward(x, lw, rcfg)) < 1e-5

    sel, weights = softmax_topk_routing(x @ router, 4, True)
    assert sorted(np.unique(np.asarray(sel))) == [4, 5, 6, 7]
    np.testing.assert_allclose(weights.sum(-1), 1.0, rtol=1e-6)
    from apex_tpu.ops.grouped_mm import rows_capacity
    cap = rows_capacity(64 * 4, 4, 8)
    layout, slot_row, row_slot = shard_dispatch(sel, (4, 8), cap, 8)
    assert int((slot_row < cap).sum()) == 64 * 4            # every slot placed
    assert int((row_slot < 64 * 4).sum()) == 64 * 4         # each on its own row
    assert np.asarray(layout.tile_valid).sum() == 256


def test_softmax_routing_weights():
    """The weights are the picked probabilities of a softmax over ALL
    experts, renormalised or not; no bias, no scale."""
    logits = 3.0 * jax.random.normal(jax.random.PRNGKey(5), (32, 512))
    probs = np.asarray(jax.nn.softmax(logits, -1))
    sel, w = softmax_topk_routing(logits, 10, True)
    np.testing.assert_array_equal(
        np.sort(sel, -1), np.sort(np.argsort(-probs, -1)[:, :10], -1))
    picked = np.take_along_axis(probs, np.asarray(sel), -1)
    np.testing.assert_allclose(w, picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    _, raw = softmax_topk_routing(logits, 10, False)
    np.testing.assert_allclose(raw, picked, rtol=1e-6)


def test_layer_refuses_an_unknown_score_function_and_has_no_bias():
    x = jnp.zeros((16, 128))
    params = _layer(tiny_cfg(), (4, 8)).init(jax.random.PRNGKey(0), x)["params"]
    assert "expert_bias" not in params and params["shared_gate"].shape == (128, 1)
    with pytest.raises(ValueError, match="score_func"):
        ExpertShardMLP(num_experts=16, experts_held=(0, 4), d_ff=128, k=4,
                       score_func="tanh").init(jax.random.PRNGKey(0), x)


def test_rotary_leaves_the_rest_of_a_head_untouched():
    from apex_tpu.models.decoder import rotary

    x = jax.random.normal(jax.random.PRNGKey(6), (2, 3, 40, 256))
    y = rotary(x, 1e7, 64)
    np.testing.assert_array_equal(y[..., 64:], x[..., 64:])
    np.testing.assert_array_equal(y[..., 0, :], x[..., 0, :])   # position 0
    assert float(jnp.max(jnp.abs(y[..., 1:, :64] - x[..., 1:, :64]))) > 0.1
    want = ref.rotary(x, 1e7, jnp.arange(40), 64)
    assert rel_gap(y, want) < 1e-6
    # a rotation: the rotated part keeps its length
    np.testing.assert_allclose(jnp.linalg.norm(y[..., :64], axis=-1),
                               jnp.linalg.norm(x[..., :64], axis=-1), rtol=1e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["float32", "bfloat16"])
def test_conv_kernels_leave_loss_and_gradients_where_the_xla_form_has_them(
        dtype, monkeypatch):
    """``Qwen3NextConfig.tiny()`` with the convolution's two kernels forced
    on (interpret mode; rows of 64 tokens in two row blocks) and everything
    else as off the TPU, against the same model on the XLA form: the loss
    and every leaf's gradient."""
    import functools

    from apex_tpu import obs
    from apex_tpu.models import qwen3_next as program
    from apex_tpu.ops import gated_delta as gd

    monkeypatch.setattr(gd, "_CONV_ROWS", 32)
    cfg = program.Qwen3NextConfig.tiny(compute_dtype=dtype)
    model = program.Qwen3NextLM(cfg)
    ids, labels = batch(seq=64, vocab=cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    loss_of = lambda p: model.apply({"params": p}, ids, labels=labels,
                                    deterministic=False)[1]
    # one program a side, not an eager walk of the model's primitives
    want_loss, want = jax.jit(jax.value_and_grad(loss_of))(params)
    gauge = obs.default_registry().get("gdn.conv_kernel")
    assert gauge.value == 0
    monkeypatch.setattr(program, "split_conv_qkvz", functools.partial(
        gd.split_conv_qkvz, use_pallas=True))
    loss, got = jax.jit(jax.value_and_grad(loss_of))(params)
    assert gauge.value == 1
    # float32: 1e-3 a leaf as the test against the reference above — dw's
    # sum in another order leaves 2e-4 of A_log's gradient, itself 1e-6
    tol = 1e-3 if dtype == jnp.float32 else 2e-2
    assert abs(float(loss) - float(want_loss)) <= tol * 1e-2 * float(want_loss)
    flat = lambda t: jax.tree_util.tree_leaves_with_path(t)
    for (path, a), (_, b) in zip(flat(got), flat(want)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        assert rel_gap(a, b) <= tol, jax.tree_util.keystr(path)


def test_delta_nets_parameter_tree_is_what_to_program_fills():
    """The kernels read the projection's output in the PUBLISHED per-key-
    head layout and the convolution's weights in the published channel
    order: every name and shape of the program's tree is the one
    ``families/qwen3_next.py::to_program`` loads into, unedited."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    ids, _ = batch(rows=1, seq=64)
    made = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    shapes = lambda t: jax.tree_util.tree_map(lambda x: tuple(x.shape), t)
    assert shapes(made) == shapes(fam.to_program(w, cfg))
    hk, hv, d, hidden, taps = 1, 2, 128, 128, 4
    assert shapes(made["layer_0"]["gdn"]) == {
        "in_proj_qkvz": {"kernel": (hidden, hk * (2 * d + 2 * (hv // hk) * d))},
        "in_proj_ba": {"kernel": (hidden, 2 * hv)},
        "conv": (2 * hk * d + hv * d, taps),
        "A_log": (hv,), "dt_bias": (hv,), "norm": (d,),
        "out_proj": {"kernel": (hv * d, hidden)},
    }


def test_weights_round_trip_through_the_programs_layouts():
    """``to_program`` fuses query|gate, keys and values into one matrix and
    stacks the experts; ``from_program`` gives every reference leaf back,
    and the fused matrix's columns are where the model cuts them."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    tree = fam.to_program(w, cfg)
    back = fam.from_program(tree, cfg)
    assert sorted(back) == sorted(w)
    assert all((np.asarray(back[k]) == np.asarray(w[k])).all() for k in w)
    qgkv = tree["layer_3"]["attn"]["qgkv"]["kernel"]
    hq, hk, hd = 4, 2, 64
    assert qgkv.shape == (128, (2 * hq + 2 * hk) * hd)
    per_head = w["layers.3.attn.w_q"].reshape(128, hq, 2 * hd)
    np.testing.assert_array_equal(            # head 1's gate follows its query
        qgkv[:, 2 * hd + hd:2 * hd + 2 * hd], per_head[:, 1, hd:])
    np.testing.assert_array_equal(qgkv[:, 2 * hq * hd:(2 * hq + hk) * hd],
                                  w["layers.3.attn.w_k"])
    assert "gdn" in tree["layer_0"] and "attn" not in tree["layer_0"]
    seen = fam.views(w)
    assert seen["layers.1.moe.experts.w_gate"].shape == (4, 128, 128)
    assert not any(".experts.4." in k for k in seen)


def test_model_is_called_as_gptlm_is():
    """``apply(ids)`` -> logits; with labels -> (logits, loss); blocks are
    ``layer_<i>``; the scopes the readers look for are in the jaxpr and the
    gauges are set."""
    from apex_tpu import obs
    from apex_tpu.models.qwen3_next import Qwen3NextConfig, Qwen3NextLM

    cfg = Qwen3NextConfig.tiny()
    model = Qwen3NextLM(cfg)
    ids, labels = batch(rows=1, vocab=cfg.vocab_size)
    params = jax.jit(model.init)(jax.random.PRNGKey(0), ids)["params"]
    assert {f"layer_{i}" for i in range(4)} <= set(params)
    assert "gdn" in params["layer_2"] and "attn" in params["layer_3"]
    assert all("moe" in params[f"layer_{i}"] for i in range(4))
    a_log = np.asarray(params["layer_0"]["gdn"]["A_log"])
    assert (np.exp(a_log) > 0).all() and (np.exp(a_log) <= 16).all()
    logits, loss = jax.jit(lambda p: model.apply(
        {"params": p}, ids, labels=labels, deterministic=False))(params)
    assert logits.shape == (1, 136, cfg.vocab_size) and logits.dtype == jnp.float32
    assert loss.shape == () and np.isfinite(float(loss))
    reg = obs.default_registry()
    assert reg.get("gdn.chunk").value == 64
    assert reg.get("gdn.chunks_per_row").value == 3          # 136 tokens
    assert reg.get("gdn.value_heads").value == 2
    assert reg.get("gdn.kernels").value == 0                 # off the TPU
    text = str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, ids, labels=labels)[1])(params).pretty_print(
            name_stack=True))
    for scope in ("gdn_proj", "gdn_conv", "gdn_scan", "gdn_out", "attn_full",
                  "moe_router", "moe_dispatch", "moe_experts", "moe_shared",
                  "lm_head", "lm_loss", "layer_3"):
        assert scope in text, scope
    with pytest.raises(ValueError, match="multiple"):
        Qwen3NextLM(Qwen3NextConfig.tiny(linear_num_key_heads=3)).init(
            jax.random.PRNGKey(0), ids)
