"""The ``afmoe`` decoder (``models/afmoe.py``) and one chip's share of its
expert layers (``parallel/moe.py::ExpertShardMLP``) against the benchmark's
plain reference (``benchmark/reference/afmoe.py``) at tiny widths."""
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
                                   sigmoid_topk_routing)
from benchmark.families import afmoe as fam  # noqa: E402
from benchmark.reference import afmoe as ref  # noqa: E402

W, F = "sliding_attention", "full_attention"


def tiny_cfg(held=(4, 8), routed_over=16, k=4, **assumed):
    return {
        "hidden_size": 128, "num_hidden_layers": 3, "num_dense_layers": 1,
        "layer_types": [W, W, F], "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 64, "sliding_window": 48,
        "rope_theta": 10000, "intermediate_size": 256,
        "moe_intermediate_size": 128, "num_experts": held[1] - held[0],
        "num_experts_per_tok": k, "num_shared_experts": 1,
        "route_norm": True, "route_scale": 2.826, "rms_norm_eps": 1e-5,
        "mup_enabled": True, "vocab_size": 250,
        "published": {"num_experts": routed_over},
        "assumed": {"padded_vocab_size": 256, "experts_held": list(held),
                    "initializer_range": 0.02, **assumed},
    }


def seeded(cfg, seed=0):
    """Reference weights with the norm scales and the selection bias moved
    off their initial 1 and 0, so that each is seen to matter."""
    rcfg = fam.reference_config(cfg)
    w = ref.init_params(jax.random.PRNGKey(seed), rcfg)
    for i, name in enumerate(sorted(w)):
        if "norm" in name or name.endswith("expert_bias"):
            # (the scores spread by ~0.05: a larger bias would starve experts)
            step = 0.01 if name.endswith("expert_bias") else 0.1
            w[name] = w[name] + step * jax.random.normal(
                jax.random.PRNGKey(1000 + i), w[name].shape)
    return rcfg, w


def batch(rows=2, seq=128, vocab=250):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=-1)
    return ids, labels


def rel_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))


@pytest.mark.parametrize("kernels", [False, True], ids=["ragged_dot", "pallas"])
@pytest.mark.parametrize("remat", ["none", "dots_saveable", "full_block"])
def test_float32_matches_the_reference_leaf_by_leaf(kernels, remat):
    """Logits, loss and every leaf's gradient; with the Pallas kernels
    (interpret mode: the grouped products and the row movement of
    ``ops/moe_rows.py``) and with their off-TPU paths; with per-block remat."""
    from apex_tpu import obs

    cfg = tiny_cfg(remat_policy=remat)
    rcfg, w = seeded(cfg)
    ids, labels = batch()
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    params = fam.to_program(w, cfg)

    def program_loss(p):
        return model.apply({"params": p}, ids, labels=labels,
                           deterministic=False)[1]

    def reference_loss(w):
        return (jnp.sum(ref.loss_rows(w, (ids, labels), rcfg))
                / jnp.sum(labels >= 0))

    with force_pallas(kernels):
        # (each side ONE compiled program: op by op these cost the suite
        # minutes)
        logits = jax.jit(lambda p: model.apply({"params": p}, ids))(params)
        loss, grads = jax.jit(jax.value_and_grad(program_loss))(params)
    assert obs.default_registry().get("moe.dispatch.kernels").value == kernels
    assert rel_gap(logits, jax.jit(lambda w: ref.logits(w, ids, rcfg))(w)) < 1e-5
    want_loss, want = jax.jit(jax.value_and_grad(reference_loss))(w)
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got = fam.from_program(grads, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 2e-4, name
    # the selection bias has no gradient; everything else has one
    assert not np.asarray(got["layers.1.moe.expert_bias"]).any()
    assert all(np.asarray(g).any() for n, g in got.items()
               if not n.endswith("expert_bias"))


def test_o2_stays_close_to_the_reference():
    """AMP O2 (bfloat16 compute, float32 masters) through AmpOptimizer's
    cast, as the benchmark's runner calls the model."""
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
        lambda w: jnp.sum(ref.loss_rows(w, (ids, labels), rcfg))
        / jnp.sum(labels >= 0)))(w)
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
        shared_d_ff=cfg["moe_intermediate_size"] if shared else 0,
        route_norm=True, route_scale=cfg["route_scale"], tile_rows=8)


def test_the_shares_add_up():
    """The eight shares' routed parts plus the shared expert, counted once,
    are the uncut layer of the reference (every expert held)."""
    whole = tiny_cfg(held=(0, 16))
    rcfg, w = seeded(whole)
    h = "layers.1."
    lw = {k[len(h):]: v for k, v in w.items() if k.startswith(h)}
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
    shared = ref.swiglu(x, lw["shared.w_gate"], lw["shared.w_up"],
                        lw["shared.w_down"])
    assert rel_gap(total + shared, uncut) < 1e-5
    # and one share WITH its shared expert is its routed part plus that
    lo, hi = 4, 8
    params = {"router": tree["router"], "expert_bias": tree["expert_bias"],
              "wi": tree["wi"][lo:hi], "wo": tree["wo"][lo:hi],
              "shared": tree["shared"]}
    cut = {**rcfg, "experts_held": [lo, hi]}
    cut_w = {k: v for k, v in lw.items()
             if not k.startswith("moe.experts.")
             or lo <= int(k.split(".")[2]) < hi}
    assert rel_gap(_layer(whole, (lo, hi)).apply({"params": params}, x),
                   ref.feed_forward(x, cut_w, cut)) < 1e-5


def test_no_token_is_dropped_in_the_worst_case():
    """Every token picks the SAME held experts: the buffer is full to its
    last row, every slot has a row, and the result is still the
    reference's."""
    cfg = tiny_cfg(held=(4, 8), k=4)
    rcfg, w = seeded(cfg)
    h = "layers.1."
    lw = {k[len(h):]: v for k, v in w.items() if k.startswith(h)}
    bias = jnp.zeros((16,)).at[4:8].set(10.0)      # all pick experts 4..7
    lw["moe.expert_bias"] = bias
    x = jax.random.normal(jax.random.PRNGKey(4), (64, 128))
    tree = fam.to_program(w, cfg)["layer_1"]["moe"]
    got = _layer(cfg, (4, 8)).apply(
        {"params": {**tree, "expert_bias": bias}}, x)
    assert rel_gap(got, ref.feed_forward(x, lw, rcfg)) < 1e-5

    sel = jnp.tile(jnp.arange(4, 8)[None], (64, 1))
    from apex_tpu.ops.grouped_mm import rows_capacity
    cap = rows_capacity(64 * 4, 4, 8)
    layout, slot_row, row_slot = shard_dispatch(sel, (4, 8), cap, 8)
    assert int((slot_row < cap).sum()) == 64 * 4            # every slot placed
    assert int((row_slot < 64 * 4).sum()) == 64 * 4         # each on its own row
    assert sorted(np.asarray(row_slot[row_slot < 256])) == list(range(256))
    assert np.asarray(layout.tile_valid).sum() == 256
    # and a slot's row leads back to the slot
    rows = np.asarray(slot_row).reshape(-1)
    assert (np.asarray(row_slot)[rows] == np.arange(256)).all()


def test_selection_bias_changes_the_selection_and_not_the_weights():
    """top-8 over 128 with b != 0: other experts are picked, and a picked
    expert's weight is still its own score over the picked scores' sum."""
    logits = jax.random.normal(jax.random.PRNGKey(5), (32, 128))
    scores = jax.nn.sigmoid(logits)
    zero = jnp.zeros((128,))
    bias = 0.5 * jax.random.normal(jax.random.PRNGKey(6), (128,))
    sel0, w0 = sigmoid_topk_routing(logits, zero, 8, True, 2.826)
    sel1, w1 = sigmoid_topk_routing(logits, bias, 8, True, 2.826)
    assert (np.sort(sel0, -1) != np.sort(sel1, -1)).any()
    np.testing.assert_array_equal(
        np.sort(sel1, -1),
        np.sort(np.argsort(-(np.asarray(scores) + np.asarray(bias)), -1)[:, :8], -1))
    picked = np.take_along_axis(np.asarray(scores), np.asarray(sel1), -1)
    np.testing.assert_allclose(
        w1, 2.826 * picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(w1.sum(-1), 2.826, rtol=1e-6)
    # without the normalisation the weights are the raw scores
    _, raw = sigmoid_topk_routing(logits, bias, 8, False, 1.0)
    np.testing.assert_allclose(raw, picked, rtol=1e-6)


def test_layer_sets_its_counters_and_refuses_a_bad_range():
    from apex_tpu import obs

    cfg = tiny_cfg()
    x = jnp.zeros((16, 128))
    layer = _layer(cfg, (4, 8))
    layer.init(jax.random.PRNGKey(0), x)
    reg = obs.default_registry()
    assert reg.get("moe.experts_held").value == 4
    assert reg.get("moe.experts_routed_over").value == 16
    with pytest.raises(ValueError, match="experts_held"):
        _layer(cfg, (12, 20)).init(jax.random.PRNGKey(0), x)


def test_model_is_called_as_gptlm_is():
    """``apply(ids)`` -> logits; with labels -> (logits, loss); blocks are
    ``layer_<i>``; the scopes the readers look for are in the jaxpr."""
    from apex_tpu.models.afmoe import AfmoeConfig, AfmoeLM

    cfg = AfmoeConfig.tiny()
    model = AfmoeLM(cfg)
    ids, labels = batch(rows=1, vocab=cfg.vocab_size)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    assert {f"layer_{i}" for i in range(3)} <= set(params)
    assert "mlp" in params["layer_0"] and "moe" in params["layer_1"]
    logits = model.apply({"params": params}, ids)
    assert logits.shape == (1, 128, cfg.vocab_size) and logits.dtype == jnp.float32
    _, loss = model.apply({"params": params}, ids, labels=labels,
                          deterministic=False)
    assert loss.shape == () and np.isfinite(float(loss))
    text = str(jax.make_jaxpr(lambda p: model.apply(
        {"params": p}, ids, labels=labels)[1])(params).pretty_print(
            name_stack=True))
    for scope in ("attn_window", "attn_full", "moe_router", "moe_dispatch",
                  "moe_experts", "moe_shared", "lm_head", "lm_loss", "layer_2"):
        assert scope in text, scope
    with pytest.raises(ValueError, match="layer type"):
        AfmoeLM(AfmoeConfig.tiny(layer_types=("banded",))).init(
            jax.random.PRNGKey(0), ids)
