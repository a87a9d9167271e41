"""The ``kimi_linear`` decoder (``models/kimi_linear.py``: Kimi Delta
Attention — the delta rule with a decay a key channel, ``ops/kda.py`` — in the
layers a published list names, position-free latent attention in the others, a
leading dense layer, sigmoid-routed experts under a selection bias beside a
shared one) against the benchmark's plain reference
(``benchmark/reference/kimi_linear.py``, whose rule is the token-by-token
recurrence) at tiny widths."""
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
from benchmark.families import kimi_linear as fam  # noqa: E402
from benchmark.reference import kimi_linear as ref  # noqa: E402


def tiny_cfg(held=(4, 8), routed_over=16, k=4, **assumed):
    """KDA + dense, KDA + experts, latent + experts; KDA's heads keep the
    kernels' 128 lanes; values narrower than keys in the latent layer; a
    strict subset of the experts held."""
    return {
        "hidden_size": 128, "num_hidden_layers": 3, "first_k_dense_replace": 1,
        "num_dense_layers": 1, "num_attention_heads": 2,
        "num_key_value_heads": 2,
        "linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [3],
                               "num_heads": 2, "head_dim": 128,
                               "short_conv_kernel_size": 4},
        "qk_nope_head_dim": 96, "qk_rope_head_dim": 32, "v_head_dim": 64,
        "kv_lora_rank": 64, "q_lora_rank": None, "mla_use_nope": True,
        "intermediate_size": 256, "moe_intermediate_size": 128,
        "num_experts": held[1] - held[0], "num_experts_per_token": k,
        "num_shared_experts": 1, "moe_renormalize": True,
        "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5,
        "num_expert_group": 1, "topk_group": 1, "moe_layer_freq": 1,
        "moe_router_activation_func": "sigmoid",
        "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
        "vocab_size": 250,
        "published": {"num_experts": routed_over},
        "assumed": {"padded_vocab_size": 256, "experts_held": list(held),
                    "initializer_range": 0.02, "latent_norm_eps": 1e-6,
                    **assumed},
    }


def seeded(cfg, seed=0):
    """Reference weights with the norm scales, ``dt_bias`` and the selection
    bias moved off their initial values and the mixers' projections widened
    (at N(0, 0.02) and hidden 128 neither the scores nor the gates leave
    their resting points), so that each is seen to matter."""
    rcfg = fam.reference_config(cfg)
    w = ref.init_params(jax.random.PRNGKey(seed), rcfg)
    for i, name in enumerate(sorted(w)):
        if "norm" in name or name.endswith(("expert_bias", "dt_bias")):
            step = 0.01 if name.endswith("expert_bias") else 0.1
            w[name] = w[name] + step * jax.random.normal(
                jax.random.PRNGKey(1000 + i), w[name].shape)
        if ".attn.w_" in name or ".kda.w_" in name or name.endswith("kda.conv"):
            w[name] = 8.0 * w[name]
    return rcfg, w


def batch(rows=2, seq=64, vocab=250):
    ids = jax.random.randint(jax.random.PRNGKey(1), (rows, seq), 0, vocab)
    labels = jnp.concatenate(
        [ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=-1)
    return ids, labels


def rel_gap(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return float(np.max(np.abs(a - b)) / (np.max(np.abs(b)) + 1e-12))


def reference_loss(w, ids, labels, rcfg):
    return jnp.sum(ref.loss_rows(w, (ids, labels), rcfg)) / jnp.sum(labels >= 0)


@pytest.fixture(scope="module")
def wanted():
    """The reference's logits, loss and gradients: one making for the cases."""
    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    ids, labels = batch()
    loss, grads = jax.jit(jax.value_and_grad(
        lambda w: reference_loss(w, ids, labels, rcfg)))(w)
    return w, jax.jit(lambda w: ref.logits(w, ids, rcfg))(w), loss, grads


HYPER = dict(lr=1e-3, wd=0.1, eps=1e-8)


@pytest.mark.parametrize("kernels,remat", [(True, "full_block")],
                         ids=["pallas-full_block"])
def test_float32_matches_the_reference_leaf_by_leaf(kernels, remat, wanted):
    """Logits, loss, every leaf's gradient and one optimizer step
    (``fused_adam`` through ``AmpOptimizer``, as the benchmark's runner steps
    the model, against the reference's AdamW); with the Pallas kernels
    (interpret mode: the rule's pair, the convolution's, flash at 128-wide
    keys against 64-wide values, the grouped products, the row movement)
    under per-block recomputation (their off-TPU paths each have their
    operator's own test: the suite's clock is tight).  float32 on
    both sides, two derivations of the same sums — the chunked rule against
    the token recurrence among them: 1e-5 on the loss, 1e-4 on the logits,
    1e-3 on a leaf's gradient and on the norm of a leaf's step, as in the
    other sparse families' tests."""
    import apex_tpu.amp as amp
    from apex_tpu import obs
    from apex_tpu.optimizers import fused_adam
    from benchmark.reference import common as C

    cfg = tiny_cfg(remat_policy=remat)
    w, want_logits, want_loss, want = wanted
    ids, labels = batch()
    amp_ = amp.initialize("O0")
    model = fam.program_model(fam.program_config(cfg, jnp.float32))
    opt = amp.AmpOptimizer(fused_adam(HYPER["lr"], eps=HYPER["eps"],
                                      weight_decay=HYPER["wd"]), amp_)
    params = fam.to_program(w, cfg)

    @jax.jit
    def step(p):
        state = opt.init(p)
        logits = model.apply({"params": p}, ids)
        loss, grads = jax.value_and_grad(lambda p: model.apply(
            {"params": opt.model_params(p)}, ids, labels=labels,
            deterministic=False)[1])(p)
        return logits, loss, grads, opt.step(grads, state, p)[0]

    with force_pallas(kernels):
        logits, loss, grads, stepped = step(params)
    reg = obs.default_registry()
    assert reg.get("kda.kernels").value == kernels
    assert reg.get("kda.conv_kernel").value == kernels
    assert rel_gap(logits, want_logits) < 1e-4
    assert abs(float(loss) - float(want_loss)) < 1e-5 * float(want_loss)
    got, moved = fam.from_program(grads, cfg), fam.from_program(stepped, cfg)
    assert set(got) == set(want)
    for name in want:
        assert rel_gap(got[name], want[name]) < 1e-3, name
        ref_step = C.adamw_step(w[name], want[name], 0.0, 0.0, 1, **HYPER)[0] - w[name]
        if not name.endswith("expert_bias"):    # (zero gradient, zero value)
            # (Adam's first step is lr * sign(g): a near-zero gradient's
            # sign is a full step, so the norms are compared, not the steps)
            got_norm = float(jnp.linalg.norm(moved[name] - w[name]))
            assert abs(got_norm - float(jnp.linalg.norm(ref_step))) \
                < 1e-2 * got_norm, name
    # every leaf is reached but the selection bias, which only selects
    assert all(np.asarray(g).any() for n, g in got.items()
               if not n.endswith("expert_bias"))


def _layer(cfg, held, shared=True):
    return ExpertShardMLP(
        num_experts=cfg["published"]["num_experts"], experts_held=held,
        d_ff=cfg["moe_intermediate_size"], k=cfg["num_experts_per_token"],
        shared_d_ff=(cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
                     if shared else 0),
        route_norm=True, route_scale=cfg["routed_scaling_factor"], tile_rows=8)


def test_the_shares_add_up():
    """The routed parts of all eight shares of a 16-expert layer (two experts
    each) plus the shared expert, counted once, are the uncut layer of the
    reference: the deployment's chips together compute the model."""
    whole = tiny_cfg(held=(0, 16))
    rcfg, w = seeded(whole)
    lw = {k[len("layers.1."):]: v for k, v in w.items()
          if k.startswith("layers.1.")}
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


@pytest.mark.parametrize("family", ["kimi_linear", "deepseek_v3"])
def test_latent_attention_rotates_only_where_the_configuration_says(family):
    """``LatentAttention`` under Kimi Linear's configuration (``rope_theta``
    None) is position-free: with no causal future to tell positions apart
    but the mask, swapping two EARLIER tokens leaves a later token's output
    as it was.  Under Moonlight's (a ``rope_theta``) the same swap moves it."""
    from apex_tpu.models import deepseek_v3, kimi_linear

    if family == "kimi_linear":
        cfg = kimi_linear.KimiLinearConfig.tiny(compute_dtype=jnp.float32)
        assert cfg.rope_theta is None
    else:
        cfg = deepseek_v3.DeepseekV3Config.tiny(compute_dtype=jnp.float32)
        assert cfg.rope_theta is not None
    mixer = deepseek_v3.LatentAttention(cfg)
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 16, cfg.hidden_size))
    params = mixer.init(jax.random.PRNGKey(1), x)
    params = jax.tree_util.tree_map(
        lambda p: 8.0 * p if p.ndim == 2 else p, params)
    swapped = x.at[:, 2].set(x[:, 5]).at[:, 5].set(x[:, 2])
    moved = rel_gap(mixer.apply(params, swapped)[:, 8:],
                    mixer.apply(params, x)[:, 8:])
    assert (moved < 1e-5) if family == "kimi_linear" else (moved > 1e-3)


def test_weights_round_trip_and_the_model_is_called_as_gptlm_is():
    """``to_program`` fuses q, k, v a head and gate | up and stacks the
    experts, ``from_program`` gives every reference leaf back; ``apply(ids)``
    -> logits, with labels -> (logits, loss); the scopes the readers look
    for are in the jaxpr; what the family does not build is refused."""
    from apex_tpu.models import KimiLinearConfig, KimiLinearLM

    cfg = tiny_cfg()
    rcfg, w = seeded(cfg)
    tree = fam.to_program(w, cfg)
    back = fam.from_program(tree, cfg)
    assert sorted(back) == sorted(w)
    assert all((np.asarray(back[k]) == np.asarray(w[k])).all() for k in w)
    qkv = np.asarray(tree["layer_0"]["kda"]["qkv_proj"]["kernel"])
    assert qkv.shape == (128, 2 * 3 * 128)          # per head [q | k | v]
    np.testing.assert_array_equal(qkv[:, 384 + 128:384 + 256],
                                  w["layers.0.kda.w_k"][:, 128:])
    assert "mlp" in tree["layer_0"] and "attn" in tree["layer_2"]
    assert fam.views(w)["layers.1.moe.experts.w_gate"].shape == (4, 128, 128)
    assert [fam.is_full(cfg, i) for i in range(3)] == [False, False, True]

    pcfg = KimiLinearConfig.tiny()
    assert [pcfg.is_full_attention(i) for i in range(3)] == [False, False, True]
    model = KimiLinearLM(pcfg)
    ids, labels = batch(rows=1, vocab=pcfg.vocab_size)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), ids)["params"])
    assert sorted(params) == ["embed", "head", "layer_0", "layer_1", "layer_2",
                              "norm_f"]
    out = lambda p: model.apply({"params": p}, ids, labels=labels)
    logits, loss = jax.eval_shape(out, params)
    assert logits.shape == (1, 64, pcfg.vocab_size) and loss.shape == ()
    text = str(jax.make_jaxpr(out)(params).pretty_print(name_stack=True))
    for scope in ("kda_proj", "kda_conv", "kda_gate", "kda_scan", "kda_out",
                  "mla_proj", "attn_full", "mla_out", "dense_ffn",
                  "moe_router", "lm_head"):
        assert scope in text, scope
    with pytest.raises(ValueError, match="outside"):
        KimiLinearLM(KimiLinearConfig.tiny(full_attn_layers=(4,))).init(
            jax.random.PRNGKey(0), ids)
    with pytest.raises(ValueError, match="split"):
        fam.program_config({**cfg, "linear_attn_config": {
            **cfg["linear_attn_config"], "kda_layers": [1]}}, jnp.float32)
    # the operations counted: the rule as the scalar rule's family counts
    # its own, attention at half the square
    flops = fam.forward_flops_per_token(cfg, 64)
    assert flops["kda_rule"] == 2 * 2 * 2 * (5 * 64 * 128 + 64 * 64 + 3 * 128 * 128)
    assert flops["attention"] == 2 * 2 * (128 + 64) * 32.5
    (f_ops, f_bytes), (b_ops, b_bytes) = fam.kda_needed(cfg, 64, 1)
    assert (b_ops, b_bytes) == (2 * f_ops, 2 * f_bytes)
    assert f_bytes == 64 * 2 * (8 * 128 + 4 * 128 + 4)


# -- the configuration file at its published widths ---------------------------

CONFIG = os.path.join(ROOT, "benchmark", "configs", "kimi-linear-48b-a3b.json")
CATALOG = "/opt/skills/guides/model-configs/architectures.jsonl"
_OURS = {"name", "source", "family", "num_dense_layers", "num_dense_layers_why",
         "reduced", "published", "reduced_why", "assumed", "deployment",
         "precision"}


def _config():
    import json

    with open(CONFIG) as f:
        return json.load(f)


@pytest.mark.parametrize("key", sorted(set(_config()) - _OURS))
def test_configuration_key_is_the_published_one(key):
    """Every key of the catalog row's ``config`` stands in the file under the
    same name with the published value, but the four cuts ``reduced`` names
    (whose published values ``published`` keeps); inside the one nested group
    that was cut only the two layer lists differ, no width."""
    import json

    if not os.path.exists(CATALOG):
        pytest.skip("no catalog beside the guides here")
    with open(CATALOG) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    cfg = _config()
    assert set(row["config"]) == set(cfg) - _OURS
    assert cfg["source"] == row["source_url"]
    if key not in cfg["reduced"]:
        assert cfg[key] == row["config"][key]
        return
    assert cfg[key] != row["config"][key] == cfg["published"][key]
    if key == "linear_attn_config":
        differing = {k for k, v in row["config"][key].items() if cfg[key][k] != v}
        assert differing == {"kda_layers", "full_attn_layers"}
        for name in differing:      # the first five published layers
            assert cfg[key][name] == [i for i in row["config"][key][name] if i <= 5]
