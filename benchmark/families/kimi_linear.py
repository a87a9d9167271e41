"""The ``kimi_linear`` family: Kimi-Linear configurations through the
program's ``KimiLinearLM``, and their plain reference.  A configuration file
names this module by ``"family": "kimi_linear"``.

As in ``families/afmoe.py`` the configuration is one chip's share of an
expert-parallel deployment: ``num_experts`` counts the routed experts HELD
here (``assumed.experts_held`` names them), ``published.num_experts`` the
experts the router scores, and ``vocab_size`` the slice of the vocabulary
held.  Which layers mix by KDA and which by latent attention is the published
pair of 1-indexed lists inside ``linear_attn_config``, cut to the layers held.

Beside ``train_flops_per_token`` stand the operations and bytes the kernels
need (:func:`grouped_mm_needed`, :func:`flash_needed`, :func:`kda_needed`),
from which the roofline readers in ``layer_metrics/`` work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# The program's model, asked for at once: a checkout that cannot run this
# family (an older commit) then fails before the reference is computed.
import apex_tpu.models.kimi_linear  # noqa: F401
# What the sparse-expert families share, from the oldest: the leaves that are
# compared (a layer's held experts' matrices taken TOGETHER), the grouped
# products' needs (:func:`grouped_mm_needed` below) and the roofline's time.
from benchmark.families import afmoe
from benchmark.families.afmoe import (  # noqa: F401
    mean_keys, needed_seconds, views)
# One latent layer's attention, by the true head sizes: the older latent
# family's count (the same published keys; what is not rotated changes no
# product)
from benchmark.families.deepseek_v3 import flash_needed  # noqa: F401
from benchmark.reference import kimi_linear as reference  # noqa: F401 (the family's reference)

#: tokens of a chunk of the chunked rule whose operations :func:`kda_needed`
#: counts (the program's own default)
KDA_CHUNK = 64


def _held(cfg: Dict) -> Tuple[int, int]:
    lo, hi = cfg["assumed"]["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("assumed.experts_held does not hold num_experts experts")
    return int(lo), int(hi)


def _routed_over(cfg: Dict) -> int:
    return cfg["published"]["num_experts"]


def is_full(cfg: Dict, i: int) -> bool:
    """Whether layer ``i`` (0-indexed) mixes by latent attention."""
    return reference.is_full(cfg, i)


def _layer_counts(cfg: Dict) -> Tuple[int, int]:
    """``(KDA layers, latent-attention layers)``."""
    full = sum(is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - full, full


def grouped_mm_needed(cfg: Dict, tokens: int):
    """``families/afmoe.py``'s count under this family's published names
    (here 256 rows an expert: the weights' bytes bound every product)."""
    return afmoe.grouped_mm_needed(
        {**cfg, "num_experts_per_tok": cfg["num_experts_per_token"],
         "published": {"num_experts": _routed_over(cfg)}}, tokens)


def _check(cfg: Dict) -> None:
    """What this family's program and reference do not do."""
    lin = cfg["linear_attn_config"]
    layers = set(range(1, cfg["num_hidden_layers"] + 1))
    if (set(lin["kda_layers"]) | set(lin["full_attn_layers"]) != layers
            or set(lin["kda_layers"]) & set(lin["full_attn_layers"])):
        raise ValueError("kda_layers and full_attn_layers do not split layers "
                         f"1..{cfg['num_hidden_layers']} between them")
    if cfg["q_lora_rank"] is not None or not cfg["mla_use_nope"]:
        raise ValueError("only position-free latent attention with full-rank "
                         "queries is built")
    if (cfg["num_expert_group"], cfg["topk_group"], cfg["moe_layer_freq"]) != (1, 1, 1):
        raise ValueError("group-limited routing and expert layers that "
                         "alternate with dense ones are not built")
    if cfg["moe_router_activation_func"] != "sigmoid":
        raise ValueError("only sigmoid scores under a selection bias")
    if cfg["num_nextn_predict_layers"] or cfg["tie_word_embeddings"]:
        raise ValueError("no multi-token-prediction module, no tied head")
    if cfg["first_k_dense_replace"] != cfg["num_dense_layers"]:
        raise ValueError("num_dense_layers is not first_k_dense_replace")
    if (lin["num_heads"], cfg["num_key_value_heads"]) != (
            cfg["num_attention_heads"],) * 2:
        raise ValueError("KDA's heads and the latent layer's are one count")


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.kimi_linear import KimiLinearConfig

    _check(cfg)
    lin = cfg["linear_attn_config"]
    return KimiLinearConfig(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        full_attn_layers=tuple(lin["full_attn_layers"]),
        first_k_dense_replace=cfg["first_k_dense_replace"],
        kda_num_heads=lin["num_heads"], kda_head_dim=lin["head_dim"],
        short_conv_kernel_size=lin["short_conv_kernel_size"],
        kda_gate_rank=lin["head_dim"],
        num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=None,
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=_routed_over(cfg), experts_held=_held(cfg),
        num_experts_per_tok=cfg["num_experts_per_token"],
        n_shared_experts=cfg["num_shared_experts"],
        norm_topk_prob=cfg["moe_renormalize"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        latent_norm_eps=cfg["assumed"]["latent_norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        remat_policy=cfg["assumed"].get("remat_policy", "none"),
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.kimi_linear import KimiLinearLM

    return KimiLinearLM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis, the experts held and the experts routed over."""
    from benchmark.reference.deepseek_v3 import LATENT_NORM_EPS

    _check(cfg)
    if cfg["assumed"]["latent_norm_eps"] != LATENT_NORM_EPS:
        raise ValueError("the reference norms the latent at another eps")
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"],
            "experts_held": list(_held(cfg)),
            "experts_routed_over": _routed_over(cfg)}


_NORMS = (("input_norm", "norm_in"), ("post_attn_norm", "norm_post"))
_ATTN = (("q_proj", "kernel", "attn.w_q"), ("kv_a_proj", "kernel", "attn.w_dkv"),
         ("kv_b_proj", "kernel", "attn.w_ukv"), ("o_proj", "kernel", "attn.w_o"),
         ("kv_a_norm", "scale", "attn.latent_norm"))
_KDA = (("f_a_proj", "kda.w_fa"), ("f_b_proj", "kda.w_fb"),
        ("g_a_proj", "kda.w_ga"), ("g_b_proj", "kda.w_gb"),
        ("b_proj", "kda.w_b"), ("o_proj", "kda.w_o"))
_KDA_LEAVES = (("conv", "kda.conv"), ("A_log", "kda.A_log"),
               ("dt_bias", "kda.dt_bias"), ("norm", "kda.norm"))
_QKV = ("kda.w_q", "kda.w_k", "kda.w_v")


def _kda_heads(cfg: Dict) -> Tuple[int, int]:
    return reference.kda_sizes(cfg)[:2]


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree: KDA's
    three projections as ONE matrix laid out per head ``[q | k | v]``, gate
    and up as one matrix, the held experts' matrices stacked.  Nothing is
    rotated, so the latent layer's columns stay as published."""
    import jax.numpy as jnp

    cat = lambda names: jnp.concatenate([w[n] for n in names], axis=-1)
    hk, hd = _kda_heads(cfg)
    tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
            "norm_f": {"scale": w["norm_f"]}}
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        layer = {prog: {"scale": w[h + ref]} for prog, ref in _NORMS}
        if is_full(cfg, i):
            layer["attn"] = {prog: {leaf: w[h + ref]} for prog, leaf, ref in _ATTN}
        else:
            qkv = jnp.stack([w[h + n].reshape(-1, hk, hd) for n in _QKV], axis=2)
            layer["kda"] = {
                "qkv_proj": {"kernel": qkv.reshape(-1, hk * 3 * hd)},
                **{prog: {"kernel": w[h + ref]} for prog, ref in _KDA},
                **{prog: w[h + ref] for prog, ref in _KDA_LEAVES}}
        swiglu = lambda p: {
            "gate_up": {"kernel": cat([h + p + "w_gate", h + p + "w_up"])},
            "down": {"kernel": w[h + p + "w_down"]}}
        if i < cfg["first_k_dense_replace"]:
            layer["mlp"] = swiglu("mlp.")
        else:
            experts = [h + f"moe.experts.{e}." for e in range(*_held(cfg))]
            layer["moe"] = {
                "router": w[h + "moe.router"],
                "expert_bias": w[h + "moe.expert_bias"],
                "wi": jnp.stack([cat([x + "w_gate", x + "w_up"]) for x in experts]),
                "wo": jnp.stack([w[x + "w_down"] for x in experts]),
                "shared": swiglu("shared.")}
        tree[f"layer_{i}"] = layer
    return tree


def from_program(tree: Dict, cfg: Dict) -> Dict:
    """A tree shaped like the program's parameters under the reference's
    leaf names, the fused matrices split back into their parts."""
    import jax.numpy as jnp

    hk, hd = _kda_heads(cfg)
    w = {"embed": tree["embed"]["embedding"], "head": tree["head"]["kernel"],
         "norm_f": tree["norm_f"]["scale"]}
    for i in range(cfg["num_hidden_layers"]):
        h, t = f"layers.{i}.", tree[f"layer_{i}"]
        for prog, ref in _NORMS:
            w[h + ref] = t[prog]["scale"]
        if "attn" in t:
            for prog, leaf, ref in _ATTN:
                w[h + ref] = t["attn"][prog][leaf]
        else:
            a = t["kda"]
            qkv = a["qkv_proj"]["kernel"].reshape(-1, hk, 3, hd)
            for j, name in enumerate(_QKV):
                w[h + name] = qkv[:, :, j].reshape(-1, hk * hd)
            for prog, ref in _KDA:
                w[h + ref] = a[prog]["kernel"]
            for prog, ref in _KDA_LEAVES:
                w[h + ref] = a[prog]

        def swiglu(p, m):
            w[h + p + "w_gate"], w[h + p + "w_up"] = jnp.split(
                m["gate_up"]["kernel"], 2, axis=-1)
            w[h + p + "w_down"] = m["down"]["kernel"]
        if "mlp" in t:
            swiglu("mlp.", t["mlp"])
            continue
        m = t["moe"]
        w[h + "moe.router"], w[h + "moe.expert_bias"] = m["router"], m["expert_bias"]
        for j, e in enumerate(range(*_held(cfg))):
            x = h + f"moe.experts.{e}."
            w[x + "w_gate"], w[x + "w_up"] = jnp.split(m["wi"][j], 2, axis=-1)
            w[x + "w_down"] = m["wo"][j]
        swiglu("shared.", m["shared"])
    return w


#: no leaf's gradient is identically zero but the selection bias's, whose
#: change is zero on both sides (zero gradient, zero value: AdamW leaves it)
ZERO_GRADIENT_SUFFIX = None


# -- operations the model requires ------------------------------------------

def kda_rule_flops_per_token(cfg: Dict, chunk: int = KDA_CHUNK) -> float:
    """Forward operations a token of ONE KDA layer needs in the chunked rule
    at chunks of ``chunk``, as ``families/qwen3_next.py`` counts the scalar
    rule's: per head five ``C x d`` products a token (the two decayed score
    products ``A`` and ``P`` — ``C d`` each however they are sub-blocked —,
    ``T (beta exp(G) K)``, ``T (beta V)``, ``P U``), the triangular system's
    ``C^2``, and the three products with the state ``d x d``.  Nothing for
    the channel-wise work of the VPU."""
    hk, hd = _kda_heads(cfg)
    return hk * 2 * (5 * chunk * hd + chunk * chunk + 3 * hd * hd)


def forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """Matrix multiplications, attention and the delta rule one token's
    forward pass requires here, by part.  Causal attention counts half the
    square; the routed experts count the EXPECTED experts a token finds held
    here, ``k * held / routed_over``."""
    d = cfg["hidden_size"]
    h, dn, dr, dv, r = reference.sizes(cfg)
    hk, hd = _kda_heads(cfg)
    rank = hd
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    layers, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    n_kda, n_full = _layer_counts(cfg)
    n_moe = layers - n_dense
    routed_over = _routed_over(cfg)
    expected = cfg["num_experts_per_token"] * cfg["num_experts"] / routed_over
    return {
        "kda_proj": n_kda * (2 * d * 3 * hk * hd + 2 * hk * hd * d
                             + 2 * (2 * d * rank + 2 * rank * hk * hd)
                             + 2 * d * hk),
        "kda_conv": n_kda * 2 * cfg["linear_attn_config"][
            "short_conv_kernel_size"] * 3 * hk * hd,
        "kda_rule": n_kda * kda_rule_flops_per_token(cfg),
        "attn_proj": n_full * (2 * d * h * (dn + dr) + 2 * d * (r + dr)
                               + 2 * r * h * (dn + dv) + 2 * h * dv * d),
        "attention": n_full * 2 * h * (dn + dr + dv) * mean_keys(seq),
        "dense_mlp": n_dense * 6 * d * fd,
        "router": n_moe * 2 * d * routed_over,
        "shared": n_moe * 6 * d * f * cfg["num_shared_experts"],
        "routed": n_moe * expected * 6 * d * f,
        "head": 2 * d * cfg["assumed"]["padded_vocab_size"],
    }


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation is not
    counted."""
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def kda_needed(cfg: Dict, seq: int, rows: int, chunk: int = KDA_CHUNK
               ) -> List[Tuple[float, float]]:
    """``(flops, bytes)`` ONE KDA layer's rule needs for the forward and the
    backward pass, whatever implements it.  Operations: per token and head,
    forward, the products of the chunked form at chunks of ``chunk`` = C,
    ``2 (5 C d + C^2 + 3 d^2)`` (:func:`kda_rule_flops_per_token`: the two
    decayed score products at ``C d`` each however they are sub-blocked,
    nothing for the VPU's channel-wise work); backward twice that; the
    forward recomputed under per-block recomputation is not needed.  Bytes:
    q, k, v, o in the compute dtype (2 bytes), the log-decay ``g`` in float32
    at (S, H, d), beta in float32 — backward: and each one's gradient —
    crossing HBM once."""
    hk, hd = _kda_heads(cfg)
    tokens = rows * seq
    fwd = tokens * kda_rule_flops_per_token(cfg, chunk)
    nbytes = tokens * hk * (2 * 4 * hd + 4 * hd + 4)
    return [(fwd, nbytes), (2 * fwd, 2 * nbytes)]
