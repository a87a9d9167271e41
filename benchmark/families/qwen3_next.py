"""The ``qwen3_next`` family: Qwen3-Next configurations through the program's
``Qwen3NextLM``, and their plain reference.  A configuration file names this
module by ``"family": "qwen3_next"``.

As in ``families/afmoe.py`` the configuration is one chip's share of an
expert-parallel deployment: ``num_experts`` counts the routed experts HELD
here (``assumed.experts_held`` names them), ``published.num_experts`` the
experts the router scores, and ``vocab_size`` the slice of the vocabulary held.

Beside ``train_flops_per_token`` stand the operations and bytes the kernels
need (:func:`grouped_mm_needed`, :func:`gdn_needed`), from which the roofline
readers in ``layer_metrics/`` work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# The program's model, asked for at once: a checkout that cannot run this
# family (an older commit) then fails before the reference is computed.
import apex_tpu.models.qwen3_next  # noqa: F401
# What the two sparse-expert families share, from the older one: the leaves
# that are compared (a layer's held experts' matrices taken TOGETHER — here a
# held expert sees 160 rows a step, a third of Trinity's), the grouped
# products' needs (the same keys; here the WEIGHTS' bytes bound every product:
# 160 rows an expert against 2048 x 1024 of weights) and the roofline's time.
from benchmark.families.afmoe import (  # noqa: F401
    grouped_mm_needed, needed_seconds, views)
from benchmark.reference import qwen3_next as reference  # noqa: F401 (the family's reference)

#: tokens of a chunk of the chunked delta rule whose operations
#: :func:`gdn_needed` counts (the program's own default)
GDN_CHUNK = 64


def _held(cfg: Dict) -> Tuple[int, int]:
    lo, hi = cfg["assumed"]["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("assumed.experts_held does not hold num_experts experts")
    return int(lo), int(hi)


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.qwen3_next import Qwen3NextConfig

    return Qwen3NextConfig(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg["shared_expert_intermediate_size"],
        num_experts=cfg["published"]["num_experts"], experts_held=_held(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        remat_policy=cfg["assumed"].get("remat_policy", "none"),
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.qwen3_next import Qwen3NextLM

    return Qwen3NextLM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis, the experts held and the experts routed over."""
    if cfg["mlp_only_layers"] or cfg["decoder_sparse_step"] != 1:
        raise ValueError("every layer of this family's program has experts")
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"],
            "experts_held": list(_held(cfg)),
            "experts_routed_over": cfg["published"]["num_experts"]}


def is_full(cfg: Dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


_GDN = (("in_proj_qkvz", "kernel", "gdn.w_qkvz"), ("in_proj_ba", "kernel", "gdn.w_ba"),
        ("out_proj", "kernel", "gdn.w_out"))
_GDN_LEAVES = (("conv", "gdn.conv"), ("A_log", "gdn.A_log"),
               ("dt_bias", "gdn.dt_bias"), ("norm", "gdn.norm"))
_QGKV = ("attn.w_q", "attn.w_k", "attn.w_v")


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree.  The
    delta net's ``in_proj_qkvz`` / ``in_proj_ba`` keep the published
    per-key-head layout; the program keeps query|gate, keys and values as
    ONE matrix (the per-head query|gate layout first), gate and up as one,
    and the held experts' matrices stacked."""
    import jax.numpy as jnp

    cat = lambda names: jnp.concatenate([w[n] for n in names], axis=-1)
    tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
            "norm_f": {"scale": w["norm_f"]}}
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        layer = {"input_norm": {"scale": w[h + "norm_in"]},
                 "post_attn_norm": {"scale": w[h + "norm_post"]}}
        if is_full(cfg, i):
            layer["attn"] = {
                "qgkv": {"kernel": cat([h + n for n in _QGKV])},
                "o_proj": {"kernel": w[h + "attn.w_o"]},
                "q_norm": {"scale": w[h + "attn.q_norm"]},
                "k_norm": {"scale": w[h + "attn.k_norm"]}}
        else:
            layer["gdn"] = {
                **{prog: {leaf: w[h + ref]} for prog, leaf, ref in _GDN},
                **{prog: w[h + ref] for prog, ref in _GDN_LEAVES}}
        experts = [h + f"moe.experts.{e}." for e in range(*_held(cfg))]
        layer["moe"] = {
            "router": w[h + "moe.router"],
            "wi": jnp.stack([cat([x + "w_gate", x + "w_up"]) for x in experts]),
            "wo": jnp.stack([w[x + "w_down"] for x in experts]),
            "shared": {
                "gate_up": {"kernel": cat([h + "shared.w_gate", h + "shared.w_up"])},
                "down": {"kernel": w[h + "shared.w_down"]}},
            "shared_gate": w[h + "shared.gate"]}
        tree[f"layer_{i}"] = layer
    return tree


def from_program(tree: Dict, cfg: Dict) -> Dict:
    """A tree shaped like the program's parameters under the reference's
    leaf names, the fused matrices split back into their parts."""
    import jax.numpy as jnp

    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    w = {"embed": tree["embed"]["embedding"], "head": tree["head"]["kernel"],
         "norm_f": tree["norm_f"]["scale"]}
    for i in range(cfg["num_hidden_layers"]):
        h, t = f"layers.{i}.", tree[f"layer_{i}"]
        w[h + "norm_in"] = t["input_norm"]["scale"]
        w[h + "norm_post"] = t["post_attn_norm"]["scale"]
        if "attn" in t:
            a = t["attn"]
            cuts = [2 * hq * hd, (2 * hq + hk) * hd]
            for name, part in zip(_QGKV, jnp.split(a["qgkv"]["kernel"], cuts, -1)):
                w[h + name] = part
            w[h + "attn.w_o"] = a["o_proj"]["kernel"]
            w[h + "attn.q_norm"] = a["q_norm"]["scale"]
            w[h + "attn.k_norm"] = a["k_norm"]["scale"]
        else:
            g = t["gdn"]
            for prog, leaf, ref in _GDN:
                w[h + ref] = g[prog][leaf]
            for prog, ref in _GDN_LEAVES:
                w[h + ref] = g[prog]
        m = t["moe"]
        w[h + "moe.router"] = m["router"]
        for j, e in enumerate(range(*_held(cfg))):
            x = h + f"moe.experts.{e}."
            w[x + "w_gate"], w[x + "w_up"] = jnp.split(m["wi"][j], 2, axis=-1)
            w[x + "w_down"] = m["wo"][j]
        w[h + "shared.w_gate"], w[h + "shared.w_up"] = jnp.split(
            m["shared"]["gate_up"]["kernel"], 2, axis=-1)
        w[h + "shared.w_down"] = m["shared"]["down"]["kernel"]
        w[h + "shared.gate"] = m["shared_gate"]
    return w


#: no leaf's gradient is identically zero
ZERO_GRADIENT_SUFFIX = None


# -- operations the model requires ------------------------------------------

def _gdn_sizes(cfg: Dict):
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    return hk, hv, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]


def gdn_rule_flops_per_token(cfg: Dict, chunk: int = GDN_CHUNK) -> float:
    """Forward operations a token of ONE delta-net layer needs in the
    chunked rule at chunks of ``chunk``: per value head five ``C x d``
    products a token (K K^T, T (beta exp(G) K), T (beta V), Q K^T, (M . Q
    K^T) U), the triangular system's ``C^2``, and the three products with
    the state ``d_k x d_v``."""
    _, hv, dk, dv = _gdn_sizes(cfg)
    d = (dk + dv) / 2
    return hv * 2 * (5 * chunk * d + chunk * chunk + 3 * dk * dv)


def forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """Matrix multiplications, attention and the delta rule one token's
    forward pass requires here, by part.  The routed experts count the
    EXPECTED experts a token finds held here, ``k * held / routed_over``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv, dk, dv = _gdn_sizes(cfg)
    f, fs = cfg["moe_intermediate_size"], cfg["shared_expert_intermediate_size"]
    routed_over = cfg["published"]["num_experts"]
    layers = cfg["num_hidden_layers"]
    n_full = sum(is_full(cfg, i) for i in range(layers))
    n_gdn = layers - n_full
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed_over
    return {
        "gdn_proj": n_gdn * (2 * d * (2 * hk * dk + 2 * hv * dv)
                             + 2 * d * 2 * hv + 2 * hv * dv * d),
        "gdn_conv": n_gdn * 2 * cfg["linear_conv_kernel_dim"] * (
            2 * hk * dk + hv * dv),
        "gdn_rule": n_gdn * gdn_rule_flops_per_token(cfg),
        "attn_proj": n_full * (2 * d * (2 * hq + 2 * hkv) * hd
                               + 2 * hq * hd * d),
        "attention": n_full * 4 * hq * hd * (seq + 1) / 2,
        "router": layers * 2 * d * routed_over,
        "shared": layers * (6 * d * fs + 2 * d),
        "routed": layers * expected * 6 * d * f,
        "head": 2 * d * cfg["assumed"]["padded_vocab_size"],
    }


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation is not
    counted."""
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def gdn_needed(cfg: Dict, seq: int, rows: int, chunk: int = GDN_CHUNK
               ) -> List[Tuple[float, float]]:
    """``(flops, bytes)`` ONE delta-net layer's rule needs for the forward
    and the backward pass (twice the forward's operations; the forward
    recomputed under per-block recomputation is not needed): the chunked
    rule's operations at chunks of ``chunk``, and q, k, v (in the compute
    dtype, at the value heads), g, beta (float32) and o — backward: and
    their gradients — crossing HBM once."""
    _, hv, dk, dv = _gdn_sizes(cfg)
    tokens = rows * seq
    fwd = tokens * gdn_rule_flops_per_token(cfg, chunk)
    nbytes = tokens * hv * (2 * (2 * dk + 2 * dv) + 4 * 2)
    return [(fwd, nbytes), (2 * fwd, 2 * nbytes)]
