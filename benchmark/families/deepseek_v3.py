"""The ``deepseek_v3`` family: Moonlight configurations through the program's
``DeepseekV3LM``, and their plain reference.  A configuration file names this
module by ``"family": "deepseek_v3"``.

As in ``families/afmoe.py`` the configuration is one chip's share of an
expert-parallel deployment: ``n_routed_experts`` counts the routed experts
HELD here (``assumed.experts_held`` names them), ``published.n_routed_experts``
the experts the router scores, and ``vocab_size`` the slice of the vocabulary
held.

Beside ``train_flops_per_token`` stand the operations and bytes the kernels
need (:func:`grouped_mm_needed`, :func:`flash_needed`), from which the roofline
readers in ``layer_metrics/`` work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# The program's model, asked for at once: a checkout that cannot run this
# family (an older commit) then fails before the reference is computed.
import apex_tpu.models.deepseek_v3  # noqa: F401
# What the sparse-expert families share, from the oldest: the leaves that are
# compared (a layer's held experts' matrices taken TOGETHER), the grouped
# products' needs (:func:`grouped_mm_needed` below) and the roofline's time.
from benchmark.families import afmoe
from benchmark.families.afmoe import (  # noqa: F401
    mean_keys, needed_seconds, views)
from benchmark.reference import deepseek_v3 as reference  # noqa: F401 (the family's reference)


def _held(cfg: Dict) -> Tuple[int, int]:
    lo, hi = cfg["assumed"]["experts_held"]
    if hi - lo != cfg["n_routed_experts"]:
        raise ValueError("assumed.experts_held does not hold n_routed_experts "
                         "experts")
    return int(lo), int(hi)


def _routed_over(cfg: Dict) -> int:
    return cfg["published"]["n_routed_experts"]


def grouped_mm_needed(cfg: Dict, tokens: int):
    """``families/afmoe.py``'s count under this family's published names
    (here 768 rows an expert: compute-bound, as Trinity's)."""
    return afmoe.grouped_mm_needed(
        {**cfg, "num_experts": cfg["n_routed_experts"],
         "published": {"num_experts": _routed_over(cfg)}}, tokens)


def _check(cfg: Dict) -> None:
    """What this family's program and reference do not do."""
    if cfg["q_lora_rank"] is not None:
        raise ValueError("queries through a low-rank latent are not built")
    if (cfg["n_group"], cfg["topk_group"], cfg["moe_layer_freq"]) != (1, 1, 1):
        raise ValueError("group-limited routing and expert layers that "
                         "alternate with dense ones are not built")
    if (cfg["scoring_func"], cfg["topk_method"]) != ("sigmoid", "noaux_tc"):
        raise ValueError("only sigmoid scores under a selection bias")
    if cfg["num_nextn_predict_layers"] or cfg["tie_word_embeddings"]:
        raise ValueError("no multi-token-prediction module, no tied head")
    if cfg["first_k_dense_replace"] != cfg["num_dense_layers"]:
        raise ValueError("num_dense_layers is not first_k_dense_replace")


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.deepseek_v3 import DeepseekV3Config

    _check(cfg)
    return DeepseekV3Config(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_heads=cfg["num_attention_heads"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
        rope_theta=float(cfg["rope_theta"]),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        n_routed_experts=_routed_over(cfg), experts_held=_held(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        rms_norm_eps=cfg["rms_norm_eps"],
        latent_norm_eps=cfg["assumed"]["latent_norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        remat_policy=cfg["assumed"].get("remat_policy", "none"),
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.deepseek_v3 import DeepseekV3LM

    return DeepseekV3LM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis, the experts held and the experts routed over."""
    _check(cfg)
    if cfg["assumed"]["latent_norm_eps"] != reference.LATENT_NORM_EPS:
        raise ValueError("the reference norms the latent at another eps")
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"],
            "experts_held": list(_held(cfg)),
            "experts_routed_over": _routed_over(cfg)}


def _sizes(cfg: Dict):
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def _rotary_columns(w_q, w_dkv, cfg: Dict, to_program_order: bool):
    """The rotary columns of ``W_q`` (each head's last ``qk_rope_head_dim``)
    and of ``W_dkv`` (its last ``qk_rope_head_dim``) between the published
    order — adjacent dims ``(2j, 2j + 1)`` are a pair — and the program's —
    evens first, then odds, so that the two HALVES are the pairs
    (``models/deepseek_v3.py``).  A permutation of columns: the scores, which
    sum over them, do not see it."""
    import numpy as np

    h, dn, dr, _, r = _sizes(cfg)
    perm = np.concatenate([np.arange(0, dr, 2), np.arange(1, dr, 2)])
    if not to_program_order:
        perm = np.argsort(perm)
    d = w_q.shape[0]
    q = w_q.reshape(d, h, dn + dr)
    q = q.at[:, :, dn:].set(q[:, :, dn:][:, :, perm])
    return (q.reshape(d, h * (dn + dr)),
            w_dkv.at[:, r:].set(w_dkv[:, r:][:, perm]))


_NORMS = (("input_norm", "norm_in"), ("post_attn_norm", "norm_post"))
_ATTN = (("kv_b_proj", "kernel", "attn.w_ukv"), ("o_proj", "kernel", "attn.w_o"),
         ("kv_a_norm", "scale", "attn.latent_norm"))


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree: the
    rotary columns de-interleaved, gate and up as one matrix, the held
    experts' matrices stacked."""
    import jax.numpy as jnp

    cat = lambda names: jnp.concatenate([w[n] for n in names], axis=-1)
    tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
            "norm_f": {"scale": w["norm_f"]}}
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        layer = {prog: {"scale": w[h + ref]} for prog, ref in _NORMS}
        w_q, w_dkv = _rotary_columns(w[h + "attn.w_q"], w[h + "attn.w_dkv"],
                                     cfg, True)
        layer["attn"] = {"q_proj": {"kernel": w_q}, "kv_a_proj": {"kernel": w_dkv},
                         **{prog: {leaf: w[h + ref]} for prog, leaf, ref in _ATTN}}
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
    leaf names, the fused matrices split back into their parts and the
    rotary columns back in the published order."""
    import jax.numpy as jnp

    w = {"embed": tree["embed"]["embedding"], "head": tree["head"]["kernel"],
         "norm_f": tree["norm_f"]["scale"]}
    for i in range(cfg["num_hidden_layers"]):
        h, t = f"layers.{i}.", tree[f"layer_{i}"]
        for prog, ref in _NORMS:
            w[h + ref] = t[prog]["scale"]
        a = t["attn"]
        w[h + "attn.w_q"], w[h + "attn.w_dkv"] = _rotary_columns(
            a["q_proj"]["kernel"], a["kv_a_proj"]["kernel"], cfg, False)
        for prog, leaf, ref in _ATTN:
            w[h + ref] = a[prog][leaf]

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

def forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """Matrix multiplications and attention one token's forward pass requires
    here, by part.  Attention counts scores over ``d_qk`` and values over
    ``d_v`` — the true head sizes; the routed experts count the EXPECTED
    experts a token finds held here, ``k * held / routed_over``."""
    d = cfg["hidden_size"]
    h, dn, dr, dv, r = _sizes(cfg)
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    layers, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    n_moe = layers - n_dense
    routed_over = _routed_over(cfg)
    expected = cfg["num_experts_per_tok"] * cfg["n_routed_experts"] / routed_over
    return {
        "attn_proj": layers * (2 * d * h * (dn + dr) + 2 * d * (r + dr)
                               + 2 * r * h * (dn + dv) + 2 * h * dv * d),
        "attention": layers * 2 * h * (dn + dr + dv) * mean_keys(seq),
        "dense_mlp": n_dense * 6 * d * fd,
        "router": n_moe * 2 * d * routed_over,
        "shared": n_moe * 6 * d * f * cfg["n_shared_experts"],
        "routed": n_moe * expected * 6 * d * f,
        "head": 2 * d * cfg["assumed"]["padded_vocab_size"],
    }


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation is not
    counted."""
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def flash_needed(cfg: Dict, seq: int, rows: int, itemsize: int = 2
                 ) -> List[Tuple[float, float]]:
    """``(flops, bytes)`` ONE layer's attention needs for the forward and the
    backward pass (twice the forward's operations), by the TRUE head sizes
    whatever a program pads: ``2 H (d_qk + d_v)`` operations a query a key it
    may see; q, k (``d_qk`` wide), v, o (``d_v`` wide) — backward: and their
    gradients — crossing HBM once."""
    h, dn, dr, dv, _ = _sizes(cfg)
    fwd = rows * seq * 2 * h * (dn + dr + dv) * mean_keys(seq)
    nbytes = itemsize * rows * seq * h * 2 * (dn + dr + dv)
    return [(fwd, nbytes), (2 * fwd, 2 * nbytes)]
