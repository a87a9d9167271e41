"""The ``smallthinker`` family: SmallThinker configurations through the
program's ``SmallThinkerLM``, and their plain reference.  A configuration file
names this module by ``"family": "smallthinker"``.

As in ``families/afmoe.py`` the configuration is one chip's share of an
expert-parallel deployment: ``moe_num_primary_experts`` counts the routed
experts HELD here (``assumed.experts_held`` names them),
``published.moe_num_primary_experts`` the experts the router scores, and
``vocab_size`` the slice of the vocabulary held.

The published file says which layers take a window and which rotate q and k
in two lists of flags, ``sliding_window_layout`` and ``rope_layout``.  The
accepted readers of ``layer_metrics/`` read ``layer_types``,
``sliding_window`` and ``num_dense_layers`` from the file, so it carries those
as DERIVED keys, and :func:`_check` refuses a file in which a derived key and
its source differ.

Beside ``train_flops_per_token`` stand the operations and bytes the kernels
need (:func:`grouped_mm_needed`, :func:`flash_needed`), from which the
roofline readers work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# The program's model, asked for at once: a checkout that cannot run this
# family (an older commit) then fails before the reference is computed.
import apex_tpu.models.smallthinker  # noqa: F401
# What the sparse-expert families share, from the oldest: the leaves that are
# compared (a layer's held experts' matrices taken TOGETHER), the keys a query
# sees, the grouped products' and the flash calls' needs and the roofline's time.
from benchmark.families import afmoe
from benchmark.families.afmoe import (  # noqa: F401
    WINDOW, flash_needed, mean_keys, needed_seconds, views)
from benchmark.reference import smallthinker as reference  # noqa: F401 (the family's reference)

FULL = "full_attention"


def _held(cfg: Dict) -> Tuple[int, int]:
    lo, hi = cfg["assumed"]["experts_held"]
    if hi - lo != cfg["moe_num_primary_experts"]:
        raise ValueError("assumed.experts_held does not hold "
                         "moe_num_primary_experts experts")
    return int(lo), int(hi)


def _routed_over(cfg: Dict) -> int:
    return cfg["published"]["moe_num_primary_experts"]


def _check(cfg: Dict) -> None:
    """The derived keys say what their sources say, and nothing is asked for
    that this family's program and reference do not do."""
    layout = cfg["sliding_window_layout"]
    if len(layout) != cfg["num_hidden_layers"] or len(cfg["rope_layout"]) != len(layout):
        raise ValueError("sliding_window_layout and rope_layout do not name "
                         "num_hidden_layers layers")
    if cfg["layer_types"] != [WINDOW if w else FULL for w in layout]:
        raise ValueError("layer_types is not sliding_window_layout's")
    if cfg["sliding_window"] != cfg["sliding_window_size"]:
        raise ValueError("sliding_window is not sliding_window_size")
    if cfg["num_dense_layers"] != 0:
        raise ValueError("every layer has routed experts: num_dense_layers is 0")
    if not cfg["moe_primary_router_apply_softmax"] or cfg["rope_scaling"]:
        raise ValueError("only a softmax over the picked logits and unscaled "
                         "rotary positions are built")
    if cfg["tie_word_embeddings"]:
        raise ValueError("no tied head")


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.smallthinker import SmallThinkerConfig

    _check(cfg)
    return SmallThinkerConfig(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"],
        sliding_window_layout=tuple(cfg["sliding_window_layout"]),
        rope_layout=tuple(cfg["rope_layout"]),
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window_size=cfg["sliding_window_size"],
        rope_theta=float(cfg["rope_theta"]),
        moe_ffn_hidden_size=cfg["moe_ffn_hidden_size"],
        num_experts=_routed_over(cfg), experts_held=_held(cfg),
        num_experts_per_tok=cfg["moe_num_active_primary_experts"],
        norm_topk_prob=cfg["norm_topk_prob"],
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        remat_policy=cfg["assumed"].get("remat_policy", "none"),
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.smallthinker import SmallThinkerLM

    return SmallThinkerLM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis, the experts held and the experts routed over."""
    _check(cfg)
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"],
            "experts_held": list(_held(cfg)),
            "experts_routed_over": _routed_over(cfg)}


_NORMS = (("input_norm", "norm_in"), ("post_attn_norm", "norm_post"))
_QKV = ("attn.w_q", "attn.w_k", "attn.w_v")


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree (the
    program keeps q, k and v as one matrix, gate and up as one, and the held
    experts' matrices stacked)."""
    import jax.numpy as jnp

    cat = lambda names: jnp.concatenate([w[n] for n in names], axis=-1)
    tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
            "norm_f": {"scale": w["norm_f"]}}
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        layer = {prog: {"scale": w[h + ref]} for prog, ref in _NORMS}
        layer["qkv"] = {"kernel": cat([h + n for n in _QKV])}
        layer["o_proj"] = {"kernel": w[h + "attn.w_o"]}
        experts = [h + f"moe.experts.{e}." for e in range(*_held(cfg))]
        layer["moe"] = {
            "router": w[h + "moe.router"],
            "wi": jnp.stack([cat([x + "w_gate", x + "w_up"]) for x in experts]),
            "wo": jnp.stack([w[x + "w_down"] for x in experts])}
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
        for prog, ref in _NORMS:
            w[h + ref] = t[prog]["scale"]
        cuts = [hq * hd, (hq + hk) * hd]
        for name, part in zip(_QKV, jnp.split(t["qkv"]["kernel"], cuts, -1)):
            w[h + name] = part
        w[h + "attn.w_o"] = t["o_proj"]["kernel"]
        m = t["moe"]
        w[h + "moe.router"] = m["router"]
        for j, e in enumerate(range(*_held(cfg))):
            x = h + f"moe.experts.{e}."
            w[x + "w_gate"], w[x + "w_up"] = jnp.split(m["wi"][j], 2, axis=-1)
            w[x + "w_down"] = m["wo"][j]
    return w


#: no leaf's gradient is identically zero
ZERO_GRADIENT_SUFFIX = None


# -- operations the model requires ------------------------------------------

def forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """Matrix multiplications and attention one token's forward pass requires
    here, by part.  Window layers count ``min(i + 1, window)`` keys; the
    routed experts count the EXPECTED experts a token finds held here, ``k *
    held / routed_over``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers, f = cfg["num_hidden_layers"], cfg["moe_ffn_hidden_size"]
    routed_over = _routed_over(cfg)
    expected = (cfg["moe_num_active_primary_experts"]
                * cfg["moe_num_primary_experts"] / routed_over)
    return {
        "attn_proj": layers * (2 * d * (hq + 2 * hk) * hd + 2 * hq * hd * d),
        "attention": sum(4 * hq * hd * mean_keys(
            seq, cfg["sliding_window_size"] if windowed else None)
            for windowed in cfg["sliding_window_layout"]),
        "router": layers * 2 * d * routed_over,
        "routed": layers * expected * 6 * d * f,
        "head": 2 * d * cfg["assumed"]["padded_vocab_size"],
    }


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation is not
    counted."""
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def grouped_mm_needed(cfg: Dict, tokens: int) -> List[Tuple[float, float]]:
    """``families/afmoe.py``'s count under this family's published names
    (here 1536 rows an expert, the down-projection 768 wide in)."""
    return afmoe.grouped_mm_needed(
        {**cfg, "moe_intermediate_size": cfg["moe_ffn_hidden_size"],
         "num_experts": cfg["moe_num_primary_experts"],
         "num_experts_per_tok": cfg["moe_num_active_primary_experts"],
         "published": {"num_experts": _routed_over(cfg)}}, tokens)
