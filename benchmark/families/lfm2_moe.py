"""The ``lfm2_moe`` family: LFM2 sparse-expert configurations through the
program's ``Lfm2LM``, and their plain reference.  A configuration file names
this module by ``"family": "lfm2_moe"``.

As in ``families/afmoe.py`` the configuration is one chip's share of an
expert-parallel deployment: ``num_experts`` counts the routed experts HELD
here (``assumed.experts_held`` names them), ``published.num_experts`` the
experts the router scores, and ``vocab_size`` the slice of the vocabulary
held — rows of the embedding and, the head being tied to it, columns of the
head.  The benchmark's weights hold no ``head`` leaf.

Beside ``train_flops_per_token`` stand the operations and bytes the kernels
need (:func:`gated_conv_needed`, :func:`flash_needed`,
:func:`grouped_mm_needed`), from which the roofline readers work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# The program's model, asked for at once: a checkout that cannot run this
# family (an older commit) then fails before the reference is computed.
import apex_tpu.models.lfm2  # noqa: F401
# What the sparse-expert families share, from the oldest: the leaves that are
# compared (a layer's held experts' matrices taken TOGETHER), the keys a query
# sees, the grouped products' needs (this family's published names are the
# oldest's) and the roofline's time.
from benchmark.families import afmoe
from benchmark.families.afmoe import (  # noqa: F401
    grouped_mm_needed, mean_keys, needed_seconds, views)
from benchmark.reference import lfm2_moe as reference  # noqa: F401 (the family's reference)

CONV, FULL = "conv", "full_attention"
#: what ``parallel/moe.py::sigmoid_topk_routing`` adds to the picked scores'
#: sum before it divides
ROUTE_NORM_EPS = 1e-20


def _held(cfg: Dict) -> Tuple[int, int]:
    lo, hi = cfg["assumed"]["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("assumed.experts_held does not hold num_experts experts")
    return int(lo), int(hi)


def _routed_over(cfg: Dict) -> int:
    return cfg["published"]["num_experts"]


def _head_dim(cfg: Dict) -> int:
    """The published file carries no ``head_dim``: a head is the hidden size
    over the query heads."""
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _check(cfg: Dict) -> None:
    """The file's three statements of its depth agree, and nothing is asked
    for that this family's program and reference do not do."""
    kinds = cfg["layer_types"]
    if len(kinds) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    if set(kinds) - {CONV, FULL}:
        raise ValueError(f"layer_types {sorted(set(kinds) - {CONV, FULL})}: "
                         f"only {CONV!r} and {FULL!r} are built")
    if not 0 <= cfg["num_dense_layers"] <= len(kinds):
        raise ValueError("num_dense_layers is not a count of layer_types' layers")
    if cfg["conv_bias"]:
        raise ValueError("no bias in the convolution layers' projections")
    if not cfg["use_expert_bias"] or not cfg["norm_topk_prob"]:
        raise ValueError("only sigmoid scores under a selection bias, the "
                         "picked scores normalised")
    if cfg["rope_parameters"]["rope_type"] != "default":
        raise ValueError("only unscaled rotary positions are built")
    if not cfg["assumed"]["tie_word_embeddings"]:
        raise ValueError("the head is tied to the embedding")
    if cfg["assumed"]["route_norm_eps"] != ROUTE_NORM_EPS:
        raise ValueError("the program normalises the picked scores at another eps")


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.lfm2 import Lfm2Config

    _check(cfg)
    return Lfm2Config(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"], layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        conv_L_cache=cfg["conv_L_cache"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=_head_dim(cfg),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=_routed_over(cfg), experts_held=_held(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        route_norm=cfg["norm_topk_prob"],
        route_scale=float(cfg["routed_scaling_factor"]),
        norm_eps=cfg["norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        remat_policy=cfg["assumed"].get("remat_policy", "none"),
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.lfm2 import Lfm2LM

    return Lfm2LM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis, the experts held and the experts routed over."""
    _check(cfg)
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"],
            "experts_held": list(_held(cfg)),
            "experts_routed_over": _routed_over(cfg)}


_NORMS = (("operator_norm", "norm_op"), ("pre_mlp_norm", "norm_ffn"))
_QKV = ("attn.w_q", "attn.w_k", "attn.w_v")
_QK_NORMS = (("q_norm", "attn.q_norm"), ("k_norm", "attn.k_norm"))


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree (the
    program keeps q, k and v as one matrix, gate and up as one, and the held
    experts' matrices stacked; the embedding is the head)."""
    import jax.numpy as jnp

    cat = lambda names: jnp.concatenate([w[n] for n in names], axis=-1)
    tree = {"embed": {"embedding": w["embed"]}, "norm_f": {"scale": w["norm_f"]}}
    for i, kind in enumerate(cfg["layer_types"]):
        h = f"layers.{i}."
        layer = {prog: {"scale": w[h + ref]} for prog, ref in _NORMS}
        if kind == CONV:
            layer["conv"] = {"in_proj": {"kernel": w[h + "conv.w_in"]},
                             "taps": w[h + "conv.taps"],
                             "out_proj": {"kernel": w[h + "conv.w_out"]}}
        else:
            layer["qkv"] = {"kernel": cat([h + n for n in _QKV])}
            layer["o_proj"] = {"kernel": w[h + "attn.w_o"]}
            layer.update({prog: {"scale": w[h + ref]} for prog, ref in _QK_NORMS})
        if i < cfg["num_dense_layers"]:
            layer["mlp"] = {
                "gate_up": {"kernel": cat([h + "mlp.w_gate", h + "mlp.w_up"])},
                "down": {"kernel": w[h + "mlp.w_down"]}}
        else:
            experts = [h + f"moe.experts.{e}." for e in range(*_held(cfg))]
            layer["moe"] = {
                "router": w[h + "moe.router"],
                "expert_bias": w[h + "moe.expert_bias"],
                "wi": jnp.stack([cat([x + "w_gate", x + "w_up"]) for x in experts]),
                "wo": jnp.stack([w[x + "w_down"] for x in experts])}
        tree[f"layer_{i}"] = layer
    return tree


def from_program(tree: Dict, cfg: Dict) -> Dict:
    """A tree shaped like the program's parameters under the reference's
    leaf names, the fused matrices split back into their parts."""
    import jax.numpy as jnp

    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  _head_dim(cfg))
    w = {"embed": tree["embed"]["embedding"], "norm_f": tree["norm_f"]["scale"]}
    for i, kind in enumerate(cfg["layer_types"]):
        h, t = f"layers.{i}.", tree[f"layer_{i}"]
        for prog, ref in _NORMS:
            w[h + ref] = t[prog]["scale"]
        if kind == CONV:
            w[h + "conv.w_in"] = t["conv"]["in_proj"]["kernel"]
            w[h + "conv.taps"] = t["conv"]["taps"]
            w[h + "conv.w_out"] = t["conv"]["out_proj"]["kernel"]
        else:
            cuts = [hq * hd, (hq + hk) * hd]
            for name, part in zip(_QKV, jnp.split(t["qkv"]["kernel"], cuts, -1)):
                w[h + name] = part
            w[h + "attn.w_o"] = t["o_proj"]["kernel"]
            for prog, ref in _QK_NORMS:
                w[h + ref] = t[prog]["scale"]
        if "mlp" in t:
            w[h + "mlp.w_gate"], w[h + "mlp.w_up"] = jnp.split(
                t["mlp"]["gate_up"]["kernel"], 2, axis=-1)
            w[h + "mlp.w_down"] = t["mlp"]["down"]["kernel"]
            continue
        m = t["moe"]
        w[h + "moe.router"], w[h + "moe.expert_bias"] = m["router"], m["expert_bias"]
        for j, e in enumerate(range(*_held(cfg))):
            x = h + f"moe.experts.{e}."
            w[x + "w_gate"], w[x + "w_up"] = jnp.split(m["wi"][j], 2, axis=-1)
            w[x + "w_down"] = m["wo"][j]
    return w


#: no leaf's gradient is identically zero but the selection bias's, whose
#: change is zero on both sides (zero gradient, zero value: AdamW leaves it)
ZERO_GRADIENT_SUFFIX = None


# -- operations the model requires ------------------------------------------

def _conv_ops_per_channel(cfg: Dict) -> int:
    """Multiply-adds of the gated convolution a channel a token, forward:
    the gate in front, ``K`` multiplies and ``K - 1`` adds, the gate behind."""
    return 2 * cfg["conv_L_cache"] + 1


def forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """Matrix multiplications, attention and the gated convolution one
    token's forward pass requires here, by part.  The routed experts count
    the EXPECTED experts a token finds held here, ``k * held / routed_over``."""
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    n_conv = sum(kind == CONV for kind in cfg["layer_types"])
    n_full = len(cfg["layer_types"]) - n_conv
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    routed_over = _routed_over(cfg)
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed_over
    return {
        "conv_mixer": n_conv * (2 * d * 3 * d + 2 * d * d
                                + _conv_ops_per_channel(cfg) * d),
        "attn_proj": n_full * (2 * d * (hq + 2 * hk) * hd + 2 * hq * hd * d),
        "attention": n_full * 4 * hq * hd * mean_keys(seq),
        "dense_mlp": n_dense * 6 * d * fd,
        "router": n_moe * 2 * d * routed_over,
        "routed": n_moe * expected * 6 * d * f,
        "head": 2 * d * cfg["assumed"]["padded_vocab_size"],
    }


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    """Forward plus backward (twice the forward); recomputation is not
    counted."""
    return 3 * sum(forward_flops_per_token(cfg, seq).values())


def flash_needed(cfg: Dict, seq: int, rows: int, window=None
                 ) -> List[Tuple[float, float]]:
    """``families/afmoe.py``'s count at this family's head size (the file
    carries no ``head_dim``)."""
    return afmoe.flash_needed({**cfg, "head_dim": _head_dim(cfg)}, seq, rows,
                              window)


def gated_conv_needed(cfg: Dict, seq: int, rows: int, itemsize: int = 2
                      ) -> List[Tuple[float, float]]:
    """``(flops, bytes)`` ONE convolution layer's gated convolution needs for
    the forward and the backward pass: B, C, X in and the output out —
    backward: B, C, X and dy in, dB, dC, dX out, the taps and their gradient
    in float32 — each crossing HBM once in the compute dtype; ``2 K + 1``
    operations a channel a token forward, twice that and ``2 K`` for the taps'
    gradient backward (they never bind).  The forward recomputed in the
    backward pass is not counted as needed."""
    d, k = cfg["hidden_size"], cfg["conv_L_cache"]
    elements = rows * seq * d
    ops = _conv_ops_per_channel(cfg)
    return [(ops * elements, itemsize * 4 * elements + 4 * k * d),
            ((2 * ops + 2 * k) * elements,
             itemsize * 7 * elements + 2 * 4 * k * d)]
