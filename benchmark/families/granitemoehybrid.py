"""The ``granitemoehybrid`` family: Granite 4.0-H configurations WITHOUT
experts through the program's ``GraniteHybridLM``, and their plain reference.
A configuration file names this module by ``"family": "granitemoehybrid"``.

The configuration is one pipeline stage of a deployment (whole blocks, no
layer divided) with a slice of the vocabulary: ``vocab_size`` is the slice
held — rows of the embedding and, the head being tied to it, columns of the
head.  The benchmark's weights hold no ``head`` leaf.  The family's expert
branch is absent (``num_local_experts`` 0): a file that asks for experts is
refused, not run dense.

Beside ``train_flops_per_token`` stand the operations and bytes the kernels
need (:func:`ssd_needed`, :func:`flash_needed`), from which the roofline
readers work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# The program's model, asked for at once: a checkout that cannot run this
# family (an older commit) then fails before the reference is computed.
import apex_tpu.models.granite_hybrid  # noqa: F401
# What the decoder families share, from the oldest: the keys a query sees,
# attention's needs and the roofline's time.
from benchmark.families import afmoe
from benchmark.families.afmoe import mean_keys, needed_seconds  # noqa: F401
from benchmark.reference import granitemoehybrid as reference  # noqa: F401 (the family's reference)

MAMBA, ATTENTION = "mamba", "attention"


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
    if set(kinds) - {MAMBA, ATTENTION}:
        raise ValueError(f"layer_types {sorted(set(kinds) - {MAMBA, ATTENTION})}"
                         f": only {MAMBA!r} and {ATTENTION!r} are built")
    if list(cfg["published"]["layer_types"][:len(kinds)]) != list(kinds):
        raise ValueError("layer_types is not the leading layers of "
                         "published.layer_types")
    if cfg["num_local_experts"] or cfg["num_experts_per_tok"]:
        raise ValueError("the family's expert branch is not built: "
                         "num_local_experts and num_experts_per_tok must be 0")
    if cfg["shared_intermediate_size"] != cfg["intermediate_size"]:
        raise ValueError("one dense MLP a layer: shared_intermediate_size "
                         "must be intermediate_size")
    if cfg["mamba_proj_bias"] or cfg["attention_bias"] \
            or not cfg["mamba_conv_bias"]:
        raise ValueError("no bias but the convolution's is built")
    if cfg["mamba_expand"] * cfg["hidden_size"] \
            != cfg["mamba_n_heads"] * cfg["mamba_d_head"]:
        raise ValueError("mamba_expand x hidden_size is not mamba_n_heads "
                         "heads of mamba_d_head")
    if cfg["position_embedding_type"] != "nope":
        raise ValueError("only attention without positions is built")
    if cfg["hidden_act"] != "silu" or cfg["normalization_function"] != "rmsnorm":
        raise ValueError("only silu and rmsnorm are built")
    if not cfg["tie_word_embeddings"] \
            or not cfg["assumed"]["tie_word_embeddings"]:
        raise ValueError("the head is tied to the embedding")


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.granite_hybrid import GraniteHybridConfig

    _check(cfg)
    return GraniteHybridConfig(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"], layer_types=tuple(cfg["layer_types"]),
        mamba_n_heads=cfg["mamba_n_heads"], mamba_d_head=cfg["mamba_d_head"],
        mamba_d_state=cfg["mamba_d_state"],
        mamba_n_groups=cfg["mamba_n_groups"], mamba_d_conv=cfg["mamba_d_conv"],
        mamba_chunk_size=cfg["mamba_chunk_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        intermediate_size=cfg["intermediate_size"],
        embedding_multiplier=float(cfg["embedding_multiplier"]),
        attention_multiplier=float(cfg["attention_multiplier"]),
        residual_multiplier=float(cfg["residual_multiplier"]),
        logits_scaling=float(cfg["logits_scaling"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        initializer_range=cfg["assumed"]["initializer_range"],
        remat_policy=cfg["assumed"].get("remat_policy", "none"),
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.granite_hybrid import GraniteHybridLM

    return GraniteHybridLM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis."""
    _check(cfg)
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"]}


_NORMS = (("input_norm", "norm_in"), ("post_norm", "norm_post"))
_QKV = ("attn.w_q", "attn.w_k", "attn.w_v")
#: a mamba layer's leaves: (path in the program's ``mamba`` module, the
#: reference's name)
_MAMBA = ((("in_proj", "kernel"), "mamba.w_in"), (("conv_taps",), "mamba.conv_w"),
          (("conv_bias",), "mamba.conv_b"), (("dt_bias",), "mamba.dt_bias"),
          (("A_log",), "mamba.A_log"), (("D",), "mamba.D"),
          (("norm", "scale"), "mamba.norm"),
          (("out_proj", "kernel"), "mamba.w_out"))


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree (the
    program keeps q, k and v as one matrix and gate and up as one; the
    embedding is the head)."""
    import jax.numpy as jnp

    cat = lambda names: jnp.concatenate([w[n] for n in names], axis=-1)
    tree = {"embed": {"embedding": w["embed"]}, "norm_f": {"scale": w["norm_f"]}}
    for i, kind in enumerate(cfg["layer_types"]):
        h = f"layers.{i}."
        layer = {prog: {"scale": w[h + ref]} for prog, ref in _NORMS}
        if kind == MAMBA:
            layer["mamba"] = {}
            for path, ref in _MAMBA:
                node = layer["mamba"]
                for part in path[:-1]:
                    node = node.setdefault(part, {})
                node[path[-1]] = w[h + ref]
        else:
            layer["qkv"] = {"kernel": cat([h + n for n in _QKV])}
            layer["o_proj"] = {"kernel": w[h + "attn.w_o"]}
        layer["mlp"] = {
            "gate_up": {"kernel": cat([h + "mlp.w_gate", h + "mlp.w_up"])},
            "down": {"kernel": w[h + "mlp.w_down"]}}
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
        if kind == MAMBA:
            for path, ref in _MAMBA:
                leaf = t["mamba"]
                for part in path:
                    leaf = leaf[part]
                w[h + ref] = leaf
        else:
            cuts = [hq * hd, (hq + hk) * hd]
            for name, part in zip(_QKV, jnp.split(t["qkv"]["kernel"], cuts, -1)):
                w[h + name] = part
            w[h + "attn.w_o"] = t["o_proj"]["kernel"]
        w[h + "mlp.w_gate"], w[h + "mlp.w_up"] = jnp.split(
            t["mlp"]["gate_up"]["kernel"], 2, axis=-1)
        w[h + "mlp.w_down"] = t["mlp"]["down"]["kernel"]
    return w


#: no leaf's gradient is identically zero
ZERO_GRADIENT_SUFFIX = None


def views(w: Dict) -> Dict:
    """The leaves that are compared: the reference's own (a dense model has
    no experts to take together)."""
    return w


# -- operations the model requires ------------------------------------------

def _ssd_flops_per_token(cfg: Dict, chunk: int) -> float:
    """The chunked scan's products a token, forward, ONE layer: ``C B^T``
    once a group (``2 Q N``) and, a head, its own ``(L . C B^T) (dt x)`` (``2
    Q P``), ``C S^T`` and the state's update (``2 P N`` each) — the chunk's
    square counted whole."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return g * 2 * chunk * n + h * (2 * chunk * p + 4 * p * n)


def forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """Matrix multiplications, attention and the scan one token's forward
    pass requires here, by part."""
    d, hd, f = cfg["hidden_size"], _head_dim(cfg), cfg["intermediate_size"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    d_in = h * p
    n_mamba = sum(kind == MAMBA for kind in cfg["layer_types"])
    n_attn = len(cfg["layer_types"]) - n_mamba
    return {
        "ssm_proj": n_mamba * (2 * d * (2 * d_in + 2 * g * n + h)
                               + 2 * d_in * d),
        "ssm_scan": n_mamba * _ssd_flops_per_token(cfg, cfg["mamba_chunk_size"]),
        "attn_proj": n_attn * (2 * d * (hq + 2 * hk) * hd + 2 * hq * hd * d),
        "attention": n_attn * 4 * hq * hd * mean_keys(seq),
        "dense_mlp": cfg["num_hidden_layers"] * 6 * d * f,
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


def ssd_needed(cfg: Dict, seq: int, rows: int, chunk: int = 256,
               itemsize: int = 2) -> List[Tuple[float, float]]:
    """``(operations, bytes)`` ONE mamba layer's scan needs for the forward
    and the backward pass.  Operations: :func:`_ssd_flops_per_token` a token
    forward, twice that backward.  Bytes, each array crossing HBM once: x, B,
    C in and o out in the compute dtype, dt in float32, and the state at
    each chunk's start (``H x P x N`` float32 a chunk) written forward and
    read backward; backward also x, B, C, dt in again, do in, and dx, dB, dC,
    ddt out.  The forward run again under per-block recomputation is not
    counted as needed."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    tokens = rows * seq
    fwd = tokens * _ssd_flops_per_token(cfg, chunk)
    wide, shared, small = tokens * h * p, tokens * g * n, tokens * h
    states = 4 * rows * (seq // chunk) * h * p * n
    fwd_bytes = itemsize * (2 * wide + 2 * shared) + 4 * small + states
    bwd_bytes = itemsize * (3 * wide + 4 * shared) + 2 * 4 * small + states
    return [(fwd, fwd_bytes), (2 * fwd, bwd_bytes)]
