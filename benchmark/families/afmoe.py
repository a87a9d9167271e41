"""The ``afmoe`` family: Arcee Trinity configurations through the program's
``AfmoeLM``, and their plain reference.  A configuration file names this
module by ``"family": "afmoe"``.

The configuration is one chip's share of an expert-parallel deployment:
``num_experts`` counts the routed experts HELD here (``assumed.experts_held``
names them), ``published.num_experts`` the experts the router scores, and
``vocab_size`` the slice of the vocabulary held.

Beside ``train_flops_per_token`` stand the FLOPs and bytes the new kernels
need (:func:`grouped_mm_needed`, :func:`flash_needed`), from which the
roofline readers in ``layer_metrics/`` work.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

# The program's model, asked for at once: a checkout that cannot run this
# family (an older commit) then fails before the reference is computed.
import apex_tpu.models.afmoe  # noqa: F401
from benchmark.reference import afmoe as reference  # noqa: F401 (the family's reference)

WINDOW = "sliding_attention"


def _held(cfg: Dict) -> Tuple[int, int]:
    lo, hi = cfg["assumed"]["experts_held"]
    if hi - lo != cfg["num_experts"]:
        raise ValueError("assumed.experts_held does not hold num_experts experts")
    return int(lo), int(hi)


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.afmoe import AfmoeConfig

    return AfmoeConfig(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"], layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"], head_dim=cfg["head_dim"],
        sliding_window=cfg["sliding_window"], rope_theta=float(cfg["rope_theta"]),
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_experts=cfg["published"]["num_experts"], experts_held=_held(cfg),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_shared_experts=cfg["num_shared_experts"],
        route_norm=cfg["route_norm"], route_scale=cfg["route_scale"],
        rms_norm_eps=cfg["rms_norm_eps"], mup_enabled=cfg["mup_enabled"],
        initializer_range=cfg["assumed"]["initializer_range"],
        remat_policy=cfg["assumed"].get("remat_policy", "none"),
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.afmoe import AfmoeLM

    return AfmoeLM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis, the experts held and the experts routed over."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"],
            "experts_held": list(_held(cfg)),
            "experts_routed_over": cfg["published"]["num_experts"]}


_NORMS = (("input_norm", "norm_in"), ("post_attn_norm", "norm_post_attn"),
          ("pre_mlp_norm", "norm_pre_mlp"), ("post_mlp_norm", "norm_post_mlp"),
          ("q_norm", "attn.q_norm"), ("k_norm", "attn.k_norm"))
_QKVG = ("attn.w_q", "attn.w_k", "attn.w_v", "attn.w_g")


def _cat(w: Dict, h: str, names) -> object:
    import jax.numpy as jnp

    return jnp.concatenate([w[h + n] for n in names], axis=-1)


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree (the
    program keeps q, k, v and the gate as one matrix, gate and up as one,
    and the held experts' matrices stacked)."""
    import jax.numpy as jnp

    tree = {"embed": {"embedding": w["embed"]}, "head": {"kernel": w["head"]},
            "norm_f": {"scale": w["norm_f"]}}
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        layer = {prog: {"scale": w[h + ref]} for prog, ref in _NORMS}
        layer["qkvg"] = {"kernel": _cat(w, h, _QKVG)}
        layer["o_proj"] = {"kernel": w[h + "attn.w_o"]}
        swiglu = lambda p: {
            "gate_up": {"kernel": _cat(w, h, (p + "w_gate", p + "w_up"))},
            "down": {"kernel": w[h + p + "w_down"]}}
        if i < cfg["num_dense_layers"]:
            layer["mlp"] = swiglu("mlp.")
        else:
            experts = [h + f"moe.experts.{e}." for e in range(*_held(cfg))]
            layer["moe"] = {
                "router": w[h + "moe.router"],
                "expert_bias": w[h + "moe.expert_bias"],
                "wi": jnp.stack([_cat(w, x, ("w_gate", "w_up")) for x in experts]),
                "wo": jnp.stack([w[x + "w_down"] for x in experts]),
                "shared": swiglu("shared.")}
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
        cuts = [hq * hd, (hq + hk) * hd, (hq + 2 * hk) * hd]
        for name, part in zip(_QKVG, jnp.split(t["qkvg"]["kernel"], cuts, -1)):
            w[h + name] = part
        w[h + "attn.w_o"] = t["o_proj"]["kernel"]

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


def views(w: Dict) -> Dict:
    """The leaves that are compared: the reference's own, but a layer's held
    experts' matrices of one kind taken TOGETHER (``moe.experts.w_gate``,
    stacked in the experts' order), as the program holds them.  The
    reference keeps each expert's matrices as leaves of their own for its
    memory's sake; compared one by one, the worst leaf is the emptiest
    expert's — a few rows a step, where one token that the program's
    bfloat16 scores route differently from the reference's float32 ones
    moves the norm of that expert's Adam steps by percents (read 0.0196 on
    one seed in nineteen where the others read 0.004-0.007: PERF.md §2)."""
    import re

    import jax.numpy as jnp

    out, experts = {}, {}
    for name, x in w.items():
        m = re.fullmatch(r"(.*\.moe\.experts)\.(\d+)\.(\w+)", name)
        if m:
            experts.setdefault(f"{m[1]}.{m[3]}", []).append((int(m[2]), x))
        else:
            out[name] = x
    for name, parts in experts.items():
        out[name] = jnp.stack([x for _, x in sorted(parts, key=lambda p: p[0])])
    return out


# -- operations the model requires ------------------------------------------

def mean_keys(seq: int, window=None) -> float:
    """Keys a query sees on average under the causal mask (and a window)."""
    if window is None or window >= seq:
        return (seq + 1) / 2
    return (window * (window + 1) / 2 + (seq - window) * window) / seq


def forward_flops_per_token(cfg: Dict, seq: int) -> Dict[str, float]:
    """Matrix multiplications and attention one token's forward pass
    requires here, by part.  Window layers count ``min(i + 1, window)``
    keys; the routed experts count the EXPECTED experts a token finds held
    here, ``k * held / routed_over``."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    f, fd = cfg["moe_intermediate_size"], cfg["intermediate_size"]
    routed_over = cfg["published"]["num_experts"]
    n_dense = cfg["num_dense_layers"]
    n_moe = cfg["num_hidden_layers"] - n_dense
    expected = cfg["num_experts_per_tok"] * cfg["num_experts"] / routed_over
    attn = sum(4 * hq * hd * mean_keys(
        seq, cfg["sliding_window"] if kind == WINDOW else None)
        for kind in cfg["layer_types"])
    return {
        "attn_proj": cfg["num_hidden_layers"] * (
            2 * d * (2 * hq + 2 * hk) * hd + 2 * hq * hd * d),
        "attention": attn,
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


def grouped_mm_needed(cfg: Dict, tokens: int, itemsize: int = 2
                      ) -> List[Tuple[float, float]]:
    """``(flops, bytes)`` of each grouped product ONE expert layer's step
    needs (forward, input gradients, weight gradients) for an evenly routed
    batch of ``tokens``: every held expert sees ``tokens * k / routed_over``
    rows; each operand crosses HBM once."""
    d, f, held = cfg["hidden_size"], cfg["moe_intermediate_size"], cfg["num_experts"]
    rows = tokens * cfg["num_experts_per_tok"] * held / cfg["published"]["num_experts"]
    out = []
    for c, n in ((d, 2 * f), (f, d)):       # gate|up, then down
        flops = 2 * rows * c * n
        weights, x, y = held * c * n, rows * c, rows * n
        out += [(flops, itemsize * (x + weights + y))] * 3   # out, dx, dw
    return out


def flash_needed(cfg: Dict, seq: int, rows: int, window, itemsize: int = 2
                 ) -> List[Tuple[float, float]]:
    """``(flops, bytes)`` ONE layer's attention needs for the forward and
    the backward pass (twice the forward's operations): the keys a query
    may see under the mask; q, k, v, o (backward: and their gradients)
    crossing HBM once."""
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    fwd = rows * seq * 4 * hq * hd * mean_keys(seq, window)
    qo = 2 * rows * hq * seq * hd
    kv = 2 * rows * hk * seq * hd
    return [(fwd, itemsize * (qo + kv)), (2 * fwd, itemsize * 2 * (qo + kv))]


def needed_seconds(parts, peaks: Dict) -> float:
    """The least time the chip could take for ``parts``: each the larger
    of its operations over the peak rate and its bytes over the peak
    bandwidth."""
    return sum(max(flops / peaks["bf16_flops_per_s"],
                   nbytes / peaks["hbm_bytes_per_s"]) for flops, nbytes in parts)
