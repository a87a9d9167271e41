"""The ``gpt2`` family: GPT-2 configurations through the program's
``GPTLM``, and their plain reference.  A configuration file names this module by ``"family": "gpt2"``.
"""
from __future__ import annotations

from typing import Dict

from benchmark import flops
from benchmark.reference import gpt2 as reference  # noqa: F401 (the family's reference)


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.gpt import GPTConfig

    return GPTConfig(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["n_embd"], num_layers=cfg["n_layer"],
        num_heads=cfg["n_head"], max_position=cfg["n_positions"],
        dropout_rate=cfg["resid_pdrop"], attn_dropout_rate=cfg["attn_pdrop"],
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.gpt import GPTLM

    return GPTLM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    """The configuration as the reference reads it: the padded vocabulary
    axis in place of the published one."""
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"]}


def to_program(w: Dict, cfg: Dict) -> Dict:
    """The benchmark's seeded weights in the program's parameter tree."""
    tree = {
        "wte": {"embedding": w["wte"]}, "wpe": {"embedding": w["wpe"]},
        "ln_f": {"scale": w["ln_f.g"], "bias": w["ln_f.b"]},
    }
    for i in range(cfg["n_layer"]):
        h = f"h.{i}."
        tree[f"layer_{i}"] = {
            "ln1": {"scale": w[h + "ln_1.g"], "bias": w[h + "ln_1.b"]},
            "ln2": {"scale": w[h + "ln_2.g"], "bias": w[h + "ln_2.b"]},
            "qkv": {"kernel": w[h + "attn.w_qkv"], "bias": w[h + "attn.b_qkv"]},
            "proj": {"kernel": w[h + "attn.w_o"], "bias": w[h + "attn.b_o"]},
            "ffn_in": {"kernel": w[h + "mlp.w_in"], "bias": w[h + "mlp.b_in"]},
            "ffn_out": {"kernel": w[h + "mlp.w_out"], "bias": w[h + "mlp.b_out"]},
        }
    return tree


def from_program(tree: Dict, cfg: Dict) -> Dict:
    """A tree shaped like the program's parameters (values, moments) under
    the reference's leaf names."""
    w = {"wte": tree["wte"]["embedding"], "wpe": tree["wpe"]["embedding"],
         "ln_f.g": tree["ln_f"]["scale"], "ln_f.b": tree["ln_f"]["bias"]}
    for i in range(cfg["n_layer"]):
        h, t = f"h.{i}.", tree[f"layer_{i}"]
        w.update({
            h + "ln_1.g": t["ln1"]["scale"], h + "ln_1.b": t["ln1"]["bias"],
            h + "ln_2.g": t["ln2"]["scale"], h + "ln_2.b": t["ln2"]["bias"],
            h + "attn.w_qkv": t["qkv"]["kernel"], h + "attn.b_qkv": t["qkv"]["bias"],
            h + "attn.w_o": t["proj"]["kernel"], h + "attn.b_o": t["proj"]["bias"],
            h + "mlp.w_in": t["ffn_in"]["kernel"], h + "mlp.b_in": t["ffn_in"]["bias"],
            h + "mlp.w_out": t["ffn_out"]["kernel"], h + "mlp.b_out": t["ffn_out"]["bias"],
        })
    return w


#: The key bias's gradient is identically zero in exact arithmetic (a
#: softmax does not see a shift of all its scores), so what Adam or LAMB
#: make of it is rounding noise normalised into steps: its change is not
#: compared.  Its gradient is (both sides read about zero).
ZERO_GRADIENT_SUFFIX = "attn.b_k"


def views(w: Dict) -> Dict:
    """The leaves that are compared: as the reference names them, with each
    fused QKV bias split into its query, key and value parts."""
    out = {}
    for name, x in w.items():
        if name.endswith("attn.b_qkv"):
            d = x.shape[0] // 3
            for i, part in enumerate(("attn.b_q", "attn.b_k", "attn.b_v")):
                out[name[:-len("attn.b_qkv")] + part] = x[i * d:(i + 1) * d]
        else:
            out[name] = x
    return out


def train_flops_per_token(cfg: Dict, seq: int) -> float:
    return flops.gpt2_train_flops_per_token(cfg, seq)

