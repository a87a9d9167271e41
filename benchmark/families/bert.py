"""The ``bert`` family: BERT configurations with the masked-LM head through
the program's ``BertForMLM``, and their plain reference.  A configuration
file names this module by ``"family": "bert"``.
"""
from __future__ import annotations

from typing import Dict

from benchmark import flops
from benchmark.reference import bert as reference  # noqa: F401 (the family's reference)


def program_config(cfg: Dict, compute_dtype):
    from apex_tpu.models.bert import BertConfig

    return BertConfig(
        vocab_size=cfg["assumed"]["padded_vocab_size"],
        hidden_size=cfg["hidden_size"], num_layers=cfg["num_hidden_layers"],
        num_heads=cfg["num_attention_heads"],
        intermediate_size=cfg["intermediate_size"],
        max_position=cfg["max_position_embeddings"],
        type_vocab_size=cfg["type_vocab_size"],
        dropout_rate=cfg["hidden_dropout_prob"],
        attn_dropout_rate=cfg["attention_probs_dropout_prob"],
        compute_dtype=compute_dtype,
    )


def program_model(pcfg):
    from apex_tpu.models.bert import BertForMLM

    return BertForMLM(pcfg)


def reference_config(cfg: Dict) -> Dict:
    return {**cfg, "vocab_size": cfg["assumed"]["padded_vocab_size"]}


_LAYER = (("attn_ln", "scale", "attn.ln.g"), ("attn_ln", "bias", "attn.ln.b"),
          ("ffn_ln", "scale", "ffn.ln.g"), ("ffn_ln", "bias", "ffn.ln.b"),
          ("self_attn", "in_proj_weight", "attn.w_qkv"),
          ("self_attn", "in_proj_bias", "attn.b_qkv"),
          ("self_attn", "out_proj_weight", "attn.w_o"),
          ("self_attn", "out_proj_bias", "attn.b_o"),
          ("ffn_in", "kernel", "ffn.w_in"), ("ffn_in", "bias", "ffn.b_in"),
          ("ffn_out", "kernel", "ffn.w_out"), ("ffn_out", "bias", "ffn.b_out"))


def to_program(w: Dict, cfg: Dict) -> Dict:
    enc = {
        "word_embeddings": {"embedding": w["emb.word"]},
        "position_embeddings": {"embedding": w["emb.pos"]},
        "embed_ln": {"scale": w["emb.ln.g"], "bias": w["emb.ln.b"]},
    }
    for i in range(cfg["num_hidden_layers"]):
        layer: Dict = {}
        for mod, leaf, name in _LAYER:
            layer.setdefault(mod, {})[leaf] = w[f"layer.{i}.{name}"]
        enc[f"layer_{i}"] = layer
    return {
        "encoder": enc,
        "mlm_transform": {"kernel": w["mlm.w"], "bias": w["mlm.b"]},
        "mlm_ln": {"scale": w["mlm.ln.g"], "bias": w["mlm.ln.b"]},
        "mlm_bias": w["mlm.bias"],
    }


def from_program(tree: Dict, cfg: Dict) -> Dict:
    enc = tree["encoder"]
    w = {
        "emb.word": enc["word_embeddings"]["embedding"],
        "emb.pos": enc["position_embeddings"]["embedding"],
        "emb.ln.g": enc["embed_ln"]["scale"], "emb.ln.b": enc["embed_ln"]["bias"],
        "mlm.w": tree["mlm_transform"]["kernel"], "mlm.b": tree["mlm_transform"]["bias"],
        "mlm.ln.g": tree["mlm_ln"]["scale"], "mlm.ln.b": tree["mlm_ln"]["bias"],
        "mlm.bias": tree["mlm_bias"],
    }
    for i in range(cfg["num_hidden_layers"]):
        for mod, leaf, name in _LAYER:
            w[f"layer.{i}.{name}"] = enc[f"layer_{i}"][mod][leaf]
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
    return flops.bert_train_flops_per_token(cfg, seq)
