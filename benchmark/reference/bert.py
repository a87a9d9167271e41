"""BERT (Devlin et al. 2019) with the masked-LM head, written from the
published description: word + position embeddings under a LayerNorm,
post-LN blocks (bidirectional softmax attention, an exact-erf GELU MLP), and
the MLM head — dense, GELU, LayerNorm, a decoder tied to the word embedding
plus a bias.  Plain ``jax.numpy``, float32, ``highest`` matmul precision; no
kernels or batching tricks; imports nothing of the program.

Departures from the source, stated in the configuration file: the padded
vocabulary axis, dropout off, and no segment (token-type) embedding — every
sequence here is one segment, for which the published model adds one constant
row that the program's ``BertForMLM`` does not have.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights at BERT's own init: N(0, initializer_range), biases
    0, LayerNorm 1 and 0."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    f, std = cfg["intermediate_size"], cfg["initializer_range"]
    normal = jax.random.normal
    keys = iter(jax.random.split(key, 3 + 4 * L))
    ones, zeros = (lambda n: jnp.ones((n,), jnp.float32),
                   lambda n: jnp.zeros((n,), jnp.float32))
    p = {
        "emb.word": std * normal(next(keys), (V, d), jnp.float32),
        "emb.pos": std * normal(
            next(keys), (cfg["max_position_embeddings"], d), jnp.float32),
        "emb.ln.g": ones(d), "emb.ln.b": zeros(d),
        "mlm.w": std * normal(next(keys), (d, d), jnp.float32),
        "mlm.b": zeros(d), "mlm.ln.g": ones(d), "mlm.ln.b": zeros(d),
        "mlm.bias": zeros(V),
    }
    for i in range(L):
        h = f"layer.{i}."
        for name, shape in (("attn.w_qkv", (d, 3 * d)), ("attn.w_o", (d, d)),
                            ("ffn.w_in", (d, f)), ("ffn.w_out", (f, d))):
            p[h + name] = std * normal(next(keys), shape, jnp.float32)
        for name, n in (("attn.b_qkv", 3 * d), ("attn.b_o", d),
                        ("ffn.b_in", f), ("ffn.b_out", d)):
            p[h + name] = zeros(n)
        for ln in ("attn.ln", "ffn.ln"):
            p[h + ln + ".g"], p[h + ln + ".b"] = ones(d), zeros(d)
    return p


def logits(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, vocab)`` MLM logits.  ``remat``
    recomputes each block's inside in the backward pass instead of keeping
    it (the same arithmetic, less memory)."""
    d, nh = cfg["hidden_size"], cfg["num_attention_heads"]
    eps, act = cfg["layer_norm_eps"], C.ACTIVATIONS[cfg["hidden_act"]]
    b, s = ids.shape
    heads = lambda t: t.reshape(b, s, nh, d // nh).transpose(0, 2, 1, 3)

    def block(x, w):
        qkv = C.mm(x, w["attn.w_qkv"]) + w["attn.b_qkv"]
        q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
        a = C.attention(q, k, v, causal=False)
        a = a.transpose(0, 2, 1, 3).reshape(b, s, d)
        a = C.mm(a, w["attn.w_o"]) + w["attn.b_o"]
        x = C.layer_norm(x + a, w["attn.ln.g"], w["attn.ln.b"], eps)
        y = act(C.mm(x, w["ffn.w_in"]) + w["ffn.b_in"])
        y = C.mm(y, w["ffn.w_out"]) + w["ffn.b_out"]
        return C.layer_norm(x + y, w["ffn.ln.g"], w["ffn.ln.b"], eps)

    if remat:
        block = jax.checkpoint(block)
    x = p["emb.word"][ids] + p["emb.pos"][:s]
    x = C.layer_norm(x, p["emb.ln.g"], p["emb.ln.b"], eps)
    for i in range(cfg["num_hidden_layers"]):
        h = f"layer.{i}."
        x = block(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)})
    y = act(C.mm(x, p["mlm.w"]) + p["mlm.b"])
    y = C.layer_norm(y, p["mlm.ln.g"], p["mlm.ln.b"], eps)
    return C.mm(y, p["emb.word"].T) + p["mlm.bias"]


def loss_rows(p: C.Params, batch, cfg: Dict):
    """Per row of ``batch = (ids, labels)``, the masked-LM cross-entropy summed
    over its predicted positions (label -100: not predicted)."""
    ids, labels = batch
    return C.row_loss_sums(logits(p, ids, cfg, remat=True), labels)
