"""GPT-2 (Radford et al. 2019), written from the published description:
learned positions, pre-LN blocks, one fused QKV projection, causal softmax
attention, a 4x ``gelu_new`` MLP, a final LayerNorm and an output head tied
to the token embedding.  Plain ``jax.numpy``, float32, ``highest`` matmul
precision; no kernels, no cache, no batching tricks; imports nothing of the
program.

Departures from the source, both stated in the configuration file: the
vocabulary axis is the padded one the configuration runs (tokens are drawn
below the published 50257, the padded columns still enter the softmax, as
they do in the program), and dropout is off.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights at GPT-2's own init scale: N(0, 0.02) (positions
    0.01), the two residual projections scaled by 1/sqrt(2 * n_layer),
    biases 0, LayerNorm 1 and 0."""
    d, L, V = cfg["n_embd"], cfg["n_layer"], cfg["vocab_size"]
    normal = jax.random.normal
    keys = iter(jax.random.split(key, 2 + 4 * L))
    p = {
        "wte": 0.02 * normal(next(keys), (V, d), jnp.float32),
        "wpe": 0.01 * normal(next(keys), (cfg["n_positions"], d), jnp.float32),
        "ln_f.g": jnp.ones((d,), jnp.float32),
        "ln_f.b": jnp.zeros((d,), jnp.float32),
    }
    resid = 0.02 / math.sqrt(2 * L)
    for i in range(L):
        h = f"h.{i}."
        for name, shape, std in (("attn.w_qkv", (d, 3 * d), 0.02),
                                 ("attn.w_o", (d, d), resid),
                                 ("mlp.w_in", (d, 4 * d), 0.02),
                                 ("mlp.w_out", (4 * d, d), resid)):
            p[h + name] = std * normal(next(keys), shape, jnp.float32)
        for name, n in (("attn.b_qkv", 3 * d), ("attn.b_o", d),
                        ("mlp.b_in", 4 * d), ("mlp.b_out", d)):
            p[h + name] = jnp.zeros((n,), jnp.float32)
        for ln in ("ln_1", "ln_2"):
            p[h + ln + ".g"] = jnp.ones((d,), jnp.float32)
            p[h + ln + ".b"] = jnp.zeros((d,), jnp.float32)
    return p


def logits(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, vocab)`` logits of the full forward.
    ``remat`` recomputes each block's inside in the backward pass instead of
    keeping it (the same arithmetic; the reference's training steps use it to
    stay small in memory)."""
    d, nh, eps = cfg["n_embd"], cfg["n_head"], cfg["layer_norm_epsilon"]
    act = C.ACTIVATIONS[cfg["activation_function"]]
    b, s = ids.shape
    heads = lambda t: t.reshape(b, s, nh, d // nh).transpose(0, 2, 1, 3)

    def block(x, w):
        y = C.layer_norm(x, w["ln_1.g"], w["ln_1.b"], eps)
        qkv = C.mm(y, w["attn.w_qkv"]) + w["attn.b_qkv"]
        q, k, v = (heads(t) for t in jnp.split(qkv, 3, axis=-1))
        a = C.attention(q, k, v, causal=True)
        a = a.transpose(0, 2, 1, 3).reshape(b, s, d)
        x = x + C.mm(a, w["attn.w_o"]) + w["attn.b_o"]
        y = C.layer_norm(x, w["ln_2.g"], w["ln_2.b"], eps)
        y = act(C.mm(y, w["mlp.w_in"]) + w["mlp.b_in"])
        return x + C.mm(y, w["mlp.w_out"]) + w["mlp.b_out"]

    if remat:
        block = jax.checkpoint(block)
    x = p["wte"][ids] + p["wpe"][:s]
    for i in range(cfg["n_layer"]):
        h = f"h.{i}."
        x = block(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)})
    x = C.layer_norm(x, p["ln_f.g"], p["ln_f.b"], eps)
    return C.mm(x, p["wte"].T)


def loss_rows(p: C.Params, batch, cfg: Dict):
    """Per row of ``batch = (ids, labels)``, the next-token cross-entropy summed
    over its predicted positions (label -100: not predicted)."""
    ids, labels = batch
    return C.row_loss_sums(logits(p, ids, cfg, remat=True), labels)
