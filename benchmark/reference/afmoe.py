"""Arcee ``afmoe`` (Trinity-Mini's ``config.json``, ``model_type`` afmoe),
written from the published configuration and the equations of ISSUE 26:
a decoder of RMSNorm-sandwiched blocks, grouped-query attention with an
output gate and normed queries and keys, rotary positions on the
sliding-window layers only, gated SiLU MLPs, and sigmoid-routed experts
beside a shared one.  Plain ``jax.numpy``, float32, ``highest`` matmul
precision; no kernels, no sorting, no buffers; imports nothing of the program.

Per block (eps ``rms_norm_eps``, no biases anywhere)::

    h += RMS_post_attn(Attn(RMS_in(h)));  h += RMS_post_mlp(FF(RMS_pre_mlp(h)))
    Attn(x) = W_o (softmax(q k^T / sqrt(D), causal [and i - j < window]) v * sigmoid(W_g x))
        q, k RMS-normed over the head size with a learned scale, then (window
        layers only) rotated; each key/value head serves H / H_kv query heads
    FF dense   = W_down (silu(W_gate x) * W_up x)
    FF experts = SwiGLU_shared(x) + sum_{e in top-k(s + b), e held} w_e SwiGLU_e(x)
        s = sigmoid(W_r x) over ALL experts; w = s[sel] / (sum s[sel] + 1e-20) * route_scale

``h = E[ids] * sqrt(hidden)`` (``mup_enabled``), ``logits = W_head RMS_f(h)``.

The share.  ``cfg["experts_held"] = [first, past_last]`` names the routed
experts whose weights exist here; the router still scores all
``cfg["experts_routed_over"]``; what a token's other experts would add is
not in the result — exactly what one chip of the expert-parallel job
computes before the exchange.  With every expert held this is the whole
model.  ``vocab_size`` is the (padded) slice of the vocabulary held here.

Memory.  At the cell's size (8192 tokens, 705.6M parameters, of which the
training steps hold four float32 copies) one layer's scores alone would be
8.6 GB, so attention is taken a head at a time and the token-wise parts
(feed-forward, head and loss) a block of tokens at a time, each recomputed
in the backward pass (:func:`in_blocks`): the same arithmetic on the same
numbers, every score of a head still materialised.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rotary(x, theta: float, positions):
    """Rotate ``x`` (..., seq, D), whose rows stand at ``positions`` (seq,),
    over the whole head, the two halves paired (``rotate_half``)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1) for f in (jnp.cos, jnp.sin))
    half = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], -1)
    return x * cos + half * sin


#: tokens of a block of the token-wise parts; queries of a block of a head
TOKEN_BLOCK = 512
QUERY_BLOCK = 1024


def in_blocks(fn, block: int, *arrays):
    """``fn(*arrays)`` computed a block of ``block`` leading rows at a time
    (``fn`` works on rows independently), each block recomputed in the
    backward pass; ``fn`` itself where the rows are one block or do not
    divide."""
    n = arrays[0].shape[0]
    if n <= block or n % block:
        return fn(*arrays)
    split = lambda a: a.reshape(n // block, block, *a.shape[1:])
    out = jax.lax.map(lambda a: jax.checkpoint(fn)(*a),
                      tuple(split(a) for a in arrays))
    return jax.tree_util.tree_map(
        lambda o: o.reshape(n, *o.shape[2:]), out)


def held(cfg: Dict):
    lo, hi = cfg["experts_held"]
    return int(lo), int(hi)


def is_dense(cfg: Dict, i: int) -> bool:
    return i < cfg["num_dense_layers"]


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights: N(0, 0.02) on every matrix, norms 1, the router's
    selection bias 0.  Only the held experts' matrices are made, under
    their own ids (``moe.experts.<id>.``)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lo, hi = held(cfg)
    f, fd, E = (cfg["moe_intermediate_size"], cfg["intermediate_size"],
                cfg["experts_routed_over"])
    std = cfg["assumed"]["initializer_range"]
    keys = iter(jax.random.split(key, 2 + (16 + 3 * (hi - lo)) * L))
    normal = lambda shape: std * jax.random.normal(next(keys), shape, jnp.float32)
    p = {"embed": normal((V, d)), "head": normal((d, V)),
         "norm_f": jnp.ones((d,), jnp.float32)}
    for i in range(L):
        h = f"layers.{i}."
        for n in ("norm_in", "norm_post_attn", "norm_pre_mlp", "norm_post_mlp"):
            p[h + n] = jnp.ones((d,), jnp.float32)
        p[h + "attn.w_q"] = normal((d, hq * hd))
        p[h + "attn.w_k"] = normal((d, hk * hd))
        p[h + "attn.w_v"] = normal((d, hk * hd))
        p[h + "attn.w_g"] = normal((d, hq * hd))
        p[h + "attn.w_o"] = normal((hq * hd, d))
        p[h + "attn.q_norm"] = jnp.ones((hd,), jnp.float32)
        p[h + "attn.k_norm"] = jnp.ones((hd,), jnp.float32)
        if is_dense(cfg, i):
            p[h + "mlp.w_gate"] = normal((d, fd))
            p[h + "mlp.w_up"] = normal((d, fd))
            p[h + "mlp.w_down"] = normal((fd, d))
            continue
        p[h + "moe.router"] = normal((d, E))
        p[h + "moe.expert_bias"] = jnp.zeros((E,), jnp.float32)
        for e in range(lo, hi):     # a held expert's matrices: leaves of its own
            x = h + f"moe.experts.{e}."
            p[x + "w_gate"], p[x + "w_up"] = normal((d, f)), normal((d, f))
            p[x + "w_down"] = normal((f, d))
        fs = f * cfg["num_shared_experts"]
        p[h + "shared.w_gate"] = normal((d, fs))
        p[h + "shared.w_up"] = normal((d, fs))
        p[h + "shared.w_down"] = normal((fs, d))
    return p


def swiglu(x, w_gate, w_up, w_down):
    return C.mm(jax.nn.silu(C.mm(x, w_gate)) * C.mm(x, w_up), w_down)


def attention(x, w, cfg: Dict, window):
    """A head's scores are materialised a block of its queries at a time
    (against all the head's keys), blocks and heads one after the other."""
    b, s, _ = x.shape
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    heads = lambda t, n: t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)
    k = rms_norm(heads(C.mm(x, w["attn.w_k"]), hk), w["attn.k_norm"], eps)
    if window is not None:
        k = rotary(k, theta, jnp.arange(s))
    k = k.reshape(b * hk, s, hd)
    v = heads(C.mm(x, w["attn.w_v"]), hk).reshape(b * hk, s, hd)
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    per_head = s // bq

    def one_block(q, n):            # q (1, bq, D): block n[0] of all heads'
        head, first = n[0] // per_head, (n[0] % per_head) * bq
        i = first + jnp.arange(bq)
        q = rms_norm(q[0], w["attn.q_norm"], eps)
        if window is not None:
            q = rotary(q, theta, i)
        kv = head // (hq // hk)     # (row, query head) -> (row, its kv head)
        scores = C.mm(q, k[kv].T) / math.sqrt(hd)
        seen = jnp.arange(s)[None, :] <= i[:, None]
        if window is not None:
            seen = seen & (i[:, None] - jnp.arange(s)[None, :] < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return C.mm(probs, v[kv])[None]

    q = heads(C.mm(x, w["attn.w_q"]), hq).reshape(b * hq * per_head, bq, hd)
    out = in_blocks(one_block, 1, q, jnp.arange(q.shape[0]))
    out = out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3).reshape(b * s, hq * hd)
    gated = lambda o, t: C.mm(o * jax.nn.sigmoid(C.mm(t, w["attn.w_g"])),
                              w["attn.w_o"])
    return in_blocks(gated, TOKEN_BLOCK, out,
                     x.reshape(b * s, -1)).reshape(x.shape)


def routed(x, w, cfg: Dict):
    """The held experts' part of the routed sum, every held expert run on
    every token and weighted (zero where the token did not pick it)."""
    lo, hi = held(cfg)
    scores = jax.nn.sigmoid(C.mm(x, w["moe.router"]))
    _, sel = jax.lax.top_k(scores + w["moe.expert_bias"],
                           cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["route_norm"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * cfg["route_scale"]

    # one held expert after the other on all the tokens, each recomputed
    # in the backward pass: no loop carries the experts' matrices
    part = jax.checkpoint(lambda weight, *mats: weight[..., None] * swiglu(x, *mats))
    y = jnp.zeros_like(x)
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(sel == e, picked, 0.0), axis=-1)
        x_e = f"moe.experts.{e}."
        y = y + part(weight, w[x_e + "w_gate"], w[x_e + "w_up"],
                     w[x_e + "w_down"])
    return y


def feed_forward(x, w, cfg: Dict):
    """``x`` (tokens, d): the dense MLP, or the held experts' routed part
    plus the shared expert (the dense ones a block of tokens at a time)."""
    dense = lambda p: in_blocks(
        lambda t: swiglu(t, w[p + "w_gate"], w[p + "w_up"], w[p + "w_down"]),
        TOKEN_BLOCK, x)
    if "mlp.w_gate" in w:
        return dense("mlp.")
    return routed(x, w, cfg) + dense("shared.")


def hidden(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, d)``: the last block's output.
    ``remat`` recomputes each block's inside in the backward pass."""
    eps = cfg["rms_norm_eps"]

    def block(x, w, window):
        a = attention(rms_norm(x, w["norm_in"], eps), w, cfg, window)
        x = x + rms_norm(a, w["norm_post_attn"], eps)
        y = rms_norm(x, w["norm_pre_mlp"], eps)
        f = feed_forward(y.reshape(-1, y.shape[-1]), w, cfg).reshape(y.shape)
        return x + rms_norm(f, w["norm_post_mlp"], eps)

    if remat:
        block = jax.checkpoint(block, static_argnums=(2,))
    x = p["embed"][ids]
    if cfg["mup_enabled"]:
        x = x * math.sqrt(cfg["hidden_size"])
    for i, kind in enumerate(cfg["layer_types"]):
        h = f"layers.{i}."
        window = cfg["sliding_window"] if kind == "sliding_attention" else None
        x = block(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)},
                  window)
    return x


def head(p: C.Params, x, cfg: Dict):
    return C.mm(rms_norm(x, p["norm_f"], cfg["rms_norm_eps"]), p["head"])


def logits(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, vocab)`` logits of the full forward."""
    return head(p, hidden(p, ids, cfg, remat), cfg)


def loss_rows(p: C.Params, batch, cfg: Dict):
    """Per row of ``batch = (ids, labels)``, the next-token cross-entropy
    summed over its predicted positions (label -100: not predicted); the
    head and the loss a block of tokens at a time."""
    ids, labels = batch
    x = hidden(p, ids, cfg, remat=True)
    token_loss = lambda t, lab: C.row_loss_sums(
        head(p, t, cfg)[:, None, :], lab[:, None])
    per_token = in_blocks(token_loss, TOKEN_BLOCK,
                          x.reshape(-1, x.shape[-1]), labels.reshape(-1))
    return jnp.sum(per_token.reshape(labels.shape), axis=-1)
