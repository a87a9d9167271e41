"""``lfm2_moe`` (LFM2-24B-A2B's ``config.json``), written from the published
configuration, the model's description ("gated short conv; GQA"; "64 experts,
top-4, 0 shared") and the equations of ISSUE 39: a decoder of pre-norm blocks
whose sequence mixer is a GATED SHORT CONVOLUTION in the ``conv`` layers and
grouped-query attention with normed, rotated queries and keys in the
``full_attention`` ones; a dense SwiGLU in the leading ``num_dense_layers``
and, past them, sigmoid-routed experts under a selection bias, none shared;
the head tied to the embedding.  Plain ``jax.numpy``, float32, ``highest``
matmul precision; no kernels, no sorting, no buffers; imports nothing of the
program.

Block ``i`` on ``h`` (no biases anywhere; embeddings not scaled)::

    Norm(x) = x * rsqrt(mean(x^2) + eps) * w                    eps = norm_eps
    y = Norm_op(h)
    conv layer:       [B | C | X] = y W_in                       # thirds, in this order
                      u = B * X
                      c_t = sum_{j<K} taps[:, j] * u_{t-(K-1)+j} # K = conv_L_cache; depthwise;
                                                                 # zeros before the row's start; no activation
                      a = (C * c) W_out
    attention layer:  q, k, v = y W_q, y W_k, y W_v              # H, H_kv, H_kv heads of D
                      q, k = Norm_q(q), Norm_k(k)                # per head over its D dims
                      q, k = rotary(q), rotary(k)                # whole head, rotate_half, theta
                      a = softmax(q k^T / sqrt(D), causal) v W_o
    h = h + a
    z = Norm_ffn(h)
    i < num_dense_layers:  h = h + W_down (silu(W_gate z) * W_up z)
    else:  s = sigmoid(z W_r) over ALL experts;  e = top_k(s + b)   (use_expert_bias)
           g = s_e / (sum s_e + route_norm_eps) * routed_scaling_factor   (norm_topk_prob)
           h = h + sum_{j, e_j held} g_j W_down[e_j] (silu(W_gate[e_j] z) * W_up[e_j] z)
    logits = Norm_f(h) E^T

The share.  ``cfg["experts_held"] = [first, past_last]`` names the routed
experts whose weights exist here; the router still scores all
``cfg["experts_routed_over"]``; what a token's other experts would add is not
in the result — what one chip of the expert-parallel job computes before the
exchange.  With every expert held this is the whole model.  ``vocab_size`` is
the (padded) slice of the vocabulary held here: rows of the embedding, and so
columns of the tied head.

Memory.  At the cell's size (16,384 tokens, 469M parameters, of which the
training steps hold four float32 copies, 7.5 GB) the 32 heads' scores would
be 34 GB, so a head's scores are materialised a block of 1024 of its queries
at a time (against all 16,384 keys of its key/value head) and the token-wise
parts (projections, the dense feed-forward 11,776 wide, head and loss) a
block of tokens at a time, the held experts one after the other, each
recomputed in the backward pass (:func:`in_blocks`): the same arithmetic on
the same numbers.  The convolution runs on the whole row: three shifted
multiply-adds on a zero-padded copy of ``u`` (134 MB).
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C

# What the sparse-expert references share, from the oldest: the blocks'
# sizes, the norm, the rotation over halves, the gated unit, recomputation a
# block of rows at a time and the held experts' range.
from .afmoe import (QUERY_BLOCK, TOKEN_BLOCK, held, in_blocks, rms_norm,
                    rotary, swiglu)

CONV, FULL = "conv", "full_attention"


def is_dense(cfg: Dict, i: int) -> bool:
    return i < cfg["num_dense_layers"]


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights: N(0, ``initializer_range``) on every matrix and on
    the convolutions' taps, norms 1, the selection bias 0.  No head: it is
    the embedding.  Only the held experts' matrices are made, under their
    own ids (``moe.experts.<id>.``)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    lo, hi = held(cfg)
    f, fd, E = (cfg["moe_intermediate_size"], cfg["intermediate_size"],
                cfg["experts_routed_over"])
    std = cfg["assumed"]["initializer_range"]
    keys = iter(jax.random.split(key, 1 + (8 + 3 * (hi - lo)) * L))
    normal = lambda shape: std * jax.random.normal(next(keys), shape, jnp.float32)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    p = {"embed": normal((V, d)), "norm_f": ones(d)}
    for i, kind in enumerate(cfg["layer_types"]):
        h = f"layers.{i}."
        p[h + "norm_op"], p[h + "norm_ffn"] = ones(d), ones(d)
        if kind == CONV:
            p[h + "conv.w_in"] = normal((d, 3 * d))
            p[h + "conv.taps"] = normal((d, cfg["conv_L_cache"]))
            p[h + "conv.w_out"] = normal((d, d))
        else:
            p[h + "attn.w_q"] = normal((d, hq * hd))
            p[h + "attn.w_k"] = normal((d, hk * hd))
            p[h + "attn.w_v"] = normal((d, hk * hd))
            p[h + "attn.w_o"] = normal((hq * hd, d))
            p[h + "attn.q_norm"], p[h + "attn.k_norm"] = ones(hd), ones(hd)
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
    return p


def short_conv(u, taps):
    """``c_t = sum_j taps[:, j] * u_{t-(K-1)+j}`` on ``u`` (rows, seq, d):
    ``K`` shifted multiply-adds on the row padded with ``K - 1`` zeros in
    front."""
    k, s = taps.shape[1], u.shape[1]
    padded = jnp.pad(u, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(padded[:, j:j + s] * taps[:, j] for j in range(k))


def conv_mixer(y, w):
    """``y`` (rows, seq, d) -> (rows, seq, d): the gated short convolution
    between its two projections, the projections a block of tokens at a time."""
    b, s, d = y.shape
    bcx = in_blocks(lambda t: C.mm(t, w["conv.w_in"]), TOKEN_BLOCK,
                    y.reshape(b * s, d)).reshape(b, s, 3 * d)
    gate_in, gate_out, x = jnp.split(bcx, 3, axis=-1)       # [B | C | X]
    mixed = gate_out * short_conv(gate_in * x, w["conv.taps"])
    return in_blocks(lambda t: C.mm(t, w["conv.w_out"]), TOKEN_BLOCK,
                     mixed.reshape(b * s, d)).reshape(y.shape)


def attention(y, w, cfg: Dict):
    """``y`` (rows, seq, d) -> (rows, seq, d).  A head's scores are
    materialised a block of its queries at a time (against all the keys of
    its key/value head), blocks and heads one after the other."""
    b, s, d = y.shape
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    eps, theta = cfg["norm_eps"], float(cfg["rope_parameters"]["rope_theta"])
    tokens = y.reshape(b * s, d)
    project = lambda name: in_blocks(lambda t: C.mm(t, w[name]),
                                     TOKEN_BLOCK, tokens)
    heads = lambda t, n: t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)
    k = rms_norm(heads(project("attn.w_k"), hk), w["attn.k_norm"], eps)
    k = rotary(k, theta, jnp.arange(s)).reshape(b * hk, s, hd)
    v = heads(project("attn.w_v"), hk).reshape(b * hk, s, hd)
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    per_head = s // bq

    def one_block(q, n):            # q (1, bq, D): block n[0] of all heads'
        head, first = n[0] // per_head, (n[0] % per_head) * bq
        i = first + jnp.arange(bq)
        q = rotary(rms_norm(q[0], w["attn.q_norm"], eps), theta, i)
        kv = head // (hq // hk)     # (row, query head) -> (row, its kv head)
        scores = C.mm(q, k[kv].T) / math.sqrt(hd)
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return C.mm(probs, v[kv])[None]

    q = heads(project("attn.w_q"), hq).reshape(b * hq * per_head, bq, hd)
    out = in_blocks(one_block, 1, q, jnp.arange(q.shape[0]))
    out = out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3).reshape(b * s, hq * hd)
    return in_blocks(lambda o: C.mm(o, w["attn.w_o"]), TOKEN_BLOCK,
                     out).reshape(y.shape)


def routing(z, w, cfg: Dict):
    """``(sel (T, k), weights (T, k))``: sigmoid scores over ALL experts,
    the ``k`` largest of score + bias picked (the bias steers the selection
    only), the picked SCORES over their sum (+ ``route_norm_eps``) times
    ``routed_scaling_factor``."""
    scores = jax.nn.sigmoid(C.mm(z, w["moe.router"]))
    steered = scores + w["moe.expert_bias"] if cfg["use_expert_bias"] else scores
    _, sel = jax.lax.top_k(steered, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    if cfg["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True)
                           + cfg["assumed"]["route_norm_eps"])
    return sel, picked * cfg["routed_scaling_factor"]


def routed(z, w, cfg: Dict):
    """The held experts' part of the routed sum on ``z`` (tokens, d): every
    held expert run on every token and weighted (zero where the token did
    not pick it).  No shared expert."""
    lo, hi = held(cfg)
    sel, weights = routing(z, w, cfg)

    # one held expert after the other on all the tokens, each recomputed
    # in the backward pass: no loop carries the experts' matrices
    part = jax.checkpoint(lambda weight, *mats: weight[..., None] * swiglu(z, *mats))
    y = jnp.zeros_like(z)
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(sel == e, weights, 0.0), axis=-1)
        x_e = f"moe.experts.{e}."
        y = y + part(weight, w[x_e + "w_gate"], w[x_e + "w_up"],
                     w[x_e + "w_down"])
    return y


def feed_forward(z, w, cfg: Dict):
    """``z`` (tokens, d): the dense MLP a block of tokens at a time, or the
    held experts' routed part."""
    if "mlp.w_gate" in w:
        return in_blocks(
            lambda t: swiglu(t, w["mlp.w_gate"], w["mlp.w_up"], w["mlp.w_down"]),
            TOKEN_BLOCK, z)
    return routed(z, w, cfg)


def block(x, w, cfg: Dict, kind: str):
    """One block on ``x`` (rows, seq, d)."""
    eps = cfg["norm_eps"]
    y = rms_norm(x, w["norm_op"], eps)
    h = x + (conv_mixer(y, w) if kind == CONV else attention(y, w, cfg))
    z = rms_norm(h, w["norm_ffn"], eps)
    return h + feed_forward(z.reshape(-1, z.shape[-1]), w, cfg).reshape(x.shape)


def hidden(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, d)``: the last block's output.
    ``remat`` recomputes each block's inside in the backward pass."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    run = lambda x, w, kind: block(x, w, cfg, kind)
    if remat:
        run = jax.checkpoint(run, static_argnums=(2,))
    x = p["embed"][ids]
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in (CONV, FULL):
            raise ValueError(f"no layer type {kind!r}")
        h = f"layers.{i}."
        x = run(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)},
                kind)
    return x


def head(p: C.Params, x, cfg: Dict):
    """The tied head: the embedding's rows are its columns."""
    return C.mm(rms_norm(x, p["norm_f"], cfg["norm_eps"]), p["embed"].T)


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
