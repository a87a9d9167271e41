"""``smallthinker`` (SmallThinker-21BA3B-Instruct's ``config.json``), written
from the published configuration, the model's description and the equations
of ISSUE 37: a decoder of pre-norm blocks, grouped-query attention over a
rotary sliding window in three layers of four and over the whole causal
context with NO position signal in the fourth (which comes first), and in
every layer routed experts of ReGLU units, no shared expert, whose ROUTER
READS THE BLOCK'S INPUT, ahead of the input norm and attention.  Plain
``jax.numpy``, float32, ``highest`` matmul precision; no kernels, no sorting,
no buffers; imports nothing of the program.

Block ``i`` on ``x`` (no biases anywhere; embeddings not scaled; head untied)::

    Norm(x) = x * rsqrt(mean(x^2) + eps) * w                eps = rms_norm_eps
    r  = x W_r                                  # logits over ALL experts, from the block's INPUT
    y  = Norm_in(x);  q, k, v = y W_q, y W_k, y W_v         # H, H_kv, H_kv heads of D
    if rope_layout[i]:  q, k = rotary(q), rotary(k)         # whole head, rotate_half, theta
    a  = softmax(q k^T / sqrt(D), causal [and i - j < window if sliding_window_layout[i]]) v
    h  = x + a W_o
    u  = Norm_post(h)
    (s, e) = top_k(r);  w = softmax(s)          # the PUBLISHED order: pick, then a softmax
                                                # over the picked logits (norm_topk_prob then
                                                # divides by their sum, which is 1)
    out = h + sum_{j, e_j held} w_j W_down[e_j] (relu(W_gate[e_j] u) * (W_up[e_j] u))

``logits = W_head Norm_f(h)``.  The gradient of ``relu`` at 0 is 0.

Left out, and said so under the configuration's ``assumed``: the secondary
experts of the model's description (a predictor of which of an expert's
neurons fire, for inference from slow storage): the published configuration
holds no key of it and training computes the dense unit.

The share.  ``cfg["experts_held"] = [first, past_last]`` names the routed
experts whose weights exist here; the router still scores all
``cfg["experts_routed_over"]``; what a token's other experts would add is not
in the result — what one chip of the expert-parallel job computes before the
exchange.  With every expert held this is the whole model.  ``vocab_size`` is
the (padded) slice of the vocabulary held here.

Memory.  At the cell's size (16,384 tokens, 371M parameters, of which the
training steps hold four float32 copies, 5.9 GB) the 28 heads' scores would
be 30 GB a layer, so a head's scores are materialised a block of 1024 of its
queries at a time (against all 16,384 keys of its key/value head) and the
token-wise parts (projections, the output projection, head and loss) a block
of tokens at a time, the held experts one after the other, each recomputed
in the backward pass (:func:`in_blocks`): the same arithmetic on the same
numbers.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C

# What the sparse-expert references share, from the oldest: the blocks'
# sizes, the norm, the rotation over halves, recomputation a block of rows at
# a time and the held experts' range.
from .afmoe import QUERY_BLOCK, TOKEN_BLOCK, held, in_blocks, rms_norm, rotary


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights: N(0, ``initializer_range``) on every matrix, norms 1;
    the embedding's rows N(0, ``embedding_initializer_range``) where the
    configuration's ``assumed`` gives one (the router reads the un-normed
    stream: what stands in it beside the embedding decides how the seeded
    tokens route, ``assumed.embedding_why``).  Only the held experts'
    matrices are made, under their own ids (``moe.experts.<id>.``)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    lo, hi = held(cfg)
    f, E = cfg["moe_ffn_hidden_size"], cfg["experts_routed_over"]
    std = cfg["assumed"]["initializer_range"]
    embed_std = cfg["assumed"].get("embedding_initializer_range", std)
    keys = iter(jax.random.split(key, 2 + (5 + 3 * (hi - lo)) * L))
    normal = lambda shape, s=std: s * jax.random.normal(next(keys), shape,
                                                        jnp.float32)
    p = {"embed": normal((V, d), embed_std), "head": normal((d, V)),
         "norm_f": jnp.ones((d,), jnp.float32)}
    for i in range(L):
        h = f"layers.{i}."
        p[h + "norm_in"] = jnp.ones((d,), jnp.float32)
        p[h + "norm_post"] = jnp.ones((d,), jnp.float32)
        p[h + "attn.w_q"] = normal((d, hq * hd))
        p[h + "attn.w_k"] = normal((d, hk * hd))
        p[h + "attn.w_v"] = normal((d, hk * hd))
        p[h + "attn.w_o"] = normal((hq * hd, d))
        p[h + "moe.router"] = normal((d, E))
        for e in range(lo, hi):     # a held expert's matrices: leaves of its own
            x = h + f"moe.experts.{e}."
            p[x + "w_gate"], p[x + "w_up"] = normal((d, f)), normal((d, f))
            p[x + "w_down"] = normal((f, d))
    return p


def reglu(x, w_gate, w_up, w_down):
    return C.mm(jax.nn.relu(C.mm(x, w_gate)) * C.mm(x, w_up), w_down)


def attention(y, w, cfg: Dict, window, rotate: bool):
    """``y`` (rows, seq, d) -> (rows, seq, d).  A head's scores are
    materialised a block of its queries at a time (against all the keys of
    its key/value head), blocks and heads one after the other."""
    b, s, _ = y.shape
    hq, hk, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                  cfg["head_dim"])
    theta = float(cfg["rope_theta"])
    tokens = y.reshape(b * s, -1)
    project = lambda name: in_blocks(lambda t: C.mm(t, w[name]),
                                     TOKEN_BLOCK, tokens)
    heads = lambda t, n: t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)
    k = heads(project("attn.w_k"), hk)
    if rotate:
        k = rotary(k, theta, jnp.arange(s))
    k = k.reshape(b * hk, s, hd)
    v = heads(project("attn.w_v"), hk).reshape(b * hk, s, hd)
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    per_head = s // bq

    def one_block(q, n):            # q (1, bq, D): block n[0] of all heads'
        head, first = n[0] // per_head, (n[0] % per_head) * bq
        i = first + jnp.arange(bq)
        q = rotary(q[0], theta, i) if rotate else q[0]
        kv = head // (hq // hk)     # (row, query head) -> (row, its kv head)
        scores = C.mm(q, k[kv].T) / math.sqrt(hd)
        seen = jnp.arange(s)[None, :] <= i[:, None]
        if window is not None:
            seen = seen & (i[:, None] - jnp.arange(s)[None, :] < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return C.mm(probs, v[kv])[None]

    q = heads(project("attn.w_q"), hq).reshape(b * hq * per_head, bq, hd)
    out = in_blocks(one_block, 1, q, jnp.arange(q.shape[0]))
    out = out.reshape(b, hq, s, hd).transpose(0, 2, 1, 3).reshape(b * s, hq * hd)
    return in_blocks(lambda o: C.mm(o, w["attn.w_o"]), TOKEN_BLOCK,
                     out).reshape(y.shape)


def routing(logits, cfg: Dict):
    """``(sel (T, k), weights (T, k))`` in the published order: the ``k``
    largest LOGITS are picked, then a softmax over the picked logits alone
    (``moe_primary_router_apply_softmax``); ``norm_topk_prob`` divides by
    their sum."""
    picked, sel = jax.lax.top_k(logits, cfg["moe_num_active_primary_experts"])
    if not cfg["moe_primary_router_apply_softmax"]:
        raise ValueError("only softmax over the picked logits is written")
    weights = jax.nn.softmax(picked, axis=-1)
    if cfg["norm_topk_prob"]:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return sel, weights


def routed(u, logits, w, cfg: Dict):
    """The held experts' part of the routed sum on ``u`` (tokens, d), routed
    by ``logits`` (tokens, experts): every held expert run on every token and
    weighted (zero where the token did not pick it)."""
    lo, hi = held(cfg)
    sel, weights = routing(logits, cfg)

    # one held expert after the other on all the tokens, each recomputed
    # in the backward pass: no loop carries the experts' matrices
    part = jax.checkpoint(lambda weight, *mats: weight[..., None] * reglu(u, *mats))
    y = jnp.zeros_like(u)
    for e in range(lo, hi):
        weight = jnp.sum(jnp.where(sel == e, weights, 0.0), axis=-1)
        x_e = f"moe.experts.{e}."
        y = y + part(weight, w[x_e + "w_gate"], w[x_e + "w_up"],
                     w[x_e + "w_down"])
    return y


def block(x, w, cfg: Dict, window, rotate: bool):
    """One block on ``x`` (rows, seq, d)."""
    eps, flat = cfg["rms_norm_eps"], lambda t: t.reshape(-1, t.shape[-1])
    logits = C.mm(flat(x), w["moe.router"])         # from the block's INPUT
    h = x + attention(rms_norm(x, w["norm_in"], eps), w, cfg, window, rotate)
    u = rms_norm(h, w["norm_post"], eps)
    return h + routed(flat(u), logits, w, cfg).reshape(x.shape)


def layer_kinds(cfg: Dict):
    """``[(window or None, rotated)]`` a layer, from the published pair of
    lists."""
    if len(cfg["rope_layout"]) != len(cfg["sliding_window_layout"]):
        raise ValueError("rope_layout and sliding_window_layout differ in length")
    return [(cfg["sliding_window_size"] if windowed else None, bool(rotated))
            for windowed, rotated in zip(cfg["sliding_window_layout"],
                                         cfg["rope_layout"])]


def hidden(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, d)``: the last block's output.
    ``remat`` recomputes each block's inside in the backward pass."""
    run = lambda x, w, window, rotate: block(x, w, cfg, window, rotate)
    if remat:
        run = jax.checkpoint(run, static_argnums=(2, 3))
    x = p["embed"][ids]
    for i, (window, rotate) in enumerate(layer_kinds(cfg)):
        h = f"layers.{i}."
        x = run(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)},
                window, rotate)
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
