"""``qwen3_next`` (Qwen3-Next-80B-A3B's ``config.json``), written from the
published configuration and the equations of ISSUE 30: a decoder of pre-norm
blocks with zero-centred RMSNorms whose token mixer is a gated delta net
(linear attention with a recurrent state) in three layers of four and gated
softmax attention in the fourth, and softmax-routed experts beside a gated
shared one in every layer.  Plain ``jax.numpy``, float32, ``highest`` matmul
precision; no kernels, no chunked algebra, no sorting, no buffers; imports
nothing of the program.

Per block (eps ``rms_norm_eps``, no biases anywhere, embeddings not scaled)::

    Norm(x) = x * rsqrt(mean(x^2) + eps) * (1 + w)
    h += Mixer_i(Norm_in(h));  h += MoE(Norm_post(h));  logits = W_head Norm_f(h)
    Mixer_i: full attention where (i + 1) % full_attention_interval == 0,
             else the gated delta net

    Gated delta net:  qkvz = x W_qkvz viewed (S, H_k, 2 d_k + 2 r d_v), split
      per key head into q, k (d_k each), v, z (r d_v each; r = H_v / H_k);
      ba = x W_ba viewed (S, H_k, 2 r) -> b, a;  cat(q, k, v) through the
      causal depthwise convolution y_t = sum_{j<4} w[c, j] x_{t-3+j} and SiLU;
      beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias);  q, k
      repeated to the value heads, x * rsqrt(sum x^2 + 1e-6), q times d_k^-0.5;
      THE RECURRENCE, token by token, per head, S_0 = 0:
        S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;  o_t = S^T q_t
      o <- rsqrt(mean(o^2) + eps) o * w_norm * silu(z);  out = W_out concat(o)
    Gated attention:  W_q x viewed (S, H, 2 D) -> query | gate per head; q, k
      Norm'ed over the head; rotary (rotate_half) on the first D *
      partial_rotary_factor dims, inv_freq = theta^(-2j / rot); causal softmax
      at D^-0.5, a key/value head shared by H / H_kv query heads;
      out = W_o (attn * sigmoid(gate))
    MoE:  p = softmax(W_r x) over ALL experts; top-k; w = p_sel / sum p_sel
      (norm_topk_prob);  sum_{e picked, e held} w_e SwiGLU_e(x)
      + sigmoid(x w_sg) SwiGLU_shared(x)

The share, the padded vocabulary and the memory plan are those of
``reference/afmoe.py``: ``cfg["experts_held"]`` names the routed experts whose
weights exist here, the router scores all ``cfg["experts_routed_over"]``, and
what the other experts would add is left out.  The recurrence keeps a
``(H_v, d_k, d_v)`` state: walked in blocks of :data:`SCAN_BLOCK` tokens, each
recomputed in the backward pass, so that no state per token is kept (8192
states of 2 MB would be 17 GB); attention a block of a head's queries at a
time, the token-wise parts a block of tokens at a time.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C
# blocks of rows recomputed in the backward pass, a held range of experts and
# the gated MLP are the older sparse-expert reference's, as plain as this one
from .afmoe import QUERY_BLOCK, TOKEN_BLOCK, held, in_blocks, swiglu

#: tokens of a block of the recurrence
SCAN_BLOCK = 128


def norm(x, w, eps):
    """Zero-centred: the learned scale is ``1 + w``."""
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * (1.0 + w)


def is_full(cfg: Dict, i: int) -> bool:
    return (i + 1) % cfg["full_attention_interval"] == 0


def gdn_sizes(cfg: Dict):
    """``(H_k, H_v, d_k, d_v, r)``."""
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    return hk, hv, cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], hv // hk


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights: N(0, initializer_range) on every matrix (the
    convolution's taps among them), block norms' ``w`` 0, the delta net's
    ``w_norm`` and ``dt_bias`` 1, ``A_log = log U(0, 16)``.  Only the held
    experts' matrices are made, under their own ids."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    hk, hv, dk, dv, r = gdn_sizes(cfg)
    lo, hi = held(cfg)
    f, fs, E = (cfg["moe_intermediate_size"],
                cfg["shared_expert_intermediate_size"],
                cfg["experts_routed_over"])
    std = cfg["assumed"]["initializer_range"]
    keys = iter(jax.random.split(key, 2 + (16 + 3 * (hi - lo)) * L))
    normal = lambda shape: std * jax.random.normal(next(keys), shape, jnp.float32)
    zeros = lambda n: jnp.zeros((n,), jnp.float32)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    p = {"embed": normal((V, d)), "head": normal((d, V)), "norm_f": zeros(d)}
    for i in range(L):
        h = f"layers.{i}."
        p[h + "norm_in"], p[h + "norm_post"] = zeros(d), zeros(d)
        if is_full(cfg, i):
            p[h + "attn.w_q"] = normal((d, hq * 2 * hd))    # query | gate a head
            p[h + "attn.w_k"] = normal((d, hkv * hd))
            p[h + "attn.w_v"] = normal((d, hkv * hd))
            p[h + "attn.w_o"] = normal((hq * hd, d))
            p[h + "attn.q_norm"], p[h + "attn.k_norm"] = zeros(hd), zeros(hd)
        else:
            p[h + "gdn.w_qkvz"] = normal((d, hk * (2 * dk + 2 * r * dv)))
            p[h + "gdn.w_ba"] = normal((d, hk * 2 * r))
            p[h + "gdn.conv"] = normal((2 * hk * dk + hv * dv,
                                        cfg["linear_conv_kernel_dim"]))
            p[h + "gdn.A_log"] = jnp.log(jax.random.uniform(
                next(keys), (hv,), jnp.float32, 1e-4, 16.0))
            p[h + "gdn.dt_bias"], p[h + "gdn.norm"] = ones(hv), ones(dv)
            p[h + "gdn.w_out"] = normal((hv * dv, d))
        p[h + "moe.router"] = normal((d, E))
        for e in range(lo, hi):     # a held expert's matrices: leaves of its own
            x = h + f"moe.experts.{e}."
            p[x + "w_gate"], p[x + "w_up"] = normal((d, f)), normal((d, f))
            p[x + "w_down"] = normal((f, d))
        p[h + "shared.w_gate"], p[h + "shared.w_up"] = normal((d, fs)), normal((d, fs))
        p[h + "shared.w_down"] = normal((fs, d))
        p[h + "shared.gate"] = normal((d, 1))
    return p


# -- the gated delta net ------------------------------------------------------

def causal_conv(x, w):
    """``y_t = sum_j w[c, j] x_{t - (K-1) + j}`` over the rows of ``x``
    (S, channels), zeros before the start: K shifted multiply-adds."""
    s, taps = x.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    return sum(padded[j:j + s] * w[:, j] for j in range(taps))


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token.  ``q``, ``k`` (S, H, d_k), ``v``
    (S, H, d_v), ``g``, ``beta`` (S, H) -> (S, H, d_v).  Blocks of
    :data:`SCAN_BLOCK` tokens, each recomputed in the backward pass: the
    states kept are one a block."""
    hi = jax.lax.Precision.HIGHEST

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, None, None]
        r = v_t - jnp.einsum("hkv,hk->hv", state, k_t, precision=hi)
        state = state + jnp.einsum("hk,hv->hkv", k_t, b_t[:, None] * r,
                                   precision=hi)
        return state, jnp.einsum("hkv,hk->hv", state, q_t, precision=hi)

    s, h, dk = q.shape
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    walk = jax.checkpoint(lambda state, xs: jax.lax.scan(token, state, xs))
    split = lambda a: a.reshape(s // block, block, *a.shape[1:])
    _, out = jax.lax.scan(walk, jnp.zeros((h, dk, v.shape[-1]), jnp.float32),
                          tuple(map(split, (q, k, v, g, beta))))
    return out.reshape(s, h, v.shape[-1])


def gated_delta_net(x, w, cfg: Dict):
    """One row ``x`` (S, d) -> (S, d).  What comes before the recurrence
    is computed a KEY head at a time (its 768 columns of ``W_qkvz``, its 4
    of ``W_ba``, its 512 convolution channels), each head recomputed in the
    backward pass: all sixteen at once hold ~3 GB of float32 intermediates
    beside the four copies of the model.  The recurrence then walks all the
    value heads together."""
    hk, hv, dk, dv, r = gdn_sizes(cfg)
    s, d = x.shape
    per_head = lambda m, width: m.reshape(-1, hk, width).transpose(1, 0, 2)
    conv = w["gdn.conv"]
    heads_w = (
        per_head(w["gdn.w_qkvz"], 2 * dk + 2 * r * dv),        # (H_k, d, 768)
        per_head(w["gdn.w_ba"], 2 * r),                        # (H_k, d, 2 r)
        jnp.concatenate([                                      # (H_k, 512, K)
            conv[:hk * dk].reshape(hk, dk, -1),
            conv[hk * dk:2 * hk * dk].reshape(hk, dk, -1),
            conv[2 * hk * dk:].reshape(hk, r * dv, -1)], axis=1),
        w["gdn.A_log"].reshape(hk, r), w["gdn.dt_bias"].reshape(hk, r))
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                                     + 1e-6)

    @jax.checkpoint
    def before(w_qkvz, w_ba, w_conv, a_log, dt_bias):
        """One key head: q, k (S, d_k), v, z (S, r, d_v), g, beta (S, r)."""
        qkvz, ba = C.mm(x, w_qkvz), C.mm(x, w_ba)
        z = qkvz[:, 2 * dk + r * dv:].reshape(s, r, dv)
        mixed = jax.nn.silu(causal_conv(qkvz[:, :2 * dk + r * dv], w_conv))
        q, k = l2(mixed[:, :dk]) / math.sqrt(dk), l2(mixed[:, dk:2 * dk])
        v = mixed[:, 2 * dk:].reshape(s, r, dv)
        g = -jnp.exp(a_log) * jax.nn.softplus(ba[:, r:] + dt_bias)
        return q, k, v, z, g, jax.nn.sigmoid(ba[:, :r])

    q, k, v, z, g, beta = jax.lax.map(lambda hw: before(*hw), heads_w)
    value_heads = lambda t: t.transpose(1, 0, 2, 3).reshape(s, hv, -1)
    repeated = lambda t: jnp.repeat(t.transpose(1, 0, 2), r, axis=1)
    flat = lambda t: t.transpose(1, 0, 2).reshape(s, hv)
    o = delta_rule(repeated(q), repeated(k), value_heads(v), flat(g), flat(beta))

    def after(o, z):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + cfg["rms_norm_eps"])
        o = o * w["gdn.norm"] * jax.nn.silu(z)
        return C.mm(o.reshape(-1, hv * dv), w["gdn.w_out"])

    return in_blocks(after, TOKEN_BLOCK, o, value_heads(z))


# -- gated full attention -----------------------------------------------------

def rotary(x, theta: float, positions, rot: int):
    """Rotate the first ``rot`` dims of ``x`` (..., seq, D), whose rows stand
    at ``positions``, the two halves of those dims paired; the rest pass."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = (jnp.concatenate([f(ang), f(ang)], -1) for f in (jnp.cos, jnp.sin))
    head, rest = x[..., :rot], x[..., rot:]
    half = jnp.concatenate([-head[..., rot // 2:], head[..., :rot // 2]], -1)
    return jnp.concatenate([head * cos + half * sin, rest], -1)


def attention(x, w, cfg: Dict):
    """One row ``x`` (S, d).  A head's scores are materialised a block of
    its queries at a time (against all the head's keys)."""
    s = x.shape[0]
    hq, hkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   cfg["head_dim"])
    eps, theta = cfg["rms_norm_eps"], float(cfg["rope_theta"])
    rot = int(hd * cfg["partial_rotary_factor"])
    heads = lambda t, n: t.reshape(s, n, hd).transpose(1, 0, 2)
    k = rotary(norm(heads(C.mm(x, w["attn.w_k"]), hkv), w["attn.k_norm"], eps),
               theta, jnp.arange(s), rot)
    v = heads(C.mm(x, w["attn.w_v"]), hkv)
    qg = in_blocks(lambda t: C.mm(t, w["attn.w_q"]), TOKEN_BLOCK, x)
    qg = qg.reshape(s, hq, 2 * hd)
    q, gate = qg[..., :hd], qg[..., hd:]
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    per_head = s // bq

    def one_block(q, n):            # q (1, bq, D): block n[0] of all heads'
        head, first = n[0] // per_head, (n[0] % per_head) * bq
        i = first + jnp.arange(bq)
        q = rotary(norm(q[0], w["attn.q_norm"], eps), theta, i, rot)
        kv = head // (hq // hkv)
        scores = C.mm(q, k[kv].T) / math.sqrt(hd)
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return C.mm(probs, v[kv])[None]

    q = q.transpose(1, 0, 2).reshape(hq * per_head, bq, hd)
    out = in_blocks(one_block, 1, q, jnp.arange(q.shape[0]))
    out = out.reshape(hq, s, hd).transpose(1, 0, 2).reshape(s, hq * hd)
    gated = lambda o, g: C.mm(o * jax.nn.sigmoid(g), w["attn.w_o"])
    return in_blocks(gated, TOKEN_BLOCK, out, gate.reshape(s, hq * hd))


# -- the expert layer ---------------------------------------------------------

def routed(x, w, cfg: Dict):
    """The held experts' part of the routed sum, every held expert run on
    every token and weighted (zero where the token did not pick it)."""
    lo, hi = held(cfg)
    probs = jax.nn.softmax(C.mm(x, w["moe.router"]), axis=-1)
    picked, sel = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, -1, keepdims=True)
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


def shared(x, w):
    return jax.nn.sigmoid(C.mm(x, w["shared.gate"])) * swiglu(
        x, w["shared.w_gate"], w["shared.w_up"], w["shared.w_down"])


def feed_forward(x, w, cfg: Dict):
    """``x`` (tokens, d): the held experts' routed part plus the gated
    shared expert (a block of tokens at a time)."""
    return routed(x, w, cfg) + in_blocks(lambda t: shared(t, w), TOKEN_BLOCK, x)


# -- the model ----------------------------------------------------------------

def hidden(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, d)``: the last block's output.
    ``remat`` recomputes each block's inside in the backward pass."""
    eps = cfg["rms_norm_eps"]

    def block(x, w, full):
        mixer = attention if full else gated_delta_net
        mix = lambda y: jnp.stack([mixer(row, w, cfg) for row in y])
        experts = lambda y: feed_forward(
            y.reshape(-1, y.shape[-1]), w, cfg).reshape(y.shape)
        if remat:
            # each half recomputed on its own inside the recomputed block:
            # the mixer's and the expert layer's intermediates (~1 and ~2 GB)
            # are then never held together
            mix, experts = jax.checkpoint(mix), jax.checkpoint(experts)
        x = x + mix(norm(x, w["norm_in"], eps))
        return x + experts(norm(x, w["norm_post"], eps))

    if remat:
        block = jax.checkpoint(block, static_argnums=(2,))
    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        x = block(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)},
                  is_full(cfg, i))
    return x


def head(p: C.Params, x, cfg: Dict):
    return C.mm(norm(x, p["norm_f"], cfg["rms_norm_eps"]), p["head"])


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
