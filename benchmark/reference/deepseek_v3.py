"""``deepseek_v3`` (Moonlight-16B-A3B's ``config.json``, ``model_type``
deepseek_v3), written from the published configuration and the equations of
ISSUE 32: a decoder of pre-norm blocks, multi-head LATENT attention (keys and
values made from one low-rank latent a token, one rotary key shared by all
heads, queries and keys wider than values), a dense SwiGLU in the leading
layer and sigmoid-routed experts under a selection bias beside a shared
expert after it.  Plain ``jax.numpy``, float32, ``highest`` matmul precision;
no kernels, no sorting, no buffers; imports nothing of the program.

Per block (no biases anywhere; embeddings not scaled; head untied)::

    Norm(x) = x * rsqrt(mean(x^2) + eps) * w
    h += MLA(Norm_in(h));  h += FF_i(Norm_post(h));  logits = W_head Norm_f(h)
    FF_i dense   = W_down (silu(W_gate x) * W_up x)         i < first_k_dense_replace
    FF_i experts = SwiGLU_shared(x) + sum_{e in top-k(s + b), e held} w_e SwiGLU_e(x)
        s = sigmoid(W_r x) over ALL experts; w = s[sel] / (sum s[sel] + 1e-20)
        * routed_scaling_factor   (topk_method noaux_tc at n_group 1: no group limit)

    MLA on x (S, d), H heads, d_qk = qk_nope_head_dim + qk_rope_head_dim:
      q = x W_q viewed (S, H, d_qk), per head q_nope then q_pe
      x W_dkv (kv_lora_rank + qk_rope_head_dim wide) = the latent c, then ONE
        rotary key k_pe for all heads
      c <- Norm(c) over the latent (eps 1e-6, the modelling code's default: assumed)
      c W_ukv viewed (S, H, qk_nope_head_dim + v_head_dim), per head k_nope then v
      q_pe, k_pe rotated by position over ADJACENT pairs (2j, 2j + 1),
        inv_freq_j = theta^(-2j / qk_rope_head_dim)
      q_h = [q_nope_h, q_pe_h];  k_h = [k_nope_h, k_pe]
      o_h = softmax(q_h k_h^T d_qk^-0.5, causal) v_h;  out = W_o concat_heads(o)

Departures from the published description, each under the configuration's
``assumed``: the rotation is written on adjacent pairs IN PLACE (the modelling
code de-interleaves the slice first and rotates its halves, q and k alike: a
permutation of the rotated slice, the same scores); ``kv_a_layernorm``'s eps;
N(0, ``initializer_range``) weights; no router auxiliary loss (``seq_aux``)
and no update of the selection bias: the step is the plain causal-LM loss.

The share.  ``cfg["experts_held"] = [first, past_last]`` names the routed
experts whose weights exist here; the router still scores all
``cfg["experts_routed_over"]``; what a token's other experts would add is not
in the result — what one chip of the expert-parallel job computes before the
exchange.  With every expert held this is the whole model.  ``vocab_size`` is
the slice of the vocabulary held here.

Memory.  At the cell's size (8192 tokens, 669M parameters, of which the
training steps hold four float32 copies, 10.0 GiB) the sixteen heads' scores
would be 17 GB a layer, so a head's scores are materialised a block of its
queries at a time and the token-wise parts (projections, feed-forwards, head
and loss) a block of tokens at a time, each recomputed in the backward pass
(:func:`in_blocks`): the same arithmetic on the same numbers.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C

# What the sparse-expert references share, from the oldest: the blocks'
# sizes, the norm, recomputation a block of rows at a time, the held experts'
# range and the gated MLP.
from . import afmoe
from .afmoe import QUERY_BLOCK, TOKEN_BLOCK, held, in_blocks, rms_norm, swiglu

#: ``kv_a_layernorm``'s eps: the modelling code's default, not ``rms_norm_eps``
LATENT_NORM_EPS = 1e-6


def rotary_pairs(x, theta: float, positions):
    """Rotate ``x`` (..., seq, D), whose rows stand at ``positions`` (seq,),
    over ADJACENT pairs of dims: ``(x[2j], x[2j + 1])`` turns by ``position *
    theta^(-2j / D)``."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    even, odd = x[..., 0::2], x[..., 1::2]
    return jnp.stack([even * cos - odd * sin, odd * cos + even * sin],
                     axis=-1).reshape(x.shape)


def is_dense(cfg: Dict, i: int) -> bool:
    return i < cfg["first_k_dense_replace"]


def sizes(cfg: Dict):
    """``(heads, qk_nope, qk_rope, v, latent)`` widths."""
    return (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
            cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights: N(0, ``initializer_range``) on every matrix, norms 1,
    the router's selection bias 0.  Only the held experts' matrices are made,
    under their own ids (``moe.experts.<id>.``)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    h, dn, dr, dv, r = sizes(cfg)
    lo, hi = held(cfg)
    f, fd, E = (cfg["moe_intermediate_size"], cfg["intermediate_size"],
                cfg["experts_routed_over"])
    std = cfg["assumed"]["initializer_range"]
    keys = iter(jax.random.split(key, 2 + (8 + 3 * (hi - lo)) * L))
    normal = lambda shape: std * jax.random.normal(next(keys), shape, jnp.float32)
    p = {"embed": normal((V, d)), "head": normal((d, V)),
         "norm_f": jnp.ones((d,), jnp.float32)}
    for i in range(L):
        x = f"layers.{i}."
        p[x + "norm_in"] = jnp.ones((d,), jnp.float32)
        p[x + "norm_post"] = jnp.ones((d,), jnp.float32)
        p[x + "attn.w_q"] = normal((d, h * (dn + dr)))
        p[x + "attn.w_dkv"] = normal((d, r + dr))
        p[x + "attn.latent_norm"] = jnp.ones((r,), jnp.float32)
        p[x + "attn.w_ukv"] = normal((r, h * (dn + dv)))
        p[x + "attn.w_o"] = normal((h * dv, d))
        if is_dense(cfg, i):
            p[x + "mlp.w_gate"] = normal((d, fd))
            p[x + "mlp.w_up"] = normal((d, fd))
            p[x + "mlp.w_down"] = normal((fd, d))
            continue
        p[x + "moe.router"] = normal((d, E))
        p[x + "moe.expert_bias"] = jnp.zeros((E,), jnp.float32)
        for e in range(lo, hi):     # a held expert's matrices: leaves of its own
            y = x + f"moe.experts.{e}."
            p[y + "w_gate"], p[y + "w_up"] = normal((d, f)), normal((d, f))
            p[y + "w_down"] = normal((f, d))
        fs = f * cfg["n_shared_experts"]    # the shared experts: ONE SwiGLU
        p[x + "shared.w_gate"] = normal((d, fs))
        p[x + "shared.w_up"] = normal((d, fs))
        p[x + "shared.w_down"] = normal((fs, d))
    return p


def latent_kv(x, w, cfg: Dict):
    """``x`` (tokens, d) -> ``(k_nope | v as (tokens, H (d_nope + d_v)),
    k_pe (tokens, d_rope))``: the down-projection, the latent's norm, the
    up-projection; the rotary key not yet rotated."""
    r = cfg["kv_lora_rank"]
    down = C.mm(x, w["attn.w_dkv"])
    c = rms_norm(down[:, :r], w["attn.latent_norm"], LATENT_NORM_EPS)
    return C.mm(c, w["attn.w_ukv"]), down[:, r:]


def attention(x, w, cfg: Dict):
    """Latent attention on ``x`` (rows, seq, d).  A head's scores are
    materialised a block of its queries at a time (against all the head's
    keys), blocks and heads one after the other."""
    b, s, d = x.shape
    h, dn, dr, dv, _ = sizes(cfg)
    theta = cfg["rope_theta"]
    tokens = x.reshape(b * s, d)
    kv, k_pe = in_blocks(lambda t: latent_kv(t, w, cfg), TOKEN_BLOCK, tokens)
    kv = kv.reshape(b, s, h, dn + dv).transpose(0, 2, 1, 3)
    k_pe = rotary_pairs(k_pe.reshape(b, 1, s, dr), theta, jnp.arange(s))
    k = jnp.concatenate(        # ONE rotary key, the same for every head
        [kv[..., :dn], jnp.broadcast_to(k_pe, (b, h, s, dr))], axis=-1)
    k, v = k.reshape(b * h, s, dn + dr), kv[..., dn:].reshape(b * h, s, dv)
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    per_head = s // bq

    def one_block(q, n):            # q (1, bq, d_qk): block n[0] of all heads'
        head, first = n[0] // per_head, (n[0] % per_head) * bq
        i = first + jnp.arange(bq)
        q = jnp.concatenate(
            [q[0, :, :dn], rotary_pairs(q[0, :, dn:], theta, i)], axis=-1)
        scores = C.mm(q, k[head].T) * (dn + dr) ** -0.5
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return C.mm(probs, v[head])[None]

    q = in_blocks(lambda t: C.mm(t, w["attn.w_q"]), TOKEN_BLOCK, tokens)
    q = q.reshape(b, s, h, dn + dr).transpose(0, 2, 1, 3)
    out = in_blocks(one_block, 1, q.reshape(b * h * per_head, bq, dn + dr),
                    jnp.arange(b * h * per_head))
    out = out.reshape(b, h, s, dv).transpose(0, 2, 1, 3).reshape(b * s, h * dv)
    return in_blocks(lambda o: C.mm(o, w["attn.w_o"]), TOKEN_BLOCK,
                     out).reshape(x.shape)


def routed(x, w, cfg: Dict):
    """The held experts' part of the routed sum.  ``noaux_tc`` at one group
    IS the older reference's routing — sigmoid scores over all experts, the k
    largest of score + bias, the picked scores over their sum (+ 1e-20) times
    a scale, every held expert run on every token and weighted — under this
    configuration's names for the two switches."""
    return afmoe.routed(x, w, {**cfg, "route_norm": cfg["norm_topk_prob"],
                               "route_scale": cfg["routed_scaling_factor"]})


def shared(x, w):
    """The shared experts (one SwiGLU of their summed width), a block of
    tokens at a time."""
    return in_blocks(
        lambda t: swiglu(t, w["shared.w_gate"], w["shared.w_up"],
                         w["shared.w_down"]), TOKEN_BLOCK, x)


def feed_forward(x, w, cfg: Dict):
    """``x`` (tokens, d): the dense MLP, or the held experts' routed part
    plus the shared experts."""
    if "mlp.w_gate" in w:
        return in_blocks(
            lambda t: swiglu(t, w["mlp.w_gate"], w["mlp.w_up"], w["mlp.w_down"]),
            TOKEN_BLOCK, x)
    return routed(x, w, cfg) + shared(x, w)


def hidden(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, d)``: the last block's output.
    ``remat`` recomputes each block's mixer and its feed-forward, each on
    its own, in the backward pass."""
    eps = cfg["rms_norm_eps"]
    keep = jax.checkpoint if remat else (lambda f: f)

    def block(x, w):
        x = x + keep(lambda t: attention(rms_norm(t, w["norm_in"], eps), w, cfg))(x)

        def ff(t):
            y = rms_norm(t, w["norm_post"], eps)
            return feed_forward(y.reshape(-1, y.shape[-1]), w, cfg).reshape(y.shape)
        return x + keep(ff)(x)

    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        x = block(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)})
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
