"""``kimi_linear`` (Kimi-Linear-48B-A3B-Instruct's ``config.json``), written
from the published configuration and the equations of ISSUE 46: a decoder of
pre-norm blocks whose token mixer is Kimi Delta Attention (KDA: the gated
delta rule with a log-decay for EVERY key channel of every head, behind short
convolutions, with a low-rank decay gate and a low-rank output gate) in the
layers ``linear_attn_config.kda_layers`` lists and position-free multi-head
latent attention in those ``full_attn_layers`` lists, a dense SwiGLU in the
leading layer and sigmoid-routed experts under a selection bias beside a
shared expert after it.  Plain ``jax.numpy``, float32, ``highest`` matmul
precision; no kernels, no chunked algebra, no sorting, no buffers; imports
nothing of the program.

Per block (eps ``rms_norm_eps``, no biases anywhere, embeddings not scaled,
head untied)::

    Norm(x) = x * rsqrt(mean(x^2) + eps) * w
    h += Mixer_i(Norm_in(h));  h += FF_i(Norm_post(h));  logits = W_head Norm_f(h)
    Mixer_i: latent attention where i + 1 is in full_attn_layers, else KDA
    FF_i dense   = W_down (silu(W_gate x) * W_up x)         i < first_k_dense_replace
    FF_i experts = SwiGLU_shared(x) + sum_{e in top-k(s + b), e held} w_e SwiGLU_e(x)
        s = sigmoid(W_r x) over ALL experts; w = s[sel] / (sum s[sel] + 1e-20)
        * routed_scaling_factor   (one group: no group limit)

    KDA on x (S, d), H heads of d_h (keys and values alike), r = d_h:
      q, k, v = silu(conv(x W_q)), silu(conv(x W_k)), silu(conv(x W_v)), the
        causal depthwise convolution y_t = sum_{j<4} w[c, j] x_{t-3+j}
      q_h <- q_h * rsqrt(sum q_h^2 + 1e-6) * d_h^-0.5;  k_h likewise, unscaled
      g = -exp(A_log_h) softplus((x W_fa) W_fb + dt_bias)      (S, H, d_h), <= 0
      beta = sigmoid(x W_b)                                    (S, H)
      THE RECURRENCE, token by token, per head, S_0 = 0 (d_h, d_h):
        S <- Diag(exp g_t) S;  r = v_t - S^T k_t;  S <- S + beta_t k_t r^T;  o_t = S^T q_t
      o <- rsqrt(mean(o^2) + eps) o * w_norm * sigmoid((x W_ga) W_gb);  out = W_o concat(o)

    Latent attention (position-free, ``mla_use_nope``), d_qk = 128 + 64:
      q = x W_q viewed (S, H, d_qk);  x W_dkv = the latent c (kv_lora_rank),
      then ONE further key k_pe (64) for all heads;  c <- Norm(c) (eps 1e-6);
      c W_ukv viewed (S, H, 128 + d_v) -> k_nope, v;  k_h = [k_nope_h, k_pe];
      o_h = softmax(q_h k_h^T d_qk^-0.5, causal) v_h;  out = W_o concat(o)
      — ``reference/deepseek_v3.py``'s with NOTHING rotated.

The share, the vocabulary slice and the memory plan are those of
``reference/afmoe.py`` and ``reference/qwen3_next.py``: ``cfg["experts_held"]``
names the routed experts whose weights exist here, the router scores all
``cfg["experts_routed_over"]``, and what the other experts would add is left
out.  The recurrence keeps an ``(H, d_h, d_h)`` state: walked in blocks of
:data:`SCAN_BLOCK` tokens, each recomputed in the backward pass; what comes
before it a head at a time; attention a block of a head's queries at a time;
the token-wise parts a block of tokens at a time.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C
# What the sparse-expert references share, from the oldest: recomputation a
# block of rows at a time, the held experts' range, the norm, the gated MLP
# and the sigmoid routing under a selection bias.
from . import afmoe
from .afmoe import QUERY_BLOCK, TOKEN_BLOCK, held, in_blocks, rms_norm, swiglu
# the latent's down-projection, norm and up-projection, the shared expert and
# which layers lead with a dense MLP
from .deepseek_v3 import is_dense, latent_kv, shared, sizes
# the short convolution: four shifted products
from .qwen3_next import causal_conv

#: tokens of a block of the recurrence
SCAN_BLOCK = 128


def is_full(cfg: Dict, i: int) -> bool:
    """Whether layer ``i`` (0-indexed) mixes by latent attention: the
    published lists are 1-indexed."""
    return i + 1 in cfg["linear_attn_config"]["full_attn_layers"]


def kda_sizes(cfg: Dict):
    """``(heads, head size, convolution taps, the low-rank gates' width)``;
    the gates' inner width is the head size."""
    lin = cfg["linear_attn_config"]
    return (lin["num_heads"], lin["head_dim"], lin["short_conv_kernel_size"],
            lin["head_dim"])


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights: N(0, ``initializer_range``) on every matrix (the
    convolution's taps among them), norms 1, ``dt_bias`` 1, ``A_log = log
    U(0, 16)`` a head, the router's selection bias 0.  Only the held experts'
    matrices are made, under their own ids (``moe.experts.<id>.``)."""
    d, L, V = cfg["hidden_size"], cfg["num_hidden_layers"], cfg["vocab_size"]
    h, dn, dr, dv, r = sizes(cfg)
    hk, hd, taps, rank = kda_sizes(cfg)
    lo, hi = held(cfg)
    f, fd, E = (cfg["moe_intermediate_size"], cfg["intermediate_size"],
                cfg["experts_routed_over"])
    std = cfg["assumed"]["initializer_range"]
    keys = iter(jax.random.split(key, 2 + (16 + 3 * (hi - lo)) * L))
    normal = lambda shape: std * jax.random.normal(next(keys), shape, jnp.float32)
    ones = lambda n: jnp.ones((n,), jnp.float32)
    p = {"embed": normal((V, d)), "head": normal((d, V)), "norm_f": ones(d)}
    for i in range(L):
        x = f"layers.{i}."
        p[x + "norm_in"], p[x + "norm_post"] = ones(d), ones(d)
        if is_full(cfg, i):
            p[x + "attn.w_q"] = normal((d, h * (dn + dr)))
            p[x + "attn.w_dkv"] = normal((d, r + dr))
            p[x + "attn.latent_norm"] = ones(r)
            p[x + "attn.w_ukv"] = normal((r, h * (dn + dv)))
            p[x + "attn.w_o"] = normal((h * dv, d))
        else:
            for n in ("w_q", "w_k", "w_v"):
                p[x + "kda." + n] = normal((d, hk * hd))
            for n in ("f", "g"):    # the decay gate and the output gate
                p[x + f"kda.w_{n}a"] = normal((d, rank))
                p[x + f"kda.w_{n}b"] = normal((rank, hk * hd))
            p[x + "kda.conv"] = normal((3 * hk * hd, taps))    # [q | k | v]
            p[x + "kda.w_b"] = normal((d, hk))
            p[x + "kda.A_log"] = jnp.log(jax.random.uniform(
                next(keys), (hk,), jnp.float32, 1e-4, 16.0))
            p[x + "kda.dt_bias"], p[x + "kda.norm"] = ones(hk * hd), ones(hd)
            p[x + "kda.w_o"] = normal((hk * hd, d))
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
        fs = f * cfg["num_shared_experts"]
        p[x + "shared.w_gate"] = normal((d, fs))
        p[x + "shared.w_up"] = normal((d, fs))
        p[x + "shared.w_down"] = normal((fs, d))
    return p


# -- Kimi Delta Attention -----------------------------------------------------

def delta_rule(q, k, v, g, beta):
    """The recurrence token by token.  ``q``, ``k``, ``g`` (S, H, d_k), ``v``
    (S, H, d_v), ``beta`` (S, H) -> (S, H, d_v).  Blocks of
    :data:`SCAN_BLOCK` tokens, each recomputed in the backward pass: the
    states kept are one a block."""
    hi = jax.lax.Precision.HIGHEST

    def token(state, x):
        q_t, k_t, v_t, g_t, b_t = x
        state = state * jnp.exp(g_t)[:, :, None]    # a decay a key channel
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


def kda(x, w, cfg: Dict):
    """One row ``x`` (S, d) -> (S, d).  What comes before the recurrence is
    computed a head at a time (its 128 columns of each projection, its 384
    convolution channels), each head recomputed in the backward pass; the
    recurrence then walks all the heads together."""
    h, hd, taps, rank = kda_sizes(cfg)
    s, d = x.shape
    per_head = lambda m: m.reshape(-1, h, hd).transpose(1, 0, 2)
    heads_w = (
        *(per_head(w["kda." + n]) for n in ("w_q", "w_k", "w_v", "w_fb", "w_gb")),
        w["kda.conv"].reshape(3, h, hd, taps).transpose(1, 0, 2, 3),
        w["kda.w_b"].T, w["kda.A_log"], w["kda.dt_bias"].reshape(h, hd))
    low_f, low_g = C.mm(x, w["kda.w_fa"]), C.mm(x, w["kda.w_ga"])
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(jnp.square(t), -1, keepdims=True)
                                     + 1e-6)

    @jax.checkpoint
    def before(w_q, w_k, w_v, w_fb, w_gb, conv, w_b, a_log, dt_bias):
        """One head: q, k, v, g, the output gate (S, d_h) and beta (S,)."""
        q, k, v = (jax.nn.silu(causal_conv(C.mm(x, m), taps_of))
                   for m, taps_of in zip((w_q, w_k, w_v), conv))
        g = -jnp.exp(a_log) * jax.nn.softplus(C.mm(low_f, w_fb) + dt_bias)
        return (l2(q) * hd ** -0.5, l2(k), v, g,
                jax.nn.sigmoid(C.mm(low_g, w_gb)),
                jax.nn.sigmoid(C.mm(x, w_b[:, None])[:, 0]))

    q, k, v, g, gate, beta = jax.lax.map(lambda hw: before(*hw), heads_w)
    seq_major = lambda t: jnp.swapaxes(t, 0, 1)
    o = delta_rule(*map(seq_major, (q, k, v, g, beta)))

    def after(o, gate):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), -1, keepdims=True)
                              + cfg["rms_norm_eps"])
        o = o * w["kda.norm"] * gate
        return C.mm(o.reshape(-1, h * hd), w["kda.w_o"])

    return in_blocks(after, TOKEN_BLOCK, o, seq_major(gate))


# -- position-free latent attention -------------------------------------------

def attention(x, w, cfg: Dict):
    """One row ``x`` (S, d): ``reference/deepseek_v3.py``'s latent attention
    with nothing rotated.  A head's scores are materialised a block of its
    queries at a time (against all the head's keys)."""
    s, d = x.shape
    h, dn, dr, dv, _ = sizes(cfg)
    kv, k_pe = in_blocks(lambda t: latent_kv(t, w, cfg), TOKEN_BLOCK, x)
    kv = kv.reshape(s, h, dn + dv).transpose(1, 0, 2)
    k = jnp.concatenate(        # ONE further key, the same for every head
        [kv[..., :dn], jnp.broadcast_to(k_pe[None], (h, s, dr))], axis=-1)
    v = kv[..., dn:]
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    per_head = s // bq

    def one_block(q, n):            # q (1, bq, d_qk): block n[0] of all heads'
        head, first = n[0] // per_head, (n[0] % per_head) * bq
        i = first + jnp.arange(bq)
        scores = C.mm(q[0], k[head].T) * (dn + dr) ** -0.5
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return C.mm(probs, v[head])[None]

    q = in_blocks(lambda t: C.mm(t, w["attn.w_q"]), TOKEN_BLOCK, x)
    q = q.reshape(s, h, dn + dr).transpose(1, 0, 2)
    out = in_blocks(one_block, 1, q.reshape(h * per_head, bq, dn + dr),
                    jnp.arange(h * per_head))
    out = out.reshape(h, s, dv).transpose(1, 0, 2).reshape(s, h * dv)
    return in_blocks(lambda o: C.mm(o, w["attn.w_o"]), TOKEN_BLOCK, out)


# -- the feed-forwards ----------------------------------------------------------

def routed(x, w, cfg: Dict):
    """The held experts' part of the routed sum: ``reference/afmoe.py``'s
    sigmoid routing under this configuration's names for its switches."""
    return afmoe.routed(x, w, {
        **cfg, "route_norm": cfg["moe_renormalize"],
        "route_scale": cfg["routed_scaling_factor"],
        "num_experts_per_tok": cfg["num_experts_per_token"]})


def feed_forward(x, w, cfg: Dict):
    """``x`` (tokens, d): the dense MLP, or the held experts' routed part
    plus the shared expert."""
    if "mlp.w_gate" in w:
        return in_blocks(
            lambda t: swiglu(t, w["mlp.w_gate"], w["mlp.w_up"], w["mlp.w_down"]),
            TOKEN_BLOCK, x)
    return routed(x, w, cfg) + shared(x, w)


# -- the model ----------------------------------------------------------------

def hidden(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, d)``: the last block's output.
    ``remat`` recomputes each block's mixer and its feed-forward, each on
    its own, in the backward pass."""
    eps = cfg["rms_norm_eps"]
    keep = jax.checkpoint if remat else (lambda f: f)

    def block(x, w, full):
        mixer = attention if full else kda
        mix = lambda t: jnp.stack(
            [mixer(row, w, cfg) for row in rms_norm(t, w["norm_in"], eps)])
        x = x + keep(mix)(x)

        def ff(t):
            y = rms_norm(t, w["norm_post"], eps)
            return feed_forward(y.reshape(-1, y.shape[-1]), w, cfg).reshape(y.shape)
        return x + keep(ff)(x)

    x = p["embed"][ids]
    for i in range(cfg["num_hidden_layers"]):
        h = f"layers.{i}."
        x = block(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)},
                  is_full(cfg, i))
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
