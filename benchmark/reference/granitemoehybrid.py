"""``granitemoehybrid`` (Granite-4.0-H-Micro's ``config.json``), written from
the published configuration, the model's description ("Mamba-2 + GQA", "dense
(no MoE)") and the equations of ISSUE 43: a decoder of pre-norm blocks whose
sequence mixer is a MAMBA-2 STATE-SPACE LAYER in the ``mamba`` layers and
grouped-query attention WITHOUT positions, at a published score scale, in the
``attention`` ones; a dense SwiGLU in every layer; four multipliers (on the
embedding, on each branch before it joins the stream, on the scores, under
the logits); the head tied to the embedding; no experts.  Plain
``jax.numpy``, float32, ``highest`` matmul precision; no kernels, no chunked
algebra; imports nothing of the program.

No bias anywhere except the convolution's.  ``d`` hidden, ``H`` heads of ``P``
channels (``d_in = H P``), ``N`` the state size, ``G`` groups, ``K`` taps::

    Norm(x) = x * rsqrt(mean(x^2) + eps) * w                 eps = rms_norm_eps
    h_0 = E[ids] * embedding_multiplier
    block i:   y = Norm_in(h)
      mamba:   [z | xBC | dt] = y W_in                       # d_in | d_in + 2 G N | H
               xBC = silu(conv_K(xBC) + b_conv)              # depthwise, causal: y_t = sum_j w[:, j] x_{t-(K-1)+j},
                                                             # zeros before the row's start
               x, B, C = split(xBC, [d_in, G N, G N])        # x as (T, H, P); B, C (T, G, N), head h reads group h // (H / G)
               dt = softplus(dt + dt_bias)                   # (T, H), no clamp
               THE RECURRENCE, token by token, per head, S_0 = 0 (P, N):
                 S_t = exp(dt_t * -exp(A_log)) S_{t-1} + dt_t * x_t B_t^T
                 o_t = S_t C_t + D * x_t
               m = Norm_gate(o * silu(z)) W_out              # over all d_in channels (one group)
      attention: q, k, v = y W_q, y W_k, y W_v               # H_q, H_kv, H_kv heads of d / H_q; no positions
               m = softmax(q k^T * attention_multiplier, causal) v W_o
      h = h + residual_multiplier * m
      u = Norm_post(h)
      h = h + residual_multiplier * W_down (silu(u W_gate) * u W_up)
    logits = (Norm_f(h) E^T) / logits_scaling

``vocab_size`` is the slice of the vocabulary held here: rows of the
embedding, and so columns of the tied head.

Memory.  At the cell's size (8192 tokens, 772M parameters, of which the
training steps hold four float32 copies, 12.35 GB, and the gradient a fifth
while it is made) little is left, so a mamba layer's mixer is computed a
block of :data:`TOKEN_BLOCK` tokens at a time — projection, convolution,
recurrence, gate, norm and output — each block recomputed in the backward
pass and handing the next the recurrence's ``(H, P, N)`` state (2.1 MB at 64
x 64 x 128) and the convolution's last ``K - 1`` inputs; inside a block the
recurrence is walked in blocks of :data:`SCAN_BLOCK` tokens, each recomputed
again, so that no state per token is kept beyond the block being walked
(8192 of them would be 17 GB); attention a block of a head's queries at a
time; the other token-wise parts (the attention layer's projections, the
feed-forward 8192 wide, head and loss) a block of tokens at a time, each
recomputed (:func:`in_blocks`); and a block's mixer and its feed-forward are
each recomputed on their own in the backward pass, which is made to finish a
half — its weights' gradients among it — before it goes on to the half before
(:func:`recomputed`: left to itself the compiler holds a half's intermediates
until the end of the program).  Compiled for a described v5e the step's
temporaries are 2.84 GiB beside 11.51 GiB of copies
(``benchmark/tests/test_granite_cell.py``).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp

from . import common as C
# blocks of rows recomputed in the backward pass, the norm and the gated unit
# are the oldest sparse-expert reference's, as plain as this one
from .afmoe import QUERY_BLOCK, TOKEN_BLOCK, in_blocks, rms_norm, swiglu

MAMBA, ATTENTION = "mamba", "attention"
#: tokens of a block of the recurrence (its backward pass keeps a handful of
#: (H, P, N) arrays a token of the block it walks: 10 MB each at 64 x 64 x 128)
SCAN_BLOCK = 16


def sizes(cfg: Dict):
    """``(H, P, N, G, K, d_in, conv channels)`` of a mamba layer."""
    h, p = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, g = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    return h, p, n, g, cfg["mamba_d_conv"], h * p, h * p + 2 * g * n


def init_params(key, cfg: Dict) -> C.Params:
    """Seeded weights: N(0, ``initializer_range``) on every matrix, the
    convolution's taps and the embedding; norms 1; the convolution's bias 0;
    ``D`` 1; ``A_log = log A`` with ``A ~ U[1, 16]``; ``dt_bias =
    softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3, 1e-1], floored at
    1e-4 (the public Mamba-2 code's initial values).  No head: it is the
    embedding."""
    d, V = cfg["hidden_size"], cfg["vocab_size"]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, f = d // hq, cfg["intermediate_size"]
    h, p, n, g, taps, d_in, conv = sizes(cfg)
    std = cfg["assumed"]["initializer_range"]
    keys = iter(jax.random.split(key, 1 + 8 * cfg["num_hidden_layers"]))
    normal = lambda shape: std * jax.random.normal(next(keys), shape, jnp.float32)
    ones = lambda m: jnp.ones((m,), jnp.float32)
    params = {"embed": normal((V, d)), "norm_f": ones(d)}
    for i, kind in enumerate(cfg["layer_types"]):
        x = f"layers.{i}."
        params[x + "norm_in"], params[x + "norm_post"] = ones(d), ones(d)
        if kind == MAMBA:
            params[x + "mamba.w_in"] = normal((d, d_in + conv + h))
            params[x + "mamba.conv_w"] = normal((conv, taps))
            params[x + "mamba.conv_b"] = jnp.zeros((conv,), jnp.float32)
            params[x + "mamba.A_log"] = jnp.log(jax.random.uniform(
                next(keys), (h,), jnp.float32, 1.0, 16.0))
            dt = jnp.maximum(jnp.exp(jax.random.uniform(
                next(keys), (h,), jnp.float32, jnp.log(1e-3), jnp.log(1e-1))),
                1e-4)
            params[x + "mamba.dt_bias"] = dt + jnp.log(-jnp.expm1(-dt))
            params[x + "mamba.D"], params[x + "mamba.norm"] = ones(h), ones(d_in)
            params[x + "mamba.w_out"] = normal((d_in, d))
        else:
            params[x + "attn.w_q"] = normal((d, hq * hd))
            params[x + "attn.w_k"] = normal((d, hk * hd))
            params[x + "attn.w_v"] = normal((d, hk * hd))
            params[x + "attn.w_o"] = normal((hq * hd, d))
        params[x + "mlp.w_gate"] = normal((d, f))
        params[x + "mlp.w_up"] = normal((d, f))
        params[x + "mlp.w_down"] = normal((f, d))
    return params


# -- the state-space layer ----------------------------------------------------

def recurrence(x, dt, a, bm, cm, skip, state):
    """The recurrence token by token from ``state`` (H, P, N).  ``x`` (T, H,
    P), ``dt`` (T, H), ``a`` (H,) negative, ``bm``, ``cm`` (T, G, N) — head
    ``h`` reads group ``h // (H / G)`` —, ``skip`` (H,) -> ``((T, H, P), the
    state after the last token)``.  Blocks of :data:`SCAN_BLOCK` tokens, each
    recomputed in the backward pass: the states kept are one a block."""
    hi = jax.lax.Precision.HIGHEST
    per_group = x.shape[1] // bm.shape[1]

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp
        b_t, c_t = (jnp.repeat(t, per_group, axis=0) for t in (b_t, c_t))
        state = (state * jnp.exp(dt_t * a)[:, None, None]
                 + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :])
        o_t = jnp.einsum("hpn,hn->hp", state, c_t, precision=hi)
        return state, o_t + skip[:, None] * x_t

    s, h, p = x.shape
    block = SCAN_BLOCK if s % SCAN_BLOCK == 0 else s
    walk = jax.checkpoint(lambda state, xs: jax.lax.scan(token, state, xs))
    split = lambda t: t.reshape(s // block, block, *t.shape[1:])
    state, out = jax.lax.scan(walk, state, tuple(map(split, (x, dt, bm, cm))))
    return out.reshape(s, h, p), state


def mamba_mixer(y, w, cfg: Dict):
    """One row ``y`` (S, d) -> (S, d), :data:`TOKEN_BLOCK` tokens at a time
    and each block recomputed in the backward pass: a block hands the next
    the recurrence's state and the last ``K - 1`` rows that entered its
    convolution (zeros, both, before the row's start) — the same arithmetic
    on the same numbers as the row at once, whose projection (8512 wide) and
    per-token states would not fit beside the copies of the model."""
    h, p, n, g, taps, d_in, conv = sizes(cfg)
    s, d = y.shape
    block = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s
    a, eps = -jnp.exp(w["mamba.A_log"]), cfg["rms_norm_eps"]

    def one(carry, y_block):
        state, before = carry
        z, xbc, dt = jnp.split(C.mm(y_block, w["mamba.w_in"]),
                               [d_in, d_in + conv], axis=-1)
        padded = jnp.concatenate([before, xbc])
        xbc = jax.nn.silu(sum(padded[j:j + block] * w["mamba.conv_w"][:, j]
                              for j in range(taps)) + w["mamba.conv_b"])
        x, bm, cm = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
        o, state = recurrence(
            x.reshape(block, h, p), jax.nn.softplus(dt + w["mamba.dt_bias"]),
            a, bm.reshape(block, g, n), cm.reshape(block, g, n), w["mamba.D"],
            state)
        gated = rms_norm(o.reshape(block, d_in) * jax.nn.silu(z),
                         w["mamba.norm"], eps)
        return (state, padded[block:]), C.mm(gated, w["mamba.w_out"])

    start = (jnp.zeros((h, p, n), jnp.float32),
             jnp.zeros((taps - 1, conv), jnp.float32))
    _, out = jax.lax.scan(jax.checkpoint(one), start,
                          y.reshape(s // block, block, d))
    return out.reshape(s, d)


# -- attention without positions ----------------------------------------------

def attention(y, w, cfg: Dict):
    """One row ``y`` (S, d) -> (S, d).  A head's scores are materialised a
    block of its queries at a time (against all the keys of its key/value
    head), blocks and heads one after the other; the scores' scale is
    ``attention_multiplier``."""
    s, d = y.shape
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // hq
    project = lambda name: in_blocks(lambda t: C.mm(t, w[name]), TOKEN_BLOCK, y)
    heads = lambda t, m: t.reshape(s, m, hd).transpose(1, 0, 2)
    k, v = heads(project("attn.w_k"), hk), heads(project("attn.w_v"), hk)
    bq = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s
    per_head = s // bq

    def one_block(q, m):            # q (1, bq, D): block m[0] of all heads'
        head, first = m[0] // per_head, (m[0] % per_head) * bq
        i = first + jnp.arange(bq)
        kv = head // (hq // hk)     # a query head -> its key/value head
        scores = C.mm(q[0], k[kv].T) * cfg["attention_multiplier"]
        seen = jnp.arange(s)[None, :] <= i[:, None]
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return C.mm(probs, v[kv])[None]

    q = heads(project("attn.w_q"), hq).reshape(hq * per_head, bq, hd)
    out = in_blocks(one_block, 1, q, jnp.arange(q.shape[0]))
    out = out.reshape(hq, s, hd).transpose(1, 0, 2).reshape(s, hq * hd)
    return in_blocks(lambda o: C.mm(o, w["attn.w_o"]), TOKEN_BLOCK, out)


# -- the model ----------------------------------------------------------------

def recomputed(fn):
    """``fn(x, w)`` with a backward pass that keeps ``x`` and ``w`` alone,
    computes ``fn`` again and FINISHES — every gradient of ``w`` among it —
    before it hands ``x``'s gradient on: ``jax.checkpoint`` with the two
    fences (``optimization_barrier``) the compiler is otherwise free to leave
    out.  Without the second it may put a half's weight gradients off to the
    end of the program (only the program's result reads them) and hold what
    they are made from until then: the step's temporaries read 5.4 GiB so,
    which does not fit beside the copies of the model."""
    @jax.custom_vjp
    def run(x, w):
        return fn(x, w)

    def forward(x, w):
        return fn(x, w), (x, w)

    def backward(kept, dy):
        x, w, dy = jax.lax.optimization_barrier((*kept, dy))
        return jax.lax.optimization_barrier(jax.vjp(fn, x, w)[1](dy))

    run.defvjp(forward, backward)
    return run


def hidden(p: C.Params, ids, cfg: Dict, remat: bool = False):
    """``(rows, seq) -> (rows, seq, d)``: the last block's output.
    ``remat`` recomputes each HALF of a block — its mixer, its feed-forward
    — on its own in the backward pass (:func:`recomputed`): what is kept is
    each half's normed input and the stream beside it, and the two halves'
    intermediates are never held together."""
    if len(cfg["layer_types"]) != cfg["num_hidden_layers"]:
        raise ValueError("layer_types does not name num_hidden_layers layers")
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    again = recomputed if remat else (lambda fn: fn)

    def block(x, w, kind):
        mixer = mamba_mixer if kind == MAMBA else attention
        mix = again(lambda y, w: jnp.stack([mixer(row, w, cfg) for row in y]))
        mlp = again(lambda u, w: in_blocks(
            lambda t: swiglu(t, w["mlp.w_gate"], w["mlp.w_up"], w["mlp.w_down"]),
            TOKEN_BLOCK, u.reshape(-1, u.shape[-1])).reshape(u.shape))
        x = x + res * mix(rms_norm(x, w["norm_in"], eps), w)
        return x + res * mlp(rms_norm(x, w["norm_post"], eps), w)

    x = p["embed"][ids] * cfg["embedding_multiplier"]
    for i, kind in enumerate(cfg["layer_types"]):
        if kind not in (MAMBA, ATTENTION):
            raise ValueError(f"no layer type {kind!r}")
        h = f"layers.{i}."
        x = block(x, {k[len(h):]: v for k, v in p.items() if k.startswith(h)},
                  kind)
    return x


def head(p: C.Params, x, cfg: Dict):
    """The tied head: the embedding's rows are its columns; the logits
    divided by ``logits_scaling``."""
    return C.mm(rms_norm(x, p["norm_f"], cfg["rms_norm_eps"]),
                p["embed"].T) / cfg["logits_scaling"]


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
