"""The delta rule with a decay of its own for every key channel (Kimi Delta
Attention, KDA) over a sequence, by chunks — two Pallas TPU kernels that hold
everything between q, k, v, g, beta and o in VMEM, and a ``lax.scan`` path.

``ops/gated_delta.py``'s rule with the scalar log-decay ``g_t`` of a head
become a VECTOR over the head's ``d_k`` key channels
(``models/kimi_linear.py``'s mixer).  Per head, a float32 state ``S`` of shape
``(d_k, d_v)``::

    S <- Diag(exp g_t) S;  r = v_t - S^T k_t;  S <- S + beta_t k_t r^T;  o_t = S^T q_t

with ``g_t <= 0`` (d_k values a token a head) and ``beta_t`` in (0, 1); q, k
and v share their heads.

**The chunked form** is the scalar rule's with ``G`` (C, d_k) the running sum
of ``g`` over the chunk, a channel at a time::

    (I + A) U = beta (V - (exp G . K) S_0),  A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)  (j < i)
    O  = (exp G . Q) S_0 + P U,              P_ij = sum_d q_id k_jd exp(G_id - G_jd)         (j <= i)
    S' = Diag(exp G_C) S_0 + (exp(G_C - G) . K)^T U

**Why this is a file of its own, not ``gated_delta.py`` widened.**  With one
decay a head ``A = (K K^T) . M``: a product, then a mask of decays ``exp(G_i -
G_j)`` made from a (C, C) difference.  With a decay a channel the decay sits
INSIDE the sum over ``d`` and ``A`` is no product of ``K`` with itself.  As
one product it needs ``k_i . exp(G_i - G_ref)`` and ``k_j . exp(G_ref -
G_j)``, and the second factor overflows float32 inside one chunk (-21 a token
a channel at the strongest decay, +1300 over 64 tokens) unless ``G_ref`` lies
between ``j`` and ``i``.  So a chunk is cut into sub-blocks of
:data:`SUB_BLOCK` tokens:

- a sub-block ``I`` against every token BEFORE it: one product, the reference
  row the sub-block's first (``G_r``): ``[q_i ; k_i] . exp(G_i - G_r)`` against
  ``k_j . exp(G_r - G_j)`` — both exponents are never positive; a factor that
  underflows to 0 stands for a decay that is smaller still;
- a sub-block against ITSELF: channel by channel on the VPU, a sub-diagonal
  at a time — for the offset ``o = i - j`` the rows ``G`` and ``K`` rolled down
  by ``o``, ``exp(min(G - G_rolled, 0))``, a product and a sum over the lanes:
  ``SUB_BLOCK - 1`` passes over a (C, d) tile, no pair's exponent ever
  positive.

The scalar rule's kernels, their operands and ``qwen3-next.train-8k``'s
program stay as they are, to the byte; what the two files share —
``(I + A)^-1`` by doubling, the products' helpers, the small (B, S, H) arrays'
layouts — this one imports.

**On the TPU two kernels,** which take their operands as their producers
leave them.  ``apex_kda_fwd`` (grid (rows, head groups, chunks), the heads'
float32 states in VMEM scratch) reads a chunk's q, k, v — blocks of the (B,
S, H d) arrays the model has, in its compute dtype —, ``beta`` and the
log-decay ``g`` ((B, S, H d_k) float32, a token's own: as large as q and k
together, the one float32 array of that size that crosses HBM, once).  On the
(C, d) tile of a head it holds it makes the running sum ``G`` of ``g`` from
the chunk's start (:func:`_chunk_sum`: a product with a triangle of ones on
the idle MXU, float32 sums) and, where the caller says so (``qk_norm``), the
l2 norm of q and k — float32, rounded to the operands' dtype once —; then
``A``, ``P``, ``T = (I + A)^-1``, the three lines, and writes o, the state at
each chunk's START and, for the backward pass, ``T`` and ``P`` in the
operands' dtype.  ``apex_kda_bwd`` walks the chunks from the last with ``dS``
in scratch, makes the forward's values again but ``T`` and ``P`` (the norm and
``G`` among them), and carries on through ``dA = -(T^T dU) U^T``, ``dP = dO
U^T`` and every decay to dq, dk, dv, dbeta and dG; through the decays of ``A``
and ``P`` a channel's ``dG`` is ``q . dq + k . dk_row - k . dk_col`` of the
parts of dq and dk that came through them.  It sums dG back over the tokens
that follow in the chunk and writes ``dg``; with ``qk_norm`` its dq and dk
are those in the q and k it was handed (``r (d - u sum(u d))`` a row, ``u``
the unit vector, ``r`` the reciprocal norm).  XLA does nothing to an array of
q's size on either side of the kernels: no norm, no running sum, no cast.

**Off the TPU, and as the kernels' oracle,** a ``lax.scan`` over the chunks
whose body makes ``G`` by ``jnp.cumsum`` and the (C, C, d) decays outright
(masked BEFORE the exponential), in float32, differentiated by JAX, the norm
(where asked) ``jax.numpy``'s in front of it: an independent composition.
It also takes the shapes :func:`supported` refuses.

**Precision** as the scalar rule's: float32 arithmetic, an operand rounded to
v's dtype once, where it enters a product; ``S``, ``dS``, ``G``, the norms
and every decay stay float32.  With float32 inputs (the tests, in interpret
mode) the kernels compute in float32 throughout.  The decays read
DIFFERENCES of ``G``, which reaches hundreds inside a chunk: its float32
spacing there (~3e-5), not the order of the sum, is the chunked form's
distance from the token recurrence (~1e-5 of the largest output, scan path
and kernels alike, against the recurrence in float64).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import pallas_call as _pallas_call, pallas_default
from apex_tpu.ops.gated_delta import (_COMPILER_PARAMS, DEFAULT_CHUNK, _NN,
                                      _NT, _TN, ConvLayout, _chunk_masks,
                                      _dot, _small_layouts, _trace_key,
                                      _tri_inverse_tiles, conv_columns,
                                      tri_inverse)
from apex_tpu.remat import KDA_OUT, KDA_SCORES, KDA_STATES, KDA_TRI

__all__ = ["kda_rule", "kda_rule_recurrent", "split_conv_qkv", "supported",
           "SUB_BLOCK"]

#: tokens of a sub-block of a chunk: against itself a sub-block costs
#: ``SUB_BLOCK - 1`` passes over the chunk on the VPU, against what came
#: before it one small product
SUB_BLOCK = 16
#: heads a grid step of the kernels takes together, at most
_HEADS_PER_STEP = 4


# ---------------------------------------------------------------------------
# the short convolution in front of the rule
# ---------------------------------------------------------------------------

def split_conv_qkv(qkv, w, *, heads: int, head_dim: int,
                   use_pallas: Optional[bool] = None):
    """The fused projection's output cut into q, k and v, each through the
    short causal depthwise convolution and SiLU on the way — read where it
    lies, by ``ops/gated_delta.py``'s convolution under a layout of this
    mixer's.

    ``qkv`` (B, S, 3 H d) laid out per head ``[q d | k d | v d]`` (a group a
    head, each part one lane tile at d = 128, nothing handed through), ``w``
    (3 H d, K) with its channels in the order ``[q | k | v]``, each over all
    heads; no bias.  Returns ``(q, k, v)``, each (B, S, H d) in ``qkv``'s
    dtype.  On the TPU, where the shapes tile, the ``apex_conv1d_*`` kernel
    pair; else ``jax.numpy``.  The gauge ``kda.conv_kernel`` says which was
    traced."""
    d = head_dim
    if qkv.shape[2] != 3 * heads * d:
        raise ValueError(f"a width of {qkv.shape[2]} is not {heads} heads "
                         f"of [q {d} | k {d} | v {d}]")
    lay = ConvLayout(heads, 3 * d, tuple((i * d, d, i, 0) for i in range(3)),
                     (d, d, d), ())
    return conv_columns(qkv, w, None, lay, use_pallas, "kda.conv_kernel")


# ---------------------------------------------------------------------------
# the token recurrence: the definition, and the oracle of the tests
# ---------------------------------------------------------------------------

def kda_rule_recurrent(q, k, v, g, beta):
    """The rule token by token (``lax.scan`` over the sequence), float32 at
    ``highest`` precision: the definition the chunked form is held to.
    Shapes as :func:`kda_rule`."""
    hi = jax.lax.Precision.HIGHEST
    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x           # (B, H, d) / (B, H)
        state = state * jnp.exp(g_t)[..., None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * r,
                                   precision=hi)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)

    b, _, h, dk = q.shape
    init = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, init, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


# ---------------------------------------------------------------------------
# the chunks chained by lax.scan: the path off the TPU, the kernels' oracle
# ---------------------------------------------------------------------------

def _chunk_step(state, x):
    """One chunk of every (row, head) at once: ``state`` (BH, d_k, d_v),
    ``x`` = q, k (BH, C, d_k), v (BH, C, d_v), g (BH, C, d_k), beta (BH, C),
    float32.  The (C, C, d_k) decays are made outright, every exponent a
    masked non-positive difference."""
    q, k, v, g, beta = x
    c = q.shape[1]
    big_g = jnp.cumsum(g, axis=1)
    lower = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    diff = big_g[:, :, None, :] - big_g[:, None, :, :]
    kd = k[:, None] * jnp.exp(jnp.where(lower[..., None], diff, -jnp.inf))
    a = jnp.where(strict, jnp.einsum("bid,bijd->bij", k, kd), 0.0)
    p = jnp.einsum("bid,bijd->bij", q, kd)
    t = tri_inverse(beta[..., None] * a)
    gamma = jnp.exp(big_g)
    u = t @ (beta[..., None] * (v - (gamma * k) @ state))
    o = (gamma * q) @ state + p @ u
    last = big_g[:, -1:]
    new = (jnp.swapaxes(jnp.exp(last), -1, -2) * state
           + jnp.swapaxes(jnp.exp(last - big_g) * k, -1, -2) @ u)
    return new, o


# ---------------------------------------------------------------------------
# the rule in kernels
# ---------------------------------------------------------------------------

def _rows(x):
    return jnp.sum(x, axis=1, keepdims=True)


def _against_earlier(g, first: int):
    """The two factors of sub-block ``first // SUB_BLOCK``'s decays against every
    EARLIER token, the reference row the sub-block's first: ``(exp(G_i -
    G_r) (sub, d), exp(G_r - G_j) (C, d) — 0 from the sub-block's first row
    on)``; no exponent is positive."""
    ref = g[first:first + 1, :]
    earlier = jax.lax.broadcasted_iota(jnp.int32, g.shape, 0) < first
    return (jnp.exp(g[first:first + SUB_BLOCK, :] - ref),
            jnp.where(earlier, jnp.exp(jnp.minimum(ref - g, 0.0)), 0.0))


def _rolled(x, off: int):
    """Row ``i`` holds ``x``'s row ``i - off`` (the first ``off`` rows wrap:
    their readers mask them)."""
    return pltpu.roll(x, off, 0)


def _on_offset(row, col, off: int):
    """The (C, C) entries ``i - j == off`` whose ``i`` and ``j`` lie in one
    sub-block."""
    return (row - col == off) & ((row & (SUB_BLOCK - 1)) >= off)


def _scores(q, k, g, row, col, mx):
    """``(a (C, C) strictly lower — A before beta —, P (C, C) lower)`` of one
    head from q, k and the running sum G, all (C, d) float32."""
    c, sub = q.shape[0], SUB_BLOCK
    a = jnp.zeros((c, c), jnp.float32)
    p = jnp.where(row == col, _rows(q * k), 0.0)
    for off in range(1, sub):           # a sub-block against itself
        ke = _rolled(k, off) * jnp.exp(jnp.minimum(g - _rolled(g, off), 0.0))
        on = _on_offset(row, col, off)
        a = jnp.where(on, _rows(k * ke), a)
        p = jnp.where(on, _rows(q * ke), p)
    a_rows = [jnp.zeros((sub, c), jnp.float32)]
    p_rows = [jnp.zeros((sub, c), jnp.float32)]
    for first in range(sub, c, sub):    # a sub-block against what came before
        e_row, e_col = _against_earlier(g, first)
        both = jnp.concatenate([q[first:first + sub] * e_row,
                                k[first:first + sub] * e_row], axis=0)
        res = _dot(mx(both), mx(k * e_col), _NT)            # (2 sub, C)
        p_rows.append(res[:sub])
        a_rows.append(res[sub:])
    return (a + jnp.concatenate(a_rows, axis=0),
            p + jnp.concatenate(p_rows, axis=0))


def _as_column(x_row, eye):
    """(1, d) -> (d, 1), exactly: a select against the identity and a sum
    over the lanes."""
    return _rows(jnp.where(eye, x_row, 0.0))


def _as_row(x_col, eye):
    """(d, 1) -> (1, d), exactly."""
    return jnp.sum(jnp.where(eye, x_col, 0.0), axis=0, keepdims=True)


def _head(ref, h: int, d: int):
    return ref[0, :, h * d:(h + 1) * d]


def _chunk_sum(x, reverse: bool = False):
    """``x`` (C, d) float32 summed down its rows from the chunk's start
    (``reverse``: back from its end): ``G`` from ``g``, and ``dg`` from
    ``dG``.  A product with the (C, C) triangle of ones at ``highest``
    precision — float32 sums that carry all of x's bits, as XLA's product
    over the whole array was — on the MXU, which the rule leaves idle."""
    row, col = _chunk_masks(x.shape[0])
    ones = (row <= col if reverse else row >= col).astype(jnp.float32)
    return jax.lax.dot_general(ones, x, _NN,
                               precision=jax.lax.Precision.HIGHEST,
                               preferred_element_type=jnp.float32)


def _unit(x, eps: float):
    """``(x r, r)`` with ``r = rsqrt(sum x^2 + eps)`` a row: ``x`` (C, d)
    float32 at l2 norm one (the model's norm of q and k), and ``r`` (C, 1)."""
    r = jax.lax.rsqrt(_rows(x * x) + eps)
    return x * r, r


def _unit_grad(unit, r, d_unit):
    """The gradient in ``x`` of ``x r`` (:func:`_unit`) from its own."""
    return r * (d_unit - unit * _rows(unit * d_unit))


def _q_and_k(q_ref, k_ref, h: int, d: int, norm, mx):
    """One head's q and k (C, d) float32 as the products see them, and what
    the backward pass needs of the norm.  ``norm`` None: the blocks as they
    lie, already normalised.  ``norm`` ``(eps, q's scale)``: the blocks are
    the convolution's output; each row is normalised in float32 and rounded
    to the operands' dtype ONCE, here (where the caller's cast rounded it
    when XLA made the norm) — then ``(q, k, (q's unit vector and r, k's))``,
    the unit vectors before the rounding, as the norm's vjp reads them."""
    q = _head(q_ref, h, d).astype(jnp.float32)
    k = _head(k_ref, h, d).astype(jnp.float32)
    if norm is None:
        return q, k, None
    eps, scale = norm
    (qu, qr), (ku, kr) = _unit(q, eps), _unit(k, eps)
    return (mx(qu * scale).astype(jnp.float32), mx(ku).astype(jnp.float32),
            ((qu, qr), (ku, kr)))


def _kda_fwd_kernel(q_ref, k_ref, v_ref, g_ref, bc_ref, o_ref, s_ref, t_ref,
                    p_ref, state, *, heads: int, norm):
    @pl.when(pl.program_id(2) == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    f32 = jnp.float32
    c = q_ref.shape[1]
    dk, dv = state.shape[1:]
    mx = lambda x: x.astype(v_ref.dtype)    # an operand, as it enters a product
    row, col = _chunk_masks(c)
    eye = jnp.equal(*_chunk_masks(dk))
    betas = bc_ref[0, 0]
    hs = range(heads)
    qk = [_q_and_k(q_ref, k_ref, h, dk, norm, mx) for h in hs]
    q, k = [x[0] for x in qk], [x[1] for x in qk]
    # G from here on: g summed from the chunk's start
    g = [_chunk_sum(_head(g_ref, h, dk).astype(f32)) for h in hs]
    v = [_head(v_ref, h, dv).astype(f32) for h in hs]
    beta = [betas[:, h:h + 1] for h in hs]
    scores = [_scores(q[h], k[h], g[h], row, col, mx) for h in hs]
    t = _tri_inverse_tiles(
        [jnp.where(row > col, beta[h] * scores[h][0], 0.0) for h in hs],
        row, col, mx)
    for h in hs:
        gamma = jnp.exp(g[h])
        last = g[h][c - 1:c, :]
        s = state[h]
        sm = mx(s)
        # K S_0 and Q S_0, each row decayed from the chunk's start: one product
        kq = jnp.concatenate([k[h] * gamma, q[h] * gamma], axis=0)
        ksqs = _dot(mx(kq), sm, _NN)
        rhs = mx(beta[h] * (v[h] - ksqs[:c]))
        u = mx(_dot(mx(t[h]), rhs, _NN))
        p = mx(scores[h][1])
        o = ksqs[c:] + _dot(p, u, _NN)
        new = (_as_column(jnp.exp(last), eye) * s
               + _dot(mx(k[h] * jnp.exp(last - g[h])), u, _TN))
        s_ref[0, 0, h] = s
        t_ref[0, 0, h] = mx(t[h])
        p_ref[0, 0, h] = p
        o_ref[0, :, h * dv:(h + 1) * dv] = o.astype(o_ref.dtype)
        state[h] = new


def _kda_bwd_kernel(q_ref, k_ref, v_ref, g_ref, bc_ref, s_ref, t_ref, p_ref,
                    do_ref, dq_ref, dk_ref, dv_ref, dg_ref, dbc_ref, dstate,
                    *, heads: int, norm):
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    f32 = jnp.float32
    c, sub = q_ref.shape[1], SUB_BLOCK
    dk, dv = dstate.shape[1:]
    mx = lambda x: x.astype(v_ref.dtype)
    row, col = _chunk_masks(c)
    eye = jnp.equal(*_chunk_masks(dk))
    is_last = jax.lax.broadcasted_iota(jnp.int32, (c, 1), 0) == c - 1
    betas = bc_ref[0, 0]
    for h in range(heads):
        q, k, units = _q_and_k(q_ref, k_ref, h, dk, norm, mx)
        # G from here on: g summed from the chunk's start
        g = _chunk_sum(_head(g_ref, h, dk).astype(f32))
        v = _head(v_ref, h, dv).astype(f32)
        do = mx(_head(do_ref, h, dv))
        beta = betas[:, h:h + 1]
        s, ds_next = s_ref[0, 0, h], dstate[h]
        t, p = t_ref[0, 0, h], p_ref[0, 0, h]
        sm, dsm = mx(s), mx(ds_next)
        gamma = jnp.exp(g)
        last = g[c - 1:c, :]
        gamma_c = jnp.exp(last)
        delta = jnp.exp(last - g)
        kg, qg, kd = k * gamma, q * gamma, k * delta
        # the forward's values, made again (T and P were kept)
        resid = v - _dot(mx(kg), sm, _NN)
        u = mx(_dot(t, mx(beta * resid), _NN))
        # the three lines that touch the state
        du = mx(_dot(p, do, _TN) + _dot(mx(kd), dsm, _NN))
        dqg = _dot(do, sm, _NT)
        dkd = _dot(u, dsm, _NT)
        # U = T (beta (V - Kg S)):  d rhs = T^T dU,  dA = -(T^T dU) U^T
        dr = _dot(t, du, _TN)
        bdr = mx(beta * dr)
        d_a = jnp.where(row > col, -_dot(mx(dr), u, _NT), 0.0)
        d_p = jnp.where(row >= col, _dot(do, u, _NT), 0.0)
        da = beta * d_a                     # dA through A = beta a
        dkg = -_dot(bdr, sm, _NT)
        dstate[h] = (_as_column(gamma_c, eye) * ds_next
                     + _dot(mx(qg), do, _TN) - _dot(mx(kg), bdr, _TN))
        # through A and P: dq_p, dk_row at the entry's ROW i, dk_col at its
        # column j; dbeta's part sum_j dA_ij a_ij with a made again
        dp_diag = _rows(jnp.where(row == col, d_p, 0.0))
        dq_p, dk_col = dp_diag * k, dp_diag * q
        dk_row = jnp.zeros_like(k)
        dbeta = _rows(dr * resid)
        for off in range(1, sub):       # a sub-block against itself
            e = jnp.exp(jnp.minimum(g - _rolled(g, off), 0.0))
            ke = _rolled(k, off) * e
            on = _on_offset(row, col, off)
            dp_o = _rows(jnp.where(on, d_p, 0.0))
            da_o = _rows(jnp.where(on, da, 0.0))
            dbeta = dbeta + _rows(jnp.where(on, d_a, 0.0)) * _rows(k * ke)
            dq_p = dq_p + dp_o * ke
            dk_row = dk_row + da_o * ke
            dk_col = dk_col + _rolled((da_o * k + dp_o * q) * e, c - off)
        dq_rows = [dq_p[:sub]]
        dk_rows = [dk_row[:sub]]
        dbeta_rows = [dbeta[:sub]]
        for first in range(sub, c, sub):    # against what came before
            e_row, e_col = _against_earlier(g, first)
            here = slice(first, first + sub)
            kt = mx(k * e_col)
            k_here = k[here] * e_row
            both = mx(jnp.concatenate([q[here] * e_row, k_here], axis=0))
            # columns from ``first`` on meet kt's zero rows, or e_col's
            m = mx(jnp.concatenate([d_p[here], da[here]], axis=0))
            z = _dot(m, kt, _NN)                        # (2 sub, d)
            dq_rows.append(dq_p[here] + z[:sub] * e_row)
            dk_rows.append(dk_row[here] + z[sub:] * e_row)
            dk_col = dk_col + _dot(m, both, _TN) * e_col
            dbeta_rows.append(dbeta[here] + _rows(
                d_a[here] * _dot(mx(k_here), kt, _NT)))
        dq_p = jnp.concatenate(dq_rows, axis=0)
        dk_row = jnp.concatenate(dk_rows, axis=0)
        at_end = (jnp.sum(kd * dkd, axis=0, keepdims=True)
                  + gamma_c * _as_row(_rows(s * ds_next), eye))
        dq = gamma * dqg + dq_p
        dk_ = gamma * dkg + delta * dkd + dk_row + dk_col
        if units is not None:
            # through the rounding as a cast's vjp passes it, then the norm:
            # the gradients in the convolution's output
            (qu, qr), (ku, kr) = units
            dq = _unit_grad(qu, qr, dq * norm[1])
            dk_ = _unit_grad(ku, kr, dk_)
        dq_ref[0, :, h * dk:(h + 1) * dk] = dq.astype(dq_ref.dtype)
        dk_ref[0, :, h * dk:(h + 1) * dk] = dk_.astype(dk_ref.dtype)
        dv_ref[0, :, h * dv:(h + 1) * dv] = (beta * dr).astype(dv_ref.dtype)
        # dG, the gradient of the chunk's running sum, summed back over the
        # tokens that follow in the chunk: dg
        dg_ref[0, :, h * dk:(h + 1) * dk] = _chunk_sum(
            qg * dqg + kg * dkg - kd * dkd + q * dq_p + k * (dk_row - dk_col)
            + jnp.where(is_last, at_end, 0.0), reverse=True).astype(
                dg_ref.dtype)
        dbc_ref[0, 0, :, h:h + 1] = jnp.concatenate(dbeta_rows, axis=0)


def _heads_per_step(h: int) -> int:
    return max(n for n in range(1, _HEADS_PER_STEP + 1) if h % n == 0)


def _blocks(c, hb, dk, dv, chunk_of):
    """BlockSpecs over grid (rows, head groups, chunks), step ``i`` walking
    chunk ``chunk_of(i)``: ``(q | k | G at hb heads of d_k, v | o | do at
    d_v — blocks of the (B, S, H d) arrays —, a (B, S, H) array as columns,
    the states, the (C, C) matrices)``."""
    wide = lambda d: pl.BlockSpec(
        (1, c, hb * d), lambda b, h, i: (b, chunk_of(i), h))
    per_head = lambda *tile: pl.BlockSpec(
        (1, 1, hb) + tile, lambda b, h, i: (b, chunk_of(i), h, 0, 0))
    return (wide(dk), wide(dv),
            pl.BlockSpec((1, 1, c, hb), lambda b, h, i: (b, h, chunk_of(i), 0)),
            per_head(dk, dv), per_head(c, c))


def _kda_fwd_pallas(q, k, v, g, beta, chunk, norm):
    """``q``, ``k`` (B, S, H, d_k) — in v's dtype as the products take them,
    or with ``norm`` (:func:`_q_and_k`) as their producer left them —, ``v``
    (B, S, H, d_v), ``g`` (B, S, H, d_k) float32, ``beta`` (B, S, H), ``S``
    whole chunks.  ``(o (B, S, H, d_v) in v's dtype, the state at each
    chunk's start (B, N, H, d_k, d_v) float32, each chunk's T and P (B, N,
    H, C, C) in v's dtype)``."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n, c = s // chunk, chunk
    hb = _heads_per_step(h)
    keys, values, cols, states, square = _blocks(c, hb, dk, dv, lambda i: i)
    mat = jax.ShapeDtypeStruct((b, n, h, c, c), v.dtype)
    o, states, tri, scores = _pallas_call(
        functools.partial(_kda_fwd_kernel, heads=hb, norm=norm),
        name="apex_kda_fwd", grid=(b, h // hb, n),
        in_specs=[keys, keys, values, keys, cols],
        out_specs=[values, states, square, square],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, n, h, dk, dv), jnp.float32),
                   mat, mat],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
    )(q.reshape(b, s, h * dk), k.reshape(b, s, h * dk),
      v.reshape(b, s, h * dv), g.reshape(b, s, h * dk),
      _small_layouts(beta, n, hb)[0])
    return o.reshape(b, s, h, dv), states, tri, scores


def _kda_bwd_pallas(q, k, v, g, beta, states, tri, scores, do, chunk, norm):
    """The gradients of :func:`_kda_fwd_pallas`'s ``o`` in its five inputs,
    the chunks walked from the last."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    n, c = s // chunk, chunk
    hb = _heads_per_step(h)
    keys, values, cols, per_state, square = _blocks(
        c, hb, dk, dv, lambda i: n - 1 - i)
    beta_cols = _small_layouts(beta, n, hb)[0]
    dq, dk_, dv_, dg, dbeta_cols = _pallas_call(
        functools.partial(_kda_bwd_kernel, heads=hb, norm=norm),
        name="apex_kda_bwd", grid=(b, h // hb, n),
        in_specs=[keys, keys, values, keys, cols, per_state, square, square,
                  values],
        out_specs=[keys, keys, values, keys, cols],
        out_shape=[jax.ShapeDtypeStruct((b, s, h * dk), q.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dk), k.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dv), v.dtype),
                   jax.ShapeDtypeStruct((b, s, h * dk), g.dtype),
                   jax.ShapeDtypeStruct(beta_cols.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
    )(q.reshape(b, s, h * dk), k.reshape(b, s, h * dk),
      v.reshape(b, s, h * dv), g.reshape(b, s, h * dk), beta_cols, states,
      tri, scores, do.reshape(b, s, h * dv))
    dbeta = dbeta_cols.reshape(b, h // hb, n, c, hb).transpose(
        0, 2, 3, 1, 4).reshape(b, s, h)
    return (dq.reshape(q.shape), dk_.reshape(k.shape), dv_.reshape(v.shape),
            dg.reshape(g.shape), dbeta.astype(beta.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _kda_kernels(q, k, v, g, beta, chunk: int, norm):
    return _kda_fwd_pallas(q, k, v, g, beta, chunk, norm)[0]


def _kda_kernels_fwd(q, k, v, g, beta, chunk, norm):
    o, states, tri, scores = _kda_fwd_pallas(q, k, v, g, beta, chunk, norm)
    # declared to the block-recomputing policies (apex_tpu.remat), as the
    # scalar rule declares its own: where a policy keeps these names the
    # recomputed block's forward rule is dead code
    o = checkpoint_name(o, KDA_OUT)
    states = checkpoint_name(states, KDA_STATES)
    tri = checkpoint_name(tri, KDA_TRI)
    scores = checkpoint_name(scores, KDA_SCORES)
    return o, (q, k, v, g, beta, states, tri, scores)


def _kda_kernels_bwd(chunk, norm, res, do):
    return _kda_bwd_pallas(*res, do, chunk, norm)


_kda_kernels.defvjp(_kda_kernels_fwd, _kda_kernels_bwd)


def supported(chunk: int, dk: int, dv: int) -> bool:
    """Whether the kernels take these shapes: chunks of whole sub-blocks (a
    16-bit tile of rows), lanes of 128."""
    return chunk % SUB_BLOCK == 0 and dk % 128 == 0 and dv % 128 == 0


# Called through jit so that a model's KDA layers — every one the same call —
# share ONE trace and ONE lowering, as the scalar rule's.
@functools.partial(jax.jit, static_argnums=(5, 6, 7, 8))
def _kda_jit(q, k, v, g, beta, chunk, kernels, qk_norm, trace_key):
    del trace_key
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    n = (s + pad) // chunk
    # the padding tokens (zero k, beta, g) leave state and outputs as they are
    padded = lambda t: jnp.pad(t, ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
    if kernels:
        if qk_norm is None:
            # the products' operands are v's dtype: q and k are rounded to
            # it once, here (a cast their producer absorbs), not in every
            # product; with ``qk_norm`` the kernels round what they normalise
            q, k = q.astype(v.dtype), k.astype(v.dtype)
        args = (q, k, v, g, beta)
        return _kda_kernels(*(map(padded, args) if pad else args),
                            chunk, qk_norm)[:, :s]
    if qk_norm is not None:
        eps, scale = qk_norm
        l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(
            jnp.square(t), axis=-1, keepdims=True) + eps)
        q = l2(q.astype(jnp.float32)) * scale
        k = l2(k.astype(jnp.float32))

    def chunks(t):
        """(B, S, H, ...) -> (N, B H, C, ...), float32."""
        t = padded(t.astype(jnp.float32))
        t = t.reshape((b, n, chunk, h) + t.shape[3:])
        t = jnp.moveaxis(jnp.moveaxis(t, 3, 1), 2, 0)      # (N, B, H, C, ...)
        return t.reshape((n, b * h, chunk) + t.shape[4:])

    # a chunk's (C, C, d) decays live inside the step, made again in the
    # backward pass: what is kept is one state a chunk
    _, o = jax.lax.scan(jax.checkpoint(_chunk_step),
                        jnp.zeros((b * h, dk, dv), jnp.float32),
                        tuple(map(chunks, (q, k, v, g, beta))))
    o = o.reshape(n, b, h, chunk, dv).transpose(1, 0, 3, 2, 4)
    return o.reshape(b, n * chunk, h, dv)[:, :s].astype(v.dtype)


def kda_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
             qk_norm: Optional[Tuple[float, float]] = None,
             use_pallas: Optional[bool] = None):
    """The delta rule with a decay a key channel over every row of a batch,
    by chunks.

    ``q``, ``k`` (B, S, H, d_k) — already normalised and scaled as the model
    wants them, unless ``qk_norm`` says otherwise —, ``v`` (B, S, H, d_v),
    ``g`` (B, S, H, d_k) the log-decay of every key channel, a token's own
    (<= 0, float32: the rule sums it over a chunk), ``beta`` (B, S, H).
    Returns (B, S, H, d_v) in ``v``'s dtype; float32 arithmetic, the
    products at JAX's default precision, an operand rounded once where it
    enters a product; the state, the running sums and the decays stay
    float32.  Each row starts from a zero state; ``S`` need not be whole
    chunks.  Differentiable in all five.

    ``qk_norm`` ``(eps, scale)``: q and k come as their producer left them
    and the rule normalises each head's row first, in float32 — ``x *
    rsqrt(sum x^2 + eps)``, q then times ``scale`` —, and its gradients are
    those in the q and k it was handed.  In the kernels that costs no pass
    over HBM: a chunk's tile is normalised where it is read.

    On the TPU, where the shapes tile (:func:`supported`), the whole rule
    runs in the kernels ``apex_kda_fwd`` / ``apex_kda_bwd``; else as a
    ``lax.scan`` over the chunks in float32.  The gauge ``kda.kernels`` says
    which was traced (1: the kernels, 0: the scan) and
    ``kda.qk_norm_in_kernel`` whether the traced kernels normalise (0 where
    the caller did, or the scan path ran), beside ``kda.chunks_per_row``."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    if not (q.shape == k.shape == g.shape and v.shape[:3] == q.shape[:3]
            and beta.shape == q.shape[:3]):
        raise ValueError(
            f"q, k and g share (B, S, H, d_k), v is (B, S, H, d_v) and beta "
            f"(B, S, H): got {q.shape}, {k.shape}, {v.shape}, {g.shape}, "
            f"{beta.shape}")
    ok = supported(chunk, q.shape[-1], v.shape[-1])
    if use_pallas is None:
        use_pallas = pallas_default(ok)
    elif use_pallas and not ok:
        raise ValueError(f"the kernels want head sizes of 128 lanes and "
                         f"chunks of {SUB_BLOCK} rows: got {q.shape}, "
                         f"{v.shape}, {chunk}")
    from apex_tpu import obs

    reg = obs.default_registry()
    reg.gauge("kda.chunks_per_row").set(-(-q.shape[1] // chunk))
    reg.gauge("kda.kernels").set(int(use_pallas))
    reg.gauge("kda.qk_norm_in_kernel").set(
        int(use_pallas and qk_norm is not None))
    if qk_norm is not None:
        qk_norm = tuple(map(float, qk_norm))
    return _kda_jit(q, k, v, g, beta, chunk, bool(use_pallas), qk_norm,
                    _trace_key())
