"""The gated delta rule over a sequence, by chunks — Pallas TPU kernels + a
``lax.scan`` path — and the short causal depthwise convolution in front of it.

The first sequential operator of ``ops/``: a linear-attention layer
(``models/qwen3_next.py``'s gated delta net) keeps, per head, a float32
state ``S`` of shape ``(d_k, d_v)`` and walks the sequence::

    S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;  o_t = S^T q_t

with ``g_t <= 0`` a log-decay and ``beta_t`` in (0, 1).  No reference
counterpart (apex has no recurrent attention).

**The chunked (WY) form.**  Inside a chunk of ``C`` tokens that starts from
state ``S_0``, with ``G_i`` the running sum of ``g`` over the chunk, the
corrected values ``u_i = beta_i r_i`` solve one unit-lower-triangular system

    (I + A) U = beta (V - diag(exp G) K S_0),   A_ij = beta_i exp(G_i - G_j) k_i.k_j  (j < i)

so with ``T = (I + A)^-1``, ``W = T (beta exp(G) K)`` and ``U0 = T (beta V)``::

    U  = U0 - W S_0
    O  = (exp(G) Q) S_0 + (M . Q K^T) U,        M_ij = exp(G_i - G_j)  (j <= i)
    S' = exp(G_C) S_0 + (exp(G_C - G) K)^T U

Everything but those three lines is local to a chunk and is computed for all
chunks at once by batched products (:func:`_chunk_local`, plain ``jax.numpy``,
differentiated by JAX; ``T`` by :func:`tri_inverse`).  The three lines touch
the state by three ``(C, d_k) x (d_k, d_v)``-shaped products and chain the
chunks: :func:`_chain`, a ``custom_vjp`` whose forward and backward each walk
the chunks once — kernels ``apex_gdn_fwd`` / ``apex_gdn_bwd`` on the TPU
(grid (heads, chunks), the state in VMEM scratch), ``lax.scan`` elsewhere and
as the kernels' oracle (end to end in ``qwen3-next.train-8k`` the kernels
are worth 1.3-1.7% tokens/s over the scan, PERF.md section 5).  The forward
keeps the state at each chunk's START (``chunks x heads x d_k x d_v``
float32), never a state per token.

**The trap.**  A head's log-decay reaches -21 a token (``A_log = log 16``,
``softplus`` of a large ``a``), -1300 over a chunk of 64.  Every decay here
is built as ``exp(G_i - G_j)`` for ``i >= j`` ONLY — a difference of the
running sum that is never positive, masked BEFORE the exponential.  Factored
as ``exp(G_i) * exp(-G_j)`` the second factor overflows float32 inside one
chunk (``tests/test_ops_gated_delta.py`` runs the strongest decay).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops._common import pallas_call as _pallas_call, pallas_default
from apex_tpu.remat import GDN_OUT, GDN_STATES, GDN_TRI

__all__ = ["gated_delta_rule", "gated_delta_rule_recurrent",
           "causal_conv1d_silu", "tri_inverse", "DEFAULT_CHUNK"]

DEFAULT_CHUNK = 64
#: heads a grid step of the kernels takes together (the largest that
#: divides batch x heads): a step's work is small, its fixed cost is not
_HEADS_PER_STEP = (8, 4, 2, 1)


# ---------------------------------------------------------------------------
# the convolution in front of the rule
# ---------------------------------------------------------------------------

def causal_conv1d_silu(x, w):
    """Depthwise causal convolution over the sequence, then SiLU.

    ``x`` (B, S, channels), ``w`` (channels, K): ``y_t = sum_j w[:, j] *
    x_{t - (K-1) + j}`` with zeros before the row's start, no bias.  ``K``
    shifted multiply-adds in float32 (XLA fuses them into one pass; a
    ``K``-tap depthwise convolution has no use for the MXU); ``x``'s dtype
    out."""
    k = w.shape[-1]
    s = x.shape[1]
    x32 = jnp.pad(x.astype(jnp.float32), ((0, 0), (k - 1, 0), (0, 0)))
    w32 = w.astype(jnp.float32)
    y = sum(x32[:, j:j + s] * w32[:, j] for j in range(k))
    return jax.nn.silu(y).astype(x.dtype)


# ---------------------------------------------------------------------------
# the token recurrence: the definition, and the oracle of the tests
# ---------------------------------------------------------------------------

def gated_delta_rule_recurrent(q, k, v, g, beta):
    """The rule token by token (``lax.scan`` over the sequence), float32 at
    ``highest`` precision: the definition the chunked form is held to.
    Shapes as :func:`gated_delta_rule`."""
    hi = jax.lax.Precision.HIGHEST
    f32 = lambda t: jnp.moveaxis(t.astype(jnp.float32), 1, 0)

    def step(state, x):
        q_t, k_t, v_t, g_t, b_t = x                   # (B, H, d) / (B, H)
        state = state * jnp.exp(g_t)[..., None, None]
        r = v_t - jnp.einsum("bhkv,bhk->bhv", state, k_t, precision=hi)
        state = state + jnp.einsum("bhk,bhv->bhkv", k_t, b_t[..., None] * r,
                                   precision=hi)
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t, precision=hi)

    b, _, h, dk = q.shape
    init = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(step, init, tuple(map(f32, (q, k, v, g, beta))))
    return jnp.moveaxis(out, 0, 1).astype(v.dtype)


# ---------------------------------------------------------------------------
# (I + A)^-1 for a strictly lower-triangular A
# ---------------------------------------------------------------------------

def _tri_inverse(a):
    """Doubling over the diagonal blocks: with ``T`` the inverses of the
    diagonal blocks of size ``b`` (block diagonal), those of size ``2 b``
    are ``T - T E T`` where ``E`` holds ``A``'s entries in the lower-left
    quarter of each ``2 b`` block (from ``T = I`` the first step is ``I -
    E``).  log2(C) - 1 steps of two C x C products: no loop over rows,
    nothing that is not a matrix product."""
    c = a.shape[-1]
    row = jnp.arange(c)[:, None]
    col = jnp.arange(c)[None, :]
    quarter = lambda b: ((row // (2 * b) == col // (2 * b))
                         & (row // b > col // b))
    t = jnp.eye(c, dtype=a.dtype) - jnp.where(quarter(1), a, 0.0)
    b = 2
    while b < c:
        t = t - t @ jnp.where(quarter(b), a, 0.0) @ t
        b *= 2
    return t


@jax.custom_vjp
def tri_inverse(a):
    """``(I + A)^-1`` over the last two axes; ``A`` (..., C, C) strictly
    lower triangular (entries on and above the diagonal are NOT read as
    zero: hand over zeros), ``C`` a power of two.  The gradient is ``-T^T
    dT T^T`` on the strict lower triangle: two products, and ``T`` the
    only residual."""
    return _tri_inverse(a)


def _tri_inverse_fwd(a):
    # declared to the block-recomputing policies (apex_tpu.remat): ten
    # dependent products to make again, one C x C matrix a chunk to hold
    t = checkpoint_name(_tri_inverse(a), GDN_TRI)
    return t, t


def _tri_inverse_bwd(t, dt):
    tt = jnp.swapaxes(t, -1, -2)
    c = t.shape[-1]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    return (jnp.where(strict, -(tt @ dt @ tt), 0.0),)


tri_inverse.defvjp(_tri_inverse_fwd, _tri_inverse_bwd)


# ---------------------------------------------------------------------------
# what is local to a chunk
# ---------------------------------------------------------------------------

def _chunk_local(q, k, v, g, beta):
    """``(W, U0, Qg, P, Kd, c)`` of every chunk at once.  ``q``, ``k``
    (N, BH, C, d_k), ``v`` (N, BH, C, d_v), ``g``, ``beta`` (N, BH, C), all
    float32.  Every decay is ``exp`` of a masked non-positive difference."""
    c = q.shape[2]
    big_g = jnp.cumsum(g, axis=-1)
    lower = jnp.arange(c)[:, None] >= jnp.arange(c)[None, :]
    strict = jnp.arange(c)[:, None] > jnp.arange(c)[None, :]
    diff = big_g[..., :, None] - big_g[..., None, :]
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))       # M, diagonal 1
    kk = jnp.einsum("nhid,nhjd->nhij", k, k)
    a = jnp.where(strict, beta[..., :, None] * decay * kk, 0.0)
    t = tri_inverse(a)
    gamma = jnp.exp(big_g)
    w = jnp.einsum("nhij,nhjd->nhid", t, (beta * gamma)[..., None] * k)
    u0 = jnp.einsum("nhij,nhjd->nhid", t, beta[..., None] * v)
    qg = gamma[..., None] * q
    p = decay * jnp.einsum("nhid,nhjd->nhij", q, k)
    to_end = jnp.exp(big_g[..., -1:] - big_g)
    return w, u0, qg, p, to_end[..., None] * k, jnp.exp(big_g[..., -1])


# ---------------------------------------------------------------------------
# the chain over the chunks: lax.scan
# ---------------------------------------------------------------------------

def _chain_fwd_scan(w, u0, qg, p, kd, c):
    """``(O (N, BH, C, d_v), the state at each chunk's start (N, BH, d_k,
    d_v))``."""
    def body(s, x):
        w_, u0_, qg_, p_, kd_, c_ = x
        u = u0_ - w_ @ s
        o = qg_ @ s + p_ @ u
        return (c_[:, None, None] * s + jnp.swapaxes(kd_, -1, -2) @ u,
                (o, s))

    init = jnp.zeros((w.shape[1], w.shape[3], u0.shape[3]), jnp.float32)
    _, (o, states) = jax.lax.scan(body, init, (w, u0, qg, p, kd, c))
    return o, states


def _chain_bwd_scan(w, u0, qg, p, kd, c, states, do):
    tr = lambda t: jnp.swapaxes(t, -1, -2)

    def body(ds_next, x):
        w_, u0_, qg_, p_, kd_, c_, s, do_ = x
        u = u0_ - w_ @ s
        du = tr(p_) @ do_ + kd_ @ ds_next
        ds = tr(qg_) @ do_ + c_[:, None, None] * ds_next - tr(w_) @ du
        return ds, (-du @ tr(s), du, do_ @ tr(s), do_ @ tr(u),
                    u @ tr(ds_next), jnp.sum(s * ds_next, axis=(-1, -2)))

    _, grads = jax.lax.scan(body, jnp.zeros_like(states[0]),
                            (w, u0, qg, p, kd, c, states, do), reverse=True)
    return grads


# ---------------------------------------------------------------------------
# the chain over the chunks: kernels
# ---------------------------------------------------------------------------

_NN = (((1,), (0,)), ((), ()))      # a @ b
_TN = (((0,), (0,)), ((), ()))      # a.T @ b
_NT = (((1,), (1,)), ((), ()))      # a @ b.T


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def _fwd_kernel(c_ref, w_ref, u0_ref, qg_ref, p_ref, kd_ref, o_ref, s_ref,
                state, *, heads: int, total_heads: int):
    n = pl.program_id(1)

    @pl.when(n == 0)
    def _():
        state[...] = jnp.zeros_like(state)

    for h in range(heads):
        s = state[h]
        s_ref[0, h] = s
        u = u0_ref[0, h] - _dot(w_ref[0, h], s, _NN)
        o_ref[0, h] = _dot(qg_ref[0, h], s, _NN) + _dot(p_ref[0, h], u, _NN)
        decay = c_ref[n * total_heads + pl.program_id(0) * heads + h]
        state[h] = decay * s + _dot(kd_ref[0, h], u, _TN)


def _bwd_kernel(c_ref, w_ref, u0_ref, qg_ref, p_ref, kd_ref, s_ref, do_ref,
                dw_ref, du_ref, dqg_ref, dp_ref, dkd_ref, dc_ref, dstate, *,
                heads: int, total_heads: int, chunks: int):
    i = pl.program_id(1)
    n = chunks - 1 - i              # the chunks are walked from the last

    @pl.when(i == 0)
    def _():
        dstate[...] = jnp.zeros_like(dstate)

    for h in range(heads):
        s, ds_next, do = s_ref[0, h], dstate[h], do_ref[0, h]
        w, qg, p, kd = w_ref[0, h], qg_ref[0, h], p_ref[0, h], kd_ref[0, h]
        u = u0_ref[0, h] - _dot(w, s, _NN)
        du = _dot(p, do, _TN) + _dot(kd, ds_next, _NN)
        decay = c_ref[n * total_heads + pl.program_id(0) * heads + h]
        dstate[h] = _dot(qg, do, _TN) + decay * ds_next - _dot(w, du, _TN)
        dw_ref[0, h] = -_dot(du, s, _NT)
        du_ref[0, h] = du
        dqg_ref[0, h] = _dot(do, s, _NT)
        dp_ref[0, h] = _dot(do, u, _NT)
        dkd_ref[0, h] = _dot(u, ds_next, _NT)
        dc_ref[0, h] = jnp.full(dc_ref.shape[2:], jnp.sum(s * ds_next))


def _heads_per_step(bh: int) -> int:
    return next(h for h in _HEADS_PER_STEP if bh % h == 0)


def _block(shape, heads, index):
    return pl.BlockSpec((1, heads) + tuple(shape[2:]), index)


def _chain_fwd_pallas(w, u0, qg, p, kd, c):
    n, bh, _, dk = w.shape
    dv = u0.shape[3]
    hb = _heads_per_step(bh)
    at = lambda h, i, c_ref: (i, h, 0, 0)
    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    return _pallas_call(
        functools.partial(_fwd_kernel, heads=hb, total_heads=bh),
        name="apex_gdn_fwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh // hb, n),
            in_specs=[_block(x.shape, hb, at) for x in (w, u0, qg, p, kd)],
            out_specs=[_block(u0.shape, hb, at),
                       _block((n, bh, dk, dv), hb, at)],
            scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)]),
        out_shape=[f32(*u0.shape), f32(n, bh, dk, dv)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(c.reshape(-1), w, u0, qg, p, kd)


def _chain_bwd_pallas(w, u0, qg, p, kd, c, states, do):
    n, bh, _, _ = w.shape
    hb = _heads_per_step(bh)
    at = lambda h, i, c_ref: (n - 1 - i, h, 0, 0)
    f32 = lambda shape: jax.ShapeDtypeStruct(shape, jnp.float32)
    dc_shape = (n, bh, 8, 128)
    outs = (w, u0, qg, p, kd)
    *grads, dc = _pallas_call(
        functools.partial(_bwd_kernel, heads=hb, total_heads=bh, chunks=n),
        name="apex_gdn_bwd",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(bh // hb, n),
            in_specs=[_block(x.shape, hb, at)
                      for x in (w, u0, qg, p, kd, states, do)],
            out_specs=[_block(x.shape, hb, at) for x in outs]
            + [_block(dc_shape, hb, at)],
            scratch_shapes=[pltpu.VMEM((hb,) + states.shape[2:],
                                       jnp.float32)]),
        out_shape=[f32(x.shape) for x in outs] + [f32(dc_shape)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(c.reshape(-1), w, u0, qg, p, kd, states, do)
    return (*grads, dc[:, :, 0, 0])


# ---------------------------------------------------------------------------
# the chain, differentiable
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _chain(w, u0, qg, p, kd, c, kernels: bool):
    """``O`` (N, BH, C, d_v) float32 of the chunks chained from a zero
    state (the three lines of the module's docstring)."""
    return _chain_fwd(w, u0, qg, p, kd, c, kernels)[0]


def _chain_fwd(w, u0, qg, p, kd, c, kernels):
    if kernels:
        return _chain_fwd_pallas(w, u0, qg, p, kd, c)
    return _chain_fwd_scan(w, u0, qg, p, kd, c)


def _chain_fwd_rule(w, u0, qg, p, kd, c, kernels):
    o, states = _chain_fwd(w, u0, qg, p, kd, c, kernels)
    # declared to the block-recomputing policies (apex_tpu.remat), as the
    # flash kernel declares its output: where a policy keeps these names
    # the backward pass does not walk the chunks forward a second time
    o = checkpoint_name(o, GDN_OUT)
    states = checkpoint_name(states, GDN_STATES)
    return o, (w, u0, qg, p, kd, c, states)


def _chain_bwd_rule(kernels, res, do):
    w, u0, qg, p, kd, c, states = res
    do = do.astype(jnp.float32)
    if kernels:
        return _chain_bwd_pallas(w, u0, qg, p, kd, c, states, do)
    return _chain_bwd_scan(w, u0, qg, p, kd, c, states, do)


_chain.defvjp(_chain_fwd_rule, _chain_bwd_rule)


def supported(chunk: int, dk: int, dv: int) -> bool:
    """Whether the kernels take these shapes: blocks whose last two axes
    are the arrays' own, lanes of 128."""
    return chunk % 8 == 0 and dk % 128 == 0 and dv % 128 == 0


def _trace_key():
    return jax.default_backend()


# Called through jit so that a model's linear-attention layers — every one
# the same call — share ONE trace and ONE lowering (PERF.md section 6, PR 25
# and PR 27: kernels traced once a layer doubled warm set-up).
@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _rule_jit(q, k, v, g, beta, chunk, kernels, trace_key):
    del trace_key
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    pad = (-s) % chunk
    n = (s + pad) // chunk

    def chunks(t):
        """(B, S, H, ...) -> (N, B H, C, ...), float32; the padding tokens
        (zero k, beta, g) leave state and outputs as they are."""
        t = jnp.pad(t.astype(jnp.float32),
                    ((0, 0), (0, pad)) + ((0, 0),) * (t.ndim - 2))
        t = t.reshape((b, n, chunk, h) + t.shape[3:])
        t = jnp.moveaxis(jnp.moveaxis(t, 3, 1), 2, 0)      # (N, B, H, C, ...)
        return t.reshape((n, b * h, chunk) + t.shape[4:])

    local = _chunk_local(*map(chunks, (q, k, v, g, beta)))
    o = _chain(*local, kernels)                             # (N, BH, C, dv)
    o = o.reshape(n, b, h, chunk, dv).transpose(1, 0, 3, 2, 4)
    return o.reshape(b, n * chunk, h, dv)[:, :s].astype(v.dtype)


def gated_delta_rule(q, k, v, g, beta, *, chunk: int = DEFAULT_CHUNK,
                     use_pallas: Optional[bool] = None):
    """The gated delta rule over every row of a batch, by chunks.

    ``q``, ``k`` (B, S, H, d_k) — already normalised and scaled as the
    model wants them —, ``v`` (B, S, H, d_v), ``g`` (B, S, H) the log-decay
    (<= 0), ``beta`` (B, S, H).  Returns (B, S, H, d_v) in ``v``'s dtype;
    the arithmetic is float32, the products at JAX's default precision (on
    the TPU the MXU's bfloat16 pass with float32 accumulation, as the
    model's other products).  Each row starts from a zero state; ``S`` need
    not be whole chunks.  Differentiable in all five.

    The chunks are chained by the kernels ``apex_gdn_fwd`` / ``apex_gdn_bwd``
    on the TPU where the shapes tile (:func:`supported`), else by
    ``lax.scan``; the gauge ``gdn.kernels`` says which was traced, beside
    ``gdn.chunk``, ``gdn.chunks_per_row`` and ``gdn.value_heads``."""
    if chunk & (chunk - 1):
        raise ValueError(f"chunk must be a power of two, got {chunk}")
    ok = supported(chunk, q.shape[-1], v.shape[-1])
    if use_pallas is None:
        use_pallas = pallas_default(ok)
    elif use_pallas and not ok:
        raise ValueError(f"the kernels want head sizes of 128 lanes and "
                         f"chunks of 8 rows: got {q.shape}, {v.shape}, {chunk}")
    from apex_tpu import obs

    reg = obs.default_registry()
    reg.gauge("gdn.chunk").set(chunk)
    reg.gauge("gdn.chunks_per_row").set(-(-q.shape[1] // chunk))
    reg.gauge("gdn.value_heads").set(v.shape[2])
    reg.gauge("gdn.kernels").set(int(use_pallas))
    return _rule_jit(q, k, v, g, beta, chunk, bool(use_pallas), _trace_key())

