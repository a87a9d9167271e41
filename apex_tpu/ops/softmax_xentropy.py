"""Fused softmax cross-entropy with label smoothing — Pallas kernel + jnp ref.

ref: apex/contrib/csrc/xentropy/ (interface.cpp, xentropy_kernel.cu) exposed
as apex/contrib/xentropy/softmax_xentropy.py (SoftmaxCrossEntropyLoss.apply
with ``label_smoothing`` and ``half_to_float``).

Why fused: the unfused path materializes log-softmax (B x V fp32) just to
gather one column — at BERT/GPT vocab sizes that is the largest activation
in the model.  The fused kernel computes per-row (max, logsumexp, label
logit, logit sum) in one streaming pass and never writes the softmax;
backward recomputes the softmax tile from the logits it already has
(d_logits = softmax - (1-eps)*onehot - eps/V, scaled by the incoming
cotangent).

Kernel structure (round 3 — VOCAB-TILED): the round-2 kernel loaded whole
(block_rows, V) rows, so large vocab (BERT V=30592) shrank the row block
to 16 inside the VMEM budget and the kernel lost to XLA (PERF.md r2).
This version tiles the VOCAB axis instead, grid (row_blocks, vocab_blocks)
with an online-logsumexp accumulator (the same streaming-softmax rule as
flash attention), so row blocks stay at 256 for ANY vocab size:

- forward: per (ri, vj) tile, fold (max, sum-exp, label logit, logit sum)
  into VMEM scratch; at the last vocab tile compute lse and the loss, and
  ALSO write lse as a second output (a (rows,) fp32 vector — negligible).
- backward: with lse saved there is no cross-tile dependency at all —
  each tile independently computes p = exp(l - lse) and writes its
  dlogits tile.  No accumulation, no shrinking blocks, no Mosaic
  scratch-carry (the round-2 backward's block_rows=32 Mosaic crash is
  structurally impossible here).
- ragged vocab tails are masked IN-KERNEL to -1e30 (exp underflows to
  exactly 0; the label-smoothing sum masks by global column index) —
  never by padding the array, which would cost a full extra copy of
  the logits — so any V works, lane-aligned or not.

Semantics (matching the reference kernel):
    nll_i     = lse_i - logit_i[label_i]
    smooth_i  = lse_i - mean_j logits_ij
    loss_i    = (1-eps) * nll_i + eps * smooth_i
Loss is always returned in fp32 (the reference's ``half_to_float=True`` is
the only sane mode on TPU and is the default here).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from apex_tpu.ops._common import (
    pallas_call as _pallas_call,
    pad_rows as _pad_rows,
)
from jax.experimental.pallas import tpu as pltpu

_LANE = 128
DEFAULT_BLOCK_ROWS = 256
DEFAULT_BLOCK_V = 2048
_PAD_NEG = -1e30




def softmax_cross_entropy_ref(
    logits: jax.Array, labels: jax.Array, label_smoothing: float = 0.0
) -> jax.Array:
    """Pure-jnp reference; per-example fp32 losses, shape labels.shape."""
    l32 = logits.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(l32, axis=-1)
    label_logit = jnp.take_along_axis(l32, labels[..., None], axis=-1)[..., 0]
    nll = lse - label_logit
    if label_smoothing:
        smooth = lse - jnp.mean(l32, axis=-1)
        return (1.0 - label_smoothing) * nll + label_smoothing * smooth
    return nll


def _xent_fwd_kernel(
    logits_ref, labels_ref, loss_ref, lse_ref, m_scr, l_scr, ll_scr, tot_scr,
    *, smoothing: float, v_real: int, block_v: int, nv: int, ragged: bool,
):
    vj = pl.program_id(1)

    @pl.when(vj == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _PAD_NEG)
        l_scr[:] = jnp.zeros_like(l_scr)
        ll_scr[:] = jnp.zeros_like(ll_scr)
        if smoothing:
            tot_scr[:] = jnp.zeros_like(tot_scr)

    l = logits_ref[:].astype(jnp.float32)  # (bm, block_v)
    bm = l.shape[0]
    labels = labels_ref[0, 0, :]  # (bm,) int32
    cols = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (bm, block_v), 1
    )
    if ragged:
        # V doesn't divide the tile (e.g. BERT's 30592 = 128*239 has no
        # usable tile divisor): Pallas DMAs a full final block whose
        # out-of-bounds lanes are garbage — neutralize them instead of
        # PADDING the array, which would cost a full extra copy of the
        # logits (the round-3a version did; it lost ~2 passes to it)
        l = jnp.where(cols < v_real, l, _PAD_NEG)
    m_prev = m_scr[:, :1]
    m_new = jnp.maximum(m_prev, jnp.max(l, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[:, :1] + jnp.sum(
        jnp.exp(l - m_new), axis=-1, keepdims=True
    )
    m_scr[:] = jnp.broadcast_to(m_new, m_scr.shape)
    l_scr[:] = jnp.broadcast_to(l_new, l_scr.shape)
    onehot = cols == labels[:, None]
    ll_scr[:] += jnp.broadcast_to(
        jnp.sum(jnp.where(onehot, l, 0.0), axis=-1, keepdims=True),
        ll_scr.shape,
    )
    if smoothing:
        # mask padded columns out of the smoothing sum (their -1e30 fill
        # would poison it; exp() handles them for lse automatically)
        tot_scr[:] += jnp.broadcast_to(
            jnp.sum(jnp.where(cols < v_real, l, 0.0), axis=-1,
                    keepdims=True),
            tot_scr.shape,
        )

    @pl.when(vj == nv - 1)
    def _finalize():
        lse = m_scr[:, :1] + jnp.log(l_scr[:, :1])
        nll = lse[:, 0] - ll_scr[:, 0]
        if smoothing:
            smooth = lse[:, 0] - tot_scr[:, 0] / v_real
            nll = (1.0 - smoothing) * nll + smoothing * smooth
        loss_ref[0, 0, :] = nll
        lse_ref[0, 0, :] = lse[:, 0]


def _xent_bwd_kernel(
    logits_ref, labels_ref, g_ref, lse_ref, dlogits_ref,
    *, smoothing: float, v_real: int, block_v: int, ragged: bool,
):
    vj = pl.program_id(1)
    l = logits_ref[:].astype(jnp.float32)
    bm = l.shape[0]
    labels = labels_ref[0, 0, :]
    g = g_ref[0, 0, :].astype(jnp.float32)  # per-row cotangent
    lse = lse_ref[0, 0, :]
    cols = vj * block_v + jax.lax.broadcasted_iota(
        jnp.int32, (bm, block_v), 1
    )
    if ragged:
        l = jnp.where(cols < v_real, l, _PAD_NEG)  # see _xent_fwd_kernel
    p = jnp.exp(l - lse[:, None])  # masked cols: exp(-1e30 - lse) == 0
    onehot = (cols == labels[:, None]).astype(jnp.float32)
    target = (1.0 - smoothing) * onehot
    if smoothing:
        target = target + jnp.where(cols < v_real, smoothing / v_real, 0.0)
    dlogits_ref[:] = ((p - target) * g[:, None]).astype(dlogits_ref.dtype)


def _tile(v: int, block_v: int):
    """(block_v, n_vocab_blocks, ragged): ragged final blocks are handled
    in-kernel by masking, NOT by padding the array (no copy)."""
    block_v = min(block_v, ((v + _LANE - 1) // _LANE) * _LANE)
    nv = (v + block_v - 1) // block_v
    return block_v, nv, v % block_v != 0


def _resolve_pallas(use_pallas, v, dtype, training):
    """Auto-gate: kernel for half-precision logits at mid/large vocab,
    fused XLA path otherwise (measured r3, v5e).

    The evidence hierarchy behind this rule (PERF.md r3 xentropy
    section): the ISOLATED fwd+bwd microbench says the kernel loses at
    V=30592 bf16 (0.83x), but the IN-CONTEXT measurement — the full
    BERT-large step A/B'd with only this gate changed — says the kernel
    path is ~3% faster end-to-end (71.4 vs 69.5 seq/s; better overlap
    with the surrounding step).  End-to-end wins the argument.  The
    fwd-only/inference path also favors the kernel in isolation (1.19x
    at V=30592 bf16).  fp32 logits lose on both evidence levels -> XLA.

    ``training`` is accepted for documentation/experiments; both paths
    currently resolve identically.  Explicit ``use_pallas`` and the L1
    harness's ``force_pallas`` pin the choice regardless (the kernel is
    correct everywhere; this gate is a measured performance preference).
    """
    del training
    if use_pallas is not None:
        return bool(use_pallas)
    from apex_tpu.ops import _common

    if _common._FORCE_PALLAS is not None:
        return _common.pallas_default(True)
    half = jnp.dtype(dtype).itemsize <= 2
    return _common.pallas_default(half and v >= 4096)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5))
def _xent(logits2, labels1, smoothing, block_rows, block_v, use_pallas):
    up = _resolve_pallas(use_pallas, logits2.shape[-1], logits2.dtype,
                         training=False)
    out, _ = _xent_fwd_impl(
        logits2, labels1, smoothing, block_rows, block_v, up
    )
    return out


def _xent_fwd_impl(logits2, labels1, smoothing, block_rows, block_v,
                   use_pallas):
    if not use_pallas:
        return softmax_cross_entropy_ref(logits2, labels1, smoothing), None
    v = logits2.shape[-1]
    block_v, nv, ragged = _tile(v, block_v)
    lp, m = _pad_rows(logits2, block_rows)
    lab, _ = _pad_rows(labels1.astype(jnp.int32), block_rows)
    nblocks = lp.shape[0] // block_rows
    loss, lse = _pallas_call(
        functools.partial(
            _xent_fwd_kernel, smoothing=smoothing, v_real=v,
            block_v=block_v, nv=nv, ragged=ragged,
        ),
        name="apex_xent_fwd",
        grid=(nblocks, nv),
        in_specs=[
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1, block_rows), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, block_rows), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_rows), lambda i, j: (i, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nblocks, 1, block_rows), jnp.float32),
            jax.ShapeDtypeStruct((nblocks, 1, block_rows), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_rows, _LANE), jnp.float32),
            pltpu.VMEM((block_rows, _LANE), jnp.float32),
            pltpu.VMEM((block_rows, _LANE), jnp.float32),
            pltpu.VMEM((block_rows, _LANE), jnp.float32),
        ],
    )(lp, lab.reshape(nblocks, 1, block_rows))
    return loss.reshape(-1)[:m], lse.reshape(-1)[:m]


def _xent_fwd_rule(logits2, labels1, smoothing, block_rows, block_v,
                   use_pallas):
    up = _resolve_pallas(use_pallas, logits2.shape[-1], logits2.dtype,
                         training=True)
    out, lse = _xent_fwd_impl(
        logits2, labels1, smoothing, block_rows, block_v, up
    )
    return out, (logits2, labels1, lse)


def _xent_bwd_rule(smoothing, block_rows, block_v, use_pallas, res, g):
    logits2, labels1, lse = res
    # consistency with the fwd_rule's resolution: the saved lse is None
    # exactly when the fwd took the jnp path
    use_pallas = lse is not None
    if not use_pallas:
        # jnp reference backward (autodiff of the ref math, written out)
        l32 = logits2.astype(jnp.float32)
        p = jax.nn.softmax(l32, axis=-1)
        v = l32.shape[-1]
        onehot = jax.nn.one_hot(labels1, v, dtype=jnp.float32)
        target = (1.0 - smoothing) * onehot + smoothing / v
        dlogits = (p - target) * g[..., None].astype(jnp.float32)
        return dlogits.astype(logits2.dtype), None
    v = logits2.shape[-1]
    block_v, nv, ragged = _tile(v, block_v)
    lp, m = _pad_rows(logits2, block_rows)
    lab, _ = _pad_rows(labels1.astype(jnp.int32), block_rows)
    gp, _ = _pad_rows(g.astype(jnp.float32), block_rows)
    lsep, _ = _pad_rows(lse, block_rows)
    nblocks = lp.shape[0] // block_rows
    dlogits = _pallas_call(
        functools.partial(
            _xent_bwd_kernel, smoothing=smoothing, v_real=v,
            block_v=block_v, ragged=ragged,
        ),
        name="apex_xent_bwd",
        grid=(nblocks, nv),
        in_specs=[
            pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
            pl.BlockSpec((1, 1, block_rows), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_rows), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, 1, block_rows), lambda i, j: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((block_rows, block_v), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct(lp.shape, logits2.dtype),
    )(
        lp,
        lab.reshape(nblocks, 1, block_rows),
        gp.reshape(nblocks, 1, block_rows),
        lsep.reshape(nblocks, 1, block_rows),
    )
    return dlogits[:m, :v], None


_xent.defvjp(_xent_fwd_rule, _xent_bwd_rule)


def softmax_cross_entropy(
    logits: jax.Array,
    labels: jax.Array,
    label_smoothing: float = 0.0,
    *,
    block_rows: int = DEFAULT_BLOCK_ROWS,
    block_v: int = DEFAULT_BLOCK_V,
    use_pallas: Optional[bool] = None,
) -> jax.Array:
    """Fused softmax CE with label smoothing; fp32 per-example losses.

    Any leading shape: logits (..., V), labels (...) int.  The
    vocab-tiled kernel keeps 256-row blocks at any V (ragged vocab tails
    masked in-kernel); ``use_pallas=None`` selects the kernel for
    half-precision logits at V >= 4096 on ALL differentiation paths —
    the in-context A/B on the full BERT step favored the kernel even
    though the isolated fwd+bwd microbench did not (the evidence
    hierarchy is documented in :func:`_resolve_pallas` and PERF.md r3).
    """
    v = logits.shape[-1]
    lead = labels.shape
    out = _xent(
        logits.reshape((-1, v)),
        labels.reshape((-1,)),
        float(label_smoothing),
        block_rows,
        block_v,
        None if use_pallas is None else bool(use_pallas),
    )
    return out.reshape(lead)
