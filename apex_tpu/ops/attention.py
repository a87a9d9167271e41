"""Fused multihead attention — flash-style Pallas TPU kernel + jnp reference.

ref: apex/contrib/csrc/multihead_attn/* (8 CUDA extensions: fused QKV GEMMs +
masked softmax + dropout, self & encdec, norm-add variants) surfaced as
apex/contrib/multihead_attn/{self,encdec}_multihead_attn.py.

TPU design: the reference fuses *around* cuBLAS batched GEMMs because it
must; a flash-style kernel is strictly stronger — it never materializes the
(Sq, Sk) score matrix, so memory goes from O(S^2) to O(S) and HBM traffic
drops by the same factor.  This is the canonical Pallas attention:

- forward: grid (batch*heads, q_blocks, k_blocks), online-softmax
  accumulation in VMEM scratch (m, l, acc), writes O and the per-row
  logsumexp (for backward) — ONE float32 a row, ``(batch*heads, 1, Sq)``
  in ``(1, 1, block_q)`` blocks: m and l sit lane-broadcast in their
  scratch, and the kernel's last step turns the block's column to a row
  (:func:`_put_column_as_row`);
- backward: recompute-based with the stored lse, no O(S^2) residuals.
  The kernels read q, k, v, do, o and that lse: each turns the lse block
  back to a column in VMEM and makes ``delta = sum_d do * o`` there from
  the ``do`` and ``o`` blocks (:func:`_stage_stats`) — no statistic crosses
  HBM at 128 lanes a row, and delta crosses it in no form.
  ONE sweep over a head's visited score tiles computes s, p, dp and ds once
  a tile and feeds dk, dv AND dq from them (5 MXU dots per visited tile
  pair instead of the two-pass flash-v2's 7): with one key block the
  key-major ``apex_flash_bwd_fused`` writes dq directly; with several the
  query-major ``apex_flash_bwd_sweep`` finishes dq in its inner loop and
  keeps dk and dv of the WHOLE key/value head in float32 VMEM accumulators
  for the length of the sweep, written to HBM once, in the output dtype.
  The classic two passes (dkv then dq) are left for the learned-bias path
  and for a head whose accumulators pass ``_SWEEP_ACC_BUDGET_BYTES``;
- supports causal masking — the masked half is not computed: grid tiles
  wholly above the diagonal are never visited, and where a head is ONE
  grid tile (GPT-2's S = 1024, where the grid has nothing to skip) each
  query sub-tile is taken against just the keys its rows reach, the mask
  applied only to the sub-tiles the diagonal crosses (``_for_pieces``) —
  and an optional additive bias/mask (B, Sq, Sk) — the reference's
  additive-mask / key-padding-mask path — indexed per head group in-kernel
  (never broadcast-materialized to (B*H, Sq, Sk));
- in-kernel attention-probability dropout (ref fused masked-softmax-dropout,
  apex/contrib/csrc/multihead_attn/dropout.h): the keep mask is a
  counter-based hash of (seed, GLOBAL head, global row, global col) — a
  murmur3-style 32-bit mixer — so forward and the recompute backward
  regenerate the IDENTICAL mask from the seed with no stored mask
  tensor (the reference stores the mask; flash recomputation makes storing
  it O(S^2) again, which defeats the point), and sharded callers (ring via
  row/col offsets, Ulysses via ``dropout_heads``) draw bitwise the
  unsharded mask.  The same hash evaluated on the full matrix gives the
  jnp reference path, so kernel-vs-reference digests match exactly even
  with dropout active.

All softmax/accumulation math in fp32 regardless of input dtype (the
reference kernels do softmax in fp32 for half inputs too).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl

from apex_tpu.ops._common import pallas_call as _pallas_call, pad_rows as _pad_rows
from apex_tpu.remat import FLASH_LSE, FLASH_OUT
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128



DEFAULT_BLOCK_K = 128
# caps for auto-picked blocks (measured on v5e, PERF.md "flash block
# autotune": 512/512 halves fwd+bwd time vs 128/128 at BERT-large shapes;
# block_k=1024 keeps winning at S=2048 while the fp32 scores block stays
# <= 512*1024*4 = 2 MB of VMEM)
MAX_AUTO_BLOCK_Q = 512
MAX_AUTO_BLOCK_K = 1024
_NEG_INF = -1e30

import os as _os


def _env_flag(name: str, default: bool) -> bool:
    v = _os.environ.get(name)
    if v is None:
        return default
    return v not in ("0", "false", "False", "")


# The one-sweep backward (apex_flash_bwd_sweep) keeps dk and dv of a whole
# key/value head resident in float32 VMEM scratch: sk x (d + d_v) x 4 bytes,
# each width rounded up to the 128 lanes VMEM holds it in (_sweep_acc_bytes:
# 12.6 MB at Moonlight's 8192 x (192 + 128), 8.4 MB at Trinity's 128 + 128,
# 16.8 MB at Qwen3-Next's 256 + 256).  A head past this budget (a 32k ring
# shard at 128 + 128 is 33.6 MB) takes the two-pass route.  A v5e core has
# 128 MiB of VMEM; the budget leaves what is not the accumulators to the
# pipeline's blocks and the tile's temporaries, and to spare.
_SWEEP_ACC_BUDGET_BYTES = 24 * 2 ** 20
# What the sweep's call asks for beside its accumulators: Mosaic's default
# scoped limit (16 MiB) holds either pass of the two-pass backward at the
# largest auto blocks (512 x 1024: four float32 score-sized temporaries of
# 2 MiB, ds's transpose, the double-buffered q/k/v/do/o blocks with lse's
# (1, block_q) row, the two (block_q, 128) float32 statistics' scratch); the
# sweep holds the union of the two passes' temporaries, so twice that.
_SWEEP_TILE_VMEM_BYTES = 32 * 2 ** 20


def paged_fused_default() -> bool:
    """Resolve the serving-side fused paged-attention default.

    Default OFF (ROADMAP carried risk):
    :func:`paged_fused_attention` is a new Pallas serving kernel that has
    never compiled on real TPU hardware — tier-1 exercises it through the
    interpreter only, and ``tools/check_paged_fused.py`` is the
    live-TPU probe that must pass before flipping the default.  Opt in
    with ``APEX_TPU_PAGED_FUSED=1``.  Read per-call (not cached at
    import) so decoder construction under a test's monkeypatched env
    picks the flip up.
    """
    return _env_flag("APEX_TPU_PAGED_FUSED", False)


# shared tiling heuristic (ops/_common.py); re-exported under the local
# name because ring_attention imports it from here
from apex_tpu.ops._common import auto_block as _auto_block  # noqa: E402


# ---------------------------------------------------------------------------
# counter-based dropout mask (shared by kernel and jnp reference)
# ---------------------------------------------------------------------------

def _keep_mask(seed, bh, row0, col0, shape, rate: float):
    """Bernoulli(1-rate) keep mask from a murmur3-fmix32-style hash of
    (seed, batch*head index, global row, global col).

    Pure jnp uint32 ops, so the exact same function runs inside the Pallas
    kernel on a block (row0/col0 = block offsets) and on host/XLA over the
    full matrix (the reference path) — mask parity by construction.
    """
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    x = (
        rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)
        + cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)
        + jnp.asarray(bh).astype(jnp.uint32) * jnp.uint32(0xC2B2AE3D)
    ) ^ jnp.asarray(seed).astype(jnp.uint32)
    x = x ^ (x >> 16)
    x = x * jnp.uint32(0x7FEB352D)
    x = x ^ (x >> 15)
    x = x * jnp.uint32(0x846CA68B)
    x = x ^ (x >> 16)
    # keep iff hash < (1-rate)*2^32
    thresh = jnp.uint32(min(int((1.0 - rate) * 2 ** 32), 2 ** 32 - 1))
    return x < thresh


# ---------------------------------------------------------------------------
# jnp reference
# ---------------------------------------------------------------------------

def _head_group(h_q: int, h_k: int, h_v: int) -> int:
    """How many consecutive query heads share one key/value head."""
    if h_k != h_v or h_k < 1 or h_q % h_k:
        raise ValueError(
            f"{h_q} query heads cannot share {h_k} key / {h_v} value heads")
    return h_q // h_k


def attention_ref(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
    dropout_heads=None,
    window: Optional[int] = None,
) -> jax.Array:
    """Plain attention.  q,k,v: (B, H, S, D); bias: (B, Sq, Sk) additive.
    ``v`` may be ``(B, H, S, D_v)`` (the output then too); the default
    ``scale`` is ``D ** -0.5``.  ``k``/``v`` may hold H / G heads (each
    serves G consecutive query heads); ``window`` keeps, under ``causal``,
    only keys ``j`` with ``i - j < window``.

    ``dropout_rate`` > 0 applies probability dropout with the SAME
    counter-based mask the Pallas kernel uses (exact parity).
    ``dropout_heads=(h_total, head_offset)`` keys the mask on GLOBAL
    head indices when the local H is a shard of a larger head dim
    (Ulysses head groups) — see :func:`flash_attention`."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, sq, _ = q.shape
    sk = k.shape[2]
    group = _head_group(h, k.shape[1], v.shape[1])
    if group > 1:
        k, v = (jnp.repeat(t, group, axis=1) for t in (k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s * scale
    if bias is not None:
        s = s + bias[:, None, :, :].astype(jnp.float32)
    if window is not None and not causal:
        raise ValueError("window requires causal=True")
    if causal:
        row = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        keep = row >= col
        if window is not None:
            keep = keep & (row - col < window)
        s = jnp.where(keep, s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0:
        if dropout_seed is None:
            raise ValueError("dropout_rate > 0 requires dropout_seed")
        if dropout_heads is None:
            h_total, head0 = h, jnp.int32(0)
        else:
            h_total, head0 = dropout_heads
        keep = jax.vmap(
            lambda i: _keep_mask(
                dropout_seed, (i // h) * h_total + head0 + i % h,
                0, 0, (sq, sk), dropout_rate
            )
        )(jnp.arange(b * h, dtype=jnp.int32)).reshape(b, h, sq, sk)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


# ---------------------------------------------------------------------------
# cached (decode-path) attention
# ---------------------------------------------------------------------------

def cached_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    *,
    positions: jax.Array,
    cache_k: Optional[jax.Array] = None,
    cache_v: Optional[jax.Array] = None,
    cache_lengths: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    block_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """Attention of T new tokens against a KV cache — the decode path.

    ``q``/``k_new``/``v_new``: (B, H, T, D) projections of the T NEW
    tokens, which sit at global positions ``positions`` (B, T) int32.
    ``cache_k``/``cache_v``: (B, H, S, D) previously-written cache (any
    dtype — a bf16 cache is upcast inside the fp32 dots), with
    ``cache_lengths`` (B,) the valid prefix per row; None = no history
    (the prefill case: pure causal self-attention over the new block).

    Two score blocks instead of one concatenated pass: scoring the cache
    and the new tokens separately keeps the per-step work at
    O(T·(S + T)) *reads* with no (B, H, S+T, D) concat copy of the cache
    — the fused K-token decode window calls this once per scanned token,
    so a cache-sized copy per call would dominate HBM traffic.

    Masking: cache key j is visible to query t iff ``j <
    cache_lengths[b]`` and ``j <= positions[b, t]``; new key t' is
    visible iff ``positions[b, t'] <= positions[b, t]`` (in-block
    causal — which also hides right-padding keys from valid prefill
    queries, since padding sits at later positions).

    ``block_mask`` (T, T) bool further restricts IN-BLOCK visibility:
    new key t' is visible to query t only where ``block_mask[t, t']`` —
    the tree-speculation branch mask (sibling draft branches share the
    block but must not attend across branches).  None leaves the
    in-block rule exactly as before (bitwise: the mask op is not even
    traced).

    All softmax/accumulation math in fp32 regardless of input/cache
    dtype (the same accumulator discipline as the flash kernels); the
    output is cast back to ``q.dtype``.
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    b, h, t, d = q.shape
    q32 = q.astype(jnp.float32) * scale
    pos_q = positions[:, None, :, None].astype(jnp.int32)  # (B, 1, T, 1)

    # in-block scores: (B, H, T, T), causal by global position
    s_new = jnp.einsum("bhqd,bhkd->bhqk", q32, k_new.astype(jnp.float32))
    pos_k = positions[:, None, None, :].astype(jnp.int32)  # (B, 1, 1, T)
    if block_mask is None:
        s_new = jnp.where(pos_k <= pos_q, s_new, _NEG_INF)
    else:
        ok = (pos_k <= pos_q) & block_mask[None, None, :, :]
        s_new = jnp.where(ok, s_new, _NEG_INF)

    if cache_k is not None:
        if cache_lengths is None:
            raise ValueError("cache_k requires cache_lengths")
        s_c = jnp.einsum("bhqd,bhkd->bhqk", q32, cache_k.astype(jnp.float32))
        j = jax.lax.broadcasted_iota(jnp.int32, s_c.shape, 3)
        valid = (j < cache_lengths[:, None, None, None]) & (j <= pos_q)
        s_c = jnp.where(valid, s_c, _NEG_INF)
        s = jnp.concatenate([s_c, s_new], axis=-1)
    else:
        s = s_new
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum(
        "bhqk,bhkd->bhqd", p[..., -t:], v_new.astype(jnp.float32)
    )
    if cache_k is not None:
        out = out + jnp.einsum(
            "bhqk,bhkd->bhqd", p[..., : -t], cache_v.astype(jnp.float32)
        )
    return out.astype(q.dtype)


def quantize_kv(x: jax.Array) -> Tuple[jax.Array, jax.Array]:
    """Symmetric int8 quantization of K/V vectors over the LAST axis.

    ``x`` (..., D) any float dtype -> ``(q, scale)`` with ``q`` int8
    (..., D) and ``scale`` fp32 (...,) the per-vector abs-max / 127
    (floored at a tiny eps so an all-zero vector round-trips to exact
    zeros instead of 0/0).  Deterministic round-to-nearest — inference
    storage wants bitwise-reproducible reads, not the unbiased
    stochastic rounding the training-side quantization patterns use.
    The inverse is a plain ``q.astype(f32) * scale[..., None]`` inside
    :func:`paged_cached_attention`'s gather, so attention accumulation
    never sees the int8 encoding.
    """
    x32 = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x32), axis=-1)
    s = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x32 / s[..., None]), -127.0, 127.0)
    return q.astype(jnp.int8), s


def paged_cached_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    *,
    positions: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    page_table: jax.Array,
    cache_lengths: jax.Array,
    pool_k_scale: Optional[jax.Array] = None,
    pool_v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    layer: int = 0,
    block_mask: Optional[jax.Array] = None,
    use_fused: Optional[bool] = None,
) -> jax.Array:
    """:func:`cached_attention` reading K/V through a page table.

    ``pool_k``/``pool_v``: one layer's slice of the global page pool,
    ``(num_pages, H, page_len, D)`` (any dtype — upcast inside the fp32
    dots), or the FULL pool ``(num_pages, L, H, page_len, D)`` with
    ``layer`` naming the layer to read (the fused kernel wants the full
    pool so XLA never materializes a per-layer slice copy as a kernel
    operand; the materializing path slices it to the same per-layer
    view).  ``page_table``: ``(B, n_pages)`` int32 physical page per
    logical page of each row; unmapped logical pages point at the trash
    page, whose garbage is masked because it only covers positions at or
    beyond ``cache_lengths``.

    ``use_fused`` routes to :func:`paged_fused_attention` (the Pallas
    page-gather + dequant + attention kernel); None reads the
    ``APEX_TPU_PAGED_FUSED`` default (OFF until live-TPU validated —
    see :func:`paged_fused_default`).  Both routes are bitwise-identical
    by contract (tests/test_paged_fused.py pins the grid).
    ``block_mask`` (T, T) bool is forwarded to the in-block visibility
    rule (tree speculation); None keeps the plain causal rule.

    The gather assembles each row's logical ``(B, H, n_pages*page_len,
    D)`` cache view and delegates to :func:`cached_attention` — so given
    equal cached VALUES the paged path is bit-identical to the
    contiguous path (the tests/test_paged_kv.py parity lever), while the
    pool itself can be sized to live traffic instead of ``slots *
    max_len`` worst case.  The gathered view is a per-layer temp; the
    POOL is what stays resident, and its bytes are the serving memory
    ceiling the paging exists to shrink.

    Int8 pools pass ``pool_k_scale``/``pool_v_scale`` ``(num_pages, H,
    page_len)`` fp32 per-token scales (written by :func:`quantize_kv`):
    the gathered int8 view is dequantized HERE, inside the gather, so
    everything downstream — score dots, softmax, value accumulation —
    runs the exact fp32 discipline of the unquantized path and the only
    divergence is the one write-time rounding of stored K/V.
    """
    if use_fused is None:
        use_fused = paged_fused_default()
    if use_fused:
        return paged_fused_attention(
            q, k_new, v_new,
            positions=positions,
            pool_k=pool_k, pool_v=pool_v,
            page_table=page_table, cache_lengths=cache_lengths,
            pool_k_scale=pool_k_scale, pool_v_scale=pool_v_scale,
            scale=scale, layer=layer, block_mask=block_mask,
        )
    if pool_k.ndim == 5:  # full pool: slice the requested layer
        pool_k = pool_k[:, layer]
        pool_v = pool_v[:, layer]
        if pool_k_scale is not None:
            pool_k_scale = pool_k_scale[:, layer]
            pool_v_scale = pool_v_scale[:, layer]
    b = q.shape[0]
    _, h, page_len, d = pool_k.shape
    n_pages = page_table.shape[1]

    def view(pool, pscale):
        g = pool[page_table]  # (B, n_pages, H, page_len, D)
        g = g.transpose(0, 2, 1, 3, 4).reshape(
            b, h, n_pages * page_len, d
        )
        if pscale is not None:
            s = pscale[page_table]  # (B, n_pages, H, page_len)
            s = s.transpose(0, 2, 1, 3).reshape(b, h, n_pages * page_len)
            g = g.astype(jnp.float32) * s[..., None]
        return g

    return cached_attention(
        q, k_new, v_new,
        positions=positions,
        cache_k=view(pool_k, pool_k_scale),
        cache_v=view(pool_v, pool_v_scale),
        cache_lengths=cache_lengths,
        scale=scale,
        block_mask=block_mask,
    )


# ---------------------------------------------------------------------------
# fused paged-attention serving kernel (gather + dequant + attention)
# ---------------------------------------------------------------------------

def _paged_fused_kernel(
    pt_ref, len_ref,      # scalar-prefetch: page table (B, P), lengths (B,)
    *refs,
    n_pages: int, page_len: int, t: int, s_total: int,
    quantized: bool, masked: bool, scale: float,
):
    """One (b, p) grid step: dequantize page p of row b into the VMEM
    K/V assembly buffers; on the LAST page of the row, run the whole-row
    attention (scores vs assembled cache + in-block scores vs the new
    tokens, one concat softmax, fp32 accumulation) and write the output
    block.  The grid iterates pages innermost, so the scratch buffers
    are fully assembled exactly when the flush step fires."""
    if quantized and masked:
        (q_ref, kn_ref, vn_ref, kp_ref, vp_ref, ks_ref, vs_ref,
         pos_ref, mask_ref, o_ref, kbuf, vbuf) = refs
    elif quantized:
        (q_ref, kn_ref, vn_ref, kp_ref, vp_ref, ks_ref, vs_ref,
         pos_ref, o_ref, kbuf, vbuf) = refs
    elif masked:
        (q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
         pos_ref, mask_ref, o_ref, kbuf, vbuf) = refs
    else:
        (q_ref, kn_ref, vn_ref, kp_ref, vp_ref,
         pos_ref, o_ref, kbuf, vbuf) = refs

    b = pl.program_id(0)
    p = pl.program_id(1)

    # gather + dequant: this page's (H, page_len, D) tile, DMA'd straight
    # from the pool by the page-table index_map, lands in the row buffer.
    kp = kp_ref[0, 0].astype(jnp.float32)
    vp = vp_ref[0, 0].astype(jnp.float32)
    if quantized:
        kp = kp * ks_ref[0, 0][..., None]
        vp = vp * vs_ref[0, 0][..., None]
    kbuf[:, pl.ds(p * page_len, page_len), :] = kp
    vbuf[:, pl.ds(p * page_len, page_len), :] = vp

    @pl.when(p == n_pages - 1)
    def _flush():
        q32 = q_ref[0].astype(jnp.float32) * scale   # (H, T, D)
        kn = kn_ref[0].astype(jnp.float32)
        vn = vn_ref[0].astype(jnp.float32)
        pos = pos_ref[0, 0].astype(jnp.int32)        # (T,)
        pos_q = pos.reshape(t, 1)
        pos_k = pos.reshape(1, t)
        ln = len_ref[b]

        # scores vs the assembled cache rows: (H, T, S)
        dn_qk = (((2,), (2,)), ((0,), (0,)))   # contract D, batch H
        s_c = jax.lax.dot_general(q32, kbuf[...], dn_qk)
        j = jax.lax.broadcasted_iota(jnp.int32, (t, s_total), 1)
        valid = (j < ln) & (j <= pos_q)
        s_c = jnp.where(valid[None], s_c, _NEG_INF)

        # in-block scores: (H, T, T), causal by global position (+ the
        # tree branch mask when present)
        s_n = jax.lax.dot_general(q32, kn, dn_qk)
        ok = pos_k <= pos_q
        if masked:
            ok = ok & (mask_ref[...] != 0)
        s_n = jnp.where(ok[None], s_n, _NEG_INF)

        s_all = jnp.concatenate([s_c, s_n], axis=-1)
        prob = jax.nn.softmax(s_all, axis=-1)
        dn_pv = (((2,), (1,)), ((0,), (0,)))   # contract keys, batch H
        out = jax.lax.dot_general(prob[..., s_total:], vn, dn_pv)
        out = out + jax.lax.dot_general(prob[..., :s_total], vbuf[...], dn_pv)
        o_ref[0] = out.astype(o_ref.dtype)


def paged_fused_attention(
    q: jax.Array,
    k_new: jax.Array,
    v_new: jax.Array,
    *,
    positions: jax.Array,
    pool_k: jax.Array,
    pool_v: jax.Array,
    page_table: jax.Array,
    cache_lengths: jax.Array,
    pool_k_scale: Optional[jax.Array] = None,
    pool_v_scale: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    layer: int = 0,
    block_mask: Optional[jax.Array] = None,
) -> jax.Array:
    """The fused serving read: page gather + int8 dequant + attention in
    ONE Pallas kernel (ROADMAP item 4; default OFF, see
    :func:`paged_fused_default`).

    The materializing path (:func:`paged_cached_attention`,
    ``use_fused=False``) moves the active cache through HBM twice per
    call — once assembling the gathered ``(B, H, S, D)`` logical view
    (int8 adds the dequant pass over it), once reading it back into the
    score/accumulate dots.  Here the page table rides scalar prefetch
    and drives the kernel's BlockSpec index maps directly, so each
    ``(H, page_len, D)`` page tile is DMA'd from the pool into VMEM
    exactly once, dequantized in-register against its per-token scales,
    and consumed by the fp32 attention math without the logical view
    ever existing in HBM.  ``pool_k``/``pool_v`` may be the FULL
    ``(num_pages, L, H, page_len, D)`` pool with ``layer`` static — the
    per-layer selection also happens in the index map, so no per-layer
    slice copy is materialized either.

    Math contract: bitwise-identical to the materializing path on every
    supported dtype (fp32 / bf16 / int8 pages) — same masking rule, same
    ``[cache, new]`` concat-softmax, same accumulation order, verified
    by tests/test_paged_fused.py.  Off-TPU the kernel runs in Pallas
    interpreter mode (ops/_common.pallas_call), which doubles as the
    executable reference.

    ``block_mask`` (T, T) bool: the tree-speculation in-block branch
    mask (see :func:`cached_attention`).
    """
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if pool_k.ndim == 4:   # per-layer slice: treat as a 1-layer pool
        pool_k = pool_k[:, None]
        pool_v = pool_v[:, None]
        if pool_k_scale is not None:
            pool_k_scale = pool_k_scale[:, None]
            pool_v_scale = pool_v_scale[:, None]
        layer = 0
    b, h, t, d = q.shape
    num_pool_pages, n_layers, hp, page_len, dp = pool_k.shape
    if (hp, dp) != (h, d):
        raise ValueError(
            f"pool heads/dim {(hp, dp)} do not match q {(h, d)}")
    n_pages = page_table.shape[1]
    s_total = n_pages * page_len
    quantized = pool_k_scale is not None
    masked = block_mask is not None

    # index maps: grid is (b, p); the scalar-prefetch page table turns
    # the logical page coordinate into a physical pool page, and the
    # static `layer` picks the layer plane — the whole gather is
    # expressed as BlockSpec indexing, no HBM-side gather op.
    def _bcast(bi, pi, pt, ln):
        return (bi, 0, 0, 0)

    def _pool(bi, pi, pt, ln):
        return (pt[bi, pi], layer, 0, 0, 0)

    def _pool_scale(bi, pi, pt, ln):
        return (pt[bi, pi], layer, 0, 0)

    in_specs = [
        pl.BlockSpec((1, h, t, d), _bcast),            # q
        pl.BlockSpec((1, h, t, d), _bcast),            # k_new
        pl.BlockSpec((1, h, t, d), _bcast),            # v_new
        pl.BlockSpec((1, 1, h, page_len, d), _pool),   # pool_k page
        pl.BlockSpec((1, 1, h, page_len, d), _pool),   # pool_v page
    ]
    args = [
        q, k_new, v_new, pool_k, pool_v,
    ]
    if quantized:
        in_specs += [
            pl.BlockSpec((1, 1, h, page_len), _pool_scale),
            pl.BlockSpec((1, 1, h, page_len), _pool_scale),
        ]
        args += [pool_k_scale, pool_v_scale]
    # positions ride as (B, 1, T): a (1, T) block of a (B, T) array
    # breaks the TPU rule that a block's last two dims be (8, 128)
    # multiples or the array's own (Mosaic refuses it at every shape)
    in_specs.append(
        pl.BlockSpec((1, 1, t), lambda bi, pi, pt, ln: (bi, 0, 0)))
    args.append(positions.astype(jnp.int32)[:, None, :])
    if masked:
        in_specs.append(
            pl.BlockSpec((t, t), lambda bi, pi, pt, ln: (0, 0)))
        args.append(block_mask.astype(jnp.int32))

    kernel = functools.partial(
        _paged_fused_kernel,
        n_pages=n_pages, page_len=page_len, t=t, s_total=s_total,
        quantized=quantized, masked=masked, scale=float(scale),
    )
    fn = _pallas_call(
        kernel,
        name="apex_paged_attn",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, n_pages),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, t, d), _bcast),
            scratch_shapes=[
                pltpu.VMEM((h, s_total, d), jnp.float32),  # assembled K
                pltpu.VMEM((h, s_total, d), jnp.float32),  # assembled V
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, h, t, d), q.dtype),
    )
    return fn(
        page_table.astype(jnp.int32),
        cache_lengths.astype(jnp.int32),
        *args,
    )


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

# Causal sub-tiles: where a causal head is ONE grid tile (one query block,
# one key block: GPT-2's S = 1024) the kernel body takes the tile in query
# sub-tiles of sub_q rows, each against the key sub-tiles (sub_k wide) its
# rows reach: nothing is done for the ones wholly above the diagonal, and
# only the ones the diagonal crosses are masked.  The grid cannot skip
# there: with one key block every grid tile reaches the triangle.  The
# grid indices are 0, so the pieces are static and the walk is
# straight-line code.  A grid of several tiles a head keeps the one-piece
# masked body and the grid-level skip (no benchmark cell has one yet).
# Width from chip runs (PERF.md section 6, PR 25).
_CAUSAL_SUB = 128


def _causal_key_bounds(row0, rows, col0, width, n, xp=jnp):
    """``(n_full, n_vis)`` for query rows ``[row0, row0 + rows)`` against
    ``n`` key sub-tiles of ``width`` columns starting at ``col0``: of the
    sub-tiles, ``[0, n_full)`` lie wholly at or below the causal diagonal
    (no mask needed), ``[n_full, n_vis)`` are crossed by it (masked), and
    ``[n_vis, n)`` lie wholly above it (no work).

    Coordinates are the call's LOCAL ones, top-left aligned (row i sees
    columns <= i) — see the _fwd_kernel comment.  The ONE definition of
    what a causal kernel visits: the grid-level ``run`` predicate
    (:func:`_causal_tile_visited`), the pieces the kernel bodies take
    (:func:`_for_pieces`) and the census (:func:`flash_tile_census`) all
    come from here, so they cannot drift.
    ``xp`` is ``jnp`` inside a kernel, ``numpy`` on the host.
    """
    n_vis = xp.minimum(xp.maximum(row0 + rows - 1 - col0 + width, 0) // width, n)
    n_full = xp.minimum(xp.maximum(row0 - col0 + 1, 0) // width, n)
    return n_full, n_vis


def _causal_tile_visited(qi, ki, block_q, block_k, xp=jnp, window=None):
    """True iff the (qi, ki) grid tile intersects the causal lower
    triangle — the tile taken as one sub-tile of its own width — and,
    under a ``window``, the band ``i - j < window`` below the diagonal
    (its nearest corner: first row, last column)."""
    visited = _causal_key_bounds(
        qi * block_q, block_q, ki * block_k, block_k, 1, xp)[1] > 0
    if window is not None:
        visited = visited & ((ki + 1) * block_k - 1 > qi * block_q - window)
    return visited


def _visited_key_blocks(qi, block_q, block_k, nk, window):
    """``(lo, hi)``: the key blocks ``[lo, hi]`` that
    :func:`_causal_tile_visited` holds true for at query block ``qi``
    (a contiguous run).  The index maps clamp to it, so that a skipped
    grid step names the block its neighbour fetched and moves nothing."""
    hi = jnp.minimum((qi * block_q + block_q - 1) // block_k, nk - 1)
    if window is None:
        return 0, hi
    return jnp.maximum(qi * block_q - window + 1, 0) // block_k, hi


def _visited_query_blocks(ki, block_q, block_k, nq, window):
    """The same run from a key block's side: the query blocks
    ``[lo, hi]`` that visit key block ``ki``."""
    lo = jnp.minimum((ki * block_k) // block_q, nq - 1)
    if window is None:
        return lo, nq - 1
    return lo, jnp.minimum(
        ((ki + 1) * block_k + window - 2) // block_q, nq - 1)


def _causal_subtile(block_q, block_k, nq, nk, causal, window=None):
    """``(sub_q, sub_k)``: the sub-tiles a kernel body takes a grid tile
    in.  The tile itself — one piece, masked whole when causal — unless
    the call is causal with one grid tile a head that divides into
    several sub-tiles.  A windowed call keeps the one piece (its band is
    cut by the grid, where there is one)."""
    sub_q, sub_k = min(block_q, _CAUSAL_SUB), min(block_k, _CAUSAL_SUB)
    if (not causal or window is not None or nq != 1 or nk != 1
            or block_q % sub_q or block_k % sub_k):
        return block_q, block_k
    return sub_q, sub_k


def flash_tile_census(sq, sk, block_q, block_k, causal, window=None):
    """``(total, visited, masked)`` sub-tiles of ONE head's (sq, sk) score
    matrix under a flash kernel with these blocks: how many there are,
    how many the kernel computes, and on how many of those it applies the
    causal mask.  From the same bounds the kernels' pieces come from.  A
    causal tile that is a single sub-tile is one piece, masked whenever it
    runs: there ``masked == visited``.  Under a ``window`` the visited
    tiles are those of the band."""
    import numpy as np

    sub_q, sub_k = _causal_subtile(
        block_q, block_k, sq // block_q, sk // block_k, causal, window)
    total = (sq // sub_q) * (sk // sub_k)
    if not causal:
        return total, total, 0
    if window is not None:
        visited = int(_causal_tile_visited(
            np.arange(sq // block_q)[:, None], np.arange(sk // block_k)[None, :],
            block_q, block_k, np, window).sum())
        return total, visited, visited
    row0 = np.arange(sq // sub_q)[:, None] * sub_q
    col0 = np.arange(sk // block_k)[None, :] * block_k
    n_full, n_vis = _causal_key_bounds(
        row0, sub_q, col0, sub_k, block_k // sub_k, np)
    visited = int(n_vis.sum())
    if (sub_q, sub_k) == (block_q, block_k):
        return total, visited, visited
    return total, visited, visited - int(n_full.sum())


def _count_tiles(bh, sq, sk, block_q, block_k, causal, window=None):
    """The engagement counter of the causal skip: every call of
    :func:`flash_attention` that takes the kernels adds its census x
    ``bh`` (query heads) — when it is TRACED, since the skip is static
    and there is nothing to count at run time.  The backward kernels walk
    the same sub-tiles and are not counted again."""
    from apex_tpu import obs

    reg = obs.default_registry()
    census = flash_tile_census(sq, sk, block_q, block_k, causal, window)
    for name, n in zip(("total", "visited", "masked"), census):
        reg.counter("ops.flash.tiles_" + name).inc(bh * n)


def _causal_mask_tail(s, start, row0, col0, window=None):
    """The causal mask on columns ``[start, width)`` of the score piece
    ``s`` alone (``row0``/``col0``: local position of its element (0, 0));
    the columns before ``start`` lie wholly below the diagonal.  Under a
    ``window`` (always with ``start`` 0) also the band's lower edge."""
    tail = s if start == 0 else s[:, start:]
    row = row0 + jax.lax.broadcasted_iota(jnp.int32, tail.shape, 0)
    col = col0 + start + jax.lax.broadcasted_iota(jnp.int32, tail.shape, 1)
    keep = row >= col
    if window is not None:
        keep = keep & (row - col < window)
    tail = jnp.where(keep, tail, _NEG_INF)
    return tail if start == 0 else jnp.concatenate([s[:, :start], tail], axis=1)


def _for_pieces(block_q, block_k, nq, nk, causal, piece, window=None):
    """Inside a visited grid tile: run ``piece(r0, rows, width, mask_from)``
    — all four static — for each of the tile's query sub-tiles (``rows``
    rows from row ``r0`` of the tile) on the tile's first ``width`` key
    columns, the ones those rows reach, of which ``[mask_from, width)``
    are crossed by the causal diagonal.  Nothing is done for the columns
    past ``width``.  A tile that is not sub-tiled (:func:`_causal_subtile`)
    is one piece, masked whole when causal."""
    import numpy as np

    sub_q, sub_k = _causal_subtile(block_q, block_k, nq, nk, causal, window)
    if (sub_q, sub_k) == (block_q, block_k):
        piece(0, block_q, block_k, 0 if causal else block_k)
        return
    # the one tile of its head: grid indices (0, 0), so the bounds are
    # host numbers; row 0 reaches column 0, so no piece is empty
    for r0 in range(0, block_q, sub_q):
        n_full, n_vis = _causal_key_bounds(
            r0, sub_q, 0, sub_k, block_k // sub_k, np)
        piece(r0, sub_q, int(n_vis) * sub_k, int(n_full) * sub_k)


def _drop_bh(seed_ref, h_map, bh=None):
    """The batch*head index the DROPOUT hash is keyed on (``bh``: the
    call's local query head where the grid's first index is not it).

    ``h_map=(h_local, h_total)`` maps the local grid index to the GLOBAL
    head coordinate (seed_ref[3] = traced head offset of this shard's
    head group) so a head-sharded call (Ulysses) draws the bitwise-same
    mask as the unsharded one.  None = identity (the common case; no
    SMEM read, no div/mod)."""
    if bh is None:
        bh = pl.program_id(0)
    if h_map is None:
        return bh
    h_local, h_total = h_map
    return (bh // h_local) * h_total + seed_ref[3] + bh % h_local


def _put_column_as_row(row_ref, x):
    """``row_ref[0]``, ``(1, rows)``, takes the column that ``x`` ``(rows,
    128)`` holds lane-broadcast (every lane of a row the same number, as the
    forward's m and l).  128 rows at a time: the chunk's diagonal picked
    under an iota mask and summed over the chunk's rows — one number and
    zeros a lane, so exact.  Selects, adds and one sublane reduction a chunk:
    at GPT-2's call, where a head is one grid step, that left the forward
    kernel 7% faster than row 0 of ``jnp.transpose(x)`` did (PERF.md section
    6, PR 41)."""
    rows, lanes = x.shape
    for r in range(0, rows, lanes):
        c = min(lanes, rows - r)
        diagonal = (jax.lax.broadcasted_iota(jnp.int32, (c, lanes), 0)
                    == jax.lax.broadcasted_iota(jnp.int32, (c, lanes), 1))
        row_ref[0, :, r:r + c] = jnp.sum(
            jnp.where(diagonal, x[r:r + c], 0.0), axis=0, keepdims=True)[:, :c]


def _fwd_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
    m_scr, l_scr, acc_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, nq: int,
    nk: int, dropout_rate: float = 0.0, h_map=None, probs_bf16: bool = False,
    window: Optional[int] = None,
):
    bh = _drop_bh(seed_ref, h_map)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    # seed_ref (SMEM) = [dropout seed, dropout row offset, dropout col
    # offset].  The offsets key the DROPOUT counter hash on global
    # positions (ring attention passes its shard offsets so the sharded
    # mask is bitwise-identical to the unsharded one).  Causal masking
    # deliberately stays in LOCAL block coordinates: a dynamic (SMEM-
    # dependent) `run` predicate would defeat Mosaic's static grid
    # pruning — skipped blocks would still be DMA'd (measured 1.5x SLOWER
    # on the ring bench) — and the pieces a tile is taken in (_for_pieces)
    # could not be worked out on the host.  Ring callers get global-causal
    # semantics for free anyway: the diagonal block has row0 == col0
    # (local == global masking) and off-diagonal visible blocks need no
    # mask at all.

    @pl.when(ki == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    run = True
    if causal:
        # skip blocks strictly above the diagonal (static predicate:
        # Mosaic prunes the whole grid step, DMAs included).  That saves
        # something only where nk > 1: with one key block (GPT-2's
        # S = 1024) every grid tile reaches the triangle, and the masked
        # half is skipped by the pieces below instead
        run = _causal_tile_visited(qi, ki, block_q, block_k, window=window)

    def update(r0, rows, width, mask_from):
        """One online-softmax step of the tile's query rows ``[r0, r0 +
        rows)`` against its first ``width`` keys, masked from column
        ``mask_from`` on."""
        rows, cols = slice(r0, r0 + rows), slice(0, width)
        # q/k stay in their input dtype: a bf16xbf16 MXU dot with fp32
        # accumulation (preferred_element_type) is bit-identical to the
        # fp32 dot of the same bf16 values and runs at 2x rate
        q = q_ref[0, rows]  # (bq, d)
        k = k_ref[0, cols]  # (bk, d)
        # p@v: fp32 probabilities by default (the accumulator-precision
        # dot); probs_bf16 keeps v native and rounds p to the input dtype
        # so the dot runs at full MXU rate (the reference's own fused-MHA
        # softmax emits half-precision probabilities — see flash_attention)
        v = v_ref[0, cols] if probs_bf16 else v_ref[0, cols].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # (bq, bk)
        if bias_ref is not None:
            s = s + bias_ref[0, rows, cols].astype(jnp.float32)
        if mask_from < width:
            s = _causal_mask_tail(
                s, mask_from, qi * block_q + r0, ki * block_k, window)
        m_prev = m_scr[rows, :1]  # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_new = alpha * l_scr[rows, :1] + jnp.sum(p, axis=-1, keepdims=True)
        if dropout_rate > 0.0:
            # dropout AFTER the l accumulation: the softmax normalizer is
            # the full sum; only the p@v accumulation is masked
            keep = _keep_mask(
                seed_ref[0], bh, seed_ref[1] + qi * block_q + r0,
                seed_ref[2] + ki * block_k, p.shape,
                dropout_rate,
            )
            p = jnp.where(keep, p, 0.0)
        p_dot = p.astype(v.dtype) if probs_bf16 else p
        acc_scr[rows] = acc_scr[rows] * alpha + jax.lax.dot_general(
            p_dot, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        stat = (s.shape[0], m_scr.shape[1])
        m_scr[rows] = jnp.broadcast_to(m_new, stat)
        l_scr[rows] = jnp.broadcast_to(l_new, stat)

    @pl.when(run)
    def _body():
        # a query sub-tile's keys of this tile in ONE step.  Keys ascend
        # over the grid, so every row has met a visible column (column 0
        # of the call) before any stretch masked whole: m is finite by
        # then and exp(-1e30 - m) an exact 0.  Under a window a row may
        # meet a tile masked whole FIRST (the band's lower edge cuts the
        # tile below the row): p reads 1 there, and the row's first visible
        # column, which its own diagonal guarantees, wipes that with
        # alpha = exp(-1e30 - m) = 0
        _for_pieces(block_q, block_k, nq, nk, causal, update, window)

    @pl.when(ki == nk - 1)
    def _finalize():
        l_safe = jnp.where(l_scr[:] == 0.0, 1.0, l_scr[:])
        denom = l_safe[:, :1]
        if dropout_rate > 0.0:
            denom = denom * (1.0 - dropout_rate)
        o_ref[0] = (acc_scr[:] / denom).astype(o_ref.dtype)
        # one float32 a row leaves: the block's column turned to a row
        _put_column_as_row(lse_ref, m_scr[:] + jnp.log(l_safe))


# ---------------------------------------------------------------------------
# backward kernels (recompute with stored lse)
# ---------------------------------------------------------------------------

def _stage_stats(do_ref, o_ref, lse_ref, lse_scr, delta_scr):
    """A query block's two softmax statistics, a column each, into the
    ``(block_q, 128)`` float32 scratch the tiles read them from (lane-
    broadcast, as the forward's m and l).  ``lse`` arrives as the forward
    left it, one float32 a row in a ``(1, block_q)`` block, and is turned
    from a row to a column here; ``delta_i = sum_d do * o`` (the flash-v2
    trick: avoids recomputing p@v row sums) is made from the ``do`` and
    ``o`` blocks and crosses HBM in no form.  Once a query block in the
    query-major kernels (at ``ki == 0``), once a visited grid step in the
    key-major ones."""
    block_q, lanes = lse_scr.shape
    lse_scr[:] = jnp.transpose(jnp.broadcast_to(lse_ref[0], (lanes, block_q)))
    delta = jnp.sum(
        do_ref[0].astype(jnp.float32) * o_ref[0].astype(jnp.float32),
        axis=-1, keepdims=True)
    delta_scr[:] = jnp.broadcast_to(delta, delta_scr.shape)


def _bwd_tile(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_scr, delta_scr,
    dk_acc, dv_acc, put_dq, bh, qi, ki, acc_rows=None,
    *, scale: float, block_q: int, block_k: int, dropout_rate: float,
    probs_bf16: bool, window: Optional[int],
):
    """The one-sweep backwards' work on a visited tile, as the function
    :func:`_for_pieces` calls: s, p, dp and ds of query block ``qi``
    against key block ``ki`` ONCE, and from them the tile's dv and dk added
    to the float32 accumulators ``dv_acc`` / ``dk_acc`` — at ``acc_rows``,
    or where there is none at the tile's own key columns — and, where
    ``put_dq`` is given, its dq contribution ``ds @ K`` handed to
    ``put_dq(rows, contribution)``.  ``lse_scr`` / ``delta_scr``: the query
    block's statistics as :func:`_stage_stats` left them."""

    def tile(r0, rows, width, mask_from):
        """The tile's query rows ``[r0, r0 + rows)`` against its first
        ``width`` keys, masked from column ``mask_from`` on."""
        rows, cols = slice(r0, r0 + rows), slice(0, width)
        acc = cols if acc_rows is None else acc_rows
        # native-dtype operands for the input-sourced dots (see _fwd_kernel
        # note: bf16 MXU dot + fp32 accumulate == fp32 dot of bf16 values)
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        # fp32 partner for the accumulator-precision dots; probs_bf16
        # instead rounds the probability/ds operands to the input dtype
        # (full MXU rate, documented tolerance cost — see flash_attention)
        do32 = do if probs_bf16 else do.astype(jnp.float32)
        lse = lse_scr[rows, :1]
        delta = delta_scr[rows, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, rows, cols].astype(jnp.float32)
        if mask_from < width:
            s = _causal_mask_tail(
                s, mask_from, qi * block_q + r0, ki * block_k, window)
        p = jnp.exp(s - lse)  # (bq, bk) — normalized probabilities
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_rate > 0.0:
            keep = _keep_mask(
                seed_ref[0], bh, seed_ref[1] + qi * block_q + r0,
                seed_ref[2] + ki * block_k, p.shape,
                dropout_rate,
            )
            inv = 1.0 / (1.0 - dropout_rate)
            pd = jnp.where(keep, p * inv, 0.0)
            dp = jnp.where(keep, dp * inv, 0.0)
        else:
            pd = p
        if probs_bf16:
            pd = pd.astype(q.dtype)
        dv_acc[acc] += jax.lax.dot_general(
            pd, do32, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta) * scale
        q_dot = q if probs_bf16 else q.astype(jnp.float32)
        if probs_bf16:
            ds = ds.astype(q.dtype)
        dk_acc[acc] += jax.lax.dot_general(
            ds, q_dot, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        if put_dq is not None:
            k_dot = k if probs_bf16 else k.astype(jnp.float32)
            put_dq(rows, jax.lax.dot_general(
                ds, k_dot, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ))

    return tile


def _bwd_dkv_body(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref,
    dk_ref, dv_ref, dq_ref, dk_scr, dv_scr, lse_scr, delta_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, nq: int,
    nk: int, dropout_rate: float = 0.0, h_map=None, probs_bf16: bool = False,
    window: Optional[int] = None, group: int = 1,
):
    """The key-major backward body — grid (bh, k_blocks, q_blocks), q
    inner; dk/dv accumulate in VMEM scratch across the q loop.  With
    ``group`` query heads to a key/value head the grid is (key/value
    heads, k_blocks, group * q_blocks): the inner loop walks the group's
    query heads one after the other, so dk/dv come out summed over the
    group from the same scratch.

    ``dq_ref`` selects the variant at trace time:

    - None: the flash-v2 dkv pass of the two-pass route (a separate dq
      pass recomputes s/p);
    - set: the one-sweep backward of a call with ONE key block
      (``apex_flash_bwd_fused``) — each query block's dq is whole after
      its single key step, so ``ds @ K`` is written straight out, in the
      output dtype.  One s/p recompute instead of two, 5 MXU dots per
      visited tile pair instead of 7, and q/k/v/do/o/lse read once
      instead of twice (measured +4.5% end-to-end on the BERT step in r4.
      Ref capability: apex/contrib/csrc/multihead_attn/).  With several
      key blocks the query-major :func:`_bwd_sweep_kernel` does the same.
    """
    if group == 1:
        bh = _drop_bh(seed_ref, h_map)
        ki = pl.program_id(1)
        qi = step = pl.program_id(2)
    else:
        # the inner axis walks the group's query heads, each through its
        # query blocks; the dropout hash is keyed on the QUERY head
        ki, step = pl.program_id(1), pl.program_id(2)
        qi = step % nq
        bh = _drop_bh(seed_ref, h_map,
                      pl.program_id(0) * group + step // nq)

    @pl.when(step == 0)
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = _causal_tile_visited(qi, ki, block_q, block_k, window=window)

    put_dq = None
    if dq_ref is not None:
        # whole, since the tile's other keys lie above the rows' diagonal
        def put_dq(rows, contrib):
            dq_ref[0, 0, rows] = contrib.astype(dq_ref.dtype)

    tile = _bwd_tile(
        seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_scr, delta_scr,
        dk_scr, dv_scr, put_dq, bh, qi, ki,
        scale=scale, block_q=block_q, block_k=block_k,
        dropout_rate=dropout_rate, probs_bf16=probs_bf16, window=window)

    @pl.when(run)
    def _body():
        # q inner: every visited step brings another query block
        _stage_stats(do_ref, o_ref, lse_ref, lse_scr, delta_scr)
        _for_pieces(block_q, block_k, nq, nk, causal, tile, window)

    if dq_ref is not None and window is not None:
        # more queries than keys under a window: a query block past the
        # last key's band sees no key, runs no tile, and its dq is 0
        @pl.when(jnp.logical_not(run))
        def _zero_skipped_dq():
            dq_ref[0, 0] = jnp.zeros_like(dq_ref[0, 0])

    @pl.when(step == group * nq - 1)
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dkv_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref,
    dk_ref, dv_ref, *scratch, **kw,
):
    _bwd_dkv_body(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                  lse_ref, dk_ref, dv_ref, None, *scratch, **kw)


def _bwd_sweep_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref,
    dq_ref, dk_ref, dv_ref, dq_scr, dk_acc, dv_acc, lse_scr, delta_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, nq: int,
    nk: int, dropout_rate: float = 0.0, h_map=None, probs_bf16: bool = False,
    window: Optional[int] = None, group: int = 1,
):
    """The one-sweep backward of a call with several key blocks
    (``apex_flash_bwd_sweep``) — grid (query heads, q_blocks, k_blocks), k
    inner, as the forward's.  Each visited tile's s, p, dp and ds are
    computed once and feed all three gradients:

    - dq finishes inside the inner loop, in the ``(block_q, d)`` scratch
      the two-pass dq kernel has;
    - dk and dv of the WHOLE key/value head stay in ``dk_acc`` (sk, d) and
      ``dv_acc`` (sk, d_v), float32 VMEM scratch, for the length of the
      head's sweep — through the ``group`` query heads that share it, which
      the grid walks one after the other — and each tile adds to the rows
      of its key block.  The head's first query row zeroes key block ``ki``
      as it passes it; its last hands block ``ki``, then final, to the
      output in the output dtype (the output's index map follows ``ki``
      there and rests on block 0 before: :func:`_flash_bwd`).

    Nothing of the gradients crosses HBM but the results themselves."""
    head = pl.program_id(0)
    bh = _drop_bh(seed_ref, h_map)
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    keys = pl.ds(pl.multiple_of(ki * block_k, block_k), block_k)

    @pl.when(ki == 0)
    def _init_dq():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        _stage_stats(do_ref, o_ref, lse_ref, lse_scr, delta_scr)

    @pl.when((head % group == 0) & (qi == 0))
    def _init_dkv():
        dk_acc[keys] = jnp.zeros((block_k, dk_acc.shape[1]), dk_acc.dtype)
        dv_acc[keys] = jnp.zeros((block_k, dv_acc.shape[1]), dv_acc.dtype)

    run = True
    if causal:
        run = _causal_tile_visited(qi, ki, block_q, block_k, window=window)

    def put_dq(rows, contrib):
        dq_scr[rows] += contrib

    # several key blocks: a tile is one piece (_causal_subtile), all of its
    # block_k columns, so the accumulators' rows are the key block's
    tile = _bwd_tile(
        seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, lse_scr, delta_scr,
        dk_acc, dv_acc, put_dq, bh, qi, ki, acc_rows=keys,
        scale=scale, block_q=block_q, block_k=block_k,
        dropout_rate=dropout_rate, probs_bf16=probs_bf16, window=window)

    @pl.when(run)
    def _body():
        _for_pieces(block_q, block_k, nq, nk, causal, tile, window)

    @pl.when(ki == nk - 1)
    def _finalize_dq():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)

    @pl.when((head % group == group - 1) & (qi == nq - 1))
    def _finalize_dkv():
        dk_ref[0] = dk_acc[keys].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[keys].astype(dv_ref.dtype)


def _bwd_dq_kernel(
    seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref, lse_ref,
    dq_ref, dbias_ref, dq_scr, lse_scr, delta_scr,
    *, scale: float, causal: bool, block_q: int, block_k: int, nq: int,
    nk: int, dropout_rate: float = 0.0, h_map=None, probs_bf16: bool = False,
    window: Optional[int] = None,
):
    bh = _drop_bh(seed_ref, h_map)
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)
        _stage_stats(do_ref, o_ref, lse_ref, lse_scr, delta_scr)

    run = True
    if causal:
        run = _causal_tile_visited(qi, ki, block_q, block_k, window=window)

    def tile(r0, rows, width, mask_from):
        if dbias_ref is not None and width < block_k:
            # the keys above the rows' diagonal are never entered
            dbias_ref[0, r0:r0 + rows, width:] = jnp.zeros(
                (rows, block_k - width), dbias_ref.dtype)
        rows, cols = slice(r0, r0 + rows), slice(0, width)
        # native-dtype operands for the input-sourced dots (see _fwd_kernel)
        q = q_ref[0, rows]
        k = k_ref[0, cols]
        v = v_ref[0, cols]
        do = do_ref[0, rows]
        lse = lse_scr[rows, :1]
        delta = delta_scr[rows, :1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale
        if bias_ref is not None:
            s = s + bias_ref[0, rows, cols].astype(jnp.float32)
        if mask_from < width:
            s = _causal_mask_tail(
                s, mask_from, qi * block_q + r0, ki * block_k, window)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(
            do, v, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )
        if dropout_rate > 0.0:
            keep = _keep_mask(
                seed_ref[0], bh, seed_ref[1] + qi * block_q + r0,
                seed_ref[2] + ki * block_k, p.shape,
                dropout_rate,
            )
            dp = jnp.where(keep, dp / (1.0 - dropout_rate), 0.0)
        ds = p * (dp - delta) * scale
        if dbias_ref is not None:
            # dL/dbias for this (qi, ki) tile: the bias enters AFTER the
            # QK^T scaling, so the tile gradient is p*(dp - delta) without
            # the scale factor; each tile is visited exactly once in this
            # grid, so a plain write (no accumulation) is correct
            dbias_ref[0, rows, cols] = (p * (dp - delta)).astype(dbias_ref.dtype)
        if probs_bf16:
            ds = ds.astype(q.dtype)
            k_dot = k
        else:
            k_dot = k.astype(jnp.float32)
        dq_scr[rows] += jax.lax.dot_general(
            ds, k_dot, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )

    @pl.when(run)
    def _body():
        _for_pieces(block_q, block_k, nq, nk, causal, tile, window)

    if causal and dbias_ref is not None:
        @pl.when(jnp.logical_not(run))
        def _zero_skipped_dbias():
            dbias_ref[0] = jnp.zeros_like(dbias_ref[0])

    @pl.when(ki == nk - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call plumbing
# ---------------------------------------------------------------------------

def _grouped_route(window, group):
    """True for the calls this module gained with windows and grouped
    key/value heads.  They alone clamp their index maps to the visited
    blocks (:func:`_visited_key_blocks`); every other call keeps the maps,
    and so the programs, it had."""
    return window is not None or group > 1


def _kv_spec_by_query(block_q, block_k, d, nk, causal, window, group,
                      clamp=None):
    """The key/value BlockSpec of a grid ``(query head, q block, k
    block)``: query head ``b`` reads key/value head ``b // group``; where
    the map is ``clamp``ed (by default on the grouped route alone, whose
    calls are the newer programs) a step the causal band skips names the
    nearest visited block, so nothing moves for it."""
    if clamp is None:
        clamp = _grouped_route(window, group)
    if not clamp:
        return pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, j, 0))
    if not causal:
        return pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // group, j, 0))

    def index(b, i, j):
        lo, hi = _visited_key_blocks(i, block_q, block_k, nk, window)
        return b // group, jnp.clip(j, lo, hi), 0
    return pl.BlockSpec((1, block_k, d), index)


def _specs(block_q, block_k, d, d_v, sq, sk, with_bias, h, causal, window,
           group):
    """Common BlockSpecs: arrays are reshaped to (BH, S, D), v to (BH, S,
    D_v) / bias (B, Sq, Sk)."""
    q_spec = pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0))
    k_spec, v_spec = (
        _kv_spec_by_query(block_q, block_k, width, sk // block_k, causal,
                          window, group)
        for width in (d, d_v))
    bias_spec = (
        pl.BlockSpec((1, block_q, block_k), lambda b, i, j: (b // h, i, j))
        if with_bias
        else None
    )
    return q_spec, k_spec, v_spec, bias_spec


def _flash_fwd(q, k, v, bias, seed, scale, causal, block_q, block_k,
               dropout_rate, h_map=None, probs_bf16=False, window=None):
    bh, sq, d = q.shape
    sk = k.shape[1]
    d_v = v.shape[2]          # v and o at a head size of their own
    group = bh // k.shape[0]
    # bias stays UNEXPANDED at (B, Sq, Sk); the BlockSpec index maps divide
    # the batch*head grid index by h, so no (B*H, Sq, Sk) broadcast is ever
    # materialized in HBM (callers may still pass a pre-expanded (B*H, ...)
    # bias, in which case h == 1)
    h = 1 if bias is None else bh // bias.shape[0]
    nq = sq // block_q
    nk = sk // block_k
    q_spec, k_spec, v_spec, bias_spec = _specs(
        block_q, block_k, d, d_v, sq, sk, bias is not None, h, causal, window,
        group)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    in_specs = [seed_spec, q_spec, k_spec, v_spec]
    inputs = [seed, q, k, v]
    if bias is not None:
        in_specs.append(bias_spec)
        inputs.append(bias)
    kernel = functools.partial(
        _fwd_kernel if bias is not None else _no_bias(_fwd_kernel),
        scale=scale, causal=causal, block_q=block_q, block_k=block_k, nq=nq,
        nk=nk, dropout_rate=dropout_rate, h_map=h_map, probs_bf16=probs_bf16,
        **_window_kw(window),
    )
    out, lse = _pallas_call(
        kernel,
        name="apex_flash_fwd",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d_v), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, 1, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d_v), q.dtype),
            jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, d_v), jnp.float32),
        ],
    )(*inputs)
    return out, lse.reshape(bh, sq)


def _window_kw(window, group=1):
    """The kernels' keywords for a window and grouped heads — none for a
    call that has neither, whose partial is then the one it always was."""
    kw = {}
    if window is not None:
        kw["window"] = window
    if group > 1:
        kw["group"] = group
    return kw


def _no_bias(kernel):
    """``kernel`` for a call that has no bias: no ``bias_ref`` among its
    arguments, None in its place."""
    def without_bias(seed_ref, q_ref, k_ref, v_ref, *refs, **kw):
        kernel(seed_ref, q_ref, k_ref, v_ref, None, *refs, **kw)
    return without_bias


def _bwd_dq_only(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                 lse_ref, dq_ref, *scratch, **kw):
    _bwd_dq_kernel(seed_ref, q_ref, k_ref, v_ref, bias_ref, do_ref, o_ref,
                   lse_ref, dq_ref, None, *scratch, **kw)


def _sweep_acc_bytes(sk, d, d_v):
    """Bytes of the one-sweep backward's resident accumulators for one
    key/value head: (sk, d) and (sk, d_v) float32, as VMEM holds them —
    the last dimension in whole 128-lane tiles."""
    lanes = lambda width: -(-width // 128) * 128
    return sk * (lanes(d) + lanes(d_v)) * 4


def _bwd_sweeps(nk, bias_grad, acc_bytes):
    """How many times the backward walks a head's visited score tiles: 1
    where one kernel feeds dq, dk and dv from one computation of s, p, dp
    and ds — ``apex_flash_bwd_fused`` with one key block,
    ``apex_flash_bwd_sweep`` with several, while dk's and dv's resident
    accumulators (:func:`_sweep_acc_bytes`) fit the VMEM budget — else the
    2 of ``apex_flash_bwd_dkdv`` + ``apex_flash_bwd_dq``: a learned bias's
    gradient (each dbias tile is written once in the dq pass's grid) and a
    head too long for the budget.  The route is read from these three and
    nothing else."""
    if bias_grad:
        return 2
    return 1 if nk == 1 or acc_bytes <= _SWEEP_ACC_BUDGET_BYTES else 2


def _flash_bwd(q, k, v, bias, seed, out, lse, do, scale, causal, block_q,
               block_k, dropout_rate, bias_grad=False, h_map=None,
               probs_bf16=False, window=None):
    from apex_tpu import obs

    bh, sq, d = q.shape
    sk = k.shape[1]
    d_v = v.shape[2]          # v, o, do, dv at a head size of their own
    bhk = k.shape[0]          # key/value heads: bh // group
    group = bh // bhk
    h = 1 if bias is None else bh // bias.shape[0]  # unexpanded-bias divisor
    nq = sq // block_q
    nk = sk // block_k
    # the statistics cross HBM as one float32 a row: lse as the forward wrote
    # it, a (1, block_q) block a query block; delta is made in the kernels
    # from do and out (_stage_stats), which take do's blocks
    lse = lse.reshape(bh, 1, sq)
    stat_scratch = [pltpu.VMEM((block_q, 128), jnp.float32)] * 2
    with_bias = bias is not None
    acc_bytes = _sweep_acc_bytes(sk, d, d_v)
    sweeps = _bwd_sweeps(nk, with_bias and bias_grad, acc_bytes)
    # counted when the backward is TRACED, as the tiles are (_count_tiles);
    # layers that share one trace of the kernels (_flash_jit) count once
    reg = obs.default_registry()
    reg.counter("ops.flash.bwd_calls").inc(1)
    reg.counter("ops.flash.bwd_sweeps").inc(sweeps)
    # the statistics' bytes across HBM for this forward + backward: lse
    # written once by the forward's kernel and read once by each of the
    # backward's (one a sweep); delta adds none
    reg.counter("ops.flash.stat_hbm_bytes").inc(
        (1 + sweeps) * lse.size * lse.dtype.itemsize)
    kernel_kw = dict(
        scale=scale, causal=causal, block_q=block_q, block_k=block_k, nq=nq,
        nk=nk, dropout_rate=dropout_rate, h_map=h_map, probs_bf16=probs_bf16)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    for_bias = (lambda kernel: kernel) if with_bias else _no_bias

    def by_query(clamp=None):
        """Inputs and their specs for a query-major grid ``(query head, q
        block, k block)``, and dq's spec there."""
        by_q = lambda width: pl.BlockSpec(
            (1, block_q, width), lambda b, i, j: (b, i, 0))
        k_spec, v_spec = (
            _kv_spec_by_query(block_q, block_k, width, nk, causal, window,
                              group, clamp)
            for width in (d, d_v))
        in_specs, inputs = [seed_spec, by_q(d), k_spec, v_spec], [seed, q, k, v]
        if with_bias:
            in_specs.append(pl.BlockSpec(
                (1, block_q, block_k), lambda b, i, j: (b // h, i, j)))
            inputs.append(bias)
        in_specs += [by_q(d_v), by_q(d_v), pl.BlockSpec(
            (1, 1, block_q), lambda b, i, j: (b, 0, i))]
        inputs += [do, out, lse]
        return in_specs, inputs, by_q(d)

    if sweeps == 1 and nk > 1:
        # query-major, k inner; dk/dv's output blocks rest on key block 0
        # while the head's accumulators fill, and follow the inner index
        # through the key/value head's LAST query row, where the kernel
        # hands each block out — so every block is written back once
        def dkv_index(b, i, j):
            last_row = (b % group == group - 1) & (i == nq - 1)
            return b // group, jnp.where(last_row, j, 0), 0

        in_specs, inputs, dq_spec = by_query(clamp=True)
        dq, dk, dv = _pallas_call(
            functools.partial(for_bias(_bwd_sweep_kernel), **kernel_kw,
                              **_window_kw(window, group)),
            name="apex_flash_bwd_sweep",
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=[
                dq_spec,
                pl.BlockSpec((1, block_k, d), dkv_index),
                pl.BlockSpec((1, block_k, d_v), dkv_index),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bhk, sk, d), q.dtype),
                jax.ShapeDtypeStruct((bhk, sk, d_v), q.dtype),
            ],
            scratch_shapes=[
                pltpu.VMEM((block_q, d), jnp.float32),
                pltpu.VMEM((sk, d), jnp.float32),
                pltpu.VMEM((sk, d_v), jnp.float32),
                *stat_scratch,
            ],
            # the resident accumulators, and beside them what the tiles and
            # the pipeline's blocks take (_SWEEP_TILE_VMEM_BYTES)
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=acc_bytes + _SWEEP_TILE_VMEM_BYTES),
        )(*inputs)
        return dq, dk, dv, None

    # key-major, q inner: the one-key-block sweep and the two-pass dkv
    if not _grouped_route(window, group):
        q_index = dq_index = lambda b, i, j: (b, j, 0)
    else:
        # grid (key/value head, k block, group x q block): step j is query
        # block j % nq of the group's query head j // nq; a step the
        # causal band skips names the nearest visited query block
        def dq_index(b, i, j):
            return b * group + j // nq, j % nq, 0

        def q_index(b, i, j):
            qi = j % nq
            if causal:
                qi = jnp.clip(qi, *_visited_query_blocks(
                    i, block_q, block_k, nq, window))
            return b * group + j // nq, qi, 0
    q_spec = pl.BlockSpec((1, block_q, d), q_index)
    do_spec = pl.BlockSpec((1, block_q, d_v), q_index)

    def lse_index(b, i, j):
        head, qi, _ = q_index(b, i, j)
        return head, 0, qi
    k_spec = pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0))
    v_spec = pl.BlockSpec((1, block_k, d_v), lambda b, i, j: (b, i, 0))
    bias_spec = pl.BlockSpec((1, block_q, block_k), lambda b, i, j: (b // h, j, i))
    in_specs = [seed_spec, q_spec, k_spec, v_spec]
    inputs = [seed, q, k, v]
    if with_bias:
        in_specs.append(bias_spec)
        inputs.append(bias)
    in_specs += [do_spec, do_spec, pl.BlockSpec((1, 1, block_q), lse_index)]
    inputs += [do, out, lse]
    dkv_call = dict(
        grid=(bhk, nk, group * nq),
        in_specs=in_specs,
        out_shape=[
            jax.ShapeDtypeStruct((bhk, sk, d), q.dtype),
            jax.ShapeDtypeStruct((bhk, sk, d_v), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d_v), jnp.float32),
            *stat_scratch,
        ],
    )
    kernel_kw.update(_window_kw(window, group))

    if sweeps == 1:
        # one key block (BERT S=512, GPT S=1024 with block_k=1024): each dq
        # block is complete after its single k step — the kernel writes it
        # in the output dtype
        dkv_call["out_shape"].append(
            jax.ShapeDtypeStruct((1, bh, sq, d), q.dtype))
        dk, dv, dq = _pallas_call(
            functools.partial(for_bias(_bwd_dkv_body), **kernel_kw),
            name="apex_flash_bwd_fused",
            out_specs=[k_spec, v_spec, pl.BlockSpec(
                (1, 1, block_q, d), lambda b, i, j: (i, *dq_index(b, i, j)))],
            **dkv_call,
        )(*inputs)
        return dq[0], dk, dv, None

    dk, dv = _pallas_call(
        functools.partial(for_bias(_bwd_dkv_kernel), **kernel_kw),
        name="apex_flash_bwd_dkdv",
        out_specs=[k_spec, v_spec],
        **dkv_call,
    )(*inputs)

    in_specs, inputs, dq_spec = by_query()
    kernel_kw.pop("group", None)
    if with_bias and bias_grad:
        dq, dbias = _pallas_call(
            functools.partial(_bwd_dq_kernel, **kernel_kw),
            name="apex_flash_bwd_dq_dbias",
            grid=(bh, nq, nk),
            in_specs=in_specs,
            out_specs=[
                dq_spec,
                pl.BlockSpec((1, block_q, block_k), lambda b, i, j: (b, i, j)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
                jax.ShapeDtypeStruct((bh, sq, sk), jnp.float32),
            ],
            scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                            *stat_scratch],
        )(*inputs)
        return dq, dk, dv, dbias
    dq = _pallas_call(
        functools.partial(for_bias(_bwd_dq_only), **kernel_kw),
        name="apex_flash_bwd_dq",
        grid=(bh, nq, nk),
        in_specs=in_specs,
        out_specs=dq_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32), *stat_scratch],
    )(*inputs)
    return dq, dk, dv, None


# ---------------------------------------------------------------------------
# custom_vjp + public API
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11, 12, 13))
def _flash(q3, k3, v3, bias3, seed1, scale, causal, block_q, block_k,
           dropout_rate, bias_grad, h_map, probs_bf16, window=None):
    out, _ = _flash_fwd(
        q3, k3, v3, bias3, seed1, scale, causal, block_q, block_k,
        dropout_rate, h_map=h_map, probs_bf16=probs_bf16, window=window,
    )
    return out


def _flash_fwd_rule(q3, k3, v3, bias3, seed1, scale, causal, block_q, block_k,
                    dropout_rate, bias_grad, h_map, probs_bf16, window):
    out, lse = _flash_fwd(
        q3, k3, v3, bias3, seed1, scale, causal, block_q, block_k,
        dropout_rate, h_map=h_map, probs_bf16=probs_bf16, window=window,
    )
    # declared to the block-recomputing policies (apex_tpu.remat): under
    # them the backward reads THESE arrays and the kernel is not run a
    # second time.  q3/k3/v3 stay unnamed — cheap to make again from the
    # block's input.  Outside a jax.checkpoint the names lower to nothing.
    out = checkpoint_name(out, FLASH_OUT)
    lse = checkpoint_name(lse, FLASH_LSE)
    return out, (q3, k3, v3, bias3, seed1, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, dropout_rate, bias_grad,
                    h_map, probs_bf16, window, res, do):
    import numpy as np

    q3, k3, v3, bias3, seed1, out, lse = res
    dq, dk, dv, dbias3 = _flash_bwd(
        q3, k3, v3, bias3, seed1, out, lse, do, scale, causal, block_q,
        block_k, dropout_rate, bias_grad=bias_grad, h_map=h_map,
        probs_bf16=probs_bf16, window=window,
    )
    if bias3 is None:
        dbias = None
    elif bias_grad:
        # head reduction in fp32 BEFORE the dtype cast: a bf16 learned
        # bias keeps a full-precision gradient accumulation across heads
        b = bias3.shape[0]
        h = dbias3.shape[0] // b
        dbias = (
            dbias3.reshape(b, h, *dbias3.shape[1:])
            .sum(axis=1)
            .astype(bias3.dtype)
        )
    else:
        dbias = jnp.zeros_like(bias3)
    dseed = np.zeros(seed1.shape, jax.dtypes.float0)  # int arg: float0 cotangent
    return dq, dk, dv, dbias, dseed


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _trace_key():
    """What a trace of ``_flash`` reads besides its arguments: the
    backend (interpreter or Mosaic), the one-sweep backward's VMEM budget
    and the sub-tile width.  Tests flip these between calls of one
    signature; any new trace-time read of module state belongs here."""
    return jax.default_backend(), _SWEEP_ACC_BUDGET_BYTES, _CAUSAL_SUB


# Called through jit, so that the layers of a model — every one the same
# call — share ONE trace and ONE lowering of the kernels, forward and
# backward.  The causal bodies are unrolled over their query sub-tiles, and
# traced once a layer they doubled GPT-2 small's warm set-up on the chip
# (55 s against 27 s, PERF.md section 6, PR 25).  XLA inlines the call.
# ``trace_key`` (_trace_key()) is there only to be part of the cache key.
@functools.partial(jax.jit, static_argnums=tuple(range(5, 15)))
def _flash_jit(q3, k3, v3, bias3, seed1, scale, causal, block_q, block_k,
               dropout_rate, bias_grad, h_map, probs_bf16, window, trace_key):
    del trace_key
    return _flash(q3, k3, v3, bias3, seed1, scale, causal, block_q, block_k,
                  dropout_rate, bias_grad, h_map, probs_bf16, window)


def _pack_seed(dropout_seed, row_offset, col_offset, head_offset=0):
    """SMEM scalar block: [dropout seed, dropout row offset, dropout col
    offset, dropout head offset].  The offsets locate the call's tile
    inside the full score matrix for the DROPOUT counter hash only (ring
    attention passes its shard row/col offsets, Ulysses its head-group
    offset, so the sharded mask equals the unsharded one); causal
    masking stays in local coordinates — see the _fwd_kernel comment."""
    seed = (jnp.zeros((), jnp.int32) if dropout_seed is None
            else jnp.asarray(dropout_seed, jnp.int32).reshape(()))
    return jnp.stack([
        seed,
        jnp.asarray(row_offset, jnp.int32).reshape(()),
        jnp.asarray(col_offset, jnp.int32).reshape(()),
        jnp.asarray(head_offset, jnp.int32).reshape(()),
    ])


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    bias: Optional[jax.Array] = None,
    causal: bool = False,
    scale: Optional[float] = None,
    *,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
    dropout_heads=None,
    bias_grad: bool = False,
    probs_bf16: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    use_pallas: Optional[bool] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Flash attention.  q,k,v: (B, H, S, D); optional additive bias (B, Sq, Sk).

    ``v`` may have a head size of its own, ``(B, H, S, D_v)`` with ``D_v !=
    D`` (latent attention: 192-wide queries and keys against 128-wide
    values).  The output is then ``(B, H, S, D_v)``, and v, o, do and dv go
    through the kernels at ``D_v`` — their blocks, the output accumulator
    and dv's scratch are that wide, nothing is padded to ``D`` in HBM.  The
    default ``scale`` stays ``D ** -0.5``, the queries' size.  Read from the
    shapes: at ``D_v == D`` every call is the program it always was.

    ``k`` and ``v`` may hold fewer heads than ``q``, ``H / G``: each then
    serves ``G`` consecutive query heads (grouped-query attention).  The
    kernels read key/value head ``h // G`` through their BlockSpecs —
    nothing is repeated in memory — and the backward sums ``dk``/``dv``
    over the group inside the kernel (its inner loop walks the group's
    query heads over one scratch accumulator).

    ``window`` (with ``causal=True``) keeps for query ``i`` only the keys
    ``j`` with ``i - j < window``.  Grid tiles wholly below the band are
    skipped like those above the diagonal, the tiles the band's two edges
    cross are masked, and a skipped step moves no key block
    (:func:`_visited_key_blocks`).  A window that covers every key is
    plain causal attention and takes its programs.  Neither grouped heads
    nor a window go with ``bias``.

    ``block_q``/``block_k`` default to auto-picked sizes (the largest
    power-of-two tile of the sequence up to 512/1024 — ~2x faster than
    fixed 128 tiles on v5e, see PERF.md; a causal self-attention of up
    to 1024 positions takes its queries as ONE block as well).  The
    dropout mask is keyed on GLOBAL positions, so results are invariant
    to the block choice.

    ``causal=True`` does not compute the masked half.  Grid tiles wholly
    above the diagonal are skipped by a predicate on the grid indices;
    with one key block — every call up to S = 1024, GPT-2's among them —
    there is no such tile.  Where a head is ONE grid tile (one query
    block too: what the auto blocks pick for a causal self-attention up
    to S = 1024) the skipping happens inside the kernel body: each
    128-row query sub-tile is taken against only the keys its rows
    reach, and the mask is applied only to the key sub-tiles the
    diagonal crosses (36 of 64 sub-tiles computed and 8 masked at
    S = 1024).  The skipped elements were exact zeros, so only the
    float32 summation order changes.  A grid of several tiles a head
    keeps the grid-level skip alone.  Each call that takes the kernels
    adds its sub-tile census x batch*heads to the
    ``ops.flash.tiles_{total,visited,masked}`` counters when it is traced
    (:func:`flash_tile_census`).

    The backward walks a head's visited tiles ONCE and feeds dq, dk and dv
    from one computation of the scores — whatever the number of key blocks,
    while dk's and dv's float32 accumulators for one key/value head fit the
    VMEM budget (``sk * (d + d_v) * 4`` bytes against 24 MiB: every head up
    to 16k positions at 128 + 128) — and twice, dkv then dq, past it and
    for ``bias_grad``.  The route is read from the shapes
    (:func:`_bwd_sweeps`); a traced backward adds 1 to ``ops.flash.bwd_calls``,
    its 1 or 2 to ``ops.flash.bwd_sweeps``, and to
    ``ops.flash.stat_hbm_bytes`` the bytes ``lse`` takes across HBM: four a
    query row a head for the forward's write and for each sweep's read.

    Differentiable in q/k/v, and in ``bias`` when ``bias_grad=True``: the
    dq backward pass then also emits the per-tile dL/dbias, summed over
    the head dim in fp32 inside the vjp rule, so a *learned* bias (e.g.
    relative-position biases) trains through the kernel with a
    full-precision cross-head accumulation.  Cost note: the
    per-(batch*head) dbias tiles are materialized before the head
    reduction — an H-times-(B, Sq, Sk) fp32 write per backward;
    acceptable for the opt-in learned-bias path (the grid order needed
    for dq accumulation cannot also accumulate over heads in one pass —
    a head-inner dedicated pass would trade an extra O(S^2 D) recompute
    for the smaller write).  The default ``bias_grad=False``
    keeps the bias a constant mask (the reference's additive
    key-padding/attention masks are inputs, not parameters) and skips the
    O(S^2) dbias write entirely.

    ``dropout_rate`` > 0 applies in-kernel attention-probability dropout
    (ref fused mask+softmax+dropout); ``dropout_seed`` is a traced int32
    scalar — vary it per step, the counter-based mask derives from it
    deterministically (forward and backward regenerate the same mask).
    ``dropout_heads=(h_total, head_offset)`` declares that this call's H
    heads are the contiguous head-group [head_offset, head_offset+H) of
    a larger h_total-head attention: the mask is then keyed on GLOBAL
    head indices, making a head-sharded (Ulysses) call bitwise-identical
    to the unsharded one — the head-group analogue of the ring path's
    global row/col offsets.
    The jnp fallback uses the identical mask, so kernel and reference
    agree exactly.  Falls back to :func:`attention_ref` when shapes are
    not block-aligned or when not running on TPU.

    ``probs_bf16=True`` (opt-in, r5) rounds the softmax probabilities —
    and the backward's ds — to the INPUT dtype before the accumulator-
    precision MXU dots (p@V fwd; pd^T@do, ds^T@q, ds@K bwd), which
    otherwise run fp32 at half MXU rate.  Direct reference precedent: the
    fused-MHA extensions keep softmax outputs in half precision
    (apex/contrib/csrc/multihead_attn/softmax.h, dropout.h) — this is the
    O3 philosophy applied inside the kernel.  Accumulation stays fp32, so
    the error is one bf16 rounding of p/ds (relative ~2^-8 per element;
    measured tolerance deltas vs the fp32 kernel in
    tests/test_attention_probs_bf16.py and PERF.md r5).  No-op for fp32
    inputs and on the jnp fallback path (which keeps reference fp32
    semantics).
    """
    b, h, sq, d = q.shape
    sk = k.shape[2]
    d_v = v.shape[3]
    group = _head_group(h, k.shape[1], v.shape[1])
    if k.shape[3] != d or v.shape[2] != sk:
        raise ValueError(
            f"q {q.shape}, k {k.shape}, v {v.shape}: q and k share a head "
            f"size, k and v a length")
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True")
        if window < 1:
            raise ValueError(f"window must be positive, got {window}")
        if window >= sk:
            window = None       # every key a query may see is in the band
    if bias is not None and _grouped_route(window, group):
        raise ValueError("bias with a window or grouped heads is not supported")
    if bias is not None and bias.shape != (b, sq, sk):
        # validate eagerly: the kernel path indexes bias via b // h and
        # would read silently-wrong blocks for a mis-shaped bias
        raise ValueError(
            f"bias shape {bias.shape} != expected ({b}, {sq}, {sk})"
        )
    if scale is None:
        scale = d ** -0.5
    if block_q is None:
        # a causal head that fits one key block takes its queries as one
        # block too: with one grid tile a head the causal walk inside the
        # kernel is straight-line code (see _CAUSAL_SUB)
        one_tile = causal and sq == sk and sq <= MAX_AUTO_BLOCK_K
        block_q = _auto_block(
            sq, MAX_AUTO_BLOCK_K if one_tile else MAX_AUTO_BLOCK_Q)
    if block_k is None:
        block_k = _auto_block(sk, MAX_AUTO_BLOCK_K)
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError("dropout_rate > 0 requires dropout_seed")
    if use_pallas is None:
        from apex_tpu.ops._common import pallas_default

        use_pallas = pallas_default(
            sq % block_q == 0
            and sk % block_k == 0
            # full-dim blocks: 64/128/192/256 all map to the MXU (64 and
            # 128 in GPT-2's, BERT's and Trinity's cells, 256 in
            # Qwen3-Next's, 192 against a 128-wide v in Moonlight's)
            and d % 64 == 0 and d_v % 64 == 0
        )
    if not use_pallas:
        bias_ = bias
        if bias is not None and not bias_grad:
            bias_ = jax.lax.stop_gradient(bias)
        return attention_ref(
            q, k, v, bias_, causal, scale,
            dropout_rate=dropout_rate, dropout_seed=dropout_seed,
            dropout_heads=dropout_heads, window=window,
        )
    q3 = q.reshape(b * h, sq, d)
    k3 = k.reshape(b * h // group, sk, d)
    v3 = v.reshape(b * h // group, sk, d_v)
    bias3 = None
    if bias is not None:
        # UNEXPANDED (B, Sq, Sk): the kernels' BlockSpec index maps divide
        # the batch*head grid index by h, and the bwd rule sums the
        # per-head dbias tiles in fp32 — no (B*H, Sq, Sk) broadcast copy
        bias3 = bias if bias_grad else jax.lax.stop_gradient(bias)
    if dropout_heads is None:
        h_map = None
        seed3 = _pack_seed(dropout_seed, 0, 0)
    else:
        h_total, head0 = dropout_heads
        h_map = (h, int(h_total))
        seed3 = _pack_seed(dropout_seed, 0, 0, head0)
    _count_tiles(b * h, sq, sk, block_q, block_k, causal, window)
    out = _flash_jit(
        q3, k3, v3, bias3, seed3, float(scale), bool(causal), block_q,
        block_k, float(dropout_rate), bool(bias_grad), h_map,
        bool(probs_bf16), window, _trace_key(),
    )
    return out.reshape(b, h, sq, d_v)
