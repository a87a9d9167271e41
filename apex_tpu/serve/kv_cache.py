"""Slot-based KV cache — the serving-side memory plan.

One preallocated pair of arrays ``[slots, layers, heads, max_len,
head_dim]`` holds every in-flight sequence's keys/values; a sequence
occupies one SLOT for its lifetime and the continuous-batching engine
(:mod:`apex_tpu.serve.engine`) recycles slots at dispatch boundaries.
Preallocation is the point: decode-side memory is cache-dominated, and a
fixed footprint means admission control is a free-slot check, not an
allocator gamble mid-traffic.

dtype comes from the AMP policy (:meth:`apex_tpu.amp.Policy.cache_dtype`
— bf16 under the half policies, halving bytes/slot; fp32 under O0);
attention ACCUMULATION stays fp32 regardless — the cache dtype only
rounds the stored K/V once, the serve analog of the flash kernels'
accumulator discipline (bounded in tests/test_serve.py).

The cache is a plain NamedTuple pytree, so it rides jit carries and the
fused decode window's DONATED dispatch unchanged.  Mind the repo's
aliasing gotcha (PR 2): a donated window consumes its input cache — the
caller must rebind, and host-kept copies need ``jnp.array(x, copy=True)``.

``lengths`` (the per-slot valid prefix) is device-side and authoritative
inside fused windows; the engine mirrors it on host for scheduling.
``decoded`` is the on-device generated-token counter (throughput
accounting: accumulated inside the scan carry, read once per stats
call — never per token).

Int8 KV pages (ISSUE 7): the PAGED pool additionally supports int8
storage with per-(page, layer, head, position) fp32 scales riding in
``k_scale``/``v_scale`` alongside the pool.  Each written token's K/V
vector is abs-max/127 symmetric-quantized ONCE at write time (scales are
per stored token, so incremental page writes never requantize earlier
tokens), and the gather inside
:func:`apex_tpu.ops.attention.paged_cached_attention` dequantizes into
the fp32 attention accumulation.  dtype comes from the same policy hook
(``Policy.kv_cache_dtype = jnp.int8``) or the ``APEX_TPU_KV_INT8`` env;
the contiguous slot cache stays bf16/fp32 (it is the parity reference).
"""
from __future__ import annotations

import os
from typing import Any, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


class KVCache(NamedTuple):
    """Device state of the decode engine (a pytree; see module docs)."""

    k: jax.Array        # (slots, layers, heads, max_len, head_dim)
    v: jax.Array        # (slots, layers, heads, max_len, head_dim)
    lengths: jax.Array  # (slots,) int32 valid prefix per slot
    decoded: jax.Array  # () int32 total generated tokens (on-device meter)

    @property
    def slots(self) -> int:
        return self.k.shape[0]

    @property
    def layers(self) -> int:
        return self.k.shape[1]

    @property
    def heads(self) -> int:
        return self.k.shape[2]

    @property
    def max_len(self) -> int:
        return self.k.shape[3]

    @property
    def head_dim(self) -> int:
        return self.k.shape[4]

    @property
    def bytes_per_slot(self) -> int:
        """K+V bytes one slot pins for its lifetime."""
        per = self.layers * self.heads * self.max_len * self.head_dim
        return 2 * per * jnp.dtype(self.k.dtype).itemsize


def cache_bytes_per_slot(cfg, max_len: int, dtype=None) -> int:
    """Shape-only bytes/slot for a :class:`GPTConfig` — the admission
    planner's figure, no arrays needed."""
    d = cfg.hidden_size // cfg.num_heads
    per = cfg.num_layers * cfg.num_heads * max_len * d
    return 2 * per * jnp.dtype(dtype or cfg.compute_dtype).itemsize


def init_cache(
    cfg,
    slots: int,
    max_len: int,
    dtype: Optional[Any] = None,
    policy=None,
) -> KVCache:
    """Preallocate a zeroed cache for ``slots`` concurrent sequences.

    ``dtype`` wins when given; else ``policy.cache_dtype`` (the AMP
    hook); else the config's compute dtype.  ``max_len`` must fit the
    model's learned positions (``cfg.max_position``).
    """
    if max_len > cfg.max_position:
        raise ValueError(
            f"max_len {max_len} exceeds cfg.max_position {cfg.max_position}"
        )
    if dtype is None:
        dtype = policy.cache_dtype if policy is not None else cfg.compute_dtype
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        raise ValueError(
            "int8 KV storage is paged-only (per-page scale columns live "
            "with the page pool) — use init_paged_cache, or keep the "
            "contiguous cache at bf16/fp32 as the parity reference"
        )
    d = cfg.hidden_size // cfg.num_heads
    shape = (slots, cfg.num_layers, cfg.num_heads, max_len, d)
    return KVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((slots,), jnp.int32),
        decoded=jnp.zeros((), jnp.int32),
    )


def reset_slots(cache: KVCache, slots) -> KVCache:
    """Zero the valid prefix of the given slots (freeing is a length
    reset — the K/V bytes are garbage the next prefill overwrites)."""
    slots = jnp.asarray(slots, jnp.int32)
    return cache._replace(lengths=cache.lengths.at[slots].set(0))


class SlotAllocator:
    """Host-side free-list over the cache's slot axis.

    Pure scheduling state (which slot is occupied lives with the engine
    on host; the device only sees per-slot lengths + active masks), so
    allocation never touches the device.  FIFO free list: a retired
    slot goes to the back, maximizing the time before its stale K/V is
    overwritten — harmless either way, helpful when debugging.
    """

    def __init__(self, n_slots: int):
        if n_slots < 1:
            raise ValueError("need at least one slot")
        self.n_slots = n_slots
        self._free: List[int] = list(range(n_slots))

    @property
    def n_free(self) -> int:
        return len(self._free)

    def allocate(self) -> Optional[int]:
        """Pop a free slot id, or None when the cache is full (the
        engine then leaves the request queued — continuous batching
        admits it at a later dispatch boundary)."""
        if not self._free:
            return None
        return self._free.pop(0)

    def free(self, slot: int) -> None:
        if slot in self._free:
            raise ValueError(f"slot {slot} double-freed")
        if not 0 <= slot < self.n_slots:
            raise ValueError(f"slot {slot} out of range")
        self._free.append(slot)


# ---------------------------------------------------------------------------
# paged cache — the global page pool + host-side page-table allocator
# ---------------------------------------------------------------------------

TRASH_PAGE = 0  # physical page 0 is never allocated: free/unmapped table
# entries point here, so inactive slots' masked decode writes land in a
# sink instead of corrupting a live request's pages


def paged_kv_default(flag: Optional[bool] = None) -> bool:
    """Resolve the paged-KV toggle (explicit arg > ``APEX_TPU_PAGED_KV``
    env — ``=0`` is the kill switch restoring the contiguous per-slot
    cache — > default ON)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("APEX_TPU_PAGED_KV", "1") != "0"


def kv_int8_default(flag: Optional[bool] = None) -> bool:
    """Resolve the int8 KV page toggle (explicit arg >
    ``APEX_TPU_KV_INT8`` env — ``=1`` quantizes the paged pool, ``=0``
    is the kill switch — > default OFF: int8 pages trade bounded logit
    divergence for ~2x cache bytes, an opt-in trade)."""
    if flag is not None:
        return bool(flag)
    return os.environ.get("APEX_TPU_KV_INT8", "0") not in ("0", "")


class PagedKVCache(NamedTuple):
    """Device state of the PAGED decode engine (a pytree, donated
    through every prefill-chunk/decode/copy dispatch exactly like
    :class:`KVCache`).

    Instead of one ``max_len`` row per slot, K/V live in a global pool
    of fixed-size pages; a host-side :class:`PagePool` maps each slot's
    logical positions to physical pages and passes the ``(slots,
    pages_per_slot)`` int32 page table to every dispatch as a plain
    argument (it is tiny, changes at dispatch boundaries only, and
    keeping it host-side makes allocation/copy-on-write pure host
    bookkeeping — no device round-trip per table edit).
    """

    k: jax.Array        # (num_pages, layers, heads, page_len, head_dim)
    v: jax.Array        # (num_pages, layers, heads, page_len, head_dim)
    lengths: jax.Array  # (slots,) int32 valid prefix per slot
    decoded: jax.Array  # () int32 total generated tokens (on-device meter)
    # int8 mode only: per-(page, layer, head, position) fp32 abs-max
    # scales (None leaves on fp32/bf16 pools — the pytree structure is
    # what selects the quantized read/write paths in models/gpt.py)
    k_scale: Optional[jax.Array] = None  # (num_pages, layers, heads, page_len)
    v_scale: Optional[jax.Array] = None

    @property
    def num_pages(self) -> int:
        return self.k.shape[0]

    @property
    def layers(self) -> int:
        return self.k.shape[1]

    @property
    def heads(self) -> int:
        return self.k.shape[2]

    @property
    def page_len(self) -> int:
        return self.k.shape[3]

    @property
    def head_dim(self) -> int:
        return self.k.shape[4]

    @property
    def slots(self) -> int:
        return self.lengths.shape[0]

    @property
    def quantized(self) -> bool:
        return self.k_scale is not None

    @property
    def bytes_per_page(self) -> int:
        """K+V bytes one physical page pins while allocated (including
        the per-token scale columns in int8 mode)."""
        per = self.layers * self.heads * self.page_len * self.head_dim
        n = 2 * per * jnp.dtype(self.k.dtype).itemsize
        if self.k_scale is not None:
            per_s = self.layers * self.heads * self.page_len
            n += 2 * per_s * jnp.dtype(self.k_scale.dtype).itemsize
        return n


def auto_page_len(max_len: int, preferred: int = 16) -> int:
    """Largest power-of-two page length <= ``preferred`` dividing
    ``max_len`` — the engine's default when none is given (a ragged
    ``max_len`` like 12 still pages cleanly at 4)."""
    p = preferred
    while p > 1 and max_len % p:
        p //= 2
    return p


def init_paged_cache(
    cfg,
    num_pages: int,
    slots: int,
    page_len: int,
    dtype: Optional[Any] = None,
    policy=None,
) -> PagedKVCache:
    """Preallocate a zeroed page pool (page 0 is the reserved trash
    page).  dtype resolution matches :func:`init_cache`."""
    if num_pages < 2:
        raise ValueError("need at least one real page beyond the trash page")
    if page_len < 1:
        raise ValueError("page_len must be >= 1")
    if dtype is None:
        dtype = policy.cache_dtype if policy is not None else cfg.compute_dtype
    d = cfg.hidden_size // cfg.num_heads
    shape = (num_pages, cfg.num_layers, cfg.num_heads, page_len, d)
    scale = None
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        # per-token symmetric scales ride alongside the pool; init 1.0
        # so unwritten (trash) entries dequantize to harmless zeros
        scale = jnp.ones(shape[:4], jnp.float32)
    return PagedKVCache(
        k=jnp.zeros(shape, dtype),
        v=jnp.zeros(shape, dtype),
        lengths=jnp.zeros((slots,), jnp.int32),
        decoded=jnp.zeros((), jnp.int32),
        k_scale=scale,
        v_scale=None if scale is None else jnp.ones(shape[:4], jnp.float32),
    )


class PagePool:
    """Host-side allocator over the physical page axis: free list,
    refcounts, per-slot page tables, and the shared-prefix registry.

    Pure scheduling state, like :class:`SlotAllocator` — the device only
    ever sees the page table rows the engine passes to each dispatch.
    Sharing model:

    - a physical page may back the same logical page of several slots
      (refcount > 1) when their prompts agree on every token up to the
      end of that page's coverage — prefix reuse;
    - APPENDS require exclusive ownership: :meth:`ensure_writable` is
      called with the position range a dispatch will write, and any
      shared page in range is copy-on-write split (fresh page + a
      device-side content copy the caller must execute BEFORE the
      write dispatch) while unmapped logical pages get fresh pages;
    - freeing is refcount-decrement; a page returning to the free list
      is dropped from the prefix registry.

    The registry keys are full token prefixes (``tuple(prompt[:n])``):
    causal attention makes a page's K/V content a pure function of every
    token up to its coverage, so equal keys == bitwise-equal pages.
    Registered pages may later be appended to by their owner — safe,
    because a reader sharing the page masks all positions at or beyond
    its own length, and a writer first goes through copy-on-write.
    """

    def __init__(self, num_pages: int, page_len: int, slots: int,
                 pages_per_slot: int):
        if num_pages - 1 < pages_per_slot:
            raise ValueError(
                f"pool of {num_pages} pages (1 reserved) cannot hold even "
                f"one full-length sequence ({pages_per_slot} pages)"
            )
        self.num_pages = num_pages
        self.page_len = page_len
        self.pages_per_slot = pages_per_slot
        self._free: List[int] = list(range(1, num_pages))
        self.ref = np.zeros((num_pages,), np.int32)
        self.tables = np.zeros((slots, pages_per_slot), np.int32)
        self._prefix: Dict[Tuple[int, ...], int] = {}
        self._rev: Dict[int, Tuple[int, ...]] = {}
        # observability (surfaced by ServeEngine.stats())
        self.peak_in_use = 0
        self.cow_copies = 0
        self.prefix_hits = 0
        self.prefix_hit_tokens = 0

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_pages - 1 - len(self._free)

    def _alloc(self) -> Optional[int]:
        if not self._free:
            return None
        page = self._free.pop(0)
        self.ref[page] = 1
        self.peak_in_use = max(self.peak_in_use, self.in_use)
        return page

    def _decref(self, page: int) -> None:
        self.ref[page] -= 1
        if self.ref[page] < 0:
            raise ValueError(f"page {page} refcount underflow")
        if self.ref[page] == 0:
            key = self._rev.pop(page, None)
            if key is not None:
                self._prefix.pop(key, None)
            self._free.append(page)

    # -- prefix sharing -------------------------------------------------

    def match_prefix(self, prompt: List[int]) -> Tuple[List[int], int]:
        """Longest registered prefix of ``prompt``: returns the shared
        physical pages (one per covered logical page, in order) and the
        number of tokens they cover.  Full pages match greedily; at most
        one trailing PARTIAL page may match (longest registered tail),
        after which the requester diverges mid-page and copy-on-write
        takes over on its first append."""
        pl = self.page_len
        pages: List[int] = []
        pos = 0
        while pos + pl <= len(prompt):
            page = self._prefix.get(tuple(prompt[: pos + pl]))
            if page is None:
                break
            pages.append(page)
            pos += pl
        rem = min(pl - 1, len(prompt) - pos)
        for m in range(rem, 0, -1):
            page = self._prefix.get(tuple(prompt[: pos + m]))
            if page is not None:
                pages.append(page)
                pos += m
                break
        return pages, pos

    def share(self, slot: int, pages: List[int], tokens: int) -> None:
        """Map ``pages`` (from :meth:`match_prefix`) as the first
        logical pages of ``slot``, increffing each."""
        for i, page in enumerate(pages):
            if self.tables[slot, i]:
                raise ValueError(f"slot {slot} logical page {i} occupied")
            self.tables[slot, i] = page
            self.ref[page] += 1
        if pages:
            self.prefix_hits += 1
            self.prefix_hit_tokens += tokens

    def register(self, slot: int, prompt: List[int]) -> None:
        """Publish ``slot``'s freshly prefilled prompt pages for reuse:
        one key per full page, plus the partial tail (exact-prompt
        matches and mid-page divergence both hit it)."""
        pl = self.page_len
        n = len(prompt)
        for i in range((n + pl - 1) // pl):
            end = min((i + 1) * pl, n)
            key = tuple(prompt[:end])
            page = int(self.tables[slot, i])
            if page == TRASH_PAGE or key in self._prefix:
                continue
            if page in self._rev:  # a page holds at most one key
                continue
            self._prefix[key] = page
            self._rev[page] = key

    # -- write ownership ------------------------------------------------

    def ensure_writable(self, slot: int, start: int, end: int):
        """Make positions ``[start, end)`` of ``slot`` exclusively
        writable: allocate unmapped logical pages, copy-on-write shared
        ones.  Returns the ``(src, dst)`` physical copy pairs the caller
        must execute on device BEFORE its write dispatch, or ``None``
        when the pool is exhausted (caller preempts or truncates;
        allocations already made stay mapped and are reclaimed by
        :meth:`release_slot`)."""
        pl = self.page_len
        end = min(end, self.pages_per_slot * pl)
        copies: List[Tuple[int, int]] = []
        if start >= end:
            return copies
        for pidx in range(start // pl, (end - 1) // pl + 1):
            cur = int(self.tables[slot, pidx])
            if cur == TRASH_PAGE:
                page = self._alloc()
                if page is None:
                    return None
                self.tables[slot, pidx] = page
            elif self.ref[cur] > 1:
                page = self._alloc()
                if page is None:
                    return None
                copies.append((cur, page))
                self.tables[slot, pidx] = page
                self._decref(cur)
                self.cow_copies += 1
        return copies

    def release_slot(self, slot: int) -> None:
        """Decref every page the slot maps and reset its table row to
        the trash page (inactive slots' masked decode writes must land
        in the sink, never a recycled page)."""
        for pidx in range(self.pages_per_slot):
            page = int(self.tables[slot, pidx])
            if page != TRASH_PAGE:
                self._decref(page)
        self.tables[slot, :] = TRASH_PAGE

    def slot_pages(self, slot: int) -> List[int]:
        """Physical pages currently mapped by ``slot`` (debug/tests)."""
        return [int(p) for p in self.tables[slot] if p != TRASH_PAGE]

    # -- disaggregated handoff (ISSUE 12) -------------------------------

    def export_slot(self, slot: int, n_pages: int) -> List[int]:
        """The slot's first ``n_pages`` physical pages in logical order
        — the page-table half of a prefill→decode handoff.  Pure read:
        refcounts and the prefix registry are untouched (the source
        keeps serving the pages until the transfer lands; shared /
        COW'd pages export their CONTENT, ownership never travels)."""
        pages = []
        for pidx in range(int(n_pages)):
            page = int(self.tables[slot, pidx])
            if page == TRASH_PAGE:
                raise ValueError(
                    f"slot {slot} logical page {pidx} unmapped — cannot "
                    f"export {n_pages} page(s)"
                )
            pages.append(page)
        return pages

    def import_slot(self, slot: int, n_pages: int) -> Optional[List[int]]:
        """Map ``n_pages`` FRESH exclusively-owned pages (refcount 1)
        as the slot's first logical pages — the destination half of a
        handoff; the caller scatters the transferred contents into the
        returned physical pages.  All-or-nothing: returns None (and
        leaves the pool untouched) when the free list cannot supply the
        run, so a starved import falls cleanly back to recompute."""
        if any(self.tables[slot, :]):
            raise ValueError(f"slot {slot} already mapped")
        if n_pages < 1 or n_pages > self.pages_per_slot:
            raise ValueError(
                f"import of {n_pages} page(s) outside [1, "
                f"{self.pages_per_slot}]"
            )
        pages: List[int] = []
        for pidx in range(int(n_pages)):
            page = self._alloc()
            if page is None:
                for p in pages:  # rollback: nothing stays half-mapped
                    self._decref(p)
                self.tables[slot, :] = TRASH_PAGE
                return None
            self.tables[slot, pidx] = page
            pages.append(page)
        return pages

    def import_pages(self, slot: int, start_pidx: int,
                     n_pages: int) -> Optional[List[int]]:
        """Incremental (chunked) variant of :meth:`import_slot`: map
        ``n_pages`` fresh exclusively-owned pages at logical indices
        ``[start_pidx, start_pidx + n_pages)`` of ``slot``.  Earlier
        chunks' pages stay mapped; the target range must be unmapped.
        All-or-nothing PER CHUNK: returns None (this chunk rolled back,
        prior chunks untouched) when the free list starves — the caller
        aborts the staged adoption via :meth:`release_slot`."""
        if n_pages < 1 or start_pidx < 0 \
                or start_pidx + n_pages > self.pages_per_slot:
            raise ValueError(
                f"chunk of {n_pages} page(s) at {start_pidx} outside "
                f"[0, {self.pages_per_slot})"
            )
        if any(self.tables[slot, start_pidx:start_pidx + n_pages]):
            raise ValueError(
                f"slot {slot} logical pages [{start_pidx}, "
                f"{start_pidx + n_pages}) already mapped"
            )
        pages: List[int] = []
        for pidx in range(start_pidx, start_pidx + int(n_pages)):
            page = self._alloc()
            if page is None:
                for i, p in enumerate(pages):
                    self.tables[slot, start_pidx + i] = TRASH_PAGE
                    self._decref(p)
                return None
            self.tables[slot, pidx] = page
            pages.append(page)
        return pages

    # -- proactive prefix adoption (ISSUE 17 rebalancer) ----------------

    def adopt_prefix(self, tokens: List[int]) -> Optional[List[int]]:
        """Allocate fresh ANCHOR pages for a page-aligned token prefix
        and publish them in the prefix registry without mapping them to
        any slot — the destination half of a proactive page migration.
        The refcount-1 anchor keeps the pages (and their registry keys)
        alive so later arrivals :meth:`match_prefix` straight into
        them; :meth:`release_prefix` drops the anchor.  Returns the
        physical pages (the caller scatters the migrated contents into
        them), or None when the prefix is already registered or the
        free list cannot supply the run (nothing mapped)."""
        pl = self.page_len
        if not tokens or len(tokens) % pl:
            raise ValueError(
                f"adopt_prefix needs a page-aligned prefix, got "
                f"{len(tokens)} token(s) at page_len {pl}"
            )
        keys = [tuple(tokens[:(i + 1) * pl])
                for i in range(len(tokens) // pl)]
        if any(k in self._prefix for k in keys):
            return None
        pages: List[int] = []
        for _ in keys:
            page = self._alloc()
            if page is None:
                for p in pages:
                    self._decref(p)
                return None
            pages.append(page)
        for key, page in zip(keys, pages):
            self._prefix[key] = page
            self._rev[page] = key
        return pages

    def release_prefix(self, pages: List[int]) -> None:
        """Drop the anchor refs taken by :meth:`adopt_prefix` (pages
        still shared by live slots survive until their last reader)."""
        for page in pages:
            self._decref(int(page))

    def drop_prefixes(self) -> int:
        """Unpublish EVERY prefix-registry key (returns how many).

        The weight-change invalidation (ISSUE 18): cached prompt pages
        encode K/V computed under the OLD weights, so after a
        changed-weights swap a future prompt must not ``match_prefix``
        into them.  Pages mapped by live slots keep their refs — they
        are about to be released by the swap's recompute requeue — but
        no new reader can share them; anchor-only pages (refcount held
        solely by :meth:`adopt_prefix`) stay allocated until their
        anchor is released by the owner."""
        n = len(self._prefix)
        self._prefix.clear()
        self._rev.clear()
        return n

    # -- out-of-band reservations ---------------------------------------

    def reserve(self, n: int) -> List[int]:
        """Take up to ``n`` pages out of circulation WITHOUT mapping
        them to any slot — the page-pressure lever: admission and
        :meth:`ensure_writable` see a smaller free list, so saturation
        behaviors (backpressure, preemption) are exercisable on demand
        (``apex_tpu.resilience`` fault injection; also usable as a
        static HBM headroom reservation).  Returns the reserved page
        ids; give them back with :meth:`unreserve`."""
        pages: List[int] = []
        for _ in range(max(0, int(n))):
            page = self._alloc()
            if page is None:
                break
            pages.append(page)
        return pages

    def unreserve(self, pages: List[int]) -> None:
        """Return pages taken by :meth:`reserve` to the free list."""
        for page in pages:
            self._decref(int(page))


def paged_cache_bytes(cfg, pages: int, page_len: int, dtype=None) -> int:
    """Shape-only bytes for ``pages`` pool pages — the paged analog of
    :func:`cache_bytes_per_slot`.  int8 includes the
    per-token fp32 scale columns, so the planner figure is honest about
    the quantization overhead (4/head_dim per stored byte)."""
    d = cfg.hidden_size // cfg.num_heads
    dt = jnp.dtype(dtype or cfg.compute_dtype)
    per = cfg.num_layers * cfg.num_heads * page_len * d
    n = 2 * pages * per * dt.itemsize
    if dt == jnp.dtype(jnp.int8):
        n += 2 * pages * cfg.num_layers * cfg.num_heads * page_len * 4
    return n
