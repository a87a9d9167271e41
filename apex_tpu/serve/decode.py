"""Fused multi-token decode — K sampled tokens per donated dispatch.

"LLM Inference Acceleration via Efficient Operation Fusion" (PAPERS.md)
and the train driver's own measurements agree on where decode time goes:
not the per-token GEMMs but the boundaries around them — one dispatch,
one sample, one host round-trip per token.  ``GPTDecoder`` ports the
``FusedTrainDriver`` playbook (PR 1) to inference:

- ``prefill``: one batched dispatch writes a padded prompt batch's K/V
  into cache slots and returns next-token logits at each prompt's last
  valid position;
- ``decode_window``: K decode steps — cached attention, sampling, cache
  append, length advance — inside ONE donated ``lax.scan`` dispatch.
  Sampling lives IN the scan, so no logits ever leave the device
  mid-window; the K sampled tokens come back as one (K, slots) fetch.
- ``spec_decode_window`` (ISSUE 7): SELF-speculative decoding — each
  scan step proposes ``spec_tokens`` draft tokens from a cheap proposer
  (an n-gram/suffix matcher over the per-slot token history carried in
  the scan state, or a shallow-exit draft running the first E layers),
  verifies the whole ``1 + spec_tokens`` block in ONE batched model
  forward (``GPTLM.decode_block``), and accepts the longest draft
  prefix that matches the target tokens sampled from the verify
  logits.  Accept/rollback is pure carry arithmetic: the slot's length
  advances by the accepted count and rejected positions hold masked
  garbage K/V the next block overwrites.  Under greedy the output is
  token-exact vs the non-speculative engine; under temperature/top-k/p
  sampling each emitted token is drawn from the true conditional given
  the accepted prefix (targets are sampled independently per position,
  drafts accepted on exact match), so the DISTRIBUTION is exact even
  though the stream differs from the non-spec key sequence.  The host
  gets ``(steps, slots)`` accepted counts back with the token block —
  one fetch, as before.

Sampling is a fused on-device epilogue (``sample_tokens``): greedy,
temperature, top-k, nucleus top-p and min-p all run inside the
dispatch on per-request :class:`SamplingParams` arrays that ride the
program like the page tables — logits never leave the device on the
warm path (the host-transfer lint in tools/lint_graphs.py keeps it
that way).  One descending sort per step finds a per-row logit
threshold (top-k index, top-p cumulative-mass prefix, min-p relative
floor are all PREFIXES of the sorted order, so their intersection is a
single threshold) and masking happens in original logit order.

The cache carry is donated exactly like the train driver's: the caller
must rebind (``cache = decoder.decode_window(cache, ...)[0]``), and any
host-kept tree reused across windows needs a copy first (the PR 2
aliasing gotcha).

Programs compile per (batch, K) shape — the same static-length contract
as ``FusedTrainDriver``'s per-window-length programs; the K knob:
constructor arg > ``APEX_TPU_TOKENS_PER_DISPATCH`` env > library
default.

With a ``mesh``, every program runs through
``parallel.mesh.shard_map_compat`` with the cache sharded over the head
axis (:mod:`apex_tpu.serve.sharding`): the collectives are the
``num_layers`` head-reassembly psums traced ONCE in the scan body, so
the census is invariant in K — fusing K tokens adds zero collectives
(pinned in tests/test_inspect_hlo.py).
"""
from __future__ import annotations

import copy
import dataclasses
import math
import os
from typing import Any, Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.models.gpt import GPTConfig, GPTLM
from apex_tpu.serve.kv_cache import (
    KVCache,
    PagedKVCache,
    init_cache,
    init_paged_cache,
    kv_int8_default,
)

__all__ = [
    "DEFAULT_SPEC_HIST",
    "DEFAULT_TOKENS_PER_DISPATCH",
    "GPTDecoder",
    "SamplingParams",
    "paged_fused_serve_default",
    "propose_ngram",
    "propose_ngram_tree",
    "reference_generate",
    "sample_tokens",
    "spec_autotune_default",
    "spec_decode_default",
    "spec_tree_default",
    "tokens_per_dispatch_default",
]

DEFAULT_TOKENS_PER_DISPATCH = 8
# tokens of per-slot history the n-gram proposer matches over (carried
# in the spec window's scan state; mirrored on host by the engine)
DEFAULT_SPEC_HIST = 32


def tokens_per_dispatch_default(k: Optional[int] = None) -> int:
    """Resolve the fused decode window length K (constructor arg >
    ``APEX_TPU_TOKENS_PER_DISPATCH`` env — ``=1`` is the kill switch
    restoring per-token dispatch — > library default)."""
    if k is not None:
        return int(k)
    env = os.environ.get("APEX_TPU_TOKENS_PER_DISPATCH")
    if env:
        return int(env)
    return DEFAULT_TOKENS_PER_DISPATCH


def spec_decode_default(draft: Optional[int] = None) -> int:
    """Resolve the self-speculative DRAFT length (tokens proposed per
    verify forward): constructor arg > ``APEX_TPU_SPEC_DECODE`` env >
    default 0 (off).  ``=0`` is the kill switch restoring one model
    call per token; ``=D`` verifies ``D+1`` positions per forward."""
    if draft is not None:
        return int(draft)
    env = os.environ.get("APEX_TPU_SPEC_DECODE")
    if env:
        return int(env)
    return 0


def spec_tree_default(width: Optional[int] = None) -> int:
    """Resolve the tree-speculation branch WIDTH (candidate
    continuations verified per slot per forward): constructor arg >
    ``APEX_TPU_SPEC_TREE`` env > default 0 (chain).  ``<= 1`` keeps the
    single-branch chain proposer; ``=W >= 2`` verifies W branches in
    one batched tree forward and accepts the longest matching path."""
    if width is not None:
        return int(width)
    env = os.environ.get("APEX_TPU_SPEC_TREE")
    if env:
        return int(env)
    return 0


def spec_autotune_default(flag: Optional[bool] = None) -> bool:
    """Resolve the acceptance-histogram draft-depth autotuner:
    explicit arg > ``APEX_TPU_SPEC_AUTOTUNE`` env > default off.  The
    tuner lives in the ENGINE (host-side, reading the same per-step
    accepted counts that feed the ``serve.spec.*`` registry); the
    decoder only has to honor per-dispatch ``draft`` overrides."""
    if flag is not None:
        return bool(flag)
    env = os.environ.get("APEX_TPU_SPEC_AUTOTUNE")
    if env is None:
        return False
    return env not in ("0", "false", "False", "")


def paged_fused_serve_default(fused: Optional[bool] = None) -> bool:
    """Resolve the fused paged-attention route for a decoder:
    constructor arg > ``APEX_TPU_PAGED_FUSED`` env > default OFF (the
    live-TPU validation gate — see
    :func:`apex_tpu.ops.attention.paged_fused_default`).  Resolved ONCE
    at decoder construction and baked into every paged program the
    decoder compiles, so lazily-lowered canonical programs
    (tools/lint_graphs.py) and the engine's warm program cache see one
    fixed route."""
    if fused is not None:
        return bool(fused)
    from apex_tpu.ops.attention import paged_fused_default

    return paged_fused_default()


# ---------------------------------------------------------------------------
# fused sampling epilogue
# ---------------------------------------------------------------------------

class SamplingParams(NamedTuple):
    """Per-request sampling knobs as device arrays — one entry per
    cache slot, riding every decode dispatch as a tiny replicated
    argument (like the page tables: values are TRACED, so changing a
    request's temperature never recompiles the window).

    ``temperature <= 0`` = greedy (the others are then ignored),
    ``top_k == 0`` / ``top_p >= 1`` / ``min_p <= 0`` = that filter off.
    """

    temperature: jax.Array  # (B,) fp32
    top_k: jax.Array        # (B,) int32
    top_p: jax.Array        # (B,) fp32
    min_p: jax.Array        # (B,) fp32

    @staticmethod
    def make(b: int, temperature=0.0, top_k=0, top_p=1.0, min_p=0.0
             ) -> "SamplingParams":
        """Broadcast scalars or per-slot sequences to (b,) arrays."""
        def full(x, dt):
            return jnp.broadcast_to(jnp.asarray(x, dt), (b,))

        return SamplingParams(
            temperature=full(temperature, jnp.float32),
            top_k=full(top_k, jnp.int32),
            top_p=full(top_p, jnp.float32),
            min_p=full(min_p, jnp.float32),
        )


def _sample_filtered(logits, key, temperature, top_k, top_p, min_p):
    """The fused epilogue core: ``logits`` (..., V) any float dtype,
    the four params (...,)-shaped fp32/int32 arrays broadcastable over
    the leading dims.  One descending sort per row finds the logit
    threshold implied by the INTERSECTION of the three filters (each
    keeps a prefix of the sorted order: top-k by index, top-p by
    cumulative mass BEFORE the entry, min-p by probability relative to
    the mode), then masking happens in original order — no scatter of
    the sorted permutation back.  Greedy rows (t <= 0) return argmax
    exactly (the filters cannot remove the mode, but the explicit
    select keeps greedy bitwise key-independent)."""
    v = logits.shape[-1]
    l32 = logits.astype(jnp.float32)
    greedy = jnp.argmax(l32, axis=-1).astype(jnp.int32)
    lt = l32 / jnp.maximum(temperature, 1e-6)[..., None]
    srt = jnp.flip(jnp.sort(lt, axis=-1), axis=-1)  # descending
    keff = jnp.clip(jnp.where(top_k > 0, top_k, v), 1, v)
    idx = jnp.arange(v, dtype=jnp.int32)
    keep_k = idx < keff[..., None]
    p = jax.nn.softmax(jnp.where(keep_k, srt, -jnp.inf), axis=-1)
    cum = jnp.cumsum(p, axis=-1)
    keep_p = ((cum - p) < top_p[..., None]) | (top_p >= 1.0)[..., None]
    keep_mp = p >= min_p[..., None] * p[..., :1]
    keep = keep_k & keep_p & keep_mp
    n_keep = jnp.maximum(jnp.sum(keep, axis=-1), 1)
    thr = jnp.take_along_axis(srt, (n_keep - 1)[..., None], axis=-1)
    masked = jnp.where(lt >= thr, lt, -jnp.inf)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(
        jnp.int32
    )
    return jnp.where(temperature <= 0.0, greedy, sampled)


def sample_tokens(
    logits: jax.Array,
    key: jax.Array,
    temperature=0.0,
    *,
    top_k=None,
    top_p=None,
    min_p=None,
) -> jax.Array:
    """(B, V) fp32 logits -> (B,) int32 tokens.

    With a scalar ``temperature`` and no filters this is the PR 3
    surface, bit for bit: ``<= 0`` is greedy argmax (key unused — fully
    deterministic, the parity-test mode), else
    ``jax.random.categorical`` over ``logits/temperature``.  Passing
    any of ``top_k``/``top_p``/``min_p`` (scalars or per-row arrays) or
    an ARRAY temperature engages the fused epilogue
    (:class:`SamplingParams` semantics, per-row independent).  Pure and
    traced, so it runs identically inside the fused scan and on
    host-fetched prefill logits — and identically on every shard of a
    tensor-parallel mesh (logits and key are replicated there)."""
    if (top_k is None and top_p is None and min_p is None
            and not isinstance(temperature, (jax.Array, np.ndarray))):
        if temperature <= 0.0:
            return jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return jax.random.categorical(
            key, logits / temperature
        ).astype(jnp.int32)
    lead = logits.shape[:-1]
    full = lambda x, d, dt: jnp.broadcast_to(
        jnp.asarray(d if x is None else x, dt), lead
    )
    return _sample_filtered(
        logits, key,
        full(temperature, 0.0, jnp.float32),
        full(top_k, 0, jnp.int32),
        full(top_p, 1.0, jnp.float32),
        full(min_p, 0.0, jnp.float32),
    )


# ---------------------------------------------------------------------------
# self-speculative draft proposers
# ---------------------------------------------------------------------------

def propose_ngram(hist: jax.Array, draft: int) -> jax.Array:
    """Suffix-bigram draft proposal over per-slot token history.

    ``hist`` (B, H) int32: each row the last H tokens of that slot's
    sequence INCLUDING the not-yet-cached current token at ``[-1]``
    (``-1`` pads short histories and can never match a real token).
    Finds the most recent earlier occurrence of the trailing bigram and
    proposes the tokens that followed it, cycling with the implied
    period when the draft runs past the history end (so a period-p
    repetition proposes its exact continuation — the prompt-lookup
    decoding trick).  No match falls back to repeating the last token.
    Proposal quality only ever affects SPEED: the verify forward
    accepts exactly the tokens the model itself would have produced.
    """
    b, h = hist.shape
    a, z = hist[:, -2], hist[:, -1]
    idx = jnp.arange(h - 2, dtype=jnp.int32)
    m = (hist[:, :-2] == a[:, None]) & (hist[:, 1:-1] == z[:, None])
    m = m & ((a >= 0) & (z >= 0))[:, None]
    j = jnp.max(jnp.where(m, idx[None, :], -1), axis=1)  # latest match
    period = jnp.maximum((h - 2) - j, 1)
    take = j[:, None] + 2 + (
        jnp.arange(draft, dtype=jnp.int32)[None, :] % period[:, None]
    )
    cand = jnp.take_along_axis(hist, jnp.clip(take, 0, h - 1), axis=1)
    fallback = jnp.broadcast_to(jnp.maximum(z, 0)[:, None], (b, draft))
    drafts = jnp.where((j >= 0)[:, None], cand, fallback)
    return jnp.maximum(drafts, 0).astype(jnp.int32)


def propose_ngram_tree(hist: jax.Array, draft: int,
                       width: int) -> jax.Array:
    """:func:`propose_ngram` widened to ``width`` branches: the W MOST
    RECENT occurrences of the trailing bigram each seed a candidate
    continuation (same period-cycling readout per match), so a history
    with several competing continuations gets them all verified in one
    tree forward instead of betting on the latest.

    Returns (B, width, draft) int32.  Branch 0 is BY CONSTRUCTION the
    single-branch :func:`propose_ngram` draft (the most recent match,
    identical fallback), which is what makes tree acceptance >= chain
    acceptance per verify step — the chain path is always one of the
    candidates.  Rows with fewer than ``width`` matches duplicate the
    fallback/last-match continuation into the spare branches (duplicate
    branches are harmless: they tie and ``argmax`` keeps the lowest
    branch index).
    """
    b, h = hist.shape
    a, z = hist[:, -2], hist[:, -1]
    idx = jnp.arange(h - 2, dtype=jnp.int32)
    m = (hist[:, :-2] == a[:, None]) & (hist[:, 1:-1] == z[:, None])
    m = m & ((a >= 0) & (z >= 0))[:, None]
    scores = jnp.where(m, idx[None, :], -1)
    # W latest match positions, descending (-1 fills when fewer)
    j = jnp.flip(jnp.sort(scores, axis=1), axis=1)[:, :width]  # (B, W)
    period = jnp.maximum((h - 2) - j, 1)
    take = j[..., None] + 2 + (
        jnp.arange(draft, dtype=jnp.int32)[None, None, :]
        % period[..., None]
    )
    hist_b = jnp.broadcast_to(hist[:, None, :], (b, width, h))
    cand = jnp.take_along_axis(
        hist_b, jnp.clip(take, 0, h - 1), axis=2
    )
    fallback = jnp.broadcast_to(
        jnp.maximum(z, 0)[:, None, None], (b, width, draft)
    )
    drafts = jnp.where((j >= 0)[..., None], cand, fallback)
    return jnp.maximum(drafts, 0).astype(jnp.int32)


def _serve_config(cfg: GPTConfig, tp_axis: Optional[str]) -> GPTConfig:
    """Inference view of a training config: no dropout, no remat (no
    backward to save memory for), decode-TP axis threaded through.
    Param structure is unchanged, so trained checkpoints bind as-is."""
    return dataclasses.replace(
        cfg,
        dropout_rate=0.0,
        attn_dropout_rate=0.0,
        remat_policy="none",
        decode_tp_axis=tp_axis,
    )


class GPTDecoder:
    """Compiled prefill + fused K-token decode over a slot KV cache.

    Args:
      cfg / params: the trained ``GPTLM`` config and params (the decoder
        rebuilds the module with the inference config — same tree).
      cache_dtype / policy: cache storage dtype — explicit wins, else
        ``policy.cache_dtype`` (the AMP hook: bf16 cache under O1/O2/O3,
        fp32 under O0), else ``cfg.compute_dtype``.
      tokens_per_dispatch: the K knob (None -> env/default).
      temperature: 0.0 = greedy; > 0 samples ``categorical(logits/T)``.
        The engine may override per request via :class:`SamplingParams`
        (this value is the default for requests that don't).
      spec_tokens: self-speculative DRAFT length D (None ->
        ``APEX_TPU_SPEC_DECODE`` env, default 0 = off).  Each spec scan
        step verifies ``D+1`` positions in one model forward; the
        window runs ``ceil(K / (D+1))`` steps, so a dispatch emits
        between that many and K tokens.
      spec_proposer: ``"ngram"`` (suffix-bigram over carried history —
        zero extra model compute and zero extra collectives, the
        canonical mode) or ``"shallow"`` (shallow-exit draft: the first
        ``spec_exit_layers`` blocks run autoregressively per draft
        token — better drafts on non-repetitive text, at E extra psums
        per draft token under TP).
      spec_hist: history tokens the n-gram proposer matches over.
      spec_exit_layers: shallow-draft depth (default num_layers // 2).
      spec_tree: tree-speculation branch width W (None ->
        ``APEX_TPU_SPEC_TREE`` env, default 0 = chain).  ``W >= 2``
        verifies W candidate continuations per slot in one batched
        tree forward (ngram proposer only, paged engine only) and
        accepts the longest matching path — acceptance per verify step
        is >= the chain's because branch 0 IS the chain draft.
      paged_fused: route paged attention through the fused Pallas
        gather+dequant+attention kernel (None ->
        ``APEX_TPU_PAGED_FUSED`` env, default OFF until live-TPU
        validated).  Bitwise-identical tokens either way; baked into
        every paged program at construction.
      kv_int8: int8 paged KV pages (None -> ``APEX_TPU_KV_INT8`` env,
        default off; also implied by ``cache_dtype``/policy int8).
        Quantizes the PAGED pool only — per-token fp32 scales, fp32
        attention accumulation, bounded logit divergence.
      mesh / tp_axis: tensor-parallel serving — every program is wrapped
        in ``shard_map_compat`` with the cache head-sharded over
        ``tp_axis`` and everything else replicated.
      donate: donate the cache to prefill/decode dispatches (default;
        the caller rebinds, matching ``FusedTrainDriver``).
    """

    def __init__(
        self,
        cfg: GPTConfig,
        params,
        *,
        cache_dtype: Optional[Any] = None,
        policy=None,
        tokens_per_dispatch: Optional[int] = None,
        temperature: float = 0.0,
        spec_tokens: Optional[int] = None,
        spec_proposer: str = "ngram",
        spec_hist: int = DEFAULT_SPEC_HIST,
        spec_exit_layers: Optional[int] = None,
        spec_tree: Optional[int] = None,
        kv_int8: Optional[bool] = None,
        paged_fused: Optional[bool] = None,
        mesh=None,
        tp_axis: str = "model",
        donate: bool = True,
    ):
        self.mesh = mesh
        self.tp_axis = tp_axis if mesh is not None else None
        self.cfg = _serve_config(cfg, self.tp_axis)
        if self.tp_axis is not None:
            tp = mesh.shape[tp_axis]
            if cfg.num_heads % tp != 0:
                raise ValueError(
                    f"num_heads {cfg.num_heads} not divisible by the "
                    f"{tp_axis!r} axis size {tp}"
                )
        self.model = GPTLM(self.cfg)
        self.params = params
        if cache_dtype is None:
            cache_dtype = (
                policy.cache_dtype if policy is not None
                else cfg.compute_dtype
            )
        self.cache_dtype = cache_dtype
        self.tokens_per_dispatch = tokens_per_dispatch_default(
            tokens_per_dispatch
        )
        if self.tokens_per_dispatch < 1:
            raise ValueError("tokens_per_dispatch must be >= 1")
        self.temperature = float(temperature)
        self.spec_tokens = spec_decode_default(spec_tokens)
        if self.spec_tokens < 0:
            raise ValueError("spec_tokens must be >= 0")
        if spec_proposer not in ("ngram", "shallow"):
            raise ValueError(
                f"spec_proposer must be 'ngram' or 'shallow', got "
                f"{spec_proposer!r}"
            )
        self.spec_proposer = spec_proposer
        self.spec_hist = int(spec_hist)
        if self.spec_enabled and self.spec_hist < 4:
            raise ValueError("spec_hist must be >= 4 (bigram + context)")
        self.spec_exit_layers = (
            max(1, cfg.num_layers // 2) if spec_exit_layers is None
            else int(spec_exit_layers)
        )
        if not 1 <= self.spec_exit_layers <= cfg.num_layers:
            raise ValueError(
                f"spec_exit_layers {self.spec_exit_layers} outside "
                f"[1, {cfg.num_layers}]"
            )
        self.spec_tree = spec_tree_default(spec_tree)
        if self.spec_tree > 1:
            if not self.spec_enabled:
                raise ValueError(
                    "spec_tree needs speculation on (spec_tokens >= 1)"
                )
            if self.spec_proposer != "ngram":
                raise ValueError(
                    "tree speculation only composes with the 'ngram' "
                    "proposer (the shallow draft is a single chain)"
                )
        self.kv_int8 = (
            kv_int8_default(kv_int8)
            or jnp.dtype(self.cache_dtype) == jnp.dtype(jnp.int8)
        )
        self.paged_fused = paged_fused_serve_default(paged_fused)
        self.donate = donate
        self._programs: Dict[Tuple, Callable] = {}

    # -- speculative geometry -------------------------------------------

    @property
    def spec_enabled(self) -> bool:
        return self.spec_tokens > 0

    @property
    def spec_steps(self) -> int:
        """Verify forwards per spec window: ``ceil(K / (D+1))`` — a
        fully-accepting window emits ``spec_steps * (D+1) >= K``
        tokens, an all-rejecting one ``spec_steps``."""
        return self._spec_steps_for(self.spec_tokens)

    def _spec_steps_for(self, draft: int) -> int:
        """Verify forwards a window at draft depth ``draft`` runs to
        cover ``tokens_per_dispatch`` on full acceptance."""
        return max(1, math.ceil(self.tokens_per_dispatch / (draft + 1)))

    @property
    def spec_tree_width(self) -> int:
        """Tree branches per verify forward (1 = chain)."""
        return max(1, self.spec_tree)

    @property
    def max_tokens_per_dispatch(self) -> int:
        """Upper bound on positions ONE window may write past each
        slot's length — what the engine must ``ensure_writable`` (and
        size page headroom) for.  Equals ``tokens_per_dispatch`` when
        speculation is off."""
        if not self.spec_enabled:
            return self.tokens_per_dispatch
        return self.spec_steps * (self.spec_tokens + 1)

    def write_horizon(self, draft: Optional[int] = None) -> int:
        """Positions one window at draft depth ``draft`` (None = the
        configured depth) may WRITE past a slot's length — the
        ``ensure_writable`` span.  Chain: every step advances at most
        ``draft + 1``, so ``steps * (draft + 1)``.  Tree: the last
        step additionally PARKS all ``width * draft`` branch nodes
        (plus the root) before compaction, so the transient peak is
        ``(steps - 1) * (draft + 1) + 1 + width * draft``."""
        if not self.spec_enabled:
            return self.tokens_per_dispatch
        d = self.spec_tokens if draft is None else int(draft)
        steps = self._spec_steps_for(d)
        w = self.spec_tree_width
        if w > 1:
            return (steps - 1) * (d + 1) + 1 + w * d
        return steps * (d + 1)

    @property
    def max_write_horizon(self) -> int:
        """``write_horizon`` maximized over every draft depth the
        engine's autotuner may pick (1 .. spec_tokens) — the static
        page-headroom sizing bound."""
        if not self.spec_enabled:
            return self.tokens_per_dispatch
        return max(
            self.write_horizon(d)
            for d in range(1, self.spec_tokens + 1)
        )

    # -- cache ----------------------------------------------------------

    def init_cache(self, slots: int, max_len: int) -> KVCache:
        return init_cache(self.cfg, slots, max_len, dtype=self.cache_dtype)

    def init_paged_cache(
        self, num_pages: int, slots: int, page_len: int
    ) -> PagedKVCache:
        dtype = jnp.int8 if self.kv_int8 else self.cache_dtype
        return init_paged_cache(
            self.cfg, num_pages, slots, page_len, dtype=dtype
        )

    # -- program construction ------------------------------------------

    def _wrap(self, fn, n_extra_in: int, n_extra_out: int,
              paged: bool = False, cache_argnum: int = 1,
              quantized: bool = False):
        """shard_map the program on a TP mesh: cache head-sharded,
        params and every other in/out replicated."""
        if self.mesh is None:
            return fn
        from jax.sharding import PartitionSpec as P

        from apex_tpu.serve.sharding import (
            cache_pspec,
            paged_cache_pspec,
            shard_decode_fn,
        )

        spec = (
            paged_cache_pspec(self.tp_axis, quantized=quantized)
            if paged else cache_pspec(self.tp_axis)
        )
        in_specs = (
            (P(),) * cache_argnum + (spec,) + (P(),) * n_extra_in
        )
        out_specs = (spec,) + (P(),) * n_extra_out
        return shard_decode_fn(fn, self.mesh, in_specs, out_specs)

    def _jit(self, fn):
        return jax.jit(fn, donate_argnums=(1,) if self.donate else ())

    def _prefill_fn(self):
        def prefill(params, cache, slots, ids, lengths):
            logits, ks, vs = self.model.apply(
                {"params": params}, ids, lengths, method=GPTLM.prefill
            )
            p = ids.shape[1]
            k = cache.k.at[slots, :, :, :p, :].set(ks.astype(cache.k.dtype))
            v = cache.v.at[slots, :, :, :p, :].set(vs.astype(cache.v.dtype))
            ln = cache.lengths.at[slots].set(lengths.astype(jnp.int32))
            return cache._replace(k=k, v=v, lengths=ln), logits

        return self._jit(self._wrap(prefill, 3, 1))

    @staticmethod
    def _sample(logits, key, samp):
        """The in-scan epilogue: per-slot params, any leading shape —
        (B, V) single-step logits or (B, T, V) verify blocks (params
        broadcast over T)."""
        extra = logits.ndim - samp.temperature.ndim - 1
        exp = lambda x: x.reshape(x.shape + (1,) * extra)
        return _sample_filtered(
            logits, key, exp(samp.temperature), exp(samp.top_k),
            exp(samp.top_p), exp(samp.min_p),
        )

    def _window_fn(self, k_tokens: int):
        def window(params, cache, tokens, active, samp, key):
            smax = cache.max_len

            def body(carry, _):
                ck, cv, ln, dec, tok, ky = carry
                logits, ck, cv = self.model.apply(
                    {"params": params}, tok, ck, cv, ln,
                    method=GPTLM.decode_step,
                )
                ky, sub = jax.random.split(ky)
                nxt = self._sample(logits, sub, samp)
                tok = jnp.where(active, nxt, tok)
                ln = jnp.where(active, jnp.minimum(ln + 1, smax), ln)
                dec = dec + jnp.sum(active.astype(jnp.int32))
                return (ck, cv, ln, dec, tok, ky), tok

            init = (
                cache.k, cache.v, cache.lengths, cache.decoded,
                tokens.astype(jnp.int32), key,
            )
            (ck, cv, ln, dec, _, _), toks = jax.lax.scan(
                body, init, None, length=k_tokens
            )
            cache2 = cache._replace(k=ck, v=cv, lengths=ln, decoded=dec)
            return cache2, toks

        return self._jit(self._wrap(window, 4, 1))

    def _spec_window_fn(self, steps: int, draft: int):
        """Self-speculative window: ``steps`` scan iterations, each one
        propose -> ONE (1+draft)-position verify forward -> in-carry
        accept/rollback.  Returns the cache plus ``(steps, B, 1+draft)``
        candidate tokens and ``(steps, B)`` accepted counts — the host
        consumes ``toks[i, b, :acc[i, b]]``."""
        proposer = self.spec_proposer
        exit_layers = self.spec_exit_layers

        def window(params, cache, tokens, active, hist, samp, key):
            smax = cache.max_len

            def body(carry, _):
                ck, cv, ln, dec, tok, hs, ky = carry
                if proposer == "shallow":
                    # autoregressive shallow-exit draft: the first
                    # exit_layers blocks write their own cache layers at
                    # the draft positions (the full-depth verify below
                    # overwrites them before anything reads them)
                    dtok, dln, ds = tok, ln, []
                    for _d in range(draft):
                        lgt, ck, cv = self.model.apply(
                            {"params": params}, dtok, ck, cv, dln,
                            n_layers=exit_layers,
                            method=GPTLM.decode_step,
                        )
                        dtok = jnp.argmax(lgt, axis=-1).astype(jnp.int32)
                        ds.append(dtok)
                        dln = jnp.minimum(dln + 1, smax - 1)
                    drafts = jnp.stack(ds, axis=1)
                else:
                    drafts = propose_ngram(hs, draft)
                block = jnp.concatenate([tok[:, None], drafts], axis=1)
                logits, ck, cv = self.model.apply(
                    {"params": params}, block, ck, cv, ln,
                    method=GPTLM.decode_block,
                )
                ky, sub = jax.random.split(ky)
                targ = self._sample(logits, sub, samp)  # (B, 1+draft)
                match = drafts == targ[:, :-1]
                ok = jnp.cumprod(match.astype(jnp.int32), axis=1)
                n_acc = 1 + jnp.sum(ok, axis=1)          # in [1, 1+draft]
                n_eff = jnp.where(
                    active, jnp.minimum(n_acc, smax - ln), 0
                )
                new_tok = jnp.take_along_axis(
                    targ, (n_acc - 1)[:, None], axis=1
                )[:, 0]
                tok = jnp.where(active, new_tok, tok)
                ext = jnp.concatenate([hs, targ], axis=1)
                hidx = n_eff[:, None] + jnp.arange(
                    hs.shape[1], dtype=jnp.int32
                )[None, :]
                hs = jnp.take_along_axis(ext, hidx, axis=1)
                ln = ln + n_eff
                dec = dec + jnp.sum(n_eff)
                return (ck, cv, ln, dec, tok, hs, ky), (targ, n_acc)

            init = (
                cache.k, cache.v, cache.lengths, cache.decoded,
                tokens.astype(jnp.int32), hist.astype(jnp.int32), key,
            )
            (ck, cv, ln, dec, _, _, _), (toks, acc) = jax.lax.scan(
                body, init, None, length=steps
            )
            cache2 = cache._replace(k=ck, v=cv, lengths=ln, decoded=dec)
            return cache2, toks, acc

        return self._jit(self._wrap(window, 5, 2))

    # -- paged program construction ------------------------------------

    @staticmethod
    def _unpack_paged(cache, out):
        """Rebind a paged model method's return into the cache pytree
        (the int8 methods return their updated scale arrays too)."""
        if cache.k_scale is not None:
            logits, pk, pv, ks, vs = out
            return logits, cache._replace(k=pk, v=pv, k_scale=ks,
                                          v_scale=vs)
        logits, pk, pv = out
        return logits, cache._replace(k=pk, v=pv)

    def _paged_chunk_fn(self, quantized: bool):
        def chunk(params, cache, slot_tables, slots, ids, base, valid):
            out = self.model.apply(
                {"params": params}, ids, base, valid, cache.k, cache.v,
                slot_tables, k_scale=cache.k_scale,
                v_scale=cache.v_scale,
                method=GPTLM.paged_prefill_chunk,
            )
            logits, cache = self._unpack_paged(cache, out)
            ln = cache.lengths.at[slots].set(
                (base + valid).astype(jnp.int32)
            )
            return cache._replace(lengths=ln), logits

        return self._jit(
            self._wrap(chunk, 5, 1, paged=True, quantized=quantized)
        )

    def _paged_window_fn(self, k_tokens: int, quantized: bool,
                         fused: bool = False):
        def window(params, cache, tables, tokens, active, samp, key):
            smax = tables.shape[1] * cache.page_len

            def body(carry, _):
                cch, tok, ky = carry
                ln = cch.lengths
                out = self.model.apply(
                    {"params": params}, tok, cch.k, cch.v, tables, ln,
                    k_scale=cch.k_scale, v_scale=cch.v_scale,
                    fused=fused,
                    method=GPTLM.paged_decode_step,
                )
                logits, cch = self._unpack_paged(cch, out)
                ky, sub = jax.random.split(ky)
                nxt = self._sample(logits, sub, samp)
                tok = jnp.where(active, nxt, tok)
                ln = jnp.where(active, jnp.minimum(ln + 1, smax), ln)
                dec = cch.decoded + jnp.sum(active.astype(jnp.int32))
                cch = cch._replace(lengths=ln, decoded=dec)
                return (cch, tok, ky), tok

            init = (cache, tokens.astype(jnp.int32), key)
            (cache2, _, _), toks = jax.lax.scan(
                body, init, None, length=k_tokens
            )
            return cache2, toks

        return self._jit(
            self._wrap(window, 5, 1, paged=True, quantized=quantized)
        )

    def _paged_spec_window_fn(self, steps: int, draft: int,
                              quantized: bool, fused: bool = False):
        """The paged twin of :meth:`_spec_window_fn` — verify blocks
        read/write through the page table (int8 pools compose: the
        verify block quantizes exactly like the single-token step, so
        spec-vs-nonspec stays token-identical under greedy at equal
        pool dtype)."""
        proposer = self.spec_proposer
        exit_layers = self.spec_exit_layers

        def window(params, cache, tables, tokens, active, hist, samp,
                   key):
            smax = tables.shape[1] * cache.page_len

            def body(carry, _):
                cch, tok, hs, ky = carry
                ln = cch.lengths
                if proposer == "shallow":
                    dtok, dln, ds = tok, ln, []
                    for _d in range(draft):
                        out = self.model.apply(
                            {"params": params}, dtok, cch.k, cch.v,
                            tables, dln, k_scale=cch.k_scale,
                            v_scale=cch.v_scale, n_layers=exit_layers,
                            fused=fused,
                            method=GPTLM.paged_decode_step,
                        )
                        lgt, cch = self._unpack_paged(cch, out)
                        dtok = jnp.argmax(lgt, axis=-1).astype(jnp.int32)
                        ds.append(dtok)
                        dln = jnp.minimum(dln + 1, smax - 1)
                    drafts = jnp.stack(ds, axis=1)
                else:
                    drafts = propose_ngram(hs, draft)
                block = jnp.concatenate([tok[:, None], drafts], axis=1)
                out = self.model.apply(
                    {"params": params}, block, cch.k, cch.v, tables, ln,
                    k_scale=cch.k_scale, v_scale=cch.v_scale,
                    fused=fused,
                    method=GPTLM.paged_decode_block,
                )
                logits, cch = self._unpack_paged(cch, out)
                ky, sub = jax.random.split(ky)
                targ = self._sample(logits, sub, samp)
                match = drafts == targ[:, :-1]
                ok = jnp.cumprod(match.astype(jnp.int32), axis=1)
                n_acc = 1 + jnp.sum(ok, axis=1)
                n_eff = jnp.where(
                    active, jnp.minimum(n_acc, smax - ln), 0
                )
                new_tok = jnp.take_along_axis(
                    targ, (n_acc - 1)[:, None], axis=1
                )[:, 0]
                tok = jnp.where(active, new_tok, tok)
                ext = jnp.concatenate([hs, targ], axis=1)
                hidx = n_eff[:, None] + jnp.arange(
                    hs.shape[1], dtype=jnp.int32
                )[None, :]
                hs = jnp.take_along_axis(ext, hidx, axis=1)
                cch = cch._replace(
                    lengths=ln + n_eff,
                    decoded=cch.decoded + jnp.sum(n_eff),
                )
                return (cch, tok, hs, ky), (targ, n_acc)

            init = (cache, tokens.astype(jnp.int32),
                    hist.astype(jnp.int32), key)
            (cache2, _, _, _), (toks, acc) = jax.lax.scan(
                body, init, None, length=steps
            )
            return cache2, toks, acc

        return self._jit(
            self._wrap(window, 6, 2, paged=True, quantized=quantized)
        )

    @staticmethod
    def _tree_compact(cch, tables, ln, rstar, n_eff, active, draft):
        """Move the WINNING branch's parked K/V into the canonical
        chain slots after tree acceptance.

        The tree block parks branch r's node j at slot ``ln + 1 + r *
        draft + j``; acceptance commits nodes ``0 .. n_eff - 2`` of
        branch ``rstar`` to logical slots ``ln + 1 ..``.  Branch 0 is
        already canonical (its parking IS the chain layout), so rows
        with ``rstar == 0`` — and inactive/overflow rows — degrade to
        identity writes (src == dst).  For ``rstar >= 1`` the source
        range sits strictly above every destination (``ln + 1 + draft
        > ln + 1 + draft - 1``), so one gather + one scatter with no
        aliasing hazard; pages are per-slot-owned, so cross-row index
        collisions only happen on the trash page, where garbage is
        spec.  Pure page-axis moves with full head slices: under TP
        this is shard-local — the window census stays at the
        num_layers reassembly psums."""
        pl_ = cch.page_len
        smax = tables.shape[1] * pl_
        b = tables.shape[0]
        jvec = jnp.arange(draft, dtype=jnp.int32)
        dst = jnp.minimum(ln[:, None] + 1 + jvec[None, :], smax - 1)
        src = jnp.minimum(
            ln[:, None] + 1 + rstar[:, None] * draft + jvec[None, :],
            smax - 1,
        )
        do = (
            active[:, None]
            & (rstar > 0)[:, None]
            & (jvec[None, :] < (n_eff - 1)[:, None])
        )
        src = jnp.where(do, src, dst)
        bidx = jnp.arange(b)
        ps, os_ = tables[bidx[:, None], src // pl_], src % pl_
        pd, od = tables[bidx[:, None], dst // pl_], dst % pl_
        k = cch.k.at[pd, :, :, od].set(cch.k[ps, :, :, os_])
        v = cch.v.at[pd, :, :, od].set(cch.v[ps, :, :, os_])
        upd = {}
        if cch.k_scale is not None:
            upd["k_scale"] = cch.k_scale.at[pd, :, :, od].set(
                cch.k_scale[ps, :, :, os_]
            )
            upd["v_scale"] = cch.v_scale.at[pd, :, :, od].set(
                cch.v_scale[ps, :, :, os_]
            )
        return cch._replace(k=k, v=v, **upd)

    def _paged_tree_window_fn(self, steps: int, draft: int, width: int,
                              quantized: bool, fused: bool = False):
        """Tree-speculative window: each scan step proposes ``width``
        branch continuations (:func:`propose_ngram_tree`), verifies all
        of them in ONE batched tree forward
        (:meth:`GPTLM.paged_decode_tree_block`), picks the
        longest-accepted path, and compacts its K/V into the chain
        slots.  Downstream of branch selection the carry arithmetic is
        EXACTLY the chain window's, applied to the winning branch's
        chain-equivalent ``(B, draft + 1)`` target block — so greedy
        tokens are identical to the chain (and non-spec) engines, and
        per-step acceptance is >= chain's because branch 0 IS the
        chain draft.  Returns ``(cache, toks, acc, branches)`` with
        ``branches`` (steps, B) the winning branch index per step (the
        engine's tree-win stats)."""

        def window(params, cache, tables, tokens, active, hist, samp,
                   key):
            smax = tables.shape[1] * cache.page_len

            def body(carry, _):
                cch, tok, hs, ky = carry
                ln = cch.lengths
                drafts = propose_ngram_tree(hs, draft, width)
                b = tok.shape[0]
                block = jnp.concatenate(
                    [tok[:, None], drafts.reshape(b, width * draft)],
                    axis=1,
                )
                out = self.model.apply(
                    {"params": params}, block, cch.k, cch.v, tables,
                    ln, k_scale=cch.k_scale, v_scale=cch.v_scale,
                    width=width, depth=draft, fused=fused,
                    method=GPTLM.paged_decode_tree_block,
                )
                logits, cch = self._unpack_paged(cch, out)
                ky, sub = jax.random.split(ky)
                targ = self._sample(logits, sub, samp)  # (B, 1+W*D)
                # per-branch longest accepted prefix: node (r, j) is
                # accepted iff every draft token up to j matches the
                # target sampled at its PREDECESSOR node (root for
                # j=0, else node (r, j-1))
                ridx = (
                    1
                    + jnp.arange(width, dtype=jnp.int32)[:, None] * draft
                    + jnp.arange(draft, dtype=jnp.int32)[None, :]
                )  # (W, D) node index of branch r's j-th draft token
                prev = jnp.concatenate(
                    [jnp.zeros((width, 1), jnp.int32), ridx[:, :-1]],
                    axis=1,
                )
                tprev = targ[:, prev]                    # (B, W, D)
                match = drafts == tprev
                okm = jnp.cumprod(match.astype(jnp.int32), axis=2)
                n_acc_r = 1 + jnp.sum(okm, axis=2)       # (B, W)
                # first max wins ties -> branch 0 (the chain draft)
                rstar = jnp.argmax(n_acc_r, axis=1).astype(jnp.int32)
                # near the page-capacity clamp the extra branches'
                # parked writes collide at slot smax-1; fall back to
                # branch 0 there, which restores the chain window's
                # exact clamp behavior
                fits = (ln + width * draft) <= (smax - 1)
                rstar = jnp.where(fits, rstar, 0)
                n_acc = jnp.take_along_axis(
                    n_acc_r, rstar[:, None], axis=1
                )[:, 0]
                # the winning branch's chain-equivalent (D+1) targets
                sel = jnp.concatenate(
                    [
                        jnp.zeros((b, 1), jnp.int32),
                        1 + rstar[:, None] * draft
                        + jnp.arange(draft, dtype=jnp.int32)[None, :],
                    ],
                    axis=1,
                )
                ctarg = jnp.take_along_axis(targ, sel, axis=1)
                n_eff = jnp.where(
                    active, jnp.minimum(n_acc, smax - ln), 0
                )
                new_tok = jnp.take_along_axis(
                    ctarg, (n_acc - 1)[:, None], axis=1
                )[:, 0]
                tok = jnp.where(active, new_tok, tok)
                ext = jnp.concatenate([hs, ctarg], axis=1)
                hidx = n_eff[:, None] + jnp.arange(
                    hs.shape[1], dtype=jnp.int32
                )[None, :]
                hs = jnp.take_along_axis(ext, hidx, axis=1)
                cch = self._tree_compact(
                    cch, tables, ln, rstar, n_eff, active, draft
                )
                cch = cch._replace(
                    lengths=ln + n_eff,
                    decoded=cch.decoded + jnp.sum(n_eff),
                )
                return (cch, tok, hs, ky), (ctarg, n_acc, rstar)

            init = (cache, tokens.astype(jnp.int32),
                    hist.astype(jnp.int32), key)
            (cache2, _, _, _), (toks, acc, br) = jax.lax.scan(
                body, init, None, length=steps
            )
            return cache2, toks, acc, br

        return self._jit(
            self._wrap(window, 6, 3, paged=True, quantized=quantized)
        )

    def _copy_pages_fn(self, quantized: bool):
        def copy(cache, src, dst):
            k = cache.k.at[dst].set(cache.k[src])
            v = cache.v.at[dst].set(cache.v[src])
            upd = {}
            if cache.k_scale is not None:
                upd["k_scale"] = cache.k_scale.at[dst].set(
                    cache.k_scale[src]
                )
                upd["v_scale"] = cache.v_scale.at[dst].set(
                    cache.v_scale[src]
                )
            return cache._replace(k=k, v=v, **upd)

        wrapped = self._wrap(copy, 2, 0, paged=True, cache_argnum=0,
                             quantized=quantized)
        return jax.jit(
            wrapped, donate_argnums=(0,) if self.donate else ()
        )

    def _gather_pages_fn(self, quantized: bool):
        """Read physical pages out of the pool — the EXPORT half of a
        prefill→decode handoff (ISSUE 12).  NOT donated: the source
        cache keeps serving until the transfer lands (a lost handoff
        falls back to recompute, so nothing may be consumed early)."""
        def gather(cache, pages):
            out = [cache.k[pages], cache.v[pages]]
            if cache.k_scale is not None:
                out += [cache.k_scale[pages], cache.v_scale[pages]]
            return tuple(out)

        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from apex_tpu.serve.sharding import (
                paged_cache_pspec,
                shard_decode_fn,
            )

            kv = P(None, None, self.tp_axis)
            outs = (kv, kv) + ((kv, kv) if quantized else ())
            gather = shard_decode_fn(
                gather, self.mesh,
                (paged_cache_pspec(self.tp_axis, quantized=quantized),
                 P()),
                outs,
            )
        return jax.jit(gather)

    def _adopt_pages_fn(self, quantized: bool):
        """Write transferred page contents into fresh pool pages AND
        set the adopted slot's length — the IMPORT half of a handoff,
        one donated dispatch (``copy_pages``-style: identity pad rows
        target the trash page sink)."""
        if quantized:
            def adopt(cache, pages, kb, vb, ksb, vsb, slot, length):
                return cache._replace(
                    k=cache.k.at[pages].set(kb.astype(cache.k.dtype)),
                    v=cache.v.at[pages].set(vb.astype(cache.v.dtype)),
                    k_scale=cache.k_scale.at[pages].set(ksb),
                    v_scale=cache.v_scale.at[pages].set(vsb),
                    lengths=cache.lengths.at[slot].set(length),
                )
            n_extra = 7
        else:
            def adopt(cache, pages, kb, vb, slot, length):
                return cache._replace(
                    k=cache.k.at[pages].set(kb.astype(cache.k.dtype)),
                    v=cache.v.at[pages].set(vb.astype(cache.v.dtype)),
                    lengths=cache.lengths.at[slot].set(length),
                )
            n_extra = 5
        if self.mesh is not None:
            from jax.sharding import PartitionSpec as P

            from apex_tpu.serve.sharding import (
                paged_cache_pspec,
                shard_decode_fn,
            )

            spec = paged_cache_pspec(self.tp_axis, quantized=quantized)
            kv = P(None, None, self.tp_axis)
            ins = (spec, P(), kv, kv)
            if quantized:
                ins = ins + (kv, kv)
            ins = ins + (P(), P())
            assert len(ins) == n_extra + 1
            adopt = shard_decode_fn(adopt, self.mesh, ins, spec)
        return jax.jit(
            adopt, donate_argnums=(0,) if self.donate else ()
        )

    @staticmethod
    def _page_bucket(n: int) -> int:
        """Power-of-two page-count bucket — one compiled transfer
        program per bucket, like the COW copy executor."""
        width = 1
        while width < n:
            width *= 2
        return width

    def gather_pages(self, cache: PagedKVCache, pages):
        """Fetch the contents of ``pages`` (physical ids, logical
        order) to host: ``(k, v, k_scale, v_scale)`` numpy arrays of
        leading dim ``len(pages)`` (scales None on fp32/bf16 pools).
        Pads the id vector to a power-of-two bucket with the trash page
        (its garbage rows are trimmed before return)."""
        n = len(pages)
        if n < 1:
            raise ValueError("gather_pages needs at least one page")
        width = self._page_bucket(n)
        ids = np.zeros((width,), np.int32)
        ids[:n] = pages
        prog = self._program(
            ("pgather", width, cache.page_len, cache.quantized)
        )
        out = prog(cache, jnp.asarray(ids))
        k, v = np.asarray(out[0])[:n], np.asarray(out[1])[:n]
        if cache.quantized:
            return k, v, np.asarray(out[2])[:n], np.asarray(out[3])[:n]
        return k, v, None, None

    def adopt_pages(
        self, cache: PagedKVCache, pages, k, v, k_scale, v_scale,
        slot: int, length: int,
    ) -> PagedKVCache:
        """Scatter transferred page contents into ``pages`` (freshly
        imported physical ids) and set ``slot``'s valid length, in ONE
        donated bucket-padded dispatch — rebind the cache."""
        n = len(pages)
        width = self._page_bucket(n)
        ids = np.zeros((width,), np.int32)
        ids[:n] = pages

        def pad(a):
            if a.shape[0] == width:
                return a
            out = np.zeros((width,) + a.shape[1:], a.dtype)
            out[:n] = a
            return out

        prog = self._program(
            ("pscatter", width, cache.page_len, cache.quantized)
        )
        args = [cache, jnp.asarray(ids), pad(k), pad(v)]
        if cache.quantized:
            args += [pad(k_scale), pad(v_scale)]
        args += [jnp.asarray(slot, jnp.int32),
                 jnp.asarray(length, jnp.int32)]
        return prog(*args)

    def reset_programs(self) -> None:
        """Drop every compiled program (simulated host preemption: a
        restarted process starts with a cold jit cache — the resilience
        harness uses this to make cold-restart costs measurable; engine
        CRASH recovery deliberately keeps the decoder, which is why its
        replay adds zero compiles)."""
        self._programs.clear()

    def with_params(self, params) -> "GPTDecoder":
        """A shallow clone serving ``params`` through the SAME compiled
        programs (the ``_programs`` dict is shared by reference).

        The live-promotion primitive (ISSUE 18): params ride every
        program as a call argument, so rebinding them costs zero warm
        compiles as long as the new tree matches the old one leaf for
        leaf in shape and dtype — enforced here, because an aval
        mismatch would otherwise surface later as a silent retrace.
        Cloning (rather than mutating ``self.params``) keeps fleet
        hosts that share one decoder object independently promotable:
        host 0 can serve the new weights while host 1 still drains on
        the old ones.
        """
        old = jax.tree_util.tree_flatten_with_path(self.params)
        new = jax.tree_util.tree_flatten_with_path(params)
        if jax.tree_util.tree_structure(self.params) != \
                jax.tree_util.tree_structure(params):
            raise ValueError(
                "with_params: new tree structure differs from the "
                "served one — a geometry change needs a new decoder"
            )
        for (path, a), (_, b) in zip(old[0], new[0]):
            if a.shape != b.shape or a.dtype != b.dtype:
                raise ValueError(
                    f"with_params: leaf {jax.tree_util.keystr(path)} "
                    f"changed aval {a.dtype}{a.shape} -> "
                    f"{b.dtype}{b.shape} — a geometry change needs a "
                    "new decoder (and pays its compile bill)"
                )
        clone = copy.copy(self)
        clone.params = params
        return clone

    def _program(self, key: Tuple) -> Callable:
        prog = self._programs.get(key)
        if prog is None:
            if key[0] == "prefill":
                prog = self._prefill_fn()
            elif key[0] == "pchunk":
                prog = self._paged_chunk_fn(key[-1])
            elif key[0] == "pwindow":
                prog = self._paged_window_fn(key[1], key[-2], key[-1])
            elif key[0] == "pswindow":
                prog = self._paged_spec_window_fn(
                    key[1], key[2], key[-2], key[-1]
                )
            elif key[0] == "ptwindow":
                prog = self._paged_tree_window_fn(
                    key[1], key[2], key[3], key[-2], key[-1]
                )
            elif key[0] == "swindow":
                prog = self._spec_window_fn(key[1], key[2])
            elif key[0] == "pcopy":
                prog = self._copy_pages_fn(key[-1])
            elif key[0] == "pgather":
                prog = self._gather_pages_fn(key[-1])
            elif key[0] == "pscatter":
                prog = self._adopt_pages_fn(key[-1])
            else:
                prog = self._window_fn(key[1])
            self._programs[key] = prog
        return prog

    # -- execution ------------------------------------------------------

    def prefill(self, cache: KVCache, slots, input_ids, lengths):
        """Write a padded prompt batch into ``slots``; returns
        ``(cache, next_logits)``.  ``input_ids`` (B, P) right-padded,
        ``lengths`` (B,); one compiled program per (B, P).  The cache is
        donated — rebind it."""
        slots = jnp.asarray(slots, jnp.int32)
        input_ids = jnp.asarray(input_ids, jnp.int32)
        lengths = jnp.asarray(lengths, jnp.int32)
        prog = self._program(("prefill", input_ids.shape))
        return prog(self.params, cache, slots, input_ids, lengths)

    def _samp_default(self, b: int) -> SamplingParams:
        return SamplingParams.make(b, temperature=self.temperature)

    def decode_window(
        self, cache: KVCache, tokens, active, key,
        k_tokens: Optional[int] = None, samp: Optional[SamplingParams] = None,
    ):
        """ONE fused dispatch of K decode steps over every slot.

        ``tokens`` (slots,) the last sampled token per slot, ``active``
        (slots,) bool — inactive (free) slots decode garbage that never
        advances their length or the token counter.  ``samp`` carries
        per-slot :class:`SamplingParams` (None -> the decoder's scalar
        temperature for every slot).  Returns ``(cache, toks)`` with
        ``toks`` (K, slots) the sampled tokens.  The cache is donated —
        rebind it.
        """
        k = self.tokens_per_dispatch if k_tokens is None else int(k_tokens)
        tokens = jnp.asarray(tokens, jnp.int32)
        active = jnp.asarray(active, bool)
        if samp is None:
            samp = self._samp_default(tokens.shape[0])
        prog = self._program(("window", k, tokens.shape[0]))
        return prog(self.params, cache, tokens, active, samp, key)

    def spec_decode_window(
        self, cache: KVCache, tokens, active, hist, key,
        samp: Optional[SamplingParams] = None,
        draft: Optional[int] = None,
    ):
        """ONE fused SELF-SPECULATIVE dispatch: ``spec_steps``
        propose->verify->accept iterations over every slot.

        ``hist`` (slots, spec_hist) int32 — each slot's trailing token
        history INCLUDING its current token (``-1`` padding; the engine
        mirrors this on host from the accepted tokens it fetches, so
        the array is a plain replicated argument, not a donated carry).
        Returns ``(cache, toks, acc)``: ``toks`` (steps, slots,
        1+spec_tokens) candidate tokens, ``acc`` (steps, slots)
        accepted counts — the emitted stream is ``toks[i, s, :acc[i,
        s]]`` per step.  The cache is donated — rebind it."""
        d = self.spec_tokens if draft is None else int(draft)
        if not 1 <= d <= self.spec_tokens:
            raise ValueError(
                f"draft override {d} outside [1, {self.spec_tokens}]"
            )
        tokens = jnp.asarray(tokens, jnp.int32)
        active = jnp.asarray(active, bool)
        hist = jnp.asarray(hist, jnp.int32)
        if samp is None:
            samp = self._samp_default(tokens.shape[0])
        prog = self._program(
            ("swindow", self._spec_steps_for(d), d,
             tokens.shape[0])
        )
        return prog(self.params, cache, tokens, active, hist, samp, key)

    def lower_window(
        self, cache: KVCache, tokens, active, key,
        k_tokens: Optional[int] = None,
        samp: Optional[SamplingParams] = None,
    ):
        """``jax.jit(...).lower(...)`` of the decode window — the HLO
        proof object (tests/test_inspect_hlo.py pins the K-invariant
        collective census on it)."""
        k = self.tokens_per_dispatch if k_tokens is None else int(k_tokens)
        tokens = jnp.asarray(tokens, jnp.int32)
        active = jnp.asarray(active, bool)
        if samp is None:
            samp = self._samp_default(tokens.shape[0])
        prog = self._program(("window", k, tokens.shape[0]))
        return prog.lower(self.params, cache, tokens, active, samp, key)

    # -- paged execution ------------------------------------------------

    def prefill_chunk(
        self, cache: PagedKVCache, slot_tables, slots, input_ids,
        base, valid,
    ):
        """Write ONE chunk of a paged prefill; returns ``(cache,
        logits)`` with logits at each row's last valid chunk position.

        ``slot_tables`` (B, pages_per_slot): the page-table rows of the
        chunk's slots (the host allocator's view — every page in the
        written range must already be exclusively owned, see
        :meth:`~apex_tpu.serve.kv_cache.PagePool.ensure_writable`);
        ``input_ids`` (B, C) right-padded to the chunk bucket, ``base``/
        ``valid`` (B,) absolute start positions and real token counts.
        One compiled program per (B, C) bucket; the cache is donated —
        rebind it.
        """
        prog, args = self._chunk_call(cache, slot_tables, slots,
                                      input_ids, base, valid)
        return prog(*args)

    def _chunk_call(self, cache, slot_tables, slots, input_ids, base,
                    valid):
        """The chunk program for these shapes and its call arguments."""
        slot_tables = jnp.asarray(slot_tables, jnp.int32)
        slots = jnp.asarray(slots, jnp.int32)
        input_ids = jnp.asarray(input_ids, jnp.int32)
        base = jnp.asarray(base, jnp.int32)
        valid = jnp.asarray(valid, jnp.int32)
        prog = self._program(
            ("pchunk", input_ids.shape, slot_tables.shape[1],
             cache.page_len, cache.quantized)
        )
        return prog, (self.params, cache, slot_tables, slots, input_ids,
                      base, valid)

    def lower_prefill_chunk(
        self, cache: PagedKVCache, slot_tables, slots, input_ids,
        base, valid,
    ):
        """``jax.jit(...).lower(...)`` of the :meth:`prefill_chunk`
        program for these shapes (the cache is not consumed) — the
        prefill twin of :meth:`lower_paged_window`, for asking what was
        compiled into it (``chip_smoke.py`` counts its Mosaic calls)."""
        prog, args = self._chunk_call(cache, slot_tables, slots,
                                      input_ids, base, valid)
        return prog.lower(*args)

    def paged_decode_window(
        self, cache: PagedKVCache, tables, tokens, active, key,
        k_tokens: Optional[int] = None,
        samp: Optional[SamplingParams] = None,
    ):
        """The fused K-token decode window over the page pool — same
        contract as :meth:`decode_window` (one donated dispatch, K
        sampled tokens back as (K, slots)), with K/V read and written
        through ``tables`` (slots, pages_per_slot).  The host must have
        made each active slot's ``[len, len+K)`` range exclusively
        writable first."""
        k = self.tokens_per_dispatch if k_tokens is None else int(k_tokens)
        tables = jnp.asarray(tables, jnp.int32)
        tokens = jnp.asarray(tokens, jnp.int32)
        active = jnp.asarray(active, bool)
        if samp is None:
            samp = self._samp_default(tokens.shape[0])
        prog = self._program(
            ("pwindow", k, tokens.shape[0], tables.shape[1],
             cache.page_len, cache.quantized, self.paged_fused)
        )
        return prog(self.params, cache, tables, tokens, active, samp,
                    key)

    def paged_spec_decode_window(
        self, cache: PagedKVCache, tables, tokens, active, hist, key,
        samp: Optional[SamplingParams] = None,
        draft: Optional[int] = None,
    ):
        """:meth:`spec_decode_window` over the page pool: the host must
        have made each active slot's ``[len, len +
        write_horizon(draft))`` range exclusively writable first
        (every position a fully-accepting window could reach).  Returns
        ``(cache, toks, acc)`` shaped as in
        :meth:`spec_decode_window`.  ``draft`` overrides the configured
        depth for THIS dispatch (the engine autotuner's lever; each
        distinct depth compiles its own window once, then serves
        warm)."""
        d = self.spec_tokens if draft is None else int(draft)
        if not 1 <= d <= self.spec_tokens:
            raise ValueError(
                f"draft override {d} outside [1, {self.spec_tokens}]"
            )
        tables = jnp.asarray(tables, jnp.int32)
        tokens = jnp.asarray(tokens, jnp.int32)
        active = jnp.asarray(active, bool)
        hist = jnp.asarray(hist, jnp.int32)
        if samp is None:
            samp = self._samp_default(tokens.shape[0])
        prog = self._program(
            ("pswindow", self._spec_steps_for(d), d,
             tokens.shape[0], tables.shape[1], cache.page_len,
             cache.quantized, self.paged_fused)
        )
        return prog(self.params, cache, tables, tokens, active, hist,
                    samp, key)

    def paged_tree_spec_decode_window(
        self, cache: PagedKVCache, tables, tokens, active, hist, key,
        samp: Optional[SamplingParams] = None,
        draft: Optional[int] = None,
    ):
        """The TREE-speculative paged window (``spec_tree`` width W >=
        2): W candidate branches per slot verified in one batched tree
        forward per step, longest accepted path compacted into the
        chain slots.  The host must have made each active slot's
        ``[len, len + write_horizon(draft))`` range exclusively
        writable first (the tree PARKS all branches before
        compaction).  Returns ``(cache, toks, acc, branches)`` —
        ``toks``/``acc`` exactly as :meth:`paged_spec_decode_window`
        (the winning branch's chain-equivalent block), ``branches``
        (steps, slots) the winning branch per step."""
        if self.spec_tree_width < 2:
            raise ValueError(
                "paged_tree_spec_decode_window needs spec_tree >= 2"
            )
        d = self.spec_tokens if draft is None else int(draft)
        if not 1 <= d <= self.spec_tokens:
            raise ValueError(
                f"draft override {d} outside [1, {self.spec_tokens}]"
            )
        tables = jnp.asarray(tables, jnp.int32)
        tokens = jnp.asarray(tokens, jnp.int32)
        active = jnp.asarray(active, bool)
        hist = jnp.asarray(hist, jnp.int32)
        if samp is None:
            samp = self._samp_default(tokens.shape[0])
        prog = self._program(
            ("ptwindow", self._spec_steps_for(d), d,
             self.spec_tree_width, tokens.shape[0], tables.shape[1],
             cache.page_len, cache.quantized, self.paged_fused)
        )
        return prog(self.params, cache, tables, tokens, active, hist,
                    samp, key)

    def copy_pages(self, cache: PagedKVCache, src, dst) -> PagedKVCache:
        """Copy-on-write executor: physical pages ``src[i] -> dst[i]``
        (all layers/heads/columns — int8 pools copy their scale rows in
        the same dispatch) in one donated dispatch.  Pad with ``src =
        dst = 0`` identity rows to hold a fixed bucket width (the trash
        page copying onto itself is a no-op)."""
        src = jnp.asarray(src, jnp.int32)
        dst = jnp.asarray(dst, jnp.int32)
        prog = self._program(
            ("pcopy", src.shape[0], cache.page_len, cache.quantized)
        )
        return prog(cache, src, dst)

    def lower_paged_window(
        self, cache: PagedKVCache, tables, tokens, active, key,
        k_tokens: Optional[int] = None,
        samp: Optional[SamplingParams] = None,
    ):
        """``lower()`` of the paged decode window — the HLO proof object
        for the paged collective census (tools/lint_graphs.py)."""
        k = self.tokens_per_dispatch if k_tokens is None else int(k_tokens)
        tables = jnp.asarray(tables, jnp.int32)
        tokens = jnp.asarray(tokens, jnp.int32)
        active = jnp.asarray(active, bool)
        if samp is None:
            samp = self._samp_default(tokens.shape[0])
        prog = self._program(
            ("pwindow", k, tokens.shape[0], tables.shape[1],
             cache.page_len, cache.quantized, self.paged_fused)
        )
        return prog.lower(self.params, cache, tables, tokens, active,
                          samp, key)


def reference_generate(
    cfg: GPTConfig,
    params,
    prompt_ids,
    n_tokens: int,
    temperature: float = 0.0,
    key: Optional[jax.Array] = None,
    pad_to: Optional[int] = None,
):
    """Naive per-token FULL-RECOMPUTE loop — the correctness oracle.

    Each step runs the whole training forward (``GPTLM.__call__``, no
    cache) on the sequence so far and samples from the last position:
    one dispatch AND one O(S²) recompute per token.  The fused cached
    decode must be token-identical to this under greedy sampling
    (tests/test_serve.py) — it shares ``_logits`` and the fp32
    attention-accumulation discipline, it just never recomputes.

    The sequence lives in a FIXED-width right-padded buffer (``pad_to``,
    default the final length rounded up to a power of two) so the whole
    rollout is ONE compiled program: causal attention makes the logits
    at position ``len-1`` independent of the zero padding to its right,
    and a per-length recompile would otherwise dominate the loop.
    """
    model = GPTLM(_serve_config(cfg, None))
    total = len(prompt_ids) + n_tokens
    if pad_to is None:
        pad_to = 8
        while pad_to < total:
            pad_to *= 2
    if pad_to < total or pad_to > cfg.max_position:
        raise ValueError(
            f"pad_to {pad_to} must fit prompt+n_tokens ({total}) and "
            f"max_position ({cfg.max_position})"
        )
    apply = jax.jit(lambda p, ids: model.apply({"params": p}, ids))
    buf = [int(t) for t in prompt_ids] + [0] * (pad_to - len(prompt_ids))
    cur = len(prompt_ids)
    if key is None:
        key = jax.random.PRNGKey(0)
    out = []
    for _ in range(n_tokens):
        logits = apply(params, jnp.asarray([buf], jnp.int32))[0, cur - 1]
        key, sub = jax.random.split(key)
        tok = int(sample_tokens(logits[None], sub, temperature)[0])
        out.append(tok)
        if cur < pad_to:
            buf[cur] = tok
        cur += 1
    return out
