"""GPT-style causal decoder LM — the long-context benchmark vehicle.

No direct reference counterpart (the reference's transformer surface is
the contrib MHA kernels exercised by BERT-style encoders); this decoder
completes the model zoo with the causal-LM family the flash kernel's
causal path and the sequence-parallel layer (ring/Ulysses) exist for.
Design mirrors :mod:`apex_tpu.models.bert` so one policy story serves
both: pre-LN blocks (GPT-2), fused LayerNorm, flash attention with
causal=True (block-skipping kernel path) and in-kernel probability
dropout, fused/auto-gated softmax-xentropy loss, tied embeddings.

Sequence parallelism: ``GPTLayer`` takes an ``attention_fn`` so the same
block runs single-device flash attention (default) or a sequence-sharded
construction — pass ``ring_attention``/``ulysses_attention`` partials
inside shard_map (tests/test_models.py shows the ring-sharded layer
matching the single-device layer).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp import functional as F
from apex_tpu.amp.layers import Dense
from apex_tpu.models.decoder import masked_token_mean_loss
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.ops.attention import (
    cached_attention,
    flash_attention,
    paged_cached_attention,
    quantize_kv,
)
from apex_tpu.remat import remat_module

__all__ = ["GPTConfig", "GPTLayer", "GPTLM"]


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50304  # GPT-2 50257 padded to a multiple of 128
    hidden_size: int = 768  # GPT-2 small
    num_layers: int = 12
    num_heads: int = 12
    max_position: int = 1024
    dropout_rate: float = 0.1
    attn_dropout_rate: float = 0.1
    # opt-in half-precision-probability dots in the flash kernel
    probs_bf16: bool = False
    # activation rematerialization per decoder block: none | dots_saveable
    # | full_block (apex_tpu.remat; both keep the flash kernel's output
    # and lse) — memory freed here + ZeRO sharding
    # buys larger microbatches under the accumulation driver mode
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16
    tie_word_embeddings: bool = True
    # serving (apex_tpu.serve): mesh axis the decode path's heads + KV
    # cache are sharded over.  None = single-device decode.  When set,
    # the cached-attention branch of GPTLayer computes only its local
    # head group and reassembles the head axis with ONE psum per layer
    # (the Megatron minimum) — see apex_tpu/serve/sharding.py.
    decode_tp_axis: Any = None

    @property
    def intermediate_size(self) -> int:
        return 4 * self.hidden_size

    @staticmethod
    def small(**kw) -> "GPTConfig":
        return GPTConfig(**kw)

    @staticmethod
    def medium(**kw) -> "GPTConfig":
        return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16, **kw)

    @staticmethod
    def tiny(**kw) -> "GPTConfig":
        """For tests: 2 layers, 128 hidden."""
        return GPTConfig(
            vocab_size=1024, hidden_size=128, num_layers=2, num_heads=2,
            max_position=128, **kw,
        )


def _default_attention(q, k, v, *, dropout_rate, dropout_seed,
                       probs_bf16=False):
    return flash_attention(
        q, k, v, causal=True,
        dropout_rate=dropout_rate, dropout_seed=dropout_seed,
        probs_bf16=probs_bf16,
    )


def _cast(t, dtype):
    """``t`` in ``dtype``, under the scope ``norm_cast``: the casts around a
    block's float32 LayerNorms on the training path."""
    with jax.named_scope("norm_cast"):
        return t.astype(dtype)


class GPTLayer(nn.Module):
    """Pre-LN decoder block: x + attn(LN(x)); x + mlp(LN(x))."""

    cfg: GPTConfig
    # (q, k, v, *, dropout_rate, dropout_seed) -> out; q,k,v (B, H, S, D).
    # Swap in a sequence-parallel attention (ring/ulysses) under shard_map.
    # NOTE: a custom attention_fn owns its whole kernel config —
    # cfg.probs_bf16 applies ONLY to the built-in default attention; pass
    # the flag inside your partial if you want it (a silent drop here
    # would confound A/B logs that trust the config).
    attention_fn: Callable = None

    @nn.compact
    def __call__(self, x, deterministic: bool = True, decode_state=None):
        cfg = self.cfg
        h, nh = cfg.hidden_size, cfg.num_heads
        d = h // nh
        dt = cfg.compute_dtype
        attention = self.attention_fn or functools.partial(
            _default_attention, probs_bf16=cfg.probs_bf16
        )
        b, s, _ = x.shape
        if decode_state is not None:
            return self._decode(x, decode_state)

        y = _cast(FusedLayerNorm(h, name="ln1")(_cast(x, jnp.float32)), dt)
        qkv = Dense(3 * h, dtype=dt, name="qkv")(y)
        with jax.named_scope("qkv_split"):
            q, k, v = jnp.split(qkv, 3, axis=-1)
        # scope ``heads_layout``: heads-major around the flash call, and back
        with jax.named_scope("heads_layout"):
            q, k, v = (t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)
                       for t in (q, k, v))
        needs_drop = cfg.attn_dropout_rate > 0 and not deterministic
        seed = None
        if needs_drop:
            seed = jax.random.randint(
                self.make_rng("dropout"), (), 0, jnp.iinfo(jnp.int32).max
            )
        # the kernels' own wrapping (the gradients' way back to heads-major)
        with jax.named_scope("attention"):
            attn = attention(
                q, k, v,
                dropout_rate=cfg.attn_dropout_rate if needs_drop else 0.0,
                dropout_seed=seed,
            )
        with jax.named_scope("heads_layout"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, h)
        attn = Dense(h, dtype=dt, name="proj")(attn)
        if not deterministic and cfg.dropout_rate > 0:
            attn = nn.Dropout(cfg.dropout_rate, deterministic=False)(attn)
        x = x + attn.astype(x.dtype)

        y = _cast(FusedLayerNorm(h, name="ln2")(_cast(x, jnp.float32)), dt)
        y = Dense(cfg.intermediate_size, dtype=dt, name="ffn_in")(y)
        y = jax.nn.gelu(y)
        y = Dense(h, dtype=dt, name="ffn_out")(y)
        if not deterministic and cfg.dropout_rate > 0:
            y = nn.Dropout(cfg.dropout_rate, deterministic=False)(y)
        return x + y.astype(x.dtype)

    def _decode(self, x, decode_state):
        """Cached-attention (serving) branch — ``apex_tpu.serve``.

        ``decode_state`` keys: ``positions`` (B, T) int32 global
        positions of the T new tokens; optional ``cache_k``/``cache_v``
        (B, H[, local], S, D) + ``cache_lengths`` (B,) — the
        already-written KV history (absent during prefill, where the
        block self-attends causally).  The PAGED alternative passes
        ``pool_k``/``pool_v`` (one layer's ``(num_pages, H[, local],
        page_len, D)`` pool slice) + ``page_table`` (B, n_pages) +
        ``cache_lengths`` instead, and the history is read through the
        table (``ops.attention.paged_cached_attention``) — same math,
        pool-resident storage.  Returns ``(x_out, k_new, v_new)``
        with k/v the new tokens' projections for the CALLER to scatter
        into the slot cache — the layer never copies the cache (the
        fused decode window carries it donated; see
        ops.attention.cached_attention's no-concat design note).

        Int8 pages: with ``pool_k_scale``/``pool_v_scale`` (one layer's
        ``(num_pages, H[, local], page_len)`` scale slices) present, the
        gather dequantizes the pool view AND the new tokens' K/V are
        quantized HERE — the in-block keys the new tokens attend to are
        the round-tripped ``int8 * scale`` values, bitwise what every
        later read of the cache will see, so a K-token verify block and
        K single-token steps stay token-identical under greedy.  The
        return is then ``(x_out, (k_q, k_scale), (v_q, v_scale))`` with
        int8 payloads for the caller to scatter as-is (re-quantizing a
        round-tripped vector is not guaranteed bit-stable, so the layer
        hands back the one canonical encoding).

        Always deterministic (inference).  Submodule names match the
        training branch exactly, so trained params bind unchanged.
        """
        cfg = self.cfg
        h, nh = cfg.hidden_size, cfg.num_heads
        d = h // nh
        dt = cfg.compute_dtype
        b, s, _ = x.shape
        positions = decode_state["positions"]

        y = FusedLayerNorm(h, name="ln1")(x.astype(jnp.float32)).astype(dt)
        qkv = Dense(3 * h, dtype=dt, name="qkv")(y)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        split = lambda t: t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)
        q, k, v = split(q), split(k), split(v)  # (B, nh, T, d)
        tp = cfg.decode_tp_axis
        if tp is not None:
            # local head group: the qkv GEMM is replicated (trivial at
            # decode shapes); only this shard's heads are kept, attended
            # against the head-sharded cache, and written back
            from apex_tpu.parallel.mesh import axis_size

            nh_loc = nh // axis_size(tp)
            h0 = jax.lax.axis_index(tp) * nh_loc
            take = lambda t: jax.lax.dynamic_slice_in_dim(t, h0, nh_loc, 1)
            q, k, v = take(q), take(k), take(v)
        quant = decode_state.get("pool_k_scale") is not None
        if quant:
            k, k_s = quantize_kv(k)
            v, v_s = quantize_kv(v)
            k_att = k.astype(jnp.float32) * k_s[..., None]
            v_att = v.astype(jnp.float32) * v_s[..., None]
        else:
            k_att, v_att = k, v
        if "page_table" in decode_state:
            # "pool_k" is either this layer's (num_pages, H, page_len, D)
            # slice (materializing path) or the FULL 5-D pool with
            # "pool_layer" static (fused kernel: the per-layer pick then
            # happens in the kernel's index map, never as an HBM slice
            # copy).  "paged_fused" is baked statically at trace time so
            # the program cache / lint census see one fixed route.
            attn = paged_cached_attention(
                q, k_att, v_att,
                positions=positions,
                pool_k=decode_state["pool_k"],
                pool_v=decode_state["pool_v"],
                page_table=decode_state["page_table"],
                cache_lengths=decode_state["cache_lengths"],
                pool_k_scale=decode_state.get("pool_k_scale"),
                pool_v_scale=decode_state.get("pool_v_scale"),
                layer=decode_state.get("pool_layer", 0),
                block_mask=decode_state.get("block_mask"),
                use_fused=decode_state.get("paged_fused", False),
            )
        else:
            attn = cached_attention(
                q, k_att, v_att,
                positions=positions,
                cache_k=decode_state.get("cache_k"),
                cache_v=decode_state.get("cache_v"),
                cache_lengths=decode_state.get("cache_lengths"),
                block_mask=decode_state.get("block_mask"),
            )
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, -1)
        if tp is not None:
            # reassemble the head axis: scatter the local head block to
            # full width and psum — ONE collective per layer per
            # dispatch-window body (the Megatron head-reassembly
            # minimum; payload equals the row-parallel alternative's)
            full = jnp.zeros((b, s, h), attn.dtype)
            attn = jax.lax.psum(
                jax.lax.dynamic_update_slice_in_dim(full, attn, h0 * d, 2),
                tp,
            )
        attn = Dense(h, dtype=dt, name="proj")(attn)
        x = x + attn.astype(x.dtype)

        y = FusedLayerNorm(h, name="ln2")(x.astype(jnp.float32)).astype(dt)
        y = Dense(cfg.intermediate_size, dtype=dt, name="ffn_in")(y)
        y = jax.nn.gelu(y)
        y = Dense(h, dtype=dt, name="ffn_out")(y)
        x = x + y.astype(x.dtype)
        if quant:
            return x, (k, k_s), (v, v_s)
        return x, k, v


def _pool_read_state(pool_k, pool_v, k_scale, v_scale, li, fused):
    """The per-layer pool-read keys of a paged ``decode_state``.

    Materializing path: per-layer slices, exactly the historical layout.
    Fused path: the FULL pools plus the static layer index — the fused
    kernel's BlockSpec index maps do the layer pick and the page gather
    in one DMA, so no per-layer slice copy ever exists as a kernel
    operand."""
    if fused:
        return {
            "pool_k": pool_k, "pool_v": pool_v,
            "pool_k_scale": k_scale, "pool_v_scale": v_scale,
            "pool_layer": li, "paged_fused": True,
        }
    return {
        "pool_k": pool_k[:, li], "pool_v": pool_v[:, li],
        "pool_k_scale": None if k_scale is None else k_scale[:, li],
        "pool_v_scale": None if v_scale is None else v_scale[:, li],
    }


def _paged_write(pool, scale_arr, li, phys, off, kv):
    """Scatter new-token K/V through the page table: ``kv`` is the
    layer's return — ``(B, H, T, D)`` floats, or ``((B, H, T, D) int8,
    (B, H, T) scales)`` in quantized mode — written at physical pages
    ``phys`` / in-page offsets ``off`` (both ``(B, T)``).  Advanced
    indices separated by the head slice put the broadcast dims FIRST
    (target ``(B, T, H, ...)``), hence the transposes."""
    if scale_arr is not None:
        kv, s = kv
        scale_arr = scale_arr.at[phys, li, :, off].set(
            s.transpose(0, 2, 1)
        )
    pool = pool.at[phys, li, :, off].set(
        kv.transpose(0, 2, 1, 3).astype(pool.dtype)
    )
    return pool, scale_arr


class GPTLM(nn.Module):
    """Decoder LM: embeddings + pre-LN stack + final LN + (tied) head.

    ``__call__(ids)`` returns (B, S, V) fp32 logits; with ``labels``
    (next-token ids, -100 = ignore) also returns the mean fused-xentropy
    loss, mirroring :class:`apex_tpu.models.bert.BertForMLM`.
    """

    cfg: GPTConfig

    def setup(self):
        cfg = self.cfg
        h = cfg.hidden_size
        self.wte = nn.Embed(cfg.vocab_size, h, dtype=jnp.float32)
        self.wpe = nn.Embed(cfg.max_position, h, dtype=jnp.float32)
        # per-block remat (identity for "none"); deterministic is
        # static_argnum 2 (self=0), so blocks are called positionally
        layer_cls = remat_module(GPTLayer, cfg.remat_policy,
                                 static_argnums=(2,))
        self.layers = [
            layer_cls(cfg, name=f"layer_{i}") for i in range(cfg.num_layers)
        ]
        self.ln_f = FusedLayerNorm(h)
        self.embed_drop = nn.Dropout(cfg.dropout_rate)
        if not cfg.tie_word_embeddings:
            self.head = Dense(cfg.vocab_size, dtype=jnp.float32,
                              use_bias=False)

    def __call__(self, input_ids, labels=None, deterministic: bool = True):
        cfg = self.cfg
        b, s = input_ids.shape
        # ``embed`` / ``lm_head`` / ``lm_loss``: the phases of the step
        # that no flax module names (blocks are ``layer_i``); a device
        # trace sums time by these scopes (docs/observability.md)
        with jax.named_scope("embed"):
            x = self.wte(input_ids) + self.wpe(jnp.arange(s)[None, :])
            if not deterministic and cfg.dropout_rate > 0:
                x = self.embed_drop(x, deterministic=False)
            x = x.astype(cfg.compute_dtype)
        for layer in self.layers:
            x = layer(x, deterministic)
        x = self.ln_f(x.astype(jnp.float32))
        logits = self._logits(x)
        if labels is None:
            return logits
        with jax.named_scope("lm_loss"):
            loss = masked_token_mean_loss(logits, labels, cfg.compute_dtype)
        return logits, loss

    def _logits(self, x):
        """(B, T, h) fp32 post-``ln_f`` hidden -> (B, T, V) fp32 logits.

        The vocab matmul is the single biggest GEMM in the model (>half
        of GPT-2 small's FLOPs): run it in compute_dtype (bf16 under
        O2/O3; O1's autocast recasts via the policy table; fp32 under
        O0) with fp32 accumulation.  The RETURNED logits stay fp32
        (eval/generation use); the training LOSS path deliberately
        re-rounds them to compute_dtype — the reference xentropy
        kernel's half_to_float design, trading ~0.4% per-logit rounding
        for halving the bytes of the model's largest activation (see
        PERF.md r3).  Shared by training ``__call__`` and the serve
        paths (``prefill``/``decode_step``) so decode logits are
        bitwise the training forward's.
        """
        cfg = self.cfg
        with jax.named_scope("lm_head"):
            if cfg.tie_word_embeddings:
                dt = cfg.compute_dtype
                logits = F.matmul(
                    x.astype(dt), self.wte.embedding.T.astype(dt),
                    preferred_element_type=jnp.float32,
                )
            else:
                logits = self.head(x)
            return logits.astype(jnp.float32)

    # -- serving paths (apex_tpu.serve) ---------------------------------

    def prefill(self, input_ids, lengths):
        """Prompt pass for the KV-cache decode engine.

        ``input_ids`` (B, P) right-padded prompts, ``lengths`` (B,)
        their valid lengths.  Returns ``(next_logits, k_stack,
        v_stack)``: fp32 (B, V) logits at each prompt's LAST valid
        position (the first generated token samples from these) and the
        per-layer K/V projections (B, L, H[, local], P, D) for the
        caller to scatter into cache slots (``serve.decode.GPTDecoder``
        owns the scatter — padding columns are written too, but the
        decode path overwrites position ``lengths`` before it is ever
        read).
        """
        cfg = self.cfg
        b, p = input_ids.shape
        positions = jnp.broadcast_to(
            jnp.arange(p, dtype=jnp.int32), (b, p)
        )
        x = self.wte(input_ids) + self.wpe(jnp.arange(p))
        x = x.astype(cfg.compute_dtype)
        ks, vs = [], []
        for layer in self.layers:
            x, k, v = layer(x, True, {"positions": positions})
            ks.append(k)
            vs.append(v)
        x = self.ln_f(x.astype(jnp.float32))
        last = jnp.clip(lengths - 1, 0, p - 1)
        x_last = x[jnp.arange(b), last]  # (B, h)
        logits = self._logits(x_last[:, None, :])[:, 0]
        return logits, jnp.stack(ks, axis=1), jnp.stack(vs, axis=1)

    def decode_step(self, token_ids, cache_k, cache_v, lengths,
                    n_layers=None):
        """ONE cached decode token for every slot.

        ``token_ids`` (B,) the tokens sampled last step, ``cache_k``/
        ``cache_v`` (B, L, H, S, D) slot caches, ``lengths`` (B,) valid
        prefix per slot.  Each layer attends its new token against the
        cache + itself (no cache concat/copy), then the new K/V is
        scattered at position ``lengths`` — a (B, H, D)-sized write per
        layer that XLA keeps in place under the fused window's donated
        carry.  Returns ``(logits, cache_k, cache_v)``; the CALLER
        advances ``lengths`` (gated by its active mask).  Writes are
        clamped to the last cache column so a slot at capacity degrades
        to garbage tokens (trimmed by the engine) instead of OOB.

        ``n_layers`` truncates the stack — the SHALLOW-EXIT draft head
        of the self-speculative decoder (serve.decode): the first
        ``n_layers`` blocks run (reading/writing only their own cache
        layers), then ``ln_f`` + the tied head produce approximate
        logits.  Draft-quality only — the full-depth verify forward
        overwrites the shallow K/V at the same positions before any
        accepted token depends on it.
        """
        cfg = self.cfg
        b = token_ids.shape[0]
        smax = cache_k.shape[3]
        pos = jnp.minimum(lengths, smax - 1).astype(jnp.int32)
        posq = jnp.minimum(pos, cfg.max_position - 1)
        x = self.wte(token_ids[:, None]) + self.wpe(posq[:, None])
        x = x.astype(cfg.compute_dtype)
        bidx = jnp.arange(b)
        for li, layer in enumerate(self.layers[:n_layers]):
            x, k, v = layer(
                x, True,
                {
                    "positions": posq[:, None],
                    "cache_k": cache_k[:, li],
                    "cache_v": cache_v[:, li],
                    "cache_lengths": pos,
                },
            )
            cache_k = cache_k.at[bidx, li, :, pos].set(
                k[:, :, 0].astype(cache_k.dtype)
            )
            cache_v = cache_v.at[bidx, li, :, pos].set(
                v[:, :, 0].astype(cache_v.dtype)
            )
        x = self.ln_f(x.astype(jnp.float32))
        logits = self._logits(x)[:, 0]
        return logits, cache_k, cache_v

    def decode_block(self, token_ids, cache_k, cache_v, lengths):
        """T cached decode tokens per slot in ONE forward — the
        VERIFY pass of self-speculative decoding (serve.decode).

        ``token_ids`` (B, T): the current token followed by T-1 draft
        tokens, occupying global positions ``lengths .. lengths+T-1``.
        Each layer attends the block against the cache (masked at
        ``lengths``) plus in-block causal self-attention, then scatters
        the block's K/V at those positions.  Returns ``(logits,
        cache_k, cache_v)`` with fp32 (B, T, V) logits at EVERY block
        position — position ``i``'s logits condition on the cache plus
        block tokens ``0..i`` exactly as T successive
        :meth:`decode_step` calls would, which is what makes greedy
        accept/rollback token-exact (the only difference is softmax
        reduction grouping over exactly-zero masked columns, the same
        regime chunked prefill already pins).  The caller advances
        ``lengths`` by the ACCEPTED count only; rejected positions hold
        garbage K/V that every reader masks and the next block
        overwrites.
        """
        cfg = self.cfg
        b, t = token_ids.shape
        smax = cache_k.shape[3]
        positions = lengths[:, None].astype(jnp.int32) + jnp.arange(
            t, dtype=jnp.int32
        )
        wpos = jnp.minimum(positions, smax - 1)
        posq = jnp.minimum(positions, cfg.max_position - 1)
        x = self.wte(token_ids) + self.wpe(posq)
        x = x.astype(cfg.compute_dtype)
        bidx = jnp.arange(b)
        ln = jnp.minimum(lengths, smax - 1).astype(jnp.int32)
        for li, layer in enumerate(self.layers):
            x, k, v = layer(
                x, True,
                {
                    "positions": posq,
                    "cache_k": cache_k[:, li],
                    "cache_v": cache_v[:, li],
                    "cache_lengths": ln,
                },
            )
            # k/v (B, H, T, D) -> (B, T, H, D): broadcast dims first
            cache_k = cache_k.at[bidx[:, None], li, :, wpos].set(
                k.transpose(0, 2, 1, 3).astype(cache_k.dtype)
            )
            cache_v = cache_v.at[bidx[:, None], li, :, wpos].set(
                v.transpose(0, 2, 1, 3).astype(cache_v.dtype)
            )
        x = self.ln_f(x.astype(jnp.float32))
        logits = self._logits(x)
        return logits, cache_k, cache_v

    # -- paged serving paths (apex_tpu.serve paged KV) -------------------

    def paged_prefill_chunk(self, input_ids, base, valid, pool_k, pool_v,
                            page_tables, k_scale=None, v_scale=None):
        """One CHUNK of a chunked paged prefill.

        ``input_ids`` (B, C) right-padded chunk tokens starting at
        absolute positions ``base`` (B,) with ``valid`` (B,) real tokens
        per row; ``pool_k``/``pool_v`` the global page pools
        ``(num_pages, L, H, page_len, D)``; ``page_tables`` (B, n_pages)
        each row's logical->physical map.  Each layer attends the chunk
        against the already-written history (read through the table,
        masked at ``base``) plus in-chunk causal self-attention, then
        scatters the chunk's K/V through the table.  Returns ``(logits,
        pool_k, pool_v)`` with fp32 logits at each row's LAST valid
        chunk position (the final chunk's logits seed sampling).

        Padding columns scatter garbage like the contiguous prefill —
        always at positions >= ``base + valid`` where every reader masks
        them, and always through table entries the host allocator owns
        for this row (or the trash page beyond them), so no other
        request's pages can be touched.  The host must have made
        ``[base, base+valid)`` exclusively writable first
        (``PagePool.ensure_writable`` — the copy-on-write gate).

        With ``k_scale``/``v_scale`` (int8 pools) the chunk's K/V is
        quantized per token/head at write time and the return grows to
        ``(logits, pool_k, pool_v, k_scale, v_scale)``.
        """
        cfg = self.cfg
        b, c = input_ids.shape
        pl = pool_k.shape[3]
        smax = page_tables.shape[1] * pl
        positions = base[:, None].astype(jnp.int32) + jnp.arange(
            c, dtype=jnp.int32
        )
        posq = jnp.minimum(positions, cfg.max_position - 1)
        x = self.wte(input_ids) + self.wpe(posq)
        x = x.astype(cfg.compute_dtype)
        wpos = jnp.minimum(positions, smax - 1)
        bidx = jnp.arange(b)
        phys = page_tables[bidx[:, None], wpos // pl]  # (B, C)
        off = wpos % pl
        lens = base.astype(jnp.int32)
        for li, layer in enumerate(self.layers):
            x, k, v = layer(
                x, True,
                {
                    "positions": posq,
                    "pool_k": pool_k[:, li],
                    "pool_v": pool_v[:, li],
                    "page_table": page_tables,
                    "cache_lengths": lens,
                    "pool_k_scale": None if k_scale is None
                    else k_scale[:, li],
                    "pool_v_scale": None if v_scale is None
                    else v_scale[:, li],
                },
            )
            pool_k, k_scale = _paged_write(pool_k, k_scale, li, phys,
                                           off, k)
            pool_v, v_scale = _paged_write(pool_v, v_scale, li, phys,
                                           off, v)
        x = self.ln_f(x.astype(jnp.float32))
        last = jnp.clip(valid - 1, 0, c - 1)
        x_last = x[bidx, last]
        logits = self._logits(x_last[:, None, :])[:, 0]
        if k_scale is not None:
            return logits, pool_k, pool_v, k_scale, v_scale
        return logits, pool_k, pool_v

    def paged_decode_step(self, token_ids, pool_k, pool_v, page_tables,
                          lengths, k_scale=None, v_scale=None,
                          n_layers=None, fused=False):
        """:meth:`decode_step` over the paged pool: ONE cached decode
        token per slot, K/V history read through ``page_tables`` and the
        new token's K/V scattered at physical ``(table[pos // page_len],
        pos % page_len)``.  Free slots' table rows point at the trash
        page, so their masked garbage writes corrupt nothing.  The
        attention math delegates to the same fp32-accumulation
        :func:`~apex_tpu.ops.attention.cached_attention` core over the
        gathered view, so tokens are identical to the contiguous path.

        ``k_scale``/``v_scale`` select the int8 write/read paths (the
        return grows their updated arrays); ``n_layers`` is the
        shallow-exit draft head, as in :meth:`decode_step`.
        """
        cfg = self.cfg
        b = token_ids.shape[0]
        pl = pool_k.shape[3]
        smax = page_tables.shape[1] * pl
        pos = jnp.minimum(lengths, smax - 1).astype(jnp.int32)
        posq = jnp.minimum(pos, cfg.max_position - 1)
        x = self.wte(token_ids[:, None]) + self.wpe(posq[:, None])
        x = x.astype(cfg.compute_dtype)
        bidx = jnp.arange(b)
        phys = page_tables[bidx, pos // pl]  # (B,)
        off = pos % pl
        for li, layer in enumerate(self.layers[:n_layers]):
            x, k, v = layer(
                x, True,
                dict(
                    _pool_read_state(pool_k, pool_v, k_scale, v_scale,
                                     li, fused),
                    positions=posq[:, None],
                    page_table=page_tables,
                    cache_lengths=pos,
                ),
            )
            pool_k, k_scale = _paged_write(
                pool_k, k_scale, li, phys[:, None], off[:, None], k
            )
            pool_v, v_scale = _paged_write(
                pool_v, v_scale, li, phys[:, None], off[:, None], v
            )
        x = self.ln_f(x.astype(jnp.float32))
        logits = self._logits(x)[:, 0]
        if k_scale is not None:
            return logits, pool_k, pool_v, k_scale, v_scale
        return logits, pool_k, pool_v

    def paged_decode_block(self, token_ids, pool_k, pool_v, page_tables,
                           lengths, k_scale=None, v_scale=None,
                           fused=False):
        """:meth:`decode_block` over the paged pool — the verify pass of
        self-speculative decoding with pool-resident (optionally int8)
        storage.  ``token_ids`` (B, T) occupy positions ``lengths ..
        lengths+T-1``; the host must have made that whole range
        exclusively writable (``PagePool.ensure_writable``) before the
        window that calls this.  Returns fp32 (B, T, V) logits at every
        block position plus the updated pools (and scales when int8).
        """
        cfg = self.cfg
        b, t = token_ids.shape
        pl = pool_k.shape[3]
        smax = page_tables.shape[1] * pl
        positions = lengths[:, None].astype(jnp.int32) + jnp.arange(
            t, dtype=jnp.int32
        )
        wpos = jnp.minimum(positions, smax - 1)
        posq = jnp.minimum(positions, cfg.max_position - 1)
        x = self.wte(token_ids) + self.wpe(posq)
        x = x.astype(cfg.compute_dtype)
        bidx = jnp.arange(b)
        phys = page_tables[bidx[:, None], wpos // pl]  # (B, T)
        off = wpos % pl
        ln = jnp.minimum(lengths, smax - 1).astype(jnp.int32)
        for li, layer in enumerate(self.layers):
            x, k, v = layer(
                x, True,
                dict(
                    _pool_read_state(pool_k, pool_v, k_scale, v_scale,
                                     li, fused),
                    positions=posq,
                    page_table=page_tables,
                    cache_lengths=ln,
                ),
            )
            pool_k, k_scale = _paged_write(pool_k, k_scale, li, phys,
                                           off, k)
            pool_v, v_scale = _paged_write(pool_v, v_scale, li, phys,
                                           off, v)
        x = self.ln_f(x.astype(jnp.float32))
        logits = self._logits(x)
        if k_scale is not None:
            return logits, pool_k, pool_v, k_scale, v_scale
        return logits, pool_k, pool_v

    def paged_decode_tree_block(self, token_ids, pool_k, pool_v,
                                page_tables, lengths, k_scale=None,
                                v_scale=None, width=2, depth=1,
                                fused=False):
        """Tree-speculation verify pass: ``width`` draft branches of
        ``depth`` tokens each, verified in ONE batched block forward.

        ``token_ids`` (B, T) with ``T = 1 + width * depth`` laid out
        ``[committed_token, branch0[0..depth-1], ...,
        branch{width-1}[0..depth-1]]``.  Every branch continues the same
        committed token, so branch r's token j sits at LOGICAL position
        ``lengths + 1 + j`` regardless of r — sibling branches share
        positions, and a static (T, T) branch mask keeps each query's
        in-block view to its own branch plus the shared root (cache
        history reads are position-masked as usual and see no
        in-flight branch).  WRITE slots are sequential ``lengths ..
        lengths+T-1`` (each node parks its K/V in its own page slot; the
        caller compacts the winning branch into the canonical
        ``lengths+1 ..`` slots after acceptance — serve/decode.py's
        ``_tree_compact``), so the host must have made the whole T-slot
        range writable.  Returns fp32 (B, T, V) logits per node plus the
        updated pools (and scales when int8).
        """
        cfg = self.cfg
        b, t = token_ids.shape
        if t != 1 + width * depth:
            raise ValueError(
                f"tree block of width {width} depth {depth} wants "
                f"T={1 + width * depth}, got {t}")
        pl = pool_k.shape[3]
        smax = page_tables.shape[1] * pl
        # static per-node depth and branch ids for the [root, b0..,
        # b{W-1}..] layout
        dvec = [0] + [j + 1 for _ in range(width) for j in range(depth)]
        bvec = [-1] + [r for r in range(width) for _ in range(depth)]
        depths = jnp.asarray(dvec, jnp.int32)
        block_mask = jnp.asarray(
            [[bvec[kk] < 0 or bvec[kk] == bvec[qq] for kk in range(t)]
             for qq in range(t)],
            bool,
        )
        positions = lengths[:, None].astype(jnp.int32) + depths[None, :]
        posq = jnp.minimum(positions, cfg.max_position - 1)
        x = self.wte(token_ids) + self.wpe(posq)
        x = x.astype(cfg.compute_dtype)
        # sequential PHYSICAL parking slots, decoupled from the logical
        # positions above
        wslot = lengths[:, None].astype(jnp.int32) + jnp.arange(
            t, dtype=jnp.int32
        )
        wpos = jnp.minimum(wslot, smax - 1)
        bidx = jnp.arange(b)
        phys = page_tables[bidx[:, None], wpos // pl]  # (B, T)
        off = wpos % pl
        ln = jnp.minimum(lengths, smax - 1).astype(jnp.int32)
        for li, layer in enumerate(self.layers):
            x, k, v = layer(
                x, True,
                dict(
                    _pool_read_state(pool_k, pool_v, k_scale, v_scale,
                                     li, fused),
                    positions=posq,
                    page_table=page_tables,
                    cache_lengths=ln,
                    block_mask=block_mask,
                ),
            )
            pool_k, k_scale = _paged_write(pool_k, k_scale, li, phys,
                                           off, k)
            pool_v, v_scale = _paged_write(pool_v, v_scale, li, phys,
                                           off, v)
        x = self.ln_f(x.astype(jnp.float32))
        logits = self._logits(x)
        if k_scale is not None:
            return logits, pool_k, pool_v, k_scale, v_scale
        return logits, pool_k, pool_v
