"""Arcee ``afmoe`` decoder LM (Trinity family) on the training path.

The second decoder block of the zoo's three (``models/gpt.py`` is the first,
``models/qwen3_next.py`` the third): four
RMSNorms a block in sandwich position, grouped-query flash attention with
an output gate and normed queries and keys, rotary positions on the
sliding-window layers only (the full-attention layers carry no position
signal), gated SiLU MLPs, and — past the leading dense layers — sigmoid
routed experts beside a shared one (``parallel/moe.py::ExpertShardMLP``).
Layers of unequal kind are chosen by ``layer_types``.

Per block (no biases anywhere)::

    h += RMS(Attn(RMS(h)));  h += RMS(FF(RMS(h)))
    Attn(x) = W_o (softmax(q k^T / sqrt(D), causal [, i - j < window]) v * sigmoid(W_g x))

``h = E[ids] * sqrt(hidden)`` (``mup_enabled``), ``logits = W_head RMS(h)``,
the head untied.  Called as :class:`apex_tpu.models.gpt.GPTLM` is:
``model.apply({"params": p}, ids, labels=labels, deterministic=...)`` ->
``(logits, loss)``.

Expert parallelism enters as ``experts_held``: this model instance holds
that range of each expert layer's routed experts, routes over all
``num_experts`` and computes its own experts' part (one chip's share before
the exchange; the exchange itself is not built yet — ROADMAP M2).
``vocab_size`` is likewise whatever slice of the vocabulary is held.

Serving methods (``prefill``, ``decode_*``) are not part of this model yet.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp.layers import Dense
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.softmax_xentropy import softmax_cross_entropy
from apex_tpu.parallel.moe import ExpertShardMLP, SwiGLU
from apex_tpu.remat import remat_module

__all__ = ["AfmoeConfig", "AfmoeLayer", "AfmoeLM", "RMSNorm", "rotary"]

WINDOW, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class AfmoeConfig:
    vocab_size: int = 25088           # the slice held, padded to 128
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (WINDOW, WINDOW, WINDOW, WINDOW, FULL)
    num_dense_layers: int = 1
    num_heads: int = 32
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    intermediate_size: int = 6144     # the dense layers' MLP
    moe_intermediate_size: int = 1024  # one expert
    num_experts: int = 128            # routed over
    experts_held: Tuple[int, int] = (0, 16)
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    route_norm: bool = True
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    mup_enabled: bool = True
    initializer_range: float = 0.02
    # activation rematerialization per block (apex_tpu.remat): full_block
    # keeps a block's input and the flash kernel's output and lse
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @staticmethod
    def tiny(**kw) -> "AfmoeConfig":
        """For tests: every mechanism at toy widths."""
        base = dict(
            vocab_size=256, hidden_size=128,
            layer_types=(WINDOW, WINDOW, FULL), num_dense_layers=1,
            num_heads=4, num_kv_heads=2, head_dim=64, sliding_window=48,
            intermediate_size=256, moe_intermediate_size=128,
            num_experts=16, experts_held=(0, 4), num_experts_per_tok=4)
        base.update(kw)
        return AfmoeConfig(**base)


class RMSNorm(nn.Module):
    """``x / sqrt(mean(x^2) + eps) * scale`` over the last axis, in
    float32 (XLA's fusion: it merges with the residual add and the casts
    around it; no Pallas kernel)."""

    eps: float = 1e-5
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones_init(),
                           (x.shape[-1],), jnp.float32)
        x32 = x.astype(jnp.float32)
        inv = jax.lax.rsqrt(
            jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
        return (x32 * inv * scale.astype(jnp.float32)).astype(self.dtype)


def rotary(x, theta: float):
    """Rotate ``x`` (..., seq, D) by position over the whole head, the two
    halves paired (``rotate_half``); float32 inside, ``x``'s dtype out."""
    s, d = x.shape[-2], x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    x32 = x.astype(jnp.float32)
    half = jnp.concatenate([-x32[..., d // 2:], x32[..., :d // 2]], axis=-1)
    return (x32 * cos + half * sin).astype(x.dtype)


class AfmoeLayer(nn.Module):
    """One block; ``index`` picks its attention (``cfg.layer_types``) and
    its feed-forward (dense below ``cfg.num_dense_layers``)."""

    cfg: AfmoeConfig
    index: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic           # no dropout in this family
        cfg = self.cfg
        b, s, h = x.shape
        hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, name=name)
        windowed = cfg.layer_types[self.index] == WINDOW

        y = norm("input_norm")(x)
        # one projection: queries, keys, values and the output gate
        qkvg = Dense((2 * hq + 2 * hk) * hd, use_bias=False, dtype=dt,
                     kernel_init=init, name="qkvg")(y)
        q, k, v, g = jnp.split(
            qkvg, [hq * hd, (hq + hk) * hd, (hq + 2 * hk) * hd], axis=-1)
        heads = lambda t, n: t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)
        q = norm("q_norm")(heads(q, hq))
        k = norm("k_norm")(heads(k, hk))
        if windowed:
            q, k = rotary(q, cfg.rope_theta), rotary(k, cfg.rope_theta)
        with jax.named_scope("attn_window" if windowed else "attn_full"):
            attn = flash_attention(
                q, k, heads(v, hk), causal=True,
                window=cfg.sliding_window if windowed else None)
        attn = attn.transpose(0, 2, 1, 3).reshape(b, s, hq * hd)
        attn = attn * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dt)
        attn = Dense(h, use_bias=False, dtype=dt, kernel_init=init,
                     name="o_proj")(attn)
        x = x + norm("post_attn_norm")(attn)

        y = norm("pre_mlp_norm")(x)
        if self.index < cfg.num_dense_layers:
            ff = SwiGLU(cfg.intermediate_size, dt, init, name="mlp")(y)
        else:
            ff = ExpertShardMLP(
                num_experts=cfg.num_experts, experts_held=cfg.experts_held,
                d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_tok,
                shared_d_ff=(cfg.moe_intermediate_size
                             * cfg.num_shared_experts),
                route_norm=cfg.route_norm, route_scale=cfg.route_scale,
                compute_dtype=dt, kernel_init=init, name="moe",
            )(y.reshape(b * s, h)).reshape(b, s, h)
        return x + norm("post_mlp_norm")(ff)


class AfmoeLM(nn.Module):
    """Embedding, the blocks ``layer_<i>``, a final RMSNorm and the untied
    head.  ``__call__(ids)`` returns (B, S, V) float32 logits; with
    ``labels`` (-100: not predicted) also the token-mean fused-xentropy
    loss, as :class:`apex_tpu.models.gpt.GPTLM` does."""

    cfg: AfmoeConfig

    def setup(self):
        cfg = self.cfg
        for kind in cfg.layer_types:
            if kind not in (WINDOW, FULL):
                raise ValueError(f"no layer type {kind!r}")
        init = nn.initializers.normal(cfg.initializer_range)
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                              embedding_init=init, dtype=jnp.float32)
        # deterministic is static_argnum 2 (self=0): called positionally
        layer_cls = remat_module(AfmoeLayer, cfg.remat_policy,
                                 static_argnums=(2,))
        self.layers = [layer_cls(cfg, i, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.norm_f = RMSNorm(cfg.rms_norm_eps, cfg.compute_dtype)
        self.head = Dense(cfg.vocab_size, use_bias=False,
                          dtype=cfg.compute_dtype, kernel_init=init)

    def __call__(self, input_ids, labels=None, deterministic: bool = True):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self.embed(input_ids)
            if cfg.mup_enabled:
                x = x * (cfg.hidden_size ** 0.5)
            x = x.astype(cfg.compute_dtype)
        for layer in self.layers:
            x = layer(x, deterministic)
        x = self.norm_f(x)
        with jax.named_scope("lm_head"):
            logits = self.head(x).astype(jnp.float32)
        if labels is None:
            return logits
        with jax.named_scope("lm_loss"):
            valid = labels >= 0
            safe = jnp.where(valid, labels, 0)
            # compute-dtype logits into the fused loss, as GPTLM
            per_tok = softmax_cross_entropy(
                logits.astype(cfg.compute_dtype), safe)
            n = jnp.maximum(jnp.sum(valid), 1)
            loss = jnp.sum(jnp.where(valid, per_tok, 0.0)) / n
        return logits, loss
