"""Granite 4.0-H decoder LM (IBM's ``granitemoehybrid`` family, dense) on the
training path.

The block: two RMSNorms in pre-norm position and TWO KINDS OF MIXER under one
residual scheme, chosen by ``layer_types`` — a MAMBA-2 STATE-SPACE LAYER
(``mamba``: nine layers of ten at the published sizes) and grouped-query
flash attention WITHOUT positions at a published scale (``attention``) — a
dense SwiGLU in every layer, and the family's four multipliers: on the
embedding, on each branch before it joins the residual stream, on the
attention scores (in place of ``D ** -0.5``) and under the logits.  The head
is TIED to the embedding.  The family's expert branch is absent here
(``num_local_experts`` 0), not empty: this module builds no router.

No bias anywhere except the convolution's.  ``RMS(x) = x * rsqrt(mean(x^2) +
eps) * w`` in float32, ``w`` initialised 1.  ``d`` hidden, ``H`` heads of
``P`` channels (``d_in = H P``), ``N`` the state size, ``G`` groups (1), ``K``
taps::

    h_0 = E[ids] * embedding_multiplier
    block i:   y = RMS_in(h)
      mamba:   [z | xBC | dt] = y W_in                    # d_in | d_in + 2 G N | H
               xBC = silu(conv_K(xBC) + b_conv)           # depthwise, causal, zeros before the row's start
               x, B, C = split(xBC, [d_in, G N, G N])     # x as (T, H, P); B, C shared by a group's heads
               dt = softplus(dt + dt_bias)                # (T, H) float32, no clamp
               a_t = dt_t * (-exp(A_log))                 # the log-decay, <= 0
               S_t = exp(a_t) S_{t-1} + dt_t * x_t B_t^T  # per head, (P, N) float32, S_0 = 0
               o_t = S_t C_t + D * x_t
               m = RMS_gate(o * silu(z)) W_out            # the norm over all d_in channels (one group)
      attention: q, k, v = y W_q, y W_k, y W_v            # H_q, H_kv, H_kv heads; NO positions
               m = softmax(q k^T * attention_multiplier, causal) v W_o
      h = h + residual_multiplier * m
      u = RMS_post(h);  [g | v] = u W_1                   # gate first
      h = h + residual_multiplier * W_2 (silu(g) * v)
    logits = (RMS_f(h) E^T) / logits_scaling

The scan is ``ops/ssd.py::ssd_scan``: on the TPU two kernels over chunks of
``mamba_chunk_size`` tokens that keep every (chunk, chunk) tensor in VMEM,
float32 states carried between chunks; ``dt``, the decays and the states
float32, ``x``, ``B``, ``C`` and ``o`` in the compute dtype at the kernels'
edge.  The convolution with its bias and SiLU is
``ops/ssd.py::split_conv_xbc``: on the TPU the kernel pair ``apex_conv1d_*``
reads x, B and C out of ``in_proj``'s output where they lie and hands them to
the scan as the three arrays its kernels take, z and dt cut out beside; its
backward writes ``in_proj``'s whole gradient.  The gated norm is XLA's float32
fusion like every ``RMSNorm``.

Left out: nothing of a step (there is no router and no auxiliary loss).  No
step-size clamp (the published configuration gives no ``time_step_limit``).
``A_log`` starts at ``log U[1, 16]``, ``dt_bias`` at ``softplus^-1`` of a
step size log-uniform in [1e-3, 1e-1] (floored at 1e-4), ``D`` at 1, the
convolution's bias at 0 — the public Mamba-2 code's initial values — every
matrix and the taps at N(0, ``initializer_range``).

The shell and how it is called: ``models/decoder.py`` — ``vocab_size`` is
whatever slice of the vocabulary is held: rows of the embedding, and so
columns of the tied head.  Scopes ``ssm_proj`` (``W_in``), ``ssm_conv`` (the
convolution and the cut of the projection's output into its five parts),
``ssm_scan`` (softplus, decays, the scan), ``ssm_out`` (gate, norm,
``W_out``), ``attn_full`` (the flash call), ``dense_ffn``, ``embed``,
``lm_head``, ``lm_loss``.  Under ``remat_policy`` ``full_block`` the attention
layer keeps its input and the flash kernel's output and ``lse``, a mamba layer
its input alone: the projection, the convolution and the scan's forward run
again in the backward pass.  Serving methods are not part of this model yet:
a state-space layer's (H, P, N) state and its convolution's last ``K - 1``
inputs are a second kind of per-sequence state beside K/V pages (ROADMAP M6).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.decoder import (DecoderLM, RMSNorm, causal_attention,
                                     linear, merge_heads, split_heads)
from apex_tpu.ops.ssd import split_conv_xbc, ssd_scan
from apex_tpu.parallel.moe import SwiGLU

__all__ = ["GraniteHybridConfig", "GraniteHybridLayer", "GraniteHybridLM",
           "Mamba2Mixer"]

MAMBA, ATTENTION = "mamba", "attention"


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig:
    vocab_size: int = 12544           # the slice held (a multiple of 128)
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (MAMBA,) * 5 + (ATTENTION,) + (MAMBA,) * 4
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    num_heads: int = 32
    num_kv_heads: int = 8
    intermediate_size: int = 8192
    embedding_multiplier: float = 12.0
    attention_multiplier: float = 0.015625
    residual_multiplier: float = 0.22
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    # activation rematerialization per block (apex_tpu.remat): full_block
    # keeps a block's input and, in the attention layer, the flash kernel's
    # output and lse
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_heads

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @staticmethod
    def tiny(**kw) -> "GraniteHybridConfig":
        """For tests: every mechanism at toy widths — two mamba layers
        around an attention layer with four query heads a key/value head,
        heads of 64 channels side by side, several chunks a row."""
        base = dict(
            vocab_size=256, hidden_size=128,
            layer_types=(MAMBA, ATTENTION, MAMBA), mamba_n_heads=4,
            mamba_d_head=64, mamba_d_state=32, mamba_chunk_size=32,
            num_heads=8, num_kv_heads=2, intermediate_size=256)
        base.update(kw)
        return GraniteHybridConfig(**base)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A ~ U[1, 16]``."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """``softplus^-1(dt)`` with ``dt`` log-uniform in [1e-3, 1e-1], floored
    at 1e-4."""
    dt = jnp.exp(jax.random.uniform(key, shape, dtype, math.log(1e-3),
                                    math.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)
    return dt + jnp.log(-jnp.expm1(-dt))


class Mamba2Mixer(nn.Module):
    """The mamba layers' mixer.  Parameters ``in_proj`` (d, 2 d_in + 2 G N +
    H) laid out ``[z | x | B | C | dt]``, ``conv_taps`` (d_in + 2 G N, K) — a
    channel's tap ``j`` multiplies the input ``K - 1 - j`` tokens back — and
    ``conv_bias``, ``dt_bias``, ``A_log``, ``D`` (H,) each, the gated norm's
    ``norm/scale`` (d_in,) and ``out_proj`` (d_in, d)."""

    cfg: GraniteHybridConfig

    @nn.compact
    def __call__(self, y):
        cfg = self.cfg
        b, s, _ = y.shape
        h, p = cfg.mamba_n_heads, cfg.mamba_d_head
        g, n = cfg.mamba_n_groups, cfg.mamba_d_state
        d_in, conv = h * p, h * p + 2 * g * n
        f32 = lambda t: t.astype(jnp.float32)
        init = nn.initializers.normal(cfg.initializer_range)
        with jax.named_scope("ssm_proj"):
            zxbcdt = linear(cfg, d_in + conv + h, "in_proj")(y)
        with jax.named_scope("ssm_conv"):
            taps = self.param("conv_taps", init, (conv, cfg.mamba_d_conv),
                              jnp.float32)
            bias = self.param("conv_bias", nn.initializers.zeros_init(),
                              (conv,), jnp.float32)
            z, x, bm, cm, dt = split_conv_xbc(zxbcdt, taps, bias,
                                              d_inner=d_in, d_bc=g * n)
        with jax.named_scope("ssm_scan"):
            dt_bias = self.param("dt_bias", _dt_bias_init, (h,), jnp.float32)
            a_log = self.param("A_log", _a_log_init, (h,), jnp.float32)
            skip = self.param("D", nn.initializers.ones_init(), (h,),
                              jnp.float32)
            o = ssd_scan(x.reshape(b, s, h, p),
                         jax.nn.softplus(f32(dt) + f32(dt_bias)),
                         -jnp.exp(f32(a_log)), bm.reshape(b, s, g, n),
                         cm.reshape(b, s, g, n), f32(skip),
                         chunk=cfg.mamba_chunk_size)
        with jax.named_scope("ssm_out"):
            gated = f32(o.reshape(b, s, d_in)) * jax.nn.silu(f32(z))
            normed = RMSNorm(cfg.rms_norm_eps, cfg.compute_dtype,
                             name="norm")(gated)
            return linear(cfg, y.shape[-1], "out_proj")(normed)


class GraniteHybridLayer(nn.Module):
    """One block; ``index`` picks its mixer (``cfg.layer_types``)."""

    cfg: GraniteHybridConfig
    index: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic           # no dropout in this family
        cfg = self.cfg
        d = x.shape[-1]
        hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, name=name)

        y = norm("input_norm")(x)
        if cfg.layer_types[self.index] == MAMBA:
            mixed = Mamba2Mixer(cfg, name="mamba")(y)
        else:
            qkv = linear(cfg, (hq + 2 * hk) * hd, "qkv")(y)
            with jax.named_scope("qkv_split"):
                q, k, v = jnp.split(qkv, [hq * hd, (hq + hk) * hd], axis=-1)
            attn = causal_attention(
                split_heads(q, hq, hd), split_heads(k, hk, hd),
                split_heads(v, hk, hd), scale=cfg.attention_multiplier)
            mixed = linear(cfg, d, "o_proj")(merge_heads(attn))
        x = x + cfg.residual_multiplier * mixed

        u = norm("post_norm")(x)
        with jax.named_scope("dense_ffn"):
            return x + cfg.residual_multiplier * SwiGLU(
                cfg.intermediate_size, dt, init, name="mlp")(u)


class GraniteHybridLM(DecoderLM):
    """The shell with the head TIED to the embedding, the embedding's rows
    multiplied by ``embedding_multiplier`` and the logits divided by
    ``logits_scaling``."""

    cfg: GraniteHybridConfig
    layer_cls = GraniteHybridLayer
    tied_head = True

    @staticmethod
    def validate(cfg):
        for kind in cfg.layer_types:
            if kind not in (MAMBA, ATTENTION):
                raise ValueError(f"no layer type {kind!r}")
        if cfg.mamba_n_heads % cfg.mamba_n_groups:
            raise ValueError(f"{cfg.mamba_n_heads} heads do not divide into "
                             f"{cfg.mamba_n_groups} groups")
        if cfg.num_heads % cfg.num_kv_heads \
                or cfg.hidden_size % cfg.num_heads:
            raise ValueError(f"{cfg.num_heads} query heads on "
                             f"{cfg.num_kv_heads} key/value heads at hidden "
                             f"size {cfg.hidden_size}")

    @staticmethod
    def embed_scale(cfg):
        return cfg.embedding_multiplier

    @staticmethod
    def logits_divisor(cfg):
        return cfg.logits_scaling
