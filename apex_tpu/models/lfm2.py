"""LFM2 decoder LM (Liquid AI's ``lfm2_moe`` family) on the training path.

The block: two RMSNorms in pre-norm position and TWO KINDS OF MIXER under one
residual scheme, chosen by ``layer_types`` — a GATED SHORT CONVOLUTION
(``conv``: no attention, no state beyond the last ``K - 1`` inputs; three
layers of four at the published sizes) and grouped-query flash attention with
per-head RMS norms on q and k and rotary positions (``full_attention``).  The
feed-forward is a dense SwiGLU in the leading ``num_dense_layers`` and, past
them, sigmoid-routed experts under a selection bias with NO shared expert
(``parallel/moe.py::ExpertShardMLP``).  The head is TIED to the embedding.

Block ``i`` on ``h`` (T, d); no bias anywhere; RMSNorm ``x * rsqrt(mean(x^2)
+ eps) * w`` in float32::

    y = RMS_op(h)
    conv layer:       [B | C | X] = y W_in                   # (T, 3 d), thirds in this order
                      u = B * X
                      c_t = sum_{j<K} w[:, j] * u_{t-(K-1)+j}   # depthwise, zeros before the row's start
                      a = (C * c) W_out
    attention layer:  q, k, v = y W_q, y W_k, y W_v          # H, H_kv, H_kv heads of D
                      q, k = RMS_q(q), RMS_k(k)              # per head over its D dims
                      q, k = rotary(q), rotary(k)            # whole head, rotate_half
                      a = softmax(q k^T / sqrt(D), causal) v W_o
    h = h + a
    z = RMS_ffn(h)
    i < num_dense_layers:  h = h + W_2 (silu(W_1 z) * W_3 z)
    else:  s = sigmoid(z W_r) over ALL experts, float32;  e = top_k(s + b)
           g = s_e / (sum s_e + 1e-20) * route_scale
           h = h + sum_j g_j W_2[e_j] (silu(W_1[e_j] z) * W_3[e_j] z)
    logits = E^T RMS_final(h)

The gated convolution is ``ops/gated_conv.py::gated_short_conv``: on the TPU
one kernel pass over ``W_in``'s output where it lies, forward and backward
(float32 taps and gates, one rounding at the output).  ``b`` enters the
selection only and takes no gradient; its loss-free balancing update is no
part of a step made of gradients (as ``models/deepseek_v3.py``).

Left out: any router auxiliary loss (the step is the plain causal-LM loss),
the selection bias's update, rotary scaling (the declared positions are
native).

The shell, how it is called and how expert parallelism enters
(``experts_held``, a sliced ``vocab_size``): ``models/decoder.py`` — the slice
is of the embedding's rows, and so of the tied head's columns.  Scopes
``conv_proj`` (``W_in``), ``conv_mix`` (the gated convolution alone),
``conv_out`` (``W_out``), ``attn_full`` (the flash call), ``dense_ffn``, the
three ``moe_*``, ``embed``, ``lm_head``, ``lm_loss``.  Under ``remat_policy``
``full_block`` an attention layer keeps its input and the flash kernel's
output and ``lse``, a convolution layer its input alone: the gated
convolution runs again in the backward pass.  Serving methods are not part
of this model yet: a convolution layer's last ``K - 1`` inputs are a second
kind of per-sequence state beside K/V pages (ROADMAP M6).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.decoder import (DecoderLM, RMSNorm, causal_attention,
                                     linear, merge_heads, rotary, split_heads)
from apex_tpu.ops.gated_conv import gated_short_conv
from apex_tpu.parallel.moe import ExpertShardMLP, SwiGLU

__all__ = ["Lfm2Config", "Lfm2Layer", "Lfm2LM", "ShortConv"]

CONV, FULL = "conv", "full_attention"


@dataclasses.dataclass(frozen=True)
class Lfm2Config:
    vocab_size: int = 8192            # the slice held (a multiple of 128)
    hidden_size: int = 2048
    layer_types: Tuple[str, ...] = (CONV, FULL, CONV, CONV, CONV)
    num_dense_layers: int = 1
    conv_L_cache: int = 3             # the convolution's taps, K
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 64
    rope_theta: float = 1e6
    intermediate_size: int = 11776    # the dense layers' MLP
    moe_intermediate_size: int = 1536  # one expert
    num_experts: int = 64             # routed over
    experts_held: Tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 4
    route_norm: bool = True
    route_scale: float = 1.0
    norm_eps: float = 1e-5
    initializer_range: float = 0.02
    # activation rematerialization per block (apex_tpu.remat): full_block
    # keeps a block's input and, in an attention layer, the flash kernel's
    # output and lse
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @staticmethod
    def tiny(**kw) -> "Lfm2Config":
        """For tests: every mechanism at toy widths — a dense convolution
        layer, an attention layer with four query heads a key/value head, an
        expert convolution layer, a strict subset of the experts held."""
        base = dict(
            vocab_size=256, hidden_size=128, layer_types=(CONV, FULL, CONV),
            num_dense_layers=1, num_heads=8, num_kv_heads=2, head_dim=64,
            intermediate_size=256, moe_intermediate_size=128, num_experts=16,
            experts_held=(0, 4), num_experts_per_tok=4)
        base.update(kw)
        return Lfm2Config(**base)


class ShortConv(nn.Module):
    """The convolution layers' mixer: ``(C * conv_K(B * X)) W_out`` with
    ``[B | C | X] = x W_in``.  Parameters ``in_proj`` (d, 3 d), ``taps`` (d,
    K) — a channel's tap ``j`` multiplies the input ``K - 1 - j`` tokens
    back — and ``out_proj`` (d, d)."""

    cfg: Lfm2Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        d = x.shape[-1]
        with jax.named_scope("conv_proj"):
            bcx = linear(cfg, 3 * d, "in_proj")(x)
        init = nn.initializers.normal(cfg.initializer_range)
        taps = self.param("taps", init, (d, cfg.conv_L_cache), jnp.float32)
        with jax.named_scope("conv_mix"):
            mixed = gated_short_conv(bcx, taps)
        with jax.named_scope("conv_out"):
            return linear(cfg, d, "out_proj")(mixed)


class Lfm2Layer(nn.Module):
    """One block; ``index`` picks its mixer (``cfg.layer_types``) and its
    feed-forward (dense below ``cfg.num_dense_layers``)."""

    cfg: Lfm2Config
    index: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic           # no dropout in this family
        cfg = self.cfg
        b, s, d = x.shape
        hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        norm = lambda name: RMSNorm(cfg.norm_eps, dt, name=name)

        y = norm("operator_norm")(x)
        if cfg.layer_types[self.index] == CONV:
            x = x + ShortConv(cfg, name="conv")(y)
        else:
            qkv = linear(cfg, (hq + 2 * hk) * hd, "qkv")(y)
            with jax.named_scope("qkv_split"):
                q, k, v = jnp.split(qkv, [hq * hd, (hq + hk) * hd], axis=-1)
            q = rotary(norm("q_norm")(split_heads(q, hq, hd)), cfg.rope_theta)
            k = rotary(norm("k_norm")(split_heads(k, hk, hd)), cfg.rope_theta)
            attn = causal_attention(q, k, split_heads(v, hk, hd))
            x = x + linear(cfg, d, "o_proj")(merge_heads(attn))

        z = norm("pre_mlp_norm")(x)
        if self.index < cfg.num_dense_layers:
            with jax.named_scope("dense_ffn"):
                return x + SwiGLU(cfg.intermediate_size, dt, init,
                                  name="mlp")(z)
        ff = ExpertShardMLP(
            num_experts=cfg.num_experts, experts_held=cfg.experts_held,
            d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_tok,
            route_norm=cfg.route_norm, route_scale=cfg.route_scale,
            score_func="sigmoid", compute_dtype=dt, kernel_init=init,
            name="moe",
        )(z.reshape(b * s, d)).reshape(b, s, d)
        return x + ff


class Lfm2LM(DecoderLM):
    """The shell with the head TIED to the embedding: the embedding's rows
    (the vocabulary slice held) are the head's columns."""

    cfg: Lfm2Config
    layer_cls = Lfm2Layer
    eps_field = "norm_eps"
    tied_head = True

    @staticmethod
    def validate(cfg):
        for kind in cfg.layer_types:
            if kind not in (CONV, FULL):
                raise ValueError(f"no layer type {kind!r}")
        if not 0 <= cfg.num_dense_layers <= cfg.num_layers:
            raise ValueError(f"num_dense_layers {cfg.num_dense_layers} of "
                             f"{cfg.num_layers} layers")
