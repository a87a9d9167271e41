"""Arcee ``afmoe`` decoder LM (Trinity family) on the training path.

The block: four RMSNorms in sandwich position, grouped-query flash attention
with an output gate and normed queries and keys, rotary positions on the
sliding-window layers only (the full-attention layers carry no position
signal), gated SiLU MLPs, and — past the leading dense layers — sigmoid routed
experts beside a shared one (``parallel/moe.py::ExpertShardMLP``). Layers of
unequal kind are chosen by ``layer_types``.

Per block (no biases anywhere)::

    h += RMS(Attn(RMS(h)));  h += RMS(FF(RMS(h)))
    Attn(x) = W_o (softmax(q k^T / sqrt(D), causal [, i - j < window]) v * sigmoid(W_g x))

From ``qkvg``'s output to the flash kernels — the cut, the two per-head
norms, the rotation, the way to heads-major — is one call,
``models/decoder.py::qkv_heads``, which chooses by shape: at the published
head size, 128 (one lane tile), every layer goes through
``ops/qk_heads.py``'s kernel pair on the TPU — the norm and, in a window layer,
the rotation done where ``qkvg`` lies, the gate's columns left to the gate's
own fusion, the projection's gradient written as one array (scope ``rope`` in
a window layer, ``heads_layout`` in a full one); at this file's tiny test
size, heads of 64, and off the TPU the same call is composed of ``jnp.split``,
``split_heads``, ``RMSNorm`` and ``rotary``.  The parameters are the same
either way (``q_norm/scale``, ``k_norm/scale``).

``h = E[ids] * sqrt(hidden)`` (``mup_enabled``), ``logits = W_head RMS(h)``,
the head untied.  The shell, how it is called and how expert parallelism
enters (``experts_held``, a sliced ``vocab_size``): ``models/decoder.py``.

Serving methods (``prefill``, ``decode_*``) are not part of this model yet.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.decoder import (DecoderLM, RMSNorm, causal_attention,
                                     linear, merge_heads, qkv_heads)
from apex_tpu.parallel.moe import ExpertShardMLP, SwiGLU

__all__ = ["AfmoeConfig", "AfmoeLayer", "AfmoeLM"]

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
        qkvg = linear(cfg, (2 * hq + 2 * hk) * hd, "qkvg")(y)
        q, k, v, g = qkv_heads(
            qkvg, hq, hk, hd, norm_eps=cfg.rms_norm_eps,
            theta=cfg.rope_theta if windowed else None)
        attn = merge_heads(causal_attention(
            q, k, v, window=cfg.sliding_window if windowed else None))
        with jax.named_scope("output_gate"):
            attn = attn * jax.nn.sigmoid(g.astype(jnp.float32)).astype(dt)
        x = x + norm("post_attn_norm")(linear(cfg, h, "o_proj")(attn))

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


class AfmoeLM(DecoderLM):
    """The shell with the embedding scaled by ``sqrt(hidden)`` under
    ``mup_enabled`` and the head untied."""

    cfg: AfmoeConfig
    layer_cls = AfmoeLayer

    @staticmethod
    def validate(cfg):
        for kind in cfg.layer_types:
            if kind not in (WINDOW, FULL):
                raise ValueError(f"no layer type {kind!r}")

    @staticmethod
    def embed_scale(cfg):
        return cfg.hidden_size ** 0.5 if cfg.mup_enabled else None
