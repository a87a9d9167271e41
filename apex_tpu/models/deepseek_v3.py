"""``deepseek_v3`` decoder LM (the block Moonlight-16B-A3B publishes its
``config.json`` under) on the training path.

The block: two RMSNorms in pre-norm position, multi-head LATENT attention —
keys and values made from one low-rank latent a token, one rotary key shared
by all heads, queries and keys wider than values — through the flash kernels
at a value head size of their own (``ops/attention.py``), a dense SwiGLU in
the leading layers and, past them, sigmoid-routed experts under a selection
bias beside a shared expert (``parallel/moe.py::ExpertShardMLP``).

The equations (no biases anywhere; embeddings not scaled; head untied)::

    Norm(x)  = x * rsqrt(mean(x^2) + eps) * w                 float32, w init 1
    block i:   h += MLA(Norm(h));  h += FF_i(Norm(h))
    FF_i     = W_down (silu(W_gate x) * W_up x)   for i < first_k_dense_replace,
               else the expert layer;   logits = W_head Norm(h)

    MLA on x (S, d), H heads:
      q = x W_q as (S, H, d_nope + d_rope) -> q_nope, q_pe     (no low-rank q)
      x W_dkv (kv_lora_rank + d_rope wide) -> the latent c, ONE rotary key k_pe
      c <- Norm(c) (eps ``latent_norm_eps``);  c W_ukv as (S, H, d_nope + d_v)
        -> k_nope, v
      q_pe, k_pe rotated by position, inv_freq_j = theta^(-2j / d_rope)
      q_h = [q_nope_h, q_pe_h];  k_h = [k_nope_h, k_pe]  (k_pe broadcast)
      o_h = softmax(q_h k_h^T (d_nope + d_rope)^-0.5, causal) v_h   (S, d_v)
      out = W_o concat_heads(o)                  no output gate, no q/k norm

    Expert layer (``noaux_tc`` at one group):  s = sigmoid(x W_r) over ALL
      experts, float32;  the k largest of s + b;  weights s_sel / (sum s_sel +
      1e-20) * routed_scaling_factor;  plus SharedSwiGLU(x), width
      n_shared_experts * moe_intermediate_size, ungated

**The rotary pairing.**  The published modelling code pairs ADJACENT dims
``(2j, 2j + 1)`` of the rotary slice (it de-interleaves them, then rotates the
two halves).  This model rotates the two HALVES of the slice as it finds it
(``(j, j + d_rope / 2)``: slices and a concatenation, no lane shuffle), so it
keeps the rotary columns of ``q_proj`` and of ``kv_a_proj`` DE-INTERLEAVED —
evens first, then odds.  Scores are dot products over the slice and q and k
are permuted alike, so they are the published ones; whoever brings weights in
the published order permutes those columns once
(``benchmark/families/deepseek_v3.py::to_program`` does).

Left out: ``q_lora_rank`` (null in the configuration this was built for: a
low-rank query path is not here), any router auxiliary loss (the step is the
plain causal-LM loss), the selection bias's loss-free update (it is a
parameter held at its value, as ``models/afmoe.py``'s), rotary scaling
(``max_position_embeddings`` positions are native).

The shell, how it is called and how expert parallelism enters
(``experts_held``, a sliced ``vocab_size``): ``models/decoder.py``.  Scopes
``mla_proj`` (everything before the kernel: the three projections, the
latent's norm, the rotation, assembling q and k), ``attn_full`` (the flash
call), ``mla_out``, the four ``moe_*``, ``lm_head``, ``lm_loss``.  The shared
rotary key is broadcast to the heads and concatenated by XLA before the kernel
(its gradient is the sum over heads of ``dk``'s rotary slice).  Serving
methods are not part of this model yet: a latent cache row and the absorbed
decode path are ROADMAP M5's serving half.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.decoder import (DecoderLM, RMSNorm, causal_attention,
                                     linear, merge_heads, rotary, split_heads)
from apex_tpu.parallel.moe import ExpertShardMLP, SwiGLU

__all__ = ["DeepseekV3Config", "DeepseekV3Layer", "DeepseekV3LM",
           "LatentAttention"]


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    vocab_size: int = 20480           # the slice held (a multiple of 128)
    hidden_size: int = 2048
    num_layers: int = 6
    first_k_dense_replace: int = 1
    # latent attention
    num_heads: int = 16
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: float = 50000.0
    # feed-forwards
    intermediate_size: int = 11264    # the dense layers' MLP
    moe_intermediate_size: int = 1408  # one expert
    n_routed_experts: int = 64        # routed over
    experts_held: Tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 6
    n_shared_experts: int = 2
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6     # the modelling code's default
    initializer_range: float = 0.02
    # activation rematerialization per block (apex_tpu.remat): full_block
    # keeps a block's input and the flash kernel's output and lse; the
    # latent path runs again in the backward pass
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16

    @staticmethod
    def tiny(**kw) -> "DeepseekV3Config":
        """For tests: every mechanism at toy widths (values narrower than
        keys, fewer rotary dims than the rest, a leading dense layer, a
        strict subset of the experts held)."""
        base = dict(
            vocab_size=256, hidden_size=128, num_layers=3,
            first_k_dense_replace=1, num_heads=4, qk_nope_head_dim=96,
            qk_rope_head_dim=32, v_head_dim=64, kv_lora_rank=64,
            intermediate_size=256, moe_intermediate_size=128,
            n_routed_experts=16, experts_held=(0, 4), num_experts_per_tok=4,
            n_shared_experts=2)
        base.update(kw)
        return DeepseekV3Config(**base)


class LatentAttention(nn.Module):
    """The mixer (the module docstring has its equations).  ``cfg`` is this
    family's, or another's with the same fields (``models/kimi_linear.py``);
    where its ``rope_theta`` is None nothing is rotated (position-free:
    ``q_pe`` and ``k_pe`` enter the scores as they are projected)."""

    cfg: Any

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        h, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        r, dt = cfg.kv_lora_rank, cfg.compute_dtype
        with jax.named_scope("mla_proj"):
            q = linear(cfg, h * (dn + dr), "q_proj")(x).reshape(
                b, s, h, dn + dr)
            # the down-projection: the latent and the rotary key, one product
            c, k_pe = jnp.split(linear(cfg, r + dr, "kv_a_proj")(x), [r], -1)
            c = RMSNorm(cfg.latent_norm_eps, dt, name="kv_a_norm")(c)
            kv = linear(cfg, h * (dn + dv), "kv_b_proj")(c).reshape(
                b, s, h, dn + dv)
            q_nope, q_pe = jnp.split(split_heads(q, h, dn + dr), [dn], axis=-1)
            k_nope, v = jnp.split(split_heads(kv, h, dn + dv), [dn], axis=-1)
            if cfg.rope_theta is None:
                k_pe = k_pe[:, None]                         # (b, 1, s, dr)
            else:
                q_pe = rotary(q_pe, cfg.rope_theta)
                k_pe = rotary(k_pe[:, None], cfg.rope_theta)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (b, h, s, dr))], axis=-1)
        attn = causal_attention(q, k, v)                     # (b, h, s, dv)
        with jax.named_scope("mla_out"):
            return linear(cfg, d, "o_proj")(merge_heads(attn))


class DeepseekV3Layer(nn.Module):
    """One block; ``index`` picks its feed-forward (dense below
    ``cfg.first_k_dense_replace``)."""

    cfg: DeepseekV3Config
    index: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic           # no dropout in this family
        cfg = self.cfg
        b, s, h = x.shape
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, name=name)
        x = x + LatentAttention(cfg, name="attn")(norm("input_norm")(x))
        y = norm("post_attn_norm")(x)
        if self.index < cfg.first_k_dense_replace:
            return x + SwiGLU(cfg.intermediate_size, dt, init, name="mlp")(y)
        ff = ExpertShardMLP(
            num_experts=cfg.n_routed_experts, experts_held=cfg.experts_held,
            d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_tok,
            shared_d_ff=cfg.moe_intermediate_size * cfg.n_shared_experts,
            route_norm=cfg.norm_topk_prob,
            route_scale=cfg.routed_scaling_factor, score_func="sigmoid",
            compute_dtype=dt, kernel_init=init, name="moe",
        )(y.reshape(b * s, h)).reshape(b, s, h)
        return x + ff


class DeepseekV3LM(DecoderLM):
    """The shell as it stands: embeddings not scaled, the head untied."""

    cfg: DeepseekV3Config
    layer_cls = DeepseekV3Layer

    @staticmethod
    def validate(cfg):
        if cfg.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head is not whole pairs")
