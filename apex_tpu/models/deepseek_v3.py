"""``deepseek_v3`` decoder LM (the block Moonlight-16B-A3B publishes its
``config.json`` under) on the training path.

The fourth decoder block of the zoo (``models/gpt.py``, ``models/afmoe.py``,
``models/qwen3_next.py``): two RMSNorms a block in pre-norm position,
multi-head LATENT attention — keys and values made from one low-rank latent a
token, one rotary key shared by all heads, queries and keys wider than values
— through the flash kernels at a value head size of their own
(``ops/attention.py``), a dense SwiGLU in the leading layers and, past them,
sigmoid-routed experts under a selection bias beside a shared expert
(``parallel/moe.py::ExpertShardMLP``).

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

Called as :class:`apex_tpu.models.gpt.GPTLM`, ``AfmoeLM`` and ``Qwen3NextLM``
are: ``model.apply({"params": p}, ids, labels=labels, deterministic=...)`` ->
``(logits, loss)``.  Expert parallelism enters as ``experts_held`` and a sliced
``vocab_size``, as in ``models/afmoe.py``.  Scopes ``mla_proj`` (everything
before the kernel: the three projections, the latent's norm, the rotation,
assembling q and k), ``attn_full`` (the flash call), ``mla_out``, the four
``moe_*``, ``lm_head``, ``lm_loss``.  The shared
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

from apex_tpu.amp.layers import Dense
from apex_tpu.models.afmoe import RMSNorm, rotary
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.softmax_xentropy import softmax_cross_entropy
from apex_tpu.parallel.moe import ExpertShardMLP, SwiGLU
from apex_tpu.remat import remat_module

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
    """The mixer (the module docstring has its equations)."""

    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        h, dn, dr, dv = (cfg.num_heads, cfg.qk_nope_head_dim,
                         cfg.qk_rope_head_dim, cfg.v_head_dim)
        r, dt = cfg.kv_lora_rank, cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        dense = lambda n, name: Dense(n, use_bias=False, dtype=dt,
                                      kernel_init=init, name=name)

        with jax.named_scope("mla_proj"):
            q = dense(h * (dn + dr), "q_proj")(x).reshape(b, s, h, dn + dr)
            # the down-projection: the latent and the rotary key, one product
            c, k_pe = jnp.split(dense(r + dr, "kv_a_proj")(x), [r], axis=-1)
            c = RMSNorm(cfg.latent_norm_eps, dt, name="kv_a_norm")(c)
            kv = dense(h * (dn + dv), "kv_b_proj")(c).reshape(b, s, h, dn + dv)
            heads = lambda t: t.transpose(0, 2, 1, 3)        # (b, h, s, .)
            q_nope, q_pe = jnp.split(heads(q), [dn], axis=-1)
            k_nope, v = jnp.split(heads(kv), [dn], axis=-1)
            q_pe = rotary(q_pe, cfg.rope_theta)
            k_pe = rotary(k_pe[:, None], cfg.rope_theta)     # (b, 1, s, dr)
            q = jnp.concatenate([q_nope, q_pe], axis=-1)
            k = jnp.concatenate(
                [k_nope, jnp.broadcast_to(k_pe, (b, h, s, dr))], axis=-1)
        with jax.named_scope("attn_full"):
            attn = flash_attention(q, k, v, causal=True)     # (b, h, s, dv)
        with jax.named_scope("mla_out"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, h * dv)
            return dense(d, "o_proj")(attn)


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


class DeepseekV3LM(nn.Module):
    """Embedding, the blocks ``layer_<i>``, a final RMSNorm and the untied
    head.  ``__call__(ids)`` returns (B, S, V) float32 logits; with
    ``labels`` (-100: not predicted) also the token-mean fused-xentropy
    loss, as :class:`apex_tpu.models.gpt.GPTLM` does."""

    cfg: DeepseekV3Config

    def setup(self):
        cfg = self.cfg
        if cfg.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head is not whole pairs")
        init = nn.initializers.normal(cfg.initializer_range)
        self.embed = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                              embedding_init=init, dtype=jnp.float32)
        # deterministic is static_argnum 2 (self=0): called positionally
        layer_cls = remat_module(DeepseekV3Layer, cfg.remat_policy,
                                 static_argnums=(2,))
        self.layers = [layer_cls(cfg, i, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.norm_f = RMSNorm(cfg.rms_norm_eps, cfg.compute_dtype)
        self.head = Dense(cfg.vocab_size, use_bias=False,
                          dtype=cfg.compute_dtype, kernel_init=init)

    def __call__(self, input_ids, labels=None, deterministic: bool = True):
        cfg = self.cfg
        with jax.named_scope("embed"):
            x = self.embed(input_ids).astype(cfg.compute_dtype)
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
