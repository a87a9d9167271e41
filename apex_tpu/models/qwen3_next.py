"""``qwen3_next`` decoder LM (Qwen3-Next family) on the training path.

The block: two zero-centred RMSNorms in pre-norm position, a token mixer that
is a gated delta net (linear attention: a recurrent float32 state per head,
``ops/gated_delta.py``) except in every ``full_attention_interval``-th layer,
where it is gated softmax attention (flash, grouped-query, rotary on part of
the head), and an expert layer in EVERY layer: softmax-routed experts beside a
gated shared one (``parallel/moe.py::ExpertShardMLP``).

The equations (no biases anywhere; embeddings not scaled; head untied)::

    Norm(x)   = x * rsqrt(mean(x^2) + eps) * (1 + w)          float32, w init 0
    block i:    h += Mixer_i(Norm(h));  h += MoE(Norm(h))
    Mixer_i   = full attention where (i + 1) % full_attention_interval == 0,
                else the gated delta net;   logits = W_head Norm(h)

    Gated delta net on x (S, d):  qkvz = x W_qkvz,  ba = x W_ba
      laid out per KEY head: qkvz as (S, H_k, 2 d_k + 2 r d_v) -> q (d_k),
      k (d_k), v (r d_v), z (r d_v), r = H_v / H_k;  ba as (S, H_k, 2 r) ->
      b (r), a (r);  v, z reshape to (S, H_v, d_v), b, a to (S, H_v)
      cat(q, k, v) -> causal depthwise conv (kernel 4, zeros before the
      row's start, no bias) -> SiLU -> split back
      beta = sigmoid(b);  g = -exp(A_log) * softplus(a + dt_bias)   float32
      q, k each x * rsqrt(sum x^2 + 1e-6);  q times d_k^-0.5;  value head
      h reads key head h // r (the source's repeat_interleave r, made by the
      rule's kernels through their index maps, never as an array)
      per head, float32, S_0 = 0 (d_k, d_v):
        S <- exp(g_t) S;  r = v_t - S^T k_t;  S <- S + k_t (beta_t r)^T;  o_t = S^T q_t
      o <- rsqrt(mean(o^2) + eps) o * w_norm * silu(z)   per head, w_norm init 1
      out = W_out concat_heads(o)

    Gated full attention:  q_proj(x) as (S, H, 2 D), cut per head into query
      (D) and gate (D);  k, v (S, H_kv, D);  q, k Norm'ed over the head;
      rotary (rotate_half pairing) on the first D * partial_rotary_factor
      dims, inv_freq = theta^(-2j / rot), the rest untouched;  causal
      softmax at D^-0.5, each key/value head serving H / H_kv query heads;
      out = W_o (attn * sigmoid(gate))

    Expert layer:  p = softmax(x W_r) over ALL experts, float32;  the k
      largest;  weights p_sel / sum p_sel (norm_topk_prob);  expert e:
      W_down (silu(W_gate x) * W_up x);  plus sigmoid(x w_sg) * SharedSwiGLU(x)

Left out: the multi-token-prediction module and any router auxiliary loss
(the step is the plain causal-LM loss).  ``A_log`` starts at ``log U(0, 16)``,
``dt_bias`` and ``w_norm`` at 1, the block norms' ``w`` at 0, every matrix
at N(0, ``initializer_range``).

The shell, how it is called and how expert parallelism enters
(``experts_held``, a sliced ``vocab_size``): ``models/decoder.py``, whose
``RMSNorm`` is zero-centred throughout this family.  Scopes ``gdn_proj``,
``gdn_conv``, ``gdn_scan``, ``gdn_out``, ``attn_full``, the four ``moe_*``,
``lm_head``, ``lm_loss``.  Under ``gdn_conv`` the projection's output goes
into ``ops/gated_delta.py::split_conv_qkvz`` as it lies: on the TPU two
kernels read q, k, v out of it through BlockSpecs and write them contiguous
over their heads, and the backward one writes the projection's gradient in
the same per-key-head layout — no concatenated, no float32 and no padded copy
crosses HBM there; z's cut stays XLA's copy.  Under ``gdn_scan`` XLA keeps
what is a row's own (sigmoid, softplus, the two l2 norms, the casts) and the
rule is two kernels that read q, k, v in the compute dtype as this model lays
them out and write o (``ops/gated_delta.py``): no float32 array of q's size
crosses HBM there.
Serving methods are not part of this model yet: a recurrent state beside K/V
pages is ROADMAP M6's other half.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.decoder import (DecoderLM, RMSNorm, causal_attention,
                                     linear, merge_heads, rotary, split_heads)
from apex_tpu.ops.gated_delta import gated_delta_rule, split_conv_qkvz
from apex_tpu.parallel.moe import ExpertShardMLP

__all__ = ["Qwen3NextConfig", "Qwen3NextLayer", "Qwen3NextLM"]


@dataclasses.dataclass(frozen=True)
class Qwen3NextConfig:
    vocab_size: int = 19072           # the slice held, padded to 128
    hidden_size: int = 2048
    num_layers: int = 4
    full_attention_interval: int = 4
    # gated full attention
    num_heads: int = 16
    num_kv_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # gated delta net
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    num_experts: int = 512            # routed over
    experts_held: Tuple[int, int] = (0, 32)
    num_experts_per_tok: int = 10
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # activation rematerialization per block (apex_tpu.remat)
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16

    def is_full_attention(self, index: int) -> bool:
        return (index + 1) % self.full_attention_interval == 0

    @staticmethod
    def tiny(**kw) -> "Qwen3NextConfig":
        """For tests: every mechanism at toy widths (the kernels' lanes of
        128 kept in the delta net's heads)."""
        base = dict(
            vocab_size=256, hidden_size=128, num_layers=4,
            full_attention_interval=4, num_heads=4, num_kv_heads=2,
            head_dim=64, linear_num_key_heads=1, linear_num_value_heads=2,
            linear_key_head_dim=128, linear_value_head_dim=128,
            moe_intermediate_size=128, shared_expert_intermediate_size=128,
            num_experts=16, experts_held=(0, 4), num_experts_per_tok=4)
        base.update(kw)
        return Qwen3NextConfig(**base)


def a_log_init(key, shape, dtype=jnp.float32):
    """``log U(0, 16)`` (the draw kept off 0, whose log has no value)."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1e-4, 16.0))


class GatedDeltaNet(nn.Module):
    """The linear-attention mixer (the module docstring has its equations)."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
        dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
        r = hv // hk
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)

        with jax.named_scope("gdn_proj"):
            qkvz = linear(cfg, hk * (2 * dk + 2 * r * dv), "in_proj_qkvz")(x)
            ba = linear(cfg, hk * 2 * r, "in_proj_ba")(x)
            beta_in, a = jnp.split(ba.reshape(b, s, hk, 2 * r), 2, axis=-1)
        with jax.named_scope("gdn_conv"):
            conv_w = self.param("conv", init,
                                (2 * hk * dk + hv * dv,
                                 cfg.linear_conv_kernel_dim), jnp.float32)
            # the projection's output goes in as it lies, per KEY head [q |
            # k | v | z]: on the TPU the kernels read q, k, v through
            # BlockSpecs on it and write them contiguous over their heads
            q, k, v, z = split_conv_qkvz(qkvz, conv_w, key_heads=hk,
                                         key_dim=dk, value_dim=dv)
        with jax.named_scope("gdn_scan"):
            a_log = self.param("A_log", a_log_init, (hv,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.ones_init(),
                                 (hv,), jnp.float32)
            f32 = lambda t: t.astype(jnp.float32)
            beta = jax.nn.sigmoid(f32(beta_in.reshape(b, s, hv)))
            g = -jnp.exp(f32(a_log)) * jax.nn.softplus(
                f32(a.reshape(b, s, hv)) + f32(dt_bias))
            # q and k stay at their 16 KEY heads (a value head reads its key
            # head's rows inside the kernels: no repeat), normalised in
            # float32; the kernels take them in v's dtype, a cast XLA fuses
            # into the norm, so no float32 copy of q, k or v crosses HBM
            l2 = lambda t: t * jax.lax.rsqrt(
                jnp.sum(jnp.square(t), axis=-1, keepdims=True) + 1e-6)
            key_heads = lambda t: l2(f32(t.reshape(b, s, hk, dk)))
            o = f32(gated_delta_rule(
                key_heads(q) * dk ** -0.5, key_heads(k),
                v.reshape(b, s, hv, dv), g, beta))
        with jax.named_scope("gdn_out"):
            w_norm = self.param("norm", nn.initializers.ones_init(),
                                (dv,), jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + cfg.rms_norm_eps)
            o = o * f32(w_norm) * jax.nn.silu(f32(z.reshape(b, s, hv, dv)))
            return linear(cfg, d, "out_proj")(
                o.reshape(b, s, hv * dv).astype(dt))


class GatedAttention(nn.Module):
    """The full-attention mixer (the module docstring has its equations)."""

    cfg: Qwen3NextConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.compute_dtype
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, True, name=name)
        # one projection: per query head its query then its gate, then the
        # keys, then the values
        qgkv = linear(cfg, (2 * hq + 2 * hk) * hd, "qgkv")(x)
        with jax.named_scope("qkv_split"):
            qg, k, v = jnp.split(
                qgkv, [2 * hq * hd, (2 * hq + hk) * hd], axis=-1)
            q, gate = jnp.split(qg.reshape(b, s, hq, 2 * hd), 2, axis=-1)
        rot = int(hd * cfg.partial_rotary_factor)
        q = rotary(norm("q_norm")(split_heads(q, hq, hd)), cfg.rope_theta, rot)
        k = rotary(norm("k_norm")(split_heads(k, hk, hd)), cfg.rope_theta, rot)
        attn = merge_heads(causal_attention(q, k, split_heads(v, hk, hd)))
        with jax.named_scope("output_gate"):
            gate = jax.nn.sigmoid(
                gate.reshape(b, s, hq * hd).astype(jnp.float32))
            attn = attn * gate.astype(dt)
        return linear(cfg, d, "o_proj")(attn)


class Qwen3NextLayer(nn.Module):
    """One block; ``index`` picks its mixer
    (``cfg.full_attention_interval``)."""

    cfg: Qwen3NextConfig
    index: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic           # no dropout in this family
        cfg = self.cfg
        b, s, h = x.shape
        dt = cfg.compute_dtype
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, True, name=name)
        y = norm("input_norm")(x)
        if cfg.is_full_attention(self.index):
            x = x + GatedAttention(cfg, name="attn")(y)
        else:
            x = x + GatedDeltaNet(cfg, name="gdn")(y)
        y = norm("post_attn_norm")(x)
        ff = ExpertShardMLP(
            num_experts=cfg.num_experts, experts_held=cfg.experts_held,
            d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_tok,
            shared_d_ff=cfg.shared_expert_intermediate_size,
            route_norm=cfg.norm_topk_prob, score_func="softmax",
            shared_gate=True, compute_dtype=dt,
            kernel_init=nn.initializers.normal(cfg.initializer_range),
            name="moe",
        )(y.reshape(b * s, h)).reshape(b, s, h)
        return x + ff


class Qwen3NextLM(DecoderLM):
    """The shell with the final norm zero-centred as the blocks' are;
    embeddings not scaled, the head untied."""

    cfg: Qwen3NextConfig
    layer_cls = Qwen3NextLayer
    zero_centred = True

    @staticmethod
    def validate(cfg):
        if cfg.linear_num_value_heads % cfg.linear_num_key_heads:
            raise ValueError("linear_num_value_heads is not a multiple of "
                             "linear_num_key_heads")
        if int(cfg.head_dim * cfg.partial_rotary_factor) % 2:
            raise ValueError("the rotated part of a head is not whole pairs")
