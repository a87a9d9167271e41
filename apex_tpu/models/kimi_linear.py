"""``kimi_linear`` decoder LM (Kimi-Linear-48B-A3B's family) on the training
path.

The block: two RMSNorms in pre-norm position, a token mixer that is Kimi Delta
Attention (KDA: the gated delta rule whose decay is a VECTOR over the key
channels of each head, ``ops/kda.py``) except in the layers the configuration
lists as full attention, where it is multi-head LATENT attention
(``models/deepseek_v3.py::LatentAttention``) with NO rotation
(``mla_use_nope``: position enters through the KDA layers alone), a dense
SwiGLU in the leading layers and, past them, sigmoid-routed experts under a
selection bias beside a shared expert (``parallel/moe.py::ExpertShardMLP``).
Which layer is which is a published LIST (``kda_layers`` /
``full_attn_layers``, 1-indexed), not a period.

The equations (no biases anywhere; embeddings not scaled; head untied)::

    Norm(x)  = x * rsqrt(mean(x^2) + eps) * w                 float32, w init 1
    block i:   h += Mixer_i(Norm(h));  h += FF_i(Norm(h));  logits = W_head Norm(h)
    Mixer_i  = latent attention where i + 1 is in full_attn_layers, else KDA
    FF_i     = W_down (silu(W_gate x) * W_up x)   for i < first_k_dense_replace,
               else the expert layer

    KDA on x (S, d), H heads of d_h for keys and values alike:
      qkv = x W_qkv, laid out per head [q d_h | k d_h | v d_h] -> depthwise
        causal conv (kernel 4, zeros before the row's start, no bias) -> SiLU
      q_h <- q_h * rsqrt(sum q_h^2 + 1e-6) * d_h^-0.5;  k_h likewise, no scale
      g    = -exp(A_log_h) * softplus((x W_fa) W_fb + dt_bias)   (S, H, d_h)
             float32, <= 0: a log-decay for EVERY key channel
      beta = sigmoid(x W_b)                                      (S, H)
      per head, float32, S_0 = 0 (d_h, d_h):
        S <- Diag(exp g_t) S;  r = v_t - S^T k_t;  S <- S + beta_t k_t r^T;  o_t = S^T q_t
      o <- rsqrt(mean(o^2) + eps) o * w_norm * sigmoid((x W_ga) W_gb)   per head
      out = W_o concat_heads(o)

    Latent attention and the expert layer: ``models/deepseek_v3.py``'s, the
      first with ``rope_theta`` None — q_pe and k_pe enter the scores as they
      are projected.

Left out: any router auxiliary loss (the step is the plain causal-LM loss),
the selection bias's loss-free update (a parameter held at its value), the
multi-token-prediction module (``num_nextn_predict_layers`` 0 in the published
configuration).  ``A_log`` starts at ``log U(0, 16)`` a head, ``dt_bias`` and
the norms' ``w`` at 1, every matrix (the convolution's taps among them) at
N(0, ``initializer_range``).

The shell, how it is called and how expert parallelism enters
(``experts_held``, a sliced ``vocab_size``): ``models/decoder.py``.  Scopes
``kda_proj``, ``kda_conv``, ``kda_gate`` (the two low-rank gates, beta, the
softplus: the log-decay ``g``, made over (S, H d_h) as ``f_b_proj`` lies),
``kda_scan`` (the rule's kernels or scan — the l2 norm of q and k and the
chunks' running sums of ``g`` inside them), ``kda_out``; the latent layer's
``mla_proj``, ``attn_full``, ``mla_out``; ``dense_ffn`` around the leading layers' MLP, the four ``moe_*``,
``lm_head``, ``lm_loss``.  Under ``kda_conv`` the projection's output goes into
:func:`apex_tpu.ops.kda.split_conv_qkv` as it lies (on the TPU the
``apex_conv1d_*`` kernels read q, k, v through BlockSpecs on it); under
``kda_scan`` the rule is the ``apex_kda_*`` kernel pair, which reads q, k, v
in the compute dtype as the convolution wrote them and ``g`` in float32 as
the gate made it, and normalises and sums on the tile it holds: no array of
q's size is made between the convolution, the gate and the rule.
Serving methods are not part of this model yet: the rule's one-token step and
its state beside latent cache rows are ROADMAP "Reach".
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.decoder import DecoderLM, RMSNorm, linear
from apex_tpu.models.deepseek_v3 import LatentAttention
from apex_tpu.models.qwen3_next import a_log_init
from apex_tpu.ops.kda import kda_rule, split_conv_qkv
from apex_tpu.parallel.moe import ExpertShardMLP, SwiGLU

__all__ = ["KimiLinearConfig", "KimiDeltaAttention", "KimiLinearLayer",
           "KimiLinearLM"]


@dataclasses.dataclass(frozen=True)
class KimiLinearConfig:
    vocab_size: int = 20480           # the slice held (a multiple of 128)
    hidden_size: int = 2304
    num_layers: int = 5
    # which layers (1-indexed, as published) mix by full attention: the rest
    # are KDA layers
    full_attn_layers: Tuple[int, ...] = (4,)
    first_k_dense_replace: int = 1
    # KDA
    kda_num_heads: int = 32
    kda_head_dim: int = 128
    short_conv_kernel_size: int = 4
    kda_gate_rank: int = 128          # the two low-rank gates' inner width
    # latent attention (the names LatentAttention reads)
    num_heads: int = 32
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    kv_lora_rank: int = 512
    rope_theta: Optional[float] = None    # mla_use_nope: nothing is rotated
    # feed-forwards
    intermediate_size: int = 9216     # the dense layers' MLP
    moe_intermediate_size: int = 1024  # one expert
    n_routed_experts: int = 256       # routed over
    experts_held: Tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 8
    n_shared_experts: int = 1
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 2.446
    rms_norm_eps: float = 1e-5
    latent_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # activation rematerialization per block (apex_tpu.remat): full_block
    # keeps a block's input and what the rule's (or flash's) kernels declare
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16

    def is_full_attention(self, index: int) -> bool:
        return index + 1 in self.full_attn_layers

    @staticmethod
    def tiny(**kw) -> "KimiLinearConfig":
        """For tests: every mechanism at toy widths (the kernels' lanes of
        128 kept in KDA's heads; KDA + dense, KDA + experts, latent +
        experts)."""
        base = dict(
            vocab_size=256, hidden_size=128, num_layers=3,
            full_attn_layers=(3,), first_k_dense_replace=1, kda_num_heads=2,
            kda_head_dim=128, kda_gate_rank=32, num_heads=4,
            qk_nope_head_dim=96, qk_rope_head_dim=32, v_head_dim=64,
            kv_lora_rank=64, intermediate_size=256, moe_intermediate_size=128,
            n_routed_experts=16, experts_held=(0, 4), num_experts_per_tok=4)
        base.update(kw)
        return KimiLinearConfig(**base)


class KimiDeltaAttention(nn.Module):
    """The linear-attention mixer (the module docstring has its equations)."""

    cfg: KimiLinearConfig

    @nn.compact
    def __call__(self, x):
        cfg = self.cfg
        b, s, d = x.shape
        h, hd, rank = cfg.kda_num_heads, cfg.kda_head_dim, cfg.kda_gate_rank
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        f32 = lambda t: t.astype(jnp.float32)
        heads = lambda t: t.reshape(b, s, h, hd)
        low_rank = lambda name: linear(cfg, h * hd, name + "_b_proj")(
            linear(cfg, rank, name + "_a_proj")(x))

        with jax.named_scope("kda_proj"):
            qkv = linear(cfg, 3 * h * hd, "qkv_proj")(x)
        with jax.named_scope("kda_conv"):
            conv_w = self.param("conv", init,
                                (3 * h * hd, cfg.short_conv_kernel_size),
                                jnp.float32)
            # the projection's output goes in as it lies, per head [q | k |
            # v]: on the TPU the kernels read the three through BlockSpecs
            # on it and write each contiguous over its heads
            q, k, v = split_conv_qkv(qkv, conv_w, heads=h, head_dim=hd)
        with jax.named_scope("kda_gate"):
            a_log = self.param("A_log", a_log_init, (h,), jnp.float32)
            dt_bias = self.param("dt_bias", nn.initializers.ones_init(),
                                 (h * hd,), jnp.float32)
            # made over (b, s, h d) as the projection lies — a head's rate
            # at each of its channels — so that the rule's kernels read it
            # there: over (b, s, h, d) it is tiled another way, and every
            # change of shape a 134 MB copy
            g = jnp.repeat(-jnp.exp(f32(a_log)), hd) * jax.nn.softplus(
                f32(low_rank("f")) + f32(dt_bias))
            beta = jax.nn.sigmoid(f32(linear(cfg, h, "b_proj")(x)))
            gate = jax.nn.sigmoid(f32(low_rank("g")))
        with jax.named_scope("kda_scan"):
            # q and k as the convolution wrote them, g a token's own decay:
            # the rule normalises and sums where it reads them
            o = f32(kda_rule(heads(q), heads(k), heads(v), heads(g), beta,
                             qk_norm=(1e-6, hd ** -0.5)))
        with jax.named_scope("kda_out"):
            w_norm = self.param("norm", nn.initializers.ones_init(), (hd,),
                                jnp.float32)
            o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1,
                                           keepdims=True) + cfg.rms_norm_eps)
            o = (o * f32(w_norm)).reshape(b, s, h * hd) * gate
            return linear(cfg, d, "o_proj")(o.astype(dt))


class KimiLinearLayer(nn.Module):
    """One block; ``index`` picks its mixer (``cfg.full_attn_layers``) and
    its feed-forward (dense below ``cfg.first_k_dense_replace``)."""

    cfg: KimiLinearConfig
    index: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic           # no dropout in this family
        cfg = self.cfg
        b, s, h = x.shape
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, name=name)
        y = norm("input_norm")(x)
        if cfg.is_full_attention(self.index):
            x = x + LatentAttention(cfg, name="attn")(y)
        else:
            x = x + KimiDeltaAttention(cfg, name="kda")(y)
        y = norm("post_attn_norm")(x)
        if self.index < cfg.first_k_dense_replace:
            with jax.named_scope("dense_ffn"):
                return x + SwiGLU(cfg.intermediate_size, dt, init,
                                  name="mlp")(y)
        ff = ExpertShardMLP(
            num_experts=cfg.n_routed_experts, experts_held=cfg.experts_held,
            d_ff=cfg.moe_intermediate_size, k=cfg.num_experts_per_tok,
            shared_d_ff=cfg.moe_intermediate_size * cfg.n_shared_experts,
            route_norm=cfg.norm_topk_prob,
            route_scale=cfg.routed_scaling_factor, score_func="sigmoid",
            compute_dtype=dt, kernel_init=init, name="moe",
        )(y.reshape(b * s, h)).reshape(b, s, h)
        return x + ff


class KimiLinearLM(DecoderLM):
    """The shell as it stands: embeddings not scaled, the head untied."""

    cfg: KimiLinearConfig
    layer_cls = KimiLinearLayer

    @staticmethod
    def validate(cfg):
        if not all(1 <= i <= cfg.num_layers for i in cfg.full_attn_layers):
            raise ValueError(f"full_attn_layers {cfg.full_attn_layers} name "
                             f"layers outside 1..{cfg.num_layers}")
        if cfg.rope_theta is not None and cfg.qk_rope_head_dim % 2:
            raise ValueError("the rotary part of a head is not whole pairs")
