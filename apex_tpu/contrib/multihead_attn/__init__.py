"""Fused multihead-attention modules — SelfMultiheadAttn / EncdecMultiheadAttn.

ref: apex/contrib/multihead_attn/{self,encdec}_multihead_attn.py (modules),
self_multihead_attn_func.py (unfused "default" impl),
fast_self_multihead_attn_func.py + 8 CUDA extensions ("fast" impl),
*_norm_add_func.py (pre-LN fused variants), mask_softmax_dropout_func.py.

TPU re-design: the reference's "fast" path fuses QKV GEMM + masked softmax +
dropout + out-proj around cuBLAS.  Here "fast" routes the attention core
through the Pallas flash kernel (:func:`apex_tpu.ops.flash_attention`) —
strictly stronger fusion (no (Sq,Sk) materialization).  The reference's
fast-vs-default switch is preserved:

- ``impl='fast'``    -> flash kernel, including in-kernel attention-
  probability dropout (counter-based mask regenerated in forward and
  backward from a per-call seed; see apex_tpu.ops.attention).
- ``impl='default'`` -> pure-jnp attention with jax.random probability
  dropout (ref self_multihead_attn_func.py:74-88: dropout on softmax
  results).  The two impls use different RNG streams, like the
  reference's fast (curand) vs default (torch) impls.

Differences from the reference kept deliberately:

- Inputs are batch-first ``(B, S, H)`` (flax convention), not the reference's
  seq-first ``(T, B, C)``.
- ``forward`` returns just the output tensor (the reference returns
  ``(outputs, None)`` — the None is its unused need_weights slot).
- Dropout randomness comes from flax's ``'dropout'`` rng collection.

``include_norm_add`` is the pre-LN fused variant (ref *_norm_add_func.py):
LN(query) feeds attention and the module returns ``dropout(attn) + query``
(residual add of the RAW query, self_multihead_attn.py:160-167).
"""
from __future__ import annotations

import math
from typing import Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp import functional as F
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.ops.attention import flash_attention

__all__ = [
    "SelfMultiheadAttn",
    "EncdecMultiheadAttn",
    "mask_softmax_dropout",
]


def _masks_to_bias(
    key_padding_mask, attn_mask, mask_additive, b, sq, sk
) -> Optional[jax.Array]:
    """Fold the reference's two mask flavors into one additive (B, Sq, Sk) bias.

    key_padding_mask: (B, Sk), nonzero = PAD (ref: 'padding elements are
    indicated by 1s').  attn_mask: (Sq, Sk) time mask, nonzero = masked.
    mask_additive: the key_padding_mask already holds additive values
    (ref mask_additive flag, self_multihead_attn.py:42-46).
    """
    if key_padding_mask is not None and attn_mask is not None:
        raise ValueError(
            "attn_mask and key_padding_mask should not be both defined"
        )
    if key_padding_mask is not None:
        if key_padding_mask.ndim == 2:  # (B, Sk)
            kpm = key_padding_mask[:, None, :]
        else:  # already (B, Sq, Sk)
            kpm = key_padding_mask
        if mask_additive:
            bias = kpm.astype(jnp.float32)
        else:
            bias = jnp.where(kpm != 0, -1e9, 0.0)
        return jnp.broadcast_to(bias, (b, sq, sk))
    if attn_mask is not None:
        bias = jnp.where(attn_mask != 0, -1e9, 0.0).astype(jnp.float32)
        return jnp.broadcast_to(bias[None, :, :], (b, sq, sk))
    return None


def mask_softmax_dropout(
    scores: jax.Array,
    bias: Optional[jax.Array] = None,
    dropout_rate: float = 0.0,
    deterministic: bool = True,
    rng: Optional[jax.Array] = None,
) -> jax.Array:
    """Masked softmax + probability dropout in fp32.

    ref: apex/contrib/multihead_attn/mask_softmax_dropout_func.py (the
    standalone fused kernel the reference also exports).  ``scores``:
    (..., Sq, Sk); ``bias`` broadcastable additive mask.
    """
    s = scores.astype(jnp.float32)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    p = jax.nn.softmax(s, axis=-1)
    if dropout_rate > 0.0 and not deterministic:
        if rng is None:
            raise ValueError("dropout requires an rng")
        keep = jax.random.bernoulli(rng, 1.0 - dropout_rate, p.shape)
        p = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
    return p.astype(scores.dtype)


def _core_attention(
    module: nn.Module,
    q, k, v,  # (B, H, S, D)
    bias,  # (B, Sq, Sk) additive or None
    scale: float,
    dropout_rate: float,
    is_training: bool,
    impl: str,
    probs_bf16: bool = False,
):
    """fast -> flash kernel (in-kernel dropout); default -> unfused
    (``probs_bf16`` applies only to the kernel path — the unfused path
    keeps reference fp32 softmax numerics)."""
    needs_dropout = dropout_rate > 0.0 and is_training
    if impl == "fast":
        seed = None
        if needs_dropout:
            # one int32 seed per call from the module's dropout rng stream;
            # the kernel's counter-based mask derives from it
            seed = jax.random.randint(
                module.make_rng("dropout"), (), 0, jnp.iinfo(jnp.int32).max
            )
        return flash_attention(
            q, k, v, bias=bias, scale=scale,
            dropout_rate=dropout_rate if needs_dropout else 0.0,
            dropout_seed=seed, probs_bf16=probs_bf16,
        )
    # unfused reference numerics (ref self_multihead_attn_func.py:40-88)
    s = jnp.einsum(
        "bhqd,bhkd->bhqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    rng = module.make_rng("dropout") if needs_dropout else None
    p = mask_softmax_dropout(
        s,
        bias=bias[:, None, :, :] if bias is not None else None,
        dropout_rate=dropout_rate,
        deterministic=not is_training,
        rng=rng,
    )
    return jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32)).astype(q.dtype)


class SelfMultiheadAttn(nn.Module):
    """Self-attention module (ref self_multihead_attn.py:26-178).

    Constructor knobs mirror the reference: ``bias`` adds in/out projection
    biases, ``include_norm_add`` enables the pre-LN + residual variant,
    ``impl`` picks fast (Pallas flash) vs default (unfused jnp),
    ``separate_qkv_params`` stores q/k/v weights as three parameters,
    ``mask_additive`` marks key_padding_mask as already-additive.
    """

    embed_dim: int
    num_heads: int
    dropout: float = 0.0
    bias: bool = False
    include_norm_add: bool = False
    impl: str = "fast"
    separate_qkv_params: bool = False
    mask_additive: bool = False
    # opt-in half-precision-probability MXU dots in the flash kernel
    # (flash_attention(probs_bf16=...); tolerance contract documented there)
    probs_bf16: bool = False
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        if self.embed_dim % self.num_heads != 0:
            raise ValueError("embed_dim must be divisible by num_heads")
        if self.impl not in ("fast", "default"):
            raise ValueError(f"Unsupported impl: {self.impl}")
        if self.mask_additive and self.include_norm_add:
            raise ValueError("additive mask not supported with layer norm")
        h = self.embed_dim
        # xavier_uniform with gain sqrt(2): the 3h x h joint weight must be
        # initialized like an h x h matrix (ref reset_parameters comment,
        # self_multihead_attn.py:101-107)
        joint_init = nn.initializers.variance_scaling(
            2.0, "fan_avg", "uniform", in_axis=-2, out_axis=-1
        )
        xavier = nn.initializers.xavier_uniform()
        if self.separate_qkv_params:
            self.q_weight = self.param("q_weight", xavier, (h, h), jnp.float32)
            self.k_weight = self.param("k_weight", xavier, (h, h), jnp.float32)
            self.v_weight = self.param("v_weight", xavier, (h, h), jnp.float32)
        else:
            self.in_proj_weight = self.param(
                "in_proj_weight", joint_init, (h, 3 * h), jnp.float32
            )
        self.out_proj_weight = self.param(
            "out_proj_weight", xavier, (h, h), jnp.float32
        )
        if self.bias:
            zeros = nn.initializers.zeros
            if self.separate_qkv_params:
                self.q_bias = self.param("q_bias", zeros, (h,), jnp.float32)
                self.k_bias = self.param("k_bias", zeros, (h,), jnp.float32)
                self.v_bias = self.param("v_bias", zeros, (h,), jnp.float32)
            else:
                self.in_proj_bias = self.param(
                    "in_proj_bias", zeros, (3 * h,), jnp.float32
                )
            self.out_proj_bias = self.param(
                "out_proj_bias", zeros, (h,), jnp.float32
            )
        if self.include_norm_add:
            self.lyr_nrm = FusedLayerNorm(h, name="lyr_nrm")

    def __call__(
        self,
        query: jax.Array,  # (B, S, H)
        key: Optional[jax.Array] = None,  # accepted for API parity; must
        value: Optional[jax.Array] = None,  # equal query in self-attention
        key_padding_mask: Optional[jax.Array] = None,
        attn_mask: Optional[jax.Array] = None,
        is_training: bool = True,
    ) -> jax.Array:
        # Q, K and V are ALL projected from `query`; the key/value arguments
        # exist only for torch-API parity and are ignored — the reference
        # does the same (self_multihead_attn.py:124-132 "Self-attention can
        # be implemented by passing in the same arguments").  An identity
        # check would be unreliable under jit (each argument traces to its
        # own tracer), so this mirrors the reference's documented contract.
        del key, value
        h, nh = self.embed_dim, self.num_heads
        d = h // nh
        b, s, _ = query.shape
        dt = self.dtype

        x = query
        if self.include_norm_add:
            x = self.lyr_nrm(x.astype(jnp.float32))
        x = x.astype(dt)

        if self.separate_qkv_params:
            w = jnp.concatenate(
                [self.q_weight, self.k_weight, self.v_weight], axis=-1
            )
        else:
            w = self.in_proj_weight
        if self.bias:
            if self.separate_qkv_params:
                bvec = jnp.concatenate([self.q_bias, self.k_bias, self.v_bias])
            else:
                bvec = self.in_proj_bias
            bvec = bvec.astype(dt)
        else:
            bvec = None
        # through the policy table so O1 autocast reaches the projections
        qkv = F.dense(x, w.astype(dt), bvec)
        with jax.named_scope("qkv_split"):
            q, k, v = jnp.split(qkv, 3, axis=-1)
        # scope ``heads_layout``: to heads-major around the core, and back
        with jax.named_scope("heads_layout"):
            q, k, v = (t.reshape(b, s, nh, d).transpose(0, 2, 1, 3)
                       for t in (q, k, v))

        bias_ = _masks_to_bias(
            key_padding_mask, attn_mask, self.mask_additive, b, s, s
        )
        attn = _core_attention(
            self, q, k, v, bias_,
            scale=d ** -0.5, dropout_rate=self.dropout,
            is_training=is_training, impl=self.impl,
            probs_bf16=self.probs_bf16,
        )
        with jax.named_scope("heads_layout"):
            attn = attn.transpose(0, 2, 1, 3).reshape(b, s, h)
        out = F.dense(
            attn, self.out_proj_weight.astype(dt),
            self.out_proj_bias.astype(dt) if self.bias else None,
        )

        if self.include_norm_add:
            # residual dropout + add of the RAW query (ref :160-167)
            if self.dropout > 0.0 and is_training:
                out = nn.Dropout(self.dropout, deterministic=False)(out)
            out = out + query.astype(out.dtype)
        return out


class EncdecMultiheadAttn(nn.Module):
    """Encoder-decoder cross-attention (ref encdec_multihead_attn.py:27-159):
    Q projected from the decoder query, K/V jointly from the encoder output.
    The reference's fast impl rejects biases (encdec_multihead_attn.py:47-48);
    here bias works on both impls (the flash kernel doesn't care), kept
    anyway as a constructor knob for config parity."""

    embed_dim: int
    num_heads: int
    dropout: float = 0.0
    bias: bool = False
    include_norm_add: bool = False
    impl: str = "fast"
    probs_bf16: bool = False  # see SelfMultiheadAttn
    dtype: jnp.dtype = jnp.float32

    def setup(self):
        if self.embed_dim % self.num_heads != 0:
            raise ValueError("embed_dim must be divisible by num_heads")
        if self.impl not in ("fast", "default"):
            raise ValueError(f"Unsupported impl: {self.impl}")
        h = self.embed_dim
        xavier = nn.initializers.xavier_uniform()
        kv_init = nn.initializers.variance_scaling(
            # 2h x h joint kv weight initialized like h x h (gain sqrt(1.5):
            # sqrt(6/(h+h)) / sqrt(6/(2h+h)) = sqrt(3/2))
            1.5, "fan_avg", "uniform", in_axis=-2, out_axis=-1
        )
        self.in_proj_weight_q = self.param(
            "in_proj_weight_q", xavier, (h, h), jnp.float32
        )
        self.in_proj_weight_kv = self.param(
            "in_proj_weight_kv", kv_init, (h, 2 * h), jnp.float32
        )
        self.out_proj_weight = self.param(
            "out_proj_weight", xavier, (h, h), jnp.float32
        )
        if self.bias:
            zeros = nn.initializers.zeros
            self.in_proj_bias_q = self.param(
                "in_proj_bias_q", zeros, (h,), jnp.float32
            )
            self.in_proj_bias_kv = self.param(
                "in_proj_bias_kv", zeros, (2 * h,), jnp.float32
            )
            self.out_proj_bias = self.param(
                "out_proj_bias", zeros, (h,), jnp.float32
            )
        if self.include_norm_add:
            self.lyr_nrm = FusedLayerNorm(h, name="lyr_nrm")

    def __call__(
        self,
        query: jax.Array,  # (B, Sq, H) decoder side
        key: jax.Array,  # (B, Sk, H) encoder side (value source too)
        value: Optional[jax.Array] = None,  # parity arg; K/V come from `key`
        key_padding_mask: Optional[jax.Array] = None,
        attn_mask: Optional[jax.Array] = None,
        is_training: bool = True,
    ) -> jax.Array:
        # K and V are BOTH projected from `key` via the joint kv weight;
        # `value` exists for torch-API parity and is ignored, matching the
        # reference (encdec_multihead_attn.py forward uses key for both).
        # Identity checks are unreliable under jit; documented instead.
        del value
        h, nh = self.embed_dim, self.num_heads
        d = h // nh
        b, sq, _ = query.shape
        sk = key.shape[1]
        dt = self.dtype

        x = query
        if self.include_norm_add:
            x = self.lyr_nrm(x.astype(jnp.float32))
        x = x.astype(dt)

        q = F.dense(
            x, self.in_proj_weight_q.astype(dt),
            self.in_proj_bias_q.astype(dt) if self.bias else None,
        )
        kv = F.dense(
            key.astype(dt), self.in_proj_weight_kv.astype(dt),
            self.in_proj_bias_kv.astype(dt) if self.bias else None,
        )
        k, v = jnp.split(kv, 2, axis=-1)
        q4 = q.reshape(b, sq, nh, d).transpose(0, 2, 1, 3)
        k4 = k.reshape(b, sk, nh, d).transpose(0, 2, 1, 3)
        v4 = v.reshape(b, sk, nh, d).transpose(0, 2, 1, 3)

        bias_ = _masks_to_bias(key_padding_mask, attn_mask, False, b, sq, sk)
        attn = _core_attention(
            self, q4, k4, v4, bias_,
            scale=d ** -0.5, dropout_rate=self.dropout,
            is_training=is_training, impl=self.impl,
            probs_bf16=self.probs_bf16,
        )
        attn = attn.transpose(0, 2, 1, 3).reshape(b, sq, h)
        out = F.dense(
            attn, self.out_proj_weight.astype(dt),
            self.out_proj_bias.astype(dt) if self.bias else None,
        )

        if self.include_norm_add:
            if self.dropout > 0.0 and is_training:
                out = nn.Dropout(self.dropout, deterministic=False)(out)
            out = out + query.astype(out.dtype)
        return out
