"""BERT encoder — the FusedLAMB pretraining benchmark vehicle.

ref: the reference's LAMB/multihead-attn/xentropy kernels exist for NVIDIA's
BERT MLPerf recipe (SURVEY.md §2.3: DistributedFusedLAMB, fast_*_multihead_
attn, xentropy).  This model exercises every one of those TPU equivalents:
FusedLayerNorm (Pallas), flash attention (Pallas), fused MLP chain, fused
softmax-xentropy MLM loss, FusedLAMB optimizer.

Pre-LN vs post-LN: BERT is post-LN (LN after residual add) — matching the
reference's fused "norm-add" attention variants which fuse exactly that
residual+LN epilogue (apex/contrib/csrc/multihead_attn/*norm_add*).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp import functional as F
from apex_tpu.amp.layers import Dense
from apex_tpu.contrib.multihead_attn import SelfMultiheadAttn
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.ops.softmax_xentropy import softmax_cross_entropy
from apex_tpu.remat import remat_module


@dataclasses.dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30592  # BERT vocab 30522 padded to a multiple of 128
    # (MLPerf pads to 30528 = 64-aligned for Tensor Cores; TPU lanes are 128
    # wide, so the fused-xentropy kernel wants the next 128 multiple)
    hidden_size: int = 1024  # BERT-large
    num_layers: int = 24
    num_heads: int = 16
    intermediate_size: int = 4096
    max_position: int = 512
    type_vocab_size: int = 2
    dropout_rate: float = 0.1
    # attention-PROBABILITY dropout (ref BERT applies it in-kernel; the
    # flash kernel implements it in-kernel too, so this stays on the fast
    # path).  Default matches the reference recipe.
    attn_dropout_rate: float = 0.1
    # opt-in half-precision-probability dots in the flash kernel (the O3
    # philosophy applied in-kernel; see flash_attention's probs_bf16)
    probs_bf16: bool = False
    # activation rematerialization per encoder block: none | dots_saveable
    # | full_block (apex_tpu.remat; both keep the flash kernel's output
    # and lse)
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16
    tie_word_embeddings: bool = True  # MLPerf BERT ties decoder to embeddings

    @staticmethod
    def large(**kw) -> "BertConfig":
        return BertConfig(**kw)

    @staticmethod
    def base(**kw) -> "BertConfig":
        return BertConfig(
            hidden_size=768, num_layers=12, num_heads=12,
            intermediate_size=3072, **kw,
        )

    @staticmethod
    def tiny(**kw) -> "BertConfig":
        """For tests: 2 layers, 128 hidden."""
        return BertConfig(
            vocab_size=1024, hidden_size=128, num_layers=2, num_heads=2,
            intermediate_size=512, max_position=128, **kw,
        )


class BertLayer(nn.Module):
    cfg: BertConfig

    @nn.compact
    def __call__(self, x, mask_bias=None, deterministic: bool = True):
        cfg = self.cfg
        h = cfg.hidden_size
        dt = cfg.compute_dtype

        # the contrib MHA module: fast (flash) impl, additive mask path,
        # in-kernel probability dropout (stays on the flash fast path)
        attn = SelfMultiheadAttn(
            embed_dim=h,
            num_heads=cfg.num_heads,
            dropout=cfg.attn_dropout_rate,
            bias=True,
            mask_additive=True,
            impl="fast",
            probs_bf16=cfg.probs_bf16,
            dtype=dt,
            name="self_attn",
        )(
            x.astype(dt),
            key_padding_mask=mask_bias,
            is_training=not deterministic,
        )
        if not deterministic and cfg.dropout_rate > 0:
            attn = nn.Dropout(cfg.dropout_rate, deterministic=False)(attn)
        # post-LN residual (the reference's fused norm-add epilogue)
        x = FusedLayerNorm(h, name="attn_ln")(x.astype(jnp.float32) + attn.astype(jnp.float32))

        # scope ``norm_cast``: the float32 LayerNorms' output in compute dtype
        with jax.named_scope("norm_cast"):
            y = x.astype(dt)
        y = Dense(cfg.intermediate_size, dtype=dt, name="ffn_in")(y)
        y = jax.nn.gelu(y)
        y = Dense(h, dtype=dt, name="ffn_out")(y)
        if not deterministic and cfg.dropout_rate > 0:
            y = nn.Dropout(cfg.dropout_rate, deterministic=False)(y)
        x = FusedLayerNorm(h, name="ffn_ln")(x.astype(jnp.float32) + y.astype(jnp.float32))
        with jax.named_scope("norm_cast"):
            return x.astype(dt)


class BertEncoder(nn.Module):
    """Embeddings + transformer stack; returns final hidden states.

    setup-style so :meth:`attend` can reuse the word-embedding table for a
    tied MLM decoder (the MLPerf BERT recipe ties them).
    """

    cfg: BertConfig

    def setup(self):
        cfg = self.cfg
        h = cfg.hidden_size
        self.word_embeddings = nn.Embed(cfg.vocab_size, h, dtype=jnp.float32)
        self.position_embeddings = nn.Embed(cfg.max_position, h, dtype=jnp.float32)
        self.token_type_embeddings = nn.Embed(
            cfg.type_vocab_size, h, dtype=jnp.float32
        )
        self.embed_ln = FusedLayerNorm(h)
        # per-block remat (identity for "none"); deterministic is
        # static_argnum 3 (self=0, x=1, mask_bias=2) — called positionally
        layer_cls = remat_module(BertLayer, cfg.remat_policy,
                                 static_argnums=(3,))
        self.layers = [
            layer_cls(cfg, name=f"layer_{i}") for i in range(cfg.num_layers)
        ]

    def __call__(self, input_ids, token_type_ids=None, attention_mask=None,
                 deterministic: bool = True):
        cfg = self.cfg
        b, s = input_ids.shape
        with jax.named_scope("embed"):
            x = self.word_embeddings(input_ids) + self.position_embeddings(
                jnp.arange(s)[None, :]
            )
            if token_type_ids is not None:
                x = x + self.token_type_embeddings(token_type_ids)
            x = self.embed_ln(x)
        mask_bias = None
        if attention_mask is not None:
            # additive key-padding mask (B, Sk): 0 keep, -1e9 drop
            mask_bias = (1.0 - attention_mask.astype(jnp.float32)) * -1e9
        x = x.astype(cfg.compute_dtype)
        for layer in self.layers:
            x = layer(x, mask_bias, deterministic)
        return x

    def attend(self, x):
        """Tied decoder: hidden states -> vocab logits via the embedding
        table (nn.Embed.attend semantics).  The single biggest matmul in
        the model: runs in compute_dtype (bf16 under O2/O3; O1 recasts
        via the policy table; fp32 under O0) with fp32 accumulation so
        the logits keep full precision for the loss."""
        dt = self.cfg.compute_dtype
        return F.matmul(
            x.astype(dt), self.word_embeddings.embedding.T.astype(dt),
            preferred_element_type=jnp.float32,
        )


class BertForMLM(nn.Module):
    """Encoder + MLM head (tied to the embedding table when
    cfg.tie_word_embeddings, the MLPerf recipe) + fused xentropy loss."""

    cfg: BertConfig

    @nn.compact
    def __call__(self, input_ids, labels=None, attention_mask=None,
                 deterministic: bool = True):
        cfg = self.cfg
        encoder = BertEncoder(cfg, name="encoder")
        x = encoder(
            input_ids, attention_mask=attention_mask, deterministic=deterministic
        )
        # ``lm_head`` / ``lm_loss`` (and the encoder's ``embed``): the
        # phases a device trace sums time by, as in GPTLM
        with jax.named_scope("lm_head"):
            x = Dense(cfg.hidden_size, dtype=cfg.compute_dtype,
                      name="mlm_transform")(x.astype(cfg.compute_dtype))
            x = jax.nn.gelu(x)
            x = FusedLayerNorm(cfg.hidden_size, name="mlm_ln")(x)
            if cfg.tie_word_embeddings:
                logits = encoder.attend(x) + self.param(
                    "mlm_bias", nn.initializers.zeros, (cfg.vocab_size,),
                    jnp.float32
                )
            else:
                logits = Dense(cfg.vocab_size, dtype=cfg.compute_dtype,
                               name="mlm_head")(x)
        if labels is None:
            return logits
        with jax.named_scope("lm_loss"):
            # fused softmax-xentropy; ignore label -100 (masked-out
            # positions).
            valid = labels >= 0
            safe_labels = jnp.where(valid, labels, 0)
            # Under half-precision policies the loss takes the logits in
            # compute dtype and upcasts INSIDE (the reference xentropy
            # kernel's half_to_float=True mode) — at V=30592 the logits are
            # the model's largest activation, and halving their bytes is the
            # loss path's main cost; the softmax/lse math is fp32 either way.
            losses = softmax_cross_entropy(
                logits.astype(cfg.compute_dtype), safe_labels
            )
            loss = jnp.sum(losses * valid) / jnp.maximum(jnp.sum(valid), 1)
        return logits, loss
