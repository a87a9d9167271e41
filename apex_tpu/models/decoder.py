"""What the sparse decoder families share: the norm, the rotation, the
bias-free projection, the head-split around the flash kernels — and the three
taken together on the way from a fused projection to those kernels,
:func:`qkv_heads` —, the language-model shell and the masked loss.
``models/afmoe.py``, ``deepseek_v3.py``, ``qwen3_next.py``,
``smallthinker.py`` and ``lfm2.py`` each keep their configuration, their
mixers and their block; no family's
module imports another's — but ``kimi_linear.py``, whose full-attention
layers ARE ``deepseek_v3.py``'s latent mixer with the rotation off (one
switch there, ``rope_theta`` None) and whose ``A_log`` is drawn as
``qwen3_next.py`` draws its own.

A new family writes a config dataclass (with ``vocab_size``,
``hidden_size``, ``num_layers``, ``rms_norm_eps``, ``initializer_range``,
``remat_policy``, ``compute_dtype``), its mixers, one block ``Layer(cfg, index)`` called as
``layer(x, deterministic)``, and a :class:`DecoderLM` subclass that states
what differs from the defaults below.  What differs BETWEEN blocks — where
the norms sit, gates, what the router reads — stays in the block: shared
code that branched on those would be five blocks in one.

Expert parallelism enters a family as ``experts_held``: a model instance
holds that range of each expert layer's routed experts, routes over all of
them and computes its own experts' part (one chip's share before the
exchange; the exchange itself is not built yet — ROADMAP M2).  ``vocab_size``
is likewise whatever slice of the vocabulary is held.

Between a block's ``[q | k | v]`` projection and ``flash_attention`` lie a
cut, a per-head norm (two families), a rotation (most layers) and a
transposition to heads-major: no arithmetic to speak of, and composed of
:func:`split_heads`, :class:`RMSNorm` and :func:`rotary` four to six passes
over q and k in float32, run again by a recomputed block.  :func:`qkv_heads`
is that stretch as ONE call, and it takes one of two paths BY SHAPE: heads of
one lane tile (128) rotated whole or not at all go through
``ops/qk_heads.py``'s kernel pair, which reads the projection's output where
it lies and writes its gradient as one array (``afmoe.py``'s and
``smallthinker.py``'s layers at their published sizes); everything else —
heads of 64 (``lfm2.py``, ``granite_hybrid.py``, every family's tiny test
size), a rotation over part of a head and a zero-centred gain
(``qwen3_next.py``), the latent layers' 64-wide rotary part of a 192-wide head
(``deepseek_v3.py``) — is composed of the three as before, and those families
go on calling them directly.  One algorithm whose tiling wants a head to be
a lane tile: no model's name is asked.

The helpers are plain functions called inside a block's ``@nn.compact``
method, not flax modules: a module would add a segment to every parameter's
path and every ``op_name`` under it, and the benchmark's references map
parameters, and its trace readers scopes, by those paths.
"""
from __future__ import annotations

from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.amp import functional as F
from apex_tpu.amp.layers import Dense
from apex_tpu.ops import qk_heads
from apex_tpu.ops._common import pallas_default
from apex_tpu.ops.attention import flash_attention
from apex_tpu.ops.softmax_xentropy import softmax_cross_entropy
from apex_tpu.remat import remat_module

__all__ = ["DecoderLM", "RMSNorm", "causal_attention", "linear",
           "masked_token_mean_loss", "merge_heads", "qkv_heads", "rotary",
           "split_heads"]


class RMSNorm(nn.Module):
    """``x * rsqrt(mean(x^2) + eps) * w`` over the last axis, in float32
    (XLA's fusion: it merges with the residual add and the casts around it;
    no Pallas kernel).  ``w`` is ``scale``, initialised 1 — or, zero-centred
    (Qwen3-Next's form), ``1 + scale`` with ``scale`` initialised 0."""

    eps: float = 1e-5
    dtype: Any = jnp.float32
    zero_centred: bool = False

    @nn.compact
    def __call__(self, x):
        init = (nn.initializers.zeros_init() if self.zero_centred
                else nn.initializers.ones_init())
        scale = self.param("scale", init, (x.shape[-1],), jnp.float32)
        # inside the module: ``<flax name>/rms_norm`` reads under both
        with jax.named_scope("rms_norm"):
            x32 = x.astype(jnp.float32)
            normed = x32 * jax.lax.rsqrt(
                jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + self.eps)
            gain = scale.astype(jnp.float32)
            if self.zero_centred:
                gain = 1.0 + gain
            return (normed * gain).astype(self.dtype)


def linear(cfg, features: int, name: Optional[str] = None):
    """The families' one kind of projection: no bias, the config's compute
    dtype and initialiser.  Made inside the caller's ``@nn.compact`` method,
    its kernel sits at ``<caller>/<name>/kernel``."""
    return Dense(features, use_bias=False, dtype=cfg.compute_dtype,
                 kernel_init=nn.initializers.normal(cfg.initializer_range),
                 name=name)


class _NormGain(nn.Module):
    """An :class:`RMSNorm`'s parameter without its arithmetic, at the path
    ``RMSNorm(name=...)`` puts it: what :func:`qkv_heads` hands the kernels,
    which norm a head where they read it."""

    @nn.compact
    def __call__(self, features: int):
        return self.param("scale", nn.initializers.ones_init(), (features,),
                          jnp.float32)


def _rotary_tables(s: int, rot: int, theta: float):
    """``(cos, sin)`` (s, rot) float32 of the rotation's angles, position
    times ``inv_freq_j = theta^(-2j / rot)``, the two halves of ``rot``
    filled alike."""
    inv = theta ** (-jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], axis=-1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], axis=-1)
    return cos, sin


def rotary(x, theta: float, rot: Optional[int] = None):
    """Rotate the first ``rot`` dims of ``x`` (..., seq, D) by position — the
    whole head where ``rot`` is None — the two halves of those dims paired
    (``rotate_half``), ``inv_freq_j = theta^(-2j / rot)``; dims ``rot..`` pass
    untouched.  float32 inside, ``x``'s dtype out.  Scope ``rope``."""
    s, d = x.shape[-2], x.shape[-1]
    rot = d if rot is None else rot
    with jax.named_scope("rope"):
        cos, sin = _rotary_tables(s, rot, theta)
        # a whole head is neither sliced nor concatenated back: no copy
        head = (x if rot == d else x[..., :rot]).astype(jnp.float32)
        half = jnp.concatenate(
            [-head[..., rot // 2:], head[..., :rot // 2]], -1)
        out = (head * cos + half * sin).astype(x.dtype)
        return (out if rot == d
                else jnp.concatenate([out, x[..., rot:]], axis=-1))


def split_heads(t, n: int, hd: int):
    """(b, s, n * hd) — or (b, s, n, hd) already — to heads-major
    (b, n, s, hd), as the flash kernels take q, k and v.  Scope
    ``heads_layout``, as :func:`merge_heads`."""
    b, s = t.shape[:2]
    with jax.named_scope("heads_layout"):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)


def merge_heads(t):
    """Heads-major (b, n, s, hd) back to (b, s, n * hd)."""
    b, n, s, hd = t.shape
    with jax.named_scope("heads_layout"):
        return t.transpose(0, 2, 1, 3).reshape(b, s, n * hd)


def qkv_heads(qkv, hq: int, hk: int, hd: int, *,
              norm_eps: Optional[float] = None, zero_centred: bool = False,
              theta: Optional[float] = None, rot: Optional[int] = None):
    """``(q, k, v, rest)`` for the flash kernels from a block's fused
    projection ``qkv`` (b, s, W) laid out ``[q | k | v | rest]``: q (b, hq, s,
    hd), k and v (b, hk, s, hd) heads-major, ``rest`` (b, s, W - (hq + 2 hk)
    hd) as it lies (an output gate's columns) or None.  On the way q and k
    are normed over each head where ``norm_eps`` is given (two
    :class:`RMSNorm` s ``q_norm`` and ``k_norm`` in the caller's scope, its
    ``zero_centred`` form on request) and rotated by position where ``theta``
    is (:func:`rotary`, over the first ``rot`` dims).  Called inside a block's
    ``@nn.compact`` method.

    Two paths, chosen by what the shapes are, never by whose they are.  A
    head of ONE LANE TILE (128) rotated whole or not at all goes through
    ``ops/qk_heads.py``'s kernel pair on the TPU: one read of ``qkv`` where
    it lies and one write, and the projection's gradient written as one array
    (scope ``rope`` in a layer that rotates, ``heads_layout`` in one that does
    not; the parameters sit where the norms would put them).  Everything
    else — heads of 64, a rotation over part of a head, a zero-centred gain,
    any shape off the TPU — is composed of ``jnp.split`` (scope
    ``qkv_split``), :func:`split_heads`, :class:`RMSNorm` and :func:`rotary`,
    each under its own scope: what the kernels are tested against.  The
    counters ``ops.qk_heads.kernel`` / ``ops.qk_heads.composed``
    (``obs.default_registry()``) count the call sites traced each way."""
    from apex_tpu import obs

    s = qkv.shape[1]
    kernels = pallas_default(
        not zero_centred and qk_heads.supported(
            s, hq, hk, hd, rot if theta is not None else None))
    obs.default_registry().counter(
        "ops.qk_heads." + ("kernel" if kernels else "composed")).inc()
    if kernels:
        with jax.named_scope("heads_layout" if theta is None else "rope"):
            gains = None if norm_eps is None else (
                _NormGain(name="q_norm")(hd), _NormGain(name="k_norm")(hd))
            tables = None if theta is None else _rotary_tables(s, hd, theta)
            return qk_heads.qkv_heads(qkv, hq, hk, gains=gains, eps=norm_eps,
                                      tables=tables)
    cuts = [hq * hd, (hq + hk) * hd, (hq + 2 * hk) * hd]
    with jax.named_scope("qkv_split"):
        q, k, v, *rest = jnp.split(
            qkv, cuts if qkv.shape[-1] > cuts[-1] else cuts[:-1], axis=-1)
    def heads(t, n, name):
        t = split_heads(t, n, hd)
        if norm_eps is None:
            return t
        return RMSNorm(norm_eps, qkv.dtype, zero_centred, name=name)(t)

    # q whole, then k: the order the blocks had, whose gradient programs'
    # texts are pinned (tests/test_moe.py)
    q, k = heads(q, hq, "q_norm"), heads(k, hk, "k_norm")
    if theta is not None:
        q, k = rotary(q, theta, rot), rotary(k, theta, rot)
    return q, k, split_heads(v, hk, hd), (rest[0] if rest else None)


def causal_attention(q, k, v, *, window: Optional[int] = None,
                     scale: Optional[float] = None):
    """Causal flash attention over heads-major q (b, hq, s, d), k and v
    (b, hk, s, .), each key/value head serving ``hq / hk`` query heads; a
    query sees the last ``window`` keys where one is given, and the scores
    are multiplied by ``scale`` (None: ``d ** -0.5``).  The scopes
    ``attn_window`` / ``attn_full`` are what a device trace finds the
    kernels under."""
    with jax.named_scope("attn_full" if window is None else "attn_window"):
        return flash_attention(q, k, v, causal=True, scale=scale,
                               window=window)


def masked_token_mean_loss(logits, labels, dtype):
    """Mean fused-xentropy loss over the tokens whose label is >= 0 (-100:
    not predicted); 0, not NaN, where none is.  The loss takes the logits in
    the compute ``dtype`` (the reference xentropy kernel's half_to_float
    mode): at a vocabulary of tens of thousands the logits are the biggest
    activation, and the fused loss upcasts internally."""
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    per_tok = softmax_cross_entropy(logits.astype(dtype), safe)
    n = jnp.maximum(jnp.sum(valid), 1)
    return jnp.sum(jnp.where(valid, per_tok, 0.0)) / n


class DecoderLM(nn.Module):
    """Embedding (a float32 table), the blocks ``layer_<i>`` under the
    config's ``remat_policy``, a final RMSNorm ``norm_f`` and the head.
    Called as :class:`apex_tpu.models.gpt.GPTLM` is: ``model.apply({"params":
    p}, ids, labels=labels, deterministic=...)``; ``__call__(ids)`` returns
    (B, S, V) float32 logits, with ``labels`` (-100: not predicted) also the
    token-mean loss: ``(logits, loss)``.  Scopes ``embed``, ``lm_head``,
    ``lm_loss``: the phases of a step that no flax module names.

    A family subclasses it, annotates ``cfg`` with its config and states:
    """

    cfg: Any
    layer_cls = None        # the block: layer_cls(cfg, index, name=...)
    eps_field = "rms_norm_eps"  # the config's field with the norms' eps
    zero_centred = False    # the final norm's form (RMSNorm)
    tied_head = False       # logits = RMS(h) E^T, not a Dense named ``head``

    @staticmethod
    def validate(cfg) -> None:
        """Raise ValueError for a config the family cannot build."""

    @staticmethod
    def embed_scale(cfg) -> Optional[float]:
        """What the embedding's rows are multiplied by; None: nothing."""
        return None

    @staticmethod
    def logits_divisor(cfg) -> Optional[float]:
        """What the float32 logits are divided by; None: nothing."""
        return None

    def setup(self):
        cfg = self.cfg
        self.validate(cfg)
        self.embed = nn.Embed(
            cfg.vocab_size, cfg.hidden_size, dtype=jnp.float32,
            embedding_init=nn.initializers.normal(cfg.initializer_range))
        # deterministic is static_argnum 2 (self=0): called positionally
        layer_cls = remat_module(self.layer_cls, cfg.remat_policy,
                                 static_argnums=(2,))
        self.layers = [layer_cls(cfg, i, name=f"layer_{i}")
                       for i in range(cfg.num_layers)]
        self.norm_f = RMSNorm(getattr(cfg, self.eps_field), cfg.compute_dtype,
                              self.zero_centred)
        if not self.tied_head:
            self.head = linear(cfg, cfg.vocab_size)

    def __call__(self, input_ids, labels=None, deterministic: bool = True):
        cfg = self.cfg
        dt = cfg.compute_dtype
        with jax.named_scope("embed"):
            x = self.embed(input_ids)
            scale = self.embed_scale(cfg)
            if scale is not None:
                x = x * scale
            x = x.astype(dt)
        for layer in self.layers:
            x = layer(x, deterministic)
        x = self.norm_f(x)
        with jax.named_scope("lm_head"):
            if self.tied_head:
                # the embedding's rows are the head's columns
                logits = F.matmul(x.astype(dt),
                                  self.embed.embedding.T.astype(dt),
                                  preferred_element_type=jnp.float32)
            else:
                logits = self.head(x).astype(jnp.float32)
            divisor = self.logits_divisor(cfg)
            if divisor is not None:
                logits = logits / divisor
        if labels is None:
            return logits
        with jax.named_scope("lm_loss"):
            loss = masked_token_mean_loss(logits, labels, dt)
        return logits, loss
