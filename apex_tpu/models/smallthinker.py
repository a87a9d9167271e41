"""SmallThinker decoder LM (PowerInfer's ``smallthinker`` family) on the
training path.

The block: two RMSNorms in pre-norm position, grouped-query flash attention
(seven query heads a key/value head at the published sizes) over a rotary
sliding window in three layers of four and over the whole causal context, WITH
NO POSITION SIGNAL, in the fourth — which comes first in a period — and in
every layer routed experts of ReGLU units with no shared expert
(``parallel/moe.py::ExpertShardMLP``).  THE ROUTER READS THE BLOCK'S INPUT,
ahead of the input norm and attention; the experts read the normed stream
after attention.  The routing plan (the top-k, the sorts and the integer
tables of ``parallel/moe.py::_route``) therefore depends on nothing attention
computes: nothing here schedules it, the data flow alone lets the compiler
place it beside the attention kernels.

Per block ``i``, input ``x`` (no biases anywhere; RMSNorm eps 1e-6)::

    r = x W_r                                   # float32 logits over all experts
    y = RMS_1(x);  q, k, v = y W_q, y W_k, y W_v
    if rope_layout[i]: q, k = rotary(q), rotary(k)   # whole head, rotate_half
    a = softmax(q k^T / sqrt(D), causal [, i - j < window if sliding_window_layout[i]]) v
    h = x + a W_o
    u = RMS_2(h)
    (s, e) = top_k(r);  w = softmax(s)          # over the picked logits
    out = h + sum_j w_j W_down[e_j] (relu(W_gate[e_j] u) * (W_up[e_j] u))

``w`` is computed as ``softmax_topk_routing(r, k, norm_topk_prob=True)``: a
softmax over all experts, its ``k`` largest renormalised — ``exp(r_i) /
sum_picked exp(r_j)``, the same number (``tests/test_smallthinker.py``
holds the two orders to each other).  ``h = E[ids]`` (no scale), ``logits =
W_head RMS(h)``, the head untied.

From ``qkv``'s output to the flash kernels — the cut, the rotation where a
layer has one, the way to heads-major — is one call,
``models/decoder.py::qkv_heads``, which chooses by shape: at the published
head size, 128 (one lane tile), every layer goes through
``ops/qk_heads.py``'s kernel pair on the TPU (scope ``rope`` in a rotating
layer; ``heads_layout`` in the first of a period, where the pair is a plain
transposition that still writes ``qkv``'s gradient as one array); at this
file's tiny test size, heads of 64, and off the TPU the same call is
composed of ``jnp.split``, ``split_heads`` and ``rotary``.

Left out: what the model's description calls secondary experts — a
predictor of which of an expert's neurons fire, by which inference from
slow storage skips the rows of the down-projection that ``relu`` zeroed.
The published configuration holds no key of it and training computes the
dense unit.  Rotary scaling (null in the configuration; the declared
positions are native).

The shell, how it is called and how expert parallelism enters
(``experts_held``, a sliced ``vocab_size``): ``models/decoder.py``.  Scopes
``moe_router`` (under it only what reads the block's input: the scores and
the top-k), ``attn_full`` / ``attn_window`` (the flash call),
``moe_dispatch``, ``moe_experts``, ``embed``, ``lm_head``, ``lm_loss``.
Serving methods are not part of this model yet: window layers' pages are
ROADMAP M4's.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from apex_tpu.models.decoder import (DecoderLM, RMSNorm, causal_attention,
                                     linear, merge_heads, qkv_heads)
from apex_tpu.parallel.moe import ExpertShardMLP

__all__ = ["SmallThinkerConfig", "SmallThinkerLayer", "SmallThinkerLM"]


@dataclasses.dataclass(frozen=True)
class SmallThinkerConfig:
    vocab_size: int = 19072           # the slice held, padded to 128
    hidden_size: int = 2560
    # a layer a flag: 1 = a sliding window / rotary positions, 0 = the whole
    # causal context / no position signal (the published pair of lists)
    sliding_window_layout: Tuple[int, ...] = (0, 1, 1, 1)
    rope_layout: Tuple[int, ...] = (0, 1, 1, 1)
    num_heads: int = 28
    num_kv_heads: int = 4
    head_dim: int = 128
    sliding_window_size: int = 4096
    rope_theta: float = 1.5e6
    moe_ffn_hidden_size: int = 768    # one expert
    num_experts: int = 64             # routed over
    experts_held: Tuple[int, int] = (0, 8)
    num_experts_per_tok: int = 6
    norm_topk_prob: bool = True
    rms_norm_eps: float = 1e-6
    initializer_range: float = 0.02
    # activation rematerialization per block (apex_tpu.remat): full_block
    # keeps a block's input and the flash kernel's output and lse
    remat_policy: str = "none"
    compute_dtype: Any = jnp.bfloat16

    @property
    def num_layers(self) -> int:
        return len(self.sliding_window_layout)

    @staticmethod
    def tiny(**kw) -> "SmallThinkerConfig":
        """For tests: every mechanism at toy widths — a full layer first,
        three query heads a key/value head, a window shorter than a row."""
        base = dict(
            vocab_size=256, hidden_size=128,
            sliding_window_layout=(0, 1, 1), rope_layout=(0, 1, 1),
            num_heads=6, num_kv_heads=2, head_dim=64, sliding_window_size=48,
            moe_ffn_hidden_size=128, num_experts=16, experts_held=(0, 4),
            num_experts_per_tok=4)
        base.update(kw)
        return SmallThinkerConfig(**base)


class SmallThinkerLayer(nn.Module):
    """One block; ``index`` picks its attention (``cfg.sliding_window_layout``)
    and whether q and k are rotated (``cfg.rope_layout``)."""

    cfg: SmallThinkerConfig
    index: int

    @nn.compact
    def __call__(self, x, deterministic: bool = True):
        del deterministic           # no dropout in this family
        cfg = self.cfg
        b, s, d = x.shape
        hq, hk, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        dt = cfg.compute_dtype
        init = nn.initializers.normal(cfg.initializer_range)
        norm = lambda name: RMSNorm(cfg.rms_norm_eps, dt, name=name)
        windowed = bool(cfg.sliding_window_layout[self.index])

        y = norm("input_norm")(x)
        qkv = linear(cfg, (hq + 2 * hk) * hd, "qkv")(y)
        q, k, v, _ = qkv_heads(
            qkv, hq, hk, hd,
            theta=cfg.rope_theta if cfg.rope_layout[self.index] else None)
        attn = causal_attention(
            q, k, v, window=cfg.sliding_window_size if windowed else None)
        h = x + linear(cfg, d, "o_proj")(merge_heads(attn))

        # the router scores the block's INPUT x, the experts take the normed
        # stream after attention
        ff = ExpertShardMLP(
            num_experts=cfg.num_experts, experts_held=cfg.experts_held,
            d_ff=cfg.moe_ffn_hidden_size, k=cfg.num_experts_per_tok,
            route_norm=cfg.norm_topk_prob, score_func="softmax",
            unit_func="relu", compute_dtype=dt, kernel_init=init, name="moe",
        )(norm("post_attn_norm")(h).reshape(b * s, d),
          router_input=x.reshape(b * s, d))
        return h + ff.reshape(b, s, d)


class SmallThinkerLM(DecoderLM):
    """The shell as it stands: embeddings not scaled, the head untied."""

    cfg: SmallThinkerConfig
    layer_cls = SmallThinkerLayer

    @staticmethod
    def validate(cfg):
        if len(cfg.rope_layout) != len(cfg.sliding_window_layout):
            raise ValueError(
                f"rope_layout has {len(cfg.rope_layout)} layers, "
                f"sliding_window_layout {len(cfg.sliding_window_layout)}")
