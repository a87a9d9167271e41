"""Operations a model's forward and backward passes REQUIRE, from its
shapes — the numerator of ``model.mfu``.  Matrix multiplications and
attention only (2 operations per multiply-add); elementwise work, the
softmax and the optimizer are not counted, and recomputation never is.
The backward pass costs twice the forward.

``cfg`` is a configuration file of ``benchmark/configs`` as loaded.
"""
from __future__ import annotations

import json
import os
from typing import Dict

HERE = os.path.dirname(os.path.abspath(__file__))


def _block_forward(d: int, ffn: int, seq: int, causal: bool) -> float:
    """One transformer block, per token: QKV and output projections, the
    MLP, and attention's two matmuls over the keys a query may see — all
    ``seq`` of them, or on average half under a causal mask."""
    keys = (seq + 1) / 2 if causal else seq
    return 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ffn + 2 * 2 * keys * d


def gpt2_train_flops_per_token(cfg: Dict, seq: int) -> float:
    d, vocab = cfg["n_embd"], cfg["assumed"]["padded_vocab_size"]
    fwd = cfg["n_layer"] * _block_forward(d, 4 * d, seq, causal=True)
    return 3 * (fwd + 2 * d * vocab)


def bert_train_flops_per_token(cfg: Dict, seq: int) -> float:
    """The MLM head (transform and tied decoder) is counted at every
    position, as the plain reference computes it."""
    d, vocab = cfg["hidden_size"], cfg["assumed"]["padded_vocab_size"]
    fwd = cfg["num_hidden_layers"] * _block_forward(
        d, cfg["intermediate_size"], seq, causal=False)
    return 3 * (fwd + 2 * d * d + 2 * d * vocab)


def peaks(device_kind: str) -> Dict:
    """The peak rates of ``device_kind``; a device that is not in
    ``peaks.json`` is an error, never a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in peaks.json "
            f"(known: {sorted(table)})")
    return table[device_kind]


def mfu_percent(flops_per_token: float, tokens_per_s: float, chips: int,
                device_kind: str) -> float:
    peak = peaks(device_kind)["bf16_flops_per_s"]
    return 100.0 * flops_per_token * tokens_per_s / (chips * peak)
