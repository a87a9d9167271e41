"""apex_tpu.ops — the Pallas kernel library + pure-jnp references.

TPU-native equivalents of the reference's CUDA kernel zoo (SURVEY.md §2.2):

- :mod:`apex_tpu.ops.layer_norm` — fused LayerNorm (ref fused_layer_norm_cuda)
- :mod:`apex_tpu.ops.softmax_xentropy` — fused softmax CE (ref xentropy_cuda)
- :mod:`apex_tpu.ops.attention` — flash attention (ref fast_*_multihead_attn)
- :mod:`apex_tpu.ops.mlp` — whole-MLP fused chain (ref mlp_cuda)
- :mod:`apex_tpu.ops.grouped_mm`, :mod:`apex_tpu.ops.moe_rows` — the expert
  layer's grouped products and row movement (no reference counterpart)
- :mod:`apex_tpu.ops.gated_delta` — the gated delta rule by chunks, the
  first sequential operator (no reference counterpart)
- :mod:`apex_tpu.ops.gated_conv` — the gated short convolution ``C *
  conv(B * X)``, read out of its projection's output in one kernel pass (no
  reference counterpart)
- :mod:`apex_tpu.ops.qk_heads` — q, k and v from a fused projection's output
  to heads-major, the per-head norm and the rotation on the way, in one
  kernel pass (heads of 128; no reference counterpart)
- :mod:`apex_tpu.ops.conv_bn` — fused matmul+BN-stats / BN-apply+matmul
  building blocks (ref groupbn/welford fused epilogues; library-only, see
  the module docstring for the measured RN50 verdict)

Every kernel ships with a pure-jnp reference implementation and is tested
kernel-vs-reference under identical inputs (the reference's L1 "extensions
vs Python build must match" harness, tests/L1/common/run_test.sh);
``qk_heads``'s reference is its callers' composed path
(``models/decoder.py::qkv_heads``).
"""
from apex_tpu.ops._common import force_pallas, mosaic_call_count  # noqa: F401
from apex_tpu.ops.layer_norm import layer_norm, layer_norm_ref  # noqa: F401
from apex_tpu.ops.softmax_xentropy import (  # noqa: F401
    softmax_cross_entropy,
    softmax_cross_entropy_ref,
)
from apex_tpu.ops.attention import attention_ref, flash_attention  # noqa: F401
from apex_tpu.ops.grouped_mm import grouped_matmul  # noqa: F401
from apex_tpu.ops.gated_delta import causal_conv1d_silu, gated_delta_rule  # noqa: F401
from apex_tpu.ops.gated_conv import gated_short_conv, gated_short_conv_ref  # noqa: F401
from apex_tpu.ops.qk_heads import qkv_heads  # noqa: F401
from apex_tpu.ops.mlp import mlp, mlp_ref  # noqa: F401
from apex_tpu.ops.conv_bn import bn_relu_matmul, matmul_stats  # noqa: F401
