"""apex_tpu.models — the benchmark/example model zoo.

These are the models the reference's examples and kernels exist to serve
(SURVEY.md §6 benchmark configs): ResNet-50 (imagenet amp O0-O3 + DDP +
SyncBN), BERT-large (FusedLAMB + fused attention + xentropy), DCGAN
(multi-model multi-loss-scaler amp), a simple MLP (the minimum
end-to-end slice), and the eight decoders: GPT-2 (``gpt.py``), the Arcee
Trinity block with sigmoid-routed experts (``afmoe.py``, training path), the
Qwen3-Next block — gated-delta-rule linear attention beside gated full
attention, softmax-routed experts (``qwen3_next.py``, training path) — and
the DeepSeek-V3 block as Moonlight publishes it — multi-head latent attention
(a low-rank key/value latent, one rotary key for all heads, values narrower
than keys) and bias-steered sigmoid-routed experts (``deepseek_v3.py``,
training path) — and the SmallThinker block — a router that reads the block's
input ahead of attention, ReGLU experts with no shared one, a position-free
full layer before rotary window layers (``smallthinker.py``, training path) —
and the LFM2 block — a gated short convolution in place of attention in three
layers of four, grouped-query attention with normed queries and keys in the
fourth, bias-steered sigmoid experts with none shared, the head tied to the
embedding (``lfm2.py``, training path) — and the Granite 4.0-H block — a
Mamba-2 state-space layer (the SSD scan by chunks) in nine layers of ten,
position-free grouped-query attention at a published scale in the tenth, a
dense SwiGLU in every layer, four multipliers, no experts
(``granite_hybrid.py``, training path) — and the Kimi Linear block — Kimi
Delta Attention (the delta rule with a decay a key channel behind short
convolutions, a low-rank decay gate and a low-rank output gate) in the layers
a published list names, position-free latent attention in the others, a
leading dense layer, bias-steered sigmoid experts beside a shared one
(``kimi_linear.py``, training path).
"""
from apex_tpu.models.resnet import ResNet, resnet50, resnet101, resnet152  # noqa: F401
from apex_tpu.models.bert import (  # noqa: F401
    BertConfig,
    BertEncoder,
    BertForMLM,
    BertLayer,
)
from apex_tpu.models.dcgan import Discriminator, Generator  # noqa: F401
from apex_tpu.models.gpt import GPTConfig, GPTLayer, GPTLM  # noqa: F401
from apex_tpu.models.afmoe import AfmoeConfig, AfmoeLayer, AfmoeLM  # noqa: F401
from apex_tpu.models.qwen3_next import (  # noqa: F401
    Qwen3NextConfig,
    Qwen3NextLayer,
    Qwen3NextLM,
)
from apex_tpu.models.deepseek_v3 import (  # noqa: F401
    DeepseekV3Config,
    DeepseekV3Layer,
    DeepseekV3LM,
)
from apex_tpu.models.smallthinker import (  # noqa: F401
    SmallThinkerConfig,
    SmallThinkerLayer,
    SmallThinkerLM,
)
from apex_tpu.models.lfm2 import Lfm2Config, Lfm2Layer, Lfm2LM  # noqa: F401
from apex_tpu.models.granite_hybrid import (  # noqa: F401
    GraniteHybridConfig,
    GraniteHybridLayer,
    GraniteHybridLM,
    Mamba2Mixer,
)
from apex_tpu.models.kimi_linear import (  # noqa: F401
    KimiDeltaAttention,
    KimiLinearConfig,
    KimiLinearLayer,
    KimiLinearLM,
)
from apex_tpu.mlp import MLP  # noqa: F401
