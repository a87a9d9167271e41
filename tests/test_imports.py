"""Every module the package docstrings advertise must import (VERDICT r1:
phantom-module docstrings are worse than missing code)."""
import importlib

import pytest

ADVERTISED = [
    "apex_tpu",
    "apex_tpu.amp",
    "apex_tpu.amp.layers",
    "apex_tpu.amp.functional",
    "apex_tpu.amp.lists",
    "apex_tpu.optimizers",
    "apex_tpu.parallel",
    "apex_tpu.ops",
    "apex_tpu.multi_tensor",
    "apex_tpu.normalization",
    "apex_tpu.mlp",
    "apex_tpu.bf16_utils",
    "apex_tpu.reparameterization",
    "apex_tpu.RNN",
    "apex_tpu.pyprof",
    "apex_tpu.models",
    "apex_tpu.contrib",
    "apex_tpu.contrib.optimizers",
    "apex_tpu.contrib.multihead_attn",
    "apex_tpu.contrib.xentropy",
    "apex_tpu.contrib.groupbn",
    "apex_tpu.contrib.sparsity",
    "apex_tpu.checkpoint",
    "apex_tpu.chip",
    "apex_tpu.data",
    "apex_tpu.parallel.ring_attention",
    "apex_tpu.parallel.ulysses",
    "apex_tpu.ops.conv_bn",
    "apex_tpu.pyprof.parse",
    "apex_tpu.sharding",
    "apex_tpu.sharding.rules",
    "apex_tpu.sharding.apply",
    "apex_tpu.serve",
    "apex_tpu.serve.kv_cache",
    "apex_tpu.serve.decode",
    "apex_tpu.serve.engine",
    "apex_tpu.serve.sharding",
    "apex_tpu.serve.loadgen",
    "apex_tpu.obs",
    "apex_tpu.obs.metrics",
    "apex_tpu.obs.trace",
    "apex_tpu.obs.lifecycle",
    "apex_tpu.obs.export",
    "apex_tpu.obs.slo",
    "apex_tpu.obs.flightrec",
    "apex_tpu.obs.gangview",
    "apex_tpu.obs.aggregate",
    "apex_tpu.analysis",
    "apex_tpu.analysis.costs",
    "apex_tpu.resilience",
    "apex_tpu.resilience.faults",
    "apex_tpu.resilience.train",
    "apex_tpu.resilience.serve",
    "apex_tpu.fleet",
    "apex_tpu.fleet.serve",
    "apex_tpu.fleet.preflight",
    "apex_tpu.fleet.train",
]


@pytest.mark.parametrize("mod", ADVERTISED)
def test_advertised_module_imports(mod):
    importlib.import_module(mod)


def test_key_symbols():
    from apex_tpu.contrib.sparsity import ASP  # noqa: F401
    from apex_tpu.contrib.groupbn import BatchNorm2d_NHWC  # noqa: F401
    from apex_tpu.contrib.xentropy import SoftmaxCrossEntropyLoss  # noqa: F401
    from apex_tpu.contrib.multihead_attn import (  # noqa: F401
        EncdecMultiheadAttn,
        SelfMultiheadAttn,
    )
    from apex_tpu.reparameterization import apply_weight_norm  # noqa: F401
    from apex_tpu.bf16_utils import BF16_Optimizer  # noqa: F401
    from apex_tpu.contrib.optimizers import FP16_Optimizer  # noqa: F401
    from apex_tpu.parallel import (  # noqa: F401
        MoEMLP,
        TensorParallelMLP,
        pipeline_apply,
        ring_attention,
    )
    from apex_tpu.amp import maybe_print, set_verbosity  # noqa: F401
    from apex_tpu.amp.layers import Conv, ConvTranspose, Dense  # noqa: F401
