"""Shared plumbing for the Pallas kernel library."""
from __future__ import annotations

import contextlib
import re
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# Global override for the per-op ``use_pallas=None`` auto-selection.
# None = auto (kernel on TPU when shapes allow); True/False forces the
# choice wherever shapes allow.  This is the L1 harness's "run the same
# config with extensions on and off" switch (ref tests/L1/common/
# run_test.sh installs/uninstalls the CUDA extensions; here it's a flag).
_FORCE_PALLAS: Optional[bool] = None


def pallas_default(shape_ok: bool) -> bool:
    """Resolve ``use_pallas=None`` for an op whose shape gate is shape_ok.

    Auto-selects the kernel ONLY on TPU — must agree with pallas_call's
    interpret condition below, or non-TPU backends would silently run the
    Pallas interpreter on the hot path.  Off-TPU the op is therefore its
    jnp reference: right for the CPU test suite, wrong for anything that
    reports a device number — see :func:`mosaic_call_count`."""
    if _FORCE_PALLAS is not None:
        return _FORCE_PALLAS and shape_ok
    return shape_ok and jax.default_backend() == "tpu"


@contextlib.contextmanager
def force_pallas(value: Optional[bool]):
    """Context manager pinning the kernel-vs-reference choice (see above)."""
    global _FORCE_PALLAS
    prev = _FORCE_PALLAS
    _FORCE_PALLAS = value
    try:
        yield
    finally:
        _FORCE_PALLAS = prev


#: Every Pallas kernel of the library, by the ``name`` its ``pallas_call``
#: carries: ``apex_<op>_<pass>[_<variant>]``.  The compiled custom call's
#: HLO instruction — and so its event in a profiler trace — bears this
#: name whatever scope called the kernel.  Readers of a trace match the
#: family prefixes (``apex_flash_fwd``, ``apex_flash_bwd``, ``apex_ln_``,
#: ``apex_xent_``, ``apex_gmm``, ``apex_gdn_``, ``apex_ssd_``), so a variant can be added without
#: touching them — and a new family's names must contain none of them.
KERNEL_NAMES = (
    "apex_paged_attn",
    "apex_flash_fwd",
    "apex_flash_bwd_fused",
    "apex_flash_bwd_sweep",
    "apex_flash_bwd_dkdv",
    "apex_flash_bwd_dq_dbias",
    "apex_flash_bwd_dq",
    "apex_ln_fwd",
    "apex_ln_bwd_dx",
    "apex_ln_bwd_dx_dwdb",
    "apex_xent_fwd",
    "apex_xent_bwd",
    "apex_lamb_stage1",
    "apex_conv_bn_matmul_stats",
    "apex_conv_bn_relu_matmul",
    "apex_conv_bn_matmul_bwd",
    "apex_gmm_dw",
    "apex_gmm",
    "apex_moe_records",
    "apex_moe_gather",
    "apex_moe_combine_dw",
    "apex_moe_combine",
    "apex_gdn_fwd",
    "apex_gdn_bwd",
    "apex_kda_fwd",
    "apex_kda_bwd",
    "apex_conv1d_fwd",
    "apex_conv1d_bwd",
    "apex_gated_conv_fwd",
    "apex_gated_conv_bwd",
    "apex_ssd_fwd",
    "apex_ssd_bwd",
    "apex_qk_heads_fwd",
    "apex_qk_heads_bwd",
)


def pallas_call(*args, name: str, **kw):
    """pl.pallas_call under a stable ``name`` (one of :data:`KERNEL_NAMES`;
    a kernel without one cannot be found in a device trace), in interpreter
    mode off-TPU so the kernel-vs-reference parity tests run on CPU (the
    reference's Python-fallback testing trick, SURVEY §4).  An interpreted
    kernel lowers to plain XLA ops, so a program that landed on the CPU by
    accident still completes — slowly; :func:`mosaic_call_count` is how a
    caller refuses that."""
    if name not in KERNEL_NAMES:
        raise ValueError(
            f"pallas_call name {name!r} is not listed in KERNEL_NAMES")
    return pl.pallas_call(*args, name=name,
                          interpret=jax.default_backend() != "tpu", **kw)


_MOSAIC_INSTR_RE = re.compile(
    r'%([\w.\-]+) = [^\n]*custom_call_target="tpu_custom_call"')


def mosaic_call_names(text: str):
    """The HLO instruction names of the Mosaic custom calls in a compiled
    program's text (``compiled.as_text()``), in order."""
    return _MOSAIC_INSTR_RE.findall(text)


def unnamed_mosaic_calls(text: str):
    """Those of :func:`mosaic_call_names` that bear no name from
    :data:`KERNEL_NAMES`: a kernel that would be anonymous in a device
    trace.  Called from inside any scope — a model always is — a kernel's
    instruction is its own name plus XLA's ``.N``; differentiated with no
    scope around it JAX wraps the name itself (``jvp_apex_xent_fwd_``),
    so the test is containment."""
    return [n for n in mosaic_call_names(text)
            if not any(k in n for k in KERNEL_NAMES)]


def mosaic_call_count(compiled) -> int:
    """Mosaic kernels in a compiled executable (``jit(f).lower(...)
    .compile()``): the ``tpu_custom_call``s in its text.

    The two gates above pick the reference or the interpreter off-TPU
    without a word, and a shape gate can pick the reference on it.  What
    was COMPILED is the one place that shows the choice, so a program
    that claims the chip (``chip_smoke.py``) checks the device up front
    (:func:`apex_tpu.chip.require_tpu`) and then this count: zero where a
    kernel was promised means it ran interpreted or as its reference."""
    return compiled.as_text().count("tpu_custom_call")


def auto_block(dim: int, cap: int, floor: int = 128) -> int:
    """Largest power-of-two block <= cap that tiles dim; ``floor`` minimum
    (one shared tiling heuristic for every kernel's auto block pick)."""
    b = cap
    while b > floor and dim % b != 0:
        b //= 2
    return b


def pad_rows(x, block_rows: int):
    """Pad the leading axis up to a multiple of block_rows.

    Returns (padded, original_rows).  Padded rows compute garbage that the
    caller slices off; kernels must not reduce across the row axis.
    """
    m = x.shape[0]
    pad = (-m) % block_rows
    if pad:
        x = jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1))
    return x, m
