"""apex_tpu.pyprof — profiling layer (pyprof parity).

ref: apex/pyprof/ (~5k LoC, three stages):

1. ``pyprof.nvtx.init()`` monkey-patches torch.* to emit NVTX markers with
   op name + arg shapes (apex/pyprof/nvtx/nvmarker.py:1-60);
2. ``python -m apex.pyprof.parse`` joins nvprof's SQLite kernel records to
   those markers (apex/pyprof/parse/parse.py);
3. ``python -m apex.pyprof.prof`` computes per-op FLOPs/bytes/efficiency
   with per-category formulas (apex/pyprof/prof/blas.py, conv.py, ...).

TPU re-design (SURVEY.md §5.1): no monkey-patching — XLA already carries
the full attribution chain:

1. **Markers**: ``jax.named_scope`` (and flax's automatic per-module
   scoping) stamp every HLO instruction's ``metadata.op_name`` with the
   scope path — the moral NVTX range.  :func:`annotate` /
   :func:`annotate_function` re-export that in the reference's vocabulary,
   and the library's hot paths are pre-annotated: the phases of a train
   step (flax's ``layer_i`` blocks plus ``embed``, ``lm_head``,
   ``lm_loss`` in the models, ``apex_amp_cast`` / ``apex_amp_step`` in
   ``amp``, each optimizer's ``named_update_scope`` inside that,
   ``apex_train_meters`` in the driver's scan body,
   ``apex_ddp_allreduce``, ``apex_sync_bn_stats``), and every Pallas
   kernel under its entry of ``apex_tpu.ops._common.KERNEL_NAMES``
   (``apex_flash_fwd``, ``apex_ln_bwd_dx``, ... — the custom call's own
   instruction name, whatever scope calls the kernel).
2. **Parse**: the compiled executable's optimized HLO text *is* the joined
   database — each instruction line has opcode, shapes, and the marker in
   ``metadata={op_name=...}``.  :func:`apex_tpu.pyprof.prof.parse_hlo`
   replaces the SQLite join.
3. **Prof**: :func:`apex_tpu.pyprof.prof.profile` computes per-instruction
   FLOPs (dot/conv from contraction shapes, elementwise/reductions from
   sizes) and bytes, aggregates by scope, and cross-checks totals against
   XLA's own ``compiled.cost_analysis()``.  CLI:
   ``python -m apex_tpu.pyprof.prof <hlo.txt>`` or
   ``ProfiledFunction.table()``.
"""
from contextlib import contextmanager
from functools import wraps

import jax

from apex_tpu.pyprof.prof import (  # noqa: F401
    Instruction,
    OpStats,
    parse_hlo,
    profile,
    profile_hlo,
)

__all__ = [
    "annotate",
    "annotate_function",
    "parse_hlo",
    "profile",
    "profile_hlo",
    "Instruction",
    "OpStats",
]


@contextmanager
def annotate(name: str):
    """Marker context (ref pyprof.nvtx: torch.cuda.nvtx.range_push/pop).

    Every op traced inside lands in HLO metadata as ``.../name/...`` and is
    aggregated under that scope by the profiler."""
    with jax.named_scope(name):
        yield


def annotate_function(name_or_fn):
    """Decorator form (ref nvmarker.py wraps every patched fn)."""

    def deco(fn, name):
        @wraps(fn)
        def wrapped(*args, **kwargs):
            with jax.named_scope(name):
                return fn(*args, **kwargs)

        return wrapped

    if callable(name_or_fn):
        return deco(name_or_fn, name_or_fn.__name__)
    return lambda fn: deco(fn, name_or_fn)
