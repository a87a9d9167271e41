"""Precision lint — dtype-propagation checks on a closed jaxpr.

The AMP contract (:mod:`apex_tpu.amp`) is that HALF precision is an
*operand* format, never an *accumulation* format: softmax statistics,
layer-norm moments, loss reductions and cross-replica gradient sums all
run in fp32 even when every matmul input is bf16, and under O1/O2 the
optimizer's fp32 master copies are never silently narrowed.  All of
that is statically visible in the traced jaxpr — every equation carries
input/output avals — so this module walks the jaxpr (recursing into
``scan``/``while``/``cond``/``pjit``/``shard_map``/``remat``
sub-jaxprs) and flags the half-precision patterns that jnp itself can
never emit (``jnp`` reductions upcast f16/bf16 internally): a hit is
always lax-level or kernel-level code that dropped the fp32 discipline.

Rules (``Violation.rule``):

- ``half-loss-reduction`` — a ``reduce_sum``/``reduce_max``/
  ``reduce_min``/``reduce_prod``/``reduce`` collapsing to a SCALAR with
  a half-precision input or output: a loss (or logsumexp) accumulated
  in half.  Batch-axis sums of bf16 *gradients* (standard O2, matching
  the reference's half grads) have non-scalar outputs and do not fire.
- ``half-softmax`` — ``exp`` on a half-precision operand: softmax /
  logsumexp internals must subtract the max and exponentiate in fp32
  (generalizes the one-off ``tests/test_attention_probs_bf16.py``
  assertions — the *opt-in* ``probs_bf16`` mode rounds the already-
  normalized probabilities, never the exp/sum statistics).
- ``half-norm-stats`` — ``rsqrt`` on a half-precision operand: a
  layer-norm/RMS variance path computed in half.
- ``half-psum`` — a ``psum``/``pmean``/``all_gather``-family collective
  with a half-precision operand of at least ``min_psum_bytes``: a
  cross-replica gradient accumulation in half
  (``DistributedDataParallel(allreduce_always_fp32=True)`` is the
  library discipline).
- ``master-downcast`` (:func:`lint_step` only) — a carry leaf that
  enters fp32 and leaves half under a policy with master weights (O1's
  implicit / O2's explicit fp32 masters): the optimizer narrowed its
  own state, the exact silent-downcast Apex exists to prevent.

``tools/lint_graphs.py`` runs this over the canonical driver/serve
programs; ``tests/test_analysis.py`` seeds each rule.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, List, Mapping, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.extend.core import ClosedJaxpr, Jaxpr

__all__ = [
    "PrecisionError",
    "Violation",
    "assert_precision",
    "lint_fn",
    "lint_jaxpr",
    "lint_step",
]

_HALF_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))

# scalar-accumulation reductions (the generic `reduce` is what
# lax.reduce(..., lax.add) traces to — jnp never emits it in half)
_REDUCE_PRIMS = ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                 "reduce")
# cross-replica accumulations: pmean traces to psum + div, so psum is
# the one that matters; the gather/scatter pair covers the ZeRO path
_COLLECTIVE_PRIMS = ("psum", "psum2", "pmean", "psum_scatter",
                     "reduce_scatter", "all_gather", "all_reduce")


class PrecisionError(AssertionError):
    """Raised by :func:`assert_precision` with the violation report."""


@dataclasses.dataclass(frozen=True)
class Violation:
    """One precision-lint finding.

    ``rule`` names the invariant (see module docstring), ``primitive``
    the offending jaxpr equation, ``dtype`` the half dtype observed,
    ``where`` the source location jax recorded for the equation (best
    effort — empty when unavailable), ``context`` the enclosing
    higher-order primitives (``pjit/scan/...``).
    """

    rule: str
    primitive: str
    dtype: str
    message: str
    where: str = ""
    context: str = ""

    def __str__(self):
        loc = f" [{self.where}]" if self.where else ""
        ctx = f" (inside {self.context})" if self.context else ""
        return f"{self.rule}: {self.message}{ctx}{loc}"


def _is_half(aval) -> bool:
    return getattr(aval, "dtype", None) in _HALF_DTYPES


def _aval_bytes(aval) -> int:
    try:
        return int(aval.size) * aval.dtype.itemsize
    except Exception:
        return 0


def _source(eqn) -> str:
    try:
        from jax._src import source_info_util

        frame = source_info_util.user_frame(eqn.source_info)
        if frame is None:
            return ""
        return f"{frame.file_name}:{frame.start_line}"
    except Exception:
        return ""


def _sub_jaxprs(params):
    """Jaxprs nested in an equation's params (scan/cond/pjit/shard_map/
    custom_vjp/remat all stash theirs under different keys — duck-walk
    every value instead of keying on primitive names)."""
    for v in params.values():
        items = v if isinstance(v, (list, tuple)) else (v,)
        for item in items:
            if isinstance(item, ClosedJaxpr):
                yield item.jaxpr
            elif isinstance(item, Jaxpr):
                yield item


def lint_jaxpr(
    closed_jaxpr,
    *,
    policy=None,
    min_psum_bytes: int = 0,
    allow: Sequence[str] = (),
    half_collective_bytes: Optional[Mapping[str, int]] = None,
) -> List[Violation]:
    """Lint a ``jax.make_jaxpr`` result (or raw ``Jaxpr``) against the
    half-precision accumulation rules.

    ``policy`` is accepted for symmetry with :func:`lint_step` (the
    jaxpr rules are policy-independent: a half accumulation is wrong
    under every opt level — O3 keeps *operands* half, not statistics).
    ``min_psum_bytes`` filters the ``half-psum`` rule to gradient-sized
    payloads (scalar half flag/metric psums below it pass).  ``allow``
    suppresses rule names, for programs with a documented exception.

    ``half_collective_bytes`` is the budget-derived allow-list for
    DELIBERATE half-width collectives (ISSUE 16's compressed bf16
    gradient exchange): ``{hlo_kind: exact_operand_bytes}`` (e.g.
    ``{"all_reduce": GRAD_BYTES // 2}``, from a
    :class:`~apex_tpu.analysis.collectives.CollectiveBudget` whose
    ``half_ok`` names the kind).  A half-dtype collective is exempted
    ONLY when its operand bytes exactly match the declared payload for
    its kind — any other half collective still violates, so this is a
    per-payload contract, not a blanket ``allow=("half-psum",)``.
    """
    del policy  # reserved: rules below are opt-level independent
    jaxpr = getattr(closed_jaxpr, "jaxpr", closed_jaxpr)
    out: List[Violation] = []
    allowed = frozenset(allow)
    half_declared = dict(half_collective_bytes or {})
    # jaxpr primitive -> lowered-HLO kind (the budget's vocabulary)
    prim_kind = {
        "psum": "all_reduce", "psum2": "all_reduce",
        "pmean": "all_reduce", "all_reduce": "all_reduce",
        "psum_scatter": "reduce_scatter",
        "reduce_scatter": "reduce_scatter",
        "all_gather": "all_gather",
    }

    def emit(rule, eqn, dtype, msg, context):
        if rule in allowed:
            return
        out.append(Violation(
            rule=rule, primitive=eqn.primitive.name, dtype=str(dtype),
            message=msg, where=_source(eqn), context=context,
        ))

    def walk(jaxpr, context):
        for eqn in jaxpr.eqns:
            name = eqn.primitive.name
            in_avals = [v.aval for v in eqn.invars
                        if hasattr(v.aval, "dtype")]
            out_avals = [v.aval for v in eqn.outvars
                         if hasattr(v.aval, "dtype")]
            half_in = next((a for a in in_avals if _is_half(a)), None)
            half_out = next((a for a in out_avals if _is_half(a)), None)
            if name in _REDUCE_PRIMS and (half_in or half_out):
                if out_avals and all(
                    getattr(a, "ndim", 1) == 0 or a.size == 1
                    for a in out_avals
                ):
                    a = half_out or half_in
                    emit(
                        "half-loss-reduction", eqn, a.dtype,
                        f"{name} collapses to a scalar with "
                        f"{a.dtype} input/output — losses accumulate "
                        "in fp32 (cast after the reduction, not before)",
                        context,
                    )
            elif name == "exp" and half_in is not None:
                emit(
                    "half-softmax", eqn, half_in.dtype,
                    f"exp on {half_in.dtype} — softmax/logsumexp "
                    "statistics must be computed in fp32 "
                    "(probs_bf16 rounds probabilities AFTER the "
                    "fp32 normalization)",
                    context,
                )
            elif name == "rsqrt" and half_in is not None:
                emit(
                    "half-norm-stats", eqn, half_in.dtype,
                    f"rsqrt on {half_in.dtype} — layer-norm/RMS "
                    "variance paths must be fp32 (keep_batchnorm_fp32 "
                    "is the same rule for BN)",
                    context,
                )
            elif name in _COLLECTIVE_PRIMS and half_in is not None:
                nbytes = _aval_bytes(half_in)
                kind = prim_kind.get(name)
                declared = (
                    kind is not None
                    and half_declared.get(kind) == nbytes
                )
                if nbytes >= min_psum_bytes and not declared:
                    emit(
                        "half-psum", eqn, half_in.dtype,
                        f"{name} accumulates {half_in.dtype} across "
                        "replicas — gradient collectives run in fp32 "
                        "(DistributedDataParallel "
                        "allreduce_always_fp32)",
                        context,
                    )
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, f"{context}/{name}" if context else name)

    walk(jaxpr, "")
    return out


def lint_fn(fn: Callable, *args, policy=None, min_psum_bytes: int = 0,
            allow: Sequence[str] = (),
            half_collective_bytes: Optional[Mapping[str, int]] = None,
            **kwargs) -> List[Violation]:
    """Trace ``fn(*args, **kwargs)`` and lint the resulting jaxpr."""
    closed = jax.make_jaxpr(lambda *a: fn(*a, **kwargs))(*args)
    return lint_jaxpr(closed, policy=policy,
                      min_psum_bytes=min_psum_bytes, allow=allow,
                      half_collective_bytes=half_collective_bytes)


def _carry_downcasts(carry, out_carry_shapes) -> List[Tuple[str, Any, Any]]:
    """(path, in_dtype, out_dtype) for carry leaves narrowed f32 -> half."""
    flat_in = jax.tree_util.tree_flatten_with_path(carry)[0]
    flat_out = jax.tree_util.tree_leaves(out_carry_shapes)
    found = []
    if len(flat_in) != len(flat_out):
        return found  # structure changed: not a dtype lint's business
    for (path, leaf_in), leaf_out in zip(flat_in, flat_out):
        din = getattr(leaf_in, "dtype", None)
        dout = getattr(leaf_out, "dtype", None)
        if din == jnp.dtype(jnp.float32) and dout in _HALF_DTYPES:
            found.append((jax.tree_util.keystr(path), din, dout))
    return found


def lint_step(
    step_fn: Callable,
    carry,
    batch=None,
    *,
    policy=None,
    min_psum_bytes: int = 0,
    allow: Sequence[str] = (),
) -> List[Violation]:
    """Lint a driver-shaped ``step_fn(carry, batch) -> (carry, metrics)``.

    Runs :func:`lint_jaxpr` on the traced step, then the carry-level
    ``master-downcast`` rule: with master weights in play (``policy``
    is None, or O1's ``master_weights=None``, or O2's ``True`` — only
    an explicit ``False`` opts out), any carry leaf that enters fp32
    and leaves bf16/fp16 is flagged.  That is the optimizer narrowing
    its own persistent state — one window later the "fp32 masters" are
    reconstructed from half, which is exactly the silent accuracy bug
    master weights exist to prevent (a structure change between input
    and output carry is left to the driver's own errors).
    """
    violations = lint_fn(step_fn, carry, batch, policy=policy,
                         min_psum_bytes=min_psum_bytes, allow=allow)
    masters = policy is None or policy.master_weights is not False
    if masters and "master-downcast" not in frozenset(allow):
        out_shapes = jax.eval_shape(step_fn, carry, batch)[0]
        for path, din, dout in _carry_downcasts(carry, out_shapes):
            violations.append(Violation(
                rule="master-downcast", primitive="<carry>",
                dtype=str(dout),
                message=(
                    f"carry leaf {path or '<root>'} enters {din} and "
                    f"leaves {dout} — fp32 master/optimizer state was "
                    "silently narrowed (cast model params at USE, "
                    "never in the stored state)"
                ),
            ))
    return violations


def assert_precision(violations: List[Violation], label: str = "program"):
    """Raise :class:`PrecisionError` listing ``violations`` (no-op when
    clean) — the one-line gate tests and ``lint_graphs`` call."""
    if violations:
        lines = "\n  ".join(str(v) for v in violations)
        raise PrecisionError(
            f"{label}: {len(violations)} precision violation(s):\n  {lines}"
        )
