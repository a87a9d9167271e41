"""Collective census + declarative budgets on lowered StableHLO.

Promoted from ``tools/inspect_hlo.py`` (PR 2), which remains as a thin
CLI shim.  TPU access is flaky (PERF.md r5), so the communication
contracts — ALL cross-replica gradient traffic deferred to ONE
collective per accumulation boundary, a K-invariant decode-window
census — are proven hardware-free from the *lowered* StableHLO text of
the program (``driver.lower(...).as_text()``): every ``lax.psum`` /
``psum_scatter`` / ``all_gather`` in the traced step appears there
exactly once per traced call site (the scan body is emitted once
regardless of trip count, and the microbatch loop is unrolled precisely
so a per-microbatch regression shows up as M ops).

Two layers:

- the census primitives (:func:`parse_collectives`,
  :func:`collective_summary`, :func:`gradient_collective_bytes`) and
  the PR-2 boundary contract (:func:`assert_boundary_collectives`);
- declarative :class:`CollectiveBudget` checks — per-program expected
  counts/bytes per op class, consumed by ``tests/test_analysis.py``
  and ``tools/lint_graphs.py`` so a new program states its
  communication contract as data instead of a bespoke assertion.

Used by:
- tests/test_inspect_hlo.py (tier-1): exactly one gradient all-reduce
  (or one reduce-scatter + all-gather pair for ``zero=True``) per
  boundary, for M in {2, 4}, and collective bytes per sample falling
  M-fold.

CLI (via the shim)::

    python tools/inspect_hlo.py <stablehlo.txt>     # or - for stdin
    ... | python tools/inspect_hlo.py --min-bytes 1024 -
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Any, Dict, List, Mapping, NamedTuple, Optional, Tuple

__all__ = [
    "COLLECTIVE_OPS",
    "BudgetError",
    "Collective",
    "CollectiveBudget",
    "assert_boundary_collectives",
    "assert_budget",
    "boundary_budget",
    "check_budget",
    "collective_summary",
    "compiled_memory",
    "gradient_collective_bytes",
    "parse_collectives",
]

COLLECTIVE_OPS = (
    "all_reduce",
    "reduce_scatter",
    "all_gather",
    "all_to_all",
    "collective_permute",
)

_OP_RE = re.compile(
    r'"stablehlo\.(%s)"' % "|".join(COLLECTIVE_OPS)
)
# the op's function-type trailer: `: (operand types) -> result type(s)`.
# For region-carrying ops (all_reduce/reduce_scatter) it follows the
# region close a few lines down; region bodies contain no `: (...) ->`
# shaped text, so the first match after the op name is this op's own.
_SIG_RE = re.compile(r":\s*\(([^)]*)\)\s*->\s*([^\n]+)")
_TENSOR_RE = re.compile(r"tensor<([^>]*)>")

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2,
    "f8E4M3FN": 1, "f8E5M2": 1, "f8E4M3B11FNUZ": 1,
    "i64": 8, "ui64": 8, "i32": 4, "ui32": 4,
    "i16": 2, "ui16": 2, "i8": 1, "ui8": 1, "i1": 1,
    "c64": 8, "c128": 16,
}


def _tensor_bytes(spec: str) -> int:
    """Bytes of one ``tensor<...>`` type, e.g. ``4x8xf32`` or ``f32``."""
    parts = spec.strip().split("x")
    dtype = parts[-1]
    if dtype not in _DTYPE_BYTES:
        raise ValueError(f"unknown element type in tensor<{spec}>")
    n = 1
    for d in parts[:-1]:
        n *= int(d)
    return n * _DTYPE_BYTES[dtype]


class Collective(NamedTuple):
    """One collective op: kind + operand/result payload bytes.

    ``bytes`` is ``max(operand, result)`` — the full-gradient payload for
    all three shapes (all-reduce: in == out; reduce-scatter: in is full;
    all-gather: out is full).
    """

    kind: str
    operand_bytes: int
    result_bytes: int

    @property
    def bytes(self) -> int:
        return max(self.operand_bytes, self.result_bytes)


def parse_collectives(stablehlo_text: str) -> List[Collective]:
    """All collective ops in a StableHLO module, in textual order."""
    out = []
    for m in _OP_RE.finditer(stablehlo_text):
        sig = _SIG_RE.search(stablehlo_text, m.end())
        if sig is None:
            raise ValueError(
                f"no type signature found after stablehlo.{m.group(1)}"
            )
        operand = sum(_tensor_bytes(t) for t in _TENSOR_RE.findall(sig.group(1)))
        result = sum(_tensor_bytes(t) for t in _TENSOR_RE.findall(sig.group(2)))
        out.append(Collective(m.group(1), operand, result))
    return out


def collective_summary(
    stablehlo_text: str, min_bytes: int = 0
) -> Dict[str, Dict[str, int]]:
    """``{kind: {count, bytes}}`` over collectives with payload >=
    ``min_bytes`` (0 = everything; pass e.g. 1024 to keep only
    gradient-sized ops and drop scalar flag/metric psums)."""
    summary: Dict[str, Dict[str, int]] = {}
    for c in parse_collectives(stablehlo_text):
        if c.bytes < min_bytes:
            continue
        s = summary.setdefault(c.kind, {"count": 0, "bytes": 0})
        s["count"] += 1
        s["bytes"] += c.bytes
    return summary


# --------------------------------------------------------------------------
# declarative budgets
# --------------------------------------------------------------------------

class BudgetError(AssertionError):
    """Raised by :func:`assert_budget` with the violation list."""


@dataclasses.dataclass(frozen=True)
class CollectiveBudget:
    """A program's declared communication contract.

    ``counts`` maps op kind -> EXACT expected count among collectives
    with payload >= ``min_bytes``; kinds not listed must not appear at
    all (a budget is a whitelist — new collective kinds are regressions
    until declared).  ``bytes`` optionally pins exact per-kind total
    payload (e.g. the flat fp32 gradient bytes), and
    ``max_total_bytes`` caps the summed payload across kinds.

    Examples::

        # one bucketed gradient all-reduce per boundary (PR 2)
        CollectiveBudget(name="train_m4", min_bytes=1024,
                         counts={"all_reduce": 1},
                         bytes={"all_reduce": GRAD_BYTES})
        # ZeRO boundary pair, no gradient-sized all-reduce survives
        CollectiveBudget(name="train_zero", min_bytes=1024,
                         counts={"reduce_scatter": 1, "all_gather": 1})
        # decode window: num_layers head-reassembly psums, K-invariant
        CollectiveBudget(name="decode", counts={"all_reduce": 2})
    """

    counts: Mapping[str, int]
    name: str = "program"
    min_bytes: int = 0
    bytes: Optional[Mapping[str, int]] = None
    max_total_bytes: Optional[int] = None
    #: op kinds whose collective is DELIBERATELY half-width (the
    #: compressed-gradient bf16 psum of ISSUE 16).  Not a blanket
    #: waiver: the precision lint exempts a half-dtype collective only
    #: when its payload exactly matches this budget's ``bytes`` pin
    #: for the kind (see ``lint_jaxpr(half_collective_bytes=...)``) —
    #: an unplanned half psum of any other size still fires.
    half_ok: Tuple[str, ...] = ()

    def describe(self) -> str:
        parts = [f"{k}={v}" for k, v in sorted(self.counts.items())]
        return (f"{self.name}: " + ", ".join(parts)
                + f" (>= {self.min_bytes} B)")


def check_budget(
    stablehlo_text: str, budget: CollectiveBudget
) -> List[str]:
    """Violation strings for ``stablehlo_text`` against ``budget``
    (empty = within budget).  Checks exact counts for declared kinds,
    rejects undeclared kinds, then the optional bytes pins/cap."""
    summary = collective_summary(stablehlo_text,
                                 min_bytes=budget.min_bytes)
    census = json.dumps(collective_summary(stablehlo_text),
                        sort_keys=True)
    out: List[str] = []
    for kind, want in budget.counts.items():
        got = summary.get(kind, {"count": 0})["count"]
        if got != want:
            out.append(
                f"{budget.name}: expected {want} {kind} "
                f"(>= {budget.min_bytes} B), found {got}; "
                f"full census: {census}"
            )
    for kind in sorted(set(summary) - set(budget.counts)):
        out.append(
            f"{budget.name}: undeclared collective kind {kind} "
            f"(count {summary[kind]['count']}, "
            f"{summary[kind]['bytes']} B) — extend the budget if this "
            f"traffic is intended; full census: {census}"
        )
    for kind, want in (budget.bytes or {}).items():
        got = summary.get(kind, {"bytes": 0})["bytes"]
        if got != want:
            out.append(
                f"{budget.name}: {kind} moves {got} B, expected "
                f"{want} B; full census: {census}"
            )
    if budget.max_total_bytes is not None:
        total = sum(s["bytes"] for s in summary.values())
        if total > budget.max_total_bytes:
            out.append(
                f"{budget.name}: total collective payload {total} B "
                f"exceeds cap {budget.max_total_bytes} B; "
                f"full census: {census}"
            )
    return out


def assert_budget(stablehlo_text: str, budget: CollectiveBudget):
    """Raise :class:`BudgetError` listing every violation of
    ``budget`` (no-op when the program is within budget)."""
    violations = check_budget(stablehlo_text, budget)
    if violations:
        raise BudgetError(
            f"{len(violations)} collective-budget violation(s):\n  "
            + "\n  ".join(violations)
        )


def boundary_budget(
    *, zero: bool = False, min_bytes: int = 1024,
    expect_bytes: Optional[int] = None, name: str = "boundary",
) -> CollectiveBudget:
    """The PR-2 deferred-collective contract as a budget: one gradient
    all-reduce per boundary, or the reduce-scatter + all-gather pair
    (and NO gradient-sized all-reduce) for ``zero=True``."""
    if zero:
        return CollectiveBudget(
            name=name, min_bytes=min_bytes,
            counts={"all_reduce": 0, "reduce_scatter": 1,
                    "all_gather": 1},
        )
    return CollectiveBudget(
        name=name, min_bytes=min_bytes,
        counts={"all_reduce": 1, "reduce_scatter": 0, "all_gather": 0},
        bytes=(None if expect_bytes is None
               else {"all_reduce": expect_bytes}),
    )


def assert_boundary_collectives(
    stablehlo_text: str,
    *,
    zero: bool = False,
    min_bytes: int = 1024,
    expect_bytes: Optional[int] = None,
) -> Dict[str, Dict[str, int]]:
    """Assert the deferred-collective contract of one driver window.

    Exactly ONE gradient-sized (>= ``min_bytes``) all-reduce per
    accumulation boundary — or, with ``zero=True``, exactly one
    reduce-scatter + all-gather pair and NO gradient-sized all-reduce.
    ``expect_bytes`` additionally pins the all-reduce payload (the flat
    fp32 gradient bytes).  Returns the >=min_bytes summary for further
    checks/recording.  Raises AssertionError with the full op census on
    mismatch — the failure mode this guards is a refactor reintroducing
    a per-microbatch psum (M ops, because the microbatch loop is
    unrolled) or a second full-gradient reduction.

    (Kept as the PR-2 API; implemented over :func:`check_budget` —
    undeclared-kind violations are ignored here for back-compat, the
    historical contract only constrained the three gradient kinds.)
    """
    budget = boundary_budget(zero=zero, min_bytes=min_bytes,
                             expect_bytes=expect_bytes)
    summary = collective_summary(stablehlo_text, min_bytes=min_bytes)
    violations = [
        v for v in check_budget(stablehlo_text, budget)
        if "undeclared collective kind" not in v
    ]
    if violations:
        raise AssertionError("; ".join(violations))
    return summary


def gradient_collective_bytes(
    stablehlo_text: str, min_bytes: int = 1024
) -> int:
    """Total gradient-sized collective payload bytes per optimizer step
    (each traced call site fires once per scan iteration)."""
    return sum(
        s["bytes"]
        for s in collective_summary(stablehlo_text, min_bytes=min_bytes).values()
    )


def compiled_memory(compiled) -> Optional[Dict[str, int]]:
    """Peak-memory facts of a ``lowered.compile()`` program, or None when
    the backend exposes no analysis.  ``temp_size_in_bytes`` is the
    activation/workspace peak — the figure remat + ZeRO shrink."""
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    fields = (
        "temp_size_in_bytes",
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    out = {}
    for f in fields:
        v = getattr(ma, f, None)
        if v is not None:
            out[f] = int(v)
    return out or None


def main(argv=None):
    import argparse
    import sys

    ap = argparse.ArgumentParser(
        description="Collective-op census of a StableHLO module"
    )
    ap.add_argument("path", help="StableHLO text file, or - for stdin")
    ap.add_argument("--min-bytes", type=int, default=0,
                    help="drop collectives with payload below this")
    args = ap.parse_args(argv)
    text = (
        sys.stdin.read() if args.path == "-"
        else open(args.path).read()
    )
    print(json.dumps(
        collective_summary(text, min_bytes=args.min_bytes),
        indent=2, sort_keys=True,
    ))
