"""apexlint — AST invariant analyzer for the repo's own bug classes.

The compiled-graph sanitizers (:mod:`apex_tpu.analysis.precision` /
``donation`` / ``collectives`` / ``recompile`` / ``costs``) prove
invariants about what XLA runs; this module proves the HOST-side
invariants the repo's postmortem-replay, seeded-determinism and
atomic-commit story depends on.  Every rule encodes a bug class that
actually shipped (or nearly shipped) in a past PR — the CHANGES.md
ledger as machine-checked law:

==========================  ==============================================
rule                        originating bug class
==========================  ==============================================
wall-clock-in-deterministic PR 15: wall-derived fields leaking into
                            ``deterministic_view()`` / digest inputs
unseeded-rng                PR 7/10: unseeded ``random``/``np.random``
                            breaking byte-replayable load plans
nonatomic-json-write        PR 8/9: checkpoint/exchange files that must
                            land whole-or-not-at-all (tmp+``os.replace``)
unregistered-env-knob       PR 19: ``APEX_TPU_*`` reads with no row in
                            :mod:`apex_tpu.envs` — undocumentable knobs
env-doc-drift               PR 19: registry vs README env-table drift
clock-into-flightrec        PR 11: forwarding an engine's wall ``clock=``
                            into ``FlightRecorder``/``GangTelemetry``
                            breaks byte-identical postmortem replay
use-after-donate            PR 2/3: reading a buffer after passing it to
                            a ``donate_argnums`` call site
unsorted-walk               PR 9: ``os.listdir``/glob order feeding
                            deterministic artifacts (DcnExchange class)
record-kind-keyword         PR 11: ``record(kind=...)`` keyword misuse of
                            the positional-only ``record(kind, /)``
suppression-hygiene         PR 19: ``# apexlint: disable`` without a
                            reason, or naming an unknown rule
==========================  ==============================================

Suppression syntax (counted and pinned by the perf gate)::

    something_flagged()  # apexlint: disable=<rule> -- <why it is safe>

on the offending line or the line directly above it.  A disable with
no ``-- reason``, or naming a rule that does not exist, is itself a
violation (``suppression-hygiene``).

Deliberately dependency-free (stdlib ``ast`` only; the env registry is
loaded from ``apex_tpu/envs.py`` by file path) so ``tools/apexlint.py``
runs on a box without jax.  The jaxpr-side donation dataflow pass lives
in :mod:`apex_tpu.analysis.dataflow` (which does need jax).
"""
from __future__ import annotations

import ast
import dataclasses
import importlib.util
import os
import re
import sys
from typing import Any, Callable, Dict, List, Optional, Sequence, Set, Tuple

__all__ = [
    "RULES",
    "Finding",
    "Report",
    "Rule",
    "Suppression",
    "iter_source_files",
    "load_env_registry",
    "scan_files",
    "scan_repo",
]

_HERE = os.path.dirname(os.path.abspath(__file__))
REPO_ROOT = os.path.dirname(os.path.dirname(_HERE))

#: the trees the analyzer sweeps (plus EXTRA_FILES at the repo root)
SCAN_ROOTS: Tuple[str, ...] = ("apex_tpu", "tools", "tests")
EXTRA_FILES: Tuple[str, ...] = ("chip_smoke.py",)

#: modules whose ENTIRE content must be wall-clock-free: everything
#: they emit feeds a digest, a byte-replayed postmortem, or a seeded
#: plan.  Wall time in these files must arrive through an injected
#: ``clock=`` callable (the flightrec contract).
DETERMINISTIC_MODULES: Tuple[str, ...] = (
    "apex_tpu/obs/flightrec.py",
    "apex_tpu/obs/gangview.py",
    "apex_tpu/serve/loadgen.py",
    "apex_tpu/resilience/faults.py",
    "apex_tpu/checkpoint.py",
)

#: function names that are deterministic wherever they live (their
#: output is hashed or replayed byte-for-byte)
_DETERMINISTIC_FN = re.compile(r"(_digest$|^deterministic_view$)")

_WALL_CLOCK_CALLS = {
    "time.time", "time.time_ns", "time.perf_counter",
    "time.perf_counter_ns", "time.monotonic", "time.monotonic_ns",
    "datetime.now", "datetime.utcnow", "datetime.today",
}

_PY_RANDOM_FNS = {
    "random", "randint", "randrange", "uniform", "gauss",
    "normalvariate", "choice", "choices", "sample", "shuffle",
    "betavariate", "expovariate", "getrandbits", "randbytes", "seed",
}
_NP_RANDOM_FNS = {
    "rand", "randn", "randint", "random", "random_sample", "choice",
    "permutation", "shuffle", "uniform", "normal", "standard_normal",
    "seed", "bytes", "binomial", "poisson", "exponential",
}

_ENV_NAME = re.compile(r"APEX_TPU_[A-Z0-9_]+\Z")

_SUPPRESS = re.compile(
    r"#\s*apexlint:\s*disable=([A-Za-z0-9_,-]+)"
    r"(?:\s*--\s*(.*\S))?"
)


# ---------------------------------------------------------------------------
# data model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Rule:
    """One registered invariant.

    Args:
      name: the kebab-case rule id (the ``disable=`` token).
      origin: the PR / bug class the rule encodes.
      doc: one line on what the rule forbids.
      scope: ``"all"`` (every scanned file), ``"nontest"`` (skip
        ``tests/``), or ``"deterministic"`` (only
        :data:`DETERMINISTIC_MODULES` + ``*_digest`` /
        ``deterministic_view`` functions).
    """

    name: str
    origin: str
    doc: str
    scope: str = "nontest"


@dataclasses.dataclass(frozen=True)
class Finding:
    """One rule violation at a source location."""

    rule: str
    path: str
    line: int
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


@dataclasses.dataclass
class Suppression:
    """One ``# apexlint: disable=`` comment."""

    rule: str
    path: str
    line: int
    reason: str
    used: bool = False


@dataclasses.dataclass
class Report:
    """A full sweep's outcome: unsuppressed findings are the
    violations; the census is what the perf gate pins."""

    files: List[str]
    findings: List[Finding]
    suppressed: List[Finding]
    suppressions: List[Suppression]

    def census(self) -> Dict[str, int]:
        return {
            "rules": len(RULES),
            "files": len(self.files),
            "violations": len(self.findings),
            "suppressions": len(self.suppressions),
        }

    def render(self) -> str:
        lines = [f.render() for f in self.findings]
        c = self.census()
        lines.append(
            f"# apexlint: {c['rules']} rules, {c['files']} files, "
            f"{c['violations']} violation(s), "
            f"{c['suppressions']} suppression(s)"
        )
        return "\n".join(lines)


RULES: Tuple[Rule, ...] = (
    Rule("wall-clock-in-deterministic",
         "PR 15 (wall fields leaking into deterministic_view)",
         "time.time/perf_counter/datetime.now in deterministic "
         "modules or *_digest functions; inject a clock= instead",
         scope="deterministic"),
    Rule("unseeded-rng",
         "PR 7/10 (seeded load plans, byte-replayable chaos)",
         "bare random.*/np.random.* module-level sampling; use a "
         "seeded RandomState/default_rng/PRNGKey"),
    Rule("nonatomic-json-write",
         "PR 8/9 (checkpoint + DcnExchange commit discipline)",
         "open(path, 'w') feeding json.dump(s) without the "
         "tmp + os.replace pattern in the same function"),
    Rule("unregistered-env-knob",
         "PR 19 (the env registry this rule forced into existence)",
         "an APEX_TPU_* name used in code with no EnvKnob row in "
         "apex_tpu/envs.py", scope="all"),
    Rule("env-doc-drift",
         "PR 19 (README env table vs reality)",
         "apex_tpu/envs.py registry and README.md env table out of "
         "sync, or a knob without a doc line", scope="all"),
    Rule("clock-into-flightrec",
         "PR 11 (never forward an engine's clock= to flightrec)",
         "FlightRecorder(clock=...)/GangTelemetry(clock=...) with a "
         "non-None clock — postmortems stop byte-replaying"),
    Rule("use-after-donate",
         "PR 2/3 (jnp.array(copy=True) use-after-donate class)",
         "a name passed at a donate_argnums call site is read again "
         "without an intervening rebind (function-local)"),
    Rule("unsorted-walk",
         "PR 9 (DcnExchange eager-delete race / listdir order)",
         "os.listdir/glob.glob/os.scandir/.iterdir() not wrapped in "
         "sorted() — filesystem order is not deterministic"),
    Rule("record-kind-keyword",
         "PR 11 (record(kind, /) is positional-only)",
         ".record(kind=...) with no positional event kind — the "
         "keyword lands in **attrs and the call raises when enabled",
         scope="all"),
    Rule("suppression-hygiene",
         "PR 19 (suppressions are counted, pinned and justified)",
         "# apexlint: disable without a '-- reason' or naming an "
         "unknown rule", scope="all"),
)

_RULE_NAMES: Set[str] = {r.name for r in RULES}


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _dotted(node: ast.AST) -> Optional[str]:
    """``a.b.c`` for a Name/Attribute chain, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def _tail(dotted: Optional[str], n: int = 2) -> Optional[str]:
    if not dotted:
        return None
    return ".".join(dotted.split(".")[-n:])


def _is_test_path(relpath: str) -> bool:
    return relpath.startswith("tests/") or "/tests/" in relpath


def load_env_registry(root: str = REPO_ROOT) -> Set[str]:
    """The registered knob names, loaded from ``<root>/apex_tpu/envs.py``
    by file path (no package import, no jax); falls back to the
    analyzer's own repo when ``root`` has no registry (tmp-tree
    scans)."""
    for base in (root, REPO_ROOT):
        path = os.path.join(base, "apex_tpu", "envs.py")
        if os.path.exists(path):
            spec = importlib.util.spec_from_file_location(
                "_apexlint_envs", path
            )
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod
            spec.loader.exec_module(mod)
            return set(mod.REGISTRY)
    return set()


def iter_source_files(root: str = REPO_ROOT) -> List[str]:
    """Every ``.py`` under :data:`SCAN_ROOTS` plus :data:`EXTRA_FILES`,
    as repo-relative paths, sorted."""
    out: List[str] = []
    for sub in SCAN_ROOTS:
        base = os.path.join(root, sub)
        if not os.path.isdir(base):
            continue
        for dirpath, dirnames, filenames in os.walk(base):
            dirnames[:] = sorted(
                d for d in dirnames if d != "__pycache__"
            )
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    out.append(os.path.relpath(
                        os.path.join(dirpath, fn), root
                    ).replace(os.sep, "/"))
    for fn in EXTRA_FILES:
        if os.path.exists(os.path.join(root, fn)):
            out.append(fn)
    return sorted(out)


@dataclasses.dataclass
class _FileCtx:
    relpath: str
    tree: ast.Module
    lines: List[str]
    is_test: bool
    registry: Set[str]

    def segment(self, node: ast.AST) -> str:
        try:
            return ast.get_source_segment(
                "\n".join(self.lines), node
            ) or ""
        except Exception:
            return ""


# ---------------------------------------------------------------------------
# rule checkers (one function per rule, registered in _CHECKERS)
# ---------------------------------------------------------------------------

def _check_wall_clock(ctx: _FileCtx) -> List[Finding]:
    whole_file = ctx.relpath in DETERMINISTIC_MODULES
    out: List[Finding] = []

    def flag_calls(node: ast.AST, where: str) -> None:
        for sub in ast.walk(node):
            if not isinstance(sub, ast.Call):
                continue
            tail = _tail(_dotted(sub.func))
            if tail in _WALL_CLOCK_CALLS:
                out.append(Finding(
                    "wall-clock-in-deterministic", ctx.relpath,
                    sub.lineno,
                    f"{tail}() in deterministic {where} — wall reads "
                    f"must flow through an injected clock=",
                ))

    if whole_file:
        flag_calls(ctx.tree, f"module {ctx.relpath}")
        return out
    for node in ast.walk(ctx.tree):
        if (isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef))
                and _DETERMINISTIC_FN.search(node.name)):
            flag_calls(node, f"function {node.name}()")
    return out


def _check_unseeded_rng(ctx: _FileCtx) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = _dotted(node.func)
        if not dotted:
            continue
        parts = dotted.split(".")
        if (len(parts) == 2 and parts[0] == "random"
                and parts[1] in _PY_RANDOM_FNS):
            out.append(Finding(
                "unseeded-rng", ctx.relpath, node.lineno,
                f"module-level random.{parts[1]}() — use a seeded "
                f"random.Random(seed) instance",
            ))
        elif (len(parts) == 3 and parts[0] in ("np", "numpy")
                and parts[1] == "random" and parts[2] in _NP_RANDOM_FNS):
            out.append(Finding(
                "unseeded-rng", ctx.relpath, node.lineno,
                f"module-level {parts[0]}.random.{parts[2]}() — use a "
                f"seeded RandomState/default_rng",
            ))
    return out


def _json_feeding_write(with_node: ast.With) -> bool:
    """Does this with-block's body serialize JSON into the handle?"""
    for sub in ast.walk(with_node):
        if not isinstance(sub, ast.Call):
            continue
        tail = _tail(_dotted(sub.func))
        if tail in ("json.dump", "json.dumps"):
            return True
    return False


def _check_nonatomic_write(ctx: _FileCtx) -> List[Finding]:
    out: List[Finding] = []
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node

    def enclosing_scope(node: ast.AST) -> ast.AST:
        cur = parents.get(id(node))
        while cur is not None and not isinstance(
                cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
            cur = parents.get(id(cur))
        return cur if cur is not None else ctx.tree

    replace_scopes = set()
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Call)
                and _tail(_dotted(node.func)) == "os.replace"):
            replace_scopes.add(id(enclosing_scope(node)))

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.With):
            continue
        for item in node.items:
            call = item.context_expr
            if not (isinstance(call, ast.Call)
                    and _dotted(call.func) in ("open", "io.open")):
                continue
            mode = None
            if len(call.args) > 1 and isinstance(
                    call.args[1], ast.Constant):
                mode = call.args[1].value
            for kw in call.keywords:
                if kw.arg == "mode" and isinstance(
                        kw.value, ast.Constant):
                    mode = kw.value.value
            if mode not in ("w", "wt"):
                continue
            path_src = ctx.segment(call.args[0]) if call.args else ""
            if "tmp" in path_src.lower():
                continue  # writing the tmp half of the pattern
            if not _json_feeding_write(node):
                continue
            if id(enclosing_scope(node)) in replace_scopes:
                continue  # tmp + os.replace discipline in this scope
            out.append(Finding(
                "nonatomic-json-write", ctx.relpath, call.lineno,
                "open(..., 'w') feeding json without tmp + "
                "os.replace — a crash mid-write leaves a torn "
                "artifact",
            ))
    return out


def _check_unregistered_env(ctx: _FileCtx) -> List[Finding]:
    if ctx.relpath == "apex_tpu/envs.py":
        return []
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if (isinstance(node, ast.Constant)
                and isinstance(node.value, str)
                and _ENV_NAME.match(node.value)
                and node.value not in ctx.registry):
            out.append(Finding(
                "unregistered-env-knob", ctx.relpath, node.lineno,
                f"{node.value} has no EnvKnob row in apex_tpu/envs.py "
                f"(and therefore no README doc line)",
            ))
    return out


def _check_clock_into_flightrec(ctx: _FileCtx) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        tail = _tail(_dotted(node.func), 1)
        if tail not in ("FlightRecorder", "GangTelemetry"):
            continue
        for kw in node.keywords:
            if kw.arg == "clock" and not (
                    isinstance(kw.value, ast.Constant)
                    and kw.value.value is None):
                out.append(Finding(
                    "clock-into-flightrec", ctx.relpath, node.lineno,
                    f"{tail}(clock=...) — forwarding a live clock "
                    f"breaks byte-identical postmortem replay; leave "
                    f"the default logical-seq stamp",
                ))
    return out


def _check_record_kind_keyword(ctx: _FileCtx) -> List[Finding]:
    out: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        if not (isinstance(node.func, ast.Attribute)
                and node.func.attr == "record"):
            continue
        if node.args:
            continue  # positional kind present; kind= is a data attr
        if any(kw.arg == "kind" for kw in node.keywords):
            out.append(Finding(
                "record-kind-keyword", ctx.relpath, node.lineno,
                ".record(kind=...) with no positional event kind — "
                "record(kind, /) is positional-only and this raises "
                "TypeError when the recorder is enabled",
            ))
    return out


def _check_unsorted_walk(ctx: _FileCtx) -> List[Finding]:
    walk_calls = {"os.listdir", "glob.glob", "glob.iglob",
                  "os.scandir"}
    out: List[Finding] = []
    parents: Dict[int, ast.AST] = {}
    for node in ast.walk(ctx.tree):
        for child in ast.iter_child_nodes(node):
            parents[id(child)] = node
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        tail = _tail(_dotted(node.func))
        is_walk = tail in walk_calls or (
            isinstance(node.func, ast.Attribute)
            and node.func.attr == "iterdir"
        )
        if not is_walk:
            continue
        parent = parents.get(id(node))
        if (isinstance(parent, ast.Call)
                and isinstance(parent.func, ast.Name)
                and parent.func.id == "sorted"):
            continue
        label = tail or ".iterdir()"
        out.append(Finding(
            "unsorted-walk", ctx.relpath, node.lineno,
            f"{label} without sorted() — filesystem order is "
            f"nondeterministic and leaks into downstream artifacts",
        ))
    return out


# -- use-after-donate: function-local exec-order dataflow -------------------

_LOAD, _STORE, _DONATE = 0, 1, 2


def _expr_events(node: ast.AST, donors: Dict[str, Optional[Tuple[int, ...]]],
                 events: List[Tuple[int, int, Any]]) -> None:
    """Append (kind, lineno, payload) events for one expression in
    evaluation order.  Calls emit their argument loads first, then the
    donate event (the callee consumes its buffers on return)."""
    if isinstance(node, ast.Name):
        kind = _STORE if isinstance(node.ctx, (ast.Store, ast.Del)) \
            else _LOAD
        events.append((kind, node.lineno, node.id))
        return
    if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                         ast.Lambda, ast.ClassDef)):
        return  # separate scope
    if isinstance(node, ast.Call):
        _expr_events(node.func, donors, events)
        for a in node.args:
            _expr_events(a, donors, events)
        for kw in node.keywords:
            _expr_events(kw.value, donors, events)
        callee = node.func.id if isinstance(node.func, ast.Name) else None
        if callee in donors:
            positions = donors[callee]
            poisoned = []
            for i, a in enumerate(node.args):
                if isinstance(a, ast.Name) and (
                        positions is None or i in positions):
                    poisoned.append(a.id)
            if poisoned:
                events.append((_DONATE, node.lineno, tuple(poisoned)))
        return
    for child in ast.iter_child_nodes(node):
        _expr_events(child, donors, events)


def _stmt_events(body: Sequence[ast.stmt],
                 donors: Dict[str, Optional[Tuple[int, ...]]],
                 events: List[Tuple[int, int, Any]]) -> None:
    for st in body:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            continue
        if isinstance(st, ast.Assign):
            _expr_events(st.value, donors, events)
            for t in st.targets:
                _expr_events(t, donors, events)
        elif isinstance(st, ast.AugAssign):
            ld = ast.Name(id=st.target.id, ctx=ast.Load(),
                          lineno=st.lineno, col_offset=0) \
                if isinstance(st.target, ast.Name) else st.target
            _expr_events(ld, donors, events)
            _expr_events(st.value, donors, events)
            _expr_events(st.target, donors, events)
        elif isinstance(st, ast.AnnAssign):
            if st.value is not None:
                _expr_events(st.value, donors, events)
            _expr_events(st.target, donors, events)
        elif isinstance(st, ast.For):
            _expr_events(st.iter, donors, events)
            _expr_events(st.target, donors, events)
            _stmt_events(st.body, donors, events)
            _stmt_events(st.orelse, donors, events)
        elif isinstance(st, (ast.While, ast.If)):
            _expr_events(st.test, donors, events)
            _stmt_events(st.body, donors, events)
            _stmt_events(st.orelse, donors, events)
        elif isinstance(st, ast.With):
            for item in st.items:
                _expr_events(item.context_expr, donors, events)
                if item.optional_vars is not None:
                    _expr_events(item.optional_vars, donors, events)
            _stmt_events(st.body, donors, events)
        elif isinstance(st, ast.Try):
            _stmt_events(st.body, donors, events)
            for h in st.handlers:
                _stmt_events(h.body, donors, events)
            _stmt_events(st.orelse, donors, events)
            _stmt_events(st.finalbody, donors, events)
        else:
            _expr_events(st, donors, events)


def _donate_positions(call: ast.Call) -> Optional[Tuple[int, ...]]:
    """Literal donate_argnums positions from a jit(...) call, or None
    when unparseable (= treat every positional arg as donated)."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return (v.value,)
        if isinstance(v, (ast.Tuple, ast.List)):
            vals = []
            for e in v.elts:
                if isinstance(e, ast.Constant) and isinstance(
                        e.value, int):
                    vals.append(e.value)
                else:
                    return None
            return tuple(vals)
        return None
    return None


def _check_use_after_donate(ctx: _FileCtx) -> List[Finding]:
    out: List[Finding] = []
    for fn in ast.walk(ctx.tree):
        if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        # pass 1: local names bound to jit(..., donate_argnums=...)
        donors: Dict[str, Optional[Tuple[int, ...]]] = {}
        for st in fn.body:
            if not (isinstance(st, ast.Assign) and len(st.targets) == 1
                    and isinstance(st.targets[0], ast.Name)
                    and isinstance(st.value, ast.Call)):
                continue
            callee = _tail(_dotted(st.value.func), 1)
            if callee in ("jit", "pjit") and any(
                    kw.arg == "donate_argnums"
                    for kw in st.value.keywords):
                donors[st.targets[0].id] = _donate_positions(st.value)
        if not donors:
            continue
        # pass 2: exec-order events; a load of a poisoned name before
        # a rebind is the PR 2/3 class
        events: List[Tuple[int, int, Any]] = []
        _stmt_events(fn.body, donors, events)
        poisoned: Dict[str, int] = {}
        for kind, lineno, payload in events:
            if kind == _DONATE:
                for name in payload:
                    poisoned[name] = lineno
            elif kind == _STORE:
                poisoned.pop(payload, None)
            elif kind == _LOAD and payload in poisoned:
                out.append(Finding(
                    "use-after-donate", ctx.relpath, lineno,
                    f"'{payload}' was donated at line "
                    f"{poisoned[payload]} and is read again without a "
                    f"rebind — the buffer may already be aliased away",
                ))
                poisoned.pop(payload)  # one finding per donation
    return out


_CHECKERS: Dict[str, Callable[[_FileCtx], List[Finding]]] = {
    "wall-clock-in-deterministic": _check_wall_clock,
    "unseeded-rng": _check_unseeded_rng,
    "nonatomic-json-write": _check_nonatomic_write,
    "unregistered-env-knob": _check_unregistered_env,
    "clock-into-flightrec": _check_clock_into_flightrec,
    "use-after-donate": _check_use_after_donate,
    "unsorted-walk": _check_unsorted_walk,
    "record-kind-keyword": _check_record_kind_keyword,
}


# ---------------------------------------------------------------------------
# the sweep
# ---------------------------------------------------------------------------

def _collect_suppressions(relpath: str,
                          lines: List[str]) -> Tuple[List[Suppression],
                                                     List[Finding]]:
    sups: List[Suppression] = []
    hygiene: List[Finding] = []
    for i, line in enumerate(lines, start=1):
        m = _SUPPRESS.search(line)
        if not m:
            continue
        reason = (m.group(2) or "").strip()
        for rule in m.group(1).split(","):
            rule = rule.strip()
            if rule not in _RULE_NAMES:
                hygiene.append(Finding(
                    "suppression-hygiene", relpath, i,
                    f"disable={rule!r} names no apexlint rule",
                ))
                continue
            if not reason:
                hygiene.append(Finding(
                    "suppression-hygiene", relpath, i,
                    f"disable={rule} without a '-- reason' — every "
                    f"suppression documents why it is safe",
                ))
                continue
            sups.append(Suppression(rule, relpath, i, reason))
    return sups, hygiene


def _rule_applies(rule: Rule, relpath: str, is_test: bool) -> bool:
    if rule.scope == "all":
        return True
    if rule.scope == "nontest":
        return not is_test
    if rule.scope == "deterministic":
        # the checker itself narrows to modules/functions; scanning a
        # test file for *_digest defs is intended
        return not is_test
    return True


def scan_files(relpaths: Sequence[str], root: str = REPO_ROOT,
               registry: Optional[Set[str]] = None,
               readme: Optional[str] = None) -> Report:
    """Run every rule over ``relpaths`` (repo-relative, under
    ``root``), apply suppressions, and append the cross-artifact
    ``env-doc-drift`` check (``readme``: explicit README.md path, else
    ``<root>/README.md``; missing file skips the check so tmp-tree
    fixtures stay self-contained)."""
    if registry is None:
        registry = load_env_registry(root)
    findings: List[Finding] = []
    all_sups: List[Suppression] = []
    scanned: List[str] = []
    for relpath in relpaths:
        full = os.path.join(root, relpath)
        try:
            with open(full, encoding="utf-8") as f:
                src = f.read()
            tree = ast.parse(src, filename=relpath)
        except (OSError, SyntaxError) as e:
            findings.append(Finding(
                "suppression-hygiene", relpath, 1,
                f"unparseable source: {e}",
            ))
            continue
        scanned.append(relpath)
        lines = src.splitlines()
        is_test = _is_test_path(relpath)
        ctx = _FileCtx(relpath, tree, lines, is_test, registry)
        sups, hygiene = _collect_suppressions(relpath, lines)
        all_sups.extend(sups)
        findings.extend(hygiene)
        for rule in RULES:
            checker = _CHECKERS.get(rule.name)
            if checker is None or not _rule_applies(
                    rule, relpath, is_test):
                continue
            findings.extend(checker(ctx))
    # cross-artifact: registry vs README env table
    readme_path = readme or os.path.join(root, "README.md")
    if os.path.exists(readme_path):
        envs_path = next(
            (p for p in (os.path.join(root, "apex_tpu", "envs.py"),
                         os.path.join(REPO_ROOT, "apex_tpu", "envs.py"))
             if os.path.exists(p)), None,
        )
        if envs_path is not None:
            spec = importlib.util.spec_from_file_location(
                "_apexlint_envs_drift", envs_path
            )
            mod = importlib.util.module_from_spec(spec)
            sys.modules[spec.name] = mod
            spec.loader.exec_module(mod)
            with open(readme_path, encoding="utf-8") as f:
                for msg in mod.check_readme_drift(f.read()):
                    findings.append(Finding(
                        "env-doc-drift",
                        os.path.basename(readme_path), 0, msg,
                    ))
    # apply suppressions: same line or the line directly above
    by_key = {}
    for s in all_sups:
        by_key[(s.rule, s.path, s.line)] = s
    live: List[Finding] = []
    quashed: List[Finding] = []
    for f in findings:
        s = (by_key.get((f.rule, f.path, f.line))
             or by_key.get((f.rule, f.path, f.line - 1)))
        if s is not None:
            s.used = True
            quashed.append(f)
        else:
            live.append(f)
    live.sort(key=lambda f: (f.path, f.line, f.rule))
    return Report(files=scanned, findings=live, suppressed=quashed,
                  suppressions=all_sups)


def scan_repo(root: str = REPO_ROOT,
              readme: Optional[str] = None) -> Report:
    """The full sweep: every file under :data:`SCAN_ROOTS` +
    :data:`EXTRA_FILES`."""
    return scan_files(iter_source_files(root), root=root,
                      readme=readme)
