"""Donation checker — compiled aliasing proof + use-after-donate guard.

Every hot program in this repo donates its carry: the train driver's
K-step window, the serve decoder's prefill/decode dispatches.  Donation
is a *request* — jax drops it silently when an input has no matching
output (dtype/shape mismatch after a refactor) or when a wrapper loses
``donate_argnums`` — and a dropped donation doesn't fail, it just keeps
two copies of the params/optimizer state/KV cache live and silently
doubles HBM.  The proof object is the COMPILED executable: XLA records
every honored donation in the ``input_output_alias`` field of the
post-optimization HloModule header (backend-independent — present on
the CPU test mesh too, unlike the lowered StableHLO's
``tf.aliasing_output`` attr, which the shard_map path does not emit).
:func:`assert_donated` parses it and asserts every donated leaf was
actually aliased.

The second half is the HOST side of the same bug class: a donated
buffer's *Python tree* stays importable after the dispatch, and
``device_put``/``replicate`` may alias rather than copy, so reusing a
donated tree reads deleted (TPU) or stale (CPU, where donation is
quietly unhonored) memory — the PR 2/PR 3 ``jnp.array(x, copy=True)``
workaround class.  :class:`DonationGuard` wraps a donated program and
raises :class:`UseAfterDonateError` the moment a previously-donated
leaf is passed in again; :func:`poison` turns a donated tree into
sentinels that raise on ANY array use (``jnp.asarray``, jit argument
binding, ``.shape``), for callers that hold references elsewhere.
"""
from __future__ import annotations

import dataclasses
import re
import weakref
from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import jax

__all__ = [
    "DonationError",
    "DonationGuard",
    "DonationReport",
    "InputOutputAlias",
    "UseAfterDonateError",
    "assert_donated",
    "check_donation",
    "guard_donation",
    "parse_input_output_aliases",
    "poison",
]


class DonationError(AssertionError):
    """A donated input was not aliased in the compiled executable."""


class UseAfterDonateError(RuntimeError):
    """A pytree already donated to a dispatch was used again."""


class InputOutputAlias(NamedTuple):
    """One honored donation: compiled output ``output_index`` reuses the
    buffer of entry parameter ``param_number`` (``kind`` is XLA's
    ``may-alias``/``must-alias``)."""

    output_index: Tuple[int, ...]
    param_number: int
    param_index: Tuple[int, ...]
    kind: str


# the alias block nests one brace level ({output_index} and {param_index}
# inside the outer {...}), so match "anything but braces, or one braced
# group" instead of a non-greedy dot (which would stop at the first '}')
_ALIAS_BLOCK_RE = re.compile(
    r"input_output_alias=\{((?:[^{}]|\{[^{}]*\})*)\}"
)
_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d, ]*)\}:\s*\((\d+),\s*\{([\d, ]*)\},\s*(may-alias|must-alias)\)"
)
_ENTRY_LAYOUT_RE = re.compile(r"entry_computation_layout=\{\((.*?)\)->")


def _ints(csv: str) -> Tuple[int, ...]:
    return tuple(int(t) for t in csv.replace(",", " ").split())


def _hlo_text(compiled_or_text) -> str:
    if isinstance(compiled_or_text, str):
        return compiled_or_text
    return compiled_or_text.as_text()


def parse_input_output_aliases(compiled_or_text) -> List[InputOutputAlias]:
    """All honored donations of a compiled executable (a
    ``lowered.compile()`` object or its ``as_text()`` HLO).  An absent
    ``input_output_alias`` header — the dropped-donation signature —
    parses as the empty list."""
    text = _hlo_text(compiled_or_text)
    # the header is one line; scan it (not the whole module) so region
    # bodies can never confuse the entry regex
    header = text.split("\n", 1)[0]
    m = _ALIAS_BLOCK_RE.search(header)
    if m is None:
        return []
    return [
        InputOutputAlias(_ints(a), int(b), _ints(c), d)
        for a, b, c, d in _ALIAS_ENTRY_RE.findall(m.group(1))
    ]


def _entry_param_count(text: str) -> int:
    """Number of entry-computation parameters, from the header layout
    (split on top-level commas — shapes like ``f32[64,32]{1,0}`` carry
    commas inside brackets).  -1 when the header is unparseable."""
    m = _ENTRY_LAYOUT_RE.search(text.split("\n", 1)[0])
    if m is None:
        return -1
    body, depth, count = m.group(1).strip(), 0, 0
    if not body:
        return 0
    for ch in body:
        if ch in "[{(":
            depth += 1
        elif ch in "]})":
            depth -= 1
        elif ch == "," and depth == 0:
            count += 1
    return count + 1


@dataclasses.dataclass(frozen=True)
class DonationReport:
    """Outcome of :func:`check_donation`.

    ``dropped`` lists ``(argnum, leaf_path)`` pairs whose buffers were
    NOT aliased.  ``exact`` is False when jit dropped unused parameters
    from the executable (``keep_unused=False`` default), in which case
    leaf positions can no longer be mapped and the check degrades to
    comparing counts — ``dropped`` then holds ``(argnum, "<count>")``
    markers instead of real paths.
    """

    expected: int
    aliased: int
    dropped: List[Tuple[int, str]]
    exact: bool

    @property
    def ok(self) -> bool:
        return not self.dropped


def _leaf_paths(tree) -> List[str]:
    return [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]
    ]


def check_donation(
    compiled_or_text,
    args: Sequence[Any],
    donate_argnums: Sequence[int],
    kwargs: Dict[str, Any] = None,
) -> DonationReport:
    """Compare the compiled executable's honored aliases against the
    donation REQUEST (``args`` as passed to the jitted call +
    ``donate_argnums``).

    Flattened jit parameters are contiguous per top-level argument, so
    each donated argnum maps to a leaf-index range; every parameter in
    those ranges must appear as an alias source.  When the executable's
    parameter count differs from the flattened leaf count (jit dropped
    an unused arg — e.g. the greedy decode window's RNG key), exact
    positions are unknowable and the check falls back to count
    comparison, which still catches the real failure modes (a wholly
    dropped ``donate_argnums`` → zero aliases; one unaliasable leaf →
    count short by one).

    Donation is BUFFER-POOL based: XLA may satisfy any compatible
    output from any donated buffer, so when one leaf's donation is
    dropped the reported path names the input buffer left unconsumed —
    not necessarily the leaf whose matching output disappeared.
    """
    if kwargs:
        raise ValueError(
            "kwargs-carrying jit signatures are not supported; pass "
            "every argument positionally when checking donation"
        )
    text = _hlo_text(compiled_or_text)
    aliases = parse_input_output_aliases(text)
    aliased_params = {a.param_number for a in aliases}

    ranges: List[Tuple[int, int, int, Any]] = []  # argnum, start, stop, tree
    pos = 0
    donate = frozenset(int(i) for i in donate_argnums)
    for i, a in enumerate(args):
        n = len(jax.tree_util.tree_leaves(a))
        if i in donate:
            ranges.append((i, pos, pos + n, a))
        pos += n
    expected = sum(stop - start for _, start, stop, _ in ranges)

    exact = _entry_param_count(text) == pos
    dropped: List[Tuple[int, str]] = []
    if exact:
        for argnum, start, stop, tree in ranges:
            paths = _leaf_paths(tree)
            for k, param in enumerate(range(start, stop)):
                if param not in aliased_params:
                    dropped.append((argnum, paths[k] or "<root>"))
    elif len(aliases) < expected:
        short = expected - len(aliases)
        first = ranges[0][0] if ranges else -1
        dropped.append(
            (first, f"<{short} of {expected} donated leaves unaliased; "
                    "executable dropped unused params so leaf paths "
                    "are unavailable>")
        )
    return DonationReport(
        expected=expected, aliased=len(aliases), dropped=dropped,
        exact=exact,
    )


def assert_donated(
    compiled_or_text,
    args: Sequence[Any],
    donate_argnums: Sequence[int],
    label: str = "program",
) -> DonationReport:
    """Raise :class:`DonationError` unless every donated leaf of
    ``args`` is aliased in the compiled executable; returns the report.
    The failure this guards: a donated carry that stops aliasing
    silently doubles the program's HBM footprint."""
    report = check_donation(compiled_or_text, args, donate_argnums)
    if not report.ok:
        drops = "\n  ".join(f"argnum {a}: {p}" for a, p in report.dropped)
        raise DonationError(
            f"{label}: {len(report.dropped)} donated leaf/leaves were "
            f"NOT aliased in the compiled executable ({report.aliased} "
            f"of {report.expected} honored) — a dropped donation keeps "
            f"both copies live:\n  {drops}"
        )
    return report


# --------------------------------------------------------------------------
# host-side use-after-donate guard
# --------------------------------------------------------------------------

class _DonatedLeaf:
    """Sentinel for a leaf whose buffer was donated.  Any array-shaped
    use — jit argument binding (``__jax_array__``), ``np.asarray``,
    shape/dtype inspection, arithmetic — raises loudly instead of
    reading deleted/stale memory."""

    __slots__ = ("_label", "_path")

    def __init__(self, label: str, path: str):
        self._label = label
        self._path = path

    def _raise(self, how: str):
        raise UseAfterDonateError(
            f"leaf {self._path or '<root>'} of {self._label} was donated "
            f"to a dispatch and then used again (via {how}); rebind the "
            "returned carry instead, or copy with jnp.array(x, copy=True) "
            "BEFORE donating if the tree must be reused"
        )

    # a property: jax 0.9 raises its own ValueError on any object that
    # merely HAS ``__jax_array__`` as it abstractifies a jit argument,
    # before calling it — the ``hasattr`` probe itself must raise
    @property
    def __jax_array__(self):
        self._raise("__jax_array__")

    def __array__(self, *a, **k):
        self._raise("__array__")

    @property
    def shape(self):
        self._raise("shape")

    @property
    def dtype(self):
        self._raise("dtype")

    def __getattr__(self, name):
        self._raise(f"attribute {name!r}")

    def __repr__(self):
        return (f"<donated leaf {self._path or '<root>'} of "
                f"{self._label}: use raises UseAfterDonateError>")


def poison(tree, label: str = "donated tree"):
    """Same-structure tree of :class:`_DonatedLeaf` sentinels — assign
    it over the stale reference after a donating dispatch
    (``old = analysis.poison(old)``) so any forgotten reuse raises
    :class:`UseAfterDonateError` instead of silently reading a dead
    buffer."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    return jax.tree_util.tree_unflatten(
        treedef,
        [_DonatedLeaf(label, jax.tree_util.keystr(p)) for p, _ in flat],
    )


class DonationGuard:
    """Callable wrapper that enforces rebinding of donated arguments.

    ``guarded = DonationGuard(program, donate_argnums=(0,))`` behaves
    like ``program``, but after each call every array leaf of the
    donated arguments is remembered (by identity, weakly — a collected
    leaf cannot be resubmitted and is dropped); passing any remembered
    leaf into a later call raises :class:`UseAfterDonateError` BEFORE
    the dispatch reads freed memory.  This is the host-side twin of
    :func:`assert_donated`: that one proves the compiler honored the
    donation, this one proves the *caller* did.

    Works on the CPU test mesh too, where XLA quietly declines the
    donation and reuse returns stale-but-valid numbers — the worst
    variant of the bug, because nothing crashes.
    """

    def __init__(self, fn, donate_argnums: Sequence[int] = (0,),
                 label: str = None):
        self._fn = fn
        self._donate = tuple(int(i) for i in donate_argnums)
        self._label = label or getattr(fn, "__name__", "donated program")
        self._dead: Dict[int, Any] = {}  # id -> weakref (or leaf repr)
        self.calls = 0

    def _remember(self, leaf):
        try:
            self._dead[id(leaf)] = weakref.ref(leaf)
        except TypeError:
            self._dead[id(leaf)] = lambda _l=leaf: _l  # strong fallback

    def _check(self, argnum, tree):
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
            if isinstance(leaf, _DonatedLeaf):
                leaf._raise("argument binding")
            ref = self._dead.get(id(leaf))
            if ref is not None and ref() is leaf:
                raise UseAfterDonateError(
                    f"argnum {argnum} leaf "
                    f"{jax.tree_util.keystr(path) or '<root>'} was "
                    f"donated to a previous {self._label} call and "
                    "passed in again; rebind the returned carry (the "
                    "PR 2 aliasing gotcha: device_put/replicate may "
                    "alias host trees — copy before re-donating)"
                )

    def __call__(self, *args, **kwargs):
        if kwargs:
            raise ValueError(
                "DonationGuard requires positional calling (donate "
                "argnums are positional)"
            )
        for i in self._donate:
            if i < len(args):
                self._check(i, args[i])
        out = self._fn(*args)
        for i in self._donate:
            if i < len(args):
                for leaf in jax.tree_util.tree_leaves(args[i]):
                    self._remember(leaf)
        self.calls += 1
        return out


def guard_donation(fn, donate_argnums: Sequence[int] = (0,),
                   label: str = None) -> DonationGuard:
    """Convenience constructor for :class:`DonationGuard`."""
    return DonationGuard(fn, donate_argnums, label)
