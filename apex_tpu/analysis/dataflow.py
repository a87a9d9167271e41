"""Jaxpr dataflow pass — donated buffers captured as scan closure consts.

The AST side of the use-after-donate story
(:mod:`apex_tpu.analysis.staticcheck` rule ``use-after-donate``,
:class:`apex_tpu.analysis.donation.DonationGuard`) catches the HOST
replay of a donated tree.  This module catches the sibling bug INSIDE
the traced program, where no host code ever touches the buffer twice:
a ``lax.scan`` body that closes over a leaf of the donated carry.

The trap is easy to spring.  The idiomatic window step reads

::

    @partial(jax.jit, donate_argnums=(0,))
    def window(state, batches):
        anchor = state.params["w0"]          # "just a reference"...
        def body(carry, batch):
            ...anchor...                      # ...now a scan CONST
        return lax.scan(body, state, batches)[0]

In the jaxpr, ``anchor`` becomes one of the scan's
``invars[:num_consts]`` — read on EVERY iteration — while the same
donated buffer is also the carry XLA is being told it may overwrite in
place.  Best case the compiler silently drops the donation and the
window runs at 2x carry HBM (the exact regression
:func:`apex_tpu.analysis.donation.assert_donated` exists to catch,
but only post-compile, on a backend that honors aliasing).  This pass
proves the property at TRACE time, devices-free: walk the jaxpr, map
``donate_argnums`` onto flat invars, and flag every scan whose const
set intersects the donated set.

Scope notes, honestly stated: the pass tracks the donated *invars
themselves* (plus positional flow through ``jit``/``closed_call``
sub-jaxprs and nested scan bodies) — a donated leaf laundered through
an arithmetic op before capture produces a fresh var and is NOT
flagged.  That copy genuinely breaks the alias, so the silence is
correct, not a blind spot.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Dict, List, Sequence, Tuple

import jax

__all__ = [
    "ScanCaptureError",
    "ScanCaptureFinding",
    "assert_no_donated_captures",
    "scan_donated_captures",
]

# primitives whose sub-jaxpr invars map positionally onto eqn.invars
# (a nested ``jax.jit`` is the ``jit`` primitive in the installed jax 0.9)
_CALL_PRIMS = ("jit", "closed_call", "core_call")


class ScanCaptureError(Exception):
    """A donated leaf is captured as a scan closure constant."""


@dataclass(frozen=True)
class ScanCaptureFinding:
    """One donated leaf reaching a scan's const slots."""

    argnum: int          # donated top-level argument index
    path: str            # pytree keystr of the leaf within that arg
    scan_name: str       # primitive name, "scan"
    also_carry: bool     # the same var is simultaneously a scan carry

    def __str__(self) -> str:
        role = "const+carry" if self.also_carry else "const"
        return (
            f"donated arg {self.argnum} leaf {self.path or '<root>'} "
            f"captured as {self.scan_name} closure {role} — the body "
            f"re-reads a buffer XLA was told it may overwrite; bind it "
            f"through the carry (or copy it) instead"
        )


def _donated_invars(
    closed, args: Sequence[Any], donate_argnums: Sequence[int]
) -> Dict[Any, Tuple[int, str]]:
    """Map each donated flat invar Var -> (argnum, leaf keystr).

    Flattened jaxpr invars are contiguous per top-level argument, same
    layout :func:`apex_tpu.analysis.donation.check_donation` leans on.
    """
    donate = frozenset(int(i) for i in donate_argnums)
    out: Dict[Any, Tuple[int, str]] = {}
    pos = 0
    invars = closed.jaxpr.invars
    for i, a in enumerate(args):
        flat = jax.tree_util.tree_flatten_with_path(a)[0]
        if i in donate:
            for (path, _leaf), var in zip(flat, invars[pos:pos + len(flat)]):
                out[var] = (i, jax.tree_util.keystr(path))
        pos += len(flat)
    if pos != len(invars):
        raise ValueError(
            f"flat arg leaves ({pos}) do not line up with jaxpr invars "
            f"({len(invars)}); pass exactly the args the traced call "
            f"takes, positionally"
        )
    return out


def _walk(jaxpr, donated: Dict[Any, Tuple[int, str]],
          findings: List[ScanCaptureFinding]) -> None:
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        if name == "scan":
            nc = eqn.params["num_consts"]
            ncarry = eqn.params["num_carry"]
            consts = eqn.invars[:nc]
            carries = set(eqn.invars[nc:nc + ncarry])
            for v in consts:
                if v in donated:
                    argnum, path = donated[v]
                    findings.append(ScanCaptureFinding(
                        argnum=argnum, path=path, scan_name=name,
                        also_carry=v in carries,
                    ))
            # nested scans capturing an outer donated const: map outer
            # invars onto the body jaxpr positionally and recurse
            body = eqn.params["jaxpr"].jaxpr
            inner = {
                bv: donated[ov]
                for ov, bv in zip(eqn.invars, body.invars)
                if ov in donated
            }
            if inner:
                _walk(body, inner, findings)
        elif name in _CALL_PRIMS and "jaxpr" in eqn.params:
            sub = eqn.params["jaxpr"]
            body = getattr(sub, "jaxpr", sub)
            inner = {
                bv: donated[ov]
                for ov, bv in zip(eqn.invars, body.invars)
                if ov in donated
            }
            if inner:
                _walk(body, inner, findings)


def scan_donated_captures(
    fn, *args, donate_argnums: Sequence[int] = (), **kwargs
) -> List[ScanCaptureFinding]:
    """Trace ``fn(*args)`` and return every donated leaf that a
    ``lax.scan`` in the program captures as a closure constant.

    ``fn`` is the PYTHON callable (not the jitted wrapper) — tracing
    happens here via :func:`jax.make_jaxpr`, so the check runs on a
    devices-free host; ``donate_argnums`` is whatever the real call
    site passes to ``jax.jit``.  Empty list = the donation is clean.
    """
    if kwargs:
        raise ValueError(
            "kwargs-carrying signatures are not supported; pass every "
            "argument positionally (same contract as check_donation)"
        )
    closed = jax.make_jaxpr(fn)(*args)
    donated = _donated_invars(closed, args, donate_argnums)
    findings: List[ScanCaptureFinding] = []
    if donated:
        _walk(closed.jaxpr, donated, findings)
    return findings


def assert_no_donated_captures(
    fn, *args, donate_argnums: Sequence[int] = (), label: str = "program"
) -> None:
    """Raise :class:`ScanCaptureError` if any donated leaf is captured
    as a scan closure constant in the traced ``fn(*args)``."""
    findings = scan_donated_captures(
        fn, *args, donate_argnums=donate_argnums
    )
    if findings:
        lines = "\n  ".join(str(f) for f in findings)
        raise ScanCaptureError(
            f"{label}: {len(findings)} donated leaf/leaves captured as "
            f"scan closure consts:\n  {lines}"
        )
