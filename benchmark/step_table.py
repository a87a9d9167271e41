"""A traced window as ONE table: rows ``(scope path, phase, kind)`` that sum
to the chip's busy time.

``program_trace.reduce`` cuts a window by scope and ``trace_reduce.reduce`` by
kind of operation (the ledger's ``breakdown``); neither says WHICH scope's
``fusion``.  This is a view of the same parse — ``program_trace.load``'s
events, ``trace_reduce.self_times``' self times (a ``while`` less its body),
``program_trace.scopes_of``'s scopes, ``trace_reduce.short_name``'s kinds —
so its rows sum to ``program_trace.reduce(trace)["op_ns"]`` to the
nanosecond.  For every event of the first chip's ``XLA Ops`` line:

- **scope path**: the program's scopes along the event's ``op_name``,
  outermost first, ``layer_<i>`` folded to ``layer_*`` (``keep_index``
  keeps the index) and JAX's ``rematted_computation`` taken out: it is no
  scope of the program, it is the phase;
- **phase** (:data:`PHASES`), one an event, in this order: ``optimizer``
  (under ``apex_amp_step``, ``apex_amp_cast`` or an optimizer's own
  ``apex_fused_*`` scope, whatever transformation wraps it), ``recompute``
  (``rematted_computation`` in the path: the forward a ``jax.checkpoint``
  runs again inside the backward), ``backward`` (a ``transpose(`` wraps a
  component), ``forward`` (any other event under a scope of the program),
  ``unscoped`` (no ``op_name``, or JAX's structure alone: exactly what
  ``program_trace.reduce`` counts in ``unscoped_ns``).  Where the compiler
  joined several ``op_name``s with ``;`` the first that has a scope decides;
  the event's time also counts as ``mixed`` where the others disagree, so
  that nobody trusts a split the names cannot make;
- **kind**: ``short_name`` of the event (``fusion``, ``copy``,
  ``apex_flash_fwd`` ...), for an ``unscoped`` event with the instruction's
  result shape (``copy-done f32[32,4096]``), read from the event's own name.

An event is **bare** when the innermost scope of its path is the block
itself (``layer_<i>``): a residual add, a gate, a cast of a block's own
``__call__``.  ``bare`` holds those rows by result shape.  Nothing here
knows a cell, a model or a shape.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

from benchmark import program_trace
from benchmark.trace_reduce import (device_planes, op_events, self_times,
                                    short_name)

PHASES = ("forward", "recompute", "backward", "optimizer", "unscoped")
#: what ``jax.checkpoint`` names the forward it runs again
#: (``jax/_src/ad_checkpoint.py``); ``program_trace.scopes_of`` keeps it
REMAT = "rematted_computation"
_LAYER = re.compile(r"layer_(\d+|\*)")
_OPTIMIZER = re.compile(r"apex_amp_step|apex_amp_cast|apex_fused_\w+")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_RESULT = re.compile(r"^\S+ = (\([^()]*\)|\S+) ")


def phase_of(path: str, scopes: List[str]) -> str:
    """The phase of ONE ``op_name`` path that has ``scopes`` (its
    ``scopes_of``, not empty)."""
    if any(_OPTIMIZER.fullmatch(s) for s in scopes):
        return "optimizer"
    if REMAT in scopes:
        return "recompute"
    return "backward" if "transpose(" in path else "forward"


def classify(op_name: str, keep_index: bool = False
             ) -> Tuple[Tuple[str, ...], str, bool]:
    """``(scope path, phase, mixed)`` of an event's ``op_name``."""
    scoped = [(scopes, phase_of(path, scopes))
              for path in op_name.split(";")
              if (scopes := program_trace.scopes_of(path))]
    if not scoped:
        return (), "unscoped", False
    scopes, phase = scoped[0]
    return (tuple("layer_*" if not keep_index and _LAYER.fullmatch(s) else s
                  for s in scopes if s != REMAT),
            phase, any(other != phase for _, other in scoped[1:]))


def result_shape(event_name: str) -> str:
    """The result of the HLO instruction an event is named by, layouts
    struck: ``%copy-done.4 = f32[32,4096]{1,0:T(8,128)S(1)} copy-done(...)``
    -> ``f32[32,4096]``; ``""`` where the name is no instruction's text."""
    m = _RESULT.match(_LAYOUT.sub("", event_name))
    return m.group(1) if m else ""


def table(trace: Dict, keep_index: bool = False) -> Dict:
    """Of the first chip's ``XLA Ops`` line, in nanoseconds of self time:
    ``rows`` (``{(scope path joined by "/", phase, kind): [ns, calls, ns of
    it that is mixed]}``), ``bare`` (``{(path, phase, kind, result shape):
    [ns, calls]}`` over the bare events), and the sums ``phase_ns`` (every
    phase a key), ``op_ns``, ``mixed_ns``, ``bare_ns`` and ``layer_ns`` (the
    time under any ``layer_<i>``)."""
    planes = device_planes(trace)
    events = sorted(op_events(planes[0]),
                    key=lambda ev: (ev[1], -ev[2])) if planes else []
    rows: Dict[Tuple[str, str, str], List[int]] = {}
    bare: Dict[Tuple[str, str, str, str], List[int]] = {}
    phase_ns = dict.fromkeys(PHASES, 0)
    mixed_ns = bare_ns = layer_ns = 0
    classified = functools.lru_cache(maxsize=None)(
        lambda op_name: classify(op_name, keep_index))
    # self_times sorts by the same key (stably): its rows are ours, in order
    for ev, (name, self_ns) in zip(events,
                                   self_times([ev[:3] for ev in events])):
        stats = ev[3] if len(ev) > 3 else {}
        path, phase, mixed = classified(stats.get("op_name", ""))
        kind = short_name(name)
        if phase == "unscoped":
            kind = f"{kind} {result_shape(name)}".rstrip()
        row = rows.setdefault(("/".join(path), phase, kind), [0, 0, 0])
        row[0] += self_ns
        row[1] += 1
        phase_ns[phase] += self_ns
        if mixed:
            row[2] += self_ns
            mixed_ns += self_ns
        if any(_LAYER.fullmatch(s) for s in path):
            layer_ns += self_ns
            if _LAYER.fullmatch(path[-1]):
                bare_ns += self_ns
                row = bare.setdefault(
                    ("/".join(path), phase, kind, result_shape(name)), [0, 0])
                row[0] += self_ns
                row[1] += 1
    return {"rows": rows, "bare": bare, "phase_ns": phase_ns,
            "op_ns": sum(phase_ns.values()), "mixed_ns": mixed_ns,
            "bare_ns": bare_ns, "layer_ns": layer_ns}


# -- what the readers call ----------------------------------------------------

def newest_profile(trace_dir: str = program_trace.TRACE_DIR) -> Optional[str]:
    """The newest ``.xplane.pb`` under ``trace_dir`` (the file
    ``program_trace.newest`` reduces); None where there is none."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


@functools.lru_cache(maxsize=1)
def newest() -> Optional[Dict]:
    """The table of :func:`newest_profile`; loaded once a process — a second
    parse of the profile.  None where there is none."""
    path = newest_profile()
    return table(program_trace.load(path)) if path else None


def of(run: Dict) -> Optional[Tuple[Dict, int]]:
    """``(table, optimizer steps)`` of a traced train run's record; None for
    an untraced record (nothing is read) and where the program wrote no
    dispatch span to count steps by (an older commit)."""
    t = program_trace.of(run)
    if not t or not t["steps"]:
        return None
    tab = newest()
    return (tab, t["steps"]) if tab else None


def phase_ms_per_step(run: Dict, phase: str) -> Optional[float]:
    """Self time a step of the events of ``phase``, in milliseconds."""
    got = of(run)
    return got[0]["phase_ns"][phase] * 1e-6 / got[1] if got else None
