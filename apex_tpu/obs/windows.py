"""A train loop's wall time, window by window, from the tracer's own spans.

``FusedTrainDriver`` opens ``train/dispatch`` around every window's enqueue
and ``read_metrics`` opens ``train/fetch_metrics`` around the blocking fetch
of its result; both carry the window's number (``window``), the main
thread's CPU clock and the process's (:class:`~apex_tpu.obs.trace.Span`).
:func:`train_windows` lays each window out as four parts that follow one
another without a hole::

    ... fetch n-1 | between | enqueue | in flight, host free | wait | between ...
                  ^ t0                                              ^ t0 of n+1

- ``between_ms``: the previous window's fetch returned, this window's
  dispatch has not begun — the chip has nothing queued: the time a step
  waits for data (batch making, logging, a checkpoint, the garbage
  collector);
- ``enqueue_ms``: ``train/dispatch`` — program lookup and the asynchronous
  enqueue (a cold call's trace, lowering, compile or cache load: ``jit``);
- ``inflight_host_ms``: the dispatch returned, the fetch has not begun — the
  host is free while the chip works;
- ``wait_ms``: ``train/fetch_metrics`` — the host blocked on the device.

Each part has its main-thread CPU (``*_cpu_ms``) and the whole process's
(``*_cpu_all_ms``): a part the host slept through reads ~0, one in which
Python or the collector worked reads about its wall time, one in which the
runtime's own threads worked (a compile, a program load, a transfer) reads
``cpu_all`` well above ``cpu``.  A straggling window in a long job is then
one row: which part held the time, and whether the host worked or waited.
"""
from __future__ import annotations

import bisect
import itertools
from typing import Any, Dict, Iterable, List, Optional

__all__ = ["DISPATCH_SPAN", "FETCH_SPAN", "train_windows"]

DISPATCH_SPAN = "train/dispatch"
FETCH_SPAN = "train/fetch_metrics"
_WINDOW_SPANS = (DISPATCH_SPAN, FETCH_SPAN)

_MS = 1e-6      # per ns
_PARTS = ("between", "enqueue", "inflight_host", "wait")


def _end(span: Dict, key: str = "ts", dur: str = "dur") -> int:
    return span.get(key, 0) + span.get(dur, 0)


def _gap(before: Dict, after: Dict) -> Dict[str, int]:
    """Wall, main-thread CPU and process CPU ns between two spans."""
    return {"dur": after["ts"] - _end(before),
            "cpu": after.get("cpu0", 0) - _end(before, "cpu0", "cpu"),
            "cpu_all": (after.get("cpu_all0", 0)
                        - _end(before, "cpu_all0", "cpu_all"))}


def _source(tracer, rows: Optional[Iterable[Dict]]):
    """``(window spans as dicts, gc pauses as (t0, dur))`` from exported
    rows (:func:`~apex_tpu.obs.export.read_jsonl`'s events) or a tracer."""
    if rows is not None:
        rows = list(rows)
        spans = [r for r in rows if r.get("type") == "span"
                 and r.get("name") in _WINDOW_SPANS]
        pauses = [(r["ts"], r["dur"]) for r in rows if r.get("type") == "gc"]
    else:
        if tracer is None:
            from apex_tpu.obs.trace import default_tracer

            tracer = default_tracer()
        spans = [sp.to_dict() for sp in tracer.spans
                 if sp.name in _WINDOW_SPANS]
        pauses = [(t0, dur) for t0, dur, _ in tuple(tracer.gc_pauses)]
    spans = [s for s in spans
             if (s.get("attrs") or {}).get("window") is not None]
    return sorted(spans, key=lambda s: s["ts"]), sorted(pauses)


def train_windows(tracer=None, *, rows: Optional[Iterable[Dict]] = None
                  ) -> List[Dict[str, Any]]:
    """One row a dispatched window, in the order they were dispatched —
    from ``tracer`` (default: the ambient one), or from the ``rows`` an
    exported ``trace.jsonl`` reads back as.

    A row holds ``window`` and ``k`` (the dispatch span's), ``t0`` (clock
    ns at which the row begins), the four parts in ms with their CPU
    (module docstring), ``wall_ms`` (their sum) with ``cpu_ms`` and
    ``cpu_all_ms``, ``gc_ms`` (collector pauses that began inside the
    row), ``compiles`` and ``jit`` (what the compile bridge put on the
    two spans) and ``profiled`` (a profiler session was open over the
    dispatch or the fetch: its own start and stop sit in the gaps beside).

    A part that was not seen is None and is left out of the sums: a
    window that was never fetched through ``read_metrics`` has neither
    ``inflight_host_ms`` nor ``wait_ms``; one whose predecessor was not
    (or whose predecessor's fetch returned after this dispatch began: a
    loop that dispatches ahead, where rows overlap) has no ``between_ms``.
    Where every window is fetched before the next is dispatched, row
    ``n``'s ``t0 + wall`` is row ``n+1``'s ``t0``: the rows tile the
    loop's wall time.  Spans without ``window`` (an older program, a fetch
    of a tree no window made) are not windows and make no row."""
    spans, pauses = _source(tracer, rows)
    pause_t0 = [at for at, _ in pauses]
    pause_ns = [0, *itertools.accumulate(dur for _, dur in pauses)]
    out: List[Dict[str, Any]] = []
    unfetched: Dict[Any, Dict] = {}     # window -> its row, until fetched
    for span in spans:
        window = span["attrs"]["window"]
        if span["name"] == DISPATCH_SPAN:
            row = {"window": window, "dispatch": span, "fetch": None}
            out.append(row)
            unfetched[window] = row
        elif window in unfetched:
            unfetched.pop(window)["fetch"] = span

    previous = None
    for row in out:
        dispatch, fetch = row.pop("dispatch"), row.pop("fetch")
        parts: Dict[str, Optional[Dict]] = dict.fromkeys(_PARTS)
        if previous is not None and _end(previous) <= dispatch["ts"]:
            parts["between"] = _gap(previous, dispatch)
        parts["enqueue"] = dispatch
        if fetch is not None:
            parts["inflight_host"] = _gap(dispatch, fetch)
            parts["wait"] = fetch
        seen = [p for p in parts.values() if p is not None]
        between, fetched = parts["between"], fetch or {}
        t0 = dispatch["ts"] - (between["dur"] if between else 0)
        t1 = _end(fetch if fetch is not None else dispatch)
        jit: Dict[str, float] = {}
        for span in (dispatch, fetched):
            for key, value in (span.get("jit") or {}).items():
                jit[key] = jit.get(key, 0) + value
        row.update(k=dispatch["attrs"].get("k"), t0=t0)
        for name, part in parts.items():
            for key, field in (("dur", "_ms"), ("cpu", "_cpu_ms"),
                               ("cpu_all", "_cpu_all_ms")):
                row[name + field] = (None if part is None
                                     else part.get(key, 0) * _MS)
        row.update(
            wall_ms=sum(p["dur"] for p in seen) * _MS,
            cpu_ms=sum(p.get("cpu", 0) for p in seen) * _MS,
            cpu_all_ms=sum(p.get("cpu_all", 0) for p in seen) * _MS,
            gc_ms=(pause_ns[bisect.bisect_left(pause_t0, t1)]
                   - pause_ns[bisect.bisect_left(pause_t0, t0)]) * _MS,
            compiles=dispatch.get("compiles", 0) + fetched.get("compiles", 0),
            jit=jit,
            profiled=bool(dispatch.get("profiled")
                          or fetched.get("profiled")))
        previous = fetch
    return out
