"""What the PROGRAM's own tracer says about the windows of a train run:
each measured window as the gap before it, its enqueue, the host's time
while it was in flight and the blocked fetch, each with its CPU time
(``apex_tpu.obs.train_windows``), and what the compile bridge counted
(``jit.*`` in ``apex_tpu.obs.default_registry()``).

``program_trace`` reads the two windows the profiler saw; this reads every
window of the run, from spans the program records whether or not a profiler
is open.  The readers are handed a traced train run's record:

- :func:`measured` gives the rows of the LAST ``len(run["window_ms"])``
  windows (the runner's measured ones; the window before them is set-up's
  first, :func:`first`), less the ``profiled`` ones, and with no
  ``between_ms`` on the window that follows a profiled one: the profiler's
  own start sits in the gap before its first window (gone with that
  window), its stop in the gap after its last.  The measured run's first
  window has no ``between_ms`` either (set-up fetched the window before it
  another way), so its ``wall_ms`` reads that gap short: 1-4 ms of
  775-1,390;
- :func:`counter_at_open` gives a ``jit.*`` counter as it stood when the
  first measured window opened.

A record may carry ``obs_jsonl``, the path of a ``trace.jsonl`` that
``apex_tpu.obs.export_default`` wrote: then that is read instead of the live
tracer and registry (``tests/data/windows_recorded.jsonl``).  A program that has
no ``obs.train_windows`` or wrote no ``window`` on its spans (an older
commit), an untraced record, one none of whose measured windows the program
saw profiled (the profiler's start and stop could then not be told from late
windows) and a record that is no train run's all read as None, and the metric
is left out of the line.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple


def _source(run: Dict) -> Optional[Tuple[List[Dict], Dict[str, float]]]:
    """``(a row a window, {counter: value})`` of the run's process, else
    None."""
    if run.get("kind") != "train" or not run.get("trace"):
        return None
    from apex_tpu import obs

    reduce = getattr(obs, "train_windows", None)
    if reduce is None:
        return None
    if run.get("obs_jsonl"):
        events, metrics = obs.read_jsonl(run["obs_jsonl"])
        return reduce(rows=events), {
            name: snap["value"] for name, snap in (metrics or {}).items()
            if snap.get("type") == "counter"}
    registry = obs.default_registry()
    return reduce(), {
        name: registry.get(name).value for name in registry.names()
        if name.startswith("jit.")}


def _split(run: Dict) -> Optional[Tuple[Optional[Dict], List[Dict], Dict]]:
    """``(set-up's first window, the measured windows, counters)``."""
    source = _source(run)
    n = len(run.get("window_ms") or ())
    if source is None or not n or len(source[0]) < n:
        return None
    rows, counters = source
    if not any(row["profiled"] for row in rows[-n:]):
        # a traced record none of whose windows the program saw profiled:
        # the profiler's start and stop cannot be placed, and are long
        return None
    return (rows[-n - 1] if len(rows) > n else None), rows[-n:], counters


def first(run: Dict) -> Optional[Dict]:
    """The row of the window before the measured ones: set-up's first
    window, whose dispatch traced, lowered and compiled or loaded the
    cell's one program."""
    split = _split(run)
    return split[0] if split else None


def measured(run: Dict) -> Optional[List[Dict]]:
    """The measured windows' rows as the module docstring says, each with
    ``host_ms`` (``between_ms + enqueue_ms + inflight_host_ms``, a part
    not seen counted 0); None where there are none to read."""
    split = _split(run)
    if split is None:
        return None
    out, after_profiled = [], False
    for row in split[1]:
        if row["profiled"]:
            after_profiled = True
            continue
        row = dict(row)
        if after_profiled and row["between_ms"] is not None:
            for total, part in (("wall_ms", "between_ms"),
                                ("cpu_ms", "between_cpu_ms"),
                                ("cpu_all_ms", "between_cpu_all_ms")):
                row[total] -= row[part]
                row[part] = None
        after_profiled = False
        row["host_ms"] = sum(row[part] or 0.0 for part in (
            "between_ms", "enqueue_ms", "inflight_host_ms"))
        out.append(row)
    return out or None


def slowest(run: Dict) -> Optional[Dict]:
    """The measured window with the longest ``wall_ms``."""
    rows = measured(run)
    return max(rows, key=lambda row: row["wall_ms"]) if rows else None


def counter_at_open(run: Dict, name: str, key: str) -> Optional[float]:
    """The counter ``name`` (``jit.cache_load_s``) as it stood when the
    first measured window opened: its value now, less what the compile
    bridge put under ``key`` on the measured windows' spans.  What fired
    outside every span since then is still in it; the runner's own
    ``compiles_in_window`` says whether anything did."""
    split = _split(run)
    if split is None:
        return None
    _, rows, counters = split
    return counters.get(name, 0) - sum(
        (row.get("jit") or {}).get(key, 0) for row in rows)
