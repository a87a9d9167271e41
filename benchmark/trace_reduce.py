"""From a profiler trace to numbers: device busy time, idle gaps and what the
host was doing in them, the operations that took most time, and the share of
Mosaic (Pallas) kernels.

The reduction works on a plain structure, so that it can be checked on a
small recorded trace (``tests/data/trace_small.json``)::

    {"planes": [{"name": "/device:TPU:0",
                 "lines": [{"name": "XLA Ops",
                            "events": [[name, start_ns, duration_ns], ...]}]}]}

:func:`load_xplane` makes that structure from the ``.xplane.pb`` the JAX
profiler writes, with nothing but JAX.
"""
from __future__ import annotations

import re
from typing import Dict, List, Tuple

#: the line of a device plane that holds the core's operations one after
#: another (the other lines are overlays: steps, modules, async copies)
OPS_LINE = "XLA Ops"
#: host spans the benchmark writes with ``TraceAnnotation``
SPAN_PREFIX = "bench/"
#: an event of that line is named by the whole text of its HLO instruction;
#: a Mosaic (Pallas) kernel is the custom call with this target.  The
#: instruction itself is named after the scope that called the kernel
#: (``%layer_6.15``, ``%ln1.168``, ``%jvp_GPTLM_.7``), not after the kernel
MOSAIC_RE = re.compile(r'custom_call_target="tpu_custom_call"')

Interval = Tuple[int, int]


def load_xplane(path: str, keep_host_prefix: str = SPAN_PREFIX) -> Dict:
    """The trace at ``path`` as the plain structure above: every line of
    every device plane, and of the host planes only the benchmark's own
    spans (a host plane holds millions of Python events)."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events
                      if device or ev.name.startswith(keep_host_prefix)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def device_planes(trace: Dict) -> List[Dict]:
    """One plane per chip's TensorCore, lowest ids first."""
    planes = [p for p in trace["planes"]
              if re.fullmatch(r"/device:TPU:\d+", p["name"])]
    return sorted(planes, key=lambda p: int(p["name"].rsplit(":", 1)[1]))


def op_events(plane: Dict) -> List[List]:
    return [ev for line in plane["lines"] if line["name"] == OPS_LINE
            for ev in line["events"]]


def union(intervals: List[Interval]) -> List[Interval]:
    """Sorted, merged intervals."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def host_spans(trace: Dict) -> List[Tuple[str, int, int]]:
    return sorted(
        (ev[0][len(SPAN_PREFIX):], ev[1], ev[1] + ev[2])
        for p in trace["planes"] if not p["name"].startswith("/device:")
        for line in p["lines"] for ev in line["events"]
        if ev[0].startswith(SPAN_PREFIX))


def innermost_span_at(spans, t: int) -> str:
    """The name of the shortest of the benchmark's spans that covers
    ``t`` — what the host was doing then."""
    best, best_len = "(no span of the benchmark)", None
    for name, s, e in spans:
        if s <= t < e and (best_len is None or e - s < best_len):
            best, best_len = name, e - s
    return best


def self_times(events: List[List]) -> List[Tuple[str, int]]:
    """``(name, self nanoseconds)`` of every event of one line.  A ``while``
    or a ``conditional`` spans the operations of its body, which are events
    of the same line: an event's self time is its duration less that of the
    events nested directly inside it, so that times sum to busy time."""
    out: List[List] = []                    # [name, self_ns]
    stack: List[Tuple[int, int]] = []       # (end, index into out)
    for name, start, dur in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur
        out.append([name, dur])
        stack.append((start + dur, len(out) - 1))
    return [(n, max(0, d)) for n, d in out]


def short_name(name: str) -> str:
    """An event's name without the per-instance suffix XLA gives it
    (``fusion.123`` -> ``fusion``), so that one kind of operation sums."""
    return re.sub(r"[.:]\d+$", "", name.split(" ")[0].lstrip("%"))


def reduce(trace: Dict, chips: int) -> Dict:
    """Busy seconds (union of operation intervals, averaged over the first
    ``chips`` device planes), the traced window (first operation's start to
    last operation's end over those planes), and the breakdowns of the
    first chip: self time by kind of operation and of Mosaic kernels, and
    idle gaps by the benchmark's span that covers them."""
    planes = device_planes(trace)[:chips]
    if not planes:
        raise ValueError(
            "the trace holds no /device:TPU:n plane; planes: "
            f"{[p['name'] for p in trace['planes']]}")
    per_plane = [union([(ev[1], ev[1] + ev[2]) for ev in op_events(p)])
                 for p in planes]
    if not any(per_plane):
        raise ValueError("no operation ran on the device inside the trace")
    t0 = min(iv[0][0] for iv in per_plane if iv)
    t1 = max(iv[-1][1] for iv in per_plane if iv)
    busy = [sum(e - s for s, e in iv) for iv in per_plane]

    first = planes[0]
    by_name: Dict[str, int] = {}
    mosaic = total = 0
    for name, dur in self_times(op_events(first)):
        by_name[short_name(name)] = by_name.get(short_name(name), 0) + dur
        total += dur
        mosaic += dur if MOSAIC_RE.search(name) else 0
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])

    spans = host_spans(trace)
    gaps = [(s, e) for (_, s), (e, _) in zip(
        [(0, t0)] + per_plane[0], per_plane[0] + [(t1, 0)])][1:-1]
    by_span: Dict[str, int] = {}
    for s, e in gaps:
        where = innermost_span_at(spans, (s + e) // 2)
        by_span[where] = by_span.get(where, 0) + (e - s)
    idle = sorted(by_span.items(), key=lambda kv: -kv[1])

    return {
        "busy_s": sum(busy) / len(busy) * 1e-9,
        "window_s": (t1 - t0) * 1e-9,
        "op_time_s": total * 1e-9,
        "mosaic_s": mosaic * 1e-9,
        "device_ops": [[n, d * 1e-9] for n, d in ops[:10]],
        "idle_gaps": [[n, d * 1e-9] for n, d in idle[:10]],
        "longest_gap_s": max((e - s for s, e in gaps), default=0) * 1e-9,
        "planes": [p["name"] for p in planes],
    }
