"""What the PROGRAM's own names say about a traced window: device time by
Pallas kernel, by phase of the step, optimizer steps dispatched, and the idle
time of the chip under each of the program's host spans.

``trace_reduce`` sees the trace from outside (busy, idle, Mosaic as a whole).
This module reads what the program wrote INTO the trace:

- every Pallas kernel's HLO instruction is named from
  ``apex_tpu.ops._common.KERNEL_NAMES`` (``%apex_flash_fwd.84 = ...``);
- every instruction's ``op_name`` holds the ``jax.named_scope`` path it was
  traced under (``jit(window)/while/body/closed_call/jvp(GPTLM)/layer_0/
  apex_flash_fwd/pallas_call``).  On the TPU the profiler keeps it as the
  stat ``tf_op`` of the event's METADATA, which ``jax.profiler.ProfileData``
  does not hand out: :func:`op_names` reads it from the file's own bytes;
- every ``apex_tpu.obs`` span is a host event ``apex/<span>`` with the
  span's scalar attrs as stats (``apex/train/dispatch`` carries ``k``).

The reduction works on the plain structure ``trace_reduce`` uses, an event
being ``[name, start_ns, duration_ns, stats]`` here (device events:
``{"op_name": ...}`` where the instruction has one; host events: the span's
attrs), so that it can be checked on a small recorded trace
(``tests/data/trace_named.json``).  A trace of a program that has none of
these names (an older commit) reduces to zeros and empty tables; the readers
then return None.  Nothing here knows a cell, a model or a shape.
"""
from __future__ import annotations

import functools
import glob
import os
import re
from typing import Dict, Iterator, List, Optional, Tuple

from benchmark.trace_reduce import (MOSAIC_RE, OPS_LINE, device_planes,
                                    self_times, short_name, union)

#: host spans the program's ``obs.Tracer`` writes with ``TraceAnnotation``
SPAN_PREFIX = "apex/"
#: the span around one window's lookup + enqueue; its ``k`` is the number of
#: optimizer steps the window runs
DISPATCH_SPAN = "train/dispatch"
#: where ``harness.Tracer`` keeps the run's profile
TRACE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".bench_trace")

#: components of an ``op_name`` that are JAX's own structure, not a scope
#: the program chose
_STRUCTURAL = re.compile(
    r"jit\(.*\)|pjit|while|body|cond|branch_\d+_fun|closed_call|checkpoint|"
    r"shard_map")
#: ``jvp(GPTLM)``, ``transpose(jvp(GPTLM))``, ``vmap(...)``: a transformation
#: JAX wrapped around the scope
_TRANSFORM = re.compile(r"^\w+\((.*)\)$")


# -- the file -----------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {wire} in an xplane file")
        yield key >> 3, value


def _map_value(entry):
    """The value (field 2) of one entry of a protobuf map."""
    for field, value in _fields(entry):
        if field == 2:
            return value
    return b""


def op_names(path: str) -> Dict[str, str]:
    """``{event name: op_name}`` over the device planes of the ``.xplane.pb``
    at ``path``: the ``tf_op`` stat of each event's metadata, which is the
    instruction's ``metadata.op_name`` (``xplane.proto``: XSpace.planes = 1;
    XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStatMetadata.id = 1, .name = 2;
    XStat.metadata_id = 1, .str_value = 5, .ref_value = 7).  An instruction
    the compiler made itself (a layout copy) has none and is left out."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, str] = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, metas, stat_names = "", [], {}
        for pf, value in _fields(plane):
            if pf == 2:
                name = bytes(value).decode()
            elif pf == 4:
                metas.append(_map_value(value))
            elif pf == 5:
                sid, sname = 0, ""
                for sf, sv in _fields(_map_value(value)):
                    if sf == 1:
                        sid = sv
                    elif sf == 2:
                        sname = bytes(sv).decode()
                stat_names[sid] = sname
        if not name.startswith("/device:"):
            continue
        tf_op = {i for i, n in stat_names.items() if n == "tf_op"}
        for meta in metas:
            ev_name, op = "", None
            for mf, value in _fields(meta):
                if mf == 2:
                    ev_name = bytes(value).decode()
                elif mf == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in tf_op:
                        op = (bytes(stat[5]).decode() if 5 in stat
                              else stat_names.get(stat.get(7), ""))
            if op:
                out[ev_name] = op.rstrip(":")
    return out


def load(path: str) -> Dict:
    """The trace at ``path`` as the plain structure: the ``XLA Ops`` line of
    every device plane, each event with its ``op_name``, and of the host
    planes the program's ``apex/`` spans with their attrs."""
    from jax.profiler import ProfileData

    ops = op_names(path)
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        lines = []
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            if device:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           {"op_name": ops[ev.name]} if ev.name in ops else {}]
                          for ev in line.events]
            else:
                events = [[ev.name, int(ev.start_ns), int(ev.duration_ns),
                           dict(ev.stats)]
                          for ev in line.events
                          if ev.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- the reduction ------------------------------------------------------------

def scopes_of(op_name: str) -> List[str]:
    """The scopes the program chose along ``op_name``, outermost first:
    JAX's structure (``jit(window)``, ``while``, ``body``, ``closed_call``),
    the transformations wrapped around a scope (``transpose(jvp(GPTLM))`` ->
    ``GPTLM``) and the primitive at the end taken off.  The compiler joins
    the ``op_name``s of instructions it merged with ``;``: every one counts."""
    out = []
    for path in op_name.split(";"):
        for part in path.split("/")[:-1]:
            while (m := _TRANSFORM.match(part)) and not part.startswith("jit("):
                part = m.group(1)
            if part and not _STRUCTURAL.fullmatch(part) and part not in out:
                out.append(part)
    return out


def _stats(ev: List) -> Dict:
    """An event's stats; ``trace_reduce``'s three-element events have none."""
    return ev[3] if len(ev) > 3 else {}


def _overlap(a: List[Tuple[int, int]], b: List[Tuple[int, int]]) -> int:
    """Nanoseconds that two sorted lists of disjoint intervals share."""
    total = i = j = 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, hi - lo)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def reduce(trace: Dict) -> Dict:
    """Of the first chip's ``XLA Ops`` line, in nanoseconds of self time
    (``trace_reduce.self_times``: a ``while`` less its body, so that times
    sum to busy time): ``kernels`` by Mosaic instruction (``{name: [ns,
    calls]}``), ``scopes`` by each scope of the program an event lies under
    (an event counts under every scope along its path, so nested scopes
    overlap and siblings do not), ``unscoped_ns`` on events under none, and
    ``op_ns`` / ``mosaic_ns`` over all.  Of the host's ``apex/`` spans:
    ``spans`` (``{name: [count, ns]}``), ``steps`` (the sum of ``k`` over
    ``apex/train/dispatch`` events: counted where the work is dispatched),
    ``windows`` (their number), and ``idle_under_ns``: for each span name the
    time between the chip's operations that its events cover."""
    planes = device_planes(trace)
    events = sorted((ev for line in planes[0]["lines"]
                     if line["name"] == OPS_LINE for ev in line["events"]),
                    key=lambda ev: (ev[1], -ev[2])) if planes else []
    kernels: Dict[str, List[int]] = {}
    scopes: Dict[str, int] = {}
    op_ns = mosaic_ns = unscoped_ns = 0
    # self_times sorts by the same key (stably): its rows are ours, in order
    for ev, (name, self_ns) in zip(events,
                                   self_times([ev[:3] for ev in events])):
        op_ns += self_ns
        if MOSAIC_RE.search(name):
            mosaic_ns += self_ns
            row = kernels.setdefault(short_name(name), [0, 0])
            row[0] += self_ns
            row[1] += 1
        under = scopes_of(_stats(ev).get("op_name", ""))
        if not under:
            unscoped_ns += self_ns
        for scope in under:
            scopes[scope] = scopes.get(scope, 0) + self_ns

    busy = union([(ev[1], ev[1] + ev[2]) for ev in events])
    gaps = [(a[1], b[0]) for a, b in zip(busy, busy[1:])]
    spans: Dict[str, List[int]] = {}
    covered: Dict[str, List[Tuple[int, int]]] = {}
    steps = 0
    for plane in trace["planes"]:
        if plane["name"].startswith("/device:"):
            continue
        for line in plane["lines"]:
            for ev in line["events"]:
                if not ev[0].startswith(SPAN_PREFIX):
                    continue
                name = ev[0][len(SPAN_PREFIX):]
                row = spans.setdefault(name, [0, 0])
                row[0] += 1
                row[1] += ev[2]
                covered.setdefault(name, []).append((ev[1], ev[1] + ev[2]))
                if name == DISPATCH_SPAN:
                    steps += int(_stats(ev).get("k", 0))
    return {
        "op_ns": op_ns, "mosaic_ns": mosaic_ns, "unscoped_ns": unscoped_ns,
        "kernels": kernels, "scopes": scopes, "spans": spans,
        "steps": steps, "windows": spans.get(DISPATCH_SPAN, [0, 0])[0],
        "idle_under_ns": {name: _overlap(gaps, union(ivs))
                          for name, ivs in covered.items()},
    }


# -- what the readers call ----------------------------------------------------

@functools.lru_cache(maxsize=1)
def newest(trace_dir: str = TRACE_DIR) -> Optional[Dict]:
    """The newest profile under ``trace_dir`` (the file
    ``harness.Tracer.reduced`` picked), reduced; loaded once a process.
    None where there is none."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return reduce(load(paths[-1])) if paths else None


def of(run: Dict) -> Optional[Dict]:
    """The reduced program trace of a traced train run's record, else
    None (an untraced record carries no ``trace``, and nothing is read)."""
    if run.get("kind") != "train" or not run.get("trace"):
        return None
    return newest()


def _per_step_ms(t: Optional[Dict], ns) -> Optional[float]:
    """``ns(t)`` over the optimizer steps the trace's dispatch spans carry,
    in milliseconds; None where the program wrote none (an older commit)."""
    return ns(t) * 1e-6 / t["steps"] if t and t["steps"] else None


def kernel_ms_per_step(run: Dict, family: str) -> Optional[float]:
    """Self time a step of the Mosaic kernels whose instruction bears
    ``family`` (``apex_flash_bwd`` sums every backward variant)."""
    return _per_step_ms(of(run), lambda t: sum(
        ns for name, (ns, _) in t["kernels"].items() if family in name))


def scope_ms_per_step(run: Dict, pattern: str) -> Optional[float]:
    """Self time a step under the scopes whose whole name matches
    ``pattern`` (scopes that do not nest in one another)."""
    return _per_step_ms(of(run), lambda t: sum(
        ns for name, ns in t["scopes"].items() if re.fullmatch(pattern, name)))
