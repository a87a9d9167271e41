"""Measured-kernel-time profiling — the pyprof "parse" stage for TPU.

ref: apex/pyprof/parse/ (parse.py:1-50, db.py, kernel.py, nvvp.py): reads
the nvprof SQLite DB, joins *measured* kernel durations to their NVTX
markers, and hands the joined records to the prof stage, which then
reports per-op achieved (not just analytic) efficiency.

TPU version: ``jax.profiler`` writes an XPlane protobuf; the device
plane's "XLA Ops" timeline carries one event per executed HLO instruction
with its measured device duration.  The event name embeds the HLO
instruction name, which joins 1:1 to the optimized HLO text — and the HLO
text carries the ``jax.named_scope`` path in ``metadata={op_name=...}``
plus everything the analytic model (:mod:`apex_tpu.pyprof.prof`) needs.
So the three reference stages map to:

- nvtx markers        -> ``jax.named_scope`` paths in HLO metadata
- parse (nvprof DB)   -> :func:`parse_xplane` over the XPlane proto
- prof (FLOP models)  -> join with :func:`prof.parse_hlo` instructions,
  reporting measured time per scope and achieved vs analytic FLOP/s

No TensorFlow/TensorBoard dependency: ``jax.profiler.ProfileData`` (ships
with jaxlib) reads the serialized XSpace directly.

Typical use::

    mp = capture(step_fn, args, trace_dir="/tmp/prof")   # runs + joins
    print(mp.table())

or offline, matching ``python -m apex.pyprof.parse`` / ``prof``::

    python -m apex_tpu.pyprof.prof --trace /tmp/prof

(:func:`capture` saves the optimized HLO text as ``hlo.txt`` inside the
trace dir so the offline CLI can re-join without re-running the model.)
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Sequence, Tuple

from apex_tpu.pyprof.prof import (
    Instruction,
    _OPNAME_RE,
    parse_hlo,
)

__all__ = [
    "KernelTime",
    "MeasuredProfile",
    "MeasuredRow",
    "capture",
    "find_xplane",
    "join",
    "parse_chrome_trace",
    "parse_xplane",
]

# event names: TPU "XLA Ops" events read "%instr_name = f32[...] opcode(...)";
# CPU per-op events are just "instr_name"; both may repeat per step
_EVENT_INSTR_RE = re.compile(r"^%([\w.\-]+)\s*=")
# computation header in optimized HLO text: "%fused_computation (p0: ...) -> ... {"
_COMP_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\([^)]*\)\s*->.*\{")
_CALLS_RE = re.compile(r"calls=%?([\w.\-]+)")
# any instruction line, independent of prof.py's stricter shape parsing
# (tuple shapes with layout annotations defeat a shape regex; for the
# measured join we only need name + metadata + calls + container-ness)
_NAME_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=")
# events on these double-count their children (the per-op timeline also
# reports every instruction INSIDE the loop/call body)
_CONTAINER_MARKS = (" while(", " conditional(", " call(", " async-start(")
# scan/autodiff wrappers that hide the model scopes in a scanned train
# step: jit(...)/while/body/closed_call/transpose(jvp(Model))/stage1/...
_WRAPPER_PARTS = {"while", "body", "cond", "closed_call", "checkpoint"}
_BWD_RE = re.compile(r"^transpose\(")
_UNWRAP_RE = re.compile(r"^(?:jvp|vmap|remat|transpose)\((.*)\)$")


def _clean_scope(op_name: str, depth: int) -> str:
    """Scope key for aggregation: drops jit()/scan wrappers, unwraps
    jvp()/transpose() decorations (a leading ``bwd/`` marks the
    backward), keeps ``depth`` levels of the model path."""
    parts = [p for p in op_name.split("/") if p]
    bwd = any(_BWD_RE.match(p) for p in parts)
    cleaned = []
    for p in parts:
        if p.startswith("jit(") or p in _WRAPPER_PARTS:
            continue
        while True:
            m = _UNWRAP_RE.match(p)
            if not m:
                break
            p = m.group(1)
        if p:
            cleaned.append(p)
    # the unwrapped model-class name (e.g. "ResNet") is a constant prefix
    if len(cleaned) > 1:
        cleaned = cleaned[1:]
    if not cleaned:
        return "<unattributed>"
    key = "/".join(cleaned[:depth]) if depth > 0 else "/".join(cleaned)
    return f"bwd/{key}" if bwd else key


@dataclasses.dataclass
class KernelTime:
    """Measured device time for one HLO instruction (summed occurrences)."""

    name: str
    duration_ns: float = 0.0
    count: int = 0


def find_xplane(trace_dir: str) -> str:
    """Newest ``*.xplane.pb`` under a ``jax.profiler.trace`` directory."""
    files = sorted(glob.glob(
        os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True
    ))
    if not files:
        raise FileNotFoundError(f"no *.xplane.pb under {trace_dir!r}")
    return max(files, key=os.path.getmtime)


def parse_xplane(path: str) -> Dict[str, KernelTime]:
    """Measured per-instruction device times from an XPlane proto file.

    Prefers accelerator planes ("/device:TPU:n"); falls back to the host
    plane's per-op execution line (the CPU backend) so the join is
    testable without hardware.  Times are summed over occurrences (a
    train step traced for k iterations reports k x per-step time; the
    ``count`` field lets callers normalize).
    """
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(path).planes
    per_device: Dict[str, Dict[str, KernelTime]] = {}
    host: Dict[str, KernelTime] = {}

    def add(table, name, dur_ns):
        m = _EVENT_INSTR_RE.match(name)
        key = m.group(1) if m else name.split()[0] if name else name
        if not key or key.startswith(("end:", "$")):
            return
        kt = table.get(key)
        if kt is None:
            kt = table[key] = KernelTime(name=key)
        kt.duration_ns += float(dur_ns or 0.0)
        kt.count += 1

    for plane in planes:
        is_device = plane.name.startswith("/device:")
        is_host_ops = plane.name.startswith("/host:")
        if not (is_device or is_host_ops):
            continue
        for line in plane.lines:
            # TPU: "XLA Ops" is the per-instruction TensorCore timeline
            # (skip "Async XLA Ops"/overlays — they double-count); CPU:
            # the tf_XLA... thread line carries per-op events
            if is_device and line.name != "XLA Ops":
                continue
            if not is_device and not line.name.startswith("tf_"):
                continue
            for ev in line.events:
                table = (per_device.setdefault(plane.name, {})
                         if is_device else host)
                add(table, ev.name, ev.duration_ns)
    if per_device:
        # one REPRESENTATIVE device plane (lowest id), not a sum across
        # planes: under SPMD every device runs the same program, and
        # summing 8 planes would report 8x the per-step time
        return per_device[min(per_device)]
    return host


def parse_chrome_trace(path: str) -> Dict[str, KernelTime]:
    """Per-name summed durations from a Chrome ``trace_event`` JSON —
    the :mod:`apex_tpu.obs` bridge: the span tracer's
    ``export_chrome()`` output (host-side spans around dispatches)
    parses into the same ``{name: KernelTime}`` table device timelines
    do, so :class:`MeasuredProfile` machinery (tables, percent-of-
    total) works on a runtime trace with no profiler run.

    Accepts the object form (``{"traceEvents": [...]}``) or a bare
    event list; complete events (``"ph": "X"``) contribute ``dur``
    (µs, the format's unit) converted to ns.  Counter/instant events
    carry no duration and are skipped.
    """
    import json

    with open(path) as f:
        doc = json.load(f)
    events = doc.get("traceEvents", doc) if isinstance(doc, dict) else doc
    times: Dict[str, KernelTime] = {}
    for ev in events:
        if not isinstance(ev, dict) or ev.get("ph") != "X":
            continue
        name = ev.get("name") or "<unnamed>"
        kt = times.get(name)
        if kt is None:
            kt = times[name] = KernelTime(name=name)
        kt.duration_ns += float(ev.get("dur", 0.0)) * 1e3  # us -> ns
        kt.count += 1
    return times


@dataclasses.dataclass
class MeasuredRow:
    """One aggregation row of the joined (measured x analytic) profile."""

    key: str
    time_ns: float = 0.0
    count: int = 0
    flops: float = 0.0
    bytes: float = 0.0

    @property
    def achieved_tflops(self) -> float:
        return self.flops / self.time_ns / 1e3 if self.time_ns else 0.0

    @property
    def achieved_gbps(self) -> float:
        return self.bytes / self.time_ns if self.time_ns else 0.0


@dataclasses.dataclass
class MeasuredProfile:
    """Per-instruction measured times joined to analytic costs + scopes."""

    rows: List[MeasuredRow]  # per instruction, measured-time order
    unmatched_ns: float  # trace time on instructions absent from the HLO
    # capture(chain=True) donates the caller's argument buffers; the final
    # chained output lands here so callers have a LIVE carry to continue
    # with (reusing the passed-in arrays raises a deleted-buffer error)
    final_carry: object = None

    def by_scope(self, depth: int = 2) -> List[MeasuredRow]:
        agg: Dict[str, MeasuredRow] = defaultdict(lambda: MeasuredRow(key=""))
        for r in self.rows:
            key = (_clean_scope(r.key.split("::", 1)[0], depth)
                   if "::" in r.key else r.key)
            a = agg[key]
            a.key = key
            a.time_ns += r.time_ns
            a.count += r.count
            a.flops += r.flops
            a.bytes += r.bytes
        return sorted(agg.values(), key=lambda r: -r.time_ns)

    @property
    def total_ns(self) -> float:
        return sum(r.time_ns for r in self.rows)

    def table(self, depth: int = 2, top: int = 30) -> str:
        rows = self.by_scope(depth)
        total = self.total_ns
        lines = [
            f"{'scope':<44} {'ms':>9} {'%time':>6} {'count':>6} "
            f"{'GFLOP':>9} {'TF/s':>7} {'GB/s':>7}"
        ]
        for r in rows[:top]:
            pct = 100.0 * r.time_ns / total if total else 0.0
            lines.append(
                f"{r.key[:44]:<44} {r.time_ns / 1e6:>9.3f} {pct:>6.1f} "
                f"{r.count:>6} {r.flops / 1e9:>9.3f} "
                f"{r.achieved_tflops:>7.2f} {r.achieved_gbps:>7.1f}"
            )
        lines.append(
            f"{'TOTAL':<44} {total / 1e6:>9.3f} {100.0 if total else 0.0:>6.1f} "
            f"{sum(r.count for r in rows):>6} "
            f"{sum(r.flops for r in rows) / 1e9:>9.3f} "
            f"{(sum(r.flops for r in rows) / total / 1e3 if total else 0):>7.2f} "
            f"{(sum(r.bytes for r in rows) / total if total else 0):>7.1f}"
        )
        if self.unmatched_ns:
            lines.append(
                f"(unmatched trace time: {self.unmatched_ns / 1e6:.3f} ms)"
            )
        return "\n".join(lines)


def _computation_costs(hlo_text: str, instrs: Sequence[Instruction]):
    """Map instruction -> its computation, and computation -> summed cost.

    Trace events are per TOP-LEVEL instruction: a fusion's measured time
    covers its whole fused computation, so the join credits the fusion
    with the analytic cost of the computation it ``calls=``.
    """
    comp_of: Dict[str, str] = {}
    comp = ""
    for line in hlo_text.splitlines():
        m = _COMP_RE.match(line.strip())
        if m:
            comp = m.group(1)
            continue
        m = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", line)
        if m:
            comp_of[m.group(1)] = comp
    comp_flops: Dict[str, float] = defaultdict(float)
    comp_bytes: Dict[str, float] = defaultdict(float)
    for ins in instrs:
        c = comp_of.get(ins.name, "")
        comp_flops[c] += ins.flops
        comp_bytes[c] += ins.bytes
    return comp_flops, comp_bytes


def join(hlo_text: str, times: Dict[str, KernelTime]) -> MeasuredProfile:
    """Join measured times to HLO instructions (the parse->prof handoff).

    Row key is ``"<op_name scope>::<instr name>"`` when the instruction
    carries named-scope metadata, else the instruction name — so
    :meth:`MeasuredProfile.by_scope` can aggregate like the analytic
    profile does.  Loop/call events are dropped (their bodies' per-op
    events are reported individually — counting both double-counts).
    """
    instrs = parse_hlo(hlo_text)
    by_name = {i.name: i for i in instrs}
    comp_flops, comp_bytes = _computation_costs(hlo_text, instrs)
    # raw per-line scan: tolerant of tuple shapes/layout annotations that
    # the analytic parser's shape regex rejects
    meta: Dict[str, Tuple[str, Optional[str], bool]] = {}
    for line in hlo_text.splitlines():
        m = _NAME_RE.match(line)
        if not m:
            continue
        opn = _OPNAME_RE.search(line)
        called = _CALLS_RE.search(line)
        container = any(mark in line for mark in _CONTAINER_MARKS)
        meta[m.group(1)] = (
            opn.group(1) if opn else "",
            called.group(1) if called else None,
            container,
        )
    rows: List[MeasuredRow] = []
    unmatched = 0.0
    for name, kt in times.items():
        mt = meta.get(name)
        if mt is None:
            unmatched += kt.duration_ns
            continue
        op_name, called, container = mt
        if container:
            continue  # its body's events are counted individually
        ins = by_name.get(name)
        flops = ins.flops if ins is not None else 0.0
        nbytes = ins.bytes if ins is not None else 0.0
        if called and called in comp_flops:
            flops += comp_flops[called]
            nbytes += comp_bytes[called]
        key = f"{op_name}::{name}" if op_name else name
        rows.append(
            MeasuredRow(
                key=key, time_ns=kt.duration_ns, count=kt.count,
                flops=flops * kt.count, bytes=nbytes * kt.count,
            )
        )
    rows.sort(key=lambda r: -r.time_ns)
    return MeasuredProfile(rows=rows, unmatched_ns=unmatched)


def capture(
    fn,
    args: Sequence = (),
    *,
    trace_dir: str,
    iters: int = 3,
    static_argnums=(),
    chain: bool = False,
) -> MeasuredProfile:
    """Trace ``iters`` executions of ``jit(fn)(*args)`` and join.

    Also writes the optimized HLO text to ``<trace_dir>/hlo.txt`` so the
    offline CLI (``python -m apex_tpu.pyprof.prof --trace <dir>``) can
    re-join later without re-running the model.

    ``chain=True`` requires a single-argument ``fn`` returning the same
    pytree structure (a train-step carry), donates the argument, and
    feeds each call's output into the next: profiling then needs no
    second copy of the train state in HBM (a memory-tight bench config
    would otherwise OOM under the profiler).  Donation INVALIDATES the
    caller's argument buffers — continue from the returned profile's
    ``final_carry`` (the last chained output), not the passed-in state.
    """
    import jax

    donate = (0,) if chain else ()
    compiled = (
        jax.jit(fn, static_argnums=static_argnums, donate_argnums=donate)
        .lower(*args)
        .compile()
    )
    hlo_text = compiled.as_text()
    out = compiled(*args)  # warm (outside the trace)
    jax.block_until_ready(out)
    if chain:
        args = (out,)
    with jax.profiler.trace(trace_dir):
        for _ in range(iters):
            out = compiled(*args)
            if chain:
                args = (out,)
            jax.block_until_ready(out)
    os.makedirs(trace_dir, exist_ok=True)
    with open(os.path.join(trace_dir, "hlo.txt"), "w") as f:
        f.write(hlo_text)
    mp = join(hlo_text, parse_xplane(find_xplane(trace_dir)))
    mp.final_carry = out
    return mp
