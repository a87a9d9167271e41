"""Host-side span tracer — monotonic, nestable, compile-attributed.

The fused drivers (train PR 1, serve PR 3/5) buy their speed from
dispatch boundaries; nothing so far recorded when those boundaries
actually happen.  This tracer does, under hard constraints:

- **Host-side only.** Spans wrap host code around dispatches; nothing
  is traced *inside* jit, so instrumentation can never add an op, a
  host transfer, or a recompile to a compiled program
  (``tools/lint_graphs.py`` keeps proving the warm paths compile-free
  with instrumentation live).
- **Monotonic clock.** ``time.perf_counter_ns`` — immune to wall-clock
  steps; timestamps are ns since an arbitrary origin, durations are
  exact differences.
- **Allocation-light.** One ``Span`` object (``__slots__``) and two
  clock reads per span; disabled tracing (``APEX_TPU_OBS=0``) costs a
  single truthiness check and returns a shared no-op span.
- **On the profiler's clock too.** Every span is also a
  ``jax.profiler.TraceAnnotation("apex/" + name, **scalar attrs)`` for
  its lifetime: while a profiler session is open the span is an event of
  the host plane of that trace, nested as the tracer nests it, its attrs
  as event stats, next to the device's operations — so an idle gap of
  the device can be put down to what the program was doing.  With no
  session open a span pays one check for it.
- **Bounded.** ``spans`` and ``events`` are rings of
  :data:`DEFAULT_CAPACITY` entries each (like the flight recorder's): a
  process that never ends keeps the newest and counts the rest in
  ``dropped``.
- **Compile-attributed.** The tracer keeps a PR 4
  :class:`~apex_tpu.analysis.recompile.CompileMonitor` entered for its
  lifetime with an ``on_compile`` callback: every XLA backend compile
  lands on the innermost open span (``span.compiles``), so an
  *executed-vs-compiled* tag rides on every span and a warm-path
  compile is a visible, testable anomaly instead of a silent stall.
  The same bridge takes JAX's other compile-path events
  (:data:`JIT_EVENTS`): seconds of Python tracing, of lowering, of
  backend compile and of loading from the persistent cache, and the
  cache's hits and misses, land on the innermost open span
  (``span.jit``) and, for the ambient tracer, in the ``jit.*`` counters
  of :func:`default_registry`.
- **CPU-clocked.** A span reads the main thread's CPU clock and the
  process's at both ends (``cpu0``/``cpu``, ``cpu_all0``/``cpu_all``):
  a span the host slept through reads ``cpu`` ~ 0, one in which Python
  worked reads ``cpu`` ~ ``dur``, one in which the runtime's threads
  compiled or loaded reads ``cpu_all`` >> ``cpu``.  ``cpu0`` of the next
  span less ``cpu0 + cpu`` of this one is the CPU of the gap between.
- **GC-aware.** The ambient tracer owns a ``gc.callbacks`` hook: each
  collection's pause is kept as ``(t0, dur, generation)`` in the ring
  ``gc_pauses`` (its own: never a span or an event) and in the
  ``host.gc_ms`` histogram of :func:`default_registry`.

::

    tr = Tracer()
    with tr.span("serve/decode_window", k=8) as sp:
        cache, toks = decoder.paged_decode_window(...)
    tr.counter("serve/pages_in_use", pool.in_use)
    tr.export_jsonl("trace.jsonl"); tr.export_chrome("trace.json")

Module-level singletons (:func:`default_tracer`,
:func:`default_registry`) give the library's built-in instrumentation
one ambient destination; ``APEX_TPU_OBS=0`` (or
:func:`set_enabled_override`) swaps the tracer for
:data:`NULL_TRACER`.
"""
from __future__ import annotations

import collections
import gc
import os
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from apex_tpu.analysis.recompile import CompileMonitor
from apex_tpu.obs.metrics import MetricsRegistry

__all__ = [
    "NULL_TRACER",
    "Span",
    "Tracer",
    "default_registry",
    "default_tracer",
    "enabled",
    "reset_default",
    "set_enabled_override",
]

_ENABLED_OVERRIDE: Optional[bool] = None

#: ring slots for finished spans, and as many for instant/counter events
DEFAULT_CAPACITY = 16384
#: what every span's event in a profiler trace is named by
PROFILER_PREFIX = "apex/"
#: how many compile-path intervals are kept until a later one wraps them:
#: one window program's trace holds ~10,000 inner traces (GPT-2 small) to a
#: few ten thousand, all children of the one outer trace that ends last;
#: a ring shorter than that charges the outer trace its children's time too
_JIT_ROOTS = 1 << 17
#: the ``jax.monitoring`` events the compile bridge takes, as JAX 0.9.0
#: fires them, and the key each lands under (``span.jit``, ``jit.<key>``
#: in the registry).  Seconds are EXCLUSIVE: JAX fires an event as its
#: interval ends and intervals nest (an inner ``jit``'s trace inside the
#: outer's, helper traces inside lowering, the cache's retrieval inside
#: ``backend_compile_duration`` -- which wraps ``compile_or_get_cached``
#: and so fires on a persistent-cache hit too), so a wrapper is charged
#: what its children did not take and the four sum to wall time at most.
JIT_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
    "/jax/compilation_cache/cache_retrieval_time_sec": "cache_load_s",
    "/jax/compilation_cache/cache_hits": "cache_hits",
    "/jax/compilation_cache/cache_misses": "cache_misses",
}


def enabled() -> bool:
    """Whether obs instrumentation is on: the programmatic override
    (:func:`set_enabled_override`) wins, else ``APEX_TPU_OBS`` (default
    on; ``=0`` is the kill switch)."""
    if _ENABLED_OVERRIDE is not None:
        return _ENABLED_OVERRIDE
    return os.environ.get("APEX_TPU_OBS", "1") != "0"


def set_enabled_override(value: Optional[bool]) -> None:
    """Force instrumentation on/off regardless of the env (None =
    defer to ``APEX_TPU_OBS`` again).  The tests' A/B lever."""
    global _ENABLED_OVERRIDE
    _ENABLED_OVERRIDE = value


class Span:
    """One finished (or open) span: name, [t0, t0+dur) in clock ns,
    nesting depth, free-form attrs, and the number of XLA backend
    compiles that fired while it was the innermost open span.

    ``cpu0`` / ``cpu`` are the main thread's CPU ns when the span opened
    and over it, ``cpu_all0`` / ``cpu_all`` the whole process's;
    ``profiled`` says a profiler session was open when the span opened
    (it is then an event of that profile too); ``jit`` is None or the
    :data:`JIT_EVENTS` seconds and counts that fired under the span."""

    __slots__ = ("name", "t0", "dur", "depth", "attrs", "compiles",
                 "cpu0", "cpu", "cpu_all0", "cpu_all", "profiled", "jit")

    def __init__(self, name: str, t0: int, depth: int,
                 attrs: Optional[Dict[str, Any]],
                 cpu0: int = 0, cpu_all0: int = 0):
        self.name = name
        self.t0 = t0
        self.dur = 0
        self.depth = depth
        self.attrs = attrs
        self.compiles = 0
        self.cpu0 = cpu0
        self.cpu = 0
        self.cpu_all0 = cpu_all0
        self.cpu_all = 0
        self.profiled = False
        self.jit: Optional[Dict[str, float]] = None

    def set(self, key: str, value: Any) -> None:
        """Attach/overwrite one attr on an open span."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value

    @property
    def compiled(self) -> bool:
        """Executed-vs-compiled tag: did this span trigger a compile?"""
        return self.compiles > 0

    def to_dict(self) -> Dict[str, Any]:
        d: Dict[str, Any] = {
            "type": "span", "name": self.name, "ts": self.t0,
            "dur": self.dur, "depth": self.depth,
            "compiles": self.compiles,
            "cpu0": self.cpu0, "cpu": self.cpu,
            "cpu_all0": self.cpu_all0, "cpu_all": self.cpu_all,
            "profiled": self.profiled,
        }
        if self.jit:
            d["jit"] = self.jit
        if self.attrs:
            d["attrs"] = self.attrs
        return d


class _NullSpan:
    """Shared no-op span: the entire cost of disabled instrumentation."""

    __slots__ = ()
    name = ""
    t0 = dur = depth = compiles = 0
    cpu0 = cpu = cpu_all0 = cpu_all = 0
    attrs = jit = None
    compiled = profiled = False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        pass


_NULL_SPAN = _NullSpan()


class _SpanCtx:
    """Context manager pairing one span's enter/exit with the tracer's
    open-span stack and with the span's annotation in the profiler's
    trace (kept separate from :class:`Span` so finished spans carry no
    manager state)."""

    __slots__ = ("_tracer", "_span", "_annotation")

    def __init__(self, tracer: "Tracer", span: Span):
        self._tracer = tracer
        self._span = span
        self._annotation = None
        if TraceAnnotation.is_enabled():    # a profiler session is open
            span.profiled = True
            # the attrs known when the span opens, scalars only, become
            # the event's stats (``sp.set`` later reaches only the tracer)
            attrs = span.attrs or {}
            self._annotation = TraceAnnotation(
                PROFILER_PREFIX + span.name,
                **{k: v for k, v in attrs.items()
                   if isinstance(v, (bool, int, float, str))})

    def __enter__(self) -> Span:
        if self._annotation is not None:
            self._annotation.__enter__()
        return self._span

    def __exit__(self, *exc):
        if self._annotation is not None:
            self._annotation.__exit__(*exc)
        self._tracer._finish(self._span)
        return False


class Tracer:
    """Nestable host-side span recorder.

    Args:
      enabled: None = the ambient :func:`enabled` gate, else forced.
      clock: ns-returning monotonic callable (default
        ``time.perf_counter_ns``; tests inject a fake).
      monitor_compiles: bridge a :class:`CompileMonitor` for the
        tracer's lifetime so spans carry compile attribution (default
        on; pointless for fake-clock unit tracers).

    Finished spans land in the ring ``.spans`` (order = finish order,
    Chrome-trace convention); instant/counter events in the ring
    ``.events`` as ``(ts, kind, name, payload)`` tuples.  Each ring keeps
    its newest :data:`DEFAULT_CAPACITY` entries; ``recorded`` counts all
    ever made and ``dropped`` those that fell off.  ``close()`` detaches the
    compile listener (and the ambient tracer's GC hook); tracers are
    single-threaded like the schedulers they instrument.

    ``thread_clock`` / ``process_clock`` (``time.thread_time_ns`` /
    ``time.process_time_ns``) are attributes a test may replace.
    ``gc_pauses`` is the ring of ``(t0, dur, generation)`` the GC hook
    fills; only :func:`default_tracer` installs the hook, and neither it
    nor the compile bridge's seconds touch ``spans``, ``events`` or
    ``recorded``.
    """

    def __init__(self, enabled: Optional[bool] = None, clock=None,
                 monitor_compiles: bool = True):
        self.enabled = _enabled_default() if enabled is None else enabled
        self.clock = clock or time.perf_counter_ns
        self.thread_clock = time.thread_time_ns
        self.process_clock = time.process_time_ns
        self.spans: Deque[Span] = collections.deque(maxlen=DEFAULT_CAPACITY)
        self.events: Deque[Tuple[int, str, str, Any]] = collections.deque(
            maxlen=DEFAULT_CAPACITY)
        self.gc_pauses: Deque[Tuple[int, int, int]] = collections.deque(
            maxlen=DEFAULT_CAPACITY)
        self.recorded = 0
        self.compiles = 0
        self._stack: List[Span] = []
        # (start ns, ns) of the compile-path intervals that no later one
        # has yet been seen to wrap: see _on_jit_event
        self._jit_roots: Deque[Tuple[int, int]] = collections.deque(
            maxlen=_JIT_ROOTS)
        self._ambient = False       # default_tracer()'s: owns the GC hook
        self._gc_t0: Optional[int] = None
        self._monitor: Optional[CompileMonitor] = None
        if self.enabled and monitor_compiles:
            self._monitor = CompileMonitor(on_compile=self._on_compile,
                                           on_event=self._on_jit_event)
            self._monitor.__enter__()

    # -- recording -----------------------------------------------------

    def span(self, name: str, **attrs):
        """Open a nested span; use as ``with tracer.span("x") as sp:``.
        Returns the shared no-op span when disabled."""
        if not self.enabled:
            return _NULL_SPAN
        sp = Span(name, self.clock(), len(self._stack), attrs or None,
                  self.thread_clock(), self.process_clock())
        self._stack.append(sp)
        return _SpanCtx(self, sp)

    def _finish(self, sp: Span) -> None:
        sp.dur = self.clock() - sp.t0
        sp.cpu = self.thread_clock() - sp.cpu0
        sp.cpu_all = self.process_clock() - sp.cpu_all0
        # tolerate exception-path unwinding out of order: pop through
        if self._stack and self._stack[-1] is sp:
            self._stack.pop()
        elif sp in self._stack:
            self._stack.remove(sp)
        self.spans.append(sp)
        self.recorded += 1

    def instant(self, name: str, **attrs) -> None:
        """Zero-duration event (retirement, preemption, anomaly)."""
        if self.enabled:
            self.events.append(
                (self.clock(), "instant", name, attrs or None)
            )
            self.recorded += 1

    def counter(self, name: str, value) -> None:
        """Timestamped counter sample — the timeline primitive
        (page-pool utilization, active slots, queue depth)."""
        if self.enabled:
            self.events.append((self.clock(), "counter", name, value))
            self.recorded += 1

    def _on_compile(self, dur_s: float) -> None:
        self.compiles += 1
        if self._stack:
            self._stack[-1].compiles += 1

    def _on_jit_event(self, event: str, value: float) -> None:
        """One ``jax.monitoring`` event of :data:`JIT_EVENTS`: a count,
        or seconds made exclusive.  JAX fires a duration as its interval
        ends, so what this one wraps has already arrived: the roots that
        began inside it are its children, and it is charged the rest."""
        key = JIT_EVENTS.get(event)
        if key is None or (self._ambient and not enabled()):
            return
        if key.endswith("_s"):
            ns = int(value * 1e9)
            start = self.clock() - ns
            roots = self._jit_roots
            while roots and roots[-1][0] >= start:
                value -= roots.pop()[1] * 1e-9
            roots.append((start, ns))
            value = max(value, 0.0)
        if self._stack:
            sp = self._stack[-1]
            if sp.jit is None:
                sp.jit = {}
            sp.jit[key] = sp.jit.get(key, 0) + value
        if self._ambient:
            default_registry().counter("jit." + key).inc(value)

    def _on_gc(self, phase: str, info: Dict[str, int]) -> None:
        try:
            if phase == "start":
                # an override flipped off mid-process silences the hook
                self._gc_t0 = self.clock() if enabled() else None
            elif self._gc_t0 is not None:
                t0, self._gc_t0 = self._gc_t0, None
                dur = self.clock() - t0
                self.gc_pauses.append((t0, dur, info.get("generation", -1)))
                default_registry().histogram("host.gc_ms").observe(
                    dur * 1e-6)
        except Exception:
            pass    # the interpreter is shutting down under the hook

    # -- lifecycle -----------------------------------------------------

    def _make_ambient(self) -> "Tracer":
        """What only the process's one ambient tracer does: feed the
        ``jit.*`` counters and time the garbage collector's pauses."""
        self._ambient = True
        gc.callbacks.append(self._on_gc)
        return self

    def close(self) -> None:
        """Detach the compile listener and the GC hook (idempotent)."""
        if self._monitor is not None:
            self._monitor.__exit__(None, None, None)
            self._monitor = None
        if self._ambient:
            self._ambient = False
            if self._on_gc in gc.callbacks:
                gc.callbacks.remove(self._on_gc)

    def clear(self) -> None:
        """Drop recorded spans/events (open spans stay open)."""
        self.spans.clear()
        self.events.clear()
        self.gc_pauses.clear()
        self.recorded = 0
        self.compiles = 0

    # -- queries -------------------------------------------------------

    @property
    def dropped(self) -> int:
        """Spans and events that fell off the rings."""
        return self.recorded - len(self.spans) - len(self.events)

    def span_names(self) -> Dict[str, int]:
        """``{name: count}`` over the finished spans kept (sorted)."""
        out: Dict[str, int] = {}
        for sp in self.spans:
            out[sp.name] = out.get(sp.name, 0) + 1
        return dict(sorted(out.items()))

    def compiled_spans(self) -> List[Span]:
        """Spans that triggered at least one backend compile — the
        cold-vs-warm ledger (a warm loop's span here is the anomaly)."""
        return [sp for sp in self.spans if sp.compiles]

    # -- export (delegates; see apex_tpu.obs.export) -------------------

    def export_jsonl(self, path: str,
                     registry: Optional[MetricsRegistry] = None) -> str:
        from apex_tpu.obs.export import write_jsonl

        return write_jsonl(self, path, registry=registry)

    def export_chrome(self, path: str,
                      registry: Optional[MetricsRegistry] = None) -> str:
        from apex_tpu.obs.export import write_chrome_trace

        return write_chrome_trace(self, path, registry=registry)


def _enabled_default() -> bool:
    return enabled()


class _NullTracer(Tracer):
    """The disabled tracer: every entry point is a cheap no-op."""

    def __init__(self):
        super().__init__(enabled=False, monitor_compiles=False)


NULL_TRACER = _NullTracer()

_DEFAULT_TRACER: Optional[Tracer] = None
_DEFAULT_REGISTRY: Optional[MetricsRegistry] = None


def default_tracer() -> Tracer:
    """The ambient tracer the library's instrumentation writes to —
    :data:`NULL_TRACER` whenever obs is disabled (checked per call, so
    flipping the override mid-process takes effect immediately)."""
    global _DEFAULT_TRACER
    if not enabled():
        return NULL_TRACER
    if _DEFAULT_TRACER is None:
        _DEFAULT_TRACER = Tracer(enabled=True)._make_ambient()
    return _DEFAULT_TRACER


def default_registry() -> MetricsRegistry:
    """The ambient metrics registry (always live — counters are cheap
    and ``stats()``-style shims must work with tracing off)."""
    global _DEFAULT_REGISTRY
    if _DEFAULT_REGISTRY is None:
        _DEFAULT_REGISTRY = MetricsRegistry()
    return _DEFAULT_REGISTRY


def reset_default() -> None:
    """Drop the ambient tracer/registry (tests, bench A/B legs)."""
    global _DEFAULT_TRACER, _DEFAULT_REGISTRY
    if _DEFAULT_TRACER is not None:
        _DEFAULT_TRACER.close()
    _DEFAULT_TRACER = None
    _DEFAULT_REGISTRY = None
