"""Recompile + host-transfer detectors.

The dispatch-fusion layers (PR 1 train driver, PR 3 serve decoder) buy
their speed from programs that compile ONCE and run many times; both
are silently defeated by shape-varying loops (one XLA compile per
sequence length — the bug class ``serve.decode.reference_generate``
pads a fixed-width buffer to avoid) and by host transfers hiding inside
a "fused" program (a callback or infeed turns one dispatch into a
device-host round trip per step).  Neither failure crashes — they just
turn a 10 ms window into seconds — so this module makes both countable:

- :class:`CompileMonitor` — counts backend compiles via
  ``jax.monitoring`` (the ``/jax/core/compile/backend_compile_duration``
  event fires exactly once per compile-cache MISS, never on a hit) and
  tracks named jitted functions' live program counts
  (:func:`jit_cache_size`).  ``monitor.check(max_compiles=N)`` raises
  :class:`RecompileError` when a loop compiled more programs than its
  shape contract allows.
- :func:`host_transfers` — scans lowered StableHLO text for
  device-host traffic (python callbacks, infeed/outfeed, host
  send/recv); :func:`assert_no_host_transfers` is the gate.  Mosaic
  kernel custom calls are NOT transfers and never match.

Both are backend-free: the monitor counts CPU-mesh compiles identically
to TPU ones, and the text scan needs no devices at all.
"""
from __future__ import annotations

import re
from typing import Callable, Dict, List, Optional

import jax

__all__ = [
    "CompileMonitor",
    "HOST_TRANSFER_TARGETS",
    "RecompileError",
    "TransferError",
    "assert_no_host_transfers",
    "host_transfers",
    "jit_cache_size",
]

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

# custom_call targets that move data between device and host; Mosaic /
# kernel custom calls (tpu_custom_call, ...) are compute, not transfer
HOST_TRANSFER_TARGETS = frozenset({
    "xla_python_cpu_callback",
    "xla_python_gpu_callback",
    "xla_ffi_python_cpu_callback",
    "xla_ffi_python_gpu_callback",
    "tpu_py_callback",
    "SendToHost",
    "RecvFromHost",
})

_CUSTOM_CALL_RE = re.compile(r"stablehlo\.custom_call\s+@([\w$.]+)")
_FEED_RE = re.compile(r"stablehlo\.(infeed|outfeed|send|recv)\b")


class RecompileError(AssertionError):
    """A program (or loop) compiled more than its shape contract allows."""


class TransferError(AssertionError):
    """A jitted program contains device-host transfers."""


def jit_cache_size(fn) -> Optional[int]:
    """Number of compiled programs a ``jax.jit`` function currently
    holds (None when the object exposes no cache — e.g. a plain
    callable).  One entry per (shape, dtype, static-arg) signature: a
    loop that grows this linearly is recompiling per iteration."""
    try:
        return int(fn._cache_size())
    except Exception:
        return None


class CompileMonitor:
    """Count XLA backend compiles across a region of host code.

    ::

        with CompileMonitor() as mon:
            for ids in batches:          # MUST be shape-stable
                step(pad(ids))
        mon.check(max_compiles=1)        # RecompileError on miss-storm

    ``compiles`` is the number of compile-cache misses observed while
    the monitor was active (jax fires the backend-compile event only on
    a miss, so steady-state loops count 0).  It counts EVERY backend
    compile in the region — including array-creation helpers like a
    per-shape ``jnp.ones`` — so build inputs outside the region, and
    use :meth:`track` for per-function attribution when the budget must
    be tight.  ``track(fn, label)``
    additionally snapshots a jitted function's program-cache size so
    :meth:`report` can attribute growth per function.  Monitors nest;
    each counts independently.  Listener registration survives jax's
    lack of an unregister API in some versions by deactivating the
    callback instead (a dead callback costs one predicate per compile).

    ``on_compile`` is the runtime-telemetry bridge (:mod:`apex_tpu.obs`):
    a callback invoked with the compile duration (seconds) on every
    counted event, so a live tracer can attribute the compile to the
    span that was open when it happened (a warm-path compile then shows
    up as a tagged span, not just a bigger count).  ``on_event`` hands
    the same bridge EVERY ``jax.monitoring`` event while the monitor is
    active, as ``(name, value)``: a duration event's seconds, 1 for a
    plain event (the persistent cache's ``cache_hits`` / ``cache_misses``)
    -- the tracer picks the compile path's out of them.  Note what the
    counted event wraps in JAX 0.9.0: ``compile_or_get_cached``, so it
    fires once per program the in-memory jit cache missed, whether XLA
    then compiled it or the persistent cache handed it back.
    """

    def __init__(self, on_compile: Optional[Callable[[float], None]] = None,
                 on_event: Optional[Callable[[str, float], None]] = None):
        self.compiles = 0
        self._active = False
        self._tracked: Dict[str, tuple] = {}
        self._on_compile = on_compile
        self._on_any = on_event

    # -- context protocol ----------------------------------------------

    @staticmethod
    def _tell(callback, *args) -> None:
        """Telemetry must never break the compile path."""
        if callback is not None:
            try:
                callback(*args)
            except Exception:
                pass

    def _on_event(self, name: str, *args, **kwargs):
        if not self._active:
            return
        dur = float(args[0]) if args else 0.0
        if name == _COMPILE_EVENT:
            self.compiles += 1
            self._tell(self._on_compile, dur)
        self._tell(self._on_any, name, dur)

    def _on_plain_event(self, name: str, **kwargs):
        if self._active:
            self._tell(self._on_any, name, 1.0)

    def __enter__(self):
        self._active = True
        jax.monitoring.register_event_duration_secs_listener(
            self._on_event
        )
        if self._on_any is not None:
            jax.monitoring.register_event_listener(self._on_plain_event)
        return self

    def __exit__(self, *exc):
        self._active = False
        from jax._src import monitoring as _m

        for unregister, listener in (
                ("unregister_event_duration_listener", self._on_event),
                ("unregister_event_listener", self._on_plain_event)):
            try:
                getattr(_m, unregister)(listener)
            except Exception:
                pass  # deactivated above; a dead listener is inert
        return False

    # -- per-function attribution --------------------------------------

    def track(self, fn, label: str = None) -> "CompileMonitor":
        """Snapshot ``fn``'s jit program-cache size under ``label``;
        :meth:`report` shows the growth since.  Chainable."""
        label = label or getattr(fn, "__name__", f"fn{len(self._tracked)}")
        self._tracked[label] = (fn, jit_cache_size(fn) or 0)
        return self

    def report(self) -> Dict[str, int]:
        """``{label: programs compiled since track()}`` for every
        tracked function, plus ``"<backend>"``: the global compile
        count (misses from untracked functions included)."""
        out = {
            label: (jit_cache_size(fn) or 0) - base
            for label, (fn, base) in self._tracked.items()
        }
        out["<backend>"] = self.compiles
        return out

    def check(self, max_compiles: int, label: str = "region") -> int:
        """Raise :class:`RecompileError` when more than ``max_compiles``
        backend compiles happened inside the monitored region — the
        per-sequence-length recompile loop signature.  Returns the
        observed count."""
        if self.compiles > max_compiles:
            per_fn = {k: v for k, v in self.report().items()
                      if k != "<backend>"}
            raise RecompileError(
                f"{label}: {self.compiles} backend compiles, expected "
                f"<= {max_compiles} — a shape-varying loop is "
                f"recompiling per iteration (pad to a fixed width, as "
                f"serve.decode.reference_generate does)"
                + (f"; per-function growth: {per_fn}" if per_fn else "")
            )
        return self.compiles


def host_transfers(stablehlo_text: str) -> List[str]:
    """Device-host transfer sites in a lowered StableHLO module: python
    callback custom_calls (``jax.pure_callback`` / ``io_callback`` /
    ``jax.debug.print``) and infeed/outfeed/host-send ops.  Empty list
    = the program runs device-resident end to end (custom kernel calls
    like Mosaic's do not count)."""
    out = [
        f"custom_call @{m.group(1)}"
        for m in _CUSTOM_CALL_RE.finditer(stablehlo_text)
        if m.group(1) in HOST_TRANSFER_TARGETS
    ]
    out.extend(
        f"stablehlo.{m.group(1)}"
        for m in _FEED_RE.finditer(stablehlo_text)
    )
    return out


def assert_no_host_transfers(stablehlo_text: str,
                             label: str = "program") -> None:
    """Raise :class:`TransferError` when the lowered program contains
    device-host traffic — inside a fused window each one is a
    synchronizing round trip per dispatch (a leftover debug callback is
    the common culprit)."""
    found = host_transfers(stablehlo_text)
    if found:
        raise TransferError(
            f"{label}: {len(found)} host transfer(s) inside a jitted "
            f"program: {sorted(set(found))} — remove debug callbacks "
            "or hoist the host I/O out of the fused window"
        )
