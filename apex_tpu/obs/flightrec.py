"""Flight recorder — always-on bounded ring of boundary events.

MegaScale (PAPERS.md) credits much of its 10k-GPU operability to
postmortem-capable event capture: when a run dies, what matters is the
*sequence of events leading up to the death*, not the aggregate
counters that survive it.  The repo's metrics registry and span tracer
(PR 6) are aggregates and timelines for the happy path; this module is
the black box for the unhappy one:

- a :class:`FlightRecorder` is a fixed-capacity ring of structured
  events — train dispatches, serve admit/prefill/decode boundaries,
  fleet routing/eviction decisions, fault-injector firings, SLO alert
  transitions, checkpoint saves/restores — each stamped with a
  monotonically increasing sequence number, a timestamp from the
  recorder's **injectable clock**, and whatever correlation ids the
  call site attaches (request uid, host id, window index, ...);
- recording is **allocation-light**: one tuple written into a
  preallocated slot, no I/O, no device work; a full ring simply
  overwrites the oldest event (``dropped`` counts what fell off);
- the **default stamp is the logical sequence number** (``clock=None``),
  so two runs of the same seeded chaos schedule produce *byte-identical*
  dumps — the replay property every resilience artifact in this repo
  holds.  Inject ``time.perf_counter_ns`` (or the load harness's
  virtual clock) when wall/virtual timestamps matter more than replay;
- on any uncaught failure or resilience-layer recovery the wired
  components dump the last-N events as a machine-readable postmortem —
  ``flightrec.jsonl``, schema ``apex_tpu.obs.v1``, one JSON object per
  line, written atomically (tmp + ``os.replace``).  The dump target is
  the recorder's ``dump_dir`` (or ``APEX_TPU_FLIGHTREC_DIR``); with
  neither set, recording still works but recoveries leave no file.

Kill switches: ``APEX_TPU_FLIGHTREC=0`` disables the recorder alone;
``APEX_TPU_OBS=0`` (the PR 6 master switch) disables it for free along
with the rest of the telemetry layer — a disabled recorder's
``record()`` is a single truthiness check.  ``APEX_TPU_FLIGHTREC=<n>``
(n > 1) sizes the ambient recorder's ring.

Wired into :mod:`apex_tpu.train.driver`, :mod:`apex_tpu.serve.engine`,
:mod:`apex_tpu.resilience` (train + serve), :mod:`apex_tpu.fleet.serve`,
:mod:`apex_tpu.fleet.train` (the elastic gang launcher's
``gang/relaunch`` / ``gang/peer_lost`` / ``gang/resize`` events, with
an automatic dump on every resize — ISSUE 14's byte-replayable elastic
postmortem) and :mod:`apex_tpu.obs.slo`; ``tools/lint_graphs.py``'s
``flightrec_overhead`` check proves a warm traffic pass with the
recorder live records events while adding ZERO backend compiles.
"""
from __future__ import annotations

import json
import os
from typing import Any, Dict, List, Optional, Tuple

from apex_tpu.obs.trace import enabled as obs_enabled

__all__ = [
    "DEFAULT_CAPACITY",
    "FlightRecorder",
    "NULL_FLIGHTREC",
    "SCHEMA",
    "default_flightrec",
    "flightrec_enabled",
    "read_flightrec",
    "reset_default_flightrec",
    "set_flightrec_override",
]

SCHEMA = "apex_tpu.obs.v1"
DEFAULT_CAPACITY = 256
DUMP_NAME = "flightrec.jsonl"

_OVERRIDE: Optional[bool] = None


def flightrec_enabled() -> bool:
    """Whether flight recording is on: free (False) whenever the obs
    master switch is off, else the programmatic override
    (:func:`set_flightrec_override`) wins, else ``APEX_TPU_FLIGHTREC``
    (default on; ``=0`` is the recorder's own kill switch)."""
    if not obs_enabled():
        return False
    if _OVERRIDE is not None:
        return _OVERRIDE
    return os.environ.get("APEX_TPU_FLIGHTREC", "1") != "0"


def set_flightrec_override(value: Optional[bool]) -> None:
    """Force the recorder on/off regardless of the env (None = defer
    to ``APEX_TPU_FLIGHTREC`` again).  The obs master switch still
    wins when it is off."""
    global _OVERRIDE
    _OVERRIDE = value


def _env_capacity() -> int:
    """Ambient ring capacity: ``APEX_TPU_FLIGHTREC=<n>`` with n > 1
    sizes the ring (``1``/unset = the default; ``0`` never reaches
    here — the recorder is disabled)."""
    try:
        n = int(os.environ.get("APEX_TPU_FLIGHTREC", ""))
    except ValueError:
        return DEFAULT_CAPACITY
    return n if n > 1 else DEFAULT_CAPACITY


class FlightRecorder:
    """Fixed-capacity ring of ``(seq, ts, kind, attrs)`` events.

    Args:
      capacity: ring slots; the newest ``capacity`` events survive.
      clock: ns-returning callable stamping each event, or None (the
        default) to stamp the logical sequence number instead — the
        deterministic mode postmortem byte-replay depends on.
      enabled: None -> the ambient :func:`flightrec_enabled` gate,
        else forced.  A disabled recorder's ``record`` is one check.
      dump_dir: where :meth:`dump` writes ``flightrec.jsonl`` when
        called without a path (None -> ``APEX_TPU_FLIGHTREC_DIR`` env;
        unset -> dumps are no-ops returning None).

    Hot-path discipline: call sites guard with ``if fr.enabled:`` so a
    disabled recorder never even builds the attrs dict.
    """

    __slots__ = ("enabled", "capacity", "dump_dir", "dumps",
                 "_clock", "_buf", "_seq")

    def __init__(self, capacity: int = DEFAULT_CAPACITY, clock=None,
                 enabled: Optional[bool] = None,
                 dump_dir: Optional[str] = None):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        self.enabled = (flightrec_enabled() if enabled is None
                        else bool(enabled))
        self.capacity = int(capacity)
        self.dump_dir = dump_dir
        self.dumps = 0
        self._clock = clock
        # a disabled recorder holds NO ring: record() returns before
        # touching it, and the disabled-mode cost is one truthiness
        # check with zero retained allocation
        self._buf: List[Optional[Tuple]] = (
            [None] * self.capacity if self.enabled else []
        )
        self._seq = 0

    # -- recording -------------------------------------------------------

    def record(self, kind: str, /, **attrs: Any) -> None:
        """Append one event (no-op when disabled).  ``attrs`` carry the
        correlation ids (uid/host/window/...; ``kind`` is
        positional-only so an attr may reuse the name — the fault
        injector's ``kind=`` does); keep them to plain JSON-able
        scalars so dumps stay machine-readable."""
        if not self.enabled:
            return
        seq = self._seq
        self._seq = seq + 1
        ts = seq if self._clock is None else self._clock()
        self._buf[seq % self.capacity] = (seq, ts, kind, attrs or None)

    @property
    def recorded(self) -> int:
        """Total events ever recorded (ring retains the last
        ``capacity`` of them)."""
        return self._seq

    @property
    def dropped(self) -> int:
        """Events that fell off the ring."""
        return max(0, self._seq - self.capacity)

    def clear(self) -> None:
        """Rewind the ring (tests, bench legs)."""
        self._seq = 0

    # -- queries ---------------------------------------------------------

    def events(self, last: Optional[int] = None) -> List[Dict[str, Any]]:
        """The retained events, oldest first, as JSON-able dicts
        (``last`` trims to the newest N)."""
        n = min(self._seq, self.capacity)
        if last is not None:
            n = min(n, int(last))
        out: List[Dict[str, Any]] = []
        for i in range(self._seq - n, self._seq):
            ev = self._buf[i % self.capacity]
            if ev is None:
                continue
            seq, ts, kind, attrs = ev
            d: Dict[str, Any] = {"seq": seq, "ts": ts, "kind": kind}
            if attrs:
                d["attrs"] = attrs
            out.append(d)
        return out

    def kinds(self) -> Dict[str, int]:
        """``{kind: count}`` over the retained events (sorted)."""
        out: Dict[str, int] = {}
        for d in self.events():
            out[d["kind"]] = out.get(d["kind"], 0) + 1
        return dict(sorted(out.items()))

    # -- the postmortem --------------------------------------------------

    def dump(self, path: Optional[str] = None, reason: str = "",
             extra_meta: Optional[dict] = None) -> Optional[str]:
        """Write the retained events as ``flightrec.jsonl`` — a meta
        header line (schema, reason, recorded/dropped/capacity) plus
        one sorted-key JSON object per event — atomically (tmp +
        ``os.replace``, the checkpoint discipline).  Returns the path,
        or None when disabled / no destination is configured.  Dumps
        are deterministic: with the default logical clock, two
        identical event sequences dump byte-identically."""
        if not self.enabled:
            return None
        if path is None:
            d = self.dump_dir or os.environ.get("APEX_TPU_FLIGHTREC_DIR")
            if not d:
                return None
            path = os.path.join(d, DUMP_NAME)
        os.makedirs(os.path.dirname(os.path.abspath(path)), exist_ok=True)
        header = {
            "type": "meta", "schema": SCHEMA, "kind": "flightrec",
            "reason": reason, "recorded": self._seq,
            "dropped": self.dropped, "capacity": self.capacity,
        }
        if extra_meta:
            header.update(extra_meta)
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            f.write(json.dumps(header, sort_keys=True) + "\n")
            for d in self.events():
                f.write(json.dumps({"type": "event", **d},
                                   sort_keys=True) + "\n")
        os.replace(tmp, path)
        self.dumps += 1
        return path


def read_flightrec(path: str) -> Tuple[dict, List[dict]]:
    """Parse a :meth:`FlightRecorder.dump` file back into
    ``(meta, events)`` — the postmortem consumer's entry point (a
    directory resolves to its ``flightrec.jsonl``)."""
    if os.path.isdir(path):
        path = os.path.join(path, DUMP_NAME)
    meta: dict = {}
    events: List[dict] = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            d = json.loads(line)
            if d.get("type") == "meta":
                meta = d
            else:
                events.append(d)
    return meta, events


NULL_FLIGHTREC = FlightRecorder(capacity=1, enabled=False)

_DEFAULT: Optional[FlightRecorder] = None


def default_flightrec() -> FlightRecorder:
    """The ambient recorder the library's instrumentation writes to —
    :data:`NULL_FLIGHTREC` whenever recording is disabled (checked per
    call, so flipping the override mid-process takes effect
    immediately)."""
    global _DEFAULT
    if not flightrec_enabled():
        return NULL_FLIGHTREC
    if _DEFAULT is None:
        _DEFAULT = FlightRecorder(capacity=_env_capacity(), enabled=True)
    return _DEFAULT


def reset_default_flightrec() -> None:
    """Drop the ambient recorder (tests, bench A/B legs)."""
    global _DEFAULT
    _DEFAULT = None
