"""apex_tpu.resilience — fault injection + self-healing recovery.

The operability pillar (ROADMAP item 3, MegaScale direction): the
difference between a framework that is fast and one that is DEPLOYABLE
is what happens when a dispatch fails, a loss goes NaN, a host is
preempted, or an engine dies mid-stream.  This package makes those
events (a) injectable deterministically — every failure mode is a
replayable regression test keyed by a seed — and (b) survivable, by
wiring the repo's two recovery primitives (bitwise K-boundary
checkpoint resume, PR 1; recompute-preemption from the paged prefix
registry, PR 5) into actively self-healing wrappers:

- :mod:`~apex_tpu.resilience.faults` — :class:`FaultPlan` (seeded,
  byte-for-byte replayable schedules over host dispatch boundaries) and
  :class:`FaultInjector` (executes them: dispatch errors, simulated
  preemption/engine crash, NaN meter bursts, loader stalls, straggler
  delays, page-pool pressure spikes — compiled programs untouched);
- :mod:`~apex_tpu.resilience.train` — :class:`ResilientTrainDriver`:
  per-dispatch watchdog, bounded retry with backoff+jitter, a
  non-finite meter sentry that rolls back to the last good checkpoint
  and replays bitwise, and preemption recovery that rebuilds the
  driver from durable state;
- :mod:`~apex_tpu.resilience.serve` — :class:`ResilientServeEngine`:
  per-request deadlines/abandonment, bounded decode-boundary retry,
  admission backpressure, and full engine crash-recovery replaying
  in-flight requests as prompt+generated (token-exact under greedy).

Every recovery lands in ``resilience.*`` obs counters and the
``resilience.recovery_ms`` histogram; ``tools/trace_report.py`` renders
the recovery ledger, ``tools/lint_graphs.py`` pins the retry/replay
paths compile-free, and ``tests/test_resilience.py`` holds a seeded
chaos run to the clean run's tokens and bitwise-equal parameters.
Kill switch: ``APEX_TPU_RESILIENCE=0`` (wrappers become transparent
pass-throughs — no retries, no rollback, faults propagate).
"""
from apex_tpu.resilience.faults import (  # noqa: F401
    DISPATCH_ERROR,
    ENGINE_CRASH,
    EXCHANGE_STALL,
    FAULT_KINDS,
    GANG_FAULT_KINDS,
    HEARTBEAT_DROP,
    HOST_FAULT_KINDS,
    HOST_LOSS,
    HOST_STALL,
    LOADER_STALL,
    NAN_METERS,
    PAGE_PRESSURE,
    PREEMPTION,
    RANK_LOSS,
    RESTART,
    STRAGGLER,
    DispatchFailure,
    FaultEvent,
    FaultInjector,
    FaultPlan,
    HostPreemption,
    InjectedFault,
    gang_site,
    host_site,
    resilience_default,
)
from apex_tpu.resilience.serve import ResilientServeEngine  # noqa: F401
from apex_tpu.resilience.train import (  # noqa: F401
    NonFiniteMeters,
    ResilientTrainDriver,
    RetryBudgetExceeded,
)

__all__ = [
    "DISPATCH_ERROR",
    "ENGINE_CRASH",
    "EXCHANGE_STALL",
    "FAULT_KINDS",
    "GANG_FAULT_KINDS",
    "HEARTBEAT_DROP",
    "HOST_FAULT_KINDS",
    "HOST_LOSS",
    "HOST_STALL",
    "LOADER_STALL",
    "NAN_METERS",
    "PAGE_PRESSURE",
    "PREEMPTION",
    "RANK_LOSS",
    "RESTART",
    "STRAGGLER",
    "DispatchFailure",
    "FaultEvent",
    "FaultInjector",
    "FaultPlan",
    "HostPreemption",
    "InjectedFault",
    "NonFiniteMeters",
    "ResilientServeEngine",
    "ResilientTrainDriver",
    "RetryBudgetExceeded",
    "gang_site",
    "host_site",
    "resilience_default",
]
