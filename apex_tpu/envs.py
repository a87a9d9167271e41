"""The canonical ``APEX_TPU_*`` env-knob registry (ISSUE 19).

Every environment variable the package, its tools, or its tests read
is declared HERE — name, default, one-line doc, and whether it is an
internal launcher→worker wire rather than a user-facing knob.  The
``unregistered-env-knob`` apexlint rule (see
:mod:`apex_tpu.analysis.staticcheck`) rejects any ``APEX_TPU_*`` name
that appears in code without a row in this registry, and the
``env-doc-drift`` rule cross-checks the registry against README.md's
env table — a knob added in code without a registry entry AND a README
row fails the lint, which is how the table stopped rotting.

Deliberately dependency-free (no jax, no apex_tpu imports): the
analyzer and ``tools/apexlint.py`` load this module straight from its
file path so the whole lint stays importable on a box without jax.

Reading a knob through :func:`get`/:func:`flag`/:func:`integer` is
optional sugar — direct ``os.environ.get("APEX_TPU_X", ...)`` reads
stay idiomatic; the lint checks the NAME is registered, not the call
path.
"""
from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, List, Optional

__all__ = [
    "KNOBS",
    "REGISTRY",
    "EnvKnob",
    "check_readme_drift",
    "flag",
    "get",
    "integer",
    "is_registered",
    "readme_table_names",
]


@dataclasses.dataclass(frozen=True)
class EnvKnob:
    """One registered environment knob.

    Args:
      name: the full ``APEX_TPU_*`` variable name.
      default: the effective default as a string, or None for unset
        (the knob only acts when exported).
      doc: one line of documentation — what the knob does and what the
        default means.  Must be non-empty; ``env-doc-drift`` checks.
      internal: True for launcher→worker coordination wires (set by
        ``run_gang``/the test harness, never hand-tuned).  Internal
        knobs still get a README row — the table is the complete list.
    """

    name: str
    default: Optional[str]
    doc: str
    internal: bool = False


KNOBS: List[EnvKnob] = [
    # -- dispatch / precision / kernels --------------------------------
    EnvKnob("APEX_TPU_STEPS_PER_DISPATCH", "10",
            "Driver window length K; =1 restores per-step dispatch."),
    EnvKnob("APEX_TPU_TOKENS_PER_DISPATCH", "8",
            "Serve-side fused decode window length; =1 restores "
            "per-token dispatch."),
    EnvKnob("APEX_TPU_MICROBATCHES", "1",
            "Default M for microbatch-step builders without an "
            "explicit count."),
    EnvKnob("APEX_TPU_PAGED_KV", "1",
            "0 restores the contiguous per-slot KV cache (the parity "
            "reference)."),
    EnvKnob("APEX_TPU_SPEC_DECODE", "0",
            "=D enables self-speculative decode with D draft tokens "
            "per forward; =0 is the kill switch."),
    EnvKnob("APEX_TPU_KV_INT8", "0",
            "=1 stores paged KV as int8 with per-token fp32 scales."),
    EnvKnob("APEX_TPU_LN_FUSED_DGAMMA", "1",
            "0 forces the bit-exact XLA-reduction LayerNorm backward."),
    EnvKnob("APEX_TPU_PAGED_FUSED", "0",
            "1 enables the fused paged-attention serving kernel "
            "(page gather + int8 dequant + scores in one pass; "
            "hardware validation pending via "
            "tools/check_paged_fused.py)."),
    EnvKnob("APEX_TPU_SPEC_TREE", "0",
            "=W>=2 widens speculative decode to W draft branches per "
            "slot, verified in one batched tree forward; 0/1 keeps "
            "the chain proposer."),
    EnvKnob("APEX_TPU_SPEC_AUTOTUNE", "0",
            "1 lets the serve engine walk the speculative draft depth "
            "from the accepted-per-step histogram (each depth "
            "compiles its window once)."),
    # -- sharding / training -------------------------------------------
    EnvKnob("APEX_TPU_SHARDING_RULES", "1",
            "0 restores the legacy hand-threaded sharding specs "
            "everywhere the rules engine derives them."),
    EnvKnob("APEX_TPU_GRAD_COMPRESS", "none",
            "Gradient-exchange compression for the boundary "
            "collective and the DCN blob codec: bf16 | int8 | none."),
    EnvKnob("APEX_TPU_HIER_EXCHANGE", "0",
            "1 defaults gang workers to the sharded scatter-reduce "
            "DCN exchange (mean_tree_sharded)."),
    EnvKnob("APEX_TPU_GANG_ELASTIC", "0",
            "1 makes run_gang elastic: a rank dead past its restart "
            "budget reforms the gang at world N-1."),
    EnvKnob("APEX_TPU_GANG_MIN_WORLD", "1",
            "The world-size floor an elastic gang may shrink to; a "
            "resize crossing it raises GangFailure."),
    EnvKnob("APEX_TPU_DIST_INIT_TIMEOUT_S", "300",
            "jax.distributed.initialize coordinator timeout for gang "
            "workers."),
    # -- launcher -> worker wires (internal, never hand-tuned) ---------
    EnvKnob("APEX_TPU_SHARDING_TABLE", None,
            "Launcher->worker wire: the serialized rules table every "
            "gang member derives its sharding from.", internal=True),
    EnvKnob("APEX_TPU_GANG_EPOCH", None,
            "Launcher->worker wire: the exchange epoch, bumped on "
            "every membership change so a dead world's blobs can "
            "never be summed.", internal=True),
    EnvKnob("APEX_TPU_GANG_SURVIVORS", None,
            "Launcher->worker wire: comma list of surviving ORIGINAL "
            "ranks in sorted order.", internal=True),
    EnvKnob("APEX_TPU_GANG_FAULT_PLAN", None,
            "Caller->worker wire: a serialized FaultPlan carrying the "
            "gang fault kinds, polled per window.", internal=True),
    EnvKnob("APEX_TPU_FLEET_KILL", None,
            "Test-harness wire: 'rank:window' makes that gang worker "
            "os._exit(17) at that window (fleet-train chaos tests).",
            internal=True),
    # -- observability --------------------------------------------------
    EnvKnob("APEX_TPU_OBS", "1",
            "0 disables runtime telemetry (spans, lifecycle "
            "histograms, timeline counters, the jit.* compile counters "
            "and the gc-pause hook)."),
    EnvKnob("APEX_TPU_OBS_TRACE_DIR", None,
            "Export the ambient obs trace here at tier-1 session end "
            "(set by tools/run_tier1.sh --trace DIR)."),
    EnvKnob("APEX_TPU_FLIGHTREC", "1",
            "0 disables the flight recorder; an integer > 1 sizes the "
            "ambient ring."),
    EnvKnob("APEX_TPU_FLIGHTREC_DIR", None,
            "Where resilience-layer recoveries dump the "
            "flightrec.jsonl postmortem."),
    EnvKnob("APEX_TPU_GANG_TELEMETRY", "1",
            "0 disables per-rank gang K-boundary telemetry rows."),
    EnvKnob("APEX_TPU_FLEET_SCRAPE_ROUNDS", "8",
            "Router rounds between live fleet-aggregator scrapes."),
    EnvKnob("APEX_TPU_SLO_ADMISSION", "0",
            "1 enables SLO-aware admission in ServeEngine (priority "
            "classes, TTFT-burn overtake)."),
    # -- resilience / fleet ---------------------------------------------
    EnvKnob("APEX_TPU_RESILIENCE", "1",
            "0 makes the self-healing wrappers transparent "
            "pass-throughs; faults propagate."),
    EnvKnob("APEX_TPU_FLEET_HEARTBEAT_MISSES", "2",
            "Consecutive missed heartbeats before the FleetRouter "
            "evicts a host."),
    EnvKnob("APEX_TPU_FLEET_STRAGGLER_FACTOR", "3.0",
            "A host whose decode-window p99 exceeds this multiple of "
            "the fleet median is flagged a straggler."),
    EnvKnob("APEX_TPU_FLEET_STRAGGLER_ROUNDS", "3",
            "Consecutive flagged scan rounds before a straggler "
            "verdict sticks (debounce)."),
    EnvKnob("APEX_TPU_FLEET_AFFINITY", "1",
            "0 kills prefix-affinity routing in the FleetRouter "
            "(back to pure least-loaded)."),
    EnvKnob("APEX_TPU_FLEET_AFFINITY_GAP", "2",
            "Load guard for affinity routing: max outstanding-request "
            "gap before falling back to least-loaded."),
    EnvKnob("APEX_TPU_FLEET_ROLES", None,
            "Disaggregated prefill/decode: comma list of host roles "
            "by id; unset = every host mixed."),
    EnvKnob("APEX_TPU_FLEET_AUTOSCALE", "0",
            "1 enables SLO-driven autoscaling of standby hosts "
            "through the preflight gate."),
    EnvKnob("APEX_TPU_FLEET_REBALANCE", "0",
            "1 enables proactive KV-page migration off hot hosts at "
            "calm boundaries (the 100-host scenario's lever)."),
    EnvKnob("APEX_TPU_FLEET_STREAM_HANDOFF", "0",
            "1 streams KV handoffs in fixed-size chunks (pages flow "
            "while prefill continues) instead of one blob."),
    # -- deployment ------------------------------------------------------
    EnvKnob("APEX_TPU_DEPLOY", "0",
            "1 arms PromotionController.tick(), the poll-every-round "
            "live checkpoint promotion hook."),
    EnvKnob("APEX_TPU_DEPLOY_DRAIN_ROUNDS", None,
            "Per-host drain budget (fleet rounds) before a "
            "promotion's weight swap fires; unset = wait until calm."),
]

REGISTRY: Dict[str, EnvKnob] = {k.name: k for k in KNOBS}

if len(REGISTRY) != len(KNOBS):  # pragma: no cover - registry typo guard
    raise RuntimeError("duplicate APEX_TPU knob names in apex_tpu.envs")


def is_registered(name: str) -> bool:
    """Whether ``name`` has a registry row."""
    return name in REGISTRY


def get(name: str, default: Optional[str] = None) -> Optional[str]:
    """A registered read: raises ``KeyError`` on an unregistered name
    (the runtime twin of the static rule), else returns the env value,
    the explicit ``default``, or the registry default."""
    knob = REGISTRY.get(name)
    if knob is None:
        raise KeyError(f"{name} is not a registered APEX_TPU knob — "
                       f"add an EnvKnob row in apex_tpu/envs.py")
    if default is None:
        default = knob.default
    return os.environ.get(name, default)


def flag(name: str, default: Optional[bool] = None) -> bool:
    """A registered boolean read: ``"0"``/``""``/unset-with-falsy-
    default are False, everything else True."""
    knob = REGISTRY.get(name)
    if knob is None:
        raise KeyError(f"{name} is not a registered APEX_TPU knob")
    if default is None:
        default = (knob.default or "0") not in ("0", "")
    raw = os.environ.get(name)
    if raw is None:
        return bool(default)
    return raw not in ("0", "")


def integer(name: str, default: Optional[int] = None) -> int:
    """A registered integer read (ValueError on junk falls back to the
    registry default)."""
    knob = REGISTRY.get(name)
    if knob is None:
        raise KeyError(f"{name} is not a registered APEX_TPU knob")
    if default is None:
        default = int(knob.default or 0)
    try:
        return int(os.environ.get(name, ""))
    except ValueError:
        return int(default)


# ---------------------------------------------------------------------------
# the README cross-check (the env-doc-drift rule's engine)
# ---------------------------------------------------------------------------

_README_ROW = re.compile(r"^\|\s*`(APEX_TPU_[A-Z0-9_]+)`\s*\|")


def readme_table_names(readme_text: str) -> List[str]:
    """The ``APEX_TPU_*`` names documented as rows of README.md's env
    table (``| \\`APEX_TPU_X\\` | default | doc |``)."""
    out = []
    for line in readme_text.splitlines():
        m = _README_ROW.match(line.strip())
        if m:
            out.append(m.group(1))
    return out


def check_readme_drift(readme_text: str) -> List[str]:
    """Cross-check this registry against README's env table; returns
    drift messages (empty = in sync).  Every registry row must have a
    table row and vice versa, and every registry row must carry a doc
    line — the machine-checked half of 'the README env table is the
    complete knob list'."""
    errs: List[str] = []
    table = set(readme_table_names(readme_text))
    registered = set(REGISTRY)
    for name in sorted(registered - table):
        errs.append(
            f"env-doc-drift: {name} is registered in apex_tpu/envs.py "
            f"but has no README env-table row"
        )
    for name in sorted(table - registered):
        errs.append(
            f"env-doc-drift: README env table documents {name} but "
            f"apex_tpu/envs.py has no such knob"
        )
    for knob in KNOBS:
        if not knob.doc.strip():
            errs.append(f"env-doc-drift: {knob.name} has an empty doc "
                        f"line in apex_tpu/envs.py")
    return errs
