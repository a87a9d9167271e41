"""Render a text summary of an apex_tpu.obs trace capture.

The consumption end of the runtime telemetry layer (ISSUE 6): given a
``trace.jsonl`` written by :func:`apex_tpu.obs.write_jsonl` (or a
directory holding one — e.g. ``tools/run_tier1.sh --trace <dir>`` /
``obs.export_default``), print what a perf PR needs to SHOW rather
than claim:

- **top spans** — count / total / p50 / p99 per span name, compile
  count alongside (executed-vs-compiled attribution);
- **dispatch percentiles** — the train window and every serve phase
  dispatch, the boundary economics both fused drivers exist for;
- **per-request latency** — TTFT / inter-token latency / queue delay
  p50/p99 from the lifecycle histograms in the metrics snapshot;
- **compile events** — the total and which spans compiled: on a warm
  run this must be cold compiles only, so a nonzero count on a
  steady-state span name is the recompile anomaly made visible;
- **pool utilization timeline** — ``serve/pages_in_use`` counter
  samples bucketed over the run (the page-pool economics over time);
- **recovery ledger** — every ``resilience.*`` counter/histogram and
  ``resilience/*`` instant (injected faults, retries, rollbacks,
  restarts, deadline abandons, recovery-latency percentiles): the
  self-healing layer's accounting (ISSUE 8), rendered so each injected
  cause sits next to the recovery it triggered;
- **SLO section** (ISSUE 10) — when the trace carries a ``{"type":
  "slo"}`` line (a live :class:`~apex_tpu.obs.slo.SloReport`): each
  objective's current sliding-window quantile vs its threshold, the
  fast/slow error-budget burn rates, alert state with trip/clear
  counts, and the lifecycle goodput/abandonment summary.  The
  ``--merge`` fleet view renders the same as a per-host table plus
  fleet totals — and (ISSUE 12) a prefix-cache + role table (per-host
  prompt/prefix-hit tokens, handoff adoptions/detaches, fleet hit
  rate) next to the straggler table;
- **roofline section** (ISSUE 11) — with ``--census FILE`` (the JSON
  ``tools/lint_graphs.py --census-out`` writes): each canonical
  program's compiled FLOPs/bytes joined against its dispatch span's
  measured p50 wall time into achieved GFLOP/s / GB/s, and — given
  ``--peak-gflops`` / ``--peak-gbps`` — achieved-vs-peak utilization
  with a compute/memory-bound verdict.  XLA counts a scan body once,
  so rates over a whole fused window are lower bounds;
- **flight-recorder section** (ISSUE 11) — when the trace carries a
  ``{"type": "flightrec"}`` line (``write_jsonl(flightrec=...)``):
  the black box's event-kind census and its newest events, the same
  tail a postmortem dump would hold.

``--capture <dir>`` first records the canonical hardware-free run
(fused train driver, microbatches=2 + paged serve mixed traffic with a
shared-prefix duplicate) into ``<dir>`` and then reports it — the one
command that proves the whole pipeline end to end::

    JAX_PLATFORMS=cpu python tools/trace_report.py --capture /tmp/obs
    python tools/trace_report.py /tmp/obs          # re-render later
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# standalone CLI must pin the CPU backend BEFORE jax initializes (the
# capture run is hardware-free, and on a chip machine a process that
# initializes the TPU holds it against every other process)
if "jax" not in sys.modules:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")

import argparse  # noqa: E402
import math  # noqa: E402
from typing import Dict, List, Optional, Tuple  # noqa: E402

__all__ = ["capture", "expand_merge_paths", "load", "load_hosts",
           "render", "render_fleet", "render_gang",
           "stitch_correlations"]

# span names whose distributions are the dispatch-boundary economics
DISPATCH_SPANS = (
    "train/dispatch",
    "serve/decode_window",
    "serve/prefill",
    "serve/prefill_chunk",
    "serve/cow_copy",
)
POOL_COUNTER = "serve/pages_in_use"
_MS = 1e-6  # ns -> ms


def load(path: str) -> Tuple[List[dict], Optional[dict]]:
    """``(events, metrics)`` from a trace.jsonl file or a directory
    containing one (the ``export_default`` layout)."""
    from apex_tpu.obs import read_jsonl

    if os.path.isdir(path):
        path = os.path.join(path, "trace.jsonl")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no trace.jsonl at {path!r}")
    return read_jsonl(path)


def _pct(vals: List[float], q: float) -> float:
    """Nearest-rank percentile (the obs.Histogram definition)."""
    if not vals:
        return math.nan
    s = sorted(vals)
    return s[max(0, min(len(s) - 1, math.ceil(q * len(s)) - 1))]


def _span_rows(events: List[dict]) -> Dict[str, dict]:
    rows: Dict[str, dict] = {}
    for ev in events:
        if ev.get("type") != "span":
            continue
        r = rows.setdefault(
            ev["name"],
            {"count": 0, "total_ns": 0.0, "durs": [], "compiles": 0},
        )
        r["count"] += 1
        r["total_ns"] += ev.get("dur", 0)
        r["durs"].append(ev.get("dur", 0))
        r["compiles"] += ev.get("compiles", 0)
    return rows


def _fmt_hist(snap: dict) -> str:
    return (f"n={snap.get('count', 0):<6} "
            f"p50={snap.get('p50', math.nan):>9.3f}  "
            f"p99={snap.get('p99', math.nan):>9.3f}  "
            f"mean={snap.get('mean', math.nan):>9.3f}  "
            f"max={snap.get('max', math.nan):>9.3f}")


def _timeline(samples: List[Tuple[int, float]], buckets: int = 12,
              width: int = 24) -> List[str]:
    """Bucket (ts, value) counter samples into a text bar timeline."""
    if not samples:
        return ["(no samples)"]
    t0, t1 = samples[0][0], samples[-1][0]
    span = max(t1 - t0, 1)
    peak = max(v for _, v in samples) or 1
    rows = []
    for b in range(buckets):
        lo = t0 + span * b // buckets
        hi = t0 + span * (b + 1) // buckets
        vals = [v for t, v in samples
                if lo <= t < hi or (b == buckets - 1 and t == hi)]
        if not vals:
            continue
        mean = sum(vals) / len(vals)
        bar = "#" * max(1, round(width * max(vals) / peak))
        rows.append(
            f"  +{(lo - t0) * _MS:>9.1f}ms  mean {mean:>7.1f}  "
            f"max {max(vals):>5.0f}  {bar}"
        )
    return rows


def _fmt_val(v, nan: str = "-") -> str:
    if v is None:
        return nan
    return f"{v:.3f}" if isinstance(v, float) else str(v)


def _slo_lines(report: dict) -> List[str]:
    """Render one SloReport dict (the ``{"type": "slo"}`` line)."""
    lines = ["\n-- SLO objectives (sliding window) --"]
    lines.append(f"{'objective':<22} {'window':>8} {'current':>9} "
                 f"{'target':>9} {'burn f/s':>11}  state")
    for row in report.get("objectives", []):
        state = "ALERTING" if row.get("alerting") else (
            "met" if row.get("met") else
            ("violated" if row.get("met") is False else "no data"))
        trips = row.get("trips", 0)
        if trips:
            state += f" (trips={trips} clears={row.get('clears', 0)})"
        lines.append(
            f"{row['name'][:22]:<22} "
            f"{row.get('window_ms', 0) / 1e3:>7.1f}s "
            f"{_fmt_val(row.get('current')):>9} "
            f"{_fmt_val(row.get('threshold')):>9} "
            f"{row.get('burn_fast', 0):>5.2f}/"
            f"{row.get('burn_slow', 0):<5.2f} {state}"
        )
    lc = report.get("lifecycle")
    if lc:
        lines.append(
            f"{'goodput':<22} {lc.get('goodput_tokens_per_s', 0):g} "
            f"tok/s ({lc.get('completed_tokens', 0)} tokens over "
            f"{lc.get('wall_ms', 0):g} ms)"
        )
        lines.append(
            f"{'abandonment':<22} {lc.get('abandoned', 0)} of "
            f"{lc.get('abandoned', 0) + lc.get('completed', 0)} "
            f"({lc.get('abandonment_rate', 0):.1%})"
        )
    return lines


def _roofline_lines(census: Dict[str, dict], rows: Dict[str, dict],
                    peak_flops: Optional[float] = None,
                    peak_bytes: Optional[float] = None) -> List[str]:
    """The achieved-vs-peak section: census numbers over each
    program's dispatch-span p50 wall time (the join key is the
    ``span`` field lint_graphs stamps on every census entry)."""
    from apex_tpu.analysis import roofline

    lines = ["\n-- roofline (census x span wall) --"]
    lines.append(f"{'program':<18} {'span':<22} {'p50_ms':>8} "
                 f"{'GFLOP/s':>9} {'GB/s':>8} {'int.':>6}  bound/util")
    for name in sorted(census):
        row = census[name]
        span = row.get("span")
        r = rows.get(span) if span else None
        if r is None or not r["durs"]:
            continue
        wall_s = _pct(r["durs"], 0.5) * 1e-9
        rl = roofline(row.get("flops"), row.get("bytes_accessed"),
                      wall_s, peak_flops_per_s=peak_flops,
                      peak_bytes_per_s=peak_bytes)
        gf = rl["achieved_flops_per_s"]
        gb = rl["achieved_bytes_per_s"]
        ai = rl["arithmetic_intensity"]
        tail = ""
        if rl["bound"]:
            tail = f"{rl['bound']} {rl['utilization']:.1%}"
        elif row.get("census_partial"):
            tail = "census partial"
        lines.append(
            f"{name[:18]:<18} {str(span)[:22]:<22} "
            f"{wall_s * 1e3:>8.3f} "
            f"{gf / 1e9 if gf else math.nan:>9.3f} "
            f"{gb / 1e9 if gb else math.nan:>8.3f} "
            f"{ai if ai is not None else math.nan:>6.1f}  {tail}"
        )
    return lines


def _flightrec_lines(line: dict, tail: int = 12) -> List[str]:
    """Render one ``{"type": "flightrec"}`` trace line — the black
    box's kind census and newest events."""
    evs = line.get("events", [])
    kinds: Dict[str, int] = {}
    for e in evs:
        kinds[e.get("kind", "?")] = kinds.get(e.get("kind", "?"), 0) + 1
    out = [f"\n-- flight recorder ({line.get('recorded', len(evs))} "
           f"recorded, {line.get('dropped', 0)} dropped) --"]
    out.append("  " + ", ".join(f"{k} x{v}"
                                for k, v in sorted(kinds.items())))
    for e in evs[-tail:]:
        attrs = e.get("attrs") or {}
        a = " ".join(f"{k}={v}" for k, v in attrs.items())
        out.append(f"  #{e.get('seq'):<6} {e.get('kind'):<28} {a}")
    return out


def render(events: List[dict], metrics: Optional[dict] = None,
           top: int = 15, census: Optional[Dict[str, dict]] = None,
           peak_flops: Optional[float] = None,
           peak_bytes: Optional[float] = None) -> str:
    """The text report (see module docstring for the sections)."""
    lines: List[str] = []
    meta = next((e for e in events if e.get("type") == "meta"), {})
    rows = _span_rows(events)
    total_spans = sum(r["count"] for r in rows.values())
    lines.append(
        f"== apex_tpu trace report: {total_spans} spans, "
        f"{len(rows)} names, {meta.get('compiles', 0)} backend "
        f"compile(s) =="
    )

    lines.append("\n-- top spans (by total time) --")
    lines.append(f"{'span':<28} {'count':>6} {'total_ms':>10} "
                 f"{'p50_ms':>9} {'p99_ms':>9} {'compiles':>8}")
    by_total = sorted(rows.items(), key=lambda kv: -kv[1]["total_ns"])
    for name, r in by_total[:top]:
        lines.append(
            f"{name[:28]:<28} {r['count']:>6} "
            f"{r['total_ns'] * _MS:>10.3f} "
            f"{_pct(r['durs'], 0.5) * _MS:>9.3f} "
            f"{_pct(r['durs'], 0.99) * _MS:>9.3f} {r['compiles']:>8}"
        )

    lines.append("\n-- dispatch-time percentiles --")
    for name in DISPATCH_SPANS:
        r = rows.get(name)
        if r is None:
            continue
        lines.append(
            f"{name:<28} n={r['count']:<6} "
            f"p50={_pct(r['durs'], 0.5) * _MS:>9.3f}ms  "
            f"p99={_pct(r['durs'], 0.99) * _MS:>9.3f}ms"
        )

    if metrics:
        req = [("TTFT", "serve.ttft_ms"), ("ITL", "serve.itl_ms"),
               ("queue delay", "serve.queue_delay_ms"),
               ("request latency", "serve.request_latency_ms")]
        have = [(label, metrics[k]) for label, k in req if k in metrics]
        if have:
            lines.append("\n-- per-request latency (ms) --")
            for label, snap in have:
                lines.append(f"{label:<16} {_fmt_hist(snap)}")
            # speculation economics next to ITL (ISSUE 7): the
            # acceptance rate is what makes a low ITL attributable to
            # speculation rather than batch shrinkage
            drafts = metrics.get("serve.spec.draft_tokens", {})
            accepted = metrics.get("serve.spec.accepted_tokens", {})
            d = drafts.get("value", 0)
            if d:
                a = accepted.get("value", 0)
                roll = metrics.get("serve.spec.rollbacks", {}).get(
                    "value", 0
                )
                lines.append(
                    f"{'spec acceptance':<16} "
                    f"{a / d:.1%} ({a}/{d} drafts, {roll} rollbacks)"
                )
                acc_h = metrics.get("serve.spec.accepted_per_step")
                if acc_h and acc_h.get("count"):
                    lines.append(
                        f"{'accepted/step':<16} {_fmt_hist(acc_h)}"
                    )

    # recovery ledger (ISSUE 8): every resilience.* metric plus the
    # injected-fault / recovery instants — the section that shows each
    # injected cause next to the healing it triggered
    res_metrics = {
        k: v for k, v in (metrics or {}).items()
        if k.startswith("resilience.")
    }
    res_instants: Dict[str, int] = {}
    for e in events:
        if e.get("type") == "instant" and str(e.get("name", "")).startswith(
            "resilience/"
        ):
            res_instants[e["name"]] = res_instants.get(e["name"], 0) + 1
    if res_metrics or res_instants:
        lines.append("\n-- recovery ledger (resilience.*) --")
        for name in sorted(res_metrics):
            snap = res_metrics[name]
            if snap.get("type") == "histogram":
                lines.append(f"{name:<36} {_fmt_hist(snap)}")
            else:
                val = snap.get("value", 0)
                extra = (f"  peak={snap['max']}"
                         if snap.get("type") == "gauge" else "")
                lines.append(f"{name:<36} {val}{extra}")
        for name in sorted(res_instants):
            lines.append(f"{name:<36} x{res_instants[name]}")
        rec = res_metrics.get("resilience.recovery_ms", {})
        if rec.get("count"):
            lines.append(
                f"{'recovery latency':<36} p50="
                f"{rec.get('p50', math.nan):.3f}ms  "
                f"p99={rec.get('p99', math.nan):.3f}ms over "
                f"{rec['count']} recover(ies)"
            )

    slo = next((e.get("report") for e in events
                if e.get("type") == "slo"), None)
    if slo:
        lines.extend(_slo_lines(slo))

    if census:
        lines.extend(_roofline_lines(census, rows,
                                     peak_flops=peak_flops,
                                     peak_bytes=peak_bytes))

    frline = next((e for e in events if e.get("type") == "flightrec"),
                  None)
    if frline:
        lines.extend(_flightrec_lines(frline))

    lines.append("\n-- compile events --")
    compiled = {n: r["compiles"] for n, r in rows.items() if r["compiles"]}
    total_c = meta.get("compiles", sum(compiled.values()))
    lines.append(f"total backend compiles: {total_c}")
    for name in sorted(compiled):
        lines.append(f"  {name}: {compiled[name]} "
                     f"(over {rows[name]['count']} span(s))")
    warm_anoms = [
        n for n, r in rows.items()
        if r["compiles"] and r["count"] > max(1, r["compiles"])
    ]
    if warm_anoms:
        lines.append(
            "  NOTE: span name(s) with more executions than compiles — "
            "verify the compiles are the cold calls: "
            + ", ".join(sorted(warm_anoms))
        )

    pool = [(e["ts"], float(e.get("value", 0))) for e in events
            if e.get("type") == "counter" and e.get("name") == POOL_COUNTER]
    if pool:
        lines.append("\n-- page-pool utilization (pages in use) --")
        lines.extend(_timeline(sorted(pool)))
    return "\n".join(lines)


# --------------------------------------------------------------------------
# fleet merge (ISSUE 9): per-host trace.jsonl files -> one fleet report
# --------------------------------------------------------------------------

def expand_merge_paths(paths):
    """Resolve ``--merge`` arguments into trace files: each argument
    may be a trace.jsonl, an export directory holding one, or (ISSUE
    15) a PARENT directory whose immediate children hold per-host
    exports — ``--merge /run`` finds ``/run/*/trace.jsonl`` sorted, so
    one argument covers a whole fleet capture."""
    import glob as _glob

    out = []
    for p in paths:
        if os.path.isdir(p) and not os.path.exists(
            os.path.join(p, "trace.jsonl")
        ):
            found = sorted(_glob.glob(os.path.join(p, "*",
                                                   "trace.jsonl")))
            if not found:
                raise FileNotFoundError(
                    f"--merge {p!r}: no trace.jsonl here or in any "
                    "child directory"
                )
            out.extend(found)
        else:
            out.append(p)
    return out


def load_hosts(paths):
    """Load N per-host traces (files or export dirs) as
    ``[(host_id, events, metrics), ...]``.  The host id comes from the
    meta header's ``host`` key (stamped by
    ``FleetHost.export_trace``; a ``FleetRouter.export_trace`` file's
    ``router`` flag maps to the id ``"router"``), falling back to the
    first span's ``host`` attr, then to the file's position.  The meta
    header's ``role`` (disaggregation, ISSUE 12) rides along inside
    ``metrics`` under the reserved ``_fleet_role`` key."""
    out = []
    for i, p in enumerate(expand_merge_paths(paths)):
        events, metrics = load(p)
        meta = next((e for e in events if e.get("type") == "meta"), {})
        host = meta.get("host")
        if host is None and meta.get("router"):
            host = "router"
        if host is None:
            host = next(
                (e.get("attrs", {}).get("host") for e in events
                 if e.get("type") == "span"
                 and e.get("attrs", {}).get("host") is not None),
                i,
            )
        if meta.get("role") is not None:
            metrics = dict(metrics or {})
            metrics["_fleet_role"] = meta["role"]
        out.append((host, events, metrics))
    return out


# --------------------------------------------------------------------------
# cross-host correlation stitching (ISSUE 15)
# --------------------------------------------------------------------------

# milestone instants (router clock ``t`` attr) in causal order; the
# stitched TTFT decomposition telescopes over consecutive milestones,
# so its segments SUM EXACTLY to the router-observed TTFT
_CORR_MILESTONES = ("fleet/submit", "fleet/assign", "fleet/first_token",
                    "fleet/handoff", "fleet/handoff_fallback",
                    "fleet/decode_first_token", "fleet/finished")

# deployment-plane instants (ISSUE 18): corr-stamped like requests but
# keyed by a PROMOTION id — they render in their own timeline and must
# not surface as orphaned request flows
_PROMO_PHASES = ("deploy/candidate", "deploy/verify",
                 "deploy/verify_fail", "deploy/reshard", "fleet/roll",
                 "fleet/roll_calm", "fleet/roll_readmit",
                 "serve/swap_weights", "deploy/swap",
                 "deploy/swap_fail", "deploy/rollback", "deploy/abort",
                 "deploy/complete")


class CorrelationStitcher:
    """Streaming cross-host correlation join (ISSUE 17).

    Feed it events one host (or one line) at a time — it keeps only a
    bounded per-correlation accumulator (milestone timestamps, host
    path, counts), never the raw event lists, so stitching a 100-host
    capture with thousands of correlation ids stays O(flows) memory
    regardless of how many events each host emitted.  ``finish()``
    derives the TTFT decomposition and returns the same ``(flows,
    orphans)`` pair :func:`stitch_correlations` always has."""

    def __init__(self):
        self.flows = {}

    def feed_event(self, e) -> None:
        """Fold one raw trace event (only corr-stamped instants
        matter; everything else is ignored)."""
        if e.get("type") != "instant":
            return
        if e.get("name") in _PROMO_PHASES:
            return  # deployment plane: rendered by its own timeline
        attrs = e.get("attrs") or {}
        corr = attrs.get("corr")
        if corr is None:
            return
        f = self.flows.setdefault(corr, {
            "events": 0, "hosts": [], "milestones": {}, "uid": None,
        })
        f["events"] += 1
        if attrs.get("uid") is not None and f["uid"] is None:
            f["uid"] = attrs["uid"]
        name = e.get("name")
        h = attrs.get("host", attrs.get("dst"))
        if h is not None and (not f["hosts"] or f["hosts"][-1] != h):
            f["hosts"].append(h)
        if name in _CORR_MILESTONES and attrs.get("t") is not None:
            ms = f["milestones"]
            # first occurrence wins (a recompute fallback may
            # re-assign; the FIRST assign ends the queue segment)
            if name == "fleet/handoff" and attrs.get("t0") is not None:
                ms.setdefault("handoff_t0", attrs["t0"])
            ms.setdefault(name, attrs["t"])

    def feed(self, events) -> None:
        """Fold one host's events (any iterable, consumed once)."""
        for e in events:
            self.feed_event(e)

    def finish(self):
        """Derive the per-flow TTFT decomposition and return
        ``(flows, orphans)``."""
        flows = self.flows
        orphans = sorted(c for c, f in flows.items()
                         if "fleet/submit" not in f["milestones"])
        for corr, f in flows.items():
            ms = f["milestones"]
            sub = ms.get("fleet/submit")
            asg = ms.get("fleet/assign")
            ft = ms.get("fleet/first_token")
            if sub is not None and asg is not None:
                f["queue_ms"] = round((asg - sub) * _MS, 3)
            if asg is not None and ft is not None:
                f["prefill_ms"] = round((ft - asg) * _MS, 3)
            if sub is not None and ft is not None:
                f["ttft_ms"] = round((ft - sub) * _MS, 3)
            ho, ho0 = ms.get("fleet/handoff"), ms.get("handoff_t0")
            if ho is not None and ho0 is not None:
                f["handoff_wire_ms"] = round((ho - ho0) * _MS, 3)
            df = ms.get("fleet/decode_first_token")
            anchor = ho if ho is not None else ms.get(
                "fleet/handoff_fallback"
            )
            if df is not None and anchor is not None:
                f["decode_first_ms"] = round((df - anchor) * _MS, 3)
            f["done"] = "fleet/finished" in ms
        return flows, orphans


def stitch_correlations(hosts):
    """Join every correlation-id-stamped event across the merged
    traces into per-request flows.

    Returns ``(flows, orphans)``: ``flows`` maps corr id to a dict of
    milestones (``submit``/``assign``/``first_token``/``handoff``/
    ``decode_first``/``finished`` timestamps on the ROUTER clock), the
    hosts the request touched in order, its TTFT decomposition
    (``queue_ms`` = submit->assign, ``prefill_ms`` =
    assign->first_token — the two legs that telescope to ``ttft_ms``
    exactly — plus ``handoff_wire_ms`` and ``decode_first_ms`` for
    handed-off requests) and the raw event count.  ``orphans`` lists
    corr ids seen on some host with NO ``fleet/submit`` anchor — the
    broken-stitching signal ``--merge`` exits nonzero on.  Thin
    wrapper over the streaming :class:`CorrelationStitcher`."""
    st = CorrelationStitcher()
    for _host, events, _metrics in hosts:
        st.feed(events)
    return st.finish()


def stitch_paths(paths):
    """Stitch correlations straight off per-host ``trace.jsonl``
    files, one line at a time — never materializes any host's event
    list (the bounded-memory path a 100-host merge wants).  Accepts
    the same path forms as ``--merge`` (files, export dirs, or a
    parent of per-host export dirs)."""
    import json

    st = CorrelationStitcher()
    for p in expand_merge_paths(paths):
        if os.path.isdir(p):
            p = os.path.join(p, "trace.jsonl")
        with open(p) as f:
            for line in f:
                line = line.strip()
                if not line:
                    continue
                try:
                    e = json.loads(line)
                except ValueError:
                    continue
                st.feed_event(e)
    return st.finish()


def _correlation_lines(flows, orphans, top: int = 30):
    """The stitched per-request table ``--merge`` renders."""
    lines = [f"\n-- correlation-stitched requests ({len(flows)} "
             f"flow(s), {len(orphans)} orphan(s)) --"]
    lines.append(f"{'corr':<12} {'uid':>5} {'hosts':<14} "
                 f"{'queue':>8} {'prefill':>8} {'ttft':>8} "
                 f"{'wire':>7} {'dec1st':>7}  state")
    nan = "-"

    def fv(f, k):
        v = f.get(k)
        return f"{v:.3f}" if isinstance(v, float) else nan

    for corr in sorted(flows)[:top]:
        f = flows[corr]
        path = ">".join(str(h) for h in f["hosts"][:4]) or nan
        state = ("ORPHAN" if corr in orphans
                 else "done" if f.get("done") else "open")
        lines.append(
            f"{str(corr)[:12]:<12} {str(f.get('uid', nan)):>5} "
            f"{path[:14]:<14} {fv(f, 'queue_ms'):>8} "
            f"{fv(f, 'prefill_ms'):>8} {fv(f, 'ttft_ms'):>8} "
            f"{fv(f, 'handoff_wire_ms'):>7} "
            f"{fv(f, 'decode_first_ms'):>7}  {state}"
        )
    ttfts = [f["ttft_ms"] for f in flows.values() if "ttft_ms" in f]
    if ttfts:
        lines.append(
            f"{'ttft (stitched)':<12} p50={_pct(ttfts, 0.5):.3f}ms  "
            f"p99={_pct(ttfts, 0.99):.3f}ms over {len(ttfts)} request(s)"
        )
    if orphans:
        lines.append(
            f"ORPHANED correlation id(s) — host events with no "
            f"fleet/submit anchor: {', '.join(str(o) for o in orphans[:10])}"
        )
    return lines


def _stitch_promotions(hosts):
    """Group deploy/* + fleet/roll* + serve/swap_weights instants by
    their promotion corr id, preserving per-host emit order (the
    controller emits every phase itself, so the router's single event
    stream IS the causal order)."""
    promos: Dict[str, List[dict]] = {}
    for _host, events, _metrics in hosts:
        for e in events:
            if e.get("type") != "instant":
                continue
            if e.get("name") not in _PROMO_PHASES:
                continue
            attrs = e.get("attrs") or {}
            corr = attrs.get("corr")
            if corr is None:
                continue
            promos.setdefault(corr, []).append(e)
    return promos


def _promotion_lines(promos, top: int = 10):
    """The per-promotion phase table ``--merge`` renders."""
    lines = [f"\n-- deployment timeline ({len(promos)} "
             f"promotion(s)) --"]
    for corr in sorted(promos)[:top]:
        evs = promos[corr]
        by_name = {}
        for e in evs:
            by_name.setdefault(e["name"], e.get("attrs") or {})
        cand = by_name.get("deploy/candidate", {})
        comp = by_name.get("deploy/complete")
        outcome = ("complete" if comp is not None
                   else "ABORTED" if "deploy/abort" in by_name
                   else "VERIFY FAILED" if "deploy/verify_fail" in by_name
                   else "open")
        swaps = [e["attrs"] for e in evs if e["name"] == "deploy/swap"]
        recomputed = sum(int(a.get("recomputed", 0)) for a in swaps)
        digest = (comp or {}).get("digest") or ""
        head = (f"{corr}: step {cand.get('step', '-')}"
                + (f" -> {digest}" if digest else "")
                + f"  [{outcome}]")
        if swaps:
            head += (f"  hosts={[a.get('host') for a in swaps]}"
                     f" recomputed={recomputed}")
        lines.append(head)
        for e in evs:
            a = e.get("attrs") or {}
            detail = " ".join(
                f"{k}={a[k]}" for k in
                ("host", "step", "digest", "identical", "recomputed",
                 "rounds", "outstanding", "calm", "rolled_back",
                 "error") if k in a
            )
            lines.append(f"    {e['name']:<22} {detail}")
    return lines


def render_fleet(hosts, straggler_factor: float = 3.0,
                 top: int = 10) -> str:
    """The merged fleet report: per-host straggler table
    (``serve/decode_window`` p50/p99 per host vs the fleet median —
    the MegaScale in-situ diagnostic, offline) plus per-host span
    totals and the fleet recovery ledger summed across hosts."""
    lines: List[str] = []
    total = sum(
        sum(1 for e in ev if e.get("type") == "span")
        for _, ev, _ in hosts
    )
    lines.append(
        f"== apex_tpu FLEET report: {len(hosts)} host(s), "
        f"{total} spans =="
    )

    # per-host decode-window percentiles + straggler flags
    rows = []
    for host, events, _ in hosts:
        durs = [e.get("dur", 0) for e in events
                if e.get("type") == "span"
                and e.get("name") == "serve/decode_window"]
        rows.append((host, durs))
    p99s = {h: _pct(d, 0.99) for h, d in rows if d}
    med = math.nan
    if p99s:
        # LOWER median, matching FleetRouter._scan_stragglers: a small
        # fleet's straggler must not drag the reference past itself
        vals = sorted(p99s.values())
        med = vals[(len(vals) - 1) // 2]
    lines.append("\n-- per-host decode_window (straggler table) --")
    lines.append(f"{'host':<8} {'windows':>8} {'p50_ms':>10} "
                 f"{'p99_ms':>10}  flag")
    for host, durs in rows:
        if not durs:
            lines.append(f"{str(host):<8} {'0':>8} {'-':>10} {'-':>10}")
            continue
        p99 = p99s[host]
        flag = ("STRAGGLER"
                if med and not math.isnan(med) and med > 0
                and p99 > straggler_factor * med else "")
        lines.append(
            f"{str(host):<8} {len(durs):>8} "
            f"{_pct(durs, 0.5) * _MS:>10.3f} {p99 * _MS:>10.3f}  {flag}"
        )
    if not math.isnan(med):
        lines.append(f"{'fleet':<8} {'median':>8} {'':>10} "
                     f"{med * _MS:>10.3f}")

    # fleet prefix-cache + role table (ISSUE 12): each host's prompt
    # economics from its own registry counters — prefix-affinity
    # routing's win rendered next to the straggler table it pairs with
    def _cval(metrics, name):
        snap = (metrics or {}).get(name) or {}
        return snap.get("value", 0)

    cache_rows = []
    for host, _, metrics in hosts:
        pt = _cval(metrics, "serve.prompt_tokens")
        pht = _cval(metrics, "serve.prefix_hit_tokens")
        cache_rows.append((
            host, (metrics or {}).get("_fleet_role", "mixed"),
            _cval(metrics, "serve.prefix_hits"), pt, pht,
            _cval(metrics, "serve.adoptions"),
            _cval(metrics, "serve.detached"),
        ))
    if any(r[3] or r[5] or r[6] for r in cache_rows):
        lines.append("\n-- prefix cache + roles (per host) --")
        lines.append(f"{'host':<8} {'role':<8} {'hits':>6} "
                     f"{'prompt_tok':>11} {'hit_tok':>8} "
                     f"{'hit_rate':>9} {'adopt':>6} {'detach':>7}")
        tot_pt = tot_pht = 0
        for host, role, hits, pt, pht, adopt, det in cache_rows:
            tot_pt += pt
            tot_pht += pht
            rate = f"{pht / pt:>9.1%}" if pt else f"{'-':>9}"
            lines.append(
                f"{str(host):<8} {role:<8} {hits:>6} {pt:>11} "
                f"{pht:>8} {rate} {adopt:>6} {det:>7}"
            )
        frate = f"{tot_pht / tot_pt:.1%}" if tot_pt else "-"
        lines.append(f"{'fleet':<8} {'':<8} {'':>6} {tot_pt:>11} "
                     f"{tot_pht:>8} {frate:>9}")

    # per-host span totals (compiles alongside)
    lines.append("\n-- per-host spans --")
    for host, events, _ in hosts:
        r = _span_rows(events)
        n = sum(v["count"] for v in r.values())
        c = sum(v["compiles"] for v in r.values())
        busiest = sorted(r.items(), key=lambda kv: -kv[1]["total_ns"])
        names = ", ".join(f"{k} x{v['count']}" for k, v in busiest[:top])
        lines.append(f"host {host}: {n} spans, {c} compile(s) — {names}")

    # per-host SLO merge (ISSUE 10): one row per (host, objective) from
    # each host's {"type": "slo"} line, plus fleet goodput/abandonment
    # totals — the straggler table's SLO twin
    slo_hosts = []
    for host, events, _ in hosts:
        rep = next((e.get("report") for e in events
                    if e.get("type") == "slo"), None)
        if rep:
            slo_hosts.append((host, rep))
    if slo_hosts:
        lines.append("\n-- per-host SLO (sliding window) --")
        lines.append(f"{'host':<8} {'objective':<22} {'current':>9} "
                     f"{'target':>9} {'burn f/s':>11}  state")
        tot_tokens = tot_completed = tot_abandoned = 0
        wall = 0.0
        for host, rep in slo_hosts:
            for row in rep.get("objectives", []):
                state = ("ALERTING" if row.get("alerting")
                         else "met" if row.get("met")
                         else ("violated" if row.get("met") is False
                               else "no data"))
                lines.append(
                    f"{str(host):<8} {row['name'][:22]:<22} "
                    f"{_fmt_val(row.get('current')):>9} "
                    f"{_fmt_val(row.get('threshold')):>9} "
                    f"{row.get('burn_fast', 0):>5.2f}/"
                    f"{row.get('burn_slow', 0):<5.2f} {state}"
                )
            lc = rep.get("lifecycle") or {}
            tot_tokens += lc.get("completed_tokens", 0)
            tot_completed += lc.get("completed", 0)
            tot_abandoned += lc.get("abandoned", 0)
            wall = max(wall, lc.get("wall_ms", 0.0))
        retired = tot_completed + tot_abandoned
        lines.append(
            f"{'fleet':<8} goodput {tot_tokens} completed tokens over "
            f"{wall:g} ms"
            + (f", abandonment {tot_abandoned}/{retired} "
               f"({tot_abandoned / retired:.1%})" if retired else "")
        )

    # correlation-stitched per-request flows (ISSUE 15): the causal
    # cross-host table — router queue -> prefill -> handoff wire ->
    # decode first window — keyed by the router-minted corr id
    flows, orphans = stitch_correlations(hosts)
    if flows:
        lines.extend(_correlation_lines(flows, orphans, top=top * 3))

    # deployment timeline (ISSUE 18): every promotion's phase sequence
    # — candidate -> verify -> reshard -> per-host roll/swap ->
    # complete (or rollback/abort) — grouped by the promotion corr id
    # the controller stamps on deploy/* and fleet/roll* instants
    promos = _stitch_promotions(hosts)
    if promos:
        lines.extend(_promotion_lines(promos))

    # fleet/resilience ledger summed across the per-host registries
    ledger: Dict[str, float] = {}
    for _, _, metrics in hosts:
        for k, snap in (metrics or {}).items():
            if k.startswith(("fleet.", "resilience.")) and "value" in snap:
                ledger[k] = ledger.get(k, 0) + snap["value"]
    if ledger:
        lines.append("\n-- fleet recovery ledger (summed) --")
        for k in sorted(ledger):
            lines.append(f"{k:<36} {ledger[k]:g}")
    return "\n".join(lines)


# --------------------------------------------------------------------------
# merged gang telemetry rendering (ISSUE 15)
# --------------------------------------------------------------------------

def render_gang(root: str) -> str:
    """Text rendering of :func:`apex_tpu.obs.gangview.merge_gang_view`
    over an exchange root: epochs/resizes, replayed windows, a
    per-rank table (windows, compiles, exchange-wait p50/p99, skew,
    slowest-window counts) and the straggler verdict."""
    from apex_tpu.obs.gangview import merge_gang_view

    view = merge_gang_view(root)
    lines: List[str] = []
    lines.append(
        f"== apex_tpu GANG view: {len(view['ranks'])} rank(s), "
        f"{len(view['epochs'])} epoch(s), "
        f"{len(view['timeline'])} row(s) =="
    )
    for e in view["epochs"]:
        w = e["windows"]
        span = (f"w{w[0]}..w{w[-1]}" if w else "-")
        lines.append(
            f"  epoch {e['epoch']}: world {e['world']}, ranks "
            f"{e['ranks']}, windows {span}"
        )
    for rz in view["resizes"]:
        lines.append(
            f"  RESIZE -> epoch {rz['epoch']}: world "
            f"{rz['old_world']} -> {rz['world']}, lost {rz['lost']}"
        )
    lines.append(f"  windows replayed (failure cost): "
                 f"{view['windows_replayed']}")
    waits = view.get("exchange_wait_ms", {})
    skews = view.get("skew_ms", {})
    slowest = view.get("attribution", {}).get("slowest_windows", {})
    lines.append("\n-- per-rank gang telemetry --")
    lines.append(f"{'rank':<6} {'windows':>8} {'compiles':>9} "
                 f"{'wait_p50':>9} {'wait_p99':>9} {'skew_p99':>9} "
                 f"{'slowest':>8}")
    for r in view["ranks"]:
        pr = view["per_rank"][str(r)]
        wt = waits.get(str(r), {})
        sk = skews.get(str(r), {})

        def v(d, k):
            return f"{d[k]:.3f}" if k in d else "-"

        lines.append(
            f"{r:<6} {pr['windows']:>8} {pr['compiles']:>9} "
            f"{v(wt, 'p50_ms'):>9} {v(wt, 'p99_ms'):>9} "
            f"{v(sk, 'p99_ms'):>9} {slowest.get(str(r), 0):>8}"
        )
    straggler = view.get("attribution", {}).get("straggler")
    if straggler is not None:
        lines.append(
            f"  slowest-rank attribution: rank {straggler} gated the "
            "exchange most often (its peers waited on it)"
        )
    return "\n".join(lines)


# --------------------------------------------------------------------------
# the canonical hardware-free capture (train m2 + paged serve)
# --------------------------------------------------------------------------

def capture(out_dir: str) -> dict:
    """Record the canonical run into ``out_dir`` and return the
    exported paths (``trace.jsonl`` / ``trace.chrome.json`` /
    ``metrics.json``).

    Two legs against the ambient tracer/registry (reset first so the
    artifact is exactly this run): (1) the fused train driver with
    gradient-accumulation microbatches=2 on the toy AMP O2 problem —
    several windows so warm dispatches dominate and the cold compile is
    attributable; (2) the paged serve engine on the tiny GPT stack
    draining mixed-length traffic with a shared-prefix duplicate
    (prefix hits + a copy-on-write split) and chunked prefill
    interleaving.  CPU-only, no hardware, ~half a minute.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import apex_tpu.amp as amp
    from apex_tpu import obs
    from apex_tpu.train import (
        FusedTrainDriver,
        amp_microbatch_step,
        read_metrics,
    )

    obs.reset_default()
    obs.reset_default_flightrec()
    registry = obs.default_registry()

    # -- leg 1: train, microbatches=2 -----------------------------------
    amp_ = amp.initialize("O2")
    from apex_tpu.optimizers import fused_sgd

    opt = amp.AmpOptimizer(fused_sgd(0.05, momentum=0.9), amp_)

    def grad_fn(carry, batch):
        params, state = carry
        x, y = batch

        def scaled(mp):
            loss = jnp.mean(jnp.square(x @ mp["w"] - y))
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        return grads, {"loss": loss}

    rng = np.random.RandomState(0)
    p = {"w": jnp.asarray(rng.randn(64, 32).astype(np.float32) * 0.1)}
    step = amp_microbatch_step(grad_fn, opt, microbatches=2)
    driver = FusedTrainDriver(step, steps_per_dispatch=2,
                              metrics={"loss": "last"})
    carry = (p, opt.init(p))
    for _ in range(4):  # window 1 compiles; 2-4 are the warm economics
        xs = jnp.asarray(rng.randn(4, 16, 64).astype(np.float32))
        ys = jnp.asarray(rng.randn(4, 16, 32).astype(np.float32))
        carry, res = driver.run_window(carry, (xs, ys))
        read_metrics(res.metrics, registry=registry)

    # -- leg 2: paged serve, mixed traffic ------------------------------
    import apex_tpu.serve as serve
    from apex_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    pool = rng.randint(0, cfg.vocab_size, size=(48,))
    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(pool[None, :16])
    )["params"]
    dec = serve.GPTDecoder(cfg, params, tokens_per_dispatch=4)
    # live SLO machinery (ISSUE 10): tight objectives so the rendered
    # report shows real window quantiles and burn state
    slo = obs.SloTracker([
        obs.SloObjective("ttft_ms", 0.99, 5.0, 2_000.0),
        obs.SloObjective("itl_ms", 0.99, 2.0, 2_000.0),
    ])
    eng = serve.ServeEngine(dec, slots=2, max_len=64, paged=True,
                            page_len=8, prefill_chunk=16,
                            registry=registry, slo_tracker=slo,
                            slo_admission=True)
    long_p = [int(t) for t in pool[:19]]
    short_p = [int(t) for t in pool[19:24]]
    eng.submit(long_p, max_new_tokens=8)
    eng.submit(short_p, max_new_tokens=5, priority=2)
    for _ in range(3):
        eng.step()
    # shared-prefix duplicate: page-identity reuse + a COW split
    eng.submit(list(long_p), max_new_tokens=5)
    eng.submit([int(t) for t in pool[5:14]], max_new_tokens=6)
    eng.run()
    eng.stats()
    slo_report = eng.slo_report()

    # -- leg 3: self-healing serve under a fixed fault plan -------------
    # (one retried dispatch + one engine crash-recovery, so the
    # rendered report exercises the recovery ledger end to end)
    from apex_tpu.resilience import (
        DISPATCH_ERROR,
        ENGINE_CRASH,
        FaultEvent,
        FaultPlan,
        ResilientServeEngine,
    )

    plan = FaultPlan([
        FaultEvent("serve/decode_window", 1, DISPATCH_ERROR),
        FaultEvent("serve/boundary", 3, ENGINE_CRASH),
    ])
    res = ResilientServeEngine(
        dec, fault_plan=plan, registry=registry, slots=2, max_len=64,
        paged=True, page_len=8, prefill_chunk=16,
    )
    res.submit(list(long_p), max_new_tokens=6)
    res.submit([int(t) for t in pool[9:16]], max_new_tokens=5)
    res.run()
    assert res.retries and res.restarts, "capture plan did not fire"

    paths = obs.export_default(out_dir)
    assert paths is not None, "capture recorded nothing (obs disabled?)"
    # the SLO snapshot rides the (line-appendable) jsonl as its own line
    obs.write_slo_line(paths["jsonl"], slo_report)
    # ... and so does the flight recorder's ring (ISSUE 11): the
    # faulted leg above recorded boundaries + fault + recovery, so the
    # rendered report's flight-recorder section shows a real postmortem
    fr = obs.default_flightrec()
    if fr.enabled and fr.recorded:
        obs.write_flightrec_line(paths["jsonl"], fr)
    obs.write_openmetrics(
        os.path.join(out_dir, "metrics.om.txt"), registry, slo_report
    )
    paths["openmetrics"] = os.path.join(out_dir, "metrics.om.txt")
    return paths


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Text summary of an apex_tpu.obs trace"
    )
    ap.add_argument("trace", nargs="?", default=None,
                    help="trace.jsonl (or a directory containing one)")
    ap.add_argument("--capture", metavar="DIR", default=None,
                    help="record the canonical train+serve run into DIR "
                         "first, then report it")
    ap.add_argument("--merge", metavar="DIR", nargs="+", default=None,
                    help="merge per-host trace.jsonl exports (host id "
                         "stamped in the meta/span args) into ONE fleet "
                         "report with a per-host straggler table and "
                         "the correlation-stitched request table; a "
                         "PARENT directory globs its children's "
                         "exports; exits nonzero on orphaned "
                         "correlation ids")
    ap.add_argument("--gang", metavar="DIR", default=None,
                    help="render the merged per-rank GANG telemetry "
                         "view (apex_tpu.obs.gangview) recorded under "
                         "DIR (an exchange root)")
    ap.add_argument("--straggler-factor", type=float, default=3.0,
                    help="--merge: flag a host whose decode_window p99 "
                         "exceeds this multiple of the fleet median")
    ap.add_argument("--census", metavar="FILE", default=None,
                    help="compiled-cost census JSON (tools/lint_graphs.py "
                         "--census-out) — adds the roofline section")
    ap.add_argument("--peak-gflops", type=float, default=None,
                    help="machine peak GFLOP/s for utilization "
                         "(omit: achieved rates only)")
    ap.add_argument("--peak-gbps", type=float, default=None,
                    help="machine peak memory GB/s for utilization")
    ap.add_argument("--top", type=int, default=15)
    args = ap.parse_args(argv)
    if args.gang:
        print(render_gang(args.gang))
        if not (args.merge or args.trace or args.capture):
            return 0
    if args.merge:
        hosts = load_hosts(args.merge)
        print(render_fleet(hosts,
                           straggler_factor=args.straggler_factor,
                           top=args.top))
        _, orphans = stitch_correlations(hosts)
        if orphans:
            print(f"# ERROR: {len(orphans)} orphaned correlation "
                  "id(s) — stitching is broken", file=sys.stderr)
            return 1
        return 0
    if args.capture:
        paths = capture(args.capture)
        print(f"# captured: {paths['jsonl']}")
        target = args.capture
    elif args.trace:
        target = args.trace
    else:
        ap.error("give a trace path or --capture DIR")
    census = None
    if args.census:
        import json

        with open(args.census) as f:
            census = json.load(f)
    events, metrics = load(target)
    print(render(
        events, metrics, top=args.top, census=census,
        peak_flops=(args.peak_gflops * 1e9 if args.peak_gflops
                    else None),
        peak_bytes=(args.peak_gbps * 1e9 if args.peak_gbps else None),
    ))
    return 0


if __name__ == "__main__":
    sys.exit(main())
