"""Live SLO engine + open-loop traffic harness (ISSUE 10).

Three layers under test, all deterministic by construction:

- **windowed quantiles** — :class:`apex_tpu.obs.WindowedHistogram` on
  a fake clock: hand-computed sliding p50/p99 across sub-window
  rotation, expiry after quiet periods, decimation determinism, and
  the lifetime-exact count/sum contract;
- **burn alerts** — :class:`apex_tpu.obs.SloTracker`: multi-rate
  trigger (fast AND slow burn), hand-computed hysteresis (the band
  between ``clear_burn`` and ``fast_burn`` holds state), objective
  parsing, machine-readable report round-trip, and the
  ``APEX_TPU_OBS=0`` free-tracker contract;
- **the harness + scheduler** — seeded
  :class:`apex_tpu.serve.TrafficPlan` byte-stability, byte-identical
  replay of a full engine run on the virtual clock (tokens, TTFT
  timeline and SLO report included), the same plan driving
  ServeEngine / ResilientServeEngine / FleetRouter, priority classes
  honored at admission, prefill-yield under ITL burn, and greedy
  token-exactness across FIFO vs SLO-aware admission.
"""
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.serve as serve
from apex_tpu import obs
from apex_tpu.models.gpt import GPTConfig, GPTLM
from apex_tpu.obs.slo import SloObjective, SloTracker, WindowedHistogram

MS = 1_000_000  # ns per ms


# ---------------------------------------------------------------------------
# windowed quantiles
# ---------------------------------------------------------------------------

class TestWindowedHistogram:
    def test_hand_computed_sliding_quantiles(self):
        """4 sub-windows of 25 ms over a 100 ms window: observations
        older than the ring fall out, and p50/p99 over the survivors
        match the nearest-rank definition by hand."""
        wh = WindowedHistogram("x", window_ms=100.0, sub_windows=4,
                               clock=lambda: 0)
        # one observation per 10 ms: values 0..9 at t=0..90ms
        for i in range(10):
            wh.observe(float(i), t=i * 10 * MS)
        # head bucket = 90//25 = 3, ring floor = 0: all 10 retained.
        # nearest-rank p50 over [0..9] = ceil(0.5*10)-1 = idx 4 -> 4.0
        assert wh.quantile(0.5) == 4.0
        assert wh.quantile(0.99) == 9.0
        # advance to t=130ms: head bucket 5, floor 2 -> buckets 0 and 1
        # (values 0..4 at t<50ms) expire; survivors are 5..9
        wh.advance(130 * MS)
        assert wh.window_count() == 5
        assert wh.quantile(0.5) == 7.0  # ceil(.5*5)-1 = idx 2 of [5..9]
        assert wh.quantile(0.99) == 9.0
        # lifetime accounting never expires
        assert wh.count == 10 and wh.sum == sum(range(10))
        assert wh.min == 0.0 and wh.max == 9.0

    def test_full_expiry_is_empty(self):
        wh = WindowedHistogram("x", window_ms=100.0, sub_windows=4,
                               clock=lambda: 0)
        wh.observe(1.0, t=0)
        wh.advance(500 * MS)
        assert wh.window_count() == 0
        assert math.isnan(wh.quantile(0.5))
        assert wh.count == 1  # lifetime survives

    def test_stale_timestamp_clamps_forward(self):
        """A timestamp older than the window head lands in the head
        bucket instead of resurrecting an expired one."""
        wh = WindowedHistogram("x", window_ms=100.0, sub_windows=4,
                               clock=lambda: 0)
        wh.observe(1.0, t=200 * MS)
        wh.observe(2.0, t=0)  # stale: clamped into the head bucket
        assert wh.window_count() == 2
        wh.advance(320 * MS)  # head 12, floor 9; bucket 8 expires
        assert wh.window_count() == 0

    def test_decimation_determinism(self):
        """Two histograms fed the identical over-capacity sequence
        retain identical samples (fixed-stride thinning, no
        randomness)."""
        def feed():
            wh = WindowedHistogram("x", window_ms=100.0, sub_windows=2,
                                   max_samples=64, clock=lambda: 0)
            rng = np.random.RandomState(3)
            for i in range(500):
                wh.observe(float(rng.rand()), t=i * MS)
            return wh
        a, b = feed(), feed()
        assert a._window_samples() == b._window_samples()
        assert a.quantile(0.99) == b.quantile(0.99)
        assert a.count == b.count == 500

    def test_snapshot_shape(self):
        wh = WindowedHistogram("x", window_ms=50.0, sub_windows=2,
                               clock=lambda: 0)
        assert wh.snapshot()["window_count"] == 0
        wh.observe(3.0, t=0)
        snap = wh.snapshot()
        assert snap["p50"] == 3.0 and snap["lifetime_count"] == 1


# ---------------------------------------------------------------------------
# objectives + burn alerts
# ---------------------------------------------------------------------------

class TestObjectives:
    def test_parse(self):
        o = obs.parse_objective("ttft_ms p99 < 50 over 15s")
        assert o == SloObjective("ttft_ms", 0.99, 50.0, 15_000.0)
        o = obs.parse_objective("itl_ms p90 < 2.5")
        assert o.quantile == 0.9 and o.window_ms == 15_000.0
        assert "p90" in o.name and o.budget == pytest.approx(0.1)
        with pytest.raises(ValueError):
            obs.parse_objective("nonsense < 5")

    def test_validation(self):
        with pytest.raises(ValueError):
            SloObjective("x", 1.5, 10.0)
        with pytest.raises(ValueError):
            SloObjective("x", 0.9, -1.0)


class TestBurnAlerts:
    def _tracker(self, **kw):
        kw.setdefault("clock", lambda: 0)
        kw.setdefault("enabled", True)
        # p90 objective -> 10% error budget; fast window 100 ms, slow
        # 4x.  fast_burn 2.0 => trip at >= 20% violating; clear_burn
        # 1.0 => clear below 10% violating.
        return SloTracker(
            [SloObjective("m", 0.9, 10.0, 100.0)],
            fast_burn=2.0, slow_burn=1.0, clear_burn=1.0, **kw,
        )

    def test_trigger_and_hysteresis_hand_computed(self):
        tr = self._tracker()
        # 8 good + 2 bad in the window = 20% of budget-10% -> burn 2.0:
        # exactly at the trigger; slow burn identical -> alert trips
        for i in range(8):
            tr.observe("m", 1.0, t=i * MS)
        tr.observe("m", 99.0, t=8 * MS)
        assert not tr.burning("m", t=8 * MS)  # burn 1/9/0.1 = 1.11 < 2
        tr.observe("m", 99.0, t=9 * MS)       # burn 2/10/0.1 = 2.0
        assert tr.burning("m", t=9 * MS)
        rep = tr.report(t=9 * MS)
        row = rep.objectives[0]
        assert row["alerting"] and row["trips"] == 1
        assert row["burn_fast"] == pytest.approx(2.0)
        # hysteresis: dilute to 2 bad / 14 total = 14.3% -> burn 1.43,
        # inside the (1.0, 2.0) band: alert HOLDS
        for i in range(10, 14):
            tr.observe("m", 1.0, t=i * MS)
        assert tr.burning("m", t=13 * MS)
        # dilute below clear_burn: 2 bad / 22 total = 9.1% -> burn
        # 0.91 < 1.0: alert clears
        for i in range(14, 22):
            tr.observe("m", 1.0, t=i * MS)
        assert not tr.burning("m", t=21 * MS)
        row = tr.report(t=21 * MS).objectives[0]
        assert row["trips"] == 1 and row["clears"] == 1

    def test_slow_window_gates_the_trip(self):
        """A fast-window spike alone must not alert when the slow
        window is still healthy (the multi-rate rule)."""
        tr = self._tracker()
        # 360 good observations spread over the slow window (400 ms)
        for i in range(360):
            tr.observe("m", 1.0, t=i * MS)
        # now a fast burst of 12 bad inside one fast window: fast burn
        # = 12/12/0.1 >> 2, but slow burn over ~372 obs with the good
        # history: well under 1.0 -> NO alert
        for i in range(12):
            tr.observe("m", 99.0, t=(400 + i) * MS)
        assert not tr.burning("m", t=412 * MS)

    def test_time_passing_clears(self):
        tr = self._tracker()
        for i in range(10):
            tr.observe("m", 99.0, t=i * MS)
        assert tr.burning("m", t=9 * MS)
        # the window empties after enough quiet time: burn 0 -> clear
        assert not tr.burning("m", t=2_000 * MS)

    def test_clear_above_fast_raises(self):
        with pytest.raises(ValueError):
            SloTracker([], fast_burn=1.0, clear_burn=2.0, enabled=True)

    def test_disabled_tracker_is_free(self):
        tr = self._tracker(enabled=False)
        for i in range(50):
            tr.observe("m", 99.0, t=i * MS)
        assert tr.observations == 0
        assert not tr.burning("m", t=50 * MS)
        rep = tr.report(t=50 * MS)
        assert rep.enabled is False
        assert rep.objectives[0]["window_count"] == 0

    def test_obs_kill_switch_defaults_tracker_off(self):
        obs.set_enabled_override(False)
        try:
            tr = SloTracker([SloObjective("m", 0.9, 1.0, 100.0)],
                            clock=lambda: 0)
            tr.observe("m", 99.0, t=0)
            assert tr.observations == 0 and not tr.enabled
        finally:
            obs.set_enabled_override(None)

    def test_report_round_trip(self):
        tr = self._tracker()
        tr.observe("m", 5.0, t=0)
        rep = tr.report(t=MS, lifecycle={"completed": 1})
        back = obs.SloReport.from_json(rep.to_json())
        assert back.to_dict() == rep.to_dict()
        assert back.lifecycle == {"completed": 1}

    def test_openmetrics_exposition(self):
        reg = obs.MetricsRegistry()
        reg.counter("serve.decode_dispatches").inc(7)
        reg.gauge("serve.peak").set(3)
        reg.histogram("serve.ttft_ms").observe(12.5)
        tr = self._tracker()
        tr.observe("m", 5.0, t=0)
        text = obs.to_openmetrics(reg, tr.report(t=MS))
        assert text.endswith("# EOF\n")
        assert "apex_tpu_serve_decode_dispatches_total 7" in text
        assert 'apex_tpu_serve_ttft_ms{quantile="0.5"} 12.5' in text
        assert "# TYPE apex_tpu_serve_ttft_ms summary" in text
        assert ('apex_tpu_slo_objective_threshold{objective="m_p90",'
                'metric="m"} 10') in text
        # deterministic: identical inputs -> identical text
        assert text == obs.to_openmetrics(reg, tr.report(t=MS))


# ---------------------------------------------------------------------------
# traffic plans
# ---------------------------------------------------------------------------

def _mkplan(seed=5, **kw):
    base = dict(requests=12, rate_rps=150.0, arrival="bursty",
                burst_factor=6.0, burst_on_s=0.1, burst_off_s=0.3,
                vocab_size=97, n_prefixes=3, prefix_len=6, zipf_s=1.2,
                shared_frac=0.5, prompt_min=2, prompt_scale=4.0,
                prompt_alpha=1.2, prompt_cap=30, output_min=2,
                output_scale=3.0, output_alpha=1.3, output_cap=10,
                priorities=(0, 2), interactive_max_prompt=12)
    base.update(kw)
    return serve.TrafficPlan.from_seed(seed, **base)


class TestTrafficPlan:
    def test_seeded_plan_is_byte_stable(self):
        assert _mkplan().to_json() == _mkplan().to_json()
        assert _mkplan(seed=6).to_json() != _mkplan().to_json()

    def test_json_round_trip(self):
        p = _mkplan(deadline_frac=0.5, deadline_ms=40.0)
        q = serve.TrafficPlan.from_json(p.to_json())
        assert q.to_json() == p.to_json()
        assert q.seed == 5

    def test_shapes(self):
        p = _mkplan(deadline_frac=1.0)
        assert len(p) == 12
        ats = [r.at_ms for r in p.requests]
        assert ats == sorted(ats) and ats[0] > 0
        assert all(r.deadline_ms is not None for r in p.requests)
        assert any(r.prefix_id >= 0 for r in p.requests)
        # size-assigned priorities: short prompts are interactive
        for r in p.requests:
            assert r.priority == (2 if len(r.prompt) <= 12 else 0)
        st = p.stats()
        assert st["requests"] == 12 and st["with_deadline"] == 12

    def test_poisson_arrivals(self):
        p = _mkplan(arrival="poisson")
        assert p.meta["burst_factor"] == 1.0
        with pytest.raises(ValueError):
            _mkplan(arrival="weird")


# ---------------------------------------------------------------------------
# the harness driving real engines
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_decoder():
    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(16,))
    params = model.init(
        jax.random.PRNGKey(0), jnp.asarray(ids[None, :])
    )["params"]
    return serve.GPTDecoder(cfg, params, tokens_per_dispatch=4), cfg


def _engine_plan(cfg, seed=5, **kw):
    return _mkplan(seed, vocab_size=cfg.vocab_size, **kw)


def _run_engine_leg(dec, plan, slo_on, *, tracker_objs=None, slots=2,
                    num_pages=None):
    gen = serve.LoadGen(plan, step_cost_ms=5.0)
    tracker = None
    if tracker_objs is not None:
        tracker = SloTracker(tracker_objs, clock=gen.clock)
    eng = serve.ServeEngine(
        dec, slots=slots, max_len=64, paged=True, page_len=8,
        num_pages=num_pages, prefill_chunk=16, clock=gen.clock,
        slo_tracker=tracker, slo_admission=slo_on,
        registry=obs.MetricsRegistry(),
    )
    return gen.run(eng)


class TestLoadGen:
    def test_engine_run_is_byte_replayable(self, tiny_decoder):
        """Same seed -> identical arrival timeline, identical greedy
        tokens, identical latency quantiles and SLO report across two
        full runs (the ISSUE 10 acceptance)."""
        dec, cfg = tiny_decoder
        plan = _engine_plan(cfg)
        objs = [SloObjective("ttft_ms", 0.9, 30.0, 300.0)]
        a = _run_engine_leg(dec, plan, True, tracker_objs=objs)
        b = _run_engine_leg(dec, plan, True, tracker_objs=objs)
        assert a.to_json() == b.to_json()
        assert a.submitted == 12 and a.completed == 12
        assert a.ttft_ms["count"] == 12
        assert a.slo is not None  # the report rode along

    def test_clock_mismatch_rejected(self, tiny_decoder):
        dec, cfg = tiny_decoder
        plan = _engine_plan(cfg)
        gen = serve.LoadGen(plan)
        eng = serve.ServeEngine(dec, slots=2, max_len=64, paged=True,
                                page_len=8,
                                registry=obs.MetricsRegistry())
        with pytest.raises(ValueError, match="virtual clock"):
            gen.run(eng)

    def test_resilient_engine_deadlines_abandon(self, tiny_decoder):
        """The same plan through ResilientServeEngine on the virtual
        clock: deadlines fire at deterministic virtual times and land
        in the abandonment summary."""
        from apex_tpu.resilience import ResilientServeEngine

        dec, cfg = tiny_decoder
        plan = _engine_plan(cfg, deadline_frac=1.0, deadline_ms=30.0,
                            output_cap=16)

        def leg():
            gen = serve.LoadGen(plan, step_cost_ms=5.0)
            eng = ResilientServeEngine(
                dec, clock=gen.clock, registry=obs.MetricsRegistry(),
                slots=2, max_len=64, paged=True, page_len=8,
                prefill_chunk=16,
            )
            return gen.run(eng)

        a, b = leg(), leg()
        assert a.to_json() == b.to_json()  # abandonment is replayable
        assert a.abandoned > 0
        assert a.abandonment_rate == pytest.approx(
            a.abandoned / (a.abandoned + a.completed), abs=1e-3
        )

    def test_fleet_router_target(self, tiny_decoder):
        """The same generator drives a 2-host fleet: per-host
        registries merge into one report, and the run is replayable."""
        from apex_tpu.fleet import FleetHost, FleetRouter

        dec, cfg = tiny_decoder
        plan = _engine_plan(cfg)

        def leg():
            gen = serve.LoadGen(plan, step_cost_ms=5.0)
            hosts = [
                FleetHost(i, dec, slots=2, max_len=64, paged=True,
                          page_len=8, prefill_chunk=16,
                          clock=gen.clock)
                for i in range(2)
            ]
            router = FleetRouter(hosts, preflight=False,
                                 registry=obs.MetricsRegistry(),
                                 tracer=obs.NULL_TRACER)
            return gen.run(router)

        a = leg()
        assert a.completed == 12 and a.ttft_ms["count"] == 12
        assert a.to_json() == leg().to_json()

    def test_greedy_tokens_match_across_targets(self, tiny_decoder):
        """ServeEngine vs ResilientServeEngine vs FleetRouter on the
        SAME plan (no deadlines): every request's greedy stream is
        identical — the harness drives all three identically."""
        from apex_tpu.fleet import FleetHost, FleetRouter
        from apex_tpu.resilience import ResilientServeEngine

        dec, cfg = tiny_decoder
        plan = _engine_plan(cfg)
        plain = _run_engine_leg(dec, plan, False)

        gen = serve.LoadGen(plan, step_cost_ms=5.0)
        resil = gen.run(ResilientServeEngine(
            dec, clock=gen.clock, registry=obs.MetricsRegistry(),
            slots=2, max_len=64, paged=True, page_len=8,
            prefill_chunk=16,
        ))
        gen2 = serve.LoadGen(plan, step_cost_ms=5.0)
        hosts = [FleetHost(0, dec, slots=2, max_len=64, paged=True,
                           page_len=8, prefill_chunk=16,
                           clock=gen2.clock)]
        fleet = gen2.run(FleetRouter(hosts, preflight=False,
                                     registry=obs.MetricsRegistry(),
                                     tracer=obs.NULL_TRACER))
        assert plain.tokens == resil.tokens == fleet.tokens


class TestSloAdmission:
    def test_priority_classes_honored(self, tiny_decoder):
        """With one slot, the high-priority request submitted LAST is
        admitted at the first boundary under SLO-aware admission; the
        FIFO engine admits the head.  Both drains complete."""
        dec, cfg = tiny_decoder
        rng = np.random.RandomState(1)
        prompts = [[int(t) for t in rng.randint(0, cfg.vocab_size,
                                                size=6)]
                   for _ in range(3)]

        def first_admitted(slo_on):
            eng = serve.ServeEngine(
                dec, slots=1, max_len=64, paged=True, page_len=8,
                prefill_chunk=16, slo_admission=slo_on,
                registry=obs.MetricsRegistry(),
            )
            uids = [eng.submit(p, max_new_tokens=2, priority=pr)
                    for p, pr in zip(prompts, (0, 0, 5))]
            eng.step()
            started = {u for u, (t, _) in eng.progress().items() if t}
            out = eng.run()
            assert set(out) == set(uids)  # everyone still finishes
            return uids, started

        uids_f, started_f = first_admitted(False)
        uids_p, started_p = first_admitted(True)
        assert uids_f[0] in started_f       # FIFO: head first
        assert uids_f[2] not in started_f
        assert uids_p[2] in started_p       # priority: hi first
        assert uids_p[0] not in started_p

    def test_prefill_yields_under_itl_burn(self, tiny_decoder):
        """Force the ITL alert on and verify prefill chunks yield the
        boundary while decodes are active (serve.slo.prefill_yields),
        and that the yielded prefill still completes."""
        dec, cfg = tiny_decoder
        tracker = SloTracker([SloObjective("itl_ms", 0.9, 1e-9,
                                           10_000.0)], enabled=True)
        reg = obs.MetricsRegistry()
        eng = serve.ServeEngine(dec, slots=2, max_len=64, paged=True,
                                page_len=8, prefill_chunk=8,
                                slo_tracker=tracker, slo_admission=True,
                                registry=reg)
        rng = np.random.RandomState(2)
        eng.submit([int(t) for t in rng.randint(0, cfg.vocab_size,
                                                size=5)],
                   max_new_tokens=24)
        for _ in range(3):
            eng.step()  # ITL observations all violate -> alert trips
        assert tracker.burning("itl_ms")
        eng.submit([int(t) for t in rng.randint(0, cfg.vocab_size,
                                                size=30)],
                   max_new_tokens=4)
        eng.run()
        assert reg.get("serve.slo.prefill_yields").value > 0
        assert all(done for _, done in eng.progress().values())

    def test_tokens_exact_across_policies(self, tiny_decoder):
        """Every request that completes under both FIFO and SLO-aware
        admission streams IDENTICAL tokens under greedy decoding —
        scheduling reorders time, never content."""
        dec, cfg = tiny_decoder
        plan = _engine_plan(cfg, seed=9, requests=14)
        objs = [SloObjective("ttft_ms", 0.9, 20.0, 200.0),
                SloObjective("itl_ms", 0.99, 100.0, 200.0)]
        fifo = _run_engine_leg(dec, plan, False, num_pages=1 + 10)
        slo = _run_engine_leg(dec, plan, True, tracker_objs=objs,
                              num_pages=1 + 10)
        assert set(fifo.tokens) == set(slo.tokens)
        for uid in fifo.tokens:
            a, b = fifo.tokens[uid], slo.tokens[uid]
            n = min(len(a), len(b))
            assert a[:n] == b[:n], f"uid {uid} diverged"

    def test_interactive_class_waits_less_than_under_fifo(
            self, tiny_decoder):
        """What the policy is for: on a seeded plan whose interactive
        requests queue behind batch prompts for a page-starved pool,
        admission by class with the TTFT tracker live cuts the
        interactive class's p99 time to first token against FIFO.
        Virtual clock: the direction is the claim, not the
        milliseconds."""
        dec, cfg = tiny_decoder
        plan = _engine_plan(cfg, seed=9, requests=24)
        objs = [SloObjective("ttft_ms", 0.9, 20.0, 200.0),
                SloObjective("itl_ms", 0.99, 100.0, 200.0)]
        fifo = _run_engine_leg(dec, plan, False, num_pages=1 + 10)
        slo = _run_engine_leg(dec, plan, True, tracker_objs=objs,
                              num_pages=1 + 10)
        assert slo.ttft_ms_by_priority[2]["p99"] \
            < fifo.ttft_ms_by_priority[2]["p99"]

    def test_env_knob_default_off(self, tiny_decoder, monkeypatch):
        dec, _ = tiny_decoder
        monkeypatch.delenv("APEX_TPU_SLO_ADMISSION", raising=False)
        eng = serve.ServeEngine(dec, slots=2, max_len=64,
                                registry=obs.MetricsRegistry())
        assert eng.slo_admission is False and eng._slo is None
        monkeypatch.setenv("APEX_TPU_SLO_ADMISSION", "1")
        eng = serve.ServeEngine(dec, slots=2, max_len=64,
                                registry=obs.MetricsRegistry())
        assert eng.slo_admission is True
        assert eng._slo is not None  # default_serve tracker built

    def test_disabled_obs_keeps_engine_working(self, tiny_decoder):
        """APEX_TPU_OBS=0 + slo_admission: no tracker observations,
        priorities still honored, drain still completes."""
        dec, cfg = tiny_decoder
        obs.set_enabled_override(False)
        try:
            eng = serve.ServeEngine(dec, slots=2, max_len=64,
                                    paged=True, page_len=8,
                                    slo_admission=True,
                                    registry=obs.MetricsRegistry())
            assert eng._slo is None  # nothing to feed it
            rng = np.random.RandomState(4)
            for n in (5, 9):
                eng.submit([int(t) for t in rng.randint(
                    0, cfg.vocab_size, size=n)], max_new_tokens=3)
            out = eng.run()
            assert len(out) == 2
        finally:
            obs.set_enabled_override(None)


# ---------------------------------------------------------------------------
# reporting surfaces
# ---------------------------------------------------------------------------

class TestReporting:
    def test_lifecycle_summary_single_source(self):
        reg = obs.MetricsRegistry()
        lc = obs.RequestLifecycle(reg)
        lc.submitted(0, 0)
        lc.admitted(0, 2 * MS)
        lc.tokens(0, 1, 10 * MS)
        lc.tokens(0, 4, 20 * MS)
        lc.finished(0, 20 * MS)
        lc.submitted(1, 5 * MS)
        lc.tokens(1, 2, 15 * MS)
        lc.abandoned(1, 30 * MS)
        s = lc.summary()
        assert s["completed"] == 1 and s["abandoned"] == 1
        assert s["abandonment_rate"] == 0.5
        assert s["completed_tokens"] == 5
        assert s["abandoned_tokens"] == 2
        assert s["wall_ms"] == 30.0
        # goodput = completed tokens / wall between first submit and
        # last event = 5 / 30ms
        assert s["goodput_tokens_per_s"] == pytest.approx(5 / 0.030,
                                                          rel=1e-3)
        # the counter mirror trace_report reads
        assert reg.get("serve.completed_tokens").value == 5

    def test_trace_report_slo_section(self, tmp_path):
        """write_jsonl(slo_report=...) -> render() shows the SLO
        objectives and lifecycle lines; --merge renders per host."""
        from tools import trace_report

        tr = obs.Tracer(enabled=True, clock=lambda: 0,
                        monitor_compiles=False)
        with tr.span("serve/decode_window"):
            pass
        tracker = SloTracker([SloObjective("ttft_ms", 0.99, 50.0,
                                           15_000.0)], enabled=True,
                             clock=lambda: 0)
        tracker.observe("ttft_ms", 12.0, t=0)
        rep = tracker.report(t=MS, lifecycle={
            "completed": 3, "abandoned": 1, "abandonment_rate": 0.25,
            "completed_tokens": 30, "abandoned_tokens": 2,
            "wall_ms": 100.0, "goodput_tokens_per_s": 300.0,
        })
        p = tmp_path / "trace.jsonl"
        obs.write_jsonl(tr, str(p), slo_report=rep)
        events, metrics = trace_report.load(str(p))
        text = trace_report.render(events, metrics)
        assert "SLO objectives" in text
        assert "ttft_ms_p99" in text and "met" in text
        assert "goodput" in text and "abandonment" in text
        # fleet merge: two hosts, same report
        p2 = tmp_path / "h2.jsonl"
        obs.write_jsonl(tr, str(p2), extra_meta={"host": 1},
                        slo_report=rep)
        hosts = trace_report.load_hosts([str(p), str(p2)])
        ftext = trace_report.render_fleet(hosts)
        assert "per-host SLO" in ftext
        assert "fleet" in ftext

    def test_fleet_host_export_carries_slo(self, tiny_decoder,
                                           tmp_path):
        from apex_tpu.fleet import FleetHost
        from tools import trace_report

        dec, cfg = tiny_decoder
        tracker = SloTracker([SloObjective("ttft_ms", 0.99, 1e6,
                                           15_000.0)], enabled=True)
        host = FleetHost(3, dec, slots=2, max_len=64, paged=True,
                         page_len=8, prefill_chunk=16,
                         slo_tracker=tracker, slo_admission=True)
        host.start()
        rng = np.random.RandomState(6)
        host.engine.submit([int(t) for t in rng.randint(
            0, cfg.vocab_size, size=6)], max_new_tokens=3)
        while host.engine.step():
            pass
        path = host.export_trace(str(tmp_path / "host3.jsonl"))
        events, _ = trace_report.load(path)
        slo = next(e for e in events if e.get("type") == "slo")
        assert slo["report"]["objectives"][0]["metric"] == "ttft_ms"
        assert slo["report"]["lifecycle"]["completed"] == 1
        text = trace_report.render_fleet(
            trace_report.load_hosts([path]))
        assert "per-host SLO" in text


def test_plan_json_is_parseable():
    p = _mkplan()
    d = json.loads(p.to_json())
    assert d["meta"]["schema"] == "apex_tpu.loadgen.v1"
    assert len(d["requests"]) == 12
