"""Runtime telemetry layer tests (ISSUE 6).

Four strata, cheapest first: pure-host units with a fake clock
(registry quantile exactness, tracer nesting/exporters, the TTFT/ITL
math against hand-computed timelines), the CompileMonitor bridge
(executed-vs-compiled span tagging, the seeded warm-compile anomaly),
the pyprof Chrome-trace round trip, and finally the instrumented
engine/driver plus the canonical ``tools/trace_report.py`` capture —
all hardware-free.
"""
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import obs
from apex_tpu.models.gpt import GPTConfig, GPTLM
from apex_tpu.serve import GPTDecoder, ServeEngine

MS = 1_000_000  # ns per ms


class FakeClock:
    """Deterministic ns clock for hand-computed timelines."""

    def __init__(self):
        self.t = 0

    def __call__(self):
        return self.t

    def advance_ms(self, ms):
        self.t += int(ms * MS)
        return self.t


@pytest.fixture
def clean_default():
    """Isolate the ambient tracer/registry and the enabled override."""
    obs.reset_default()
    yield
    obs.set_enabled_override(None)
    obs.reset_default()


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

class TestRegistry:
    def test_counter_and_gauge(self):
        reg = obs.MetricsRegistry()
        reg.counter("c").inc()
        reg.counter("c").inc(3)
        g = reg.gauge("g")
        g.set(5)
        g.set(2)
        g.set_max(1)  # below the running value: no-op
        snap = reg.snapshot()
        assert snap["c"] == {"type": "counter", "value": 4}
        assert snap["g"]["value"] == 2 and snap["g"]["max"] == 5

    def test_histogram_quantiles_exact(self):
        """Nearest-rank over 1..10 — every value hand-checkable."""
        h = obs.Histogram("h")
        for v in [7, 1, 10, 3, 5, 8, 2, 9, 4, 6]:
            h.observe(v)
        assert h.quantile(0.0) == 1
        assert h.quantile(0.5) == 5    # ceil(0.5*10)=5th smallest
        assert h.quantile(0.9) == 9
        assert h.quantile(0.99) == 10
        assert h.quantile(1.0) == 10
        assert h.count == 10 and h.sum == 55
        assert h.min == 1 and h.max == 10 and h.mean == 5.5
        assert h.exact

    def test_histogram_decimation_deterministic(self):
        """Past max_samples the reservoir thins by a fixed stride —
        exactness flag drops, totals stay exact, and two identically-fed
        histograms stay byte-identical."""
        a, b = (obs.Histogram("h", max_samples=8) for _ in range(2))
        for v in range(100):
            a.observe(float(v))
            b.observe(float(v))
        assert a.count == 100 and a.sum == sum(range(100))
        assert not a.exact
        assert len(a._samples) < 100
        assert a.snapshot() == b.snapshot()

    def test_snapshot_deterministic_under_seed(self):
        regs = []
        for _ in range(2):
            rng = np.random.RandomState(42)
            reg = obs.MetricsRegistry()
            h = reg.histogram("lat_ms")
            for v in rng.rand(500):
                h.observe(float(v))
            reg.counter("n").inc(500)
            regs.append(reg)
        assert regs[0].snapshot() == regs[1].snapshot()
        # JSON round trip preserves the snapshot
        assert json.loads(regs[0].to_json()) == json.loads(
            json.dumps(regs[0].snapshot())
        )

    def test_type_clash_raises(self):
        reg = obs.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.histogram("x")


# ---------------------------------------------------------------------------
# tracer
# ---------------------------------------------------------------------------

class TestTracer:
    def test_nesting_depth_and_durations(self):
        clk = FakeClock()
        tr = obs.Tracer(enabled=True, clock=clk, monitor_compiles=False)
        with tr.span("outer", k=2):
            clk.advance_ms(1)
            with tr.span("inner"):
                clk.advance_ms(3)
            clk.advance_ms(1)
        by = {sp.name: sp for sp in tr.spans}
        assert by["outer"].depth == 0 and by["inner"].depth == 1
        assert by["inner"].dur == 3 * MS
        assert by["outer"].dur == 5 * MS
        assert by["outer"].attrs == {"k": 2}
        # finish order (inner first) — the chrome containment convention
        assert [sp.name for sp in tr.spans] == ["inner", "outer"]

    def test_span_set_and_instant_counter(self):
        clk = FakeClock()
        tr = obs.Tracer(enabled=True, clock=clk, monitor_compiles=False)
        with tr.span("s") as sp:
            sp.set("tokens", 7)
        tr.instant("retire", uid=3)
        tr.counter("pages", 5)
        assert tr.spans[0].attrs == {"tokens": 7}
        kinds = [(k, n) for _, k, n, _ in tr.events]
        assert kinds == [("instant", "retire"), ("counter", "pages")]

    def test_disabled_is_noop(self):
        tr = obs.Tracer(enabled=False)
        s1 = tr.span("a")
        s2 = tr.span("b", x=1)
        assert s1 is s2  # the shared null span: zero allocation
        with s1 as sp:
            sp.set("x", 1)
        tr.instant("i")
        tr.counter("c", 1)
        assert not tr.spans and not tr.events and tr.recorded == 0

    def test_env_kill_switch(self, monkeypatch, clean_default):
        monkeypatch.setenv("APEX_TPU_OBS", "0")
        assert not obs.enabled()
        assert obs.default_tracer() is obs.NULL_TRACER
        monkeypatch.setenv("APEX_TPU_OBS", "1")
        assert obs.enabled()
        assert obs.default_tracer() is not obs.NULL_TRACER
        # the programmatic override wins over the env
        obs.set_enabled_override(False)
        assert obs.default_tracer() is obs.NULL_TRACER

    def test_exporters(self, tmp_path):
        clk = FakeClock()
        tr = obs.Tracer(enabled=True, clock=clk, monitor_compiles=False)
        with tr.span("a"):
            clk.advance_ms(2)
        tr.counter("pages", 3)
        reg = obs.MetricsRegistry()
        reg.histogram("h").observe(1.5)
        jpath = tr.export_jsonl(str(tmp_path / "t.jsonl"), registry=reg)
        events, metrics = obs.read_jsonl(jpath)
        assert events[0]["type"] == "meta"
        span = next(e for e in events if e["type"] == "span")
        assert span["name"] == "a" and span["dur"] == 2 * MS
        counter = next(e for e in events if e["type"] == "counter")
        assert counter["value"] == 3
        assert metrics["h"]["count"] == 1
        cpath = tr.export_chrome(str(tmp_path / "t.json"), registry=reg)
        doc = json.load(open(cpath))
        x = [e for e in doc["traceEvents"] if e["ph"] == "X"]
        c = [e for e in doc["traceEvents"] if e["ph"] == "C"]
        assert x[0]["name"] == "a" and x[0]["dur"] == 2000.0  # us
        assert c[0]["args"]["value"] == 3
        assert doc["otherData"]["metrics"]["h"]["count"] == 1


# ---------------------------------------------------------------------------
# CompileMonitor bridge: executed-vs-compiled span tagging
# ---------------------------------------------------------------------------

class TestCompileAttribution:
    def test_cold_tagged_warm_not(self):
        tr = obs.Tracer(enabled=True)
        try:
            f = jax.jit(lambda x: x * 2 + 1)
            x = jnp.ones((13,))
            with tr.span("cold") as sp_cold:
                f(x)
            with tr.span("warm") as sp_warm:
                f(x)
            assert sp_cold.compiles > 0 and sp_cold.compiled
            assert sp_warm.compiles == 0 and not sp_warm.compiled
            assert tr.compiled_spans() == [sp_cold]
        finally:
            tr.close()

    def test_warm_compile_anomaly_surfaced(self):
        """The seeded anomaly: a shape-varying loop inside a span that
        SHOULD be steady-state shows up as a compiled-tagged span — the
        per-sequence-length recompile bug class, now visible per span
        instead of only as a global count."""
        tr = obs.Tracer(enabled=True)
        try:
            g = jax.jit(lambda x: jnp.sum(x * x))
            with tr.span("decode_window_warm") as sp:
                for n in (3, 4, 5):  # unpadded lengths: one compile each
                    g(jnp.ones((n,)))
            assert sp.compiles >= 3, sp.compiles
            anomalies = [s.name for s in tr.compiled_spans()]
            assert "decode_window_warm" in anomalies
        finally:
            tr.close()

    def test_nested_attribution_innermost(self):
        tr = obs.Tracer(enabled=True)
        try:
            f = jax.jit(lambda x: x - 3)
            with tr.span("outer") as out_sp:
                with tr.span("inner") as in_sp:
                    f(jnp.ones((17,)))
            assert in_sp.compiles > 0
            assert out_sp.compiles == 0  # attributed to the innermost
            assert tr.compiles >= in_sp.compiles
        finally:
            tr.close()


# ---------------------------------------------------------------------------
# request lifecycle: TTFT / ITL / queue delay, hand-computed
# ---------------------------------------------------------------------------

class TestLifecycle:
    def test_hand_computed_timeline(self):
        """submit@0, admit@10ms, first token@30ms, 4 tokens@70ms,
        finish@70ms: queue=10, TTFT=30, ITL=(70-30)/4=10 x4, latency=70,
        5 tokens total."""
        reg = obs.MetricsRegistry()
        lc = obs.RequestLifecycle(reg)
        lc.submitted(1, 0)
        lc.admitted(1, 10 * MS)
        lc.tokens(1, 1, 30 * MS)
        lc.tokens(1, 4, 70 * MS)
        lc.finished(1, 70 * MS)
        s = reg.snapshot()
        assert s["serve.queue_delay_ms"]["p50"] == 10.0
        assert s["serve.ttft_ms"]["p50"] == 30.0
        itl = s["serve.itl_ms"]
        assert itl["count"] == 4 and itl["min"] == itl["max"] == 10.0
        assert s["serve.request_latency_ms"]["p50"] == 70.0
        assert s["serve.tokens_per_request"]["p50"] == 5.0

    def test_first_batch_of_k_tokens(self):
        """A K-token first fetch: one TTFT, K-1 zero ITLs (the window
        produced them in the same sync)."""
        reg = obs.MetricsRegistry()
        lc = obs.RequestLifecycle(reg)
        lc.submitted(7, 5 * MS)
        lc.admitted(7, 5 * MS)
        lc.tokens(7, 4, 25 * MS)
        s = reg.snapshot()
        assert s["serve.ttft_ms"]["p50"] == 20.0
        assert s["serve.itl_ms"]["count"] == 3
        assert s["serve.itl_ms"]["max"] == 0.0
        assert s["serve.queue_delay_ms"]["p50"] == 0.0

    def test_preemption_does_not_recount_queue_delay(self):
        reg = obs.MetricsRegistry()
        lc = obs.RequestLifecycle(reg)
        lc.submitted(1, 0)
        lc.admitted(1, 10 * MS)
        lc.admitted(1, 90 * MS)  # re-admission after preemption
        assert reg.snapshot()["serve.queue_delay_ms"]["count"] == 1

    def test_unknown_uid_ignored(self):
        reg = obs.MetricsRegistry()
        lc = obs.RequestLifecycle(reg)
        lc.tokens(99, 3, 10 * MS)
        lc.finished(99, 10 * MS)
        assert "serve.itl_ms" in reg.names()  # created but empty
        assert reg.snapshot()["serve.itl_ms"]["count"] == 0


# ---------------------------------------------------------------------------
# pyprof bridge: chrome trace round trip
# ---------------------------------------------------------------------------

class TestPyprofRoundTrip:
    def test_chrome_trace_parses_back(self, tmp_path):
        from apex_tpu.pyprof.parse import parse_chrome_trace

        clk = FakeClock()
        tr = obs.Tracer(enabled=True, clock=clk, monitor_compiles=False)
        for dur in (2, 3):  # two "train/dispatch" spans: 2ms + 3ms
            with tr.span("train/dispatch"):
                clk.advance_ms(dur)
        with tr.span("serve/decode_window"):
            clk.advance_ms(4)
        tr.counter("serve/pages_in_use", 2)  # no duration: skipped
        path = tr.export_chrome(str(tmp_path / "t.json"))
        times = parse_chrome_trace(path)
        assert times["train/dispatch"].count == 2
        assert times["train/dispatch"].duration_ns == 5 * MS
        assert times["serve/decode_window"].duration_ns == 4 * MS
        assert "serve/pages_in_use" not in times


# ---------------------------------------------------------------------------
# instrumented engine + driver (real programs, tiny, CPU)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lm():
    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 32)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return cfg, params, np.asarray(ids[0])


@pytest.fixture(scope="module")
def dec4(lm):
    cfg, params, _ = lm
    return GPTDecoder(cfg, params, tokens_per_dispatch=4)


class TestEngineObs:
    def test_stats_is_registry_shim_and_lifecycle_counts(self, dec4, lm):
        _, _, pool = lm
        tracer = obs.Tracer(enabled=True, monitor_compiles=False)
        eng = ServeEngine(dec4, slots=2, max_len=64, paged=True,
                          page_len=8, prefill_chunk=8, tracer=tracer)
        prompts = [[int(t) for t in pool[:6]],
                   [int(t) for t in pool[:6]],  # shared-prefix duplicate
                   [int(t) for t in pool[3:12]]]
        for p in prompts:
            eng.submit(p, max_new_tokens=6)
        out = eng.run()
        s = eng.stats()
        reg = eng.obs_registry
        # the stats dict is a SHIM over the registry counters
        assert s["decode_dispatches"] == \
            reg.get("serve.decode_dispatches").value
        assert s["prefill_dispatches"] == \
            reg.get("serve.prefill_dispatches").value
        assert s["preemptions"] == reg.get("serve.preemptions").value
        assert s["cow_dispatches"] == reg.get("serve.cow_dispatches").value
        assert s["peak_live_tokens"] == \
            reg.get("serve.peak_live_tokens").value
        assert reg.get("serve.requests_finished").value == len(prompts)
        # lifecycle histograms: one TTFT + one queue delay per request,
        # one ITL observation per non-first generated token
        snap = reg.snapshot()
        generated = sum(len(t) for t in out.values())
        assert snap["serve.ttft_ms"]["count"] == len(prompts)
        assert snap["serve.queue_delay_ms"]["count"] == len(prompts)
        assert snap["serve.itl_ms"]["count"] == generated - len(prompts)
        assert snap["serve.tokens_per_request"]["count"] == len(prompts)
        # spans cover every phase the boundary ran; pool timeline exists
        names = tracer.span_names()
        for must in ("serve/admit", "serve/prefix_match",
                     "serve/prefill_chunk", "serve/cow_plan",
                     "serve/decode_window"):
            assert names.get(must, 0) > 0, (must, names)
        pages = [v for _, kind, n, v in tracer.events
                 if kind == "counter" and n == "serve/pages_in_use"]
        assert pages and max(pages) > 0

    def test_disabled_engine_still_counts_stats(self, dec4, lm,
                                                clean_default):
        """APEX_TPU_OBS=0: spans/lifecycle off, the stats() accounting
        still works (it is bookkeeping, not telemetry)."""
        _, _, pool = lm
        obs.set_enabled_override(False)
        eng = ServeEngine(dec4, slots=2, max_len=64, paged=True,
                          page_len=8, prefill_chunk=8)
        eng.submit([int(t) for t in pool[:5]], max_new_tokens=4)
        eng.run()
        s = eng.stats()
        assert s["decode_dispatches"] > 0
        assert s["requests_done"] == 1
        snap = eng.obs_registry.snapshot()
        assert "serve.ttft_ms" not in snap  # lifecycle was off
        assert obs.default_tracer() is obs.NULL_TRACER


class TestDriverObs:
    def test_dispatch_spans_and_registry(self, clean_default):
        from apex_tpu.train import FusedTrainDriver, read_metrics

        obs.set_enabled_override(True)

        def step(carry, _):
            return carry + 1.0, {"loss": jnp.sum(carry)}

        driver = FusedTrainDriver(step, steps_per_dispatch=3,
                                  metrics={"loss": "last"})
        carry = jnp.zeros(())
        for _ in range(2):
            carry, res = driver.run_window(carry)
            read_metrics(res.metrics, registry=obs.default_registry())
        tracer = obs.default_tracer()
        assert tracer.span_names().get("train/dispatch") == 2
        reg = obs.default_registry()
        assert reg.get("train.dispatches").value == 2
        assert reg.get("train.steps").value == 6
        assert reg.get("train.dispatch_ms").count == 2
        # read_metrics fed the meter histogram (host-side plumbing)
        assert reg.get("train.loss").count == 2
        # cold window tagged compiled, warm not (bridge end to end)
        dispatch = [sp for sp in tracer.spans
                    if sp.name == "train/dispatch"]
        assert dispatch[0].compiles > 0
        assert dispatch[1].compiles == 0

    def test_checkpoint_spans(self, tmp_path, clean_default):
        from apex_tpu.train import FusedTrainDriver

        obs.set_enabled_override(True)

        def step(carry, _):
            w = carry["w"] + 1.0
            return {"w": w}, {"loss": jnp.sum(w)}

        driver = FusedTrainDriver(step, steps_per_dispatch=2)
        carry, _ = driver.run_window({"w": jnp.zeros((4,))})
        driver.save(str(tmp_path / "ck"), carry, 2)
        driver.restore(str(tmp_path / "ck"), {"w": jnp.zeros((4,))})
        names = obs.default_tracer().span_names()
        assert names.get("train/checkpoint_save") == 1
        assert names.get("train/checkpoint_restore") == 1


# ---------------------------------------------------------------------------
# the ring: an ambient tracer that never ends stays bounded
# ---------------------------------------------------------------------------

class TestRing:
    def test_spans_and_events_keep_the_newest_and_count_the_rest(
            self, tmp_path):
        from apex_tpu.obs.trace import DEFAULT_CAPACITY

        clk = FakeClock()
        tr = obs.Tracer(enabled=True, clock=clk, monitor_compiles=False)
        extra = 5
        for i in range(DEFAULT_CAPACITY + extra):
            with tr.span("serve/decode_window", i=i):
                clk.advance_ms(1)
        for i in range(DEFAULT_CAPACITY + 2):
            tr.counter("serve/pages_in_use", i)
        assert len(tr.spans) == len(tr.events) == DEFAULT_CAPACITY
        assert tr.spans[0].attrs == {"i": extra}       # the oldest kept
        assert tr.spans[-1].attrs == {"i": DEFAULT_CAPACITY + extra - 1}
        assert tr.recorded == 2 * DEFAULT_CAPACITY + extra + 2
        assert tr.dropped == extra + 2
        assert tr.span_names() == {"serve/decode_window": DEFAULT_CAPACITY}
        events, _ = obs.read_jsonl(tr.export_jsonl(str(tmp_path / "t.jsonl")))
        assert events[0]["dropped"] == extra + 2
        assert sum(e["type"] == "span" for e in events) == DEFAULT_CAPACITY
        with open(tr.export_chrome(str(tmp_path / "t.json"))) as f:
            assert json.load(f)["otherData"]["dropped"] == extra + 2
        tr.clear()
        assert tr.recorded == tr.dropped == 0 and not tr.spans

    def test_nothing_dropped_reads_zero(self):
        tr = obs.Tracer(enabled=True, clock=FakeClock(),
                        monitor_compiles=False)
        with tr.span("a"):
            tr.instant("i")
        assert (tr.recorded, tr.dropped) == (2, 0)


# ---------------------------------------------------------------------------
# the profiler bridge: spans on the profiler's clock, scopes in the step
# ---------------------------------------------------------------------------

def _apex_events(trace_dir):
    """``[(name, start_ns, end_ns, stats)]`` of the ``apex/`` events in
    the profile written under ``trace_dir``, in start order."""
    import glob

    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(str(trace_dir), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    assert len(paths) == 1, paths
    out = []
    for plane in ProfileData.from_file(paths[0]).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("apex/"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: (e[1], -e[2]))


class TestProfilerBridge:
    def test_span_is_an_event_of_the_profile_nested_with_its_attrs(
            self, tmp_path):
        tr = obs.Tracer(enabled=True, monitor_compiles=False)
        off = obs.Tracer(enabled=False)
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("train/dispatch", k=3, microbatches=1,
                         shape=(2, 2)) as sp:
                with tr.span("train/prefetch", depth=2):
                    jnp.ones(4).block_until_ready()
                sp.set("late", 1)
            with off.span("train/fetch_metrics"):
                pass
        (outer, o0, o1, ostats), (inner, i0, i1, istats) = \
            _apex_events(tmp_path)
        assert (outer, inner) == ("apex/train/dispatch",
                                  "apex/train/prefetch")
        assert o0 <= i0 and i1 <= o1            # enclosed, one clock
        # scalar attrs known at open are the event's stats; the rest
        # (a tuple, a late ``set``) stay with the tracer's own span
        assert ostats == {"k": 3, "microbatches": 1}
        assert istats == {"depth": 2}
        assert tr.spans[-1].attrs["late"] == 1 and not off.spans

    def test_no_profiler_open_no_annotation(self):
        from apex_tpu.obs.trace import _SpanCtx

        tr = obs.Tracer(enabled=True, monitor_compiles=False)
        ctx = tr.span("train/dispatch", k=1)
        assert isinstance(ctx, _SpanCtx) and ctx._annotation is None
        with ctx:
            pass
        assert tr.span_names() == {"train/dispatch": 1}

    def test_train_window_and_fetch_are_in_the_profile(self, tmp_path,
                                                       clean_default):
        from apex_tpu.train import FusedTrainDriver, read_metrics

        obs.set_enabled_override(True)
        driver = FusedTrainDriver(
            lambda c, _: (c + 1.0, {"loss": jnp.sum(c)}),
            steps_per_dispatch=3, metrics={"loss": "last"})
        carry, res = driver.run_window(jnp.zeros(()))      # warm
        with jax.profiler.trace(str(tmp_path)):
            carry, res = driver.run_window(carry)
            read_metrics(res.metrics)
        got = {name: stats for name, _, _, stats in _apex_events(tmp_path)}
        # both spans of one window carry its number into the profile
        assert got == {"apex/train/dispatch": {"k": 3, "microbatches": 1,
                                               "window": 2},
                       "apex/train/fetch_metrics": {"window": 2}}

    def test_engine_step_is_in_the_profile(self, dec4, lm, tmp_path):
        _, _, pool = lm
        tracer = obs.Tracer(enabled=True, monitor_compiles=False)
        eng = ServeEngine(dec4, slots=2, max_len=64, paged=True,
                          page_len=8, prefill_chunk=8, tracer=tracer)
        eng.submit([int(t) for t in pool[:6]], max_new_tokens=6)
        eng.step()                      # admits and prefills, warm
        with jax.profiler.trace(str(tmp_path)):
            eng.step()
        names = {name for name, *_ in _apex_events(tmp_path)}
        assert "apex/serve/decode_window" in names, names


@pytest.mark.parametrize("family", ["gpt", "bert"])
def test_train_step_carries_the_phase_scopes(family):
    """The lowered window program of a tiny model through ``AmpOptimizer``
    holds the phases' scopes in its ``op_name``s — what a device trace
    sums time by (metadata only: no op is added)."""
    import re

    import apex_tpu.amp as amp
    from apex_tpu.models.bert import BertConfig, BertForMLM
    from apex_tpu.optimizers import fused_adam
    from apex_tpu.train import FusedTrainDriver

    model = (GPTLM(GPTConfig.tiny()) if family == "gpt"
             else BertForMLM(BertConfig.tiny()))
    amp_ = amp.initialize("O2")
    opt = amp.AmpOptimizer(fused_adam(1e-3), amp_)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def step(carry, batch):
        params, state = carry

        def scaled(mp):
            _, loss = model.apply({"params": opt.model_params(mp)}, batch,
                                  labels=batch)
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        params, state, _ = opt.step(grads, state, params)
        return (params, state), {"loss": loss}

    text = FusedTrainDriver(step, steps_per_dispatch=2).lower(
        (params, opt.init(params)), jnp.zeros((2, 2, 16), jnp.int32)
    ).as_text(debug_info=True)
    op_names = set(re.findall(r'loc\("([^"]+)"', text))
    for scope in ("embed", "layer_0", "lm_head", "lm_loss", "apex_amp_step",
                  "apex_amp_cast", "apex_train_meters"):
        pat = re.compile(r"(^|[/(])%s[/)]" % scope)
        assert any(pat.search(n) for n in op_names), scope
    # forward and backward of the head are both under its scope
    assert any(n.startswith("transpose(jvp(") and "/lm_head/" in n
               for n in op_names)


# ---------------------------------------------------------------------------
# the captured run + trace report (the acceptance path)
# ---------------------------------------------------------------------------

class TestTraceReport:
    def test_render_from_synthetic_events(self):
        import tools.trace_report as trp

        events = [
            {"type": "meta", "schema": obs.SCHEMA, "compiles": 2},
            {"type": "span", "name": "train/dispatch", "ts": 0,
             "dur": 4 * MS, "depth": 0, "compiles": 2},
            {"type": "span", "name": "train/dispatch", "ts": 5 * MS,
             "dur": 1 * MS, "depth": 0, "compiles": 0},
            {"type": "counter", "name": "serve/pages_in_use",
             "ts": 1 * MS, "value": 3},
        ]
        metrics = {"serve.ttft_ms": {"type": "histogram", "count": 2,
                                     "p50": 1.0, "p99": 2.0,
                                     "mean": 1.5, "max": 2.0}}
        text = trp.render(events, metrics)
        assert "2 backend compile(s)" in text
        assert "train/dispatch" in text
        assert "TTFT" in text and "p99" in text
        assert "page-pool utilization" in text

    def test_captured_run_reports_everything(self, tmp_path,
                                             clean_default):
        """The ISSUE 6 acceptance: one captured run (train m2 + paged
        serve mixed traffic) -> JSONL + Chrome trace; the report shows
        dispatch percentiles, TTFT/ITL p50/p99, the pool timeline, and
        compile events attributable to cold spans only."""
        import tools.trace_report as trp

        out = str(tmp_path / "cap")
        paths = trp.capture(out)
        assert os.path.exists(paths["jsonl"])
        assert os.path.exists(paths["chrome"])
        assert os.path.exists(paths["metrics"])
        events, metrics = trp.load(out)
        text = trp.render(events, metrics)
        assert "train/dispatch" in text
        assert "serve/decode_window" in text
        assert "TTFT" in text and "ITL" in text
        assert "page-pool utilization" in text
        # compile accounting: cold only — every span NAME that compiled
        # ran more often than it compiled (the warm majority is clean),
        # and the metrics snapshot carries the request histograms
        spans = {}
        for e in events:
            if e.get("type") == "span":
                r = spans.setdefault(e["name"], [0, 0])
                r[0] += 1
                r[1] += e.get("compiles", 0)
        assert spans["train/dispatch"][1] >= 1  # the cold window
        for name, (count, compiles) in spans.items():
            if compiles:
                assert count > compiles, (
                    f"{name}: {compiles} compiles over {count} runs — "
                    "warm recompiles leaked into the captured run"
                )
        assert metrics["serve.ttft_ms"]["count"] >= 3
        assert metrics["serve.itl_ms"]["count"] > 0
        assert metrics["train.dispatch_ms"]["count"] == 4
        # the chrome trace parses through the pyprof bridge
        from apex_tpu.pyprof.parse import parse_chrome_trace

        times = parse_chrome_trace(paths["chrome"])
        assert times["train/dispatch"].count == 4
        assert math.isclose(
            times["train/dispatch"].duration_ns,
            sum(e["dur"] for e in events
                if e.get("type") == "span"
                and e["name"] == "train/dispatch"),
            rel_tol=1e-6,
        )
