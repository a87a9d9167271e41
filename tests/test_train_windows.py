"""The train loop's accounting outside the step (ISSUE 34): a span's CPU
clocks and ``profiled`` tag through both exporters, window numbers that pair
``train/dispatch`` with ``train/fetch_metrics`` by the RESULT that is
fetched, ``obs.train_windows()`` rows that tile the wall time, the compile
bridge's exclusive seconds, the ambient tracer's GC hook, and stalls planted
in a real ``FusedTrainDriver`` loop on the CPU.  Hardware-free; the
milliseconds a planted stall reads are the host's own, never a device's.
"""
import gc
import hashlib
import json
import time
import weakref

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import obs
from apex_tpu.obs.trace import JIT_EVENTS
from apex_tpu.train import FusedTrainDriver, read_metrics
from apex_tpu.train import driver as driver_mod

MS = 1_000_000  # ns per ms
TRACE, LOWER, COMPILE, LOAD, HITS, MISSES = JIT_EVENTS


class FakeClock:
    def __init__(self, t=0):
        self.t = t

    def __call__(self):
        return self.t

    def advance_ms(self, ms):
        self.t += int(ms * MS)


@pytest.fixture
def clean_default():
    """Isolate the ambient tracer/registry and the enabled override."""
    obs.reset_default()
    yield
    obs.set_enabled_override(None)
    obs.reset_default()


def fake_tracer():
    """A tracer whose three clocks are the test's: wall, main-thread CPU,
    process CPU."""
    tr = obs.Tracer(enabled=True, clock=FakeClock(), monitor_compiles=False)
    tr.thread_clock, tr.process_clock = FakeClock(1000), FakeClock(5000)
    return tr


def tiny_driver(k=3):
    return FusedTrainDriver(
        lambda c, _: (c + 1.0, {"loss": jnp.sum(c), "norm": jnp.sum(c) * 2}),
        steps_per_dispatch=k, metrics={"loss": "last"}, per_step=("loss",))


def window_spans(tracer=None):
    return [(sp.name.split("/")[1], (sp.attrs or {}).get("window"))
            for sp in sorted((tracer or obs.default_tracer()).spans,
                             key=lambda sp: sp.t0)
            if sp.name in ("train/dispatch", "train/fetch_metrics")]


# ---------------------------------------------------------------------------
# a span's CPU clocks and its profiled tag
# ---------------------------------------------------------------------------

class TestSpanClocks:
    def test_cpu_clocks_through_both_exporters(self, tmp_path):
        tr = fake_tracer()
        with tr.span("work", k=2):
            tr.clock.advance_ms(10)
            tr.thread_clock.advance_ms(4)       # the main thread worked 4
            tr.process_clock.advance_ms(9)      # the runtime's threads 5 more
        sp = tr.spans[0]
        assert (sp.cpu0, sp.cpu, sp.cpu_all0, sp.cpu_all, sp.profiled) == (
            1000, 4 * MS, 5000, 9 * MS, False)
        d = sp.to_dict()
        assert {k: d[k] for k in ("cpu0", "cpu", "cpu_all0", "cpu_all",
                                  "profiled")} == {
            "cpu0": 1000, "cpu": 4 * MS, "cpu_all0": 5000,
            "cpu_all": 9 * MS, "profiled": False}
        events, _ = obs.read_jsonl(tr.export_jsonl(str(tmp_path / "t.jsonl")))
        assert next(e for e in events if e["type"] == "span") == d
        doc = json.load(open(tr.export_chrome(str(tmp_path / "t.json"))))
        x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
        # the format's own thread clock (us), the process's beside it
        assert (x["tts"], x["tdur"]) == (1.0, 4000.0)
        assert x["args"] == {"k": 2, "cpu_all_us": 9000.0}

    def test_blocked_span_reads_no_cpu_and_busy_span_its_wall(self):
        tr = obs.Tracer(enabled=True, monitor_compiles=False)
        with tr.span("blocked") as blocked:
            time.sleep(0.1)
        with tr.span("busy") as busy:
            # 0.1 s of this thread's CPU, however long the other test
            # workers make that take: the wall may be longer, never shorter
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.1:
                pass
        assert blocked.dur >= 100 * MS and blocked.cpu < 20 * MS
        assert busy.dur + MS >= busy.cpu >= 99 * MS  # the clocks are read in turn
        assert busy.cpu_all + MS >= busy.cpu
        # cpu0 places the CPU of the gap between two spans
        assert busy.cpu0 >= blocked.cpu0 + blocked.cpu

    def test_profiled_says_a_profiler_session_was_open(self, tmp_path):
        tr = obs.Tracer(enabled=True, monitor_compiles=False)
        with tr.span("before"):
            pass
        with jax.profiler.trace(str(tmp_path)):
            with tr.span("during", k=1):
                pass
        with tr.span("after"):
            pass
        assert [(sp.name, sp.profiled) for sp in tr.spans] == [
            ("before", False), ("during", True), ("after", False)]
        assert tr.spans[1].to_dict()["profiled"] is True
        doc = json.load(open(tr.export_chrome(str(tmp_path / "t.json"))))
        during = next(e for e in doc["traceEvents"] if e["name"] == "during")
        assert during["args"]["profiled"] is True

    def test_null_span_has_the_new_attributes(self):
        sp = obs.Tracer(enabled=False).span("x")
        assert (sp.cpu0, sp.cpu, sp.cpu_all0, sp.cpu_all) == (0, 0, 0, 0)
        assert sp.profiled is False and sp.jit is None


# ---------------------------------------------------------------------------
# window numbers: paired by the result that is fetched
# ---------------------------------------------------------------------------

class TestWindowIds:
    def test_synchronous_loop(self, clean_default):
        obs.set_enabled_override(True)
        driver, carry = tiny_driver(), jnp.zeros(())
        for _ in range(3):
            carry, res = driver.run_window(carry)
            read_metrics(res.metrics)
        assert window_spans() == [
            ("dispatch", 1), ("fetch_metrics", 1), ("dispatch", 2),
            ("fetch_metrics", 2), ("dispatch", 3), ("fetch_metrics", 3)]
        assert driver.windows_dispatched == 3

    def test_dispatching_ahead_of_the_fetch(self, clean_default):
        obs.set_enabled_override(True)
        driver, carry = tiny_driver(), jnp.zeros(())
        carry, previous = driver.run_window(carry)
        for _ in range(3):
            carry, res = driver.run_window(carry)   # n+1 goes out
            read_metrics(previous.metrics)          # before n is fetched
            previous = res
        read_metrics(previous.metrics)
        assert window_spans() == [
            ("dispatch", 1), ("dispatch", 2), ("fetch_metrics", 1),
            ("dispatch", 3), ("fetch_metrics", 2), ("dispatch", 4),
            ("fetch_metrics", 3), ("fetch_metrics", 4)]
        rows = obs.train_windows()
        assert [r["window"] for r in rows] == [1, 2, 3, 4]
        assert all(r["wait_ms"] is not None for r in rows)
        # rows overlap in such a loop: no window waited for data
        assert all(r["between_ms"] is None for r in rows)

    def test_first_window_fetched_another_way(self, clean_default):
        """The benchmark's set-up: window 1 is read with ``device_get`` and
        never through ``read_metrics``; a queue of ids popped in order
        would put 1 on window 2's fetch, and so on for the whole run."""
        obs.set_enabled_override(True)
        driver, carry = tiny_driver(), jnp.zeros(())
        carry, res = driver.run_window(carry)
        jax.device_get(res.per_step)
        for _ in range(3):
            carry, res = driver.run_window(carry)
            read_metrics(res.metrics)
        assert window_spans() == [
            ("dispatch", 1), ("dispatch", 2), ("fetch_metrics", 2),
            ("dispatch", 3), ("fetch_metrics", 3), ("dispatch", 4),
            ("fetch_metrics", 4)]
        rows = obs.train_windows()
        assert rows[0]["wait_ms"] is None and rows[0]["compiles"] > 0
        assert rows[1]["between_ms"] is None        # no fetch span before it
        assert all(r["between_ms"] is not None for r in rows[2:])

    def test_any_part_of_the_result_names_its_window(self, clean_default):
        obs.set_enabled_override(True)
        driver, carry = tiny_driver(), jnp.zeros(())
        carry, res = driver.run_window(carry)
        read_metrics(res)                           # the whole WindowResult
        carry, res = driver.run_window(carry)
        read_metrics(res.per_step)                  # its traces alone
        assert [w for name, w in window_spans()
                if name == "fetch_metrics"] == [1, 2]

    def test_a_tree_no_window_made_carries_no_window(self, clean_default):
        obs.set_enabled_override(True)
        driver, carry = tiny_driver(), jnp.zeros(())
        carry, res = driver.run_window(carry)
        read_metrics({"x": jnp.ones(())})           # nobody's result
        read_metrics(res.metrics)
        read_metrics(res.metrics)                   # asked for once already
        fetches = [sp.attrs for sp in obs.default_tracer().spans
                   if sp.name == "train/fetch_metrics"]
        assert fetches == [None, {"window": 1}, None]
        assert len(obs.train_windows()) == 1

    def test_the_map_of_results_is_bounded_and_off_with_obs(self,
                                                            clean_default):
        obs.set_enabled_override(True)
        driver_mod._WINDOW_OF.clear()
        driver, carry = tiny_driver(k=1), jnp.zeros(())
        held = []
        for _ in range(driver_mod._REMEMBERED_WINDOWS + 8):
            carry, res = driver.run_window(carry)   # never fetched
            held.append(res)
        assert len(driver_mod._WINDOW_OF) == driver_mod._REMEMBERED_WINDOWS
        # remembered weakly: the map keeps no result (no device buffer) alive
        loss = weakref.ref(held[-1].metrics["loss"])
        assert read_metrics(held[-1].metrics)["loss"] >= 0
        del held, res
        assert loss() is None
        driver_mod._WINDOW_OF.clear()
        obs.set_enabled_override(False)
        carry, res = driver.run_window(carry)
        assert read_metrics(res.metrics)["loss"] >= 0
        assert not driver_mod._WINDOW_OF


# ---------------------------------------------------------------------------
# the reducer
# ---------------------------------------------------------------------------

def scripted_loop(tr, windows, slow_fetch=None, fetch_first=True):
    """A loop on the tracer's fake clocks: per window 2 ms between, a 1 ms
    dispatch, 3 ms in flight with the host free, a 10 ms blocked fetch
    (``slow_fetch``: that window's takes 500).  The main thread works
    through ``between`` and the dispatch and sleeps through the fetch."""
    def tick(ms, cpu=0.0, cpu_all=None):
        tr.clock.advance_ms(ms)
        tr.thread_clock.advance_ms(cpu)
        tr.process_clock.advance_ms(cpu if cpu_all is None else cpu_all)

    for n in range(1, windows + 1):
        tick(2, cpu=2)
        with tr.span("train/dispatch", k=4, window=n):
            tick(1, cpu=1)
        tick(3, cpu=0.5)
        if n == 1 and not fetch_first:
            tick(10)
            continue
        with tr.span("train/fetch_metrics", window=n):
            tick(500 if n == slow_fetch else 10, cpu=0, cpu_all=1)


class TestTrainWindows:
    def test_rows_tile_the_wall_time_exactly(self):
        tr = fake_tracer()
        scripted_loop(tr, 5)
        rows = obs.train_windows(tr)
        assert [r["window"] for r in rows] == [1, 2, 3, 4, 5]
        assert rows[0]["between_ms"] is None and rows[0]["t0"] == 2 * MS
        for a, b in zip(rows, rows[1:]):
            assert a["t0"] + round(a["wall_ms"] * MS) == b["t0"]
        last = rows[-1]
        assert last["t0"] + round(last["wall_ms"] * MS) == tr.clock.t
        for r in rows[1:]:
            assert (r["between_ms"], r["enqueue_ms"], r["inflight_host_ms"],
                    r["wait_ms"], r["wall_ms"], r["k"]) == (
                2.0, 1.0, 3.0, 10.0, 16.0, 4)
            # the host worked between and in the dispatch, slept in the
            # fetch while a runtime thread worked 1 ms
            assert (r["between_cpu_ms"], r["enqueue_cpu_ms"],
                    r["inflight_host_cpu_ms"], r["wait_cpu_ms"]) == (
                2.0, 1.0, 0.5, 0.0)
            assert r["wait_cpu_all_ms"] == 1.0
            assert r["cpu_ms"] == 3.5 and r["cpu_all_ms"] == 4.5
            assert not r["profiled"] and r["gc_ms"] == 0 and not r["jit"]

    def test_a_slow_fetch_lands_in_wait_ms(self):
        tr = fake_tracer()
        scripted_loop(tr, 6, slow_fetch=4)
        rows = obs.train_windows(tr)
        slow = max(rows, key=lambda r: r["wall_ms"])
        assert slow["window"] == 4 and slow["wait_ms"] == 500.0
        assert slow["wait_cpu_ms"] == 0.0           # blocked, not working
        assert slow["between_ms"] == 2.0 and slow["enqueue_ms"] == 1.0
        assert [r["wait_ms"] for r in rows if r is not slow] == [10.0] * 5

    def test_an_unfetched_first_window_leaves_one_hole_and_no_more(self):
        tr = fake_tracer()
        scripted_loop(tr, 4, fetch_first=False)
        rows = obs.train_windows(tr)
        assert rows[0]["wait_ms"] is None and rows[0]["wall_ms"] == 1.0
        assert rows[1]["between_ms"] is None and rows[1]["wall_ms"] == 14.0
        assert rows[2]["between_ms"] == 2.0 and rows[3]["wall_ms"] == 16.0

    def test_gc_pauses_are_charged_to_the_window_they_began_in(self):
        tr = fake_tracer()
        scripted_loop(tr, 3)
        rows = obs.train_windows(tr)
        tr.gc_pauses.append((rows[1]["t0"] + MS, 7 * MS, 2))
        tr.gc_pauses.append((rows[2]["t0"], MS // 2, 0))
        tr.gc_pauses.append((rows[2]["t0"] - 1, MS // 4, 0))  # still row 1's
        assert [r["gc_ms"] for r in obs.train_windows(tr)] == [0, 7.25, 0.5]

    def test_exported_rows_reduce_as_the_live_tracer_does(self, tmp_path):
        tr = fake_tracer()
        scripted_loop(tr, 4, slow_fetch=3)
        tr.gc_pauses.append((20 * MS, 2 * MS, 1))
        with tr.span("serve/step"):                 # not a window's span
            pass
        events, _ = obs.read_jsonl(tr.export_jsonl(str(tmp_path / "t.jsonl")))
        assert [e for e in events if e["type"] == "gc"] == [
            {"type": "gc", "ts": 20 * MS, "dur": 2 * MS, "generation": 1}]
        assert obs.train_windows(rows=events) == obs.train_windows(tr)
        doc = json.load(open(tr.export_chrome(str(tmp_path / "t.json"))))
        pause = next(e for e in doc["traceEvents"] if e.get("cat") == "gc")
        assert (pause["ts"], pause["dur"], pause["tid"]) == (20e3, 2e3, 1)

    def test_spans_without_a_window_make_no_row(self):
        tr = fake_tracer()
        with tr.span("train/dispatch", k=4):        # an older program's
            pass
        with tr.span("train/fetch_metrics"):
            pass
        assert obs.train_windows(tr) == []
        assert obs.train_windows(rows=[]) == []


# ---------------------------------------------------------------------------
# the compile bridge: JAX's own events, seconds made exclusive
# ---------------------------------------------------------------------------

class TestCompileBridge:
    def test_nested_intervals_are_charged_once(self):
        """JAX fires a duration as its interval ENDS: an inner jit's trace
        arrives before the outer's, helper traces before the lowering that
        made them, the cache's retrieval before the backend event around
        it.  Each second lands once, under the innermost event."""
        tr = obs.Tracer(enabled=True, clock=FakeClock(), monitor_compiles=False)
        with tr.span("train/dispatch") as sp:
            tr.clock.advance_ms(3)
            tr._on_jit_event(TRACE, 0.002)          # inner jit: [1, 3)
            tr.clock.advance_ms(2)
            tr._on_jit_event(TRACE, 0.001)          # a sibling: [4, 5)
            tr.clock.advance_ms(1)
            tr._on_jit_event(TRACE, 0.006)          # the outer: [0, 6)
            tr.clock.advance_ms(3)
            tr._on_jit_event(TRACE, 0.001)          # a helper: [8, 9)
            tr.clock.advance_ms(1)
            tr._on_jit_event(LOWER, 0.004)          # lowering: [6, 10)
            tr.clock.advance_ms(5)
            tr._on_jit_event(HITS, 1.0)
            tr._on_jit_event(LOAD, 0.004)           # retrieval: [11, 15)
            tr._on_jit_event(COMPILE, 0.005)        # around it: [10, 15)
            tr._on_jit_event("/jax/some/other_event", 9.0)
        assert sp.jit == pytest.approx({
            "trace_s": 0.007, "lower_s": 0.003, "cache_load_s": 0.004,
            "compile_s": 0.001, "cache_hits": 1.0})
        assert sum(v for k, v in sp.jit.items() if k.endswith("_s")) == (
            pytest.approx(sp.dur * 1e-9))

    def test_an_outer_trace_is_not_charged_ten_thousand_children(self):
        """GPT-2 small's window program fires ~9,650 inner traces before
        the one outer trace that wraps them ends (4,617 of them direct
        children): all have to be remembered until then."""
        tr = obs.Tracer(enabled=True, clock=FakeClock(), monitor_compiles=False)
        children = 10_000
        with tr.span("train/dispatch") as sp:
            for _ in range(children):
                tr.clock.advance_ms(1)
                tr._on_jit_event(TRACE, 0.001)
            tr.clock.advance_ms(50)
            tr._on_jit_event(TRACE, (children + 50) * 1e-3)
        assert sp.jit["trace_s"] == pytest.approx(sp.dur * 1e-9)
        assert len(tr._jit_roots) == 1
        assert tr.recorded == 1 and not tr.events   # seconds are no events

    def test_cold_dispatch_carries_its_seconds_and_warm_none(self,
                                                             clean_default):
        obs.set_enabled_override(True)
        driver, carry = tiny_driver(), jnp.zeros(())
        reg = obs.default_registry()
        for _ in range(2):
            carry, res = driver.run_window(carry)
            read_metrics(res.metrics)
        cold, warm = obs.train_windows()
        assert cold["compiles"] >= 1 and warm["compiles"] == 0 == len(
            warm["jit"])
        assert set(cold["jit"]) >= {"trace_s", "lower_s", "compile_s"}
        assert all(v > 0 for v in cold["jit"].values())
        # exclusive seconds fit in the span they fired under
        assert sum(v for k, v in cold["jit"].items()
                   if k.endswith("_s")) <= cold["enqueue_ms"] * 1e-3
        # the ambient tracer feeds the process-wide counters too
        for key in ("trace_s", "lower_s", "compile_s"):
            assert reg.get("jit." + key).value >= cold["jit"][key] > 0

    def test_persistent_cache_hits_and_misses_are_told_apart(
            self, tmp_path, clean_default):
        """``backend_compile_duration`` wraps ``compile_or_get_cached``: it
        fires on a persistent-cache HIT too (``span.compiles`` counts
        programs the jit cache missed, compiled or loaded).  The cache's
        own events say which: a miss when XLA compiled and wrote the
        entry, a hit with its retrieval seconds when it was loaded."""
        from jax.experimental.compilation_cache import compilation_cache as cc

        obs.set_enabled_override(True)
        tr = obs.default_tracer()
        saved = {k: getattr(jax.config, k) for k in (
            "jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")}
        try:
            jax.config.update("jax_enable_compilation_cache", True)
            jax.config.update("jax_compilation_cache_dir", str(tmp_path))
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            cc.reset_cache()
            x = jnp.arange(37.0)
            with tr.span("first") as first:
                jax.jit(lambda v: jnp.cos(v) * 3 + 1)(x)
            with tr.span("again") as again:         # a new jit: same program
                jax.jit(lambda v: jnp.cos(v) * 3 + 1)(x)
        finally:
            for k, v in saved.items():
                jax.config.update(k, v)
            cc.reset_cache()
        assert first.compiles == again.compiles == 1
        assert first.jit["cache_misses"] == 1 and "cache_hits" not in first.jit
        assert again.jit["cache_hits"] == 1 and "cache_misses" not in again.jit
        assert again.jit["cache_load_s"] > 0
        reg = obs.default_registry()
        assert reg.get("jit.cache_misses").value >= 1
        assert reg.get("jit.cache_hits").value >= 1

    def test_only_the_ambient_tracer_feeds_the_registry(self, clean_default):
        obs.set_enabled_override(True)
        reg = obs.default_registry()
        own = obs.Tracer(enabled=True, clock=FakeClock(),
                         monitor_compiles=False)
        own._on_jit_event(TRACE, 1.0)
        assert reg.get("jit.trace_s") is None
        obs.default_tracer()._on_jit_event(TRACE, 0.0)
        assert reg.get("jit.trace_s").value == 0.0

    def test_monitor_listeners_do_not_outlive_it(self):
        from jax._src import monitoring

        from apex_tpu.analysis import CompileMonitor

        before = (len(monitoring.get_event_duration_listeners()),
                  len(monitoring.get_event_listeners()))
        seen = []
        with CompileMonitor(on_event=lambda name, v: seen.append(name)):
            jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
            jax.monitoring.record_event_duration_secs(TRACE, 0.5)
        jax.monitoring.record_event("/jax/compilation_cache/cache_hits")
        assert seen == ["/jax/compilation_cache/cache_hits", TRACE]
        assert (len(monitoring.get_event_duration_listeners()),
                len(monitoring.get_event_listeners())) == before


# ---------------------------------------------------------------------------
# the garbage collector's pauses: a ring of their own
# ---------------------------------------------------------------------------

def gc_hooks():
    return [cb for cb in gc.callbacks
            if getattr(cb, "__name__", "") == "_on_gc"]


class TestGcHook:
    def test_pauses_leave_spans_events_and_recorded_untouched(
            self, clean_default):
        obs.set_enabled_override(True)
        tr = obs.default_tracer()
        assert gc_hooks() == [tr._on_gc]
        with tr.span("a"):
            pass
        tr.instant("i")
        counted = (tr.recorded, len(tr.spans), len(tr.events), tr.dropped)
        tr.gc_pauses.clear()
        gc.collect()
        gc.collect(0)
        assert (tr.recorded, len(tr.spans), len(tr.events),
                tr.dropped) == counted
        assert [g for _, _, g in tr.gc_pauses][:2] == [2, 0]
        assert all(dur > 0 for _, dur, _ in tr.gc_pauses)
        hist = obs.default_registry().get("host.gc_ms")
        assert hist.count >= 2 and hist.max >= max(
            dur for _, dur, _ in tr.gc_pauses) * 1e-6

    def test_hook_goes_with_its_tracer(self, clean_default):
        obs.set_enabled_override(True)
        tr = obs.default_tracer()
        assert gc_hooks() == [tr._on_gc]
        tr.close()
        tr.close()                                  # idempotent
        assert gc_hooks() == []
        obs.reset_default()
        again = obs.default_tracer()
        assert again is not tr and gc_hooks() == [again._on_gc]
        obs.reset_default()
        assert gc_hooks() == []

    def test_a_tracer_of_ones_own_installs_nothing(self):
        before = gc_hooks()
        tr = obs.Tracer(enabled=True, monitor_compiles=False)
        gc.collect()
        assert gc_hooks() == before and not tr.gc_pauses
        tr.close()

    def test_obs_off_records_nothing_and_installs_no_callback(
            self, clean_default):
        obs.set_enabled_override(False)
        driver, carry = tiny_driver(), jnp.zeros(())
        for _ in range(2):
            carry, res = driver.run_window(carry)
            read_metrics(res.metrics)
        gc.collect()
        assert obs.default_tracer() is obs.NULL_TRACER
        assert gc_hooks() == []
        assert not obs.NULL_TRACER.spans and not obs.NULL_TRACER.gc_pauses
        assert obs.train_windows() == []
        reg = obs.default_registry()
        assert reg.get("host.gc_ms") is None
        assert not [n for n in reg.names() if n.startswith(("jit.", "train."))]

    def test_override_flipped_off_silences_a_live_hook(self, clean_default):
        obs.set_enabled_override(True)
        tr = obs.default_tracer()
        obs.set_enabled_override(False)
        tr.gc_pauses.clear()
        gc.collect()
        tr._on_jit_event(TRACE, 1.0)
        assert not tr.gc_pauses
        assert obs.default_registry().get("jit.trace_s") is None


# ---------------------------------------------------------------------------
# stalls planted in a real driver's loop
# ---------------------------------------------------------------------------

STALL_S = 0.2


def loop_with(stall, windows=5, at=3):
    """The runner's loop on a real driver on the CPU: set-up's window read
    by ``device_get``, then ``windows`` measured ones through
    ``read_metrics``, ``stall()`` called before window ``at`` of them is
    dispatched.  Returns the measured windows' rows."""
    driver, carry = tiny_driver(), jnp.zeros(())
    carry, res = driver.run_window(carry)
    jax.device_get(res.per_step)
    for n in range(1, windows + 1):
        if n == at:
            stall()
        carry, res = driver.run_window(carry)
        read_metrics(res.metrics)
    rows = obs.train_windows()
    assert len(rows) == windows + 1
    return rows[1:]


def busy_wait():
    """``STALL_S`` of the main thread's CPU, however long that takes on a
    machine the other test workers share."""
    t0 = time.thread_time()
    while time.thread_time() - t0 < STALL_S:
        pass


class TestPlantedStalls:
    def test_sleep_between_windows_is_between_ms_with_the_cpu_idle(
            self, clean_default):
        obs.set_enabled_override(True)
        rows = loop_with(lambda: time.sleep(STALL_S))
        late = max(rows, key=lambda r: r["wall_ms"])
        assert late["window"] == 4                  # set-up's was window 1
        assert late["between_ms"] >= STALL_S * 1e3
        assert late["between_cpu_ms"] < 0.25 * late["between_ms"]
        assert late["wall_ms"] - late["between_ms"] < late["between_ms"]
        assert late["gc_ms"] < 50

    def test_busy_loop_between_windows_is_between_ms_with_the_cpu_busy(
            self, clean_default):
        obs.set_enabled_override(True)
        rows = loop_with(busy_wait)
        late = max(rows, key=lambda r: r["wall_ms"])
        assert late["window"] == 4
        assert late["between_cpu_ms"] >= 0.99 * STALL_S * 1e3
        assert late["between_ms"] >= 0.99 * late["between_cpu_ms"]
        assert late["cpu_ms"] >= late["between_cpu_ms"]
        others = [r["between_cpu_ms"] for r in rows[1:] if r is not late]
        assert max(others) < 0.5 * late["between_cpu_ms"]

    def test_forced_collection_is_gc_ms(self, clean_default):
        obs.set_enabled_override(True)
        junk = [[i] for i in range(200_000)]        # something to walk
        rows = loop_with(gc.collect)
        del junk
        late = max(rows, key=lambda r: r["gc_ms"])
        assert late["window"] == 4 and late["gc_ms"] > 0
        assert late["gc_ms"] <= late["between_ms"]
        assert sum(r["gc_ms"] for r in rows if r is not late) < late["gc_ms"]

    def test_rows_of_a_real_loop_tile_its_wall_time(self, clean_default):
        obs.set_enabled_override(True)
        t0 = time.perf_counter_ns()
        rows = loop_with(lambda: None, windows=6)
        elapsed_ms = (time.perf_counter_ns() - t0) * 1e-6
        for a, b in zip(rows[1:], rows[2:]):
            assert a["t0"] + a["wall_ms"] * MS == pytest.approx(b["t0"],
                                                                abs=2)
        assert sum(r["wall_ms"] for r in rows) <= elapsed_ms


# ---------------------------------------------------------------------------
# host-side only: the window program does not know obs exists
# ---------------------------------------------------------------------------

def lowered_sha(enabled, sparse):
    obs.set_enabled_override(enabled)
    obs.reset_default()
    if sparse:
        from apex_tpu.models.afmoe import AfmoeConfig, AfmoeLM

        cfg = AfmoeConfig.tiny()
        model = AfmoeLM(cfg)
    else:
        from apex_tpu.models.gpt import GPTConfig, GPTLM

        cfg = GPTConfig.tiny()
        model = GPTLM(cfg)
    import apex_tpu.amp as amp
    from apex_tpu.optimizers import fused_adam

    amp_ = amp.initialize("O2")
    opt = amp.AmpOptimizer(fused_adam(1e-3), amp_)
    ids = jnp.zeros((2, 16), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]

    def step(carry, batch):
        params, state = carry

        def scaled(mp):
            out = model.apply({"params": opt.model_params(mp)}, batch,
                              labels=batch)
            loss = out[1] if isinstance(out, tuple) else out
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        params, state, _ = opt.step(grads, state, params)
        return (params, state), {"loss": loss}

    driver = FusedTrainDriver(step, steps_per_dispatch=2,
                              metrics={"loss": "mean"})
    text = driver.lower((params, opt.init(params)),
                        jnp.zeros((2, 2, 16), jnp.int32)).as_text()
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_window_program_is_the_same_text_with_obs_on_and_off(clean_default,
                                                             sparse):
    assert lowered_sha(True, sparse) == lowered_sha(False, sparse)
