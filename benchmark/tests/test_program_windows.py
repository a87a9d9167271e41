"""The eleven readers of the program's own window accounting
(``program_windows`` over ``apex_tpu.obs.train_windows``): on a small
recorded run (``data/windows_recorded.jsonl``: a tiny driver on the CPU in
the runner's own loop — set-up's window fetched by ``device_get``, eight
measured windows through ``read_metrics``, a profiler open over the second
and third) with a late window planted in each part in turn, and once
through the harness end to end at a tiny size.  Milliseconds of a CPU run
stand for nothing but themselves."""
import json
import os
import statistics

import pytest

from benchmark import harness, program_windows
from benchmark.tests import tiny

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
RECORDED = os.path.join(HERE, "data", "windows_recorded.jsonl")
MEASURED = 8                     # windows 2..9 of the recording
PROFILED = (3, 4)                # the profiler was open over these
MS = 1_000_000

with open(os.path.join(ROOT, "BENCHMARK.json")) as _f:
    BENCH = json.load(_f)
NEW = [m for m in BENCH["per_layer"]
       if m["name"].startswith(("train.window_mean", "train.slowest_",
                                "train.between_", "train.host_cpu",
                                "train.gc_ms", "setup."))]


def read(name, run):
    return harness.load_module(ROOT, "layer_metrics", name).read(run)


def events():
    with open(RECORDED) as f:
        return [json.loads(line) for line in f]


def record(tmp_path, rows, **over):
    """A traced train run's record whose tracer export is ``rows``."""
    path = tmp_path / "trace.jsonl"
    path.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return {"kind": "train", "trace": {"busy_s": 1.0},
            "window_ms": [1.0] * MEASURED, "obs_jsonl": str(path), **over}


def span(rows, name, window):
    return next(r for r in rows if r.get("name") == name
                and r.get("attrs", {}).get("window") == window)


def delay(rows, at, ns):
    """Everything that begins at or after ``at`` happens ``ns`` later."""
    for r in rows:
        if r.get("ts", -1) >= at:
            r["ts"] += ns


def plant(rows, part, window=7, ns=500 * MS):
    """Window ``window`` comes ``ns`` late, the time spent in ``part``."""
    dispatch = span(rows, "train/dispatch", window)
    fetch = span(rows, "train/fetch_metrics", window)
    if part == "between":
        delay(rows, dispatch["ts"], ns)
    elif part == "enqueue":
        delay(rows, dispatch["ts"] + 1, ns)
        dispatch["dur"] += ns
    elif part == "inflight_host":
        delay(rows, fetch["ts"], ns)
    else:
        delay(rows, fetch["ts"] + 1, ns)
        fetch["dur"] += ns


def test_every_new_entry_has_a_reader_and_names_what_it_moves():
    assert len(NEW) == 11
    end_to_end = {m["name"] for m in BENCH["end_to_end"]}
    cells = [c["name"] for c in BENCH["workloads"]]
    for entry in NEW:
        module = harness.load_module(ROOT, "layer_metrics", entry["name"])
        assert callable(module.read) and module.__doc__.startswith("Layer: ")
        assert module.__doc__.startswith(f"Layer: {entry['layer']}.")
        assert entry["unit"] and entry["better"] == "lower"
        assert entry["source"] == "program_counter"
        assert entry["moves"] in end_to_end
        assert entry["workloads"] == cells and len(cells) == 5
    assert {e["moves"] for e in NEW} == {"train_tokens_per_s", "setup_s"}


def test_the_recording_as_it_is(tmp_path):
    run = record(tmp_path, events())
    rows = program_windows.measured(run)
    # windows 2..9 less the two profiled ones; the gap after them dropped
    assert [r["window"] for r in rows] == [2, 5, 6, 7, 8, 9]
    assert rows[0]["between_ms"] is None        # set-up fetched window 1
    assert rows[1]["between_ms"] is None        # the profiler's stop sat there
    assert all(r["between_ms"] is not None for r in rows[2:])
    for r in rows:
        parts = [r[p] for p in ("between_ms", "enqueue_ms",
                                "inflight_host_ms", "wait_ms")]
        assert r["wall_ms"] == pytest.approx(sum(p or 0.0 for p in parts))
    wall = [r["wall_ms"] for r in rows]
    assert read("train.window_mean_over_median", run) == pytest.approx(
        statistics.fmean(wall) / statistics.median(wall))
    assert read("train.slowest_window_ms", run) == max(wall)
    assert read("train.between_windows_ms", run) == pytest.approx(
        statistics.median(r["between_ms"] for r in rows[2:]))
    assert 0 < read("train.host_cpu_share", run) <= 100
    assert read("train.gc_ms_per_window", run) == 0.0
    first = program_windows.first(run)
    assert first["window"] == 1 and first["wait_ms"] is None
    assert read("setup.first_dispatch_s", run) == first["enqueue_ms"] * 1e-3
    jit = first["jit"]
    assert read("setup.first_dispatch_trace_s", run) == pytest.approx(
        jit["trace_s"] + jit["lower_s"])
    # exclusive seconds: what the bridge counted under the span fits in it
    assert sum(v for k, v in jit.items() if k.endswith("_s")) <= (
        first["enqueue_ms"] * 1e-3)
    # the recording ran from a warm persistent cache
    assert read("setup.cache_misses", run) == 0
    assert read("setup.cache_load_s", run) > 0


@pytest.mark.parametrize("part", ["between", "enqueue", "inflight_host",
                                  "wait"])
def test_a_late_window_is_found_in_the_part_that_held_it(tmp_path, part):
    rows = events()
    plant(rows, part)
    run = record(tmp_path, rows)
    slow = program_windows.slowest(run)
    assert slow["window"] == 7 and slow[part + "_ms"] > 500
    assert read("train.slowest_window_ms", run) == slow["wall_ms"] > 500
    wait = read("train.slowest_window_wait_ms", run)
    host = read("train.slowest_window_host_ms", run)
    assert wait + host == pytest.approx(slow["wall_ms"])
    if part == "wait":
        assert wait > 500 > host
    else:
        assert host > 500 > wait
    # one window of six 500 ms late: the mean leaves the median behind
    assert read("train.window_mean_over_median", run) > 5
    # the median gap does not move for one late window
    assert read("train.between_windows_ms", run) < 50


def test_gc_pauses_and_cpu_land_in_their_window(tmp_path):
    rows = events()
    dispatch = span(rows, "train/dispatch", 8)
    plant(rows, "between", window=8, ns=40 * MS)
    rows.insert(-1, {"type": "gc", "ts": dispatch["ts"] - 30 * MS,
                     "dur": 12 * MS, "generation": 2})
    for r in rows:                  # the main thread worked through the gap
        if r.get("type") == "span" and r["ts"] >= dispatch["ts"]:
            r["cpu0"] += 40 * MS
    run = record(tmp_path, rows)
    slow = program_windows.slowest(run)
    assert slow["window"] == 8 and slow["gc_ms"] == pytest.approx(12.0)
    assert slow["between_cpu_ms"] == pytest.approx(slow["between_ms"], rel=0.1)
    assert read("train.gc_ms_per_window", run) == pytest.approx(12.0 / 6)


def test_profiled_windows_and_the_gaps_beside_them_are_left_out(tmp_path):
    rows = events()
    for window in PROFILED:         # the profiler makes its windows late
        plant(rows, "wait", window=window, ns=300 * MS)
    plant(rows, "between", window=5, ns=2000 * MS)      # its stop
    run = record(tmp_path, rows)
    assert read("train.slowest_window_ms", run) < 100
    assert read("train.window_mean_over_median", run) < 2


def test_compiles_in_a_measured_window_are_not_set_up(tmp_path):
    rows = events()
    span(rows, "train/dispatch", 6)["jit"] = {"cache_misses": 1,
                                              "cache_load_s": 0.25}
    metrics = next(r for r in rows if r.get("type") == "metrics")["metrics"]
    before = metrics["jit.cache_load_s"]["value"]
    metrics["jit.cache_load_s"]["value"] += 0.25
    metrics["jit.cache_misses"] = {"type": "counter", "value": 1}
    run = record(tmp_path, rows)
    assert read("setup.cache_misses", run) == 0
    assert read("setup.cache_load_s", run) == pytest.approx(before)


@pytest.mark.parametrize("why", ["untraced", "not a train run", "no window",
                                 "no profiled window", "too few windows"])
def test_nothing_to_read_reads_none(tmp_path, why):
    rows = events()
    over = {}
    if why == "untraced":
        over = {"trace": None}
    elif why == "not a train run":
        over = {"kind": "serve"}
    elif why == "no window":        # an older program's spans
        for r in rows:
            r.get("attrs", {}).pop("window", None)
    elif why == "no profiled window":
        for r in rows:
            if r.get("type") == "span":
                r["profiled"] = False
    else:
        over = {"window_ms": [1.0] * 40}
    run = record(tmp_path, rows, **over)
    for entry in NEW:
        assert read(entry["name"], run) is None, entry["name"]


def test_a_program_without_the_reducer_reads_none(tmp_path, monkeypatch):
    from apex_tpu import obs

    monkeypatch.delattr(obs, "train_windows")
    run = record(tmp_path, events())
    for entry in NEW:
        assert read(entry["name"], run) is None, entry["name"]


def test_traced_run_of_the_harness_reports_the_eleven(tmp_path, capsys,
                                                      monkeypatch):
    """Through ``harness.main`` at a tiny size with a real profiler session
    over the second and third measured windows (the reduction of its device
    planes stood in: the CPU has none): the live tracer and registry."""
    root = tiny.make_root(str(tmp_path))
    monkeypatch.setattr(harness.Tracer, "reduced", lambda self, chips: {
        "busy_s": 0.9, "window_s": 1.0, "op_time_s": 0.9, "mosaic_s": 0.3,
        "device_ops": [["f", .9]], "idle_gaps": [["driver.run_window", 0.1]]})
    as_v5e = lambda chips: dict(tiny.fake_device(chips), kind="TPU v5 lite")
    rc, line, lines = tiny.run_cell(root, "gpt2-tiny.train", capsys, trace=1,
                                    seconds=2.0, device_check=as_v5e)
    assert rc == 0 and line["correct"] is True, "\n".join(lines)
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert {e["name"] for e in NEW} <= set(got)
    assert 0.5 < got["train.window_mean_over_median"] < 3
    assert got["train.slowest_window_ms"] == pytest.approx(
        got["train.slowest_window_wait_ms"]
        + got["train.slowest_window_host_ms"])
    assert got["train.between_windows_ms"] > 0
    assert 0 < got["train.host_cpu_share"] <= 100
    assert got["setup.first_dispatch_s"] > got["setup.first_dispatch_trace_s"] > 0
    assert got["setup.cache_load_s"] >= 0 and got["setup.cache_misses"] >= 0
    units = {m["name"]: m["unit"] for m in NEW}
    assert all(line["metrics"][n]["unit"] == u for n, u in units.items())
