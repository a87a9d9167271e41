"""One run of one cell: what every runner shares.

``run.py`` parses the command line and calls :func:`main`.  The harness is
driven by data: the cell, its configuration and its traffic are looked up by
name in ``BENCHMARK.json`` and under ``benchmark/``; the family, the runner
and every per-layer metric are modules found by name (see ``README.md``).
Nothing here knows a cell, a model or a metric by name.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))


# -- finding things by name ---------------------------------------------------

def load_json(path: str) -> Dict:
    with open(path) as f:
        return json.load(f)


def find(root: str, sub: str, name: str, ext: str) -> str:
    """``<root>/benchmark/<sub>/<name><ext>``, else the same under this
    harness's own directory (a checkout's root and the harness agree; a
    test's temporary root holds only what it adds)."""
    for base in (os.path.join(root, "benchmark"), HERE):
        path = os.path.join(base, sub, name + ext)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(
        f"no {sub}/{name}{ext} under {os.path.join(root, 'benchmark')} or {HERE}")


def load_module(root: str, sub: str, name: str):
    """The module ``<sub>/<name>.py`` — file names may hold dots, so this
    loads by path, not by import name."""
    path = find(root, sub, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{sub}_{name.replace('.', '_').replace('-', '_')}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def load_cell(root: str, workload: str) -> Dict:
    """The cell ``workload`` with its configuration, traffic and metric
    entries, all from ``BENCHMARK.json`` and the files it names."""
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {c["name"]: c for c in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json "
                         f"(known: {sorted(cells)})")
    cell = cells[workload]
    config = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    applies = lambda m: "workloads" not in m or workload in m["workloads"]
    return {
        "cell": cell,
        "cfg": load_json(os.path.join(root, config["file"])),
        "traffic": load_json(find(root, "traffic", cell["traffic"], ".json")),
        "end_to_end": [m for m in bench["end_to_end"] if applies(m)],
        "per_layer": [m for m in bench["per_layer"] if applies(m)],
    }


# -- the device ---------------------------------------------------------------

def tpu_or_exit(chips: int) -> Dict:
    """The device as JAX reports it; exits nonzero (no result line) unless
    it is a TPU with at least ``chips`` chips."""
    from apex_tpu.chip import require_tpu

    device = require_tpu()
    if device["count"] < chips:
        raise SystemExit(f"the cell needs {chips} chip(s); JAX reports "
                         f"{device['count']}")
    return device


def memory_bytes(devices) -> int:
    """Device memory held right now on the fullest of ``devices``: live
    arrays (``bytes_in_use``) plus what the runtime has reserved for the
    loaded programs' temporaries (``bytes_reserved`` — on the TPU a
    program's temporaries are not in ``bytes_in_use``: GPT-2 small's train
    window holds 1.6 GB of arrays and reserves 10.4 GB, PERF.md §4).
    Sampled by the runners while the program's state is live and after the
    reference's is freed, so the figure is the program's.  0 where the
    backend reports nothing."""
    held = [s.get("bytes_in_use", 0) + s.get("bytes_reserved", 0)
            for s in ((d.memory_stats() or {}) for d in devices)]
    return int(max(held)) if held else 0


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (the driver's pass 2**31)."""
    import jax

    return jax.random.fold_in(jax.random.PRNGKey(seed % (2 ** 31)),
                              seed // (2 ** 31))


# -- spans: the benchmark's own, written into the profiler's trace ------------

class Spans:
    """Host spans around the calls into each layer.  Recorded only in a
    traced run (end-to-end numbers are taken with tracing off), each also
    as a ``jax.profiler.TraceAnnotation`` so that the device trace carries
    it on its own clock."""

    PREFIX = "bench/"

    def __init__(self, on: bool):
        self.on = on
        self.rows: List[tuple] = []          # (name, start_ns, end_ns)

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.on:
            yield
            return
        import jax

        t0 = time.perf_counter_ns()
        with jax.profiler.TraceAnnotation(self.PREFIX + name):
            yield
        self.rows.append((name, t0, time.perf_counter_ns()))


class Tracer:
    """The profiler, switched on for a few seconds of steady state."""

    def __init__(self, root: str, on: bool):
        self.dir = os.path.join(root, ".bench_trace") if on else None
        self.active = False
        self.t_start = self.t_stop = None

    def start(self) -> None:
        if self.dir is None or self.active or self.t_start is not None:
            return
        import jax
        import shutil

        shutil.rmtree(self.dir, ignore_errors=True)
        jax.profiler.start_trace(self.dir)
        self.active = True
        self.t_start = time.perf_counter()

    def stop(self) -> None:
        if not self.active:
            return
        import jax

        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        self.active = False

    def reduced(self, chips: int) -> Optional[Dict]:
        """The trace reduced to busy time, shares and gaps (None when no
        trace was taken)."""
        if self.dir is None or self.t_stop is None:
            return None
        from benchmark import trace_reduce

        paths = sorted(glob.glob(os.path.join(
            self.dir, "plugins", "profile", "*", "*.xplane.pb")))
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under {self.dir}")
        out = trace_reduce.reduce(trace_reduce.load_xplane(paths[-1]), chips)
        out["host_window_s"] = self.t_stop - self.t_start
        return out


# -- a run --------------------------------------------------------------------

class Run:
    """What a runner is handed, and where it leaves what it measured."""

    def __init__(self, root: str, loaded: Dict, args, device: Dict,
                 t_process_start: float):
        self.root = root
        self.cell, self.cfg, self.traffic = (
            loaded["cell"], loaded["cfg"], loaded["traffic"])
        self.end_to_end, self.per_layer = (
            loaded["end_to_end"], loaded["per_layer"])
        self.seed, self.seconds, self.trace = args.seed, args.seconds, args.trace
        self.device = device
        self.chips = int(self.cell["chips"])
        self.family = load_module(root, "families", self.cfg["family"])
        self.spans = Spans(on=bool(args.trace))
        self.tracer = Tracer(root, on=bool(args.trace))
        self.t_process_start = t_process_start
        self.excluded_s = 0.0       # the reference's time before the window
        #: what the per-layer readers read: spans, counters, reduced trace
        self.record: Dict[str, Any] = {"spans": self.spans, "memory_bytes": 0}
        self.checks: List[Dict] = []
        self._tag = (f"[{device['platform']} {device['kind']} "
                     f"x{device['count']}]")

    def log(self, msg: str) -> None:
        """Every line names the device it was measured on."""
        print(f"{self._tag} {msg}", flush=True)

    def sample_memory(self, devices) -> None:
        """Keep the most device memory seen held at any sampling point."""
        self.record["memory_bytes"] = max(self.record["memory_bytes"],
                                          memory_bytes(devices))

    def phase(self, what: str) -> None:
        """Where set-up's time goes: seconds since the process started."""
        self.log(f"  t+{time.perf_counter() - self.t_process_start:7.2f} s  {what}")

    @contextlib.contextmanager
    def excluded(self):
        """Time spent here (the plain reference) is not set-up."""
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.excluded_s += time.perf_counter() - t0

    def setup_s(self, t_window_open: float) -> float:
        return t_window_open - self.t_process_start - self.excluded_s

    def check(self, name: str, value: float, limit: float,
              why: str = "") -> bool:
        """Hold ``value`` to ``limit`` (value <= limit passes; a value
        that is not a number fails) and print both."""
        ok = bool(value == value and value <= limit)
        self.checks.append({"name": name, "value": value, "limit": limit,
                            "ok": ok})
        self.log(f"check {name}: {value:.6g} (limit {limit:.6g}) "
                 f"{'ok' if ok else 'FAILED'}{' — ' + why if why else ''}")
        return ok

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(c["ok"] for c in self.checks)


def timing_line(run: Run, name: str, values_ms: List[float]) -> None:
    """A timing with its sample count, median and highest percentile that
    has ten samples beyond it."""
    import statistics

    if not values_ms:
        run.log(f"{name}: no samples")
        return
    vals = sorted(values_ms)
    n = len(vals)
    line = f"{name}: n={n} median={statistics.median(vals):.3f} ms"
    for pct in (99, 95, 90, 75):
        if n * (100 - pct) / 100 >= 10:
            line += f" p{pct}={vals[min(n - 1, int(n * pct / 100))]:.3f} ms"
            break
    run.log(line)


def per_layer_metrics(run: Run) -> Dict[str, Dict]:
    """Each per-layer metric of this cell through its own reader; a reader
    that finds nothing to read returns None and the metric is left out."""
    out = {}
    for entry in run.per_layer:
        reader = load_module(run.root, "layer_metrics", entry["name"])
        value = reader.read(run.record)
        if value is not None:
            out[entry["name"]] = {"value": float(value), "unit": entry["unit"]}
    return out


def result_line(run: Run, attempted: int, failed: int,
                end_to_end: Dict[str, float], devices) -> Dict:
    device = dict(run.device,
                  memory_peak_bytes=int(run.record.get("memory_bytes", 0)))
    if run.trace:
        reduced = run.record.get("trace")
        metrics = per_layer_metrics(run)
        line = {"correct": run.correct, "attempted": attempted,
                "failed": failed, "metrics": metrics, "device": device}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            line["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                                 "idle_gaps": reduced["idle_gaps"][:10]}
        return line
    units = {m["name"]: m["unit"] for m in run.end_to_end}
    missing = sorted(set(units) - set(end_to_end))
    if missing:
        raise RuntimeError(f"the runner reported no {missing}")
    metrics = {k: {"value": float(v), "unit": units[k]}
               for k, v in end_to_end.items() if k in units}
    return {"correct": run.correct, "attempted": attempted, "failed": failed,
            "metrics": metrics, "device": device}


def place_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at ``JAX_COMPILATION_CACHE_DIR``
    where that is set, else at the fixed ``<root>/.jax_cache``; every
    program cached, however quick its compile, and none evicted (the chip
    machine caps the cache at 192 MiB, under which BERT-large's programs
    evicted each other and every run compiled)."""
    import jax
    from apex_tpu.chip import compile_cache_dir

    cache_dir = compile_cache_dir(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_compilation_cache_max_size", -1)
    return cache_dir


def prepare(argv: Optional[List[str]], root: str, t_process_start: float,
            device_check: Callable[[int], Dict]) -> "Run":
    """Parse the command line, look for the chips, place the compile cache
    and load the cell: everything before the runner."""
    ap = argparse.ArgumentParser(description="run one cell of the benchmark once")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    loaded = load_cell(root, args.workload)
    device = device_check(int(loaded["cell"]["chips"]))

    cache_dir = place_compile_cache(root)
    run = Run(root, loaded, args, device, t_process_start)
    run.log(f"workload {args.workload} seed {args.seed} seconds {args.seconds} "
            f"trace {args.trace}; compile cache {cache_dir}")
    return run


def main(argv: Optional[List[str]], root: str, t_process_start: float,
         device_check: Callable[[int], Dict] = tpu_or_exit) -> int:
    """One run.  ``device_check`` is the only thing a rehearsal on the CPU
    replaces; the command line always runs :func:`tpu_or_exit`."""
    import jax

    run = prepare(argv, root, t_process_start, device_check)
    runner = load_module(root, "runners", run.traffic["kind"])
    attempted, failed, end_to_end = runner.run(run)
    line = result_line(run, attempted, failed, end_to_end,
                       jax.devices()[:run.chips])
    sys.stdout.flush()
    print(json.dumps(line), flush=True)
    return 0
