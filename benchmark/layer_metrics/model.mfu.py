"""Layer: model.  Model FLOP/s utilisation of the whole step: operations the
forward and backward passes require per token (``benchmark/flops.py``; matrix
multiplications and attention, recomputation not counted) times tokens per
second, over chips times the bf16 peak of ``peaks.json``.  Tokens per second
are those of the median window (a traced run's whole-window rate holds the
profiler's own starts and stops)."""
import statistics

from benchmark import flops


def read(run):
    if run.get("kind") != "train":
        return None
    rate = run["tokens_per_window"] / (statistics.median(run["window_ms"]) * 1e-3)
    return flops.mfu_percent(run["flops_per_token"], rate, run["chips"],
                             run["device_kind"])
