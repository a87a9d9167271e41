"""Layer: train loop.  Median host milliseconds of one K-step dispatch,
loss fetched, over the windows of the run."""
import statistics


def read(run):
    return statistics.median(run["window_ms"]) if run.get("window_ms") else None
