"""Layer: train loop.  Milliseconds a measured window that the garbage
collector paused the main thread for (``gc_ms``: the ambient tracer's
``gc.callbacks`` hook), over the measured windows."""
from benchmark import program_windows


def read(run):
    rows = program_windows.measured(run)
    return sum(row["gc_ms"] for row in rows) / len(rows) if rows else None
