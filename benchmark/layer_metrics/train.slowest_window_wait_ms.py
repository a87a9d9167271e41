"""Layer: train loop.  Of the measured window with the longest ``wall_ms``,
its ``wait_ms``: the ``train/fetch_metrics`` span, the host blocked on the
device — the device's time, or the runtime's under a blocked fetch."""
from benchmark import program_windows


def read(run):
    row = program_windows.slowest(run)
    return None if row is None else row["wait_ms"]
