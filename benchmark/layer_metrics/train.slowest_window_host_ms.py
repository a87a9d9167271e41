"""Layer: train loop.  Of the measured window with the longest ``wall_ms``,
``between_ms + enqueue_ms + inflight_host_ms``: what is not the blocked
fetch — the host's batch making, the dispatch, and its time in between."""
from benchmark import program_windows


def read(run):
    row = program_windows.slowest(run)
    return None if row is None else row["host_ms"]
