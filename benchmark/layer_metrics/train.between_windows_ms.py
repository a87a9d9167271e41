"""Layer: train loop.  Median ``between_ms`` of the measured windows: from
one window's fetch returning to the next window's dispatch beginning, the
chip with nothing queued — the time a step waits for data (here the
benchmark's ``make_batches`` and the loop around it)."""
import statistics

from benchmark import program_windows


def read(run):
    gaps = [row["between_ms"] for row in program_windows.measured(run) or ()
            if row["between_ms"] is not None]
    return statistics.median(gaps) if gaps else None
