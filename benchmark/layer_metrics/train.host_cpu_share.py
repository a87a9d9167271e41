"""Layer: train loop.  The main thread's CPU time over the measured
windows as a percentage of their wall time (``cpu_ms`` over ``wall_ms``,
summed): small in a loop that sleeps in ``device_get``; Python or the
collector at work between windows raises it."""
from benchmark import program_windows


def read(run):
    rows = program_windows.measured(run)
    if not rows:
        return None
    return (100.0 * sum(row["cpu_ms"] for row in rows)
            / sum(row["wall_ms"] for row in rows))
