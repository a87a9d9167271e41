"""Layer: train loop.  Mean over median of the measured windows' ``wall_ms``
(``apex_tpu.obs.train_windows`` through ``program_windows.measured``): what
ties ``train.window_ms``, a median, to ``train_tokens_per_s``, a mean over
the run's wall time.  1.000-1.003 in a steady run; one window 1.4 s late in
twenty of 0.9 s reads 1.08."""
import statistics

from benchmark import program_windows


def read(run):
    rows = program_windows.measured(run)
    if not rows:
        return None
    wall = [row["wall_ms"] for row in rows]
    return statistics.fmean(wall) / statistics.median(wall)
