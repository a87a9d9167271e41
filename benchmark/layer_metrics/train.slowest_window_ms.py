"""Layer: train loop.  The longest ``wall_ms`` of the measured windows
(``program_windows.slowest``): the straggler, whose parts
``train.slowest_window_wait_ms`` and ``train.slowest_window_host_ms`` are."""
from benchmark import program_windows


def read(run):
    row = program_windows.slowest(run)
    return None if row is None else row["wall_ms"]
