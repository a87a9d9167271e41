"""Layer: compiler and device.  Wall seconds of the first window's
``train/dispatch`` span — set-up's: Python tracing the window program,
lowering it, and the backend compiling it or loading it from the
persistent cache, before the enqueue.  It lies inside ``setup_s``."""
from benchmark import program_windows


def read(run):
    row = program_windows.first(run)
    return None if row is None else row["enqueue_ms"] * 1e-3
