"""Layer: compiler and device.  Of the first window's ``train/dispatch``
span, its ``trace_s + lower_s`` (``span.jit``, exclusive seconds): Python's
share of the first dispatch — tracing the step and lowering it to MLIR —
which no compile cache shortens."""
from benchmark import program_windows


def read(run):
    row = program_windows.first(run)
    if row is None or not row.get("jit"):
        return None
    return row["jit"].get("trace_s", 0.0) + row["jit"].get("lower_s", 0.0)
