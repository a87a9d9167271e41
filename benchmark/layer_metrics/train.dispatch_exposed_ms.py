"""Layer: train loop.  Device idle time inside the program's
``apex/train/dispatch`` spans over the windows traced, in milliseconds: the
part of a window's enqueue that the chip waited for (train cells, traced
lines)."""
from benchmark import program_trace


def read(run):
    t = program_trace.of(run)
    if not t or not t["windows"]:
        return None
    idle = t["idle_under_ns"].get(program_trace.DISPATCH_SPAN, 0)
    return idle * 1e-6 / t["windows"]
