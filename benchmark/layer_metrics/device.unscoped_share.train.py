"""Layer: compiler and device.  Device self time of the events under none of
the program's scopes (instructions with no ``op_name``, or with JAX's own
structure alone in it: copies, layout changes and async transfers the
compiler made) over busy time, in percent (train cells, traced lines)."""
from benchmark import program_trace


def read(run):
    t = program_trace.of(run)
    if not t or not t["op_ns"]:
        return None
    return 100.0 * t["unscoped_ns"] / t["op_ns"]
