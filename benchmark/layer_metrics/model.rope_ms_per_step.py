"""Layer: model.  Device self time under the scope ``rope``
(``models/decoder.py::rotary``: the rotation of q and k by position, float32
inside; forward, recomputed forward and backward) over the optimizer steps of
the trace, in milliseconds.  None for a program without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, "rope") or None
