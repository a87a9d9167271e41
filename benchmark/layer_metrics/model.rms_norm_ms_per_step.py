"""Layer: model.  Device self time under the scope ``rms_norm``
(``models/decoder.py::RMSNorm``'s arithmetic at every call site — block
norms, ``q_norm`` / ``k_norm``, ``norm_f``; forward, recomputed forward and
backward) over the optimizer steps of the trace, in milliseconds.  A fusion
bears its root's ``op_name``: a norm merged into the product that follows it
counts with the product.  None for a program without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, "rms_norm") or None
