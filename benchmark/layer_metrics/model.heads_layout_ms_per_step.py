"""Layer: model.  Device self time under the scope ``heads_layout`` (q, k and
v to heads-major in front of the flash kernels and the result back:
``models/decoder.py::split_heads`` / ``merge_heads``, GPT-2's and
``SelfMultiheadAttn``'s own; forward, recomputed forward and backward) over
the optimizer steps of the trace, in milliseconds.  None for a program
without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, "heads_layout") or None
