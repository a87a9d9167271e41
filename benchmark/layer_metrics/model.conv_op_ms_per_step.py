"""Layer: model.  Device self time under the gated short convolution
mixer's three scopes (``conv_proj``, ``conv_mix``, ``conv_out``;
``models/lfm2.py::ShortConv``), forward, recomputed forward and backward,
the convolution's kernels included, over the optimizer steps of the trace,
in milliseconds.  None for a program without such scopes."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, r"conv_(proj|mix|out)") or None
