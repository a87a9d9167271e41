"""Layer: kernels.  Device self time of the fused softmax cross-entropy
kernels, forward and backward (Mosaic events whose instruction bears
``apex_xent_``), over the optimizer steps the trace's ``apex/train/dispatch``
spans carry, in milliseconds (train cells, traced lines)."""
from benchmark import program_trace


def read(run):
    return program_trace.kernel_ms_per_step(run, "apex_xent_")
