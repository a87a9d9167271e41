"""Layer: kernels.  Device self time a step of the grouped matrix products
(Mosaic events whose instruction bears ``apex_gmm``: ``apex_gmm`` forward
and input gradient, ``apex_gmm_dw`` weight gradient), in milliseconds.
None where the program runs no such kernel."""
from benchmark import program_trace


def read(run):
    return program_trace.kernel_ms_per_step(run, "apex_gmm") or None
