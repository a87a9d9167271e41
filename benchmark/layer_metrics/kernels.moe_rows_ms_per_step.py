"""Layer: kernels.  Device self time a step of the expert layer's row
movement (Mosaic events whose instruction bears ``apex_moe_``:
``apex_moe_records``, ``apex_moe_gather``, ``apex_moe_combine``,
``apex_moe_combine_dw``), in milliseconds.  None where the program runs no
such kernel."""
from benchmark import program_trace


def read(run):
    return program_trace.kernel_ms_per_step(run, "apex_moe_") or None
