"""Layer: kernels.  Device self time of the flash-attention backward kernels
(every variant: Mosaic events whose instruction bears ``apex_flash_bwd``)
over the optimizer steps the trace's ``apex/train/dispatch`` spans carry, in
milliseconds (train cells, traced lines)."""
from benchmark import program_trace


def read(run):
    return program_trace.kernel_ms_per_step(run, "apex_flash_bwd")
