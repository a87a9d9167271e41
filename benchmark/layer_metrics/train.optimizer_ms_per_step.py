"""Layer: train loop.  Device self time of the events under
``apex_amp_step`` (unscale, inf check, norms, the optimizer's update) and
``apex_amp_cast`` (masters to compute dtype, and the gradients' way back)
over the optimizer steps the trace's ``apex/train/dispatch`` spans carry, in
milliseconds (train cells, traced lines).  A fusion bears one ``op_name``:
an update the compiler merged into a gradient's fusion counts with the
model."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, "apex_amp_step|apex_amp_cast")
