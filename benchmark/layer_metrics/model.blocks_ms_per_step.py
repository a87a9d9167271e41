"""Layer: model.  Device self time of the events under the transformer
blocks' scopes (``layer_<i>``, forward and backward, the kernels called
there included) over the optimizer steps the trace's ``apex/train/dispatch``
spans carry, in milliseconds (train cells, traced lines).  A fusion bears
one ``op_name``: what the compiler merged into a block's fusion counts
here."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"layer_\d+")
