"""Layer: model.  Device self time of the events under the ``lm_head``
(hidden state to logits) and ``lm_loss`` (logits to the scalar loss) scopes,
forward and backward, over the optimizer steps the trace's
``apex/train/dispatch`` spans carry, in milliseconds (train cells, traced
lines): the vocabulary end of the step."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, "lm_head|lm_loss")
