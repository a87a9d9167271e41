"""Layer: model.  Device self time under ``moe_router`` alone (the router's
float32 scores over all experts and the top-k; forward, recomputed forward
and backward) a step, in milliseconds.  Beside
``model.moe_dispatch_ms_per_step``, which holds it, it says what the scores
and the selection cost apart from the routing plan and the row movement — in a
block whose router reads the block's input (``models/smallthinker.py``) this
is the part the compiler may place beside attention.  None for a program
without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"moe_router") or None
