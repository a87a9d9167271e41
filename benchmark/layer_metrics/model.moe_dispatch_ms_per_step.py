"""Layer: model.  Device self time under ``moe_router`` + ``moe_dispatch``
(scores, top-k, the slots' rows, gather into the row buffer, weighted
combine; forward and backward) a step, in milliseconds: what routing costs
beside the products.  None for a program without such scopes."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"moe_(router|dispatch)") or None
