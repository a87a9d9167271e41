"""Layer: model.  Device self time under the expert layers' four scopes
(``moe_router``, ``moe_dispatch``, ``moe_experts``, ``moe_shared``;
``parallel/moe.py::ExpertShardMLP``), forward and backward, the grouped
products included, over the optimizer steps of the trace, in milliseconds.
None for a program without such scopes."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, r"moe_(router|dispatch|experts|shared)") or None
