"""Layer: model.  Device self time of the events whose ``op_name`` puts them
in the FORWARD pass — under a scope of the program, outside the optimizer's
scopes, no ``transpose(`` around a component and no ``rematted_computation``
in the path (``benchmark/step_table.py``: embedding, blocks, head and loss as
the step first runs them) — over the optimizer steps of the trace, in
milliseconds (train cells, traced lines).  With ``model.recompute_``,
``model.backward_`` and ``train.optimizer_ms_per_step`` and the unscoped share
it sums to the busy step."""
from benchmark import step_table


def read(run):
    return step_table.phase_ms_per_step(run, "forward")
