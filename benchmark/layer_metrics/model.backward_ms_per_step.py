"""Layer: model.  Device self time of the events whose ``op_name`` holds a
``transpose(`` around a component and no ``rematted_computation`` — the
gradients' own operations, the forward a ``jax.checkpoint`` runs again left to
``model.recompute_ms_per_step`` (``benchmark/step_table.py``) — over the
optimizer steps of the trace, in milliseconds (train cells, traced lines).  A
fusion bears one ``op_name``: an optimizer update the compiler merged into a
gradient's fusion counts here."""
from benchmark import step_table


def read(run):
    return step_table.phase_ms_per_step(run, "backward")
