"""Layer: model.  Device self time of the events under JAX's
``rematted_computation`` — the forward that ``remat_policy: full_block`` runs
again inside the backward, less what the policy keeps (``apex_tpu/remat.py``)
and what the compiler finds dead (``benchmark/step_table.py``) — over the
optimizer steps of the trace, in milliseconds.  A kept residual's price is the
row of its scope in this phase (``benchmark/tools/step_table.py``).  None for
a program that recomputes nothing."""
from benchmark import step_table


def read(run):
    return step_table.phase_ms_per_step(run, "recompute") or None
