"""Layer: model.  Device self time under the state-space mixer's four scopes
(``ssm_proj``, ``ssm_conv``, ``ssm_scan``, ``ssm_out``;
``models/granite_hybrid.py::Mamba2Mixer``), forward, recomputed forward and
backward, the scan's kernels included, over the optimizer steps of the trace,
in milliseconds.  None for a program without such scopes."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, r"ssm_(proj|conv|scan|out)") or None
