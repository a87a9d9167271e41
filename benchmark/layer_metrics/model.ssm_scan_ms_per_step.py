"""Layer: model.  Device self time under the scope ``ssm_scan`` alone — the
state-space scan itself (the step size's softplus, the decays, the running
sums and their layouts, the chunked scan; kernels or ``jax.numpy``), forward,
recomputed forward and backward — over the optimizer steps of the trace, in
milliseconds.  None for a program without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"ssm_scan") or None
