"""Layer: model.  Device self time under the scope ``kda_scan`` alone — the
delta rule with a decay a key channel itself (normalised q and k, the running
sums of the log-decays, the rule's kernels or ``lax.scan``), forward,
recomputed forward and backward — over the optimizer steps of the trace, in
milliseconds.  None for a program without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"kda_scan") or None
