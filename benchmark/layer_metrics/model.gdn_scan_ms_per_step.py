"""Layer: model.  Device self time under the scope ``gdn_scan`` alone — the
gated delta rule itself (decays, normalised q and k, what is local to a
chunk, the chain over the chunks; kernels or ``lax.scan``), forward,
recomputed forward and backward — over the optimizer steps of the trace, in
milliseconds.  None for a program without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"gdn_scan") or None
