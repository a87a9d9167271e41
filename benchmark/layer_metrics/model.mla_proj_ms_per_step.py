"""Layer: model.  Device self time under the scope ``mla_proj`` alone — what
the latent costs outside the kernel: the query projection, the
down-projection, the latent's norm, the up-projection, the rotation and
assembling q and k (the shared rotary key broadcast to the heads), forward,
recomputed forward and backward — over the optimizer steps of the trace, in
milliseconds.  None for a program without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"mla_proj") or None
