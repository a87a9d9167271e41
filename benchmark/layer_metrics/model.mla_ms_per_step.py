"""Layer: model.  Device self time under the latent-attention mixer's three
scopes (``mla_proj``, ``attn_full``, ``mla_out``;
``models/deepseek_v3.py::LatentAttention``), forward, recomputed forward and
backward, the flash kernels included, over the optimizer steps of the trace,
in milliseconds.  None for a program without the latent path's scopes (a
program that has ``attn_full`` alone is another mixer)."""
from benchmark import program_trace


def read(run):
    if not program_trace.scope_ms_per_step(run, r"mla_(proj|out)"):
        return None
    return program_trace.scope_ms_per_step(
        run, r"mla_proj|attn_full|mla_out") or None
