"""Layer: model.  Device self time under Kimi Delta Attention's five scopes
(``kda_proj``, ``kda_conv``, ``kda_gate``, ``kda_scan``, ``kda_out``;
``models/kimi_linear.py::KimiDeltaAttention``), forward, recomputed forward
and backward, the rule's and the convolution's kernels included, over the
optimizer steps of the trace, in milliseconds.  None for a program without
such scopes."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(
        run, r"kda_(proj|conv|gate|scan|out)") or None
