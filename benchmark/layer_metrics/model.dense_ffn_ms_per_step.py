"""Layer: model.  Device self time under the scope ``dense_ffn`` — the dense
gated MLP of every layer of a model without experts
(``models/granite_hybrid.py``: gate|up, SiLU, the product, down), forward,
recomputed forward and backward — over the optimizer steps of the trace, in
milliseconds.  None for a program without that scope."""
from benchmark import program_trace


def read(run):
    return program_trace.scope_ms_per_step(run, r"dense_ffn") or None
