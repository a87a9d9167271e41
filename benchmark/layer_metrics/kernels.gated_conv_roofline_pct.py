"""Layer: kernels.  The gated short convolution as a share of its roofline:
the least time the chip could take for ``C * conv(B * X)`` of every
convolution layer (the family's ``gated_conv_needed``: B, C, X in and the
output out forward — ``4 S d`` elements —, B, C, X, dy in and dB, dC, dX out
backward — ``7 S d`` — and the taps, each crossing HBM once in the compute
dtype, over the HBM peak of ``peaks.json``; ~10 operations a channel a token
never bind; the forward recomputed in the backward pass is not counted as
needed) over the measured self time under ``conv_mix``, WHATEVER implements
it — the two ``apex_gated_conv_*`` kernels or XLA's passes: the same work —,
in percent."""
from benchmark import cell_shapes, flops, program_trace

NAME = "kernels.gated_conv_roofline_pct"


def read(run):
    measured_ms = program_trace.scope_ms_per_step(run, r"conv_mix")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = sum(kind == fam.CONV for kind in cfg["layer_types"])
    parts = layers * fam.gated_conv_needed(cfg, job["seq"], job["rows"])
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
