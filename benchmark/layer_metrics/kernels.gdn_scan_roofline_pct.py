"""Layer: kernels.  The gated delta rule as a share of its roofline: the
least time the chip could take for the chunked rule of every delta-net layer
(the family's ``gdn_needed``: per token and value head ``2 (5 C d + C^2 + 3
d_k d_v)`` operations forward at chunks of C = 64, backward twice that; q, k,
v, g, beta, o and their gradients crossing HBM once; each pass the larger of
operations over the bf16 peak and bytes over the HBM peak of ``peaks.json``;
the forward recomputed in the backward pass is not counted as needed) over
the measured self time under ``gdn_scan``, in percent."""
from benchmark import cell_shapes, flops, program_trace

NAME = "kernels.gdn_scan_roofline_pct"


def read(run):
    measured_ms = program_trace.scope_ms_per_step(run, r"gdn_scan")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = sum(not fam.is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    parts = layers * fam.gdn_needed(cfg, job["seq"], job["rows"])
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
