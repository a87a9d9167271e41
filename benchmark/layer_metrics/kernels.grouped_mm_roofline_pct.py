"""Layer: kernels.  The grouped products' share of their roofline: the least
time the chip could take for an EVENLY routed batch (the family's
``grouped_mm_needed``: operations and bytes of the forward, input-gradient
and weight-gradient products of every expert layer, each the larger of
operations over the bf16 peak and bytes over the HBM peak of ``peaks.json``;
the forward recomputed in the backward pass is not counted as needed) over
the measured self time of the ``apex_gmm*`` kernels, in percent."""
from benchmark import cell_shapes, flops, program_trace

NAME = "kernels.grouped_mm_roofline_pct"


def read(run):
    measured_ms = program_trace.kernel_ms_per_step(run, "apex_gmm")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    parts = layers * fam.grouped_mm_needed(cfg, job["rows"] * job["seq"])
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
