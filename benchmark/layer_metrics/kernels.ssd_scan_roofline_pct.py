"""Layer: kernels.  The state-space scan as a share of its roofline: the
least time the chip could take for the chunked scan of every mamba layer
(the family's ``ssd_needed``: a token forward ``2 Q N`` once — ``C B^T`` —
and a head ``2 Q P + 4 P N`` at chunks of Q, the chunk's square counted
whole, backward twice that; x, dt, B, C, o, their gradients and the state at
each chunk's start crossing HBM once; each pass the larger of operations over
the bf16 peak and bytes over the HBM peak of ``peaks.json``; the forward
recomputed in the backward pass is not counted as needed) over the measured
self time under ``ssm_scan``, WHATEVER implements it — the two ``apex_ssd_*``
kernels or XLA's passes: the same work —, in percent."""
from benchmark import cell_shapes, flops, program_trace

NAME = "kernels.ssd_scan_roofline_pct"


def read(run):
    measured_ms = program_trace.scope_ms_per_step(run, r"ssm_scan")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = sum(kind == fam.MAMBA for kind in cfg["layer_types"])
    parts = layers * fam.ssd_needed(cfg, job["seq"], job["rows"],
                                    cfg["mamba_chunk_size"])
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
