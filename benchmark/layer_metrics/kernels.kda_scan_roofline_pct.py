"""Layer: kernels.  The delta rule with a decay a key channel as a share of
its roofline: the least time the chip could take for the chunked rule of every
KDA layer (the family's ``kda_needed``: per token and head ``2 (5 C d + C^2 +
3 d^2)`` operations forward at chunks of C = 64 — the two decayed score
products at ``C d`` each however they are sub-blocked, nothing for the VPU's
channel-wise work —, backward twice that; q, k, v, o in the compute dtype, the
log-decay g in float32 at (S, H, d), beta and their gradients crossing HBM
once; each pass the larger of operations over the bf16 peak and bytes over the
HBM peak of ``peaks.json``; the forward recomputed in the backward pass is not
counted as needed) over the measured self time under ``kda_scan``, WHATEVER
implements it — the two ``apex_kda_*`` kernels or a scan —, in percent."""
from benchmark import cell_shapes, flops, program_trace

NAME = "kernels.kda_scan_roofline_pct"


def read(run):
    measured_ms = program_trace.scope_ms_per_step(run, r"kda_scan")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = sum(not fam.is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    parts = layers * fam.kda_needed(cfg, job["seq"], job["rows"])
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
