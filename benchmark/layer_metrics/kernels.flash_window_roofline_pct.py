"""Layer: kernels.  The window layers' flash attention as a share of its
roofline: the least time for the band's operations (4 H D sum_i min(i + 1,
window) forward, twice that backward; the family's ``flash_needed``) and for
q, k, v, o and their gradients crossing HBM once, over the measured self time
of the ``apex_flash_*`` kernels under ``attn_window``, in percent.  The
forward recomputed in the backward pass is in the measured time and not in
the needed."""
from benchmark import cell_shapes, flops, scoped_kernels

NAME = "kernels.flash_window_roofline_pct"


def read(run):
    measured_ms = scoped_kernels.kernel_ms_per_step_under(
        run, "apex_flash", "attn_window")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = sum(kind == fam.WINDOW for kind in cfg["layer_types"])
    parts = layers * fam.flash_needed(cfg, job["seq"], job["rows"],
                                      cfg["sliding_window"])
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
