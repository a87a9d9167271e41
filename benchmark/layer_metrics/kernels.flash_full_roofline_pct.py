"""Layer: kernels.  The full-attention layers' flash attention as a share of
its roofline: the least time for the causal triangle's operations (4 H D
(S + 1) / 2 a query forward, twice that backward; the family's
``flash_needed`` with no window) and for q, k, v, o and their gradients
crossing HBM once, over the measured self time of the ``apex_flash_*`` kernels
under ``attn_full``, in percent — what ``kernels.flash_window_roofline_pct``
reads of the window layers, of the layers ``layer_types`` does not call
windowed.  The forward is not run again under per-block recomputation, which
keeps the kernel's output.  None for a program without that scope, and for a
cell whose family counts no ``flash_needed`` by window."""
from benchmark import cell_shapes, flops, scoped_kernels

NAME = "kernels.flash_full_roofline_pct"


def read(run):
    measured_ms = scoped_kernels.kernel_ms_per_step_under(
        run, "apex_flash", "attn_full")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = sum(kind != fam.WINDOW for kind in cfg["layer_types"])
    parts = layers * fam.flash_needed(cfg, job["seq"], job["rows"], None)
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
