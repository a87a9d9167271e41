"""Layer: kernels.  Position-free latent attention's flash kernels as a share
of their roofline, in a model whose latent layers are SOME of its layers: the
least time the chip could take for the causal attention of the layers the
configuration lists as full attention (the family's ``is_full`` counts them;
its ``flash_needed``: ``2 H (d_qk + d_v)`` operations a query a key it sees
forward, backward twice that; q, k ``d_qk`` wide and v, o ``d_v`` wide and
their gradients crossing HBM once; each pass the larger of operations over the
bf16 peak and bytes over the HBM peak of ``peaks.json``; the forward is not
run again under per-block recomputation, which keeps the kernel's output) over
the measured self time of the ``apex_flash_*`` kernels under ``attn_full``, in
percent.  (``kernels.flash_mla_roofline_pct`` multiplies by EVERY layer: right
where every layer is latent.)  None for a program without the latent path's
scopes."""
from benchmark import cell_shapes, flops, program_trace, scoped_kernels

NAME = "kernels.flash_mla_nope_roofline_pct"


def read(run):
    if not program_trace.scope_ms_per_step(run, r"mla_proj"):
        return None
    measured_ms = scoped_kernels.kernel_ms_per_step_under(
        run, "apex_flash", "attn_full")
    found = cell_shapes.of(run, NAME)
    if not measured_ms or found is None:
        return None
    cfg, job, fam = found
    layers = sum(fam.is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    parts = layers * fam.flash_needed(cfg, job["seq"], job["rows"])
    needed_s = fam.needed_seconds(parts, flops.peaks(run["device_kind"]))
    return 100.0 * needed_s * 1e3 / measured_ms
