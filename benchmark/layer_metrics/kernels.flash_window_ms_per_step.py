"""Layer: kernels.  Device self time a step of the flash attention kernels
(``apex_flash_*``, forward, recomputed forward and backward) called from the
sliding-window layers' scope ``attn_window``, in milliseconds.  None for a
program without that scope."""
from benchmark import scoped_kernels


def read(run):
    return scoped_kernels.kernel_ms_per_step_under(run, "apex_flash", "attn_window")
