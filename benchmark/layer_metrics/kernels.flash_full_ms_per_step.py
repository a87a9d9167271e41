"""Layer: kernels.  Device self time a step of the flash attention kernels
(``apex_flash_*``) called from the full-attention layers' scope
``attn_full``, in milliseconds.  None for a program without that scope."""
from benchmark import scoped_kernels


def read(run):
    return scoped_kernels.kernel_ms_per_step_under(run, "apex_flash", "attn_full")
