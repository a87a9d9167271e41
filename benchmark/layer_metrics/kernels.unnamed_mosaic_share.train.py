"""Layer: kernels.  Device self time of Mosaic events whose instruction
bears no name from the program's ``apex_tpu.ops._common.KERNEL_NAMES`` over
all Mosaic time, in percent: reads 0 while every kernel has its name (train
cells, traced lines; None for a program that has no such list)."""
from benchmark import program_trace


def read(run):
    t = program_trace.of(run)
    if not t or not t["mosaic_ns"]:
        return None
    try:
        from apex_tpu.ops._common import KERNEL_NAMES
    except ImportError:
        return None
    unnamed = sum(ns for name, (ns, _) in t["kernels"].items()
                  if not any(k in name for k in KERNEL_NAMES))
    return 100.0 * unnamed / t["mosaic_ns"]
