"""Layer: kernels.  Of the score sub-tiles of every flash attention call the
program traced, the share the kernels compute, in percent: the program's own
counters ``ops.flash.tiles_visited / ops.flash.tiles_total``
(``apex_tpu.obs.default_registry()``, counted when a call is traced:
``ops/attention.py::flash_tile_census``, band-aware).  Read beside the
traced line's other numbers: None for an untraced record, for a run whose
profile holds no dispatch span, and for a program that counts no tiles."""
from benchmark import program_trace


def read(run):
    t = program_trace.of(run)
    if not t or not t["windows"]:
        return None
    try:
        from apex_tpu import obs
    except ImportError:
        return None
    reg = obs.default_registry()
    total, visited = (reg.get("ops.flash.tiles_" + n) for n in ("total", "visited"))
    if total is None or visited is None or not total.value:
        return None
    return 100.0 * visited.value / total.value
