"""Layer: train loop.  Median of the program's own ``train.dispatch_ms``
histogram (``apex_tpu.obs.default_registry()``): host milliseconds of one
window's program lookup + enqueue, as ``FusedTrainDriver`` times it around
its ``train/dispatch`` span (train cells; traced lines whose profile holds
those spans, beside ``train.dispatch_exposed_ms``)."""
from benchmark import program_trace


def read(run):
    t = program_trace.of(run)
    if not t or not t["windows"]:
        return None
    from apex_tpu import obs

    hist = obs.default_registry().get("train.dispatch_ms")
    return hist.quantile(0.5) if hist is not None and hist.count else None
