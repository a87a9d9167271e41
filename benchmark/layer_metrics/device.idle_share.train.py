"""Layer: compiler and device.  1 - (union of device-operation intervals /
traced steady window), in percent (train cells)."""


def read(run):
    t = run.get("trace")
    if run.get("kind") != "train" or not t:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
