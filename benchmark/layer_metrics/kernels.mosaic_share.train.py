"""Layer: kernels.  Device time of Mosaic (Pallas) custom-call events over
the device's busy time in the traced window, in percent (train cells)."""


def read(run):
    t = run.get("trace")
    if run.get("kind") != "train" or not t or not t["op_time_s"]:
        return None
    return 100.0 * t["mosaic_s"] / t["op_time_s"]
