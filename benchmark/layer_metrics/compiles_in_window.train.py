"""Layer: compiler and device.  Backend compiles counted by the program's
``CompileMonitor`` around the measured window; should read 0 (train cells)."""


def read(run):
    if run.get("kind") != "train":
        return None
    return run.get("compiles_in_window")
