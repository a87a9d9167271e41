"""Layer: compiler and device.  ``jit.cache_load_s`` when the first
measured window opens: seconds the persistent compile cache took to hand
back executables, over the WHOLE process since ``apex_tpu`` was imported —
the plain reference's programs are in it (their time is not in
``setup_s``), as are the state's, the batch maker's and the window's."""
from benchmark import program_windows


def read(run):
    return program_windows.counter_at_open(
        run, "jit.cache_load_s", "cache_load_s")
