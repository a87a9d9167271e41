"""Layer: compiler and device.  ``jit.cache_misses`` when the first measured
window opens: programs XLA truly compiled (and wrote to the persistent
cache), over the whole process, the reference's among them.  0 in a warm
checkout, dozens in a first run: whether two ``setup_s`` readings were like
for like."""
from benchmark import program_windows


def read(run):
    return program_windows.counter_at_open(
        run, "jit.cache_misses", "cache_misses")
