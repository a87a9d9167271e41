"""Test configuration: run everything on 8 virtual CPU devices.

This is the TPU build's analog of the reference's 2-GPU
``torch.distributed.launch`` test harness (ref tests/distributed/): real XLA
collectives over a `jax.sharding.Mesh`, no hardware needed.  Must set the
env vars before jax initializes its backends.
"""
import os

# force the CPU: tier-1 is hardware-free, and on a machine with a chip a
# test process that initialized the TPU would hold it against everything
# else (one process per chip)
os.environ["JAX_PLATFORMS"] = "cpu"
xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    os.environ["XLA_FLAGS"] = (
        xla_flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# the env var only counts if jax has not been imported yet (a pytest plugin
# or an in-process caller may have); the config update wins either way
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", False)

import gc  # noqa: E402

import numpy as np  # noqa: E402
import pytest  # noqa: E402

#: memory mappings a test process may hold before JAX's caches are dropped:
#: half the kernel's default ``vm.max_map_count`` (65,530)
MAPPINGS_HIGH = 32_000


@pytest.fixture(autouse=True)
def executables_released_before_the_mappings_run_out():
    """JAX keeps every CPU executable it compiled, and each holds three
    memory mappings a code object.  The worker that runs
    ``test_ops_attention.py`` (214 tests of eager interpret-mode kernels)
    ends the file at ~58,000 mappings; the kernel allows a process 65,530,
    and the first compile past that dies of a segmentation fault inside
    ``backend_compile_and_load`` — in whatever file the worker took next
    (PR 43 lost a worker in five whole runs before the count was read).
    After a test that leaves the process past ``MAPPINGS_HIGH``, drop the
    caches: the executables go, and their mappings with them."""
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:                 # no procfs: nothing to read, nothing done
        return
    if held > MAPPINGS_HIGH:
        jax.clear_caches()
        gc.collect()


#: the files whose tests take longest, dearest first (junit times of a whole
#: run, PR 43).  xdist's ``loadfile`` hands files to workers in collection
#: order; alphabetical order started ``test_qwen3_next.py`` — a tenth of the
#: suite's time in one file — after most of the rest, and the run ended when
#: it did, 1,413 s of the 1,470 allowed.  Dearest first, the short files fill
#: the tail.
LONGEST_FIRST = (
    "test_qwen3_next.py", "test_ops_attention.py", "test_ring_attention.py",
    "test_afmoe.py", "test_deepseek_v3.py", "test_chip_smoke.py",
    "test_remat.py", "test_models.py", "test_ops_gated_delta.py",
    "test_moe.py", "test_smallthinker.py", "test_tpu_compile.py",
)


def pytest_collection_modifyitems(config, items):
    """The dearest files first; every other file, and every test inside a
    file, in the order it was collected (the sort is stable)."""
    rank = {name: i for i, name in enumerate(LONGEST_FIRST)}
    items.sort(key=lambda item: rank.get(item.path.name, len(rank)))


@pytest.fixture
def rng():
    return np.random.RandomState(0)


@pytest.fixture
def mesh8():
    from jax.sharding import Mesh

    devices = np.array(jax.devices()[:8])
    return Mesh(devices, axis_names=("data",))


def pytest_sessionfinish(session, exitstatus):
    """Tier-1 trace artifact: with ``APEX_TPU_OBS_TRACE_DIR`` set
    (``tools/run_tier1.sh --trace <dir>``), export the ambient
    apex_tpu.obs tracer/registry — every instrumented engine/driver
    span the suite exercised — as trace.jsonl / trace.chrome.json /
    metrics.json.  No-op otherwise."""
    out_dir = os.environ.get("APEX_TPU_OBS_TRACE_DIR")
    if not out_dir:
        return
    try:
        from apex_tpu import obs

        paths = obs.export_default(out_dir)
        if paths:
            print(f"\nobs trace artifact: {paths['jsonl']}")
    except Exception as e:  # the artifact must never fail the suite
        print(f"\nobs trace export failed: {e!r}")


@pytest.fixture(scope="session")
def canonical():
    """Session-scoped lazy registry of the canonical programs
    (``tools/lint_graphs.CanonicalPrograms``): the train-driver windows
    (M in {1, 2, 4} amp O2, zero=True) and the serve decode windows —
    contiguous and PAGED — (K in {1, 8}, tensor-parallel mesh).

    Shared by tests/test_inspect_hlo.py and tests/test_analysis.py so
    each program is built, LOWERED and COMPILED at most once per
    session — the jit/lowering work dominates those files' runtime and
    the 418-test suite must stay inside the tier-1 budget.  Programs
    build lazily on first ``canonical.get(name)``, so running a single
    test builds only what it touches.  The registry's ``args`` are
    reserved for shape-only analysis: EXECUTING a program must go
    through ``make_args()`` (the windows donate their carry — see
    ``tools/lint_graphs.check_warm_redispatch``).
    """
    import os
    import sys

    sys.path.insert(
        0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    )
    from tools.lint_graphs import CanonicalPrograms

    return CanonicalPrograms()
