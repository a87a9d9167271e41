"""Compile-only rehearsal of each train cell's window program at its REAL
size for a described ``v5e:2x2`` — what the chip's compiler refuses (a block
off the tiling, too much VMEM, a program that does not fit 16 GB) it refuses
here, at no chip time.  Nothing runs: a compile that passes is not a chip
run.  ``memory_analysis()`` per device is printed (``-s``) and copied into
PERF.md §4.  Slow (minutes): run by hand,

    python -m pytest benchmark/tests/test_compile_real_size.py -s

The topology is described inside a fixture, never at import.
"""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from benchmark import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A described-topology executable cannot be read back from the
    persistent cache without a chip."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r:.200}")


@pytest.fixture
def as_tpu(monkeypatch):
    """Answer the kernels' backend gates the way the chip would."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("workload,config,traffic,chips", [
    ("gpt2-small.train", "gpt2-small", "causal-lm-16x1024", 1),
    ("bert-large.train", "bert-large", "mlm-12x512", 1),
    # kept for later (PERF.md, Open questions), rehearsed here all the same:
    # bert-large.train's job at 12 rows a chip over four chips
    ("bert-large.train-dp4", "bert-large", "mlm-12x512", 4),
])
def test_train_window_compiles_at_real_size(topo, as_tpu, workload, config,
                                            traffic, chips):
    from apex_tpu.ops import mosaic_call_count

    cfg, job = load("configs", config), load("traffic", traffic)
    if chips > 1:
        job = {**job, "rows": job["rows"] * chips, "data_parallel": True}
    fam = harness.load_module(ROOT, "families", cfg["family"])
    train = harness.load_module(ROOT, "runners", "train")

    mesh = None
    if job.get("data_parallel"):
        mesh = Mesh(np.array(topo.devices[:chips]), ("data",))
        carry_sh = NamedSharding(mesh, P())
        batch_sh = NamedSharding(mesh, P(None, "data"))
    else:
        carry_sh = batch_sh = SingleDeviceSharding(topo.devices[0])
    driver, init_carry = train.build_program(
        cfg, job, fam, cfg["precision"]["opt_level"], mesh)
    rcfg = fam.reference_config(cfg)
    key = jax.random.PRNGKey(0)
    weights = jax.eval_shape(
        lambda k: fam.reference.init_params(k, rcfg), key)
    carry = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=carry_sh),
        jax.eval_shape(init_carry, weights, key))
    k, rows, seq = job["steps_per_dispatch"], job["rows"], job["seq"]
    batch = jax.ShapeDtypeStruct((k, rows, seq), jnp.int32, sharding=batch_sh)

    compiled = driver.lower(carry, (batch, batch)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    print(f"\n{workload}: per device arguments "
          f"{mem.argument_size_in_bytes / 2**30:.2f} GiB, temporaries "
          f"{mem.temp_size_in_bytes / 2**30:.2f} GiB, aliased "
          f"{mem.alias_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls "
          f"{mosaic_call_count(compiled)}")
    assert total < 16e9
    assert mosaic_call_count(compiled) > 0
    if mesh is not None:
        assert "all-reduce" in compiled.as_text()
