"""The harness end to end at a tiny size on the CPU, the look for a chip
stood in by the test: control flow and the decision of ``correct``, never a
device number.  The tiny benchmark is made of new files and entries alone
(``tiny.make_root``), which is also the proof that a configuration, a
traffic mix, a cell and a per-layer metric can each be added that way.
"""
import json

import jax.numpy as jnp
import pytest

from benchmark.tests import tiny

CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(str(tmp_path_factory.mktemp("tinybench")))


def rewrite(root, sub, name, **changes):
    path = f"{root}/benchmark/{sub}/{name}.json"
    with open(path) as f:
        data = json.load(f)
    with open(path, "w") as f:
        json.dump({**data, **changes}, f)
    return data


@pytest.mark.parametrize("workload,metric", [
    ("gpt2-tiny.train", "train_tokens_per_s"),
    ("bert-tiny.train", "train_tokens_per_s"),
    ("bert-tiny.train-dp4", "train_tokens_per_s"),    # four virtual devices
])
def test_cell_runs_and_is_correct(root, capsys, workload, metric):
    rc, line, lines = tiny.run_cell(root, workload, capsys)
    assert rc == 0 and set(line) == CONTRACT_KEYS
    assert line["correct"] is True, "\n".join(lines)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {metric, "setup_s"} <= set(line["metrics"])
    assert all(m["value"] > 0 for m in line["metrics"].values())
    # every line names the device; every number compared stands by its limit
    assert all(l.startswith("[cpu ") for l in lines[:-1])
    assert any("check " in l and "(limit " in l for l in lines)
    if workload.endswith("dp4"):
        assert any("replicas_disagreeing: 0" in l for l in lines)


def test_traced_run_reports_per_layer_metrics(root, capsys, monkeypatch):
    """``--trace 1`` reports the cell's per-layer metrics — here with the
    profiler's trace stood in (the CPU has no device plane), so the readers
    of the device trace get a reduced trace to read; a metric added by a
    file of its own (``train.windows``) is found by its name."""
    from benchmark import harness

    reduced = {"busy_s": 0.9, "window_s": 1.0, "op_time_s": 0.9,
               "mosaic_s": 0.3, "device_ops": [["f", .9]],
               "idle_gaps": [["driver.run_window", 0.1]]}
    monkeypatch.setattr(harness.Tracer, "start", lambda self: None)
    monkeypatch.setattr(harness.Tracer, "reduced",
                        lambda self, chips: dict(reduced))
    # model.mfu needs a device that is in the table of peaks
    as_v5e = lambda chips: dict(tiny.fake_device(chips), kind="TPU v5 lite")
    rc, line, _ = tiny.run_cell(root, "gpt2-tiny.train", capsys, trace=1,
                                device_check=as_v5e)
    assert rc == 0 and set(line) == CONTRACT_KEYS | {"breakdown"}
    got = line["metrics"]
    assert set(got) == {"train.window_ms", "train.windows", "model.mfu",
                        "kernels.mosaic_share.train", "device.idle_share.train",
                        "compiles_in_window.train"}
    assert got["device.idle_share.train"]["value"] == pytest.approx(10.0)
    assert got["compiles_in_window.train"]["value"] == 0
    assert line["device"]["busy_s"] == 0.9 and line["device"]["window_s"] == 1.0


def test_without_a_tpu_the_command_line_exits_nonzero(root):
    """No injected check: the measuring path refuses the CPU."""
    import time

    from benchmark import harness

    with pytest.raises(SystemExit) as e:
        harness.main(["--workload", "gpt2-tiny.train", "--seed", "1",
                      "--seconds", "1"], root, time.perf_counter())
    assert e.value.code not in (0, None)


# -- a broken timed path comes out as not correct -----------------------------

def unchanged_state(step):
    """A step that reports its loss and returns its state as it got it."""
    return lambda carry, batch: (carry, step(carry, batch)[1])


def half_the_rows(step):
    """A step that leaves the second half of its rows out of the loss."""
    def broken(carry, batch):
        ids, labels = batch
        keep = (jnp.arange(labels.shape[0]) < labels.shape[0] // 2)[:, None]
        return step(carry, (ids, jnp.where(keep, labels, -100)))
    return broken


@pytest.mark.parametrize("breakage,caught_by", [
    (unchanged_state, "param_delta_leaf_gap"),
    (half_the_rows, "grad_norm_rel_gap"),
])
def test_broken_step_inside_the_timed_window_is_not_correct(
        root, capsys, monkeypatch, breakage, caught_by):
    """The step that the window program scans, broken underneath: the rest
    of a run is driven as it is, and ``correct`` comes out false."""
    from benchmark import harness

    real_load = harness.load_module

    def load(root_, sub, name):
        module = real_load(root_, sub, name)
        if (sub, name) == ("runners", "train"):
            build = module.build_step
            module.build_step = lambda *a, **kw: breakage(build(*a, **kw))
        return module

    monkeypatch.setattr(harness, "load_module", load)
    _, line, lines = tiny.run_cell(root, "gpt2-tiny.train", capsys)
    assert line["correct"] is False
    assert any(caught_by in l and "FAILED" in l for l in lines)


def test_dropped_allreduce_is_not_correct(root, capsys, monkeypatch):
    """Data parallel without the exchange between chips: each replica
    trains on its own rows, and the replicas drift apart."""
    from apex_tpu.parallel import DistributedDataParallel

    monkeypatch.setattr(DistributedDataParallel, "allreduce",
                        lambda self, grads: grads)
    _, line, lines = tiny.run_cell(root, "bert-tiny.train-dp4", capsys)
    assert line["correct"] is False
    assert any("replicas_disagreeing" in l and "FAILED" in l for l in lines)


def test_allreduce_that_sums_is_not_correct(root, capsys, monkeypatch):
    import jax

    from apex_tpu.parallel import DistributedDataParallel

    monkeypatch.setattr(
        DistributedDataParallel, "allreduce",
        lambda self, grads: jax.tree_util.tree_map(
            lambda g: jax.lax.psum(g, self.axis_name), grads))
    _, line, lines = tiny.run_cell(root, "bert-tiny.train-dp4", capsys)
    assert line["correct"] is False
    assert any("grad_norm_rel_gap" in l and "FAILED" in l for l in lines)


# -- the control: a lower precision than the configuration states ------------

def test_control_tool_rehearsal(root, capsys, monkeypatch, tmp_path):
    """``tools/control.py`` end to end at the tiny size: the program's own
    pure-bfloat16 path (AMP O3) in the program's place — LayerNorm scales at
    1.0 cannot take a step of 6e-4 in bfloat16, so the parameters' change
    misses the reference's by a whole leaf — reads far above the limit that
    the sound readings set, and the traffic file is written with it."""
    from benchmark import harness
    from benchmark.tools import control

    monkeypatch.setattr(harness, "tpu_or_exit", tiny.fake_device)
    out = tmp_path / "lm-tiny.json"
    assert control.main(["--workload", "gpt2-tiny.train", "--seeds", "1", "2",
                         "--control-seeds", "1", "--earlier",
                         "loss_rel_gap=2e-4", "--write-traffic", str(out)],
                        root) == 0
    rows = [json.loads(l.split("READING ", 1)[1])
            for l in capsys.readouterr().out.splitlines() if "READING " in l]
    assert [(r["variant"], r["seed"]) for r in rows] == [
        ("sound", 1), ("sound", 2), ("control", 1)]
    written = json.loads(out.read_text())
    limit = written["limits"]["param_delta_leaf_gap"]
    assert max(r["param_delta_leaf_gap"] for r in rows[:2]) < limit
    assert rows[2]["param_delta_leaf_gap"] > 3 * limit
    assert written["limits"]["loss_rel_gap"] >= 6e-4      # 3 x the earlier
    assert "control" in written["limits_from"]
    assert written["rows"] == 4 and written["kind"] == "train"


def test_control_that_passes_every_limit_is_refused(root, capsys, monkeypatch):
    """With the configuration's own precision in the control's place, no
    number separates the two: the tool exits 1."""
    from benchmark import harness
    from benchmark.tools import control

    monkeypatch.setattr(harness, "tpu_or_exit", tiny.fake_device)
    monkeypatch.setattr(control, "CONTROL_OPT_LEVEL", "O2")
    assert control.main(["--workload", "gpt2-tiny.train", "--seeds", "1",
                         "--control-seeds", "1"], root) == 1
