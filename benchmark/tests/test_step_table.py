"""``step_table``: one traced window as rows (scope path, phase, kind) — by
hand on a synthetic trace, on slices recorded on the chip
(``data/trace_phases.json``), against the first reader's sums on every
recorded trace; the eight readers of ISSUE 48 and the tool."""
import json
import os

import pytest

from benchmark import harness, program_trace, step_table
from benchmark.tests import test_program_trace as first_reader
from benchmark.tools import step_table as tool

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
STEP = "jit(window)/while/body/closed_call/"
BWD = STEP + "transpose(jvp(M))/jvp(M)/checkpoint/"
TRACED = {"kind": "train", "trace": {"busy_s": 1.0}}

#: ISSUE 48's readers, with what each reads of :func:`synthetic` (ms a step)
READERS = {
    "model.forward_ms_per_step": 60e-6 / 2,
    "model.backward_ms_per_step": 55e-6 / 2,
    "model.recompute_ms_per_step": 35e-6 / 2,
    "model.blocks_bare_share.train": 100.0 * 10 / 140,
    "model.rope_ms_per_step": 25e-6 / 2,
    "model.heads_layout_ms_per_step": 30e-6 / 2,
    "model.rms_norm_ms_per_step": 20e-6 / 2,
    "kernels.moe_rows_ms_per_step": 15e-6 / 2,
}


def recorded(name):
    with open(os.path.join(HERE, "data", name)) as f:
        return json.load(f)


def synthetic():
    """One chip, one ``while`` of 200 ns over a step's operations one after
    another, each 5–25 ns: the new scopes in each phase, a bare residual add,
    a row kernel, an optimizer fusion, an unscoped copy, and a fusion whose
    joined ``op_name``s disagree on the phase; one dispatch span of 2 steps."""
    def ev(name, dur, op_name=None):
        ev.t += dur
        return [name, ev.t - dur, dur, {"op_name": op_name} if op_name else {}]
    ev.t = 10
    ops = [
        ev("%fusion.1 = bf16[8,4]{1,0} fusion(...)", 10,
           STEP + "jvp(M)/layer_0/input_norm/rms_norm/mul"),
        ev("%copy.2 = bf16[1,2,8,4]{3,2,1,0} copy(...)", 15,
           STEP + "jvp(M)/layer_0/attn/heads_layout/transpose"),
        ev("%slice_negate_fusion.3 = f32[8,4]{1,0} fusion(...)", 15,
           STEP + "jvp(M)/layer_0/attn/rope/concatenate"),
        ev("%fusion.4 = bf16[8,4]{1,0:T(8,128)(2,1)} fusion(...)", 10,
           STEP + "jvp(M)/layer_0/add"),
        ev("%fusion.5 = f32[8,9]{1,0} fusion(...)", 10,
           STEP + "jvp(M)/lm_head/head/dot_general"),
        ev("%fusion.6 = bf16[8,4]{1,0} fusion(...)", 10,
           BWD + "rematted_computation/layer_0/input_norm/rms_norm/mul"),
        ev("%copy.7 = bf16[1,2,8,4]{3,2,1,0} copy(...)", 15,
           BWD + "rematted_computation/layer_0/attn/heads_layout/transpose"),
        ev("%slice_negate_fusion.8 = f32[8,4]{1,0} fusion(...)", 10,
           BWD + "rematted_computation/layer_0/attn/rope/neg"),
        ev('%apex_moe_combine.9 = f32[8,4]{1,0} custom-call(...), '
           'custom_call_target="tpu_custom_call"', 15,
           BWD + "layer_0/moe/moe_dispatch/apex_moe_combine/pallas_call"),
        ev("%fusion.10 = f32[8,4]{1,0} fusion(...)", 25,
           BWD + "layer_0/mlp/down/dot_general"),
        # the gradient of a block's last product with an update merged in
        ev("%fusion.11 = f32[8,4]{1,0} fusion(...)", 15,
           BWD + "layer_0/mlp/up/dot_general;"
           + STEP + "apex_amp_step/apex_fused_adam/mul"),
        ev("%select_add_fusion.12 = f32[8,4]{1,0} fusion(...)", 20,
           STEP + "apex_amp_step/apex_fused_adam/add"),
        ev("%copy-done.13 = f32[32,4096]{1,0:T(8,128)S(1)} copy-done("
           "(f32[32,4096]{1,0:T(8,128)S(1)}, u32[]{:S(2)}) %copy-start.13)",
           5),
    ]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while(...)", 0, 200,
             {"op_name": "jit(window)/while"}]] + ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["apex/train/dispatch", 0, 20, {"k": 2, "microbatches": 1}]]}]},
    ]}


def test_table_of_the_synthetic_trace_by_hand():
    tab = step_table.table(synthetic())
    # 175 ns of operations, 25 of the while's own (JAX's structure: unscoped)
    assert tab["op_ns"] == 200
    assert tab["phase_ns"] == {"forward": 60, "recompute": 35, "backward": 55,
                               "optimizer": 20, "unscoped": 5 + 25}
    assert tab["mixed_ns"] == 15
    rows = tab["rows"]
    assert rows[("M/layer_*/attn/rope", "forward",
                 "slice_negate_fusion")] == [15, 1, 0]
    assert rows[("M/layer_*/attn/rope", "recompute",
                 "slice_negate_fusion")] == [10, 1, 0]
    # the first of the joined names decides; the row says how much is mixed
    assert rows[("M/layer_*/mlp/up", "backward", "fusion")] == [15, 1, 15]
    assert rows[("apex_amp_step/apex_fused_adam", "optimizer",
                 "select_add_fusion")] == [20, 1, 0]
    # an unscoped event names its result, layouts struck
    assert rows[("", "unscoped", "copy-done f32[32,4096]")] == [5, 1, 0]
    assert rows[("", "unscoped", "while (s32[])")] == [25, 1, 0]
    assert sum(ns for ns, _, _ in rows.values()) == tab["op_ns"]
    # bare: the block's own residual add, 10 of the 140 ns under layer_0
    assert (tab["bare_ns"], tab["layer_ns"]) == (10, 140)
    assert tab["bare"] == {
        ("M/layer_*", "forward", "fusion", "bf16[8,4]"): [10, 1]}
    kept = step_table.table(synthetic(), keep_index=True)
    assert ("M/layer_0", "forward", "fusion") in kept["rows"]
    assert kept["bare_ns"] == 10


@pytest.mark.parametrize("trace", [
    synthetic(), first_reader.synthetic(), recorded("trace_phases.json"),
    recorded("trace_named.json"), recorded("trace_small.json"),
    {"planes": []}], ids=["synthetic", "first_readers", "phases", "named",
                          "small", "empty"])
def test_rows_sum_to_the_first_readers_busy_and_unscoped_time(trace):
    """ROADMAP D8: a view of the first reader, not a fourth reader."""
    tab, first = step_table.table(trace), program_trace.reduce(trace)
    assert sum(ns for ns, _, _ in tab["rows"].values()) == first["op_ns"]
    assert tab["op_ns"] == first["op_ns"]
    assert tab["phase_ns"]["unscoped"] == first["unscoped_ns"]
    assert tab["layer_ns"] == sum(
        ns for scope, ns in first["scopes"].items()
        if scope.startswith("layer_"))
    optimizer = sum(ns for scope, ns in first["scopes"].items()
                    if scope in ("apex_amp_step", "apex_amp_cast"))
    # joined names that disagree count once here (the first decides) and
    # under every scope there: the two part by the mixed time at most
    assert 0 <= optimizer - tab["phase_ns"]["optimizer"] <= tab["mixed_ns"]


def test_table_of_the_recorded_trace():
    """Slices recorded on the chip (kimi-linear.train-8k, PR 47's program:
    none of ISSUE 48's scopes); the numbers are the file's own, worked out
    on the ``op_name`` strings (see its ``recorded`` key)."""
    trace = recorded("trace_phases.json")
    tab = step_table.table(trace)
    assert tab["phase_ns"] == trace["expect"]["phase_ns"]
    assert tab["op_ns"] == trace["expect"]["op_ns"]
    assert tab["mixed_ns"] == 0 and tab["bare_ns"] == 0
    by_kernel = {(kind, phase) for (_, phase, kind) in tab["rows"]
                 if kind.startswith("apex_")}
    assert by_kernel == {("apex_conv1d_fwd", "forward"),
                         ("apex_conv1d_fwd", "recompute"),
                         ("apex_xent_fwd", "forward"),
                         ("apex_xent_bwd", "backward")}
    assert ("", "unscoped", "copy f32[1024,8,32,128]") in tab["rows"]
    assert ("KimiLinearLM/layer_*/kda/kda_proj/qkv_proj", "recompute",
            "convolution_bitcast_fusion") in tab["rows"]


@pytest.mark.parametrize("op_name,want", [
    (STEP + "jvp(M)/layer_3/q_norm/rms_norm/mul",
     (("M", "layer_*", "q_norm", "rms_norm"), "forward", False)),
    (BWD + "layer_3/attn/rope/mul",
     (("M", "layer_*", "attn", "rope"), "backward", False)),
    (BWD + "rematted_computation/layer_3/attn/rope/mul",
     (("M", "layer_*", "attn", "rope"), "recompute", False)),
    # the optimizer's scopes come first, whatever wraps them
    (STEP + "transpose(jvp(apex_amp_cast))/convert_element_type",
     (("apex_amp_cast",), "optimizer", False)),
    (STEP + "apex_fused_lamb/mul", (("apex_fused_lamb",), "optimizer", False)),
    # joined names that agree are not mixed; a nameless first one is skipped
    (BWD + "layer_1/reshape;" + BWD + "layer_1/squeeze",
     (("M", "layer_*"), "backward", False)),
    ("jit(window)/while/body/add;" + STEP + "jvp(M)/embed/mul",
     (("M", "embed"), "forward", False)),
    (STEP + "jvp(M)/layer_0/add;" + BWD + "layer_0/add_any",
     (("M", "layer_*"), "forward", True)),
    ("jit(window)/while", ((), "unscoped", False)),
    ("", ((), "unscoped", False)),
])
def test_classify(op_name, want):
    assert step_table.classify(op_name) == want


@pytest.mark.parametrize("name,want", [
    ("%copy-done.4 = f32[32,4096]{1,0:T(8,128)S(1)} copy-done((f32[32,4096]"
     "{1,0:T(8,128)S(1)}, u32[]{:S(2)}) %copy-start.4)", "f32[32,4096]"),
    ("%copy-start = (u32[2]{0:T(128)S(1)}, u32[2]{0:T(128)}, u32[]{:S(2)}) "
     "copy-start(u32[2]{0:T(128)} %key.1)", "(u32[2], u32[2], u32[])"),
    ("%fusion.7 = f32[8] fusion(...)", "f32[8]"),
    ("a host event", ""),
])
def test_result_shape(name, want):
    assert step_table.result_shape(name) == want


# -- the readers --------------------------------------------------------------

def _reader(name):
    return harness.load_module(ROOT, "layer_metrics", name)


def _seen(monkeypatch, trace):
    """Both readers' ``newest`` answer with ``trace`` (None: no profile)."""
    reduced = program_trace.reduce(trace) if trace else None
    tab = step_table.table(trace) if trace else None
    monkeypatch.setattr(program_trace, "newest", lambda: reduced)
    monkeypatch.setattr(step_table, "newest", lambda: tab)


def test_every_new_metric_is_declared_at_the_end_and_has_a_reader():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cells = [w["name"] for w in bench["workloads"]]
    entries = bench["per_layer"][-len(READERS):]
    assert [e["name"] for e in entries] == list(READERS)
    for entry in entries:
        assert callable(_reader(entry["name"]).read)
        assert (entry["source"], entry["moves"], entry["better"]) == (
            "device_trace", "train_tokens_per_s", "lower")
        assert set(entry["workloads"]) <= set(cells)
    lists = {e["name"]: e["workloads"] for e in entries}
    assert lists["model.forward_ms_per_step"] == cells
    assert lists["model.heads_layout_ms_per_step"] == cells
    # GPT-2 and BERT run no ``jax.checkpoint`` and neither ``DecoderLM``
    assert lists["model.recompute_ms_per_step"] == cells[2:]
    assert lists["model.rms_norm_ms_per_step"] == cells[2:]


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_none_on_an_untraced_record(name, monkeypatch):
    fail = lambda: pytest.fail("an untraced record must not look for a profile")
    monkeypatch.setattr(program_trace, "newest", fail)
    monkeypatch.setattr(step_table, "newest", fail)
    read = _reader(name).read
    assert read({"kind": "train", "trace": None, "window_ms": [1.0]}) is None
    assert read({"kind": "serve", "trace": {"busy_s": 1.0}}) is None


@pytest.mark.parametrize("name", list(READERS))
def test_reader_returns_none_where_the_program_wrote_no_names(name,
                                                             monkeypatch):
    """An older program, traced: no ``op_name``, no ``apex/`` span to count
    steps by — and a traced record with no profile at all."""
    older = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%layer_0.14 = bf16[8] custom-call(...)", 0, 10],
            ["%fusion.3 = f32[8] fusion(...)", 10, 10]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench/driver.run_window", 0, 20]]}]}]}
    for trace in (older, {"planes": []}, None):
        _seen(monkeypatch, trace)
        assert _reader(name).read(TRACED) is None


@pytest.mark.parametrize("name,want", sorted(READERS.items()))
def test_reader_reads_the_table(name, want, monkeypatch):
    _seen(monkeypatch, synthetic())
    assert _reader(name).read(TRACED) == pytest.approx(want)


def test_readers_on_the_parents_program(monkeypatch):
    """The recorded slices are of PR 47's program: the phases and the bare
    share read (its ``op_name``s carry them), the three scopes and the
    recomputed forward of a program without them do not."""
    trace = recorded("trace_phases.json")
    _seen(monkeypatch, trace)
    got = {name: _reader(name).read(TRACED) for name in READERS}
    steps, want = trace["expect"]["steps"], trace["expect"]["phase_ns"]
    for phase in ("forward", "backward", "recompute"):
        assert got[f"model.{phase}_ms_per_step"] == pytest.approx(
            want[phase] * 1e-6 / steps)
    assert got["model.blocks_bare_share.train"] == 0.0
    for name in ("model.rope_ms_per_step", "model.heads_layout_ms_per_step",
                 "model.rms_norm_ms_per_step", "kernels.moe_rows_ms_per_step"):
        assert got[name] is None


def test_the_phases_and_the_first_readers_metrics_sum_to_the_busy_step(
        monkeypatch):
    """ISSUE 48's identity, on the recorded slices."""
    trace = recorded("trace_phases.json")
    _seen(monkeypatch, trace)
    read = lambda name: _reader(name).read(TRACED)
    busy = program_trace.reduce(trace)["op_ns"] * 1e-6 / trace["expect"]["steps"]
    parts = (read("model.forward_ms_per_step")
             + read("model.recompute_ms_per_step")
             + read("model.backward_ms_per_step")
             + read("train.optimizer_ms_per_step")
             + read("device.unscoped_share.train") / 100.0 * busy)
    assert parts == pytest.approx(busy)


# -- the tool -----------------------------------------------------------------

def test_tool_reports_the_synthetic_trace(tmp_path, capsys):
    rep = tool.report(synthetic())
    assert rep["steps"] == 2 and rep["busy_ms"] == pytest.approx(200e-6 / 2)
    assert sum(v for v, _ in rep["phases"].values()) == pytest.approx(
        rep["busy_ms"])
    assert sum(share for _, share in rep["phases"].values()) == pytest.approx(100)
    scopes = {path: cols for path, *cols in rep["scopes"]}
    assert scopes["M/layer_*/attn/rope"] == pytest.approx(
        [15e-6 / 2, 10e-6 / 2, 0.0])
    # a path holds what lies under it: the whole model, optimizer left out
    assert scopes["M"] == pytest.approx([60e-6 / 2, 35e-6 / 2, 55e-6 / 2])
    assert rep["mixed_ms"] == pytest.approx(15e-6 / 2)
    assert rep["bare_share_pct"] == pytest.approx(100.0 * 10 / 140)
    assert rep["unscoped"][0][0] == "while (s32[])"
    # the compiler's words by their holder; a kernel's name is not one
    holders = {(path, phase) for path, phase, _ in rep["xla_named"]}
    assert ("M/layer_*/mlp/down", "backward") in holders
    assert not any("moe_dispatch" in path for path, _ in holders)
    json.dumps(rep)                     # what --json writes
    tool.show(rep, top=5)
    out = capsys.readouterr().out
    assert "1. phases" in out and "7. holders" in out


def test_tool_reads_a_profile_from_its_path(tmp_path, monkeypatch, capsys):
    monkeypatch.setattr(program_trace, "load",
                        lambda path: recorded("trace_phases.json"))
    out_json = tmp_path / "table.json"
    assert tool.main(["somewhere.xplane.pb", "--top", "3",
                      "--json", str(out_json)]) == 0
    rep = json.loads(out_json.read_text())
    assert rep["steps"] == 8 and rep["phases"]["recompute"][0] > 0
    assert "recompute" in capsys.readouterr().out
    monkeypatch.setattr(step_table, "newest_profile", lambda: None)
    with pytest.raises(SystemExit):
        tool.main([])
