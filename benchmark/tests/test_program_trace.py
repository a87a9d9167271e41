"""``program_trace``: the reduction by the program's own names, by hand on a
synthetic trace and on a slice recorded on the chip; the file reader on a
hand-built ``.xplane.pb``; every reader of this module's record."""
import json
import os

import pytest

from benchmark import harness, program_trace

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
STEP = "jit(window)/while/body/closed_call/"
MOSAIC = ' custom-call(...), custom_call_target="tpu_custom_call"'

#: the readers this module feeds, with what each reads of SYNTHETIC below
READERS = {
    "kernels.flash_fwd_ms_per_step": 60e-6 / 6,
    "kernels.flash_bwd_ms_per_step": 20e-6 / 6,
    "kernels.layer_norm_ms_per_step": 0.0,
    "kernels.xentropy_ms_per_step": 0.0,
    "kernels.unnamed_mosaic_share.train": 0.0,
    "model.blocks_ms_per_step": 80e-6 / 6,
    "model.lm_head_ms_per_step": 15e-6 / 6,
    "train.optimizer_ms_per_step": 15e-6 / 6,
    "train.dispatch_exposed_ms": 45e-6 / 2,
    "device.unscoped_share.train": 100.0 * 30 / 150,
}


def synthetic():
    """One chip: a ``while`` of 100 ns (two kernels, a head fusion, a copy
    the compiler made, an optimizer fusion), a batch program, the ``while``
    again for 40 ns; two dispatch spans of 3 steps over the gaps."""
    fwd = ["%apex_flash_fwd.3 = bf16[8]" + MOSAIC, 10, 30,
           {"op_name": STEP + "jvp(GPTLM)/layer_0/apex_flash_fwd/pallas_call"}]
    ops = [
        ["%while.1 = (s32[]) while(...)", 0, 100,
         {"op_name": "jit(window)/while"}],
        fwd,
        ["%apex_flash_bwd_fused.4 = bf16[8]" + MOSAIC, 40, 20,
         {"op_name": STEP + "transpose(jvp(GPTLM))/layer_0/"
                            "apex_flash_bwd_fused/pallas_call"}],
        ["%fusion.7 = f32[8] fusion(...)", 60, 15,
         {"op_name": STEP + "transpose(jvp(GPTLM))/GPTLM._logits/lm_head/"
                            "dot_general"}],
        ["%copy.9 = f32[8] copy(...)", 75, 5, {}],
        ["%fusion.8 = f32[8] fusion(...)", 80, 15,
         {"op_name": STEP + "apex_amp_step/fused_adam/add"}],
        ["%fusion.1 = s32[8] fusion(...)", 130, 10,
         {"op_name": "jit(<lambda>)/make_batches/add"}],
        ["%while.1 = (s32[]) while(...)", 160, 40,
         {"op_name": "jit(window)/while"}],
        [fwd[0], 165, 30, fwd[3]],
    ]
    spans = [["apex/train/fetch_metrics", 90, 14, {}],
             ["apex/train/dispatch", 105, 45, {"k": 3, "microbatches": 1}],
             ["apex/train/dispatch", 150, 15, {"k": 3, "microbatches": 1}],
             ["bench/driver.run_window", 100, 70, {}]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops}]},
        {"name": "/device:TPU:1",
         "lines": [{"name": "XLA Ops", "events": [ops[6]]}]},
        {"name": "/host:CPU",
         "lines": [{"name": "python3", "events": spans}]},
    ]}


def test_reduce_synthetic_trace_by_hand():
    out = program_trace.reduce(synthetic())
    assert out["op_ns"] == 150 and out["mosaic_ns"] == 80
    assert out["kernels"] == {"apex_flash_fwd": [60, 2],
                              "apex_flash_bwd_fused": [20, 1]}
    assert out["scopes"] == {
        "GPTLM": 95, "layer_0": 80, "apex_flash_fwd": 60,
        "apex_flash_bwd_fused": 20, "GPTLM._logits": 15, "lm_head": 15,
        "apex_amp_step": 15, "fused_adam": 15, "make_batches": 10}
    # the copy, and both whiles' own time (JAX's structure is no scope)
    assert out["unscoped_ns"] == 5 + 15 + 10
    assert (out["steps"], out["windows"]) == (6, 2)
    assert out["spans"] == {"train/fetch_metrics": [1, 14],
                            "train/dispatch": [2, 60]}
    # gaps [100, 130) and [140, 160) against the spans' intervals
    assert out["idle_under_ns"] == {"train/fetch_metrics": 4,
                                    "train/dispatch": 25 + 10 + 10}


def test_reduce_recorded_trace():
    """Slices of a trace recorded on the chip (gpt2-small.train, PR 24):
    two named kernels inside the ``while``, scoped and unscoped operations,
    two ``apex/train/dispatch`` events with ``k``, the gap under each; the
    numbers are the file's own, worked out on a 1 ns timeline (see its
    ``recorded`` key)."""
    with open(os.path.join(HERE, "data", "trace_named.json")) as f:
        recorded = json.load(f)
    out = program_trace.reduce(recorded)
    for key, want in recorded["expect"].items():
        assert out[key] == want, key
    assert set(out["kernels"]) == {"apex_ln_fwd", "apex_flash_fwd"}
    assert out["steps"] == 20 and out["idle_under_ns"]["train/dispatch"] > 0
    assert 0 < out["unscoped_ns"] < out["op_ns"]
    assert out["mosaic_ns"] == sum(ns for ns, _ in out["kernels"].values())


def test_a_trace_without_the_programs_names_reduces_to_nothing():
    """An older commit's trace: kernels named after their callers, no
    ``op_name``, no ``apex/`` span."""
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            ["%layer_0.14 = bf16[8]" + MOSAIC, 0, 10],
            ["%fusion.3 = f32[8] fusion(...)", 10, 10]]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ["bench/driver.run_window", 0, 20]]}]}]}
    out = program_trace.reduce(trace)
    assert (out["steps"], out["windows"], out["spans"]) == (0, 0, {})
    assert out["kernels"] == {"layer_0": [10, 1]} and out["scopes"] == {}
    assert out["unscoped_ns"] == out["op_ns"] == 20
    assert program_trace.reduce({"planes": []})["op_ns"] == 0


@pytest.mark.parametrize("op_name,want", [
    (STEP + "jvp(GPTLM)/layer_3/ln1/apex_ln_fwd/pallas_call",
     ["GPTLM", "layer_3", "ln1", "apex_ln_fwd"]),
    (STEP + "transpose(jvp(BertForMLM))/encoder/layer_0/self_attn/mul",
     ["BertForMLM", "encoder", "layer_0", "self_attn"]),
    (STEP + "jvp(apex_amp_cast)/convert_element_type", ["apex_amp_cast"]),
    (STEP + "transpose(jvp(GPTLM))/layer_1/reshape;"
            "transpose(jvp(GPTLM))/layer_1/squeeze", ["GPTLM", "layer_1"]),
    (STEP + "jvp(GPTLM)/embed/wte/jit(_take)/gather",
     ["GPTLM", "embed", "wte"]),
    ("jit(window)/while", []), ("jit(window)/while/body/add", []), ("", []),
])
def test_scopes_of(op_name, want):
    assert program_trace.scopes_of(op_name) == want


# -- the file reader, on a hand-built .xplane.pb ------------------------------

def _varint(n):
    out = b""
    while True:
        out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
        n >>= 7
        if not n:
            return out


def _field(number, value):
    """One protobuf field: a varint for an int, else length-delimited."""
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    value = value.encode() if isinstance(value, str) else value
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _plane(name, event_metas, stat_names):
    """An XPlane: ``event_metas`` is ``{id: (name, [XStat bytes])}``."""
    out = _field(2, name)
    for mid, (ev_name, stats) in event_metas.items():
        meta = _field(1, mid) + _field(2, ev_name) + b"".join(
            _field(5, s) for s in stats)
        out += _field(4, _field(1, mid) + _field(2, meta))
    for sid, sname in stat_names.items():
        out += _field(5, _field(1, sid) + _field(
            2, _field(1, sid) + _field(2, sname)))
    return out + _field(3, _field(2, "XLA Ops") + _field(3, 12345))


def test_op_names_reads_the_tf_op_of_each_events_metadata(tmp_path):
    stats = {7: "tf_op", 9: "hlo_category", 300: STEP + "apex_amp_step/add"}
    device = _plane("/device:TPU:0", {
        1: ("%apex_ln_fwd.1 = f32[8] custom-call(...)",
            [_field(1, 9) + _field(5, "custom-call"),
             _field(1, 7) + _field(5, STEP + "jvp(GPTLM)/ln_f/apex_ln_fwd/"
                                              "pallas_call:")]),
        2: ("%copy.5 = f32[8] copy(...)",
            [_field(1, 9) + _field(5, "data formatting")]),
        400: ("%fusion.8 = f32[8] fusion(...)",
              [_field(1, 7) + _field(7, 300)]),       # a ref_value
    }, stats)
    host = _plane("/host:CPU", {
        1: ("apex/train/dispatch", [_field(1, 7) + _field(5, "not/a/device")]),
    }, stats)
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_field(1, host) + _field(1, device) + _field(4, "host0"))
    assert program_trace.op_names(str(path)) == {
        "%apex_ln_fwd.1 = f32[8] custom-call(...)":
            STEP + "jvp(GPTLM)/ln_f/apex_ln_fwd/pallas_call",
        "%fusion.8 = f32[8] fusion(...)": STEP + "apex_amp_step/add",
    }


# -- the readers --------------------------------------------------------------

def _reader(name):
    return harness.load_module(ROOT, "layer_metrics", name)


def test_every_metric_this_module_feeds_is_declared_for_the_train_cells():
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    declared = {m["name"]: m for m in bench["per_layer"]}
    for name in list(READERS) + ["train.dispatch_ms"]:
        entry = declared[name]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["workloads"] == ["gpt2-small.train", "bert-large.train"]


@pytest.mark.parametrize("name", list(READERS) + ["train.dispatch_ms"])
def test_reader_returns_none_on_an_untraced_record(name, monkeypatch):
    monkeypatch.setattr(program_trace, "newest", lambda: pytest.fail(
        "an untraced record must not look for a profile"))
    read = _reader(name).read
    assert read({"kind": "train", "trace": None, "window_ms": [1.0]}) is None
    assert read({"kind": "serve", "trace": {"busy_s": 1.0}}) is None


@pytest.mark.parametrize("name", list(READERS) + ["train.dispatch_ms"])
def test_reader_returns_none_where_the_program_wrote_no_names(name,
                                                             monkeypatch):
    """The parent commit, traced: a profile with no ``apex/`` span and no
    kernel of ``KERNEL_NAMES`` — and a traced record with no profile."""
    traced = {"kind": "train", "trace": {"busy_s": 1.0}}
    bare = program_trace.reduce({"planes": []})
    for reduced in (bare, None):
        monkeypatch.setattr(program_trace, "newest", lambda: reduced)
        assert _reader(name).read(traced) is None


@pytest.mark.parametrize("name,want", sorted(READERS.items()))
def test_reader_reads_the_reduced_trace(name, want, monkeypatch):
    reduced = program_trace.reduce(synthetic())
    monkeypatch.setattr(program_trace, "newest", lambda: reduced)
    got = _reader(name).read({"kind": "train", "trace": {"busy_s": 1.0}})
    assert got == pytest.approx(want)


def test_unnamed_mosaic_share_counts_a_kernel_named_after_its_caller(
        monkeypatch):
    trace = synthetic()
    trace["planes"][0]["lines"][0]["events"].append(
        ["%layer_0.14 = bf16[8]" + MOSAIC, 200, 20, {}])
    reduced = program_trace.reduce(trace)
    monkeypatch.setattr(program_trace, "newest", lambda: reduced)
    got = _reader("kernels.unnamed_mosaic_share.train").read(
        {"kind": "train", "trace": {"busy_s": 1.0}})
    assert got == pytest.approx(100.0 * 20 / 100)


def test_dispatch_ms_is_the_median_of_the_programs_histogram(monkeypatch):
    from apex_tpu import obs

    reduced = program_trace.reduce(synthetic())
    monkeypatch.setattr(program_trace, "newest", lambda: reduced)
    obs.reset_default()
    try:
        for ms in (900.0, 2.0, 3.0, 2.5, 2.6):       # the first one compiled
            obs.default_registry().histogram("train.dispatch_ms").observe(ms)
        got = _reader("train.dispatch_ms").read(
            {"kind": "train", "trace": {"busy_s": 1.0}})
    finally:
        obs.reset_default()
    assert got == 2.6
