"""The yardstick's own arithmetic: operation counts against hand counts, the
peaks table, and the trace reduction on a small recorded trace."""
import json
import os

import pytest

from benchmark import flops, trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def config(name):
    with open(os.path.join(BENCH, "configs", name + ".json")) as f:
        return json.load(f)


def test_gpt2_small_flops_per_token_by_hand():
    d, layers, seq, vocab = 768, 12, 1024, 50304
    block = (2 * d * 3 * d) + (2 * d * d) + 2 * (2 * d * 4 * d)   # 24 d^2
    attention = 2 * 2 * d * (seq + 1) / 2          # QK^T and PV, causal
    forward = layers * (block + attention) + 2 * d * vocab
    assert flops.gpt2_train_flops_per_token(
        config("gpt2-small"), seq) == pytest.approx(3 * forward)
    # 6 x parameters in the matmuls is the usual rule of thumb: 85M in the
    # blocks, 38.6M in the head
    assert 3 * forward == pytest.approx(6 * (85e6 + 38.6e6) + 3 * 12 * attention,
                                        rel=0.01)


def test_bert_large_flops_per_token_by_hand():
    d, ffn, layers, seq, vocab = 1024, 4096, 24, 512, 30592
    block = 2 * d * 3 * d + 2 * d * d + 2 * 2 * d * ffn + 2 * 2 * d * seq
    forward = layers * block + 2 * d * d + 2 * d * vocab
    assert flops.bert_train_flops_per_token(
        config("bert-large"), seq) == pytest.approx(3 * forward)


def test_mfu_and_peaks():
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    # 197e12 / 1e9 FLOPs a token = 197k tokens/s is 100%
    assert flops.mfu_percent(1e9, 98.5e3, 1, "TPU v5 lite") == pytest.approx(50)
    assert flops.mfu_percent(1e9, 98.5e3, 4, "TPU v5 lite") == pytest.approx(12.5)
    with pytest.raises(KeyError):
        flops.peaks("a device that is not in the table")


# -- the trace reduction ------------------------------------------------------

def synthetic():
    """Two chips; chip 0: a ``while`` over [0,90) with its body's
    operations inside it and 10 ns of its own, then a gap of 9 ns under the
    benchmark's ``window`` span, in a [0,100) window."""
    ops0 = [["%while.4 = (s32[]) while(...)", 0, 90],     # spans its body
            ["%fusion.1 = f32[8] fusion(...)", 0, 30],
            ['%layer_0.2 = bf16[8] custom-call(...), custom_call_target='
             '"tpu_custom_call"', 30, 10],
            ['%custom-call.5 = f32[8] custom-call(...), custom_call_target='
             '"ConcatBitcast"', 50, 0],
            ["%all-reduce.7 = f32[8] all-reduce(...)", 50, 20],
            ["%fusion.3 = f32[8] fusion(...)", 70, 20],
            ["%tail.9 = f32[8] copy(...)", 99, 1]]
    ops1 = [["%fusion.1 = f32[8] fusion(...)", 0, 50],
            ["%fusion.3 = f32[8] fusion(...)", 50, 50]]
    return {"planes": [
        {"name": "/device:TPU:0",
         "lines": [{"name": "XLA Ops", "events": ops0}]},
        {"name": "/device:TPU:1",
         "lines": [{"name": "XLA Ops", "events": ops1}]},
        {"name": "/host:CPU", "lines": [{"name": "python", "events": [
            ["bench/engine.step", 35, 30], ["bench/window", 0, 100]]}]},
    ]}


def test_reduce_synthetic_trace():
    out = trace_reduce.reduce(synthetic(), chips=2)
    assert out["window_s"] == pytest.approx(100e-9)
    assert out["busy_s"] == pytest.approx((91 + 100) / 2 * 1e-9)
    assert out["op_time_s"] == pytest.approx(91e-9)     # self times sum to busy
    assert out["mosaic_s"] == pytest.approx(10e-9)
    assert out["device_ops"][0] == ["fusion", pytest.approx(50e-9)]
    gaps = dict(map(tuple, out["idle_gaps"]))
    assert gaps == {"window": pytest.approx(9e-9)}
    assert dict(map(tuple, out["device_ops"]))["while"] == pytest.approx(10e-9)
    one = trace_reduce.reduce(synthetic(), chips=1)
    assert one["busy_s"] == pytest.approx(91e-9)


def test_reduce_refuses_a_trace_without_device_operations():
    with pytest.raises(ValueError):
        trace_reduce.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}, 1)


def test_reduce_recorded_trace():
    """A slice of a trace recorded on the chip (gpt2-small.train, PR 23):
    the numbers are the recorded slice's own, worked out by hand from the
    file (see its ``expect`` key)."""
    path = os.path.join(HERE, "data", "trace_small.json")
    with open(path) as f:
        recorded = json.load(f)
    out = trace_reduce.reduce(recorded, chips=1)
    for key, want in recorded["expect"].items():
        assert out[key] == pytest.approx(want, rel=1e-6), key
    assert 0 < out["busy_s"] <= out["window_s"]
