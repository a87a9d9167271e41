"""The ``afmoe`` family and the ``trinity-mini.train-8k`` cell, rehearsed on
the CPU: a tiny afmoe cell through the harness (new files and entries alone,
as ``tiny.make_root`` builds the GPT-2 and BERT ones), the family's FLOPs
worked out by hand, the new readers on a small recorded trace, and the cell's
window compiled at its REAL size for a described ``v5e:2x2`` (arguments +
temporaries under 16 GiB: the fit, before any chip time; slow, minutes).

    python -m pytest benchmark/tests/test_afmoe_cell.py -s
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import harness
from benchmark.tests import tiny

ROOT = tiny.REAL
W, F = "sliding_attention", "full_attention"
CELL = "afmoe-tiny.train"
AFMOE_TINY = {
    "name": "afmoe-tiny", "family": "afmoe", "hidden_size": 128,
    "num_hidden_layers": 3, "num_dense_layers": 1, "layer_types": [W, W, F],
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "sliding_window": 48, "rope_theta": 10000, "intermediate_size": 256,
    "moe_intermediate_size": 128, "num_experts": 4, "num_experts_per_tok": 4,
    "num_shared_experts": 1, "route_norm": True, "route_scale": 2.826,
    "rms_norm_eps": 1e-5, "mup_enabled": True, "vocab_size": 250,
    "published": {"num_experts": 16},
    "assumed": {"padded_vocab_size": 256, "experts_held": [4, 8],
                "initializer_range": 0.02, "remat_policy": "full_block"},
    "precision": {"opt_level": "O2"},
}


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny afmoe configuration, its job, its cell
    and the real benchmark's afmoe metrics retargeted to it."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("tinyafmoe")))
    with open(f"{root}/benchmark/configs/afmoe-tiny.json", "w") as f:
        json.dump(AFMOE_TINY, f)
    with open(f"{root}/benchmark/traffic/lm-tiny-1row.json", "w") as f:
        json.dump(tiny.train_mix("causal_lm", tiny.ADAMW, rows=1,
                                 reference_rows_per_block=1), f)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "afmoe-tiny", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/afmoe-tiny.json"})
    bench["workloads"].append({"name": CELL, "config": "afmoe-tiny",
                               "traffic": "lm-tiny-1row", "chips": 1,
                               "why": "tiny"})
    names = {m["name"] for m in bench["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] != "kernels.layer_norm_ms_per_step":
            m["workloads"].append(CELL)
    bench["per_layer"] += [
        {**m, "workloads": [CELL]} for m in real["per_layer"]
        if m["name"] not in names and "trinity-mini.train-8k" in m["workloads"]]
    with open(f"{root}/BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root


def checked(lines, name):
    """The value the run printed for the check ``name``."""
    line = next(l for l in lines if f"check {name}:" in l)
    return float(line.split(f"check {name}:")[1].split()[0])


def test_tiny_afmoe_cell_is_correct_and_lower_precision_stands_apart(
        root, capsys):
    rc, line, lines = tiny.run_cell(root, CELL, capsys)
    assert rc == 0 and line["correct"] is True, "\n".join(lines)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"train_tokens_per_s", "setup_s"} <= set(line["metrics"])
    sound = checked(lines, "param_delta_leaf_gap")
    # the control: AMP O3 (no float32 masters) in the program's place.  Its
    # norm scales cannot take a step of 6e-4 at 1.0 in bfloat16, so the
    # worst leaf's change reads several times the sound run's — a limit
    # between the two readings fails it (the tiny job's own limit is loose)
    path = f"{root}/benchmark/configs/afmoe-tiny.json"
    with open(path, "w") as f:
        json.dump({**AFMOE_TINY, "precision": {"opt_level": "O3"}}, f)
    try:
        rc, line, lines = tiny.run_cell(root, CELL, capsys)
    finally:
        with open(path, "w") as f:
            json.dump(AFMOE_TINY, f)
    assert rc == 0
    assert checked(lines, "param_delta_leaf_gap") > 3 * sound, "\n".join(lines)


def test_train_flops_by_hand():
    """Trinity-Mini's share at 8192 tokens, every term written out."""
    cfg = load("configs", "trinity-mini")
    fam = harness.load_module(ROOT, "families", "afmoe")
    proj = 2 * 2048 * (4096 + 512 + 512 + 4096) + 2 * 4096 * 2048
    window_keys = (2048 * 2049 / 2 + (8192 - 2048) * 2048) / 8192
    attention = 4 * 32 * 128 * (4 * window_keys + 8193 / 2)
    dense = 6 * 2048 * 6144
    expert_layer = (2 * 2048 * 128            # router over all 128
                    + 6 * 2048 * 1024         # the shared expert
                    + 8 * 16 / 128 * 6 * 2048 * 1024)   # one expert expected
    head = 2 * 2048 * 25088
    by_hand = 3 * (5 * proj + attention + dense + 4 * expert_layer + head)
    assert fam.train_flops_per_token(cfg, 8192) == pytest.approx(by_hand, rel=1e-12)
    assert 2.2e9 < by_hand < 2.3e9
    parts = fam.forward_flops_per_token(cfg, 8192)
    assert parts["routed"] / sum(parts.values()) < 0.1
    # the kernels' needs: 512 rows an expert; the band of the window layers
    gmm = fam.grouped_mm_needed(cfg, 8192)
    assert len(gmm) == 6 and gmm[0][0] == 2 * 8192 * 2048 * 2048
    fwd, bwd = fam.flash_needed(cfg, 8192, 1, 2048)
    assert fwd[0] == pytest.approx(8192 * 4 * 32 * 128 * window_keys)
    assert bwd[0] == 2 * fwd[0]


def test_views_take_a_layers_held_experts_together():
    """The reference keeps each held expert's matrices as leaves of their
    own; they are compared stacked, as the program holds them, and the
    program's tree comes back to the same leaves."""
    fam = harness.load_module(ROOT, "families", "afmoe")
    rcfg = fam.reference_config(AFMOE_TINY)
    w = fam.reference.init_params(jax.random.PRNGKey(0), rcfg)
    seen = fam.views(w)
    assert "layers.1.moe.experts.4.w_gate" in w
    assert seen["layers.1.moe.experts.w_gate"].shape == (4, 128, 128)
    assert not any(".experts.4." in k for k in seen)
    assert (seen["layers.1.moe.experts.w_down"][1]
            == w["layers.1.moe.experts.5.w_down"]).all()
    back = fam.views(fam.from_program(fam.to_program(w, AFMOE_TINY), AFMOE_TINY))
    assert sorted(back) == sorted(seen)
    assert all((back[k] == seen[k]).all() for k in seen)


def test_configuration_keeps_the_published_widths():
    cfg = load("configs", "trinity-mini")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "Trinity-Mini")
    differing = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differing == set(cfg["reduced"])
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}


def test_new_readers_on_a_recorded_trace(monkeypatch):
    """The scoped readers on a small trace: flash under ``attn_window`` and
    ``attn_full`` apart, the grouped products and the moe scopes summed."""
    from benchmark import program_trace, scoped_kernels

    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    step = "jit(window)/while/body/closed_call/"
    ev = lambda name, t0, dur, scope: [
        f"%{name} = bf16[8]" + mosaic, t0, dur,
        {"op_name": step + f"jvp(AfmoeLM)/layer_1/{scope}/pallas_call"}]
    device = [
        ev("apex_flash_fwd.1", 0, 10, "attn_window/jit(_flash)/apex_flash_fwd"),
        ev("apex_flash_bwd_dq.2", 10, 30, "attn_window/jit(_flash)/apex_flash_bwd_dq"),
        ev("apex_flash_fwd.3", 40, 7, "attn_full/jit(_flash)/apex_flash_fwd"),
        ev("apex_gmm.4", 50, 20, "moe/moe_experts/apex_gmm"),
        ev("apex_gmm_dw.5", 70, 5, "moe/moe_experts/apex_gmm_dw"),
        ["%fusion.9 = f32[8] fusion()", 80, 4,
         {"op_name": step + "jvp(AfmoeLM)/layer_1/moe/moe_dispatch/gather"}],
    ]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["apex/train/dispatch", 0, 5, {"k": 2}]]}]}]}
    monkeypatch.setattr(program_trace, "newest",
                        lambda *a: program_trace.reduce(trace))
    monkeypatch.setattr(scoped_kernels, "newest",
                        lambda *a: scoped_kernels.reduce(trace))
    run = {"kind": "train", "trace": {"busy_s": 1}}
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    assert read("kernels.flash_window_ms_per_step") == pytest.approx(40e-6 / 2)
    assert read("kernels.flash_full_ms_per_step") == pytest.approx(7e-6 / 2)
    assert read("kernels.grouped_mm_ms_per_step") == pytest.approx(25e-6 / 2)
    assert read("model.moe_ms_per_step") == pytest.approx(29e-6 / 2)
    assert read("model.moe_dispatch_ms_per_step") == pytest.approx(4e-6 / 2)
    # a program without the scopes (the parent): nothing to read, no error
    bare = {"planes": [trace["planes"][1]]}
    monkeypatch.setattr(program_trace, "newest",
                        lambda *a: program_trace.reduce(bare))
    monkeypatch.setattr(scoped_kernels, "newest",
                        lambda *a: scoped_kernels.reduce(bare))
    for name in ("kernels.flash_window_ms_per_step", "model.moe_ms_per_step",
                 "kernels.grouped_mm_roofline_pct",
                 "kernels.flash_window_roofline_pct"):
        assert read(name) is None


def test_roofline_readers_find_the_cell(monkeypatch):
    from benchmark import program_trace, scoped_kernels

    cfg, job = load("configs", "trinity-mini"), load("traffic", "causal-lm-1x8192")
    fam = harness.load_module(ROOT, "families", "afmoe")
    run = {"kind": "train", "trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "tokens_per_window": 4 * 8192,
           "flops_per_token": fam.train_flops_per_token(cfg, job["seq"])}
    monkeypatch.setattr(program_trace, "newest", lambda *a: {
        "steps": 4, "kernels": {"apex_gmm.1": [40_000_000, 8]}, "scopes": {}})
    monkeypatch.setattr(scoped_kernels, "newest", lambda *a: {
        ("apex_flash_fwd.1", "attn_window"): 400_000_000})
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    gmm_s = 4 * fam.needed_seconds(fam.grouped_mm_needed(cfg, 8192), peaks)
    assert read("kernels.grouped_mm_roofline_pct") == pytest.approx(100 * gmm_s / 0.010)
    flash_s = 4 * fam.needed_seconds(fam.flash_needed(cfg, 8192, 1, 2048), peaks)
    assert read("kernels.flash_window_roofline_pct") == pytest.approx(100 * flash_s / 0.100)
    assert 0 < read("kernels.flash_window_roofline_pct") < 100


# -- the cell's window at its real size, for a described chip -----------------

@pytest.fixture(scope="module")
def topo():
    try:
        from jax.experimental import topologies

        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e!r:.200}")


@pytest.fixture
def no_compile_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def test_train_window_compiles_at_real_size(topo, no_compile_cache, monkeypatch):
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, job = load("configs", "trinity-mini"), load("traffic", "causal-lm-1x8192")
    fam = harness.load_module(ROOT, "families", cfg["family"])
    train = harness.load_module(ROOT, "runners", "train")
    chip = SingleDeviceSharding(topo.devices[0])
    driver, init_carry = train.build_program(
        cfg, job, fam, cfg["precision"]["opt_level"], None)
    rcfg = fam.reference_config(cfg)
    key = jax.random.PRNGKey(0)
    weights = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg), key)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(weights))
    assert 705e6 < n_params < 706e6
    carry = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        jax.eval_shape(init_carry, weights, key))
    batch = jax.ShapeDtypeStruct(
        (job["steps_per_dispatch"], job["rows"], job["seq"]), jnp.int32,
        sharding=chip)
    compiled = driver.lower(carry, (batch, batch)).compile()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    text = compiled.as_text()
    names = mosaic_call_names(text)
    print(f"\ntrinity-mini.train-8k: {n_params / 1e6:.1f}M parameters; per "
          f"device arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls {len(names)}")
    assert total < 16 * 2 ** 30
    assert not unnamed_mosaic_calls(text)
    for kernel in ("apex_gmm", "apex_gmm_dw", "apex_flash_fwd",
                   "apex_flash_bwd", "apex_xent_fwd"):
        assert any(kernel in n for n in names), kernel
