"""The ``qwen3_next`` family and the ``qwen3-next.train-8k`` cell, rehearsed
on the CPU: the cell's files through ``harness.load_cell``, a tiny cell of the
family through the harness (new files and entries alone), the family's
operations worked out by hand, the three new readers on a small recorded
trace, and the cell's window compiled at its REAL size for a described
``v5e:2x2`` (arguments + temporaries in GiB and its Mosaic calls by name: the
fit, before any chip time; slow, minutes).

    python -m pytest benchmark/tests/test_qwen3_next_cell.py -s
"""
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from benchmark import harness
from benchmark.tests import tiny

ROOT = tiny.REAL
REAL_CELL = "qwen3-next.train-8k"
CELL = "qwen3-next-tiny.train"
QWEN_TINY = {
    "name": "qwen3-next-tiny", "family": "qwen3_next", "hidden_size": 128,
    "num_hidden_layers": 4, "full_attention_interval": 4,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 64,
    "partial_rotary_factor": 0.25, "rope_theta": 10000000,
    "linear_num_key_heads": 1, "linear_num_value_heads": 2,
    "linear_key_head_dim": 128, "linear_value_head_dim": 128,
    "linear_conv_kernel_dim": 4, "moe_intermediate_size": 128,
    "shared_expert_intermediate_size": 128, "num_experts": 4,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "rms_norm_eps": 1e-6,
    "vocab_size": 250, "mlp_only_layers": [], "decoder_sparse_step": 1,
    "num_dense_layers": 0, "published": {"num_experts": 16},
    "assumed": {"padded_vocab_size": 256, "experts_held": [4, 8],
                "initializer_range": 0.02, "remat_policy": "full_block"},
    "precision": {"opt_level": "O2"},
}
NEW_READERS = ("model.gdn_ms_per_step", "model.gdn_scan_ms_per_step",
               "kernels.gdn_scan_roofline_pct")


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_the_issue_says():
    loaded = harness.load_cell(ROOT, REAL_CELL)
    cfg, job, cell = loaded["cfg"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "causal-lm-1x8192-gdn"
    assert (job["rows"], job["seq"], job["steps_per_dispatch"]) == (1, 8192, 4)
    assert job["optimizer"] == {"name": "adamw", "lr": 3e-4, "wd": 0.1, "eps": 1e-8}
    assert set(job["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                  "param_delta_leaf_gap"}
    assert cfg["family"] == "qwen3_next" and cfg["num_dense_layers"] == 0
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_READERS) <= reported
    assert {"model.moe_ms_per_step", "kernels.grouped_mm_roofline_pct",
            "kernels.flash_full_ms_per_step", "model.mfu"} <= reported
    assert "kernels.layer_norm_ms_per_step" not in reported
    assert "kernels.flash_window_ms_per_step" not in reported
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW_READERS:        # each lists this cell alone
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]


def test_configuration_keeps_the_published_widths():
    cfg = load("configs", "qwen3-next-80b-a3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differing == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["assumed"]["experts_held"] == [0, cfg["num_experts"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny qwen3_next configuration, its job, its
    cell and the real benchmark's metrics of the real cell retargeted to it."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("tinyqwen")))
    with open(f"{root}/benchmark/configs/qwen3-next-tiny.json", "w") as f:
        json.dump(QWEN_TINY, f)
    with open(f"{root}/benchmark/traffic/lm-tiny-1row.json", "w") as f:
        json.dump(tiny.train_mix("causal_lm", tiny.ADAMW, rows=1,
                                 reference_rows_per_block=1), f)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "qwen3-next-tiny", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/qwen3-next-tiny.json"})
    bench["workloads"].append({"name": CELL, "config": "qwen3-next-tiny",
                               "traffic": "lm-tiny-1row", "chips": 1,
                               "why": "tiny"})
    mine = {m["name"] for m in real["per_layer"] + real["end_to_end"]
            if REAL_CELL in m.get("workloads", [REAL_CELL])}
    names = {m["name"] for m in bench["per_layer"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m and m["name"] in mine:
            m["workloads"].append(CELL)
    bench["per_layer"] += [
        {**m, "workloads": [CELL]} for m in real["per_layer"]
        if m["name"] not in names and m["name"] in mine]
    with open(f"{root}/BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root


def checked(lines, name):
    """The value the run printed for the check ``name``."""
    line = next(l for l in lines if f"check {name}:" in l)
    return float(line.split(f"check {name}:")[1].split()[0])


def test_tiny_cell_is_correct_and_lower_precision_stands_apart(root, capsys):
    rc, line, lines = tiny.run_cell(root, CELL, capsys)
    assert rc == 0 and line["correct"] is True, "\n".join(lines)
    assert line["failed"] == 0 and line["attempted"] > 0
    assert {"train_tokens_per_s", "setup_s"} <= set(line["metrics"])
    sound = checked(lines, "param_delta_leaf_gap")
    # the control: AMP O3 (no float32 masters) in the program's place.  The
    # delta net's w_norm and dt_bias stand at 1.0 and cannot take a step of
    # 6e-4 in bfloat16 (the block norms, zero-centred, can)
    path = f"{root}/benchmark/configs/qwen3-next-tiny.json"
    with open(path, "w") as f:
        json.dump({**QWEN_TINY, "precision": {"opt_level": "O3"}}, f)
    try:
        rc, line, lines = tiny.run_cell(root, CELL, capsys)
    finally:
        with open(path, "w") as f:
            json.dump(QWEN_TINY, f)
    assert rc == 0
    assert checked(lines, "param_delta_leaf_gap") > 3 * sound, "\n".join(lines)


def test_train_flops_by_hand():
    """The share at 8192 tokens, every term written out (ISSUE 30's
    arithmetic: 469.5 MFLOP a token forward, 1.408 GFLOP to train)."""
    cfg = load("configs", "qwen3-next-80b-a3b")
    fam = harness.load_module(ROOT, "families", "qwen3_next")
    gdn_proj = 2 * 2048 * 12288 + 2 * 2048 * 64 + 2 * 4096 * 2048
    conv = 2 * 4 * 8192
    rule = 32 * 2 * (5 * 64 * 128 + 64 * 64 + 3 * 128 * 128)
    attn_proj = 2 * 2048 * (8192 + 512 + 512) + 2 * 4096 * 2048
    attention = 4 * 16 * 256 * 8193 / 2
    expert_layer = (2 * 2048 * 512                       # router over all 512
                    + 6 * 2048 * 512 + 2 * 2048          # the gated shared expert
                    + 10 * 32 / 512 * 6 * 2048 * 512)    # 0.625 expert expected
    head = 2 * 2048 * 19072
    forward = (3 * (gdn_proj + conv + rule) + attn_proj + attention
               + 4 * expert_layer + head)
    assert fam.train_flops_per_token(cfg, 8192) == pytest.approx(3 * forward, rel=1e-12)
    assert 469.4e6 < forward < 469.6e6
    assert rule / 32 == 188416
    parts = fam.forward_flops_per_token(cfg, 8192)
    assert parts["gdn_proj"] + parts["gdn_conv"] + parts["gdn_rule"] > parts["attention"] + parts["attn_proj"]
    # the kernels' needs: 160 rows an expert, weights-bound; the rule
    gmm = fam.grouped_mm_needed(cfg, 8192)
    assert len(gmm) == 6 and gmm[0][0] == 2 * 160 * 32 * 2048 * 1024
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    assert all(nbytes / peaks["hbm_bytes_per_s"] > flops / peaks["bf16_flops_per_s"]
               for flops, nbytes in gmm)
    fwd, bwd = fam.gdn_needed(cfg, 8192, 1)
    assert fwd[0] == 8192 * rule and bwd[0] == 2 * fwd[0]
    assert 3 * (fwd[0] + bwd[0]) == pytest.approx(444.5e9, rel=1e-3)
    assert fwd[1] == 8192 * 32 * (2 * 4 * 128 + 8)


def test_new_readers_on_a_recorded_trace(monkeypatch):
    """The delta net's scopes summed, ``gdn_scan`` alone, and its roofline
    share found through the cell's shapes; nothing to read, no error, on a
    program without the scopes (the parent)."""
    from benchmark import program_trace

    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    step = "jit(window)/while/body/closed_call/"
    scan = "gdn_scan/jit(_rule_jit)/"
    ev = lambda name, t0, dur, scope: [
        name, t0, dur,
        {"op_name": step + f"jvp(Qwen3NextLM)/layer_0/gdn/{scope}"}]
    device = [
        ev("%fusion.1 = bf16[8] fusion()", 0, 10, "gdn_proj/in_proj_qkvz/dot_general"),
        ev("%fusion.2 = bf16[8] fusion()", 10, 4, "gdn_conv/mul"),
        ev("%apex_gdn_fwd.3 = f32[8]" + mosaic, 14, 30, scan + "apex_gdn_fwd/pallas_call"),
        ev("%fusion.4 = f32[8] fusion()", 44, 6, scan + "dot_general"),
        ev("%apex_gdn_bwd.5 = f32[8]" + mosaic, 50, 60, scan + "apex_gdn_bwd/pallas_call"),
        ev("%fusion.6 = bf16[8] fusion()", 110, 8, "gdn_out/out_proj/dot_general"),
        ["%fusion.7 = f32[8] fusion()", 118, 5,
         {"op_name": step + "jvp(Qwen3NextLM)/layer_0/moe/moe_router/dot_general"}],
    ]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["apex/train/dispatch", 0, 5, {"k": 2}]]}]}]}
    monkeypatch.setattr(program_trace, "newest",
                        lambda *a: program_trace.reduce(trace))
    cfg, job = load("configs", "qwen3-next-80b-a3b"), load("traffic", "causal-lm-1x8192-gdn")
    fam = harness.load_module(ROOT, "families", "qwen3_next")
    run = {"kind": "train", "trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "tokens_per_window": 4 * 8192,
           "flops_per_token": fam.train_flops_per_token(cfg, job["seq"])}
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    assert read("model.gdn_ms_per_step") == pytest.approx(118e-6 / 2)
    assert read("model.gdn_scan_ms_per_step") == pytest.approx(96e-6 / 2)
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    needed_s = 3 * fam.needed_seconds(fam.gdn_needed(cfg, 8192, 1), peaks)
    assert 2e-3 < needed_s < 3.5e-3
    assert read("kernels.gdn_scan_roofline_pct") == pytest.approx(
        100 * needed_s / (96e-9 / 2))
    assert read("model.moe_ms_per_step") == pytest.approx(5e-6 / 2)
    # a program without the scopes (the parent): nothing to read, no error
    bare = {"planes": [trace["planes"][1]]}
    monkeypatch.setattr(program_trace, "newest",
                        lambda *a: program_trace.reduce(bare))
    for name in NEW_READERS:
        assert read(name) is None
    # another cell's record (Trinity's operations a token): not this cell
    other = {**run, "flops_per_token": 1.0}
    monkeypatch.setattr(program_trace, "newest",
                        lambda *a: program_trace.reduce(trace))
    assert harness.load_module(
        ROOT, "layer_metrics", "kernels.gdn_scan_roofline_pct").read(other) is None


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
    from apex_tpu import obs
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, job = load("configs", "qwen3-next-80b-a3b"), load("traffic", "causal-lm-1x8192-gdn")
    fam = harness.load_module(ROOT, "families", cfg["family"])
    train = harness.load_module(ROOT, "runners", "train")
    chip = SingleDeviceSharding(topo.devices[0])
    driver, init_carry = train.build_program(
        cfg, job, fam, cfg["precision"]["opt_level"], None)
    rcfg = fam.reference_config(cfg)
    key = jax.random.PRNGKey(0)
    weights = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg), key)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(weights))
    assert n_params == 625_994_816
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
    by_kernel = {}
    for n in names:
        base = re.sub(r"\.\d+$", "", n)
        by_kernel[base] = by_kernel.get(base, 0) + 1
    print(f"\nqwen3-next.train-8k: {n_params / 1e6:.1f}M parameters; per "
          f"device arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls {len(names)}: {by_kernel}")
    assert total < 16 * 2 ** 30
    assert not unnamed_mosaic_calls(text)
    for kernel in ("apex_gdn_fwd", "apex_gdn_bwd", "apex_gmm", "apex_gmm_dw",
                   "apex_moe_gather", "apex_moe_combine", "apex_flash_fwd",
                   "apex_flash_bwd", "apex_xent_fwd"):
        assert any(kernel in n for n in names), kernel
    reg = obs.default_registry()
    assert reg.get("moe.dispatch.kernels").value == 1
    assert reg.get("moe.dispatch.rows_capacity").value == 90112
    assert reg.get("moe.dispatch.slots").value == 81920
    assert reg.get("gdn.kernels").value == 1
    assert reg.get("gdn.chunks_per_row").value == 128
