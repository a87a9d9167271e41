"""The ``kimi_linear`` family and the ``kimi-linear.train-8k`` cell, rehearsed
on the CPU: the cell's files through ``harness.load_cell``, the configuration
against the catalog row, a tiny cell of the family through the harness (new
files and entries alone), the family's operations worked out by hand, the four
new readers on a small recorded trace, and the cell's window compiled at its
REAL size for a described ``v5e:2x2`` (arguments + temporaries in GiB against
the chip's 15.75 and its Mosaic calls by name and count: the fit, before any
chip time; slow, minutes).

    python -m pytest benchmark/tests/test_kimi_linear_cell.py -s
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
REAL_CELL = "kimi-linear.train-8k"
CELL = "kimi-linear-tiny.train"
KIMI_TINY = {
    "name": "kimi-linear-tiny", "family": "kimi_linear", "hidden_size": 128,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_dense_layers": 1,
    "num_attention_heads": 2, "num_key_value_heads": 2,
    "linear_attn_config": {"kda_layers": [1, 2], "full_attn_layers": [3],
                           "num_heads": 2, "head_dim": 128,
                           "short_conv_kernel_size": 4},
    "qk_nope_head_dim": 96, "qk_rope_head_dim": 32, "v_head_dim": 64,
    "kv_lora_rank": 64, "q_lora_rank": None, "mla_use_nope": True,
    "intermediate_size": 256, "moe_intermediate_size": 128, "num_experts": 4,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_renormalize": True, "routed_scaling_factor": 2.446,
    "rms_norm_eps": 1e-5, "num_expert_group": 1, "topk_group": 1,
    "moe_layer_freq": 1, "moe_router_activation_func": "sigmoid",
    "num_nextn_predict_layers": 0, "tie_word_embeddings": False,
    "vocab_size": 250, "published": {"num_experts": 16},
    "assumed": {"padded_vocab_size": 256, "experts_held": [4, 8],
                "initializer_range": 0.02, "latent_norm_eps": 1e-6,
                "remat_policy": "full_block"},
    "precision": {"opt_level": "O2"},
}
NEW_READERS = ("model.kda_ms_per_step", "model.kda_scan_ms_per_step",
               "kernels.kda_scan_roofline_pct",
               "kernels.flash_mla_nope_roofline_pct")


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_the_issue_says():
    loaded = harness.load_cell(ROOT, REAL_CELL)
    cfg, job, cell = loaded["cfg"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "causal-lm-1x8192-kda"
    assert (job["rows"], job["seq"], job["steps_per_dispatch"]) == (1, 8192, 4)
    assert job["optimizer"] == {"name": "adamw", "lr": 1e-5, "wd": 0.1, "eps": 1e-8}
    assert set(job["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                  "param_delta_leaf_gap"}
    assert cfg["family"] == "kimi_linear" and cfg["num_dense_layers"] == 1
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_READERS) <= reported
    assert {"model.moe_ms_per_step", "kernels.grouped_mm_roofline_pct",
            "kernels.flash_full_ms_per_step", "model.mla_ms_per_step",
            "model.dense_ffn_ms_per_step", "model.mfu"} <= reported
    # that reader multiplies one layer's need by EVERY layer: five times this
    # cell's, which has one latent layer
    assert "kernels.flash_mla_roofline_pct" not in reported
    assert "kernels.gdn_scan_roofline_pct" not in reported
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW_READERS:        # each lists this cell alone
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]


def test_configuration_keeps_the_published_widths():
    cfg = load("configs", "kimi-linear-48b-a3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Kimi-Linear-48B-A3B-Instruct")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items() if cfg.get(k) != v}
    assert differing == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_experts", "vocab_size", "linear_attn_config"}
    assert cfg["published"] == {k: row["config"][k] for k in cfg["reduced"]}
    # inside the one nested group only the two layer lists were cut: the
    # first five published layers, one whole period at 3 : 1
    lin, pub = cfg["linear_attn_config"], row["config"]["linear_attn_config"]
    assert {k for k in pub if lin[k] != pub[k]} == {"kda_layers", "full_attn_layers"}
    assert lin["kda_layers"] == [i for i in pub["kda_layers"] if i <= 5]
    assert lin["full_attn_layers"] == [i for i in pub["full_attn_layers"] if i <= 5]
    assert cfg["assumed"]["experts_held"] == [0, cfg["num_experts"]]
    assert cfg["assumed"]["parameters"] == 602_434_432


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny kimi_linear configuration, its job, its
    cell and the real benchmark's metrics of the real cell retargeted to it."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("tinykimi")))
    with open(f"{root}/benchmark/configs/kimi-linear-tiny.json", "w") as f:
        json.dump(KIMI_TINY, f)
    with open(f"{root}/benchmark/traffic/lm-tiny-1row.json", "w") as f:
        json.dump(tiny.train_mix("causal_lm", tiny.ADAMW, rows=1,
                                 reference_rows_per_block=1), f)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "kimi-linear-tiny", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/kimi-linear-tiny.json"})
    bench["workloads"].append({"name": CELL, "config": "kimi-linear-tiny",
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
    # the control: AMP O3 (no float32 masters) in the program's place
    path = f"{root}/benchmark/configs/kimi-linear-tiny.json"
    with open(path, "w") as f:
        json.dump({**KIMI_TINY, "precision": {"opt_level": "O3"}}, f)
    try:
        rc, line, lines = tiny.run_cell(root, CELL, capsys)
    finally:
        with open(path, "w") as f:
            json.dump(KIMI_TINY, f)
    assert rc == 0
    assert checked(lines, "param_delta_leaf_gap") > 3 * sound, "\n".join(lines)


def test_train_flops_by_hand():
    """The share at 8192 tokens, every term written out."""
    cfg = load("configs", "kimi-linear-48b-a3b")
    fam = harness.load_module(ROOT, "families", "kimi_linear")
    d = 2304
    kda_proj = (2 * d * 12288 + 2 * 4096 * d            # q | k | v, o_proj
                + 2 * (2 * d * 128 + 2 * 128 * 4096)    # the two low-rank gates
                + 2 * d * 32)                           # beta
    conv = 2 * 4 * 12288
    rule = 32 * 2 * (5 * 64 * 128 + 64 * 64 + 3 * 128 * 128)
    attn_proj = (2 * d * 32 * 192 + 2 * d * 576 + 2 * 512 * 32 * 256
                 + 2 * 4096 * d)
    attention = 2 * 32 * (192 + 128) * 8193 / 2
    dense = 6 * d * 9216
    expert_layer = (2 * d * 256                         # router over all 256
                    + 6 * d * 1024                      # the shared expert
                    + 8 * 8 / 256 * 6 * d * 1024)       # 0.25 expert expected
    head = 2 * d * 20480
    forward = (4 * (kda_proj + conv + rule) + attn_proj + attention + dense
               + 4 * expert_layer + head)
    assert fam.train_flops_per_token(cfg, 8192) == pytest.approx(3 * forward, rel=1e-12)
    assert 779e6 < forward < 780e6
    # the rule is counted as the scalar rule's family counts its own
    qwen = harness.load_module(ROOT, "families", "qwen3_next")
    assert rule == qwen.gdn_rule_flops_per_token(load("configs", "qwen3-next-80b-a3b"))
    # the kernels' needs: 256 rows an expert, weights-bound; the rule; flash
    gmm = fam.grouped_mm_needed(cfg, 8192)
    assert len(gmm) == 6 and gmm[0][0] == 2 * 256 * 8 * d * 2048
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    assert all(nbytes / peaks["hbm_bytes_per_s"] > flops / peaks["bf16_flops_per_s"]
               for flops, nbytes in gmm)
    fwd, bwd = fam.kda_needed(cfg, 8192, 1)
    assert fwd[0] == 8192 * rule and bwd[0] == 2 * fwd[0]
    # g in float32 at (S, H, d): as many bytes as q and k together
    assert fwd[1] == 8192 * 32 * (2 * 4 * 128 + 4 * 128 + 4) and bwd[1] == 2 * fwd[1]
    # bytes bound the rule: 12 bytes a channel against 5.9 operations a byte
    assert fwd[1] / peaks["hbm_bytes_per_s"] > fwd[0] / peaks["bf16_flops_per_s"]
    flash = fam.flash_needed(cfg, 8192, 1)
    assert flash[0][0] == 8192 * attention


def test_new_readers_on_a_recorded_trace(monkeypatch):
    """KDA's scopes summed, ``kda_scan`` alone, its roofline share and the
    latent layer's flash share found through the cell's shapes; nothing to
    read, no error, on a program without the scopes (the parent)."""
    from benchmark import program_trace, scoped_kernels

    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    step = "jit(window)/while/body/closed_call/"
    scan = "kda_scan/jit(_kda_jit)/"
    ev = lambda name, t0, dur, scope: [
        name, t0, dur, {"op_name": step + f"jvp(KimiLinearLM)/{scope}"}]
    device = [
        ev("%fusion.1 = bf16[8] fusion()", 0, 10, "layer_0/kda/kda_proj/qkv_proj/dot_general"),
        ev("%apex_conv1d_fwd.2 = bf16[8]" + mosaic, 10, 4, "layer_0/kda/kda_conv/pallas_call"),
        ev("%fusion.3 = f32[8] fusion()", 14, 6, "layer_0/kda/kda_gate/mul"),
        ev("%apex_kda_fwd.4 = f32[8]" + mosaic, 20, 30, "layer_0/kda/" + scan + "apex_kda_fwd/pallas_call"),
        ev("%apex_kda_bwd.5 = f32[8]" + mosaic, 50, 60, "layer_0/kda/" + scan + "apex_kda_bwd/pallas_call"),
        ev("%fusion.6 = bf16[8] fusion()", 110, 8, "layer_0/kda/kda_out/o_proj/dot_general"),
        ev("%fusion.7 = bf16[8] fusion()", 118, 5, "layer_3/attn/mla_proj/q_proj/dot_general"),
        ev("%apex_flash_fwd.8 = bf16[8]" + mosaic, 123, 40, "layer_3/attn/attn_full/pallas_call"),
    ]
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
        {"name": "/host:CPU", "lines": [{"name": "t", "events": [
            ["apex/train/dispatch", 0, 5, {"k": 2}]]}]}]}
    monkeypatch.setattr(program_trace, "newest",
                        lambda *a: program_trace.reduce(trace))
    monkeypatch.setattr(scoped_kernels, "newest",
                        lambda *a: scoped_kernels.reduce(trace))
    cfg, job = load("configs", "kimi-linear-48b-a3b"), load("traffic", "causal-lm-1x8192-kda")
    fam = harness.load_module(ROOT, "families", "kimi_linear")
    run = {"kind": "train", "trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "tokens_per_window": 4 * 8192,
           "flops_per_token": fam.train_flops_per_token(cfg, job["seq"])}
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    assert read("model.kda_ms_per_step") == pytest.approx(118e-6 / 2)
    assert read("model.kda_scan_ms_per_step") == pytest.approx(90e-6 / 2)
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    needed_s = 4 * fam.needed_seconds(fam.kda_needed(cfg, 8192, 1), peaks)
    assert read("kernels.kda_scan_roofline_pct") == pytest.approx(
        100 * needed_s / (90e-9 / 2))
    flash_s = 1 * fam.needed_seconds(fam.flash_needed(cfg, 8192, 1), peaks)
    assert read("kernels.flash_mla_nope_roofline_pct") == pytest.approx(
        100 * flash_s / (40e-9 / 2))
    assert read("model.mla_ms_per_step") == pytest.approx(45e-6 / 2)
    # a program without the scopes (the parent): nothing to read, no error
    bare = {"planes": [trace["planes"][1]]}
    monkeypatch.setattr(program_trace, "newest",
                        lambda *a: program_trace.reduce(bare))
    monkeypatch.setattr(scoped_kernels, "newest",
                        lambda *a: scoped_kernels.reduce(bare))
    for name in NEW_READERS:
        assert read(name) is None


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
    cfg, job = load("configs", "kimi-linear-48b-a3b"), load("traffic", "causal-lm-1x8192-kda")
    fam = harness.load_module(ROOT, "families", cfg["family"])
    train = harness.load_module(ROOT, "runners", "train")
    chip = SingleDeviceSharding(topo.devices[0])
    driver, init_carry = train.build_program(
        cfg, job, fam, cfg["precision"]["opt_level"], None)
    rcfg = fam.reference_config(cfg)
    key = jax.random.PRNGKey(0)
    weights = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg), key)
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(weights))
    assert n_params == cfg["assumed"]["parameters"] == 602_434_432
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
    print(f"\nkimi-linear.train-8k: {n_params / 1e6:.1f}M parameters; per "
          f"device arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls {len(names)}: {by_kernel}")
    assert total < 15.75 * 2 ** 30
    assert not unnamed_mosaic_calls(text)
    # four KDA layers: the rule forward ONCE each (its residuals are kept
    # under full_block), the convolution forward twice (recomputed); one
    # latent layer: flash once forward, once backward
    assert by_kernel["apex_kda_fwd"] == by_kernel["apex_kda_bwd"] == 4
    assert (by_kernel["apex_conv1d_fwd"], by_kernel["apex_conv1d_bwd"]) == (8, 4)
    assert by_kernel["apex_flash_fwd"] == 1
    assert sum(v for k, v in by_kernel.items() if "apex_flash_bwd" in k) == 1
    for kernel in ("apex_gmm", "apex_gmm_dw", "apex_moe_gather",
                   "apex_moe_combine", "apex_xent_fwd"):
        assert any(kernel in n for n in names), kernel
    reg = obs.default_registry()
    assert reg.get("moe.dispatch.kernels").value == 1
    assert reg.get("kda.kernels").value == 1
    assert reg.get("kda.conv_kernel").value == 1
    assert reg.get("kda.chunks_per_row").value == 128
