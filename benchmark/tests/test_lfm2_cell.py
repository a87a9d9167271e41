"""The ``lfm2_moe`` family and the ``lfm2.train-16k`` cell, rehearsed on the
CPU: the cell's files through ``harness.load_cell``, the configuration against
the catalog, the family's parameter count and operations worked out by hand,
the gated convolution's needed bytes by hand, a tiny cell of the family through
the harness (new files and entries alone), the two new readers on small
recorded traces (one of a kernel run, one of the ``jax.numpy`` path: the same
needed work), and the cell's window and its reference's step compiled at their
REAL size for a described ``v5e:2x2`` (arguments + temporaries in GiB and the
Mosaic calls by name: the fit, before any chip time; slow, minutes).

    python -m pytest benchmark/tests/test_lfm2_cell.py -s
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
REAL_CELL = "lfm2.train-16k"
CONFIG, TRAFFIC = "lfm2-24b-a2b", "causal-lm-1x16384-conv"
CELL = "lfm2-tiny.train"
LFM2_TINY = {
    "name": "lfm2-tiny", "family": "lfm2_moe", "hidden_size": 128,
    "num_hidden_layers": 3, "layer_types": ["conv", "full_attention", "conv"],
    "num_dense_layers": 1, "conv_L_cache": 3, "conv_bias": False,
    "num_attention_heads": 2, "num_key_value_heads": 1,
    "intermediate_size": 256, "moe_intermediate_size": 128, "num_experts": 4,
    "num_experts_per_tok": 4, "norm_topk_prob": True, "norm_eps": 1e-5,
    "routed_scaling_factor": 1, "use_expert_bias": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 250,
    "published": {"num_experts": 16},
    "assumed": {"padded_vocab_size": 256, "experts_held": [4, 8],
                "tie_word_embeddings": True, "route_norm_eps": 1e-20,
                "initializer_range": 0.02, "remat_policy": "full_block"},
    "precision": {"opt_level": "O2"},
}
NEW_READERS = ("model.conv_op_ms_per_step", "kernels.gated_conv_roofline_pct")
N_PARAMS = 469_285_248
KINDS = ["conv", "full_attention", "conv", "conv", "conv"]


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_the_issue_says():
    loaded = harness.load_cell(ROOT, REAL_CELL)
    cfg, job, cell = loaded["cfg"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert (job["rows"], job["seq"], job["steps_per_dispatch"]) == (1, 16384, 4)
    assert job["seq"] <= cfg["max_position_embeddings"] == 128000
    assert job["optimizer"] == {"name": "adamw", "lr": 1e-5, "wd": 0.1, "eps": 1e-8}
    assert set(job["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                  "param_delta_leaf_gap"}
    assert "PR 23" not in job["limits_from"] and "PLACEHOLDER" not in job["limits_from"]
    assert cfg["family"] == "lfm2_moe"
    assert cfg["layer_types"] == KINDS and cfg["num_dense_layers"] == 1
    assert (cfg["num_experts_per_tok"], cfg["num_experts"],
            cfg["published"]["num_experts"]) == (4, 8, 64)
    assert cfg["vocab_size"] == cfg["assumed"]["padded_vocab_size"] == 8192
    assert cfg["assumed"]["tie_word_embeddings"] is True
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_READERS) <= reported
    assert {"model.moe_ms_per_step", "model.moe_dispatch_ms_per_step",
            "model.moe_router_ms_per_step", "kernels.grouped_mm_ms_per_step",
            "kernels.grouped_mm_roofline_pct", "kernels.flash_full_ms_per_step",
            "model.mfu", "ops.flash_tiles_visited_share.train"} <= reported
    # the full layers' roofline reader counts every layer layer_types does not
    # call windowed, and would count the conv layers: not this cell's
    assert not [m for m in reported if "gdn" in m or "mla" in m
                or "flash_window" in m or "flash_full_roofline" in m]
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW_READERS:        # each lists this cell alone
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]
    # what PR 39 adds stands AFTER what was there in the lists
    names = lambda key: [x["name"] for x in bench[key]]
    assert names("configs").index(CONFIG) > names("configs").index("smallthinker-21ba3b")
    assert names("workloads").index(REAL_CELL) > names("workloads").index("smallthinker.train-16k")
    for name in NEW_READERS:
        assert names("per_layer").index(name) > names("per_layer").index(
            "kernels.flash_full_roofline_pct")
    for m in bench["per_layer"] + bench["end_to_end"]:
        cells = m.get("workloads", [])
        if REAL_CELL in cells and "smallthinker.train-16k" in cells:
            assert cells.index(REAL_CELL) > cells.index("smallthinker.train-16k")
    assert all(c["chips"] == 1 for c in bench["workloads"])


def test_configuration_keeps_the_published_widths():
    cfg = load("configs", CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f) if r["name"] == "LFM2-24B-A2B")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items()
                 if k not in cfg or cfg[k] != v}
    assert differing == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size"}
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["assumed"]["experts_held"] == [0, cfg["num_experts"]]
    # the widths the issue names
    assert (cfg["hidden_size"], cfg["conv_L_cache"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["moe_intermediate_size"],
            cfg["intermediate_size"], cfg["norm_eps"],
            cfg["rope_parameters"]["rope_theta"], cfg["routed_scaling_factor"],
            cfg["use_expert_bias"]) == (2048, 3, 32, 8, 1536, 11776, 1e-5,
                                        1000000, 1, True)
    # the floors: a whole period (published layers 2-5, in their order) of
    # four layers past the dense one, 8 experts, 1/8 vocab
    dense = cfg["num_dense_layers"]
    assert cfg["layer_types"][dense:] == row["config"]["layer_types"][2:6]
    assert cfg["layer_types"][:dense] == row["config"]["layer_types"][:1]
    assert cfg["num_hidden_layers"] - dense >= 4
    assert cfg["num_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0
    # three conv layers to one attention layer among the expert layers: the
    # published ratio (30 to 10)
    assert cfg["layer_types"][dense:].count("conv") == 3


def test_parameter_count_by_the_familys_own_count():
    """469,285,248: the dense conv layer, three expert conv layers and the
    expert attention layer of 8 held experts each, an eighth of the
    embedding — which is the head — by the shapes the reference makes, and
    again by hand."""
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", "lfm2_moe")
    rcfg = fam.reference_config(cfg)
    shapes = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in shapes.values()) == N_PARAMS
    assert "head" not in shapes
    d = 2048
    conv_mixer = d * 3 * d + d * d + d * 3
    attn_mixer = d * (32 + 8 + 8) * 64 + 32 * 64 * d + 2 * 64
    assert (conv_mixer, attn_mixer) == (16_783_360, 10_485_888)
    expert, dense_ff = 3 * d * 1536, 3 * d * 11776
    assert (expert, dense_ff) == (9_437_184, 72_351_744)
    beside = d * 64 + 64 + 2 * d            # router, bias, the two norms
    dense_layer = conv_mixer + dense_ff + 2 * d
    conv_layer = 8 * expert + conv_mixer + beside
    attn_layer = 8 * expert + attn_mixer + beside
    assert (dense_layer, conv_layer, attn_layer) == (
        89_139_200, 92_416_064, 86_118_592)
    assert dense_layer + 3 * conv_layer + attn_layer + 8192 * d + d == N_PARAMS
    # the program holds the same numbers in its own tree
    pcfg = fam.program_config(cfg, jnp.bfloat16)
    tree = jax.eval_shape(lambda w: fam.to_program(w, cfg), shapes)
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == N_PARAMS
    assert (pcfg.num_heads, pcfg.num_kv_heads, pcfg.head_dim) == (32, 8, 64)
    assert pcfg.experts_held == (0, 8) and pcfg.num_experts == 64
    assert pcfg.layer_types == tuple(KINDS) and pcfg.conv_L_cache == 3
    assert pcfg.remat_policy == "full_block"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny lfm2_moe configuration, its job, its
    cell and the real benchmark's metrics of the real cell retargeted to it."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("tinylfm2")))
    with open(f"{root}/benchmark/configs/lfm2-tiny.json", "w") as f:
        json.dump(LFM2_TINY, f)
    with open(f"{root}/benchmark/traffic/lm-tiny-1row.json", "w") as f:
        json.dump(tiny.train_mix("causal_lm", tiny.ADAMW, rows=1,
                                 reference_rows_per_block=1), f)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "lfm2-tiny", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/lfm2-tiny.json"})
    bench["workloads"].append({"name": CELL, "config": "lfm2-tiny",
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
    # norm scales stand at 1.0 and cannot take a small step in bfloat16
    path = f"{root}/benchmark/configs/lfm2-tiny.json"
    with open(path, "w") as f:
        json.dump({**LFM2_TINY, "precision": {"opt_level": "O3"}}, f)
    try:
        rc, line, lines = tiny.run_cell(root, CELL, capsys)
    finally:
        with open(path, "w") as f:
            json.dump(LFM2_TINY, f)
    assert rc == 0
    assert checked(lines, "param_delta_leaf_gap") > 3 * sound, "\n".join(lines)


def test_train_flops_and_kernels_needs_by_hand():
    """The share at 16,384 tokens, every term written out (ISSUE 39's
    arithmetic: 439.4 MFLOP a token forward, 1.318 GFLOP to train), the gated
    convolution's bytes — forward ``4 S d`` elements, backward ``7 S d`` —
    and its 0.33 + 0.57 ms a layer at the HBM peak, and the flash call's need
    at a head of 64."""
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", "lfm2_moe")
    d, seq = 2048, 16384
    conv_mixer = 2 * d * 3 * d + 2 * d * d + 7 * d      # W_in, W_out, 2K+1 a channel
    attn_proj = 2 * d * 3072 + 2 * 2048 * d
    attention = 4 * 32 * 64 * (seq + 1) / 2
    dense = 6 * d * 11776
    experts = 4 * 8 / 64 * 6 * d * 1536                 # half an expert expected
    router = 2 * d * 64
    head = 2 * d * 8192
    forward = (4 * conv_mixer + attn_proj + attention + dense
               + 4 * (experts + router) + head)
    assert fam.train_flops_per_token(cfg, seq) == pytest.approx(3 * forward, rel=1e-12)
    assert 439.3e6 < forward < 439.5e6 and 1.3175e9 < 3 * forward < 1.3185e9
    parts = fam.forward_flops_per_token(cfg, seq)
    assert parts["conv_mixer"] == pytest.approx(134.3e6, rel=1e-3)
    assert parts["dense_mlp"] == pytest.approx(144.7e6, rel=1e-3)
    assert parts["attention"] == pytest.approx(67.1e6, rel=1e-3)
    assert parts["attn_proj"] == pytest.approx(21.0e6, rel=2e-3)
    assert parts["routed"] == pytest.approx(37.7e6, rel=2e-3)
    assert parts["router"] == pytest.approx(1.05e6, rel=2e-3)
    assert parts["head"] == pytest.approx(33.6e6, rel=2e-3)
    assert 0.30 < parts["conv_mixer"] / forward < 0.32      # 31%
    assert 0.15 < parts["attention"] / forward < 0.16       # 15%

    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    fwd, bwd = fam.gated_conv_needed(cfg, seq, 1)
    assert fwd[1] == 4 * seq * d * 2 + 3 * d * 4
    assert bwd[1] == 7 * seq * d * 2 + 2 * 3 * d * 4
    assert fwd[0] == 7 * seq * d and bwd[0] == 20 * seq * d
    # memory-bound by three orders: the operations never bind
    assert all(n / peaks["hbm_bytes_per_s"] > 100 * f / peaks["bf16_flops_per_s"]
               for f, n in (fwd, bwd))
    ms = lambda part: 1e3 * fam.needed_seconds([part], peaks)
    assert ms(fwd) == pytest.approx(0.328, rel=5e-3)
    assert ms(bwd) == pytest.approx(0.574, rel=5e-3)
    assert 4 * (ms(fwd) + ms(bwd)) == pytest.approx(3.61, rel=5e-3)   # a step
    # a share over 100 would be a wrong count: at the floor the reader reads 100
    assert 4 * (2 * ms(fwd) + ms(bwd)) == pytest.approx(4.92, rel=5e-3)

    f_fwd, f_bwd = fam.flash_needed(cfg, seq, 1)
    assert f_fwd[0] == seq * 4 * 32 * 64 * (seq + 1) / 2 and f_bwd[0] == 2 * f_fwd[0]
    assert f_fwd[1] == 2 * (2 * 32 * seq * 64 + 2 * 8 * seq * 64)
    gmm = fam.grouped_mm_needed(cfg, seq)
    assert len(gmm) == 6 and gmm[0][0] == 2 * 1024 * 8 * 2048 * 3072
    assert gmm[3][0] == 2 * 1024 * 8 * 1536 * 2048


def test_new_readers_on_recorded_traces(monkeypatch):
    """The mixer's three scopes summed and the gated convolution's roofline
    share found through the cell's shapes — from a trace of the kernels and
    from one of the ``jax.numpy`` path (XLA's fusions under ``conv_mix``):
    the same needed work over whatever ran there; nothing to read, no error,
    on a program without the scopes (the parent) — and the delta net's
    ``gdn_conv`` is not this metric's to read."""
    from benchmark import program_trace

    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    step = "jit(window)/while/body/closed_call/"
    ev = lambda name, t0, dur, scope, wrap="jvp(Lfm2LM)": [
        name, t0, dur, {"op_name": step + f"{wrap}/layer_2/conv/{scope}"}]
    mix = "conv_mix/jit(_jit)/"
    bwd = "transpose(jvp(Lfm2LM))"
    kernel_run = [
        ev("%fusion.1 = bf16[8] fusion()", 0, 20, "conv_proj/in_proj/dot_general"),
        ev("%apex_gated_conv_fwd.2 = bf16[8]" + mosaic, 20, 8,
           mix + "apex_gated_conv_fwd/pallas_call"),
        ev("%fusion.3 = bf16[8] fusion()", 28, 10, "conv_out/out_proj/dot_general"),
        ev("%apex_gated_conv_bwd.4 = bf16[8]" + mosaic, 38, 14,
           mix + "apex_gated_conv_bwd/pallas_call", bwd),
        ev("%fusion.5 = f32[8] fusion()", 52, 2, mix + "transpose", bwd),
        ["%fusion.6 = f32[8] fusion()", 54, 6,
         {"op_name": step + "jvp(Lfm2LM)/layer_2/moe/moe_router/dot_general"}],
    ]
    jnp_run = [
        ev("%fusion.1 = bf16[8] fusion()", 0, 20, "conv_proj/in_proj/dot_general"),
        ev("%fusion.2 = f32[8] fusion()", 20, 30, mix + "pad"),
        ev("%fusion.3 = f32[8] fusion()", 50, 40, mix + "mul"),
        ev("%fusion.4 = bf16[8] fusion()", 90, 10, "conv_out/out_proj/dot_general"),
        ev("%fusion.5 = f32[8] fusion()", 100, 50, mix + "mul", bwd),
    ]
    host = {"name": "/host:CPU", "lines": [{"name": "t", "events": [
        ["apex/train/dispatch", 0, 5, {"k": 2}]]}]}
    trace_of = lambda device: {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
        host]}

    def use(t):
        monkeypatch.setattr(program_trace, "newest",
                            lambda *a: program_trace.reduce(t))

    cfg, job = load("configs", CONFIG), load("traffic", TRAFFIC)
    fam = harness.load_module(ROOT, "families", "lfm2_moe")
    run = {"kind": "train", "trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "tokens_per_window": 4 * 16384,
           "flops_per_token": fam.train_flops_per_token(cfg, job["seq"])}
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    needed_s = 4 * fam.needed_seconds(fam.gated_conv_needed(cfg, 16384, 1), peaks)
    assert needed_s == pytest.approx(3.61e-3, rel=5e-3)

    use(trace_of(kernel_run))
    assert read("model.conv_op_ms_per_step") == pytest.approx(54e-6 / 2)
    assert read("kernels.gated_conv_roofline_pct") == pytest.approx(
        100 * needed_s / (24e-9 / 2))
    assert read("model.moe_router_ms_per_step") == pytest.approx(6e-6 / 2)
    use(trace_of(jnp_run))          # the same needed work over XLA's passes
    assert read("model.conv_op_ms_per_step") == pytest.approx(150e-6 / 2)
    assert read("kernels.gated_conv_roofline_pct") == pytest.approx(
        100 * needed_s / (120e-9 / 2))
    # a program without the scopes (the parent): nothing to read, no error
    use({"planes": [host]})
    for name in NEW_READERS:
        assert read(name) is None
    # the delta net's convolution is another scope
    other = [[e[0], e[1], e[2], {"op_name": e[3]["op_name"].replace(
        "conv_mix", "gdn_conv").replace("conv_proj", "gdn_proj").replace(
            "conv_out", "gdn_out")}] for e in kernel_run]
    use(trace_of(other))
    for name in NEW_READERS:
        assert read(name) is None
    # another cell's record (its own operations a token): not this cell
    use(trace_of(kernel_run))
    assert harness.load_module(
        ROOT, "layer_metrics", "kernels.gated_conv_roofline_pct").read(
            {**run, "flops_per_token": 1.0}) is None


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


def _shapes_on(chip, tree):
    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip), tree)


def test_train_window_compiles_at_real_size(topo, no_compile_cache, monkeypatch):
    from apex_tpu import obs
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg, job = load("configs", CONFIG), load("traffic", TRAFFIC)
    fam = harness.load_module(ROOT, "families", cfg["family"])
    train = harness.load_module(ROOT, "runners", "train")
    chip = SingleDeviceSharding(topo.devices[0])
    driver, init_carry = train.build_program(
        cfg, job, fam, cfg["precision"]["opt_level"], None)
    rcfg = fam.reference_config(cfg)
    key = jax.random.PRNGKey(0)
    weights = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg), key)
    assert sum(x.size for x in jax.tree_util.tree_leaves(weights)) == N_PARAMS
    carry = _shapes_on(chip, jax.eval_shape(init_carry, weights, key))
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
    print(f"\nlfm2.train-16k: {N_PARAMS / 1e6:.1f}M parameters; per "
          f"device arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls {len(names)}: {by_kernel}")
    assert total < 16 * 2 ** 30
    assert not unnamed_mosaic_calls(text)
    # four conv layers: the gated convolution forward, again in the
    # recomputed block, and backward — nothing of it is kept
    assert by_kernel["apex_gated_conv_fwd"] == 8
    assert by_kernel["apex_gated_conv_bwd"] == 4
    # one attention layer: one forward (its output is kept under per-block
    # recomputation), one sweep backward
    assert by_kernel["apex_flash_fwd"] == 1
    assert by_kernel["apex_flash_bwd_sweep"] == 1
    for kernel in ("apex_gmm", "apex_gmm_dw", "apex_moe_gather",
                   "apex_moe_combine", "apex_xent_fwd"):
        assert any(kernel in n for n in names), kernel
    # the flash call at the head's own 64: no operand padded to a lane tile
    for line in text.splitlines():
        if "tpu_custom_call" in line and "apex_flash" in line and " = " in line:
            assert set(re.findall(r"bf16\[(?:32|8),16384,(\d+)\]", line)) == {"64"}, line[:300]
    reg = obs.default_registry()
    assert reg.get("gated_conv.kernel").value == 1
    assert reg.get("moe.dispatch.kernels").value == 1
    assert reg.get("moe.dispatch.slots").value == 4 * 16384
    assert reg.get("moe.experts_held").value == 8


def test_reference_step_fits_beside_four_float32_copies(topo, no_compile_cache):
    """The plain reference's gradient of one 16,384-token row, compiled for
    the described chip: its temporaries beside the weights, the summed
    gradient and both moments (four float32 copies, 6.99 GiB) have to stay
    under the chip's 15.75 GiB."""
    import functools

    cfg, job = load("configs", CONFIG), load("traffic", TRAFFIC)
    fam = harness.load_module(ROOT, "families", cfg["family"])
    rcfg = fam.reference_config(cfg)
    chip = SingleDeviceSharding(topo.devices[0])
    weights = _shapes_on(chip, jax.eval_shape(
        lambda k: fam.reference.init_params(k, rcfg), jax.random.PRNGKey(0)))
    row = jax.ShapeDtypeStruct((1, job["seq"]), jnp.int32, sharding=chip)
    scale = jax.ShapeDtypeStruct((1,), jnp.float32, sharding=chip)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def block_grad(p, acc, batch, weight):      # reference/train.py's
        value, g = jax.value_and_grad(lambda q: jnp.sum(
            weight * fam.reference.loss_rows(q, batch, rcfg)))(p)
        return value, jax.tree_util.tree_map(jnp.add, acc, g)

    mem = block_grad.lower(weights, weights, (row, row), scale).compile(
        ).memory_analysis()
    copies = 4 * 4 * N_PARAMS
    print(f"\nreference step: temporaries {mem.temp_size_in_bytes / 2**30:.2f} "
          f"GiB beside four float32 copies {copies / 2**30:.2f} GiB")
    assert copies + mem.temp_size_in_bytes < 15.75 * 2 ** 30
