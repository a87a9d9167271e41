"""The ``deepseek_v3`` family and the ``moonlight.train-8k`` cell, rehearsed on
the CPU: the cell's files through ``harness.load_cell``, the configuration
against the catalog, the family's parameter count and operations worked out by
hand, a tiny cell of the family through the harness (new files and entries
alone), the three new readers on a small recorded trace, and the cell's window
and its reference's step compiled at their REAL size for a described
``v5e:2x2`` (arguments + temporaries in GiB and the Mosaic calls by name: the
fit, before any chip time; slow, minutes).

    python -m pytest benchmark/tests/test_moonlight_cell.py -s
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
REAL_CELL = "moonlight.train-8k"
CELL = "moonlight-tiny.train"
MOON_TINY = {
    "name": "moonlight-tiny", "family": "deepseek_v3", "hidden_size": 128,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "num_dense_layers": 1,
    "num_attention_heads": 4, "qk_nope_head_dim": 96, "qk_rope_head_dim": 32,
    "v_head_dim": 64, "kv_lora_rank": 64, "q_lora_rank": None,
    "rope_theta": 50000, "intermediate_size": 256,
    "moe_intermediate_size": 128, "n_routed_experts": 4,
    "num_experts_per_tok": 4, "n_shared_experts": 2, "norm_topk_prob": True,
    "routed_scaling_factor": 2.446, "rms_norm_eps": 1e-5, "n_group": 1,
    "topk_group": 1, "moe_layer_freq": 1, "scoring_func": "sigmoid",
    "topk_method": "noaux_tc", "num_nextn_predict_layers": 0,
    "tie_word_embeddings": False, "vocab_size": 250,
    "published": {"n_routed_experts": 16},
    "assumed": {"padded_vocab_size": 256, "experts_held": [4, 8],
                "initializer_range": 0.02, "latent_norm_eps": 1e-6,
                "remat_policy": "full_block"},
    "precision": {"opt_level": "O2"},
}
NEW_READERS = ("model.mla_ms_per_step", "model.mla_proj_ms_per_step",
               "kernels.flash_mla_roofline_pct")
N_PARAMS = 668_890_432


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_the_issue_says():
    loaded = harness.load_cell(ROOT, REAL_CELL)
    cfg, job, cell = loaded["cfg"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == "causal-lm-1x8192-mla"
    assert (job["rows"], job["seq"], job["steps_per_dispatch"]) == (1, 8192, 4)
    assert job["seq"] == cfg["max_position_embeddings"]
    assert job["optimizer"] == {"name": "adamw", "lr": 3e-4, "wd": 0.1, "eps": 1e-8}
    assert set(job["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                  "param_delta_leaf_gap"}
    assert "PR 23" not in job["limits_from"]
    assert cfg["family"] == "deepseek_v3"
    # the one alias the file carries: the older reader's name for the depth
    assert cfg["num_dense_layers"] == cfg["first_k_dense_replace"] == 1
    assert "num_experts" not in cfg and "num_experts" not in cfg["published"]
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_READERS) <= reported
    assert {"model.moe_ms_per_step", "model.moe_dispatch_ms_per_step",
            "kernels.grouped_mm_ms_per_step", "kernels.grouped_mm_roofline_pct",
            "kernels.flash_full_ms_per_step", "model.mfu",
            "ops.flash_tiles_visited_share.train"} <= reported
    assert not [m for m in reported if "gdn" in m or "flash_window" in m]
    assert "kernels.layer_norm_ms_per_step" not in reported
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW_READERS:        # each lists this cell alone
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]
    # what PR 32 adds stands at the end of its lists
    assert bench["configs"][-1]["name"] == "moonlight-16b-a3b"
    assert bench["workloads"][-1]["name"] == REAL_CELL
    assert [m["name"] for m in bench["per_layer"][-3:]] == list(NEW_READERS)


def test_configuration_keeps_the_published_widths():
    cfg = load("configs", "moonlight-16b-a3b")
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Moonlight-16B-A3B")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items()
                 if k not in cfg or cfg[k] != v}
    assert differing == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        k: row["config"][k] for k in cfg["reduced"]}
    assert cfg["assumed"]["experts_held"] == [0, cfg["n_routed_experts"]]
    # the floors: four expert layers past the dense one, 8 experts, 1/8 vocab
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert cfg["n_routed_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0


def test_parameter_count_by_the_familys_own_count():
    """668,890,432: the dense layer, five expert layers of 8 held experts,
    an eighth of the embedding and of the head — by the shapes the
    reference makes, and again by hand."""
    cfg = load("configs", "moonlight-16b-a3b")
    fam = harness.load_module(ROOT, "families", "deepseek_v3")
    rcfg = fam.reference_config(cfg)
    shapes = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in shapes.values()) == N_PARAMS
    d = 2048
    mixer = d * 16 * 192 + d * 576 + 512 + 512 * 16 * 256 + 16 * 128 * d
    assert mixer == 13_763_072
    dense = mixer + 3 * d * 11264 + 2 * d
    expert = 3 * d * 1408
    beside = mixer + 3 * d * 2816 + d * 64 + 64 + 2 * d
    assert (dense, 8 * expert, beside) == (82_973_184, 69_206_016, 31_199_808)
    assert dense + 5 * (8 * expert + beside) + 2 * 20480 * d + d == N_PARAMS
    # the program holds the same numbers in its own tree
    pcfg = fam.program_config(cfg, jnp.bfloat16)
    tree = jax.eval_shape(lambda w: fam.to_program(w, cfg), shapes)
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == N_PARAMS
    assert (pcfg.qk_nope_head_dim, pcfg.qk_rope_head_dim, pcfg.v_head_dim) == (128, 64, 128)
    assert pcfg.experts_held == (0, 8) and pcfg.n_routed_experts == 64


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny deepseek_v3 configuration, its job, its
    cell and the real benchmark's metrics of the real cell retargeted to it."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("tinymoon")))
    with open(f"{root}/benchmark/configs/moonlight-tiny.json", "w") as f:
        json.dump(MOON_TINY, f)
    with open(f"{root}/benchmark/traffic/lm-tiny-1row.json", "w") as f:
        json.dump(tiny.train_mix("causal_lm", tiny.ADAMW, rows=1,
                                 reference_rows_per_block=1), f)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "moonlight-tiny", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/moonlight-tiny.json"})
    bench["workloads"].append({"name": CELL, "config": "moonlight-tiny",
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
    # norm scales stand at 1.0 and cannot take a step of 6e-4 in bfloat16
    path = f"{root}/benchmark/configs/moonlight-tiny.json"
    with open(path, "w") as f:
        json.dump({**MOON_TINY, "precision": {"opt_level": "O3"}}, f)
    try:
        rc, line, lines = tiny.run_cell(root, CELL, capsys)
    finally:
        with open(path, "w") as f:
            json.dump(MOON_TINY, f)
    assert rc == 0
    assert checked(lines, "param_delta_leaf_gap") > 3 * sound, "\n".join(lines)


def test_train_flops_and_flash_needed_by_hand():
    """The share at 8192 tokens, every term written out (ISSUE 32's
    arithmetic: 878.3 MFLOP a token forward, 2.635 GFLOP to train), and the
    flash kernels' need by the TRUE head sizes: scores over 192, values over
    128 — a fifth under what a v padded to 192 would count."""
    cfg = load("configs", "moonlight-16b-a3b")
    fam = harness.load_module(ROOT, "families", "deepseek_v3")
    d = 2048
    attn_proj = 2 * d * 3072 + 2 * d * 576 + 2 * 512 * 4096 + 2 * 2048 * d
    attention = 2 * 16 * (192 + 128) * 8193 / 2
    dense = 6 * d * 11264
    expert_layer = (2 * d * 64                           # router over all 64
                    + 6 * d * 2816                       # the shared experts
                    + 6 * 8 / 64 * 6 * d * 1408)         # 0.75 expert expected
    head = 2 * d * 20480
    forward = 6 * (attn_proj + attention) + dense + 5 * expert_layer + head
    assert fam.train_flops_per_token(cfg, 8192) == pytest.approx(3 * forward, rel=1e-12)
    assert 878.2e6 < forward < 878.5e6
    parts = fam.forward_flops_per_token(cfg, 8192)
    assert parts["attention"] / 6 == pytest.approx(41.9e6, rel=2e-3)
    # the latent mixer is 59% of an expert layer's operations
    mixer = (parts["attn_proj"] + parts["attention"]) / 6
    layer = mixer + expert_layer
    assert 0.58 < mixer / layer < 0.60

    fwd, bwd = fam.flash_needed(cfg, 8192, 1)
    assert fwd[0] == 8192 * 2 * 16 * 320 * 4096.5 and bwd[0] == 2 * fwd[0]
    assert fwd[1] == 2 * 8192 * 16 * (192 + 192 + 128 + 128)
    assert bwd[1] == 2 * fwd[1]
    padded = 8192 * 2 * 16 * (192 + 192) * 4096.5
    assert padded / fwd[0] == pytest.approx(1.2)
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    assert all(f / peaks["bf16_flops_per_s"] > n / peaks["hbm_bytes_per_s"]
               for f, n in (fwd, bwd))                   # compute-bound
    assert 6 * fam.needed_seconds([fwd, bwd], peaks) == pytest.approx(31.4e-3, rel=5e-3)
    # the grouped products: 768 rows an expert, compute-bound like Trinity's
    gmm = fam.grouped_mm_needed(cfg, 8192)
    assert len(gmm) == 6 and gmm[0][0] == 2 * 768 * 8 * 2048 * 2816
    assert all(f / peaks["bf16_flops_per_s"] > n / peaks["hbm_bytes_per_s"]
               for f, n in gmm)


def test_new_readers_on_a_recorded_trace(monkeypatch):
    """The mixer's three scopes summed, ``mla_proj`` alone, and the flash
    kernels' roofline share found through the cell's shapes; nothing to
    read, no error, on a program without the scopes (the parent) — and
    another mixer's ``attn_full`` alone is not this metric's to read."""
    from benchmark import program_trace, scoped_kernels

    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    step = "jit(window)/while/body/closed_call/"
    flash = "attn_full/jit(_flash_jit)/"
    ev = lambda name, t0, dur, scope: [
        name, t0, dur,
        {"op_name": step + f"jvp(DeepseekV3LM)/layer_1/attn/{scope}"}]
    device = [
        ev("%fusion.1 = bf16[8] fusion()", 0, 10, "mla_proj/q_proj/dot_general"),
        ev("%fusion.2 = bf16[8] fusion()", 10, 4, "mla_proj/concatenate"),
        ev("%apex_flash_fwd.3 = bf16[8]" + mosaic, 14, 30, flash + "apex_flash_fwd/pallas_call"),
        ev("%apex_flash_bwd_dkdv.4 = bf16[8]" + mosaic, 44, 40, flash + "apex_flash_bwd_dkdv/pallas_call"),
        ev("%apex_flash_bwd_dq.5 = bf16[8]" + mosaic, 84, 26, flash + "apex_flash_bwd_dq/pallas_call"),
        ev("%fusion.6 = f32[8] fusion()", 110, 3, "attn_full/mul"),
        ev("%fusion.7 = bf16[8] fusion()", 113, 8, "mla_out/o_proj/dot_general"),
        ["%fusion.8 = f32[8] fusion()", 121, 5,
         {"op_name": step + "jvp(DeepseekV3LM)/layer_1/moe/moe_router/dot_general"}],
    ]
    host = {"name": "/host:CPU", "lines": [{"name": "t", "events": [
        ["apex/train/dispatch", 0, 5, {"k": 2}]]}]}
    trace = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": device}]},
        host]}

    def use(t):
        monkeypatch.setattr(program_trace, "newest",
                            lambda *a: program_trace.reduce(t))
        monkeypatch.setattr(scoped_kernels, "newest",
                            lambda *a: scoped_kernels.reduce(t))

    use(trace)
    cfg, job = load("configs", "moonlight-16b-a3b"), load("traffic", "causal-lm-1x8192-mla")
    fam = harness.load_module(ROOT, "families", "deepseek_v3")
    run = {"kind": "train", "trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "tokens_per_window": 4 * 8192,
           "flops_per_token": fam.train_flops_per_token(cfg, job["seq"])}
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    assert read("model.mla_ms_per_step") == pytest.approx(121e-6 / 2)
    assert read("model.mla_proj_ms_per_step") == pytest.approx(14e-6 / 2)
    assert read("kernels.flash_full_ms_per_step") == pytest.approx(96e-6 / 2)
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    needed_s = 6 * fam.needed_seconds(fam.flash_needed(cfg, 8192, 1), peaks)
    assert read("kernels.flash_mla_roofline_pct") == pytest.approx(
        100 * needed_s / (96e-9 / 2))
    assert read("model.moe_ms_per_step") == pytest.approx(5e-6 / 2)
    # a program without the scopes (the parent): nothing to read, no error
    use({"planes": [host]})
    for name in NEW_READERS:
        assert read(name) is None
    # another mixer's attn_full (Trinity's, Qwen3-Next's) is not latent attention
    other_mixer = {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Ops", "events": [
            e for e in device if "mla_" not in e[3]["op_name"]]}]}, host]}
    use(other_mixer)
    assert read("kernels.flash_full_ms_per_step") == pytest.approx(96e-6 / 2)
    for name in NEW_READERS:
        assert read(name) is None
    # another cell's record (its own operations a token): not this cell
    use(trace)
    assert harness.load_module(
        ROOT, "layer_metrics", "kernels.flash_mla_roofline_pct").read(
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
    cfg, job = load("configs", "moonlight-16b-a3b"), load("traffic", "causal-lm-1x8192-mla")
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
    print(f"\nmoonlight.train-8k: {N_PARAMS / 1e6:.1f}M parameters; per "
          f"device arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls {len(names)}: {by_kernel}")
    assert total < 16 * 2 ** 30
    assert not unnamed_mosaic_calls(text)
    # six layers: one forward, one dkdv, one dq each (the kernel's output is
    # kept under per-block recomputation: no second forward)
    assert by_kernel["apex_flash_fwd"] == 6
    assert sum(v for k, v in by_kernel.items() if "apex_flash_bwd" in k) == 12
    for kernel in ("apex_gmm", "apex_gmm_dw", "apex_moe_gather",
                   "apex_moe_combine", "apex_xent_fwd"):
        assert any(kernel in n for n in names), kernel
    # no flash operand or result is padded to the queries' width
    for line in text.splitlines():
        if "tpu_custom_call" in line and "apex_flash" in line and " = " in line:
            widths = set(re.findall(r"bf16\[16,8192,(\d+)\]", line))
            assert widths == {"192", "128"}, line[:300]
    reg = obs.default_registry()
    assert reg.get("moe.dispatch.kernels").value == 1
    assert reg.get("moe.dispatch.rows_capacity").value == 51200
    assert reg.get("moe.dispatch.slots").value == 49152
    assert reg.get("moe.experts_held").value == 8


def test_reference_step_fits_beside_four_float32_copies(topo, no_compile_cache):
    """The plain reference's gradient of one 8192-token row, compiled for the
    described chip: its temporaries beside the weights, the summed gradient
    and both moments (four float32 copies, 9.97 GiB) have to stay under the
    chip's 15.75 GiB."""
    import functools

    cfg, job = load("configs", "moonlight-16b-a3b"), load("traffic", "causal-lm-1x8192-mla")
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
