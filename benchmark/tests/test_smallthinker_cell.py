"""The ``smallthinker`` family and the ``smallthinker.train-16k`` cell, rehearsed
on the CPU: the cell's files through ``harness.load_cell``, the configuration
against the catalog, the family's parameter count and operations worked out by
hand, the derived keys the accepted readers read, a tiny cell of the family
through the harness (new files and entries alone), the two new readers on a
small recorded trace, and the cell's window and its reference's step compiled
at their REAL size for a described ``v5e:2x2`` (arguments + temporaries in GiB
and the Mosaic calls by name: the fit, before any chip time; slow, minutes).

    python -m pytest benchmark/tests/test_smallthinker_cell.py -s
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
REAL_CELL = "smallthinker.train-16k"
CONFIG, TRAFFIC = "smallthinker-21ba3b", "causal-lm-1x16384-swa"
CELL = "smallthinker-tiny.train"
ST_TINY = {
    "name": "smallthinker-tiny", "family": "smallthinker", "hidden_size": 128,
    "num_hidden_layers": 3, "rope_layout": [0, 1, 1],
    "sliding_window_layout": [0, 1, 1],
    "layer_types": ["full_attention", "sliding_attention", "sliding_attention"],
    "sliding_window_size": 48, "sliding_window": 48, "num_dense_layers": 0,
    "num_attention_heads": 6, "num_key_value_heads": 2, "head_dim": 64,
    "rope_theta": 1500000, "rope_scaling": None, "moe_ffn_hidden_size": 128,
    "moe_num_primary_experts": 4, "moe_num_active_primary_experts": 4,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "vocab_size": 250,
    "published": {"moe_num_primary_experts": 16},
    "assumed": {"padded_vocab_size": 256, "experts_held": [4, 8],
                "initializer_range": 0.02, "remat_policy": "full_block"},
    "precision": {"opt_level": "O2"},
}
NEW_READERS = ("model.moe_router_ms_per_step", "kernels.flash_full_roofline_pct")
N_PARAMS = 370_956_800


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_the_issue_says():
    loaded = harness.load_cell(ROOT, REAL_CELL)
    cfg, job, cell = loaded["cfg"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert (job["rows"], job["seq"], job["steps_per_dispatch"]) == (1, 16384, 2)
    assert job["seq"] == cfg["max_position_embeddings"]
    assert job["optimizer"] == {"name": "adamw", "lr": 1e-5, "wd": 0.1, "eps": 1e-8}
    assert set(job["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                  "param_delta_leaf_gap"}
    assert "PR 37" in job["limits_from"] and "PR 23" not in job["limits_from"]
    assert cfg["family"] == "smallthinker"
    assert cfg["moe_num_primary_experts"] == 8
    assert cfg["published"]["moe_num_primary_experts"] == 64
    assert cfg["assumed"]["padded_vocab_size"] == 19072
    assert cfg["assumed"]["remat_policy"] == "full_block"
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_READERS) <= reported
    assert {"model.moe_ms_per_step", "model.moe_dispatch_ms_per_step",
            "kernels.grouped_mm_ms_per_step", "kernels.grouped_mm_roofline_pct",
            "kernels.flash_full_ms_per_step", "kernels.flash_window_ms_per_step",
            "kernels.flash_window_roofline_pct", "model.mfu",
            "ops.flash_tiles_visited_share.train"} <= reported
    assert not [m for m in reported if "gdn" in m or "mla" in m]
    assert "kernels.layer_norm_ms_per_step" not in reported
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    # every list trinity-mini.train-8k is in but the LayerNorm kernels'
    for m in bench["per_layer"]:
        if ("trinity-mini.train-8k" in m.get("workloads", [])
                and m["name"] != "kernels.layer_norm_ms_per_step"):
            assert REAL_CELL in m["workloads"], m["name"]
    entries = {m["name"]: m for m in bench["per_layer"]}
    assert entries["model.moe_router_ms_per_step"]["workloads"] == [
        "trinity-mini.train-8k", "qwen3-next.train-8k", "moonlight.train-8k",
        REAL_CELL]
    assert entries["kernels.flash_full_roofline_pct"]["workloads"] == [
        REAL_CELL, "trinity-mini.train-8k"]
    # what PR 37 adds stands AFTER what was there (a later PR appends after
    # it: nothing here asks to be last); no cell on four chips
    order = lambda key, name: [x["name"] for x in bench[key]].index(name)
    assert order("configs", CONFIG) == order("configs", "moonlight-16b-a3b") + 1
    assert order("workloads", REAL_CELL) == order("workloads", "moonlight.train-8k") + 1
    assert [order("per_layer", n) for n in NEW_READERS] == [
        order("per_layer", "setup.cache_misses") + 1,
        order("per_layer", "setup.cache_misses") + 2]
    assert all(c["chips"] == 1 for c in bench["workloads"])
    # the driver's limits of form on what PR 37 wrote (the first hand-in's
    # configuration `why` had 202 characters and was refused before any run)
    mine = [bench["configs"][order("configs", CONFIG)], cell]
    mine += [entries[n] for n in NEW_READERS]
    for entry in mine:
        for key, text in entry.items():
            if isinstance(text, str):
                assert 1 <= len(text) <= 200 and text.isprintable(), (entry["name"], key)


def test_configuration_keeps_the_published_widths():
    cfg = load("configs", CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "SmallThinker-21BA3B-Instruct")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items()
                 if k not in cfg or cfg[k] != v}
    assert differing == set(cfg["reduced"]) == {
        "num_hidden_layers", "rope_layout", "sliding_window_layout",
        "moe_num_primary_experts", "vocab_size"}
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        k: row["config"][k] for k in cfg["reduced"]}
    # one whole period of the published pattern, the full layer first
    period = row["config"]["sliding_window_layout"][:4]
    assert cfg["sliding_window_layout"] == period == [0, 1, 1, 1]
    assert row["config"]["sliding_window_layout"] == period * 13
    assert cfg["rope_layout"] == row["config"]["rope_layout"][:4] == period
    assert cfg["assumed"]["experts_held"] == [0, cfg["moe_num_primary_experts"]]
    # the widths
    assert (cfg["hidden_size"], cfg["num_attention_heads"],
            cfg["num_key_value_heads"], cfg["head_dim"]) == (2560, 28, 4, 128)
    assert (cfg["sliding_window_size"], cfg["rope_theta"], cfg["rms_norm_eps"],
            cfg["moe_ffn_hidden_size"], cfg["moe_num_active_primary_experts"]
            ) == (4096, 1500000, 1e-6, 768, 6)
    # the floors: a whole period and four layers, 8 experts, 1/8 vocabulary
    assert cfg["num_hidden_layers"] >= 4 and cfg["moe_num_primary_experts"] >= 8
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cfg["assumed"]["padded_vocab_size"] % 128 == 0
    assert 0 <= cfg["assumed"]["padded_vocab_size"] - cfg["vocab_size"] < 128


def test_derived_keys_say_what_their_sources_say():
    """``layer_types``, ``sliding_window`` and ``num_dense_layers`` are what
    the accepted readers read from the file; each carries its why, and the
    family refuses a file in which one and its source differ."""
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", "smallthinker")
    assert cfg["layer_types"] == ["full_attention"] + ["sliding_attention"] * 3
    assert cfg["sliding_window"] == cfg["sliding_window_size"]
    assert cfg["num_dense_layers"] == 0
    for key in ("layer_types", "sliding_window", "num_dense_layers"):
        assert "not a published key" in cfg[key + "_why"]
    assert fam.WINDOW == "sliding_attention"
    fam.reference_config(cfg)
    for key, value in (("layer_types", ["sliding_attention"] * 4),
                       ("sliding_window", 2048), ("num_dense_layers", 1),
                       ("rope_layout", [0, 1, 1])):
        with pytest.raises(ValueError):
            fam.reference_config({**cfg, key: value})
    with pytest.raises(ValueError, match="experts_held"):
        fam.reference_config({**cfg, "moe_num_primary_experts": 16})


def test_parameter_count_by_the_familys_own_count():
    """370,956,800: four layers of 8 held experts, an eighth of the embedding
    and of the head — by the shapes the reference makes, and again by hand."""
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", "smallthinker")
    rcfg = fam.reference_config(cfg)
    shapes = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in shapes.values()) == N_PARAMS
    d = 2560
    mixer = d * (28 + 4 + 4) * 128 + 28 * 128 * d
    expert = 3 * d * 768
    assert (mixer, expert, 8 * expert) == (20_971_520, 5_898_240, 47_185_920)
    layer = 8 * expert + mixer + d * 64 + 2 * d
    assert layer == 68_326_400
    assert 4 * layer + 2 * 19072 * d + d == N_PARAMS
    # the model whole: 52 layers of 64 experts, the whole vocabulary
    whole = 52 * (64 * expert + mixer + d * 64 + 2 * d) + 2 * 151936 * d + d
    assert 21.4e9 < whole < 21.6e9
    # the program holds the same numbers in its own tree
    pcfg = fam.program_config(cfg, jnp.bfloat16)
    tree = jax.eval_shape(lambda w: fam.to_program(w, cfg), shapes)
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == N_PARAMS
    assert (pcfg.num_heads, pcfg.num_kv_heads, pcfg.head_dim) == (28, 4, 128)
    assert pcfg.experts_held == (0, 8) and pcfg.num_experts == 64
    assert pcfg.sliding_window_layout == pcfg.rope_layout == (0, 1, 1, 1)
    assert pcfg.remat_policy == "full_block"


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny smallthinker configuration, its job, its
    cell and the real benchmark's metrics of the real cell retargeted to it."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("tinythinker")))
    with open(f"{root}/benchmark/configs/smallthinker-tiny.json", "w") as f:
        json.dump(ST_TINY, f)
    with open(f"{root}/benchmark/traffic/lm-tiny-1row.json", "w") as f:
        json.dump(tiny.train_mix("causal_lm", tiny.ADAMW, rows=1,
                                 reference_rows_per_block=1), f)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "smallthinker-tiny", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/smallthinker-tiny.json"})
    bench["workloads"].append({"name": CELL, "config": "smallthinker-tiny",
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
    path = f"{root}/benchmark/configs/smallthinker-tiny.json"
    with open(path, "w") as f:
        json.dump({**ST_TINY, "precision": {"opt_level": "O3"}}, f)
    try:
        rc, line, lines = tiny.run_cell(root, CELL, capsys)
    finally:
        with open(path, "w") as f:
            json.dump(ST_TINY, f)
    assert rc == 0
    assert checked(lines, "param_delta_leaf_gap") > 3 * sound, "\n".join(lines)


def test_census_tool_reads_the_block_inputs_of_a_tiny_cell(root, capsys, monkeypatch):
    """``tools/routing_census_block_input.py`` on the tiny cell: a line a
    seed, a layer a block, the held experts' rows counted from the stream
    each block's router reads."""
    from benchmark.tools import routing_census_block_input as tool

    monkeypatch.setattr(tool, "ROOT", root)
    capsys.readouterr()
    assert tool.main(["--workload", CELL, "--seeds", "1", "2147483737"]) == 0
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    assert [l["seed"] for l in lines] == [1, 2147483737]
    for line in lines:
        assert line["workload"] == CELL and line["held"] == [4, 8]
        assert sorted(line["layers"]) == ["layer_0", "layer_1", "layer_2"]
        slots = line["tokens"] * ST_TINY["moe_num_active_primary_experts"]
        for layer in line["layers"].values():
            assert 0 <= layer["rows_held_min"] <= layer["rows_held_mean"] \
                <= layer["rows_held_max"] <= layer["rows_all_experts_max"] <= line["tokens"]
            assert layer["rows_held_total"] <= slots
        assert line["rows_held_a_step"] == sum(
            l["rows_held_total"] for l in line["layers"].values())
    assert lines[0]["layers"] != lines[1]["layers"]


def test_the_embedding_is_drawn_at_its_own_scale():
    """The one departure from ISSUE 37's initializer (``assumed.embedding_why``):
    embedding rows N(0, 1), every other matrix N(0, 0.02)."""
    cfg = load("configs", CONFIG)
    assert cfg["assumed"]["initializer_range"] == 0.02
    assert cfg["assumed"]["embedding_initializer_range"] == 1.0
    assert "DEPARTURE" in cfg["assumed"]["embedding_why"]
    fam = harness.load_module(ROOT, "families", "smallthinker")
    rcfg = {**fam.reference_config(cfg), "num_hidden_layers": 0}
    w = jax.jit(lambda k: fam.reference.init_params(k, rcfg))(jax.random.PRNGKey(0))
    assert float(jnp.std(w["embed"])) == pytest.approx(1.0, rel=0.01)
    assert float(jnp.std(w["head"])) == pytest.approx(0.02, rel=0.01)


def test_train_flops_and_flash_needed_by_hand():
    """The share at 16,384 tokens, every term written out (ISSUE 37's
    arithmetic: 573.7 MFLOP a token forward, 1.721 GFLOP to train; attention
    47% of it, a full layer 117.4 MFLOP against a window layer's 51.4), and
    the flash kernels' need with and without the band."""
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", "smallthinker")
    d, s, w = 2560, 16384, 4096
    attn_proj = 2 * d * (28 + 8) * 128 + 2 * 28 * 128 * d
    full = 4 * 28 * 128 * (s + 1) / 2
    keys_in_band = (w * (w + 1) / 2 + (s - w) * w) / s
    assert keys_in_band == 3584.125 and fam.mean_keys(s, w) == keys_in_band
    window = 4 * 28 * 128 * keys_in_band
    assert full == pytest.approx(117.4e6, rel=1e-3)
    assert window == pytest.approx(51.4e6, rel=1e-3)
    router = 2 * d * 64
    routed = 6 * 8 / 64 * 6 * d * 768                   # 0.75 expert expected
    head = 2 * d * 19072
    forward = 4 * (attn_proj + router + routed) + full + 3 * window + head
    assert fam.train_flops_per_token(cfg, s) == pytest.approx(3 * forward, rel=1e-12)
    assert forward == pytest.approx(573.7e6, rel=2e-4)
    assert 3 * forward == pytest.approx(1.721e9, rel=2e-4)
    parts = fam.forward_flops_per_token(cfg, s)
    assert parts["attention"] == pytest.approx(271.6e6, rel=1e-3)
    assert 0.47 < parts["attention"] / forward < 0.48
    assert (parts["attn_proj"], parts["head"]) == (
        pytest.approx(167.8e6, rel=1e-3), pytest.approx(97.6e6, rel=1e-3))
    assert parts["routed"] == pytest.approx(35.4e6, rel=1e-3)
    # the band visits 43.7% of the causal triangle's keys
    assert keys_in_band / ((s + 1) / 2) == pytest.approx(0.4375, rel=1e-3)

    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    for win, keys in ((None, (s + 1) / 2), (w, keys_in_band)):
        fwd, bwd = fam.flash_needed(cfg, s, 1, win)
        assert fwd[0] == s * 4 * 28 * 128 * keys and bwd[0] == 2 * fwd[0]
        # q, o at 28 heads, k, v at 4, two bytes each; backward twice that
        assert fwd[1] == 2 * (2 * 28 + 2 * 4) * s * 128 and bwd[1] == 2 * fwd[1]
        assert all(f / peaks["bf16_flops_per_s"] > n / peaks["hbm_bytes_per_s"]
                   for f, n in (fwd, bwd))               # compute-bound
    # a step's least time: the full layer 29.3 ms, the three window layers 38.5
    assert fam.needed_seconds(fam.flash_needed(cfg, s, 1, None), peaks) == \
        pytest.approx(3 * s * full / 197e12, rel=1e-9)
    assert 3 * s * full / 197e12 == pytest.approx(29.3e-3, rel=2e-3)
    assert 3 * fam.needed_seconds(fam.flash_needed(cfg, s, 1, w), peaks) == \
        pytest.approx(38.5e-3, rel=2e-3)
    # the grouped products: 1536 rows an expert, compute-bound
    gmm = fam.grouped_mm_needed(cfg, s)
    assert len(gmm) == 6 and gmm[0][0] == 2 * 1536 * 8 * d * 1536
    assert gmm[3][0] == 2 * 1536 * 8 * 768 * d
    assert all(f / peaks["bf16_flops_per_s"] > n / peaks["hbm_bytes_per_s"]
               for f, n in gmm)


def test_new_readers_on_a_recorded_trace(monkeypatch):
    """``moe_router`` alone beside router + dispatch, and the full layers'
    flash kernels against the causal triangle's need found through the
    cell's shapes (the window layers' kernels are the other metric's);
    nothing to read, no error, on a program without the scopes; the share
    worked out by hand, and under 100 at a time no chip can beat."""
    from benchmark import program_trace, scoped_kernels

    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    step = "jit(window)/while/body/closed_call/"
    ev = lambda name, t0, dur, scope: [
        name, t0, dur,
        {"op_name": step + f"jvp(SmallThinkerLM)/{scope}"}]
    flash = lambda layer, scope, kernel: (
        f"layer_{layer}/{scope}/jit(_flash_jit)/{kernel}/pallas_call")
    ms = 1_000_000
    device = [
        ev("%fusion.1 = f32[8] fusion()", 0, 2 * ms, "layer_0/moe/moe_router/dot_general"),
        ev("%fusion.2 = f32[8] fusion()", 2 * ms, 1 * ms, "layer_0/moe/moe_router/top_k"),
        ev("%apex_flash_fwd.3 = bf16[8]" + mosaic, 3 * ms, 40 * ms,
           flash(0, "attn_full", "apex_flash_fwd")),
        ev("%apex_flash_bwd_sweep.4 = bf16[8]" + mosaic, 43 * ms, 80 * ms,
           flash(0, "attn_full", "apex_flash_bwd_sweep")),
        ev("%apex_flash_fwd.5 = bf16[8]" + mosaic, 123 * ms, 30 * ms,
           flash(1, "attn_window", "apex_flash_fwd")),
        ev("%apex_flash_bwd_sweep.6 = bf16[8]" + mosaic, 153 * ms, 70 * ms,
           flash(1, "attn_window", "apex_flash_bwd_sweep")),
        ev("%fusion.7 = s32[8] fusion()", 223 * ms, 5 * ms, "layer_0/moe/moe_dispatch/cumsum"),
        ev("%apex_moe_gather.8 = bf16[8]" + mosaic, 228 * ms, 4 * ms,
           "layer_0/moe/moe_dispatch/apex_moe_gather/pallas_call"),
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
    cfg, job = load("configs", CONFIG), load("traffic", TRAFFIC)
    fam = harness.load_module(ROOT, "families", "smallthinker")
    run = {"kind": "train", "trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "tokens_per_window": 2 * 16384,
           "flops_per_token": fam.train_flops_per_token(cfg, job["seq"])}
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    assert read("model.moe_router_ms_per_step") == pytest.approx(3 / 2)
    assert read("model.moe_dispatch_ms_per_step") == pytest.approx(12 / 2)
    assert read("kernels.flash_full_ms_per_step") == pytest.approx(120 / 2)
    assert read("kernels.flash_window_ms_per_step") == pytest.approx(100 / 2)
    # by hand: one full layer's triangle, forward + twice that backward, at
    # the bf16 peak — 29.3 ms a step against 60 measured
    needed_ms = 3 * 16384 * 4 * 28 * 128 * 8192.5 / 197e12 * 1e3
    share = read("kernels.flash_full_roofline_pct")
    assert share == pytest.approx(100 * needed_ms / 60) and 48 < share < 49.5
    # the three window layers' band: 38.5 ms against 50 measured
    assert read("kernels.flash_window_roofline_pct") == pytest.approx(
        100 * 3 * 3 * 16384 * 4 * 28 * 128 * 3584.125 / 197e12 * 1e3 / 50)
    # a program without the scopes (an older model): nothing to read, no error
    use({"planes": [host]})
    for name in NEW_READERS:
        assert read(name) is None
    # another cell's record (its own operations a token): not this cell
    use(trace)
    assert harness.load_module(
        ROOT, "layer_metrics", "kernels.flash_full_roofline_pct").read(
            {**run, "flops_per_token": 1.0}) is None
    # trinity-mini.train-8k's record finds ITS shapes (one full layer at 8k,
    # 32 heads): 4 x 8192 tokens a window, as this cell's 2 x 16384
    tcfg, tjob = load("configs", "trinity-mini"), load("traffic", "causal-lm-1x8192")
    tfam = harness.load_module(ROOT, "families", "afmoe")
    trun = {**run, "flops_per_token": tfam.train_flops_per_token(tcfg, tjob["seq"])}
    assert trun["tokens_per_window"] == 4 * tjob["seq"]
    got = harness.load_module(
        ROOT, "layer_metrics", "kernels.flash_full_roofline_pct").read(trun)
    full_layers = sum(k != "sliding_attention" for k in tcfg["layer_types"])
    assert got == pytest.approx(
        100 * full_layers * 3 * 8192 * 4 * 32 * 128 * 4096.5 / 197e12 * 1e3 / 60)


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
    print(f"\n{REAL_CELL}: {N_PARAMS / 1e6:.1f}M parameters; per "
          f"device arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls {len(names)}: {by_kernel}")
    assert total < 16 * 2 ** 30
    assert total > 0.25 * 16e9              # the driver's floor, by rehearsal
    assert not unnamed_mosaic_calls(text)
    # four layers: one forward and ONE backward sweep each (the kernel's
    # output is kept under per-block recomputation: no second forward)
    assert by_kernel["apex_flash_fwd"] == 4
    assert by_kernel["apex_flash_bwd_sweep"] == 4
    assert not [k for k in by_kernel if "bwd_dkdv" in k or "bwd_dq" in k]
    # the row movement goes through the kernels at hidden 2560
    for kernel in ("apex_gmm", "apex_gmm_dw", "apex_moe_records",
                   "apex_moe_gather", "apex_moe_combine",
                   "apex_moe_combine_dw", "apex_xent_fwd"):
        assert by_kernel.get(kernel), kernel
    reg = obs.default_registry()
    assert reg.get("moe.dispatch.kernels").value == 1
    assert reg.get("moe.dispatch.rows_capacity").value == 100352
    assert reg.get("moe.dispatch.slots").value == 98304
    assert reg.get("moe.experts_held").value == 8
    assert reg.get("moe.experts_routed_over").value == 64


def test_reference_step_fits_beside_four_float32_copies(topo, no_compile_cache):
    """The plain reference's gradient of one 16,384-token row, compiled for
    the described chip: its temporaries beside the weights, the summed
    gradient and both moments (four float32 copies, 5.53 GiB) have to stay
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
