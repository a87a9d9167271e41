"""The ``granitemoehybrid`` family and the ``granite-h.train-8k`` cell,
rehearsed on the CPU: the cell's files through ``harness.load_cell``, the
configuration against the catalog, the family's parameter count and
operations worked out by hand, the scan's needed operations and bytes by hand,
a tiny cell of the family through the harness (new files and entries alone),
the four new readers on small recorded traces (one of a kernel run, one of
the ``jax.numpy`` path: the same needed work), and the cell's window and its
reference's step compiled at their REAL size for a described ``v5e:2x2``
(arguments + temporaries in GiB and the Mosaic calls by name: the fit, before
any chip time; slow, minutes).

    python -m pytest benchmark/tests/test_granite_cell.py -s
"""
import functools
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
REAL_CELL = "granite-h.train-8k"
CONFIG, TRAFFIC = "granite-4.0-h-micro", "causal-lm-1x8192-ssd"
FAMILY = "granitemoehybrid"
CELL = "granite-tiny.train"
TINY_KINDS = ["mamba", "attention", "mamba"]
GRANITE_TINY = {
    "name": "granite-tiny", "family": FAMILY, "hidden_size": 128,
    "num_hidden_layers": 3, "layer_types": TINY_KINDS,
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu",
    "intermediate_size": 256, "shared_intermediate_size": 256,
    "logits_scaling": 8, "mamba_chunk_size": 32, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 32,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 4,
    "mamba_proj_bias": False, "normalization_function": "rmsnorm",
    "num_attention_heads": 2, "num_key_value_heads": 1,
    "num_experts_per_tok": 0, "num_local_experts": 0,
    "position_embedding_type": "nope", "residual_multiplier": 0.22,
    "rms_norm_eps": 1e-5, "tie_word_embeddings": True, "vocab_size": 256,
    "published": {"layer_types": TINY_KINDS + ["mamba"]},
    "assumed": {"padded_vocab_size": 256, "tie_word_embeddings": True,
                "initializer_range": 0.02, "remat_policy": "full_block"},
    "precision": {"opt_level": "O2"},
}
NEW_READERS = ("model.ssm_ms_per_step", "model.ssm_scan_ms_per_step",
               "model.dense_ffn_ms_per_step", "kernels.ssd_scan_roofline_pct")
N_PARAMS = 772_160_448
KINDS = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4


def load(sub, name):
    with open(os.path.join(ROOT, "benchmark", sub, name + ".json")) as f:
        return json.load(f)


def test_the_cells_files_load_and_say_what_the_issue_says():
    loaded = harness.load_cell(ROOT, REAL_CELL)
    cfg, job, cell = loaded["cfg"], loaded["traffic"], loaded["cell"]
    assert cell["chips"] == 1 and cell["traffic"] == TRAFFIC
    assert (job["rows"], job["seq"], job["steps_per_dispatch"]) == (1, 8192, 2)
    assert job["seq"] <= cfg["max_position_embeddings"] == 131072
    assert job["optimizer"] == {"name": "adamw", "lr": 1e-5, "wd": 0.1, "eps": 1e-8}
    assert set(job["limits"]) == {"loss_rel_gap", "grad_norm_rel_gap",
                                  "param_delta_leaf_gap"}
    assert "PR 23" not in job["limits_from"] and "PROVISIONAL" not in job["limits_from"]
    assert cfg["family"] == FAMILY
    assert cfg["layer_types"] == KINDS and cfg["num_hidden_layers"] == 10
    assert cfg["vocab_size"] == cfg["assumed"]["padded_vocab_size"] == 12544
    assert cfg["assumed"]["tie_word_embeddings"] is True
    assert cfg["assumed"]["remat_policy"] == "full_block"
    for key in ("reduced_why", "deployment"):
        assert cfg[key]
    for key in ("equations", "remat_why", "weights", "optimizer", "left_out"):
        assert cfg["assumed"][key]
    assert cfg["precision"]["opt_level"] == "O2"
    reported = {m["name"] for m in loaded["per_layer"]}
    assert set(NEW_READERS) <= reported
    assert {"kernels.flash_full_ms_per_step", "model.mfu", "model.blocks_ms_per_step",
            "ops.flash_tiles_visited_share.train", "train.optimizer_ms_per_step",
            "kernels.unnamed_mosaic_share.train", "compiles_in_window.train",
            "device.idle_share.train", "setup.cache_misses"} <= reported
    # the full layers' roofline reader counts every layer layer_types does not
    # call windowed, and would count the mamba layers: not this cell's; and a
    # dense model has no expert rows
    assert not [m for m in reported if "gdn" in m or "mla" in m or "moe" in m
                or "conv" in m or "grouped_mm" in m or "layer_norm" in m
                or "flash_window" in m or "flash_full_roofline" in m]
    assert [m["name"] for m in loaded["end_to_end"]] == [
        "train_tokens_per_s", "setup_s"]
    bench = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    for name in NEW_READERS:        # each lists this cell alone
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [REAL_CELL]
        assert entry["moves"] == "train_tokens_per_s"
        assert entry["source"] == "device_trace"
    # what PR 43 adds stands AFTER what was there in the lists
    names = lambda key: [x["name"] for x in bench[key]]
    assert names("configs")[-1] == CONFIG and names("workloads")[-1] == REAL_CELL
    assert names("per_layer")[-4:] == list(NEW_READERS)
    for m in bench["per_layer"] + bench["end_to_end"]:
        cells = m.get("workloads", [])
        if REAL_CELL in cells:
            assert cells[-1] == REAL_CELL
    assert all(c["chips"] == 1 for c in bench["workloads"])
    assert len(bench["workloads"]) == 8


def test_configuration_keeps_the_published_widths():
    cfg = load("configs", CONFIG)
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if not os.path.exists(catalog):
        pytest.skip("no catalog beside the guides here")
    with open(catalog) as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "granite-4.0-h-micro")
    assert cfg["source"] == row["source_url"]
    differing = {k for k, v in row["config"].items()
                 if k not in cfg or cfg[k] != v}
    assert differing == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "vocab_size"}
    assert {k: cfg["published"][k] for k in cfg["reduced"]} == {
        k: row["config"][k] for k in cfg["reduced"]}
    # the widths the issue names
    assert (cfg["hidden_size"], cfg["mamba_n_heads"], cfg["mamba_d_head"],
            cfg["mamba_d_state"], cfg["mamba_n_groups"], cfg["mamba_d_conv"],
            cfg["mamba_chunk_size"], cfg["mamba_expand"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["intermediate_size"], cfg["shared_intermediate_size"],
            cfg["rms_norm_eps"]) == (
                2048, 64, 64, 128, 1, 4, 256, 2, 32, 8, 8192, 8192, 1e-5)
    assert (cfg["embedding_multiplier"], cfg["attention_multiplier"],
            cfg["residual_multiplier"], cfg["logits_scaling"]) == (
                12, 0.015625, 0.22, 8)
    assert cfg["num_local_experts"] == cfg["num_experts_per_tok"] == 0
    # the floors: one whole period (published layers 0-9 in their order: nine
    # mamba layers to one attention layer, the published 36 : 4), 1/8 vocab
    assert cfg["layer_types"] == row["config"]["layer_types"][:10]
    assert cfg["layer_types"].count("attention") == 1
    assert row["config"]["layer_types"].count("attention") == 4
    assert [i for i, k in enumerate(row["config"]["layer_types"])
            if k == "attention"] == [5, 15, 25, 35]
    assert cfg["vocab_size"] * 8 >= row["config"]["vocab_size"]
    assert cfg["vocab_size"] % 128 == 0


def test_the_family_refuses_what_it_does_not_build():
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", FAMILY)
    fam.reference_config(cfg)
    for broken, match in (
            ({"num_hidden_layers": 9}, "num_hidden_layers"),
            ({"layer_types": ["attention"] + KINDS[1:]}, "published"),
            ({"layer_types": ["conv"] + KINDS[1:]}, "only"),
            ({"num_local_experts": 8}, "expert"),
            ({"num_experts_per_tok": 2}, "expert"),
            ({"mamba_proj_bias": True}, "bias"),
            ({"position_embedding_type": "rope"}, "positions")):
        with pytest.raises(ValueError, match=match):
            fam.program_config({**cfg, **broken}, jnp.bfloat16)


def test_parameter_count_by_the_familys_own_count():
    """772,160,448: nine mamba layers and the attention layer, each with its
    8192-wide MLP, an eighth of the embedding — which is the head — by the
    shapes the reference makes, and again by hand."""
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", FAMILY)
    rcfg = fam.reference_config(cfg)
    shapes = jax.eval_shape(lambda k: fam.reference.init_params(k, rcfg),
                            jax.random.PRNGKey(0))
    assert sum(x.size for x in shapes.values()) == N_PARAMS
    assert "head" not in shapes
    d = 2048
    mixer = d * 8512 + 4352 * 4 + 4352 + 3 * 64 + 4096 + 4096 * d
    mlp = d * 16384 + 8192 * d
    assert (mixer, mlp) == (25_847_232, 50_331_648)
    mamba_layer = mixer + mlp + 2 * d
    attn_layer = 2 * d * d + 2 * d * 512 + mlp + 2 * d
    assert (mamba_layer, attn_layer) == (76_182_976, 60_821_504)
    assert 9 * mamba_layer + attn_layer + 12544 * d + d == N_PARAMS
    # the model whole: 36 + 4 layers and the whole vocabulary
    assert 36 * mamba_layer + 4 * attn_layer + 100352 * d + d == 3_191_396_096
    # the program holds the same numbers in its own tree
    pcfg = fam.program_config(cfg, jnp.bfloat16)
    tree = jax.eval_shape(lambda w: fam.to_program(w, cfg), shapes)
    assert sum(x.size for x in jax.tree_util.tree_leaves(tree)) == N_PARAMS
    assert (pcfg.num_heads, pcfg.num_kv_heads, pcfg.head_dim) == (32, 8, 64)
    assert (pcfg.mamba_n_heads, pcfg.mamba_d_head, pcfg.mamba_d_state,
            pcfg.mamba_chunk_size, pcfg.mamba_d_inner) == (64, 64, 128, 256, 4096)
    assert pcfg.layer_types == tuple(KINDS)
    assert pcfg.remat_policy == "full_block"
    model = fam.program_model(pcfg)
    ids = jax.ShapeDtypeStruct((1, 256), jnp.int32)
    made = jax.eval_shape(model.init, jax.random.PRNGKey(0), ids)["params"]
    assert (jax.tree_util.tree_structure(made)
            == jax.tree_util.tree_structure(tree))
    assert jax.tree_util.tree_map(lambda a: a.shape, made) \
        == jax.tree_util.tree_map(lambda a: a.shape, tree)


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    """The tiny benchmark plus a tiny granitemoehybrid configuration, its job,
    its cell and the real benchmark's metrics of the real cell retargeted to
    it."""
    root = tiny.make_root(str(tmp_path_factory.mktemp("tinygranite")))
    with open(f"{root}/benchmark/configs/granite-tiny.json", "w") as f:
        json.dump(GRANITE_TINY, f)
    with open(f"{root}/benchmark/traffic/lm-tiny-1row.json", "w") as f:
        json.dump(tiny.train_mix("causal_lm", tiny.ADAMW, rows=1,
                                 reference_rows_per_block=1), f)
    with open(f"{root}/BENCHMARK.json") as f:
        bench = json.load(f)
    real = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    bench["configs"].append({"name": "granite-tiny", "source": "test",
                             "reduced": [], "why": "tiny",
                             "file": "benchmark/configs/granite-tiny.json"})
    bench["workloads"].append({"name": CELL, "config": "granite-tiny",
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
    # norm scales and D stand at 1.0 and cannot take a small step in bfloat16
    path = f"{root}/benchmark/configs/granite-tiny.json"
    with open(path, "w") as f:
        json.dump({**GRANITE_TINY, "precision": {"opt_level": "O3"}}, f)
    try:
        rc, line, lines = tiny.run_cell(root, CELL, capsys)
    finally:
        with open(path, "w") as f:
            json.dump(GRANITE_TINY, f)
    assert rc == 0
    assert checked(lines, "param_delta_leaf_gap") > 3 * sound, "\n".join(lines)


def test_the_scan_in_bfloat16_is_the_scan_a_precision_lower():
    """``tools/control_scan_precision.py``'s stand-in for ``ssd_scan``: the
    same function of its six arguments as the token recurrence, to bfloat16's
    precision and no closer, with a gradient in every argument."""
    from apex_tpu.ops.ssd import ssd_recurrent
    from benchmark.tools.control_scan_precision import scan_in_bfloat16

    keys = jax.random.split(jax.random.PRNGKey(43), 7)
    b, s, h, p, n = 1, 128, 4, 64, 32
    args = (jax.random.normal(keys[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)) - 3.0),
            -jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0),
            jax.random.normal(keys[3], (b, s, 1, n)),
            jax.random.normal(keys[4], (b, s, 1, n)),
            jnp.ones((h,)))
    cot = jax.random.normal(keys[6], (b, s, h, p))
    want = ssd_recurrent(*args)
    gap = lambda got, want: float(jnp.max(jnp.abs(got - want))
                                  / jnp.max(jnp.abs(want)))
    as_written = functools.partial(scan_in_bfloat16, chunk=32, dtype="float32")
    lowered = functools.partial(scan_in_bfloat16, chunk=32)
    assert gap(as_written(*args), want) < 1e-5 < gap(lowered(*args), want) < 5e-2
    grad = lambda f: jax.grad(lambda *a: jnp.sum(f(*a) * cot),
                              tuple(range(6)))(*args)
    wants = grad(ssd_recurrent)
    for g, low, w in zip(grad(as_written), grad(lowered), wants):
        assert gap(g, w) < 1e-4
        assert low.shape == w.shape and bool(jnp.all(jnp.isfinite(low)))
        assert 1e-4 < gap(low, w)


def test_scan_precision_control_rehearsal(root, capsys, monkeypatch):
    """``tools/control_scan_precision.py`` end to end at the tiny size: a
    reading a seed of the window with the bfloat16 scan in the program's
    place, each beside its limit, the program's own scan put back after; a
    cell of another family is refused."""
    import apex_tpu.models.granite_hybrid as program
    from benchmark.tools import control_scan_precision as tool

    monkeypatch.setattr(harness, "tpu_or_exit", tiny.fake_device)
    scan, lowered, traced = program.ssd_scan, tool.scan_in_bfloat16, []

    def spy(x, *rest, **kw):
        traced.append(x.shape)
        return lowered(x, *rest, **kw)

    monkeypatch.setattr(tool, "scan_in_bfloat16", spy)
    rc = tool.main(["--workload", CELL, "--seeds", "1", "2"], root)
    out = capsys.readouterr().out
    rows = [json.loads(l.split("READING ", 1)[1])
            for l in out.splitlines() if "READING " in l]
    assert [(r["variant"], r["seed"]) for r in rows] == [
        ("control_scan_bfloat16", 1), ("control_scan_bfloat16", 2)]
    assert rc in (0, 1) and program.ssd_scan is scan and traced
    for name in ("loss_rel_gap", "grad_norm_rel_gap", "param_delta_leaf_gap"):
        assert all(name in r for r in rows)
        assert any(l.split("] ", 1)[1].startswith(f"{name}: control ")
                   for l in out.splitlines() if "] " in l)
    with pytest.raises(SystemExit):
        tool.main(["--workload", "gpt2-tiny.train", "--seeds", "1"], root)


def test_train_flops_and_kernels_needs_by_hand():
    """The share at 8192 tokens, every term written out (ISSUE 43's
    arithmetic: 1,615 MFLOP a token forward, 4.85 GFLOP to train, 39.7 TFLOP
    a step), the scan's operations — a token ``2 Q N`` once and a head ``2 Q
    P + 4 P N`` — and bytes, and its time a layer at the peaks."""
    cfg = load("configs", CONFIG)
    fam = harness.load_module(ROOT, "families", FAMILY)
    d, seq = 2048, 8192
    ssm_proj = 2 * d * 8512 + 2 * 4096 * d
    scan = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128)
    attn_proj = 2 * d * 3072 + 2 * 2048 * d
    attention = 4 * 32 * 64 * (seq + 1) / 2
    mlp = 6 * d * 8192
    head = 2 * d * 12544
    forward = 9 * (ssm_proj + scan) + attn_proj + attention + 10 * mlp + head
    assert fam.train_flops_per_token(cfg, seq) == pytest.approx(3 * forward, rel=1e-12)
    assert 1.614e9 < forward < 1.616e9
    assert 3 * forward * seq == pytest.approx(39.7e12, rel=2e-3)
    parts = fam.forward_flops_per_token(cfg, seq)
    assert parts["ssm_proj"] == pytest.approx(464.8e6, rel=1e-3)
    assert parts["ssm_scan"] == pytest.approx(38.3e6, rel=2e-3)
    assert parts["dense_mlp"] == pytest.approx(1006.6e6, rel=1e-3)
    assert parts["attention"] == pytest.approx(33.6e6, rel=2e-3)
    assert parts["attn_proj"] == pytest.approx(21.0e6, rel=2e-3)
    assert parts["head"] == pytest.approx(51.4e6, rel=2e-3)
    assert 0.62 < parts["dense_mlp"] / forward < 0.63       # 62%
    assert 0.31 < (parts["ssm_proj"] + parts["ssm_scan"]) / forward < 0.32

    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    fwd, bwd = fam.ssd_needed(cfg, seq, 1, 256)
    assert fwd[0] == seq * scan and bwd[0] == 2 * fwd[0]
    wide, shared, small = seq * 4096, seq * 128, seq * 64
    states = 32 * 64 * 64 * 128 * 4                     # 67 MB a layer
    assert states == 67_108_864
    assert fwd[1] == 2 * (2 * wide + 2 * shared) + 4 * small + states
    assert bwd[1] == 2 * (3 * wide + 4 * shared) + 8 * small + states
    ms = lambda part: 1e3 * fam.needed_seconds([part], peaks)
    # the bytes bind forward (0.255 ms against 0.177 of operations), the
    # operations backward (0.354 against 0.344 of bytes)
    assert ms(fwd) == pytest.approx(fwd[1] / 819e9 * 1e3, rel=1e-9)
    assert ms(bwd) == pytest.approx(bwd[0] / 197e12 * 1e3, rel=1e-9)
    assert ms(fwd) == pytest.approx(0.255, rel=1e-2)
    assert ms(bwd) == pytest.approx(0.354, rel=1e-2)
    assert 9 * (ms(fwd) + ms(bwd)) == pytest.approx(5.48, rel=1e-2)  # a step

    f_fwd, f_bwd = fam.flash_needed(cfg, seq, 1)
    assert f_fwd[0] == seq * 4 * 32 * 64 * (seq + 1) / 2 and f_bwd[0] == 2 * f_fwd[0]
    assert f_fwd[1] == 2 * (2 * 32 * seq * 64 + 2 * 8 * seq * 64)


def test_new_readers_on_recorded_traces(monkeypatch):
    """The mixer's four scopes summed, the scan's alone, the dense MLP's, and
    the scan's roofline share found through the cell's shapes — from a trace
    of the kernels and from one of the ``jax.numpy`` path (XLA's fusions under
    ``ssm_scan``): the same needed work over whatever ran there; nothing to
    read, no error, on a program without the scopes (the parent) — and the
    delta net's ``gdn_scan`` is not this metric's to read."""
    from benchmark import program_trace

    mosaic = ' custom-call(), custom_call_target="tpu_custom_call"'
    step = "jit(window)/while/body/closed_call/"
    ev = lambda name, t0, dur, scope, wrap="jvp(GraniteHybridLM)": [
        name, t0, dur, {"op_name": step + f"{wrap}/layer_2/mamba/{scope}"}]
    scan = "ssm_scan/jit(_jit)/"
    bwd = "transpose(jvp(GraniteHybridLM))"
    kernel_run = [
        ev("%fusion.1 = bf16[8] fusion()", 0, 20, "ssm_proj/in_proj/dot_general"),
        ev("%fusion.2 = bf16[8] fusion()", 20, 6, "ssm_conv/mul"),
        ev("%fusion.3 = f32[8] fusion()", 26, 2, scan + "cumsum"),
        ev("%apex_ssd_fwd.4 = bf16[8]" + mosaic, 28, 8,
           scan + "apex_ssd_fwd/pallas_call"),
        ev("%fusion.5 = bf16[8] fusion()", 36, 10, "ssm_out/out_proj/dot_general"),
        ev("%apex_ssd_bwd.6 = bf16[8]" + mosaic, 46, 14,
           scan + "apex_ssd_bwd/pallas_call", bwd),
        ["%fusion.7 = bf16[8] fusion()", 60, 30,
         {"op_name": step + "jvp(GraniteHybridLM)/layer_2/dense_ffn/mlp/gate_up/dot_general"}],
    ]
    jnp_run = [
        ev("%fusion.1 = bf16[8] fusion()", 0, 20, "ssm_proj/in_proj/dot_general"),
        ev("%fusion.2 = f32[8] fusion()", 20, 30, scan + "exp"),
        ev("%fusion.3 = f32[8] fusion()", 50, 40, scan + "dot_general"),
        ev("%fusion.4 = bf16[8] fusion()", 90, 10, "ssm_out/out_proj/dot_general"),
        ev("%fusion.5 = f32[8] fusion()", 100, 50, scan + "dot_general", bwd),
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
    fam = harness.load_module(ROOT, "families", FAMILY)
    run = {"kind": "train", "trace": {"busy_s": 1}, "device_kind": "TPU v5 lite",
           "tokens_per_window": 2 * 8192,
           "flops_per_token": fam.train_flops_per_token(cfg, job["seq"])}
    read = lambda name: harness.load_module(ROOT, "layer_metrics", name).read(run)
    peaks = harness.load_json(os.path.join(ROOT, "benchmark", "peaks.json"))["TPU v5 lite"]
    needed_s = 9 * fam.needed_seconds(fam.ssd_needed(cfg, 8192, 1, 256), peaks)
    assert needed_s == pytest.approx(5.48e-3, rel=1e-2)

    use(trace_of(kernel_run))
    assert read("model.ssm_ms_per_step") == pytest.approx(60e-6 / 2)
    assert read("model.ssm_scan_ms_per_step") == pytest.approx(24e-6 / 2)
    assert read("model.dense_ffn_ms_per_step") == pytest.approx(30e-6 / 2)
    assert read("kernels.ssd_scan_roofline_pct") == pytest.approx(
        100 * needed_s / (24e-9 / 2))
    use(trace_of(jnp_run))          # the same needed work over XLA's passes
    assert read("model.ssm_ms_per_step") == pytest.approx(150e-6 / 2)
    assert read("model.ssm_scan_ms_per_step") == pytest.approx(120e-6 / 2)
    assert read("kernels.ssd_scan_roofline_pct") == pytest.approx(
        100 * needed_s / (120e-9 / 2))
    assert read("model.dense_ffn_ms_per_step") is None
    # a program without the scopes (the parent): nothing to read, no error
    use({"planes": [host]})
    for name in NEW_READERS:
        assert read(name) is None
    # the delta net's scan is another scope
    other = [[e[0], e[1], e[2], {"op_name": e[3]["op_name"].replace(
        "ssm_", "gdn_").replace("dense_ffn", "moe_shared")}] for e in kernel_run]
    use(trace_of(other))
    for name in NEW_READERS:
        assert read(name) is None
    # another cell's record (its own operations a token): not this cell
    use(trace_of(kernel_run))
    assert harness.load_module(
        ROOT, "layer_metrics", "kernels.ssd_scan_roofline_pct").read(
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
    print(f"\ngranite-h.train-8k: {N_PARAMS / 1e6:.1f}M parameters; per "
          f"device arguments {mem.argument_size_in_bytes / 2**30:.2f} GiB, "
          f"temporaries {mem.temp_size_in_bytes / 2**30:.2f} GiB, total "
          f"{total / 2**30:.2f} GiB; Mosaic calls {len(names)}: {by_kernel}")
    # 8.63 GiB of float32 masters and two Adam moments, and 3.29 GiB a step
    # holds beside them (the bfloat16 weights, the gradients, ten blocks'
    # inputs, one block's inside): 11.92 GiB of the chip's 15.75
    assert 8.6 * 2 ** 30 < mem.argument_size_in_bytes < 8.7 * 2 ** 30
    assert 2.9 * 2 ** 30 < mem.temp_size_in_bytes < 3.7 * 2 ** 30
    assert total < 12.5 * 2 ** 30
    assert not unnamed_mosaic_calls(text)
    # nine mamba layers: the scan forward, again in the recomputed block, and
    # backward — nothing of it is kept
    assert by_kernel["apex_ssd_fwd"] == 18
    assert by_kernel["apex_ssd_bwd"] == 9
    # one attention layer: one forward (its output is kept under per-block
    # recomputation), one sweep backward
    assert by_kernel["apex_flash_fwd"] == 1
    assert by_kernel["apex_flash_bwd_sweep"] == 1
    assert any("apex_xent_fwd" in n for n in names)
    # the flash call at the head's own 64: no operand padded to a lane tile
    for line in text.splitlines():
        if "tpu_custom_call" in line and "apex_flash" in line and " = " in line:
            assert set(re.findall(r"bf16\[(?:32|8),8192,(\d+)\]", line)) == {"64"}, line[:300]
    # no float32 (chunks, heads, 256, 256) decay matrix among the buffers
    assert not re.findall(r"f32\[(?:1,)?32,64,256,256\]", text)
    assert not re.findall(r"f32\[(?:1,)?64,32,256,256\]", text)
    assert obs.default_registry().get("ssd.kernel").value == 1


def test_reference_step_fits_beside_four_float32_copies(topo, no_compile_cache):
    """The plain reference's gradient of one 8192-token row, compiled for the
    described chip: its temporaries (2.84 GiB) beside the weights, the summed
    gradient and both moments (four float32 copies, 11.51 GiB) have to stay
    under the chip's 15.75 GiB — they read 5.4 GiB, and did not, before the
    reference's backward pass was made to finish a half of a block before it
    goes on (``reference/granitemoehybrid.py::recomputed``)."""
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
    assert 2.4 * 2 ** 30 < mem.temp_size_in_bytes < 3.4 * 2 ** 30
    assert copies + mem.temp_size_in_bytes < 15.0 * 2 ** 30
