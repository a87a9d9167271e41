"""chip_smoke.py's control flow, rehearsed on the CPU at a tiny size.

This is rehearsal 1 of the on-chip-measurement guide (§2): wrong paths,
arguments and control flow are found here at no chip time.  The only
things that differ from the chip are the two device facts — which device
JAX reports, and whether Mosaic calls are compiled in — and they differ
HERE, in the harness (``rehearse``), not through an option of the script
that could ship a CPU pass.  A pass of these tests is not a chip run.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    model=dict(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4),
    ctx=128, kernel_batch=1, train_batch=2,
    steps_per_dispatch=2, windows=3, slots=6,
    prompt_lens=(5, 20, 70), prefix_len=32, prefix_tails=(3, 9),
    new_tokens=20, compared=2, dp_batch=4, dp_steps_per_dispatch=2,
    tp_prompt_lens=(5, 20), tp_new_tokens=6,
)
FAKE_TPU = {"platform": "tpu", "kind": "rehearsal (CPU)", "count": 1}


@pytest.fixture
def rehearse(monkeypatch, tmp_path):
    """Stand in for the chip's two device facts, and keep the persistent
    cache out of the checkout: with ``JAX_COMPILATION_CACHE_DIR`` set the
    script sets no directory, so this process's jax config is untouched."""
    import jax

    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: dict(FAKE_TPU))
    monkeypatch.setattr(chip_smoke, "mosaic_call_count", lambda compiled: 10**6)
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "cache"))
    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs", threshold)


def _lines(capsys):
    return [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
            if ln.startswith("{")]


def test_phases_run_in_order_and_last_line_is_the_contract(rehearse, capsys):
    assert chip_smoke.main([], sizes=TINY) == 0
    lines = _lines(capsys)
    assert [ln["phase"] for ln in lines[:-1] if "phase" in ln] == \
        ["kernels", "train", "serve"]
    assert lines[-1] == {"ok": True, "device": FAKE_TPU}
    assert list(lines[-1]) == ["ok", "device"]          # nothing more
    kernels, train, serve = (ln for ln in lines if "phase" in ln)
    for ln in (kernels, train, serve):
        assert {"wall_s", "compile_s", "compiles", "cache",
                "mosaic_calls"} <= set(ln)
        assert ln["cache"]["from_env"] is True
    assert set(kernels["mosaic_calls"]) == {
        "flash", "layer_norm", "xentropy", "flash_window_grouped",
        "grouped_mm", "moe_dispatch", "gated_delta", "flash_latent", "conv1d",
        "gated_conv", "ssd", "kda", "kda_raw", "ssm_conv", "dense_ffn_block",
        *(f"qk_heads.{call[0]}" for call in chip_smoke.QK_HEADS_CALLS)}
    # the backward's two routes at the three 8k cells' calls, timed and held
    # to each other and to the reference
    routes = kernels["flash_backward"]
    assert set(routes) == {"moonlight", "trinity_window", "trinity_full",
                           "qwen3_next", "smallthinker_window",
                           "smallthinker_full"}
    # groups of seven over twice the others' positions
    assert routes["smallthinker_window"]["shape"][:3] == [7, 1, 16 * TINY.ctx]
    for rec in routes.values():
        assert {"fwd_us", "shipped_grad_us", "two_pass_grad_us",
                "shipped_kernels", "two_pass_kernels"} <= set(rec)
    assert "flash_backward.trinity_window.dk_vs_two_pass" in kernels["parity"]
    # one layer's call of each of the seven cells: the dense cells' and
    # LFM2's timed here, the other four the records above
    stats = kernels["flash_stats_at_cell"]
    assert len(stats) == 7
    assert stats["gpt2-small.train"]["shape"] == [
        TINY.ctx // 64, 1, 1, TINY.ctx, 64, 64, None, True, False]
    assert stats["bert-large.train"]["shape"][-2:] == [False, True]
    assert stats["lfm2.train-16k"]["shape"][:3] == [4, 1, 16 * TINY.ctx]
    assert stats["moonlight.train-8k"] == routes["moonlight"]
    for cell, rec in stats.items():
        assert {"fwd_us", "shipped_grad_us", "shipped_kernels"} <= set(rec)
    # one key block asks no budget: the one route is the only one
    assert "two_pass_grad_us" not in stats["bert-large.train"]
    assert "two_pass_grad_us" in stats["lfm2.train-16k"]
    for cell in ("gpt2-small.train", "bert-large.train", "lfm2.train-16k"):
        assert f"flash_stats_at_cell.{cell}.dq_vs_ref" in kernels["parity"]
    # the row movement at hidden 2560: every pass timed on both paths and
    # held to the other
    rows = kernels["moe_rows_at_2560"]
    assert rows["shape"] == [2 * TINY.kernel_batch * TINY.ctx, 6, 2560, 8, 64]
    assert 0 < rows["rows_live"] < rows["rows_capacity"]
    for name in ("gather", "gather_grad", "combine", "combine_grad"):
        assert {f"{name}_kernels_us", f"{name}_take_us"} <= set(rows)
        assert f"moe_rows_at_2560.{name}.0" in kernels["parity"]
    # the grouped products at a cell's two calls, in the worst-case buffer
    # and in one with no dead tail: every pass timed alone, held to ragged_dot
    at_cell = kernels["grouped_mm_at_cell"]
    assert at_cell["gate_up.worst_case"]["tiles"] >= 4 * at_cell["tiles_live"]
    assert at_cell["down.exact"]["tiles"] == at_cell["tiles_live"]
    for product in ("gate_up", "down"):
        for buffer in ("worst_case", "exact"):
            assert {"fwd_us", "dx_us", "dw_us"} <= set(
                at_cell[f"{product}.{buffer}"])
            for name in ("fwd", "dx", "dw"):
                assert (f"grouped_mm_at_cell.{product}.{buffer}.{name}"
                        in kernels["parity"])
    # the gated short convolution at the LFM2 cell's call: both routes (the
    # kernels, the jax.numpy form) timed forward and with gradients, and the
    # kernels held to the form
    gated = kernels["gated_conv_at_cell"]
    assert gated["shape"] == [1, 16 * TINY.ctx, 6 * TINY.ctx, 3]
    assert {"fwd_kernels_us", "grad_kernels_us", "fwd_jnp_us",
            "grad_jnp_us"} <= set(gated)
    for name in ("fwd", "dx", "dw"):
        assert f"gated_conv.{name}" in kernels["parity"]
    # the state-space scan at the Granite cell's call: both routes timed
    # forward and with gradients, the kernels held to the token recurrence in
    # the forward and all six gradients
    scan = kernels["ssd_at_cell"]
    assert scan["shape"] == [1, 8 * TINY.ctx, TINY.ctx // 16, 64, 128, 256]
    assert {"fwd_kernels_us", "grad_kernels_us", "fwd_jnp_us",
            "grad_jnp_us", "kernels"} <= set(scan)
    for name in ("oracle_in_blocks", "fwd", "dx", "ddt", "dA", "dB", "dC", "dD"):
        assert f"ssd.{name}" in kernels["parity"]
    # the delta rule with a decay a key channel at the Kimi Linear cell's
    # call: both routes timed, the kernels held to the token recurrence in
    # the forward and to the scan path in all five gradients
    rule = kernels["kda_at_cell"]
    assert rule["shape"] == [1, 8 * TINY.ctx, max(TINY.ctx // 32, 1), 128, 64, 16]
    assert {"grad_kernels_us", "grad_kernels_raw_us", "grad_scan_us",
            "kernels"} <= set(rule)
    for name in ("scan_is_the_recurrence", "fwd", "dq", "dk", "dv", "dg", "dbeta"):
        assert f"kda.{name}" in kernels["parity"]
    # and as the model calls it: q and k normalised inside the kernels
    for name in ("fwd", "dq", "dk", "dv", "dg", "dbeta"):
        assert f"kda.raw.{name}" in kernels["parity"]
    # the convolution in front of it, x, B and C read out of in_proj's output
    conv = kernels["ssm_conv_at_cell"]
    assert conv["shape"] == [1, 8 * TINY.ctx, 8 * TINY.ctx + 256
                             + TINY.ctx // 16, 4]
    assert {"fwd_kernels_us", "grad_kernels_us", "fwd_jnp_us",
            "grad_jnp_us", "kernels"} <= set(conv)
    for name in ("fwd", "dx", "dw", "dbias"):
        assert f"ssm_conv.{name}" in kernels["parity"]
    # one block of that cell under full_block: its gradient runs three
    # products of gate_up's size, timed in a trace
    block = kernels["dense_ffn_at_cell"]
    assert block["shape"] == [1, 8 * TINY.ctx, 2 * TINY.ctx, 8 * TINY.ctx]
    assert block["gate_up_products"] == 3
    assert 0 < block["gate_up_ms"] < block["dense_ffn_ms"] < block["device_ms"]
    assert block["block_ms"] > 0
    # q, k and v on their way from the projection to the flash kernels at
    # Trinity's and SmallThinker's calls, both paths timed
    heads = kernels["qk_heads_at_cell"]
    assert list(heads) == [c[0] for c in chip_smoke.QK_HEADS_CALLS]
    assert heads["trinity_window"]["shape"] == [1, 8 * TINY.ctx, 9216, 32, 4]
    assert heads["smallthinker_full"]["shape"] == [1, 16 * TINY.ctx, 4608, 28, 4]
    for call, rec in heads.items():
        assert {f"{what}_{side}_us" for what in ("fwd", "grad")
                for side in ("kernels", "composed")} <= set(rec)
        assert rec["fwd_kernels_gb_per_s"] > 0 < rec["grad_bytes"]
        for name in ("q", "k", "v", "dx") + (
                ("dq_norm", "dk_norm", "rest") if rec["norm"] else ()):
            assert f"qk_heads.{call}.{name}" in kernels["parity"]
    # the routing plan at the five sparse cells' shapes: one making, its dear
    # parts and each lookup both ways timed, the tables equal to the bit
    plans = kernels["moe_plan_at_cell"]
    assert list(plans) == [c[0] for c in chip_smoke.MOE_PLAN_CELLS]
    assert plans["qwen3-next.train-8k"]["shape"] == [8 * TINY.ctx, 10, 512, 32, 256]
    assert plans["smallthinker.train-16k"]["shape"][0] == 16 * TINY.ctx
    for cell, rec in plans.items():
        assert 0 < rec["rows_live"] < rec["rows_capacity"]
        assert rec["picked_same_bits"] is True
        assert {f"{name}_us" for name in (
            "making", "top_k", "running_count", "argsort", "slot_row_gather",
            "slot_row_sum", "picked_gather", "picked_sum", "row_slot_gathers",
            "row_slot_sums", "row_slot_sums_shifts")} <= set(rec)
        assert f"moe_plan_at_cell.{cell}.picked.dlogits" in kernels["parity"]
    assert train["loss_per_window"][-1] < train["loss_per_window"][0]
    assert train["compiles_after_first_window"] == 0
    assert serve["compiles_after_warmup"] == 0
    assert serve["tokens"] == 5 * TINY.new_tokens
    assert serve["prefix_hit_tokens"] > 0
    assert all(c["tokens_identical"] for c in serve["reference"])


def test_the_scan_probes_blocked_oracle_is_the_token_recurrence():
    """``ssd_at_cell`` takes its six gradients from the recurrence walked in
    recomputed blocks, because ``ssd_recurrent``'s own gradient keeps a state
    a token and does not fit the chip at the cell's call: at a size where
    both fit they are one function, forward and every gradient."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from apex_tpu.ops.ssd import ssd_recurrent

    keys = jax.random.split(jax.random.PRNGKey(43), 7)
    b, s, h, p, n = 2, 96, 4, 8, 16
    args = (jax.random.normal(keys[0], (b, s, h, p)),
            jax.nn.softplus(jax.random.normal(keys[1], (b, s, h)) - 2.0),
            -jax.random.uniform(keys[2], (h,), minval=1.0, maxval=16.0),
            jax.random.normal(keys[3], (b, s, 1, n)),
            jax.random.normal(keys[4], (b, s, 1, n)),
            jax.random.normal(keys[5], (h,)))
    cot = jax.random.normal(keys[6], (b, s, h, p))

    def both(fn):
        return jax.value_and_grad(
            lambda *a: jnp.sum(fn(*a) * cot), tuple(range(6)))(*args)

    want, want_grads = both(ssd_recurrent)
    got, got_grads = both(lambda *a: chip_smoke._token_recurrence_in_blocks(
        *a, block=32))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(got_grads, want_grads):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6)


def test_planted_failure_gives_nonzero_exit_and_no_ok_line(
        rehearse, monkeypatch, capsys):
    """A check that fails in a phase raises out of main (a nonzero exit
    from the command line): its line says ``failed`` and keeps what was
    measured up to there, later phases never start, no ok line."""
    ran = []
    monkeypatch.setattr(
        chip_smoke, "phase_kernels",
        lambda sizes, seed, facts: ran.append("kernels"),
    )

    def failing_train(sizes, seed, facts, handoff):
        ran.append("train")
        facts["loss_per_window"] = [9.0]
        chip_smoke._require(False, "planted")

    monkeypatch.setattr(chip_smoke, "phase_train", failing_train)
    monkeypatch.setattr(
        chip_smoke, "phase_serve", lambda *a: ran.append("serve"),
    )
    with pytest.raises(chip_smoke.SmokeFailure, match="planted"):
        chip_smoke.main([], sizes=TINY)
    assert ran == ["kernels", "train"]
    lines = _lines(capsys)
    assert [ln["phase"] for ln in lines] == ["kernels", "train"]
    assert "failed" not in lines[0]
    assert lines[1]["failed"] == "SmokeFailure: planted"
    assert lines[1]["loss_per_window"] == [9.0]
    assert not any("ok" in ln for ln in lines)


def test_missing_mosaic_call_fails_the_phase(rehearse, monkeypatch):
    """On the CPU the kernels are their references: without the
    harness's stand-in the very first compiled program is refused."""
    from apex_tpu.ops import mosaic_call_count

    monkeypatch.setattr(chip_smoke, "mosaic_call_count", mosaic_call_count)
    with pytest.raises(chip_smoke.SmokeFailure, match="tpu_custom_call"):
        chip_smoke.main([], sizes=TINY)


def test_device_check_refuses_the_cpu():
    """The shipped command, no harness: no TPU, no run — a nonzero exit
    within seconds and no result line."""
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "no TPU" in proc.stderr


def test_four_chip_option_needs_four_devices(rehearse):
    with pytest.raises(SystemExit, match="--chips 4 needs 4 devices"):
        chip_smoke.main(["--chips", "4"], sizes=TINY)


def test_four_chip_phases_on_virtual_devices(rehearse, monkeypatch, capsys):
    """Rehearsal 2: the path across chips on four of conftest's virtual
    CPU devices — only the two comparisons run, and the last line carries
    the count the device check reported."""
    four = dict(FAKE_TPU, count=4)
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: dict(four))
    assert chip_smoke.main(["--chips", "4"], sizes=TINY) == 0
    lines = _lines(capsys)
    assert [ln["phase"] for ln in lines if "phase" in ln] == ["dp", "tp"]
    dp, tp = (ln for ln in lines if "phase" in ln)
    assert dp["devices_holding_carry"] == 4
    assert dp["loss_per_step_mesh"] != [] and dp["loss_max_rel_diff"] <= 1e-5
    assert tp["devices_holding_kv_pool"] == 4 and tp["tokens_identical"]
    assert lines[-1] == {"ok": True, "device": four}


def test_compile_cache_dir_honours_env_else_fixed_checkout_path(monkeypatch):
    import jax

    from apex_tpu.chip import compile_cache_dir

    updates = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: updates.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache_dir(ROOT) == "/somewhere/else"
    assert updates == []                 # no other directory set in code
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    fixed = os.path.join(ROOT, ".jax_cache")
    assert compile_cache_dir(ROOT) == fixed
    assert compile_cache_dir(ROOT + "/") == fixed      # same path each time
    assert updates == [("jax_compilation_cache_dir", fixed)] * 2
