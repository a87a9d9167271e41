"""Gradient-accumulation microbatching — the deferred-collective contract.

The accumulation layer's claim mirrors the fused driver's: consuming M
microbatches per optimizer step with one deferred collective changes
WHEN gradients are communicated, never WHAT is computed.  Params and
scaler trajectories must be bitwise-identical to a per-microbatch
reference loop (separate dispatch per microbatch, same fp32 accumulate
arithmetic), for M in {1, 2, 4}, with and without shard_map, and a
mid-window overflow must skip the WHOLE accumulated update while the
dynamic loss scale backs off exactly once per boundary.
"""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

import apex_tpu.amp as amp
from apex_tpu.optimizers import fused_sgd
from apex_tpu.parallel import DistributedDataParallel, replicate
from apex_tpu.parallel.mesh import shard_map_compat
from apex_tpu.train import (
    FusedTrainDriver,
    MicrobatchedStep,
    amp_microbatch_step,
    microbatches_default,
    read_metrics,
)
from apex_tpu.train.accum import build_opt_step

N_DEV = 8
N_MB = 8  # total microbatches every test consumes


def _tree_equal(a, b):
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert len(la) == len(lb)
    return all(
        np.array_equal(np.asarray(x), np.asarray(y), equal_nan=True)
        for x, y in zip(la, lb)
    )


def _setup(with_ddp):
    """AMP O2 grad_fn over a linear model; scaled grads, NO collectives."""
    amp_ = amp.initialize("O2")
    opt = amp.AmpOptimizer(fused_sgd(0.05, momentum=0.9), amp_)
    ddp = (
        DistributedDataParallel(axis_name="data", allreduce_always_fp32=True)
        if with_ddp else None
    )

    def grad_fn(carry, batch):
        params, state = carry
        x, y = batch

        def scaled(mp):
            pred = x.astype(jnp.bfloat16) @ opt.model_params(mp)["w"]
            loss = jnp.mean(jnp.square(pred.astype(jnp.float32) - y))
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        return grads, {"loss": loss}

    rng = np.random.RandomState(0)
    w0 = rng.randn(16, 4).astype(np.float32) * 0.3
    xs = rng.randn(N_MB, 32, 16).astype(np.float32)
    ys = rng.randn(N_MB, 32, 4).astype(np.float32)

    def fresh(mesh=None):
        p = {"w": jnp.asarray(w0.copy())}
        c = (p, opt.init(p))
        return (replicate(c[0], mesh), replicate(c[1], mesh)) if mesh else c

    return grad_fn, opt, ddp, fresh, jnp.asarray(xs), jnp.asarray(ys)


def _accumulate(grad, carry, xs, ys):
    """fp32 sum of ``grad``'s gradients over the microbatches ``xs, ys``."""
    acc = None
    for x, y in zip(xs, ys):
        g, _ = grad(carry, (x, y))
        g32 = jax.tree_util.tree_map(lambda t: t.astype(jnp.float32), g)
        acc = g32 if acc is None else jax.tree_util.tree_map(jnp.add, acc, g32)
    return acc


def _reference_loop(step, carry, xs, ys, *, mesh=None):
    """The per-step dispatch loop, same arithmetic as the fused path with
    no window and no scan over microbatches.  On a mesh: one jitted grad
    dispatch per microbatch, fp32 accumulate on the host-side loop, one
    jitted update dispatch per boundary (M+1 dispatches per optimizer step
    instead of 1 per window).  Off the mesh the same loop is ONE jitted
    program per optimizer step: XLA's CPU backend rounds ``momentum * buf
    + grad`` in a program that also makes the gradient as it does in the
    window's scan body, and otherwise in a stand-alone update program —
    which left 15 of 64 ``momentum_buf`` elements 1 ulp apart from the
    second step on (ROADMAP D9); inside ``shard_map`` the two programs
    already round alike."""
    m = step.microbatches
    if mesh is None:
        one_step = jax.jit(lambda c, x, y: step.update_fn(
            c, _accumulate(step.grad_fn, c, x, y))[0])
    else:
        grad_d = jax.jit(shard_map_compat(
            step.grad_fn, mesh=mesh,
            in_specs=(P(), P("data")), out_specs=(P(), P()),
            check_vma=False,
        ))
        upd_d = jax.jit(shard_map_compat(
            step.update_fn, mesh=mesh,
            in_specs=(P(), P()), out_specs=(P(), P()),
            check_vma=False,
        ))
        one_step = lambda c, x, y: upd_d(c, _accumulate(grad_d, c, x, y))[0]
    for s in range(xs.shape[0] // m):
        sl = slice(s * m, (s + 1) * m)
        carry = one_step(carry, xs[sl], ys[sl])
    return carry


class TestBitwiseParity:
    @pytest.mark.parametrize("m", [1, 2, 4])
    def test_m_sweep_matches_reference_loop(self, m):
        """Fused M-microbatch windows == the per-step dispatch loop,
        bitwise, without shard_map."""
        grad_fn, opt, _, fresh, xs, ys = _setup(with_ddp=False)
        step = amp_microbatch_step(grad_fn, opt, microbatches=m)
        driver = FusedTrainDriver(
            step, steps_per_dispatch=2,
            metrics={"loss": "mean", "scale": "last", "skipped": "sum"},
        )
        c = fresh()
        for w in range(N_MB // (2 * m)):
            sl = slice(w * 2 * m, (w + 1) * 2 * m)
            c, _ = driver.run_window(c, (xs[sl], ys[sl]))
        ref = _reference_loop(step, fresh(), xs, ys)
        assert _tree_equal(c, ref)

    @pytest.mark.parametrize("m", [2, 4])
    def test_shard_map_parity(self, mesh8, m):
        """Same bitwise contract through shard_map + the ONE deferred
        DDP allreduce per boundary."""
        grad_fn, opt, ddp, fresh, xs, ys = _setup(with_ddp=True)
        step = amp_microbatch_step(grad_fn, opt, ddp=ddp, microbatches=m)
        driver = FusedTrainDriver(
            step, steps_per_dispatch=2, mesh=mesh8, check_vma=False,
        )
        c = fresh(mesh8)
        for w in range(N_MB // (2 * m)):
            sl = slice(w * 2 * m, (w + 1) * 2 * m)
            c, _ = driver.run_window(c, (xs[sl], ys[sl]))
        ref = _reference_loop(step, fresh(mesh8), xs, ys, mesh=mesh8)
        assert _tree_equal(c, ref)


class TestAmpOverflowSkip:
    def test_mid_window_overflow_skips_whole_accumulated_update(
        self, mesh8
    ):
        """An inf in microbatch 5 (optimizer step 2 of 4, M=2) must: be
        detected on the ACCUMULATED gradient, skip that whole boundary's
        update, back the scale off exactly once, and land bitwise on the
        per-microbatch reference loop."""
        grad_fn, opt, ddp, fresh, xs, ys = _setup(with_ddp=True)
        xs = xs.at[5, 0, 0].set(jnp.inf)
        step = amp_microbatch_step(grad_fn, opt, ddp=ddp, microbatches=2)
        driver = FusedTrainDriver(
            step, steps_per_dispatch=2, mesh=mesh8, check_vma=False,
            metrics={"scale": "last", "skipped": "sum"},
        )
        c = fresh(mesh8)
        skipped = 0.0
        for w in range(2):
            sl = slice(w * 4, (w + 1) * 4)
            c, res = driver.run_window(c, (xs[sl], ys[sl]))
            skipped += read_metrics(res.metrics)["skipped"]
        assert skipped == 1.0  # exactly the one poisoned boundary

        ref = _reference_loop(step, fresh(mesh8), xs, ys, mesh=mesh8)
        assert _tree_equal(c, ref)
        _, state = c
        assert float(state.scaler[0].loss_scale) == 2.0 ** 15
        assert int(state.scaler[0].overflows) == 1

    def test_skipped_boundary_leaves_params_unchanged(self):
        """The whole M-microbatch update is gated, not just the poisoned
        microbatch's share."""
        grad_fn, opt, _, fresh, xs, ys = _setup(with_ddp=False)
        xs = xs.at[1, 0, 0].set(jnp.nan)  # second microbatch of step 0
        step = amp_microbatch_step(grad_fn, opt, microbatches=2)
        driver = FusedTrainDriver(step, steps_per_dispatch=1)
        c0 = fresh()
        w0 = np.asarray(c0[0]["w"])
        c1, res = driver.run_window(c0, (xs[:2], ys[:2]))
        np.testing.assert_array_equal(np.asarray(c1[0]["w"]), w0)
        assert read_metrics(res.metrics)["skipped"] == 1.0


class TestAccumDtype:
    def test_bf16_compensated_tracks_fp32(self):
        """Kahan-compensated bf16 accumulation stays close to the fp32
        buffer (and the driver accepts the knob end-to-end)."""
        grad_fn, opt, _, fresh, xs, ys = _setup(with_ddp=False)

        def run(accum_dtype):
            step = amp_microbatch_step(
                grad_fn, opt, microbatches=4, accum_dtype=accum_dtype
            )
            driver = FusedTrainDriver(step, steps_per_dispatch=2)
            c = fresh()
            c, _ = driver.run_window(c, (xs, ys))
            return np.asarray(c[0]["w"])

        w32, wbf = run("float32"), run("bf16_compensated")
        assert np.all(np.isfinite(wbf))
        np.testing.assert_allclose(wbf, w32, rtol=2e-2, atol=2e-3)

    def test_unknown_accum_dtype_rejected(self):
        grad_fn, opt, _, _, _, _ = _setup(with_ddp=False)
        with pytest.raises(ValueError):
            amp_microbatch_step(grad_fn, opt, microbatches=2,
                                accum_dtype="float16")


class TestContract:
    def test_env_default(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_MICROBATCHES", "3")
        assert microbatches_default() == 3
        assert microbatches_default(5) == 5
        monkeypatch.delenv("APEX_TPU_MICROBATCHES")
        assert microbatches_default() == 1

    def test_window_len_divisibility(self):
        grad_fn, opt, _, fresh, xs, ys = _setup(with_ddp=False)
        step = amp_microbatch_step(grad_fn, opt, microbatches=4)
        driver = FusedTrainDriver(step, steps_per_dispatch=2)
        assert driver.microbatches == 4
        with pytest.raises(ValueError):
            driver.run_window(fresh(), (xs[:6], ys[:6]))  # 6 % 4 != 0

    def test_bad_microbatch_count_rejected(self):
        step = MicrobatchedStep(
            lambda c, b: (c, {}), lambda c, a: (c, {}), microbatches=0
        )
        with pytest.raises(ValueError):
            build_opt_step(step)

    def test_metric_name_clash_rejected(self):
        step = MicrobatchedStep(
            lambda c, b: (jnp.float32(0.0), {"scale": jnp.float32(1.0)}),
            lambda c, a: (c, {"scale": jnp.float32(1.0)}),
            microbatches=2,
        )
        driver = FusedTrainDriver(step, steps_per_dispatch=1)
        with pytest.raises(ValueError):
            driver.run_window(jnp.float32(0.0))

    def test_closure_data_mode(self):
        """batches=None: grad_fn runs M times per step on captured data."""
        calls = []

        def grad_fn(carry, batch):
            assert batch is None
            return {"g": jnp.float32(1.0)}, {"loss": jnp.float32(0.0)}

        def update_fn(carry, acc):
            return carry + acc["g"], {"acc": acc["g"]}

        step = MicrobatchedStep(grad_fn, update_fn, microbatches=3)
        driver = FusedTrainDriver(step, steps_per_dispatch=2)
        carry, res = driver.run_window(jnp.float32(0.0))
        # 2 steps x (sum of 3 unit grads) accumulated into the carry
        assert float(carry) == 6.0
        assert read_metrics(res.metrics)["acc"] == 3.0
