"""Model zoo smoke + training-sanity tests (tiny configs, CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.amp as amp
from apex_tpu.models import (
    BertConfig,
    BertForMLM,
    Discriminator,
    Generator,
    ResNet,
    resnet50,
)
from apex_tpu.optimizers import fused_adam, fused_lamb, fused_sgd
from apex_tpu.train import FusedTrainDriver, read_metrics


class TestResNet:
    def test_rn50_param_count(self):
        m = resnet50(num_classes=1000)
        v = m.init(jax.random.PRNGKey(0), jnp.ones((1, 64, 64, 3)))
        n = sum(x.size for x in jax.tree_util.tree_leaves(v["params"]))
        assert abs(n - 25.56e6) < 0.1e6  # torchvision RN50 = 25,557,032

    def test_space_to_depth_stem_exact(self, rng):
        """s2d stem == plain 7x7/s2 conv: same param, same math, same
        checkpoint layout — forward and input gradient."""
        from apex_tpu.models.resnet import SpaceToDepthStem
        from apex_tpu.amp.layers import Conv

        x = jnp.asarray(rng.randn(2, 32, 32, 3).astype(np.float32))
        stem = SpaceToDepthStem(16)
        plain = Conv(16, (7, 7), (2, 2), padding=[(3, 3), (3, 3)],
                     use_bias=False)
        params = stem.init(jax.random.PRNGKey(0), x)
        out_s2d = stem.apply(params, x)
        out_plain = plain.apply(params, x)  # identical param pytree
        assert out_s2d.shape == out_plain.shape == (2, 16, 16, 16)
        np.testing.assert_allclose(np.asarray(out_s2d), np.asarray(out_plain),
                                   atol=1e-5, rtol=1e-5)
        dy = jnp.asarray(rng.randn(*out_s2d.shape).astype(np.float32))
        g_s2d = jax.grad(
            lambda p, x: jnp.sum(stem.apply(p, x) * dy), argnums=(0, 1)
        )(params, x)
        g_plain = jax.grad(
            lambda p, x: jnp.sum(plain.apply(p, x) * dy), argnums=(0, 1)
        )(params, x)
        for a, b in zip(jax.tree_util.tree_leaves(g_s2d),
                        jax.tree_util.tree_leaves(g_plain)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4)

    def test_space_to_depth_stem_odd_fallback(self, rng):
        from apex_tpu.models.resnet import SpaceToDepthStem

        x = jnp.asarray(rng.randn(1, 31, 31, 3).astype(np.float32))
        stem = SpaceToDepthStem(8)
        params = stem.init(jax.random.PRNGKey(0), x)
        assert stem.apply(params, x).shape == (1, 16, 16, 8)

    def test_tiny_resnet_trains(self, rng):
        m = ResNet(stage_sizes=(1, 1), num_classes=4, width=8,
                   compute_dtype=jnp.float32)
        x = jnp.asarray(rng.randn(8, 32, 32, 3).astype(np.float32))
        y = jnp.asarray(rng.randint(0, 4, size=(8,)))
        v = m.init(jax.random.PRNGKey(0), x[:1])
        params, bstats = v["params"], v["batch_stats"]
        tx = fused_adam(1e-2)
        ost = tx.init(params)

        @jax.jit
        def step(params, bstats, ost):
            def loss_fn(p):
                logits, upd = m.apply({"params": p, "batch_stats": bstats},
                                      x, train=True, mutable=["batch_stats"])
                logp = jax.nn.log_softmax(logits)
                return -jnp.mean(jnp.take_along_axis(logp, y[:, None], 1)), upd
            (loss, upd), g = jax.value_and_grad(loss_fn, has_aux=True)(params)
            u, ost2 = tx.update(g, ost, params)
            return jax.tree_util.tree_map(lambda a, b: a + b, params, u), \
                upd["batch_stats"], ost2, loss

        losses = []
        for _ in range(10):
            params, bstats, ost, loss = step(params, bstats, ost)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_o2_sgd_window_through_the_fused_driver(self, rng):
        """The convolution + batch-norm training step of BASELINE.md's
        config 2 (O2, FusedSGD with momentum and weight decay, the BN
        statistics riding the donated carry, K steps a dispatch) at a
        width tier-1 can afford: finite loss, no skipped step, masters
        and BN statistics still fp32 after the window."""
        from apex_tpu.ops import softmax_cross_entropy

        amp_ = amp.initialize("O2")
        m = ResNet(stage_sizes=(1, 1), num_classes=4, width=8,
                   compute_dtype=amp_.policy.compute_dtype)
        opt = amp.AmpOptimizer(
            fused_sgd(0.1, momentum=0.9, weight_decay=1e-4), amp_)
        x = jnp.asarray(rng.randn(4, 32, 32, 3).astype(np.float32))
        y = jnp.asarray(rng.randint(0, 4, size=(4,)))
        v = jax.jit(m.init)(jax.random.PRNGKey(0), x[:1])
        params, bstats = v["params"], v["batch_stats"]

        def step(carry, _):
            params, bstats, state = carry

            def scaled(mp):
                logits, upd = m.apply(
                    {"params": opt.model_params(mp),
                     "batch_stats": bstats},
                    x, train=True, mutable=["batch_stats"])
                loss = jnp.mean(softmax_cross_entropy(logits, y))
                return (amp_.scale_loss(loss, state.scaler[0]),
                        (loss, upd["batch_stats"]))

            grads, (loss, bstats) = jax.grad(scaled, has_aux=True)(params)
            params, state, stats = opt.step(grads, state, params)
            return (params, bstats, state), {
                "loss": loss, "skipped": stats.found_inf}

        driver = FusedTrainDriver(
            step, steps_per_dispatch=3,
            metrics={"loss": "last", "skipped": "sum"})
        w0 = np.asarray(jax.tree_util.tree_leaves(params)[0]).copy()
        carry, res = driver.run_window((params, bstats, opt.init(params)))
        got = read_metrics(res.metrics)
        assert np.isfinite(got["loss"]) and got["skipped"] == 0
        params, bstats, state = carry
        leaves = jax.tree_util.tree_leaves((params, bstats))
        assert all(a.dtype == jnp.float32 for a in leaves)
        assert not np.array_equal(
            np.asarray(jax.tree_util.tree_leaves(params)[0]), w0)
        assert int(state.scaler[0].unskipped) == 3

    def test_bf16_compute_fp32_logits(self, rng):
        m = ResNet(stage_sizes=(1,), num_classes=4, width=8,
                   compute_dtype=jnp.bfloat16)
        x = jnp.asarray(rng.randn(2, 32, 32, 3).astype(np.float32))
        v = m.init(jax.random.PRNGKey(0), x)
        out = m.apply(v, x, train=False, mutable=False)
        assert out.dtype == jnp.float32  # loss path fp32 (amp FP32 list)


class TestBert:
    def test_mlm_trains_with_lamb(self, rng):
        cfg = BertConfig.tiny(compute_dtype=jnp.float32)
        m = BertForMLM(cfg)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(2, 128)))
        labels = jnp.where(
            jnp.asarray(rng.rand(2, 128)) < 0.15, ids, -100
        )
        v = m.init(jax.random.PRNGKey(0), ids, labels)
        params = v["params"]
        tx = fused_lamb(1e-2)
        ost = tx.init(params)

        @jax.jit
        def step(params, ost):
            def loss_fn(p):
                _, loss = m.apply({"params": p}, ids, labels)
                return loss
            loss, g = jax.value_and_grad(loss_fn)(params)
            u, ost2 = tx.update(g, ost, params)
            return jax.tree_util.tree_map(lambda a, b: a + b, params, u), ost2, loss

        losses = []
        for _ in range(8):
            params, ost, loss = step(params, ost)
            losses.append(float(loss))
        assert losses[-1] < losses[0] * 0.8

    def test_attention_mask_changes_output(self, rng):
        cfg = BertConfig.tiny(compute_dtype=jnp.float32)
        m = BertForMLM(cfg)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 128)))
        v = m.init(jax.random.PRNGKey(0), ids)
        full = m.apply(v, ids)
        mask = jnp.ones((1, 128)).at[:, 64:].set(0)
        masked = m.apply(v, ids, attention_mask=mask)
        assert not np.allclose(np.asarray(full[:, :64]), np.asarray(masked[:, :64]),
                               atol=1e-5)


class TestDCGAN:
    def test_shapes_and_one_gan_step(self, rng):
        g, d = Generator(nz=16, ngf=8), Discriminator(ndf=8)
        z = jnp.asarray(rng.randn(2, 1, 1, 16).astype(np.float32))
        gv = g.init(jax.random.PRNGKey(0), z)
        img, _ = g.apply(gv, z, mutable=["batch_stats"])
        assert img.shape == (2, 64, 64, 3)
        assert float(jnp.max(jnp.abs(img))) <= 1.0
        dv = d.init(jax.random.PRNGKey(1), img)
        logits, _ = d.apply(dv, img, mutable=["batch_stats"])
        assert logits.shape == (2,)

    @pytest.mark.parametrize("opt_level", ["O2", "O0"])
    def test_three_scaler_gan_window_through_the_fused_driver(
            self, rng, opt_level):
        """BASELINE.md's config 5 as `examples/dcgan` runs it: one
        G + D iteration is three losses under three loss scalers
        (`loss_id` 0/1/2) and two optimizers, the discriminator's two
        gradients accumulated into one step, K iterations a dispatch.
        Finite losses, both nets moved, and each scaler counted only
        its own loss's clean steps."""
        from apex_tpu.amp import F

        amp_ = amp.initialize(opt_level, num_losses=3)
        dt = amp_.policy.compute_dtype
        netG = Generator(nz=16, ngf=8, compute_dtype=dt)
        netD = Discriminator(ndf=8, compute_dtype=dt)
        optG = amp.AmpOptimizer(fused_adam(2e-4, betas=(0.5, 0.999)), amp_)
        optD = amp.AmpOptimizer(fused_adam(2e-4, betas=(0.5, 0.999)), amp_)
        real = jnp.asarray(rng.rand(2, 64, 64, 3) * 2 - 1, jnp.float32)
        z = jnp.asarray(rng.randn(2, 1, 1, 16), jnp.float32)
        gv = jax.jit(netG.init)(jax.random.PRNGKey(0), z)
        dv = jax.jit(netD.init)(jax.random.PRNGKey(1), real)
        bce = F.binary_cross_entropy_with_logits

        def step(carry, _):
            gp, gs, gstate, dp, ds, dstate = carry
            fake, _ = netG.apply({"params": gp, "batch_stats": gs}, z,
                                 mutable=["batch_stats"])

            def d_loss(p, stats, img, target, loss_id):
                out, upd = netD.apply(
                    {"params": optD.model_params(p), "batch_stats": stats},
                    img, mutable=["batch_stats"])
                loss = bce(out, jnp.full_like(out, target))
                return (amp_.scale_loss(loss, dstate.scaler[loss_id],
                                        loss_id=loss_id),
                        (loss, upd["batch_stats"]))

            g_real, (err_real, ds) = jax.grad(d_loss, has_aux=True)(
                dp, ds, real, 1.0, 0)
            g_fake, (err_fake, ds) = jax.grad(d_loss, has_aux=True)(
                dp, ds, fake, 0.0, 1)
            dstate = optD.accumulate(g_real, dstate, loss_id=0)
            dp, dstate, _ = optD.step(g_fake, dstate, dp, loss_id=1)

            def g_loss(p):
                img, upd = netG.apply(
                    {"params": optG.model_params(p), "batch_stats": gs},
                    z, mutable=["batch_stats"])
                out, _ = netD.apply({"params": dp, "batch_stats": ds},
                                    img, mutable=["batch_stats"])
                loss = bce(out, jnp.ones_like(out))
                return (amp_.scale_loss(loss, gstate.scaler[2], loss_id=2),
                        (loss, upd["batch_stats"]))

            g_g, (err_g, gs) = jax.grad(g_loss, has_aux=True)(gp)
            gp, gstate, _ = optG.step(g_g, gstate, gp, loss_id=2)
            return (gp, gs, gstate, dp, ds, dstate), {
                "errD": err_real + err_fake, "errG": err_g}

        driver = FusedTrainDriver(
            step, steps_per_dispatch=2,
            metrics={"errD": "last", "errG": "last"})
        g0 = np.asarray(jax.tree_util.tree_leaves(gv["params"])[0]).copy()
        d0 = np.asarray(jax.tree_util.tree_leaves(dv["params"])[0]).copy()
        carry, res = driver.run_window(
            (gv["params"], gv["batch_stats"], optG.init(gv["params"]),
             dv["params"], dv["batch_stats"], optD.init(dv["params"])))
        got = read_metrics(res.metrics)
        assert np.isfinite(got["errD"]) and np.isfinite(got["errG"])
        gp, _, gstate, dp, _, dstate = carry
        assert not np.array_equal(
            np.asarray(jax.tree_util.tree_leaves(gp)[0]), g0)
        assert not np.array_equal(
            np.asarray(jax.tree_util.tree_leaves(dp)[0]), d0)
        # each optimizer advanced only the scalers of its own losses
        # (a static O0 scaler counts nothing), and none saw an overflow
        clean = 2 if opt_level == "O2" else 0
        assert [int(s.unskipped) for s in dstate.scaler] == [clean, clean, 0]
        assert [int(s.unskipped) for s in gstate.scaler] == [0, 0, clean]
        assert not any(int(s.overflows)
                       for s in dstate.scaler + gstate.scaler)


class TestGPT:
    def test_lm_trains_with_adam(self, rng):
        from apex_tpu.models import GPTConfig, GPTLM

        cfg = GPTConfig.tiny(compute_dtype=jnp.float32)
        model = GPTLM(cfg)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(2, 32)))
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((2, 1), -100)], axis=1
        )
        v = model.init(jax.random.PRNGKey(0), ids, labels=labels)
        params = v["params"]
        tx = fused_adam(1e-3)
        ost = tx.init(params)

        @jax.jit
        def step(params, ost):
            def loss_fn(p):
                _, loss = model.apply({"params": p}, ids, labels=labels)
                return loss

            loss, g = jax.value_and_grad(loss_fn)(params)
            u, ost2 = tx.update(g, ost, params)
            return (
                jax.tree_util.tree_map(lambda a, b: a + b, params, u),
                ost2, loss,
            )

        losses = []
        for _ in range(8):
            params, ost, loss = step(params, ost)
            losses.append(float(loss))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0]

    def test_dropout_training_path(self, rng):
        """deterministic=False exercises embed/residual/attention dropout
        (the bench's real training configuration)."""
        from apex_tpu.models import GPTConfig, GPTLM

        cfg = GPTConfig.tiny(compute_dtype=jnp.float32)
        model = GPTLM(cfg)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(2, 32)))
        labels = jnp.concatenate(
            [ids[:, 1:], jnp.full((2, 1), -100)], axis=1
        )
        v = model.init(jax.random.PRNGKey(0), ids, labels=labels)
        _, loss = model.apply(
            v, ids, labels=labels, deterministic=False,
            rngs={"dropout": jax.random.PRNGKey(1)},
        )
        assert np.isfinite(float(loss))

    def test_causality(self, rng):
        """Perturbing a future token must not change earlier logits."""
        from apex_tpu.models import GPTConfig, GPTLM

        cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                             attn_dropout_rate=0.0)
        model = GPTLM(cfg)
        ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 16)))
        params = model.init(jax.random.PRNGKey(0), ids)
        base = model.apply(params, ids)
        ids2 = ids.at[0, 10].set((int(ids[0, 10]) + 1) % cfg.vocab_size)
        pert = model.apply(params, ids2)
        np.testing.assert_allclose(
            np.asarray(base[:, :10]), np.asarray(pert[:, :10]),
            atol=1e-5, rtol=1e-5,
        )
        assert not np.allclose(np.asarray(base[:, 10:]),
                               np.asarray(pert[:, 10:]))

    @pytest.mark.parametrize("kernels", [False, True],
                             ids=["reference", "pallas"])
    @pytest.mark.parametrize("family", ["gpt", "bert"])
    def test_remat_policy_preserves_params_and_grads(self, rng, family,
                                                     kernels):
        """remat_policy is a free A/B: every policy binds the same param
        structure as "none" and produces matching loss + grads — only
        the backward's memory/compute schedule changes.  With the kernels
        (interpret mode) the block-recomputing policies keep the flash
        forward's output and lse (tests/test_remat.py) and the gradients
        are still those of "none"."""
        from apex_tpu.models import BertConfig, BertForMLM, GPTConfig, GPTLM
        from apex_tpu.ops._common import force_pallas

        ids = jnp.asarray(rng.randint(0, 1024, size=(2, 32)))
        if family == "gpt":
            config, lm = GPTConfig, GPTLM
            labels = jnp.concatenate(
                [ids[:, 1:], jnp.full((2, 1), -100)], axis=1
            )
        else:
            config, lm = BertConfig, BertForMLM
            labels = jnp.asarray(
                np.where(rng.rand(2, 32) < 0.15, np.asarray(ids), -100))

        def loss_and_grads(policy, params=None):
            cfg = config.tiny(compute_dtype=jnp.float32,
                              remat_policy=policy)
            model = lm(cfg)
            if params is None:
                params = model.init(jax.random.PRNGKey(0), ids,
                                    labels=labels)
            loss, g = jax.value_and_grad(
                lambda p: model.apply(p, ids, labels=labels)[1]
            )(params)
            return params, float(loss), g

        with force_pallas(kernels):
            params, loss0, g0 = loss_and_grads("none")
            for policy in ("dots_saveable", "full_block"):
                p2, loss, g = loss_and_grads(policy, params)
                assert loss == pytest.approx(loss0, rel=1e-6)
                for a, b in zip(jax.tree_util.tree_leaves(g0),
                                jax.tree_util.tree_leaves(g)):
                    np.testing.assert_allclose(
                        np.asarray(a), np.asarray(b), atol=1e-5, rtol=1e-5
                    )

    def test_ring_sharded_layer_matches_single_device(self, mesh8, rng):
        """The same GPTLayer params run with ring attention over a
        sequence-sharded mesh == the single-device layer (long-context
        path; sp composes at the model level via attention_fn)."""
        import functools

        from apex_tpu.parallel.mesh import shard_map_compat as shard_map
        from jax.sharding import PartitionSpec as P

        from apex_tpu.models import GPTConfig, GPTLayer
        from apex_tpu.parallel import ring_attention

        # attention dropout ON: the ring mask is keyed on global
        # positions, so the sharded layer matches the single-device layer
        # exactly even mid-training (residual dropout stays off — flax's
        # nn.Dropout draws shape-dependent masks that cannot match across
        # shardings; attention dropout is the in-kernel counter-based one)
        cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                             attn_dropout_rate=0.2)
        s = 8 * 16  # 16 positions per device
        x = jnp.asarray(
            rng.randn(2, s, cfg.hidden_size).astype(np.float32) * 0.3
        )
        single = GPTLayer(cfg)
        params = single.init(jax.random.PRNGKey(0), x)
        dropout_key = jax.random.PRNGKey(7)
        want = single.apply(params, x, deterministic=False,
                            rngs={"dropout": dropout_key})

        def ring_attn(q, k, v, *, dropout_rate, dropout_seed):
            assert dropout_rate > 0.0  # the training path, dropout on
            return ring_attention(q, k, v, axis_name="data", causal=True,
                                  dropout_rate=dropout_rate,
                                  dropout_seed=dropout_seed)

        sharded = GPTLayer(cfg, attention_fn=ring_attn)

        def fn(params, xb):
            # every device folds the same rng path -> same in-kernel seed
            # as the single-device run
            return sharded.apply(params, xb, deterministic=False,
                                 rngs={"dropout": dropout_key})

        f = shard_map(
            fn, mesh=mesh8, in_specs=(P(), P(None, "data")),
            out_specs=P(None, "data"), check_vma=False,
        )
        got = f(params, x)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


class TestRNN:
    def test_lstm_matches_torch(self, rng):
        torch = pytest.importorskip("torch")

        from apex_tpu.RNN import LSTM

        xs = jnp.asarray(rng.randn(6, 3, 10).astype(np.float32))
        m = LSTM(hidden_size=8, num_layers=1)
        v = m.init(jax.random.PRNGKey(0), xs)
        p = v["params"]["layer_0"]["ScanRNNCell_0"]
        tl = torch.nn.LSTM(10, 8, 1)
        # torch gate order i,f,g,o == ours
        with torch.no_grad():
            tl.weight_ih_l0.copy_(torch.tensor(np.asarray(p["wi"]).T))
            tl.weight_hh_l0.copy_(torch.tensor(np.asarray(p["wh"]).T))
            tl.bias_ih_l0.copy_(torch.tensor(np.asarray(p["bi"])))
            tl.bias_hh_l0.copy_(torch.tensor(np.asarray(p["bh"])))
            tout, _ = tl(torch.tensor(np.asarray(xs)))
        jout, _ = m.apply(v, xs)
        np.testing.assert_allclose(
            np.asarray(jout), tout.numpy(), atol=1e-5
        )

    def test_gru_matches_torch(self, rng):
        torch = pytest.importorskip("torch")

        from apex_tpu.RNN import GRU

        xs = jnp.asarray(rng.randn(6, 3, 10).astype(np.float32))
        m = GRU(hidden_size=8, num_layers=1)
        v = m.init(jax.random.PRNGKey(0), xs)
        p = v["params"]["layer_0"]["ScanRNNCell_0"]
        tg = torch.nn.GRU(10, 8, 1)
        with torch.no_grad():
            tg.weight_ih_l0.copy_(torch.tensor(np.asarray(p["wi"]).T))
            tg.weight_hh_l0.copy_(torch.tensor(np.asarray(p["wh"]).T))
            tg.bias_ih_l0.copy_(torch.tensor(np.asarray(p["bi"])))
            tg.bias_hh_l0.copy_(torch.tensor(np.asarray(p["bh"])))
            tout, _ = tg(torch.tensor(np.asarray(xs)))
        jout, _ = m.apply(v, xs)
        np.testing.assert_allclose(np.asarray(jout), tout.numpy(), atol=1e-5)

    def test_stack_and_bidirectional_shapes(self, rng):
        from apex_tpu.RNN import LSTM, mLSTM, BidirectionalRNN

        xs = jnp.asarray(rng.randn(5, 2, 12).astype(np.float32))
        m = LSTM(hidden_size=16, num_layers=3)
        v = m.init(jax.random.PRNGKey(0), xs)
        ys, carries = m.apply(v, xs)
        assert ys.shape == (5, 2, 16) and len(carries) == 3
        bi = BidirectionalRNN(16)
        v = bi.init(jax.random.PRNGKey(0), xs)
        ys, _ = bi.apply(v, xs)
        assert ys.shape == (5, 2, 32)
        ml = mLSTM(hidden_size=16)
        v = ml.init(jax.random.PRNGKey(0), xs)
        ys, _ = ml.apply(v, xs)
        assert ys.shape == (5, 2, 16)


class TestO2CastHeuristic:
    def test_rn50_o2_keeps_bn_fp32(self):
        """keep_batchnorm_fp32 must actually hit RN50's bn1/bn2/downsample_bn
        names (regression: heuristic missed short 'bnN' names)."""
        m = resnet50(num_classes=10)
        v = m.init(jax.random.PRNGKey(0), jnp.ones((1, 64, 64, 3)))
        amp_ = amp.initialize("O2")
        cast = amp_.cast_model(v["params"])
        flat = jax.tree_util.tree_flatten_with_path(cast)[0]
        bn_leaves = [l for p, l in flat if any("bn" in str(k).lower() for k in p)]
        conv_leaves = [l for p, l in flat if any("conv" in str(k).lower() for k in p)]
        assert bn_leaves and all(l.dtype == jnp.float32 for l in bn_leaves)
        assert conv_leaves and all(l.dtype == jnp.bfloat16 for l in conv_leaves)
