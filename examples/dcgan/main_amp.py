"""DCGAN with per-loss dynamic scalers — parity with ref examples/dcgan/
main_amp.py: two models, two optimizers, THREE losses each with its own
dynamic loss scaler (amp.initialize(..., num_losses=3) and loss_id-tagged
scale_loss calls).

Synthetic 64x64 data; demonstrates the multi-model/multi-scaler API shape,
driven by the fused K-steps-per-dispatch driver (``apex_tpu.train``) —
each G+D alternating iteration is one scan step, the three scaler states
thread through the scan carry, and the loss/scale meters are read back
once per window.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))


import argparse

import jax

import jax.numpy as jnp
import numpy as np

import apex_tpu.amp as amp
from apex_tpu.amp import F
from apex_tpu.models import Discriminator, Generator
from apex_tpu.optimizers import fused_adam
from apex_tpu.train import FusedTrainDriver, read_metrics


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--opt-level", default="O1", choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--steps", default=20, type=int)
    p.add_argument("-b", "--batch-size", default=16, type=int)
    p.add_argument("--nz", default=100, type=int)
    p.add_argument("--steps-per-dispatch", default=5, type=int,
                   help="fused G+D iterations per dispatch (the print "
                        "cadence: meters are read once per window)")
    args = p.parse_args()

    # one Amp context, three scalers: errD_real=0, errD_fake=1, errG=2
    amp_ = amp.initialize(args.opt_level, num_losses=3)
    dt = amp_.policy.compute_dtype
    netG = Generator(nz=args.nz, compute_dtype=dt)
    netD = Discriminator(compute_dtype=dt)
    optG = amp.AmpOptimizer(fused_adam(2e-4, betas=(0.5, 0.999)), amp_)
    optD = amp.AmpOptimizer(fused_adam(2e-4, betas=(0.5, 0.999)), amp_)

    rng = np.random.RandomState(0)
    key = jax.random.PRNGKey(0)
    z0 = jnp.zeros((args.batch_size, 1, 1, args.nz))
    x0 = jnp.zeros((args.batch_size, 64, 64, 3))
    gv = netG.init(key, z0)
    dv = netD.init(key, x0)
    gparams, gstats = gv["params"], gv["batch_stats"]
    dparams, dstats = dv["params"], dv["batch_stats"]
    gstate, dstate = optG.init(gparams), optD.init(dparams)

    def d_step(dparams, dstats, dstate, gparams, gstats, real, z):
        """Two backward passes with separate scalers (loss_id 0 and 1)."""
        fake, _ = netG.apply(
            {"params": gparams, "batch_stats": gstats}, z, mutable=["batch_stats"]
        )

        def loss_real(dp):
            out, upd = netD.apply(
                {"params": optD.model_params(dp), "batch_stats": dstats},
                real, mutable=["batch_stats"],
            )
            loss = F.binary_cross_entropy_with_logits(out, jnp.ones_like(out))
            return amp_.scale_loss(loss, dstate.scaler[0], loss_id=0), (loss, upd)

        g_real, (errD_real, upd) = jax.grad(loss_real, has_aux=True)(dparams)
        dstats2 = upd["batch_stats"]

        def loss_fake(dp):
            out, upd = netD.apply(
                {"params": optD.model_params(dp), "batch_stats": dstats2},
                fake, mutable=["batch_stats"],
            )
            loss = F.binary_cross_entropy_with_logits(out, jnp.zeros_like(out))
            return amp_.scale_loss(loss, dstate.scaler[1], loss_id=1), (loss, upd)

        g_fake, (errD_fake, upd) = jax.grad(loss_fake, has_aux=True)(dparams)

        # accumulate the two unscaled grad sets, then one step (ref pattern:
        # two backward() calls into the same optimizer before optD.step())
        dstate1 = optD.accumulate(g_real, dstate, loss_id=0)
        dparams, dstate, stats = optD.step(g_fake, dstate1, dparams, loss_id=1)
        return dparams, upd["batch_stats"], dstate, errD_real + errD_fake, stats

    def g_step(gparams, gstats, gstate, dparams, dstats, z):
        def loss_g(gp):
            fake, gupd = netG.apply(
                {"params": optG.model_params(gp), "batch_stats": gstats},
                z, mutable=["batch_stats"],
            )
            out, _ = netD.apply(
                {"params": dparams, "batch_stats": dstats}, fake,
                mutable=["batch_stats"],
            )
            loss = F.binary_cross_entropy_with_logits(out, jnp.ones_like(out))
            return amp_.scale_loss(loss, gstate.scaler[2], loss_id=2), (loss, gupd)

        grads, (errG, gupd) = jax.grad(loss_g, has_aux=True)(gparams)
        gparams, gstate, _ = optG.step(grads, gstate, gparams, loss_id=2)
        return gparams, gupd["batch_stats"], gstate, errG

    def step(carry, batch):
        """One G+D alternating iteration — a single scan step of the
        fused driver; all three scaler states ride in the carry."""
        gparams, gstats, gstate, dparams, dstats, dstate = carry
        real, z = batch
        dparams, dstats, dstate, errD, _ = d_step(
            dparams, dstats, dstate, gparams, gstats, real, z
        )
        gparams, gstats, gstate, errG = g_step(
            gparams, gstats, gstate, dparams, dstats, z
        )
        return (gparams, gstats, gstate, dparams, dstats, dstate), {
            "errD": errD,
            "errG": errG,
            "scale_d_real": dstate.scaler[0].loss_scale,
            "scale_d_fake": dstate.scaler[1].loss_scale,
            "scale_g": gstate.scaler[2].loss_scale,
        }

    driver = FusedTrainDriver(
        step,
        steps_per_dispatch=args.steps_per_dispatch,
        metrics={"errD": "last", "errG": "last", "scale_d_real": "last",
                 "scale_d_fake": "last", "scale_g": "last"},
    )
    carry = (gparams, gstats, gstate, dparams, dstats, dstate)
    done = 0
    while done < args.steps:
        k = min(args.steps_per_dispatch, args.steps - done)
        real = jnp.asarray(
            rng.rand(k, args.batch_size, 64, 64, 3) * 2 - 1, jnp.float32
        )
        z = jnp.asarray(
            rng.randn(k, args.batch_size, 1, 1, args.nz), jnp.float32
        )
        carry, res = driver.run_window(carry, (real, z))
        done += k
        m = read_metrics(res.metrics)  # one host read per K iterations
        scales = [m["scale_d_real"], m["scale_d_fake"], m["scale_g"]]
        print(
            f"[{done}/{args.steps}] Loss_D {m['errD']:.4f} "
            f"Loss_G {m['errG']:.4f} scales {scales}"
        )
    print("done")


if __name__ == "__main__":
    main()
