"""ImageNet ResNet-50 mixed-precision training — parity with
ref examples/imagenet/main_amp.py (argparse flags, O0-O3 sweep, AverageMeter,
img/s Speed metric, checkpoint incl. amp state, --prof window, digest output
for the L1-style loss-comparison harness).

The training loop runs on the fused driver (``apex_tpu.train``):
``--steps-per-dispatch`` K steps compile into ONE donated scan dispatch,
loss/scale/skip meters accumulate on device and are read back once per
WINDOW (the reference keeps host syncs off the hot path,
main_amp.py:363-399; the driver removes them from the step entirely).

Data: synthetic deterministic batches by default; ``--data <path>`` feeds a
fixed-record dataset through the native C++ loader + device prefetcher
(apex_tpu.data — the DALI/DataLoader role), windowed K steps at a time
with the transfer of window k+1 overlapping the compute of window k.

Examples:
    # single chip, O2, synthetic data
    python examples/imagenet/main_amp.py --opt-level O2 -b 128
    # 8-device data parallel + SyncBN on the CPU mesh
    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      python examples/imagenet/main_amp.py --sync_bn --image-size 64
    # native input pipeline (see apex_tpu.data.write_records for the format)
    python examples/imagenet/main_amp.py --data /data/train.bin
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import argparse
import json
import time

import jax

import jax.numpy as jnp
import numpy as np
import apex_tpu.amp as amp
from apex_tpu.models import resnet50
from apex_tpu.ops import softmax_cross_entropy
from apex_tpu.optimizers import fused_sgd
from apex_tpu.parallel import (
    DistributedDataParallel,
    data_parallel_mesh,
    replicate,
)
from apex_tpu.train import FusedTrainDriver, read_metrics


def parse_args():
    p = argparse.ArgumentParser(description="apex_tpu imagenet example")
    p.add_argument("--opt-level", default="O1", choices=["O0", "O1", "O2", "O3"])
    p.add_argument("--loss-scale", default=None,
                   help="float or 'dynamic' (ref --loss-scale)")
    p.add_argument("--keep-batchnorm-fp32", default=None, type=lambda s: s == "True")
    p.add_argument("-b", "--batch-size", default=64, type=int,
                   help="GLOBAL batch size")
    p.add_argument("--lr", default=0.1, type=float)
    p.add_argument("--momentum", default=0.9, type=float)
    p.add_argument("--weight-decay", default=1e-4, type=float)
    p.add_argument("--epochs", default=1, type=int)
    p.add_argument("--steps-per-epoch", default=30, type=int)
    p.add_argument("--image-size", default=224, type=int)
    p.add_argument("--num-classes", default=1000, type=int)
    p.add_argument("--sync_bn", action="store_true",
                   help="cross-replica SyncBatchNorm (ref --sync_bn)")
    p.add_argument("--data", default=None,
                   help="fixed-record dataset (apex_tpu.data.write_records "
                        "format: uint8 image HWC + int32 label); default "
                        "synthetic random batches")
    p.add_argument("--prof", default=-1, type=int,
                   help="trace the dispatch window containing this step, "
                        "then exit (ref --prof)")
    p.add_argument("--steps-per-dispatch", default=None, type=int,
                   help="fused steps per dispatch (K); default: "
                        "APEX_TPU_STEPS_PER_DISPATCH env or 10")
    p.add_argument("--print-freq", default=10, type=int)
    p.add_argument("--digest-file", default=None,
                   help="write per-step loss digests (L1 compare harness)")
    p.add_argument("--resume", default=None)
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--seed", default=0, type=int)
    return p.parse_args()


class AverageMeter:
    """ref main_amp.py AverageMeter."""

    def __init__(self):
        self.reset()

    def reset(self):
        self.val = self.sum = self.count = 0.0

    def update(self, val, n=1):
        self.val = val
        self.sum += val * n
        self.count += n

    @property
    def avg(self):
        return self.sum / max(self.count, 1)


def main():
    args = parse_args()
    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size
    assert args.batch_size % n_dev == 0, "global batch must divide devices"

    loss_scale = args.loss_scale
    if loss_scale is not None and loss_scale != "dynamic":
        loss_scale = float(loss_scale)
    amp_ = amp.initialize(
        args.opt_level,
        loss_scale=loss_scale,
        keep_batchnorm_fp32=args.keep_batchnorm_fp32,
    )
    # O2/O3 cast params+inputs to the half dtype; O1 keeps the model fp32
    # and the autocast tables (amp_.autocast() around the forward) cast the
    # matmul/conv operands instead — the reference's patched-torch O1 path
    model = resnet50(
        num_classes=args.num_classes,
        compute_dtype=amp_.policy.cast_model_dtype or jnp.float32,
        sync_batchnorm=args.sync_bn,
    )
    opt = amp.AmpOptimizer(
        fused_sgd(args.lr, momentum=args.momentum, weight_decay=args.weight_decay),
        amp_,
    )
    ddp = DistributedDataParallel(axis_name="data")

    rng = np.random.RandomState(args.seed)
    sample = jnp.zeros((2, args.image_size, args.image_size, 3), jnp.float32)
    variables = model.init(jax.random.PRNGKey(args.seed), sample)
    params, bstats = variables["params"], variables["batch_stats"]
    state = opt.init(params)

    from apex_tpu.checkpoint import restore_or_init

    ckpt, start_epoch = restore_or_init(
        args.resume,
        {"params": params, "batch_stats": bstats, "state": state},
    )
    if start_epoch:
        params, bstats, state = ckpt["params"], ckpt["batch_stats"], ckpt["state"]
        print(f"resumed from {args.resume} at epoch {start_epoch}")

    def step(carry, batch):
        params, bstats, state = carry
        x, y = batch

        def scaled(mp):
            with amp_.autocast():  # live under O1, no-op elsewhere
                logits, upd = model.apply(
                    {"params": opt.model_params(mp), "batch_stats": bstats},
                    x, train=True, mutable=["batch_stats"],
                )
            loss = jnp.mean(softmax_cross_entropy(logits, y))
            return amp_.scale_loss(loss, state.scaler[0]), (loss, upd["batch_stats"])

        grads, (loss, new_bstats) = jax.grad(scaled, has_aux=True)(
            ddp.local_params(params)
        )
        grads = ddp.allreduce(grads)
        params, state, stats = opt.step(grads, state, params)
        metrics = {
            "loss": jax.lax.pmean(loss, "data"),
            "scale": stats.loss_scale,
            "skipped": stats.found_inf,
        }
        return (params, new_bstats, state), metrics

    # K fused steps per donated dispatch; loss/scale/skip meters live in
    # the scan carry and are read back ONCE per window (no per-step host
    # sync left anywhere).  per_step keeps the L1 digest trajectory.
    driver = FusedTrainDriver(
        step,
        steps_per_dispatch=args.steps_per_dispatch,
        mesh=mesh,
        check_vma=False,
        metrics={"loss": "mean", "scale": "last", "skipped": "sum"},
        per_step=("loss",),
    )
    k = driver.steps_per_dispatch

    carry = (replicate(params, mesh), replicate(bstats, mesh), replicate(state, mesh))
    batch_time = AverageMeter()
    losses = AverageMeter()
    digests = []

    loader = None
    if args.data:
        # native C++ loader + device prefetch (the DALI/DataLoader role)
        from apex_tpu.data import DevicePrefetcher, NativeDataLoader

        loader = NativeDataLoader(
            args.data,
            {"image": (np.uint8, (args.image_size, args.image_size, 3)),
             "label": (np.int32, ())},
            batch_size=args.batch_size, shuffle=True, seed=args.seed,
        )

    from jax.sharding import NamedSharding, PartitionSpec as P

    # stacked windows: leading K axis unsharded, batch axis on the mesh
    window_sharding = (
        NamedSharding(mesh, P(None, "data")),
        NamedSharding(mesh, P(None, "data")),
    )

    def windows(epoch):
        """K-stacked batch windows, one per dispatch."""
        if loader is None:
            done = 0
            while done < args.steps_per_epoch:
                kk = min(k, args.steps_per_epoch - done)
                x = rng.randn(kk, args.batch_size, args.image_size,
                              args.image_size, 3)
                y = rng.randint(0, args.num_classes,
                                size=(kk, args.batch_size))
                yield jax.device_put(
                    (np.float32(x), y.astype(np.int32)), window_sharding
                )
                done += kk
            return
        from apex_tpu.data import window_batches

        # one device_put per K-window straight onto the mesh (no
        # default-device hop); the prefetcher keeps window w+1's transfer
        # in flight while the fused dispatch over window w computes
        for b in DevicePrefetcher(
            window_batches(loader.epoch(epoch), k, drop_last=True),
            transform=lambda b: (
                (b["image"].astype(np.float32) - 127.5) / 127.5,
                b["label"],
            ),
            sharding=window_sharding,
        ):
            yield b

    tracing = False
    for epoch in range(start_epoch, args.epochs):
        for w, batch_w in enumerate(windows(epoch)):
            i = w * k  # first step index of this window
            kk = jax.tree_util.tree_leaves(batch_w)[0].shape[0]
            # trace the whole dispatch window containing step --prof,
            # then exit (ref brackets iterations [prof, prof+N) with
            # cudaProfiler, main_amp.py:334-410; the fused dispatch makes
            # the window the natural trace unit)
            if args.prof >= 0 and i <= args.prof < i + kk and not tracing:
                jax.profiler.start_trace("/tmp/apex_tpu_trace")
                tracing = True
            t0 = time.time()
            carry, res = driver.run_window(carry, batch_w)
            m = read_metrics(res.metrics)  # ONE host sync per window
            dt = time.time() - t0
            if tracing:
                jax.profiler.stop_trace()
                print("profile written to /tmp/apex_tpu_trace")
                return
            if w > 0:  # skip compile window
                batch_time.update(dt / kk, n=kk)
            losses.update(m["loss"], n=kk)
            digests.extend(float(v) for v in np.asarray(res.per_step["loss"]))
            if i % args.print_freq < kk:
                # first window is compile; no timing sample yet
                speed = (args.batch_size / batch_time.avg
                         if batch_time.count else float("nan"))
                print(
                    f"Epoch [{epoch}][{i}/{args.steps_per_epoch}]  "
                    f"Time {batch_time.val:.3f} ({batch_time.avg:.3f})  "
                    f"Speed {speed:.1f} img/s  "
                    f"Loss {losses.val:.4f} ({losses.avg:.4f})  "
                    f"scale {m['scale']:.0f}  skipped {m['skipped']:.0f}"
                )
        if args.checkpoint:
            # orbax-backed, multi-host-safe (ref torch.save of
            # model/optimizer/amp dicts, README.md:60-99); epoch ends are
            # window boundaries, so the resumed scaler trajectory
            # continues bitwise
            params, bstats, state = carry
            driver.save(
                args.checkpoint,
                {"params": params, "batch_stats": bstats, "state": state},
                step=epoch + 1,
            )
            print(f"checkpoint -> {args.checkpoint}/{epoch + 1}")

    if args.digest_file:
        with open(args.digest_file, "w") as f:
            json.dump({"opt_level": args.opt_level, "losses": digests}, f)
        print(f"digests -> {args.digest_file}")


if __name__ == "__main__":
    main()
