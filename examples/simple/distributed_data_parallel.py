"""Minimum distributed example — parity with
ref examples/simple/distributed/distributed_data_parallel.py.

The reference: init_process_group from env, wrap model in DDP, train a toy
model.  Here: build a mesh over local devices (+jax.distributed when env
says multi-process), shard the batch, average grads with the DDP policy.

Run single-host (8 virtual devices):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python examples/simple/distributed_data_parallel.py
Multi-process (DCN path):
    WORLD_SIZE=2 python -m apex_tpu.parallel.multiproc \
        examples/simple/distributed_data_parallel.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "..", ".."))

import jax

import jax.numpy as jnp
import numpy as np
import apex_tpu.amp as amp
from apex_tpu.optimizers import fused_sgd
from apex_tpu.parallel import (
    DistributedDataParallel,
    data_parallel_mesh,
    data_parallel_step,
    init_distributed,
    replicate,
    shard_batch,
)


def main():
    init_distributed()  # no-op unless WORLD_SIZE/RANK are set
    mesh = data_parallel_mesh()
    n_dev = mesh.devices.size
    if jax.process_index() == 0:
        print(f"mesh: {n_dev} devices, {jax.process_count()} processes")

    amp_ = amp.initialize("O2")
    opt = amp.AmpOptimizer(fused_sgd(0.03, momentum=0.9), amp_)
    ddp = DistributedDataParallel(axis_name="data")

    rng = np.random.RandomState(42)
    params = {
        "w1": jnp.asarray(rng.randn(32, 64).astype(np.float32) * 0.2),
        "w2": jnp.asarray(rng.randn(64, 8).astype(np.float32) * 0.2),
    }
    state = opt.init(params)

    def step(carry, batch):
        params, state = carry
        x, y = batch

        def scaled(mp):
            p = opt.model_params(mp)
            h = jax.nn.relu(x.astype(p["w1"].dtype) @ p["w1"])
            pred = h @ p["w2"]
            loss = jnp.mean(jnp.square(pred.astype(jnp.float32) - y))
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(ddp.local_params(params))
        grads = ddp.allreduce(grads)
        params, state, _ = opt.step(grads, state, params)
        return (params, state), jax.lax.pmean(loss, "data")

    f = data_parallel_step(step, mesh, donate_state=False)

    per_dev = 16
    x = rng.randn(n_dev * per_dev, 32).astype(np.float32)
    w_true = rng.randn(32, 8).astype(np.float32) * 0.5
    y = x @ w_true
    carry = (replicate(params, mesh), replicate(state, mesh))
    xb = shard_batch(jnp.asarray(x), mesh)
    yb = shard_batch(jnp.asarray(y), mesh)
    for i in range(50):
        carry, loss = f(carry, (xb, yb))
        if i % 10 == 0 and jax.process_index() == 0:
            print(f"step {i:3d}  loss {float(loss):.5f}  "
                  f"scale {float(carry[1].scaler[0].loss_scale):.0f}")
    if jax.process_index() == 0:
        print("final loss:", float(loss))


if __name__ == "__main__":
    main()
