"""The reference's training steps: the same batches, the same optimizer, in
float32, a block of rows at a time and every dead buffer given back, so that
it stays under the program's own peak of device memory (weights, summed
gradient and both moments stay on the device: four float32 copies of the
model).
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import common as C


def leaf_norms(tree: C.Params) -> Dict[str, float]:
    return {k: float(v) for k, v in jax.device_get(
        {k: jnp.linalg.norm(v.astype(jnp.float32).ravel())
         for k, v in tree.items()}).items()}


def row_weights(labels: np.ndarray, replicas: int) -> np.ndarray:
    """The weight of each row's summed loss in the job's loss.  One replica:
    1 / (predicted positions of the whole batch) — the token mean.  A
    data-parallel job averages gradients across replicas, so its loss is the
    mean over replicas of each replica's OWN token mean (equal shares of the
    rows, in order): 1 / (replicas x predicted positions of the row's
    replica).  The two differ where replicas predict different numbers of
    positions."""
    share = labels.shape[0] // replicas
    counts = (labels >= 0).reshape(replicas, -1).sum(axis=1)
    return np.repeat(1.0 / (replicas * counts), share).astype(np.float32)


def follow(loss_rows: Callable, initial: Callable[[], C.Params],
           batches: Sequence, cfg: Dict, *, optimizer: str, hyper: Dict,
           rows_per_block: int,
           views: Callable[[C.Params], C.Params] = lambda t: t,
           replicas: int = 1, devices: Sequence = ()) -> Dict:
    """Train the weights ``initial()`` makes (called again at the end, to
    measure the change from them without holding a second copy meanwhile)
    on ``batches`` (one per step, each ``(ids, labels)`` of whole-batch
    rows) and return what the program's window is compared with: each
    step's loss, the global norm of the FIRST gradient before any clipping,
    and the per-leaf norms of the parameters' change over all the steps.
    ``views`` names the leaves that are compared (it may split one
    parameter into parts).

    With ``devices`` (one per replica) the same plain program is placed
    across them — weights on each, each replica's rows on its own device —
    so that four chips' worth of rows does not take four times as long.
    """
    prepare, leaf_step = C.optimizer(optimizer, **hyper)
    whole = rows = None
    if devices:
        mesh = Mesh(np.array(list(devices)), ("replica",))
        whole, rows = (NamedSharding(mesh, P()),
                       NamedSharding(mesh, P("replica")))
    place = lambda x, sh: x if sh is None else jax.device_put(x, sh)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def block_grad(p, acc, batch, weights):
        """(this block's part of the loss, ``acc`` + its gradient)."""
        value, g = jax.value_and_grad(
            lambda q: jnp.sum(weights * loss_rows(q, batch, cfg)))(p)
        return value, jax.tree_util.tree_map(jnp.add, acc, g)

    @functools.partial(jax.jit, donate_argnums=(0, 2, 3))
    def update(p, raw, m, v, t):
        """One optimizer step; also the global norm of the gradient before
        any clipping."""
        g = prepare(raw)
        out = {k: leaf_step(p[k], g[k], m[k], v[k], t) for k in p}
        return ({k: o[0] for k, o in out.items()},
                {k: o[1] for k, o in out.items()},
                {k: o[2] for k, o in out.items()},
                jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in raw.values())))

    zeros = jax.jit(lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
    params = place(initial(), whole)
    m, v = zeros(params), zeros(params)
    losses: List = []
    first_grad_norm = None
    for t, (ids, labels) in enumerate(batches, start=1):
        ids, labels = np.asarray(ids), np.asarray(labels)
        weights = row_weights(labels, replicas)
        share = ids.shape[0] // replicas
        acc, loss = zeros(params), jnp.float32(0.0)
        for lo in range(0, share, rows_per_block):
            # the same rows of every replica's share, replica by replica
            pick = np.concatenate([
                np.arange(i * share + lo,
                          i * share + min(lo + rows_per_block, share))
                for i in range(replicas)])
            value, acc = block_grad(
                params, acc, place((ids[pick], labels[pick]), rows),
                place(weights[pick], rows))
            loss = loss + value        # on the device: no wait per block
        losses.append(loss)
        params, m, v, raw_norm = update(
            params, acc, m, v, jnp.float32(t))
        del acc
        if first_grad_norm is None:
            first_grad_norm = float(raw_norm)
    del m, v
    losses = [float(x) for x in jax.device_get(losses)]
    delta = leaf_norms(views(jax.jit(
        lambda a, b: jax.tree_util.tree_map(jnp.subtract, a, b),
        donate_argnums=(0, 1))(params, place(initial(), whole))))
    if not all(np.isfinite(losses)):
        raise FloatingPointError(f"reference loss not finite: {losses}")
    return {"losses": losses, "first_grad_norm": first_grad_norm,
            "delta": delta}
