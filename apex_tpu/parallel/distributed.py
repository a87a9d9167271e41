"""Data parallelism over a named mesh axis — the DistributedDataParallel
re-design.

ref: apex/parallel/distributed.py (640 LoC of bucketing, per-param backward
hooks, side streams, first-iteration bucket-structure discovery, rank-0
structure broadcast).  ALL of that machinery exists to overlap NCCL
allreduce with torch's eager backward.  Under XLA the backward is one
compiled computation and the latency-hiding scheduler overlaps the psum with
remaining backward compute automatically, so the TPU design keeps only the
*semantic* surface:

===============================================  ===========================
reference knob                                   apex_tpu equivalent
===============================================  ===========================
``message_size`` bucketing                       compiler's job (no knob)
param broadcast at init                          :func:`mesh.replicate`
``gradient_average`` (divide by world)           ``gradient_average=True``
``gradient_predivide_factor`` (pre/post split)   same, same math
``allreduce_always_fp32`` (bf16 grads summed     same: upcast->psum->downcast
  in fp32)
``delay_allreduce`` (skip overlap, reduce at     accepted, no-op (XLA owns
  end of backward)                                 scheduling) — kept so
                                                   configs port unchanged
``disable_allreduce`` / DDP ``forward`` no-sync  ``enabled=False`` (grad
                                                   accumulation microbatches)
``Reducer`` (manual reduction helper)            :class:`Reducer`
===============================================  ===========================

Usage inside a shard_map/pjit-traced step::

    ddp = DistributedDataParallel(axis_name="data", allreduce_always_fp32=True)
    grads = jax.grad(loss_fn)(ddp.local_params(params))  # per-shard grads
    grads = ddp.allreduce(grads)             # averaged over the data axis

(Differentiating w.r.t. the raw replicated params also works — shard_map's
type system then inserts the summing psum itself — but the DDP policy knobs
only apply when the collective is the explicit one above.)
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel import mesh as mesh_lib

PyTree = Any


@dataclasses.dataclass(frozen=True)
class DistributedDataParallel:
    """Gradient-averaging policy over a mesh axis (ref distributed.py:129-253).

    ``axis_index_groups`` restricts the reduction to subgroups (the
    process-group argument of the reference's constructor).
    """

    axis_name: str = "data"
    gradient_average: bool = True
    gradient_predivide_factor: float = 1.0
    allreduce_always_fp32: bool = False
    delay_allreduce: bool = False  # accepted for config parity; XLA schedules
    axis_index_groups: Optional[Sequence[Sequence[int]]] = None

    def __post_init__(self):
        if self.delay_allreduce:
            from apex_tpu.amp import warn_once

            warn_once(
                "ddp.delay_allreduce",
                "apex_tpu DDP: delay_allreduce=True is accepted for config "
                "parity but has no effect — XLA schedules the grad "
                "collectives (overlap happens automatically).",
            )

    def _group_size(self) -> Optional[int]:
        if self.axis_index_groups is not None:
            return len(self.axis_index_groups[0])
        return None

    def local_params(self, params: PyTree) -> PyTree:
        """Mark replicated params device-varying so their grads stay LOCAL.

        shard_map's type system auto-inserts the psum when differentiating
        w.r.t. replicated (invariant) params — grads arrive already summed.
        That is the "let XLA insert collectives" mode and needs no DDP at
        all.  To apply the reference's collective policy knobs (fp32
        allreduce, predivide, no-sync microbatching), differentiate w.r.t.
        ``ddp.local_params(params)`` instead: the cotangents then stay
        per-shard and :meth:`allreduce` performs the one explicit collective
        (the moral twin of the reference's hook-driven NCCL allreduce).
        """
        return jax.tree_util.tree_map(
            lambda p: jax.lax.pcast(p, self.axis_name, to="varying"), params
        )

    def allreduce(self, grads: PyTree, enabled: bool = True) -> PyTree:
        """Sum-reduce grads over the axis with the reference's scaling policy.

        ref allreduce_bucket (distributed.py:425-475): optional fp32 upcast,
        divide by predivide_factor before the reduction, then by
        (world_size / predivide_factor) after — numerically safer than one
        post-divide for large worlds, bit-matching the reference's split.
        ``enabled=False`` is the no-sync path (ref disable_allreduce,
        distributed.py:275-279).
        """
        if not enabled:
            return grads

        # marker parity: ref pushes an NVTX "allreduce" range around the
        # bucket reduction (distributed.py:359-360); scope consumed by
        # apex_tpu.pyprof
        scope = jax.named_scope("apex_ddp_allreduce")

        def reduce_leaf(g):
            orig_dtype = g.dtype
            if self.allreduce_always_fp32:
                g = g.astype(jnp.float32)
            # The reference predivides unconditionally (distributed.py:445-446),
            # even when gradient_average=False (result = sum/predivide).
            if self.gradient_predivide_factor != 1.0:
                g = g / self.gradient_predivide_factor
            if self.axis_index_groups is not None:
                g = mesh_lib.grouped_psum(g, self.axis_name, self.axis_index_groups)
            else:
                g = jax.lax.psum(g, self.axis_name)
            if self.gradient_average:
                world = self._axis_size(g)
                g = g / (world / self.gradient_predivide_factor)
            if self.allreduce_always_fp32 and g.dtype != orig_dtype:
                g = g.astype(orig_dtype)
            return g

        with scope:
            return jax.tree_util.tree_map(reduce_leaf, grads)

    def _axis_size(self, _leaf) -> int:
        gs = self._group_size()
        if gs is not None:
            return gs
        return mesh_lib.axis_size(self.axis_name)


class Reducer:
    """Manual gradient/buffer reduction helper (ref distributed.py:89-126:
    "intended mostly to be used with raw gradients"; reduction is in-place
    sum there — here it returns the summed (or averaged) tree)."""

    def __init__(self, axis_name: str = "data", average: bool = True):
        self.axis_name = axis_name
        self.average = average

    def reduce(self, tree: PyTree) -> PyTree:
        op = jax.lax.pmean if self.average else jax.lax.psum
        return jax.tree_util.tree_map(lambda x: op(x, self.axis_name), tree)


def data_parallel_step(
    step_fn: Callable,
    mesh: Mesh,
    *,
    axis_name: str = "data",
    donate_state: bool = True,
    check_vma: bool = True,
    steps_per_dispatch: int = 1,
) -> Callable:
    """Wrap a per-shard ``step_fn(state, batch) -> (state, metrics)`` into a
    jitted SPMD step over ``mesh``.

    The wrapper shard_maps ``step_fn`` with params/state replicated and the
    batch sharded on ``axis_name``.  ``step_fn`` runs with the axis in scope,
    so ``ddp.allreduce`` / ``lax.psum`` work inside.  This is the moral
    equivalent of the reference's "wrap the model in DDP and keep your
    training loop" promise.

    ``steps_per_dispatch=K > 1`` fuses K steps into ONE donated dispatch:
    the returned function takes batches with a leading K axis (see
    ``apex_tpu.data.window_batches``) and returns per-step metrics stacked
    on that axis.  For window meters read once per dispatch, use
    :class:`apex_tpu.train.FusedTrainDriver` — this wrapper keeps the
    per-step metrics contract.
    """
    k = int(steps_per_dispatch)
    if k < 1:
        raise ValueError(f"steps_per_dispatch must be >= 1, got {k}")
    if k == 1:
        body = step_fn
        batch_spec = P(axis_name)
    else:
        def body(state, batches):
            return jax.lax.scan(step_fn, state, batches)

        # leading K axis unsharded, per-step batch axis on the data axis
        batch_spec = P(None, axis_name)

    mapped = mesh_lib.shard_map_compat(
        body,
        mesh=mesh,
        in_specs=(P(), batch_spec),
        out_specs=(P(), P()),
        check_vma=check_vma,  # False when state carries per-group BN stats
    )
    donate = (0,) if donate_state else ()
    return jax.jit(mapped, donate_argnums=donate)


def flatten_tree(tree: PyTree):
    """Concatenate all leaves into one flat fp32 buffer + recovery spec.

    ref: apex_C.flatten / csrc/flatten_unflatten.cpp (flat NCCL buckets).
    On TPU this is only needed for the ZeRO-style sharded optimizers
    (contrib), where one flat buffer makes psum_scatter shard boundaries
    independent of parameter shapes.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    shapes = [l.shape for l in leaves]
    dtypes = [l.dtype for l in leaves]
    sizes = [int(np.prod(s)) for s in shapes]
    flat = jnp.concatenate([l.astype(jnp.float32).reshape(-1) for l in leaves])
    return flat, (treedef, shapes, dtypes, sizes)


def unflatten_tree(flat, spec):
    """Inverse of :func:`flatten_tree` (ref apex_C.unflatten)."""
    treedef, shapes, dtypes, sizes = spec
    out = []
    offset = 0
    for shape, dtype, size in zip(shapes, dtypes, sizes):
        out.append(flat[offset : offset + size].reshape(shape).astype(dtype))
        offset += size
    return jax.tree_util.tree_unflatten(treedef, out)
