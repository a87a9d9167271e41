"""apex_tpu.amp — automatic mixed precision for TPU training loops.

Capability parity with ``apex.amp`` (ref apex/amp/__init__.py), re-designed
for jit-traced functional training steps:

=====================================  =====================================
reference API                          apex_tpu API
=====================================  =====================================
``amp.initialize(models, opts, ...)``  ``amp.initialize(opt_level, ...)`` ->
                                       :class:`Amp` (policy + scalers); model
                                       casting via :meth:`Amp.cast_model`,
                                       optimizer wrapping via
                                       :class:`AmpOptimizer`
``with amp.scale_loss(l, opt) as sl``  ``sl = amp_.scale_loss(l, state)`` +
                                       ``AmpOptimizer.step`` (unscale,
                                       inf-check, where-gated update)
``amp.master_params(optimizer)``       ``AmpOptimizer`` keeps the fp32 master
                                       tree as *the* params; model copy is a
                                       pure cast
``amp.state_dict()``                   ``Amp.state_dict(states)`` (per-loss
                                       scale + unskipped, ref frontend.py:361)
``@amp.half_function``                 same decorator, trace-time
``amp.disable_casts()``                same, trace-time
=====================================  =====================================

The train-step shape this module is designed around::

    amp_ = amp.initialize(opt_level="O2", num_losses=1)
    opt  = amp.AmpOptimizer(optax.sgd(1e-3), amp_)
    state = opt.init(master_params)           # fp32 masters + scaler state

    @jax.jit
    def train_step(state, master_params, batch):
        def loss_fn(mp):
            model_p = opt.model_params(mp)     # bf16 copy, BN kept fp32 (O2)
            loss = forward(model_p, batch)
            return amp_.scale_loss(loss, state.scaler[0])
        grads = jax.grad(loss_fn)(master_params)
        return opt.step(grads, state, master_params)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.amp.policy import (  # noqa: F401
    O0,
    O1,
    O2,
    O3,
    Policy,
    make_policy,
    opt_levels,
)
from apex_tpu.amp.scaler import (  # noqa: F401
    LossScaler,
    LossScalerState,
    apply_if_finite,
)
from apex_tpu.amp.functional import (  # noqa: F401
    autocast,
    disable_casts,
    current_policy,
    half_function,
    float_function,
    promote_function,
    register_half_function,
    register_float_function,
    register_promote_function,
)
from apex_tpu.amp import functional as F  # noqa: F401
from apex_tpu.amp import layers  # noqa: F401 — policy-aware Dense/Conv

PyTree = Any

_amp_verbosity = 1


def set_verbosity(v: int) -> None:
    """ref apex/amp/frontend.py verbosity kwarg (0 silences maybe_print)."""
    global _amp_verbosity
    _amp_verbosity = v


def _process_index() -> int:
    """Current process rank WITHOUT forcing backend initialization.

    ``jax.process_index()`` initializes the backend as a side effect — a
    log call must never do that (it would break a later
    ``jax.distributed.initialize``).  The distributed global state carries
    the rank once initialize() has run and defaults to 0 before it, which
    is exactly the semantics a logger wants."""
    try:
        pid = jax._src.distributed.global_state.process_id
        return 0 if pid is None else int(pid)
    except Exception:  # pragma: no cover - private-module moved/renamed
        # NEVER fall back to jax.process_index() here: it would initialize
        # the backend, the exact side effect this helper exists to avoid.
        # Worst case (multi-host + moved private API) every host prints.
        return 0


def maybe_print(msg: str, rank0: bool = True) -> None:
    """Print unless silenced; by default only on process 0.

    ref apex/amp/_amp_state.py:38-50 — the reference checks
    ``torch.distributed.get_rank() == 0``; the TPU equivalent is process
    index 0 (one process per host, chips are not processes).  Library code
    should log through this so multi-host runs don't emit world_size
    copies of every message.
    """
    if _amp_verbosity <= 0:
        return
    if rank0 and _process_index() != 0:
        return
    print(msg)


_warned_once: set = set()


def warn_once(key: str, msg: str) -> None:
    """``maybe_print`` at most once per process per key.

    Used for accepted-but-inert parity knobs (delay_allreduce, groupbn
    CUDA grid tuning): a user porting an apex config should learn the
    knob does nothing here rather than silently believe it acted."""
    if key in _warned_once:
        return
    _warned_once.add(key)
    maybe_print(msg)


def default_is_batchnorm(path: Tuple) -> bool:
    """Heuristic matching flax naming: does this param path belong to a BN?

    ref keep_batchnorm_fp32 applies to _BatchNorm modules only
    (apex/fp16_utils/fp16util.py:60-70 convert_network).  Matches the
    conventional module names: 'BatchNorm_0', 'SyncBatchNorm_1', 'bn',
    'bn1'/'bn2', 'downsample_bn', 'bn_relu', ...
    """
    for p in path:
        name = getattr(p, "key", None) or getattr(p, "name", None) or str(p)
        low = str(name).lower()
        if "batchnorm" in low or "batch_norm" in low:
            return True
        if low == "bn" or low.startswith("bn") or low.endswith("_bn") or low.endswith("bn"):
            return True
    return False


@dataclasses.dataclass(frozen=True)
class Amp:
    """Initialized AMP context: a policy plus one scaler per loss.

    ref: the (properties, loss_scalers) pair built by
    apex/amp/_initialize.py:145-263.
    """

    policy: Policy
    scalers: Tuple[LossScaler, ...]

    # -- state ----------------------------------------------------------
    def init_state(self) -> Tuple[LossScalerState, ...]:
        return tuple(s.init() for s in self.scalers)

    # -- hot loop -------------------------------------------------------
    def scale_loss(self, loss, scaler_state: LossScalerState, loss_id: int = 0):
        """ref apex/amp/handle.py:16-158 (the yield of the context manager)."""
        if not self.policy.enabled:
            return loss
        return self.scalers[loss_id].scale_loss(loss, scaler_state)

    def autocast(self):
        """O1 policy-table casting for everything traced inside the block.

        Returns a live :func:`apex_tpu.amp.functional.autocast` context when
        the policy uses autocast (O1), else a no-op context — so training
        code can wrap its forward unconditionally::

            with amp_.autocast():
                logits = model.apply(params, x)
        """
        import contextlib

        if self.policy.enabled and self.policy.autocast:
            return autocast(self.policy)
        return contextlib.nullcontext()

    def unscale(self, grads, scaler_state, loss_id: int = 0):
        return self.scalers[loss_id].unscale(grads, scaler_state)

    def update_scaler(self, scaler_state, found_inf, loss_id: int = 0):
        return self.scalers[loss_id].update(scaler_state, found_inf)

    # -- model casting (O2/O3) ------------------------------------------
    def cast_model(
        self,
        params: PyTree,
        is_batchnorm: Callable[[Tuple], bool] = default_is_batchnorm,
    ) -> PyTree:
        """Pure cast of an fp32 param tree to the policy's model dtype.

        Under O2 (keep_batchnorm_fp32) BN leaves stay fp32
        (ref apex/amp/_initialize.py:176-182 + fp16util.py:60-70).
        Under O0/O1 this is the identity.
        """
        dtype = self.policy.cast_model_dtype
        if dtype is None or dtype == jnp.float32:
            return params
        keep_bn = bool(self.policy.keep_batchnorm_fp32)

        def cast(path, x):
            if not jnp.issubdtype(jnp.result_type(x), jnp.floating):
                return x
            if keep_bn and is_batchnorm(path):
                return x.astype(jnp.float32)
            return x.astype(dtype)

        # masters -> compute dtype, under one name in the device trace
        # (and, transposed, the gradients' way back to float32)
        with jax.named_scope("apex_amp_cast"):
            return jax.tree_util.tree_map_with_path(cast, params)

    def cast_output(self, out: PyTree) -> PyTree:
        """ref _initialize.py:190-201 patched-forward output cast."""
        dtype = self.policy.cast_model_outputs
        if dtype is None:
            return out
        return jax.tree_util.tree_map(
            lambda x: x.astype(dtype)
            if jnp.issubdtype(jnp.result_type(x), jnp.floating)
            else x,
            out,
        )

    # -- checkpointing (ref apex/amp/frontend.py:361-400) ---------------
    def state_dict(self, states: Sequence[LossScalerState]) -> dict:
        return {
            f"loss_scaler{i}": s.state_dict(st)
            for i, (s, st) in enumerate(zip(self.scalers, states))
        }

    def load_state_dict(self, d: dict) -> Tuple[LossScalerState, ...]:
        return tuple(
            s.load_state_dict(d[f"loss_scaler{i}"]) for i, s in enumerate(self.scalers)
        )


def initialize(
    opt_level: str = "O1",
    num_losses: int = 1,
    enabled: bool = True,
    cast_model_dtype=None,
    keep_batchnorm_fp32: Optional[bool] = None,
    master_weights: Optional[bool] = None,
    loss_scale=None,
    cast_model_outputs=None,
    min_loss_scale: Optional[float] = None,
    max_loss_scale: float = 2.0 ** 24,
) -> Amp:
    """Build an :class:`Amp` context (ref apex/amp/frontend.py:195-358).

    Unlike the reference this does not mutate models/optimizers; pair it with
    :meth:`Amp.cast_model` and :class:`AmpOptimizer`.
    """
    policy = make_policy(
        opt_level,
        cast_model_dtype=cast_model_dtype,
        keep_batchnorm_fp32=keep_batchnorm_fp32,
        master_weights=master_weights,
        loss_scale=loss_scale,
        cast_model_outputs=cast_model_outputs,
    )
    if not enabled:
        policy = policy.replace(enabled=False, loss_scale=1.0)
    scaler_kw = dict(max_loss_scale=max_loss_scale, min_loss_scale=min_loss_scale)
    scalers = tuple(policy.make_scaler(**scaler_kw) for _ in range(num_losses))
    return Amp(policy=policy, scalers=scalers)


# --------------------------------------------------------------------------
# AmpOptimizer: the functional `_process_optimizer`
# --------------------------------------------------------------------------


class AmpOptState(NamedTuple):
    opt_state: Any  # inner optimizer state (over fp32 masters)
    scaler: Tuple[LossScalerState, ...]
    stash: Optional[PyTree]  # accumulated fp32 grads (delay_unscale path)


class StepStats(NamedTuple):
    found_inf: jax.Array  # bool — this step was skipped
    loss_scale: jax.Array  # f32 — scale after update
    # f32 — global L2 norm of the UNSCALED master grads, or None unless the
    # optimizer was built with track_grad_norm=True (the fused train
    # driver's grad-norm meter; an extra reduction pass, so opt-in)
    grad_norm: Optional[jax.Array] = None


class AmpOptimizer:
    """Master-weight + loss-scale wrapper around an optax transform.

    ref: apex/amp/_process_optimizer.py:321-489.  The reference mutates the
    optimizer (stash, wrapped step/zero_grad); here the wrapper owns the
    whole unscale -> inf-check -> update -> where-gate -> scaler-update
    pipeline as one pure function, so XLA fuses it into a single pass over
    the parameters (the multi-tensor-apply property for free).
    """

    def __init__(self, tx, amp_: Amp, *, track_grad_norm: bool = False):
        self.tx = tx
        self.amp = amp_
        # opt-in: report the unscaled master-grad L2 norm in StepStats
        # (one extra fused reduction over the grads — the train driver's
        # grad-norm meter reads it from the scan carry, never the host)
        self.track_grad_norm = track_grad_norm

    def init(self, master_params: PyTree) -> AmpOptState:
        return AmpOptState(
            opt_state=self.tx.init(master_params),
            scaler=self.amp.init_state(),
            stash=None,
        )

    def model_params(self, master_params: PyTree) -> PyTree:
        """The half model copy (pure cast; identity under O0/O1)."""
        return self.amp.cast_model(master_params)

    @jax.named_scope("apex_amp_step")
    def step(
        self,
        scaled_grads: PyTree,
        state: AmpOptState,
        master_params: PyTree,
        loss_id: int = 0,
    ) -> Tuple[PyTree, AmpOptState, StepStats]:
        """One optimizer step from *scaled* grads (the whole hot path of
        ref apex/amp/handle.py:107-158 + _process_optimizer post_backward).

        Returns (new_master_params, new_state, stats).  On overflow the
        params and optimizer state are returned unchanged and the scale is
        backed off — all under jit, no host sync.  The whole pipeline
        (unscale, inf check, norms, the update under its own
        ``named_update_scope``) is the ``apex_amp_step`` scope of a
        device trace.
        """
        scaler = self.amp.scalers[loss_id]
        sstate = state.scaler[loss_id]
        from apex_tpu import multi_tensor
        from apex_tpu.optimizers._common import AmpFusedTransformation

        if state.stash is None and isinstance(self.tx, AmpFusedTransformation):
            # amp-fused optimizer: the unscale multiplier and the
            # overflow gate run INSIDE the optimizer's own passes — no
            # materialized master-grad copy, no separate where-gates
            # over params/state.  The check must see the UNSCALED
            # magnitudes (a loss_scale < 1 can overflow finite scaled
            # grads during unscale), so it tests max|g| * inv_scale —
            # one max reduction over the same read the grad norm makes,
            # catching input inf/nan (max propagates them) AND unscale
            # overflow, matching the legacy check on the unscaled copy.
            inv_scale = 1.0 / sstate.loss_scale
            maxabs = multi_tensor.multi_tensor_l2norm(
                scaled_grads, max_norm=True
            )
            found_inf = jnp.logical_not(jnp.isfinite(maxabs * inv_scale))
            updates, new_opt_state = self.tx.update(
                scaled_grads, state.opt_state, master_params,
                inv_scale=inv_scale, found_inf=found_inf,
            )
            grad_norm = (
                multi_tensor.multi_tensor_l2norm(scaled_grads) * inv_scale
                if self.track_grad_norm else None
            )
        else:
            if state.stash is not None:
                master_grads, found_inf = scaler.unscale_with_stashed(
                    scaled_grads, state.stash, sstate
                )
            else:
                master_grads, found_inf = scaler.unscale(scaled_grads, sstate)
            grad_norm = (
                multi_tensor.multi_tensor_l2norm(master_grads)
                if self.track_grad_norm else None
            )
            updates, new_opt_state = self.tx.update(
                master_grads, state.opt_state, master_params
            )
            new_opt_state = apply_if_finite(
                found_inf, new_opt_state, state.opt_state
            )
            updates = apply_if_finite(
                found_inf,
                updates,
                jax.tree_util.tree_map(jnp.zeros_like, updates),
            )
        new_params = jax.tree_util.tree_map(
            lambda p, u: p + u.astype(p.dtype), master_params, updates
        )
        new_sstate = scaler.update(sstate, found_inf)
        new_scalers = tuple(
            new_sstate if i == loss_id else s for i, s in enumerate(state.scaler)
        )
        return (
            new_params,
            AmpOptState(opt_state=new_opt_state, scaler=new_scalers, stash=None),
            StepStats(found_inf=found_inf, loss_scale=new_sstate.loss_scale,
                      grad_norm=grad_norm),
        )

    def accumulate(
        self,
        scaled_grads: PyTree,
        state: AmpOptState,
        loss_id: int = 0,
        update_scaler: bool = True,
    ) -> AmpOptState:
        """Accumulate a loss's grads into the fp32 stash without stepping.

        Two reference patterns share this call:
        - multiple losses, one optimizer (dcgan errD_real+errD_fake): each
          loss's scale_loss exit updates ITS scaler (handle.py:119-127) —
          the default ``update_scaler=True``;
        - micro-batch accumulation of ONE loss with ``delay_unscale=True``
          (handle.py:75-105), where the reference leaves the scaler
          untouched until the real step — pass ``update_scaler=False``.
        Any inf in the stash also trips the final step's combined check, so
        the eventual step is skipped either way.
        """
        scaler = self.amp.scalers[loss_id]
        sstate = state.scaler[loss_id]
        if state.stash is None:
            stashed, found_inf = scaler.unscale(scaled_grads, sstate)
        else:
            stashed, found_inf = scaler.unscale_with_stashed(
                scaled_grads, state.stash, sstate
            )
        if not update_scaler:
            return state._replace(stash=stashed)
        new_sstate = scaler.update(sstate, found_inf)
        new_scalers = tuple(
            new_sstate if i == loss_id else s for i, s in enumerate(state.scaler)
        )
        return state._replace(stash=stashed, scaler=new_scalers)


def master_params(state_or_params):
    """ref apex/amp/_amp_state.py:59-68 — the fp32 master tree.

    In apex_tpu the master params *are* the canonical params the user holds;
    this helper exists for API parity and returns its argument (or the
    params field of a train-state-like object).
    """
    return getattr(state_or_params, "params", state_or_params)
