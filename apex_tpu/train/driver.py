"""Fused multi-step training driver — K optimizer steps per dispatch.

PERF.md's own measurements locate the remaining overhead AROUND the
kernels, not in them: sub-20 ms steps are dispatch-bound (±30% wall noise
until scan-chained), and every benchmark hand-rolled the same
``jax.lax.scan`` wrapper to keep host round-trips off the hot path.
MegaScale (arxiv 2402.15627) attributes large-scale efficiency chiefly to
hiding host/communication overhead behind compute; the operation-fusion
line (arxiv 2502.17728) shows boundary elimination pays more than per-op
tuning.  This module makes that pattern a library surface instead of a
per-caller idiom:

- ``step_fn(carry, batch) -> (carry, metrics)`` is the user's ONE-step
  function — the same shape :func:`apex_tpu.parallel.data_parallel_step`
  takes.  ``carry`` is any pytree (params, ``AmpOptState`` with its
  dynamic-loss-scale state, batch stats, rng keys, ...); ``metrics`` is a
  flat dict of scalars.
- The driver compiles K steps into ONE donated ``lax.scan`` dispatch.
  The AMP scaler trajectory (growth/backoff/``found_inf`` skip gates)
  threads through the scan carry bitwise-identically to a per-step loop —
  tested in ``tests/test_train_driver.py``.
- Metric METERS (loss / grad-norm / scale, declared per-name as
  ``mean``/``sum``/``last``/``max``/``min``) accumulate in fp32 on device
  through the scan carry and are read once per window, not once per step.
  Optional ``per_step`` names are additionally stacked as scan outputs
  (still one dispatch) for trajectory consumers (L1 digests).
- With a ``mesh``, the WHOLE window runs inside one shard_map region, so
  ``ddp.allreduce`` / ``lax.psum`` / ``lax.pmean`` work inside
  ``step_fn`` exactly as they do under ``data_parallel_step``.
- Checkpoints compose at any window boundary: :meth:`FusedTrainDriver.save`
  / :meth:`FusedTrainDriver.restore` delegate to ``apex_tpu.checkpoint``
  and a resumed run continues the scaler trajectory bitwise (tested).

The steps-per-dispatch knob: constructor argument >
``APEX_TPU_STEPS_PER_DISPATCH`` env var > ``DEFAULT_STEPS_PER_DISPATCH``.

Runtime telemetry (ISSUE 6): every window dispatch, checkpoint
save/restore, and data prefetch stage runs inside a host-side
:mod:`apex_tpu.obs` span (``train/dispatch`` carries K and the
microbatch count; a cold call's compile is tagged on the span via the
``CompileMonitor`` bridge, with its seconds of tracing, lowering,
compiling and loading from the persistent cache in ``span.jit``), and
dispatch wall times accumulate in the ambient metrics registry
(``train.dispatch_ms`` histogram, ``train.dispatches``/``train.steps``
counters).  The driver numbers its windows: ``train/dispatch`` carries
``window=<n>``, and :func:`read_metrics` puts the same number on the
``train/fetch_metrics`` span of the result it is handed, so
:func:`apex_tpu.obs.train_windows` can lay each window out as the gap
before it, its enqueue, the host's time while it was in flight and the
blocked fetch, each with its CPU time.  All host-side — the compiled
programs are unchanged.  ``APEX_TPU_OBS=0`` turns off the spans, the
``train.*`` registry entries above, the ``jit.*`` counters, the ambient
tracer's ``gc.callbacks`` hook (``host.gc_ms``) and, with them, the
flight recorder; the registry itself stays live (gauges the ops set at
trace time, a ``registry=`` handed to :func:`read_metrics`), as do
``last_dispatch_ms``, ``last_dispatch_compiles`` and
``windows_dispatched``.

Gradient-accumulation microbatching (ISSUE 2): pass a
:class:`~apex_tpu.train.accum.MicrobatchedStep` (built by
``amp_microbatch_step`` / ``zero_microbatch_step``) as ``step_fn`` and
each scanned optimizer step consumes M microbatches with ALL
cross-replica communication deferred to one collective per accumulation
boundary; ``carry_spec`` lets the ZeRO mode keep its sharded optimizer
state sharded through the window.  See :mod:`apex_tpu.train.accum`.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import time
import weakref
from typing import Any, Callable, Dict, Iterable, Mapping, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu import obs
from apex_tpu.train.accum import MicrobatchedStep, build_opt_step

PyTree = Any

DEFAULT_STEPS_PER_DISPATCH = 10

_REDUCTIONS = ("mean", "sum", "last", "max", "min")


def steps_per_dispatch_default(k: Optional[int] = None) -> int:
    """Resolve the fused window length K.

    Explicit argument wins; else the ``APEX_TPU_STEPS_PER_DISPATCH`` env
    override (the kill switch: ``=1`` restores per-step dispatch
    everywhere without touching callers); else the library default.
    """
    if k is not None:
        return int(k)
    env = os.environ.get("APEX_TPU_STEPS_PER_DISPATCH")
    if env:
        return int(env)
    return DEFAULT_STEPS_PER_DISPATCH


class WindowResult(NamedTuple):
    """Device-side results of one fused window.

    ``metrics``: finalized 0-d meters (fp32), one per declared name.
    ``per_step``: (K,)-stacked traces for the names listed in
    ``per_step`` — empty dict unless requested.
    Fetch with :func:`read_metrics` — ONE host sync for the whole window.
    """

    metrics: Dict[str, jax.Array]
    per_step: Dict[str, jax.Array]


#: how many windows' results are remembered for :func:`read_metrics`
_REMEMBERED_WINDOWS = 64
#: ``{id(first leaf of a window's result): (weak reference to the leaf,
#: window number)}``, newest last.  The reference is weak so that no device
#: buffer outlives its loop's use of it, and tells a leaf from a later
#: array that was given its id.
_WINDOW_OF: "collections.OrderedDict[int, tuple]" = collections.OrderedDict()


def _remember_window(result: PyTree, window: int) -> None:
    """Note which window made ``result``, for the fetch that may follow:
    by the first leaf of its meters and of its per-step traces, so that
    ``read_metrics(res)``, ``(res.metrics)`` and ``(res.per_step)`` are
    all known (a tree's first leaf is what :func:`_window_of` asks for)."""
    for tree in result:
        leaves = jax.tree_util.tree_leaves(tree)
        if leaves:
            _WINDOW_OF.pop(id(leaves[0]), None)     # a dead leaf's id, reused
            _WINDOW_OF[id(leaves[0])] = (weakref.ref(leaves[0]), window)
    while len(_WINDOW_OF) > _REMEMBERED_WINDOWS:
        _WINDOW_OF.popitem(last=False)


def _window_of(tree: PyTree) -> Optional[int]:
    """The window that made ``tree``, if it is remembered — forgotten
    once asked for.  None for a tree no remembered window made."""
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return None
    leaf, window = _WINDOW_OF.pop(id(leaves[0]), (None, None))
    return window if leaf is not None and leaf() is leaves[0] else None


def read_metrics(tree: PyTree, registry=None,
                 prefix: str = "train.") -> PyTree:
    """One blocking device->host fetch of a metrics pytree (floats out).

    With a ``registry`` (an :class:`apex_tpu.obs.MetricsRegistry`),
    every scalar additionally lands in a ``<prefix><name>`` histogram —
    the host-side meter plumbing that used to be per-caller print/append
    code now accumulates where the trace artifact snapshots it.  The
    fetch is the ``train/fetch_metrics`` span: the host waiting for the
    window it dispatched.  The span carries ``window=<n>``, the number
    ``train/dispatch`` gave the window that made ``tree`` — found by the
    tree itself, not by order, so a loop may fetch some windows another
    way, or none, or dispatch ahead; a tree that no remembered window
    made carries none."""
    tracer = obs.default_tracer()
    window = _window_of(tree) if tracer.enabled else None
    attrs = {} if window is None else {"window": window}
    with tracer.span("train/fetch_metrics", **attrs):
        host = jax.device_get(tree)
    out = jax.tree_util.tree_map(
        lambda x: float(x) if getattr(x, "ndim", 1) == 0 else x, host
    )
    if registry is not None and isinstance(out, dict):
        for name, v in out.items():
            if isinstance(v, float):
                registry.histogram(prefix + name).observe(v)
    return out


def _acc_init(reduction: str) -> jax.Array:
    if reduction == "max":
        return jnp.float32(-jnp.inf)
    if reduction == "min":
        return jnp.float32(jnp.inf)
    return jnp.float32(0.0)  # mean / sum / last all start from overwrite/add


def _acc_update(acc: jax.Array, val: jax.Array, reduction: str) -> jax.Array:
    v = val.astype(jnp.float32)
    if reduction in ("mean", "sum"):
        return acc + v
    if reduction == "last":
        return v
    if reduction == "max":
        return jnp.maximum(acc, v)
    return jnp.minimum(acc, v)


def _acc_final(acc: jax.Array, reduction: str, k: int) -> jax.Array:
    if reduction == "mean":
        return acc / k
    return acc


@dataclasses.dataclass
class FusedTrainDriver:
    """Compile ``step_fn`` into fused K-step dispatches.

    Args:
      step_fn: ``(carry, batch) -> (carry, metrics)`` with ``metrics`` a
        flat dict of scalars.  When the driver runs without batches
        (synthetic/closure-captured data, ``run_window(carry)``),
        ``step_fn`` is called with ``batch=None``.  Pass a
        :class:`~apex_tpu.train.accum.MicrobatchedStep` instead to make
        each optimizer step consume M microbatches with the gradient
        accumulated on device and ALL cross-replica communication
        deferred to one collective per accumulation boundary — batched
        windows then carry a leading axis of ``K * M`` microbatches.
      steps_per_dispatch: window length K (None -> env/default; see
        :func:`steps_per_dispatch_default`).  A batched window whose
        leading axis differs from K (the tail of an epoch) compiles a
        second program for that length — lengths are static under jit.
      metrics: ``{name: reduction}`` meter declarations; reductions are
        ``mean`` (default for any undeclared name the step returns),
        ``sum``, ``last``, ``max``, ``min``.
      per_step: metric names additionally returned as (K,) traces.
      mesh / axis_name / batch_spec / check_vma: SPMD composition.  With a
        mesh, carry and metrics are replicated (``P()``) and each leaf of
        the per-step batch uses ``batch_spec`` (a single PartitionSpec or
        a pytree of them; default ``P(axis_name)``) with the window axis
        prepended unsharded.
      carry_spec: PartitionSpec pytree (prefix) for the carry — default
        ``P()`` (fully replicated).  The ZeRO driver mode passes the
        sharded optimizer state here, e.g. ``carry_spec=(P(),
        accum.zero_state_spec(), P())`` for a ``(params, state, rng)``
        carry, so master/moment shards stay 1/world per device.  A
        :class:`~apex_tpu.sharding.RulesTable` is also accepted
        (ISSUE 13): the spec tree is derived from the table by
        matching the FIRST dispatched carry's named paths — the
        declarative replacement for hand-built literal spec trees.
      donate: donate the carry buffers to the dispatch (params/opt-state
        update in place; the default, matching the benches' scan wrappers).
    """

    step_fn: Any  # Callable[(carry, batch) -> (carry, metrics)] | MicrobatchedStep
    steps_per_dispatch: Optional[int] = None
    metrics: Optional[Mapping[str, str]] = None
    per_step: Sequence[str] = ()
    mesh: Optional[Mesh] = None
    axis_name: str = "data"
    batch_spec: Any = None
    carry_spec: Any = None
    check_vma: bool = True
    donate: bool = True

    def __post_init__(self):
        self.steps_per_dispatch = steps_per_dispatch_default(
            self.steps_per_dispatch
        )
        if self.steps_per_dispatch < 1:
            raise ValueError(
                f"steps_per_dispatch must be >= 1, got {self.steps_per_dispatch}"
            )
        for name, red in (self.metrics or {}).items():
            if red not in _REDUCTIONS:
                raise ValueError(
                    f"metric {name!r}: unknown reduction {red!r} "
                    f"(expected one of {_REDUCTIONS})"
                )
        self._accum = isinstance(self.step_fn, MicrobatchedStep)
        if self._accum:
            self._microbatches = int(self.step_fn.microbatches)
            self._step_fn = build_opt_step(self.step_fn)
        else:
            self._microbatches = 1
            self._step_fn = self.step_fn
        self._programs: Dict[Tuple[int, bool], Callable] = {}
        # per-dispatch telemetry surface (ISSUE 15): the newest
        # window's wall and compile bill, readable WITHOUT the ambient
        # registry — gang workers copy these into their K-boundary
        # telemetry rows (apex_tpu.obs.gangview)
        self.last_dispatch_ms: Optional[float] = None
        self.last_dispatch_compiles: int = 0
        self.last_window_k: int = 0
        #: windows dispatched so far: the newest ``train/dispatch``
        #: span's ``window``
        self.windows_dispatched: int = 0

    @property
    def microbatches(self) -> int:
        """Microbatches per optimizer step (1 unless ``step_fn`` is a
        :class:`~apex_tpu.train.accum.MicrobatchedStep`)."""
        return self._microbatches

    # -- window program construction ------------------------------------

    def _reductions_for(self, names: Iterable[str]) -> Dict[str, str]:
        declared = dict(self.metrics or {})
        return {n: declared.get(n, "mean") for n in names}

    def _build_window(self, k: int, has_batch: bool) -> Callable:
        step_fn = self._step_fn
        per_step = tuple(self.per_step)
        m = self._microbatches

        accum = self._accum

        def window(carry, batches):
            if has_batch and accum:
                # leading K*M microbatch axis -> (K, M, ...): the outer
                # scan steps the optimizer, the unrolled inner loop
                # accumulates the M microbatch grads
                batches = jax.tree_util.tree_map(
                    lambda x: x.reshape((k, m) + x.shape[1:]), batches
                )
            # trace-time peek at the step's metric names/shapes so the
            # scan carry can hold one fp32 accumulator per meter
            peek_batch = (
                jax.tree_util.tree_map(lambda x: x[0], batches)
                if has_batch else None
            )
            m_struct = jax.eval_shape(
                lambda c, b: step_fn(c, b)[1], carry, peek_batch
            )
            if not isinstance(m_struct, dict):
                raise TypeError(
                    "step_fn must return (carry, metrics) with metrics a "
                    f"dict of scalars; got {type(m_struct).__name__}"
                )
            reductions = self._reductions_for(m_struct.keys())
            missing = [n for n in per_step if n not in reductions]
            if missing:
                raise KeyError(
                    f"per_step names {missing} not in step metrics "
                    f"{sorted(reductions)}"
                )
            acc0 = {n: _acc_init(r) for n, r in reductions.items()}

            def body(sc, xs):
                c, acc = sc
                c, m = step_fn(c, xs)
                with jax.named_scope("apex_train_meters"):
                    acc = {
                        n: _acc_update(acc[n], m[n], r)
                        for n, r in reductions.items()
                    }
                return (c, acc), {n: m[n] for n in per_step}

            (carry, acc), traces = jax.lax.scan(
                body, (carry, acc0), batches,
                length=None if has_batch else k,
            )
            meters = {
                n: _acc_final(acc[n], r, k) for n, r in reductions.items()
            }
            return carry, WindowResult(metrics=meters, per_step=traces)

        if self.mesh is not None:
            from apex_tpu.parallel.mesh import shard_map_compat

            spec = self.batch_spec
            if spec is None:
                spec = P(self.axis_name)
            is_spec = lambda s: isinstance(s, P)  # noqa: E731
            window_spec = jax.tree_util.tree_map(
                lambda s: P(None, *s), spec, is_leaf=is_spec
            )
            cspec = P() if self.carry_spec is None else self.carry_spec
            window = shard_map_compat(
                window,
                mesh=self.mesh,
                in_specs=(cspec, window_spec if has_batch else P()),
                out_specs=(cspec, P()),
                check_vma=self.check_vma,
            )
        return jax.jit(window, donate_argnums=(0,) if self.donate else ())

    def reset_programs(self) -> None:
        """Drop every compiled window program — the simulated host
        preemption's teardown (``apex_tpu.resilience``): a restarted
        process re-traces on its next dispatch, exactly like a real
        restart would."""
        self._programs.clear()

    def _resolve_carry_spec(self, carry: PyTree) -> None:
        """Materialize a RulesTable ``carry_spec`` against the first
        real carry (path-matched once; programs compile against the
        resulting spec tree like any hand-built one)."""
        from apex_tpu.sharding import RulesTable, carry_spec_from_rules

        if isinstance(self.carry_spec, RulesTable):
            self.carry_spec = carry_spec_from_rules(
                self.carry_spec, carry, mesh=self.mesh
            )

    def _program(self, k: int, has_batch: bool) -> Callable:
        key = (k, has_batch)
        prog = self._programs.get(key)
        if prog is None:
            prog = self._programs[key] = self._build_window(k, has_batch)
        return prog

    def _window_len(self, batches: PyTree) -> int:
        leaves = jax.tree_util.tree_leaves(batches)
        if not leaves:
            raise ValueError("batched window has no array leaves")
        k = leaves[0].shape[0]
        for leaf in leaves[1:]:
            if leaf.shape[0] != k:
                raise ValueError(
                    "window leaves disagree on the leading (step) axis: "
                    f"{k} vs {leaf.shape[0]}"
                )
        if self._accum:
            m = self._microbatches
            if k % m:
                raise ValueError(
                    f"batched window leading axis ({k} microbatches) is "
                    f"not a multiple of microbatches={m}"
                )
            k //= m
        return k

    # -- execution ------------------------------------------------------

    def run_window(
        self, carry: PyTree, batches: Optional[PyTree] = None
    ) -> Tuple[PyTree, WindowResult]:
        """ONE fused dispatch.

        ``batches`` is a pytree whose leaves carry a leading window axis
        (length ``K * microbatches``; K is this window's optimizer-step
        count), or None to run ``steps_per_dispatch`` steps of
        closure-captured data (``step_fn``/``grad_fn`` receives
        ``batch=None``).  The carry is donated by default — the caller
        must rebind it.
        """
        if batches is None:
            return self._dispatch(self.steps_per_dispatch, False, carry,
                                  None)
        return self._dispatch(self._window_len(batches), True, carry,
                              batches)

    def _dispatch(self, k: int, has_batch: bool, carry, batches):
        """One traced window dispatch: the span covers program lookup
        (a cold call's trace/compile lands here and is tagged via the
        compile-monitor bridge) plus the async dispatch itself."""
        self._resolve_carry_spec(carry)
        tracer = obs.default_tracer()
        fr = obs.default_flightrec()
        if fr.enabled:
            # the black-box entry event: recorded BEFORE the dispatch
            # launches so a crash postmortem shows what was in flight
            fr.record("train/dispatch", k=k,
                      microbatches=self._microbatches)
        self.windows_dispatched += 1
        t0 = time.perf_counter_ns()
        with tracer.span("train/dispatch", k=k,
                         microbatches=self._microbatches,
                         window=self.windows_dispatched) as sp:
            out = self._program(k, has_batch)(carry, batches)
        self.last_dispatch_ms = (time.perf_counter_ns() - t0) * 1e-6
        self.last_dispatch_compiles = sp.compiles
        self.last_window_k = k
        if tracer.enabled:
            _remember_window(out[1], self.windows_dispatched)
            reg = obs.default_registry()
            reg.counter("train.dispatches").inc()
            reg.counter("train.steps").inc(k)
            reg.histogram("train.dispatch_ms").observe(
                self.last_dispatch_ms
            )
        return out

    def run(
        self,
        carry: PyTree,
        windows: Optional[Iterable[PyTree]] = None,
        *,
        steps: Optional[int] = None,
        on_window: Optional[Callable[[int, WindowResult], None]] = None,
    ) -> Tuple[PyTree, int]:
        """Drive many windows; returns ``(carry, total_steps)``.

        ``windows`` yields pre-stacked window pytrees (see
        ``apex_tpu.data.window_batches`` and ``DevicePrefetcher`` for the
        double-buffered host->device overlap).  Without ``windows``,
        ``steps`` closure-data steps run, chunked into K-sized dispatches
        (tail window compiles its own shorter program).  ``on_window`` is
        called after each dispatch with the cumulative step count and the
        window's :class:`WindowResult` — the one place per window where a
        host read is sensible.
        """
        done = 0
        if windows is not None:
            if steps is not None:
                raise ValueError("pass either windows or steps, not both")
            for w in windows:
                carry, res = self.run_window(carry, w)
                done += self._window_len(w)
                if on_window is not None:
                    on_window(done, res)
            return carry, done
        if steps is None:
            raise ValueError("run() needs windows or steps")
        while done < steps:
            k = min(self.steps_per_dispatch, steps - done)
            carry, res = self._dispatch(k, False, carry, None)
            done += k
            if on_window is not None:
                on_window(done, res)
        return carry, done

    def lower(self, carry: PyTree, batches: Optional[PyTree] = None):
        """``jax.jit(...).lower(...)`` of the window program — for HLO
        inspection (``chip_smoke.py`` counts the Mosaic custom calls in
        it) and AOT ``.compile()``."""
        self._resolve_carry_spec(carry)
        if batches is None:
            return self._program(self.steps_per_dispatch, False).lower(
                carry, None
            )
        return self._program(self._window_len(batches), True).lower(
            carry, batches
        )

    # -- checkpointing (window-boundary resume) -------------------------

    def save(self, path: str, carry: PyTree, step: int, **kw) -> str:
        """Persist the carry at a window boundary (any K-boundary works —
        the scaler state rides inside the carry, so a restored run
        continues the growth/backoff trajectory bitwise)."""
        from apex_tpu import checkpoint

        fr = obs.default_flightrec()
        if fr.enabled:
            fr.record("train/checkpoint_save", step=step)
        with obs.default_tracer().span("train/checkpoint_save",
                                       step=step):
            return checkpoint.save_checkpoint(path, carry, step, **kw)

    def restore(
        self, path: str, carry_template: PyTree, step: Optional[int] = None
    ) -> Tuple[PyTree, int]:
        """Restore a carry saved by :meth:`save` into the template's
        structure/shardings; returns ``(carry, step)``."""
        from apex_tpu import checkpoint

        with obs.default_tracer().span("train/checkpoint_restore"):
            restored, step = checkpoint.restore_checkpoint(
                path, carry_template, step
            )
        fr = obs.default_flightrec()
        if fr.enabled:
            fr.record("train/checkpoint_restore", step=step)
        return jax.tree_util.tree_map(jnp.asarray, restored), step
