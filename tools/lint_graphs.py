"""Run the graph-sanitizer suite over the canonical programs.

The four :mod:`apex_tpu.analysis` sanitizers prove Apex's invariants
hardware-free; this tool pins them on the programs that matter — the
fused train-driver window (M in {1, 4} under amp O2, and the zero=True
reduce-scatter/all-gather mode) and the serve K-token decode window on
a tensor-parallel mesh:

- precision lint: no half-precision loss/softmax/norm-stat
  accumulations, no half psums, no master-weight downcast through the
  donated carry;
- collective budgets: exactly one gradient all-reduce per accumulation
  boundary (the RS+AG pair for zero), exactly ``num_layers``
  head-reassembly psums per decode step, census invariant in K;
- donation: every donated carry/cache leaf aliased in the COMPILED
  executable (a dropped donation silently doubles HBM);
- recompile/transfer: re-dispatching a warmed window adds ZERO backend
  compiles, and no host transfers hide inside any lowered program;
- obs instrumentation (ISSUE 6): the apex_tpu.obs telemetry layer is
  host-side by construction, and this sweep PROVES it stays that way —
  the warm mixed-traffic pass runs with engine spans live, and an
  extra check requires the instrumented engine to both record spans
  and add zero backend compiles;
- slo overhead (ISSUE 10): the LIVE half of the telemetry layer — a
  warm traffic pass with the sliding-window SLO tracker live and
  SLO-aware admission enabled must record windowed observations and
  add ZERO backend compiles (burn-alert scheduling reorders host
  decisions, never programs);
- resilience retry (ISSUE 8): a warm fault-injected serve run — one
  retried decode boundary plus one full engine crash-recovery replay —
  must add ZERO backend compiles: the healing paths reuse the
  surviving decoder's compiled programs, never respecialize;
- fleet failover (ISSUE 9): a warm 2-host fleet run that loses one
  host mid-stream (survivors replay its in-flight requests as
  prompt+generated, the host preflights back in) must ALSO add ZERO
  backend compiles — fleet recovery rides the shared warm decoder
  artifact end to end;
- fleet affinity (ISSUE 12): a warm 2-host fleet routing two passes of
  shared-prefix traffic AFFINE (consistent-hash prefix routing), plus
  a disaggregated prefill→decode page handoff and its chaos-killed
  recompute fallback, must add ZERO backend compiles — cache-aware
  routing reorders host choice and the transfer executor is
  bucket-padded, so no program ever respecializes;
- cost census (ISSUE 11): every canonical program's compiled FLOPs /
  bytes-accessed / peak-HBM (XLA ``cost_analysis()`` +
  ``memory_analysis()``) is pinned against its declared
  :class:`~apex_tpu.analysis.costs.CostBudget` — exact FLOPs, bytes
  within tolerance — so a kernel or sharding change that silently
  doubles bytes-moved fails the sweep like a leaked collective would.
  Capability-guarded: a backend whose executables omit the analyses
  records ``census_partial`` instead of failing;
- flightrec overhead (ISSUE 11): a warm traffic pass with the flight
  recorder LIVE must record boundary events while adding ZERO backend
  compiles — the black box is host-side by construction and this
  proves it stays that way;
- sharding rules (ISSUE 13): ONE declarative partition-rule table
  (``apex_tpu.sharding.DEFAULT_RULES``) matched over the GPT + BERT +
  RN50 param trees produces a PINNED spec census per canonical mesh
  shape (dp×tp 2×2, dp 4, dp×fsdp 2×2) with zero unmatched leaves,
  and the fsdp train program (params dp-sharded at rest, one
  all_gather + one reduce_scatter per boundary) passes the
  precision/donation/collective-budget sanitizers with the exact
  collective count pin and zero warm recompiles;
- elastic resize (ISSUE 14): shrinking a warm dp train gang from
  world 4 to world 2 through the canonical gather→reshard path costs
  EXACTLY the new geometry's compiles on the first post-resize window
  (pinned) and ZERO on the second — the elastic gang's recovery
  latency is a relaunch plus one compile bill, never a
  recompile-per-window tax;
- apexlint (ISSUE 19): the SOURCE-side sweep —
  :mod:`apex_tpu.analysis.staticcheck`'s AST rule registry (wall clock
  in deterministic paths, unseeded RNG, non-atomic JSON writes, env
  knobs vs the :mod:`apex_tpu.envs` registry and README table,
  ``clock=`` into flightrec, use-after-donate, unsorted walks,
  ``record(kind=...)``) over ``apex_tpu/``+``tools/``+``tests/`` with
  its census (rules, files, suppressions, violations==0) pinned
  against :data:`APEXLINT_PINS` — ``tools/apexlint.py`` is the same
  sweep as a jax-free CLI.

Exit status is nonzero on any violation::

    JAX_PLATFORMS=cpu python tools/lint_graphs.py [--only NAME]

``tests/test_analysis.py`` wraps this in tier-1 (sharing the lowered
programs through the session-scoped ``canonical`` fixture in
``tests/conftest.py``).  To add a program: add
a ``_build_<name>`` returning a :class:`CanonicalProgram` with its
declared :class:`~apex_tpu.analysis.collectives.CollectiveBudget`, and
list it in ``LINT_PROGRAMS``.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

# CLI-standalone must pin the 8-device CPU mesh BEFORE jax initializes
# its backends (under pytest, tests/conftest.py has already done this)
if "jax" not in sys.modules:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

import dataclasses  # noqa: E402
import time  # noqa: E402
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from apex_tpu.analysis import (  # noqa: E402
    CollectiveBudget,
    CompileMonitor,
    CostBudget,
    DonationError,
    assert_donated,
    census_capability,
    check_budget,
    check_cost_budget,
    collective_summary,
    cost_summary,
    host_transfers,
    lint_jaxpr,
)

N_DEV = 8
D_IN, D_OUT = 64, 32  # w: 64x32 fp32 = 8192 B — well over min_bytes
GRAD_BYTES = D_IN * D_OUT * 4
MIN_BYTES = 1024

# the canonical sweep (the tier-1 gate);
# train_m2 exists for tests/test_inspect_hlo.py's M in {2, 4} contract.
# spec_k8 / paged_int8_k8 (ISSUE 7): the self-speculative window and
# the int8 page pool must hold the same contracts as their plain twins
# — num_layers psums, full donation (scales included), fp32
# accumulation (the int8 gather dequantizes before any reduction, so
# the precision lint stays clean with no allow-list), no host
# transfers, zero warm recompiles.
# train_bf16_m2 / train_int8_m2 / train_dptp_m1 (ISSUE 16): the
# compressed boundary collectives (bf16 half-width psum sanctioned by
# the budget's half_ok pin; int8+error-feedback with the fp32 residual
# in the donated carry) and the dp×tp GSPMD window consuming
# DEFAULT_RULES + activation_rules end to end — all three hold the
# full sanitizer battery, and the `grad_compress` check pins the wire
# ratios on top.
# paged_fused_k8 (ISSUE 20): the fused-read serving window
# (`APEX_TPU_PAGED_FUSED`) — paged_k8's contracts verbatim (num_layers
# psums, full donation, fp32 accumulation, zero warm compiles) with the
# one-pass Pallas gather+dequant+attention read in place of the
# materializing view.
LINT_PROGRAMS = (
    "train_m1", "train_m4", "train_zero_m2", "train_bf16_m2",
    "train_int8_m2", "train_dptp_m1", "decode_k1", "decode_k8",
    "paged_k1", "paged_k8", "spec_k8", "paged_int8_k8",
    "paged_fused_k8",
)
# train_fsdp_m2 is exercised by the `sharding_rules` check (ISSUE 13)
# rather than as its own sweep row — one check covers the tri-model
# rules census AND the fsdp program's sanitizer pass.
ALL_PROGRAMS = LINT_PROGRAMS + ("train_m2", "train_fsdp_m2")

_HALF = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


@dataclasses.dataclass
class CanonicalProgram:
    """One jitted program + its declared contracts, lazily analyzed.

    ``program`` is the jitted callable, ``args`` example arguments for
    lowering (shape-only use), ``make_args`` a rebuilder for execution
    checks (execution DONATES, so static analyses never reuse executed
    args).  ``jaxpr``/``lowered_text``/``compiled`` each compute once
    and cache — the property the session-scoped test fixture exists
    for.
    """

    name: str
    program: Callable
    args: Tuple[Any, ...]
    make_args: Callable[[], Tuple[Any, ...]]
    donate_argnums: Tuple[int, ...]
    budget: CollectiveBudget
    policy: Any = None
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    # the ISSUE 11 cost pin, declared next to the collective budget;
    # None = census recorded but unpinned
    cost_budget: Optional[CostBudget] = None
    _jaxpr: Any = None
    _lowered_text: Optional[str] = None
    _compiled: Any = None
    _cost_summary: Any = None

    def jaxpr(self):
        if self._jaxpr is None:
            self._jaxpr = jax.make_jaxpr(self.program)(*self.args)
        return self._jaxpr

    def lowered_text(self) -> str:
        if self._lowered_text is None:
            self._lowered_text = self.program.lower(*self.args).as_text()
        return self._lowered_text

    def compiled(self):
        if self._compiled is None:
            self._compiled = self.program.lower(*self.args).compile()
        return self._compiled

    def cost_summary(self) -> Dict[str, Any]:
        """The compiled executable's cost census (cached; see
        :func:`apex_tpu.analysis.cost_summary` — capability-guarded,
        never raises on a census-less backend)."""
        if self._cost_summary is None:
            self._cost_summary = cost_summary(self.compiled())
        return self._cost_summary


# ISSUE 11: the compiled-cost pins, measured on this container's XLA
# (jax 0.9.0 CPU, 8-device mesh; re-pinned in PR 28 from the 0.4.37
# figures, old and new in CHANGES.md) — FLOPs pinned EXACTLY (HLO cost
# analysis is deterministic for a fixed toolchain), bytes within 10%,
# the peak-HBM bound (args + temps + outputs) within 25%.  A failing
# pin means the program's compute or memory traffic changed: re-measure
# with ``tools/lint_graphs.py --census-out -`` and re-pin DELIBERATELY.
# Note XLA counts a while/scan body once, not times its trip count —
# which is why decode_k1 and decode_k8 pin nearly identical numbers.
COST_PINS: Dict[str, CostBudget] = {
    "train_m1": CostBudget(flops=41329.0, bytes_accessed=110909.0,
                           peak_hbm_bytes=51284),
    "train_m4": CostBudget(flops=99646.0, bytes_accessed=224925.0,
                           peak_hbm_bytes=80596),
    "train_zero_m2": CostBudget(flops=54216.0, bytes_accessed=175261.0,
                                peak_hbm_bytes=59124),
    "train_bf16_m2": CostBudget(flops=74422.0, bytes_accessed=157789.0,
                                peak_hbm_bytes=61012),
    "train_int8_m2": CostBudget(flops=99021.0, bytes_accessed=242357.0,
                                peak_hbm_bytes=79524),
    "train_dptp_m1": CostBudget(flops=27161094.0, bytes_accessed=14778988.0,
                                peak_hbm_bytes=4859228),
    "decode_k1": CostBudget(flops=2448143.0, bytes_accessed=4942264.0,
                            peak_hbm_bytes=2627322),
    "decode_k8": CostBudget(flops=2450187.0, bytes_accessed=5047761.0,
                            peak_hbm_bytes=2744650),
    "paged_k1": CostBudget(flops=2448429.0, bytes_accessed=5036824.0,
                           peak_hbm_bytes=2684218),
    "paged_k8": CostBudget(flops=2450329.0, bytes_accessed=5146577.0,
                           peak_hbm_bytes=2773466),
    "spec_k8": CostBudget(flops=9837879.0, bytes_accessed=6551975.0,
                          peak_hbm_bytes=2853922),
    "paged_int8_k8": CostBudget(flops=2521537.0, bytes_accessed=3931329.0,
                                peak_hbm_bytes=2426234),
    # the fused read in INTERPRET mode (off-TPU the kernel body traces
    # as plain ops, so this census prices the interpreter's explicit
    # page staging, not the Mosaic DMA schedule)
    "paged_fused_k8": CostBudget(flops=2416399.0, bytes_accessed=6506619.0,
                                 peak_hbm_bytes=2914578),
}

# which tracer span each program's dispatches run under — the join key
# the trace_report roofline section uses (census flops over span wall)
_CENSUS_SPANS = {"train": "train/dispatch", "decode": "serve/decode_window",
                 "paged": "serve/decode_window",
                 "spec": "serve/decode_window"}


def _census_span(name: str) -> str:
    return _CENSUS_SPANS.get(name.split("_")[0], "train/dispatch")


class CanonicalPrograms:
    """Lazy name -> :class:`CanonicalProgram` registry (each program is
    built, lowered and compiled at most once per process — shared by
    ``tests/conftest.py`` as a session fixture)."""

    def __init__(self):
        self._cache: Dict[str, CanonicalProgram] = {}

    def get(self, name: str) -> CanonicalProgram:
        if name not in self._cache:
            builder = _BUILDERS.get(name)
            if builder is None:
                raise KeyError(
                    f"unknown canonical program {name!r}; have "
                    f"{sorted(_BUILDERS)}"
                )
            prog = builder()
            prog.cost_budget = COST_PINS.get(name)
            prog.meta.setdefault("span", _census_span(name))
            self._cache[name] = prog
        return self._cache[name]


# --------------------------------------------------------------------------
# canonical program builders
# --------------------------------------------------------------------------

def _mesh8():
    from jax.sharding import Mesh

    return Mesh(np.array(jax.devices()[:N_DEV]), axis_names=("data",))


def amp_problem(with_ddp: bool = True):
    """The PR-2 toy AMP O2 problem every driver-window proof runs on:
    fp32 data, bf16 compute params + fp32 masters, scaled loss, loss
    pmean per microbatch (scalar — excluded by MIN_BYTES)."""
    import apex_tpu.amp as amp
    from apex_tpu.optimizers import fused_sgd
    from apex_tpu.parallel import DistributedDataParallel

    amp_ = amp.initialize("O2")
    opt = amp.AmpOptimizer(fused_sgd(0.05, momentum=0.9), amp_)
    ddp = (
        DistributedDataParallel(axis_name="data",
                                allreduce_always_fp32=True)
        if with_ddp else None
    )

    def grad_fn(carry, batch):
        # index, don't unpack: the int8+ef carry appends the
        # error-feedback residual as a third leaf (train_int8_m2)
        params, state = carry[0], carry[1]
        x, y = batch

        def scaled(mp):
            pred = x @ mp["w"]
            loss = jnp.mean(jnp.square(pred - y))
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        return grads, {"loss": jax.lax.pmean(loss, "data")}

    rng = np.random.RandomState(0)
    p = {"w": jnp.asarray(rng.randn(D_IN, D_OUT).astype(np.float32) * 0.1)}
    xs = jnp.asarray(rng.randn(8, 16, D_IN).astype(np.float32))
    ys = jnp.asarray(rng.randn(8, 16, D_OUT).astype(np.float32))
    return amp_, opt, ddp, grad_fn, p, xs, ys


def _build_train(m: int) -> CanonicalProgram:
    from apex_tpu.parallel import replicate
    from apex_tpu.train import FusedTrainDriver, amp_microbatch_step

    amp_, opt, ddp, grad_fn, p, xs, ys = amp_problem()
    mesh = _mesh8()
    step = amp_microbatch_step(grad_fn, opt, ddp=ddp, microbatches=m)
    driver = FusedTrainDriver(step, steps_per_dispatch=2, mesh=mesh,
                              check_vma=False)

    def make_args():
        carry = (replicate(p, mesh), replicate(opt.init(p), mesh))
        return carry, (xs[: 2 * m], ys[: 2 * m])

    args = make_args()
    return CanonicalProgram(
        name=f"train_m{m}",
        program=driver._program(2, True),
        args=args,
        make_args=make_args,
        donate_argnums=(0,),
        budget=CollectiveBudget(
            name=f"train_m{m}", min_bytes=MIN_BYTES,
            counts={"all_reduce": 1},
            bytes={"all_reduce": GRAD_BYTES},
        ),
        policy=amp_.policy,
        meta={"grad_bytes": GRAD_BYTES, "microbatches": m,
              "samples_per_boundary": m * xs.shape[1]},
    )


def _build_train_zero(m: int) -> CanonicalProgram:
    from jax.sharding import PartitionSpec as P

    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.parallel import replicate
    from apex_tpu.train import (
        FusedTrainDriver,
        zero_init,
        zero_microbatch_step,
        zero_state_spec,
    )

    amp_, _, _, grad_fn, p, xs, ys = amp_problem()
    mesh = _mesh8()
    zopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
    spec = zopt.make_spec(p, N_DEV)
    step = zero_microbatch_step(grad_fn, zopt, amp_, spec, microbatches=m)
    driver = FusedTrainDriver(
        step, steps_per_dispatch=2, mesh=mesh, check_vma=False,
        carry_spec=(P(), zero_state_spec()),
    )

    def make_args():
        carry = (replicate(p, mesh), zero_init(zopt, amp_, p, spec, mesh))
        return carry, (xs[: 2 * m], ys[: 2 * m])

    args = make_args()
    return CanonicalProgram(
        name=f"train_zero_m{m}",
        program=driver._program(2, True),
        args=args,
        make_args=make_args,
        donate_argnums=(0,),
        budget=CollectiveBudget(
            name=f"train_zero_m{m}", min_bytes=MIN_BYTES,
            counts={"reduce_scatter": 1, "all_gather": 1},
            bytes={"reduce_scatter": spec.padded * 4,
                   "all_gather": spec.padded * 4},
        ),
        policy=amp_.policy,
        meta={"padded": spec.padded, "microbatches": m},
    )


def _build_train_fsdp(m: int) -> CanonicalProgram:
    """The fsdp reduction policy's window (ISSUE 13): params at rest
    as the dp-sharded flat fp32 master, ONE all_gather (the boundary
    prepare) + ONE reduce_scatter per boundary — both pinned at the
    padded flat size, scan-body-traced once so the census is
    K-invariant like the zero twin's."""
    from apex_tpu.contrib.optimizers import DistributedFusedAdam
    from apex_tpu.train import (
        FusedTrainDriver,
        fsdp_init,
        fsdp_microbatch_step,
        fsdp_param_spec,
        fsdp_state_spec,
    )

    amp_, _, _, grad_fn, p, xs, ys = amp_problem()
    mesh = _mesh8()
    fopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
    spec = fopt.make_spec(p, N_DEV)
    step = fsdp_microbatch_step(grad_fn, fopt, amp_, spec, microbatches=m)
    driver = FusedTrainDriver(
        step, steps_per_dispatch=2, mesh=mesh, check_vma=False,
        carry_spec=(fsdp_param_spec(), fsdp_state_spec()),
    )

    def make_args():
        carry = fsdp_init(fopt, amp_, p, spec, mesh)
        return carry, (xs[: 2 * m], ys[: 2 * m])

    args = make_args()
    return CanonicalProgram(
        name=f"train_fsdp_m{m}",
        program=driver._program(2, True),
        args=args,
        make_args=make_args,
        donate_argnums=(0,),
        budget=CollectiveBudget(
            name=f"train_fsdp_m{m}", min_bytes=MIN_BYTES,
            counts={"reduce_scatter": 1, "all_gather": 1},
            bytes={"reduce_scatter": spec.padded * 4,
                   "all_gather": spec.padded * 4},
        ),
        policy=amp_.policy,
        meta={"padded": spec.padded, "microbatches": m},
    )


def _build_train_compress(mode: str, m: int) -> CanonicalProgram:
    """The ISSUE 16 compressed boundary collective on the amp window:
    ``bf16`` halves the gradient all-reduce payload (a DELIBERATE
    half-width psum — sanctioned by the budget's ``half_ok`` pin, not
    an allow-list waiver), ``int8`` quarters it and carries the fp32
    error-feedback residual through the donated scan carry (its amax
    pmax is a 4 B scalar, below ``MIN_BYTES``)."""
    from jax.sharding import PartitionSpec as P

    from apex_tpu.parallel import replicate
    from apex_tpu.train import (
        FusedTrainDriver,
        amp_microbatch_step,
        ef_init,
        ef_length,
        ef_place,
        ef_state_spec,
    )

    amp_, opt, ddp, grad_fn, p, xs, ys = amp_problem()
    mesh = _mesh8()
    step = amp_microbatch_step(grad_fn, opt, ddp=ddp, microbatches=m,
                               compress=mode)
    use_ef = step.compress.error_feedback
    carry_spec = (P(), P()) + ((ef_state_spec(),) if use_ef else ())
    driver = FusedTrainDriver(step, steps_per_dispatch=2, mesh=mesh,
                              check_vma=False, carry_spec=carry_spec)

    def make_args():
        carry = (replicate(p, mesh), replicate(opt.init(p), mesh))
        if use_ef:
            carry = carry + (ef_place(ef_init(ef_length(p), N_DEV),
                                      mesh),)
        return carry, (xs[: 2 * m], ys[: 2 * m])

    wire_bytes = GRAD_BYTES // (2 if mode == "bf16" else 4)
    args = make_args()
    return CanonicalProgram(
        name=f"train_{mode}_m{m}",
        program=driver._program(2, True),
        args=args,
        make_args=make_args,
        donate_argnums=(0,),
        budget=CollectiveBudget(
            name=f"train_{mode}_m{m}", min_bytes=MIN_BYTES,
            counts={"all_reduce": 1},
            bytes={"all_reduce": wire_bytes},
            half_ok=("all_reduce",) if mode == "bf16" else (),
        ),
        policy=amp_.policy,
        meta={"grad_bytes": GRAD_BYTES, "wire_bytes": wire_bytes,
              "microbatches": m, "compress": mode,
              "samples_per_boundary": m * xs.shape[1]},
    )


# the dp×tp window is GSPMD: its collectives are the partitioner's to
# derive from the sharding annotations at compile time, so the
# unpartitioned StableHLO the budget reads must stay COLLECTIVE-FREE —
# a hand-rolled psum/all_gather appearing here means someone bypassed
# the rules layer, which is exactly the regression this pin catches.
_DPTP_BUDGET = CollectiveBudget(
    name="train_dptp_m1", min_bytes=0, counts={},
)


def _build_train_dptp(m: int) -> CanonicalProgram:
    """The dp×tp GSPMD train window (the ISSUE 16 hierarchical-exchange
    prerequisite): ONE declarative pass shards the whole step — tiny-GPT
    params at rest under ``sharding.DEFAULT_RULES`` on ``train_mesh(2,
    tp=2)``, activations constrained INSIDE the jitted step through
    ``sharding.activation_rules`` (the ``act/<role>`` anchor
    convention), no shard_map anywhere.  The budget pins the program
    collective-free: every byte of its communication is the
    partitioner's, derived from the declarative rules."""
    from apex_tpu import sharding as shd
    from apex_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    mesh = shd.train_mesh(2, tp=2)
    act_rules = shd.activation_rules()
    rng = np.random.RandomState(0)
    ids0 = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(4, 8)))
    params0 = model.init(jax.random.PRNGKey(0), ids0)["params"]

    def step_fn(params, ids):
        acts = shd.constrain_tree({"act": {"tokens": ids}}, act_rules,
                                  mesh)
        ids = acts["act"]["tokens"]

        def loss_fn(p):
            logits = model.apply({"params": p}, ids)
            logits = shd.constrain_tree(
                {"act": {"hidden": logits}}, act_rules, mesh
            )["act"]["hidden"]
            targets = jnp.roll(ids, -1, axis=1)
            logp = jax.nn.log_softmax(logits.astype(jnp.float32))
            return -jnp.mean(
                jnp.take_along_axis(logp, targets[..., None], axis=-1)
            )

        loss, grads = jax.value_and_grad(loss_fn)(params)
        new_params = jax.tree_util.tree_map(
            lambda w, g: w - 0.05 * g, params, grads
        )
        # grads inherit the partitioner's layout; pin the updated
        # params back to the SAME at-rest rules the args entered under
        new_params = shd.constrain_tree(new_params, shd.DEFAULT_RULES,
                                        mesh)
        return new_params, loss

    program = jax.jit(step_fn, donate_argnums=(0,))

    def make_args():
        params = shd.shard_tree(
            jax.tree_util.tree_map(np.asarray, params0),
            shd.DEFAULT_RULES, mesh,
        )
        return params, jax.device_put(ids0)

    args = make_args()
    return CanonicalProgram(
        name=f"train_dptp_m{m}",
        program=program,
        args=args,
        make_args=make_args,
        donate_argnums=(0,),
        budget=_DPTP_BUDGET,
        meta={"mesh": "dp2_tp2", "microbatches": m,
              "num_layers": cfg.num_layers},
    )


def _build_decode(k: int) -> CanonicalProgram:
    import apex_tpu.serve as serve
    from apex_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 8)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    dec = serve.GPTDecoder(cfg, params, mesh=serve.serve_mesh(2))
    slots = 2

    def make_args():
        cache = dec.init_cache(slots, 64)
        toks = jnp.zeros((slots,), jnp.int32)
        active = jnp.ones((slots,), bool)
        return (dec.params, cache, toks, active,
                dec._samp_default(slots), jax.random.PRNGKey(0))

    args = make_args()
    return CanonicalProgram(
        name=f"decode_k{k}",
        program=dec._program(("window", k, slots)),
        args=args,
        make_args=make_args,
        donate_argnums=(1,),
        # the Megatron attention minimum on a head-sharded cache: ONE
        # reassembly psum per layer, traced once in the scan body (so
        # the census is K-invariant — checked across k1/k8 in run())
        budget=CollectiveBudget(
            name=f"decode_k{k}",
            counts={"all_reduce": cfg.num_layers},
        ),
        meta={"k_tokens": k, "num_layers": cfg.num_layers},
    )


PAGED_SLOTS, PAGED_PAGE_LEN, PAGED_MAX_LEN = 2, 8, 64


def _build_paged_decode(k: int) -> CanonicalProgram:
    import apex_tpu.serve as serve
    from apex_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 8)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    dec = serve.GPTDecoder(cfg, params, mesh=serve.serve_mesh(2))
    pps = PAGED_MAX_LEN // PAGED_PAGE_LEN
    num_pages = 1 + PAGED_SLOTS * pps

    def make_args():
        cache = dec.init_paged_cache(num_pages, PAGED_SLOTS,
                                     PAGED_PAGE_LEN)
        # each slot owns a distinct page run (the engine's steady state)
        tables = np.arange(
            1, 1 + PAGED_SLOTS * pps, dtype=np.int32
        ).reshape(PAGED_SLOTS, pps)
        toks = jnp.zeros((PAGED_SLOTS,), jnp.int32)
        active = jnp.ones((PAGED_SLOTS,), bool)
        return (dec.params, cache, jnp.asarray(tables), toks, active,
                dec._samp_default(PAGED_SLOTS), jax.random.PRNGKey(0))

    args = make_args()
    return CanonicalProgram(
        name=f"paged_k{k}",
        program=dec._program(
            ("pwindow", k, PAGED_SLOTS, pps, PAGED_PAGE_LEN, False,
             False)
        ),
        args=args,
        make_args=make_args,
        donate_argnums=(1,),
        # paging must not change the collective story: the page-table
        # gather indexes the UNSHARDED page axis, so the census stays
        # the Megatron head-reassembly minimum — num_layers psums per
        # step, traced once in the scan body (K-invariant, checked
        # across paged_k1/paged_k8 in run())
        budget=CollectiveBudget(
            name=f"paged_k{k}",
            counts={"all_reduce": cfg.num_layers},
        ),
        meta={"k_tokens": k, "num_layers": cfg.num_layers,
              "decoder": dec, "page_len": PAGED_PAGE_LEN,
              "num_pages": num_pages},
    )


SPEC_DRAFT = 3  # verify blocks of 1 + 3 positions, 2 steps at K=8


def _build_spec_decode(k: int) -> CanonicalProgram:
    """The self-speculative window on the TP2 mesh (ngram proposer —
    the canonical mode: drafting is pure carry arithmetic, so the
    collective census must STAY the num_layers head-reassembly psums of
    the plain window, verify-block width notwithstanding)."""
    import apex_tpu.serve as serve
    from apex_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 8)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    dec = serve.GPTDecoder(cfg, params, mesh=serve.serve_mesh(2),
                           tokens_per_dispatch=k,
                           spec_tokens=SPEC_DRAFT)
    slots = 2

    def make_args():
        cache = dec.init_cache(slots, 64)
        toks = jnp.zeros((slots,), jnp.int32)
        active = jnp.ones((slots,), bool)
        hist = jnp.full((slots, dec.spec_hist), -1, jnp.int32)
        return (dec.params, cache, toks, active, hist,
                dec._samp_default(slots), jax.random.PRNGKey(0))

    args = make_args()
    return CanonicalProgram(
        name=f"spec_k{k}",
        program=dec._program(
            ("swindow", dec.spec_steps, SPEC_DRAFT, slots)
        ),
        args=args,
        make_args=make_args,
        donate_argnums=(1,),
        budget=CollectiveBudget(
            name=f"spec_k{k}",
            counts={"all_reduce": cfg.num_layers},
        ),
        meta={"k_tokens": k, "num_layers": cfg.num_layers,
              "spec_steps": dec.spec_steps, "draft": SPEC_DRAFT},
    )


def _build_paged_int8(k: int) -> CanonicalProgram:
    """The int8 page-pool window on the TP2 mesh: the quantized gather
    dequantizes into fp32 BEFORE any reduction (no half/precision-lint
    exception needed), the scale arrays donate with the pool, and the
    census stays num_layers psums."""
    import apex_tpu.serve as serve
    from apex_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 8)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    dec = serve.GPTDecoder(cfg, params, mesh=serve.serve_mesh(2),
                           kv_int8=True)
    pps = PAGED_MAX_LEN // PAGED_PAGE_LEN
    num_pages = 1 + PAGED_SLOTS * pps

    def make_args():
        cache = dec.init_paged_cache(num_pages, PAGED_SLOTS,
                                     PAGED_PAGE_LEN)
        tables = np.arange(
            1, 1 + PAGED_SLOTS * pps, dtype=np.int32
        ).reshape(PAGED_SLOTS, pps)
        toks = jnp.zeros((PAGED_SLOTS,), jnp.int32)
        active = jnp.ones((PAGED_SLOTS,), bool)
        return (dec.params, cache, jnp.asarray(tables), toks, active,
                dec._samp_default(PAGED_SLOTS), jax.random.PRNGKey(0))

    args = make_args()
    return CanonicalProgram(
        name=f"paged_int8_k{k}",
        program=dec._program(
            ("pwindow", k, PAGED_SLOTS, pps, PAGED_PAGE_LEN, True,
             False)
        ),
        args=args,
        make_args=make_args,
        donate_argnums=(1,),
        budget=CollectiveBudget(
            name=f"paged_int8_k{k}",
            counts={"all_reduce": cfg.num_layers},
        ),
        meta={"k_tokens": k, "num_layers": cfg.num_layers,
              "decoder": dec, "page_len": PAGED_PAGE_LEN,
              "num_pages": num_pages},
    )


def _build_paged_fused(k: int) -> CanonicalProgram:
    """The ISSUE 20 fused-read window on the TP2 mesh: the paged K8
    program with ``paged_fused=True``, so every layer's cache read is
    the one-pass Pallas gather+dequant+attention kernel (interpret mode
    off-TPU) instead of the materializing view.  The kernel indexes the
    UNSHARDED page axis and reduces nothing across devices, so the
    census must STAY the num_layers head-reassembly psums — fusing the
    read changes bytes moved, never the collective story."""
    import apex_tpu.serve as serve
    from apex_tpu.models.gpt import GPTConfig, GPTLM

    cfg = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                         attn_dropout_rate=0.0)
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, cfg.vocab_size, size=(1, 8)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    dec = serve.GPTDecoder(cfg, params, mesh=serve.serve_mesh(2),
                           paged_fused=True)
    pps = PAGED_MAX_LEN // PAGED_PAGE_LEN
    num_pages = 1 + PAGED_SLOTS * pps

    def make_args():
        cache = dec.init_paged_cache(num_pages, PAGED_SLOTS,
                                     PAGED_PAGE_LEN)
        tables = np.arange(
            1, 1 + PAGED_SLOTS * pps, dtype=np.int32
        ).reshape(PAGED_SLOTS, pps)
        toks = jnp.zeros((PAGED_SLOTS,), jnp.int32)
        active = jnp.ones((PAGED_SLOTS,), bool)
        return (dec.params, cache, jnp.asarray(tables), toks, active,
                dec._samp_default(PAGED_SLOTS), jax.random.PRNGKey(0))

    args = make_args()
    return CanonicalProgram(
        name=f"paged_fused_k{k}",
        program=dec._program(
            ("pwindow", k, PAGED_SLOTS, pps, PAGED_PAGE_LEN, False,
             True)
        ),
        args=args,
        make_args=make_args,
        donate_argnums=(1,),
        budget=CollectiveBudget(
            name=f"paged_fused_k{k}",
            counts={"all_reduce": cfg.num_layers},
        ),
        meta={"k_tokens": k, "num_layers": cfg.num_layers,
              "decoder": dec, "page_len": PAGED_PAGE_LEN,
              "num_pages": num_pages},
    )


_BUILDERS = {
    "train_m1": lambda: _build_train(1),
    "train_m2": lambda: _build_train(2),
    "train_m4": lambda: _build_train(4),
    "train_zero_m2": lambda: _build_train_zero(2),
    "train_fsdp_m2": lambda: _build_train_fsdp(2),
    "train_bf16_m2": lambda: _build_train_compress("bf16", 2),
    "train_int8_m2": lambda: _build_train_compress("int8", 2),
    "train_dptp_m1": lambda: _build_train_dptp(1),
    "decode_k1": lambda: _build_decode(1),
    "decode_k8": lambda: _build_decode(8),
    "paged_k1": lambda: _build_paged_decode(1),
    "paged_k8": lambda: _build_paged_decode(8),
    "spec_k8": lambda: _build_spec_decode(8),
    "paged_int8_k8": lambda: _build_paged_int8(8),
    "paged_fused_k8": lambda: _build_paged_fused(8),
}


# --------------------------------------------------------------------------
# the four sanitizers over one program
# --------------------------------------------------------------------------

def _carry_downcasts(prog: CanonicalProgram) -> List[str]:
    """Donated-carry leaves that enter fp32 and leave half — the
    master-weight downcast, visible on the whole window program (the
    carry is output 0 by driver/decoder convention)."""
    out_shapes = jax.eval_shape(prog.program, *prog.args)[0]
    found = []
    for argnum in prog.donate_argnums:
        flat_in = jax.tree_util.tree_flatten_with_path(prog.args[argnum])[0]
        flat_out = jax.tree_util.tree_leaves(out_shapes)
        if len(flat_in) != len(flat_out):
            continue  # structure change is the driver's own error
        for (path, leaf_in), leaf_out in zip(flat_in, flat_out):
            din = getattr(leaf_in, "dtype", None)
            dout = getattr(leaf_out, "dtype", None)
            if din == jnp.dtype(jnp.float32) and dout in _HALF:
                found.append(
                    f"{prog.name}: master-downcast: carry leaf "
                    f"{jax.tree_util.keystr(path)} enters {din} and "
                    f"leaves {dout}"
                )
    return found


def lint_program(prog: CanonicalProgram) -> List[str]:
    """Static sanitizers (precision, budget, donation, transfers) over
    one canonical program; violation strings, empty = clean.

    A budget that names kinds in ``half_ok`` sanctions exactly one
    half-width payload per kind — the budget's ``bytes`` pin for it
    (ISSUE 16's deliberate bf16 gradient psum).  The precision lint
    receives that as its per-payload allow-list, never a blanket
    ``allow=("half-psum",)``."""
    errs: List[str] = []
    half_declared = {
        kind: (prog.budget.bytes or {})[kind]
        for kind in getattr(prog.budget, "half_ok", ())
        if kind in (prog.budget.bytes or {})
    }
    for v in lint_jaxpr(prog.jaxpr(), policy=prog.policy,
                        half_collective_bytes=half_declared or None):
        errs.append(f"{prog.name}: {v}")
    if prog.policy is None or prog.policy.master_weights is not False:
        errs.extend(_carry_downcasts(prog))
    errs.extend(check_budget(prog.lowered_text(), prog.budget))
    try:
        assert_donated(prog.compiled(), prog.args, prog.donate_argnums,
                       label=prog.name)
    except DonationError as e:
        errs.append(str(e))
    for t in host_transfers(prog.lowered_text()):
        errs.append(f"{prog.name}: host transfer inside jitted "
                    f"program: {t}")
    return errs


def check_warm_redispatch(prog: CanonicalProgram) -> List[str]:
    """Execute the program twice (rebinding the donated carry, fresh
    args — the originals stay un-donated for the static checks) and
    require the steady-state dispatch to add zero backend compiles:
    the fused-window economics depend on compile-once-run-many.  TWO
    warm calls, because the first rebind can legitimately specialize
    once more — a host-built carry enters unsharded, the returned one
    carries the mesh's NamedSharding."""
    args = list(prog.make_args())
    for _ in range(2):
        out = prog.program(*args)
        for i in prog.donate_argnums:
            args[i] = out[0]  # rebind the donated carry/cache
    with CompileMonitor() as mon:
        prog.program(*args)
    if mon.compiles:
        return [
            f"{prog.name}: re-dispatching the warmed window compiled "
            f"{mon.compiles} new program(s) — shape-unstable loop"
        ]
    return []


def check_cost_census(canonical: CanonicalPrograms,
                      names: Sequence[str]) -> List[str]:
    """The ISSUE 11 cost pin: every program with a declared
    :class:`~apex_tpu.analysis.costs.CostBudget` must report the
    pinned FLOPs exactly and bytes/peak within tolerance.  On a
    backend whose executables omit the analyses the check degrades to
    clean — the recorded census carries ``census_partial`` flags
    saying why (never a KeyError mid-sweep)."""
    if not census_capability():
        return []
    errs: List[str] = []
    for name in names:
        prog = canonical.get(name)
        if prog.cost_budget is None:
            continue
        errs.extend(check_cost_budget(prog.cost_summary(),
                                      prog.cost_budget, name))
    return errs


def collect_census(canonical: Optional[CanonicalPrograms] = None,
                   names: Sequence[str] = LINT_PROGRAMS
                   ) -> Dict[str, Dict[str, Any]]:
    """The machine-readable census over ``names``: per-program
    FLOPs/bytes/peak (``census_partial`` flagged where the backend
    omits them) plus the dispatch-span join key the trace_report
    roofline section consumes.  Written by ``--census-out``."""
    canonical = canonical or CanonicalPrograms()
    out: Dict[str, Dict[str, Any]] = {}
    for name in names:
        prog = canonical.get(name)
        row = dict(prog.cost_summary())
        row["span"] = prog.meta.get("span")
        out[name] = row
    return out


def check_flightrec_overhead(canonical: CanonicalPrograms) -> List[str]:
    """The black box may watch the warm paths but not perturb them
    (ISSUE 11): a warm traffic pass with a live
    :class:`~apex_tpu.obs.FlightRecorder` must (a) record boundary
    events and (b) add ZERO backend compiles — recording is one tuple
    write into a preallocated ring, never device work.  Skipped
    (clean) when the recorder is disabled (``APEX_TPU_FLIGHTREC=0`` /
    ``APEX_TPU_OBS=0``)."""
    from apex_tpu import obs
    from apex_tpu.analysis import CompileMonitor

    if not obs.flightrec_enabled():
        return []
    dec = canonical.get("paged_k8").meta["decoder"]
    fr = obs.FlightRecorder(capacity=512, enabled=True)
    with CompileMonitor() as mon:
        _drive_paged_workload(dec, flightrec=fr)
    errs = []
    if mon.compiles:
        errs.append(
            f"warm traffic with the flight recorder live compiled "
            f"{mon.compiles} new program(s) — recording must stay "
            "host-side (one ring write), never touch compiled programs"
        )
    if not fr.recorded:
        errs.append(
            "the live flight recorder captured no events over the "
            "paged workload — the engine's black-box hookup is dead"
        )
    return errs


def _drive_paged_workload(dec, flightrec=None) -> None:
    """One fixed mixed-length pass through a fresh paged engine on the
    TP2 mesh: two chunk buckets (16 and 8), a shared-prefix duplicate
    admitted after its twin's pages are registered (exercising the
    fully-shared resample path AND a copy-on-write split), and decode
    windows interleaving throughout.  Deterministic — both sweeps run
    byte-identical traffic."""
    from apex_tpu.serve import ServeEngine

    rng = np.random.RandomState(7)
    pool = [int(t) for t in rng.randint(0, 1000, size=(32,))]
    long_p, short_p = pool[:19], pool[19:24]
    kw = {} if flightrec is None else {"flightrec": flightrec}
    eng = ServeEngine(
        dec, slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, paged=True,
        page_len=PAGED_PAGE_LEN, prefill_chunk=16, **kw,
    )
    eng.submit(long_p, max_new_tokens=10)   # chunks: width 16 + width 8
    eng.submit(short_p, max_new_tokens=6)   # chunk: width 8
    for _ in range(3):
        eng.step()
    # long_p is now prefilled + registered: the duplicate shares every
    # page (partial tail included), COWs the written one, and resamples
    # its last token through the 1-token chunk bucket
    eng.submit(list(long_p), max_new_tokens=6)
    eng.run()


def check_paged_mixed_traffic(canonical: CanonicalPrograms) -> List[str]:
    """Warm mixed-length traffic through the paged engine must be
    recompile-free: chunked prefill pads to power-of-two buckets and
    copy-on-write pads to power-of-two copy batches, so after one
    warming pass every program a second identical pass needs is
    compiled.  A violation here means a shape leaked per-length into
    the paged scheduler — the contiguous engine's per-prompt-bucket
    discipline regressed."""
    from apex_tpu.analysis import CompileMonitor

    dec = canonical.get("paged_k8").meta["decoder"]
    _drive_paged_workload(dec)  # warm every bucket/program
    with CompileMonitor() as mon:
        _drive_paged_workload(dec)
    if mon.compiles:
        return [
            f"paged mixed-length warm traffic compiled {mon.compiles} "
            "new program(s) — a per-length shape escaped the "
            "chunk/copy bucketing"
        ]
    return []


def _drive_resilient_workload(dec) -> None:
    """The paged mixed workload behind the self-healing wrapper with a
    FIXED fault plan: one decode-boundary dispatch failure (retried)
    and one full engine crash (fresh engine rebuilt, in-flight
    requests replayed as prompt+generated).  Deterministic — two runs
    inject and recover identically."""
    from apex_tpu.obs import MetricsRegistry
    from apex_tpu.resilience import (
        DISPATCH_ERROR,
        ENGINE_CRASH,
        FaultEvent,
        FaultInjector,
        FaultPlan,
        ResilientServeEngine,
    )

    plan = FaultPlan([
        FaultEvent("serve/decode_window", 1, DISPATCH_ERROR),
        FaultEvent("serve/boundary", 3, ENGINE_CRASH),
    ])
    inj = FaultInjector(plan, registry=MetricsRegistry())
    rng = np.random.RandomState(7)
    pool = [int(t) for t in rng.randint(0, 1000, size=(32,))]
    long_p, short_p = pool[:19], pool[19:24]
    eng = ResilientServeEngine(
        dec, injector=inj, registry=inj.registry, enabled=True,
        slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, paged=True,
        page_len=PAGED_PAGE_LEN, prefill_chunk=16,
    )
    eng.submit(long_p, max_new_tokens=10)
    eng.submit(short_p, max_new_tokens=6)
    eng.run()
    if not (eng.retries and eng.restarts):
        raise AssertionError(
            f"resilient workload did not exercise recovery (retries="
            f"{eng.retries}, restarts={eng.restarts})"
        )


def check_resilience_retry(canonical: CanonicalPrograms) -> List[str]:
    """The self-healing paths may not respecialize (ISSUE 8): a warm
    RETRIED decode boundary re-runs the identical compiled window, and
    a rebuilt-engine crash replay re-prefills through already-compiled
    bucket programs (the decoder — and its program cache — survives
    the crash by design).  One warming pass covers every program the
    faulted run needs (replayed prompt+generated lengths included);
    the second identical faulted pass must then add ZERO backend
    compiles."""
    from apex_tpu.analysis import CompileMonitor

    dec = canonical.get("paged_k8").meta["decoder"]
    _drive_resilient_workload(dec)  # warm retry + crash-replay paths
    with CompileMonitor() as mon:
        _drive_resilient_workload(dec)
    if mon.compiles:
        return [
            f"warm fault-injected serve run compiled {mon.compiles} "
            "new program(s) — the retry/crash-replay path respecialized "
            "(a resilient recovery must reuse the surviving decoder's "
            "compiled programs)"
        ]
    return []


def _drive_fleet_workload(dec) -> None:
    """A 2-host fleet draining mixed traffic (shared-prefix duplicate
    included) with a FIXED host-scoped fault plan: host 0 dies
    mid-stream, its in-flight requests replay on host 1 as
    prompt+generated, and host 0 is later restarted through a
    preflight-gated readmission.  Deterministic — two runs inject and
    recover identically."""
    from apex_tpu.fleet import FleetHost, FleetRouter
    from apex_tpu.resilience import (
        HOST_LOSS,
        RESTART,
        FaultEvent,
        FaultPlan,
        host_site,
    )

    rng = np.random.RandomState(7)
    pool = [int(t) for t in rng.randint(0, 1000, size=(32,))]
    long_p, short_p = pool[:19], pool[19:24]
    plan = FaultPlan([
        FaultEvent(host_site(0), 2, HOST_LOSS),
        FaultEvent(host_site(0), 4, RESTART),
    ])
    hosts = [
        FleetHost(i, dec, slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN,
                  paged=True, page_len=PAGED_PAGE_LEN, prefill_chunk=16)
        for i in range(2)
    ]
    router = FleetRouter(hosts, fault_plan=plan)
    router.submit(long_p, max_new_tokens=10)
    router.submit(short_p, max_new_tokens=6)
    router.submit(list(long_p), max_new_tokens=6)  # shared prefix
    router.run()
    stats = router.stats()
    if not stats["host_losses"]:
        raise AssertionError(
            f"fleet workload never lost a host: {stats}"
        )


def check_fleet_failover(canonical: CanonicalPrograms) -> List[str]:
    """Host-loss failover may not respecialize (ISSUE 9): survivors
    replay a dead host's in-flight requests as prompt+generated through
    their OWN warm programs (the fleet shares the compiled decoder
    artifact), and preflight-gated readmission re-runs already-compiled
    windows.  One warming pass covers every program (replay lengths and
    the preflight sweep included); the second identical chaotic pass —
    host loss, recovery, restart, preflight — must add ZERO backend
    compiles."""
    from apex_tpu.analysis import CompileMonitor

    dec = canonical.get("paged_k8").meta["decoder"]
    _drive_fleet_workload(dec)  # warm failover + preflight paths
    with CompileMonitor() as mon:
        _drive_fleet_workload(dec)
    if mon.compiles:
        return [
            f"warm fleet failover compiled {mon.compiles} new "
            "program(s) — host-loss replay on survivors (or the "
            "preflight readmission) respecialized instead of reusing "
            "the shared warm decoder programs"
        ]
    return []


def _drive_affinity_workload(dec) -> None:
    """ISSUE 12's fleet traffic, twice over one decoder: (1) a 2-host
    AFFINITY fleet draining two passes of Zipf-style shared-prefix
    traffic — routing must land the sharers where the pages are
    (asserted via affinity hits + a nonzero fleet prefix-hit rate);
    (2) a DISAGGREGATED prefill/decode fleet where one handoff
    completes and a second is killed mid-transfer by host-scoped chaos
    (the prefill host dies in the pending window), recovering through
    the recompute fallback.  Deterministic — both sweeps run
    byte-identical traffic, so the second pass pins zero compiles."""
    from apex_tpu.fleet import FleetHost, FleetRouter
    from apex_tpu.obs import MetricsRegistry
    from apex_tpu.resilience import (
        HOST_LOSS,
        RESTART,
        FaultEvent,
        FaultPlan,
        host_site,
    )

    rng = np.random.RandomState(11)
    pool = [int(t) for t in rng.randint(0, 1000, size=(64,))]
    pA, pB = pool[:8], pool[8:16]
    kw = dict(slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, paged=True,
              page_len=PAGED_PAGE_LEN, prefill_chunk=16)
    # -- leg 1: prefix-affinity routing, shared prefixes land affine --
    hosts = [FleetHost(i, dec, **kw) for i in range(2)]
    router = FleetRouter(hosts, registry=MetricsRegistry(),
                         affinity=True)
    # one long-lived anchor per prefix family keeps its pages
    # registered while the sharers (two passes) admit against them
    router.submit(pA + pool[16:20], max_new_tokens=24)
    router.submit(pB + pool[20:24], max_new_tokens=24)
    for s in (24, 29, 43, 46):
        router.submit(pA + pool[s:s + 4], max_new_tokens=6)
        router.submit(pB + pool[s + 4:s + 8], max_new_tokens=6)
    router.run()
    stats = router.stats()
    if not stats["affinity_hits"]:
        raise AssertionError(
            f"affinity fleet routed no request affine: {stats}"
        )
    if stats["fleet_prefix_hit_rate"] <= 0:
        raise AssertionError(
            "affine routing produced no fleet-level prefix hits: "
            f"{stats}"
        )
    # -- leg 2: disaggregated prefill/decode + mid-transfer chaos -----
    plan = FaultPlan([
        FaultEvent(host_site(0), 2, HOST_LOSS),
        FaultEvent(host_site(0), 4, RESTART),
    ])
    hosts = [FleetHost(0, dec, role="prefill", **kw),
             FleetHost(1, dec, role="decode", **kw)]
    router = FleetRouter(hosts, registry=MetricsRegistry(),
                         fault_plan=plan, affinity=True)
    router.submit(pA + pool[16:20], max_new_tokens=10)
    router.submit(pool[24:33], max_new_tokens=8)
    router.submit(pB + pool[20:24], max_new_tokens=8)
    router.run()
    stats = router.stats()
    if not stats["handoffs"] and not stats["handoff_fallbacks"] \
            and not stats["requests_recovered"]:
        raise AssertionError(
            f"disaggregated fleet neither handed off nor recovered: "
            f"{stats}"
        )
    if not stats["host_losses"]:
        raise AssertionError(
            f"chaos plan never killed the prefill host: {stats}"
        )


def check_fleet_affinity(canonical: CanonicalPrograms) -> List[str]:
    """Cache-aware fleet routing may not respecialize (ISSUE 12): a
    warm 2-host fleet routing two passes of shared-prefix traffic
    affine — plus a disaggregated prefill→decode handoff and its
    chaos-killed recompute fallback — must add ZERO backend compiles.
    The gather/scatter transfer executor is bucket-padded like the COW
    copy batch, handoff adoption reuses the warm decode windows, and
    the recompute fallback re-prefills through already-compiled chunk
    buckets."""
    from apex_tpu.analysis import CompileMonitor

    dec = canonical.get("paged_k8").meta["decoder"]
    _drive_affinity_workload(dec)  # warm routing + handoff + fallback
    with CompileMonitor() as mon:
        _drive_affinity_workload(dec)
    if mon.compiles:
        return [
            f"warm affinity/disaggregation fleet traffic compiled "
            f"{mon.compiles} new program(s) — the handoff transfer "
            "executor (or the recompute fallback) respecialized "
            "instead of reusing bucket-padded warm programs"
        ]
    return []


def _drive_fleet_scale_workload(dec):
    """ISSUE 17's scale policies over one decoder: (1) a flat 3-host
    fleet with the proactive page REBALANCER live — shared-prefix
    waves heat one owner, the tick ships its registered prefix pages
    to the least-loaded host (export_prefix → wire → import_prefix)
    and re-aims affinity there; (2) a disaggregated prefill/decode
    pair with STREAMING KV handoff — finished page chunks ship while
    the tail of chunked prefill runs, the decode host adopts them
    into a staged slot.  Deterministic; returns the two routers'
    stats so the check can prove both policies actually fired."""
    from apex_tpu.fleet import FleetHost, FleetRouter
    from apex_tpu.obs import MetricsRegistry

    rng = np.random.RandomState(3)
    pool = [int(t) for t in rng.randint(0, 1000, size=(48,))]
    kw = dict(slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, paged=True,
              page_len=PAGED_PAGE_LEN, prefill_chunk=16)
    # -- leg 1: proactive rebalance on a flat fleet ------------------
    shared = pool[0:16]
    hosts = [FleetHost(i, dec, **dict(kw, slots=4))
             for i in range(3)]
    router = FleetRouter(hosts, registry=MetricsRegistry(),
                         rebalance=True, rebalance_every=1,
                         rebalance_min_heat=2, affinity_gap=4)
    # waves, not a burst: proactive migration needs LIVE arrivals
    # after the owner heats up but before spill hosts prefill (and
    # register) the prefix themselves
    for i in range(5):
        router.submit(shared + pool[16 + i:20 + i],
                      max_new_tokens=16, temperature=0.0)
    for _ in range(2):
        router.step()
    for i in range(5, 14):
        router.submit(shared + pool[16 + i:20 + i],
                      max_new_tokens=16, temperature=0.0)
    router.run()
    flat = router.stats()
    # -- leg 2: streaming KV handoff on a disagg pair ----------------
    hosts = [FleetHost(0, dec, role="prefill", **kw),
             FleetHost(1, dec, role="decode", **kw)]
    router = FleetRouter(hosts, registry=MetricsRegistry(),
                         stream_handoff=True)
    for lo, hi in ((0, 40), (1, 44), (2, 38)):
        router.submit(pool[lo:hi], max_new_tokens=8, temperature=0.0)
    router.run()
    return flat, router.stats()


def check_fleet_scale(canonical: CanonicalPrograms) -> List[str]:
    """The ISSUE 17 scale policies may not respecialize: a warm fleet
    pass with the proactive page rebalancer AND streaming KV handoff
    live must add ZERO backend compiles — page migration rides the
    bucket-padded gather/adopt transfer executors and streamed chunks
    adopt through the same warm programs as the monolithic hop.  The
    drive also proves both policies fired (≥1 migration, ≥1 streamed
    chunk), so 'zero compiles' can never mean 'nothing happened'."""
    from apex_tpu.analysis import CompileMonitor

    dec = canonical.get("paged_k8").meta["decoder"]
    _drive_fleet_scale_workload(dec)  # warm migration + streaming
    with CompileMonitor() as mon:
        flat, disagg = _drive_fleet_scale_workload(dec)
    errs = []
    if mon.compiles:
        errs.append(
            f"warm rebalance/streaming fleet traffic compiled "
            f"{mon.compiles} new program(s) — page migration or chunk "
            "adoption respecialized instead of reusing the warm "
            "transfer executors"
        )
    if not flat["rebalances"]:
        errs.append(
            f"the proactive rebalancer never migrated a prefix on the "
            f"heated flat fleet: {flat}"
        )
    if not disagg["handoff_chunks"] or disagg["handoff_chunk_aborts"]:
        errs.append(
            "streaming handoff shipped no clean chunks: "
            f"chunks={disagg['handoff_chunks']} "
            f"aborts={disagg['handoff_chunk_aborts']}"
        )
    return errs


def _drive_promotion_workload(dec):
    """ISSUE 18's deployment plane over one decoder: a 2-host fleet
    mid-traffic rolls through TWO promotions at the served geometry —
    (1) an identical-weights flip (same digest: KV pages and in-flight
    requests survive untouched) and (2) a changed-weights swap (new
    digest: the host's in-flight requests recompute as
    prompt+generated through the warm prefill buckets), then a swap
    back to the original bundle.  Deterministic; returns the final
    per-host digests plus the swap summaries so the check can prove
    the swaps actually happened (and that 'zero compiles' never means
    'nothing promoted')."""
    from apex_tpu.checkpoint import state_digest
    from apex_tpu.deploy import WeightBundle, current_bundle
    from apex_tpu.fleet import FleetHost, FleetRouter
    from apex_tpu.obs import MetricsRegistry

    rng = np.random.RandomState(7)
    pool = [int(t) for t in rng.randint(0, 1000, size=(48,))]
    kw = dict(slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, paged=True,
              page_len=PAGED_PAGE_LEN, prefill_chunk=16)
    hosts = [FleetHost(i, dec, **kw) for i in range(2)]
    router = FleetRouter(hosts, registry=MetricsRegistry())
    for lo, hi in ((0, 5), (3, 14), (7, 15), (2, 18)):
        router.submit(pool[lo:hi], max_new_tokens=40, temperature=0.0)
    for _ in range(3):
        router.step()
    # -- leg 1: identical-digest flip, mid-stream, zero drain --------
    same = current_bundle(hosts[0].engine.decoder)
    flips = [router.roll_host(h.host_id,
                              lambda hh: hh.swap_weights(same),
                              drain_rounds=0)["result"]
             for h in hosts]
    router.step()
    # -- leg 2: changed weights force the recompute fallback ---------
    prev = current_bundle(hosts[0].engine.decoder)
    bumped = jax.tree_util.tree_map(
        lambda x: (x * (1.0 + 2.0 ** -12)).astype(x.dtype), dec.params
    )
    changed = WeightBundle(params=bumped, digest=state_digest(bumped),
                           step=1)
    swaps = [router.roll_host(h.host_id,
                              lambda hh: hh.swap_weights(changed),
                              drain_rounds=0)["result"]
             for h in hosts]
    for _ in range(2):
        router.step()
    # -- swap back (the rollback direction) and drain ----------------
    for h in hosts:
        router.roll_host(h.host_id,
                         lambda hh: hh.swap_weights(prev),
                         drain_rounds=0)
    router.run()
    digests = [h.weights_digest for h in hosts]
    return digests, flips, swaps


def check_promotion_zero_compile(canonical: CanonicalPrograms) -> List[str]:
    """Live promotion may not respecialize (ISSUE 18): rolling a warm
    2-host fleet through identical-weights AND changed-weights swaps
    at the served geometry — mid-traffic, with the changed swap
    recomputing in-flight requests — must add ZERO backend compiles.
    The swapped decoder is a shallow clone sharing the compiled
    ``_programs`` dict, params ride the programs as replicated call
    arguments (same avals, same shardings), and the recompute fallback
    re-prefills through already-compiled chunk buckets."""
    from apex_tpu.analysis import CompileMonitor

    dec = canonical.get("paged_k8").meta["decoder"]
    _drive_promotion_workload(dec)  # warm traffic + both swap paths
    with CompileMonitor() as mon:
        digests, flips, swaps = _drive_promotion_workload(dec)
    errs = []
    if mon.compiles:
        errs.append(
            f"warm identical-geometry promotion compiled "
            f"{mon.compiles} new program(s) — the weight swap (or the "
            "changed-weights recompute) respecialized instead of "
            "riding the shared warm decoder programs"
        )
    if len(set(digests)) != 1:
        errs.append(
            f"fleet left digest-divergent after the rollout: {digests}"
        )
    if not all(f["identical"] and not f["recomputed"] for f in flips):
        errs.append(
            f"identical-digest flip disturbed in-flight work: {flips}"
        )
    if not any(s["recomputed"] for s in swaps):
        errs.append(
            "changed-weights swap never exercised the recompute "
            f"fallback (no request was in flight): {swaps}"
        )
    return errs


def _drive_slo_workload(dec):
    """The paged mixed workload with the ISSUE 10 SLO machinery LIVE:
    a tracker with tight objectives (so windows record real
    observations), SLO-aware admission on, and a priority-classed
    queue.  Deterministic traffic; returns the tracker so the check
    can prove windows actually recorded."""
    from apex_tpu.obs import SloObjective, SloTracker
    from apex_tpu.serve import ServeEngine

    tracker = SloTracker([
        SloObjective("ttft_ms", 0.99, 5.0, 200.0),
        SloObjective("itl_ms", 0.99, 1.0, 200.0),
    ])
    rng = np.random.RandomState(7)
    pool = [int(t) for t in rng.randint(0, 1000, size=(32,))]
    long_p, short_p = pool[:19], pool[19:24]
    eng = ServeEngine(
        dec, slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, paged=True,
        page_len=PAGED_PAGE_LEN, prefill_chunk=16,
        slo_tracker=tracker, slo_admission=True,
    )
    eng.submit(long_p, max_new_tokens=10, priority=0)
    eng.submit(short_p, max_new_tokens=6, priority=2)
    for _ in range(3):
        eng.step()
    eng.submit(list(long_p), max_new_tokens=6, priority=1)
    eng.run()
    return tracker


def check_slo_overhead(canonical: CanonicalPrograms) -> List[str]:
    """The live SLO engine may observe the warm paths but not perturb
    them (ISSUE 10): a warm traffic pass with the tracker live and
    SLO-aware admission ON must (a) record sliding-window observations
    and (b) add ZERO backend compiles — burn alerts, priority
    admission and prefill-yield are pure host-side ordering over the
    same compiled programs.  Skipped (clean) under ``APEX_TPU_OBS=0``
    — the kill switch makes the tracker inert by design."""
    from apex_tpu import obs
    from apex_tpu.analysis import CompileMonitor

    if not obs.enabled():
        return []
    dec = canonical.get("paged_k8").meta["decoder"]
    _drive_slo_workload(dec)  # warm every program the SLO run needs
    with CompileMonitor() as mon:
        tracker = _drive_slo_workload(dec)
    errs = []
    if mon.compiles:
        errs.append(
            f"warm SLO-tracked traffic compiled {mon.compiles} new "
            "program(s) — the SLO engine must be host-side ordering "
            "only, never a recompile"
        )
    if not tracker.observations:
        errs.append(
            "the live SLO tracker recorded no windowed observations "
            "over the traffic pass — the lifecycle tee is dead"
        )
    return errs


def check_obs_instrumentation(canonical: CanonicalPrograms) -> List[str]:
    """Telemetry must observe the warm paths without perturbing them:
    drive the (already-warmed) paged mixed workload once more with
    instrumentation live and require BOTH that the ambient tracer
    recorded engine spans and that zero backend compiles happened —
    i.e. the instrumented canonical engine programs stay compile-free
    warm.  Skipped (clean) when ``APEX_TPU_OBS=0``: the kill switch
    must not fail the sweep."""
    from apex_tpu import obs
    from apex_tpu.analysis import CompileMonitor

    if not obs.enabled():
        return []
    dec = canonical.get("paged_k8").meta["decoder"]
    tracer = obs.default_tracer()
    n0 = tracer.recorded
    with CompileMonitor() as mon:
        _drive_paged_workload(dec)
    errs = []
    if mon.compiles:
        errs.append(
            f"instrumented warm paged traffic compiled {mon.compiles} "
            "new program(s) — telemetry must never touch the compiled "
            "programs (host-side spans only)"
        )
    if tracer.recorded <= n0:
        errs.append(
            "obs instrumentation recorded no spans over the paged "
            "workload — the engine's tracer hookup is dead"
        )
    return errs


# ISSUE 13: the rules-census pins — ONE table (sharding.DEFAULT_RULES)
# matched over the GPT + BERT + RN50 tiny param trees per canonical
# mesh shape, pinned as {spec_string: leaf_count}.  A changed rule, a
# renamed module or a new param family moves a count (or trips the
# unmatched-leaf error) and fails the sweep.  Axes a mesh lacks fall
# away, which is why the same table pins three different censuses.
SHARDING_MESH_SHAPES = (
    ("dp4", {"dp": 4}),
    ("dp2_tp2", {"dp": 2, "tp": 2}),
    ("dp2_fsdp2", {"dp": 2, "fsdp": 2}),
)
SHARDING_CENSUS_PINS: Dict[str, Dict[str, Dict[str, int]]] = {
    "dp4": {
        "gpt": {"PartitionSpec()": 28},
        "bert": {"PartitionSpec()": 33},
        "rn50": {"PartitionSpec()": 29},
    },
    "dp2_tp2": {
        "gpt": {"PartitionSpec()": 14, "PartitionSpec('model',)": 8,
                "PartitionSpec(None, 'model')": 6},
        "bert": {"PartitionSpec()": 19, "PartitionSpec('model',)": 8,
                 "PartitionSpec(None, 'model')": 6},
        "rn50": {"PartitionSpec()": 20,
                 "PartitionSpec(None, None, None, 'model')": 9},
    },
    "dp2_fsdp2": {
        "gpt": {"PartitionSpec()": 18, "PartitionSpec('fsdp',)": 6,
                "PartitionSpec(None, 'fsdp')": 4},
        "bert": {"PartitionSpec()": 23, "PartitionSpec('fsdp',)": 6,
                 "PartitionSpec(None, 'fsdp')": 4},
        "rn50": {"PartitionSpec()": 20,
                 "PartitionSpec(None, None, 'fsdp')": 9},
    },
}


def _sharding_model_trees() -> Dict[str, Any]:
    """Tiny GPT + BERT + RN50 param trees — the zoo the one-table
    contract is pinned over."""
    from apex_tpu.models.bert import BertConfig, BertForMLM
    from apex_tpu.models.gpt import GPTConfig, GPTLM
    from apex_tpu.models.resnet import ResNet

    key = jax.random.PRNGKey(0)
    ids = jnp.zeros((1, 8), jnp.int32)
    gpt = GPTLM(GPTConfig.tiny(compute_dtype=jnp.float32)).init(
        key, ids
    )["params"]
    bert = BertForMLM(BertConfig(
        vocab_size=512, hidden_size=64, num_layers=2, num_heads=2,
        max_position=64, compute_dtype=jnp.float32,
    )).init(key, ids)["params"]
    rn50 = ResNet(stage_sizes=(1, 1), num_classes=10, width=16).init(
        key, jnp.zeros((1, 32, 32, 3), jnp.float32), train=False
    )["params"]
    return {"gpt": gpt, "bert": bert, "rn50": rn50}


# ISSUE 14: the compile cost of an elastic gang resize, pinned.  The
# new-geometry window legitimately compiles (new mesh = new program +
# the driver's carry-placement/metric-fetch programs — 3 on this
# toolchain); the SECOND window at the new world must add ZERO, or the
# reform would recompile every window and the elastic story's
# recovery-latency claim is fiction.
EXPECTED_RESIZE_COMPILES = 3


def check_elastic_resize(canonical: CanonicalPrograms) -> List[str]:
    """The ISSUE 14 canonical check: shrink a warm world-4 dp train
    gang to world 2 the way the elastic relaunch path does — gather
    the carry to its canonical host form, re-place it under the SAME
    rules table projected onto the new mesh, rebuild the driver — and
    pin the compile bill: the first post-resize window adds exactly
    :data:`EXPECTED_RESIZE_COMPILES` (the new geometry's programs,
    placement itself compiles nothing), the second adds ZERO."""
    from apex_tpu import sharding as shd
    from apex_tpu.parallel import replicate
    from apex_tpu.train import FusedTrainDriver, amp_microbatch_step

    amp_, opt, ddp, grad_fn, p, xs, ys = amp_problem()
    mesh4, mesh2 = shd.train_mesh(4), shd.train_mesh(2)
    step = amp_microbatch_step(grad_fn, opt, ddp=ddp, microbatches=1)
    table = shd.train_state_rules()
    d4 = FusedTrainDriver(step, steps_per_dispatch=2, mesh=mesh4,
                          check_vma=False)
    carry = (replicate(p, mesh4), replicate(opt.init(p), mesh4))
    carry, _ = d4.run_window(carry, (xs[:2], ys[:2]))  # the old world
    canon = shd.gather_tree(carry, to_host=True)
    with CompileMonitor() as placed:
        carry2 = shd.shard_tree(canon, table.match(canon, mesh=mesh2),
                                mesh2)
    d2 = FusedTrainDriver(step, steps_per_dispatch=2, mesh=mesh2,
                          check_vma=False)
    with CompileMonitor() as first:
        carry2, _ = d2.run_window(carry2, (xs[2:4], ys[2:4]))
    with CompileMonitor() as second:
        d2.run_window(carry2, (xs[4:6], ys[4:6]))
    errs: List[str] = []
    if placed.compiles:
        errs.append(
            f"elastic_resize: canonical re-placement compiled "
            f"{placed.compiles} program(s) — shard_tree placement must "
            "be pure device_put, never a compile"
        )
    if first.compiles != EXPECTED_RESIZE_COMPILES:
        errs.append(
            f"elastic_resize: first post-resize window compiled "
            f"{first.compiles} program(s), expected exactly "
            f"{EXPECTED_RESIZE_COMPILES} (the new geometry's bill) — "
            "re-pin DELIBERATELY if the driver's program set changed"
        )
    if second.compiles:
        errs.append(
            f"elastic_resize: SECOND post-resize window compiled "
            f"{second.compiles} program(s) — the reformed gang must "
            "redispatch warm (compile-once-run-many survives a resize)"
        )
    return errs


def check_gang_telemetry(canonical: CanonicalPrograms) -> List[str]:
    """The ISSUE 15 canonical check: gang telemetry and the live fleet
    scrape are host-side reads — a WARM gang window recorded into a
    :class:`~apex_tpu.obs.gangview.GangTelemetry` row (driver dispatch
    + world-1 DCN exchange + the K-boundary row write) and a warm
    fleet pass scraped every round by a
    :class:`~apex_tpu.obs.aggregate.FleetAggregator` (merged
    host/role-labeled OpenMetrics rewrite included) must add ZERO
    backend compiles, while provably recording rows, scrapes and a
    non-empty merged gang view.  Skipped (clean) when
    ``APEX_TPU_OBS=0``."""
    import shutil
    import tempfile

    from apex_tpu import obs
    from apex_tpu.fleet import FleetHost, FleetRouter
    from apex_tpu.fleet.train import DcnExchange
    from apex_tpu.train import FusedTrainDriver

    if not obs.enabled():
        return []
    errs: List[str] = []
    tmp = tempfile.mkdtemp(prefix="apex_gang_telemetry_")
    try:
        # -- train half: a warm gang window with telemetry live -------
        rng = np.random.RandomState(0)
        x = jnp.asarray(rng.randn(16, 32).astype(np.float32))
        y = jnp.asarray(rng.randn(16, 8).astype(np.float32))
        w0 = jnp.asarray(rng.randn(32, 8).astype(np.float32) * 0.1)

        def step(w, _):
            loss, g = jax.value_and_grad(
                lambda w: jnp.mean(jnp.square(x @ w - y))
            )(w)
            return w - 0.05 * g, {"loss": loss}

        driver = FusedTrainDriver(step, steps_per_dispatch=4,
                                  metrics={"loss": "last"})
        carry, _ = driver.run_window(w0)  # the cold compile, outside
        exch = DcnExchange(os.path.join(tmp, "exchange"), 0, 1,
                           timeout_s=10.0)
        gv = obs.GangTelemetry.for_exchange(exch)
        with CompileMonitor() as mon:
            carry, res = driver.run_window(carry)
            host_mean = exch.mean_tree("w1", {"w": carry})
            gv.record_window(
                1, k=4, compiles=driver.last_dispatch_compiles,
                meters={}, dispatch_ms=driver.last_dispatch_ms,
                exchange=exch.last_timing,
            )
        del host_mean, res
        if mon.compiles:
            errs.append(
                f"gang_telemetry: warm gang window with telemetry "
                f"live compiled {mon.compiles} new program(s) — the "
                "K-boundary row write must be a pure host-side append"
            )
        if driver.last_dispatch_compiles:
            errs.append(
                "gang_telemetry: the warm window's own dispatch "
                f"attributed {driver.last_dispatch_compiles} "
                "compile(s) — the telemetry row would report a warm "
                "window as cold"
            )
        view = obs.merge_gang_view(os.path.join(tmp, "exchange"))
        if not gv.rows or not view["timeline"]:
            errs.append(
                "gang_telemetry: the gang window recorded no "
                "mergeable telemetry rows — the writer is dead"
            )
        # -- fleet half: warm traffic under a live every-round scrape -
        dec = canonical.get("paged_k8").meta["decoder"]
        rng = np.random.RandomState(7)
        pool = [int(t) for t in rng.randint(0, 1000, size=(32,))]
        kw = dict(slots=PAGED_SLOTS, max_len=PAGED_MAX_LEN, paged=True,
                  page_len=PAGED_PAGE_LEN, prefill_chunk=16)

        def drive(aggregator=None):
            hosts = [FleetHost(i, dec, **kw) for i in range(2)]
            router = FleetRouter(
                hosts, registry=obs.MetricsRegistry(),
                preflight=False, aggregator=aggregator,
                scrape_every=1,
            )
            router.submit(pool[:19], max_new_tokens=8)
            router.submit(pool[19:24], max_new_tokens=6)
            router.run()
            return router

        drive()  # warm every program this traffic touches
        agg = obs.FleetAggregator(
            window_ms=60_000.0,
            out_path=os.path.join(tmp, "fleet.om.txt"),
        )
        with CompileMonitor() as mon:
            drive(aggregator=agg)
        if mon.compiles:
            errs.append(
                f"gang_telemetry: warm fleet traffic under a live "
                f"every-round scrape compiled {mon.compiles} new "
                "program(s) — aggregation must be registry reads only"
            )
        if not agg.scrapes:
            errs.append(
                "gang_telemetry: the router never scraped the live "
                "aggregator — the scrape_every wiring is dead"
            )
        if not os.path.exists(os.path.join(tmp, "fleet.om.txt")):
            errs.append(
                "gang_telemetry: no merged OpenMetrics file written "
                "by the live scrape"
            )
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return errs


def check_sharding_rules(canonical: CanonicalPrograms) -> List[str]:
    """The ISSUE 13 canonical check, two halves:

    (1) ONE rules table shards the whole model zoo: DEFAULT_RULES
    matched over GPT + BERT + RN50 param trees on each canonical mesh
    shape must produce the pinned spec census with ZERO unmatched
    leaves (the table is error-mode; an unmatched leaf raises and is
    reported, never silently replicated).

    (2) the fsdp train program holds every sanitizer the other driver
    windows hold — precision lint, full carry donation, the EXACT
    one-reduce_scatter + one-all_gather budget at the padded flat
    size, no host transfers — and redispatches warm with zero
    compiles."""
    from apex_tpu import sharding as shd

    errs: List[str] = []
    trees = _sharding_model_trees()
    for mesh_name, kw in SHARDING_MESH_SHAPES:
        mesh = shd.train_mesh(**kw)
        for model, tree in trees.items():
            try:
                census = shd.DEFAULT_RULES.census(tree, mesh=mesh)
            except shd.UnmatchedLeafError as e:
                errs.append(f"sharding_rules: {model}@{mesh_name}: {e}")
                continue
            pin = SHARDING_CENSUS_PINS[mesh_name][model]
            if census != pin:
                errs.append(
                    f"sharding_rules: {model}@{mesh_name} census "
                    f"moved: {census} != pinned {pin} — a rule or a "
                    "param family changed; re-pin DELIBERATELY"
                )
    prog = canonical.get("train_fsdp_m2")
    errs.extend(lint_program(prog))
    errs.extend(check_warm_redispatch(prog))
    return errs


def check_grad_compress(canonical: CanonicalPrograms) -> List[str]:
    """The ISSUE 16 canonical check over the compressed windows (their
    per-program sanitizers run in the sweep proper; this pins what the
    budgets alone cannot):

    - the wire ratios: the bf16 window's gradient all-reduce moves
      EXACTLY half the fp32 payload and the int8 window's exactly a
      quarter — the bytes-per-boundary claim of the compressed
      exchange, read straight from the lowered programs;
    - the half allow-list is LOAD-BEARING: linting the bf16 window
      without it must trip ``half-psum`` (the deliberate half psum is
      visible to the lint, and the budget's ``half_ok`` + ``bytes``
      pin is the only thing sanctioning it — not a blind spot);
    - compression ``"none"`` is STRUCTURALLY inert: a window built
      with ``compress="none"`` lowers to byte-identical StableHLO as
      the uncompressed twin, so the existing fp32 parity gates stay
      bitwise with the feature merged."""
    from apex_tpu.train import FusedTrainDriver, amp_microbatch_step

    errs: List[str] = []
    bf16 = canonical.get("train_bf16_m2")
    int8 = canonical.get("train_int8_m2")
    for prog, div in ((bf16, 2), (int8, 4)):
        census = collective_summary(prog.lowered_text(), MIN_BYTES)
        got = census.get("all_reduce", {"bytes": 0})["bytes"]
        want = GRAD_BYTES // div
        if got != want:
            errs.append(
                f"grad_compress: {prog.name} moves {got} B of "
                f"all_reduce per boundary, expected {want} "
                f"(fp32 {GRAD_BYTES} B / {div}) — the compressed "
                f"wire format changed; full census: {census}"
            )
    naked = [
        v for v in lint_jaxpr(bf16.jaxpr(), policy=bf16.policy)
        if v.rule == "half-psum"
    ]
    if not naked:
        errs.append(
            "grad_compress: linting the bf16 window WITHOUT the "
            "half_ok allow-list trips nothing — either the half-width "
            "psum vanished or the precision lint went blind to it "
            "(the budget pin must be what sanctions it)"
        )
    # the structural-identity gate: compress="none" == no compress arg
    amp_, opt, ddp, grad_fn, p, xs, ys = amp_problem()
    mesh = _mesh8()
    step = amp_microbatch_step(grad_fn, opt, ddp=ddp, microbatches=2,
                               compress="none")
    driver = FusedTrainDriver(step, steps_per_dispatch=2, mesh=mesh,
                              check_vma=False)
    from apex_tpu.parallel import replicate

    carry = (replicate(p, mesh), replicate(opt.init(p), mesh))
    none_text = driver._program(2, True).lower(
        carry, (xs[:4], ys[:4])
    ).as_text()
    if none_text != canonical.get("train_m2").lowered_text():
        errs.append(
            "grad_compress: compress=\"none\" lowers DIFFERENTLY from "
            "the uncompressed window — the off-switch is no longer "
            "structurally inert, so the fp32 bitwise parity gates are "
            "at risk"
        )
    return errs


#: the pinned apexlint census (ISSUE 19).  ``rules`` and
#: ``suppressions`` are EXACT — adding a rule or a suppression is a
#: deliberate act that re-pins here; ``files`` is a floor, lowered only
#: with the files a PR deletes (193 -> 184 in PR 28); ``violations`` is zero,
#: always — a new violation is fixed or suppressed-with-reason, never
#: ridden.
APEXLINT_PINS: Dict[str, int] = {
    "rules": 10,
    "files": 184,
    "suppressions": 0,
    "violations": 0,
}


def check_apexlint() -> List[str]:
    """The source-side sweep (ISSUE 19): run
    :func:`apex_tpu.analysis.staticcheck.scan_repo` over the tree and
    pin its census against :data:`APEXLINT_PINS`.

    Violations are reported individually (file:line, rule, message) so
    the sweep output is actionable, then the census itself is gated:
    a silently dropped rule, a suppression that appeared without a
    re-pin, or a shrinking file sweep all fail even at zero
    violations."""
    from apex_tpu.analysis import staticcheck

    report = staticcheck.scan_repo()
    errs = [
        f"apexlint {f.path}:{f.line}: [{f.rule}] {f.message}"
        for f in report.findings
    ]
    c = report.census()
    pins = APEXLINT_PINS
    if c["rules"] != pins["rules"]:
        errs.append(
            f"apexlint rule registry drifted: {c['rules']} rules vs "
            f"pinned {pins['rules']} — re-pin APEXLINT_PINS "
            "deliberately"
        )
    if c["files"] < pins["files"]:
        errs.append(
            f"apexlint swept {c['files']} files, below the pinned "
            f"floor {pins['files']} — the sweep lost coverage "
            "(SCAN_ROOTS or the extension filter changed?)"
        )
    if c["suppressions"] != pins["suppressions"]:
        errs.append(
            f"apexlint suppression count {c['suppressions']} != pinned "
            f"{pins['suppressions']} — every '# apexlint: disable' is "
            "a counted liability; re-pin with the reason in the diff"
        )
    if c["violations"] != pins["violations"]:
        errs.append(
            f"apexlint violations {c['violations']} != "
            f"{pins['violations']} — fix or suppress-with-reason"
        )
    return errs


def run(canonical: Optional[CanonicalPrograms] = None,
        names: Sequence[str] = LINT_PROGRAMS) -> Dict[str, List[str]]:
    """All sanitizers over ``names``; ``{program: [violations]}`` with
    extra ``"decode_k_invariance"``/``"paged_k_invariance"`` entries
    when both windows of a family are in the sweep, a
    ``"cost_census"`` pin over every program with a declared
    :data:`COST_PINS` budget, a ``"grad_compress"`` check (ISSUE 16:
    compressed-wire ratio pins, the load-bearing half allow-list, the
    structurally-inert off-switch) when both compressed windows are in
    the sweep, a ``"sharding_rules"`` check (ISSUE 13:
    tri-model rules census pins + the fsdp window's sanitizer pass)
    when the zero program is in the sweep, and the warm-traffic
    recompile sweeps
    (``paged_mixed_traffic``/``obs_instrumentation``/``slo_overhead``/
    ``resilience_retry``/``fleet_failover``/``fleet_affinity``/
    ``flightrec_overhead``/``gang_telemetry``)
    when the paged programs are in, plus the unconditional
    ``"apexlint"`` source sweep (ISSUE 19: the AST rule registry over
    the whole tree with its pinned census).  Pass an existing registry
    to reuse its cached lowerings (the tier-1 test passes the session
    fixture)."""
    canonical = canonical or CanonicalPrograms()
    report: Dict[str, List[str]] = {}
    for name in names:
        prog = canonical.get(name)
        report[name] = lint_program(prog) + check_warm_redispatch(prog)
    for fam in ("decode", "paged"):
        k1, k8 = f"{fam}_k1", f"{fam}_k8"
        if k1 in names and k8 in names:
            c1 = collective_summary(canonical.get(k1).lowered_text())
            c8 = collective_summary(canonical.get(k8).lowered_text())
            report[f"{fam}_k_invariance"] = [] if c1 == c8 else [
                f"{fam} collective census varies with K: K=1 {c1} vs "
                f"K=8 {c8} — a per-token collective leaked out of the "
                "scan body"
            ]
    report["cost_census"] = check_cost_census(canonical, names)
    if "train_bf16_m2" in names and "train_int8_m2" in names:
        report["grad_compress"] = check_grad_compress(canonical)
    if "train_zero_m2" in names:
        report["sharding_rules"] = check_sharding_rules(canonical)
    if "train_m1" in names:
        report["elastic_resize"] = check_elastic_resize(canonical)
    if "paged_k8" in names:
        report["paged_mixed_traffic"] = check_paged_mixed_traffic(
            canonical
        )
        report["obs_instrumentation"] = check_obs_instrumentation(
            canonical
        )
        report["slo_overhead"] = check_slo_overhead(canonical)
        report["resilience_retry"] = check_resilience_retry(canonical)
        report["fleet_failover"] = check_fleet_failover(canonical)
        report["fleet_affinity"] = check_fleet_affinity(canonical)
        report["fleet_scale"] = check_fleet_scale(canonical)
        report["promotion_zero_compile"] = check_promotion_zero_compile(
            canonical
        )
        report["flightrec_overhead"] = check_flightrec_overhead(
            canonical
        )
        report["gang_telemetry"] = check_gang_telemetry(canonical)
    report["apexlint"] = check_apexlint()
    return report


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(
        description="Graph-sanitizer sweep over the canonical programs"
    )
    ap.add_argument("--only", choices=sorted(_BUILDERS), default=None,
                    help="lint a single program instead of the sweep")
    ap.add_argument("--census-out", metavar="FILE", default=None,
                    help="also write the compiled-cost census as JSON "
                         "('-' = stdout) — the re-pin and trace_report "
                         "--census input")
    args = ap.parse_args(argv)
    names = (args.only,) if args.only else LINT_PROGRAMS
    t0 = time.time()
    canonical = CanonicalPrograms()
    report = run(canonical, names=names)
    if args.census_out:
        import json

        census = collect_census(canonical, names)
        text = json.dumps(census, indent=1, sort_keys=True)
        if args.census_out == "-":
            print(text)
        else:
            with open(args.census_out, "w") as f:
                f.write(text)
            print(f"# census -> {args.census_out}")
    violations = 0
    for name in sorted(report):
        errs = report[name]
        violations += len(errs)
        status = "ok" if not errs else f"{len(errs)} VIOLATION(S)"
        print(f"{name:24s} {status}")
        for e in errs:
            print(f"    {e}")
    print(f"# {len(report)} checks, {violations} violation(s), "
          f"{time.time() - t0:.1f}s")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
