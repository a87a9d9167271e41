"""apex_tpu — a TPU-native mixed-precision + distributed-training framework.

A from-scratch JAX/XLA/Pallas re-design of the capability surface of NVIDIA
Apex (reference: /root/reference, see SURVEY.md):

- :mod:`apex_tpu.amp` — automatic mixed precision: O0-O3 precision policies,
  dynamic loss scaling carried as device state inside jit (no host syncs),
  checkpointable scaler state.  (ref: apex/amp/)
- :mod:`apex_tpu.optimizers` — fused optimizers (Adam/AdamW, SGD, LAMB,
  NovoGrad, Adagrad) as pure optax-style transforms whose whole update is one
  traced, XLA-fused region; plus the LARC wrapper.  (ref: apex/optimizers/)
- :mod:`apex_tpu.parallel` — data parallelism over a named device mesh
  (psum over ICI replaces NCCL bucketed allreduce), SyncBatchNorm with
  cross-replica Welford stats, process-subgroup helpers, and ring
  attention (exact sequence/context parallelism over a mesh axis via
  ppermute — long-context capability beyond the single-device reference).
  (ref: apex/parallel/)
- :mod:`apex_tpu.ops` — the Pallas kernel library (LayerNorm, softmax
  cross-entropy, fused attention, fused MLP, multi-tensor primitives), each
  with a pure-jnp reference implementation and parity harness.  (ref: csrc/)
- :mod:`apex_tpu.contrib` — ZeRO-style sharded optimizers, fused multihead
  attention modules, group batchnorm, 2:4 structured sparsity.
  (ref: apex/contrib/)
- :mod:`apex_tpu.normalization`, :mod:`apex_tpu.mlp` — fused layer modules.
- :mod:`apex_tpu.bf16_utils` — manual master-weight mixed precision helpers
  (ref: apex/fp16_utils/ — bf16 is the TPU half type).
- :mod:`apex_tpu.reparameterization` — weight-norm reparameterization.
- :mod:`apex_tpu.RNN` — recurrent stacks built on lax.scan.
- :mod:`apex_tpu.pyprof` — profiling: named-scope annotation + compiled cost
  analysis. (ref: apex/pyprof/)
- :mod:`apex_tpu.train` — the fused multi-step training driver: K
  optimizer steps per donated ``lax.scan`` dispatch with on-device metric
  meters read once per window (the dispatch-overhead layer every bench
  and example runs on; beyond-reference, MegaScale-style overlap), plus
  gradient-accumulation microbatching (``train.accum``): M microbatches
  per step, fp32/bf16-compensated on-device accumulation, ALL collectives
  deferred to one psum (or reduce_scatter/all_gather with the first-class
  ``zero`` sharded-optimizer mode) per boundary.
- :mod:`apex_tpu.remat` — named rematerialization policies
  (``none | dots_saveable | full_block``) threaded through the model zoo
  and ``ops.mlp`` — the activation-memory knob that converts freed HBM
  into larger microbatches; the block-recomputing policies keep what a
  kernel declares dear to make again (the flash forward's output and lse).
- :mod:`apex_tpu.analysis` — the graph sanitizer suite: hardware-free
  static proofs of the framework's invariants on traced/lowered
  programs — precision lint against the active amp policy, donation
  checking on compiled input-output aliasing (+ use-after-donate
  guard), declarative collective budgets, recompile/host-transfer
  detection, and the compiled-program cost census
  (``analysis.costs``: per-program FLOPs/bytes/peak-HBM pinned per
  canonical program, capability-guarded, with a roofline estimator).
  ``tools/lint_graphs.py`` gates the canonical programs.
- :mod:`apex_tpu.obs` — the runtime telemetry layer: deterministic
  metrics registry (counters/gauges/exact-quantile histograms),
  host-side monotonic span tracer with compile-vs-execute attribution
  (bridged from the analysis suite's CompileMonitor), per-request
  TTFT/ITL/queue-delay lifecycle histograms, and JSONL +
  Chrome/Perfetto trace exporters (``tools/trace_report.py`` renders
  them), plus the flight recorder (``obs.flightrec``: an always-on
  bounded ring of boundary events dumped as a byte-replayable
  ``flightrec.jsonl`` postmortem on resilience recoveries;
  ``APEX_TPU_FLIGHTREC=0`` kill switch).  Instruments the train
  driver and serve engine; host-side only (zero recompile risk),
  ``APEX_TPU_OBS=0`` kill switch.
- :mod:`apex_tpu.resilience` — fault injection + self-healing recovery:
  deterministic seeded :class:`FaultPlan` chaos schedules over the host
  dispatch boundaries (dispatch errors, simulated preemption/engine
  crash, NaN meter bursts, loader stalls, stragglers, page-pool
  pressure), a :class:`ResilientTrainDriver` (watchdog, bounded retry
  with backoff, non-finite sentry rolling back to the last good
  checkpoint bitwise) and a :class:`ResilientServeEngine` (per-request
  deadlines, decode-boundary retry, admission backpressure, engine
  crash-recovery replaying in-flight requests token-exact under
  greedy).  ``APEX_TPU_RESILIENCE=0`` kill switch.
- :mod:`apex_tpu.fleet` — multi-host fault-tolerant scale-out: a
  health-checked :class:`FleetRouter` over per-host serve replicas
  (heartbeat eviction, host-loss recovery token-exact on survivors,
  straggler detection, preflight-gated readmission), host-scoped
  seeded chaos (``host_loss``/``host_stall``/``heartbeat_drop``/
  ``restart``), and train gang scale-out over ``jax.distributed``
  (gang launcher with bounded restarts, deterministic DCN-bridge
  exchange fallback, coordinated K-boundary checkpoints — a
  killed-and-restarted gang resumes bitwise).
- :mod:`apex_tpu.sharding` — the declarative partition-rule engine:
  ordered regex rules over named pytree paths produce
  ``PartitionSpec``/``NamedSharding`` trees for params, optimizer
  state, driver carries and KV caches alike
  (``match_partition_rules``/``make_shard_and_gather_fns``; validated
  :class:`~apex_tpu.sharding.RulesTable` with an unmatched-leaf error
  mode), mesh-aware so ONE table serves dp / dp×tp / dp×fsdp shapes.
  Drives the ZeRO and fsdp driver carry specs, the serve cache
  pspecs, fleet gang wiring and the checkpoint reshard-on-restore
  record (``APEX_TPU_SHARDING_RULES=0`` kill switch to the legacy
  hand-threaded literals).  Unlocks the ``fsdp`` reduction policy
  (``train.accum.fsdp_microbatch_step``: params dp-sharded at rest,
  one all_gather + one reduce_scatter per boundary).
- :mod:`apex_tpu.checkpoint` — orbax train-state save/restore with bitwise
  resume (ref: the amp state_dict + torch.save workflow); saves are
  crash-safe (checksum sidecar committed via tmp + ``os.replace``,
  verified on restore, previous last-good retained), and record their
  sharding-rules outcome for cross-mesh resharded restores.
- :mod:`apex_tpu.data` — native C++ threaded data loader + device
  prefetcher (ref role: DALI / torch DataLoader workers).
- :mod:`apex_tpu.chip` — what a program settles before it says it ran
  on the chip: ``require_tpu`` (no TPU, no run) and
  ``compile_cache_dir`` (the persistent compile cache, placed from
  outside); used by ``chip_smoke.py`` and the benchmark's harness.
"""

__version__ = "0.5.0"

from apex_tpu import amp  # noqa: F401
from apex_tpu import multi_tensor  # noqa: F401
from apex_tpu import optimizers  # noqa: F401
from apex_tpu import sharding  # noqa: F401
from apex_tpu import train  # noqa: F401
