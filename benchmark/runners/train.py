"""Runner of ``"kind": "train"`` traffic: one training job through the
program's ``FusedTrainDriver``, K optimizer steps to a dispatch.

Set-up builds ONE driver with its state and drives it through its first
window — the window's own call, ``driver.run_window(carry, batches)``, the K
steps the timed windows run, on seeded rows that all differ — while the plain
reference has followed the same K steps in float32.  That window compiles (or
loads) the one program this cell has; the same driver and state then go to
the measured windows.  What is compared is in :func:`compare`.
"""
from __future__ import annotations

import functools
import statistics
import time
from typing import Dict, List

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from benchmark.harness import seed_key, timing_line
from benchmark.reference import train as ref_train

#: how many of the window's first steps' losses are compared.  Later steps
#: are not: at a full learning rate from seeded weights the two trajectories
#: part (GPT-2 small, K=10: the gap over all ten steps read 4e-5 on four
#: seeds and 1.6e-3 on the fifth, PERF.md section 2)
LOSS_STEPS = 3

def make_batches(key, k: int, rows: int, seq: int, vocab: int,
                 objective: str, mlm_probability: float = 0.15):
    """``(ids, labels)``, each ``(k, rows, seq)`` int32, every row its own
    draw.  ``causal_lm``: the label of a position is the next token, the
    last has none (-100).  ``mlm``: a drawn 15% of positions are predicted
    (a drawn token), the rest are -100."""
    k_ids, k_mask, k_lab = jax.random.split(key, 3)
    ids = jax.random.randint(k_ids, (k, rows, seq), 0, vocab, jnp.int32)
    if objective == "causal_lm":
        labels = jnp.concatenate(
            [ids[..., 1:], jnp.full((k, rows, 1), -100, jnp.int32)], axis=-1)
    elif objective == "mlm":
        mask = jax.random.uniform(k_mask, ids.shape) < mlm_probability
        drawn = jax.random.randint(k_lab, ids.shape, 0, vocab, jnp.int32)
        labels = jnp.where(mask, drawn, -100)
    else:
        raise ValueError(f"no objective {objective!r}")
    return ids, labels


def build_optimizer(job: Dict):
    from apex_tpu.optimizers import fused_adam, fused_lamb

    o = job["optimizer"]
    if o["name"] == "adamw":
        return fused_adam(o["lr"], eps=o["eps"], weight_decay=o["wd"])
    if o["name"] == "lamb":
        return fused_lamb(o["lr"], eps=o["eps"], weight_decay=o["wd"])
    raise ValueError(f"no optimizer {o['name']!r}")


def build_step(model, opt, amp_, ddp=None):
    """The job's one-step function, as a user of the library writes it:
    scaled loss, gradients of the master weights, (across chips) the
    allreduce, the AMP-fused optimizer step.  It reports the loss and the
    global gradient norm."""
    def step(carry, batch):
        params, state, key = carry
        ids, labels = batch
        key, dkey = jax.random.split(key)

        def scaled(mp):
            _, loss = model.apply(
                {"params": opt.model_params(mp)}, ids, labels=labels,
                deterministic=False, rngs={"dropout": dkey})
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        if ddp is not None:
            grads = ddp.allreduce(grads)
            loss = jax.lax.pmean(loss, ddp.axis_name)
        params, state, stats = opt.step(grads, state, params)
        return (params, state, key), {"loss": loss,
                                      "grad_norm": stats.grad_norm}
    return step


def build_program(cfg: Dict, job: Dict, fam, opt_level: str, mesh=None):
    """``(driver, init_carry)``: the job's ``FusedTrainDriver`` and a
    function from the benchmark's seeded weights and a key to the driver's
    carry ``(master params, optimizer state, dropout key)``.  ``mesh`` is
    the data-parallel mesh of a job across chips."""
    import apex_tpu.amp as amp
    from apex_tpu.train import FusedTrainDriver

    ddp = None
    if mesh is not None:
        from apex_tpu.parallel import DistributedDataParallel

        ddp = DistributedDataParallel(axis_name="data",
                                      allreduce_always_fp32=True)
    amp_ = amp.initialize(opt_level)
    model = fam.program_model(
        fam.program_config(cfg, amp_.policy.compute_dtype))
    opt = amp.AmpOptimizer(build_optimizer(job), amp_, track_grad_norm=True)

    def init_carry(weights, key):
        params = fam.to_program(weights, cfg)
        if not amp_.policy.master_weights and opt_level != "O0":
            # the control's lower precision: no float32 masters (O3)
            params = amp_.cast_model(params)
        return params, opt.init(params), key

    driver = FusedTrainDriver(
        build_step(model, opt, amp_, ddp),
        steps_per_dispatch=job["steps_per_dispatch"], mesh=mesh,
        check_vma=False, metrics={"loss": "mean", "grad_norm": "mean"},
        per_step=("loss", "grad_norm"))
    return driver, init_carry


def worst_leaf_gap(got: Dict[str, float], ref: Dict[str, float],
                   skip_suffix: str = None):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger (some gradients are all but zero).  The gap
    between norms, not the norm of the difference: Adam's normalised update
    turns a near-zero gradient's sign into a full step."""
    floor = statistics.median(ref.values())
    gaps = {k: abs(got[k] - ref[k]) / max(ref[k], floor) for k in ref
            if not (skip_suffix and k.endswith(skip_suffix))}
    worst = max(gaps, key=gaps.get)
    return gaps[worst], worst


class Job:
    """One cell's job, from its configuration, traffic file and family:
    the seeded rows, the reference's K steps, the program, and the
    program's first window.  ``run`` below and ``tools/control.py`` (many
    seeds and the control in one process) both go through it."""

    def __init__(self, cfg: Dict, job: Dict, fam, chips: int):
        self.cfg, self.job, self.fam, self.chips = cfg, job, fam, chips
        self.k, self.rows, self.seq = (
            job["steps_per_dispatch"], job["rows"], job["seq"])
        self.rcfg = fam.reference_config(cfg)
        self.devices = jax.devices()[:chips]
        self.mesh = batch_sharding = None
        if job.get("data_parallel"):
            from apex_tpu.parallel.mesh import data_parallel_mesh

            self.mesh = data_parallel_mesh(chips)
            batch_sharding = NamedSharding(self.mesh, P(None, "data"))
        ref, rcfg = fam.reference, self.rcfg
        self.make_weights = jax.jit(lambda key: ref.init_params(key, rcfg))
        self.batch_fn = jax.jit(
            lambda key: make_batches(
                key, self.k, self.rows, self.seq, cfg["vocab_size"],
                job["objective"], cfg["assumed"].get("mlm_probability", 0.15)),
            out_shardings=batch_sharding)
        self.delta_norms = jax.jit(lambda p, key: {
            name: jnp.linalg.norm((x.astype(jnp.float32) - w0).ravel())
            for (name, x), (_, w0) in zip(
                sorted(fam.views(fam.from_program(p, cfg)).items()),
                sorted(fam.views(ref.init_params(key, rcfg)).items()))})

    @staticmethod
    @functools.lru_cache(maxsize=None)
    def keys(seed: int):
        """(weights, batches, dropout) keys of a seed — made once: between
        two measured windows the host does nothing but fold the window's
        number into the batches' key."""
        return tuple(jax.random.split(seed_key(seed), 3))

    def batches(self, seed: int, n: int):
        """Window ``n``'s rows: ``(ids, labels)``, each (K, rows, seq)."""
        return self.batch_fn(jax.random.fold_in(self.keys(seed)[1], n))

    def reference(self, seed: int) -> Dict:
        """The plain reference through the first window's K steps."""
        w_key = self.keys(seed)[0]
        ids, labels = jax.device_get(self.batches(seed, 0))
        dp = bool(self.job.get("data_parallel"))
        return ref_train.follow(
            self.fam.reference.loss_rows, lambda: self.make_weights(w_key),
            [(ids[i], labels[i]) for i in range(self.k)], self.rcfg,
            optimizer=self.job["optimizer"]["name"],
            hyper={x: self.job["optimizer"][x] for x in ("lr", "wd", "eps")},
            rows_per_block=self.job["reference_rows_per_block"],
            views=self.fam.views, replicas=self.chips if dp else 1,
            devices=self.devices if dp else ())

    def program(self, opt_level: str):
        """``(driver, make_carry)``; ``make_carry(seed)`` is the seeded
        state, made on the device in one program."""
        driver, init_carry = build_program(self.cfg, self.job, self.fam,
                                           opt_level, self.mesh)
        ref, rcfg = self.fam.reference, self.rcfg
        made = jax.jit(lambda kw, kd: init_carry(ref.init_params(kw, rcfg), kd))

        def make_carry(seed: int):
            w_key, _, d_key = self.keys(seed)
            carry = made(w_key, d_key)
            if self.mesh is not None:
                from apex_tpu.parallel import replicate

                carry = replicate(carry, self.mesh)
            return carry
        return driver, make_carry

    def first_window(self, driver, carry, seed: int):
        """The program's first window: ``(carry, what compare() reads)``."""
        carry, res = driver.run_window(carry, self.batches(seed, 0))
        steps = jax.device_get(res.per_step)
        delta = self.delta_norms(carry[0], self.keys(seed)[0])
        return carry, {
            "losses": [float(x) for x in steps["loss"]],
            "first_grad_norm": float(steps["grad_norm"][0]),
            "delta": {k: float(v) for k, v in jax.device_get(delta).items()},
        }


def compare(got: Dict, want: Dict, fam) -> Dict[str, tuple]:
    """``{name: (value, what it is)}``: the numbers a window is held to,
    each against its own limit in the traffic file."""
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(
        got["losses"][:LOSS_STEPS], want["losses"][:LOSS_STEPS]))
    g, w = got["first_grad_norm"], want["first_grad_norm"]
    d_gap, d_leaf = worst_leaf_gap(got["delta"], want["delta"],
                                   fam.ZERO_GRADIENT_SUFFIX)
    k = len(want["losses"])
    return {
        "loss_rel_gap": (
            loss_gap, f"widest |program - reference| / reference over the "
                      f"window's first {LOSS_STEPS} steps"),
        "grad_norm_rel_gap": (
            abs(g - w) / w, f"global norm of the first gradient before any "
                            f"clipping: program {g:.6g}, reference {w:.6g}"),
        "param_delta_leaf_gap": (
            d_gap, f"worst leaf {d_leaf}: norm of the parameters' change "
                   f"over the window's {k} steps"),
    }


def run(run) -> tuple:
    from apex_tpu.analysis import CompileMonitor
    from apex_tpu.train import read_metrics

    cfg, fam = run.cfg, run.family
    job = Job(cfg, run.traffic, fam, run.chips)
    k, rows, seq = job.k, job.rows, job.seq
    limits = run.traffic["limits"]

    # -- the plain reference follows the first window (not set-up) -------
    with run.excluded():
        t0 = time.perf_counter()
        want = job.reference(run.seed)
        run.phase("reference done")
        run.log(f"reference: {k} steps in float32 took "
                f"{time.perf_counter() - t0:.2f} s (not counted in setup_s); "
                f"losses {want['losses']}")

    # -- the program: one driver, one state, one window program ----------
    driver, make_carry = job.program(cfg["precision"]["opt_level"])
    carry = make_carry(run.seed)
    run.phase("program state made")
    with run.spans.span("first_window"):
        carry, got = job.first_window(driver, carry, run.seed)
    run.phase("first window driven (the window program is warm)")
    run.log(f"program losses {got['losses']}")
    for name, (value, what) in compare(got, want, fam).items():
        run.check(name, value, limits[name], what)

    # -- measure ---------------------------------------------------------
    def window(carry, n):
        with run.spans.span("make_batches"):
            batches = job.batches(run.seed, n)
        with run.spans.span("driver.run_window"):
            carry, res = driver.run_window(carry, batches)
        with run.spans.span("fetch_loss"):
            loss = read_metrics(res.metrics)["loss"]
        return carry, loss

    losses: List[float] = []
    window_ms: List[float] = []
    n = 0
    with CompileMonitor() as mon:
        t_open = time.perf_counter()
        t1 = t_open
        while t1 - t_open < run.seconds:
            n += 1
            if n == 2:
                run.tracer.start()      # two windows of steady state
            if n == 4:
                run.tracer.stop()
            t0 = time.perf_counter()
            carry, loss = window(carry, n)
            t1 = time.perf_counter()
            losses.append(loss)
            window_ms.append((t1 - t0) * 1e3)
        run.tracer.stop()
    run.sample_memory(job.devices)
    elapsed = t1 - t_open
    attempted = len(window_ms)
    tokens_per_s = attempted * k * rows * seq / elapsed

    failed = sum(1 for x in losses if not np.isfinite(x))
    run.check("windows_with_nonfinite_loss", failed, 0,
              f"losses of the measured windows: first {losses[0]:.4f}, "
              f"last {losses[-1]:.4f}")
    if job.mesh is not None:
        run.check("replicas_disagreeing", replica_spread(carry[0], job.mesh),
                  0, "parameters after the window, chip against chip")

    timing_line(run, "train window (one dispatch of "
                     f"{k} steps, loss fetched)", window_ms)
    run.log(f"{attempted} windows, {attempted * k * rows * seq} tokens in "
            f"{elapsed:.4f} s = {tokens_per_s:.1f} tokens/s; compiles inside "
            f"the window: {mon.compiles}; setup {run.setup_s(t_open):.2f} s")
    reduced = run.tracer.reduced(run.chips)
    run.record.update(
        window_ms=window_ms, tokens_per_window=k * rows * seq,
        compiles_in_window=mon.compiles, kind="train", chips=run.chips,
        device_kind=run.device["kind"],
        flops_per_token=fam.train_flops_per_token(cfg, seq),
        trace=reduced)
    return attempted, failed, {
        "train_tokens_per_s": tokens_per_s,
        "setup_s": run.setup_s(t_open),
    }


def replica_spread(params, mesh) -> int:
    """How many chips hold parameters that differ from the first chip's
    (0: the replicas agree bit for bit)."""
    def digest(p):
        total = sum(jnp.sum(x.astype(jnp.float32))
                    for x in jax.tree_util.tree_leaves(p))
        sq = sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                 for x in jax.tree_util.tree_leaves(p))
        return jnp.stack([total, sq])[None]

    per_chip = np.asarray(jax.jit(jax.shard_map(
        digest, mesh=mesh, in_specs=P(), out_specs=P("data"),
        check_vma=False))(params))
    return int(np.sum(np.any(per_chip != per_chip[0], axis=-1)))
