#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the program still starts on the chip.

Drives the train→serve main path once, in ONE process, through the entry
points a user calls, at the full width of GPT-2 small (12 layers, d 768,
12 heads, ctx 1024, V 50304; weights random from ``--seed``):

- ``kernels`` — each main-path Pallas kernel (flash attention, fused
  LayerNorm with the dgamma/dbeta epilogue, fused softmax-xentropy),
  COMPILED, forward and gradients, against its own jnp reference at the
  tolerance tiers of the kernels' tests; flash attention's backward at
  the three 8k cells' calls and the 16k cell's two, the shipped route
  against the two-pass one, with the time of a call of each
  (``flash_backward``); and the expert layer's row movement at the 16k
  cell's shape (hidden 2560), kernels against ``jnp.take``, with the time
  of a call of each (``moe_rows_at_2560``); and the grouped products at
  Moonlight's two calls in the expert layer's worst-case buffer and in one
  with no dead tail, forward, ``dx`` and ``dw`` each timed alone
  (``grouped_mm_at_cell``); and the gated short convolution at the LFM2
  cell's call, its two kernels against the ``jax.numpy`` form, forward and
  gradient timed on both paths (``gated_conv_at_cell``); and the state-space
  scan at the Granite cell's call, its two kernels against the token
  recurrence, forward and the six gradients, both paths timed
  (``ssd_at_cell``), the delta rule with a decay a key channel at the Kimi
  Linear cell's call, its two kernels against the token recurrence and the
  scan path, forward and the five gradients, both paths timed
  (``kda_at_cell``), and the convolution in front of the state-space scan,
  reading x, B and C out of ``in_proj``'s output, against the ``jax.numpy``
  form (``ssm_conv_at_cell``), and the gradient of one whole block of that cell
  under ``full_block``, the products of ``gate_up``'s size that its program
  runs counted and timed in a trace (``dense_ffn_at_cell``); and q, k and v
  on their way from the projection's output to the flash kernels at Trinity's
  and SmallThinker's attention calls, the kernel pair against the composed
  norm, rotation and transposition (``qk_heads_at_cell``); and ONE making
  of the expert layer's routing plan at the five sparse cells' shapes, with
  each lookup inside it as the gather it was and as the sum over the held
  experts it can be, timed on the device and the tables held equal to the
  bit (``moe_plan_at_cell``);
- ``train`` — AMP O2 + ``fused_adam`` through ``FusedTrainDriver``, with
  dropout on: three windows on a fixed seeded batch;
- ``serve`` — the params that phase produced, through ``GPTDecoder`` +
  ``ServeEngine`` with the engine's own defaults (paged, bf16 cache, the
  default K): six requests across the prefill buckets, two sharing a
  long prefix, the short ones checked against ``reference_generate``.

``--chips 4`` runs instead — and only — the path across chips and what it
is compared with: ``dp``, the fused driver on a 4-device ``data`` mesh
with the DDP allreduce against the same windows on one device, and
``tp``, ``ServeEngine`` tensor-parallel over 4 chips against TP = 1.

Nothing here hides the device.  The script sets no platform: it asks
``jax.devices()`` and exits nonzero at once unless that is a TPU.  Every
compiled program whose kernels' shape gates promise Mosaic calls is held
to them (``apex_tpu.ops.mosaic_call_count``), so a kernel that ran
interpreted or as its reference fails the run, and so does a Mosaic call
that bears no name from ``KERNEL_NAMES``.  Any failed check or
exception in any phase ends the process nonzero; nothing is caught and
carried on from.  No utilisation is computed: the chip's peak rates are
not this script's to assume.

Output: one JSON line per phase, then as the LAST line exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Needs no network and nothing outside the checkout.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.chip import compile_cache_dir, require_tpu
from apex_tpu.ops import mosaic_call_count
from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

HERE = os.path.dirname(os.path.abspath(__file__))

# A greedy token may differ from the full-recompute reference only where
# the reference itself calls it a tie: its logit within this much of the
# reference's maximum.  bf16 keeps 8 bits — one ulp at the logit scale of
# a barely-trained GPT-2 (|logit| < 8) is 2^-5 — and the cached and the
# recomputed hidden states round differently through 12 layers.
LOGIT_TIE_TOL = 2.0 ** -4


@dataclasses.dataclass(frozen=True)
class Sizes:
    """What the smoke runs at.  :data:`FULL` is the only size the command
    line runs; ``tests/test_chip_smoke.py`` rehearses the control flow on
    the CPU with a tiny one (and its own device check)."""

    model: Dict[str, int]            # overrides of GPTConfig.small()
    ctx: int                         # max_position, and the serve max_len
    kernel_batch: int                # flash (b, heads, ctx, d); b*ctx LN rows
    train_batch: int
    steps_per_dispatch: int
    windows: int
    slots: int
    prompt_lens: Tuple[int, ...]     # spanning the prefill buckets
    prefix_len: int                  # two more requests share this prefix
    prefix_tails: Tuple[int, int]
    new_tokens: int
    compared: int                    # first N requests vs reference_generate
    dp_batch: int                    # --chips 4: the global batch, both sides
    dp_steps_per_dispatch: int
    tp_prompt_lens: Tuple[int, ...]
    tp_new_tokens: int


FULL = Sizes(
    model={}, ctx=1024, kernel_batch=8,
    # batch / steps_per_dispatch as the `gpt2-small.train` cell
    train_batch=16, steps_per_dispatch=10, windows=3,
    slots=8, prompt_lens=(5, 64, 200, 700), prefix_len=256,
    prefix_tails=(17, 40), new_tokens=32, compared=2,
    # fp32 at batch 16 leaves the one-chip side of the comparison under
    # 1 GiB of the chip's 16 (sandbox compile for the described v5e:
    # 13.4 GiB temporaries + 1.4 GiB state), so the comparison runs at 8
    dp_batch=8, dp_steps_per_dispatch=2,
    tp_prompt_lens=(5, 64, 200), tp_new_tokens=16,
)


class SmokeFailure(RuntimeError):
    """A check of a phase did not hold."""


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def _require_mosaic(compiled, at_least: int, record: Dict, key: str) -> None:
    """Record the program's Mosaic call count under ``record[key]`` and
    hold it to what its kernels' shape gates promise — fewer means a
    kernel ran interpreted or was replaced by its reference — and every
    such call to a name of ``KERNEL_NAMES``."""
    record[key] = n = mosaic_call_count(compiled)
    _require(n >= at_least,
             f"{key}: {n} tpu_custom_call(s) compiled in, expected >= "
             f"{at_least}")
    nameless = unnamed_mosaic_calls(compiled.as_text())
    _require(not nameless,
             f"{key}: tpu_custom_call(s) {nameless} bear no name from "
             "apex_tpu.ops._common.KERNEL_NAMES — a device trace could not "
             "tell them from the scope that called them")


# ---------------------------------------------------------------------------
# metering: wall, compile events, persistent-cache hits — one JSON line/phase
# ---------------------------------------------------------------------------

class _Meter:
    """Counts what JAX's monitoring reports while a phase runs: backend
    compiles (``analysis.CompileMonitor`` — a persistent-cache hit still
    fires the event, with the retrieval time) and the persistent cache's
    hits and misses."""

    _HIT = "/jax/compilation_cache/cache_hits"
    _MISS = "/jax/compilation_cache/cache_misses"

    def __init__(self, cache_dir: str):
        self.cache_dir = cache_dir
        self.cache_from_env = bool(os.environ.get("JAX_COMPILATION_CACHE_DIR"))
        self.cache_entries_at_start = (
            len(sorted(os.listdir(cache_dir)))
            if os.path.isdir(cache_dir) else 0
        )
        self.hits = self.misses = 0
        self.active = True
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, name: str, **_):
        if self.active:
            self.hits += name == self._HIT
            self.misses += name == self._MISS

    def phase(self, name: str, fn: Callable[[Dict], None]) -> None:
        """Run one phase — ``fn(facts)`` records what it measured, then
        checks it — and print its line.  Whatever ``fn`` raises ends the
        run: the line then carries ``"failed"`` and the facts recorded
        up to that point, and the exception goes on up."""
        from apex_tpu.analysis import CompileMonitor

        compile_s: List[float] = []
        facts: Dict = {}
        failed = None
        hits0, misses0 = self.hits, self.misses
        t0 = time.perf_counter()
        try:
            with CompileMonitor(on_compile=compile_s.append):
                fn(facts)
        except BaseException as e:
            failed = f"{type(e).__name__}: {e}"[:600]
            raise
        finally:
            line = {
                "phase": name,
                "wall_s": round(time.perf_counter() - t0, 3),
                "compile_s": round(sum(compile_s), 3),
                "compiles": len(compile_s),
                "cache": {
                    "dir": self.cache_dir,
                    "from_env": self.cache_from_env,
                    "warm": self.cache_entries_at_start > 0,
                    "hits": self.hits - hits0,
                    "misses": self.misses - misses0,
                },
            }
            if failed:
                line["failed"] = failed
            line.update(facts)
            print(json.dumps(line), flush=True)


@jax.jit
def _err_stats(got, ref):
    """(max |got - ref|, max |ref|, all finite), in fp32 — one program
    per shape, not one per operator."""
    got32, ref32 = got.astype(jnp.float32), ref.astype(jnp.float32)
    return (jnp.max(jnp.abs(got32 - ref32)), jnp.max(jnp.abs(ref32)),
            jnp.all(jnp.isfinite(got32)))


def _compare(name: str, got, ref, tol: float, out: Dict) -> None:
    """Hold ``got`` to ``ref`` at ``tol`` (scaled by the reference's
    magnitude where that exceeds 1) and record the max error."""
    err, scale, finite = (x.item() for x in _err_stats(got, ref))
    out[name] = {"max_err": err, "ref_max": scale, "tol": tol}
    _require(finite, f"kernel parity {name}: non-finite output")
    _require(err <= tol * max(1.0, scale),
             f"kernel parity {name}: max_err {err:.3e} > tol {tol:.1e} x "
             f"max(1, {scale:.3e})")


def _us_a_call(fn, args, n=10):
    """Microseconds a call of ``fn(*args)``, the first (its compile) apart."""
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return round((time.perf_counter() - t0) / n * 1e6, 1)


def _us_on_device(fn, args, reps=16):
    """Microseconds ``fn(*args)`` takes ON THE DEVICE, for a program shorter
    than the host's dispatch of a call (~0.2 ms): ``reps`` makings in one
    program, each fed the sum of the one before's results times a zero the
    compiler cannot see — none is hoisted, none merged — and the call's time
    divided by ``reps``.  The sum is part of what is timed, on every side."""
    def many(zero, *args):
        def body(_, carry):
            nudge = zero * carry
            fed = jax.tree_util.tree_map(
                lambda a: a ^ (nudge != 0) if a.dtype == jnp.bool_
                else a + nudge.astype(a.dtype), args)
            return sum(jnp.sum(leaf).astype(jnp.int32)
                       for leaf in jax.tree_util.tree_leaves(fn(*fed)))
        return jax.lax.fori_loop(0, reps, body, jnp.int32(0))

    compiled = jax.jit(many).lower(jnp.int32(0), *args).compile()
    return round(_us_a_call(compiled, (jnp.int32(0), *args), n=3) / reps, 1)


# ---------------------------------------------------------------------------
# the state-space scan at the cell's call
# ---------------------------------------------------------------------------

def _token_recurrence_in_blocks(x, dt, A, B, C, D, *, block: int = 64):
    """``ops/ssd.py::ssd_recurrent``'s recurrence — one group of B and C,
    float32, each row from a zero state — walked in blocks of ``block`` tokens
    that are made again in the backward pass.  Differentiated, the plain
    oracle keeps a state a token (17 GB at the cell's call, more than the chip
    has); this keeps one a block (268 MB) and one block's own.  The same
    sums in the same order: ``ssd_at_cell`` holds its forward to the oracle's
    at the cell's call, ``tests/test_chip_smoke.py`` its gradients at a
    small one."""
    f32 = jnp.float32
    b, s, h, p = x.shape
    x, dt, B, C = (t.astype(f32) for t in (x, dt, B, C))
    A, D = A.astype(f32), D.astype(f32)

    def token(state, inp):
        x_t, dt_t, b_t, c_t = inp                 # (b, H, P) (b, H) (b, 1, N) x 2
        state = (state * jnp.exp(dt_t * A)[..., None, None]
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, :])
        o_t = jnp.einsum("bhpn,bn->bhp", state, c_t[:, 0],
                         precision=jax.lax.Precision.HIGHEST)
        return state, o_t + D[None, :, None] * x_t

    walk = jax.checkpoint(lambda state, inp: jax.lax.scan(token, state, inp))
    blocks = lambda t: jnp.moveaxis(t, 1, 0).reshape(
        (s // block, block, b) + t.shape[2:])
    _, o = jax.lax.scan(walk, jnp.zeros((b, h, p, B.shape[3]), f32),
                        tuple(map(blocks, (x, dt, B, C))))
    return jnp.moveaxis(o.reshape(s, b, h, p), 0, 1)


def ssd_at_cell(s: int, root_key, parity: Dict, calls: Dict) -> Dict:
    """``ops/ssd.py::ssd_scan`` at ``granite-h.train-8k``'s call — a row of 8
    contexts, ``s / 16`` heads of 64 channels in bfloat16 (two heads a lane
    tile), a 64 x 128 float32 state a head, B and C of one group, chunks of
    256, ``dt`` and ``A`` as the cell's seeded weights give them (a step size
    log-uniform in [1e-3, 1e-1], A in -[1, 16]): the two kernels against the
    TOKEN RECURRENCE in float32 on the same bfloat16 values, forward and all
    six gradients.  The forward is held to ``ssd_recurrent`` itself; the
    gradients to the same recurrence walked in recomputed blocks
    (:func:`_token_recurrence_in_blocks` — the oracle's own gradient does not
    fit the chip at this call), whose forward is first held to the oracle's
    at 1e-5 (``ssd.oracle_in_blocks``).  Tolerances, against each array's
    largest element: 2e-2 forward (one rounding of o and of the products'
    operands), 5e-2 on dx, ddt, dB, dC and dD, 1e-1 on dA (a head's sum over
    every token of terms of both signs).  Then us a call of the forward and
    of the gradient program, the kernels and the ``jax.numpy`` chunked form."""
    from apex_tpu.ops.ssd import ssd_recurrent, ssd_scan

    f32, bf16, normal = jnp.float32, jnp.bfloat16, jax.random.normal
    shape, state = (1, 8 * s, s // 16, 64), 128
    heads = shape[2]

    def make(kx, kd, kb, kc):
        ka, kstep, kcot, kdt = jax.random.split(kd, 4)
        step = jnp.exp(jax.random.uniform(kstep, (heads,), f32,
                                          np.log(1e-3), np.log(1e-1)))
        dt = jax.nn.softplus(0.5 * normal(kdt, shape[:3], f32)
                             + jnp.log(jnp.expm1(step)))
        shared = lambda key: normal(key, (1, shape[1], 1, state), f32).astype(bf16)
        return (normal(kx, shape, f32).astype(bf16), dt,
                -jax.random.uniform(ka, (heads,), f32, 1.0, 16.0),
                shared(kb), shared(kc), jnp.ones((heads,), f32),
                normal(kcot, shape, f32).astype(bf16))

    *args, cot = jax.jit(lambda key: make(*jax.random.split(key, 4)))(
        jax.random.fold_in(root_key, 170))
    cot = cot.astype(f32)       # a cotangent bfloat16 holds, as conv1d's

    def both(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(f32) * cot), out
        return jax.jit(jax.value_and_grad(loss, tuple(range(6)), has_aux=True))

    compiled = both(ssd_scan).lower(*args).compile()
    _require_mosaic(compiled, 2, calls, "ssd")
    (_, out), grads = compiled(*args)
    with jax.default_matmul_precision("highest"):
        want = jax.jit(ssd_recurrent)(*args)
        (_, in_blocks), want_grads = both(_token_recurrence_in_blocks)(*args)
    _compare("ssd.oracle_in_blocks", in_blocks, want, 1e-5, parity)
    _compare("ssd.fwd", out, want, 2e-2, parity)
    for name, tol, g, w in zip(("dx", "ddt", "dA", "dB", "dC", "dD"),
                               (5e-2, 5e-2, 1e-1, 5e-2, 5e-2, 5e-2),
                               grads, want_grads):
        _compare(f"ssd.{name}", g, w, tol, parity)
    timed = {"shape": [*shape, state, 256],
             "kernels": mosaic_call_names(compiled.as_text())}
    for side, use_pallas in (("kernels", None), ("jnp", False)):
        scan = lambda *a: ssd_scan(*a, use_pallas=use_pallas)
        grad = jax.jit(jax.grad(lambda *a: jnp.sum(
            scan(*a).astype(f32) * cot), tuple(range(6))))
        timed[f"fwd_{side}_us"] = _us_a_call(jax.jit(scan), args)
        timed[f"grad_{side}_us"] = _us_a_call(grad, args)
    return timed


# ---------------------------------------------------------------------------
# the delta rule with a decay a key channel at the cell's call
# ---------------------------------------------------------------------------

def kda_at_cell(s: int, root_key, parity: Dict, calls: Dict) -> Dict:
    """``ops/kda.py::kda_rule`` at ``kimi-linear.train-8k``'s call — a row of
    8 contexts, ``s / 32`` heads of 128 for q, k and v in bfloat16, a 128 x
    128 float32 state a head, the log-decay (1, 8 s, heads, 128) float32 as
    the cell's seeded weights give it (``A = exp(A_log)`` log-uniform up to
    16 a head, a gate of its own a channel), chunks of 64 in sub-blocks of
    16 — twice: AS THE MODEL CALLS IT (``raw``: q and k as the convolution
    leaves them, the kernels normalising where they read, ``qk_norm``), and
    with q and k normalised in front of the call.  Each way the two kernels
    against the ``lax.scan`` path at ``highest`` precision in all five
    gradients (the raw call's dq and dk those in the raw q and k), and the
    normalised call's forward against the TOKEN RECURRENCE in float32 on the
    same bfloat16 values, to which the scan path is itself held at 1e-3
    (both rounded to bfloat16 on the way out:
    ``kda.scan_is_the_recurrence``) — the recurrence's own gradient keeps a
    state a token.  Tolerances, against each array's largest element: 2e-2
    forward, 5e-2 on dq, dk, dv, dbeta, 1e-1 on dg (a sum back over a chunk
    of terms of both signs).  Then us a call of the kernels' gradient
    program (forward included), both ways, and of the scan path's."""
    from apex_tpu.ops.kda import kda_rule, kda_rule_recurrent

    f32, bf16, normal = jnp.float32, jnp.bfloat16, jax.random.normal
    shape = (1, 8 * s, max(s // 32, 1), 128)
    norm = (1e-6, 128 ** -0.5)

    def make(kq, kk, kv, kg):
        ka, kgate, kb, kcot = jax.random.split(kg, 4)
        l2 = lambda x: x * jax.lax.rsqrt(
            jnp.sum(x * x, -1, keepdims=True) + norm[0])
        a = jax.random.uniform(ka, (shape[2], 1), f32, 1e-4, 16.0)
        q, k = (normal(key, shape, f32).astype(bf16) for key in (kq, kk))
        rest = (normal(kv, shape, f32).astype(bf16),
                -a * jax.nn.softplus(normal(kgate, shape, f32) + 1.0),
                jax.nn.sigmoid(normal(kb, shape[:3], f32)))
        return ((q, k, *rest),
                ((l2(q.astype(f32)) * norm[1]).astype(bf16),
                 l2(k.astype(f32)).astype(bf16), *rest),
                normal(kcot, shape, f32).astype(bf16))

    raw, args, cot = jax.jit(lambda key: make(*jax.random.split(key, 4)))(
        jax.random.fold_in(root_key, 180))
    cot = cot.astype(f32)

    def both(fn):
        def loss(*a):
            out = fn(*a)
            return jnp.sum(out.astype(f32) * cot), out
        return jax.jit(jax.value_and_grad(loss, tuple(range(5)), has_aux=True))

    timed = {"shape": [*shape, 64, 16]}
    for record, parity_key, time_key, operands, kw in (
            ("kda", "kda.{}", "grad_kernels_us", args, {}),
            ("kda_raw", "kda.raw.{}", "grad_kernels_raw_us", raw,
             {"qk_norm": norm})):
        compiled = both(functools.partial(kda_rule, **kw)).lower(
            *operands).compile()
        _require_mosaic(compiled, 2, calls, record)
        (_, out), grads = compiled(*operands)
        with jax.default_matmul_precision("highest"):
            by_scan = both(functools.partial(kda_rule, use_pallas=False, **kw))
            (_, want), want_grads = by_scan(*operands)
            if not kw:
                scanned, want = want, jax.jit(kda_rule_recurrent)(*operands)
                _compare("kda.scan_is_the_recurrence", scanned, want, 1e-3,
                         parity)
        _compare(parity_key.format("fwd"), out, want, 2e-2, parity)
        for name, tol, g, w in zip(("dq", "dk", "dv", "dg", "dbeta"),
                                   (5e-2, 5e-2, 5e-2, 1e-1, 5e-2),
                                   grads, want_grads):
            _compare(parity_key.format(name), g, w, tol, parity)
        # forward with the five gradients: the kernels' program
        timed[time_key] = _us_a_call(compiled, operands, n=3)
    timed["kernels"] = mosaic_call_names(compiled.as_text())
    # and the scan path's (float32 at ``highest`` precision), normalising
    timed["grad_scan_us"] = _us_a_call(by_scan, raw, n=2)
    return timed


# ---------------------------------------------------------------------------
# the Mamba-2 layer's short convolution at the cell's call
# ---------------------------------------------------------------------------

def ssm_conv_at_cell(s: int, root_key, parity: Dict, calls: Dict) -> Dict:
    """``ops/ssd.py::split_conv_xbc`` at ``granite-h.train-8k``'s call — a
    mamba layer's ``in_proj`` output of one row of 8 contexts, ``[z | x | B |
    C | dt]`` with ``s / 16`` heads of 64 channels and a state of 128 in
    bfloat16, 4 taps and a bias over the ``xBC`` channels: the two kernels,
    reading x, B and C where they lie, against the ``jax.numpy`` form in
    float32 on the same bfloat16 values — the five outputs in the
    projection's own order and the gradients of the projection's output, the
    taps and the bias (1e-2 of each array's largest element: one rounding of
    an output; 1e-3 on dw and dbias, float32 sums in another order) — every
    Mosaic call named.  Then us a call of the forward and of the gradient
    program on both paths.  An array 66.5 lane tiles wide that ENTERS or
    LEAVES a program is laid out the other way round by the chip's compiler,
    so every timed program opens (the gradient's also closes) with a relayout
    copy of the whole array that no model's program has, on both paths alike:
    the us bound the operator from above; the kernels' own times are the
    cell's trace's (PERF.md section 5)."""
    from apex_tpu.ops.ssd import split_conv_xbc

    f32, bf16, normal = jnp.float32, jnp.bfloat16, jax.random.normal
    heads, state, taps = s // 16, 128, 4
    d_in = heads * 64
    conv = d_in + 2 * state
    shape = (1, 8 * s, d_in + conv + heads)
    x, w, bias, cot = jax.jit(lambda key: [
        make(k_) for make, k_ in zip(
            (lambda k_: normal(k_, shape, f32).astype(bf16),
             lambda k_: 0.5 * normal(k_, (conv, taps), f32),
             lambda k_: 0.5 * normal(k_, (conv,), f32),
             lambda k_: normal(k_, shape, f32).astype(bf16)),
            jax.random.split(key, 4))])(jax.random.fold_in(root_key, 180))
    cot = cot.astype(f32)       # a cotangent bfloat16 holds, as conv1d's

    def parts(use_pallas):
        return lambda x, w, bias: split_conv_xbc(
            x, w, bias, d_inner=d_in, d_bc=state, use_pallas=use_pallas)

    def loss(use_pallas):
        def fn(x, w, bias, cot):
            out = jnp.concatenate(parts(use_pallas)(x, w, bias), axis=-1)
            return jnp.sum(out.astype(f32) * cot), out
        return fn

    both = lambda use_pallas: jax.jit(jax.value_and_grad(
        loss(use_pallas), (0, 1, 2), has_aux=True))
    compiled = both(None).lower(x, w, bias, cot).compile()
    _require_mosaic(compiled, 2, calls, "ssm_conv")
    (_, out), grads = compiled(x, w, bias, cot)
    (_, want), want_grads = both(False)(x.astype(f32), w, bias, cot)
    _compare("ssm_conv.fwd", out, want, 1e-2, parity)
    for name, tol, g, wg in zip(("dx", "dw", "dbias"), (1e-2, 1e-3, 1e-3),
                                grads, want_grads):
        _compare(f"ssm_conv.{name}", g, wg, tol, parity)
    timed = {"shape": [*shape, taps],
             "kernels": mosaic_call_names(compiled.as_text())}
    for side, use_pallas in (("kernels", None), ("jnp", False)):
        grad = jax.jit(jax.grad(lambda *a: loss(use_pallas)(*a)[0], (0, 1, 2)))
        timed[f"fwd_{side}_us"] = _us_a_call(jax.jit(parts(use_pallas)),
                                             (x, w, bias))
        timed[f"grad_{side}_us"] = _us_a_call(grad, (x, w, bias, cot))
    return timed


# ---------------------------------------------------------------------------
# q, k and v from the projection's output to heads-major at the cells' calls
# ---------------------------------------------------------------------------

#: (name, contexts of ``s``, query heads, key heads, an output gate's columns
#: behind v, the norm's eps, the rotation's theta): an attention layer's call
#: of ``models/decoder.py::qkv_heads`` in ``trinity-mini.train-8k`` (window
#: and full layers) and ``smallthinker.train-16k`` (the same), heads of 128
QK_HEADS_CALLS = (
    ("trinity_window", 8, 32, 4, True, 1e-5, 1e4),
    ("trinity_full", 8, 32, 4, True, 1e-5, None),
    ("smallthinker_window", 16, 28, 4, False, None, 1.5e6),
    ("smallthinker_full", 16, 28, 4, False, None, None),
)


def qk_heads_at_cell(s: int, root_key, parity: Dict, calls: Dict) -> Dict:
    """``models/decoder.py::qkv_heads`` at the four calls of
    :data:`QK_HEADS_CALLS`, one row of bfloat16: the kernel pair of
    ``ops/qk_heads.py`` against the composed path (``jnp.split``,
    ``split_heads``, ``RMSNorm``, ``rotary``) on the same values and the
    same parameter tree — q, k, v, the gate's columns, the two gains'
    gradients (1e-2 of each array's largest element: one rounding of an
    output) and the projection's (2e-2) — every Mosaic call named.  Then us
    a call of the forward and of the gradient program (cotangents in, the
    projection's gradient out: XLA drops a forward nothing reads) on both
    paths, with the bytes the call has to move — forward: q, k and v read
    once and written once; gradient: the cotangents of the whole width read,
    q's and k's columns again where there is a norm, the whole width
    written; the tables either way — and the GB/s that makes of the kernels'
    time."""
    import flax.linen as nn

    from apex_tpu.models import decoder
    from apex_tpu.ops._common import force_pallas

    f32, bf16, normal, hd = jnp.float32, jnp.bfloat16, jax.random.normal, 128
    out = {}
    for i, (name, ctxs, hq, hk, gate, eps, theta) in enumerate(QK_HEADS_CALLS):
        rows = ctxs * s
        used = (hq + 2 * hk) * hd
        width = used + (hq * hd if gate else 0)

        class Heads(nn.Module):
            @nn.compact
            def __call__(self, x):
                return decoder.qkv_heads(x, hq, hk, hd, norm_eps=eps,
                                         theta=theta)

        def loss(use_pallas):
            def fn(params, x, cots):
                with force_pallas(use_pallas):
                    outs = Heads().apply({"params": params}, x)
                outs = [o for o in outs if o is not None]
                return sum(jnp.sum(o.astype(f32) * c.astype(f32))
                           for o, c in zip(outs, cots)), outs
            return fn

        shapes = ([(1, hq, rows, hd)] + 2 * [(1, hk, rows, hd)]
                  + ([(1, rows, width - used)] if gate else []))
        params, x, cots = jax.jit(lambda key: (
            {} if eps is None else {
                n_: {"scale": 1 + 0.1 * normal(jax.random.fold_in(key, j),
                                               (hd,), f32)}
                for j, n_ in enumerate(("q_norm", "k_norm"))},
            normal(jax.random.fold_in(key, 2), (1, rows, width), f32
                   ).astype(bf16),
            [normal(jax.random.fold_in(key, 3 + j), shape, f32).astype(bf16)
             for j, shape in enumerate(shapes)],
        ))(jax.random.fold_in(root_key, 190 + i))
        both = lambda use_pallas: jax.jit(jax.value_and_grad(
            loss(use_pallas), (0, 1), has_aux=True))
        compiled = both(None).lower(params, x, cots).compile()
        _require_mosaic(compiled, 2, calls, f"qk_heads.{name}")
        (_, got), grads = compiled(params, x, cots)
        (_, want), want_grads = both(False)(params, x, cots)
        for part, g, w in zip(("q", "k", "v", "rest"), got, want):
            _compare(f"qk_heads.{name}.{part}", g, w, 1e-2, parity)
        # (the composed path rounds the cotangent once more, between the
        # rotation's gradient and the norm's)
        _compare(f"qk_heads.{name}.dx", grads[1], want_grads[1], 2e-2, parity)
        for gain in grads[0]:
            _compare(f"qk_heads.{name}.d{gain}", grads[0][gain]["scale"],
                     want_grads[0][gain]["scale"], 1e-2, parity)
        tables = 0 if theta is None else 2 * rows * hd * 4
        moved = {"fwd": 2 * rows * used * 2 + tables,
                 "grad": rows * (2 * width + (eps is not None)
                                 * (hq + hk) * hd) * 2 + tables}
        timed = out[name] = {
            "shape": [1, rows, width, hq, hk], "norm": eps is not None,
            "rotated": theta is not None,
            "kernels": mosaic_call_names(compiled.as_text())}
        for side, use_pallas in (("kernels", None), ("composed", False)):
            fn = loss(use_pallas)
            for what, timed_fn in (
                    ("fwd", lambda *a: fn(*a)[1]),
                    ("grad", jax.grad(lambda *a: fn(*a)[0], (0, 1)))):
                us = timed[f"{what}_{side}_us"] = _us_a_call(
                    jax.jit(timed_fn), (params, x, cots))
                if side == "kernels":
                    timed[f"{what}_bytes"] = moved[what]
                    timed[f"{what}_kernels_gb_per_s"] = round(
                        moved[what] / us / 1e3, 1)
    return out


# ---------------------------------------------------------------------------
# one dense block's gradient at the cell's shapes
# ---------------------------------------------------------------------------

def dense_ffn_at_cell(s: int, root_key, calls: Dict) -> Dict:
    """The gradient of ONE mamba block of ``granite-h.train-8k`` under
    ``full_block`` — a row of 8 contexts, hidden ``2 s``, a gated MLP ``8 s``
    wide, ``s / 16`` heads of 64 channels with a state of 128, bfloat16
    matrices — with respect to its parameters and its input, its output
    handed on as to a next block (so the forward pass is live, and what the
    backward pass runs again is a SECOND forward), compiled, three calls of it
    traced and the trace joined to the program's own text
    (``apex_tpu.pyprof``).  Reports how many products of ``gate_up``'s size
    (tokens x hidden x 2 d_ff) the program RUNS — three: the forward and the
    two gradient products, the recomputed block reading the forward's result
    kept (``remat.MLP_GATE_UP``); four before PR 45 — and holds the run to
    that; then the device ms a call of those products, of everything under
    the scope ``dense_ffn`` (a tenth of the cell's
    ``model.dense_ffn_ms_per_step``) and of the whole block, and the host's
    ms a call."""
    import tempfile

    from apex_tpu.models.granite_hybrid import (
        MAMBA, GraniteHybridConfig, GraniteHybridLayer)
    from apex_tpu.pyprof.parse import find_xplane, join, parse_xplane
    from apex_tpu.pyprof.prof import parse_hlo
    from apex_tpu.remat import remat_module

    f32, bf16 = jnp.float32, jnp.bfloat16
    tokens, d, d_ff, iters = 8 * s, 2 * s, 8 * s, 3
    cfg = GraniteHybridConfig(
        hidden_size=d, layer_types=(MAMBA,), mamba_n_heads=s // 16,
        num_heads=max(1, s // 32), num_kv_heads=max(1, s // 128),
        intermediate_size=d_ff, remat_policy="full_block")
    block = remat_module(GraniteHybridLayer, cfg.remat_policy,
                         static_argnums=(2,))(cfg, 0)
    kx, kp, kc = jax.random.split(jax.random.fold_in(root_key, 190), 3)
    x = jax.random.normal(kx, (1, tokens, d), f32).astype(bf16)
    cot = jax.random.normal(kc, (1, tokens, d), f32).astype(bf16).astype(f32)
    params = jax.tree_util.tree_map_with_path(     # O2: matrices in bfloat16
        lambda path, leaf: leaf.astype(bf16)
        if path[-1].key == "kernel" else leaf,
        jax.jit(lambda key: block.init(key, x, True))(kp)["params"])

    def loss(params, x):        # the output leaves too, as to a next block:
        out = block.apply({"params": params}, x, True)  # the forward is live
        return jnp.sum(out.astype(f32) * cot), out

    compiled = jax.jit(jax.grad(loss, (0, 1), has_aux=True)).lower(
        params, x).compile()
    _require_mosaic(compiled, 2, calls, "dense_ffn_block")
    text = compiled.as_text()
    a_pass = 2.0 * tokens * d * 2 * d_ff
    products = [i.name for i in parse_hlo(text)
                if i.opcode in ("convolution", "dot") and i.flops == a_pass]
    _require(len(products) == 3,
             f"dense_ffn_at_cell: {len(products)} products of gate_up's size "
             f"in the block's gradient, not 3: {products}")
    host_ms = _us_a_call(compiled, (params, x), n=iters) / 1e3
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(iters):
                jax.block_until_ready(compiled(params, x))
        profile = join(text, parse_xplane(find_xplane(trace_dir)))
    ms = lambda rows: round(sum(r.time_ns for r in rows) / iters / 1e6, 3)
    return {
        "shape": [1, tokens, d, d_ff], "gate_up_products": len(products),
        # an event is a top-level instruction: a product and what XLA fused
        # to it (the cast, silu(gate) * up) are one
        "gate_up_ms": ms([r for r in profile.rows
                          if r.flops >= a_pass * r.count]),
        "dense_ffn_ms": ms([r for r in profile.rows if "dense_ffn" in r.key]),
        "device_ms": ms(profile.rows), "block_ms": round(host_ms, 3),
        "kernels": mosaic_call_names(text)}


# ---------------------------------------------------------------------------
# flash attention's forward + gradient at the cells' calls
# ---------------------------------------------------------------------------

def _flash_call_at(tag: str, name: str, parity: Dict, key, *, hq, hkv, seq, d,
                   d_v, window=None, causal=True, batch=1, bias=False,
                   blocks=None, n=10) -> Dict:
    """One ``flash_attention`` call, bfloat16: us a call forward and with
    gradients, the Mosaic calls of the gradient program by name — on the
    shipped route and, where the call has several key blocks, on the
    two-pass route a head past the VMEM budget keeps (dkdv + dq) — the
    routes held to each other and to the float32 reference taken a head at
    a time (``parity``, under ``tag.name``)."""
    import apex_tpu.ops.attention as attention_mod
    from apex_tpu.ops import attention_ref, flash_attention

    f32, bf16 = jnp.float32, jnp.bfloat16
    q_, k_, v_, w_ = jax.jit(lambda key: [
        (jax.random.normal(ki, (batch, heads, seq, width), f32) * 0.3
         ).astype(bf16)
        for ki, heads, width in zip(jax.random.split(key, 4),
                                    (hq, hkv, hkv, hq), (d, d, d_v, d_v))
    ])(key)
    extra = ()
    if bias:    # an additive mask a row, as BERT's padding mask arrives
        extra = (jax.jit(lambda key: jax.random.normal(
            key, (batch, seq, seq), f32))(jax.random.fold_in(key, 7)),)
    kw_ = dict(causal=causal, window=window)
    blocks = blocks or {}

    def attend(q, k, v, *bias):
        return flash_attention(q, k, v, *bias, **kw_, **blocks)

    def loss(q, k, v, w, *bias):
        return jnp.sum(attend(q, k, v, *bias).astype(f32) * w.astype(f32))

    def ref_loss(q, k, v, w, *bias):
        def head(t):
            q1, k1, v1, *b1 = t
            return attention_ref(
                *(x[None, None] for x in (q1, k1, v1)),
                *(x[None] for x in b1), **kw_)[0, 0]
        flat = lambda t: t.reshape(-1, *t.shape[2:])
        k, v = (jnp.repeat(t, hq // hkv, axis=1) for t in (k, v))
        out = jax.lax.map(jax.checkpoint(head), tuple(map(flat, (q, k, v)))
                          + tuple(jnp.repeat(b, hq, axis=0) for b in bias))
        return jnp.sum(out.reshape(w.shape) * w)

    args = (q_, k_, v_, w_, *extra)
    rec = {"shape": [hq, hkv, seq, d, d_v, window],
           "fwd_us": _us_a_call(jax.jit(attend), (q_, k_, v_, *extra), n)}
    if batch > 1 or bias:
        rec["shape"] = [batch, *rec["shape"], bool(causal), bool(bias)]
    grads = {}
    budget = attention_mod._SWEEP_ACC_BUDGET_BYTES
    several = seq > blocks.get("block_k", attention_mod.MAX_AUTO_BLOCK_K)
    try:
        for route, room in (("shipped", budget), ("two_pass", 0)):
            if route == "two_pass" and not several:
                continue    # one key block: the budget is not asked
            # read when the call is traced, and part of the trace's key
            attention_mod._SWEEP_ACC_BUDGET_BYTES = room
            compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(
                *args).compile()
            rec[route + "_kernels"] = mosaic_call_names(compiled.as_text())
            rec[route + "_grad_us"] = _us_a_call(compiled, args, n)
            grads[route] = compiled(*args)
    finally:
        attention_mod._SWEEP_ACC_BUDGET_BYTES = budget
    with jax.default_matmul_precision("highest"):
        grads["ref"] = jax.jit(jax.grad(ref_loss, (0, 1, 2)))(
            *(t.astype(f32) for t in args))
    for other, tol in (("two_pass", 1e-2), ("ref", 3e-2)):
        for gname, g, o in zip(("dq", "dk", "dv"), grads["shipped"],
                               grads.get(other, ())):
            _compare(f"{tag}.{name}.{gname}_vs_{other}", g, o, tol, parity)
    return rec


def flash_backward_routes(s: int, root_key, parity: Dict) -> Dict:
    """Flash attention's backward at the three 8k cells' calls (a row of 8
    contexts: Moonlight's 16 heads at 192 / 128, Trinity-Mini's 32 query
    heads to 4 at 128 with and without its window, Qwen3-Next's 16 to 2 at
    256) and at smallthinker.train-16k's (a row of 16 contexts, 28 query
    heads to 4 — groups of SEVEN — at 128, the 4096-wide band and the whole
    triangle, dk / dv accumulators of 16.8 MB a key/value head): the shipped
    route — ONE sweep, dk and dv resident in VMEM — against the two-pass
    route (the kernels these shapes ran before the one sweep) and against
    the float32 reference; us a call forward and with gradients for both."""
    sw, hl = 8 * s, s // 64
    hs = max(hl // 4, 1)
    blocks = dict(block_q=sw // 16, block_k=sw // 8)   # the auto blocks at 8k
    routes = {}
    for i, (name, hq, hkv, d, d_v, window, seq, n) in enumerate((
            ("moonlight", hl, hl, 192, 128, None, sw, 10),
            ("trinity_window", 2 * hl, hs, 128, 128, sw // 4, sw, 10),
            ("trinity_full", 2 * hl, hs, 128, 128, None, sw, 10),
            ("qwen3_next", hl, max(hl // 8, 1), 256, 256, None, sw, 10),
            ("smallthinker_window", 7 * hs, hs, 128, 128, sw // 2, 2 * sw, 3),
            ("smallthinker_full", 7 * hs, hs, 128, 128, None, 2 * sw, 3))):
        # the keys the probe has drawn its inputs from since PRs 31 and 37
        key = jax.random.fold_in(root_key, 100 + i if i < 4 else 106 + i)
        routes[name] = _flash_call_at(
            "flash_backward", name, parity, key, hq=hq, hkv=hkv, seq=seq, d=d,
            d_v=d_v, window=window, blocks=blocks, n=n)
    return routes


def flash_stats_at_cell(s: int, root_key, parity: Dict, routes: Dict) -> Dict:
    """The forward + gradient of ONE layer's attention call of each of the
    seven cells, a record a cell: us a call, the Mosaic calls by name, the
    gradients held to the float32 reference.  What the softmax statistics'
    layout between the kernels costs shows here in every route: GPT-2
    small's ``[16, 12, 1024, 64]`` causal and BERT-large's ``[12, 16, 512,
    64]`` under an additive mask (one key block: ``apex_flash_bwd_fused``),
    LFM2's 32 query heads to 8 of 64 at 16k and the four calls
    :func:`flash_backward_routes` has timed already (``routes``: the one
    sweep, and the two passes no cell runs), whose records are these."""
    hl = s // 64
    cells = {
        "gpt2-small.train": dict(
            batch=hl, hq=max(3 * hl // 4, 1), seq=s, d=64),
        "bert-large.train": dict(
            batch=max(3 * hl // 4, 1), hq=hl, seq=s // 2, d=64, causal=False,
            bias=True),
        "lfm2.train-16k": dict(
            hq=2 * hl, hkv=max(hl // 2, 1), seq=16 * s, d=64, n=3),
    }
    out = {}
    for i, (cell, at) in enumerate(cells.items()):
        at.setdefault("hkv", at["hq"])
        out[cell] = _flash_call_at(
            "flash_stats_at_cell", cell, parity,
            jax.random.fold_in(root_key, 170 + i), d_v=at["d"], **at)
    for cell, name in (("trinity-mini.train-8k", "trinity_window"),
                       ("qwen3-next.train-8k", "qwen3_next"),
                       ("moonlight.train-8k", "moonlight"),
                       ("smallthinker.train-16k", "smallthinker_window")):
        out[cell] = routes[name]
    return out


# ---------------------------------------------------------------------------
# the expert layer's routing plan, one making, at the sparse cells' shapes
# ---------------------------------------------------------------------------

# (cell, contexts of tokens, k, experts scored, held, hidden / ctx, scores)
MOE_PLAN_CELLS = (
    ("trinity-mini.train-8k", 8, 8, 128, 16, 2.0, "sigmoid"),
    ("qwen3-next.train-8k", 8, 10, 512, 32, 2.0, "softmax"),
    ("moonlight.train-8k", 8, 6, 64, 8, 2.0, "sigmoid"),
    ("smallthinker.train-16k", 16, 6, 64, 8, 2.5, "softmax"),
    ("lfm2.train-16k", 16, 4, 64, 8, 2.0, "sigmoid"),
)


def moe_plan_at_cell(s: int, root_key, parity: Dict) -> Dict:
    """ONE making of ``ExpertShardMLP``'s routing plan (the router's
    selection and weights from seeded logits, then ``_route``) at each sparse
    cell's ``(tokens, k, experts, held)``, us on the device; its dear parts alone
    (``top_k``, the running count, the ``argsort``); and each lookup the plan
    makes, as the GATHER out of a table it was and as the SUM over the held
    experts it can be — a slot's row, the picked weights with their gradient,
    a row's slot — us of each, the tables held equal to the bit and
    the weights to 1e-6 (``parity``)."""
    from apex_tpu.ops import grouped_mm as gmm
    from apex_tpu.ops import moe_rows
    from apex_tpu.parallel import moe

    f32, tile = jnp.float32, gmm.DEFAULT_TILE_ROWS
    out: Dict = {}
    for i, (cell, ctxs, k, e, n_held, width, score) in enumerate(MOE_PLAN_CELLS):
        t, n, d = ctxs * s, ctxs * s * k, int(width * s)
        cap = gmm.rows_capacity(t * min(k, n_held), n_held, tile)
        block = moe_rows.combine_block(t, k, d)
        logits, bias, cot = jax.jit(lambda key: [
            scale * jax.random.normal(ki, shape, f32) for ki, shape, scale in
            zip(jax.random.split(key, 3), ((t, e), (e,), (t, k)),
                (1.0, 0.1, 1.0))])(jax.random.fold_in(root_key, 150 + i))

        if score == "sigmoid":
            values, lean = jax.nn.sigmoid, bias
            route = lambda lg: moe.sigmoid_topk_routing(lg, bias, k, True, 2.5)
            gathered = lambda lg: jnp.take_along_axis(
                values(lg), jax.lax.top_k(values(lg) + lean, k)[1], axis=-1)
        else:
            values, lean = jax.nn.softmax, 0.0
            route = lambda lg: moe.softmax_topk_routing(lg, k, True)
            gathered = lambda lg: jax.lax.top_k(values(lg), k)[0]
        summed = lambda lg: moe._picked(
            values(lg), jax.lax.top_k(values(lg) + lean, k)[1])

        def making(lg):
            sel, w = route(lg)
            return sel, w, moe._route(sel, (0, n_held), cap, tile, block)

        def first_half(sel):        # shard_dispatch down to the layout
            local = sel.reshape(n)
            mine = local < n_held
            onehot = ((local[:, None] == jnp.arange(n_held)[None, :])
                      & mine[:, None]).astype(jnp.int32)
            count = jnp.cumsum(onehot, axis=0)
            return local, mine, onehot, count, gmm.group_layout(
                count[-1], cap, tile)

        def slot_row_gather(local, mine, onehot, count, layout):
            rank = jnp.sum((count - onehot) * onehot, axis=-1)
            return jnp.where(mine, layout.row_start[
                jnp.clip(local, 0, n_held - 1)] + rank, cap)

        def slot_row_sum(local, mine, onehot, count, layout):
            return jnp.where(mine, jnp.sum(onehot * (
                layout.row_start[None, :] + count - onehot), axis=-1), cap)

        row = jnp.arange(cap, dtype=jnp.int32)

        def rows_gathers(layout, sizes, order):
            group = layout.tile_group[row // tile]
            within = row - layout.row_start[group]
            live = (within < sizes[group]) & (
                row // tile < layout.tiles_used[0])
            first = jnp.cumsum(sizes) - sizes
            return jnp.where(live, order[
                jnp.clip(first[group] + within, 0, n - 1)], n)

        def in_groups(layout):
            reached = row[None, :] >= layout.row_start[:, None]
            return reached & ~jnp.concatenate(
                [reached[1:], jnp.zeros((1, cap), bool)])

        def rows_sums(layout, sizes, order, shifts: bool):
            in_group = in_groups(layout)
            of_group = lambda table: jnp.sum(
                jnp.where(in_group, table[:, None], 0), axis=0)
            within = row - of_group(layout.row_start)
            live = (within < of_group(sizes)) & (
                row // tile < layout.tiles_used[0])
            first = jnp.cumsum(sizes) - sizes
            if not shifts:
                return jnp.where(live, order[
                    jnp.clip(of_group(first) + within, 0, n - 1)], n)
            room = jnp.full((cap,), n, order.dtype)
            padded = jnp.concatenate([room, order, room])

            def shifted(row_slot, group):
                rows_of, moved = group
                run = jax.lax.dynamic_slice(padded, (cap - moved,), (cap,))
                return jnp.where(rows_of & live, run, row_slot), None

            return jax.lax.scan(
                shifted, room, (in_group, layout.row_start - first))[0]

        def with_grad(picked):
            def both(lg):
                w, back = jax.vjp(picked, lg)
                return w, back(cot)[0]
            return both

        sel, _, routing = jax.jit(making)(logits)
        half = jax.jit(first_half)(sel)
        slot_row = jax.jit(slot_row_sum)(*half)
        order = jax.jit(jnp.argsort)(slot_row)
        second = (half[-1], half[3][-1], order)
        rec = out[cell] = {
            "shape": [t, k, e, n_held, tile], "rows_capacity": cap,
            "rows_live": int(jnp.sum(routing.row_slot < n))}
        timed = {
            "making": (making, (logits,)),
            "top_k": (lambda lg: jax.lax.top_k(lg, k), (logits,)),
            "running_count": (lambda sel: first_half(sel)[3], (sel,)),
            "argsort": (jnp.argsort, (slot_row,)),
            "slot_row_gather": (slot_row_gather, half),
            "slot_row_sum": (slot_row_sum, half),
            "picked_gather": (with_grad(gathered), (logits,)),
            "picked_sum": (with_grad(summed), (logits,)),
            "row_slot_gathers": (rows_gathers, second),
            "row_slot_sums": (
                lambda *a: rows_sums(*a, shifts=False), second),
            "row_slot_sums_shifts": (
                lambda *a: rows_sums(*a, shifts=True), second),
        }
        results = {}
        for name, (fn, args) in timed.items():
            rec[f"{name}_us"] = _us_on_device(fn, args)
            results[name] = jax.tree_util.tree_leaves(jax.jit(fn)(*args))
        same = lambda a, b: all(
            x.dtype == y.dtype and bool(jnp.array_equal(x, y))
            for x, y in zip(results[a], results[b], strict=True))
        # the weights are floats: held to 1e-6 and said whether to the bit
        # (each side's program rounds its own sigmoid or softmax)
        for j, what in enumerate(("w", "dlogits")):
            _compare(f"moe_plan_at_cell.{cell}.picked.{what}",
                     results["picked_sum"][j], results["picked_gather"][j],
                     1e-6, parity)
        rec["picked_same_bits"] = same("picked_gather", "picked_sum")
        for a, b in (("slot_row_gather", "slot_row_sum"),
                     ("row_slot_gathers", "row_slot_sums"),
                     ("row_slot_gathers", "row_slot_sums_shifts")):
            _require(same(a, b), f"moe_plan_at_cell {cell}: {b} is not {a} "
                                 "to the bit")
        _require(bool(jnp.array_equal(results["slot_row_sum"][0].reshape(t, k),
                                      routing.slot_row))
                 and bool(jnp.array_equal(results["row_slot_gathers"][0],
                                          routing.row_slot)),
                 f"moe_plan_at_cell {cell}: the forms timed alone do not "
                 "make the shipped plan's tables")
    return out


# ---------------------------------------------------------------------------
# the model: GPT-2 small, AMP + fused_adam, a fixed seeded batch
# ---------------------------------------------------------------------------

def _init_params(model, seed: int, *args, **kw):
    """``model.init(...)["params"]`` as ONE program (eagerly it is a
    hundred small ones, each a compile of its own on the chip)."""
    return jax.jit(
        lambda key: model.init(key, *args, **kw)["params"]
    )(jax.random.PRNGKey(seed))


def _gpt_config(sizes: Sizes, **kw):
    from apex_tpu.models.gpt import GPTConfig

    return dataclasses.replace(
        GPTConfig.small(**kw), max_position=sizes.ctx, **sizes.model
    )


def _train_setup(sizes: Sizes, seed: int, opt_level: str, *, batch: int,
                 dropout: bool, ddp=None):
    """``(step_fn, carry, (ids, labels), cfg, amp_)`` for one GPT-2 causal-LM
    train step: AMP ``opt_level`` + ``fused_adam``, a fixed seeded batch.
    ``step_fn(carry, batch)`` trains on ``batch`` — or on the closure's
    batch when the driver passes None.  With
    ``ddp`` the per-shard grads go through its allreduce."""
    import apex_tpu.amp as amp
    from apex_tpu.models.gpt import GPTLM
    from apex_tpu.optimizers import fused_adam

    amp_ = amp.initialize(opt_level)
    rates = {} if dropout else {"dropout_rate": 0.0, "attn_dropout_rate": 0.0}
    cfg = _gpt_config(sizes, compute_dtype=amp_.policy.compute_dtype, **rates)
    model = GPTLM(cfg)
    opt = amp.AmpOptimizer(fused_adam(6e-4, weight_decay=0.1), amp_)
    rng = np.random.RandomState(seed)
    b, s = batch, sizes.ctx
    ids = rng.randint(0, cfg.vocab_size, size=(b, s)).astype(np.int32)
    labels = np.concatenate(
        [ids[:, 1:], np.full((b, 1), -100, np.int32)], axis=1
    )
    probe = min(128, s)
    params = _init_params(
        model, seed, ids[:1, :probe], labels=labels[:1, :probe]
    )
    state = jax.jit(opt.init)(params)
    ids, labels = jnp.asarray(ids), jnp.asarray(labels)

    def step(carry, batch):
        params, state, key = carry
        x, y = (ids, labels) if batch is None else batch
        key, dkey = jax.random.split(key)

        def scaled(mp):
            _, loss = model.apply(
                {"params": opt.model_params(mp)}, x, labels=y,
                deterministic=not dropout, rngs={"dropout": dkey},
            )
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(params)
        if ddp is not None:
            grads = ddp.allreduce(grads)
            loss = jax.lax.pmean(loss, ddp.axis_name)
        params, state, _ = opt.step(grads, state, params)
        return (params, state, key), {"loss": loss}

    carry = (params, state, jax.random.PRNGKey(seed + 1))
    return step, carry, (ids, labels), cfg, amp_


def _train_calls_expected(cfg, opt_level: str) -> int:
    """Mosaic calls the train step's shape gates promise: per layer the
    flash forward + combined backward and two LayerNorms forward +
    backward, the final LayerNorm, and (half-precision logits only) the
    xentropy forward + backward."""
    xent = 2 if opt_level != "O0" else 0
    return cfg.num_layers * (2 + 4) + 2 + xent


# ---------------------------------------------------------------------------
# phase: kernels
# ---------------------------------------------------------------------------

def phase_kernels(sizes: Sizes, seed: int, facts: Dict) -> None:
    """Each main-path kernel, compiled, vs its jnp reference: forward
    and gradients at the model's widths.  The references run fp32 at
    ``highest`` matmul precision (the TPU's default would round their
    operands to bf16 and make them the less exact side)."""
    from apex_tpu.ops import (
        attention_ref, flash_attention, layer_norm, layer_norm_ref,
        softmax_cross_entropy, softmax_cross_entropy_ref,
    )

    cfg = _gpt_config(sizes)
    b, h, s = sizes.kernel_batch, cfg.num_heads, sizes.ctx
    d, n, v = cfg.hidden_size // h, cfg.hidden_size, cfg.vocab_size
    rows = b * s
    f32, bf16 = jnp.float32, jnp.bfloat16
    root_key = jax.random.PRNGKey(seed)
    # the first three as they always were; the later checks fold theirs in
    keys = iter(list(jax.random.split(root_key, 3))
                + [jax.random.fold_in(root_key, i) for i in range(3, 9)])
    calls: Dict[str, int] = {}
    parity: Dict[str, Dict] = {}
    facts.update(
        shapes={"flash": [b, h, s, d], "layer_norm": [rows, n],
                "xentropy": [rows, v],
                "flash_window_grouped": [1, 4, 8 * s, 128],
                "grouped_mm": [rows, n, 2 * n],
                "moe_dispatch": [rows, -(-n // 1024) * 1024],
                "gated_delta": [1, 8 * s, [s // 64, s // 32], 128],
                "flash_latent": [1, s // 64, 8 * s, [192, 128]],
                "conv1d": [1, 8 * s, s // 64 * 768, 4],
                "gated_conv": [1, 16 * s, 3 * 2 * s, 3],
                "ssd": [1, 8 * s, s // 16, 64, 128],
                "ssm_conv": [1, 8 * s, 8 * s + 256 + s // 16, 4]},
        mosaic_calls=calls, parity=parity,
    )

    def seeded(make):
        """``make(k1, k2, k3, k4)`` -> a kernel's inputs, as one program."""
        return jax.jit(lambda key: make(*jax.random.split(key, 4)))(next(keys))

    def run(name, kernel_loss, ref_loss, args, ref_args, n_calls, tols):
        """Value and grads of both sides.  ``tols`` = (forward tol,
        ((grad name, tol), ...)): one grad per leading argument; the
        arguments after those are constants (passed, not closed over —
        a closed-over array would be baked into the program's text)."""
        argnums = tuple(range(len(tols[1])))
        kfn = jax.jit(jax.value_and_grad(kernel_loss, argnums, has_aux=True))
        compiled = kfn.lower(*args).compile()
        _require_mosaic(compiled, n_calls, calls, name)
        (_, out), grads = compiled(*args)
        with jax.default_matmul_precision("highest"):
            (_, rout), rgrads = jax.jit(
                jax.value_and_grad(ref_loss, argnums, has_aux=True)
            )(*ref_args)
        _compare(f"{name}.fwd", out, rout, tols[0], parity)
        for (gname, tol), g, rg in zip(tols[1], grads, rgrads):
            _compare(f"{name}.{gname}", g, rg, tol, parity)

    # flash attention, causal, in-kernel dropout on — as the train step
    # calls it.  bf16 in, fp32 reference of the same bf16 values: the
    # kernel tests' bf16 tier (tests/test_ops_attention.py::test_bf16)
    normal = jax.random.normal
    q, k, vv, w_out = seeded(lambda *ks: [
        (normal(ki, (b, h, s, d), f32) * scale).astype(dt)
        for ki, scale, dt in zip(ks, (0.3, 0.3, 0.3, 1.0),
                                 (bf16, bf16, bf16, f32))
    ])
    drop_seed = np.int32(seed + 11)

    def flash_loss(fn):
        def loss(q, k, v, w, drop_seed):
            out = fn(q, k, v, causal=True, dropout_rate=0.1,
                     dropout_seed=drop_seed)
            return jnp.sum(out.astype(f32) * w), out
        return loss

    run("flash", flash_loss(flash_attention), flash_loss(attention_ref),
        (q, k, vv, w_out, drop_seed),
        tuple(t.astype(f32) for t in (q, k, vv)) + (w_out, drop_seed), 2,
        (3e-2, (("dq", 3e-2), ("dk", 3e-2), ("dv", 3e-2))))

    # fused LayerNorm, fp32 rows as the model feeds it; the backward is
    # the dx pass with the dgamma/dbeta epilogue (default-on since r5,
    # first checked on hardware here).  tests/test_ops_layer_norm.py
    # tiers: forward 1e-5, grads 1e-3
    ln_args = seeded(lambda kx, kg, kb, kw: (
        normal(kx, (rows, n), f32) * 2.0 + 0.5,        # x
        1.0 + 0.1 * normal(kg, (n,), f32),             # gamma
        0.1 * normal(kb, (n,), f32),                   # beta
        normal(kw, (rows, n), f32),                    # cotangent
    ))

    def ln_loss(fn):
        def loss(x, g, b_, w):
            out = fn(x, g, b_)
            return jnp.sum(out * w), out
        return loss

    run("layer_norm", ln_loss(layer_norm), ln_loss(layer_norm_ref),
        ln_args, ln_args, 2,
        (1e-5, (("dx", 1e-3), ("dgamma", 1e-3), ("dbeta", 1e-3))))

    # fused softmax-xentropy on compute-dtype logits at the full vocab
    # (the model's loss path).  Both sides upcast the same bf16 logits:
    # losses at the fp32 tier of tests/test_ops_xentropy.py (1e-4); the
    # gradient comes back in bf16, so one bf16 ulp at 1.0 (2^-7)
    xent_args = seeded(lambda kl, kt, *_: (
        (normal(kl, (rows, v), f32) * 3.0).astype(bf16),     # logits
        jax.random.randint(kt, (rows,), 0, v, jnp.int32),    # labels
    ))

    def xent_loss(fn):
        def loss(lg, lb):
            per_row = fn(lg, lb)
            return jnp.sum(per_row), per_row
        return loss

    run("xentropy", xent_loss(softmax_cross_entropy),
        xent_loss(softmax_cross_entropy_ref), xent_args, xent_args, 2,
        (1e-4, (("dlogits", 2.0 ** -7),)))

    # the same flash kernels on the second decoder block's call
    # (models/afmoe.py): a sliding window, four query heads to a key/value
    # head, head size 128, eight query tiles a head — the banded grid and
    # the one-sweep backward, dk/dv summed over the group in its accumulators
    sw, hw = 8 * s, 4
    qw, kw, vw, w_win = seeded(lambda *ks: [
        (normal(ki, (1, heads, sw, 128), f32) * scale).astype(dt)
        for ki, heads, scale, dt in zip(ks, (hw, 1, 1, hw),
                                        (0.3, 0.3, 0.3, 1.0),
                                        (bf16, bf16, bf16, f32))
    ])

    def window_loss(fn):
        def loss(q, k, v, w):
            out = fn(q, k, v, causal=True, window=sw // 4)
            return jnp.sum(out.astype(f32) * w), out
        return loss

    run("flash_window_grouped", window_loss(flash_attention),
        window_loss(attention_ref), (qw, kw, vw, w_win),
        tuple(t.astype(f32) for t in (qw, kw, vw)) + (w_win,), 2,
        (3e-2, (("dq", 3e-2), ("dk", 3e-2), ("dv", 3e-2))))

    # the expert layer's grouped product (ops/grouped_mm.py) against
    # jax.lax.ragged_dot: an empty group, a single row, a group that
    # spans many tiles and one that ends inside a tile
    from apex_tpu.ops import grouped_mm as gmm

    sizes_e = jnp.asarray([0, 1, rows // 2 + 3, rows // 4], jnp.int32)
    cap = gmm.rows_capacity(rows, 4)
    layout = jax.jit(lambda z: gmm.group_layout(z, cap))(sizes_e)
    xg, wg, w_rows = seeded(lambda kx, kw_, kc, _: (
        (normal(kx, (cap, n), f32) * 0.5).astype(bf16),
        (normal(kw_, (4, n, 2 * n), f32) * 0.05).astype(bf16),
        normal(kc, (cap, 2 * n), f32),
    ))

    def live_rows(lay):
        """(rows, 1) bool: the tiles that belong to a group.  The kernels'
        row axis ends there; what lies past it is undefined: compared as 0."""
        tiles = lay.tile_group.shape[0]
        return jnp.repeat(jnp.arange(tiles) < lay.tiles_used[0],
                          gmm.DEFAULT_TILE_ROWS)[:, None]

    def gmm_loss(use_pallas):
        def loss(x, w, lay, cot):
            live = live_rows(lay)
            out = jnp.where(live, gmm.grouped_matmul(
                jnp.where(live, x, 0), w, lay, use_pallas=use_pallas), 0)
            return jnp.sum(out.astype(f32) * cot), out
        return loss

    run("grouped_mm", gmm_loss(None), gmm_loss(False),
        (xg, wg, layout, w_rows),
        (xg.astype(f32), wg.astype(f32), layout, w_rows), 3,
        (2e-2, (("dx", 2e-2), ("dw", 2e-2))))

    # the expert layer's row movement (ops/moe_rows.py) against the
    # jnp.take path of parallel/moe.py: tokens into the worst-case row
    # buffer and back, weighted, under one uneven routing (4 of 16 experts
    # held, 4 slots a token, skewed scores).  A record is whole tiles from
    # 1024 features on
    from apex_tpu.ops import moe_rows
    from apex_tpu.parallel import moe

    dm, slots, held, tile = -(-n // 1024) * 1024, 4, (0, 4), gmm.DEFAULT_TILE_ROWS
    cap_m = gmm.rows_capacity(rows * slots, held[1], tile)
    xm, wm, cot_m, routing = seeded(lambda kx, kw_, kc, kr: (
        (normal(kx, (rows, dm), f32) * 0.5).astype(bf16),
        jax.random.uniform(kw_, (rows, slots), f32),
        normal(kc, (rows, dm), f32),
        moe._route(jax.lax.top_k(
            normal(kr, (rows, 16), f32) + jnp.arange(16.0) / 4, slots)[1],
            held, cap_m, tile, moe_rows.combine_block(rows, slots, dm)),
    ))

    def dispatch_loss(tile_rows):
        def loss(x, w, routing, cot):
            out = moe._tokens_from_rows(
                moe._rows_from_tokens(x, routing, tile_rows), w, routing,
                tile_rows)
            return jnp.sum(out * cot), out
        return loss

    run("moe_dispatch", dispatch_loss(tile), dispatch_loss(None),
        (xm, wm, routing, cot_m), (xm.astype(f32), wm, routing, cot_m), 6,
        (1e-5, (("dx", 2e-2), ("dweights", 2e-2))))

    # the gated delta rule (ops/gated_delta.py) as qwen3-next.train-8k calls
    # it — one row of eight contexts, q and k at 16 key heads and v at 32
    # value heads of 128 in bfloat16, chunks of 64, a head's decay rate drawn
    # as the model's initialisation draws it — against the lax.scan path in
    # float32: what is local to a chunk made inside the two kernels
    from apex_tpu.ops.gated_delta import gated_delta_rule

    hk_d, hv_d = s // 64, s // 32
    l2 = lambda t: t * jax.lax.rsqrt(jnp.sum(t * t, -1, keepdims=True) + 1e-6)

    def delta_inputs(kq, kk, kv, kg):
        ka, kb, kr, kc = jax.random.split(kg, 4)
        rate = jnp.exp(jax.random.uniform(
            kr, (hv_d,), f32, jnp.log(1e-4), jnp.log(16.0)))
        return ((l2(normal(kq, (1, 8 * s, hk_d, 128), f32)) * 128 ** -0.5
                 ).astype(bf16),
                l2(normal(kk, (1, 8 * s, hk_d, 128), f32)).astype(bf16),
                normal(kv, (1, 8 * s, hv_d, 128), f32).astype(bf16),
                -rate * jax.nn.softplus(normal(ka, (1, 8 * s, hv_d), f32) + 1.0),
                jax.nn.sigmoid(2.0 * normal(kb, (1, 8 * s, hv_d), f32)),
                normal(kc, (1, 8 * s, hv_d, 128), f32))

    qd, kd, vd, gd_, bd, w_delta = seeded(delta_inputs)

    def delta_loss(use_pallas):
        def loss(q, k, v, g, beta, w):
            out = gated_delta_rule(q, k, v, g, beta, use_pallas=use_pallas)
            return jnp.sum(out.astype(f32) * w), out
        return loss

    run("gated_delta", delta_loss(None), delta_loss(False),
        (qd, kd, vd, gd_, bd, w_delta),
        tuple(t.astype(f32) for t in (qd, kd, vd)) + (gd_, bd, w_delta), 2,
        (2e-2, (("dq", 2e-2), ("dk", 2e-2), ("dv", 2e-2), ("dg", 2e-2),
                ("dbeta", 2e-2))))

    # the flash kernels on the fourth decoder block's call
    # (models/deepseek_v3.py, moonlight.train-8k): sixteen heads over eight
    # contexts, queries and keys 192 wide (1.5 lane tiles) against values 128
    # wide, causal — o and dv at the values' width, nothing padded.  The
    # reference takes a head at a time (sixteen heads' float32 scores at once
    # would be 4.3 GB, and as much again for each of their gradients)
    hl = s // 64
    ql, kl, vl, w_lat = seeded(lambda *ks: [
        (normal(ki, (1, hl, sw, width), f32) * scale).astype(dt)
        for ki, width, scale, dt in zip(ks, (192, 192, 128, 128),
                                        (0.3, 0.3, 0.3, 1.0),
                                        (bf16, bf16, bf16, f32))
    ])

    def latent_loss(q, k, v, w):
        out = flash_attention(q, k, v, causal=True)
        return jnp.sum(out.astype(f32) * w), out

    def latent_ref_loss(q, k, v, w):
        head = jax.checkpoint(lambda t: attention_ref(
            *(x[None, None] for x in t), causal=True)[0, 0])
        out = jax.lax.map(head, (q[0], k[0], v[0]))[None]
        return jnp.sum(out * w), out

    run("flash_latent", latent_loss, latent_ref_loss, (ql, kl, vl, w_lat),
        tuple(t.astype(f32) for t in (ql, kl, vl)) + (w_lat,), 2,
        (3e-2, (("dq", 3e-2), ("dk", 3e-2), ("dv", 3e-2))))

    # the short convolution in front of the rule as qwen3-next.train-8k calls
    # it (ops/gated_delta.py::split_conv_qkvz): in_proj_qkvz's output of one
    # row of 8 contexts, laid out per key head [q 128 | k 128 | v 256 | z
    # 256], read where it lies — against the XLA form on the same values in
    # float32
    from apex_tpu.ops.gated_delta import split_conv_qkvz

    xc, wc, w_conv = seeded(lambda kx, kw, kc, _: (
        normal(kx, (1, 8 * s, hk_d * 768), f32).astype(bf16),
        0.5 * normal(kw, (hk_d * 512, 4), f32),
        normal(kc, (1, 8 * s, hk_d * 768), f32).astype(bf16)))
    # a cotangent bfloat16 holds, so that both sides see the same dy — cast
    # back OUTSIDE the program that drew it: inside one fusion the chip's
    # compiler keeps a bfloat16 round trip in float32 and rounds nothing
    w_conv = w_conv.astype(f32)

    def conv_loss(use_pallas):
        def loss(x, w, cot):
            out = jnp.concatenate(split_conv_qkvz(
                x, w, key_heads=hk_d, key_dim=128, value_dim=128,
                use_pallas=use_pallas), axis=-1)
            return jnp.sum(out.astype(f32) * cot), out
        return loss

    run("conv1d", conv_loss(None), conv_loss(False), (xc, wc, w_conv),
        (xc.astype(f32), wc, w_conv), 2, (1e-2, (("dx", 1e-2), ("dw", 1e-3))))

    # flash attention's backward at the sparse cells' calls, both routes, and
    # one layer's call of every cell with what its kernels are named
    facts["flash_backward"] = flash_backward_routes(s, root_key, parity)
    facts["flash_stats_at_cell"] = flash_stats_at_cell(
        s, root_key, parity, facts["flash_backward"])

    # the gated short convolution at lfm2.train-16k's call — a row of 16
    # contexts of a convolution layer's in_proj output, [B | C | X] three
    # times 2 * ctx wide in bfloat16, 3 taps: the two kernels against the
    # jax.numpy form, forward and both gradients held to it, us a call of
    # the forward and of the gradient program on each path
    from apex_tpu.ops.gated_conv import gated_short_conv

    shape_c = (1, 16 * s, 3 * 2 * s)
    make_c = lambda kx, kw_, kc: (
        normal(kx, shape_c, f32).astype(bf16), 0.5 * normal(kw_, (2 * s, 3), f32),
        normal(kc, (1, 16 * s, 2 * s), f32).astype(bf16))
    xg, wg, cot_c = jax.jit(lambda key: make_c(*jax.random.split(key, 3)))(
        jax.random.fold_in(root_key, 140))
    cot_c = cot_c.astype(f32)       # a cotangent bfloat16 holds, as conv1d's

    def gated_loss(use_pallas):
        def loss(x, w, cot):
            out = gated_short_conv(x, w, use_pallas=use_pallas)
            return jnp.sum(out.astype(f32) * cot), out
        return loss

    run("gated_conv", gated_loss(None), gated_loss(False), (xg, wg, cot_c),
        (xg.astype(f32), wg, cot_c), 2, (1e-2, (("dx", 1e-2), ("dw", 1e-3))))
    timed_c = facts["gated_conv_at_cell"] = {"shape": [*shape_c, 3]}
    for side, use_pallas in (("kernels", None), ("jnp", False)):
        fwd = jax.jit(lambda x, w: gated_short_conv(x, w, use_pallas=use_pallas))
        grad = jax.jit(jax.grad(
            lambda *a: gated_loss(use_pallas)(*a)[0], (0, 1)))
        timed_c[f"fwd_{side}_us"] = _us_a_call(fwd, (xg, wg))
        timed_c[f"grad_{side}_us"] = _us_a_call(grad, (xg, wg, cot_c))

    # the state-space scan at granite-h.train-8k's call, its two kernels
    # against the token recurrence
    facts["ssd_at_cell"] = ssd_at_cell(s, root_key, parity, calls)

    # the delta rule with a decay a key channel at kimi-linear.train-8k's
    # call, its two kernels against the token recurrence and the scan path
    facts["kda_at_cell"] = kda_at_cell(s, root_key, parity, calls)

    # the convolution in front of that scan, read out of in_proj's output
    facts["ssm_conv_at_cell"] = ssm_conv_at_cell(s, root_key, parity, calls)

    # one block of that cell under full_block: its gradient's products of
    # gate_up's size counted in the program, timed and traced
    facts["dense_ffn_at_cell"] = dense_ffn_at_cell(s, root_key, calls)

    # q, k and v from the projection's output to heads-major, normed and
    # rotated on the way, at Trinity's and SmallThinker's attention calls
    facts["qk_heads_at_cell"] = qk_heads_at_cell(s, root_key, parity, calls)

    # the expert layer's row movement at smallthinker.train-16k's shape —
    # twice the LayerNorm rows x 2560 bfloat16 (a record 20 sublanes: two and
    # a half (8, 128) tiles, laid on 24), 6 slots a token, 8 of 64 experts
    # held, routed by seeded scores — kernels against the jnp.take path:
    # forward and gradients held to each other, us a call of each
    rows_m, d_m, k_m, held_m = 2 * rows, 20 * 128, 6, (0, 8)
    _require(moe_rows.supported(rows_m, k_m, d_m, tile, bf16),
             "moe_rows.supported at the 16k cell's shape")
    cap_r = gmm.rows_capacity(rows_m * k_m, held_m[1], tile)
    make_r = lambda kx, kw_, kc, kr: (
        (normal(kx, (rows_m, d_m), f32) * 0.5).astype(bf16),
        jax.random.uniform(kw_, (rows_m, k_m), f32),
        normal(kc, (rows_m + cap_r, d_m), f32),
        moe._route(jax.lax.top_k(normal(kr, (rows_m, 64), f32), k_m)[1],
                   held_m, cap_r, tile,
                   moe_rows.combine_block(rows_m, k_m, d_m)))
    xr, wr, cots, routing_r = jax.jit(
        lambda key: make_r(*jax.random.split(key, 4)))(
            jax.random.fold_in(root_key, 120))
    cot_t, cot_g = cots[:rows_m], cots[rows_m:]     # of the tokens, of the rows
    # the kernels leave the tiles past the live ones undefined: compared as 0
    live = live_rows(routing_r.layout)
    rows_r = jax.jit(lambda x: jnp.where(
        live, moe._rows_from_tokens(x, routing_r, None), 0))(xr)

    def rows_fns(tile_rows):
        gather = lambda x: jnp.where(
            live, moe._rows_from_tokens(x, routing_r, tile_rows), 0)
        combine = lambda r, w: moe._tokens_from_rows(r, w, routing_r, tile_rows)
        return {
            "gather": (gather, (xr,)),
            "gather_grad": (jax.grad(lambda x: jnp.sum(
                gather(x).astype(f32) * cot_g)), (xr,)),
            "combine": (combine, (rows_r, wr)),
            "combine_grad": (jax.grad(lambda r, w: jnp.sum(
                combine(r, w) * cot_t), (0, 1)), (rows_r, wr)),
        }

    timed = facts["moe_rows_at_2560"] = {
        "shape": [rows_m, k_m, d_m, held_m[1], 64], "rows_capacity": cap_r,
        "rows_live": int(jnp.sum(routing_r.row_slot < rows_m * k_m)),
        "mosaic_calls": {}}
    outs = {}
    for side, tile_rows in (("kernels", tile), ("take", None)):
        for name, (fn, args) in rows_fns(tile_rows).items():
            compiled = jax.jit(fn).lower(*args).compile()
            if side == "kernels":
                _require_mosaic(compiled, 1, timed["mosaic_calls"], name)
            timed[f"{name}_{side}_us"] = _us_a_call(compiled, args, n=3)
            outs[side, name] = jax.tree_util.tree_leaves(compiled(*args))
    for (side, name), got in outs.items():
        if side != "kernels":
            continue
        for j, (g, o) in enumerate(zip(got, outs["take", name])):
            if name == "combine_grad" and j == 0:      # d_rows: live tiles
                g, o = (jnp.where(live, t, 0) for t in (g, o))
            _compare(f"moe_rows_at_2560.{name}.{j}", g, o, 1e-4, parity)

    # the grouped product at moonlight.train-8k's two calls — 8 experts held,
    # 6 slots a token over 8 contexts, gate|up 2048 x 2816 and down 1408 x
    # 2048 — in the buffer the expert layer sizes for the worst case (6 * s
    # rows live in 28 tiles of 200) and in one that ends with the last group
    # (the same 28 tiles, all live): forward, dx and dw each a program of its
    # own, us a call, held to ragged_dot on the live tiles.  The dead tiles of
    # x and of the cotangent hold NaN: whatever read them would show
    sizes_w = [0, 1] + [6 * s * part // 6144 for part in
                        (700, 900, 768, 1300, 1200, 1275)]
    tiles_w = sum(max(1, -(-z // tile)) for z in sizes_w)
    timed_g = facts["grouped_mm_at_cell"] = {
        "sizes": sizes_w, "tiles_live": tiles_w, "mosaic_calls": {}}

    def grouped_passes(use_pallas):
        def mm(x, w, lay):
            return gmm.grouped_matmul(x, w, lay, use_pallas=use_pallas)
        return {"fwd": lambda x, w, lay, g: mm(x, w, lay),
                "dx": lambda x, w, lay, g: jax.vjp(
                    lambda x: mm(x, w, lay), x)[1](g)[0],
                "dw": lambda x, w, lay, g: jax.vjp(
                    lambda w: mm(x, w, lay), w)[1](g)[0]}

    for i, (product, c_g, n_g) in enumerate((("gate_up", 2 * s, 11 * s // 4),
                                             ("down", 11 * s // 8, 2 * s))):
        for buffer, cap_g in (
                ("worst_case", gmm.rows_capacity(6 * 8 * s, len(sizes_w))),
                ("exact", tiles_w * tile)):
            lay_g = jax.jit(lambda z: gmm.group_layout(z, cap_g))(
                jnp.asarray(sizes_w, jnp.int32))
            live = live_rows(lay_g)
            xg_, wg_, gg_ = jax.jit(lambda key: [
                jnp.where(keep, normal(ki, shape, f32) * scale,
                          jnp.nan).astype(bf16)
                for ki, shape, scale, keep in zip(
                    jax.random.split(key, 3),
                    ((cap_g, c_g), (len(sizes_w), c_g, n_g), (cap_g, n_g)),
                    (0.5, 0.05, 1.0), (live, True, live))
            ])(jax.random.fold_in(root_key, 130 + i))
            args_g = (xg_, wg_, lay_g, gg_)
            with jax.default_matmul_precision("highest"):
                refs = jax.jit(lambda *a: {
                    name: fn(*a) for name, fn in grouped_passes(False).items()
                })(xg_.astype(f32), wg_.astype(f32), lay_g, gg_.astype(f32))
            rec = timed_g[f"{product}.{buffer}"] = {
                "shape": [cap_g, c_g, n_g], "tiles": cap_g // tile}
            for name, fn in grouped_passes(None).items():
                compiled = jax.jit(fn).lower(*args_g).compile()
                key_g = f"{product}.{buffer}.{name}"
                _require_mosaic(compiled, 1, timed_g["mosaic_calls"], key_g)
                names = mosaic_call_names(compiled.as_text())
                _require(len(names) <= 1,
                         f"{key_g}: timed with another pass: {names}")
                rec[f"{name}_us"] = _us_a_call(compiled, args_g)
                got = compiled(*args_g)
                if name != "dw":
                    got, refs[name] = (jnp.where(live, t, 0)
                                       for t in (got, refs[name]))
                _compare(f"grouped_mm_at_cell.{key_g}", got, refs[name],
                         2e-2, parity)

    facts["moe_plan_at_cell"] = moe_plan_at_cell(s, root_key, parity)
    facts["max_err"] = max(p["max_err"] for p in parity.values())



# ---------------------------------------------------------------------------
# phase: train
# ---------------------------------------------------------------------------

def phase_train(sizes: Sizes, seed: int, facts: Dict, handoff: Dict) -> None:
    """Three fused-driver windows of AMP O2 + fused_adam on a fixed
    seeded batch; leaves the trained masters and config in ``handoff``
    for the serve phase."""
    from apex_tpu.analysis import CompileMonitor
    from apex_tpu.ops.layer_norm import fused_dgamma_active
    from apex_tpu.train import FusedTrainDriver, read_metrics

    step, carry, _, cfg, amp_ = _train_setup(
        sizes, seed, "O2", batch=sizes.train_batch, dropout=True
    )
    driver = FusedTrainDriver(
        step, steps_per_dispatch=sizes.steps_per_dispatch,
        metrics={"loss": "mean"},
    )
    losses: List[float] = []
    window_s: List[float] = []
    facts.update(
        model={"layers": cfg.num_layers, "hidden": cfg.hidden_size,
               "heads": cfg.num_heads, "ctx": cfg.max_position,
               "vocab": cfg.vocab_size},
        batch=sizes.train_batch, steps_per_dispatch=sizes.steps_per_dispatch,
        windows=sizes.windows, ln_fused_dgamma=fused_dgamma_active(),
        loss_per_window=losses, warm_window_s=window_s,
    )
    # what is compiled into the window: flash, LN and xentropy all engaged
    _require_mosaic(driver.lower(carry).compile(),
                    _train_calls_expected(cfg, "O2"), facts, "mosaic_calls")
    _require(fused_dgamma_active(), "LN dgamma/dbeta epilogue is switched off")

    def window(carry):
        t0 = time.perf_counter()
        carry, res = driver.run_window(carry)
        loss = read_metrics(res.metrics)["loss"]     # the host fetch
        losses.append(round(float(loss), 4))
        return carry, round(time.perf_counter() - t0, 3)

    carry, _ = window(carry)         # runs the executable compiled above
    with CompileMonitor() as mon:
        for _ in range(sizes.windows - 1):
            carry, dt = window(carry)
            window_s.append(dt)
    facts["compiles_after_first_window"] = mon.compiles
    _require(mon.compiles == 0,
             f"train: {mon.compiles} compile(s) after the first window")
    _require(all(np.isfinite(losses)), f"train: non-finite loss {losses}")
    _require(losses[-1] < losses[0],
             f"train: loss did not fall over {sizes.windows} windows: {losses}")
    params = carry[0]
    dtypes = {str(leaf.dtype) for leaf in jax.tree_util.tree_leaves(params)}
    _require(dtypes == {"float32"}, f"train: master params are {dtypes}")
    handoff.update(params=params, cfg=cfg, policy=amp_.policy)


# ---------------------------------------------------------------------------
# phase: serve
# ---------------------------------------------------------------------------

def _prompts(vocab: int, seed: int, lens: Sequence[int], prefix_len: int = 0,
             prefix_tails: Sequence[int] = ()) -> List[List[int]]:
    """Seeded prompts: one per length in ``lens``, then one per tail in
    ``prefix_tails`` that all share one ``prefix_len``-token prefix."""
    rng = np.random.RandomState(seed + 2)
    draw = lambda n: [int(t) for t in rng.randint(0, vocab, size=(n,))]
    prompts = [draw(n) for n in lens]
    prefix = draw(prefix_len)
    return prompts + [prefix + draw(n) for n in prefix_tails]


def _drain(decoder, sizes: Sizes, prompts: Sequence[Sequence[int]],
           new_tokens: int, late: int = 0):
    """A fresh engine on ``decoder`` (programs are cached per decoder),
    the engine's own defaults, driven by submit/step/run to drained.
    The last ``late`` prompts arrive late: submitted once the request
    before them has its first token, i.e. has prefilled and published
    its prompt pages — a prefix is shared with a request that is being
    served, not with one that is still queued."""
    from apex_tpu.serve import ServeEngine

    engine = ServeEngine(decoder, slots=sizes.slots, max_len=sizes.ctx)
    submit = lambda p: engine.submit(p, max_new_tokens=new_tokens)
    early = len(prompts) - late
    uids = [submit(p) for p in prompts[:early]]
    if late:
        while not engine.progress()[uids[-1]][0]:
            engine.step()
        uids += [submit(p) for p in prompts[early:]]
    out = engine.run()
    return engine, [out[u] for u in uids]


def _reference_logits(decoder) -> Callable[[Sequence[int]], np.ndarray]:
    """``seq -> (len(seq), V)`` fp32 logits of the full-recompute forward
    of ``decoder``'s model (the training forward, no cache — what
    ``reference_generate`` runs per token), padded to a power-of-two
    width so a few programs serve every length."""
    forward = jax.jit(lambda p, x: decoder.model.apply({"params": p}, x))

    def logits(seq: Sequence[int]) -> np.ndarray:
        width = 8
        while width < len(seq):
            width *= 2
        ids = np.zeros((1, min(width, decoder.cfg.max_position)), np.int32)
        ids[0, :len(seq)] = seq
        return np.asarray(forward(decoder.params, ids),
                          np.float32)[0, :len(seq)]

    return logits


def _check_greedy(reference_logits, prompt, served, expected,
                  record: List[Dict]) -> None:
    """Served greedy tokens vs the expected ones: identical, or — where
    bf16 flipped a near-tie — every served token within
    :data:`LOGIT_TIE_TOL` of the full-recompute reference's own maximum
    at that position (``prompt + served`` teacher-forced; the margin is
    0.0 where the reference picks the same token).  The comparison is
    never dropped, only restated: the entry appended to ``record`` says
    which of the two held."""
    rows = reference_logits(list(prompt) + list(served))
    rows = rows[len(prompt) - 1:-1]               # row i predicts served[i]
    picked = rows[np.arange(len(served)), np.asarray(served)]
    margin = float(np.max(rows.max(axis=-1) - picked))
    identical = list(served) == list(expected)
    record.append({"prompt_len": len(prompt), "tokens_identical": identical,
                   "logit_margin": margin, "tol": LOGIT_TIE_TOL})
    _require(identical or margin <= LOGIT_TIE_TOL,
             f"request {len(record) - 1}: tokens differ from the expected "
             f"ones and the served choice is {margin:.4f} below the "
             f"reference's maximum logit (tol {LOGIT_TIE_TOL})")


def _serve_programs_calls(decoder, engine, expected: int,
                          calls: Dict[str, int]) -> None:
    """Mosaic calls compiled into the two serve programs the drain ran —
    one prefill chunk (the widest bucket) and the decode window — from
    the engine's own cache and page tables (lowering consumes nothing)."""
    slots = engine.cache.slots
    chunk = decoder.lower_prefill_chunk(
        engine.cache, engine.pool.tables[:1], np.zeros((1,), np.int32),
        np.zeros((1, engine.prefill_chunk), np.int32),
        np.zeros((1,), np.int32), np.ones((1,), np.int32),
    ).compile()
    _require_mosaic(chunk, expected, calls, "prefill_chunk")
    window = decoder.lower_paged_window(
        engine.cache, engine.pool.tables, np.zeros((slots,), np.int32),
        np.ones((slots,), bool), jax.random.PRNGKey(0),
    ).compile()
    _require_mosaic(window, expected, calls, "decode_window")


def phase_serve(sizes: Sizes, seed: int, facts: Dict, handoff: Dict) -> None:
    """The trained params through GPTDecoder + ServeEngine: a warm-up
    drain compiles every shape the traffic uses, a second drain of the
    same traffic must compile nothing, and the short requests are held
    to ``reference_generate``."""
    from apex_tpu.analysis import CompileMonitor
    from apex_tpu.serve import GPTDecoder, reference_generate

    cfg, params = handoff["cfg"], handoff["params"]
    decoder = GPTDecoder(cfg, params, policy=handoff["policy"])
    prompts = _prompts(cfg.vocab_size, seed, sizes.prompt_lens,
                       sizes.prefix_len, sizes.prefix_tails)
    facts.update(requests=len(prompts), prompt_lens=[len(p) for p in prompts])

    t0 = time.perf_counter()
    _drain(decoder, sizes, prompts, sizes.new_tokens, late=1)  # warm-up
    facts["warmup_drain_s"] = round(time.perf_counter() - t0, 3)
    with CompileMonitor() as mon:
        t0 = time.perf_counter()
        engine, served = _drain(decoder, sizes, prompts, sizes.new_tokens,
                                late=1)
        facts["warm_drain_s"] = round(time.perf_counter() - t0, 3)
    stats = engine.stats()
    facts.update(
        compiles_after_warmup=mon.compiles,
        tokens=sum(len(t) for t in served),
        **{k: stats[k] for k in (
            "kv_dtype", "page_len", "tokens_per_dispatch",
            "prefill_dispatches", "decode_dispatches", "prefix_hit_tokens")},
    )
    _require(mon.compiles == 0,
             f"serve: {mon.compiles} compile(s) after warm-up of the same "
             "shapes")
    _require(all(len(t) == sizes.new_tokens for t in served),
             f"serve: token counts {[len(t) for t in served]}, expected "
             f"{sizes.new_tokens} each")
    _require(stats["prefix_hit_tokens"] > 0,
             "serve: the shared-prefix pair reused no cached page")

    # fused LN at least (models/gpt.py: two per layer + the final one)
    calls = facts["mosaic_calls"] = {}
    _serve_programs_calls(decoder, engine, 2 * cfg.num_layers + 1, calls)

    compared = facts["reference"] = []
    reference_logits = _reference_logits(decoder)
    for prompt, tokens in zip(prompts[:sizes.compared], served):
        expected = reference_generate(cfg, params, prompt, sizes.new_tokens)
        _check_greedy(reference_logits, prompt, tokens, expected, compared)
    facts["max_err"] = max(c["logit_margin"] for c in compared)


# ---------------------------------------------------------------------------
# --chips 4: the path across chips, and what it is compared with
# ---------------------------------------------------------------------------

def _require_spread(tree, n: int, record: Dict, key: str) -> None:
    """Every array leaf of ``tree`` really sits on ``n`` distinct devices
    (the smallest device set of any leaf goes to ``record[key]``)."""
    record[key] = got = min(
        len(leaf.sharding.device_set)
        for leaf in jax.tree_util.tree_leaves(tree)
    )
    _require(got == n, f"{key}: a leaf sits on {got} device(s), not {n}")


def phase_dp(sizes: Sizes, seed: int, facts: Dict, chips: int) -> None:
    """Data-parallel training across the chips vs one chip: the same two
    fused-driver windows of the same global batch, on a ``chips``-device
    ``data`` mesh through ``FusedTrainDriver(mesh=...)`` with the DDP
    allreduce, and on one device.

    Compared the way ``__graft_entry__``'s dry run compares — at fp32
    (O0), dropout off: the graph has the identical collective structure
    as O2, and its tiers (loss rtol 1e-5; params rtol 1e-3, atol 3e-4)
    only mean something where the two sides differ by summation order
    alone.  On the chip that also needs ``highest`` matmul precision: at
    the default, fp32 operands are rounded to bf16 at every matmul, a
    last-bit difference upstream flips such a rounding by a whole bf16
    ulp, and Adam's normalised update turns a gradient that changed
    sign into a +-lr step (first chip run of this phase, default
    precision: per-step losses within 1e-5, yet 4.9e-4 of the params
    outside the tier; at ``highest``: none of 124.5M, losses within
    2e-7).  The per-step loss is the sharp instrument — a mis-reduced
    gradient moves the next step's loss by far more than 1e-5; the
    params are held to the tier on all but 1e-5 of the elements (a ~0
    gradient may still change sign), and the whole update to a relative
    L2 error."""
    from apex_tpu.parallel import DistributedDataParallel, replicate
    from apex_tpu.parallel.mesh import data_parallel_mesh
    from apex_tpu.train import FusedTrainDriver

    k, windows = sizes.dp_steps_per_dispatch, 2
    calls = facts["mosaic_calls"] = {}
    facts.update(opt_level="O0", matmul_precision="highest",
                 global_batch=sizes.dp_batch, steps=k * windows)

    def run(mesh, tag):
        ddp = None if mesh is None else DistributedDataParallel(
            axis_name="data", allreduce_always_fp32=True
        )
        step, carry, (ids, labels), cfg, _ = _train_setup(
            sizes, seed, "O0", batch=sizes.dp_batch, dropout=False, ddp=ddp
        )
        start = jax.tree_util.tree_map(np.asarray, carry[0])
        window = tuple(np.broadcast_to(t, (k,) + t.shape)
                       for t in (ids, labels))
        if mesh is not None:
            carry = replicate(carry, mesh)
        driver = FusedTrainDriver(
            step, steps_per_dispatch=k, mesh=mesh, check_vma=False,
            metrics={"loss": "mean"}, per_step=("loss",),
        )
        _require_mosaic(driver.lower(carry, window).compile(),
                        _train_calls_expected(cfg, "O0"), calls, tag)
        losses = facts[f"loss_per_step_{tag}"] = []
        for _ in range(windows):
            carry, res = driver.run_window(carry, window)
            losses += [float(x) for x in np.asarray(res.per_step["loss"])]
        if mesh is not None:
            _require_spread(carry, chips, facts, "devices_holding_carry")
        return jax.tree_util.tree_map(np.asarray, carry[0]), losses, start

    with jax.default_matmul_precision("highest"):
        params_n, losses_n, start = run(data_parallel_mesh(chips), "mesh")
        params_1, losses_1, _ = run(None, "one_chip")

    loss_rel = max(abs(a - b) / abs(b) for a, b in zip(losses_n, losses_1))
    outside = total = 0
    max_abs = diff_sq = upd_sq = 0.0
    for a, b, p0 in zip(*(jax.tree_util.tree_leaves(t)
                          for t in (params_n, params_1, start))):
        d = np.abs(a - b)
        outside += int(np.sum(d > 3e-4 + 1e-3 * np.abs(b)))
        total += d.size
        max_abs = max(max_abs, float(d.max()))
        diff_sq += float(np.sum(np.square(d, dtype=np.float64)))
        upd_sq += float(np.sum(np.square(b - p0, dtype=np.float64)))
    frac = outside / total
    upd_rel = (diff_sq / upd_sq) ** 0.5
    facts.update(
        loss_max_rel_diff=loss_rel, params_max_abs_diff=max_abs,
        params_outside_tier=outside, params_total=total,
        update_rel_l2_diff=upd_rel, max_err=loss_rel,
    )
    _require(all(np.isfinite(losses_n + losses_1)), "dp: non-finite loss")
    _require(loss_rel <= 1e-5,
             f"dp: per-step losses differ by rel {loss_rel:.2e} > 1e-5")
    _require(frac <= 1e-5,
             f"dp: {outside} of {total} updated params outside rtol 1e-3 / "
             f"atol 3e-4 (fraction {frac:.2e} > 1e-5)")
    _require(upd_rel <= 1e-3,
             f"dp: the {chips}-chip update differs from the 1-chip update "
             f"by relative L2 {upd_rel:.3e} > 1e-3")


def phase_tp(sizes: Sizes, seed: int, facts: Dict, chips: int) -> None:
    """ServeEngine tensor-parallel over the chips (heads and KV pool
    sharded, ``serve/sharding.py``) vs TP = 1: identical greedy tokens
    (or, per :func:`_check_greedy`, reference-tied ones)."""
    import apex_tpu.amp as amp
    from apex_tpu.models.gpt import GPTLM
    from apex_tpu.serve import GPTDecoder
    from apex_tpu.serve.sharding import serve_mesh

    policy = amp.initialize("O2").policy
    cfg = _gpt_config(sizes, compute_dtype=policy.compute_dtype)
    params = _init_params(GPTLM(cfg), seed, np.zeros((1, 8), np.int32))
    prompts = _prompts(cfg.vocab_size, seed, sizes.tp_prompt_lens)
    facts.update(tp=chips, heads_per_chip=cfg.num_heads // chips,
                 requests=len(prompts))

    sharded = GPTDecoder(cfg, params, policy=policy, mesh=serve_mesh(chips))
    engine, tokens_n = _drain(sharded, sizes, prompts, sizes.tp_new_tokens)
    pool = (engine.cache.k, engine.cache.v)
    _require_spread(pool, chips, facts, "devices_holding_kv_pool")
    heads = {x.addressable_shards[0].data.shape[2] for x in pool}
    _require(heads == {cfg.num_heads // chips},
             f"tp: {heads} heads per chip, expected {cfg.num_heads // chips}")
    del engine, pool

    single = GPTDecoder(cfg, params, policy=policy)
    _, tokens_1 = _drain(single, sizes, prompts, sizes.tp_new_tokens)
    facts["tokens"] = sum(map(len, tokens_n))
    compared = facts["compared"] = []
    reference_logits = _reference_logits(single)
    for prompt, tn, t1 in zip(prompts, tokens_n, tokens_1):
        _check_greedy(reference_logits, prompt, tn, t1, compared)
    facts.update(
        tokens_identical=all(c["tokens_identical"] for c in compared),
        max_err=max(c["logit_margin"] for c in compared),
    )


# ---------------------------------------------------------------------------

def main(argv=None, sizes: Sizes = FULL) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seeds the weights, the batch and the prompts")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run ONLY the data-parallel and tensor-parallel "
                         "comparisons across four chips")
    args = ap.parse_args(argv)

    device = require_tpu()      # exits nonzero unless JAX reports a TPU
    if device["count"] < args.chips:
        raise SystemExit(
            f"--chips {args.chips} needs {args.chips} devices; JAX reports "
            f"{device['count']}"
        )
    cache_dir = compile_cache_dir(HERE)
    # Cache every program, however quick its compile.  JAX's default keeps
    # out whatever compiled in under a second — on the chip that is most
    # of a run's programs (the engine's eager sampling ops, ~0.1 s each),
    # and together a quarter of a cold run's compile time.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = _Meter(cache_dir)
    try:
        if args.chips == 1:
            handoff: Dict = {}
            meter.phase("kernels",
                        lambda f: phase_kernels(sizes, args.seed, f))
            meter.phase("train",
                        lambda f: phase_train(sizes, args.seed, f, handoff))
            meter.phase("serve",
                        lambda f: phase_serve(sizes, args.seed, f, handoff))
        else:
            meter.phase("dp",
                        lambda f: phase_dp(sizes, args.seed, f, args.chips))
            meter.phase("tp",
                        lambda f: phase_tp(sizes, args.seed, f, args.chips))
    finally:
        meter.active = False
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
