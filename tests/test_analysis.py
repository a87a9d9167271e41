"""Graph-sanitizer suite (ISSUE 4): each sanitizer must PASS the
canonical programs and CATCH a seeded violation.

The four sanitizers (apex_tpu.analysis) prove Apex's invariants
hardware-free: precision lint on the traced jaxpr, donation aliasing on
the compiled executable, declarative collective budgets on the lowered
StableHLO, recompile/transfer detection on live dispatch.  The
canonical programs come from the session-scoped ``canonical`` fixture
shared with tests/test_inspect_hlo.py (one lowering per program per
session); seeded violations are tiny purpose-built programs — jnp
itself upcasts half reductions, so every seed uses the lax-level form
a real regression would take.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import apex_tpu.amp as amp
from apex_tpu import analysis
from apex_tpu.analysis import (
    CollectiveBudget,
    CompileMonitor,
    DonationError,
    PrecisionError,
    RecompileError,
    TransferError,
    UseAfterDonateError,
)
from apex_tpu.parallel.mesh import shard_map_compat
from tools import lint_graphs


# ---------------------------------------------------------------------------
# precision lint
# ---------------------------------------------------------------------------

class TestPrecisionLint:
    def test_seeded_bf16_loss_reduction(self):
        """A loss accumulated in bf16 (lax-level scalar reduce — the
        form jnp's internal f32 upcast cannot produce)."""
        def bad_loss(x):
            return jax.lax.reduce(
                x.astype(jnp.bfloat16), jnp.bfloat16(0.0),
                jax.lax.add, (0, 1),
            )

        vs = analysis.lint_fn(bad_loss, jnp.ones((32, 32)))
        assert [v.rule for v in vs] == ["half-loss-reduction"]
        with pytest.raises(PrecisionError):
            analysis.assert_precision(vs, "seeded loss")

    def test_batch_axis_bf16_grad_sum_is_allowed(self):
        """Non-scalar bf16 sums (bias-grad over batch — standard O2,
        half grads match the reference) must NOT fire."""
        def grad_sum(g):
            return jnp.sum(g.astype(jnp.bfloat16), axis=0)

        # force a lax-level half reduction with a non-scalar output
        def lax_sum(g):
            return jax.lax.reduce(
                g.astype(jnp.bfloat16), jnp.bfloat16(0.0),
                jax.lax.add, (0,),
            )

        assert analysis.lint_fn(grad_sum, jnp.ones((8, 32))) == []
        assert analysis.lint_fn(lax_sum, jnp.ones((8, 32))) == []

    def test_seeded_bf16_softmax(self):
        vs = analysis.lint_fn(jax.nn.softmax, jnp.ones((8, 8), jnp.bfloat16))
        assert "half-softmax" in [v.rule for v in vs]

    def test_seeded_bf16_norm_stats(self):
        def bad_rms(x):
            var = jnp.mean(jnp.square(x), axis=-1, dtype=jnp.float32)
            return x * jax.lax.rsqrt(var.astype(jnp.bfloat16) + 1)[..., None]

        vs = analysis.lint_fn(bad_rms, jnp.ones((4, 16), jnp.bfloat16))
        assert "half-norm-stats" in [v.rule for v in vs]

    def test_seeded_bf16_psum(self, mesh8):
        """A cross-replica gradient accumulation in bf16 — the rule
        DistributedDataParallel(allreduce_always_fp32=True) encodes."""
        def leaky(g):
            return jax.lax.psum(g.astype(jnp.bfloat16), "data")

        sm = shard_map_compat(leaky, mesh=mesh8, in_specs=(P("data"),),
                              out_specs=P("data"), check_vma=False)
        vs = analysis.lint_fn(sm, jnp.ones((8, 256)))
        assert [v.rule for v in vs] == ["half-psum"]
        # scalar housekeeping psums pass under a bytes floor
        assert analysis.lint_fn(sm, jnp.ones((8, 256)),
                                min_psum_bytes=1024) == []

    def test_seeded_master_downcast(self):
        """The optimizer narrowing its own fp32 master state under O2
        — caught at the carry level by lint_step."""
        policy = amp.make_policy("O2")

        def bad_step(carry, batch):
            masters = carry["masters"]
            new = jax.tree_util.tree_map(
                lambda m: (m * 0.9).astype(jnp.bfloat16), masters
            )
            return {"masters": new}, {"loss": jnp.float32(0.0)}

        carry = {"masters": {"w": jnp.ones((4, 4), jnp.float32)}}
        vs = analysis.lint_step(bad_step, carry, None, policy=policy)
        assert [v.rule for v in vs] == ["master-downcast"]
        assert "masters" in vs[0].message

    def test_master_downcast_skipped_under_o3(self):
        """O3 opts out of master weights explicitly — intentional
        all-half training must not fire the carry rule."""
        policy = amp.make_policy("O3")

        def narrowing_step(carry, batch):
            return jax.tree_util.tree_map(
                lambda m: m.astype(jnp.bfloat16), carry
            ), {"loss": jnp.float32(0.0)}

        carry = {"w": jnp.ones((4, 4), jnp.float32)}
        assert analysis.lint_step(narrowing_step, carry, None,
                                  policy=policy) == []

    def test_canonical_window_clean(self, canonical):
        """The real O2 driver window (M=4, deferred collectives) holds
        every precision invariant the lint encodes."""
        prog = canonical.get("train_m4")
        assert analysis.lint_jaxpr(prog.jaxpr(), policy=prog.policy) == []


# ---------------------------------------------------------------------------
# donation checker
# ---------------------------------------------------------------------------

class TestDonationChecker:
    def test_canonical_carry_fully_aliased(self, canonical):
        """Every donated carry leaf of the real driver window is
        honored as an input-output alias in the compiled executable."""
        prog = canonical.get("train_m4")
        report = analysis.assert_donated(
            prog.compiled(), prog.args, prog.donate_argnums, prog.name
        )
        assert report.ok and report.exact
        assert report.expected == len(
            jax.tree_util.tree_leaves(prog.args[0])
        )

    def test_decode_cache_fully_aliased(self, canonical):
        """The serve window donates the KV cache (argnum 1); the greedy
        window drops its unused RNG key from the executable, so the
        checker's count fallback must still prove all 4 cache leaves
        aliased."""
        prog = canonical.get("decode_k8")
        report = analysis.assert_donated(
            prog.compiled(), prog.args, prog.donate_argnums, prog.name
        )
        assert report.ok
        assert report.expected == len(
            jax.tree_util.tree_leaves(prog.args[1])
        )

    def test_seeded_dropped_donate_argnums(self):
        """The bug class: a wrapper loses donate_argnums; the compiled
        executable has NO input_output_alias header and the checker
        must fail loudly instead of silently doubling HBM."""
        c, b = jnp.ones((64, 64)), jnp.ones((8,))
        fn = lambda c, b: (c + b.sum(), c.mean())  # noqa: E731
        compiled = jax.jit(fn).lower(c, b).compile()
        with pytest.raises(DonationError, match="NOT aliased"):
            analysis.assert_donated(compiled, (c, b), (0,), "dropped")
        # and the donated build of the SAME program passes
        donated = jax.jit(fn, donate_argnums=(0,)).lower(c, b).compile()
        assert analysis.assert_donated(donated, (c, b), (0,)).ok

    def test_seeded_unaliasable_leaf(self):
        """A dtype-changing output silently drops ONE leaf's donation —
        the checker pinpoints the leaf by path.  jax 0.9 says nothing:
        an output of the same element count makes the unmatched buffer
        an XLA ``buffer_donor`` instead of a warning, so the compiled
        header is the only witness."""
        tree = {"w": jnp.ones((64, 64), jnp.float32),
                "m": jnp.ones((64, 64), jnp.float32)}

        def narrowing(t):
            return {"w": t["w"] * 2, "m": t["m"].astype(jnp.bfloat16)}

        compiled = jax.jit(
            narrowing, donate_argnums=(0,)
        ).lower(tree).compile()
        report = analysis.check_donation(compiled, (tree,), (0,))
        assert not report.ok
        assert report.aliased == 1 and report.expected == 2
        # donation is buffer-pool based: XLA may satisfy any compatible
        # output from any donated buffer, so exactly ONE input buffer
        # ends up unconsumed (which one is XLA's choice)
        assert len(report.dropped) == 1

    def test_use_after_donate_guard(self):
        prog = jax.jit(lambda c: (c * 2, c.sum()), donate_argnums=(0,))
        guarded = analysis.guard_donation(prog, (0,), label="window")
        carry = jnp.arange(8.0)
        out, _ = guarded(carry)
        with pytest.raises(UseAfterDonateError, match="donated"):
            guarded(carry)  # stale tree resubmitted
        out2, _ = guarded(out)  # rebinding is the contract
        assert out2.shape == carry.shape

    def test_poison_raises_on_any_use(self):
        tree = analysis.poison({"w": jnp.ones((4,))}, label="old carry")
        with pytest.raises(UseAfterDonateError):
            jnp.asarray(tree["w"])
        with pytest.raises(UseAfterDonateError):
            jax.jit(lambda t: t["w"])(tree)
        with pytest.raises(UseAfterDonateError):
            _ = tree["w"].shape


# ---------------------------------------------------------------------------
# collective budgets
# ---------------------------------------------------------------------------

class TestCollectiveBudgets:
    def test_canonical_programs_within_budget(self, canonical):
        """Each canonical program's declared budget holds on its
        lowered text — counts, byte pins and the no-undeclared-kinds
        whitelist."""
        for name in ("train_m1", "train_m4", "train_zero_m2",
                     "decode_k8"):
            prog = canonical.get(name)
            assert analysis.check_budget(
                prog.lowered_text(), prog.budget
            ) == [], name

    def test_budget_bytes_pin(self):
        text = ('%0 = "stablehlo.all_reduce"(%a) : '
                '(tensor<16xf32>) -> tensor<16xf32>')
        ok = CollectiveBudget(counts={"all_reduce": 1},
                              bytes={"all_reduce": 64})
        assert analysis.check_budget(text, ok) == []
        bad = CollectiveBudget(counts={"all_reduce": 1},
                               bytes={"all_reduce": 128})
        [v] = analysis.check_budget(text, bad)
        assert "moves 64 B, expected 128 B" in v

    def test_undeclared_kind_is_a_violation(self):
        """Budgets are whitelists: traffic of a kind the program never
        declared is a regression even if declared kinds match."""
        text = ('%0 = "stablehlo.all_reduce"(%a) : '
                '(tensor<16xf32>) -> tensor<16xf32>\n'
                '%1 = "stablehlo.all_gather"(%b) : '
                '(tensor<4xf32>) -> tensor<16xf32>')
        [v] = analysis.check_budget(
            text, CollectiveBudget(counts={"all_reduce": 1})
        )
        assert "undeclared collective kind all_gather" in v
        with pytest.raises(analysis.BudgetError):
            analysis.assert_budget(
                text, CollectiveBudget(counts={"all_reduce": 1})
            )

    def test_total_bytes_cap(self):
        text = ('%0 = "stablehlo.all_reduce"(%a) : '
                '(tensor<1024xf32>) -> tensor<1024xf32>')
        [v] = analysis.check_budget(
            text, CollectiveBudget(counts={"all_reduce": 1},
                                   max_total_bytes=1024)
        )
        assert "exceeds cap" in v


# ---------------------------------------------------------------------------
# recompile / transfer detector
# ---------------------------------------------------------------------------

class TestRecompileDetector:
    def test_seeded_unpadded_decode_loop(self):
        """The reference_generate bug class: a per-token loop feeding a
        GROWING buffer compiles one program per length; the padded loop
        compiles once.  Inputs are pre-built so the monitor counts only
        the step's own compiles."""
        step = jax.jit(lambda ids: jnp.argmax(ids.sum(axis=-1)))
        lengths = list(range(8, 13))
        unpadded = [jnp.ones((1, n)) for n in lengths]
        padded = [jnp.ones((1, 16)) for _ in lengths]

        with CompileMonitor() as mon:
            mon.track(step, "step")
            for buf in unpadded:
                step(buf)
        assert mon.report()["step"] == len(lengths)
        with pytest.raises(RecompileError, match="pad to a fixed width"):
            mon.check(max_compiles=1, label="unpadded decode loop")

        padded_step = jax.jit(lambda ids: jnp.argmax(ids.sum(axis=-1)))
        with CompileMonitor() as mon2:
            mon2.track(padded_step, "step")
            for buf in padded:
                padded_step(buf)
        assert mon2.check(max_compiles=1, label="padded loop") <= 1
        assert mon2.report()["step"] == 1

    def test_monitor_counts_zero_on_warm_cache(self):
        f = jax.jit(lambda x: x * 2)
        x = jnp.ones((4,))
        f(x)  # warm
        with CompileMonitor() as mon:
            for _ in range(3):
                f(x)
        assert mon.compiles == 0

    def test_seeded_host_transfer(self):
        """A leftover debug callback inside a fused window is a
        synchronizing host round trip per dispatch."""
        def leaky(x):
            jax.debug.print("x={x}", x=x)
            return x * 2

        text = jax.jit(leaky).lower(jnp.ones((4,))).as_text()
        found = analysis.host_transfers(text)
        assert found and "callback" in found[0]
        with pytest.raises(TransferError, match="host transfer"):
            analysis.assert_no_host_transfers(text, "leaky window")

    def test_canonical_windows_are_transfer_free(self, canonical):
        for name in ("train_m4", "decode_k8"):
            analysis.assert_no_host_transfers(
                canonical.get(name).lowered_text(), name
            )


# ---------------------------------------------------------------------------
# compiled-program cost census (ISSUE 11)
# ---------------------------------------------------------------------------

class TestCostCensus:
    def test_canonical_summary_complete_or_flagged(self, canonical):
        """The census of a real compiled window: either every field is
        populated, or the capability guard flagged it partial — never
        a KeyError."""
        s = canonical.get("decode_k8").cost_summary()
        assert set(s) >= {"flops", "bytes_accessed", "peak_hbm_bytes",
                          "census_partial"}
        if analysis.census_capability():
            assert not s["census_partial"]
            assert s["flops"] > 0 and s["bytes_accessed"] > 0
            assert s["peak_hbm_bytes"] > 0

    def test_budget_catches_seeded_flops_change(self, canonical):
        """The regression the census exists for: compute moved, the
        exact FLOPs pin fails."""
        if not analysis.census_capability():
            pytest.skip("backend exposes no cost analysis")
        s = canonical.get("decode_k8").cost_summary()
        bad = analysis.CostBudget(flops=s["flops"] * 2)
        [v] = analysis.check_cost_budget(s, bad, "seeded")
        assert "FLOPs" in v and "re-pin" in v

    def test_budget_catches_seeded_bytes_change(self, canonical):
        if not analysis.census_capability():
            pytest.skip("backend exposes no cost analysis")
        s = canonical.get("decode_k8").cost_summary()
        bad = analysis.CostBudget(
            bytes_accessed=s["bytes_accessed"] / 2, bytes_tol=0.10
        )
        [v] = analysis.check_cost_budget(s, bad, "seeded")
        assert "bytes accessed" in v

    def test_partial_census_degrades_never_raises(self):
        """The capability guard: a census-less backend records nulls
        and a flag; the budget check treats it as clean (recorded, not
        failed)."""
        partial = {"flops": None, "bytes_accessed": None,
                   "transcendentals": None, "argument_bytes": None,
                   "output_bytes": None, "temp_bytes": None,
                   "peak_hbm_bytes": None, "census_partial": True}
        budget = analysis.CostBudget(flops=1.0, bytes_accessed=1.0,
                                     peak_hbm_bytes=1)
        assert analysis.check_cost_budget(partial, budget) == []

    def test_cost_summary_on_analysisless_object(self):
        """An executable-like object with no analyses degrades to an
        all-null partial summary — the mid-sweep KeyError class."""
        class NoAnalysis:
            def cost_analysis(self):
                raise NotImplementedError

            def memory_analysis(self):
                raise NotImplementedError

        s = analysis.cost_summary(NoAnalysis())
        assert s["census_partial"]
        assert s["flops"] is None and s["peak_hbm_bytes"] is None

    def test_roofline_math(self):
        r = analysis.roofline(1e9, 1e8, wall_s=1.0,
                              peak_flops_per_s=10e9,
                              peak_bytes_per_s=1e9)
        assert r["achieved_flops_per_s"] == 1e9
        assert r["arithmetic_intensity"] == 10.0
        # intensity 10 >= ridge 10 -> compute-bound at 10% of peak
        assert r["bound"] == "compute"
        assert r["utilization"] == pytest.approx(0.1)
        m = analysis.roofline(1e9, 1e9, wall_s=1.0,
                              peak_flops_per_s=10e9,
                              peak_bytes_per_s=1e9)
        assert m["bound"] == "memory"
        assert m["utilization"] == pytest.approx(1.0)
        # partial census degrades with it
        p = analysis.roofline(None, None, wall_s=1.0)
        assert p["achieved_flops_per_s"] is None and p["bound"] is None

    def test_census_pins_registered_on_lint_programs(self, canonical):
        """Every LINT program carries a cost pin (the ISSUE 11
        'registered next to the collective budget' contract) with an
        exact-FLOPs field."""
        for name in lint_graphs.LINT_PROGRAMS:
            pin = lint_graphs.COST_PINS.get(name)
            assert pin is not None, f"{name} has no cost pin"
            assert pin.flops is not None

    def test_collect_census_carries_span_join_key(self, canonical):
        census = lint_graphs.collect_census(
            canonical, names=("decode_k8", "train_m4")
        )
        assert census["decode_k8"]["span"] == "serve/decode_window"
        assert census["train_m4"]["span"] == "train/dispatch"


# ---------------------------------------------------------------------------
# the tier-1 gate: tools/lint_graphs.py end to end
# ---------------------------------------------------------------------------

class TestLintGraphs:
    def test_canonical_sweep_clean(self, canonical):
        """The acceptance gate: all four sanitizers over the canonical
        train/serve programs (sharing this session's lowerings) find
        ZERO violations on the current tree."""
        report = lint_graphs.run(canonical)
        assert set(report) == set(lint_graphs.LINT_PROGRAMS) | {
            "decode_k_invariance", "paged_k_invariance",
            "paged_mixed_traffic", "obs_instrumentation",
            "slo_overhead", "resilience_retry", "fleet_failover",
            "fleet_affinity", "cost_census", "flightrec_overhead",
            "sharding_rules", "elastic_resize", "gang_telemetry",
            "grad_compress", "fleet_scale", "promotion_zero_compile",
            "apexlint",
        }
        flat = [v for errs in report.values() for v in errs]
        assert flat == [], "\n".join(flat)
