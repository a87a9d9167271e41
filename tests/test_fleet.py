"""Multi-host fleet tests (ISSUE 9): host-scoped chaos, the
health-checked router, preflight gating, and the fleet trace merge.

The acceptance contract: a seeded run that kills one serve host
mid-stream returns greedy token streams IDENTICAL to the clean run
(shared prefixes included), every router edge case resolves to a clear
outcome (error, eviction, readmission) rather than a hang, and the
host-scoped FaultPlan sites replay byte-for-byte like the PR 8
single-process ones.
"""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import apex_tpu.serve as serve
from apex_tpu import obs
from apex_tpu.fleet import (
    FleetHost,
    FleetRouter,
    FleetUnavailable,
    PreflightCheck,
    PreflightReport,
    fleet_heartbeat_misses,
    fleet_straggler_factor,
    run_preflight,
)
from apex_tpu.models.gpt import GPTConfig, GPTLM
from apex_tpu.resilience import (
    HEARTBEAT_DROP,
    HOST_FAULT_KINDS,
    HOST_LOSS,
    HOST_STALL,
    RESTART,
    FaultEvent,
    FaultPlan,
    host_site,
)

CFG = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                     attn_dropout_rate=0.0)

ENG_KW = dict(slots=2, max_len=64, paged=True, page_len=8,
              prefill_chunk=16)


@pytest.fixture(scope="module")
def gpt_params():
    model = GPTLM(CFG)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(1, 16)))
    return model.init(jax.random.PRNGKey(0), ids)["params"]


@pytest.fixture(scope="module")
def dec4(gpt_params):
    return serve.GPTDecoder(CFG, gpt_params, tokens_per_dispatch=4)


@pytest.fixture(scope="module")
def dec_full(gpt_params):
    """The composition decoder: self-speculative (D=2) + int8 KV pages
    — fleet failover must stay token-exact with ALL of it live."""
    return serve.GPTDecoder(CFG, gpt_params, tokens_per_dispatch=8,
                            spec_tokens=2, kv_int8=True)


def _prompts():
    rng = np.random.RandomState(3)
    pool = [int(t) for t in rng.randint(0, CFG.vocab_size, size=(48,))]
    ps = [pool[0:5], pool[3:14], pool[7:15], pool[2:18]]
    ps.append(list(ps[1]))  # duplicate prompt: shared-prefix pages
    return ps


def _fleet(dec, plan=None, n_hosts=2, registry=None, **router_kw):
    hosts = [FleetHost(i, dec, **ENG_KW) for i in range(n_hosts)]
    return FleetRouter(
        hosts, fault_plan=plan,
        registry=registry if registry is not None else obs.MetricsRegistry(),
        **router_kw,
    )


def _drain(dec, plan=None, new_tokens=10, **kw):
    router = _fleet(dec, plan, **kw)
    for p in _prompts():
        router.submit(p, max_new_tokens=new_tokens)
    out = router.run()
    return router, out


# ---------------------------------------------------------------------------
# host-scoped FaultPlan sites — determinism, round-trip, replay
# ---------------------------------------------------------------------------

class TestHostFaultPlan:
    RATES = {HOST_LOSS: 0.15, HOST_STALL: 0.15, HEARTBEAT_DROP: 0.2,
             RESTART: 0.2}

    def test_seeded_host_plans_are_byte_identical(self):
        a = FaultPlan.from_seed(5, horizon=16, hosts=3, rates=self.RATES)
        b = FaultPlan.from_seed(5, horizon=16, hosts=3, rates=self.RATES)
        assert a.to_json() == b.to_json()
        assert len(a) > 0
        kinds = {ev.kind for ev in a.events}
        assert kinds & set(HOST_FAULT_KINDS), kinds
        sites = {ev.site for ev in a.events}
        assert sites <= {host_site(h) for h in range(3)}
        c = FaultPlan.from_seed(6, horizon=16, hosts=3, rates=self.RATES)
        assert a.to_json() != c.to_json()

    def test_hosts_zero_schedules_nothing_host_scoped(self):
        plan = FaultPlan.from_seed(5, horizon=16, rates=self.RATES)
        assert len(plan) == 0  # host kinds with no fleet sites: no draws

    def test_json_round_trip_and_reset_replay(self):
        plan = FaultPlan.from_seed(9, horizon=12, hosts=2,
                                   rates=self.RATES, stall_beats=3)
        back = FaultPlan.from_json(plan.to_json())
        assert back.to_json() == plan.to_json()
        stalls = [ev for ev in back.events if ev.kind == HOST_STALL]
        assert all(ev.value == 3.0 for ev in stalls)
        # poll every (site, index) the plan covers, twice via reset()
        def fire_all(p):
            fired = []
            for r in range(12):
                for h in range(2):
                    fired.extend(
                        (ev.site, ev.index, ev.kind)
                        for ev in p.poll(host_site(h))
                    )
            return fired

        first = fire_all(plan)
        plan.reset()
        assert fire_all(plan) == first  # byte-for-byte replay
        assert len(first) == len(plan)

    def test_host_site_keying(self):
        assert host_site(0) == "fleet/host0"
        assert host_site(7) == "fleet/host7"


# ---------------------------------------------------------------------------
# preflight — machine-readable PASS/FAIL
# ---------------------------------------------------------------------------

class TestPreflight:
    def test_clean_decoder_passes_all_checks(self, dec4):
        rep = run_preflight(dec4, host_id=0, **{k: ENG_KW[k] for k in
                                                ("slots", "max_len",
                                                 "page_len", "paged")})
        assert rep.passed, rep.to_json()
        assert {c.name for c in rep.checks} == {
            "precision", "transfers", "donation", "warm_compile"
        }
        assert rep.failures() == []

    def test_report_round_trips_and_cache(self, dec4):
        rep = run_preflight(dec4, host_id="h1")
        back = PreflightReport.from_json(rep.to_json())
        assert back.passed == rep.passed
        assert [c.name for c in back.checks] == [c.name for c in rep.checks]
        # repeat qualification of the same artifact is served cached
        # (stamped with the new host id)
        again = run_preflight(dec4, host_id="h2")
        assert again.host_id == "h2"
        assert again.checks == rep.checks

    def test_failed_report_is_machine_readable(self):
        rep = PreflightReport(host_id=3, checks=[
            PreflightCheck("donation", False, "carry leaf not aliased"),
            PreflightCheck("precision", True),
        ])
        assert not rep.passed
        assert [c.name for c in rep.failures()] == ["donation"]
        assert "FAIL:donation" in repr(rep)


# ---------------------------------------------------------------------------
# the acceptance: chaos fleet parity
# ---------------------------------------------------------------------------

class TestFleetChaosParity:
    def test_kill_one_host_token_identical(self, dec4):
        """Kill host 0 mid-stream (then restart it through preflight):
        the drained streams — shared-prefix duplicate included — are
        token-identical to the clean fleet's, and the ledger shows the
        loss, the recovery and the readmission."""
        _, warm = _drain(dec4)  # warm every program incl. replay paths
        _, clean = _drain(dec4)
        assert warm == clean
        plan = FaultPlan([
            FaultEvent(host_site(0), 2, HOST_LOSS),
            FaultEvent(host_site(0), 4, RESTART),
        ])
        reg = obs.MetricsRegistry()
        router, faulted = _drain(dec4, plan, registry=reg)
        assert faulted == clean
        stats = router.stats()
        assert stats["host_losses"] == 1
        assert stats["requests_recovered"] >= 1
        assert stats["readmissions"] == 1
        assert stats["hosts"][0]["state"] == "admitted"  # came back
        snap = reg.snapshot()
        assert snap["fleet.host_losses"]["value"] == 1
        assert snap["fleet.recovery_ms"]["count"] >= 1

    def test_kill_one_host_with_spec_int8_prefixes(self, dec_full):
        """The acceptance composition: host loss mid-stream with
        speculative decode + int8 KV pages + shared prefixes all live —
        greedy streams identical to the clean fleet's."""
        _, warm = _drain(dec_full, new_tokens=8)
        _, clean = _drain(dec_full, new_tokens=8)
        assert warm == clean
        plan = FaultPlan([FaultEvent(host_site(0), 2, HOST_LOSS)])
        router, faulted = _drain(dec_full, plan, new_tokens=8)
        assert router.stats()["host_losses"] == 1
        assert faulted == clean

    def test_seeded_host_chaos_replays_identically(self, dec4):
        """A from_seed(hosts=2) plan drives the fleet twice: same
        tokens, same ledger — the regression-test property."""
        def plan():
            return FaultPlan.from_seed(
                21, horizon=10, hosts=2,
                rates={HOST_LOSS: 0.12, HEARTBEAT_DROP: 0.15,
                       RESTART: 0.3},
            )

        assert len(plan()) > 0
        r1, out1 = _drain(dec4, plan())
        r2, out2 = _drain(dec4, plan())
        assert out1 == out2
        assert r1.stats()["host_losses"] == r2.stats()["host_losses"]
        assert r1.stats()["evictions"] == r2.stats()["evictions"]


# ---------------------------------------------------------------------------
# router edge cases
# ---------------------------------------------------------------------------

class TestRouterEdges:
    def test_all_hosts_unhealthy_raises_not_hangs(self, dec4):
        plan = FaultPlan([
            FaultEvent(host_site(0), 1, HOST_LOSS),
            FaultEvent(host_site(1), 1, HOST_LOSS),
        ])
        router = _fleet(dec4, plan)
        router.submit(_prompts()[0], max_new_tokens=30)
        with pytest.raises(FleetUnavailable, match="unhealthy"):
            router.run()

    def test_flapping_host_readmitted_only_after_preflight_pass(
            self, dec4):
        """Heartbeat drops evict the host; readmission is GATED: a
        failing preflight keeps it out (its traffic stays on the
        survivor), a passing one lets it back."""
        class Gate:
            fail = False

            def __call__(self, host):
                ok = not self.fail
                return PreflightReport(host_id=host.host_id, checks=[
                    PreflightCheck("gate", ok,
                                   "" if ok else "induced failure"),
                ])

        gate = Gate()
        reg = obs.MetricsRegistry()
        router = _fleet(dec4, heartbeat_misses=2, preflight=gate,
                        registry=reg)
        uids = [router.submit(p, max_new_tokens=12)
                for p in _prompts()[:3]]
        h1 = router.hosts[1]
        h1.drop_heartbeat()
        h1.drop_heartbeat()  # two consecutive misses -> evicted
        router.step()
        router.step()
        assert h1.state == "evicted"
        assert router.stats()["evictions"] == 1
        # readmission attempt under a FAILING preflight: stays out
        gate.fail = True
        assert router.admit(1) is False
        assert h1.state == "evicted"
        assert router.stats()["preflight_failures"] == 1
        # everything keeps draining on the survivor meanwhile
        out = router.run()
        assert all(len(out[u]) == 12 for u in uids)
        # a PASSING preflight readmits
        gate.fail = False
        assert router.admit(1) is True
        assert h1.state == "admitted"
        assert router.stats()["readmissions"] == 1

    def test_submit_during_recovery_window_lands_on_survivor(self, dec4):
        plan = FaultPlan([FaultEvent(host_site(0), 0, HOST_LOSS)])
        router = _fleet(dec4, plan)
        u0 = router.submit(_prompts()[1], max_new_tokens=12)
        router.step()  # host 0 dies; its request moves to host 1
        assert router.hosts[0].state == "lost"
        u1 = router.submit(_prompts()[0], max_new_tokens=8)
        rec = router._records[u1]
        assert rec.host_id == 1  # routed around the dead host
        out = router.run()
        assert len(out[u0]) == 12 and len(out[u1]) == 8

    def test_host_stall_misses_heartbeats_then_recovers(self, dec4):
        """A stalled host misses exactly `value` heartbeats — under
        the miss budget it stays admitted, over it it is evicted."""
        router = _fleet(dec4, heartbeat_misses=3)
        h0 = router.hosts[0]
        h0.stall(2)  # two missed beats < 3 budget: stays admitted
        router.submit(_prompts()[0], max_new_tokens=8)
        router.run()
        assert h0.state == "admitted"
        assert router.stats()["evictions"] == 0
        # one more beat past the stall answers again
        assert h0.heartbeat() is True

    def test_duplicate_host_ids_rejected(self, dec4):
        hosts = [FleetHost(0, dec4, **ENG_KW),
                 FleetHost(0, dec4, **ENG_KW)]
        with pytest.raises(ValueError, match="duplicate"):
            FleetRouter(hosts, registry=obs.MetricsRegistry(),
                        preflight=False)

    def test_env_knobs(self, monkeypatch):
        monkeypatch.setenv("APEX_TPU_FLEET_HEARTBEAT_MISSES", "5")
        monkeypatch.setenv("APEX_TPU_FLEET_STRAGGLER_FACTOR", "2.5")
        assert fleet_heartbeat_misses() == 5
        assert fleet_straggler_factor() == 2.5
        assert fleet_heartbeat_misses(1) == 1   # explicit arg wins
        assert fleet_straggler_factor(4.0) == 4.0


# ---------------------------------------------------------------------------
# straggler detection + fleet trace merge
# ---------------------------------------------------------------------------

class TestStragglersAndMerge:
    def test_straggler_scan_flags_slow_host(self, dec4):
        router = _fleet(dec4, straggler_factor=3.0, preflight=False)
        for h in router.hosts.values():
            h.start()
            h.state = "admitted"
        fast, slow = router.hosts[0], router.hosts[1]
        for _ in range(8):
            fast._h_decode.observe(10.0)
            slow._h_decode.observe(100.0)  # 10x the fleet median
        router._scan_stragglers()
        assert router.stragglers == {1}
        assert router.stats()["hosts"][1]["straggler"] is True
        assert router.stats()["straggler_flags"] == 1
        # recovery: enough fast samples push the slow host's p99 back
        # under the threshold and the flag clears
        for _ in range(900):
            slow._h_decode.observe(10.0)
        router._scan_stragglers()
        assert router.stragglers == set()

    def test_merge_renders_per_host_straggler_table(self, dec4, tmp_path):
        if not obs.enabled():
            pytest.skip("obs disabled")
        sys.path.insert(0, os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools"))
        from tools import trace_report

        hosts = [
            FleetHost(i, dec4, tracer=obs.Tracer(enabled=True),
                      **ENG_KW)
            for i in range(2)
        ]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry())
        for p in _prompts()[:3]:
            router.submit(p, max_new_tokens=8)
        router.run()
        paths = [
            h.export_trace(str(tmp_path / f"host{h.host_id}.jsonl"))
            for h in hosts
        ]
        merged = trace_report.load_hosts(paths)
        assert [h for h, _, _ in merged] == [0, 1]
        # every span carries its host id
        for hid, events, _ in merged:
            spans = [e for e in events if e.get("type") == "span"]
            assert spans
            assert all(e["attrs"]["host"] == hid for e in spans)
        text = trace_report.render_fleet(merged)
        assert "straggler table" in text
        assert "host 0:" in text and "host 1:" in text
        assert "fleet" in text

    def test_progress_streams_in_flight_tokens(self, dec4):
        from apex_tpu.resilience import ResilientServeEngine

        eng = ResilientServeEngine(dec4, registry=obs.MetricsRegistry(),
                                   **ENG_KW)
        uid = eng.submit(_prompts()[1], max_new_tokens=20)
        for _ in range(3):
            eng.step()
        toks, done = eng.progress()[uid]
        assert 0 < len(toks) < 20 and not done
        out = eng.run()
        assert out[uid][: len(toks)] == toks  # streamed = prefix
        assert eng.progress()[uid] == (out[uid], True)


# ---------------------------------------------------------------------------
# ISSUE 12: prefix-affinity routing
# ---------------------------------------------------------------------------

def _staggered_shared_traffic(pool):
    """Two Zipf-style prefix families, each with a long-lived anchor
    whose registered pages stay alive while the short sharers admit —
    the overlap pattern prefix affinity exists for."""
    pA, pB = pool[:8], pool[8:16]
    # the unique-prompt noise request matters: it breaks the accidental
    # submit-order/least-loaded parity that would otherwise route the
    # families affine by coincidence (alternating A,B,A,B on an empty
    # 2-host fleet makes least-loaded ping-pong exactly along family
    # lines — the PR 12 gotcha this plan exists to defeat)
    return [(pA + pool[16:20], 24), (pB + pool[20:24], 24),
            (pA + pool[24:29], 6), (pB + pool[29:33], 6),
            (pool[33:43], 6),
            (pA + pool[43:46], 6), (pB + pool[46:50], 6),
            (pA + pool[16:20], 6)]


def _assert_distinct_arcs(router, pool):
    """The other half of the PR 12 gotcha, ASSERTED instead of trusted
    to a comment: the two prefix families must hash to DIFFERENT ring
    arcs on this pool, or the affine host is shared and the A/B
    measures the load guard spilling, not affinity.  (Ring placement
    depends on the token pool — e.g. the RandomState(9) pool used by
    the determinism test collides both families onto one arc.)"""
    hosts = router.admitted()
    arc_a = router._ring_host(tuple(pool[:8]), hosts).host_id
    arc_b = router._ring_host(tuple(pool[8:16]), hosts).host_id
    assert arc_a != arc_b, (
        f"prefix families share ring arc {arc_a} — pick a pool seed "
        "that separates them or the test measures the load guard"
    )


class TestAffinityRouting:
    def test_affinity_improves_fleet_prefix_hit_rate(self, dec4):
        """The acceptance A/B: identical traffic routed least-loaded vs
        affine — tokens byte-identical (routing only reorders hosts
        under greedy), fleet prefix-hit rate strictly better affine,
        and the per-host attribution explains every decision.  (Pool
        seed chosen so the two prefix families hash to DIFFERENT ring
        arcs — a same-arc pool would spill through the load guard and
        measure the guard, not affinity.)"""
        rng = np.random.RandomState(0)
        pool = [int(t) for t in rng.randint(0, CFG.vocab_size,
                                            size=(64,))]
        reqs = _staggered_shared_traffic(pool)

        def leg(affinity):
            hosts = [FleetHost(i, dec4, **ENG_KW) for i in range(2)]
            router = FleetRouter(hosts, registry=obs.MetricsRegistry(),
                                 affinity=affinity)
            uids = [router.submit(p, max_new_tokens=n)
                    for p, n in reqs]
            out = router.run()
            return router, [out[u] for u in uids]

        r_ll, out_ll = leg(False)
        r_af, out_af = leg(True)
        _assert_distinct_arcs(r_af, pool)
        assert out_ll == out_af
        hit_ll = r_ll.stats()["fleet_prefix_hit_rate"]
        hit_af = r_af.stats()["fleet_prefix_hit_rate"]
        assert hit_af > hit_ll, (hit_ll, hit_af)
        assert r_af.stats()["affinity_hits"] >= 4
        attr = r_af.routing_attribution()
        assert set(attr) == {"0", "1"}
        assert sum(a["requests"] for a in attr.values()) == len(reqs)
        assert sum(a["affinity_hits"] for a in attr.values()) \
            == r_af.stats()["affinity_hits"]
        # least-loaded leg records zero affinity decisions
        assert r_ll.stats()["affinity_hits"] == 0

    def test_affinity_routing_is_deterministic(self, dec4):
        """Same traffic, two routers: identical routing attribution
        (the consistent-hash ring and FNV key hash are salted by
        nothing)."""
        rng = np.random.RandomState(9)
        pool = [int(t) for t in rng.randint(0, CFG.vocab_size,
                                            size=(64,))]
        reqs = _staggered_shared_traffic(pool)

        def leg():
            hosts = [FleetHost(i, dec4, **ENG_KW) for i in range(2)]
            router = FleetRouter(hosts, registry=obs.MetricsRegistry(),
                                 affinity=True)
            for p, n in reqs:
                router.submit(p, max_new_tokens=n)
            router.run()
            return router.routing_attribution()

        assert leg() == leg()

    def test_kill_switch_and_env_knobs(self, dec4, monkeypatch):
        from apex_tpu.fleet import (
            fleet_affinity_default,
            fleet_affinity_gap,
            fleet_autoscale_default,
            fleet_host_role,
        )

        assert fleet_affinity_default() is True  # default ON
        monkeypatch.setenv("APEX_TPU_FLEET_AFFINITY", "0")
        assert fleet_affinity_default() is False
        assert fleet_affinity_default(True) is True  # explicit wins
        router = _fleet(dec4)
        assert router.affinity is False  # env kill switch reached it
        monkeypatch.delenv("APEX_TPU_FLEET_AFFINITY")
        monkeypatch.setenv("APEX_TPU_FLEET_AFFINITY_GAP", "5")
        assert fleet_affinity_gap() == 5
        assert fleet_affinity_gap(1) == 1
        assert fleet_autoscale_default() is False  # default OFF
        monkeypatch.setenv("APEX_TPU_FLEET_AUTOSCALE", "1")
        assert fleet_autoscale_default() is True
        monkeypatch.setenv("APEX_TPU_FLEET_ROLES", "prefill,decode")
        assert fleet_host_role(None, 0) == "prefill"
        assert fleet_host_role(None, 1) == "decode"
        assert fleet_host_role(None, 2) == "mixed"  # past the list
        assert fleet_host_role("mixed", 0) == "mixed"  # explicit wins
        with pytest.raises(ValueError, match="role"):
            fleet_host_role("gpu", 0)

    def test_hot_affine_host_falls_back_least_loaded(self, dec4):
        """The load guard: when the affine host runs more than
        ``affinity_gap`` ahead, routing falls back and attributes the
        reason."""
        hosts = [FleetHost(i, dec4, **ENG_KW) for i in range(2)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry(),
                             affinity=True, affinity_gap=0)
        prompt = [1, 2, 3, 4, 5, 6, 7, 8]
        # same prefix repeatedly: first goes affine, later ones find
        # the affine host loaded and spill with reason=affine_hot
        for _ in range(4):
            router.submit(list(prompt), max_new_tokens=12)
        router.run()
        fb = sum(a["fallbacks"].get("affine_hot", 0)
                 for a in router.routing_attribution().values())
        assert fb >= 1
        assert router.stats()["affinity_fallbacks"] == fb


# ---------------------------------------------------------------------------
# ISSUE 12: disaggregated prefill/decode
# ---------------------------------------------------------------------------

class TestDisaggregation:
    def test_roles_parity_and_handoffs(self, dec4):
        """A prefill+decode fleet streams tokens identical to a mixed
        fleet — the handoff (serialize, CRC, import, adopt) is
        invisible under greedy — and the ledger shows pages actually
        moved."""
        _, mixed = _drain(dec4)
        hosts = [FleetHost(0, dec4, role="prefill", **ENG_KW),
                 FleetHost(1, dec4, role="decode", **ENG_KW)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry())
        for p in _prompts():
            router.submit(p, max_new_tokens=10)
        out = router.run()
        assert out == mixed
        stats = router.stats()
        assert stats["handoffs"] + stats["handoff_fallbacks"] \
            >= len(_prompts())
        assert stats["handoffs"] >= 1
        attr = router.routing_attribution()
        assert attr["0"]["role"] == "prefill"
        assert attr["1"]["role"] == "decode"
        assert attr["0"]["handoffs_out"] >= 1
        assert attr["1"]["handoffs_in"] >= 1

    def test_handoff_killed_mid_transfer_recovers(self, dec4):
        """The acceptance chaos: the prefill host dies in the pending
        window between prefill-complete and handoff execution — the
        request recovers through recompute preemption on the decode
        host, final tokens identical to the clean run."""
        _, clean = _drain(dec4)
        plan = FaultPlan([FaultEvent(host_site(0), 1, HOST_LOSS)])
        hosts = [FleetHost(0, dec4, role="prefill", **ENG_KW),
                 FleetHost(1, dec4, role="decode", **ENG_KW)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry(),
                             fault_plan=plan)
        for p in _prompts():
            router.submit(p, max_new_tokens=10)
        out = router.run()
        assert out == clean
        stats = router.stats()
        assert stats["host_losses"] == 1
        assert stats["requests_recovered"] >= 1

    def test_corrupt_handoff_falls_back_to_recompute(self, dec4,
                                                     monkeypatch):
        """Corrupted wire bytes raise (never hang) and the router's
        recompute fallback still delivers identical tokens."""
        from apex_tpu.serve import handoff as ho_mod

        _, clean = _drain(dec4)
        real = ho_mod.KVHandoff.from_bytes.__func__

        def corrupt(cls, blob):
            return real(cls, blob[:-4] + b"XXXX")

        monkeypatch.setattr(ho_mod.KVHandoff, "from_bytes",
                            classmethod(corrupt))
        hosts = [FleetHost(0, dec4, role="prefill", **ENG_KW),
                 FleetHost(1, dec4, role="decode", **ENG_KW)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry())
        for p in _prompts():
            router.submit(p, max_new_tokens=10)
        out = router.run()
        assert out == clean
        stats = router.stats()
        assert stats["handoffs"] == 0
        assert stats["handoff_fallbacks"] >= 1

    def test_handoff_with_spec_int8_composition(self, dec_full):
        """The acceptance composition: the handoff carries int8 pages
        WITH their per-token fp32 scale columns, and the adopting
        host's speculative windows resume from the seeded history —
        streams identical to the mixed fleet's."""
        _, mixed = _drain(dec_full, new_tokens=8)
        hosts = [FleetHost(0, dec_full, role="prefill", **ENG_KW),
                 FleetHost(1, dec_full, role="decode", **ENG_KW)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry())
        for p in _prompts():
            router.submit(p, max_new_tokens=8)
        out = router.run()
        assert out == mixed
        assert router.stats()["handoffs"] >= 1

    def test_prefill_host_never_decodes(self, dec4):
        """Disaggregation's point: the prefill host's engine never
        launches a decode window — bursty prefill cannot steal decode
        boundaries there."""
        hosts = [FleetHost(0, dec4, role="prefill", **ENG_KW),
                 FleetHost(1, dec4, role="decode", **ENG_KW)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry())
        for p in _prompts():
            router.submit(p, max_new_tokens=10)
        router.run()
        pf = hosts[0].registry.get("serve.decode_dispatches")
        dc = hosts[1].registry.get("serve.decode_dispatches")
        assert (pf.value if pf else 0) == 0
        assert dc.value > 0


# ---------------------------------------------------------------------------
# ISSUE 12: SLO-driven autoscaling
# ---------------------------------------------------------------------------

class TestAutoscale:
    def _plan(self):
        return serve.TrafficPlan.from_seed(
            17, requests=36, rate_rps=60.0, arrival="bursty",
            burst_factor=10.0, burst_on_s=0.3, burst_off_s=1.2,
            vocab_size=CFG.vocab_size, n_prefixes=2, prefix_len=8,
            zipf_s=1.2, shared_frac=0.5, prompt_min=2,
            prompt_scale=4.0, prompt_alpha=1.3, prompt_cap=24,
            output_min=2, output_scale=4.0, output_alpha=1.2,
            output_cap=12, priorities=(0, 2),
            interactive_max_prompt=12,
        )

    def _auto_leg(self, dec4):
        gen = serve.LoadGen(self._plan(), step_cost_ms=4.0)
        mk = lambda i: FleetHost(i, dec4, clock=gen.clock, **ENG_KW)
        tracker = obs.SloTracker(
            [obs.SloObjective("ttft_ms", 0.9, 12.0, 64.0)],
            clock=gen.clock,
        )
        router = FleetRouter(
            [mk(0)], standby=[mk(1), mk(2)],
            registry=obs.MetricsRegistry(), clock=gen.clock,
            autoscale=True, autoscale_tracker=tracker,
            scale_cooldown_rounds=2, drain_after_rounds=3,
        )
        rep = gen.run(router)
        return rep, router

    def _static_leg(self, dec4):
        gen = serve.LoadGen(self._plan(), step_cost_ms=4.0)
        hosts = [FleetHost(i, dec4, clock=gen.clock, **ENG_KW)
                 for i in range(3)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry(),
                             clock=gen.clock)
        return gen.run(router), router

    def test_burn_scales_up_and_calm_drains(self, dec4):
        """TTFT burn admits standby hosts through preflight; calm
        rounds drain the most recent scale-up (engine released, pages
        gone); every completed request still counts in the report."""
        rep, router = self._auto_leg(dec4)
        stats = router.stats()
        assert stats["scale_ups"] >= 1, stats
        assert stats["drains"] >= 1, stats
        # the drain actually released an engine at some point, and the
        # completed count survived it (the lifecycle stash)
        assert rep.completed == rep.submitted
        # host-boundaries were recorded (the goodput-per-host figure)
        assert stats["host_boundaries"] > 0

    def test_autoscale_is_byte_replayable(self, dec4):
        """Two runs of the same seeded plan: identical LoadReports —
        scale-up/drain decisions are pure functions of the virtual
        clock."""
        rep_a, r_a = self._auto_leg(dec4)
        rep_b, r_b = self._auto_leg(dec4)
        assert rep_a.to_json() == rep_b.to_json()
        assert r_a.stats()["scale_ups"] == r_b.stats()["scale_ups"]
        assert r_a.stats()["drains"] == r_b.stats()["drains"]

    def test_tokens_match_static_fleet(self, dec4):
        """Scaling only changes WHERE requests run: greedy token
        streams equal the static 3-host fleet's."""
        rep_a, _ = self._auto_leg(dec4)
        rep_s, _ = self._static_leg(dec4)
        assert rep_a.tokens == rep_s.tokens

    def test_elastic_fleet_spends_fewer_host_boundaries_than_static(
            self, dec4):
        """What scaling buys: the plan a static fleet serves with three
        hosts stepping every round, one host plus two standbys serve
        on fewer host-rounds — hosts are admitted while TTFT burns and
        drained when it is calm."""
        rep_a, r_a = self._auto_leg(dec4)
        rep_s, r_s = self._static_leg(dec4)
        assert rep_a.completed == rep_s.completed == rep_s.submitted
        assert 0 < r_a.stats()["host_boundaries"] \
            < r_s.stats()["host_boundaries"]

    def test_autoscale_off_leaves_standby_untouched(self, dec4):
        """Without the opt-in, standby hosts are registered but never
        admitted — no silent topology changes."""
        hosts = [FleetHost(0, dec4, **ENG_KW)]
        router = FleetRouter(hosts,
                             standby=[FleetHost(1, dec4, **ENG_KW)],
                             registry=obs.MetricsRegistry())
        router.submit(_prompts()[0], max_new_tokens=8)
        router.run()
        assert router.hosts[1].state == "new"
        assert router.stats()["scale_ups"] == 0


class TestRoutingReport:
    def test_loadreport_carries_routing_attribution(self, dec4):
        """ISSUE 12 satellite: a fleet-driven LoadReport records the
        per-host routing ledger — and it round-trips through to_json
        (so replay equality covers routing decisions too)."""
        import json

        plan = serve.TrafficPlan.from_seed(
            19, requests=12, rate_rps=150.0, arrival="poisson",
            vocab_size=CFG.vocab_size, n_prefixes=2, prefix_len=8,
            zipf_s=1.1, shared_frac=0.7, prompt_min=2,
            prompt_scale=4.0, prompt_alpha=1.4, prompt_cap=24,
            output_min=2, output_scale=4.0, output_alpha=1.2,
            output_cap=10,
        )
        gen = serve.LoadGen(plan, step_cost_ms=4.0)
        hosts = [FleetHost(i, dec4, clock=gen.clock, **ENG_KW)
                 for i in range(2)]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry(),
                             clock=gen.clock, affinity=True)
        rep = gen.run(router)
        assert rep.routing is not None
        assert set(rep.routing) == {"0", "1"}
        for row in rep.routing.values():
            for key in ("role", "requests", "affinity_hits",
                        "fallbacks", "handoffs_in", "handoffs_out",
                        "prompt_tokens", "prefix_hit_tokens",
                        "prefix_hit_rate"):
                assert key in row, key
        assert sum(r["requests"] for r in rep.routing.values()) \
            == len(plan)
        doc = json.loads(rep.to_json())
        assert doc["routing"] == rep.routing
        # a bare engine target records no routing section
        eng = serve.ServeEngine(dec4, **ENG_KW)
        gen2 = serve.LoadGen(plan, step_cost_ms=4.0)
        # rebuild engine on the generator's clock for the check
        eng = serve.ServeEngine(dec4, clock=gen2.clock, **ENG_KW)
        assert gen2.run(eng).routing is None

    def test_merge_renders_prefix_and_role_table(self, dec4, tmp_path):
        """The --merge fleet view renders the prefix-hit + role table
        next to the straggler table (ISSUE 12 satellite)."""
        if not obs.enabled():
            pytest.skip("obs disabled")
        from tools import trace_report

        hosts = [
            FleetHost(0, dec4, role="prefill",
                      tracer=obs.Tracer(enabled=True), **ENG_KW),
            FleetHost(1, dec4, role="decode",
                      tracer=obs.Tracer(enabled=True), **ENG_KW),
        ]
        router = FleetRouter(hosts, registry=obs.MetricsRegistry())
        for p in _prompts()[:3]:
            router.submit(p, max_new_tokens=8)
        router.run()
        paths = [
            h.export_trace(str(tmp_path / f"host{h.host_id}.jsonl"))
            for h in hosts
        ]
        merged = trace_report.load_hosts(paths)
        text = trace_report.render_fleet(merged)
        assert "prefix cache + roles" in text
        assert "prefill" in text and "decode" in text
        assert "adopt" in text and "detach" in text


# ---------------------------------------------------------------------------
# roll_host (ISSUE 18): drain -> wait-calm -> readmit, engine kept
# ---------------------------------------------------------------------------

class TestRollHost:
    def test_roll_drains_to_calm_and_keeps_the_engine(self, dec4):
        """The rolling-update primitive: the host leaves the pools,
        the router drains it to calm, the callback runs at the quiet
        boundary, and readmission keeps the SAME engine (KV pages and
        compiled programs survive — unlike admit(), which rebuilds)."""
        fr = obs.FlightRecorder(enabled=True)
        router = _fleet(dec4, flightrec=fr)
        for p in _prompts():
            router.submit(p, max_new_tokens=24)
        for _ in range(2):
            router.step()
        host = router.hosts[0]
        eng_before = host.engine
        seen = {}

        def at_calm(h):
            seen["state"] = h.state
            seen["load"] = router._load.get(0, 0)
            return "swapped"

        out = router.roll_host(0, at_calm, corr="roll-t")
        assert out["calm"] and out["outstanding"] == 0
        assert out["result"] == "swapped" and out["rounds"] >= 0
        assert seen == {"state": "draining", "load": 0}
        assert host.state == "admitted"
        assert host.engine is eng_before  # NOT rebuilt
        clean = _drain(dec4, new_tokens=24)[1]
        assert router.run() == clean  # token-exact through the roll
        kinds = [e["kind"] for e in fr.events()]
        for k in ("fleet/roll", "fleet/roll_calm", "fleet/roll_readmit"):
            assert k in kinds, kinds
        snap = router.registry.counter("fleet.rolls").snapshot()
        assert snap["value"] == 1

    def test_roll_with_zero_budget_keeps_inflight_load(self, dec4):
        """A finite drain budget swaps mid-flight: the callback sees
        outstanding requests, and readmission restores the host's load
        accounting (``_pool_join`` zeroes it) so the fleet still
        drains token-exact."""
        router = _fleet(dec4)
        for p in _prompts():
            router.submit(p, max_new_tokens=24)
        for _ in range(2):
            router.step()
        before = router._load.get(0, 0)
        assert before > 0
        out = router.roll_host(0, lambda h: None, drain_rounds=0)
        assert not out["calm"] and out["outstanding"] == before
        assert router._load.get(0, 0) == before  # restored after join
        assert router.run() == _drain(dec4, new_tokens=24)[1]

    def test_roll_rejects_non_admitted_and_readmits_on_raise(self, dec4):
        router = _fleet(dec4)
        router.submit(_prompts()[0], max_new_tokens=4)
        router.hosts[1].state = "evicted"
        with pytest.raises(ValueError, match="evicted"):
            router.roll_host(1)
        router.hosts[1].state = "admitted"

        def boom(h):
            raise RuntimeError("swap exploded")

        with pytest.raises(RuntimeError, match="swap exploded"):
            router.roll_host(0, boom)
        # the finally-block readmitted the host: the fleet is whole
        assert router.hosts[0].state == "admitted"
        assert router.run()  # still drains
