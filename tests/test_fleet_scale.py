"""Fleet-at-scale routing structures (ISSUE 17).

These tests pin the router's O(log H) structures in isolation, and the
scale policies (rebalancer, streamed scrape, straggler scan, streaming
KV handoff) on a seeded fleet small enough for tier-1:

- the incrementally-maintained consistent-hash ring is EXACTLY the
  from-scratch rebuild after any admit/evict/readmit sequence (the
  determinism story: membership history cannot leak into placement);
- losing 1 of H hosts remaps only ~K/H affinity keys, and every key
  whose owner survives keeps its owner (the minimal-disruption
  property that makes the ring worth having);
- the live router's rings/heaps stay in lockstep with pool
  membership across evict/readmit, and FleetUnavailable diagnoses a
  100-host fleet in a bounded, summarized message;
- with every scale policy live, a seeded open-loop leg is a function
  of its seed (report and flight recorder byte-identical across two
  runs), and streaming the KV handoff changes no token while only the
  tail chunk stays on the blocking hop.
"""
import json
import os
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from apex_tpu import obs, serve  # noqa: E402
from apex_tpu.fleet.serve import (  # noqa: E402
    FleetHost,
    FleetRouter,
    FleetUnavailable,
    _Ring,
    _stable_hash,
)
from apex_tpu.models.gpt import GPTConfig, GPTLM  # noqa: E402

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "tools"))
from trace_report import CorrelationStitcher  # noqa: E402

CFG = GPTConfig.tiny(compute_dtype=jnp.float32, dropout_rate=0.0,
                     attn_dropout_rate=0.0)
ENG_KW = dict(slots=2, max_len=64, paged=True, page_len=8,
              prefill_chunk=16)


@pytest.fixture(scope="module")
def dec4():
    model = GPTLM(CFG)
    rng = np.random.RandomState(0)
    ids = jnp.asarray(rng.randint(0, CFG.vocab_size, size=(1, 16)))
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    return serve.GPTDecoder(CFG, params, tokens_per_dispatch=4)


def _keys(n, seed=7):
    rng = np.random.RandomState(seed)
    return [tuple(int(t) for t in rng.randint(0, 50000, size=(6,)))
            for _ in range(n)]


# ---------------------------------------------------------------------------
# _Ring: incremental updates == from-scratch rebuild, minimal remap
# ---------------------------------------------------------------------------

class TestRing:
    def test_add_remove_matches_rebuild(self):
        ring = _Ring()
        for hid in range(10):
            ring.add(hid)
        assert ring.points() == _Ring.from_ids(range(10)).points()
        ring.remove(3)
        ring.remove(7)
        assert ring.points() == \
            _Ring.from_ids([h for h in range(10)
                            if h not in (3, 7)]).points()

    def test_random_membership_history_is_invisible(self):
        """Any admit/evict/readmit sequence lands on EXACTLY the
        rebuild of the final membership — placement depends on who is
        in the ring, never on how they got there."""
        rng = np.random.RandomState(11)
        ring = _Ring()
        alive = set()
        for _ in range(300):
            hid = int(rng.randint(0, 40))
            if hid in alive and rng.rand() < 0.5:
                ring.remove(hid)
                alive.discard(hid)
            elif hid not in alive:
                ring.add(hid)
                alive.add(hid)
        rebuilt = _Ring.from_ids(alive)
        assert ring.points() == rebuilt.points()
        assert ring.ids_tuple() == rebuilt.ids_tuple()
        for key in _keys(200):
            assert ring.lookup(key) == rebuilt.lookup(key)

    def test_losing_one_host_remaps_about_k_over_h(self):
        """The consistent-hashing contract: kill 1 of H hosts and only
        the dead host's keys move — everyone else keeps their owner,
        and the dead host's share is ~K/H."""
        H, K = 50, 2000
        ring = _Ring.from_ids(range(H))
        keys = _keys(K)
        before = {k: ring.lookup(k) for k in keys}
        victim = 17
        ring.remove(victim)
        moved = 0
        for k in keys:
            after = ring.lookup(k)
            if before[k] == victim:
                moved += 1
                assert after != victim
            else:
                # minimal disruption: surviving owners keep their keys
                assert after == before[k]
        # ~K/H = 40 expected; generous band, but far below a naive
        # rehash-everything (which would move ~K*(H-1)/H ≈ 1960)
        assert 0 < moved < 4 * K // H

    def test_incremental_equals_rebuild_after_loss(self):
        H = 25
        inc = _Ring.from_ids(range(H))
        inc.remove(9)
        rebuilt = _Ring.from_ids([h for h in range(H) if h != 9])
        assert inc.points() == rebuilt.points()
        for k in _keys(300, seed=13):
            assert inc.lookup(k) == rebuilt.lookup(k)

    def test_lookup_agrees_with_legacy_bisect(self):
        """The ring's bisect must reproduce the pre-refactor
        sorted-points + bisect_left placement bit-for-bit."""
        import bisect

        ids = [3, 1, 4, 15, 9, 2, 6]
        ring = _Ring.from_ids(ids)
        pts = sorted((_stable_hash(("vnode", hid, v)), hid)
                     for hid in ids for v in range(8))
        for key in _keys(200, seed=5):
            i = bisect.bisect_left(pts, (_stable_hash(key), -1))
            legacy = pts[i % len(pts)][1]
            assert ring.lookup(key) == legacy

    def test_empty_ring(self):
        ring = _Ring()
        assert ring.lookup(("x",)) is None
        assert len(ring) == 0
        ring.add(0)
        ring.remove(0)
        assert ring.points() == []


# ---------------------------------------------------------------------------
# live router: structures track membership; bounded diagnostics
# ---------------------------------------------------------------------------

class TestRouterScaleStructures:
    def _router(self, dec4, n=4, **kw):
        hosts = [FleetHost(i, dec4, **ENG_KW) for i in range(n)]
        return FleetRouter(hosts, registry=obs.MetricsRegistry(), **kw)

    def test_rings_track_evict_and_readmit(self, dec4):
        r = self._router(dec4)
        assert r._rings["any"].ids_tuple() == (0, 1, 2, 3)
        r._evict(r.hosts[2])
        assert r._rings["any"].ids_tuple() == (0, 1, 3)
        assert r._rings["any"].points() == \
            _Ring.from_ids([0, 1, 3]).points()
        assert r.admit(2)
        assert r._rings["any"].ids_tuple() == (0, 1, 2, 3)
        assert r._rings["any"].points() == \
            _Ring.from_ids(range(4)).points()

    def test_heap_least_matches_linear_scan(self, dec4):
        r = self._router(dec4)
        rng = np.random.RandomState(3)
        for _ in range(200):
            hid = int(rng.randint(0, 4))
            delta = 1 if rng.rand() < 0.6 or r._load[hid] == 0 else -1
            r._load_add(hid, delta)
            want = min(sorted(r._pools["any"]),
                       key=lambda h: (r._load[h], h))
            assert r._heap_least("any") == want

    def test_unavailable_message_is_bounded(self, dec4):
        r = self._router(dec4, n=6)
        r.submit([1, 2, 3, 4], max_new_tokens=4)
        for h in list(r.hosts.values()):
            r._evict(h)
        with pytest.raises(FleetUnavailable, match="unhealthy") as ei:
            r.step()
        msg = str(ei.value)
        assert "states:" in msg and "evicted=6" in msg
        assert "+2 more" in msg  # 6 hosts, 4 shown
        assert len(msg) < 400

    def test_routing_unchanged_vs_min_scan_reference(self, dec4):
        """Pick-by-heap + incremental ring reproduce the exact
        old-router choice (min over outstanding, ring over admitted
        pool) for a seeded submit stream."""
        r = self._router(dec4)
        rng = np.random.RandomState(9)
        base = [int(t) for t in rng.randint(0, CFG.vocab_size,
                                            size=(24,))]
        for i in range(12):
            prompt = base[: 8 + (i % 3) * 8] + [i]
            pool = sorted(r._pools["any"])
            want = min(pool, key=lambda h: (r._load[h], h))
            ring = _Ring.from_ids(pool)
            key = r._affinity_key(prompt)
            affine = ring.lookup(key)
            if affine is not None and \
                    r._load[affine] - r._load[want] <= r.affinity_gap:
                want = affine
            uid = r.submit(prompt, max_new_tokens=4)
            assert r._records[uid].host_id == want
            if i % 4 == 3:
                r.step()
        r.run()


# ---------------------------------------------------------------------------
# the scale policies live: a function of the seed; streaming handoff
# ---------------------------------------------------------------------------

class TestScalePolicies:
    HOSTS, REQUESTS = 6, 60

    def _leg(self, dec4):
        """Seeded Zipf-shared-prefix traffic on a virtual clock over
        six hosts with the rebalancer, the sharded streaming scrape and
        the straggler scan all live.  Outputs span more than one
        dispatch so prefix pages stay resident for the rebalancer."""
        plan = serve.TrafficPlan.from_seed(
            29, requests=self.REQUESTS, rate_rps=150.0,
            arrival="poisson", vocab_size=CFG.vocab_size, n_prefixes=8,
            prefix_len=16, zipf_s=1.2, shared_frac=0.7, prompt_min=2,
            prompt_scale=4.0, prompt_alpha=1.4, prompt_cap=24,
            output_min=6, output_scale=4.0, output_alpha=1.2,
            output_cap=16, priorities=(0, 2), interactive_max_prompt=16,
        )
        gen = serve.LoadGen(plan, step_cost_ms=2.0)
        hosts = [FleetHost(i, dec4, clock=gen.clock,
                           **dict(ENG_KW, max_len=48))
                 for i in range(self.HOSTS)]
        fr = obs.FlightRecorder(enabled=True)
        agg = obs.FleetAggregator()
        router = FleetRouter(
            hosts, registry=obs.MetricsRegistry(), clock=gen.clock,
            aggregator=agg, scrape_every=4, scrape_stream=True,
            rebalance=True, straggler_every=4, flightrec=fr,
        )
        return gen.run(router), router, fr, agg

    @pytest.fixture(scope="class")
    def two_runs(self, dec4):
        return self._leg(dec4), self._leg(dec4)

    def test_seeded_leg_replays_byte_identical(self, two_runs):
        """Routing, rebalancing and scrape pacing are functions of the
        seed and the virtual clock: two runs, one LoadReport — and the
        leg did exercise what it names."""
        (rep_a, router, _, agg), (rep_b, _, _, _) = two_runs
        assert rep_a.to_json() == rep_b.to_json()
        assert rep_a.completed == self.REQUESTS
        assert router.stats()["rebalances"] >= 1
        assert agg.scrapes > 0

    def test_seeded_leg_flightrec_identical(self, two_runs):
        """The postmortem of the same leg (routing decisions, prefix
        migrations, straggler flags) is byte-identical too."""
        (_, _, fr_a, _), (_, _, fr_b, _) = two_runs
        assert fr_a.recorded > 0
        assert json.dumps(fr_a.events()) == json.dumps(fr_b.events())

    @pytest.fixture(scope="class")
    def handoff_pair(self, dec4):
        """Six long prompts (three 16-token prefill chunks each) through
        a prefill/decode pair, monolithic then streamed."""
        rng = np.random.RandomState(0)
        pool = [int(t) for t in rng.randint(0, CFG.vocab_size,
                                            size=(48,))]
        prompts = [pool[s:s + n] for s, n in ((0, 40), (1, 44), (2, 38),
                                               (3, 42), (5, 40), (6, 43))]
        kw = dict(ENG_KW, slots=3)

        def leg(stream):
            hosts = [FleetHost(0, dec4, role="prefill", **kw),
                     FleetHost(1, dec4, role="decode", **kw)]
            router = FleetRouter(hosts, registry=obs.MetricsRegistry(),
                                 tracer=obs.Tracer(enabled=True),
                                 stream_handoff=stream)
            uids = [router.submit(p, max_new_tokens=8, temperature=0.0)
                    for p in prompts]
            out = router.run()
            return router, [out[u] for u in uids]

        return leg(False), leg(True)

    def test_streaming_handoff_tokens_match_monolithic(self, handoff_pair):
        (mono, out_m), (streamed, out_s) = handoff_pair
        assert out_s == out_m
        assert streamed.stats()["handoffs"] == \
            mono.stats()["handoffs"] == len(out_m)

    def test_streaming_keeps_only_the_tail_on_the_blocking_hop(
            self, handoff_pair):
        """Interior chunks ship while prefill still runs: the bytes that
        block the first decode step are a fraction of the handoff, and
        no chunk was aborted into the recompute fallback."""
        _, (streamed, _) = handoff_pair
        st = streamed.stats()
        assert st["handoff_chunks"] > 0
        assert st["handoff_chunk_aborts"] == 0
        assert 0 < streamed._stream_wire_bytes \
            < 0.5 * streamed._stream_total_bytes

    def test_streamed_flows_stitch_with_a_wire_segment(self, handoff_pair):
        """`tools/trace_report.py` reads the blocking hop out of the
        trace as each flow's `handoff_wire_ms`: a streamed handoff must
        leave that segment on every request's timeline, as a monolithic
        one does."""
        for router, outs in handoff_pair:
            stitcher = CorrelationStitcher()
            for ts, kind, name, payload in router.tracer.events:
                stitcher.feed_event({"type": kind, "name": name, "ts": ts,
                                     "attrs": payload})
            flows, _ = stitcher.finish()
            wired = [f for f in flows.values() if "handoff_wire_ms" in f]
            assert len(wired) == len(outs)
