"""Live train→serve checkpoint promotion tests (ISSUE 18).

The acceptance contract: only sidecar-complete steps are promotable
(mid-commit and torn-sidecar steps are invisible to the watcher); a
zero@4 checkpoint gathers through canonical form into a bundle whose
digest matches a direct verified restore; an identical-digest flip
mid-stream keeps every in-flight request token-exact; a changed-digest
swap recomputes in-flight work under the new weights; a failed host
swap rolls every already-promoted host back and leaves the fleet
digest-uniform on the OLD weights; and the promotion postmortem dumps
byte-identically across two seeded runs.
"""
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

import apex_tpu.serve as serve
from apex_tpu import amp, obs
from apex_tpu.checkpoint import (
    CHECKSUM_FILE,
    latest_step,
    restore_checkpoint,
    state_digest,
)
from apex_tpu.contrib.optimizers import DistributedFusedAdam
from apex_tpu.deploy import (
    CheckpointWatcher,
    PromotionController,
    PromotionError,
    reshard_for_serve,
)
from apex_tpu.fleet import FleetHost, FleetRouter
from apex_tpu.models.gpt import GPTConfig, GPTLM
from apex_tpu.obs.flightrec import read_flightrec
from apex_tpu.train.accum import (
    fsdp_init,
    reduction_carry_template,
    save_train_state,
    train_state_canonical,
    zero_init,
)

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "tools"))
import trace_report  # noqa: E402

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


def _save_zero(root, params, step, world=4):
    """Commit a zero@world train checkpoint of ``params`` — replicated
    fp32 masters + freshly initialized dp-sharded optimizer state,
    exactly what a train driver's ``save_train_state`` writes."""
    mesh = Mesh(np.array(jax.devices()[:world]), ("data",))
    amp_ = amp.initialize("O2")
    zopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
    spec = zopt.make_spec(params, world)
    rep = jax.device_put(params, NamedSharding(mesh, P()))
    carry = (rep, zero_init(zopt, amp_, params, spec, mesh))
    save_train_state(str(root), carry, step, mode="zero", mesh=mesh)
    return str(root)


@pytest.fixture(scope="module")
def zero_ckpt(tmp_path_factory, gpt_params):
    """zero@4 checkpoint of the SERVED weights (step 7) — promoting it
    is an identical-digest flip."""
    root = tmp_path_factory.mktemp("zero_ckpt")
    return _save_zero(root, gpt_params, 7)


@pytest.fixture(scope="module")
def bumped_params(gpt_params):
    return jax.tree_util.tree_map(
        lambda x: (x * (1.0 + 2.0 ** -12)).astype(x.dtype), gpt_params
    )


@pytest.fixture(scope="module")
def bumped_ckpt(tmp_path_factory, bumped_params):
    """zero@4 checkpoint of NUMERICALLY CHANGED weights (step 9) —
    promoting it must take the recompute path."""
    root = tmp_path_factory.mktemp("bumped_ckpt")
    return _save_zero(root, bumped_params, 9)


def _prompts():
    rng = np.random.RandomState(3)
    pool = [int(t) for t in rng.randint(0, CFG.vocab_size, size=(48,))]
    ps = [pool[0:5], pool[3:14], pool[7:15], pool[2:18]]
    ps.append(list(ps[1]))  # duplicate prompt: shared-prefix pages
    return ps


def _fleet(dec, n_hosts=2, **router_kw):
    hosts = [FleetHost(i, dec, **ENG_KW) for i in range(n_hosts)]
    # explicit fresh tracer: the ambient one may carry corr-stamped
    # events from earlier tests in the session, which would show up
    # as orphans in the merged-report test
    return FleetRouter(hosts, registry=obs.MetricsRegistry(),
                       tracer=obs.Tracer(enabled=True), **router_kw)


def _mid_stream(dec, new_tokens=24, rounds=2, **router_kw):
    """A fleet with every prompt submitted and a few rounds stepped —
    requests genuinely in flight when the promotion fires."""
    router = _fleet(dec, **router_kw)
    for p in _prompts():
        router.submit(p, max_new_tokens=new_tokens)
    for _ in range(rounds):
        router.step()
    return router


def _counter(router, name):
    return router.registry.counter(name).snapshot()["value"]


# ---------------------------------------------------------------------------
# the watcher: sidecar-complete visibility + watermark
# ---------------------------------------------------------------------------

class TestCheckpointWatcher:
    def test_reports_the_newest_verified_step_once(self, tmp_path,
                                                   gpt_params):
        root = _save_zero(tmp_path / "c", gpt_params, 3)
        _save_zero(root, gpt_params, 7)
        w = CheckpointWatcher(root)
        cand = w.poll()
        assert cand.step == 7 and cand.root == root
        assert cand.mode == "zero" and cand.world == 4
        assert len(cand.digest) == 64
        assert cand.outcome and cand.outcome["mode"] == "zero"
        # watermark: the same step is never reported twice
        assert w.watermark == 7
        assert w.poll() is None

    def test_mid_commit_step_is_invisible(self, tmp_path, gpt_params):
        """Orbax has published step 7's directory but the checksum
        sidecar has not landed: the restore path still sees the step,
        the deployment plane reports the previous verified one."""
        root = _save_zero(tmp_path / "c", gpt_params, 3)
        _save_zero(root, gpt_params, 7)
        os.remove(os.path.join(root, "7", CHECKSUM_FILE))
        assert latest_step(root) == 7
        cand = CheckpointWatcher(root).poll()
        assert cand is not None and cand.step == 3

    def test_torn_sidecar_hides_the_step(self, tmp_path, gpt_params):
        root = _save_zero(tmp_path / "c", gpt_params, 3)
        with open(os.path.join(root, "3", CHECKSUM_FILE), "w") as f:
            f.write('{"step": 3, "dig')  # torn mid-write
        assert CheckpointWatcher(root).poll() is None

    def test_start_after_skips_the_booted_step(self, zero_ckpt):
        w = CheckpointWatcher(zero_ckpt, start_after=7)
        assert w.poll() is None and w.watermark == 7


# ---------------------------------------------------------------------------
# the reshard bridge: zero@4 -> TP2 serve, digest parity
# ---------------------------------------------------------------------------

class TestReshardBridge:
    def test_zero4_to_tp2_digest_matches_direct_restore(self, zero_ckpt,
                                                        gpt_params):
        """The headline reshard: a zero@4 train checkpoint promoted
        onto a TP=2 serve mesh.  The bundle's digest must equal BOTH a
        direct verified restore's canonical params digest and the live
        served weights' digest (the checkpoint was saved from them) —
        moments dropped, dtypes matched, placement replicated."""
        dec_tp = serve.GPTDecoder(CFG, gpt_params, tokens_per_dispatch=4,
                                  mesh=serve.serve_mesh(2))
        bundle = reshard_for_serve(zero_ckpt, dec_tp)
        assert bundle.step == 7
        assert bundle.src_mode == "zero" and bundle.src_world == 4

        # direct restore baseline: template -> verify -> canonical
        tmpl = jax.tree_util.tree_map(
            lambda x: np.zeros(x.shape, np.float32), dec_tp.params
        )
        template = reduction_carry_template("zero", tmpl, 4,
                                            amp.initialize("O2"))
        restored, _ = restore_checkpoint(zero_ckpt, template, 7,
                                         verify=True)
        canon = train_state_canonical(restored, tmpl, 4, mode="zero")
        direct = jax.tree_util.tree_map(
            lambda x: np.asarray(x, np.float32), canon["params"]
        )
        assert bundle.digest == state_digest(direct)
        # ...and the served-weights identity (bitwise round trip)
        assert bundle.digest == state_digest(dec_tp.params)

        # moments dropped: the bundle IS a params tree, leaf-for-leaf
        assert (jax.tree_util.tree_structure(bundle.params)
                == jax.tree_util.tree_structure(dec_tp.params))
        # replicated placement on the TP mesh (the zero-compile
        # contract: compiled programs take params at P())
        for leaf in jax.tree_util.tree_leaves(bundle.params):
            assert leaf.sharding.spec == P(), leaf.sharding
        # aval parity with the running decoder: swap-ready
        for a, b in zip(jax.tree_util.tree_leaves(bundle.params),
                        jax.tree_util.tree_leaves(dec_tp.params)):
            assert a.shape == b.shape and a.dtype == b.dtype
        # provenance: the sidecar digest rode along
        sidecar = json.load(open(os.path.join(zero_ckpt, "7",
                                              CHECKSUM_FILE)))
        assert bundle.src_digest == sidecar["digest"]
        assert bundle.census and sum(bundle.census.values()) == len(
            jax.tree_util.tree_leaves(bundle.params)
        )

    def test_default_step_is_the_verified_latest(self, zero_ckpt, dec4):
        bundle = reshard_for_serve(zero_ckpt, dec4)
        assert bundle.step == 7
        assert bundle.digest == state_digest(dec4.params)

    def test_missing_root_raises(self, dec4, tmp_path):
        with pytest.raises(FileNotFoundError):
            reshard_for_serve(str(tmp_path / "nope"), dec4)


# ---------------------------------------------------------------------------
# identical-digest flip: token-exact mid-stream
# ---------------------------------------------------------------------------

class TestIdenticalFlip:
    def test_mid_stream_promotion_is_token_exact(self, dec4, zero_ckpt):
        clean = _fleet(dec4)
        for p in _prompts():
            clean.submit(p, max_new_tokens=24)
        baseline = clean.run()

        router = _mid_stream(dec4)
        cand = CheckpointWatcher(zero_ckpt).poll()
        ctl = PromotionController(router, drain_rounds=0)
        out = ctl.promote(cand)
        assert out["ok"] and out["identical"] and out["hosts"] == [0, 1]
        assert out["recomputed"] == 0
        # the flip really happened mid-stream: requests were in flight
        assert sum(s["kept"] for s in out["swaps"].values()) > 0
        for h in router.hosts.values():
            assert h.weights_digest == out["digest"]
        assert _counter(router, "deploy.promotions") == 1
        assert _counter(router, "deploy.rollbacks") == 0
        # ...and every stream finishes exactly as the clean run did
        assert router.run() == baseline

    def test_promote_with_no_admitted_hosts_raises(self, dec4,
                                                   zero_ckpt):
        router = _fleet(dec4)
        for h in router.hosts.values():
            h.state = "evicted"
        cand = CheckpointWatcher(zero_ckpt).poll()
        with pytest.raises(PromotionError, match="no admitted"):
            PromotionController(router).promote(cand)


class TestPromotionUnderLoad:
    ROUNDS = (4, 9, 14)

    def test_repeated_fsdp_promotions_replay_and_keep_every_token(
            self, dec4, gpt_params, tmp_path):
        """A seeded open-loop plan on the virtual clock with the fleet
        rolled through THREE promotions of an fsdp@2 checkpoint of the
        served weights while requests are in flight: the leg replays
        byte-identically, streams the clean leg's tokens, and no flip
        recomputed a request."""
        mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
        fopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        carry = fsdp_init(fopt, amp.initialize("O2"), gpt_params,
                          fopt.make_spec(gpt_params, 2), mesh)
        save_train_state(str(tmp_path), carry, 5, mode="fsdp", mesh=mesh)
        cand = CheckpointWatcher(str(tmp_path)).poll()
        assert cand.mode == "fsdp" and cand.world == 2
        plan = serve.TrafficPlan.from_seed(
            31, requests=24, rate_rps=200.0, arrival="poisson",
            vocab_size=CFG.vocab_size, n_prefixes=3, prefix_len=8,
            zipf_s=1.1, shared_frac=0.5, prompt_min=2, prompt_scale=5.0,
            prompt_alpha=1.3, prompt_cap=32, output_min=4,
            output_scale=8.0, output_alpha=1.1, output_cap=24,
            priorities=(0, 2), interactive_max_prompt=24,
        )

        class PromoteAtRounds:
            """The router, with a full rollout fired before the listed
            rounds' steps."""

            def __init__(self, router, rounds):
                self.router, self.rounds = router, set(rounds)
                self.ctl = PromotionController(router, drain_rounds=0)
                self.round, self.promos = 0, []

            def __getattr__(self, name):
                return getattr(self.router, name)

            def step(self):
                self.round += 1
                if self.round in self.rounds:
                    self.promos.append(self.ctl.promote(cand))
                return self.router.step()

        def leg(rounds):
            gen = serve.LoadGen(plan, step_cost_ms=4.0)
            hosts = [FleetHost(i, dec4, clock=gen.clock, **ENG_KW)
                     for i in range(2)]
            target = PromoteAtRounds(
                FleetRouter(hosts, registry=obs.MetricsRegistry(),
                            clock=gen.clock), rounds)
            return gen.run(target), target

        clean, _ = leg(())
        rep, target = leg(self.ROUNDS)
        assert rep.to_json() == leg(self.ROUNDS)[0].to_json()
        assert rep.tokens == clean.tokens
        assert len(target.promos) == len(self.ROUNDS)
        assert all(p["ok"] and p["identical"] and p["recomputed"] == 0
                   for p in target.promos)
        # the flips found requests in flight, or they proved nothing
        assert any(s["kept"] for p in target.promos
                   for s in p["swaps"].values())


# ---------------------------------------------------------------------------
# changed weights: the recompute fallback
# ---------------------------------------------------------------------------

class TestChangedWeights:
    def test_in_flight_recomputes_under_the_new_weights(
            self, dec4, bumped_ckpt, bumped_params):
        router = _mid_stream(dec4)
        old = router.hosts[0].weights_digest
        cand = CheckpointWatcher(bumped_ckpt).poll()
        out = PromotionController(router, drain_rounds=0).promote(cand)
        assert out["ok"] and not out["identical"]
        assert out["digest"] == state_digest(bumped_params) != old
        # cached K/V encoded the old weights: in-flight work was
        # preempted back to the queue and recomputed
        assert out["recomputed"] > 0
        assert _counter(router, "deploy.requests_recomputed") == \
            out["recomputed"]
        for h in router.hosts.values():
            assert h.weights_digest == out["digest"]
        # every request still completes its full budget
        done = router.run()
        assert len(done) == len(_prompts())
        assert all(len(t) == 24 for t in done.values())


# ---------------------------------------------------------------------------
# failed swap: rollback, blast radius one host
# ---------------------------------------------------------------------------

class TestRollback:
    def test_failed_swap_rolls_back_to_the_old_digest(
            self, dec4, bumped_ckpt, monkeypatch):
        fr = obs.FlightRecorder(enabled=True)
        router = _mid_stream(dec4, flightrec=fr)
        old = router.hosts[0].weights_digest

        def boom(bundle):
            raise RuntimeError("injected swap failure")

        monkeypatch.setattr(router.hosts[1], "swap_weights", boom)
        cand = CheckpointWatcher(bumped_ckpt).poll()
        out = PromotionController(router, drain_rounds=0).promote(cand)
        assert not out["ok"] and out["reason"] == "swap_failed"
        assert out["failed_host"] == 1 and out["rolled_back"] == [0]
        # the fleet is digest-uniform on the OLD weights again
        for h in router.hosts.values():
            assert h.weights_digest == old
        assert _counter(router, "deploy.promotions") == 0
        assert _counter(router, "deploy.rollbacks") == 1
        kinds = [e["kind"] for e in fr.events()]
        for k in ("deploy/swap_fail", "deploy/rollback", "deploy/abort"):
            assert k in kinds, kinds
        # both hosts were readmitted: the fleet still drains fully
        done = router.run()
        assert all(len(t) == 24 for t in done.values())

    def test_corrupt_step_fails_verify_and_nothing_moves(
            self, dec4, gpt_params, bumped_params, tmp_path):
        root = _save_zero(tmp_path / "c", bumped_params, 4)
        side = os.path.join(root, "4", CHECKSUM_FILE)
        doc = json.load(open(side))
        doc["digest"] = "0" * 64  # bytes no longer match the sidecar
        json.dump(doc, open(side, "w"))
        router = _mid_stream(dec4)
        old = router.hosts[0].weights_digest
        cand = CheckpointWatcher(root).poll()
        assert cand is not None  # poll is shallow; verify is the gate
        out = PromotionController(router).promote(cand)
        assert not out["ok"] and out["reason"] == "verify_failed"
        assert _counter(router, "deploy.verify_failures") == 1
        assert _counter(router, "deploy.rollbacks") == 0
        for h in router.hosts.values():
            assert h.weights_digest == old
        assert all(len(t) == 24 for t in router.run().values())


# ---------------------------------------------------------------------------
# the postmortem: byte-identical across seeded runs
# ---------------------------------------------------------------------------

class TestPostmortem:
    def test_two_seeded_runs_dump_identical_bytes(self, dec4,
                                                  zero_ckpt, tmp_path):
        def run(d):
            os.makedirs(d)
            router = _mid_stream(
                dec4, flightrec=obs.FlightRecorder(enabled=True))
            ctl = PromotionController(router, drain_rounds=0,
                                      dump_dir=str(d))
            out = ctl.promote(CheckpointWatcher(zero_ckpt).poll())
            assert out["ok"]
            router.run()
            return open(os.path.join(d, "flightrec.jsonl"), "rb").read()

        a = run(str(tmp_path / "a"))
        b = run(str(tmp_path / "b"))
        assert a == b  # logical-clock stamps: replayable postmortems
        meta, events = read_flightrec(str(tmp_path / "a"))
        assert meta["reason"] == "promotion"
        assert meta["corr"] == "promo-00000000" and meta["step"] == 7
        kinds = [e["kind"] for e in events]
        for k in ("deploy/candidate", "deploy/verify", "deploy/reshard",
                  "fleet/roll", "fleet/roll_calm", "fleet/roll_readmit",
                  "deploy/swap", "deploy/complete"):
            assert k in kinds, kinds
        assert kinds.count("deploy/swap") == 2  # one per host


# ---------------------------------------------------------------------------
# the merged report: deployment timeline, no promo orphans
# ---------------------------------------------------------------------------

class TestMergedTimeline:
    def test_merge_renders_the_promotion_without_orphans(
            self, dec4, zero_ckpt, tmp_path):
        router = _mid_stream(dec4)
        out = PromotionController(router, drain_rounds=0).promote(
            CheckpointWatcher(zero_ckpt).poll())
        assert out["ok"]
        router.run()

        root = str(tmp_path / "merge")
        os.makedirs(os.path.join(root, "router"))
        router.export_trace(os.path.join(root, "router", "trace.jsonl"))
        for h in router.hosts.values():
            d = os.path.join(root, f"host{h.host_id}")
            os.makedirs(d)
            h.export_trace(os.path.join(d, "trace.jsonl"))

        merged = trace_report.load_hosts([root])
        # promotion corrs never leak into the request stitcher
        flows, orphans = trace_report.stitch_correlations(merged)
        assert orphans == [], orphans
        text = trace_report.render_fleet(merged)
        assert "deployment timeline" in text
        assert "promo-00000000" in text
        assert "deploy/complete" in text and "complete" in text
        assert trace_report.main(["--merge", root]) == 0
