"""Declarative partition-rule sharding engine (ISSUE 13).

Four contracts:

- the RULES ENGINE: ordered regex rules over named pytree paths —
  first match wins, scalars never partition, unmatched leaves error
  loudly, one table projects onto any mesh shape, and tables
  serialize fingerprint-stably (the gang/checkpoint wire form);
- SPEC IDENTITY: every legacy hand-threaded spec constructor
  (``zero_state_spec``, serve's ``cache_pspec``/``paged_cache_pspec``)
  now derives from a rules table, and the ``APEX_TPU_SHARDING_RULES=0``
  kill switch restores literals that are SPEC-IDENTICAL to the
  derived ones;
- the FSDP reduction policy: params dp-sharded at rest, one
  all_gather + one reduce_scatter per boundary, gathered params
  bitwise-equal the ZeRO driver's (whose own parity vs the unsharded
  fp32-master reference is pinned in test_distributed_fused.py),
  overflow skip semantics identical, state never silently gathers;
- CROSS-RESHARD restore: a checkpoint saved under one rules outcome
  (zero, 4-way mesh) restores under another (fsdp, 2-way mesh) with
  params bitwise-equal the gather of the source state — the
  killed-and-resharded-gang contract of ROADMAP item 2c.
"""
import numpy as np

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, PartitionSpec as P

import apex_tpu.amp as amp
import apex_tpu.sharding as shd
from apex_tpu.contrib.optimizers import DistributedFusedAdam
from apex_tpu.contrib.optimizers.distributed_fused import _unflatten
from apex_tpu.parallel import replicate
from apex_tpu.train import (
    FusedTrainDriver,
    fsdp_init,
    fsdp_microbatch_step,
    fsdp_param_spec,
    fsdp_state_spec,
    read_metrics,
    zero_init,
    zero_microbatch_step,
    zero_state_spec,
)
from apex_tpu.train.accum import (
    carry_from_canonical,
    restore_train_state,
    save_train_state,
    train_state_canonical,
)

N_DEV = 8


class _Ph:
    """Shapeless path-matched placeholder leaf."""


def _mesh(n):
    return Mesh(np.array(jax.devices()[:n]), axis_names=("data",))


# ---------------------------------------------------------------------------
# the rules engine
# ---------------------------------------------------------------------------

class TestRulesEngine:
    def test_first_match_wins_and_paths(self):
        table = shd.RulesTable([
            (r"/qkv/kernel$", P(None, "model")),
            (r"kernel$", P("model")),
            (r".*", P()),
        ])
        tree = {"h0": {"qkv": {"kernel": _Ph()}, "proj": {"kernel": _Ph()}},
                "ln": {"scale": _Ph()}}
        specs = table.match(tree)
        assert specs["h0"]["qkv"]["kernel"] == P(None, "model")
        assert specs["h0"]["proj"]["kernel"] == P("model")
        assert specs["ln"]["scale"] == P()

    def test_scalars_never_partition(self):
        table = shd.RulesTable([(r".*", P("data"))])
        tree = {"big": jnp.ones((8, 8)), "scalar": jnp.float32(1.0),
                "one": jnp.ones((1,))}
        specs = table.match(tree)
        assert specs["big"] == P("data")
        assert specs["scalar"] == P()
        assert specs["one"] == P()

    def test_unmatched_leaf_errors_with_paths(self):
        table = shd.RulesTable([(r"w$", P())], name="partial")
        with pytest.raises(shd.UnmatchedLeafError, match="partial"):
            table.match({"w": _Ph(), "stray": {"leaf": _Ph()}})
        # replicate mode downgrades to P()
        lax_table = shd.RulesTable([(r"w$", P("data"))],
                                   on_unmatched="replicate")
        specs = lax_table.match({"w": jnp.ones((8,)),
                                 "stray": {"leaf": jnp.ones((8,))}})
        assert specs["stray"]["leaf"] == P()

    def test_catch_all_and_validation(self):
        assert shd.DEFAULT_RULES.catch_all
        assert not shd.RulesTable([("x", P())]).catch_all
        with pytest.raises(ValueError, match="compile"):
            shd.RulesTable([("(", P())])
        with pytest.raises(TypeError, match="PartitionSpec"):
            shd.RulesTable([(".*", "data")])
        with pytest.raises(ValueError, match="on_unmatched"):
            shd.RulesTable([(".*", P())], on_unmatched="ignore")

    def test_mesh_projection_drops_absent_axes(self):
        spec = P("fsdp", "model")
        assert shd.filter_spec(spec, ("data", "model")) == P(None, "model")
        assert shd.filter_spec(spec, ("data", "fsdp")) == P("fsdp")
        assert shd.filter_spec(spec, ("data",)) == P()
        # tuple dims keep only live axes
        assert shd.filter_spec(P(("data", "fsdp")), ("data",)) == P("data")

    def test_one_table_three_meshes(self):
        """The acceptance contract's engine half: DEFAULT_RULES over a
        GPT-shaped tree produces tp specs on dp×tp, fsdp specs on
        dp×fsdp, and all-replicated on pure dp — zero per-model code,
        zero unmatched leaves (full tri-model census pinned in the
        sharding_rules lint check)."""
        tree = {"layer_0": {"qkv": {"kernel": _Ph(), "bias": _Ph()},
                            "proj": {"kernel": _Ph()}},
                "wte": {"embedding": _Ph()},
                "ln_f": {"scale": _Ph()}}
        tp = shd.DEFAULT_RULES.match(tree, mesh=shd.train_mesh(2, tp=2))
        assert tp["layer_0"]["qkv"]["kernel"] == P(None, "model")
        assert tp["layer_0"]["proj"]["kernel"] == P("model")
        assert tp["wte"]["embedding"] == P(None, "model")
        fs = shd.DEFAULT_RULES.match(tree,
                                     mesh=shd.train_mesh(2, fsdp=2))
        assert fs["layer_0"]["qkv"]["kernel"] == P("fsdp")
        assert fs["layer_0"]["proj"]["kernel"] == P(None, "fsdp")
        dp = shd.DEFAULT_RULES.match(tree, mesh=shd.train_mesh(4))
        assert all(
            s == P() for s in jax.tree_util.tree_leaves(
                dp, is_leaf=lambda x: isinstance(x, P))
        )

    def test_json_round_trip_preserves_fingerprint(self):
        table = shd.default_rules()
        back = shd.RulesTable.from_json(table.to_json())
        assert back.fingerprint() == table.fingerprint()
        assert back.rules == table.rules

    def test_shard_and_gather_round_trip(self):
        mesh = shd.train_mesh(2, tp=2)
        tree = {"w": jnp.arange(64, dtype=jnp.float32).reshape(8, 8)}
        specs = {"w": P("data", "model")}
        sharded = shd.shard_tree(tree, specs, mesh)
        assert not sharded["w"].sharding.is_fully_replicated
        back = shd.gather_tree(sharded, mesh)
        assert back["w"].sharding.is_fully_replicated
        assert np.array_equal(np.asarray(back["w"]),
                              np.asarray(tree["w"]))

    def test_rules_outcome_and_differ(self):
        mesh4, mesh2 = _mesh(4), _mesh(2)
        tree = {"w": jnp.ones((8, 8))}
        a = shd.rules_outcome(shd.DEFAULT_RULES, tree, mesh4,
                              mode="zero")
        assert a["schema"] == shd.apply.OUTCOME_SCHEMA
        assert a["mesh"] == {"data": 4}
        assert not shd.outcomes_differ(a, a)
        assert shd.outcomes_differ(None, a)  # legacy = conservative
        b = shd.rules_outcome(shd.DEFAULT_RULES, tree, mesh2,
                              mode="fsdp")
        assert shd.outcomes_differ(a, b)
        c = shd.rules_outcome(shd.train_state_rules(), tree, mesh4,
                              mode="zero")
        assert shd.outcomes_differ(a, c)  # table changed, mesh same


# ---------------------------------------------------------------------------
# spec identity: rules-derived vs kill-switch literals
# ---------------------------------------------------------------------------

class TestSpecIdentity:
    def test_kill_switch_default_and_explicit(self, monkeypatch):
        assert shd.sharding_rules_default() is True
        monkeypatch.setenv("APEX_TPU_SHARDING_RULES", "0")
        assert shd.sharding_rules_default() is False
        assert shd.sharding_rules_default(True) is True  # explicit wins

    @pytest.mark.parametrize("build", [
        zero_state_spec,
        fsdp_state_spec,
        lambda: __import__("apex_tpu.serve.sharding",
                           fromlist=["x"]).cache_pspec(),
        lambda: __import__("apex_tpu.serve.sharding",
                           fromlist=["x"]).paged_cache_pspec(),
        lambda: __import__("apex_tpu.serve.sharding",
                           fromlist=["x"]).paged_cache_pspec(
                               quantized=True),
    ])
    def test_rules_and_legacy_spec_identical(self, build, monkeypatch):
        derived = build()
        monkeypatch.setenv("APEX_TPU_SHARDING_RULES", "0")
        legacy = build()
        assert derived == legacy

    def test_driver_accepts_rules_table_as_carry_spec(self):
        """The hand-threaded carry_spec literal is replaceable by the
        table itself — the driver path-matches the first dispatched
        carry and the ZeRO shards stay sharded through the window."""
        mesh = _mesh(N_DEV)
        amp_ = amp.initialize("O2")
        rng = np.random.RandomState(0)
        params = {"w": jnp.asarray(
            rng.randn(16, 4).astype(np.float32) * 0.3)}
        xs = jnp.asarray(rng.randn(4, 8, 16).astype(np.float32))
        ys = jnp.asarray(rng.randn(4, 8, 4).astype(np.float32))

        def grad_fn(carry, batch):
            p, state = carry[0], carry[1]
            x, y = batch

            def scaled(mp):
                loss = jnp.mean(jnp.square(x @ mp["w"] - y))
                return amp_.scale_loss(loss, state.scaler[0]), loss

            g, loss = jax.grad(scaled, has_aux=True)(p)
            return g, {"loss": jax.lax.pmean(loss, "data")}

        zopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        spec = zopt.make_spec(params, N_DEV)
        step = zero_microbatch_step(grad_fn, zopt, amp_, spec,
                                    microbatches=2)
        driver = FusedTrainDriver(
            step, steps_per_dispatch=2, mesh=mesh, check_vma=False,
            carry_spec=shd.train_state_rules(),
        )
        carry = (replicate(params, mesh),
                 zero_init(zopt, amp_, params, spec, mesh))
        carry, _ = driver.run_window(carry, (xs, ys))
        ms = carry[1].opt_state.master_shard
        assert ms.shape == (spec.padded,)
        assert not ms.sharding.is_fully_replicated
        # the table resolved to a real spec tree after first dispatch
        assert not isinstance(driver.carry_spec, shd.RulesTable)

    def test_rules_table_drives_the_programs_the_literal_spec_does(self):
        """Handing the driver the rules table instead of the literal
        `(P(), zero_state_spec())` changes nothing it runs: as many
        compiled programs over two windows, and a bitwise-equal master
        shard after them."""
        mesh = _mesh(N_DEV)
        amp_, grad_fn, params, xs, ys = _problem()
        zopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        spec = zopt.make_spec(params, N_DEV)

        def leg(carry_spec):
            step = zero_microbatch_step(grad_fn, zopt, amp_, spec,
                                        microbatches=2)
            driver = FusedTrainDriver(
                step, steps_per_dispatch=2, mesh=mesh, check_vma=False,
                carry_spec=carry_spec)
            carry = (replicate(_copy(params), mesh),
                     zero_init(zopt, amp_, _copy(params), spec, mesh))
            for w in range(2):
                sl = slice(4 * w, 4 * w + 4)
                carry, _ = driver.run_window(carry, (xs[sl], ys[sl]))
            master = carry[1].opt_state.master_shard
            return np.asarray(jax.device_get(master)), len(driver._programs)

        by_rules, n_rules = leg(shd.train_state_rules())
        by_literal, n_literal = leg((P(), zero_state_spec()))
        assert n_rules == n_literal == 1
        assert np.array_equal(by_rules, by_literal)

    def test_gang_rules_env_round_trip(self, monkeypatch):
        from apex_tpu.fleet.train import (
            GANG_RULES_ENV,
            gang_carry_spec,
            gang_rules,
        )

        table = shd.train_state_rules()
        monkeypatch.setenv(GANG_RULES_ENV, table.to_json())
        got = gang_rules()
        assert got.fingerprint() == table.fingerprint()
        spec = gang_carry_spec(
            {"params": {"w": _Ph()}, "master_shard": _Ph()}
        )
        assert spec["master_shard"] == P("data")
        assert spec["params"]["w"] == P()
        monkeypatch.delenv(GANG_RULES_ENV)
        assert gang_rules().fingerprint() == table.fingerprint()


# ---------------------------------------------------------------------------
# the fsdp reduction policy
# ---------------------------------------------------------------------------

def _problem():
    amp_ = amp.initialize("O2")
    rng = np.random.RandomState(0)
    params = {"w1": jnp.asarray(rng.randn(16, 8).astype(np.float32) * 0.3),
              "w2": jnp.asarray(rng.randn(8, 4).astype(np.float32) * 0.3)}
    xs = jnp.asarray(rng.randn(8, 32, 16).astype(np.float32))
    ys = jnp.asarray(rng.randn(8, 32, 4).astype(np.float32))

    def grad_fn(carry, batch):
        p, state = carry[0], carry[1]
        x, y = batch

        def scaled(mp):
            h = jnp.tanh(x @ mp["w1"])
            loss = jnp.mean(jnp.square(h @ mp["w2"] - y))
            return amp_.scale_loss(loss, state.scaler[0]), loss

        grads, loss = jax.grad(scaled, has_aux=True)(p)
        return grads, {"loss": jax.lax.pmean(loss, "data")}

    return amp_, grad_fn, params, xs, ys


def _copy(t):
    return jax.tree_util.tree_map(lambda x: jnp.array(x, copy=True), t)


def _run_zero(amp_, grad_fn, params, xs, ys, mesh, zopt, m=2, k=2):
    spec = zopt.make_spec(params, N_DEV)
    step = zero_microbatch_step(grad_fn, zopt, amp_, spec,
                                microbatches=m)
    driver = FusedTrainDriver(
        step, steps_per_dispatch=k, mesh=mesh, check_vma=False,
        carry_spec=(P(), zero_state_spec()),
        metrics={"skipped": "sum"},
    )
    carry = (replicate(_copy(params), mesh),
             zero_init(zopt, amp_, _copy(params), spec, mesh))
    skipped = 0.0
    for w in range(xs.shape[0] // (k * m)):
        sl = slice(w * k * m, (w + 1) * k * m)
        carry, res = driver.run_window(carry, (xs[sl], ys[sl]))
        skipped += read_metrics(res.metrics)["skipped"]
    return carry, skipped


def _run_fsdp(amp_, grad_fn, params, xs, ys, mesh, fopt, m=2, k=2):
    spec = fopt.make_spec(params, N_DEV)
    step = fsdp_microbatch_step(grad_fn, fopt, amp_, spec,
                                microbatches=m)
    driver = FusedTrainDriver(
        step, steps_per_dispatch=k, mesh=mesh, check_vma=False,
        carry_spec=(fsdp_param_spec(), fsdp_state_spec()),
        metrics={"skipped": "sum"},
    )
    carry = fsdp_init(fopt, amp_, _copy(params), spec, mesh)
    skipped = 0.0
    for w in range(xs.shape[0] // (k * m)):
        sl = slice(w * k * m, (w + 1) * k * m)
        carry, res = driver.run_window(carry, (xs[sl], ys[sl]))
        skipped += read_metrics(res.metrics)["skipped"]
    return carry, skipped, spec


class TestFsdpPolicy:
    def test_fsdp_matches_zero_bitwise(self, mesh8):
        """The no-compression parity gate: fsdp and zero run the SAME
        reduce_scatter + shard update arithmetic — only the params'
        resting representation differs — so the gathered fsdp params,
        the moment shards and the whole scaler trajectory must equal
        the zero driver's BITWISE (zero itself is parity-gated to the
        unsharded fp32-master reference)."""
        amp_, grad_fn, params, xs, ys = _problem()
        zopt = DistributedFusedAdam(lr=1e-2, weight_decay=0.01,
                                    axis_name="data")
        (zc, skipped_z) = _run_zero(amp_, grad_fn, params, xs, ys,
                                    mesh8, zopt)
        fc, skipped_f, spec = _run_fsdp(amp_, grad_fn, params, xs, ys,
                                        mesh8, zopt)
        assert skipped_z == skipped_f == 0.0
        full = _unflatten(jnp.asarray(
            np.asarray(jax.device_get(fc[0]))), spec)
        for key in params:
            assert np.array_equal(
                np.asarray(jax.device_get(zc[0][key])),
                np.asarray(full[key]),
            ), key
        assert np.array_equal(
            np.asarray(jax.device_get(zc[1].opt_state.m_shard)),
            np.asarray(jax.device_get(fc[1].opt_state.m_shard)))
        assert float(zc[1].scaler[0].loss_scale) == \
            float(fc[1].scaler[0].loss_scale)

    def test_fsdp_mid_window_overflow_skips_like_zero(self, mesh8):
        """A planted inf mid-window: both policies skip the SAME one
        boundary and back the scale off once — the psum-agreed
        overflow vote over the non-replicated shard works."""
        amp_, grad_fn, params, xs, ys = _problem()
        xs = xs.at[2, 0, 0].set(jnp.inf)
        zopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        (zc, skipped_z) = _run_zero(amp_, grad_fn, params, xs, ys,
                                    mesh8, zopt)
        fc, skipped_f, spec = _run_fsdp(amp_, grad_fn, params, xs, ys,
                                        mesh8, zopt)
        assert skipped_z == skipped_f == 1.0
        full = _unflatten(jnp.asarray(
            np.asarray(jax.device_get(fc[0]))), spec)
        for key in params:
            assert np.array_equal(
                np.asarray(jax.device_get(zc[0][key])),
                np.asarray(full[key]))
        assert float(fc[1].scaler[0].loss_scale) == 2.0 ** 15

    def test_params_stay_sharded_at_rest(self, mesh8):
        """THE fsdp claim: the carry's params slot comes back a flat
        1/world shard, never a gathered tree — the memory win survives
        the driver round trip."""
        amp_, grad_fn, params, xs, ys = _problem()
        fopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        fc, _, spec = _run_fsdp(amp_, grad_fn, params, xs, ys, mesh8,
                                fopt)
        assert fc[0].shape == (spec.padded,)
        assert not fc[0].sharding.is_fully_replicated
        assert fc[0].addressable_data(0).size == spec.padded // N_DEV
        assert not fc[1].opt_state.m_shard.sharding.is_fully_replicated

    def test_state_bytes_per_replica(self, mesh8):
        """The memory claim of the three reduction policies, as exact
        byte math on what one device holds of a fresh Adam carry over
        N fp32 parameters at world W (16 bytes of step and scaler
        scalars in each): mean keeps params and both moments whole,
        zero keeps params whole and master + moments at 1/W, fsdp keeps
        the flat master and the moments at 1/W and nothing whole."""
        from apex_tpu.optimizers import fused_adam

        amp_ = amp.initialize("O2")
        params = {"w": jnp.ones((64, 32), jnp.float32)}
        n, scalars = 4 * 64 * 32, 16

        def held(tree):
            return sum(leaf.addressable_data(0).nbytes
                       for leaf in jax.tree_util.tree_leaves(tree))

        opt = amp.AmpOptimizer(fused_adam(1e-2), amp_)
        zopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        spec = zopt.make_spec(params, N_DEV)
        assert spec.padded == 64 * 32
        mean = (replicate(params, mesh8),
                replicate(opt.init(params), mesh8))
        zero = (replicate(params, mesh8),
                zero_init(zopt, amp_, params, spec, mesh8))
        fsdp = fsdp_init(zopt, amp_, params, spec, mesh8)
        assert held(mean) == 3 * n + scalars
        assert held(zero) == n + 3 * n // N_DEV + scalars
        assert held(fsdp) == 3 * n // N_DEV + scalars

    def test_fsdp_rejects_lamb(self, mesh8):
        from apex_tpu.contrib.optimizers import DistributedFusedLAMB

        amp_, grad_fn, params, _, _ = _problem()
        lamb = DistributedFusedLAMB(lr=1e-2, axis_name="data")
        spec = lamb.make_spec(params, N_DEV)
        with pytest.raises(NotImplementedError, match="LAMB"):
            fsdp_microbatch_step(grad_fn, lamb, amp_, spec)
        with pytest.raises(NotImplementedError, match="LAMB"):
            fsdp_init(lamb, amp_, params, spec, mesh8)


# ---------------------------------------------------------------------------
# cross-reshard checkpoint restore
# ---------------------------------------------------------------------------

class TestCrossReshard:
    def _trained_zero_carry(self, mesh4, amp_, grad_fn, params, xs, ys):
        zopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        spec = zopt.make_spec(params, 4)
        step = zero_microbatch_step(grad_fn, zopt, amp_, spec,
                                    microbatches=2)
        driver = FusedTrainDriver(
            step, steps_per_dispatch=2, mesh=mesh4, check_vma=False,
            carry_spec=(P(), zero_state_spec()),
        )
        carry = (replicate(_copy(params), mesh4),
                 zero_init(zopt, amp_, _copy(params), spec, mesh4))
        carry, _ = driver.run_window(carry, (xs[:4], ys[:4]))
        return carry, zopt, spec

    def test_zero4_to_fsdp2_restores_bitwise(self, tmp_path):
        """The acceptance gate: save under a ZeRO rules outcome on a
        4-way mesh, restore under an fsdp table on a 2-way mesh (the
        killed-and-resharded gang), final params bitwise-equal the
        gather of the source state — and the restored carry TRAINS."""
        amp_, grad_fn, params, xs, ys = _problem()
        mesh4, mesh2 = _mesh(4), _mesh(2)
        carry, zopt, spec4 = self._trained_zero_carry(
            mesh4, amp_, grad_fn, params, xs, ys)
        src = {k: np.asarray(jax.device_get(carry[0][k]))
               for k in carry[0]}
        src_m = np.asarray(jax.device_get(carry[1].opt_state.m_shard))
        path = str(tmp_path / "ckpt")
        save_train_state(path, carry, 2, mode="zero", mesh=mesh4)

        from apex_tpu import checkpoint

        doc = checkpoint.read_sharding_outcome(path)
        assert doc is not None and doc["mode"] == "zero"
        assert doc["mesh"] == {"data": 4}

        fc, step = restore_train_state(
            path, params, opt=zopt, amp_=amp_, mode="fsdp", mesh=mesh2)
        assert step == 2
        spec2 = zopt.make_spec(params, 2)
        assert fc[0].shape == (spec2.padded,)
        assert not fc[0].sharding.is_fully_replicated
        full = _unflatten(jnp.asarray(
            np.asarray(jax.device_get(fc[0]))), spec2)
        for key in params:
            assert np.array_equal(np.asarray(full[key]), src[key]), key
        # moments: real (non-padding) elements survive the re-layout
        m_full = _unflatten(jnp.asarray(np.asarray(
            jax.device_get(fc[1].opt_state.m_shard))), spec2)
        m_src = _unflatten(jnp.asarray(src_m), spec4)
        for key in params:
            assert np.array_equal(np.asarray(m_full[key]),
                                  np.asarray(m_src[key])), key
        # the resharded carry keeps training on the NEW mesh
        fstep = fsdp_microbatch_step(grad_fn, zopt, amp_, spec2,
                                     microbatches=2)
        driver = FusedTrainDriver(
            fstep, steps_per_dispatch=2, mesh=mesh2, check_vma=False,
            carry_spec=(fsdp_param_spec(), fsdp_state_spec()),
        )
        fc, res = driver.run_window(fc, (xs[4:8], ys[4:8]))
        assert np.isfinite(read_metrics(res.metrics)["loss"])

    def test_fsdp2_to_zero4_restores_bitwise(self, tmp_path):
        """The REVERSE direction PR 13 left uncovered (ISSUE 14): an
        fsdp checkpoint on a 2-way mesh restores under a ZeRO table on
        a 4-way mesh — the gang that GREW back after an elastic shrink
        — with params bitwise-equal the gather of the source state,
        moments preserved, and the restored carry training on."""
        amp_, grad_fn, params, xs, ys = _problem()
        mesh2, mesh4 = _mesh(2), _mesh(4)
        fopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        spec2 = fopt.make_spec(params, 2)
        fstep = fsdp_microbatch_step(grad_fn, fopt, amp_, spec2,
                                     microbatches=2)
        driver = FusedTrainDriver(
            fstep, steps_per_dispatch=2, mesh=mesh2, check_vma=False,
            carry_spec=(fsdp_param_spec(), fsdp_state_spec()),
        )
        carry = fsdp_init(fopt, amp_, _copy(params), spec2, mesh2)
        carry, _ = driver.run_window(carry, (xs[:4], ys[:4]))
        src = _unflatten(jnp.asarray(
            np.asarray(jax.device_get(carry[0]))), spec2)
        src_m = _unflatten(jnp.asarray(np.asarray(
            jax.device_get(carry[1].opt_state.m_shard))), spec2)
        path = str(tmp_path / "ckpt")
        save_train_state(path, carry, 2, mode="fsdp", mesh=mesh2)

        from apex_tpu import checkpoint

        doc = checkpoint.read_sharding_outcome(path)
        assert doc is not None and doc["mode"] == "fsdp"
        assert doc["mesh"] == {"data": 2}

        zc, step = restore_train_state(
            path, params, opt=fopt, amp_=amp_, mode="zero", mesh=mesh4)
        assert step == 2
        for key in params:
            assert np.array_equal(
                np.asarray(jax.device_get(zc[0][key])),
                np.asarray(src[key])), key
        spec4 = fopt.make_spec(params, 4)
        ms = zc[1].opt_state.master_shard
        assert ms.shape == (spec4.padded,)
        assert not ms.sharding.is_fully_replicated
        m_back = _unflatten(jnp.asarray(np.asarray(
            jax.device_get(zc[1].opt_state.m_shard))), spec4)
        for key in params:
            assert np.array_equal(np.asarray(m_back[key]),
                                  np.asarray(src_m[key])), key
        # the regrown carry keeps training under zero on the 4-way mesh
        zstep = zero_microbatch_step(grad_fn, fopt, amp_, spec4,
                                     microbatches=2)
        zdriver = FusedTrainDriver(
            zstep, steps_per_dispatch=2, mesh=mesh4, check_vma=False,
            carry_spec=(P(), zero_state_spec()),
        )
        zc, res = zdriver.run_window(zc, (xs[4:8], ys[4:8]))
        assert np.isfinite(read_metrics(res.metrics)["loss"])

    def test_same_outcome_restores_without_reshard(self, tmp_path):
        """Same table, mesh and mode: the restore is a plain
        round-trip (canonicalization is the identity) — params AND
        flat layout bitwise."""
        amp_, grad_fn, params, xs, ys = _problem()
        mesh4 = _mesh(4)
        carry, zopt, spec4 = self._trained_zero_carry(
            mesh4, amp_, grad_fn, params, xs, ys)
        master = np.asarray(
            jax.device_get(carry[1].opt_state.master_shard))
        path = str(tmp_path / "ckpt")
        save_train_state(path, carry, 2, mode="zero", mesh=mesh4)
        zc, step = restore_train_state(
            path, params, opt=zopt, amp_=amp_, mode="zero", mesh=mesh4)
        assert step == 2
        assert np.array_equal(
            np.asarray(jax.device_get(zc[1].opt_state.master_shard)),
            master)
        for key in params:
            assert np.array_equal(
                np.asarray(jax.device_get(zc[0][key])),
                np.asarray(jax.device_get(carry[0][key])))

    def test_canonical_round_trip_is_identity(self, mesh8):
        """carry -> canonical -> carry preserves every real element
        through a world-size change (8 -> 2 -> gather)."""
        amp_, grad_fn, params, xs, ys = _problem()
        fopt = DistributedFusedAdam(lr=1e-2, axis_name="data")
        fc, _, spec8 = _run_fsdp(amp_, grad_fn, params, xs, ys, mesh8,
                                 fopt)
        canon = train_state_canonical(fc, params, N_DEV, mode="fsdp")
        mesh2 = _mesh(2)
        rebuilt = carry_from_canonical(canon, mode="fsdp", opt=fopt,
                                       mesh=mesh2)
        spec2 = fopt.make_spec(params, 2)
        a = _unflatten(jnp.asarray(np.asarray(
            jax.device_get(fc[0]))), spec8)
        b = _unflatten(jnp.asarray(np.asarray(
            jax.device_get(rebuilt[0]))), spec2)
        for key in params:
            assert np.array_equal(np.asarray(a[key]),
                                  np.asarray(b[key]))

    def test_bad_mode_rejected(self):
        amp_ = amp.initialize("O2")
        with pytest.raises(ValueError, match="mode"):
            train_state_canonical(({}, None), {}, 2, mode="mean")
        from apex_tpu.train.accum import reduction_carry_template

        with pytest.raises(ValueError, match="mode"):
            reduction_carry_template("ddp", {"w": jnp.ones((4,))}, 2,
                                     amp_)
