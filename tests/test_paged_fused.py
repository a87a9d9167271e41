"""ISSUE 20: fused paged-attention serving kernel + tree speculation.

Parity contract (a kernel no chip has run stays off by default):
the fused kernel (`apex_tpu.ops.attention.paged_fused_attention` —
interpret mode off-TPU) must BITWISE-match the materializing path at
fp32, the O2 bf16 policy, and int8 pages.  Comparisons are
JITTED-vs-JITTED: an eager per-op build legitimately differs from a
whole-program XLA build by ~1 ulp on CPU, and serving only ever runs
jitted programs, so jitted programs are what the gate pins.

On top of the kernel: greedy token-identity through the decoder windows
and the engine across fused/unfused x spec/non-spec x TP2, preemption
mid-speculation, tree speculation (branch 0 == chain, forced branch
wins, parking compaction) and acceptance-histogram draft auto-tuning.
Heavy compose points ride the `slow` marker.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from apex_tpu.models.gpt import GPTConfig, GPTLM
from apex_tpu.ops.attention import (
    paged_cached_attention,
    paged_fused_attention,
    paged_fused_default,
    quantize_kv,
)
from apex_tpu.serve import (
    GPTDecoder,
    ServeEngine,
    reference_generate,
    serve_mesh,
)
from apex_tpu.serve.decode import (
    paged_fused_serve_default,
    propose_ngram,
    propose_ngram_tree,
    spec_autotune_default,
    spec_tree_default,
)
from apex_tpu.serve.kv_cache import PagedKVCache


def tiny_cfg(dtype=jnp.float32):
    return GPTConfig.tiny(compute_dtype=dtype, dropout_rate=0.0,
                          attn_dropout_rate=0.0)


@pytest.fixture(scope="module")
def lm():
    cfg = tiny_cfg()
    model = GPTLM(cfg)
    rng = np.random.RandomState(0)
    ids = rng.randint(0, cfg.vocab_size, size=(1, 32))
    params = model.init(jax.random.PRNGKey(0), jnp.asarray(ids))["params"]
    return cfg, params, ids[0]


# ---------------------------------------------------------------------------
# op-level bitwise parity grid
# ---------------------------------------------------------------------------

def _pool_problem(dtype, t, masked, layers=2, seed=3):
    """A small paged-read problem: 5D pools (`layers` layers), two
    slots with different cache lengths, T new tokens."""
    rng = np.random.RandomState(seed)
    b, h, d, page_len, pps = 2, 2, 8, 8, 3
    num_pages = 1 + b * pps
    s_total = pps * page_len

    def mk(shape):
        return jnp.asarray(rng.randn(*shape).astype(np.float32) * 0.3)

    pool_k = mk((num_pages, layers, h, page_len, d))
    pool_v = mk((num_pages, layers, h, page_len, d))
    ksc = vsc = None
    if dtype == "bf16":
        pool_k = pool_k.astype(jnp.bfloat16)
        pool_v = pool_v.astype(jnp.bfloat16)
    elif dtype == "int8":
        pool_k, ksc = quantize_kv(pool_k)
        pool_v, vsc = quantize_kv(pool_v)
    table = jnp.asarray(
        np.arange(1, 1 + b * pps, dtype=np.int32).reshape(b, pps))
    lengths = jnp.asarray([s_total - 5, s_total // 2], jnp.int32)
    q, kn, vn = mk((b, h, t, d)), mk((b, h, t, d)), mk((b, h, t, d))
    positions = lengths[:, None] + jnp.arange(t, dtype=jnp.int32)
    bm = None
    if masked:
        # the tree-verify mask: root + two (t-1)//2-deep branches
        w, dep = 2, (t - 1) // 2
        bv = [-1] + [r for r in range(w) for _ in range(dep)]
        bm = jnp.asarray(
            [[bv[kk] < 0 or bv[kk] == bv[qq] for kk in range(t)]
             for qq in range(t)])
    return dict(q=q, k_new=kn, v_new=vn, positions=positions,
                pool_k=pool_k, pool_v=pool_v, page_table=table,
                cache_lengths=lengths, pool_k_scale=ksc,
                pool_v_scale=vsc, block_mask=bm)


class TestFusedKernelParity:
    @pytest.mark.parametrize("dtype", ["fp32", "bf16", "int8"])
    @pytest.mark.parametrize("t,masked", [(1, False), (4, False),
                                          (5, True)])
    def test_bitwise_vs_materializing(self, dtype, t, masked):
        p = _pool_problem(dtype, t, masked)
        q, kn, vn = p.pop("q"), p.pop("k_new"), p.pop("v_new")
        for layer in (0, 1):
            ref = jax.jit(lambda a, b, c: paged_cached_attention(
                a, b, c, layer=layer, use_fused=False, **p))(q, kn, vn)
            got = jax.jit(lambda a, b, c: paged_fused_attention(
                a, b, c, layer=layer, **p))(q, kn, vn)
            assert got.dtype == ref.dtype
            assert np.array_equal(np.asarray(got, np.float32),
                                  np.asarray(ref, np.float32)), (
                dtype, t, masked, layer,
                np.abs(np.asarray(got, np.float32)
                       - np.asarray(ref, np.float32)).max())

    def test_4d_pool_layer_slice(self):
        """4D (single-layer-slice) pools take the same fused path as
        5D pools with layer=0."""
        p = _pool_problem("fp32", 2, False, layers=1)
        q, kn, vn = p.pop("q"), p.pop("k_new"), p.pop("v_new")
        p4 = dict(p, pool_k=p["pool_k"][:, 0], pool_v=p["pool_v"][:, 0])
        ref = jax.jit(lambda a, b, c: paged_cached_attention(
            a, b, c, use_fused=False, **p4))(q, kn, vn)
        got = jax.jit(lambda a, b, c: paged_fused_attention(
            a, b, c, **p4))(q, kn, vn)
        assert np.array_equal(np.asarray(got), np.asarray(ref))

    def test_dispatch_respects_use_fused_flag(self):
        """paged_cached_attention(use_fused=True) routes to the fused
        kernel and matches its output exactly."""
        p = _pool_problem("int8", 3, False)
        q, kn, vn = p.pop("q"), p.pop("k_new"), p.pop("v_new")
        a = jax.jit(lambda x, y, z: paged_cached_attention(
            x, y, z, use_fused=True, **p))(q, kn, vn)
        b = jax.jit(lambda x, y, z: paged_fused_attention(
            x, y, z, **p))(q, kn, vn)
        assert np.array_equal(np.asarray(a), np.asarray(b))

    def test_default_off(self, monkeypatch):
        """The ROADMAP carried-risk rule: the fused path is opt-in
        until a live-TPU session runs tools/check_paged_fused.py."""
        monkeypatch.delenv("APEX_TPU_PAGED_FUSED", raising=False)
        assert paged_fused_default() is False
        assert paged_fused_serve_default(None) is False
        monkeypatch.setenv("APEX_TPU_PAGED_FUSED", "1")
        assert paged_fused_default() is True
        assert paged_fused_serve_default(None) is True
        # explicit arg beats the env
        assert paged_fused_serve_default(False) is False


# ---------------------------------------------------------------------------
# decoder/engine greedy token identity, fused vs materializing
# ---------------------------------------------------------------------------

def _drain(cfg, params, prompts, budget=18, mesh=None, engine_kw=None,
           **deckw):
    dec = GPTDecoder(cfg, params, tokens_per_dispatch=4, mesh=mesh,
                     **deckw)
    eng = ServeEngine(dec, slots=2, max_len=64, paged=True, page_len=8,
                      prefill_chunk=8, **(engine_kw or {}))
    uids = [eng.submit(p, max_new_tokens=budget) for p in prompts]
    out = eng.run()
    return [out[u] for u in uids], eng


class TestFusedServeIdentity:
    def test_greedy_identity_fp32(self, lm):
        cfg, params, pool = lm
        prompts = [[int(t) for t in pool[:6]],
                   [int(t) for t in pool[3:12]]]
        base, _ = _drain(cfg, params, prompts)
        fused, _ = _drain(cfg, params, prompts, paged_fused=True)
        assert fused == base
        assert base[0] == reference_generate(cfg, params, prompts[0], 18)

    def test_greedy_identity_spec_compose(self, lm):
        cfg, params, pool = lm
        prompts = [[int(t) for t in pool[:2]] * 4]
        base, _ = _drain(cfg, params, prompts, spec_tokens=2)
        fused, _ = _drain(cfg, params, prompts, spec_tokens=2,
                          paged_fused=True)
        assert fused == base

    def test_greedy_identity_int8(self, lm):
        cfg, params, pool = lm
        prompts = [[int(t) for t in pool[:6]]]
        base, _ = _drain(cfg, params, prompts, kv_int8=True)
        fused, _ = _drain(cfg, params, prompts, kv_int8=True,
                          paged_fused=True)
        assert fused == base

    def test_greedy_identity_bf16_o2(self):
        """The O2 policy point of the gate: bf16 compute + bf16 pages."""
        cfg = tiny_cfg(jnp.bfloat16)
        model = GPTLM(cfg)
        rng = np.random.RandomState(1)
        ids = rng.randint(0, cfg.vocab_size, size=(1, 16))
        params = model.init(jax.random.PRNGKey(0),
                            jnp.asarray(ids))["params"]
        prompts = [[int(t) for t in ids[0, :7]]]
        base, _ = _drain(cfg, params, prompts, budget=12)
        fused, _ = _drain(cfg, params, prompts, budget=12,
                          paged_fused=True)
        assert fused == base

    def test_greedy_identity_tp2_spec(self, lm):
        """The acceptance grid's TP2 point: fused x spec x TP2."""
        cfg, params, pool = lm
        prompts = [[int(t) for t in pool[:2]] * 3]
        base, _ = _drain(cfg, params, prompts, budget=12,
                         mesh=serve_mesh(2), spec_tokens=2)
        fused, _ = _drain(cfg, params, prompts, budget=12,
                          mesh=serve_mesh(2), spec_tokens=2,
                          paged_fused=True)
        assert fused == base

    @pytest.mark.slow
    def test_greedy_identity_tp2_int8_tree(self, lm):
        """The heaviest compose point: fused x int8 x tree x TP2."""
        cfg, params, pool = lm
        prompts = [[int(t) for t in pool[:2]] * 4,
                   [int(t) for t in pool[5:9]]]
        kw = dict(budget=14, mesh=serve_mesh(2), kv_int8=True,
                  spec_tokens=2, spec_tree=2)
        base, _ = _drain(cfg, params, prompts, **kw)
        fused, _ = _drain(cfg, params, prompts, paged_fused=True, **kw)
        assert fused == base

    def test_preemption_mid_speculation(self, lm):
        """A pool too small for both sequences under the speculative
        write horizon: preemption + re-prefill mid-speculation keeps
        the fused engine's streams exactly the references."""
        cfg, params, pool = lm
        p1 = [int(t) for t in pool[:6]]
        p2 = [int(t) for t in pool[10:17]]
        for fused in (False, True):
            dec = GPTDecoder(cfg, params, tokens_per_dispatch=4,
                             spec_tokens=2, paged_fused=fused)
            eng = ServeEngine(dec, slots=2, max_len=32, paged=True,
                              page_len=8, num_pages=6, prefill_chunk=8)
            u1 = eng.submit(p1, max_new_tokens=20)
            u2 = eng.submit(p2, max_new_tokens=20)
            out = eng.run()
            assert eng.stats()["preemptions"] >= 1
            assert out[u1] == reference_generate(cfg, params, p1, 20)
            assert out[u2] == reference_generate(cfg, params, p2, 20)


# ---------------------------------------------------------------------------
# tree speculation
# ---------------------------------------------------------------------------

class TestTreeSpeculation:
    def test_branch0_is_chain_proposal(self):
        rng = np.random.RandomState(0)
        hist = jnp.asarray(rng.randint(-1, 40, size=(5, 24)), jnp.int32)
        for draft in (1, 3):
            for width in (2, 3):
                tree = propose_ngram_tree(hist, draft, width)
                assert tree.shape == (5, width, draft)
                chain = propose_ngram(hist, draft)
                assert np.array_equal(np.asarray(tree[:, 0]),
                                      np.asarray(chain))

    def test_tree_greedy_identity_and_acceptance(self, lm):
        """Tree and chain engines emit identical greedy streams on a
        repetitive workload, and tree accepted-tokens/dispatch never
        falls below chain (branch 0 IS the chain proposal)."""
        cfg, params, pool = lm
        prompts = [[int(pool[0]), int(pool[1])] * 4]
        chain, ec = _drain(cfg, params, prompts, spec_tokens=2)
        tree, et = _drain(cfg, params, prompts, spec_tokens=2,
                          spec_tree=2)
        assert tree == chain
        sc = ec.stats()["spec"]
        st = et.stats()["spec"]
        assert (st["mean_tokens_per_dispatch"]
                >= sc["mean_tokens_per_dispatch"])
        assert st["tree"]["width"] == 2
        assert st["tree"]["verify_steps"] > 0

    def test_forced_branch_win_tokens_exact(self, lm):
        """Poisoned history: the chain proposal (branch 0) drafts a
        WRONG continuation while branch 1 drafts the model's true
        greedy tokens — the verify must select branch 1, compact its
        parked K/V into the canonical slots, and the NEXT step (which
        reads those slots) must still match the reference."""
        cfg, params, pool = lm
        prompt = [int(t) for t in pool[:8]]
        ref = reference_generate(cfg, params, prompt, 10)
        dec = GPTDecoder(cfg, params, tokens_per_dispatch=4,
                         spec_tokens=2, spec_tree=2)
        slots, max_len, page_len = 2, 64, 8
        pps = max_len // page_len
        cache = dec.init_paged_cache(1 + slots * pps, slots, page_len)
        tables = jnp.asarray(np.arange(
            1, 1 + slots * pps, dtype=np.int32).reshape(slots, pps))
        cache, logits = dec.prefill_chunk(
            cache, tables[:1], jnp.asarray([0], jnp.int32),
            jnp.asarray([prompt], jnp.int32),
            jnp.asarray([0], jnp.int32), jnp.asarray([8], jnp.int32))
        tok0 = int(jnp.argmax(logits[0]))
        assert tok0 == ref[0]
        # trailing bigram (prompt[-1], tok0): the latest planted match
        # is followed by a wrong token, an earlier one by ref[1:3]
        wrong = (ref[1] + 1) % cfg.vocab_size
        poison = [prompt[-1], tok0, ref[1], ref[2],
                  prompt[-1], tok0, wrong, prompt[-1], tok0]
        hist = np.full((slots, dec.spec_hist), -1, np.int32)
        hist[0, -len(poison):] = poison
        cache, toks, acc, br = dec.paged_tree_spec_decode_window(
            cache, tables, jnp.asarray([tok0, 0], jnp.int32),
            jnp.asarray([True, False]), jnp.asarray(hist),
            jax.random.PRNGKey(1))
        toks, acc, br = (np.asarray(toks), np.asarray(acc),
                         np.asarray(br))
        assert br[0, 0] == 1, br[:, 0]
        assert acc[0, 0] == 3, acc[:, 0]
        out = [tok0]
        for i in range(toks.shape[0]):
            out.extend(int(x) for x in toks[i, 0, :acc[i, 0]])
        assert out == ref[:len(out)]

    def test_tree_compact_moves_winning_branch(self):
        """Unit: _tree_compact gathers branch rstar's parked slots into
        the canonical chain slots, leaves everything else untouched,
        and degrades to identity for rstar == 0 / inactive rows."""
        layers, heads, page_len, d, pps = 1, 1, 4, 2, 4
        num_pages = 1 + pps
        k = jnp.arange(num_pages * layers * heads * page_len * d,
                       dtype=jnp.float32).reshape(
            num_pages, layers, heads, page_len, d)
        cache = PagedKVCache(k=k, v=k + 1000.0,
                             lengths=jnp.asarray([2], jnp.int32),
                             decoded=jnp.int32(0))
        tables = jnp.asarray(
            np.arange(1, 1 + pps, dtype=np.int32).reshape(1, pps))
        draft = 2

        def logical(c, slot):
            page, off = tables[0, slot // page_len], slot % page_len
            return np.asarray(c.k[page, 0, 0, off])

        before = {s: logical(cache, s) for s in range(3, 8)}
        out = GPTDecoder._tree_compact(
            cache, tables, jnp.asarray([2], jnp.int32),
            jnp.asarray([1], jnp.int32), jnp.asarray([3], jnp.int32),
            jnp.asarray([True]), draft)
        # rstar=1, n_eff=3: logical slots 3,4 <- parked slots 5,6
        assert np.array_equal(logical(out, 3), before[5])
        assert np.array_equal(logical(out, 4), before[6])
        for s in (5, 6, 7):  # sources + untouched tail stay put
            assert np.array_equal(logical(out, s), before[s])
        # rstar=0 / inactive: pure identity
        for rstar, active in ((0, True), (1, False)):
            same = GPTDecoder._tree_compact(
                cache, tables, jnp.asarray([2], jnp.int32),
                jnp.asarray([rstar], jnp.int32),
                jnp.asarray([3], jnp.int32), jnp.asarray([active]),
                draft)
            assert np.array_equal(np.asarray(same.k), np.asarray(cache.k))

    def test_tree_config_validation(self, lm):
        cfg, params, _ = lm
        with pytest.raises(ValueError):  # tree without speculation
            GPTDecoder(cfg, params, spec_tree=2)
        dec = GPTDecoder(cfg, params, tokens_per_dispatch=4,
                         spec_tokens=2, spec_tree=2)
        with pytest.raises(ValueError):  # tree + contiguous engine
            ServeEngine(dec, slots=2, max_len=64, paged=False)

    def test_write_horizon_geometry(self, lm):
        """The page-reservation horizon: K for plain windows, steps *
        (D+1) for chain speculation, and the transient parking peak
        (steps-1)*(D+1) + 1 + W*D for tree windows."""
        cfg, params, _ = lm
        plain = GPTDecoder(cfg, params, tokens_per_dispatch=4)
        assert plain.write_horizon() == 4
        chain = GPTDecoder(cfg, params, tokens_per_dispatch=4,
                           spec_tokens=3)
        assert chain.write_horizon() == chain.spec_steps * 4
        assert chain.write_horizon(1) == chain._spec_steps_for(1) * 2
        tree = GPTDecoder(cfg, params, tokens_per_dispatch=4,
                          spec_tokens=3, spec_tree=2)
        steps = tree.spec_steps
        assert tree.write_horizon() == (steps - 1) * 4 + 1 + 2 * 3
        assert tree.max_write_horizon >= tree.write_horizon()
        assert tree.max_write_horizon >= max(
            tree.write_horizon(d) for d in (1, 2, 3))


# ---------------------------------------------------------------------------
# draft auto-tuning
# ---------------------------------------------------------------------------

class TestSpecAutotune:
    def test_env_defaults(self, monkeypatch):
        monkeypatch.delenv("APEX_TPU_SPEC_TREE", raising=False)
        monkeypatch.delenv("APEX_TPU_SPEC_AUTOTUNE", raising=False)
        assert spec_tree_default(None) == 0
        assert spec_autotune_default(None) is False
        monkeypatch.setenv("APEX_TPU_SPEC_TREE", "3")
        monkeypatch.setenv("APEX_TPU_SPEC_AUTOTUNE", "1")
        assert spec_tree_default(None) == 3
        assert spec_autotune_default(None) is True
        assert spec_tree_default(2) == 2   # explicit arg wins
        assert spec_autotune_default(False) is False

    def test_tuner_walks_draft(self, lm):
        """Unit: saturation deepens, collapse shallows, both clamp to
        [1, spec_tokens], and every move lands in the trajectory."""
        cfg, params, _ = lm
        dec = GPTDecoder(cfg, params, tokens_per_dispatch=4,
                         spec_tokens=3)
        eng = ServeEngine(dec, slots=2, max_len=64, paged=True,
                          page_len=8, spec_autotune=True)
        eng._auto_draft = 2
        eng._auto_window = [3] * eng.AUTOTUNE_PERIOD  # saturated
        eng._autotune_update()
        assert eng._auto_draft == 3
        eng._auto_window = [3] * eng.AUTOTUNE_PERIOD
        eng._autotune_update()
        assert eng._auto_draft == 3  # clamped at spec_tokens
        eng._auto_window = [1] * eng.AUTOTUNE_PERIOD  # collapsed
        eng._autotune_update()
        assert eng._auto_draft == 2
        eng._auto_window = [1] * (eng.AUTOTUNE_PERIOD - 1)
        eng._autotune_update()
        assert eng._auto_draft == 2  # window not full: no move
        eng._auto_window = [1] * eng.AUTOTUNE_PERIOD
        eng._autotune_update()
        eng._auto_window = [1] * eng.AUTOTUNE_PERIOD
        eng._autotune_update()
        assert eng._auto_draft == 1  # clamped at 1
        assert [d for _, d in eng._auto_traj] == [3, 2, 1]

    def test_autotune_engine_identity(self, lm):
        """Auto-tuned engines change DISPATCH geometry only: greedy
        streams stay exactly the fixed-depth engine's, and the draft
        stays in [1, spec_tokens]."""
        cfg, params, pool = lm
        prompts = [[int(pool[0]), int(pool[1])] * 4,
                   [int(t) for t in pool[4:9]]]
        base, _ = _drain(cfg, params, prompts, budget=24, spec_tokens=3)
        auto, ea = _drain(cfg, params, prompts, budget=24, spec_tokens=3,
                          engine_kw=dict(spec_autotune=True))
        assert auto == base
        st = ea.stats()["spec"]
        assert 1 <= st["autotune"]["draft"] <= 3
        for _, d in st["autotune"]["trajectory"]:
            assert 1 <= d <= 3

    def test_draft_override_validation(self, lm):
        cfg, params, _ = lm
        dec = GPTDecoder(cfg, params, tokens_per_dispatch=4,
                         spec_tokens=2)
        cache = dec.init_paged_cache(9, 2, 8)
        tables = jnp.zeros((2, 8), jnp.int32)
        args = (cache, tables, jnp.zeros((2,), jnp.int32),
                jnp.zeros((2,), bool),
                jnp.full((2, dec.spec_hist), -1, jnp.int32),
                jax.random.PRNGKey(0))
        for bad in (0, 3, -1):
            with pytest.raises(ValueError):
                dec.paged_spec_decode_window(*args, draft=bad)
