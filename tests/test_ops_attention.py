"""Flash attention kernel vs reference (ref apex/contrib/test/multihead_attn/
test_*: fast fused impl vs default impl under identical inputs).

Interpreter mode on CPU keeps shapes small; the real-TPU run is
``chip_smoke.py``'s kernels phase and the benchmark's train cells.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu.ops.attention import attention_ref, flash_attention

B, H, S, D = 1, 2, 256, 128


def qkv(rng, s=S, d=D):
    mk = lambda: jnp.asarray(rng.randn(B, H, s, d).astype(np.float32) * 0.3)
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
def test_fwd_matches_ref(rng, causal):
    q, k, v = qkv(rng)
    out_k = flash_attention(q, k, v, causal=causal, use_pallas=True)
    out_r = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_bwd_matches_ref(rng, causal):
    q, k, v = qkv(rng)

    def lk(q, k, v):
        return jnp.mean(jnp.square(flash_attention(q, k, v, causal=causal, use_pallas=True)))

    def lr(q, k, v):
        return jnp.mean(jnp.square(attention_ref(q, k, v, causal=causal)))

    gk = jax.grad(lk, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=2e-3)


def test_additive_bias_mask(rng):
    """The reference's additive attention-mask path: -inf-style masking."""
    q, k, v = qkv(rng)
    mask = np.zeros((B, S, S), np.float32)
    mask[:, :, S // 2 :] = -1e9  # mask out second half of keys
    bias = jnp.asarray(mask)
    out_k = flash_attention(q, k, v, bias=bias, use_pallas=True)
    out_r = attention_ref(q, k, v, bias=bias)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-3)
    # masked keys must not contribute: compare to attention over first half
    half = attention_ref(q, k[:, :, : S // 2], v[:, :, : S // 2])
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(half), atol=2e-3)


class TestLearnedBias:
    """bias_grad=True: the dq backward pass emits dL/dbias, so a learned
    relative-position bias trains through the kernel (no attention_ref
    detour)."""

    @pytest.mark.parametrize("causal", [False, True])
    def test_bias_grads_match_ref(self, rng, causal):
        q, k, v = qkv(rng)
        bias = jnp.asarray(rng.randn(B, S, S).astype(np.float32) * 0.5)

        def lk(q, k, v, bias):
            return jnp.mean(jnp.square(flash_attention(
                q, k, v, bias=bias, causal=causal, bias_grad=True,
                use_pallas=True)))

        def lr(q, k, v, bias):
            return jnp.mean(jnp.square(attention_ref(
                q, k, v, bias=bias, causal=causal)))

        gk = jax.grad(lk, argnums=(0, 1, 2, 3))(q, k, v, bias)
        gr = jax.grad(lr, argnums=(0, 1, 2, 3))(q, k, v, bias)
        assert float(jnp.max(jnp.abs(gk[3]))) > 0.0  # bias grad is live
        for a, r in zip(gk, gr):
            np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                       atol=2e-3)

    def test_bias_grads_with_dropout(self, rng):
        q, k, v = qkv(rng)
        bias = jnp.asarray(rng.randn(B, S, S).astype(np.float32) * 0.5)
        seed = jnp.int32(11)

        def lk(bias):
            return jnp.mean(jnp.square(flash_attention(
                q, k, v, bias=bias, bias_grad=True, dropout_rate=0.2,
                dropout_seed=seed, use_pallas=True)))

        def lr(bias):
            return jnp.mean(jnp.square(attention_ref(
                q, k, v, bias=bias, dropout_rate=0.2, dropout_seed=seed)))

        np.testing.assert_allclose(
            np.asarray(jax.grad(lk)(bias)), np.asarray(jax.grad(lr)(bias)),
            atol=2e-3,
        )

    def test_default_bias_not_differentiated(self, rng):
        """bias_grad=False (the mask case) keeps a zero bias cotangent."""
        q, k, v = qkv(rng)
        bias = jnp.asarray(rng.randn(B, S, S).astype(np.float32))

        def lk(bias):
            return jnp.mean(jnp.square(flash_attention(
                q, k, v, bias=bias, use_pallas=True)))

        assert float(jnp.max(jnp.abs(jax.grad(lk)(bias)))) == 0.0

    def test_trains_relative_position_bias(self, rng):
        """A tiny training loop: a learned rel-pos bias must move and the
        loss must decrease — the VERDICT r2 'trains a bias' criterion."""
        q, k, v = qkv(rng)
        target = jnp.asarray(rng.randn(B, H, S, D).astype(np.float32) * 0.1)
        # (2S-1,) learned table indexed by relative offset — seeded OFF
        # zero: the all-zeros init sat exactly at a deterministic saddle
        # where 5 steps moved the loss by < 1 ulp (loss[-1] == loss[0]
        # bitwise), flaking the strict-decrease assertion; a small
        # random init breaks the symmetry and the descent is strict
        table0 = jnp.asarray(rng.randn(2 * S - 1).astype(np.float32) * 0.02)
        rel = (np.arange(S)[:, None] - np.arange(S)[None, :]) + S - 1
        rel_idx = jnp.asarray(rel)

        def loss_fn(table):
            bias = table[rel_idx][None].astype(jnp.float32)  # (1, S, S)
            out = flash_attention(q, k, v, bias=bias, bias_grad=True,
                                  use_pallas=True)
            return jnp.mean((out - target) ** 2)

        table = table0
        losses = []
        for _ in range(8):
            l, g = jax.value_and_grad(loss_fn)(table)
            losses.append(float(l))
            table = table - 2.0 * g
        assert float(jnp.max(jnp.abs(table - table0))) > 0.0
        assert losses[-1] < losses[0]


def test_cross_attention_lengths(rng):
    q = jnp.asarray(rng.randn(B, H, 128, D).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, H, 384, D).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, H, 384, D).astype(np.float32) * 0.3)
    out_k = flash_attention(q, k, v, use_pallas=True)
    out_r = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-3)


def test_bf16(rng):
    q, k, v = qkv(rng)
    qb, kb, vb = (t.astype(jnp.bfloat16) for t in (q, k, v))
    out = flash_attention(qb, kb, vb, use_pallas=True)
    assert out.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(out, np.float32),
        np.asarray(attention_ref(q, k, v)),
        atol=3e-2,
    )


def test_unaligned_falls_back(rng):
    q = jnp.asarray(rng.randn(1, 2, 100, 64).astype(np.float32))
    out = flash_attention(q, q, q)  # S=100 not block-aligned -> jnp ref
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(attention_ref(q, q, q)), atol=1e-5
    )


class TestInKernelDropout:
    """In-kernel probability dropout (ref fused mask+softmax+dropout).

    The counter-based mask makes kernel and jnp reference agree exactly,
    so these are hard equality-style parity tests, not statistical ones.
    """

    def _qkv(self, rng, b=2, h=2, s=256, d=64):
        import numpy as np
        q = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
        k = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
        v = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))
        return q, k, v

    def test_kernel_matches_ref_with_dropout(self, rng):
        import numpy as np
        q, k, v = self._qkv(rng)
        seed = jnp.int32(42)
        out_k = flash_attention(
            q, k, v, dropout_rate=0.1, dropout_seed=seed, use_pallas=True
        )
        out_r = attention_ref(q, k, v, dropout_rate=0.1, dropout_seed=seed)
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=1e-5
        )

    def test_grads_match_ref_with_dropout(self, rng):
        import numpy as np
        q, k, v = self._qkv(rng, s=128)
        seed = jnp.int32(7)

        def loss_k(q, k, v):
            return jnp.sum(
                flash_attention(q, k, v, dropout_rate=0.2, dropout_seed=seed,
                                use_pallas=True) ** 2
            )

        def loss_r(q, k, v):
            return jnp.sum(
                attention_ref(q, k, v, dropout_rate=0.2, dropout_seed=seed) ** 2
            )

        gk = jax.grad(loss_k, argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss_r, argnums=(0, 1, 2))(q, k, v)
        for a, b_ in zip(gk, gr):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=5e-4, rtol=1e-3
            )

    def test_zero_rate_equals_no_dropout(self, rng):
        import numpy as np
        q, k, v = self._qkv(rng, s=128)
        a = flash_attention(q, k, v, use_pallas=True)
        b_ = flash_attention(
            q, k, v, dropout_rate=0.0, dropout_seed=jnp.int32(3),
            use_pallas=True,
        )
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b_))

    def test_seed_changes_mask(self, rng):
        import numpy as np
        q, k, v = self._qkv(rng, s=128)
        a = flash_attention(q, k, v, dropout_rate=0.5,
                            dropout_seed=jnp.int32(1), use_pallas=True)
        b_ = flash_attention(q, k, v, dropout_rate=0.5,
                             dropout_seed=jnp.int32(2), use_pallas=True)
        assert np.abs(np.asarray(a) - np.asarray(b_)).max() > 1e-3

    def test_mask_density(self, rng):
        import numpy as np
        from apex_tpu.ops.attention import _keep_mask
        keep = _keep_mask(jnp.int32(9), 0, 0, 0, (512, 512), 0.3)
        frac = float(jnp.mean(keep.astype(jnp.float32)))
        assert abs(frac - 0.7) < 0.01

    def test_dropout_with_causal_and_bias(self, rng):
        import numpy as np
        q, k, v = self._qkv(rng, s=128)
        bias = jnp.asarray(rng.randn(2, 128, 128).astype(np.float32))
        seed = jnp.int32(11)
        out_k = flash_attention(
            q, k, v, bias=bias, causal=True, dropout_rate=0.1,
            dropout_seed=seed, use_pallas=True,
        )
        out_r = attention_ref(
            q, k, v, bias=bias, causal=True, dropout_rate=0.1,
            dropout_seed=seed,
        )
        np.testing.assert_allclose(
            np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=1e-5
        )


class TestFusedBackwardMultiBlock:
    """nk > 1 exercises the fused backward's fp32 dq-partials buffer,
    the host-side causal valid mask, and the cross-k-block sum; nk > 4
    exercises the automatic fallback to the two-pass backward."""

    @pytest.mark.parametrize("causal", [False, True])
    @pytest.mark.parametrize("block_k,nk_label", [(64, "nk4_fused"),
                                                  (32, "nk8_twopass")])
    def test_grads_match_ref(self, rng, causal, block_k, nk_label):
        b, h, s, d = 1, 2, 256, 64
        mk = lambda: jnp.asarray(rng.randn(b, h, s, d).astype(np.float32) * 0.3)
        q, k, v = mk(), mk(), mk()
        dy = jnp.asarray(rng.randn(b, h, s, d).astype(np.float32))

        def loss(up):
            def f(q, k, v):
                o = flash_attention(
                    q, k, v, causal=causal, dropout_rate=0.2,
                    dropout_seed=jnp.int32(5), block_q=64, block_k=block_k,
                    use_pallas=up,
                )
                return jnp.sum(o * dy)
            return f

        gk = jax.grad(loss(True), argnums=(0, 1, 2))(q, k, v)
        gr = jax.grad(loss(False), argnums=(0, 1, 2))(q, k, v)
        for a, b_, n in zip(gk, gr, "qkv"):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b_), atol=2e-4, rtol=1e-3,
                err_msg=f"{nk_label} causal={causal} d{n}",
            )


# ---------------------------------------------------------------------------
# causal sub-tiles: the masked half is skipped INSIDE the kernel body (PR 25)
# ---------------------------------------------------------------------------

import apex_tpu.ops.attention as attention_mod  # noqa: E402
from apex_tpu.ops.attention import flash_tile_census  # noqa: E402

# (sq, sk, block_q, block_k): one grid tile a head, square, with more keys
# than queries and with more queries than keys — the sub-tiled layouts —
# and a grid of several tiles a head, which keeps the one-piece masked
# body and the grid-level skip (nk = 2: the query-major one-sweep backward)
_SUBTILE_LAYOUTS = {
    "one_tile": (256, 256, 256, 256),
    "many_tiles": (512, 512, 256, 256),
    "sq_lt_sk": (256, 512, 256, 512),
    "sq_gt_sk": (512, 256, 512, 256),
}


@pytest.fixture
def sub_width(monkeypatch, request):
    """A sub-tile width small enough to engage at interpreter sizes."""
    monkeypatch.setattr(attention_mod, "_CAUSAL_SUB", request.param)
    return request.param


@pytest.mark.parametrize(
    "dropout,dropout_heads",
    [(0.0, None), (0.2, None), (0.2, (4, 1))],
    ids=["nodrop", "drop", "drop_heads"],
)
@pytest.mark.parametrize("with_bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("layout", sorted(_SUBTILE_LAYOUTS))
@pytest.mark.parametrize("sub_width", [64, 128], indirect=True)
def test_causal_subtiles_match_ref(rng, sub_width, layout, with_bias,
                                   dropout, dropout_heads):
    """Forward and dq, dk, dv of the sub-tiled causal kernels against the
    reference.  fp32 at 5e-5: one flipped bit of the dropout mask would
    move an output by ~1e-2, so passing means the kernel's mask is the
    reference's, sub-tile offsets included."""
    sq, sk, bq, bk = _SUBTILE_LAYOUTS[layout]
    d = 64
    q = jnp.asarray(rng.randn(B, H, sq, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(B, H, sk, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(B, H, sk, d).astype(np.float32) * 0.3)
    bias = (jnp.asarray(rng.randn(B, sq, sk).astype(np.float32) * 0.5)
            if with_bias else None)
    dy = jnp.asarray(rng.randn(B, H, sq, d).astype(np.float32))
    kw = dict(causal=True, dropout_rate=dropout,
              dropout_seed=jnp.int32(11) if dropout else None,
              dropout_heads=dropout_heads)

    def kernel(q, k, v):
        return flash_attention(q, k, v, bias, block_q=bq, block_k=bk,
                               use_pallas=True, **kw)

    def ref(q, k, v):
        return attention_ref(q, k, v, bias, **kw)

    # the traced bodies, not the census, say which width is engaged: two
    # dots forward and five backward for each query sub-tile
    pieces = bq // sub_width if (sq, sk) == (bq, bk) else 1
    bodies = _kernel_primitives(
        lambda q, k, v: jax.vjp(kernel, q, k, v)[1](dy), q, k, v)
    assert bodies["apex_flash_fwd"].count("dot_general") == 2 * pieces
    backward = "apex_flash_bwd_" + ("fused" if sk == bk else "sweep")
    assert sorted(bodies) == sorted(["apex_flash_fwd", backward])
    assert bodies[backward].count("dot_general") == 5 * pieces

    out_k, vjp_k = jax.vjp(kernel, q, k, v)
    out_r, vjp_r = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=5e-5)
    for a, r in zip(vjp_k(dy), vjp_r(dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=5e-5)


@pytest.mark.parametrize("sub_width", [64], indirect=True)
def test_causal_subtiles_learned_bias(rng, sub_width):
    """bias_grad=True takes the two-pass backward: the dq pass walks the
    same pieces and writes zeros into dbias above the diagonal."""
    sq = sk = 256
    q, k, v = qkv(rng, s=sq, d=64)
    bias = jnp.asarray(rng.randn(B, sq, sk).astype(np.float32) * 0.5)

    def lk(q, k, v, bias):
        return jnp.sum(jnp.sin(flash_attention(
            q, k, v, bias, causal=True, bias_grad=True, block_q=256,
            block_k=256, use_pallas=True)))

    def lr(q, k, v, bias):
        return jnp.sum(jnp.sin(attention_ref(q, k, v, bias, causal=True)))

    gk = jax.grad(lk, argnums=(0, 1, 2, 3))(q, k, v, bias)
    gr = jax.grad(lr, argnums=(0, 1, 2, 3))(q, k, v, bias)
    for a, r in zip(gk, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), atol=5e-5)
    assert not np.asarray(gk[3])[:, 0, 1:].any()  # row 0 sees column 0 alone


@pytest.mark.parametrize("sub_width", [64], indirect=True)
def test_ring_diagonal_block_subtiled(rng, sub_width):
    """parallel/ring_attention.py hands its diagonal block to _flash_fwd /
    _flash_bwd with causal=True and the shard's offsets in the seed block:
    sub-tiled (256-token shards in 64-wide pieces), the masks stay local
    and the dropout draw global."""
    from jax.sharding import Mesh, PartitionSpec as P

    from apex_tpu.parallel.mesh import shard_map_compat as shard_map
    from apex_tpu.parallel.ring_attention import ring_attention

    mesh = Mesh(np.array(jax.devices()[:2]), axis_names=("data",))
    s_glob, d = 2 * 256, 64
    q, k, v = (jnp.asarray(rng.randn(1, 1, s_glob, d).astype(np.float32) * 0.3)
               for _ in range(3))
    dy = jnp.asarray(rng.randn(1, 1, s_glob, d).astype(np.float32))
    seed = jnp.int32(5)
    spec = P(None, None, "data")

    def ring(q, k, v):
        return shard_map(
            lambda qb, kb, vb: ring_attention(
                qb, kb, vb, axis_name="data", causal=True, use_pallas=True,
                dropout_rate=0.2, dropout_seed=seed),
            mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
            check_vma=False,
        )(q, k, v)

    def full(q, k, v):
        return attention_ref(q, k, v, causal=True, dropout_rate=0.2,
                             dropout_seed=seed)

    out_k, vjp_k = jax.vjp(ring, q, k, v)
    out_r, vjp_r = jax.vjp(full, q, k, v)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r),
                               atol=2e-5, rtol=1e-5)
    for a, r in zip(vjp_k(dy), vjp_r(dy)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r),
                                   atol=5e-5, rtol=1e-4)


def _sub_jaxprs(eqn):
    for p in eqn.params.values():
        for sub in (p if isinstance(p, (list, tuple)) else [p]):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield inner


def _kernel_primitives(fn, *args):
    """Primitive names of every flash kernel body traced by ``fn``, by
    kernel name, sub-jaxprs (pl.when branches, loops) included."""
    found = {}

    def walk(jaxpr, names):
        for eqn in jaxpr.eqns:
            if names is None and eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = walk(eqn.params["jaxpr"], [])
                continue
            if names is not None:
                names.append(eqn.primitive.name)
            for inner in _sub_jaxprs(eqn):
                walk(inner, names)
        return names

    walk(jax.make_jaxpr(fn)(*args).jaxpr, None)
    return found


def test_noncausal_kernel_bodies_unchanged(rng):
    """causal=False (BERT-large's call, ring attention's off-diagonal
    blocks) never enters the sub-tile path: two dots forward, five in the
    combined backward, one piece, no loop."""
    q, k, v = qkv(rng, s=512, d=64)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, use_pallas=True))

    bodies = _kernel_primitives(jax.grad(loss, argnums=(0, 1, 2)), q, k, v)
    assert sorted(bodies) == ["apex_flash_bwd_fused", "apex_flash_fwd"]
    assert bodies["apex_flash_fwd"].count("dot_general") == 2
    assert bodies["apex_flash_bwd_fused"].count("dot_general") == 5
    for prims in bodies.values():
        assert not {"while", "scan", "concatenate"} & set(prims)
    # the same shapes, causal: 4 x 4 sub-tiles, a forward piece a query
    # sub-tile, the keys above the diagonal in none of them
    causal = _kernel_primitives(
        lambda q, k, v: flash_attention(q, k, v, causal=True,
                                        use_pallas=True), q, k, v)
    assert causal["apex_flash_fwd"].count("dot_general") == 2 * 4


def _brute_census(sq, sk, sub_q, sub_k):
    visible = np.tril(np.ones((sq, sk), bool))
    tiles = visible.reshape(sq // sub_q, sub_q, sk // sub_k, sub_k)
    any_ = tiles.any(axis=(1, 3))
    all_ = tiles.all(axis=(1, 3))
    return any_.size, int(any_.sum()), int((any_ & ~all_).sum())


@pytest.mark.parametrize(
    "sq,sk,block_q,block_k,sub",
    [
        # gpt2-small.train's call (auto blocks: one grid tile a head)
        (1024, 1024, 1024, 1024, (128, 128)),
        (512, 512, 512, 512, (128, 128)),
        (512, 1024, 512, 1024, (128, 128)),
        (1024, 256, 1024, 256, (128, 128)),
        # several grid tiles a head are not sub-tiled: the same sequence
        # under the old 512 x 1024 blocks, and longer ones
        (1024, 1024, 512, 1024, (512, 1024)),
        (2048, 2048, 512, 1024, (512, 1024)),
        (2048, 512, 1024, 256, (1024, 256)),
    ],
)
def test_census_matches_brute_force(sq, sk, block_q, block_k, sub):
    assert attention_mod._causal_subtile(
        block_q, block_k, sq // block_q, sk // block_k, True) == sub
    total, visited, masked = _brute_census(sq, sk, *sub)
    if sub == (block_q, block_k):
        masked = visited  # one piece a tile, masked whenever it runs
    assert flash_tile_census(sq, sk, block_q, block_k, True) == \
        (total, visited, masked)


def test_census_of_the_cells():
    # gpt2-small.train: 36 of 64 sub-tiles visited, 8 of them masked
    assert flash_tile_census(1024, 1024, 1024, 1024, True) == (64, 36, 8)
    # bert-large.train: bidirectional, one piece, nothing skipped or masked
    assert flash_tile_census(512, 512, 512, 512, False) == (1, 1, 0)
    # a causal tile that is one sub-tile is masked whenever it runs
    assert flash_tile_census(256, 256, 128, 128, True) == (4, 3, 3)


def test_tracing_moves_the_tile_counters(rng):
    from apex_tpu import obs

    q, k, v = qkv(rng, s=512, d=64)
    reg = obs.default_registry()
    names = ["ops.flash.tiles_" + n for n in ("total", "visited", "masked")]

    def moved_by(fn):
        before = [reg.counter(n).snapshot()["value"] for n in names]
        jax.make_jaxpr(fn)(q, k, v)
        return [reg.counter(n).snapshot()["value"] - b
                for n, b in zip(names, before)]

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, use_pallas=True)

    census = flash_tile_census(512, 512, 512, 512, True)
    assert census == (16, 10, 4)
    want = [B * H * n for n in census]
    assert moved_by(fwd) == want
    # every traced call counts (a new function, or make_jaxpr would not
    # trace again), whether or not the kernels' trace is shared
    # (attention._flash_jit); the backward is not counted again
    assert moved_by(lambda q, k, v: fwd(q, k, v)) == want
    assert moved_by(jax.grad(lambda q, k, v: jnp.sum(fwd(q, k, v)))) == want
    # bidirectional: everything visited, nothing masked
    assert moved_by(lambda q, k, v: flash_attention(
        q, k, v, use_pallas=True)) == [B * H, B * H, 0]


def _backward_kernels(q, k, v, **kw):
    """Names of the flash kernels in the gradient of one call, sorted."""
    # a new function each time: make_jaxpr keeps the trace of one it has seen
    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, use_pallas=True, **kw))

    return sorted(_kernel_primitives(
        jax.grad(loss, argnums=(0, 1, 2)), q, k, v))


_ONE_KEY_BLOCK = ["apex_flash_bwd_fused", "apex_flash_fwd"]
_SWEEP = ["apex_flash_bwd_sweep", "apex_flash_fwd"]
_TWO_PASS = ["apex_flash_bwd_dkdv", "apex_flash_bwd_dq", "apex_flash_fwd"]


@pytest.fixture
def two_pass(monkeypatch, request):
    """The route of a head past the VMEM budget, at interpreter sizes: with
    no room for the resident accumulators every call of several key blocks
    takes dkdv + dq (the budget is the one thing the route reads that is
    not a shape)."""
    if request.param:
        monkeypatch.setattr(attention_mod, "_SWEEP_ACC_BUDGET_BYTES", 0)
    return request.param


_ROUTES = pytest.mark.parametrize(
    "two_pass", [False, True], ids=["one_sweep", "two_pass"], indirect=True)


def test_shared_trace_keyed_on_the_budget(rng, monkeypatch):
    """The kernels' trace is shared between calls of one signature
    (attention._flash_jit); what it reads from the module at trace time —
    the accumulators' VMEM budget — is part of the key, so flipping it
    between two calls gives the other route, not the first trace again."""
    q, k, v = qkv(rng, s=512, d=64)
    kw = dict(causal=True, block_q=256, block_k=256)
    assert _backward_kernels(q, k, v, **kw) == _SWEEP
    monkeypatch.setattr(attention_mod, "_SWEEP_ACC_BUDGET_BYTES", 0)
    assert _backward_kernels(q, k, v, **kw) == _TWO_PASS
    monkeypatch.undo()
    assert _backward_kernels(q, k, v, **kw) == _SWEEP


@pytest.mark.parametrize("nk", [1, 2, 4, 8])
@pytest.mark.parametrize("bias_grad", [False, True], ids=["", "bias_grad"])
def test_backward_route_follows_the_shapes(rng, nk, bias_grad):
    """The route is a function of nk, bias_grad and the accumulators' bytes
    alone: one key block takes the key-major one-sweep kernel, several the
    query-major one while dk's and dv's accumulators fit the budget, a
    learned bias's gradient the two passes (dbias comes out of the dq
    pass)."""
    s = 512
    q, k, v = qkv(rng, s=s, d=64)
    kw = dict(block_q=128, block_k=s // nk)
    if bias_grad:
        kw.update(bias=jnp.zeros((B, s, s)), bias_grad=True)
        want = ["apex_flash_bwd_dkdv", "apex_flash_bwd_dq_dbias",
                "apex_flash_fwd"]
    else:
        want = _ONE_KEY_BLOCK if nk == 1 else _SWEEP
    assert _backward_kernels(q, k, v, **kw) == want
    assert attention_mod._bwd_sweeps(nk, bias_grad, 0) == len(want) - 1


@pytest.mark.parametrize("sk,d,d_v,fits", [
    (8192, 192, 128, True),      # moonlight.train-8k: 12.6 MB in VMEM
    (8192, 128, 128, True),      # trinity-mini.train-8k: 8.4 MB
    (8192, 256, 256, True),      # qwen3-next.train-8k: 16.8 MB
    (32768, 128, 128, False),    # a 32k ring shard: 33.6 MB
    (65536, 64, 64, False),
])
def test_a_head_past_the_vmem_budget_takes_two_passes(monkeypatch, sk, d, d_v,
                                                      fits):
    """The accumulators are counted as VMEM holds them (whole 128-lane
    tiles), the three cells' heads fit the budget and a 32k ring shard
    does not; what does not fit keeps dkdv + dq."""
    acc = attention_mod._sweep_acc_bytes(sk, d, d_v)
    assert acc == sk * 4 * sum(-(-w // 128) * 128 for w in (d, d_v))
    assert (acc <= attention_mod._SWEEP_ACC_BUDGET_BYTES) == fits
    assert attention_mod._bwd_sweeps(sk // 1024, False, acc) == (1 if fits else 2)
    # the route the kernels take is that function's: the same call, traced
    # with a budget one byte short of its head's accumulators
    q = jnp.zeros((1, 2, 512, d)); kv = jnp.zeros((1, 2, 512, d_v))
    small = attention_mod._sweep_acc_bytes(512, d, d_v)
    for budget, want in ((small, _SWEEP), (small - 1, _TWO_PASS)):
        monkeypatch.setattr(attention_mod, "_SWEEP_ACC_BUDGET_BYTES", budget)
        assert _backward_kernels(
            q, q, kv, causal=True, block_q=128, block_k=128) == want


def _walk_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for inner in _sub_jaxprs(eqn):
            yield from _walk_eqns(inner)


def test_one_sweep_backward_is_one_kernel_and_no_partials(rng):
    """The backward of an nk = 8 call holds ONE apex_flash_bwd* kernel of
    five dot_generals a piece; no float32 array with a leading nk axis (the
    dq partials that were) and no float32 array of a gradient's size at all
    leaves or enters it — dq, dk and dv cross HBM once, in the output
    dtype — and nothing is summed over key blocks in XLA afterwards."""
    nk, s, d = 8, 1024, 64
    q, k, v = (t.astype(jnp.bfloat16) for t in qkv(rng, s=s, d=d))

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, causal=True, block_q=128, block_k=s // nk,
            use_pallas=True).astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))
    bodies = _kernel_primitives(grad, q, k, v)
    assert sorted(bodies) == _SWEEP
    assert bodies["apex_flash_bwd_sweep"].count("dot_general") == 5
    eqns = list(_walk_eqns(jax.make_jaxpr(grad)(q, k, v).jaxpr))
    (call,) = [e for e in eqns if e.primitive.name == "pallas_call"
               and e.params["name"].startswith("apex_flash_bwd")]
    assert [(x.aval.shape, x.aval.dtype) for x in call.outvars] == \
        [((B * H, s, d), jnp.bfloat16)] * 3
    for e in eqns:
        for x in e.outvars:
            shape, dtype = x.aval.shape, x.aval.dtype
            assert not (dtype == jnp.float32 and len(shape) == 4
                        and shape[0] == nk), (e.primitive.name, shape)
    # what follows the kernel in XLA (its own body, where delta is summed
    # over a row's d_v, comes first in the walk)
    body = len(list(_walk_eqns(call.params["jaxpr"])))
    after = eqns[eqns.index(call) + 1 + body:]
    assert "reduce_sum" not in [e.primitive.name for e in after]


@pytest.mark.parametrize("name,hq,hkv,sq,sk,d,d_v,causal,bias,drop", [
    ("gpt2", 12, 12, 1024, 1024, 64, 64, True, False, 0.0),
    ("bert", 16, 16, 512, 512, 64, 64, False, True, 0.1),
    ("cross", 4, 2, 2048, 512, 64, 64, False, False, 0.0),
    ("cross_causal", 4, 4, 2048, 1024, 128, 64, True, False, 0.0),
])
def test_one_key_block_lowers_to_the_text_it_lowered_to(
        name, hq, hkv, sq, sk, d, d_v, causal, bias, drop):
    """nk = 1 — gpt2-small.train's and bert-large.train's calls, and cross
    attention — keeps the program PR 34 guarded by its text, held by its
    structure since the statistics' layout changed that text (PR 41): the
    backward is ONE ``apex_flash_bwd_fused`` kernel of five ``dot_general``s
    a piece and straight-line code, and dq, dk and dv leave it in the input
    dtype, dk and dv at the key/value heads' shapes."""
    q, k, v = (jnp.zeros((2, h_, s_, d_), jnp.bfloat16)
               for h_, s_, d_ in ((hq, sq, d), (hkv, sk, d), (hkv, sk, d_v)))
    b = jnp.zeros((2, sq, sk), jnp.float32) if bias else None

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, b, causal=causal, use_pallas=True, dropout_rate=drop,
            dropout_seed=jnp.int32(3) if drop else None).astype(jnp.float32))

    grad = jax.grad(loss, (0, 1, 2))
    bodies = _kernel_primitives(grad, q, k, v)
    assert sorted(bodies) == _ONE_KEY_BLOCK
    # the pieces a causal head that is ONE grid tile is taken in (gpt2's
    # eight query sub-tiles); every other call is one piece a tile
    block_q = sq if causal and sq == sk else 512
    sub_q, _ = attention_mod._causal_subtile(
        block_q, sk, sq // block_q, 1, causal)
    assert bodies["apex_flash_bwd_fused"].count("dot_general") == \
        5 * (block_q // sub_q)
    assert bodies["apex_flash_fwd"].count("dot_general") == \
        2 * (block_q // sub_q)
    for prims in bodies.values():
        assert not {"while", "scan"} & set(prims)
    (call,) = [e for e in _walk_eqns(jax.make_jaxpr(grad)(q, k, v).jaxpr)
               if e.primitive.name == "pallas_call"
               and e.params["name"].startswith("apex_flash_bwd")]
    assert sorted((x.aval.shape[-3:], x.aval.dtype) for x in call.outvars) == \
        sorted([((2 * hkv, sk, d), jnp.bfloat16),
                ((2 * hkv, sk, d_v), jnp.bfloat16),
                ((2 * hq, sq, d), jnp.bfloat16)])


def _case(hq, hkv, sq, sk, d, d_v, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (1, hq, sq, d)),
            jax.random.normal(k[1], (1, hkv, sk, d)),
            jax.random.normal(k[2], (1, hkv, sk, d_v)),
            jax.random.normal(k[3], (1, hq, sq, d_v)))


@pytest.mark.parametrize("nk", [2, 4, 8])
@pytest.mark.parametrize("hq,hkv,sq,d,d_v,kw", [
    (2, 2, 512, 64, 64, dict(causal=False)),
    (2, 2, 512, 64, 64, dict(causal=True)),
    # a window that crosses block edges at every nk (blocks 256, 128, 64)
    (2, 2, 512, 64, 64, dict(causal=True, window=200)),
    # grouped heads: dk / dv summed over the group in the accumulators
    (4, 1, 512, 64, 64, dict(causal=True)),
    (8, 1, 512, 64, 64, dict(causal=True, window=150)),
    # v at a head size of its own, as DeepseekV3Config.tiny (96 / 64)
    (2, 2, 512, 96, 64, dict(causal=True)),
    (4, 2, 512, 96, 64, dict(causal=False)),
    # dropout on: the mask is the reference's on every tile
    (2, 2, 512, 64, 64, dict(causal=True, dropout_rate=0.2,
                             dropout_seed=jnp.int32(11))),
    (4, 2, 512, 64, 64, dict(causal=True, window=200, dropout_rate=0.1,
                             dropout_seed=jnp.int32(7))),
    # more query blocks than the keys' (cross attention), and one query block
    (2, 2, 1024, 64, 64, dict(causal=False)),
    (2, 2, 128, 64, 64, dict(causal=False)),
    # query rows past the reach of the last key's band: the three last query
    # blocks masked whole (l == 0, lse = -1e30 through the statistics' blocks)
    (4, 2, 1024, 64, 64, dict(causal=True, window=129)),
], ids=["full", "causal", "win200", "g4", "g8_win150", "96_64", "96_64_g2",
        "drop", "drop_win_g2", "sq_gt_sk", "one_q_block", "rows_masked_whole"])
def test_one_sweep_backward_matches_ref(nk, hq, hkv, sq, d, d_v, kw):
    """dq, dk, dv of the one-sweep backward against ``attention_ref``'s
    float32 gradients, interpreted; dk and dv at the key/value heads'
    shapes."""
    sk = 512
    q, k, v, do = _case(hq, hkv, sq, sk, d, d_v)
    blocks = dict(block_q=128, block_k=sk // nk)
    assert _backward_kernels(q, k, v, **blocks, **kw) == _SWEEP
    _grads_match_ref(q, k, v, do, blocks, kw)


def _rows_with_no_key(sq, sk, window):
    """The first query row of a causal call whose band, ``window`` keys back
    from its own position, lies wholly past the last key (``sq`` where there
    is none): from there on a row is masked whole."""
    return sq if window is None else min(sq, sk - 1 + window)


def _grads_match_ref(q, k, v, do, blocks, kw, diff=(0, 1, 2)):
    """The kernels' gradients under ``blocks`` against ``attention_ref``'s
    float32 ones at this file's tolerances.  Rows masked whole carry no
    cotangent: the kernels give them an output of 0, the reference's softmax
    of a row of -1e30 the mean of v, and neither is asked for."""
    live = _rows_with_no_key(q.shape[2], k.shape[2], kw.get("window"))
    do = do.at[:, :, live:].set(0.0)
    kw = dict(kw)
    bias = kw.pop("bias", None)
    args = (q, k, v) if bias is None else (q, k, v, bias)
    loss = lambda fn, **kw_: lambda q, k, v, *b: jnp.sum(
        fn(q, k, v, *b, **kw_) * do)
    got = jax.grad(loss(flash_attention, **blocks, use_pallas=True, **kw),
                   diff)(*args)
    kw.pop("bias_grad", None)
    want = jax.grad(loss(attention_ref, **kw), diff)(*args)
    assert [g.shape for g in got] == [args[i].shape for i in diff]
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


# the bytes the softmax statistics take across HBM (PR 41): one case a
# backward route, every one with dropout on and v at a head size of its own;
# the three that take no bias also under a window, with grouped heads and —
# sq 512 over sk 256 under a window of 129 — with the last query block's rows
# masked WHOLE (l == 0: the forward's l_safe branch writes their lse, -1e30)
_BAND = dict(hq=4, hkv=2, sk=256, kw=dict(window=129))
_STAT_ROUTES = {
    "fused": dict(_BAND, blocks=dict(block_q=128, block_k=256),
                  kernels=_ONE_KEY_BLOCK),
    "sweep": dict(_BAND, blocks=dict(block_q=128, block_k=64), kernels=_SWEEP),
    "dkdv_dq": dict(_BAND, blocks=dict(block_q=128, block_k=64),
                    kernels=_TWO_PASS),
    # two heads: the bias itself is then no array of bh x sq x 128 elements
    "dq_dbias": dict(hq=2, hkv=2, sk=512, kw=dict(bias_grad=True),
                     blocks=dict(block_q=128, block_k=128),
                     kernels=["apex_flash_bwd_dkdv", "apex_flash_bwd_dq_dbias",
                              "apex_flash_fwd"]),
}


@pytest.mark.parametrize("route", sorted(_STAT_ROUTES))
def test_statistics_cross_hbm_at_four_bytes_a_row(route, monkeypatch):
    """No float32 array of ``bh x sq x 128`` elements — the lane-broadcast
    ``lse`` and ``delta`` that were — is an operand or a result of any flash
    kernel or is broadcast anywhere in the gradient's program; the ``lse``
    kept for the backward is ``(bh, sq)`` float32; ``ops.flash.stat_hbm_bytes``
    counts four bytes a row a head for the forward's write and for each
    backward kernel's read; and the gradients are the reference's."""
    from apex_tpu import obs
    from apex_tpu.remat import FLASH_LSE

    at = _STAT_ROUTES[route]
    blocks, kernels, bh, sk = at["blocks"], at["kernels"], at["hq"], at["sk"]
    if kernels == _TWO_PASS:
        monkeypatch.setattr(attention_mod, "_SWEEP_ACC_BUDGET_BYTES", 0)
    sq, d, d_v = 512, 96, 64
    # a scale no other test traces: the counter moves when the kernels are
    # TRACED, and calls of one signature share a trace (_flash_jit)
    kw = dict(at["kw"], causal=True, dropout_rate=0.1,
              dropout_seed=jnp.int32(13), scale=0.1015625)
    q, k, v, do = _case(bh, at["hkv"], sq, sk, d, d_v, seed=41)
    bias = None
    if "bias_grad" in kw:
        bias = 0.5 * jax.random.normal(jax.random.PRNGKey(5), (1, sq, sk))

    def loss(q, k, v):
        return jnp.sum(flash_attention(
            q, k, v, bias, **blocks, use_pallas=True, **kw) * do)

    counter = obs.default_registry().counter("ops.flash.stat_hbm_bytes")
    before = counter.snapshot()["value"]
    jaxpr = jax.make_jaxpr(jax.grad(loss, (0, 1, 2)))(q, k, v).jaxpr
    assert counter.snapshot()["value"] - before == len(kernels) * bh * sq * 4

    eqns = list(_walk_eqns(jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"
             and e.params["name"].startswith("apex_flash_")]
    assert sorted(e.params["name"] for e in calls) == kernels
    wide = lambda x: (x.aval.dtype == jnp.float32
                      and x.aval.size == bh * sq * 128)
    for e in calls:
        assert not [x.aval.shape for x in (*e.invars, *e.outvars) if wide(x)], \
            e.params["name"]
        # lse, one float32 a row: the one operand or result of that size
        stats = [x.aval.shape for x in (*e.invars, *e.outvars)
                 if x.aval.dtype == jnp.float32 and x.aval.size == bh * sq]
        assert stats == [(bh, 1, sq)], (e.params["name"], stats)
    for e in eqns:
        if e.primitive.name == "broadcast_in_dim":
            assert not wide(e.outvars[0]), e
    (kept,) = [e for e in eqns if e.primitive.name == "name"
               and e.params["name"] == FLASH_LSE]
    assert (kept.outvars[0].aval.shape, kept.outvars[0].aval.dtype) == \
        ((bh, sq), jnp.float32)

    if "window" in kw:
        # the rows past the band's reach of the last key: masked whole
        live = _rows_with_no_key(sq, sk, kw["window"])
        assert live == 384
        seed3 = attention_mod._pack_seed(kw["dropout_seed"], 0, 0)
        out, lse = attention_mod._flash_fwd(
            q[0], k[0], v[0], None, seed3, kw["scale"], True,
            blocks["block_q"], blocks["block_k"], kw["dropout_rate"],
            window=kw["window"])
        assert lse.shape == (bh, sq) and lse.dtype == jnp.float32
        np.testing.assert_array_equal(lse[:, live:], np.float32(-1e30))
        assert np.all(np.asarray(lse[:, :live]) > -1e3)
        np.testing.assert_array_equal(out[:, live:], 0.0)
    if bias is None:
        _grads_match_ref(q, k, v, do, blocks, kw)
    else:
        _grads_match_ref(q, k, v, do, blocks, dict(kw, bias=bias), (0, 1, 2, 3))


@_ROUTES
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("sq,sk,row0,col0", [
    (256, 512, 512, 0),       # a ring shard's queries against two shards' keys
    (512, 256, 0, 1024),
    (256, 256, 256, 256),     # the diagonal block
])
def test_backward_with_offsets_as_the_ring_passes_them(rng, sq, sk, row0,
                                                       col0, causal, two_pass):
    """``_flash_bwd`` called directly, as parallel/ring_attention.py calls
    it: ``sq`` != ``sk`` and the shard's row and column offsets in the seed
    block.  The causal mask stays local, the dropout hash global: both
    routes give the gradients of the reference whose mask is drawn at the
    offsets."""
    d, rate, seed = 64, 0.2, jnp.int32(5)
    q = jnp.asarray(rng.randn(H, sq, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rng.randn(H, sk, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rng.randn(H, sk, d).astype(np.float32) * 0.3)
    do = jnp.asarray(rng.randn(H, sq, d).astype(np.float32))
    seed3 = attention_mod._pack_seed(seed, row0, col0)
    args = (d ** -0.5, causal, 128, 128, rate)
    out, lse = attention_mod._flash_fwd(q, k, v, None, seed3, *args)
    got = attention_mod._flash_bwd(q, k, v, None, seed3, out, lse, do, *args)

    def ref(q, k, v):
        s = jnp.einsum("hqd,hkd->hqk", q, k) * d ** -0.5
        if causal:
            s = jnp.where(np.tril(np.ones((sq, sk), bool)), s, -1e30)
        p = jax.nn.softmax(s, axis=-1)
        keep = jnp.stack([attention_mod._keep_mask(
            seed, h_, row0, col0, (sq, sk), rate) for h_ in range(H)])
        return jnp.einsum("hqk,hkd->hqd",
                          jnp.where(keep, p / (1 - rate), 0.0), v)

    out_r, vjp = jax.vjp(ref, q, k, v)
    np.testing.assert_allclose(out, out_r, atol=2e-5)
    for a, b_ in zip(got[:3], vjp(do)):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)
    assert got[3] is None


def test_tracing_the_backward_moves_the_sweep_counters(rng, monkeypatch):
    """``ops.flash.bwd_calls`` / ``ops.flash.bwd_sweeps``: one call, and one
    sweep over the score tiles on the one-sweep routes, two on the
    fallback — counted when the backward is traced."""
    from apex_tpu import obs

    reg = obs.default_registry()
    names = ["ops.flash.bwd_calls", "ops.flash.bwd_sweeps"]
    q, k, v = qkv(rng, s=512, d=64)

    def moved_by(**kw):
        before = [reg.counter(n).snapshot()["value"] for n in names]
        _backward_kernels(q, k, v, **kw)
        return [reg.counter(n).snapshot()["value"] - b
                for n, b in zip(names, before)]

    # a scale no other test of this worker has traced: a backward whose
    # trace is shared (attention._flash_jit) is not traced, or counted, again
    blocks = dict(causal=True, block_q=128, block_k=128, scale=0.1375)
    assert moved_by(causal=True, scale=0.1375) == [1, 1]    # one key block
    assert moved_by(**blocks) == [1, 1]                 # four: the sweep
    assert moved_by(**blocks, bias=jnp.zeros((B, 512, 512)),
                    bias_grad=True) == [1, 2]
    monkeypatch.setattr(attention_mod, "_SWEEP_ACC_BUDGET_BYTES", 0)
    assert moved_by(**blocks) == [1, 2]
    assert moved_by(**blocks) == [0, 0]                 # the shared trace
    # a forward alone has no backward to count
    before = [reg.counter(n).snapshot()["value"] for n in names]
    jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, use_pallas=True, **blocks))(q, k, v)
    assert [reg.counter(n).snapshot()["value"] for n in names] == before


# ---------------------------------------------------------------------------
# a sliding window and grouped key/value heads (models/afmoe.py's call)
# ---------------------------------------------------------------------------

from apex_tpu.ops import attention_ref as _ref  # noqa: E402


def _window_case(hq, hkv, s, d=64, b=1, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (b, hq, s, d)),
            jax.random.normal(k[1], (b, hkv, s, d)),
            jax.random.normal(k[2], (b, hkv, s, d)),
            jax.random.normal(k[3], (b, hq, s, d)))


@_ROUTES
@pytest.mark.parametrize("hq,hkv,s,window,bq,bk", [
    (4, 2, 512, 200, 128, 128),      # S not a multiple of the window
    (8, 2, 512, None, 128, 256),     # grouped heads alone, two rows
    (4, 1, 1024, 300, 128, 128),     # one key/value head for all
    (2, 2, 384, 100, 128, 128),      # a window alone
    (4, 2, 256, 100, 256, 256),      # one grid tile a head: masked whole
    (4, 2, 512, 128, 128, 256),      # window == a query block
    # SEVEN query heads a key/value head (models/smallthinker.py), a window
    # that crosses the 128-wide blocks' edges; and two such groups, no window
    (7, 1, 512, 200, 128, 128),
    (14, 2, 256, None, 128, 128),
], ids=["win200_g2", "g4_b2", "win300_g4", "win100", "one_tile", "win128",
        "win200_g7", "g7_x2"])
def test_window_and_grouped_heads_match_ref(hq, hkv, s, window, bq, bk,
                                            two_pass):
    """Forward, dq, dk, dv against ``attention_ref`` on both backward
    routes (one sweep, and dkdv + dq)."""
    b = 2 if window is None else 1
    q, k, v, do = _window_case(hq, hkv, s, b=b)
    kw = dict(causal=True, window=window)
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) * do)
    flash = lambda q, k, v: flash_attention(
        q, k, v, block_q=bq, block_k=bk, use_pallas=True, **kw)
    ref = lambda q, k, v: _ref(q, k, v, **kw)
    np.testing.assert_allclose(flash(q, k, v), ref(q, k, v), atol=2e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    assert got[1].shape == k.shape and got[2].shape == v.shape
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


@_ROUTES
def test_32_on_8_heads_of_64_with_normed_queries_and_keys_match_ref(two_pass):
    """``models/lfm2.py``'s call: 32 query heads on 8 key/value heads of 64
    — half a lane tile, groups of 4 —, q and k RMS-normed over the head and
    rotated upstream of the kernel, gradients taken THROUGH the norms to the
    projections' outputs, on both backward routes."""
    from apex_tpu.models.decoder import rotary

    q, k, v, do = _window_case(32, 8, 256)
    scale = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (2, 64))

    def normed(t, g):
        t = t * jax.lax.rsqrt(jnp.mean(jnp.square(t), -1, keepdims=True) + 1e-5)
        return rotary(t * g, 1e6)

    def loss(attend):
        return lambda q, k, v: jnp.sum(attend(
            normed(q, scale[0]), normed(k, scale[1]), v) * do)

    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, block_q=128, block_k=128, use_pallas=True)
    ref = lambda q, k, v: _ref(q, k, v, causal=True)
    np.testing.assert_allclose(
        flash(normed(q, scale[0]), normed(k, scale[1]), v),
        ref(normed(q, scale[0]), normed(k, scale[1]), v), atol=2e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    assert got[1].shape == (1, 8, 256, 64)
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


def test_window_with_dropout_and_grouped_heads(monkeypatch):
    """The dropout hash is keyed on the QUERY head on every route, so the
    kernel's mask is the reference's."""
    q, k, v, do = _window_case(4, 2, 512)
    kw = dict(causal=True, window=200, dropout_rate=0.1,
              dropout_seed=jnp.int32(7))
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v, **kw) * do)
    flash = lambda *a, **kw_: flash_attention(
        *a, block_q=128, block_k=128, use_pallas=True, **kw_)
    want = jax.grad(loss(_ref), (0, 1, 2))(q, k, v)
    for budget in (attention_mod._SWEEP_ACC_BUDGET_BYTES, 0):
        monkeypatch.setattr(attention_mod, "_SWEEP_ACC_BUDGET_BYTES", budget)
        for a, b_ in zip(jax.grad(loss(flash), (0, 1, 2))(q, k, v), want):
            np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


def test_window_covering_every_key_is_plain_causal():
    """``window >= S`` lowers to the causal program: the same jaxpr."""
    q, k, v, _ = _window_case(2, 2, 256)
    plain = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, use_pallas=True))(q, k, v)
    wide = jax.make_jaxpr(lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=256, use_pallas=True))(q, k, v)
    assert str(plain) == str(wide)
    np.testing.assert_array_equal(
        flash_attention(q, k, v, causal=True, window=4096, use_pallas=True),
        flash_attention(q, k, v, causal=True, use_pallas=True))


def test_window_and_groups_refuse_what_they_do_not_do():
    q, k, v, _ = _window_case(4, 2, 256)
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, window=64)
    with pytest.raises(ValueError, match="bias"):
        flash_attention(q, k, v, bias=jnp.zeros((1, 256, 256)), causal=True)
    with pytest.raises(ValueError, match="share"):
        flash_attention(q, k[:, :1], v, causal=True)
    with pytest.raises(ValueError, match="share"):
        flash_attention(q[:, :3], k, v, causal=True)


@pytest.mark.parametrize("s,bq,bk,window", [
    (8192, 512, 1024, 2048),     # trinity-mini's window layers
    (8192, 512, 1024, None),     # and its full layer
    (512, 128, 128, 200),
    (512, 128, 128, 1),
])
def test_census_counts_the_band(s, bq, bk, window):
    """Visited tiles are those that hold a (row, column) of the band; every
    one of them is masked whole (the one-piece body)."""
    total, visited, masked = flash_tile_census(s, s, bq, bk, True, window)
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    band = (j <= i) & ((i - j < window) if window else True)
    tiles = band.reshape(s // bq, bq, s // bk, bk).any(axis=(1, 3))
    assert (total, visited, masked) == (tiles.size, tiles.sum(), tiles.sum())
    if window == 2048 and s == 8192:
        # 16 query tiles x 8 key tiles: the band reaches 2 or 3 key tiles
        assert (total, visited) == (128, 42)


def test_window_call_counts_its_band_for_query_heads():
    from apex_tpu import obs

    reg = obs.default_registry()
    before = {n: reg.counter("ops.flash.tiles_" + n).value
              for n in ("total", "visited", "masked")}
    q, k, v, _ = _window_case(4, 2, 512)
    flash_attention(q, k, v, causal=True, window=200, block_q=128,
                    block_k=128, use_pallas=True)
    total, visited, masked = flash_tile_census(512, 512, 128, 128, True, 200)
    for n, want in zip(("total", "visited", "masked"), (total, visited, masked)):
        assert reg.counter("ops.flash.tiles_" + n).value - before[n] == 4 * want


# ---------------------------------------------------------------------------
# v at a head size of its own (latent attention: 192-wide q and k, 128-wide v)
# ---------------------------------------------------------------------------

def _unequal_case(hq, hkv, s, d_qk, d_v, b=1, seed=0):
    k = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(k[0], (b, hq, s, d_qk)),
            jax.random.normal(k[1], (b, hkv, s, d_qk)),
            jax.random.normal(k[2], (b, hkv, s, d_v)),
            jax.random.normal(k[3], (b, hq, s, d_v)))


@_ROUTES
@pytest.mark.parametrize("causal", [False, True], ids=["full", "causal"])
@pytest.mark.parametrize("hq,hkv,s,d_qk,d_v,window,bq,bk", [
    (2, 2, 256, 192, 128, None, 128, 128),   # the latent mixer's head sizes
    (2, 2, 256, 128, 64, None, 128, 128),    # a toy pair
    (2, 2, 256, 64, 128, None, 128, 128),    # values WIDER than keys
    (2, 2, 256, 192, 128, None, None, None),  # auto blocks: one tile a head
    (4, 2, 256, 192, 128, None, 128, 128),   # grouped heads, unequal sizes
    (2, 2, 384, 192, 128, 100, 128, 128),    # a window, unequal sizes
    (4, 2, 256, 64, 64, None, 128, 128),     # grouped heads, equal sizes
    (2, 2, 384, 64, 64, 100, 128, 128),      # a window, equal sizes
], ids=["192_128", "128_64", "64_128", "192_128_one_tile", "192_128_g2",
        "192_128_win100", "64_64_g2", "64_64_win100"])
def test_value_head_size_of_its_own_matches_ref(hq, hkv, s, d_qk, d_v, window,
                                                bq, bk, causal, two_pass):
    """o, dq, dk, dv against ``attention_ref`` where v's head size is not q's
    and k's, on both backward routes; o and dv come out at v's size, dq and
    dk at q's; the scale is q's ``D ** -0.5``.  The equal-size grouped and
    window cases run the same assertions over the routes they always took."""
    if not causal:
        window = None       # a window goes with causal: three blocks, full
    q, k, v, do = _unequal_case(hq, hkv, s, d_qk, d_v)
    kw = dict(causal=causal, window=window)
    loss = lambda fn: lambda q, k, v: jnp.sum(fn(q, k, v) * do)
    flash = lambda q, k, v: flash_attention(
        q, k, v, block_q=bq, block_k=bk, use_pallas=True, **kw)
    ref = lambda q, k, v: _ref(q, k, v, **kw)
    out = flash(q, k, v)
    assert out.shape == (1, hq, s, d_v)
    np.testing.assert_allclose(out, ref(q, k, v), atol=2e-5)
    got = jax.grad(loss(flash), (0, 1, 2))(q, k, v)
    want = jax.grad(loss(ref), (0, 1, 2))(q, k, v)
    assert [g.shape for g in got] == [q.shape, k.shape, v.shape]
    for a, b_ in zip(got, want):
        np.testing.assert_allclose(a, b_, atol=5e-5, rtol=1e-4)


def test_value_head_size_is_read_from_the_shapes():
    """No switch: the same call at ``D_v == D`` hands the kernels blocks ``D``
    wide alone, as it always did, and at ``D_v != D`` v's own width beside it
    — v, o, do, dv are never padded to ``D``.  The default scale is the
    queries' ``D ** -0.5`` either way, and the off-TPU fallback takes the
    same shapes."""
    def kernel_widths(d_v):
        """Last dims of the 3-d bfloat16 operands and results of every
        pallas_call in the gradient's jaxpr."""
        q, k, v, do = (t.astype(jnp.bfloat16)
                       for t in _unequal_case(2, 2, 256, 192, d_v))
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda q, k, v: jnp.sum(flash_attention(
                q, k, v, causal=True, block_q=128, block_k=128,
                use_pallas=True) * do), (0, 1, 2)))(q, k, v)
        found = set()

        def walk(jp):
            for eqn in jp.eqns:
                if eqn.primitive.name == "pallas_call":
                    found.update(
                        x.aval.shape[-1] for x in eqn.invars + eqn.outvars
                        if x.aval.ndim == 3 and x.aval.dtype == jnp.bfloat16)
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)
        walk(jaxpr.jaxpr)
        return found

    assert kernel_widths(192) == {192}
    assert kernel_widths(128) == {192, 128}
    q, k, v, _ = _unequal_case(2, 2, 256, 192, 128)
    np.testing.assert_allclose(
        flash_attention(q, k, v, causal=True, use_pallas=True),
        flash_attention(q, k, v, causal=True, scale=192 ** -0.5,
                        use_pallas=False), atol=2e-5)
    with pytest.raises(ValueError, match="share a head size"):
        flash_attention(q, k[..., :128], v, causal=True)
    with pytest.raises(ValueError, match="share a head size"):
        flash_attention(q, k, v[:, :, :128], causal=True)
