"""Compile-only checks of the main-path kernels for a DESCRIBED TPU v5e.

The chip's compiler (libtpu) is installed wherever the tests run, and it
compiles for a ``v5e:2x2`` topology that is described, not attached: what
Mosaic would refuse on the chip — a block that breaks the (8, 128)
tiling rule, a kernel that wants more VMEM than it may use — it refuses
here, at no chip time.  Interpret-mode parity tests cannot see either.

Nothing RUNS in this file: a compile that passes says nothing about
results or times and is never a chip run (``chip_smoke.py`` is).  The
kernels' backend gates (``ops/_common.py``) ask ``jax.default_backend()``
and see the CPU, so the ``as_tpu`` fixture steers that question in the
test; the program grows no option for it.  The LN dgamma/dbeta epilogue
is compiled by calling ``_ln_bwd_dx_dwdb_pallas`` directly, so no gate
stands between the test and the kernel.
"""
import importlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# the ops package rebinds `layer_norm` to the function; the module is
# only reachable through importlib
_ln = importlib.import_module("apex_tpu.ops.layer_norm")

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module", autouse=True)
def _no_compile_cache():
    """A described-topology executable can be written to the persistent
    cache but not read back without a chip (the next compile would warn
    and compile again), so the cache is off around these tests."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture
def as_tpu(monkeypatch):
    """Answer the kernels' backend gates the way the chip would."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


@pytest.fixture(scope="module")
def chip():
    """One chip of the described topology (described on first use, not
    at collection); where it cannot be described every case skips."""
    try:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no libtpu in this installation
        pytest.skip(f"cannot describe a v5e:2x2 topology: {e!r:.200}")
    return SingleDeviceSharding(topo.devices[0])


def _mosaic_calls(chip, fn, *avals) -> int:
    """Compile ``fn`` for the described chip; Mosaic calls in it."""
    from apex_tpu.ops import mosaic_call_count

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    return mosaic_call_count(jax.jit(fn).lower(*args).compile())


def _mosaic_names(chip, fn, *avals):
    """Compile ``fn`` for the described chip; the HLO instruction names of
    its Mosaic calls, per-instance ``.N`` suffix taken off."""
    import re

    from apex_tpu.ops._common import mosaic_call_names

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    return [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]


# -- every kernel under its own name in the compiled program ----------------

def _flash_grad(shape, causal, **kw):
    from apex_tpu.ops import flash_attention

    def loss(q, k, v, *bias):
        with jax.named_scope("layer_0"):    # a caller's scope, as a model's
            out = flash_attention(q, k, v, *bias, causal=causal, **kw)
        return jnp.sum(out.astype(F32))

    return loss, [(shape, BF16)] * 3


def _named_case(kernel):
    """``(fn, avals)`` whose compiled program holds ``kernel``."""
    if kernel in ("apex_flash_fwd", "apex_flash_bwd_fused"):
        # GPT-2 small's own shape: one key block, the key-major one sweep
        loss, avals = _flash_grad((8, 12, 1024, 64), True)
        return jax.grad(loss, argnums=(0, 1, 2)), avals
    if kernel == "apex_flash_bwd_sweep":
        # eight key blocks: the query-major one sweep, dk and dv resident
        loss, avals = _flash_grad((1, 4, 8192, 64), True)
        return jax.grad(loss, argnums=(0, 1, 2)), avals
    if kernel in ("apex_flash_bwd_dkdv", "apex_flash_bwd_dq"):
        # a 32k ring shard's head: dk's and dv's accumulators (33.6 MB) are
        # past the VMEM budget, so two passes
        loss, avals = _flash_grad((1, 1, 32768, 128), True)
        return jax.grad(loss, argnums=(0, 1, 2)), avals
    if kernel == "apex_flash_bwd_dq_dbias":
        # a learned bias wants its gradient: the dq pass writes it
        loss, avals = _flash_grad((2, 4, 512, 64), False, bias_grad=True)
        return (jax.grad(loss, argnums=(0, 1, 2, 3)),
                avals + [((2, 512, 512), F32)])
    if kernel.startswith("apex_ln_"):
        fn = getattr(_ln, "_ln_" + kernel[len("apex_ln_"):] + "_pallas")
        rows, vec = ((_LN_ROWS, 768), F32), ((768,), F32)
        if kernel == "apex_ln_fwd":
            return (lambda x, w, b: fn(x, w, b, 1e-5, _ln.DEFAULT_BLOCK_ROWS),
                    [rows, vec, vec])
        return (lambda x, w, dy: fn(x, w, dy, 1e-5, _ln.DEFAULT_BLOCK_ROWS),
                [rows, vec, rows])
    if kernel == "apex_paged_attn":
        return _paged_fused_case(12, 8, False)
    if kernel.startswith("apex_gmm"):
        # the expert layer's products at trinity-mini's widths: 16 held
        # experts, the worst-case row buffer of one 8192-token row
        from apex_tpu.ops import grouped_mm as gmm

        cap = gmm.rows_capacity(8 * 8192, 16)

        def loss(x, w, sizes):
            with jax.named_scope("moe_experts"):
                out = gmm.grouped_matmul(x, w, gmm.group_layout(sizes, cap))
            return jnp.sum(out.astype(F32))

        # value and gradients: the forward product, then dx and dw
        return (jax.value_and_grad(loss, argnums=(0, 1)),
                [((cap, 2048), BF16), ((16, 2048, 2048), BF16), ((16,), I32)])
    if kernel.startswith("apex_moe_"):
        # the expert layer's row movement at trinity-mini.train-8k's own
        # shapes: 8192 tokens x 2048 bf16, 8 slots a token, 16 held experts,
        # capacity 69,632 in tiles of 256 — tokens into the row buffer and
        # back, forward and backward (ops/moe_rows.py)
        from apex_tpu.ops import grouped_mm as gmm
        from apex_tpu.ops import moe_rows
        from apex_tpu.parallel import moe

        cap = gmm.rows_capacity(8 * 8192, 16)
        assert cap == 69632

        def loss(x, w, sel):
            routing = moe._route(sel, (0, 16), cap, gmm.DEFAULT_TILE_ROWS,
                                 moe_rows.combine_block(8192, 8, 2048))
            with jax.named_scope("moe_dispatch"):
                rows = moe._rows_from_tokens(x, routing, gmm.DEFAULT_TILE_ROWS)
                out = moe._tokens_from_rows(rows, w, routing,
                                            gmm.DEFAULT_TILE_ROWS)
            return jnp.sum(out)

        return (jax.value_and_grad(loss, argnums=(0, 1)),
                [((8192, 2048), BF16), ((8192, 8), F32), ((8192, 8), I32)])
    if kernel.startswith("apex_gdn_"):
        return _gdn_case()
    if kernel.startswith("apex_kda_"):
        return _kda_case()
    if kernel.startswith("apex_conv1d_"):
        return _conv_case()
    if kernel.startswith("apex_gated_conv_"):
        return _gated_conv_case()
    if kernel.startswith("apex_ssd_"):
        return _ssd_case()
    if kernel.startswith("apex_qk_heads_"):
        return _qk_heads_case()
    if kernel.startswith("apex_xent_"):
        from apex_tpu.ops import softmax_cross_entropy

        def loss(logits, labels):
            with jax.named_scope("lm_loss"):
                return jnp.sum(softmax_cross_entropy(logits, labels))

        return jax.grad(loss), [((8192, 50304), BF16), ((8192,), I32)]
    raise KeyError(kernel)


def _gdn_case():
    """The gated delta rule as ``qwen3-next.train-8k`` calls it: one
    8192-token row, q and k at 16 key heads and v at 32 value heads of 128 in
    bfloat16, a 128 x 128 float32 state a value head, 128 chunks of 64
    (ops/gated_delta.py), forward and backward."""
    from apex_tpu.ops.gated_delta import gated_delta_rule

    def loss(q, k, v, g, beta):
        with jax.named_scope("gdn_scan"):
            return jnp.sum(gated_delta_rule(q, k, v, g, beta).astype(F32))

    qk, v, gb = ((1, 8192, 16, 128), BF16), ((1, 8192, 32, 128), BF16), \
        ((1, 8192, 32), F32)
    return jax.grad(loss, argnums=(0, 1, 2, 3, 4)), [qk, qk, v, gb, gb]


def _kda_case():
    """The delta rule with a decay a key channel as ``kimi-linear.train-8k``
    calls it: one 8192-token row, q, k and v at 32 heads of 128 in bfloat16,
    the log-decay (1, 8192, 32, 128) float32, 128 chunks of 64 in sub-blocks
    of 16 (ops/kda.py), forward and backward — and the convolution in front
    of it reading a fused projection laid out per head [q | k | v].  Called
    as the model calls it: q and k as the convolution wrote them and ``g`` a
    token's own decay, so the kernels compile with their prologue (the two
    l2 norms, the chunk's running sums)."""
    from apex_tpu.ops.kda import kda_rule, split_conv_qkv

    def loss(qkv, w, g, beta):
        heads = lambda t: t.reshape(1, 8192, 32, 128)
        with jax.named_scope("kda_conv"):
            q, k, v = map(heads, split_conv_qkv(qkv, w, heads=32, head_dim=128))
        with jax.named_scope("kda_scan"):
            o = kda_rule(q, k, v, heads(g), beta, qk_norm=(1e-6, 128 ** -0.5))
        return jnp.sum(o.astype(F32))

    return jax.grad(loss, argnums=(0, 1, 2, 3)), [
        ((1, 8192, 12288), BF16), ((12288, 4), F32),
        ((1, 8192, 4096), F32), ((1, 8192, 32), F32)]


def _conv_case():
    """The delta net's short convolution as ``qwen3-next.train-8k`` calls
    it: ``in_proj_qkvz``'s output of one 8192-token row, 16 key heads of [q
    128 | k 128 | v 256 | z 256] in bfloat16, 4 taps over 8192 channels
    (ops/gated_delta.py), forward and backward."""
    from apex_tpu.ops.gated_delta import split_conv_qkvz

    def loss(qkvz, w):
        with jax.named_scope("gdn_conv"):
            parts = split_conv_qkvz(qkvz, w, key_heads=16, key_dim=128,
                                    value_dim=128)
        return sum(jnp.sum(t.astype(F32) ** 2) for t in parts)

    return (jax.grad(loss, argnums=(0, 1)),
            [((1, 8192, 12288), BF16), ((8192, 4), F32)])


def _ssm_conv_case():
    """The Mamba-2 layer's short convolution as ``granite-h.train-8k`` calls
    it: ``in_proj``'s output of one 8192-token row, ``[z 4096 | x 4096 | B
    128 | C 128 | dt 64]`` in bfloat16, 4 taps and a bias over the 4352
    ``xBC`` channels at column 4096 (ops/ssd.py::split_conv_xbc over
    ops/gated_delta.py's kernels), forward and backward."""
    from apex_tpu.ops.ssd import split_conv_xbc

    def loss(zxbcdt, w, bias):
        with jax.named_scope("ssm_conv"):
            parts = split_conv_xbc(zxbcdt, w, bias, d_inner=4096, d_bc=128)
        return sum(jnp.sum(t.astype(F32) ** 2) for t in parts)

    return (jax.grad(loss, argnums=(0, 1, 2)),
            [((1, 8192, 8512), BF16), ((4352, 4), F32), ((4352,), F32)])


def _gated_conv_case():
    """The gated short convolution as ``lfm2.train-16k`` calls it: a
    convolution layer's ``in_proj`` output of one 16,384-token row, ``[B | C
    | X]`` 6144 wide in bfloat16, 3 taps over 2048 channels
    (ops/gated_conv.py), forward and backward."""
    from apex_tpu.ops.gated_conv import gated_short_conv

    def loss(bcx, w):
        with jax.named_scope("conv_mix"):
            return jnp.sum(gated_short_conv(bcx, w).astype(F32) ** 2)

    return (jax.grad(loss, argnums=(0, 1)),
            [((1, 16384, 6144), BF16), ((2048, 3), F32)])


def _qk_heads_case(hq=32, hk=4, seq=8192, gate=True, **kw):
    """A block's way from its fused projection to the flash kernels as a
    window layer of ``trinity-mini.train-8k`` calls it
    (``models/decoder.py::qkv_heads``): ``qkvg``'s output of one 8192-token
    row, 32 query and 4 key/value heads of 128 and the output gate's 4096
    columns behind them in bfloat16, q and k normed a head and rotated
    (ops/qk_heads.py), forward and backward.  ``(fn, avals)`` of the
    gradients of the projection's output and the two gains."""
    import flax.linen as nn

    from apex_tpu.models.decoder import qkv_heads

    kw = kw or dict(norm_eps=1e-5, theta=1e4)

    class Heads(nn.Module):
        @nn.compact
        def __call__(self, qkv):
            return qkv_heads(qkv, hq, hk, 128, **kw)

    def loss(qkv, *gains):
        params = {f"{n}_norm": {"scale": g} for n, g in zip("qk", gains)}
        with jax.named_scope("layer_0"):
            outs = Heads().apply({"params": params}, qkv)
        return sum(jnp.sum(t.astype(F32) ** 2) for t in outs if t is not None)

    gains = [((128,), F32)] * (2 if "norm_eps" in kw else 0)
    width = (hq + 2 * hk + (hq if gate else 0)) * 128
    return (jax.grad(loss, argnums=tuple(range(1 + len(gains)))),
            [((1, seq, width), BF16)] + gains)


def _ssd_case():
    """The state-space scan as ``granite-h.train-8k`` calls it: one
    8192-token row, 64 heads of 64 channels in bfloat16 (two heads a lane
    tile), a 64 x 128 float32 state a head, B and C of one group 128 wide, 32
    chunks of 256 (ops/ssd.py), forward and backward."""
    from apex_tpu.ops.ssd import ssd_scan

    def loss(x, dt, a, b, c, d):
        with jax.named_scope("ssm_scan"):
            return jnp.sum(ssd_scan(x, dt, a, b, c, d).astype(F32))

    shared = ((1, 8192, 1, 128), BF16)
    return (jax.grad(loss, argnums=tuple(range(6))),
            [((1, 8192, 64, 64), BF16), ((1, 8192, 64), F32), ((64,), F32),
             shared, shared, ((64,), F32)])


_NAMES_OF_CASE = {}


@pytest.mark.parametrize("kernel", [
    "apex_flash_fwd", "apex_flash_bwd_fused", "apex_flash_bwd_sweep",
    "apex_flash_bwd_dkdv", "apex_flash_bwd_dq", "apex_flash_bwd_dq_dbias",
    "apex_ln_fwd",
    "apex_ln_bwd_dx", "apex_ln_bwd_dx_dwdb", "apex_xent_fwd",
    "apex_xent_bwd", "apex_paged_attn", "apex_gmm", "apex_gmm_dw",
    "apex_moe_records", "apex_moe_gather", "apex_moe_combine",
    "apex_moe_combine_dw", "apex_gdn_fwd", "apex_gdn_bwd",
    "apex_kda_fwd", "apex_kda_bwd",
    "apex_conv1d_fwd", "apex_conv1d_bwd",
    "apex_gated_conv_fwd", "apex_gated_conv_bwd",
    "apex_ssd_fwd", "apex_ssd_bwd",
    "apex_qk_heads_fwd", "apex_qk_heads_bwd",
])
def test_kernel_is_named_in_the_compiled_program(chip, as_tpu, kernel):
    """The custom call's HLO instruction — what a device trace names the
    kernel's events by — bears the kernel's entry of ``KERNEL_NAMES``,
    not the scope that called it, and no Mosaic call is anonymous."""
    from apex_tpu.ops._common import KERNEL_NAMES

    assert kernel in KERNEL_NAMES
    # (the four apex_moe_* kernels are one program, the two apex_gdn_*, the
    # two apex_kda_*, the two apex_conv1d_*, the two apex_gated_conv_*, the
    # two apex_ssd_* and the two apex_qk_heads_* six more: each compiled once)
    case = next((f for f in ("apex_moe_", "apex_gdn_", "apex_kda_",
                             "apex_conv1d_", "apex_gated_conv_", "apex_ssd_",
                             "apex_qk_heads_")
                 if kernel.startswith(f)), kernel)
    if case not in _NAMES_OF_CASE:
        fn, avals = _named_case(kernel)
        _NAMES_OF_CASE[case] = _mosaic_names(chip, fn, *avals)
    names = _NAMES_OF_CASE[case]
    assert kernel in names, names
    assert set(names) <= set(KERNEL_NAMES), names


def _xla_outside_the_kernels(text, scope, banned, size):
    """The compiled program's instructions that bear ``scope`` and are no
    Mosaic call — held to this: none has an opcode of ``banned``, and none
    is a float32 array of ``size`` elements or more, but a kernel's own
    result or a view of one.  Returns their lines."""
    import re

    instr = re.compile(r"= (\(?)(\w+)\[([\d,]*)\][^ ]* ([\w\-]+)\(")
    seen = []
    for line in text.splitlines():
        m = instr.search(line)
        if not m or scope not in line or "tpu_custom_call" in line:
            continue
        seen.append(line)
        is_tuple, dtype, dims, opcode = m.groups()
        assert opcode not in banned, line[:200]
        if is_tuple or opcode in ("get-tuple-element", "bitcast"):
            continue        # a kernel's own result, or a view of one
        elements = 1
        for d in filter(None, dims.split(",")):
            elements *= int(d)
        assert not (dtype == "f32" and elements >= size), line[:200]
    return seen


def test_delta_rule_keeps_what_is_local_to_a_chunk_inside_its_kernels(
        chip, as_tpu):
    """At the cell's shape the rule's program is ONE ``apex_gdn_fwd`` and
    ONE ``apex_gdn_bwd`` and, around them, XLA's work on the (B, S, H_v)
    arrays alone: no product (``dot`` / ``convolution``, what
    ``_chunk_local`` and the triangular inverse lower to) and no float32
    array of q's size or more under ``_rule_jit`` outside the custom calls —
    either would be an HBM round trip of the kind the kernels exist to
    avoid, and no parity test would notice it."""
    import re

    from apex_tpu import obs
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    fn, avals = _gdn_case()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]
    assert names == ["apex_gdn_fwd", "apex_gdn_bwd"], names
    assert not unnamed_mosaic_calls(text)
    reg = obs.default_registry()
    assert reg.get("gdn.kernels").value == 1
    assert reg.get("gdn.local_in_kernel").value == 1
    seen = _xla_outside_the_kernels(text, "_rule_jit", ("dot", "convolution"),
                                    8192 * 16 * 128)
    assert len(seen) > 10   # the witness that the lines were found at all


def test_vector_decay_rule_reads_its_operands_where_their_producers_leave_them(
        chip, as_tpu):
    """At the cell's call — q and k as the convolution wrote them, ``g`` as
    the gate made it, over (S, H d) — the rule's program is ONE
    ``apex_kda_fwd`` and ONE ``apex_kda_bwd`` and, under ``kda_scan``
    around them, XLA's work on the (B, S, H) arrays alone: no product (the
    running sums' triangle of ones), no windowed reduction (``cumsum``), no
    reduction or ``rsqrt`` (the l2 norms) and no float32 array of q's size
    outside the custom calls' own results (``G``, a normalised q) — each was
    a pass over HBM that the kernels now make on the tile they hold."""
    import re

    from apex_tpu import obs
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    fn, avals = _kda_case()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]
    assert sorted(n for n in names if "kda" in n) == [
        "apex_kda_bwd", "apex_kda_fwd"], names
    assert not unnamed_mosaic_calls(text)
    reg = obs.default_registry()
    assert reg.get("kda.kernels").value == 1
    assert reg.get("kda.qk_norm_in_kernel").value == 1
    seen = _xla_outside_the_kernels(
        text, "kda_scan", ("dot", "convolution", "reduce-window", "reduce"),
        8192 * 32 * 128)
    assert len(seen) > 5    # the witness that the lines were found at all
    for line in seen:       # nor inside a fusion, by the primitive's name
        made_by = re.search(r'op_name="[^"]*kda_scan/([^"]*)"', line)
        assert not re.search(r"reduce_sum|rsqrt|cumsum|dot_general",
                             made_by.group(1) if made_by else ""), line[:300]


def test_state_space_scan_keeps_every_chunk_square_inside_its_kernels(
        chip, as_tpu):
    """At the cell's shape the scan's program is ONE ``apex_ssd_fwd`` and ONE
    ``apex_ssd_bwd`` and, around them, XLA's work on the (B, S, H) arrays
    alone: no product and no float32 array of x's size or more under
    ``ssd.py``'s jit outside the custom calls but the kernels' own results —
    the float32 ``(chunks, heads, 256, 256)`` decay matrix the chunked form
    costs under XLA (537 MB a layer a pass) is nowhere."""
    import re

    from apex_tpu import obs
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    fn, avals = _ssd_case()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]
    assert names == ["apex_ssd_fwd", "apex_ssd_bwd"], names
    assert not unnamed_mosaic_calls(text)
    assert obs.default_registry().get("ssd.kernel").value == 1
    assert not re.search(r"f32\[(?:1,)?(?:32,64|64,32),256,256\]", text)
    seen = _xla_outside_the_kernels(text, "ssm_scan", ("dot", "convolution"),
                                    8192 * 64 * 64)
    assert len(seen) > 10   # the witness that the lines were found at all


def test_delta_net_layer_reads_q_k_v_out_of_the_projection_in_place(
        chip, as_tpu):
    """A whole delta-net mixer's gradient at the cell's shape: ONE
    ``apex_conv1d_fwd`` and ONE ``apex_conv1d_bwd`` beside the rule's two,
    none anonymous, and under ``gdn_conv`` outside the custom calls no
    float32 array of the convolution's size (the padded ``(S + K - 1) x
    8192`` copy the XLA form makes) and no ``concatenate`` (q, k, v put side
    by side for it, or the four gradients interleaved after it): what is
    left there is z's copy, in bfloat16."""
    import re

    from apex_tpu import obs
    from apex_tpu.models.qwen3_next import GatedDeltaNet, Qwen3NextConfig
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    mixer = GatedDeltaNet(Qwen3NextConfig())
    x = jax.ShapeDtypeStruct((1, 8192, 2048), BF16, sharding=chip)
    shapes = jax.eval_shape(
        lambda: mixer.init(jax.random.PRNGKey(0), jnp.zeros(x.shape, BF16)))
    params = jax.tree_util.tree_map(
        lambda t: jax.ShapeDtypeStruct(t.shape, t.dtype, sharding=chip),
        shapes)
    loss = lambda p, x: jnp.sum(mixer.apply(p, x).astype(F32) ** 2)
    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    names = sorted(re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text))
    assert names == ["apex_conv1d_bwd", "apex_conv1d_fwd", "apex_gdn_bwd",
                     "apex_gdn_fwd"], names
    assert not unnamed_mosaic_calls(text)
    assert obs.default_registry().get("gdn.conv_kernel").value == 1
    seen = _xla_outside_the_kernels(text, "gdn_conv", ("concatenate",),
                                    8192 * 8192)
    assert len(seen) > 5    # the witness that the lines were found at all


def test_a_kernel_differentiated_outside_any_scope_still_bears_its_name(
        chip, as_tpu):
    """With no scope around it JAX wraps the kernel's own name in the
    transformation's (``jvp_apex_xent_fwd_``): still found, by containment."""
    from apex_tpu.ops import softmax_cross_entropy
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    args = [jax.ShapeDtypeStruct(s, d, sharding=chip)
            for s, d in (((1024, 50304), BF16), ((1024,), I32))]
    text = jax.jit(jax.grad(
        lambda l, y: jnp.sum(softmax_cross_entropy(l, y))
    )).lower(*args).compile().as_text()
    names = mosaic_call_names(text)
    assert len(names) == 2 and unnamed_mosaic_calls(text) == []
    assert "apex_xent_fwd" in names[0] and "apex_xent_bwd" in names[1]
    assert unnamed_mosaic_calls(text.replace("apex_xent_fwd", "lm_loss")) \
        == [names[0].replace("apex_xent_fwd", "lm_loss")]


def test_pallas_call_without_a_name_raises():
    from apex_tpu.ops._common import pallas_call

    spec = dict(out_shape=jax.ShapeDtypeStruct((8, 128), F32))
    with pytest.raises(TypeError, match="name"):
        pallas_call(lambda x_ref, o_ref: None, **spec)
    with pytest.raises(ValueError, match="KERNEL_NAMES"):
        pallas_call(lambda x_ref, o_ref: None, name="nameless", **spec)


# -- flash attention: forward + fused backward ------------------------------

@pytest.mark.parametrize(
    "shape,causal,dropout",
    [
        pytest.param((8, 12, 1024, 64), True, 0.1, id="gpt2_small"),
        pytest.param((12, 16, 512, 64), False, 0.0, id="bert_large"),
    ],
)
def test_flash_fwd_bwd_compiles(chip, as_tpu, shape, causal, dropout):
    from apex_tpu.ops import flash_attention

    def loss(q, k, v, seed):
        out = flash_attention(
            q, k, v, causal=causal, dropout_rate=dropout,
            dropout_seed=seed if dropout else None,
        )
        return jnp.sum(out.astype(F32))

    n = _mosaic_calls(
        chip, jax.grad(loss, argnums=(0, 1, 2)),
        (shape, BF16), (shape, BF16), (shape, BF16), ((), I32),
    )
    assert n >= 2  # the forward and the one-sweep dk+dv+dq backward


_ONE_SWEEP = ["apex_flash_fwd", "apex_flash_bwd_sweep"]


def _flash_calls(chip, scope, q, k, v, window=None):
    """Compile the gradient of one causal call under ``scope`` for the
    described chip — the compile is what refuses a VMEM overrun, so a pass
    says Mosaic took the backward's resident accumulators at this shape.
    ``(names, widths)``: its Mosaic calls' names, and for each the sorted
    last dimensions of its bfloat16 operands and results (the backward's
    are q, k, v, do, o — delta is made from it inside — dq, dk, dv)."""
    import re

    from apex_tpu.ops import flash_attention
    from apex_tpu.ops._common import mosaic_call_names

    def loss(q, k, v):
        with jax.named_scope(scope):
            out = flash_attention(q, k, v, causal=True, window=window)
        return jnp.sum(out.astype(F32))

    args = [jax.ShapeDtypeStruct(s, BF16, sharding=chip) for s in (q, k, v)]
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile().as_text()
    names = [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]
    calls = [l for l in text.splitlines()
             if "tpu_custom_call" in l and "apex_flash" in l and " = " in l]
    assert len(calls) == len(names)
    # the softmax statistics cross the calls at one float32 a row: lse as
    # (heads, 1, positions), delta not at all — nothing 128 lanes wide
    for l in calls:
        assert not re.search(r"f32\[\d+,\d+,128\]", l), l
        assert re.search(r"f32\[\d+,1,\d+\]", l), l
    widths = [sorted(map(int, re.findall(r"bf16\[\d+,\d+,(\d+)\]", l)))
              for l in calls]
    return names, widths


@pytest.mark.parametrize("window", [2048, None], ids=["window", "full"])
def test_flash_window_grouped_heads_compiles(chip, as_tpu, window):
    """Trinity-Mini's attention call: 32 query heads to 4 key/value heads
    of size 128 at 8192 positions — the banded grid, key/value blocks read
    through ``h // 8``, ONE backward kernel with dk/dv of a key/value head
    (8.4 MB of float32) resident in VMEM and summed over the group's eight
    query heads there (their shapes are the key/value heads')."""
    from apex_tpu.ops.attention import (
        _SWEEP_ACC_BUDGET_BYTES, _sweep_acc_bytes)

    assert _sweep_acc_bytes(8192, 128, 128) == 8192 * 256 * 4 \
        <= _SWEEP_ACC_BUDGET_BYTES
    q, kv = (1, 32, 8192, 128), (1, 4, 8192, 128)
    names, widths = _flash_calls(chip, "attn_window", q, kv, kv, window)
    assert names == _ONE_SWEEP, names
    # forward: q, k, v, o; backward: q, k, v, do, o, dq, dk, dv
    assert widths == [[128] * 4, [128] * 8], widths


@pytest.mark.parametrize("window", [4096, None], ids=["window", "full"])
def test_flash_groups_of_seven_at_16k_compile(chip, as_tpu, window):
    """SmallThinker's attention call: 28 query heads to 4 key/value heads of
    size 128 at 16,384 positions, twice any other cell's — key/value blocks
    read through ``h // 7``, the band 4096 wide, and ONE backward kernel
    with dk/dv of a key/value head (16.8 MB of float32) resident in VMEM and
    summed over the group's SEVEN query heads as the grid walks them."""
    from apex_tpu.ops.attention import (
        _SWEEP_ACC_BUDGET_BYTES, _sweep_acc_bytes)

    assert _sweep_acc_bytes(16384, 128, 128) == 2 ** 24 \
        <= _SWEEP_ACC_BUDGET_BYTES
    q, kv = (1, 28, 16384, 128), (1, 4, 16384, 128)
    names, widths = _flash_calls(
        chip, "attn_window" if window else "attn_full", q, kv, kv, window)
    assert names == _ONE_SWEEP, names
    assert widths == [[128] * 4, [128] * 8], widths


def test_flash_groups_of_four_of_size_64_at_16k_compile(chip, as_tpu):
    """LFM2's attention call: 32 query heads to 8 key/value heads of size 64
    — half a lane tile, where every other grouped cell runs 128 or more — at
    16,384 positions: key/value blocks read through ``h // 4`` and ONE
    backward kernel with dk/dv of a key/value head (two (16384, 64) float32
    accumulators, which VMEM holds in whole 128-lane tiles: 16.8 MB, what a
    head of 128 takes) resident in VMEM and summed over the group's four
    query heads."""
    from apex_tpu.ops.attention import (
        _SWEEP_ACC_BUDGET_BYTES, _sweep_acc_bytes)

    assert _sweep_acc_bytes(16384, 64, 64) == 2 ** 24 \
        <= _SWEEP_ACC_BUDGET_BYTES
    q, kv = (1, 32, 16384, 64), (1, 8, 16384, 64)
    names, widths = _flash_calls(chip, "attn_full", q, kv, kv)
    assert names == _ONE_SWEEP, names
    assert widths == [[64] * 4, [64] * 8], widths


def test_gated_conv_reads_and_writes_the_projection_in_place(chip, as_tpu):
    """At the cell's shape the gated convolution's program is ONE
    ``apex_gated_conv_fwd`` and ONE ``apex_gated_conv_bwd`` and nothing
    beside them touches an array of the projection's size: the kernels read
    B, C and X out of ``bcx`` itself and write ``[dB | dC | dX]`` as one
    array — no slice, no concatenation, no float32 copy — and XLA is left
    the taps' transposes (2048 x 3)."""
    import re

    from apex_tpu.ops._common import mosaic_call_names

    fn, avals = _gated_conv_case()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    names = [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]
    assert names == ["apex_gated_conv_fwd", "apex_gated_conv_bwd"], names
    # nothing beside the kernels moves an array of a third's size or more:
    # no slice of a third, no concatenation of the gradient, no padded copy
    entry = text[text.index("ENTRY"):]
    assert not re.findall(
        r"= (?:bf16|f32)\[1,163\d\d,(?:2048|6144)\]\S* "
        r"(?:copy|slice|concatenate|pad|dynamic-update-slice)\(", entry)
    assert compiled.memory_analysis().temp_size_in_bytes <= 16384 * 2048 * 4


@pytest.mark.parametrize("cell,case", [
    ("trinity_window", dict()),
    ("trinity_full", dict(norm_eps=1e-5)),
    ("smallthinker_window", dict(hq=28, seq=16384, gate=False, theta=1.5e6)),
])
def test_qk_heads_reads_and_writes_the_projection_in_place(chip, as_tpu, cell,
                                                           case):
    """At the two cells' calls the way from the projection to the flash
    kernels is ONE ``apex_qk_heads_fwd`` and ONE ``apex_qk_heads_bwd`` and
    nothing beside them moves an array of q's size: the kernels read q, k
    and v out of the projection's output itself and write its gradient as
    one array — no slice, no transposition, no concatenation, no float32
    copy of q; the output gate's columns are read by their consumer."""
    import re

    from apex_tpu import obs
    from apex_tpu.ops._common import mosaic_call_names

    before = obs.default_registry().counter("ops.qk_heads.kernel").value
    fn, avals = _qk_heads_case(**case)
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    text = jax.jit(fn).lower(*args).compile().as_text()
    names = [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]
    assert names == ["apex_qk_heads_fwd", "apex_qk_heads_bwd"], names
    assert obs.default_registry().counter(
        "ops.qk_heads.kernel").value == before + 1
    entry = text[text.index("ENTRY"):]
    moved = re.findall(
        r"= (?:bf16|f32)\[1,(?:\d+,)?(?:8192|16384),\d+\]\S* "
        r"(?:copy|slice|transpose|concatenate|pad|dynamic-update-slice)\(",
        entry)
    assert not moved, moved
    assert not re.findall(r"= f32\[1,(?:\d+,)?(?:8192|16384),\d\d+\]", entry)


def test_mamba_conv_reads_x_b_c_out_of_the_projection_in_place(chip, as_tpu):
    """At the cell's shape the Mamba-2 convolution's program is ONE
    ``apex_conv1d_fwd`` and ONE ``apex_conv1d_bwd`` — the delta net's
    kernels under the flat layout, a bias row beside the taps — and nothing
    beside them moves an array of the ``xBC`` columns' size or more: no
    slice of the 4352 channels, no padded or float32 copy, no concatenation
    of ``[dz | dx | dB | dC | ddt]`` (the backward kernel writes it whole).
    Under ``ssm_conv`` XLA is left z's and dt's cuts and the taps'
    transposes."""
    import re

    from jax.experimental.layout import Format, Layout

    from apex_tpu import obs
    from apex_tpu.ops._common import mosaic_call_names, unnamed_mosaic_calls

    fn, avals = _ssm_conv_case()
    args = [jax.ShapeDtypeStruct(s, d, sharding=chip) for s, d in avals]
    # the projection's output as a model's program holds it, rows of 8512
    # lanes: left to itself the compiler lays an ENTRY parameter 66.5 lane
    # tiles wide out the other way round and opens with a relayout copy
    as_in_model = (Format(Layout(major_to_minor=(0, 1, 2)), chip), chip, chip)
    text = jax.jit(fn, in_shardings=as_in_model, out_shardings=as_in_model
                   ).lower(*args).compile().as_text()
    names = [re.sub(r"\.\d+$", "", n) for n in mosaic_call_names(text)]
    assert names == ["apex_conv1d_fwd", "apex_conv1d_bwd"], names
    assert not unnamed_mosaic_calls(text)
    assert obs.default_registry().get("ssd.conv_kernel").value == 1
    entry = text[text.index("ENTRY"):]
    assert "ssm_conv" in entry
    assert not re.findall(
        r"= (?:bf16|f32)\[1,81\d\d,(?:4096|4352|8512)\]\S* "
        r"(?:copy|slice|concatenate|pad|dynamic-update-slice)\(", entry)
    assert not re.findall(r"= f32\[1,81\d\d,(?:4352|8512)\]", entry)


@pytest.mark.parametrize("kept,products", [(True, 3), (False, 4)],
                         ids=["name_kept", "name_dropped"])
def test_a_granite_blocks_gradient_makes_gate_up_once(chip, as_tpu,
                                                      monkeypatch, kept,
                                                      products):
    """One mamba block of ``granite-h.train-8k`` under ``full_block``, its
    output handed on as to a next block: the COMPILED gradient runs three
    products of ``gate_up``'s size (8192 tokens x 2048 x 16,384: the forward
    and the two gradient products) — the recomputed block reads the
    forward's result kept under ``remat.MLP_GATE_UP`` — and four where the
    policy does not keep that name (the program before PR 45).  What
    ``chip_smoke.py::dense_ffn_at_cell`` counts on the chip."""
    from apex_tpu import remat
    from apex_tpu.models.granite_hybrid import (
        MAMBA, GraniteHybridConfig, GraniteHybridLayer)
    from apex_tpu.pyprof.prof import parse_hlo

    if not kept:
        monkeypatch.setattr(remat, "KEPT_RESIDUAL_NAMES", tuple(
            n for n in remat.KEPT_RESIDUAL_NAMES if n != remat.MLP_GATE_UP))
    cfg = GraniteHybridConfig(layer_types=(MAMBA,), remat_policy="full_block")
    tokens, d, d_ff = 8192, cfg.hidden_size, cfg.intermediate_size
    block = remat.remat_module(GraniteHybridLayer, cfg.remat_policy,
                               static_argnums=(2,))(cfg, 0)
    x = jax.ShapeDtypeStruct((1, tokens, d), BF16, sharding=chip)
    params = jax.tree_util.tree_map_with_path(     # O2: matrices in bfloat16
        lambda path, leaf: jax.ShapeDtypeStruct(
            leaf.shape, BF16 if path[-1].key == "kernel" else leaf.dtype,
            sharding=chip),
        jax.eval_shape(lambda key: block.init(
            key, jnp.zeros(x.shape, BF16), True), jax.random.PRNGKey(0)
        )["params"])

    def loss(params, x):
        out = block.apply({"params": params}, x, True)
        return jnp.sum(out.astype(F32)), out

    text = jax.jit(jax.grad(loss, (0, 1), has_aux=True)).lower(
        params, x).compile().as_text()
    a_pass = 2.0 * tokens * d * 2 * d_ff
    found = [i.name for i in parse_hlo(text)
             if i.opcode in ("convolution", "dot") and i.flops == a_pass]
    assert len(found) == products, found


def test_flash_head_size_256_grouped_heads_compiles(chip, as_tpu):
    """Qwen3-Next's attention call: 16 query heads to 2 key/value heads of
    size 256 at 8192 positions — the grouped route at twice the head size
    the auto blocks (512 x 1024) were sized for, and the largest resident
    accumulators of the three cells: 16.8 MB."""
    from apex_tpu.ops.attention import (
        _SWEEP_ACC_BUDGET_BYTES, _sweep_acc_bytes)

    assert _sweep_acc_bytes(8192, 256, 256) == 2 ** 24 \
        <= _SWEEP_ACC_BUDGET_BYTES
    q, kv = (1, 16, 8192, 256), (1, 2, 8192, 256)
    names, widths = _flash_calls(chip, "attn_full", q, kv, kv)
    assert names == _ONE_SWEEP, names
    assert widths == [[256] * 4, [256] * 8], widths


def test_flash_latent_head_sizes_compile_without_padding(chip, as_tpu):
    """Moonlight's attention call: 16 heads at 8192 positions, queries and
    keys 192 wide (128 + the 64 rotary dims, 1.5 lane tiles) against values
    128 wide.  Mosaic takes the 192-wide blocks and dk's 192-wide resident
    accumulator (two lane tiles in VMEM: 12.6 MB with dv's), and v, o, do
    and dv cross the custom calls at 128: no operand or result of the two
    kernels is padded to the queries' width."""
    from apex_tpu.ops.attention import _sweep_acc_bytes

    assert _sweep_acc_bytes(8192, 192, 128) == 8192 * (256 + 128) * 4
    qk, v = (1, 16, 8192, 192), (1, 16, 8192, 128)
    names, widths = _flash_calls(chip, "attn_full", qk, qk, v)
    assert names == _ONE_SWEEP, names
    assert widths == [
        [128, 128, 192, 192],                    # forward: v, o | q, k
        [128, 128, 128, 128, 192, 192, 192, 192],  # v, do, o, dv | q, k, dq, dk
    ], widths


def test_flash_sweep_compiles_at_the_budget_and_two_passes_past_it(
        chip, as_tpu):
    """The longest head the route's rule lets through — accumulators of
    exactly the budget, 24 MiB: 24k positions at 128 + 128 — is one Mosaic
    accepts (the call asks for the accumulators plus the tiles' room); the
    next, a 32k ring shard, compiles as dkdv + dq."""
    from apex_tpu.ops.attention import (
        _SWEEP_ACC_BUDGET_BYTES, _sweep_acc_bytes)

    assert _sweep_acc_bytes(24576, 128, 128) == _SWEEP_ACC_BUDGET_BYTES
    at, past = (1, 1, 24576, 128), (1, 1, 32768, 128)
    assert _flash_calls(chip, "attn_full", at, at, at)[0] == _ONE_SWEEP
    assert _flash_calls(chip, "attn_full", past, past, past)[0] == [
        "apex_flash_fwd", "apex_flash_bwd_dkdv", "apex_flash_bwd_dq"]


def test_expert_layer_compiles_at_width_1408_and_8_held_of_64(chip, as_tpu):
    """moonlight.train-8k's expert layer: 8192 tokens x 6 slots, 8 held of
    64 experts, (8192 * 6 / 256 + 8) * 256 = 51,200 rows, expert width 1408
    = 11 x 128 — a gate|up block 2048 x 2816 and a down block 1408 x 2048,
    widths no power of two divides past 128."""
    from apex_tpu.ops import grouped_mm as gmm
    from apex_tpu.ops import moe_rows
    from apex_tpu.parallel.moe import ExpertShardMLP

    assert gmm.rows_capacity(6 * 8192, 8) == 51200
    assert moe_rows.supported(8192, 6, 2048, gmm.DEFAULT_TILE_ROWS, BF16)
    layer = ExpertShardMLP(num_experts=64, experts_held=(0, 8), d_ff=1408,
                           k=6, shared_d_ff=2816, route_scale=2.446,
                           compute_dtype=BF16)
    x = jax.ShapeDtypeStruct((8192, 2048), BF16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((8192, 2048), BF16))["params"])

    def loss(p, x):
        return jnp.sum(layer.apply({"params": p}, x).astype(F32))

    from apex_tpu.ops._common import mosaic_call_names

    text = jax.jit(jax.grad(loss, argnums=(0, 1))).lower(
        params, x).compile().as_text()
    names = [n.rsplit(".", 1)[0] if n.rsplit(".", 1)[-1].isdigit() else n
             for n in mosaic_call_names(text)]
    assert names.count("apex_gmm") == 4 and names.count("apex_gmm_dw") == 2, names
    assert {"apex_moe_records", "apex_moe_gather", "apex_moe_combine",
            "apex_moe_combine_dw"} <= set(names), names


def test_expert_layer_compiles_at_hidden_2560_and_relu_units(chip, as_tpu):
    """smallthinker.train-16k's expert layer: 16,384 tokens x 6 slots, 8 held
    of 64 experts, (16384 * 6 / 256 + 8) * 256 = 100,352 rows, hidden 2560 =
    20 x 128 — a record is 20 sublanes, two and a half (8, 128) tiles, laid
    on 24 by its layout (``ops/moe_rows.py``) — expert width 768, ReGLU
    units, no shared expert, the router fed another stream than the experts."""
    from apex_tpu.ops import grouped_mm as gmm
    from apex_tpu.ops import moe_rows
    from apex_tpu.parallel.moe import ExpertShardMLP

    t, d = 16384, 2560
    assert gmm.rows_capacity(6 * t, 8) == 100352
    assert moe_rows.supported(t, 6, d, gmm.DEFAULT_TILE_ROWS, BF16)
    assert moe_rows.combine_block(t, 6, d) == 64
    layer = ExpertShardMLP(num_experts=64, experts_held=(0, 8), d_ff=768,
                           k=6, score_func="softmax", unit_func="relu",
                           compute_dtype=BF16)
    x = jax.ShapeDtypeStruct((t, d), BF16, sharding=chip)
    params = jax.tree_util.tree_map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=chip),
        jax.eval_shape(layer.init, jax.random.PRNGKey(0),
                       jnp.zeros((t, d), BF16))["params"])

    def loss(p, x, r):
        return jnp.sum(layer.apply({"params": p}, x, router_input=r).astype(F32))

    from apex_tpu.ops._common import mosaic_call_names

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        params, x, x).compile().as_text()
    names = [n.rsplit(".", 1)[0] if n.rsplit(".", 1)[-1].isdigit() else n
             for n in mosaic_call_names(text)]
    assert names.count("apex_gmm") == 4 and names.count("apex_gmm_dw") == 2, names
    assert {"apex_moe_records", "apex_moe_gather", "apex_moe_combine",
            "apex_moe_combine_dw"} <= set(names), names


def test_moe_row_movement_compiles_at_90112_rows_and_81920_slots(chip, as_tpu):
    """qwen3-next.train-8k's expert layer: 8192 tokens x 10 slots, 32 held
    experts, a buffer of (8192 * 10 / 256 + 32) * 256 = 90,112 rows — 30%
    more indices and weights in scalar prefetch than trinity-mini's."""
    from apex_tpu.ops import grouped_mm as gmm
    from apex_tpu.ops import moe_rows
    from apex_tpu.parallel import moe

    cap = gmm.rows_capacity(10 * 8192, 32)
    assert cap == 90112
    assert moe_rows.supported(8192, 10, 2048, gmm.DEFAULT_TILE_ROWS, BF16)

    def loss(x, w, sel):
        routing = moe._route(sel, (0, 32), cap, gmm.DEFAULT_TILE_ROWS,
                             moe_rows.combine_block(8192, 10, 2048))
        rows = moe._rows_from_tokens(x, routing, gmm.DEFAULT_TILE_ROWS)
        return jnp.sum(moe._tokens_from_rows(rows, w, routing,
                                             gmm.DEFAULT_TILE_ROWS))

    names = _mosaic_names(
        chip, jax.value_and_grad(loss, argnums=(0, 1)),
        ((8192, 2048), BF16), ((8192, 10), F32), ((8192, 10), I32))
    assert {"apex_moe_records", "apex_moe_gather", "apex_moe_combine",
            "apex_moe_combine_dw"} <= set(names), names


# -- fused LayerNorm: forward, dx, dx + dgamma/dbeta epilogue ---------------

_LN_ROWS = 8192  # b8 x s1024, the GPT-2 small train step's row count


@pytest.mark.parametrize("n", [768, 1024])
@pytest.mark.parametrize(
    "kernel", ["_ln_fwd_pallas", "_ln_bwd_dx_pallas", "_ln_bwd_dx_dwdb_pallas"]
)
def test_layer_norm_kernel_compiles(chip, as_tpu, kernel, n):
    fn = getattr(_ln, kernel)
    rows = ((_LN_ROWS, n), F32)
    vec = ((n,), F32)
    if kernel == "_ln_fwd_pallas":
        call = lambda x, w, b: fn(x, w, b, 1e-5, _ln.DEFAULT_BLOCK_ROWS)
        avals = (rows, vec, vec)
    else:
        call = lambda x, w, dy: fn(x, w, dy, 1e-5, _ln.DEFAULT_BLOCK_ROWS)
        avals = (rows, vec, rows)
    assert _mosaic_calls(chip, call, *avals) == 1


# -- fused softmax cross-entropy: forward + backward ------------------------

def test_xentropy_fwd_bwd_compiles(chip, as_tpu):
    from apex_tpu.ops import softmax_cross_entropy

    def loss(logits, labels):
        return jnp.sum(softmax_cross_entropy(logits, labels))

    n = _mosaic_calls(
        chip, jax.grad(loss), ((8192, 50304), BF16), ((8192,), I32)
    )
    assert n >= 2


# -- fused paged-attention serving kernel -----------------------------------

def _paged_fused_case(heads: int, t: int, int8: bool, *,
                      slots: int = 8, ctx: int = 1024, page_len: int = 16,
                      d: int = 64, layers: int = 2):
    from apex_tpu.ops.attention import paged_fused_attention

    n_pages = ctx // page_len
    pool = (1 + slots * n_pages, layers, heads, page_len, d)
    new = ((slots, heads, t, d), BF16)
    avals = [new, new, new, ((slots, t), I32),
             (pool, jnp.int8 if int8 else BF16),
             (pool, jnp.int8 if int8 else BF16),
             ((slots, n_pages), I32), ((slots,), I32)]
    if int8:
        avals += [(pool[:-1], F32), (pool[:-1], F32)]

    def call(q, kn, vn, pos, pk, pv, table, lengths, *scales):
        ks, vs = scales if scales else (None, None)
        return paged_fused_attention(
            q, kn, vn, positions=pos, pool_k=pk, pool_v=pv,
            page_table=table, cache_lengths=lengths,
            pool_k_scale=ks, pool_v_scale=vs, layer=1,
        )

    return call, avals


def _paged_fused_calls(chip, heads: int, t: int, int8: bool) -> int:
    call, avals = _paged_fused_case(heads, t, int8)
    return _mosaic_calls(chip, call, *avals)


@pytest.mark.parametrize("t", [1, 8])
@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_fused_compiles_at_gpt2_small(chip, as_tpu, int8, t):
    assert _paged_fused_calls(chip, 12, t, int8) == 1


@pytest.mark.xfail(
    strict=True, raises=jax.errors.JaxRuntimeError,
    reason="GPT-2 medium (16 heads, ctx 1024) is refused: 'RESOURCE_"
           "EXHAUSTED: Ran out of memory in memory space vmem ... Scoped "
           "allocation with size 16.12M and limit 16.00M exceeded scoped "
           "vmem limit by 128.0K' — the two fp32 (heads, ctx, d) assembly "
           "buffers are 2 x 4 MiB before the scores (ROADMAP S2; the "
           "kernel is default-off)",
)
def test_paged_fused_at_gpt2_medium_is_refused_for_vmem(chip, as_tpu):
    try:
        _paged_fused_calls(chip, 16, 1, False)
    except jax.errors.JaxRuntimeError as e:
        # pin the compiler's words: any OTHER refusal is a new fact and
        # fails this test instead of hiding under the xfail
        assert "vmem" in str(e).lower(), str(e)[:400]
        raise
