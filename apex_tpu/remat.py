"""Rematerialization policies — the activation-memory knob.

The reference trades memory for compute per-module (torch checkpointing,
the MLP extension's reserved-buffer economy); under XLA the equivalent
lever is ``jax.checkpoint`` with a *saveable policy*.  One named knob
(``remat_policy``) threads through the model zoo (``models/gpt.py``,
``models/bert.py``, ``models/afmoe.py``) and :func:`apex_tpu.ops.mlp.mlp`,
so memory freed by ZeRO sharding + remat converts directly into larger
microbatches for the gradient-accumulation driver mode (docs/driver.md
has the trade-off table):

- ``none``          — save all activations (fastest backward, most HBM).
- ``dots_saveable`` — save matmul/dot outputs and the declared kernel
  residuals (below), recompute everything elementwise (LN, gelu,
  residual adds).  The usual sweet spot: backward re-runs only cheap VPU
  work while the MXU results — the flash kernel's among them, which is
  no ``dot_general`` and which ``dots_saveable`` alone would make again
  — stay resident.
- ``full_block``    — save the wrapped block's input and the declared
  residuals (below), nothing else; the rest of the forward re-runs in
  backward (max memory savings, ~1.3x step cost for transformer blocks).

**Declared residuals.**  A kernel whose result is dear to make again and
cheap to hold names it with ``jax.ad_checkpoint.checkpoint_name`` under
an entry of :data:`KEPT_RESIDUAL_NAMES`, and both block-recomputing
policies keep exactly those.  Today three kernels and two layers declare.
``ops/attention.py``'s forward rule names its output (``batch*heads x
seq x head_dim`` in the compute dtype — the VALUES' head size where that
is not the keys') and its log-sum-exp (``batch*heads
x seq`` float32) — what its backward reads besides q, k, v, which are
cheap to make again from the block's input: the projection, and from its
output ONE call of ``apex_qk_heads_fwd`` where the heads are a lane tile
(``ops/qk_heads.py``: norm, rotation and the way to heads-major in one pass;
its backward reads the recomputed projection's output and nothing kept), the
composed norm, rotation and transposition elsewhere.  Without them the
backward pass would run the whole attention forward a second time only to
hand its backward those two arrays.  ``ops/gated_delta.py``'s forward rule
names the rule's output (``batch x seq x heads*d_v`` in the compute dtype:
the kernels'; float32 ``chunks x heads x chunk x d_v`` on the scan path), the
state at each chunk's start (``chunks x heads x d_k x d_v`` float32) and
each chunk's triangular inverse (``chunks x heads x chunk x chunk``, the
kernels' in the compute dtype) — what its backward reads besides q, k, v,
g, beta, which are cheap to make again from the block's input.  The rule is
the one SEQUENTIAL thing in a block (128 dependent steps at 8192 tokens,
ten dependent products a chunk in the inverse): without the names it would
be walked forward twice.  ``ops/kda.py`` — the same rule with a decay a key
channel — declares its own four the same way: the output, the chunk states,
the triangular inverses and each chunk's decayed scores ``P`` (``chunks x
heads x chunk x chunk`` in the compute dtype: its backward reads ``P`` where
the scalar rule's makes ``Q K^T`` and the decays again, because there the
making is ``chunk / 4`` passes of exponentials over a (chunk, d) tile).  ``parallel/moe.py::ExpertShardMLP``, no kernel
but a layer, names its ROUTING PLAN: the selection ``sel`` (``tokens x k``
int32, under ``apex_moe_sel`` as the router picks it, so the picked
weights are read through the kept selection too) and every table of
``_Routing`` (``apex_moe_plan``: ``slot_row`` ``tokens x k``, ``row_slot``
and ``row_token`` ``rows``, the block starts, the four small arrays of
``GroupLayout``).  All integers, no gradient: with them kept a recomputed
block makes no ``top_k``, no running count, no ``argsort`` and no layout
again — only what a gradient flows through (the router's product, the
scores, the picked weights, the row movement, the grouped products).
``parallel/moe.py::SwiGLU`` — a model's dense gated MLP and the shared
expert inside ``ExpertShardMLP`` — names the output of its first product,
``gate_up`` (``... x 2 d_ff`` in the compute dtype, before the split into
gate and up) ``apex_mlp_gate_up``: the dearest product of a dense block
(``2 x d x 2 d_ff`` operations a token) and the one thing of the MLP its
backward reads besides the block's input.  With it kept a recomputed block
makes ``gate_up`` once a step — three passes of that product (the forward
and the two gradient products), not four; ``down``'s recomputed forward XLA
drops by itself, nothing in a block's backward reads the block's output.
The routed experts' grouped ``gate | up`` product over the worst-case row
buffer is NOT named: another array at another price.
Outside a ``jax.checkpoint`` a name lowers to nothing.

One rule at every shape, no threshold: per byte kept, the attention
forward costs 2 x (keys a query sees) operations at a fifth to a third
of the chip's roofline (PERF.md section 5), against a GEMM's ``d_in``
operations a byte at two or three times that efficiency — from 512 keys
up it is the dearest thing in a block to make again, and long context,
where ``full_block`` is reached for, only widens that.  What a block
keeps, by configuration (reckoned from the shapes; bf16 compute):

====================  ==================  =====================  ===========
configuration         block input         + ``out``              + ``lse``
====================  ==================  =====================  ===========
trinity-mini, 1x8192  33.6 MB (hidden     67.1 MB (32 heads x    1.05 MB
                      2048)               128: twice the hidden)
gpt2-small, 16x1024   25.2 MB             25.2 MB                0.79 MB
bert-large, 12x512    12.6 MB             12.6 MB                0.39 MB
moonlight, 1x8192     33.6 MB (hidden     33.6 MB (16 heads x    0.52 MB
                      2048)               128 wide VALUES)
====================  ==================  =====================  ===========

A latent-attention block (moonlight) declares nothing new: the two names
carry ``out`` at the values' width, and what ``full_block`` runs again is
the latent path — the query, down- and up-projections (19.1 MFLOP a
token), the latent's norm, the rotation and the assembly of k (50 MB) and
v (34 MB) from the 8192 x 576 latent (9.4 MB).  Naming the latent would
save the down-projection alone (2.4 of a block's ~117 MFLOP a token
forward) and keep the rest; it is not named (PERF.md section 5 has what
the recomputed latent path costs on the chip).

A gated-delta-net block (qwen3-next, 1x8192, 32 value heads of 128 x 128)
keeps its input 33.6 MB, the rule's output 67.1 MB (bf16 since PR 31; the
scan path's float32 134 MB), the chunk states 268 MB and the triangular
inverses 33.6 MB (bf16; 67 MB float32 before): with them kept the
recomputed block's forward rule is dead code — three ``apex_gdn_fwd`` calls
a step for six — and the compile-only rehearsal of that cell's window reads
4.98 GiB of temporaries (5.33 before the rule's kernels made what is local
to a chunk themselves, 6.17 with no name kept; PERF.md section 6, PR 30-31).

A KDA block (kimi-linear, 1x8192, 32 heads of 128 x 128) keeps its input
37.7 MB (hidden 2304), the rule's output 67.1 MB, the chunk states 268 MB,
the inverses 33.6 MB and the decayed scores 33.6 MB: 440 MB a layer, and four
``apex_kda_fwd`` calls a step for eight (the compile-only rehearsal of that
cell's window reads 6.72 GiB of temporaries, PERF.md section 6, PR 46).

An expert block keeps its plan beside all that, ``8 x (tokens x k + rows)``
bytes a layer and the small tables: trinity-mini (1x8192, k 8, 69,632
rows) 1.08 MB, qwen3-next (k 10, 90,112 rows) 1.38 MB, moonlight (k 6,
51,200 rows) 0.80 MB, smallthinker (1x16384, k 6, 100,352 rows) 1.59 MB,
lfm2 (1x16384, k 4, 67,584 rows) 1.07 MB — 4.0 to 6.4 MB over a cell's
four or five expert layers, against the 34–117 MB a layer of ``out`` above
(PERF.md section 6, PR 40, has what the second making cost on the chip).

A gated MLP keeps ``gate_up``'s output beside its block's input, ``2 d_ff
/ d`` times the input's bytes a ``SwiGLU`` (bf16; the input 33.6 MB at 8192
tokens x 2048, 67.1 MB at 16,384):

======================  ===========================  ================  ========
configuration           ``SwiGLU`` s a step          kept a ``SwiGLU``  a step
======================  ===========================  ================  ========
granite-h, 1x8192       10 dense x 8192 wide         268 MB            2.68 GB
lfm2, 1x16384           1 dense x 11,776             772 MB            0.77 GB
moonlight, 1x8192       1 dense x 11,264 +           369 MB +          0.83 GB
                        5 shared x 2816              92 MB
trinity-mini, 1x8192    1 dense x 6144 +             201 MB +          0.34 GB
                        4 shared x 1024              33.6 MB
qwen3-next, 1x8192      4 shared x 512 (gated)       16.8 MB           0.07 GB
======================  ===========================  ================  ========

The yardstick for naming the next array is the step time a GB kept buys
(PERF.md section 6, PR 45): ``gate_up`` 11.2 ms a GB (30.1 ms for 2.68 GB
in granite-h at the product's own rate on the chip, 183 TFLOP/s: a product
``d`` deep costs ``d`` operations a byte of its output; the chip read 30.3
ms off ``gate_up`` and 29.0 off the step), against 4.5 ms a
GB for the state-space scan's output and chunk states (1.2 GB for 5.4 ms),
which stay unnamed; Mamba's ``in_proj`` output prices the same as
``gate_up`` (1.25 GB, ~14 ms) and does not fit the chip beside it
(ROADMAP.md S12 has the queue).  granite-h's window holds 14.17 GiB of the
chip's 15.75 with all ten kept (the compile-only rehearsal; 11.66 before;
``memory_peak_bytes`` on the chip 15.04 GB of 17.18 for 12.72):
a user who needs that room back has ``remat_policy`` — there is no switch
a name.

For GPT and BERT heads x head size = hidden, so ``full_block`` keeps two
arrays of the input's size a block where it kept one: about 1/9th of
what ``none`` keeps (34 x rows x seq x hidden bytes a block by the usual
count), up from about 1/17th.
"""
from __future__ import annotations

from typing import Callable, Optional, Sequence

import jax

REMAT_POLICIES = ("none", "dots_saveable", "full_block")

# The residuals kernels (and the expert layer) declare (``checkpoint_name``)
# and the block-recomputing policies keep.  They import the names from
# here; this module imports only jax.
FLASH_OUT = "apex_flash_out"
FLASH_LSE = "apex_flash_lse"
GDN_OUT = "apex_gdn_out"
GDN_STATES = "apex_gdn_states"
GDN_TRI = "apex_gdn_tri"
KDA_OUT = "apex_kda_out"
KDA_STATES = "apex_kda_states"
KDA_TRI = "apex_kda_tri"
KDA_SCORES = "apex_kda_scores"
MOE_SEL = "apex_moe_sel"
MOE_PLAN = "apex_moe_plan"
MLP_GATE_UP = "apex_mlp_gate_up"
KEPT_RESIDUAL_NAMES = (FLASH_OUT, FLASH_LSE, GDN_OUT, GDN_STATES, GDN_TRI,
                       KDA_OUT, KDA_STATES, KDA_TRI, KDA_SCORES,
                       MOE_SEL, MOE_PLAN, MLP_GATE_UP)


def checkpoint_policy(policy: Optional[str]):
    """Map a policy name to the ``jax.checkpoint`` policy callable.

    Returns None for ``none``/``None`` — meaning "do not wrap at all"
    (NOT ``jax.checkpoint``'s save-nothing default).  ``full_block``
    keeps the residuals kernels declare under
    :data:`KEPT_RESIDUAL_NAMES` and nothing else; ``dots_saveable`` keeps
    them beside the dot outputs.  Building either sets the gauge
    ``remat.kept_names`` (``obs.default_registry()``) to the count of
    names it keeps.
    """
    if policy is None or policy == "none":
        return None
    if policy not in REMAT_POLICIES:
        raise ValueError(
            f"remat_policy must be one of {REMAT_POLICIES}, got {policy!r}"
        )
    from apex_tpu import obs

    obs.default_registry().gauge("remat.kept_names").set(
        len(KEPT_RESIDUAL_NAMES))
    named = jax.checkpoint_policies.save_only_these_names(
        *KEPT_RESIDUAL_NAMES)
    if policy == "full_block":
        return named
    return jax.checkpoint_policies.save_from_both_policies(
        jax.checkpoint_policies.dots_saveable, named)


def remat_fn(
    fn: Callable, policy: Optional[str], static_argnums: Sequence[int] = ()
) -> Callable:
    """``jax.checkpoint``-wrap a plain function per ``policy`` (identity
    for ``none``)."""
    pol = checkpoint_policy(policy)
    if pol is None:
        return fn
    return jax.checkpoint(
        fn, policy=pol, static_argnums=tuple(static_argnums)
    )


def remat_module(
    module_cls, policy: Optional[str], static_argnums: Sequence[int] = ()
):
    """Lift a flax module class through ``nn.remat`` per ``policy``.

    Identity for ``none`` — callers can apply it unconditionally.
    ``static_argnums`` indexes ``__call__``'s arguments with ``self`` at
    0 (so a ``deterministic`` flag at ``__call__(self, x, deterministic)``
    is index 2); flags marked static MUST then be passed positionally.
    The lifted class binds the same parameter structure as the bare one
    (tested in tests/test_models.py), so remat is a free A/B on existing
    checkpoints.
    """
    pol = checkpoint_policy(policy)
    if pol is None:
        return module_cls
    import flax.linen as nn

    return nn.remat(
        module_cls, policy=pol, static_argnums=tuple(static_argnums)
    )
