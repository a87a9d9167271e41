"""Expert parallelism — Mixture-of-Experts with all_to_all dispatch.

No reference counterpart: apex has no MoE (SURVEY.md §2.4 marks EP "NO").
On TPU, expert parallelism is a named ``expert`` mesh axis: each device
holds ``num_experts / n`` expert FFNs, tokens are routed with a top-k
gate, and two ``jax.lax.all_to_all`` collectives move each token to its
expert's device and back (the Switch/GShard construction; cf. PAPERS.md
GShard/Switch entries).

Design (einsum dispatch, the Mesh-TensorFlow formulation — dense one-hot
dispatch/combine tensors, fully static shapes, MXU-friendly):

- router: ``gates = softmax(x @ wg)`` in fp32; top-k experts per token
  with renormalized weights.
- capacity: each expert accepts at most ``C = ceil(k * T * capacity_factor
  / E)`` tokens per device-batch; overflow tokens are dropped (their
  combine weight is zero, the residual path carries them — standard
  Switch semantics).  Position within the expert's buffer is assigned by
  a cumulative-sum over the token order.
- dispatch: ``expert_in[e, c, :] = Σ_t dispatch[t, e, c] * x[t]``; the
  (T, E, C) dispatch tensor is 0/1, combine holds the gate weights.
- all_to_all over the expert axis re-shards (E_global, C, d) →
  (E_local, n*C, d): each device receives its experts' buffers from every
  peer.  After the expert FFN the inverse all_to_all routes outputs home,
  and the combine einsum restores (T, d).

The aux load-balancing loss (Switch eq. 4: ``E * Σ_e f_e * P_e``) is
returned per-device; average it over the data axis with the rest of the
loss.  Everything is differentiable — all_to_all and the dispatch einsums
transpose cleanly, so ``jax.grad`` through the layer trains router and
experts together.
"""
from __future__ import annotations

import functools
import math
from typing import Any, Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from flax import linen as nn
from jax.ad_checkpoint import checkpoint_name

from apex_tpu.remat import MLP_GATE_UP, MOE_PLAN, MOE_SEL

__all__ = ["MoEMLP", "top_k_routing", "moe_mlp_ref", "ExpertShardMLP",
           "SwiGLU", "sigmoid_topk_routing", "softmax_topk_routing",
           "shard_dispatch"]


def top_k_routing(
    logits: jax.Array, k: int, capacity: int
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Top-k gating with capacity assignment.

    logits: (T, E) fp32.  Returns (dispatch (T, E, C) 0/1,
    combine (T, E, C) gate weights, aux load-balancing loss scalar).
    """
    t, e = logits.shape
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)  # (T, E)
    # top-k expert ids per token, gates renormalized over the chosen k
    top_gates, top_idx = jax.lax.top_k(gates, k)  # (T, k)
    top_gates = top_gates / jnp.sum(top_gates, axis=-1, keepdims=True)

    # one-hot per routing slot: (k, T, E); priority order is slot-major
    # (all tokens' 1st choice before any 2nd choice, GShard style)
    sel = jax.nn.one_hot(top_idx.T, e, dtype=jnp.float32)  # (k, T, E)
    # position of each (slot, token) in its expert's buffer: running count
    # of earlier claims on that expert, flattened over (slot, token)
    flat = sel.reshape(k * t, e)
    pos = jnp.cumsum(flat, axis=0) - flat  # claims strictly before
    keep = flat * (pos < capacity)
    pos_in = jax.nn.one_hot(
        jnp.sum(pos * flat, axis=-1).astype(jnp.int32), capacity,
        dtype=jnp.float32,
    )  # (k*T, C)
    dispatch_flat = keep[:, :, None] * pos_in[:, None, :]  # (k*T, E, C)
    dispatch = jnp.sum(dispatch_flat.reshape(k, t, e, capacity), axis=0)

    combine = dispatch * jnp.einsum("kte,tk->te", sel, top_gates)[:, :, None]

    # Switch aux loss: E * Σ_e (fraction of tokens routed to e, 1st choice)
    #                        * (mean router prob of e)
    f = jnp.mean(sel[0], axis=0)
    p = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(f * p)
    return dispatch, combine, aux


class MoEMLP(nn.Module):
    """Expert-parallel MoE FFN layer.

    Call inside shard_map over ``expert_axis`` (composes with a data
    axis).  ``num_experts`` is the GLOBAL expert count; this device holds
    ``num_experts // num_partitions`` expert FFNs as params of shape
    (E_local, d, d_ff) / (E_local, d_ff, d).  With ``num_partitions=1``
    (or outside shard_map) it degrades to a single-device MoE — used as
    the parity reference in tests.

    Input x: (T, d) local tokens.  Returns (y (T, d), aux loss scalar).
    """

    num_experts: int
    d_ff: int
    num_partitions: int = 1
    expert_axis: str = "expert"
    k: int = 2
    capacity_factor: float = 2.0
    activation: Callable = nn.gelu
    param_dtype: Any = jnp.float32
    compute_dtype: Optional[Any] = None
    router_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        t, d = x.shape
        e, n = self.num_experts, self.num_partitions
        if e % n:
            raise ValueError(
                f"num_experts ({e}) must be divisible by num_partitions ({n})"
            )
        e_local = e // n
        capacity = max(1, math.ceil(self.k * t * self.capacity_factor / e))

        wg = self.param("router", self.router_init, (d, e), jnp.float32)
        # router always in fp32 (the one blanket fp32 rule every MoE
        # implementation keeps: routing decisions are precision-sensitive)
        logits = jnp.einsum("td,de->te", x.astype(jnp.float32), wg)
        dispatch, combine, aux = top_k_routing(logits, self.k, capacity)

        def expert_init(init_fn):
            def init(rng, shape, dtype=jnp.float32):
                if n > 1:
                    rng = jax.random.fold_in(
                        rng, jax.lax.axis_index(self.expert_axis)
                    )
                return init_fn(rng, shape, dtype)

            return init

        w1 = self.param(
            "wi", expert_init(nn.initializers.lecun_normal()),
            (e_local, d, self.d_ff), self.param_dtype,
        )
        w2 = self.param(
            "wo", expert_init(nn.initializers.lecun_normal()),
            (e_local, self.d_ff, d), self.param_dtype,
        )

        cdtype = self.compute_dtype or x.dtype
        expert_in = jnp.einsum(
            "td,tec->ecd", x, dispatch.astype(x.dtype)
        )  # (E, C, d)
        if n > 1:
            # (E, C, d) -> (E_local, n*C, d): split experts, gather tokens
            expert_in = jax.lax.all_to_all(
                expert_in, self.expert_axis, split_axis=0, concat_axis=1,
                tiled=True,
            )
        h = jnp.einsum(
            "ecd,edf->ecf", expert_in.astype(cdtype), w1.astype(cdtype)
        )
        h = self.activation(h)
        expert_out = jnp.einsum("ecf,efd->ecd", h, w2.astype(cdtype))
        if n > 1:
            # (E_local, n*C, d) -> (E, C, d): outputs travel home
            expert_out = jax.lax.all_to_all(
                expert_out, self.expert_axis, split_axis=1, concat_axis=0,
                tiled=True,
            )
        y = jnp.einsum(
            "ecd,tec->td", expert_out.astype(jnp.float32),
            combine.astype(jnp.float32),
        )
        return y.astype(x.dtype), aux


def moe_mlp_ref(x, params, num_experts, k, activation=nn.gelu):
    """Dense (no-capacity, no-drop) reference: every token runs through
    its top-k experts at full precision.  Used by tests to pin the routed
    math when capacity is large enough that nothing drops."""
    wg, w1, w2 = params["router"], params["wi"], params["wo"]
    gates = jax.nn.softmax(x.astype(jnp.float32) @ wg, axis=-1)
    top_gates, top_idx = jax.lax.top_k(gates, k)
    top_gates = top_gates / jnp.sum(top_gates, axis=-1, keepdims=True)
    h = jnp.einsum("td,edf->tef", x, w1)  # run ALL experts densely
    y_all = jnp.einsum("tef,efd->ted", activation(h), w2)
    sel = jax.nn.one_hot(top_idx, num_experts, dtype=jnp.float32)  # (T,k,E)
    w = jnp.einsum("tke,tk->te", sel, top_gates)
    return jnp.einsum("ted,te->td", y_all.astype(jnp.float32), w).astype(
        x.dtype
    )


# ---------------------------------------------------------------------------
# One chip's share of an expert-parallel layer: no capacity, no dropped token
# ---------------------------------------------------------------------------
#
# ``MoEMLP`` above gives every expert a fixed capacity and drops what does
# not fit.  ``ExpertShardMLP`` is the other construction (top-k of sigmoid
# scores steered by a selection bias, DeepSeek-V3 / Trinity style, or of a
# softmax over all experts, Qwen3-Next style; a shared expert, with or
# without a sigmoid gate of its own): it is told which
# experts it HOLDS, routes every token over ALL ``num_experts``, keeps the
# token-slots whose expert it holds, sorts them by expert into a row buffer
# sized for the worst case, runs the grouped SwiGLU over the held experts
# (``ops/grouped_mm.py``) and combines the rows back, weighted, per token.
# What the experts it does not hold would have added is simply not in its
# result: on one chip there is no exchange, and nothing stands in for one.

def _selected(values, k: int):
    """``(T, k)`` int32: the experts of the ``k`` largest ``values`` a token,
    named ``MOE_SEL`` — a block that is recomputed reads the kept
    selection and does not make ``top_k`` again."""
    _, sel = jax.lax.top_k(values, k)
    return checkpoint_name(sel.astype(jnp.int32), MOE_SEL)


def _picked(values, sel):
    """``values[t, sel[t, j]]`` as a masked sum over the expert axis: one
    non-zero term a slot, so the float a gather would read, to the bit — and
    its transpose is a masked broadcast where a gather's is a scatter."""
    hit = sel[..., None] == jnp.arange(values.shape[-1], dtype=sel.dtype)
    return jnp.sum(jnp.where(hit, values[:, None, :], 0), axis=-1)


def sigmoid_topk_routing(logits, bias, k: int, route_norm: bool,
                         route_scale: float):
    """``(sel (T, k) int32, weights (T, k) float32)``: sigmoid scores over
    all experts; the ``k`` largest of ``scores + bias`` are selected (the
    bias steers the selection only); the weights are the selected SCORES,
    normalised to sum to one where ``route_norm``, times ``route_scale``."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    sel = _selected(scores + bias.astype(jnp.float32), k)
    w = _picked(scores, sel)
    if route_norm:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return sel, w * route_scale


def softmax_topk_routing(logits, k: int, norm_topk_prob: bool):
    """``(sel (T, k) int32, weights (T, k) float32)``: a softmax over ALL
    experts in float32; the ``k`` largest probabilities are selected and
    are the weights, renormalised to sum to one where ``norm_topk_prob``.
    No selection bias, no scale."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), -1)
    sel = _selected(probs, k)
    w = _picked(probs, sel)
    if norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return sel, w


SCORE_FUNCS = ("sigmoid", "softmax")
# the gated unit of a routed expert: ``act(gate) * up``
UNIT_FUNCS = {"silu": nn.silu, "relu": nn.relu}


def shard_dispatch(sel, held: Tuple[int, int], capacity: int, tile_rows: int):
    """Where the token-slots ``sel`` (T, k) that pick an expert in
    ``[held[0], held[1])`` go in a row buffer of ``capacity`` rows, grouped
    by expert in the tile-aligned layout of ``ops/grouped_mm.py``.

    Returns ``(layout, slot_row (T, k), row_slot (capacity,))``: the row of
    each slot (``capacity`` for a slot whose expert is not held) and the
    flat slot ``t * k + j`` of each row (``T * k`` for a row that holds
    none).  Within an expert the rows keep the slots' order.  No sort by
    value, no scatter and, a row, no gather: a running count gives each slot
    its rank, one argsort of the rows inverts the map, what a row needs of
    its group (first row, size, first sorted slot) is a sum over the held
    experts against the one-hot of the row's group, and its slot comes out
    of a copy of the sorted slots shifted by the group's padding.  (A SLOT
    reads its group's first row by a gather still: as a sum over the one-hot
    it was no faster at ``moonlight.train-8k``'s shape, PERF.md section 5.)"""
    from apex_tpu.ops.grouped_mm import group_layout

    t, k = sel.shape
    lo, hi = held
    n_held, n = hi - lo, t * k
    local = (sel - lo).reshape(n)
    mine = (local >= 0) & (local < n_held)
    onehot = ((local[:, None] == jnp.arange(n_held)[None, :])
              & mine[:, None]).astype(jnp.int32)
    count = jnp.cumsum(onehot, axis=0)
    sizes = count[-1]
    rank = jnp.sum((count - onehot) * onehot, axis=-1)
    layout = group_layout(sizes, capacity, tile_rows)
    slot_row = jnp.where(
        mine, layout.row_start[jnp.clip(local, 0, n_held - 1)] + rank,
        capacity)
    order = jnp.argsort(slot_row)            # held slots first, by row
    row = jnp.arange(capacity, dtype=jnp.int32)
    # a row's group is the last whose first row is at or before it (past the
    # end: the last group): experts leading, the rows along the lanes
    reached = row[None, :] >= layout.row_start[:, None]
    in_group = reached & ~jnp.concatenate(
        [reached[1:], jnp.zeros((1, capacity), bool)])
    of_group = lambda table: jnp.sum(
        jnp.where(in_group, table[:, None], 0), axis=0)
    within = row - of_group(layout.row_start)
    live = (within < of_group(sizes)) & (
        row // tile_rows < layout.tiles_used[0])
    first = jnp.cumsum(sizes) - sizes        # a group's first sorted slot
    # a group's rows are one run of ``order``, moved by the tiles' padding
    # before it: a shifted copy a held expert in place of a gather a row (a
    # scan, not a Python loop: 32 groups unrolled cost seconds of tracing)
    room = jnp.full((capacity,), n, order.dtype)
    padded = jnp.concatenate([room, order, room])

    def shifted(row_slot, group):
        rows_of, moved = group
        run = jax.lax.dynamic_slice(padded, (capacity - moved,), (capacity,))
        return jnp.where(rows_of & live, run, row_slot), None

    row_slot, _ = jax.lax.scan(
        shifted, room, (in_group, layout.row_start - first))
    return layout, slot_row.reshape(t, k), row_slot.astype(jnp.int32)


def _block_starts(sel, held: Tuple[int, int], layout, block: int):
    """``(T / block + 1, held)``: the rows ``starts[b, e] .. starts[b + 1,
    e]`` of the buffer :func:`shard_dispatch` lays out are those of held
    expert ``e`` whose token lies in ``[b * block, (b + 1) * block)`` — a
    group's rows keep the token order, so a block of tokens owns one range
    of each group (``ops/moe_rows.py::combine_rows`` walks them)."""
    t, k = sel.shape
    # experts leading, a block's slots along the lanes: one compare and one
    # lane reduction
    picks = (sel.reshape(1, t // block, block * k)
             == jnp.arange(*held)[:, None, None]).astype(jnp.int32).sum(-1)
    before = jnp.cumsum(picks.T, axis=0)
    return layout.row_start[None] + jnp.concatenate(
        [jnp.zeros_like(before[:1]), before])


def _take_rows(x, idx):
    """``x[idx]`` by rows, zeros where ``idx`` is out of range."""
    return jnp.take(x, idx, axis=0, mode="fill", fill_value=0)


# The two movements between the token order and the row buffer.  Each is a
# ``custom_vjp`` so that its gradient comes back by gathers too (the transpose
# of a gather would scatter).  ``tile_rows`` None: every pass is a
# ``jnp.take`` over the whole buffer and every slot, rows that hold no token
# come out zero — the path off the TPU, and the oracle of the kernels' tests.
# ``tile_rows`` given: ``ops/moe_rows.py``'s kernels visit the live tiles and
# the held slots only, and the tiles past ``layout.tiles_used`` are UNDEFINED.

class _Routing(NamedTuple):
    """Where the slots went (all int32; no gradient)."""
    layout: Any              # ops/grouped_mm.py::GroupLayout
    slot_row: jax.Array      # (T, k) row of each slot, capacity: not held
    row_slot: jax.Array      # (capacity,) flat slot of each row, T k: none
    row_token: jax.Array     # (capacity,) token of each row, T: none
    starts: Optional[jax.Array]   # _block_starts, for the kernels alone


def _route(sel, held: Tuple[int, int], capacity: int, tile_rows: int,
           block: Optional[int] = None) -> _Routing:
    """:func:`shard_dispatch` of ``sel`` and what follows from it;
    ``block`` (``ops/moe_rows.py::combine_block``) where the kernels will
    move the rows."""
    t, k = sel.shape
    layout, slot_row, row_slot = shard_dispatch(sel, held, capacity, tile_rows)
    return _Routing(
        layout, slot_row, row_slot,
        jnp.where(row_slot < t * k, row_slot // k, t),
        None if block is None else _block_starts(sel, held, layout, block))


def _sum_slots(rows, slot_row, weights=None):
    """``(T, d)`` float32: per token the sum over its slots of the slot's
    row (times the slot's weight)."""
    acc = 0.0
    for j in range(slot_row.shape[1]):
        part = _take_rows(rows, slot_row[:, j]).astype(jnp.float32)
        acc = acc + (part if weights is None else part * weights[:, j, None])
    return acc


def _no_grad(tree):
    import numpy as np

    return jax.tree_util.tree_map(
        lambda a: np.zeros(a.shape, jax.dtypes.float0), tree)


def _live_records(rows, routing: _Routing, tile_rows: int):
    from apex_tpu.ops import moe_rows

    return moe_rows.live_records(rows, routing.layout, tile_rows=tile_rows)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _rows_from_tokens(x, routing: _Routing, tile_rows):
    """``(capacity, d)``: row r holds token ``row_token[r]``; a row that
    holds none is zero (``tile_rows`` None) or, past the live tiles,
    undefined."""
    if tile_rows is None:
        return _take_rows(x, routing.row_token)
    from apex_tpu.ops import moe_rows

    return moe_rows.gather_rows(
        moe_rows.records(x), routing.row_token, routing.layout,
        tile_rows=tile_rows, out_dtype=x.dtype)


def _rows_from_tokens_fwd(x, routing, tile_rows):
    return _rows_from_tokens(x, routing, tile_rows), routing


def _rows_from_tokens_bwd(tile_rows, routing, g):
    if tile_rows is None:
        dx = _sum_slots(g, routing.slot_row).astype(g.dtype)
    else:
        from apex_tpu.ops import moe_rows

        dx = moe_rows.combine_rows(
            _live_records(g, routing, tile_rows), routing.slot_row,
            routing.row_slot, routing.starts, out_dtype=g.dtype)
    return dx, _no_grad(routing)


_rows_from_tokens.defvjp(_rows_from_tokens_fwd, _rows_from_tokens_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _tokens_from_rows(rows, weights, routing: _Routing, tile_rows):
    """``(T, d)`` float32: token t's held slots' rows, each times its
    weight, summed in slot order."""
    if tile_rows is None:
        return _sum_slots(rows, routing.slot_row, weights)
    from apex_tpu.ops import moe_rows

    return moe_rows.combine_rows(
        _live_records(rows, routing, tile_rows), routing.slot_row,
        routing.row_slot, routing.starts, weights=weights)


def _tokens_from_rows_fwd(rows, weights, routing, tile_rows):
    return (_tokens_from_rows(rows, weights, routing, tile_rows),
            (rows, weights, routing))


def _tokens_from_rows_bwd(tile_rows, res, g):
    rows, weights, routing = res
    slot_row = routing.slot_row
    if tile_rows is None:
        row_weight = _take_rows(weights.reshape(-1), routing.row_slot)
        d_rows = (_take_rows(g, routing.row_token)
                  * row_weight[:, None]).astype(rows.dtype)
        d_weights = jnp.stack([
            jnp.sum(g * _take_rows(rows, slot_row[:, j]).astype(jnp.float32),
                    axis=-1)
            for j in range(slot_row.shape[1])], axis=-1)
    else:
        from apex_tpu.ops import moe_rows

        d_rows = moe_rows.gather_rows(
            moe_rows.records(g), routing.row_token, routing.layout,
            tile_rows=tile_rows, out_dtype=rows.dtype,
            weights=weights.reshape(-1), weight_index=routing.row_slot)
        d_weights = moe_rows.slot_dots(
            _live_records(rows, routing, tile_rows), slot_row,
            routing.row_slot, routing.starts, g)
    return d_rows, d_weights.astype(weights.dtype), _no_grad(routing)


_tokens_from_rows.defvjp(_tokens_from_rows_fwd, _tokens_from_rows_bwd)


class SwiGLU(nn.Module):
    """``down(silu(gate(x)) * up(x))``, no biases: a dense gated MLP (the
    shared expert; a model's dense layers).  ``gate`` and ``up`` are one
    matrix ``gate_up`` (d, 2 d_ff), gate first.  That product's output
    bears the name ``remat.MLP_GATE_UP``, which both block-recomputing
    policies keep (``apex_tpu/remat.py``): a block run again in the backward
    pass reads it and makes the MLP's dearest product once a step, at
    ``2 d_ff / d`` times the block's input in bytes."""

    d_ff: int
    compute_dtype: Any = jnp.float32
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x):
        from apex_tpu.amp.layers import Dense

        dense = lambda n, name: Dense(
            n, use_bias=False, dtype=self.compute_dtype,
            kernel_init=self.kernel_init, name=name)
        # a block that is recomputed reads it kept (remat.py)
        gate_up = checkpoint_name(dense(2 * self.d_ff, "gate_up")(x),
                                  MLP_GATE_UP)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        return dense(x.shape[-1], "down")(nn.silu(gate) * up)


class ExpertShardMLP(nn.Module):
    """One chip's share of an expert-parallel layer of gated units.

    ``x`` (T, d) -> (T, d): the shared expert's output plus, for every
    token, the weighted outputs of those of its ``k`` routed experts that
    lie in ``experts_held = (first, past_last)``.  The router scores all
    ``num_experts`` — from ``x``, or from ``router_input`` (T, d) where the
    call gives one: a block whose router reads the block's input while its
    experts read the normed stream after attention hands both over, and the
    routing plan then depends on nothing attention computes.  No token is
    dropped: the row buffer holds
    ``T * min(k, held)`` rows — every token picking only held experts —
    plus a tile a held expert for the alignment, and the grouped products
    touch only the tiles that hold rows.  Summed over the shares that
    together hold all experts, the routed parts are the whole layer's.

    The routing plan — the selection and every table of ``_Routing``, all
    integers — is made ONCE a step: its arrays bear the names
    ``remat.MOE_SEL`` and ``remat.MOE_PLAN``, which both block-recomputing
    policies keep (``apex_tpu/remat.py``), so a block run again in the
    backward pass reads them and makes no ``top_k``, running count,
    ``argsort`` or layout a second time; what a gradient flows through (the
    router's product, the scores, the picked weights, the row movement, the
    grouped products) still runs twice there.  The weights are picked by a
    masked sum over the experts, whose transpose is a masked broadcast: no
    gather in the forward pass and no scatter in the backward.

    ``score_func`` names the router's scores: ``sigmoid``
    (:func:`sigmoid_topk_routing`: ``route_norm``, ``route_scale`` and the
    selection bias apply) or ``softmax`` (:func:`softmax_topk_routing`: a
    softmax over all ``num_experts``, ``route_norm`` renormalises the
    picked probabilities; no bias, no scale).  ``unit_func`` names the
    routed experts' gated unit, ``act(gate) * up``: ``silu`` (SwiGLU) or
    ``relu`` (ReGLU, exact zeros where the gate is negative; the gradient
    at 0 is 0); the shared expert is a SwiGLU either way.  ``shared_gate``
    multiplies the shared expert's output by ``sigmoid(x w_sg)``, a gate of
    its own a token.

    Parameters: ``router`` (d, num_experts), ``expert_bias`` (num_experts,)
    (sigmoid scores only: added to the scores for the selection only; zero
    unless a loss-free balancing update moves it), ``wi`` (held, d, 2 d_ff)
    gate then up, ``wo`` (held, d_ff, d), the shared expert ``shared`` (a
    :class:`SwiGLU` of width ``shared_d_ff``; 0: none) and its gate
    ``shared_gate`` (d, 1).  Scopes ``moe_router``, ``moe_dispatch``,
    ``moe_experts``, ``moe_shared``.

    Rows and tokens change places by ``ops/moe_rows.py``'s kernels on the
    TPU where the shapes tile (``moe_rows.supported``), else by
    ``jnp.take``; the gauge ``moe.dispatch.kernels`` says which was traced.
    On the kernel path a pass costs what the routing made live, and the
    buffer's tiles past ``layout.tiles_used`` are UNDEFINED, not zero, in
    ``rows`` and in its gradient: no pass writes them.  Every reader
    ignores them — ``apex_gmm`` and ``apex_gmm_dw`` end their row axis at
    ``tiles_used`` and leave their outputs' tiles past it undefined too,
    the gated unit is elementwise, the combine reads held slots only — and
    whoever reads the buffer next has to as well.  (A live tile's rows past
    its ``tile_valid`` are zeros on both paths.)
    """

    num_experts: int
    experts_held: Tuple[int, int]
    d_ff: int
    k: int
    shared_d_ff: int = 0
    route_norm: bool = True
    route_scale: float = 1.0
    score_func: str = "sigmoid"
    shared_gate: bool = False
    unit_func: str = "silu"
    compute_dtype: Any = jnp.float32
    tile_rows: Optional[int] = None
    kernel_init: Callable = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x, router_input=None):
        from apex_tpu import obs
        from apex_tpu.ops import grouped_mm, moe_rows
        from apex_tpu.ops._common import pallas_default

        t, d = x.shape
        lo, hi = self.experts_held
        if self.score_func not in SCORE_FUNCS:
            raise ValueError(f"score_func must be one of {SCORE_FUNCS}, got "
                             f"{self.score_func!r}")
        if self.unit_func not in UNIT_FUNCS:
            raise ValueError(f"unit_func must be one of {tuple(UNIT_FUNCS)}, "
                             f"got {self.unit_func!r}")
        if router_input is not None and router_input.shape != x.shape:
            raise ValueError(f"router_input {router_input.shape} is not "
                             f"x's shape {x.shape}")
        if not 0 <= lo < hi <= self.num_experts:
            raise ValueError(f"experts_held {self.experts_held} is not a "
                             f"range of the {self.num_experts} experts")
        held = hi - lo
        tile_rows = self.tile_rows or grouped_mm.DEFAULT_TILE_ROWS
        capacity = grouped_mm.rows_capacity(
            t * min(self.k, held), held, tile_rows)
        dt = self.compute_dtype
        kernels = pallas_default(
            moe_rows.supported(t, self.k, d, tile_rows, dt))
        rows_tile = tile_rows if kernels else None
        reg = obs.default_registry()
        reg.gauge("moe.experts_held").set(held)
        reg.gauge("moe.experts_routed_over").set(self.num_experts)
        reg.gauge("moe.dispatch.rows_capacity").set(capacity)
        reg.gauge("moe.dispatch.slots").set(t * self.k)
        reg.gauge("moe.dispatch.kernels").set(int(kernels))

        router = self.param("router", self.kernel_init,
                            (d, self.num_experts), jnp.float32)
        sigmoid = self.score_func == "sigmoid"
        if sigmoid:
            bias = self.param("expert_bias", nn.initializers.zeros_init(),
                              (self.num_experts,), jnp.float32)
        wi = self.param("wi", self.kernel_init, (held, d, 2 * self.d_ff),
                        jnp.float32)
        wo = self.param("wo", self.kernel_init, (held, self.d_ff, d),
                        jnp.float32)

        with jax.named_scope("moe_router"):
            # float32 scores at full precision: the selection is a
            # discontinuity, so the router alone does not take the MXU's
            # single bfloat16 pass
            scored = x if router_input is None else router_input
            logits = jnp.matmul(scored.astype(jnp.float32),
                                router.astype(jnp.float32),
                                precision=jax.lax.Precision.HIGHEST)
            if sigmoid:
                sel, weights = sigmoid_topk_routing(
                    logits, bias, self.k, self.route_norm, self.route_scale)
            else:
                sel, weights = softmax_topk_routing(
                    logits, self.k, self.route_norm)
        with jax.named_scope("moe_dispatch"):
            # the plan is integers, < 2 MB a layer: named, a block that is
            # recomputed reads it kept (remat.py) and makes none of it again
            routing = jax.tree_util.tree_map(
                lambda table: checkpoint_name(table, MOE_PLAN), _route(
                    jax.lax.stop_gradient(sel), (lo, hi), capacity, tile_rows,
                    moe_rows.combine_block(t, self.k, d) if kernels else None))
            layout = routing.layout
            rows = _rows_from_tokens(x.astype(dt), routing, rows_tile)
        with jax.named_scope("moe_experts"):
            gate, up = jnp.split(grouped_mm.grouped_matmul(
                rows, wi, layout, tile_rows=tile_rows), 2, axis=-1)
            rows = grouped_mm.grouped_matmul(
                UNIT_FUNCS[self.unit_func](gate) * up, wo, layout,
                tile_rows=tile_rows)
        with jax.named_scope("moe_dispatch"):
            y = _tokens_from_rows(rows, weights, routing, rows_tile)
        if self.shared_d_ff:
            with jax.named_scope("moe_shared"):
                shared = SwiGLU(self.shared_d_ff, dt, self.kernel_init,
                                name="shared")(x.astype(dt)).astype(jnp.float32)
                if self.shared_gate:
                    w_sg = self.param("shared_gate", self.kernel_init,
                                      (d, 1), jnp.float32)
                    shared = shared * jax.nn.sigmoid(jnp.matmul(
                        x.astype(jnp.float32), w_sg.astype(jnp.float32)))
                y = y + shared
        return y.astype(x.dtype)
