"""What the plain references share: float32 arithmetic at the ``highest``
matmul precision, the normalisation, the two GELUs, the token loss and the
two optimizers.

Nothing here imports the program (``apex_tpu``): the references are the
yardstick, written from the published descriptions.
"""
from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp

Params = Dict[str, jax.Array]

def mm(a, b):
    """``a @ b`` in float32 at ``highest`` precision (on a TPU the default
    would round the operands to bfloat16)."""
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def gelu_tanh(x):
    """GPT-2's ``gelu_new``."""
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def gelu_erf(x):
    """BERT's ``gelu``."""
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


ACTIVATIONS = {"gelu_new": gelu_tanh, "gelu": gelu_erf}


def attention(q, k, v, causal: bool):
    """Softmax attention over ``(rows, heads, seq, head_dim)``, every
    score materialised."""
    d = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=jax.lax.Precision.HIGHEST) / math.sqrt(d)
    if causal:
        n = q.shape[2]
        s = jnp.where(jnp.tril(jnp.ones((n, n), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v,
                      precision=jax.lax.Precision.HIGHEST)


def row_loss_sums(logits, labels):
    """Per row, the cross-entropy summed over the positions whose label is
    >= 0 (a label of -100 is not predicted)."""
    valid = labels >= 0
    safe = jnp.where(valid, labels, 0)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, lse - picked, 0.0), axis=-1)


# -- optimizers, as published ------------------------------------------------

def adamw_step(p, g, m, v, t, *, lr, b1=0.9, b2=0.999, eps=1e-8, wd=0.0):
    """Decoupled-weight-decay Adam (Loshchilov & Hutter), one leaf, step
    ``t`` counted from 1."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p
    return p - lr * u, m, v


def lamb_step(p, g, m, v, t, *, lr, b1=0.9, b2=0.999, eps=1e-6, wd=0.01):
    """LAMB (You et al.), one leaf; ``g`` already clipped by the global
    norm.  The trust ratio is ||p|| / ||u|| where both are positive."""
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    u = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps) + wd * p
    r1, r2 = jnp.linalg.norm(p), jnp.linalg.norm(u)
    ratio = jnp.where((r1 > 0) & (r2 > 0), r1 / r2, 1.0)
    return p - lr * ratio * u, m, v


def clip_by_global_norm(grads: Params, max_norm: float) -> Params:
    total = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in grads.values()))
    scale = 1.0 / jnp.maximum(1.0, total / max_norm)
    return {k: g * scale for k, g in grads.items()}


def optimizer(name: str, **hyper):
    """``(prepare(grads) -> grads as the update gets them, leaf step)`` for
    ``adamw`` or ``lamb`` (LAMB clips the whole gradient to norm 1)."""
    if name == "adamw":
        return (lambda g: g), lambda *a: adamw_step(*a, **hyper)
    if name == "lamb":
        return (lambda g: clip_by_global_norm(g, 1.0),
                lambda *a: lamb_step(*a, **hyper))
    raise ValueError(f"no reference optimizer {name!r}")
