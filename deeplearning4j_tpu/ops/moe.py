"""Mixture-of-experts routing: top-1 (Switch-style) with a capacity, and
top-k with no capacity over experts held as sorted row groups.

No reference counterpart (SURVEY §2.6 note 5: the reference predates
expert parallelism); build-plan extension. TPU-first formulation: hard
routing is expressed as dense dispatch/combine one-hot tensors and
einsums — gathers/scatters become MXU matmuls, shapes stay static
(capacity-bounded), and when the expert dimension of the weights is
sharded over a mesh ``expert`` axis XLA lowers the dispatched einsum to
the canonical all-to-all. Overflowed tokens (expert over capacity) pass
through the residual path with zero expert output, as in Switch.

Top-k (``topk_routing`` down): every token picks ``k`` experts on its scores
(plus a per-expert bias that decides the selection only) and weights them by
the scores; nothing is dropped. The assignments to the experts held here are
sorted by expert into one buffer of ``n * k`` rows (the worst case, every
pick a held expert), whose first rows are the held experts' groups in order
(``sort_by_expert``), for the grouped products of ``ops/grouped_matmul.py``;
``dispatch`` gathers the tokens' rows into that order and ``combine`` sums
each token's weighted rows back. Both are gathers both ways: their gradients
gather again through the inverse order, never a scatter. An assignment to an
expert not held here adds nothing.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def top1_dispatch(gate_logits: jnp.ndarray, capacity: int,
                  valid: jnp.ndarray = None):
    """gate_logits [n, E] → (dispatch [n, E, C] one-hot, combine
    [n, E, C] gate-weighted, aux_loss scalar).

    ``valid`` [n] (optional): masked-out tokens are routed nowhere —
    they consume no capacity slots and are excluded from the aux loss
    (padded timesteps must not starve real tokens of capacity).

    aux_loss is the Switch load-balancing loss E·Σ_e f_e·p_e (fraction
    routed × mean router prob) — add it to the training objective to
    keep experts utilized.
    """
    n, e = gate_logits.shape
    probs = jax.nn.softmax(gate_logits.astype(jnp.float32), axis=-1)
    expert = jnp.argmax(probs, axis=-1)                      # [n]
    onehot = jax.nn.one_hot(expert, e, dtype=jnp.float32)    # [n, E]
    if valid is not None:
        v = valid.astype(jnp.float32)
        onehot = onehot * v[:, None]
        n_valid = jnp.maximum(jnp.sum(v), 1.0)
    else:
        v = None
        n_valid = float(n)
    # position of each token within its expert's queue
    pos = jnp.cumsum(onehot, axis=0) * onehot - 1.0          # [n, E]
    keep = (pos >= 0) & (pos < capacity)
    pos_clamped = jnp.clip(pos, 0, capacity - 1).astype(jnp.int32)
    dispatch = (jax.nn.one_hot(pos_clamped, capacity, dtype=jnp.float32)
                * keep[..., None].astype(jnp.float32))       # [n, E, C]
    gate = jnp.sum(probs * onehot, axis=-1)                  # [n]
    combine = dispatch * gate[:, None, None]
    frac_routed = jnp.sum(onehot, axis=0) / n_valid
    mean_prob = (jnp.sum(probs * (v[:, None] if v is not None else 1.0),
                         axis=0) / n_valid)
    aux_loss = e * jnp.sum(frac_routed * mean_prob)
    return dispatch, combine, aux_loss


def moe_ffn(x: jnp.ndarray, Wg, W1, b1, W2, b2,
            capacity_factor: float = 1.25, valid: jnp.ndarray = None):
    """x [n, d] → ([n, d], aux_loss). Expert weights: W1 [E, d, f],
    b1 [E, f], W2 [E, f, d], b2 [E, d]; router Wg [d, E]. ``valid``
    [n]: tokens to route (masked tokens get zero output and no slot)."""
    n, d = x.shape
    e = W1.shape[0]
    capacity = max(1, int(capacity_factor * n / e))
    gate_logits = x.astype(jnp.float32) @ Wg.astype(jnp.float32)
    dispatch, combine, aux = top1_dispatch(gate_logits, capacity, valid=valid)
    dispatch = dispatch.astype(x.dtype)
    combine = combine.astype(x.dtype)
    xe = jnp.einsum("nec,nd->ecd", dispatch, x)              # [E, C, d]
    h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", xe, W1) + b1[:, None, :])
    ye = jnp.einsum("ecf,efd->ecd", h, W2) + b2[:, None, :]  # [E, C, d]
    y = jnp.einsum("ecd,nec->nd", ye, combine)
    return y, aux


# ------------------------------------------------------- top-k, no capacity

def topk_routing(logits, k: int, bias=None, norm_topk_prob: bool = True,
                 scale: float = 1.0):
    """logits [n, E] (float32) -> (weights [n, k] float32, experts [n, k]
    int32). The scores are ``sigmoid(logits)``; the ``k`` experts of a token
    are those of the largest ``scores + bias`` (``bias`` [E] decides the
    selection only, and takes no gradient), weighted by their scores, over
    the picks' sum + 1e-6 where ``norm_topk_prob``, times ``scale``."""
    scores = jax.nn.sigmoid(logits)
    chosen = scores if bias is None else scores + jax.lax.stop_gradient(bias)
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(chosen), k)
    weights = jnp.take_along_axis(scores, experts, axis=-1)
    if norm_topk_prob:
        weights = weights / (jnp.sum(weights, axis=-1, keepdims=True) + 1e-6)
    return weights * scale, experts.astype(jnp.int32)


def sort_by_expert(experts, first: int, count: int):
    """experts [n, k] -> ``(order [n * k], group_sizes [count], held [n, k],
    position [n, k])``: the flat assignments sorted by held expert (expert
    ``first + g`` is group ``g``; the others after them), how many fall to
    each held expert, which are held, and where each one sits in the sorted
    order."""
    local = experts - first
    held = (local >= 0) & (local < count)
    key = jnp.where(held, local, count).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    position = jnp.argsort(order).astype(jnp.int32).reshape(experts.shape)
    sizes = jnp.sum(key[:, None] == jnp.arange(count, dtype=key.dtype)[None, :],
                    axis=0, dtype=jnp.int32)
    return order, sizes, held, position


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dispatch(x, order, position, held, k: int):
    """x [n, d] -> [n * k, d]: row ``r`` is the token of sorted assignment
    ``r``. The gradient gathers each token's held rows and sums them."""
    return x[order // k]


def _dispatch_fwd(x, order, position, held, k):
    return x[order // k], (position, held)


def _dispatch_bwd(k, res, g):
    position, held = res
    rows = jnp.where(held[..., None], g[position].astype(jnp.float32), 0.0)
    return jnp.sum(rows, axis=1).astype(g.dtype), None, None, None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5,))
def combine(y, weights, order, position, held, k: int):
    """y [n * k, d] (rows in sorted order) -> [n, d] in y's dtype: a token's
    held assignments' rows times their weights [n, k], summed in float32.
    Rows of assignments not held are read through a select, so whatever they
    hold (the grouped kernels leave them undefined) adds nothing."""
    rows = jnp.where(held[..., None], weights[..., None]
                     * y[position].astype(jnp.float32), 0.0)
    return jnp.sum(rows, axis=1).astype(y.dtype)


def _combine_fwd(y, weights, order, position, held, k):
    return combine(y, weights, order, position, held, k), \
        (y, weights, order, position, held)


def _combine_bwd(k, res, g):
    y, weights, order, position, held = res
    g32 = g.astype(jnp.float32)
    dw = jnp.where(held, jnp.sum(y[position].astype(jnp.float32)
                                 * g32[:, None], axis=-1), 0.0)
    # sorted row r is assignment order[r]: its token's gradient times its
    # weight, and nothing for a row that no held assignment fills
    w_sorted = weights.reshape(-1)[order]
    dy = jnp.where(held.reshape(-1)[order][:, None],
                   w_sorted[:, None] * g32[order // k], 0.0)
    return dy.astype(y.dtype), dw.astype(weights.dtype), None, None, None


combine.defvjp(_combine_fwd, _combine_bwd)
