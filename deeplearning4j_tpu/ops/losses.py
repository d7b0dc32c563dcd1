"""Loss functions with per-example masking.

Parity surface: DL4J 0.6.1 ``LossFunctions.LossFunction`` (used by output
layers, ``nn/conf/layers/OutputLayer`` + ND4J ``LossCalculation``). All
losses here:

- take pre-activation outputs OR activated outputs? → activated outputs
  ("labels" vs "predictions"), matching the reference where the output
  layer activates then scores; the fused softmax+NLL fast path is applied
  automatically for MCXENT/NEGATIVELOGLIKELIHOOD when given logits via
  ``from_logits=True`` (numerically the TPU-correct formulation),
- support an optional per-example (or per-timestep) mask, the reference's
  variable-length time-series machinery (``TimeSeriesUtils.java``),
- reduce to *mean over examples* of the *sum over output features*, the
  reference's score convention (score = loss / #examples).
"""

from __future__ import annotations

import enum
from typing import Optional, Union

import jax
import jax.numpy as jnp

_EPS = 1e-7


def _masked_mean(per_ex: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Masked mean whose value AND gradients are bitwise-identical to
    ``jnp.mean`` over the unmasked rows (the shape-bucketing tail-batch
    parity guarantee):

    - gradients flow through the true division ``total / count`` —
      its cotangent ``g / count`` is the same correctly-rounded value
      as the constant-folded ``g * (1/n)`` the mean backward emits;
    - the FORWARD value is corrected to ``total * (1/count)``, the
      rounding XLA's strength-reduced division-by-compile-time-count
      produces for ``jnp.mean`` (one extra rounding vs true division
      when the count is not a power of two). The correction rides a
      ``stop_gradient`` so the backward graph is exactly the division
      form; ``d + stop_grad(r - d) == r`` exactly (Sterbenz: r, d are
      within one ulp, so ``r - d`` and the re-add are exact)."""
    mask = mask.astype(per_ex.dtype)
    total = jnp.sum(per_ex * mask)
    count = jnp.maximum(jnp.sum(mask), 1.0)
    d = total / count
    r = total * (1.0 / count)
    return d + jax.lax.stop_gradient(r - d)


class LossFunction(str, enum.Enum):
    MSE = "mse"
    L1 = "l1"
    L2 = "l2"
    XENT = "xent"  # binary cross-entropy
    MCXENT = "mcxent"  # multi-class cross-entropy
    NEGATIVELOGLIKELIHOOD = "negativeloglikelihood"  # == MCXENT in the reference
    COSINE_PROXIMITY = "cosine_proximity"
    HINGE = "hinge"
    SQUARED_HINGE = "squared_hinge"
    KL_DIVERGENCE = "kl_divergence"
    MEAN_ABSOLUTE_ERROR = "mean_absolute_error"
    MEAN_ABSOLUTE_PERCENTAGE_ERROR = "mean_absolute_percentage_error"
    MEAN_SQUARED_LOGARITHMIC_ERROR = "mean_squared_logarithmic_error"
    POISSON = "poisson"
    RECONSTRUCTION_CROSSENTROPY = "reconstruction_crossentropy"


def _per_example(loss_fn_name: LossFunction, labels: jnp.ndarray, preds: jnp.ndarray) -> jnp.ndarray:
    """Per-example loss: sum over the feature axis (last). Shapes [..., nOut] -> [...]."""
    f = loss_fn_name
    if f in (LossFunction.MSE, LossFunction.L2):
        # DL4J scores MSE as the sum of squared errors over the feature axis
        d = labels - preds
        return jnp.sum(d * d, axis=-1)
    if f in (LossFunction.L1, LossFunction.MEAN_ABSOLUTE_ERROR):
        return jnp.sum(jnp.abs(labels - preds), axis=-1)
    if f in (LossFunction.XENT, LossFunction.RECONSTRUCTION_CROSSENTROPY):
        p = jnp.clip(preds, _EPS, 1.0 - _EPS)
        return -jnp.sum(labels * jnp.log(p) + (1.0 - labels) * jnp.log1p(-p), axis=-1)
    if f in (LossFunction.MCXENT, LossFunction.NEGATIVELOGLIKELIHOOD):
        p = jnp.clip(preds, _EPS, 1.0)
        return -jnp.sum(labels * jnp.log(p), axis=-1)
    if f is LossFunction.COSINE_PROXIMITY:
        ln = labels / (jnp.linalg.norm(labels, axis=-1, keepdims=True) + _EPS)
        pn = preds / (jnp.linalg.norm(preds, axis=-1, keepdims=True) + _EPS)
        return -jnp.sum(ln * pn, axis=-1)
    if f is LossFunction.HINGE:
        # labels in {-1, +1} (or one-hot converted upstream)
        return jnp.sum(jax.nn.relu(1.0 - labels * preds), axis=-1)
    if f is LossFunction.SQUARED_HINGE:
        h = jax.nn.relu(1.0 - labels * preds)
        return jnp.sum(h * h, axis=-1)
    if f is LossFunction.KL_DIVERGENCE:
        l = jnp.clip(labels, _EPS, 1.0)
        p = jnp.clip(preds, _EPS, 1.0)
        return jnp.sum(l * (jnp.log(l) - jnp.log(p)), axis=-1)
    if f is LossFunction.MEAN_ABSOLUTE_PERCENTAGE_ERROR:
        # sign-preserving clamp of the denominator (zero labels treated as +eps)
        denom = jnp.where(labels >= 0, 1.0, -1.0) * jnp.maximum(jnp.abs(labels), _EPS)
        return jnp.sum(jnp.abs((labels - preds) / denom), axis=-1) * 100.0
    if f is LossFunction.MEAN_SQUARED_LOGARITHMIC_ERROR:
        d = jnp.log1p(jnp.maximum(preds, -1.0 + _EPS)) - jnp.log1p(jnp.maximum(labels, -1.0 + _EPS))
        return jnp.sum(d * d, axis=-1)
    if f is LossFunction.POISSON:
        p = jnp.clip(preds, _EPS, None)
        return jnp.sum(p - labels * jnp.log(p), axis=-1)
    raise ValueError(f"unknown loss function {f}")


def target_value(z: jnp.ndarray, ids: jnp.ndarray) -> jnp.ndarray:
    """``z[..., ids]``: the value of sparse ids ``[...]`` along the last axis
    of ``z`` ``[..., V]``, as a masked sum over that axis (negative ids read
    class 0; ids >= V read 0.0).

    Not ``take_along_axis``: a gather fixes its operand's layout, so where
    the head's matmul writes the logits vocabulary-second (as it does for a
    ``[8, 1024, 50257]`` float32 LM batch on a v5e) XLA relays all of them
    out to read one number a token. A masked sum reads whatever layout the
    matmul chose, and XLA fuses it into a pass that reads the logits anyway
    (the log-sum-exp's, or the bias gradient's). The value is the gather's
    to the bit (a sum of one value and zeros), the gradient the same
    ``iota == id`` select the gather's transpose lowers to."""
    hit = jax.lax.broadcasted_iota(jnp.int32, z.shape, z.ndim - 1) \
        == jnp.clip(ids, 0, None)[..., None]
    return jnp.sum(jnp.where(hit, z, 0), axis=-1)


def compute_loss(
    name: Union[str, LossFunction],
    labels: jnp.ndarray,
    predictions: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    from_logits: bool = False,
    reduction: str = "mean",
) -> jnp.ndarray:
    """Masked mean-over-examples loss (scalar).

    ``labels``/``predictions``: [batch, nOut] or [batch, T, nOut] (RNN,
    reference reshapes [b,nOut,T]→[b*T,nOut]; we keep time as a leading
    structure and mask instead). ``mask`` broadcasts over the feature axis:
    [batch] or [batch, T].

    ``from_logits=True`` uses the fused log-softmax formulation for
    MCXENT/NLL and sigmoid-BCE-with-logits for XENT — numerically stable
    and what XLA fuses best; gradient-check tests verify it matches the
    activate-then-score reference semantics.

    Reduction semantics for [b, T, nOut] sequences: the default
    ``reduction="mean"`` averages over all b*T timesteps (or the mask
    count), which keeps the score scale independent of sequence length.
    The reference (``BaseOutputLayer.computeScore``) instead divides the
    summed sequence loss by minibatch size b only, so its RNN scores and
    effective learning rates scale with T; pass ``reduction="batch"`` to
    reproduce that behavior when matching reference configs exactly.
    """
    f = LossFunction(name)
    sparse = labels.ndim == predictions.ndim - 1
    if sparse and f not in (LossFunction.MCXENT,
                            LossFunction.NEGATIVELOGLIKELIHOOD):
        raise ValueError(
            f"sparse integer labels (shape {labels.shape} vs predictions "
            f"{predictions.shape}) are only supported for mcxent/nll")
    if sparse:
        # integer class-id labels: pick the target log-prob instead of
        # materializing one-hots — for a [b, t] LM batch over vocab V
        # this removes the [b, t, V] label tensor entirely (HBM traffic
        # and host->device staging shrink by a factor of V).
        # Contract: ids must be in [0, V); NEGATIVE ids are the
        # ignore-index convention — zero loss, excluded from the mean.
        # (ids >= V read a target of 0.0 silently — data validation
        # belongs host-side.)
        ids = labels.astype(jnp.int32)
        ignore = ids < 0
        tgt = target_value(predictions, ids)
        if from_logits:
            # -log_softmax[target] == logsumexp - target logit, both read
            # from the RAW logits
            per_ex = jax.scipy.special.logsumexp(predictions, axis=-1) - tgt
        else:
            # pick first, then log N elements (not the [N, V] matrix)
            per_ex = -jnp.log(jnp.clip(tgt, _EPS, 1.0))
        if mask is None:
            mask = (~ignore).astype(per_ex.dtype)
        else:
            mask = mask.astype(per_ex.dtype) * (~ignore).astype(per_ex.dtype)
    elif from_logits and f in (LossFunction.MCXENT, LossFunction.NEGATIVELOGLIKELIHOOD):
        logp = jax.nn.log_softmax(predictions, axis=-1)
        per_ex = -jnp.sum(labels * logp, axis=-1)
    elif from_logits and f is LossFunction.XENT:
        z, y = predictions, labels
        per_ex = jnp.sum(jax.nn.relu(z) - z * y + jnp.log1p(jnp.exp(-jnp.abs(z))), axis=-1)
    else:
        per_ex = _per_example(f, labels, predictions)

    if reduction == "batch":
        # reference semantics: sum everything, divide by minibatch size
        batch = per_ex.shape[0]
        if mask is not None:
            per_ex = per_ex * mask.astype(per_ex.dtype)
        return jnp.sum(per_ex) / batch
    if reduction != "mean":
        raise ValueError(f"unknown reduction {reduction!r} (use 'mean' or 'batch')")
    if mask is not None:
        return _masked_mean(per_ex, mask)
    return jnp.mean(per_ex)
