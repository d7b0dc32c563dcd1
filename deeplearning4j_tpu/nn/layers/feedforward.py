"""Feed-forward layer family: Dense, Output/RnnOutput/Loss, Embedding,
AutoEncoder, RBM, Activation, Dropout, GlobalPooling.

Parity anchors: ``nn/layers/feedforward/dense/DenseLayer.java``,
``nn/layers/BaseOutputLayer.java``, ``embedding/EmbeddingLayer.java``,
``autoencoder/AutoEncoder.java``, ``rbm/RBM.java`` (contrastive
divergence), ``nn/layers/BasePretrainNetwork.java``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.base import LayerImpl, register_impl, apply_dropout
from deeplearning4j_tpu.nn.quantize import is_quantized, qmatmul, qtake
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.activations import Activation, activate
from deeplearning4j_tpu.ops.losses import (LossFunction, _masked_mean,
                                           compute_loss, target_value)


def _fused_logits_pair(activation: str, loss_function: str) -> bool:
    """True when activation+loss compute via the numerically-stable fused
    from-logits path (identical math, one fewer HBM round-trip)."""
    act = Activation(activation)
    lf = LossFunction(loss_function)
    return (act is Activation.SOFTMAX and lf in (LossFunction.MCXENT,
                                                 LossFunction.NEGATIVELOGLIKELIHOOD)) or \
           (act is Activation.SIGMOID and lf is LossFunction.XENT)


class BaseDenseImpl(LayerImpl):
    """z = x·W + b ; a = act(z) (``BaseLayer.preOutput`` :354)."""

    supports_no_bias = True
    applies_drop_connect = True

    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        c = self.conf
        kW, _ = jax.random.split(key)
        W = init_weights(kW, (c.n_in, c.n_out), self.weight_init, c.n_in, c.n_out,
                         c.dist_mean, c.dist_std, dist=c.dist)
        if not c.has_bias:
            return {"W": W}
        b = jnp.full((c.n_out,), self.bias_init, jnp.float32)
        return {"W": W, "b": b}

    def preout(self, params, x):
        # serving-slice seam: a previous column-sharded dense layer left
        # x sharded on its feature dim — all-gather before W contracts
        # over it, so the contraction never reduces across shards
        x = self._slice_replicate(x)
        if is_quantized(params, "W"):
            # int8/fp8 weights: dequant fused into the matmul
            # (nn/quantize.py) — bias added in the scaled dtype
            z = qmatmul(x, params, "W")
            return z + params["b"].astype(z.dtype) if "b" in params else z
        z = x @ params["W"]
        return z + params["b"] if "b" in params else z

    def forward(self, params, x, state, train, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        params = self.maybe_drop_connect(params, train, rng)
        return activate(self.activation, self.preout(params, x)), state


@register_impl(L.DenseLayer)
class DenseImpl(BaseDenseImpl):
    pass


@register_impl(L.OutputLayer)
class OutputImpl(BaseDenseImpl):
    """Dense + loss (``nn/layers/OutputLayer.java``). Scoring uses the
    fused from-logits path when activation/loss pair allows (softmax+
    mcxent/nll, sigmoid+xent) — numerically identical, XLA-fused."""

    def has_loss(self) -> bool:
        return True

    def init_params(self, key):
        if getattr(self.conf, "tied_to", None):
            # the container hands this head the leaf it names (transposed,
            # as "W"): it owns nothing, so nothing is held twice
            if self.conf.has_bias:
                raise ValueError("a tied head owns no parameters: has_bias=False")
            return {}
        return super().init_params(key)

    def preout(self, params, x):
        # OUTPUT-HEAD override only (hidden dense layers keep their
        # policy dtype end to end): on half-precision operands the head
        # matmul stays at full MXU rate but the logits land in f32, so
        # all loss math keeps the documented always-f32 guarantee.
        # Higher-precision models (incl. the f64 gradcheck oracle) keep
        # their native matmul — forcing f32 there would DOWNcast.
        x = self._slice_replicate(x)
        W = params["W"]
        if is_quantized(params, "W"):
            # quantized head: int8/fp8 matmul operand, scale fused
            # after; logits land in f32 downstream (the generate-path
            # _head_logits / loss casts), matching the always-f32 rule
            # within the quantized numeric contract
            z = qmatmul(x, params, "W")
        elif jnp.promote_types(x.dtype, W.dtype) in (jnp.bfloat16,
                                                     jnp.float16):
            z = jnp.matmul(x, W, preferred_element_type=jnp.float32)
        else:
            z = x @ W
        scale = getattr(self.conf, "logits_scale", 1.0)
        if scale != 1.0:
            z = z * scale
        return z + params["b"].astype(z.dtype) if "b" in params else z

    @property
    def loss_function(self) -> str:
        return self.conf.loss_function

    def score(self, params, x, labels, state, train, rng=None, mask=None):
        """Mean-over-examples data loss for this output layer."""
        x = self.maybe_dropout_input(x, train, rng)
        params = self.maybe_drop_connect(params, train, rng)
        # scoped apart: the head's matmul, and the loss proper (the f32
        # log-softmax over the vocabulary)
        with jax.named_scope("lm_head"):
            z = self.preout(params, x)
        with jax.named_scope("loss"):
            if _fused_logits_pair(self.activation, self.loss_function):
                return compute_loss(self.loss_function, labels, z, mask=mask, from_logits=True)
            return compute_loss(self.loss_function, labels, activate(self.activation, z), mask=mask)


@register_impl(L.RnnOutputLayer)
class RnnOutputImpl(OutputImpl):
    """Per-timestep output over [b, t, f] inputs
    (``nn/layers/recurrent/RnnOutputLayer.java``); the label mask is
    [b, t]. The dense transform broadcasts over the time axis."""


@register_impl(L.ExitGateOutputLayer)
class ExitGateOutputImpl(RnnOutputImpl):
    """The head over the ``R`` pass outputs of a repeated span: ``score``
    takes them as a sequence (one array: one pass), ``forward`` the last
    one. Logits, the per-token cross-entropies, the gate and the exit
    distribution are float32 whatever the compute dtype."""

    #: the container hands ``score`` the span's output after every pass
    scores_every_pass = True

    def init_params(self, key):
        params = super().init_params(key)
        c = self.conf
        params["w_gate"] = init_weights(
            jax.random.fold_in(key, 1), (c.n_in, 1), self.weight_init, c.n_in,
            1, c.dist_mean, c.dist_std, dist=c.dist)
        params["b_gate"] = jnp.zeros((1,), jnp.float32)
        return params

    def cast_params(self, params, dtype):
        # the gate's bias is added to a float32 product
        return {**super().cast_params(params, dtype),
                "b_gate": params["b_gate"]}

    def _token_losses(self, params, x, ids):
        """Cross-entropy of every token [b, t] from the float32 logits."""
        with jax.named_scope("lm_head"):
            z = self.preout(params, x)
        with jax.named_scope("loss"):
            z = z.astype(jnp.float32)
            return (jax.scipy.special.logsumexp(z, axis=-1)
                    - target_value(z, ids))

    def _gate_logit(self, params, x):
        with jax.named_scope("exit_gate"):
            z = jnp.matmul(x, params["w_gate"],
                           preferred_element_type=jnp.float32)[..., 0]
            return z + params["b_gate"].astype(jnp.float32)

    def score(self, params, x, labels, state, train, rng=None, mask=None):
        if not _fused_logits_pair(self.activation, self.loss_function) \
                or labels.ndim != 2:
            raise ValueError(
                "ExitGateOutputLayer scores softmax + mcxent over sparse "
                "token ids [b, t]")
        passes = list(x) if isinstance(x, (list, tuple)) else [x]
        ids = labels.astype(jnp.int32)
        ces = jnp.stack([self._token_losses(params, h, ids) for h in passes])
        with jax.named_scope("exit_loss"):
            # log p_s = log lambda_s + sum_{j<s} log(1 - lambda_j); the last
            # pass takes what is left, so its own gate is never read
            stay = jnp.zeros(ids.shape, jnp.float32)
            log_p = []
            for h in passes[:-1]:
                g = self._gate_logit(params, h)
                log_p.append(jax.nn.log_sigmoid(g) + stay)
                stay = stay + jax.nn.log_sigmoid(-g)
            log_p = jnp.stack(log_p + [stay])
            p = jnp.exp(log_p)
            per_token = jnp.sum(p * (ces + self.conf.entropy_weight * log_p),
                                axis=0)
            if mask is None:
                return jnp.mean(per_token)
            return _masked_mean(per_token, mask)


@register_impl(L.LossLayer)
class LossImpl(LayerImpl):
    """``nn/layers/LossLayer.java`` — parameterless activation + loss."""

    def has_loss(self) -> bool:
        return True

    @property
    def loss_function(self) -> str:
        return self.conf.loss_function

    def forward(self, params, x, state, train, rng=None, mask=None):
        return activate(self.activation, x), state

    def score(self, params, x, labels, state, train, rng=None, mask=None):
        with jax.named_scope("loss"):
            if _fused_logits_pair(self.activation, self.loss_function):
                return compute_loss(self.loss_function, labels, x, mask=mask, from_logits=True)
            return compute_loss(self.loss_function, labels,
                                activate(self.activation, x), mask=mask)


@register_impl(L.EmbeddingLayer)
class EmbeddingImpl(LayerImpl):
    """``nn/layers/feedforward/embedding/EmbeddingLayer.java`` — index
    lookup. Input: int indices [b] or [b, 1]; output [b, n_out].
    jnp.take lowers to a TPU gather; bias added as in the reference."""

    cast_input = False  # ids must stay exact (see LayerImpl.cast_input)

    def init_params(self, key):
        c = self.conf
        W = init_weights(key, (c.n_in, c.n_out), self.weight_init, c.n_in, c.n_out,
                         c.dist_mean, c.dist_std, dist=c.dist)
        b = jnp.full((c.n_out,), self.bias_init, jnp.float32)
        return {"W": W, "b": b}

    def forward(self, params, x, state, train, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 2:
            idx = idx[:, 0]
        z = qtake(params, "W", idx) + params["b"]
        return activate(self.activation, z), state


@register_impl(L.ActivationLayer)
class ActivationImpl(LayerImpl):
    def forward(self, params, x, state, train, rng=None, mask=None):
        return activate(self.activation, x), state


@register_impl(L.DropoutLayer)
class DropoutImpl(LayerImpl):
    def forward(self, params, x, state, train, rng=None, mask=None):
        rate = self.dropout_rate
        if train and rate > 0.0 and rng is not None:
            x = apply_dropout(x, rate, rng)
        return x, state


@register_impl(L.GlobalPoolingLayer)
class GlobalPoolingImpl(LayerImpl):
    """Pool over time ([b,t,f] -> [b,f], honoring the feature mask) or
    space ([b,h,w,c] -> [b,c])."""

    def forward(self, params, x, state, train, rng=None, mask=None):
        pt = self.conf.pooling_type
        if x.ndim == 3:
            if mask is not None:
                m = mask[:, :, None].astype(x.dtype)
                if pt == L.PoolingType.MAX:
                    big_neg = jnp.asarray(-1e30, x.dtype)
                    return jnp.max(jnp.where(m > 0, x, big_neg), axis=1), state
                if pt == L.PoolingType.PNORM:
                    p = self.conf.pnorm
                    s = jnp.sum(jnp.power(jnp.abs(x) * m, p), axis=1)
                    return jnp.power(s, 1.0 / p), state
                s = jnp.sum(x * m, axis=1)
                if pt == L.PoolingType.SUM:
                    return s, state
                return s / jnp.maximum(jnp.sum(m, axis=1), 1.0), state
            axis = (1,)
        else:
            axis = (1, 2)
        if pt == L.PoolingType.MAX:
            return jnp.max(x, axis=axis), state
        if pt == L.PoolingType.SUM:
            return jnp.sum(x, axis=axis), state
        if pt == L.PoolingType.PNORM:
            p = self.conf.pnorm
            return jnp.power(jnp.sum(jnp.power(jnp.abs(x), p), axis=axis), 1.0 / p), state
        return jnp.mean(x, axis=axis), state


@register_impl(L.AutoEncoder)
class AutoEncoderImpl(BaseDenseImpl):
    """Denoising autoencoder (``nn/layers/feedforward/autoencoder/
    AutoEncoder.java``): encode a = act(xW+b), decode x' = act(aWᵀ+vb);
    pretrain loss is reconstruction of the *uncorrupted* input."""

    def init_params(self, key):
        p = super().init_params(key)
        p["vb"] = jnp.zeros((self.conf.n_in,), jnp.float32)  # visible bias
        return p

    def encode(self, params, x):
        return activate(self.activation, x @ params["W"] + params["b"])

    def decode(self, params, a):
        return activate(self.activation, a @ params["W"].T + params["vb"])

    def forward(self, params, x, state, train, rng=None, mask=None):
        return self.encode(params, x), state

    def pretrain_loss(self, params, x, rng):
        c = self.conf
        corrupted = x
        if c.corruption_level > 0.0 and rng is not None:
            keep = jax.random.bernoulli(rng, 1.0 - c.corruption_level, x.shape)
            corrupted = jnp.where(keep, x, 0.0)
        recon = self.decode(params, self.encode(params, corrupted))
        loss = compute_loss(c.loss_function, x, recon)
        if c.sparsity > 0.0:
            a_mean = jnp.mean(self.encode(params, x), axis=0)
            loss = loss + jnp.sum((a_mean - c.sparsity) ** 2)
        return loss


@register_impl(L.RBM)
class RBMImpl(BaseDenseImpl):
    """Restricted Boltzmann machine with CD-k pretraining
    (``nn/layers/feedforward/rbm/RBM.java``).

    TPU formulation: the positive/negative phases are batched matmuls and
    the Gibbs chain is a ``lax.scan`` of length k (static), so the whole
    CD update is one XLA program — the reference ran a host loop of ND4J
    calls per step. The CD gradient is supplied directly (not via
    jax.grad; contrastive divergence is not the gradient of a tractable
    objective).
    """

    def init_params(self, key):
        p = super().init_params(key)
        p["vb"] = jnp.zeros((self.conf.n_in,), jnp.float32)
        return p

    def _prop_up(self, params, v):
        z = v @ params["W"] + params["b"]
        return jax.nn.sigmoid(z) if self.conf.hidden_unit == L.RBMHiddenUnit.BINARY else jax.nn.relu(z)

    def _prop_down(self, params, h):
        z = h @ params["W"].T + params["vb"]
        vu = self.conf.visible_unit
        if vu == L.RBMVisibleUnit.BINARY:
            return jax.nn.sigmoid(z)
        if vu == L.RBMVisibleUnit.SOFTMAX:
            return jax.nn.softmax(z, axis=-1)
        return z  # gaussian / linear: mean-field identity

    def forward(self, params, x, state, train, rng=None, mask=None):
        x = self.maybe_dropout_input(x, train, rng)
        return activate(self.activation, x @ params["W"] + params["b"]), state

    def cd_gradients(self, params, v0, rng):
        """CD-k gradient estimate + reconstruction error, all in-step."""
        c = self.conf
        h0 = self._prop_up(params, v0)

        def gibbs(carry, key):
            h, _ = carry
            hs = jax.random.bernoulli(key, h).astype(v0.dtype) \
                if c.hidden_unit == L.RBMHiddenUnit.BINARY else h
            v = self._prop_down(params, hs)
            return (self._prop_up(params, v), v), None

        keys = jax.random.split(rng, c.k)
        (hk, vk), _ = jax.lax.scan(gibbs, (h0, v0), keys)
        n = v0.shape[0]
        gW = -(v0.T @ h0 - vk.T @ hk) / n
        gb = -jnp.mean(h0 - hk, axis=0)
        gvb = -jnp.mean(v0 - vk, axis=0)
        recon_err = compute_loss(c.loss_function, v0, jnp.clip(vk, 1e-7, 1 - 1e-7))
        return {"W": gW, "b": gb, "vb": gvb}, recon_err

    def pretrain_loss(self, params, x, rng):
        # used only for score reporting; gradients come from cd_gradients
        _, err = self.cd_gradients(params, x, rng)
        return err
