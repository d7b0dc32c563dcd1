"""Decoder blocks of the hybrid state-space family: a Mamba-2 block and a
grouped-query attention block, which share their RMSNorm, their gated MLP and
their scaled residual branches, and the stand-alone RMSNorm before the head.

No reference counterpart. Both blocks run ``x + m * mixer(norm(x))`` then
``x + m * mlp(norm(x))``; the mixers differ. With ``branch_norms`` each
branch's output takes a norm of its own before the add (the sandwich block),
and with ``rope_theta`` the attention block turns q and k by rotary
positions. Norms, the gate, the rotation and every decay
run in float32 under a bfloat16 compute policy, as LayerNorm does in
``transformer.py``. Training only: ``init_cache`` / ``prefill`` /
``decode_step`` raise :class:`TrainingOnlyError`; a cache that holds a
state-space layer's state is ROADMAP Reach A.8.
"""

from __future__ import annotations

import math
from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.attention import dispatch_attention
from deeplearning4j_tpu.nn.layers.base import LayerImpl, register_impl
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.attention import rotary
from deeplearning4j_tpu.ops.flash_attention import FLASH_RESIDUAL_NAMES
from deeplearning4j_tpu.ops.ssd import ssd_scan
from deeplearning4j_tpu.util.dtypes import cast_floats


#: the ``checkpoint_name`` of the gated MLP's wide product ``h @ W_gate_up``
GATE_UP_PRODUCT = "mlp_gate_up_product"


class TrainingOnlyError(NotImplementedError):
    """A serving entry point of a layer that can only be trained."""


def rms_norm(x, gain, eps):
    xf = x.astype(jnp.float32)
    out = xf * jax.lax.rsqrt(jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
                             + eps) * gain
    return out.astype(x.dtype)


@register_impl(L.RMSNormLayer)
class RMSNormImpl(LayerImpl):
    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        return {"g": jnp.ones((self.conf.n_out,), jnp.float32)}

    def forward(self, params, x, state, train, rng=None, mask=None):
        with jax.named_scope("final_norm"):
            return rms_norm(x, params["g"], self.conf.eps), state


class GatedDecoderImpl(LayerImpl):
    """The shared body; a subclass gives ``_mixer_params`` and ``_mixer``."""

    #: the container may recompute this layer's forward in the backward pass
    recomputable = True
    #: and then keeps the gated MLP's wide product, the dearest value of the
    #: body to make again: 4 * ffn_hidden bytes a token in bfloat16 beside
    #: the 2 * d_model of the block's input
    kept_names = (GATE_UP_PRODUCT,)
    #: what ``kept_values`` of the configuration may name in its place
    KEEPABLE = (GATE_UP_PRODUCT,)

    def __init__(self, global_conf, conf, name):
        super().__init__(global_conf, conf, name)
        if conf.kept_values is not None:
            unknown = sorted(set(conf.kept_values) - set(self.KEEPABLE))
            if unknown:
                raise ValueError(
                    f"{type(conf).__name__} ({name}) cannot keep {unknown}: "
                    f"it names {list(self.KEEPABLE)}")
            self.kept_names = tuple(conf.kept_values)

    def _matrix(self, key, shape):
        c = self.conf
        return init_weights(key, shape, self.weight_init, shape[0], shape[1],
                            c.dist_mean, c.dist_std, dist=c.dist)

    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        c = self.conf
        if c.n_out != c.n_in:
            raise ValueError(f"{type(c).__name__} needs n_in == n_out (d_model)")
        if c.ffn_hidden <= 0:
            raise ValueError(f"{type(c).__name__} needs ffn_hidden > 0")
        d, f = c.n_out, c.ffn_hidden
        k_mix, k_up, k_down = jax.random.split(key, 3)
        params = self._mixer_params(k_mix)
        params.update({
            "rms1_g": jnp.ones((d,), jnp.float32),
            "rms2_g": jnp.ones((d,), jnp.float32),
            "W_gate_up": self._matrix(k_up, (d, 2 * f)),
            "W_down": self._matrix(k_down, (f, d)),
        })
        if c.branch_norms:
            params.update({"mixer_norm_g": jnp.ones((d,), jnp.float32),
                           "mlp_norm_g": jnp.ones((d,), jnp.float32)})
        return params

    def forward(self, params, x, state, train, rng=None, mask=None):
        c = self.conf
        if x.ndim != 3:
            raise ValueError(f"{type(c).__name__} needs [b, t, d], got {x.shape}")
        m = c.residual_multiplier
        with jax.named_scope("rms1"):
            h = rms_norm(x, params["rms1_g"], c.rms_eps)
        h = self._mixer(params, h, mask)
        if c.branch_norms:
            with jax.named_scope("mixer_out_norm"):
                h = rms_norm(h, params["mixer_norm_g"], c.rms_eps)
        x = x + (m * h).astype(x.dtype)
        with jax.named_scope("rms2"):
            h = rms_norm(x, params["rms2_g"], c.rms_eps)
        with jax.named_scope("mlp_gate_up"):
            a, b = jnp.split(checkpoint_name(h @ params["W_gate_up"],
                                             GATE_UP_PRODUCT), 2, axis=-1)
            h = jax.nn.silu(a) * b
        with jax.named_scope("mlp_down"):
            h = h @ params["W_down"]
        if c.branch_norms:
            with jax.named_scope("mlp_out_norm"):
                h = rms_norm(h, params["mlp_norm_g"], c.rms_eps)
        out = x + (m * h).astype(x.dtype)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, state

    # ------------------------------------------------ serving: not here
    def _training_only(self, *a, **kw):
        raise TrainingOnlyError(
            f"{type(self.conf).__name__} ({self.name}) can only be trained: "
            "it has no cache to prefill or decode from")

    init_cache = prefill = prefill_paged = decode_step = _training_only


@register_impl(L.Mamba2Block)
class Mamba2BlockImpl(GatedDecoderImpl):
    #: leaves the scan reads in float32 whatever the compute dtype: a decay
    #: rounded to bfloat16 is another model
    FLOAT32_LEAVES = ("A_log", "dt_bias", "D")

    def cast_params(self, params, dtype):
        cast = cast_floats(params, dtype)
        cast.update({k: params[k] for k in self.FLOAT32_LEAVES})
        return cast

    def _widths(self):
        c = self.conf
        inner = c.n_heads * c.d_head
        return inner, inner + 2 * c.n_groups * c.d_state  # and the conv's

    def _mixer_params(self, key):
        c = self.conf
        if c.n_heads % c.n_groups:
            raise ValueError("n_heads must be a multiple of n_groups")
        d, h = c.n_out, c.n_heads
        inner, conv = self._widths()
        k_in, k_conv, k_bias, k_dt, k_a, k_out = jax.random.split(key, 6)
        bound = 1.0 / math.sqrt(c.d_conv)
        # a step between 1e-3 and 1e-1, log-uniform, through the inverse of
        # the softplus that the forward applies (Mamba's own initialisation)
        step = jnp.exp(jax.random.uniform(
            k_dt, (h,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "W_in": self._matrix(k_in, (d, inner + conv + h)),
            "conv_w": jax.random.uniform(k_conv, (c.d_conv, conv), jnp.float32,
                                         -bound, bound),
            "conv_b": jax.random.uniform(k_bias, (conv,), jnp.float32,
                                         -bound, bound),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(k_a, (h,), jnp.float32, 1., 16.)),
            "D": jnp.ones((h,), jnp.float32),
            "norm_g": jnp.ones((inner,), jnp.float32),
            "W_out": self._matrix(k_out, (inner, d)),
        }

    def _mixer(self, params, h, mask):
        c = self.conf
        b, t, _ = h.shape
        inner, conv = self._widths()
        gn = c.n_groups * c.d_state
        with jax.named_scope("mamba_in_proj"):
            z, xbc, dt = jnp.split(h @ params["W_in"], [inner, inner + conv],
                                   axis=-1)
        with jax.named_scope("mamba_conv"):
            # causal and depthwise: tap j reads the token d_conv - 1 - j back
            padded = jnp.pad(xbc, ((0, 0), (c.d_conv - 1, 0), (0, 0)))
            w = params["conv_w"].astype(xbc.dtype)
            xbc = sum(padded[:, j:j + t] * w[j] for j in range(c.d_conv))
            xbc = jax.nn.silu(xbc + params["conv_b"].astype(xbc.dtype))
        with jax.named_scope("ssd_scan"):
            x, B, C = jnp.split(xbc, [inner, inner + gn], axis=-1)
            f32 = jnp.float32
            step = jax.nn.softplus(dt.astype(f32) + params["dt_bias"].astype(f32))
            y = ssd_scan(x.reshape(b, t, c.n_heads, c.d_head), step,
                         -jnp.exp(params["A_log"].astype(f32)),
                         B.reshape(b, t, c.n_groups, c.d_state),
                         C.reshape(b, t, c.n_groups, c.d_state),
                         params["D"], c.chunk_size)
        with jax.named_scope("mamba_gate_norm"):
            # the gate first, then the norm over the whole inner width
            y = y.reshape(b, t, inner).astype(f32) * jax.nn.silu(z.astype(f32))
            y = rms_norm(y, params["norm_g"], c.rms_eps).astype(h.dtype)
        with jax.named_scope("mamba_out_proj"):
            return y @ params["W_out"]


@register_impl(L.GroupedQueryBlock)
class GroupedQueryBlockImpl(GatedDecoderImpl):
    #: and what the flash kernels' backward reads of their forward: the
    #: output (2 * d_model bytes a token) and the lse (4 bytes a head)
    kept_names = GatedDecoderImpl.kept_names + FLASH_RESIDUAL_NAMES
    KEEPABLE = GatedDecoderImpl.KEEPABLE + FLASH_RESIDUAL_NAMES

    def _mixer_params(self, key):
        c = self.conf
        if c.n_out % c.num_heads or c.num_heads % c.num_kv_heads:
            raise ValueError(
                f"d_model {c.n_out}, {c.num_heads} heads and "
                f"{c.num_kv_heads} key/value heads do not divide")
        d = c.n_out
        kv = c.num_kv_heads * (d // c.num_heads)
        ks = jax.random.split(key, 4)
        return {"Wq": self._matrix(ks[0], (d, d)),
                "Wk": self._matrix(ks[1], (d, kv)),
                "Wv": self._matrix(ks[2], (d, kv)),
                "Wo": self._matrix(ks[3], (d, d))}

    def _mixer(self, params, h, mask):
        c = self.conf
        b, t, d = h.shape
        heads, kv = c.num_heads, c.num_kv_heads
        hd = d // heads
        with jax.named_scope("qkv_proj"):
            q, k, v = h @ params["Wq"], h @ params["Wk"], h @ params["Wv"]
        with jax.named_scope("attention"):
            # the kernels scale scores by 1/sqrt(hd): fold the rest into q
            mult = c.attention_multiplier
            q = q.reshape(b, t, heads, hd)
            if mult is not None:
                q = (q * (mult * math.sqrt(hd))).astype(q.dtype)
            if c.rope_theta is not None:
                with jax.named_scope("rope"):
                    q = rotary(q, c.rope_theta)
                    k = rotary(k.reshape(b, t, kv, hd),
                               c.rope_theta).reshape(b, t, kv * hd)
            with jax.named_scope("kv_repeat"):
                # query head i reads key/value head i // (heads / kv)
                rep = lambda z: jnp.repeat(z.reshape(b, t, kv, hd),
                                           heads // kv, axis=2)
                k, v = rep(k), rep(v)
            o = dispatch_attention(q, k, v, causal=True, mask=mask,
                                   mesh=self._mesh)
        with jax.named_scope("attn_out_proj"):
            return o.reshape(b, t, d) @ params["Wo"]
