"""Decoder blocks of the hybrid state-space family: a Mamba-2 block and a
grouped-query attention block, which share their RMSNorm, their gated MLP and
their scaled residual branches, and the stand-alone RMSNorm before the head;
and the blocks of the decoder-hybrid-decoder family (``LayerNormDecoderImpl``
down: a Mamba-1 block, a differential attention block and a gated memory
unit on one LayerNorm + gated-MLP body, some of which hand values forward to
later layers), and its stand-alone LayerNorm. The RMSNorm body also carries
a gated short-convolution mixer, and its gated MLP may be a layer of routed
experts (``nn/layers/moe.py``).

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
from deeplearning4j_tpu.nn.layers.moe import (EXPERT_BIAS_KEY,
                                              EXPERT_GATE_UP_PRODUCT,
                                              init_routed_params,
                                              routed_experts)
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.attention import rotary
from deeplearning4j_tpu.ops.flash_attention import FLASH_RESIDUAL_NAMES
from deeplearning4j_tpu.ops.selective_scan import (SELSCAN_RESIDUAL_NAMES,
                                                   selective_scan)
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


def layer_norm(x, gain, bias, eps):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mean), axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) * gain + bias
    return out.astype(x.dtype)


def causal_conv(x, w):
    """A causal depthwise convolution over time of x [b, t, c] with taps
    w [d_conv, c]: tap j reads the token d_conv - 1 - j back."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    w = w.astype(x.dtype)
    return sum(padded[:, j:j + t] * w[j] for j in range(taps))


def causal_conv_silu(x, w, bias):
    """``silu`` of ``causal_conv`` plus a bias."""
    x = causal_conv(x, w)
    return jax.nn.silu(x + bias.astype(x.dtype))


def _keep_set(impl, conf, name):
    """``kept_values`` of a block's configuration in place of its class's
    keep-set, refused where it names what the class does not make."""
    if conf.kept_values is None:
        return impl.kept_names
    unknown = sorted(set(conf.kept_values) - set(impl.KEEPABLE))
    if unknown:
        raise ValueError(
            f"{type(conf).__name__} ({name}) cannot keep {unknown}: "
            f"it names {list(impl.KEEPABLE)}")
    return tuple(conf.kept_values)


@register_impl(L.RMSNormLayer)
class RMSNormImpl(LayerImpl):
    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        return {"g": jnp.ones((self.conf.n_out,), jnp.float32)}

    def forward(self, params, x, state, train, rng=None, mask=None):
        with jax.named_scope("final_norm"):
            return rms_norm(x, params["g"], self.conf.eps), state


class GatedDecoderImpl(LayerImpl):
    """The shared body; a subclass gives ``_mixer_params`` and ``_mixer``.
    The feed-forward is the gated MLP, or the routed experts where the
    configuration has ``num_experts``."""

    #: the container may recompute this layer's forward in the backward pass
    recomputable = True
    #: and then keeps the gated MLP's wide product, the dearest value of the
    #: body to make again: 4 * ffn_hidden bytes a token in bfloat16 beside
    #: the 2 * d_model of the block's input
    kept_names = (GATE_UP_PRODUCT,)
    #: what ``kept_values`` of the configuration may name in its place (the
    #: routed experts' gate/up product where the block has experts)
    KEEPABLE = (GATE_UP_PRODUCT, EXPERT_GATE_UP_PRODUCT)
    #: leaves kept in float32 whatever the compute dtype
    FLOAT32_LEAVES = ()

    def __init__(self, global_conf, conf, name):
        super().__init__(global_conf, conf, name)
        self.kept_names = _keep_set(self, conf, name)

    def cast_params(self, params, dtype):
        cast = cast_floats(params, dtype)
        # and the routed experts' router: its product is float32
        keep = self.FLOAT32_LEAVES + tuple(k for k in ("W_router",)
                                           if k in params)
        cast.update({k: params[k] for k in keep})
        return cast

    def init_state(self):
        c = self.conf
        if c.num_experts and c.expert_bias:
            # the selection bias: set by whoever balances the experts' loads,
            # never by a gradient
            return {EXPERT_BIAS_KEY: jnp.zeros((c.num_experts,), jnp.float32)}
        return {}

    def _matrix(self, key, shape):
        c = self.conf
        return init_weights(key, shape, self.weight_init, shape[0], shape[1],
                            c.dist_mean, c.dist_std, dist=c.dist)

    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        c = self.conf
        if c.n_out != c.n_in:
            raise ValueError(f"{type(c).__name__} needs n_in == n_out (d_model)")
        d, f = c.n_out, c.ffn_hidden
        k_mix, k_up, k_down = jax.random.split(key, 3)
        params = self._mixer_params(k_mix)
        params.update({"rms1_g": jnp.ones((d,), jnp.float32),
                       "rms2_g": jnp.ones((d,), jnp.float32)})
        if c.num_experts:
            if c.expert_hidden <= 0 or not 0 < c.experts_per_token <= c.num_experts:
                raise ValueError(f"{type(c).__name__} with experts needs "
                                 "expert_hidden > 0 and 0 < experts_per_token"
                                 " <= num_experts")
            params.update(init_routed_params(k_up, c, self._matrix))
        elif f <= 0:
            raise ValueError(f"{type(c).__name__} needs ffn_hidden > 0")
        else:
            params.update({"W_gate_up": self._matrix(k_up, (d, 2 * f)),
                           "W_down": self._matrix(k_down, (f, d))})
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
        if c.num_experts:
            h = routed_experts(params, h, state, c)
        else:
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
            xbc = causal_conv_silu(xbc, params["conv_w"], params["conv_b"])
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
        params = {"Wq": self._matrix(ks[0], (d, d)),
                  "Wk": self._matrix(ks[1], (d, kv)),
                  "Wv": self._matrix(ks[2], (d, kv)),
                  "Wo": self._matrix(ks[3], (d, d))}
        if c.qk_norm:
            hd = d // c.num_heads
            params.update({"q_norm_g": jnp.ones((hd,), jnp.float32),
                           "k_norm_g": jnp.ones((hd,), jnp.float32)})
        return params

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
            if c.qk_norm:
                with jax.named_scope("qk_norm"):
                    q = rms_norm(q, params["q_norm_g"], c.rms_eps)
                    k = rms_norm(k.reshape(b, t, kv, hd), params["k_norm_g"],
                                 c.rms_eps).reshape(b, t, kv * hd)
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


@register_impl(L.ShortConvBlock)
class ShortConvBlockImpl(GatedDecoderImpl):
    """The gated short convolution: ``[B, C, x] = h W_in``, ``u = B * x``, a
    causal depthwise convolution of u (no bias, no activation), ``(C *
    conv(u)) W_out``."""

    def _mixer_params(self, key):
        c = self.conf
        d = c.n_out
        k_in, k_conv, k_out = jax.random.split(key, 3)
        bound = 1.0 / math.sqrt(c.conv_kernel)
        return {"W_in": self._matrix(k_in, (d, 3 * d)),
                "conv_w": jax.random.uniform(k_conv, (c.conv_kernel, d),
                                             jnp.float32, -bound, bound),
                "W_out": self._matrix(k_out, (d, d))}

    def _mixer(self, params, h, mask):
        with jax.named_scope("conv_in_proj"):
            gate_b, gate_c, x = jnp.split(h @ params["W_in"], 3, axis=-1)
        with jax.named_scope("short_conv"):
            y = gate_c * causal_conv(gate_b * x, params["conv_w"])
        with jax.named_scope("conv_out_proj"):
            return y @ params["W_out"]


# ------------------------------------ the decoder-hybrid-decoder family

@register_impl(L.LayerNormLayer)
class LayerNormImpl(LayerImpl):
    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        n = self.conf.n_out
        return {"g": jnp.ones((n,), jnp.float32),
                "b": jnp.zeros((n,), jnp.float32)}

    def forward(self, params, x, state, train, rng=None, mask=None):
        with jax.named_scope("final_norm"):
            return layer_norm(x, params["g"], params["b"],
                              self.conf.eps), state


class LayerNormDecoderImpl(LayerImpl):
    """The shared body: ``x + mixer(LN(x))`` then ``x + mlp(LN(x))``; a
    subclass gives ``_mixer_params`` and ``_mixer``, which also takes the
    values the block reads and returns the values it provides."""

    recomputable = True
    kept_names = (GATE_UP_PRODUCT,)
    KEEPABLE = (GATE_UP_PRODUCT,)
    #: the values a block of the class can hand forward, and those it needs
    PROVIDABLE = ()
    #: leaves kept in float32 whatever the compute dtype
    FLOAT32_LEAVES = ()

    def __init__(self, global_conf, conf, name):
        super().__init__(global_conf, conf, name)
        self.kept_names = _keep_set(self, conf, name)
        unknown = sorted(set(conf.provides) - set(self.PROVIDABLE))
        if unknown:
            raise ValueError(
                f"{type(conf).__name__} ({name}) cannot provide {unknown}: "
                f"it makes {list(self.PROVIDABLE)}")
        #: the names of the values it reads
        self.read_names = sorted(name for _, name in L.parse_reads(conf))
        if self.read_names != sorted(self._needs()):
            raise ValueError(
                f"{type(conf).__name__} ({name}) reads {sorted(self._needs())}"
                f", each as '<layer>.<name>'; it was given {list(conf.reads)}")

    def _needs(self):
        return ()

    def cast_params(self, params, dtype):
        cast = cast_floats(params, dtype)
        cast.update({k: params[k] for k in self.FLOAT32_LEAVES})
        return cast

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
        k_mix, k_fc1, k_fc2 = jax.random.split(key, 3)
        ones, zeros = jnp.ones((d,), jnp.float32), jnp.zeros((d,), jnp.float32)
        params = self._mixer_params(k_mix)
        params.update({"ln1_g": ones, "ln1_b": zeros, "ln2_g": ones,
                       "ln2_b": zeros, "W_fc1": self._matrix(k_fc1, (d, 2 * f)),
                       "W_fc2": self._matrix(k_fc2, (f, d))})
        return params

    def forward_with(self, params, x, state, train, rng=None, mask=None,
                     read=None):
        """``forward`` for the container's seam: ``read`` maps the names the
        block reads to their values; also returns the values it provides."""
        c = self.conf
        if x.ndim != 3:
            raise ValueError(f"{type(c).__name__} needs [b, t, d], got {x.shape}")
        with jax.named_scope("ln1"):
            h = layer_norm(x, params["ln1_g"], params["ln1_b"], c.ln_eps)
        h, provided = self._mixer(params, h, mask, read or {})
        x = x + h.astype(x.dtype)
        with jax.named_scope("ln2"):
            h = layer_norm(x, params["ln2_g"], params["ln2_b"], c.ln_eps)
        with jax.named_scope("mlp_fc"):
            a, g = jnp.split(checkpoint_name(h @ params["W_fc1"],
                                             GATE_UP_PRODUCT), 2, axis=-1)
            h = a * jax.nn.silu(g)
        with jax.named_scope("mlp_proj"):
            h = h @ params["W_fc2"]
        out = x + h.astype(x.dtype)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, state, {k: provided[k] for k in c.provides}

    def forward(self, params, x, state, train, rng=None, mask=None):
        if self.read_names:
            raise ValueError(
                f"{type(self.conf).__name__} ({self.name}) reads "
                f"{self.read_names}: it runs inside a "
                "MultiLayerNetwork, which hands them to it")
        return self.forward_with(params, x, state, train, rng, mask)[:2]

    init_cache = prefill = prefill_paged = decode_step = \
        GatedDecoderImpl._training_only


@register_impl(L.Mamba1Block)
class Mamba1BlockImpl(LayerNormDecoderImpl):
    kept_names = LayerNormDecoderImpl.kept_names + SELSCAN_RESIDUAL_NAMES
    KEEPABLE = kept_names
    PROVIDABLE = ("memory",)
    FLOAT32_LEAVES = ("A_log", "dt_bias", "D")

    def _mixer_params(self, key):
        c = self.conf
        d, di, n, r = c.n_out, c.d_inner, c.d_state, c.dt_rank
        k_in, k_conv, k_bias, k_x, k_dt, k_step, k_out = jax.random.split(key, 7)
        bound = 1.0 / math.sqrt(c.d_conv)
        step = jnp.exp(jax.random.uniform(
            k_step, (di,), jnp.float32, math.log(1e-3), math.log(1e-1)))
        return {
            "W_in": self._matrix(k_in, (d, 2 * di)),
            "conv_w": jax.random.uniform(k_conv, (c.d_conv, di), jnp.float32,
                                         -bound, bound),
            "conv_b": jax.random.uniform(k_bias, (di,), jnp.float32,
                                         -bound, bound),
            "W_x": self._matrix(k_x, (di, r + 2 * n)),
            "W_dt": jax.random.uniform(k_dt, (r, di), jnp.float32,
                                       -r ** -0.5, r ** -0.5),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            # S4D-real, Mamba-1's own: state s of every channel decays at s + 1
            "A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)), (di, n)),
            "D": jnp.ones((di,), jnp.float32),
            "W_out": self._matrix(k_out, (di, d)),
        }

    def _mixer(self, params, h, mask, read):
        c = self.conf
        f32 = jnp.float32
        with jax.named_scope("mamba_in_proj"):
            xs, z = jnp.split(h @ params["W_in"], 2, axis=-1)
        with jax.named_scope("mamba_conv"):
            xs = causal_conv_silu(xs, params["conv_w"], params["conv_b"])
        with jax.named_scope("mamba_x_proj"):
            dt, B, C = jnp.split(xs @ params["W_x"],
                                 [c.dt_rank, c.dt_rank + c.d_state], axis=-1)
        with jax.named_scope("mamba_dt"):
            step = jax.nn.softplus((dt @ params["W_dt"]).astype(f32)
                                   + params["dt_bias"].astype(f32))
        with jax.named_scope("selective_scan"):
            y = selective_scan(xs, step, -jnp.exp(params["A_log"].astype(f32)),
                               B, C, params["D"])
        with jax.named_scope("mamba_gate"):
            gated = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(h.dtype)
        with jax.named_scope("mamba_out_proj"):
            return gated @ params["W_out"], {"memory": y}


@register_impl(L.DiffAttentionBlock)
class DiffAttentionBlockImpl(LayerNormDecoderImpl):
    kept_names = LayerNormDecoderImpl.kept_names + FLASH_RESIDUAL_NAMES
    KEEPABLE = kept_names
    PROVIDABLE = ("kv",)
    FLOAT32_LEAVES = ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
    #: the RMSNorm over a pair's two value heads
    SUBLN_EPS = 1e-5

    def __init__(self, global_conf, conf, name):
        super().__init__(global_conf, conf, name)
        c = conf
        if c.num_heads % 2 or c.num_kv_heads % 2 or c.n_out % c.num_heads \
                or c.num_heads % c.num_kv_heads:
            raise ValueError(
                f"{type(c).__name__} ({name}): heads come in pairs and "
                f"key/value heads divide them; got {c.num_heads} over "
                f"{c.num_kv_heads} at d_model {c.n_out}")
        if c.cross and (c.provides or c.window is not None):
            raise ValueError(f"{type(c).__name__} ({name}): a cross layer "
                             "reads keys and values; it has no window and "
                             "provides none")
        self.lambda_init = 0.8 - 0.6 * math.exp(-0.3 * c.layer_index)

    def _needs(self):
        return ("kv",) if self.conf.cross else ()

    def _mixer_params(self, key):
        c = self.conf
        d, hd = c.n_out, c.n_out // c.num_heads
        k_qkv, k_o, *k_lam = jax.random.split(key, 6)
        wide = d if c.cross else d + 2 * c.num_kv_heads * hd
        params = {"Wq" if c.cross else "Wqkv": self._matrix(k_qkv, (d, wide)),
                  "bq" if c.cross else "bqkv": jnp.zeros((wide,), jnp.float32),
                  "Wo": self._matrix(k_o, (d, d)),
                  "bo": jnp.zeros((d,), jnp.float32),
                  "subln_g": jnp.ones((2 * hd,), jnp.float32)}
        for name, k in zip(self.FLOAT32_LEAVES, k_lam):
            params[name] = 0.1 * jax.random.normal(k, (hd,), jnp.float32)
        return params

    def _mixer(self, params, h, mask, read):
        c = self.conf
        b, t, d = h.shape
        heads, kv = c.num_heads, c.num_kv_heads
        hd, f32 = d // heads, jnp.float32
        with jax.named_scope("qkv_proj"):
            if c.cross:
                q = h @ params["Wq"] + params["bq"]
                k, v = read["kv"]
            else:
                q, k, v = jnp.split(h @ params["Wqkv"] + params["bqkv"],
                                    [d, d + kv * hd], axis=-1)
        with jax.named_scope("attention"):
            # A pair's two softmax maps, each applied to the pair's two value
            # heads side by side, are two heads of width 2 hd: q_j and k_j
            # padded with zeros (the scores do not change), v the two halves
            # whole. The kernels scale by 1/sqrt(2 hd): sqrt(2) goes into q.
            # One call of ``heads`` heads in place of four of ``heads / 2``
            pad = lambda z: jnp.concatenate([z, jnp.zeros_like(z)], axis=-1)
            q = pad((q.astype(f32) * math.sqrt(2.0)).astype(q.dtype)
                    .reshape(b, t, heads, hd))
            # query pair i reads key/value pair i // (heads / kv): head
            # (i, j) its key head j and both of its value heads
            ks = jnp.repeat(pad(k.reshape(b, t, kv // 2, 2, hd)), heads // kv,
                            axis=2).reshape(b, t, heads, 2 * hd)
            vs = jnp.repeat(v.reshape(b, t, kv // 2, 2 * hd),
                            2 * heads // kv, axis=2)
            o = dispatch_attention(q, ks, vs, causal=True, mask=mask,
                                   mesh=self._mesh, window=c.window)
            with jax.named_scope("diff_combine"):
                lam = jnp.exp(jnp.sum(params["lambda_q1"] * params["lambda_k1"])) \
                    - jnp.exp(jnp.sum(params["lambda_q2"] * params["lambda_k2"])) \
                    + self.lambda_init
                o = o.reshape(b, t, heads // 2, 2, 2 * hd).astype(f32)
                o = o[:, :, :, 0] - lam.astype(f32) * o[:, :, :, 1]
                o = (rms_norm(o, params["subln_g"], self.SUBLN_EPS)
                     * (1.0 - self.lambda_init)).astype(h.dtype)
        with jax.named_scope("attn_out_proj"):
            out = o.reshape(b, t, d) @ params["Wo"] + params["bo"]
        return out, {"kv": (k, v)}


@register_impl(L.GMUBlock)
class GMUBlockImpl(LayerNormDecoderImpl):
    def _needs(self):
        return ("memory",)

    def _mixer_params(self, key):
        c = self.conf
        k_1, k_2 = jax.random.split(key)
        return {"W_1": self._matrix(k_1, (c.n_out, c.d_inner)),
                "W_2": self._matrix(k_2, (c.d_inner, c.n_out))}

    def _mixer(self, params, h, mask, read):
        f32 = jnp.float32
        with jax.named_scope("gmu_in_proj"):
            g = h @ params["W_1"]
        with jax.named_scope("gmu_gate"):
            g = (jax.nn.silu(g.astype(f32))
                 * read["memory"].astype(f32)).astype(h.dtype)
        with jax.named_scope("gmu_out_proj"):
            return g @ params["W_2"], {}
