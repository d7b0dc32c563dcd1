"""Plain reference for the hybrid state-space configurations
(``granitemoehybrid`` with no routed experts): weights from a seed, forward
pass, loss, gradients and Adam, in float32 ``jax.numpy`` with every matrix
product at ``highest`` precision. No kernels, nothing imported from the
program and nothing taken from it.

The equations are the published model's (the configuration's ``source`` and
the family's public implementation):

    x = E[ids] * embedding_multiplier
    each layer:  x = x + residual_multiplier * mixer(RMSNorm(x))
                 x = x + residual_multiplier * MLP(RMSNorm(x))
    MLP(h) = (silu(a) * b) W_down,  [a, b] = h W_gate_up,  no biases
    logits = RMSNorm(x) E^T / logits_scaling        (the head is the embedding)

``attention`` mixer: ``num_attention_heads`` query heads over
``num_key_value_heads`` key/value heads, no bias, no positions, causal, the
scores scaled by ``attention_multiplier``; the full masked softmax, a block of
query rows at a time. ``mamba`` mixer: ``[z, xBC, dt] = u W_in``; ``xBC =
silu(conv(xBC))``, a causal depthwise convolution with bias; ``[x, B, C] =
xBC``; ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; per head the
TOKEN-BY-TOKEN recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T``,
``y_t = S_t C_t + D x_t`` (a ``lax.scan`` over tokens inside one over blocks
of ``SCAN_BLOCK`` tokens, the outer body rematerialised so that the backward
pass fits: the program evaluates the chunked form, the reference must not);
``y = RMSNorm(y * silu(z))`` over the whole inner width; ``out = y W_out``.

Departures from the published model (also under ``departs`` in the
configuration's file): Adam without weight decay at a constant learning rate,
no gradient clipping, seeded random weights.

Each layer takes the rows of a step one at a time (``lax.map`` over rows, the
body rematerialised) and its MLP a block of tokens at a time, so that beside
the float32 parameters, their gradient and Adam's two moments (12.4 GB at
the published widths) only one row's activations of one layer are alive
(2.6 GB by the compiler's count for two rows of 4096).

``precision="fp8"`` / ``"fp8_forward"`` are the CONTROLS of
``gpt_plain.py``, never the reference: every matrix product's operands (and
the scan's ``x``, ``B``, ``C``) in float8. ``carry_state=False`` plants the
fault of the mechanism: every chunk of ``mamba_chunk_size`` tokens starts from
a zero state.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt_plain import (CONTROLS, HIGHEST, _adam, _fp8,
                                            _product, make_tokens, seed_key)

__all__ = ["CONTROLS", "make_tokens", "seed_key", "init_params", "loss_sum",
           "follow", "num_params", "cfg_key", "leaf_shapes"]

MAMBA_LEAVES = ("rms1_g", "W_in", "conv_w", "conv_b", "dt_bias", "A_log", "D",
                "norm_g", "W_out", "rms2_g", "W_gate_up", "W_down")
ATTENTION_LEAVES = ("rms1_g", "Wq", "Wk", "Wv", "Wo", "rms2_g", "W_gate_up",
                    "W_down")
LEAVES = {"mamba": MAMBA_LEAVES, "attention": ATTENTION_LEAVES}
#: query rows that attention takes at a time
ATTENTION_ROWS = 512
#: rows that the gated MLP takes at a time
MLP_ROWS = 1024
#: tokens of the recurrence whose states the backward pass keeps at a time
SCAN_BLOCK = 64

_NUMBERS = ("vocab_size", "hidden_size", "shared_intermediate_size",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_d_conv", "mamba_n_groups",
            "mamba_chunk_size", "rms_norm_eps", "embedding_multiplier",
            "residual_multiplier", "attention_multiplier", "logits_scaling",
            "initializer_range")


def cfg_key(cfg: Dict[str, Any]) -> Tuple:
    """The numbers of a configuration that the equations read, hashable."""
    return tuple((k, cfg[k]) for k in _NUMBERS) + (
        ("layer_types", tuple(cfg["layer_types"])),)


def _sizes(cfg):
    d, h = cfg["hidden_size"], cfg["mamba_n_heads"]
    inner = h * cfg["mamba_d_head"]
    conv = inner + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    kv = cfg["num_key_value_heads"] * (d // cfg["num_attention_heads"])
    return d, cfg["shared_intermediate_size"], h, inner, conv, kv


def layer_shapes(cfg: Dict[str, Any], kind: str) -> Dict[str, Tuple]:
    d, f, h, inner, conv, kv = _sizes(cfg)
    shared = {"rms1_g": (d,), "rms2_g": (d,), "W_gate_up": (d, 2 * f),
              "W_down": (f, d)}
    if kind == "mamba":
        return {"W_in": (d, inner + conv + h),
                "conv_w": (cfg["mamba_d_conv"], conv), "conv_b": (conv,),
                "dt_bias": (h,), "A_log": (h,), "D": (h,), "norm_g": (inner,),
                "W_out": (inner, d), **shared}
    return {"Wq": (d, d), "Wk": (d, kv), "Wv": (d, kv), "Wo": (d, d), **shared}


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["hidden_size"]
    return {"embed": (cfg["vocab_size"], d), "final_g": (d,),
            "layers": [layer_shapes(cfg, k) for k in cfg["layer_types"]]}


def num_params(cfg: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_params(cfg: Dict[str, Any], seed) -> Dict[str, Any]:
    """The initialisation under ``assumed`` in the configuration's file:
    N(0, initializer_range) matrices and embedding, unit gains, the
    convolution and its bias uniform in +-1/sqrt(d_conv), ``A_log`` the log of
    a uniform(1, 16), ``dt_bias`` the inverse softplus of a log-uniform(1e-3,
    1e-1) step, ``D`` = 1. Traceable: ``seed`` may be a key."""
    key = seed if isinstance(seed, jax.Array) else seed_key(seed)
    std = float(cfg["initializer_range"])
    bound = 1.0 / math.sqrt(cfg["mamba_d_conv"])
    f32 = jnp.float32

    def leaf(k, name, shape):
        if name.endswith("_g"):
            return jnp.ones(shape, f32)
        if name in ("conv_w", "conv_b"):
            return jax.random.uniform(k, shape, f32, -bound, bound)
        if name == "D":
            return jnp.ones(shape, f32)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, f32, 1.0, 16.0))
        if name == "dt_bias":
            step = jnp.exp(jax.random.uniform(k, shape, f32, math.log(1e-3),
                                              math.log(1e-1)))
            return step + jnp.log(-jnp.expm1(-step))
        return std * jax.random.normal(k, shape, f32)

    shapes = leaf_shapes(cfg)
    k_embed, *k_layers = jax.random.split(key, 1 + len(shapes["layers"]))
    layers = []
    for k, shp in zip(k_layers, shapes["layers"]):
        ks = jax.random.split(k, len(shp))
        layers.append({n: leaf(kk, n, s)
                       for kk, (n, s) in zip(ks, sorted(shp.items()))})
    return {"embed": leaf(k_embed, "embed", shapes["embed"]),
            "final_g": jnp.ones(shapes["final_g"], f32), "layers": layers}


# ------------------------------------------------------------------ forward

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def _operand(precision: str):
    """An operand of the scan in the stated precision: what ``_product``
    does to a matrix product's operands, for products that are written out
    token by token."""
    if precision == "float32":
        return lambda x: x
    q5 = (lambda g: _fp8(g, jnp.float8_e5m2)) if precision == "fp8" else (lambda g: g)

    @jax.custom_vjp
    def f(x):
        return _fp8(x, jnp.float8_e4m3fn)

    f.defvjp(lambda x: (f(x), None), lambda _, g: (q5(g),))
    return f


def _recurrence(x, dt, A, B, C, chunk: int, carry_state: bool):
    """One row, token by token. x [t, g, r, p] (``r`` heads a group), dt
    [t, g, r], A [g, r], B and C [t, g, n] -> y [t, g, r, p]. The scan over
    tokens runs inside one over blocks of ``SCAN_BLOCK`` whose body is
    rematerialised, so the backward pass keeps a state a block and a block's
    states, not a state a token."""
    t, g, r, p = x.shape
    n = B.shape[-1]
    block = math.gcd(SCAN_BLOCK, t)

    def token(S, z):
        xt, dtt, Bt, Ct, first = z
        if not carry_state:
            S = jnp.where(first, 0.0, S)  # the planted fault
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * xt)[..., None] * Bt[:, None, None, :]
        return S, jnp.sum(S * Ct[:, None, None, :], axis=-1)

    first = jnp.arange(t) % chunk == 0  # the token that opens a chunk
    cut = lambda z: z.reshape((t // block, block) + z.shape[1:])
    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, z: jax.lax.scan(token, S, z)),
        jnp.zeros((g, r, p, n), jnp.float32),
        (cut(x), cut(dt), cut(B), cut(C), cut(first)))
    return y.reshape(t, g, r, p)


def _mamba(u, p, cfg, mm, operand, carry_state):
    d, f, h, inner, conv, kv = _sizes(cfg)
    g, n, hd = cfg["mamba_n_groups"], cfg["mamba_d_state"], cfg["mamba_d_head"]
    k = cfg["mamba_d_conv"]
    t = u.shape[0]
    z, xbc, dt = jnp.split(mm(u, p["W_in"]), [inner, inner + conv], axis=-1)
    padded = jnp.pad(xbc, ((k - 1, 0), (0, 0)))
    xbc = sum(padded[j:j + t] * p["conv_w"][j] for j in range(k)) + p["conv_b"]
    x, B, C = jnp.split(jax.nn.silu(xbc), [inner, inner + g * n], axis=-1)
    x = x.reshape(t, g, h // g, hd)
    y = _recurrence(
        operand(x),
        jax.nn.softplus(dt + p["dt_bias"]).reshape(t, g, h // g),
        -jnp.exp(p["A_log"]).reshape(g, h // g),
        operand(B).reshape(t, g, n), operand(C).reshape(t, g, n),
        cfg["mamba_chunk_size"], carry_state)
    y = y + p["D"].reshape(g, h // g)[..., None] * x
    y = y.reshape(t, inner) * jax.nn.silu(z)
    return mm(_rms(y, p["norm_g"], cfg["rms_norm_eps"]), p["W_out"])


def _attention(u, p, cfg, mm, precision):
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, t = d // heads, u.shape[0]
    qk = _product(lambda q, k: jnp.einsum("qhd,khd->hqk", q, k,
                                          precision=HIGHEST), precision)
    pv = _product(lambda w, v: jnp.einsum("hqk,khd->qhd", w, v,
                                          precision=HIGHEST), precision)
    q = mm(u, p["Wq"]).reshape(t, heads, hd)
    # query head i reads key/value head i // (heads / kv)
    rep = lambda m: jnp.repeat(m.reshape(t, kv, hd), heads // kv, axis=1)
    k, v = rep(mm(u, p["Wk"])), rep(mm(u, p["Wv"]))
    rows = min(ATTENTION_ROWS, t)
    if t % rows:
        raise ValueError("the reference takes whole blocks of query rows")

    def block(args):
        qb, first = args
        s = qk(qb, k) * cfg["attention_multiplier"]
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        return pv(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)

    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(t // rows, rows, heads, hd),
                     jnp.arange(0, t, rows)))
    return mm(o.reshape(t, d), p["Wo"])


def _layer(kind: str, cfg, precision: str, carry_state: bool):
    """``(x [t, d], leaves) -> x`` of one layer on one row."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    operand = _operand(precision)
    eps, m = cfg["rms_norm_eps"], cfg["residual_multiplier"]

    def mlp(x, p):
        a, b = jnp.split(mm(_rms(x, p["rms2_g"], eps), p["W_gate_up"]), 2, -1)
        return x + m * mm(jax.nn.silu(a) * b, p["W_down"])

    def run(x, p):
        u = _rms(x, p["rms1_g"], eps)
        mixed = (_mamba(u, p, cfg, mm, operand, carry_state) if kind == "mamba"
                 else _attention(u, p, cfg, mm, precision))
        x = x + m * mixed
        # token by token, so a block of rows at a time
        rows = math.gcd(MLP_ROWS, x.shape[0])
        return jax.lax.map(jax.checkpoint(lambda xb: mlp(xb, p)),
                           x.reshape(-1, rows, x.shape[1])).reshape(x.shape)

    return run


def loss_sum(params, ids, labels, cfg: Dict[str, Any],
             precision: str = "float32", carry_state: bool = True):
    """Summed next-token cross-entropy of rows ``ids`` [r, t] (int32)."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    x = params["embed"][ids] * cfg["embedding_multiplier"]
    for kind, p in zip(cfg["layer_types"], params["layers"]):
        run = jax.checkpoint(_layer(kind, cfg, precision, carry_state))
        x = jax.lax.map(lambda row, run=run, p=p: run(row, p), x)

    def head(args):
        row, lab = args
        logits = mm(_rms(row, params["final_g"], cfg["rms_norm_eps"]),
                    params["embed"].T) / cfg["logits_scaling"]
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(lse - jnp.take_along_axis(logits, lab[:, None], 1)[:, 0])

    return jnp.sum(jax.lax.map(jax.checkpoint(head), (x, labels)))


# -------------------------------------------------------------- training

def _grad_block(params, ids, labels, inv_tokens, acc, cfg_key, precision,
                carry_state):
    cfg = dict(cfg_key)
    loss, g = jax.value_and_grad(lambda p: loss_sum(
        p, ids, labels, cfg, precision, carry_state) * inv_tokens)(params)
    if acc is not None:
        g = jax.tree.map(jnp.add, acc, g)
    return loss, g


@functools.lru_cache(maxsize=None)
def _jitted():
    """As ``gpt_plain._jitted``: donation asked of the backend at first use."""
    cpu = jax.default_backend() == "cpu"
    grad = jax.jit(_grad_block,
                   static_argnames=("cfg_key", "precision", "carry_state"),
                   donate_argnames=() if cpu else ("acc",))
    adam = jax.jit(_adam, donate_argnums=() if cpu else (0, 1, 2),
                   static_argnames=("lr", "b1", "b2", "eps"))
    return grad, adam


def leaf_norms(tree, layer_types) -> Dict[str, Any]:
    """L2 norm of every leaf, the layers' leaves by kind:
    ``{"embed": (), "mamba.W_in": [mamba layers], "attention.Wq": [...]}``."""
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
    out = {"embed": norm(tree["embed"]), "final_g": norm(tree["final_g"])}
    for kind, names in LEAVES.items():
        of_kind = [p for k, p in zip(layer_types, tree["layers"]) if k == kind]
        for name in names if of_kind else ():
            out[f"{kind}.{name}"] = jnp.stack([norm(p[name]) for p in of_kind])
    return out


_leaf_norms_jit = jax.jit(leaf_norms, static_argnums=1)
_zeros_jit = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
_diff_norms_jit = jax.jit(lambda a, b, kinds: leaf_norms(
    jax.tree.map(jnp.subtract, a, b), kinds), static_argnums=2)


@functools.partial(jax.jit, static_argnames=("key_",))
def _init_from_key(key, key_):
    return init_params(dict(key_), key)


def init_on_device(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The seed's weights, made on the device in one jitted call."""
    return _init_from_key(seed_key(seed), cfg_key(cfg))


def follow(cfg: Dict[str, Any], train: Dict[str, Any], seed: int,
           tokens: np.ndarray, rows_per_block: int,
           precision: str = "float32", rows_used: int = 0,
           carry_state: bool = True) -> Dict[str, Any]:
    """Train from the seed's weights one step on each of ``tokens[step]`` and
    report what ``correct.training_gaps`` reads, as ``gpt_plain.follow``
    does: each step's loss, and per leaf the norm of the first gradient, of
    Adam's first moment after the last step and of the parameters' change.

    ``rows_used`` > 0 plants the "half of the batch left out" fault and
    ``carry_state=False`` the "state not carried from chunk to chunk" one."""
    ck = cfg_key(cfg)
    kinds = tuple(cfg["layer_types"])
    params = init_on_device(cfg, seed)
    m, v = _zeros_jit(params), _zeros_jit(params)
    hp = dict(lr=float(train["learning_rate"]), b1=float(train["adam_b1"]),
              b2=float(train["adam_b2"]), eps=float(train["adam_eps"]))
    grad_block, adam = _jitted()
    losses, g1_norms = [], None
    for s in range(tokens.shape[0]):
        rows = tokens[s][:rows_used] if rows_used else tokens[s]
        inv = 1.0 / float(rows.shape[0] * (rows.shape[1] - 1))
        acc, loss = None, 0.0
        for r0 in range(0, rows.shape[0], rows_per_block):
            blk = jnp.asarray(rows[r0:r0 + rows_per_block])
            l, acc = grad_block(params, blk[:, :-1], blk[:, 1:], inv, acc,
                                cfg_key=ck, precision=precision,
                                carry_state=carry_state)
            loss = loss + l
        losses.append(loss)
        if s == 0:
            g1_norms = _leaf_norms_jit(acc, kinds)
        params, m, v = adam(params, m, v, acc, jnp.asarray(s, jnp.int32), **hp)
        del acc
    m_norms = _leaf_norms_jit(m, kinds)
    del m, v
    dp_norms = _diff_norms_jit(params, init_on_device(cfg, seed), kinds)
    del params
    to_np = lambda d: {k: np.asarray(x, np.float64) for k, x in d.items()}
    return {"losses": np.asarray([float(l) for l in losses], np.float64),
            "g1_norms": to_np(g1_norms), "m_norms": to_np(m_norms),
            "dp_norms": to_np(dp_norms)}
