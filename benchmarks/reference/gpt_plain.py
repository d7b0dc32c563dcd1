"""Plain reference for the GPT configurations: weights from a seed, forward
pass, loss, gradients and Adam, in float32 ``jax.numpy`` with matmuls at
``highest`` precision. No kernels, no cache, nothing imported from the program
and nothing taken from it.

The equations are those of the architecture AS THIS REPO RUNS IT (each
departure from the published model is listed in the configuration's file under
``departs``): learned token + position embeddings, pre-LN blocks with a fused
bias-free QKV projection, causal softmax attention, a bias-free output
projection, a tanh-GELU MLP with biases, no final LayerNorm, an untied LM head
with a bias, mean next-token cross-entropy over every position.

Block leaves are stacked on a leading ``[n_layer]`` axis and the blocks run
under ``lax.scan`` with the body rematerialised, so that the reference compiles
in seconds and fits beside its own Adam state at the published widths. Rows are
processed ``rows_per_block`` at a time and their gradients summed.

``precision="fp8"`` is the CONTROL, never the reference: every matrix product
takes its operands in float8_e4m3fn and its incoming gradient in float8_e5m2,
each with a per-tensor scale, the step a later PR would be tempted to take
below bfloat16. ``precision="fp8_forward"`` is its milder form, held to the same
limits: the operands in e4m3, the incoming gradient left in float32.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: the precisions below bfloat16 that stand in the program's place as controls
CONTROLS = ("fp8", "fp8_forward")

BLOCK_LEAVES = ("ln1_g", "ln1_b", "w_qkv", "w_o", "ln2_g", "ln2_b",
                "w_fc", "b_fc", "w_proj", "b_proj")


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    v, t, d = cfg["vocab_size"], cfg["n_positions"], cfg["n_embd"]
    n, f = cfg["n_layer"], cfg["n_inner"]
    return {
        "wte": (v, d), "wpe": (t, d),
        "blocks": {
            "ln1_g": (n, d), "ln1_b": (n, d), "w_qkv": (n, d, 3 * d),
            "w_o": (n, d, d), "ln2_g": (n, d), "ln2_b": (n, d),
            "w_fc": (n, d, f), "b_fc": (n, f), "w_proj": (n, f, d),
            "b_proj": (n, d)},
        "head_w": (d, v), "head_b": (v,),
    }


def num_params(cfg: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to 2**63: the driver's seeds pass
    2**31, which a 32-bit seed argument would not hold."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_params(cfg: Dict[str, Any], seed) -> Dict[str, Any]:
    """GPT-2's own initialisation: N(0, initializer_range) matrices and
    embeddings, the two residual projections scaled by 1/sqrt(2 n_layer),
    zero biases, unit LayerNorm gains. Traceable: ``seed`` may be a key."""
    key = seed if isinstance(seed, jax.Array) else seed_key(seed)
    std = float(cfg["initializer_range"])
    res = std / math.sqrt(2.0 * cfg["n_layer"])
    shapes = leaf_shapes(cfg)
    ks = iter(jax.random.split(key, 8))
    normal = lambda s, shape: s * jax.random.normal(next(ks), shape, jnp.float32)
    b = shapes["blocks"]
    return {
        "wte": normal(std, shapes["wte"]),
        "wpe": normal(std, shapes["wpe"]),
        "blocks": {
            "ln1_g": jnp.ones(b["ln1_g"], jnp.float32),
            "ln1_b": jnp.zeros(b["ln1_b"], jnp.float32),
            "w_qkv": normal(std, b["w_qkv"]),
            "w_o": normal(res, b["w_o"]),
            "ln2_g": jnp.ones(b["ln2_g"], jnp.float32),
            "ln2_b": jnp.zeros(b["ln2_b"], jnp.float32),
            "w_fc": normal(std, b["w_fc"]),
            "b_fc": jnp.zeros(b["b_fc"], jnp.float32),
            "w_proj": normal(res, b["w_proj"]),
            "b_proj": jnp.zeros(b["b_proj"], jnp.float32),
        },
        "head_w": normal(std, shapes["head_w"]),
        "head_b": jnp.zeros(shapes["head_b"], jnp.float32),
    }


def make_tokens(cfg: Dict[str, Any], seed: int, steps: int, batch: int,
                seq_len: int) -> np.ndarray:
    """``[steps, batch, seq_len + 1]`` token ids, uniform over the
    vocabulary: position i is the input and i + 1 its label. Every row
    differs; the same seed gives the same rows."""
    rng = np.random.default_rng(int(seed))
    return rng.integers(0, cfg["vocab_size"], (steps, batch, seq_len + 1),
                        dtype=np.int32)


# ------------------------------------------------------------------ forward

def _fp8(x, dtype):
    """Round to an 8-bit float type with a per-tensor scale."""
    top = float(jnp.finfo(dtype).max)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


def _product(fn, precision: str):
    """A bilinear product ``fn(a, b)`` in the stated precision. ``fp8`` is the
    usual 8-bit training recipe (Micikevicius et al. 2022, "FP8 formats for
    deep learning"): both operands in e4m3 on the way forward, the incoming
    gradient in e5m2 on the way back, accumulation in float32.
    ``fp8_forward`` keeps the operands in e4m3 both ways and leaves the
    incoming gradient as it is."""
    if precision == "float32":
        return fn
    if precision not in CONTROLS:
        raise ValueError(f"unknown precision {precision!r}")
    q4 = lambda x: _fp8(x, jnp.float8_e4m3fn)
    q5 = (lambda x: _fp8(x, jnp.float8_e5m2)) if precision == "fp8" else (lambda x: x)

    @jax.custom_vjp
    def f(a, b):
        return fn(q4(a), q4(b))

    def fwd(a, b):
        return f(a, b), (a, b)

    def bwd(res, dy):
        _, vjp = jax.vjp(fn, q4(res[0]), q4(res[1]))
        return vjp(q5(dy))

    f.defvjp(fwd, bwd)
    return f


def _layer_norm(x, g, b, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g + b


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def loss_sum(params, ids, labels, cfg: Dict[str, Any],
             precision: str = "float32"):
    """Summed next-token cross-entropy of rows ``ids`` [r, t] (int32)."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    qk = _product(lambda q, k: jnp.einsum(
        "rhqd,rhkd->rhqk", q, k, precision=HIGHEST), precision)
    pv = _product(lambda w, v: jnp.einsum(
        "rhqk,rhkd->rhqd", w, v, precision=HIGHEST), precision)
    n_head, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    r, t = ids.shape
    d = cfg["n_embd"]
    hd = d // n_head
    causal = jnp.tril(jnp.ones((t, t), bool))

    def block(h, p):
        a = _layer_norm(h, p["ln1_g"], p["ln1_b"], eps)
        q, k, v = jnp.split(mm(a, p["w_qkv"]), 3, axis=-1)
        heads = lambda z: z.reshape(r, t, n_head, hd).transpose(0, 2, 1, 3)
        q, k, v = heads(q), heads(k), heads(v)
        s = qk(q, k) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        w = jax.nn.softmax(s, axis=-1)
        o = pv(w, v)
        h = h + mm(o.transpose(0, 2, 1, 3).reshape(r, t, d), p["w_o"])
        a = _layer_norm(h, p["ln2_g"], p["ln2_b"], eps)
        m = _gelu_tanh(mm(a, p["w_fc"]) + p["b_fc"])
        return h + mm(m, p["w_proj"]) + p["b_proj"], None

    h = params["wte"][ids] + params["wpe"][:t][None]
    h, _ = jax.lax.scan(jax.checkpoint(block), h, params["blocks"])
    logits = mm(h, params["head_w"]) + params["head_b"]
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - tgt)


# -------------------------------------------------------------- training

def _grad_block(params, ids, labels, inv_tokens, acc, cfg_key, precision):
    cfg = dict(cfg_key)
    loss, g = jax.value_and_grad(
        lambda p: loss_sum(p, ids, labels, cfg, precision) * inv_tokens)(params)
    if acc is not None:
        g = jax.tree.map(jnp.add, acc, g)
    return loss, g


def _adam(params, m, v, g, step, lr, b1, b2, eps):
    """Adam as Kingma & Ba's section 2 states its efficient form: the bias
    corrections folded into the step size, eps beside the raw sqrt(v)."""
    t = step.astype(jnp.float32) + 1.0
    alpha = lr * jnp.sqrt(1.0 - b2 ** t) / (1.0 - b1 ** t)
    m = jax.tree.map(lambda a, b: b1 * a + (1.0 - b1) * b, m, g)
    v = jax.tree.map(lambda a, b: b2 * a + (1.0 - b2) * b * b, v, g)
    params = jax.tree.map(lambda p, a, b: p - alpha * a / (jnp.sqrt(b) + eps),
                          params, m, v)
    return params, m, v


@functools.lru_cache(maxsize=None)
def _jitted():
    """``(_grad_block, _adam)`` jitted, built at first use: whether to donate
    is asked of the backend, which importing this module must not start. On
    the CPU backend donated buffers alias (this repo's ``donation-gate``
    rule), so the accumulator and the Adam state are donated only off it."""
    cpu = jax.default_backend() == "cpu"
    grad = jax.jit(_grad_block, static_argnames=("cfg_key", "precision"),
                   donate_argnames=() if cpu else ("acc",))
    adam = jax.jit(_adam, donate_argnums=() if cpu else (0, 1, 2),
                   static_argnames=("lr", "b1", "b2", "eps"))
    return grad, adam


def leaf_norms(tree) -> Dict[str, Any]:
    """L2 norm of every leaf, block leaves per layer: ``{"wte": (),
    "blocks.w_qkv": [n_layer], ...}``."""
    out = {}
    for name in ("wte", "wpe", "head_w", "head_b"):
        out[name] = jnp.sqrt(jnp.sum(jnp.square(tree[name])))
    for name in BLOCK_LEAVES:
        x = tree["blocks"][name]
        out["blocks." + name] = jnp.sqrt(
            jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
    return out


_leaf_norms_jit = jax.jit(leaf_norms)
_zeros_jit = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
_diff_norms_jit = jax.jit(lambda a, b: leaf_norms(
    jax.tree.map(jnp.subtract, a, b)))


def cfg_key(cfg: Dict[str, Any]) -> Tuple:
    """The numbers of a configuration that the equations read, hashable."""
    names = ("vocab_size", "n_positions", "n_embd", "n_layer", "n_head",
             "n_inner", "layer_norm_epsilon", "initializer_range")
    return tuple((k, cfg[k]) for k in names)


@functools.partial(jax.jit, static_argnames=("key_",))
def _init_from_key(key, key_):
    return init_params(dict(key_), key)


def init_on_device(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The seed's weights, made on the device in one jitted call."""
    return _init_from_key(seed_key(seed), cfg_key(cfg))


def follow(cfg: Dict[str, Any], train: Dict[str, Any], seed: int,
           tokens: np.ndarray, rows_per_block: int,
           precision: str = "float32", rows_used: int = 0) -> Dict[str, Any]:
    """Train from the seed's weights one step on each of ``tokens[step]`` and
    report what the comparison reads: each step's loss, and per leaf the norm
    of the first gradient, of Adam's first moment after the last step and of
    the parameters' change over all the steps.

    ``rows_used`` > 0 plants the "half of the batch left out" fault: only the
    first ``rows_used`` rows of each step count, the mean taken over them."""
    ck = cfg_key(cfg)
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
                                cfg_key=ck, precision=precision)
            loss = loss + l
        losses.append(loss)
        if s == 0:
            g1_norms = _leaf_norms_jit(acc)
        params, m, v = adam(params, m, v, acc, jnp.asarray(s, jnp.int32), **hp)
        del acc
    m_norms = _leaf_norms_jit(m)
    del m, v
    dp_norms = _diff_norms_jit(params, init_on_device(cfg, seed))
    del params
    to_np = lambda d: {k: np.asarray(x, np.float64) for k, x in d.items()}
    return {"losses": np.asarray([float(l) for l in losses], np.float64),
            "g1_norms": to_np(g1_norms), "m_norms": to_np(m_norms),
            "dp_norms": to_np(dp_norms)}
