"""Plain reference for the looped language-model configurations
(``model_type`` ``ouro``): weights from a seed, forward pass, loss, gradients
and Adam, in float32 ``jax.numpy`` with every matrix product at ``highest``
precision. A ``lax.scan`` over the passes, attention as an explicit masked
softmax, rotary positions by explicit cos / sin tables. No kernels, no
checkpoint policy shared with the program, nothing imported from the program
and nothing taken from it.

The equations are the published model's (the configuration's ``source``;
"Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741). A
block, on ``x`` [t, d], with four norm gains and no bias anywhere:

    a = rms(x; g1);  q, k, v = a Wq, a Wk, a Wv  (heads of head_dim)
    q, k <- rope(q, k; theta, all lanes of the head, rotate-half)
    o = causal softmax(q k^T / sqrt(head_dim)) v;   x <- x + rms(o Wo; g2)
    m = rms(x; g3);  f = (silu(m W_gate) * (m W_up)) W_down;  x <- x + rms(f; g4)

The model, with ``R = total_ut_steps`` and the same leaves in every pass:

    h <- E[ids];  for s = 1..R:  h <- block_L(.. block_1(h));  h <- rms(h; g_final)
        (the normed h is pass s's output AND pass s + 1's input)
        logits_s = h W_head;   lambda_s = sigmoid(h w_gate + b_gate)
    per token:  p_1 = lambda_1,  p_s = lambda_s prod_{j<s} (1 - lambda_j) for s < R,
                p_R = prod_{j<R} (1 - lambda_j)
    loss = mean over tokens of [ sum_s p_s CE(logits_s, y) - beta H(p) ],
           H(p) = -sum_s p_s log p_s        (the paper's stage-I objective)

Departures from the published model (also under ``departs`` in the
configuration's file): Adam without weight decay at a constant learning rate,
no warm-up, no clipping; seeded random weights; the stage-I objective only.

Block leaves are stacked on a leading ``[n_layer]`` axis and a pass runs the
blocks under ``lax.scan`` (as ``gpt_plain.py`` does), so that the reference
compiles once a pass and not once a block. Each block takes the rows of a
step one at a time (``lax.map`` over rows, the body rematerialised), its
attention a block of query rows at a time and its MLP a block of tokens at a
time, and the head a row at a time, so that beside the float32 parameters,
their gradient and Adam's two moments (7.4 GB at the published widths) only
the blocks' inputs (67 MB for two rows an application) and one block's
activations of one row are alive.

``precision="fp8"`` / ``"fp8_forward"`` are the CONTROLS of ``gpt_plain.py``,
never the reference: every matrix product's operands in float8.
``detach_passes=True`` plants the fault of the mechanism: a stop-gradient on
what one pass hands the next, so that every shared leaf loses what flows
through the later passes. ``rows_used`` leaves part of each batch out.
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt_plain import (CONTROLS, HIGHEST, _adam,
                                            _product, make_tokens, seed_key)

__all__ = ["CONTROLS", "make_tokens", "seed_key", "init_params", "loss_sum",
           "follow", "num_params", "cfg_key", "leaf_shapes"]

BLOCK_LEAVES = ("g1", "wq", "wk", "wv", "wo", "g2", "g3", "w_gate", "w_up",
                "w_down", "g4")
TOP_LEAVES = ("embed", "final_g", "head_w", "gate_w", "gate_b")
#: query rows that attention takes at a time
ATTENTION_ROWS = 512
#: rows that the gated MLP takes at a time
MLP_ROWS = 1024

_NUMBERS = ("vocab_size", "hidden_size", "intermediate_size", "n_layer",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "total_ut_steps", "rms_norm_eps", "rope_theta",
            "initializer_range", "exit_entropy_weight")


def cfg_key(cfg: Dict[str, Any]) -> Tuple:
    """The numbers of a configuration that the equations read, hashable."""
    return tuple((k, cfg[k]) for k in _NUMBERS)


def block_shapes(cfg: Dict[str, Any]) -> Dict[str, Tuple]:
    """The blocks' leaves, stacked on a leading ``[n_layer]`` axis."""
    n, d, f = cfg["n_layer"], cfg["hidden_size"], cfg["intermediate_size"]
    hd = cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"g1": (n, d), "wq": (n, d, q), "wk": (n, d, kv), "wv": (n, d, kv),
            "wo": (n, q, d), "g2": (n, d), "g3": (n, d), "w_gate": (n, d, f),
            "w_up": (n, d, f), "w_down": (n, f, d), "g4": (n, d)}


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    return {"embed": (v, d), "final_g": (d,), "head_w": (d, v),
            "gate_w": (d,), "gate_b": (), "blocks": block_shapes(cfg)}


def num_params(cfg: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_params(cfg: Dict[str, Any], seed) -> Dict[str, Any]:
    """The initialisation under ``assumed`` in the configuration's file:
    N(0, initializer_range) for every matrix, the embedding, the head and
    the gate's weight; unit norm gains; the gate's bias 0. Traceable:
    ``seed`` may be a key."""
    key = seed if isinstance(seed, jax.Array) else seed_key(seed)
    std = float(cfg["initializer_range"])
    f32 = jnp.float32

    def leaf(k, name, shape):
        if name in ("g1", "g2", "g3", "g4", "final_g"):
            return jnp.ones(shape, f32)
        if name == "gate_b":
            return jnp.zeros(shape, f32)
        return std * jax.random.normal(k, shape, f32)

    shapes = leaf_shapes(cfg)
    k_top, k_blocks = jax.random.split(key)
    out = {n: leaf(k, n, shapes[n])
           for k, n in zip(jax.random.split(k_top, len(TOP_LEAVES)),
                           TOP_LEAVES)}
    out["blocks"] = {
        n: leaf(k, n, shapes["blocks"][n])
        for k, n in zip(jax.random.split(k_blocks, len(BLOCK_LEAVES)),
                        BLOCK_LEAVES)}
    return out


# ------------------------------------------------------------------ forward

def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) * g


def rope_tables(t: int, head_dim: int, theta: float):
    """cos and sin [t, head_dim] of rotate-half rotary positions: lane ``i``
    and lane ``i + head_dim / 2`` share the frequency ``theta ** (-2i /
    head_dim)``."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, cos, sin):
    """x [t, heads, head_dim]: ``x cos + rotate_half(x) sin``."""
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _attention(a, p, cfg, mm, precision):
    heads, kv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                     cfg["head_dim"])
    t = a.shape[0]
    qk = _product(lambda q, k: jnp.einsum("qhd,khd->hqk", q, k,
                                          precision=HIGHEST), precision)
    pv = _product(lambda w, v: jnp.einsum("hqk,khd->qhd", w, v,
                                          precision=HIGHEST), precision)
    cos, sin = rope_tables(t, hd, float(cfg["rope_theta"]))
    q = _rope(mm(a, p["wq"]).reshape(t, heads, hd), cos, sin)
    k = _rope(mm(a, p["wk"]).reshape(t, kv, hd), cos, sin)
    v = mm(a, p["wv"]).reshape(t, kv, hd)
    # query head i reads key/value head i // (heads / kv)
    k, v = (jnp.repeat(z, heads // kv, axis=1) for z in (k, v))
    rows = min(ATTENTION_ROWS, t)
    if t % rows:
        raise ValueError("the reference takes whole blocks of query rows")

    def block(args):
        qb, first = args
        s = qk(qb, k) / math.sqrt(hd)
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        return pv(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)

    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(t // rows, rows, heads, hd),
                     jnp.arange(0, t, rows)))
    return mm(o.reshape(t, heads * hd), p["wo"])


def _block(cfg, precision: str):
    """``(x [t, d], leaves) -> x`` of one block on one row."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    eps = cfg["rms_norm_eps"]

    def mlp(x, p):
        m = _rms(x, p["g3"], eps)
        f = mm(jax.nn.silu(mm(m, p["w_gate"])) * mm(m, p["w_up"]), p["w_down"])
        return x + _rms(f, p["g4"], eps)

    def run(x, p):
        a = _rms(x, p["g1"], eps)
        x = x + _rms(_attention(a, p, cfg, mm, precision), p["g2"], eps)
        # token by token, so a block of rows at a time
        rows = math.gcd(MLP_ROWS, x.shape[0])
        return jax.lax.map(jax.checkpoint(lambda xb: mlp(xb, p)),
                           x.reshape(-1, rows, x.shape[1])).reshape(x.shape)

    return run


def pass_outputs(params, ids, cfg: Dict[str, Any], precision: str = "float32",
                 detach_passes: bool = False):
    """The normed outputs [R, r, t, d] of the ``total_ut_steps`` passes over
    rows ``ids`` [r, t]."""
    run = jax.checkpoint(_block(cfg, precision))

    @jax.checkpoint
    def layer(h, p):  # the rows of a step one at a time
        return jax.lax.map(lambda row: run(row, p), h), None

    def one_pass(h, _):
        h, _ = jax.lax.scan(layer, h, params["blocks"])
        h = _rms(h, params["final_g"], cfg["rms_norm_eps"])
        # what the next pass reads; detached: the planted fault
        return (jax.lax.stop_gradient(h) if detach_passes else h), h

    # a scan over the passes and not a Python loop: the loop's backward holds
    # one gradient of the stacked blocks a pass (4 x 1.64 GB at the published
    # widths, 11.7 GB of temporaries by the compiler's count), the scan's
    # adds each pass's into one
    _, outs = jax.lax.scan(one_pass, params["embed"][ids], None,
                           length=cfg["total_ut_steps"])
    return outs


def exit_distribution(gate_logits):
    """``gate_logits`` [R - 1, ...] of the first R - 1 passes -> p [R, ...]:
    p_s = lambda_s prod_{j<s} (1 - lambda_j), the last pass taking the rest."""
    lam = jax.nn.sigmoid(gate_logits)
    stay = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(stay[:1]), stay[:-1]], axis=0)
    return jnp.concatenate([lam * before, stay[-1:]], axis=0)


def loss_sum(params, ids, labels, cfg: Dict[str, Any],
             precision: str = "float32", detach_passes: bool = False):
    """Summed over the tokens of rows ``ids`` [r, t] (int32): the expected
    next-token cross-entropy under the exit distribution, less ``beta`` times
    that distribution's entropy."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)

    def head(args):
        row, lab = args
        logits = mm(row, params["head_w"])
        ce = jax.scipy.special.logsumexp(logits, axis=-1) \
            - jnp.take_along_axis(logits, lab[:, None], 1)[:, 0]
        return ce, mm(row, params["gate_w"][:, None])[:, 0] + params["gate_b"]

    ces, gates = zip(*(jax.lax.map(jax.checkpoint(head), (h, labels))
                       for h in pass_outputs(params, ids, cfg, precision,
                                             detach_passes)))
    ces = jnp.stack(ces)
    if len(gates) == 1:
        return jnp.sum(ces)  # one pass: p = 1, H = 0
    p = exit_distribution(jnp.stack(gates[:-1]))
    entropy = -jnp.sum(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)),
                                 0.0), axis=0)
    return jnp.sum(jnp.sum(p * ces, axis=0)
                   - cfg["exit_entropy_weight"] * entropy)


# -------------------------------------------------------------- training

def _grad(params, ids, labels, cfg_key, precision, detach_passes):
    """The mean loss over rows ``ids`` and its gradient."""
    cfg = dict(cfg_key)
    return jax.value_and_grad(lambda p: loss_sum(
        p, ids, labels, cfg, precision, detach_passes) / labels.size)(params)


@functools.lru_cache(maxsize=None)
def _jitted():
    """As ``gpt_plain._jitted``: donation asked of the backend at first use."""
    cpu = jax.default_backend() == "cpu"
    grad = jax.jit(_grad,
                   static_argnames=("cfg_key", "precision", "detach_passes"))
    adam = jax.jit(_adam, donate_argnums=() if cpu else (0, 1, 2),
                   static_argnames=("lr", "b1", "b2", "eps"))
    return grad, adam


def leaf_norms(tree) -> Dict[str, Any]:
    """L2 norm of every leaf, the blocks' leaves a layer:
    ``{"embed": (), "blocks.wq": [n_layer], ...}``."""
    out = {name: jnp.sqrt(jnp.sum(jnp.square(tree[name])))
           for name in TOP_LEAVES}
    for name in BLOCK_LEAVES:
        x = tree["blocks"][name]
        out["blocks." + name] = jnp.sqrt(
            jnp.sum(jnp.square(x), axis=tuple(range(1, x.ndim))))
    return out


_leaf_norms_jit = jax.jit(leaf_norms)
_zeros_jit = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
_diff_norms_jit = jax.jit(lambda a, b: leaf_norms(
    jax.tree.map(jnp.subtract, a, b)))


@functools.partial(jax.jit, static_argnames=("key_",))
def _init_from_key(key, key_):
    return init_params(dict(key_), key)


def init_on_device(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The seed's weights, made on the device in one jitted call."""
    return _init_from_key(seed_key(seed), cfg_key(cfg))


def follow(cfg: Dict[str, Any], train: Dict[str, Any], seed: int,
           tokens: np.ndarray, precision: str = "float32", rows_used: int = 0,
           detach_passes: bool = False) -> Dict[str, Any]:
    """Train from the seed's weights one step on each of ``tokens[step]`` and
    report what ``correct.training_gaps`` reads, as ``gpt_plain.follow``
    does: each step's loss, and per leaf the norm of the first gradient, of
    Adam's first moment after the last step and of the parameters' change.
    A step's rows go through in one program, one at a time inside each block.

    While a gradient is made, Adam's two moments wait on the host: at the
    published widths the gradient's program takes 14.1 GB by the compiler's
    count (its loops over passes and blocks each hold the stacked leaves),
    and the moments are 3.7 GB more.

    ``rows_used`` > 0 plants the "half of the batch left out" fault and
    ``detach_passes=True`` the "passes detached" one."""
    params = init_on_device(cfg, seed)
    hp = dict(lr=float(train["learning_rate"]), b1=float(train["adam_b1"]),
              b2=float(train["adam_b2"]), eps=float(train["adam_eps"]))
    grad, adam = _jitted()
    losses, g1_norms, moments = [], None, None
    for s in range(tokens.shape[0]):
        rows = jnp.asarray(tokens[s][:rows_used] if rows_used else tokens[s])
        loss, g = grad(params, rows[:, :-1], rows[:, 1:], cfg_key=cfg_key(cfg),
                       precision=precision, detach_passes=detach_passes)
        losses.append(float(loss))
        if s == 0:
            g1_norms = _leaf_norms_jit(g)
        m, v = ((_zeros_jit(params), _zeros_jit(params)) if moments is None
                else jax.device_put(moments))
        params, m, v = adam(params, m, v, g, jnp.asarray(s, jnp.int32), **hp)
        del g
        if s + 1 < tokens.shape[0]:
            moments = jax.device_get((m, v))
            del m, v
    m_norms = _leaf_norms_jit(m)
    del m, v, moments
    dp_norms = _diff_norms_jit(params, init_on_device(cfg, seed))
    del params
    to_np = lambda d: {k: np.asarray(x, np.float64) for k, x in d.items()}
    return {"losses": np.asarray(losses, np.float64),
            "g1_norms": to_np(g1_norms), "m_norms": to_np(m_norms),
            "dp_norms": to_np(dp_norms)}
