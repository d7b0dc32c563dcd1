"""Plain reference for the decoder-hybrid-decoder configurations (SambaY,
``model_type`` ``phi4flash``): weights from a seed, forward pass, loss,
gradients and Adam, in float32 ``jax.numpy`` with every matrix product at
``highest`` precision. No kernels, nothing imported from the program and
nothing taken from it.

The equations are the published model's (arXiv:2507.06607; differential
attention is arXiv:2410.05258; what the catalogued ``config.json`` does not
hold stands under ``assumed`` in the configuration's file):

    x = E[ids]                                   (no scale, no positions)
    each layer:  x = x + mixer(LN1(x));   [a, g] = LN2(x) W_fc1
                 x = x + (a * silu(g)) W_fc2         (LayerNorm with gain and bias)
    logits = LN(x) E^T                           (the head is the embedding)

``mamba`` mixer (Mamba-1): ``[xs, z] = u W_in``; ``xs = silu(conv(xs))``, a
causal depthwise convolution with bias; ``[d, B, C] = xs W_x``; ``dt =
softplus(d W_dt + dt_bias)``; ``A = -exp(A_log)`` [channels, states]; per
channel the TOKEN-BY-TOKEN recurrence ``S_t = exp(dt_t A) S_{t-1} + dt_t
xs_t B_t``, ``y_t = S_t . C_t + D xs_t`` (a ``lax.scan`` over tokens inside
one over blocks of ``SCAN_BLOCK`` tokens, the outer body rematerialised); the
layer that gives the ``memory`` hands ``y`` on, before the gate; ``out = (y *
silu(z)) W_out``.

Differential attention (``sliding_attention``, ``full_attention``): heads in
adjacent pairs, ``[q, k, v] = u W_qkv + b_qkv`` with ``num_attention_heads``
query heads over ``num_key_value_heads`` key/value heads, query pair ``i``
reading key/value pair ``i // (pairs / kv pairs)``; ``P_j = softmax(q_j k_j^T
/ sqrt(head) + mask)`` for the pair's two heads, the full masked softmax, a
block of query rows at a time; ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
lambda_init``, ``lambda_init = 0.8 - 0.6 exp(-0.3 l)`` with ``l`` the layer's
published index; ``o = (P_1 - lambda P_2) [v_1 | v_2]``; ``o = RMSNorm(o;
g_sub) (1 - lambda_init)``; ``out = o W_o + b_o``. The mask is causal; in a
``sliding_attention`` layer a query also sees only itself and the
``sliding_window - 1`` keys before it. ``cross_attention``: ``q = u W_q +
b_q`` only, keys and values those of the ``full_attention`` layer (after
their bias), full causal. ``gmu``: ``out = (silu(u W_1) * m) W_2`` with ``m``
the memory.

Each layer body is rematerialised and a step's rows go one at a time, the
MLP and the head a block of tokens at a time, so that beside the float32
parameters and their gradient one row's activations of one layer are alive;
Adam's two moments wait on the host while a gradient is made.

``precision="fp8"`` / ``"fp8_forward"`` are the CONTROLS of ``gpt_plain.py``,
never the reference: every matrix product's operands (and the scan's ``xs``,
``B``, ``C``) in float8. The planted faults: ``rows_used`` (the loss over the
first positions of every row only), ``detach_forwarded`` (a stop-gradient on
the memory and on the keys and values where they are read), ``no_window`` (a
``sliding_attention`` layer sees every earlier key), ``no_subtraction``
(``lambda = 0``).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.reference.gpt_plain import (CONTROLS, HIGHEST, _adam,
                                            _layer_norm, _product,
                                            make_tokens, seed_key)
from benchmarks.reference.granite_hybrid_plain import _operand, _rms

__all__ = ["CONTROLS", "FAULTS", "make_tokens", "seed_key", "init_params",
           "loss_sum", "follow", "num_params", "cfg_key", "leaf_shapes",
           "layer_pattern"]

#: the planted faults of this family, each a keyword of ``follow``
FAULTS = ("detach_forwarded", "no_window", "no_subtraction")
KINDS = ("mamba", "sliding_attention", "full_attention", "gmu",
         "cross_attention")
#: query rows that attention takes at a time
ATTENTION_ROWS = 256
#: rows that the gated MLP and the head take at a time
MLP_ROWS = 1024
#: tokens of the recurrence whose states the backward pass keeps at a time
SCAN_BLOCK = 64
SUBLN_EPS = 1e-5

_NUMBERS = ("vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "sliding_window",
            "layer_norm_eps", "mamba_d_state", "mamba_d_conv", "mamba_expand",
            "mamba_dt_rank", "initializer_range")


def layer_pattern(n: int):
    """The published rule: the kind of every layer of a model of ``n``."""
    if n % 4:
        raise ValueError("num_hidden_layers must be a multiple of 4")
    return [("mamba" if i <= n // 2 else "gmu") if i % 2 == 0
            else "sliding_attention" if i < n // 2
            else "full_attention" if i == n // 2 + 1 else "cross_attention"
            for i in range(n)]


def _layers(cfg):
    kinds = tuple(cfg.get("layer_types") or layer_pattern(cfg["num_hidden_layers"]))
    return kinds, tuple(cfg.get("published_layers") or range(len(kinds)))


def cfg_key(cfg: Dict[str, Any]) -> Tuple:
    """The numbers of a configuration that the equations read, hashable."""
    kinds, published = _layers(cfg)
    return tuple((k, cfg[k]) for k in _NUMBERS) + (
        ("layer_types", kinds), ("published_layers", published))


def _sizes(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // heads
    return (d, cfg["intermediate_size"], cfg["mamba_expand"] * d,
            cfg["mamba_d_state"], cfg["mamba_dt_rank"], heads,
            cfg["num_key_value_heads"], hd)


def layer_shapes(cfg: Dict[str, Any], kind: str) -> Dict[str, Tuple]:
    d, f, di, n, r, heads, kv, hd = _sizes(cfg)
    shared = {"ln1_g": (d,), "ln1_b": (d,), "ln2_g": (d,), "ln2_b": (d,),
              "W_fc1": (d, 2 * f), "W_fc2": (f, d)}
    diff = {"lambda_q1": (hd,), "lambda_k1": (hd,), "lambda_q2": (hd,),
            "lambda_k2": (hd,), "subln_g": (2 * hd,), "Wo": (d, d),
            "bo": (d,)}
    if kind == "mamba":
        return {"W_in": (d, 2 * di), "conv_w": (cfg["mamba_d_conv"], di),
                "conv_b": (di,), "W_x": (di, r + 2 * n), "W_dt": (r, di),
                "dt_bias": (di,), "A_log": (di, n), "D": (di,),
                "W_out": (di, d), **shared}
    if kind == "gmu":
        return {"W_1": (d, di), "W_2": (di, d), **shared}
    if kind == "cross_attention":
        return {"Wq": (d, d), "bq": (d,), **diff, **shared}
    wide = d + 2 * kv * hd
    return {"Wqkv": (d, wide), "bqkv": (wide,), **diff, **shared}


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["hidden_size"]
    return {"embed": (cfg["vocab_size"], d), "final_g": (d,), "final_b": (d,),
            "layers": [layer_shapes(cfg, k) for k in _layers(cfg)[0]]}


def num_params(cfg: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_params(cfg: Dict[str, Any], seed) -> Dict[str, Any]:
    """The initialisation under ``assumed`` in the configuration's file:
    N(0, initializer_range) matrices and embedding, unit gains and zero
    biases, the ``lambda`` vectors N(0, 0.1), the convolution and its bias
    uniform in +-1/sqrt(d_conv), ``W_dt`` uniform in +-dt_rank^-0.5,
    ``A_log`` = log(1 .. d_state) in every channel, ``dt_bias`` the inverse
    softplus of a log-uniform(1e-3, 1e-1) step, ``D`` = 1 (Mamba-1's own).
    Traceable: ``seed`` may be a key."""
    key = seed if isinstance(seed, jax.Array) else seed_key(seed)
    std = float(cfg["initializer_range"])
    bound = 1.0 / math.sqrt(cfg["mamba_d_conv"])
    dt_bound = cfg["mamba_dt_rank"] ** -0.5
    f32 = jnp.float32

    def leaf(k, name, shape):
        if name.endswith("_g") or name == "D":
            return jnp.ones(shape, f32)
        if name in ("bo", "bq", "bqkv") or name.endswith("_b") \
                and name != "conv_b":
            return jnp.zeros(shape, f32)
        if name.startswith("lambda_"):
            return 0.1 * jax.random.normal(k, shape, f32)
        if name in ("conv_w", "conv_b"):
            return jax.random.uniform(k, shape, f32, -bound, bound)
        if name == "W_dt":
            return jax.random.uniform(k, shape, f32, -dt_bound, dt_bound)
        if name == "A_log":
            return jnp.broadcast_to(
                jnp.log(jnp.arange(1, shape[1] + 1, dtype=f32)), shape)
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
            "final_g": jnp.ones(shapes["final_g"], f32),
            "final_b": jnp.zeros(shapes["final_b"], f32), "layers": layers}


# ------------------------------------------------------------------ forward

def _recurrence(x, dt, A, B, C):
    """One row, token by token. x, dt [t, c], A [c, n], B and C [t, n] ->
    y [t, c] (without the ``D`` term). The scan over tokens runs inside one
    over blocks of ``SCAN_BLOCK`` whose body is rematerialised."""
    t, c = x.shape
    block = math.gcd(SCAN_BLOCK, t)

    def token(S, z):
        xt, dtt, Bt, Ct = z
        S = jnp.exp(dtt[:, None] * A) * S + (dtt * xt)[:, None] * Bt[None, :]
        return S, jnp.sum(S * Ct[None, :], axis=-1)

    cut = lambda z: z.reshape((t // block, block) + z.shape[1:])
    _, y = jax.lax.scan(
        jax.checkpoint(lambda S, z: jax.lax.scan(token, S, z)),
        jnp.zeros((c, A.shape[1]), jnp.float32), (cut(x), cut(dt), cut(B), cut(C)))
    return y.reshape(t, c)


def _mamba(u, p, cfg, mm, operand):
    """-> (the mixer's output, the scan's output ``y`` before the gate)."""
    n, r, k = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    t = u.shape[0]
    xs, z = jnp.split(mm(u, p["W_in"]), 2, axis=-1)
    padded = jnp.pad(xs, ((k - 1, 0), (0, 0)))
    xs = jax.nn.silu(sum(padded[j:j + t] * p["conv_w"][j] for j in range(k))
                     + p["conv_b"])
    dt, B, C = jnp.split(mm(xs, p["W_x"]), [r, r + n], axis=-1)
    dt = jax.nn.softplus(mm(dt, p["W_dt"]) + p["dt_bias"])
    y = _recurrence(operand(xs), dt, -jnp.exp(p["A_log"]), operand(B),
                    operand(C)) + p["D"] * xs
    return mm(y * jax.nn.silu(z), p["W_out"]), y


def _diff_attention(q, k, v, p, cfg, mm, precision, published_index, window,
                    no_subtraction):
    """q [t, heads * hd]; k, v [t, kv heads * hd] -> [t, d]."""
    d, _, _, _, _, heads, kv, hd = _sizes(cfg)
    t = q.shape[0]
    pairs, group = heads // 2, heads // kv
    qk = _product(lambda a, b: jnp.einsum("qpjd,kpjd->pjqk", a, b,
                                          precision=HIGHEST), precision)
    pv = _product(lambda w, x: jnp.einsum("pqk,kpe->qpe", w, x,
                                          precision=HIGHEST), precision)
    q = q.reshape(t, pairs, 2, hd)
    # query pair i reads key/value pair i // (pairs / kv pairs)
    k = jnp.repeat(k.reshape(t, kv // 2, 2, hd), group, axis=1)
    v = jnp.repeat(v.reshape(t, kv // 2, 2 * hd), group, axis=1)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * published_index)
    lam = jnp.exp(jnp.sum(p["lambda_q1"] * p["lambda_k1"])) \
        - jnp.exp(jnp.sum(p["lambda_q2"] * p["lambda_k2"])) + lam_init
    if no_subtraction:
        lam = 0.0  # the planted fault
    rows = math.gcd(ATTENTION_ROWS, t)

    def block(args):
        qb, first = args
        s = qk(qb, k) / math.sqrt(hd)                 # [pairs, 2, rows, t]
        ago = (first + jnp.arange(rows))[:, None] - jnp.arange(t)[None, :]
        seen = ago >= 0
        if window is not None:
            seen &= ago < window
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
        return pv(w[:, 0] - lam * w[:, 1], v)          # [rows, pairs, 2 hd]

    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(t // rows, rows, pairs, 2, hd),
                     jnp.arange(0, t, rows)))
    o = _rms(o.reshape(t, pairs, 2 * hd), p["subln_g"], SUBLN_EPS) \
        * (1.0 - lam_init)
    return mm(o.reshape(t, d), p["Wo"]) + p["bo"]


def _layer(kind: str, cfg, precision: str, published_index: int, faults):
    """``(x [t, d], leaves, memory, keys and values) -> (x, memory, keys and
    values)`` of one layer on one row: what the layer does not make it hands
    on as it was."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    operand = _operand(precision)
    eps = cfg["layer_norm_eps"]
    d, _, _, _, _, _, kv, hd = _sizes(cfg)
    detach = jax.lax.stop_gradient if "detach_forwarded" in faults \
        else (lambda z: z)
    attend = functools.partial(
        _diff_attention, cfg=cfg, mm=mm, precision=precision,
        published_index=published_index,
        no_subtraction="no_subtraction" in faults)

    def mlp(x, p):
        a, g = jnp.split(mm(_layer_norm(x, p["ln2_g"], p["ln2_b"], eps),
                            p["W_fc1"]), 2, -1)
        return x + mm(a * jax.nn.silu(g), p["W_fc2"])

    def run(x, p, memory, keys):
        u = _layer_norm(x, p["ln1_g"], p["ln1_b"], eps)
        if kind == "mamba":
            mixed, memory = _mamba(u, p, cfg, mm, operand)
        elif kind == "gmu":
            mixed = mm(jax.nn.silu(mm(u, p["W_1"])) * detach(memory), p["W_2"])
        elif kind == "cross_attention":
            k, v = detach(keys)
            mixed = attend(mm(u, p["Wq"]) + p["bq"], k, v, p, window=None)
        else:
            q, k, v = jnp.split(mm(u, p["Wqkv"]) + p["bqkv"],
                                [d, d + kv * hd], axis=-1)
            keys = (k, v)
            mixed = attend(q, k, v, p, window=(
                cfg["sliding_window"] if kind == "sliding_attention"
                and "no_window" not in faults else None))
        x = x + mixed
        # token by token, so a block of rows at a time
        rows = math.gcd(MLP_ROWS, x.shape[0])
        x = jax.lax.map(jax.checkpoint(lambda xb: mlp(xb, p)),
                        x.reshape(-1, rows, x.shape[1])).reshape(x.shape)
        return x, memory, keys

    return run


def loss_sum(params, ids, labels, cfg: Dict[str, Any],
             precision: str = "float32", rows_used: int = 0,
             faults: Tuple[str, ...] = ()):
    """Summed next-token cross-entropy of rows ``ids`` [r, t] (int32), over
    the first ``rows_used`` positions of each row if that is given."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    kinds, published = _layers(cfg)
    d, _, di, _, _, _, kv, hd = _sizes(cfg)
    t = ids.shape[1]
    used = rows_used or t
    eps = cfg["layer_norm_eps"]

    def head(args):
        xb, lab, counted = args
        logits = mm(_layer_norm(xb, params["final_g"], params["final_b"], eps),
                    params["embed"].T)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.sum(counted * (
            lse - jnp.take_along_axis(logits, lab[:, None], 1)[:, 0]))

    def row(args):
        x, lab = args
        memory = jnp.zeros((t, di), jnp.float32)
        keys = (jnp.zeros((t, kv * hd), jnp.float32),) * 2
        for kind, index, p in zip(kinds, published, params["layers"]):
            x, memory, keys = jax.checkpoint(_layer(
                kind, cfg, precision, index, faults))(x, p, memory, keys)
        rows = math.gcd(MLP_ROWS, t)
        cut = lambda z: z.reshape((t // rows, rows) + z.shape[1:])
        counted = (jnp.arange(t) < used).astype(jnp.float32)
        return jnp.sum(jax.lax.map(jax.checkpoint(head),
                                   (cut(x), cut(lab), cut(counted))))

    return jnp.sum(jax.lax.map(row, (params["embed"][ids], labels)))


# -------------------------------------------------------------- training

def _grad(params, ids, labels, cfg_key, precision, rows_used, faults):
    """The mean loss over the counted positions and its gradient."""
    cfg = dict(cfg_key)
    count = labels.shape[0] * (rows_used or labels.shape[1])
    return jax.value_and_grad(lambda p: loss_sum(
        p, ids, labels, cfg, precision, rows_used, faults) / count)(params)


@functools.lru_cache(maxsize=None)
def _jitted():
    """As ``gpt_plain._jitted``: donation asked of the backend at first use."""
    cpu = jax.default_backend() == "cpu"
    grad = jax.jit(_grad, static_argnames=("cfg_key", "precision",
                                           "rows_used", "faults"))
    adam = jax.jit(_adam, donate_argnums=() if cpu else (0, 1, 2),
                   static_argnames=("lr", "b1", "b2", "eps"))
    return grad, adam


def leaf_norms(tree, layer_types) -> Dict[str, Any]:
    """L2 norm of every leaf, the layers' leaves by kind:
    ``{"embed": (), "mamba.W_in": [mamba layers], "gmu.W_1": [...]}``. The
    fused projection's leaves count as their q, k and v parts
    (``"full_attention.bqkv.k"``): the keys' bias has no gradient at all (a
    query's scores all move by the same amount), so under Adam that third of
    the leaf moves by round-off alone, which no other part should answer
    for. A layer's four ``lambda`` vectors count as ONE leaf
    (``"full_attention.lambda"``): they enter the model through one number,
    so their gradients are one number's, a sum over the whole batch of
    products that cancel, and twelve leaves that swing together would be
    more than a tenth of the leaves."""
    norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))
    out = {name: norm(tree[name]) for name in ("embed", "final_g", "final_b")}
    for kind in KINDS:
        of_kind = [p for k, p in zip(layer_types, tree["layers"]) if k == kind]
        names = sorted(of_kind[0]) if of_kind else ()
        lambdas = [n for n in names if n.startswith("lambda_")]
        if lambdas:
            out[f"{kind}.lambda"] = jnp.stack([norm(jnp.concatenate(
                [p[n] for n in lambdas])) for p in of_kind])
        for name in names:
            if name in lambdas:
                continue
            if name in ("Wqkv", "bqkv"):
                d = of_kind[0]["Wo"].shape[0]
                width = of_kind[0][name].shape[-1]
                cuts = [d, d + (width - d) // 2]
                for part, i in (("q", 0), ("k", 1), ("v", 2)):
                    out[f"{kind}.{name}.{part}"] = jnp.stack(
                        [norm(jnp.split(p[name], cuts, axis=-1)[i])
                         for p in of_kind])
                continue
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
           tokens: np.ndarray, precision: str = "float32", rows_used: int = 0,
           detach_forwarded: bool = False, no_window: bool = False,
           no_subtraction: bool = False) -> Dict[str, Any]:
    """Train from the seed's weights one step on each of ``tokens[step]`` and
    report what ``correct.training_gaps`` reads, as ``gpt_plain.follow``
    does: each step's loss, and per leaf the norm of the first gradient, of
    Adam's first moment after the last step and of the parameters' change.
    A step's rows go through in one program, one at a time.

    While a gradient is made, Adam's two moments wait on the host: parameters,
    gradient and moments are 11.15 GB at the published widths, and the
    gradient's program wants room of its own.

    ``rows_used`` > 0 plants the "half of the tokens left out" fault (the
    loss over the first ``rows_used`` positions of every row); the three
    flags plant the faults of ``FAULTS``."""
    flags = dict(detach_forwarded=detach_forwarded, no_window=no_window,
                 no_subtraction=no_subtraction)
    faults = tuple(name for name in FAULTS if flags[name])
    kinds = _layers(cfg)[0]
    params = init_on_device(cfg, seed)
    hp = dict(lr=float(train["learning_rate"]), b1=float(train["adam_b1"]),
              b2=float(train["adam_b2"]), eps=float(train["adam_eps"]))
    grad, adam = _jitted()
    losses, g1_norms, moments = [], None, None
    for s in range(tokens.shape[0]):
        rows = jnp.asarray(tokens[s])
        loss, g = grad(params, rows[:, :-1], rows[:, 1:], cfg_key=cfg_key(cfg),
                       precision=precision, rows_used=int(rows_used),
                       faults=faults)
        losses.append(float(loss))
        if s == 0:
            g1_norms = _leaf_norms_jit(g, kinds)
        m, v = ((_zeros_jit(params), _zeros_jit(params)) if moments is None
                else jax.device_put(moments))
        params, m, v = adam(params, m, v, g, jnp.asarray(s, jnp.int32), **hp)
        del g
        if s + 1 < tokens.shape[0]:
            moments = jax.device_get((m, v))
            del m, v
    m_norms = _leaf_norms_jit(m, kinds)
    del m, v, moments
    dp_norms = _diff_norms_jit(params, init_on_device(cfg, seed), kinds)
    del params
    to_np = lambda d: {k: np.asarray(x, np.float64) for k, x in d.items()}
    return {"losses": np.asarray(losses, np.float64),
            "g1_norms": to_np(g1_norms), "m_norms": to_np(m_norms),
            "dp_norms": to_np(dp_norms)}
