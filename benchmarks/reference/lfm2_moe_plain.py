"""Plain reference for the LFM2 mixture-of-experts configurations
(``model_type`` ``lfm2_moe``): weights from a seed, the expert bias balanced
on calibration rows, forward pass, loss, gradients and Adam, in float32
``jax.numpy`` with every matrix product at ``highest`` precision. No kernels,
nothing imported from the program and nothing taken from it.

The equations (what the catalogued ``config.json`` does not hold stands under
``assumed`` in the configuration's file):

    x = E[ids]                                   (no scale, no positions)
    each layer:  x = x + mixer(rms1(x));   x = x + ffn(rms2(x))
    logits = rms(x) E^T                          (the head is the embedding)

``conv`` mixer (gated short convolution): ``[B, C, x~] = u W_in``; ``v_t =
sum_j w_j * (B * x~)_{t - L + 1 + j}``, an explicit sum of shifted products
(causal, no bias, no activation); ``out = (C * v) W_out``. ``full_attention``:
``q, k, v = u Wq, u Wk, u Wv``; a per-head RMSNorm of q and of k; rotary
positions (rotate-half, cos / sin tables); an explicit causal masked softmax
a block of query rows at a time, query head ``i`` reading key/value head
``i // (heads / kv heads)``; ``out = o Wo``.

``ffn``: the gated MLP ``(silu(h W_gate) * h W_up) W_down`` in the leading
dense layers (``published_layers`` below ``num_dense_layers``); in the others
the routed experts: ``s = sigmoid(h W_router)`` over all
``num_routed_experts`` (a float32 product), the ``num_experts_per_tok``
experts of the largest ``s + b`` (``b`` the expert bias: it selects and does
not weigh), ``g = s / (sum of the picks' s + 1e-6)`` times
``routed_scaling_factor``, and the sum over the picks that are HELD here
(``experts_held_first`` .. + ``num_experts``) of ``g_e (silu(h W_gate_e) * h
W_up_e) W_down_e``. The experts are computed DENSELY: every held expert on
every token, times its weight, zero where the token did not pick it; nothing
is sorted, gathered or grouped.

The expert bias (``calibrate_bias``): on calibration rows drawn from the seed
(not the timed rows), the layers in order, each MoE layer's bias set with the
earlier ones in place so that every expert takes ``k / E`` of the batch's
assignments: the fixed point of the published balancing rule ``b_e += gamma
sign(mean load - load_e)``, iterated with a step that shrinks. That forward
is at the backend's default precision (it only places the bias, which both
sides are then given).

Each layer body is rematerialised and a step's rows go one at a time, the
feed-forward and the head a block of tokens at a time.

``precision="fp8"`` / ``"fp8_forward"`` are the CONTROLS of ``gpt_plain.py``,
never the reference (the router's product stays float32, as stated). The
planted faults: ``rows_used`` (the loss over the first positions of every row
only), ``biased_weights`` (the combine weights taken from ``s + b``),
``capacity`` (the Switch layer's rule: assignments to a held expert past
``t k / E`` a row, in token order, dropped), ``no_conv_gate`` (``y = C *
conv(x~)``).
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

__all__ = ["CONTROLS", "FAULTS", "make_tokens", "seed_key", "init_weights",
           "calibrate_bias", "init_params", "loss_sum", "follow",
           "num_params", "cfg_key", "leaf_shapes", "route_stats"]

#: the planted faults of this family, each a keyword of ``follow``
FAULTS = ("biased_weights", "capacity", "no_conv_gate")
#: query rows that attention takes at a time
ATTENTION_ROWS = 256
#: tokens that the feed-forward and the head take at a time
FFN_ROWS = 1024
#: calibration rows of the expert bias, a row of ``seq_len`` tokens each
CALIBRATION_ROWS = 16
#: the balancing rule's iterations and its step, shrinking geometrically
BIAS_ITERATIONS = 512
BIAS_STEP = (5e-2, 1e-5)

_NUMBERS = ("vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "num_experts", "num_routed_experts",
            "experts_held_first", "num_experts_per_tok", "num_dense_layers",
            "conv_L_cache", "norm_eps", "routed_scaling_factor",
            "initializer_range")


def _layers(cfg):
    kinds = tuple(cfg["layer_types"])
    published = tuple(cfg.get("published_layers") or range(len(kinds)))
    return kinds, tuple(i >= cfg["num_dense_layers"] for i in published)


def rope_theta(cfg) -> float:
    return float((cfg.get("rope_parameters") or {}).get("rope_theta")
                 or cfg["rope_theta"])


def cfg_key(cfg: Dict[str, Any]) -> Tuple:
    """The numbers of a configuration that the equations read, hashable."""
    return tuple((k, cfg[k]) for k in _NUMBERS) + (
        ("norm_topk_prob", bool(cfg["norm_topk_prob"])),
        ("rope_theta", rope_theta(cfg)),
        ("layer_types", tuple(cfg["layer_types"])),
        ("published_layers", tuple(cfg.get("published_layers")
                                   or range(len(cfg["layer_types"])))))


def _sizes(cfg):
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, heads, cfg["num_key_value_heads"], d // heads


def layer_shapes(cfg: Dict[str, Any], kind: str, routed: bool):
    d, heads, kv, hd = _sizes(cfg)
    shapes = {"rms1_g": (d,), "rms2_g": (d,)}
    if kind == "conv":
        shapes.update({"W_in": (d, 3 * d), "conv_w": (cfg["conv_L_cache"], d),
                       "W_out": (d, d)})
    else:
        shapes.update({"Wq": (d, d), "Wk": (d, kv * hd), "Wv": (d, kv * hd),
                       "Wo": (d, d), "q_norm_g": (hd,), "k_norm_g": (hd,)})
    if routed:
        g, f = cfg["num_experts"], cfg["moe_intermediate_size"]
        shapes.update({"W_router": (d, cfg["num_routed_experts"]),
                       "experts_gate_up": (g, d, 2 * f),
                       "experts_down": (g, f, d)})
    else:
        f = cfg["intermediate_size"]
        shapes.update({"W_gate_up": (d, 2 * f), "W_down": (f, d)})
    return shapes


def leaf_shapes(cfg: Dict[str, Any]) -> Dict[str, Any]:
    d = cfg["hidden_size"]
    return {"embed": (cfg["vocab_size"], d), "final_g": (d,),
            "layers": [layer_shapes(cfg, k, r) for k, r in zip(*_layers(cfg))]}


def num_params(cfg: Dict[str, Any]) -> int:
    return sum(int(np.prod(s)) for s in jax.tree.leaves(
        leaf_shapes(cfg), is_leaf=lambda x: isinstance(x, tuple)))


def init_weights(cfg: Dict[str, Any], seed) -> Dict[str, Any]:
    """N(0, initializer_range) for every matrix, the embedding and the
    convolution's taps; unit gains. Traceable: ``seed`` may be a key."""
    key = seed if isinstance(seed, jax.Array) else seed_key(seed)
    std = float(cfg["initializer_range"])
    f32 = jnp.float32

    def leaf(k, name, shape):
        if name.endswith("_g"):
            return jnp.ones(shape, f32)
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


def rope_tables(t: int, head_dim: int, theta: float):
    """cos and sin [t, head_dim] of rotate-half rotary positions."""
    inv_freq = 1.0 / theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                               / head_dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    angle = jnp.concatenate([angle, angle], axis=-1)
    return jnp.cos(angle), jnp.sin(angle)


def _rope(x, cos, sin):
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos[:, None, :] + turned * sin[:, None, :]


def _conv(u, p, mm, no_gate):
    t = u.shape[0]
    taps = p["conv_w"].shape[0]
    gate_b, gate_c, x = jnp.split(mm(u, p["W_in"]), 3, axis=-1)
    z = x if no_gate else gate_b * x
    padded = jnp.pad(z, ((taps - 1, 0), (0, 0)))
    v = sum(padded[j:j + t] * p["conv_w"][j] for j in range(taps))
    return mm(gate_c * v, p["W_out"])


def _attention(u, p, cfg, mm, precision):
    _, heads, kv, hd = _sizes(cfg)
    t = u.shape[0]
    eps = cfg["norm_eps"]
    qk = _product(lambda q, k: jnp.einsum("qhd,khd->hqk", q, k,
                                          precision=HIGHEST), precision)
    pv = _product(lambda w, v: jnp.einsum("hqk,khd->qhd", w, v,
                                          precision=HIGHEST), precision)
    cos, sin = rope_tables(t, hd, rope_theta(cfg))
    q = _rope(_rms(mm(u, p["Wq"]).reshape(t, heads, hd), p["q_norm_g"], eps),
              cos, sin)
    k = _rope(_rms(mm(u, p["Wk"]).reshape(t, kv, hd), p["k_norm_g"], eps),
              cos, sin)
    v = mm(u, p["Wv"]).reshape(t, kv, hd)
    k, v = (jnp.repeat(z, heads // kv, axis=1) for z in (k, v))
    rows = math.gcd(ATTENTION_ROWS, t)

    def block(args):
        qb, first = args
        s = qk(qb, k) / math.sqrt(hd)
        seen = (first + jnp.arange(rows))[:, None] >= jnp.arange(t)[None, :]
        return pv(jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1), v)

    o = jax.lax.map(jax.checkpoint(block),
                    (q.reshape(t // rows, rows, heads, hd),
                     jnp.arange(0, t, rows)))
    return mm(o.reshape(t, heads * hd), p["Wo"])


def _routing(h, p, bias, cfg, faults=()):
    """h [t, d] -> (held weights [t, count] float32, experts [t, k]): the
    dense weight of each held expert for each token (zero where the token
    did not pick it)."""
    k, first, count = (cfg["num_experts_per_tok"], cfg["experts_held_first"],
                       cfg["num_experts"])
    scores = jax.nn.sigmoid(jnp.matmul(h, p["W_router"], precision=HIGHEST))
    chosen = scores + jax.lax.stop_gradient(bias)
    _, experts = jax.lax.top_k(jax.lax.stop_gradient(chosen), k)
    w = jnp.take_along_axis(chosen if "biased_weights" in faults else scores,
                            experts, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    w = w * cfg["routed_scaling_factor"]
    picks = experts[..., None] == first + jnp.arange(count)     # [t, k, count]
    if "capacity" in faults:
        # the Switch rule: an expert's assignments past its capacity, in
        # token order, are dropped
        room = math.ceil(h.shape[0] * k / cfg["num_routed_experts"])
        flat = picks.reshape(-1, count)
        flat &= jnp.cumsum(flat, axis=0) <= room
        picks = flat.reshape(picks.shape)
    return jnp.sum(jnp.where(picks, w[..., None], 0.0), axis=1), experts


def _ffn(h, p, bias, cfg, mm, precision, faults):
    """The feed-forward of one row's ``h`` [t, d]: a block of tokens at a
    time (the routing of the whole row first: the capacity fault counts a
    row's assignments)."""
    rows = math.gcd(FFN_ROWS, h.shape[0])
    cut = lambda z: z.reshape((-1, rows) + z.shape[1:])
    if bias is None:
        def dense(hb):
            a, g = jnp.split(mm(hb, p["W_gate_up"]), 2, axis=-1)
            return mm(jax.nn.silu(a) * g, p["W_down"])
        return jax.lax.map(jax.checkpoint(dense), cut(h)).reshape(h.shape)

    up = _product(lambda a, b: jnp.einsum("rd,gdf->grf", a, b,
                                          precision=HIGHEST), precision)
    down = _product(lambda a, b: jnp.einsum("grf,gfd->grd", a, b,
                                            precision=HIGHEST), precision)
    weights, _ = _routing(h, p, bias, cfg, faults)

    def experts(args):
        hb, wb = args
        a, g = jnp.split(up(hb, p["experts_gate_up"]), 2, axis=-1)
        out = down(jax.nn.silu(a) * g, p["experts_down"])      # [count, r, d]
        return jnp.einsum("rg,grd->rd", wb, out, precision=HIGHEST)

    return jax.lax.map(jax.checkpoint(experts),
                       (cut(h), cut(weights))).reshape(h.shape)


def _layer(kind: str, cfg, precision: str, faults):
    """``(x [t, d], leaves, bias or None) -> x`` of one layer on one row."""
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    eps = cfg["norm_eps"]

    def run(x, p, bias):
        u = _rms(x, p["rms1_g"], eps)
        x = x + (_conv(u, p, mm, "no_conv_gate" in faults) if kind == "conv"
                 else _attention(u, p, cfg, mm, precision))
        return x + _ffn(_rms(x, p["rms2_g"], eps), p, bias, cfg, mm,
                        precision, faults)

    return run


def _head(params, x, lab, counted, cfg, precision):
    mm = _product(lambda a, b: jnp.matmul(a, b, precision=HIGHEST), precision)
    logits = mm(_rms(x, params["final_g"], cfg["norm_eps"]),
                params["embed"].T)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    return jnp.sum(counted * (
        lse - jnp.take_along_axis(logits, lab[:, None], 1)[:, 0]))


def _biases(cfg, bias):
    """The bias of every layer (None for a dense one) from the MoE layers'
    stacked ``bias`` [moe layers, routed experts]."""
    it = iter(bias)
    return [next(it) if routed else None for routed in _layers(cfg)[1]]


def loss_sum(params, bias, ids, labels, cfg: Dict[str, Any],
             precision: str = "float32", rows_used: int = 0,
             faults: Tuple[str, ...] = ()):
    """Summed next-token cross-entropy of rows ``ids`` [r, t] (int32), over
    the first ``rows_used`` positions of each row if that is given."""
    kinds = _layers(cfg)[0]
    biases = _biases(cfg, bias)
    t = ids.shape[1]
    used = rows_used or t
    rows = math.gcd(FFN_ROWS, t)
    cut = lambda z: z.reshape((t // rows, rows) + z.shape[1:])

    def row(args):
        x, lab = args
        for kind, p, b in zip(kinds, params["layers"], biases):
            x = jax.checkpoint(_layer(kind, cfg, precision, faults))(x, p, b)
        counted = (jnp.arange(t) < used).astype(jnp.float32)
        head = jax.checkpoint(lambda a: _head(params, *a, cfg, precision))
        return jnp.sum(jax.lax.map(head, (cut(x), cut(lab), cut(counted))))

    return jnp.sum(jax.lax.map(row, (params["embed"][ids], labels)))


# --------------------------------------------------------- the expert bias

def _kth_largest(x, k: int):
    """The ``k``-th largest of each row of ``x`` [N, E], [N, 1]: the row's
    maximum ``k`` times, each found value set aside."""
    for _ in range(k - 1):
        x = jnp.where(x >= jnp.max(x, axis=-1, keepdims=True), -jnp.inf, x)
    return jnp.max(x, axis=-1, keepdims=True)


def _solve_bias(scores, k: int):
    """The bias [E] at which each expert is among the ``k`` largest ``scores
    + bias`` [N, E] of N k / E tokens: the balancing rule iterated with a
    step that shrinks from ``BIAS_STEP[0]`` to ``BIAS_STEP[1]``."""
    e = scores.shape[1]
    mean = scores.shape[0] * k / e
    hi, lo = BIAS_STEP
    shrink = (lo / hi) ** (1.0 / (BIAS_ITERATIONS - 1))

    def rule(i, b):
        chosen = scores + b
        load = jnp.sum((chosen >= _kth_largest(chosen, k)).astype(jnp.float32),
                       axis=0)
        return b + hi * shrink ** i * jnp.sign(mean - load)

    return jax.lax.fori_loop(0, BIAS_ITERATIONS, rule,
                             jnp.zeros((e,), jnp.float32))


def _layer_parts(kind, cfg):
    """One layer's mixer half and feed-forward half, at the backend's
    default precision: what the calibration runs."""
    mm = jnp.matmul
    eps = cfg["norm_eps"]

    def mix(x, p):
        u = _rms(x, p["rms1_g"], eps)
        return x + (_conv(u, p, mm, False) if kind == "conv"
                    else _attention(u, p, cfg, mm, "float32"))

    def ffn(x, p, bias):
        return x + _ffn(_rms(x, p["rms2_g"], eps), p, bias, cfg, mm,
                        "float32", ())

    return mix, ffn


def _router_scores(x, p, cfg):
    h = _rms(x, p["rms2_g"], cfg["norm_eps"])
    return jax.nn.sigmoid(jnp.matmul(h.reshape(-1, h.shape[-1]),
                                     p["W_router"], precision=HIGHEST))


def calibration_tokens(cfg: Dict[str, Any], seed: int, seq_len: int):
    """``[CALIBRATION_ROWS, seq_len]`` token ids drawn from the seed apart
    from the timed rows (``make_tokens``)."""
    rng = np.random.default_rng([int(seed), 0xB1A5])
    return rng.integers(0, cfg["vocab_size"], (CALIBRATION_ROWS, seq_len),
                        dtype=np.int32)


@functools.partial(jax.jit, static_argnames=("key_",))
def _calibrate(params, ids, key_):
    cfg = dict(key_)
    kinds, routed = _layers(cfg)
    x = params["embed"][ids]
    out = []
    for kind, is_routed, p in zip(kinds, routed, params["layers"]):
        mix, ffn = _layer_parts(kind, cfg)
        x = jax.lax.map(lambda row: mix(row, p), x)
        bias = None
        if is_routed:
            bias = _solve_bias(_router_scores(x, p, cfg),
                               cfg["num_experts_per_tok"])
            out.append(bias)
        x = jax.lax.map(lambda row: ffn(row, p, bias), x)
    return jnp.stack(out)


def calibrate_bias(cfg: Dict[str, Any], weights, seed: int,
                   seq_len: int) -> jnp.ndarray:
    """The expert bias [moe layers, routed experts] of the seed's weights:
    the layers in order on ``calibration_tokens``, each MoE layer balanced
    with the biases before it in place."""
    return _calibrate(weights, jnp.asarray(calibration_tokens(
        cfg, seed, seq_len)), cfg_key(cfg))


@functools.partial(jax.jit, static_argnames=("key_",))
def _route_stats(params, bias, ids, key_):
    cfg = dict(key_)
    kinds, routed = _layers(cfg)
    first, count = cfg["experts_held_first"], cfg["num_experts"]
    k = cfg["num_experts_per_tok"]
    biases = iter(bias)
    x = params["embed"][ids]
    held, flips = [], []
    for kind, is_routed, p in zip(kinds, routed, params["layers"]):
        mix, ffn = _layer_parts(kind, cfg)
        x = jax.lax.map(lambda row: mix(row, p), x)
        b = None
        if is_routed:
            b = next(biases)
            h = _rms(x, p["rms2_g"], cfg["norm_eps"]).reshape(-1, x.shape[-1])
            pick = lambda hh: jax.lax.top_k(jax.nn.sigmoid(jnp.matmul(
                hh, p["W_router"], precision=HIGHEST)) + b, k)[1]
            mine = pick(h)
            theirs = pick(h.astype(jnp.bfloat16).astype(jnp.float32))
            held.append(jnp.mean(((mine >= first) & (mine < first + count))
                                 .astype(jnp.float32)))
            flips.append(jnp.sum(jnp.sort(mine, -1) != jnp.sort(theirs, -1)))
        x = jax.lax.map(lambda row: ffn(row, p, b), x)
    return jnp.stack(held), jnp.stack(flips)


def route_stats(cfg: Dict[str, Any], params, bias, ids):
    """Per MoE layer, on rows ``ids`` [r, t]: the held experts' share of the
    assignments, and how many assignments change when the router reads its
    input rounded to bfloat16 (the program's compute dtype)."""
    held, flips = _route_stats(params, bias, jnp.asarray(ids), cfg_key(cfg))
    return np.asarray(held, np.float64), np.asarray(flips, np.int64)


# -------------------------------------------------------------- training

def _grad(params, bias, ids, labels, cfg_key, precision, rows_used, faults):
    """The mean loss over the counted positions and its gradient."""
    cfg = dict(cfg_key)
    count = labels.shape[0] * (rows_used or labels.shape[1])
    return jax.value_and_grad(lambda p: loss_sum(
        p, bias, ids, labels, cfg, precision, rows_used, faults) / count)(
            params)


@functools.lru_cache(maxsize=None)
def _jitted():
    """As ``gpt_plain._jitted``: donation asked of the backend at first use."""
    cpu = jax.default_backend() == "cpu"
    grad = jax.jit(_grad, static_argnames=("cfg_key", "precision",
                                           "rows_used", "faults"))
    adam = jax.jit(_adam, donate_argnums=() if cpu else (0, 1, 2),
                   static_argnames=("lr", "b1", "b2", "eps"))
    return grad, adam


def leaf_norms(tree) -> Dict[str, Any]:
    """L2 norm of every leaf: ``{"embed": (), "final_g": (),
    "layers.<i>.<leaf>": ()}``, and of every held expert's matrix apart
    (``"layers.<i>.experts_gate_up"``: [held], one norm an expert): a fault
    that moves a few experts' gradients by percents is then not averaged
    over all of them."""
    norm = lambda x, axes=None: jnp.sqrt(jnp.sum(jnp.square(x), axis=axes))
    out = {name: norm(tree[name]) for name in ("embed", "final_g")}
    for i, layer in enumerate(tree["layers"]):
        out.update({f"layers.{i}.{name}": norm(v, (1, 2) if name.startswith(
            "experts_") else None) for name, v in layer.items()})
    return out


_leaf_norms_jit = jax.jit(leaf_norms)
_zeros_jit = jax.jit(lambda p: jax.tree.map(jnp.zeros_like, p))
_diff_norms_jit = jax.jit(lambda a, b: leaf_norms(
    jax.tree.map(jnp.subtract, a, b)))


@functools.partial(jax.jit, static_argnames=("key_",))
def _init_from_key(key, key_):
    return init_weights(dict(key_), key)


def init_on_device(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """The seed's weights, made on the device in one jitted call."""
    return _init_from_key(seed_key(seed), cfg_key(cfg))


def init_params(cfg: Dict[str, Any], seed: int, seq_len: int):
    """``(weights, bias)``: the seed's weights and the expert bias that
    balances them."""
    weights = init_on_device(cfg, seed)
    return weights, calibrate_bias(cfg, weights, seed, seq_len)


def follow(cfg: Dict[str, Any], train: Dict[str, Any], seed: int,
           tokens: np.ndarray, precision: str = "float32", rows_used: int = 0,
           bias=None, biased_weights: bool = False, capacity: bool = False,
           no_conv_gate: bool = False) -> Dict[str, Any]:
    """Train from the seed's weights one step on each of ``tokens[step]`` and
    report what ``correct.training_gaps`` reads, as ``gpt_plain.follow``
    does. ``bias`` is the expert bias the program was given (computed here
    from the seed where it is not given); the optimizer does not move it.

    ``rows_used`` > 0 plants the "half of the tokens left out" fault; the
    three flags plant the faults of ``FAULTS``."""
    flags = dict(biased_weights=biased_weights, capacity=capacity,
                 no_conv_gate=no_conv_gate)
    faults = tuple(name for name in FAULTS if flags[name])
    params = init_on_device(cfg, seed)
    if bias is None:
        bias = calibrate_bias(cfg, params, seed, tokens.shape[-1] - 1)
    bias = jnp.asarray(bias, jnp.float32)
    hp = dict(lr=float(train["learning_rate"]), b1=float(train["adam_b1"]),
              b2=float(train["adam_b2"]), eps=float(train["adam_eps"]))
    grad, adam = _jitted()
    m, v = _zeros_jit(params), _zeros_jit(params)
    losses, g1_norms = [], None
    for s in range(tokens.shape[0]):
        rows = jnp.asarray(tokens[s])
        loss, g = grad(params, bias, rows[:, :-1], rows[:, 1:],
                       cfg_key=cfg_key(cfg), precision=precision,
                       rows_used=int(rows_used), faults=faults)
        losses.append(float(loss))
        if s == 0:
            g1_norms = _leaf_norms_jit(g)
        params, m, v = adam(params, m, v, g, jnp.asarray(s, jnp.int32), **hp)
        del g
    m_norms = _leaf_norms_jit(m)
    del m, v
    dp_norms = _diff_norms_jit(params, init_on_device(cfg, seed))
    del params
    to_np = lambda d: {k: np.asarray(x, np.float64) for k, x in d.items()}
    return {"losses": np.asarray(losses, np.float64),
            "g1_norms": to_np(g1_norms), "m_norms": to_np(m_norms),
            "dp_norms": to_np(dp_norms)}
