"""Post-training quantization for serving: int8/fp8 weights with
on-the-fly dequant, and the accuracy-delta gate that ships with them.

The serving path is bandwidth-bound (``gemm_bf16`` runs at 0.83 MFU
while decode-side attention sits ~0.25): every generated token moves
the whole weight set and the whole KV cache through HBM, so halving or
quartering the *bytes* is worth more than any FLOP trick. This module
is the weights half of that arc (nn/kvpool.py carries the KV half):

- :func:`quantize` — the LLM.int8()/AWQ per-output-channel recipe as a
  pure post-training pass: ``quantize(net, dtype="int8")`` returns a
  NEW net (same conf, same layer names) whose Dense / Embedding /
  TransformerBlock projection matrices are stored as int8 (or
  fp8-e4m3) alongside float32 per-output-channel scales under
  ``<name>_qscale`` keys. Biases, LayerNorm affines and positional
  tables stay float32 — they are tiny and precision-critical.
- :func:`qmatmul` / :func:`qtake` — the dequant *fused into the op*:
  ``(x @ w_int8) * scale`` (the per-output-channel scale commutes with
  the contraction, so compute stays bf16/f32 while HBM moves int8
  bytes) and ``take(w_int8, ids) * scale`` for embedding gathers. The
  layer impls call these unconditionally; an unquantized weight falls
  straight through to the original matmul/gather, so every existing
  program — forward, prefill, prefill_paged, decode_step, the whole
  compiled ladder — is byte-identical when nothing is quantized.
- :func:`kv_quantize` / :func:`kv_dequantize` — the paged-pool
  quantization primitive: per-(position, head) scales (amax over
  head_dim). Per-token granularity is deliberate: a block written
  incrementally by decode steps and the same block re-written by a
  resume's prefill scatter quantize IDENTICALLY, which is what keeps
  the preempt/resume and prefix-cache bitwise-replay contracts alive
  on a quantized pool (a per-block running scale would re-quantize
  history and diverge).
- :func:`accuracy_gate` — the quality bound the perf claim ships
  with: teacher-forced greedy token match rate + logit MSE +
  next-token cross-entropy delta vs the fp32 net on a fixed seeded
  workload, with pass/fail thresholds. ``make_quality_gate`` adapts it
  to the ``ModelRegistry.deploy(quality_gate=...)`` seam so a
  quantized canary is arbitrated by measured quality.

Numeric contract (MIGRATION.md "Quantized serving"): the quantized
lane is EXACT versus itself — greedy tokens are bitwise-reproducible
across runs and invariant to coalescing/preemption/cotenants, the
house determinism bar — but only bounded-delta versus fp32 (the gate's
thresholds are the bound). Quantized nets are serving-only: the round()
in the weights has no useful gradient, so ``fit`` refuses them.
"""

from __future__ import annotations

from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.monitor import (
    QUANT_GATE_OUTCOME_COUNTER,
    QUANT_MODELS_GAUGE,
    QUANT_SCALE_ABSMAX_GAUGE,
    get_registry,
)

#: params-dict suffix marking a weight as quantized: ``params["W"]`` is
#: the int8/fp8 array and ``params["W" + QSCALE]`` its float32
#: per-output-channel scale vector.
QSCALE = "_qscale"

#: supported storage modes -> (jnp storage dtype, quantization max).
#: int8 is symmetric round-to-nearest at +-127; fp8 uses the e4m3 grid
#: (max normal 448) — "fp8-emulated" on backends without native fp8
#: matmul: storage/HBM is 1 byte/weight, compute upcasts on the fly.
_MODES: Dict[str, Tuple[Any, float]] = {
    "int8": (jnp.int8, 127.0),
    "fp8": (jnp.float8_e4m3fn, 448.0),
}

_quantized_nets: Dict[str, int] = {}


def quant_modes() -> Tuple[str, ...]:
    return tuple(sorted(_MODES))


def is_quantized(params: Dict[str, Any], name: str) -> bool:
    return (name + QSCALE) in params


def qmatmul(x, params: Dict[str, Any], name: str):
    """``x @ params[name]`` with on-the-fly dequant when the weight is
    quantized: the int8/fp8 matrix upcasts to ``x.dtype`` inside the
    program (HBM reads stay 1 byte/weight) and the per-output-channel
    scale lands as a fused post-multiply — ``(x @ q) * s`` equals
    ``x @ (q * s)`` exactly because the scale is constant along the
    contraction. Unquantized weights take the original path (matching
    the ``W.astype(x.dtype)`` idiom of every call site) bit for bit."""
    w = params[name]
    sc = params.get(name + QSCALE)
    y = x @ w.astype(x.dtype)
    if sc is None:
        return y
    return y * sc.astype(y.dtype)


def qtake(params: Dict[str, Any], name: str, idx, out_dtype=None):
    """Embedding gather with on-the-fly dequant: rows gather in storage
    precision (1 byte/row-element when quantized), then scale
    per-output-channel. ``out_dtype`` pins the result dtype for the
    quantized path (defaults to the scale's dtype); unquantized weights
    gather exactly as before."""
    w = params[name]
    z = jnp.take(w, idx, axis=0)
    sc = params.get(name + QSCALE)
    if sc is None:
        return z
    dt = out_dtype if out_dtype is not None else sc.dtype
    return z.astype(dt) * sc.astype(dt)


def quantize_array(w, mode: str = "int8"):
    """Per-output-channel quantization of one ``[in, out]`` matrix (or
    ``[vocab, d]`` embedding): scale[j] = amax(|w[:, j]|) / qmax, the
    LLM.int8() vector-wise recipe. Returns (q, scale_f32)."""
    if mode not in _MODES:
        raise ValueError(f"unknown quantization dtype {mode!r}; pick "
                         f"from {quant_modes()}")
    storage, qmax = _MODES[mode]
    wf = jnp.asarray(w, jnp.float32)
    if wf.ndim != 2:
        raise ValueError(f"per-channel quantization needs a 2-D matrix, "
                         f"got shape {wf.shape}")
    sc = jnp.maximum(jnp.max(jnp.abs(wf), axis=0) / qmax, 1e-12)
    if storage == jnp.int8:
        q = jnp.clip(jnp.round(wf / sc), -qmax, qmax).astype(jnp.int8)
    else:
        q = (wf / sc).astype(storage)
    return q, sc.astype(jnp.float32)


def dequantize_array(q, sc):
    """The reference inverse of :func:`quantize_array` (test oracle)."""
    return q.astype(jnp.float32) * sc.astype(jnp.float32)


# ----------------------------------------------------- KV-pool primitive


def kv_qparams(mode: str) -> Tuple[Any, float]:
    """(storage dtype, qmax) for a quantized KV pool mode."""
    if mode not in _MODES:
        raise ValueError(
            f"unknown KV quantization mode {mode!r}; pick from "
            f"{quant_modes()}")
    return _MODES[mode]


def kv_qmax(storage_dtype) -> float:
    """Quantization max for a KV storage dtype (static at trace time —
    the pool arrays' dtype IS the mode, no extra pytree leaf needed)."""
    dt = jnp.dtype(storage_dtype)
    if dt == jnp.dtype(jnp.int8):
        return 127.0
    if dt == jnp.dtype(jnp.float8_e4m3fn):
        return 448.0
    raise ValueError(f"not a quantized KV storage dtype: {dt}")


def kv_quantize(x, storage_dtype, qmax: Optional[float] = None):
    """Quantize K/V values with a per-(…, head) scale over the trailing
    head_dim axis: ``x`` is ``[..., h, hd]``, the scale is ``[..., h]``
    float32. Traced-code only (runs inside scatter/burst programs).
    Per-token scales make quantization a pure elementwise function of
    the written values — a resume's prefill re-quantizes bit-identically
    to the original incremental decode writes, the property every
    replay/preemption contract on the pool depends on. The scale floor
    keeps unwritten/zero positions exactly zero after dequant."""
    if qmax is None:
        qmax = kv_qmax(storage_dtype)
    xf = x.astype(jnp.float32)
    sc = jnp.maximum(jnp.max(jnp.abs(xf), axis=-1) / qmax, 1e-12)
    scaled = xf / sc[..., None]
    if storage_dtype == jnp.int8:
        q = jnp.clip(jnp.round(scaled), -qmax, qmax).astype(jnp.int8)
    else:
        q = scaled.astype(storage_dtype)
    return q, sc.astype(jnp.float32)


def kv_dequantize(q, sc, dtype):
    """Dequantize gathered K/V: ``q`` ``[..., h, hd]`` storage ints/fp8,
    ``sc`` ``[..., h]`` — back to the compute dtype for attention."""
    return q.astype(dtype) * sc[..., None].astype(dtype)


# ------------------------------------------------------- the net pass

#: which param names quantize per impl family. TransformerBlock MoE
#: expert tensors (3-D) and LSTM recurrences are out of scope — the
#: serving-transformer projections are where the bytes are.
_DENSE_NAMES = ("W",)
_TRANSFORMER_NAMES = ("Wqkv", "Wo", "W1", "W2")
_EMBED_NAMES = ("W",)


def _quant_targets(impl) -> Tuple[str, ...]:
    from deeplearning4j_tpu.nn.layers.feedforward import (BaseDenseImpl,
                                                          EmbeddingImpl)
    from deeplearning4j_tpu.nn.layers.transformer import (
        SequenceEmbeddingImpl, TransformerBlockImpl)
    if isinstance(impl, TransformerBlockImpl):
        return _TRANSFORMER_NAMES
    if isinstance(impl, (SequenceEmbeddingImpl, EmbeddingImpl)):
        return _EMBED_NAMES
    if isinstance(impl, BaseDenseImpl):
        return _DENSE_NAMES
    return ()


def _iter_impls(net) -> List[Any]:
    impls = net.impls
    if isinstance(impls, dict):
        return list(impls.values())
    return list(impls)


def quantized_param_bytes(params: Dict[str, Dict[str, Any]]) -> int:
    """Actual byte footprint of a params pytree (what the registry's
    pinned-bytes accounting charges a quantized version)."""
    total = 0
    for leaf in jax.tree.leaves(params):
        total += int(np.asarray(leaf).nbytes if not hasattr(leaf, "nbytes")
                     else leaf.nbytes)
    return total


def quantize(net, dtype: str = "int8"):
    """Post-training quantization pass: returns a NEW net with the same
    configuration and layer names whose Dense/Embedding/TransformerBlock
    projection weights are stored in ``dtype`` (``"int8"`` or
    ``"fp8"``) with float32 per-output-channel scales; every other
    parameter (biases, LayerNorms, positions, recurrences, MoE experts)
    stays float32. The result is a normal net — it serves through every
    existing engine/scheduler/registry path and deploys as a
    ``ModelRegistry`` version — but it is inference-only
    (``net.quantized`` is set and ``fit`` refuses it)."""
    if dtype not in _MODES:
        raise ValueError(f"unknown quantization dtype {dtype!r}; pick "
                         f"from {quant_modes()}")
    if net.params is None:
        raise ValueError("quantize() needs an initialized net (params)")
    if getattr(net, "quantized", None) is not None:
        raise ValueError(
            f"net is already quantized ({net.quantized}); re-quantizing "
            "quantized weights compounds the error — quantize the fp32 "
            "original")
    clone = type(net)(net.conf)
    clone.init(dtype=net._dtype)
    reg = get_registry()
    new_params: Dict[str, Dict[str, Any]] = {}
    by_name = {impl.name: impl for impl in _iter_impls(clone)}
    for lname, p in net.params.items():
        impl = by_name.get(lname)
        targets = _quant_targets(impl) if impl is not None else ()
        q: Dict[str, Any] = {}
        for pname, v in p.items():
            if pname in targets and getattr(v, "ndim", 0) == 2:
                qv, sc = quantize_array(v, dtype)
                q[pname] = qv
                q[pname + QSCALE] = sc
                reg.gauge(
                    QUANT_SCALE_ABSMAX_GAUGE,
                    "Largest per-output-channel dequant scale per "
                    "quantized weight matrix",
                    layer=lname, param=pname).set(
                        float(jnp.max(sc)))
            else:
                q[pname] = v
        new_params[lname] = q
    clone.params = new_params
    clone.states = jax.tree.map(lambda v: v, net.states) \
        if net.states is not None else None
    clone.quantized = dtype
    _quantized_nets[dtype] = _quantized_nets.get(dtype, 0) + 1
    reg.gauge(QUANT_MODELS_GAUGE,
              "Quantized nets produced by quantize() in this process",
              dtype=dtype).set(float(_quantized_nets[dtype]))
    return clone


# -------------------------------------------------- accuracy-delta gate


def _sequence_logits(net, ids: np.ndarray) -> np.ndarray:
    """Teacher-forced per-position next-token logits [b, t, V] (f32)
    from ONE causal forward — the workhorse of the gate: both nets see
    identical contexts at every position, so one token flip never
    compounds into a diverged rollout."""
    from deeplearning4j_tpu.nn.generate import (TransformerGenerator,
                                                build_generator)
    from deeplearning4j_tpu.util.dtypes import cast_floats

    gen = build_generator(net)
    if not isinstance(gen, TransformerGenerator):
        raise ValueError("accuracy_gate scores transformer stacks; got "
                         f"{type(gen).__name__}")
    cd = net._cd
    cast = (lambda p: cast_floats(p, cd)) if cd is not None else (lambda p: p)

    key = ("quant_gate_logits", ids.shape[1])
    fn = net._jits.get(key)
    if fn is None:
        def logits_fn(params, ids_d):
            x, _ = gen.emb.forward(cast(params[gen.emb.name]), ids_d,
                                   {}, False)
            for blk in gen.blocks:
                x, _ = blk.forward(cast(params[blk.name]), x,
                                   blk.init_state(), False)
            p = cast(params[gen.head.name])
            if hasattr(gen.head, "preout"):
                return gen.head.preout(p, x).astype(jnp.float32)
            return x.astype(jnp.float32)
        fn = net._jits[key] = jax.jit(logits_fn)
    return np.asarray(fn(net.params, jnp.asarray(ids, jnp.int32)))


def _xent(logits: np.ndarray, targets: np.ndarray) -> float:
    """Mean next-token cross-entropy of [b, t, V] logits against the
    [b, t] shifted targets (positions 0..t-2 predict 1..t-1)."""
    lg = logits[:, :-1].astype(np.float64)
    tg = targets[:, 1:]
    m = lg.max(axis=-1, keepdims=True)
    lse = m[..., 0] + np.log(np.exp(lg - m).sum(axis=-1))
    picked = np.take_along_axis(lg, tg[..., None], axis=-1)[..., 0]
    return float(np.mean(lse - picked))


def gate_workload(vocab: int, rows: int = 8, length: int = 24,
                  seed: int = 0) -> np.ndarray:
    """The FIXED seeded token workload the canary gate scores on: same
    seed ⇒ same ids ⇒ the gate verdict is a pure function of the two
    nets."""
    rng = np.random.default_rng(seed)
    return rng.integers(1, vocab, (rows, length)).astype(np.int32)


def accuracy_gate(ref_net, cand_net, ids: Optional[np.ndarray] = None, *,
                  rows: int = 8, length: int = 24, seed: int = 0,
                  min_greedy_match: float = 0.995,
                  max_eval_delta: float = 0.005,
                  max_logit_mse: Optional[float] = None
                  ) -> Dict[str, Any]:
    """Accuracy-delta harness: score ``cand_net`` against ``ref_net``
    on a fixed seeded workload (or explicit ``ids`` [b, t]) and apply
    the thresholds. Returns::

        {"passed": bool, "greedy_match_rate": …, "logit_mse": …,
         "eval_metric": …, "eval_metric_ref": …, "eval_metric_delta": …,
         "positions": n, "thresholds": {...}}

    - **greedy_match_rate** — fraction of teacher-forced positions
      where both nets' argmax token agrees (the serving-visible
      metric: greedy decode flips exactly where this flips);
    - **logit_mse** — mean squared logit delta (drift magnitude even
      where the argmax survives);
    - **eval_metric_delta** — relative next-token cross-entropy change
      (the "eval metric" of a language model workload).

    The outcome ticks ``dl4j_quant_accuracy_gate_outcome_total``."""
    if ids is None:
        vocab = int(_iter_impls(ref_net)[0].conf.n_in)
        ids = gate_workload(vocab, rows=rows, length=length, seed=seed)
    ids = np.asarray(ids, np.int32)
    lr = _sequence_logits(ref_net, ids)
    lq = _sequence_logits(cand_net, ids)
    match = float(np.mean(np.argmax(lr, -1) == np.argmax(lq, -1)))
    mse = float(np.mean((lr - lq) ** 2))
    xr = _xent(lr, ids)
    xq = _xent(lq, ids)
    delta = abs(xq - xr) / max(abs(xr), 1e-9)
    passed = match >= min_greedy_match and delta <= max_eval_delta
    if max_logit_mse is not None:
        passed = passed and mse <= max_logit_mse
    get_registry().counter(
        QUANT_GATE_OUTCOME_COUNTER,
        "Quantization accuracy-gate verdicts by outcome",
        outcome="pass" if passed else "fail").inc()
    return {
        "passed": bool(passed),
        "greedy_match_rate": round(match, 6),
        "logit_mse": mse,
        "eval_metric": round(xq, 6),
        "eval_metric_ref": round(xr, 6),
        "eval_metric_delta": round(delta, 6),
        "positions": int(lr.shape[0] * lr.shape[1]),
        "thresholds": {"min_greedy_match": min_greedy_match,
                       "max_eval_delta": max_eval_delta,
                       "max_logit_mse": max_logit_mse},
    }


def make_quality_gate(ids: Optional[np.ndarray] = None, *,
                      rows: int = 8, length: int = 24, seed: int = 0,
                      min_greedy_match: float = 0.995,
                      max_eval_delta: float = 0.005,
                      max_logit_mse: Optional[float] = None):
    """Adapter for ``ModelRegistry.deploy(quality_gate=...)``: the
    returned callable takes (stable_net_or_None, candidate_net) and
    returns the :func:`accuracy_gate` verdict dict (a candidate with no
    stable to compare against passes trivially — there is no reference
    to be bounded against)."""
    def gate(stable_net, cand_net) -> Dict[str, Any]:
        if stable_net is None:
            return {"passed": True, "greedy_match_rate": 1.0,
                    "logit_mse": 0.0, "eval_metric_delta": 0.0,
                    "skipped": "no stable version to compare against"}
        return accuracy_gate(
            stable_net, cand_net, ids, rows=rows, length=length,
            seed=seed, min_greedy_match=min_greedy_match,
            max_eval_delta=max_eval_delta, max_logit_mse=max_logit_mse)
    return gate
