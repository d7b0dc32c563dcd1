"""Quantized serving tests (nn/quantize.py + the nn/kvpool.py
quantized paged KV pool + the registry quality gate — ISSUE 14).

The numeric contract under test: the quantized lane is EXACT versus
itself — greedy tokens bitwise-reproducible across runs, fused ==
eager, invariant to coalescing/preemption/cotenants, the house
determinism bar — while being only bounded-delta versus fp32 (the
accuracy gate's thresholds are the bound). Plus the plumbing
invariants: per-output-channel weight quantization round-trips within
its grid, a quantized pool never shares a spec with an fp32 one, its
block bytes land in the 2-4x compression band, shared/COW quantized
blocks carry their scales through clone/preempt/retire with zero
leaks, the registry charges a quantized version its ACTUAL pinned
bytes, a quality-gated deploy rejects a bad candidate while the
stable keeps serving, zero steady-state compiles on warmed quantized
ladders, and the dl4j_quant_* schema is pinned.
"""

import sys

import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.models.zoo.transformer import gpt
from deeplearning4j_tpu.nn.generate import generate, generate_eager
from deeplearning4j_tpu.nn.kvpool import PagedKVCachePool, pool_spec
from deeplearning4j_tpu.nn.quantize import (QSCALE, accuracy_gate,
                                            dequantize_array, kv_dequantize,
                                            kv_quantize, make_quality_gate,
                                            quantize, quantize_array,
                                            quantized_param_bytes)
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.serving.continuous import ContinuousDecodeScheduler
from deeplearning4j_tpu.serving.registry import (ModelRegistry,
                                                 QualityGateFailed)

VOCAB = 11


def _tiny_gpt(seed=0, **kw):
    return gpt(vocab_size=VOCAB, d_model=16, n_layers=2, num_heads=2,
               max_len=32, compute_dtype="float32", learning_rate=0.01,
               seed=seed, **kw).init()


@pytest.fixture
def fresh_registry():
    prev = monitor.set_registry(monitor.MetricsRegistry())
    yield monitor.get_registry()
    monitor.set_registry(prev)


def _sched(net, **kw):
    kw.setdefault("slots", 4)
    kw.setdefault("burst_tokens", 4)
    kw.setdefault("block_size", 4)
    kw.setdefault("start", False)
    kw.setdefault("kv_quant", "int8")
    return ContinuousDecodeScheduler(net=net, **kw)


def _drive(sched, futures, max_steps=300):
    for _ in range(max_steps):
        if all(f.done() for f in futures):
            return
        sched.step()
    raise AssertionError(
        f"schedule did not converge in {max_steps} steps; "
        f"events={list(sched.events)}")


# ---------------------------------------------------- weight quantization

def test_quantize_array_roundtrip(rng):
    w = jnp.asarray(rng.standard_normal((32, 8)), jnp.float32)
    q, sc = quantize_array(w, "int8")
    assert q.dtype == jnp.int8 and sc.dtype == jnp.float32
    assert sc.shape == (8,)
    # per-channel int8: error bounded by half a quantization step
    err = np.abs(np.asarray(dequantize_array(q, sc)) - np.asarray(w))
    assert np.all(err <= np.asarray(sc)[None, :] * 0.5 + 1e-7)
    q8, sc8 = quantize_array(w, "fp8")
    assert q8.dtype == jnp.float8_e4m3fn
    with pytest.raises(ValueError):
        quantize_array(w, "int4")


def test_quantize_net_layout_and_footprint(rng):
    net = _tiny_gpt()
    q = quantize(net, "int8")
    # same layer/param names + _qscale companions; storage is int8
    blk = q.params["layer1"]
    for name in ("Wqkv", "Wo", "W1", "W2"):
        assert blk[name].dtype == jnp.int8
        assert blk[name + QSCALE].dtype == jnp.float32
    assert q.params["layer0"]["W"].dtype == jnp.int8       # embedding
    assert q.params["layer0"]["P"].dtype == jnp.float32    # positions stay
    assert q.params["layer3"]["W"].dtype == jnp.int8       # output head
    # the byte win the registry budget sees (scales cost a little back)
    ratio = quantized_param_bytes(net.params) / quantized_param_bytes(
        q.params)
    assert ratio > 2.0
    assert q.quantized == "int8"
    # the original net is untouched and a quantized net cannot re-quantize
    assert net.params["layer1"]["Wqkv"].dtype == jnp.float32
    with pytest.raises(ValueError):
        quantize(q, "int8")
    # serving-only: fit refuses quantized weights loudly
    with pytest.raises(ValueError, match="quantized"):
        q.fit(np.zeros((2, 4), np.float32), np.zeros((2, 4, VOCAB),
                                                     np.float32))


def test_quantized_classify_and_generate_self_exact(rng):
    """The house bar inside the quantized contract: bitwise-identical
    outputs across runs, fused decode == eager decode, bounded delta
    vs fp32."""
    net = _tiny_gpt()
    q = quantize(net, "int8")
    x = rng.integers(0, VOCAB, (3, 9)).astype(np.float32)
    o1 = np.asarray(q.output(x))
    o2 = np.asarray(q.output(x))
    np.testing.assert_array_equal(o1, o2)
    # bounded vs fp32 (classify probabilities)
    of = np.asarray(net.output(x))
    assert float(np.max(np.abs(o1 - of))) < 0.05
    prompt = rng.integers(1, VOCAB, (2, 6))
    a = generate(q, prompt, 10, seed=3)
    b = generate(q, prompt, 10, seed=3)
    e = generate_eager(q, prompt, 10, seed=3)
    np.testing.assert_array_equal(a, b)
    np.testing.assert_array_equal(a, e)
    # sampled draws too (per-row PRNG schedule is quantization-blind)
    s1 = generate(q, prompt, 10, temperature=1.3, top_k=5, seed=9)
    s2 = generate_eager(q, prompt, 10, temperature=1.3, top_k=5, seed=9)
    np.testing.assert_array_equal(s1, s2)


def test_fp8_generate_self_exact(rng):
    q = quantize(_tiny_gpt(), "fp8")
    prompt = rng.integers(1, VOCAB, (1, 5))
    a = generate(q, prompt, 8, seed=1)
    b = generate_eager(q, prompt, 8, seed=1)
    np.testing.assert_array_equal(a, b)


# -------------------------------------------------------- quantized pool

def test_quantized_pool_spec_layout_and_bytes():
    pool = PagedKVCachePool(9, 4, num_layers=2, num_heads=2, head_dim=8,
                            quant="int8", name="q")
    ref = PagedKVCachePool(9, 4, num_layers=2, num_heads=2, head_dim=8,
                           name="f")
    # a quantized pool NEVER shares a spec with an fp32 one
    assert pool.spec != ref.spec
    assert pool.spec == pool_spec(2, 2, 8, 4, jnp.float32, "int8")
    entry = pool.layers[0]
    assert entry["k"].dtype == jnp.int8
    assert entry["k_scale"].shape == (9, 4, 2)
    assert entry["k_scale"].dtype == jnp.float32
    # the 2-4x compression band (hd=8: 4*8/(8+4) = 2.67x)
    ratio = ref.block_bytes() / pool.block_bytes()
    assert 2.0 <= ratio <= 4.0
    assert pool.stats()["quant"] == "int8"
    # byte-budget sizing: same budget, ~ratio x the blocks
    bb_f = PagedKVCachePool.bytes_per_block(2, 4, 2, 8)
    bb_q = PagedKVCachePool.bytes_per_block(2, 4, 2, 8, quant="int8")
    assert bb_f == ref.block_bytes() and bb_q == pool.block_bytes()


def test_kv_quantize_dequantize_bounds(rng):
    x = jnp.asarray(rng.standard_normal((3, 5, 2, 8)) * 4.0, jnp.float32)
    q, sc = kv_quantize(x, jnp.int8)
    assert q.shape == x.shape and sc.shape == (3, 5, 2)
    back = np.asarray(kv_dequantize(q, sc, jnp.float32))
    err = np.abs(back - np.asarray(x))
    assert np.all(err <= np.asarray(sc)[..., None] * 0.5 + 1e-7)
    # zeros stay exactly zero (the unwritten-position property)
    qz, scz = kv_quantize(jnp.zeros((2, 2, 4)), jnp.int8)
    assert np.all(np.asarray(kv_dequantize(qz, scz, jnp.float32)) == 0.0)


# ---------------------------------------- scheduler: the quantized lane

def test_quantized_lane_serves_exact_vs_eager(rng):
    net = _tiny_gpt()
    q = quantize(net, "int8")
    s = _sched(q)
    prompts = [rng.integers(1, VOCAB, (1, t)) for t in (3, 5, 7)]
    futs = [s.submit(p, 10, seed=i) for i, p in enumerate(prompts)]
    _drive(s, futs)
    for i, (p, f) in enumerate(zip(prompts, futs)):
        np.testing.assert_array_equal(
            f.result(0), generate_eager(q, p, 10, seed=i))
    st = s.stats()
    assert st["kv_quant"] == "int8"
    assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]


def test_quantized_pool_preempt_resume_invariant(rng):
    """Preemption on a quantized pool: the per-token scale granularity
    makes a resume's re-prefill store bit-identical blocks, so the
    preempted run's tokens equal the unpreempted run's exactly."""
    net = _tiny_gpt()
    q = quantize(net, "int8")
    prompts = [rng.integers(1, VOCAB, (1, t)) for t in (3, 5, 7)]
    big = _sched(q)
    fb = [big.submit(p, 10, temperature=1.1, seed=i)
          for i, p in enumerate(prompts)]
    _drive(big, fb)
    tiny = _sched(q, num_blocks=9)
    ft = [tiny.submit(p, 10, temperature=1.1, seed=i)
          for i, p in enumerate(prompts)]
    _drive(tiny, ft)
    assert tiny.stats()["preemptions"] >= 1
    for a, b in zip(fb, ft):
        np.testing.assert_array_equal(a.result(0), b.result(0))
    st = tiny.stats()["pool"]
    assert st["blocks_free"] == st["blocks_total"]


def test_quantized_prefix_cache_share_and_cow_bitwise(rng):
    """Shared + COW'd quantized blocks carry their scales: cached
    admissions (full-block shares AND a partial-tail COW) produce
    bitwise the tokens an uncached quantized run produces, and the
    pool drains with zero leaks."""
    net = _tiny_gpt()
    q = quantize(net, "int8")
    cached = _sched(q, prefix_cache=True)
    # shared-preamble fan-out: full-block shares
    pre = rng.integers(1, VOCAB, (1, 10))
    tails = [rng.integers(1, VOCAB, (1, 3)) for _ in range(3)]
    full = [np.concatenate([pre, t], axis=1) for t in tails]
    fc = []
    for i, p in enumerate(full):
        fc.append(cached.submit(p, 8, seed=50 + i))
        _drive(cached, fc)
    assert cached.stats()["prefix_cache"]["hits"] >= 1
    for a, p, i in zip(fc, full, range(len(full))):
        np.testing.assert_array_equal(
            a.result(0), generate_eager(q, p, 8, seed=50 + i))
    # COW: B = A's prompt + its first generated token — the match
    # reaches INTO A's cached partial tail block, whose int8 values AND
    # scale rows must clone together for B to decode bitwise
    pA = rng.integers(1, VOCAB, (1, 10))
    wantA = generate_eager(q, pA, 2)
    fA = cached.submit(pA, 2)
    _drive(cached, [fA])
    np.testing.assert_array_equal(fA.result(0), wantA)
    pB = np.concatenate([pA, wantA[:, 10:11]], axis=1)
    wantB = generate_eager(q, pB, 6)
    fB = cached.submit(pB, 6)
    _drive(cached, [fB])
    np.testing.assert_array_equal(fB.result(0), wantB)
    st = cached.stats()["prefix_cache"]
    assert st["cow_copies"] >= 1
    # the originator's cached content survived the COW untouched
    fA2 = cached.submit(pA, 2)
    _drive(cached, [fA2])
    np.testing.assert_array_equal(fA2.result(0), wantA)
    for c in cached.prefix_caches():
        c.clear()
    ps = cached.stats()["pool"]
    assert ps["blocks_free"] == ps["blocks_total"]
    assert ps["alloc_failures"] == 0


def test_quantized_engine_zero_steady_state_compiles(rng, fresh_registry):
    net = _tiny_gpt()
    q = quantize(net, "int8")
    eng = ParallelInference(q, replicas=1, continuous=True,
                            decode_slots=4, decode_burst=4,
                            kv_block_size=4, kv_quant="int8")
    try:
        eng.warmup_generate([3, 5, 7], 10)
        before = fresh_registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
        futs = [eng.submit_generate(rng.integers(1, VOCAB, (1, t)), 10,
                                    temperature=tmp, seed=i)
                for i, (t, tmp) in enumerate(
                    [(3, 0.0), (5, 1.2), (7, 0.0), (4, 0.8)])]
        for f in futs:
            f.result(30)
        after = fresh_registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
        assert after == before, f"{after - before} steady-state compiles"
        assert eng.stats()["scheduler"]["kv_quant"] == "int8"
    finally:
        eng.shutdown()


def test_engine_kv_quant_needs_continuous():
    net = _tiny_gpt()
    with pytest.raises(ValueError, match="continuous"):
        ParallelInference(net, kv_quant="int8", start=False)
    with pytest.raises(ValueError, match="exclusive"):
        ContinuousDecodeScheduler(net=net, start=False, num_blocks=9,
                                  kv_bytes_budget=1 << 20)
    with pytest.raises(ValueError, match="kv_quant"):
        ContinuousDecodeScheduler(net=net, start=False, kv_quant="int4")


def test_kv_bytes_budget_buys_more_quantized_blocks(rng):
    net = _tiny_gpt()
    q = quantize(net, "int8")
    budget = 24 * PagedKVCachePool.bytes_per_block(2, 4, 2, 8)
    s_f = ContinuousDecodeScheduler(net=net, slots=4, burst_tokens=4,
                                    block_size=4, start=False,
                                    kv_bytes_budget=budget)
    s_q = ContinuousDecodeScheduler(net=q, slots=4, burst_tokens=4,
                                    block_size=4, start=False,
                                    kv_quant="int8",
                                    kv_bytes_budget=budget)
    f = s_f.submit(rng.integers(1, VOCAB, (1, 4)), 2)
    _drive(s_f, [f])
    g = s_q.submit(rng.integers(1, VOCAB, (1, 4)), 2)
    _drive(s_q, [g])
    bf = s_f.stats()["pool"]["blocks_total"]
    bq = s_q.stats()["pool"]["blocks_total"]
    assert bq >= 2 * bf, (bf, bq)


# ------------------------------------------- registry: gate + pinned bytes

def test_accuracy_gate_passes_self_and_fails_garbage(fresh_registry):
    net = _tiny_gpt()
    g = accuracy_gate(net, net, rows=4, length=12)
    assert g["passed"] and g["greedy_match_rate"] == 1.0
    assert g["logit_mse"] == 0.0
    other = _tiny_gpt(seed=123)  # a different model is NOT within bounds
    g2 = accuracy_gate(net, other, rows=4, length=12)
    assert not g2["passed"]
    text = fresh_registry.prometheus_text()
    assert "dl4j_quant_accuracy_gate_outcome_total" in text


def test_registry_quality_gate_and_actual_pinned_bytes(rng,
                                                      fresh_registry):
    import jax

    net = _tiny_gpt()
    q = quantize(net, "int8")
    registry = ModelRegistry()
    registry.register("m", net=net)
    # a bad candidate (different weights entirely) is rejected BEFORE
    # any traffic shifts; the stable version keeps serving
    bad = _tiny_gpt(seed=99)
    with pytest.raises(QualityGateFailed) as ei:
        registry.deploy("m", net=bad, warm=False,
                        quality_gate=make_quality_gate(rows=4, length=12))
    assert ei.value.verdict is not None
    assert registry.active_version("m") == 1
    assert registry.versions("m") == {1: "active"}
    # the quantized candidate passes its gate (loose thresholds — the
    # tiny random-init net's flat logits are not the gate's regime;
    # bench gates the trained net at the tight production thresholds)
    v2 = registry.deploy("m", net=q, warm=False,
                         quality_gate=make_quality_gate(
                             rows=4, length=12, min_greedy_match=0.5,
                             max_eval_delta=0.05))
    assert registry.active_version("m") == v2
    # pinned-bytes satellite: the pin charges the ACTUAL pytree bytes —
    # the quantized version pins ~4x fewer weight bytes than fp32
    dev = jax.devices()[0]
    registry.acquire("m", 1, dev)
    fp32_pinned = registry.pinned_bytes()
    registry.acquire("m", v2, dev)
    q_pinned = registry.pinned_bytes() - fp32_pinned
    assert 0 < q_pinned < fp32_pinned / 2, (q_pinned, fp32_pinned)
    # unpin releases exactly what was charged
    registry._unpin_all(registry.version("m", 1))
    registry._unpin_all(registry.version("m", v2))
    assert registry.pinned_bytes() == 0
    # a quantized CANARY rides the same gate + the PR-7 watch plane
    q2 = quantize(net, "fp8")
    v3 = registry.deploy("m", net=q2, warm=False, canary_fraction=0.5,
                         quality_gate=make_quality_gate(
                             rows=4, length=12, min_greedy_match=0.5,
                             max_eval_delta=0.05))
    assert registry.versions("m")[v3] == "canary"
    assert registry.active_version("m") == v2  # stable still active
    registry.rollback("m", reason="manual")    # reject the canary
    assert registry.versions("m")[v3] == "rejected"
    assert registry.active_version("m") == v2
    # deploy outcomes + rollback reason counted
    text = fresh_registry.prometheus_text()
    assert 'outcome="rejected_quality"' in text
    assert 'reason="quality_gate"' in text


# ------------------------------------------------------- schema pinning

def test_quant_metric_schema_pinned(rng, fresh_registry):
    sys.path.insert(0, "scripts")
    try:
        from check_telemetry_schema import (KNOWN_DL4J_METRICS,
                                            validate_known_metrics,
                                            validate_prometheus_text)
    finally:
        sys.path.pop(0)
    for name in ("dl4j_quant_models", "dl4j_quant_kv_blocks",
                 "dl4j_quant_scale_absmax",
                 "dl4j_quant_accuracy_gate_outcome_total"):
        assert name in KNOWN_DL4J_METRICS, name
    net = _tiny_gpt()
    q = quantize(net, "int8")
    accuracy_gate(net, q, rows=2, length=8)
    s = _sched(q)
    f = s.submit(rng.integers(1, VOCAB, (1, 4)), 4)
    _drive(s, [f])
    text = fresh_registry.prometheus_text()
    assert validate_prometheus_text(text) == []
    assert validate_known_metrics(text) == []
    for family in ("dl4j_quant_models", "dl4j_quant_kv_blocks",
                   "dl4j_quant_scale_absmax",
                   "dl4j_quant_accuracy_gate_outcome_total"):
        assert family in text, family


def test_quick_check_section_10_runs():
    """The stress battery's quantized-pool section exists and the whole
    battery stays deterministic (tier-1 runs quick_check elsewhere too;
    this pins that section 10's events are part of the replayed log)."""
    sys.path.insert(0, "scripts")
    try:
        from stress_faultinject import _scenario_log, quick_check
    finally:
        sys.path.pop(0)
    log = _scenario_log(0)
    assert "qkv spec_differs=True" in log
    assert "qkv double-free caught" in log
    assert "leaked=0" in log
    assert quick_check(seeds=(0,), runs_per_seed=2) == []
