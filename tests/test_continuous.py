"""Continuous batching tests (serving/continuous.py + nn/kvpool.py).

The ISSUE-8 battery: token-for-token parity vs ``generate_eager`` for
sequences admitted mid-stream, preempted + resumed, and served across
a PR-7 canary cutover (the session keeps its version); the
deterministic lowest-priority/youngest-first preemption order under a
tiny pool; the zero-steady-state-compile assertion via
``dl4j_jit_cache_miss_total``; pool accounting (free returns to total
after drain, typed exhaustion, bounded-queue shedding); the kill-mid-burst recovery contract; and the
``stats()`` / ``/healthz/ready`` scheduler-warmup gate + the
``dl4j_kvpool_*`` / ``dl4j_sched_*`` schema pinning.
"""

import json
import sys
import urllib.error
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.faultinject import BurstKill, InjectedFault
from deeplearning4j_tpu.models.zoo.transformer import gpt
from deeplearning4j_tpu.nn.generate import build_generator, generate_eager
from deeplearning4j_tpu.nn.kvpool import PagedKVCachePool
from deeplearning4j_tpu.parallel.inference import (InferenceBackpressure,
                                                   ParallelInference)
from deeplearning4j_tpu.serving.continuous import (
    ContinuousDecodeScheduler,
    DecodeBurstError,
    KVPoolExhausted,
)
from deeplearning4j_tpu.serving.registry import ModelRegistry

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
    return ContinuousDecodeScheduler(net=net, **kw)


def _drive(sched, futures, max_steps=200):
    for _ in range(max_steps):
        if all(f.done() for f in futures):
            return
        sched.step()
    raise AssertionError(
        f"schedule did not converge in {max_steps} steps; "
        f"events={list(sched.events)}")


def test_kvpool_accounting():
    pool = PagedKVCachePool(8, 4, num_layers=2, num_heads=2, head_dim=8)
    assert pool.total_blocks == 7 and pool.free_count == 7
    a = pool.alloc(3)
    assert a == [1, 2, 3] and pool.free_count == 4
    assert pool.alloc(5) is None  # exhausted: nothing claimed
    assert pool.free_count == 4 and pool.stats()["alloc_failures"] == 1
    pool.free_blocks(a)
    assert pool.free_count == 7
    assert pool.alloc(1) == [1]  # lowest-id-first: deterministic replay
    with pytest.raises(ValueError):
        pool.free_blocks([0])  # the trash block is never allocatable


# ------------------------------------------------------ parity battery

def test_staggered_admission_matches_eager(rng):
    """A request admitted MID-STREAM (slots already decoding) must be
    token-for-token identical to its solo eager run."""
    net = _tiny_gpt()
    s = _sched(net)
    p1 = rng.integers(0, VOCAB, (2, 5))
    f1 = s.submit(p1, 10)
    s.step()  # p1 admitted + first burst dispatched
    assert s.stats()["active_sequences"] == 2
    p2 = rng.integers(0, VOCAB, (1, 3))
    f2 = s.submit(p2, 6)  # arrives one burst after dispatch
    _drive(s, [f1, f2])
    assert np.array_equal(f1.result(0), generate_eager(net, p1, 10))
    assert np.array_equal(f2.result(0), generate_eager(net, p2, 6))
    st = s.stats()
    assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]


def test_eos_retires_between_bursts_and_fills(rng):
    """EOS rows retire between bursts (blocks freed immediately) and a
    finished row's remaining slots are filled with the EOS id — the
    whole-burst contract, kept."""
    net = _tiny_gpt()
    prompt = rng.integers(0, VOCAB, (2, 4))
    want = generate_eager(net, prompt, 12, eos_token=3)
    s = _sched(net)
    f = s.submit(prompt, 12, eos_token=3)
    _drive(s, [f])
    assert np.array_equal(f.result(0), want)
    st = s.stats()
    assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]


def test_preempt_resume_matches_eager(rng):
    """A pool too small for the offered load must preempt (blocks
    freed, victim re-queued with its generated prefix) and the resumed
    sequences must still match their uninterrupted eager runs."""
    net = _tiny_gpt()
    # 8 usable blocks of 4 tokens; three sequences growing to 15 tokens
    # each (4 blocks) cannot coexist
    s = _sched(net, num_blocks=9)
    prompts = [rng.integers(0, VOCAB, (1, 5)) for _ in range(3)]
    futs = [s.submit(p, 10) for p in prompts]
    _drive(s, futs)
    for f, p in zip(futs, prompts):
        assert np.array_equal(f.result(0), generate_eager(net, p, 10))
    st = s.stats()
    assert st["preemptions"] > 0
    assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]


def test_deterministic_preemption_order(rng):
    """The victim policy is lowest-priority first, youngest-admitted
    tie-break — and the whole schedule replays identically."""
    net = _tiny_gpt()
    prompts = [rng.integers(0, VOCAB, (1, 5)) for _ in range(3)]

    def run():
        s = _sched(net, num_blocks=9)
        futs = [s.submit(p, 10, priority=pr)
                for p, pr in zip(prompts, (5, 1, 1))]
        _drive(s, futs)
        return s, futs

    s1, futs1 = run()
    preempts = [e for e in s1.events if e.startswith("preempt")]
    assert preempts, "tiny pool must preempt"
    # seq_id 2 and 3 share the lowest priority (1); the YOUNGEST (3)
    # loses first, and seq 1 (priority 5) is never a victim
    assert preempts[0].startswith("preempt seq=3 prio=1")
    assert not any("seq=1 " in e for e in preempts)
    s2, futs2 = run()
    assert list(s1.events) == list(s2.events)
    for a, b in zip(futs1, futs2):
        assert np.array_equal(a.result(0), b.result(0))


def test_sampled_draws_invariant_to_cotenants(rng):
    """A temperature-sampled request's draws ride its own per-row PRNG
    clock: the same seed yields the same tokens whether it runs alone
    or crowded by cotenants (and across preemption-free replays)."""
    net = _tiny_gpt()
    p = rng.integers(0, VOCAB, (1, 4))
    s1 = _sched(net)
    f_alone = s1.submit(p, 8, temperature=0.8, top_k=5, seed=7)
    _drive(s1, [f_alone])
    s2 = _sched(net)
    crowd = [s2.submit(rng.integers(0, VOCAB, (1, 6)), 10, seed=i)
             for i in range(2)]
    f_crowded = s2.submit(p, 8, temperature=0.8, top_k=5, seed=7)
    _drive(s2, crowd + [f_crowded])
    assert np.array_equal(f_alone.result(0), f_crowded.result(0))


# -------------------------------------------- engine + canary cutover

def test_engine_routes_and_canary_cutover_session_pins(rng, fresh_registry):
    """``ParallelInference(continuous=True, registry=...)``: a decode
    session admitted on v1 keeps resolving v1 through a deploy (the
    KV blocks and programs live with the version), new sessions get
    v2, and both lanes share ONE block pool."""
    net1, net2 = _tiny_gpt(seed=1), _tiny_gpt(seed=9)
    reg = ModelRegistry()
    reg.register("lm", net=net1)
    eng = ParallelInference(registry=reg, replicas=1, continuous=True,
                            decode_slots=4, decode_burst=4, kv_block_size=4)
    try:
        p = rng.integers(0, VOCAB, (1, 5))
        assert np.array_equal(
            eng.submit_generate(p, 8, model="lm", session="s1").result(30),
            generate_eager(net1, p, 8))
        reg.deploy("lm", net=net2)  # atomic cutover to v2
        # same session: still v1 — a mid-stream hot-swap never switches
        # the KV-cache owner
        assert np.array_equal(
            eng.submit_generate(p, 8, model="lm", session="s1").result(30),
            generate_eager(net1, p, 8))
        # fresh session: the new active version
        assert np.array_equal(
            eng.submit_generate(p, 8, model="lm", session="s2").result(30),
            generate_eager(net2, p, 8))
        st = eng.stats()["scheduler"]
        assert st["lanes"] == 2 and len(st["pools"]) == 1
        assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]
    finally:
        eng.shutdown()


def test_zero_steady_state_compiles(rng, fresh_registry):
    """After ``warmup_generate`` the continuous path serves ANY request
    mix inside the warmed buckets with zero XLA compiles — the fixed
    (slots × K × max_blocks) burst shape is sequence-independent."""
    net = _tiny_gpt()
    eng = ParallelInference(net, replicas=1, continuous=True,
                            decode_slots=4, decode_burst=4, kv_block_size=4)
    try:
        compiled = eng.warmup_generate([2, 4, 8], 8)
        assert compiled > 0
        assert eng.stats()["scheduler"]["warmed"]
        miss0 = fresh_registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
        futs = [eng.submit_generate(rng.integers(0, VOCAB, (1, t)), mn,
                                    temperature=temp, seed=i)
                for i, (t, mn, temp) in enumerate(
                    [(3, 8, 0.0), (5, 4, 0.5), (8, 6, 0.0), (2, 3, 0.9)])]
        for f in futs:
            f.result(30)
        assert fresh_registry.family_total(
            monitor.JIT_CACHE_MISS_COUNTER) == miss0
    finally:
        eng.shutdown()


# ----------------------------------------------- shedding + exhaustion

def test_pool_exhausted_fails_typed(rng):
    """A sequence that cannot fit even alone fails fast and typed —
    never a deadlocked queue."""
    net = _tiny_gpt()
    s = _sched(net, num_blocks=3)  # 2 usable blocks = 8 tokens
    f = s.submit(rng.integers(0, VOCAB, (1, 10)), 8)
    for _ in range(5):
        if f.done():
            break
        s.step()
    with pytest.raises(KVPoolExhausted):
        f.result(0)
    assert s.stats()["pool"]["blocks_free"] == s.stats()["pool"]["blocks_total"]


def test_queue_full_sheds(rng):
    net = _tiny_gpt()
    s = _sched(net, queue_capacity=2)
    s.submit(rng.integers(0, VOCAB, (2, 4)), 4)
    with pytest.raises(InferenceBackpressure):
        s.submit(rng.integers(0, VOCAB, (1, 4)), 4)
    _drive(s, [])  # drain what was accepted
    s.shutdown()


def test_recurrent_net_rejected():
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder()
            .seed(1).learning_rate(0.01).updater("adam").activation("tanh")
            .list()
            .layer(GravesLSTM(n_in=7, n_out=8))
            .layer(RnnOutputLayer(n_in=8, n_out=7, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    with pytest.raises(ValueError, match="continuous batching"):
        ContinuousDecodeScheduler(net=net, start=False)


# ------------------------------------------------------- fault domain

@pytest.mark.faultinject
def test_kill_mid_burst_frees_blocks_and_fails_typed(rng, fresh_registry):
    """The BurstKill contract: a burst dying under live sequences fails
    their futures typed (DecodeBurstError ← InjectedFault), frees every
    riding block immediately, and the scheduler keeps serving — pool
    free returns to total after drain, never a leaked block."""
    net = _tiny_gpt()
    kill = BurstKill(after=1, failures=1)
    s = _sched(net, burst_hook=kill)
    p1 = rng.integers(0, VOCAB, (2, 5))
    f1 = s.submit(p1, 10)
    for _ in range(60):
        if f1.done():
            break
        s.step()
    with pytest.raises(DecodeBurstError) as ei:
        f1.result(0)
    assert isinstance(ei.value.__cause__, InjectedFault)
    assert kill.hits == 1
    st = s.stats()
    assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]
    # the scheduler survives: the next request serves normally
    p2 = rng.integers(0, VOCAB, (1, 4))
    f2 = s.submit(p2, 6)
    _drive(s, [f2])
    assert np.array_equal(f2.result(0), generate_eager(net, p2, 6))
    st = s.stats()
    assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]
    assert fresh_registry.family_total(monitor.FAULT_EVENTS_COUNTER) >= 1


@pytest.mark.faultinject
def test_engine_kill_mid_burst_seam(rng, fresh_registry):
    """The engine-level seam (decode_burst_hook=) arms the same
    injector through ParallelInference."""
    net = _tiny_gpt()
    kill = BurstKill(after=0, failures=1)
    eng = ParallelInference(net, replicas=1, continuous=True,
                            decode_slots=4, decode_burst=4,
                            kv_block_size=4, decode_burst_hook=kill)
    try:
        f = eng.submit_generate(rng.integers(0, VOCAB, (1, 5)), 8)
        with pytest.raises(DecodeBurstError):
            f.result(30)
        p = rng.integers(0, VOCAB, (1, 4))
        assert np.array_equal(eng.submit_generate(p, 6).result(30),
                              generate_eager(net, p, 6))
        st = eng.stats()["scheduler"]
        assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]
        assert eng.drain(5)
    finally:
        eng.shutdown()


# ------------------------------------- durable streams (token deltas)

class _Collector:
    """on_tokens audit: asserts append-only delivery while recording."""

    def __init__(self):
        self.chunks = []

    def __call__(self, off, toks):
        self.chunks.append((int(off),
                            [int(t) for t in np.asarray(toks).reshape(-1)]))

    def tokens(self, base=0):
        """Concatenated deltas, asserting contiguous offsets from
        ``base`` (0 for a fresh stream, len(prefix) for a resume)."""
        toks = []
        for off, ts in self.chunks:
            assert off == base + len(toks), \
                f"gap/repeat at {off}: {self.chunks}"
            toks.extend(ts)
        return toks


def test_stream_deltas_match_eager(rng, fresh_registry):
    """on_tokens receives per-burst deltas whose concatenation IS the
    eager sequence — offsets contiguous from 0, chunk counter ticks."""
    net = _tiny_gpt()
    p = rng.integers(0, VOCAB, (1, 5))
    want = generate_eager(net, p, 10)
    coll = _Collector()
    s = _sched(net)
    f = s.submit(p, 10, on_tokens=coll)
    _drive(s, [f])
    assert np.array_equal(f.result(0), want)
    assert coll.tokens() == [int(t) for t in want[0, 5:]]
    assert len(coll.chunks) > 1  # genuinely incremental, not terminal
    assert fresh_registry.family_total(
        monitor.STREAM_CHUNKS_COUNTER) == len(coll.chunks)


def test_stream_deltas_survive_preemption(rng):
    """A preempted-and-resumed stream keeps its delivery cursor: no
    token is re-emitted after the resume, and the delivered stream is
    still the uninterrupted eager sequence."""
    net = _tiny_gpt()
    s = _sched(net, num_blocks=9)  # tiny pool: forces preemption
    prompts = [rng.integers(0, VOCAB, (1, 5)) for _ in range(3)]
    colls = [_Collector() for _ in prompts]
    futs = [s.submit(p, 10, on_tokens=c) for p, c in zip(prompts, colls)]
    _drive(s, futs)
    assert s.stats()["preemptions"] > 0
    for f, p, c in zip(futs, prompts, colls):
        want = generate_eager(net, p, 10)
        assert np.array_equal(f.result(0), want)
        assert c.tokens() == [int(t) for t in want[0, 5:]]


def test_prefix_resume_matches_eager_and_reprefills_only_prefix(rng):
    """The cross-engine migration contract, scheduler-level: a stream
    interrupted after k tokens resumes on a FRESH scheduler from
    prompt + prefix — greedy AND seeded-sampled output token-for-token
    equal to an uninterrupted run, offsets continuing at k, and the
    resume admitted ONE row prefilled at t0 + k (resumed, not
    restarted — pinned via the admit event and the admitted-rows
    count)."""
    net = _tiny_gpt()
    p = rng.integers(0, VOCAB, (1, 5))
    for sampler in ({}, {"temperature": 0.8, "top_k": 5, "seed": 7}):
        want = generate_eager(net, p, 10, **sampler)
        k = 4
        prefix = np.asarray([int(t) for t in want[0, 5:5 + k]])
        s2 = _sched(net)
        coll = _Collector()
        f = s2.submit(p, 10, prefix=prefix, on_tokens=coll, **sampler)
        _drive(s2, [f])
        assert np.array_equal(f.result(0), want), sampler
        # delivered offsets CONTINUE after the prefix — nothing re-emitted
        assert coll.chunks[0][0] == k
        assert coll.tokens(base=k) == [int(t) for t in want[0, 5 + k:]]
        # resumed, not restarted: one admission, prefilled at t0+k
        admits = [e for e in s2.events if e.startswith("admit")]
        assert len(admits) == 1 and f" t={5 + k} " in admits[0], admits
        assert s2.stats()["admitted_rows"] == 1
        st = s2.stats()
        assert st["pool"]["blocks_free"] == st["pool"]["blocks_total"]


def test_prefix_covering_max_new_short_circuits(rng):
    """Only the terminal frame was lost: a resume whose prefix already
    holds every token resolves immediately, no admission at all."""
    net = _tiny_gpt()
    p = rng.integers(0, VOCAB, (1, 5))
    want = generate_eager(net, p, 6)
    s = _sched(net)
    f = s.submit(p, 6, prefix=np.asarray(want[0, 5:]))
    assert f.done()
    assert np.array_equal(f.result(0), want)
    assert s.stats()["admitted_rows"] == 0
    assert s.drain(1)  # accounting stayed consistent


def test_streaming_requires_single_row(rng):
    net = _tiny_gpt()
    s = _sched(net)
    with pytest.raises(ValueError, match="per-stream"):
        s.submit(rng.integers(0, VOCAB, (2, 5)), 4, on_tokens=lambda o, t: 0)
    with pytest.raises(ValueError, match="per-stream"):
        s.submit(rng.integers(0, VOCAB, (2, 5)), 4, prefix=np.asarray([1]))


def test_engine_stream_and_prefix_seams(rng, fresh_registry):
    """ParallelInference plumbs on_tokens/prefix: the continuous
    engine streams per-burst deltas and resumes from a prefix; the
    whole-burst engine degrades to ONE terminal chunk and rejects
    prefix typed (resume rides the iteration-level machinery)."""
    net = _tiny_gpt()
    p = rng.integers(0, VOCAB, (1, 5))
    want = generate_eager(net, p, 8)
    cont = ParallelInference(net, replicas=1, continuous=True,
                             decode_slots=4, decode_burst=4,
                             kv_block_size=4)
    try:
        coll = _Collector()
        f = cont.submit_generate(p, 8, on_tokens=coll)
        assert np.array_equal(f.result(30), want)
        assert coll.tokens() == [int(t) for t in want[0, 5:]]
        coll2 = _Collector()
        f2 = cont.submit_generate(p, 8, prefix=np.asarray(want[0, 5:8]),
                                  on_tokens=coll2)
        assert np.array_equal(f2.result(30), want)
        assert coll2.tokens(base=3) == [int(t) for t in want[0, 8:]]
    finally:
        cont.shutdown()
    whole = ParallelInference(net, replicas=1)
    try:
        coll3 = _Collector()
        f3 = whole.submit_generate(p, 8, on_tokens=coll3)
        assert np.array_equal(f3.result(30), want)
        assert _spin(lambda: len(coll3.chunks) == 1)
        assert coll3.tokens() == [int(t) for t in want[0, 5:]]
        with pytest.raises(ValueError, match="continuous"):
            whole.submit_generate(p, 8, prefix=np.asarray([1, 2]))
    finally:
        whole.shutdown()


def _spin(cond, timeout=10.0):
    import time
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            return False
        time.sleep(0.005)
    return True


# ------------------------------------------------ stats / healthz / schema

def test_stats_and_ready_gate(rng, fresh_registry):
    """stats() exposes the decode-scheduler state and /healthz/ready
    503s until the scheduler is warmed — the models_ready pattern."""
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    from deeplearning4j_tpu.ui.server import UiServer
    net = _tiny_gpt()
    eng = ParallelInference(net, replicas=1, continuous=True,
                            decode_slots=4, decode_burst=4, kv_block_size=4)
    eng._warmed = True  # classify plane warmed: isolate the decode gate
    srv = UiServer(InMemoryStatsStorage(), inference_engine=eng,
                   registry=fresh_registry).start()
    try:
        st = eng.stats()["scheduler"]
        assert {"warmed", "active_sequences", "queued_prefills",
                "pool"} <= set(st)

        def ready():
            try:
                with urllib.request.urlopen(srv.url + "/healthz/ready",
                                            timeout=5) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        code, body = ready()
        assert code == 503 and body["scheduler_ready"] is False
        eng.warmup_generate([4], 8)
        code, body = ready()
        assert code == 200 and body["scheduler_ready"] is True
        sched = body["inference"]["scheduler"]
        assert sched["warmed"] and sched["active_sequences"] == 0
        assert sched["pool"]["blocks_total"] > 0
    finally:
        srv.stop()
        eng.shutdown()


def test_metric_schema_pinned(rng, fresh_registry):
    """The dl4j_kvpool_* / dl4j_sched_* families validate as Prometheus
    exposition and are pinned in KNOWN_DL4J_METRICS."""
    sys.path.insert(0, "scripts")
    try:
        from check_telemetry_schema import (KNOWN_DL4J_METRICS,
                                            validate_known_metrics,
                                            validate_prometheus_text)
    finally:
        sys.path.pop(0)
    for name in ("dl4j_kvpool_blocks_total", "dl4j_kvpool_blocks_free",
                 "dl4j_kvpool_alloc_failures_total",
                 "dl4j_sched_admitted_rows_total",
                 "dl4j_sched_retired_rows_total",
                 "dl4j_sched_preemptions_total", "dl4j_sched_bursts_total",
                 "dl4j_sched_burst_latency_ms",
                 "dl4j_sched_active_sequences",
                 "dl4j_sched_queued_prefills"):
        assert name in KNOWN_DL4J_METRICS, name
    net = _tiny_gpt()
    s = _sched(net, num_blocks=9)
    futs = [s.submit(rng.integers(0, VOCAB, (1, 5)), 10) for _ in range(3)]
    _drive(s, futs)
    text = fresh_registry.prometheus_text()
    assert validate_prometheus_text(text) == []
    assert validate_known_metrics(text) == []
    for family in ("dl4j_kvpool_blocks_total", "dl4j_kvpool_blocks_free",
                   "dl4j_sched_admitted_rows_total",
                   "dl4j_sched_retired_rows_total",
                   "dl4j_sched_bursts_total",
                   "dl4j_sched_burst_latency_ms"):
        assert f"# TYPE {family}" in text, family
    # the tiny pool preempted: the counter and failure metrics moved
    assert "dl4j_sched_preemptions_total" in text
    assert "dl4j_kvpool_alloc_failures_total" in text
