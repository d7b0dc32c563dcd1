"""Multi-model serving + zero-downtime model lifecycle tests
(serving/registry.py + the registry-mode ParallelInference).

The ISSUE-7 battery, all deterministic (explicit fault seams, bounded
spins on observable state, no blind sleeps in assertions):

- registry-mode routing is bitwise each model's inline run; batches
  never mix models;
- per-model bucket ladders + ``warmup_model`` → zero steady-state XLA
  compiles;
- deficit-weighted round-robin keeps a hot model from starving its
  cotenants (unit-level DRR ordering + an integration flood);
- device-memory budget: LRU/priority eviction with lazy reload from
  the PR-4 checkpoint format;
- **zero-downtime deploy**: atomic cutover under load, instant
  rollback, corrupt-checkpoint deploys rejected while the old version
  keeps serving;
- **canary**: deterministic fraction routing, promote, NaN-output and
  error-rate auto-rollback (the poisoned-canary acceptance scenario);
- **isolation**: ``faultinject.poison_model`` opens the per-model
  circuit breaker — cotenants serve bitwise throughout, submits fail
  fast with ``ModelQuarantined``, and a probe heals the model;
- session version pinning across a cutover (a decode stream finishes
  on the version it started on; new sessions get the new version);
- model/version routing across the ``serving/wire.py`` boundary +
  ``/healthz/ready`` per-model readiness;
- ``dl4j_model_*`` Prometheus schema pinning;
- satellite guards: the donation-gate lint is clean over the repo (and
  catches a crafted violation), and the fault-injection stress quick
  check is deterministic.
"""

import importlib.util
import json
import os
import threading
import time
import urllib.request

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.faultinject import poison_model, poison_replica
from deeplearning4j_tpu.models.zoo.transformer import gpt
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.inference import (ParallelInference,
                                                   _FairBatchQueue)
from deeplearning4j_tpu.serving import (ModelQuarantined, ModelRegistry,
                                        ModelUnavailable)
from deeplearning4j_tpu.util.model_serializer import (CheckpointCorruptError,
                                                      write_model)

pytestmark = pytest.mark.faultinject

_SCRIPTS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


N_IN, N_OUT = 6, 3


def _net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .updater("adam").activation("tanh")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=N_OUT, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _spin_until(cond, timeout=60.0, tick=0.005):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            return False
        time.sleep(tick)
    return True


@pytest.fixture
def fresh_registry():
    prev = monitor.set_registry(monitor.MetricsRegistry())
    yield monitor.get_registry()
    monitor.set_registry(prev)


@pytest.fixture
def rng():
    return np.random.default_rng(0)


def _mk_engine(reg, **kw):
    kw.setdefault("max_batch_size", 8)
    kw.setdefault("max_latency_ms", 1.0)
    kw.setdefault("replicas", 1)
    return ParallelInference(registry=reg, **kw)


# ------------------------------------------------------------- routing

def test_multi_model_routing_bitwise(rng, fresh_registry,
                                     assert_bucket_exact):
    a, b = _net(1), _net(2)
    reg = ModelRegistry()
    reg.register("a", net=a)
    reg.register("b", net=b)
    eng = _mk_engine(reg)
    try:
        x = rng.standard_normal((16, N_IN)).astype(np.float32)
        futs = []
        for i in range(8):
            futs.append(("a", x[i:i + 2], eng.submit(x[i:i + 2], model="a")))
            futs.append(("b", x[i:i + 2], eng.submit(x[i:i + 2], model="b")))
        for name, rows, fut in futs:
            assert_bucket_exact(fut.result(timeout=30),
                                a if name == "a" else b, rows, eng.buckets)
    finally:
        eng.shutdown()


def test_registry_mode_requires_model_and_legacy_rejects_model(rng):
    reg = ModelRegistry()
    reg.register("a", net=_net(1))
    eng = _mk_engine(reg)
    try:
        with pytest.raises(ValueError, match="requires model="):
            eng.submit(np.zeros((1, N_IN), np.float32))
        with pytest.raises(ModelUnavailable):
            eng.submit(np.zeros((1, N_IN), np.float32), model="nope")
    finally:
        eng.shutdown()
    legacy = ParallelInference(_net(1), replicas=1)
    try:
        with pytest.raises(ValueError, match="registry"):
            legacy.submit(np.zeros((1, N_IN), np.float32), model="a")
    finally:
        legacy.shutdown()


def test_per_model_buckets_and_warmup_zero_steady_state_compiles(
        rng, fresh_registry):
    reg = ModelRegistry()
    reg.register("a", net=_net(1), warm_shapes=[(N_IN,)], buckets=(2, 4))
    eng = _mk_engine(reg)
    try:
        compiled = eng.warmup_model("a")
        assert compiled > 0
        before = fresh_registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
        for n in (1, 2, 3, 4, 1):
            eng.output(rng.standard_normal((n, N_IN)).astype(np.float32),
                       model="a", timeout=30)
        assert fresh_registry.family_total(
            monitor.JIT_CACHE_MISS_COUNTER) == before
    finally:
        eng.shutdown()


# ------------------------------------------------------ fair scheduling

class _FakeBatch:
    def __init__(self, model, rows, tag):
        self.model = model
        self.rows = rows
        self.tag = tag


def test_fair_queue_interleaves_hot_and_cold_models():
    q = _FairBatchQueue(quantum=4)
    for i in range(10):
        q.put(_FakeBatch("hot", 4, f"h{i}"))
    q.put(_FakeBatch("cold", 4, "c0"))
    q.put(_FakeBatch("cold", 4, "c1"))
    order = [q.get().tag for _ in range(12)]
    # DRR: the cold model's two batches must NOT wait out the hot
    # model's entire backlog — both land in the first half
    assert order.index("c0") < 6 and order.index("c1") < 6
    # single-model degenerates to FIFO
    q2 = _FairBatchQueue(quantum=4)
    for i in range(5):
        q2.put(_FakeBatch("only", 4, f"b{i}"))
    assert [q2.get().tag for _ in range(5)] == [f"b{i}" for i in range(5)]


def test_fair_queue_respects_weights():
    weights = {"heavy": 2.0, "light": 1.0}
    q = _FairBatchQueue(quantum=4, weight_of=lambda m: weights[m])
    for i in range(8):
        q.put(_FakeBatch("heavy", 4, f"H{i}"))
        q.put(_FakeBatch("light", 4, f"L{i}"))
    first8 = [q.get().tag for _ in range(8)]
    h = sum(1 for t in first8 if t.startswith("H"))
    l8 = sum(1 for t in first8 if t.startswith("L"))
    # 2:1 weighting: heavy gets about twice the early service
    assert h > l8


def test_hot_model_cannot_starve_cotenant(rng, fresh_registry):
    a, b = _net(1), _net(2)
    reg = ModelRegistry()
    reg.register("hot", net=a)
    reg.register("cold", net=b)
    eng = _mk_engine(reg, max_latency_ms=0.0, queue_capacity=4096)
    try:
        x = rng.standard_normal((4, N_IN)).astype(np.float32)
        hot_futs = [eng.submit(x, model="hot") for _ in range(200)]
        cold_futs = [eng.submit(x, model="cold") for _ in range(5)]
        # every cold future resolves even while the hot flood drains
        for f in cold_futs:
            np.testing.assert_array_equal(f.result(timeout=60),
                                          np.asarray(b.output(x)))
        for f in hot_futs:
            f.result(timeout=60)
    finally:
        eng.shutdown()


# ------------------------------------------------- memory budget / LRU

def test_memory_budget_evicts_lru_and_reloads_lazily(rng, tmp_path,
                                                     fresh_registry):
    a, b, c = _net(1), _net(2), _net(3)
    zip_a = str(tmp_path / "a.zip")
    write_model(a, zip_a)
    from deeplearning4j_tpu.serving.registry import _tree_nbytes
    size = _tree_nbytes(a.params)
    reg = ModelRegistry(memory_budget_bytes=int(size * 2.5))
    reg.register("a", net=None, path=zip_a)   # checkpoint-backed
    reg.register("b", net=b)
    reg.register("c", net=c)
    eng = _mk_engine(reg)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        ya1 = eng.output(x, model="a", timeout=30)
        eng.output(x, model="b", timeout=30)
        # pinning c exceeds the budget: a (least-recently-used) evicts
        eng.output(x, model="c", timeout=30)
        assert fresh_registry.counter(
            monitor.MODEL_EVICTIONS_COUNTER, "", model="a").value >= 1
        assert not reg.version("a", 1).pins
        # evicted + checkpoint-backed → lazy reload on next use, same
        # results bitwise
        ya2 = eng.output(x, model="a", timeout=30)
        np.testing.assert_array_equal(ya1, ya2)
        assert reg.pinned_bytes() <= int(size * 2.5)
    finally:
        eng.shutdown()


def test_priority_orders_eviction_before_recency(rng, tmp_path,
                                                 fresh_registry):
    a, b, c = _net(1), _net(2), _net(3)
    zip_low = str(tmp_path / "low.zip")
    write_model(a, zip_low)
    from deeplearning4j_tpu.serving.registry import _tree_nbytes
    size = _tree_nbytes(a.params)
    reg = ModelRegistry(memory_budget_bytes=int(size * 2.5))
    reg.register("low", path=zip_low, priority=0)
    reg.register("high", net=b, priority=10)
    reg.register("third", net=c, priority=0)
    eng = _mk_engine(reg)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        eng.output(x, model="high", timeout=30)
        eng.output(x, model="low", timeout=30)
        # pinning "third" must evict: "high" is the LRU pin but its
        # priority protects it — the fresher low-priority pin goes
        eng.output(x, model="third", timeout=30)
        assert reg.version("high", 1).pins
        assert not reg.version("low", 1).pins
    finally:
        eng.shutdown()


# --------------------------------------------------- deploy / rollback

def test_deploy_cutover_is_atomic_and_rollback_instant(rng, fresh_registry,
                                                       assert_bucket_exact):
    v1net, v2net = _net(1), _net(4)
    reg = ModelRegistry()
    reg.register("m", net=v1net, warm_shapes=[(N_IN,)])
    eng = _mk_engine(reg)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        y1 = np.asarray(v1net.output(x))
        y2 = np.asarray(v2net.output(x))
        np.testing.assert_array_equal(eng.output(x, model="m", timeout=30), y1)
        # deploy v2 while requests are in flight: nothing is lost, and
        # post-deploy submits serve v2
        inflight = [eng.submit(x, model="m") for _ in range(16)]
        v = reg.deploy("m", net=v2net)
        assert v == 2 and reg.active_version("m") == 2
        for f in inflight:  # every pre/post-cutover future resolves
            out = f.result(timeout=30)
            # the sixteen identical requests coalesce: v1 or v2, through
            # whichever bucket program carried the batch
            served_v1 = np.allclose(out, y1, rtol=0, atol=1e-6)
            assert_bucket_exact(out, v1net if served_v1 else v2net, x,
                                eng.buckets)
        np.testing.assert_array_equal(eng.output(x, model="m", timeout=30), y2)
        # the new version was AOT-warmed by the deploy
        assert reg.version("m", 2).warmed
        # instant rollback via the retained version
        assert reg.rollback("m") == 1
        np.testing.assert_array_equal(eng.output(x, model="m", timeout=30), y1)
        # pinned explicit versions stay reachable while retained
        with pytest.raises(ModelUnavailable):
            eng.submit(x, model="m", version=99)
    finally:
        eng.shutdown()


def test_corrupt_deploy_rejected_while_old_keeps_serving(
        rng, tmp_path, fresh_registry):
    from deeplearning4j_tpu.faultinject import corrupt_file
    v1net, v2net = _net(1), _net(4)
    reg = ModelRegistry()
    reg.register("m", net=v1net)
    eng = _mk_engine(reg)
    try:
        bad = str(tmp_path / "v2.zip")
        write_model(v2net, bad)
        corrupt_file(bad, offset=-100)
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        with pytest.raises(CheckpointCorruptError):
            reg.deploy("m", path=bad)
        # the deploy never touched the serving plane
        assert reg.active_version("m") == 1
        assert reg.versions("m") == {1: "active"}
        np.testing.assert_array_equal(
            eng.output(x, model="m", timeout=30),
            np.asarray(v1net.output(x)))
        assert fresh_registry.counter(
            monitor.MODEL_DEPLOYS_COUNTER, "", model="m",
            outcome="rejected_corrupt").value == 1
    finally:
        eng.shutdown()


# --------------------------------------------------------------- canary

def test_canary_fraction_routes_deterministically_and_promotes(
        rng, fresh_registry):
    v1net, v2net = _net(1), _net(4)
    reg = ModelRegistry()
    reg.register("m", net=v1net)
    eng = _mk_engine(reg, max_latency_ms=0.0)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        y1 = np.asarray(v1net.output(x))
        y2 = np.asarray(v2net.output(x))
        reg.deploy("m", net=v2net, canary_fraction=0.5, warm=False)
        assert reg.active_version("m") == 1  # canary does NOT cut over
        hits = {"v1": 0, "v2": 0}
        for _ in range(12):
            out = eng.output(x, model="m", timeout=30)
            hits["v2" if np.array_equal(out, y2) else "v1"] += 1
        # fraction 0.5 = every 2nd request, deterministically
        assert hits == {"v1": 6, "v2": 6}
        reg.promote("m")
        assert reg.active_version("m") == 2
        np.testing.assert_array_equal(eng.output(x, model="m", timeout=30), y2)
    finally:
        eng.shutdown()


def test_poisoned_canary_nan_output_auto_rolls_back(rng, fresh_registry):
    v1net = _net(1)
    bad = _net(4)
    # poison the canary's params: every output row goes NaN
    bad.params["layer0"]["W"] = jax.numpy.asarray(
        np.full_like(np.asarray(bad.params["layer0"]["W"]), np.nan))
    reg = ModelRegistry()
    reg.register("m", net=v1net)
    eng = _mk_engine(reg, max_latency_ms=0.0)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        y1 = np.asarray(v1net.output(x))
        reg.deploy("m", net=bad, canary_fraction=0.5, warm=False)
        # drive traffic until the watch sees the NaN canary output
        assert _spin_until(
            lambda: (eng.output(x, model="m", timeout=30) is not None
                     and reg.entry("m").canary is None), timeout=30)
        # canary rejected, stable version never stopped serving
        assert reg.versions("m")[2] == "rejected"
        assert reg.active_version("m") == 1
        for _ in range(4):
            np.testing.assert_array_equal(
                eng.output(x, model="m", timeout=30), y1)
        assert fresh_registry.counter(
            monitor.MODEL_ROLLBACKS_COUNTER, "", model="m",
            reason="canary_nan").value == 1
    finally:
        eng.shutdown()


def test_erroring_canary_auto_rolls_back_and_engine_heals(
        rng, fresh_registry):
    dev = jax.devices()[0]
    v1net, v2net = _net(1), _net(4)
    reg = ModelRegistry()
    reg.register("m", net=v1net)
    eng = ParallelInference(registry=reg, max_batch_size=8,
                            max_latency_ms=0.0, devices=[dev, dev],
                            probe_interval_ms=3600_000.0)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        y1 = np.asarray(v1net.output(x))
        eng.output(x, model="m", timeout=30)  # known-good probe shape
        v2 = reg.deploy("m", net=v2net, canary_fraction=1.0, warm=False)
        poison_model(eng, "m", failures=4, version=v2)
        # the canary's cross-replica faults roll IT back, not the model
        errs = 0
        for _ in range(4):
            try:
                eng.output(x, model="m", timeout=30)
            except Exception:
                errs += 1
            if reg.entry("m").canary is None:
                break
        assert reg.versions("m")[v2] == "rejected"
        assert not reg.breaker_open("m")
        assert fresh_registry.counter(
            monitor.MODEL_ROLLBACKS_COUNTER, "", model="m",
            reason="canary_error_rate").value == 1
        # stable version serves; the transiently-quarantined replica
        # reinstates on probe
        np.testing.assert_array_equal(eng.output(x, model="m", timeout=30), y1)
        eng.probe_now()
        assert _spin_until(lambda: eng.stats()["healthy_replicas"] == 2)
    finally:
        eng.shutdown()


# ------------------------------------------------------------ isolation

def test_model_breaker_isolates_cotenants_and_probe_heals(
        rng, fresh_registry):
    dev = jax.devices()[0]
    m, n = _net(1), _net(2)
    reg = ModelRegistry()
    reg.register("m", net=m)
    reg.register("n", net=n)
    eng = ParallelInference(registry=reg, max_batch_size=8,
                            max_latency_ms=0.0, devices=[dev, dev],
                            probe_interval_ms=3600_000.0)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        yn = np.asarray(n.output(x))
        eng.output(x, model="m", timeout=30)
        eng.output(x, model="n", timeout=30)
        poison = poison_model(eng, "m")  # 2 batches × (1+1 attempts)
        with pytest.raises(ModelQuarantined):
            eng.output(x, model="m", timeout=30)
        assert reg.breaker_open("m")
        assert poison.remaining == 0
        # isolation: submits for m now fail FAST at admission...
        with pytest.raises(ModelQuarantined):
            eng.submit(x, model="m")
        # ...while the cotenant keeps serving bitwise on every request
        for _ in range(4):
            np.testing.assert_array_equal(
                eng.output(x, model="n", timeout=30), yn)
        assert eng.stats()["models_quarantined"] == ["m"]
        assert eng.stats()["degraded"]
        # poison exhausted → the model probe closes the breaker and the
        # replica probe reinstates the transiently-quarantined replica
        eng.probe_now()
        assert not reg.breaker_open("m")
        assert _spin_until(lambda: eng.stats()["healthy_replicas"] == 2)
        np.testing.assert_array_equal(
            eng.output(x, model="m", timeout=30), np.asarray(m.output(x)))
        assert not eng.stats()["degraded"]
    finally:
        eng.shutdown()


def test_replica_fault_still_quarantines_replica_not_model(
        rng, fresh_registry):
    dev = jax.devices()[0]
    reg = ModelRegistry()
    m = _net(1)
    reg.register("m", net=m)
    eng = ParallelInference(registry=reg, max_batch_size=8,
                            max_latency_ms=0.0, devices=[dev, dev],
                            probe_interval_ms=3600_000.0)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        eng.output(x, model="m", timeout=30)
        poison = poison_replica(eng, replica=0, failures=2)
        # drive until the poisoned replica catches a batch: it fails
        # twice on replica 0, redispatches to replica 1 and SUCCEEDS →
        # replica-scoped quarantine, model untouched
        for _ in range(50):
            np.testing.assert_array_equal(
                eng.output(x, model="m", timeout=30),
                np.asarray(m.output(x)))
            if poison.hits >= 2:
                break
        assert poison.hits == 2
        assert _spin_until(lambda: eng.stats()["healthy_replicas"] == 1)
        assert not reg.breaker_open("m")
        eng.probe_now()
        assert _spin_until(lambda: eng.stats()["healthy_replicas"] == 2)
    finally:
        eng.shutdown()


def test_deploying_fixed_version_heals_quarantined_model(
        rng, fresh_registry):
    dev = jax.devices()[0]
    m = _net(1)
    fixed = _net(4)
    reg = ModelRegistry()
    reg.register("m", net=m, warm_shapes=[(N_IN,)])
    eng = ParallelInference(registry=reg, max_batch_size=8,
                            max_latency_ms=0.0, devices=[dev, dev],
                            probe_interval_ms=3600_000.0)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        eng.output(x, model="m", timeout=30)
        poison_model(eng, "m", failures=10_000)  # sick until replaced
        with pytest.raises(ModelQuarantined):
            eng.output(x, model="m", timeout=30)
        assert reg.breaker_open("m")
        # the recovery path for a quarantined model IS deploying a
        # fixed version: the deploy warms (explicit version bypasses
        # the breaker), cuts over, and resets the breaker — but the
        # poison targets the MODEL, so warmup itself still faults: heal
        # the poison as the fixed deploy would ship fixed code
        eng._poison_hook = None
        v = reg.deploy("m", net=fixed)
        assert v == 2 and not reg.breaker_open("m")
        eng.probe_now()
        np.testing.assert_array_equal(
            eng.output(x, model="m", timeout=30),
            np.asarray(fixed.output(x)))
    finally:
        eng.shutdown()


# --------------------------------------- session affinity vs cutover

def test_session_finishes_stream_on_its_version_across_cutover(
        fresh_registry):
    g1 = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
             compute_dtype="float32", learning_rate=0.01, seed=1).init()
    g2 = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
             compute_dtype="float32", learning_rate=0.01, seed=9).init()
    reg = ModelRegistry()
    reg.register("g", net=g1)
    eng = _mk_engine(reg, max_latency_ms=0.0)
    try:
        prompt = np.asarray([[1, 2, 3]], np.int64)
        solo1 = np.asarray(g1.generate(prompt, 5))
        solo2 = np.asarray(g2.generate(prompt, 5))
        assert not np.array_equal(solo1, solo2)
        # burst 1 of the pinned stream resolves v1
        np.testing.assert_array_equal(
            eng.generate(prompt, 5, session="s1", model="g", timeout=60),
            solo1)
        reg.deploy("g", net=g2, warm=False)  # hot-swap mid-stream
        # the pinned session MUST finish on the version it started on —
        # a silent KV-cache owner switch is the bug this pins
        np.testing.assert_array_equal(
            eng.generate(prompt, 5, session="s1", model="g", timeout=60),
            solo1)
        # a NEW session gets the new version
        np.testing.assert_array_equal(
            eng.generate(prompt, 5, session="s2", model="g", timeout=60),
            solo2)
        # releasing the old session re-resolves to the active version
        eng.release_session("s1")
        np.testing.assert_array_equal(
            eng.generate(prompt, 5, session="s1", model="g", timeout=60),
            solo2)
    finally:
        eng.shutdown()


# ----------------------------------------------------- wire + healthz

def test_model_routing_crosses_the_wire(rng, fresh_registry):
    from deeplearning4j_tpu.serving import EngineWorker, RemoteEndpoint
    from deeplearning4j_tpu.streaming.broker import InMemoryBroker
    a, b = _net(1), _net(2)
    reg = ModelRegistry()
    reg.register("a", net=a)
    reg.register("b", net=b)
    eng = _mk_engine(reg)
    broker = InMemoryBroker()
    worker = EngineWorker(eng, broker, "svc", heartbeat_s=0.05)
    ep = RemoteEndpoint(broker, "svc", request_timeout_s=30.0)
    try:
        assert _spin_until(ep.alive, timeout=10)
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        np.testing.assert_array_equal(
            ep.submit(x, model="a").result(timeout=30),
            np.asarray(a.output(x)))
        np.testing.assert_array_equal(
            ep.submit(x, model="b").result(timeout=30),
            np.asarray(b.output(x)))
        # unknown model surfaces TYPED across the wire
        err = ep.submit(x, model="zzz").exception(timeout=30)
        assert isinstance(err, ModelUnavailable)
    finally:
        worker.kill()
        ep.close()
        eng.shutdown()


def test_healthz_ready_gates_on_per_model_state(rng, fresh_registry):
    from deeplearning4j_tpu.ui.server import UiServer
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    dev = jax.devices()[0]
    reg = ModelRegistry()
    reg.register("m", net=_net(1), warm_shapes=[(N_IN,)])
    eng = ParallelInference(registry=reg, max_batch_size=8,
                            max_latency_ms=0.0, devices=[dev, dev],
                            probe_interval_ms=3600_000.0)
    srv = UiServer(InMemoryStatsStorage(), inference_engine=eng,
                   registry=fresh_registry).start()
    try:
        def ready():
            try:
                with urllib.request.urlopen(srv.url + "/healthz/ready",
                                            timeout=5) as r:
                    return r.status, json.loads(r.read())
            except urllib.error.HTTPError as e:
                return e.code, json.loads(e.read())

        code, body = ready()
        assert code == 503 and body["models_ready"] == {"m": False}
        eng.warmup_model("m")
        code, body = ready()
        assert code == 200 and body["models_ready"] == {"m": True}
        # open breaker → not ready, per-model detail says which
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        poison_model(eng, "m")
        with pytest.raises(ModelQuarantined):
            eng.output(x, model="m", timeout=30)
        code, body = ready()
        assert code == 503 and body["models_ready"] == {"m": False}
        # breaker probe is synchronous; replica reinstatement rides the
        # woken probe threads — spin on the observable state
        eng.probe_now()
        assert _spin_until(lambda: ready()[0] == 200)
    finally:
        srv.stop()
        eng.shutdown()


def test_model_metric_schema(rng, fresh_registry):
    schema = _load_script("check_telemetry_schema")
    reg = ModelRegistry()
    a = _net(1)
    reg.register("m", net=a)
    eng = _mk_engine(reg)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        eng.output(x, model="m", timeout=30)
        reg.deploy("m", net=_net(4), warm=False)
        reg.rollback("m")
        text = fresh_registry.prometheus_text()
        assert schema.validate_prometheus_text(text) == []
        assert schema.validate_known_metrics(text) == []
        for fam in ("dl4j_model_requests_total", "dl4j_model_latency_ms",
                    "dl4j_model_deploys_total", "dl4j_model_rollbacks_total",
                    "dl4j_model_active_version"):
            assert fam in text, fam
            assert fam in schema.KNOWN_DL4J_METRICS
    finally:
        eng.shutdown()


# ------------------------------------------------- satellite guards

def test_donation_gates_lint_repo_clean_and_catches_violation(tmp_path):
    lint = _load_script("check_donation_gates")
    root = os.path.dirname(_SCRIPTS)
    assert lint.check_repo(root) == []
    # a crafted ungated site is flagged...
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "f = jax.jit(lambda x: x, donate_argnums=(0,))\n")
    assert len(lint.check_file(str(bad))) == 1
    # ...while the inline-gated and empty-tuple forms pass
    good = tmp_path / "good.py"
    good.write_text(
        "import jax\n"
        'donate = (0,) if jax.default_backend() != "cpu" else ()\n'
        "f = jax.jit(lambda x: x, donate_argnums=donate)\n"
        "g = jax.jit(lambda x: x, donate_argnums=())\n")
    assert lint.check_file(str(good)) == []


def test_stress_faultinject_quick_mode_deterministic():
    stress = _load_script("stress_faultinject")
    assert stress.quick_check(seeds=(0, 1), runs_per_seed=2) == []
