"""ParallelInference engine tests: coalescing, result identity,
latency flush, backpressure, error propagation, shutdown drain, AOT
warmup, and the StreamingInference end-to-end round trip.

Parity doctrine: batched rows must be bitwise-identical to an inline
``net.output`` run on the same rows THROUGH THE SAME PROGRAM. XLA:CPU
does not give a row the same last bit at two batch sizes (and
special-cases batch 1: gemv vs gemm), so where the coalescer picks the
bucket the reference runs through every bucket program that could have
carried the request (conftest ``assert_bucket_exact``), and coalesced
singletons compare against the concatenated inline run.
"""

import threading
import time

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (ListDataSetIterator,
                                                   bucket_for, bucket_sizes)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.inference import (InferenceBackpressure,
                                                   ParallelInference)
from deeplearning4j_tpu.streaming import (InMemoryBroker, StreamingInference,
                                          ndarray_from_bytes, ndarray_to_bytes)
from deeplearning4j_tpu.streaming.pipeline import publish_stop

N_IN, N_OUT = 4, 3


def _net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.1)
            .updater("sgd").activation("tanh")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=N_OUT, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


@pytest.fixture
def net():
    return _net()


@pytest.fixture
def fresh_registry():
    prev = monitor.set_registry(monitor.MetricsRegistry())
    yield monitor.get_registry()
    monitor.set_registry(prev)


def test_bucket_helpers():
    assert bucket_sizes(8) == (1, 2, 4, 8)
    assert bucket_sizes(12) == (1, 2, 4, 8, 12)
    assert bucket_sizes(1) == (1,)
    assert bucket_for(3, (1, 2, 4, 8)) == 4
    assert bucket_for(8, (1, 2, 4, 8)) == 8
    assert bucket_for(9, (1, 2, 4, 8)) == 9  # oversize passes through
    with pytest.raises(ValueError):
        bucket_sizes(0)


def test_concurrent_submit_result_identity(net, rng, assert_bucket_exact):
    """Every caller gets exactly its own rows, bitwise-equal to the
    inline output() run on those rows through the bucket program that
    carried them."""
    eng = ParallelInference(net, max_batch_size=8, max_latency_ms=2.0,
                            replicas=2)
    try:
        xs = [rng.standard_normal((2 + i % 3, N_IN)).astype(np.float32)
              for i in range(24)]
        results = [None] * len(xs)

        def submit_some(lo, hi):
            futs = [(j, eng.submit(xs[j])) for j in range(lo, hi)]
            for j, f in futs:
                results[j] = f.result(timeout=60)

        threads = [threading.Thread(target=submit_some, args=(k, k + 6))
                   for k in range(0, 24, 6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for x, r in zip(xs, results):
            assert r.shape == (x.shape[0], N_OUT)
            assert_bucket_exact(r, net, x, eng.buckets)
        assert eng.stats()["requests"] == 24
    finally:
        eng.shutdown()


def test_singleton_coalescing_row_routing(net, rng):
    """Singleton requests coalesced into one batch each resolve to the
    same rows as the inline run of the concatenated batch (bitwise)."""
    eng = ParallelInference(net, max_batch_size=8, max_latency_ms=50.0,
                            replicas=1, eager_when_idle=False)
    try:
        xs = [rng.standard_normal((1, N_IN)).astype(np.float32)
              for _ in range(8)]
        futs = [eng.submit(x) for x in xs]
        rows = [f.result(timeout=60) for f in futs]
        ref = np.asarray(net.output(np.concatenate(xs)))
        np.testing.assert_array_equal(np.concatenate(rows), ref)
        # 8 singletons under one max_latency window == one full batch
        assert eng.stats()["batches"] == 1
        assert eng.stats()["rows_padded"] == 0
    finally:
        eng.shutdown()


def test_eager_dispatch_when_idle(net, rng):
    """Default discipline: an idle replica dispatches a lone request
    immediately instead of sitting out the coalescing window."""
    eng = ParallelInference(net, max_batch_size=64, max_latency_ms=500.0,
                            replicas=1)
    try:
        eng.warmup([(N_IN,)])
        t0 = time.perf_counter()
        eng.output(rng.standard_normal((2, N_IN)).astype(np.float32),
                   timeout=60)
        assert time.perf_counter() - t0 < 0.4  # never waited out 500ms
    finally:
        eng.shutdown()


def test_max_latency_flush(net, rng):
    """A lone sub-batch request must flush when max_latency_ms elapses,
    not wait for a full batch."""
    eng = ParallelInference(net, max_batch_size=64, max_latency_ms=30.0,
                            replicas=1, eager_when_idle=False)
    try:
        eng.warmup([(N_IN,)])  # exclude compile time from the bound
        t0 = time.perf_counter()
        fut = eng.submit(rng.standard_normal((2, N_IN)).astype(np.float32))
        fut.result(timeout=60)
        elapsed = time.perf_counter() - t0
        assert elapsed >= 0.02  # held for the coalescing window...
        assert elapsed < 5.0    # ...but flushed by the timer
        # padded onto the bucket ladder: 2 rows is already a bucket
        assert eng.stats()["rows_dispatched"] == 2
    finally:
        eng.shutdown()


def test_padding_to_bucket(net, rng):
    eng = ParallelInference(net, max_batch_size=8, max_latency_ms=1.0,
                            replicas=1)
    try:
        fut = eng.submit(rng.standard_normal((3, N_IN)).astype(np.float32))
        out = fut.result(timeout=60)
        assert out.shape == (3, N_OUT)  # de-padded
        s = eng.stats()
        assert s["rows_dispatched"] == 4  # 3 padded up to bucket 4
        assert s["rows_padded"] == 1
        assert 0.0 < s["padded_ratio"] <= 0.25
    finally:
        eng.shutdown()


def test_backpressure_reject_and_deferred_start(net, rng):
    """With reject_when_full the queue bounds admission; a deferred
    start drains the backlog once running."""
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            queue_capacity=2, reject_when_full=True,
                            replicas=1, start=False)
    x = rng.standard_normal((1, N_IN)).astype(np.float32)
    f1, f2 = eng.submit(x), eng.submit(x)
    with pytest.raises(InferenceBackpressure):
        eng.submit(x)
    eng.start()
    r1, r2 = f1.result(timeout=60), f2.result(timeout=60)
    np.testing.assert_array_equal(r1, r2)
    eng.shutdown()


def test_submit_rejects_bad_rank_and_closed(net, rng):
    eng = ParallelInference(net, replicas=1)
    with pytest.raises(ValueError):
        eng.submit(np.zeros((N_IN,), np.float32))  # no batch dim
    eng.shutdown()
    with pytest.raises(RuntimeError):
        eng.submit(np.zeros((1, N_IN), np.float32))


def test_worker_error_propagates_to_futures(net, rng):
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            replicas=1)
    bad = rng.standard_normal((2, N_IN + 3)).astype(np.float32)  # wrong width
    fut = eng.submit(bad)
    with pytest.raises(Exception):
        fut.result(timeout=60)
    # engine survives for well-formed traffic...
    good = rng.standard_normal((2, N_IN)).astype(np.float32)
    np.testing.assert_array_equal(eng.output(good, timeout=60),
                                  np.asarray(net.output(good)))
    # ...and shutdown re-raises the first worker error
    with pytest.raises(Exception):
        eng.shutdown()


def test_shutdown_drains_in_flight(net, rng):
    """shutdown(drain=True) racing a burst of submits must resolve every
    accepted future."""
    eng = ParallelInference(net, max_batch_size=8, max_latency_ms=2.0,
                            replicas=2)
    xs = [rng.standard_normal((1 + i % 4, N_IN)).astype(np.float32)
          for i in range(32)]
    futs = [eng.submit(x) for x in xs]
    eng.shutdown()  # immediately: queued work must still complete
    for x, f in zip(xs, futs):
        assert f.result(timeout=60).shape == (x.shape[0], N_OUT)
    assert eng.stats()["requests"] == 32


def test_shutdown_no_drain_cancels_queued(net, rng):
    eng = ParallelInference(net, queue_capacity=8, replicas=1, start=False)
    futs = [eng.submit(np.zeros((1, N_IN), np.float32)) for _ in range(3)]
    eng.shutdown(drain=False)
    for f in futs:
        with pytest.raises(RuntimeError):
            f.result(timeout=5)


def test_warmup_precompiles_bucket_set(net, rng, fresh_registry):
    """After warmup(shapes) the serve loop performs ZERO fresh
    trace+compiles across ragged request sizes within the bucket set —
    asserted via dl4j_jit_cache_miss_total."""
    reg = fresh_registry
    eng = ParallelInference(net, max_batch_size=8, max_latency_ms=1.0,
                            replicas=2)
    try:
        compiled = eng.warmup([(N_IN,)])
        assert compiled == len(bucket_sizes(8)) * 2  # buckets x replicas
        warm = reg.family_total(monitor.JIT_CACHE_MISS_COUNTER)
        assert warm == compiled
        for n in (1, 2, 3, 4, 5, 7, 8, 6, 1, 5):  # ragged request mix
            eng.output(rng.standard_normal((n, N_IN)).astype(np.float32),
                       timeout=60)
        assert reg.family_total(monitor.JIT_CACHE_MISS_COUNTER) == warm
        assert reg.family_total(monitor.INFER_REQUESTS_COUNTER) == 10
        assert reg.family_total(monitor.INFER_BATCHES_COUNTER) >= 1
    finally:
        eng.shutdown()


def test_engine_metrics_in_prometheus_exposition(net, rng, fresh_registry):
    """dl4j_infer_* families render valid, schema-pinned exposition
    (the UiServer /metrics contract)."""
    import scripts.check_telemetry_schema as schema
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            replicas=1)
    try:
        eng.output(rng.standard_normal((3, N_IN)).astype(np.float32),
                   timeout=60)
    finally:
        eng.shutdown()
    text = fresh_registry.prometheus_text()
    assert "dl4j_infer_requests_total" in text
    assert "dl4j_infer_batch_size_bucket" in text
    assert schema.validate_prometheus_text(text) == []
    assert schema.validate_known_metrics(text) == []


def test_moe_style_models_disable_coalescing(rng):
    """A model with cross-batch statistics must not be padded/coalesced
    (INPLACE mode): each request dispatches alone, unpadded."""
    net = _net()
    net.impls[0].batch_statistics = True  # simulate MoE capacity routing
    eng = ParallelInference(net, max_batch_size=8, max_latency_ms=10.0,
                            replicas=1)
    try:
        assert not eng.coalesce
        futs = [eng.submit(rng.standard_normal((3, N_IN)).astype(np.float32))
                for _ in range(2)]
        for f in futs:
            assert f.result(timeout=60).shape == (3, N_OUT)
        s = eng.stats()
        assert s["batches"] == 2 and s["rows_padded"] == 0
    finally:
        eng.shutdown()


def test_computation_graph_engine(rng):
    from deeplearning4j_tpu.nn.graph import (ComputationGraph,
                                             ComputationGraphConfiguration)
    base = NeuralNetConfiguration(seed=3, activation="tanh",
                                  learning_rate=0.1, updater="sgd")
    conf = (ComputationGraphConfiguration.builder(base)
            .add_inputs("in")
            .add_layer("h", DenseLayer(n_in=N_IN, n_out=8), "in")
            .add_layer("out", OutputLayer(n_in=8, n_out=N_OUT,
                                          activation="softmax",
                                          loss_function="mcxent"), "h")
            .set_outputs("out").build())
    cg = ComputationGraph(conf).init()
    eng = ParallelInference(cg, max_batch_size=8, max_latency_ms=2.0,
                            replicas=1)
    try:
        x = rng.standard_normal((4, N_IN)).astype(np.float32)
        np.testing.assert_array_equal(eng.output(x, timeout=60),
                                      np.asarray(cg.output(x)))
    finally:
        eng.shutdown()


# ------------------------------------------------- satellite: nn paths

def test_predict_on_device_argmax_matches_host(net, rng):
    x = rng.standard_normal((9, N_IN)).astype(np.float32)
    ids = net.predict(x)
    assert ids.dtype == np.int64 and ids.shape == (9,)
    np.testing.assert_array_equal(
        ids, np.argmax(np.asarray(net.output(x)), axis=-1))


def test_feed_forward_jit_cached(net, rng):
    x = rng.standard_normal((5, N_IN)).astype(np.float32)
    acts = net.feed_forward(x)
    assert [a.shape for a in acts] == [(5, 8), (5, N_OUT)]
    np.testing.assert_array_equal(acts[-1], np.asarray(net.output(x)))
    key_present = any(k[0] == "feed_forward" for k in net._jits
                      if isinstance(k, tuple))
    assert key_present
    # second call hits the cache (no new program objects)
    n_jits = len(net._jits)
    net.feed_forward(x)
    net.feed_forward(x, train=True)  # distinct cached entry
    assert len(net._jits) == n_jits + 1


def test_evaluate_bucketed_single_program(net, rng, fresh_registry):
    """net.evaluate over a ragged iterator reuses ONE compiled program
    (tail padded to the canonical batch) and matches the reference
    Evaluation built from full probabilities."""
    from deeplearning4j_tpu.eval.evaluation import Evaluation
    n = 21
    x = rng.standard_normal((n, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, n)]
    ev = net.evaluate(DataSet(x, y), batch_size=8)  # tail of 5
    ref = Evaluation()
    ref.eval(y, np.asarray(net.output(x)))
    np.testing.assert_array_equal(ev.confusion.counts, ref.confusion.counts)
    assert ev.accuracy() == ref.accuracy()
    # 8,8,5(->8): one predict program signature == one cache miss
    assert fresh_registry.family_total(monitor.JIT_CACHE_MISS_COUNTER) == 1


def test_evaluate_sharded_tail_no_recompile(net, rng):
    """The sharded evaluator pads ragged tails to the canonical shape:
    dispatch signatures collapse to one program (and results stay exact)."""
    from deeplearning4j_tpu.parallel import evaluate_sharded
    n = 21
    x = rng.standard_normal((n, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, n)]
    ev = evaluate_sharded(net, ListDataSetIterator(DataSet(x, y), 8))
    ev_host = net.evaluate(DataSet(x, y), batch_size=8)
    np.testing.assert_array_equal(ev.confusion.counts,
                                  ev_host.confusion.counts)
    assert int(ev.confusion.counts.sum()) == n


# --------------------------------------- satellite: streaming round trip

def test_streaming_inference_engine_end_to_end(net, rng,
                                               assert_bucket_exact):
    """Serve-route round trip through the engine: concurrent ragged
    messages come back on out_topic in order, equal to inline output."""
    broker = InMemoryBroker()
    engine = ParallelInference(net, max_batch_size=8, max_latency_ms=2.0,
                               replicas=2)
    engine.warmup([(N_IN,)])
    serve = StreamingInference(net, broker, "in", "out",
                               engine=engine).start()
    xs = [rng.standard_normal((2 + i % 3, N_IN)).astype(np.float32)
          for i in range(9)]
    for x in xs:
        broker.publish("in", ndarray_to_bytes(x))
    publish_stop(broker, "in")
    assert serve.join(timeout=120) == 9
    for x in xs:  # out_topic preserves in_topic order
        pred = ndarray_from_bytes(broker.consume("out", timeout=5))
        assert_bucket_exact(pred, net, x, engine.buckets)
    engine.shutdown()


def test_streaming_inference_owns_engine_by_default(net, rng):
    broker = InMemoryBroker()
    serve = StreamingInference(net, broker, "in", "out").start()
    x = rng.standard_normal((3, N_IN)).astype(np.float32)
    broker.publish("in", ndarray_to_bytes(x))
    publish_stop(broker, "in")
    assert serve.join(timeout=120) == 1
    np.testing.assert_array_equal(
        ndarray_from_bytes(broker.consume("out", timeout=5)),
        np.asarray(net.output(x)))


def test_ui_healthz_exposes_engine_stats(net, rng):
    import json
    from urllib.request import urlopen

    from deeplearning4j_tpu.ui.server import UiServer
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            replicas=1)
    server = UiServer(InMemoryStatsStorage(), inference_engine=eng).start()
    try:
        eng.output(rng.standard_normal((2, N_IN)).astype(np.float32),
                   timeout=60)
        body = json.loads(urlopen(server.url + "/healthz", timeout=10).read())
        assert body["inference"]["requests"] == 1
        assert body["inference"]["replicas"] == 1
        metrics = urlopen(server.url + "/metrics", timeout=10).read().decode()
        assert "dl4j_infer_requests_total" in metrics
    finally:
        server.stop()
        eng.shutdown()
