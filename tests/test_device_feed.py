"""Device-feed pipeline: ordering/reset/mid-epoch-abandon races,
deferred score sync, and shape-bucketed tail-batch parity.

Mirrors the test_observed_sync doctrine: the async seams get many-trial
race tests, the exactness claims get bitwise assertions. The parity
claim verified here: a ragged tail batch padded to the canonical batch
size with a zeroing labels mask trains EXACTLY like the unpadded batch
— the masked mean divides by the real example count and padded rows
back-propagate exact zeros (ops/losses.py ``_masked_mean`` additionally
reproduces ``jnp.mean``'s forward rounding so the scores match bitwise;
parameters agree bitwise for the pinned seed and to one float32 ulp
across seeds — reductions over different batch shapes may associate
differently inside XLA, which is the irreducible floor).
"""

import threading
import time

import jax
import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
    DeviceFeedIterator,
    ListDataSetIterator,
    ShapeBucketingIterator,
)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    BatchNormalization,
    DenseLayer,
    OutputLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.listeners import CollectScoresIterationListener


@pytest.fixture
def registry():
    reg = monitor.MetricsRegistry()
    old = monitor.set_registry(reg)
    try:
        yield reg
    finally:
        monitor.set_registry(old)


def _mlp(seed=7, bn=False):
    b = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.1)
         .updater("sgd").activation("tanh").list()
         .layer(DenseLayer(n_in=4, n_out=8)))
    if bn:
        b = b.layer(BatchNormalization(n_out=8))
    conf = b.layer(OutputLayer(n_in=8, n_out=3, activation="softmax",
                               loss_function="mcxent")).build()
    return MultiLayerNetwork(conf).init()


def _data(n, dseed=0):
    rng = np.random.default_rng(dseed)
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, n)]
    return DataSet(x, y)


# ------------------------------------------------------ device feed stage

def _feed_over(ds, batch, depth=2, place=None):
    return DeviceFeedIterator(
        AsyncDataSetIterator(ListDataSetIterator(ds, batch)),
        depth=depth, place=place)


def test_device_feed_preserves_order_and_values(registry):
    ds = _data(70)
    ref = [b for b in ListDataSetIterator(ds, 16)]
    feed = _feed_over(ds, 16)
    for epoch in range(2):  # second pass proves __iter__ -> reset works
        got = [b for b in feed]
        assert len(got) == len(ref)
        for g, r in zip(got, ref):
            np.testing.assert_array_equal(np.asarray(g.features), r.features)
            np.testing.assert_array_equal(np.asarray(g.labels), r.labels)


def test_device_feed_places_on_device(registry):
    import jax.numpy as jnp
    ds = _data(32)
    place = lambda b: DataSet(jnp.asarray(b.features), jnp.asarray(b.labels))
    got = list(_feed_over(ds, 16, place=place))
    assert all(isinstance(b.features, jax.Array) for b in got)
    # h2d traffic visible through the gauge family (set by the worker)
    assert registry.get(monitor.FEED_QUEUE_DEPTH_GAUGE) is not None


def test_device_feed_reset_mid_epoch(registry):
    ds = _data(80)
    feed = _feed_over(ds, 16)
    assert feed.has_next()
    feed.next()
    feed.next()  # two batches consumed, three still in flight
    feed.reset()
    got = [b for b in feed]
    ref = [b for b in ListDataSetIterator(ds, 16)]
    assert len(got) == len(ref)
    for g, r in zip(got, ref):
        np.testing.assert_array_equal(np.asarray(g.features), r.features)


def test_device_feed_abandon_race(registry):
    """Mid-epoch abandonment: close() must stop the worker even while
    it is blocked on a full queue, across many interleavings."""
    for trial in range(20):
        ds = _data(200, dseed=trial)
        feed = _feed_over(ds, 8, depth=2)
        k = trial % 5
        for _ in range(k):
            if feed.has_next():
                feed.next()
        if trial % 3 == 0:
            time.sleep(0.002)  # let the worker fill the buffer
        feed.close()
        t = feed._thread
        assert t is None or not t.is_alive(), f"worker leaked on trial {trial}"


def test_device_feed_worker_error_propagates(registry):
    class Boom(DataSetIterator):
        def __init__(self):
            self.i = 0

        def reset(self):
            self.i = 0

        def has_next(self):
            return self.i < 5

        def _next_impl(self):
            self.i += 1
            if self.i == 3:
                raise ValueError("bad record")
            return _data(4)

        def batch(self):
            return 4

    feed = DeviceFeedIterator(Boom(), depth=2)
    got = 0
    with pytest.raises(ValueError, match="bad record"):
        while feed.has_next():
            feed.next()
            got += 1
    assert got == 2  # both good batches arrived before the error


def test_async_iterator_close_stops_worker(registry):
    ds = _data(100)
    it = AsyncDataSetIterator(ListDataSetIterator(ds, 4), queue_size=2)
    assert it.has_next()
    it.next()
    it.close()
    t = it._thread
    assert t is None or not t.is_alive()


# -------------------------------------------------------- shape bucketing

def test_bucketing_pads_only_ragged_tail(registry):
    ds = _data(3 * 16 + 5)
    it = ShapeBucketingIterator(ListDataSetIterator(ds, 16))
    batches = list(it)
    assert [b.num_examples() for b in batches] == [16, 16, 16, 16]
    assert [b.labels_mask is None for b in batches] == [True, True, True, False]
    tail = batches[-1]
    np.testing.assert_array_equal(tail.labels_mask[:5], np.ones(5, np.float32))
    np.testing.assert_array_equal(tail.labels_mask[5:], np.zeros(11, np.float32))
    np.testing.assert_array_equal(tail.features[5:], 0.0)
    assert registry.family_total(monitor.FEED_PADDED_BATCHES_COUNTER) == 1


def test_bucketing_passthrough_for_masked_batches(registry):
    ds = _data(20)
    ds.labels_mask = np.ones(20, np.float32)
    it = ShapeBucketingIterator(ListDataSetIterator(ds, 16))
    batches = list(it)
    assert [b.num_examples() for b in batches] == [16, 4]
    assert registry.family_total(monitor.FEED_PADDED_BATCHES_COUNTER) == 0


def test_bucketing_parity_bitwise(registry):
    """The acceptance bar: padded tail-batch training equals the
    unpadded run — full batches (the same program on both sides) bit
    for bit, the ragged tail (5 rows there, 16 padded rows here: two
    programs by construction) within the last bits XLA:CPU's
    batch-size-dependent reduction order leaves open."""
    ds = _data(3 * 16 + 5, dseed=0)
    a, b = _mlp(), _mlp()
    ca, cb = CollectScoresIterationListener(), CollectScoresIterationListener()
    a.set_listeners(ca)
    b.set_listeners(cb)
    a.fit(ListDataSetIterator(ds, 16), feed_pipeline=False)  # unpadded
    b.fit(ListDataSetIterator(ds, 16), feed_pipeline=True)   # bucketed
    assert ca.scores[:3] == cb.scores[:3], "full-batch scores diverged"
    for _ in range(2):
        a.fit(ListDataSetIterator(ds, 16), feed_pipeline=False)
        b.fit(ListDataSetIterator(ds, 16), feed_pipeline=True)
    assert [i for i, _ in ca.scores] == [i for i, _ in cb.scores]
    np.testing.assert_allclose([s for _, s in ca.scores],
                               [s for _, s in cb.scores],
                               rtol=2 * np.finfo(np.float32).eps, atol=0)
    np.testing.assert_allclose(a.params_flat(), b.params_flat(),
                               rtol=0, atol=6e-8)


def test_bucketing_parity_across_seeds_one_ulp(registry):
    """Semantic exactness across data draws: scores bitwise, params
    within one float32 ulp (reductions over different batch shapes may
    associate differently inside XLA — the irreducible floor)."""
    for dseed in range(4):
        ds = _data(2 * 16 + 7, dseed=dseed)
        a, b = _mlp(seed=11), _mlp(seed=11)
        ca, cb = CollectScoresIterationListener(), CollectScoresIterationListener()
        a.set_listeners(ca)
        b.set_listeners(cb)
        a.fit(ListDataSetIterator(ds, 16), feed_pipeline=False)
        b.fit(ListDataSetIterator(ds, 16), feed_pipeline=True)
        assert ca.scores == cb.scores, f"scores diverged for dseed={dseed}"
        np.testing.assert_allclose(a.params_flat(), b.params_flat(),
                                   rtol=0, atol=6e-8)


def test_bucketing_skipped_for_batch_statistics_layers(registry):
    """BatchNormalization batch moments would be polluted by padded
    rows — the container must fall back to the legacy ragged tail."""
    ds = _data(16 + 5)
    net = _mlp(bn=True)
    assert not net._pad_tail_safe()
    net.fit(ListDataSetIterator(ds, 16), feed_pipeline=True)
    assert registry.family_total(monitor.FEED_PADDED_BATCHES_COUNTER) == 0
    assert np.isfinite(net.score())


# ----------------------------------------------------- deferred score sync

def test_zero_per_iteration_syncs_after_warmup(registry):
    """The acceptance bar: fit() on an unmasked in-memory iterator does
    ZERO per-iteration host syncs after warmup — one batched score
    resolution per fit call (end-of-fit flush), and at most one compile
    across ragged tail batches."""
    ds = _data(3 * 16 + 5)
    net = _mlp()
    net.fit(ListDataSetIterator(ds, 16))  # warmup: compiles both programs
    warm_misses = registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
    assert warm_misses == 2  # full-batch program + ONE canonical tail program
    base = registry.family_total(monitor.SCORE_SYNC_COUNTER)
    epochs = 3
    for _ in range(epochs):
        net.fit(ListDataSetIterator(ds, 16))
    syncs = registry.family_total(monitor.SCORE_SYNC_COUNTER) - base
    iterations = epochs * 4
    assert syncs == epochs, f"{syncs} syncs for {iterations} iterations"
    # no further compiles: the padded tail reuses the canonical program
    assert registry.family_total(monitor.JIT_CACHE_MISS_COUNTER) == warm_misses


def test_pipeline_off_keeps_per_iteration_sync_and_extra_compiles(registry):
    ds = _data(3 * 16 + 5)
    net = _mlp()
    net.fit(ListDataSetIterator(ds, 16), feed_pipeline=False)
    assert registry.family_total(monitor.JIT_CACHE_MISS_COUNTER) == 2
    assert registry.family_total(monitor.SCORE_SYNC_COUNTER) == 4  # one per step


def test_deferred_scores_reach_listeners_exactly(registry):
    """Listeners get every (iteration, score) pair in order, with
    exact values, whether resolution is immediate or deferred."""
    ds = _data(64)
    a, b = _mlp(), _mlp()
    ca = CollectScoresIterationListener(frequency=4)  # tolerates deferral
    cb = CollectScoresIterationListener(frequency=4)
    a.set_listeners(ca)
    b.set_listeners(cb)
    a.fit(ListDataSetIterator(ds, 16), feed_pipeline=True)
    b.fit(ListDataSetIterator(ds, 16), feed_pipeline=False)
    assert ca.scores == cb.scores
    assert [i for i, _ in ca.scores] == [4]  # frequency honored


def test_frequency_one_listener_forces_immediate_resolution(registry):
    """A listener with no declared frequency demands per-iteration
    resolution — legacy semantics preserved for plain callables."""
    ds = _data(48)
    net = _mlp()
    seen = []
    net.set_listeners(lambda m, i, s: seen.append((i, float(s))))
    net.fit(ListDataSetIterator(ds, 16), feed_pipeline=True)
    assert len(seen) == 3
    assert registry.family_total(monitor.SCORE_SYNC_COUNTER) == 3
    assert all(isinstance(s, float) and np.isfinite(s) for _, s in seen)


def test_score_resolves_on_demand(registry):
    ds = _data(32)
    net = _mlp()
    net.fit(ListDataSetIterator(ds, 16))
    s = net.score()
    assert isinstance(s, float) and np.isfinite(s)


def test_host_step_mirror_survives_and_invalidates(registry):
    from deeplearning4j_tpu.optimize.deferred import HOST_STEP_MIRROR, host_step
    ds = _data(32)
    net = _mlp()
    net.fit(ListDataSetIterator(ds, 16))
    assert net.__dict__[HOST_STEP_MIRROR] == 2
    assert host_step(net) == int(net.opt_state["step"]) == 2
    # an external opt_state write (checkpoint restore) invalidates it
    net.opt_state = net.opt_state
    assert HOST_STEP_MIRROR not in net.__dict__
    assert host_step(net) == 2  # lazily re-resolved


def test_deferred_flush_race_single_resolution(registry):
    """Two threads racing flush() on the same sink resolve each pending
    score exactly once (the ring is swapped out before fetching)."""
    from deeplearning4j_tpu.optimize.deferred import DeferredScoreSync
    import jax.numpy as jnp

    class Model:
        listeners = []
        _score = float("nan")

    for trial in range(20):
        m = Model()
        calls = []
        m.listeners = [CollectScoresIterationListener(frequency=1000)]
        sink = DeferredScoreSync(m, capacity=1000)
        for i in range(8):
            sink.push(i + 1, jnp.float32(i))
        m.listeners[0].scores = calls  # capture replays
        ts = [threading.Thread(target=sink.flush) for _ in range(2)]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert len(sink) == 0
        assert m._score == 7.0


# --------------------------------------------------------- graph container

def test_graph_fit_pipeline_single_compile_and_parity(registry):
    from deeplearning4j_tpu.nn.graph import ComputationGraphConfiguration
    from deeplearning4j_tpu.nn.graph import ComputationGraph

    def make():
        conf = (ComputationGraphConfiguration.builder(
                    NeuralNetConfiguration.builder().seed(5).learning_rate(0.1)
                    .updater("sgd").activation("tanh").build())
                .add_inputs("in")
                .add_layer("h", DenseLayer(n_in=4, n_out=8), "in")
                .add_layer("out", OutputLayer(n_in=8, n_out=3, activation="softmax",
                                              loss_function="mcxent"), "h")
                .set_outputs("out")
                .build())
        return ComputationGraph(conf).init()

    ds = _data(2 * 16 + 5, dseed=3)
    a, b = make(), make()
    a.fit(ListDataSetIterator(ds, 16), feed_pipeline=False)
    base = registry.family_total(monitor.JIT_CACHE_MISS_COUNTER)
    b.fit(ListDataSetIterator(ds, 16), feed_pipeline=True)
    misses = registry.family_total(monitor.JIT_CACHE_MISS_COUNTER) - base
    assert misses == 2  # full-batch signature + ONE canonical tail signature
    np.testing.assert_allclose(a.params_flat(), b.params_flat(),
                               rtol=0, atol=6e-8)
    assert np.isfinite(b.score())


# -------------------------------------------------------- parallel wrapper

def test_parallel_allreduce_pipeline_matches_legacy(registry):
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    ds = _data(64, dseed=2)
    a, b = _mlp(), _mlp()
    ParallelWrapper(a, feed_pipeline=False).fit(ListDataSetIterator(ds, 32))
    ParallelWrapper(b, feed_pipeline=True).fit(ListDataSetIterator(ds, 32))
    np.testing.assert_array_equal(a.params_flat(), b.params_flat())
    assert registry.family_total(monitor.H2D_BYTES_COUNTER) > 0


def test_feed_metrics_in_pinned_schema_registry(registry):
    """The feed-pipeline families are known to the telemetry schema
    checker, and a real pipeline run's exposition passes both the
    format and the name-drift validation."""
    import importlib.util
    import os as _os
    script = _os.path.join(_os.path.dirname(__file__), _os.pardir,
                           "scripts", "check_telemetry_schema.py")
    spec = importlib.util.spec_from_file_location("check_telemetry_schema2",
                                                  script)
    schema = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(schema)
    for name in (monitor.H2D_BYTES_COUNTER, monitor.FEED_QUEUE_DEPTH_GAUGE,
                 monitor.FEED_PADDED_BATCHES_COUNTER,
                 monitor.JIT_CACHE_MISS_COUNTER, monitor.SCORE_SYNC_COUNTER):
        assert name in schema.KNOWN_DL4J_METRICS, name
    net = _mlp()
    net.fit(ListDataSetIterator(_data(2 * 16 + 5), 16))
    text = registry.prometheus_text()
    assert "dl4j_score_sync_total" in text
    assert "dl4j_jit_cache_miss_total" in text
    assert "dl4j_feed_padded_batches_total" in text
    assert schema.validate_prometheus_text(text) == []
    assert schema.validate_known_metrics(text) == []
    # drift is flagged
    bad = "# TYPE dl4j_totally_new_thing counter\ndl4j_totally_new_thing 1\n"
    assert schema.validate_known_metrics(bad) != []


def test_parallel_allreduce_pipeline_pads_ragged_for_sharding(registry):
    """A tail batch not divisible by the data axis previously raised in
    shard_batch; bucketing pads it to the canonical (divisible) batch."""
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper

    ds = _data(32 + 5, dseed=4)
    net = _mlp()
    pw = ParallelWrapper(net, feed_pipeline=True)
    pw.fit(ListDataSetIterator(ds, 32))
    assert registry.family_total(monitor.FEED_PADDED_BATCHES_COUNTER) == 1
    assert np.isfinite(net.score())
