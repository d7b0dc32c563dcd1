"""Fault-injection suite: every recovery path in the stack, driven
deliberately.

Doctrine: a recovery path that has never executed is a bug waiting for
an outage. Each test injects ONE fault class through
``deeplearning4j_tpu.faultinject`` (deterministic schedules — no random
flakiness, no wall-clock sleeps in assertions) and pins the recovery
contract:

- torn / bit-flipped checkpoints  → restore falls back to the newest
  VALID unit (zip + sharded);
- NaN step                         → supervisor rollback + LR backoff +
  batch skip, clean ``TrainingDiverged`` give-up, bitwise pass-through
  when no fault fires;
- replica device errors            → quarantine keeps serving
  bitwise-correct results at reduced capacity, probe reinstates;
- broker outage / poison message   → transparent reconnect,
  ``BrokerUnavailable`` (never a silent ``None``), dead-letter routing.
"""

import json
import os
import threading
import time
import zipfile

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import (DeviceFeedIterator,
                                                   ListDataSetIterator,
                                                   bucket_sizes)
from deeplearning4j_tpu.faultinject import (FailingDataSetIterator,
                                            FlakyBroker, InjectedFault,
                                            ReplicaPoison, TornWrites,
                                            corrupt_file, poison_replica,
                                            tear_file)
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.optimize.resumable import ResumableTrainer
from deeplearning4j_tpu.optimize.supervisor import (TrainingDiverged,
                                                    TrainingSupervisor,
                                                    supervisor_enabled)
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.streaming import (BrokerUnavailable, InMemoryBroker,
                                          StreamingInference, StreamingTrainer,
                                          TcpBroker, TcpBrokerServer,
                                          ndarray_from_bytes,
                                          ndarray_to_bytes)
from deeplearning4j_tpu.streaming.pipeline import (publish_dataset,
                                                   publish_stop)
from deeplearning4j_tpu.util import sharded_checkpoint as sc
from deeplearning4j_tpu.util.model_serializer import (CheckpointCorruptError,
                                                      restore_model,
                                                      verify_model_file,
                                                      write_model)

pytestmark = pytest.mark.faultinject

N_IN, N_OUT = 4, 3


def _net(seed=7):
    conf = (NeuralNetConfiguration.builder().seed(seed).learning_rate(0.05)
            .updater("adam").activation("tanh")
            .list()
            .layer(DenseLayer(n_in=N_IN, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=N_OUT, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _batches(rng, n=6, rows=8):
    return [DataSet(rng.standard_normal((rows, N_IN)).astype(np.float32),
                    np.eye(N_OUT, dtype=np.float32)[
                        rng.integers(0, N_OUT, rows)])
            for _ in range(n)]


@pytest.fixture
def fresh_registry():
    prev = monitor.set_registry(monitor.MetricsRegistry())
    yield monitor.get_registry()
    monitor.set_registry(prev)


def _spin_until(cond, timeout=60.0, tick=0.005):
    """Bounded wait on a condition that a background thread flips —
    assertions never sleep blindly; they poll an observable state."""
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() >= deadline:
            return False
        time.sleep(tick)
    return True


# ------------------------------------------------- checkpoint integrity

def test_zip_checkpoint_atomic_and_verified(rng, tmp_path, fresh_registry):
    net = _net()
    net.fit(_batches(rng, 1)[0])
    path = str(tmp_path / "model.zip")
    write_model(net, path)
    assert verify_model_file(path) == []
    with zipfile.ZipFile(path) as z:
        assert "manifest.json" in z.namelist()
    # no temp litter after a successful atomic install
    assert [f for f in os.listdir(tmp_path) if ".tmp" in f] == []
    # bit flip → detected, CheckpointCorruptError (not a random npz error)
    corrupt_file(path, offset=len(open(path, "rb").read()) // 2 - 1)
    assert verify_model_file(path) != []
    with pytest.raises(CheckpointCorruptError):
        restore_model(path)
    assert fresh_registry.family_total(
        monitor.FAULT_CKPT_INTEGRITY_COUNTER) >= 1


def test_zip_write_crash_leaves_previous_checkpoint(rng, tmp_path):
    net = _net()
    path = str(tmp_path / "model.zip")
    write_model(net, path)
    before = open(path, "rb").read()
    net.fit(_batches(rng, 1)[0])
    with TornWrites(crash_on_call=1, path_substr="model.zip"):
        with pytest.raises(InjectedFault):
            write_model(net, path)
    # the installed file is byte-identical to the previous good one
    assert open(path, "rb").read() == before
    assert verify_model_file(path) == []


def test_sharded_restore_falls_back_to_newest_valid(rng, tmp_path,
                                                    fresh_registry):
    net = _net()
    ds = _batches(rng, 1)[0]
    root = str(tmp_path / "hist")
    flats = {}
    for step in (1, 2, 3):
        net.fit(ds)
        sc.save_checkpoint(net, root, keep=3, step=step)
        flats[step] = net.params_flat().copy()
    assert sc.checkpoint_steps(root) == [1, 2, 3]
    # tear the newest unit: truncate a manifest-listed payload file
    newest = os.path.join(root, "ckpt-0000000003")
    manifest = json.load(open(os.path.join(newest, "manifest.json")))
    victim = sorted(manifest["crc32"])[-1]
    tear_file(os.path.join(newest, victim), keep_fraction=0.25)
    restored = sc.restore_checkpoint(root)
    np.testing.assert_array_equal(restored.params_flat(), flats[2])
    assert fresh_registry.family_total(
        monitor.FAULT_CKPT_INTEGRITY_COUNTER) >= 1
    # every unit torn → CheckpointCorruptError, not garbage params
    for step in (1, 2):
        unit = os.path.join(root, f"ckpt-{step:010d}")
        man = json.load(open(os.path.join(unit, "manifest.json")))
        corrupt_file(os.path.join(unit, sorted(man["crc32"])[-1]))
    with pytest.raises(CheckpointCorruptError):
        sc.restore_checkpoint(root)


def test_sharded_save_crash_keeps_previous_unit(rng, tmp_path):
    net = _net()
    ds = _batches(rng, 1)[0]
    single = str(tmp_path / "single")
    net.fit(ds)
    sc.save_checkpoint(net, single)
    good = net.params_flat().copy()
    net.fit(ds)
    # crash on the FIRST install rename of the checkpoint unit
    with TornWrites(crash_on_call=1, path_substr="single"):
        with pytest.raises(InjectedFault):
            sc.save_checkpoint(net, single)
    restored = sc.restore_checkpoint(single)
    np.testing.assert_array_equal(restored.params_flat(), good)


def test_resumable_tolerates_half_written_unit(rng, tmp_path, caplog):
    net = _net()
    ck = str(tmp_path / "ck")
    t1 = ResumableTrainer(net, ck, checkpoint_every=1)
    t1.fit(ListDataSetIterator(
        DataSet(np.concatenate([b.features for b in _batches(rng, 4)]),
                np.concatenate([b.labels for b in _batches(rng, 4)])), 8),
        epochs=1, max_steps=2)
    # sabotage the newest unit: model.zip torn mid-write
    unit = os.path.join(ck, "checkpoint")
    tear_file(os.path.join(unit, "model.zip"), keep_fraction=0.3)
    t2 = ResumableTrainer(_net(), ck, checkpoint_every=1)
    model = t2.resume_or_start()  # warns + starts fresh, never raises
    assert model is t2.model
    assert t2.steps_done == 0
    assert any("unreadable" in r.message or "starting fresh" in r.message
               for r in caplog.records)


# --------------------------------------------------- supervisor (training)

def test_supervisor_noop_run_is_bitwise_identical(rng):
    batches = _batches(rng)
    supervised, plain = _net(), _net()
    sup = TrainingSupervisor(supervised)
    scores_sup, scores_plain = [], []
    for ds in batches:
        sup.step(ds)
        scores_sup.append(supervised.score())
    for ds in batches:
        plain.fit(ds)
        scores_plain.append(plain.score())
    assert scores_sup == scores_plain  # bitwise: exact float equality
    np.testing.assert_array_equal(supervised.params_flat(),
                                  plain.params_flat())
    assert sup.rollbacks == 0 and sup.report()["events"] == []


def test_supervisor_nan_rollback_lr_backoff_and_skip(rng, fresh_registry):
    batches = _batches(rng)
    net = _net()
    base_lr = net.gc.learning_rate
    it = FailingDataSetIterator(
        ListDataSetIterator(
            DataSet(np.concatenate([b.features for b in batches]),
                    np.concatenate([b.labels for b in batches])), 8),
        nan_at={2})
    sup = TrainingSupervisor(net, max_rollbacks=3)
    report = sup.fit(it, epochs=1)
    assert report["rollbacks"] == 1
    assert report["batches_skipped"] == [2]
    assert report["events"][0]["action"] == "rollback"
    assert net.gc.learning_rate == pytest.approx(base_lr * 0.5)
    assert np.isfinite(net.score())
    assert np.isfinite(net.params_flat()).all()
    assert fresh_registry.family_total(monitor.FAULT_ROLLBACKS_COUNTER) == 1
    assert fresh_registry.get(monitor.FAULT_EVENTS_COUNTER,
                              domain="training").value == 1
    json.dumps(report)  # structured = JSON-serializable


def test_supervisor_rollback_recovers_last_good_params(rng):
    """After a rollback the params are EXACTLY the pre-NaN-batch params:
    train a twin on the same stream minus the poison batch."""
    batches = _batches(rng, n=4)
    nan_batch = DataSet(np.full((8, N_IN), np.nan, np.float32),
                        batches[0].labels)
    guarded, twin = _net(), _net()
    sup = TrainingSupervisor(guarded)
    for ds in batches[:2] + [nan_batch] + batches[2:]:
        sup.step(ds)
    # the twin never sees the poison batch; after the rollback the
    # guarded run continues from the same params BUT at the backed-off
    # LR, so compare at the rollback point: replay twin to batch 2
    for ds in batches[:2]:
        twin.fit(ds)
    twin_flat = twin.params_flat()
    # guarded net at the moment of rollback had exactly these params —
    # verify by rolling its LR back up and replaying the remaining
    # batches on the twin with the backed-off LR
    twin.gc.learning_rate *= sup.lr_backoff
    twin._jits = {}
    for ds in batches[2:]:
        twin.fit(ds)
    np.testing.assert_array_equal(guarded.params_flat(), twin.params_flat())
    assert sup.rollbacks == 1


def test_supervisor_gives_up_with_structured_report(rng, fresh_registry):
    net = _net()
    nan_batch = DataSet(np.full((8, N_IN), np.nan, np.float32),
                        np.eye(N_OUT, dtype=np.float32)[
                            np.zeros(8, np.int64)])
    sup = TrainingSupervisor(net, max_rollbacks=2)
    with pytest.raises(TrainingDiverged) as exc:
        for _ in range(10):
            sup.step(nan_batch)
    report = exc.value.report
    assert report["rollbacks"] == 3 and report["max_rollbacks"] == 2
    assert report["events"][-1]["action"] == "give_up"
    json.dumps(report)
    assert fresh_registry.family_total(monitor.FAULT_ROLLBACKS_COUNTER) == 3


def test_supervisor_escape_hatch_env(rng, monkeypatch):
    monkeypatch.setenv("DL4J_TPU_DISABLE_SUPERVISOR", "1")
    assert not supervisor_enabled()
    net = _net()
    sup = TrainingSupervisor(net)
    assert not sup.enabled
    nan_batch = DataSet(np.full((8, N_IN), np.nan, np.float32),
                        np.eye(N_OUT, dtype=np.float32)[
                            np.zeros(8, np.int64)])
    sup.step(nan_batch)  # pass-through: no rollback, NaN flows
    assert not np.isfinite(net.score())
    assert sup.rollbacks == 0


def test_supervisor_policy_survives_resume(rng, tmp_path):
    """ResumableTrainer integration: the rollback/LR state rides the
    cursor, so a resumed run replays the same policy."""
    feats = np.concatenate([b.features for b in _batches(rng, 4)])
    labels = np.concatenate([b.labels for b in _batches(rng, 4)])

    def make_it():
        return FailingDataSetIterator(
            ListDataSetIterator(DataSet(feats, labels), 8), nan_at={1})

    ck = str(tmp_path / "ck")
    net1 = _net()
    t1 = ResumableTrainer(net1, ck, checkpoint_every=1)
    sup1 = TrainingSupervisor(net1, max_rollbacks=3)
    t1.fit(make_it(), epochs=1, max_steps=3, supervisor=sup1)
    assert sup1.rollbacks == 1
    base_lr = _net().gc.learning_rate

    t2 = ResumableTrainer(_net(), ck, checkpoint_every=1)
    sup2 = TrainingSupervisor(t2.model, max_rollbacks=3)
    t2.resume_or_start(supervisor=sup2)
    assert sup2.model is t2.model  # rebound to the restored model
    assert sup2.rollbacks == 1
    assert sup2.model.gc.learning_rate == pytest.approx(base_lr * 0.5)


# ------------------------------------------------- feed-pipeline close race

def test_device_feed_close_after_worker_death(rng):
    """Regression: close() after the staging worker died must neither
    hang nor raise; a fresh iteration afterwards works."""
    data = ListDataSetIterator(
        DataSet(rng.standard_normal((32, N_IN)).astype(np.float32),
                np.eye(N_OUT, dtype=np.float32)[
                    rng.integers(0, N_OUT, 32)]), 8)
    calls = {"n": 0}

    def exploding_place(batch):
        calls["n"] += 1
        if calls["n"] >= 2:
            raise InjectedFault("staging died")
        return batch

    feed = DeviceFeedIterator(data, depth=1, place=exploding_place)
    assert feed.has_next()
    feed.next()
    with pytest.raises(InjectedFault):
        while feed.has_next():  # worker error surfaces on the consumer
            feed.next()
    feed.close()  # after the death: returns promptly, no second raise
    assert feed._thread is None
    # close again (double-close is a no-op, not a double-raise)
    feed.close()
    # the iterator remains usable: reset semantics replay the source
    calls["n"] = -10_000  # disarm
    assert feed.has_next()


def test_device_feed_close_without_consuming_after_error(rng):
    """The worker dies while the consumer never pulls: close() must not
    deadlock against the full staging queue."""
    data = ListDataSetIterator(
        DataSet(rng.standard_normal((32, N_IN)).astype(np.float32),
                np.eye(N_OUT, dtype=np.float32)[
                    rng.integers(0, N_OUT, 32)]), 8)

    def exploding_place(batch):
        raise InjectedFault("staging died immediately")

    feed = DeviceFeedIterator(data, depth=1, place=exploding_place)
    with pytest.raises(InjectedFault):
        feed.has_next()  # starts the worker, which dies at once
    feed.close()
    assert feed._thread is None


def test_async_iterator_propagates_source_error(rng):
    """AsyncDataSetIterator used to silently truncate the epoch when the
    source raised; now the error reaches the consumer, and close() after
    it is clean."""
    from deeplearning4j_tpu.datasets.iterators import AsyncDataSetIterator

    inner = FailingDataSetIterator(
        ListDataSetIterator(
            DataSet(rng.standard_normal((32, N_IN)).astype(np.float32),
                    np.eye(N_OUT, dtype=np.float32)[
                        rng.integers(0, N_OUT, 32)]), 8),
        raise_at={1})
    it = AsyncDataSetIterator(inner, queue_size=2)
    with pytest.raises(InjectedFault):
        while it.has_next():
            it.next()
    it.close()
    assert it._thread is None


# --------------------------------------------------- serving (quarantine)

def _drive_until_quarantined(eng, net, rng, max_requests=200):
    """Submit traffic (verifying every result bitwise) until the poisoned
    replica trips its quarantine; bounded, no blind sleeps."""
    for i in range(max_requests):
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        np.testing.assert_array_equal(eng.output(x, timeout=60),
                                      np.asarray(net.output(x)))
        if eng.stats()["quarantined"]:
            return i + 1
    raise AssertionError("poisoned replica never quarantined")


def test_replica_quarantine_keeps_serving_bitwise(rng, fresh_registry,
                                                  assert_bucket_exact):
    net = _net()
    import jax
    dev = jax.devices()[0]
    # two replicas on one device: the quarantine logic only cares about
    # worker identity, so this exercises redispatch without multi-chip
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            devices=[dev, dev],
                            probe_interval_ms=3600_000.0)  # probe_now only
    try:
        eng.warmup([(N_IN,)])
        poison = poison_replica(eng, replica=0, failures=2)
        served = _drive_until_quarantined(eng, net, rng)
        s = eng.stats()
        assert s["quarantined"] == [0] and s["degraded"]
        assert s["healthy_replicas"] == 1
        assert poison.hits == 2  # initial attempt + one same-replica retry
        assert fresh_registry.get(
            monitor.FAULT_QUARANTINED_GAUGE).value == 1
        assert fresh_registry.get(monitor.FAULT_EVENTS_COUNTER,
                                  domain="serving").value >= 2
        # degraded engine keeps serving bitwise-correct results
        for _ in range(5):
            x = rng.standard_normal((3, N_IN)).astype(np.float32)
            assert_bucket_exact(eng.output(x, timeout=60), net, x,
                                eng.buckets)
        # poison exhausted → the probe passes → replica reinstated
        assert _spin_until(
            lambda: (eng.probe_now() or not eng.stats()["quarantined"]))
        s = eng.stats()
        assert s["quarantined"] == [] and not s["degraded"]
        assert fresh_registry.get(
            monitor.FAULT_QUARANTINED_GAUGE).value == 0
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        assert_bucket_exact(eng.output(x, timeout=60), net, x, eng.buckets)
        assert served >= 1
    finally:
        eng.shutdown()  # recovered faults must NOT poison shutdown


def test_all_replicas_down_fails_futures_then_heals(rng):
    net = _net()
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            replicas=1, probe_interval_ms=3600_000.0)
    eng.warmup([(N_IN,)])
    poison = poison_replica(eng, replica=0, failures=2)
    x = rng.standard_normal((2, N_IN)).astype(np.float32)
    fut = eng.submit(x)
    # futures are never stranded: with no survivor the error lands here
    with pytest.raises(InjectedFault):
        fut.result(timeout=60)
    assert eng.stats()["quarantined"] == [0]
    assert eng.stats()["healthy_replicas"] == 0
    # poison exhausted → probe heals → the engine serves again
    assert _spin_until(
        lambda: (eng.probe_now() or not eng.stats()["quarantined"]))
    np.testing.assert_array_equal(eng.output(x, timeout=60),
                                  np.asarray(net.output(x)))
    with pytest.raises(InjectedFault):
        eng.shutdown()  # first worker error re-raised (futures carried it)


def test_healthz_reports_quarantine_degraded(rng):
    import http.client

    from deeplearning4j_tpu.ui.server import UiServer
    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage

    net = _net()
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            replicas=1, probe_interval_ms=3600_000.0)
    eng.warmup([(N_IN,)])
    server = UiServer(InMemoryStatsStorage(), port=0,
                      registry=monitor.MetricsRegistry(),
                      inference_engine=eng).start()
    try:
        poison_replica(eng, replica=0, failures=2)
        fut = eng.submit(np.zeros((2, N_IN), np.float32))
        with pytest.raises(InjectedFault):
            fut.result(timeout=60)

        def healthz():
            conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                              timeout=10)
            conn.request("GET", "/healthz")
            resp = conn.getresponse()
            body = json.loads(resp.read())
            conn.close()
            return resp.status, body

        status, body = healthz()
        assert status == 503
        assert body["status"] == "degraded"
        assert body["inference"]["quarantined"] == [0]
        assert _spin_until(
            lambda: (eng.probe_now() or not eng.stats()["quarantined"]))
        status, body = healthz()
        assert status == 200 and body["status"] == "ok"
    finally:
        server.stop()
        try:
            eng.shutdown()
        except InjectedFault:
            pass


# ---------------------------------------------------- transport resilience

def test_tcp_broker_reconnects_transparently():
    srv = TcpBrokerServer(poll_timeout=0.05).start()
    try:
        host, port = srv.address
        broker = TcpBroker(host, port, max_retries=3, backoff_base_s=0.01)
        broker.publish("t", b"one")
        assert broker.consume("t", timeout=5) == b"one"
        broker._sock.close()  # sever the connection underneath
        broker.publish("t", b"two")  # reconnect + resend, no caller error
        assert broker.consume("t", timeout=5) == b"two"
        # a genuine poll timeout still returns None (healthy broker)
        assert broker.consume("t", timeout=0.2) is None
    finally:
        srv.stop()


def test_tcp_broker_unavailable_after_bounded_retries(fresh_registry):
    srv = TcpBrokerServer(poll_timeout=0.05).start()
    host, port = srv.address
    broker = TcpBroker(host, port, max_retries=2, backoff_base_s=0.01)
    broker.publish("t", b"x")
    srv.stop()
    broker._sock.close()
    # a dead broker is an EXCEPTION, never a None masquerading as idle
    with pytest.raises(BrokerUnavailable):
        broker.consume("t", timeout=5)
    assert fresh_registry.get(monitor.FAULT_EVENTS_COUNTER,
                              domain="transport").value >= 1
    # constructing against a dead broker is also bounded
    with pytest.raises(BrokerUnavailable):
        TcpBroker(host, port, max_retries=1, backoff_base_s=0.01,
                  connect_timeout=0.5)


def test_flaky_broker_surfaces_as_broker_error(rng):
    """A FlakyBroker transport error kills neither silently nor
    ambiguously: StreamingTrainer surfaces it on join()."""
    broker = FlakyBroker(InMemoryBroker(), fail_consumes={1},
                         exc=BrokerUnavailable)
    net = _net()
    x = rng.standard_normal((8, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 8)]
    publish_dataset(broker, "train", DataSet(x, y))
    trainer = StreamingTrainer(net, broker, "train", batch_size=8,
                               idle_timeout=30.0).start()
    with pytest.raises(BrokerUnavailable):
        trainer.join(timeout=60)
    assert broker.faults_injected == 1


def test_streaming_trainer_dead_letters_and_keeps_training(
        rng, fresh_registry):
    broker = InMemoryBroker()
    net = _net()
    poison = b"\x00not an npz at all"
    x = rng.standard_normal((8, N_IN)).astype(np.float32)
    y = np.eye(N_OUT, dtype=np.float32)[rng.integers(0, N_OUT, 8)]
    broker.publish("train", poison)
    publish_dataset(broker, "train", DataSet(x, y))
    broker.publish("train", poison)
    publish_dataset(broker, "train", DataSet(x, y))
    publish_stop(broker, "train")
    trainer = StreamingTrainer(net, broker, "train", batch_size=8)
    assert trainer.run() == 2  # both good batches trained
    # both poison payloads are on the DLQ, byte-identical, in order
    assert broker.consume("train.deadletter", timeout=5) == poison
    assert broker.consume("train.deadletter", timeout=5) == poison
    assert fresh_registry.get(monitor.FAULT_DEAD_LETTER_COUNTER,
                              topic="train").value == 2


def test_streaming_inference_dead_letters_poison_requests(
        rng, fresh_registry, assert_bucket_exact):
    broker = InMemoryBroker()
    net = _net()
    xs = [rng.standard_normal((2, N_IN)).astype(np.float32)
          for _ in range(3)]
    broker.publish("in", b"poison request")
    for x in xs:
        broker.publish("in", ndarray_to_bytes(x))
    publish_stop(broker, "in")
    serve = StreamingInference(net, broker, "in", "out")
    assert serve.run() == 3
    # good requests answered IN ORDER despite the interleaved poison
    for x in xs:
        pred = ndarray_from_bytes(broker.consume("out", timeout=5))
        assert_bucket_exact(pred, net, x, bucket_sizes(serve.max_batch_size))
    assert broker.consume("in.deadletter", timeout=5) == b"poison request"
    assert fresh_registry.get(monitor.FAULT_DEAD_LETTER_COUNTER,
                              topic="in").value == 1


# --------------------------------------------------------- schema pinning

def test_fault_metric_families_pinned_in_schema(fresh_registry):
    import scripts.check_telemetry_schema as schema

    monitor.record_fault("training")
    monitor.record_fault("serving")
    monitor.record_fault("transport")
    monitor.record_fault("checkpoint")
    reg = fresh_registry
    reg.counter(monitor.FAULT_ROLLBACKS_COUNTER, "h").inc()
    reg.gauge(monitor.FAULT_QUARANTINED_GAUGE, "h").set(0)
    reg.counter(monitor.FAULT_DEAD_LETTER_COUNTER, "h", topic="t").inc()
    reg.counter(monitor.FAULT_CKPT_INTEGRITY_COUNTER, "h").inc()
    text = reg.prometheus_text()
    assert schema.validate_prometheus_text(text) == []
    assert schema.validate_known_metrics(text) == []
    for name in (monitor.FAULT_EVENTS_COUNTER,
                 monitor.FAULT_ROLLBACKS_COUNTER,
                 monitor.FAULT_QUARANTINED_GAUGE,
                 monitor.FAULT_DEAD_LETTER_COUNTER,
                 monitor.FAULT_CKPT_INTEGRITY_COUNTER):
        assert name in schema.KNOWN_DL4J_METRICS
