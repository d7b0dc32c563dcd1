"""Horizontal serving tier tests (deeplearning4j_tpu/serving/).

The ISSUE-6 battery, all deterministic (explicit fault seams, bounded
spins on observable state, no blind sleeps in assertions):

- routed classify/generate results are bitwise the inline run;
- **kill-an-engine failover**: with 3 endpoints under concurrent load,
  killing one mid-flight loses ZERO requests (every future resolves
  through failover), the router ejects the dead endpoint, and
  reinstates it after recovery (half-open probe);
- hedged retry: a stalled endpoint's request resolves from the hedge,
  the stalled endpoint's late reply is dropped (no duplicate
  delivery), exactly one hedge is counted;
- deadline admission: an unmeetable deadline sheds with
  :class:`RetryAfter` (retry_after_s > 0) BEFORE any future exists —
  nothing strands — and lower priority classes shed earlier;
- session affinity keeps a multi-burst decode stream on one endpoint
  and re-pins when that endpoint dies;
- broker liveness: ``ping()`` / ``last_seen`` / server ``peers()``;
- ``/healthz`` liveness-vs-readiness split + fleet aggregation;
- ScalePolicy add/remove decisions with hysteresis, applied by
  LocalFleet;
- dl4j_router_* Prometheus schema pinning.
"""

import json
import time

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.faultinject import NetworkPartition, kill_endpoint
from deeplearning4j_tpu.models.zoo.transformer import gpt
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.inference import ParallelInference
from deeplearning4j_tpu.serving import (EngineWorker, InferenceRouter,
                                        LocalEndpoint, LocalFleet,
                                        RemoteEndpoint, RetryAfter,
                                        ScaleDecision, ScalePolicy)
from deeplearning4j_tpu.streaming.broker import (InMemoryBroker, TcpBroker,
                                                 TcpBrokerServer)

pytestmark = pytest.mark.faultinject

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
def net():
    return _net()


def _mk_fleet(net, router=None, n=3, **kw):
    def engine_factory():
        return ParallelInference(net, max_batch_size=8, max_latency_ms=1.0,
                                 replicas=1)
    fleet = LocalFleet(engine_factory, router=router, heartbeat_s=0.05,
                       request_timeout_s=kw.pop("request_timeout_s", 2.0),
                       heartbeat_timeout_s=0.5, **kw)
    for _ in range(n):
        fleet.add_endpoint()
    assert fleet.wait_ready(10)
    return fleet


# ------------------------------------------------------- broker liveness

def test_broker_ping_and_last_seen():
    srv = TcpBrokerServer().start()
    try:
        host, port = srv.address
        c = TcpBroker(host, port, max_retries=0)
        assert c.last_seen is None
        rtt = c.ping()
        assert rtt >= 0.0 and c.last_seen is not None
        t0 = c.last_seen
        c.publish("t", b"x")
        assert c.last_seen >= t0
        # the server tracked the peer's activity
        peers = srv.peers()
        assert len(peers) == 1
        c.close()
    finally:
        srv.stop()


def test_broker_ping_dead_transport_raises():
    from deeplearning4j_tpu.streaming.broker import BrokerUnavailable
    srv = TcpBrokerServer().start()
    host, port = srv.address
    c = TcpBroker(host, port, max_retries=0, backoff_base_s=1e-3)
    assert c.ping() >= 0.0
    srv.stop()
    # sever the established connection the way a broker-host death
    # would (the threading server keeps accepted sockets alive past
    # stop(), so drop the client side deterministically)
    c._drop()
    with pytest.raises(BrokerUnavailable):
        c.ping()
    c.close()
    # and a fresh client against the dead address raises at connect
    with pytest.raises(BrokerUnavailable):
        TcpBroker(host, port, max_retries=0, backoff_base_s=1e-3)


def test_inmemory_broker_ping():
    b = InMemoryBroker()
    assert b.last_seen is None
    assert b.ping() >= 0.0
    assert b.last_seen is not None


# ------------------------------------------------------- routed identity

def test_routed_classify_bitwise_and_remote_generate(net, rng,
                                                     fresh_registry):
    router = InferenceRouter(per_try_timeout_s=5.0)
    fleet = _mk_fleet(net, router)
    try:
        x = rng.standard_normal((3, N_IN)).astype(np.float32)
        inline = np.asarray(net.output(x))
        routed = router.output(x, timeout=30)
        np.testing.assert_array_equal(routed, inline)
    finally:
        fleet.shutdown()


def test_routed_generate_matches_solo(rng, fresh_registry):
    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
            compute_dtype="float32", learning_rate=0.01).init()
    router = InferenceRouter(per_try_timeout_s=30.0)
    fleet = _mk_fleet(g, router, n=2, request_timeout_s=30.0)
    try:
        prompt = rng.integers(0, 11, (2, 3))
        solo = np.asarray(g.generate(prompt, 6))
        routed = router.generate(prompt, 6, timeout=60)
        np.testing.assert_array_equal(routed, solo)
    finally:
        fleet.shutdown()


# --------------------------------------------- kill-an-engine failover

def test_kill_one_of_three_loses_zero_requests(net, rng, fresh_registry):
    """The acceptance scenario: 3 endpoints, concurrent load, one
    killed mid-flight → every future resolves via failover, the victim
    is marked out of the pool, and after recovery + probe it rejoins."""
    router = InferenceRouter(per_try_timeout_s=1.0, eject_backoff_s=0.1,
                             max_attempts=4)
    fleet = _mk_fleet(net, router, n=3, request_timeout_s=1.0)
    try:
        x = rng.standard_normal((2, N_IN)).astype(np.float32)
        inline = np.asarray(net.output(x))
        # warm the routing plane so every endpoint has seen traffic
        for _ in range(6):
            router.output(x, timeout=30)
        victim = fleet.names()[0]
        futs = [router.submit(x) for _ in range(10)]
        kill_endpoint(fleet, victim)
        futs += [router.submit(x) for _ in range(30)]
        results = [f.result(timeout=30) for f in futs]  # ZERO lost
        assert len(results) == 40
        for r in results:
            np.testing.assert_array_equal(r, inline)
        # the victim is positively out of the pool (heartbeats stale
        # and/or ejected after its timeouts)
        assert _spin_until(
            lambda: not router.fleet_snapshot()["endpoints"][victim]["in_pool"])
        snap = router.fleet_snapshot()
        assert snap["healthy_endpoints"] == 2 and snap["degraded"]
        # recovery: restart + collapse the ejection backoff; traffic
        # probes the half-open endpoint back into the pool
        fleet.restart(victim)
        assert _spin_until(
            lambda: router.fleet_snapshot()["endpoints"][victim]["alive"])
        router.probe_now()
        for _ in range(10):
            router.output(x, timeout=30)
        assert _spin_until(
            lambda: router.fleet_snapshot()["endpoints"][victim]["in_pool"])
        assert not router.fleet_snapshot()["degraded"]
    finally:
        fleet.shutdown(drain=False)


def test_killed_endpoint_requests_fail_over_not_strand(net, rng,
                                                       fresh_registry):
    """Requests already accepted by the killed worker (consumed, never
    replied) resolve through the endpoint timeout → router failover:
    the in-flight path, not just the not-yet-dispatched one."""
    router = InferenceRouter(per_try_timeout_s=0.3, eject_backoff_s=0.1,
                             max_attempts=4)
    fleet = _mk_fleet(net, router, n=2, request_timeout_s=0.3)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        inline = np.asarray(net.output(x))
        for _ in range(4):
            router.output(x, timeout=30)
        victim = fleet.names()[0]
        # kill, then immediately race a burst in — some will be routed
        # to the dead endpoint before its heartbeat goes stale
        kill_endpoint(fleet, victim)
        futs = [router.submit(x) for _ in range(20)]
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=30), inline)
        assert monitor.get_registry().family_total(
            monitor.ROUTER_FAILOVERS_COUNTER) >= 0  # may be 0 if hb won
    finally:
        fleet.shutdown(drain=False)


# ------------------------------------------------------------- hedging

class _StallingEndpoint(LocalEndpoint):
    """LocalEndpoint whose replies are withheld until released — the
    deterministic stand-in for a wedged-but-alive engine."""

    def __init__(self, engine, name):
        super().__init__(engine, name)
        import threading
        self.release = threading.Event()
        self.submitted = 0

    def submit(self, x, timeout_s=None):
        from concurrent.futures import Future
        import threading
        self.submitted += 1
        inner = self.engine.submit(x)
        out = Future()

        def hold():
            r = inner.result()
            self.release.wait(30)
            if not out.done():
                out.set_result(r)
        threading.Thread(target=hold, daemon=True).start()
        return out


def test_hedged_request_wins_without_duplicate_delivery(net, rng,
                                                        fresh_registry):
    slow_eng = ParallelInference(net, max_batch_size=4, replicas=1)
    fast_eng = ParallelInference(net, max_batch_size=4, replicas=1)
    slow = _StallingEndpoint(slow_eng, "slow")
    fast = LocalEndpoint(fast_eng, "fast")
    # deterministic: the stalled endpoint is the ONLY one at submit
    # time (primary dispatch guaranteed), the fast one arrives before
    # the hedge timer fires and becomes the hedge target
    router = InferenceRouter([slow], hedge_after_ms=30.0, max_attempts=2)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        inline = np.asarray(net.output(x))
        fut = router.submit(x)
        assert slow.submitted == 1
        router.add_endpoint(fast)
        y = fut.result(timeout=30)  # resolved by the hedge
        np.testing.assert_array_equal(y, inline)
        reg = monitor.get_registry()
        assert reg.family_total(monitor.ROUTER_HEDGES_COUNTER) == 1
        # exactly one delivery counted end-to-end (first reply won)
        assert reg.get(monitor.ROUTER_LATENCY_HISTOGRAM).count == 1
        # no duplicate delivery: releasing the stalled reply must not
        # change the resolved future
        slow.release.set()
        assert _spin_until(lambda: slow.release.is_set())
        time.sleep(0.05)  # let the late reply land (and be dropped)
        np.testing.assert_array_equal(fut.result(), y)
        assert reg.get(monitor.ROUTER_LATENCY_HISTOGRAM).count == 1
    finally:
        router.close()
        slow_eng.shutdown()
        fast_eng.shutdown()


# -------------------------------------------------- deadline admission

def test_deadline_shed_returns_retry_after(net, rng, fresh_registry):
    ep = LocalEndpoint(ParallelInference(net, max_batch_size=4, replicas=1),
                       "e0")
    router = InferenceRouter([ep])
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        for _ in range(3):  # seed the EWMA so the estimate is nonzero
            router.output(x, timeout=30)
        snap = router.fleet_snapshot()
        assert snap["endpoints"]["e0"]["ewma_ms"] > 0
        with pytest.raises(RetryAfter) as ei:
            router.submit(x, deadline_ms=1e-6)
        assert ei.value.retry_after_s > 0
        reg = monitor.get_registry()
        assert reg.family_total(monitor.ROUTER_SHED_COUNTER) == 1
        # shed happened AT ADMISSION: no future was created, so nothing
        # can strand; the engine never saw the request
        assert router.fleet_snapshot()["endpoints"]["e0"]["inflight"] == 0
        # a no-deadline request still flows
        np.testing.assert_array_equal(router.output(x, timeout=30),
                                      np.asarray(net.output(x)))
    finally:
        router.close()
        ep.close()


def test_priority_classes_shed_low_first(net, rng, fresh_registry):
    ep = LocalEndpoint(ParallelInference(net, max_batch_size=4, replicas=1),
                       "e0")
    router = InferenceRouter([ep])
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        for _ in range(3):
            router.output(x, timeout=30)
        ewma = router.fleet_snapshot()["endpoints"]["e0"]["ewma_ms"]
        # deadline between best_effort's 0.4x headroom and
        # interactive's 1.0x: interactive admits, best_effort sheds
        deadline = ewma / 0.6
        np.testing.assert_array_equal(
            router.submit(x, deadline_ms=deadline,
                          priority="interactive").result(timeout=30),
            np.asarray(net.output(x)))
        with pytest.raises(RetryAfter):
            router.submit(x, deadline_ms=deadline, priority="best_effort")
    finally:
        router.close()
        ep.close()


def test_no_endpoint_sheds(fresh_registry):
    router = InferenceRouter([])
    with pytest.raises(RetryAfter):
        router.submit(np.zeros((1, N_IN), np.float32))
    assert monitor.get_registry().family_total(
        monitor.ROUTER_SHED_COUNTER) == 1


# ---------------------------------------------------- session affinity

def test_decode_session_sticks_to_one_endpoint(rng, fresh_registry):
    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
            compute_dtype="float32", learning_rate=0.01).init()
    router = InferenceRouter(per_try_timeout_s=30.0)
    fleet = _mk_fleet(g, router, n=3, request_timeout_s=30.0)
    try:
        prompt = rng.integers(0, 11, (1, 3))
        solo = np.asarray(g.generate(prompt, 4))
        for burst in range(4):  # multi-burst decode stream
            y = router.generate(prompt, 4, session="conv-1", timeout=60)
            np.testing.assert_array_equal(y, solo)
        pinned = router.session_endpoint("conv-1")
        assert pinned is not None
        served = {n: fleet.endpoint(n).stats().get("served", 0)
                  for n in fleet.names()}
        # all 4 bursts landed on the pinned endpoint (heartbeats lag,
        # so spin until its served count catches up)
        assert _spin_until(lambda: fleet.endpoint(pinned).stats()
                           .get("served", 0) >= 4)
        for name in fleet.names():
            if name != pinned:
                assert fleet.endpoint(name).stats().get("served", 0) == 0, \
                    served
    finally:
        fleet.shutdown()


def test_affinity_repins_when_endpoint_dies(net, rng, fresh_registry):
    router = InferenceRouter(per_try_timeout_s=0.5, eject_backoff_s=0.1,
                             max_attempts=4)
    fleet = _mk_fleet(net, router, n=2, request_timeout_s=0.5)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        router.submit(x, session="s").result(timeout=30)
        first = router.session_endpoint("s")
        kill_endpoint(fleet, first)
        assert _spin_until(
            lambda: not router.fleet_snapshot()["endpoints"][first]["in_pool"])
        router.submit(x, session="s").result(timeout=30)
        second = router.session_endpoint("s")
        assert second is not None and second != first
    finally:
        fleet.shutdown(drain=False)


# --------------------------------------------------- drain-for-shutdown

def test_remove_endpoint_drains_without_loss(net, rng, fresh_registry):
    router = InferenceRouter(per_try_timeout_s=10.0)
    fleet = _mk_fleet(net, router, n=2, request_timeout_s=10.0)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        inline = np.asarray(net.output(x))
        futs = [router.submit(x) for _ in range(16)]
        victim = fleet.names()[0]
        fleet.remove_endpoint(victim)  # drains: zero lost requests
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=30), inline)
        assert victim not in router.endpoints()
    finally:
        fleet.shutdown()


def test_engine_drain_contract(net, rng):
    eng = ParallelInference(net, max_batch_size=4, max_latency_ms=1.0,
                            replicas=1)
    try:
        futs = [eng.submit(rng.standard_normal((1, N_IN)).astype(np.float32))
                for _ in range(8)]
        assert eng.drain(timeout=30)
        assert all(f.done() for f in futs)
        assert eng.stats()["inflight"] == 0
    finally:
        eng.shutdown()


# -------------------------------------------------- network partitions

def test_partitioned_heartbeats_mark_endpoint_dead(net, rng,
                                                   fresh_registry):
    broker = InMemoryBroker()
    part = NetworkPartition(broker, topic_substr=".hb", silent=True)
    eng = ParallelInference(net, max_batch_size=4, replicas=1)
    worker = EngineWorker(eng, broker, "svc-p", heartbeat_s=0.05)
    ep = RemoteEndpoint(part, "svc-p", request_timeout_s=1.0,
                        heartbeat_timeout_s=0.3)
    try:
        assert _spin_until(ep.alive, timeout=10)
        part.partition()  # heartbeats black-hole endpoint-side
        assert _spin_until(lambda: not ep.alive(), timeout=10)
        assert part.dropped > 0
        part.heal()
        assert _spin_until(ep.alive, timeout=10)
    finally:
        ep.close()
        worker.kill()
        eng.shutdown(drain=False)


# ----------------------------------------------------------- autoscale

def test_scale_policy_decisions_are_deterministic():
    pol = ScalePolicy(min_endpoints=1, max_endpoints=4,
                      target_queue_per_endpoint=4.0, queue_low=0.5,
                      p99_high_ms=100.0, cooldown_s=10.0)

    def snap(total, healthy, depth, p99=None, eps=None):
        return {"total_endpoints": total, "healthy_endpoints": healthy,
                "queue_depth": depth, "p99_ms": p99,
                "endpoints": eps or {}}

    # backlog over target → add
    d = pol.decide(snap(2, 2, 20.0), now=0.0)
    assert d == [ScaleDecision("add", None, d[0].reason)]
    # cooldown gates the next decision
    assert pol.decide(snap(2, 2, 20.0), now=5.0) == []
    # p99 breach alone also adds
    assert pol.decide(snap(2, 2, 0.0, p99=250.0),
                      now=20.0)[0].action == "add"
    # idle fleet shrinks to the least-loaded member, not below min
    eps = {"a": {"in_pool": True, "inflight": 3, "stats": {"queue_depth": 1}},
           "b": {"in_pool": True, "inflight": 0, "stats": {"queue_depth": 0}}}
    d = pol.decide(snap(2, 2, 0.0, p99=10.0, eps=eps), now=40.0)
    assert d[0].action == "remove" and d[0].endpoint == "b"
    # at max, no add even under pressure
    pol2 = ScalePolicy(max_endpoints=2, cooldown_s=0.0)
    assert pol2.decide(snap(2, 2, 100.0), now=0.0) == []
    # below min always adds
    pol3 = ScalePolicy(min_endpoints=2, cooldown_s=0.0)
    assert pol3.decide(snap(1, 1, 0.0), now=0.0)[0].action == "add"


def test_fleet_applies_scale_decisions(net, fresh_registry):
    router = InferenceRouter()
    fleet = _mk_fleet(net, router, n=1)
    try:
        pol = ScalePolicy(min_endpoints=1, max_endpoints=3,
                          target_queue_per_endpoint=0.0, cooldown_s=0.0)
        # force an add: any backlog beats target 0... use decide on a
        # synthetic pressure snapshot, apply through the fleet
        log = fleet.apply([ScaleDecision("add", None, "test pressure")])
        assert len(log) == 1 and len(fleet.names()) == 2
        assert len(router.endpoints()) == 2
        victim = fleet.names()[-1]
        log = fleet.apply([ScaleDecision("remove", victim, "test idle")])
        assert len(log) == 1 and victim not in fleet.names()
        assert victim not in router.endpoints()
    finally:
        fleet.shutdown()


# ------------------------------------------------ /healthz split + UI

def test_healthz_liveness_readiness_split(net, rng, fresh_registry):
    import http.client

    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    from deeplearning4j_tpu.ui.server import UiServer

    eng = ParallelInference(net, max_batch_size=4, replicas=1)
    server = UiServer(InMemoryStatsStorage(), registry=fresh_registry,
                      inference_engine=eng).start()

    def get(path):
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        conn.request("GET", path)
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        return resp.status, body

    try:
        # un-warmed engine: live 200, ready 503, /healthz stays 200
        status, body = get("/healthz/live")
        assert status == 200 and body["live"]
        status, body = get("/healthz/ready")
        assert status == 503 and body["status"] == "unwarmed"
        status, body = get("/healthz")
        assert status == 200 and body["ready"] is False
        eng.warmup([(N_IN,)])
        status, body = get("/healthz/ready")
        assert status == 200 and body["ready"] is True
    finally:
        server.stop()
        eng.shutdown()


def test_healthz_aggregates_fleet_state(net, rng, fresh_registry):
    import http.client

    from deeplearning4j_tpu.ui.storage import InMemoryStatsStorage
    from deeplearning4j_tpu.ui.server import UiServer

    router = InferenceRouter(per_try_timeout_s=0.5, eject_backoff_s=0.1)
    fleet = _mk_fleet(net, router, n=2, request_timeout_s=0.5)
    server = UiServer(InMemoryStatsStorage(), registry=fresh_registry,
                      router=router).start()

    def get(path):
        conn = http.client.HTTPConnection("127.0.0.1", server.port,
                                          timeout=10)
        conn.request("GET", path)
        resp = conn.getresponse()
        body = json.loads(resp.read())
        conn.close()
        return resp.status, body

    try:
        status, body = get("/healthz")
        assert status == 200
        assert body["fleet"]["healthy_endpoints"] == 2
        victim = fleet.names()[0]
        kill_endpoint(fleet, victim)
        assert _spin_until(
            lambda: get("/healthz")[1]["fleet"]["healthy_endpoints"] == 1)
        status, body = get("/healthz")
        assert status == 503  # degraded fleet: reduced capacity
        assert body["fleet"]["endpoints"][victim]["in_pool"] is False
        status, _ = get("/healthz/live")
        assert status == 200  # degraded-but-serving is NOT dead
    finally:
        server.stop()
        fleet.shutdown(drain=False)


# ------------------------------------------------------ metrics schema

def test_router_metric_schema(net, rng, fresh_registry):
    import scripts.check_telemetry_schema as schema

    ep = LocalEndpoint(ParallelInference(net, max_batch_size=4, replicas=1),
                       "e0")
    router = InferenceRouter([ep])
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        for _ in range(3):
            router.output(x, timeout=30)
        with pytest.raises(RetryAfter):
            router.submit(x, deadline_ms=1e-6)
        text = fresh_registry.prometheus_text()
        assert schema.validate_prometheus_text(text) == []
        assert schema.validate_known_metrics(text) == []
        for name in (monitor.ROUTER_REQUESTS_COUNTER,
                     monitor.ROUTER_SHED_COUNTER,
                     monitor.ROUTER_QUEUE_WAIT_HISTOGRAM,
                     monitor.ROUTER_LATENCY_HISTOGRAM,
                     monitor.ROUTER_ENDPOINT_HEALTHY_GAUGE):
            assert name in text, name
        assert {monitor.ROUTER_HEDGES_COUNTER,
                monitor.ROUTER_FAILOVERS_COUNTER} <= set(
                    schema.KNOWN_DL4J_METRICS)
    finally:
        router.close()
        ep.close()


# ----------------------- typed engine errors across the wire boundary
# (ISSUE-7 satellite: a remote worker's shed/quarantine must surface to
# the router caller as the SAME exception type as a LocalEndpoint's,
# for both classify and generate paths)

def _shedding_engine(net):
    """An engine that sheds deterministically: nothing consumes the
    1-slot admission queue (start=False), so the second submit raises
    InferenceBackpressure synchronously."""
    return ParallelInference(net, queue_capacity=1, reject_when_full=True,
                             replicas=1, start=False)


def _first_error(router, submit):
    """Submit one request at a time (the first may park in a 1-slot
    queue and never resolve); returns the first engine error seen —
    checked after EVERY submit so a router-side ejection can't mask
    the typed error under test."""
    futs = []
    for _ in range(3):
        try:
            futs.append(submit())
        except Exception as e:
            return e
        deadline = time.monotonic() + 1.0
        while time.monotonic() < deadline:
            err = next((f.exception() for f in futs
                        if f.done() and f.exception() is not None), None)
            if err is not None:
                return err
            if all(f.done() for f in futs):
                break
            time.sleep(0.01)
    raise AssertionError("engine never shed")


def test_backpressure_shed_same_type_local_and_remote(net, rng,
                                                      fresh_registry):
    from deeplearning4j_tpu.parallel.inference import InferenceBackpressure
    x = rng.standard_normal((1, N_IN)).astype(np.float32)
    prompt = rng.integers(0, 11, (1, 3))
    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
            compute_dtype="float32", learning_rate=0.01).init()

    # local path: the engine's typed exception reaches the router caller
    local_errs = {}
    for kind, engine, submit_args in (
            ("classify", _shedding_engine(net), ("submit", (x,))),
            ("generate", _shedding_engine(g), ("submit_generate", (prompt, 2)))):
        router = InferenceRouter([LocalEndpoint(engine, "solo")],
                                 max_attempts=1)
        try:
            local_errs[kind] = _first_error(
                router, lambda: getattr(router, submit_args[0])(*submit_args[1]))
        finally:
            router.close()
            engine.shutdown()

    # remote path: the worker packs the typed error, the endpoint
    # reconstructs it, the router caller sees the SAME class
    remote_errs = {}
    for kind, engine, submit_args in (
            ("classify", _shedding_engine(net), ("submit", (x,))),
            ("generate", _shedding_engine(g), ("submit_generate", (prompt, 2)))):
        broker = InMemoryBroker()
        from deeplearning4j_tpu.serving import EngineWorker
        worker = EngineWorker(engine, broker, f"shed-{kind}",
                              heartbeat_s=0.05)
        ep = RemoteEndpoint(broker, f"shed-{kind}", request_timeout_s=30.0)
        router = InferenceRouter([ep], max_attempts=1)
        try:
            assert _spin_until(ep.alive, timeout=10)
            remote_errs[kind] = _first_error(
                router, lambda: getattr(router, submit_args[0])(*submit_args[1]))
        finally:
            router.close()
            worker.kill()
            ep.close()
            engine.shutdown()

    for kind in ("classify", "generate"):
        assert isinstance(local_errs[kind], InferenceBackpressure), kind
        assert type(remote_errs[kind]) is type(local_errs[kind]), (
            kind, remote_errs[kind], local_errs[kind])


def test_model_quarantine_same_type_local_and_remote(net, rng,
                                                     fresh_registry):
    from deeplearning4j_tpu.serving import (EngineWorker, ModelQuarantined,
                                            ModelRegistry)

    def quarantined_engine():
        reg = ModelRegistry()
        reg.register("m", net=net)
        eng = ParallelInference(registry=reg, max_batch_size=4, replicas=1)
        with reg._lock:  # deterministic: breaker opened by hand
            reg._models["m"].breaker_open = True
        return eng

    x = rng.standard_normal((1, N_IN)).astype(np.float32)
    local = quarantined_engine()
    router = InferenceRouter([LocalEndpoint(local, "solo")], max_attempts=1)
    try:
        local_err = _first_error(router, lambda: router.submit(x, model="m"))
    finally:
        router.close()
        local.shutdown()

    remote = quarantined_engine()
    broker = InMemoryBroker()
    worker = EngineWorker(remote, broker, "quar", heartbeat_s=0.05)
    ep = RemoteEndpoint(broker, "quar", request_timeout_s=30.0)
    router = InferenceRouter([ep], max_attempts=1)
    try:
        assert _spin_until(ep.alive, timeout=10)
        remote_err = _first_error(router, lambda: router.submit(x, model="m"))
    finally:
        router.close()
        worker.kill()
        ep.close()
        remote.shutdown()

    assert isinstance(local_err, ModelQuarantined)
    assert type(remote_err) is type(local_err)
    assert "quarantined" in str(remote_err)


def test_retry_after_roundtrips_typed_through_wire():
    from deeplearning4j_tpu.serving import wire
    payload = wire.pack_reply("c1", error=RetryAfter("try later", 1.5))
    header, result = wire.unpack_reply(payload)
    assert result is None and header["ok"] is False
    err = wire.typed_error(header)
    assert isinstance(err, RetryAfter)
    assert err.retry_after_s == 1.5 and "try later" in str(err)


# ----------------------------- durable decode streams (ISSUE 10)

class _Chunks:
    """Router-side delivery audit: offsets must be contiguous from 0
    across ANY number of migrations (no gap, no repeat)."""

    def __init__(self):
        self.chunks = []

    def __call__(self, off, toks):
        self.chunks.append((int(off),
                            [int(t) for t in np.asarray(toks).reshape(-1)]))

    def tokens(self):
        toks = []
        for off, ts in self.chunks:
            assert off == len(toks), f"gap/repeat at {off}: {self.chunks}"
            toks.extend(ts)
        return toks


def _mk_gpt_fleet(net, router, n=2, hooks=None, request_timeout_s=30.0):
    """Continuous-decode engine fleet; ``hooks[i]`` arms a
    decode_burst_hook on the i-th engine built (None = no hook)."""
    built = []

    def engine_factory():
        hook = None
        if hooks is not None and len(built) < len(hooks):
            hook = hooks[len(built)]
        eng = ParallelInference(net, replicas=1, continuous=True,
                                decode_slots=4, decode_burst=4,
                                kv_block_size=4, decode_burst_hook=hook)
        built.append(eng)
        return eng

    fleet = LocalFleet(engine_factory, router=router, heartbeat_s=0.05,
                       request_timeout_s=request_timeout_s,
                       heartbeat_timeout_s=1.0)
    for _ in range(n):
        fleet.add_endpoint()
    assert fleet.wait_ready(30)
    return fleet


def _warm_endpoint(fleet, name, prompt, max_new):
    """Pre-compile one endpoint's decode programs by dispatching to it
    DIRECTLY (bypassing router placement), so a later migration's
    resume isn't racing XLA compiles against the silence timeout."""
    fleet.endpoint(name).submit_generate(prompt, max_new).result(60)


def _scale_timeouts(router, fleet, name, prompt, max_new,
                    floor_s, cap_s):
    """Deflake (PR-10 wall-clock-timeout family on 1-core boxes):
    tier-1 runs this file under heavy parallel load, where a WARM
    healthy dispatch alone can approach a fixed 1.5-3s reply budget —
    the timeout then fires on a healthy engine and the test flakes.
    Time one warmed dispatch on this box RIGHT NOW and scale every
    reply/silence deadline off it (floor = the original tight budget,
    so an idle box keeps the original timing; cap keeps the failure
    path inside the test's own result() budget). Returns the budget."""
    t0 = time.perf_counter()
    _warm_endpoint(fleet, name, prompt, max_new)  # warmed: measures load
    warm_s = time.perf_counter() - t0
    budget = min(cap_s, max(floor_s, 10.0 * warm_s))
    router.per_try_timeout = budget
    for n in fleet.names():
        fleet.endpoint(n).request_timeout = budget
    return budget


def test_stream_migrates_on_burst_kill_resumed_not_restarted(rng,
                                                             fresh_registry):
    """THE acceptance scenario, deterministic: the pinned engine's
    second decode burst dies under the stream (typed DecodeBurstError
    across the wire) → the router migrates the stream with its
    journaled prefix → the surviving engine RESUMES (re-prefills
    prompt + prefix only, pinned via its scheduler's admit event and
    the resume-prefix counter) → delivered tokens are token-for-token
    the uninterrupted generate_eager run with zero duplicate/missing
    offsets."""
    from deeplearning4j_tpu.faultinject import BurstKill
    from deeplearning4j_tpu.nn.generate import generate_eager
    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=64,
            compute_dtype="float32", learning_rate=0.01).init()
    for sampler in ({}, {"temperature": 0.8, "top_k": 5, "seed": 3}):
        reg = monitor.set_registry(monitor.MetricsRegistry())
        router = InferenceRouter(per_try_timeout_s=15.0,
                                 eject_backoff_s=0.1, max_attempts=4)
        kill = BurstKill(after=1, failures=1)
        fleet = _mk_gpt_fleet(g, router, n=2, hooks=[kill])
        try:
            prompt = rng.integers(0, 11, (1, 5))
            want = generate_eager(g, prompt, 16, **sampler)
            coll = _Chunks()
            fut = router.submit_generate(prompt, 16, session="mig",
                                         on_tokens=coll, **sampler)
            got = fut.result(90)
            np.testing.assert_array_equal(got, want)
            assert coll.tokens() == [int(t) for t in want[0, 5:]]
            assert kill.hits == 1
            mreg = monitor.get_registry()
            assert mreg.family_total(monitor.SESSION_MIGRATIONS_COUNTER) == 1
            prefix = mreg.family_total(monitor.ROUTER_RESUME_PREFIX_COUNTER)
            assert prefix > 0  # resumed from the journal, not restarted
            # the survivor admitted the resume at t0 + prefix — it
            # prefilled the prefix instead of re-generating it
            survivor = fleet._members["engine-1"].worker.engine
            admits = [e for e in survivor._scheduler.events
                      if e.startswith("admit")]
            assert len(admits) == 1
            assert f" t={5 + int(prefix)} " in admits[0], (admits, prefix)
            snap = router.fleet_snapshot()
            assert snap["migrations"] == 1
            assert snap["resume_prefix_tokens"] == int(prefix)
            assert snap["active_streams"] == 0  # terminal frame landed
            assert router.session_endpoint("mig") == "engine-1"
        finally:
            fleet.shutdown(drain=False)
            router.close()
            monitor.set_registry(reg)


def test_stream_survives_stalled_endpoint_timeout(rng, fresh_registry):
    """The wedged-mid-burst shape: the pinned engine stalls (burst
    gated, no chunks, no reply — but heartbeats keep flowing) → the
    stream's silence deadline fires → migration with prefix → exact
    tokens; the stalled engine's LATE chunks are dropped by the
    dispatch epoch, never double-delivered."""
    import threading
    from deeplearning4j_tpu.nn.generate import generate_eager

    class _Gate:
        def __init__(self):
            self.ev = threading.Event()
            self.calls = 0

        def __call__(self, lane, idx):
            self.calls += 1
            if self.calls == 2:
                self.ev.wait(60)

    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=64,
            compute_dtype="float32", learning_rate=0.01).init()
    # no fixed reply budget while engines compile: _scale_timeouts sets
    # the one the stall is detected by, from a warm dispatch on this box
    router = InferenceRouter(per_try_timeout_s=60.0, eject_backoff_s=0.1,
                             max_attempts=4)
    gate = _Gate()
    fleet = _mk_gpt_fleet(g, router, n=2, hooks=[gate],
                          request_timeout_s=60.0)
    try:
        prompt = rng.integers(0, 11, (1, 5))
        want = generate_eager(g, prompt, 16)
        # warm the survivor — original shape AND the resume shape
        # (prompt+prefix prefill is a different bucket) — so the
        # migrated dispatch isn't racing XLA compiles against the
        # silence budget on a loaded box
        _warm_endpoint(fleet, "engine-1", prompt, 16)
        _warm_endpoint(fleet, "engine-1",
                       rng.integers(0, 11, (1, 10)), 11)
        # then scale the silence/reply budget off this box's measured
        # warm-dispatch cost (the stalled engine holds its burst for
        # 60s, so any finite budget still fires the migration)
        _scale_timeouts(router, fleet, "engine-1", prompt, 16,
                        floor_s=3.0, cap_s=20.0)
        coll = _Chunks()
        fut = router.submit_generate(prompt, 16, session="stall",
                                     on_tokens=coll)
        got = fut.result(90)
        np.testing.assert_array_equal(got, want)
        gate.ev.set()  # release the stalled engine: late chunks fire
        time.sleep(0.2)  # ...and are dropped (epoch + swept pending)
        assert coll.tokens() == [int(t) for t in want[0, 5:]]
        reg = monitor.get_registry()
        assert reg.family_total(monitor.SESSION_MIGRATIONS_COUNTER) >= 1
        assert router.session_endpoint("stall") == "engine-1"
    finally:
        gate.ev.set()
        fleet.shutdown(drain=False)
        router.close()


def test_mid_generation_kill_restarted_stream_matches_eager(rng,
                                                            fresh_registry):
    """The satellite regression pinning (pre-journal) behavior for
    NON-streaming sessions: kill the pinned endpoint mid-generation —
    the request restarts elsewhere (no journal ⇒ zero resume prefix)
    and the result still matches eager exactly."""
    import threading
    from deeplearning4j_tpu.nn.generate import generate_eager

    class _Gate:
        def __init__(self):
            self.ev = threading.Event()
            self.calls = 0

        def __call__(self, lane, idx):
            self.calls += 1
            if self.calls == 2:
                self.ev.wait(60)

    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=64,
            compute_dtype="float32", learning_rate=0.01).init()
    # no fixed reply budget while engines compile (a cold dispatch
    # under six xdist workers outlasts 1.5 s): _scale_timeouts sets the
    # one the kill is detected by, from a warm dispatch on this box
    router = InferenceRouter(per_try_timeout_s=60.0, eject_backoff_s=0.1,
                             max_attempts=4)
    gate = _Gate()
    fleet = _mk_gpt_fleet(g, router, n=2, hooks=[gate],
                          request_timeout_s=60.0)
    try:
        prompt = rng.integers(0, 11, (1, 5))
        want = generate_eager(g, prompt, 16)
        _warm_endpoint(fleet, "engine-1", prompt, 16)
        # scale the reply budget off measured load (the kill is
        # detected by reply timeout — a fixed 1.5s budget also fires
        # on a HEALTHY loaded engine and flakes the restart count)
        _scale_timeouts(router, fleet, "engine-1", prompt, 16,
                        floor_s=1.5, cap_s=15.0)
        fut = router.submit_generate(prompt, 16, session="res")
        assert _spin_until(lambda: gate.calls >= 2, timeout=30)
        kill_endpoint(fleet, "engine-0")  # mid-generation engine death
        np.testing.assert_array_equal(fut.result(90), want)
        reg = monitor.get_registry()
        assert reg.family_total(monitor.SESSION_MIGRATIONS_COUNTER) >= 1
        # no journal (non-streaming): restarted, not resumed
        assert reg.family_total(monitor.ROUTER_RESUME_PREFIX_COUNTER) == 0
        assert router.session_endpoint("res") == "engine-1"
    finally:
        gate.ev.set()
        fleet.shutdown(drain=False)
        router.close()


def test_router_stream_generator_yields_deltas(rng, fresh_registry):
    from deeplearning4j_tpu.nn.generate import generate_eager
    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
            compute_dtype="float32", learning_rate=0.01).init()
    router = InferenceRouter(per_try_timeout_s=30.0)
    fleet = _mk_gpt_fleet(g, router, n=1)
    try:
        prompt = rng.integers(0, 11, (1, 4))
        want = generate_eager(g, prompt, 8)
        toks = []
        for off, delta in router.stream(prompt, 8, timeout=60):
            assert off == len(toks)
            toks.extend(int(t) for t in delta)
        assert toks == [int(t) for t in want[0, 4:]]
    finally:
        fleet.shutdown(drain=False)
        router.close()


# -------------------------------------------- wedged-endpoint watchdog

def test_wedged_endpoint_detected_ejected_migrated(net, rng,
                                                   fresh_registry):
    """Heartbeats prove liveness, not progress: a wedged worker (keeps
    beating, drops every request) is ejected by the progress watchdog
    BEFORE any reply timeout scores a failure, its in-flight request
    resolves via timeout → failover, and after healing it probes back
    into the pool."""
    from deeplearning4j_tpu.faultinject import WedgeEndpoint
    router = InferenceRouter(per_try_timeout_s=2.0, eject_backoff_s=0.2,
                             max_attempts=4, wedge_timeout_s=0.3)
    fleet = _mk_fleet(net, router, n=2, request_timeout_s=2.0)
    try:
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        inline = np.asarray(net.output(x))
        for _ in range(4):
            router.output(x, timeout=30)
        victim = "engine-0"
        with WedgeEndpoint(fleet, victim):
            fut = router.submit(x)  # may land on the wedged endpoint
            assert _spin_until(lambda: router.fleet_snapshot()
                               ["endpoints"][victim]["wedged"], timeout=20)
            snap = router.fleet_snapshot()
            assert snap["endpoints"][victim]["alive"]  # still beating!
            assert not snap["endpoints"][victim]["in_pool"]
            # the stuck request resolves (timeout → failover), new
            # traffic avoids the wedge
            np.testing.assert_array_equal(fut.result(30), inline)
            np.testing.assert_array_equal(router.output(x, timeout=30),
                                          inline)
        # healed: probe reinstates, wedged flag clears
        def reinstated():
            router.probe_now()
            try:
                router.output(x, timeout=30)
            except BaseException:
                return False
            ep = router.fleet_snapshot()["endpoints"][victim]
            return ep["in_pool"] and not ep["wedged"]
        assert _spin_until(reinstated, timeout=30, tick=0.05)
    finally:
        fleet.shutdown(drain=False)
        router.close()


# --------------------------------------- scale-down drain vs migration

def test_scale_down_drains_active_stream_zero_token_loss(rng,
                                                         fresh_registry):
    """drain_and_stop × migration: removing the endpoint a live stream
    is pinned to must let the stream FINISH there (every token
    delivered exactly once, no migration needed) before the goodbye
    frame; the session re-pins for its next burst."""
    import threading
    from deeplearning4j_tpu.nn.generate import generate_eager

    class _Gate:
        def __init__(self):
            self.ev = threading.Event()
            self.calls = 0

        def __call__(self, lane, idx):
            self.calls += 1
            if self.calls == 2:
                self.ev.wait(60)

    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=64,
            compute_dtype="float32", learning_rate=0.01).init()
    router = InferenceRouter(per_try_timeout_s=30.0)
    gate = _Gate()
    fleet = _mk_gpt_fleet(g, router, n=2, hooks=[gate])
    try:
        prompt = rng.integers(0, 11, (1, 5))
        want = generate_eager(g, prompt, 16)
        coll = _Chunks()
        fut = router.submit_generate(prompt, 16, session="sd",
                                     on_tokens=coll)
        assert _spin_until(lambda: gate.calls >= 2, timeout=30)
        assert router.session_endpoint("sd") == "engine-0"
        # scale down the pinned endpoint while the stream is gated
        done = []
        th = threading.Thread(
            target=lambda: done.append(fleet.remove_endpoint("engine-0")))
        th.start()
        time.sleep(0.2)
        assert not fut.done()  # drain is WAITING on the live stream
        gate.ev.set()          # release: the stream finishes on the drainer
        got = fut.result(90)
        th.join(60)
        np.testing.assert_array_equal(got, want)
        assert coll.tokens() == [int(t) for t in want[0, 5:]]
        # zero-loss hand-off: no migration was needed for the stream
        reg = monitor.get_registry()
        assert reg.family_total(monitor.ROUTER_RESUME_PREFIX_COUNTER) == 0
        # the session's NEXT burst lands on the survivor
        y = router.generate(prompt, 8, session="sd", timeout=90)
        np.testing.assert_array_equal(y, generate_eager(g, prompt, 8))
        assert router.session_endpoint("sd") == "engine-1"
    finally:
        gate.ev.set()
        fleet.shutdown(drain=False)
        router.close()


# ----------------------------------------------- wire protocol version

def test_wire_version_skew_rejected_typed(net, rng, fresh_registry):
    """A frame from a NEWER protocol is rejected with a typed
    WireVersionError reply — never served garbled. Pinned end-to-end:
    a crafted v99 request through a live worker surfaces the SAME
    exception class at the endpoint's future."""
    from deeplearning4j_tpu.serving import wire
    # unit: check_version + typed roundtrip
    with pytest.raises(wire.WireVersionError):
        wire.check_version({"v": wire.WIRE_VERSION + 1})
    wire.check_version({})          # legacy v1 headers stay accepted
    header, _ = wire.unpack_reply(
        wire.pack_reply("c", error=wire.WireVersionError("skew")))
    assert isinstance(wire.typed_error(header), wire.WireVersionError)
    # end-to-end: live worker rejects a v99 frame typed
    eng = ParallelInference(net, max_batch_size=4, replicas=1)
    broker = InMemoryBroker()
    worker = EngineWorker(eng, broker, "vskew", heartbeat_s=0.05)
    ep = RemoteEndpoint(broker, "vskew", request_timeout_s=10.0)
    try:
        assert _spin_until(ep.alive, timeout=10)
        x = rng.standard_normal((1, N_IN)).astype(np.float32)
        fut = ep.submit(x)
        corr = list(ep._pending)[0]
        # re-publish the same correlation id as a FUTURE-version frame
        import json as _json
        import struct as _struct
        payload = wire.pack_request(corr, ep.reply_topic,
                                    wire.KIND_CLASSIFY, x)
        hlen = _struct.unpack(">I", payload[:4])[0]
        hdr = _json.loads(payload[4:4 + hlen])
        hdr["v"] = 99
        h = _json.dumps(hdr, separators=(",", ":")).encode()
        broker.publish("vskew" + wire.REQ_SUFFIX,
                       _struct.pack(">I", len(h)) + h + payload[4 + hlen:])
        with pytest.raises(wire.WireVersionError):
            fut.result(30)
    finally:
        ep.close()
        worker.kill()
        eng.shutdown(drain=False)


def test_wire_v4_binary_roundtrip_and_damage_typed(fresh_registry):
    """The v4 binary framing contract: byte-exact zero-copy tensor
    segments, coalesced chunk decode, and — the chaos half — EVERY
    truncation point surfaces as a typed WireFrameError, never a
    garbled tensor. The broker's ping header constants are pinned to
    the wire's (they are mirrored across the import-graph boundary)."""
    from deeplearning4j_tpu.serving import wire
    from deeplearning4j_tpu.streaming import broker as broker_mod
    # the transport-level ping rides the SAME v4 prologue
    assert broker_mod.PING_MAGIC == wire.WIRE_MAGIC
    assert broker_mod.PING_VERSION == wire.WIRE_VERSION
    rng = np.random.default_rng(7)
    kv = rng.standard_normal((2, 3, 4, 5)).astype(np.float32)
    ids = rng.integers(0, 999, (1, 7)).astype(np.int32)
    payload = wire.pack_request_v4(
        "c1", "rsp", wire.KIND_GENERATE, ids,
        gen={"max_new": 4, "kv": True}, model="m", session="s",
        tensors={"kv": kv})
    assert wire.is_binary_frame(payload)
    meta, x, segs = wire.unpack_request_any(payload)
    assert meta["id"] == "c1" and meta["v"] == wire.WIRE_VERSION
    assert meta["model"] == "m" and meta["session"] == "s"
    assert x.dtype == ids.dtype
    np.testing.assert_array_equal(x, ids)
    assert segs["kv"].dtype == kv.dtype
    assert segs["kv"].tobytes() == kv.tobytes()  # byte-exact
    # legacy frames pass through the same seam untouched
    leg, lx, lsegs = wire.unpack_request_any(
        wire.pack_request("c2", "rsp", wire.KIND_CLASSIFY, ids))
    assert leg["id"] == "c2" and lsegs == {}
    np.testing.assert_array_equal(lx, ids)
    # coalesced chunk frame: one frame, every stream's delta
    frame = wire.pack_chunks_v4([
        ("a", 0, np.array([1, 2], np.int64)),
        ("b", 5, np.array([9], np.int64))])
    evs = wire.decode_reply_events(frame)
    assert [(e["type"], e["id"], e["off"]) for e in evs] == \
        [("chunk", "a", 0), ("chunk", "b", 5)]
    assert list(evs[0]["tokens"]) == [1, 2] and list(evs[1]["tokens"]) == [9]
    # truncation sweep: every cut of the binary frame fails TYPED
    for cut in range(len(payload)):
        with pytest.raises(wire.WireFrameError):
            wire.unpack_frame_v4(payload[:cut])
    # typed across the wire like every other registered engine error
    hdr, _ = wire.unpack_reply(
        wire.pack_reply("c", error=wire.WireFrameError("cut")))
    assert isinstance(wire.typed_error(hdr), wire.WireFrameError)


def test_wire_v4_version_skew_matrix(net, rng, fresh_registry):
    """Rolling-upgrade matrix, pinned end-to-end: a v4 endpoint serves
    against a v3-pinned worker (negotiation downgrades the framing per
    the worker's advertised heartbeat ceiling), a v3-pinned endpoint
    serves against a v4 worker (requests stay legacy; the worker
    replies in kind), and a RAW v4 binary frame forced at the v3
    worker is rejected with a typed WireVersionError — the only skew
    that may fail, and it fails typed."""
    from deeplearning4j_tpu.serving import wire
    x = rng.standard_normal((1, N_IN)).astype(np.float32)
    want = np.asarray(net.output(x))

    # v4 router ↔ v3 worker: keeps serving, all frames legacy
    eng = ParallelInference(net, max_batch_size=4, replicas=1)
    broker = InMemoryBroker()
    worker = EngineWorker(eng, broker, "skew-a", heartbeat_s=0.05,
                          wire_version=3)
    ep = RemoteEndpoint(broker, "skew-a", request_timeout_s=10.0)
    try:
        assert _spin_until(ep.alive, timeout=10)
        assert ep.negotiated_wire() == 3  # downgraded by the heartbeat
        np.testing.assert_array_equal(ep.submit(x).result(30), want)
        # a raw v4 frame AT the v3 worker: typed rejection, live corr
        fut = ep.submit(x)
        corr = list(ep._pending)[0]
        broker.publish("skew-a" + wire.REQ_SUFFIX, wire.pack_request_v4(
            corr, ep.reply_topic, wire.KIND_CLASSIFY, x))
        with pytest.raises(wire.WireVersionError):
            fut.result(30)
    finally:
        ep.close()
        worker.kill()
        eng.shutdown(drain=False)

    # v3 router ↔ v4 worker: requests stay legacy, replies in kind
    eng = ParallelInference(net, max_batch_size=4, replicas=1)
    broker = InMemoryBroker()
    worker = EngineWorker(eng, broker, "skew-b", heartbeat_s=0.05)
    ep = RemoteEndpoint(broker, "skew-b", request_timeout_s=10.0,
                        wire_version=3)
    try:
        assert _spin_until(ep.alive, timeout=10)
        assert ep.negotiated_wire() == 3
        np.testing.assert_array_equal(ep.submit(x).result(30), want)
    finally:
        ep.close()
        worker.kill()
        eng.shutdown(drain=False)

    # v4 ↔ v4: once the heartbeat proves the peer, the hot path goes
    # binary (before the first heartbeat the endpoint stays legacy)
    eng = ParallelInference(net, max_batch_size=4, replicas=1)
    broker = InMemoryBroker()
    worker = EngineWorker(eng, broker, "skew-c", heartbeat_s=0.05)
    ep = RemoteEndpoint(broker, "skew-c", request_timeout_s=10.0)
    try:
        assert _spin_until(ep.alive, timeout=10)
        assert ep.negotiated_wire() == 4
        reg = monitor.get_registry()
        before = reg.counter(monitor.WIRE_FRAMES_COUNTER,
                             transport="v4").value
        np.testing.assert_array_equal(ep.submit(x).result(30), want)
        assert reg.counter(monitor.WIRE_FRAMES_COUNTER,
                           transport="v4").value >= before + 2  # req+reply
    finally:
        ep.close()
        worker.kill()
        eng.shutdown(drain=False)


def test_wire_v4_stream_coalesced_and_disagg_byte_exact(rng,
                                                        fresh_registry):
    """The v4 hot path end-to-end on a continuous-decode engine:
    streamed tokens arrive through COALESCED burst frames (the
    coalesced-chunks counter ticks; offsets stay gapless), and the
    disagg prefill→decode handoff is BYTE-exact over raw v4 segments —
    same dtype, same bytes, same tokens as the fused local run."""
    from deeplearning4j_tpu.nn.generate import generate_eager
    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2,
            max_len=64, compute_dtype="float32", learning_rate=0.01).init()
    eng = ParallelInference(g, replicas=1, continuous=True,
                            decode_slots=4, decode_burst=4,
                            kv_block_size=4)
    broker = InMemoryBroker()
    worker = EngineWorker(eng, broker, "v4gpt", heartbeat_s=0.05)
    ep = RemoteEndpoint(broker, "v4gpt", request_timeout_s=30.0,
                        heartbeat_timeout_s=1.0)
    try:
        assert _spin_until(ep.alive, timeout=10)
        assert _spin_until(lambda: ep.negotiated_wire() == 4, timeout=10)
        prompt = rng.integers(0, 11, (1, 5))
        want = generate_eager(g, prompt, 12)
        coll = _Chunks()
        got = ep.submit_generate(prompt, 12, on_tokens=coll).result(90)
        np.testing.assert_array_equal(got, want)
        assert coll.tokens() == [int(t) for t in want[0, 5:]]
        reg = monitor.get_registry()
        assert reg.family_total(monitor.WIRE_COALESCED_COUNTER) > 0
        # disagg: shipped KV byte-exact over v4 framing
        st = ep.submit_prefill(prompt).result(60)
        local = eng.prefill_export(prompt.astype(np.int32))
        assert np.asarray(st["kv"]).dtype == np.asarray(local["kv"]).dtype
        assert np.asarray(st["kv"]).tobytes() == \
            np.asarray(local["kv"]).tobytes()
        np.testing.assert_array_equal(np.asarray(st["logits"]),
                                      np.asarray(local["logits"]))
        got2 = ep.submit_generate(
            prompt, 12, kv_state={"kv": st["kv"], "logits": st["logits"],
                                  "t_in": st["t_in"]}).result(90)
        np.testing.assert_array_equal(got2, want)
    finally:
        ep.close()
        worker.kill()
        eng.shutdown(drain=False)


# ------------------------------------------- stream metrics + healthz

def test_stream_metric_schema_and_healthz_counts(rng, fresh_registry):
    import scripts.check_telemetry_schema as schema
    from deeplearning4j_tpu.nn.generate import generate_eager
    for name in ("dl4j_stream_chunks_total",
                 "dl4j_session_migrations_total",
                 "dl4j_session_journal_bytes",
                 "dl4j_router_resume_prefix_tokens_total",
                 monitor.WIRE_FRAMES_COUNTER,
                 monitor.WIRE_BYTES_COUNTER,
                 monitor.WIRE_COALESCED_COUNTER,
                 monitor.ROUTER_LOOP_LAG_HISTOGRAM):
        assert name in schema.KNOWN_DL4J_METRICS, name
    from deeplearning4j_tpu.faultinject import BurstKill
    g = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=64,
            compute_dtype="float32", learning_rate=0.01).init()
    router = InferenceRouter(per_try_timeout_s=15.0, eject_backoff_s=0.1,
                             max_attempts=4)
    fleet = _mk_gpt_fleet(g, router, n=2,
                          hooks=[BurstKill(after=1, failures=1)])
    try:
        prompt = rng.integers(0, 11, (1, 5))
        want = generate_eager(g, prompt, 16)
        fut = router.submit_generate(prompt, 16, session="m",
                                     on_tokens=lambda o, t: None)
        np.testing.assert_array_equal(fut.result(90), want)
        text = fresh_registry.prometheus_text()
        assert schema.validate_prometheus_text(text) == []
        assert schema.validate_known_metrics(text) == []
        for family in ("dl4j_stream_chunks_total",
                       "dl4j_session_migrations_total",
                       "dl4j_session_journal_bytes",
                       "dl4j_router_resume_prefix_tokens_total"):
            assert f"# TYPE {family}" in text, family
        assert 'reason="burst_error"' in text
        snap = router.fleet_snapshot()
        for key in ("active_streams", "journal_bytes", "migrations",
                    "resume_prefix_tokens"):
            assert key in snap, key
        assert snap["migrations"] == 1
    finally:
        fleet.shutdown(drain=False)
        router.close()


# ---------------------- session (endpoint, model, version) vs cutover

def test_router_session_pins_endpoint_model_and_version(fresh_registry):
    from deeplearning4j_tpu.serving import ModelRegistry
    g1 = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
             compute_dtype="float32", learning_rate=0.01, seed=1).init()
    g2 = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2, max_len=32,
             compute_dtype="float32", learning_rate=0.01, seed=9).init()
    reg = ModelRegistry()
    reg.register("g", net=g1)
    eng = ParallelInference(registry=reg, max_batch_size=8,
                            max_latency_ms=0.0, replicas=1)
    ep = LocalEndpoint(eng, "e0")
    router = InferenceRouter([ep])
    try:
        prompt = np.asarray([[1, 2, 3]], np.int64)
        solo1 = np.asarray(g1.generate(prompt, 5))
        solo2 = np.asarray(g2.generate(prompt, 5))
        assert not np.array_equal(solo1, solo2)
        np.testing.assert_array_equal(
            router.generate(prompt, 5, session="s1", model="g", timeout=60),
            solo1)
        assert router.session_pin("s1") == ("e0", "g")
        reg.deploy("g", net=g2, warm=False)  # hot-swap mid-stream
        # the pinned stream finishes on the version it started on; the
        # version half of the pin lives engine-side on the session key
        np.testing.assert_array_equal(
            router.generate(prompt, 5, session="s1", model="g", timeout=60),
            solo1)
        np.testing.assert_array_equal(
            router.generate(prompt, 5, session="s2", model="g", timeout=60),
            solo2)
    finally:
        router.close()
        eng.shutdown()
