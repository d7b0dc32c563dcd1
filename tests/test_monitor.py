"""Unified telemetry tests: registry → spans → exports → endpoints.

Tier-1 guard for the monitor/ subsystem: a real CPU training run must
produce (a) a JSONL event stream ``scripts/check_telemetry_schema.py``
accepts, (b) a Chrome ``trace_event`` JSON with distinct
data_load/device_step/all_reduce/checkpoint spans (Perfetto-loadable),
and (c) a Prometheus ``/metrics`` exposition with the step-duration
histogram, score gauge, and NaN-watchdog counter — with span overhead
small enough to live inside the host-side step loop (<5%).
"""

import importlib.util
import json
import os
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import DenseLayer, OutputLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ui import InMemoryStatsStorage, StatsListener, UiServer

_SCRIPT = os.path.join(os.path.dirname(__file__), os.pardir, "scripts",
                       "check_telemetry_schema.py")
_spec = importlib.util.spec_from_file_location("check_telemetry_schema",
                                               _SCRIPT)
schema = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(schema)


@pytest.fixture
def registry():
    """Fresh process registry per test; the previous one is restored so
    parallel-running suites keep their own counters."""
    reg = monitor.MetricsRegistry()
    old = monitor.set_registry(reg)
    try:
        yield reg
    finally:
        monitor.set_registry(old)
        monitor.disable_tracing()


def _tiny_net():
    conf = (NeuralNetConfiguration.builder().seed(5).learning_rate(0.1)
            .updater("sgd").activation("tanh")
            .list()
            .layer(DenseLayer(n_in=4, n_out=8))
            .layer(OutputLayer(n_in=8, n_out=2, activation="softmax",
                               loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf).init()


def _tiny_data(rng, n=32):
    x = rng.standard_normal((n, 4)).astype(np.float32)
    y = np.eye(2, dtype=np.float32)[rng.integers(0, 2, n)]
    return DataSet(x, y)


# ------------------------------------------------------------- registry

def test_registry_counters_gauges_histograms(registry):
    c = registry.counter("req_total", "requests", route="/a")
    c.inc()
    c.inc(2)
    assert registry.counter("req_total", route="/a") is c
    assert c.value == 3
    registry.gauge("temp", "t").set(1.5)
    h = registry.histogram("lat_ms", "latency", buckets=(1, 10, 100))
    for v in (0.5, 5, 50, 500):
        h.observe(v)
    assert h.count == 4 and h.sum == 555.5
    assert h.cumulative_counts() == [1, 2, 3, 4]
    assert 0 <= h.percentile(0.5) <= 50
    with pytest.raises(ValueError):
        registry.gauge("req_total")  # kind conflict must be loud
    errs = schema.validate_prometheus_text(registry.prometheus_text())
    assert errs == []


def test_registry_prometheus_label_escaping(registry):
    registry.counter("odd_total", "odd", detail='he said "hi"\\n').inc()
    text = registry.prometheus_text()
    assert schema.validate_prometheus_text(text) == []
    assert '\\"hi\\"' in text


def test_phase_breakdown_from_spans(registry):
    with monitor.span("data_load"):
        pass
    with monitor.span("device_step"):
        pass
    with monitor.span("device_step"):
        pass
    b = monitor.phase_breakdown(registry)
    assert b["device_step"]["count"] == 2
    assert b["data_load"]["count"] == 1
    assert all(v["total_ms"] >= 0 for v in b.values())


def test_span_records_without_tracer(registry):
    monitor.disable_tracing()
    with monitor.span("device_step"):
        pass
    hist = registry.get(monitor.PHASE_HISTOGRAM, phase="device_step")
    assert hist is not None and hist.count == 1


def test_span_propagates_exceptions_and_tags_error(registry, tmp_path):
    tracer = monitor.enable_tracing(str(tmp_path / "e.jsonl"))
    with pytest.raises(RuntimeError):
        with monitor.span("checkpoint"):
            raise RuntimeError("boom")
    monitor.disable_tracing()
    [event] = tracer.events()
    assert event["attrs"]["error"] == "RuntimeError"


# ----------------------------------------------------------- step health

def test_watchdog_counts_nan_and_slow_steps(registry):
    w = monitor.StepHealthWatchdog(registry=registry, min_samples=10,
                                   slow_factor=3.0)
    w.record(float("nan"), None, iteration=7)
    assert registry.family_total(monitor.NAN_COUNTER) == 1
    assert w.nan_iterations == [7] and not w.healthy()
    for i in range(30):
        w.record(0.5, 1.0, iteration=i)
    w.record(0.5, 50.0, iteration=99)  # >3x rolling p50 and > rolling p99
    assert registry.family_total(monitor.SLOW_COUNTER) == 1
    assert w.slow_iterations == [99]
    p50, p99 = w.percentiles()
    assert p50 <= p99
    assert registry.get(monitor.SCORE_GAUGE).value == 0.5
    assert registry.get(monitor.STEP_HISTOGRAM).count == 31


def test_watchdog_rides_listener_chain(registry, rng):
    net = _tiny_net()
    w = monitor.StepHealthWatchdog(registry=registry)
    net.set_listeners(w)
    net.fit(_tiny_data(rng))
    assert w.healthy()
    assert registry.get(monitor.SCORE_GAUGE).value == pytest.approx(
        net.score())


# ----------------------------------------------------------- end to end

def test_end_to_end_trace_metrics_and_endpoints(registry, rng, tmp_path):
    from deeplearning4j_tpu.parallel.wrapper import ParallelWrapper
    from deeplearning4j_tpu.util.model_serializer import write_model

    jsonl = str(tmp_path / "events.jsonl")
    monitor.enable_tracing(jsonl)
    net = _tiny_net()
    storage = InMemoryStatsStorage()
    watchdog = monitor.StepHealthWatchdog(registry=registry)
    net.set_listeners(StatsListener(storage, session_id="e2e",
                                    registry=registry), watchdog)
    ds = _tiny_data(rng)
    for _ in range(3):
        net.fit(ds)                                  # data_load/device_step
    pw = ParallelWrapper(net, mode="averaging", averaging_frequency=1)
    pw.fit(ds)                                       # all_reduce
    write_model(net, str(tmp_path / "model.zip"))    # checkpoint
    net.score(ds)                                    # eval
    watchdog.record(float("nan"), None, iteration=-1)  # tick the watchdog
    tracer = monitor.disable_tracing()

    # (a) the JSONL stream validates
    assert schema.validate_events_file(jsonl) == []
    assert tracer.dropped == 0

    # (b) the Chrome trace validates and has the distinct phase spans
    trace_path = str(tmp_path / "trace.json")
    tracer.export_chrome_trace(trace_path)
    assert schema.validate_chrome_trace_file(trace_path) == []
    with open(trace_path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"] if e["ph"] == "X"}
    assert {"data_load", "device_step", "all_reduce",
            "checkpoint", "eval"} <= names

    # (c) /metrics serves Prometheus text with the required families,
    #     /healthz reports the watchdog state
    srv = UiServer(storage, registry=registry).start()
    try:
        text = urllib.request.urlopen(srv.url + "/metrics").read().decode()
        assert schema.validate_prometheus_text(text) == []
        assert "dl4j_step_duration_ms_bucket" in text
        assert "dl4j_score" in text
        assert "dl4j_nan_scores_total" in text
        assert "dl4j_phase_duration_ms_bucket" in text
        with pytest.raises(urllib.error.HTTPError) as e:
            urllib.request.urlopen(srv.url + "/healthz")
        assert e.value.code == 503  # the injected NaN degrades health
        health = json.loads(e.value.read())
        assert health["status"] == "degraded" and health["nan_scores"] >= 1
    finally:
        srv.stop()

    # the storage consumer saw the same run the registry did
    reports = storage.get_reports("e2e")
    assert reports and np.isfinite(reports[-1].score)


def test_command_line_interface(registry, tmp_path, capsys):
    monitor.enable_tracing(str(tmp_path / "ev.jsonl"))
    with monitor.span("device_step"):
        pass
    tracer = monitor.disable_tracing()
    tracer.export_chrome_trace(str(tmp_path / "trace.json"))
    metrics = tmp_path / "metrics.txt"
    metrics.write_text(registry.prometheus_text())
    rc = schema.main([str(tmp_path / "ev.jsonl"), str(tmp_path / "trace.json"),
                      "--metrics", str(metrics)])
    assert rc == 0
    bad = tmp_path / "bad.jsonl"
    bad.write_text('{"type": "span", "name": "x"}\n')
    assert schema.main([str(bad)]) == 1


# -------------------------------------------------------------- overhead

def test_monitoring_overhead_under_5_percent(registry):
    """The acceptance bar: a span around a step-loop-scale step (~2ms,
    the test_host_baseline per-batch scale) must cost <5% of it. What is
    bounded is what the span itself executes: this thread's CPU time over
    a fixed count of spans, on the thread's own clock. (The difference of
    two wall-clock loops of sleeps, which this test took before, measured
    the scheduler under six xdist workers.)"""
    step_ms, n = 2.0, 2000
    for i in range(100):  # first use: the histogram child, the annotation
        with monitor.span("device_step", iteration=i):
            pass
    t0 = time.thread_time()
    for i in range(n):
        with monitor.span("device_step", iteration=i):
            pass
    per_span_ms = (time.thread_time() - t0) / n * 1e3
    assert per_span_ms < 0.05 * step_ms, f"span overhead {per_span_ms:.4f}ms"
    # and every one of them was recorded
    hist = registry.get(monitor.PHASE_HISTOGRAM, phase="device_step")
    assert hist.count == n + 100


def test_training_stats_shares_monitor_clock(tmp_path):
    from deeplearning4j_tpu.optimize.training_stats import TrainingStats

    stats = TrainingStats()
    with stats.time("step"):
        pass
    trace = stats.chrome_trace()
    assert schema.validate_chrome_trace(trace) == []
    [ev] = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    # same origin as monitor.now_us(): the event sits in the past of "now"
    assert 0 <= ev["ts"] <= monitor.now_us()
    out = stats.export_chrome_trace(str(tmp_path / "ts.json"))
    assert schema.validate_chrome_trace_file(out) == []
