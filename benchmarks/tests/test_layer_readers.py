"""Tests of the per-layer reader that reads the program's own registry
(``dispatch_launch_ms``): on a registry with known values, and ``None`` where
there is nothing to read, as from a program that has no such span.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from deeplearning4j_tpu import monitor  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
WINDOW = {"dispatches": 3}
TRACE = types.SimpleNamespace(busy_s=4.2)  # a capture in which the device ran


@pytest.fixture
def registry():
    reg = monitor.MetricsRegistry()
    old = monitor.set_registry(reg)
    try:
        yield reg
    finally:
        monitor.set_registry(old)


def read(window=WINDOW, trace=TRACE):
    return run.load_module("layer_metrics", "dispatch_launch_ms").read(
        trace, {}, window, None)


def launches(reg, *ms):
    for v in ms:
        reg.histogram("dl4j_phase_duration_ms", "", phase="launch").observe(v)


def test_reader_is_declared_for_the_training_cells():
    entry = {m["name"]: m for m in BENCH["per_layer"]}["dispatch_launch_ms"]
    assert entry["source"] == "program_span" and entry["unit"] == "ms"
    assert entry["layer"] == "training container" and entry["better"] == "lower"
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["workloads"] == [w["name"] for w in BENCH["workloads"]
                                  if w["traffic"].startswith("pretrain")]


def test_nothing_to_read_is_none_not_zero(registry):
    assert read() is None  # the parent's program: no launch span
    registry.histogram("dl4j_phase_duration_ms", "", phase="device_step").observe(1400.0)
    assert read() is None  # another phase is not the launch


def test_the_mean_of_the_windows_launches(registry):
    launches(registry, 3.0, 3.5, 2.5)
    assert read() == {"value": pytest.approx(3.0), "min_ms": 2.5,
                      "max_ms": 3.5, "launches": 3}


def test_the_call_that_compiles_is_told_apart_by_name(registry):
    # set-up's warm dispatch: the program names its call compile_launch
    registry.histogram("dl4j_phase_duration_ms", "",
                       phase="compile_launch").observe(13020.0)
    launches(registry, 3.0, 3.5, 2.5)
    assert read()["value"] == pytest.approx(3.0)


@pytest.mark.parametrize("trace", [None, types.SimpleNamespace(busy_s=0.0)])
def test_a_registry_metric_asks_nothing_of_the_capture(registry, trace):
    launches(registry, 3.0, 3.5, 2.5)
    assert read(trace=trace) == read()


@pytest.mark.parametrize("seen, dispatches", [
    ((3.0, 3.5), 3),                      # a dispatch is missing
    ((13020.0, 3.0, 3.5, 2.5), 3),        # a launch outside the window
    ((3.0, 3.5, 2.5), 0),                 # an empty window
])
def test_reads_nothing_it_cannot_vouch_for(registry, seen, dispatches):
    launches(registry, *seen)
    assert read({"dispatches": dispatches}) is None


def test_the_count_follows_the_window(registry):
    launches(registry, 3.0, 3.5, 2.5, 2.0)
    assert read({"dispatches": 4})["value"] == pytest.approx(2.75)
