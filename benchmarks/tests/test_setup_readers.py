"""Tests of the readers of set-up (``first_dispatch_trace_s``,
``first_dispatch_lower_s``, ``first_dispatch_load_s``, ``step_executable_mib``,
``step_compiled_peak_gb``): on a registry with known values, and ``None`` where
there is nothing to read or nothing a reader can vouch for, as from a program
that has no such span or gauge.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import run  # noqa: E402
from deeplearning4j_tpu import monitor  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
SPAN_READERS = {"first_dispatch_trace_s": "trace_step",
                "first_dispatch_lower_s": "lower_step",
                "first_dispatch_load_s": "load_step"}
GAUGE_READERS = ("step_executable_mib", "step_compiled_peak_gb")
#: the four cells whose metric lists no accepted test holds closed (PERF.md 7.2)
LISTED = ["gpt2-medium.pretrain-1k", "cerebras-gpt-590m.pretrain-2k",
          "granite-4.0-h-micro.pretrain-4k", "gpt2-medium.finetune-256"]
BYTES = {"code": 130_023_424, "arguments": 5_537_000_000,
         "temporaries": 6_024_000_000, "outputs": 5_537_000_016,
         "aliased": 5_537_000_000}


@pytest.fixture
def setup_registry():
    reg = monitor.MetricsRegistry()
    old = monitor.set_registry(reg)
    try:
        yield reg
    finally:
        monitor.set_registry(old)


def _read(name):
    return run.load_module("layer_metrics", name).read(
        None, {}, {"dispatches": 3}, None)


def _span(reg, phase, *ms):
    for v in ms:
        reg.histogram("dl4j_phase_duration_ms", "", phase=phase).observe(v)


def _first_dispatch(reg):
    """A warm first dispatch as the program writes it: four stages that tile
    ``compile_launch``, the fetch under ``compile``, then the window."""
    for phase, ms in (("trace_step", 4210.0), ("lower_step", 1890.0),
                      ("load_step", 5120.0), ("first_launch", 31.0),
                      ("compile_launch", 11251.5), ("compile", 12400.5)):
        _span(reg, phase, ms)
    _span(reg, "fetch", 1149.0, 1150.0, 1150.0, 1150.0)
    _span(reg, "launch", 3.0, 3.5, 2.5)


def _gauges(reg, sizes=BYTES, loads=(5120.0,)):
    """The gauges of what ``load_step`` returned, and that span."""
    _span(reg, "load_step", *loads)
    for part, size in sizes.items():
        reg.gauge("dl4j_step_program_bytes", "", part=part).set(size)


def test_setup_metrics_are_declared_as_the_issue_gives_them():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    want = {
        "first_dispatch_trace_s": ("s", "program_span", "training container", "setup_s"),
        "first_dispatch_lower_s": ("s", "program_span", "training container", "setup_s"),
        "first_dispatch_load_s": ("s", "program_span", "training container", "setup_s"),
        "step_executable_mib": ("MiB", "program_counter", "model step", "setup_s"),
        "step_compiled_peak_gb": ("GB", "program_counter", "model step",
                                  "train_tokens_per_s")}
    assert [m["name"] for m in BENCH["per_layer"]][-5:] == list(want)
    for name, (unit, source, layer, moves) in want.items():
        e = entries[name]
        assert (e["unit"], e["source"], e["layer"], e["moves"]) == (
            unit, source, layer, moves)
        assert e["better"] == "lower" and e["workloads"] == LISTED
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            ROOT, "benchmarks", "layer_metrics", name + ".py"))
    # they are the first per-layer metrics that move setup_s
    assert [m["name"] for m in BENCH["per_layer"] if m["moves"] == "setup_s"] \
        == list(want)[:4]
    assert {w["name"] for w in BENCH["workloads"]} >= set(LISTED)


@pytest.mark.parametrize("name", [*SPAN_READERS, *GAUGE_READERS])
def test_an_empty_registry_reads_none_not_zero(setup_registry, name):
    assert _read(name) is None  # the parent's program: no span, no gauge
    _span(setup_registry, "compile_launch", 13020.0)
    _span(setup_registry, "launch", 3.0, 3.5, 2.5)
    assert _read(name) is None  # other phases are not the stages


@pytest.mark.parametrize("name, phase", SPAN_READERS.items())
def test_a_stage_reads_its_span_in_seconds(setup_registry, name, phase):
    _first_dispatch(setup_registry)
    want = {"trace_step": 4.21, "lower_step": 1.89, "load_step": 5.12}[phase]
    assert _read(name)["value"] == pytest.approx(want)


def test_the_load_line_shows_the_whole_first_dispatch(setup_registry):
    _first_dispatch(setup_registry)
    got = _read("first_dispatch_load_s")
    assert got == {"value": pytest.approx(5.12),
                   "first_launch_s": pytest.approx(0.031),
                   "compile_launch_s": pytest.approx(11.2515),
                   "compile_s": pytest.approx(12.4005),
                   "first_fetch_s": pytest.approx(1.149)}
    # the three stages and the first launch tile compile_launch
    stages = sum(_read(n)["value"] for n in SPAN_READERS)
    assert stages + got["first_launch_s"] == pytest.approx(
        got["compile_launch_s"], abs=1e-3)


@pytest.mark.parametrize("name, phase", SPAN_READERS.items())
@pytest.mark.parametrize("seen", [(4210.0, 3980.0), (4210.0, 3.0, 2.5)],
                         ids=["two-programs", "a-stage-in-the-window"])
def test_a_stage_reads_nothing_it_cannot_vouch_for(setup_registry, name,
                                                   phase, seen):
    # a process that made two step programs, or one that made one inside the
    # window: which is the cell's first dispatch is not told
    _span(setup_registry, phase, *seen)
    assert _read(name) is None


def test_a_note_is_left_out_where_its_span_cannot_be_vouched_for(setup_registry):
    _first_dispatch(setup_registry)
    _span(setup_registry, "compile", 900.0)  # a second program's dispatch
    got = _read("first_dispatch_load_s")
    assert got["value"] == pytest.approx(5.12)
    assert "compile_s" not in got and "first_fetch_s" not in got
    assert got["compile_launch_s"] == pytest.approx(11.2515)


def test_the_executables_size_in_mib(setup_registry):
    _gauges(setup_registry)
    assert _read("step_executable_mib") == {"value": pytest.approx(124.0)}


def test_the_compilers_count_and_what_the_step_keeps(setup_registry):
    _gauges(setup_registry)
    got = _read("step_compiled_peak_gb")
    # arguments + temporaries + outputs - aliased, as compile_cell.py counts
    assert got["value"] == pytest.approx(11.561000016)
    assert (got["arguments_gb"], got["temporaries_gb"], got["outputs_gb"],
            got["aliased_gb"]) == (pytest.approx(5.537), pytest.approx(6.024),
                                   pytest.approx(5.537000016),
                                   pytest.approx(5.537))
    assert "xla_tflops_a_step" not in got and "span_passes" not in got
    setup_registry.gauge("dl4j_step_program_flops", "").set(112.91e12)
    for name, v in (("dl4j_recomputed_blocks", 8), ("dl4j_span_passes", 4),
                    ("dl4j_recompute_kept_values", 64),
                    ("dl4j_block_applications", 32),
                    ("dl4j_forwarded_values", 0)):
        setup_registry.gauge(name, "").set(v)
    got = _read("step_compiled_peak_gb")
    assert got["xla_tflops_a_step"] == pytest.approx(112.91)
    assert (got["recomputed_blocks"], got["recompute_kept_values"],
            got["span_passes"], got["block_applications"],
            got["forwarded_values"]) == (8, 64, 4, 32, 0)


@pytest.mark.parametrize("name, part", [
    ("step_executable_mib", "code"), ("step_compiled_peak_gb", "temporaries"),
    ("step_compiled_peak_gb", "aliased")])
def test_an_unset_gauge_reads_none_not_zero(setup_registry, name, part):
    _gauges(setup_registry, {p: v for p, v in BYTES.items() if p != part})
    assert _read(name) is None  # the part is not there
    setup_registry.gauge("dl4j_step_program_bytes", "", part=part)
    assert _read(name) is None  # made and never set: NaN, not 0
    setup_registry.gauge("dl4j_step_program_bytes", "", part=part).set(0.0)
    assert _read(name) is not None  # a measured zero is a value


@pytest.mark.parametrize("name", GAUGE_READERS)
@pytest.mark.parametrize("loads", [(), (5120.0, 4800.0)],
                         ids=["no-program", "two-programs"])
def test_a_gauge_is_read_only_beside_one_load(setup_registry, name, loads):
    # a gauge holds what was written last: two step programs in one process
    # (or gauges with no first dispatch behind them) are not the cell's
    _gauges(setup_registry, loads=loads)
    assert _read(name) is None


def test_the_harness_prints_what_the_program_wrote(setup_registry):
    # through the harness's own selection: the four listed cells read all
    # five, the two closed-list cells none
    _first_dispatch(setup_registry)
    _gauges(setup_registry, loads=())
    new = [*SPAN_READERS, *GAUGE_READERS]
    for cell in LISTED:
        names = [m["name"] for m in run.metrics_of(BENCH, "per_layer", cell)]
        assert names[-5:] == new
        for name in new:
            assert _read(name)["value"] > 0
    for cell in ("ouro-2.6b.pretrain-looped-4k",
                 "phi-4-mini-flash-reasoning.sft-8k"):
        names = [m["name"] for m in run.metrics_of(BENCH, "per_layer", cell)]
        assert not set(names) & set(new)
