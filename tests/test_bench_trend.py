"""Perf-regression trend gate tests (scripts/bench_trend.py, ISSUE 16).

The gate's arithmetic (noise band ``mean - max(threshold·mean, nσ)``,
one-sided: improvements never flag), the strict payload schema
(malformed history is exit 2, never a silent skip), the history loader
against the repo's own committed ``BENCH_r*.json`` rounds, the
``--check`` fixture mode ``stress_faultinject.quick_check`` wires in,
and the end-to-end CLI: real history stays green, a synthetic injected
regression exits 1 and names the metric in TREND.md.
"""

import json

import pytest

from scripts.bench_trend import (
    DEFAULT_NSIGMA,
    DEFAULT_THRESHOLD,
    DEFAULT_WINDOW,
    TrendError,
    _fixture_check,
    _validate_payload,
    extract_metrics,
    gate,
    gate_metric,
    load_history,
    main,
    run_check,
)


def _payload(value=100.0, **subs):
    return {"metric": "tokens_per_sec", "value": value, "unit": "tok/s",
            "schema_version": 1,
            "sub_benchmarks": {k: {"value": v} for k, v in subs.items()}}


# ----------------------------------------------------- gate arithmetic

def test_gate_metric_flat_series_passes():
    r = gate_metric([100.0, 101.0, 99.0, 100.5], 100.0,
                    DEFAULT_THRESHOLD, DEFAULT_NSIGMA)
    assert not r["regressed"]
    assert r["mean"] == pytest.approx(100.125)
    assert r["floor"] == pytest.approx(100.125 - 0.10 * 100.125)


def test_gate_metric_injected_regression_flags():
    r = gate_metric([100.0, 101.0, 99.0, 100.5], 60.0,
                    DEFAULT_THRESHOLD, DEFAULT_NSIGMA)
    assert r["regressed"] and r["fresh"] < r["floor"]
    assert r["delta_frac"] == pytest.approx((60.0 - 100.125) / 100.125)


def test_gate_metric_one_sided():
    """Improvements NEVER flag — only the downside is gated."""
    r = gate_metric([100.0, 101.0, 99.0, 100.5], 500.0,
                    DEFAULT_THRESHOLD, DEFAULT_NSIGMA)
    assert not r["regressed"]


def test_gate_metric_noisy_series_widens_band():
    """The σ term: a drop that the 10% threshold alone would flag
    passes when the prior window is honestly that noisy."""
    noisy = [100.0, 140.0, 80.0, 120.0]
    mean = sum(noisy) / 4
    r = gate_metric(noisy, mean * 0.85, DEFAULT_THRESHOLD, DEFAULT_NSIGMA)
    assert r["floor"] < mean * 0.9  # 3σ beat the 10% band
    assert not r["regressed"]


def test_gate_marks_new_metrics_without_verdict():
    history = [(1, _payload(100.0, a=10.0)), (2, _payload(101.0, a=11.0))]
    fresh = _payload(100.5, a=10.5, brand_new=7.0)
    report = gate(history, fresh, DEFAULT_WINDOW, DEFAULT_THRESHOLD,
                  DEFAULT_NSIGMA)
    assert report["brand_new"] == {"fresh": 7.0, "new": True,
                                   "regressed": False}
    assert not report["headline"]["regressed"]
    assert report["a"]["priors"] == [10.0, 11.0]


# ------------------------------------------------------ payload schema

@pytest.mark.parametrize("payload,fragment", [
    ([1, 2], "expected object"),
    ({"value": 1.0, "unit": "x"}, "missing required key 'metric'"),
    ({"metric": "m", "value": "fast", "unit": "x"}, "key 'value' is str"),
    ({"metric": "m", "value": 1.0, "unit": "x", "schema_version": 99},
     "schema_version 99"),
    ({"metric": "m", "value": 1.0, "unit": "x", "sub_benchmarks": []},
     "sub_benchmarks is list"),
    ({"metric": "m", "value": 1.0, "unit": "x",
      "sub_benchmarks": {"s": {"value": None}}}, "expected number"),
])
def test_validate_payload_rejects(payload, fragment):
    with pytest.raises(TrendError) as e:
        _validate_payload(payload, "where")
    assert fragment in str(e.value)


def test_validate_payload_accepts_failed_sub_with_error():
    p = {"metric": "m", "value": 1.0, "unit": "x",
         "sub_benchmarks": {"s": {"error": "OOM"}}}
    assert _validate_payload(p, "w") is p
    assert extract_metrics(p) == {"headline": 1.0}  # errored sub skipped


def test_extract_metrics_orders_and_filters():
    p = _payload(5.0, b=2.0, a=1.0)
    p["sub_benchmarks"]["broken"] = {"error": "boom"}
    assert extract_metrics(p) == {"headline": 5.0, "a": 1.0, "b": 2.0}


# --------------------------------------------------- committed history

def test_load_history_rounds_in_order(tmp_path):
    _write_history(tmp_path, [100.0, 102.0, 99.0])
    rounds = load_history(str(tmp_path))
    assert [n for n, _ in rounds] == [1, 2, 3]
    for _, payload in rounds:
        assert isinstance(payload["value"], (int, float))


def test_load_history_rejects_malformed(tmp_path):
    (tmp_path / "BENCH_r01.json").write_text(json.dumps({"rc": 0}))
    with pytest.raises(TrendError, match="missing 'parsed'"):
        load_history(str(tmp_path))


def test_fixture_check_green():
    assert _fixture_check(DEFAULT_WINDOW) == []


def test_run_check_valid_history(tmp_path, capsys):
    _write_history(tmp_path, [100.0, 102.0, 99.0])
    assert run_check(str(tmp_path), DEFAULT_WINDOW) == 0
    assert "gate fixture green" in capsys.readouterr().out


def test_run_check_empty_dir_fails(tmp_path, capsys):
    assert run_check(str(tmp_path), DEFAULT_WINDOW) == 2
    assert "no BENCH_r*.json history" in capsys.readouterr().out


# ------------------------------------------------------- CLI end-to-end

def _write_history(d, values):
    for i, v in enumerate(values, start=1):
        rec = {"n": i, "cmd": "bench", "rc": 0, "tail": "",
               "parsed": _payload(v, gemm=v * 2)}
        (d / f"BENCH_r{i:02d}.json").write_text(json.dumps(rec))


def test_main_latest_round_green(tmp_path, capsys):
    _write_history(tmp_path, [100.0, 102.0, 99.0, 101.0, 100.0])
    assert main(["--history", str(tmp_path)]) == 0
    md = (tmp_path / "TREND.md").read_text()
    assert "No regressions." in md and "| headline |" in md
    assert "r05 (latest committed round)" in md


def test_main_injected_regression_exits_1(tmp_path, capsys):
    _write_history(tmp_path, [100.0, 102.0, 99.0, 101.0])
    fresh = tmp_path / "fresh.json"
    bad = _payload(100.5, gemm=120.0)  # headline fine, gemm tanked
    fresh.write_text(json.dumps(bad))
    assert main(["--history", str(tmp_path), "--fresh", str(fresh)]) == 1
    out = capsys.readouterr().out
    assert "REGRESSED gemm" in out
    md = (tmp_path / "TREND.md").read_text()
    assert "**REGRESSED**" in md
    assert md.count("ok") >= 1  # the clean headline still renders ok


def test_main_malformed_candidate_exits_2(tmp_path, capsys):
    _write_history(tmp_path, [100.0, 101.0])
    fresh = tmp_path / "fresh.json"
    fresh.write_text(json.dumps({"metric": "m"}))  # no value/unit
    assert main(["--history", str(tmp_path), "--fresh", str(fresh)]) == 2
    assert "missing required key" in capsys.readouterr().err


def test_main_too_little_history_exits_2(tmp_path, capsys):
    _write_history(tmp_path, [100.0])
    assert main(["--history", str(tmp_path)]) == 2
    assert "need >=2 committed rounds" in capsys.readouterr().err


def test_main_writes_report_where_asked(tmp_path):
    """A steady history passes its own gate, and ``--out`` places the
    report (the repo itself holds no rounds: the driver's ledger
    replaced them)."""
    _write_history(tmp_path, [100.0, 102.0, 99.0, 101.0])
    out = tmp_path / "elsewhere" / "TREND.md"
    out.parent.mkdir()
    assert main(["--history", str(tmp_path), "--out", str(out)]) == 0
    assert "No regressions." in out.read_text()


def test_bench_schema_version_pinned():
    """bench.py stamps the schema_version this gate knows."""
    import bench
    from scripts.bench_trend import KNOWN_SCHEMA_VERSIONS
    assert bench.BENCH_SCHEMA_VERSION in KNOWN_SCHEMA_VERSIONS


def test_quick_check_wires_bench_trend_section():
    from scripts.stress_faultinject import bench_trend_section
    assert bench_trend_section() == []
