"""The benchmark's own tests, under the tier-1 gate (they live with the
benchmark, in ``benchmarks/tests/``)."""
from benchmarks.tests.test_benchmark_harness import *  # noqa: F401,F403
from benchmarks.tests.test_layer_readers import *  # noqa: F401,F403
from benchmarks.tests.test_hybrid_cell import *  # noqa: F401,F403
from benchmarks.tests.test_looped_cell import *  # noqa: F401,F403
from benchmarks.tests.test_sambay_cell import *  # noqa: F401,F403
from benchmarks.tests.test_setup_readers import *  # noqa: F401,F403
from benchmarks.tests.test_lfm2_cell import *  # noqa: F401,F403


def test_no_test_of_the_benchmark_is_shadowed_by_another():
    """``import *`` keeps the last of two tests with one name: every test
    of ``benchmarks/tests`` has a name of its own (but for the one this
    file shadows on purpose, below)."""
    import collections
    import glob
    import os
    import re

    here = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmarks", "tests")
    names = collections.Counter(
        name for path in glob.glob(os.path.join(here, "test_*.py"))
        for name in re.findall(r"^def (test_\w+)", open(path).read(), re.M))
    assert [n for n, k in names.items() if k > 1] == []


def test_new_metrics_are_declared_for_the_hybrid_cell_only():
    """Shadows the test of this name in ``benchmarks/tests/test_hybrid_cell.py``,
    which takes every configuration but the hybrid one for a GPT one and so
    cannot hold beside a third family; that file is the benchmark's, and a
    ``model_config`` PR may not edit it (PERF.md 7.2g). The same assertions,
    with the GPT cells told by their traffic's driver."""
    from benchmarks import run
    from benchmarks.tests import test_hybrid_cell as hybrid_cell

    bench = hybrid_cell.BENCH
    entries = {m["name"]: m for m in bench["per_layer"]}
    for name, layer in (("hybrid_train_step_mfu", "model step"),
                        ("ssd_fwd_roofline", "kernels"),
                        ("ssd_bwd_roofline", "kernels"),
                        ("hybrid_flash_fwd_roofline", "kernels"),
                        ("hybrid_flash_bwd_roofline", "kernels")):
        e = entries[name]
        assert e["workloads"] == [hybrid_cell.CELL] and e["layer"] == layer
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "%", "higher", "device_trace", "train_tokens_per_s")
    driver_of = lambda w: run.load_json(
        run.HERE, "traffic", w["traffic"] + ".json")["driver"]
    gpt_cells = [w["name"] for w in bench["workloads"]
                 if driver_of(w) == "train_scan"]
    assert len(gpt_cells) == 3
    for name in ("train_step_mfu", "flash_fwd_roofline", "flash_bwd_roofline"):
        assert entries[name]["workloads"] == gpt_cells
    assert "workloads" not in entries["train_dispatch_host_ms"]


def test_setup_metrics_are_declared_as_the_issue_gives_them():
    """Shadows the test of this name in
    ``benchmarks/tests/test_setup_readers.py``, which holds the five metrics
    of set-up to be the LAST five per-layer metrics and so cannot hold beside
    a metric that a later cell appends (new entries go at the end of their
    list); that file is the benchmark's, and a ``model_config`` PR may not
    edit it (PERF.md 7.2i). The same assertions, with the five told by their
    place right after the metrics that stood before them and before the
    metrics that list only later cells."""
    import os

    from benchmarks import run
    from benchmarks.tests import test_setup_readers as setup

    bench = setup.BENCH
    entries = {m["name"]: m for m in bench["per_layer"]}
    names = [m["name"] for m in bench["per_layer"]]
    want = {
        "first_dispatch_trace_s": ("s", "program_span", "training container", "setup_s"),
        "first_dispatch_lower_s": ("s", "program_span", "training container", "setup_s"),
        "first_dispatch_load_s": ("s", "program_span", "training container", "setup_s"),
        "step_executable_mib": ("MiB", "program_counter", "model step", "setup_s"),
        "step_compiled_peak_gb": ("GB", "program_counter", "model step",
                                  "train_tokens_per_s")}
    at = names.index("first_dispatch_trace_s")
    assert names[at:at + 5] == list(want)
    listed = set(setup.LISTED)
    assert all(set(entries[n].get("workloads", [""])).isdisjoint(listed)
               for n in names[at + 5:])
    for name, (unit, source, layer, moves) in want.items():
        e = entries[name]
        assert (e["unit"], e["source"], e["layer"], e["moves"]) == (
            unit, source, layer, moves)
        assert e["better"] == "lower" and e["workloads"] == setup.LISTED
        assert set(e) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(
            run.ROOT, "benchmarks", "layer_metrics", name + ".py"))
    # they are the first per-layer metrics that move setup_s
    assert [m["name"] for m in bench["per_layer"] if m["moves"] == "setup_s"] \
        == list(want)[:4]
    assert {w["name"] for w in bench["workloads"]} >= listed
