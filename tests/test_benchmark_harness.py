"""The benchmark's own tests, under the tier-1 gate (they live with the
benchmark, in ``benchmarks/tests/``)."""
from benchmarks.tests.test_benchmark_harness import *  # noqa: F401,F403
from benchmarks.tests.test_layer_readers import *  # noqa: F401,F403
from benchmarks.tests.test_hybrid_cell import *  # noqa: F401,F403
from benchmarks.tests.test_looped_cell import *  # noqa: F401,F403
from benchmarks.tests.test_sambay_cell import *  # noqa: F401,F403
from benchmarks.tests.test_setup_readers import *  # noqa: F401,F403


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
