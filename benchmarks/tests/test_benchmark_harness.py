"""Tests of the benchmark itself, on the CPU at a tiny width.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Parametrised over the entries of ``BENCHMARK.json``, so a later cell is covered
without an edit. Nothing here is a chip result.
"""

import json
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import correct, flops, run, trace_reduce  # noqa: E402
from benchmarks.reference import gpt_plain  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
WIDTH = re.compile(r"(hidden|intermediate|latent|state|proj|n_embd|n_inner|"
                   r"head_dim|d_model|_dim$|_rank$|expansion|per_tok)")


def test_benchmark_json_keeps_to_the_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51 and isinstance(BENCH["run_seconds"], int)
    runs = 2 + 14 * 24  # the limit is what fits with the full 24 cells
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert BENCH["command"][:2] == ["python3", "benchmarks/run.py"]
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("benchmarks/")
        assert 1 <= len(c["why"]) <= 200 and 1 <= len(c["source"]) <= 200
        body = run.load_json(ROOT, c["file"])
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        assert not [k for k in c["reduced"] if WIDTH.search(k)], "a width is cut"
        assert body["source"] == c["source"]
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e and "bound" not in m
        assert set(m.get("workloads", [])) <= set(CELLS)
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(CELLS) // 4)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("name", CELLS)
def test_cell_resolves_to_files(name):
    w = {c["name"]: c for c in BENCH["workloads"]}[name]
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    cell = run.load_cell(BENCH, name)
    assert callable(cell["driver"].run) and callable(cell["driver"].rehearse)
    assert any("limit" in v for v in cell["limits"].values() if isinstance(v, dict))
    for m in run.metrics_of(BENCH, "per_layer", name):
        assert callable(run.load_module("layer_metrics", m["name"]).read)
    cfg = cell["config"]
    assert cfg["n_embd"] % cfg["n_head"] == 0
    assert cell["traffic"]["seq_len"] <= cfg["n_positions"]


def test_trace_reduce_on_a_written_out_trace():
    # one plane: a `while` of 100 ns holding a fusion (30) and a kernel (50),
    # a 40 ns gap, then a copy of 20 ns: busy 120 of a 160 ns span
    plane = [("%while.1 = (f32[]) while(...)", 0, 100),
             ("%fusion.3 = bf16[8] fusion(...)", 10, 30),
             ("%flash_fwd.7 = bf16[8] custom-call(...)", 45, 50),
             ("%copy.2 = f32[8] copy(...)", 140, 20)]
    t = trace_reduce.TraceReduction([plane])
    assert t.busy_s == pytest.approx(120e-9)
    assert t.self_s_by_group == pytest.approx(
        {"while": 20e-9, "fusion": 30e-9, "flash_fwd": 50e-9, "copy": 20e-9})
    assert t.kernel_seconds("flash_fwd") == pytest.approx(50e-9)
    assert t.kernel_seconds("flash_dq", "flash_dkv") == 0.0
    assert t.top_groups(1) == [["flash_fwd", pytest.approx(50e-9)]]
    assert t.idle_gaps() == [["while>copy", pytest.approx(40e-9)]]
    # two planes are averaged, as two chips would be
    assert trace_reduce.TraceReduction([plane, plane]).busy_s == pytest.approx(120e-9)
    assert trace_reduce.group_of("%jvp_flash_dq_.12.1 = ...") == "jvp_flash_dq_"


def test_flops_equal_a_hand_count():
    cfg = {"n_embd": 8, "n_inner": 32, "n_layer": 2, "n_head": 2, "vocab_size": 100}
    # per layer 3*64 + 64 + 2*8*32 = 768, attention 16*8 = 128; head 800
    assert flops.train_macs_per_token(cfg, 16) == 2 * (768 + 128) + 800
    assert flops.train_flops_per_token(cfg, 16) == 6 * 2592
    fwd = flops.flash_fwd_cost(batch=1, n_head=2, seq_len=16, head_dim=4)
    assert fwd["flops"] == 2 * (2 * 16 * 16 * 4) and fwd["bytes"] == 2 * (4 * 16 * 4 * 2 + 64)
    bwd = flops.flash_bwd_cost(batch=1, n_head=2, seq_len=16, head_dim=4)
    assert bwd["flops"] == 2 * fwd["flops"]
    peaks = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
    assert flops.roofline_seconds({"flops": 5e3, "bytes": 1e3}, peaks) == (5.0, "compute")
    assert flops.roofline_seconds({"flops": 1e3, "bytes": 5e3}, peaks) == (5.0, "memory")
    # the published sizes give the issue's counts
    gpt2 = run.load_json(ROOT, "benchmarks/configs/gpt2-medium.json")
    assert round(flops.train_macs_per_token(gpt2, 1024) / 1e6, 1) == 378.6
    assert gpt_plain.num_params(gpt2) == 406_236_241


def _tiny_cell(name=CELLS[0]):
    return run.load_cell(BENCH, name, rehearsal=True)


def test_reference_agrees_with_the_programs_gradients():
    import jax

    from benchmarks.drivers import train_scan
    from deeplearning4j_tpu.datasets.dataset import DataSet

    cfg = dict(_tiny_cell()["config"])
    cfg["train"] = dict(cfg["train"], compute_dtype="float32")
    net = train_scan.build_net(cfg, seed=5).init()
    names = [i.name for i in net.impls]
    ref = gpt_plain.init_params(cfg, 5)
    net.params = train_scan.to_program(ref, names)
    tok = gpt_plain.make_tokens(cfg, 5, 1, 2, 32)[0]
    grads, score = net.gradient_and_score(DataSet(
        tok[:, :-1].astype(np.float32), tok[:, 1:].astype(np.float32)))
    loss, g = jax.value_and_grad(lambda p: gpt_plain.loss_sum(
        p, tok[:, :-1], tok[:, 1:], cfg) / tok[:, 1:].size)(ref)
    assert score == pytest.approx(float(loss), rel=1e-5)
    theirs = train_scan.to_program(g, names)
    for layer in names:
        for leaf, want in theirs[layer].items():
            np.testing.assert_allclose(grads[layer][leaf], want,
                                       rtol=2e-3, atol=1e-7, err_msg=f"{layer}.{leaf}")


def _run_cell(capsys, name, *extra):
    rc = run.main(["--workload", name, "--seed", "2147483659", "--seconds", "1",
                   "--rehearsal", *extra])
    assert rc == 0
    return json.loads(capsys.readouterr().out.strip().splitlines()[-1])


@pytest.mark.parametrize("name", CELLS)
def test_driver_prints_the_contracts_line(capsys, name):
    line = _run_cell(capsys, name)
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "compared"
    assert line["correct"] is True and line["failed"] == 0 and line["attempted"] >= 1
    want = {"rehearsal_cpu." + m["name"]
            for m in run.metrics_of(BENCH, "end_to_end", name)}
    assert set(line["metrics"]) == want  # a CPU number under no device metric's name
    assert all(set(v) == {"value", "unit"} and v["value"] > 0
               for v in line["metrics"].values())
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(line["device"])
    assert line["extra"]["window"]["compiles_in_window"] == 0
    for c in line["compared"].values():
        assert c["value"] <= c["limit"]


def test_traced_run_leaves_out_what_it_cannot_read(capsys):
    # a CPU capture has no device plane: every reader returns nothing
    line = _run_cell(capsys, CELLS[0], "--trace", "1")
    assert line["metrics"] == {} and line["device"]["busy_s"] == 0
    assert line["breakdown"] == {"device_ops": [], "idle_gaps": []}


def test_no_chip_is_no_result(capsys):
    with pytest.raises(SystemExit) as e:
        run.main(["--workload", CELLS[0], "--seed", "1", "--seconds", "1"])
    assert e.value.code not in (0, None)
    assert capsys.readouterr().out == ""


@pytest.mark.parametrize("precision", gpt_plain.CONTROLS)
def test_control_in_fp8_comes_out_not_correct(precision):
    """The reference put in the program's place, computed in fp8, the nearest
    precision below the bfloat16 that the configurations state."""
    cell = _tiny_cell()
    cfg, traffic = cell["config"], cell["traffic"]
    tok = gpt_plain.make_tokens(cfg, 11, traffic["steps_per_dispatch"],
                                traffic["batch"], traffic["seq_len"])
    ref = gpt_plain.follow(cfg, cfg["train"], 11, tok, 1)
    ctl = gpt_plain.follow(cfg, cfg["train"], 11, tok, 1, precision=precision)
    ok, compared = correct.judge(correct.training_gaps(ctl, ref), cell["limits"])
    assert not ok, compared
    same, _ = correct.judge(correct.training_gaps(ref, ref), cell["limits"])
    assert same


def test_calibrate_holds_controls_and_faults_to_the_limits(capsys):
    """calibrate.py's exit code: 0 only where the program came out correct
    and every control and fault did not."""
    from benchmarks import calibrate

    argv = ["--workload", CELLS[0], "--seeds", "2", "--control-seeds", "1",
            "--rehearsal"]
    assert calibrate.main(argv) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["wrong"] == [] and {"program", "half_batch"} <= set(last["summary"])


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_timed_path_comes_out_not_correct(capsys, monkeypatch, fault):
    """The rest of a run, the look for a chip skipped, with the timed path
    broken underneath."""
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork as Net

    if fault == "state_unchanged":
        real = Net.fit_scan

        def fit_scan(self, *a, **kw):
            keep = self.params, self.opt_state, self.states
            out = real(self, *a, **kw)
            self.params, self.opt_state, self.states = keep
            return out

        monkeypatch.setattr(Net, "fit_scan", fit_scan)
    else:
        real = Net.stage_scan

        def stage_scan(self, ds, batch_size):
            xb, yb = real(self, ds, batch_size)
            half = max(1, batch_size // 2)  # the mean is taken over the rest
            return xb[:, :half], yb[:, :half]

        monkeypatch.setattr(Net, "stage_scan", stage_scan)
    line = _run_cell(capsys, CELLS[0])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
