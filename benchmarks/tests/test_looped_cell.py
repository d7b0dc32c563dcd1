"""Tests of what the looped language model brought to the benchmark, on the
CPU at the rehearsal's tiny width: the operation count against a hand count,
the three readers on a written-out trace, and the controls and faults held to
the rehearsal's limits.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import correct, flops, flops_looped, run, trace_reduce  # noqa: E402
from benchmarks.reference import ouro_looped_plain as plain  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELL = "ouro-2.6b.pretrain-looped-4k"
CONFIG = "benchmarks/configs/ouro-2.6b.json"
HAND = {"hidden_size": 8, "intermediate_size": 16, "n_layer": 2,
        "num_attention_heads": 2, "num_key_value_heads": 1, "head_dim": 4,
        "total_ut_steps": 3, "vocab_size": 100}
PEAKS = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
READERS = ("looped_train_step_mfu", "looped_flash_fwd_roofline",
           "looped_flash_bwd_roofline")


def test_looped_flops_equal_a_hand_count():
    # an application: q and o 2*8*8 = 128, k and v 2*8*4 = 64, the causal
    # half of 16 keys 16*8 = 128, the gated MLP 3*8*16 = 384: 704, six of
    # them a token (2 blocks, 3 passes); the head 800, three times
    assert flops_looped.block_applications(HAND) == 6
    assert flops_looped.train_macs_per_token(HAND, 16) == 6 * 704 + 3 * 800
    assert flops_looped.train_flops_per_token(HAND, 16) == 6 * 6624
    # the published sizes give the issue's counts
    cfg = run.load_json(ROOT, CONFIG)
    assert flops_looped.block_applications(cfg) == 32
    assert flops_looped.train_macs_per_token(cfg, 4096) == 2_013_265_920
    assert plain.num_params(cfg) == 461_443_073
    whole = dict(cfg, n_layer=48, vocab_size=49152)
    assert plain.num_params(whole) == 2_667_974_657
    assert plain.num_params(dict(whole, vocab_size=0)) == 48 * 51_388_416 \
        + 2048 + 2048 + 1


def test_configuration_states_the_cut_and_the_published_widths():
    cfg = run.load_json(ROOT, CONFIG)
    assert cfg["reduced"] == ["layer_types", "n_layer", "vocab_size"]
    assert cfg["layer_types"] == ["full_attention"] * 8 and cfg["n_layer"] == 8
    assert cfg["vocab_size"] * 4 == cfg["published"]["vocab_size"] == 49152
    assert cfg["num_hidden_layers"] == cfg["published"]["num_hidden_layers"] == 48
    assert (cfg["hidden_size"], cfg["intermediate_size"], cfg["head_dim"],
            cfg["num_attention_heads"], cfg["num_key_value_heads"],
            cfg["total_ut_steps"], cfg["rope_theta"], cfg["rms_norm_eps"]) == (
        2048, 5632, 128, 16, 16, 4, 1_000_000, 1e-6)
    assert (cfg["n_embd"], cfg["n_head"], cfg["n_positions"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["max_position_embeddings"])
    assert {"deployment", "departs", "reduced_why"} <= set(cfg)
    assert {"initializer_range", "bias", "exit_entropy_weight",
            "from_memory"} <= set(cfg["assumed"])
    assert cfg["train"]["recompute_blocks"] is True
    traffic = run.load_json(ROOT, "benchmarks/traffic/pretrain-looped-4k.json")
    assert (traffic["driver"], traffic["seq_len"], traffic["batch"],
            traffic["steps_per_dispatch"]) == ("train_scan_looped", 4096, 2, 2)


def _read(name, planes, window, config=HAND):
    return run.load_module("layer_metrics", name).read(
        trace_reduce.TraceReduction(planes) if planes is not None else None,
        {"config": dict(config)}, window, PEAKS)


def test_looped_readers_on_a_written_out_trace():
    # one step of one row of 16 tokens: six block applications, of which the
    # capture shows the forward kernel eight times (two of them in recomputed
    # bodies: 4 s each) and the two backward kernels six times (8 s and 12 s)
    sec = int(1e9)
    plane = [("%while.1 = (f32[]) while(...)", 0, 200 * sec)]
    plane += [(f"%flash_fwd.{i} = bf16[8] custom-call(...)", (1 + 5 * i) * sec,
               4 * sec) for i in range(8)]
    plane += [(f"%flash_dq.{i} = bf16[8] custom-call(...)", (50 + 21 * i) * sec,
               8 * sec) for i in range(6)]
    plane += [(f"%flash_dkv.{i} = bf16[8] custom-call(...)",
               (58 + 21 * i) * sec, 12 * sec) for i in range(6)]
    window = {"batch": 1, "seq_len": 16, "steps": 1, "dispatches": 1}
    # 2 heads of 4: 2 * (2 * 16 * 16 * 4) = 4096 operations a forward call
    one = flops.flash_fwd_cost(1, 2, 16, 4)
    assert one["flops"] == 4096 and one["bytes"] == 2 * (4 * 16 * 4 * 2 + 64)
    fwd = _read("looped_flash_fwd_roofline", [plane], window)
    assert fwd["value"] == pytest.approx(100 * 6 * 4.096 / 32)
    assert (fwd["bound"], fwd["kernel_events"]) == ("compute", 8)
    bwd = _read("looped_flash_bwd_roofline", [plane], window)
    assert bwd["value"] == pytest.approx(100 * 6 * 8.192 / 120)
    assert bwd["kernel_events"] == 12
    mfu = _read("looped_train_step_mfu", [plane], window)
    assert mfu["value"] == pytest.approx(100 * 6 * 6624 * 16 / (200 * 1e3))
    # the accepted reader counts n_layer calls a step: a third of these
    old = _read("flash_fwd_roofline", [plane], window,
                dict(HAND, n_embd=8, n_head=2))
    assert old["value"] == pytest.approx(fwd["value"] / 3)
    # nothing to read is None, never 0: no kernel, no capture, another family
    bare = [plane[:1]]
    for name in READERS[1:]:
        assert _read(name, bare, window) is None
    gpt = {"n_embd": 8, "n_layer": 2, "n_head": 2}
    for name in READERS:
        assert _read(name, None, window) is None
        assert _read(name, [plane], window, gpt) is None


def test_new_metrics_are_declared_for_the_looped_cell_only():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in zip(READERS, ("model step", "kernels", "kernels")):
        e = entries[name]
        assert e["workloads"] == [CELL] and e["layer"] == layer
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "%", "higher", "device_trace", "train_tokens_per_s")
    for name, m in entries.items():
        if name not in READERS and name != "dispatch_launch_ms":
            assert CELL not in m.get("workloads", ())
    assert entries["dispatch_launch_ms"]["workloads"][-1] == CELL
    cell = {w["name"]: w for w in BENCH["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "ouro-2.6b", "pretrain-looped-4k", 1)


def _tiny_cell():
    return run.load_cell(BENCH, CELL, rehearsal=True)


@pytest.mark.parametrize("fault", [
    {"precision": "fp8"}, {"precision": "fp8_forward"}, {"rows_used": 1},
    {"detach_passes": True}], ids=lambda f: "-".join(map(str, f.values())))
def test_looped_control_or_fault_in_the_references_place_is_not_correct(fault):
    """The reference put in the program's place: computed in fp8, with half
    of each batch left out, with a stop-gradient between the passes."""
    cell = _tiny_cell()
    cfg, traffic = cell["config"], cell["traffic"]
    tok = plain.make_tokens(cfg, 11, traffic["steps_per_dispatch"],
                            traffic["batch"], traffic["seq_len"])
    ref = plain.follow(cfg, cfg["train"], 11, tok)
    other = plain.follow(cfg, cfg["train"], 11, tok, **fault)
    ok, compared = correct.judge(correct.training_gaps(other, ref),
                                 cell["limits"])
    assert not ok, compared
    same, _ = correct.judge(correct.training_gaps(ref, ref), cell["limits"])
    assert same


def test_calibrate_looped_holds_controls_and_faults_to_the_limits(capsys):
    from benchmarks import calibrate_looped

    argv = ["--workload", CELL, "--seeds", "2", "--control-seeds", "1",
            "--rehearsal"]
    assert calibrate_looped.main(argv) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["wrong"] == [] and {
        "program", "control_fp8", "control_fp8_forward", "half_batch",
        "passes_detached"} == set(last["summary"])


def test_a_program_without_its_positions_comes_out_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: q and k are not rotated."""
    from deeplearning4j_tpu.nn.layers import hybrid

    monkeypatch.setattr(hybrid, "rotary", lambda x, theta: x)
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   "1", "--rehearsal"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
