"""Tests of what the hybrid state-space family brought to the benchmark, on
the CPU at the rehearsal's tiny width: the operation count against a hand
count, the three readers on a written-out trace, and the controls and faults
held to the rehearsal's limits.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import correct, flops_hybrid, run, trace_reduce  # noqa: E402
from benchmarks.reference import granite_hybrid_plain as plain  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
CELL = "granite-4.0-h-micro.pretrain-4k"
HAND = {"hidden_size": 8, "shared_intermediate_size": 16, "mamba_n_heads": 2,
        "mamba_d_head": 8, "mamba_d_state": 4, "mamba_n_groups": 1,
        "mamba_chunk_size": 4, "num_attention_heads": 2,
        "num_key_value_heads": 1, "vocab_size": 100,
        "layer_types": ["mamba", "attention"]}
PEAKS = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}


def test_hybrid_flops_equal_a_hand_count():
    # the gated MLP 3*8*16 = 384 in both layers. mamba: in_proj 8*(32+8+2) =
    # 336, out_proj 128, the scan C B^T 4*4/2 = 8 a group and (4*8/2 + 2*8*4)
    # = 80 a head = 168. attention: q and o 128, k and v 2*8*4 = 64, the
    # causal half of 16 keys 16*8 = 128. The head 800.
    assert flops_hybrid.train_macs_per_token(HAND, 16) == \
        (336 + 128 + 168 + 384) + (128 + 64 + 128 + 384) + 800
    assert flops_hybrid.train_flops_per_token(HAND, 16) == 6 * 2520
    fwd = flops_hybrid.ssd_fwd_cost(HAND, batch=1, seq_len=16)
    # x and y 16*2*8*2 B each, B and C 16*4*2, dt 16*2*4, 4 chunks' states
    assert fwd == {"flops": 2.0 * 168 * 16,
                   "bytes": 2 * 512 + 2 * 128 + 128 + 4 * 2 * 8 * 4 * 4}
    bwd = flops_hybrid.ssd_bwd_cost(HAND, batch=1, seq_len=16)
    assert bwd == {"flops": 2 * fwd["flops"],
                   "bytes": 3 * 512 + 4 * 128 + 2 * 128 + 1024}
    # the published sizes give the issue's counts
    cfg = run.load_json(ROOT, "benchmarks/configs/granite-4.0-h-micro.json")
    assert round(flops_hybrid.train_macs_per_token(cfg, 4096) / 1e6, 1) == 794.6
    assert plain.num_params(cfg) == 772_160_448


def test_configuration_states_the_cut_and_the_published_counts():
    cfg = run.load_json(ROOT, "benchmarks/configs/granite-4.0-h-micro.json")
    assert cfg["reduced"] == ["layer_types", "n_layer", "vocab_size"]
    assert cfg["layer_types"] == ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    assert cfg["n_layer"] == len(cfg["layer_types"]) == 10
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 100352
    assert cfg["num_hidden_layers"] == cfg["published"]["num_hidden_layers"] == 40
    assert (cfg["n_embd"], cfg["n_head"], cfg["n_positions"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["max_position_embeddings"])
    assert "deployment" in cfg and "initializer_range" in cfg["assumed"]


def _read(name, planes, window):
    cell = {"config": dict(HAND)}
    return run.load_module("layer_metrics", name).read(
        trace_reduce.TraceReduction(planes) if planes is not None else None,
        cell, window, PEAKS)


def test_readers_on_a_written_out_trace():
    # one plane, one step of one row of 16 tokens: a while of 100 s holding
    # the forward kernel twice (the block recomputed: 10 s and 10 s) and the
    # backward kernel (40 s), then a copy; busy 120 s
    sec = int(1e9)
    plane = [("%while.1 = (f32[]) while(...)", 0, 100 * sec),
             ("%ssd_fwd.3 = bf16[8] custom-call(...)", 10 * sec, 10 * sec),
             ("%ssd_fwd.4 = bf16[8] custom-call(...)", 30 * sec, 10 * sec),
             ("%ssd_bwd.7 = bf16[8] custom-call(...)", 45 * sec, 40 * sec),
             ("%copy.2 = f32[8] copy(...)", 140 * sec, 20 * sec)]
    window = {"batch": 1, "seq_len": 16, "steps": 1, "dispatches": 1}
    fwd = _read("ssd_fwd_roofline", [plane], window)
    # one scan a Mamba-2 layer a step: 5376 operations, 2432 bytes at 1e3
    # a second each: 5.376 s of compute against 20 s of the kernel
    assert fwd["value"] == pytest.approx(100 * 5.376 / 20)
    assert (fwd["bound"], fwd["kernel_events"]) == ("compute", 2)
    bwd = _read("ssd_bwd_roofline", [plane], window)
    assert bwd["value"] == pytest.approx(100 * 10.752 / 40)
    mfu = _read("hybrid_train_step_mfu", [plane], window)
    assert mfu["value"] == pytest.approx(100 * 6 * 2520 * 16 / (120 * 1e3))
    # nothing to read is None, never 0: no kernel in the capture, no capture,
    # and a configuration of another family
    bare = [plane[:1] + plane[-1:]]
    assert _read("ssd_fwd_roofline", bare, window) is None
    assert _read("ssd_bwd_roofline", bare, window) is None
    for name in ("ssd_fwd_roofline", "ssd_bwd_roofline", "hybrid_train_step_mfu"):
        assert _read(name, None, window) is None
    gpt = {"config": {"n_embd": 8, "n_layer": 2}}
    assert run.load_module("layer_metrics", "hybrid_train_step_mfu").read(
        trace_reduce.TraceReduction([plane]), gpt, window, PEAKS) is None


def test_attention_readers_on_a_written_out_trace():
    # one step of one row of 16 tokens through the one attention layer: the
    # streamed forward kernel twice (the block recomputed: 4 s and 4 s), then
    # the two backward kernels (8 s and 12 s)
    sec = int(1e9)
    plane = [("%while.1 = (f32[]) while(...)", 0, 100 * sec),
             ("%flash_fwd.3 = bf16[8] custom-call(...)", 10 * sec, 4 * sec),
             ("%flash_fwd.4 = bf16[8] custom-call(...)", 30 * sec, 4 * sec),
             ("%flash_dq.7 = bf16[8] custom-call(...)", 45 * sec, 8 * sec),
             ("%flash_dkv.8 = bf16[8] custom-call(...)", 60 * sec, 12 * sec)]
    window = {"batch": 1, "seq_len": 16, "steps": 1, "dispatches": 1}
    # 2 query heads of 4 over 1 key/value head: 16*16*4/2 = 512 multiply-adds
    # a head and a product; q and o 2*16*8*2 B, k and v 2*16*4*2 B, the
    # log-sum-exp 16*2*4 B
    assert flops_hybrid.attention_fwd_cost(HAND, 1, 16) == {
        "flops": 2 * 2.0 * 1024, "bytes": 512 + 256 + 128}
    assert flops_hybrid.attention_bwd_cost(HAND, 1, 16) == {
        "flops": 4 * 2.0 * 1024, "bytes": 1024 + 512 + 128}
    fwd = _read("hybrid_flash_fwd_roofline", [plane], window)
    assert fwd["value"] == pytest.approx(100 * 4.096 / 8)
    assert (fwd["bound"], fwd["kernel_events"]) == ("compute", 2)
    bwd = _read("hybrid_flash_bwd_roofline", [plane], window)
    assert bwd["value"] == pytest.approx(100 * 8.192 / 20)
    assert bwd["kernel_events"] == 2
    # nothing to read is None, never 0: no kernel, no capture, another family
    bare = [plane[:1]]
    gpt = {"config": {"n_embd": 8, "n_layer": 2, "n_head": 2}}
    for name in ("hybrid_flash_fwd_roofline", "hybrid_flash_bwd_roofline"):
        assert _read(name, bare, window) is None
        assert _read(name, None, window) is None
        assert run.load_module("layer_metrics", name).read(
            trace_reduce.TraceReduction([plane]), gpt, window, PEAKS) is None


def test_new_metrics_are_declared_for_the_hybrid_cell_only():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in (("hybrid_train_step_mfu", "model step"),
                        ("ssd_fwd_roofline", "kernels"),
                        ("ssd_bwd_roofline", "kernels"),
                        ("hybrid_flash_fwd_roofline", "kernels"),
                        ("hybrid_flash_bwd_roofline", "kernels")):
        e = entries[name]
        assert e["workloads"] == [CELL] and e["layer"] == layer
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "%", "higher", "device_trace", "train_tokens_per_s")
    gpt_cells = [w["name"] for w in BENCH["workloads"]
                 if w["config"] != "granite-4.0-h-micro"]
    for name in ("train_step_mfu", "flash_fwd_roofline", "flash_bwd_roofline"):
        assert entries[name]["workloads"] == gpt_cells
    assert "workloads" not in entries["train_dispatch_host_ms"]


def _tiny_cell():
    return run.load_cell(BENCH, CELL, rehearsal=True)


@pytest.mark.parametrize("fault", [
    {"precision": "fp8"}, {"precision": "fp8_forward"}, {"rows_used": 1},
    {"carry_state": False}], ids=lambda f: "-".join(map(str, f.values())))
def test_control_or_fault_in_the_references_place_is_not_correct(fault):
    """The reference put in the program's place: computed in fp8, with half
    of each batch left out, with the scan's state not carried from chunk to
    chunk."""
    cell = _tiny_cell()
    cfg, traffic = cell["config"], cell["traffic"]
    tok = plain.make_tokens(cfg, 11, traffic["steps_per_dispatch"],
                            traffic["batch"], traffic["seq_len"])
    ref = plain.follow(cfg, cfg["train"], 11, tok, traffic["batch"])
    other = plain.follow(cfg, cfg["train"], 11, tok, traffic["batch"], **fault)
    ok, compared = correct.judge(correct.training_gaps(other, ref),
                                 cell["limits"])
    assert not ok, compared
    same, _ = correct.judge(correct.training_gaps(ref, ref), cell["limits"])
    assert same


def test_calibrate_hybrid_holds_controls_and_faults_to_the_limits(capsys):
    from benchmarks import calibrate_hybrid

    argv = ["--workload", CELL, "--seeds", "2", "--control-seeds", "1",
            "--rehearsal"]
    assert calibrate_hybrid.main(argv) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["wrong"] == [] and {
        "program", "control_fp8", "control_fp8_forward", "half_batch",
        "state_not_carried"} == set(last["summary"])


def test_a_program_that_drops_the_state_comes_out_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: the scan starts every chunk from
    zero."""
    from deeplearning4j_tpu.ops import ssd

    real = ssd.ssd_chunked

    def every_chunk_a_row(x, dt, A, B, C, chunk):
        rows = lambda z: z.reshape((-1, chunk) + z.shape[2:])
        return real(rows(x), rows(dt), A, rows(B), rows(C), chunk) \
            .reshape(x.shape)

    monkeypatch.setattr(ssd, "ssd_chunked", every_chunk_a_row)
    rc = run.main(["--workload", CELL, "--seed", "2147483659", "--seconds",
                   "1", "--rehearsal"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
