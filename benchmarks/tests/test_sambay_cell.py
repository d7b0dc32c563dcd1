"""Tests of what the decoder-hybrid-decoder configuration (SambaY) brought to
the benchmark, on the CPU at the rehearsal's tiny width: the operation count
against a hand count and the issue's numbers, what the configuration states,
the five readers on a written-out trace, and the controls and faults held to
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

from benchmarks import correct, flops, flops_sambay, run, trace_reduce  # noqa: E402
from benchmarks.reference import sambay_plain as plain  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
SAMBAY_CELL = "phi-4-mini-flash-reasoning.sft-8k"
SAMBAY_CONFIG = "benchmarks/configs/phi-4-mini-flash-reasoning.json"
CUT = ["mamba", "sliding_attention", "mamba", "full_attention", "gmu",
       "cross_attention"]
# widths small enough to count by hand: heads of 2 in pairs, a window of 4
HAND = {"hidden_size": 8, "intermediate_size": 16, "mamba_expand": 2,
        "mamba_d_state": 4, "mamba_dt_rank": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "sliding_window": 4, "vocab_size": 100,
        "layer_types": CUT}
PEAKS = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
SAMBAY_READERS = ("sambay_train_step_mfu", "selscan_fwd_roofline",
                  "selscan_bwd_roofline", "sambay_flash_fwd_roofline",
                  "sambay_flash_bwd_roofline")


def test_sambay_flops_equal_a_hand_count_and_the_issues_numbers():
    f = flops_sambay
    # the gated MLP 3 * 8 * 16 = 384 in every block; mixers: Mamba in 8 * 32,
    # x 16 * (2 + 8), dt 2 * 16, out 16 * 8 = 576; attention 8 * (8 + 8) + 64
    # = 192; the unit 2 * 8 * 16 = 256; cross 2 * 64 = 128
    assert [f.matrix_macs_per_token(k, HAND) for k in CUT] == [
        960, 576, 960, 576, 640, 512]
    # 16 tokens: a full layer sees 8.5 keys a query, a window of 4 sees
    # (1 + 2 + 3 + 4 + 12 * 4) / 16 = 3.625; 4 heads, 2 + 4 a key each
    assert f.keys_seen("full_attention", HAND, 16) == 8.5
    assert f.keys_seen("sliding_attention", HAND, 16) == 3.625
    assert f.attention_macs_per_token("cross_attention", HAND, 16) == 24 * 8.5
    assert f.scan_macs_per_token(HAND) == 2 * 16 * 4
    assert f.train_macs_per_token(HAND, 16) == 4224 + 2 * 128 + 24 * (
        2 * 8.5 + 3.625) + 800
    # the published widths give the issue's counts
    cfg = run.load_json(ROOT, SAMBAY_CONFIG)
    assert sum(f.matrix_macs_per_token(k, cfg) for k in CUT) == 632_750_080
    assert f.attention_macs_per_token("full_attention", cfg, 8192) == 31_461_120
    assert f.attention_macs_per_token("sliding_attention", cfg, 8192) == \
        pytest.approx(3.81e6, rel=1e-3)
    assert f.keys_seen("sliding_attention", cfg, 8192) == pytest.approx(
        496.0, abs=0.05)
    assert f.train_macs_per_token(cfg, 8192) == pytest.approx(763.8e6, rel=1e-4)
    assert f.train_flops_per_token(cfg, 8192) * 8192 == pytest.approx(
        37.5e12, rel=2e-3)
    assert plain.num_params(cfg) == 697_094_272
    whole = dict(cfg, layer_types=None, published_layers=None,
                 vocab_size=200064)
    assert plain.num_params(whole) == 3_852_562_944
    per_kind = {k: plain.num_params(dict(cfg, layer_types=[k], vocab_size=0,
                                         published_layers=[0])) - 2 * 2560
                for k in CUT}
    assert per_kind == {"mamba": 119_895_040, "sliding_attention": 98_322_304,
                        "full_attention": 98_322_304, "gmu": 104_867_840,
                        "cross_attention": 91_766_144}


def test_sambay_configuration_states_the_cut_and_every_assumption():
    cfg = run.load_json(ROOT, SAMBAY_CONFIG)
    catalogued = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True, "mlp_bias": False,
        "lm_head_bias": False}
    assert {k: cfg[k] for k in catalogued} == catalogued
    assert cfg["reduced"] == ["layer_types", "n_layer", "vocab_size"]
    assert cfg["layer_types"] == CUT and cfg["n_layer"] == 6
    assert cfg["published_layers"] == [14, 15, 16, 17, 18, 19]
    assert cfg["layer_types"] == plain.layer_pattern(32)[14:20]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 200064
    assert cfg["num_hidden_layers"] == cfg["published"]["num_hidden_layers"]
    assert (cfg["mamba_expand"], cfg["mamba_d_state"], cfg["mamba_d_conv"],
            cfg["mamba_dt_rank"]) == (2, 16, 4, 160)
    assert (cfg["n_embd"], cfg["n_head"], cfg["n_positions"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["max_position_embeddings"])
    assert {"deployment", "departs", "reduced_why"} <= set(cfg)
    assert "7, 7, 6, 6, 6" in cfg["deployment"]
    # every item the issue marks as not in the catalogued config.json
    assert {"layer_pattern", "norms", "mamba", "memory",
            "differential_attention", "window", "cross_attention", "gmu",
            "head", "initializer_range", "mlp"} <= set(cfg["assumed"])
    assert cfg["train"]["recompute_blocks"] is True
    assert set(cfg["train"]["kept_values"]) >= {"flash_o", "flash_lse",
                                                "selscan_y", "selscan_states"}
    traffic = run.load_json(ROOT, "benchmarks/traffic/sft-8k.json")
    assert (traffic["driver"], traffic["seq_len"], traffic["batch"],
            traffic["steps_per_dispatch"]) == ("train_scan_sambay", 8192, 1, 2)


def _read_sambay(name, planes, window, config=HAND):
    return run.load_module("layer_metrics", name).read(
        trace_reduce.TraceReduction(planes) if planes is not None else None,
        {"config": dict(config)}, window, PEAKS)


def test_sambay_readers_on_a_written_out_trace():
    # one step of one row of 64 tokens: the capture shows each scan kernel
    # twice (one a Mamba layer: 5 s and 9 s), the flash forward three times
    # (4 s) and the two flash backward kernels three times (6 s and 8 s)
    sec = int(1e9)
    plane = [("%while.1 = (f32[]) while(...)", 0, 400 * sec)]
    at = [1]

    def events(name, n, seconds):
        for i in range(n):
            plane.append((f"%{name}.{i} = bf16[8] custom-call(...)",
                          at[0] * sec, seconds * sec))
            at[0] += seconds + 1
    events("selscan_fwd", 2, 5)
    events("selscan_bwd", 2, 9)
    events("flash_fwd", 3, 4)
    events("flash_dq", 3, 6)
    events("flash_dkv", 3, 8)
    window = {"batch": 1, "seq_len": 64, "steps": 1, "dispatches": 1}
    f = flops_sambay
    # a scan: 64 tokens of 16 channels and 4 states. Forward reads xs, B, C
    # (2 bytes), dt (4), writes y and one saved state
    one = f.selscan_fwd_cost(HAND, 1, 64)
    assert one["flops"] == 2 * 128 * 64
    assert one["bytes"] == 2 * 64 * 16 * 2 + 2 * 64 * 4 * 2 + 64 * 16 * 4 \
        + 16 * 4 * 4
    fwd = _read_sambay("selscan_fwd_roofline", [plane], window)
    assert fwd["value"] == pytest.approx(100 * 2 * one["flops"] / 1e3 / 10)
    assert (fwd["bound"], fwd["kernel_events"]) == ("compute", 2)
    # at these toy peaks; at the published widths on the chip's the scan is
    # memory-bound by the count (its work is the VPU's, which has no peak)
    real = f.selscan_fwd_cost(run.load_json(ROOT, SAMBAY_CONFIG), 1, 8192)
    assert flops.roofline_seconds(real, run.load_json(
        ROOT, "benchmarks", "peaks.json")["TPU v5 lite"])[1] == "memory"
    back = f.selscan_bwd_cost(HAND, 1, 64)
    assert back["flops"] == 2 * one["flops"]
    bwd = _read_sambay("selscan_bwd_roofline", [plane], window)
    assert bwd["value"] == pytest.approx(100 * 2 * max(
        back["flops"], back["bytes"]) / 1e3 / 18)
    # attention: the windowed layer and the full and the cross one, each at
    # the larger of its two roofline times
    layers = [f.attention_fwd_cost(k, HAND, 1, 64) for k in CUT[1::2]]
    assert layers[1] == layers[2] and layers[0]["flops"] < layers[1]["flops"]
    assert layers[1]["flops"] == 2 * 24 * 32.5 * 64
    least = sum(max(c["flops"], c["bytes"]) / 1e3 for c in layers)
    flash = _read_sambay("sambay_flash_fwd_roofline", [plane], window)
    assert flash["value"] == pytest.approx(100 * least / 12)
    assert flash["kernel_events"] == 3
    least = sum(max(c["flops"], c["bytes"]) / 1e3 for c in (
        f.attention_bwd_cost(k, HAND, 1, 64) for k in CUT[1::2]))
    flash = _read_sambay("sambay_flash_bwd_roofline", [plane], window)
    assert flash["value"] == pytest.approx(100 * least / 42)
    assert flash["kernel_events"] == 6
    mfu = _read_sambay("sambay_train_step_mfu", [plane], window)
    assert mfu["value"] == pytest.approx(
        100 * f.train_flops_per_token(HAND, 64) * 64 / (400 * 1e3))
    # nothing to read is None, never 0: no kernel, no capture, another family
    bare = [plane[:1]]
    for name in SAMBAY_READERS[1:]:
        assert _read_sambay(name, bare, window) is None
    others = ({"n_embd": 8, "n_layer": 2, "n_head": 2},
              {"hidden_size": 8, "layer_types": ["mamba", "attention"]})
    for name in SAMBAY_READERS:
        assert _read_sambay(name, None, window) is None
        for other in others:
            assert _read_sambay(name, [plane], window, other) is None


def test_new_metrics_are_declared_for_the_sambay_cell_only():
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in zip(SAMBAY_READERS, ("model step",) + ("kernels",) * 4):
        e = entries[name]
        assert e["workloads"] == [SAMBAY_CELL] and e["layer"] == layer
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "%", "higher", "device_trace", "train_tokens_per_s")
    for name, m in entries.items():
        if name not in SAMBAY_READERS:
            assert SAMBAY_CELL not in m.get("workloads", ())
    # every cell reports what the host adds to a dispatch
    assert "workloads" not in entries["train_dispatch_host_ms"]
    assert [m["name"] for m in run.metrics_of(BENCH, "per_layer", SAMBAY_CELL)] \
        == ["train_dispatch_host_ms", *SAMBAY_READERS]
    cell = {w["name"]: w for w in BENCH["workloads"]}[SAMBAY_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "phi-4-mini-flash-reasoning", "sft-8k", 1)
    assert not cell["traffic"].startswith("pretrain")  # PERF.md 7.2f


def _tiny_sambay_cell():
    return run.load_cell(BENCH, SAMBAY_CELL, rehearsal=True)


@pytest.mark.parametrize("fault", [
    {"precision": "fp8"}, {"precision": "fp8_forward"}, {"rows_used": 32},
    {"detach_forwarded": True}, {"no_window": True},
    {"no_subtraction": True}], ids=lambda f: "-".join(map(str, f)))
def test_sambay_control_or_fault_in_the_references_place_is_not_correct(fault):
    """The reference put in the program's place: computed in fp8, with the
    loss over half of the tokens, with the forwarded values detached, without
    the window, without the subtraction."""
    cell = _tiny_sambay_cell()
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


def test_calibrate_sambay_holds_controls_and_faults_to_the_limits(capsys):
    from benchmarks import calibrate_sambay

    argv = ["--workload", SAMBAY_CELL, "--seeds", "2", "--control-seeds", "1",
            "--rehearsal"]
    assert calibrate_sambay.main(argv) == 0
    last = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert last["wrong"] == [] and {
        "program", "control_fp8", "control_fp8_forward", "half_tokens",
        *plain.FAULTS} == set(last["summary"])


def test_a_program_without_its_window_comes_out_not_correct(capsys, monkeypatch):
    """The timed path broken underneath: the windowed layer sees every
    earlier key."""
    from deeplearning4j_tpu.nn.layers import hybrid

    full = hybrid.dispatch_attention
    monkeypatch.setattr(
        hybrid, "dispatch_attention",
        lambda q, k, v, causal, mask=None, mesh=None, window=None: full(
            q, k, v, causal=causal, mask=mask, mesh=mesh))
    rc = run.main(["--workload", SAMBAY_CELL, "--seed", "2147483659",
                   "--seconds", "1", "--rehearsal"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert any(c["value"] > c["limit"] for c in line["compared"].values())
