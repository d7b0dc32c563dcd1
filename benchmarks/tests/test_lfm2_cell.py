"""Tests of what the LFM2 mixture-of-experts configuration brought to the
benchmark, on the CPU at the rehearsal's tiny width: the operation count
against a hand count and the published counts, what the configuration states,
the three readers on a written-out trace, the expert bias's calibration, and
the controls and faults held to the rehearsal's limits.

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import correct, flops, flops_lfm2, run, trace_reduce  # noqa: E402
from benchmarks.reference import lfm2_moe_plain as plain  # noqa: E402

BENCH = run.load_json(ROOT, "BENCHMARK.json")
LFM2_CELL = "lfm2-24b-a2b.midtrain-8k"
LFM2_CONFIG = "benchmarks/configs/lfm2-24b-a2b.json"
CUT = ["conv", "full_attention", "conv", "conv", "conv"]
# widths small enough to count by hand: 2 heads of 4 over 1, 8 experts
# routed, 2 held, 2 picked a token
HAND = {"hidden_size": 8, "num_attention_heads": 2, "num_key_value_heads": 1,
        "intermediate_size": 16, "moe_intermediate_size": 4,
        "num_routed_experts": 8, "num_experts": 2, "num_experts_per_tok": 2,
        "num_dense_layers": 2, "vocab_size": 100, "layer_types": CUT,
        "published_layers": [1, 2, 3, 4, 5]}
PEAKS = {"bf16_flops_per_s": 1e3, "hbm_bytes_per_s": 1e3}
LFM2_READERS = ("moe_train_step_mfu", "gmm_fwd_roofline", "gmm_bwd_roofline")


def test_lfm2_flops_equal_a_hand_count_and_the_published_counts():
    f = flops_lfm2
    # mixers: conv 3 * 64 + 64 = 256, attention 2 * 64 + 2 * 8 * 4 = 192;
    # feed-forward: dense 3 * 8 * 16 = 384, routed 8 * 8 + 2 * 2 / 8 * 96 = 112
    assert f.routed_layers(HAND) == [False, True, True, True, True]
    assert [f.matrix_macs_per_token(k, r, HAND)
            for k, r in zip(CUT, f.routed_layers(HAND))] == [
        640, 304, 368, 368, 368]
    # 16 tokens: each query sees 8.5 keys; 2 heads of 4, scores and values
    assert f.attention_macs_per_token(HAND, 16) == 2 * 2 * 4 * 8.5
    assert f.train_macs_per_token(HAND, 16) == 2048 + 136 + 800
    # the grouped products: 16 tokens send 8 rows a layer to held experts
    fwd = f.gmm_fwd_cost(HAND, 16)
    assert fwd["flops"] == 4 * 2 * 8 * (8 * 8 + 4 * 8)
    assert fwd["bytes"] == 4 * 2 * (8 * (8 + 8 + 4 + 8) + 2 * 96)
    assert f.gmm_bwd_cost(HAND, 16)["flops"] == 2 * fwd["flops"]
    # the published widths give the counts of PERF.md section 4
    cfg = run.load_json(ROOT, LFM2_CONFIG)
    assert f.train_macs_per_token(cfg, 8192) == 202_901_504
    assert f.train_flops_per_token(cfg, 8192) * 16384 == pytest.approx(
        19.95e12, rel=1e-3)
    assert f.held_per_token(cfg) == 0.5
    assert plain.num_params(cfg) == 469_284_992
    per_kind = [plain.num_params(dict(cfg, layer_types=[k], vocab_size=0,
                                      published_layers=[i])) - 2048
                for k, i in zip(CUT, cfg["published_layers"])]
    assert per_kind == [89_139_200, 86_118_528] + [92_416_000] * 3


def test_lfm2_configuration_states_the_cut_and_every_assumption():
    cfg = run.load_json(ROOT, LFM2_CONFIG)
    catalogued = {
        "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
        "intermediate_size": 11776, "max_position_embeddings": 128000,
        "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
        "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
        "num_dense_layers": 2, "num_experts_per_tok": 4,
        "num_hidden_layers": 40, "num_key_value_heads": 8,
        "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
        "routed_scaling_factor": 1, "use_expert_bias": True}
    assert {k: cfg[k] for k in catalogued} == catalogued
    assert cfg["reduced"] == ["layer_types", "n_layer", "num_experts",
                              "vocab_size"]
    assert cfg["layer_types"] == CUT and cfg["n_layer"] == 5
    assert cfg["published_layers"] == [1, 2, 3, 4, 5]
    assert (cfg["num_experts"], cfg["num_routed_experts"],
            cfg["experts_held_first"]) == (8, 64, 0)
    assert cfg["published"]["num_experts"] == cfg["num_routed_experts"]
    assert cfg["vocab_size"] * 8 == cfg["published"]["vocab_size"] == 65536
    assert cfg["num_hidden_layers"] == cfg["published"]["num_hidden_layers"]
    assert (cfg["n_embd"], cfg["n_head"], cfg["n_positions"]) == (
        cfg["hidden_size"], cfg["num_attention_heads"],
        cfg["max_position_embeddings"])
    assert {"deployment", "departs", "reduced_why", "aliases"} <= set(cfg)
    assert "8 chips" in cfg["deployment"]
    assert {"head", "norms", "conv", "attention", "experts", "expert_bias",
            "initializer_range"} <= set(cfg["assumed"])
    assert cfg["train"]["recompute_blocks"] is True
    traffic = run.load_json(ROOT, "benchmarks/traffic/midtrain-8k.json")
    assert (traffic["driver"], traffic["seq_len"], traffic["batch"],
            traffic["steps_per_dispatch"]) == ("train_scan_lfm2", 8192, 2, 4)


def _read_lfm2(name, planes, window, config=HAND):
    return run.load_module("layer_metrics", name).read(
        trace_reduce.TraceReduction(planes) if planes is not None else None,
        {"config": dict(config)}, window, PEAKS)


def test_lfm2_readers_on_a_written_out_trace():
    # one step of one row of 16 tokens: the forward kernel three times
    # (2 s), the two backward kernels twice each (3 s and 4 s)
    sec = int(1e9)
    plane = [("%while.1 = (f32[]) while(...)", 0, 400 * sec)]
    at = [1]

    def events(name, n, seconds):
        for i in range(n):
            plane.append((f"%{name}.{i} = bf16[8] custom-call(...)",
                          at[0] * sec, seconds * sec))
            at[0] += seconds + 1
    events("gmm_fwd", 3, 2)
    events("gmm_dx", 2, 3)
    events("gmm_dw", 2, 4)
    window = {"batch": 1, "seq_len": 16, "steps": 1, "dispatches": 1}
    f = flops_lfm2
    fwd = _read_lfm2("gmm_fwd_roofline", [plane], window)
    least, bound = flops.roofline_seconds(f.gmm_fwd_cost(HAND, 16), PEAKS)
    assert fwd["value"] == pytest.approx(100 * least / 6)
    assert (fwd["bound"], fwd["kernel_events"]) == (bound, 3)
    bwd = _read_lfm2("gmm_bwd_roofline", [plane], window)
    least = flops.roofline_seconds(f.gmm_bwd_cost(HAND, 16), PEAKS)[0]
    assert bwd["value"] == pytest.approx(100 * least / 14)
    assert bwd["kernel_events"] == 4
    mfu = _read_lfm2("moe_train_step_mfu", [plane], window)
    assert mfu["value"] == pytest.approx(
        100 * f.train_flops_per_token(HAND, 16) * 16 / (400 * 1e3))
    # nothing to read is None, never 0: no kernel, no capture, another family
    for name in LFM2_READERS[1:]:
        assert _read_lfm2(name, [plane[:1]], window) is None
    others = ({"n_embd": 8, "n_layer": 2, "n_head": 2},
              {"hidden_size": 8, "layer_types": ["mamba", "attention"],
               "sliding_window": 4})
    for name in LFM2_READERS:
        assert _read_lfm2(name, None, window) is None
        for other in others:
            assert _read_lfm2(name, [plane], window, other) is None


def test_the_whole_steps_share_notes_what_the_step_holds():
    from deeplearning4j_tpu import monitor

    reg = monitor.MetricsRegistry()
    old = monitor.set_registry(reg)
    try:
        plane = [("%while.1 = (f32[]) while(...)", 0, int(4e9))]
        window = {"batch": 1, "seq_len": 16, "steps": 1, "dispatches": 1}
        assert set(_read_lfm2("moe_train_step_mfu", [plane], window)) == {
            "value"}  # the parent's program sets none of the gauges
        reg.gauge(monitor.MOE_EXPERTS_HELD_GAUGE).set(8)
        reg.gauge(monitor.MOE_LAYERS_GAUGE).set(4)
        reg.gauge(monitor.MOE_HELD_SHARE_GAUGE, stat="min").set(0.124)
        reg.gauge(monitor.MOE_HELD_SHARE_GAUGE, stat="max").set(0.126)
        got = _read_lfm2("moe_train_step_mfu", [plane], window)
        assert (got["experts_held"], got["moe_layers"], got["held_share_min"],
                got["held_share_max"]) == (8, 4, 0.124, 0.126)
    finally:
        monitor.set_registry(old)


def test_new_metrics_are_declared_for_the_lfm2_cell():
    """The three readers are declared for the LFM2 cell, which reports them;
    other metrics may list the cell too, and later metrics may follow."""
    entries = {m["name"]: m for m in BENCH["per_layer"]}
    for name, layer in zip(LFM2_READERS, ("model step", "kernels", "kernels")):
        e = entries[name]
        assert e["workloads"][0] == LFM2_CELL and e["layer"] == layer
        assert (e["unit"], e["better"], e["source"], e["moves"]) == (
            "%", "higher", "device_trace", "train_tokens_per_s")
    reported = [m["name"] for m in run.metrics_of(BENCH, "per_layer", LFM2_CELL)]
    assert {"train_dispatch_host_ms", *LFM2_READERS} <= set(reported)
    cell = {w["name"]: w for w in BENCH["workloads"]}[LFM2_CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "lfm2-24b-a2b", "midtrain-8k", 1)
    assert not cell["traffic"].startswith("pretrain")  # PERF.md 7.2f


def _tiny_lfm2_cell():
    return run.load_cell(BENCH, LFM2_CELL, rehearsal=True)


def test_the_expert_bias_balances_the_calibration_rows():
    """Every routed expert takes k / E of the calibration rows' assignments
    at the bias the rule settles on, in every expert layer."""
    import jax
    import jax.numpy as jnp

    cell = _tiny_lfm2_cell()
    cfg = cell["config"]
    weights = plain.init_on_device(cfg, 7)
    bias = plain.calibrate_bias(cfg, weights, 7, 64)
    assert bias.shape == (4, cfg["num_routed_experts"])
    tokens = plain.calibration_tokens(cfg, 7, 64)
    mean = tokens.size * cfg["num_experts_per_tok"] / cfg["num_routed_experts"]
    x = weights["embed"][jnp.asarray(tokens)]
    biases = iter(bias)
    for kind, routed, p in zip(*plain._layers(cfg), weights["layers"]):
        mix, ffn = plain._layer_parts(kind, cfg)
        x = jax.lax.map(lambda row: mix(row, p), x)
        b = None
        if routed:
            b = next(biases)
            chosen = plain._router_scores(x, p, cfg) + b
            kth = jax.lax.top_k(chosen, cfg["num_experts_per_tok"])[0][:, -1:]
            load = np.asarray(jnp.sum(chosen >= kth, axis=0))
            assert np.abs(load - mean).max() <= 0.05 * mean, load
        x = jax.lax.map(lambda row: ffn(row, p, b), x)


@pytest.mark.parametrize("fault", [
    {"precision": "fp8"}, {"precision": "fp8_forward"}, {"rows_used": 32},
    {"capacity": True}, {"no_conv_gate": True}],
    ids=lambda f: "-".join(map(str, f)))
def test_lfm2_control_or_fault_in_the_references_place_is_not_correct(fault):
    """The reference put in the program's place: computed in fp8, with the
    loss over half of the tokens, with the tokens past a capacity dropped,
    without the convolution's input gate. (The combine weights taken from
    the biased scores are left out: at the rehearsal's 16 rows an expert
    they move no leaf norm more than the program's own rounding does; at
    the cell's size a held expert's gradient by 1.2-1.4%, PERF.md
    section 2.)"""
    cell = _tiny_lfm2_cell()
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


def test_calibrate_lfm2_holds_controls_and_faults_to_the_limits(capsys):
    from benchmarks import calibrate_lfm2

    argv = ["--workload", LFM2_CELL, "--seeds", "2", "--control-seeds", "1",
            "--rehearsal"]
    rc = calibrate_lfm2.main(argv)
    lines = [json.loads(x) for x in capsys.readouterr().out.strip().splitlines()]
    wrong = lines[-1]["wrong"]
    assert rc == (1 if wrong else 0)
    # every program run correct, every control and fault not, but for the
    # combine weights from the biased scores, which the rehearsal's size
    # cannot tell from rounding (PERF.md section 2)
    assert all(w.startswith("biased_weights on seed") for w in wrong), wrong
    assert {"program", "control_fp8", "control_fp8_forward", "half_tokens",
            *plain.FAULTS} == set(lines[-1]["summary"])
    assert all(line["bias_unmoved"] and len(line["routing"]["held_share"]) == 4
               for line in lines[:-1])


def test_a_program_that_drops_tokens_past_a_capacity_comes_out_not_correct(
        capsys, monkeypatch):
    """The timed path broken underneath as a Switch layer is: a held
    expert's assignments past ``t k / E`` a row, in token order, dropped."""
    import math

    import jax.numpy as jnp

    from deeplearning4j_tpu.nn.layers import moe as moe_layer

    cell = _tiny_lfm2_cell()
    seq, experts_all = cell["traffic"]["seq_len"], \
        cell["config"]["num_routed_experts"]
    sort = moe_layer.sort_by_expert

    def capped(experts, first, count):
        k = experts.shape[1]
        room = math.ceil(seq * k / experts_all)
        picks = (experts - first)[..., None] == jnp.arange(count)
        rows = picks.reshape(-1, seq * k, count)
        over = rows & (jnp.cumsum(rows, axis=1) > room)
        dropped = jnp.any(over, axis=-1).reshape(experts.shape)
        return sort(jnp.where(dropped, -1, experts), first, count)

    monkeypatch.setattr(moe_layer, "sort_by_expert", capped)
    rc = run.main(["--workload", LFM2_CELL, "--seed", "2147483659",
                   "--seconds", "1", "--rehearsal"])
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is False
    assert line["compared"]["grad_norm_gap"]["value"] > \
        line["compared"]["grad_norm_gap"]["limit"]


def test_leaf_norms_count_each_held_expert_apart():
    """A held expert's matrices have a norm each; the rest one a leaf."""
    import jax
    import jax.numpy as jnp

    cfg = _tiny_lfm2_cell()["config"]
    tree = plain.init_weights(cfg, 5)
    norms = plain.leaf_norms(tree)
    held = cfg["num_experts"]
    for i, layer in enumerate(tree["layers"]):
        for name, leaf in layer.items():
            got = np.asarray(norms[f"layers.{i}.{name}"])
            if name.startswith("experts_"):
                assert got.shape == (held,)
                np.testing.assert_allclose(
                    got, [np.linalg.norm(np.asarray(e)) for e in leaf],
                    rtol=1e-5)
            else:
                assert got.shape == ()
                np.testing.assert_allclose(got, jnp.linalg.norm(leaf.ravel()),
                                           rtol=1e-5)
    names, _ = correct.norm_gaps(jax.device_get(norms), jax.device_get(norms))
    assert f"layers.1.experts_down[{held - 1}]" in names
