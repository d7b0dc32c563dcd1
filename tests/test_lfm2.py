"""The LFM2 mixture-of-experts family on the CPU at tiny sizes: the grouped
matmul kernels (Pallas interpreter) against ``jax.numpy``; top-k routing with
a selection-only bias; the expert layer that holds a share of the experts
against the dense plain reference (``benchmarks/reference/lfm2_moe_plain.py``),
share by share and under a skewed router; the gated short convolution; the
whole tiny model through ``fit_scan`` against the reference (loss, every
leaf's gradient, two Adam steps); the builder, the scopes, the counter and the
gauges, and the serving entry points."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import train_scan_lfm2 as driver
from benchmarks.reference import lfm2_moe_plain as plain
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo.lfm2_moe import lfm2_moe
from deeplearning4j_tpu.nn.conf import MultiLayerConfiguration
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers import moe as moe_layer
from deeplearning4j_tpu.nn.layers.hybrid import TrainingOnlyError, causal_conv
from deeplearning4j_tpu.nn.multilayer import LFM2_STEP_SCOPES
from deeplearning4j_tpu.ops import grouped_matmul as gm
from deeplearning4j_tpu.ops import moe as moe_ops
from deeplearning4j_tpu.ops.moe import topk_routing

CUT = ["conv", "full_attention", "conv", "conv", "conv"]
#: d 64, 8 experts routed, 2 picked a token, 2 held, a vocabulary of 128
TINY = {
    "vocab_size": 128, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_experts": 2, "num_routed_experts": 8,
    "experts_held_first": 2, "num_experts_per_tok": 2, "num_dense_layers": 2,
    "conv_L_cache": 3, "norm_eps": 1e-5, "norm_topk_prob": True,
    "routed_scaling_factor": 1, "use_expert_bias": True, "conv_bias": False,
    "rope_parameters": {"rope_theta": 10000.0, "rope_type": "default"},
    "layer_types": CUT, "published_layers": [1, 2, 3, 4, 5], "n_layer": 5,
    "initializer_range": 0.02}
PUBLISHED = dict(TINY, vocab_size=8192, hidden_size=2048,
                 intermediate_size=11776, moe_intermediate_size=1536,
                 num_attention_heads=32, num_key_value_heads=8, num_experts=8,
                 num_routed_experts=64, experts_held_first=0,
                 num_experts_per_tok=4,
                 rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
SEQ = 32


def _net(compute_dtype="float32", cfg=TINY, **kw):
    return lfm2_moe(cfg, compute_dtype=compute_dtype, seed=1, **kw)


def _with_reference_weights(net, cfg=TINY, seed=3):
    names = [i.name for i in net.impls]
    weights, bias = plain.init_params(cfg, seed, SEQ)
    net.init()
    net.params = driver.to_program(weights, names)
    net.states = driver.states_with_bias(net, bias)
    return weights, bias, names


def _tokens(cfg=TINY, seed=5, steps=1, rows=2):
    return plain.make_tokens(cfg, seed, steps, rows, SEQ)


def _rel(a, b):
    return float(jnp.linalg.norm(jnp.asarray(a, jnp.float32) - b)
                 / (jnp.linalg.norm(b) + 1e-30))


@pytest.fixture
def gmm_path(monkeypatch, request):
    """The expert layer's grouped products by the way the test names: the
    kernels (under the interpreter, at tiles of 16 rows) or ragged_dot."""
    if request.param == "gmm":
        monkeypatch.setattr(gm, "TILE_ROWS", 16)
    monkeypatch.setattr(moe_layer, "moe_path", lambda *a: request.param)
    return request.param


# ------------------------------------------------------------- the kernels

#: group sizes of a 128-row buffer: uneven, empty, one-row and tile-crossing
#: groups, an empty first and last group, everything in one, nothing at all,
#: and a full buffer (no row past the last group)
SIZES = [(20, 0, 1, 37), (0, 40, 0, 3), (0, 0, 90, 0), (0, 0, 0, 0),
         (64, 16, 16, 32)]


@pytest.mark.parametrize("sizes", SIZES, ids=str)
def test_grouped_kernels_equal_jnp(monkeypatch, sizes):
    monkeypatch.setattr(gm, "TILE_ROWS", 16)
    rng = np.random.default_rng(0)
    rows, k, n = 128, 64, 96
    group_sizes = jnp.asarray(sizes, jnp.int32)
    lhs = jnp.asarray(rng.normal(size=(rows, k)), jnp.bfloat16)
    rhs = jnp.asarray(rng.normal(size=(len(sizes), k, n)), jnp.bfloat16)
    dy = jnp.asarray(rng.normal(size=(rows, n)), jnp.bfloat16)
    used = sum(sizes)
    f32 = lambda x: jnp.asarray(x, jnp.float32)
    bounds = np.concatenate([[0], np.cumsum(sizes)])
    group = lambda r: int(np.searchsorted(bounds, r, side="right") - 1)
    by_row = lambda f, width: np.asarray(
        [f(r) for r in range(used)], np.float32).reshape(used, width)
    want = by_row(lambda r: f32(lhs[r]) @ f32(rhs[group(r)]), n)
    want_dx = by_row(lambda r: f32(dy[r]) @ f32(rhs[group(r)]).T, k)
    want_dw = np.stack([f32(lhs[a:b]).T @ f32(dy[a:b])
                        for a, b in zip(bounds[:-1], bounds[1:])])
    out = gm.gmm(lhs, rhs, group_sizes, interpret=True)
    dx = gm.gmm(dy, rhs, group_sizes, transpose_rhs=True, interpret=True)
    dw = gm.tgmm(lhs, dy, group_sizes, interpret=True)
    # bfloat16 outputs of float32 sums: a rounding of the result each
    np.testing.assert_allclose(f32(out[:used]), want, rtol=1e-2, atol=0.1)
    np.testing.assert_allclose(f32(dx[:used]), want_dx, rtol=1e-2, atol=0.1)
    np.testing.assert_allclose(f32(dw), want_dw, rtol=1e-2, atol=0.2)
    # the kernels' custom gradient is ragged_dot's, where rows are in groups
    loss = lambda path: lambda a, b: jnp.sum(jnp.where(
        jnp.arange(rows)[:, None] < used,
        f32(gm.grouped_matmul(a, b, group_sizes, path)) * f32(dy), 0.0))
    mine = jax.grad(loss("gmm"), argnums=(0, 1))(lhs, rhs)
    theirs = jax.grad(loss("xla"), argnums=(0, 1))(lhs, rhs)
    np.testing.assert_allclose(f32(mine[0][:used]), f32(theirs[0][:used]),
                               rtol=1e-2, atol=0.1)
    np.testing.assert_allclose(f32(mine[1]), f32(theirs[1]), rtol=1e-2,
                               atol=0.2)


def test_visits_cover_each_groups_tiles_once_in_order():
    offsets, group, tile, count = gm.visits(
        jnp.asarray([20, 0, 1, 37], jnp.int32), 128, 16, empty=False)
    assert list(offsets) == [0, 20, 20, 21, 58] and int(count) == 6
    # group 0 rows 0-19: tiles 0, 1; group 2 row 20: tile 1; group 3 rows
    # 21-57: tiles 1, 2, 3; the rest repeat the last visit
    assert list(group[:6]) == [0, 0, 2, 3, 3, 3]
    assert list(tile[:6]) == [0, 1, 1, 1, 2, 3]
    assert set(np.asarray(tile[6:])) == {3}
    _, group, tile, count = gm.visits(jnp.asarray([20, 0, 1, 37], jnp.int32),
                                      128, 16, empty=True)
    assert int(count) == 7 and list(group[:7]) == [0, 0, 1, 2, 3, 3, 3]
    assert gm.moe_path(65536, 2048, 1536) == "xla"  # the CPU: ragged_dot


# -------------------------------------------------------------- routing

def test_the_bias_selects_and_does_not_weigh():
    logits = jnp.asarray([[2.0, 1.0, 0.0, -1.0]], jnp.float32)
    s = jax.nn.sigmoid(logits[0])
    w, e = topk_routing(logits, 2, norm_topk_prob=False)
    assert list(e[0]) == [0, 1]
    np.testing.assert_allclose(w[0], s[:2], rtol=1e-6)
    # a bias that lifts expert 3 over expert 1 changes the pick, and the
    # weight is expert 3's score, not score + bias
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.5])
    w, e = topk_routing(logits, 2, bias, norm_topk_prob=False)
    assert sorted(np.asarray(e[0]).tolist()) == [0, 3]
    got = dict(zip(np.asarray(e[0]).tolist(), np.asarray(w[0]).tolist()))
    assert got[3] == pytest.approx(float(s[3])) and got[0] == pytest.approx(float(s[0]))
    # norm_topk_prob: the picks' weights sum to 1 less the 1e-6, then scaled
    w, e = topk_routing(logits, 2, bias, scale=2.5)
    want = 2.5 * jnp.asarray([s[0], s[3]]) / (s[0] + s[3] + 1e-6)
    np.testing.assert_allclose(sorted(np.asarray(w[0])), sorted(want), rtol=1e-6)
    # no gradient reaches the bias
    g = jax.grad(lambda b: jnp.sum(topk_routing(logits, 2, b)[0]))(bias)
    assert float(jnp.abs(g).sum()) == 0.0


@pytest.mark.parametrize("first,count", [(0, 8), (2, 3), (7, 1)])
def test_dispatch_and_combine_equal_autodiff_of_plain_gathers(first, count):
    """The sorted rows' gather and the weighted sum back, with their own
    gradient rules, against ``jax.grad`` of the same arithmetic written with
    plain indexing: every expert held, a middle range, the last one alone."""
    rng = np.random.default_rng(first)
    n, k, d = 12, 2, 8
    experts = jnp.asarray(np.argsort(rng.random((n, 8)), axis=1)[:, :k],
                          jnp.int32)
    order, _, held, position = moe_ops.sort_by_expert(experts, first, count)
    x = jnp.asarray(rng.normal(size=(n, d)), jnp.float32)
    w = jnp.asarray(rng.random((n, k)), jnp.float32)
    c = jnp.asarray(rng.normal(size=(n * k, d)), jnp.float32)

    def program(x, w):
        rows = moe_ops.dispatch(x, order, position, held, k)
        return moe_ops.combine(jnp.tanh(rows * c), w, order, position, held, k)

    def plain_form(x, w):
        z = jnp.tanh(x[order // k] * c)[position]             # [n, k, d]
        return jnp.sum(jnp.where(held[..., None], w[..., None] * z, 0.0), 1)

    loss = lambda f: lambda x, w: jnp.sum(jnp.sin(f(x, w)))
    np.testing.assert_allclose(program(x, w), plain_form(x, w), rtol=1e-6,
                               atol=1e-7)
    got = jax.grad(loss(program), argnums=(0, 1))(x, w)
    want = jax.grad(loss(plain_form), argnums=(0, 1))(x, w)
    for g, h in zip(got, want):
        np.testing.assert_allclose(g, h, rtol=1e-5, atol=1e-6)


def _expert_conf(first=0, count=0, routed=8, k=2):
    return L.ShortConvBlock(n_in=64, n_out=64, num_experts=routed,
                            experts_per_token=k, experts_held=(first, count),
                            expert_hidden=32, expert_bias=True)


def _expert_leaves(seed=0, routed=8):
    cfg = dict(TINY, num_experts=routed, experts_held_first=0)
    leaves = plain.init_weights(cfg, seed)["layers"][2]
    keep = ("rms2_g", "W_router", "experts_gate_up", "experts_down")
    return {k: leaves[k] for k in keep}, cfg


def _reference_ffn(leaves, h, bias, cfg):
    with jax.default_matmul_precision("highest"):
        mm = lambda a, b: jnp.matmul(a, b, precision="highest")
        return jnp.stack([plain._ffn(row, leaves, bias, cfg, mm, "float32", ())
                          for row in h])


@pytest.mark.parametrize("gmm_path", ["gmm", "xla"], indirect=True)
def test_the_shares_of_all_chips_add_up_to_the_uncut_layer(gmm_path):
    """Four chips of two experts each: what each computes for its own
    experts, summed, is the whole layer; the uncut reference agrees."""
    leaves, cfg = _expert_leaves()
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(2, SEQ, 64)), jnp.float32)
    bias = jnp.asarray(rng.normal(size=(8,)) * 0.1, jnp.float32)
    state = {"expert_bias": bias}
    parts = []
    for chip in range(4):
        share = dict(leaves, experts_gate_up=leaves["experts_gate_up"][2 * chip:2 * chip + 2],
                     experts_down=leaves["experts_down"][2 * chip:2 * chip + 2])
        parts.append(moe_layer.routed_experts(share, h, state,
                                              _expert_conf(2 * chip, 2)))
    whole = moe_layer.routed_experts(leaves, h, state, _expert_conf())
    want = _reference_ffn(leaves, h, bias, cfg)
    assert _rel(sum(parts), want) < 1e-5
    assert _rel(whole, want) < 1e-5
    # each share is the reference's with that chip's experts alone
    alone = dict(cfg, num_experts=2, experts_held_first=2)
    share = dict(leaves, experts_gate_up=leaves["experts_gate_up"][2:4],
                 experts_down=leaves["experts_down"][2:4])
    assert _rel(parts[1], _reference_ffn(share, h, bias, alone)) < 1e-5


@pytest.mark.parametrize("gmm_path", ["gmm", "xla"], indirect=True)
def test_a_skewed_router_drops_no_token(gmm_path):
    """A bias that sends every token to the two held experts fills the
    buffer's worst case: every assignment is computed, forward and back."""
    leaves, cfg = _expert_leaves(seed=2)
    cfg = dict(cfg, num_experts=2, experts_held_first=0)
    leaves = dict(leaves, experts_gate_up=leaves["experts_gate_up"][:2],
                  experts_down=leaves["experts_down"][:2])
    h = jnp.asarray(np.random.default_rng(3).normal(size=(2, SEQ, 64)),
                    jnp.float32)
    bias = jnp.asarray([9.0, 9.0] + [0.0] * 6, jnp.float32)
    conf = _expert_conf(0, 2)
    out = moe_layer.routed_experts(leaves, h, {"expert_bias": bias}, conf)
    assert _rel(out, _reference_ffn(leaves, h, bias, cfg)) < 1e-5
    g = jax.grad(lambda p: jnp.sum(jnp.sin(moe_layer.routed_experts(
        p, h, {"expert_bias": bias}, conf))))(leaves)
    with jax.default_matmul_precision("highest"):
        want = jax.grad(lambda p: jnp.sum(jnp.sin(_reference_ffn(
            p, h, bias, cfg))))(leaves)
    for name in ("W_router", "experts_gate_up", "experts_down"):
        assert _rel(g[name], want[name]) < 1e-4, name


# --------------------------------------------------------- the short conv

def test_short_conv_is_causal_and_the_direct_formula():
    rng = np.random.default_rng(4)
    u = jnp.asarray(rng.normal(size=(2, 10, 8)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(3, 8)), jnp.float32)
    out = causal_conv(u, w)
    # v_t = w_0 u_{t-2} + w_1 u_{t-1} + w_2 u_t, zeros before the row
    pad = np.concatenate([np.zeros((2, 2, 8)), np.asarray(u)], axis=1)
    want = sum(pad[:, j:j + 10] * np.asarray(w[j]) for j in range(3))
    np.testing.assert_allclose(out, want, rtol=1e-6, atol=1e-6)
    later = u.at[:, 6:].set(jnp.asarray(rng.normal(size=(2, 4, 8))))
    np.testing.assert_array_equal(causal_conv(later, w)[:, :6], out[:, :6])
    # the block: (C * conv(B * x)) W_out
    net = _net().init()
    impl, p = net.impls[1], net.params["layer1"]
    u = jnp.asarray(rng.normal(size=(2, 10, 64)), jnp.float32)
    b_, c_, x_ = jnp.split(u @ p["W_in"], 3, axis=-1)
    np.testing.assert_allclose(
        impl._mixer(p, u, None),
        (c_ * causal_conv(b_ * x_, p["conv_w"])) @ p["W_out"], rtol=1e-5)


# -------------------------------------------------- the model, end to end

@pytest.mark.parametrize("gmm_path, recompute", [
    ("xla", False), ("xla", True), ("gmm", True)],
    indirect=["gmm_path"])
def test_fit_scan_equals_the_reference_loss_gradients_and_two_adam_steps(
        gmm_path, recompute):
    net = _net(recompute_blocks=recompute,
               kept_values=("mlp_gate_up_product", "expert_gate_up_product",
                            "flash_o", "flash_lse"))
    weights, bias, names = _with_reference_weights(net)
    tok = _tokens(steps=2)
    ds = DataSet(tok[0, :, :-1].astype(np.float32),
                 tok[0, :, 1:].astype(np.float32))
    grads, score = net.gradient_and_score(ds)
    grad, adam = plain._jitted()
    with jax.default_matmul_precision("highest"):
        loss, want = grad(weights, bias, jnp.asarray(tok[0, :, :-1]),
                          jnp.asarray(tok[0, :, 1:]), cfg_key=plain.cfg_key(TINY),
                          precision="float32", rows_used=0, faults=())
    assert abs(score - float(loss)) < 1e-5 * float(loss)
    want = driver.to_program(want, names)
    for name in names[:-1]:
        for leaf, g in grads[name].items():
            assert _rel(g, want[name][leaf]) < 1e-3, (name, leaf)
    # two Adam steps through fit_scan, the state's bias untouched
    flat = tok.reshape(4, SEQ + 1)
    staged = net.stage_scan(DataSet(flat[:, :-1].astype(np.float32),
                                    flat[:, 1:].astype(np.float32)), 2)
    losses = net.fit_scan(None, 2, epochs=1, staged=staged)
    params = weights
    m, v = (jax.tree.map(jnp.zeros_like, params),) * 2
    ref_losses = []
    for s in range(2):
        with jax.default_matmul_precision("highest"):
            loss, g = grad(params, bias, jnp.asarray(tok[s, :, :-1]),
                           jnp.asarray(tok[s, :, 1:]),
                           cfg_key=plain.cfg_key(TINY), precision="float32",
                           rows_used=0, faults=())
        ref_losses.append(float(loss))
        params, m, v = adam(params, m, v, g, jnp.asarray(s, jnp.int32),
                            lr=1e-4, b1=0.9, b2=0.999, eps=1e-8)
    np.testing.assert_allclose(np.asarray(losses), ref_losses, rtol=1e-5)
    moved = driver.to_program(jax.tree.map(jnp.subtract, params, weights),
                              names)
    start = driver.to_program(weights, names)
    for name in names[:-1]:
        for leaf, p in net.params[name].items():
            assert _rel(p - start[name][leaf], moved[name][leaf]) < 2e-2, (
                name, leaf)
    kept = [s["expert_bias"] for s in net.states.values() if s]
    np.testing.assert_array_equal(np.stack(kept), np.asarray(bias))
    assert not any("expert_bias" in leaves for leaves in net.params.values())


def test_the_bfloat16_program_trains_and_stays_near_the_reference():
    net = _net("bfloat16")
    weights, bias, names = _with_reference_weights(net)
    tok = _tokens()
    ds = DataSet(tok[0, :, :-1].astype(np.float32),
                 tok[0, :, 1:].astype(np.float32))
    score = net.gradient_and_score(ds)[1]
    with jax.default_matmul_precision("highest"):
        loss = plain.loss_sum(weights, bias, jnp.asarray(tok[0, :, :-1]),
                              jnp.asarray(tok[0, :, 1:]), TINY) / (2 * SEQ)
    assert abs(score - float(loss)) < 2e-3 * float(loss)
    scores = net.fit_scan(None, 2, epochs=3, staged=net.stage_scan(ds, 2))
    assert np.isfinite(scores).all()


# ------------------------------------------------------------ the builder

def test_the_published_widths_count_the_stated_parameters():
    cut = dict(PUBLISHED)
    net = _net(cfg=cut)
    key = jax.random.PRNGKey(0)
    count = sum(int(np.prod(v.shape)) for impl in net.impls
                for v in jax.eval_shape(impl.init_params, key).values())
    assert count == plain.num_params(cut) == 469_284_992
    confs = net.conf.layers
    assert [type(c).__name__ for c in confs[1:6]] == [
        "ShortConvBlock", "GroupedQueryBlock", "ShortConvBlock",
        "ShortConvBlock", "ShortConvBlock"]
    assert confs[1].num_experts == 0 and confs[1].ffn_hidden == 11776
    assert all(c.num_experts == 64 and c.experts_held == (0, 8)
               and c.experts_per_token == 4 and c.expert_bias
               for c in confs[2:6])
    assert confs[2].qk_norm and confs[2].rope_theta == 1000000


@pytest.mark.parametrize("change, match", [
    ({"layer_types": ["conv", "sliding_attention"], "published_layers": [0, 1]},
     "layer_types holds"),
    ({"conv_bias": True}, "conv_bias"),
    ({"tie_word_embeddings": False}, "tied"),
    ({"experts_held_first": 7}, "experts_held")])
def test_what_the_builder_does_not_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        _net(cfg=dict(TINY, **change)).init()


def test_configuration_round_trips_through_json():
    conf = _net().conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.layers == conf.layers
    assert again.layers[3].experts_held == (2, 2)
    assert again.layers[2].qk_norm


def test_scopes_counter_and_gauges_of_the_step():
    reg = monitor.get_registry()
    value = lambda name, **labels: reg.get(name, **labels).value
    net = _net("bfloat16", kept_values=("flash_o", "flash_lse",
                                        "expert_gate_up_product"))
    net.init()
    tok = _tokens()
    ds = DataSet(tok[0, :, :-1].astype(np.float32),
                 tok[0, :, 1:].astype(np.float32))
    staged = net.stage_scan(ds, 2)
    ticks = lambda: reg.counter(monitor.MOE_PATH_COUNTER, path="xla").value
    before = ticks()
    text = net._make_scan_fit(1).lower(
        net.params, net.opt_state, net.states, *staged,
        net._train_rng()).as_text(debug_info=True)
    # one tick a traced expert layer: the four of the step
    assert ticks() == before + 4
    assert value(monitor.MOE_EXPERTS_HELD_GAUGE) == 2
    assert value(monitor.MOE_LAYERS_GAUGE) == 4
    for scope in LFM2_STEP_SCOPES:
        if scope in ("grad_norm", "fold_heads", "unfold_heads"):
            continue  # no normalization here; attention at 32 takes XLA's form
        assert scope in text, scope
    from deeplearning4j_tpu.models.zoo.transformer import gpt
    gpt(vocab_size=128, d_model=32, n_layers=1, num_heads=2, max_len=32,
        seed=3).init().fit_scan(None, 2, staged=staged)
    assert value(monitor.MOE_LAYERS_GAUGE) == 0
    assert value(monitor.MOE_EXPERTS_HELD_GAUGE) == 0


@pytest.mark.parametrize("entry", ["init_cache", "prefill", "prefill_paged",
                                   "decode_step"])
def test_serving_entry_points_raise_the_typed_error(entry):
    net = _net().init()
    for impl in net.impls[1:6]:
        with pytest.raises(TrainingOnlyError, match="only be trained"):
            getattr(impl, entry)()
