"""The looped language model on the CPU at tiny sizes: rotary positions against
an explicit complex rotation; the repeated span of the container (a shared
leaf's gradient, the span run once, a net without one); the exit head against
a hand count; the sandwich block and the whole tiny model against the plain
reference (``benchmarks/reference/ouro_looped_plain.py``); what a recomputed
block keeps; the keys the configuration classes gained."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import train_scan_looped as driver
from benchmarks.reference import ouro_looped_plain as plain
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo.looped_lm import looped_lm
from deeplearning4j_tpu.nn.conf import (MultiLayerConfiguration,
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers import hybrid
from deeplearning4j_tpu.nn.layers.hybrid import TrainingOnlyError
from deeplearning4j_tpu.nn.multilayer import (LOOPED_STEP_SCOPES,
                                              MultiLayerNetwork)
from deeplearning4j_tpu.ops.attention import rotary

TINY = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "n_layer": 2, "layer_types": ["full_attention"] * 2,
    "num_hidden_layers": 48, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "total_ut_steps": 3,
    "rms_norm_eps": 1e-6, "rope_theta": 1e6, "rope_scaling": None,
    "sliding_window": None, "use_sliding_window": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "initializer_range": 0.02, "exit_entropy_weight": 0.05}


def _net(compute_dtype="float32", seed=1, cfg=TINY, **kw):
    return looped_lm(cfg, compute_dtype=compute_dtype, seed=seed, **kw)


def _with_reference_weights(net, cfg=TINY, seed=1):
    """The net with the reference's seeded weights in its own layout."""
    names = [i.name for i in net.impls]
    ref = plain.init_params(cfg, seed)
    net.init()
    net.params = driver.to_program(ref, names)
    return ref, names


def _batch(seed=5, rows=2, t=64):
    tok = plain.make_tokens(TINY, seed, 1, rows, t)[0]
    return tok, DataSet(tok[:, :-1].astype(np.float32),
                        tok[:, 1:].astype(np.float32))


# ------------------------------------------------------------------ rotary

def test_rotary_is_a_complex_rotation_of_the_paired_lanes():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3, 16), jnp.float32)
    theta, half = 1e6, 8
    z = np.asarray(x[..., :half]) + 1j * np.asarray(x[..., half:])
    freq = theta ** (-np.arange(half) / half)
    turned = z * np.exp(1j * np.arange(9)[None, :, None, None] * freq)
    want = np.concatenate([turned.real, turned.imag], axis=-1)
    np.testing.assert_allclose(rotary(x, theta), want, rtol=1e-5, atol=1e-6)
    # position 0 is left alone, and lengths are kept
    np.testing.assert_allclose(rotary(x, theta)[:, 0], x[:, 0], rtol=1e-6)
    np.testing.assert_allclose(jnp.linalg.norm(rotary(x, theta), axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    assert rotary(x.astype(jnp.bfloat16), theta).dtype == jnp.bfloat16
    with pytest.raises(ValueError, match="even"):
        rotary(x[..., :15], theta)


def test_rotated_scores_depend_on_the_distance_alone():
    q = jax.random.normal(jax.random.PRNGKey(1), (16,), jnp.float32)
    k = jax.random.normal(jax.random.PRNGKey(2), (16,), jnp.float32)
    rows = lambda v: jnp.broadcast_to(v, (1, 12, 1, 16))
    rq, rk = rotary(rows(q), 100.0)[0, :, 0], rotary(rows(k), 100.0)[0, :, 0]
    scores = np.asarray(rq @ rk.T)  # [i, j]: q at i against k at j
    for gap in range(-5, 6):
        diagonal = np.diagonal(scores, offset=-gap)
        np.testing.assert_allclose(diagonal, diagonal[0], rtol=1e-4, atol=1e-5)
    assert np.ptp(scores) > 0.1  # and they do depend on it


def _rotary_as_written(x, theta):
    """``rotary`` as it stood on [b, t, h, d]: halves sliced out of the last
    axis, a [t, 1, d/2] table over the heads, a concatenate."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    freq = jnp.exp(jnp.arange(half, dtype=jnp.float32)
                   * (-2.0 * math.log(theta) / d))
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    xf = x.astype(jnp.float32)
    a, b = xf[..., :half], xf[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _assert_same_rotation(got, want, rtol):
    """Equal to ``rtol`` in float32 (the two forms contract their
    multiply-adds differently: a last bit of the larger product), to one
    ulp in bfloat16 (a last bit of float32 can cross a rounding boundary)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    got, want = (np.asarray(z, np.float32) for z in (got, want))
    if rtol is None:
        gap = np.abs(got - want)
        ulp = 2.0 ** (np.floor(np.log2(np.maximum(np.abs(want), 1e-30))) - 7)
        assert (gap <= ulp + 1e-6).all(), gap.max()
        assert (gap > 0).mean() < 0.01
    else:
        np.testing.assert_allclose(got, want, rtol=rtol, atol=rtol)


@pytest.mark.parametrize("heads,kv_heads", [(4, 4), (4, 2)])
@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rotary_on_the_flat_layout_is_the_written_out_rotation(
        dtype, d, heads, kv_heads):
    """q and k of a grouped-query block at widths the Pallas pass takes
    (heads in whole 128-lane column blocks): the forward against the
    formula on [b, t, h, d], the gradient rule (the inverse rotation of the
    incoming gradient) against autodiff of that formula."""
    theta, t = 1e6, 256
    for n, seed in ((heads, 3), (kv_heads, 4)):
        kx, kg = jax.random.split(jax.random.PRNGKey(seed))
        x = jax.random.normal(kx, (2, t, n, d), jnp.float32).astype(dtype)
        g = jax.random.normal(kg, (2, t, n, d), jnp.float32).astype(dtype)
        got, pull = jax.vjp(lambda x: rotary(x, theta), x)
        want, pull_written = jax.vjp(lambda x: _rotary_as_written(x, theta), x)
        loose = dtype == jnp.bfloat16
        _assert_same_rotation(got, want, None if loose else 1e-6)
        _assert_same_rotation(pull(g)[0], pull_written(g)[0],
                              None if loose else 1e-5)


def test_rotary_keeps_float32_angles_at_position_65535():
    """A bfloat16 angle at position 65k is off by whole turns; the float32
    one is off by 65,535 roundings of 6e-8, a few thousandths of a turn."""
    theta, t, d = 1e6, 65536, 128
    x = jax.random.normal(jax.random.PRNGKey(5), (1, t, 1, d), jnp.float32)
    got = rotary(x, theta)
    _assert_same_rotation(got, _rotary_as_written(x, theta), 1e-6)
    z = np.asarray(x[..., :d // 2], np.float64) \
        + 1j * np.asarray(x[..., d // 2:], np.float64)
    freq = theta ** (-np.arange(d // 2) / (d // 2))
    turned = z * np.exp(1j * np.arange(t)[None, :, None, None] * freq)
    exact = np.concatenate([turned.real, turned.imag], axis=-1)
    np.testing.assert_allclose(got[:, -256:], exact[:, -256:], atol=0.05)
    assert np.abs(exact[:, -256:] - np.asarray(x[:, -256:])).max() > 1.0


# --------------------------------------------------------- the exit head

def test_exit_probabilities_sum_to_one_and_the_loss_is_a_hand_count():
    """Two tokens, three passes, a vocabulary of four."""
    conf = L.ExitGateOutputLayer(n_in=2, n_out=4, activation="softmax",
                                 has_bias=False, entropy_weight=0.05)
    head = MultiLayerNetwork(
        NeuralNetConfiguration.builder().list()
        .layer(L.DenseLayer(n_in=2, n_out=2)).layer(conf).build()).out
    rng = np.random.default_rng(3)
    W = rng.normal(size=(2, 4))
    w_gate, b_gate = rng.normal(size=(2, 1)), np.asarray([0.3])
    hs = [rng.normal(size=(1, 2, 2)) for _ in range(3)]
    labels = np.asarray([[2, 0]])
    # by hand, in float64
    ce, lam = [], []
    for h in hs:
        z = h[0] @ W
        ce.append(np.log(np.exp(z).sum(-1)) - z[np.arange(2), labels[0]])
        lam.append(1 / (1 + np.exp(-(h[0] @ w_gate)[:, 0] - b_gate[0])))
    p = np.stack([lam[0], lam[1] * (1 - lam[0]), (1 - lam[0]) * (1 - lam[1])])
    np.testing.assert_allclose(p.sum(0), 1.0, rtol=1e-12)
    want = np.mean((p * np.stack(ce)).sum(0) + 0.05 * (p * np.log(p)).sum(0))
    f32 = lambda a: jnp.asarray(a, jnp.float32)
    params = {"W": f32(W), "w_gate": f32(w_gate), "b_gate": f32(b_gate)}
    got = head.score(params, [f32(h) for h in hs], f32(labels), {}, True)
    assert float(got) == pytest.approx(want, rel=1e-5)
    # the reference's distribution is the same one
    gates = jnp.stack([f32(np.log(l / (1 - l))) for l in lam[:2]])
    np.testing.assert_allclose(plain.exit_distribution(gates), p, rtol=1e-5)
    # one pass is the plain softmax head: p = 1, no entropy
    one = head.score(params, f32(hs[0]), f32(labels), {}, True)
    assert float(one) == pytest.approx(float(np.mean(ce[0])), rel=1e-5)
    # a label mask takes the mean over the tokens it keeps
    first = head.score(params, [f32(h) for h in hs], f32(labels), {}, True,
                       mask=f32([[1, 0]]))
    token0 = (p * np.stack(ce)).sum(0)[0] + 0.05 * (p * np.log(p)).sum(0)[0]
    assert float(first) == pytest.approx(token0, rel=1e-5)


@pytest.mark.parametrize("compute_dtype", ["float32", "bfloat16"])
def test_token_losses_are_the_gathers_value_and_gradient(compute_dtype):
    """The tiny net's head on one pass's output: the masked sum against the
    gather from the logits flattened to 2-D, the losses bitwise, the gradient
    with respect to the head's input to 1e-6 of its largest entry."""
    net = _net(compute_dtype).init()
    head = net.out
    params = head.cast_params(net.params[head.name], jnp.dtype(compute_dtype))
    x = jax.random.normal(jax.random.PRNGKey(4), (2, 32, TINY["hidden_size"]),
                          jnp.dtype(compute_dtype))
    ids = jnp.asarray(_batch(t=32)[0][:, 1:], jnp.int32)

    def gathered(x):
        z2 = head.preout(params, x).astype(jnp.float32).reshape(
            -1, TINY["vocab_size"])
        tgt = jnp.take_along_axis(z2, ids.reshape(-1, 1), axis=1)[:, 0]
        return (jax.scipy.special.logsumexp(z2, axis=-1)
                - tgt).reshape(ids.shape)

    got = head._token_losses(params, x, ids)
    want = gathered(x)
    assert got.dtype == jnp.float32
    assert np.asarray(got).tobytes() == np.asarray(want).tobytes()
    g_got = jax.grad(lambda x: head._token_losses(params, x, ids).sum())(x)
    g_want = jax.grad(lambda x: gathered(x).sum())(x)
    scale = float(jnp.max(jnp.abs(g_want.astype(jnp.float32))))
    np.testing.assert_allclose(g_got.astype(jnp.float32),
                               g_want.astype(jnp.float32),
                               rtol=1e-6, atol=1e-6 * scale)


def test_output_is_the_last_passes_prediction():
    net = _net()
    ref, _ = _with_reference_weights(net)
    tok, _ = _batch(t=32)
    with jax.default_matmul_precision("highest"):
        last = plain.pass_outputs(ref, tok[:, :-1], TINY)[-1]
        want = jax.nn.softmax(last @ ref["head_w"], axis=-1)
    np.testing.assert_allclose(net.output(tok[:, :-1].astype(np.float32)),
                               want, rtol=2e-4, atol=1e-7)


# ------------------------------------------------- the model and its block

def test_parameters_are_counted_as_the_reference_counts_them():
    net = _net().init()
    assert net.num_params() == plain.num_params(TINY)
    shapes = lambda tree: jax.tree.map(lambda v: v.shape, tree)
    ref = plain.init_params(TINY, 1)
    names = [i.name for i in net.impls]
    assert shapes(net.params) == shapes(driver.to_program(ref, names))
    back = driver.to_reference(driver.to_program(ref, names), names)
    for a, b in zip(jax.tree.leaves(back), jax.tree.leaves(ref)):
        np.testing.assert_array_equal(a, b)
    assert net.conf.repeat_span == (1, 4) and net.conf.repeat_count == 3


def test_model_agrees_with_the_reference_in_loss_and_every_gradient():
    """float32 on both sides, so the two differ by the order of their sums:
    rtol as ``test_reference_agrees_with_the_programs_gradients`` has it, the
    atol relative to the leaf's largest entry (a gain's gradient is a sum over
    every token of every pass)."""
    net = _net()
    ref, names = _with_reference_weights(net)
    tok, ds = _batch()
    grads, score = net.gradient_and_score(ds)
    with jax.default_matmul_precision("highest"):
        loss, g = jax.value_and_grad(lambda p: plain.loss_sum(
            p, tok[:, :-1], tok[:, 1:], TINY) / tok[:, 1:].size)(ref)
    assert score == pytest.approx(float(loss), rel=1e-5)
    theirs = driver.to_program(g, names)
    for layer in names:
        assert set(grads[layer]) == set(theirs[layer])
        for leaf, want in theirs[layer].items():
            np.testing.assert_allclose(
                grads[layer][leaf], want, rtol=2e-3,
                atol=2e-5 * float(jnp.max(jnp.abs(want))) + 1e-9,
                err_msg=f"{layer}.{leaf}")


def test_block_agrees_with_the_references_block():
    net = _net()
    ref, _ = _with_reference_weights(net)
    impl, p = net.impls[1], jax.tree.map(lambda v: v[0], ref["blocks"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64), jnp.float32)
    got, _ = impl.forward(net.params[impl.name], x, {}, True)
    with jax.default_matmul_precision("highest"):
        want = jax.vmap(lambda row: plain._block(TINY, "float32")(row, p))(x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    # four gains a block, and the branch norms are in the body
    assert {"rms1_g", "mixer_norm_g", "rms2_g", "mlp_norm_g"} <= set(
        net.params[impl.name])
    flat = jax.tree.map(lambda v: v, net.params[impl.name])
    flat["mixer_norm_g"] = flat["mixer_norm_g"] * 2.0
    moved, _ = impl.forward(flat, x, {}, True)
    assert float(jnp.max(jnp.abs(moved - got))) > 1e-3


# ------------------------------------------------------ the repeated span

def _score(net, params, x, y):
    return net._score_fn(params, net.states, x, y, False, None, None, None)[0]


def test_shared_leafs_gradient_is_the_sum_over_untied_copies():
    """The span written out: pass ``s`` reads its own copy of every leaf. The
    shared leaf's gradient is the sum of the copies' gradients."""
    net = _net()
    _with_reference_weights(net)
    tok, _ = _batch(seed=7, t=32)
    x, y = (jnp.asarray(tok[:, :-1], jnp.float32),
            jnp.asarray(tok[:, 1:], jnp.float32))
    shared = jax.grad(lambda p: _score(net, p, x, y))(net.params)
    span = [impl for impl in net.impls[1:-1]]
    passes = net.conf.repeat_count

    def untied(copies):
        h, _ = net.impls[0].forward(net.params["layer0"], x, {}, False)
        outs = []
        for s in range(passes):
            for impl in span:
                h, _ = impl.forward(copies[s][impl.name], h, {}, False)
            outs.append(h)
        return net.out.score(net.params[net.out.name], outs, y, {}, False)

    copies = [{impl.name: net.params[impl.name] for impl in span}
              for _ in range(passes)]
    assert float(untied(copies)) == pytest.approx(
        float(_score(net, net.params, x, y)), rel=1e-6)
    per_copy = jax.grad(untied)(copies)
    for impl in span:
        for leaf, want in shared[impl.name].items():
            parts = [per_copy[s][impl.name][leaf] for s in range(passes)]
            assert all(float(jnp.max(jnp.abs(g))) > 0 for g in parts)
            np.testing.assert_allclose(sum(parts), want, rtol=1e-4,
                                       atol=1e-6 * float(jnp.max(jnp.abs(want))),
                                       err_msg=f"{impl.name}.{leaf}")


def test_one_pass_is_the_same_blocks_under_the_plain_softmax_head():
    once = dict(TINY, total_ut_steps=1)
    net = _net(cfg=once)
    _with_reference_weights(net, once)
    head = net.params[net.out.name]
    head["w_gate"] = jnp.zeros_like(head["w_gate"])  # the gate's weight off
    _, ds = _batch()
    # the same layers with no span and the usual head
    layers = list(net.conf.layers[:-1]) + [L.RnnOutputLayer(
        n_in=64, n_out=512, activation="softmax", loss_function="mcxent",
        has_bias=False)]
    plain_net = MultiLayerNetwork(MultiLayerConfiguration(
        conf=net.gc, layers=layers)).init()
    plain_net.params = {**{k: v for k, v in net.params.items()
                           if k != net.out.name},
                        net.out.name: {"W": head["W"]}}
    g_loop, s_loop = net.gradient_and_score(ds)
    g_plain, s_plain = plain_net.gradient_and_score(ds)
    assert s_loop == pytest.approx(s_plain, rel=1e-6)
    for layer in g_plain:
        for leaf, want in g_plain[layer].items():
            np.testing.assert_allclose(g_loop[layer][leaf], want, rtol=1e-5,
                                       atol=1e-9, err_msg=f"{layer}.{leaf}")
    assert float(jnp.max(jnp.abs(g_loop[net.out.name]["w_gate"]))) == 0.0


def _lowered(net, steps=2, debug_info=False):
    ids = np.random.default_rng(0).integers(0, 64, (2 * steps, 17))
    staged = net.stage_scan(DataSet(ids[:, :-1].astype(np.float32),
                                    ids[:, 1:].astype(np.float32)), 2)
    return net._make_scan_fit(1).lower(
        net.params, net.opt_state, net.states, *staged,
        net._train_rng()).as_text(debug_info=debug_info)


def test_a_net_without_a_span_lowers_as_before():
    """No span and a span that runs once are one program, and neither holds a
    pass's name; a head that scores no passes is handed the last one."""
    once = dict(TINY, total_ut_steps=1)
    with_span = _net("bfloat16", cfg=once)
    no_span = MultiLayerNetwork(MultiLayerConfiguration(
        conf=with_span.gc, layers=with_span.conf.layers)).init()
    assert no_span.conf.repeat_span is None
    assert no_span._applications == [(i, 0) for i in range(4)]
    text = _lowered(no_span, debug_info=True)
    assert "pass0" not in text
    assert _lowered(with_span.init()) == _lowered(no_span)
    assert "pass0" in _lowered(with_span, debug_info=True)
    # three passes under the usual head: it reads the third
    layers = list(_net().conf.layers[:-1]) + [L.RnnOutputLayer(
        n_in=64, n_out=512, activation="softmax", has_bias=False)]
    usual = MultiLayerNetwork(MultiLayerConfiguration(
        conf=_net().gc, layers=layers, repeat_span=(1, 4), repeat_count=3))
    usual.init()
    tok, ds = _batch(t=16)
    x = jnp.asarray(tok[:, :-1], jnp.float32)
    acts = usual.feed_forward(np.asarray(x))
    h, _ = usual.impls[0].forward(usual.params["layer0"], x, {}, False)
    for _ in range(3):
        for impl in usual.impls[1:-1]:
            h, _ = impl.forward(usual.params[impl.name], h, {}, False)
    np.testing.assert_allclose(acts[-2], h, rtol=1e-5, atol=1e-6)
    assert np.isfinite(usual.score(ds))


@pytest.mark.parametrize("span, count, match", [
    ((1, 3), 2, "end at the head"), ((4, 4), 2, "at least one layer"),
    ((1, 4), 0, "at least once")])
def test_a_span_that_cannot_run_is_refused(span, count, match):
    conf = _net().conf
    with pytest.raises(ValueError, match=match):
        MultiLayerNetwork(MultiLayerConfiguration(
            conf=conf.conf, layers=conf.layers, repeat_span=span,
            repeat_count=count))


def test_the_span_survives_the_configurations_json():
    conf = _net(kept_values=("flash_o", "flash_lse")).conf
    back = MultiLayerConfiguration.from_json(conf.to_json())
    assert (back.repeat_span, back.repeat_count) == ((1, 4), 3)
    assert back.layers == conf.layers
    block = back.layers[1]
    assert (block.rope_theta, block.branch_norms, block.kept_values) == (
        1e6, True, ("flash_o", "flash_lse"))
    assert back.layers[-1].entropy_weight == 0.05
    bare = MultiLayerConfiguration.from_json(MultiLayerConfiguration(
        conf=conf.conf, layers=conf.layers).to_json())
    assert bare.repeat_span is None and "repeat_span" not in bare.to_json()
    # the hybrid family's keys default to what was
    old = L.GroupedQueryBlock(n_in=8, n_out=8, ffn_hidden=16)
    assert (old.rope_theta, old.branch_norms, old.kept_values) == (
        None, False, None)
    assert set(old.to_dict()) == {"@type", "n_in", "n_out", "ffn_hidden"}


# -------------------------------------------------------- recomputation

def _fit(net, steps=3):
    ids = np.random.default_rng(0).integers(0, 512, (2 * steps, 33))
    staged = net.stage_scan(DataSet(ids[:, :-1].astype(np.float32),
                                    ids[:, 1:].astype(np.float32)), 2)
    return net.fit_scan(None, 2, staged=staged)


#: what the blocks keep -> (losses of three steps, the parameters after them)
_THREE_STEPS = {}
KEEPS = {"the class's": None, "nothing": (),
         "the kernel's": ("flash_o", "flash_lse"),
         "the wide product": (hybrid.GATE_UP_PRODUCT,),
         "recomputation off": "off"}


def _three_steps(keep):
    if keep not in _THREE_STEPS:
        kept = KEEPS[keep]
        net = _net("float32", recompute_blocks=kept != "off",
                   kept_values=None if kept == "off" else kept)
        _with_reference_weights(net)
        _THREE_STEPS[keep] = (_fit(net), jax.tree.leaves(net.params))
    return _THREE_STEPS[keep]


@pytest.mark.parametrize("keep", [k for k in KEEPS if k != "recomputation off"])
def test_what_a_recomputed_block_keeps_changes_no_loss_and_no_update(keep):
    (losses, params), (want_losses, want) = (
        _three_steps(keep), _three_steps("recomputation off"))
    assert len(losses) == 3
    np.testing.assert_allclose(losses, want_losses, rtol=1e-6)
    for a, b in zip(params, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_kept_values_are_the_blocks_own_names():
    net = _net(kept_values=("flash_o", "flash_lse"))
    assert net.impls[1].kept_names == ("flash_o", "flash_lse")
    assert _net().impls[1].kept_names == \
        hybrid.GroupedQueryBlockImpl.kept_names
    with pytest.raises(ValueError, match="cannot keep"):
        _net(kept_values=("attention_scores",))
    # a Mamba-2 block has no kernel output to keep
    with pytest.raises(ValueError, match="cannot keep"):
        MultiLayerNetwork(
            NeuralNetConfiguration.builder().list()
            .layer(L.Mamba2Block(n_in=64, n_out=64, ffn_hidden=128, n_heads=8,
                                 d_head=16, kept_values=("flash_o",)))
            .layer(L.RnnOutputLayer(n_in=64, n_out=8)).build())


def test_the_gauges_count_passes_and_applications():
    reg = monitor.get_registry()
    value = lambda name: reg.get(name).value
    _fit(_net(kept_values=("flash_o", "flash_lse")).init(), 1)
    assert value(monitor.SPAN_PASSES_GAUGE) == 3
    assert value(monitor.BLOCK_APPLICATIONS_GAUGE) == 3 * 2
    assert value(monitor.RECOMPUTED_BLOCKS_GAUGE) == 2
    assert value(monitor.RECOMPUTE_KEPT_VALUES_GAUGE) == 3 * 2 * 2
    from deeplearning4j_tpu.models.zoo.transformer import gpt
    _fit(gpt(vocab_size=512, d_model=32, n_layers=2, num_heads=2, max_len=64,
             seed=3).init(), 1)
    assert value(monitor.SPAN_PASSES_GAUGE) == 0
    assert value(monitor.BLOCK_APPLICATIONS_GAUGE) == 0


def test_the_step_names_its_parts_and_its_passes():
    text = _lowered(_net("bfloat16").init(), debug_info=True)
    assert "checkpoint" in text
    for scope in LOOPED_STEP_SCOPES:
        if scope in ("grad_norm", "fold_heads", "unfold_heads"):
            continue  # no normalization here; attention at 16 takes XLA's form
        assert scope in text, scope
    assert all(f"pass{s}" in text for s in range(3)) and "pass3" not in text


def test_the_looped_step_holds_no_gather_under_its_loss(gathers_under):
    text = _lowered(_net("bfloat16").init(), debug_info=True)
    assert gathers_under(text, "loss") == []
    assert gathers_under(text, "embed")  # the reader reads this text


# ------------------------------------------------------------ the builder

@pytest.mark.parametrize("change, match", [
    ({"total_ut_steps": 0}, "at least 1"),
    ({"use_sliding_window": True, "sliding_window": 4096}, "sliding windows"),
    ({"layer_types": ["full_attention", "sliding_attention"]}, "full_attention"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"tie_word_embeddings": True}, "untied"),
    ({"head_dim": 32}, "head_dim")])
def test_what_the_builder_does_not_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        looped_lm(dict(TINY, **change))


@pytest.mark.parametrize("entry", ["init_cache", "prefill", "prefill_paged",
                                   "decode_step"])
def test_serving_entry_points_raise_the_typed_error(entry):
    net = _net().init()
    for impl in net.impls[1:3]:
        with pytest.raises(TrainingOnlyError, match="only be trained"):
            getattr(impl, entry)()


def test_published_depth_is_used_where_no_cut_is_stated():
    cfg = {k: v for k, v in TINY.items() if k not in ("n_layer", "layer_types")}
    cfg["num_hidden_layers"] = 3
    net = looped_lm(cfg)
    assert len(net.impls) == 1 + 3 + 2 and net.conf.repeat_span == (1, 5)
