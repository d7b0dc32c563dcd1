"""The hybrid state-space family on the CPU at tiny sizes: the chunked scan
(``ops/ssd.py``: the plain form and the Pallas kernels under the interpreter)
against a token-by-token recurrence; the Mamba-2 and grouped-query blocks and
the whole tiny model against the plain reference
(``benchmarks/reference/granite_hybrid_plain.py``); the tied head's one leaf;
recomputed block bodies; the keys that the GPT family's classes gained."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import train_scan_hybrid as driver
from benchmarks.reference import granite_hybrid_plain as plain
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo.granite_hybrid import granite_hybrid
from deeplearning4j_tpu.models.zoo.transformer import gpt
from deeplearning4j_tpu.nn.layers import hybrid
from deeplearning4j_tpu.nn.layers.hybrid import TrainingOnlyError
from deeplearning4j_tpu.nn.multilayer import HYBRID_STEP_SCOPES
from deeplearning4j_tpu.ops import ssd

TINY = {
    "vocab_size": 512, "hidden_size": 64, "shared_intermediate_size": 128,
    "layer_types": ["mamba", "attention", "mamba"], "n_layer": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "mamba_n_heads": 8,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_d_conv": 4,
    "mamba_n_groups": 1, "mamba_chunk_size": 16, "mamba_expand": 2,
    "rms_norm_eps": 1e-5, "embedding_multiplier": 12,
    "residual_multiplier": 0.22, "attention_multiplier": 0.015625,
    "logits_scaling": 8, "initializer_range": 0.02,
    "tie_word_embeddings": True, "position_embedding_type": "nope"}


# ------------------------------------------------------------------ the scan

def recurrence(x, dt, A, B, C, D):
    """S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T; y_t = S_t C_t + D x_t."""
    b, t, h, p = x.shape
    rep = h // B.shape[2]
    Bh, Ch = jnp.repeat(B, rep, axis=2), jnp.repeat(C, rep, axis=2)

    def step(S, z):
        xt, dtt, Bt, Ct = z
        S = jnp.exp(dtt * A)[..., None, None] * S \
            + (dtt[..., None] * xt)[..., None] * Bt[:, :, None, :]
        return S, jnp.einsum("bhpn,bhn->bhp", S, Ct) + D[:, None] * xt

    time_first = lambda z: jnp.swapaxes(z, 0, 1)
    _, y = jax.lax.scan(step, jnp.zeros((b, h, p, B.shape[-1]), x.dtype),
                        (time_first(x), time_first(dt), time_first(Bh),
                         time_first(Ch)))
    return time_first(y)


def scan_inputs(b, t, h, g, p, n, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    f32 = jnp.float32
    return (jax.random.normal(ks[0], (b, t, h, p), f32),
            jax.nn.softplus(jax.random.normal(ks[1], (b, t, h), f32) - 2.0),
            -jnp.exp(jax.random.uniform(ks[2], (h,), f32, 0.0, 2.7)),
            0.3 * jax.random.normal(ks[3], (b, t, g, n), f32),
            0.3 * jax.random.normal(ks[4], (b, t, g, n), f32),
            jnp.linspace(0.5, 1.5, h, dtype=f32))


#: (b, t, h, g, p, n, chunk): the plain form at a length that is a multiple
#: of the chunk, at one that is not, with two groups; the kernels at one
#: chunk, at a row of three and at a length that is not a multiple
SCANS = {"xla-whole": (2, 48, 4, 2, 8, 16, 16),
         "xla-ragged": (1, 41, 4, 1, 8, 16, 16),
         "kernel-one-chunk": (1, 128, 8, 1, 16, 128, 128),
         "kernel-three-chunks": (1, 384, 8, 1, 16, 128, 128),
         "kernel-ragged": (2, 200, 8, 1, 16, 128, 128)}


@pytest.mark.parametrize("case", sorted(SCANS))
def test_scan_and_its_six_gradients_agree_with_the_recurrence(case):
    b, t, h, g, p, n, chunk = SCANS[case]
    assert ssd.ssd_path(h, g, p, n, chunk) == case.split("-")[0]
    args = scan_inputs(b, t, h, g, p, n)
    want = recurrence(*args)
    got = ssd.ssd_scan(*args, chunk)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)
    w = jax.random.normal(jax.random.PRNGKey(9), want.shape, jnp.float32)
    every = tuple(range(6))
    g_want = jax.grad(lambda *a: jnp.sum(recurrence(*a) * w), every)(*args)
    g_got = jax.grad(lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk) * w),
                     every)(*args)
    for name, a, e in zip("x dt A B C D".split(), g_got, g_want):
        scale = float(jnp.max(jnp.abs(e)))
        np.testing.assert_allclose(a, e, rtol=2e-4, atol=2e-5 * scale,
                                   err_msg=name)


def test_kernels_agree_with_the_plain_chunked_form_in_bfloat16():
    b, t, h, g, p, n, chunk = 1, 256, 8, 1, 16, 128, 128
    x, dt, A, B, C, D = scan_inputs(b, t, h, g, p, n, seed=3)
    low = lambda z: z.astype(jnp.bfloat16)
    got = ssd.ssd_scan(low(x), dt, A, low(B), low(C), D, chunk)
    want = ssd.ssd_chunked(low(x), dt, A, low(B), low(C), chunk) \
        + (D[:, None] * low(x)).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    np.testing.assert_allclose(got.astype(np.float32),
                               want.astype(np.float32), rtol=0.03, atol=0.03)


def test_a_state_that_is_not_carried_is_another_result():
    args = scan_inputs(1, 64, 4, 1, 8, 16, seed=1)[:5]
    whole = ssd.ssd_chunked(*args, 16)
    # every chunk a row of its own starts from a zero state
    x, dt, A, B, C = args
    rows = lambda z: z.reshape((-1, 16) + z.shape[2:])
    cut = ssd.ssd_chunked(rows(x), rows(dt), A, rows(B), rows(C), 16) \
        .reshape(x.shape)
    np.testing.assert_allclose(cut[:, :16], whole[:, :16], rtol=1e-6)
    assert float(jnp.max(jnp.abs(cut[:, 16:] - whole[:, 16:]))) > 1e-2


def test_the_path_is_chosen_from_the_shapes_and_counted():
    assert ssd.ssd_path(64, 1, 64, 128, 256) == "kernel"   # the published sizes
    assert ssd.ssd_path(8, 1, 16, 16, 16) == "xla"         # the rehearsal's
    assert ssd.ssd_path(64, 1, 64, 128, 200) == "xla"
    assert ssd.ssd_path(64, 16, 64, 128, 256) == "xla"     # 4 heads a group
    reg = monitor.MetricsRegistry()
    old = monitor.set_registry(reg)
    try:
        ssd.ssd_scan(*scan_inputs(1, 16, 4, 1, 8, 16), 16)
        assert reg.get(monitor.SSD_PATH_COUNTER, path="xla").value == 1
    finally:
        monitor.set_registry(old)


# ------------------------------------------------------- blocks and the model

def _net(compute_dtype="float32", seed=5, **kw):
    return granite_hybrid(TINY, compute_dtype=compute_dtype, seed=seed, **kw)


def _with_reference_weights(net, seed=5):
    names = [i.name for i in net.impls]
    ref = plain.init_params(TINY, seed)
    net.init()
    net.params = driver.to_program(ref, names)
    return ref, names


def test_parameters_are_counted_once_and_as_the_reference_counts_them():
    net = _net().init()
    assert net.num_params() == plain.num_params(TINY)
    assert net.params["layer5"] == {}            # the head owns nothing
    assert "P" not in net.params["layer0"]       # and no positions exist
    assert net.opt_state["updater"]["layer5"] == {}
    shapes = lambda tree: jax.tree.map(lambda v: v.shape, tree)
    ref = plain.init_params(TINY, 1)
    assert shapes(net.params) == shapes(driver.to_program(
        ref, [i.name for i in net.impls]))
    # the published widths give the configuration's count
    from benchmarks import run
    cfg = run.load_json(run.ROOT, "benchmarks/configs/granite-4.0-h-micro.json")
    assert plain.num_params(cfg) == 772_160_448


def test_model_agrees_with_the_reference_in_loss_and_every_gradient():
    net = _net()
    ref, names = _with_reference_weights(net)
    tok = plain.make_tokens(TINY, 5, 1, 2, 64)[0]
    grads, score = net.gradient_and_score(DataSet(
        tok[:, :-1].astype(np.float32), tok[:, 1:].astype(np.float32)))
    loss, g = jax.value_and_grad(lambda p: plain.loss_sum(
        p, tok[:, :-1], tok[:, 1:], TINY) / tok[:, 1:].size)(ref)
    assert score == pytest.approx(float(loss), rel=1e-5)
    theirs = driver.to_program(g, names)
    for layer in names:
        assert set(grads[layer]) == set(theirs[layer])
        for leaf, want in theirs[layer].items():
            np.testing.assert_allclose(
                grads[layer][leaf], want, rtol=2e-3,
                atol=2e-6 * float(jnp.max(jnp.abs(want))) + 1e-9,
                err_msg=f"{layer}.{leaf}")


@pytest.mark.parametrize("kind", ["mamba", "attention"])
def test_block_agrees_with_the_references_layer(kind):
    net = _net()
    ref, names = _with_reference_weights(net)
    i = TINY["layer_types"].index(kind)
    impl, p = net.impls[1 + i], ref["layers"][i]
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 64, 64), jnp.float32)
    got, _ = impl.forward(p, x, {}, True)
    want = jax.vmap(lambda row: plain._layer(kind, TINY, "float32", True)(
        row, p))(x)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-5)


def test_tied_leafs_gradient_is_the_sum_of_both_uses():
    net = _net()
    ref, names = _with_reference_weights(net)
    tok = plain.make_tokens(TINY, 7, 1, 2, 32)[0]
    x, y = (jnp.asarray(tok[:, :-1], jnp.float32),
            jnp.asarray(tok[:, 1:], jnp.float32))
    score = lambda p: net._score_fn(p, net.states, x, y, False, None, None,
                                    None)[0]
    both = jax.grad(score)(net.params)["layer0"]["W"]
    E = net.params["layer0"]["W"]
    real = net._params_of

    def one_use(embedding, head):
        """The score with the two uses of the leaf given apart."""
        net._params_of = lambda params, impl: (
            {**params[impl.name], "W": head.T} if impl is net.out
            else real(params, impl))
        try:
            return score({**net.params, "layer0": {"W": embedding}})
        finally:
            net._params_of = real

    frozen = jax.lax.stop_gradient(E)
    by_embedding = jax.grad(lambda e: one_use(e, frozen))(E)
    by_head = jax.grad(lambda e: one_use(frozen, e))(E)
    assert float(jnp.max(jnp.abs(by_head))) > 0
    np.testing.assert_allclose(both, by_embedding + by_head, rtol=1e-5,
                               atol=1e-9)


def test_tied_head_is_saved_and_loaded_once(tmp_path):
    net = _net().init()
    flat = net.params_flat()
    assert flat.size == plain.num_params(TINY)
    other = _net(seed=9).init()
    other.set_params_flat(flat)
    np.testing.assert_array_equal(other.params["layer0"]["W"],
                                  net.params["layer0"]["W"])
    x = np.random.default_rng(0).integers(0, 512, (2, 32)).astype(np.float32)
    np.testing.assert_allclose(other.output(x), net.output(x), rtol=1e-6)


def _fit(net, steps=3):
    ids = np.random.default_rng(0).integers(0, 512, (2 * steps, 33))
    staged = net.stage_scan(DataSet(ids[:, :-1].astype(np.float32),
                                    ids[:, 1:].astype(np.float32)), 2)
    return net.fit_scan(None, 2, staged=staged)


def _keep_nothing(patch):
    """The recomputable blocks as they were before they named anything: the
    block's input is all that a recomputed body keeps."""
    patch.setattr(hybrid.GatedDecoderImpl, "kept_names", ())
    patch.setattr(hybrid.GroupedQueryBlockImpl, "kept_names", ())


#: program -> (losses of three steps, the parameters after them)
_THREE_STEPS = {}


def _three_steps(program):
    if program not in _THREE_STEPS:
        with pytest.MonkeyPatch.context() as patch:
            if program == "nothing kept":
                _keep_nothing(patch)
            net = _net(recompute_blocks=program != "recomputation off")
            _with_reference_weights(net)
            _THREE_STEPS[program] = (_fit(net), jax.tree.leaves(net.params))
    return _THREE_STEPS[program]


@pytest.mark.parametrize("one,other", [
    ("the names kept", "recomputation off"),
    ("nothing kept", "recomputation off"),
    ("the names kept", "nothing kept")])
def test_recomputed_block_bodies_change_no_loss_and_no_update(one, other):
    """Three programs, one arithmetic: what a recomputed body keeps is what
    its second run would have made."""
    (losses, params), (want_losses, want) = (_three_steps(one),
                                             _three_steps(other))
    assert len(losses) == 3
    np.testing.assert_allclose(losses, want_losses, rtol=1e-6)
    for a, b in zip(params, want):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-7)


def test_the_gauges_say_what_the_step_recomputes_and_keeps():
    reg = monitor.get_registry()
    value = lambda name: reg.get(name).value
    _fit(_net(recompute_blocks=True).init(), 1)
    assert value(monitor.RECOMPUTED_BLOCKS_GAUGE) == 3
    # the gate/up product of each block and the attention kernel's o and lse
    assert value(monitor.RECOMPUTE_KEPT_VALUES_GAUGE) == 3 + 2
    _fit(_net(recompute_blocks=False).init(), 1)
    assert value(monitor.RECOMPUTED_BLOCKS_GAUGE) == 0
    assert value(monitor.RECOMPUTE_KEPT_VALUES_GAUGE) == 0


def _lowered(net, steps=2, debug_info=True):
    ids = np.random.default_rng(0).integers(0, 64, (2 * steps, 17))
    staged = net.stage_scan(DataSet(ids[:, :-1].astype(np.float32),
                                    ids[:, 1:].astype(np.float32)), 2)
    return net._make_scan_fit(1).lower(
        net.params, net.opt_state, net.states, *staged,
        net._train_rng()).as_text(debug_info=debug_info)


def test_recomputation_is_in_the_hybrid_step_and_names_its_parts():
    text = _lowered(_net("bfloat16").init())
    assert "checkpoint" in text
    for scope in HYBRID_STEP_SCOPES:
        if scope in ("grad_norm", "fold_heads", "unfold_heads"):
            continue  # no normalization here; attention at 16 takes XLA's form
        assert f"{scope}" in text, scope
    assert "checkpoint" not in _lowered(_net(recompute_blocks=False).init())


def _forward_products(text, d, width):
    """How often a lowered step contracts [.., d] with a [d, width] matrix:
    the shape of a projection's forward alone (its two gradients contract
    over the tokens and over ``width``)."""
    return len(re.findall(
        rf"\(tensor<\d+x\d+x{d}x\w+>, tensor<{d}x{width}x\w+>\) -> ", text))


def test_a_recomputed_body_makes_the_gate_up_product_once(monkeypatch):
    """The second forward of a block no longer holds ``h @ W_gate_up``; the
    Mamba-2 in-projection, which is not kept, is still made twice."""
    d, f = TINY["hidden_size"], TINY["shared_intermediate_size"]
    inner = TINY["mamba_n_heads"] * TINY["mamba_d_head"]
    w_in = 2 * inner + 2 * TINY["mamba_d_state"] + TINY["mamba_n_heads"]
    kinds = TINY["layer_types"]
    text = _lowered(_net("bfloat16").init())
    assert _forward_products(text, d, 2 * f) == len(kinds)
    assert _forward_products(text, d, w_in) == 2 * kinds.count("mamba")
    _keep_nothing(monkeypatch)
    text = _lowered(_net("bfloat16").init())
    assert _forward_products(text, d, 2 * f) == 2 * len(kinds)
    assert _forward_products(text, d, w_in) == 2 * kinds.count("mamba")


def test_gpt_step_is_the_same_program_without_the_new_seams(monkeypatch):
    """``zoo.gpt`` through the container as it was before the keys: no tied
    leaf looked up, every float leaf cast, no recomputation asked."""
    from deeplearning4j_tpu.nn.layers.base import LayerImpl
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.util.dtypes import cast_floats

    make = lambda: gpt(vocab_size=64, d_model=32, n_layers=2, num_heads=2,
                       max_len=32, compute_dtype="bfloat16", seed=3).init()
    assert "checkpoint" not in _lowered(make())
    with_seams = _lowered(make(), debug_info=False)
    monkeypatch.setattr(MultiLayerNetwork, "_params_of",
                        lambda self, params, impl: params[impl.name])
    monkeypatch.setattr(MultiLayerNetwork, "_recomputes",
                        lambda self, impl: False)
    monkeypatch.setattr(LayerImpl, "cast_params",
                        lambda self, p, dtype: cast_floats(p, dtype))
    assert _lowered(make(), debug_info=False) == with_seams
    # no GPT block says ``recomputable`` (no cell asks it of one): the
    # setting, turned on, leaves this family's program as it is
    monkeypatch.undo()
    net = make()
    net.gc.recompute_blocks = True
    assert _lowered(net, debug_info=False) == with_seams


def test_a_recomputable_layer_that_names_nothing_is_checkpointed_plainly(
        monkeypatch):
    """No names, no policy: the step lowers to what the plain
    ``jax.checkpoint(layer)`` round each block gave before blocks could name
    values."""
    _keep_nothing(monkeypatch)
    with_seam = _lowered(_net("bfloat16").init(), debug_info=False)
    policies = []
    checkpoint = jax.checkpoint

    def plain_checkpoint(fun, policy=None):
        policies.append(policy)
        return checkpoint(fun)

    monkeypatch.setattr(jax, "checkpoint", plain_checkpoint)
    assert _lowered(_net("bfloat16").init(), debug_info=False) == with_seam
    assert policies == [None] * len(TINY["layer_types"])


def test_gpt_family_keys_default_to_what_was():
    from deeplearning4j_tpu.nn.conf import layers as L
    emb = L.SequenceEmbeddingLayer(n_in=8, n_out=4)
    assert (emb.positions, emb.output_multiplier) == (True, 1.0)
    assert set(emb.to_dict()) == {"@type", "n_in", "n_out"}
    head = L.RnnOutputLayer(n_in=4, n_out=8)
    assert (head.tied_to, head.logits_scale) == (None, 1.0)
    round_trip = L.layer_from_dict(L.Mamba2Block(
        n_in=64, n_out=64, ffn_hidden=128, n_heads=8, d_head=16).to_dict())
    assert round_trip.n_heads == 8 and round_trip.chunk_size == 256
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    assert NeuralNetConfiguration().recompute_blocks is False


def test_a_head_tied_to_no_layer_is_refused():
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf import layers as L
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    conf = (NeuralNetConfiguration.builder().list()
            .layer(L.SequenceEmbeddingLayer(n_in=8, n_out=4))
            .layer(L.RnnOutputLayer(n_in=4, n_out=8, has_bias=False,
                                    tied_to="layer7")).build())
    with pytest.raises(ValueError, match="layer7"):
        MultiLayerNetwork(conf)


@pytest.mark.parametrize("entry", ["init_cache", "prefill", "prefill_paged",
                                   "decode_step"])
def test_serving_entry_points_raise_the_typed_error(entry):
    net = _net().init()
    for impl in net.impls[1:4]:
        with pytest.raises(TrainingOnlyError, match="only be trained"):
            getattr(impl, entry)()
    assert issubclass(TrainingOnlyError, NotImplementedError)


def test_mamba_keeps_its_decays_in_float32_under_bfloat16():
    net = _net("bfloat16").init()
    impl = net.impls[1]
    cast = impl.cast_params(net.params[impl.name], jnp.bfloat16)
    assert {k for k, v in cast.items() if v.dtype == jnp.float32} == \
        set(impl.FLOAT32_LEAVES)
    assert np.isfinite(_fit(net)).all()
