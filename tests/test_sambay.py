"""The decoder-hybrid-decoder family (SambaY) on the CPU at tiny sizes: the
selective scan's kernel (Pallas interpreter) and XLA paths against a
token-by-token loop; the flash kernels' window against the masked XLA softmax;
differential attention and the gated memory unit by hand; the container's
seam for values handed forward; the whole tiny model against the plain
reference (``benchmarks/reference/sambay_plain.py``); the builder, the JSON
round trip, the gauges, scopes and counters."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.drivers import train_scan_sambay as driver
from benchmarks.reference import sambay_plain as plain
from deeplearning4j_tpu import monitor
from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo import sambay
from deeplearning4j_tpu.models.zoo.sambay import sambay_lm
from deeplearning4j_tpu.nn.conf import (MultiLayerConfiguration,
                                        NeuralNetConfiguration)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.base import build_layer
from deeplearning4j_tpu.nn.layers.hybrid import TrainingOnlyError
from deeplearning4j_tpu.nn.multilayer import (SAMBAY_STEP_SCOPES,
                                              MultiLayerNetwork)
from deeplearning4j_tpu.ops import selective_scan as ss
from deeplearning4j_tpu.ops.attention import scaled_dot_product_attention
from deeplearning4j_tpu.ops.flash_attention import flash_attention, flash_path

CUT = ["mamba", "sliding_attention", "mamba", "full_attention", "gmu",
       "cross_attention"]
TINY = {
    "vocab_size": 96, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "sliding_window": 8,
    "layer_norm_eps": 1e-5, "num_hidden_layers": 32, "layer_types": CUT,
    "published_layers": [14, 15, 16, 17, 18, 19], "n_layer": 6,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 4, "initializer_range": 0.02,
    "tie_word_embeddings": True}
PUBLISHED = {
    "vocab_size": 200064, "hidden_size": 2560, "intermediate_size": 10240,
    "num_attention_heads": 40, "num_key_value_heads": 20,
    "sliding_window": 512, "layer_norm_eps": 1e-5, "num_hidden_layers": 32,
    "mamba_d_state": 16, "mamba_d_conv": 4, "mamba_expand": 2,
    "mamba_dt_rank": 160, "initializer_range": 0.02,
    "tie_word_embeddings": True}


def _net(compute_dtype="float32", cfg=TINY, **kw):
    return sambay_lm(cfg, compute_dtype=compute_dtype, seed=1, **kw)


def _with_reference_weights(net, cfg=TINY, seed=3):
    names = [i.name for i in net.impls]
    ref = plain.init_params(cfg, seed)
    net.init()
    net.params = driver.to_program(ref, names)
    return ref, names


def _batch(cfg=TINY, seed=5, rows=2, t=32):
    tok = plain.make_tokens(cfg, seed, 1, rows, t)[0]
    return tok, DataSet(tok[:, :-1].astype(np.float32),
                        tok[:, 1:].astype(np.float32))


def _reference_grads(ref, tok, cfg=TINY, **kw):
    with jax.default_matmul_precision("highest"):
        return jax.value_and_grad(lambda p: plain.loss_sum(
            p, jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:]), cfg, **kw)
            / tok[:, 1:].size)(ref)


def _rel(a, b):
    return float(jnp.linalg.norm(a.astype(jnp.float32) - b)
                 / (jnp.linalg.norm(b) + 1e-30))


# ------------------------------------------------------- the selective scan

def _scan_operands(dtype, b=2, t=200, c=512, n=16):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, t, c)).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, t, c)) - 2.0)
    A = -jnp.exp(jax.random.uniform(ks[2], (c, n), minval=0.0, maxval=2.5))
    B = jax.random.normal(ks[3], (b, t, n)).astype(dtype)
    C = jax.random.normal(ks[4], (b, t, n)).astype(dtype)
    return x, dt, A, B, C, jnp.linspace(0.5, 1.5, c)


def _token_loop(x, dt, A, B, C, D):
    """The recurrence as its equations read, a Python loop over tokens."""
    f32 = jnp.float32
    x, B, C = x.astype(f32), B.astype(f32), C.astype(f32)
    S = jnp.zeros((x.shape[0], x.shape[2], A.shape[1]), f32)
    ys = []
    for t in range(x.shape[1]):
        S = jnp.exp(dt[:, t, :, None] * A) * S \
            + (dt[:, t] * x[:, t])[..., None] * B[:, t, None, :]
        ys.append(jnp.sum(S * C[:, t, None, :], -1) + D * x[:, t])
    return jnp.stack(ys, axis=1)


@pytest.mark.parametrize("channels, path", [(512, "kernel"), (96, "xla")])
@pytest.mark.parametrize("dtype, tol", [(jnp.float32, 2e-6), (jnp.bfloat16, 0.05)])
def test_selective_scan_equals_the_token_loop(channels, path, dtype, tol):
    # 200 tokens: four chunks of the kernel's 64, the last one padded, so the
    # carried state and its adjoint cross three chunk edges
    args = _scan_operands(dtype, t=200 if path == "kernel" else 40,
                          c=channels)
    assert ss.selscan_path(channels, 16) == path
    weights = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    f = lambda fn: lambda *a: jnp.sum(fn(*a).astype(jnp.float32) * weights)
    y, want = ss.selective_scan(*args), _token_loop(*args)
    assert y.dtype == dtype and _rel(y, want) < tol
    got = jax.grad(f(ss.selective_scan), argnums=range(6))(*args)
    ref = jax.grad(f(_token_loop), argnums=range(6))(*args)
    for name, a, b in zip("x dt A B C D".split(), got, ref):
        assert a.dtype == b.dtype and _rel(a, b) < tol, name


def test_the_scan_counts_its_path_and_names_its_residuals():
    reg = monitor.get_registry()
    count = lambda p: reg.counter(monitor.SELSCAN_PATH_COUNTER, path=p).value
    before = count("kernel"), count("xla")
    args = _scan_operands(jnp.float32, b=1, t=64)
    text = str(jax.make_jaxpr(jax.grad(
        lambda *a: jnp.sum(ss.selective_scan(*a))))(*args))
    assert (count("kernel"), count("xla")) == (before[0] + 1, before[1])
    assert all(f"name={n}" in text for n in ss.SELSCAN_RESIDUAL_NAMES)
    assert ss.selscan_path(5120, 16) == "kernel"
    assert ss.selscan_path(128, 16) == "xla"  # the rehearsals' width


# ---------------------------------------------------------------- the window

@pytest.mark.parametrize("window", [1, 37, 128, 200, 511, 512, 600])
@pytest.mark.parametrize("block", [128, None])
def test_windowed_flash_equals_the_masked_softmax(window, block):
    # a row of 512 in blocks of 128 (and the default's one block): windows
    # inside a block, a block wide, across blocks, the row and beyond it
    ks = jax.random.split(jax.random.PRNGKey(window), 4)
    q, k, v, w = (jax.random.normal(kk, (1, 512, 2, 64)) for kk in ks)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, block_q=block, block_k=block)
    plain_ = lambda q, k, v: scaled_dot_product_attention(
        q, k, v, causal=True, window=window)
    assert _rel(flash(q, k, v), plain_(q, k, v)) < 2e-6
    got = jax.grad(lambda *a: jnp.sum(flash(*a) * w), (0, 1, 2))(q, k, v)
    ref = jax.grad(lambda *a: jnp.sum(plain_(*a) * w), (0, 1, 2))(q, k, v)
    for a, b in zip(got, ref):  # one key: the gradients of q and k are 0
        assert float(jnp.max(jnp.abs(a - b))) < 5e-6 * (1 + float(jnp.max(jnp.abs(b))))


def test_the_xla_window_is_the_band_by_hand():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q, k, v = (jax.random.normal(kk, (1, 9, 1, 4)) for kk in ks)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / 2.0
    ago = jnp.arange(9)[:, None] - jnp.arange(9)[None, :]
    seen = (ago >= 0) & (ago < 3)  # itself and the two keys before it
    want = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(
        jnp.where(seen, s, -jnp.inf), -1), v)
    got = scaled_dot_product_attention(q, k, v, causal=True, window=3)
    assert _rel(got, want) < 1e-6


def test_no_window_lowers_to_the_same_text_and_a_window_takes_the_streamed_path():
    q = jnp.zeros((1, 256, 2, 64), jnp.bfloat16)
    text = lambda **kw: jax.jit(lambda q: flash_attention(
        q, q, q, causal=True, **kw)).lower(q).as_text()
    assert text() == text(window=None) == text(window=256) == text(window=999)
    assert text(window=64) != text()
    assert flash_path(256, 256, 64, jnp.bfloat16, 2) == "resident_packed"
    assert flash_path(256, 256, 64, jnp.bfloat16, 2, window=64) == "streamed"
    assert flash_path(8192, 8192, 128, jnp.bfloat16, 40) == "streamed"
    reg = monitor.get_registry()
    n = reg.counter(monitor.FLASH_WINDOWED_COUNTER).value
    text(window=64)
    assert reg.counter(monitor.FLASH_WINDOWED_COUNTER).value == n + 1
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, q, q, causal=False, window=64)


# ------------------------------------------- the mixers against hand counts

def _block(conf):
    """A block's impl and its seeded leaves, outside any net."""
    gc = NeuralNetConfiguration.builder().weight_init("distribution") \
        .list().build().conf
    impl = build_layer(gc, conf, "layer0")
    return impl, impl.init_params(jax.random.PRNGKey(2))


def test_differential_attention_on_two_tokens_by_hand():
    conf = L.DiffAttentionBlock(n_in=8, n_out=8, ffn_hidden=8, num_heads=4,
                                num_kv_heads=2, layer_index=3, dist_std=0.5)
    impl, p = _block(conf)
    key = jax.random.PRNGKey(4)
    p = dict(p, bqkv=jax.random.normal(key, p["bqkv"].shape),
             bo=jax.random.normal(key, (8,)),
             subln_g=jnp.linspace(0.5, 1.5, 4))
    h = jax.random.normal(jax.random.PRNGKey(5), (1, 2, 8))
    out, provided = impl._mixer(p, h, None, {})
    # by hand: heads of 2, query pairs (0, 1) and (2, 3) both read the one
    # key/value pair; token 0 sees itself, token 1 both
    qkv = h[0] @ p["Wqkv"] + p["bqkv"]
    q, k, v = qkv[:, :8].reshape(2, 2, 2, 2), qkv[:, 8:12].reshape(2, 2, 2), \
        qkv[:, 12:]
    lam_init = 0.8 - 0.6 * math.exp(-0.9)
    lam = jnp.exp(p["lambda_q1"] @ p["lambda_k1"]) \
        - jnp.exp(p["lambda_q2"] @ p["lambda_k2"]) + lam_init
    rows = []
    for t in range(2):
        pairs = []
        for i in range(2):
            maps = [jax.nn.softmax(jnp.stack(
                [q[t, i, j] @ k[s, j] for s in range(t + 1)]) / math.sqrt(2))
                for j in range(2)]
            o = (maps[0] - lam * maps[1]) @ v[:t + 1]          # 4 wide
            o = o / jnp.sqrt(jnp.mean(o ** 2) + 1e-5) * p["subln_g"]
            pairs.append(o * (1 - lam_init))
        rows.append(jnp.concatenate(pairs))
    want = jnp.stack(rows) @ p["Wo"] + p["bo"]
    assert _rel(out[0], want) < 1e-5
    assert provided["kv"][0].shape == (1, 2, 4)
    assert _rel(provided["kv"][1][0], v) < 1e-6


def test_gated_memory_unit_by_hand():
    impl, p = _block(L.GMUBlock(n_in=8, n_out=8, ffn_hidden=8, d_inner=16,
                                reads=("layer9.memory",), dist_std=0.5))
    h = jax.random.normal(jax.random.PRNGKey(1), (1, 3, 8))
    m = jax.random.normal(jax.random.PRNGKey(2), (1, 3, 16))
    out, _ = impl._mixer(p, h, None, {"memory": m})
    g = h @ p["W_1"]
    assert _rel(out, (g / (1 + jnp.exp(-g)) * m) @ p["W_2"]) < 1e-6
    with pytest.raises(ValueError, match="hands them to it"):
        impl.forward(p, h, {}, False)


# ------------------------------------------------ the model and the reference

@pytest.mark.parametrize("compute_dtype, recompute, tol", [
    ("float32", False, 2e-4), ("float32", True, 2e-4),
    # bfloat16 rounds every product's operands to 8 bits: a leaf's gradient
    # agrees to a few percent; lambda's is one number, a sum over the whole
    # batch of products that cancel, and is held to its size only
    ("bfloat16", True, 0.06)])
def test_loss_and_every_leafs_gradient_equal_the_reference(compute_dtype,
                                                           recompute, tol):
    net = _net(compute_dtype, recompute_blocks=recompute)
    ref, names = _with_reference_weights(net)
    tok, ds = _batch()
    grads, score = net.gradient_and_score(ds)
    loss, want = _reference_grads(ref, tok)
    assert abs(score - float(loss)) < (1e-5 if compute_dtype == "float32"
                                       else 2e-3) * float(loss)
    want = driver.to_program(want, names)
    for name in names[:-1]:
        for leaf, g in grads[name].items():
            bound = 1.5 if leaf.startswith("lambda_") \
                and compute_dtype == "bfloat16" else tol
            assert _rel(g, want[name][leaf]) < bound, (name, leaf)


def test_gradients_flow_back_through_what_is_handed_forward():
    """Layer 17's K/V projection gets its own use plus the cross layer's, and
    layer 16's scan inputs get the gated memory unit's: with a reader's values
    detached where it reads them, the program's gradient loses that reader's
    part, the parts add up, and with both detached it is the reference's
    ``detach_forwarded``."""
    net = _net()
    ref, names = _with_reference_weights(net)
    tok, ds = _batch()
    giver, full_attn = names[3], names[4]
    gmu, cross = net.impls[5], net.impls[6]

    def grads(*detached):
        mixers = {impl: impl._mixer for impl in detached}
        for impl, mixer in mixers.items():
            impl._mixer = lambda p, h, mask, read, mixer=mixer: mixer(
                p, h, mask, jax.tree.map(jax.lax.stop_gradient, read))
        try:
            return net.gradient_and_score(ds)[0]
        finally:
            for impl in mixers:
                del impl._mixer
    whole, no_gmu, no_cross, own = (grads(), grads(gmu), grads(cross),
                                    grads(gmu, cross))
    part = lambda a, b: jax.tree.map(jnp.subtract, a, b)
    gmus, crosss = part(whole, no_gmu), part(whole, no_cross)
    size = lambda tree, layer, leaf: float(jnp.linalg.norm(tree[layer][leaf]))
    # own use + the gated memory unit's + the cross layer's = the whole
    for a, b in zip(jax.tree.leaves(whole), jax.tree.leaves(
            jax.tree.map(lambda o, g, c: o + g + c, own, gmus, crosss))):
        assert _rel(a, b) < 1e-5
    want = driver.to_program(_reference_grads(
        ref, tok, faults=("detach_forwarded",))[1], names)
    for layer in names[:-1]:
        for leaf, g in own[layer].items():
            assert _rel(g, want[layer][leaf]) < 2e-4, (layer, leaf)
    # the memory is the scan's output BEFORE the gate: the unit's part reaches
    # the scan's inputs and the xs half of W_in, not z's half, not W_out, and
    # no layer after the one that gives it
    d_inner = 128
    for leaf in ("W_x", "W_dt", "conv_w", "A_log", "dt_bias", "D"):
        assert size(gmus, giver, leaf) > 0.05 * size(whole, giver, leaf), leaf
    xs, z = jnp.split(gmus[giver]["W_in"], [d_inner], axis=-1)
    assert float(jnp.linalg.norm(xs)) > 0 and float(jnp.linalg.norm(z)) == 0
    assert size(gmus, giver, "W_out") == 0
    assert all(size(gmus, full_attn, leaf) == 0 for leaf in gmus[full_attn])
    # the cross layer's part reaches the K and V columns of layer 17's
    # projection and its bias, not the Q columns and not its output projection
    d, kv = 64, 2 * 16
    for leaf in ("Wqkv", "bqkv"):
        q, k, v = jnp.split(crosss[full_attn][leaf], [d, d + kv], axis=-1)
        w = jnp.split(whole[full_attn][leaf], [d, d + kv], axis=-1)
        assert float(jnp.linalg.norm(q)) == 0
        assert float(jnp.linalg.norm(v)) > 0.05 * float(jnp.linalg.norm(w[2]))
        if leaf == "Wqkv":  # the keys' bias has no gradient at all
            assert float(jnp.linalg.norm(k)) > 0.05 * float(jnp.linalg.norm(w[1]))
    assert size(crosss, full_attn, "Wo") == 0
    assert size(crosss, giver, "W_out") > 0  # upstream of layer 17's input


def test_forwarded_values_take_one_arithmetic_recomputed_or_not():
    tok, ds = _batch()
    out = []
    for recompute in (False, True):
        net = _net("bfloat16", recompute_blocks=recompute,
                   kept_values=("flash_o", "selscan_y"))
        _with_reference_weights(net)
        out.append(net.gradient_and_score(ds))
    (g0, s0), (g1, s1) = out
    assert s0 == s1
    for a, b in zip(jax.tree.leaves(g0), jax.tree.leaves(g1)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the inference path hands them forward as the training path does
    net = _net()
    _with_reference_weights(net)
    probs = net.output(ds.features)
    assert probs.shape == (2, 32, 96) and np.isfinite(probs).all()


def test_the_model_runs_the_kernels_and_trains():
    cfg = dict(TINY, hidden_size=256, intermediate_size=256,
               num_attention_heads=4, num_key_value_heads=2, mamba_dt_rank=16,
               sliding_window=128)
    net = _net("bfloat16", cfg=cfg)
    ref, names = _with_reference_weights(net, cfg)
    tok, ds = _batch(cfg, rows=1, t=256)
    reg = monitor.get_registry()
    count = lambda name, **l: reg.counter(name, **l).value
    before = (count(monitor.SELSCAN_PATH_COUNTER, path="kernel"),
              count(monitor.FLASH_WINDOWED_COUNTER))
    scores = net.fit_scan(None, 1, epochs=2, staged=net.stage_scan(ds, 1))
    assert scores[1] < scores[0]
    # d_inner 512 takes the scan's kernels (the interpreter here), and the
    # windowed layer's call is counted
    assert count(monitor.SELSCAN_PATH_COUNTER, path="kernel") >= before[0] + 2
    assert count(monitor.FLASH_WINDOWED_COUNTER) >= before[1] + 1
    loss = _reference_grads(ref, tok, cfg)[0]
    assert abs(scores[0] - float(loss)) < 2e-3 * float(loss)


# ------------------------------------------------------------ the builder

def test_the_derived_pattern_and_the_parameter_counts():
    kinds = sambay.layer_pattern(32)
    assert kinds == plain.layer_pattern(32)
    assert [kinds.count(k) for k in sambay.KINDS] == [9, 8, 1, 7, 7]
    assert kinds[14:20] == CUT and kinds[16] == "mamba"
    whole = sambay_lm(PUBLISHED)
    count = lambda net: sum(impl.num_params() for impl in net.impls)
    assert count(whole) == plain.num_params(PUBLISHED) == 3_852_562_944
    cut = dict(PUBLISHED, layer_types=CUT, vocab_size=25008,
               published_layers=list(range(14, 20)))
    assert count(sambay_lm(cut)) == plain.num_params(cut) == 697_094_272
    confs = whole.conf.layers
    assert confs[17].provides == ("memory",) and confs[18].provides == ("kv",)
    assert confs[19].reads == ("layer17.memory",)
    assert confs[20].reads == ("layer18.kv",) and confs[20].cross
    assert [c.window for c in confs[2:19:2]] == [512] * 8 + [None]
    assert confs[18].layer_index == 17 and confs[1].provides == ()
    assert sambay_lm(cut).impls[2].lambda_init == pytest.approx(
        0.8 - 0.6 * math.exp(-4.5))


@pytest.mark.parametrize("change, match", [
    ({"layer_types": None, "num_hidden_layers": 6}, "multiple of 4"),
    ({"layer_types": ["gmu", "mamba"], "published_layers": [0, 1]}, "memory"),
    ({"layer_types": ["mamba", "cross_attention"],
      "published_layers": [0, 1]}, "keys and values"),
    ({"layer_types": ["mamba", "attention"], "published_layers": [0, 1]},
     "layer_types holds"),
    ({"rope_theta": 10000.0}, "rotary"),
    ({"tie_word_embeddings": False}, "tied")])
def test_what_the_builder_does_not_build_is_refused(change, match):
    with pytest.raises(ValueError, match=match):
        sambay_lm(dict(TINY, **change))


def _chain(*blocks, span=None):
    b = NeuralNetConfiguration.builder().list()
    for block in blocks:
        b = b.layer(block)
    b = b.layer(L.RnnOutputLayer(n_in=8, n_out=8))
    if span:
        b = b.repeat_span(*span)
    return b.build()


def test_the_container_refuses_readers_without_providers():
    shared = dict(n_in=8, n_out=8, ffn_hidden=8)
    mamba = lambda **kw: L.Mamba1Block(d_inner=16, dt_rank=2, **shared, **kw)
    gmu = lambda src: L.GMUBlock(d_inner=16, reads=(src,), **shared)
    MultiLayerNetwork(_chain(mamba(provides=("memory",)), gmu("layer0.memory")))
    for conf, match in [
            (_chain(gmu("layer1.memory"), mamba(provides=("memory",))),
             "no earlier layer"),
            (_chain(mamba(), gmu("layer0.memory")), "no earlier layer"),
            (_chain(mamba(provides=("memory",)), gmu("layer0.kv")), "reads"),
            (_chain(mamba(provides=("kv",))), "cannot provide"),
            (_chain(L.GMUBlock(d_inner=16, **shared)), "reads"),
            (_chain(mamba(provides=("memory",)), gmu("layer0.memory"),
                    span=(1, 2, 2)), "repeated span"),
            (_chain(mamba(kept_values=("flash_o",))), "cannot keep")]:
        with pytest.raises(ValueError, match=match):
            MultiLayerNetwork(conf)


def test_configuration_round_trips_through_json():
    conf = _net().conf
    again = MultiLayerConfiguration.from_json(conf.to_json())
    assert again.layers == conf.layers
    assert again.layers[4].provides == ("kv",)
    assert again.layers[5].reads == ("layer3.memory",)
    assert again.layers[2].window == 8 and again.layers[6].cross


def test_gauges_and_scopes_of_the_step():
    reg = monitor.get_registry()
    value = lambda name: reg.get(name).value
    net = _net("bfloat16", kept_values=("flash_o", "flash_lse", "selscan_y"))
    net.init()
    _, ds = _batch()
    staged = net.stage_scan(ds, 2)
    net.fit_scan(None, 2, staged=staged)
    assert value(monitor.FORWARDED_VALUES_GAUGE) == 2
    assert value(monitor.RECOMPUTED_BLOCKS_GAUGE) == 6
    # two names an attention block (3 of them), one a Mamba block (2)
    assert value(monitor.RECOMPUTE_KEPT_VALUES_GAUGE) == 3 * 2 + 2
    text = net._make_scan_fit(1).lower(
        net.params, net.opt_state, net.states, *staged,
        net._train_rng()).as_text(debug_info=True)
    for scope in SAMBAY_STEP_SCOPES:
        if scope in ("grad_norm", "fold_heads", "unfold_heads"):
            continue  # no normalization here; attention at 32 takes XLA's form
        assert scope in text, scope
    from deeplearning4j_tpu.models.zoo.transformer import gpt
    gpt(vocab_size=96, d_model=32, n_layers=1, num_heads=2, max_len=32,
        seed=3).init().fit_scan(None, 2, staged=staged)
    assert value(monitor.FORWARDED_VALUES_GAUGE) == 0


@pytest.mark.parametrize("entry", ["init_cache", "prefill", "prefill_paged",
                                   "decode_step"])
def test_serving_entry_points_raise_the_typed_error(entry):
    net = _net().init()
    for impl in net.impls[1:7]:
        with pytest.raises(TrainingOnlyError, match="only be trained"):
            getattr(impl, entry)()
