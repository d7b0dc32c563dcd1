"""Transformer block + GPT zoo tests.

SURVEY §7.7 extension layers: gradcheck, causality, training, and the
single-config single-chip vs DP×SP sequence-parallel equivalence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.models.zoo.transformer import gpt
from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    RnnOutputLayer, SequenceEmbeddingLayer, TransformerBlock)
from deeplearning4j_tpu.nn.gradientcheck import check_gradients
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.parallel.mesh import make_mesh, sequence_mesh


def _tiny_gpt(vocab=11, d=16, layers=2, max_len=16, dropout=0.0):
    return gpt(vocab_size=vocab, d_model=d, n_layers=layers, num_heads=2,
               max_len=max_len, dropout=dropout, compute_dtype="float32",
               learning_rate=0.01).init()


def _data(rng, vocab=11, b=4, t=8):
    ids = rng.integers(0, vocab, (b, t))
    x = ids.astype(np.float32)
    y = np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)]
    return DataSet(x, y)


def test_gpt_trains(rng):
    net = _tiny_gpt()
    ds = _data(rng)
    s0 = net.score(ds)
    for _ in range(30):
        net.fit(ds)
    s1 = net.score(ds)
    assert np.isfinite(s1) and s1 < s0 * 0.7, (s0, s1)


def test_transformer_block_gradcheck(rng):
    """FD-vs-analytic on a block stack over continuous inputs (the
    framework's correctness oracle, GradientCheckUtil doctrine)."""
    conf = (NeuralNetConfiguration.builder().seed(5).learning_rate(0.1)
            .updater("sgd").activation("identity").weight_init("xavier")
            .list()
            .layer(TransformerBlock(n_in=8, n_out=8, num_heads=2, causal=True))
            .layer(RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    x = rng.standard_normal((2, 4, 8)).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 4))]
    assert check_gradients(net, DataSet(x, y))


def test_causality(rng):
    """Changing a future token must not change earlier logits."""
    net = _tiny_gpt()
    ids = rng.integers(0, 11, (1, 8))
    out1 = net.output(ids.astype(np.float32))
    ids2 = ids.copy()
    ids2[0, -1] = (ids2[0, -1] + 1) % 11
    out2 = net.output(ids2.astype(np.float32))
    np.testing.assert_allclose(out1[0, :-1], out2[0, :-1], rtol=1e-5, atol=1e-6)
    assert np.abs(out1[0, -1] - out2[0, -1]).max() > 1e-6


def test_seq_mesh_equivalence(rng):
    """Same params: single-chip flash output == DP×SP ring output."""
    devs = jax.devices()
    if len(devs) < 8:
        pytest.skip("needs 8 CPU devices")
    net = _tiny_gpt(d=16, layers=2, max_len=16)
    x = rng.integers(0, 11, (4, 8)).astype(np.float32)
    full = net.output(x)
    mesh = make_mesh({"data": 2, "seq": 4}, devices=devs[:8])
    with sequence_mesh(mesh):
        ringed = net.output(x)
    np.testing.assert_allclose(ringed, full, rtol=2e-4, atol=1e-5)


def test_moe_transformer_trains_and_gradchecks(rng):
    """Mixtral wiring: TransformerBlock with routed expert MLPs."""
    net = gpt(vocab_size=11, d_model=16, n_layers=2, num_heads=2,
              max_len=16, compute_dtype="float32", learning_rate=0.01,
              num_experts=4).init()
    ds = _data(rng)
    s0 = net.score(ds)
    for _ in range(30):
        net.fit(ds)
    assert np.isfinite(net.score(ds)) and net.score(ds) < s0
    # gradcheck a single MoE block over continuous input
    conf = (NeuralNetConfiguration.builder().seed(9).learning_rate(0.1)
            .updater("sgd").activation("identity").weight_init("xavier")
            .list()
            .layer(TransformerBlock(n_in=8, n_out=8, num_heads=2,
                                    causal=True, num_experts=2,
                                    capacity_factor=8.0))
            .layer(RnnOutputLayer(n_in=8, n_out=3, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    blk = MultiLayerNetwork(conf).init()
    x = (rng.standard_normal((2, 4, 8)) * 2.0).astype(np.float32)
    y = np.eye(3, dtype=np.float32)[rng.integers(0, 3, (2, 4))]
    assert check_gradients(blk, DataSet(x, y))


def test_bf16_policy_keeps_ids_exact(rng):
    """Regression: the mixed-precision input cast must not touch token
    ids — bf16(257) rounds to 256, silently swapping embeddings (and
    bf16(511) == 512 goes out of range). ids >= 256 must select their
    own rows under a bf16 compute policy."""
    net = gpt(vocab_size=512, d_model=16, n_layers=1, num_heads=2,
              max_len=8, compute_dtype="bfloat16", seed=3).init()
    a = net.output(np.full((1, 4), 257.0, np.float32))
    b = net.output(np.full((1, 4), 256.0, np.float32))
    c = net.output(np.full((1, 4), 511.0, np.float32))
    assert np.abs(a - b).max() > 1e-6, "id 257 collapsed onto 256"
    assert np.abs(c - b).max() > 1e-6, "id 511 corrupted"
    # and bf16 training through the scanned path stays finite
    ids = rng.integers(0, 512, (8, 8))
    ds = DataSet(ids.astype(np.float32),
                 np.eye(512, dtype=np.float32)[np.roll(ids, -1, 1)])
    scores = net.fit_scan(None, 4, epochs=1, staged=net.stage_scan(ds, 4))
    assert np.isfinite(scores).all()


def test_kv_cache_generate_matches_full_forward(rng):
    """Greedy generate() with KV caches must produce exactly the tokens
    the O(t²) full-window argmax loop produces."""
    from deeplearning4j_tpu.models.zoo.transformer import generate

    net = _tiny_gpt(vocab=11, d=16, layers=2, max_len=16)
    ds = _data(rng)
    for _ in range(10):
        net.fit(ds)
    prompt = rng.integers(0, 11, (2, 3))
    got = generate(net, prompt, max_new_tokens=8)

    # oracle: full forward per step
    want = np.array(prompt, np.int64)
    for _ in range(8):
        logits = net.output(want.astype(np.float32))
        nxt = np.argmax(logits[:, -1], axis=-1)
        want = np.concatenate([want, nxt[:, None]], axis=1)
    np.testing.assert_array_equal(got, want)


def test_generate_moe_and_sampling(rng):
    from deeplearning4j_tpu.models.zoo.transformer import generate

    net = gpt(vocab_size=11, d_model=16, n_layers=1, num_heads=2,
              max_len=12, compute_dtype="float32", num_experts=2).init()
    prompt = rng.integers(0, 11, (4, 2))  # b=4 > per-expert train capacity
    out = generate(net, prompt, max_new_tokens=4, temperature=1.0, seed=3)
    assert out.shape == (4, 6)
    assert (out >= 0).all() and (out < 11).all()
    # greedy decode is deterministic and the cached jits reproduce it
    g1 = generate(net, prompt, max_new_tokens=4)
    g2 = generate(net, prompt, max_new_tokens=4)
    np.testing.assert_array_equal(g1, g2)
    # the fused engine caches one prefill program (per cache length) and
    # one decode program (per max_new × sampler) on the net
    assert any(k[0] == "gen_prefill" for k in net._jits)
    assert ("gen_decode", 4, 0.0, 0, 0.0, None) in net._jits
    # top-k=1 sampling degenerates to greedy regardless of temperature
    g3 = generate(net, prompt, max_new_tokens=4, temperature=5.0, top_k=1)
    np.testing.assert_array_equal(g3, g1)
    # nucleus filter produces valid tokens
    g4 = generate(net, prompt, max_new_tokens=4, temperature=1.0, top_p=0.8)
    assert (g4 >= 0).all() and (g4 < 11).all()
    with pytest.raises(ValueError, match="max_len"):
        generate(net, prompt, max_new_tokens=100)


# ------------------------------------------ one block body, every entry point
#
# TransformerBlockImpl writes its wiring once (``_block``) and its entry
# points differ in where K/V are kept. The matrix holds each of them to
# ``forward`` on the same tokens, at block level.

B, T, D, HEADS, HD = 2, 7, 16, 2, 8
BS, MB, NB = 4, 3, 8                    # pool: block size, blocks a row, blocks
TABLE = [[3, 1, 5], [2, 6, 4]]          # distinct blocks, out of order on purpose
LAG = 2                                 # row 1 runs this many positions behind
POOLS = {"float": None, "int8": jnp.int8, "fp8": jnp.float8_e4m3fn}
PAGED = ("decode_paged", "prefill_paged")
ENTRIES = ("prefill", "decode_scalar", "decode_vector") + PAGED
#: |hidden - forward's|: the cached entry points run a plain softmax where
#: ``forward`` runs the flash kernels; a quantized pool adds its rounding
#: (the bounds tests/test_continuous.py and tests/test_quantize.py held)
TOL = {"float": 1e-5, "int8": 0.12, "fp8": 0.12}


def _block(kind):
    # capacity_factor = E: forward routes no-drop too, as serving always does
    moe = dict(num_experts=4, capacity_factor=4.0) if kind == "moe" else {}
    net = gpt(vocab_size=11, d_model=D, n_layers=1, num_heads=HEADS,
              max_len=16, compute_dtype="float32", seed=3, **moe).init()
    blk = net.impls[1]
    return blk, net.params[blk.name]


def _tokens():
    """The matrix's [B, T, D] inputs, the same in every case. Top-1 routing
    is a step function of the hidden state, so a quantized pool's rounding
    may carry a token that sits on a boundary to another expert (the rng
    fixture's first draw has one): this seed's tokens sit clear of them."""
    return jnp.asarray(np.random.default_rng(0).standard_normal((B, T, D)),
                       jnp.float32)


def _pool(kind, rng=None):
    """A layer's share of the paged pool; seeded garbage when ``rng``."""
    dt = POOLS[kind]
    shape = (NB, BS, HEADS, HD)
    fill = (lambda s: rng.standard_normal(s)) if rng is not None else np.zeros
    if dt is None:
        return {n: jnp.asarray(fill(shape), jnp.float32) for n in "kv"}
    pool = {n: jnp.asarray(fill(shape), jnp.float32).astype(dt) for n in "kv"}
    pool.update({n + "_scale": jnp.asarray(np.abs(fill(shape[:3])),
                                           jnp.float32) for n in "kv"})
    return pool


def _paged_step(blk, params, x_t, pool, pos, write_mask=None):
    y, cache = blk.decode_step(params, x_t, {**pool, "table": jnp.asarray(
        TABLE, jnp.int32)}, jnp.asarray(pos, jnp.int32), write_mask)
    return y, {n: cache[n] for n in pool}


def _hidden(entry, blk, params, x, pool_kind):
    """``entry`` driven over all of ``x`` [B, T, D] → (the hidden state it
    gave at every position [B, T, D], the K/V store it left)."""
    if entry == "prefill":
        return blk.prefill(params, x, blk.init_cache(B, MB * BS))
    if entry == "prefill_paged":
        table = jnp.asarray(TABLE, jnp.int32)
        # chunks of 4, 3 and 1 tokens: row 1's first ends in a padding
        # position, so it runs one behind and row 0 sits the last one out
        first = jnp.asarray([[0, 1, 2, 3], [0, 1, 2, 3]], jnp.int32)
        ok = jnp.asarray([[True] * 4, [True, True, True, False]])
        ya, pool = blk.prefill_paged(params, x[:, :4], _pool(pool_kind),
                                     table, first, ok)
        rest = jnp.asarray([[4, 5, 6], [3, 4, 5]], jnp.int32)
        tail = jnp.stack([x[0, 4:7], x[1, 3:6]])
        yb, pool = blk.prefill_paged(params, tail, pool, table, rest,
                                     jnp.ones((B, 3), bool))
        last, pool = blk.prefill_paged(
            params, x[:, 6:], pool, table, jnp.asarray([[6], [6]], jnp.int32),
            jnp.asarray([[False], [True]]))
        row1 = jnp.concatenate([ya[1, :3], yb[1], last[1]])
        return jnp.stack([jnp.concatenate([ya[0], yb[0]]), row1]), pool
    # one token a step; but for a scalar position, row 1 lags behind row 0
    # (it writes position 0 again until its turn: the same K/V)
    lag = 0 if entry == "decode_scalar" else LAG
    store = _pool(pool_kind) if entry == "decode_paged" \
        else blk.init_cache(B, MB * BS)
    out = np.zeros((B, T, D), np.float32)
    for step in range(T + lag):
        pos = [min(step, T - 1), min(max(step - lag, 0), T - 1)]
        x_t = jnp.stack([x[0, pos[0]], x[1, pos[1]]])
        if entry == "decode_paged":
            # both spellings of "every row writes"
            mask = None if step % 2 else jnp.ones(B, bool)
            y, store = _paged_step(blk, params, x_t, store, pos, mask)
        else:
            at = step if entry == "decode_scalar" else jnp.asarray(pos)
            y, store = blk.decode_step(params, x_t, store, at)
        out[0, pos[0]], out[1, pos[1]] = np.asarray(y)
    return out, store


def _gathered(pool, name):
    """Each row's blocks of a float pool in causal order [B, MB*BS, h, hd]."""
    return np.asarray(pool[name])[np.asarray(TABLE)].reshape(
        B, MB * BS, HEADS, HD)


@pytest.mark.parametrize("entry,kind,pool_kind", [
    (e, k, p) for e in ENTRIES for k in ("dense", "moe")
    for p in (POOLS if e in PAGED else ("float",))])
def test_entry_point_matches_forward(entry, kind, pool_kind):
    """The hidden state at every prefix position is ``forward``'s on the
    same tokens, wherever the entry point keeps K and V."""
    blk, params = _block(kind)
    x = _tokens()
    want = np.asarray(blk.forward(params, x, blk.init_state(), False)[0])
    got, store = _hidden(entry, blk, params, x, pool_kind)
    if entry == "prefill":
        # the same attention call and the same FFN: to the bit
        np.testing.assert_array_equal(np.asarray(got), want)
        assert not np.asarray(store["k"])[:, T:].any()
    else:
        np.testing.assert_allclose(np.asarray(got), want, rtol=TOL[pool_kind],
                                   atol=TOL[pool_kind])
    if pool_kind != "float":
        # the scatter's quantization is a pure function of what is written:
        # a replay leaves the same pool and the same hidden state, to the bit
        again, store2 = _hidden(entry, blk, params, x, pool_kind)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(again))
        for name in store:
            np.testing.assert_array_equal(np.asarray(store[name]),
                                          np.asarray(store2[name]))
    elif entry in PAGED:
        # the pool holds exactly the dense cache's rows, block-permuted
        _, dense = _hidden("decode_vector", blk, params, x, "float")
        for name in "kv":
            np.testing.assert_allclose(
                _gathered(store, name)[:, :T], np.asarray(dense[name])[:, :T],
                rtol=0, atol=0 if entry == "decode_paged" else 1e-6)


@pytest.mark.parametrize("entry", PAGED)
@pytest.mark.parametrize("pool_kind", POOLS)
def test_masked_rows_write_the_trash_block_only(rng, entry, pool_kind):
    """A position whose write is masked lands in block 0 and nowhere else:
    every other slot of the pool, its scales too, keeps its bits, and the
    unmasked positions' slots are written."""
    blk, params = _block("dense")
    before = _pool(pool_kind, rng)
    table = jnp.asarray(TABLE, jnp.int32)
    if entry == "decode_paged":
        x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
        _, after = _paged_step(blk, params, x, before, [5, 6],
                               jnp.asarray([True, False]))
        written = {(TABLE[0][5 // BS], 5 % BS)}
    else:
        x = jnp.asarray(rng.standard_normal((B, 3, D)), jnp.float32)
        pos = jnp.asarray([[4, 5, 6], [9, 10, 11]], jnp.int32)
        ok = jnp.asarray([[True, True, False], [False, False, False]])
        _, after = blk.prefill_paged(params, x, before, table, pos, ok)
        written = {(TABLE[0][1], 0), (TABLE[0][1], 1)}
    assert set(after) == set(before)
    for name in before:
        old = np.asarray(before[name]).astype(np.float32)
        new = np.asarray(after[name]).astype(np.float32)
        changed = {(int(blk_), int(off)) for blk_, off in
                   zip(*np.nonzero((old != new).reshape(NB, BS, -1).any(-1)))}
        assert written <= changed, name
        assert changed - written <= {(0, 0)}, name


@pytest.mark.parametrize("kind", ("dense", "moe"))
@pytest.mark.parametrize("pool_kind", POOLS)
def test_paged_decode_step_is_prefill_paged_at_one_token(rng, kind, pool_kind):
    """``decode_step`` over a block table and ``prefill_paged`` run one
    function: at ``t = 1`` they leave the same pool, to the bit, and give
    the same hidden state."""
    blk, params = _block(kind)
    table = jnp.asarray(TABLE, jnp.int32)
    step_pool = tail_pool = _pool(pool_kind)
    for pos in ([0, 0], [1, 0], [2, 1], [3, 2]):
        x = jnp.asarray(rng.standard_normal((B, D)), jnp.float32)
        mask = jnp.asarray([True, pos[1] != 0 or pos[0] == 0])
        y, step_pool = _paged_step(blk, params, x, step_pool, pos, mask)
        y2, tail_pool = blk.prefill_paged(
            params, x[:, None], tail_pool, table,
            jnp.asarray(pos, jnp.int32)[:, None], mask[:, None])
        np.testing.assert_array_equal(np.asarray(y), np.asarray(y2[:, 0]))
        for name in step_pool:
            np.testing.assert_array_equal(np.asarray(step_pool[name]),
                                          np.asarray(tail_pool[name]))


def test_embedding_rejects_overlong(rng):
    net = _tiny_gpt(max_len=8)
    with pytest.raises(ValueError, match="max_len"):
        net.output(rng.integers(0, 11, (1, 9)).astype(np.float32))


def test_block_validation():
    with pytest.raises(ValueError, match="divisible"):
        conf = (NeuralNetConfiguration.builder().seed(0).learning_rate(0.1)
                .updater("sgd").activation("identity")
                .list()
                .layer(TransformerBlock(n_in=10, n_out=10, num_heads=3))
                .layer(RnnOutputLayer(n_in=10, n_out=2, activation="softmax",
                                      loss_function="mcxent"))
                .build())
        MultiLayerNetwork(conf).init()


def test_serialization_roundtrip(rng, tmp_path):
    from deeplearning4j_tpu.util.model_serializer import (
        restore_model, write_model)
    net = _tiny_gpt()
    ds = _data(rng)
    net.fit(ds)
    path = str(tmp_path / "gpt.zip")
    write_model(net, path)
    net2 = restore_model(path)
    np.testing.assert_allclose(net.output(ds.features),
                               net2.output(ds.features), rtol=1e-6)
