"""Flash-attention Pallas kernels vs the XLA oracle.

The exact formulation in ``ops/attention.py`` is the correctness
oracle (same doctrine as ring attention); the kernels must match it in
forward AND gradients, causal and not, square and cross-length. There
are two sets of kernels, chosen from the shapes: ``resident`` (one
program a row, the block loop in the body) and ``streamed`` (the block
loop in the grid); the resident ones read folded [b*h, t, d] copies
(``resident``) or the projections' own [b, t, h*d] layout
(``resident_packed``: heads as 128-lane column blocks, two heads of 64 a
program). The cases name their path and reach it through the private
entry points; the public function's choice is tested apart.
"""

import importlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deeplearning4j_tpu.monitor import (
    FLASH_PATH_COUNTER, MetricsRegistry, set_registry)
from deeplearning4j_tpu.ops.attention import scaled_dot_product_attention
from deeplearning4j_tpu.ops.flash_attention import flash_attention

# the module itself: ``ops/__init__`` re-exports the function under its name
FA = importlib.import_module("deeplearning4j_tpu.ops.flash_attention")
ATTENTION = importlib.import_module("deeplearning4j_tpu.ops.attention")
PATHS = ("resident", "resident_packed", "streamed")


def _qkv(rng, b=2, tq=128, tk=128, h=2, d=64):
    mk = lambda t: jnp.asarray(
        rng.standard_normal((b, t, h, d)), jnp.float32)
    return mk(tq), mk(tk), mk(tk)


def _pairs(path, h, d):
    """The head count a case runs with: the packed layout wants whole
    128-lane column blocks, so narrow heads come in pairs or fours."""
    return max(h, 128 // d) if path == "resident_packed" else h


def _via(path, causal=False, block_q=None, block_k=None):
    """``flash_attention`` held to one path: the same folds around the
    path's private entry point, under the Pallas interpreter."""
    def attend(q, k, v):
        b, tq, h, d = q.shape
        fold = lambda z: z.transpose(0, 2, 1, 3).reshape(
            b * h, z.shape[1], d)
        if path == "resident_packed":
            assert FA.flash_path(tq, tq, d, q.dtype, heads=h) == path
            pack = lambda z: z.reshape(b, tq, h * d)
            return FA._flash_resident(pack(q), pack(k), pack(v), h, causal,
                                      True).reshape(b, tq, h, d)
        if path == "resident":
            o = FA._flash_resident(fold(q), fold(k), fold(v), 1, causal, True)
        else:
            bq = FA._pick_block(tq, block_q or 1024)
            bk = FA._pick_block(k.shape[1], block_k or 1024)
            o = FA._flash_streamed(fold(q), fold(k), fold(v), causal,
                                   bq, bk, True)
        return o.reshape(b, h, tq, d).transpose(0, 2, 1, 3)
    return attend


def _grads(attend, q, k, v):
    return jax.grad(lambda q, k, v: jnp.sum(attend(q, k, v) ** 2),
                    argnums=(0, 1, 2))(q, k, v)


@pytest.fixture
def small_blocks(monkeypatch):
    """Four in-body blocks at t = 512, so a CPU test walks a diagonal,
    full blocks and dead ones."""
    monkeypatch.setattr(FA, "_RESIDENT_BLOCK", 128)
    return 128


@pytest.fixture(params=["unrolled", "looped"])
def body(request, monkeypatch):
    """The resident kernels' two bodies: the block loop as straight-line
    code (rows up to 2k on the chip) or as loops (longer rows), here both
    at the test's length. The wrappers are ``jax.jit``s keyed on shapes, so
    their caches are dropped with the choice."""
    drop = lambda: [f.clear_cache() for f in (FA._resident_fwd,
                                              FA._resident_bwd)]
    if request.param == "looped":
        monkeypatch.setattr(FA, "_UNROLLED_ROWS", 0)
    drop()
    yield request.param
    drop()


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("causal", [False, True])
def test_matches_oracle(rng, causal, path):
    q, k, v = _qkv(rng)
    got = _via(path, causal)(q, k, v)
    want = scaled_dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("entry", ["public", "streamed"])
def test_cross_length_causal(rng, entry):
    """tq != tk exercises the diagonal offset (tril k=tk-tq). The rule
    keeps cross-length calls on the streamed kernels."""
    q, k, v = _qkv(rng, tq=64, tk=256)
    assert FA.flash_path(64, 256, 64, q.dtype) == "streamed"
    attend = (lambda q, k, v: flash_attention(q, k, v, causal=True)) \
        if entry == "public" else _via("streamed", True)
    got = attend(q, k, v)
    want = scaled_dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_multi_kblock_accumulation(rng):
    """Long keys force several online-softmax steps per q block: the
    streamed path, where a caller's blocks keep their meaning."""
    q, k, v = _qkv(rng, tq=32, tk=512, d=32)
    assert FA.flash_path(32, 512, 32, q.dtype) == "streamed"
    got = flash_attention(q, k, v, block_q=32, block_k=128)
    want = scaled_dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_backward_with_oversized_caller_blocks(rng):
    """A caller block > 512 that divides t while NO candidate <= 512
    does (t=1028 = 4·257: none of 512..8 divide it) must not
    ZeroDivisionError in the backward — it falls back to the forward
    block size. Streamed: 1028 does not split into in-body blocks."""
    t = 1028
    q, k, v = _qkv(rng, b=1, tq=t, tk=t, h=1, d=32)
    assert FA.flash_path(t, t, 32, q.dtype) == "streamed"

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, block_q=t, block_k=t) ** 2)

    g = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    gr = _grads(scaled_dot_product_attention, q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_oracle(rng, causal, path):
    q, k, v = _qkv(rng, b=1, tq=64, tk=64, h=_pairs(path, 1, 32), d=32)
    gf = _grads(_via(path, causal), q, k, v)
    gr = _grads(lambda q, k, v: scaled_dot_product_attention(
        q, k, v, causal=causal), q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("path", PATHS)
@pytest.mark.parametrize("d", [64, 128])
def test_causal_gradients_over_several_blocks(rng, small_blocks, path, d):
    """t = 4 blocks: a diagonal, full blocks below it and dead blocks
    above, in both the in-body loop and the grid."""
    t = 4 * small_blocks
    q, k, v = _qkv(rng, b=1, tq=t, tk=t, h=2, d=d)
    attend = _via(path, True, block_q=small_blocks, block_k=small_blocks)
    want = scaled_dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(attend(q, k, v)),
                               np.asarray(want), rtol=2e-5, atol=2e-5)
    gr = _grads(lambda q, k, v: scaled_dot_product_attention(
        q, k, v, causal=True), q, k, v)
    for a, b in zip(_grads(attend, q, k, v), gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def _kernel_calls(jaxpr, name):
    """How many ``pallas_call``s named ``name`` a jaxpr holds, at any depth."""
    from jax._src import core
    return sum(
        name == eqn.params["name"] if eqn.primitive.name == "pallas_call"
        else sum(_kernel_calls(sub, name)
                 for sub in core.jaxprs_in_params(eqn.params))
        for eqn in jaxpr.eqns)


@pytest.mark.parametrize("path", PATHS)
def test_a_checkpoint_that_keeps_the_named_residuals_runs_no_second_forward(
        rng, path):
    """Under a ``jax.checkpoint`` whose policy saves ``FLASH_RESIDUAL_NAMES``
    the backward pass reads the kept o and lse: the gradients are those of
    the call without a checkpoint, and the forward kernel is in the gradient's
    program once, where a plain checkpoint puts it twice."""
    q, k, v = _qkv(rng, b=1, tq=64, tk=64, h=_pairs(path, 1, 32), d=32)
    attend = _via(path, True)
    keeps = jax.checkpoint(attend, policy=jax.checkpoint_policies
                           .save_only_these_names(*FA.FLASH_RESIDUAL_NAMES))
    for got, want in zip(_grads(keeps, q, k, v), _grads(attend, q, k, v)):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    backward = lambda f: jax.make_jaxpr(
        lambda *a: _grads(f, *a))(q, k, v).jaxpr
    assert _kernel_calls(backward(attend), "flash_fwd") == 1
    assert _kernel_calls(backward(keeps), "flash_fwd") == 1
    assert _kernel_calls(backward(jax.checkpoint(attend)), "flash_fwd") == 2
    # outside a rule a name keeps nothing: the backward reads the rule's own
    # residuals, not the caller's copy of the output
    outside = jax.checkpoint(
        lambda *a: jax.ad_checkpoint.checkpoint_name(attend(*a), "o"),
        policy=jax.checkpoint_policies.save_only_these_names("o"))
    assert _kernel_calls(backward(outside), "flash_fwd") == 2


@pytest.mark.parametrize("heads", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_paths_agree(rng, small_blocks, body, causal, heads):
    """The two sets of kernels on the same inputs: o, lse, dq, dk, dv to
    float32 tolerance (the sums run in another order). ``heads`` = 1 hands
    the resident body the folded rows, 2 the same rows packed two to a
    128-lane column block."""
    t, d = 4 * small_blocks, 64
    q, k, v, g = (jnp.asarray(rng.standard_normal((4, t, d)), jnp.float32)
                  for _ in range(4))
    # [b*h, t, d] -> [b, t, h*d] and back
    pack = lambda z: z.reshape(-1, heads, t, d).transpose(0, 2, 1, 3).reshape(
        -1, t, heads * d)
    fold = lambda z: z.reshape(-1, t, heads, d).transpose(0, 2, 1, 3).reshape(
        -1, t, d)
    o_s, lse_s = FA._flash_fwd_impl(q, k, v, causal, small_blocks,
                                    small_blocks, True)
    o_r, lse_r = FA._resident_fwd(pack(q), pack(k), pack(v), (0, 0, 0), heads,
                                  d, causal, small_blocks, True)
    # the same numbers, the resident one as the rows the backward reads
    assert lse_r.shape == (4 // heads, heads, 1, t) and lse_s.shape == (4, t, 1)
    np.testing.assert_allclose(np.asarray(fold(o_r)), np.asarray(o_s),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_r).reshape(4, t),
                               np.asarray(lse_s)[..., 0],
                               rtol=2e-5, atol=2e-5)
    got = FA._resident_bwd(pack(q), pack(k), pack(v), pack(o_s), lse_r,
                           pack(g), (0, 0, 0), heads, causal, small_blocks,
                           True)
    want = FA._flash_bwd_impl(q, k, v, o_s, lse_s, g, causal, small_blocks,
                              small_blocks, True)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(fold(a)), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("h,d", [(1, 128), (2, 64)])
def test_the_4k_row_agrees_with_the_streamed_kernels_and_the_oracle(rng, h, d):
    """One row of 4096 at the in-body block the chip runs (8 diagonal blocks
    of 36 live ones: the looped bodies), a head of 128 and a pair of 64 in
    one program: o and lse against the streamed forward, dq, dk, dv against
    the streamed pair and against the XLA formulation's gradients."""
    t = 4096
    q, k, v, g = (jnp.asarray(rng.standard_normal((1, t, h, d)), jnp.float32)
                  for _ in range(4))
    assert FA.flash_path(t, t, d, jnp.bfloat16, heads=h) == "resident_packed"
    pack = lambda z: z.reshape(1, t, h * d)
    fold = lambda z: z.transpose(0, 2, 1, 3).reshape(h, t, d)
    unfold = lambda z: np.asarray(z).reshape(h, t, d).transpose(1, 0, 2) \
        .reshape(1, t, h * d)
    block = FA._resident_block(t)
    assert block == 512 and t > FA._UNROLLED_ROWS
    o_r, lse_r = FA._resident_fwd(pack(q), pack(k), pack(v), (0, 0, 0), h, d,
                                  True, block, True)
    o_s, lse_s = FA._flash_fwd_impl(fold(q), fold(k), fold(v), True, 1024,
                                    1024, True)
    np.testing.assert_allclose(np.asarray(o_r), unfold(o_s),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_r).reshape(h, t),
                               np.asarray(lse_s)[..., 0],
                               rtol=2e-5, atol=2e-5)
    got = FA._resident_bwd(pack(q), pack(k), pack(v), o_r, lse_r, pack(g),
                           (0, 0, 0), h, True, block, True)
    streamed = FA._flash_bwd_impl(fold(q), fold(k), fold(v), o_s, lse_s,
                                  fold(g), True, 512, 512, True)
    oracle = jax.grad(lambda q, k, v: jnp.sum(scaled_dot_product_attention(
        q, k, v, causal=True) * g), (0, 1, 2))(q, k, v)
    for a, b, c in zip(got, streamed, oracle):
        np.testing.assert_allclose(np.asarray(a), unfold(b),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(np.asarray(a), np.asarray(pack(c)),
                                   rtol=2e-4, atol=2e-4)


def _fused(q, k, v):
    b, t, h, d = q.shape
    return jnp.concatenate([z.reshape(b, t, h * d) for z in (q, k, v)], -1)


@pytest.mark.parametrize("h,d", [(2, 64), (4, 64), (1, 128), (2, 128),
                                 (4, 32), (3, 64)])
@pytest.mark.parametrize("causal", [False, True])
def test_fused_qkv_entry_matches_split_and_fold(rng, small_blocks, body,
                                                causal, h, d):
    """``flash_attention_qkv`` on the projection's own [b, t, 3*h*d] array
    (q, k, v read at their column offsets, dq, dk, dv written at them)
    against ``jnp.split`` + the folded path: the same o and the same dqkv,
    over several in-body blocks. Three heads of 64 do not pair: there the
    entry IS the split and the fold."""
    t = 2 * small_blocks
    q, k, v = _qkv(rng, b=2, tq=t, tk=t, h=h, d=d)
    packed = FA.flash_path(t, t, d, q.dtype, heads=h) == "resident_packed"
    assert packed == (h != 3)
    attend = lambda qkv: FA.flash_attention_qkv(qkv, h, causal=causal)

    def folded(qkv):
        q, k, v = (z.reshape(2, t, h, d) for z in jnp.split(qkv, 3, axis=-1))
        return _via("resident", causal)(q, k, v).reshape(2, t, h * d)
    qkv = _fused(q, k, v)
    np.testing.assert_allclose(np.asarray(attend(qkv)),
                               np.asarray(folded(qkv)), rtol=2e-5, atol=2e-5)
    grad = lambda f: jax.grad(lambda z: jnp.sum(f(z) ** 2))(qkv)
    np.testing.assert_allclose(np.asarray(grad(attend)),
                               np.asarray(grad(folded)), rtol=2e-4, atol=2e-4)
    want = scaled_dot_product_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(attend(qkv)).reshape(want.shape),
                               np.asarray(want), rtol=2e-5, atol=2e-5)


def test_fused_qkv_entry_in_bf16(rng):
    q, k, v = _qkv(rng, h=4)
    got = FA.flash_attention_qkv(_fused(q, k, v).astype(jnp.bfloat16), 4,
                                 causal=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (2, 128, 256)
    want = scaled_dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(got, np.float32).reshape(want.shape),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


@pytest.mark.parametrize("path", PATHS)
def test_bf16_inputs(rng, path):
    q, k, v = _qkv(rng)
    got = _via(path)(q.astype(jnp.bfloat16), k.astype(jnp.bfloat16),
                     v.astype(jnp.bfloat16))
    assert got.dtype == jnp.bfloat16
    want = scaled_dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), rtol=3e-2, atol=3e-2)


def test_mask_falls_back(rng):
    """Key-validity masks take the XLA path — results must still match."""
    q, k, v = _qkv(rng, b=2, tq=16, tk=16)
    mask = np.ones((2, 16), np.float32)
    mask[:, 10:] = 0.0
    got = flash_attention(q, k, v, mask=jnp.asarray(mask))
    want = scaled_dot_product_attention(q, k, v, mask=jnp.asarray(mask))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-6, atol=1e-6)


def test_odd_lengths_fall_back(rng):
    q, k, v = _qkv(rng, tq=17, tk=23, d=16)
    got = flash_attention(q, k, v)
    want = scaled_dot_product_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


def test_jit_and_under_vmap(rng):
    """Through the public function: a short self-attention call, so the
    resident kernels."""
    q, k, v = _qkv(rng, b=1, tq=32, tk=32, d=32)
    assert FA.flash_path(32, 32, 32, q.dtype) == "resident"
    jitted = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    np.testing.assert_allclose(
        np.asarray(jitted(q, k, v)),
        np.asarray(scaled_dot_product_attention(q, k, v, causal=True)),
        rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("tq,tk,d,dtype,want", [
    (1024, 1024, 64, jnp.bfloat16, "resident"),    # gpt2-medium.pretrain-1k
    (2048, 2048, 128, jnp.bfloat16, "resident"),   # cerebras-gpt-590m...-2k
    (256, 256, 64, jnp.bfloat16, "resident"),
    (4096, 4096, 128, jnp.bfloat16, "resident"),   # ouro-2.6b...-looped-4k
    (8192, 8192, 128, jnp.bfloat16, "streamed"),   # over the VMEM budget
    (16384, 16384, 128, jnp.bfloat16, "streamed"),
    (32768, 32768, 128, jnp.bfloat16, "streamed"),
    (2048, 2048, 128, jnp.float32, "resident"),    # 28.2 MiB of the 38.4
    (3072, 3072, 128, jnp.float32, "streamed"),    # 39.3 MiB
    (4096, 4096, 128, jnp.float32, "streamed"),    # 50.4 MiB
    (64, 256, 64, jnp.bfloat16, "streamed"),       # tq < tk, the serving tail
    (1032, 1032, 64, jnp.bfloat16, "streamed"),    # 8 x 129: no block >= 128
])
def test_path_is_a_pure_function_of_shapes(tq, tk, d, dtype, want):
    assert FA.flash_path(tq, tk, d, dtype) == want
    assert FA.flash_path(tq, tk, d, jnp.dtype(dtype)) == want  # and again


@pytest.mark.parametrize("t,d,itemsize,want", [
    # the backward's, as before the forward was counted: the GPT cells'
    # kernels ask Mosaic for twice these
    (256, 64, 2, 3_235_840),
    (1024, 64, 2, 12_943_360),
    (2048, 128, 2, 19_595_264),
    (2048, 128, 4, 29_556_736),
    (4096, 128, 2, 32_899_072),    # 31.4 MiB of the 38.4
    (4096, 64, 2, 32_899_072),     # a pair of heads shares the 128 lanes
])
def test_vmem_count_is_the_larger_kernels(t, d, itemsize, want):
    """The backward's at every length that fits, and so the scoped limit in
    the lowered kernels is what it was: a forward that met the whole key
    row at once would pass it at 4k (36.3 MiB), the looped one meets a block
    at a time."""
    assert FA._resident_bytes(t, d, itemsize) == want
    assert 2 * want < FA._VMEM_CORE


@pytest.mark.parametrize("t,tk,h,d,want", [
    (1024, 1024, 16, 64, "resident_packed"),  # gpt2-medium.pretrain-1k
    (256, 256, 16, 64, "resident_packed"),    # gpt2-medium.finetune-256
    (2048, 2048, 12, 128, "resident_packed"),  # cerebras-gpt-590m...-2k
    (1024, 1024, 8, 32, "resident_packed"),   # four heads a program
    (1024, 1024, 2, 256, "resident_packed"),  # a head of two tiles
    (1024, 1024, 3, 64, "resident"),          # three heads do not pair
    (1024, 1024, 1, 64, "resident"),
    (1024, 1024, 4, 96, "resident"),          # 96 divides no tile
    (1024, 1024, 0, 64, "resident"),          # folded rows: no head count
    (64, 256, 16, 64, "streamed"),            # cross-length
    (4096, 4096, 32, 64, "resident_packed"),  # granite...-4k's one layer
    (4096, 4096, 16, 128, "resident_packed"),  # ouro-2.6b...-looped-4k
    (4096, 4096, 3, 64, "resident"),
    (8192, 8192, 16, 128, "streamed"),
    (16384, 16384, 8, 128, "streamed"),
])
def test_packed_layout_is_chosen_from_the_shapes(t, tk, h, d, want):
    """The projections' own layout where the heads are whole 128-lane
    column blocks of it, the folded copies where they are not, and nothing
    new for what the streamed kernels serve."""
    assert FA.flash_path(t, tk, d, jnp.bfloat16, heads=h) == want


@pytest.fixture
def registry():
    mine = MetricsRegistry()
    previous = set_registry(mine)
    yield mine
    set_registry(previous)


@pytest.mark.parametrize("tq,tk,h,path", [(64, 64, 1, "resident"),
                                          (64, 64, 4, "resident_packed"),
                                          (64, 64, 12, "fused"),
                                          (64, 256, 1, "streamed")])
def test_counter_ticks_once_a_traced_call(rng, registry, tq, tk, h, path):
    """The choice is made while tracing: one tick a trace with the path's
    label, none for a call the compiled program serves. The fused entry
    counts as the public one does."""
    q, k, v = _qkv(rng, b=1, tq=tq, tk=tk, h=h, d=32)
    count = lambda p: registry.counter(FLASH_PATH_COUNTER, path=p).value
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
    if path == "fused":
        path = "resident_packed"
        f = jax.jit(lambda q, k, v: FA.flash_attention_qkv(
            _fused(q, k, v), h, causal=True))
    f(q, k, v)
    assert count(path) == 1
    f(q, k, v)  # no new trace
    assert count(path) == 1
    assert sum(count(p) for p in PATHS) == 1


def _kernel_names(t, d, heads=2):
    """Mosaic kernels in the lowering for the TPU of a causal training
    call (no chip and no TPU compiler needed to lower)."""
    x = jax.ShapeDtypeStruct((1, t, heads, d), jnp.bfloat16)
    loss = lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, interpret=False).astype(jnp.float32))
    with jax.enable_x64(False):  # the suite's x64 is not the chip's setting
        text = jax.jit(jax.grad(loss, (0, 1, 2))).trace(x, x, x).lower(
            lowering_platforms=("tpu",)).as_text()
    return sorted(re.findall(r'kernel_name = "(\w+)"', text))


def test_long_context_lowers_to_the_streamed_kernels():
    """16k / head 128 reaches the code it reached before the resident
    kernels existed; a cell's shape does not."""
    assert _kernel_names(16384, 128) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert _kernel_names(8192, 128) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert _kernel_names(1024, 64) == ["flash_dq_dkv", "flash_fwd"]


@pytest.mark.parametrize("d", [128, 64])
def test_the_4k_row_lowers_to_the_resident_kernels(d):
    """The looped cell's calls (heads of 128) and the hybrid cell's one
    attention layer (pairs of 64) at 4096."""
    assert _kernel_names(4096, d) == ["flash_dq_dkv", "flash_fwd"]


def _block_step_text(monkeypatch, block, b, t, width,
                     kernels=("flash_dq_dkv", "flash_fwd")):
    """The training step of one block on [b, t, width] in bfloat16, lowered
    for the TPU with the kernels as Mosaic calls: the resident pair (and
    what else the block names in ``kernels``), and no transpose but of the
    weight gradients (two-dimensional), so no head fold stands anywhere in
    the step."""
    monkeypatch.setattr(FA, "pallas_interpret", lambda: False)
    monkeypatch.setattr(ATTENTION, "pallas_interpret", lambda: False)
    bf16 = lambda p: jax.ShapeDtypeStruct(p.shape, jnp.bfloat16)
    params = jax.tree.map(bf16, jax.eval_shape(block.init_params,
                                               jax.random.key(0)))
    x = jax.ShapeDtypeStruct((b, t, width), jnp.bfloat16)

    def loss(params, x):
        out, _ = block.forward(params, x, {}, True)
        return jnp.sum(out.astype(jnp.float32))
    with jax.enable_x64(False):  # the suite's x64 is not the chip's setting
        text = jax.jit(jax.grad(loss, (0, 1))).trace(params, x).lower(
            lowering_platforms=("tpu",)).as_text()
    assert sorted(re.findall(r'kernel_name = "(\w+)"', text)) == sorted(
        kernels)
    for shape in re.findall(r"stablehlo\.transpose .*?\(tensor<([\dx]+)x\w+>\)",
                            text):
        assert shape.count("x") == 1, shape  # a weight gradient's
    made = dict(re.findall(r"(%\d+)(?::\d+)? = (?:stablehlo\.|call @)(\w+)",
                           text))
    return text, made


@pytest.mark.parametrize("b,h,t,d", [
    (8, 16, 1024, 64),    # gpt2-medium.pretrain-1k
    (32, 16, 256, 64),    # gpt2-medium.finetune-256
    (2, 12, 2048, 128),   # cerebras-gpt-590m.pretrain-2k
])
def test_gpt_block_step_hands_the_projections_to_the_kernels(
        monkeypatch, b, h, t, d):
    """The training step of one GPT block at a cell's shapes, lowered for
    the TPU: ``qkv_proj``'s product goes into ``flash_fwd`` as it is, three
    times; the kernel's o goes into ``attn_out_proj``'s product; the
    backward kernel takes ``attn_out_proj``'s gradient and its one
    [b, t, 3*h*d] result goes into ``qkv_proj``'s two gradient products. No
    slice, no concatenation, and no transpose but of the weight gradients
    (two-dimensional) stands anywhere in the step."""
    from deeplearning4j_tpu.models.zoo.transformer import gpt

    block = gpt(vocab_size=64, d_model=h * d, n_layers=1, num_heads=h,
                max_len=t).impls[1]
    text, made = _block_step_text(monkeypatch, block, b, t, h * d)
    assert "stablehlo.slice" not in text
    assert "stablehlo.concatenate" not in text
    fwd = re.search(r"(%\d+):2 = call @_resident_fwd\((%\d+), (%\d+), "
                    r"(%\d+)\)", text)
    o, *qkv = fwd.groups()
    assert len(set(qkv)) == 1 and made[qkv[0]] == "dot_general"
    assert f"stablehlo.dot_general {o}#0, " in text
    bwd = re.search(r"(%\d+) = call @_resident_bwd\((%\d+), (%\d+), (%\d+), "
                    + f"{o}#0, {o}#1, " + r"(%\d+)\)", text)
    dqkv, *operands, g = bwd.groups()
    assert operands == qkv and made[g] == "dot_general"
    users = re.findall(r"stablehlo\.(\w+) [^\n=]*" + dqkv + r"\b", text)
    assert users == ["dot_general", "dot_general"], users


def test_looped_block_step_hands_q_k_v_to_the_kernels_as_they_lie(
        monkeypatch):
    """The training step of one looped-LM block at its cell's shapes (2 rows
    of 4096, 16 heads of 128, rotary, as many key/value heads), lowered for
    the TPU: what ``rope`` and ``kv_repeat`` make goes into ``flash_fwd`` by
    reshapes alone, the kernel's o into ``attn_out_proj``'s product, and the
    backward is the one kernel: no transpose but of the weight gradients
    (two-dimensional) stands anywhere in the step, so no head fold does.
    ``rope`` is one pass over q and one over k on [b, t, h * d], forward
    and backward: between ``qkv_proj``'s products and the kernels, and
    between the kernel's gradients and the weight-gradient products, a
    [b, t, h, d] value is only ever reshaped (but for ``kv_repeat``'s
    transpose, a sum over the one copy of each head)."""
    from deeplearning4j_tpu.models.zoo.looped_lm import looped_lm

    b, t, h, d = 2, 4096, 16, 128
    block = looped_lm(dict(
        hidden_size=h * d, vocab_size=64, num_hidden_layers=1, total_ut_steps=1,
        num_attention_heads=h, num_key_value_heads=h, head_dim=d,
        intermediate_size=256, rms_norm_eps=1e-6, rope_theta=1e6)).impls[1]
    whole, _ = _block_step_text(
        monkeypatch, block, b, t, h * d,
        kernels=["flash_dq_dkv", "flash_fwd"] + ["rotary_turn"] * 2)
    # value names start again in every function: follow them in the step's
    # own body, where ``rope``, the kernels and the products are called
    text = whole[:whole.index("func.func private")]
    made = dict(re.findall(r"(%\d+)(?::\d+)? = (?:stablehlo\.|call @)(\w+)",
                           text))
    source = dict(re.findall(r"(%\d+) = stablehlo\.reshape (%\d+) ", text))
    fwd = re.search(r"(%\d+):2 = call @_resident_fwd\((%\d+), (%\d+), "
                    r"(%\d+)\)", text)
    o, *qkv = fwd.groups()
    assert len(set(qkv)) == 3
    for z in qkv:  # [b, t, h, d] -> [b, t, h * d]: a reshape of what was made
        assert made[z] == "reshape" and made[source[z]] != "transpose", z
    bwd = re.search(r"(%\d+):3 = call @_resident_bwd\((%\d+), (%\d+), "
                    + r"(%\d+), " + f"{o}#0, {o}#1, " + r"(%\d+)\)", text)
    assert bwd and list(bwd.groups()[1:4]) == qkv
    # q and k: the product, the pass, the kernel, with reshapes between (and
    # for k the one copy ``kv_repeat`` makes of each head)
    source.update(re.findall(
        r"(%\d+) = stablehlo\.(?:reshape |broadcast_in_dim |reduce\()"
        r"(%\d+(?:#\d+)?)[ ,]", text))
    passed = dict(re.findall(
        r"(%\d+) = call @_rotate\w*\((%\d+)\) : \(tensor<2x4096x2048xbf16>\)"
        r" -> tensor<2x4096x2048xbf16>", text))

    def origin(z):
        while z in source:
            z = source[z]
        return z
    turned = [origin(z) for z in qkv[:2]]
    assert made[turned[0]] == made[turned[1]] == "_rotate"
    assert [made[origin(passed[z])] for z in turned] == ["dot_general"] * 2
    # their gradients: the kernel, the pass, the two gradient products
    back = {origin(arg): z for z, arg in passed.items() if z not in turned}
    assert sorted(back) == [f"{bwd.group(1)}#{i}" for i in (0, 1)]
    assert len({made[z] for z in back.values()}) == 1
    users = [origin(z) for z in re.findall(
        r"stablehlo\.dot_general (%\d+), ", text)]
    for z in back.values():
        assert users.count(z) == 2, z
    # and nothing but a reshape makes a [b, t, h, d] value in any function
    # of the step (the two sums are ``kv_repeat``'s transpose, over the one
    # copy of a head)
    rank4 = re.findall(r"= (?:stablehlo\.|call @)(\w+)[^\n]*"
                       r"-> tensor<2x4096x16x128x\w+>\n", whole)
    assert set(rank4) == {"reshape", "reduce"} and rank4.count("reduce") == 2
