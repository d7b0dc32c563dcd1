"""Flash-attention Pallas kernels vs the XLA oracle.

The exact formulation in ``ops/attention.py`` is the correctness
oracle (same doctrine as ring attention); the kernels must match it in
forward AND gradients, causal and not, square and cross-length. There
are two sets of kernels, chosen from the shapes: ``resident`` (one
program a row, the block loop in the body) and ``streamed`` (the block
loop in the grid). The cases name their path and reach it through the
two private entry points; the public function's choice is tested apart.
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
PATHS = ("resident", "streamed")


def _qkv(rng, b=2, tq=128, tk=128, h=2, d=64):
    mk = lambda t: jnp.asarray(
        rng.standard_normal((b, t, h, d)), jnp.float32)
    return mk(tq), mk(tk), mk(tk)


def _via(path, causal=False, block_q=None, block_k=None):
    """``flash_attention`` held to one path: the same folds around the
    path's private entry point, under the Pallas interpreter."""
    def attend(q, k, v):
        b, tq, h, d = q.shape
        fold = lambda z: z.transpose(0, 2, 1, 3).reshape(
            b * h, z.shape[1], d)
        if path == "resident":
            o = FA._flash_resident(fold(q), fold(k), fold(v), causal, True)
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
    q, k, v = _qkv(rng, b=1, tq=64, tk=64, h=1, d=32)
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


@pytest.mark.parametrize("causal", [False, True])
def test_paths_agree(rng, small_blocks, causal):
    """The two sets of kernels on the same folded inputs: o, lse, dq, dk,
    dv to float32 tolerance (the sums run in another order)."""
    t, d = 4 * small_blocks, 64
    q, k, v, g = (jnp.asarray(rng.standard_normal((3, t, d)), jnp.float32)
                  for _ in range(4))
    o_r, lse_r = FA._resident_fwd(q, k, v, causal, small_blocks, True)
    o_s, lse_s = FA._flash_fwd_impl(q, k, v, causal, small_blocks,
                                    small_blocks, True)
    # the same numbers, the resident one as the row the backward reads
    assert lse_r.shape == (3, 1, t) and lse_s.shape == (3, t, 1)
    np.testing.assert_allclose(np.asarray(o_r), np.asarray(o_s),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lse_r)[:, 0], np.asarray(lse_s)[..., 0],
                               rtol=2e-5, atol=2e-5)
    got = FA._resident_bwd(q, k, v, o_s, lse_r, g, causal, small_blocks, True)
    want = FA._flash_bwd_impl(q, k, v, o_s, lse_s, g, causal, small_blocks,
                              small_blocks, True)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


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
    (4096, 4096, 128, jnp.bfloat16, "streamed"),   # over the VMEM budget
    (16384, 16384, 128, jnp.bfloat16, "streamed"),
    (32768, 32768, 128, jnp.bfloat16, "streamed"),
    (2048, 2048, 128, jnp.float32, "resident"),    # 23.3 MiB of the 24
    (3072, 3072, 128, jnp.float32, "streamed"),
    (64, 256, 64, jnp.bfloat16, "streamed"),       # tq < tk, the serving tail
    (1032, 1032, 64, jnp.bfloat16, "streamed"),    # 8 x 129: no block >= 128
])
def test_path_is_a_pure_function_of_shapes(tq, tk, d, dtype, want):
    assert FA.flash_path(tq, tk, d, dtype) == want
    assert FA.flash_path(tq, tk, d, jnp.dtype(dtype)) == want  # and again


@pytest.fixture
def registry():
    mine = MetricsRegistry()
    previous = set_registry(mine)
    yield mine
    set_registry(previous)


@pytest.mark.parametrize("tq,tk,path", [(64, 64, "resident"),
                                        (64, 256, "streamed")])
def test_counter_ticks_once_a_traced_call(rng, registry, tq, tk, path):
    """The choice is made while tracing: one tick a trace with the path's
    label, none for a call the compiled program serves."""
    q, k, v = _qkv(rng, b=1, tq=tq, tk=tk, h=1, d=32)
    count = lambda p: registry.counter(FLASH_PATH_COUNTER, path=p).value
    f = jax.jit(lambda q, k, v: flash_attention(q, k, v, causal=True))
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
    """16k / head 128 (bench.py's long-context path) reaches the code it
    reached before the resident kernels existed; a cell's shape does not."""
    assert _kernel_names(16384, 128) == ["flash_dkv", "flash_dq", "flash_fwd"]
    assert _kernel_names(1024, 64) == ["flash_dq_dkv", "flash_fwd"]
