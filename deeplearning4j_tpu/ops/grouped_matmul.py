"""Grouped matrix products over rows sorted by group: the experts of a
mixture-of-experts layer.

``lhs`` [rows, k] holds the rows of every group one after the other: group
``g`` holds rows ``[offsets[g], offsets[g + 1])`` with ``offsets`` the running
sum of ``group_sizes``, and the rows past the last group belong to none. The
buffer's length is static and sized for the worst case; how many of its rows
are in groups is known only on the device.

    grouped_matmul(lhs, rhs, group_sizes)[r] = lhs[r] @ rhs[g(r)]

Two ways through it, chosen by ``moe_path`` from the shapes and the backend
(no argument, option or environment variable; the expert layer counts the
choice once a traced call in ``dl4j_moe_path_total{path=}``):

**gmm** - Pallas kernels over tiles of ``TILE_ROWS`` rows: ``gmm_fwd`` (a row
times its group's matrix), ``gmm_dx`` (the same with the matrices transposed:
the gradient of ``lhs``) and ``gmm_dw`` (a group's rows transposed times
their incoming gradient: the gradient of ``rhs``). The grid's row axis is a
list of VISITS, one a (tile, group) pair that meet (a tile that two groups
share is visited twice, each visit storing its group's rows), made on the
device from the group sizes and prefetched into SMEM with the offsets. The
number of visits is a traced value and sizes the grid: a tile past the last
group's end is never visited, so the kernels' time follows the rows in
groups, not the buffer. ``gmm_dw`` visits an empty group once, to write its
zeros. bfloat16 operands, float32 accumulation; the contraction is whole in a
block.

**xla** - ``jax.lax.ragged_dot``: on the CPU and where the kernels' tiles do
not divide the shapes.

Rows that belong to no group are NOT defined in what the kernels write: the
tile that holds the last group's end has them as zeros, and the tiles after
it are never written. A reader takes them out with a select, never a product
(a product with zero keeps a NaN).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.device import pallas_interpret

#: rows of a tile: a group boundary inside a tile makes the tile twice, so
#: a smaller tile wastes less at ~1k rows a group and a larger one does more
#: work a grid step
TILE_ROWS = 256
#: the widest output block a program writes (lanes)
_WIDEST = 1024
_LANES = 128
#: VMEM a call may use: a [256, 3072] row block and a [3072, 1024] block of
#: matrices, double-buffered, are 15 MiB
_VMEM_LIMIT = 64 * 2 ** 20


def moe_path(rows: int, k: int, n: int) -> str:
    """Which way ``grouped_matmul`` takes for ``[rows, k] x [groups, k, n]``
    products (and their transposes): "gmm" where the rows split into whole
    tiles and both widths into whole lane blocks, off the CPU; "xla"
    otherwise."""
    fits = rows % TILE_ROWS == 0 and k % _LANES == 0 and n % _LANES == 0
    return "gmm" if fits and not pallas_interpret() else "xla"


def _block(width: int) -> int:
    """The widest multiple of 128 lanes, up to ``_WIDEST``, that divides
    ``width`` (the whole width where none does: the interpreter's tiny
    shapes)."""
    return max((b for b in range(_LANES, min(width, _WIDEST) + 1, _LANES)
                if width % b == 0), default=width)


def visits(group_sizes, rows: int, tm: int, empty: bool):
    """``(offsets [G + 1], group [V], tile [V], count)``: the (group, tile)
    pairs in group order, each group's tiles in row order (so a tile's
    visits are consecutive), ``count`` of them real and the rest repeating
    the last. ``empty``: an empty group is visited once, at the tile where
    it would start. ``V = rows // tm + G - 1`` bounds the count."""
    g = group_sizes.shape[0]
    tiles_m = rows // tm
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    met = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1,
                    int(empty))
    upto = jnp.cumsum(met)
    v = jnp.arange(tiles_m + g - 1, dtype=jnp.int32)
    count = upto[-1]
    v = jnp.minimum(v, jnp.maximum(count - 1, 0))
    group = jnp.minimum(jnp.searchsorted(upto, v, side="right"), g - 1)
    tile = jnp.minimum(first[group] + v - (upto - met)[group], tiles_m - 1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends])
    return offsets, group.astype(jnp.int32), tile.astype(jnp.int32), count


def _rows_in(offsets, g, i, tm, shape):
    """Which rows of tile ``i``'s block belong to group ``g``."""
    rows = i * tm + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= offsets[g]) & (rows < offsets[g + 1])


def _gmm_kernel(offsets, group, tile, lhs_ref, rhs_ref, out_ref, *, tm,
                transpose_rhs):
    v = pl.program_id(1)
    g, i = group[v], tile[v]

    @pl.when((v == 0) | (tile[jnp.maximum(v - 1, 0)] != i))
    def _first_visit_of_the_tile():
        out_ref[...] = jnp.zeros_like(out_ref)

    dims = (((1,), (1,)) if transpose_rhs else ((1,), (0,))), ((), ())
    acc = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], dims,
                              preferred_element_type=jnp.float32)
    inside = _rows_in(offsets, g, i, tm, acc.shape)
    out_ref[...] = jnp.where(inside, acc, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


def gmm(lhs, rhs, group_sizes, *, transpose_rhs: bool = False,
        interpret: bool = None):
    """``lhs`` [rows, k] x ``rhs`` [G, k, n] (or [G, n, k] with
    ``transpose_rhs``) -> [rows, n] in ``lhs``'s dtype, by the Pallas kernel
    ``gmm_fwd`` (``gmm_dx`` transposed). ``interpret``: under the Pallas
    interpreter (None: on the CPU backend)."""
    rows, k = lhs.shape
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    tm, tn = TILE_ROWS, _block(n)
    offsets, group, tile, count = visits(group_sizes, rows, tm, empty=False)
    rhs_spec = (pl.BlockSpec((None, tn, k), lambda j, v, o, g, t: (g[v], j, 0))
                if transpose_rhs else
                pl.BlockSpec((None, k, tn), lambda j, v, o, g, t: (g[v], 0, j)))
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        out_shape=jax.ShapeDtypeStruct((rows, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(n // tn, count),
            in_specs=[pl.BlockSpec((tm, k), lambda j, v, o, g, t: (t[v], 0)),
                      rhs_spec],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, o, g, t: (t[v], j))),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="gmm_dx" if transpose_rhs else "gmm_fwd",
        interpret=pallas_interpret() if interpret is None else interpret,
    )(offsets, group, tile, lhs, rhs)


def _tgmm_kernel(offsets, group, tile, lhs_ref, dy_ref, out_ref, acc_ref, *,
                 tm, last):
    v = pl.program_id(2)
    g, i = group[v], tile[v]

    @pl.when((v == 0) | (group[jnp.maximum(v - 1, 0)] != g))
    def _first_visit_of_the_group():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(offsets[g + 1] > offsets[g])
    def _accumulate():
        f32 = jnp.float32
        x = lhs_ref[...].astype(f32)
        dy = dy_ref[...].astype(f32)
        x = jnp.where(_rows_in(offsets, g, i, tm, x.shape), x, 0.0)
        dy = jnp.where(_rows_in(offsets, g, i, tm, dy.shape), dy, 0.0)
        acc_ref[...] += jax.lax.dot(x.T.astype(lhs_ref.dtype),
                                    dy.astype(dy_ref.dtype),
                                    preferred_element_type=f32)

    ahead = jnp.minimum(v + 1, last)
    @pl.when((v == pl.num_programs(2) - 1) | (group[ahead] != g))
    def _last_visit_of_the_group():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


def tgmm(lhs, dy, group_sizes, *, interpret: bool = None):
    """Per group ``lhs[rows of g].T @ dy[rows of g]``: ``lhs`` [rows, k],
    ``dy`` [rows, n] -> [G, k, n] in ``dy``'s dtype (zeros for an empty
    group), by the Pallas kernel ``gmm_dw``."""
    rows, k = lhs.shape
    n = dy.shape[1]
    groups = group_sizes.shape[0]
    tm, tk, tn = TILE_ROWS, _block(k), _block(n)
    offsets, group, tile, count = visits(group_sizes, rows, tm, empty=True)
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm, last=group.shape[0] - 1),
        out_shape=jax.ShapeDtypeStruct((groups, k, n), dy.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(k // tk, n // tn, count),
            in_specs=[
                pl.BlockSpec((tm, tk), lambda a, b, v, o, g, t: (t[v], a)),
                pl.BlockSpec((tm, tn), lambda a, b, v, o, g, t: (t[v], b))],
            out_specs=pl.BlockSpec((None, tk, tn),
                                   lambda a, b, v, o, g, t: (g[v], a, b)),
            scratch_shapes=[pltpu.VMEM((tk, tn), jnp.float32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT),
        name="gmm_dw",
        interpret=pallas_interpret() if interpret is None else interpret,
    )(offsets, group, tile, lhs, dy)


@jax.custom_vjp
def _grouped_kernels(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes)


def _kernels_fwd(lhs, rhs, group_sizes):
    return gmm(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _kernels_bwd(res, dout):
    lhs, rhs, group_sizes = res
    dlhs = gmm(dout, rhs, group_sizes, transpose_rhs=True)
    drhs = tgmm(lhs, dout, group_sizes).astype(rhs.dtype)
    return dlhs, drhs, None


_grouped_kernels.defvjp(_kernels_fwd, _kernels_bwd)


def grouped_matmul(lhs, rhs, group_sizes, path: str):
    """``lhs`` [rows, k] (rows sorted by group) x ``rhs`` [G, k, n] ->
    [rows, n], differentiable in both, the way ``path`` (``moe_path``)
    names. Rows past the last group are not defined (module docstring)."""
    if path == "gmm":
        return _grouped_kernels(lhs, rhs, group_sizes)
    return jax.lax.ragged_dot(lhs, rhs, group_sizes.astype(jnp.int32))
