"""The Mamba-2 selective scan in its chunked (SSD) form.

Per head, with state ``S`` [p, n] (``p`` the head's width, ``n`` the state's):

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

``B`` and ``C`` are shared by the heads of a group. The chunked evaluation
(Dao & Gu 2024, "Transformers are SSMs", section 6) cuts a row into chunks of
``chunk`` tokens. With ``cs`` the running sum of ``dt A`` inside a chunk:

    within a chunk   Y  = (L * C B^T * dt) X,  L[t, s] = exp(cs_t - cs_s), s <= t
    its end state    S' = exp(cs_end) S + sum_s exp(cs_end - cs_s) dt_s x_s B_s^T
    from before it   Y += exp(cs_t) * (C S^T)

Two ways through it, chosen from the shapes alone by ``ssd_path`` (no
argument, option or environment variable; ``dl4j_ssd_path_total{path=}``
counts the choice once a traced call):

**kernel** - Pallas kernels ``ssd_fwd`` and ``ssd_bwd``: grid ``(batch, blocks
of heads, chunks)``, the chunk axis innermost and sequential with the blocks'
float32 states carried in VMEM scratch from chunk to chunk (in the backward
pass, the states' gradients from the last chunk to the first). ``C B^T`` of a
chunk is made once a program for the ``HEADS_PER_PROGRAM`` heads that share
it. Operands go to the MXU as they arrive (bfloat16 in a bfloat16 model),
products accumulate in float32, and every decay is float32. ``x`` and ``y``
stay in the layout the block has them in, ``[b, t, heads * p]``: nothing is
folded or transposed around the kernels. The running sums and the per-token
weights are made in XLA (a few MB) and handed over in both layouts the body
needs, as rows ``[heads, t]`` and as columns ``[t, heads]``, because a
decay ``exp(cs_t - cs_s)`` needs both and a kernel cannot cheaply turn one
into the other. The forward saves the state each chunk starts from; the
backward makes the chunk's scores again from them and returns the gradients
of ``x``, ``B``, ``C`` and of the decays, from which XLA's own transpose of
the running sums gives ``dt``'s and ``A``'s (``D``'s is a sum outside).

**xla** - the same algebra in ``jax.numpy`` (``ssd_chunked``): tiny shapes
(the CPU rehearsals), a chunk or state that does not fill a vector tile.

No block size is tuned here: a chunk is the model's own ``chunk``, a program
takes 8 heads. CPU processes run the kernels under the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.monitor import SSD_PATH_COUNTER, get_registry
from deeplearning4j_tpu.util.device import pallas_interpret

#: heads a program takes: they share one ``C B^T``; 8 rows fill a float32
#: vector tile's sublanes, which the row layout ``[heads, t]`` needs
HEADS_PER_PROGRAM = 8
_MASKED = -1e30  # exp() of it is 0; -inf minus -inf would be nan

_NT = (((1,), (1,)), ((), ()))   # a . b^T
_NN = (((1,), (0,)), ((), ()))   # a . b
_TN = (((0,), (0,)), ((), ()))   # a^T . b


def _dot(a, b, dims):
    return jax.lax.dot_general(a, b, dims, preferred_element_type=jnp.float32)


def ssd_path(heads: int, groups: int, p: int, n: int, chunk: int) -> str:
    """Which way ``ssd_scan`` takes at these shapes, a pure function of
    them: "kernel" where a chunk, the state and a program's heads fill whole
    vector tiles, "xla" otherwise."""
    hb = min(HEADS_PER_PROGRAM, heads)
    fits = (chunk % 128 == 0 and n % 128 == 0 and heads % hb == 0
            and (hb % 8 == 0 or hb == heads) and (hb * p) % 128 == 0
            and (heads // groups) % hb == 0 and p <= chunk)
    return "kernel" if fits else "xla"


# ------------------------------------------------------------ plain XLA form

def _chunk_sums(dt, A, chunk):
    """``cs``: the running sum of ``dt A`` inside each chunk, [b, t, h]."""
    b, t, h = dt.shape
    a = (dt * A).reshape(b, t // chunk, chunk, h)
    return jnp.cumsum(a, axis=2).reshape(b, t, h)


def _chunked_core(x, dt, cs, B, C, chunk: int):
    """The chunked evaluation with the running sums ``cs`` [b, t, h] given."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    r, nc = h // g, t // chunk
    f32 = jnp.float32
    cs = cs.reshape(b, nc, chunk, g, r)
    dtc = dt.reshape(b, nc, chunk, g, r)
    xs = x.reshape(b, nc, chunk, g, r, p)
    Bc, Cc = B.reshape(b, nc, chunk, g, n), C.reshape(b, nc, chunk, g, n)
    G = jnp.einsum("bctgn,bcsgn->bctsg", Cc, Bc, preferred_element_type=f32)
    keep = jnp.tril(jnp.ones((chunk, chunk), bool))[None, None, :, :, None, None]
    L = jnp.exp(jnp.where(keep, cs[:, :, :, None] - cs[:, :, None], _MASKED))
    M = (G[..., None] * L * dtc[:, :, None]).astype(x.dtype)
    y = jnp.einsum("bctsgr,bcsgrp->bctgrp", M, xs, preferred_element_type=f32)
    if nc > 1:
        w = jnp.exp(cs[:, :, -1:] - cs) * dtc
        local = jnp.einsum("bcsgrp,bcsgn->bcgrpn",
                           (xs * w[..., None]).astype(x.dtype), Bc,
                           preferred_element_type=f32)
        decay = jnp.exp(cs[:, :, -1])[..., None, None]    # [b, nc, g, r, 1, 1]

        def step(S, inp):
            d, loc = inp
            return d * S + loc, S   # the state the chunk STARTS from

        _, before = jax.lax.scan(
            step, jnp.zeros((b, g, r, p, n), f32),
            (jnp.moveaxis(decay, 1, 0), jnp.moveaxis(local, 1, 0)))
        before = jnp.moveaxis(before, 0, 1).astype(x.dtype)
        y = y + jnp.exp(cs)[..., None] * jnp.einsum(
            "bctgn,bcgrpn->bctgrp", Cc, before, preferred_element_type=f32)
    return y.reshape(b, t, h, p).astype(x.dtype)


def ssd_chunked(x, dt, A, B, C, chunk: int):
    """The chunked evaluation in ``jax.numpy``; ``t`` a multiple of
    ``chunk``. x [b, t, h, p], dt [b, t, h] (float32, after the softplus),
    A [h], B and C [b, t, g, n]."""
    return _chunked_core(x, dt, _chunk_sums(dt, A, chunk), B, C, chunk)


# ------------------------------------------------------------------ kernels

def _tri(chunk):
    rows = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    return rows >= cols


def _fwd_kernel(x_ref, dt_ref, csr_ref, col_ref, b_ref, c_ref,
                y_ref, st_ref, s_scr, *, hb, p, chunk):
    @pl.when(pl.program_id(2) == 0)
    def _first_chunk():
        s_scr[:] = jnp.zeros_like(s_scr)

    st_ref[0, 0] = s_scr[:]  # what this chunk starts from, for the backward
    Bm, Cm = b_ref[0], c_ref[0]                       # [chunk, n]
    G = _dot(Cm, Bm, _NT)                             # once for hb heads
    keep = _tri(chunk)
    for i in range(hb):
        lanes = slice(i * p, (i + 1) * p)
        cs_c = col_ref[0, 0, :, i:i + 1]              # [chunk, 1]
        w_c = col_ref[0, 0, :, hb + i:hb + i + 1]
        # the chunk's whole decay, the same in every row of its column
        end = jnp.exp(col_ref[0, 0, :p, 2 * hb + i:2 * hb + i + 1])  # [p, 1]
        cs_r, dt_r = csr_ref[0, i:i + 1, :], dt_ref[0, i:i + 1, :]
        L = jnp.exp(jnp.where(keep, cs_c - cs_r, _MASKED))
        xi = x_ref[0, :, lanes]
        S = s_scr[lanes, :]                           # [p, n] float32
        y = _dot((G * L * dt_r).astype(xi.dtype), xi, _NN)
        y += jnp.exp(cs_c) * _dot(Cm, S.astype(Cm.dtype), _NT)
        y_ref[0, :, lanes] = y.astype(y_ref.dtype)
        s_scr[lanes, :] = end * S + _dot((xi * w_c).astype(xi.dtype), Bm, _TN)


def _bwd_kernel(x_ref, dy_ref, dt_ref, csr_ref, col_ref, b_ref, c_ref, st_ref,
                dx_ref, drow_ref, dcol_ref, db_ref, dc_ref, ds_scr,
                *, hb, p, chunk):
    """One chunk of ``hb`` heads, the chunks walked from the last to the
    first: ``ds_scr`` holds the gradient of the state the chunk ENDS in.
    The gradients of the decays come out in the layouts they went in:
    ``drow`` = [dt's rows | cs's rows], ``dcol`` = [cs | w | cs_end] columns."""
    @pl.when(pl.program_id(2) == 0)
    def _last_chunk():
        ds_scr[:] = jnp.zeros_like(ds_scr)

    f32 = jnp.float32
    Bm, Cm = b_ref[0], c_ref[0]                       # [chunk, n]
    G = _dot(Cm, Bm, _NT)
    keep = _tri(chunk)
    dG = jnp.zeros((chunk, chunk), f32)               # summed over the heads
    dB = jnp.zeros(Bm.shape, f32)
    dC = jnp.zeros(Cm.shape, f32)
    dcol_ref[...] = jnp.zeros_like(dcol_ref)
    for i in range(hb):
        lanes = slice(i * p, (i + 1) * p)
        cs_c = col_ref[0, 0, :, i:i + 1]              # [chunk, 1]
        w_c = col_ref[0, 0, :, hb + i:hb + i + 1]
        end = jnp.exp(col_ref[0, 0, :p, 2 * hb + i:2 * hb + i + 1])  # [p, 1]
        cs_r, dt_r = csr_ref[0, i:i + 1, :], dt_ref[0, i:i + 1, :]
        xi, dyi = x_ref[0, :, lanes], dy_ref[0, :, lanes]
        low = xi.dtype
        S0 = st_ref[0, 0, lanes, :]                   # the chunk's start state
        dS1 = ds_scr[lanes, :]                        # its end state's gradient
        S0l, dS1l = S0.astype(low), dS1.astype(low)
        # the scores again, then each product's transposes
        L = jnp.exp(jnp.where(keep, cs_c - cs_r, _MASKED))
        GL = G * L
        M = GL * dt_r
        dM = _dot(dyi, xi, _NT)                       # [chunk t, chunk s]
        W = dM * M
        dx = _dot(M.astype(low), dyi, _TN)
        dG += dM * L * dt_r
        drow_ref[0, i:i + 1, :] = jnp.sum(dM * GL, axis=0, keepdims=True)
        drow_ref[0, hb + i:hb + i + 1, :] = -jnp.sum(W, axis=0, keepdims=True)
        # what came in from before the chunk: y += exp(cs) * (C S0^T)
        dye = dyi * jnp.exp(cs_c)                     # float32
        dcol_ref[0, 0, :, i:i + 1] = jnp.sum(W, axis=1, keepdims=True) \
            + jnp.sum(dye * _dot(Cm, S0l, _NT), axis=1, keepdims=True)
        dye = dye.astype(low)
        dC += _dot(dye, S0l, _NN)
        # the chunk's end state: S1 = end * S0 + (x * w)^T B
        R = _dot(Bm, dS1l, _NT)                       # [chunk, p]
        dx += w_c * R
        dcol_ref[0, 0, :, hb + i:hb + i + 1] = jnp.sum(
            R * xi, axis=1, keepdims=True)
        dcol_ref[0, 0, :p, 2 * hb + i:2 * hb + i + 1] = jnp.sum(
            dS1 * end * S0, axis=1, keepdims=True)
        dB += _dot((xi * w_c).astype(low), dS1l, _NN)
        dx_ref[0, :, lanes] = dx.astype(dx_ref.dtype)
        ds_scr[lanes, :] = end * dS1 + _dot(dye, Cm, _TN)
    dGl = dG.astype(Bm.dtype)
    db_ref[0, 0] = dB + _dot(dGl, Cm, _TN)
    dc_ref[0, 0] = dC + _dot(dGl, Bm, _NN)


def _specs(h, g, p, n, chunk, hb, chunk_of):
    """Block specs over the grid (batch, head block, step) for an array in
    each layout the kernels use; ``chunk_of`` maps the grid's step to the
    chunk (the backward walks them from the last)."""
    per_group = (h // g) // hb  # head blocks that share one group's B and C
    spec = lambda shape, index: pl.BlockSpec(
        shape, lambda i, j, c: index(i, j, chunk_of(c)))
    return {
        "x": spec((1, chunk, hb * p), lambda i, j, c: (i, c, j)),
        "row": spec((1, hb, chunk), lambda i, j, c: (i, j, c)),
        "row2": spec((1, 2 * hb, chunk), lambda i, j, c: (i, j, c)),
        "col": spec((1, 1, chunk, 3 * hb), lambda i, j, c: (i, j, c, 0)),
        "bc": spec((1, chunk, n), lambda i, j, c: (i, c, j // per_group)),
        "state": spec((1, 1, hb * p, n), lambda i, j, c: (i, c, j, 0)),
        "bc_part": spec((1, 1, chunk, n), lambda i, j, c: (i, j, c, 0)),
    }


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "parallel", "arbitrary"),
    vmem_limit_bytes=32 * 2 ** 20)


def _layouts(dt, cs, chunk, hb):
    """The decays as the kernels read them: rows ``[b, h, t]`` of ``dt`` and
    ``cs``, and columns ``[b, h / hb, t, 3 hb]`` of ``cs``, of
    ``w_s = exp(cs_end - cs_s) dt_s``, a token's weight in its chunk's end
    state, and of ``cs_end`` itself, repeated down its chunk (a kernel
    cannot spread one number over a whole tile, a column it can)."""
    b, t, h = dt.shape
    c4 = cs.reshape(b, t // chunk, chunk, h)
    end = jnp.broadcast_to(c4[:, :, -1:, :], c4.shape)
    w = jnp.exp(end - c4) * dt.reshape(c4.shape)
    blocks = lambda z: z.reshape(b, t, h // hb, hb).transpose(0, 2, 1, 3)
    cols = jnp.concatenate([blocks(cs), blocks(w), blocks(end)], axis=-1)
    return dt.transpose(0, 2, 1), cs.transpose(0, 2, 1), cols


# jitted, both wrappers: every Mamba-2 layer of a model calls with the same
# shapes, so the unrolled body is traced and lowered once a program
@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9))
def _fwd_call(x, dt_r, cs_r, cols, B, C, g: int, p: int, chunk: int,
              interpret: bool):
    """x [b, t, h p]; dt_r, cs_r, cols as ``_layouts`` gives them; B and C
    [b, t, g n] -> (y, the state each chunk starts from [b, nc, h p, n])."""
    b, t, hp = x.shape
    h, n, nc, hb = hp // p, B.shape[2] // g, t // chunk, cols.shape[-1] // 3
    s = _specs(h, g, p, n, chunk, hb, lambda c: c)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, hb=hb, p=p, chunk=chunk),
        grid=(b, h // hb, nc),
        in_specs=[s["x"], s["row"], s["row"], s["col"], s["bc"], s["bc"]],
        out_specs=[s["x"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, hp, n), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb * p, n), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_fwd",
    )(x, dt_r, cs_r, cols, B, C)


@functools.partial(jax.jit, static_argnums=(8, 9, 10, 11))
def _bwd_call(x, dy, dt_r, cs_r, cols, B, C, states, g: int, p: int,
              chunk: int, interpret: bool):
    """-> the gradients of x, [dt_r | cs_r], cols, and of B and C a head
    block ([b, h / hb, t, n] float32: the blocks of a group are summed
    outside)."""
    b, t, hp = x.shape
    h, n, nc, hb = hp // p, B.shape[2] // g, t // chunk, cols.shape[-1] // 3
    s = _specs(h, g, p, n, chunk, hb, lambda c: nc - 1 - c)
    part = jax.ShapeDtypeStruct((b, h // hb, t, n), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, hb=hb, p=p, chunk=chunk),
        grid=(b, h // hb, nc),
        in_specs=[s["x"], s["x"], s["row"], s["row"], s["col"], s["bc"],
                  s["bc"], s["state"]],
        out_specs=[s["x"], s["row2"], s["col"], s["bc_part"], s["bc_part"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, 2 * h, t), jnp.float32),
                   jax.ShapeDtypeStruct(cols.shape, jnp.float32), part, part],
        scratch_shapes=[pltpu.VMEM((hb * p, n), jnp.float32)],
        compiler_params=_PARAMS, interpret=interpret, name="ssd_bwd",
    )(x, dy, dt_r, cs_r, cols, B, C, states)


# ---------------------------------------------------------- the custom rule

@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8, 9))
def _ssd_kernels(x, dt_r, cs_r, cols, B, C, g, p, chunk, interpret):
    """The two kernels as one differentiable function of the arrays they
    read, each gradient in its array's own layout; XLA differentiates
    ``_layouts`` and the running sums around it."""
    return _fwd_call(x, dt_r, cs_r, cols, B, C, g, p, chunk, interpret)[0]


def _ssd_kernels_fwd(x, dt_r, cs_r, cols, B, C, g, p, chunk, interpret):
    y, states = _fwd_call(x, dt_r, cs_r, cols, B, C, g, p, chunk, interpret)
    return y, (x, dt_r, cs_r, cols, B, C, states)


def _ssd_kernels_bwd(g, p, chunk, interpret, res, dy):
    x, dt_r, cs_r, cols, B, C, states = res
    b, t, hp = x.shape
    h = hp // p
    hb = cols.shape[-1] // 3
    dx, drow, dcols, dB, dC = _bwd_call(x, dy, dt_r, cs_r, cols, B, C, states,
                                        g, p, chunk, interpret)
    # rows come back a head block at a time: [dt's hb rows | cs's hb rows]
    drow = drow.reshape(b, h // hb, 2, hb, t)
    group = lambda z: z.reshape(b, g, -1, t, z.shape[-1]).sum(axis=2).transpose(
        0, 2, 1, 3).reshape(B.shape).astype(B.dtype)
    return (dx, drow[:, :, 0].reshape(b, h, t), drow[:, :, 1].reshape(b, h, t),
            dcols, group(dB), group(dC))


_ssd_kernels.defvjp(_ssd_kernels_fwd, _ssd_kernels_bwd)


def _ssd_core(x, dt, A, B, C, chunk, interpret):
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    hb = min(HEADS_PER_PROGRAM, h)
    dt_r, cs_r, cols = _layouts(dt, _chunk_sums(dt, A, chunk), chunk, hb)
    y = _ssd_kernels(x.reshape(b, t, h * p), dt_r, cs_r, cols,
                     B.reshape(b, t, g * n), C.reshape(b, t, g * n),
                     g, p, chunk, interpret)
    return y.reshape(b, t, h, p)


def ssd_scan(x, dt, A, B, C, D, chunk: int, interpret=None):
    """``y`` [b, t, h, p] of the recurrence above. x [b, t, h, p]; dt
    [b, t, h] after the softplus; A [h], negative; B and C [b, t, g, n]; D
    [h]. Any length: a row is padded to whole chunks with ``dt = 0``, which
    neither decays the state nor adds to it."""
    b, t, h, p = x.shape
    g, n = B.shape[2:]
    dt = dt.astype(jnp.float32)
    A = A.astype(jnp.float32)
    pad = -t % chunk
    if pad:
        grow = lambda z: jnp.pad(z, [(0, 0), (0, pad)] + [(0, 0)] * (z.ndim - 2))
        xp, dtp, Bp, Cp = grow(x), grow(dt), grow(B), grow(C)
    else:
        xp, dtp, Bp, Cp = x, dt, B, C
    path = ssd_path(h, g, p, n, chunk)
    if path == "kernel":
        if interpret is None:
            interpret = pallas_interpret()
        y = _ssd_core(xp, dtp, A, Bp, Cp, chunk, interpret)
    else:
        y = ssd_chunked(xp, dtp, A, Bp, Cp, chunk)
    # decided while tracing, so counted there: nothing in the compiled step
    get_registry().counter(
        SSD_PATH_COUNTER, "ssd_scan calls traced, by the way their shapes "
        "chose", path=path).inc()
    y = y[:, :t] if pad else y
    return y + (D.astype(jnp.float32)[:, None] * x).astype(y.dtype)
