"""The Mamba-1 selective scan: a decay for every (channel, state) pair.

Per channel ``c`` with state ``S`` [n] (``n`` the state's width):

    S_t[c] = exp(dt_t[c] A[c]) * S_{t-1}[c] + dt_t[c] x_t[c] B_t
    y_t[c] = S_t[c] . C_t + D[c] x_t[c]

``B_t`` and ``C_t`` [n] are shared by every channel of a token. The decay
``exp(dt_t[c] A[c, s])`` differs in every channel AND every state, so the
chunked matrix form of ``ops/ssd.py`` (one scalar decay a head, the chunk's
scores as a product on the MXU) does not exist here: the recurrence is
evaluated token by token, on the VPU and the EUP, one ``exp`` a state
element a token.

Two ways through it, chosen from the shapes alone by ``selscan_path`` (no
argument, option or environment variable; ``dl4j_selscan_path_total{path=}``
counts the choice once a traced call):

**kernel** - Pallas kernels ``selscan_fwd`` and ``selscan_bwd``: grid
``(batch, chunks)``, the chunk axis sequential. The float32 state of ALL
channels, ``[n, channels]`` (states on sublanes, channels on lanes), stays in
VMEM scratch from chunk to chunk; inside a chunk a program takes
``CHANNELS_PER_BLOCK`` channels at a time through the chunk's tokens with the
block's state in registers. ``x``, ``dt`` and ``y`` stay in the layout the
block has them in, ``[b, t, channels]``; a token is a row of it. ``B`` and
``C`` are handed over with each value spread over 128 lanes (``[b, t, n,
128]``, made in XLA: a kernel cannot cheaply turn a token's ``[1, n]`` row
into the ``[n, 1]`` column the state's layout wants), a tile a token. The
forward saves the state each chunk starts from. The backward walks the chunks
from the last to the first: it makes a chunk's states again from the saved
one, keeps them in VMEM, and runs the adjoint recurrence over the chunk's
tokens from the last to the first with the state's gradient carried in VMEM;
``A``'s gradient accumulates in VMEM over a row and the gradients of ``B`` and
``C`` come back spread over the 128 lanes they went in on (summed outside).

**xla** - a plain ``lax.scan`` over the tokens (tiny shapes, the CPU
rehearsals), differentiated by JAX.

Decays, ``dt``, the state and its gradient are float32 whatever ``x``, ``B``
and ``C`` are. CPU processes run the kernels under the Pallas interpreter.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.monitor import SELSCAN_PATH_COUNTER, get_registry
from deeplearning4j_tpu.util.device import pallas_interpret

#: tokens a grid step takes; the forward saves a state a chunk
CHUNK = 64
#: channels whose state a program holds in registers through a chunk: a
#: [16, 512] float32 state is 8 vector registers, its decays 8 more
CHANNELS_PER_BLOCK = 512
_LANES = 128

#: the names (``jax.ad_checkpoint.checkpoint_name``) of what the forward rule
#: makes: a ``jax.checkpoint`` whose policy saves them does not run the
#: forward kernel again (``y`` is the rule's output, the states its residual)
SELSCAN_RESIDUAL_NAMES = ("selscan_y", "selscan_states")


def selscan_path(channels: int, n: int) -> str:
    """Which way ``selective_scan`` takes at these shapes, a pure function
    of them: "kernel" where the channels split into whole blocks and the
    state fills whole sublane tiles, "xla" otherwise."""
    fits = channels % CHANNELS_PER_BLOCK == 0 and n % 8 == 0
    return "kernel" if fits else "xla"


# ------------------------------------------------------------ plain XLA form

def selective_scan_xla(x, dt, A, B, C):
    """The recurrence token by token, without the ``D`` term. x, dt
    [b, t, c] (dt float32, after the softplus), A [c, n] (negative), B and C
    [b, t, n] -> y [b, t, c] in ``x``'s dtype."""
    f32 = jnp.float32
    b, t, c = x.shape

    def token(S, z):
        xt, dtt, Bt, Ct = z                           # [b, c], [b, c], [b, n]
        S = jnp.exp(dtt[..., None] * A) * S \
            + (dtt * xt)[..., None] * Bt[:, None, :]
        return S, jnp.sum(S * Ct[:, None, :], axis=-1)

    steps = tuple(jnp.moveaxis(z.astype(f32), 1, 0) for z in (x, dt, B, C))
    _, y = jax.lax.scan(token, jnp.zeros((b, c, A.shape[1]), f32), steps)
    return jnp.moveaxis(y, 0, 1).astype(x.dtype)


# ------------------------------------------------------------------ kernels

def _spread(tile, width):
    """A token's [n, 128] tile of ``B`` or ``C`` over ``width`` lanes."""
    return jnp.concatenate([tile] * (width // _LANES), axis=1)


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, st_ref,
                s_scr, u_scr, y_scr, *, chunk, cb):
    @pl.when(pl.program_id(1) == 0)
    def _first_chunk():
        s_scr[:] = jnp.zeros_like(s_scr)

    f32 = jnp.float32
    st_ref[0, 0] = s_scr[:]  # what this chunk starts from, for the backward
    u_scr[:] = dt_ref[0] * x_ref[0].astype(f32)       # dt x, every token
    for c0 in range(0, s_scr.shape[1], cb):
        lanes = slice(c0, c0 + cb)
        A = a_ref[:, lanes]                            # [n, cb]

        def token(t, S, lanes=lanes, A=A):
            row = pl.ds(t, 1)
            S = jnp.exp(dt_ref[0, row, lanes] * A) * S \
                + u_scr[row, lanes] * _spread(b_ref[0, t].astype(f32), cb)
            y_scr[row, lanes] = jnp.sum(
                S * _spread(c_ref[0, t].astype(f32), cb), axis=0,
                keepdims=True)
            return S

        s_scr[:, lanes] = jax.lax.fori_loop(0, chunk, token, s_scr[:, lanes])
    y_ref[0] = y_scr[:].astype(y_ref.dtype)


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, st_ref, dy_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref,
                g_scr, da_scr, all_scr, x_scr, dy_scr, dx_scr, *, chunk, cb):
    """One chunk, the chunks walked from the last to the first: ``g_scr``
    holds the gradient of the state the chunk ENDS in, already decayed to
    it. ``db`` and ``dc`` come out as they went in, a [n, 128] tile a token,
    each lane a partial sum over the channels that share it."""
    @pl.when(pl.program_id(1) == 0)
    def _last_chunk():
        g_scr[:] = jnp.zeros_like(g_scr)
        da_scr[:] = jnp.zeros_like(da_scr)

    f32 = jnp.float32
    n = g_scr.shape[0]
    # float32 copies: a token is one row, and a row of a packed dtype is
    # not a load or a store of its own
    x_scr[:] = x_ref[0].astype(f32)
    dy_scr[:] = dy_ref[0].astype(f32)
    db_ref[...] = jnp.zeros_like(db_ref)
    dc_ref[...] = jnp.zeros_like(dc_ref)
    fold = lambda z: functools.reduce(  # [n, cb] -> [n, 128], lane groups added
        jnp.add, [z[:, i:i + _LANES] for i in range(0, cb, _LANES)])
    for c0 in range(0, g_scr.shape[1], cb):
        lanes = slice(c0, c0 + cb)
        A = a_ref[:, lanes]

        # the chunk's states again: all_scr[t] is the state BEFORE token t
        def token(t, S, lanes=lanes, A=A):
            row = pl.ds(t, 1)
            all_scr[t] = S
            dtt = dt_ref[0, row, lanes]
            return jnp.exp(dtt * A) * S + (dtt * x_scr[row, lanes]) \
                * _spread(b_ref[0, t].astype(f32), cb)

        jax.lax.fori_loop(0, chunk, token, st_ref[0, 0, :, lanes])

        # the adjoint recurrence, from the chunk's last token to its first
        def back(i, carry, lanes=lanes, A=A):
            G, dA = carry
            t = chunk - 1 - i
            row = pl.ds(t, 1)
            dtt, xt = dt_ref[0, row, lanes], x_scr[row, lanes]
            u = dtt * xt
            Bt = _spread(b_ref[0, t].astype(f32), cb)
            S0 = all_scr[t]
            decay = jnp.exp(dtt * A)
            S1 = decay * S0 + u * Bt
            dyt = dy_scr[row, lanes]
            G = G + dyt * _spread(c_ref[0, t].astype(f32), cb)
            dc_ref[0, t] += fold(S1 * dyt)
            db_ref[0, t] += fold(G * u)
            q = jnp.sum(G * Bt, axis=0, keepdims=True)      # [1, cb]
            W = G * decay * S0
            dx_scr[row, lanes] = dtt * q
            ddt_ref[0, row, lanes] = xt * q + jnp.sum(W * A, axis=0,
                                                      keepdims=True)
            return G * decay, dA + dtt * W

        G, dA = jax.lax.fori_loop(
            0, chunk, back, (g_scr[:, lanes], jnp.zeros((n, cb), f32)))
        g_scr[:, lanes] = G
        da_scr[:, lanes] += dA
    dx_ref[0] = dx_scr[:].astype(dx_ref.dtype)
    da_ref[0] = da_scr[:]


_PARAMS = pltpu.CompilerParams(
    dimension_semantics=("parallel", "arbitrary"),
    vmem_limit_bytes=64 * 2 ** 20)


def _specs(c, n, chunk, chunk_of):
    """Block specs over the grid (batch, step); ``chunk_of`` maps the grid's
    step to the chunk (the backward walks them from the last)."""
    return {
        "row": pl.BlockSpec((1, chunk, c), lambda i, j: (i, chunk_of(j), 0)),
        "a": pl.BlockSpec((n, c), lambda i, j: (0, 0)),
        "bc": pl.BlockSpec((1, chunk, n, _LANES),
                           lambda i, j: (i, chunk_of(j), 0, 0)),
        "state": pl.BlockSpec((1, 1, n, c),
                              lambda i, j: (i, chunk_of(j), 0, 0)),
        "da": pl.BlockSpec((1, n, c), lambda i, j: (i, 0, 0)),
    }


# jitted, both wrappers: every Mamba-1 layer of a model calls with the same
# shapes, so the body is traced and lowered once a program
@functools.partial(jax.jit, static_argnums=(5, 6))
def _fwd_call(x, dt, a_t, bx, cx, chunk: int, interpret: bool):
    """x [b, t, c]; dt [b, t, c] float32; a_t [n, c]; bx, cx [b, t, n, 128]
    -> (y, the state each chunk starts from [b, t / chunk, n, c])."""
    b, t, c = x.shape
    n, nc = a_t.shape[0], t // chunk
    cb = min(CHANNELS_PER_BLOCK, c)
    s = _specs(c, n, chunk, lambda j: j)
    f32 = jnp.float32
    return pl.pallas_call(
        functools.partial(_fwd_kernel, chunk=chunk, cb=cb),
        grid=(b, nc),
        in_specs=[s["row"], s["row"], s["a"], s["bc"], s["bc"]],
        out_specs=[s["row"], s["state"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b, nc, n, c), f32)],
        scratch_shapes=[pltpu.VMEM((n, c), f32), pltpu.VMEM((chunk, c), f32),
                        pltpu.VMEM((chunk, c), f32)],
        compiler_params=_PARAMS, interpret=interpret, name="selscan_fwd",
    )(x, dt, a_t, bx, cx)


@functools.partial(jax.jit, static_argnums=(7, 8))
def _bwd_call(x, dt, a_t, bx, cx, states, dy, chunk: int, interpret: bool):
    """-> the gradients of x, dt, a_t (a batch row each), bx and cx."""
    b, t, c = x.shape
    n, nc = a_t.shape[0], t // chunk
    cb = min(CHANNELS_PER_BLOCK, c)
    s = _specs(c, n, chunk, lambda j: nc - 1 - j)
    f32 = jnp.float32
    spread = jax.ShapeDtypeStruct(bx.shape, f32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, chunk=chunk, cb=cb),
        grid=(b, nc),
        in_specs=[s["row"], s["row"], s["a"], s["bc"], s["bc"], s["state"],
                  s["row"]],
        out_specs=[s["row"], s["row"], s["da"], s["bc"], s["bc"]],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct(x.shape, f32),
                   jax.ShapeDtypeStruct((b, n, c), f32), spread, spread],
        scratch_shapes=[pltpu.VMEM((n, c), f32), pltpu.VMEM((n, c), f32),
                        pltpu.VMEM((chunk, n, cb), f32)]
        + [pltpu.VMEM((chunk, c), f32)] * 3,
        compiler_params=_PARAMS, interpret=interpret, name="selscan_bwd",
    )(x, dt, a_t, bx, cx, states, dy)


# ---------------------------------------------------------- the custom rule

def _spread_lanes(z):
    """B or C [b, t, n] with each value over 128 lanes, a tile a token."""
    return jnp.broadcast_to(z[..., None], z.shape + (_LANES,))


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6))
def _selscan_kernels(x, dt, a_t, B, C, chunk, interpret):
    return _selscan_kernels_fwd(x, dt, a_t, B, C, chunk, interpret)[0]


def _selscan_kernels_fwd(x, dt, a_t, B, C, chunk, interpret):
    y, states = _fwd_call(x, dt, a_t, _spread_lanes(B), _spread_lanes(C),
                          chunk, interpret)
    y, states = (checkpoint_name(z, name)
                 for z, name in zip((y, states), SELSCAN_RESIDUAL_NAMES))
    return y, (x, dt, a_t, B, C, states)


def _selscan_kernels_bwd(chunk, interpret, res, dy):
    x, dt, a_t, B, C, states = res
    dx, ddt, da, dbx, dcx = _bwd_call(
        x, dt, a_t, _spread_lanes(B), _spread_lanes(C), states, dy, chunk,
        interpret)
    lanes = lambda z, like: jnp.sum(z, axis=-1).astype(like.dtype)
    return dx, ddt, jnp.sum(da, axis=0), lanes(dbx, B), lanes(dcx, C)


_selscan_kernels.defvjp(_selscan_kernels_fwd, _selscan_kernels_bwd)


def selective_scan(x, delta, A, B, C, D, interpret=None):
    """``y`` [b, t, c] of the recurrence above. x [b, t, c]; delta [b, t, c]
    after the softplus; A [c, n], negative; B and C [b, t, n]; D [c]. Any
    length: on the kernel path a row is padded to whole chunks with
    ``delta = 0``, which neither decays the state nor adds to it."""
    b, t, c = x.shape
    f32 = jnp.float32
    delta, A = delta.astype(f32), A.astype(f32)
    path = selscan_path(c, A.shape[1])
    if path == "kernel":
        if interpret is None:
            interpret = pallas_interpret()
        pad = -t % CHUNK
        grow = lambda z: jnp.pad(z, ((0, 0), (0, pad), (0, 0))) if pad else z
        y = _selscan_kernels(grow(x), grow(delta), A.T, grow(B), grow(C),
                             CHUNK, interpret)[:, :t]
    else:
        y = selective_scan_xla(x, delta, A, B, C)
    # decided while tracing, so counted there: nothing in the compiled step
    get_registry().counter(
        SELSCAN_PATH_COUNTER, "selective_scan calls traced, by the way "
        "their shapes chose", path=path).inc()
    return y + (D.astype(f32) * x).astype(y.dtype)
