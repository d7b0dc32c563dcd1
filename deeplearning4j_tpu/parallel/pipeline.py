"""Pipeline parallelism — GPipe-style microbatched stage pipeline.

No reference counterpart (SURVEY §2.6 note 5: the reference predates
pipeline parallelism); mesh-axis extension alongside TP/SP/EP.

TPU-first formulation (the scaling-book SPMD pipelining pattern): the
model is a stack of P IDENTICAL stages (e.g. transformer blocks) whose
parameters carry a leading stage dim sharded over the mesh ``pp`` axis
— each device holds one stage. Execution is ONE ``shard_map``ed program:
a ``fori_loop`` over P+M-1 ticks where every device runs its stage on
the activation it holds, then rotates activations to the next stage
with ``ppermute`` (ICI neighbor exchange). Microbatch m occupies stage
s at tick s+m; the (P-1)-tick bubble computes on garbage that is never
read (static shapes, no control-flow divergence — the compiler-friendly
way). Outputs are collected on the last stage and ``psum``-broadcast.

Differentiable end-to-end: ``ppermute`` has a transpose rule, so
``jax.grad`` through ``pipeline_apply`` yields the reverse-schedule
backward pipeline automatically.

Uniform stages are the deliberate scope: the dominant pp use-case is a
homogeneous block stack, and uniformity is what lets ONE traced program
serve every stage (SPMD), instead of P distinct programs + a scheduler
(the GPU formulation).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from deeplearning4j_tpu.parallel.mesh import device_collective


def pipeline_apply(stage_params, fn: Callable, x: jnp.ndarray,
                   mesh: Mesh, axis: str = "pp",
                   microbatches: int = None) -> jnp.ndarray:
    """Apply P stacked stages as a pipeline over the ``axis`` mesh axis.

    stage_params: pytree whose leaves have leading dim P (stage-stacked,
    shard leading dim over ``axis``). fn(params_slice, h) -> h with
    unchanged activation shape. x: [batch, ...]; batch must divide into
    ``microbatches`` (default: the axis size). Returns fn applied
    stage-by-stage, exactly equal to the sequential loop (tested).
    """
    p = mesh.shape[axis]
    m = microbatches or p
    b = x.shape[0]
    if b % m:
        raise ValueError(f"batch {b} not divisible into {m} microbatches")
    xm = x.reshape((m, b // m) + x.shape[1:])

    def staged(params_local, xm_local):
        # params_local leaves: [1, ...] (this device's stage); xm: [M, mb, ...]
        my = jax.lax.axis_index(axis)
        params_my = jax.tree.map(lambda v: v[0], params_local)
        mb_shape = xm_local.shape[1:]
        n_ticks = p + m - 1

        def tick(t, carry):
            h, outs = carry
            # stage 0 ingests microbatch t (clamped; bubble ticks read a
            # valid-but-unused slot), later stages take the carried h
            mb_idx = jnp.clip(t, 0, m - 1)
            inp = jnp.where(my == 0, xm_local[mb_idx], h)
            h_out = fn(params_my, inp)
            # last stage completes microbatch t-(P-1)
            out_idx = t - (p - 1)
            valid = (my == p - 1) & (out_idx >= 0)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(valid, h_out, jax.lax.dynamic_index_in_dim(
                    outs, jnp.clip(out_idx, 0, m - 1), 0, keepdims=False)),
                jnp.clip(out_idx, 0, m - 1), 0)
            # rotate activations to the next stage around the ring
            h_next = jax.lax.ppermute(h_out, axis,
                                      [(i, (i + 1) % p) for i in range(p)])
            return h_next, outs

        # typed varying over the stage axis: every tick writes this
        # device's stage output back into the carries
        h0, outs0 = jax.lax.pcast(
            (jnp.zeros(mb_shape, x.dtype),
             jnp.zeros((m,) + mb_shape, x.dtype)), axis, to="varying")
        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (h0, outs0))
        # only the last stage holds real outputs; broadcast over the axis
        outs = jnp.where(my == p - 1, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    # a genuinely per-device program (every device ticks its stage and
    # rotates activations around the ring) — the plane's one sanctioned
    # shard_map entry point
    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    out = device_collective(
        staged, mesh,
        in_specs=(spec_params, P()), out_specs=P(),
    )(stage_params, xm)
    return out.reshape((b,) + x.shape[1:])
