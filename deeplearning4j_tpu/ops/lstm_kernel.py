"""Fused LSTM-scan Pallas TPU kernels — forward AND backward.

The second custom-kernel slot (after ``ops/flash_attention.py``): the
BASELINE.json "CudnnLSTMHelper → XLA while-loop" north star. Measured
on v5e at the char-RNN bench shape (b1024/n512/t128, bf16):

- forward: XLA ``lax.scan`` 25.2 ms → this kernel 17.1 ms (-32%) —
  the recurrent gemm and the gate nonlinearities fuse in VMEM, with
  the [n, 4n] recurrent weight and the (h, c) carries resident in
  scratch across every timestep (grid (batch_blocks, t), t innermost
  "arbitrary"),
- training (r5): the Pallas BPTT below takes the FULL char-RNN train
  step from 28.8% MFU (XLA fused scan-grad, the best r3/r4 result) to
  **63.5% MFU** — reverse-time grid, the dh/dc carries AND the f32
  [n, 4n] dWr accumulator resident in VMEM, gate-derivative math fused
  with both per-step gemms (dg@Wrᵀ and h_prevᵀ@dg). The r3/r4 split
  alternative (fused forward + an XLA residual-scan BPTT) measured
  21.0% — the win comes specifically from keeping the BACKWARD
  sequential loop inside one kernel too. Gradients equal the XLA scan's
  to 1e-6 in a single on-chip SGD step; ``DL4J_TPU_LSTM_TRAIN=xla``
  restores the scan path. The XLA residual BPTT (``
  _bwd_from_residuals``) remains as the n>512 / fallback backward.

Semantics: Graves LSTM with peepholes, sigmoid gates / tanh block
(``LSTMHelpers.java:131``) — exactly ``_lstm_scan``'s math; dispatch
requires no mask, default activations, and tileable shapes. CPU test
meshes run the same kernel under the Pallas interpreter.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from deeplearning4j_tpu.util.device import pallas_interpret


def _scratch(shape, dtype=jnp.float32):
    return pltpu.VMEM(shape, dtype)


_VMEM = dict(memory_space=pltpu.VMEM)


def _cell(xg_ref, wr_ref, wci_ref, wcf_ref, wco_ref, h0_ref, c0_ref,
          h_scr, c_scr, n: int):
    """ONE Graves step against the VMEM-resident carries — the shared
    body of both kernel variants (keeping the gate math in one place so
    the residual and inference paths can never desynchronize).
    Returns (i, f, o, blk, c_new, h_new) and advances the scratch."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        # h carry lives in the MXU operand dtype: a per-step f32->bf16
        # cast would relayout [b, n] before every recurrent gemm
        h_scr[:] = h0_ref[...].astype(h_scr.dtype)
        c_scr[:] = c0_ref[...].astype(jnp.float32)

    c_prev = c_scr[:]
    # recurrent gemm fused with the gate math: g = xg_t + h_prev @ Wr
    g = xg_ref[0].astype(jnp.float32) + jax.lax.dot_general(
        h_scr[:], wr_ref[...],
        (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    # Graves gate order [input, forget, output, block]; peepholes read
    # c_prev for i/f and c_new for o (LSTMHelpers.java:131)
    i = jax.nn.sigmoid(g[:, :n] + c_prev * wci_ref[0])
    f = jax.nn.sigmoid(g[:, n:2 * n] + c_prev * wcf_ref[0])
    blk = jnp.tanh(g[:, 3 * n:])
    c_new = f * c_prev + i * blk
    o = jax.nn.sigmoid(g[:, 2 * n:3 * n] + c_new * wco_ref[0])
    h_new = o * jnp.tanh(c_new)
    h_scr[:] = h_new.astype(h_scr.dtype)
    c_scr[:] = c_new
    return i, f, o, blk, c_new, h_new


def _fwd_kernel(xg_ref, wr_ref, wci_ref, wcf_ref, wco_ref, h0_ref, c0_ref,
                h_ref, i_ref, f_ref, o_ref, blk_ref, c_ref,
                h_scr, c_scr, *, n: int):
    """Training/vjp variant: streams gate residuals for the BPTT."""
    i, f, o, blk, c_new, h_new = _cell(
        xg_ref, wr_ref, wci_ref, wcf_ref, wco_ref, h0_ref, c0_ref,
        h_scr, c_scr, n)
    h_ref[0] = h_new.astype(h_ref.dtype)
    i_ref[0] = i.astype(i_ref.dtype)
    f_ref[0] = f.astype(f_ref.dtype)
    o_ref[0] = o.astype(o_ref.dtype)
    blk_ref[0] = blk.astype(blk_ref.dtype)
    c_ref[0] = c_new.astype(c_ref.dtype)


def _fwd_only_kernel(xg_ref, wr_ref, wci_ref, wcf_ref, wco_ref, h0_ref,
                     c0_ref, h_ref, hl_ref, cl_ref, h_scr, c_scr, *, n: int):
    """Inference variant: h sequence + final carries only — no residual
    streaming (5/6 of the full variant's output bandwidth)."""
    nt = pl.num_programs(1)
    _, _, _, _, c_new, h_new = _cell(
        xg_ref, wr_ref, wci_ref, wcf_ref, wco_ref, h0_ref, c0_ref,
        h_scr, c_scr, n)
    h_ref[0] = h_new.astype(h_ref.dtype)

    @pl.when(pl.program_id(1) == nt - 1)
    def _final():
        hl_ref[...] = h_new.astype(hl_ref.dtype)
        cl_ref[...] = c_new.astype(cl_ref.dtype)


def _fwd_pallas(xg, wr, wci, wcf, wco, h0, c0, block_b: int, interpret: bool,
                with_residuals: bool = True):
    """xg: [t, b, 4n] → with_residuals: (h_seq, (i, f, o, blk, c));
    else (h_seq, (h_last, c_last)) with no residual streaming."""
    t, b, g4 = xg.shape
    n = g4 // 4
    nb = b // block_b
    kernel = functools.partial(
        _fwd_kernel if with_residuals else _fwd_only_kernel, n=n)
    step_spec = lambda last: pl.BlockSpec((1, block_b, last),
                                          lambda i, s: (s, i, 0), **_VMEM)
    wr_spec = pl.BlockSpec((n, g4), lambda i, s: (0, 0), **_VMEM)
    row_spec = pl.BlockSpec((1, n), lambda i, s: (0, 0), **_VMEM)
    carry_spec = pl.BlockSpec((block_b, n), lambda i, s: (i, 0), **_VMEM)
    if with_residuals:
        out_specs = [step_spec(n)] * 6
        out_shape = [jax.ShapeDtypeStruct((t, b, n), xg.dtype)] * 6
    else:
        out_specs = [step_spec(n), carry_spec, carry_spec]
        out_shape = [jax.ShapeDtypeStruct((t, b, n), xg.dtype),
                     jax.ShapeDtypeStruct((b, n), xg.dtype),
                     jax.ShapeDtypeStruct((b, n), jnp.float32)]
    out = pl.pallas_call(
        kernel,
        grid=(nb, t),
        in_specs=[step_spec(g4), wr_spec, row_spec, row_spec, row_spec,
                  carry_spec, carry_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[_scratch((block_b, n), xg.dtype),
                        _scratch((block_b, n))],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
        name="lstm_fwd" if with_residuals else "lstm_fwd_only",
    )(xg, wr, wci.reshape(1, n), wcf.reshape(1, n), wco.reshape(1, n),
      h0, c0)
    return out[0], tuple(out[1:])


def _bptt_gates(i_t, f_t, o_t, blk_t, c_prev, th, dh, dc_carry,
                wci, wcf, wco):
    """ONE reverse Graves step's gate-derivative chain — the shared
    body of the Pallas backward and the XLA residual BPTT (the _cell
    principle applied to the backward: the two paths can never
    desynchronize). All operands f32. Returns (da_i, da_f, da_o, da_g,
    dc_next)."""
    do = dh * th
    da_o = do * o_t * (1.0 - o_t)
    dc = dh * o_t * (1.0 - th * th) + dc_carry + da_o * wco
    dblk = dc * i_t
    da_g = dblk * (1.0 - blk_t * blk_t)
    di = dc * blk_t
    da_i = di * i_t * (1.0 - i_t)
    df = dc * c_prev
    da_f = df * f_t * (1.0 - f_t)
    dc_next = dc * f_t + da_i * wci + da_f * wcf
    return da_i, da_f, da_o, da_g, dc_next


def _bwd_kernel(i_ref, f_ref, o_ref, blk_ref, c_ref, cprev_ref, oprev_ref,
                gout_ref, wr_ref, wci_ref, wcf_ref, wco_ref, h0_ref, c0_ref,
                gclast_ref,
                dg_ref, dh0_ref, dc0_ref, dwr_ref, dwci_ref, dwcf_ref,
                dwco_ref,
                dh_scr, dc_scr, dwr_scr, dwci_scr, dwcf_scr, dwco_scr,
                *, n: int):
    """Fused BPTT step (reverse time): gate-derivative math + BOTH
    per-step gemms (dh recurrence dg@Wrᵀ and the dWr accumulation
    h_prevᵀ@dg) against VMEM-resident carries and a VMEM-resident
    [n, 4n] f32 dWr accumulator — the flash-bwd pattern applied to the
    LSTM scan. Grid (batch_blocks, t) with the time index map REVERSED;
    peephole/bias-free residuals (i, f, o, blk, c) stream in from the
    forward kernel, dg streams out for the (parallel, outside-kernel)
    input-projection gradients."""
    s = pl.program_id(1)
    nt = pl.num_programs(1)
    bi = pl.program_id(0)
    nb = pl.num_programs(0)

    @pl.when(s == 0)
    def _init_carries():
        dh_scr[:] = jnp.zeros_like(dh_scr)
        dc_scr[:] = gclast_ref[...].astype(jnp.float32)

    @pl.when((s == 0) & (bi == 0))
    def _init_weight_accums():
        dwr_scr[:] = jnp.zeros_like(dwr_scr)
        dwci_scr[:] = jnp.zeros_like(dwci_scr)
        dwcf_scr[:] = jnp.zeros_like(dwcf_scr)
        dwco_scr[:] = jnp.zeros_like(dwco_scr)

    f32 = jnp.float32
    i_t = i_ref[0].astype(f32)
    f_t = f_ref[0].astype(f32)
    o_t = o_ref[0].astype(f32)
    blk_t = blk_ref[0].astype(f32)
    c_t = c_ref[0].astype(f32)
    is_t0 = s == nt - 1  # reversed: the last program handles time 0
    c_prev = jnp.where(is_t0, c0_ref[...].astype(f32),
                       cprev_ref[0].astype(f32))
    th = jnp.tanh(c_t)
    dh = gout_ref[0].astype(f32) + dh_scr[:]
    da_i, da_f, da_o, da_g, dc_next = _bptt_gates(
        i_t, f_t, o_t, blk_t, c_prev, th, dh, dc_scr[:],
        wci_ref[0], wcf_ref[0], wco_ref[0])
    dc_scr[:] = dc_next
    dg = jnp.concatenate([da_i, da_f, da_o, da_g], axis=-1)  # [bb, 4n]
    dg_ref[0] = dg.astype(dg_ref.dtype)
    wdt = wr_ref.dtype
    # dh recurrence: dg @ Wrᵀ, f32 accumulation on bf16 operands
    dh_scr[:] = jax.lax.dot_general(
        dg.astype(wdt), wr_ref[...], (((1,), (1,)), ((), ())),
        preferred_element_type=f32)
    # dWr accumulation over time IN VMEM: h_prevᵀ @ dg
    h_prev = jnp.where(is_t0, h0_ref[...].astype(f32),
                       oprev_ref[0].astype(f32) * jnp.tanh(c_prev))
    dwr_scr[:] += jax.lax.dot_general(
        h_prev.astype(wdt), dg.astype(wdt), (((0,), (0,)), ((), ())),
        preferred_element_type=f32)
    dwci_scr[0] += jnp.sum(da_i * c_prev, axis=0)
    dwcf_scr[0] += jnp.sum(da_f * c_prev, axis=0)
    dwco_scr[0] += jnp.sum(da_o * c_t, axis=0)

    @pl.when(s == nt - 1)
    def _final_carries():  # this batch block's sweep is done
        dh0_ref[...] = dh_scr[:].astype(dh0_ref.dtype)
        dc0_ref[...] = dc_scr[:].astype(dc0_ref.dtype)

    @pl.when((s == nt - 1) & (bi == nb - 1))
    def _final_weights():
        dwr_ref[...] = dwr_scr[:].astype(dwr_ref.dtype)
        dwci_ref[...] = dwci_scr[:].astype(dwci_ref.dtype)
        dwcf_ref[...] = dwcf_scr[:].astype(dwcf_ref.dtype)
        dwco_ref[...] = dwco_scr[:].astype(dwco_ref.dtype)


def _bwd_pallas(res, wr, wci, wcf, wco, h0, c0, gout, g_clast,
                block_b: int, interpret: bool):
    """Reverse-time Pallas BPTT over streamed forward residuals.
    Returns (dg_seq, dwr, dwci, dwcf, dwco, dh0, dc0) in f32 (except
    dg_seq, emitted in the residual dtype for the outer projections)."""
    i, f, o, blk, c = res
    t, b, n = i.shape
    g4 = 4 * n
    nb = b // block_b
    kernel = functools.partial(_bwd_kernel, n=n)
    rev = lambda last: pl.BlockSpec((1, block_b, last),
                                    lambda bi, s: (t - 1 - s, bi, 0), **_VMEM)
    # previous-timestep view: index t-2-s clamped at 0 (the t==0 program
    # overrides with h0/c0 in-kernel, so the clamped read is discarded)
    prev = pl.BlockSpec((1, block_b, n),
                        lambda bi, s: (jnp.maximum(t - 2 - s, 0), bi, 0),
                        **_VMEM)
    wr_spec = pl.BlockSpec((n, g4), lambda bi, s: (0, 0), **_VMEM)
    row_spec = pl.BlockSpec((1, n), lambda bi, s: (0, 0), **_VMEM)
    carry_spec = pl.BlockSpec((block_b, n), lambda bi, s: (bi, 0), **_VMEM)
    out = pl.pallas_call(
        kernel,
        grid=(nb, t),
        in_specs=[rev(n)] * 5 + [prev, prev, rev(n), wr_spec,
                                 row_spec, row_spec, row_spec,
                                 carry_spec, carry_spec, carry_spec],
        out_specs=[rev(g4), carry_spec, carry_spec, wr_spec,
                   row_spec, row_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((t, b, g4), i.dtype),
                   jax.ShapeDtypeStruct((b, n), jnp.float32),
                   jax.ShapeDtypeStruct((b, n), jnp.float32),
                   jax.ShapeDtypeStruct((n, g4), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        scratch_shapes=[_scratch((block_b, n)), _scratch((block_b, n)),
                        _scratch((n, g4)), _scratch((1, n)),
                        _scratch((1, n)), _scratch((1, n))],
        # BOTH dims "arbitrary": the dWr/peephole accumulators live in
        # scratch SHARED across batch blocks (init at bi==0, store at
        # bi==nb-1) — a "parallel" first dim would let a multi-core
        # Mosaic schedule split the blocks across cores and silently
        # lose contributions. (v5e is single-core; this is for v4/v5p.)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="lstm_bptt",
    )(i, f, o, blk, c, c, o, gout, wr,
      wci.reshape(1, n), wcf.reshape(1, n), wco.reshape(1, n),
      h0, c0, g_clast)
    dg_seq, dh0, dc0, dwr, dwci, dwcf, dwco = out
    return (dg_seq, dwr, dwci.reshape(n), dwcf.reshape(n),
            dwco.reshape(n), dh0, dc0)


#: VMEM budget gate for the backward kernel: the f32 [n, 4n] dWr
#: accumulator (4n²·4 bytes) + resident Wr + step blocks must fit the
#: ~16MB scoped budget — n=512 uses ~10MB, n=1024 would need 16MB for
#: the accumulator alone
_BWD_MAX_N = 512


def _bwd_from_residuals(res, wr, wci, wcf, wco, h0, c0, g_hseq, g_hlast,
                        g_clast):
    """Hand-written BPTT from forward residuals.

    res: (i, f, o, blk, c) each [t, b, n]; g_hseq [t, b, n] cotangent
    of the h sequence; g_hlast/g_clast cotangents of the final carry.
    Returns (d_xg, dWr, dwci, dwcf, dwco, dh0, dc0).
    """
    i, f, o, blk, c = (r.astype(jnp.float32) for r in res)
    t, b, n = i.shape
    wr_w = wr  # bf16 gemm operand; f32 accumulation via preferred type
    c_prev = jnp.concatenate([c0.astype(jnp.float32)[None], c[:-1]], axis=0)
    tanh_c = jnp.tanh(c)
    gout = g_hseq.astype(jnp.float32).at[-1].add(
        g_hlast.astype(jnp.float32))

    def step(carry, inp):
        dh_rec, dc_carry = carry
        i_t, f_t, o_t, blk_t, c_t, cp_t, th_t, gout_t = inp
        dh = gout_t + dh_rec
        da_i, da_f, da_o, da_g, dc_next = _bptt_gates(
            i_t, f_t, o_t, blk_t, cp_t, th_t, dh, dc_carry,
            wci, wcf, wco)
        dg = jnp.concatenate([da_i, da_f, da_o, da_g], axis=-1)  # [b, 4n]
        dh_next = jax.lax.dot_general(
            dg.astype(wr_w.dtype), wr_w, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        return (dh_next, dc_next), dg

    zero = jnp.zeros((b, n), jnp.float32)
    (dh0, dc0), dg_seq = jax.lax.scan(
        step, (zero, g_clast.astype(jnp.float32)),
        (i, f, o, blk, c, c_prev, tanh_c, gout),
        reverse=True)
    # non-sequential reductions hoisted to full-sequence einsums;
    # h_{t-1} = o_{t-1} * tanh(c_{t-1}) with h_{-1} = h0
    h_prev = jnp.concatenate(
        [h0.astype(jnp.float32)[None], (o * tanh_c)[:-1]], axis=0)
    dwr = jnp.einsum("tbn,tbg->ng", h_prev, dg_seq,
                     preferred_element_type=jnp.float32)
    da_i, da_f, da_o = (dg_seq[..., :n], dg_seq[..., n:2 * n],
                        dg_seq[..., 2 * n:3 * n])
    dwci = jnp.sum(da_i * c_prev, axis=(0, 1))
    dwcf = jnp.sum(da_f * c_prev, axis=(0, 1))
    dwco = jnp.sum(da_o * c, axis=(0, 1))
    return dg_seq, dwr, dwci, dwcf, dwco, dh0, dc0


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8))
def _fused(xg, wr, wci, wcf, wco, h0, c0, block_b, interpret):
    # primal (not being differentiated): the fwd-only kernel — no
    # residual streaming (5/6 less output bandwidth)
    h_seq, (h_last, c_last) = _fwd_pallas(
        xg, wr, wci, wcf, wco, h0, c0, block_b, interpret,
        with_residuals=False)
    return h_seq, h_last, c_last


def _vjp_fwd(xg, wr, wci, wcf, wco, h0, c0, block_b, interpret):
    h_seq, res = _fwd_pallas(xg, wr, wci, wcf, wco, h0, c0, block_b,
                             interpret)
    return ((h_seq, h_seq[-1], res[4][-1].astype(jnp.float32)),
            (res, wr, wci, wcf, wco, h0, c0))


def _use_pallas_bwd(t: int, b: int, n: int, block_b: int,
                    itemsize: int = 2) -> bool:
    """The fused backward applies within its VMEM budget unless
    DL4J_TPU_LSTM_BWD=xla forces the scan BPTT (A/B seam). The budget
    (_BWD_MAX_N) was measured for bf16 streams; f32 residual/gout/dg
    blocks double the footprint, so the admitted n halves with
    itemsize."""
    import os
    if os.environ.get("DL4J_TPU_LSTM_BWD", "").lower() == "xla":
        return False
    return n * itemsize <= _BWD_MAX_N * 2 and b % block_b == 0


def _vjp_bwd(block_b, interpret, saved, cotangents):
    res, wr, wci, wcf, wco, h0, c0 = saved
    g_hseq, g_hlast, g_clast = cotangents
    t, b, n = res[0].shape
    if _use_pallas_bwd(t, b, n, block_b, itemsize=res[0].dtype.itemsize):
        # fold the final-h cotangent into the sequence stream; the
        # final-c cotangent enters the kernel's dc carry directly
        gout = g_hseq.astype(jnp.float32).at[-1].add(
            g_hlast.astype(jnp.float32)).astype(res[0].dtype)
        import os
        bwd_block = min(block_b,
                        int(os.environ.get("DL4J_TPU_LSTM_BWD_BLOCK",
                                           "128")))
        if b % bwd_block != 0:  # a non-dividing sweep override would
            bwd_block = block_b  # silently truncate the batch grid
        dg_seq, dwr, dwci, dwcf, dwco, dh0, dc0 = _bwd_pallas(
            res, wr, wci.astype(jnp.float32).reshape(1, n),
            wcf.astype(jnp.float32).reshape(1, n),
            wco.astype(jnp.float32).reshape(1, n), h0,
            c0.astype(jnp.float32),
            gout, g_clast.astype(jnp.float32),
            bwd_block, interpret)
    else:
        dg_seq, dwr, dwci, dwcf, dwco, dh0, dc0 = _bwd_from_residuals(
            res, wr, wci.astype(jnp.float32), wcf.astype(jnp.float32),
            wco.astype(jnp.float32), h0, c0, g_hseq, g_hlast, g_clast)
    # cotangents must match the primal dtypes (bf16 params included)
    return (dg_seq.astype(res[0].dtype), dwr.astype(wr.dtype),
            dwci.astype(wci.dtype), dwcf.astype(wcf.dtype),
            dwco.astype(wco.dtype), dh0.astype(h0.dtype),
            dc0.astype(c0.dtype))


_fused.defvjp(_vjp_fwd, _vjp_bwd)


def _pick_block_b(b: int) -> int:
    # 256 rows max: six double-buffered per-step output blocks + the
    # xg block + resident Wr must fit the 16MB scoped-VMEM budget
    for cand in (256, 128, 64, 32, 16, 8):
        if b % cand == 0:
            return cand
    return 0


def _on_tpu() -> bool:  # patchable seam for tests
    return not pallas_interpret()


#: largest hidden size the kernel accepts per dtype width: the
#: VMEM-resident [n, 4n] recurrent weight is 4n²·itemsize bytes and
#: must leave room for the step blocks inside the ~16MB scoped budget
_MAX_N = {2: 1024, 4: 512}


def fused_lstm_applicable(b: int, n: int, gate_act: str, block_act: str,
                          mask, itemsize: int = 2) -> bool:
    """The kernel covers the default Graves configuration on tileable
    shapes ON TPU; everything else keeps the XLA scan (on CPU/GPU hosts
    the kernel would run under the Pallas interpreter, orders of
    magnitude slower — tests exercise it by calling fused_lstm_scan
    directly). ``itemsize``: activation dtype width in bytes (bounds
    the VMEM-resident weight)."""
    return (_on_tpu()
            and mask is None and gate_act == "sigmoid"
            and block_act == "tanh"
            and n % 128 == 0 and n <= _MAX_N.get(itemsize, 512)
            and _pick_block_b(b) > 0)


def train_fused_enabled() -> bool:
    """Training routes through the fused kernels (fwd + Pallas BPTT) by
    DEFAULT — measured 63.5% vs 28.8% MFU for the XLA scan-grad at the
    char-RNN bench shape (r5, BASELINE.md). DL4J_TPU_LSTM_TRAIN=xla is
    the escape hatch back to the scan."""
    import os
    return os.environ.get("DL4J_TPU_LSTM_TRAIN", "").lower() != "xla"


def fused_lstm_train_applicable(b: int, n: int, gate_act: str,
                                block_act: str, mask,
                                itemsize: int = 2) -> bool:
    """Training additionally requires the PALLAS backward to apply
    (n within the dWr-accumulator VMEM budget): falling back to the
    XLA residual BPTT from the fused forward measured SLOWER than the
    plain scan-grad (21% vs 28.8%, r3/r4), so larger hiddens keep the
    XLA scan for training. The budget scales with the stream dtype:
    bf16 admits n<=512, f32 n<=256. ``DL4J_TPU_LSTM_BWD=xla`` (the
    documented A/B seam, mirroring ``_use_pallas_bwd``) restores the
    plain XLA scan end to end — without this gate it silently
    dispatched the SLOWER fused-fwd + XLA-bwd combination."""
    import os
    if os.environ.get("DL4J_TPU_LSTM_BWD", "").lower() == "xla":
        return False
    return (train_fused_enabled() and n * itemsize <= _BWD_MAX_N * 2
            and fused_lstm_applicable(b, n, gate_act, block_act, mask,
                                      itemsize=itemsize))


def fused_lstm_scan(xg, wr, wci, wcf, wco, h0, c0
                    ) -> Tuple[jnp.ndarray, Tuple[jnp.ndarray, jnp.ndarray]]:
    """xg [t, b, 4n] pre-projected gates → (h_seq [t, b, n], (h_T, c_T)).

    Differentiable end-to-end (custom VJP above); the final carries
    flow gradients too, so TBPTT chunk boundaries behave exactly like
    the XLA scan's.
    """
    t, b, g4 = xg.shape
    block_b = _pick_block_b(b)
    if block_b == 0:
        raise ValueError(
            f"batch {b} is not tileable (must be a multiple of 8); "
            f"gate with fused_lstm_applicable or use the XLA scan")
    h_seq, h_last, c_last = _fused(xg, wr, wci, wcf, wco, h0, c0,
                                   block_b, pallas_interpret())
    return h_seq, (h_last, c_last)
