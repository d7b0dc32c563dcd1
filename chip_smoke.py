#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

    python3 chip_smoke.py                  # needs a TPU; the driver's check
    python3 chip_smoke.py --rehearsal      # CPU, tiny widths, NOT a chip result
    python3 chip_smoke.py --only serve     # builder's debugging: some phases

One process, no children, seeded synthetic tokens, no network. Drives the
two main paths — train and serve — through the entry points a user calls,
at the full width of the one model the repo both trains and serves (zoo
GPT, vocab 8192 / d_model 1024 / 16 layers / 8 heads of 128 / seq 2048,
bf16 compute), then every Pallas kernel at its benchmark shape against a
``jax.numpy`` reference, a device trace, and the host<->device clock.
With four or more devices it adds the same paths on exactly four chips.

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only
if every phase passed. A phase that raises, produces a non-finite value
or misses an assertion ends the run with a traceback and a non-zero code
(``--keep-going`` runs the remaining phases first — the exit code is
still non-zero). Without a TPU the default invocation exits 2 before
doing anything.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import tempfile
import time
import traceback

_HERE = os.path.dirname(os.path.abspath(__file__))

#: the full-width configuration and the CPU rehearsal's stand-in
FULL = dict(
    vocab=8192, d_model=1024, n_layers=16, heads=8, seq=2048, batch=8,
    learning_rate=1e-4, scan_steps=48, new_tokens=64,
    prompt_lengths=(50, 120, 250, 500, 700, 1000, 1300, 1500),
    preamble=512, preamble_tails=(20, 75, 130, 190),
    decode_slots=8, decode_burst=8, kv_block_size=16,
    preempt_lengths=(1500, 1400, 1000, 800),
    classify_len=128,
    flash_shapes=(  # (batch, time, heads, head_dim, backward too)
        (8, 2048, 8, 128, True), (16, 1024, 8, 64, True),
        (1, 16384, 8, 128, False), (1, 32768, 8, 128, True)),
    rotary_shapes=((2, 4096, 16, 128), (2, 2048, 8, 64)),
    lstm=dict(vocab=64, hidden=512, seq=128, batch=1024),
    gemm_n=8192, gemm_chain=8,
)
TINY = dict(
    vocab=64, d_model=32, n_layers=2, heads=4, seq=64, batch=4,
    learning_rate=3e-3, scan_steps=12, new_tokens=4,
    prompt_lengths=(5, 9, 14, 20, 27, 33, 40, 50),
    preamble=16, preamble_tails=(3, 6, 9, 12),
    decode_slots=4, decode_burst=2, kv_block_size=4,
    preempt_lengths=(50, 44, 33, 27),
    classify_len=16,
    flash_shapes=((2, 64, 2, 16, True), (1, 128, 2, 8, False)),
    rotary_shapes=((2, 64, 2, 64), (1, 32, 3, 16)),
    lstm=dict(vocab=16, hidden=128, seq=8, batch=8),
    gemm_n=256, gemm_chain=2,
)

#: normalized max-abs error (max|a-b| / max|b|) allowed between a bf16
#: kernel and the float32 jax.numpy formulation on the same bf16 inputs:
#: bf16 keeps 8 mantissa bits (2^-8 = 0.4% per rounding) and each kernel
#: chains a handful of roundings (scores, weights, accumulator casts)
FLASH_TOL = 2e-2
LSTM_TOL = 2e-2
#: one bfloat16 rounding of the result, and float32 angles at 4k positions
ROTARY_TOL = 1e-2
#: ... and for the LSTM's gradients, which compound 128 recurrent steps
#: of it and (the bias) sum 131072 bf16 terms: 5e-2 measured under the
#: interpreter at the benchmark shape, the rest under 1e-2
LSTM_GRAD_TOL = 1e-1


class Smoke:
    """Run state shared by the phases: configuration, labelled output,
    compile accounting, and what later phases reuse from earlier ones."""

    def __init__(self, cfg, rehearsal: bool):
        self.cfg = cfg
        self.rehearsal = rehearsal
        self.net = None           # the trained d1024/L16 GPT
        self.train_rows = None    # [batch, seq] int tokens it memorized
        from deeplearning4j_tpu.util.compile_cache import CompileWatch
        self.watch = CompileWatch()

    @property
    def on_chip(self) -> bool:
        return not self.rehearsal

    def log(self, phase: str, msg: str) -> None:
        prefix = "rehearsal, not a chip result | " if self.rehearsal else ""
        print(f"{prefix}[{phase}] {msg}", flush=True)

    def compiles_since(self, before):
        now = self.watch.snapshot()
        return {k: round(now[k] - before[k], 3) for k in now}


def _normalized_error(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    assert np.isfinite(got).all(), "non-finite kernel output"
    return float(np.max(np.abs(got - want)) / max(np.max(np.abs(want)), 1e-30))


def _mosaic_kernels(compiled_text: str):
    """{kernel name: count} over the Mosaic custom calls of a compiled
    module — a Pallas kernel that ran interpreted leaves none."""
    import re
    out = {}
    for line in compiled_text.split("\n"):
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        m = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call', line)
        name = m.group(1) if m else "?"
        out[name] = out.get(name, 0) + 1
    return out


def _fit_step_text(net, x, y) -> str:
    """The program ``net.fit`` runs on (x, y), as compiled (the persistent
    cache hands back the executable fit() just built)."""
    import jax.numpy as jnp
    step = net._get_jit("train", fm=False, lm=False)
    zero = jnp.zeros((), net._dtype)
    return step.lower(net.params, net.opt_state, net.states,
                      jnp.asarray(x, net._dtype), jnp.asarray(y, net._dtype),
                      zero, zero, net._train_rng()).compile().as_text()


# ------------------------------------------------------------------ train

def phase_train(s: Smoke) -> None:
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.transformer import gpt

    c = s.cfg
    t0 = time.perf_counter()
    net = gpt(vocab_size=c["vocab"], d_model=c["d_model"],
              n_layers=c["n_layers"], num_heads=c["heads"],
              max_len=c["seq"], learning_rate=c["learning_rate"],
              seed=0).init()
    n_params = sum(int(np.prod(v.shape)) for ld in net.params.values()
                   for v in ld.values())
    s.log("train", f"gpt d{c['d_model']}/L{c['n_layers']}/h{c['heads']}"
                   f"/seq{c['seq']}/vocab{c['vocab']} bf16: "
                   f"{n_params / 1e6:.1f}M params, init "
                   f"{time.perf_counter() - t0:.1f}s")

    rows = np.random.default_rng(0).integers(
        0, c["vocab"], (c["batch"], c["seq"]))
    # sparse next-token labels (ops/losses.py); ONE batch, repeated, so
    # a falling loss is memorization — which also gives the serve phase
    # peaked logits: greedy tokens then do not hang on a rounding bit
    data = DataSet(rows.astype(np.float32),
                   np.roll(rows, -1, axis=1).astype(np.float32))
    before = s.watch.snapshot()
    staged = net.stage_scan(data, c["batch"])
    t0 = time.perf_counter()
    scores = net.fit_scan(None, c["batch"], epochs=c["scan_steps"],
                          staged=staged)
    t_first = time.perf_counter() - t0
    assert scores.shape == (c["scan_steps"],), scores.shape
    assert np.isfinite(scores).all(), f"non-finite fit_scan loss {scores}"
    assert scores[-1] < scores[0], f"loss not falling: {scores}"
    t0 = time.perf_counter()
    scores2 = net.fit_scan(None, c["batch"], epochs=c["scan_steps"],
                           staged=staged)
    t_warm = time.perf_counter() - t0
    assert np.isfinite(scores2).all() and scores2[-1] < scores[0], scores2
    s.log("train", f"fit_scan {c['scan_steps']} steps of "
                   f"{c['batch']}x{c['seq']}: loss {scores[0]:.3f} -> "
                   f"{scores[-1]:.3f} -> {scores2[-1]:.3f}; first call "
                   f"{t_first:.1f}s (compile included), second "
                   f"{t_warm:.2f}s = {t_warm / c['scan_steps'] * 1e3:.1f} "
                   f"ms/step")

    t0 = time.perf_counter()
    net.fit(data)                      # the plain per-step path
    fit_score = float(net.score())
    assert np.isfinite(fit_score) and fit_score < scores[0], fit_score
    s.log("train", f"fit one step: loss {fit_score:.3f}, "
                   f"{time.perf_counter() - t0:.1f}s (compile included); "
                   f"programs {s.compiles_since(before)}")

    # the program fit() just ran, as compiled: flash_attention() must
    # have taken its Pallas branch, compiled by Mosaic, in every block
    t0 = time.perf_counter()
    text = _fit_step_text(net, data.features, data.labels)
    kernels = _mosaic_kernels(text)
    s.log("train", f"compiled fit step: Mosaic custom calls {kernels} "
                   f"({time.perf_counter() - t0:.1f}s to re-obtain)")
    if s.on_chip:
        from deeplearning4j_tpu.ops.flash_attention import flash_path
        head = c["d_model"] // c["heads"]
        backward = {"resident": ("flash_dq_dkv",),
                    "streamed": ("flash_dq", "flash_dkv")}[
            flash_path(c["seq"], c["seq"], head, "bfloat16")]
        want = {k: c["n_layers"] for k in ("flash_fwd",) + backward}
        assert kernels == want, (
            f"flash attention is not running as Mosaic kernels in every "
            f"block: found {kernels}, want {want}")
    else:
        assert kernels == {}, kernels  # interpreter: plain HLO only
    s.net, s.train_rows = net, rows


# ------------------------------------------------------------------ serve

def _sampler(i: int) -> dict:
    """Request i's sampler: even requests decode greedily, odd ones
    sample (seeded, so a replay draws the same tokens)."""
    return dict(temperature=0.8, top_k=40, seed=100 + i) if i % 2 else {}


def _submit_all(engine, prompts, new_tokens):
    """Queue every request on a not-yet-started engine, then start it:
    the whole schedule is then a function of the request set alone, so
    a second engine fed the same set replays it program for program."""
    futures = [engine.submit_generate(p[None, :], new_tokens, **_sampler(i))
               for i, p in enumerate(prompts)]
    engine.start()
    return [f.result(timeout=900) for f in futures]


def _check_generated(s, prompts, outs):
    import numpy as np
    c = s.cfg
    for p, out in zip(prompts, outs):
        out = np.asarray(out)
        assert out.shape == (1, len(p) + c["new_tokens"]), out.shape
        assert (out[0, :len(p)] == p).all(), "prompt not echoed"
        assert ((out >= 0) & (out < c["vocab"])).all(), "token out of range"


def _match(got, want, t0) -> tuple:
    """(first generated token equal, share of the remaining equal)."""
    import numpy as np
    g, w = np.asarray(got)[0, t0:], np.asarray(want)[0, t0:]
    return bool(g[0] == w[0]), float(np.mean(g[1:] == w[1:]))


def _drained(engine) -> dict:
    assert engine.drain(timeout=60), "engine did not drain"
    return engine.stats()["scheduler"]


def phase_serve(s: Smoke) -> None:
    import numpy as np
    from deeplearning4j_tpu import monitor
    from deeplearning4j_tpu.parallel.inference import ParallelInference

    c, net, rows = s.cfg, s.net, s.train_rows
    assert net is not None, "serve needs the train phase's net"
    new = c["new_tokens"]
    jit_miss = lambda: monitor.get_registry().family_total(
        monitor.JIT_CACHE_MISS_COUNTER)

    # prompts are prefixes of the memorized rows; the preamble group are
    # nested prefixes of row 0 (they share its first `preamble` tokens)
    spread = [rows[i % len(rows), :n]
              for i, n in enumerate(c["prompt_lengths"])]
    shared = [rows[0, :c["preamble"] + t] for t in c["preamble_tails"]]
    prompts = spread + shared
    greedy = [i for i in range(len(prompts)) if i % 2 == 0]

    def continuous(**kw):
        return ParallelInference(
            net, continuous=True, start=False,
            decode_slots=c["decode_slots"], decode_burst=c["decode_burst"],
            kv_block_size=c["kv_block_size"], **kw)

    # -- 1. continuous batching over the paged pool. Pass 1 is the
    # warm-up: it compiles exactly the programs this traffic uses (the
    # full warmup() ladder is ~100 programs at this depth and nothing
    # compiled survives a chip call). Pass 2 replays the same request
    # set on a fresh engine: steady state, zero compiles allowed.
    before = s.watch.snapshot()
    t0 = time.perf_counter()
    eng = continuous()
    outs = _submit_all(eng, prompts, new)
    _drained(eng)
    eng.shutdown()
    t_warm = time.perf_counter() - t0
    _check_generated(s, prompts, outs)
    setup = s.compiles_since(before)
    s.log("serve", f"continuous warm-up pass: {len(prompts)} requests "
                   f"({min(map(len, prompts))}..{max(map(len, prompts))} "
                   f"prompt tokens, {new} new, greedy+sampled) in "
                   f"{t_warm:.1f}s; set-up: {setup['compiles']:.0f} programs, "
                   f"{setup['compile_seconds']:.1f}s compiling, persistent "
                   f"cache {setup['cache_hits']:.0f} hits / "
                   f"{setup['cache_misses']:.0f} misses")
    before, miss0 = s.watch.snapshot(), jit_miss()
    t0 = time.perf_counter()
    eng = continuous()
    outs2 = _submit_all(eng, prompts, new)
    sched = _drained(eng)
    t_steady = time.perf_counter() - t0
    steady = s.compiles_since(before)
    pool = sched["pool"]
    s.log("serve", f"continuous steady pass: {t_steady:.2f}s, "
                   f"{sched['bursts']} bursts, {sched['preemptions']} "
                   f"preemptions, pool {pool['blocks_free']}/"
                   f"{pool['blocks_total']} blocks free after drain, "
                   f"{steady['compiles']:.0f} XLA compiles, "
                   f"{jit_miss() - miss0:.0f} jit-cache misses")
    eng.shutdown()
    _check_generated(s, prompts, outs2)
    assert pool["blocks_free"] == pool["blocks_total"], f"leaked blocks: {pool}"
    assert jit_miss() == miss0 and steady["compiles"] == 0, (
        f"compiles after warm-up: {steady}")
    for i, (a, b) in enumerate(zip(outs, outs2)):
        assert (np.asarray(a) == np.asarray(b)).all(), (
            f"request {i}: replayed schedule produced different tokens")

    # -- 2. the default engine (continuous=False): one classify round
    # and the whole-burst generate path, which IS net.generate — so it
    # doubles as the reference the continuous tokens are held to.
    # max_batch_size=1: every request dispatches alone, in the same
    # program a solo net.generate compiles.
    before = s.watch.snapshot()
    with ParallelInference(net, max_batch_size=1) as default:
        x = rows[:1, :c["classify_len"]].astype(np.float32)
        probs = np.asarray(default.submit(x).result(timeout=600))
        direct = np.asarray(net.output(x))
        assert probs.shape == (1, c["classify_len"], c["vocab"]), probs.shape
        assert np.isfinite(probs).all()
        np.testing.assert_allclose(probs, direct, rtol=1e-5, atol=1e-7)
        s.log("serve", f"default engine submit(): [1,{c['classify_len']}] "
                       f"-> {probs.shape}; engine.output(x) == "
                       f"net.output(x) bit for bit: "
                       f"{bool((probs == direct).all())}")
        ref_futs = {i: default.submit_generate(prompts[i][None, :], new)
                    for i in greedy}
        refs = {i: np.asarray(f.result(timeout=900))
                for i, f in ref_futs.items()}
    direct = net.generate(prompts[greedy[0]][None, :], new)
    assert (np.asarray(direct) == refs[greedy[0]]).all(), (
        "default engine submit_generate != net.generate")
    s.log("serve", f"default engine submit_generate(): {len(refs)} greedy "
                   f"requests == solo net.generate; programs "
                   f"{s.compiles_since(before)}")
    firsts, rest, truth = [], [], []
    for i in greedy:
        n = len(prompts[i])
        first, share = _match(outs2[i], refs[i], n)
        firsts.append(first)
        rest.append(share)
        row = rows[i % len(rows)] if i < len(spread) else rows[0]
        want = row[n:n + new]
        truth.append(float(np.mean(
            np.asarray(outs2[i])[0, n:n + len(want)] == want)))
    s.log("serve", f"continuous vs net.generate, greedy: first token equal "
                   f"{sum(firsts)}/{len(firsts)}, later tokens equal "
                   f"{np.mean(rest):.3f} (min {min(rest):.3f}); tokens equal "
                   f"to the memorized rows {np.mean(truth):.3f}")
    assert all(firsts), f"first greedy token differs from net.generate: {firsts}"

    # -- 3. prefix cache: one request plants the preamble's blocks,
    # the rest admit through the shared (refcounted, copy-on-write)
    # blocks and prefill only their tails.
    before = s.watch.snapshot()
    eng = continuous(prefix_cache=True).start()
    base = len(spread)
    outs_b = [eng.submit_generate(shared[0][None, :], new, **_sampler(base))
              .result(timeout=900)]
    futs = [eng.submit_generate(p[None, :], new, **_sampler(base + j))
            for j, p in enumerate(shared) if j > 0]
    outs_b += [f.result(timeout=900) for f in futs]
    sched = _drained(eng)
    pc, pool = sched["prefix_cache"], sched["pool"]
    s.log("serve", f"prefix cache: {pc['hits']} hits / {pc['misses']} misses, "
                   f"{pc['saved_prefill_tokens']} prefill tokens saved, "
                   f"{pc['cow_copies']} copy-on-write copies; pool "
                   f"{pool['blocks_free']} free + {pc['cached_blocks']} "
                   f"cached of {pool['blocks_total']}; programs "
                   f"{s.compiles_since(before)}")
    _check_generated(s, shared, outs_b)
    assert pc["hits"] >= len(shared) - 1, f"preamble not shared: {pc}"
    assert pool["blocks_free"] + pc["cached_blocks"] == pool["blocks_total"], (
        f"leaked blocks: {pool} {pc}")
    for cache in eng._scheduler.prefix_caches():
        cache.clear()
    pool = eng.stats()["scheduler"]["pool"]
    assert pool["blocks_free"] == pool["blocks_total"], f"leak: {pool}"
    eng.shutdown()
    for j, out in enumerate(outs_b):
        if (base + j) % 2 == 0:      # greedy: same tokens as uncached
            first, share = _match(out, outs2[base + j], len(shared[j]))
            s.log("serve", f"prefix cache request {j}: first token equal to "
                           f"uncached {first}, later tokens equal {share:.3f}")
            assert first, "cached admission changed the first token"

    # -- 4. a pool too small for its traffic: the first two prompts
    # fit, their generated tokens do not (one usable block short, block
    # 0 being the pool's trash block), so a sequence is preempted —
    # blocks given back mid-flight — and resumed.
    before = s.watch.snapshot()
    squeezed = [rows[i % len(rows), :n]
                for i, n in enumerate(c["preempt_lengths"])]
    blocks_for = lambda tokens: -(-tokens // c["kv_block_size"])
    kv_blocks = sum(blocks_for(n + new) for n in c["preempt_lengths"][:2])
    eng = continuous(kv_blocks=kv_blocks)
    outs_c = [eng.submit_generate(p[None, :], new) for p in squeezed]
    eng.start()
    outs_c = [f.result(timeout=900) for f in outs_c]
    sched = _drained(eng)
    eng.shutdown()
    pool = sched["pool"]
    s.log("serve", f"small pool ({kv_blocks} blocks): "
                   f"{sched['preemptions']} preemptions, "
                   f"{sched['resume_reprefill_tokens']} tokens re-prefilled, "
                   f"pool {pool['blocks_free']}/{pool['blocks_total']} free "
                   f"after drain; programs {s.compiles_since(before)}")
    _check_generated(s, squeezed, outs_c)
    assert sched["preemptions"] >= 1, "pool never ran out: nothing preempted"
    assert pool["blocks_free"] == pool["blocks_total"], f"leaked blocks: {pool}"
    for j, (p, out) in enumerate(zip(squeezed, outs_c)):
        n = len(p)
        want = rows[j % len(rows), n:n + new]
        share = float(np.mean(np.asarray(out)[0, n:n + len(want)] == want))
        s.log("serve", f"small pool, prompt {n}: tokens equal to the "
                       f"memorized row {share:.3f}")


# ---------------------------------------------------------------- kernels

def _flash_check(s: Smoke, b, t, h, d, backward: bool) -> None:
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(t + d)
    q, k, v = (jax.random.normal(jax.random.fold_in(key, i), (b, t, h, d),
                                 jnp.bfloat16) for i in range(3))
    # the [b,h,t,t] reference does not fit at 32k: hold the kernel to
    # the jax.numpy formulation on a sample of query rows (each output
    # row depends on its own query and every key), and make the loss
    # read those rows only so dK/dV have a full-size reference too
    n_rows = min(t, 256)
    idx = jnp.sort(jax.random.choice(jax.random.fold_in(key, 7), t,
                                     (n_rows,), replace=False))
    w = jax.random.normal(jax.random.fold_in(key, 8), (b, n_rows, h, d),
                          jnp.float32)

    # idx and w ride as arguments: closed over, XLA would spend the
    # compile constant-folding gathers and scatters of them
    def reference(q, k, v, idx):
        qs = q[:, idx].astype(jnp.float32)
        sc = jnp.einsum("bqhd,bkhd->bhqk", qs, k.astype(jnp.float32))
        sc = sc / jnp.sqrt(jnp.float32(d))
        sc = jnp.where(idx[:, None] >= jnp.arange(t)[None, :], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v.astype(jnp.float32))

    def kernel(q, k, v, idx):
        return flash_attention(q, k, v, causal=True)[:, idx]

    t0 = time.perf_counter()
    err = _normalized_error(jax.jit(kernel)(q, k, v, idx),
                            jax.jit(reference)(q, k, v, idx))
    msg = f"flash causal b{b} t{t} h{h} hd{d}: fwd err {err:.1e}"
    assert err <= FLASH_TOL, f"{msg} > {FLASH_TOL}"
    if backward:
        loss = lambda f: (lambda q, k, v, idx, w: jnp.sum(
            f(q, k, v, idx).astype(jnp.float32) * w))
        got = jax.jit(jax.grad(loss(kernel), (0, 1, 2)))(q, k, v, idx, w)
        want = jax.jit(jax.grad(loss(reference), (0, 1, 2)))(q, k, v, idx, w)
        errs = [_normalized_error(g, r) for g, r in zip(got, want)]
        msg += " dq/dk/dv err " + "/".join(f"{e:.1e}" for e in errs)
        assert max(errs) <= FLASH_TOL, f"{msg} > {FLASH_TOL}"
    s.log("kernels", f"{msg} (tol {FLASH_TOL}, vs float32 jax.numpy on "
                     f"{n_rows} query rows) {time.perf_counter() - t0:.1f}s")


def _rotary_check(s: Smoke, b, t, h, d) -> None:
    """``rotary`` (the Pallas pass where the heads are whole 128-lane
    column blocks: one roll a head of 128, two and a select where two
    heads share the lanes) and its gradient rule against the rotation
    written out in complex float32 on the same bfloat16 inputs."""
    import jax
    import jax.numpy as jnp
    from deeplearning4j_tpu.ops.attention import rotary

    theta = 1e6
    key = jax.random.PRNGKey(t + d)
    x, g = (jax.random.normal(jax.random.fold_in(key, i), (b, t, h, d),
                              jnp.bfloat16) for i in range(2))

    def reference(x):
        z = jax.lax.complex(*jnp.split(x.astype(jnp.float32), 2, axis=-1))
        angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * theta ** (
            -jnp.arange(d // 2, dtype=jnp.float32) / (d // 2))
        z = z * jnp.exp(1j * angle)
        return jnp.concatenate([z.real, z.imag], axis=-1)

    def both(f):
        def out_and_dx(x, g):
            out, pull = jax.vjp(f, x)
            return (out,) + pull(g.astype(out.dtype))
        return jax.jit(out_and_dx)
    t0 = time.perf_counter()
    errs = [_normalized_error(got, want) for got, want in zip(
        both(lambda x: rotary(x, theta))(x, g), both(reference)(x, g))]
    msg = (f"rotary b{b} t{t} h{h} hd{d}: fwd err {errs[0]:.1e} dx err "
           f"{errs[1]:.1e}")
    assert max(errs) <= ROTARY_TOL, f"{msg} > {ROTARY_TOL}"
    s.log("kernels", f"{msg} (tol {ROTARY_TOL}, vs complex float32 "
                     f"jax.numpy) {time.perf_counter() - t0:.1f}s")


def _lstm_check(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
    from deeplearning4j_tpu.nn.conf.layers import GravesLSTM, RnnOutputLayer
    from deeplearning4j_tpu.nn.layers.recurrent import _lstm_scan
    from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
    from deeplearning4j_tpu.ops.lstm_kernel import fused_lstm_scan

    c = s.cfg["lstm"]
    vocab, n, t, b = c["vocab"], c["hidden"], c["seq"], c["batch"]
    conf = (NeuralNetConfiguration.builder()
            .seed(1).learning_rate(0.01).updater("adam").activation("tanh")
            .compute_dtype("bfloat16").list()
            .layer(GravesLSTM(n_in=vocab, n_out=n))
            .layer(GravesLSTM(n_in=n, n_out=n))
            .layer(RnnOutputLayer(n_in=n, n_out=vocab, activation="softmax",
                                  loss_function="mcxent"))
            .build())
    net = MultiLayerNetwork(conf).init()
    ids = np.random.default_rng(0).integers(0, vocab, (b, t))
    data = DataSet(np.eye(vocab, dtype=np.float32)[ids],
                   np.eye(vocab, dtype=np.float32)[np.roll(ids, -1, axis=1)])

    # kernel vs the jax.numpy scan on the first layer's own weights:
    # fwd-only kernel (no grad), then fwd + BPTT kernels (grad)
    p32 = net.params["layer0"]
    p16 = jax.tree.map(lambda v: v.astype(jnp.bfloat16), p32)
    x = jnp.asarray(data.features, jnp.bfloat16)

    def kernel(p, x):
        h0 = jnp.zeros((b, n), x.dtype)
        xg = jnp.einsum("btf,fg->btg", x, p["Wx"]) + p["b"]
        h_seq, _ = fused_lstm_scan(jnp.swapaxes(xg, 0, 1), p["Wr"], p["wci"],
                                   p["wcf"], p["wco"], h0, h0)
        return jnp.swapaxes(h_seq, 0, 1)

    def reference(p, x):   # a mask keeps _lstm_scan on its lax.scan path
        f32 = lambda v: v.astype(jnp.float32)
        h0 = jnp.zeros((b, n), jnp.float32)
        return _lstm_scan(jax.tree.map(f32, p), f32(x), h0, h0, "sigmoid",
                          "tanh", mask=jnp.ones((b, t), jnp.float32))[0]

    err = _normalized_error(jax.jit(kernel)(p16, x),
                            jax.jit(reference)(p16, x))
    assert err <= LSTM_TOL, f"fused LSTM fwd err {err:.1e} > {LSTM_TOL}"
    w = jax.random.normal(jax.random.PRNGKey(3), (b, t, n), jnp.float32)
    loss = lambda f: (lambda p, x, w: jnp.sum(
        f(p, x).astype(jnp.float32) * w))
    got = jax.jit(jax.grad(loss(kernel)))(p16, x, w)
    want = jax.jit(jax.grad(loss(reference)))(p16, x, w)
    errs = {k: _normalized_error(got[k], want[k]) for k in sorted(want)}
    s.log("kernels", f"fused LSTM b{b} n{n} t{t}: fwd-only err {err:.1e} "
                     f"(tol {LSTM_TOL}), fwd+BPTT grad err "
                     + " ".join(f"{k}={e:.1e}" for k, e in errs.items())
                     + f" (tol {LSTM_GRAD_TOL}), vs float32 jax.numpy scan")
    assert max(errs.values()) <= LSTM_GRAD_TOL, errs

    # ... and through the layer: one fit step of the two-layer stack
    net.fit(data)
    score = float(net.score())
    assert np.isfinite(score), score
    kernels = _mosaic_kernels(
        _fit_step_text(net, data.features, data.labels))
    s.log("kernels", f"GravesLSTM x2 fit step: loss {score:.3f}, Mosaic "
                     f"custom calls {kernels}")
    if s.on_chip:
        assert kernels == {"lstm_fwd": 2, "lstm_bptt": 2}, kernels
        out = jax.jit(lambda p, st, x: net._forward(
            p, st, x, False, None, None)[0][-1])
        kernels = _mosaic_kernels(out.lower(
            net.params, net.states,
            jnp.asarray(data.features, net._dtype)).compile().as_text())
        assert kernels == {"lstm_fwd_only": 2}, kernels


def phase_kernels(s: Smoke) -> None:
    before = s.watch.snapshot()
    for shape in s.cfg["flash_shapes"]:
        _flash_check(s, *shape)
    for shape in s.cfg["rotary_shapes"]:
        _rotary_check(s, *shape)
    _lstm_check(s)
    s.log("kernels", f"programs {s.compiles_since(before)}")


# ------------------------------------------------------------------ trace

def phase_trace(s: Smoke) -> None:
    import glob
    import numpy as np
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.util import profiler

    c, net, rows = s.cfg, s.net, s.train_rows
    assert net is not None, "trace needs the train phase's net"
    data = DataSet(rows.astype(np.float32),
                   np.roll(rows, -1, axis=1).astype(np.float32))
    prompt = rows[:1, :c["prompt_lengths"][0]]
    net.generate(prompt, c["new_tokens"])   # programs exist before tracing
    with tempfile.TemporaryDirectory() as log_dir:
        with profiler.trace(log_dir):
            with profiler.annotate("smoke-train"):
                for _ in range(3):
                    net.fit(data)
                float(net.score())
            with profiler.annotate("smoke-decode"):
                net.generate(prompt, c["new_tokens"])
        files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        assert files, f"no .xplane.pb under {log_dir}"
        profile = profiler.load_trace(log_dir)
        size = os.path.getsize(files[0])
    planes = {p.name: sum(len(list(line.events)) for line in p.lines)
              for p in profile.planes}
    s.log("trace", f"{os.path.basename(files[0])} {size / 1e6:.1f} MB; "
                   f"events per plane {planes}")
    host = [e.name for p in profile.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events
            if e.name.startswith("smoke-")]
    assert {"smoke-train", "smoke-decode"} <= set(host), (
        f"annotations missing from the host plane: {sorted(set(host))}")
    devices = profiler.device_planes(profile)
    if s.on_chip:
        assert devices and all(planes[p.name] > 0 for p in devices), (
            f"no device plane with events: {planes}")
        # what the next reduction will read: lines, and the heaviest ops
        plane = devices[0]
        for line in plane.lines:
            events = list(line.events)
            if not events:
                continue
            total = {}
            for e in events:
                total[e.name] = total.get(e.name, 0.0) + e.duration_ns
            top = sorted(total.items(), key=lambda kv: -kv[1])[:4]
            s.log("trace", f"{plane.name} line {line.name!r}: {len(events)} "
                           f"events; heaviest "
                           + ", ".join(f"{n[:40]} {d / 1e6:.1f}ms"
                                       for n, d in top))
    else:
        assert not devices, "a CPU capture has no device plane"


# ------------------------------------------------------------------ clock

def phase_clock(s: Smoke) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    c = s.cfg
    tick = jax.jit(lambda x: x + 1)
    x = tick(jnp.zeros((), jnp.float32))
    x.block_until_ready()
    samples = []
    for _ in range(200):
        t0 = time.perf_counter()
        x = tick(x)
        x.block_until_ready()
        samples.append(time.perf_counter() - t0)
    fetch = []
    for _ in range(200):
        t0 = time.perf_counter()
        float(tick(x))
        fetch.append(time.perf_counter() - t0)
    s.log("clock", f"trivial jitted call, dispatch + block_until_ready: "
                   f"median {np.median(samples) * 1e6:.0f} us (p90 "
                   f"{np.percentile(samples, 90) * 1e6:.0f} us); dispatch + "
                   f"scalar fetch: median {np.median(fetch) * 1e6:.0f} us")

    n, chain = c["gemm_n"], c["gemm_chain"]
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (n, n), jnp.bfloat16)
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, n), jnp.bfloat16)

    @jax.jit
    def gemm_chain(a, b):
        x = a
        for _ in range(chain):
            x = (x @ b) * jnp.bfloat16(0.01)   # keep the chain finite
        return x

    @jax.jit
    def checksum(x):
        return jnp.sum(x.astype(jnp.float32))

    float(checksum(gemm_chain(a, b)))          # compile both, warm up
    t0 = time.perf_counter()
    out = gemm_chain(a, b)
    t_enqueue = time.perf_counter() - t0
    out.block_until_ready()
    t_block = time.perf_counter() - t0
    t0 = time.perf_counter()
    total = float(checksum(gemm_chain(a, b)))
    t_fetch = time.perf_counter() - t0
    assert np.isfinite(total), total
    flops = chain * 2 * n ** 3
    msg = (f"{chain}x{n}^3 bf16 matmul chain: enqueue returned after "
           f"{t_enqueue * 1e3:.2f} ms, block_until_ready after "
           f"{t_block * 1e3:.2f} ms, scalar fetch after "
           f"{t_fetch * 1e3:.2f} ms")
    if s.on_chip:
        from deeplearning4j_tpu.util.device import device_peaks
        floor = flops / device_peaks().bf16_flops
        s.log("clock", f"{msg}; at the table's peak the chain takes "
                       f"{floor * 1e3:.1f} ms -> {flops / t_block / 1e12:.0f} "
                       f"TFLOP/s by block_until_ready")
        assert t_block >= floor, (
            f"block_until_ready returned in {t_block * 1e3:.2f} ms, sooner "
            f"than the chip can run the chain ({floor * 1e3:.1f} ms): it "
            f"does not wait for the device")
        assert t_fetch >= floor, f"scalar fetch too fast: {t_fetch}"
    else:
        s.log("clock", msg)


# -------------------------------------------------------------- four chips

def _device_bytes(devices):
    """bytes_in_use per device (None where the backend keeps no
    memory stats — the CPU rehearsal)."""
    stats = [d.memory_stats() for d in devices]
    return [int(st["bytes_in_use"]) if st else None for st in stats]


def _resident(what, devices, expected_each: float) -> str:
    """Per-device bytes_in_use against what the layout puts on EVERY
    device: a device holding less than half its share means the arrays
    sit somewhere else (all on device 0, say)."""
    got = _device_bytes(devices)
    if None in got:
        return "memory stats not available"
    assert min(got) >= 0.5 * expected_each, (
        f"{what}: bytes in use per device {got}, expected at least "
        f"{expected_each:.3g} on each")
    return ("MB in use per device " + "/".join(f"{v / 1e6:.0f}" for v in got)
            + f" (layout puts {expected_each / 1e6:.0f} on each)")


def _assert_spread(what, arrays, devices, sharded: bool) -> None:
    """Every array lives on exactly ``devices``; when ``sharded``, no
    device holds a whole copy of the big ones."""
    want = set(devices)
    for name, a in arrays:
        got = a.sharding.device_set
        assert got == want, f"{what}: {name} on {sorted(d.id for d in got)}"
        if sharded and a.size >= 1 << 16:
            assert not a.sharding.is_fully_replicated, (
                f"{what}: {name} {a.shape} is replicated on every device")
            shard = a.addressable_shards[0].data
            assert shard.size * len(devices) <= a.size * 2, (
                f"{what}: {name} shard {shard.shape} of {a.shape}")


def _named(tree, prefix=""):
    return [(f"{prefix}{ln}.{pn}", v) for ln, ld in tree.items()
            for pn, v in ld.items()]


def _sharded_trainer(s: Smoke, axes, devices) -> None:
    import re
    import numpy as np
    from deeplearning4j_tpu.nn.quantize import \
        quantized_param_bytes as tree_bytes
    from jax.sharding import PartitionSpec as P
    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.transformer import gpt
    from deeplearning4j_tpu.parallel.mesh import MeshPlane
    from deeplearning4j_tpu.parallel.tensor_parallel import apply_shardings
    from deeplearning4j_tpu.parallel.zero import fsdp_specs

    c = s.cfg
    tag = "x".join(f"{k}{v}" for k, v in axes.items())
    plane = MeshPlane.build(axes, devices)
    net = gpt(vocab_size=c["vocab"], d_model=c["d_model"],
              n_layers=c["n_layers"], num_heads=c["heads"],
              max_len=c["seq"], learning_rate=c["learning_rate"],
              seed=0).init()
    specs = fsdp_specs(net, plane.mesh, "data")
    if "tp" in axes:
        # Megatron column/row pairs over tp, ZeRO-3 over data on the
        # other dim: every block weight carries both axes
        for impl in net.impls[1:-1]:
            specs[impl.name].update(
                Wqkv=P("data", "tp"), Wo=P("tp", "data"),
                W1=P("data", "tp"), b1=P("tp"), W2=P("tp", "data"))
    apply_shardings(net, plane.mesh, specs)
    rows = np.random.default_rng(0).integers(
        0, c["vocab"], (c["batch"], c["seq"]))
    x, y = plane.shard_batch(rows.astype(np.float32),
                             np.roll(rows, -1, axis=1).astype(np.float32))
    _assert_spread(tag, [("batch.x", x), ("batch.y", y)], devices, True)
    data = DataSet(x, y)
    before = s.watch.snapshot()
    losses = []
    for _ in range(4):
        net.fit(data)
        losses.append(float(net.score()))
    assert np.isfinite(losses).all() and losses[-1] < losses[0], losses
    _assert_spread(tag, _named(net.params, "params."), devices, True)
    adam = [(f"adam.{ln}.{pn}.{k}", v)
            for ln, ld in net.opt_state["updater"].items()
            for pn, st in ld.items() for k, v in st.items()
            if hasattr(v, "sharding")]
    _assert_spread(tag, adam, devices, True)
    s.log("four", f"train {tag}: loss " + " ".join(f"{v:.3f}" for v in losses)
                  + f"; params/Adam/batch sharded over all four devices, "
                  + _resident(tag, devices, (tree_bytes(net.params)
                                             + tree_bytes(net.opt_state))
                              / len(devices))
                  + f"; programs {s.compiles_since(before)}")

    # does every chip compute the whole batch's attention? Read the
    # Mosaic calls' operand shapes off the partitioned module: batch
    # (and, under tp, heads) must arrive divided. An operand is
    # [rows, seq, heads-in-a-row * head]: folded copies hold one head a
    # row, the projections' own layout all of a batch row's
    text = _fit_step_text(net, x, y)
    head = c["d_model"] // c["heads"]
    folded = set()
    for line in text.split("\n"):
        if 'custom_call_target="tpu_custom_call"' in line \
                and "flash_fwd" in line:
            folded.update(int(n) * (int(w) // head) for n, w in re.findall(
                r"bf16\[(\d+),%d,(\d+)\]" % c["seq"], line))
    per_device = c["batch"] * c["heads"] // len(devices)
    s.log("four", f"train {tag}: flash_fwd operands hold batch*heads = "
                  f"{sorted(folded)} per device (whole problem "
                  f"{c['batch'] * c['heads']}, a quarter {per_device})")
    if s.on_chip:
        assert folded == {per_device}, (
            f"{tag}: every chip should run a quarter of the attention")
    del net, data, x, y
    gc.collect()


def phase_four(s: Smoke) -> None:
    """The same paths on exactly four devices, in this process."""
    import jax
    import numpy as np
    from deeplearning4j_tpu.nn.quantize import \
        quantized_param_bytes as tree_bytes
    from deeplearning4j_tpu.parallel.inference import ParallelInference
    from deeplearning4j_tpu.parallel.mesh import MeshPlane

    c, rows = s.cfg, s.train_rows
    devices = jax.devices()[:4]
    new = c["new_tokens"]
    _sharded_trainer(s, {"data": 4}, devices)
    _sharded_trainer(s, {"data": 2, "tp": 2}, devices)

    # serving: the train phase left s.net trained on device 0; every
    # engine below gets its own copy of it, and is held to its
    # whole-burst greedy tokens
    lengths = c["prompt_lengths"][::2]
    prompts = [rows[i % len(rows), :n] for i, n in enumerate(lengths)]
    refs = [np.asarray(s.net.generate(p[None, :], new)) for p in prompts]
    host_params = jax.tree.map(np.asarray, s.net.params)

    def fresh_net(device=None):
        net = s.net.clone()
        if device is None:      # the slice: apply_serving_slice places it
            net.params = host_params
        else:
            net.params = jax.device_put(host_params, device)
            net.opt_state = None
        return net

    # four one-chip replicas, one engine per device
    before = s.watch.snapshot()
    engines = [ParallelInference(
        fresh_net(d), continuous=True, devices=[d],
        decode_slots=c["decode_slots"], decode_burst=c["decode_burst"],
        kv_block_size=c["kv_block_size"]) for d in devices]
    futs = [engines[i % 4].submit_generate(p[None, :], new)
            for i, p in enumerate(prompts)]
    outs = [np.asarray(f.result(timeout=900)) for f in futs]
    for i, eng in enumerate(engines):
        assert eng.stats()["replicas"] == 1
        sched = _drained(eng)
        pool = eng._scheduler._lanes[(None, None)].pool
        where = {d for layer in pool.layers
                 for a in layer.values() for d in a.sharding.device_set}
        assert where == {devices[i]}, f"replica {i}: KV pool on {where}"
        params = {d for _, v in _named(eng._replicas[0][1])
                  for d in v.sharding.device_set}
        assert params == {devices[i]}, f"replica {i}: params on {params}"
        assert sched["resolved"] >= 1, f"replica {i} served nothing"
        assert sched["pool"]["blocks_free"] == sched["pool"]["blocks_total"]
    pool = engines[0]._scheduler._lanes[(None, None)].pool
    placed = _resident("replicas", devices, tree_bytes(host_params)
                       + tree_bytes(pool.layers))
    for eng in engines:
        eng.shutdown()
    firsts = [_match(o, r, n) for o, r, n in zip(outs, refs, lengths)]
    s.log("four", f"four one-chip replicas: every replica served from its "
                  f"own device (params + KV pool placed there), {placed}; "
                  f"vs one-chip tokens: first equal {sum(f for f, _ in firsts)}/"
                  f"{len(firsts)}, later equal "
                  f"{np.mean([r for _, r in firsts]):.3f}; programs "
                  f"{s.compiles_since(before)}")
    assert all(f for f, _ in firsts), firsts
    del engines, eng, pool
    gc.collect()

    # one tp=4 serving slice
    before = s.watch.snapshot()
    plane = MeshPlane.build({"tp": 4}, devices)
    eng = ParallelInference(
        fresh_net(), continuous=True, slice_plane=plane,
        decode_slots=c["decode_slots"], decode_burst=c["decode_burst"],
        kv_block_size=c["kv_block_size"])
    futs = [eng.submit_generate(p[None, :], new) for p in prompts]
    outs = [np.asarray(f.result(timeout=900)) for f in futs]
    sched = _drained(eng)
    pool = eng._scheduler._lanes[(None, None)].pool
    kv = [(f"kv.{i}.{k}", a) for i, layer in enumerate(pool.layers)
          for k, a in layer.items()]
    _assert_spread("tp4 slice", kv, devices, True)
    blocks = [(n, v) for n, v in _named(eng.net.params)
              if n.split(".")[-1] in ("Wqkv", "Wo", "W1", "W2")]
    _assert_spread("tp4 slice", blocks, devices, True)
    assert sched["pool"]["blocks_free"] == sched["pool"]["blocks_total"]
    placed = _resident("tp4 slice", devices,
                       (tree_bytes(dict(blocks)) + tree_bytes(pool.layers))
                       / len(devices))
    eng.shutdown()
    firsts = [_match(o, r, n) for o, r, n in zip(outs, refs, lengths)]
    s.log("four", f"tp=4 serving slice: block weights and KV pool sharded "
                  f"over the four devices, {placed}; "
                  f"vs one-chip tokens: first equal "
                  f"{sum(f for f, _ in firsts)}/{len(firsts)}, later equal "
                  f"{np.mean([r for _, r in firsts]):.3f}; programs "
                  f"{s.compiles_since(before)}")
    assert all(f for f, _ in firsts), firsts
    del eng
    gc.collect()

    sys.path.insert(0, _HERE)
    import __graft_entry__
    __graft_entry__.dryrun_multichip(4)
    s.log("four", "dryrun_multichip(4): dp+tp, dp+sp ring, dp+ep, pp, fsdp, "
                  "fsdp x tp steps ran on this process's devices")


# ------------------------------------------------------------------- main

PHASES = (("train", phase_train), ("serve", phase_serve),
          ("kernels", phase_kernels), ("trace", phase_trace),
          ("clock", phase_clock), ("four", phase_four))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--rehearsal", action="store_true",
                    help="CPU, tiny widths, interpreted kernels: debugs "
                         "this command, proves nothing about the chip")
    ap.add_argument("--only", default="",
                    help="comma-separated phases (train is always run "
                         "first when serve/trace/four need its net)")
    ap.add_argument("--keep-going", action="store_true",
                    help="run later phases after one fails (exit code "
                         "stays non-zero)")
    args = ap.parse_args(argv)

    if args.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    backend = jax.default_backend()
    if args.rehearsal:
        if backend != "cpu":
            print(f"--rehearsal is the CPU dry run; backend is {backend!r}",
                  file=sys.stderr)
            return 2
    elif backend != "tpu":
        print(f"chip_smoke needs a TPU: jax.default_backend() is "
              f"{backend!r} (use --rehearsal to debug the command on CPU)",
              file=sys.stderr)
        return 2

    from importlib import metadata
    from deeplearning4j_tpu.native import data_plane
    from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
    from deeplearning4j_tpu.util.device import device_info

    cache_dir = enable_compile_cache()
    s = Smoke(TINY if args.rehearsal else FULL, args.rehearsal)
    device = device_info()
    versions = {p: metadata.version(p) for p in ("jax", "jaxlib", "libtpu")}
    s.log("start", f"platform {device['platform']}, device_kind "
                   f"{device['kind']!r}, {device['count']} device(s); "
                   + ", ".join(f"{k} {v}" for k, v in versions.items())
                   + f"; compile cache {cache_dir}; host data plane: "
                   + data_plane())

    only = [p for p in args.only.split(",") if p]
    unknown = set(only) - {name for name, _ in PHASES}
    if unknown:
        ap.error(f"unknown phases {sorted(unknown)}")
    if only and set(only) & {"serve", "trace", "four"}:
        only.append("train")
    failed, ran = [], []
    t_start = time.perf_counter()
    for name, fn in PHASES:
        if only and name not in only:
            continue
        if name == "four" and device["count"] < 4:
            s.log("four", f"skipped: {device['count']} device(s), needs 4")
            continue
        t0 = time.perf_counter()
        try:
            fn(s)
        except Exception:
            if not args.keep_going:
                raise
            traceback.print_exc()
            failed.append(name)
            s.log(name, "FAILED")
        else:
            ran.append(name)
            s.log(name, f"passed in {time.perf_counter() - t0:.1f}s")
        gc.collect()
    total = s.watch.snapshot()
    s.log("done", f"{time.perf_counter() - t_start:.0f}s; this process "
                  f"built {total['compiles']:.0f} programs in "
                  f"{total['compile_seconds']:.0f}s; persistent cache "
                  f"{total['cache_hits']:.0f} hits / "
                  f"{total['cache_misses']:.0f} misses")
    if failed:
        print(f"FAILED phases: {failed}", file=sys.stderr)
        return 1
    if args.rehearsal:      # no result line: a rehearsal proves nothing
        s.log("done", f"rehearsed {ran}")
        return 0
    result = {"ok": True, "device": device}
    if only:
        result["only"] = ran
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
