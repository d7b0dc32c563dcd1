"""Fused autoregressive generation: prefill + single-dispatch decode.

The serving-side complement of the training stack: before this module,
generating N tokens meant N host round-trips through eager per-token
dispatches (the exact host-loop shape PR 2 killed on the fit path).
Here generation is TWO dispatches total, the single-chip version of
iteration-level batched decoding (Orca's scheduling discipline, vLLM's
resident-cache doctrine):

- **prefill** — ONE batched forward over the padded prompt that writes
  every transformer layer's KV cache (``TransformerBlockImpl.prefill``)
  or streams the prompt through the scanned LSTM recurrence. Prompt
  lengths are padded up the PR-3 power-of-two bucket ladder and enter
  the program as a traced per-row ``lengths`` vector, so ANY prompt mix
  inside a bucket reuses one AOT-warmable compiled program;
- **decode** — ALL of ``max_new_tokens`` runs as ONE ``jax.lax.scan``
  dispatch: embed → stacked ``decode_step`` over layers (per-row cache
  positions) → logits → on-device sample → feed back. The carry is
  (caches, token, positions, done-mask); cache buffers are donated to
  the program off-CPU; an EOS done-mask short-circuits the whole step
  (``lax.cond``) once every row has finished;
- **on-device sampling** — greedy, temperature, top-k and top-p
  (nucleus) composed inside the traced step via per-row PRNG keys
  (gumbel-max), so only the final token ids ever cross the wire and a
  request's draws are invariant to how the engine coalesces it.

The same API drives LSTM nets (char-RNN generation) through the
existing scanned ``one_step`` recurrence, and single-input linear-chain
ComputationGraphs through the identical machinery.

``generate_eager`` is the per-token host-loop reference — one dispatch
per token, same math and same per-row PRNG fold indices, so fused and
eager agree token-for-token (the correctness oracle).
"""

from __future__ import annotations

import time
from typing import Any, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from deeplearning4j_tpu.datasets.iterators import bucket_for, bucket_sizes
from deeplearning4j_tpu.monitor import (
    DECODE_LATENCY_HISTOGRAM,
    DECODE_PREFILL_LATENCY_HISTOGRAM,
    DECODE_PREFILL_TOKENS_COUNTER,
    DECODE_REQUESTS_COUNTER,
    DECODE_TOKENS_COUNTER,
    get_registry,
    span,
)
from deeplearning4j_tpu.nn.layers.transformer import (
    SequenceEmbeddingImpl,
    TransformerBlockImpl,
)
from deeplearning4j_tpu.nn.quantize import kv_quantize, qtake
from deeplearning4j_tpu.optimize.deferred import note_dispatch
from deeplearning4j_tpu.util.dtypes import cast_floats

#: (temperature, top_k, top_p, eos_token-or-None) — the hashable static
#: sampler signature baked into a compiled decode program.
SamplerSig = Tuple[float, int, float, Optional[int]]


def sampler_sig(temperature: float = 0.0, top_k: int = 0,
                top_p: float = 0.0, eos_token: Optional[int] = None
                ) -> SamplerSig:
    """Normalize sampler knobs into the static program signature."""
    return (float(temperature), int(top_k), float(top_p),
            None if eos_token is None else int(eos_token))


def row_keys(seed: int, rows: int) -> jax.Array:
    """Per-row PRNG keys [rows, 2]: ``fold_in(PRNGKey(seed), row)``.
    Sampling draws key off a row's OWN key (folded again by step), so a
    request's tokens are identical whether it runs solo or coalesced
    into a served batch with other requests."""
    return jax.vmap(jax.random.fold_in, (None, 0))(
        jax.random.PRNGKey(int(seed)), jnp.arange(rows))


def _pow2_bucket(n: int) -> int:
    """Smallest power of two >= n (the un-anchored bucket ladder for
    recurrent prompts, which have no max_len to cap at)."""
    return 1 << max(0, int(n) - 1).bit_length() if n > 1 else 1


def sample_tokens(logits, keys, step, temperature: float, top_k: int,
                  top_p: float):
    """On-device sampler over [b, V] logits with per-row keys [b, 2]
    folded by ``step``: greedy (temperature <= 0), temperature softmax,
    optionally restricted to the ``top_k`` highest logits and/or the
    smallest nucleus with cumulative probability >= ``top_p``.
    Traced-code only; sampling is gumbel-max so filtered logits
    (-inf) can never be drawn."""
    if temperature <= 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = logits.astype(jnp.float32) / float(temperature)
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
    vocab = lg.shape[-1]
    if top_k and top_k < vocab:
        kth = jax.lax.top_k(lg, int(top_k))[0][:, -1:]
        lg = jnp.where(lg < kth, neg, lg)
    if top_p and top_p < 1.0:
        srt = jnp.sort(lg, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(srt, axis=-1)
        # smallest prefix with cumulative prob >= top_p
        keep = jnp.cumsum(probs, axis=-1) - probs < top_p
        cutoff = jnp.min(jnp.where(keep, srt, jnp.inf),
                         axis=-1, keepdims=True)
        lg = jnp.where(lg < cutoff, neg, lg)
    step_keys = jax.vmap(jax.random.fold_in, (0, None))(keys, step)
    g = jax.vmap(lambda k: jax.random.gumbel(k, (vocab,), jnp.float32))(
        step_keys)
    return jnp.argmax(lg + g, axis=-1).astype(jnp.int32)


def _filter_logits(logits, temp_v, top_k_v, top_p_v):
    """The rowwise sampler's temperature/top-k/top-p filter over [b, V]
    logits with per-row traced knob vectors: scaled f32 logits with
    every filtered entry at ``finfo.min`` (softmax → exactly the
    sampler's support). Factored out of :func:`sample_tokens_rowwise`
    so the speculative rejection sampler computes its target/draft
    distributions p and q from PRECISELY the distribution the plain
    sampler draws from — the exactness contract hinges on the filters
    matching bit for bit."""
    vocab = logits.shape[-1]
    lg = logits.astype(jnp.float32) / jnp.maximum(temp_v, 1e-6)[:, None]
    neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
    # top-k: the kth-largest value per row (k <= 0 or k >= V: no filter)
    srt = jnp.sort(lg, axis=-1)[:, ::-1]
    k_idx = jnp.clip(top_k_v - 1, 0, vocab - 1)
    kth = jnp.take_along_axis(srt, k_idx[:, None], axis=1)
    use_k = ((top_k_v > 0) & (top_k_v < vocab))[:, None]
    lg = jnp.where(use_k & (lg < kth), neg, lg)
    # top-p over the k-filtered logits (matches the static ordering)
    srt2 = jnp.sort(lg, axis=-1)[:, ::-1]
    probs = jax.nn.softmax(srt2, axis=-1)
    keep = jnp.cumsum(probs, axis=-1) - probs < top_p_v[:, None]
    cutoff = jnp.min(jnp.where(keep, srt2, jnp.inf), axis=-1, keepdims=True)
    use_p = ((top_p_v > 0.0) & (top_p_v < 1.0))[:, None]
    return jnp.where(use_p & (lg < cutoff), neg, lg)


def sample_tokens_rowwise(logits, keys, folds, temp_v, top_k_v, top_p_v):
    """Per-row sampler over [b, V] logits — the continuous-batching
    variant of :func:`sample_tokens`: every sampler knob is a traced
    [b] vector (temperature, top-k, top-p) and the PRNG fold index is
    per row (``folds`` — each sequence's own generated-token counter),
    so ONE compiled burst program serves any sampler mix and a
    sequence's draws depend only on its own key and token index, never
    on which batch slot or cotenants it shares a burst with.
    ``temp_v <= 0`` rows are greedy. Same filter semantics as the
    static sampler: top-k first, then the top-p nucleus over the
    k-filtered logits."""
    vocab = logits.shape[-1]
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    lg = _filter_logits(logits, temp_v, top_k_v, top_p_v)
    step_keys = jax.vmap(jax.random.fold_in)(keys, folds)
    g = jax.vmap(lambda k: jax.random.gumbel(k, (vocab,), jnp.float32))(
        step_keys)
    sampled = jnp.argmax(lg + g, axis=-1).astype(jnp.int32)
    return jnp.where(temp_v > 0.0, sampled, greedy)


#: Disjoint PRNG fold lanes for speculative decoding (Leviathan et al.
#: 2023; Chen et al. 2023). Every draw in a speculative round derives
#: from ``fold_in(fold_in(row_key, SALT), token_index)`` — three salted
#: lanes (draft proposal gumbels, accept-test uniforms, residual/bonus
#: gumbels), all clocked by the row's generated-token index, NEVER by
#: round or batch position. A round that accepts ``a`` proposals emits
#: ``a + 1`` tokens and consumed nothing past index ``n_gen + a`` on
#: any lane whose value reached the output (the first rejection is a
#: stopping time over the index clock: discarded deeper proposals never
#: enter the output σ-algebra), so the next round's draws at index
#: ``n_gen + a + 1`` onward are fresh — the rejection sampler stays
#: distribution-exact AND every draw is a pure function of (seed, row,
#: token index): coalescing- and preemption-invariant like the plain
#: sampler's unsalted clock, which stays an independent stream (its
#: draws fold the row key once, the spec lanes twice).
SPEC_DRAFT_SALT = 101
SPEC_ACCEPT_SALT = 102
SPEC_RESID_SALT = 103


def spec_lane_keys(keys, salt: int):
    """Fold every row key [b, 2] onto one speculative lane (traced)."""
    return jax.vmap(jax.random.fold_in, (0, None))(keys, salt)


def _ordered_impls(net) -> List[Any]:
    """The net's layer impls in forward order. MultiLayerNetwork: the
    stack as-is. ComputationGraph: the single-input linear layer chain
    in topological order (anything else — multi-input vertices, op
    vertices, multiple outputs — has no defined decode order)."""
    impls = net.impls
    if isinstance(impls, list):
        return impls
    if len(net.input_names) != 1 or len(net.output_names) != 1:
        raise ValueError(
            "generate() serves single-input/single-output graphs; this "
            f"one has inputs {net.input_names} and outputs "
            f"{net.output_names}")
    chain: List[Any] = []
    for name in net.order:
        v = net.defs[name]
        if v.kind == "input":
            continue
        if v.kind != "layer" or len(v.inputs) != 1:
            raise ValueError(
                "generate() supports linear layer chains; vertex "
                f"'{name}' ({v.kind}, inputs {v.inputs}) breaks the chain")
        chain.append(impls[name])
    return chain


class _GeneratorBase:
    """Shared plumbing: jit-cache access on the owning net, dispatch
    accounting (``dl4j_jit_cache_miss_total`` via note_dispatch, same
    doctrine as the serving engine), and the decode-metric family."""

    def __init__(self, net, impls: List[Any]):
        self.net = net
        self.impls = impls
        self.head = impls[-1]
        self.cd = net._cd

    # --- jit cache on the net (resets with init(), like every program)

    def _jit(self, key, builder, donate_caches: bool = False,
             donate: Optional[Tuple[int, ...]] = None):
        jits = self.net._jits
        if key not in jits:
            argnums: Tuple[int, ...] = ()
            if jax.default_backend() != "cpu":
                if donate is not None:
                    argnums = donate
                elif donate_caches:
                    argnums = (1,)
            jits[key] = jax.jit(builder(), donate_argnums=argnums)
        return jits[key]

    def _head_logits(self, params, h):
        """Final-token logits from the head layer: its ``preout`` when
        it has one (dense heads — the f32-logits contract OutputImpl
        already guarantees under a bf16 policy), else the activations
        themselves (LossLayer-style heads)."""
        p = params[self.head.name]
        if hasattr(self.head, "preout"):
            if self.cd is not None and "W" in p:
                p = cast_floats(p, self.cd)
            return self.head.preout(p, h).astype(jnp.float32)
        return h.astype(jnp.float32)

    def _cast(self, p):
        return cast_floats(p, self.cd) if self.cd is not None else p

    # ------------------------------------------------------ metrics

    def _observe(self, reg, rows: int, prompt_tokens: int, max_new: int,
                 pre_ms: float, dec_ms: float) -> None:
        reg.counter(DECODE_PREFILL_TOKENS_COUNTER,
                    "Prompt tokens prefilled into decode caches").inc(
            prompt_tokens)
        reg.counter(DECODE_TOKENS_COUNTER,
                    "Tokens produced by fused decode dispatches").inc(
            rows * max_new)
        reg.histogram(DECODE_PREFILL_LATENCY_HISTOGRAM,
                      "Prefill dispatch latency (one batched prompt "
                      "forward)").observe(pre_ms)
        reg.histogram(DECODE_LATENCY_HISTOGRAM,
                      "Fused decode dispatch latency (all of "
                      "max_new_tokens in one scan)").observe(dec_ms)


class TransformerGenerator(_GeneratorBase):
    """KV-cache generation for SequenceEmbedding → TransformerBlock* →
    head stacks: bucketed batched prefill + one-scan decode."""

    def __init__(self, net, impls):
        super().__init__(net, impls)
        self.emb: SequenceEmbeddingImpl = impls[0]
        self.blocks: List[TransformerBlockImpl] = list(impls[1:-1])

    def prompt_bucket(self, t_in: int, max_new: int) -> int:
        max_len = self.emb.conf.max_len
        if t_in < 1:
            raise ValueError(f"empty prompt (length {t_in})")
        if t_in + max_new > max_len:
            raise ValueError(
                f"prompt {t_in} + {max_new} new tokens exceeds "
                f"max_len {max_len}")
        return bucket_for(t_in, bucket_sizes(max_len))

    # ----------------------------------------------------- programs

    def _embed_token(self, p_emb, tok, pos):
        """[b] ids at per-row positions [b] → [b, d]. ``qtake`` is the
        quantized-embedding seam: int8/fp8 rows gather at 1 byte per
        element and dequant per-channel (identical to the plain take on
        an unquantized table)."""
        return self.emb._slice_replicate(
            qtake(p_emb, "W", tok)
            + jnp.take(p_emb["P"], pos, axis=0))

    def _get_prefill(self, cache_len: int):
        def builder():
            def prefill(params, ids, lengths):
                b, t_pad = ids.shape
                p_emb = self._cast(params[self.emb.name])
                x = self.emb._slice_replicate(
                    qtake(p_emb, "W", ids)
                    + p_emb["P"][:t_pad][None])
                cache_dtype = self.cd if self.cd is not None else jnp.float32
                caches = []
                for blk in self.blocks:
                    cache = blk.init_cache(b, cache_len, cache_dtype)
                    x, cache = blk.prefill(
                        self._cast(params[blk.name]), x, cache)
                    caches.append(cache)
                # last REAL token's hidden state per row (lengths is
                # traced: every prompt length in the bucket reuses this
                # one program); length-0 rows are serving-side padding
                # and read garbage that their done-mask discards
                last = x[jnp.arange(b), lengths - 1]
                return caches, self._head_logits(params, last)
            return prefill
        return self._jit(("gen_prefill", cache_len), builder)

    def _get_decode(self, max_new: int, sampler: SamplerSig):
        temperature, top_k, top_p, eos = sampler

        def builder():
            def decode(params, caches, logits0, lengths, keys):
                p_emb = self._cast(params[self.emb.name])
                tok0 = sample_tokens(logits0, keys, 0,
                                     temperature, top_k, top_p)
                if eos is not None:
                    tok0 = jnp.where(lengths == 0, eos, tok0)
                    done0 = tok0 == eos
                else:
                    done0 = jnp.zeros(tok0.shape, bool)

                def live(args, s):
                    caches, tok, pos, done = args
                    x = self._embed_token(p_emb, tok, pos)
                    new_caches = []
                    for blk, cache in zip(self.blocks, caches):
                        x, cache = blk.decode_step(
                            self._cast(params[blk.name]), x, cache, pos)
                        new_caches.append(cache)
                    nxt = sample_tokens(self._head_logits(params, x),
                                        keys, s + 1,
                                        temperature, top_k, top_p)
                    if eos is not None:
                        nxt = jnp.where(done, eos, nxt)
                        done = done | (nxt == eos)
                    return new_caches, nxt, pos + 1, done

                def body(carry, s):
                    if eos is not None:
                        # EOS early-exit: one predicate skips the whole
                        # transformer step once every row is finished
                        carry = jax.lax.cond(
                            jnp.all(carry[3]),
                            lambda a: (a[0], jnp.full_like(a[1], eos),
                                       a[2] + 1, a[3]),
                            lambda a: live(a, s), carry)
                    else:
                        carry = live(carry, s)
                    return carry, carry[1]

                carry0 = (caches, tok0, lengths.astype(jnp.int32), done0)
                carry, ys = jax.lax.scan(body, carry0,
                                         jnp.arange(max_new - 1))
                # the final caches ride out (callers drop them): a
                # donated argument that no output can alias is not
                # donated at all, and the loop would copy the caches
                return jnp.concatenate(
                    [tok0[:, None], jnp.swapaxes(ys, 0, 1)], axis=1), carry[0]
            return decode
        return self._jit(("gen_decode", max_new) + sampler, builder,
                         donate_caches=True)

    # --------------------------------------------------------- run

    def run(self, params, ids: np.ndarray, lengths: np.ndarray,
            max_new: int, sampler: SamplerSig, keys,
            replica=None, device=None) -> np.ndarray:
        """Fused generation over a bucket-padded prompt batch:
        ``ids`` [b, t_pad] int32 (rows right-padded past ``lengths``),
        returns the [b, max_new] generated ids. Two dispatches total."""
        b, t_pad = ids.shape
        cache_len = t_pad + max_new
        reg = get_registry()
        put = (lambda a: jax.device_put(a, device)) if device is not None \
            else (lambda a: a)
        ids_d = put(jnp.asarray(ids, jnp.int32))
        len_d = put(jnp.asarray(lengths, jnp.int32))
        keys_d = put(jnp.asarray(keys))

        pre = self._get_prefill(cache_len)
        fresh = note_dispatch(
            self.net, ("gen_prefill", replica, b, t_pad, cache_len))
        t0 = time.perf_counter()
        with span("compile" if fresh else "inference",
                  path="generate_prefill", bucket=t_pad, rows=b):
            caches, logits0 = pre(params, ids_d, len_d)
            # SANCTIONED SYNC (1 of 2 per request): fences the prefill
            # so the prefill/decode phase split the span records is real
            # dl4j-lint: disable=hot-path-host-sync
            jax.block_until_ready(logits0)
        t1 = time.perf_counter()

        dec = self._get_decode(max_new, sampler)
        fresh = note_dispatch(
            self.net,
            ("gen_decode", replica, b, cache_len, max_new) + sampler)
        with span("compile" if fresh else "inference",
                  path="generate_decode", rows=b, max_new=max_new):
            # SANCTIONED SYNC (2 of 2): the whole burst's tokens come
            # home in ONE fetch — the fused path's entire host traffic
            # dl4j-lint: disable=hot-path-host-sync
            toks = np.asarray(
                dec(params, caches, logits0, len_d, keys_d)[0])
        t2 = time.perf_counter()
        # dl4j-lint: disable=hot-path-host-sync — host ints, ms math
        self._observe(reg, b, int(np.sum(lengths)), max_new,
                      (t1 - t0) * 1e3, (t2 - t1) * 1e3)
        return toks

    # ------------------------------------ continuous paged decoding
    # (serving/continuous.py drives these: vLLM-style block-table
    # attention + Orca-style fixed-K bursts — see nn/kvpool.py)

    def kv_layout(self) -> Tuple[int, int, int, Any]:
        """(num_layers, num_heads, head_dim, cache dtype) — the pool
        layout this net's paged caches need."""
        c = self.blocks[0].conf
        dtype = self.cd if self.cd is not None else jnp.float32
        return (len(self.blocks), c.num_heads, c.n_out // c.num_heads,
                dtype)

    def slice_plane(self):
        """The net's serving slice plane (``apply_serving_slice``), or
        None for a single-device net."""
        return getattr(self.net, "slice_plane", None)

    def kv_sharding(self):
        """The paged pool's block-array sharding on a sliced net: heads
        partitioned over ``tp`` (``[num_blocks, block_size, HEADS,
        head_dim]`` — per-head attention is embarrassingly parallel, so
        a sharded pool changes no arithmetic), replicated None when the
        net is not slice-served. num_heads must divide the tp width."""
        plane = self.slice_plane()
        if plane is None:
            return None
        from jax.sharding import NamedSharding, PartitionSpec
        tp = plane.axis_size("tp")
        heads = self.blocks[0].conf.num_heads
        if heads % max(1, tp) != 0:
            raise ValueError(
                f"KV pool shards heads over tp: {heads} heads not "
                f"divisible by slice width {tp}")
        return NamedSharding(plane.mesh,
                             PartitionSpec(None, None, "tp", None))

    def export_prefill(self, params, ids: np.ndarray, lengths: np.ndarray
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Disaggregated-prefill export: run the bucketed prompt prefill
        and hand back host copies of (kv [L, 2, b, t_pad, h, hd],
        last-token logits [b, V]) — the state a DECODE endpoint needs to
        admit this prompt without recomputing it. The kv tensor is what
        a local prefill of the same tokens would have written (same
        program, same params), so a handoff-admitted sequence's tokens
        are exactly a local run's."""
        b, t_pad = ids.shape
        pre = self.prefill_program(t_pad)
        fresh = note_dispatch(self.net,
                              ("gen_prefill", "export", b, t_pad, t_pad))
        with span("compile" if fresh else "inference",
                  path="prefill_export", bucket=t_pad, rows=b):
            caches, logits = pre(params, jnp.asarray(ids, jnp.int32),
                                 jnp.asarray(lengths, jnp.int32))
        kv = np.stack([np.stack([np.asarray(c["k"]), np.asarray(c["v"])])
                       for c in caches])
        # SANCTIONED SYNC: the export's whole purpose is materializing
        # the prompt KV + logits on host to ship across the wire
        # dl4j-lint: disable=hot-path-host-sync
        return kv, np.asarray(logits)

    def max_context(self) -> int:
        return int(self.emb.conf.max_len)

    def prefill_program(self, cache_len: int):
        """The bucketed prompt prefill, reused verbatim for the paged
        path: dense per-row caches [b, cache_len, h, hd] the scatter
        program then pages into pool blocks (cache_len = the prompt
        bucket rounded up to a whole number of blocks)."""
        return self._get_prefill(cache_len)

    def scatter_program(self, rows: int, t_blk: int, block_size: int):
        """Pages a prefill's dense caches into the shared pool: every
        layer's [rows, t_blk, h, hd] K/V reshapes into t_blk/block_size
        block-sized chunks and scatters to the rows' block-table ids
        (unallocated tail entries are 0 — the trash block). A QUANTIZED
        pool quantizes each position per head on the way in — the SAME
        per-token granularity the burst's incremental writes use, so a
        resume's re-prefill stores bit-identical blocks to the original
        decode (the replay contract on a quantized pool)."""
        if t_blk % block_size != 0:
            raise ValueError(
                f"t_blk {t_blk} not a multiple of block_size {block_size}")
        nb = t_blk // block_size

        def builder():
            def scatter(pools, caches, tables):
                out = []
                for pool, cache in zip(pools, caches):
                    tail = cache["k"].shape[2:]
                    kr = cache["k"].reshape(rows, nb, block_size, *tail)
                    vr = cache["v"].reshape(rows, nb, block_size, *tail)
                    if "k_scale" in pool:
                        kq, ksc = kv_quantize(kr, pool["k"].dtype)
                        vq, vsc = kv_quantize(vr, pool["v"].dtype)
                        out.append({
                            "k": pool["k"].at[tables].set(kq),
                            "v": pool["v"].at[tables].set(vq),
                            "k_scale": pool["k_scale"].at[tables].set(ksc),
                            "v_scale": pool["v_scale"].at[tables].set(vsc)})
                        continue
                    out.append({
                        "k": pool["k"].at[tables].set(
                            kr.astype(pool["k"].dtype)),
                        "v": pool["v"].at[tables].set(
                            vr.astype(pool["v"].dtype))})
                return out
            return scatter
        return self._jit(("gen_pool_scatter", rows, t_blk, block_size),
                         builder, donate=(0,))

    def tail_prefill_program(self, rows: int, t_tail: int, tier: int,
                             num_blocks: int, block_size: int):
        """Prefill ONLY a prompt's uncached tail through the paged pool
        (the prefix-cache admission path): each row's table carries its
        matched cached blocks followed by its fresh tail blocks, tail
        token positions enter as per-row traced ``starts`` (any cached
        prefix length reuses this one program — the bucket doctrine
        applied to cache hits), tail K/V scatters into the fresh blocks
        and attention runs tail-queries × whole-table causally. Returns
        (pools, last-tail-token logits) — the logits the admission
        sampler needs for tok0. Shape = (rows × t_tail bucket × tier),
        a small AOT-warmable ladder like every other program here."""
        def builder():
            def tail_prefill(params, pools, ids, starts, lens, tables):
                p_emb = self._cast(params[self.emb.name])
                pos = starts[:, None] + jnp.arange(t_tail)[None, :]
                x = self.emb._slice_replicate(
                    qtake(p_emb, "W", ids)
                    + jnp.take(p_emb["P"], pos, axis=0))
                write_ok = jnp.arange(t_tail)[None, :] < lens[:, None]
                new_pools = []
                for blk, pool in zip(self.blocks, pools):
                    x, pool = blk.prefill_paged(
                        self._cast(params[blk.name]), x, pool, tables,
                        pos, write_ok)
                    new_pools.append(pool)
                last = x[jnp.arange(x.shape[0]), jnp.maximum(lens - 1, 0)]
                return new_pools, self._head_logits(params, last)
            return tail_prefill
        return self._jit(("gen_tail_prefill", rows, t_tail, tier,
                          num_blocks, block_size), builder, donate=(1,))

    def block_copy_program(self, n: int, num_blocks: int, block_size: int):
        """Copy-on-write: duplicate ``n`` pool blocks (src → dst ids,
        traced) across every layer's K/V pools in one dispatch — the
        copy a writer makes before scattering into a refcount>1 partial
        tail block. Bitwise block clones; interior shared blocks are
        never written, so this is the ONLY mutation sharing needs."""
        def builder():
            def copy(pools, src, dst):
                # generic over the pool entry set: a quantized pool's
                # k_scale/v_scale arrays clone with their blocks, so a
                # COW'd block dequantizes identically to its source
                return [{name: arr.at[dst].set(arr[src])
                         for name, arr in pool.items()}
                        for pool in pools]
            return copy
        return self._jit(("gen_block_copy", n, num_blocks, block_size),
                         builder, donate=(0,))

    def row_sample_program(self):
        """One rowwise-sampler dispatch off prefill logits: per-row
        keys, fold indices (a resumed sequence continues its own token
        clock) and sampler knobs — the admission-time tok0 sample."""
        def builder():
            def rsample(logits, keys, folds, temp_v, top_k_v, top_p_v):
                return sample_tokens_rowwise(logits, keys, folds,
                                             temp_v, top_k_v, top_p_v)
            return rsample
        return self._jit(("gen_row_sample",), builder)

    def burst_program(self, slots: int, k_burst: int, max_blocks: int,
                      num_blocks: int, block_size: int,
                      sampling: bool = True):
        """ONE fixed-shape program for a whole scheduler burst: K
        decode steps over ``slots`` batch rows with paged block-table
        attention, per-row traced positions / sampler knobs / PRNG fold
        clocks / max-new quotas, and a done-mask that freezes finished
        rows (their writes redirect to the trash block, so a retired
        slot can never touch the pool between bursts). The shape is
        (slots × K × max_blocks) — static no matter which sequences
        occupy the slots, which is what makes steady state compile-free.
        Returns (pools, ys [slots, K], tok, pos, n_gen, done).
        ``sampling=False`` compiles the greedy-only variant (argmax,
        no sorts/PRNG in the step — the scheduler picks it whenever no
        active row has a temperature, mirroring the static sampler
        specialization of the whole-burst programs)."""
        def builder():
            def burst(params, pools, tables, pos, tok, n_gen, done, keys,
                      temp_v, top_k_v, top_p_v, eos_v, max_new_v):
                p_emb = self._cast(params[self.emb.name])

                def live(carry):
                    pools, tok, pos, n_gen, done = carry
                    active = ~done
                    x = self._embed_token(p_emb, tok, pos)
                    new_pools = []
                    for blk, pool in zip(self.blocks, pools):
                        # the whole pool entry set rides the cache dict
                        # (a quantized pool's scale arrays scatter and
                        # gather inside decode_step's paged branch)
                        cache = dict(pool)
                        cache["table"] = tables
                        x, cache = blk.decode_step(
                            self._cast(params[blk.name]), x, cache, pos,
                            write_mask=active)
                        new_pools.append({name: cache[name]
                                          for name in pool})
                    logits = self._head_logits(params, x)
                    if sampling:
                        nxt = sample_tokens_rowwise(logits, keys, n_gen,
                                                    temp_v, top_k_v, top_p_v)
                    else:
                        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    step = active.astype(jnp.int32)
                    n2 = n_gen + step
                    new_done = done | (active & (eos_v >= 0)
                                       & (nxt == eos_v)) \
                        | (n2 >= max_new_v)
                    out = jnp.where(active, nxt, jnp.int32(0))
                    return (new_pools, jnp.where(active, nxt, tok),
                            pos + step, n2, new_done), out

                def body(carry, _):
                    # every row done: skip the whole transformer step
                    # (the whole-burst EOS short-circuit, per burst)
                    return jax.lax.cond(
                        jnp.all(carry[4]),
                        lambda c: (c, jnp.zeros_like(c[1])),
                        live, carry)

                carry0 = (pools, tok, pos.astype(jnp.int32),
                          n_gen.astype(jnp.int32), done)
                (pools, tok, pos, n_gen, done), ys = jax.lax.scan(
                    body, carry0, jnp.arange(k_burst))
                return (pools, jnp.swapaxes(ys, 0, 1), tok, pos, n_gen,
                        done)
            return burst
        return self._jit(
            ("gen_burst", slots, k_burst, max_blocks, num_blocks,
             block_size, bool(sampling)), builder, donate=(1,))

    # ------------------------------------------ speculative decoding
    # (serving/continuous.py speculative=True rounds: this generator
    # built on the DRAFT net runs spec_draft_program, the TARGET net's
    # generator runs spec_verify_program — two dispatches per round)

    def spec_draft_program(self, slots: int, k_spec: int, max_blocks: int,
                           num_blocks: int, block_size: int):
        """K chained draft proposals on this (draft) net's OWN paged
        lane: feed the pending token at ``pos``, sample proposal
        ``x_{s+1}`` from the filtered draft distribution on the DRAFT
        fold lane at token index ``n_gen + s``, feed it back. Rows with
        ``temp <= 0`` propose greedily (argmax of the raw logits — the
        same greedy the plain sampler degenerates to). ``live`` masks
        padding rows (their writes redirect to the trash block).
        Returns (pools, proposals [slots, K], q [slots, K, V]) — q is
        the filtered proposal distribution softmax the verify program's
        rejection test divides by. No EOS/max-new gating in-program:
        the scheduler truncates on the host, so accept length never
        shapes a compiled program (the reason the accept "ladder" is
        one fixed (slots × K) shape and steady state compiles
        nothing).

        The scan runs K+1 steps: the extra step feeds the LAST proposal
        back so its own K/V lands in the draft pool (its sampled token
        is discarded). Without it an all-accepted round would leave the
        draft lane one position short of the target — the next round's
        feed position would attend an unwritten slot. Discarded draws
        are harmless per the stopping-time argument above."""
        def builder():
            def draft(params, pools, tables, pos, tok, n_gen, keys,
                      temp_v, top_k_v, top_p_v, live):
                p_emb = self._cast(params[self.emb.name])
                dkeys = spec_lane_keys(keys, SPEC_DRAFT_SALT)

                def step(carry, s):
                    pools, tok, pos = carry
                    x = self._embed_token(p_emb, tok, pos)
                    new_pools = []
                    for blk, pool in zip(self.blocks, pools):
                        cache = dict(pool)
                        cache["table"] = tables
                        x, cache = blk.decode_step(
                            self._cast(params[blk.name]), x, cache, pos,
                            write_mask=live)
                        new_pools.append({name: cache[name]
                                          for name in pool})
                    logits = self._head_logits(params, x)
                    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                    lgf = _filter_logits(logits, temp_v, top_k_v, top_p_v)
                    step_keys = jax.vmap(jax.random.fold_in)(dkeys,
                                                             n_gen + s)
                    g = jax.vmap(lambda k: jax.random.gumbel(
                        k, (lgf.shape[-1],), jnp.float32))(step_keys)
                    sampled = jnp.argmax(lgf + g, axis=-1).astype(jnp.int32)
                    nxt = jnp.where(temp_v > 0.0, sampled, greedy)
                    nxt = jnp.where(live, nxt, tok)
                    q = jax.nn.softmax(lgf, axis=-1)
                    return ((new_pools, nxt,
                             pos + live.astype(jnp.int32)), (nxt, q))

                (pools, _, _), (ys, qs) = jax.lax.scan(
                    step, (pools, tok, pos.astype(jnp.int32)),
                    jnp.arange(k_spec + 1))
                return (pools, jnp.swapaxes(ys, 0, 1)[:, :k_spec],
                        jnp.swapaxes(qs, 0, 1)[:, :k_spec])
            return draft
        return self._jit(
            ("gen_spec_draft", slots, k_spec, max_blocks, num_blocks,
             block_size), builder, donate=(1,))

    def spec_verify_program(self, slots: int, k_spec: int, max_blocks: int,
                            num_blocks: int, block_size: int):
        """ONE target forward over the pending token + K proposals
        (``prefill_paged``'s per-row traced-positions machinery — the
        tail-prefill body with logits taken at EVERY position) fused
        with the exact rejection sampler. Position ``i`` accepts
        proposal ``x_{i+1}`` with probability ``min(1, p_i[x]/q_i[x])``
        (greedy rows: accept iff the target argmax equals it); the
        first rejection draws the correction from the normalized
        residual ``max(p_a − q_a, 0)``; a fully-accepted row draws the
        bonus token straight from ``p_K`` through the same gather (q
        pads with zeros at index K, making the residual p itself).
        Accept uniforms ride the ACCEPT fold lane and residual/bonus
        gumbels the RESID lane, both at the token's own index — see
        the lane-salt doctrine above. Returns (pools, out_tokens
        [slots, K+1] — accepted proposals with the correction/bonus
        scattered at index ``a``; entries past ``a`` are dead, the host
        truncates — and accept_len [slots])."""
        t = k_spec + 1

        def builder():
            def verify(params, pools, tables, pos, tok, props, q, n_gen,
                       keys, temp_v, top_k_v, top_p_v, live):
                p_emb = self._cast(params[self.emb.name])
                ids = jnp.concatenate([tok[:, None], props], axis=1)
                posm = pos[:, None] + jnp.arange(t)[None, :]
                x = self.emb._slice_replicate(
                    qtake(p_emb, "W", ids)
                    + jnp.take(p_emb["P"], posm, axis=0))
                write_ok = jnp.broadcast_to(live[:, None], ids.shape)
                new_pools = []
                for blk, pool in zip(self.blocks, pools):
                    x, pool = blk.prefill_paged(
                        self._cast(params[blk.name]), x, pool, tables,
                        posm, write_ok)
                    new_pools.append(pool)
                lg = self._head_logits(
                    params, x.reshape(slots * t, x.shape[-1])
                ).reshape(slots, t, -1)
                vocab = lg.shape[-1]
                g_tok = jnp.argmax(lg, axis=-1).astype(jnp.int32)
                pf = _filter_logits(
                    lg.reshape(slots * t, vocab), jnp.repeat(temp_v, t),
                    jnp.repeat(top_k_v, t), jnp.repeat(top_p_v, t)
                ).reshape(slots, t, vocab)
                p = jax.nn.softmax(pf, axis=-1)
                # accept test u_i * q_i[x] < p_i[x] (division-free) on
                # the ACCEPT lane at the proposal's own token index
                akeys = spec_lane_keys(keys, SPEC_ACCEPT_SALT)
                folds = (n_gen[:, None]
                         + jnp.arange(k_spec)[None, :]).reshape(-1)
                ukeys = jax.vmap(jax.random.fold_in)(
                    jnp.repeat(akeys, k_spec, axis=0), folds)
                u = jax.vmap(lambda k: jax.random.uniform(
                    k, (), jnp.float32))(ukeys).reshape(slots, k_spec)
                px = jnp.take_along_axis(p[:, :k_spec], props[..., None],
                                         axis=-1)[..., 0]
                qx = jnp.take_along_axis(q, props[..., None],
                                         axis=-1)[..., 0]
                acc = jnp.where(temp_v[:, None] > 0.0, u * qx < px,
                                g_tok[:, :k_spec] == props)
                a = jnp.sum(jnp.cumprod(acc.astype(jnp.int32), axis=1),
                            axis=1)
                # correction/bonus from the residual at the first
                # rejected position (a == K: q_pad is zero, residual=p_K)
                p_a = jnp.take_along_axis(p, a[:, None, None],
                                          axis=1)[:, 0]
                q_pad = jnp.concatenate(
                    [q, jnp.zeros((slots, 1, vocab), q.dtype)], axis=1)
                q_a = jnp.take_along_axis(q_pad, a[:, None, None],
                                          axis=1)[:, 0]
                r = jnp.maximum(p_a - q_a, 0.0)
                # float-degenerate p ≈ q can zero the residual; a true
                # rejection implies p < q somewhere, so falling back to
                # p itself only fires inside rounding error of p == q
                rr = jnp.where(jnp.sum(r, axis=-1, keepdims=True) > 0.0,
                               r, p_a)
                neg = jnp.asarray(jnp.finfo(jnp.float32).min, jnp.float32)
                logr = jnp.where(rr > 0.0,
                                 jnp.log(jnp.maximum(rr, 1e-38)), neg)
                rkeys = jax.vmap(jax.random.fold_in)(
                    spec_lane_keys(keys, SPEC_RESID_SALT), n_gen + a)
                gr = jax.vmap(lambda k: jax.random.gumbel(
                    k, (vocab,), jnp.float32))(rkeys)
                corr_s = jnp.argmax(logr + gr, axis=-1).astype(jnp.int32)
                corr_g = jnp.take_along_axis(g_tok, a[:, None],
                                             axis=1)[:, 0]
                corr = jnp.where(temp_v > 0.0, corr_s, corr_g)
                padded = jnp.concatenate(
                    [props, jnp.zeros((slots, 1), jnp.int32)], axis=1)
                out = jnp.where(jnp.arange(t)[None, :] == a[:, None],
                                corr[:, None], padded)
                return new_pools, out, a
            return verify
        return self._jit(
            ("gen_spec_verify", slots, k_spec, max_blocks, num_blocks,
             block_size), builder, donate=(1,))

    def run_eager(self, params, ids, lengths, max_new, sampler, keys,
                  replica=None) -> np.ndarray:
        """Per-token host-loop reference: same prefill, then ONE
        dispatch per generated token (the pre-fused status quo). Same
        math and same per-row PRNG fold indices as ``run``, so the two
        agree token-for-token."""
        temperature, top_k, top_p, eos = sampler
        b, t_pad = ids.shape
        cache_len = t_pad + max_new
        pre = self._get_prefill(cache_len)
        caches, logits0 = pre(params, jnp.asarray(ids, jnp.int32),
                              jnp.asarray(lengths, jnp.int32))
        keys_d = jnp.asarray(keys)

        def builder_sample():
            return lambda lg, k, s: sample_tokens(
                lg, k, s, temperature, top_k, top_p)
        samp = self._jit(("gen_sample",) + sampler[:3], builder_sample)

        def builder_step():
            def step(params, caches, tok, pos, keys, s):
                p_emb = self._cast(params[self.emb.name])
                x = self._embed_token(p_emb, tok, pos)
                new_caches = []
                for blk, cache in zip(self.blocks, caches):
                    x, cache = blk.decode_step(
                        self._cast(params[blk.name]), x, cache, pos)
                    new_caches.append(cache)
                nxt = sample_tokens(self._head_logits(params, x),
                                    keys, s, temperature, top_k, top_p)
                return new_caches, nxt
            return step
        step = self._jit(("gen_step",) + sampler[:3], builder_step)

        tok = np.asarray(samp(logits0, keys_d, jnp.int32(0)))
        done = np.zeros(b, bool)
        if eos is not None:
            tok = np.where(np.asarray(lengths) == 0, eos, tok)
            done |= tok == eos
        pos = np.asarray(lengths, np.int32)
        out = [tok]
        for s in range(1, max_new):
            caches, nxt = step(params, caches, jnp.asarray(tok, jnp.int32),
                               jnp.asarray(pos, jnp.int32), keys_d,
                               jnp.int32(s))
            nxt = np.asarray(nxt)
            if eos is not None:
                nxt = np.where(done, eos, nxt)
                done |= nxt == eos
            pos = pos + 1
            out.append(nxt)
            tok = nxt
        return np.stack(out, axis=1)


class RecurrentGenerator(_GeneratorBase):
    """Char-RNN generation for GravesLSTM stacks through the existing
    scanned ``one_step`` recurrence: the prompt streams through one
    masked scan (bucketed length, carries held past each row's end),
    then the whole decode runs as one scan feeding sampled ids back as
    one-hot rows. No positional state — the carry IS the history."""

    def __init__(self, net, impls):
        super().__init__(net, impls)
        self.n_in = impls[0].conf.n_in
        self._rec = [i for i in impls[:-1] if hasattr(i, "rnn_time_step")]
        self._head_in = impls[-2].conf.n_out

    def prompt_bucket(self, t_in: int, max_new: int) -> int:
        if t_in < 1:
            raise ValueError(f"empty prompt (length {t_in})")
        return _pow2_bucket(t_in)

    def _init_state(self, b: int):
        dt = self.net._dtype
        return {i.name: {"h": jnp.zeros((b, i.conf.n_out), dt),
                         "c": jnp.zeros((b, i.conf.n_out), dt)}
                for i in self._rec}

    def _one_step(self, params, rstate, xt):
        """Whole-stack one-timestep forward below the head (the
        MultiLayerNetwork ``_make_rnn_step`` recurrence): returns the
        head INPUT [b, f] + new carries."""
        new_rstate = dict(rstate)
        for impl in self.impls[:-1]:
            if hasattr(impl, "rnn_time_step"):
                xt, new_rstate[impl.name] = impl.rnn_time_step(
                    params[impl.name], xt, rstate[impl.name])
            else:
                xt, _ = impl.forward(params[impl.name], xt,
                                     self.net.states[impl.name],
                                     False, None)
        return xt, new_rstate

    def _get_prefill(self):
        def builder():
            def prefill(params, ids, lengths):
                b, t_pad = ids.shape
                dt = self.net._dtype
                xs = jax.nn.one_hot(ids, self.n_in, dtype=dt)  # [b,t,v]

                def body(carry, inp):
                    rstate, last_h = carry
                    xt, t = inp
                    h, new_rstate = self._one_step(params, rstate, xt)
                    upd = t < lengths  # hold carries past each row's end
                    rstate = jax.tree.map(
                        lambda new, old: jnp.where(upd[:, None], new, old),
                        new_rstate, rstate)
                    last_h = jnp.where((t == lengths - 1)[:, None],
                                       h, last_h)
                    return (rstate, last_h), None

                carry0 = (self._init_state(b),
                          jnp.zeros((b, self._head_in), dt))
                (rstate, last_h), _ = jax.lax.scan(
                    body, carry0,
                    (jnp.swapaxes(xs, 0, 1), jnp.arange(t_pad)))
                return rstate, self._head_logits(params, last_h)
            return prefill
        return self._jit(("gen_rnn_prefill",), builder)

    def _get_decode(self, max_new: int, sampler: SamplerSig):
        temperature, top_k, top_p, eos = sampler

        def builder():
            def decode(params, rstate, logits0, lengths, keys):
                dt = self.net._dtype
                tok0 = sample_tokens(logits0, keys, 0,
                                     temperature, top_k, top_p)
                if eos is not None:
                    tok0 = jnp.where(lengths == 0, eos, tok0)
                    done0 = tok0 == eos
                else:
                    done0 = jnp.zeros(tok0.shape, bool)

                def live(args, s):
                    rstate, tok, done = args
                    xt = jax.nn.one_hot(tok, self.n_in, dtype=dt)
                    h, rstate = self._one_step(params, rstate, xt)
                    nxt = sample_tokens(self._head_logits(params, h),
                                        keys, s + 1,
                                        temperature, top_k, top_p)
                    if eos is not None:
                        nxt = jnp.where(done, eos, nxt)
                        done = done | (nxt == eos)
                    return rstate, nxt, done

                def body(carry, s):
                    if eos is not None:
                        carry = jax.lax.cond(
                            jnp.all(carry[2]),
                            lambda a: (a[0], jnp.full_like(a[1], eos),
                                       a[2]),
                            lambda a: live(a, s), carry)
                    else:
                        carry = live(carry, s)
                    return carry, carry[1]

                carry, ys = jax.lax.scan(body, (rstate, tok0, done0),
                                         jnp.arange(max_new - 1))
                # final carries ride out so the donation can alias them
                # (same contract as TransformerGenerator's decode)
                return jnp.concatenate(
                    [tok0[:, None], jnp.swapaxes(ys, 0, 1)], axis=1), carry[0]
            return decode
        return self._jit(("gen_rnn_decode", max_new) + sampler, builder,
                         donate_caches=True)

    def run(self, params, ids, lengths, max_new, sampler, keys,
            replica=None, device=None) -> np.ndarray:
        b, t_pad = ids.shape
        reg = get_registry()
        put = (lambda a: jax.device_put(a, device)) if device is not None \
            else (lambda a: a)
        ids_d = put(jnp.asarray(ids, jnp.int32))
        len_d = put(jnp.asarray(lengths, jnp.int32))
        keys_d = put(jnp.asarray(keys))

        pre = self._get_prefill()
        fresh = note_dispatch(self.net,
                              ("gen_rnn_prefill", replica, b, t_pad))
        t0 = time.perf_counter()
        with span("compile" if fresh else "inference",
                  path="generate_prefill", bucket=t_pad, rows=b):
            rstate, logits0 = pre(params, ids_d, len_d)
            # SANCTIONED SYNC (1 of 2 per request): phase fence, same
            # contract as TransformerGenerator.run
            # dl4j-lint: disable=hot-path-host-sync
            jax.block_until_ready(logits0)
        t1 = time.perf_counter()

        dec = self._get_decode(max_new, sampler)
        fresh = note_dispatch(
            self.net, ("gen_rnn_decode", replica, b, max_new) + sampler)
        with span("compile" if fresh else "inference",
                  path="generate_decode", rows=b, max_new=max_new):
            # SANCTIONED SYNC (2 of 2): one whole-burst token fetch
            # dl4j-lint: disable=hot-path-host-sync
            toks = np.asarray(
                dec(params, rstate, logits0, len_d, keys_d)[0])
        t2 = time.perf_counter()
        # dl4j-lint: disable=hot-path-host-sync — host ints, ms math
        self._observe(reg, b, int(np.sum(lengths)), max_new,
                      (t1 - t0) * 1e3, (t2 - t1) * 1e3)
        return toks

    def run_eager(self, params, ids, lengths, max_new, sampler, keys,
                  replica=None) -> np.ndarray:
        temperature, top_k, top_p, eos = sampler
        b, _ = ids.shape
        pre = self._get_prefill()
        rstate, logits0 = pre(params, jnp.asarray(ids, jnp.int32),
                              jnp.asarray(lengths, jnp.int32))
        keys_d = jnp.asarray(keys)

        def builder_sample():
            return lambda lg, k, s: sample_tokens(
                lg, k, s, temperature, top_k, top_p)
        samp = self._jit(("gen_sample",) + sampler[:3], builder_sample)

        def builder_step():
            def step(params, rstate, tok, keys, s):
                xt = jax.nn.one_hot(tok, self.n_in, dtype=self.net._dtype)
                h, rstate = self._one_step(params, rstate, xt)
                nxt = sample_tokens(self._head_logits(params, h), keys, s,
                                    temperature, top_k, top_p)
                return rstate, nxt
            return step
        step = self._jit(("gen_rnn_step",) + sampler[:3], builder_step)

        tok = np.asarray(samp(logits0, keys_d, jnp.int32(0)))
        done = np.zeros(b, bool)
        if eos is not None:
            tok = np.where(np.asarray(lengths) == 0, eos, tok)
            done |= tok == eos
        out = [tok]
        for s in range(1, max_new):
            rstate, nxt = step(params, rstate, jnp.asarray(tok, jnp.int32),
                               keys_d, jnp.int32(s))
            nxt = np.asarray(nxt)
            if eos is not None:
                nxt = np.where(done, eos, nxt)
                done |= nxt == eos
            out.append(nxt)
            tok = nxt
        return np.stack(out, axis=1)


def build_generator(net):
    """Detect the net's generation family and build (or return the
    cached) generator: SequenceEmbedding → TransformerBlock* → head
    stacks get KV-cache prefill/decode; stacks with ``rnn_time_step``
    layers get the scanned-recurrence path. Anything else raises."""
    gen = net.__dict__.get("_generator")
    if gen is not None and gen.net is net:
        return gen
    impls = _ordered_impls(net)
    if (len(impls) >= 3 and isinstance(impls[0], SequenceEmbeddingImpl)
            and all(isinstance(i, TransformerBlockImpl)
                    for i in impls[1:-1])
            and impls[-1].has_loss()):
        gen = TransformerGenerator(net, impls)
    elif (len(impls) >= 2 and impls[-1].has_loss()
          and any(hasattr(i, "rnn_time_step") for i in impls[:-1])):
        gen = RecurrentGenerator(net, impls)
    else:
        raise ValueError(
            "generate() needs a SequenceEmbedding + TransformerBlock "
            "stack or a recurrent (rnn_time_step) stack under an "
            f"output head; got {[type(i).__name__ for i in impls]}")
    net.__dict__["_generator"] = gen
    return gen


def _prep(net, prompt_ids, max_new_tokens: int):
    gen = build_generator(net)
    prompt = np.asarray(prompt_ids)
    if prompt.ndim != 2:
        raise ValueError(
            f"prompt_ids must be [batch, t] int tokens, got {prompt.shape}")
    max_new = int(max_new_tokens)
    if max_new < 1:
        raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
    b, t_in = prompt.shape
    t_pad = gen.prompt_bucket(t_in, max_new)
    ids = np.zeros((b, t_pad), np.int32)
    ids[:, :t_in] = prompt
    lengths = np.full((b,), t_in, np.int32)
    return gen, prompt, ids, lengths, max_new


def generate(net, prompt_ids, max_new_tokens: int, *,
             temperature: float = 0.0, top_k: int = 0, top_p: float = 0.0,
             eos_token: Optional[int] = None, seed: int = 0) -> np.ndarray:
    """Fused autoregressive generation — the transformer analog of the
    stateful ``rnnTimeStep`` path (``MultiLayerNetwork.java:1233``
    role), TWO dispatches end to end (bucketed prefill + one-scan
    decode) instead of one per token.

    ``prompt_ids``: [b, t0] int tokens. Returns
    [b, t0 + max_new_tokens] int64 (prompt + generated). With
    ``eos_token`` set, a finished row's remaining slots are filled with
    the EOS id and the decode step short-circuits once every row is
    done. ``temperature`` 0 = greedy; else softmax sampling through the
    optional ``top_k``/``top_p`` filters, seeded per row by ``seed``.
    """
    gen, prompt, ids, lengths, max_new = _prep(net, prompt_ids,
                                               max_new_tokens)
    get_registry().counter(DECODE_REQUESTS_COUNTER,
                           "generate() requests").inc()
    toks = gen.run(net.params, ids, lengths, max_new,
                   sampler_sig(temperature, top_k, top_p, eos_token),
                   row_keys(seed, prompt.shape[0]))
    return np.concatenate([prompt.astype(np.int64),
                           toks.astype(np.int64)], axis=1)


def generate_eager(net, prompt_ids, max_new_tokens: int, *,
                   temperature: float = 0.0, top_k: int = 0,
                   top_p: float = 0.0, eos_token: Optional[int] = None,
                   seed: int = 0) -> np.ndarray:
    """Per-token host-loop reference for :func:`generate` — identical
    math and PRNG schedule, one dispatch per token. The correctness
    oracle."""
    gen, prompt, ids, lengths, max_new = _prep(net, prompt_ids,
                                               max_new_tokens)
    toks = gen.run_eager(net.params, ids, lengths, max_new,
                         sampler_sig(temperature, top_k, top_p, eos_token),
                         row_keys(seed, prompt.shape[0]))
    return np.concatenate([prompt.astype(np.int64),
                           toks.astype(np.int64)], axis=1)
