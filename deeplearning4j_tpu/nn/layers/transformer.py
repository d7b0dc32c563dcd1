"""Transformer block + sequence embedding layer impls.

No reference counterpart (SURVEY §7.7 extension — the reference's only
sequence model is the Graves LSTM); these are the layers the modern
long-context stack is built from, wired so ONE config runs single-chip
(flash Pallas kernel, ``ops/flash_attention.py``) or sequence-parallel
(ring attention over the mesh ``seq`` axis, DP×SP composed) with no
model change — the same auto-select doctrine as ``AttentionImpl``.

Pre-LN wiring (x + Attn(LN(x)), x + MLP(LN(x))): the standard stable
variant; LayerNorm runs in f32 even under a bf16 compute policy
(variance of bf16 activations underflows), matching the output-head-f32
rule in ``multilayer.py``.
"""

from __future__ import annotations

import contextlib
from typing import Dict

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.attention import (
    dispatch_qkv_attention, xla_attention)
from deeplearning4j_tpu.nn.layers.base import (
    LayerImpl, apply_dropout, register_impl)
from deeplearning4j_tpu.nn.layers.moe import (
    AUX_LOSS_KEY, init_moe_params, run_moe_ffn)
from deeplearning4j_tpu.nn.quantize import (kv_dequantize, kv_quantize,
                                            qmatmul, qtake)
from deeplearning4j_tpu.nn.weights import init_weights


def _layer_norm(x, gamma, beta, eps=1e-5):
    xf = x.astype(jnp.float32)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    out = (xf - mean) * jax.lax.rsqrt(var + eps) * gamma + beta
    return out.astype(x.dtype)


def _attend_cached(q, k, v, live):
    """Masked softmax attention of queries ``q`` [b, t, h, hd] over cached
    keys and values [b, n, h, hd]; ``live`` [b or 1, t, n] says which
    slots each query may see. → [b, t, h, hd]."""
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k.astype(q.dtype)) * scale
    s = jnp.where(live[:, None], s,
                  jnp.asarray(jnp.finfo(s.dtype).min, s.dtype))
    w = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(q.dtype))


@register_impl(L.SequenceEmbeddingLayer)
class SequenceEmbeddingImpl(LayerImpl):
    """int ids [b, t] → [b, t, d]: token gather + learned positions."""

    cast_input = False  # ids must stay exact (see LayerImpl.cast_input)

    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        c = self.conf
        kw, kp = jax.random.split(key)
        W = init_weights(kw, (c.n_in, c.n_out), self.weight_init,
                         c.n_in, c.n_out, c.dist_mean, c.dist_std,
                         dist=c.dist)
        if not c.positions:
            return {"W": W}
        P = 0.01 * jax.random.normal(kp, (c.max_len, c.n_out), jnp.float32)
        return {"W": W, "P": P}

    def forward(self, params, x, state, train, rng=None, mask=None):
        idx = x.astype(jnp.int32)
        if idx.ndim == 3:  # one-hot input tolerated
            idx = jnp.argmax(idx, axis=-1)
        t = idx.shape[1]
        if self.conf.positions and t > self.conf.max_len:
            raise ValueError(f"sequence length {t} > max_len {self.conf.max_len}")
        with jax.named_scope("embed"):
            z = qtake(params, "W", idx)
            if self.conf.positions:
                z = z + params["P"][:t][None]
            if self.conf.output_multiplier != 1.0:
                z = (z * self.conf.output_multiplier).astype(z.dtype)
        return self._slice_replicate(z), state


@register_impl(L.TransformerBlock)
class TransformerBlockImpl(LayerImpl):
    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        c = self.conf
        if c.n_out != c.n_in:
            raise ValueError("TransformerBlock needs n_in == n_out (d_model)")
        if c.n_out % c.num_heads != 0:
            raise ValueError(f"d_model {c.n_out} not divisible by "
                             f"num_heads {c.num_heads}")
        d, f = c.n_out, c.ffn_mult * c.n_out
        # split(key, 4) as in the dense-only original: a fixed seed must
        # keep producing bit-identical dense-block inits
        ks = jax.random.split(key, 4)
        mk = lambda k, shape: init_weights(k, shape, self.weight_init,
                                           shape[0], shape[1],
                                           c.dist_mean, c.dist_std,
                                           dist=c.dist)
        params = {
            "Wqkv": mk(ks[0], (d, 3 * d)),
            "Wo": mk(ks[1], (d, d)),
            "ln1_g": jnp.ones((d,), jnp.float32),
            "ln1_b": jnp.zeros((d,), jnp.float32),
            "ln2_g": jnp.ones((d,), jnp.float32),
            "ln2_b": jnp.zeros((d,), jnp.float32),
        }
        if c.num_experts > 0:  # Mixtral-style routed MLP (shared init)
            params.update(init_moe_params(
                ks[2], d, f, c.num_experts, self.weight_init,
                c.dist_mean, c.dist_std, dist=c.dist))
        else:
            params.update({
                "W1": mk(ks[2], (d, f)), "b1": jnp.zeros((f,), jnp.float32),
                "W2": mk(ks[3], (f, d)), "b2": jnp.zeros((d,), jnp.float32),
            })
        return params

    def init_state(self):
        if self.conf.num_experts > 0:
            return {AUX_LOSS_KEY: jnp.zeros((), jnp.float32)}
        return {}

    def forward(self, params, x, state, train, rng=None, mask=None):
        if x.ndim != 3:
            raise ValueError(f"TransformerBlock needs [b, t, d], got {x.shape}")
        out, new_state, _ = self._block(
            params, x, lambda qkv: self._attend_sequence(qkv, mask),
            state=state, train=train, rng=rng, mask=mask,
            capacity_factor=self.conf.capacity_factor)
        return out, new_state

    def _block(self, params, x, attend, *, state, train, rng, mask,
               capacity_factor):
        """The block's wiring, written once, over ``x`` of [..., d]:
        x + Wo·attend(Wqkv·LN(x)), then x + FFN(LN(x)). ``attend`` maps
        the fused projection [..., 3d] to (o [..., d], new KV store) and is
        all that ``forward``, ``prefill``, ``prefill_paged`` and
        ``decode_step`` differ in. Returns (out, new state, new store)."""
        d = x.shape[-1]
        # static scope names: the device trace reads each part of the
        # block under them (util/profiler.scope_seconds); JAX adds
        # jvp(...) / transpose(jvp(...)) for forward and backward
        with jax.named_scope("ln1"):
            h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        with jax.named_scope("qkv_proj"):
            qkv = qmatmul(h, params, "Wqkv")
        with jax.named_scope("attention"):
            o, store = attend(qkv)
        with jax.named_scope("attn_out_proj"):
            attn = qmatmul(self._slice_replicate(o), params, "Wo")
        if train and self.dropout_rate > 0.0 and rng is not None:
            attn = apply_dropout(attn, self.dropout_rate,
                                 jax.random.fold_in(rng, 1))
        # replicate BEFORE ln2: its mean/var reduce over the feature dim
        # the attn matmul left sharded
        x = self._slice_replicate(x + attn)

        with jax.named_scope("ln2"):
            h2 = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        mlp, new_state = self._ffn(params, h2.reshape(-1, d), state,
                                   mask=mask,
                                   capacity_factor=capacity_factor)
        mlp = mlp.reshape(x.shape)
        if train and self.dropout_rate > 0.0 and rng is not None:
            mlp = apply_dropout(mlp, self.dropout_rate,
                                jax.random.fold_in(rng, 2))
        out = self._slice_replicate(x + mlp)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, new_state, store

    def _serve(self, params, x, attend):
        """``_block`` as every serving entry point runs it: inference,
        maskless, and MoE routed NO-DROP (capacity = ceil(cf·n/E) >= n
        when cf = E) — the training-time capacity heuristic over b·t
        tokens has no stepwise equivalent, and dropping tokens at
        inference is never what serving wants. Returns (out, store)."""
        out, _, store = self._block(
            params, x, attend, state={}, train=False, rng=None, mask=None,
            capacity_factor=float(max(1, self.conf.num_experts)))
        return out, store

    def _ffn(self, params, h2, state, mask=None, capacity_factor=None):
        """Post-LN2 feed-forward over flattened tokens [n, d]: dense
        GELU MLP or routed experts — the ONE implementation both
        ``forward`` and ``decode_step`` use."""
        c = self.conf
        if c.num_experts > 0:
            return run_moe_ffn(params, h2, capacity_factor,
                               c.aux_loss_weight, mask=mask)
        with jax.named_scope("mlp_fc"):
            mlp = jax.nn.gelu(qmatmul(h2, params, "W1")
                              + params["b1"].astype(h2.dtype))
        # sliced: W1 is column-sharded so mlp is sharded on its hidden
        # dim — all-gather it before W2 contracts over that dim, so the
        # contraction never reduces across shards (bitwise seam)
        mlp = self._slice_replicate(mlp)
        with jax.named_scope("mlp_proj"):
            mlp = qmatmul(mlp, params, "W2") \
                + params["b2"].astype(h2.dtype)
        return mlp, state

    # ------------------------------------------- incremental decoding

    def init_cache(self, batch: int, max_len: int, dtype=jnp.float32):
        """KV cache for autoregressive decoding (the transformer analog
        of ``BaseRecurrentLayer`` stateMap / ``rnnTimeStep``)."""
        c = self.conf
        h, hd = c.num_heads, c.n_out // c.num_heads
        shape = (batch, max_len, h, hd)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}

    def prefill(self, params, x, cache):
        """Batched prompt forward that ALSO writes every position's K/V
        into ``cache`` (the ``decode_step`` layout): [b, t, d] →
        ([b, t, d], cache). The attention/residual math is exactly
        ``forward``'s (causal flash/ring dispatch, maskless), so prefill
        hidden states equal ``forward``'s; the FFN routes NO-DROP like
        ``decode_step`` when MoE (serving never wants dropped tokens).
        Right-padded prompt rows are safe: a padded position's garbage
        K/V slot is only ever attended to after a decode step has
        overwritten it (decode writes slot ``pos`` before reading)."""
        def attend(qkv):
            _, k, v = self._heads(qkv)
            write = lambda buf, z: jax.lax.dynamic_update_slice_in_dim(
                buf, z.astype(buf.dtype), 0, axis=1)
            o, _ = self._attend_sequence(qkv, None)
            return o, {"k": write(cache["k"], k), "v": write(cache["v"], v)}
        return self._serve(params, x, attend)

    def prefill_paged(self, params, x, pool, table, pos, write_ok):
        """Chunked (tail) prefill straight through the paged pool — the
        prefix-cache admission path: the prompt's cached prefix already
        lives in pool blocks, so only the TAIL runs here. ``x`` is
        [b, t, d] tail activations, ``pos`` [b, t] each tail token's
        ABSOLUTE cache position (per-row ``start + j`` — the cached
        prefix length enters traced, so one compiled program serves any
        match-length mix), ``write_ok`` [b, t] masks padding positions
        (their writes redirect to trash block 0, the ``decode_step``
        discipline). Tail K/V scatters into the row's table blocks
        FIRST, then attention gathers the whole table back — so tail
        self-attention sees its own fresh K/V and the cached prefix in
        one causal pass (``_attend_paged``). Returns ([b, t, d] out,
        new pool {"k", "v"})."""
        return self._serve(params, x, lambda qkv: self._attend_paged(
            qkv, pool, table, pos, write_ok))

    def decode_step(self, params, x_t, cache, pos, write_mask=None):
        """One-token forward [b, d] with cached keys/values; ``pos`` is
        the (traced) current position — a scalar (whole-batch position)
        or a [b] vector (per-row positions, the ragged-prompt serving
        path; the K/V write becomes a per-row one-hot scatter). Returns
        (y_t [b, d], new cache). Dense blocks match ``forward`` exactly
        at every prefix position (tested); MoE blocks route NO-DROP at
        decode time (capacity = batch, ``_serve``).

        **Paged mode** (the vLLM PagedAttention layout, nn/kvpool.py):
        when ``cache`` carries a ``"table"`` entry, ``cache["k"]`` /
        ``cache["v"]`` are the SHARED pool buffers
        ``[num_blocks, block_size, h, hd]`` and ``cache["table"]`` is
        the per-row block table ``[b, max_blocks]`` of pool indices.
        The K/V write scatters into (table[pos // bs], pos % bs) and
        attention gathers the row's blocks back into causal order;
        ``write_mask`` [b] bool redirects masked rows' writes to the
        reserved trash block 0, so retired rows / batch-slot padding /
        warmup dispatches can never scribble over a live sequence's
        blocks. ``pos`` must be a [b] vector in paged mode: the step is
        ``prefill_paged``'s attention at ``t = 1``."""
        if "table" not in cache:
            return self._serve(params, x_t, lambda qkv: self._attend_cache(
                qkv, cache, pos))

        def attend(qkv):
            o, new_cache = self._attend_paged(
                qkv[:, None], cache, cache["table"], pos[:, None],
                None if write_mask is None else write_mask[:, None])
            return o[:, 0], new_cache
        return self._serve(params, x_t, attend)

    # --------------------------------- attention, by where K/V are kept

    def _heads(self, qkv):
        """The fused projection [..., 3d] as q, k, v of [..., h, hd]."""
        return tuple(z.reshape(*z.shape[:-1], self.conf.num_heads, -1)
                     for z in jnp.split(qkv, 3, axis=-1))

    def _attend_sequence(self, qkv, mask):
        """A whole sequence [b, t, 3d] over itself, nothing read from a
        store and none made: the flash/ring dispatch on the fused
        projection → ([b, t, d], None)."""
        c = self.conf
        # sliced serving: heads are sharded over tp — the Pallas flash
        # kernel cannot see the mesh, so stay on the XLA formulation
        # GSPMD partitions per-head
        force_xla = xla_attention() if self._slice_mesh is not None \
            else contextlib.nullcontext()
        with force_xla:
            return dispatch_qkv_attention(
                qkv, c.num_heads, causal=c.causal, mask=mask,
                mesh=self._mesh), None

    def _attend_cache(self, qkv, cache, pos):
        """One token a row [b, 3d] against the dense cache
        [b, L, h, hd]: write slot ``pos`` FIRST (scalar: one
        dynamic_update_slice; [b] vector: a per-row one-hot write), then
        attend over the slots <= ``pos`` → ([b, d], new cache)."""
        q, k, v = (z[:, None] for z in self._heads(qkv))
        slots = jnp.arange(cache["k"].shape[1])
        if jnp.ndim(pos) == 0:
            write = lambda buf, z: jax.lax.dynamic_update_slice_in_dim(
                buf, z.astype(buf.dtype), pos, axis=1)
            live = (slots <= pos)[None, :]       # causal, whole batch
        else:
            sel = (slots[None, :] == pos[:, None])[:, :, None, None]
            write = lambda buf, z: jnp.where(sel, z.astype(buf.dtype), buf)
            live = slots[None, :] <= pos[:, None]       # [b, L] per row
        ck, cv = write(cache["k"], k), write(cache["v"], v)
        o = _attend_cached(q, ck, cv, live[:, None])
        return o.reshape(qkv.shape[0], -1), {"k": ck, "v": cv}

    def _attend_paged(self, qkv, pool, table, pos, write_ok):
        """Gather/scatter attention over a block table, for ``t`` tokens
        a row ([b, t, 3d], ``pos`` and ``write_ok`` [b, t]; ``write_ok``
        None means all true): scatter each token's K/V into its row's
        (block, offset) slot of the SHARED pool [NB, bs, h, hd], gather
        the row's blocks back as a contiguous [b, MB*bs] view, and run
        the dense cache's masked softmax over it. Positions whose
        ``write_ok`` is false write the trash block 0 — never a live
        sequence. Gathered positions past each query's ``pos`` (stale
        partial-block content, and every trash/garbage block the table
        pads with) are causally masked, so pool garbage is numerically
        inert exactly like the dense path's padded tail.

        A QUANTIZED pool (``"k_scale"``/``"v_scale"`` entries — the
        nn/kvpool.py int8/fp8 variant) quantizes incoming K/V per
        (position, head) over head_dim on the scatter and dequantizes
        the gathered view before the softmax; everything else — table
        discipline, trash redirect, causal mask — is identical, and the
        scale arrays ride the same (block, offset) addressing as the
        values. Returns ([b, t, d], ``pool`` with its buffers renewed)."""
        q, k, v = self._heads(qkv)
        b, t = pos.shape
        bs = pool["k"].shape[1]
        blk = jnp.take_along_axis(table, pos // bs, axis=1)
        off = pos % bs
        if write_ok is not None:
            blk = jnp.where(write_ok, blk, 0)
            off = jnp.where(write_ok, off, 0)
        quantized = "k_scale" in pool
        new_pool = dict(pool)

        def through_pool(name, z):
            buf = pool[name]
            if quantized:
                z, sc = kv_quantize(z, buf.dtype)
                new_pool[name + "_scale"] = \
                    pool[name + "_scale"].at[blk, off].set(sc)
            new_pool[name] = buf.at[blk, off].set(z.astype(buf.dtype))
            # the row's cache back in causal order: [b, MB*bs, h, hd]
            g = jnp.take(new_pool[name], table, axis=0).reshape(
                b, -1, *buf.shape[2:])
            if quantized:
                scales = jnp.take(new_pool[name + "_scale"], table,
                                  axis=0).reshape(b, -1, buf.shape[2])
                g = kv_dequantize(g, scales, q.dtype)
            return g

        live = jnp.arange(table.shape[1] * bs)[None, None, :] \
            <= pos[:, :, None]
        o = _attend_cached(q, through_pool("k", k), through_pool("v", v),
                           live)
        return o.reshape(b, t, -1), new_pool
