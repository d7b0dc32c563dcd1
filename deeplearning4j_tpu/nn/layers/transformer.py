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

from typing import Dict

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.attention import (
    dispatch_attention, dispatch_qkv_attention, xla_attention)
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
        c = self.conf
        if x.ndim != 3:
            raise ValueError(f"TransformerBlock needs [b, t, d], got {x.shape}")
        b, t, d = x.shape
        # static scope names: the device trace reads each part of the
        # block under them (util/profiler.scope_seconds); JAX adds
        # jvp(...) / transpose(jvp(...)) for forward and backward
        with jax.named_scope("ln1"):
            h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        with jax.named_scope("qkv_proj"):
            qkv = qmatmul(h, params, "Wqkv")
        with jax.named_scope("attention"):
            if self._slice_mesh is not None:
                # sliced serving: heads are sharded over tp — the Pallas
                # flash kernel cannot see the mesh, so stay on the XLA
                # formulation GSPMD partitions per-head
                with xla_attention():
                    o = dispatch_qkv_attention(qkv, c.num_heads,
                                               causal=c.causal, mask=mask)
            else:
                o = dispatch_qkv_attention(qkv, c.num_heads, causal=c.causal,
                                           mask=mask, mesh=self._mesh)
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
                                   capacity_factor=c.capacity_factor)
        mlp = mlp.reshape(b, t, d)
        if train and self.dropout_rate > 0.0 and rng is not None:
            mlp = apply_dropout(mlp, self.dropout_rate,
                                jax.random.fold_in(rng, 2))
        out = self._slice_replicate(x + mlp)
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, new_state

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
        c = self.conf
        b, t, d = x.shape
        h_count, hd = c.num_heads, c.n_out // c.num_heads
        h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        qkv = qmatmul(h, params, "Wqkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = lambda z: z.reshape(b, t, h_count, hd)
        q, k, v = shape(q), shape(k), shape(v)
        ck = jax.lax.dynamic_update_slice_in_dim(
            cache["k"], k.astype(cache["k"].dtype), 0, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(
            cache["v"], v.astype(cache["v"].dtype), 0, axis=1)
        if self._slice_mesh is not None:
            with xla_attention():
                o = dispatch_attention(q, k, v, causal=c.causal, mask=None)
        else:
            o = dispatch_attention(q, k, v, causal=c.causal, mask=None,
                                   mesh=self._mesh)
        x = self._slice_replicate(
            x + qmatmul(self._slice_replicate(o.reshape(b, t, d)),
                        params, "Wo"))
        h2 = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        mlp, _ = self._ffn(params, h2.reshape(-1, d), {},
                           capacity_factor=float(max(1, c.num_experts)))
        return self._slice_replicate(x + mlp.reshape(b, t, d)), \
            {"k": ck, "v": cv}

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
        one causal pass. Gathered positions past each query's ``pos``
        (stale partial-block content, trash padding) are causally
        masked, numerically inert exactly like the dense path's padded
        tail. Returns ([b, t, d] out, new pool {"k", "v"})."""
        c = self.conf
        b, t, d = x.shape
        h_count, hd = c.num_heads, c.n_out // c.num_heads
        h = _layer_norm(x, params["ln1_g"], params["ln1_b"])
        qkv = qmatmul(h, params, "Wqkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = lambda z: z.reshape(b, t, h_count, hd)
        q, k, v = shape(q), shape(k), shape(v)
        kp, vp = pool["k"], pool["v"]        # [NB, bs, h, hd] shared pool
        bs = kp.shape[1]
        mb = table.shape[1]
        blk = jnp.take_along_axis(table, pos // bs, axis=1)     # [b, t]
        off = pos % bs
        blk = jnp.where(write_ok, blk, 0)    # padding → trash block
        off = jnp.where(write_ok, off, 0)
        new_pool = dict(pool)
        if "k_scale" in pool:
            # quantized pool (nn/quantize.py): per-(position, head)
            # scales over head_dim — quantize on scatter here, dequant
            # on gather below, attention math unchanged
            kq, ksc = kv_quantize(k, kp.dtype)
            vq, vsc = kv_quantize(v, vp.dtype)
            kp = kp.at[blk, off].set(kq)
            vp = vp.at[blk, off].set(vq)
            new_pool["k_scale"] = pool["k_scale"].at[blk, off].set(ksc)
            new_pool["v_scale"] = pool["v_scale"].at[blk, off].set(vsc)
        else:
            kp = kp.at[blk, off].set(k.astype(kp.dtype))
            vp = vp.at[blk, off].set(v.astype(vp.dtype))
        new_pool["k"], new_pool["v"] = kp, vp
        kg = jnp.take(kp, table, axis=0).reshape(b, mb * bs, *kp.shape[2:])
        vg = jnp.take(vp, table, axis=0).reshape(b, mb * bs, *vp.shape[2:])
        if "k_scale" in pool:
            ksg = jnp.take(new_pool["k_scale"], table, axis=0).reshape(
                b, mb * bs, h_count)
            vsg = jnp.take(new_pool["v_scale"], table, axis=0).reshape(
                b, mb * bs, h_count)
            kg = kv_dequantize(kg, ksg, q.dtype)
            vg = kv_dequantize(vg, vsg, q.dtype)
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kg.astype(q.dtype)) * scale
        live = jnp.arange(mb * bs)[None, None, :] <= pos[:, :, None]
        s = jnp.where(live[:, None], s,
                      jnp.asarray(jnp.finfo(s.dtype).min, s.dtype))
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", w, vg.astype(q.dtype))
        x = self._slice_replicate(
            x + qmatmul(self._slice_replicate(o.reshape(b, t, d)),
                        params, "Wo"))
        h2 = _layer_norm(x, params["ln2_g"], params["ln2_b"])
        mlp, _ = self._ffn(params, h2.reshape(-1, d), {},
                           capacity_factor=float(max(1, c.num_experts)))
        return self._slice_replicate(x + mlp.reshape(b, t, d)), new_pool

    def decode_step(self, params, x_t, cache, pos, write_mask=None):
        """One-token forward [b, d] with cached keys/values; ``pos`` is
        the (traced) current position — a scalar (whole-batch position)
        or a [b] vector (per-row positions, the ragged-prompt serving
        path; the K/V write becomes a per-row one-hot scatter). Returns
        (y_t [b, d], new cache). Dense blocks match ``forward`` exactly
        at every prefix position (tested); MoE blocks route NO-DROP at
        decode time (capacity = batch) — the training-time capacity
        heuristic over b*t tokens has no stepwise equivalent, and
        dropping tokens at inference is never what serving wants.

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
        blocks. ``pos`` must be a [b] vector in paged mode."""
        c = self.conf
        b, d = x_t.shape
        h_count, hd = c.num_heads, c.n_out // c.num_heads
        h = _layer_norm(x_t, params["ln1_g"], params["ln1_b"])
        qkv = qmatmul(h, params, "Wqkv")
        q, k, v = jnp.split(qkv, 3, axis=-1)
        shape = lambda z: z.reshape(b, h_count, hd)
        q, k, v = shape(q), shape(k), shape(v)
        if "table" in cache:
            return self._decode_step_paged(params, x_t, cache, pos,
                                           q, k, v, write_mask)
        slots = jnp.arange(cache["k"].shape[1])
        if jnp.ndim(pos) == 0:
            ck = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k[:, None].astype(cache["k"].dtype), pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v[:, None].astype(cache["v"].dtype), pos, axis=1)
            # causal: only positions <= pos are live
            live = (slots <= pos)[None, :]
        else:
            sel = (slots[None, :] == pos[:, None])[:, :, None, None]
            ck = jnp.where(sel, k[:, None].astype(cache["k"].dtype),
                           cache["k"])
            cv = jnp.where(sel, v[:, None].astype(cache["v"].dtype),
                           cache["v"])
            live = slots[None, :] <= pos[:, None]  # [b, L] per-row causal
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
        s = jnp.einsum("bhd,bkhd->bhk", q, ck.astype(q.dtype)) * scale
        s = jnp.where(live[:, None, :], s,
                      jnp.asarray(jnp.finfo(s.dtype).min, s.dtype))
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhk,bkhd->bhd", w, cv.astype(q.dtype))
        x_t = self._slice_replicate(
            x_t + qmatmul(self._slice_replicate(o.reshape(b, d)),
                          params, "Wo"))

        h2 = _layer_norm(x_t, params["ln2_g"], params["ln2_b"])
        # no-drop capacity: capacity = ceil(cf*b/E) >= b when cf = E
        mlp, _ = self._ffn(params, h2, {},
                           capacity_factor=float(max(1, c.num_experts)))
        return self._slice_replicate(x_t + mlp), {"k": ck, "v": cv}

    def _decode_step_paged(self, params, x_t, cache, pos, q, k, v,
                           write_mask):
        """Gather/scatter attention over a block table (decode_step's
        paged-pool branch — q/k/v already projected): scatter this
        token's K/V into its row's (block, offset) pool slot, gather
        the row's blocks back as a contiguous [b, MB*bs] view, and run
        the same masked softmax attention as the dense branch. Gathered
        positions past ``pos`` (including every trash/garbage block the
        table pads with) are causally masked, so pool garbage is
        numerically inert exactly like the dense path's padded tail.

        A QUANTIZED pool (``"k_scale"``/``"v_scale"`` entries — the
        nn/kvpool.py int8/fp8 variant) quantizes the incoming token's
        K/V per head on the scatter and dequantizes the gathered view
        before the softmax; everything else — table discipline, trash
        redirect, causal mask — is identical, and the scale arrays ride
        the same (block, offset) addressing as the values."""
        c = self.conf
        b, d = x_t.shape
        kp, vp = cache["k"], cache["v"]      # [NB, bs, h, hd] shared pool
        table = cache["table"]               # [b, MB] int32 block ids
        bs = kp.shape[1]
        mb = table.shape[1]
        blk_of = pos // bs
        off = pos % bs
        blk = jnp.take_along_axis(table, blk_of[:, None], axis=1)[:, 0]
        if write_mask is not None:
            # masked rows write the trash block — never a live sequence
            blk = jnp.where(write_mask, blk, 0)
            off = jnp.where(write_mask, off, 0)
        new_cache = dict(cache)
        if "k_scale" in cache:
            kq, ksc = kv_quantize(k, kp.dtype)
            vq, vsc = kv_quantize(v, vp.dtype)
            kp = kp.at[blk, off].set(kq)
            vp = vp.at[blk, off].set(vq)
            new_cache["k_scale"] = cache["k_scale"].at[blk, off].set(ksc)
            new_cache["v_scale"] = cache["v_scale"].at[blk, off].set(vsc)
        else:
            kp = kp.at[blk, off].set(k.astype(kp.dtype))
            vp = vp.at[blk, off].set(v.astype(vp.dtype))
        new_cache["k"], new_cache["v"] = kp, vp
        # gather the row's cache back into causal order: [b, MB*bs, h, hd]
        kg = jnp.take(kp, table, axis=0).reshape(b, mb * bs, *kp.shape[2:])
        vg = jnp.take(vp, table, axis=0).reshape(b, mb * bs, *vp.shape[2:])
        if "k_scale" in cache:
            h_count = c.num_heads
            ksg = jnp.take(new_cache["k_scale"], table, axis=0).reshape(
                b, mb * bs, h_count)
            vsg = jnp.take(new_cache["v_scale"], table, axis=0).reshape(
                b, mb * bs, h_count)
            kg = kv_dequantize(kg, ksg, q.dtype)
            vg = kv_dequantize(vg, vsg, q.dtype)
        hd = c.n_out // c.num_heads
        scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
        s = jnp.einsum("bhd,bkhd->bhk", q, kg.astype(q.dtype)) * scale
        live = jnp.arange(mb * bs)[None, :] <= pos[:, None]
        s = jnp.where(live[:, None, :], s,
                      jnp.asarray(jnp.finfo(s.dtype).min, s.dtype))
        w = jax.nn.softmax(s, axis=-1)
        o = jnp.einsum("bhk,bkhd->bhd", w, vg.astype(q.dtype))
        x_t = self._slice_replicate(
            x_t + qmatmul(self._slice_replicate(o.reshape(b, d)),
                          params, "Wo"))

        h2 = _layer_norm(x_t, params["ln2_g"], params["ln2_b"])
        mlp, _ = self._ffn(params, h2, {},
                           capacity_factor=float(max(1, c.num_experts)))
        return self._slice_replicate(x_t + mlp), new_cache
