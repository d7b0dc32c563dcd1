"""Multi-head self-attention layer impl.

No reference counterpart (SURVEY.md §5: the reference's only
long-context tool is truncated BPTT); this makes the round-1 orphan
``ops/attention.py`` capability user-reachable as a layer (VERDICT r1
next-round #8) and is the on-ramp to sequence parallelism: when a
``parallel.mesh.sequence_mesh`` context is active the forward switches
to the ring-attention kernel (``parallel/ring_attention.py``), sharding
time over the mesh's ``seq`` axis with K/V blocks rotating over ICI.
"""

from __future__ import annotations

import contextlib
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.base import LayerImpl, register_impl
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.attention import scaled_dot_product_attention
from deeplearning4j_tpu.ops.flash_attention import (flash_attention,
                                                    flash_attention_qkv)
from deeplearning4j_tpu.parallel.mesh import (current_sequence_mesh,
                                              device_collective)
from deeplearning4j_tpu.parallel.ring_attention import ring_attention

_FORCE_XLA: list = []


@contextlib.contextmanager
def xla_attention():
    """Force the plain XLA attention formulation while tracing under
    this context. Needed where a Pallas call can't apply — notably
    inside the pipeline-parallel ``shard_map`` (pallas_call outputs
    carry no varying-mesh-axes info, and pp stages hold short
    per-microbatch activations where flash's memory advantage is moot
    anyway)."""
    _FORCE_XLA.append(True)
    try:
        yield
    finally:
        _FORCE_XLA.pop()


def _flash_per_device(q, k, v, causal: bool, mesh, window=None):
    """The flash kernel mapped over a placement mesh: attention is
    independent per (batch row, head), so every device runs the kernel
    on its own rows and heads — batch divided over the mesh's
    ``data``/``fsdp`` axes, heads over ``tp``, whatever else the mesh
    has replicated. The partitioner cannot do this itself: a Pallas
    kernel is opaque to it, and Mosaic refuses to lower one under a
    multi-device jit outside a per-device region."""
    b, _, h, _ = q.shape
    batch = tuple(a for a in ("data", "fsdp") if mesh.shape.get(a, 1) > 1)
    if b % int(np.prod([mesh.shape[a] for a in batch], dtype=int)):
        batch = ()
    heads = next((a for a in ("tp", "model")
                  if mesh.shape.get(a, 1) > 1 and h % mesh.shape[a] == 0),
                 None)
    spec = P(batch or None, None, heads, None)
    local = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                            window=window)
    # the kernel's outputs carry no varying-axes type: skip that check
    return device_collective(local, mesh, in_specs=(spec, spec, spec),
                             out_specs=spec, check_vma=False)(q, k, v)


def dispatch_attention(q, k, v, causal: bool, mask=None, mesh=None,
                       window=None):
    """Shared parallelism dispatch for every attention-bearing layer:
    ring attention under an active sequence mesh (DP×SP when the mesh
    also has a 'data' axis), otherwise the flash Pallas kernel — per
    device when the net is placed over ``mesh`` (the impl's ``_mesh``).
    Key-validity masks fall back to the XLA path inside the kernel
    wrapper (which the partitioner handles, so they skip the per-device
    map; ring blocks assume dense time, so masked inputs also stay off
    the ring). An active ``xla_attention()`` context overrides all.
    ``window`` (a query sees itself and the ``window - 1`` keys before it)
    goes to the flash kernels and to the XLA form; the ring has none."""
    if _FORCE_XLA:
        return scaled_dot_product_attention(q, k, v, causal=causal, mask=mask,
                                            window=window)
    seq = current_sequence_mesh()
    if seq is not None and mask is None:
        if window is not None:
            raise NotImplementedError("ring attention has no window")
        mesh, axis = seq
        batch_axis = "data" if "data" in mesh.shape else None
        return ring_attention(q, k, v, mesh, axis=axis, causal=causal,
                              batch_axis=batch_axis)
    if mesh is not None and mesh.size > 1 and mask is None:
        return _flash_per_device(q, k, v, causal, mesh, window)
    return flash_attention(q, k, v, causal=causal, mask=mask, window=window)


def dispatch_qkv_attention(qkv, heads: int, causal: bool, mask=None,
                           mesh=None):
    """``dispatch_attention`` for a block that holds q, k and v as one
    fused projection [b, t, 3 * heads * d] and wants [b, t, heads * d] for
    its output projection. The plain one-device call hands the fused array
    to the flash kernels as it lies (no split, no head fold where the
    shapes allow: ``flash_attention_qkv``); a key mask, a sequence mesh, a
    placement mesh or ``xla_attention()`` take the three thirds through
    ``dispatch_attention``."""
    b, t, features = qkv.shape
    if not _FORCE_XLA and mask is None and current_sequence_mesh() is None \
            and (mesh is None or mesh.size == 1):
        return flash_attention_qkv(qkv, heads, causal=causal)
    q, k, v = (z.reshape(b, t, heads, -1)
               for z in jnp.split(qkv, 3, axis=-1))
    return dispatch_attention(q, k, v, causal=causal, mask=mask,
                              mesh=mesh).reshape(b, t, features // 3)


@register_impl(L.AttentionLayer)
class AttentionImpl(LayerImpl):
    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        c = self.conf
        if c.n_out % c.num_heads != 0:
            raise ValueError(f"n_out {c.n_out} not divisible by num_heads {c.num_heads}")
        kq, kk, kv, ko = jax.random.split(key, 4)
        mk = lambda k, shape: init_weights(k, shape, self.weight_init,
                                           shape[0], shape[1],
                                           c.dist_mean, c.dist_std,
                                           dist=c.dist)
        return {
            "Wq": mk(kq, (c.n_in, c.n_out)),
            "Wk": mk(kk, (c.n_in, c.n_out)),
            "Wv": mk(kv, (c.n_in, c.n_out)),
            "Wo": mk(ko, (c.n_out, c.n_out)),
            "bo": jnp.zeros((c.n_out,), jnp.float32),
        }

    def forward(self, params, x, state, train, rng=None, mask=None):
        c = self.conf
        if x.ndim != 3:
            raise ValueError(
                f"AttentionLayer needs [batch, time, features] input, got "
                f"shape {x.shape}. Stepwise rnn_time_step inference is not "
                f"supported for attention (no KV cache) — feed full windows.")
        x = self.maybe_dropout_input(x, train, rng)
        b, t, _ = x.shape
        h = c.num_heads
        d = c.n_out // h
        split = lambda z: z.reshape(b, t, h, d)
        q = split(x @ params["Wq"].astype(x.dtype))
        k = split(x @ params["Wk"].astype(x.dtype))
        v = split(x @ params["Wv"].astype(x.dtype))
        o = dispatch_attention(q, k, v, causal=c.causal, mask=mask,
                               mesh=self._mesh)
        out = o.reshape(b, t, c.n_out) @ params["Wo"].astype(x.dtype) \
            + params["bo"].astype(x.dtype)
        if c.residual:
            if c.n_in != c.n_out:
                raise ValueError("residual attention needs n_in == n_out")
            out = out + x
        if mask is not None:
            out = out * mask[:, :, None].astype(out.dtype)
        return out, state
