"""Mixture-of-experts layer impl (expert parallelism), and the routed-expert
feed-forward of the hybrid family's blocks (``routed_experts``).

No reference counterpart (SURVEY §2.6 lists expert parallelism as
absent from the reference); the routing math lives in ``ops/moe.py``.
Expert parallelism is a sharding, not a code path: put
``PartitionSpec("expert", ...)`` on the leading dim of W1/b1/W2/b2
(``parallel.tensor_parallel.moe_ep_specs``) and XLA lowers the
dispatch/combine einsums to the canonical all-to-all over the mesh —
the forward below never mentions devices.

The Switch load-balancing aux loss is activation-dependent, so it
can't flow through ``regularization_penalty(params)``; instead it
rides the layer-state seam: ``forward`` writes the weighted aux into
``state["__aux_loss__"]`` and the containers add every such entry to
the training objective (differentiably — state is produced inside the
traced step).
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from deeplearning4j_tpu.monitor import MOE_PATH_COUNTER, get_registry
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.layers.base import LayerImpl, register_impl
from deeplearning4j_tpu.nn.weights import init_weights
from deeplearning4j_tpu.ops.grouped_matmul import grouped_matmul, moe_path
from deeplearning4j_tpu.ops.moe import (combine, dispatch, moe_ffn,
                                        sort_by_expert, topk_routing)

AUX_LOSS_KEY = "__aux_loss__"
#: the layer-state key of the per-expert selection bias
EXPERT_BIAS_KEY = "expert_bias"
#: the ``checkpoint_name`` of the held experts' gate/up product
EXPERT_GATE_UP_PRODUCT = "expert_gate_up_product"


def init_moe_params(key, d: int, f: int, e: int, weight_init: str,
                    dist_mean: float, dist_std: float,
                    dist=None) -> Dict[str, jnp.ndarray]:
    """Router + expert FFN weights (shared by MoEImpl and the MoE
    variant of TransformerBlock)."""
    ks = jax.random.split(key, 3)
    mk = lambda k, shape, fi, fo: init_weights(
        k, shape, weight_init, fi, fo, dist_mean, dist_std, dist=dist)
    return {
        "Wg": mk(ks[0], (d, e), d, e),
        "W1": mk(ks[1], (e, d, f), d, f),
        "b1": jnp.zeros((e, f), jnp.float32),
        "W2": mk(ks[2], (e, f, d), f, d),
        "b2": jnp.zeros((e, d), jnp.float32),
    }


def run_moe_ffn(params, x2: jnp.ndarray, capacity_factor: float,
                aux_loss_weight: float, mask=None):
    """Flattened-token MoE forward + weighted aux packaged for the
    layer-state seam: returns (y2, {AUX_LOSS_KEY: weighted_aux})."""
    valid = mask.reshape(-1) if mask is not None else None
    y2, aux = moe_ffn(x2, params["Wg"], params["W1"], params["b1"],
                      params["W2"], params["b2"],
                      capacity_factor=capacity_factor, valid=valid)
    return y2, {AUX_LOSS_KEY: aux_loss_weight * aux.astype(jnp.float32)}


@register_impl(L.MoELayer)
class MoEImpl(LayerImpl):
    batch_statistics = True  # load-balancing aux loss + expert capacity
    # are batch-level quantities: padded rows would skew both, so
    # shape-bucketing tail padding is gated off for MoE stacks

    def init_params(self, key) -> Dict[str, jnp.ndarray]:
        c = self.conf
        if c.n_out != c.n_in:
            raise ValueError("MoELayer needs n_in == n_out (FFN block)")
        return init_moe_params(key, c.n_in, c.ffn_mult * c.n_in,
                               c.num_experts, self.weight_init,
                               c.dist_mean, c.dist_std, dist=c.dist)

    def init_state(self):
        return {AUX_LOSS_KEY: jnp.zeros((), jnp.float32)}

    def forward(self, params, x, state, train, rng=None, mask=None):
        c = self.conf
        x = self.maybe_dropout_input(x, train, rng)
        shape = x.shape
        if x.ndim == 3:
            x2 = x.reshape(-1, shape[-1])
        elif x.ndim == 2:
            x2 = x
        else:
            raise ValueError(f"MoELayer needs [b, d] or [b, t, d], got {shape}")
        # masked timesteps must not occupy capacity or skew the aux
        routing_mask = mask if (mask is not None and x.ndim == 3) else None
        y2, new_state = run_moe_ffn(params, x2, c.capacity_factor,
                                    c.aux_loss_weight, mask=routing_mask)
        y = y2.reshape(shape[:-1] + (c.n_out,))
        if c.residual:
            y = y + x
        if mask is not None and y.ndim == 3:
            y = y * mask[:, :, None].astype(y.dtype)
        return y, new_state


# ------------------------------------------- routed experts, experts held

def held_experts(conf):
    """``(first, count)`` of the experts a block's configuration holds."""
    first, count = conf.experts_held
    count = count or conf.num_experts
    if not 0 <= first < first + count <= conf.num_experts:
        raise ValueError(f"experts_held {conf.experts_held} is not a range "
                         f"of the {conf.num_experts} experts")
    return first, count


def init_routed_params(key, conf, matrix) -> Dict[str, jnp.ndarray]:
    """The router over all ``num_experts`` (float32, [d, E]) and the held
    experts' fused gate/up [count, d, 2 f] and down [count, f, d] matrices;
    ``matrix(key, shape)`` initialises a [fan_in, fan_out] matrix."""
    d, f = conf.n_out, conf.expert_hidden
    _, count = held_experts(conf)
    k_router, *ks = jax.random.split(key, 1 + 2 * count)
    return {
        "W_router": matrix(k_router, (d, conf.num_experts)),
        "experts_gate_up": jnp.stack([matrix(k, (d, 2 * f))
                                      for k in ks[:count]]),
        "experts_down": jnp.stack([matrix(k, (f, d)) for k in ks[count:]]),
    }


def routed_experts(params, h, state, conf):
    """h [b, t, d] -> [b, t, d]: the held experts' part of the routed
    experts' result (``ops/moe.py``: top-k on all the router's experts, the
    assignments to held experts sorted into groups, the grouped products of
    ``ops/grouped_matmul.py``, the weighted rows summed back). The router's
    product is float32 at the highest precision."""
    b, t, d = h.shape
    first, count = held_experts(conf)
    k = conf.experts_per_token
    x = h.reshape(b * t, d)
    with jax.named_scope("router"):
        logits = jnp.matmul(x.astype(jnp.float32),
                            params["W_router"].astype(jnp.float32),
                            precision=jax.lax.Precision.HIGHEST)
        weights, experts = topk_routing(
            logits, k, state.get(EXPERT_BIAS_KEY) if conf.expert_bias else None,
            conf.norm_topk_prob, conf.routed_scaling_factor)
    with jax.named_scope("moe_permute"):
        order, sizes, held, position = sort_by_expert(experts, first, count)
        rows = dispatch(x, order, position, held, k)
    path = moe_path(b * t * k, d, conf.expert_hidden)
    get_registry().counter(
        MOE_PATH_COUNTER, "routed expert layers traced, by the way their "
        "shapes chose the grouped products", path=path).inc()
    with jax.named_scope("expert_gate_up"):
        gate, up = jnp.split(checkpoint_name(
            grouped_matmul(rows, params["experts_gate_up"], sizes, path),
            EXPERT_GATE_UP_PRODUCT), 2, axis=-1)
        rows = jax.nn.silu(gate) * up
    with jax.named_scope("expert_down"):
        rows = grouped_matmul(rows, params["experts_down"], sizes, path)
    with jax.named_scope("moe_combine"):
        out = combine(rows, weights, order, position, held, k)
    return out.reshape(b, t, d)
