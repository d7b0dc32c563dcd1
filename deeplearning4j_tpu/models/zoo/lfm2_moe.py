"""An LFM2 mixture-of-experts language model for the zoo, built from the keys
of the family's published ``config.json`` (``model_type`` ``lfm2_moe``): a
token embedding with no scale and no positions; the layers that
``layer_types`` lists, each a gated short-convolution mixer (``conv``) or a
grouped-query attention mixer with an RMSNorm on each head of q and k and
rotary positions (``full_attention``), then a feed-forward that is the gated
MLP in the leading ``num_dense_layers`` layers and routed experts in the
others; a final RMSNorm and a head tied to the embedding.

The routed experts (``nn/layers/moe.py``): a router over all
``num_routed_experts`` (the published ``num_experts``), sigmoid scores, the
``num_experts_per_tok`` largest scores plus the expert bias (a selection-only
bias in the layer's state, ``use_expert_bias``), weights normalised over the
picks (``norm_topk_prob``) times ``routed_scaling_factor``. A layer holds the
``num_experts`` experts from ``experts_held_first`` on (one chip's share of
a layer divided over chips by experts) and computes their part of the result.
``published_layers`` gives each layer's index in the published model: layers
below ``num_dense_layers`` are the dense ones.

Training only: the blocks have no cache (``nn/layers/hybrid.py``). Block
bodies are recomputed in the backward pass unless ``recompute_blocks=False``;
``kept_values`` names what a recomputed body keeps beside its input, each
block taking the names that its mixer and feed-forward make.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    GroupedQueryBlock,
    RMSNormLayer,
    RnnOutputLayer,
    SequenceEmbeddingLayer,
    ShortConvBlock,
)
from deeplearning4j_tpu.nn.layers.hybrid import GATE_UP_PRODUCT
from deeplearning4j_tpu.nn.layers.moe import EXPERT_GATE_UP_PRODUCT
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork
from deeplearning4j_tpu.ops.flash_attention import FLASH_RESIDUAL_NAMES

KINDS = ("conv", "full_attention")


def lfm2_moe(config: Dict[str, Any], learning_rate: float = 1e-4,
             compute_dtype: str = "bfloat16", seed: int = 0,
             recompute_blocks: bool = True,
             kept_values: Optional[Sequence[str]] = None
             ) -> MultiLayerNetwork:
    """Decoder-only LM over int token ids [b, t] with sparse next-token
    labels [b, t], from the published keys: ``hidden_size``, ``vocab_size``,
    ``layer_types`` (+ ``published_layers``), ``num_dense_layers``,
    ``intermediate_size``, ``moe_intermediate_size``, ``num_experts`` (held
    here; ``num_routed_experts`` scored, ``experts_held_first``),
    ``num_experts_per_tok``, ``norm_topk_prob``, ``routed_scaling_factor``,
    ``use_expert_bias``, ``num_attention_heads``, ``num_key_value_heads``,
    ``conv_L_cache``, ``norm_eps``, ``rope_parameters`` (or ``rope_theta``)
    and, where given, ``initializer_range`` (0.02)."""
    c = config
    if c.get("conv_bias"):
        raise ValueError("only conv_bias false is built")
    if not c.get("tie_word_embeddings", True):
        raise ValueError("only the tied head is built")
    kinds = list(c["layer_types"])
    published = list(c.get("published_layers") or range(len(kinds)))
    unknown = sorted(set(kinds) - set(KINDS))
    if unknown or len(published) != len(kinds):
        raise ValueError(f"layer_types holds {unknown} (of {KINDS}), or "
                         f"published_layers is not as long")
    d = c["hidden_size"]
    std = float(c.get("initializer_range", 0.02))
    theta = (c.get("rope_parameters") or {}).get("rope_theta",
                                                 c.get("rope_theta"))
    shared = dict(n_in=d, n_out=d, rms_eps=c["norm_eps"],
                  weight_init="distribution", dist_std=std)
    dense = dict(ffn_hidden=c["intermediate_size"])
    routed = dict(
        num_experts=int(c.get("num_routed_experts", c["num_experts"])),
        experts_per_token=int(c["num_experts_per_tok"]),
        experts_held=(int(c.get("experts_held_first", 0)),
                      int(c["num_experts"])),
        expert_hidden=c["moe_intermediate_size"],
        norm_topk_prob=bool(c["norm_topk_prob"]),
        routed_scaling_factor=float(c["routed_scaling_factor"]),
        expert_bias=bool(c.get("use_expert_bias", False)))

    b = (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(learning_rate).updater("adam")
         .activation("identity").weight_init("distribution")
         .compute_dtype(compute_dtype).recompute_blocks(recompute_blocks)
         .list()
         .layer(SequenceEmbeddingLayer(n_in=c["vocab_size"], n_out=d,
                                       positions=False, dist_std=std)))
    for kind, index in zip(kinds, published):
        is_dense = index < c["num_dense_layers"]
        makes = {GATE_UP_PRODUCT if is_dense else EXPERT_GATE_UP_PRODUCT}
        if kind == "full_attention":
            makes |= set(FLASH_RESIDUAL_NAMES)
        keep = None if kept_values is None else tuple(
            v for v in kept_values if v in makes)
        ffn = dense if is_dense else routed
        if kind == "conv":
            b = b.layer(ShortConvBlock(conv_kernel=c["conv_L_cache"],
                                       kept_values=keep, **ffn, **shared))
        else:
            b = b.layer(GroupedQueryBlock(
                num_heads=c["num_attention_heads"],
                num_kv_heads=c["num_key_value_heads"], rope_theta=theta,
                qk_norm=True, kept_values=keep, **ffn, **shared))
    conf = (b.layer(RMSNormLayer(n_in=d, n_out=d, eps=c["norm_eps"]))
            .layer(RnnOutputLayer(
                n_in=d, n_out=c["vocab_size"], activation="softmax",
                loss_function="mcxent", has_bias=False, tied_to="layer0"))
            .build())
    return MultiLayerNetwork(conf)
