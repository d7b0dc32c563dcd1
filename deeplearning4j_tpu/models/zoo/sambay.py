"""A decoder-hybrid-decoder language model (SambaY, arXiv:2507.06607) for the
zoo, built from the keys of the family's published ``config.json``
(``model_type`` ``phi4flash``): a token embedding with no scale and no
positions; a self-decoder of Mamba-1 layers alternating with differential
attention layers that see a window of ``sliding_window`` keys; at the
middle a Mamba-1 layer that hands forward its scan output (the ``memory``)
and a full causal differential attention layer that hands forward its keys
and values (``kv``); a cross-decoder of gated memory units that read the
memory in place of a scan of their own, alternating with differential
cross-attention layers that have a query projection only and read those keys
and values; a final LayerNorm and a head tied to the embedding.

The layer pattern follows the published rule from ``num_hidden_layers`` =
``n`` (a multiple of 4): layer ``i`` even is a Mamba-like layer, odd an
attention-like one; layers below ``n / 2`` are the self-decoder, layer
``n / 2`` gives the memory, ``n / 2 + 1`` the keys and values, the rest read
them. ``layer_types`` (with ``published_layers``, each layer's index in the
published model, which sets its ``lambda_init``) overrides the derived
pattern: a cut of the model names the layers it keeps.

Training only: the blocks have no cache (``nn/layers/hybrid.py``). Block
bodies are recomputed in the backward pass unless ``recompute_blocks=False``;
``kept_values`` names what a recomputed body keeps beside its input, each
block taking the names its class makes.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    DiffAttentionBlock,
    GMUBlock,
    LayerNormLayer,
    Mamba1Block,
    RnnOutputLayer,
    SequenceEmbeddingLayer,
)
from deeplearning4j_tpu.nn.layers.hybrid import (DiffAttentionBlockImpl,
                                                 GMUBlockImpl, Mamba1BlockImpl)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

KINDS = ("mamba", "sliding_attention", "full_attention", "gmu",
         "cross_attention")
_IMPLS = {"mamba": Mamba1BlockImpl, "gmu": GMUBlockImpl,
          "sliding_attention": DiffAttentionBlockImpl,
          "full_attention": DiffAttentionBlockImpl,
          "cross_attention": DiffAttentionBlockImpl}


def layer_pattern(num_hidden_layers: int) -> List[str]:
    """The published rule: the kind of every layer of a model of ``n``
    layers."""
    n = num_hidden_layers
    if n % 4:
        raise ValueError(f"num_hidden_layers must be a multiple of 4: the "
                         f"two decoders are halves of pairs; got {n}")
    kinds = []
    for i in range(n):
        if i % 2 == 0:
            kinds.append("mamba" if i <= n // 2 else "gmu")
        elif i < n // 2:
            kinds.append("sliding_attention")
        else:
            kinds.append("full_attention" if i == n // 2 + 1
                         else "cross_attention")
    return kinds


def sambay_lm(config: Dict[str, Any], learning_rate: float = 1e-4,
              compute_dtype: str = "bfloat16", seed: int = 0,
              recompute_blocks: bool = True,
              kept_values: Optional[Sequence[str]] = None
              ) -> MultiLayerNetwork:
    """Decoder-only LM over int token ids [b, t] with sparse next-token
    labels [b, t], from the published keys: ``hidden_size``, ``vocab_size``,
    ``num_hidden_layers`` (or ``layer_types`` + ``published_layers``),
    ``num_attention_heads``, ``num_key_value_heads``, ``intermediate_size``,
    ``sliding_window``, ``layer_norm_eps``, ``tie_word_embeddings``; and,
    where given, ``mamba_d_state`` (16), ``mamba_d_conv`` (4),
    ``mamba_expand`` (2), ``mamba_dt_rank`` (hidden_size / 16, rounded up)
    and ``initializer_range`` (0.02)."""
    c = config
    if not c.get("tie_word_embeddings", True):
        raise ValueError("only the tied head is built")
    if c.get("rope_theta") is not None or c.get("rope_scaling") is not None:
        raise ValueError("the family has no positional encoding: rotary "
                         "keys are not built")
    kinds = list(c.get("layer_types") or layer_pattern(c["num_hidden_layers"]))
    published = list(c.get("published_layers") or range(len(kinds)))
    unknown = sorted(set(kinds) - set(KINDS))
    if unknown or len(published) != len(kinds):
        raise ValueError(f"layer_types holds {unknown} (of {KINDS}), or "
                         f"published_layers is not as long")
    d = c["hidden_size"]
    di = int(c.get("mamba_expand", 2)) * d
    std = float(c.get("initializer_range", 0.02))
    shared = dict(n_in=d, n_out=d, ffn_hidden=c["intermediate_size"],
                  ln_eps=c["layer_norm_eps"], weight_init="distribution",
                  dist_std=std)
    attention = dict(num_heads=c["num_attention_heads"],
                     num_kv_heads=c["num_key_value_heads"])

    layers, memory, kv = [], None, None  # the newest provider of each value
    for i, (kind, index) in enumerate(zip(kinds, published)):
        name = f"layer{i + 1}"  # layer0 is the embedding
        later = kinds[i + 1:]
        keep = None if kept_values is None else tuple(
            v for v in kept_values if v in _IMPLS[kind].KEEPABLE)
        if kind == "mamba":
            gives = "gmu" in later and "mamba" not in later
            layers.append(Mamba1Block(
                d_inner=di, d_state=int(c.get("mamba_d_state", 16)),
                d_conv=int(c.get("mamba_d_conv", 4)),
                dt_rank=int(c.get("mamba_dt_rank", math.ceil(d / 16))),
                provides=("memory",) if gives else (), kept_values=keep,
                **shared))
            memory = name if gives else memory
        elif kind == "gmu":
            if memory is None:
                raise ValueError(f"layer {i} is a gated memory unit with no "
                                 "Mamba layer before it to give its memory")
            layers.append(GMUBlock(d_inner=di, reads=(f"{memory}.memory",),
                                   kept_values=keep, **shared))
        elif kind == "cross_attention":
            if kv is None:
                raise ValueError(f"layer {i} is a cross-attention layer with "
                                 "no full attention layer before it to give "
                                 "its keys and values")
            layers.append(DiffAttentionBlock(
                cross=True, layer_index=index, reads=(f"{kv}.kv",),
                kept_values=keep, **attention, **shared))
        else:
            gives = kind == "full_attention" and "cross_attention" in later
            layers.append(DiffAttentionBlock(
                window=(c["sliding_window"] if kind == "sliding_attention"
                        else None),
                layer_index=index, provides=("kv",) if gives else (),
                kept_values=keep, **attention, **shared))
            kv = name if gives else kv

    b = (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(learning_rate).updater("adam")
         .activation("identity").weight_init("distribution")
         .compute_dtype(compute_dtype).recompute_blocks(recompute_blocks)
         .list()
         .layer(SequenceEmbeddingLayer(n_in=c["vocab_size"], n_out=d,
                                       positions=False, dist_std=std)))
    for layer in layers:
        b = b.layer(layer)
    conf = (b.layer(LayerNormLayer(n_in=d, n_out=d, eps=c["layer_norm_eps"]))
            .layer(RnnOutputLayer(
                n_in=d, n_out=c["vocab_size"], activation="softmax",
                loss_function="mcxent", has_bias=False, tied_to="layer0"))
            .build())
    return MultiLayerNetwork(conf)
