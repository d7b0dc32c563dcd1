"""A looped language model for the zoo, built from the keys of the family's
published ``config.json`` (``model_type`` ``ouro``; "Scaling Latent Reasoning
via Looped Language Models", arXiv:2510.25741): a token embedding, a stack of
``n_layer`` attention blocks and a final RMSNorm that run ``total_ut_steps``
times on the same weights (``MultiLayerConfiguration.repeat_span``: the normed
output of a pass is the next pass's input), and an untied head that scores
every pass's output, with a learned gate that gives each token a distribution
over the pass at which to exit (``ExitGateOutputLayer``).

A block is the hybrid family's grouped-query block with rotary positions over
the whole head and a norm on each branch's output (four norms a block, no
bias anywhere). Training only: the blocks have no cache, and a cache for a
repeated span holds ``total_ut_steps * n_layer`` entries a token (ROADMAP).

Block bodies are recomputed in the backward pass unless
``recompute_blocks=False``, and every block runs ``total_ut_steps`` times a
step: what a block keeps, it keeps once an application. ``kept_values`` names
it for every block; ``None`` leaves the block's own choice (the gated MLP's
wide product and the flash kernel's output and lse).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Sequence

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    ExitGateOutputLayer,
    GroupedQueryBlock,
    RMSNormLayer,
    SequenceEmbeddingLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def looped_lm(config: Dict[str, Any], learning_rate: float = 1e-4,
              compute_dtype: str = "bfloat16", seed: int = 0,
              recompute_blocks: bool = True, entropy_weight: float = 0.05,
              kept_values: Optional[Sequence[str]] = None) -> MultiLayerNetwork:
    """Decoder-only LM over int token ids [b, t] with sparse next-token
    labels [b, t], from the published keys: ``hidden_size``, ``vocab_size``,
    ``n_layer`` (the blocks of the stack; ``num_hidden_layers`` where it is
    not given), ``total_ut_steps``, ``num_attention_heads``,
    ``num_key_value_heads``, ``head_dim``, ``intermediate_size``,
    ``rms_norm_eps``, ``rope_theta``."""
    c = config
    d, heads = c["hidden_size"], c["num_attention_heads"]
    n_layer = c.get("n_layer", c["num_hidden_layers"])
    passes = c["total_ut_steps"]
    if passes < 1:
        raise ValueError(f"total_ut_steps must be at least 1, got {passes}")
    if c.get("use_sliding_window") or c.get("sliding_window"):
        raise ValueError("sliding windows are not built: every layer "
                         "attends to the whole prefix")
    if any(kind != "full_attention" for kind in c.get("layer_types", ())):
        raise ValueError("only full_attention layers are built")
    if c.get("rope_scaling"):
        raise ValueError("rope_scaling is not built: plain rotary positions")
    if c.get("tie_word_embeddings"):
        raise ValueError("only the untied head is built")
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError("only the silu gated MLP is built")
    if c.get("head_dim", d // heads) * heads != d:
        raise ValueError("head_dim * num_attention_heads must equal "
                         "hidden_size")
    std = float(c.get("initializer_range", 0.02))
    b = (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(learning_rate).updater("adam")
         .activation("identity").weight_init("distribution")
         .compute_dtype(compute_dtype).recompute_blocks(recompute_blocks)
         .list()
         .layer(SequenceEmbeddingLayer(n_in=c["vocab_size"], n_out=d,
                                       positions=False, dist_std=std)))
    kept = None if kept_values is None else tuple(kept_values)
    for _ in range(n_layer):
        b = b.layer(GroupedQueryBlock(
            n_in=d, n_out=d, ffn_hidden=c["intermediate_size"],
            rms_eps=c["rms_norm_eps"], num_heads=heads,
            num_kv_heads=c["num_key_value_heads"],
            rope_theta=float(c["rope_theta"]), branch_norms=True,
            kept_values=kept,
            weight_init="distribution", dist_std=std))
    conf = (b.layer(RMSNormLayer(n_in=d, n_out=d, eps=c["rms_norm_eps"]))
            .layer(ExitGateOutputLayer(
                n_in=d, n_out=c["vocab_size"], activation="softmax",
                loss_function="mcxent", has_bias=False,
                entropy_weight=entropy_weight, weight_init="distribution",
                dist_std=std))
            .repeat_span(1, n_layer + 2, passes)
            .build())
    return MultiLayerNetwork(conf)
