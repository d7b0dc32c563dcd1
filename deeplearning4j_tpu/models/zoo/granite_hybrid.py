"""A hybrid state-space language model for the zoo, built from the keys of
the family's published ``config.json`` (``model_type`` ``granitemoehybrid``
with no routed experts): a scaled token embedding with no positions, the
layers that ``layer_types`` lists, each a Mamba-2 or a grouped-query
attention mixer followed by the shared gated MLP, a final RMSNorm, and a head
tied to the embedding with its logits divided by ``logits_scaling``.

Training only: the blocks have no cache (``nn/layers/hybrid.py``). Block
bodies are recomputed in the backward pass unless ``recompute_blocks=False``:
a Mamba-2 block keeps about 150 KB a token otherwise. Recomputed, a block
keeps its input (``2 * d`` bytes a token in bfloat16: 4 KB at d 2048) and
the gated MLP's wide product ``h @ W_gate_up`` (``4 * f`` bytes: 32 KB at f
8192), the dearest value of the body to make again; the attention block also
keeps its flash kernel's output and lse (``2 * d`` bytes and 4 bytes a head).
Everything else runs again: the Mamba-2 in-projection, the convolution, the
scan, the gate and the norms.
"""

from __future__ import annotations

from typing import Any, Dict

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    GroupedQueryBlock,
    Mamba2Block,
    RMSNormLayer,
    RnnOutputLayer,
    SequenceEmbeddingLayer,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def granite_hybrid(config: Dict[str, Any], learning_rate: float = 1e-4,
                   compute_dtype: str = "bfloat16", seed: int = 0,
                   recompute_blocks: bool = True) -> MultiLayerNetwork:
    """Decoder-only LM over int token ids [b, t] with sparse next-token
    labels [b, t], from the published keys: ``hidden_size``, ``vocab_size``,
    ``layer_types``, the ``mamba_*`` keys, ``num_attention_heads``,
    ``num_key_value_heads``, ``shared_intermediate_size``, ``rms_norm_eps``,
    the four multipliers and ``tie_word_embeddings``."""
    c = config
    if c.get("num_local_experts", 0):
        raise ValueError("routed experts are not built: num_local_experts "
                         "must be 0 (every layer takes the shared MLP)")
    if c.get("position_embedding_type", "nope") != "nope":
        raise ValueError("only position_embedding_type 'nope' is built")
    if not c.get("tie_word_embeddings", True):
        raise ValueError("only the tied head is built")
    d = c["hidden_size"]
    if c["mamba_expand"] * d != c["mamba_n_heads"] * c["mamba_d_head"]:
        raise ValueError("mamba_expand * hidden_size must equal "
                         "mamba_n_heads * mamba_d_head")
    std = float(c.get("initializer_range", 0.02))
    shared = dict(n_in=d, n_out=d, ffn_hidden=c["shared_intermediate_size"],
                  rms_eps=c["rms_norm_eps"],
                  residual_multiplier=c["residual_multiplier"],
                  weight_init="distribution", dist_std=std)
    kinds = {
        "mamba": lambda: Mamba2Block(
            n_heads=c["mamba_n_heads"], d_head=c["mamba_d_head"],
            d_state=c["mamba_d_state"], d_conv=c["mamba_d_conv"],
            n_groups=c["mamba_n_groups"], chunk_size=c["mamba_chunk_size"],
            **shared),
        "attention": lambda: GroupedQueryBlock(
            num_heads=c["num_attention_heads"],
            num_kv_heads=c["num_key_value_heads"],
            attention_multiplier=c["attention_multiplier"], **shared),
    }
    b = (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(learning_rate).updater("adam")
         .activation("identity").weight_init("distribution")
         .compute_dtype(compute_dtype).recompute_blocks(recompute_blocks)
         .list()
         .layer(SequenceEmbeddingLayer(
             n_in=c["vocab_size"], n_out=d, positions=False,
             output_multiplier=float(c["embedding_multiplier"]),
             dist_std=std)))
    for kind in c["layer_types"]:
        b = b.layer(kinds[kind]())
    conf = (b.layer(RMSNormLayer(n_in=d, n_out=d, eps=c["rms_norm_eps"]))
            .layer(RnnOutputLayer(
                n_in=d, n_out=c["vocab_size"], activation="softmax",
                loss_function="mcxent", has_bias=False, tied_to="layer0",
                logits_scale=1.0 / float(c["logits_scaling"])))
            .build())
    return MultiLayerNetwork(conf)
