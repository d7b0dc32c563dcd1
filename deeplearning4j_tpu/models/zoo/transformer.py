"""GPT-style causal language model for the zoo.

No reference counterpart (the reference's sequence flagship is the
GravesLSTM char-RNN, ``LSTMHelpers.java:54``); this is the modern
long-context flagship built from the SURVEY §7.7 extension layers:
token+position embedding → N pre-LN transformer blocks (flash Pallas
attention single-chip, ring attention under a seq mesh) → tied-free
softmax LM head. One config serves single-chip, DP, and DP×SP runs.
"""

from __future__ import annotations

import numpy as np

from deeplearning4j_tpu.nn.conf import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.layers import (
    RnnOutputLayer,
    SequenceEmbeddingLayer,
    TransformerBlock,
)
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork


def gpt(vocab_size: int = 50257, d_model: int = 512, n_layers: int = 8,
        num_heads: int = 8, max_len: int = 1024, ffn_mult: int = 4,
        dropout: float = 0.0, learning_rate: float = 3e-4,
        compute_dtype: str = "bfloat16", num_experts: int = 0,
        capacity_factor: float = 1.25, aux_loss_weight: float = 0.01,
        seed: int = 0) -> MultiLayerNetwork:
    """Decoder-only LM over int token ids [b, t]; labels are SPARSE
    next-token ids [b, t] (ops/losses.py gathers target log-probs — no
    [b, t, vocab] one-hot; negative ids are ignored). One-hot labels
    also work. ``num_experts > 0`` swaps the dense MLPs for
    Mixtral-style top-1 routed experts (capacity_factor/aux_loss_weight
    tune the routing)."""
    b = (NeuralNetConfiguration.builder()
         .seed(seed).learning_rate(learning_rate).updater("adam")
         .activation("identity").weight_init("xavier")
         .compute_dtype(compute_dtype)
         .list()
         .layer(SequenceEmbeddingLayer(n_in=vocab_size, n_out=d_model,
                                       max_len=max_len)))
    for _ in range(n_layers):
        b = b.layer(TransformerBlock(n_in=d_model, n_out=d_model,
                                     num_heads=num_heads, ffn_mult=ffn_mult,
                                     causal=True, dropout=dropout,
                                     num_experts=num_experts,
                                     capacity_factor=capacity_factor,
                                     aux_loss_weight=aux_loss_weight))
    conf = (b.layer(RnnOutputLayer(n_in=d_model, n_out=vocab_size,
                                   activation="softmax",
                                   loss_function="mcxent"))
            .build())
    return MultiLayerNetwork(conf)


def generate(net: MultiLayerNetwork, prompt_ids: np.ndarray,
             max_new_tokens: int, temperature: float = 0.0, *,
             top_k: int = 0, top_p: float = 0.0,
             eos_token: int = None, seed: int = 0) -> np.ndarray:
    """Autoregressive decoding with per-block KV caches — now a thin
    facade over the fused generation engine (``nn/generate.py``):
    bucketed batched prefill writes every block's cache in ONE
    dispatch, then ALL of ``max_new_tokens`` runs as one ``lax.scan``
    dispatch with on-device greedy/temperature/top-k/top-p sampling
    (and EOS early-exit when ``eos_token`` is set). The original
    fed the prompt through the single-token step inside the scan —
    O(t0) wasted steps the prefill now does as one batched forward.

    ``prompt_ids``: [b, t0] int tokens; returns [b, t0 + max_new_tokens].
    """
    return net.generate(prompt_ids, max_new_tokens,
                        temperature=temperature, top_k=top_k, top_p=top_p,
                        eos_token=eos_token, seed=seed)


def gpt_stack_blocks(net: MultiLayerNetwork):
    """Stage-stack the (identical) TransformerBlock params of a ``gpt``
    net: every leaf gains a leading [n_layers] stage dim, the layout
    ``parallel.pipeline.pipeline_apply`` shards over the ``pp`` axis."""
    import jax
    import jax.numpy as jnp

    blocks = net.impls[1:-1]
    trees = [net.params[b.name] for b in blocks]
    return jax.tree.map(lambda *vs: jnp.stack(vs), *trees)


def gpt_unstack_blocks(net: MultiLayerNetwork, stacked) -> None:
    """Write stage-stacked block params back onto the net (inverse of
    ``gpt_stack_blocks``) so the pipelined trainer and the sequential
    container share one parameter store."""
    import jax

    for i, b in enumerate(net.impls[1:-1]):
        net.params = {**net.params,
                      b.name: jax.tree.map(lambda v, i=i: v[i], stacked)}


def gpt_pipeline_loss_fn(net: MultiLayerNetwork, mesh, axis: str = "pp",
                         microbatches: int = None):
    """Pipelined LM loss for a ``gpt`` net: embedding and LM head run
    replicated; the TransformerBlock stack runs as a GPipe microbatch
    pipeline over the mesh ``axis`` (``parallel/pipeline.py`` — each
    device holds one stage, activations rotate via ppermute).

    Returns ``loss(p_emb, p_blocks, p_head, ids, labels)`` with
    ``p_blocks`` stage-stacked ([n_layers] leading dim, from
    ``gpt_stack_blocks``). Differentiable end-to-end — ``jax.grad``
    yields the reverse-schedule backward pipeline, equal to the
    sequential container's gradients (tested).

    Scope: DENSE blocks only. MoE blocks carry a router aux loss in
    layer state that the stage pipeline does not thread (it would
    silently train a different objective than the container), so they
    are rejected; dropout likewise runs 0 here (the gpt default)."""
    import jax.numpy as jnp

    from deeplearning4j_tpu.parallel.pipeline import pipeline_apply

    emb, head = net.impls[0], net.impls[-1]
    blk = net.impls[1]
    if getattr(blk.conf, "num_experts", 0) > 0:
        raise NotImplementedError(
            "pipelined GPT supports dense TransformerBlocks only: MoE "
            "blocks carry a router aux loss in layer state that the "
            "stage pipeline does not thread — train MoE via the "
            "expert-parallel path (parallel.tensor_parallel.moe_ep_specs) "
            "instead")
    if getattr(blk, "dropout_rate", 0.0):
        raise NotImplementedError(
            "pipelined GPT runs blocks without dropout; build the net "
            "with dropout=0")

    def loss(p_emb, p_blocks, p_head, ids, labels):
        from deeplearning4j_tpu.nn.layers.attention import xla_attention

        z, _ = emb.forward(p_emb, ids, {}, False)
        fn = lambda p, h: blk.forward(p, h, {}, False)[0]
        with xla_attention():  # pallas can't run under the pp shard_map
            z = pipeline_apply(p_blocks, fn, z, mesh, axis=axis,
                               microbatches=microbatches)
        return head.score(p_head, z.astype(jnp.float32), labels, {}, False)

    return loss


def gpt_pipelined_train_step(net: MultiLayerNetwork, mesh, axis: str = "pp",
                             learning_rate: float = 1e-3,
                             microbatches: int = None):
    """Jitted SGD train step over (emb, stage-stacked blocks, head)
    params with the block stack pipelined over ``axis``. Returns
    ``step(p_emb, p_blocks, p_head, ids, labels) -> (params..., loss)``."""
    import jax

    loss_fn = gpt_pipeline_loss_fn(net, mesh, axis, microbatches)

    @jax.jit
    def step(p_emb, p_blocks, p_head, ids, labels):
        loss, grads = jax.value_and_grad(loss_fn, argnums=(0, 1, 2))(
            p_emb, p_blocks, p_head, ids, labels)
        upd = lambda t, g: jax.tree.map(
            lambda v, gv: v - learning_rate * gv, t, g)
        return (upd(p_emb, grads[0]), upd(p_blocks, grads[1]),
                upd(p_head, grads[2]), loss)

    return step


def gpt_train_flops_per_token(vocab_size: int, d_model: int, n_layers: int,
                              seq_len: int, ffn_mult: int = 4) -> float:
    """Per-token train FLOPs ≈ 6 * (params-ish MACs) + attention term."""
    per_layer = 3 * d_model * d_model + d_model * d_model \
        + 2 * ffn_mult * d_model * d_model          # qkv + proj + mlp
    attn = 2 * seq_len * d_model / 2                # causal qk^T + pv
    head = d_model * vocab_size
    macs = n_layers * (per_layer + attn) + head + d_model  # + embed gather
    return 6.0 * macs
