"""Declarative layer configurations.

Parity: ``nn/conf/layers/*.java`` — 21 Jackson-serializable layer config
types with per-layer overrides of global hyperparameters
(``NeuralNetConfiguration.java:84-86``). Here each config is a frozen
dataclass registered in a polymorphic type registry (the analog of the
reference's Jackson ``registerSubtypes`` :320, including user-defined
custom layers).

All fields with value ``None`` inherit the global default from the
enclosing :class:`~deeplearning4j_tpu.nn.conf.NeuralNetConfiguration`.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple, Type

_LAYER_REGISTRY: Dict[str, Type["Layer"]] = {}


def register_layer(cls: Type["Layer"]) -> Type["Layer"]:
    """Register a layer config type for serialization (the custom-layer
    seam tested by the reference's ``TestCustomLayers.java``)."""
    _LAYER_REGISTRY[cls.__name__] = cls
    return cls


def layer_from_dict(d: Dict[str, Any]) -> "Layer":
    d = dict(d)
    type_name = d.pop("@type")
    cls = _LAYER_REGISTRY[type_name]
    field_names = {f.name for f in dataclasses.fields(cls)}
    kwargs = {k: v for k, v in d.items() if k in field_names}
    # tuples arrive from JSON as lists
    for f in dataclasses.fields(cls):
        if f.name in kwargs and isinstance(kwargs[f.name], list):
            kwargs[f.name] = tuple(kwargs[f.name])
    if isinstance(kwargs.get("dist"), dict):
        from deeplearning4j_tpu.nn.weights import Distribution
        kwargs["dist"] = Distribution.from_dict(kwargs["dist"])
    return cls(**kwargs)


@dataclasses.dataclass(frozen=True)
class Layer:
    """Base layer config (``nn/conf/layers/Layer.java``)."""

    name: Optional[str] = None
    activation: Optional[str] = None
    weight_init: Optional[str] = None
    bias_init: Optional[float] = None
    # Layers feeding BatchNormalization don't need a bias: BN's beta
    # absorbs it, and on TPU the bias *gradient* is a full HBM reduce
    # over the layer's output — measurably expensive in conv nets.
    has_bias: bool = True
    dist_mean: float = 0.0
    dist_std: float = 1.0
    # explicit WeightInit.DISTRIBUTION source (nn/conf/distribution/):
    # a weights.Distribution; overrides dist_mean/dist_std when set
    dist: Optional[object] = None
    dropout: Optional[float] = None  # keep DL4J semantics: probability of RETAINING is 1-dropout? see layers/base.py
    l1: Optional[float] = None
    l2: Optional[float] = None
    # per-layer updater overrides
    learning_rate: Optional[float] = None
    momentum: Optional[float] = None
    updater: Optional[str] = None
    gradient_normalization: Optional[str] = None
    gradient_normalization_threshold: Optional[float] = None

    def to_dict(self) -> Dict[str, Any]:
        d = {"@type": type(self).__name__}
        for f in dataclasses.fields(self):
            v = getattr(self, f.name)
            if v is not None and v != f.default:
                if dataclasses.is_dataclass(v) and not isinstance(v, type):
                    v = dataclasses.asdict(v)  # e.g. weights.Distribution
                d[f.name] = list(v) if isinstance(v, tuple) else v
        return d


@dataclasses.dataclass(frozen=True)
class FeedForwardLayer(Layer):
    """Base for layers with explicit nIn/nOut
    (``nn/conf/layers/FeedForwardLayer.java``)."""

    n_in: Optional[int] = None  # auto-wired from InputType when None
    n_out: Optional[int] = None


@register_layer
@dataclasses.dataclass(frozen=True)
class DenseLayer(FeedForwardLayer):
    """``nn/conf/layers/DenseLayer.java`` — z = x·W + b, activation."""


@register_layer
@dataclasses.dataclass(frozen=True)
class OutputLayer(FeedForwardLayer):
    """``nn/conf/layers/OutputLayer.java`` — dense + loss function."""

    loss_function: str = "mcxent"


@register_layer
@dataclasses.dataclass(frozen=True)
class RnnOutputLayer(FeedForwardLayer):
    """``nn/conf/layers/RnnOutputLayer.java`` — per-timestep output + loss,
    honoring a [batch, T] label mask.

    ``tied_to`` names another layer of the net (``"layer0"``) whose leaf
    ``W`` this head reads, transposed, in place of a ``W`` of its own (a
    language model's head tied to its embedding: one leaf, whose
    gradient is the sum of both uses); such a head owns no parameters, so
    ``has_bias`` must be False. ``logits_scale`` multiplies the logits."""

    loss_function: str = "mcxent"
    tied_to: Optional[str] = None
    logits_scale: float = 1.0


@register_layer
@dataclasses.dataclass(frozen=True)
class ExitGateOutputLayer(RnnOutputLayer):
    """The head of a net whose repeated span
    (``MultiLayerConfiguration.repeat_span``) hands it the span's output
    after every pass: the same ``W`` scores each of the ``R`` outputs, and a
    learned gate ``lambda_s = sigmoid(h_s w_gate + b_gate)`` gives every
    token a distribution over the pass at which to exit, ``p_s = lambda_s
    prod_{j<s} (1 - lambda_j)`` with the last pass taking what is left. The
    score is the mean over tokens of ``sum_s p_s CE_s - entropy_weight *
    H(p)``; ``output()`` gives the last pass's prediction (its activation
    over its logits, as every head's). With one pass it is the plain
    softmax head."""

    entropy_weight: float = 0.05


@register_layer
@dataclasses.dataclass(frozen=True)
class LossLayer(Layer):
    """``nn/conf/layers/LossLayer.java`` — loss without params (identity
    or activation-only forward)."""

    loss_function: str = "mse"


@register_layer
@dataclasses.dataclass(frozen=True)
class ConvolutionLayer(FeedForwardLayer):
    """``nn/conf/layers/ConvolutionLayer.java``.

    NHWC; kernel [kh, kw, inC, outC]. n_in = input channels. The
    reference's ``cudnnAlgoMode`` knob has no analog — algorithm choice
    belongs to XLA on TPU.
    """

    kernel_size: Tuple[int, int] = (5, 5)
    stride: Tuple[int, int] = (1, 1)
    padding: Tuple[int, int] = (0, 0)
    convolution_mode: str = "truncate"  # truncate|same (reference ConvolutionMode)


class PoolingType:
    MAX = "max"
    AVG = "avg"
    SUM = "sum"
    PNORM = "pnorm"


@register_layer
@dataclasses.dataclass(frozen=True)
class SubsamplingLayer(Layer):
    """``nn/conf/layers/SubsamplingLayer.java`` — max/avg/sum pooling."""

    pooling_type: str = PoolingType.MAX
    kernel_size: Tuple[int, int] = (2, 2)
    stride: Tuple[int, int] = (2, 2)
    padding: Tuple[int, int] = (0, 0)
    pnorm: int = 2


@register_layer
@dataclasses.dataclass(frozen=True)
class BatchNormalization(FeedForwardLayer):
    """``nn/conf/layers/BatchNormalization.java`` — train-time batch stats
    + moving averages for inference, optional learned gamma/beta."""

    decay: float = 0.9
    eps: float = 1e-5
    is_minibatch: bool = True
    lock_gamma_beta: bool = False
    gamma: float = 1.0
    beta: float = 0.0


@register_layer
@dataclasses.dataclass(frozen=True)
class LocalResponseNormalization(Layer):
    """``nn/conf/layers/LocalResponseNormalization.java`` — cross-channel
    LRN (cuDNN slot in the reference; a fused reduce window here)."""

    k: float = 2.0
    n: float = 5.0
    alpha: float = 1e-4
    beta: float = 0.75


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesLSTM(FeedForwardLayer):
    """``nn/conf/layers/GravesLSTM.java`` — LSTM with peephole connections
    (Graves 2013 formulation, matching ``LSTMHelpers.java``)."""

    forget_gate_bias_init: float = 1.0
    gate_activation: str = "sigmoid"


@register_layer
@dataclasses.dataclass(frozen=True)
class GravesBidirectionalLSTM(GravesLSTM):
    """``nn/conf/layers/GravesBidirectionalLSTM.java`` — fwd+bwd LSTMs,
    outputs summed (reference semantics)."""


@register_layer
@dataclasses.dataclass(frozen=True)
class AttentionLayer(FeedForwardLayer):
    """Multi-head self-attention over [b, t, f] sequences.

    No reference counterpart (the reference predates attention —
    SURVEY.md §5 long-context note); this is the SURVEY §7.7 extension
    made user-reachable. Backed by ``ops/attention.py``; when a
    sequence-parallel mesh is active (``parallel.mesh.sequence_mesh``),
    the impl automatically switches to the ring-attention kernel
    (``parallel/ring_attention.py``) and shards time over the mesh's
    ``seq`` axis."""

    num_heads: int = 4
    causal: bool = False
    residual: bool = True  # x + attn(x) — standard transformer block wiring


@register_layer
@dataclasses.dataclass(frozen=True)
class EmbeddingLayer(FeedForwardLayer):
    """``nn/conf/layers/EmbeddingLayer.java`` — index lookup as one-hot
    matmul (MXU-friendly gather; input is int indices [batch] or
    [batch, 1])."""


@register_layer
@dataclasses.dataclass(frozen=True)
class SequenceEmbeddingLayer(FeedForwardLayer):
    """Token + learned positional embedding: int indices [b, t] →
    [b, t, n_out]. No reference counterpart (the reference embeds only
    [b] ids, ``EmbeddingLayer.java``); this is the transformer on-ramp
    (SURVEY §7.7 extension). ``positions=False`` is a model without
    positional embeddings: no ``P`` leaf exists. ``output_multiplier``
    scales the embedded tokens."""

    max_len: int = 2048
    positions: bool = True
    output_multiplier: float = 1.0


@register_layer
@dataclasses.dataclass(frozen=True)
class TransformerBlock(FeedForwardLayer):
    """Pre-LN transformer decoder/encoder block: LN → multi-head
    attention (flash Pallas kernel / ring under a seq mesh) → residual →
    LN → GELU MLP → residual. No reference counterpart (SURVEY §7.7
    extension); n_in == n_out == d_model."""

    num_heads: int = 8
    ffn_mult: int = 4
    causal: bool = True
    # Mixtral-style MoE FFN: > 0 replaces the dense MLP with a top-1
    # routed expert mix (ops/moe.py); shard experts via moe_ep_specs
    num_experts: int = 0
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01


@register_layer
@dataclasses.dataclass(frozen=True)
class RMSNormLayer(FeedForwardLayer):
    """``x / sqrt(mean(x^2) + eps) * g`` over the last axis, one gain a
    channel: the norm before a modern decoder's head."""

    eps: float = 1e-5


@dataclasses.dataclass(frozen=True)
class GatedDecoderBlock(FeedForwardLayer):
    """What the decoder blocks of the hybrid family share: pre-RMSNorm,
    a mixer, a gated MLP ``(silu(a) * b) W_down`` with ``[a, b] = h
    W_gate_up`` of hidden width ``ffn_hidden``, no biases, and both
    residual branches scaled by ``residual_multiplier``. Training only:
    these blocks have no cache (ROADMAP Reach A.8).

    With ``num_experts`` > 0 the gated MLP is a layer of routed experts
    (``nn/layers/moe.py::routed_experts``): a router scores all
    ``num_experts``, each token takes ``experts_per_token`` of them on the
    sigmoid scores plus, with
    ``expert_bias``, a per-expert bias that decides the selection only and
    lives in the layer's state, weighted by the scores (normalised over the
    picks with ``norm_topk_prob``, times ``routed_scaling_factor``); each
    expert is a gated MLP of width ``expert_hidden``. The layer holds the
    experts ``experts_held = (first, count)`` (count 0: all) and computes
    their part of the result; no capacity, no token dropped."""

    ffn_hidden: int = 0
    rms_eps: float = 1e-5
    residual_multiplier: float = 1.0
    # a second RMSNorm on each branch's OUTPUT, before the residual add (the
    # sandwich block: ``x + norm(mixer(norm(x)))``); two more gains a block
    branch_norms: bool = False
    # what a recomputed body keeps beside its input (``checkpoint_name``s of
    # nn/layers/hybrid.py); None: what the block's class keeps. A block that
    # runs several times a step holds each kept value once an application
    kept_values: Optional[Tuple[str, ...]] = None
    num_experts: int = 0
    experts_per_token: int = 0
    experts_held: Tuple[int, int] = (0, 0)
    expert_hidden: int = 0
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    expert_bias: bool = False


@register_layer
@dataclasses.dataclass(frozen=True)
class Mamba2Block(GatedDecoderBlock):
    """A Mamba-2 state-space mixer (``ops/ssd.py``) and the gated MLP.
    ``n_heads`` heads of ``d_head`` channels (``n_heads * d_head`` is the
    inner width), a state of ``d_state`` a channel, ``n_groups`` groups of
    heads sharing ``B`` and ``C``, a causal depthwise convolution of width
    ``d_conv`` before the scan, ``chunk_size`` the chunk of the chunked
    evaluation. n_in == n_out == d_model."""

    n_heads: int = 64
    d_head: int = 64
    d_state: int = 128
    d_conv: int = 4
    n_groups: int = 1
    chunk_size: int = 256


@register_layer
@dataclasses.dataclass(frozen=True)
class GroupedQueryBlock(GatedDecoderBlock):
    """Causal self-attention with ``num_kv_heads`` key/value heads under
    ``num_heads`` query heads, no biases, and the gated MLP. Scores are
    scaled by ``attention_multiplier`` (``None``: the usual 1/sqrt(head
    width)). ``rope_theta`` turns q and k by rotary positions of that base
    over the whole head (``ops/attention.rotary``); ``None``: no positions.
    n_in == n_out == d_model."""

    num_heads: int = 8
    num_kv_heads: int = 8
    attention_multiplier: Optional[float] = None
    rope_theta: Optional[float] = None
    # an RMSNorm over each head of q and of k (a gain of ``d_head`` each)
    # before the rotation
    qk_norm: bool = False


@register_layer
@dataclasses.dataclass(frozen=True)
class ShortConvBlock(GatedDecoderBlock):
    """A gated short-convolution mixer and the gated MLP (or routed experts):
    ``[B, C, x] = h W_in`` (three widths of d_model), ``u = B * x``, a causal
    depthwise convolution of ``conv_kernel`` taps over time with no bias and
    no activation, ``y = (C * conv(u)) W_out``. n_in == n_out == d_model."""

    conv_kernel: int = 3


def parse_reads(layer: "Layer"):
    """The values a layer reads, as ``(providing layer, name)`` pairs: its
    configuration's ``reads`` entries ``"<layer>.<name>"``; none for a layer
    whose configuration has no such key."""
    return [tuple(entry.partition(".")[::2])
            for entry in getattr(layer, "reads", ())]


@dataclasses.dataclass(frozen=True)
class LayerNormDecoderBlock(FeedForwardLayer):
    """What the decoder blocks of the decoder-hybrid-decoder family share:
    pre-LayerNorm with gain and bias, a mixer, and a gated MLP
    ``(a * silu(g)) W_fc2`` with ``[a, g] = h W_fc1`` of hidden width
    ``ffn_hidden``, no MLP bias. Training only, as the hybrid family.

    A block may hand values forward to later layers and read what earlier
    ones handed on (``MultiLayerNetwork``'s seam): ``provides`` lists the
    names it makes, ``reads`` the names it takes, each with the layer that
    makes it as ``"<layer>.<name>"`` (``"layer3.memory"``)."""

    ffn_hidden: int = 0
    ln_eps: float = 1e-5
    kept_values: Optional[Tuple[str, ...]] = None
    provides: Tuple[str, ...] = ()
    reads: Tuple[str, ...] = ()


@register_layer
@dataclasses.dataclass(frozen=True)
class Mamba1Block(LayerNormDecoderBlock):
    """A Mamba-1 selective-scan mixer (``ops/selective_scan.py``) and the
    gated MLP. ``d_inner`` channels, a state of ``d_state`` a channel with a
    decay of its own for every (channel, state) pair, a causal depthwise
    convolution of width ``d_conv`` before the scan, the step projected
    through ``dt_rank``. May provide ``memory``: the scan's output before
    the gate. n_in == n_out == d_model."""

    d_inner: int = 0
    d_state: int = 16
    d_conv: int = 4
    dt_rank: int = 0


@register_layer
@dataclasses.dataclass(frozen=True)
class DiffAttentionBlock(LayerNormDecoderBlock):
    """Differential attention (arXiv:2410.05258) and the gated MLP: heads in
    adjacent pairs, the second softmax map of a pair subtracted from the
    first times a learned ``lambda``, the difference applied to the pair's
    two value heads side by side, an RMSNorm over them, ``1 - lambda_init``
    with ``lambda_init = 0.8 - 0.6 exp(-0.3 layer_index)``. ``num_kv_heads``
    key/value heads under ``num_heads`` query heads, biases on both
    projections, no positions. ``window``: a query sees itself and the
    ``window - 1`` keys before it. May provide ``kv`` (its keys and values
    after their bias); with ``cross`` it has a query projection only and
    reads ``kv``. n_in == n_out == d_model."""

    num_heads: int = 8
    num_kv_heads: int = 8
    window: Optional[int] = None
    cross: bool = False
    layer_index: int = 0


@register_layer
@dataclasses.dataclass(frozen=True)
class GMUBlock(LayerNormDecoderBlock):
    """A gated memory unit and the gated MLP: ``(silu(h W_1) * m) W_2`` with
    ``m`` [b, t, d_inner] the ``memory`` it reads from an earlier Mamba-1
    layer, in place of a scan of its own. n_in == n_out == d_model."""

    d_inner: int = 0


@register_layer
@dataclasses.dataclass(frozen=True)
class LayerNormLayer(FeedForwardLayer):
    """LayerNorm over the last axis with gain and bias: the norm before the
    head of a model whose blocks use it."""

    eps: float = 1e-5


@register_layer
@dataclasses.dataclass(frozen=True)
class MoELayer(FeedForwardLayer):
    """Mixture-of-experts FFN with Switch-style top-1 routing
    (capacity-bounded dense dispatch; see ``ops/moe.py``). No reference
    counterpart (SURVEY §2.6 note 5 — expert parallelism postdates it);
    shard the expert weight dim over a mesh ``expert`` axis for EP.
    Contributes the load-balancing aux loss to the objective via the
    layer-state seam (``__aux_loss__``)."""

    num_experts: int = 8
    ffn_mult: int = 4
    capacity_factor: float = 1.25
    aux_loss_weight: float = 0.01
    residual: bool = False


@register_layer
@dataclasses.dataclass(frozen=True)
class AutoEncoder(FeedForwardLayer):
    """``nn/conf/layers/AutoEncoder.java`` — denoising autoencoder for
    layerwise pretraining."""

    corruption_level: float = 0.3
    sparsity: float = 0.0
    loss_function: str = "mse"


class RBMHiddenUnit:
    BINARY = "binary"
    RECTIFIED = "rectified"
    GAUSSIAN = "gaussian"
    SOFTMAX = "softmax"


class RBMVisibleUnit:
    BINARY = "binary"
    GAUSSIAN = "gaussian"
    LINEAR = "linear"
    SOFTMAX = "softmax"


@register_layer
@dataclasses.dataclass(frozen=True)
class RBM(FeedForwardLayer):
    """``nn/conf/layers/RBM.java`` — restricted Boltzmann machine trained
    by contrastive divergence (pretrain path)."""

    hidden_unit: str = RBMHiddenUnit.BINARY
    visible_unit: str = RBMVisibleUnit.BINARY
    k: int = 1  # CD-k steps
    loss_function: str = "reconstruction_crossentropy"


@register_layer
@dataclasses.dataclass(frozen=True)
class ActivationLayer(Layer):
    """``nn/conf/layers/ActivationLayer.java`` — parameterless activation."""


@register_layer
@dataclasses.dataclass(frozen=True)
class DropoutLayer(FeedForwardLayer):
    """``nn/conf/layers/DropoutLayer.java`` — dropout as its own layer."""


@register_layer
@dataclasses.dataclass(frozen=True)
class GlobalPoolingLayer(Layer):
    """Global pooling over time (RNN) or space (CNN). Extension the
    reference gained in 0.7; needed for masked sequence classification."""

    pooling_type: str = PoolingType.MAX
