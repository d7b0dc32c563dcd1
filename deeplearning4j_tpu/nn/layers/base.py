"""Layer implementation protocol + registry + shared helpers.

Parity anchor: ``nn/layers/BaseLayer.java`` (preOutput :354,
backpropGradient :145 — the latter intentionally absent here, see package
docstring) and ``util/Dropout.java``.
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple, Type

import jax
import jax.numpy as jnp

from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.util.dtypes import cast_floats

_IMPL_REGISTRY: Dict[Type[L.Layer], Type["LayerImpl"]] = {}


def register_impl(conf_cls: Type[L.Layer]):
    def deco(impl_cls):
        _IMPL_REGISTRY[conf_cls] = impl_cls
        impl_cls.conf_cls = conf_cls
        return impl_cls

    return deco


def build_layer(global_conf: NeuralNetConfiguration, layer_conf: L.Layer, name: str) -> "LayerImpl":
    """Instantiate the impl for a layer config (the reference resolved this
    via ``Layer.instantiate``; custom layers register with
    :func:`register_impl`)."""
    for cls in type(layer_conf).__mro__:
        if cls in _IMPL_REGISTRY:
            return _IMPL_REGISTRY[cls](global_conf, layer_conf, name)
    raise ValueError(f"no implementation registered for {type(layer_conf).__name__}")


def apply_dropout(x: jnp.ndarray, rate: float, rng: jax.Array) -> jnp.ndarray:
    """Inverted dropout (``util/Dropout.java``): each unit dropped with
    probability ``rate``, survivors scaled by 1/(1-rate) so inference
    needs no rescale."""
    keep = 1.0 - rate
    mask = jax.random.bernoulli(rng, keep, x.shape)
    return jnp.where(mask, x / keep, 0.0)


class LayerImpl:
    """A layer = pure ``init_params`` + ``forward``.

    ``forward(params, x, state, train, rng) -> (out, new_state)``.
    ``state`` carries non-trainable variables (batch-norm moving stats,
    RNN last-step carry for ``rnnTimeStep``); pure so the container can
    trace it into one XLA program.
    """

    conf_cls: Type[L.Layer] = L.Layer

    # False for layers whose input is integer indices (embeddings): the
    # mixed-precision input cast must NOT touch them — bf16 has an
    # 8-bit mantissa, so ids >= 256 round (bf16(511) == 512), producing
    # wrong or out-of-range gathers/scatter-grads
    cast_input = True

    # Impls that honor ``has_bias=False`` set this True (conv/dense); all
    # others reject the flag loudly instead of silently training a bias.
    supports_no_bias = False

    # True for layers whose train-mode output/loss depends on CROSS-batch
    # statistics (batch-norm moments, MoE load-balancing aux loss): the
    # shape-bucketing tail-batch padding is only exact for per-example-
    # independent layers, so the containers skip padding when any layer
    # sets this.
    batch_statistics = False

    # Serving-slice seam (parallel/mesh.py apply_serving_slice): when a
    # net is placed on a mesh SLICE with the column-only tensor-parallel
    # layout, every impl gets its slice mesh pinned here, and the impl's
    # traced code calls :meth:`_slice_replicate` right before any
    # reduction that would otherwise cross shards (a LayerNorm mean over
    # a sharded feature dim, a matmul contracting a sharded activation).
    # The constraint lowers to an all-gather — pure data movement — so
    # sliced output stays BITWISE equal to the single-device program.
    # None (the default) keeps every existing path byte-identical.
    _slice_mesh = None

    # Placement seam (parallel/tensor_parallel.py apply_shardings): the
    # mesh the net's params were placed over, or None on one device. A
    # Pallas kernel is opaque to the SPMD partitioner (Mosaic refuses to
    # lower one under a multi-device jit), so attention impls hand this
    # to ``dispatch_attention``, which maps the kernel over the mesh.
    _mesh = None

    #: True for block layers whose forward the container may run again in
    #: the backward pass instead of keeping its activations
    #: (``NeuralNetConfiguration.recompute_blocks``)
    recomputable = False

    #: the ``jax.ad_checkpoint.checkpoint_name``s of the values of its
    #: forward that a recomputed block keeps beside its input, so that the
    #: second run does not make them again; empty: the input alone
    kept_names = ()

    #: True for an output layer whose ``score`` takes the output of a
    #: repeated span (``MultiLayerConfiguration.repeat_span``) after EVERY
    #: pass, as a list; any other head is handed the last pass's
    scores_every_pass = False

    def cast_params(self, params, dtype):
        """The layer's parameters as its forward takes them under a
        half-precision compute policy: every float leaf cast, unless the
        layer keeps some in float32."""
        return cast_floats(params, dtype)

    def _slice_replicate(self, x):
        """Constrain ``x`` to replicated over the slice mesh (identity
        when the net is not slice-served)."""
        mesh = self._slice_mesh
        if mesh is None:
            return x
        from jax.sharding import NamedSharding, PartitionSpec
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, PartitionSpec()))

    def __init__(self, global_conf: NeuralNetConfiguration, conf: L.Layer, name: str):
        self.gc = global_conf
        self.conf = conf
        self.name = name
        if not getattr(conf, "has_bias", True) and not self.supports_no_bias:
            raise ValueError(
                f"{type(conf).__name__} ({name}): has_bias=False is not "
                f"supported by {type(self).__name__}")

    # -- config resolution helpers --
    @property
    def activation(self) -> str:
        return self.conf.activation or self.gc.activation

    @property
    def weight_init(self) -> str:
        return self.conf.weight_init or self.gc.weight_init

    @property
    def bias_init(self) -> float:
        return self.conf.bias_init if self.conf.bias_init is not None else self.gc.bias_init

    @property
    def dropout_rate(self) -> float:
        return self.conf.dropout if self.conf.dropout is not None else self.gc.dropout

    @property
    def l1(self) -> float:
        return self.conf.l1 if self.conf.l1 is not None else self.gc.l1

    @property
    def l2(self) -> float:
        return self.conf.l2 if self.conf.l2 is not None else self.gc.l2

    # -- protocol --
    def init_params(self, key: jax.Array) -> Dict[str, jnp.ndarray]:
        return {}

    def init_state(self) -> Dict[str, Any]:
        return {}

    def num_params(self) -> int:
        import numpy as np

        key = jax.random.PRNGKey(0)
        return int(sum(np.prod(v.shape) for v in self.init_params(key).values()))

    def forward(
        self,
        params: Dict[str, jnp.ndarray],
        x: jnp.ndarray,
        state: Dict[str, Any],
        train: bool,
        rng: Optional[jax.Array] = None,
        mask: Optional[jnp.ndarray] = None,
    ) -> Tuple[jnp.ndarray, Dict[str, Any]]:
        raise NotImplementedError

    def maybe_dropout_input(self, x: jnp.ndarray, train: bool, rng: Optional[jax.Array]) -> jnp.ndarray:
        """The reference applies dropout to a layer's *input* activations
        (``BaseLayer.preOutput`` → ``Dropout.applyDropout``) — UNLESS
        DropConnect is on, which redirects the same probability to the
        weights instead (``BaseLayer.java:449`` has ``!useDropConnect``
        in the input-dropout condition)."""
        rate = self.dropout_rate
        if (train and rate > 0.0 and rng is not None
                and not (self.applies_drop_connect
                         and getattr(self.gc, "use_drop_connect", False))):
            return apply_dropout(x, rate, rng)
        return x

    # True only for impls whose forward actually calls maybe_drop_connect
    # (dense family, conv, output — the layers where the reference's
    # BaseLayer.preOutput/ConvolutionLayer apply it). Layers WITHOUT the
    # weight-mask path keep their input dropout even under
    # use_drop_connect, so the flag can never silently strip a layer's
    # only stochastic regularization (review r4).
    applies_drop_connect = False

    def maybe_drop_connect(self, params: Dict[str, jnp.ndarray], train: bool,
                           rng: Optional[jax.Array]) -> Dict[str, jnp.ndarray]:
        """DropConnect (``BaseLayer.preOutput:350``,
        ``ConvolutionLayer.java:189`` → ``util/Dropout.java:13``
        ``applyDropConnect``): with ``use_drop_connect``, the layer's
        dropout probability masks the WEIGHT matrix (W only — biases are
        untouched, matching the reference's WEIGHT_KEY-only call).
        Inverted scaling (survivors / keep) like this framework's input
        dropout, so inference needs no rescale."""
        rate = self.dropout_rate
        if not (train and rate > 0.0 and rng is not None and "W" in params
                and getattr(self.gc, "use_drop_connect", False)):
            return params
        # distinct stream from any input-dropout use of the same rng
        key = jax.random.fold_in(rng, 0x0D20)
        return {**params, "W": apply_dropout(params["W"], rate, key)}

    def regularization_penalty(self, params: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        """L1/L2 score term (``BaseLayer.calcL2/calcL1``; weights only, not
        biases — reference convention)."""
        pen = jnp.asarray(0.0, jnp.float32)
        if self.l2 > 0.0:
            for k, v in params.items():
                if k != "b":
                    pen = pen + 0.5 * self.l2 * jnp.sum(v.astype(jnp.float32) ** 2)
        if self.l1 > 0.0:
            for k, v in params.items():
                if k != "b":
                    pen = pen + self.l1 * jnp.sum(jnp.abs(v.astype(jnp.float32)))
        return pen

    def has_loss(self) -> bool:
        return False
