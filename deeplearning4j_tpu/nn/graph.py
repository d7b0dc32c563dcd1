"""ComputationGraph — the DAG model container.

Parity: ``nn/graph/ComputationGraph.java:74`` (init :264, Kahn
topological sort w/ cycle detection :844-880, computeGradientAndScore
:884, fit(MultiDataSet) :677) and
``nn/conf/ComputationGraphConfiguration.java`` (GraphBuilder API).

As with MultiLayerNetwork, the whole DAG iteration — every vertex
forward in topological order, loss over all output layers, backward,
updaters — is traced into ONE XLA program; vertex hops have no dispatch
cost (XLA fuses across them), where the reference paid per-vertex ND4J
op dispatch.
"""

from __future__ import annotations

import dataclasses
import json
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.flatten_util
import jax.numpy as jnp
import numpy as np

import deeplearning4j_tpu.nn.layers  # noqa: F401  (registers layer impls)
from deeplearning4j_tpu.datasets.dataset import DataSet, MultiDataSet
from deeplearning4j_tpu.datasets.iterators import (
    AsyncDataSetIterator,
    DataSetIterator,
    DeviceFeedIterator,
    ListMultiDataSetIterator,
    MultiDataSetIterator,
    ShapeBucketingIterator,
    feed_pipeline_enabled,
)
from deeplearning4j_tpu.nn.conf import layers as L
from deeplearning4j_tpu.nn.conf.configuration import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.graph import GraphVertex, vertex_from_dict
from deeplearning4j_tpu.monitor import H2D_BYTES_COUNTER, get_registry, span
from deeplearning4j_tpu.nn.conf.layers import layer_from_dict
from deeplearning4j_tpu.nn.scan_dispatch import scan_dispatch
from deeplearning4j_tpu.optimize.deferred import (
    host_step,
    note_dispatch,
    score_sink,
    set_host_step,
)
from deeplearning4j_tpu.nn.layers.base import build_layer
from deeplearning4j_tpu.nn.observed import SyncedStateAttr
from deeplearning4j_tpu.nn.updater import (
    GradientNormalization,
    apply_updater,
    init_updater_state,
    normalize_gradient,
)
from deeplearning4j_tpu.util.dtypes import cast_floats, cast_like, resolve_compute_dtype


@dataclasses.dataclass
class VertexDef:
    name: str
    kind: str  # "input" | "layer" | "op"
    inputs: List[str]
    layer: Optional[L.Layer] = None
    vertex: Optional[GraphVertex] = None


@dataclasses.dataclass
class ComputationGraphConfiguration:
    conf: NeuralNetConfiguration
    vertices: List[VertexDef]
    outputs: List[str]
    pretrain: bool = False
    backprop_type: str = "standard"
    tbptt_fwd_length: int = 20
    tbptt_back_length: int = 20

    class GraphBuilder:
        """``ComputationGraphConfiguration.GraphBuilder`` fluent API."""

        def __init__(self, conf: Optional[NeuralNetConfiguration] = None):
            self._conf = conf or NeuralNetConfiguration()
            self._vertices: List[VertexDef] = []
            self._outputs: List[str] = []
            self._pretrain = False
            self._backprop_type = "standard"
            self._tbptt_fwd = 20
            self._tbptt_back = 20

        def pretrain(self, flag: bool):
            self._pretrain = flag
            return self

        def backprop_type(self, t: str):
            self._backprop_type = t
            return self

        def t_bptt_forward_length(self, n: int):
            self._tbptt_fwd = n
            return self

        def t_bptt_backward_length(self, n: int):
            self._tbptt_back = n
            return self

        def add_inputs(self, *names: str) -> "ComputationGraphConfiguration.GraphBuilder":
            for n in names:
                self._vertices.append(VertexDef(n, "input", []))
            return self

        def add_layer(self, name: str, layer: L.Layer, *inputs: str):
            self._vertices.append(VertexDef(name, "layer", list(inputs), layer=layer))
            return self

        def add_vertex(self, name: str, vertex: GraphVertex, *inputs: str):
            self._vertices.append(VertexDef(name, "op", list(inputs), vertex=vertex))
            return self

        def set_outputs(self, *names: str):
            self._outputs = list(names)
            return self

        def build(self) -> "ComputationGraphConfiguration":
            import copy
            return ComputationGraphConfiguration(
                conf=self._conf, vertices=copy.deepcopy(self._vertices),
                outputs=list(self._outputs), pretrain=self._pretrain,
                backprop_type=self._backprop_type,
                tbptt_fwd_length=self._tbptt_fwd,
                tbptt_back_length=self._tbptt_back)

    @staticmethod
    def builder(conf: Optional[NeuralNetConfiguration] = None):
        return ComputationGraphConfiguration.GraphBuilder(conf)

    # -------- serialization --------

    def to_json(self) -> str:
        def vd(v: VertexDef):
            d = {"name": v.name, "kind": v.kind, "inputs": v.inputs}
            if v.layer is not None:
                d["layer"] = v.layer.to_dict()
            if v.vertex is not None:
                d["vertex"] = v.vertex.to_dict()
            return d

        return json.dumps({
            "conf": self.conf.to_dict(),
            "vertices": [vd(v) for v in self.vertices],
            "outputs": self.outputs,
            "pretrain": self.pretrain,
            "backprop_type": self.backprop_type,
            "tbptt_fwd_length": self.tbptt_fwd_length,
            "tbptt_back_length": self.tbptt_back_length,
        }, indent=2)

    def to_yaml(self) -> str:
        from deeplearning4j_tpu.util.yaml_io import json_to_yaml
        return json_to_yaml(self.to_json())

    @staticmethod
    def from_yaml(s: str) -> "ComputationGraphConfiguration":
        from deeplearning4j_tpu.util.yaml_io import yaml_to_json
        return ComputationGraphConfiguration.from_json(yaml_to_json(s))

    @staticmethod
    def from_json(s: str) -> "ComputationGraphConfiguration":
        d = json.loads(s)
        verts = [VertexDef(
            name=v["name"], kind=v["kind"], inputs=v["inputs"],
            layer=layer_from_dict(v["layer"]) if "layer" in v else None,
            vertex=vertex_from_dict(v["vertex"]) if "vertex" in v else None,
        ) for v in d["vertices"]]
        return ComputationGraphConfiguration(
            conf=NeuralNetConfiguration.from_dict(d["conf"]),
            vertices=verts, outputs=d["outputs"],
            pretrain=d.get("pretrain", False),
            backprop_type=d.get("backprop_type", "standard"),
            tbptt_fwd_length=d.get("tbptt_fwd_length", 20),
            tbptt_back_length=d.get("tbptt_back_length", 20))


def topological_order(vertices: Sequence[VertexDef]) -> List[str]:
    """Kahn's algorithm with cycle detection
    (``ComputationGraph.java:844-880``)."""
    by_name = {v.name: v for v in vertices}
    for v in vertices:
        for i in v.inputs:
            if i not in by_name:
                raise ValueError(f"vertex '{v.name}' references unknown input '{i}'")
    in_deg = {v.name: len(v.inputs) for v in vertices}
    children: Dict[str, List[str]] = {v.name: [] for v in vertices}
    for v in vertices:
        for i in v.inputs:
            children[i].append(v.name)
    queue = [n for n, d in in_deg.items() if d == 0]
    order: List[str] = []
    while queue:
        n = queue.pop(0)
        order.append(n)
        for c in children[n]:
            in_deg[c] -= 1
            if in_deg[c] == 0:
                queue.append(c)
    if len(order) != len(vertices):
        cyc = [n for n, d in in_deg.items() if d > 0]
        raise ValueError(f"cycle detected in graph involving {cyc}")
    return order


class ComputationGraph:
    # observer-visible state: reads run any pending lazy sync installed
    # by ParallelWrapper's averaging mode (nn/observed.py)
    params = SyncedStateAttr("params")
    states = SyncedStateAttr("states")
    opt_state = SyncedStateAttr("opt_state", invalidates="_host_step_mirror")

    # deferred score resolution (optimize/deferred.py) — same doctrine
    # as MultiLayerNetwork; fit() flips it to the pipeline switch
    _defer_scores = True

    def __init__(self, conf: ComputationGraphConfiguration):
        self.conf = conf
        self.gc = conf.conf
        self.defs = {v.name: v for v in conf.vertices}
        self.order = topological_order(conf.vertices)
        self.input_names = [v.name for v in conf.vertices if v.kind == "input"]
        self.output_names = conf.outputs
        if not self.output_names:
            raise ValueError("graph has no outputs set")
        self.impls = {}
        for v in conf.vertices:
            if v.kind == "layer":
                self.impls[v.name] = build_layer(self.gc, v.layer, v.name)
        # output layers that carry loss
        self.loss_outputs = [n for n in self.output_names
                             if n in self.impls and self.impls[n].has_loss()]
        if not self.loss_outputs:
            raise ValueError("at least one output must be an output/loss layer")
        self.params = None
        self.states = None
        self.opt_state = None
        self.listeners: List[Callable] = []
        self._score = float("nan")
        self._dtype = jnp.float32
        self._pretrained = False
        # mixed precision: same policy as MultiLayerNetwork
        # (util/dtypes.py — bf16 vertex compute, f32 params/states/loss)
        self._cd = resolve_compute_dtype(self.gc.compute_dtype)
        # input vertices feeding an index-input layer (embedding) keep
        # their raw dtype — bf16 would corrupt the ids (LayerImpl.cast_input).
        # Walk transitively through non-layer op vertices (merge/stack/...)
        # since those pass ids along unchanged; layers terminate the walk.
        self._input_casts = {}
        for name in self.input_names:
            ok = True
            frontier, seen = [name], set()
            while frontier and ok:
                src = frontier.pop()
                if src in seen:
                    continue
                seen.add(src)
                for v in conf.vertices:
                    if src not in getattr(v, "inputs", ()):
                        continue
                    if v.kind == "layer":
                        ok = ok and self.impls[v.name].cast_input
                    elif v.kind != "input":
                        frontier.append(v.name)
            self._input_casts[name] = ok
        self._jits: Dict[Any, Callable] = {}
        self._dispatch_sigs: set = set()
        self._train_rng_key = None
        # mesh plane seam (see MultiLayerNetwork): sharding appliers pin
        # the MeshPlane here; sharded checkpoints + /healthz read it
        self.mesh_plane = None

    # ------------------------------------------------------------------ init

    def init(self, dtype=jnp.float32) -> "ComputationGraph":
        self._dtype = dtype
        key = jax.random.PRNGKey(self.gc.seed)
        self.params, self.states, upd = {}, {}, {}
        names = sorted(self.impls.keys())
        keys = jax.random.split(key, max(1, len(names)))
        for name, k in zip(names, keys):
            impl = self.impls[name]
            p = {n: v.astype(dtype) for n, v in impl.init_params(k).items()}
            self.params[name] = p
            self.states[name] = impl.init_state()
            ucfg = self.gc.updater_config_for(impl.conf)
            upd[name] = {n: init_updater_state(ucfg, v) for n, v in p.items()}
        self.opt_state = {"step": jnp.zeros((), jnp.int32), "updater": upd}
        self._jits = {}
        self._dispatch_sigs = set()
        self._pretrained = False
        self.mesh_plane = None  # init() re-places on the default device
        for impl in self.impls.values():
            impl._mesh = None
        return self

    def set_listeners(self, *listeners):
        self.listeners = list(listeners)

    def _train_rng(self) -> jax.Array:
        """Fit-path PRNG key, built once per model (was rebuilt on host
        for every minibatch)."""
        if self._train_rng_key is None:
            self._train_rng_key = jax.random.PRNGKey(self.gc.seed + 7919)
        return self._train_rng_key

    # -------------------------------------------------------- functional core

    def _forward_all(self, params, states, inputs: Dict[str, jnp.ndarray],
                     train: bool, rng, fmasks: Dict[str, jnp.ndarray]):
        acts: Dict[str, jnp.ndarray] = {}
        masks: Dict[str, Optional[jnp.ndarray]] = {}
        new_states = dict(states)
        for vi, name in enumerate(self.order):
            v = self.defs[name]
            if v.kind == "input":
                x_in = inputs[name]
                if self._cd is not None and self._input_casts.get(name, True):
                    x_in = x_in.astype(self._cd)
                acts[name] = x_in
                masks[name] = fmasks.get(name)
            elif v.kind == "layer":
                impl = self.impls[name]
                x = acts[v.inputs[0]]
                m = masks[v.inputs[0]]
                p = params[name]
                if self._cd is not None:
                    if impl.has_loss() and "W" not in p:
                        # matmul-free heads: loss math runs f32. Heads
                        # WITH a weight matmul keep policy-dtype
                        # operands — their preout emits f32 logits
                        # (OutputImpl.preout), same as MultiLayerNetwork
                        x = x.astype(jnp.float32)
                    else:
                        p = cast_floats(p, self._cd)
                lrng = jax.random.fold_in(rng, vi) if rng is not None else None
                out, ns = impl.forward(p, x, states[name], train, lrng, mask=m)
                if self._cd is not None:
                    ns = cast_like(ns, states[name])
                acts[name] = out
                new_states[name] = ns
                # rnn layers preserve mask; pooling over time consumes it
                masks[name] = m if out.ndim == 3 else None
            else:
                ins = [acts[i] for i in v.inputs]
                ms = [masks[i] for i in v.inputs]
                acts[name] = v.vertex.forward(ins, ms)
                masks[name] = ms[0] if acts[name].ndim == 3 else None
        return acts, masks, new_states

    def _score_fn(self, params, states, inputs, labels: Dict[str, jnp.ndarray],
                  train: bool, rng, fmasks, lmasks):
        """Σ output-layer losses + L1/L2 (``computeGradientAndScore`` :884,
        score summed over output layers :895-908)."""
        acts, masks, new_states = self._forward_all(params, states, inputs, train, rng, fmasks)
        score = None
        for vi, name in enumerate(self.loss_outputs):
            v = self.defs[name]
            impl = self.impls[name]
            x = acts[v.inputs[0]]
            p_head = params[name]
            if self._cd is not None:
                if "W" in p_head:  # bf16 head matmul, f32 logits (preout)
                    p_head = cast_floats(p_head, self._cd)
                else:
                    x = x.astype(jnp.float32)  # loss always f32
            lrng = jax.random.fold_in(rng, 10_000 + vi) if rng is not None else None
            lmask = lmasks.get(name) if lmasks else None
            s = impl.score(p_head, x, labels[name], states[name], train, lrng, mask=lmask)
            score = s if score is None else score + s
        for name, impl in self.impls.items():
            score = score + impl.regularization_penalty(params[name]).astype(score.dtype)
        # activation-dependent auxiliary losses (e.g. MoE load balancing)
        # ride the state seam — same contract as MultiLayerNetwork
        for ns in new_states.values():
            if isinstance(ns, dict) and "__aux_loss__" in ns:
                score = score + ns["__aux_loss__"].astype(score.dtype)
        return score, new_states

    def _make_train_step(self):
        gn, ucfgs = {}, {}
        for name, impl in self.impls.items():
            gn[name] = (GradientNormalization(self.gc.resolve(impl.conf, "gradient_normalization")),
                        self.gc.resolve(impl.conf, "gradient_normalization_threshold"))
            ucfgs[name] = self.gc.updater_config_for(impl.conf)

        def step(params, opt_state, states, inputs, labels, fmasks, lmasks, rng_key):
            it = opt_state["step"]
            rng = jax.random.fold_in(rng_key, it)

            def loss(p):
                return self._score_fn(p, states, inputs, labels, True, rng, fmasks, lmasks)

            (score, new_states), grads = jax.value_and_grad(loss, has_aux=True)(params)
            new_params, new_upd = {}, {}
            for name, impl in self.impls.items():
                nt, thr = gn[name]
                g = normalize_gradient(nt, grads[name], thr)
                new_params[name], new_upd[name] = {}, {}
                for pname, gval in g.items():
                    u, ust = apply_updater(ucfgs[name], gval, opt_state["updater"][name][pname], it)
                    new_params[name][pname] = params[name][pname] - u.astype(params[name][pname].dtype)
                    new_upd[name][pname] = ust
            return new_params, {"step": it + 1, "updater": new_upd}, new_states, score

        # states donated too off-CPU; CPU donation is off entirely —
        # same overlap-aliasing hazard gate as
        # MultiLayerNetwork._make_train_step (deferred scores remove the
        # per-step sync that used to serialize donated dispatches)
        donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
        return jax.jit(step, donate_argnums=donate)

    # ----------------------------------------------------------------- train

    def _to_mds(self, data) -> MultiDataSet:
        if isinstance(data, DataSet):
            return MultiDataSet(
                features=[data.features], labels=[data.labels],
                features_masks=[data.features_mask] if data.features_mask is not None else None,
                labels_masks=[data.labels_mask] if data.labels_mask is not None else None)
        return data

    def _tensors(self, mds: MultiDataSet):
        """Features map positionally onto ``add_inputs`` order; labels and
        label masks onto ``set_outputs`` order (loss outputs selected by
        name from that alignment)."""
        inputs = {n: jnp.asarray(f, self._dtype) for n, f in zip(self.input_names, mds.features)}
        by_output = dict(zip(self.output_names, mds.labels))
        labels = {n: jnp.asarray(by_output[n], self._dtype) for n in self.loss_outputs}
        fmasks = {}
        if mds.features_masks:
            for n, m in zip(self.input_names, mds.features_masks):
                if m is not None:
                    fmasks[n] = jnp.asarray(m, self._dtype)
        lmasks = {}
        if mds.labels_masks:
            for n, m in zip(self.output_names, mds.labels_masks):
                if m is not None and n in self.loss_outputs:
                    lmasks[n] = jnp.asarray(m, self._dtype)
        return inputs, labels, fmasks, lmasks

    def _pad_tail_safe(self) -> bool:
        """Tail-batch padding is exact only for per-example-independent
        layers (ShapeBucketingIterator doctrine)."""
        return not any(getattr(i, "batch_statistics", False)
                       for i in self.impls.values())

    def _stage_mds(self, b) -> MultiDataSet:
        """Device-feed placement (worker thread): normalize to
        MultiDataSet and stage every array so ``_tensors`` becomes a
        no-op on the step loop."""
        mds = self._to_mds(b)
        was_host = isinstance(mds.features[0], np.ndarray)
        with span("stage", path="device_feed"):
            out = self._device_mds(mds)
        if was_host:
            arrs = list(out.features) + list(out.labels) + \
                [m for m in (out.features_masks or []) if m is not None] + \
                [m for m in (out.labels_masks or []) if m is not None]
            get_registry().counter(
                H2D_BYTES_COUNTER,
                "Host->device bytes staged by the feed pipeline").inc(
                sum(int(a.nbytes) for a in arrs if a is not None))
        return out

    def fit(self, data: Union[DataSet, MultiDataSet, DataSetIterator, MultiDataSetIterator],
            epochs: int = 1, batch_size: Optional[int] = None,
            feed_pipeline: Optional[bool] = None) -> None:
        """``fit(MultiDataSet)`` :677 / ``fit(DataSetIterator)`` :621 /
        ``fit(MultiDataSetIterator)`` :640 — iterators stream minibatches
        through async prefetch, exactly the MLN doctrine; with the feed
        pipeline on (default) batches are shape-bucketed and staged on
        device by a background thread and scores resolve in deferred
        batches (see MultiLayerNetwork.fit)."""
        if getattr(self, "quantized", None) is not None:
            raise ValueError(
                f"this net holds {self.quantized}-quantized serving "
                "weights (nn/quantize.py) — the round() in them has no "
                "useful gradient; train the fp32 original and re-quantize")
        if self.params is None:
            self.init()
        pipeline = feed_pipeline_enabled(feed_pipeline)
        prev_defer, self._defer_scores = self._defer_scores, pipeline
        feed = None
        try:
            if self.conf.pretrain and not self._pretrained:
                self.pretrain(data, batch_size=batch_size)
                self._pretrained = True
            if isinstance(data, (DataSet, MultiDataSet)):
                if batch_size is not None:
                    mds = self._to_mds(data)
                    data = ListMultiDataSetIterator(mds, batch_size)
                else:
                    # stage arrays to device ONCE; _tensors' jnp.asarray
                    # then becomes a no-op on every subsequent epoch
                    mds = self._device_mds(self._to_mds(data))
                    for _ in range(epochs):
                        self._fit_batch(mds)
                    return
            it = data
            if pipeline and self._pad_tail_safe():
                it = ShapeBucketingIterator(it)
            if it.async_supported():
                it = AsyncDataSetIterator(it)  # payload-agnostic prefetch
            if pipeline:
                it = feed = DeviceFeedIterator(it, place=self._stage_mds)
            for _ in range(epochs):
                for mds in it:
                    self._fit_batch(self._to_mds(mds))
        finally:
            if feed is not None:
                feed.close()
            score_sink(self).flush()
            self._defer_scores = prev_defer

    def _device_mds(self, mds: MultiDataSet) -> MultiDataSet:
        dev = lambda a: None if a is None else jnp.asarray(a, self._dtype)
        devs = lambda arrs: None if arrs is None else [dev(a) for a in arrs]
        return MultiDataSet(features=[dev(f) for f in mds.features],
                            labels=[dev(l) for l in mds.labels],
                            features_masks=devs(mds.features_masks),
                            labels_masks=devs(mds.labels_masks))

    def _fit_batch(self, mds: MultiDataSet) -> None:
        feats = mds.features
        if (self.conf.backprop_type == "truncated_bptt"
                and any(f.ndim == 3 and f.shape[1] > self.conf.tbptt_fwd_length
                        for f in feats)):
            self._fit_tbptt(mds)
            return
        self._fit_batch_inner(mds)

    def _seq_token(self):
        """Sequence-parallel context marker for jit cache keys
        (parallel/mesh.py sequence_mesh_token)."""
        from deeplearning4j_tpu.parallel.mesh import sequence_mesh_token
        return sequence_mesh_token()

    def _fit_batch_inner(self, mds: MultiDataSet) -> None:
        key = ("train", self._seq_token())
        if key not in self._jits:
            self._jits[key] = self._make_train_step()
        step = self._jits[key]
        rng_key = self._train_rng()
        with span("data_load", path="graph_fit"):
            # no-ops for device-staged batches (DeviceFeedIterator)
            inputs, labels, fmasks, lmasks = self._tensors(mds)
        # one jit entry serves many operand signatures: fresh shapes (a
        # ragged tail) or a fresh mask pytree structure retrace+compile
        compiling = note_dispatch(self, key + (
            tuple(sorted((n, a.shape, str(a.dtype)) for n, a in inputs.items())),
            tuple(sorted((n, a.shape) for n, a in labels.items())),
            tuple(sorted((n, a.shape) for n, a in fmasks.items())),
            tuple(sorted((n, a.shape) for n, a in lmasks.items()))))
        sink = score_sink(self)
        hs = host_step(self)
        for _ in range(max(1, self.gc.iterations)):
            with span("compile" if compiling else "device_step"):
                self.params, self.opt_state, self.states, score = step(
                    self.params, self.opt_state, self.states, inputs, labels, fmasks, lmasks, rng_key)
            compiling = False
            hs += 1
            set_host_step(self, hs)
            sink.push(hs, score)  # device scalar; batched resolution
            if not self._defer_scores:
                sink.flush()

    # --------------------------------------------------------------- tbptt

    def _recurrent_names(self):
        from deeplearning4j_tpu.nn.layers.recurrent import GravesLSTMImpl
        return [n for n, impl in self.impls.items() if isinstance(impl, GravesLSTMImpl)]

    def _fit_tbptt(self, mds: MultiDataSet) -> None:
        """Truncated BPTT over the DAG (``ComputationGraph`` TBPTT path
        :887-889): every 3-D features/labels tensor is cut into
        ``tbptt_fwd_length`` chunks; LSTM carries cross chunk boundaries
        as data (gradients stop there)."""
        rec = self._recurrent_names()
        if not rec:
            raise ValueError("TBPTT configured but no recurrent layers present")
        seq_feats = [f for f in mds.features if f.ndim == 3]
        T = max(f.shape[1] for f in seq_feats)
        Lc = self.conf.tbptt_fwd_length
        b = mds.features[0].shape[0]
        if not any(lab.ndim == 3 for lab in mds.labels):
            # mixed graphs may pair a sequence head (3-D, chunked) with a
            # static head (2-D, repeated per chunk); but with NO 3-D label
            # there is nothing to truncate and the config is a mistake
            raise ValueError(
                "TBPTT requires at least one per-timestep label [batch, T, "
                f"nOut]; got shapes {[lab.shape for lab in mds.labels]}")
        saved = {}
        for name in rec:
            saved[name] = self.states[name]
            n = self.impls[name].conf.n_out
            self.states[name] = {"h": jnp.zeros((b, n), self._dtype),
                                 "c": jnp.zeros((b, n), self._dtype)}

        def tslice(arrs, sl):
            if arrs is None:
                return None
            return [None if a is None else (a[:, sl] if a.ndim >= 2 else a)
                    for a in arrs]

        try:
            for t0 in range(0, T, Lc):
                sl = slice(t0, t0 + Lc)
                chunk = MultiDataSet(
                    features=[f[:, sl] if f.ndim == 3 else f for f in mds.features],
                    labels=[l[:, sl] if l.ndim == 3 else l for l in mds.labels],
                    features_masks=tslice(mds.features_masks, sl),
                    labels_masks=tslice(mds.labels_masks, sl))
                self._fit_batch_inner(chunk)
        finally:
            for name in rec:
                self.states[name] = saved[name]

    # ------------------------------------------------- scanned multi-step fit

    def _make_scan_fit(self, epochs: int = 1):
        """Epochs-as-one-XLA-program over staged minibatches — the DAG
        analog of MultiLayerNetwork.fit_scan (ONE host dispatch for the
        whole run; every vertex of every step fused by XLA). The epoch
        count is baked into the program, so short epochs pay no host
        round trip each (~0.9 ms on a v5e host, PERF.md)."""
        py_step = self._make_train_step().__wrapped__
        iters = max(1, self.gc.iterations)

        def run(params, opt_state, states, xb, yb, rng_key):
            def body(carry, batch):
                p, o, s = carry
                xs, ys = batch
                for _ in range(iters):
                    p, o, s, score = py_step(p, o, s, xs, ys, {}, {}, rng_key)
                return (p, o, s), score

            def epoch(carry, _):
                carry, scores = jax.lax.scan(body, carry, (xb, yb))
                return carry, scores

            (p, o, s), scores = jax.lax.scan(
                epoch, (params, opt_state, states), None, length=epochs)
            return p, o, s, scores.reshape((-1,))

        # same CPU donation gate as _make_train_step: donated-buffer
        # aliasing on the CPU backend corrupts the heap
        donate = (0, 1, 2) if jax.default_backend() != "cpu" else ()
        return jax.jit(run, donate_argnums=donate)

    def stage_scan(self, data: Union[DataSet, MultiDataSet], batch_size: int):
        """Stage a dataset on device as scan-ready minibatch stacks — do
        this ONCE and pass to ``fit_scan(staged=...)`` to avoid paying
        the host→device transfer per call (for image-scale data it
        outweighs the compute of a short run)."""
        mds = self._to_mds(data)
        has_mask = any(m is not None for m in (mds.features_masks or [])) or \
            any(m is not None for m in (mds.labels_masks or []))
        if has_mask:
            raise ValueError("fit_scan does not support masked data; use fit()")
        n = (mds.num_examples() // batch_size) * batch_size
        if n == 0:
            raise ValueError("batch_size larger than dataset")
        if n != mds.num_examples():
            import logging
            logging.getLogger("deeplearning4j_tpu").warning(
                "fit_scan: dropping %d tail examples (dataset %d %% batch %d)",
                mds.num_examples() - n, mds.num_examples(), batch_size)
        stage = lambda a: jnp.asarray(a[:n], self._dtype).reshape(
            (-1, batch_size) + a.shape[1:])
        with span("data_load", path="stage_scan", examples=n):
            xb = {name: stage(f) for name, f in zip(self.input_names, mds.features)}
            by_output = dict(zip(self.output_names, mds.labels))
            yb = {name: stage(by_output[name]) for name in self.loss_outputs}
        return xb, yb

    def fit_scan(self, data: Optional[Union[DataSet, MultiDataSet]], batch_size: int,
                 epochs: int = 1, staged=None) -> np.ndarray:
        """Device-resident multi-step training; returns per-step scores
        (one host fetch at the end)."""
        if self.params is None:
            self.init()
        xb, yb = staged if staged is not None else self.stage_scan(data, batch_size)
        return scan_dispatch(self, "graph_fit_scan", epochs, xb, yb)

    # ------------------------------------------------------------- pretrain

    def pretrain(self, data, epochs: int = 1,
                 batch_size: Optional[int] = None) -> Dict[str, float]:
        """Layer-wise greedy pretraining over the DAG: each RBM/AE layer
        vertex trains on the frozen activations of its input subgraph
        (``ComputationGraph.pretrain`` path)."""
        if self.params is None:
            self.init()
        if isinstance(data, (DataSet, MultiDataSet)):
            data = ListMultiDataSetIterator(self._to_mds(data), batch_size or 32)
        losses: Dict[str, float] = {}
        for vi, name in enumerate(self.order):
            v = self.defs[name]
            if v.kind != "layer" or not hasattr(self.impls[name], "pretrain_loss"):
                continue
            impl = self.impls[name]
            ucfg = self.gc.updater_config_for(impl.conf)
            use_cd = hasattr(impl, "cd_gradients")

            def make_step(name=name, impl=impl, ucfg=ucfg, use_cd=use_cd):
                def step(params, ustate, it, states, inputs, rng_key):
                    rng = jax.random.fold_in(rng_key, it)
                    acts, _, _ = self._forward_all(params, states, inputs, False, None, {})
                    x = acts[self.defs[name].inputs[0]]
                    if self._cd is not None:
                        x = x.astype(jnp.float32)
                    p_i = params[name]
                    if use_cd:
                        g, loss = impl.cd_gradients(p_i, x, rng)
                    else:
                        loss, g = jax.value_and_grad(
                            lambda p: impl.pretrain_loss(p, x, rng))(p_i)
                    new_p, new_u = {}, {}
                    for pname, gval in g.items():
                        u, ust = apply_updater(ucfg, gval, ustate[pname], it)
                        new_p[pname] = p_i[pname] - u.astype(p_i[pname].dtype)
                        new_u[pname] = ust
                    return new_p, new_u, it + 1, loss
                return jax.jit(step)

            step = make_step()
            ustate = {n: init_updater_state(ucfg, vv)
                      for n, vv in self.params[name].items()}
            it = jnp.zeros((), jnp.int32)
            rng_key = jax.random.PRNGKey(self.gc.seed + 104729 * (vi + 1))
            loss = float("nan")
            for _ in range(max(1, epochs)):
                for mds in data:
                    mds = self._to_mds(mds)
                    inputs = {n: jnp.asarray(f, self._dtype)
                              for n, f in zip(self.input_names, mds.features)}
                    new_p, ustate, it, loss = step(
                        self.params, ustate, it, self.states, inputs, rng_key)
                    self.params = {**self.params, name: new_p}
            losses[name] = float(loss)
        return losses

    # ------------------------------------------------------- streaming rnn

    def _make_rnn_step(self):
        """Compiled stateful single-step inference over the DAG: every
        vertex's one-timestep forward — recurrent carries included — is
        ONE XLA program, scanned over the burst length for [b, t, f]
        inputs. The round-1..4 version ran a Python loop with one
        dispatch per vertex per timestep, the exact host-loop shape the
        MultiLayerNetwork path killed in PR 2."""
        def one_step(params, rstate, inputs):
            acts: Dict[str, jnp.ndarray] = {}
            new_rstate = dict(rstate)
            for name in self.order:
                v = self.defs[name]
                if v.kind == "input":
                    acts[name] = inputs[name]
                elif v.kind == "layer":
                    impl = self.impls[name]
                    x = acts[v.inputs[0]]
                    if hasattr(impl, "rnn_time_step"):
                        x, new_rstate[name] = impl.rnn_time_step(
                            params[name], x, rstate[name])
                    else:
                        x, _ = impl.forward(params[name], x,
                                            self.states[name], False, None)
                    acts[name] = x
                else:
                    ins = [acts[i] for i in v.inputs]
                    acts[name] = v.vertex.forward(ins, [None] * len(ins))
            return tuple(acts[n] for n in self.output_names), new_rstate

        def burst_scan(params, rstate, seq_inputs, static_inputs):
            # seq_inputs: {name: [t, b, f]} time-major bursts;
            # static_inputs: {name: [b, f]} fed whole every step
            def body(carry, xt):
                outs, carry = one_step(params, carry,
                                       {**static_inputs, **xt})
                return carry, outs
            rstate, outs = jax.lax.scan(body, rstate, seq_inputs)
            return outs, rstate

        return jax.jit(one_step), jax.jit(burst_scan)

    def _init_rnn_state(self, b: int):
        state = {}
        for name in self._recurrent_names():
            n = self.impls[name].conf.n_out
            state[name] = {"h": jnp.zeros((b, n), self._dtype),
                           "c": jnp.zeros((b, n), self._dtype)}
        return state

    def rnn_time_step(self, *features: np.ndarray) -> List[np.ndarray]:
        """Stateful streaming inference over the DAG
        (``ComputationGraph.rnnTimeStep`` :1063 semantics): feed one
        timestep [b, f] per input (or [b, t, f] bursts = one scanned
        XLA program), LSTM vertices keep their carry across calls."""
        xs = [np.asarray(f) for f in features]
        # per-input burst detection: 3-D inputs are [b, t, f] bursts and
        # get time-sliced; 2-D inputs are static and fed whole each step
        bursts = [x.ndim == 3 for x in xs]
        lengths = {x.shape[1] for x, b3 in zip(xs, bursts) if b3}
        if len(lengths) > 1:
            raise ValueError(
                f"rnn_time_step burst inputs disagree on length: {sorted(lengths)}")
        if not hasattr(self, "_rnn_state") or not self._rnn_state:
            self._rnn_state = self._init_rnn_state(xs[0].shape[0])
        key = ("rnn_step",)
        if key not in self._jits:
            self._jits[key] = self._make_rnn_step()
        one, scan = self._jits[key]
        if not any(bursts):
            inputs = {n: jnp.asarray(x, self._dtype)
                      for n, x in zip(self.input_names, xs)}
            outs, self._rnn_state = one(self.params, self._rnn_state, inputs)
            return [np.asarray(o) for o in outs]
        seq = {n: jnp.swapaxes(jnp.asarray(x, self._dtype), 0, 1)
               for (n, x), b3 in zip(zip(self.input_names, xs), bursts)
               if b3}
        static = {n: jnp.asarray(x, self._dtype)
                  for (n, x), b3 in zip(zip(self.input_names, xs), bursts)
                  if not b3}
        outs, self._rnn_state = scan(self.params, self._rnn_state,
                                     seq, static)
        # scan stacks outputs time-major [t, b, ...] → [b, t, ...]
        return [np.asarray(jnp.swapaxes(o, 0, 1)) for o in outs]

    def rnn_clear_previous_state(self) -> None:
        self._rnn_state = {}

    # --------------------------------------------------- generation

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 **kwargs) -> np.ndarray:
        """Fused autoregressive generation over a single-input linear
        layer chain (``nn/generate.py``; the MultiLayerNetwork
        ``generate`` contract): bucketed prefill + one-scan decode with
        on-device sampling. Knobs: ``temperature`` / ``top_k`` /
        ``top_p`` / ``eos_token`` / ``seed``."""
        from deeplearning4j_tpu.nn.generate import generate
        return generate(self, prompt_ids, max_new_tokens, **kwargs)

    # ------------------------------------------------------------- inference

    def outputs(self, *features: np.ndarray,
                features_masks: Optional[Dict[str, np.ndarray]] = None) -> List[np.ndarray]:
        """``ComputationGraph.outputs`` — activations of all graph outputs."""
        inputs = {n: jnp.asarray(f, self._dtype) for n, f in zip(self.input_names, features)}
        fmasks = {k: jnp.asarray(v, self._dtype) for k, v in (features_masks or {}).items()}
        key = ("outputs", tuple(sorted(fmasks)), self._seq_token())
        if key not in self._jits:
            self._jits[key] = jax.jit(
                lambda p, s, i, fm: self._forward_all(p, s, i, False, None, fm)[0])
        acts = self._jits[key](self.params, self.states, inputs, fmasks)
        return [np.asarray(acts[n]) for n in self.output_names]

    def output(self, *features: np.ndarray) -> np.ndarray:
        return self.outputs(*features)[0]

    def infer_output_fn(self):
        """Engine-facing batched output program (the MultiLayerNetwork
        ``infer_output_fn`` contract): a jit-cached pure ``(params,
        states, x, fmask) -> predictions`` for single-input /
        single-output graphs — ParallelInference replicas call it with
        device-pinned param/state copies."""
        if len(self.input_names) != 1 or len(self.output_names) != 1:
            raise ValueError(
                "ParallelInference serves single-input/single-output "
                f"graphs; this one has inputs {self.input_names} and "
                f"outputs {self.output_names} — serve per-output with "
                "outputs() directly")
        key = ("infer_output", self._seq_token())
        if key not in self._jits:
            inp, outn = self.input_names[0], self.output_names[0]

            def fn(p, s, x, fm):
                fmasks = {} if fm is None else {inp: fm}
                acts = self._forward_all(p, s, {inp: x}, False, None, fmasks)[0]
                return acts[outn]

            self._jits[key] = jax.jit(fn)
        return self._jits[key]

    def score(self, data=None) -> float:
        if data is None:
            return float(self._score)  # may be a deferred device scalar
        mds = self._to_mds(data)
        inputs, labels, fmasks, lmasks = self._tensors(mds)
        with span("eval", path="graph_score"):
            return float(self._score_fn(self.params, self.states, inputs, labels,
                                        False, None, fmasks, lmasks)[0])

    # ----------------------------------------------------- flat param views

    def params_flat(self) -> np.ndarray:
        flat, _ = jax.flatten_util.ravel_pytree(self.params)
        return np.asarray(flat)

    def set_params_flat(self, vec: np.ndarray) -> None:
        _, unravel = jax.flatten_util.ravel_pytree(self.params)
        self.params = unravel(jnp.asarray(vec, self._dtype))

    def num_params(self) -> int:
        return int(self.params_flat().shape[0])
