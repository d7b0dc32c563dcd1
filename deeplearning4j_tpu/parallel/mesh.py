"""The mesh plane: one named-axis device mesh + canonical sharding layout.

The mesh is the TPU-native replacement for the reference's cluster
topology (Spark executors / ParallelWrapper threads). Every multi-chip
path in the repo — DP/FSDP/TP training, sequence-parallel ring
attention, the GPipe stage pipeline, sharded embedding training,
multi-host DCN — hangs off the two abstractions here (the GSPMD
discipline, Xu et al.):

- :class:`MeshPlane` owns the named-axis ``jax.sharding.Mesh`` plus a
  :class:`SpecLayout`, and is the ONLY place a raw ``Mesh`` may be
  constructed (``scripts/check_mesh_api.py`` lints the repo for rogue
  mesh construction and for the deprecated experimental ``shard_map``
  import);
- :class:`SpecLayout` maps parameter names → ``PartitionSpec``s. It is
  JSON-serializable, which is what makes checkpoints MESH-PORTABLE: the
  layout rides in the checkpoint manifest and ``restore_checkpoint``
  re-lowers the saved shards onto ANY current mesh (8 → 4 → 1 chips),
  restricting each spec to the axes the new mesh actually has.

Axis convention (canonical names; extension axes ride alongside):

- ``data``  — batch (data parallelism; gradient all-reduce rides ICI)
- ``fsdp``  — parameter/optimizer sharding (ZeRO; ``zero.py`` defaults
  to folding it onto ``data`` so DP+FSDP share one axis)
- ``tp``    — tensor parallelism (``model`` is the accepted legacy
  spelling; both resolve)
- ``seq``   — sequence parallelism (ring attention block axis)
- ``pp``    — pipeline stage axis

Most code should never touch per-device programs: ``jax.jit`` with
sharded inputs (or explicit ``in_shardings``/``out_shardings``) lets
GSPMD insert the collectives. The exceptions — programs whose SEMANTICS
are per-device (ring ppermute schedules, pipeline tick loops, psum'd
scatter-adds) — go through :func:`device_collective`, the one sanctioned
``jax.shard_map`` entry point.

Multi-host: call ``jax.distributed.initialize()`` before ``make_mesh``
and the same code spans hosts — device order follows ``jax.devices()``,
DCN-connected slices become outer mesh dims (``multihost.py`` builds
its global mesh through :func:`mesh_from_grid`).
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deeplearning4j_tpu.monitor import (MESH_AXIS_SIZE_GAUGE,
                                        MESH_DEVICES_GAUGE, get_registry)

#: canonical axis vocabulary (extension axes are allowed; these are the
#: names the stock layouts and MIGRATION.md speak)
CANONICAL_AXES = ("data", "fsdp", "tp", "seq", "pp")

#: accepted legacy spellings → canonical (tensor_parallel.py predates
#: the tp rename; both keep working)
AXIS_ALIASES = {"model": "tp"}


def mesh_from_grid(device_grid, axis_names: Sequence[str]) -> Mesh:
    """Construct a Mesh from an explicit device grid — the ONE raw
    ``Mesh(...)`` call in the repo (the check_mesh_api lint pins this).
    ``multihost.make_multihost_mesh`` routes its DCN×ICI grid through
    here; everyone else should use :func:`make_mesh`."""
    return Mesh(np.asarray(device_grid), tuple(axis_names))


def make_mesh(axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh from {axis: size}. Sizes must multiply to the device
    count; a single ``{"data": N}`` axis is the default (pure DP)."""
    devices = list(devices if devices is not None else jax.devices())
    if axes is None:
        axes = {"data": len(devices)}
    sizes = list(axes.values())
    if int(np.prod(sizes)) != len(devices):
        raise ValueError(f"mesh axes {axes} need {np.prod(sizes)} devices, "
                         f"have {len(devices)}")
    arr = np.asarray(devices).reshape(sizes)
    return mesh_from_grid(arr, tuple(axes.keys()))


def device_collective(fn, mesh: Mesh, in_specs, out_specs,
                      check_vma: bool = True):
    """Map ``fn`` as a per-device program over ``mesh`` — the sanctioned
    entry point for code whose semantics are genuinely per-device
    (``ppermute`` rings, pipeline tick loops, psum'd scatter-adds).
    Anything expressible as global-array math should instead use
    ``jax.jit`` over sharded inputs and let GSPMD derive the
    collectives."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_vma)


# -------------------------------------------------------------- SpecLayout

def _encode_spec(spec: Optional[P]):
    """PartitionSpec → JSON-able: list over dims, each entry None, an
    axis name, or a list of axis names."""
    if spec is None:
        return None
    out = []
    for part in tuple(spec):
        if part is None:
            out.append(None)
        elif isinstance(part, (tuple, list)):
            out.append(list(part))
        else:
            out.append(str(part))
    return out


def _decode_spec(enc) -> Optional[P]:
    if enc is None:
        return None
    return P(*[tuple(e) if isinstance(e, list) else e for e in enc])


def _restrict_dim(part, dim_size: int, mesh: Mesh):
    """Restrict one spec dim entry to the axes ``mesh`` has, dropping it
    entirely when the dim stops being divisible — the re-lowering rule
    that makes a layout portable across mesh shapes."""
    if part is None:
        return None
    names = list(part) if isinstance(part, (tuple, list)) else [part]
    kept = [n for n in names if n in mesh.shape]
    if not kept:
        return None
    total = int(np.prod([mesh.shape[n] for n in kept]))
    if total == 0 or dim_size % total != 0:
        return None
    return tuple(kept) if len(kept) > 1 else kept[0]


class SpecLayout:
    """Parameter name → ``PartitionSpec`` mapping (two-level:
    ``layer → param → spec``; unlisted params are replicated).

    The layout is the serializable half of the mesh plane: it rides in
    sharded-checkpoint manifests (:mod:`util.sharded_checkpoint` writes
    ``layout.json``) so a checkpoint written on one topology can be
    re-lowered onto any other — :meth:`restricted_spec` drops axes the
    target mesh lacks and falls back to replication where a dim stops
    dividing."""

    def __init__(self, specs: Optional[Dict[str, Dict[str, P]]] = None):
        self.specs: Dict[str, Dict[str, P]] = {
            ln: dict(ld) for ln, ld in (specs or {}).items()}

    # ------------------------------------------------------------ access

    def get(self, layer: str, pname: str) -> Optional[P]:
        return self.specs.get(layer, {}).get(pname)

    def set(self, layer: str, pname: str, spec: Optional[P]) -> None:
        if spec is None:
            self.specs.get(layer, {}).pop(pname, None)
        else:
            self.specs.setdefault(layer, {})[pname] = spec

    def __bool__(self) -> bool:
        return any(self.specs.values())

    def __eq__(self, other) -> bool:
        return isinstance(other, SpecLayout) and self.specs == other.specs

    # ------------------------------------------------------- construction

    @classmethod
    def from_params(cls, params) -> "SpecLayout":
        """Read the layout off live arrays: every param placed under a
        non-replicated ``NamedSharding`` contributes its spec. This is
        the save-time truth — whatever sharding the arrays actually
        carry is what the checkpoint records."""
        layout = cls()
        for ln, ld in (params or {}).items():
            for pn, v in ld.items():
                sh = getattr(v, "sharding", None)
                if isinstance(sh, NamedSharding) and tuple(sh.spec):
                    if any(part is not None for part in tuple(sh.spec)):
                        layout.set(ln, pn, sh.spec)
        return layout

    # -------------------------------------------------------- re-lowering

    def restricted_spec(self, layer: str, pname: str, shape,
                        mesh: Mesh) -> P:
        """The spec for (layer, pname) re-lowered onto ``mesh``: axes
        the mesh lacks are dropped, and a dim whose size stops being
        divisible by the (possibly different) axis size falls back to
        replication. Always returns a spec valid on ``mesh``."""
        spec = self.get(layer, pname)
        if spec is None:
            return P()
        shape = tuple(shape)
        parts = list(tuple(spec))[:len(shape)]
        out = [_restrict_dim(part, shape[i], mesh)
               for i, part in enumerate(parts)]
        while out and out[-1] is None:
            out.pop()
        return P(*out)

    def param_shardings(self, params, mesh: Mesh):
        """Per-param ``NamedSharding`` tree over ``mesh`` (the restore
        template + ``jax.jit`` ``in_shardings`` seam), restricted to
        what ``mesh`` can actually carry."""
        return {ln: {pn: NamedSharding(
            mesh, self.restricted_spec(ln, pn, np.shape(v), mesh))
            for pn, v in ld.items()} for ln, ld in params.items()}

    # ------------------------------------------------------ serialization

    def to_payload(self) -> Dict[str, Any]:
        return {ln: {pn: _encode_spec(sp) for pn, sp in ld.items()}
                for ln, ld in self.specs.items()}

    @classmethod
    def from_payload(cls, payload) -> "SpecLayout":
        layout = cls()
        for ln, ld in (payload or {}).items():
            for pn, enc in ld.items():
                layout.set(ln, pn, _decode_spec(enc))
        return layout


# --------------------------------------------------------------- MeshPlane

_ACTIVE_LOCK = threading.Lock()
_ACTIVE_PLANE: list = []  # [MeshPlane] — last-activated, for /healthz


def active_plane() -> Optional["MeshPlane"]:
    """The most recently constructed/activated MeshPlane (what
    ``/healthz`` reports as the process's mesh topology), or None when
    the process never built one (single-device serving)."""
    with _ACTIVE_LOCK:
        return _ACTIVE_PLANE[-1] if _ACTIVE_PLANE else None


@dataclasses.dataclass
class MeshPlane:
    """A named-axis mesh + canonical shardings + SpecLayout — the one
    distributed-plumbing handle (training AND inference slice off the
    same plane). Constructible from an existing ``Mesh`` (the legacy
    ``MeshContext(mesh)`` spelling) or from ``{axis: size}`` dicts via
    :meth:`build`."""

    mesh: Mesh
    layout: SpecLayout = dataclasses.field(default_factory=SpecLayout)

    def __post_init__(self):
        if isinstance(self.mesh, dict):  # MeshPlane({"data": 8}) spelling
            self.mesh = make_mesh(self.mesh)
        with _ACTIVE_LOCK:
            _ACTIVE_PLANE[:] = [self]
        reg = get_registry()
        reg.gauge(MESH_DEVICES_GAUGE,
                  "Devices in the active mesh plane").set(self.devices)
        for axis, size in self.mesh.shape.items():
            reg.gauge(MESH_AXIS_SIZE_GAUGE,
                      "Axis sizes of the active mesh plane",
                      axis=str(axis)).set(int(size))

    @classmethod
    def build(cls, axes: Optional[Dict[str, int]] = None,
              devices: Optional[Sequence] = None,
              layout: Optional[SpecLayout] = None) -> "MeshPlane":
        return cls(make_mesh(axes, devices), layout or SpecLayout())

    # -------------------------------------------------------- topology

    @property
    def devices(self) -> int:
        return int(self.mesh.devices.size)

    def axis_size(self, axis: str) -> int:
        axis = AXIS_ALIASES.get(axis, axis)
        shape = dict(self.mesh.shape)
        for name, size in shape.items():
            if name == axis or AXIS_ALIASES.get(name) == axis:
                return int(size)
        return 1

    def data_axis_size(self) -> int:
        return self.mesh.shape.get("data", 1)

    def topology(self) -> Dict[str, Any]:
        """JSON-able mesh description (``/healthz`` + checkpoint
        manifests speak this shape)."""
        return {"devices": self.devices,
                "axes": {str(k): int(v) for k, v in self.mesh.shape.items()},
                "device_ids": [int(d.id) for d in self.mesh.devices.flat]}

    # ------------------------------------------------------- shardings

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def sharding(self, *spec_parts) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec_parts))

    def batch_sharded(self, ndim: int = 2, axis: str = "data") -> NamedSharding:
        """Shard dim 0 (batch) over ``axis``, replicate the rest."""
        return NamedSharding(self.mesh, P(axis, *([None] * (ndim - 1))))

    def shard_batch(self, *arrays):
        """Place host arrays with batch dim sharded over ``data``
        (the broadcast+partition step of the reference's
        ``NetBroadcastTuple``/repartition plane, done by the runtime)."""
        n = self.data_axis_size()
        out = []
        for a in arrays:
            if a is None:
                out.append(None)
            else:
                if np.shape(a)[0] % n != 0:
                    raise ValueError(
                        f"batch size {np.shape(a)[0]} not divisible by data axis "
                        f"size {n}; pad or trim the batch")
                out.append(jax.device_put(a, self.batch_sharded(np.ndim(a))))
        return out

    # ----------------------------------------------------- collectives

    def device_collective(self, fn, in_specs, out_specs,
                          check_vma: bool = True):
        """Per-device program over THIS plane's mesh (see module-level
        :func:`device_collective`)."""
        return device_collective(fn, self.mesh, in_specs, out_specs,
                                 check_vma=check_vma)

    # ------------------------------------------------- model placement

    def apply(self, model, specs: Optional[Dict[str, Dict[str, P]]] = None
              ) -> "MeshPlane":
        """Place ``model``'s params (+ updater mirror, + states) per the
        layout (``specs`` replaces the layout first; unlisted params are
        replicated) and pin the plane on the model (``model.mesh_plane``)
        — the seam sharded checkpoints and the supervisor read."""
        if specs is not None:
            self.layout = specs if isinstance(specs, SpecLayout) \
                else SpecLayout(specs)
        from deeplearning4j_tpu.parallel.tensor_parallel import apply_shardings
        apply_shardings(model, self.mesh, self.layout.specs, plane=self)
        return self


#: legacy spelling — ``MeshContext(mesh)`` predates the plane; same type.
MeshContext = MeshPlane


# ------------------------------------------------------- serving slices

def slice_planes(width: int, devices: Optional[Sequence] = None,
                 axis: str = "tp") -> list:
    """Partition ``devices`` (default: all) into serving SLICES of
    ``width`` chips — one :class:`MeshPlane` with a single ``tp`` axis
    per slice, in device order. The unit a mesh-sharded serving
    endpoint runs on: a fleet trades ``len(slices)`` replicas against
    ``width`` chips per replica out of the same chip budget."""
    devices = list(devices if devices is not None else jax.devices())
    width = max(1, int(width))
    if len(devices) < width:
        raise ValueError(
            f"slice width {width} needs {width} devices, have "
            f"{len(devices)}")
    return [MeshPlane.build({axis: width}, devices[i:i + width])
            for i in range(0, len(devices) - width + 1, width)]


def serving_slice_layout(net, axis: str = "tp") -> SpecLayout:
    """The COLUMN-ONLY tensor-parallel SpecLayout for a serving slice.

    Every sharded weight is partitioned on a NON-contracting (output)
    dim — Megatron's column half without the row half — so no matmul
    ever reduces across shards: each output element is computed with
    the full contraction in single-device order, and the activation
    all-gathers the seam inserts (``LayerImpl._slice_replicate``) are
    pure data movement. That is what makes sliced serving output
    BITWISE equal to the single-device engine (the house bar), where
    training-style row/column pairing is only ever allclose.

    Covered params: SequenceEmbedding ``W`` (d columns), TransformerBlock
    ``Wqkv``/``Wo``/``W1``/``W2`` (+ paired biases), hidden Dense
    ``W``/``b``. The output head (``impls[-1]``) and all LayerNorm
    params stay replicated — logits must be whole on every chip for
    on-device sampling. MoE blocks are rejected (no serving-slice seam
    for routed experts yet)."""
    from deeplearning4j_tpu.nn.layers.feedforward import BaseDenseImpl
    from deeplearning4j_tpu.nn.layers.transformer import (
        SequenceEmbeddingImpl, TransformerBlockImpl)
    impls = net.impls
    if not isinstance(impls, list):
        impls = [impls[name] for name in net.order
                 if net.defs[name].kind == "layer"]
    layout = SpecLayout()
    for impl in impls[:-1]:  # the head stays replicated
        if isinstance(impl, SequenceEmbeddingImpl):
            layout.set(impl.name, "W", P(None, axis))
        elif isinstance(impl, TransformerBlockImpl):
            if impl.conf.num_experts > 0:
                raise ValueError(
                    "serving_slice_layout has no seam for MoE blocks; "
                    "serve routed-expert nets on single-device replicas")
            layout.set(impl.name, "Wqkv", P(None, axis))
            layout.set(impl.name, "Wo", P(None, axis))
            layout.set(impl.name, "W1", P(None, axis))
            layout.set(impl.name, "b1", P(axis))
            layout.set(impl.name, "W2", P(None, axis))
            layout.set(impl.name, "b2", P(axis))
        elif isinstance(impl, BaseDenseImpl):
            layout.set(impl.name, "W", P(None, axis))
            layout.set(impl.name, "b", P(axis))
    return layout


def apply_serving_slice(net, plane: MeshPlane,
                        layout: Optional[SpecLayout] = None) -> MeshPlane:
    """Turn ``net`` into a SLICE-served model: place its params per the
    (column-only) serving layout over ``plane``'s mesh, pin the plane
    (``net.mesh_plane`` — the PR-9 seam checkpoints read — plus
    ``net.slice_plane`` for the serving engine), and arm the
    bitwise-exactness seam on every layer impl (``_slice_mesh``: the
    impls constrain activations back to replicated before each
    cross-shard reduction, and attention stays on the XLA formulation —
    a Pallas kernel cannot see the mesh). ``apply_shardings`` drops the
    existing jit caches: programs traced before the placement baked no
    constraints.

    The net must be dedicated to this slice (restore the mesh-portable
    checkpoint per slice, or deep-copy): program caches live on the net
    and a slice trace is wrong for an unsliced dispatch."""
    axis = "tp"
    tp = plane.axis_size(axis)
    if tp < 1:
        raise ValueError(f"slice plane needs a {axis!r} axis")
    impls_seq = net.impls
    if not isinstance(impls_seq, list):
        impls_seq = list(impls_seq.values())
    from deeplearning4j_tpu.nn.layers.transformer import \
        TransformerBlockImpl
    for impl in impls_seq:
        if isinstance(impl, TransformerBlockImpl) \
                and impl.conf.num_heads % max(1, tp) != 0:
            # the bitwise seam keeps attention sharded on the HEADS
            # axis; a width that does not divide the heads would make
            # GSPMD re-shard head_dim — whose contraction then reduces
            # across shards. Refuse loudly instead of serving un-exact.
            raise ValueError(
                f"slice width {tp} does not divide num_heads "
                f"{impl.conf.num_heads} ({impl.name}): per-head "
                f"attention must shard whole heads")
    if layout is None:
        layout = serving_slice_layout(net, axis=axis)
    from deeplearning4j_tpu.parallel.tensor_parallel import apply_shardings
    apply_shardings(net, plane.mesh, layout.specs,
                    plane=MeshPlane(plane.mesh, layout))
    impls = net.impls
    if not isinstance(impls, list):
        impls = list(impls.values())
    for impl in impls:
        impl._slice_mesh = net.mesh_plane.mesh
    net.slice_plane = net.mesh_plane
    return net.mesh_plane


# ---------------------------------------------------------- seq-parallel ctx

_SEQ_MESH: list = []  # stack of (mesh, axis)


class sequence_mesh:
    """Context manager activating sequence parallelism: while active,
    AttentionLayer impls route through the ring-attention kernel with
    time sharded over ``axis`` of ``mesh``::

        with sequence_mesh(mesh):          # mesh has a "seq" axis
            net.fit(...)                   # attention now rings over ICI
    """

    def __init__(self, mesh: Mesh, axis: str = "seq"):
        if isinstance(mesh, MeshPlane):
            mesh = mesh.mesh
        if axis not in mesh.shape:
            raise ValueError(f"mesh {dict(mesh.shape)} has no '{axis}' axis")
        self.mesh = mesh
        self.axis = axis

    def __enter__(self):
        _SEQ_MESH.append((self.mesh, self.axis))
        return self

    def __exit__(self, *exc):
        _SEQ_MESH.pop()
        return False


def current_sequence_mesh():
    """(mesh, axis) if sequence parallelism is active, else None."""
    return _SEQ_MESH[-1] if _SEQ_MESH else None


def sequence_mesh_token():
    """Hashable marker of the active sequence-parallel context, for jit
    cache keys: a trace made inside ``sequence_mesh`` bakes the ring
    path in, so cached executables must be keyed on the mesh identity —
    by topology + device ids (NOT ``id(mesh)``, which can be reused
    after garbage collection and would serve a stale executable)."""
    s = current_sequence_mesh()
    if s is None:
        return None
    mesh, axis = s
    return (tuple(mesh.shape.items()),
            tuple(d.id for d in mesh.devices.flat), axis)
