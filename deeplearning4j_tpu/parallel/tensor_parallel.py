"""Tensor parallelism — parameter sharding rules over the ``model`` axis.

No reference counterpart (SURVEY.md §2.6 item 5: the reference has no
tensor/model parallelism); this is the mesh-axis extension of §7.7.

Mechanism: the SAME compiled train step, with parameters placed under
``NamedSharding``s instead of replicated — XLA's SPMD partitioner
splits the matmuls over ``model`` and inserts the activation
collectives. Megatron-style pairing: alternate column/row sharding on
consecutive dense layers so the intermediate activation stays sharded
and only one all-reduce per pair is needed — XLA derives this from the
parameter specs.
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dense_tp_specs(layer_names, alternate: bool = True,
                   axis: str = "model") -> Dict[str, Dict[str, P]]:
    """Column/row-alternating PartitionSpecs for a dense stack.

    Even layers: W [in, out] column-sharded P(None, axis), b sharded
    P(axis). Odd layers: W row-sharded P(axis, None), b replicated
    (the Megatron pattern). Output layers are usually left replicated
    (small) — pass them through ``replicated_names``.
    """
    specs = {}
    for i, name in enumerate(layer_names):
        if alternate and i % 2 == 1:
            specs[name] = {"W": P(axis, None), "b": P()}
        else:
            specs[name] = {"W": P(None, axis), "b": P(axis)}
    return specs


def conv_tp_specs(layer_names, axis: str = "model") -> Dict[str, Dict[str, P]]:
    """Output-channel sharding for conv kernels [kh, kw, in, out]."""
    return {n: {"W": P(None, None, None, axis), "b": P(axis)} for n in layer_names}


def moe_ep_specs(layer_names, axis: str = "expert") -> Dict[str, Dict[str, P]]:
    """Expert-parallel PartitionSpecs for ``MoELayer``s: shard the
    leading expert dim of the expert weights over ``axis``; the router
    stays replicated. XLA lowers the dispatch/combine einsums to the
    canonical MoE all-to-all over the mesh."""
    return {name: {"Wg": P(), "W1": P(axis, None, None), "b1": P(axis, None),
                   "W2": P(axis, None, None), "b2": P(axis, None)}
            for name in layer_names}


def lstm_tp_specs(layer_names, axis: str = "model") -> Dict[str, Dict[str, P]]:
    """Gate-dimension sharding for LSTM packed weights.

    NOTE: the 4n gate axis is sharded, which also shards the hidden
    state h [b, n] implicitly through Wr [n, 4n] -> P(None, axis); XLA
    all-gathers h once per step of the scan.
    """
    return {n: {"Wx": P(None, axis), "Wr": P(None, axis), "b": P(axis),
                "wci": P(axis), "wcf": P(axis), "wco": P(axis)}
            for n in layer_names}


def _placer(mesh: Mesh, specs: Dict[str, Dict[str, P]]):
    repl = NamedSharding(mesh, P())

    def place(layer, pname, v):
        spec = specs.get(layer, {}).get(pname)
        return jax.device_put(v, NamedSharding(mesh, spec) if spec is not None else repl)

    return place


def place_updater_state(model, mesh: Mesh,
                        specs: Dict[str, Dict[str, P]]) -> None:
    """Shard the updater-state mirror of each parameter per ``specs``
    (unlisted -> replicated). Used by apply_shardings and ZeRO-1."""
    place = _placer(mesh, specs)
    upd = model.opt_state["updater"]
    model.opt_state["updater"] = {
        ln: {pn: jax.tree.map(lambda s: place(ln, pn, s), st) for pn, st in ld.items()}
        for ln, ld in upd.items()}
    model.opt_state["step"] = jax.device_put(
        model.opt_state["step"], NamedSharding(mesh, P()))


def apply_shardings(model, mesh: Mesh,
                    specs: Dict[str, Dict[str, P]], plane=None) -> None:
    """Place the model's params (and matching updater state) according to
    ``specs``; unlisted params are replicated. Subsequent ``fit`` calls
    compile SPMD with these placements. The layout is pinned on the
    model as ``model.mesh_plane`` (a :class:`~..mesh.MeshPlane`) — the
    seam mesh-portable checkpoints and the supervisor read — and on
    every layer impl as ``_mesh``, which per-device kernels map over
    (``nn/layers/attention.py``). Existing jit caches are dropped:
    programs traced before the placement baked no mesh in."""
    from deeplearning4j_tpu.parallel.mesh import MeshPlane, SpecLayout

    place = _placer(mesh, specs)
    model.params = {ln: {pn: place(ln, pn, v) for pn, v in ld.items()}
                    for ln, ld in model.params.items()}
    place_updater_state(model, mesh, specs)
    model.states = jax.device_put(model.states, NamedSharding(mesh, P()))
    if plane is None:
        plane = MeshPlane(mesh, SpecLayout(specs))
    model.mesh_plane = plane
    impls = model.impls
    for impl in (impls if isinstance(impls, list) else impls.values()):
        impl._mesh = mesh
    model._jits.clear()
    model.__dict__.pop("_generator", None)
