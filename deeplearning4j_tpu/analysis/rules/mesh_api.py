"""mesh-api — no deprecated ``jax.experimental.shard_map``, one
``jax.shard_map`` call site, one mesh factory, serving takes a
MeshPlane (engine port of ``scripts/check_mesh_api.py``)."""

from __future__ import annotations

import ast
import os
from typing import List

from deeplearning4j_tpu.analysis.engine import (Finding, Project, Rule,
                                                attr_chain)

#: the one file allowed to import/construct the raw primitives.
ALLOWED_FILES = ("parallel/mesh.py",)

#: directories where even the sanctioned low-level mesh factories are
#: banned: serving code takes a MeshPlane, it never builds topology.
SERVING_DIRS = ("deeplearning4j_tpu/serving/",)
SERVING_BANNED_CALLS = ("make_mesh", "mesh_from_grid")


#: the deprecated shim (``check_rep=`` spelling) — banned everywhere,
#: ``parallel/mesh.py`` included: ``jax.shard_map(..., check_vma=)`` is
#: the API of the installed JAX.
_DEPRECATED = "jax.experimental.shard_map"
_DEPRECATED_MSG = (
    "jax.experimental.shard_map is the deprecated shim — per-device "
    "programs go through parallel.mesh.device_collective, which calls "
    "jax.shard_map(..., check_vma=)")


def _in_serving(rel: str) -> bool:
    rel = rel.replace(os.sep, "/")
    return any(d in rel for d in SERVING_DIRS)


def _is_mesh_ctor(node: ast.Call) -> bool:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id == "Mesh"
    if isinstance(f, ast.Attribute):
        return f.attr == "Mesh"
    return False


class MeshApiRule(Rule):
    name = "mesh-api"
    description = ("no jax.experimental.shard_map (deprecated shim), "
                   "jax.shard_map and raw Mesh() only in "
                   "parallel/mesh.py, serving/ is handed a MeshPlane")

    def check(self, project: Project) -> List[Finding]:
        out: List[Finding] = []
        for m in project.modules:
            if m.tree is None:
                continue
            allowed = any(m.rel.endswith(a) for a in ALLOWED_FILES)
            for node in ast.walk(m.tree):
                if isinstance(node, ast.Attribute):
                    chain = attr_chain(node)
                    if chain.startswith(_DEPRECATED):
                        out.append(Finding(
                            self.name, m.rel, node.lineno, _DEPRECATED_MSG))
                    elif "shard_map" in chain.split(".") and not allowed:
                        out.append(Finding(
                            self.name, m.rel, node.lineno,
                            "shard_map reference outside "
                            "parallel/mesh.py — per-device programs go "
                            "through parallel.mesh.device_collective"))
                elif isinstance(node, (ast.Import, ast.ImportFrom)):
                    mod = getattr(node, "module", "") or ""
                    names = [a.name for a in node.names]
                    if mod.startswith(_DEPRECATED) or any(
                            n.startswith(_DEPRECATED) for n in names):
                        out.append(Finding(
                            self.name, m.rel, node.lineno, _DEPRECATED_MSG))
                        continue
                    if allowed:
                        continue
                    if "shard_map" in mod or \
                            any("shard_map" in n for n in names):
                        out.append(Finding(
                            self.name, m.rel, node.lineno,
                            "shard_map import outside parallel/mesh.py "
                            "— per-device programs go through "
                            "parallel.mesh.device_collective"))
                    if _in_serving(m.rel) and (
                            any(n == "Mesh" or n.endswith(".Mesh")
                                for n in names)
                            or any(n in SERVING_BANNED_CALLS
                                   for n in names)):
                        out.append(Finding(
                            self.name, m.rel, node.lineno,
                            "mesh-topology import inside serving/ — "
                            "serving components take a MeshPlane "
                            "(MeshPlane.build), they never assemble "
                            "raw meshes"))
                elif isinstance(node, ast.Call) and _is_mesh_ctor(node) \
                        and not allowed:
                    out.append(Finding(
                        self.name, m.rel, node.lineno,
                        "raw Mesh(...) construction outside "
                        "parallel/mesh.py — build meshes via "
                        "parallel.mesh (make_mesh / mesh_from_grid / "
                        "MeshPlane)"))
                elif isinstance(node, ast.Call) and _in_serving(m.rel):
                    f = node.func
                    callee = f.id if isinstance(f, ast.Name) else (
                        f.attr if isinstance(f, ast.Attribute) else "")
                    if callee in SERVING_BANNED_CALLS:
                        out.append(Finding(
                            self.name, m.rel, node.lineno,
                            f"{callee}() inside serving/ — the "
                            "sharded-serving code goes through "
                            "MeshPlane (MeshPlane.build / a plane "
                            "handed in), never the low-level mesh "
                            "factories"))
        return out
