#!/usr/bin/env python
"""Mesh-API lint — THIN SHIM over the ``mesh-api`` rule of the unified
static-analysis engine (``deeplearning4j_tpu/analysis/``; run
everything via ``scripts/analyze.py``).

The invariants:

1. **One shard_map call site**: ``jax.shard_map`` may be referenced
   ONLY by ``parallel/mesh.py`` — per-device programs go through its
   one sanctioned ``device_collective`` wrapper — and the deprecated
   ``jax.experimental.shard_map`` shim is an error everywhere.
2. **One mesh factory**: ``Mesh(...)`` construction outside
   ``parallel/mesh.py`` is an error — topology lives on the MeshPlane.
3. **Serving goes through the plane**: inside
   ``deeplearning4j_tpu/serving/`` even ``make_mesh`` /
   ``mesh_from_grid`` calls and ``Mesh`` imports are banned — a
   serving component is HANDED a ``MeshPlane``.

Importable (tier-1 runs :func:`check_repo`) and a CLI::

    python scripts/check_mesh_api.py [root]

Exit 0 when the repo is clean; 1 with one line per violation.
"""

from __future__ import annotations

import os
import sys
from typing import List

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from deeplearning4j_tpu.analysis.engine import Project  # noqa: E402
from deeplearning4j_tpu.analysis.rules.mesh_api import \
    MeshApiRule  # noqa: E402

_RULE = MeshApiRule()


def check_file(path: str, rel: str = "") -> List[str]:
    """Violations ([] = clean) for one file."""
    rel = rel or path
    project = Project(os.path.dirname(path) or ".", paths=[path],
                      rels=[rel])
    m = project.modules[0]
    if m.parse_error is not None:
        return [f"{rel}: unparseable ({m.parse_error})"]
    return [f"{f.path}:{f.line}: {f.message}"
            for f in _RULE.check(project)
            if not m.suppressed(_RULE.name, f.line)]


def check_repo(root: str) -> List[str]:
    """Violations across every ``.py`` file under ``root``."""
    project = Project(root)
    out = []
    for f in sorted(_RULE.check(project),
                    key=lambda f: (f.path, f.line)):
        m = project.by_rel.get(f.path)
        if m is not None and m.suppressed(_RULE.name, f.line):
            continue
        out.append(f"{f.path}:{f.line}: {f.message}")
    return out


def main(argv=None) -> int:
    args = argv if argv is not None else sys.argv[1:]
    root = args[0] if args else _ROOT
    problems = check_repo(root)
    for p in problems:
        print(p, file=sys.stderr)
    if not problems:
        print(f"ok: no stray shard_map and no rogue mesh construction "
              f"under {root}")
    return 1 if problems else 0


if __name__ == "__main__":
    sys.exit(main())
