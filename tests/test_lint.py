"""dl4j-analyze: the unified static-analysis engine (ISSUE 15).

Per-rule fixture corpora (tests/lint_fixtures/: one CLEAN and one
SEEDED-VIOLATION file each), the suppression and baseline round-trips,
the legacy ``check_*`` shim contracts, the quick_check section-0
wiring, the EngineShutdown typed-wire fix the typed-wire-raise rule
forced, and — the acceptance bar — a repo-wide ``analyze()`` green
assertion plus the REAL serving-plane lock graph reconstructed and
proven acyclic.
"""

import importlib.util
import json
import os

import pytest

from deeplearning4j_tpu.analysis import (
    analyze,
    all_rules,
    render_json,
    write_baseline,
)
from deeplearning4j_tpu.analysis.engine import Project
from deeplearning4j_tpu.analysis.rules import rule_by_name
from deeplearning4j_tpu.analysis.rules.lock_order import build_lock_graph

_HERE = os.path.dirname(os.path.abspath(__file__))
_ROOT = os.path.dirname(_HERE)
_FIX = os.path.join(_HERE, "lint_fixtures")
_SCRIPTS = os.path.join(_ROOT, "scripts")


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_SCRIPTS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _fixture_findings(rule_name, fixture):
    """Run ONE rule over ONE fixture file (explicit-path project —
    the file is treated as in-package)."""
    path = os.path.join(_FIX, fixture)
    project = Project(_ROOT, paths=[path], rels=[fixture])
    return rule_by_name(rule_name).check(project)


# ------------------------------------------------- per-rule corpora

#: rule -> (expected violation count in the bad fixture, a substring
#: every corpus finding's message must contain)
_CORPUS = {
    "donation-gate": (1, "CPU gate"),
    "mesh-api": (3, ""),
    "metric-name": (1, "dl4j_totally_unpinned_total"),
    "lock-order": (1, "cycle"),
    "hot-path-host-sync": (5, "sync"),
    "recompile-hazard": (4, ""),
    "typed-wire-raise": (2, "typed"),
    "prng-reuse": (3, "consumed more than once"),
}


@pytest.mark.parametrize("rule_name", sorted(_CORPUS))
def test_rule_clean_fixture_passes(rule_name):
    fixture = rule_name.replace("-", "_")
    fixture = {"hot-path-host-sync": "host_sync",
               "recompile-hazard": "recompile",
               "typed-wire-raise": "typed_raise",
               "metric-name": "metric_name",
               "prng-reuse": "prng_reuse",
               "donation-gate": "donation_gate",
               "mesh-api": "mesh_api",
               "lock-order": "lock_order"}[rule_name]
    assert _fixture_findings(rule_name, fixture + "_clean.py") == []


@pytest.mark.parametrize("rule_name", sorted(_CORPUS))
def test_rule_bad_fixture_caught(rule_name):
    stem = {"hot-path-host-sync": "host_sync",
            "recompile-hazard": "recompile",
            "typed-wire-raise": "typed_raise",
            "metric-name": "metric_name",
            "prng-reuse": "prng_reuse",
            "donation-gate": "donation_gate",
            "mesh-api": "mesh_api",
            "lock-order": "lock_order"}[rule_name]
    want_n, want_sub = _CORPUS[rule_name]
    found = _fixture_findings(rule_name, stem + "_bad.py")
    assert len(found) == want_n, [f.render() for f in found]
    for f in found:
        assert f.rule == rule_name
        assert want_sub in f.message


def test_mesh_bad_fixture_flags_all_three_shapes():
    msgs = [f.message
            for f in _fixture_findings("mesh-api", "mesh_api_bad.py")]
    assert any("jax.experimental.shard_map is the deprecated" in m
               for m in msgs)
    assert any("shard_map reference outside" in m for m in msgs)
    assert any("raw Mesh(...)" in m for m in msgs)


def test_lock_order_bad_fixture_names_the_inversion():
    found = _fixture_findings("lock-order", "lock_order_bad.py")
    (f,) = found
    assert "PeerA._lock" in f.message and "PeerB._lock" in f.message
    assert "witness" in f.message


# ------------------------------------------- suppression round-trip

def test_suppression_same_line_and_line_above(tmp_path):
    bad = tmp_path / "sup.py"
    bad.write_text(
        "import jax\n"
        "f = jax.jit(lambda x: x, donate_argnums=(0,))"
        "  # dl4j-lint: disable=donation-gate\n"
        "# dl4j-lint: disable=donation-gate — documented why\n"
        "g = jax.jit(lambda x: x, donate_argnums=(0,))\n"
        "h = jax.jit(lambda x: x, donate_argnums=(0,))\n")
    report = analyze(_ROOT, rules=[rule_by_name("donation-gate")],
                     paths=[str(bad)], rels=["sup.py"])
    by_line = {f.line: f for f in report.findings}
    assert by_line[2].suppressed       # same-line pragma
    assert by_line[4].suppressed       # comment-line-above pragma
    assert not by_line[5].suppressed   # unsuppressed stays NEW
    assert not report.ok


def test_suppression_disable_all(tmp_path):
    bad = tmp_path / "supall.py"
    bad.write_text(
        "import jax\n"
        "f = jax.jit(lambda x: x, donate_argnums=(0,))"
        "  # dl4j-lint: disable=all\n")
    report = analyze(_ROOT, rules=[rule_by_name("donation-gate")],
                     paths=[str(bad)], rels=["supall.py"])
    assert report.ok and report.findings[0].suppressed


# --------------------------------------------- baseline round-trip

def test_baseline_roundtrip(tmp_path):
    tree = tmp_path / "repo"
    tree.mkdir()
    (tree / "bad.py").write_text(
        "import jax\nf = jax.jit(lambda x: x, donate_argnums=(0,))\n")
    baseline = tmp_path / "baseline.json"
    rules = [rule_by_name("donation-gate")]
    first = analyze(str(tree), rules=rules, baseline=str(baseline))
    assert not first.ok and len(first.new) == 1
    write_baseline(str(baseline), first.new)
    again = analyze(str(tree), rules=rules, baseline=str(baseline))
    assert again.ok
    assert [f.baselined for f in again.findings] == [True]
    # the baseline is line-free: editing ABOVE the finding keeps it
    # grandfathered
    (tree / "bad.py").write_text(
        "import jax\n# a new comment shifts the line\n"
        "f = jax.jit(lambda x: x, donate_argnums=(0,))\n")
    moved = analyze(str(tree), rules=rules, baseline=str(baseline))
    assert moved.ok and moved.findings[0].baselined
    # a NEW violation is still caught next to the baselined one
    (tree / "bad.py").write_text(
        "import jax\nf = jax.jit(lambda x: x, donate_argnums=(0,))\n"
        "g = jax.jit(lambda y: y, donate_argnums=(0, 1))\n")
    # note: same (rule, path, message) key — the baseline grandfathers
    # the finding CLASS at that path, which is the documented trade
    third = analyze(str(tree), rules=rules, baseline=str(baseline))
    assert all(f.baselined for f in third.findings)
    entries = json.loads(baseline.read_text())["findings"]
    assert entries and all("note" in e for e in entries)


# ------------------------------------------------ repo-wide greens

def test_repo_wide_analyze_green():
    """THE acceptance bar: zero unsuppressed, unbaselined findings
    across the whole tree, every rule."""
    report = analyze(_ROOT)
    assert report.ok, "\n".join(f.render() for f in report.new)
    # the run actually covered the tree and ran every rule
    assert report.files > 200
    assert len(report.rules) == len(all_rules()) == 8
    # the sweep left its documented marks: sanctioned syncs are
    # suppressed (not silently ignored), accepted hazards baselined
    c = report.counts()
    assert c["suppressed"] >= 10
    assert c["baselined"] == 2


def test_serving_plane_lock_graph_reconstructed_and_acyclic():
    """The lock-order rule sees the REAL serving plane: the known
    load-bearing locks are nodes, the router's request-lock →
    router-lock ordering and the scheduler → pool/cache edges are
    reconstructed, and the whole graph is acyclic."""
    g = build_lock_graph(Project(_ROOT))
    for lock in ("InferenceRouter._lock", "_Routed.lock",
                 "ContinuousDecodeScheduler._lock",
                 "PagedKVCachePool._lock", "PrefixCache._lock",
                 "ModelRegistry._lock", "MetricsRegistry._lock"):
        assert lock in g.nodes, sorted(g.nodes)
    edges = set(g.edges)
    assert ("_Routed.lock", "InferenceRouter._lock") in edges
    assert ("ContinuousDecodeScheduler._lock",
            "PagedKVCachePool._lock") in edges
    assert ("PrefixCache._lock", "PagedKVCachePool._lock") in edges
    assert g.cycles() == []
    # PR-18 event-loop collapse: broker client faults are DEFERRED out
    # of the transport lock, so TcpBroker no longer orders ahead of the
    # metrics locks, and the router's one clock never calls out while
    # holding its condition (no outgoing edges from the loop)
    assert "_RouterLoop._cond" in g.nodes
    assert not any(src == "_RouterLoop._cond" for src, _ in edges)
    assert ("TcpBroker._lock", "Counter._lock") not in edges
    assert ("TcpBroker._lock", "MetricsRegistry._lock") not in edges
    # the committed snapshot tracks the live reconstruction
    with open(os.path.join(_ROOT, "scripts", "lock_graph.json")) as f:
        snap = json.load(f)
    assert set(snap["nodes"]) == set(g.nodes)
    assert {(e["from"], e["to"]) for e in snap["edges"]} == edges
    assert snap["cycles"] == []


# ------------------------------------------------- shims + CLI + QC

def test_legacy_shims_keep_their_contracts(tmp_path):
    donation = _load_script("check_donation_gates")
    mesh = _load_script("check_mesh_api")
    metric = _load_script("check_metric_names")
    assert donation.check_repo(_ROOT) == []
    assert mesh.check_repo(_ROOT) == []
    assert metric.check_repo(_ROOT) == []
    bad = tmp_path / "bad.py"
    bad.write_text("import jax\n"
                   "f = jax.jit(lambda x: x, donate_argnums=(0,))\n")
    assert len(donation.check_file(str(bad))) == 1
    assert donation.main([str(tmp_path)]) == 1
    assert mesh.main([_ROOT]) == 0


def test_analyze_cli_text_json_and_rules(capsys):
    az = _load_script("analyze")
    assert az.main([]) == 0
    out = capsys.readouterr().out
    assert "ok:" in out and "8 rules" in out
    assert az.main(["--json"]) == 0
    data = json.loads(capsys.readouterr().out)
    assert data["ok"] is True
    assert data["counts"]["new"] == 0
    assert az.main(["--list-rules"]) == 0
    listing = capsys.readouterr().out
    for r in all_rules():
        assert r.name in listing
    assert az.main(["--lock-graph"]) == 0
    graph = json.loads(capsys.readouterr().out)
    assert graph["cycles"] == [] and len(graph["nodes"]) > 10
    assert az.main(["--rules", "lock-order,prng-reuse"]) == 0
    capsys.readouterr()


def test_quick_check_section0_fail_fast(monkeypatch):
    stress = _load_script("stress_faultinject")
    # clean tree: section 0 passes and contributes nothing
    assert stress.analysis_section() == []
    # a seeded finding aborts quick_check BEFORE any chaos phase
    ran = []
    monkeypatch.setattr(stress, "_scenario_log",
                        lambda seed: ran.append(seed) or "log")
    monkeypatch.setattr(
        stress, "analysis_section",
        lambda: ["analysis: x.py:1: [lock-order] seeded"])
    out = stress.quick_check(seeds=(0,))
    assert out == ["analysis: x.py:1: [lock-order] seeded"]
    assert ran == []  # fail fast: the battery never ran


def test_render_json_is_stable():
    report = analyze(_ROOT, rules=[rule_by_name("mesh-api")])
    data = json.loads(render_json(report))
    assert set(data) == {"ok", "files", "rules", "counts", "findings"}


# --------------------------------- the typed-wire fix the rule forced

def test_engine_shutdown_is_wire_typed():
    """Satellite: the bare RuntimeErrors the typed-wire-raise rule
    caught on the worker frame paths (engine/scheduler shutdown
    guards) are now EngineShutdown — registered in the wire typed-error
    family, so remote == local by type."""
    from deeplearning4j_tpu.parallel.inference import EngineShutdown
    from deeplearning4j_tpu.serving import wire
    assert issubclass(EngineShutdown, RuntimeError)
    reg = wire._typed_error_registry()
    assert reg["EngineShutdown"] is EngineShutdown
    err = wire.typed_error({"etype": "EngineShutdown",
                            "error": "engine is shut down"})
    assert isinstance(err, EngineShutdown)
    # and it round-trips through a packed error reply
    header, _ = wire.unpack_frame(
        wire.pack_reply("c1", error=EngineShutdown("down")))
    assert header["etype"] == "EngineShutdown"
    assert isinstance(wire.typed_error(header), EngineShutdown)
