#!/usr/bin/env python
"""Perf-regression trend gate over a directory of bench rounds.

A round is one ``BENCH_r<NN>.json`` — the raw driver record ``{"n",
"cmd", "rc", "tail", "parsed"}`` where ``parsed`` is ``bench.py``'s
stdout JSON (``schema_version`` + headline + ``sub_benchmarks``). The
repo itself holds none any more (the driver's ``PERF_LEDGER.jsonl``
replaced them); point ``--history`` at wherever rounds are kept. This
script turns that history into per-metric trend series and GATES a
candidate payload against them:

- **history** — every ``BENCH_r*.json`` in ``--history`` (default:
  repo root), ordered by round number; malformed rounds fail loudly
  (a gate that skips what it cannot read is not a gate);
- **candidate** — ``--fresh FILE`` (a saved ``bench.py`` stdout JSON),
  or by default the LATEST history round judged against the rounds
  before it — so the committed history itself must stay green;
- **noise band** — per metric, the trailing ``--window`` prior values
  give (mean, population stddev); the candidate regresses when it
  falls below ``mean - max(threshold·mean, nsigma·stddev)``. Every
  ``value`` here is a throughput (tokens/sec, TFLOP/s, examples/sec —
  higher is better); latencies ride inside sub-payloads and are not
  gated;
- **TREND.md** — the per-metric table (prior window, band floor,
  candidate, delta, verdict) is rewritten on every gating run;
- exit status: 0 green, 1 regression, 2 malformed history/candidate.

``--check`` is the schema-only mode ``stress_faultinject.quick_check``
wires in: it validates every committed round's shape AND replays a
deterministic synthetic fixture through the gate logic (an injected
regression must flag, a flat series must pass) — no bench run, no
TREND.md rewrite, seconds not minutes.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import re
import sys
from typing import Any, Dict, List, Optional, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HISTORY_PATTERN = "BENCH_r*.json"
_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")

#: schema_version values this gate knows how to diff (bench.py's
#: BENCH_SCHEMA_VERSION). Older committed rounds predate the field —
#: absent means "version 1 shape", which is what they are.
KNOWN_SCHEMA_VERSIONS = (1,)

DEFAULT_WINDOW = 4
DEFAULT_THRESHOLD = 0.10
DEFAULT_NSIGMA = 3.0


class TrendError(Exception):
    """Malformed history/candidate — exit 2, never a silent skip."""


# ----------------------------------------------------------- loading

def _validate_payload(payload: Any, where: str) -> Dict[str, Any]:
    """One bench.py stdout payload: required shape or TrendError."""
    if not isinstance(payload, dict):
        raise TrendError(f"{where}: payload is {type(payload).__name__}, "
                         "expected object")
    for key, kinds in (("metric", (str,)), ("value", (int, float)),
                       ("unit", (str,))):
        if key not in payload:
            raise TrendError(f"{where}: missing required key {key!r}")
        if not isinstance(payload[key], kinds):
            raise TrendError(
                f"{where}: key {key!r} is "
                f"{type(payload[key]).__name__}, expected "
                f"{'/'.join(k.__name__ for k in kinds)}")
    sv = payload.get("schema_version", 1)
    if sv not in KNOWN_SCHEMA_VERSIONS:
        raise TrendError(f"{where}: schema_version {sv!r} unknown to "
                         f"this gate (knows {KNOWN_SCHEMA_VERSIONS})")
    subs = payload.get("sub_benchmarks", {})
    if not isinstance(subs, dict):
        raise TrendError(f"{where}: sub_benchmarks is "
                         f"{type(subs).__name__}, expected object")
    for name, sub in subs.items():
        if not isinstance(sub, dict):
            raise TrendError(f"{where}: sub_benchmarks[{name!r}] is "
                             f"{type(sub).__name__}, expected object")
        if "error" in sub:
            continue  # a failed sub-bench carries its error, no value
        if not isinstance(sub.get("value"), (int, float)):
            raise TrendError(
                f"{where}: sub_benchmarks[{name!r}].value is "
                f"{type(sub.get('value')).__name__}, expected number")
    return payload


def load_history(history_dir: str) -> List[Tuple[int, Dict[str, Any]]]:
    """Every committed round as (round_number, validated payload),
    ascending. Rounds whose bench run itself failed (rc != 0 or no
    parsed payload) are malformed history — fail, don't skip."""
    rounds: List[Tuple[int, Dict[str, Any]]] = []
    for path in glob.glob(os.path.join(history_dir, HISTORY_PATTERN)):
        m = _ROUND_RE.search(path)
        if m is None:
            continue
        n = int(m.group(1))
        with open(path) as f:
            rec = json.load(f)
        if not isinstance(rec, dict) or "parsed" not in rec:
            raise TrendError(f"{path}: not a driver record "
                             "(missing 'parsed')")
        rounds.append((n, _validate_payload(rec["parsed"], path)))
    rounds.sort()
    return rounds


def extract_metrics(payload: Dict[str, Any]) -> Dict[str, float]:
    """Gated series from one payload: one entry per clean sub-benchmark
    (keyed by sub name — stable across rounds even when the headline
    metric rotates) plus the headline under ``headline``."""
    out: Dict[str, float] = {"headline": float(payload["value"])}
    for name, sub in sorted((payload.get("sub_benchmarks") or {}).items()):
        if isinstance(sub, dict) and "error" not in sub \
                and isinstance(sub.get("value"), (int, float)):
            out[name] = float(sub["value"])
    return out


# ------------------------------------------------------------- gating

def gate_metric(priors: List[float], fresh: float,
                threshold: float, nsigma: float) -> Dict[str, Any]:
    """One metric's verdict. The band floor is
    ``mean - max(threshold·mean, nsigma·stddev)``: the fractional
    threshold catches regressions on quiet series, the sigma term
    widens the band for series whose round-to-round history is noisy
    (each growth round changes the code — honest noise, not jitter)."""
    mean = sum(priors) / len(priors)
    var = sum((v - mean) ** 2 for v in priors) / len(priors)
    std = math.sqrt(var)
    band = max(threshold * abs(mean), nsigma * std)
    floor = mean - band
    delta = (fresh - mean) / mean if mean else 0.0
    return {"priors": list(priors), "mean": mean, "stddev": std,
            "floor": floor, "fresh": fresh, "delta_frac": delta,
            "regressed": fresh < floor}


def gate(history: List[Tuple[int, Dict[str, Any]]],
         fresh_payload: Dict[str, Any], window: int,
         threshold: float, nsigma: float) -> Dict[str, Dict[str, Any]]:
    """Every metric present in BOTH the candidate and ≥2 prior rounds
    gets a verdict; single-occurrence metrics (a brand-new sub-bench)
    have no trend yet and report ``new`` instead of a verdict."""
    series: Dict[str, List[float]] = {}
    for _, payload in history:
        for name, value in extract_metrics(payload).items():
            series.setdefault(name, []).append(value)
    fresh = extract_metrics(fresh_payload)
    report: Dict[str, Dict[str, Any]] = {}
    for name, value in sorted(fresh.items()):
        priors = series.get(name, [])[-window:]
        if len(priors) < 2:
            report[name] = {"fresh": value, "new": True,
                            "regressed": False}
            continue
        report[name] = gate_metric(priors, value, threshold, nsigma)
    return report


# ------------------------------------------------------------ TREND.md

def render_trend_md(report: Dict[str, Dict[str, Any]],
                    rounds: List[int], window: int, threshold: float,
                    nsigma: float, candidate_label: str) -> str:
    lines = [
        "# Bench trend",
        "",
        f"Candidate **{candidate_label}** gated against the trailing "
        f"{window}-round window of committed history "
        f"(rounds {', '.join(f'r{n:02d}' for n in rounds)}).",
        "",
        f"Noise band per metric: `mean - max({threshold:.0%}·mean, "
        f"{nsigma:g}σ)` over the prior window; a candidate below the "
        "floor is a regression (all gated values are throughputs — "
        "higher is better).",
        "",
        "| metric | prior mean | band floor | candidate | delta | "
        "verdict |",
        "|---|---|---|---|---|---|",
    ]
    for name, r in sorted(report.items()):
        if r.get("new"):
            lines.append(f"| {name} | — | — | {r['fresh']:.4g} | — | "
                         "new (no trend yet) |")
            continue
        verdict = "**REGRESSED**" if r["regressed"] else "ok"
        lines.append(
            f"| {name} | {r['mean']:.4g} | {r['floor']:.4g} | "
            f"{r['fresh']:.4g} | {r['delta_frac']:+.1%} | {verdict} |")
    regressed = sorted(n for n, r in report.items() if r["regressed"])
    lines += ["", ("Regressions: " + ", ".join(regressed)
                   if regressed else "No regressions."), ""]
    return "\n".join(lines)


# -------------------------------------------------------- check mode

def _fixture_check(window: int) -> List[str]:
    """Deterministic gate-logic replay: the synthetic injected
    regression MUST flag and the flat series MUST pass, or the gate's
    own logic has rotted. Pure arithmetic — no bench run."""
    problems: List[str] = []
    flat = [100.0, 101.0, 99.0, 100.5][-window:]
    ok = gate_metric(flat, 100.0, DEFAULT_THRESHOLD, DEFAULT_NSIGMA)
    if ok["regressed"]:
        problems.append("fixture: flat series (100,101,99,100.5 -> "
                        "100.0) flagged as regression")
    injected = gate_metric(flat, 60.0, DEFAULT_THRESHOLD, DEFAULT_NSIGMA)
    if not injected["regressed"]:
        problems.append("fixture: injected -40% regression "
                        "(priors ~100 -> 60.0) NOT flagged")
    improved = gate_metric(flat, 140.0, DEFAULT_THRESHOLD, DEFAULT_NSIGMA)
    if improved["regressed"]:
        problems.append("fixture: +40% improvement flagged as "
                        "regression (gate must be one-sided)")
    return problems


def run_check(history_dir: str, window: int) -> int:
    """--check: committed-history schema validation + the gate-logic
    fixture. Prints one line per problem; exit 0 clean, 2 otherwise."""
    problems: List[str] = []
    try:
        rounds = load_history(history_dir)
        if not rounds:
            problems.append(f"no {HISTORY_PATTERN} history found in "
                            f"{history_dir}")
    except (TrendError, json.JSONDecodeError) as e:
        problems.append(str(e))
        rounds = []
    problems.extend(_fixture_check(window))
    if problems:
        for p in problems:
            print(f"bench_trend --check: {p}")
        return 2
    print(f"bench_trend --check: {len(rounds)} committed rounds valid, "
          "gate fixture green")
    return 0


# --------------------------------------------------------------- main

def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--history", default=REPO_ROOT,
                    help="directory holding BENCH_r*.json "
                    "(default: repo root)")
    ap.add_argument("--fresh", default=None,
                    help="candidate payload: a saved bench.py stdout "
                    "JSON file (default: gate the latest committed "
                    "round against the rounds before it)")
    ap.add_argument("--out", default=None,
                    help="TREND.md path (default: <history>/TREND.md)")
    ap.add_argument("--window", type=int, default=DEFAULT_WINDOW,
                    help="prior rounds in the noise band "
                    f"(default {DEFAULT_WINDOW})")
    ap.add_argument("--threshold", type=float, default=DEFAULT_THRESHOLD,
                    help="fractional regression threshold "
                    f"(default {DEFAULT_THRESHOLD})")
    ap.add_argument("--nsigma", type=float, default=DEFAULT_NSIGMA,
                    help="stddev multiplier widening the band "
                    f"(default {DEFAULT_NSIGMA})")
    ap.add_argument("--check", action="store_true",
                    help="schema-only: validate committed history + "
                    "replay the gate-logic fixture (no gating, no "
                    "TREND.md)")
    args = ap.parse_args(argv)

    if args.check:
        return run_check(args.history, args.window)

    try:
        rounds = load_history(args.history)
        if args.fresh is not None:
            with open(args.fresh) as f:
                fresh_payload = _validate_payload(json.load(f),
                                                  args.fresh)
            label = os.path.basename(args.fresh)
            history = rounds
        else:
            if len(rounds) < 2:
                raise TrendError(
                    f"need >=2 committed rounds to gate the latest "
                    f"(found {len(rounds)} in {args.history})")
            n, fresh_payload = rounds[-1]
            label = f"r{n:02d} (latest committed round)"
            history = rounds[:-1]
        if not history:
            raise TrendError("no prior rounds to trend against")
    except (TrendError, json.JSONDecodeError, OSError) as e:
        print(f"bench_trend: {e}", file=sys.stderr)
        return 2

    report = gate(history, fresh_payload, args.window,
                  args.threshold, args.nsigma)
    out_path = args.out or os.path.join(args.history, "TREND.md")
    md = render_trend_md(report, [n for n, _ in history], args.window,
                         args.threshold, args.nsigma, label)
    with open(out_path, "w") as f:
        f.write(md)

    regressed = sorted(n for n, r in report.items() if r["regressed"])
    gated = sum(1 for r in report.values() if not r.get("new"))
    print(f"bench_trend: {gated} metrics gated, "
          f"{len(report) - gated} new, "
          f"{len(regressed)} regressed -> {out_path}")
    for name in regressed:
        r = report[name]
        print(f"  REGRESSED {name}: {r['fresh']:.4g} < floor "
              f"{r['floor']:.4g} (prior mean {r['mean']:.4g}, "
              f"{r['delta_frac']:+.1%})")
    return 1 if regressed else 0


if __name__ == "__main__":
    sys.exit(main())
