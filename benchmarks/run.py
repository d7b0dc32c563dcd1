"""The benchmark's one command.

    python3 benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is data found by the names in
``BENCHMARK.json``: ``configs/<config>.json``, ``traffic/<traffic>.json`` (which
names its driver, ``drivers/<driver>.py``), ``limits/<cell>.json`` and, for a
traced run, one reader ``layer_metrics/<metric>.py`` for each per-layer metric
that lists the cell. The last line of standard output is the result.
"""

import time

T_START = time.time()  # as near to the start of the process as Python gets

import argparse
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str):
    """``benchmarks/<kind>/<name>.py``, by path: a metric's name may hold
    characters that a module's may not."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"{kind[:-1]} {name!r} has no file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.{kind}.{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(bench: dict, workload: str, rehearsal: bool = False) -> dict:
    """The cell's entry with its configuration, traffic and limits read in,
    and its driver's module under ``driver``. ``rehearsal`` has the driver
    shrink the cell to a tiny copy for the CPU."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise SystemExit(f"no workload {workload!r} in BENCHMARK.json; "
                         f"there are: {sorted(cells)}")
    cell = dict(cells[workload])
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    cell["config"] = load_json(ROOT, conf["file"])
    cell["traffic"] = load_json(HERE, "traffic", cell["traffic"] + ".json")
    cell["limits"] = load_json(HERE, "limits", workload + ".json")
    cell["driver"] = load_module("drivers", cell["traffic"]["driver"])
    if rehearsal:
        cell["driver"].rehearse(cell)
    return cell


def metrics_of(bench: dict, kind: str, workload: str):
    """The cell's metrics of one kind: those that list it, or list nothing."""
    return [m for m in bench[kind]
            if "workloads" not in m or workload in m["workloads"]]


def find_device(chips: int, rehearsal: bool) -> dict:
    """The device as JAX reports it. No accelerator, or fewer chips than the
    cell asks for, ends the run without a result."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearsal:
        return info
    if info["platform"] == "cpu":
        raise SystemExit("no accelerator: JAX reports the CPU only. "
                         "(--rehearsal runs a tiny copy here, not a result.)")
    if len(devs) < chips:
        raise SystemExit(f"the cell asks for {chips} chips, JAX reports "
                         f"{len(devs)}")
    return info


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="a tiny copy of the cell on the CPU: finds wrong "
                         "paths and arguments, and is not a chip result")
    args = ap.parse_args(argv)

    bench = load_json(ROOT, "BENCHMARK.json")
    cell = load_cell(bench, args.workload, args.rehearsal)
    device = find_device(int(cell["chips"]), args.rehearsal)
    to_chip_s = time.time() - T_START  # imports and the runtime's start
    peaks = load_json(HERE, "peaks.json")
    if not args.rehearsal and device["kind"] not in peaks:
        raise SystemExit(f"no published peaks for {device['kind']!r} in "
                         f"benchmarks/peaks.json")

    out = cell["driver"].run(cell, args.seed, args.seconds, bool(args.trace),
                             T_START)

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": out["correct"], "attempted": out["attempted"],
              "failed": out["failed"], "metrics": {}, "device": device}
    notes = {}
    if args.trace:
        trace = out["trace"]
        device["busy_s"] = trace.busy_s
        device["window_s"] = out["window_s"]
        for m in metrics_of(bench, "per_layer", args.workload):
            got = load_module("layer_metrics", m["name"]).read(
                trace, cell, out["window"], peaks.get(device["kind"]))
            if got is None:
                continue  # nothing to read: the metric is left out
            result["metrics"][m["name"]] = {"value": got.pop("value"),
                                            "unit": m["unit"]}
            if got:
                notes[m["name"]] = got
        top = trace.top_groups(10)
        result["breakdown"] = {"device_ops": top,
                               "idle_gaps": trace.idle_gaps(10)}
        notes["device_op_events"] = {g: trace.count_by_group[g] for g, _ in top}
    else:
        for m in metrics_of(bench, "end_to_end", args.workload):
            result["metrics"][m["name"]] = {
                "value": out["end_to_end"][m["name"]], "unit": m["unit"]}
    if args.rehearsal:
        # a CPU number never stands under a device metric's name
        result["rehearsal"] = "rehearsal on the CPU at a tiny size, not a chip result"
        result["metrics"] = {"rehearsal_cpu." + k: v
                             for k, v in result["metrics"].items()}
    result["to_chip_s"] = to_chip_s
    result["extra"] = out.get("extra", {})  # the driver's own, copied as it is
    if notes:
        result["notes"] = notes
    result["compared"] = out["compared"]  # last: the record keeps the end

    for name, c in out["compared"].items():
        print(f"compared {name}: {c['value']:.6g} (limit {c['limit']:.6g}"
              + (f", at {c['at']})" if "at" in c else ")"), file=sys.stderr)
    print(f"correct: {out['correct']}  attempted: {out['attempted']}  "
          f"failed: {out['failed']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
