"""The readings that a ``train_scan_lfm2`` cell's limits are set from, in one
process on the chip: ``calibrate.py``'s procedure with this family's driver
and reference, and its own faults.

    python3 benchmarks/calibrate_lfm2.py --workload <cell> --seeds 12 --control-seeds 3

For each seed the program runs its first dispatch (the window's own call) and
is compared with the plain reference, both given the expert bias that the
set-up calibrated: the LOWER readings. For the first ``--control-seeds`` of
them the reference itself is put in the program's place, computed in fp8 (the
control, and its milder forward-only form), with the loss over the first half
of every row's tokens only (a fault), and with each fault of the mechanisms
(``lfm2_moe_plain.FAULTS``: the combine weights taken from the BIASED scores,
the assignments to a held expert past a CAPACITY of ``t k / E`` a row
DROPPED, the short convolution WITHOUT its input gate): the UPPER readings.
Each seed's line also carries the held experts' share of the first
dispatch's assignments a layer and how many of them the router's input
rounded to bfloat16 would change (``lfm2_moe_plain.route_stats``). Prints one
JSON line per seed and a summary; the limits in ``limits/<cell>.json`` are
then set between the two, as PERF.md records.

Every reading goes through ``correct.judge`` with the cell's limits as they
stand. The exit code is 0 only where every run of the program came out correct
and every control and fault came out not correct.
"""

import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=2_200_000_011)
    ap.add_argument("--rehearsal", action="store_true")
    args = ap.parse_args(argv)

    from benchmarks import correct, run
    from benchmarks.reference import lfm2_moe_plain as plain

    bench = run.load_json(ROOT, "BENCHMARK.json")
    cell = run.load_cell(bench, args.workload, args.rehearsal)
    run.find_device(int(cell["chips"]), args.rehearsal)
    cfg, traffic, limits = cell["config"], cell["traffic"], cell["limits"]
    readings, wrong = {}, []
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        r = cell["driver"].Run(cfg, traffic, limits, seed)
        r.setup()
        tokens, sides = r.tokens, {"program": r.prog}
        r.free()
        routing = r.route()
        follow = lambda **kw: plain.follow(cfg, cfg["train"], seed, tokens,
                                           bias=r.bias, **kw)
        ref = follow()
        if i < args.control_seeds:
            for precision in plain.CONTROLS:
                sides["control_" + precision] = follow(precision=precision)
            sides["half_tokens"] = follow(
                rows_used=int(traffic["seq_len"]) // 2)
            for fault in plain.FAULTS:
                sides[fault] = follow(**{fault: True})
        row = {"seed": seed, "routing": routing,
               "bias_unmoved": r.bias_unmoved}
        for kind, side in sides.items():
            ok, compared = correct.judge(correct.training_gaps(side, ref), limits)
            ok = ok and (kind != "program" or r.bias_unmoved)
            row[kind] = {"correct": ok, **compared}
            readings.setdefault(kind, []).append(compared)
            if ok != (kind == "program"):
                wrong.append(f"{kind} on seed {seed} came out "
                             f"{'correct' if ok else 'not correct'}")
        row["seconds"] = time.perf_counter() - t0
        print(json.dumps(row), flush=True)
    summary = {}
    for kind, rs in readings.items():
        values = {n: [c[n]["value"] for c in rs] for n in rs[0]}
        summary[kind] = {n: {"min": min(v), "max": max(v),
                             "limit": rs[0][n]["limit"]}
                         for n, v in values.items()}
    print(json.dumps({"summary": summary, "wrong": wrong}), flush=True)
    for line in wrong:
        print(line, file=sys.stderr)
    return 1 if wrong else 0


if __name__ == "__main__":
    sys.exit(main())
