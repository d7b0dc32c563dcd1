"""Profile the GPT train step on the real chip and attribute MFU.

Usage: python scripts/profile_gpt.py [--trace] [--d-model N] ...
       python scripts/profile_gpt.py --cell <benchmark cell> [--seed N]
Prints tokens/sec + MFU; with --trace, aggregates device op self-times
from the captured trace by op group (the flash kernels,
fusions, copies) — the BASELINE.md attribution workflow — then by the
program's named scopes, and the device's idle time by the program's
host spans (util/profiler.scope_seconds / gaps_by_host_span). ``--cell`` traces a
cell of ``BENCHMARK.json`` as its driver runs it (the driver's run object:
its net, its weights from the seed, its staged tokens) and prints the same
tables, for any family the benchmark has.
"""
import argparse
import collections
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.monitor import (FLASH_PATH_COUNTER,
                                        FLASH_WINDOWED_COUNTER,
                                        MOE_PATH_COUNTER,
                                        SELSCAN_PATH_COUNTER, SSD_PATH_COUNTER,
                                        get_registry)
from deeplearning4j_tpu.monitor import phase_breakdown
from deeplearning4j_tpu.nn.scan_dispatch import step_program_report
from deeplearning4j_tpu.nn.multilayer import (HYBRID_STEP_SCOPES,
                                              LFM2_STEP_SCOPES,
                                              LOOPED_STEP_SCOPES,
                                              SAMBAY_STEP_SCOPES, STEP_SCOPES)
from deeplearning4j_tpu.util import profiler
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
from deeplearning4j_tpu.util.device import device_peaks


def aggregate_trace(log_dir):
    """Aggregate XLA-Ops-line SELF times (events nest: while > fusion —
    ``util/profiler.self_times`` walks each line with a stack and
    subtracts child time) from the newest ``.xplane.pb`` under
    ``log_dir``. Returns [(group_name, total_us, count)] sorted by time,
    where group_name is the HLO instruction's name with trailing .N
    instance suffixes stripped (``fusion``, ``copy``, ``jvp_flash_fwd_``
    …; the capture carries no hlo_category per event)."""
    agg = collections.Counter()
    cnt = collections.Counter()
    for group, _, _, ns in profiler.scoped_self_times(
            profiler.load_trace(log_dir), ()):
        agg[group] += ns / 1e3
        cnt[group] += 1
    return sorted(((n, d, cnt[n]) for n, d in agg.items()),
                  key=lambda t: -t[1])


def print_scope_tables(log_dir, steps, scopes=STEP_SCOPES):
    """Device self time by the program's named scopes (and which op
    groups each scope owns), then the device's idle time by what the
    host was doing (``dl4j/`` spans on the capture's host plane)."""
    profile = profiler.load_trace(log_dir)
    ops = profiler.op_names(log_dir)
    rows = profiler.scoped_self_times(profile, scopes, ops)
    total = sum(ns for *_, ns in rows) or 1
    by_scope = collections.Counter()
    groups = collections.defaultdict(collections.Counter)
    for group, scope, pass_, ns in rows:
        key = f"{scope}/{pass_}" if scope else "(no scope)"
        by_scope[key] += ns
        groups[key][group] += ns
    print(f"\ndevice self time by scope ({len(ops)} op names in the "
          f"capture; by fusion: XLA gives a fusion one instruction's name):")
    for key, ns in by_scope.most_common():
        top = ", ".join(f"{g} {v / steps / 1e6:.2f}"
                        for g, v in groups[key].most_common(3))
        print(f"  {ns / steps / 1e6:8.3f} ms/step {100 * ns / total:5.1f}%  "
              f"{key:22s} [{top}]")
    claimed = total - by_scope["(no scope)"]
    print(f"  no scope claimed {100 * (1 - claimed / total):.1f}% of self time")
    for group in ("fusion", "divide_subtract_fusion", "copy"):
        owners = collections.Counter()
        for g, scope, pass_, ns in rows:
            if g == group:
                owners[f"{scope}/{pass_}" if scope else "(no scope)"] += ns
        print(f"  {group} is owned by: " + ", ".join(
            f"{k} {v / steps / 1e6:.2f} ms" for k, v in owners.most_common(6)))
    gaps = profiler.gaps_by_host_span(profile)
    if not gaps:
        print("\nno dl4j/ dispatch span on the capture's host plane")
        return
    n = gaps["dispatches"]
    print(f"\ndevice idle by host span, {n} dispatches, window "
          f"{gaps['window_s']:.3f} s, idle {1e3 * gaps['idle_s'] / n:.3f} "
          f"ms a dispatch:")
    for key in ("fetch", "python", "launch", "unattributed"):
        print(f"  {key:13s} {1e3 * gaps[key + '_s'] / n:8.3f} ms a dispatch")


def print_trace(log_dir, steps, scopes=STEP_SCOPES):
    rows = aggregate_trace(log_dir)
    total = sum(d for _, d, _ in rows)
    print(f"\ndevice self-time total: {total/1e3:.1f} ms "
          f"over {len(rows)} op groups")
    print("top 20 op groups:")
    for n, d, k in rows[:20]:
        print(f"  {d/1e3:8.1f} ms  {100*d/total:5.1f}%  x{k:<5d} {n[:70]}")
    print_scope_tables(log_dir, steps, scopes)


def profile_cell(workload, seed, dispatches=3):
    """Trace ``dispatches`` dispatches of a benchmark cell through its
    driver's run object and print the tables."""
    from benchmarks import run as bench_run

    cell = bench_run.load_cell(
        bench_run.load_json(bench_run.ROOT, "BENCHMARK.json"), workload)
    driver = cell["driver"]
    r = getattr(driver, "Run", None) or driver.TrainScanRun
    r = r(cell["config"], cell["traffic"], cell["limits"], seed)
    print("set-up:", r.setup())
    # one tick a traced call, by the path its shapes chose
    print("kernels chosen while tracing:", {
        f"{name}{dict(labels)}": metric.value
        for name in (FLASH_PATH_COUNTER, FLASH_WINDOWED_COUNTER,
                     SSD_PATH_COUNTER, SELSCAN_PATH_COUNTER, MOE_PATH_COUNTER)
        for labels, metric in get_registry().family(name).items()})
    # the first dispatch by stage, and what it made by the compiler's count
    phases = phase_breakdown()
    print("first dispatch, s:", {
        p: round(phases[p]["total_ms"] / 1e3, 3)
        for p in ("compile", "compile_launch", "trace_step", "lower_step",
                  "load_step", "first_launch") if p in phases})
    made = step_program_report()
    print("the step's program:", made and {
        "code_MiB": made["code_bytes"] / 2 ** 20,
        **{k[:-len("_bytes")] + "_GB": made[k] / 1e9
           for k in ("arguments_bytes", "temporaries_bytes", "outputs_bytes",
                     "aliased_bytes", "count_bytes")},
        "xla_flops_a_step_T": made.get("flops", float("nan")) / 1e12})
    log_dir = os.path.join("chiprun_out", "trace-" + workload)
    with profiler.trace(log_dir):
        for _ in range(dispatches):
            r.dispatch()
    # the family by the key only its configurations have
    scopes = (LOOPED_STEP_SCOPES if "total_ut_steps" in cell["config"]
              else SAMBAY_STEP_SCOPES if "sliding_window" in cell["config"]
              else LFM2_STEP_SCOPES if "num_routed_experts" in cell["config"]
              else HYBRID_STEP_SCOPES if "layer_types" in cell["config"]
              else STEP_SCOPES)
    print_trace(log_dir, dispatches * r.k, scopes)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--cell", help="a cell of BENCHMARK.json: trace it as "
                    "its driver runs it, and nothing else")
    ap.add_argument("--seed", type=int, default=2_200_000_011)
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()
    if args.cell:
        return profile_cell(args.cell, args.seed)
    peak = device_peaks().bf16_flops

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.transformer import (
        gpt, gpt_train_flops_per_token)

    net = gpt(vocab_size=args.vocab, d_model=args.d_model,
              n_layers=args.layers, num_heads=args.heads, max_len=args.seq,
              learning_rate=args.lr).init()
    rng = np.random.default_rng(0)
    ids = rng.integers(0, args.vocab, (args.batch * args.steps, args.seq))
    data = DataSet(ids.astype(np.float32),
                   np.roll(ids, -1, axis=1).astype(np.float32))
    staged = net.stage_scan(data, args.batch)
    t0 = time.perf_counter()
    net.fit_scan(None, args.batch, epochs=args.epochs, staged=staged)
    print(f"compile+warmup: {time.perf_counter()-t0:.1f}s")

    dt = float("inf")
    for _ in range(2):
        t0 = time.perf_counter()
        scores = net.fit_scan(None, args.batch, epochs=args.epochs,
                              staged=staged)
        dt = min(dt, time.perf_counter() - t0)
    tokens = args.epochs * args.steps * args.batch * args.seq
    tps = tokens / dt
    fpt = gpt_train_flops_per_token(args.vocab, args.d_model, args.layers,
                                    args.seq)
    print(f"d_model={args.d_model} L={args.layers} seq={args.seq} "
          f"b={args.batch}: {tps:.0f} tok/s  mfu={tps*fpt/peak:.4f}  "
          f"ms/step={1000*dt/(args.epochs*args.steps):.2f}")
    assert np.isfinite(np.asarray(scores)).all()

    if args.trace:
        log_dir = os.path.join("chiprun_out", "trace-gpt")
        net.fit_scan(None, args.batch, epochs=1, staged=staged)  # warm
        with profiler.trace(log_dir):
            for _ in range(3):  # three, to hold the gaps between them
                net.fit_scan(None, args.batch, epochs=1, staged=staged)
        print_trace(log_dir, 3 * args.steps)


if __name__ == "__main__":
    main()
