"""Profile the GPT train step on the real chip and attribute MFU.

Usage: python scripts/profile_gpt.py [--trace] [--d-model N] ...
Prints tokens/sec + MFU; with --trace, aggregates device op self-times
from the captured trace by op group (flash fwd/dq/dkv kernels,
fusions, copies) — the BASELINE.md attribution workflow.
"""
import argparse
import collections
import os
import re
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.util import profiler
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
from deeplearning4j_tpu.util.device import device_peaks


def aggregate_trace(log_dir):
    """Aggregate XLA-Ops-line SELF times (events nest: while > fusion —
    walk each line's intervals with a stack and subtract child time)
    from the newest ``.xplane.pb`` under ``log_dir``.
    Returns [(group_name, total_us, count)] sorted by time, where
    group_name is the HLO instruction's name with trailing .N instance
    suffixes stripped (``fusion``, ``copy``, ``jvp_flash_fwd_`` …; the
    capture carries no hlo_category per event)."""
    agg = collections.Counter()
    cnt = collections.Counter()
    for plane in profiler.device_planes(profiler.load_trace(log_dir)):
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            events = sorted(line.events,
                            key=lambda e: (e.start_ns, -e.duration_ns))
            stack = []  # [end_ns, event, child_ns]

            def pop_one():
                _, e0, child0 = stack.pop()
                # event names are HLO text: "%fusion.12 = bf16[...] ..."
                key = re.sub(r"(\.\d+)+$", "",
                             e0.name.split(" = ")[0].lstrip("%"))
                agg[key] += max(e0.duration_ns - child0, 0) / 1e3
                cnt[key] += 1
                if stack:
                    stack[-1][2] += e0.duration_ns

            for e in events:
                while stack and e.start_ns >= stack[-1][0]:
                    pop_one()
                stack.append([e.start_ns + e.duration_ns, e, 0])
            while stack:
                pop_one()
    return sorted(((n, d, cnt[n]) for n, d in agg.items()),
                  key=lambda t: -t[1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--vocab", type=int, default=8192)
    ap.add_argument("--d-model", type=int, default=512)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--seq", type=int, default=1024)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--steps", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=12)
    args = ap.parse_args()
    enable_compile_cache()
    peak = device_peaks().bf16_flops

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.models.zoo.transformer import (
        gpt, gpt_train_flops_per_token)

    net = gpt(vocab_size=args.vocab, d_model=args.d_model,
              n_layers=args.layers, max_len=args.seq).init()
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
            net.fit_scan(None, args.batch, epochs=1, staged=staged)
        rows = aggregate_trace(log_dir)
        total = sum(d for _, d, _ in rows)
        print(f"\ndevice self-time total: {total/1e3:.1f} ms "
              f"over {len(rows)} op groups")
        print("top 20 op groups:")
        for n, d, k in rows[:20]:
            print(f"  {d/1e3:8.1f} ms  {100*d/total:5.1f}%  x{k:<5d} {n[:70]}")


if __name__ == "__main__":
    main()
