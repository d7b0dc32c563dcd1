"""Profile / ablate the ResNet-50 train step on the real chip.

Usage: python scripts/profile_resnet.py [--trace] [--batch N] [--steps N]
Prints examples/sec + MFU for the configured variant.
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

from deeplearning4j_tpu.datasets.dataset import MultiDataSet
from deeplearning4j_tpu.models.zoo.resnet import (
    resnet50, resnet50_train_flops_per_example)
from deeplearning4j_tpu.util.compile_cache import enable_compile_cache
from deeplearning4j_tpu.util.device import device_peaks


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", action="store_true")
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--steps", type=int, default=8)
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--image-size", type=int, default=224)
    args = ap.parse_args()
    enable_compile_cache()
    peak = device_peaks().bf16_flops

    net = resnet50()
    rng = np.random.default_rng(0)
    n = args.batch * args.steps
    x = rng.standard_normal((n, args.image_size, args.image_size, 3)).astype(np.float32)
    y = np.eye(1000, dtype=np.float32)[rng.integers(0, 1000, n)]
    mds = MultiDataSet([x], [y])

    t0 = time.perf_counter()
    staged = net.stage_scan(mds, args.batch)
    print(f"stage: {time.perf_counter()-t0:.1f}s")
    t0 = time.perf_counter()
    net.fit_scan(None, args.batch, epochs=args.epochs, staged=staged)
    print(f"compile+warmup: {time.perf_counter()-t0:.1f}s")

    if args.trace:
        from deeplearning4j_tpu.util import profiler
        net.fit_scan(None, args.batch, epochs=1, staged=staged)  # warm epochs=1 program
        log_dir = os.path.join("chiprun_out", "trace-resnet")
        with profiler.trace(log_dir):
            net.fit_scan(None, args.batch, epochs=1, staged=staged)
        print(f"trace written to {log_dir}")

    t0 = time.perf_counter()
    scores = net.fit_scan(None, args.batch, epochs=args.epochs, staged=staged)
    dt = time.perf_counter() - t0
    eps = args.epochs * n / dt
    mfu = eps * resnet50_train_flops_per_example(args.image_size) / peak
    assert np.isfinite(np.asarray(scores)).all()
    print(f"batch={args.batch} eps={eps:.1f} mfu={mfu:.4f} "
          f"ms/step={1000*dt/(args.epochs*args.steps):.1f}")


if __name__ == "__main__":
    main()
