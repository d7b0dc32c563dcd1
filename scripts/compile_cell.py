"""Compile a benchmark cell's ``fit_scan`` program for a v5e in a sandbox that
has none, and print what the compiler says of it: the gauges a chip run's
first dispatch sets from ``memory_analysis()`` and ``cost_analysis()``
(``nn/scan_dispatch.record_step_program``: the only numbers that see the
step's temporaries; the benchmark's ``step_compiled_peak_gb`` and
``step_executable_mib`` read the same gauges on the chip), and the Pallas
kernels in the compiled text. An AOT compile, not a chip run: nothing is
executed and nothing here is a time.

Usage: python scripts/compile_cell.py <cell of BENCHMARK.json>

One compile at a time: libtpu holds a lock file. What a recomputed block keeps
is ``LayerImpl.kept_names``: edit it (or ``recompute_blocks`` in the cell's
configuration) in the tree and compile again to size another keep-set.
"""
import collections
import importlib
import json
import os
import re
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

from benchmarks import run as bench
from deeplearning4j_tpu.nn.scan_dispatch import (record_step_program,
                                                 step_program_report)


def main(cell):
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    workload = next(w for w in spec["workloads"] if w["name"] == cell)
    cfg = bench.load_json(bench.ROOT,
                          f"benchmarks/configs/{workload['config']}.json")
    traffic = bench.load_json(bench.ROOT,
                              f"benchmarks/traffic/{workload['traffic']}.json")
    # the kernels themselves, not the interpreter the CPU backend would get
    # (by module name: ``ops/__init__`` re-exports functions under them)
    for mod in ("ssd", "selective_scan", "flash_attention", "attention"):
        importlib.import_module(
            f"deeplearning4j_tpu.ops.{mod}").pallas_interpret = lambda: False
    # a compile for a described device cannot be read back from the cache
    jax.config.update("jax_enable_compilation_cache", False)

    net = importlib.import_module(
        f"benchmarks.drivers.{traffic['driver']}").build_net(cfg, 1)

    def state():
        net.init()
        return net.params, net.opt_state, net.states

    chip = SingleDeviceSharding(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices[0])
    on_chip = lambda *shape_dtype: jax.ShapeDtypeStruct(*shape_dtype,
                                                        sharding=chip)
    shapes = jax.tree.map(lambda s: on_chip(s.shape, s.dtype),
                          jax.eval_shape(state))
    batch = on_chip((traffic["steps_per_dispatch"], traffic["batch"],
                     traffic["seq_len"]), jnp.float32)
    # the state is donated on the chip, and the compiler's count depends on it
    backend, jax.default_backend = jax.default_backend, lambda: "tpu"
    try:
        fit = net._make_scan_fit(1)
    finally:
        jax.default_backend = backend
    t0 = time.perf_counter()
    compiled = fit.trace(*shapes, batch, batch, on_chip((2,), jnp.uint32)) \
        .lower(lowering_platforms=("tpu",)).compile()
    record_step_program(compiled)
    made = step_program_report()
    kernels = collections.Counter(
        re.match(r"\s*%([A-Za-z_]+)", line).group(1)
        for line in compiled.as_text().splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    print(json.dumps({
        "cell": cell, "compile_s": round(time.perf_counter() - t0, 1),
        "arguments_GB": made["arguments_bytes"] / 1e9,
        "temporaries_GB": made["temporaries_bytes"] / 1e9,
        "count_GB": made["count_bytes"] / 1e9,
        "code_MiB": made["code_bytes"] / 2 ** 20,
        "flops_a_step_T": made["flops"] / 1e12,
        "kernel_calls_a_step": dict(kernels)}))


if __name__ == "__main__":
    main(sys.argv[1])
