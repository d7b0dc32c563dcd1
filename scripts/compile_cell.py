"""Compile a benchmark cell's ``fit_scan`` program for a v5e in a sandbox that
has none, and print what the compiler says of it: the gauges a chip run's
first dispatch sets from ``memory_analysis()`` and ``cost_analysis()``
(``nn/scan_dispatch.record_step_program``: the only numbers that see the
step's temporaries; the benchmark's ``step_compiled_peak_gb`` and
``step_executable_mib`` read the same gauges on the chip), the Pallas
kernels in the compiled text, and its ``copy`` instructions of 64 MB and more
(a relayout XLA put between a value's writer and a reader that wants another
layout: shape, the two layouts, ``op_name``). An AOT compile, not a chip run:
nothing is executed and nothing here is a time.

Usage: python scripts/compile_cell.py <cell of BENCHMARK.json>

One compile at a time: libtpu holds a lock file. What a recomputed block keeps
is ``LayerImpl.kept_names``: edit it (or ``recompute_blocks`` in the cell's
configuration) in the tree and compile again to size another keep-set.
"""
import collections
import importlib
import json
import math
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


#: `%name = f32[8,1024,50257]{2,1,0:T(8,128)} copy(%operand), metadata={...}`
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = (\w+)\[([\d,]*)\](\{[^}]*\})? "
    r"([\w\-]+)\((?:[^%)]*?%([\w.\-]+))?")


def large_copies(text, at_least=64e6):
    """The compiled text's ``copy`` instructions of ``at_least`` bytes and
    more (not ``copy-start`` / ``copy-done``, XLA's asynchronous moves),
    alike ones counted together: the shape, the operand's layout and the
    copy's own, whether the order of the dimensions differs (a relayout: the
    other copies move a value between memory spaces), and ``op_name``."""
    layouts, copies = {}, collections.Counter()
    for line in text.splitlines():
        found = _INSTRUCTION.match(line)
        if not found:
            continue
        name, dtype, dims, layout, opcode, operand = found.groups()
        layouts[name] = layout
        width = re.search(r"\d+", dtype)
        size = math.prod(int(d) for d in dims.split(",") if d) \
            * (int(width.group()) // 8 if width else 1)
        if opcode == "copy" and size >= at_least:
            op_name = re.search(r'op_name="([^"]*)"', line)
            # an operand stands above its reader in the text
            copies[f"{dtype}[{dims}]", round(size / 1e6, 1),
                   layouts.get(operand), layout,
                   op_name and op_name.group(1)] += 1
    order = lambda layout: layout.strip("{}").split(":")[0]
    return [{"shape": shape, "MB": mb, "from": was, "to": to,
             "relayout": bool(was and to) and order(was) != order(to),
             "op_name": op_name, "copies": n}
            for (shape, mb, was, to, op_name), n in copies.items()]


def main(cell):
    spec = bench.load_json(bench.ROOT, "BENCHMARK.json")
    workload = next(w for w in spec["workloads"] if w["name"] == cell)
    cfg = bench.load_json(bench.ROOT,
                          f"benchmarks/configs/{workload['config']}.json")
    traffic = bench.load_json(bench.ROOT,
                              f"benchmarks/traffic/{workload['traffic']}.json")
    # the kernels themselves, not the interpreter the CPU backend would get
    # (by module name: ``ops/__init__`` re-exports functions under them)
    for mod in ("ssd", "selective_scan", "flash_attention", "attention",
                "grouped_matmul"):
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
    text = compiled.as_text()
    kernels = collections.Counter(
        re.match(r"\s*%([A-Za-z_]+)", line).group(1)
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line)
    print(json.dumps({
        "cell": cell, "compile_s": round(time.perf_counter() - t0, 1),
        "arguments_GB": made["arguments_bytes"] / 1e9,
        "temporaries_GB": made["temporaries_bytes"] / 1e9,
        "count_GB": made["count_bytes"] / 1e9,
        "code_MiB": made["code_bytes"] / 2 ** 20,
        "flops_a_step_T": made["flops"] / 1e12,
        "kernel_calls_a_step": dict(kernels),
        "copies_of_64_MB_and_more": large_copies(text)}))


if __name__ == "__main__":
    main(sys.argv[1])
