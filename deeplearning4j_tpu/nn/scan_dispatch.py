"""One dispatch of a ``fit_scan`` program, for ``MultiLayerNetwork`` and
``ComputationGraph`` alike: the span tree, the first dispatch made in
stages, and what the compiler says of the program it made.

A dispatch is one span tree. Where the net holds a program for the staged
set's shapes: ``device_step`` > ``launch`` (argument handling and enqueue: it
returns before the device is done), ``fetch`` (the wait and the
device-to-host copy). Where it holds none, the program is made by the three
calls of JAX's stages API that a first ``jit`` call makes anyway, each under
a span of its own: ``compile`` > ``compile_launch`` > ``trace_step``
(``.trace``: the step as a jaxpr), ``lower_step`` (``.lower``: StableHLO),
``load_step`` (``.compile``: the persistent cache's retrieval and load, or
the backend's compile, whichever happened), ``first_launch`` (the call of
what was made), then ``fetch``. The four tile ``compile_launch``; the
``launch`` histogram holds steady-state calls only. What ``.compile()``
returned is asked for its analyses once the dispatch is enqueued, while the
host would only wait for it (``record_step_program``).

Later dispatches call the ``jax.jit`` function itself: its trace, lowering
and executable caches are the ones the staged calls filled, so its first
call finds all three and its later ones take the C++ fast path they always
took (donation and dispatch cost are the jit's own).
"""

from __future__ import annotations

import math
from typing import Dict, Optional

import jax
import numpy as np

from deeplearning4j_tpu.monitor import (STEP_PROGRAM_BYTES_GAUGE,
                                        STEP_PROGRAM_FLOPS_GAUGE,
                                        get_registry, span)
from deeplearning4j_tpu.optimize.deferred import count_jit_cache_miss

#: ``dl4j_step_program_bytes{part=...}`` <- field of ``memory_analysis()``
PROGRAM_PARTS = {"code": "generated_code_size_in_bytes",
                 "arguments": "argument_size_in_bytes",
                 "temporaries": "temp_size_in_bytes",
                 "outputs": "output_size_in_bytes",
                 "aliased": "alias_size_in_bytes"}


def record_step_program(compiled) -> None:
    """Set the gauges of what ``.compile()`` returned: the executable's
    size and the compiler's memory count by part, and XLA's operation count
    of a step (a scanned body counts once). A runtime that gives no
    analysis leaves its gauges unset."""
    reg = get_registry()
    try:
        mem = compiled.memory_analysis()
        sizes = {part: getattr(mem, field)
                 for part, field in PROGRAM_PARTS.items()}
    except Exception:
        sizes = {}  # telemetry must never break the training loop
    for part, size in sizes.items():
        reg.gauge(STEP_PROGRAM_BYTES_GAUGE, "the step program the last first "
                  "dispatch made, by the compiler's count: its executable's "
                  "code, its arguments, temporaries and outputs, and the "
                  "outputs that alias arguments", part=part).set(size)
    try:
        cost = compiled.cost_analysis()
        flops = (cost[0] if isinstance(cost, (list, tuple)) else cost)["flops"]
    except Exception:
        return
    reg.gauge(STEP_PROGRAM_FLOPS_GAUGE, "XLA's operation count of that "
              "program, a scanned step counted once").set(flops)


def step_program_report() -> Optional[Dict[str, float]]:
    """The gauges ``record_step_program`` set, as ``scripts/`` print them:
    bytes by part, ``count_bytes`` (arguments + temporaries + outputs less
    what is aliased: what the step needs of the chip's memory) and
    ``flops``; ``None`` where no analysis was recorded."""
    reg = get_registry()
    out = {}
    for part in PROGRAM_PARTS:
        g = reg.get(STEP_PROGRAM_BYTES_GAUGE, part=part)
        if g is None or math.isnan(g.value):
            return None
        out[part + "_bytes"] = g.value
    out["count_bytes"] = (out["arguments_bytes"] + out["temporaries_bytes"]
                          + out["outputs_bytes"] - out["aliased_bytes"])
    flops = reg.get(STEP_PROGRAM_FLOPS_GAUGE)
    if flops is not None and not math.isnan(flops.value):
        out["flops"] = flops.value
    return out


def scan_dispatch(model, path: str, epochs: int, xb, yb) -> np.ndarray:
    """Run ``model``'s ``fit_scan`` program of ``epochs`` epochs over the
    staged stacks ``xb``, ``yb`` once; returns the per-step scores. The
    program is kept by the stacks' shapes and dtypes too: another staged
    set is another program, and its first dispatch says so (``compile``,
    the four stages, one tick of ``dl4j_jit_cache_miss_total``)."""
    leaves, tree = jax.tree.flatten((xb, yb))
    key = ("scan_fit", epochs, model._seq_token(), tree,
           tuple((a.shape, a.dtype) for a in leaves))
    fit = model._jits.get(key)
    if fit is None:
        fit = model._jits[key] = model._make_scan_fit(epochs)
        count_jit_cache_miss()
        with span("compile", path=path, epochs=epochs):
            with span("compile_launch"):
                args = (model.params, model.opt_state, model.states, xb, yb,
                        model._train_rng())
                with span("trace_step"):
                    traced = fit.trace(*args)
                with span("lower_step"):
                    lowered = traced.lower()
                with span("load_step"):
                    compiled = lowered.compile()
                with span("first_launch"):
                    model.params, model.opt_state, model.states, scores = \
                        fit(*args)
                    # the donated state goes here, not after the fetch
                    del args, traced, lowered
            with span("fetch"):
                # the device is busy with the dispatch: the analysis (58 ms
                # at gpt2-medium's size) is read in its shadow, not in set-up
                record_step_program(compiled)
                del compiled
                out = np.asarray(scores)  # score fetch = device sync
    else:
        with span("device_step", path=path, epochs=epochs):
            with span("launch"):
                # the old state's arrays are dropped by the assignment, inside
                # the span and while the device runs: keep no other reference
                model.params, model.opt_state, model.states, scores = fit(
                    model.params, model.opt_state, model.states, xb, yb,
                    model._train_rng())
            with span("fetch"):
                out = np.asarray(scores)
    model._score = float(out[-1])
    return out
