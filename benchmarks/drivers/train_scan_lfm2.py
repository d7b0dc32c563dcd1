"""Driver for training traffic on an LFM2 mixture-of-experts model (gated
short convolutions, QK-normed grouped-query attention, routed experts held
as one chip's share): ``train_scan.py``'s run (``MultiLayerNetwork.fit_scan``
on a ``stage_scan``-staged set, one compiled program of
``steps_per_dispatch`` optimizer steps, the warm dispatch compared with the
plain reference) with this family's net, reference and names. The window
loop, the dispatch and the device trace are ``train_scan.py``'s own.

Set-up also places the expert bias: the reference balances the seed's
weights on calibration rows drawn from the seed (``lfm2_moe_plain.
calibrate_bias``), and the program's expert layers are given that bias in
their state, which no gradient and no optimizer moves. The bias stands for
the checkpoint a midtraining job loads, so its seconds (``bias_s``) are kept
out of ``setup_s``. After the window, the held experts' share of the first
dispatch's assignments, a layer at a time, goes to the gauge
``dl4j_moe_held_share{stat="min"|"max"}`` and to the line's ``extra``.

From the program this file takes the system under test (``zoo.lfm2_moe``,
``stage_scan``, ``fit_scan``), its compile cache and its compile counter, and
two of its formats: the names of the parameter tree and of the layers' state
(``to_program`` / ``to_reference`` below).
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from benchmarks import correct
from benchmarks.drivers.train_scan import TrainScanRun
from benchmarks.reference import lfm2_moe_plain as plain

#: the program's layer-state key of the expert bias
EXPERT_BIAS = "expert_bias"
#: what `rehearse` shrinks a configuration and its traffic to, for the CPU:
#: tiny in every width, the five layers as the cell has them, 16 routed
#: experts of which 4 are held, 2 picked a token
REHEARSAL_CONFIG = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_routed_experts": 16, "num_experts": 4,
    "num_experts_per_tok": 2, "n_embd": 64, "n_head": 4}
REHEARSAL_TRAFFIC = {"seq_len": 64, "batch": 2}


def build_net(cfg: Dict[str, Any], seed: int):
    from deeplearning4j_tpu.models.zoo.lfm2_moe import lfm2_moe

    tr = cfg["train"]
    if (tr["optimizer"], tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]) != \
            ("adam", 0.9, 0.999, 1e-8) or tr["param_dtype"] != "float32":
        raise ValueError("zoo.lfm2_moe trains with Adam(0.9, 0.999, 1e-8) "
                         "on float32 parameters; the configuration states "
                         f"otherwise: {tr}")
    if len(cfg["layer_types"]) != cfg["n_layer"]:
        raise ValueError("n_layer is not the length of layer_types")
    return lfm2_moe(cfg, learning_rate=float(tr["learning_rate"]),
                    compute_dtype=tr["compute_dtype"],
                    seed=int(seed) % 2 ** 30,
                    recompute_blocks=bool(tr["recompute_blocks"]),
                    kept_values=tr.get("kept_values"))


def to_program(ref: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """The reference's tree of leaves in the program's layout: the embedding,
    the blocks (the same leaf names on both sides), the final norm, and a
    head that owns nothing."""
    tree = {layer_names[0]: {"W": ref["embed"]},
            layer_names[-2]: {"g": ref["final_g"]}, layer_names[-1]: {}}
    for name, leaves in zip(layer_names[1:-2], ref["layers"]):
        tree[name] = dict(leaves)
    return tree


def to_reference(tree: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """A tree in the program's layout (parameters, or one of Adam's moments)
    in the reference's."""
    return {"embed": tree[layer_names[0]]["W"],
            "final_g": tree[layer_names[-2]]["g"],
            "layers": [tree[name] for name in layer_names[1:-2]]}


def states_with_bias(net, bias) -> Dict[str, Any]:
    """The layers' states with the expert bias [moe layers, experts] placed
    in the expert layers' state, in order."""
    states = {impl.name: impl.init_state() for impl in net.impls}
    rows = iter(bias)
    for impl in net.impls:
        if EXPERT_BIAS in states[impl.name]:
            states[impl.name] = {EXPERT_BIAS: next(rows)}
    return states


class LFM2TrainScanRun(TrainScanRun):
    """One run of one cell. ``setup`` -> ``window`` -> ``check``; the window,
    the dispatch and ``free`` are the parent's."""

    def setup(self) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.util.compile_cache import (CompileWatch,
                                                           enable_compile_cache)

        split = {}
        t0 = time.perf_counter()
        enable_compile_cache()
        self.watch = CompileWatch()
        cfg = self.cfg
        net = self.net = build_net(cfg, self.seed)
        names = self.layer_names = [impl.name for impl in net.impls]
        split["build_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.tokens = plain.make_tokens(cfg, self.seed, self.k, self.batch,
                                        self.seq)
        flat = self.tokens.reshape(self.k * self.batch, self.seq + 1)
        split["tokens_s"] = time.perf_counter() - t0

        # the seed's weights and the expert bias that balances them: the
        # checkpoint a midtraining job would load, which the reference makes
        # here (``bias_s`` is kept out of ``setup_s``, as ``reference_s`` is)
        t0 = time.perf_counter()
        ref = plain.init_on_device(cfg, self.seed)
        self.bias = plain.calibrate_bias(cfg, ref, self.seed, self.seq)
        jax.block_until_ready(self.bias)
        split["bias_s"] = time.perf_counter() - t0

        # the seed's weights and a fresh Adam state on the device; the bias
        # in the expert layers' state
        t0 = time.perf_counter()
        zeros = lambda p: {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}
        net.params = to_program(ref, names)
        net.opt_state = jax.jit(lambda p: {
            "step": jnp.zeros((), jnp.int32),
            "updater": jax.tree.map(zeros, p)})(net.params)
        net.states = states_with_bias(net, self.bias)
        del ref
        jax.block_until_ready(net.params)
        split["weights_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        data = DataSet(flat[:, :-1].astype(np.float32),
                       flat[:, 1:].astype(np.float32))
        self.staged = net.stage_scan(data, self.batch)
        split["tokens_s"] += time.perf_counter() - t0

        # the warm dispatch: the window's own call and feed. It compiles or
        # loads the program, and its result is what `correct` compares.
        t0 = time.perf_counter()
        before = self.watch.snapshot()
        first_losses = np.asarray(self.dispatch(), np.float64)
        after = self.watch.snapshot()
        split["first_dispatch_s"] = time.perf_counter() - t0
        split["compile_s"] = after["compile_seconds"] - before["compile_seconds"]
        split["cache_hits"] = after["cache_hits"] - before["cache_hits"]
        split["cache_misses"] = after["cache_misses"] - before["cache_misses"]

        # what the first dispatch left in the state, reduced to norms on the
        # device, leaf by leaf of the reference's layout
        t0 = time.perf_counter()

        def norms(params, updater, key):
            init = to_program(plain.init_weights(plain_cfg, key), names)
            moved = jax.tree.map(jnp.subtract, params, init)
            m = jax.tree.map(lambda s: s["m"], updater,
                             is_leaf=lambda x: isinstance(x, dict) and "m" in x)
            return (plain.leaf_norms(to_reference(moved, names)),
                    plain.leaf_norms(to_reference(m, names)))

        plain_cfg = dict(plain.cfg_key(cfg))
        dp, m = jax.device_get(jax.jit(norms)(
            net.params, net.opt_state["updater"], plain.seed_key(self.seed)))
        f64 = lambda d: {k: np.asarray(v, np.float64) for k, v in d.items()}
        self.prog = {"losses": first_losses, "dp_norms": f64(dp),
                     "m_norms": f64(m)}
        # the bias the program ran with, as its state holds it after the
        # dispatch: nothing may have moved it
        after_bias = [np.asarray(s[EXPERT_BIAS]) for s in net.states.values()
                      if EXPERT_BIAS in s]
        self.bias_unmoved = bool(np.array_equal(np.stack(after_bias),
                                                np.asarray(self.bias)))
        split["state_norms_s"] = time.perf_counter() - t0
        return split

    def route(self) -> Dict[str, Any]:
        """How the first dispatch's rows fall on the held experts under the
        bias, a layer at a time (the reference's forward, after the window):
        the held share, also set as the gauge ``dl4j_moe_held_share{stat=
        "min"|"max"}``, and how many assignments rounding the router's input
        to bfloat16 would change."""
        from deeplearning4j_tpu.monitor import (MOE_HELD_SHARE_GAUGE,
                                                get_registry)

        flat = self.tokens.reshape(self.k * self.batch, self.seq + 1)
        held, flips = plain.route_stats(
            self.cfg, plain.init_on_device(self.cfg, self.seed), self.bias,
            flat[:, :-1])
        registry = get_registry()
        for stat, value in (("min", held.min()), ("max", held.max())):
            registry.gauge(MOE_HELD_SHARE_GAUGE, "the held experts' share of "
                           "the first dispatch's assignments, over the expert "
                           "layers", stat=stat).set(float(value))
        return {"held_share": held.tolist(), "bf16_flips": flips.tolist()}

    def check(self) -> Dict[str, Any]:
        self.free()
        routing = self.route()
        t0 = time.perf_counter()
        ref = plain.follow(self.cfg, self.cfg["train"], self.seed,
                           self.tokens, bias=self.bias)
        gaps = correct.training_gaps(self.prog, ref)
        ok, compared = correct.judge(gaps, self.limits)
        return {"correct": ok and self.bias_unmoved, "compared": compared,
                "reference_s": time.perf_counter() - t0, "routing": routing,
                "losses": {"program": list(self.prog["losses"]),
                           "reference": list(ref["losses"])}}


#: the run object, for whoever drives a cell by hand (scripts/profile_gpt.py)
Run = LFM2TrainScanRun


def rehearse(cell: Dict[str, Any]) -> None:
    """Shrink the cell in place to a tiny copy that the CPU can run: the same
    control flow at sizes that prove nothing about the chip."""
    cell["config"].update(REHEARSAL_CONFIG)
    cell["traffic"].update(REHEARSAL_TRAFFIC)
    cell["limits"] = cell["limits"]["rehearsal"]


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """Run one cell; the same return keys as ``train_scan.run``."""
    r = LFM2TrainScanRun(cell["config"], cell["traffic"], cell["limits"],
                         seed)
    split = r.setup()
    setup_s = time.time() - t_start - split["bias_s"]
    w = r.window(seconds, trace)
    reduction = w.pop("trace")
    chk = r.check()
    ok = chk["correct"] and w["failed"] == 0 and w["compiles_in_window"] == 0
    return {
        "correct": bool(ok), "attempted": w["dispatches"],
        "failed": w["failed"],
        "end_to_end": {"train_tokens_per_s": w["tokens"] / w["wall_s"],
                       "setup_s": setup_s},
        "memory_peak_bytes": w["memory_peak_bytes"], "window_s": w["wall_s"],
        "window": w, "trace": reduction, "compared": chk["compared"],
        "extra": {"window": w, "setup_split": split, "routing": chk["routing"],
                  "reference_s": chk["reference_s"], "losses": chk["losses"]},
    }
