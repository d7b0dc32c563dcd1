"""Driver for training traffic on a model of the decoder-hybrid-decoder
family (SambaY): ``train_scan.py``'s run (``MultiLayerNetwork.fit_scan`` on a
``stage_scan``-staged set, one compiled program of ``steps_per_dispatch``
optimizer steps, the warm dispatch compared with the plain reference) with
this family's net, reference and names. The window loop, the dispatch and the
device trace are ``train_scan.py``'s own.

From the program this file takes the system under test (``zoo.sambay_lm``,
``stage_scan``, ``fit_scan``), its compile cache and its compile counter, and
two of its formats: the names of the parameter tree and of the Adam state
(``to_program`` / ``to_reference`` below).
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from benchmarks import correct
from benchmarks.drivers.train_scan import TrainScanRun
from benchmarks.reference import sambay_plain as plain

#: what `rehearse` shrinks a configuration and its traffic to, for the CPU:
#: tiny in every width, the six kinds of layer as the cell has them, a window
#: shorter than the row
REHEARSAL_CONFIG = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "num_attention_heads": 4, "num_key_value_heads": 2, "sliding_window": 16,
    "mamba_dt_rank": 4, "n_embd": 64, "n_head": 4}
REHEARSAL_TRAFFIC = {"seq_len": 64, "batch": 2}


def build_net(cfg: Dict[str, Any], seed: int):
    from deeplearning4j_tpu.models.zoo.sambay import sambay_lm

    tr = cfg["train"]
    if (tr["optimizer"], tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]) != \
            ("adam", 0.9, 0.999, 1e-8) or tr["param_dtype"] != "float32":
        raise ValueError("zoo.sambay_lm trains with Adam(0.9, 0.999, 1e-8) "
                         "on float32 parameters; the configuration states "
                         f"otherwise: {tr}")
    if len(cfg["layer_types"]) != cfg["n_layer"]:
        raise ValueError("n_layer is not the length of layer_types")
    return sambay_lm(cfg, learning_rate=float(tr["learning_rate"]),
                     compute_dtype=tr["compute_dtype"],
                     seed=int(seed) % 2 ** 30,
                     recompute_blocks=bool(tr["recompute_blocks"]),
                     kept_values=tr.get("kept_values"))


def to_program(ref: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """The reference's tree of leaves in the program's layout: the embedding,
    the blocks (the same leaf names on both sides), the final norm, and a
    head that owns nothing."""
    tree = {layer_names[0]: {"W": ref["embed"]},
            layer_names[-2]: {"g": ref["final_g"], "b": ref["final_b"]},
            layer_names[-1]: {}}
    for name, leaves in zip(layer_names[1:-2], ref["layers"]):
        tree[name] = dict(leaves)
    return tree


def to_reference(tree: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """A tree in the program's layout (parameters, or one of Adam's moments)
    in the reference's: the blocks' leaves go by the same names."""
    return {"embed": tree[layer_names[0]]["W"],
            "final_g": tree[layer_names[-2]]["g"],
            "final_b": tree[layer_names[-2]]["b"],
            "layers": [tree[name] for name in layer_names[1:-2]]}


class SambaYTrainScanRun(TrainScanRun):
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

        # the seed's weights and a fresh Adam state, on the device, in one
        # jitted call
        t0 = time.perf_counter()
        key_ = plain.cfg_key(cfg)
        self._make = jax.jit(lambda key: to_program(
            plain.init_params(dict(key_), key), names))

        def fresh(key):
            params = self._make(key)
            zeros = lambda p: {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}
            return params, {"step": jnp.zeros((), jnp.int32),
                            "updater": jax.tree.map(zeros, params)}

        net.params, net.opt_state = jax.jit(fresh)(plain.seed_key(self.seed))
        net.states = {impl.name: impl.init_state() for impl in net.impls}
        jax.block_until_ready(net.params)
        split["weights_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.tokens = plain.make_tokens(cfg, self.seed, self.k, self.batch,
                                        self.seq)
        flat = self.tokens.reshape(self.k * self.batch, self.seq + 1)
        data = DataSet(flat[:, :-1].astype(np.float32),
                       flat[:, 1:].astype(np.float32))
        self.staged = net.stage_scan(data, self.batch)
        split["tokens_s"] = time.perf_counter() - t0

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
        # device, leaf by leaf of the reference's layout: a copy of the state
        # would not fit beside the window
        t0 = time.perf_counter()
        kinds = tuple(cfg["layer_types"])

        def norms(params, updater, key):
            moved = jax.tree.map(jnp.subtract, params, self._make(key))
            m = jax.tree.map(lambda s: s["m"], updater,
                             is_leaf=lambda x: isinstance(x, dict) and "m" in x)
            return (plain.leaf_norms(to_reference(moved, names), kinds),
                    plain.leaf_norms(to_reference(m, names), kinds))

        dp, m = jax.device_get(jax.jit(norms)(
            net.params, net.opt_state["updater"], plain.seed_key(self.seed)))
        f64 = lambda d: {k: np.asarray(v, np.float64) for k, v in d.items()}
        self.prog = {"losses": first_losses, "dp_norms": f64(dp),
                     "m_norms": f64(m)}
        split["state_norms_s"] = time.perf_counter() - t0
        return split

    def check(self) -> Dict[str, Any]:
        self.free()
        t0 = time.perf_counter()
        # every row of a step in one pass, one at a time inside it
        ref = plain.follow(self.cfg, self.cfg["train"], self.seed,
                           self.tokens)
        gaps = correct.training_gaps(self.prog, ref)
        ok, compared = correct.judge(gaps, self.limits)
        return {"correct": ok, "compared": compared,
                "reference_s": time.perf_counter() - t0,
                "losses": {"program": list(self.prog["losses"]),
                           "reference": list(ref["losses"])}}


#: the run object, for whoever drives a cell by hand (scripts/profile_gpt.py)
Run = SambaYTrainScanRun


def rehearse(cell: Dict[str, Any]) -> None:
    """Shrink the cell in place to a tiny copy that the CPU can run: the same
    control flow at sizes that prove nothing about the chip."""
    cell["config"].update(REHEARSAL_CONFIG)
    cell["traffic"].update(REHEARSAL_TRAFFIC)
    cell["limits"] = cell["limits"]["rehearsal"]


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """Run one cell; the same return keys as ``train_scan.run``."""
    r = SambaYTrainScanRun(cell["config"], cell["traffic"], cell["limits"],
                           seed)
    split = r.setup()
    setup_s = time.time() - t_start
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
        "extra": {"window": w, "setup_split": split,
                  "reference_s": chk["reference_s"], "losses": chk["losses"]},
    }
