"""Driver for pre-training traffic on a model of the hybrid state-space
family: ``train_scan.py``'s run (``MultiLayerNetwork.fit_scan`` on a
``stage_scan``-staged set, one compiled program of ``steps_per_dispatch``
optimizer steps, the warm dispatch compared with the plain reference) with
this family's net, reference and names. The window loop, the dispatch and the
device trace are ``train_scan.py``'s own.

From the program this file takes the system under test
(``zoo.granite_hybrid``, ``stage_scan``, ``fit_scan``), its compile cache and
its compile counter, and two of its formats: the names of the parameter tree
and of the Adam state (``to_program`` / ``from_program`` below).
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from benchmarks import correct
from benchmarks.drivers.train_scan import TrainScanRun
from benchmarks.reference import granite_hybrid_plain as plain

#: what `rehearse` shrinks a configuration and its traffic to, for the CPU:
#: tiny in every width, two Mamba-2 layers round one attention layer
REHEARSAL_CONFIG = {
    "vocab_size": 512, "hidden_size": 64, "shared_intermediate_size": 128,
    "layer_types": ["mamba", "attention", "mamba"], "n_layer": 3,
    "num_attention_heads": 4, "num_key_value_heads": 2, "mamba_n_heads": 8,
    "mamba_d_head": 16, "mamba_d_state": 16, "mamba_chunk_size": 16,
    "n_embd": 64, "n_head": 4}
REHEARSAL_TRAFFIC = {"seq_len": 64, "batch": 2}


def build_net(cfg: Dict[str, Any], seed: int):
    from deeplearning4j_tpu.models.zoo.granite_hybrid import granite_hybrid

    tr = cfg["train"]
    if (tr["optimizer"], tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]) != \
            ("adam", 0.9, 0.999, 1e-8) or tr["param_dtype"] != "float32":
        raise ValueError("zoo.granite_hybrid trains with Adam(0.9, 0.999, "
                         "1e-8) on float32 parameters; the configuration "
                         f"states otherwise: {tr}")
    if len(cfg["layer_types"]) != cfg["n_layer"]:
        raise ValueError("n_layer is not the length of layer_types")
    return granite_hybrid(cfg, learning_rate=float(tr["learning_rate"]),
                          compute_dtype=tr["compute_dtype"],
                          seed=int(seed) % 2 ** 30,
                          recompute_blocks=bool(tr["recompute_blocks"]))


def to_program(ref: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """The reference's tree of leaves in the program's layout: the embedding,
    the blocks (the same leaf names on both sides), the final norm, and a
    head that owns nothing."""
    tree = {layer_names[0]: {"W": ref["embed"]},
            layer_names[-2]: {"g": ref["final_g"]}, layer_names[-1]: {}}
    for name, leaves in zip(layer_names[1:-2], ref["layers"]):
        tree[name] = dict(leaves)
    return tree


def from_program(tree: Dict[str, Any], layer_names, layer_types):
    """Per-leaf scalars in the program's layout -> the reference's names,
    the blocks' leaves stacked over the layers of their kind."""
    out = {"embed": tree[layer_names[0]]["W"],
           "final_g": tree[layer_names[-2]]["g"]}
    for kind, leaves in plain.LEAVES.items():
        of_kind = [tree[n] for n, k in zip(layer_names[1:-2], layer_types)
                   if k == kind]
        for leaf in leaves if of_kind else ():
            out[f"{kind}.{leaf}"] = np.stack(
                [np.asarray(layer[leaf]) for layer in of_kind])
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


class HybridTrainScanRun(TrainScanRun):
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
        kinds = list(cfg["layer_types"])
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
        # device: a copy of the state would not fit beside the window
        t0 = time.perf_counter()
        norm = lambda x: jnp.sqrt(jnp.sum(jnp.square(x)))

        def norms(params, updater, key):
            p0 = self._make(key)
            dp = jax.tree.map(lambda a, b: norm(a - b), params, p0)
            m = jax.tree.map(lambda s: norm(s["m"]), updater,
                             is_leaf=lambda x: isinstance(x, dict) and "m" in x)
            return dp, m

        dp, m = jax.jit(norms)(net.params, net.opt_state["updater"],
                               plain.seed_key(self.seed))
        self.prog = {
            "losses": first_losses,
            "dp_norms": from_program(jax.device_get(dp), names, kinds),
            "m_norms": from_program(jax.device_get(m), names, kinds)}
        split["state_norms_s"] = time.perf_counter() - t0
        return split

    def check(self) -> Dict[str, Any]:
        self.free()
        t0 = time.perf_counter()
        # every row of a step in one pass: the reference takes them one at a
        # time inside each layer, and needs no second copy of the gradient
        ref = plain.follow(self.cfg, self.cfg["train"], self.seed,
                           self.tokens, self.batch)
        gaps = correct.training_gaps(self.prog, ref)
        ok, compared = correct.judge(gaps, self.limits)
        return {"correct": ok, "compared": compared,
                "reference_s": time.perf_counter() - t0,
                "losses": {"program": list(self.prog["losses"]),
                           "reference": list(ref["losses"])}}


#: the run object, for whoever drives a cell by hand (scripts/profile_gpt.py)
Run = HybridTrainScanRun


def rehearse(cell: Dict[str, Any]) -> None:
    """Shrink the cell in place to a tiny copy that the CPU can run: the same
    control flow at sizes that prove nothing about the chip."""
    cell["config"].update(REHEARSAL_CONFIG)
    cell["traffic"].update(REHEARSAL_TRAFFIC)
    cell["limits"] = cell["limits"]["rehearsal"]


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """Run one cell; the same return keys as ``train_scan.run``."""
    r = HybridTrainScanRun(cell["config"], cell["traffic"], cell["limits"],
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
