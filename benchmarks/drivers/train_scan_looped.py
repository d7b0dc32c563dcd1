"""Driver for pre-training traffic on a looped language model (a stack of
blocks run ``total_ut_steps`` times on the same weights, an exit gate and the
expected loss over the passes): ``train_scan.py``'s run
(``MultiLayerNetwork.fit_scan`` on a ``stage_scan``-staged set, one compiled
program of ``steps_per_dispatch`` optimizer steps, dispatched whole until the
window has passed) with this family's net, reference and names. The window
loop, the dispatch and the device trace are ``train_scan.py``'s own.

Set-up builds ONE object, the net with its state and its compiled program,
drives it from the seed through its first dispatch (the warm dispatch) and
hands that same object to the window. What that first dispatch returned and
left in the state is what ``correct`` compares with the plain reference
(``reference/ouro_looped_plain.py``), once the window has closed and the
program's state is freed.

From the program this file takes the system under test (``zoo.looped_lm``,
``stage_scan``, ``fit_scan``), its compile cache and its compile counter, and
two of its formats: the names of the parameter tree and of the Adam state
(``to_program`` / ``to_reference`` below; the program holds the gated MLP's
two wide matrices as one leaf, the reference as two).
"""

from __future__ import annotations

import time
from typing import Any, Dict

import numpy as np

from benchmarks import correct
from benchmarks.drivers.train_scan import TrainScanRun
from benchmarks.reference import ouro_looped_plain as plain

#: what `rehearse` shrinks a configuration and its traffic to, for the CPU:
#: tiny in every width, two blocks run three times
REHEARSAL_CONFIG = {
    "vocab_size": 512, "hidden_size": 64, "intermediate_size": 128,
    "n_layer": 2, "layer_types": ["full_attention"] * 2,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "total_ut_steps": 3, "n_embd": 64, "n_head": 4}
REHEARSAL_TRAFFIC = {"seq_len": 64, "batch": 2}

#: reference block leaf -> the program's GroupedQueryBlock leaf; the gated
#: MLP's ``w_gate`` and ``w_up`` are the halves of the program's ``W_gate_up``
BLOCK_NAMES = {"g1": "rms1_g", "wq": "Wq", "wk": "Wk", "wv": "Wv", "wo": "Wo",
               "g2": "mixer_norm_g", "g3": "rms2_g", "w_down": "W_down",
               "g4": "mlp_norm_g"}


def build_net(cfg: Dict[str, Any], seed: int):
    from deeplearning4j_tpu.models.zoo.looped_lm import looped_lm

    tr = cfg["train"]
    if (tr["optimizer"], tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]) != \
            ("adam", 0.9, 0.999, 1e-8) or tr["param_dtype"] != "float32":
        raise ValueError("zoo.looped_lm trains with Adam(0.9, 0.999, 1e-8) "
                         "on float32 parameters; the configuration states "
                         f"otherwise: {tr}")
    if len(cfg["layer_types"]) != cfg["n_layer"]:
        raise ValueError("n_layer is not the length of layer_types")
    return looped_lm(cfg, learning_rate=float(tr["learning_rate"]),
                     compute_dtype=tr["compute_dtype"],
                     seed=int(seed) % 2 ** 30,
                     recompute_blocks=bool(tr["recompute_blocks"]),
                     entropy_weight=float(cfg["exit_entropy_weight"]),
                     kept_values=tr.get("kept_values"))


def to_program(ref: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """The reference's tree of leaves in the program's layout: the embedding,
    the blocks, the final norm and the exit head."""
    import jax.numpy as jnp

    tree = {layer_names[0]: {"W": ref["embed"]},
            layer_names[-2]: {"g": ref["final_g"]},
            layer_names[-1]: {"W": ref["head_w"],
                              "w_gate": ref["gate_w"][:, None],
                              "b_gate": ref["gate_b"].reshape(1)}}
    blocks = ref["blocks"]  # leaves stacked [n_layer, ...]
    for i, name in enumerate(layer_names[1:-2]):
        tree[name] = {prog: blocks[leaf][i] for leaf, prog in BLOCK_NAMES.items()}
        tree[name]["W_gate_up"] = jnp.concatenate(
            [blocks["w_gate"][i], blocks["w_up"][i]], axis=1)
    return tree


def to_reference(tree: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """A tree in the program's layout (parameters, or one of Adam's moments)
    in the reference's."""
    import jax.numpy as jnp

    head = tree[layer_names[-1]]
    layers = [tree[name] for name in layer_names[1:-2]]
    blocks = {leaf: jnp.stack([p[prog] for p in layers])
              for leaf, prog in BLOCK_NAMES.items()}
    blocks["w_gate"], blocks["w_up"] = jnp.split(
        jnp.stack([p["W_gate_up"] for p in layers]), 2, axis=2)
    return {"embed": tree[layer_names[0]]["W"],
            "final_g": tree[layer_names[-2]]["g"], "head_w": head["W"],
            "gate_w": head["w_gate"][:, 0], "gate_b": head["b_gate"][0],
            "blocks": blocks}


class LoopedTrainScanRun(TrainScanRun):
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

        def norms(params, updater, key):
            moved = jax.tree.map(jnp.subtract, params, self._make(key))
            m = jax.tree.map(lambda s: s["m"], updater,
                             is_leaf=lambda x: isinstance(x, dict) and "m" in x)
            return (plain.leaf_norms(to_reference(moved, names)),
                    plain.leaf_norms(to_reference(m, names)))

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
        ref = plain.follow(self.cfg, self.cfg["train"], self.seed,
                           self.tokens)
        gaps = correct.training_gaps(self.prog, ref)
        ok, compared = correct.judge(gaps, self.limits)
        return {"correct": ok, "compared": compared,
                "reference_s": time.perf_counter() - t0,
                "losses": {"program": list(self.prog["losses"]),
                           "reference": list(ref["losses"])}}


#: the run object, for whoever drives a cell by hand (scripts/profile_gpt.py)
Run = LoopedTrainScanRun


def rehearse(cell: Dict[str, Any]) -> None:
    """Shrink the cell in place to a tiny copy that the CPU can run: the same
    control flow at sizes that prove nothing about the chip."""
    cell["config"].update(REHEARSAL_CONFIG)
    cell["traffic"].update(REHEARSAL_TRAFFIC)
    cell["limits"] = cell["limits"]["rehearsal"]


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """Run one cell; the same return keys as ``train_scan.run``."""
    r = LoopedTrainScanRun(cell["config"], cell["traffic"], cell["limits"],
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
