"""Driver for pre-training traffic: ``MultiLayerNetwork.fit_scan`` on a
``stage_scan``-staged set, one compiled program of ``steps_per_dispatch``
optimizer steps, dispatched whole until the window has passed.

Set-up builds ONE object, the net with its state and its compiled program,
drives it from the seed through its first dispatch (the warm dispatch) and
hands that same object to the window. What that first dispatch returned and
left in the state is what ``correct`` compares with the plain reference, once
the window has closed and the program's state is freed.

From the program this file takes only the system under test (``zoo.gpt``,
``stage_scan``, ``fit_scan``), its compile cache and its compile counter. It
knows two of the program's formats: the names of the parameter tree and of the
Adam state (``to_program`` / ``from_program`` below).
"""

from __future__ import annotations

import contextlib
import shutil
import tempfile
import time
from typing import Any, Dict

import numpy as np

from benchmarks import correct, trace_reduce
from benchmarks.reference import gpt_plain

#: dispatches that a traced run records: enough to hold the gaps between them
TRACE_DISPATCHES = 3
#: rows that the reference takes at a time, so that it fits beside its state
REFERENCE_ROWS_PER_BLOCK = 2
#: what `rehearse` shrinks a configuration and its traffic to, for the CPU
REHEARSAL_CONFIG = {"vocab_size": 512, "n_positions": 128, "n_embd": 64,
                    "n_inner": 256, "n_layer": 2, "n_head": 2}
REHEARSAL_TRAFFIC = {"seq_len": 64, "batch": 2}

#: reference block leaf -> the program's TransformerBlock leaf
BLOCK_NAMES = {"ln1_g": "ln1_g", "ln1_b": "ln1_b", "w_qkv": "Wqkv",
               "w_o": "Wo", "ln2_g": "ln2_g", "ln2_b": "ln2_b",
               "w_fc": "W1", "b_fc": "b1", "w_proj": "W2", "b_proj": "b2"}


def build_net(cfg: Dict[str, Any], seed: int):
    from deeplearning4j_tpu.models.zoo.transformer import gpt

    tr = cfg["train"]
    if cfg["n_inner"] % cfg["n_embd"]:
        raise ValueError("zoo.gpt takes a whole ffn_mult: n_inner / n_embd")
    if (tr["optimizer"], tr["adam_b1"], tr["adam_b2"], tr["adam_eps"]) != \
            ("adam", 0.9, 0.999, 1e-8) or tr["param_dtype"] != "float32":
        raise ValueError("zoo.gpt trains with Adam(0.9, 0.999, 1e-8) on "
                         "float32 parameters; the configuration states "
                         f"otherwise: {tr}")
    return gpt(vocab_size=cfg["vocab_size"], d_model=cfg["n_embd"],
               n_layers=cfg["n_layer"], num_heads=cfg["n_head"],
               max_len=cfg["n_positions"],
               ffn_mult=cfg["n_inner"] // cfg["n_embd"],
               dropout=float(cfg["resid_pdrop"]),
               learning_rate=float(tr["learning_rate"]),
               compute_dtype=tr["compute_dtype"], seed=int(seed) % 2 ** 30)


def to_program(ref: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """The reference's tree of leaves in the program's layout."""
    n = len(layer_names) - 2
    tree = {layer_names[0]: {"W": ref["wte"], "P": ref["wpe"]},
            layer_names[-1]: {"W": ref["head_w"], "b": ref["head_b"]}}
    for i in range(n):
        tree[layer_names[i + 1]] = {
            prog: ref["blocks"][name][i] for name, prog in BLOCK_NAMES.items()}
    return tree


def from_program(tree: Dict[str, Any], layer_names) -> Dict[str, Any]:
    """Per-leaf scalars in the program's layout -> the reference's names,
    block leaves stacked ``[n_layer]``."""
    first, last = tree[layer_names[0]], tree[layer_names[-1]]
    out = {"wte": first["W"], "wpe": first["P"],
           "head_w": last["W"], "head_b": last["b"]}
    for name, prog in BLOCK_NAMES.items():
        out["blocks." + name] = np.stack(
            [np.asarray(tree[ln][prog]) for ln in layer_names[1:-1]])
    return {k: np.asarray(v, np.float64) for k, v in out.items()}


class TrainScanRun:
    """One run of one cell. ``setup`` -> ``window`` -> ``check``."""

    def __init__(self, cfg, traffic, limits, seed: int):
        self.cfg, self.traffic, self.limits = cfg, traffic, limits
        self.seed = int(seed)
        self.batch = int(traffic["batch"])
        self.seq = int(traffic["seq_len"])
        self.k = int(traffic["steps_per_dispatch"])
        if self.seq > cfg["n_positions"]:
            raise ValueError("the traffic is longer than n_positions")

    # ------------------------------------------------------------- set-up
    def setup(self) -> Dict[str, float]:
        import jax
        import jax.numpy as jnp

        from deeplearning4j_tpu.datasets.dataset import DataSet
        from deeplearning4j_tpu.util.compile_cache import (CompileWatch,
                                                           enable_compile_cache)

        split = {}
        t0 = time.perf_counter()
        enable_compile_cache()  # <checkout>/.jax_cache, or where the environment says
        self.watch = CompileWatch()
        cfg = self.cfg
        net = self.net = build_net(cfg, self.seed)
        names = self.layer_names = [impl.name for impl in net.impls]
        split["build_s"] = time.perf_counter() - t0

        # the seed's weights and a fresh Adam state, on the device, in one
        # jitted call: what net.init() would build leaf by leaf
        t0 = time.perf_counter()
        key_ = gpt_plain.cfg_key(cfg)
        self._make = jax.jit(lambda key: to_program(
            gpt_plain.init_params(dict(key_), key), names))

        def fresh(key):
            params = self._make(key)
            zeros = lambda p: {"m": jnp.zeros_like(p), "v": jnp.zeros_like(p)}
            return params, {"step": jnp.zeros((), jnp.int32),
                            "updater": jax.tree.map(zeros, params)}

        net.params, net.opt_state = jax.jit(fresh)(gpt_plain.seed_key(self.seed))
        net.states = {impl.name: impl.init_state() for impl in net.impls}
        jax.block_until_ready(net.params)
        split["weights_s"] = time.perf_counter() - t0

        t0 = time.perf_counter()
        self.tokens = gpt_plain.make_tokens(cfg, self.seed, self.k,
                                            self.batch, self.seq)
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
                               gpt_plain.seed_key(self.seed))
        self.prog = {"losses": first_losses,
                     "dp_norms": from_program(jax.device_get(dp), names),
                     "m_norms": from_program(jax.device_get(m), names)}
        split["state_norms_s"] = time.perf_counter() - t0
        return split

    def dispatch(self):
        """The window's call: one compiled program of ``k`` optimizer steps;
        returns their losses (the fetch is the device sync)."""
        return self.net.fit_scan(None, self.batch, epochs=1, staged=self.staged)

    # ------------------------------------------------------------- window
    def window(self, seconds: float, trace: bool) -> Dict[str, Any]:
        import jax

        before = self.watch.snapshot()
        cap = TRACE_DISPATCHES if trace else None
        log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        attempted = failed = 0
        ctx = _device_trace(log_dir) if trace else contextlib.nullcontext()
        try:
            with ctx:
                t0 = now = time.perf_counter()
                while now - t0 < seconds and (cap is None or attempted < cap):
                    losses = self.dispatch()
                    attempted += 1
                    failed += int(not np.isfinite(losses).all())
                    now = time.perf_counter()
                wall = now - t0
            reduction = None
            if trace:
                reduction = trace_reduce.TraceReduction(
                    trace_reduce.read_xplane(log_dir))
        finally:
            if log_dir:
                shutil.rmtree(log_dir, ignore_errors=True)
        after = self.watch.snapshot()
        compiled = sum(after[k] - before[k]
                       for k in ("compiles", "cache_hits", "cache_misses"))
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in jax.devices())
        steps = attempted * self.k
        return {"wall_s": wall, "dispatches": attempted, "failed": failed,
                "steps": steps, "tokens": steps * self.batch * self.seq,
                "steps_per_dispatch": self.k, "batch": self.batch,
                "seq_len": self.seq, "compiles_in_window": compiled,
                "memory_peak_bytes": peak, "trace": reduction}

    # -------------------------------------------------------------- check
    def free(self) -> None:
        """Drop the program's state: the reference needs the room."""
        import jax

        self.net.params = self.net.opt_state = self.net.states = None
        self.net = self.staged = self._make = None
        jax.clear_caches()

    def check(self) -> Dict[str, Any]:
        self.free()
        t0 = time.perf_counter()
        ref = gpt_plain.follow(self.cfg, self.cfg["train"], self.seed,
                               self.tokens, REFERENCE_ROWS_PER_BLOCK)
        gaps = correct.training_gaps(self.prog, ref)
        ok, compared = correct.judge(gaps, self.limits)
        return {"correct": ok, "compared": compared,
                "reference_s": time.perf_counter() - t0,
                "losses": {"program": list(self.prog["losses"]),
                           "reference": list(ref["losses"])}}


@contextlib.contextmanager
def _device_trace(log_dir: str):
    """A device trace of the enclosed block; a profiler that cannot start is
    an error, not a run without a trace."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.raise_error_on_start_failure = True
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def rehearse(cell: Dict[str, Any]) -> None:
    """Shrink the cell in place to a tiny copy that the CPU can run: the same
    control flow at sizes that prove nothing about the chip."""
    cell["config"].update(REHEARSAL_CONFIG)
    cell["traffic"].update(REHEARSAL_TRAFFIC)
    cell["limits"] = cell["limits"]["rehearsal"]


def run(cell: Dict[str, Any], seed: int, seconds: float, trace: bool,
        t_start: float) -> Dict[str, Any]:
    """Run one cell; ``cell`` holds its ``config``, ``traffic`` and ``limits``
    as read from their files. Returns what the harness prints."""
    r = TrainScanRun(cell["config"], cell["traffic"], cell["limits"], seed)
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
