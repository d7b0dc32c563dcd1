"""Deferred device→host score synchronization.

The reference's host loop read the score after every iteration for free
(same JVM heap); here every ``float(score)`` is a device→host round-trip
that stalls the dispatch queue — the chip finishes step N and sits idle
while the host fetches a 4-byte scalar before it will dispatch step N+1.
This module keeps per-step scores as device scalars in a small ring and
resolves them to host in ONE batched fetch only when

- a listener's declared ``frequency`` (``.frequency`` on
  PerformanceListener/StatsListener/CollectScores..., ``.n`` on
  ScoreIterationListener) says it would act on this iteration — a
  listener with no frequency attribute demands every iteration, which
  preserves the legacy immediate semantics for plain callables;
- the ring reaches capacity (bounds device-buffer retention); or
- the owning fit() call ends.

Listeners still receive the EXACT per-iteration score for every
iteration, in order — the calls just arrive in bursts (a listener that
reads ``model.params`` during a burst sees the flush-time parameters,
not the iteration-time ones; see MIGRATION.md "Host feed pipeline").

The companion ``host_step``/``set_host_step`` helpers mirror
``opt_state["step"]`` on the host so the fit loop never fetches the
device step counter per iteration (that ``int(...)`` was the second
hidden per-step sync). The mirror is invalidated by any external
``opt_state`` assignment (``nn/observed.py`` SyncedStateAttr pops it),
so checkpoint restores and ``fit_scan`` re-resolve lazily.
"""

from __future__ import annotations

import threading
from typing import List, Tuple

import numpy as np

from deeplearning4j_tpu.monitor import SCORE_SYNC_COUNTER, get_registry, span

HOST_STEP_MIRROR = "_host_step_mirror"


def host_step(model) -> int:
    """Host mirror of ``opt_state["step"]``; resolves (one device sync)
    only when the mirror is missing/invalidated."""
    v = model.__dict__.get(HOST_STEP_MIRROR)
    if v is None:
        v = int(model.opt_state["step"])
        model.__dict__[HOST_STEP_MIRROR] = v
    return v


def set_host_step(model, value: int) -> None:
    """Advance the mirror after a train-step's ``opt_state`` assignment
    (the assignment itself pops the mirror, so set AFTER it)."""
    model.__dict__[HOST_STEP_MIRROR] = int(value)


def listener_sync_period(cb) -> int:
    """How many iterations a listener tolerates between score
    resolutions: its declared frequency, else 1 (act-immediately)."""
    f = getattr(cb, "frequency", None)
    if f is None:
        f = getattr(cb, "n", None)
    try:
        f = int(f)
    except (TypeError, ValueError):
        return 1
    return max(1, f)


class DeferredScoreSync:
    """Ring of (iteration, device-scalar score) pending host resolution.

    ``push`` is called once per compiled step with the raw device score;
    ``flush`` resolves every pending score in one stacked fetch (ONE
    ``dl4j_score_sync_total`` tick), updates ``model._score`` to a host
    float, and replays the listener chain in iteration order."""

    def __init__(self, model, capacity: int = 64):
        self.model = model
        self.capacity = max(1, capacity)
        self._pending: List[Tuple[int, object]] = []
        # guards the take-all swap: a UI/observer thread may call flush()
        # while the training thread pushes — each pending score must
        # resolve (and replay to listeners) exactly once
        self._lock = threading.Lock()

    def __len__(self) -> int:
        return len(self._pending)

    def push(self, iteration: int, score) -> None:
        self._pending.append((iteration, score))
        m = self.model
        m._score = score  # device scalar; score() resolves on demand
        listeners = getattr(m, "listeners", None) or []
        due = any(iteration % listener_sync_period(cb) == 0
                  for cb in listeners)
        if due or len(self._pending) >= self.capacity:
            self.flush()

    def flush(self) -> None:
        with self._lock:
            if not self._pending:
                return
            pending, self._pending = self._pending, []
        import jax.numpy as jnp
        with span("score_sync", count=len(pending)):
            vals = np.asarray(jnp.stack([s for _, s in pending]))
        get_registry().counter(
            SCORE_SYNC_COUNTER,
            "Device->host score fetches (each is a chip round-trip)").inc()
        m = self.model
        m._score = float(vals[-1])
        listeners = list(getattr(m, "listeners", None) or [])
        for (it, _), v in zip(pending, vals):
            for cb in listeners:
                cb(m, it, float(v))


def score_sink(model) -> DeferredScoreSync:
    """The model's lazily-created deferred-score ring (one per model —
    ParallelWrapper and the container fit paths share it, so an
    end-of-fit flush drains everything either produced)."""
    s = model.__dict__.get("_deferred_scores")
    if s is None:
        s = model.__dict__["_deferred_scores"] = DeferredScoreSync(model)
    return s


def note_dispatch(model, sig) -> bool:
    """Record a train-step dispatch signature (program kind + operand
    shapes/dtypes); True the first time a signature is seen — that
    dispatch traces+compiles, so callers label its span ``compile`` —
    and every first-seen signature ticks ``dl4j_jit_cache_miss_total``.
    The signature set lives next to the model's jit cache and resets
    with it (``init()``)."""
    seen = model.__dict__.setdefault("_dispatch_sigs", set())
    if sig in seen:
        return False
    seen.add(sig)
    count_jit_cache_miss()
    return True


def count_jit_cache_miss() -> None:
    """Tick ``dl4j_jit_cache_miss_total``: a train-step dispatch traced
    and compiled (or loaded) a program."""
    from deeplearning4j_tpu.monitor import JIT_CACHE_MISS_COUNTER
    get_registry().counter(
        JIT_CACHE_MISS_COUNTER,
        "Train-step dispatches that traced+compiled a fresh program").inc()
