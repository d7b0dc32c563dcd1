"""Deterministic fault injectors — the harness that proves the
fault-tolerance layer.

A recovery path that has never run IS a bug; the only way to trust the
detect → isolate → recover machinery (crash-safe checkpoints, the
training supervisor, replica quarantine, broker reconnect/dead-letter)
is to inject each fault class deliberately. Every injector here is
deterministic: faults fire on explicit schedules (batch/call indices)
or from a SEEDED rng — a failing test replays bit-identically.

Injector ↔ fault domain map:

- :class:`FailingDataSetIterator` — NaN batches / mid-epoch iterator
  exceptions (training domain: supervisor rollback, feed-pipeline
  worker death);
- :class:`FlakyBroker` — scheduled transport errors on publish/consume
  (transport domain: reconnect, ``BrokerUnavailable`` surfacing);
- :func:`tear_file` / :func:`corrupt_file` / :class:`TornWrites` —
  torn and bit-flipped checkpoint artifacts, and a crash *between* the
  tmp write and the atomic install (checkpoint domain);
- :func:`poison_replica` — scheduled device errors on one serving
  replica (serving domain: retry, quarantine, probe reinstatement);
- :func:`poison_model` — scheduled device errors on ONE model across
  every replica (multi-model domain: the per-model circuit breaker
  must quarantine the model, leave the replicas serving its cotenants,
  and probe it back once the poison clears);
- :func:`kill_endpoint` / :class:`NetworkPartition` /
  :class:`WedgeEndpoint` — abrupt engine endpoint death, broker-level
  partitions, and liveness-without-progress wedges (routing domain:
  the InferenceRouter's heartbeat death detection, progress watchdog,
  failover, ejection and half-open reinstatement, and decode-stream
  migration);
- :class:`ChaosSchedule` / :func:`run_chaos_drill`
  (``faultinject/chaos.py``) — the COMPOSED drill: several injectors
  on one seeded event clock against a 3-endpoint fleet under mixed
  decode+classify load, asserting the global invariants (zero
  lost/duplicated tokens, zero stranded futures, zero leaked KV
  blocks, ``/healthz`` converges healthy) after drain;
- :class:`MeshShrink` / :class:`ChipFailure` — chips dying out of the
  mesh plane mid-epoch (mesh domain: checkpoint fallback, MeshPlane
  rebuild from the survivors, ``restore_checkpoint(mesh=...)``
  re-lowering, bitwise-deterministic resume on the smaller mesh);
- :class:`HostTierPressure` / :func:`run_hibernation_drill`
  (``faultinject/chaos.py``) — host-RAM KV-tier budget squeezes and
  the session-hibernation drill (KV-tiering domain: hibernate N
  sessions, kill the pinned endpoint, resume every session on the
  survivors down the host → shipped-blocks → journaled-prefix
  exactness ladder, with the squeeze forcing the refusal/fallback
  paths; zero leaked blocks on BOTH tiers after drain).
"""

from __future__ import annotations

import os
import random
import time
from typing import Iterable, Optional, Sequence

import numpy as np

from deeplearning4j_tpu.datasets.dataset import DataSet
from deeplearning4j_tpu.datasets.iterators import DataSetIterator
from deeplearning4j_tpu.streaming.broker import MessageBroker


class InjectedFault(RuntimeError):
    """The marker exception every injector raises — a test that sees a
    different exception type knows recovery swallowed the wrong thing."""


# ------------------------------------------------------------- training

class FailingDataSetIterator(DataSetIterator):
    """Wraps an iterator and injects batch-level faults on a
    deterministic schedule (0-based batch indices, counted across
    resets): ``nan_at`` batches keep their shape but carry all-NaN
    features (the classic diverged-upstream-pipeline batch — scores go
    NaN one step later); ``raise_at`` batches raise
    :class:`InjectedFault` from ``next()`` (a dead data source).
    ``p_nan`` adds seeded random NaN batches on top."""

    def __init__(self, wrapped: DataSetIterator, nan_at: Iterable[int] = (),
                 raise_at: Iterable[int] = (), p_nan: float = 0.0,
                 seed: int = 0):
        self._wrapped = wrapped
        self.nan_at = frozenset(int(i) for i in nan_at)
        self.raise_at = frozenset(int(i) for i in raise_at)
        self._p_nan = float(p_nan)
        self._rng = random.Random(seed)
        self._count = 0  # batches emitted, across resets (deterministic)
        self.injected_nan: list = []
        self.injected_raise: list = []

    def reset(self):
        self._wrapped.reset()

    def has_next(self):
        return self._wrapped.has_next()

    def batch(self):
        return self._wrapped.batch()

    def async_supported(self) -> bool:
        return self._wrapped.async_supported()

    def set_pre_processor(self, pp) -> None:
        self._wrapped.set_pre_processor(pp)

    def pre_processor(self):
        return self._wrapped.pre_processor()

    def _next_impl(self):
        idx = self._count
        self._count += 1
        if idx in self.raise_at:
            self.injected_raise.append(idx)
            raise InjectedFault(f"injected iterator failure at batch {idx}")
        ds = self._wrapped.next()
        if idx in self.nan_at or (self._p_nan > 0
                                  and self._rng.random() < self._p_nan):
            self.injected_nan.append(idx)
            feats = np.full_like(np.asarray(ds.features), np.nan)
            ds = DataSet(feats, ds.labels, ds.features_mask, ds.labels_mask)
        return ds


# ------------------------------------------------------------ transport

class FlakyBroker(MessageBroker):
    """Wraps any ``MessageBroker`` and fails scheduled calls (0-based,
    per operation kind) with ``exc`` — after its schedule is exhausted
    the broker heals. ``p_fail`` adds seeded random failures. The
    wrapped broker is NOT touched on a failed call (the op never
    happened — the at-most-once half of a real dropped connection)."""

    def __init__(self, wrapped: MessageBroker,
                 fail_publishes: Iterable[int] = (),
                 fail_consumes: Iterable[int] = (),
                 p_fail: float = 0.0, seed: int = 0,
                 exc=ConnectionError):
        self._wrapped = wrapped
        self.fail_publishes = frozenset(int(i) for i in fail_publishes)
        self.fail_consumes = frozenset(int(i) for i in fail_consumes)
        self._p_fail = float(p_fail)
        self._rng = random.Random(seed)
        self._exc = exc
        self._publishes = 0
        self._consumes = 0
        self.faults_injected = 0

    def _maybe_fail(self, idx: int, schedule: frozenset, what: str) -> None:
        if idx in schedule or (self._p_fail > 0
                               and self._rng.random() < self._p_fail):
            self.faults_injected += 1
            raise self._exc(f"injected broker failure on {what} #{idx}")

    def publish(self, topic: str, payload: bytes) -> None:
        idx, self._publishes = self._publishes, self._publishes + 1
        self._maybe_fail(idx, self.fail_publishes, "publish")
        self._wrapped.publish(topic, payload)

    def consume(self, topic: str, timeout: Optional[float] = None):
        idx, self._consumes = self._consumes, self._consumes + 1
        self._maybe_fail(idx, self.fail_consumes, "consume")
        return self._wrapped.consume(topic, timeout=timeout)

    def close(self) -> None:
        self._wrapped.close()


# ----------------------------------------------------------- checkpoint

def tear_file(path: str, keep_fraction: float = 0.5) -> None:
    """Truncate ``path`` to a prefix — the torn write a crash leaves
    behind on a filesystem without the atomic-install discipline."""
    size = os.path.getsize(path)
    keep = max(0, int(size * keep_fraction))
    with open(path, "rb+") as f:
        f.truncate(keep)


def corrupt_file(path: str, offset: int = -8, flip: int = 0xFF) -> None:
    """XOR one byte of ``path`` (negative offsets count from the end) —
    silent media corruption the CRC manifest must catch."""
    with open(path, "rb+") as f:
        f.seek(offset, os.SEEK_END if offset < 0 else os.SEEK_SET)
        pos = f.tell()
        byte = f.read(1)
        f.seek(pos)
        f.write(bytes([byte[0] ^ (flip & 0xFF)]))


class TornWrites:
    """Context manager that crashes the Nth atomic install (1-based
    count of ``os.replace``/``os.rename`` calls whose destination
    contains ``path_substr``) with :class:`InjectedFault` — simulating a
    preemption BETWEEN writing the temp artifact and renaming it into
    place, the exact window crash-safe persistence must survive."""

    def __init__(self, crash_on_call: int = 1,
                 path_substr: Optional[str] = None):
        self.crash_on_call = int(crash_on_call)
        self.path_substr = path_substr
        self.calls = 0
        self._orig_replace = None
        self._orig_rename = None

    def _wrap(self, orig):
        def patched(src, dst, *a, **k):
            if self.path_substr is None or self.path_substr in str(dst):
                self.calls += 1
                if self.calls == self.crash_on_call:
                    raise InjectedFault(
                        f"injected crash before installing {dst}")
            return orig(src, dst, *a, **k)
        return patched

    def __enter__(self) -> "TornWrites":
        self._orig_replace = os.replace
        self._orig_rename = os.rename
        os.replace = self._wrap(self._orig_replace)
        os.rename = self._wrap(self._orig_rename)
        return self

    def __exit__(self, *exc) -> None:
        os.replace = self._orig_replace
        os.rename = self._orig_rename


# -------------------------------------------------------------- serving

class ReplicaPoison:
    """Poison hook for ``ParallelInference``: the target replica's next
    ``failures`` dispatches (serving AND probe) raise
    :class:`InjectedFault`; afterwards the replica heals. Install via
    :func:`poison_replica` or pass as ``poison_hook=``."""

    def __init__(self, replica: int, failures: int):
        self.replica = int(replica)
        self.remaining = int(failures)
        self.hits = 0

    def __call__(self, replica_idx: int, shape: Sequence[int]) -> None:
        if replica_idx == self.replica and self.remaining > 0:
            self.remaining -= 1
            self.hits += 1
            raise InjectedFault(
                f"injected device fault on replica {replica_idx}")


def poison_replica(engine, replica: int = 0, failures: int = 2
                   ) -> ReplicaPoison:
    """Arm a :class:`ReplicaPoison` on a live engine (the engine's
    ``poison_hook`` seam); returns the handle so the test can watch
    ``remaining``/``hits``. ``failures=2`` defeats the single same-replica
    retry and forces a quarantine; the next probe then heals it."""
    poison = ReplicaPoison(replica, failures)
    engine._poison_hook = poison
    return poison


class ModelPoison:
    """Model-scoped poison hook for a multi-model ``ParallelInference``:
    dispatches (serving AND probe) of the target ``model`` — any
    replica, optionally one ``version`` — raise :class:`InjectedFault`
    for the next ``failures`` hits; afterwards the model heals.
    ``wants_model=True`` makes the engine pass the dispatch's model
    name to the hook. The recovery contract under test: the model's
    circuit breaker opens (its batch fails with ``ModelQuarantined``
    and its submits reject at admission), replicas stay in the pool for
    cotenant models, and a probe closes the breaker once healed."""

    wants_model = True

    def __init__(self, model: str, failures: int,
                 version: Optional[int] = None):
        self.model = model
        self.version = version  # None = any version of the model
        self.remaining = int(failures)
        self.hits = 0

    def __call__(self, replica_idx: int, shape: Sequence[int],
                 model: Optional[str]) -> None:
        if model == self.model and self.remaining > 0:
            self.remaining -= 1
            self.hits += 1
            raise InjectedFault(
                f"injected device fault for model {model!r} "
                f"on replica {replica_idx}")


def poison_model(engine, model: str, failures: Optional[int] = None,
                 version: Optional[int] = None) -> ModelPoison:
    """Arm a :class:`ModelPoison` on a live registry-mode engine.
    ``failures`` counts per-dispatch-attempt hits: opening the breaker
    takes ``breaker_threshold`` FAILED BATCHES, each burning
    ``1 + max_batch_retries`` attempts — the default arms exactly that
    many (e.g. 4 with the stock 1-retry engine and threshold 2), so the
    model's breaker opens and then the very next probe heals it.
    Cotenant models keep serving throughout."""
    if failures is None:
        threshold = 2
        if getattr(engine, "_registry", None) is not None:
            threshold = engine._registry.breaker_threshold
        failures = threshold * (1 + engine.max_batch_retries)
    poison = ModelPoison(model, failures, version)
    engine._poison_hook = poison
    return poison


# ----------------------------------------------------------------- mesh

class ChipFailure(InjectedFault):
    """A chip (subset of the mesh's devices) died mid-run. Carries the
    SURVIVING device ids so the recovery path can rebuild a smaller
    MeshPlane from exactly the devices the drill left alive."""

    def __init__(self, message: str, survivor_ids: Sequence[int]):
        super().__init__(message)
        self.survivor_ids = tuple(int(i) for i in survivor_ids)


class MeshShrink:
    """Deterministic mesh-shrink drill: at training step
    ``fail_at_step`` (0-based, counted across :meth:`step` calls) the
    drill raises :class:`ChipFailure` naming ``survivors`` devices
    chosen by a SEEDED rng from the ``total`` the mesh started with —
    the stand-in for chips dropping out of the plane mid-epoch.

    The recovery contract under test (tests/test_mesh_plane.py, marker
    ``faultinject``): the training loop falls back to its newest
    checkpoint, rebuilds a MeshPlane from the survivors, restores via
    ``restore_checkpoint(..., mesh=...)`` (saved shards re-lowered onto
    the smaller topology) and resumes — with a bitwise-identical
    forward on the restored step across drill reruns. Same
    ``(seed, fail_at_step, survivors)`` → identical failure step AND
    identical survivor set, so a failing drill replays exactly."""

    def __init__(self, fail_at_step: int, survivors: int,
                 total: Optional[int] = None, seed: int = 0):
        if survivors < 1:
            raise ValueError(f"survivors must be >= 1, got {survivors}")
        self.fail_at_step = int(fail_at_step)
        self.survivors = int(survivors)
        self.total = total
        self.seed = int(seed)
        self.steps_seen = 0
        self.fired = False

    def survivor_ids(self, total: Optional[int] = None) -> tuple:
        """The seeded choice of surviving device ids out of ``total``
        (ascending — a stable mesh rebuild order)."""
        n = int(total if total is not None else self.total)
        if self.survivors > n:
            raise ValueError(f"{self.survivors} survivors > {n} devices")
        rng = random.Random(self.seed)
        return tuple(sorted(rng.sample(range(n), self.survivors)))

    def step(self, total: Optional[int] = None) -> int:
        """Account one training step; raises :class:`ChipFailure` when
        the schedule says the chips die. Returns the step index."""
        idx = self.steps_seen
        self.steps_seen += 1
        if idx == self.fail_at_step and not self.fired:
            self.fired = True
            ids = self.survivor_ids(total)
            raise ChipFailure(
                f"injected chip failure at step {idx}: "
                f"{self.survivors} of {total if total is not None else self.total} "
                f"devices survive ({list(ids)})", ids)
        return idx


class SliceKill:
    """Kill-a-chip injector for a live SERVING SLICE (the ISSUE-12
    drill): from ``fail_at`` (0-based count of engine dispatches —
    classify batches, decode bursts and probes all tick the same
    clock), every dispatch raises :class:`ChipFailure` naming the
    slice's SURVIVORS — the seeded ``victim`` chip chosen from the
    slice's devices is gone for good, which is why the schedule never
    heals: a dead chip's dispatches stay dead until the fleet rebuilds
    the slice from the survivors (``LocalFleet.rebuild_slice``).

    Installable as BOTH engine seams at once: the ``poison_hook``
    (classify dispatches; ``wants_model`` so multi-model engines work)
    and the continuous scheduler's ``burst_hook`` (decode bursts) —
    ``LocalFleet.kill_chip`` arms both. Same ``(devices, seed,
    fail_at)`` ⇒ same victim, same survivor set, same failure tick:
    the drill replays bit-identically."""

    wants_model = True

    def __init__(self, plane_or_devices, victim: Optional[int] = None,
                 seed: int = 0, fail_at: int = 0):
        mesh = getattr(plane_or_devices, "mesh", None)
        if mesh is not None:
            devices = sorted(int(d.id) for d in mesh.devices.flat)
        else:
            devices = sorted(int(i) for i in plane_or_devices)
        if not devices:
            raise ValueError("SliceKill needs the slice's devices")
        self.devices = tuple(devices)
        if victim is not None:
            victim = int(victim)
            if victim not in self.devices:
                raise ValueError(
                    f"victim chip {victim} not in slice {devices}")
        else:
            victim = devices[random.Random(seed).randrange(len(devices))]
        self.victim = victim
        self.survivors = tuple(i for i in self.devices if i != victim)
        self.fail_at = int(fail_at)
        self.calls = 0
        self.hits = 0

    def __call__(self, *args) -> None:
        idx = self.calls
        self.calls += 1
        if idx >= self.fail_at:
            self.hits += 1
            raise ChipFailure(
                f"injected chip {self.victim} failure in slice "
                f"{list(self.devices)} at dispatch {idx} "
                f"(survivors {list(self.survivors)})", self.survivors)


# -------------------------------------------------------------- routing

class BurstKill:
    """Kill-mid-burst injector for the continuous decode scheduler
    (``ContinuousDecodeScheduler``'s ``burst_hook`` /
    ``ParallelInference(decode_burst_hook=...)`` seam): the hook fires
    once per accounted burst dispatch, and burst indices
    ``[after, after + failures)`` raise :class:`InjectedFault` BEFORE
    the device program runs — a deterministic stand-in for a dispatch
    dying under live sequences. The recovery contract under test: the
    scheduler fails every riding sequence's future with a typed
    ``DecodeBurstError``, frees their KV blocks immediately (pool free
    count returns to total after drain — never a leaked block), and
    keeps serving later admissions. Optionally scoped to one ``lane``
    key (a (model, version) pair) in multi-model schedulers."""

    def __init__(self, after: int = 1, failures: int = 1,
                 lane: Optional[tuple] = None):
        self.after = int(after)
        self.failures = int(failures)
        self.lane = lane
        self.calls = 0
        self.hits = 0

    def __call__(self, lane_key, burst_index: int) -> None:
        if self.lane is not None and tuple(lane_key) != tuple(self.lane):
            return
        idx = self.calls
        self.calls += 1
        if self.after <= idx < self.after + self.failures:
            self.hits += 1
            raise InjectedFault(
                f"injected burst kill at dispatch {idx} (lane {lane_key})")


class WedgeEndpoint:
    """Wedge injector for the serving fleet: the named member keeps
    heartbeating (liveness intact) but silently drops every consumed
    request — zero progress with work queued, the failure mode a
    heartbeat-only health plane can NEVER see. Context-managed so the
    drill always unwedges::

        with WedgeEndpoint(fleet, "engine-0"):
            ...  # router's wedge watchdog must eject + migrate

    The recovery contract under test: the router's progress watchdog
    (``wedge_timeout_s``) observes flat ``resolved``/``served``/burst
    counters while its own inflight count is nonzero, ejects the
    endpoint exactly like a crash, and the endpoint's in-flight
    requests resolve through timeout → failover (streams migrate with
    their journaled prefix)."""

    def __init__(self, fleet, name: str):
        self.fleet = fleet
        self.name = name
        self.active = False

    def wedge(self) -> "WedgeEndpoint":
        self.fleet.wedge(self.name)
        self.active = True
        return self

    def heal(self) -> None:
        if self.active:
            self.active = False
            try:
                self.fleet.unwedge(self.name)
            except KeyError:
                pass  # the member was removed while wedged

    def __enter__(self) -> "WedgeEndpoint":
        return self.wedge()

    def __exit__(self, *exc) -> None:
        self.heal()


class HostTierPressure:
    """Budget-squeeze injector for the paged pool's host-RAM tier (the
    KV-tiering PR's ``set_host_budget`` seam): while active, the
    targeted pools' host budgets shrink to ``budget`` blocks, so
    swap-outs, prefix-cache demotions and shipped-block imports hit
    the REFUSAL path (``swap_out``/``host_insert`` return None) and
    the caller must take its pre-tier fallback — free, cache-drop, or
    journaled re-prefill. Existing host entries are never dropped
    (the pool's shrink contract), so hibernated sessions stay exact
    under pressure; only NEW demotions are squeezed. Context-managed,
    restoring the original budgets on exit::

        with HostTierPressure(engine, budget=0):
            ...  # every swap-out refused; resume must still be exact

    Targets a ``PagedKVCachePool``, a ``ContinuousDecodeScheduler``,
    or a live continuous ``ParallelInference`` (every lane pool of the
    scheduler is squeezed). Deterministic by construction — no clocks,
    no rng; the squeeze window is the ``with`` block."""

    def __init__(self, target, budget: int = 0):
        if hasattr(target, "set_host_budget"):
            pools = [target]
        elif hasattr(target, "_pools"):
            pools = list(target._pools.values())
        elif getattr(target, "_scheduler", None) is not None:
            pools = list(target._scheduler._pools.values())
        else:
            raise ValueError(
                "HostTierPressure needs a PagedKVCachePool, a "
                "continuous scheduler, or a continuous engine with a "
                "built scheduler")
        self.pools = pools
        self.budget = max(0, int(budget))
        self._saved: list = []
        self.active = False

    def squeeze(self) -> "HostTierPressure":
        if not self.active:
            self._saved = [p.host_budget() for p in self.pools]
            for p in self.pools:
                p.set_host_budget(self.budget)
            self.active = True
        return self

    def heal(self) -> None:
        if self.active:
            self.active = False
            for p, old in zip(self.pools, self._saved):
                p.set_host_budget(old)

    def __enter__(self) -> "HostTierPressure":
        return self.squeeze()

    def __exit__(self, *exc) -> None:
        self.heal()


def kill_endpoint(fleet, name: str) -> str:
    """Process-kill injector for the serving fleet: abruptly stop the
    named endpoint's engine worker — consumed requests vanish without
    replies and heartbeats go silent (SIGKILL's wire signature).
    Returns the name so tests can ``fleet.restart(name)``
    after asserting the failover. The router must keep every affected
    future resolving (timeout → failover) and eject the endpoint."""
    fleet.kill(name)
    return name


class NetworkPartition(MessageBroker):
    """Broker wrapper that partitions deterministically: while
    ``active``, operations on topics matching ``topic_substr`` (all
    topics when None) fail with ``exc`` (default: swallow publishes /
    return-None consumes when ``silent=True`` — a black-holing
    partition — else raise ``ConnectionError``, a detectable one).
    ``heal()`` reconnects. Wrap the broker handed to one side of a
    channel to partition exactly that side."""

    def __init__(self, wrapped: MessageBroker,
                 topic_substr: Optional[str] = None,
                 silent: bool = False, exc=ConnectionError):
        self._wrapped = wrapped
        self.topic_substr = topic_substr
        self.silent = bool(silent)
        self._exc = exc
        self.active = False
        self.dropped = 0

    def partition(self) -> "NetworkPartition":
        self.active = True
        return self

    def heal(self) -> None:
        self.active = False

    def _cut(self, topic: str) -> bool:
        return self.active and (self.topic_substr is None
                                or self.topic_substr in topic)

    def publish(self, topic: str, payload: bytes) -> None:
        if self._cut(topic):
            self.dropped += 1
            if self.silent:
                return  # black hole: the message is gone
            raise self._exc(f"injected partition on publish to {topic}")
        self._wrapped.publish(topic, payload)

    def consume(self, topic: str, timeout: Optional[float] = None):
        if self._cut(topic):
            self.dropped += 1
            if self.silent:
                if timeout:
                    time.sleep(min(timeout, 0.05))
                return None  # looks exactly like an idle topic
            raise self._exc(f"injected partition on consume of {topic}")
        return self._wrapped.consume(topic, timeout=timeout)

    def ping(self) -> float:
        if self.active and self.topic_substr is None:
            self.dropped += 1
            raise self._exc("injected partition on ping")
        return self._wrapped.ping()

    def close(self) -> None:
        self._wrapped.close()


# ------------------------------------------------------ composed drill
# (imported last: chaos.py composes the injectors defined above)

from deeplearning4j_tpu.faultinject.chaos import (  # noqa: E402,F401
    ACTIONS as CHAOS_ACTIONS,
    SLICE_ACTIONS,
    ChaosEvent,
    ChaosSchedule,
    run_chaos_drill,
    run_hibernation_drill,
    run_slice_drill,
)
