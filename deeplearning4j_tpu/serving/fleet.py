"""LocalFleet — spawn, kill, restart and autoscale engine endpoints.

The fleet manager the tests and the ``router_slo`` bench drive: it
owns a broker, spawns engine workers (each with its OWN
``ParallelInference`` engine from ``engine_factory``), wires a
``RemoteEndpoint`` per worker, and applies
:class:`~deeplearning4j_tpu.serving.policy.ScalePolicy` decisions.

Workers run on daemon threads in THIS process, reached through the
same broker wire protocol remote workers use: an accelerator belongs
to one process at a time, so one process drives every chip of a host
(a child spawned by a parent that has touched JAX could never claim
one). ``kill()`` stops a worker abruptly — no replies, no heartbeats,
requests already consumed vanish — which is exactly the wire signature
of SIGKILL on an engine process, while staying deterministic.
"""

from __future__ import annotations

import itertools
import logging
import threading
import time
from typing import Any, Callable, Dict, List, Optional

from deeplearning4j_tpu.serving.endpoint import RemoteEndpoint
from deeplearning4j_tpu.serving.policy import ScaleDecision, ScalePolicy
from deeplearning4j_tpu.serving.worker import EngineWorker
from deeplearning4j_tpu.streaming.broker import InMemoryBroker

logger = logging.getLogger("deeplearning4j_tpu")


class _Member:
    """One fleet slot: endpoint + the worker thread backing it."""

    def __init__(self, name: str, endpoint: RemoteEndpoint,
                 worker: EngineWorker, plane=None):
        self.name = name
        self.endpoint = endpoint
        self.worker = worker
        # mesh-slice backing (slice_width mode): the MeshPlane this
        # member's engine is sharded over — rebuild_slice narrows it
        self.plane = plane


class LocalFleet:
    """Manage a fleet of engine endpoints behind one broker.

    ``engine_factory()`` must return a fresh started
    ``ParallelInference``. ``router=`` (optional) keeps
    an :class:`InferenceRouter` membership in sync with the fleet.
    """

    def __init__(self, engine_factory: Callable,
                 service_prefix: str = "engine",
                 router=None,
                 heartbeat_s: float = 0.1,
                 request_timeout_s: float = 5.0,
                 heartbeat_timeout_s: float = 1.0,
                 slice_width: Optional[int] = None,
                 slice_devices: Optional[List] = None):
        self.engine_factory = engine_factory
        # mesh-sharded slices: each endpoint's engine runs on a
        # slice_width-chip MeshPlane carved from slice_devices (default:
        # every local device); engine_factory is then called WITH the
        # plane — restore the mesh-portable checkpoint onto it. The
        # device budget is explicit: killing a chip shrinks a member's
        # slice (rebuild_slice), trading width for replica count.
        self.slice_width = None if slice_width is None else int(slice_width)
        self._slice_free: List = []
        if self.slice_width is not None:
            import jax
            self._slice_free = list(
                slice_devices if slice_devices is not None
                else jax.devices())
        self.service_prefix = service_prefix
        self.router = router
        self.heartbeat_s = float(heartbeat_s)
        self.request_timeout_s = float(request_timeout_s)
        self.heartbeat_timeout_s = float(heartbeat_timeout_s)
        self._members: Dict[str, _Member] = {}
        self._ids = itertools.count()
        self._lock = threading.Lock()
        self._broker = InMemoryBroker()

    # --------------------------------------------------------- members

    def _carve_slice(self, width: int):
        """Claim ``width`` devices from the free budget and build the
        slice's MeshPlane (via the sanctioned parallel.mesh factory —
        serving code never constructs a raw Mesh)."""
        from deeplearning4j_tpu.parallel.mesh import MeshPlane
        if len(self._slice_free) < width:
            raise RuntimeError(
                f"no device budget for a {width}-chip slice "
                f"({len(self._slice_free)} free)")
        devs, self._slice_free = (self._slice_free[:width],
                                  self._slice_free[width:])
        return MeshPlane.build({"tp": width}, devices=devs)

    def add_endpoint(self, name: Optional[str] = None) -> RemoteEndpoint:
        name = name or f"{self.service_prefix}-{next(self._ids)}"
        service = name
        plane = None
        if self.slice_width is not None:
            plane = self._carve_slice(self.slice_width)
            engine = self.engine_factory(plane)
        else:
            engine = self.engine_factory()
        worker = EngineWorker(engine, self._broker, service, name=name,
                              heartbeat_s=self.heartbeat_s)
        endpoint = RemoteEndpoint(
            self._broker, service, name=name,
            request_timeout_s=self.request_timeout_s,
            heartbeat_timeout_s=self.heartbeat_timeout_s)
        with self._lock:
            self._members[name] = _Member(name, endpoint, worker, plane)
        if self.router is not None:
            self.router.add_endpoint(endpoint)
        return endpoint

    def names(self) -> List[str]:
        with self._lock:
            return sorted(self._members)

    def endpoint(self, name: str) -> RemoteEndpoint:
        with self._lock:
            return self._members[name].endpoint

    def timeseries_summary(self) -> Dict[str, Any]:
        """Fleet-wide window answer from the heartbeat-carried
        per-endpoint summaries (engine batch fill ratio, jit-miss
        rate, worker served delta): counts and rates add across
        members, means combine count-weighted, p99 takes the max —
        the same merge :meth:`InferenceRouter.fleet_snapshot`
        reports, available without a router."""
        from deeplearning4j_tpu.monitor import merge_summaries
        with self._lock:
            members = list(self._members.values())
        summaries = []
        for m in members:
            try:
                ts = (m.endpoint.stats() or {}).get("timeseries")
            except Exception:
                continue  # a dead member answers no window queries
            if isinstance(ts, dict):
                summaries.append(ts)
        return merge_summaries(summaries)

    def wait_ready(self, timeout: float = 10.0) -> bool:
        """Block until every member heartbeats alive (bounded)."""
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                members = list(self._members.values())
            if members and all(m.endpoint.alive() for m in members):
                return True
            time.sleep(5e-3)
        return False

    # ------------------------------------------------------ fault seams

    def kill(self, name: str) -> None:
        """Abrupt endpoint death (the faultinject process-kill seam):
        the worker stops without replies or heartbeats. The endpoint
        object stays registered — the router observes the death through
        missed heartbeats and reply timeouts, exactly as it would a
        remote host loss."""
        with self._lock:
            m = self._members[name]
        m.worker.kill()
        try:  # the worker's engine dies with it
            m.worker.engine.shutdown(drain=False)
        except BaseException:
            pass
        logger.info("fleet: killed %s", name)

    def wedge(self, name: str) -> None:
        """Faultinject seam: the member keeps heartbeating but silently
        drops every consumed request — the liveness-without-progress
        failure the router's wedge watchdog exists for."""
        with self._lock:
            m = self._members[name]
        m.worker.wedge()
        logger.info("fleet: wedged %s", name)

    def unwedge(self, name: str) -> None:
        with self._lock:
            m = self._members[name]
        m.worker.unwedge()
        logger.info("fleet: unwedged %s", name)

    def kill_chip(self, name: str, victim: Optional[int] = None,
                  seed: int = 0):
        """Faultinject seam (slice mode): arm a seeded
        :class:`~deeplearning4j_tpu.faultinject.SliceKill` on the
        member's engine — its next dispatch (classify batch or decode
        burst) raises a ``ChipFailure`` naming the slice's survivors,
        the engine poisons the whole slice (typed ``SliceDegraded`` in
        heartbeats, never silence), and the router migrates its
        streams. Returns the injector so the drill can read the victim
        chip it chose."""
        from deeplearning4j_tpu.faultinject import SliceKill
        with self._lock:
            m = self._members[name]
        if m.plane is None:
            raise RuntimeError("kill_chip() is a slice-mode seam")
        eng = m.worker.engine
        inj = SliceKill(m.plane, victim=victim, seed=seed, fail_at=0)
        eng._poison_hook = inj
        if eng._scheduler is not None:
            eng._scheduler._burst_hook = inj
        else:
            eng._decode_burst_hook = inj
        logger.info("fleet: armed chip kill on %s (victim chip %d)",
                    name, inj.victim)
        return inj

    def rebuild_slice(self, name: str, width: Optional[int] = None) -> int:
        """Elastic recovery: the member's slice died (a chip inside it
        failed) — stop the poisoned worker, rebuild a NARROWER slice
        from the survivors (default: half the old width, the 8→4→1
        mesh-portable-checkpoint ladder), hand the new plane to
        ``engine_factory`` (which restores the checkpoint onto it), and
        bring the worker back on the SAME service topics. Unused
        survivor devices return to the free budget — capacity lost as
        width comes back as replica count through the normal ``add``
        path. Returns the new width."""
        from deeplearning4j_tpu.faultinject import ChipFailure
        from deeplearning4j_tpu.monitor import (SLICE_REBUILDS_COUNTER,
                                                get_registry)
        with self._lock:
            m = self._members[name]
        if m.plane is None:
            raise RuntimeError("rebuild_slice() is a slice-mode seam")
        old_devs = list(m.plane.mesh.devices.flat)
        # the dead chip: named by the engine's ChipFailure when it
        # carries survivor ids, else assume the first chip died
        dead_ids = None
        err = getattr(m.worker.engine, "_slice_dead", None)
        seen = 0
        while err is not None and seen < 8:
            if isinstance(err, ChipFailure):
                dead_ids = {d.id for d in old_devs} \
                    - set(err.survivor_ids)
                break
            err = err.__cause__
            seen += 1
        if dead_ids is None:
            dead_ids = {old_devs[0].id}
        survivors = [d for d in old_devs if d.id not in dead_ids]
        new_width = int(width) if width is not None \
            else max(1, len(old_devs) // 2)
        new_width = min(new_width, max(1, len(survivors)))
        if not m.worker._killed.is_set():
            m.worker.kill()
        try:
            m.worker.engine.shutdown(drain=False)
        except BaseException:
            pass
        from deeplearning4j_tpu.parallel.mesh import MeshPlane
        plane = MeshPlane.build({"tp": new_width},
                                devices=survivors[:new_width])
        engine = self.engine_factory(plane)
        with self._lock:
            m.plane = plane
            m.worker = EngineWorker(engine, self._broker, name, name=name,
                                    heartbeat_s=self.heartbeat_s)
            # leftover survivors go back to the budget: width traded
            # for replica count under the ScalePolicy's add path
            self._slice_free.extend(survivors[new_width:])
        get_registry().counter(
            SLICE_REBUILDS_COUNTER,
            "Serving slices rebuilt at a narrower width after a chip "
            "death (mesh-portable checkpoint restored onto survivors)",
            width=str(new_width)).inc()
        from deeplearning4j_tpu.monitor.reqtrace import flight_event
        flight_event("slice_rebuild", endpoint=name, width=new_width,
                     survivors=len(survivors))
        logger.info("fleet: rebuilt %s as a %d-chip slice (%d survivors)",
                    name, new_width, len(survivors))
        return new_width

    def restart(self, name: str) -> None:
        """Bring a killed member back on the SAME service topics (the
        endpoint reconnects through its existing consumer threads)."""
        with self._lock:
            m = self._members[name]
        if not m.worker._killed.is_set():
            m.worker.kill()
        engine = (self.engine_factory(m.plane) if m.plane is not None
                  else self.engine_factory())
        m.worker = EngineWorker(engine, self._broker, name, name=name,
                                heartbeat_s=self.heartbeat_s)
        logger.info("fleet: restarted %s", name)

    def remove_endpoint(self, name: str,
                        drain_timeout: float = 30.0) -> None:
        """Planned scale-down: drain, stop, deregister — zero lost
        requests."""
        with self._lock:
            m = self._members.pop(name)
        if self.router is not None:
            self.router.remove_endpoint(name)
        m.worker.drain_and_stop(timeout=drain_timeout)
        m.endpoint.close()

    # -------------------------------------------------------- autoscale

    def apply(self, decisions: List[ScaleDecision]) -> List[str]:
        """Apply ScalePolicy decisions; returns a log of actions."""
        log = []
        for d in decisions:
            if d.action == "add":
                ep = self.add_endpoint()
                log.append(f"add {ep.name}: {d.reason}")
            elif d.action == "remove" and d.endpoint in self._members:
                self.remove_endpoint(d.endpoint)
                log.append(f"remove {d.endpoint}: {d.reason}")
            elif d.action == "rebuild" and d.endpoint in self._members:
                w = self.rebuild_slice(d.endpoint)
                log.append(f"rebuild {d.endpoint} width={w}: {d.reason}")
        return log

    def autoscale(self, policy: ScalePolicy,
                  now: Optional[float] = None) -> List[str]:
        """One policy step against the live router snapshot."""
        if self.router is None:
            raise RuntimeError("autoscale needs a router")
        snap = self.router.fleet_snapshot()
        return self.apply(policy.decide(
            snap, time.monotonic() if now is None else now))

    # -------------------------------------------------------- lifecycle

    def shutdown(self, drain: bool = True) -> None:
        for name in self.names():
            try:
                if drain:
                    self.remove_endpoint(name, drain_timeout=10.0)
                else:
                    self.kill(name)
                    with self._lock:
                        m = self._members.pop(name, None)
                    if m is not None:
                        m.endpoint.close()
            except KeyError:
                pass

    def __enter__(self) -> "LocalFleet":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.shutdown(drain=exc_type is None)
