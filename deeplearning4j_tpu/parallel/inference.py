"""ParallelInference — dynamic micro-batching inference engine.

Parity: ``deeplearning4j-parallel-wrapper/.../ParallelInference.java``
(BATCHED mode: observables queued, a batching thread coalesces them,
worker threads run the model; INPLACE mode maps to ``coalesce=False``).
The serving problem is the one Clipper (NSDI '17) and TF-Serving's
adaptive batcher solve: per-request dispatch leaves the chip idle
between tiny programs and pays one host→device→host round-trip per
request, so concurrent requests must be coalesced into padded
micro-batches that amortize dispatch and fill the MXU.

Mechanics:

- ``submit(x)`` (thread-safe, returns a Future) / ``output(x)``
  (blocking facade) enqueue requests onto a bounded admission queue —
  backpressure is configurable reject-vs-block;
- a dispatcher thread coalesces same-shaped requests into one batch
  under a ``max_batch_size`` / ``max_latency_ms`` policy, then pads the
  ragged row count up onto the ``bucket_sizes`` ladder (the
  ShapeBucketingIterator doctrine applied to serving) so every request
  mix dispatches one of a small set of pre-compilable programs;
- worker threads — one per model replica, params/states pinned on their
  ``jax.devices()`` entry once at construction — pull formed batches
  from a shared queue (idle workers steal work: least-loaded dispatch
  for free), run the container's jit-cached batched output program, and
  deliver each caller's de-padded rows to its Future;
- ``warmup(shapes)`` AOT-compiles the full bucket × replica program set
  so first-request latency is bounded and the steady-state serve loop
  performs zero XLA compiles (observable via
  ``dl4j_jit_cache_miss_total``);
- ``shutdown()`` drains in-flight work and re-raises the first worker
  error; a worker error also lands on every affected Future.

Serving degradation (detect → isolate → recover): a per-batch device
error is retried once on the same replica; a second failure
**quarantines** the replica — it leaves the dispatch pool, the
in-flight batch is redispatched to the surviving replicas (futures are
never stranded: when no survivor remains the batch's futures carry the
error), and the engine keeps serving at reduced capacity. A
quarantined replica is **probed** every ``probe_interval_ms`` with a
known-good single-row program (or reinstated optimistically when no
good shape has been seen yet) and rejoins the pool when the probe
passes. ``stats()["quarantined"]`` / ``dl4j_fault_quarantined_replicas``
surface the degraded state — ``UiServer /healthz`` turns 503-degraded
while any replica is out.

Exactness: batched rows are bitwise-equal to an unbatched ``output()``
run (row-independent programs; the same property PR 2's bucketing
parity test pins for training). Models with cross-batch statistics
(``LayerImpl.batch_statistics`` — MoE capacity routing) auto-disable
coalescing: each request dispatches alone, unpadded.

Generation serving: ``submit_generate(prompt_ids, max_new_tokens)``
routes decode requests through the fused generation engine
(``nn/generate.py`` — bucketed prefill + one-scan decode with
on-device sampling). Requests coalesce per (prompt-length bucket,
max_new_tokens, sampler) across replicas; per-row traced lengths and
PRNG keys make a request's tokens identical to a solo
``net.generate`` run regardless of coalescing, and
``warmup_generate`` AOT-compiles the (bucket × row-bucket × replica)
program set so steady-state decode serving performs zero XLA
compiles.

Continuous batching (``continuous=True``): ``submit_generate`` routes
through a :class:`~deeplearning4j_tpu.serving.continuous.
ContinuousDecodeScheduler` instead of the per-(bucket, max_new,
sampler) coalescing dispatcher — decode runs in short fixed-K bursts
over a paged KV block pool (``nn/kvpool.py``); between bursts the
scheduler retires finished rows (freeing their blocks immediately),
admits queued prefills into the vacated batch slots, and preempts
deterministically (lowest-priority / youngest-first, re-queued with
the generated prefix) when the pool is exhausted. ``decode_slots`` /
``decode_burst`` / ``kv_block_size`` / ``kv_blocks`` size the slot
batch and the pool; ``stats()["scheduler"]`` exposes the live state
and ``/healthz/ready`` gates on its warmup.

Multi-model serving (``registry=`` mode): instead of one pinned net,
the engine serves every model in a
:class:`~deeplearning4j_tpu.serving.registry.ModelRegistry` —
``submit(x, model=..., version=...)``. Versions resolve at submit
time (so a registry deploy cuts traffic over atomically — in-flight
requests finish on the version they resolved), params pin per device
through the registry's LRU/priority memory budget, batches never mix
models (the coalescing signature carries model+version), each model
can override the row-bucket ladder, and formed batches dispatch
through a **deficit-weighted round-robin** queue so one hot model
cannot starve its cotenants. A model whose batches fault across more
than one replica trips its per-model circuit breaker: its futures
fail with :class:`~deeplearning4j_tpu.serving.registry.
ModelQuarantined`, its submits reject at admission, replicas stay in
the pool for the other models, and the engine probes the opened model
(``probe_interval_ms`` / ``probe_now()``) until it heals. A decode
``session=`` pins its version on first use — a mid-stream hot-swap
never switches the KV-cache owner; new sessions get the new version.
"""

from __future__ import annotations

import queue
import threading
import time
from collections import OrderedDict, deque
from concurrent.futures import Future
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

from deeplearning4j_tpu.datasets.iterators import (bucket_for, bucket_sizes,
                                                   pad_rows)
from deeplearning4j_tpu.monitor import (
    DECODE_REQUESTS_COUNTER,
    FAULT_QUARANTINED_GAUGE,
    INFER_BATCH_SIZE_BUCKETS,
    INFER_BATCH_SIZE_HISTOGRAM,
    INFER_BATCHES_COUNTER,
    INFER_LATENCY_HISTOGRAM,
    INFER_PADDED_RATIO_GAUGE,
    INFER_QUEUE_DEPTH_GAUGE,
    INFER_REQUESTS_COUNTER,
    TS_ENGINE_FILL_RATIO,
    TS_ENGINE_JIT_MISS,
    TimeSeriesStore,
    get_registry,
    mark,
    record_fault,
    span,
    timeseries_enabled,
)
from deeplearning4j_tpu.monitor import reqtrace
from deeplearning4j_tpu.monitor.tracing import to_origin_us
from deeplearning4j_tpu.optimize.deferred import note_dispatch


class InferenceBackpressure(RuntimeError):
    """Raised by ``submit`` when the admission queue is full and the
    engine was built with ``reject_when_full=True``."""


class EngineShutdown(RuntimeError):
    """Submit/prefill rejected because the engine (or its decode
    scheduler) is shut down. TYPED — registered in
    ``serving/wire.py _typed_error_registry`` — so a remote caller
    racing a worker's drain sees the same exception class an
    in-process caller would, not an anonymous ``EndpointError``
    (the typed-wire-raise contract: bare RuntimeError must never
    cross a frame handler)."""


class SliceDegraded(RuntimeError):
    """A chip inside this engine's mesh slice died: the whole slice is
    one failure domain (its params and KV pools are sharded across
    every chip), so the engine poisons itself — in-flight and queued
    work fails with this typed error, new submits reject at admission,
    and heartbeats carry the degraded slice topology so the router
    POSITIVELY knows (no silence, no timeout inference). Recovery is
    fleet-level: restore the mesh-portable checkpoint onto a narrower
    slice of the survivors (``LocalFleet.rebuild_slice``)."""


class _Request:
    __slots__ = ("x", "n", "future", "t_submit", "model", "version",
                 "coalescible", "trace")

    def __init__(self, x: np.ndarray, model: Optional[str] = None,
                 version: Optional[int] = None, coalescible: bool = True):
        self.x = x
        self.n = int(x.shape[0])
        self.future: "Future[np.ndarray]" = Future()
        self.t_submit = time.perf_counter()
        self.model = model
        self.version = version
        self.coalescible = coalescible
        # request-trace context, captured AT SUBMIT on the caller's
        # thread (where the router/worker installed it); None when
        # tracing is off — every span record below then no-ops
        self.trace = reqtrace.current_trace()

    def sig(self) -> Tuple:
        """Coalescing signature: only same-sig requests may share a
        dispatched batch (a batch never mixes models or versions)."""
        return (self.model, self.version) + tuple(self.x.shape[1:])

    def finish(self, rows: np.ndarray) -> np.ndarray:
        """Map the batch's de-padded result rows onto this request's
        Future value."""
        return rows


class _GenRequest(_Request):
    """A decode request: bucket-padded prompt rows [n, t_pad] plus the
    per-row true lengths and PRNG keys. Coalesces with other requests
    of the same (prompt bucket, max_new_tokens, sampler) signature —
    per-row lengths/keys keep each request's tokens identical to a
    solo ``net.generate`` run of the same rows."""

    __slots__ = ("lengths", "keys", "t_in", "max_new", "sampler")

    def __init__(self, ids_pad: np.ndarray, lengths: np.ndarray,
                 keys: np.ndarray, t_in: int, max_new: int,
                 sampler: Tuple, model: Optional[str] = None,
                 version: Optional[int] = None, coalescible: bool = True):
        super().__init__(ids_pad, model, version, coalescible)
        self.lengths = lengths
        self.keys = keys
        self.t_in = t_in
        self.max_new = max_new
        self.sampler = sampler

    def sig(self) -> Tuple:
        return ("gen", self.model, self.version, self.x.shape[1],
                self.max_new) + self.sampler

    def finish(self, rows: np.ndarray) -> np.ndarray:
        return np.concatenate(
            [self.x[:, :self.t_in].astype(np.int64),
             rows.astype(np.int64)], axis=1)


class _Batch:
    __slots__ = ("requests", "x", "rows", "tried", "payload", "model",
                 "version")

    def __init__(self, requests: List[_Request], x: np.ndarray, rows: int,
                 payload: Optional[Tuple] = None,
                 model: Optional[str] = None,
                 version: Optional[int] = None):
        self.requests = requests
        self.x = x  # bucket-padded, model dtype
        self.rows = rows  # real (unpadded) row count
        self.tried: set = set()  # replicas that gave up on this batch
        # generate batches carry (lengths, keys, max_new, sampler);
        # plain inference batches carry None
        self.payload = payload
        self.model = model
        self.version = version


_STOP = object()


class _FairBatchQueue:
    """Deficit-weighted round-robin over per-model batch FIFOs (DRR,
    Shreedhar & Varghese) — the cross-model fairness half of the
    multi-model dispatcher. Each model key owns a FIFO and a deficit
    counter measured in rows; a ``get()`` serves the head of the ring
    while its deficit covers the head batch, refilling deficits by
    ``quantum × weight`` per ring pass, so a model flooding the queue
    advances the ring instead of monopolizing it. With a single active
    key the queue degenerates to plain FIFO (no deficit churn).
    ``_STOP`` pills deliver only once no batch remains — workers drain
    formed work before exiting, same contract as the FIFO it replaces.
    """

    def __init__(self, quantum: int, weight_of=None):
        self._cv = threading.Condition()
        self._quantum = max(1, int(quantum))
        self._weight_of = weight_of
        self._subq: Dict[object, deque] = {}
        self._ring: deque = deque()
        self._deficit: Dict[object, float] = {}
        self._stops = 0
        self._size = 0

    def put(self, item) -> None:
        with self._cv:
            if item is _STOP:
                self._stops += 1
            else:
                key = item.model
                q = self._subq.get(key)
                if q is None:
                    q = self._subq[key] = deque()
                    self._deficit[key] = 0.0
                    self._ring.append(key)
                q.append(item)
                self._size += 1
            self._cv.notify()

    def _pop_locked(self):
        if self._size == 0:
            return None
        while True:
            key = self._ring[0]
            q = self._subq.get(key)
            if not q:
                # retire the idle key; a fresh arrival re-enters the
                # ring with a zero deficit (no banked credit)
                self._ring.popleft()
                self._subq.pop(key, None)
                self._deficit.pop(key, None)
                continue
            head = q[0]
            need = max(1, head.rows)
            if len(self._ring) == 1 or self._deficit[key] >= need:
                self._deficit[key] = max(0.0, self._deficit[key] - need)
                q.popleft()
                self._size -= 1
                return head
            w = 1.0 if self._weight_of is None else \
                max(1e-3, float(self._weight_of(key)))
            self._deficit[key] += self._quantum * w
            self._ring.rotate(-1)

    def get(self):
        with self._cv:
            while True:
                item = self._pop_locked()
                if item is not None:
                    return item
                if self._stops:
                    self._stops -= 1
                    return _STOP
                self._cv.wait()

    def get_nowait(self):
        with self._cv:
            item = self._pop_locked()
            if item is None:
                raise queue.Empty
            return item

    def qsize(self) -> int:
        with self._cv:
            return self._size


class ParallelInference:
    """Multi-replica micro-batching serving engine for a
    MultiLayerNetwork or single-input/single-output ComputationGraph.

    Requests carry their batch dimension: ``submit(x)`` with ``x`` of
    shape ``[n, ...features]`` resolves to the ``[n, ...out]`` rows that
    an inline ``net.output(x)`` would return (masked inputs are not
    coalescible — use ``net.output`` directly for those).

    Knobs (``ParallelInference.java`` mapping in MIGRATION.md):
    ``max_batch_size`` / ``max_latency_ms`` bound the coalescing window
    — which only holds requests while every replica is busy
    (``eager_when_idle``): idle capacity dispatches immediately, so the
    window is a throughput knob under load, not a latency floor at
    light load. ``queue_capacity`` + ``reject_when_full`` set the
    backpressure policy, ``replicas`` limits how many ``jax.devices()``
    entries get a pinned copy of the model (default: all of them; one
    under ``continuous=True``, whose scheduler serves from one device),
    ``coalesce=False`` is
    INPLACE mode (one request = one dispatch, no padding)."""

    def __init__(self, net=None, max_batch_size: int = 32,
                 max_latency_ms: float = 5.0, queue_capacity: int = 256,
                 reject_when_full: bool = False,
                 replicas: Optional[int] = None,
                 devices: Optional[Sequence] = None,
                 buckets: Optional[Sequence[int]] = None,
                 coalesce: Optional[bool] = None,
                 eager_when_idle: bool = True, start: bool = True,
                 max_batch_retries: int = 1,
                 probe_interval_ms: float = 50.0,
                 poison_hook=None,
                 registry=None,
                 max_sessions: int = 4096,
                 continuous: bool = False,
                 decode_slots: int = 8,
                 decode_burst: int = 8,
                 kv_block_size: int = 16,
                 kv_blocks: Optional[int] = None,
                 kv_quant: Optional[str] = None,
                 kv_bytes_budget: Optional[int] = None,
                 decode_burst_hook=None,
                 prefix_cache: bool = False,
                 prefix_cache_blocks: Optional[int] = None,
                 speculative: bool = False,
                 spec_tokens: int = 4,
                 spec_max_rows: Optional[int] = None,
                 draft_net=None,
                 kv_host_blocks: Optional[int] = None,
                 slice_plane=None):
        if net is None and registry is None:
            raise ValueError("ParallelInference needs a net or a registry")
        if net is not None and registry is not None:
            raise ValueError(
                "net= and registry= are exclusive: register the net as a "
                "model in the registry instead")
        if net is not None and net.params is None:
            net.init()
        # mesh-sharded serving: the engine's ONE replica is a mesh SLICE
        # (params column-sharded per the model's pinned SpecLayout, the
        # KV pool heads-sharded over tp, programs jitted-with-shardings
        # on the slice mesh) — and the slice is a first-class FAILURE
        # DOMAIN: a ChipFailure inside it poisons the whole engine
        # (typed SliceDegraded, never silence)
        self.slice_plane = slice_plane
        self._slice_dead: Optional[BaseException] = None
        if slice_plane is not None:
            if net is None:
                raise ValueError(
                    "slice_plane= serves one net per slice: build the "
                    "engine with net= (restore the mesh-portable "
                    "checkpoint onto the slice)")
            if getattr(net, "slice_plane", None) is not slice_plane:
                from deeplearning4j_tpu.parallel.mesh import \
                    apply_serving_slice
                self.slice_plane = apply_serving_slice(net, slice_plane)
        self.net = net
        self._registry = registry
        self.max_batch_size = int(max_batch_size)
        if self.max_batch_size < 1:
            raise ValueError(f"max_batch_size must be >= 1, got {max_batch_size}")
        self.max_latency = max(0.0, float(max_latency_ms)) / 1e3
        self.reject_when_full = bool(reject_when_full)
        if coalesce is None:
            coalesce = (net._pad_tail_safe()
                        if net is not None and hasattr(net, "_pad_tail_safe")
                        else True)
        self.coalesce = bool(coalesce)
        self.buckets: Tuple[int, ...] = tuple(sorted(
            buckets if buckets is not None else bucket_sizes(self.max_batch_size)))
        devs = list(devices) if devices is not None else jax.devices()
        if replicas is not None:
            devs = devs[:max(1, int(replicas))]
        elif devices is None and continuous:
            # the decode scheduler serves from ONE device (one slot
            # batch, one paged pool): a default replica on every other
            # chip would hold weights and serve nothing. replicas= /
            # devices= still widen the classify path; to decode on every
            # chip run one engine per chip (devices=[d]).
            devs = devs[:1]
        if not devs:
            raise ValueError("no devices to place replicas on")
        if net is not None and self.slice_plane is not None:
            # ONE slice replica: params/states already placed (sharded)
            # by apply_serving_slice — device None means "dispatch on
            # the slice mesh, inputs replicated onto it"
            self._fn = net.infer_output_fn()
            self._np_dtype = np.dtype(net._dtype)
            self._replicas = [(None, net.params, net.states)]
        elif net is not None:
            self._fn = net.infer_output_fn()
            self._np_dtype = np.dtype(net._dtype)
            with span("stage", path="infer_replicas", replicas=len(devs)):
                self._replicas = [
                    (d, jax.device_put(net.params, d),
                     jax.device_put(net.states, d))
                    for d in devs]
        else:
            # registry mode: params pin lazily per (model, version,
            # device) through the registry's memory budget
            self._fn = None
            self._np_dtype = None
            self._replicas = [(d, None, None) for d in devs]
            registry.attach(self)
        # decode sessions pin the version they started on — a
        # mid-stream hot-swap must never switch the KV-cache owner
        self._session_versions: "OrderedDict[Tuple[str, str], int]" = \
            OrderedDict()
        self._max_sessions = max(1, int(max_sessions))
        # model -> (version, per-example shape): the known-good probe
        # program per model, and the last wall time model probes ran
        self._model_probe: Dict[str, Tuple[int, Tuple[int, ...]]] = {}
        self._model_probe_at = 0.0
        # adaptive-batching discipline (Clipper/TF-Serving): requests
        # wait out the coalescing window ONLY while every replica is
        # busy — idle capacity dispatches immediately, so light load
        # pays dispatch latency, not max_latency_ms
        self.eager_when_idle = bool(eager_when_idle)
        self._inflight = 0  # batches queued or running on a replica
        self._rq: "queue.Queue" = queue.Queue(maxsize=max(1, int(queue_capacity)))
        # formed batches dispatch in deficit-weighted round-robin order
        # across models (plain FIFO when only one model is in flight)
        self._bq = _FairBatchQueue(
            quantum=self.max_batch_size,
            weight_of=registry.weight if registry is not None else None)
        self._closed = False
        self._error: Optional[BaseException] = None
        self._lock = threading.Lock()
        # fault tolerance: per-batch retry budget on one replica, then
        # quarantine + probe-based reinstatement
        self.max_batch_retries = max(0, int(max_batch_retries))
        self.probe_interval = max(1e-3, float(probe_interval_ms)) / 1e3
        self._poison_hook = poison_hook  # faultinject seam (tests/bench)
        self._quarantined: set = set()
        self._probe_wake: Dict[int, threading.Event] = {
            i: threading.Event() for i in range(len(self._replicas))}
        self._stopping = False
        self._probe_shape: Optional[Tuple[int, ...]] = None
        self._fault_log: List[str] = []
        self._rows_dispatched = 0
        self._rows_padded = 0
        # engine-PRIVATE windowed series (batch fill ratio, jit-miss
        # rate): a LocalFleet runs several engines in one process, so
        # the process-global store would blur them together — each
        # engine keeps its own and ships a compact summary in stats()
        # (heartbeat-carried for remote workers)
        self._ts = TimeSeriesStore()
        self._batches = 0
        self._requests = 0
        self._resolved = 0  # futures delivered (result or error)
        self._warmed = False
        self._started = False
        self._threads: List[threading.Thread] = []
        # continuous batching (serving/continuous.py): submit_generate
        # routes through an iteration-level decode scheduler + paged KV
        # pool instead of the whole-burst coalescing dispatcher
        self.continuous = bool(continuous)
        self.decode_slots = int(decode_slots)
        self.decode_burst = int(decode_burst)
        self.kv_block_size = int(kv_block_size)
        self.kv_blocks = kv_blocks
        # quantized paged KV (nn/quantize.py): "int8"/"fp8" pool
        # storage; kv_bytes_budget sizes the pool from device bytes so
        # a quantized engine holds 2-4x the decode rows per byte
        self.kv_quant = kv_quant
        self.kv_bytes_budget = kv_bytes_budget
        if (kv_quant is not None or kv_bytes_budget is not None) \
                and not self.continuous:
            raise ValueError(
                "kv_quant=/kv_bytes_budget= size the paged-pool "
                "scheduler: build the engine with continuous=True")
        self._decode_burst_hook = decode_burst_hook
        # cross-request prefix cache (serving/prefixcache.py): cache-hit
        # admissions clone their matched prefix's block table and
        # prefill only the tail; requires continuous=True
        self.prefix_cache = bool(prefix_cache)
        self.prefix_cache_blocks = prefix_cache_blocks
        if self.prefix_cache and not self.continuous:
            raise ValueError(
                "prefix_cache=True rides the paged-pool scheduler: "
                "build the engine with continuous=True")
        # speculative decoding (nn/generate.py spec programs): draft
        # proposes spec_tokens, target verifies them in ONE forward,
        # exact rejection sampling keeps the output distribution
        # unchanged; draft_net overrides the int8 self-speculation
        # default (registry mode pairs drafts via deploy(draft=...))
        self.speculative = bool(speculative)
        self.spec_tokens = int(spec_tokens)
        self.spec_max_rows = spec_max_rows
        self.draft_net = draft_net
        if (speculative or draft_net is not None) and not self.continuous:
            raise ValueError(
                "speculative=/draft_net= ride the paged-pool scheduler: "
                "build the engine with continuous=True")
        # host-RAM KV tier (nn/kvpool.py): preempted/hibernated sessions
        # swap their paged blocks to pinned host memory instead of
        # freeing them, so resume is a D2H/H2D round trip — not a
        # re-prefill — and end-of-turn hibernation survives the engine
        self.kv_host_blocks = kv_host_blocks
        if kv_host_blocks is not None and not self.continuous:
            raise ValueError(
                "kv_host_blocks= tiers the paged-pool scheduler: "
                "build the engine with continuous=True")
        self._scheduler = None
        if self.slice_plane is not None:
            self._publish_slice_gauges()
        if start:
            self.start()

    # ----------------------------------------------------------- slices

    def _slice_name(self) -> str:
        return "-".join(str(i) for i in
                        sorted(d.id for d in self.slice_plane.mesh
                               .devices.flat))

    def _slice_info(self) -> Dict:
        """The slice topology heartbeats carry: (width, devices,
        degraded) — what fleet_snapshot()/healthz show per endpoint
        instead of a bare healthy bit."""
        plane = self.slice_plane
        return {
            "width": int(plane.axis_size("tp")),
            "devices": sorted(int(d.id) for d in plane.mesh.devices.flat),
            "degraded": self._slice_dead is not None,
        }

    def _publish_slice_gauges(self) -> None:
        from deeplearning4j_tpu.monitor import (SLICE_DEGRADED_GAUGE,
                                                SLICE_DEVICES_GAUGE)
        reg = self._reg()
        name = self._slice_name()
        reg.gauge(SLICE_DEVICES_GAUGE,
                  "Devices in this engine's serving mesh slice",
                  slice=name).set(self.slice_plane.devices)
        reg.gauge(SLICE_DEGRADED_GAUGE,
                  "Serving slice poisoned by a chip failure (1) or "
                  "healthy (0)", slice=name).set(
            1.0 if self._slice_dead is not None else 0.0)

    def _slice_put(self, x):
        """Place one host batch for a dispatch on the slice mesh
        (replicated — activations stay whole; the PARAMS carry the
        sharding and GSPMD partitions the program around them)."""
        return jax.device_put(x, self.slice_plane.replicated())

    def _slice_error(self) -> SliceDegraded:
        err = SliceDegraded(
            f"slice {self._slice_name()} degraded: "
            f"{type(self._slice_dead).__name__}: {self._slice_dead}")
        err.__cause__ = self._slice_dead
        return err

    def _slice_fail(self, err: BaseException) -> None:
        """Poison the whole slice: a chip inside it died, so every chip
        in it is unusable (params and pools are sharded across all of
        them). Idempotent; queued work fails typed, the scheduler's
        sequences fail typed, and submits reject from here on. The
        engine stays ALIVE — heartbeats keep flowing with
        ``slice.degraded`` set, which is what lets the router declare
        the endpoint dead positively instead of waiting out timeouts."""
        if self.slice_plane is None:
            return
        with self._lock:
            if self._slice_dead is not None:
                return
            self._slice_dead = err
        record_fault("serving")
        mark("slice_degraded", slice=self._slice_name(),
             error=type(err).__name__)
        reqtrace.flight_trigger("slice_death", slice=self._slice_name(),
                                error=type(err).__name__)
        self._publish_slice_gauges()
        typed = self._slice_error()
        if self._scheduler is not None:
            self._scheduler.poison(typed)
        self._drain_cancel_with(typed)

    def _drain_cancel_with(self, err: BaseException) -> None:
        while True:
            try:
                item = self._rq.get_nowait()
            except queue.Empty:
                return
            if isinstance(item, _Request):
                item.future.set_exception(err)
                self._note_resolved(1)

    @staticmethod
    def _is_chip_failure(err: BaseException) -> bool:
        from deeplearning4j_tpu.faultinject import ChipFailure
        seen = 0
        while err is not None and seen < 8:
            if isinstance(err, ChipFailure):
                return True
            err = err.__cause__
            seen += 1
        return False

    # ------------------------------------------------------------ metrics

    def _reg(self):
        return get_registry()

    def _depth_gauge(self):
        return self._reg().gauge(
            INFER_QUEUE_DEPTH_GAUGE,
            "Requests queued awaiting the micro-batch dispatcher")

    # ------------------------------------------------------------- public

    def start(self) -> "ParallelInference":
        if self._started:
            return self
        self._started = True
        t = threading.Thread(target=self._dispatch_loop, daemon=True,
                             name="dl4j-tpu-infer-dispatch")
        t.start()
        self._threads = [t]
        for i in range(len(self._replicas)):
            w = threading.Thread(target=self._worker_loop, args=(i,),
                                 daemon=True, name=f"dl4j-tpu-infer-w{i}")
            w.start()
            self._threads.append(w)
        if self._scheduler is not None:
            self._scheduler.start()
        return self

    def _resolve_model(self, model: Optional[str], version: Optional[int],
                       session: Optional[str]):
        """(model, version, ModelVersion|None, coalescible) for one
        request. Registry mode resolves the version AT SUBMIT TIME —
        that is what makes a deploy's cutover atomic: requests resolved
        before the swap finish on the old version, requests after it
        get the new one. A ``session`` pins the version it first
        resolved (decode streams must not switch KV-cache owners
        mid-stream); rejected/pruned pinned versions re-resolve."""
        if self._registry is None:
            if model is not None:
                raise ValueError(
                    "this engine serves one pinned net; build it with "
                    "registry= for model= routing")
            return None, None, None, True
        if model is None:
            raise ValueError("registry-mode engine requires model=")
        from deeplearning4j_tpu.serving.registry import (STATE_REJECTED,
                                                         ModelUnavailable)
        pinned = None
        if session is not None and version is None:
            with self._lock:
                pinned = self._session_versions.get((model, session))
        if pinned is not None:
            try:
                mv = self._registry.version(model, pinned)
                if mv.state != STATE_REJECTED:
                    version = pinned
            except ModelUnavailable:
                pass  # pruned: the session re-pins on the fresh resolve
        v = self._registry.resolve(model, version)
        if session is not None:
            with self._lock:
                self._session_versions[(model, session)] = v
                while len(self._session_versions) > self._max_sessions:
                    self._session_versions.popitem(last=False)
        mv = self._registry.version(model, v)
        return model, v, mv, self._registry.entry(model).coalesce

    def release_session(self, session: str, model: Optional[str] = None) -> None:
        """Drop a session's version pins (stream finished)."""
        with self._lock:
            for key in [k for k in self._session_versions
                        if k[1] == session and (model is None or k[0] == model)]:
                self._session_versions.pop(key, None)

    def submit(self, x: np.ndarray, model: Optional[str] = None,
               version: Optional[int] = None,
               session: Optional[str] = None) -> "Future[np.ndarray]":
        """Enqueue one request (``x``: [n, ...features]); the Future
        resolves to the [n, ...out] predictions for exactly those rows.
        Registry mode routes by ``model=`` (and optionally a pinned
        ``version=``); the version is resolved here, atomically with
        respect to deploys."""
        if self._closed:
            raise EngineShutdown("ParallelInference is shut down")
        if self._slice_dead is not None:
            raise self._slice_error()
        model, v, mv, coalescible = self._resolve_model(model, version, session)
        x = np.asarray(x, dtype=self._np_dtype if mv is None else mv.np_dtype)
        if x.ndim < 2:
            raise ValueError(
                f"requests carry their batch dimension: got shape {x.shape}; "
                "a single example must be submitted as x[None, ...]")
        return self._enqueue(_Request(x, model, v, coalescible))

    def _enqueue(self, req: _Request) -> "Future[np.ndarray]":
        try:
            self._rq.put(req, block=not self.reject_when_full)
        except queue.Full:
            raise InferenceBackpressure(
                f"admission queue full ({self._rq.maxsize} requests) and "
                "reject_when_full=True") from None
        with self._lock:
            self._requests += 1
        self._reg().counter(INFER_REQUESTS_COUNTER,
                            "Inference requests submitted to the engine").inc()
        self._depth_gauge().set(self._rq.qsize())
        return req.future

    def output(self, x: np.ndarray, timeout: Optional[float] = None,
               **kwargs) -> np.ndarray:
        """Blocking facade: inline ``net.output`` semantics through the
        batching engine (``model=``/``version=`` in registry mode)."""
        return self.submit(x, **kwargs).result(timeout=timeout)

    # ---------------------------------------------------- generation

    def _generator(self):
        """The net's fused generation engine (nn/generate.py), built
        lazily — raises on nets with no generation family."""
        gen = self.__dict__.get("_gen")
        if gen is None:
            from deeplearning4j_tpu.nn.generate import build_generator
            gen = self.__dict__["_gen"] = build_generator(self.net)
        return gen

    def _continuous_scheduler(self):
        """The engine's iteration-level decode scheduler (built lazily:
        transformer nets only). Runs on the first replica's device —
        one slot batch, one shared paged KV pool; classify traffic
        keeps using every replica."""
        sched = self._scheduler
        if sched is None:
            from deeplearning4j_tpu.serving.continuous import (
                ContinuousDecodeScheduler)
            dev = self._replicas[0][0]
            sched = self._scheduler = ContinuousDecodeScheduler(
                net=self.net, registry=self._registry, device=dev,
                slots=self.decode_slots, burst_tokens=self.decode_burst,
                block_size=self.kv_block_size, num_blocks=self.kv_blocks,
                host_kv_blocks=self.kv_host_blocks,
                kv_quant=self.kv_quant,
                kv_bytes_budget=self.kv_bytes_budget,
                queue_capacity=self._rq.maxsize,
                burst_hook=self._decode_burst_hook,
                on_resolve=self._note_resolved,
                prefix_cache=self.prefix_cache,
                prefix_cache_blocks=self.prefix_cache_blocks,
                speculative=self.speculative,
                spec_tokens=self.spec_tokens,
                spec_max_rows=self.spec_max_rows,
                draft_net=self.draft_net,
                on_fatal=self._slice_fail,
                start=self._started)
        return sched

    def submit_generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                        temperature: float = 0.0, top_k: int = 0,
                        top_p: float = 0.0, eos_token: Optional[int] = None,
                        seed: int = 0, model: Optional[str] = None,
                        version: Optional[int] = None,
                        session: Optional[str] = None,
                        priority: int = 0,
                        on_tokens=None,
                        prefix: Optional[np.ndarray] = None,
                        kv_state=None,
                        hibernate: bool = False
                        ) -> "Future[np.ndarray]":
        """Enqueue one decode request (``prompt_ids``: [n, t0] int
        tokens); the Future resolves to the [n, t0 + max_new_tokens]
        ids a solo ``net.generate`` of the same rows would return.
        Requests coalesce per (prompt-length bucket, max_new_tokens,
        sampler) across replicas — the prompt length enters the
        compiled program as a traced per-row vector, so any prompt mix
        inside a bucket shares one AOT-warmable program, and per-row
        PRNG keys make a request's draws coalescing-invariant. A
        ``session`` pins the (model, version) its first burst resolved
        — later bursts of the stream stay on that version through any
        deploy (the KV state lives with the version's programs).

        ``on_tokens(offset, tokens)`` (single-row requests) streams
        incremental token deltas: the continuous scheduler emits one
        chunk per retiring burst; the whole-burst path emits one
        terminal chunk when the burst resolves (a single-chunk stream —
        same contract, coarser granularity). ``prefix`` resumes a
        migrated stream from prompt + already-generated tokens; it
        rides the continuous scheduler's preempt/resume machinery and
        therefore requires ``continuous=True``.

        ``hibernate=True`` (continuous + ``kv_host_blocks`` engines)
        swaps the session's KV blocks to the host tier at end-of-turn
        instead of freeing them — the next ``submit_generate`` of the
        same ``session`` restores them via swap-in rather than
        re-prefilling. A ``kv_state`` dict carrying ``"blocks"`` is a
        hibernation payload from another endpoint's
        :meth:`hibernate_export`: it is imported into the local host
        tier first, then the request resumes through the same swap-in
        path."""
        if self._closed:
            raise EngineShutdown("ParallelInference is shut down")
        if self._slice_dead is not None:
            raise self._slice_error()
        from deeplearning4j_tpu.nn.generate import row_keys, sampler_sig
        model, v, mv, coalescible = self._resolve_model(model, version, session)
        if self.continuous:
            # iteration-level path: the scheduler admits/retires rows
            # between fixed-K bursts over the paged KV pool; the
            # (model, version) resolved HERE — atomically vs deploys,
            # session-pinned — stays with the sequence for its
            # lifetime (its blocks and programs live with the version)
            self._reg().counter(DECODE_REQUESTS_COUNTER,
                                "generate() requests").inc()
            with self._lock:
                self._requests += 1
            sched = self._continuous_scheduler()
            if isinstance(kv_state, dict) and "blocks" in kv_state:
                # shipped hibernation payload (cross-endpoint resume):
                # seed the local host tier, then resume rides the SAME
                # swap-in path a locally-hibernated session takes
                sched.hibernate_import(
                    session, kv_state["blocks"], kv_state["covered"],
                    kv_state["tokens"], model=model, version=v,
                    prompt=kv_state.get("prompt"),
                    generated=kv_state.get("generated"))
                kv_state = None
            return sched.submit(
                prompt_ids, max_new_tokens, temperature=temperature,
                top_k=top_k, top_p=top_p, eos_token=eos_token, seed=seed,
                priority=priority, model=model, version=v, session=session,
                on_tokens=on_tokens, prefix=prefix, kv_state=kv_state,
                hibernate=hibernate)
        if prefix is not None:
            raise ValueError(
                "prefix resume rides the iteration-level preempt/resume "
                "machinery: build the engine with continuous=True")
        if kv_state is not None:
            raise ValueError(
                "kv_state handoff rides the paged-pool scheduler: build "
                "the engine with continuous=True")
        if hibernate:
            raise ValueError(
                "hibernate=True parks KV in the paged pool's host tier: "
                "build the engine with continuous=True and kv_host_blocks=")
        gen = self._generator() if mv is None else mv.generator()
        prompt = np.asarray(prompt_ids)
        if prompt.ndim != 2:
            raise ValueError(
                f"prompt_ids must be [n, t0] int tokens, got {prompt.shape}")
        n, t_in = prompt.shape
        if on_tokens is not None and n != 1:
            raise ValueError(
                f"token streaming is per-stream: prompt must be [1, t0], "
                f"got {prompt.shape}")
        max_new = int(max_new_tokens)
        t_pad = gen.prompt_bucket(t_in, max_new)
        ids = np.zeros((n, t_pad), np.int32)
        ids[:, :t_in] = prompt
        lengths = np.full((n,), t_in, np.int32)
        keys = np.asarray(row_keys(seed, n))
        self._reg().counter(DECODE_REQUESTS_COUNTER,
                            "generate() requests").inc()
        fut = self._enqueue(_GenRequest(
            ids, lengths, keys, t_in, max_new,
            sampler_sig(temperature, top_k, top_p, eos_token),
            model, v, coalescible))
        if on_tokens is not None:
            # whole-burst streaming degrades to ONE terminal chunk: the
            # first token only exists when the whole scan resolves
            from deeplearning4j_tpu.monitor import STREAM_CHUNKS_COUNTER

            def _emit(f, t0=t_in):
                if f.exception() is not None:
                    return
                self._reg().counter(
                    STREAM_CHUNKS_COUNTER,
                    "Incremental decode-token chunks emitted through "
                    "the on_tokens streaming seam").inc()
                try:
                    on_tokens(0, np.asarray(f.result())[0, t0:]
                              .astype(np.int64))
                except BaseException:
                    pass  # consumer bug; the Future already carries all
            fut.add_done_callback(_emit)
        return fut

    def generate(self, prompt_ids: np.ndarray, max_new_tokens: int,
                 timeout: Optional[float] = None, **kwargs) -> np.ndarray:
        """Blocking facade over :meth:`submit_generate`."""
        return self.submit_generate(prompt_ids, max_new_tokens,
                                    **kwargs).result(timeout=timeout)

    # --------------------------------------------- session hibernation

    def _hibernation_scheduler(self):
        if not self.continuous:
            raise ValueError(
                "session hibernation parks KV in the paged pool's host "
                "tier: build the engine with continuous=True and "
                "kv_host_blocks=")
        return self._continuous_scheduler()

    def hibernate_export(self, session: str) -> Optional[Dict]:
        """Snapshot a hibernated session's host-tier KV as a portable
        payload (non-consuming): per-block raw K/V + quantized scales,
        the covered token journal, and the (model, version) lane — what
        a router ships to a surviving endpoint so the session resumes
        THERE bitwise after this endpoint dies. None if the session has
        no hibernation record."""
        if self._scheduler is None:
            self._hibernation_scheduler()
            return None
        return self._hibernation_scheduler().hibernate_export(session)

    def hibernate_import(self, session: str, blocks, covered: int,
                         tokens, model: Optional[str] = None,
                         version: Optional[int] = None,
                         prompt=None, generated=None) -> bool:
        """Seed the local host tier with a shipped hibernation payload
        (:meth:`hibernate_export` from another endpoint) so the next
        ``submit_generate(session=...)`` resumes via swap-in instead of
        re-prefilling. Returns False when the host tier is disabled or
        over budget (the caller falls back to journaled-prefix resume)."""
        v = version
        if model is not None and self._registry is not None:
            v = self._registry.resolve(model, version)
        return self._hibernation_scheduler().hibernate_import(
            session, blocks, covered, tokens, model=model, version=v,
            prompt=prompt, generated=generated)

    def hibernate_release(self, session: str) -> bool:
        """Drop a session's hibernation record and free its host-tier
        blocks (the abandon path — resume consumes the record itself)."""
        if self._scheduler is None:
            self._hibernation_scheduler()
            return False
        return self._hibernation_scheduler().hibernate_release(session)

    def hibernated_count(self) -> int:
        """Live hibernated-session records parked in the host tier."""
        if not self.continuous or self._scheduler is None:
            return 0
        return self._scheduler.hibernated_count()

    # --------------------------------------- disaggregated prefill

    def prefill_export(self, prompt_ids: np.ndarray) -> Dict:
        """The PREFILL half of disaggregated serving (the DistServe /
        Splitwise split): run ONLY the prompt forward and export the KV
        it wrote plus the last-token logits — the state a DECODE
        endpoint needs to admit the session without recomputing the
        prompt (``submit_generate(kv_state=...)``). Returns
        ``{"kv": [L, 2, 1, t_pad, h, hd], "logits": [1, V],
        "t_in": int}``. The export is exactly what a local prefill of
        the same tokens computes (same program, same params), so the
        handed-off stream's tokens equal an undisaggregated run's."""
        if self._closed:
            raise EngineShutdown("ParallelInference is shut down")
        if self._slice_dead is not None:
            raise self._slice_error()
        if self.net is None:
            raise ValueError(
                "prefill_export serves one pinned net: build the "
                "prefill endpoint's engine with net=")
        from deeplearning4j_tpu.nn.generate import TransformerGenerator
        gen = self._generator()
        if not isinstance(gen, TransformerGenerator):
            raise ValueError(
                "disaggregated prefill ships KV caches; "
                f"{type(gen).__name__} nets have none")
        prompt = np.asarray(prompt_ids)
        if prompt.ndim != 2 or prompt.shape[0] != 1:
            raise ValueError(
                f"prefill_export is per-session: prompt must be "
                f"[1, t0], got {prompt.shape}")
        n, t_in = prompt.shape
        t_pad = gen.prompt_bucket(t_in, 1)
        ids = np.zeros((n, t_pad), np.int32)
        ids[:, :t_in] = prompt
        lengths = np.full((n,), t_in, np.int32)
        dev, params, _ = self._replicas[0]
        kv, logits = gen.export_prefill(params, ids, lengths)
        with self._lock:
            self._requests += 1
            self._resolved += 1
        return {"kv": kv, "logits": logits, "t_in": int(t_in)}

    def warmup_prefill(self, prompt_lengths: Sequence[int]) -> int:
        """AOT-compile the prefill-export program ladder (one program
        per covering prompt bucket) — what a prefill-specialized
        endpoint warms instead of the decode set."""
        from deeplearning4j_tpu.monitor import JIT_CACHE_MISS_COUNTER
        from deeplearning4j_tpu.nn.generate import row_keys  # noqa: F401
        gen = self._generator()
        reg = self._reg()
        before = reg.family_total(JIT_CACHE_MISS_COUNTER)
        done = set()
        for t_in in prompt_lengths:
            t_pad = gen.prompt_bucket(int(t_in), 1)
            if t_pad in done:
                continue
            done.add(t_pad)
            ids = np.zeros((1, t_pad), np.int32)
            lens = np.full((1,), min(int(t_in), t_pad), np.int32)
            gen.export_prefill(self._replicas[0][1], ids, lens)
        self._warmed = True
        return int(reg.family_total(JIT_CACHE_MISS_COUNTER) - before)

    def warmup_generate(self, prompt_lengths: Sequence[int],
                        max_new_tokens: int, temperature: float = 0.0,
                        top_k: int = 0, top_p: float = 0.0,
                        eos_token: Optional[int] = None,
                        model: Optional[str] = None,
                        version: Optional[int] = None,
                        tail_lengths=None) -> int:
        """AOT-compile the decode program set: for every prompt-length
        bucket covering ``prompt_lengths``, run a zero-prompt batch of
        every row-bucket size on every replica (prefill + decode).
        Returns the number of fresh programs compiled; after it,
        steady-state ``submit_generate`` serving of any request mix
        within the covered (bucket, max_new) set performs zero XLA
        compiles (observable via ``dl4j_jit_cache_miss_total``)."""
        from deeplearning4j_tpu.monitor import JIT_CACHE_MISS_COUNTER
        from deeplearning4j_tpu.nn.generate import row_keys, sampler_sig
        if model is not None and self._registry is None:
            raise ValueError("model= needs a registry-mode engine")
        if self.continuous:
            v = None
            if model is not None:
                v = self._registry.resolve(model, version)
            return self._continuous_scheduler().warmup(
                prompt_lengths, int(max_new_tokens), model=model, version=v,
                tail_lengths=tail_lengths)
        mv = None
        if model is not None:
            v = self._registry.resolve(model, version)
            mv = self._registry.version(model, v)
        gen = self._generator() if mv is None else mv.generator()
        sampler = sampler_sig(temperature, top_k, top_p, eos_token)
        max_new = int(max_new_tokens)
        sizes = self.buckets if self.coalesce else (1,)
        if mv is not None:
            sizes = self._model_buckets(model) if self.coalesce else (1,)
        reg = self._reg()
        before = reg.family_total(JIT_CACHE_MISS_COUNTER)
        done = set()
        for t_in in prompt_lengths:
            t_pad = gen.prompt_bucket(int(t_in), max_new)
            for rows in sizes:
                if (t_pad, rows) in done:
                    continue
                done.add((t_pad, rows))
                ids = np.zeros((rows, t_pad), np.int32)
                lengths = np.full((rows,), min(int(t_in), t_pad), np.int32)
                keys = np.asarray(row_keys(0, rows))
                for i, (dev, params, states) in enumerate(self._replicas):
                    if mv is not None:
                        _, params, states = self._registry.acquire(
                            model, mv.version, dev)
                    with span("stage", path="warmup_generate", bucket=t_pad,
                              rows=rows, replica=i):
                        gen.run(params, ids, lengths, max_new, sampler,
                                keys, replica=i, device=dev)
        if mv is not None:
            mv.warmed = True
        else:
            self._warmed = True
        return int(reg.family_total(JIT_CACHE_MISS_COUNTER) - before)

    def warmup(self, shapes: Sequence[Tuple[int, ...]]) -> int:
        """AOT-compile the serving program set: for every per-example
        trailing ``shape`` in ``shapes``, dispatch a zero batch of every
        bucket size on every replica (sequentially, blocking until each
        executable is built). Returns the number of fresh programs
        compiled; after it, steady-state serving of any request mix
        within the bucket set performs zero XLA compiles. In registry
        mode this warms EVERY registered model's serving version with
        ``shapes`` (per-model ``warm_shapes`` take precedence when
        set); use :meth:`warmup_model` for one model."""
        if self._registry is not None:
            compiled = 0
            for name in self._registry.models():
                entry = self._registry.entry(name)
                compiled += self.warmup_model(
                    name, shapes=entry.warm_shapes or shapes)
            return compiled
        sizes = self.buckets if self.coalesce else (1,)
        compiled = 0
        for shape in shapes:
            for b in sizes:
                zeros = np.zeros((b,) + tuple(shape), self._np_dtype)
                for i, (dev, params, states) in enumerate(self._replicas):
                    x = (self._slice_put(zeros)
                         if self.slice_plane is not None
                         else jax.device_put(zeros, dev))
                    fresh = note_dispatch(
                        self.net, self._dispatch_sig(i, zeros.shape))
                    with span("compile" if fresh else "inference",
                              path="warmup", bucket=b, replica=i):
                        np.asarray(self._fn(params, states, x, None))
                    compiled += int(fresh)
            with self._lock:
                # a warmed shape doubles as the quarantine probe program
                self._probe_shape = tuple(shape)
        self._warmed = True
        return compiled

    def _model_buckets(self, model: Optional[str]) -> Tuple[int, ...]:
        """The row-bucket ladder for one model: its registry override,
        else the engine ladder."""
        if model is not None and self._registry is not None:
            entry = self._registry.entry(model)
            if entry.buckets:
                return entry.buckets
        return self.buckets

    def warmup_model(self, model: str, version: Optional[int] = None,
                     shapes: Optional[Sequence[Tuple[int, ...]]] = None) -> int:
        """AOT-compile one model version's serving programs (every
        bucket × replica) OFF the hot path — what a registry deploy
        runs before its atomic cutover, so the first post-cutover
        request never eats an XLA compile. ``version=None`` warms the
        version fresh requests would resolve to. Returns fresh-program
        count."""
        if self._registry is None:
            raise ValueError("warmup_model needs a registry-mode engine")
        if version is not None:
            # explicit version bypasses the breaker check: deploying a
            # FIXED version is how a quarantined model gets replaced
            v = int(version)
        else:
            v = self._registry.resolve(model, None)
        mv = self._registry.version(model, v)
        shapes = [tuple(s) for s in
                  (shapes or self._registry.entry(model).warm_shapes or [])]
        entry = self._registry.entry(model)
        sizes = self._model_buckets(model) if (self.coalesce and entry.coalesce) \
            else (1,)
        compiled = 0
        net = mv.net()
        for shape in shapes:
            for b in sizes:
                zeros = np.zeros((b,) + tuple(shape), mv.np_dtype)
                for i, (dev, _, _) in enumerate(self._replicas):
                    fn, params, states = self._registry.acquire(model, v, dev)
                    x = jax.device_put(zeros, dev)
                    fresh = note_dispatch(
                        net, self._dispatch_sig(i, zeros.shape, model, v))
                    with span("compile" if fresh else "inference",
                              path="warmup_model", model=model, version=v,
                              bucket=b, replica=i):
                        np.asarray(fn(params, states, x, None))
                    compiled += int(fresh)
            with self._lock:
                self._model_probe[model] = (v, tuple(shape))
        mv.warmed = True
        return compiled

    @property
    def timeseries(self) -> TimeSeriesStore:
        """This engine's private windowed-series store (fill ratio,
        jit-miss rate; the fleet worker adds its served-delta series).
        Private per engine so LocalFleet's in-process endpoints don't
        blur into one store."""
        return self._ts

    def stats(self) -> Dict[str, float]:
        with self._lock:
            rows, padded = self._rows_dispatched, self._rows_padded
            quarantined = sorted(self._quarantined)
            sessions = len(self._session_versions)
            out = {
                "requests": self._requests,
                "resolved": self._resolved,
                "batches": self._batches,
                "rows_dispatched": rows,
                "rows_padded": padded,
                "padded_ratio": (padded / rows) if rows else 0.0,
                "queue_depth": self._rq.qsize(),
                "inflight": self._inflight,
                "replicas": len(self._replicas),
                "buckets": list(self.buckets),
                "coalesce": self.coalesce,
                "quarantined": quarantined,
                "healthy_replicas": len(self._replicas) - len(quarantined),
                "degraded": bool(quarantined),
                "warmed": self._warmed,
                "faults": len(self._fault_log),
            }
        # compact windowed summary riding the stats snapshot (and so
        # every fleet heartbeat): fleet_snapshot() merges these into
        # the fleet-wide window view
        if timeseries_enabled():
            out["timeseries"] = self._ts.summary()
        if self.slice_plane is not None:
            # heartbeats carry the slice topology: fleet_snapshot() and
            # /healthz show per-endpoint (width, devices, degraded)
            # instead of a bare healthy bit
            out["slice"] = self._slice_info()
            out["degraded"] = out["degraded"] or out["slice"]["degraded"]
        if self.continuous:
            # decode-scheduler state (active sequences, queued
            # prefills, pool occupancy) — /healthz/ready gates on its
            # warmed flag, mirroring the models_ready pattern
            out["scheduler"] = (
                self._scheduler.stats() if self._scheduler is not None
                else {"warmed": False, "active_sequences": 0,
                      "queued_prefills": 0, "pool": {}})
        if self._registry is not None:
            # per-model lifecycle view (outside the engine lock: the
            # registry has its own)
            models = self._registry.stats()
            open_models = sorted(n for n, m in models.items()
                                 if m["breaker_open"])
            out["models"] = models
            out["models_quarantined"] = open_models
            out["sessions"] = sessions
            out["degraded"] = out["degraded"] or bool(open_models)
            out["warmed"] = bool(models) and all(
                m["warmed"] for m in models.values())
        return out

    def drain(self, timeout: Optional[float] = None,
              poll_s: float = 2e-3) -> bool:
        """Block until every accepted request has resolved (admission
        queue empty, no batch queued or running) WITHOUT stopping the
        engine — the graceful half of shutdown a fleet worker runs
        before leaving the serving pool, so a drained engine can be
        stopped with zero stranded futures. Returns False when
        ``timeout`` elapses first."""
        deadline = None if timeout is None else time.monotonic() + timeout
        while True:
            with self._lock:
                # resolved-vs-accepted, not queue emptiness: a request
                # coalescing inside the dispatcher window is in neither
                # queue, but it has not resolved yet either
                idle = self._resolved >= self._requests
            if idle:
                return True
            if deadline is not None and time.monotonic() >= deadline:
                return False
            time.sleep(poll_s)

    def _note_resolved(self, n: int) -> None:
        with self._lock:
            self._resolved += n

    def probe_now(self) -> None:
        """Wake every quarantined replica's probe immediately (instead
        of waiting out ``probe_interval_ms``) and probe every
        open-breaker model synchronously — the deterministic seam the
        fault-injection tests and operators use."""
        for ev in self._probe_wake.values():
            ev.set()
        self._probe_open_models()

    def shutdown(self, drain: bool = True, timeout: Optional[float] = None) -> None:
        """Stop accepting work; drain (default) or cancel what is queued,
        join the threads, then re-raise the first worker error (which
        every affected Future also carries)."""
        if self._closed:
            return
        self._closed = True
        if self._scheduler is not None:
            self._scheduler.shutdown(drain=drain and self._started,
                                     timeout=timeout)
        if not self._started:
            # never ran: resolve queued futures so no caller hangs
            self._drain_cancel()
            return
        if not drain:
            self._drain_cancel()
        self._rq.put(_STOP)
        for t in self._threads:
            t.join(timeout)
        # belt-and-braces: a batch redispatched in the shutdown race can
        # outlive every worker — its futures must still resolve
        while True:
            try:
                b = self._bq.get_nowait()
            except queue.Empty:
                break
            if isinstance(b, _Batch):
                err = self._error or RuntimeError(
                    "ParallelInference shut down before dispatch")
                for r in b.requests:
                    if not r.future.done():
                        r.future.set_exception(err)
                        self._note_resolved(1)
        if self._error is not None:
            raise self._error

    def __enter__(self) -> "ParallelInference":
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        # don't mask an in-flight exception with a worker error rethrow
        try:
            self.shutdown()
        except BaseException:
            if exc_type is None:
                raise

    def _drain_cancel(self):
        err = RuntimeError("ParallelInference shut down before dispatch")
        while True:
            try:
                item = self._rq.get_nowait()
            except queue.Empty:
                return
            if isinstance(item, _Request):
                item.future.set_exception(err)
                self._note_resolved(1)

    # --------------------------------------------------------- dispatcher

    @staticmethod
    def _sig(req: _Request) -> Tuple:
        return req.sig()

    def _dispatch_sig(self, replica: int, shape: Tuple[int, ...],
                      model: Optional[str] = None,
                      version: Optional[int] = None) -> Tuple:
        """jit-cache-miss signature of one device dispatch: program kind
        + operand shape + replica (each replica's placement compiles its
        own executable, so warmup must cover all of them) + the model
        version it ran for (multi-model engines compile per version)."""
        return ("infer_output", replica, tuple(shape),
                str(self._np_dtype), model, version)

    def _dispatch_loop(self):
        pending: Dict[Tuple, List[_Request]] = {}
        oldest: Dict[Tuple, float] = {}

        def flush(sig):
            reqs = pending.pop(sig)
            oldest.pop(sig, None)
            self._bq.put(self._form_batch(reqs))

        def idle_capacity() -> bool:
            with self._lock:
                healthy = len(self._replicas) - len(self._quarantined)
                return self._inflight < healthy

        while True:
            timeout = None
            if oldest:
                timeout = max(
                    1e-4, min(oldest.values()) + self.max_latency - time.perf_counter())
            elif self._registry is not None:
                # bounded idle wakeups so open model breakers get their
                # probes even when no submit arrives to trigger one
                timeout = self.probe_interval
            try:
                item = self._rq.get(timeout=timeout)
            except queue.Empty:
                item = None
            if item is None and self._registry is not None:
                self._maybe_probe_models()
            if item is _STOP:
                # a submit() racing shutdown may have enqueued behind the
                # stop pill — drain it too so no accepted future strands
                while True:
                    try:
                        late = self._rq.get_nowait()
                    except queue.Empty:
                        break
                    if isinstance(late, _Request):
                        pending.setdefault(self._sig(late), []).append(late)
                for sig in list(pending):
                    flush(sig)
                # after _stopping, workers finish what is queued and
                # exit on their pill; quarantined workers exit from
                # their probe wait (woken below)
                self._stopping = True
                for _ in self._replicas:
                    self._bq.put(_STOP)
                for ev in self._probe_wake.values():
                    ev.set()
                return
            if item is not None:
                self._depth_gauge().set(self._rq.qsize())
                if not self.coalesce or not item.coalescible \
                        or item.n >= self.max_batch_size:
                    # INPLACE mode / batch-statistics model / oversized
                    # request: its own batch
                    self._bq.put(self._form_batch([item]))
                else:
                    sig = self._sig(item)
                    group = pending.setdefault(sig, [])
                    if not group:
                        oldest[sig] = time.perf_counter()
                    group.append(item)
                    if sum(r.n for r in group) >= self.max_batch_size:
                        flush(sig)
                    elif (self.eager_when_idle and self._rq.empty()
                          and idle_capacity()):
                        # an idle replica beats a fuller batch: dispatch
                        # now; the window only buys batching when every
                        # replica is already busy
                        flush(sig)
            now = time.perf_counter()
            for sig in [s for s, t0 in oldest.items()
                        if now - t0 >= self.max_latency]:
                flush(sig)

    def _form_batch(self, reqs: List[_Request]) -> _Batch:
        rows = sum(r.n for r in reqs)
        x = reqs[0].x if len(reqs) == 1 else np.concatenate(
            [r.x for r in reqs], axis=0)
        payload = None
        pad_ok = self.coalesce and reqs[0].coalescible
        buckets = self._model_buckets(reqs[0].model)
        if isinstance(reqs[0], _GenRequest):
            # decode batch: per-row lengths + PRNG keys ride along;
            # row-bucket padding uses length 0 — the decode program's
            # done-mask retires those rows on their first step
            lengths = np.concatenate([r.lengths for r in reqs])
            keys = np.concatenate([r.keys for r in reqs], axis=0)
            if pad_ok:
                pad = bucket_for(rows, buckets) - rows
                x = pad_rows(x, pad)
                lengths = pad_rows(lengths, pad)
                keys = pad_rows(keys, pad)
            payload = (lengths, keys, reqs[0].max_new, reqs[0].sampler)
        elif pad_ok:
            x = pad_rows(x, bucket_for(rows, buckets) - rows)
        with self._lock:
            self._inflight += 1  # until delivered or failed, not requeues
            self._batches += 1
            self._rows_dispatched += x.shape[0]
            self._rows_padded += x.shape[0] - rows
            ratio = self._rows_padded / self._rows_dispatched
        reg = self._reg()
        reg.counter(INFER_BATCHES_COUNTER,
                    "Micro-batches dispatched to the replicas").inc()
        reg.histogram(INFER_BATCH_SIZE_HISTOGRAM,
                      "Rows per dispatched micro-batch (after padding)",
                      buckets=INFER_BATCH_SIZE_BUCKETS).observe(x.shape[0])
        reg.gauge(INFER_PADDED_RATIO_GAUGE,
                  "Cumulative fraction of dispatched rows that were bucket "
                  "padding").set(ratio)
        if timeseries_enabled():
            # per-batch fill ratio (real rows / padded batch rows):
            # the windowed view of how much bucket padding costs NOW,
            # vs the cumulative gauge above
            self._ts.record(TS_ENGINE_FILL_RATIO, rows / x.shape[0])
        return _Batch(reqs, x, rows, payload,
                      model=reqs[0].model, version=reqs[0].version)

    # ------------------------------------------------------------ workers

    def _hook(self, idx: int, shape, model: Optional[str]) -> None:
        """Invoke the faultinject poison seam; model-aware hooks
        (``wants_model=True`` — ``ModelPoison``) also see which model
        the dispatch ran for."""
        h = self._poison_hook
        if h is None:
            return
        if getattr(h, "wants_model", False):
            h(idx, shape, model)
        else:
            h(idx, shape)

    def _dispatch(self, idx: int, params, states, x, fn=None,
                  model: Optional[str] = None):
        """One replica dispatch; the ``poison_hook`` seam lets the
        faultinject harness stand in for a device fault
        deterministically (it raises instead of the device)."""
        self._hook(idx, x.shape, model)
        return (self._fn if fn is None else fn)(params, states, x, None)

    def _worker_loop(self, idx: int):
        dev, params, states = self._replicas[idx]
        lat = self._reg().histogram(
            INFER_LATENCY_HISTOGRAM,
            "Per-request submit-to-result latency")
        wake = self._probe_wake[idx]
        while True:
            if idx in self._quarantined:
                wake.wait(self.probe_interval)
                wake.clear()
                if self._stopping:
                    return
                self._probe(idx, dev, params, states)
                continue
            b = self._bq.get()
            if b is _STOP:
                return
            err = self._run_batch(idx, dev, params, states, b, lat)
            if err is not None:
                self._fault_verdict(idx, b, err)

    def _fault_verdict(self, idx: int, b: _Batch, err: BaseException) -> None:
        """Attribute a batch fault (same-replica retries exhausted):
        multi-model batches ask the registry first — a fault the
        breaker pins on the MODEL fails the batch model-scoped and
        leaves the replica in the pool for its cotenants; a canary
        fault that just rolled the canary back fails the batch without
        touching either; anything else follows the PR-4 replica
        quarantine/redispatch path."""
        if self.slice_plane is not None and (
                self._is_chip_failure(err) or self._slice_dead is not None):
            # a chip died INSIDE the slice: the whole slice is the
            # failure domain — poison it and fail the batch typed
            # (replica quarantine makes no sense: there is no sibling
            # replica holding a whole copy of the params)
            self._slice_fail(err)
            self._fail_batch(b, self._slice_error())
            return
        verdict = "retry"
        if b.model is not None:
            verdict = self._registry.note_error(b.model, b.version)
        if verdict == "model_open":
            from deeplearning4j_tpu.serving.registry import ModelQuarantined
            mq = ModelQuarantined(
                f"model {b.model!r} v{b.version} quarantined after "
                f"cross-replica faults ({type(err).__name__}: {err})")
            mq.__cause__ = err
            mark("model_batch_failed", model=b.model, version=b.version,
                 scope="model")
            self._fail_batch(b, mq)
        elif verdict == "version_rejected":
            mark("model_batch_failed", model=b.model, version=b.version,
                 scope="version")
            self._fail_batch(b, err)
        else:
            self._quarantine(idx, b, err)

    def _fail_batch(self, b: _Batch, err: BaseException) -> None:
        """Resolve a model-scoped failed batch: futures carry the typed
        error, the engine (and its replicas) stay healthy."""
        failed = 0
        for r in b.requests:
            if not r.future.done():
                r.future.set_exception(err)
                failed += 1
        with self._lock:
            self._inflight -= 1
            self._resolved += failed

    def _run_batch(self, idx, dev, params, states, b, lat):
        """Run one batch with the per-replica retry budget; None on
        success (futures resolved), else the last error (batch NOT yet
        resolved — the caller decides quarantine/redispatch). Model
        batches resolve (fn, params, states) through the registry's
        per-device pins; canary batches additionally pay a host-side
        NaN scan so the canary watch sees poisoned outputs."""
        if self._slice_dead is not None:
            # the slice is already poisoned: fail fast and typed — a
            # dead chip's dispatch outcome is undefined, never retried
            self._fail_batch(b, self._slice_error())
            return None
        fn, gen, net, nan_check = self._fn, None, self.net, False
        if b.model is not None:
            try:
                mv = self._registry.version(b.model, b.version)
                fn, params, states = self._registry.acquire(
                    b.model, b.version, dev)
                net = mv.net()
                if b.payload is not None:
                    gen = mv.generator()
                nan_check = self._registry.wants_nan_check(b.model, b.version)
            except BaseException as e:
                record_fault("serving")
                self._fault_log.append(
                    f"replica {idx} acquire {b.model} v{b.version}: "
                    f"{type(e).__name__}: {e}")
                return e
        last: Optional[BaseException] = None
        for attempt in range(1 + self.max_batch_retries):
            t_disp = time.perf_counter()
            try:
                if b.payload is not None:
                    # fused decode batch: prefill + one-scan decode on
                    # this replica's pinned params (two dispatches)
                    lengths, keys, max_new, sampler = b.payload
                    self._hook(idx, b.x.shape, b.model)
                    y = (gen if gen is not None else self._generator()).run(
                        params, b.x, lengths, max_new, sampler, keys,
                        replica=idx, device=dev)
                else:
                    with span("stage", path="infer_feed", replica=idx):
                        x = (self._slice_put(b.x)
                             if self.slice_plane is not None
                             else jax.device_put(b.x, dev))
                    fresh = note_dispatch(
                        net, self._dispatch_sig(idx, b.x.shape,
                                                b.model, b.version))
                    if timeseries_enabled():
                        # jit-miss rate on the SERVE path: mean over a
                        # window is the fraction of dispatches that ate
                        # an XLA compile (steady state: 0.0)
                        self._ts.record(TS_ENGINE_JIT_MISS,
                                        1.0 if fresh else 0.0)
                    with span("compile" if fresh else "inference",
                              path="parallel_inference", replica=idx,
                              rows=b.rows, batch=int(b.x.shape[0])):
                        y = np.asarray(self._dispatch(
                            idx, params, states, x, fn=fn, model=b.model))
            except BaseException as e:
                last = e
                record_fault("serving")
                self._fault_log.append(
                    f"replica {idx} attempt {attempt + 1}: "
                    f"{type(e).__name__}: {e}")
                continue
            if b.payload is None:
                with self._lock:
                    self._probe_shape = tuple(b.x.shape[1:])
                    if b.model is not None:
                        self._model_probe[b.model] = (
                            b.version, tuple(b.x.shape[1:]))
            nan = False
            if nan_check and np.issubdtype(np.asarray(y).dtype, np.floating):
                # canary-only host scan: the NaN-output rollback signal
                nan = bool(np.isnan(np.asarray(y)).any())
            off = 0
            now = time.perf_counter()
            for r in b.requests:
                if r.trace is not None:
                    # per-request engine attribution from timestamps the
                    # path already takes: admission-queue wait, then the
                    # device dispatch this batch rode (spans recorded
                    # BEFORE the future resolves so the trace owner sees
                    # them at completion)
                    reqtrace.record_span(
                        r.trace, "engine_queue",
                        to_origin_us(r.t_submit),
                        (t_disp - r.t_submit) * 1e6, replica=idx)
                    reqtrace.record_span(
                        r.trace, "engine_dispatch",
                        to_origin_us(t_disp), (now - t_disp) * 1e6,
                        replica=idx, rows=b.rows,
                        batch=int(b.x.shape[0]),
                        kind="generate" if b.payload is not None
                        else "classify")
                r.future.set_result(r.finish(y[off:off + r.n]))
                off += r.n
                lat.observe((now - r.t_submit) * 1e3)
            with self._lock:
                self._inflight -= 1
                self._resolved += len(b.requests)
            if b.model is not None:
                self._registry.note_result(
                    b.model, b.version, (now - t_disp) * 1e3,
                    rows=len(b.requests), nan=nan,
                    shape=(tuple(b.x.shape[1:]) if b.payload is None
                           else None))
            return None
        return last

    # -------------------------------------------- quarantine + probing

    def _quarantined_gauge(self):
        return self._reg().gauge(
            FAULT_QUARANTINED_GAUGE,
            "Serving replicas currently quarantined after device errors")

    def _quarantine(self, idx: int, b: _Batch, err: BaseException) -> None:
        """Pull replica ``idx`` from the dispatch pool and hand its batch
        to a survivor; when every replica has given up on the batch (or
        none survive), fail its futures — a future is never stranded."""
        with self._lock:
            self._quarantined.add(idx)
            n_quarantined = len(self._quarantined)
            survivors = [i for i in range(len(self._replicas))
                         if i not in self._quarantined and i not in b.tried]
        self._quarantined_gauge().set(n_quarantined)
        mark("replica_quarantined", replica=idx, error=type(err).__name__)
        reqtrace.flight_event("quarantine", replica=idx,
                              error=type(err).__name__)
        b.tried.add(idx)
        if survivors and not self._stopping:
            self._bq.put(b)  # a surviving worker picks it up
            return
        failed = 0
        for r in b.requests:
            if not r.future.done():
                r.future.set_exception(err)
                failed += 1
        if self._error is None:
            self._error = err
        with self._lock:
            self._inflight -= 1
            self._resolved += failed

    def _probe_program(self, idx: int, dev, params, states):
        """(fn, params, states, shape, dtype, net, model, version) of a
        known-good single-row probe, or None when nothing trustworthy
        has served yet. Registry mode picks a model whose breaker is
        CLOSED — probing a quarantined replica with a poisoned model
        would pin the model's fault on the replica forever."""
        if self._registry is None:
            with self._lock:
                shape = self._probe_shape
            if shape is None:
                return None
            return (self._fn, params, states, shape, self._np_dtype,
                    self.net, None, None)
        with self._lock:
            cands = sorted(self._model_probe.items())
        for m, (v, shape) in cands:
            if self._registry.breaker_open(m):
                continue
            try:
                fn, p, s = self._registry.acquire(m, v, dev)
                mv = self._registry.version(m, v)
                return fn, p, s, shape, mv.np_dtype, mv.net(), m, v
            except BaseException:
                continue
        return None

    def _probe(self, idx: int, dev, params, states) -> None:
        """Reinstatement probe: dispatch a known-good single-row program
        on the quarantined replica; pass → rejoin the pool. Before any
        shape has served successfully there is nothing trustworthy to
        probe with — reinstate optimistically and let real traffic
        re-quarantine if the replica is still sick."""
        probe = self._probe_program(idx, dev, params, states)
        if probe is not None:
            fn, p, s, shape, dtype, net, m, v = probe
            try:
                zeros = np.zeros((1,) + tuple(shape), dtype)
                x = (self._slice_put(zeros) if self.slice_plane is not None
                     else jax.device_put(zeros, dev))
                note_dispatch(net, self._dispatch_sig(idx, zeros.shape, m, v))
                with span("inference", path="quarantine_probe", replica=idx):
                    np.asarray(self._dispatch(idx, p, s, x, fn=fn, model=m))
            except BaseException as e:
                record_fault("serving")
                self._fault_log.append(
                    f"replica {idx} probe: {type(e).__name__}: {e}")
                return  # still sick — stay quarantined
        with self._lock:
            self._quarantined.discard(idx)
            n_quarantined = len(self._quarantined)
        self._quarantined_gauge().set(n_quarantined)
        mark("replica_reinstated", replica=idx)

    # ---------------------------------------------- model circuit probes

    def _maybe_probe_models(self) -> None:
        """Throttled idle-path model probing (the dispatcher calls this
        on its bounded wakeups)."""
        now = time.monotonic()
        if now - self._model_probe_at < self.probe_interval:
            return
        self._model_probe_at = now
        self._probe_open_models()

    def _probe_open_models(self) -> None:
        """Probe every open-breaker model with a one-row known-good
        dispatch; a pass closes the breaker and the model rejoins the
        pool — the version-level mirror of replica reinstatement."""
        if self._registry is None:
            return
        for name in self._registry.open_models():
            version, shape, dtype = self._registry.probe_info(name)
            if version is None:
                continue
            if shape is None:
                # nothing known-good to probe with: reinstate
                # optimistically; real traffic re-opens if still sick
                self._registry.close_breaker(name)
                continue
            with self._lock:
                healthy = [i for i in range(len(self._replicas))
                           if i not in self._quarantined]
            idx = healthy[0] if healthy else 0
            dev = self._replicas[idx][0]
            try:
                fn, params, states = self._registry.acquire(
                    name, version, dev)
                net = self._registry.version(name, version).net()
                zeros = np.zeros((1,) + tuple(shape), dtype)
                x = jax.device_put(zeros, dev)
                note_dispatch(net, self._dispatch_sig(idx, zeros.shape,
                                                      name, version))
                with span("inference", path="model_probe", model=name,
                          replica=idx):
                    self._hook(idx, zeros.shape, name)
                    np.asarray(fn(params, states, x, None))
            except BaseException as e:
                record_fault("serving")
                self._fault_log.append(
                    f"model {name} probe: {type(e).__name__}: {e}")
                continue  # still sick — breaker stays open
            self._registry.close_breaker(name)
