#!/usr/bin/env python
"""Validate emitted telemetry against the checked-in schema.

Guards the three monitor/ wire formats against drift (a renamed field
silently breaks every downstream consumer — Perfetto, Prometheus
scrapers, BENCH attribution):

- JSONL event streams (``monitor.enable_tracing(jsonl_path=...)``)
- request-trace JSONL (``monitor/reqtrace.py`` flight-recorder dumps /
  ``UiServer /debug/traces``): span records whose parent edges must
  resolve, one root per trace, per-process monotonic timestamps —
  plus :func:`validate_migration_coverage`, the durable-decode bar
  that a migrated stream's token-gap is fully attributed by spans
- Chrome ``trace_event`` JSON exports (``PhaseTracer.chrome_trace``)
- Prometheus text exposition (``MetricsRegistry.prometheus_text`` /
  ``UiServer /metrics``)

Importable (``tests/test_monitor.py`` wires it into tier-1) and a CLI::

    python scripts/check_telemetry_schema.py run/events.jsonl \
        run/trace.json --metrics metrics.txt

Exit 0 when everything validates; 1 with one line per violation.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
from typing import Any, Dict, Iterable, List

# ----------------------------------------------------------- JSONL events

EVENT_TYPES = {"span", "event"}
# required key -> allowed python types, per event type
SPAN_KEYS = {"type": str, "name": str, "ts_us": (int, float),
             "dur_us": (int, float), "pid": int, "tid": int}
INSTANT_KEYS = {"type": str, "name": str, "ts_us": (int, float),
                "pid": int, "tid": int}
OPTIONAL_KEYS = {"attrs": dict}
# the span tree (monitor/tracing.py): a span's own id, the id of the span
# that was open on its thread when it started (null for a root), and the id
# of its tree's root, which the spans of one dispatch share — fit_scan emits
# ``device_step`` > ``launch``, ``fetch`` and, for a program's first
# dispatch, ``compile`` > ``compile_launch`` (> ``trace_step``,
# ``lower_step``, ``load_step``, ``first_launch``), ``fetch``
SPAN_TREE_KEYS = {"id": int, "parent": (int, type(None)), "dispatch": int}


def validate_event(obj: Any, where: str = "event") -> List[str]:
    errors: List[str] = []
    if not isinstance(obj, dict):
        return [f"{where}: not a JSON object"]
    etype = obj.get("type")
    if etype not in EVENT_TYPES:
        return [f"{where}: type {etype!r} not in {sorted(EVENT_TYPES)}"]
    required = SPAN_KEYS if etype == "span" else INSTANT_KEYS
    for key, types in required.items():
        if key not in obj:
            errors.append(f"{where}: missing required key {key!r}")
        elif not isinstance(obj[key], types):
            errors.append(f"{where}: key {key!r} has type "
                          f"{type(obj[key]).__name__}")
    tree = SPAN_TREE_KEYS if etype == "span" else {}
    for key in obj:
        if key in tree:
            if not isinstance(obj[key], tree[key]) \
                    or isinstance(obj[key], bool):
                errors.append(f"{where}: key {key!r} has type "
                              f"{type(obj[key]).__name__}")
        elif key not in required and key not in OPTIONAL_KEYS:
            errors.append(f"{where}: unknown key {key!r}")
    if tree and any(k in obj for k in tree) and not all(k in obj for k in tree):
        errors.append(f"{where}: a span carries all of "
                      f"{sorted(SPAN_TREE_KEYS)} or none")
    if "attrs" in obj and not isinstance(obj["attrs"], dict):
        errors.append(f"{where}: attrs must be an object")
    if not errors:
        if not obj["name"]:
            errors.append(f"{where}: empty name")
        if obj["ts_us"] < 0:
            errors.append(f"{where}: negative ts_us")
        if etype == "span" and obj["dur_us"] < 0:
            errors.append(f"{where}: negative dur_us")
    return errors


def validate_events_lines(lines: Iterable[str],
                          where: str = "events") -> List[str]:
    errors: List[str] = []
    n = 0
    spans: Dict[int, Dict[str, Any]] = {}
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        n += 1
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"{where}:{i}: invalid JSON: {e}")
            continue
        found = validate_event(obj, f"{where}:{i}")
        errors.extend(found)
        if not found and obj["type"] == "span" and "id" in obj:
            if (obj["pid"], obj["id"]) in spans:
                errors.append(f"{where}:{i}: duplicate span id {obj['id']}")
            spans[(obj["pid"], obj["id"])] = obj
    # children close, and are written, before their parents: edges are
    # checked once the stream has ended. A tree whose root never arrived
    # was still open when the stream was cut (a crash mid-dispatch, a
    # tracer switched on or off inside a span): a warning, not an error
    for (pid, _), obj in spans.items():
        parent = spans.get((pid, obj["parent"]))
        if obj["parent"] is None:
            if obj["dispatch"] != obj["id"]:
                errors.append(f"{where}: root span {obj['id']} is not its "
                              f"own dispatch")
        elif parent is None:
            msg = (f"{where}: span {obj['id']} ({obj['name']}) has an "
                   f"unresolved parent {obj['parent']}")
            if (pid, obj["dispatch"]) in spans:
                errors.append(msg)
            else:
                print(f"warning: {msg}: its tree {obj['dispatch']} never "
                      f"closed, the stream was cut", file=sys.stderr)
        elif parent["dispatch"] != obj["dispatch"]:
            errors.append(f"{where}: span {obj['id']} and its parent "
                          f"disagree on the dispatch id")
    if n == 0:
        errors.append(f"{where}: no events (empty stream)")
    return errors


def validate_events_file(path: str) -> List[str]:
    with open(path) as f:
        return validate_events_lines(f, path)


# ------------------------------------------- request traces (reqtrace)

# monitor/reqtrace.py span records: the cross-process request-trace
# JSONL (flight-recorder dumps, UiServer /debug/traces). One record
# per span; parent edges must RESOLVE inside the merged trace.
REQSPAN_KEYS = {"type": str, "trace": str, "span": str, "name": str,
                "ts_us": (int, float), "dur_us": (int, float),
                "pid": int, "tid": int}
REQSPAN_OPTIONAL = {"attrs": dict}
FLIGHT_EVENT_KEYS = {"type": str, "kind": str, "ts_us": (int, float),
                     "pid": int}


def validate_reqspan(obj: Any, where: str = "reqspan") -> List[str]:
    errors: List[str] = []
    if not isinstance(obj, dict):
        return [f"{where}: not a JSON object"]
    if obj.get("type") != "reqspan":
        return [f"{where}: type {obj.get('type')!r} != 'reqspan'"]
    for key, types in REQSPAN_KEYS.items():
        if key not in obj:
            errors.append(f"{where}: missing required key {key!r}")
        elif not isinstance(obj[key], types):
            errors.append(f"{where}: key {key!r} has type "
                          f"{type(obj[key]).__name__}")
    if "parent" not in obj:
        errors.append(f"{where}: missing required key 'parent'")
    elif obj["parent"] is not None and not isinstance(obj["parent"], str):
        errors.append(f"{where}: parent must be a span id or null")
    for key in obj:
        if key not in REQSPAN_KEYS and key != "parent" \
                and key not in REQSPAN_OPTIONAL:
            errors.append(f"{where}: unknown key {key!r}")
    if not errors:
        if not obj["name"]:
            errors.append(f"{where}: empty name")
        if obj["ts_us"] < 0:
            errors.append(f"{where}: negative ts_us")
        if obj["dur_us"] < 0:
            errors.append(f"{where}: negative dur_us")
    return errors


def validate_trace_spans(spans: List[Any], where: str = "trace",
                         require_single_root: bool = True) -> List[str]:
    """Structural validity of ONE merged request trace: every span
    record well-formed, span ids unique, every parent edge resolves
    (no orphan spans), exactly one root, and per-(pid, tid) record
    order monotonic in span END time — a process whose clock ran
    backwards (or a buggy producer recording out of order) fails here,
    while cross-process clock skew (different origins) does not."""
    errors: List[str] = []
    for i, s in enumerate(spans):
        errors.extend(validate_reqspan(s, f"{where}[{i}]"))
    if errors:
        return errors
    if not spans:
        return [f"{where}: empty trace (no spans)"]
    traces = {s["trace"] for s in spans}
    if len(traces) != 1:
        errors.append(f"{where}: spans from {len(traces)} trace ids "
                      f"in one trace")
    ids = [s["span"] for s in spans]
    if len(set(ids)) != len(ids):
        errors.append(f"{where}: duplicate span ids")
    known = set(ids)
    roots = 0
    for i, s in enumerate(spans):
        if s["parent"] is None:
            roots += 1
        elif s["parent"] not in known:
            errors.append(f"{where}[{i}]: orphan span {s['span']!r} "
                          f"({s['name']}): parent {s['parent']!r} does "
                          f"not resolve")
    if require_single_root and roots != 1:
        errors.append(f"{where}: {roots} root spans (want exactly 1)")
    # per-process monotonicity: records land in close order, so within
    # one (pid, tid) the END timestamps must be non-decreasing in list
    # order (1us slack for the 3-decimal rounding)
    last_end: Dict[tuple, float] = {}
    for i, s in enumerate(spans):
        key = (s["pid"], s["tid"])
        end = s["ts_us"] + s["dur_us"]
        prev = last_end.get(key)
        if prev is not None and end < prev - 1.0:
            errors.append(
                f"{where}[{i}]: non-monotonic timestamps in pid "
                f"{s['pid']}/tid {s['tid']}: span {s['name']} ends at "
                f"{end:.1f}us after a record ending {prev:.1f}us")
        last_end[key] = max(prev or 0.0, end)
    return errors


def validate_migration_coverage(spans: List[Dict[str, Any]],
                                where: str = "trace",
                                tol_us: float = 5e3) -> List[str]:
    """The durable-decode acceptance bar, checked on ONE migrated
    stream's merged trace: the migration token-gap must be fully
    attributed — a ``silence_wait`` span (last chunk → failure
    detection), a ``repin`` span (re-pin + resume re-submit), a resume
    ``dispatch`` carrying the journaled prefix, the resume re-prefill
    (``prefill`` span with ``resume: true``), and a first post-resume
    ``decode_burst`` — and those spans must TILE the interval from
    silence start to the end of the resume prefill with no hole larger
    than ``tol_us``."""
    errors: List[str] = []
    by = lambda n: [s for s in spans if s["name"] == n]
    sw, rp = by("silence_wait"), by("repin")
    resume_pre = [s for s in by("prefill")
                  if (s.get("attrs") or {}).get("resume")]
    disp = by("dispatch")
    resume_disp = [s for s in disp
                   if (s.get("attrs") or {}).get("resume_prefix")]
    if not sw:
        errors.append(f"{where}: migrated stream has no silence_wait span")
    if not rp:
        errors.append(f"{where}: no repin span")
    if len(disp) < 2:
        errors.append(f"{where}: fewer than 2 dispatch spans for a "
                      f"migrated stream")
    if not resume_disp:
        errors.append(f"{where}: no dispatch carrying a resume prefix")
    if not resume_pre:
        errors.append(f"{where}: resume re-prefill not attributed "
                      f"(no prefill span with resume=true)")
    if not errors:
        t_rp = max(s["ts_us"] for s in rp)
        bursts_after = [s for s in by("decode_burst")
                        if s["ts_us"] >= t_rp - 1.0]
        if not bursts_after:
            errors.append(f"{where}: no decode_burst span after the "
                          f"resume (first resumed burst unattributed)")
    if errors:
        return errors
    # gap coverage (one merged clock): from silence start to the end of
    # the resume re-prefill, the migration machinery's spans must tile
    # the interval — any hole is unattributed token-gap time
    t0 = min(s["ts_us"] for s in sw)
    t1 = max(s["ts_us"] + s["dur_us"] for s in resume_pre)
    segs = sorted(
        (s["ts_us"], s["ts_us"] + s["dur_us"]) for s in spans
        if s["name"] in ("silence_wait", "repin", "dispatch",
                         "queue_wait", "prefill", "decode_burst"))
    cover = t0
    for a, b in segs:
        if b <= cover:
            continue
        if a > cover + tol_us:
            errors.append(
                f"{where}: migration gap hole "
                f"{cover:.0f}..{a:.0f}us uncovered by spans")
            return errors
        cover = max(cover, b)
        if cover >= t1:
            break
    if cover < t1 - tol_us:
        errors.append(f"{where}: migration gap uncovered after "
                      f"{cover:.0f}us (resume prefill ends {t1:.0f}us)")
    return errors


def validate_flight_lines(lines: Iterable[str],
                          where: str = "flight") -> List[str]:
    """Validate a flight-recorder JSONL dump (or UiServer
    /debug/traces body): ``flight_event`` records, ``trace`` records
    (each embedded span list fully validated), and bare ``reqspan``
    streams."""
    errors: List[str] = []
    n = 0
    for i, line in enumerate(lines, 1):
        line = line.strip()
        if not line:
            continue
        n += 1
        w = f"{where}:{i}"
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"{w}: invalid JSON: {e}")
            continue
        if not isinstance(obj, dict):
            errors.append(f"{w}: not a JSON object")
            continue
        t = obj.get("type")
        if t == "flight_event":
            for key, types in FLIGHT_EVENT_KEYS.items():
                if key not in obj:
                    errors.append(f"{w}: missing required key {key!r}")
                elif not isinstance(obj[key], types):
                    errors.append(f"{w}: key {key!r} has type "
                                  f"{type(obj[key]).__name__}")
        elif t == "trace":
            for key in ("trace", "root", "name", "spans"):
                if key not in obj:
                    errors.append(f"{w}: missing required key {key!r}")
            if isinstance(obj.get("spans"), list):
                errors.extend(validate_trace_spans(obj["spans"], w))
            else:
                errors.append(f"{w}: spans is not an array")
        elif t == "reqspan":
            errors.extend(validate_reqspan(obj, w))
        else:
            errors.append(f"{w}: unknown record type {t!r}")
    if n == 0:
        errors.append(f"{where}: no records (empty stream)")
    return errors


def validate_flight_file(path: str) -> List[str]:
    with open(path) as f:
        return validate_flight_lines(f, path)


def validate_jsonl_file(path: str) -> List[str]:
    """Sniff a .jsonl file: flight-recorder / reqtrace records get the
    request-trace validation, everything else the PhaseTracer event
    schema."""
    with open(path) as f:
        lines = f.readlines()
    for line in lines:
        line = line.strip()
        if not line:
            continue
        try:
            t = json.loads(line).get("type")
        except Exception:
            break
        if t in ("reqspan", "flight_event", "trace"):
            return validate_flight_lines(lines, path)
        break
    return validate_events_lines(lines, path)


# ------------------------------------------------------ Chrome trace JSON

def validate_chrome_trace(obj: Any, where: str = "trace") -> List[str]:
    errors: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return [f"{where}: must be an object with a traceEvents array"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return [f"{where}: traceEvents is not an array"]
    phases_seen = 0
    for i, e in enumerate(events):
        w = f"{where}.traceEvents[{i}]"
        if not isinstance(e, dict):
            errors.append(f"{w}: not an object")
            continue
        ph = e.get("ph")
        if ph not in ("X", "i", "M", "B", "E"):
            errors.append(f"{w}: unknown ph {ph!r}")
            continue
        if "name" not in e or "pid" not in e:
            errors.append(f"{w}: missing name/pid")
        if ph == "X":
            phases_seen += 1
            for k in ("ts", "dur", "tid"):
                if not isinstance(e.get(k), (int, float)):
                    errors.append(f"{w}: ph=X needs numeric {k}")
        if ph == "i" and not isinstance(e.get("ts"), (int, float)):
            errors.append(f"{w}: ph=i needs numeric ts")
    if phases_seen == 0:
        errors.append(f"{where}: no complete (ph=X) span events")
    return errors


def validate_chrome_trace_file(path: str) -> List[str]:
    try:
        with open(path) as f:
            obj = json.load(f)
    except json.JSONDecodeError as e:
        return [f"{path}: invalid JSON: {e}"]
    return validate_chrome_trace(obj, path)


# ------------------------------------------------ known dl4j metric names

# The pinned registry of in-tree ``dl4j_``-prefixed metric families.
# A renamed family silently breaks every downstream consumer (BENCH
# attribution, Prometheus dashboards), so ``validate_known_metrics``
# flags any dl4j_ family an exposition declares that is not listed
# here — add new names HERE in the same PR that introduces them.
KNOWN_DL4J_METRICS = {
    # monitor core (tracing / step health / listeners)
    "dl4j_phase_duration_ms",
    "dl4j_step_duration_ms",
    "dl4j_step_duration_p50_ms",
    "dl4j_step_duration_p99_ms",
    "dl4j_score",
    "dl4j_nan_scores_total",
    "dl4j_slow_steps_total",
    "dl4j_iterations_total",
    "dl4j_iterations_per_sec",
    "dl4j_examples_per_sec",
    # streaming pipelines
    "dl4j_stream_batches_total",
    "dl4j_stream_buffer_examples",
    "dl4j_stream_examples_total",
    "dl4j_stream_requests_total",
    # device-feed pipeline (datasets/iterators.py + the fit() paths)
    "dl4j_feed_h2d_bytes_total",
    "dl4j_feed_queue_depth",
    "dl4j_feed_padded_batches_total",
    "dl4j_jit_cache_miss_total",
    "dl4j_score_sync_total",
    "dl4j_flash_path_total",
    "dl4j_ssd_path_total",
    "dl4j_recomputed_blocks",
    "dl4j_recompute_kept_values",
    "dl4j_span_passes",
    "dl4j_block_applications",
    "dl4j_selscan_path_total",
    "dl4j_flash_windowed_total",
    "dl4j_forwarded_values",
    "dl4j_step_program_bytes",
    "dl4j_step_program_flops",
    "dl4j_moe_path_total",
    "dl4j_moe_experts_held",
    "dl4j_moe_layers",
    "dl4j_moe_held_share",
    # serving plane (parallel/inference.py ParallelInference)
    "dl4j_infer_requests_total",
    "dl4j_infer_batches_total",
    "dl4j_infer_batch_size",
    "dl4j_infer_queue_depth",
    "dl4j_infer_padded_ratio",
    "dl4j_infer_latency_ms",
    # generation plane (nn/generate.py fused autoregressive decode,
    # served via ParallelInference.submit_generate)
    "dl4j_decode_requests_total",
    "dl4j_decode_prefill_tokens_total",
    "dl4j_decode_tokens_total",
    "dl4j_decode_prefill_latency_ms",
    "dl4j_decode_latency_ms",
    # multi-model serving plane (serving/registry.py ModelRegistry +
    # the registry-mode ParallelInference): per-model traffic/latency,
    # lifecycle events (deploys by outcome, rollbacks by reason,
    # budget evictions), active-version / breaker / pinned-bytes gauges
    "dl4j_model_requests_total",
    "dl4j_model_errors_total",
    "dl4j_model_latency_ms",
    "dl4j_model_deploys_total",
    "dl4j_model_rollbacks_total",
    "dl4j_model_evictions_total",
    "dl4j_model_active_version",
    "dl4j_model_breaker_open",
    "dl4j_model_pinned_bytes",
    # continuous batching plane (serving/continuous.py decode
    # scheduler + nn/kvpool.py paged KV block pool): pool occupancy /
    # exhaustion and the iteration-level scheduler's admit / retire /
    # preempt / burst accounting
    "dl4j_kvpool_blocks_total",
    "dl4j_kvpool_blocks_free",
    "dl4j_kvpool_alloc_failures_total",
    "dl4j_sched_admitted_rows_total",
    "dl4j_sched_retired_rows_total",
    "dl4j_sched_preemptions_total",
    "dl4j_sched_bursts_total",
    "dl4j_sched_burst_latency_ms",
    "dl4j_sched_active_sequences",
    "dl4j_sched_queued_prefills",
    # cross-request prefix cache (serving/prefixcache.py PrefixCache
    # over the refcounted paged pool): admission hit/miss volume,
    # deterministic LRU evictions, copy-on-write block duplications,
    # cached/shared block gauges, and the prompt tokens whose prefill
    # was skipped because their KV blocks were already cached
    "dl4j_prefixcache_hits_total",
    "dl4j_prefixcache_misses_total",
    "dl4j_prefixcache_evictions_total",
    "dl4j_prefixcache_cow_copies_total",
    "dl4j_prefixcache_cached_blocks",
    "dl4j_prefixcache_shared_blocks",
    "dl4j_prefixcache_saved_prefill_tokens_total",
    # horizontal serving tier (serving/router.py InferenceRouter)
    "dl4j_router_requests_total",
    "dl4j_router_shed_total",
    "dl4j_router_hedges_total",
    "dl4j_router_failovers_total",
    "dl4j_router_queue_wait_ms",
    "dl4j_router_latency_ms",
    "dl4j_router_endpoint_healthy",
    # wire/transport data plane (serving/wire.py v4 binary framing +
    # the router's event-loop core): frames/bytes packed by framing
    # (legacy npz vs v4 zero-copy segments), stream deltas that rode a
    # coalesced burst frame, and the router timer-loop's firing lag
    "dl4j_wire_frames_total",
    "dl4j_wire_bytes_total",
    "dl4j_wire_coalesced_chunks_total",
    "dl4j_router_loop_lag_ms",
    # end-to-end request tracing + SLO attribution
    # (monitor/reqtrace.py): per-request phase decomposition, TTFT /
    # TPOT as the caller observed them, per-model SLO burn outcomes,
    # span volume / bounded-buffer drops / open-trace gauge, and
    # flight-recorder triggers (each dumps the trace+event rings as
    # JSONL when a dump dir is armed)
    "dl4j_req_phase_ms",
    "dl4j_req_ttft_ms",
    "dl4j_req_tpot_ms",
    "dl4j_req_slo_burn_total",
    "dl4j_trace_spans_total",
    "dl4j_trace_dropped_total",
    "dl4j_trace_active",
    "dl4j_trace_flight_dumps_total",
    # durable decode streams (chunked token deltas, session journals,
    # cross-engine migration resume): chunks emitted by the decode
    # plane, migrations by reason, live journal bytes, and the resume
    # cost in re-submitted prefix tokens
    "dl4j_stream_chunks_total",
    "dl4j_session_migrations_total",
    "dl4j_session_journal_bytes",
    "dl4j_router_resume_prefix_tokens_total",
    # mesh plane (parallel/mesh.py MeshPlane): active named-axis
    # topology (devices + per-axis size) and checkpoint restores that
    # re-lowered saved shards onto a different mesh shape
    "dl4j_mesh_devices",
    "dl4j_mesh_axis_size",
    "dl4j_mesh_restore_relayouts_total",
    # mesh-sharded serving slices (parallel/inference.py slice_plane= +
    # serving/fleet.py): per-slice topology/degraded state, elastic
    # narrower-width rebuilds, and disaggregated prefill→decode KV
    # handoffs (zero prompt tokens recomputed on the decode side)
    "dl4j_slice_devices",
    "dl4j_slice_degraded",
    "dl4j_slice_rebuilds_total",
    "dl4j_disagg_kv_handoffs_total",
    # quantized serving plane (nn/quantize.py weight quantization +
    # the nn/kvpool.py quantized paged KV pool): quantized-net count
    # by dtype, quantized-pool block gauge, per-matrix dequant scale
    # stats, and the accuracy-gate pass/fail verdict counter
    "dl4j_quant_models",
    "dl4j_quant_kv_blocks",
    "dl4j_quant_scale_absmax",
    "dl4j_quant_accuracy_gate_outcome_total",
    # fault-tolerance plane (supervisor / quarantine / dead-letter /
    # checkpoint integrity — see monitor/__init__.py FAULT_* names)
    "dl4j_fault_events_total",
    "dl4j_fault_rollbacks_total",
    "dl4j_fault_quarantined_replicas",
    "dl4j_fault_dead_letter_total",
    "dl4j_fault_checkpoint_integrity_failures_total",
    # capacity observatory — windowed time-series (monitor/timeseries.py
    # TimeSeriesStore behind the registry; the dl4j_ts_* names are
    # SERIES keys answered by query(name, window), carried in stats()
    # payloads and served at UiServer /timeseries rather than exposed
    # as Prometheus families — pinned here all the same, one name one
    # meaning):
    "dl4j_ts_sched_active_rows",
    "dl4j_ts_sched_queued_prefills",
    "dl4j_ts_sched_pool_occupancy",
    "dl4j_ts_sched_prefix_hit_rate",
    "dl4j_ts_router_queue_depth",
    "dl4j_ts_router_admit_error_ms",
    "dl4j_ts_router_shed",
    "dl4j_ts_engine_fill_ratio",
    "dl4j_ts_engine_jit_miss",
    "dl4j_ts_slo_burn",
    "dl4j_ts_worker_served",
    # capacity observatory — per-owner resource attribution
    # (nn/kvpool.py byte-seconds + serving/continuous.py token/queue
    # accounting, label model=/owner=):
    "dl4j_attr_kv_byte_seconds",
    "dl4j_attr_prefill_tokens_total",
    "dl4j_attr_decode_tokens_total",
    "dl4j_attr_queue_ms_total",
    # speculative decoding (nn/generate.py spec programs +
    # serving/continuous.py fused draft/verify rounds, label model=):
    # proposed/accepted/rejected count draft tokens through the exact
    # rejection sampler; accept_rate is the running acceptance rate
    # (compare against the deploy-time quality-gate prior in
    # registry stats); draft_latency_ms is the draft-phase wall time
    "dl4j_spec_proposed_tokens_total",
    "dl4j_spec_accepted_tokens_total",
    "dl4j_spec_rejected_tokens_total",
    "dl4j_spec_accept_rate",
    "dl4j_spec_draft_latency_ms",
    # KV tiering + session hibernation (nn/kvpool.py host-RAM tier +
    # serving/continuous.py swap-aware scheduler + serving/router.py
    # durable session handles): swap traffic both directions,
    # prefix-cache demote-to-host rescues, host-tier occupancy and
    # per-direction swap latency, hibernated-session volume, restores
    # by exactness rung (label path=host|ship|journal), and host-tier
    # byte-seconds attribution (label owner=)
    "dl4j_kvtier_swap_out_total",
    "dl4j_kvtier_swap_in_total",
    "dl4j_kvtier_demotions_total",
    "dl4j_kvtier_hibernated_sessions_total",
    "dl4j_kvtier_restore_total",
    "dl4j_kvtier_host_blocks",
    "dl4j_kvtier_swap_latency_ms",
    "dl4j_prefixcache_demotions_total",
    "dl4j_attr_kv_host_byte_seconds",
}


def validate_known_metrics(text: str, where: str = "metrics") -> List[str]:
    """Flag dl4j_ families not in the pinned registry (drift guard)."""
    errors: List[str] = []
    for i, line in enumerate(text.splitlines(), 1):
        if not line.startswith("# TYPE "):
            continue
        parts = line.split()
        if len(parts) != 4:
            continue  # malformed TYPE lines are validate_prometheus_text's job
        name = parts[2]
        if name.startswith("dl4j_") and name not in KNOWN_DL4J_METRICS:
            errors.append(
                f"{where}:{i}: unknown dl4j_ metric family {name!r} — "
                "add it to KNOWN_DL4J_METRICS if it is intentional")
    return errors


# -------------------------------------------------- Prometheus exposition

_METRIC_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^}]*\})?"
    r" (?P<value>-?[0-9.eE+]+|NaN|\+Inf|-Inf)"
    r"( -?[0-9]+)?$")
# label values may escape ONLY backslash, double-quote and newline
# (text-format spec 0.0.4) — any other backslash escape is malformed
_LABEL_RE = re.compile(r'^[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\["\\n])*"$')
# HELP text may escape ONLY backslash and newline (quotes stay literal)
_HELP_TEXT_RE = re.compile(r"^(?:[^\\]|\\\\|\\n)*$")


def _base_family(name: str, families: Dict[str, str]) -> str:
    """Map a sample name to its declared family (histogram samples use
    the ``_bucket``/``_sum``/``_count`` suffixes)."""
    if name in families:
        return name
    for suffix in ("_bucket", "_sum", "_count"):
        if name.endswith(suffix) and name[:-len(suffix)] in families:
            return name[:-len(suffix)]
    return name


def validate_prometheus_text(text: str,
                             where: str = "metrics") -> List[str]:
    errors: List[str] = []
    families: Dict[str, str] = {}  # name -> kind
    helps: Dict[str, int] = {}     # name -> HELP line number
    samples: Dict[str, List[Dict[str, str]]] = {}
    for i, line in enumerate(text.splitlines(), 1):
        w = f"{where}:{i}"
        if not line.strip():
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in (
                    "counter", "gauge", "histogram", "summary", "untyped"):
                errors.append(f"{w}: malformed TYPE line")
                continue
            if parts[2] in families:
                errors.append(f"{w}: duplicate TYPE for {parts[2]}")
            families[parts[2]] = parts[3]
            continue
        if line.startswith("# HELP "):
            parts = line.split(None, 3)  # "#", "HELP", name, text
            if len(parts) < 3:
                errors.append(f"{w}: malformed HELP line")
                continue
            hname = parts[2]
            if hname in helps:
                errors.append(f"{w}: duplicate HELP for {hname}")
            helps[hname] = i
            htext = parts[3] if len(parts) == 4 else ""
            if not _HELP_TEXT_RE.match(htext):
                errors.append(
                    f"{w}: HELP text for {hname} has an invalid escape "
                    "(only \\\\ and \\n are allowed)")
            continue
        if line.startswith("#"):
            continue  # other comments
        m = _METRIC_RE.match(line)
        if m is None:
            errors.append(f"{w}: unparseable sample: {line!r}")
            continue
        name = m.group("name")
        labels: Dict[str, str] = {}
        raw = (m.group("labels") or "{}")[1:-1]
        if raw:
            for part in raw.split(","):
                if not _LABEL_RE.match(part):
                    errors.append(f"{w}: malformed label {part!r}")
                    continue
                k, v = part.split("=", 1)
                labels[k] = v[1:-1]
        fam = _base_family(name, families)
        if fam not in families:
            errors.append(f"{w}: sample {name} has no preceding # TYPE")
            continue
        samples.setdefault(fam, []).append(
            {"name": name, "labels": labels, "value": m.group("value")})
    # every HELP line must name a family that a TYPE line declares
    for hname, hline in helps.items():
        if hname not in families:
            errors.append(f"{where}:{hline}: HELP for {hname} has no "
                          f"matching # TYPE declaration")
    # histogram families must ship the full bucket/sum/count triple with a
    # +Inf bucket whose count equals _count
    for fam, kind in families.items():
        fam_samples = samples.get(fam, [])
        if not fam_samples:
            errors.append(f"{where}: family {fam} declared but no samples")
            continue
        if kind != "histogram":
            continue
        names = {s["name"] for s in fam_samples}
        for suffix in ("_bucket", "_sum", "_count"):
            if fam + suffix not in names:
                errors.append(f"{where}: histogram {fam} missing {suffix}")
        by_key: Dict[tuple, Dict[str, float]] = {}
        for s in fam_samples:
            key = tuple(sorted((k, v) for k, v in s["labels"].items()
                               if k != "le"))
            slot = by_key.setdefault(key, {})
            if s["name"] == fam + "_bucket" and s["labels"].get("le") == "+Inf":
                slot["inf"] = float(s["value"])
            if s["name"] == fam + "_count":
                slot["count"] = float(s["value"])
        for key, slot in by_key.items():
            if "inf" not in slot:
                errors.append(f"{where}: histogram {fam}{dict(key)} "
                              f"missing le=\"+Inf\" bucket")
            elif slot.get("count") is not None and slot["inf"] != slot["count"]:
                errors.append(f"{where}: histogram {fam}{dict(key)} +Inf "
                              f"bucket {slot['inf']} != count {slot['count']}")
    return errors


def validate_prometheus_file(path: str) -> List[str]:
    with open(path) as f:
        return validate_prometheus_text(f.read(), path)


# ---------------------------------------------------------------- CLI

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("paths", nargs="*",
                    help=".jsonl = event stream, .json = Chrome trace")
    ap.add_argument("--metrics", action="append", default=[],
                    help="Prometheus text exposition file(s)")
    ap.add_argument("--check-names", action="store_true",
                    help="additionally flag dl4j_ metric families missing "
                         "from the pinned KNOWN_DL4J_METRICS registry")
    args = ap.parse_args(argv)
    if not args.paths and not args.metrics:
        ap.error("nothing to validate")
    errors: List[str] = []
    for path in args.paths:
        if path.endswith(".jsonl"):
            errors.extend(validate_jsonl_file(path))
        else:
            errors.extend(validate_chrome_trace_file(path))
    for path in args.metrics:
        errors.extend(validate_prometheus_file(path))
        if args.check_names:
            with open(path) as f:
                errors.extend(validate_known_metrics(f.read(), path))
    for e in errors:
        print(e, file=sys.stderr)
    total = len(args.paths) + len(args.metrics)
    if not errors:
        print(f"ok: {total} file(s) validated")
    return 1 if errors else 0


if __name__ == "__main__":
    sys.exit(main())
