#!/usr/bin/env python
"""Stress the fault-injection suite: rerun it K times with rotating
seeds and fail on ANY nondeterminism.

Order-dependent flakes (the w2v trained-vector family PR 6 root-caused
to CPU donation aliasing) present as tests whose outcome depends on
what ran before them — a single green run proves nothing. This tool
pins the determinism contract the ``faultinject`` marker promises
("a failing test replays bit-identically") two ways:

**Full mode (CLI)** — spawn ``pytest -m faultinject`` in a FRESH
process K times (subprocess-per-run is mandatory: fork-after-jax is
unreliable on this box, and a fresh interpreter is the only honest
replay), rotating ``PYTHONHASHSEED`` / ``DL4J_TPU_STRESS_SEED`` across
runs. Any test whose outcome differs between runs is nondeterministic
→ exit 1 (a test that fails identically every run is a deterministic
failure — also exit 1, but reported as such)::

    python scripts/stress_faultinject.py --runs 3 [--seed-base 0]
        [-m faultinject] [--pytest-args ...]

**Quick mode (importable — wired into tier-1)** — :func:`quick_check`
first runs SECTION 0: the unified static-analysis engine
(``scripts/analyze.py --json`` semantics — every rule, repo-wide,
suppressions + baseline applied) and FAILS FAST on any new finding
before a single chaos phase spends time — a lock-order inversion or an
untyped wire raise is cheaper to report from the AST than to hunt in a
drill log. Then it replays the in-process deterministic injector
battery (seeded NaN/raise schedules, flaky-broker schedules,
torn-write counting, replica/model poison sequences, burst-kill
windows, mesh-shrink drills, and the composed ChaosSchedule event
clock, the prefix-cache refcount/COW/eviction accounting drill, and
the slice-kill / slice-drill schedules, the quantized-pool ×
prefix-cache accounting drill, the speculative-decoding dual-lane
(draft + target) accounting drill, the wire-v4 torn-frame /
reassembly drill, and the host-tier (KV tiering) swap /
budget-pressure / reclaimer-chain accounting drill — sections 1–13)
twice per seed
across rotating seeds and compares the full event logs bit-for-bit.
It runs in milliseconds with no subprocess and no jax compute, so the
tier-1 sweep carries it on every run; the full mode is the pre-merge /
CI deep check.

**Chaos mode (CLI)** — ``--chaos`` runs the COMPOSED drill
(:func:`deeplearning4j_tpu.faultinject.chaos.run_chaos_drill` — every
injector on one seeded event clock against a live 3-endpoint fleet)
twice per rotating seed in fresh subprocesses, failing on any global
invariant violation (lost/duplicated tokens, stranded futures, leaked
KV blocks, unhealthy fleet) or ANY outcome drift between the two
replays of one seed::

    python scripts/stress_faultinject.py --chaos --runs 3

**Hibernation mode (CLI)** — ``--hibernation`` runs the
SESSION-HIBERNATION drill
(:func:`deeplearning4j_tpu.faultinject.chaos.run_hibernation_drill` —
hibernate N sessions into the host KV tier, kill the seeded endpoint,
resume every session on the survivors down the host → shipped-blocks
→ journaled-prefix exactness ladder, the second half under
``HostTierPressure``) twice per rotating seed in fresh subprocesses,
failing on any invariant violation (token mismatches, dup/gap
offsets, leaked blocks on either tier, stranded handles) or outcome
drift between replays::

    python scripts/stress_faultinject.py --hibernation --runs 3
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys
from typing import Dict, List

# runnable from anywhere: the repo root (the package's parent) must be
# importable when invoked as a script rather than through pytest
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

# ----------------------------------------------------------- quick mode


def _scenario_log(seed: int) -> str:
    """One deterministic pass over the injector battery; returns the
    full event log. The determinism contract: same seed → identical
    log, bit for bit."""
    import numpy as np

    from deeplearning4j_tpu.datasets.dataset import DataSet
    from deeplearning4j_tpu.datasets.iterators import ListDataSetIterator
    from deeplearning4j_tpu.faultinject import (BurstKill, ChipFailure,
                                                FailingDataSetIterator,
                                                FlakyBroker, InjectedFault,
                                                MeshShrink, ModelPoison,
                                                ReplicaPoison, TornWrites)
    from deeplearning4j_tpu.streaming.broker import InMemoryBroker

    events: List[str] = []

    # 1) seeded NaN/raise schedules across resets
    rng = np.random.default_rng(seed)
    ds = DataSet(rng.standard_normal((8, 3)).astype(np.float32),
                 np.tile(np.eye(2, dtype=np.float32), (4, 1)))
    it = FailingDataSetIterator(ListDataSetIterator(ds, batch_size=2),
                                nan_at=(seed % 3,), raise_at=(5,),
                                p_nan=0.3, seed=seed)
    for epoch in range(2):
        it.reset()
        while it.has_next():
            try:
                batch = it.next()
            except InjectedFault as e:
                events.append(f"iter raise: {e}")
                continue
            nan = bool(np.isnan(np.asarray(batch.features)).any())
            events.append(f"iter batch nan={nan}")
    events.append(f"iter injected nan={it.injected_nan} "
                  f"raise={it.injected_raise}")

    # 2) flaky broker schedules + seeded random failures
    broker = FlakyBroker(InMemoryBroker(), fail_publishes=(1,),
                         fail_consumes=(0,), p_fail=0.25, seed=seed)
    for i in range(6):
        try:
            broker.publish("t", f"m{i}".encode())
            events.append(f"pub {i} ok")
        except ConnectionError as e:
            events.append(f"pub {i} fail: {e}")
    for i in range(8):
        try:
            msg = broker.consume("t", timeout=0)
            events.append(f"con {i} -> "
                          f"{msg.decode() if msg is not None else None}")
        except ConnectionError as e:
            events.append(f"con {i} fail: {e}")
    events.append(f"broker faults={broker.faults_injected}")

    # 3) torn-write crash scheduling (counted os.replace/rename installs)
    import tempfile
    with tempfile.TemporaryDirectory() as td:
        with TornWrites(crash_on_call=2, path_substr="unit") as torn:
            for i in range(3):
                tmp = os.path.join(td, f"t{i}")
                dst = os.path.join(td, f"unit{i}")
                with open(tmp, "w") as f:
                    f.write("x")
                try:
                    os.replace(tmp, dst)
                    events.append(f"install {i} ok")
                except InjectedFault:
                    # log the index, not the message — the tempdir path
                    # inside it is fresh per run by design
                    events.append(f"install {i} crash")
        events.append(f"torn calls={torn.calls}")

    # 4) replica/model poison hit sequences
    rp = ReplicaPoison(replica=1, failures=2)
    for i in range(4):
        for replica in (0, 1):
            try:
                rp(replica, (1, 3))
                events.append(f"rp {i}/{replica} ok")
            except InjectedFault:
                events.append(f"rp {i}/{replica} hit")
    mp = ModelPoison("m", failures=3)
    for i in range(5):
        for model in ("m", "other"):
            try:
                mp(i % 2, (1, 3), model)
                events.append(f"mp {i}/{model} ok")
            except InjectedFault:
                events.append(f"mp {i}/{model} hit")
    events.append(f"rp hits={rp.hits} mp hits={mp.hits}")

    # 5) kill-mid-burst schedules (continuous decode scheduler seam):
    # seeded window, lane-scoped filtering — the injector the
    # tests/test_continuous.py kill-mid-burst scenario arms; here its
    # hit schedule itself is pinned deterministic
    bk = BurstKill(after=seed % 3, failures=2)
    bk_lane = BurstKill(after=0, failures=2, lane=("m", 1))
    for i in range(6):
        for lane in ((None, None), ("m", 1)):
            for inj in (bk, bk_lane):
                try:
                    inj(lane, i)
                    events.append(f"bk {i}/{lane} ok")
                except InjectedFault:
                    events.append(f"bk {i}/{lane} hit")
    events.append(f"bk hits={bk.hits} lane_hits={bk_lane.hits}")

    # 6) mesh-shrink drill schedule (the MeshShrink/ChipFailure seam
    # tests/test_mesh_plane.py arms against a real training loop): the
    # failure STEP and the seeded SURVIVOR SET are pinned deterministic
    # here — so the full drill's kill → checkpoint fallback → resume-on-
    # smaller-mesh sequence replays identically across stress reruns
    ms = MeshShrink(fail_at_step=seed % 4 + 1, survivors=4, total=8,
                    seed=seed)
    for i in range(8):
        try:
            idx = ms.step()
            events.append(f"ms step {idx} ok")
        except ChipFailure as e:
            events.append(f"ms step {i} chipfail survivors="
                          f"{list(e.survivor_ids)}")
    events.append(f"ms survivors={list(ms.survivor_ids())} "
                  f"fired={ms.fired} seen={ms.steps_seen}")

    # 7) composed chaos schedule (faultinject/chaos.py ChaosSchedule —
    # the seeded event clock run_chaos_drill replays against a live
    # fleet): the schedule ITSELF is pinned deterministic here (same
    # seed ⇒ identical ticks/actions/targets/heals, and wedge
    # injector state transitions replay); the full live drill runs in
    # fresh subprocesses via `--chaos` (outcome-drift contract)
    from deeplearning4j_tpu.faultinject import ChaosSchedule
    for n_events, n_eps in ((4, 3), (seed % 5 + 2, 3)):
        cs = ChaosSchedule(seed, n_events=n_events, n_endpoints=n_eps)
        events.append(f"chaos[{n_events}x{n_eps}]={cs.signature()}")

    # 8) prefix-cache refcount/COW/eviction accounting (serving/
    # prefixcache.py over the refcounted paged pool): seeded
    # interleavings of admit (match + share + alloc, COW-releasing a
    # matched partial), retire (insert-then-free), kill (free without
    # insert — the burst-kill shape) and eviction pressure on a tiny
    # pool — the free-list order, refcounts and cached-node counts
    # must replay bit-identically, the drill must drain to fully-free
    # with ZERO leaked blocks, and a double free must raise (caught
    # here, logged as part of the pinned schedule)
    from deeplearning4j_tpu.nn.kvpool import PagedKVCachePool
    from deeplearning4j_tpu.serving.prefixcache import PrefixCache
    rng8 = np.random.default_rng(seed * 31 + 5)
    pool = PagedKVCachePool(17, 2, num_layers=1, num_heads=1, head_dim=2,
                            name=f"qc{seed}")
    cache = PrefixCache(pool)
    lane = ("m", 1)
    live: List[tuple] = []
    for i in range(28):
        op = int(rng8.integers(0, 4))
        if op == 0:
            t = int(rng8.integers(3, 9))
            toks = [int(x) for x in rng8.integers(0, 4, t)]
            m, full, part = cache.match(lane, toks)
            got = pool.alloc(pool.blocks_for(t) - len(full))
            if got is None:
                pool.free_blocks(full
                                 + ([part] if part is not None else []))
                events.append(f"pc {i} admit-short m={m}")
                continue
            if part is not None:
                # COW: the fresh block stands in, the shared ref drops
                blocks = full + got
                pool.free_blocks([part])
            else:
                blocks = full + got
            live.append((blocks, toks))
            events.append(f"pc {i} admit m={m} blocks={blocks}")
        elif op == 1 and live:
            blocks, toks = live.pop(int(rng8.integers(0, len(live))))
            pinned = cache.insert(lane, toks, blocks)
            pool.free_blocks(blocks)
            events.append(f"pc {i} retire pinned={pinned} "
                          f"free={pool.free_count}")
        elif op == 2 and live:
            blocks, _ = live.pop(int(rng8.integers(0, len(live))))
            pool.free_blocks(blocks)
            events.append(f"pc {i} kill free={pool.free_count}")
        else:
            freed = cache.reclaim(int(rng8.integers(1, 4)))
            events.append(f"pc {i} evict freed={freed} "
                          f"cached={cache.cached_blocks()}")
    for blocks, _ in live:
        pool.free_blocks(blocks)
    cache.clear()
    try:
        pool.free_blocks([1])
        events.append("pc double-free MISSED")
    except RuntimeError:
        events.append("pc double-free caught")
    events.append(f"pc final free={pool.free_count}/{pool.total_blocks} "
                  f"shared={pool.shared_count()} "
                  f"leaked={pool.total_blocks - pool.free_count}")

    # 9) slice-kill schedule determinism (faultinject.SliceKill — the
    # kill-a-chip-inside-a-live-slice injector LocalFleet.kill_chip
    # arms): the seeded victim chip, the survivor set and the failure
    # tick must replay bit-identically, and a dead chip NEVER heals —
    # every dispatch from the tick on fails (the reason recovery is an
    # elastic rebuild, not a retry). The slice-drill ChaosSchedule
    # (slice_kill/partition_hb/wedge action set) is pinned alongside,
    # the same way section 7 pins the main drill's clock.
    from deeplearning4j_tpu.faultinject import SliceKill
    from deeplearning4j_tpu.faultinject.chaos import SLICE_ACTIONS
    sk = SliceKill([0, 1, 2, 3], seed=seed, fail_at=seed % 3 + 1)
    for i in range(6):
        try:
            sk(("lane", None), i)
            events.append(f"sk {i} ok")
        except ChipFailure as e:
            events.append(f"sk {i} chipfail "
                          f"survivors={list(e.survivor_ids)}")
    events.append(f"sk victim={sk.victim} hits={sk.hits} "
                  f"devices={list(sk.devices)}")
    for n_events in (3, seed % 4 + 2):
        cs = ChaosSchedule(seed, n_events=n_events, n_endpoints=2,
                           actions=SLICE_ACTIONS)
        events.append(f"slice_chaos[{n_events}]={cs.signature()}")

    # 10) quantized-KV × prefix-cache interop (nn/quantize.py + the
    # kvpool quant variant): the section-8 admit/retire/kill/evict
    # battery replayed on a TINY INT8 pool — block ids, refcounts,
    # shared/COW accounting and the free list must replay
    # bit-identically (scale arrays ride the same block addressing, so
    # accounting is the whole sharing contract), the pool must drain
    # to fully-free with zero leaks, a double free must raise, and the
    # quantized layout facts are pinned: a quantized spec NEVER
    # matches the fp32 spec (a quantized lane cannot silently share an
    # fp32 pool) and its per-block bytes land in the 2-4x compression
    # band that buys the extra decode rows.
    qpool = PagedKVCachePool(17, 2, num_layers=1, num_heads=1, head_dim=8,
                             name=f"qq{seed}", quant="int8")
    fpool = PagedKVCachePool(3, 2, num_layers=1, num_heads=1, head_dim=8,
                             name=f"qf{seed}")
    events.append(f"qkv spec_differs={qpool.spec != fpool.spec} "
                  f"ratio={fpool.block_bytes() / qpool.block_bytes():.3f} "
                  f"scales={sorted(qpool.layers[0])}")
    qcache = PrefixCache(qpool)
    rngA = np.random.default_rng(seed * 131 + 7)
    qlive: List[tuple] = []
    for i in range(24):
        op = int(rngA.integers(0, 4))
        if op == 0:
            t = int(rngA.integers(3, 9))
            toks = [int(x) for x in rngA.integers(0, 4, t)]
            m, full, part = qcache.match(lane, toks)
            got = qpool.alloc(qpool.blocks_for(t) - len(full))
            if got is None:
                qpool.free_blocks(full
                                  + ([part] if part is not None else []))
                events.append(f"qkv {i} admit-short m={m}")
                continue
            if part is not None:
                # COW on a quantized pool: the fresh block stands in
                # (its scale rows clone with it on device), the shared
                # reference drops — accounting identical to fp32
                blocks = full + got
                qpool.free_blocks([part])
                events.append(f"qkv {i} cow m={m}")
            else:
                blocks = full + got
            qlive.append((blocks, toks))
            events.append(f"qkv {i} admit m={m} blocks={blocks}")
        elif op == 1 and qlive:
            blocks, toks = qlive.pop(int(rngA.integers(0, len(qlive))))
            pinned = qcache.insert(lane, toks, blocks)
            qpool.free_blocks(blocks)
            events.append(f"qkv {i} retire pinned={pinned} "
                          f"free={qpool.free_count}")
        elif op == 2 and qlive:
            blocks, _ = qlive.pop(int(rngA.integers(0, len(qlive))))
            qpool.free_blocks(blocks)
            events.append(f"qkv {i} kill free={qpool.free_count}")
        else:
            freed = qcache.reclaim(int(rngA.integers(1, 4)))
            events.append(f"qkv {i} evict freed={freed} "
                          f"cached={qcache.cached_blocks()}")
    for blocks, _ in qlive:
        qpool.free_blocks(blocks)
    qcache.clear()
    try:
        qpool.free_blocks([1])
        events.append("qkv double-free MISSED")
    except RuntimeError:
        events.append("qkv double-free caught")
    events.append(f"qkv final free={qpool.free_count}/{qpool.total_blocks} "
                  f"shared={qpool.shared_count()} "
                  f"leaked={qpool.total_blocks - qpool.free_count}")

    # 11) speculative-decoding dual-lane accounting (the PR-17
    # scheduler's contract): every stream holds blocks on TWO pools —
    # the target lane and the draft lane — and every lifecycle edge
    # (admit, spec-round growth, preempt, rollback, burst-kill, retire)
    # must free or carry BOTH sides in lockstep. A draft-lane leak is
    # invisible to the target pool's audit, which is why the draft pool
    # is dedicated; this drill replays a seeded battery of those edges
    # and pins that both pools drain to fully-free, that a draft-side
    # double free raises, and that an admit whose draft alloc falls
    # short degrades to a DRAFT-LESS row (spec fallback) instead of
    # failing the admission — speculation is an accelerator, never a
    # correctness dependency.
    tpool = PagedKVCachePool(13, 4, num_layers=1, num_heads=1, head_dim=8,
                             name=f"spec_t{seed}")
    dpool = PagedKVCachePool(9, 4, num_layers=1, num_heads=1, head_dim=8,
                             name=f"spec_d{seed}", quant="int8")
    rngS = np.random.default_rng(seed * 157 + 11)
    k_spec = int(rngS.integers(2, 5))
    # live rows: (target_blocks, draft_blocks or [], pos)
    slive: List[list] = []
    for i in range(28):
        op = int(rngS.integers(0, 5))
        if op == 0:
            t = int(rngS.integers(2, 10))
            tb = tpool.alloc(tpool.blocks_for(t))
            if tb is None:
                events.append(f"spec {i} admit-short")
                continue
            db = dpool.alloc(dpool.blocks_for(t))
            if db is None:
                # draft-less admission: the row serves on plain bursts
                events.append(f"spec {i} admit draftless pos={t}")
                slive.append([tb, [], t])
            else:
                events.append(f"spec {i} admit tb={tb} db={db}")
                slive.append([tb, db, t])
        elif op == 1 and slive:
            # spec round: grow BOTH lanes to pos + k_spec + 1, accept a
            # seeded prefix, roll pos forward (rollback of rejected
            # positions is pure pos bookkeeping — stale KV is
            # overwritten by the next round's writes, never freed)
            row = slive[int(rngS.integers(0, len(slive)))]
            tb, db, pos = row
            if not db:
                events.append(f"spec {i} round skipped (draftless)")
                continue
            horizon = pos + k_spec + 1
            ok = True
            for pool_, blocks in ((tpool, tb), (dpool, db)):
                delta = pool_.blocks_for(horizon) - len(blocks)
                if delta > 0:
                    got = pool_.alloc(delta)
                    if got is None:
                        ok = False
                        break
                    blocks.extend(got)
            if not ok:
                events.append(f"spec {i} grow-short pos={pos}")
                continue
            a = int(rngS.integers(0, k_spec + 1))
            row[2] = pos + a + 1
            events.append(f"spec {i} round a={a} pos={row[2]} "
                          f"tb={len(tb)} db={len(db)}")
        elif op == 2 and slive:
            # preempt: target KV may ship or drop; the draft lane NEVER
            # ships (it re-prefills on resume) — both freed here
            tb, db, pos = slive.pop(int(rngS.integers(0, len(slive))))
            tpool.free_blocks(tb)
            if db:
                dpool.free_blocks(db)
            events.append(f"spec {i} preempt tfree={tpool.free_count} "
                          f"dfree={dpool.free_count}")
        elif op == 3 and slive:
            # burst-kill: every row's BOTH lanes freed
            for tb, db, _ in slive:
                tpool.free_blocks(tb)
                if db:
                    dpool.free_blocks(db)
            slive.clear()
            events.append(f"spec {i} burstkill tfree={tpool.free_count} "
                          f"dfree={dpool.free_count}")
        elif slive:
            tb, db, pos = slive.pop(int(rngS.integers(0, len(slive))))
            tpool.free_blocks(tb)
            if db:
                dpool.free_blocks(db)
            events.append(f"spec {i} retire pos={pos}")
    for tb, db, _ in slive:
        tpool.free_blocks(tb)
        if db:
            dpool.free_blocks(db)
    try:
        dpool.free_blocks([1])
        events.append("spec draft double-free MISSED")
    except RuntimeError:
        events.append("spec draft double-free caught")
    events.append(f"spec final t={tpool.free_count}/{tpool.total_blocks} "
                  f"d={dpool.free_count}/{dpool.total_blocks} "
                  f"tleak={tpool.total_blocks - tpool.free_count} "
                  f"dleak={dpool.total_blocks - dpool.free_count}")

    # 12) wire-v4 torn-frame drill (the PR-18 data plane's contract):
    # the zero-copy binary framing must fail TYPED on ANY truncation —
    # a half-written frame (torn write, worker killed mid-publish, cut
    # connection) surfaces as WireFrameError, never a garbled tensor —
    # while a fragmented-but-complete delivery reassembles byte-exact,
    # including the shipped-KV disagg segments, and a coalesced
    # token-chunk frame decodes back to every stream's exact delta.
    from deeplearning4j_tpu.serving import wire
    rngW = np.random.default_rng(seed * 211 + 5)
    kv = rngW.standard_normal((2, 2, 4, 8)).astype(np.float32)
    ids = rngW.integers(0, 997,
                        (1, int(rngW.integers(3, 9)))).astype(np.int32)
    frame = wire.pack_request_v4(f"w{seed}", "rsp", wire.KIND_GENERATE,
                                 ids, gen={"kv": True}, tensors={"kv": kv})
    events.append(f"wire frame len={len(frame)}")
    for c in sorted(int(c) for c in rngW.integers(0, len(frame), 6)):
        try:
            wire.unpack_frame_v4(frame[:c])
            events.append(f"wire cut {c} MISSED")
        except wire.WireFrameError:
            events.append(f"wire cut {c} typed")
    try:
        wire.unpack_frame_v4(b"\x00\x00" + frame[2:])
        events.append("wire bad-magic MISSED")
    except wire.WireFrameError:
        events.append("wire bad-magic caught")
    parts, off = [], 0
    while off < len(frame):
        n = int(rngW.integers(1, max(2, len(frame) // 3)))
        parts.append(frame[off:off + n])
        off += n
    meta, x, segs = wire.unpack_request_any(b"".join(parts))
    events.append(f"wire reassembled frags={len(parts)} "
                  f"ids={bool(np.array_equal(x, ids))} "
                  f"kv_byte_exact={segs['kv'].tobytes() == kv.tobytes()} "
                  f"v={meta['v']}")
    entries = [(f"s{j}", int(rngW.integers(0, 50)),
                rngW.integers(0, 11,
                              int(rngW.integers(1, 5))).astype(np.int64))
               for j in range(3)]
    evs = wire.decode_reply_events(wire.pack_chunks_v4(entries))
    exact = all(ev["id"] == c and ev["off"] == o and
                list(ev["tokens"]) == [int(t) for t in toks]
                for ev, (c, o, toks) in zip(evs, entries))
    events.append(f"wire coalesced n={len(evs)} exact={exact}")

    # 13) host-tier (KV tiering) accounting drill: a seeded battery of
    # swap_out / swap_in / host_export→host_insert (the shipped-blocks
    # round trip) / free_host edges on a tiny tiered pool, with a
    # deterministic HostTierPressure window mid-drill (budget squeezed
    # to 0 ⇒ every demotion and landing-dock insert REFUSES and the
    # caller takes its pre-tier fallback — the exactness ladder's
    # degrade path), plus the reclaimer CHAIN consulted in
    # registration order (demote-to-host before drop — the order the
    # prefix cache registers). Both tiers must drain to empty, a
    # host-side double free must raise, and the whole log replays
    # bit-for-bit.
    import zlib

    from deeplearning4j_tpu.faultinject import HostTierPressure
    hpool = PagedKVCachePool(11, 2, num_layers=1, num_heads=1, head_dim=2,
                             name=f"ht{seed}", host_blocks=5)
    rngH = np.random.default_rng(seed * 31 + 13)
    hlive: List[list] = []      # device rows
    hparked: List[list] = []    # host handle batches
    squeeze = HostTierPressure(hpool, budget=0)
    for i in range(30):
        if i == 14:
            squeeze.squeeze()
            events.append(f"ht {i} squeeze budget={hpool.host_budget()}")
        if i == 20:
            squeeze.heal()
            events.append(f"ht {i} heal budget={hpool.host_budget()}")
        op = int(rngH.integers(0, 5))
        if op == 0:
            got = hpool.alloc(int(rngH.integers(1, 4)))
            if got is None:
                events.append(f"ht {i} admit-short")
            else:
                hlive.append(got)
                events.append(f"ht {i} admit blocks={got}")
        elif op == 1 and hlive:
            blocks = hlive.pop(int(rngH.integers(0, len(hlive))))
            hs = hpool.swap_out(blocks, owner="lm@v1")
            if hs is None:
                hlive.append(blocks)  # refusal: caller keeps device refs
                events.append(f"ht {i} swapout-refused "
                              f"used={hpool.host_blocks_used()}")
            else:
                hparked.append(hs)
                events.append(f"ht {i} swapout handles={hs} "
                              f"free={hpool.free_count}")
        elif op == 2 and hparked:
            hs = hparked.pop(int(rngH.integers(0, len(hparked))))
            got = hpool.swap_in(hs, owner="lm@v1")
            if got is None:
                hparked.append(hs)  # handles stay valid on refusal
                events.append(f"ht {i} swapin-short")
            else:
                hlive.append(got)
                events.append(f"ht {i} swapin blocks={got} "
                              f"used={hpool.host_blocks_used()}")
        elif op == 3 and hparked:
            hs = hparked[int(rngH.integers(0, len(hparked)))]
            shipped = hpool.host_export(hs)
            crc = zlib.crc32(b"".join(
                v.tobytes() for b in shipped
                for _, v in sorted(b.items())))
            ins = hpool.host_insert(shipped, owner="ship")
            if ins is None:
                events.append(f"ht {i} insert-refused crc={crc}")
            else:
                back = zlib.crc32(b"".join(
                    v.tobytes() for b in hpool.host_export(ins)
                    for _, v in sorted(b.items())))
                hparked.append(ins)
                events.append(f"ht {i} shipped crc={crc} "
                              f"byte_exact={crc == back} "
                              f"used={hpool.host_blocks_used()}")
        elif hparked:
            hs = hparked.pop(int(rngH.integers(0, len(hparked))))
            hpool.free_host(hs, owner="lm@v1")
            events.append(f"ht {i} freehost "
                          f"used={hpool.host_blocks_used()}")
    squeeze.heal()
    for blocks in hlive:
        hpool.free_blocks(blocks)
    doomed = list(hparked)
    for hs in doomed:
        hpool.free_host(hs)
    try:
        if doomed and doomed[0]:
            hpool.free_host(doomed[0])
            events.append("ht double-free MISSED")
        else:
            raise RuntimeError("no parked handles to double-free")
    except RuntimeError:
        events.append("ht double-free caught")
    events.append(f"ht final free={hpool.free_count}/{hpool.total_blocks} "
                  f"host_used={hpool.host_blocks_used()}")

    # reclaimer-chain order: exhaustion consults the seams in
    # registration order (demote first, drop second) and stops as soon
    # as the free list covers the request
    cpool = PagedKVCachePool(7, 2, num_layers=1, num_heads=1, head_dim=2,
                             name=f"hc{seed}")
    held = cpool.alloc(cpool.free_count)
    chain: List[str] = []

    def demote(n_short):
        chain.append(f"demote({n_short})")
        if held:
            cpool.free_blocks([held.pop()])
            return 1
        return 0

    def drop(n_short):
        chain.append(f"drop({n_short})")
        freed = len(held)
        if held:
            cpool.free_blocks(held)
            held.clear()
        return freed

    cpool.register_reclaimer(demote)
    cpool.register_reclaimer(drop)
    got1 = cpool.alloc(1)
    got3 = cpool.alloc(3)
    events.append(f"ht chain={chain} got1={got1} got3={got3}")
    cpool.free_blocks((got1 or []) + (got3 or []))
    events.append(f"ht chain final free={cpool.free_count}"
                  f"/{cpool.total_blocks}")
    return "\n".join(events)


def analysis_section() -> List[str]:
    """SECTION 0 — static analysis, fail fast: run the unified engine
    (``deeplearning4j_tpu/analysis``, same report ``scripts/analyze.py
    --json`` emits) repo-wide and surface every NEW finding
    (suppressions and the committed baseline already applied). A
    finding here aborts quick_check before any chaos phase runs."""
    from deeplearning4j_tpu.analysis import analyze
    report = analyze(_ROOT)
    return [f"analysis: {f.render()}" for f in report.new]


def quick_check(seeds=(0, 1, 2), runs_per_seed: int = 2) -> List[str]:
    """Section 0 (static analysis, fail fast), then replay the injector
    battery ``runs_per_seed`` times per seed; returns violations ([] =
    clean + deterministic). Tier-1 runs this."""
    problems: List[str] = list(analysis_section())
    if problems:
        return problems  # fail fast: no chaos phase on a dirty tree
    for seed in seeds:
        logs = [_scenario_log(int(seed)) for _ in range(runs_per_seed)]
        for i, log in enumerate(logs[1:], 2):
            if log != logs[0]:
                a, b = logs[0].splitlines(), log.splitlines()
                diff = next((j for j, (x, y) in enumerate(zip(a, b))
                             if x != y), min(len(a), len(b)))
                problems.append(
                    f"seed {seed}: run {i} diverged from run 1 at event "
                    f"{diff}: {a[diff] if diff < len(a) else '<end>'!r} vs "
                    f"{b[diff] if diff < len(b) else '<end>'!r}")
    return problems


# ----------------------------------------------------------- chaos mode


def _run_chaos_subprocess(seed: int, n_requests: int,
                          n_events: int) -> Dict[str, object]:
    """One composed chaos drill in a FRESH interpreter (the only
    honest replay on this box — see the full-mode rationale); returns
    the drill's invariant summary, or a synthetic failure record when
    the subprocess died."""
    import json
    code = (
        "import json\n"
        "from deeplearning4j_tpu.faultinject.chaos import run_chaos_drill\n"
        f"out = run_chaos_drill(seed={int(seed)}, "
        f"n_requests={int(n_requests)}, n_events={int(n_events)})\n"
        "print('CHAOS_JSON ' + json.dumps(out, sort_keys=True))\n")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONHASHSEED"] = str(seed)
    proc = subprocess.run([sys.executable, "-c", code], cwd=_ROOT,
                          capture_output=True, text=True, env=env,
                          timeout=900)
    for line in proc.stdout.splitlines():
        if line.startswith("CHAOS_JSON "):
            return json.loads(line[len("CHAOS_JSON "):])
    return {"error": f"rc={proc.returncode}",
            "stderr": proc.stderr[-2000:]}


def _run_hibernation_subprocess(seed: int,
                                n_sessions: int) -> Dict[str, object]:
    """One hibernation drill in a fresh interpreter; returns its
    invariant summary or a synthetic failure record."""
    import json
    code = (
        "import json\n"
        "from deeplearning4j_tpu.faultinject.chaos import "
        "run_hibernation_drill\n"
        f"out = run_hibernation_drill(seed={int(seed)}, "
        f"n_sessions={int(n_sessions)})\n"
        "print('HIB_JSON ' + json.dumps(out, sort_keys=True))\n")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env["PYTHONHASHSEED"] = str(seed)
    proc = subprocess.run([sys.executable, "-c", code], cwd=_ROOT,
                          capture_output=True, text=True, env=env,
                          timeout=900)
    for line in proc.stdout.splitlines():
        if line.startswith("HIB_JSON "):
            return json.loads(line[len("HIB_JSON "):])
    return {"error": f"rc={proc.returncode}",
            "stderr": proc.stderr[-2000:]}


def run_hibernation(runs: int, seed_base: int,
                    n_sessions: int = 4) -> int:
    """The `hibernation` section: the session-hibernation drill twice
    per seed in fresh subprocesses; fail on any invariant violation or
    outcome drift between the two replays of one seed."""
    bad = 0
    for i in range(runs):
        seed = seed_base + i
        print(f"hibernation seed {seed} ({i + 1}/{runs}) ...", flush=True)
        a = _run_hibernation_subprocess(seed, n_sessions)
        b = _run_hibernation_subprocess(seed, n_sessions)
        for run_id, out in (("run1", a), ("run2", b)):
            if "error" in out:
                print(f"  {run_id} DIED: {out}", file=sys.stderr)
                bad += 1
                continue
            violations = [
                k for k, want in (
                    ("token_mismatches", 0), ("dup_offsets", 0),
                    ("gap_events", 0), ("leaked_blocks", 0),
                    ("leaked_host_blocks", 0), ("stranded_handles", 0))
                if out.get(k) != want]
            if out.get("resumed") != out.get("sessions"):
                violations.append("resumed")
            if out.get("handles_shipped") != out.get("sessions"):
                violations.append("handles_shipped")
            if violations:
                print(f"  {run_id} INVARIANT VIOLATIONS {violations}: "
                      f"{out}", file=sys.stderr)
                bad += 1
        if "error" not in a and "error" not in b and a != b:
            drift = sorted(k for k in set(a) | set(b)
                           if a.get(k) != b.get(k))
            print(f"  OUTCOME DRIFT between replays of seed {seed}: "
                  f"{drift}", file=sys.stderr)
            bad += 1
        elif "error" not in a:
            print(f"  ok: {a['sessions']} sessions hibernated + "
                  f"resumed across the death of {a['victim']}",
                  flush=True)
    if not bad:
        print(f"ok: hibernation drill deterministic + invariant-clean "
              f"over {runs} seeds x 2 fresh-process replays")
    return 1 if bad else 0


def run_chaos(runs: int, seed_base: int, n_requests: int = 14,
              n_events: int = 4) -> int:
    """The `chaos` section: run the composed drill TWICE per seed in
    fresh subprocesses across rotating seeds; fail on any invariant
    violation OR any outcome drift between the two replays of one
    seed — the same determinism contract sections 1–11 pin for the
    injectors, applied to the whole composed drill."""
    bad = 0
    for i in range(runs):
        seed = seed_base + i
        print(f"chaos seed {seed} ({i + 1}/{runs}) ...", flush=True)
        a = _run_chaos_subprocess(seed, n_requests, n_events)
        b = _run_chaos_subprocess(seed, n_requests, n_events)
        for run_id, out in (("run1", a), ("run2", b)):
            if "error" in out:
                print(f"  {run_id} DIED: {out}", file=sys.stderr)
                bad += 1
                continue
            violations = [
                k for k, want in (
                    ("failed", 0), ("stranded_futures", 0),
                    ("token_mismatches", 0), ("dup_offsets", 0),
                    ("gap_events", 0), ("leaked_blocks", 0))
                if out.get(k) != want]
            if out.get("healthy_endpoints") != 3:
                violations.append("healthy_endpoints")
            if out.get("completed") != out.get("submitted"):
                violations.append("completed")
            if violations:
                print(f"  {run_id} INVARIANT VIOLATIONS {violations}: "
                      f"{out}", file=sys.stderr)
                bad += 1
        if "error" not in a and "error" not in b and a != b:
            drift = sorted(k for k in set(a) | set(b)
                           if a.get(k) != b.get(k))
            print(f"  OUTCOME DRIFT between replays of seed {seed}: "
                  f"{drift}", file=sys.stderr)
            bad += 1
        elif "error" not in a:
            print(f"  ok: {a['submitted']} requests, "
                  f"schedule {a['schedule']}", flush=True)
    if not bad:
        print(f"ok: composed chaos drill deterministic + invariant-clean "
              f"over {runs} seeds x 2 fresh-process replays")
    return 1 if bad else 0


# ------------------------------------------------------------ full mode

_RESULT_RE = re.compile(r"^(PASSED|FAILED|ERROR|XFAIL|XPASS|SKIPPED) "
                        r"(\S+)", re.MULTILINE)


def _run_suite(seed: int, marker: str, extra: List[str]) -> Dict[str, str]:
    """One fresh-process pytest run; returns {test_id: outcome}."""
    env = dict(os.environ)
    env["PYTHONHASHSEED"] = str(seed)
    env["DL4J_TPU_STRESS_SEED"] = str(seed)
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, "-m", "pytest", "tests/", "-m", marker, "-q",
           "-rA", "--tb=no", "-p", "no:cacheprovider", "-p", "no:randomly",
           "--continue-on-collection-errors", *extra]
    proc = subprocess.run(cmd, capture_output=True, text=True, env=env)
    outcomes: Dict[str, str] = {}
    for m in _RESULT_RE.finditer(proc.stdout):
        outcomes[m.group(2)] = m.group(1)
    if not outcomes:
        outcomes["<collection>"] = f"rc={proc.returncode}"
    return outcomes


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--runs", type=int, default=3,
                    help="fresh-process pytest runs (default 3)")
    ap.add_argument("--seed-base", type=int, default=0)
    ap.add_argument("-m", "--marker", default="faultinject",
                    help="pytest marker expression (default: faultinject)")
    ap.add_argument("--quick", action="store_true",
                    help="run only the in-process injector battery "
                         "(what tier-1 wires in)")
    ap.add_argument("--chaos", action="store_true",
                    help="run the COMPOSED chaos drill in fresh "
                         "subprocesses (2 replays per rotating seed), "
                         "failing on invariant violations or outcome "
                         "drift")
    ap.add_argument("--chaos-requests", type=int, default=14)
    ap.add_argument("--chaos-events", type=int, default=4)
    ap.add_argument("--hibernation", action="store_true",
                    help="run the session-hibernation drill in fresh "
                         "subprocesses (2 replays per rotating seed), "
                         "failing on invariant violations or outcome "
                         "drift")
    ap.add_argument("--hibernation-sessions", type=int, default=4)
    ap.add_argument("--pytest-args", nargs=argparse.REMAINDER, default=[],
                    help="extra args forwarded to pytest")
    args = ap.parse_args(argv)

    if args.chaos:
        return run_chaos(args.runs, args.seed_base,
                         n_requests=args.chaos_requests,
                         n_events=args.chaos_events)

    if args.hibernation:
        return run_hibernation(args.runs, args.seed_base,
                               n_sessions=args.hibernation_sessions)

    if args.quick:
        problems = quick_check(
            seeds=range(args.seed_base, args.seed_base + args.runs))
        for p in problems:
            print(p, file=sys.stderr)
        if not problems:
            print(f"ok: injector battery deterministic over {args.runs} "
                  "seeds x 2 runs")
        return 1 if problems else 0

    runs: List[Dict[str, str]] = []
    for i in range(args.runs):
        seed = args.seed_base + i
        print(f"run {i + 1}/{args.runs} (seed {seed}) ...", flush=True)
        outcomes = _run_suite(seed, args.marker, args.pytest_args)
        n_fail = sum(1 for o in outcomes.values()
                     if o in ("FAILED", "ERROR"))
        print(f"  {len(outcomes)} tests, {n_fail} failed", flush=True)
        runs.append(outcomes)

    flaky: List[str] = []
    all_tests = sorted(set().union(*runs))
    for test in all_tests:
        seen = {r.get(test, "<missing>") for r in runs}
        if len(seen) > 1:
            flaky.append(f"NONDETERMINISTIC {test}: "
                         + " / ".join(sorted(seen)))
    deterministic_failures = sorted(
        t for t in all_tests
        if all(r.get(t) in ("FAILED", "ERROR") for r in runs))
    for f in flaky:
        print(f, file=sys.stderr)
    for t in deterministic_failures:
        print(f"DETERMINISTIC FAILURE {t}", file=sys.stderr)
    if not flaky and not deterministic_failures:
        print(f"ok: {len(all_tests)} tests deterministic over "
              f"{args.runs} runs")
        return 0
    return 1


if __name__ == "__main__":
    sys.exit(main())
