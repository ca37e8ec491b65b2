"""Tiered segment storage: corpora bigger than HBM, plus snapshot/restore.

The whole serving stack so far assumes the ``SegmentedStore`` is device-
resident — which caps the corpus at HBM, exactly the hardware barrier the
toolkit exists to remove (paper §1). This module lifts that cap:

- **residency tiers** — hot segments stay device-resident, cold segments
  spill to host RAM as numpy arrays of the SAME keys/shapes/dtypes
  (``Segment.tier``). Residency is PLACEMENT, never shape:
  ``SegmentedStore.layout_key()`` is tier-blind and the per-segment
  executables take the segment's global slot offset as traced data, so
  tier churn adds zero retrace axes.
- **traffic-keyed promotion/demotion** — an LRU over segment touches
  (the frontend's result-cache idiom, at segment granularity) under a
  byte ``hbm_budget``; demotion is a ``jax.device_get`` and promotion a
  ``jax.device_put`` of bit-identical buffers, so an evict/promote round
  trip is bitwise and tiered search results equal the fully-resident
  search. Every swap goes through ``SegmentedStore.tier_swap``, which
  bumps the store generation — result caches keyed on it (the
  frontend's) conservatively drop entries instead of reasoning about
  residency.
- **async prefetch** — a background worker thread owns every
  host<->device transfer. ``prefetch(scope)`` enqueues the segments a
  scheduler predicts next (the next query in an admission queue, or
  segment i+1 of the current scope); the copy then lands UNDER the
  current segment's MaxSim compute, because JAX dispatch is async and
  the worker's ``device_put`` runs off the critical path. The
  double-buffering at CHUNK granularity — HBM->VMEM inside the scan
  kernel — is the same idea one level down (the Pallas grid pipeline
  of ``kernels.maxsim.maxsim.maxsim_pallas``).
- **snapshot/restore** — ``snapshot``/``restore_store`` persist the full
  ``SegmentedStore`` (arrays + schema + slot maps + tenant/filter/IVF
  companions + router policy) through ``training/checkpoint.py``'s
  atomic streamed writer, so ``serve.py --snapshot-dir`` cold-starts to
  serving without re-ingesting. ``store.snapshot_entries`` fixes the
  array enumeration; the checkpoint meta records everything host-side.

The per-segment search pipeline (``TieredEngine.search``, single-host)
runs the SAME per-segment code the joint cascade runs
(``engine._segment_stage0`` / ``_segment_rerank`` via
``engine.make_segment_scan_fn`` / ``make_segment_rerank_fn``) and merges
segment results with the same ``merge_topk`` / elementwise-max combine,
so tiered results are bitwise the fully-resident search after the
retriever-level NEG-filler id masking. On a mesh the scope runs as one
joint sharded executable over the (promoted) scope segments instead —
per-segment host pipelining is a single-host optimisation.

This module is the ONE place in ``repro.retrieval`` that is legitimately
host-synchronous on the serving path (thread waits, ``device_get``,
blocking transfers): the contract auditor scopes its R3 exemption to
exactly this module (``analysis.rules.R3_HOST_EXEMPT_MODULES``); the
jitted combine bodies below still satisfy R1 (``record_trace``) and the
traced-scope rules like every other serving jit.
"""
from __future__ import annotations

import functools
import queue
import threading
import time
from collections import OrderedDict
from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.retrieval import engine
from repro.retrieval import faults as FLT
from repro.retrieval import routing as RT
from repro.retrieval.segments import Segment, SegmentedStore
from repro.retrieval.store import (ROUTING_KEYS, as_filter_arrays,
                                   filter_words, snapshot_entries)
from repro.retrieval.topk import merge_topk
from repro.retrieval.tracing import record_trace
from repro.training import checkpoint as CKPT

SNAPSHOT_KIND = "segmented_store"


class TierError(RuntimeError):
    """A tier transfer failed PERMANENTLY (bounded retries exhausted, or
    no recovery path). Waiters get this typed error, never a hang and
    never a raw exception from another thread's context."""


@dataclass(frozen=True)
class DegradePolicy:
    """How a deadline-budgeted search degrades instead of missing.

    skip_cold
        Serve from resident segments only once the remaining budget
        cannot cover the next cold segment's promotion: the segment is
        skipped (counted in ``TieredResult.skipped_segments``) and the
        result is flagged ``degraded=True``. With False, the deadline is
        advisory (nothing is skipped; results stay exact).
    min_segments
        Always scan at least this many scope segments — even past the
        deadline a request gets a real (if partial) answer, never an
        empty one.
    stages_degraded
        Optional cheaper cascade (smaller candidate-k / n_probe) used
        when the deadline is ALREADY blown on arrival; results from it
        are flagged degraded even when no segment is skipped. None keeps
        the request's own stages.
    """
    skip_cold: bool = True
    min_segments: int = 1
    stages_degraded: tuple | None = None


@dataclass
class TieredResult:
    """A tiered search answer plus its degradation provenance.

    Iterates as the classic ``(scores, ids)`` pair, so every
    pre-degradation call site keeps working unchanged. The
    exact-or-flagged invariant: ``degraded=False`` means bitwise
    equality with the fully-resident oracle over the same scope;
    ``degraded=True`` means ``skipped_segments`` scope segments (or a
    cheaper cascade) were dropped to meet the deadline — partial, but
    every returned id/score is still the exact score of a scanned
    segment, never junk."""
    scores: np.ndarray
    ids: np.ndarray
    degraded: bool = False
    skipped_segments: int = 0

    def __iter__(self):
        yield self.scores
        yield self.ids


# ---------------------------------------------------------------------------
# jitted combine steps (shared shapes -> one trace each; scope SIZE is the
# only shape axis, so a fixed scope family warms once and stays dispatch)
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("k",))
def _merge_pair(av, ai, bv, bi, k: int):
    """Fold one segment's (vals, ids) into the running stage-0 top-k —
    the sequential twin of the joint body's concat-then-merge (same
    multiset in, same top-k out)."""
    record_trace()
    return merge_topk(jnp.concatenate([av, bv], axis=1),
                      jnp.concatenate([ai, bi], axis=1), k)


@jax.jit
def _max_scores(a, b):
    """Combine per-segment rerank scores: each candidate is real in
    exactly one segment (NEG everywhere else), so elementwise max is the
    exact owner's score — and float max is exactly associative, so the
    sequential fold is bitwise the joint body's."""
    record_trace()
    return jnp.maximum(a, b)


@functools.partial(jax.jit, static_argnames=("k",))
def _select_stage(s_all, cand, k: int):
    """Finish one rerank stage: top-k over the combined scores, candidates
    gathered along — the joint body's exact closing ops."""
    record_trace()
    v, sel = jax.lax.top_k(s_all, k)
    return v, jnp.take_along_axis(cand, sel, axis=1)


# ---------------------------------------------------------------------------
# snapshot / restore
# ---------------------------------------------------------------------------

def snapshot(store: SegmentedStore, directory: str, *,
             step: int | None = None, keep: int = 3,
             faults=None) -> str:
    """Persist a full ``SegmentedStore`` under ``directory``.

    The arrays flow through ``training.checkpoint.save`` — atomic
    tmp+rename, keep-last-k, ONE leaf host-side at a time (so an
    8x-over-HBM corpus snapshots without 2x the corpus in host RAM),
    extended dtypes (bfloat16) stored as bit patterns. Everything else —
    per-segment key order (``store.snapshot_entries``), capacities, slot
    maps, tiers, IVF ``RouteState``, the router policy, store scalars —
    rides the checkpoint meta, so ``restore_store`` rebuilds the exact
    live object. Host-tier segments persist as-is (their arrays are
    already host numpy). ``step`` defaults to the store generation, so
    repeated snapshots of a mutating corpus keep distinct directories
    under the keep-last-k GC. ``faults`` (a ``faults.FaultInjector``)
    arms the checkpoint writer's crash/corruption emulation; per-leaf
    CRC32 checksums and ``seg<i>/<key>`` leaf names ride the meta so a
    damaged snapshot fails restore loudly, naming the bad array."""
    tree, seg_meta, leaf_names = [], [], []
    for si, seg in enumerate(store.segments):
        entries = snapshot_entries(seg.vectors)
        tree.append([v for _, v in entries])
        leaf_names.extend(f"seg{si}/{k}" for k, _ in entries)
        seg_meta.append({
            "keys": [k for k, _ in entries],
            "capacity": seg.capacity,
            "n_docs": seg.n_docs,
            "doc_ids": np.asarray(seg.doc_ids).tolist(),
            "tier": seg.tier,
            "routing": None if seg.routing is None else {
                "fills": np.asarray(seg.routing.fills).tolist(),
                "drift": int(seg.routing.drift)},
        })
    meta = {
        "kind": SNAPSHOT_KIND,
        "store_dtype": store.store_dtype,
        "n_shards": store.n_shards,
        "next_id": store.next_id,
        "filter_words": store.filter_words,
        "generation": store.generation,
        "router": None if store.router is None else {
            "n_clusters": store.router.n_clusters,
            "cluster_capacity": store.router.cluster_capacity,
            "iters": store.router.iters,
            "drift_threshold": store.router.drift_threshold},
        "segments": seg_meta,
    }
    step = store.generation if step is None else step
    return CKPT.save(directory, step, tree, meta=meta, keep=keep,
                     leaf_names=leaf_names,
                     faults=FLT.as_injector(faults))


def restore_store(directory: str, *, mesh=None, step: int | None = None,
                  place: bool = True) -> SegmentedStore:
    """Rebuild a ``SegmentedStore`` from a ``snapshot`` directory —
    bitwise: arrays come back through the checkpoint's bit-pattern round
    trip, slot maps / tenants / filters / IVF companions and their host
    ``RouteState`` from the meta. Every segment restores device-resident
    ("device" tier); wrap the result in a ``TieredEngine`` to re-impose
    an HBM budget. With ``mesh`` (and ``place``), leaves are restored
    straight onto the mesh's doc-sharded layout (routing companions
    replicated) — restore doubles as elastic restart onto a different
    topology."""
    ckpt_meta = CKPT.load_meta(directory, step)
    m = ckpt_meta["meta"]
    if m.get("kind") != SNAPSHOT_KIND:
        raise ValueError(
            f"{directory} is not a store snapshot (kind={m.get('kind')!r})")
    example, shardings, flat_i = [], [], 0
    axes = tuple(mesh.axis_names) if mesh is not None else ()
    for sm in m["segments"]:
        ex_seg, sh_seg = [], []
        for k in sm["keys"]:
            shape = tuple(ckpt_meta["shapes"][flat_i])
            dt = CKPT.named_dtype(ckpt_meta["dtypes"][flat_i])
            ex_seg.append(jax.ShapeDtypeStruct(shape, dt))
            if mesh is not None and place:
                sh_seg.append(NamedSharding(
                    mesh, P() if k in ROUTING_KEYS else P(axes)))
            flat_i += 1
        example.append(ex_seg)
        shardings.append(sh_seg)
    tree, _ = CKPT.restore(
        directory, example, step=step,
        shardings=shardings if (mesh is not None and place) else None)
    out = SegmentedStore([], m["store_dtype"], n_shards=m["n_shards"],
                         next_id=m["next_id"], mesh=mesh,
                         filter_words=m["filter_words"])
    if m["router"] is not None:
        out.router = RT.RoutingPolicy(**m["router"])
    for sm, leaves in zip(m["segments"], tree):
        seg = Segment(dict(zip(sm["keys"], leaves)), sm["capacity"],
                      sm["n_docs"],
                      np.asarray(sm["doc_ids"], np.int64))
        if sm["routing"] is not None:
            seg.routing = RT.RouteState(
                fills=np.asarray(sm["routing"]["fills"], np.int64),
                drift=int(sm["routing"]["drift"]))
        out.segments.append(seg)
    out.generation = m["generation"]
    return out


# ---------------------------------------------------------------------------
# the tiered engine
# ---------------------------------------------------------------------------

class _PendingOp:
    """One in-flight async promotion: completion event + the worker's
    PER-OP error (a shared error slot would let concurrent failures
    overwrite each other and surface on the wrong waiter)."""
    __slots__ = ("event", "error")

    def __init__(self):
        self.event = threading.Event()
        self.error: Exception | None = None


class TieredEngine:
    """Budgeted residency + per-segment pipelined search over a Retriever.

    ``hbm_budget`` caps the BYTES of device-resident segment arrays; the
    rest of the corpus lives in host RAM. Searches take an optional
    ``scope`` (segment indices — the natural unit of traffic locality:
    a collection, a tenant's segments); touched segments promote, LRU
    segments demote. ``prefetch`` is the async half: hand it the scopes
    a scheduler expects next and the worker thread's host->device copies
    land under the current query's compute.

    The budget is a soft cap at the margin: a promotion that cannot make
    room (every other resident segment is pinned by an in-flight scan)
    overshoots and counts ``stats["overflow"]`` rather than deadlocking.

    Thread model: ONE background worker owns all transfers; public
    methods are safe to call from the serving thread. ``close()`` (or
    use as a context manager) stops the worker."""

    def __init__(self, retriever, hbm_budget: int, prefetch: bool = True,
                 link_bw: float | None = None, faults=None,
                 max_retries: int = 3, retry_backoff_s: float = 0.002):
        self.r = retriever
        self.store: SegmentedStore = retriever.store
        self.hbm_budget = int(hbm_budget)
        self.prefetch_enabled = bool(prefetch)
        # link emulation (benchmarks): pad every tier transfer to
        # bytes / link_bw wall time. On hosts where device_put aliases
        # host memory (the CPU backend: ~free "transfers"), an overlap
        # A/B would measure nothing; the pad rides on whichever thread
        # performs the transfer — the worker (hidden under compute) or
        # the caller (exposed) — so the scheduling property under test
        # is preserved while the bytes stay bitwise-real.
        self.link_bw = float(link_bw) if link_bw else None
        # fault tolerance: transient transfer failures retry with bounded
        # exponential backoff; ``faults`` arms a faults.FaultInjector /
        # FaultPlan on this engine's transfer and worker sites
        self.max_retries = int(max_retries)
        self.retry_backoff_s = float(retry_backoff_s)
        self._faults = FLT.as_injector(faults)
        self._lock = threading.RLock()
        self._lru: OrderedDict = OrderedDict()     # resident seg_i -> True
        self._resident_bytes = 0
        self._pins: dict = {}                      # seg_i -> pin count
        self._pending: dict = {}                   # seg_i -> _PendingOp
        self._queue: queue.Queue = queue.Queue()
        self._closed = False
        self._promote_ema = 0.0                    # s, recent promote cost
        self._fns: dict = {}
        self.stats = {"promotions": 0, "demotions": 0, "bytes_h2d": 0,
                      "bytes_d2h": 0, "hits": 0, "misses": 0,
                      "overflow": 0, "wait_s": 0.0, "retries": 0,
                      "transfer_errors": 0, "worker_restarts": 0,
                      "oom_evictions": 0, "deadline_skips": 0,
                      "degraded": 0}
        for i, seg in enumerate(self.store.segments):
            if seg.tier == "device":
                self._lru[i] = True
                self._resident_bytes += seg.nbytes
        self._worker = threading.Thread(
            target=self._run, name="tiering-worker", daemon=True)
        self._worker.start()
        self.enforce_budget()

    # -- lifecycle -----------------------------------------------------

    def arm(self, faults) -> FLT.FaultInjector | None:
        """(Re)arm fault injection on this engine's transfer/worker
        sites; ``None`` disarms. Returns the live injector."""
        self._faults = FLT.as_injector(faults)
        return self._faults

    def close(self) -> None:
        with self._lock:
            self._closed = True
        if self._worker.is_alive():
            self._queue.put(None)
            self._worker.join(timeout=30)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()

    # -- residency bookkeeping ------------------------------------------

    def resident(self) -> tuple:
        """Device-resident segment indices, LRU order (oldest first)."""
        with self._lock:
            return tuple(self._lru)

    @property
    def resident_bytes(self) -> int:
        return self._resident_bytes

    def enforce_budget(self) -> None:
        """Demote LRU segments until the budget holds (used at
        construction and after mutations grow a resident segment set)."""
        while True:
            with self._lock:
                victim = self._pick_victim()
                if victim is None:
                    return
            self._demote(victim)

    def _pick_victim(self):
        """Under ``self._lock``: the LRU unpinned resident segment, or
        None when the budget already holds (or nothing is evictable)."""
        if self._resident_bytes <= self.hbm_budget:
            return None
        for i in self._lru:
            if not self._pins.get(i):
                return i
        self.stats["overflow"] += 1
        return None

    def _demote(self, i: int) -> None:
        """Spill segment ``i`` to host RAM. ``device_get`` is bitwise
        (and safe against in-flight consumers: JAX computations hold
        their own buffer references), so a later promotion restores the
        exact bytes. Transient transfer failures retry with bounded
        exponential backoff; exhaustion raises ``TierError``. The copy
        commits via ``tier_swap`` only after it fully succeeds, so a
        failed attempt leaves the segment resident and consistent."""
        seg = self.store.segments[i]
        last = None
        delay = self.retry_backoff_s
        for attempt in range(self.max_retries + 1):
            try:
                t0 = time.monotonic()
                if self._faults is not None:
                    self._faults.fire("d2h")
                host = {k: np.asarray(jax.device_get(v))
                        for k, v in seg.vectors.items()}
                self._pace(seg.nbytes, t0)
            except FLT.TransientTransferError as e:
                last = e
                if attempt == self.max_retries:
                    break
                self.stats["retries"] += 1
                time.sleep(delay)
                delay = min(delay * 2, 0.1)
                continue
            with self._lock:
                if i not in self._lru:         # raced with another demote
                    return
                n = seg.nbytes
                self.store.tier_swap(i, host, "host")
                del self._lru[i]
                self._resident_bytes -= n
                self.stats["demotions"] += 1
                self.stats["bytes_d2h"] += n
            return
        self.stats["transfer_errors"] += 1
        raise TierError(
            f"demotion of segment {i} failed after "
            f"{self.max_retries + 1} attempts") from last

    def _pace(self, n_bytes: int, t0: float) -> None:
        """Emulated-link pacing: hold this thread until the transfer has
        taken at least ``n_bytes / link_bw`` seconds (no-op without
        ``link_bw``). Sleeps release the GIL, so a paced worker transfer
        still overlaps the serving thread's compute."""
        if self.link_bw:
            time.sleep(max(0.0, n_bytes / self.link_bw
                           - (time.monotonic() - t0)))

    def _to_device(self, key: str, v):
        mesh = self.store.mesh
        if mesh is not None:
            spec = P() if key in ROUTING_KEYS \
                else P(tuple(mesh.axis_names))
            return jax.device_put(v, NamedSharding(mesh, spec))
        return jax.device_put(v)

    def _make_room(self, i: int, need: int) -> None:
        """Demote LRU victims until ``need`` fits (or nothing unpinned is
        left — budget overshoots rather than deadlocking)."""
        while True:
            with self._lock:
                if self._resident_bytes + need <= self.hbm_budget:
                    return
                victim = None
                for j in self._lru:
                    if not self._pins.get(j) and j != i:
                        victim = j
                        break
                if victim is None:
                    self.stats["overflow"] += 1
                    return
            self._demote(victim)

    def _oom_victim(self, i: int):
        """Under fault pressure: one more unpinned resident segment to
        evict when the device allocator (not the budget) says no."""
        with self._lock:
            for j in self._lru:
                if not self._pins.get(j) and j != i:
                    return j
        return None

    def _promote(self, i: int) -> None:
        """Host->device transfer of segment ``i`` plus the room-making
        demotions it needs. Runs on the worker thread (prefetch) or
        inline (synchronous acquire).

        Failure handling: transient transfer errors retry with bounded
        exponential backoff; a device-OOM retries after evicting one
        more unpinned victim (eviction, not waiting, is the allocator
        remedy); exhaustion raises ``TierError``. The swap commits only
        after the full copy lands, so any failed attempt leaves the
        segment host-tier and every residency structure consistent."""
        with self._lock:
            if i in self._lru:
                self._lru.move_to_end(i)
                return
            seg = self.store.segments[i]
            need = seg.nbytes
        # make room first so the device never holds budget + need
        self._make_room(i, need)
        last = None
        delay = self.retry_backoff_s
        for attempt in range(self.max_retries + 1):
            try:
                t0 = time.monotonic()
                if self._faults is not None:
                    self._faults.fire("h2d")
                dev = {k: self._to_device(k, v)
                       for k, v in seg.vectors.items()}
                for v in dev.values():
                    v.block_until_ready()
                self._pace(need, t0)
            except (FLT.TransientTransferError, FLT.DeviceOOM) as e:
                last = e
                if isinstance(e, FLT.DeviceOOM):
                    victim = self._oom_victim(i)
                    if victim is not None:
                        self._demote(victim)
                        self.stats["oom_evictions"] += 1
                if attempt == self.max_retries:
                    break
                self.stats["retries"] += 1
                if isinstance(e, FLT.TransientTransferError):
                    time.sleep(delay)
                    delay = min(delay * 2, 0.1)
                continue
            dt = time.monotonic() - t0
            with self._lock:
                self.store.tier_swap(i, dev, "device")
                self._lru[i] = True
                self._lru.move_to_end(i)
                self._resident_bytes += need
                self.stats["promotions"] += 1
                self.stats["bytes_h2d"] += need
                self._promote_ema = dt if not self._promote_ema \
                    else 0.8 * self._promote_ema + 0.2 * dt
            return
        self.stats["transfer_errors"] += 1
        raise TierError(
            f"promotion of segment {i} failed after "
            f"{self.max_retries + 1} attempts") from last

    def _promote_estimate(self, i: int) -> float:
        """Expected seconds to promote segment ``i``: exact under the
        emulated link, else an EMA of recent promotions (0.0 until one
        lands — optimistic, so an unknown-cost transfer is attempted
        rather than skipped)."""
        if self.link_bw:
            return self.store.segments[i].nbytes / self.link_bw
        return self._promote_ema

    # -- async worker ----------------------------------------------------

    def _run(self) -> None:
        while True:
            i = self._queue.get()
            if i is None:
                return
            try:
                if self._faults is not None:
                    self._faults.fire("worker")
            except FLT.WorkerKilled:
                # injected thread death: exit WITHOUT finishing item i —
                # its waiters (and everything queued behind it) are
                # stranded until the supervisor restarts us. That
                # stranding is exactly the failure mode _ensure_worker
                # and _wait_op exist to recover from.
                return
            err = None
            try:
                self._promote(i)                # has its own retry budget
            except Exception as e:              # surfaced to THIS waiter
                err = e
            self._finish(i, err)

    def _finish(self, i: int, err: Exception | None) -> None:
        with self._lock:
            op = self._pending.pop(i, None)
        if op is not None:
            op.error = err
            op.event.set()

    def _ensure_worker(self) -> None:
        """Supervisor: if the worker thread died (injected kill, or any
        escape from its loop), restart it and re-enqueue every pending
        promotion so stranded waiters complete. Re-enqueueing an item the
        old worker had already finished is harmless — ``_promote`` is
        idempotent on resident segments and ``_finish`` tolerates an
        already-popped op. Pins and residency stay valid across the
        restart: pins are owned by serving threads, and swaps commit
        atomically under the lock, so a mid-transfer death can never
        leave half a segment resident."""
        with self._lock:
            if self._closed or self._worker.is_alive():
                return
            self.stats["worker_restarts"] += 1
            stranded = list(self._pending)
            self._worker = threading.Thread(
                target=self._run, name="tiering-worker", daemon=True)
            self._worker.start()
            for i in stranded:
                self._queue.put(i)

    def _wait_op(self, op: _PendingOp) -> None:
        """Wait for an async promotion without ever hanging on a dead
        worker: poll with a short timeout and run the supervisor between
        polls — a restart re-enqueues the op, whose event then fires."""
        while not op.event.wait(0.05):
            self._ensure_worker()

    def _request(self, i: int):
        """Enqueue an async promotion of segment ``i`` (idempotent);
        returns the in-flight ``_PendingOp``, or None when already
        resident."""
        self._ensure_worker()
        with self._lock:
            if i in self._lru:
                self._lru.move_to_end(i)
                return None
            op = self._pending.get(i)
            if op is None:
                op = _PendingOp()
                self._pending[i] = op
                self._queue.put(i)
            return op

    def prefetch(self, scope) -> None:
        """Async-promote the segments a scheduler predicts are needed
        next (the next query's scope, segment i+1 of the current one).
        Never blocks; the worker's copies overlap the caller's compute."""
        if not self.prefetch_enabled:
            return
        for i in scope:
            self._request(int(i))

    def _acquire(self, i: int, overlap: bool) -> None:
        """Make segment ``i`` resident and pin it until ``_release``.
        ``overlap=True`` waits on the worker (the transfer was ideally
        prefetched and already done); ``overlap=False`` is the
        synchronous-fetch baseline — the transfer runs inline, fully
        exposed on the caller's critical path.

        Never hangs and never leaks: waits are supervised (a dead worker
        is restarted and its queue replayed), a worker-side failure is
        retried once inline on this thread, and a permanent failure
        raises ``TierError`` with the pin released."""
        t0 = time.perf_counter()
        with self._lock:
            resident = i in self._lru
            if resident:
                self._lru.move_to_end(i)
                self.stats["hits"] += 1
            else:
                self.stats["misses"] += 1
            self._pins[i] = self._pins.get(i, 0) + 1
        if not resident:
            try:
                if overlap:
                    op = self._request(i)
                    if op is not None:
                        self._wait_op(op)
                    if op is not None and op.error is not None:
                        # the worker already spent its retry budget; one
                        # last inline attempt on the waiter's thread
                        self._promote(i)
                    else:
                        with self._lock:
                            still_missing = i not in self._lru
                        if still_missing:        # worker raced/failed
                            self._promote(i)
                else:
                    self._ensure_worker()
                    with self._lock:
                        op = self._pending.get(i)
                    if op is not None:           # a stray prefetch owns it
                        self._wait_op(op)
                    self._promote(i)
            except BaseException:
                self._release(i)                 # failed acquire: no pin
                raise
            self.stats["wait_s"] += time.perf_counter() - t0

    def _release(self, i: int) -> None:
        with self._lock:
            left = self._pins.get(i, 0) - 1
            if left > 0:
                self._pins[i] = left
            else:
                self._pins.pop(i, None)

    def _try_acquire(self, i: int, deadline: float | None) -> bool:
        """Deadline-budgeted acquire: pin and return True when segment
        ``i`` is resident or its promotion fits the remaining budget;
        return False (nothing pinned) when promoting it would blow the
        deadline — the degraded search skips it."""
        with self._lock:
            if i in self._lru:
                self._lru.move_to_end(i)
                self.stats["hits"] += 1
                self._pins[i] = self._pins.get(i, 0) + 1
                return True
        if deadline is not None:
            budget = deadline - time.monotonic()
            if budget <= 0 or self._promote_estimate(i) > budget:
                self.stats["deadline_skips"] += 1
                return False
        self._acquire(i, overlap=False)
        return True

    # -- compiled-fn cache ------------------------------------------------

    def _seg_fn(self, kind: str, stages: tuple, si_stage: int, seg_i: int,
                layout):
        key = (kind, stages, si_stage, layout[seg_i])
        fn = self._fns.get(key)
        if fn is None:
            cap = self.store.segments[seg_i].capacity
            if kind == "scan":
                fn = engine.make_segment_scan_fn(stages, cap)
            else:
                fn = engine.make_segment_rerank_fn(stages, si_stage, cap)
            self._fns[key] = fn
        return fn

    # -- search ------------------------------------------------------------

    def search(self, q, q_mask=None, *, stages: tuple, scope=None,
               filter=None, overlap: bool | None = None,
               deadline_ms: float | None = None,
               degrade: DegradePolicy | None = None) -> TieredResult:
        """Tiered cascade -> ``TieredResult`` (iterates as the classic
        ``(scores [B,k], stable page ids [B,k])`` pair).

        ``scope`` restricts the search to those segment indices (default:
        the whole corpus) — the unit of traffic locality the LRU keys on.
        ``overlap=None`` follows the engine's prefetch setting; False is
        the synchronous-fetch A/B baseline. Results are bitwise the
        fully-resident search over the same scope (same per-segment
        executables + exact combines; NEG-filler ids are masked to -1
        exactly as ``Retriever.search`` does). Segment residency and
        scope POSITION are data; only the scope SIZE family and query
        bucket are shapes — warm those once and tier churn re-dispatches
        cached executables (zero steady-state retraces).

        ``deadline_ms`` gives the request a wall budget: when promoting
        the next cold segment cannot fit the remaining budget, the
        engine degrades per ``degrade`` (default ``DegradePolicy()``)
        instead of blocking — cold segments are skipped and the result
        comes back ``degraded=True`` with the skip count (the
        exact-or-flagged invariant: a non-degraded result is ALWAYS the
        bitwise oracle answer). Degraded dispatch reuses the same warmed
        per-segment executables and combines — fewer fold steps, zero
        new shapes, zero retraces. Single-host only; on a mesh the
        deadline is ignored (the scope runs as one joint executable)."""
        t_entry = time.monotonic()
        store = self.store
        stages = self.r._normalize(tuple(stages))
        scope = tuple(range(len(store.segments))) if scope is None \
            else tuple(int(s) for s in scope)
        if not scope:
            raise ValueError("empty scope")
        overlap = self.prefetch_enabled if overlap is None else bool(overlap)
        q = jnp.asarray(q)
        if q_mask is None:
            q_mask = jnp.ones(q.shape[:2], bool)
        else:
            q_mask = jnp.asarray(q_mask)
            if q_mask.dtype != jnp.bool_:
                q_mask = q_mask.astype(bool)
        fspec = as_filter_arrays(
            filter, filter_words(store.segments[scope[0]].vectors))
        if self.r.mesh is not None:
            scores, ids = self._search_mesh(q, q_mask, stages, scope,
                                            fspec, overlap)
            return TieredResult(scores, ids)
        if deadline_ms:
            return self._search_degraded(
                q, q_mask, stages, scope, fspec,
                t_entry + deadline_ms / 1e3, degrade or DegradePolicy())
        offs = engine._offsets(store.capacities)
        caps = store.capacities
        layout = store.layout_key()
        k0 = stages[0].k

        # stage 0: per-segment scans, merged as each lands; the prefetch
        # of segment j+1 is dispatched BEFORE segment j's scan so the
        # worker's copy runs under it
        acc_v = acc_i = None
        width = 0
        self._acquire(scope[0], overlap)
        for j, si in enumerate(scope):
            nxt = scope[j + 1] if j + 1 < len(scope) else None
            if overlap and nxt is not None:
                self._request(nxt)
            fn = self._seg_fn("scan", stages, 0, si, layout)
            v, i = fn(store.segments[si].vectors, q, q_mask, fspec,
                      offs[si])
            self._release(si)
            if acc_v is None:
                acc_v, acc_i = v, i
                width = caps[si]
            else:
                width += caps[si]
                acc_v, acc_i = _merge_pair(acc_v, acc_i, v, i,
                                           min(k0, width))
            if nxt is not None:
                self._acquire(nxt, overlap)
        scores, cand = acc_v, acc_i

        # rerank stages: same pipeline shape; each segment scores the
        # global candidate set (NEG for non-owned) and the exact max-fold
        # recovers the owner's score
        for si_stage, stage in enumerate(stages[1:], start=1):
            s_all = None
            self._acquire(scope[0], overlap)
            for j, si in enumerate(scope):
                nxt = scope[j + 1] if j + 1 < len(scope) else None
                if overlap and nxt is not None:
                    self._request(nxt)
                fn = self._seg_fn("rerank", stages, si_stage, si, layout)
                s = fn(store.segments[si].vectors, q, q_mask, fspec,
                       offs[si], cand)
                self._release(si)
                s_all = s if s_all is None else _max_scores(s_all, s)
                if nxt is not None:
                    self._acquire(nxt, overlap)
            scores, cand = _select_stage(s_all, cand,
                                         min(stage.k, cand.shape[1]))
        return TieredResult(*self._translate(scores, cand))

    def _search_degraded(self, q, q_mask, stages, scope, fspec,
                         deadline: float, policy: DegradePolicy
                         ) -> TieredResult:
        """Deadline-budgeted cascade: scan scope segments in order,
        skipping cold ones whose promotion would blow the remaining
        budget (``_try_acquire``); the scanned set is an order-preserving
        subsequence of ``scope``, so a run that skips nothing folds in
        the exact oracle order and stays bitwise (degraded=False).

        Acquires are synchronous here — prefetching a segment the
        deadline may force us to skip would waste link budget and evict
        hot residents. Rerank stages revisit only the SCANNED segments
        (skipped segments contributed no candidates, so their rerank
        contribution is all-NEG by construction) and never skip: every
        candidate's owner score stays exact, which is what makes a
        degraded answer partial-but-never-wrong."""
        store = self.store
        offs = engine._offsets(store.capacities)
        caps = store.capacities
        layout = store.layout_key()
        degraded_stages = False
        if policy.stages_degraded is not None \
                and time.monotonic() >= deadline:
            # already blown on arrival: drop to the cheaper cascade
            stages = self.r._normalize(tuple(policy.stages_degraded))
            degraded_stages = True
        k0 = stages[0].k
        skip = deadline if policy.skip_cold else None
        acc_v = acc_i = None
        width = 0
        scanned, skipped = [], []

        def scan_one(si):
            nonlocal acc_v, acc_i, width
            fn = self._seg_fn("scan", stages, 0, si, layout)
            v, i = fn(store.segments[si].vectors, q, q_mask, fspec,
                      offs[si])
            self._release(si)
            if acc_v is None:
                acc_v, acc_i = v, i
                width = caps[si]
            else:
                width += caps[si]
                acc_v, acc_i = _merge_pair(acc_v, acc_i, v, i,
                                           min(k0, width))
            scanned.append(si)

        for si in scope:
            if not self._try_acquire(si, skip):
                skipped.append(si)
                continue
            scan_one(si)
        if len(scanned) < min(max(1, policy.min_segments), len(scope)):
            # deadline or not, a request gets a real answer: force the
            # first skipped segments in (still in scope order — nothing
            # else was scanned ahead of them out of order)
            for si in skipped[:max(1, policy.min_segments)
                              - len(scanned)]:
                self._acquire(si, overlap=False)
                scan_one(si)
                skipped.remove(si)
        scores, cand = acc_v, acc_i

        for si_stage, stage in enumerate(stages[1:], start=1):
            s_all = None
            for si in scanned:
                self._acquire(si, overlap=False)
                fn = self._seg_fn("rerank", stages, si_stage, si, layout)
                s = fn(store.segments[si].vectors, q, q_mask, fspec,
                       offs[si], cand)
                self._release(si)
                s_all = s if s_all is None else _max_scores(s_all, s)
            scores, cand = _select_stage(s_all, cand,
                                         min(stage.k, cand.shape[1]))
        degraded = bool(skipped) or degraded_stages
        if degraded:
            self.stats["degraded"] += 1
        return TieredResult(*self._translate(scores, cand),
                            degraded=degraded,
                            skipped_segments=len(skipped))

    def _search_mesh(self, q, q_mask, stages, scope, fspec,
                     overlap: bool) -> tuple:
        """Mesh path: promote the scope (transfers overlap EACH OTHER via
        the worker; per-segment host pipelining of compute is a
        single-host optimisation), then run the scope as one joint
        sharded cascade — the exact ``make_segmented_search_fn``
        executable a fully-resident scoped search runs."""
        if overlap:
            self.prefetch(scope)
        for si in scope:
            self._acquire(si, overlap)
        try:
            caps = tuple(self.store.segments[si].capacity for si in scope)
            layout = self.store.layout_key()
            key = ("mesh", stages, tuple(layout[si] for si in scope))
            fn = self._fns.get(key)
            if fn is None:
                fn = engine.make_segmented_search_fn(
                    self.r.mesh, stages, caps, self.r.rerank_overcommit)
                self._fns[key] = fn
            scores, slots = fn(
                tuple(self.store.segments[si].vectors for si in scope),
                q, q_mask, fspec)
        finally:
            for si in scope:
                self._release(si)
        table = np.concatenate(
            [self.store.segments[si].doc_ids for si in scope])
        slots = np.asarray(slots)
        ids = np.where(slots >= 0,
                       table[np.clip(slots, 0, len(table) - 1)],
                       np.int64(-1))
        return np.asarray(scores), np.where(
            np.asarray(scores) <= engine.NEG / 2, np.int64(-1), ids)

    def _translate(self, scores, cand) -> tuple:
        """Slot ids -> stable page ids with the retriever's NEG-filler
        masking (dead slots, filter-excluded live slots, and dropped-id
        sentinels all come back as -1)."""
        scores = np.asarray(scores)
        ids = self.store.translate_slots(np.asarray(cand))
        return scores, np.where(scores <= engine.NEG / 2,
                                np.int64(-1), ids)

    # -- persistence -------------------------------------------------------

    def snapshot(self, directory: str, **kw) -> str:
        """``tiering.snapshot`` under the residency lock (no tier swap
        can interleave with the flatten)."""
        with self._lock:
            return snapshot(self.store, directory, **kw)
