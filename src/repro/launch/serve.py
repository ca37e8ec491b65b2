"""Serving launcher: index a corpus, run batched multi-stage search.

  PYTHONPATH=src python -m repro.launch.serve --arch colpali \
      --pages 300 --queries 64 --stages 2 --use-kernel --chunk 128

Measures QPS for 1/2/3-stage configurations on the same corpus — the
CPU-scale twin of the paper's Table 2 throughput columns (benchmarks/run.py
does the full sweep). Search goes through the ``Retriever`` facade, which
owns the segmented corpus + mesh and caches the compiled cascade per
(stages, segment capacities); ``--use-kernel`` dispatches the scan stage to
the Pallas MaxSim kernel, ``--chunk`` bounds its per-launch corpus tile,
``--int8`` stores the scan vectors quantised. ``--n-clusters K --n-probe p``
switches the scan stage to IVF centroid routing: the corpus is k-means
clustered at index time (maintained through every mutation mode below) and
each query scans only the top-``p`` clusters' members instead of the whole
corpus (``p == K`` recovers the exhaustive result).

Dynamic-corpus mode:

  PYTHONPATH=src python -m repro.launch.serve --arch colpali --pages 100 \
      --ingest-batches 8 --ingest-batch-size 32

starts from a capacity-padded corpus and measures steady-state live
ingestion: upsert throughput (pages/s), search-after-upsert QPS, and the
no-retrace contract (retrace count printed, expected 0 after warm-up).
Add ``--ingest-pipeline`` to ingest RAW pages through the device-resident
``IngestPipeline`` (fused hygiene -> pooling -> quantise -> segment write,
one jit per power-of-two batch bucket; ``--use-kernel`` also dispatches
the pooling to the fused operator) instead of host-driven ``build_store``
+ ``upsert``.

Streaming-traffic mode:

  PYTHONPATH=src python -m repro.launch.serve --arch colpali --pages 100 \
      --traffic 200 --max-batch 16 --flush-ms 2

replays an open-loop Poisson arrival process of single RAGGED queries
(varying token counts) through the ``ServingFrontend``: shape-bucketed
padding + deadline-based micro-batching. Prints p50/p95/p99 latency,
ragged-traffic QPS vs the fixed-shape static QPS on the same corpus, and
the steady-state query-shape retrace count (expected 0 after bucket
warm-up). ``--arrival-rate 0`` (default) auto-sets the offered load to
~0.8x the measured static QPS, keeping the system stable but busy.

Multi-tenant mode (composes with static and traffic modes):

  PYTHONPATH=src python -m repro.launch.serve --arch colpali --pages 120 \
      --tenants 4 --traffic 200 --tenant-quota 8

splits the corpus round-robin across ``--tenants`` tenants (each batch
upserted with its tenant id stamped into the ``doc_tenant`` store
companion) and scopes every request to a random tenant via a
``store.FilterSpec`` — request filters are DATA through the compiled
cascade, so mixed-tenant traffic at warmed buckets causes zero retraces.
The frontend queues per filter, flushes round-robin (a bursting tenant
cannot starve a quiet one), and ``--tenant-quota`` bounds queued rows per
tenant (excess submits are rejected at admission).

Failure drills (compose with static/tiered/traffic modes):

  PYTHONPATH=src python -m repro.launch.serve --pages 100 --hbm-budget \
      20000000 --fault-plan transfer_fail_rate=0.05,seed=7 \
      --deadline-ms 50 --degrade

``--fault-plan`` arms the deterministic fault injector
(``retrieval.faults.FaultPlan.parse`` spec) on the tiered engine's
transfer/worker sites; ``--deadline-ms``/``--degrade`` give requests a
wall budget under which the engine serves from resident segments only
(results flagged degraded) instead of blocking on cold promotions. On
SIGTERM/SIGINT the launcher exits GRACEFULLY: drain the frontend's
queued requests, take a final generation-stamped snapshot (with
``--snapshot-dir``), report shed/degraded/retry counters, exit 0 — or 1
when a request errored and no ``--fault-plan`` was armed, as every mode
does.
"""
from __future__ import annotations

import argparse
import signal
import sys
import time

import numpy as np

# live serving objects the SIGTERM/SIGINT path drains/snapshots; mode
# runners register what they build (a launcher-scoped registry, not a
# library surface)
_LIVE: dict = {}


class _Shutdown(BaseException):
    """Raised inside the serving loop by the signal handler; unwinds to
    main()'s graceful-exit path. A ``BaseException`` on purpose: the
    frontend's poisoned-dispatch recovery catches ``Exception`` so one
    bad cohort can't take the server down — a kill signal must sail
    through that net, not be absorbed as a per-request error."""


def _install_signals():
    def handler(signum, frame):
        raise _Shutdown(signal.Signals(signum).name)
    for s in (signal.SIGTERM, signal.SIGINT):
        signal.signal(s, handler)


def _graceful_exit(args, reason: str) -> None:
    """Drain, snapshot, report, exit — a SIGTERM'd server finishes the
    work it admitted and leaves a corpus the next process cold-starts
    from (the restart-without-re-ingest loop)."""
    print(f"\n{reason}: graceful shutdown")
    fe = _LIVE.get("frontend")
    if fe is not None:
        served = fe.drain()
        print(f"  drained {served} queued request(s); stats: "
              f"shed={fe.stats['shed']} degraded={fe.stats['degraded']} "
              f"errors={fe.stats['errors']} rejected={fe.stats['rejected']}")
    eng = _LIVE.get("engine")
    if eng is not None:
        st = eng.stats
        print(f"  engine: retries={st['retries']} "
              f"transfer_errors={st['transfer_errors']} "
              f"worker_restarts={st['worker_restarts']} "
              f"degraded={st['degraded']} "
              f"deadline_skips={st['deadline_skips']}")
    retriever = _LIVE.get("retriever")
    if retriever is not None and args.snapshot_dir:
        # generation-stamped: snapshot() defaults step to the store
        # generation, so a drained final state lands under its own step
        path = retriever.snapshot(args.snapshot_dir)
        print(f"  final snapshot -> {path}")
    if eng is not None:
        eng.close()
    sys.exit(_exit_status(args, fe.stats["errors"] if fe is not None else 0))


def _multi_tenant_retriever(args, cfg, bench, stages, int8_on, **kw):
    """Build a Retriever whose corpus is split round-robin across
    ``args.tenants`` tenants: tenant t owns benchmark pages t, t+T, ...,
    upserted with its tenant id stamped into the ``doc_tenant`` store
    companion. Returns the retriever (page ids are reassigned in upsert
    order, so qrels-based metrics don't apply in tenant mode)."""
    import jax.numpy as jnp
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.segments import bucket_capacity
    from repro.retrieval.store import build_store, quantize_store

    T = args.tenants
    pages = np.asarray(bench.pages)
    tt = jnp.asarray(bench.token_types)
    batches = []
    for t in range(T):
        sel = np.arange(t, len(pages), T)
        b = build_store(cfg, jnp.asarray(pages[sel]), tt)
        if int8_on:
            b = quantize_store(b, names=(stages[0].vector,), stages=stages)
        batches.append(b)
    kw.setdefault("capacity", bucket_capacity(len(pages)))
    kw.setdefault("routing", args.n_clusters or None)
    retriever = Retriever(batches[0], **kw)       # seed batch = tenant 0
    for t in range(1, T):
        retriever.upsert(batches[t], tenant=t)
    return retriever


def _run_static(args, cfg, bench, store, stages, int8_on):
    import jax.numpy as jnp
    from repro.data.synthetic import evaluate_ranking
    from repro.retrieval.retriever import Retriever

    if args.tenants > 1:
        return _run_static_tenants(args, cfg, bench, stages, int8_on)
    retriever = None
    if args.snapshot_dir:
        from repro.training.checkpoint import latest_step
        if latest_step(args.snapshot_dir) is not None:
            t0 = time.time()
            retriever = Retriever.from_snapshot(args.snapshot_dir)
            print(f"cold-start: restored {retriever.n_docs} pages from "
                  f"{args.snapshot_dir} in {time.time()-t0:.2f}s "
                  "(bitwise the saved corpus; no re-ingest)")
    if retriever is None:
        retriever = Retriever(store, routing=args.n_clusters or None)
        if args.snapshot_dir:
            t0 = time.time()
            path = retriever.snapshot(args.snapshot_dir)
            print(f"snapshot -> {path} ({time.time()-t0:.2f}s; restart "
                  "with the same --snapshot-dir to cold-start from it)")
    if args.hbm_budget > 0:
        return _run_tiered(args, bench, retriever, stages)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    retriever.search(q, qm, stages=stages)                    # compile
    t0 = time.time()
    for _ in range(3):
        # time raw dispatch (device slot ids); translate once for metrics
        scores, _ = retriever.search(q, qm, stages=stages,
                                     translate_ids=False)
    scores.block_until_ready()
    dt = (time.time() - t0) / 3
    qps = len(q) / dt
    _, ids = retriever.search(q, qm, stages=stages)
    metrics = evaluate_ranking(np.asarray(ids), bench.qrels, ks=(5, 10))
    scan = ("kernel" if args.use_kernel else "ref") + \
        (f"/chunk={args.chunk}" if args.chunk else "") + \
        ("/scan-topk" if args.scan_topk else "") + \
        ("/rerank-kernel" if args.rerank_kernel else "") + \
        ("/int8" if int8_on else "")
    print(f"{args.stages}-stage [{scan}]: QPS={qps:.1f}  " +
          "  ".join(f"{k}={v:.3f}" for k, v in metrics.items()))


def _run_tiered(args, bench, retriever, stages):
    """Static QPS through the tiered residency engine: device-resident
    segment bytes capped at ``--hbm-budget``, cold segments spilled to
    host RAM, async-prefetch overlap vs synchronous fetch both timed."""
    import jax.numpy as jnp

    from repro.retrieval.faults import FaultPlan
    from repro.retrieval.tiering import DegradePolicy

    store_bytes = sum(s.nbytes for s in retriever.store.segments)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    plan = FaultPlan.parse(args.fault_plan) if args.fault_plan else None
    with retriever.tiered(args.hbm_budget, faults=plan) as eng:
        _LIVE["engine"] = eng
        _LIVE["retriever"] = retriever
        for overlap in (True, False):
            eng.search(q, qm, stages=stages, overlap=overlap)  # warm
            t0 = time.time()
            for _ in range(3):
                eng.search(q, qm, stages=stages, overlap=overlap)
            qps = 3 * len(q) / (time.time() - t0)
            mode = "overlap" if overlap else "sync"
            print(f"tiered [{mode}, budget {args.hbm_budget/1e6:.0f}MB / "
                  f"corpus {store_bytes/1e6:.0f}MB]: QPS={qps:.1f}  "
                  f"resident={len(eng.resident())}/"
                  f"{len(retriever.store.segments)} segments")
        if args.deadline_ms > 0:
            res = eng.search(
                q, qm, stages=stages, deadline_ms=args.deadline_ms,
                degrade=DegradePolicy() if args.degrade else None)
            print(f"  deadline {args.deadline_ms:.0f}ms: "
                  f"degraded={res.degraded} "
                  f"skipped_segments={res.skipped_segments}")
        st = eng.stats
        print(f"  promotions={st['promotions']} demotions="
              f"{st['demotions']} h2d={st['bytes_h2d']/1e6:.0f}MB "
              f"hit-rate={st['hits']/max(st['hits']+st['misses'],1):.2f} "
              f"wait={st['wait_s']*1e3:.1f}ms retries={st['retries']} "
              f"transfer_errors={st['transfer_errors']} "
              f"worker_restarts={st['worker_restarts']}")


def _run_static_tenants(args, cfg, bench, stages, int8_on):
    """Static mode over a tenant-partitioned corpus: per-tenant scoped
    searches (tenant filters are traced data — one compiled cascade serves
    every tenant, asserted via the retrace counter)."""
    import jax.numpy as jnp
    from repro.retrieval import tracing
    from repro.retrieval.store import FilterSpec

    retriever = _multi_tenant_retriever(args, cfg, bench, stages, int8_on)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    retriever.search(q, qm, stages=stages,
                     filter=FilterSpec(tenant=0))             # compile
    warm = tracing.trace_count()
    per_tenant = []
    for t in range(args.tenants):
        t0 = time.time()
        for _ in range(3):
            scores, _ = retriever.search(q, qm, stages=stages,
                                         translate_ids=False,
                                         filter=FilterSpec(tenant=t))
        scores.block_until_ready()
        per_tenant.append(len(q) / ((time.time() - t0) / 3))
    retraces = tracing.trace_count() - warm
    qps = ", ".join(f"t{t}={v:.1f}" for t, v in enumerate(per_tenant))
    print(f"{args.stages}-stage x {args.tenants} tenants "
          f"[{retriever.n_docs} docs total]: scoped QPS {qps}  "
          f"tenant-swap retraces={retraces} (expect 0)")


def _make_ragged_requests(bench, n_req: int, rng, min_tokens: int = 3):
    """Sample single-query requests with RAGGED token counts: each request
    truncates a benchmark query to a random prefix of its valid tokens (a
    short/long query mix, the shape mix real traffic has)."""
    base_q = np.asarray(bench.queries)
    base_m = np.asarray(bench.query_mask)
    reqs = []
    for _ in range(n_req):
        j = int(rng.integers(len(base_q)))
        q_len = int(base_m[j].sum())
        keep = int(rng.integers(min(min_tokens, q_len), q_len + 1))
        reqs.append((base_q[j, :keep], base_m[j, :keep]))
    return reqs


def _exit_status(args, errors: int) -> int:
    """Process exit status after serving: non-zero when any request
    errored, unless ``--fault-plan`` armed the fault injector on purpose
    (then errors are the drill's expected outcome, reported above)."""
    if errors and not args.fault_plan:
        print(f"FAILED: {errors} request(s) errored", file=sys.stderr)
        return 1
    return 0


def _run_traffic(args, cfg, bench, store, stages, int8_on):
    """Open-loop Poisson traffic of ragged single queries through the
    shape-bucketed micro-batching frontend; tail latency + QPS report.
    Returns the number of requests that errored."""
    import jax.numpy as jnp
    from repro.retrieval import tracing
    from repro.retrieval.frontend import ServingFrontend, replay_open_loop
    from repro.retrieval.retriever import Retriever

    from repro.retrieval.store import FilterSpec

    if args.tenants > 1:
        retriever = _multi_tenant_retriever(args, cfg, bench, stages,
                                            int8_on, scan_chunk=args.chunk)
    else:
        retriever = Retriever(store, scan_chunk=args.chunk,
                              routing=args.n_clusters or None)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)

    # fixed-shape static reference on the same corpus (the _run_static
    # protocol: one [B, Q] block, raw slot ids, timed after compile)
    retriever.search(q, qm, stages=stages)
    t0 = time.time()
    for _ in range(3):
        scores, _ = retriever.search(q, qm, stages=stages,
                                     translate_ids=False)
    scores.block_until_ready()
    static_qps = len(q) / ((time.time() - t0) / 3)

    fe = ServingFrontend(retriever, stages, max_batch=args.max_batch,
                         max_q=bench.queries.shape[1],
                         flush_ms=args.flush_ms,
                         cache_size=args.result_cache,
                         tenant_quota=args.tenant_quota,
                         deadline_ms=args.deadline_ms)
    _LIVE["frontend"] = fe
    _LIVE["retriever"] = retriever
    n_warm = fe.warm()
    rate = args.arrival_rate or 0.8 * static_qps
    rng = np.random.default_rng(17)
    reqs = _make_ragged_requests(bench, args.traffic, rng)
    if args.tenants > 1:
        # scope every request to a random tenant — filters are data, so
        # the mixed-tenant stream re-dispatches the warmed executables
        tenant_of = rng.integers(0, args.tenants, size=len(reqs))
        reqs = [(rq, rm, FilterSpec(tenant=int(t)))
                for (rq, rm), t in zip(reqs, tenant_of)]

    warm_traces = tracing.trace_count()
    served, wall = replay_open_loop(fe, reqs, rate, seed=18)
    retraces = tracing.trace_count() - warm_traces

    lat_ms = np.asarray([p.latency for p in served]) * 1e3
    qps = len(served) / wall
    p50, p95, p99 = np.percentile(lat_ms, (50, 95, 99))
    tenants = f", {args.tenants} tenants" if args.tenants > 1 else ""
    print(f"traffic [{args.traffic} ragged req, Poisson {rate:.0f}/s, "
          f"buckets B<={fe.max_batch} Q<={fe.max_q} ({n_warm} warmed), "
          f"flush {args.flush_ms:.1f}ms{tenants}]:")
    print(f"  p50={p50:.2f}ms  p95={p95:.2f}ms  p99={p99:.2f}ms  "
          f"QPS={qps:.1f} (static fixed-shape QPS={static_qps:.1f}, "
          f"ratio {qps/static_qps:.2f}x)")
    print(f"  dispatches={fe.stats['dispatches']}  "
          f"rows/dispatch="
          f"{fe.stats['rows_real']/max(fe.stats['dispatches'], 1):.1f}  "
          f"padded rows={fe.stats['rows_padded']}  "
          f"cache hits={fe.stats['cache_hits']}  "
          f"rejected={fe.stats['rejected']}  "
          f"shed={fe.stats['shed']}  degraded={fe.stats['degraded']}  "
          f"errors={fe.stats['errors']}  "
          f"steady-state retraces={retraces} (expect 0)")
    return fe.stats["errors"]


def _run_ingest(args, cfg, bench, store, stages, int8_on):
    """Steady-state live-corpus benchmark: upsert batches into preallocated
    segment headroom, search after every upsert, count retraces.

    ``--ingest-pipeline`` switches the write path from host-driven
    ``build_store`` + ``upsert`` to the device-resident ``IngestPipeline``
    (raw pages in, one fused dispatch per batch)."""
    import jax
    import jax.numpy as jnp
    from repro.retrieval import tracing
    from repro.retrieval.ingest import IngestPipeline, batch_bucket
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.segments import bucket_capacity
    from repro.retrieval.store import build_store, quantize_store

    bs = args.ingest_batch_size
    n_batches = args.ingest_batches
    total = store.n_docs + (n_batches + 1) * bs
    # the pipeline writes full bucket-wide blocks, so its last batch needs
    # batch_bucket(bs) free tail slots, not just bs — size the default
    # capacity for that or the steady state would allocate a new segment
    # (and retrace) right at the end
    slack = batch_bucket(bs) if args.ingest_pipeline else 0
    cap = args.capacity or bucket_capacity(total + slack)
    quantize = (stages[0].vector,) if int8_on else ()
    pipe = IngestPipeline.for_config(
        cfg, quantize=quantize, stages=stages if int8_on else None,
        use_kernel=args.use_kernel) if args.ingest_pipeline else None
    retriever = Retriever(store, capacity=cap, scan_chunk=args.chunk,
                          ingest=pipe, routing=args.n_clusters or None)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)

    rng = np.random.default_rng(13)
    base = np.asarray(bench.pages)
    tt = jnp.asarray(bench.token_types)

    def make_pages():
        # fresh synthetic pages with the same geometry (resampled + jittered
        # real pages stand in for newly ingested PDFs)
        sel = rng.integers(0, len(base), size=bs)
        return jnp.asarray(base[sel] + 0.05 * rng.normal(
            size=base[sel].shape), jnp.float32)

    def ingest_batch():
        if pipe is not None:
            return retriever.ingest(make_pages(), tt)   # fused device path
        batch = build_store(cfg, make_pages(), tt)
        if int8_on:
            batch = quantize_store(batch, names=(stages[0].vector,),
                                   stages=stages)
        return retriever.upsert(batch)

    # ---- warm-up: one upsert + delete + search compiles every executable
    # (delete the same count as the steady-state delete below, so the
    # padded slot-bucket shape — and thus the _invalidate executable —
    # matches for any batch size)
    ids = ingest_batch()
    retriever.delete(ids[: max(1, bs // 8)])
    s, _ = retriever.search(q, qm, stages=stages)
    s.block_until_ready()
    warm_traces = tracing.trace_count()

    up_dt, search_dt = [], []
    for _ in range(n_batches):
        t0 = time.time()
        ids = ingest_batch()
        jax.block_until_ready(retriever.store.stores())
        up_dt.append(time.time() - t0)
        t0 = time.time()
        s, _ = retriever.search(q, qm, stages=stages)
        s.block_until_ready()
        search_dt.append(time.time() - t0)
    retriever.delete(ids[: max(1, bs // 8)])
    s, _ = retriever.search(q, qm, stages=stages)
    s.block_until_ready()
    retraces = tracing.trace_count() - warm_traces

    mode = "pipeline" if pipe is not None else "host build_store"
    ingest_pps = bs / np.mean(up_dt)
    qps = len(q) / np.mean(search_dt)
    print(f"ingest [{n_batches} x {bs} pages into capacity {cap}, "
          f"{mode}]: {ingest_pps:.0f} pages/s upsert, "
          f"search-after-upsert QPS={qps:.1f}, "
          f"live docs={retriever.n_docs}, "
          f"segments={retriever.store.capacities}, "
          f"steady-state retraces={retraces} (expect 0)")


def main():
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.launch.runtime import device_line, setup_compile_cache
    from repro.retrieval.store import build_store, quantize_store

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="colpali")
    ap.add_argument("--pages", type=int, default=300)
    ap.add_argument("--queries", type=int, default=60)
    ap.add_argument("--stages", type=int, default=2, choices=(1, 2, 3))
    ap.add_argument("--prefetch-k", type=int, default=256)
    ap.add_argument("--top-k", type=int, default=100)
    ap.add_argument("--use-kernel", action="store_true",
                    help="dispatch the scan stage to the Pallas MaxSim "
                         "kernel (jnp ref fallback when unavailable)")
    ap.add_argument("--chunk", type=int, default=0,
                    help="scan-stage corpus chunk (0 = unchunked)")
    ap.add_argument("--scan-topk", action="store_true",
                    help="stream a running per-query top-k across scan "
                         "chunks instead of assembling the [B, N] score "
                         "matrix (HBM write O(B*k*n_chunks), not O(B*N))")
    ap.add_argument("--rerank-kernel", action="store_true",
                    help="dispatch rerank stages to the fused gather+"
                         "MaxSim path (scalar-prefetch Pallas kernel on "
                         "TPU, blockwise jnp twin elsewhere) — no "
                         "materialised [B, L, D, d] candidate copy")
    ap.add_argument("--int8", action="store_true",
                    help="int8-quantise the scan-stage vectors")
    ap.add_argument("--n-clusters", type=int, default=0,
                    help="enable IVF centroid routing: cluster each "
                         "segment's routing vectors into this many "
                         "clusters (k-means at index time, maintained "
                         "through upsert/delete/compact)")
    ap.add_argument("--n-probe", type=int, default=0,
                    help="clusters probed per query by the routed scan "
                         "stage (requires --n-clusters; n-probe == "
                         "n-clusters is the exhaustive-parity mode)")
    ap.add_argument("--ingest-batches", type=int, default=0,
                    help="dynamic-corpus mode: upsert this many batches "
                         "into preallocated headroom, measuring steady-"
                         "state ingestion + search-after-upsert")
    ap.add_argument("--ingest-batch-size", type=int, default=32)
    ap.add_argument("--ingest-pipeline", action="store_true",
                    help="ingest raw pages through the device-resident "
                         "IngestPipeline (fused hygiene/pooling/quantise/"
                         "write, one jit per batch bucket) instead of "
                         "host-driven build_store + upsert")
    ap.add_argument("--capacity", type=int, default=0,
                    help="preallocated corpus capacity (0 = bucketed "
                         "power-of-two over the expected total)")
    ap.add_argument("--traffic", type=int, default=0,
                    help="streaming-traffic mode: replay this many Poisson-"
                         "arriving ragged single queries through the shape-"
                         "bucketed micro-batching frontend and report "
                         "p50/p95/p99 latency + QPS")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="offered load in req/s (0 = auto: ~0.8x the "
                         "measured fixed-shape static QPS)")
    ap.add_argument("--flush-ms", type=float, default=2.0,
                    help="micro-batch deadline: flush when the oldest "
                         "queued request has waited this long")
    ap.add_argument("--max-batch", type=int, default=16,
                    help="micro-batch row cap (= largest batch bucket; "
                         "power of two)")
    ap.add_argument("--result-cache", type=int, default=0,
                    help="LRU result-cache entries (0 = off)")
    ap.add_argument("--tenants", type=int, default=0,
                    help="multi-tenant mode: split the corpus round-robin "
                         "across this many tenants (doc_tenant-stamped "
                         "upserts) and scope requests via FilterSpec")
    ap.add_argument("--snapshot-dir", default="",
                    help="persist/restore the indexed corpus: when the "
                         "directory holds a snapshot, cold-start from it "
                         "(skip re-ingesting); otherwise index normally "
                         "and save one there (static mode)")
    ap.add_argument("--hbm-budget", type=int, default=0,
                    help="tiered-residency mode (static): cap device-"
                         "resident segment bytes at this budget, spill "
                         "cold segments to host RAM, and report QPS with "
                         "async prefetch vs synchronous fetch")
    ap.add_argument("--tenant-quota", type=int, default=0,
                    help="max queued rows per tenant in the traffic "
                         "frontend (0 = unlimited); excess submits are "
                         "rejected at admission")
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request wall budget: tiered searches over "
                         "budget serve resident segments only (flagged "
                         "degraded); queued traffic requests past their "
                         "deadline are shed instead of dispatched "
                         "(0 = no deadline)")
    ap.add_argument("--degrade", action="store_true",
                    help="with --deadline-ms: apply the DegradePolicy "
                         "(skip cold segments under deadline pressure) "
                         "instead of the default resident-only fallback")
    ap.add_argument("--fault-plan", default="",
                    help="arm the deterministic fault injector on the "
                         "tiered engine (FaultPlan.parse spec, e.g. "
                         "'transfer_fail_rate=0.05,kill_worker_at=3,"
                         "seed=7')")
    args = ap.parse_args()
    setup_compile_cache()
    print(device_line(), flush=True)
    _install_signals()

    cfg = get_config(args.arch)
    per = max(args.pages // 3, 30)
    qper = max(args.queries // 3, 10)
    bench = make_benchmark(cfg, (per, per, per), (qper, qper, qper))
    restoring = False
    if args.snapshot_dir:
        from repro.training.checkpoint import latest_step
        restoring = (args.traffic == 0 and args.ingest_batches == 0
                     and args.tenants <= 1
                     and latest_step(args.snapshot_dir) is not None)
    t0 = time.time()
    store = None
    if not restoring:
        store = build_store(cfg, jnp.asarray(bench.pages),
                            jnp.asarray(bench.token_types))

    stages = {1: MST.one_stage(args.top_k),
              2: MST.two_stage(args.prefetch_k, args.top_k),
              3: MST.three_stage(4 * args.prefetch_k, args.prefetch_k,
                                 args.top_k)}[args.stages]
    stages = MST.with_scan_policy(stages, use_kernel=args.use_kernel,
                                  chunk=args.chunk,
                                  scan_topk=args.scan_topk)
    stages = MST.with_rerank_policy(stages,
                                    rerank_kernel=args.rerank_kernel)
    if args.n_probe > 0:
        if args.n_clusters <= 0:
            ap.error("--n-probe requires --n-clusters")
        stages = MST.with_routing_policy(stages, n_probe=args.n_probe,
                                         n_clusters=args.n_clusters)
    int8_on = False
    if args.int8 and store is not None:
        # quantise the vector the scan stage scores; a single-vector scan
        # (3-stage global_pooling) has nothing worth quantising
        scan_vec = stages[0].vector
        if store.vectors[scan_vec].ndim == 3:
            # stages-aware: drops the float copy when no later stage
            # reranks with the scan vector, so int8 actually halves
            # (not doubles) that vector's HBM
            store = quantize_store(store, names=(scan_vec,), stages=stages)
            int8_on = True
        else:
            print(f"--int8: scan stage '{scan_vec}' is single-vector; "
                  "skipping quantisation")
    if store is not None:
        print(f"indexed {store.n_docs} pages in {time.time()-t0:.2f}s "
              f"(named vectors: {sorted(store.dims())})")
    errors = 0
    try:
        if args.traffic > 0:
            errors = _run_traffic(args, cfg, bench, store, stages, int8_on)
        elif args.ingest_batches > 0:
            _run_ingest(args, cfg, bench, store, stages, int8_on)
        else:
            _run_static(args, cfg, bench, store, stages, int8_on)
    except _Shutdown as e:
        _graceful_exit(args, str(e))
    if _exit_status(args, errors):
        sys.exit(1)


if __name__ == "__main__":
    main()
