"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (harness convention) plus the
full result tables to stdout and benchmarks/results/paper_tables.json.

  table2_quality_qps   paper Table 2: 1/2/3-stage NDCG/Recall@{5,10,100} +
                       QPS per model (colpali/colqwen/colsmol analogues),
                       union scope, with token hygiene  [§5]
  scope_scaling        paper §5 "Throughput": per-dataset vs union QPS
                       ratio (the 2x -> 4x trend with corpus size)
  eq1_cost_model       paper §1 Eq. 1: measured madds reduction vs D/D'
  pooling_ablation     paper §2.3.3/§5: conv1d vs gaussian vs triangular on
                       the PatchMerger geometry (double-smoothing effect)
  hygiene_ablation     paper §2.1: clean vs dirty MaxSim quality
  kernel_micro         maxsim / pooling / embed_bag kernel timings (jnp ref
                       path on CPU; Pallas path is interpret-validated)
  rerank_kernel_vs_ref candidate-path A/B: fused gather-rerank + streamed
                       scan top-k vs the reference path — e2e cascade QPS
                       (interleaved-min), rerank-stage micro timings,
                       oracle parity asserted (bitwise on ref, tolerance
                       on fused), zero steady-state retraces asserted,
                       predicted (HBM byte model) vs measured speedup;
                       rows persist to BENCH_candidate_path.json by sha
  dynamic_corpus       live mutable corpus: search QPS at 25/50/75/100%
                       segment fill, steady-state upsert/delete latency,
                       retrace count asserted == 0 (beyond-paper serving)
  serving_tail_latency open-loop Poisson traffic of ragged single queries
                       through the shape-bucketed micro-batching frontend:
                       p50/p95/p99 latency, ragged QPS vs fixed-shape
                       static QPS, query-shape retrace count asserted == 0
                       (beyond-paper serving)
  mixed_tenant_tail_latency
                       two tenants on one corpus, one bursting ~7x the
                       other, every request tenant-scoped via FilterSpec:
                       per-tenant p50/p99, tenant isolation of returned
                       ids asserted, zero retraces across filter swaps
                       asserted, quiet-tenant p99 within the round-robin
                       fair-flush bound asserted; rows persist to
                       BENCH_multi_tenant.json by sha (beyond-paper)
  ingest_throughput    device-resident ingest pipeline: pages/sec per
                       batch bucket, fused-kernel vs ref pooling, int8
                       on/off, vs legacy build_store+upsert; mixed-size
                       steady-state retrace count asserted == 0
                       (beyond-paper serving)
  routed_scan          centroid-routed (IVF) candidate generation vs the
                       exhaustive scan: N-ladder QPS crossover curve,
                       recall@10 vs exhaustive asserted >= 0.95 at the
                       benchmarked n_probe, n_probe sweep, BITWISE parity
                       at n_probe == n_clusters asserted, zero retraces
                       asserted; rows persist to BENCH_routed_scan.json
                       by sha

``--suite name`` (repeatable; see SUITES) runs a named subset;
``--quick`` shrinks sizes for CI. Ledger keys grow a ``-dirty`` suffix
when the working tree is modified, so dirty reruns never clobber a
committed sha's row.
"""
from __future__ import annotations

import json
import os
import time

import numpy as np

RESULTS = os.path.join(os.path.dirname(__file__), "results")
ROWS = []


def _git_sha() -> str:
    """Ledger key: short sha of HEAD, with a ``-dirty`` suffix when the
    working tree differs from it. The BENCH_*.json ledgers key rows by
    sha, so without the suffix a dirty-tree rerun would silently clobber
    the committed clean-sha row with numbers no commit corresponds to."""
    import subprocess
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    try:
        sha = subprocess.check_output(
            ["git", "rev-parse", "--short", "HEAD"],
            cwd=root, text=True).strip()
        dirty = subprocess.check_output(
            ["git", "status", "--porcelain"], cwd=root, text=True).strip()
        return sha + ("-dirty" if dirty else "")
    except Exception:
        return "unknown"


def _persist_ledger(filename: str, entry: dict) -> None:
    """Write ``entry`` into the repo-root ledger ``filename`` under the
    current git sha (see ``_git_sha``). The file is a COMMITTED ledger:
    each PR's pre-commit quick-bench run appends its row and the PR
    checks it in, so the perf trajectory accumulates in git history
    (re-running on the same clean sha overwrites that sha's entry; a
    fresh CI checkout re-records the current sha and uploads the file as
    an artifact — the cross-PR trend lives in the committed copy)."""
    path = os.path.abspath(os.path.join(os.path.dirname(__file__), "..",
                                        filename))
    hist = {}
    if os.path.exists(path):
        try:
            with open(path) as f:
                hist = json.load(f)
        except Exception:
            hist = {}
    hist[_git_sha()] = entry
    with open(path, "w") as f:
        json.dump(hist, f, indent=1, default=float)


def _t(fn, *args, reps=2):
    fn(*args)                                    # compile
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    _block(out)
    return (time.time() - t0) / reps


def _block(out):
    import jax
    for x in jax.tree.leaves(out):
        getattr(x, "block_until_ready", lambda: None)()


def _emit(name, seconds, derived=""):
    ROWS.append((name, seconds * 1e6, derived))
    print(f"{name},{seconds*1e6:.1f},{derived}")


def table2_quality_qps(table: dict):
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import evaluate_ranking, make_benchmark
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import build_store

    out = {}
    for arch in ("colpali", "colqwen", "colsmol"):
        cfg = get_config(arch)
        # page/query counts scaled to CPU wall-clock; same protocol shape
        # as the paper's ESG/Bio/Econ split (union scope, hygiene on)
        bench = make_benchmark(cfg, (110, 90, 70), (25, 25, 20), seed=2)
        store = build_store(cfg, jnp.asarray(bench.pages),
                            jnp.asarray(bench.token_types))
        retriever = Retriever(store)
        q = jnp.asarray(bench.queries)
        qm = jnp.asarray(bench.query_mask)
        configs = {
            "1stage": MST.one_stage(100),
            "2stage": MST.two_stage(256, 100),
            "3stage": MST.three_stage(512, 256, 100),
        }
        out[arch] = {}
        for name, stages in configs.items():
            fn = retriever.search_fn(stages)
            dt = _t(fn, retriever.store.stores(), q, qm)
            _, ids = fn(retriever.store.stores(), q, qm)
            m = evaluate_ranking(np.asarray(ids), bench.qrels,
                                 ks=(5, 10, 100))
            qps = len(q) / dt
            out[arch][name] = {**m, "qps": qps}
            _emit(f"table2/{arch}/{name}", dt / len(q),
                  f"qps={qps:.1f};ndcg5={m['ndcg@5']:.3f};"
                  f"r100={m['recall@100']:.3f}")
    table["table2"] = out


def scope_scaling(table: dict):
    """Per-dataset vs union QPS for 1- and 2-stage (paper: 2x -> 4x)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.retrieval.engine import make_search_fn
    from repro.retrieval.store import build_store

    cfg = get_config("colpali")
    bench = make_benchmark(cfg, (160, 120, 90), (30, 30, 30), seed=3)
    store = build_store(cfg, jnp.asarray(bench.pages),
                        jnp.asarray(bench.token_types))
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    res = {}
    for scope in ("perds", "union"):
        if scope == "union":
            vecs, n = store.vectors, store.n_docs
            t1 = _t(make_search_fn(None, MST.one_stage(50), n), vecs, q, qm)
            t2 = _t(make_search_fn(None, MST.two_stage(128, 50), n),
                    vecs, q, qm)
            nq = len(q)
        else:
            # QPS over the actual per-split query counts: total queries
            # answered divided by total wall time across the 3 splits.
            t1 = t2 = 0.0
            nq = 0
            for ds in range(3):
                pages = np.where(bench.dataset_of_page == ds)[0]
                qs = np.where(bench.dataset_of_query == ds)[0]
                sub = {k: v[pages] for k, v in store.vectors.items()}
                n = len(pages)
                t1 += _t(make_search_fn(None, MST.one_stage(50), n),
                         sub, q[qs], qm[qs])
                t2 += _t(make_search_fn(None, MST.two_stage(128, 50), n),
                         sub, q[qs], qm[qs])
                nq += len(qs)
        res[scope] = {"qps_1stage": nq / t1, "qps_2stage": nq / t2}
        res[scope]["speedup"] = res[scope]["qps_2stage"] / \
            res[scope]["qps_1stage"]
        _emit(f"scope/{scope}", t2, f"speedup={res[scope]['speedup']:.2f}")
    table["scope_scaling"] = res


def eq1_cost_model(table: dict):
    from repro.core.maxsim import search_cost_madds
    rows = {}
    for dp in (1024, 34, 32, 13, 1):
        c = search_cost_madds(1, 10, 10_000, dp, 128)
        rows[dp] = c
        _emit(f"eq1/D={dp}", 0.0, f"madds={c};reduction={rows[1024]/c:.0f}x")
    table["eq1"] = rows


def pooling_ablation(table: dict):
    """conv1d vs gaussian vs triangular on the PatchMerger (colqwen)
    geometry — reproduces the §2.3.3 double-smoothing failure direction."""
    import dataclasses
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import evaluate_ranking, make_benchmark
    from repro.retrieval.engine import make_search_fn
    from repro.retrieval.store import build_store

    out = {}
    base = get_config("colqwen")
    bench = make_benchmark(base, (120, 100, 80), (30, 30, 30), seed=4)
    for smooth in ("gaussian", "triangular", "uniform", "none"):
        cfg = dataclasses.replace(base, smooth=smooth
                                  if smooth != "none" else "none")
        store = build_store(cfg, jnp.asarray(bench.pages),
                            jnp.asarray(bench.token_types))
        fn = make_search_fn(None, MST.two_stage(64, 10), store.n_docs)
        _, ids = fn(store.vectors, jnp.asarray(bench.queries),
                    jnp.asarray(bench.query_mask))
        m = evaluate_ranking(np.asarray(ids), bench.qrels, ks=(5, 10))
        out[smooth] = m
        _emit(f"pooling/{smooth}", 0.0, f"ndcg5={m['ndcg@5']:.3f}")
    table["pooling_ablation"] = out


def hygiene_ablation(table: dict):
    """Clean (visual-only) vs dirty (all tokens) 1-stage MaxSim (§2.1)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import evaluate_ranking, make_benchmark
    from repro.retrieval.engine import make_search_fn

    cfg = get_config("colpali")
    bench = make_benchmark(cfg, (120, 100, 80), (30, 30, 30), seed=5)
    pages = jnp.asarray(bench.pages)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    out = {}
    for mode in ("clean", "dirty"):
        if mode == "clean":
            from repro.retrieval.store import build_store
            store = build_store(cfg, pages, jnp.asarray(bench.token_types))
            vecs = store.vectors
            n = store.n_docs
        else:
            vecs = {"initial": pages.astype(jnp.bfloat16),
                    "initial_mask": jnp.ones(pages.shape[:2], bool)}
            n = pages.shape[0]
        fn = make_search_fn(None, MST.one_stage(10), n)
        _, ids = fn(vecs, q, qm)
        m = evaluate_ranking(np.asarray(ids), bench.qrels, ks=(5, 10))
        out[mode] = m
        _emit(f"hygiene/{mode}", 0.0, f"ndcg5={m['ndcg@5']:.3f}")
    table["hygiene"] = out


def kernel_micro(table: dict):
    import jax.numpy as jnp
    from repro.kernels.maxsim import maxsim_scores
    from repro.kernels.pooling import pool_pages_fused, pooling_matrix
    from repro.kernels.embed_bag import embed_bag
    from repro.configs import get_config
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(8, 16, 128)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(512, 64, 128)), jnp.float32)
    dt = _t(lambda: maxsim_scores(q, docs, impl="ref"))
    _emit("kernel/maxsim_ref_512x64", dt,
          f"gflops={(2*8*16*512*64*128)/dt/1e9:.1f}")
    cfg = get_config("colpali")
    x = jnp.asarray(rng.normal(size=(64, 1024, 128)), jnp.float32)
    m = jnp.ones((64, 1024), jnp.float32)
    pm = jnp.asarray(pooling_matrix(cfg))
    dt = _t(lambda: pool_pages_fused(x, m, pm, impl="ref"))
    _emit("kernel/pooling_ref_64pages", dt, "")
    table_arr = jnp.asarray(rng.normal(size=(100_000, 64)), jnp.float32)
    idx = jnp.asarray(rng.integers(0, 100_000, (4096, 8)), jnp.int32)
    dt = _t(lambda: embed_bag(table_arr, idx, impl="ref"))
    _emit("kernel/embed_bag_ref_4096x8", dt, "")
    table["kernel_micro"] = True


def kernel_vs_ref_scan(table: dict, quick: bool = False):
    """Scan-stage dispatch A/B: Pallas kernel vs jnp ref QPS on the same
    2-stage cascade, via the Retriever facade (§2.4 — the scan stage is the
    memory-roofline term; off-TPU the kernel runs interpreted, so the rows
    validate dispatch + parity rather than making a CPU throughput claim).
    Sizes are kept small: interpret-mode Pallas is Python-loop slow."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import build_store, quantize_store

    cfg = get_config("colpali")
    pages, queries = ((20, 16, 12), (4, 4, 4)) if quick else \
        ((40, 30, 20), (8, 8, 8))
    bench = make_benchmark(cfg, pages, queries, seed=6)
    store = build_store(cfg, jnp.asarray(bench.pages),
                        jnp.asarray(bench.token_types))
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    base = MST.two_stage(24, 10)
    chunk = 16
    retriever = Retriever(store)
    # quantise the vector the scan stage actually scores (mean_pooling for
    # the 2-stage cascade), or the int8 row silently measures bf16
    retriever_i8 = Retriever(quantize_store(store, names=(base[0].vector,)))
    variants = {
        "ref": (retriever, base),
        "ref_chunked": (retriever, MST.with_scan_policy(base, chunk=chunk)),
        "kernel": (retriever, MST.with_scan_policy(base, use_kernel=True)),
        "kernel_chunked": (retriever, MST.with_scan_policy(
            base, use_kernel=True, chunk=chunk)),
        "kernel_int8": (retriever_i8, MST.with_scan_policy(
            base, use_kernel=True, chunk=chunk)),
    }
    out = {}
    for name, (r, stages) in variants.items():
        fn = r.search_fn(stages)
        dt = _t(fn, r.store.stores(), q, qm)
        qps = len(q) / dt
        out[name] = {"qps": qps, "us_per_query": dt / len(q) * 1e6}
        _emit(f"scan/{name}", dt, f"qps={qps:.1f}")
    table["scan_dispatch"] = out


def rerank_kernel_vs_ref(table: dict, quick: bool = False):
    """Candidate-path A/B: the fused gather-rerank path + streamed scan
    top-k vs the reference path, end to end through the Retriever.

    - e2e cascade QPS, interleaved-min protocol (one call per variant per
      round, min over rounds — identical machine conditions for the A/B);
      off-TPU the fused rerank runs its blockwise jnp twin (the Pallas
      gather kernel compiles natively on TPU only), so the CPU rows are a
      real memory-bounding win, not an interpret-mode artifact;
    - parity asserted: the ref path is BITWISE the multistage oracle; the
      fused path returns the oracle ranking with tight score tolerance;
    - steady-state retraces asserted ZERO across the timed reps;
    - the fused path is asserted to have actually routed through
      ``maxsim_rerank`` (trace-counter delta — a silent fallback to the
      reference gather fails this bench, and CI);
    - predicted-vs-measured: the ``cascade_hbm_bytes`` roofline's fused
      speedup printed next to the measured one;
    - every run's QPS rows append to BENCH_candidate_path.json keyed by
      git sha — the perf trajectory stays machine-readable across PRs.
    """
    import functools
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.kernels import dispatch as DSP
    from repro.kernels.maxsim import ops as KOPS
    from repro.retrieval import tracing
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import build_store

    cfg = get_config("colpali")
    pages, queries = ((56, 40, 32), (4, 2, 2)) if quick else \
        ((96, 80, 80), (6, 6, 4))
    rounds = 5 if quick else 9
    bench = make_benchmark(cfg, pages, queries, seed=23)
    store = build_store(cfg, jnp.asarray(bench.pages),
                        jnp.asarray(bench.token_types))
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    # prefetch_k=64: the candidate set is large enough that the rerank
    # gather's working set dominates host noise (the paper's common
    # cutoffs rerank 100-256 candidates at production N)
    base = MST.two_stage(64, 10)
    # ref = the pre-PR default path, unchunked: bitwise the oracle
    ref_stages = base
    fused_stages = MST.with_rerank_policy(
        MST.with_scan_policy(base, chunk=32, scan_topk=True),
        rerank_kernel=True)
    r = Retriever(store)

    # ---- parity (before timing: the numbers must mean the same thing);
    # the oracle is jitted — eager XLA lowers the same contraction a ulp
    # apart, and the bitwise contract is between COMPILED programs
    oracle = jax.jit(functools.partial(MST.search, stages=base))
    so, io = oracle(store.vectors, q, q_mask=qm)
    so, io = np.asarray(so), np.asarray(io)
    s_ref, i_ref = r.search(q, qm, stages=ref_stages)
    np.testing.assert_array_equal(np.asarray(i_ref), io)
    np.testing.assert_array_equal(np.asarray(s_ref), so)   # bitwise
    before_fused = DSP.kernel_dispatch_count("maxsim_rerank")
    s_fus, i_fus = r.search(q, qm, stages=fused_stages)
    fused_traces = DSP.kernel_dispatch_count("maxsim_rerank") - before_fused
    np.testing.assert_array_equal(np.asarray(i_fus), io)
    np.testing.assert_allclose(np.asarray(s_fus), so, rtol=1e-4, atol=1e-4)
    assert fused_traces > 0, (
        "the fused-policy cascade never routed through maxsim_rerank — "
        "silent fallback to the reference gather")

    # ---- e2e QPS, interleaved min, zero steady-state retraces
    # (scan_topk = the streamed scan top-k alone, reference rerank — the
    # scan-topk table row; fused = both policies, the headline A/B)
    topk_stages = MST.with_scan_policy(base, chunk=32, scan_topk=True)
    fns = {"ref": (r.search_fn(ref_stages), ref_stages),
           "scan_topk": (r.search_fn(topk_stages), topk_stages),
           "fused": (r.search_fn(fused_stages), fused_stages)}
    stores = r.store.stores()
    for fn, _ in fns.values():
        _block(fn(stores, q, qm))              # warm
    warm = tracing.trace_count()
    dts = {name: [] for name in fns}
    # up to 2 measurement passes: on a contended host the first pass's
    # interleaved-min can still be skewed; re-measure once before
    # concluding the fused path lost (perf gates must not flake)
    for attempt in range(2):
        for _ in range(rounds):
            for name, (fn, _) in fns.items():
                t0 = time.time()
                _block(fn(stores, q, qm))
                dts[name].append(time.time() - t0)
        if np.min(dts["fused"]) < np.min(dts["ref"]):
            break
    retraces = tracing.trace_count() - warm
    out = {"n_docs": store.n_docs, "batch": int(q.shape[0]),
           "retraces": retraces, "fused_rerank_traces": fused_traces,
           "rerank_impl": DSP.resolve("maxsim_rerank", True)[0], "qps": {}}
    for name in fns:
        dt = float(np.min(dts[name]))
        out["qps"][name] = len(q) / dt
        _emit(f"candidate/e2e/{name}", dt / len(q),
              f"qps={len(q)/dt:.1f}")
    out["measured_speedup"] = out["qps"]["fused"] / out["qps"]["ref"]

    # ---- rerank stage micro A/B (the component the policy switches);
    # interleaved, with the same re-measure-once-before-failing pass as
    # the e2e ratio — perf gates must not flake on a contended host
    rng = np.random.default_rng(29)
    L = 64
    rows = jnp.asarray(rng.integers(0, store.n_docs, (len(q), L)), jnp.int32)
    docs = store.vectors["initial"]
    dm = store.vectors["initial_mask"].astype(jnp.float32)
    qmf = qm.astype(jnp.float32)
    micro_fns = {impl: functools.partial(KOPS.maxsim_rerank, impl=impl)
                 for impl in ("ref", "jnp")}
    micro_ts = {impl: [] for impl in micro_fns}
    for fn in micro_fns.values():
        _block(fn(q, docs, rows, qmf, dm))
    for attempt in range(2):
        for _ in range(rounds):
            for impl, fn in micro_fns.items():
                t0 = time.time()
                _block(fn(q, docs, rows, qmf, dm))
                micro_ts[impl].append(time.time() - t0)
        if np.min(micro_ts["jnp"]) < np.min(micro_ts["ref"]):
            break
    micro = {impl: float(np.min(ts)) for impl, ts in micro_ts.items()}
    for impl in micro:
        _emit(f"candidate/rerank_{impl}", micro[impl],
              f"cands_per_s={len(q)*L/micro[impl]:.0f}")
    out["rerank_micro_speedup"] = micro["ref"] / micro["jnp"]

    # ---- predicted-vs-measured (HBM-roofline byte model)
    try:
        from benchmarks.roofline import (UnknownDeviceError,
                                         candidate_path_roofline)
    except ImportError:
        from roofline import UnknownDeviceError, candidate_path_roofline
    seg = r.store.segments[0]
    try:
        pred = candidate_path_roofline(
            seg.capacity, int(q.shape[1]), int(q.shape[2]), base,
            store.dims(), store.vec_dims(), batch=int(q.shape[0]))
        predicted = f"{pred['speedup']:.2f}x"
        out["predicted_speedup"] = pred["speedup"]
    except UnknownDeviceError:
        predicted = "none (no peak table for this device)"
    _emit("candidate/speedup", 0.0,
          f"measured={out['measured_speedup']:.2f}x;"
          f"predicted={predicted};"
          f"rerank_micro={out['rerank_micro_speedup']:.2f}x")
    assert retraces == 0, (
        f"steady-state candidate-path reps retraced {retraces} times")
    # the rerank-stage micro ratio has a wide margin (1.7-1.9x on this
    # host) — a HARD gate; the e2e ratio's margin (~1.2x) can be eaten by
    # a contended runner, so it gates at a regression backstop and the
    # real value is reported + persisted for trend tracking
    assert out["rerank_micro_speedup"] > 1.0, (
        f"fused rerank stage lost to the reference gather: "
        f"{out['rerank_micro_speedup']:.2f}x")
    assert out["measured_speedup"] > 0.9, (
        f"fused candidate path regressed end to end: "
        f"{out['measured_speedup']:.2f}x")
    table["rerank_kernel_vs_ref"] = out
    _persist_candidate_path(out)


def _persist_candidate_path(out: dict) -> None:
    """Append this run's candidate-path QPS rows to
    BENCH_candidate_path.json (committed-ledger convention: see
    ``_persist_ledger``)."""
    _persist_ledger("BENCH_candidate_path.json",
                    {"qps": out["qps"],
                     "measured_speedup": out["measured_speedup"],
                     "predicted_speedup": out.get("predicted_speedup"),
                     "rerank_micro_speedup": out["rerank_micro_speedup"],
                     "rerank_impl": out["rerank_impl"],
                     "n_docs": out["n_docs"], "batch": out["batch"]})


def dynamic_corpus(table: dict, quick: bool = False):
    """Live-corpus serving: search QPS at 25/50/75/100% segment fill,
    steady-state upsert/delete latency, and the no-retrace contract
    (asserted — an ingestion-path regression that reintroduces retracing
    fails this bench, and therefore CI, outright)."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.retrieval import tracing
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import build_store

    cfg = get_config("colpali")
    cap = 64 if quick else 256
    batch = cap // 4
    bench = make_benchmark(cfg, (cap // 2, cap // 4, cap // 4),
                           (4, 4, 4) if quick else (10, 10, 10), seed=11)
    pages = jnp.asarray(bench.pages)
    tt = jnp.asarray(bench.token_types)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)

    def indexed(lo, hi):
        return build_store(cfg, pages[lo:hi], tt)

    r = Retriever(indexed(0, batch), capacity=cap)
    stages = MST.two_stage(min(24, batch), 10)
    fn = r.search_fn(stages)
    out = {"capacity": cap, "batch": batch, "fill_qps": {}}

    # warm-up: compile the search fn and the (batch-shaped) write/delete
    # executables once; everything after this line must re-dispatch
    fn(r.store.stores(), q, qm)
    r.delete([0])
    warm = tracing.trace_count()

    dt = _t(fn, r.store.stores(), q, qm)
    out["fill_qps"][25] = len(q) / dt
    _emit("dynamic/fill25", dt, f"qps={len(q)/dt:.1f}")
    up_times = []
    for step in range(1, 4):
        t0 = time.time()
        ids = r.upsert(indexed(step * batch, (step + 1) * batch))
        _block(r.store.stores())
        up_times.append(time.time() - t0)
        dt = _t(fn, r.store.stores(), q, qm)
        fill = 25 * (step + 1)
        out["fill_qps"][fill] = len(q) / dt
        _emit(f"dynamic/fill{fill}", dt, f"qps={len(q)/dt:.1f}")
    t0 = time.time()
    r.delete(ids[:1])
    _block(r.store.stores())
    del_time = time.time() - t0
    fn(r.store.stores(), q, qm)
    out["upsert_s"] = float(np.mean(up_times))
    out["delete_s"] = del_time
    out["retraces"] = tracing.trace_count() - warm
    _emit("dynamic/upsert", out["upsert_s"],
          f"pages_per_s={batch/out['upsert_s']:.0f}")
    _emit("dynamic/retrace", 0.0, f"count={out['retraces']}")
    assert out["retraces"] == 0, (
        f"steady-state mutation retraced {out['retraces']} times — "
        "the no-retrace contract is broken")
    table["dynamic_corpus"] = out


def ingest_throughput(table: dict, quick: bool = False):
    """Device-resident ingest pipeline, three measurements per
    power-of-two ingest batch bucket:

    - POOLING-STAGE dispatch A/B (pages/sec through the component
      ``use_kernel`` actually switches): the fused pooling operator vs
      the functional reference chain — ``kernel_vs_ref`` comes from here;
    - INDEX throughput (pages/sec through the whole fused hygiene ->
      pooling -> quantise jit): kernel vs ref x int8 on/off, as context
      (the shared hygiene/cast/write work dilutes the dispatch delta);
    - end-to-end INGEST (index + segment write): the pipeline vs the
      legacy host-driven ``build_store``+``upsert`` path. After one
      warm-up trace per bucket, a MIXED-size ingest sequence through the
      pipeline must cause zero retraces — asserted, so an ingest-path
      regression that reintroduces per-shape recompilation fails this
      bench (and CI). The legacy path's retrace count on the same mixed
      sizes is reported as the contrast (its write executables key on the
      exact block shape).
    """
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.kernels import dispatch as DSP
    from repro.kernels.pooling import ops as POPS
    from repro.retrieval import tracing
    from repro.retrieval.ingest import IngestPipeline
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.segments import bucket_capacity
    from repro.retrieval.store import build_store, quantize_store

    cfg = get_config("colpali")
    buckets = (16, 32) if quick else (16, 32, 64)
    # the fused operator targets index-time BULK batches (the paper's
    # indexing shape is 256 pages/step); measure its dispatch A/B in that
    # regime — tiny batches are write-/overhead-bound either way
    index_buckets = (64,) if quick else (64, 128)
    reps = 3 if quick else 5
    index_rounds = 11 if quick else 13
    stages = MST.two_stage(24, 10)
    bench = make_benchmark(cfg, (16, 8, 8) if quick else (24, 12, 12),
                           (4, 4, 4), seed=14)
    base = np.asarray(bench.pages)
    tt = jnp.asarray(bench.token_types)
    rng = np.random.default_rng(15)
    # odd sizes that land inside already-warmed buckets
    mixed = [max(1, b - 3) for b in buckets] + [buckets[-1] // 2 + 1]

    def pages_for(n):
        sel = rng.integers(0, len(base), size=n)
        return jnp.asarray(base[sel], jnp.float32)

    def timed(fn, b):
        dts = []
        for _ in range(reps):
            p = pages_for(b)
            t0 = time.time()
            jax.block_until_ready(fn(p))
            dts.append(time.time() - t0)
        return float(np.median(dts))           # robust to scheduler noise

    out = {"buckets": list(buckets), "index_pages_per_s": {},
           "ingest_pages_per_s": {},
           "pallas_pooling_available": DSP.available("pooling"),
           "pool_impl": DSP.resolve("pooling", True)[0]}
    # OBSERVE (not infer from config) that the kernel-mode pipeline's
    # pooling really routes to a fused operator: tracing its body must
    # bump the fused-pool trace counter. A regression that silently falls
    # back to the reference chain leaves the counter untouched — the CI
    # gate asserts on this
    kpipe = IngestPipeline.for_config(cfg, use_kernel=True)
    before_fused = DSP.kernel_dispatch_count("pooling")
    jax.eval_shape(
        lambda p, t: kpipe._index_arrays(p, t, None),
        jax.ShapeDtypeStruct((8, cfg.seq_len, cfg.out_dim), jnp.float32),
        jax.ShapeDtypeStruct((cfg.seq_len,), jnp.int32))
    out["kernel_fused_pool_traces"] = \
        DSP.kernel_dispatch_count("pooling") - before_fused
    out["kernel_pool_path"] = kpipe.pool_path

    # ---- section 1: pooling-stage dispatch A/B ----
    # timed INTERLEAVED (one call each per round, min over rounds) so the
    # A/B sees identical machine conditions — the noise-robust protocol
    # for this host's scheduler jitter
    import functools
    from repro.core.pooling import pool_pages_batch
    g, p2, _ = POPS.pooling_factors(cfg)
    p2 = jnp.asarray(p2)
    pool_fns = {
        "ref": jax.jit(lambda x, m: pool_pages_batch(cfg, x, m)[0]),
        "kernel": jax.jit(functools.partial(
            POPS.pool_pages_grouped, p2=p2, n_groups=g)),
    }
    out["pool_pages_per_s"] = {name: {} for name in pool_fns}
    for b in index_buckets:
        x = pages_for(b)[:, -cfg.n_patches:]
        m = jnp.ones((b, cfg.n_patches), jnp.float32)
        for fn in pool_fns.values():
            jax.block_until_ready(fn(x, m))    # warm
        dts = {name: [] for name in pool_fns}
        for _ in range(index_rounds):
            for name, fn in pool_fns.items():
                t0 = time.time()
                jax.block_until_ready(fn(x, m))
                dts[name].append(time.time() - t0)
        for name in pool_fns:
            dt = float(np.min(dts[name]))
            out["pool_pages_per_s"][name][b] = b / dt
            _emit(f"ingest/pool/{name}/b{b}", dt / b,
                  f"pages_per_s={b/dt:.0f}")

    # ---- section 1b: whole-index throughput, kernel vs ref x int8 ----
    pipes = {name: IngestPipeline.for_config(
        cfg, use_kernel=name.startswith("kernel"),
        quantize=("mean_pooling",) if name.endswith("-int8") else (),
        stages=stages if name.endswith("-int8") else None)
        for name in ("ref", "kernel", "ref-int8", "kernel-int8")}
    for b in index_buckets:
        for pipe in pipes.values():
            pipe.index(pages_for(b), tt)       # warm the bucket
        dts = {name: [] for name in pipes}
        for _ in range(index_rounds):
            for name, pipe in pipes.items():
                p = pages_for(b)
                t0 = time.time()
                jax.block_until_ready(pipe.index(p, tt).vectors)
                dts[name].append(time.time() - t0)
        for name in pipes:
            dt = float(np.min(dts[name]))
            out["index_pages_per_s"].setdefault(name, {})[b] = b / dt
            _emit(f"ingest/index/{name}/b{b}", dt / b,
                  f"pages_per_s={b/dt:.0f}")

    # ---- section 2: end-to-end ingest, pipeline vs legacy write path ----
    cap = bucket_capacity(
        (2 + reps) * sum(buckets) + sum(mixed) + buckets[-1] + 8)
    retrace_counts = {}
    for name in ("legacy", "pipeline"):
        pipe = (IngestPipeline.for_config(cfg, use_kernel=True)
                if name == "pipeline" else None)
        seed = (pipe.index(pages_for(4), tt) if pipe is not None
                else build_store(cfg, pages_for(4), tt))
        r = Retriever(seed, capacity=cap, ingest=pipe)

        def ingest(p):
            if pipe is not None:
                return r.ingest(p, tt)
            return r.upsert(build_store(cfg, p, tt))
        for b in buckets:                      # warm each bucket once
            ingest(pages_for(b))
        jax.block_until_ready(r.store.stores())
        warm = tracing.trace_count()
        res = {}
        for b in buckets:
            dt = timed(lambda p: (ingest(p), r.store.stores())[1], b)
            res[b] = b / dt
            _emit(f"ingest/write/{name}/b{b}", dt / b,
                  f"pages_per_s={b/dt:.0f}")
        for n in mixed:                        # mixed sizes, warmed buckets
            ingest(pages_for(n))
        jax.block_until_ready(r.store.stores())
        retrace_counts[name] = tracing.trace_count() - warm
        out["ingest_pages_per_s"][name] = res

    out["retraces"] = retrace_counts["pipeline"]
    out["legacy_retraces"] = retrace_counts["legacy"]
    out["kernel_vs_ref"] = {
        b: out["pool_pages_per_s"]["kernel"][b]
        / out["pool_pages_per_s"]["ref"][b] for b in index_buckets}
    out["pipeline_vs_legacy"] = {
        b: out["ingest_pages_per_s"]["pipeline"][b]
        / out["ingest_pages_per_s"]["legacy"][b] for b in buckets}
    _emit("ingest/retrace", 0.0,
          f"count={out['retraces']};legacy={out['legacy_retraces']}")
    assert out["retraces"] == 0, (
        f"steady-state pipeline ingestion retraced {out['retraces']} "
        "times across mixed batch sizes — the ingest no-retrace contract "
        "is broken")
    table["ingest_throughput"] = out


def serving_tail_latency(table: dict, quick: bool = False):
    """Ragged-traffic tail latency through the ServingFrontend: Poisson
    arrivals of single queries with mixed token counts, shape-bucketed
    padding + deadline micro-batching. Reports p50/p95/p99 latency and the
    ragged-traffic QPS vs the fixed-shape static QPS on the same corpus;
    asserts the steady-state query-shape retrace count is ZERO — a frontend
    regression that reintroduces per-shape recompilation fails this bench,
    and therefore CI, outright."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.launch.serve import _make_ragged_requests
    from repro.retrieval import tracing
    from repro.retrieval.frontend import ServingFrontend, replay_open_loop
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import build_store

    cfg = get_config("colpali")
    pages, queries, n_req, max_batch = \
        ((16, 16, 16), (4, 4, 4), 48, 8) if quick else \
        ((60, 50, 40), (10, 10, 10), 200, 16)
    bench = make_benchmark(cfg, pages, queries, seed=12)
    store = build_store(cfg, jnp.asarray(bench.pages),
                        jnp.asarray(bench.token_types))
    retriever = Retriever(store)
    stages = MST.two_stage(24, 10)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)

    # fixed-shape static reference: one [B, Q] block, raw slot ids
    fn = retriever.search_fn(stages)
    dt = _t(fn, retriever.store.stores(), q, qm)
    static_qps = len(q) / dt

    fe = ServingFrontend(retriever, stages, max_batch=max_batch,
                         max_q=bench.queries.shape[1], flush_ms=2.0)
    n_warm = fe.warm()
    rng = np.random.default_rng(21)
    reqs = _make_ragged_requests(bench, n_req, rng)
    rate = 0.8 * static_qps

    warm_traces = tracing.trace_count()
    served, wall = replay_open_loop(fe, reqs, rate, seed=22)
    retraces = tracing.trace_count() - warm_traces

    lat_ms = np.asarray([p.latency for p in served]) * 1e3
    qps = len(served) / wall
    p50, p95, p99 = (float(x) for x in
                     np.percentile(lat_ms, (50, 95, 99)))
    out = {"n_requests": n_req, "rate": rate, "buckets_warmed": n_warm,
           "p50_ms": p50, "p95_ms": p95, "p99_ms": p99, "qps": qps,
           "static_qps": static_qps, "qps_ratio": qps / static_qps,
           "dispatches": fe.stats["dispatches"],
           "rows_per_dispatch": fe.stats["rows_real"]
           / fe.stats["dispatches"],
           "retraces": retraces}
    _emit("serving/p50", p50 / 1e3, f"p95={p95:.2f}ms;p99={p99:.2f}ms")
    _emit("serving/qps", 1.0 / qps,
          f"qps={qps:.1f};static={static_qps:.1f};"
          f"ratio={qps/static_qps:.2f}")
    _emit("serving/retrace", 0.0, f"count={retraces}")
    assert retraces == 0, (
        f"ragged traffic retraced {retraces} times after bucket warm-up — "
        "the query-shape no-retrace contract is broken")
    table["serving_tail_latency"] = out


def mixed_tenant_tail_latency(table: dict, quick: bool = False):
    """Multi-tenant serving under a noisy neighbour: two tenants share one
    corpus (disjoint page ranges via tenant-stamped upserts); open-loop
    Poisson traffic where tenant 1 sends ~7x tenant 0's request rate, every
    request scoped with ``FilterSpec(tenant=...)``. Reports per-tenant
    p50/p99 and asserts three contracts outright (CI gates):

    - **filters are data** — steady-state retraces across the tenant-filter
      swaps are ZERO: both tenants' traffic (and the unscoped warm-up)
      re-dispatch the same bucket executables.
    - **isolation** — a tenant-scoped request only ever returns that
      tenant's page ids (filler is -1, never another tenant's id).
    - **fairness** — the quiet tenant's p99 is bounded by the flush
      deadline plus a few micro-batch service times (self-normalised to
      this host's measured dispatch cost), so a bursting tenant's backlog
      cannot starve it — the round-robin-flush contract, measured.

    Rows persist to BENCH_multi_tenant.json at the repo root by git sha."""
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.data.synthetic import make_benchmark
    from repro.launch.serve import _make_ragged_requests
    from repro.retrieval import tracing
    from repro.retrieval.frontend import ServingFrontend, replay_open_loop
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.segments import bucket_capacity
    from repro.retrieval.store import FilterSpec, build_store

    cfg = get_config("colpali")
    pages, queries, n_req, max_batch = \
        ((16, 16, 16), (4, 4, 4), 48, 8) if quick else \
        ((60, 50, 40), (10, 10, 10), 200, 16)
    bench = make_benchmark(cfg, pages, queries, seed=16)
    p = jnp.asarray(bench.pages)
    tt = jnp.asarray(bench.token_types)
    half = len(p) // 2
    # tenant 0 = the wrapped seed store (companions default to tenant 0),
    # tenant 1 = a stamped upsert into the same segment's headroom
    r = Retriever(build_store(cfg, p[:half], tt),
                  capacity=bucket_capacity(len(p) + 8))
    r.upsert(build_store(cfg, p[half:], tt), tenant=1)
    stages = MST.two_stage(24, 10)
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)

    # fixed-shape reference for the arrival rate (as serving_tail_latency)
    fn = r.search_fn(stages)
    dt = _t(fn, r.store.stores(), q, qm)
    static_qps = len(q) / dt

    flush_ms = 2.0
    fe = ServingFrontend(r, stages, max_batch=max_batch,
                         max_q=bench.queries.shape[1], flush_ms=flush_ms)
    fe.warm()

    # merged Poisson stream, thinned by tenant: ~7/8 of arrivals belong to
    # the bursting tenant, so at the merged rate the quiet tenant sees a
    # trickle while tenant 1 queues a backlog
    rng = np.random.default_rng(23)
    base_reqs = _make_ragged_requests(bench, n_req, rng)
    tenants = rng.integers(0, 8, size=n_req)     # 0 => quiet, else burst
    reqs = [(rq, rm, FilterSpec(tenant=0 if t == 0 else 1))
            for (rq, rm), t in zip(base_reqs, tenants)]

    warm_traces = tracing.trace_count()
    served, wall = replay_open_loop(fe, reqs, rate=static_qps, seed=24)
    retraces = tracing.trace_count() - warm_traces

    # isolation: a scoped request's ids live in its tenant's page range
    for (_, _, fs), pr in zip(reqs, served):
        ids = np.asarray(pr.ids)
        lo, hi = (0, half) if fs.tenant == 0 else (half, len(p))
        assert np.all((ids == -1) | ((ids >= lo) & (ids < hi))), (
            f"tenant {fs.tenant} request returned foreign page ids "
            f"{ids[(ids != -1) & ((ids < lo) | (ids >= hi))]}")

    lat = {t: np.asarray([pr.latency for (_, _, fs), pr
                          in zip(reqs, served) if fs.tenant == t]) * 1e3
           for t in (0, 1)}
    dispatch_ms = wall / max(fe.stats["dispatches"], 1) * 1e3
    out = {"n_requests": n_req, "rate": static_qps,
           "retraces": retraces, "dispatch_ms": dispatch_ms,
           "rejected": fe.stats["rejected"]}
    for t in (0, 1):
        p50, p99 = (float(x) for x in np.percentile(lat[t], (50, 99)))
        role = "quiet" if t == 0 else "burst"
        out[f"{role}_n"] = int(len(lat[t]))
        out[f"{role}_p50_ms"] = p50
        out[f"{role}_p99_ms"] = p99
        _emit(f"tenants/{role}/p50", p50 / 1e3,
              f"p99={p99:.2f}ms;n={len(lat[t])}")
    _emit("tenants/retrace", 0.0,
          f"count={retraces};dispatch_ms={dispatch_ms:.2f}")
    assert retraces == 0, (
        f"mixed-tenant traffic retraced {retraces} times after warm-up — "
        "a tenant/filter swap is recompiling; the filters-are-data "
        "contract is broken")
    # round-robin fairness: the quiet tenant waits at most the flush
    # deadline plus a couple of other queues' micro-batch turns. Budget 8
    # service times (vs the tens a FIFO starved behind the burst backlog
    # would take) so a contended host can't flake the gate — the bound
    # scales with the measured per-dispatch cost
    bound_ms = flush_ms + 8.0 * dispatch_ms
    assert out["quiet_p99_ms"] <= bound_ms, (
        f"quiet-tenant p99 {out['quiet_p99_ms']:.2f}ms exceeds the "
        f"fair-flush bound {bound_ms:.2f}ms — the bursting tenant is "
        "starving the quiet one")
    table["mixed_tenant_tail_latency"] = out
    _persist_multi_tenant(out)


def _persist_multi_tenant(out: dict) -> None:
    """Append this run's mixed-tenant rows to BENCH_multi_tenant.json
    (committed-ledger convention: see ``_persist_ledger``)."""
    _persist_ledger("BENCH_multi_tenant.json",
                    {k: out[k] for k in
                     ("quiet_p50_ms", "quiet_p99_ms", "burst_p50_ms",
                      "burst_p99_ms", "dispatch_ms", "retraces",
                      "n_requests", "rate")})


def routed_scan(table: dict, quick: bool = False):
    """Centroid-routed sublinear candidate generation vs the exhaustive
    scan (paper §3 "multi-stage search", PLAID-style routing):

    - N-ladder QPS curve, exhaustive vs routed, interleaved-min timing —
      the crossover where routing's K-centroid overhead pays for itself;
      routed must beat exhaustive at the largest N (asserted)
    - recall@10 vs the exhaustive oracle at the benchmarked n_probe
      (asserted >= 0.95) plus an n_probe sweep at the smallest N
    - BITWISE oracle parity at n_probe == n_clusters (asserted — routing
      with every cluster probed must be the exhaustive scan, not an
      approximation of it)
    - zero steady-state retraces across the timed loop (asserted)
    - observed dispatch routing of the ivf_route family (recorded)

    Rows persist to BENCH_routed_scan.json at the repo root by git sha."""
    import jax.numpy as jnp
    from repro.core import multistage as MST
    from repro.kernels import dispatch as DSP
    from repro.retrieval import tracing
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import VectorStore

    D, d, B, Q, topk = 4, 32, 4, 8, 10
    ladder = (4096, 16384, 65536) if quick else (10_000, 100_000, 1_000_000)
    rounds = 5 if quick else 3
    rng = np.random.default_rng(31)
    # clustered corpus: a mixture of generator centers, so the data HAS
    # the structure IVF exploits (uniform noise would make any routed
    # recall number meaningless — every cluster equally likely). Centers
    # scale with N so each holds >> topk docs — otherwise the tail of the
    # true top-k is arbitrary far-away docs and recall measures noise.
    def corpus(n):
        G = int(np.clip(n // 64, 64, 1024))
        centers = rng.standard_normal((G, d)).astype(np.float32)
        g = rng.integers(0, G, size=n)
        toks = centers[g][:, None, :] + 0.25 * rng.standard_normal(
            (n, D, d)).astype(np.float32)
        return toks.astype(np.float32), centers, g

    def queries(centers, g_of_doc):
        # each query aims at a random doc's generator center — its true
        # neighbours share that center, so exhaustive top-k is a real
        # target, not noise
        tgt = rng.integers(0, len(g_of_doc), size=B)
        qs = centers[g_of_doc[tgt]][:, None, :] + 0.25 * \
            rng.standard_normal((B, Q, d)).astype(np.float32)
        return jnp.asarray(qs)

    out = {"quick": quick, "topk": topk, "batch": B,
           "route_impl": DSP.resolve("ivf_route", True)[0],
           "ladder": []}
    for li, n in enumerate(ladder):
        toks, centers, g = corpus(n)
        k_c = 1 << max(2, int(round(np.log2(np.sqrt(n)))))
        n_probe = max(4, k_c // 16)
        r = Retriever(VectorStore({"mean_pooling": jnp.asarray(toks)}, n),
                      routing=k_c)
        q = queries(centers, g)
        qm = jnp.ones((B, Q), bool)
        ex = (MST.Stage("mean_pooling", topk),)
        rt = MST.with_routing_policy(ex, n_probe=n_probe, n_clusters=k_c)
        fn_ex, fn_rt = r.search_fn(ex), r.search_fn(rt)
        stores = r.store.stores()
        for fn in (fn_ex, fn_rt):
            _block(fn(stores, q, qm, None))          # compile + warm
        warm = tracing.trace_count()
        best = {"exhaustive": float("inf"), "routed": float("inf")}
        for _ in range(rounds):                       # interleaved-min A/B
            for name, fn in (("exhaustive", fn_ex), ("routed", fn_rt)):
                t0 = time.time()
                _block(fn(stores, q, qm, None))
                best[name] = min(best[name], time.time() - t0)
        retraces = tracing.trace_count() - warm
        assert retraces == 0, (
            f"routed/exhaustive timed loop retraced {retraces}x at N={n} — "
            "the routing companions leaked into a trace axis")

        def recall(probe):
            st = MST.with_routing_policy(ex, n_probe=probe, n_clusters=k_c)
            _, ids_p = r.search(q, qm, stages=st)
            return float(np.mean([
                len(set(a.tolist()) & set(b.tolist())) / topk
                for a, b in zip(np.asarray(ids_p), np.asarray(ids_ex))]))

        s_ex, ids_ex = r.search(q, qm, stages=ex)
        rec = recall(n_probe)
        assert rec >= 0.95, (
            f"routed recall@{topk} {rec:.3f} < 0.95 at N={n}, "
            f"n_probe={n_probe}/{k_c} — routing is dropping true hits")
        row = {"n_docs": n, "n_clusters": k_c, "n_probe": n_probe,
               "qps_exhaustive": B / best["exhaustive"],
               "qps_routed": B / best["routed"],
               "speedup": best["exhaustive"] / best["routed"],
               "recall_at_k": rec, "retraces": retraces}
        out["ladder"].append(row)
        _emit(f"routed_scan_n{n}", best["routed"],
              f"speedup={row['speedup']:.2f}x recall={rec:.3f}")
        if li == 0:
            # oracle parity: every cluster probed == the exhaustive scan,
            # bitwise — scores AND translated ids
            s_all, ids_all = r.search(
                q, qm, stages=MST.with_routing_policy(
                    ex, n_probe=k_c, n_clusters=k_c))
            assert np.array_equal(np.asarray(s_ex), np.asarray(s_all)), \
                "routed n_probe == n_clusters diverged from exhaustive"
            assert np.array_equal(ids_ex, ids_all)
            out["parity_exact"] = True
            sweep, probe = {}, 1
            while probe < k_c:
                sweep[str(probe)] = recall(probe)
                probe *= 4
            sweep[str(k_c)] = 1.0                     # parity, asserted
            out["n_probe_sweep"] = sweep
    last = out["ladder"][-1]
    assert last["qps_routed"] > last["qps_exhaustive"], (
        f"no crossover: routed {last['qps_routed']:.1f} QPS <= exhaustive "
        f"{last['qps_exhaustive']:.1f} QPS at N={last['n_docs']} — the "
        "routed read bill should win well before this corpus size")
    out["crossover_n"] = next(
        (row["n_docs"] for row in out["ladder"]
         if row["qps_routed"] > row["qps_exhaustive"]), None)
    out["route_dispatches"] = DSP.dispatch_count("ivf_route")
    table["routed_scan"] = out
    _persist_routed_scan(out)


def _persist_routed_scan(out: dict) -> None:
    """Append this run's routed-vs-exhaustive ladder to
    BENCH_routed_scan.json (committed-ledger convention: see
    ``_persist_ledger``)."""
    _persist_ledger("BENCH_routed_scan.json",
                    {"ladder": out["ladder"],
                     "crossover_n": out["crossover_n"],
                     "parity_exact": out.get("parity_exact", False),
                     "n_probe_sweep": out.get("n_probe_sweep", {}),
                     "route_impl": out["route_impl"],
                     "quick": out["quick"]})


def tiered_qps(table: dict, quick: bool = False):
    """Corpus beyond HBM (ROADMAP item 2): QPS through the tiered
    residency engine (``retrieval.tiering.TieredEngine``) at corpus sizes
    of 1x/2x/4x/8x a fixed HBM budget, under hit-rate-controlled traffic
    (80/95/99% of queries land on a hot set that fits in budget; cold
    queries force a host->device promote + an LRU demote), async-prefetch
    overlap vs synchronous fetch, interleaved-min A/B:

    - at 4x budget / 95% hit rate, overlap QPS >= 1.3x sync (asserted —
      the transfer roundtrip must actually hide under MaxSim compute)
    - tiered results BITWISE equal to fully-resident search over the
      identical trace, both overlap and sync (asserted)
    - zero steady-state retraces across every timed trace — residency is
      placement, never shape (asserted)
    - predicted-vs-measured vs the ``tiered_overlap_roofline`` transfer
      model and ``cascade_hbm_bytes(cold_rows=...)``'s freight bill

    The corpus carries the cascade's real freight asymmetry: a fat
    rerank-only "initial" slab that must MOVE on a tier swap but is only
    gathered at prefetch_k rows, over a thin "mean_pooling" scan — which
    is exactly why transfers are expensive relative to a scan and why
    hiding them pays. The host<->device link is EMULATED
    (``TieredEngine(link_bw=...)``, calibrated so a miss roundtrip costs
    ~10 scan dispatches): on the hosts this benchmark must gate on, a
    ``device_put`` aliases host memory (~free), so the native A/B would
    measure nothing — the pace rides on whichever thread performs the
    transfer, which is exactly the scheduling property under test. The
    ledger records the emulated rate next to the measured native one.

    Rows persist to BENCH_tiered.json at the repo root by git sha."""
    import jax.numpy as jnp
    from repro.core import multistage as MST
    from repro.retrieval import tracing
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import VectorStore
    try:
        from benchmarks import roofline as RF
    except ImportError:
        import roofline as RF

    d, D_scan, D_full = 64, 4, 96
    B, Q, prefetch_k, topk = 4, 8, 16, 4
    R = 256 if quick else 512       # rows per segment
    m_res = 6                       # segments the budget holds: hot set
    #                                 + in-use cold + in-flight prefetches
    ladder = (1, 2, 4, 8)           # corpus = x * budget
    hit_rates = (0.80, 0.95, 0.99)
    rounds = 2 if quick else 3
    PACE = 14                       # miss roundtrip ~= PACE scan calls
    st = MST.two_stage(prefetch_k, topk)

    def seg_arrays(seed, rows):
        r2 = np.random.default_rng(1000 + seed)
        full = r2.standard_normal((rows, D_full, d)).astype(np.float32)
        pooled = full.reshape(rows, D_scan, D_full // D_scan, d).mean(2)
        return {"initial": full, "mean_pooling": pooled}

    def corpus(n_segs, rows):
        r = Retriever(VectorStore(seg_arrays(0, rows), rows),
                      capacity=rows)
        for s in range(1, n_segs):
            r.store.add_pages(VectorStore(seg_arrays(s, rows), rows))
        assert len(r.store.segments) == n_segs
        return r

    # --- calibrate the emulated link to this host's dispatch floor -----
    qr = np.random.default_rng(9)
    q = jnp.asarray(qr.standard_normal((B, Q, d)).astype(np.float32))
    qm = jnp.ones((B, Q), bool)
    probe = corpus(2, R)
    seg_bytes = probe.store.segments[0].nbytes
    with probe.tiered(4 * seg_bytes) as eng:
        eng.search(q, qm, stages=st, scope=[0])          # compile
        t0 = time.time()
        for _ in range(8):
            eng.search(q, qm, stages=st, scope=[0])
        t_scan = (time.time() - t0) / 8
    link_bw = 2 * seg_bytes / (PACE * t_scan)
    del probe

    def make_trace(n_segs, hit, length, ci0=0):
        # deterministic hit-rate control: every round(1/(1-hit))-th query
        # visits the next cold segment (the cursor ``ci0`` carries across
        # repeat rounds so re-timing a trace keeps MISSING instead of
        # warming yesterday's cold set into the budget); the rest stay on
        # the hot segment. The budget (m_res) holds hot + in-use cold +
        # in-flight prefetches, so LRU never evicts the hot set and the
        # measured hit rate tracks the target instead of collapsing.
        period = max(2, int(round(1.0 / (1.0 - hit))))
        cold = list(range(1, n_segs)) or [0]
        trace, ci = [], ci0
        for t in range(length):
            if n_segs > 1 and t % period == period - 1:
                trace.append([cold[ci % len(cold)]])
                ci += 1
            else:
                trace.append([0])
        return trace, ci

    W = 16                       # prefetch lookahead (queries) — covers
    #                              the PACE-call roundtrip of one miss

    def run_trace(eng, trace, overlap):
        outs = []
        if overlap:
            for w in range(min(W, len(trace))):
                eng.prefetch(trace[w])
        t0 = time.time()
        for t, scope in enumerate(trace):
            if overlap and t + W < len(trace):
                eng.prefetch(trace[t + W])
            outs.append(eng.search(q, qm, stages=st, scope=scope,
                                   overlap=overlap))
        return time.time() - t0, outs

    def bitwise(a, b):
        return all(np.array_equal(sa, sb) and np.array_equal(ia, ib)
                   for (sa, ia), (sb, ib) in zip(a, b))

    out = {"quick": quick, "rows_per_segment": R, "m_res": m_res,
           "batch": B, "hit_rates": list(hit_rates),
           "seg_bytes": seg_bytes, "budget_bytes": m_res * seg_bytes,
           "link_bw": link_bw, "t_scan_s": t_scan,
           "native_h2d_bw": RF.measured_h2d_bw(), "ladder": []}
    budget = m_res * seg_bytes
    for x in ladder:
        n_segs = m_res * x
        r = corpus(n_segs, R)
        with r.tiered(budget, link_bw=link_bw) as eng:
            # warm: compile scan/rerank/merge on a hot and a cold scope
            eng.search(q, qm, stages=st, scope=[0])
            eng.search(q, qm, stages=st, scope=[n_segs - 1])
            warm = tracing.trace_count()
            for hit in hit_rates:
                period = max(2, int(round(1.0 / (1.0 - hit))))
                T = max(80 if quick else 160, 4 * period)
                best = {"overlap": float("inf"), "sync": float("inf")}
                sync_misses, sync_q, ci = 0, 0, 0
                for _ in range(rounds):              # interleaved-min A/B
                    # every timed run gets a FRESH cold cursor: replaying
                    # one trace would warm its cold set into the budget
                    # and the second mode would measure pure hits.
                    # Segments are homogeneous, so fresh traces cost the
                    # same; results parity is asserted against the
                    # fully-resident oracle below on a shared trace.
                    for mode, ov in (("overlap", True), ("sync", False)):
                        trace, ci = make_trace(n_segs, hit, T, ci)
                        h0 = dict(eng.stats)
                        dt, _o = run_trace(eng, trace, ov)
                        best[mode] = min(best[mode], dt)
                        if mode == "sync":
                            # query-level hit rate, and only from the
                            # un-prefetched mode (a prefetched miss is
                            # resident by acquire time and counts as a
                            # hit; the rerank stage re-acquires the scan
                            # stage's segment, which is always a hit)
                            sync_misses += (eng.stats["misses"]
                                            - h0["misses"])
                            sync_q += len(trace)
                row = {"corpus_x": x, "n_segments": n_segs,
                       "hit_target": hit,
                       "hit_measured": 1.0 - sync_misses / max(sync_q, 1),
                       "qps_overlap": T * B / best["overlap"],
                       "qps_sync": T * B / best["sync"],
                       "speedup": best["sync"] / best["overlap"]}
                out["ladder"].append(row)
                _emit(f"tiered_qps_{x}x_h{int(hit*100)}",
                      best["overlap"] / T,
                      f"speedup={row['speedup']:.2f}x "
                      f"hit={row['hit_measured']:.2f}")
            retraces = tracing.trace_count() - warm
            assert retraces == 0, (
                f"tiered timed loops retraced {retraces}x at {x}x budget "
                "— residency leaked into a trace axis")
            out["retraces"] = retraces
        # fully-resident oracle over the SAME trace (budget covers the
        # whole corpus, so after the first pass every access hits) —
        # tiered residency must be bitwise invisible to results
        with r.tiered((n_segs + 1) * seg_bytes) as ref:
            trace, _ = make_trace(n_segs, 0.95, 80)
            _, ref_outs = run_trace(ref, trace, False)
            assert not ref.stats["demotions"], "oracle engine evicted"
        with r.tiered(budget) as eng:
            for ov in (True, False):
                _, got = run_trace(eng, trace, ov)
                assert bitwise(got, ref_outs), (
                    f"tiered (overlap={ov}) diverged from fully-resident "
                    f"search at {x}x budget — eviction corrupted results")
        out["parity_resident"] = True
        del r

    # --- predicted-vs-measured at the gate point (4x / 95%) ------------
    gate = next(row for row in out["ladder"]
                if row["corpus_x"] == 4 and row["hit_target"] == 0.95)
    out["gate"] = dict(gate)
    dims = {"initial": D_full, "mean_pooling": D_scan}
    hbm = MST.cascade_hbm_bytes(
        R, Q, d, st, dims, batch=B, cold_rows=R,
        bytes_per_coord={"initial": 4, "mean_pooling": 4})
    xfer_pred = next(s["total_bytes"] for s in hbm["stages"]
                     if s["kind"] == "tier-transfer")
    scan_bytes = next(s["total_bytes"] for s in hbm["stages"]
                      if s["kind"] == "scan")
    flops = 2.0 * B * Q * R * D_scan * d
    pred = RF.tiered_overlap_roofline(scan_bytes, flops, 2 * seg_bytes,
                                      0.95, h2d_bw=link_bw,
                                      t_scan_s=t_scan)
    out["roofline"] = {"xfer_bytes_pred": xfer_pred,
                       "seg_bytes_measured": seg_bytes,
                       "speedup_pred": pred["speedup"],
                       "speedup_measured": gate["speedup"],
                       "link_bw": link_bw}
    print(f"tiered roofline @4x/95%: predicted speedup "
          f"{pred['speedup']:.2f}x vs measured {gate['speedup']:.2f}x; "
          f"freight {xfer_pred/1e6:.1f}MB modelled vs "
          f"{seg_bytes/1e6:.1f}MB/segment measured "
          f"(emulated link {link_bw/1e9:.2f} GB/s, native h2d "
          f"{out['native_h2d_bw']/1e9:.1f} GB/s)")
    assert gate["speedup"] >= 1.3, (
        f"overlap speedup {gate['speedup']:.2f}x < 1.3x at 4x budget / "
        "95% hit — prefetch is not hiding the transfer roundtrip")
    table["tiered_qps"] = out
    _persist_tiered(out)


def _persist_tiered(out: dict) -> None:
    """Append this run's tiered residency ladder to BENCH_tiered.json
    (committed-ledger convention: see ``_persist_ledger``)."""
    _persist_ledger("BENCH_tiered.json",
                    {"ladder": out["ladder"], "gate": out["gate"],
                     "parity_resident": out["parity_resident"],
                     "retraces": out["retraces"],
                     "roofline": out["roofline"],
                     "budget_bytes": out["budget_bytes"],
                     "rows_per_segment": out["rows_per_segment"],
                     "quick": out["quick"]})


def chaos_serving(table: dict, quick: bool = False):
    """Serving under failure (ROADMAP item 3): open-loop traffic over a
    tiered corpus at 4x the HBM budget while the deterministic fault
    injector (``retrieval.faults``) turns the screws, asserting the
    exact-or-flagged serving contract end to end:

    - fault ladder 0% / 1% / 5% injected transient transfer failures
      (plus deadline pressure from injected slow transfers at the faulty
      rungs): availability >= 99.9% of requests complete at EVERY rung
      (transient failures are retried, never surfaced), every
      non-degraded result is BITWISE the fully-resident oracle, and
      every degraded result is flagged with its skip count (asserted)
    - p99 latency at the 5% rung bounded by 3x the clean rung's p99
      + 50ms — fault recovery degrades the tail, it must not unbound it
      (asserted)
    - one worker-kill rung: the background tiering worker thread is
      killed mid-traffic; the supervisor restarts it
      (``worker_restarts >= 1``) and results stay bitwise (asserted)
    - zero steady-state retraces across ALL rungs — retries, restarts
      and degraded folds re-dispatch warmed executables (asserted)
    - one corrupt-snapshot restore attempt: a bit flipped under a stored
      array fails restore LOUDLY (``CheckpointCorrupt`` naming the
      ``seg<i>/<key>`` leaf) while the previous step restores bitwise
      (asserted)

    Every fault is seeded and counter-keyed (no wall-clock randomness),
    so the rung outcomes are reproducible run to run. Rows persist to
    BENCH_chaos.json at the repo root by git sha (CI gates on them)."""
    import tempfile

    import jax.numpy as jnp
    from repro.core import multistage as MST
    from repro.retrieval import faults as FLT
    from repro.retrieval import tiering as TIER
    from repro.retrieval import tracing
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import VectorStore
    from repro.training import checkpoint as CKPT

    d, D_scan, D_full = 64, 4, 96
    B, Q, prefetch_k, topk = 4, 8, 16, 4
    R = 128 if quick else 256            # rows per segment
    m_res = 4                            # segments the budget holds
    n_segs = 4 * m_res                   # corpus = 4x budget
    T = 30 if quick else 60              # requests per rung
    PACE = 6                             # promote ~= PACE/2 scan calls
    AVAIL_GATE = 0.999
    st = MST.two_stage(prefetch_k, topk)

    def seg_arrays(seed, rows):
        r2 = np.random.default_rng(3000 + seed)
        full = r2.standard_normal((rows, D_full, d)).astype(np.float32)
        pooled = full.reshape(rows, D_scan, D_full // D_scan, d).mean(2)
        return {"initial": full, "mean_pooling": pooled}

    r = Retriever(VectorStore(seg_arrays(0, R), R), capacity=R)
    for s in range(1, n_segs):
        r.store.add_pages(VectorStore(seg_arrays(s, R), R))
    seg_bytes = r.store.segments[0].nbytes
    budget = m_res * seg_bytes

    qr = np.random.default_rng(11)
    q = jnp.asarray(qr.standard_normal((B, Q, d)).astype(np.float32))
    qm = jnp.ones((B, Q), bool)

    # request stream: every request scans a 3-segment scope — the always-
    # hot segment 0 plus a rotating cold pair, so steady state promotes 2
    # segments per request (transfer faults get plenty of ops to land on)
    # and the deadline has a real second promotion to skip under pressure
    pairs = [(a, a + 1) for a in range(1, n_segs - 1, 2)]
    scopes = [(0, a, b) for a, b in pairs]

    def oracle_outs():
        with r.tiered((n_segs + 1) * seg_bytes) as ref:
            outs = {sc: ref.search(q, qm, stages=st, scope=sc)
                    for sc in scopes}
            assert not ref.stats["demotions"], "oracle engine evicted"
            return {sc: (np.asarray(o.scores), np.asarray(o.ids))
                    for sc, o in outs.items()}

    def bitwise(res, ref):
        return (np.array_equal(np.asarray(res.scores), ref[0])
                and np.array_equal(np.asarray(res.ids), ref[1]))

    ref_outs = oracle_outs()
    out = {"quick": quick, "rows_per_segment": R, "n_segments": n_segs,
           "budget_bytes": budget, "requests_per_rung": T, "rungs": []}

    with r.tiered(budget, link_bw=None) as probe:
        probe.search(q, qm, stages=st, scope=scopes[0])     # compile
        t0 = time.time()
        for _ in range(8):
            probe.search(q, qm, stages=st, scope=scopes[0])
        t_scan3 = (time.time() - t0) / 8
    t_scan = t_scan3 / len(scopes[0])
    link_bw = 2 * seg_bytes / (PACE * t_scan)
    t_promote = seg_bytes / link_bw
    # generous enough that BOTH steady-state promotions fit; an injected
    # slow transfer (2.5x a promote) blows it and degrades the request
    deadline_ms = (2.2 * t_promote + 12 * t_scan) * 1e3
    out.update(link_bw=link_bw, t_scan_s=t_scan, deadline_ms=deadline_ms)

    with r.tiered(budget, link_bw=link_bw) as eng:
        # warm every executable the rungs dispatch: the 3-scope cascade,
        # the degraded fold, and a forced skip (same executables, fewer
        # fold steps — warmth is about shapes, not visit counts)
        eng.search(q, qm, stages=st, scope=scopes[0])
        eng.search(q, qm, stages=st, scope=scopes[1],
                   deadline_ms=deadline_ms)
        eng.search(q, qm, stages=st, scope=scopes[2], deadline_ms=1e-3)
        warm = tracing.trace_count()

        def run_rung(plan, use_deadline=True, overlap=False, W=2):
            inj = eng.arm(plan)
            h0 = dict(eng.stats)
            lat, completed, failed, degraded, skips = [], 0, 0, 0, 0
            # offered ~= fault-free service rate (2 promotes + 3 scans +
            # rerank), so backlog — and thus the tail — is what FAULT
            # recovery adds, not a load mismatch baked into the schedule
            period = 2 * t_promote + 8 * t_scan
            start = time.monotonic()
            for t in range(T):
                sc = scopes[t % len(scopes)]
                sched = start + t * period
                now = time.monotonic()
                if now < sched:                 # open-loop: arrivals are
                    time.sleep(sched - now)     # scheduled, not gated on
                if overlap:                     # the previous completion
                    eng.prefetch(scopes[(t + W) % len(scopes)])
                try:
                    res = eng.search(
                        q, qm, stages=st, scope=sc,
                        deadline_ms=deadline_ms if use_deadline else None,
                        overlap=overlap)
                except Exception as e:          # injected-fault fallout
                    failed += 1
                    lat.append(time.monotonic() - sched)
                    print(f"chaos: request {t} failed: {e!r}")
                    continue
                lat.append(time.monotonic() - sched)
                completed += 1
                if res.degraded:
                    degraded += 1
                    skips += res.skipped_segments
                else:
                    assert bitwise(res, ref_outs[sc]), (
                        "non-degraded result diverged from the fully-"
                        f"resident oracle on scope {sc} — the exact-or-"
                        "flagged contract is broken")
            eng.arm(None)
            delta = {k: eng.stats[k] - h0[k] for k in
                     ("retries", "transfer_errors", "worker_restarts",
                      "oom_evictions", "deadline_skips", "degraded")}
            return {"completed": completed, "failed": failed,
                    "availability": completed / T, "degraded": degraded,
                    "skipped_segments": skips,
                    "p50_ms": float(np.percentile(lat, 50) * 1e3),
                    "p99_ms": float(np.percentile(lat, 99) * 1e3),
                    "injected": inj.counts() if inj else {},
                    "stats": delta}

        # --- the fault ladder ------------------------------------------
        for rate in (0.0, 0.01, 0.05):
            plan = None if rate == 0.0 else FLT.FaultPlan(
                seed=23, transfer_fail_rate=rate, transfer_fail_burst=1,
                slow_transfer_rate=0.25, slow_transfer_s=2.5 * t_promote)
            rung = run_rung(plan)
            rung["fail_rate"] = rate
            out["rungs"].append(rung)
            _emit(f"chaos_fail_{int(rate*100)}pct",
                  rung["p99_ms"] / 1e3,
                  f"avail={rung['availability']:.4f} "
                  f"degraded={rung['degraded']}/{T} "
                  f"retries={rung['stats']['retries']}")
            assert rung["availability"] >= AVAIL_GATE, (
                f"availability {rung['availability']:.4f} < {AVAIL_GATE} "
                f"at {rate:.0%} transfer-failure rate — transient faults "
                "are leaking out of the retry envelope")

        p99_clean = out["rungs"][0]["p99_ms"]
        p99_worst = out["rungs"][-1]["p99_ms"]
        assert p99_worst <= 3 * p99_clean + 50.0, (
            f"p99 {p99_worst:.1f}ms at the 5% rung vs {p99_clean:.1f}ms "
            "clean — fault recovery is unbounding the tail")

        # --- worker-kill rung ------------------------------------------
        kill = run_rung(FLT.FaultPlan(seed=23, kill_worker_at=(1, 5)),
                        use_deadline=False, overlap=True)
        out["worker_kill"] = kill
        _emit("chaos_worker_kill", kill["p99_ms"] / 1e3,
              f"restarts={kill['stats']['worker_restarts']} "
              f"avail={kill['availability']:.4f}")
        assert kill["stats"]["worker_restarts"] >= 1, (
            "the worker-kill rung never killed the worker — the "
            "supervisor path went unexercised")
        assert kill["availability"] >= AVAIL_GATE and not kill["degraded"], (
            "worker death leaked into served results — the supervisor "
            "must make restarts invisible")

        retraces = tracing.trace_count() - warm
        assert retraces == 0, (
            f"chaos rungs retraced {retraces}x — fault recovery leaked "
            "into a trace axis")
        out["retraces"] = retraces

    # --- corrupt-snapshot restore attempt ------------------------------
    with tempfile.TemporaryDirectory() as td:
        TIER.snapshot(r.store, td, step=1)
        TIER.snapshot(r.store, td, step=2, faults=FLT.FaultPlan(
            snapshot_bitflip_leaf=2))
        try:
            TIER.restore_store(td)               # latest = the bad step
            raise AssertionError(
                "restore of a bit-flipped snapshot succeeded silently")
        except CKPT.CheckpointCorrupt as e:
            assert "seg" in str(e), f"corrupt array not named: {e}"
            out["corrupt_named"] = str(e).split("'")[1]
        prev = TIER.restore_store(td, step=1)    # previous step: bitwise
        for si, seg in enumerate(r.store.segments):
            for k, v in seg.vectors.items():
                assert np.array_equal(np.asarray(prev.segments[si].
                                                 vectors[k]),
                                      np.asarray(v)), (
                    f"previous-step restore diverged at seg{si}/{k}")
        out["prev_step_bitwise"] = True
    _emit("chaos_snapshot", 0.0,
          f"corrupt_named={out['corrupt_named']} prev_step_bitwise=True")

    table["chaos_serving"] = out
    _persist_ledger("BENCH_chaos.json", out)


# named suites for --suite: subsets a CI job or a dev loop can run
# without paying for the whole harness (names match the fns above)
SUITES = {
    "tables": ("table2_quality_qps", "scope_scaling", "eq1_cost_model",
               "pooling_ablation", "hygiene_ablation"),
    "kernels": ("kernel_micro", "kernel_vs_ref_scan"),
    "candidate": ("rerank_kernel_vs_ref",),
    "serving": ("dynamic_corpus", "serving_tail_latency",
                "mixed_tenant_tail_latency", "ingest_throughput"),
    "routed": ("routed_scan",),
    "tiered": ("tiered_qps",),
    "chaos": ("chaos_serving",),
}


def main() -> None:
    import argparse
    import inspect
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI smoke run: small sizes, core tables only")
    ap.add_argument("--suite", action="append", choices=sorted(SUITES),
                    help="run only the named suite(s) (repeatable); "
                         "composes with --quick; default is everything")
    args = ap.parse_args()
    from repro.launch.runtime import device_line, setup_compile_cache
    setup_compile_cache()
    print(device_line(), flush=True)
    os.makedirs(RESULTS, exist_ok=True)
    table: dict = {}
    print("name,us_per_call,derived")
    if args.suite:
        names = [n for s in args.suite for n in SUITES[s]]
    elif args.quick:
        names = ["eq1_cost_model", "kernel_vs_ref_scan",
                 "rerank_kernel_vs_ref", "routed_scan", "tiered_qps",
                 "chaos_serving", "dynamic_corpus",
                 "serving_tail_latency", "mixed_tenant_tail_latency",
                 "ingest_throughput", "kernel_micro"]
    else:
        names = ["table2_quality_qps", "scope_scaling", "eq1_cost_model",
                 "pooling_ablation", "hygiene_ablation", "kernel_micro",
                 "kernel_vs_ref_scan", "rerank_kernel_vs_ref",
                 "routed_scan", "tiered_qps", "chaos_serving",
                 "dynamic_corpus", "serving_tail_latency",
                 "mixed_tenant_tail_latency", "ingest_throughput"]
    from repro.kernels import dispatch as DSP
    for name in names:
        # dispatch counters are per-process; without a reset a counter
        # bumped by one benchmark could satisfy a later --suite run's
        # observed-routing gate (per-benchmark deltas stay correct, and
        # absolute reads like routed_scan's route_dispatches become
        # clean per-run counts)
        DSP.reset_counts()
        fn = globals()[name]
        if args.quick and "quick" in inspect.signature(fn).parameters:
            fn(table, quick=True)
        else:
            fn(table)
    stem = "paper_tables"
    if args.suite:
        stem += "_" + "_".join(args.suite)
    name = f"{stem}_quick.json" if args.quick else f"{stem}.json"
    with open(os.path.join(RESULTS, name), "w") as f:
        json.dump(table, f, indent=1, default=float)
    print(f"\nwrote {os.path.join(RESULTS, name)}")


if __name__ == "__main__":
    main()
