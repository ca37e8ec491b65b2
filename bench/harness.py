"""One run of one cell: set-up, the measured window, the check.

Set-up builds the system under test exactly as a deployment would: raw
pages made on the device from the seed, ingested through
``IngestPipeline`` (hygiene, the Pallas pooling kernel, the segment write)
into a ``Retriever``, and served by a ``ServingFrontend`` over the
kernel-routed two-stage cascade (Pallas scan over the pooled vectors,
Pallas gather-rerank over the full-resolution ones). It warms the buckets
the cell's mix names and no others. A cell of more than one chip is the
deployment doc-sharded over the first ``chips`` devices: the store is laid
out on a one-axis mesh through the program's own ``mesh=``, and the
configuration's ``pages`` is the whole corpus over those chips.

The window drives that frontend with the mix's generator for ``seconds``.
Afterwards the metric readers (``bench/metrics``) turn what the window
recorded into numbers, the program's state is freed, and a sample of the
answers served in the window, drawn from the seed with the longest queries
in it, is compared with the plain reference (``bench.reference``).
"""
from __future__ import annotations

import gc
import json
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from bench import corpus, manifest, reference, trace_reduce

BENCH_DIR = Path(__file__).resolve().parent
TRACE_DIR = BENCH_DIR / "out" / "trace"
KERNEL_FAMILIES = ("maxsim_scan", "maxsim_rerank", "pooling")
LONGEST_IN_SAMPLE = 16


def peaks(kind: str) -> dict:
    """The published peaks of device kind ``kind`` (``bench/peaks.json``);
    a kind without a row is an error, never a default."""
    with open(BENCH_DIR / "peaks.json") as f:
        table = json.load(f)
    if kind not in table["devices"]:
        raise LookupError(f"no published peaks for device kind {kind!r} in "
                          f"bench/peaks.json (known: "
                          f"{sorted(table['devices'])})")
    return table["devices"][kind]


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclass
class Run:
    """What the window recorded; the metric readers read this."""
    cell: manifest.Cell
    setup_s: float
    t0: float
    t1: float
    requests: list                # traffic.loop.Request, sent in the window
    counters: dict                # frontend stats, window delta
    buckets: list                 # (B, Q) of each dispatch in the window
    shapes: dict                  # corpus and cascade sizes
    peaks: dict
    kernels_ran: frozenset = frozenset()   # families on their kernel
    trace: trace_reduce.Summary | None = None
    notes: list = field(default_factory=list)

    def note(self, msg: str) -> None:
        self.notes.append(msg)

    def latencies_ms(self) -> np.ndarray:
        """Arrival-to-answer of every request sent in the window; a failed
        or unanswered request counts as infinitely late."""
        out = []
        for r in self.requests:
            h = r.handle
            ok = h.done() and h.error is None
            out.append((h.t_done - r.t_sched) * 1e3 if ok else np.inf)
        return np.asarray(out, float)


def doc_mesh(devices: list):
    """A one-axis mesh over ``devices`` to doc-shard the store on; None for
    one device, where a mesh would put the program on its ``shard_map``
    body instead of the one-chip path a deployment of one chip runs."""
    if len(devices) == 1:
        return None
    return jax.make_mesh((len(devices),), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,),
                         devices=devices)


def fullest(devices: list, key: str) -> int:
    """The largest ``memory_stats()[key]`` over ``devices``: the fullest
    chip's reading (0 where the backend keeps no statistics)."""
    return max(int((d.memory_stats() or {}).get(key, 0)) for d in devices)


class Served:
    """The system under test for one seed: retriever, frontend, queries."""

    def __init__(self, cell: manifest.Cell, seed: int, quantize=()):
        from repro.configs import get_config
        from repro.core import multistage as MST
        from repro.retrieval.ingest import IngestPipeline
        from repro.retrieval.retriever import Retriever

        cfg, mix = cell.config, cell.traffic
        geo = cfg["geometry"]
        rcfg = get_config(cfg["repro_config"])
        _check_geometry(geo, rcfg)
        casc = cfg["cascade"]
        self.stages = MST.with_rerank_policy(
            MST.with_scan_policy(MST.two_stage(casc["prefetch_k"],
                                               casc["top_k"]),
                                 use_kernel=True), rerank_kernel=True)
        # quantized names keep no float copy: with the scan stage alone as
        # the pipeline's cascade, the rerank reads int8 codes too
        pipe = IngestPipeline.for_config(
            rcfg, store_dtype=cfg["store_dtype"], use_kernel=True,
            quantize=tuple(quantize),
            stages=self.stages[:1] if quantize else None)
        tt = corpus.token_types(geo)
        topic_vecs = corpus.topics(seed, cfg["topics"], geo["dim"])
        n, batch = cfg["pages"], cfg["ingest_batch"]
        first = corpus.page_batch(geo, seed, 0, topic_vecs, batch)
        self.devices = jax.devices()[:cell.chips]    # the cell's chips
        self.retriever = Retriever(pipe.index(first, tt), capacity=n,
                                   ingest=pipe, mesh=doc_mesh(self.devices))
        del first
        for b in range(1, n // batch):
            self.retriever.ingest(
                corpus.page_batch(geo, seed, b, topic_vecs, batch), tt)
        jax.block_until_ready(self.retriever.store.stores())
        store = self.retriever.store
        if self.retriever.n_docs != n or len(store.segments) != 1:
            raise RuntimeError(f"indexed {self.retriever.n_docs} pages in "
                               f"{len(store.segments)} segments, expected "
                               f"{n} in one")
        self.fe = self.retriever.frontend(self.stages, **cfg["frontend"])
        qc = mix["queries"]
        self.queries, self.lens = corpus.query_pool(
            seed, topic_vecs, qc["pool"], qc["min_tokens"], qc["max_tokens"])

    def warm(self, buckets) -> int:
        """Compile (or load from the cache) the cascade for ``buckets``:
        ``"all"`` for every bucket of the frontend, else a list of
        [B, Q]. Each is warmed by a real search through the frontend."""
        if buckets == "all":
            return self.fe.warm()
        for b, q in buckets:
            take = np.arange(b) % len(self.queries)
            qm = np.arange(q)[None, :] < np.minimum(self.lens[take], q)[:,
                                                                        None]
            self.fe.search(self.queries[take, :q], qm)
        return len(buckets)


def _check_geometry(geo: dict, rcfg) -> None:
    """The configuration file's sizes must be the program's for that
    encoder: the reference reads the file, the program its own config."""
    have = {"n_special": rcfg.n_special, "n_patches": rcfg.n_patches,
            "dim": rcfg.out_dim, "n_pooled": rcfg.n_pooled}
    bad = {k: (geo[k], v) for k, v in have.items() if geo[k] != v}
    if bad:
        raise ValueError(f"configuration geometry disagrees with the "
                         f"program's {rcfg.name!r} config: {bad}")


def _window_buckets(fe, requests, lens, t0, t1) -> list:
    """(B, Q) of each dispatch answered in [t0, t1]: a flush stamps one
    ``t_done`` on its whole cohort."""
    cohorts: dict = {}
    for r in requests:
        h = r.handle
        if h.done() and h.error is None and t0 <= h.t_done <= t1:
            rows, q = cohorts.get(h.t_done, (0, 0))
            cohorts[h.t_done] = (rows + 1, max(q, int(lens[r.query])))
    return [fe.bucket_for(rows, q) for rows, q in cohorts.values()]


class _CompileCounter:
    """Counts XLA backend compiles inside a ``with`` block."""

    def __init__(self):
        self.n = 0

    def _event(self, event: str, duration: float, **_) -> None:
        if "backend_compile" in event:
            self.n += 1

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._event)


def _dispatch_counts() -> dict:
    """{family: {impl: traces so far}} from the kernel dispatch registry."""
    from repro.kernels import dispatch as DSP
    return {f: {i: DSP.dispatch_count(f, i) for i in ("pallas", "jnp", "ref")}
            for f in KERNEL_FAMILIES}


def _kernel_impls(family: str) -> set:
    """The impls that count as a family's kernel: on a TPU the native
    Pallas kernel alone, off it (the tests) the registry's kernel-path
    impls."""
    from repro.kernels import dispatch as DSP
    return {"pallas"} if not DSP.default_interpret() \
        else set(DSP.get(family).kernel_impls)


def _kernels_ran(after: dict) -> frozenset:
    """Families whose kernel this process has traced."""
    return frozenset(f for f in KERNEL_FAMILIES
                     if any(after[f][i] for i in _kernel_impls(f)))


def _routing_faults(before: dict, after: dict) -> int:
    """Kernel families that took another path than their kernel. A family
    faults when this run traced any other impl, or when the process never
    traced its kernel (a jit cached from an earlier run in the same
    process traces nothing new)."""
    faults = 0
    for f in KERNEL_FAMILIES:
        ok = _kernel_impls(f)
        other = sum(after[f][i] - before[f][i] for i in after[f]
                    if i not in ok)
        faults += int(other > 0 or f not in _kernels_ran(after))
    return faults


def _sample(requests, lens, seed: int, size: int) -> list:
    """Answered requests to compare: the ``LONGEST_IN_SAMPLE`` with the
    most query tokens, and the rest drawn from the seed."""
    answered = [r for r in requests
                if r.handle.done() and r.handle.error is None]
    if len(answered) <= size:
        return answered
    order = np.argsort(-lens[[r.query for r in answered]], kind="stable")
    longest = set(order[:LONGEST_IN_SAMPLE].tolist())
    rest = [i for i in range(len(answered)) if i not in longest]
    rng = np.random.default_rng([int(seed), 5])
    drawn = rng.choice(rest, size - len(longest), replace=False)
    return [answered[i] for i in sorted(longest | set(drawn.tolist()))]


def run_cell(cell: manifest.Cell, seed: int, seconds: float, trace: bool,
             *, t_process: float, device_peaks: dict, quantize=(),
             patch=None) -> dict:
    """One run; returns the result object the benchmark prints last.

    ``t_process`` is when the process started, on ``time.perf_counter``'s
    clock. ``quantize`` (vector names to store as int8) and ``patch`` (a
    function applied to the built frontend) exist for the control and
    fault runs of ``bench/control.py`` and the tests; a benchmark run
    passes neither."""
    from repro.retrieval import tracing

    dispatch0 = _dispatch_counts()
    served = Served(cell, seed, quantize)
    if patch is not None:
        patch(served.fe)
    n_warm = served.warm(cell.traffic["warm"])
    fe = served.fe
    log(f"set-up: {served.retriever.n_docs} pages, {n_warm} buckets warmed")

    gen = manifest.generator(cell.traffic)
    devices = served.devices
    # what the served state holds on the fullest chip, before the window
    resident = fullest(devices, "bytes_in_use")
    before = dict(fe.stats)
    traces0 = tracing.trace_count()
    if trace:
        trace_dir = TRACE_DIR / cell.name
        trace_reduce.clear(trace_dir)
        jax.profiler.start_trace(str(trace_dir))
    t_window = time.perf_counter()
    setup_s = t_window - t_process
    with _CompileCounter() as compiles, \
            TraceAnnotation(trace_reduce.WINDOW_SPAN):
        requests, t0, t1 = gen.drive(fe, served.queries, served.lens,
                                     cell.traffic, seconds, seed)
    if trace:
        jax.profiler.stop_trace()
    after = dict(fe.stats)
    fe.drain()                # answers still in flight: late, not missing
    retraces = tracing.trace_count() - traces0
    routing = _dispatch_counts()
    routing_faults = _routing_faults(dispatch0, routing)
    window = requests

    dev = jax.devices()
    device = {"platform": dev[0].platform, "kind": dev[0].device_kind,
              "count": len(dev),
              "memory_peak_bytes": fullest(devices, "peak_bytes_in_use"),
              "memory_window_start_bytes": resident}
    shapes = _shapes(cell.config, served.retriever)
    run = Run(cell, setup_s, t0, t1, window,
              {k: after[k] - before[k] for k in after},
              _window_buckets(fe, window, served.lens, t0, t1), shapes,
              device_peaks, _kernels_ran(routing))
    if trace:
        run.trace = trace_reduce.summarize(trace_reduce.load(trace_dir))
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = manifest.metric_reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    for msg in run.notes:
        log(msg)
    failed = sum(1 for r in window
                 if not r.handle.done() or r.handle.error is not None)
    log(f"window: {len(window)} requests, {run.counters['dispatches']} "
        f"dispatches, {compiles.n} backend compiles, retraces={retraces}, "
        f"{resident} bytes resident at its start; "
        f"kernel dispatch counts {routing}")

    # the check: program state freed first, so the reference has the chip
    sample = _sample(window, served.lens, seed, cell.config["check"]["sample"])
    queries, lens = served.queries, served.lens
    del served, fe, gen
    gc.collect()
    checks = check(cell.config, seed, sample, queries, lens,
                   devices if len(devices) > 1 else None)
    checks["unanswered"] = (failed, 0)
    checks["retraces"] = (retraces, 0)
    checks["routing"] = (routing_faults, 0)
    correct = all(v <= lim for v, lim in checks.values())
    out = {"correct": correct, "attempted": len(window), "failed": failed,
           "metrics": metrics, "device": device}
    if trace:
        out["breakdown"] = run.trace.breakdown()
    out["checks"] = {k: {"value": v, "limit": lim}
                     for k, (v, lim) in checks.items()}
    return out


def _shapes(cfg: dict, retriever) -> dict:
    """Sizes the work counters need, read from the live store."""
    seg = retriever.store.segments[0].vectors
    from repro.retrieval.store import rerank_arrays, scan_arrays
    pooled = scan_arrays(seg, "mean_pooling")[0]
    full = rerank_arrays(seg, "initial")[0]
    return {"n_docs": int(retriever.n_docs),
            "pooled": tuple(int(x) for x in pooled.shape),
            "pooled_itemsize": int(pooled.dtype.itemsize),
            "full": tuple(int(x) for x in full.shape),
            "full_itemsize": int(full.dtype.itemsize),
            "prefetch_k": cfg["cascade"]["prefetch_k"]}


def check(cfg: dict, seed: int, sample: list, queries, lens,
          devices=None) -> dict:
    """Compare the sampled answers with the plain reference, doc-sharded
    over ``devices`` when given: {name: (value, limit)}."""
    limits = cfg["check"]["limits"]
    if not sample:
        return {"answers": (1, 0)}
    q = np.stack([queries[r.query] for r in sample])
    ln = np.asarray([lens[r.query] for r in sample])
    scores = np.concatenate([r.handle.scores for r in sample])
    ids = np.concatenate([r.handle.ids for r in sample])
    t = time.perf_counter()
    ref = reference.Reference(cfg, seed, devices)
    res = ref.search(q, ln, cfg["cascade"]["prefetch_k"], ids)
    del ref
    nums = reference.compare(res, scores, ids, cfg["pages"],
                             cfg["cascade"]["prefetch_k"],
                             limits["prefetch_gap"])
    log(f"reference: {len(sample)} answers ({nums.pop('compared')} ids) "
        f"compared in {time.perf_counter() - t:.1f}s")
    return {k: (v, limits.get(k, 0)) for k, v in nums.items()}
