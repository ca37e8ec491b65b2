"""Chip smoke test: the serving cascade end to end on a TPU.

    python chip_smoke.py               # one chip: ingest, serve, check
    python chip_smoke.py --four-chips  # 4-chip sharded search vs one device

One chip: a colpali-geometry corpus (32x32 grid, 1024 visual + 6 special
tokens, d=128) of 8,192 pages is generated on the device from a fixed
seed, batch by batch, and indexed through ``IngestPipeline`` with the
Pallas pooling kernel (the ``serve.py --ingest-pipeline`` write path).
64 ragged single-query requests are then served through ``Retriever`` and
``ServingFrontend`` with the kernel-routed two-stage cascade (Pallas scan
over the pooled vectors, Pallas gather-rerank over the full-resolution
ones) and checked against the ``core.multistage.search`` oracle on the
same corpus: equal top-10 page ids, allclose scores, no errored, shed or
degraded request, and every kernel-routed stage dispatched to Pallas.

``--four-chips`` builds the same corpus, searches it on one device, then
shards it over a 4-device mesh (``Retriever(mesh=...)``) and searches
again: the corpus must be spread over all 4 devices and the top-10 ids
must equal the one-device run.

The run needs a TPU: on any other platform it exits non-zero at once.
Its last line on success is ``{"ok": true, "device": {...}}``; any failed
phase exits non-zero without it. Timings printed on the way are single
smoke figures, not benchmarks.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

N_PAGES = 8192          # ~2.1 GB of bf16 full-resolution vectors in HBM
INGEST_BATCH = 256
N_REQUESTS = 64
MAX_TOKENS = 32
N_TOPICS = 64
SEED = 0
PREFETCH_K, TOP_K, CHECK_K = 256, 100, 10
SCAN_CHUNK = 1024


class SmokeFailure(RuntimeError):
    """A phase's check did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def kernel_stages():
    """The kernel-routed two-stage cascade the smoke serves."""
    from repro.core import multistage as MST
    return MST.with_rerank_policy(
        MST.with_scan_policy(MST.two_stage(PREFETCH_K, TOP_K),
                             use_kernel=True, chunk=SCAN_CHUNK),
        rerank_kernel=True)


def build_corpus(cfg, n_pages: int, batch: int, seed: int):
    """Generate ``n_pages`` raw pages on the device, ``batch`` at a time,
    and index them through one ``IngestPipeline`` into a ``Retriever``
    whose single segment holds exactly ``n_pages``. Returns (retriever,
    topics [N_TOPICS, d], seconds of the first batch — compile included —
    and of the rest)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.synthetic import page_batch
    from repro.retrieval.ingest import IngestPipeline
    from repro.retrieval.retriever import Retriever

    rng = np.random.default_rng(seed)
    topics = rng.normal(size=(N_TOPICS, cfg.out_dim))
    topics /= np.linalg.norm(topics, axis=1, keepdims=True)
    topics_dev = jnp.asarray(topics, jnp.float32)
    tt = jnp.asarray(np.concatenate([np.ones(cfg.n_special, np.int32),
                                     np.zeros(cfg.n_patches, np.int32)]))
    key = jax.random.PRNGKey(seed)
    pipe = IngestPipeline.for_config(cfg, use_kernel=True)

    t0 = time.perf_counter()
    pages, _ = page_batch(cfg, jax.random.fold_in(key, 0), topics_dev, batch)
    retriever = Retriever(pipe.index(pages, tt), capacity=n_pages,
                          ingest=pipe)
    pages, _ = page_batch(cfg, jax.random.fold_in(key, 1), topics_dev, batch)
    retriever.ingest(pages, tt)
    jax.block_until_ready(retriever.store.stores())
    t_first = time.perf_counter() - t0
    t0 = time.perf_counter()
    for i in range(2, n_pages // batch):
        pages, _ = page_batch(cfg, jax.random.fold_in(key, i), topics_dev,
                              batch)
        retriever.ingest(pages, tt)
    jax.block_until_ready(retriever.store.stores())
    check(retriever.n_docs == n_pages,
          f"indexed {retriever.n_docs} pages, expected {n_pages}")
    check(len(retriever.store.segments) == 1,
          f"corpus spilled into {len(retriever.store.segments)} segments")
    return retriever, topics, t_first, time.perf_counter() - t0


def corpus_bytes(retriever) -> int:
    return sum(int(v.nbytes) for seg in retriever.store.segments
               for v in seg.vectors.values())


def oracle(retriever, q, qm, block: int = 8) -> tuple:
    """``core.multistage.search`` over the retriever's corpus at full f32
    matmul precision (the kernels contract in f32 too), ``block`` queries
    per call: host (scores [n, k], page ids [n, k])."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import multistage as MST

    check(len(retriever.store.segments) == 1, "oracle expects one segment")
    vectors = retriever.store.segments[0].vectors
    fn = jax.jit(functools.partial(MST.search,
                                   stages=MST.two_stage(PREFETCH_K, TOP_K)))
    scores, slots = [], []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(q), block):
            s, ids = fn(vectors, jnp.asarray(q[i:i + block]),
                        q_mask=jnp.asarray(qm[i:i + block]))
            scores.append(np.asarray(s))
            slots.append(np.asarray(ids))
    slots = np.concatenate(slots)
    return np.concatenate(scores), retriever.store.translate_slots(slots)


def compare(name: str, scores, ids, ref_scores, ref_ids) -> float:
    """Equal top-``CHECK_K`` ids for every query, allclose scores. Returns
    the largest absolute score difference over the compared entries."""
    import numpy as np
    bad = [i for i in range(len(ids))
           if not np.array_equal(ids[i, :CHECK_K], ref_ids[i, :CHECK_K])]
    check(not bad, f"{name}: top-{CHECK_K} ids differ from the reference "
                   f"for queries {bad[:8]} (of {len(bad)})")
    a = scores[:, :CHECK_K]
    b = ref_scores[:, :CHECK_K]
    check(np.allclose(a, b, rtol=1e-4, atol=1e-3),
          f"{name}: scores not allclose (max |diff| "
          f"{float(np.max(np.abs(a - b)))})")
    return float(np.max(np.abs(a - b)))


def check_routing() -> dict:
    """Every kernel-routed family resolved and dispatched to Pallas, and
    no reference or jnp twin ran in its place."""
    from repro.kernels import dispatch as DSP
    resolved = {name: DSP.resolve(name, True)
                for name in ("maxsim_scan", "maxsim_rerank", "pooling")}
    for name, (impl, interp) in resolved.items():
        check(impl == "pallas" and not interp,
              f"{name} resolved to {impl} (interpret={interp})")
        check(DSP.dispatch_count(name, "pallas") > 0,
              f"{name}: no dispatch reached the Pallas kernel")
        for twin in ("ref", "jnp"):
            check(DSP.dispatch_count(name, twin) == 0,
                  f"{name}: {DSP.dispatch_count(name, twin)} dispatches "
                  f"ran the {twin} impl")
    return {name: impl for name, (impl, _) in resolved.items()}


def one_chip(cfg) -> None:
    import numpy as np
    from repro.data.synthetic import ragged_queries

    retriever, topics, t_first, t_rest = build_corpus(
        cfg, N_PAGES, INGEST_BATCH, SEED)
    print(f"corpus: {retriever.n_docs} colpali pages indexed through "
          f"IngestPipeline ({corpus_bytes(retriever) / 1e9:.3f} GB on "
          f"device); first 2 batches {t_first:.2f}s incl. compile, other "
          f"{N_PAGES // INGEST_BATCH - 2} batches {t_rest:.2f}s", flush=True)

    stages = kernel_stages()
    fe = retriever.frontend(stages, max_batch=16, max_q=MAX_TOKENS,
                            min_q=MAX_TOKENS // 2)
    t0 = time.perf_counter()
    n_buckets = fe.warm()
    print(f"compile: {n_buckets} cascade buckets warmed in "
          f"{time.perf_counter() - t0:.2f}s", flush=True)

    q, qm = ragged_queries(topics, N_REQUESTS, MAX_TOKENS, seed=SEED + 1)
    lens = qm.sum(axis=1)
    t0 = time.perf_counter()
    fe.search(q[0, :lens[0]], qm[0, :lens[0]])
    lat_ms = (time.perf_counter() - t0) * 1e3
    pending = [fe.submit(q[i, :lens[i]], qm[i, :lens[i]])
               for i in range(N_REQUESTS)]
    fe.drain()
    st = fe.stats
    print(f"requests: {len(pending)} ragged single queries "
          f"({int(lens.min())}-{int(lens.max())} tokens) in "
          f"{st['dispatches']} dispatches; errors={st['errors']} "
          f"shed={st['shed']} degraded={st['degraded']}", flush=True)
    print(f"latency sample: {lat_ms:.2f} ms for one request through the "
          "warmed frontend (a single smoke figure, not a benchmark)",
          flush=True)
    check(all(p.done() for p in pending), "requests left unserved")
    check(st["errors"] == 0 and st["shed"] == 0 and st["degraded"] == 0,
          f"frontend stats {st}")
    scores = np.concatenate([p.result()[0] for p in pending])
    ids = np.concatenate([p.result()[1] for p in pending])

    impls = check_routing()
    print(f"resolved impls: {impls}", flush=True)
    ref_scores, ref_ids = oracle(retriever, q, qm)
    diff = compare("frontend vs oracle", scores, ids, ref_scores, ref_ids)
    print(f"oracle: top-{CHECK_K} ids equal for all {N_REQUESTS} requests; "
          f"max |score diff| {diff:.3g}", flush=True)


def four_chips(cfg) -> None:
    import jax
    import numpy as np
    from repro.data.synthetic import ragged_queries
    from repro.launch.mesh import make_mesh
    from repro.retrieval.retriever import Retriever

    check(len(jax.devices()) == 4,
          f"--four-chips needs 4 devices, found {len(jax.devices())}")
    one, topics, _, _ = build_corpus(cfg, N_PAGES, INGEST_BATCH, SEED)
    q, qm = ragged_queries(topics, N_REQUESTS, MAX_TOKENS, seed=SEED + 1)
    stages = kernel_stages()

    def run(retriever):
        out_s, out_i = [], []
        for i in range(0, N_REQUESTS, 16):
            s, ids = retriever.search(q[i:i + 16], qm[i:i + 16],
                                      stages=stages)
            out_s.append(np.asarray(s))
            out_i.append(np.asarray(ids))
        return np.concatenate(out_s), np.concatenate(out_i)

    t0 = time.perf_counter()
    s1, i1 = run(one)
    print(f"one device: {N_REQUESTS} queries over {one.n_docs} pages in "
          f"{time.perf_counter() - t0:.2f}s incl. compile", flush=True)

    mesh = make_mesh((4,), ("data",))
    sharded = Retriever(one.store, mesh=mesh)
    for name in ("initial", "mean_pooling"):
        arr = sharded.store.segments[0].vectors[name]
        devs = {sh.device for sh in arr.addressable_shards}
        rows = sorted(sh.data.shape[0] for sh in arr.addressable_shards)
        check(len(devs) == 4 and rows == [arr.shape[0] // 4] * 4,
              f"{name} not split over 4 devices: {len(devs)} devices, "
              f"shard rows {rows}")
        print(f"sharding: {name} {tuple(arr.shape)} split over "
              f"{len(devs)} devices, {rows[0]} rows each", flush=True)
    t0 = time.perf_counter()
    s4, i4 = run(sharded)
    print(f"four devices: {N_REQUESTS} queries in "
          f"{time.perf_counter() - t0:.2f}s incl. compile", flush=True)
    impls = check_routing()
    print(f"resolved impls: {impls}", flush=True)
    diff = compare("4-device vs 1-device", s4, i4, s1, i1)
    print(f"compare: top-{CHECK_K} ids equal for all {N_REQUESTS} queries; "
          f"max |score diff| {diff:.3g}", flush=True)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip sharded search and the "
                         "one-device run it is compared with")
    args = ap.parse_args()

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {platform!r}",
              file=sys.stderr)
        return 2
    from repro.configs import get_config
    from repro.launch.runtime import (device_info, device_line,
                                      setup_compile_cache)
    print(f"compile cache: {setup_compile_cache()}")
    print(device_line(), flush=True)
    cfg = get_config("colpali")
    try:
        if args.four_chips:
            four_chips(cfg)
        else:
            one_chip(cfg)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device_info()}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
