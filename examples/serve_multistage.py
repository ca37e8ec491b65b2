"""Serve a trained (or randomly initialised) retriever with batched
requests through the ``Retriever`` facade, including int8 and Matryoshka
stage-1 variants (beyond-paper levers).

    PYTHONPATH=src python examples/serve_multistage.py

The facade owns the segmented corpus and caches one compiled cascade per
stages config, so each timed loop below is pure dispatch after its first
call.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import numpy as np
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.runtime import device_line
from repro.core import multistage as MST
from repro.core.matryoshka import add_truncated_stage
from repro.data.synthetic import evaluate_ranking, make_benchmark
from repro.retrieval import Retriever
from repro.retrieval.store import VectorStore, build_store


def bench_config(name, stages, retriever, q, qm, qrels):
    retriever.search(q, qm, stages=stages)            # compile
    t0 = time.time()
    for _ in range(3):
        # time raw dispatch (device slot ids); translate once for metrics
        scores, _ = retriever.search(q, qm, stages=stages,
                                     translate_ids=False)
    scores.block_until_ready()
    dt = (time.time() - t0) / 3
    _, ids = retriever.search(q, qm, stages=stages)
    m = evaluate_ranking(np.asarray(ids), qrels, ks=(5, 10))
    print(f"{name:28s} QPS={len(q)/dt:7.1f}  "
          + "  ".join(f"{k}={v:.3f}" for k, v in m.items()))


def main():
    print(device_line(), flush=True)
    cfg = get_config("colqwen")
    bench = make_benchmark(cfg, (150, 120, 100), (30, 30, 30), seed=7)
    store = build_store(cfg, jnp.asarray(bench.pages),
                        jnp.asarray(bench.token_types))
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    # add a truncated (Matryoshka) prefetch vector alongside the named set
    vecs = add_truncated_stage(store.vectors, "mean_pooling", 32)
    retriever = Retriever(VectorStore(vecs, store.n_docs, store.store_dtype))

    print(f"corpus: {retriever.n_docs} pages ({cfg.name} geometry)")
    bench_config("1-stage exact", MST.one_stage(10), retriever,
                 q, qm, bench.qrels)
    bench_config("2-stage pooled", MST.two_stage(128, 10), retriever,
                 q, qm, bench.qrels)
    bench_config("3-stage cascade", MST.three_stage(256, 128, 10), retriever,
                 q, qm, bench.qrels)
    mrl = (MST.Stage("mean_pooling_mrl32", 128), MST.Stage("initial", 10))
    bench_config("2-stage pooled+MRL32 (ours)", mrl, retriever,
                 q, qm, bench.qrels)


if __name__ == "__main__":
    main()
