"""Quickstart: index synthetic pages, run 1-/2-/3-stage visual retrieval.

    PYTHONPATH=src python examples/quickstart.py

Walks the paper's full pipeline on CPU: synthetic pages (with blank margins
+ special/padding tokens) -> cropping -> token hygiene -> model-aware
pooling -> named-vector store -> multi-stage MaxSim search through the
``Retriever`` facade -> metrics — then mutates the live corpus (upsert +
delete into preallocated segment headroom) without recompiling the search.
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.runtime import device_line
from repro.core import multistage as MST
from repro.core.cropping import crop_box
from repro.data.synthetic import (evaluate_ranking, make_benchmark,
                                  make_page_image)
from repro.retrieval import Retriever, tracing
from repro.retrieval.store import build_store


def main():
    print(device_line(), flush=True)
    rng = np.random.default_rng(0)

    # 1. preprocessing demo: empty-region cropping on a rendered page
    img, true_box = make_page_image(rng)
    box = crop_box(img, std_thresh=0.02, page_number_strip=0.05)
    print(f"[crop] content box {box} (true margins {true_box})")

    # 2. build a 3-dataset corpus + queries with known relevance
    cfg = get_config("colpali")
    bench = make_benchmark(cfg, n_pages_per_ds=(120, 100, 80),
                           queries_per_ds=(25, 25, 25))
    print(f"[data] {bench.pages.shape[0]} pages x {bench.pages.shape[1]} "
          f"tokens, {len(bench.queries)} queries")

    # 3. index: hygiene + model-aware pooling into named vectors, owned by
    #    a Retriever with ingestion headroom (capacity-padded segment)
    store = build_store(cfg, jnp.asarray(bench.pages),
                        jnp.asarray(bench.token_types))
    retriever = Retriever(store, capacity=512)
    print(f"[index] named vectors: "
          + ", ".join(f"{k}[D={v}]" for k, v in retriever.store.dims().items())
          + f"; capacity {retriever.store.total_capacity}")

    # 4. search: 1-stage exact vs 2-stage (pooled prefetch) vs 3-stage
    q = jnp.asarray(bench.queries)
    qm = jnp.asarray(bench.query_mask)
    for name, stages in [("1-stage exact", MST.one_stage(10)),
                         ("2-stage (K=128)", MST.two_stage(128, 10)),
                         ("3-stage cascade", MST.three_stage(256, 128, 10))]:
        _, ids = retriever.search(q, qm, stages=stages)
        m = evaluate_ranking(np.asarray(ids), bench.qrels, ks=(5, 10))
        print(f"[search] {name:18s} " +
              "  ".join(f"{k}={v:.3f}" for k, v in m.items()))

    # 5. live corpus: upsert new pages / delete old ones — shapes are
    #    capacity-stable, so the compiled cascade is reused, not retraced
    def batch_of(seed):
        extra = bench.pages[:16] + 0.05 * np.random.default_rng(
            seed).normal(size=bench.pages[:16].shape)
        return build_store(cfg, jnp.asarray(extra, jnp.float32),
                           jnp.asarray(bench.token_types))

    ids = retriever.upsert(batch_of(1))          # warm the write executables
    retriever.delete(ids[:8])
    retriever.search(q, qm, stages=MST.two_stage(128, 10))
    traces = tracing.trace_count()
    ids = retriever.upsert(batch_of(2))          # steady state: pure dispatch
    retriever.delete(ids[:8])
    retriever.search(q, qm, stages=MST.two_stage(128, 10))
    print(f"[mutate] upserted 2x16, deleted 2x8 -> {retriever.n_docs} live "
          f"docs; steady-state retraces: {tracing.trace_count() - traces}")


if __name__ == "__main__":
    main()
