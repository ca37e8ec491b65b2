"""Corpus-size scaling study (paper §5: the 2x -> 4x QPS trend).

1-stage cost grows linearly with N; 2-stage rerank is capped at K. This
sweeps N and reports the measured speedup alongside the Eq.-1 prediction.

    PYTHONPATH=src python examples/scaling_study.py
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import time

import numpy as np
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.runtime import device_line
from repro.core import multistage as MST
from repro.data.synthetic import make_benchmark
from repro.retrieval.engine import make_search_fn
from repro.retrieval.store import build_store


def qps(fn, vectors, q, qm):
    fn(vectors, q, qm)
    t0 = time.time()
    out = [fn(vectors, q, qm) for _ in range(3)][-1]
    out[0].block_until_ready()
    return len(q) / ((time.time() - t0) / 3)


def main():
    print(device_line(), flush=True)
    cfg = get_config("colpali")
    print(f"{'N pages':>8s} {'1-stage QPS':>12s} {'2-stage QPS':>12s} "
          f"{'speedup':>8s} {'Eq.1 pred':>9s}")
    for per_ds in (40, 80, 160):
        bench = make_benchmark(cfg, (per_ds,) * 3, (20, 20, 20), seed=11)
        store = build_store(cfg, jnp.asarray(bench.pages),
                            jnp.asarray(bench.token_types))
        q = jnp.asarray(bench.queries)
        qm = jnp.asarray(bench.query_mask)
        n = store.n_docs
        k = 64
        q1 = qps(make_search_fn(None, MST.one_stage(10), n),
                 store.vectors, q, qm)
        q2 = qps(make_search_fn(None, MST.two_stage(k, 10), n),
                 store.vectors, q, qm)
        dims = store.dims()
        pred = (n * dims["initial"]) / (n * dims["mean_pooling"]
                                        + k * dims["initial"])
        print(f"{n:8d} {q1:12.1f} {q2:12.1f} {q2/q1:8.2f} {pred:9.2f}")


if __name__ == "__main__":
    main()
