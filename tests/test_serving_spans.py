"""The serving path's profiler spans, dispatch stamps and stage scopes.

- ``ServingFrontend`` writes one ``frontend.flush`` span per dispatch,
  carrying the dispatch's number, with its pad, launch, sync and
  translate spans nested inside in that order (the tiered engine
  translates inside launch);
- every live member of a cohort is stamped with the cohort's dispatch
  time and number; shed requests and cache hits are not;
- the compiled cascade's ops carry their stage's ``jax.named_scope`` in
  their op metadata, on the local and the ``shard_map`` body.
"""
import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.core import multistage as MST
from repro.retrieval import engine, tracing
from repro.retrieval.frontend import ServingFrontend
from repro.retrieval.retriever import Retriever
from repro.retrieval.store import (VectorStore, as_filter_arrays,
                                   filter_words)

D, DP, DIM = 4, 2, 8
STAGES = MST.two_stage(8, 4)
CHILDREN = {"retriever": [tracing.PAD, tracing.LAUNCH, tracing.SYNC,
                          tracing.TRANSLATE],
            "tiered": [tracing.PAD, tracing.LAUNCH, tracing.SYNC]}


def _store(n: int = 24, seed: int = 0) -> VectorStore:
    r = np.random.default_rng(seed)
    ini = r.normal(size=(n, D, DIM)).astype(np.float32)
    ini /= np.linalg.norm(ini, axis=-1, keepdims=True)
    return VectorStore({
        "initial": jnp.asarray(ini),
        "initial_mask": jnp.ones((n, D), bool),
        "mean_pooling": jnp.asarray(ini[:, :DP]),
        "mean_pooling_mask": jnp.ones((n, DP), bool),
    }, n, "float32")


def _frontend(path: str, **kw) -> ServingFrontend:
    r = Retriever(_store())
    if path == "tiered":
        kw["engine"] = r.tiered(1 << 30)
    return ServingFrontend(r, STAGES, max_batch=4, max_q=8, min_q=2,
                           flush_ms=1.0, **kw)


def _queries(n: int, seed: int = 3) -> list:
    rng = np.random.default_rng(seed)
    return [rng.normal(size=(int(rng.integers(1, 9)), DIM))
            .astype(np.float32) for _ in range(n)]


def _frontend_spans(trace_dir: Path) -> list:
    """(start, end, name, stats) of the frontend.* host spans, by start."""
    from jax.profiler import ProfileData
    files = sorted(trace_dir.rglob("*.xplane.pb"))
    data = ProfileData.from_file(str(files[-1]))
    out = [(e.start_ns, e.start_ns + e.duration_ns, e.name, dict(e.stats))
           for plane in data.planes if plane.name.startswith("/host:")
           for line in plane.lines for e in line.events
           if e.name.startswith("frontend.")]
    return sorted(out)


@pytest.mark.parametrize("path", ["retriever", "tiered"])
def test_one_flush_span_per_dispatch_with_children_in_order(path, tmp_path):
    fe = _frontend(path)
    jax.profiler.start_trace(str(tmp_path))
    fe.warm()
    before = fe.stats["dispatches"]
    for q in _queries(10):
        fe.submit(q)
        fe.pump()
    fe.drain()
    jax.profiler.stop_trace()
    n = fe.stats["dispatches"] - before
    assert n >= 3

    spans = _frontend_spans(tmp_path)
    flushes = [sp for sp in spans if sp[2] == tracing.FLUSH]
    assert len(flushes) == n
    assert [int(sp[3]["dispatch"]) for sp in flushes] == \
        list(range(before + 1, before + n + 1))
    for s, e, _, _ in flushes:
        inner = [sp for sp in spans
                 if sp[2] != tracing.FLUSH and s <= sp[0] and sp[1] <= e]
        assert [sp[2] for sp in inner] == CHILDREN[path]
        # siblings in sequence, not overlapping
        assert all(a[1] <= b[0] for a, b in zip(inner, inner[1:]))


def test_cohort_members_share_their_dispatch_stamp():
    t = [0.0]

    def clock():
        t[0] += 1e-3
        return t[0]

    fe = _frontend("retriever", clock=clock)
    first = [fe.submit(q) for q in _queries(3)]
    fe.flush()
    second = [fe.submit(q) for q in _queries(2, seed=4)]
    fe.flush()
    assert {pr.dispatch for pr in first} == {1}
    assert {pr.dispatch for pr in second} == {2}
    for cohort in (first, second):
        assert len({pr.t_dispatch for pr in cohort}) == 1
        for pr in cohort:
            assert pr.t_submit <= pr.t_dispatch <= pr.t_done


@pytest.mark.parametrize("how", ["shed", "cache_hit"])
def test_requests_never_dispatched_carry_no_stamp(how):
    t = [0.0]
    fe = _frontend("retriever", clock=lambda: t[0], deadline_ms=10.0,
                   cache_size=8)
    q = _queries(1)[0]
    if how == "shed":
        pr = fe.submit(q)
        t[0] = 0.02                       # 20 ms past a 10 ms deadline
        fe.flush()
        assert pr.shed
    else:
        fe.search(q)
        pr = fe.submit(q)
        assert pr.cached
    assert pr.done() and pr.t_dispatch is None and pr.dispatch is None


@pytest.mark.parametrize("sharded", [False, True])
def test_cascade_stages_named_in_op_metadata(sharded):
    r = Retriever(_store())
    stages = r._normalize(STAGES)
    stores = r.store.stores()
    mesh = Mesh(np.array(jax.devices()[:1]), ("d",)) if sharded else None
    body = engine._build_body(mesh, stages, r.store.capacities, 8)
    q = jnp.ones((2, 4, DIM), jnp.float32)
    qm = jnp.ones((2, 4), bool)
    fspec = as_filter_arrays(None, filter_words(stores[0]))
    hlo = jax.jit(body).lower(stores, q, qm, fspec).compile().as_text()
    scopes = set(re.findall(r'op_name="[^"]*?/(cascade\.\w+)/', hlo))
    assert scopes == {tracing.SCOPE_MASK, tracing.SCOPE_SCAN,
                      tracing.SCOPE_RERANK}
