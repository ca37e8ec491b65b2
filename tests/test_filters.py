"""Multi-tenant & metadata-filtered retrieval (ISSUE 6 tentpole).

Contracts under test:

- ``FilterSpec``/``pack_tags``: canonicalisation (dedup/sort/int-cast,
  hashable), bitset packing, out-of-range tag validation;
- ``effective_validity``: each filter term (tenant scope, require-all
  tags, any-of tags) ANDs with ``doc_valid`` exactly as documented;
- **rebuild equivalence** — a filtered search over the full corpus is
  BITWISE the unfiltered search over a corpus rebuilt from only the
  matching documents (same capacity both sides), on the reference path
  and every kernel-policy path (scan kernel, streamed top-k, fused
  rerank) — and as a hypothesis property over arbitrary tenant-stamped
  upsert/delete/compact sequences;
- **filters are data** — swapping tenant/filter values (including the
  null filter) at a fixed corpus layout and query shape triggers ZERO
  new traces;
- filler never leaks: ids for filter-excluded live docs come back -1;
- the ingest pipeline stamps ``tenant``/``tags`` onto the fused write
  path identically to ``upsert``;
- the frontend's multi-tenant serving: cross-tenant result-cache
  isolation (the regression behind keying the cache on filter
  identity), per-tenant admission quotas (``AdmissionError``), and
  round-robin fair flush across filter queues;
- sharded parity: tenant/filter scoping on a real 4-shard mesh matches
  the single-device ``multistage.search`` oracle (subprocess with fake
  CPU devices);
- the kernel dispatch registry: one resolve policy for all four op
  families, probe exemption from the dispatch counters, observed
  kernel-routing counts.
"""
import os
import subprocess
import sys
import textwrap

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import multistage as MST
from repro.kernels import dispatch
from repro.retrieval import tracing
from repro.retrieval.frontend import AdmissionError, ServingFrontend
from repro.retrieval.retriever import Retriever
from repro.retrieval.store import (FilterSpec, NULL_FILTER, VectorStore,
                                   as_filter_arrays, effective_validity,
                                   pack_tags)

D, DP, DIM = 4, 2, 8
NEG_CUT = -1e29          # anything below is masked filler


def _batch(n: int, seed: int) -> VectorStore:
    r = np.random.default_rng(seed)

    def unit(*s):
        x = r.normal(size=s).astype(np.float32)
        return x / np.maximum(np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)

    ini = unit(n, D, DIM)
    return VectorStore({
        "initial": jnp.asarray(ini),
        "initial_mask": jnp.ones((n, D), bool),
        "mean_pooling": jnp.asarray(ini[:, :DP]),
        "mean_pooling_mask": jnp.ones((n, DP), bool),
        "global_pooling": jnp.asarray(ini.mean(1)),
    }, n, "float32")


def _rows(batch: VectorStore) -> list:
    arrs = {k: np.asarray(v) for k, v in batch.vectors.items()}
    return [{k: a[i] for k, a in arrs.items()} for i in range(batch.n_docs)]


def _rebuild(rows: list) -> VectorStore:
    vecs = {k: jnp.asarray(np.stack([r[k] for r in rows]))
            for k in rows[0]}
    return VectorStore(vecs, len(rows), "float32")


QUERY = jnp.asarray(np.random.default_rng(99).normal(
    size=(3, 5, DIM)).astype(np.float32))
QMASK = jnp.ones((3, 5), bool)


# ----------------------------------------------------------------------
# FilterSpec / pack_tags units
# ----------------------------------------------------------------------

def test_pack_tags_bits_and_bounds():
    w = pack_tags((0, 5, 31), 1)
    assert w.dtype == np.uint32 and w.shape == (1,)
    assert int(w[0]) == (1 << 0) | (1 << 5) | (1 << 31)
    w2 = pack_tags((35,), 2)
    assert int(w2[0]) == 0 and int(w2[1]) == 1 << 3
    assert (pack_tags((), 3) == 0).all()
    with pytest.raises(ValueError):
        pack_tags((32,), 1)                    # word 1 doesn't exist
    with pytest.raises(ValueError):
        pack_tags((-1,), 1)


def test_filterspec_canonical_and_hashable():
    a = FilterSpec(tenant=np.int64(3), require_tags=[5, 3, 5],
                   any_tags=(2,))
    b = FilterSpec(tenant=3, require_tags=(3, 5), any_tags=[2])
    assert a == b and hash(a) == hash(b)
    assert a.tenant == 3 and a.require_tags == (3, 5)
    assert not a.is_null
    assert NULL_FILTER.is_null and FilterSpec().is_null
    assert not FilterSpec(tenant=0).is_null    # tenant 0 IS a scope


def test_as_filter_arrays_shapes_match_null():
    """The null filter and a loaded filter are the SAME traced structure —
    the precondition for zero retraces across filter swaps."""
    import jax
    loaded = as_filter_arrays(FilterSpec(tenant=2, require_tags=(1,)), 2)
    null = as_filter_arrays(None, 2)
    assert jax.tree.structure(loaded) == jax.tree.structure(null)
    for x, y in zip(jax.tree.leaves(loaded), jax.tree.leaves(null)):
        assert x.shape == y.shape and x.dtype == y.dtype
    # an already-packed triple passes through untouched
    assert as_filter_arrays(loaded, 2) is loaded


def test_effective_validity_terms():
    vecs = {
        "doc_valid": jnp.asarray([True, True, True, False]),
        "doc_tenant": jnp.asarray([0, 1, 1, 1], jnp.int32),
        "doc_filter": jnp.asarray(
            [pack_tags((1, 2), 1), pack_tags((1,), 1),
             pack_tags((3,), 1), pack_tags((1, 2), 1)]),
    }

    def eff(spec):
        return np.asarray(effective_validity(
            vecs, as_filter_arrays(spec, 1)))

    np.testing.assert_array_equal(eff(None), [1, 1, 1, 0])
    np.testing.assert_array_equal(eff(FilterSpec(tenant=1)), [0, 1, 1, 0])
    np.testing.assert_array_equal(
        eff(FilterSpec(require_tags=(1, 2))), [1, 0, 0, 0])
    np.testing.assert_array_equal(
        eff(FilterSpec(any_tags=(2, 3))), [1, 0, 1, 0])
    np.testing.assert_array_equal(
        eff(FilterSpec(tenant=1, any_tags=(1, 3))), [0, 1, 1, 0])
    # doc_valid always ANDs in: the dead slot never matches anything
    assert not eff(FilterSpec(tenant=1, require_tags=(1, 2)))[3]


# ----------------------------------------------------------------------
# rebuild equivalence, all kernel-policy paths
# ----------------------------------------------------------------------

def _two_tenant_retriever(cap=64):
    """Tenant 0: pages 4-11 (tags 1,2). Tenant 1: pages 12-19 (tag 1) and
    20-23 (no tags). Seed pages 0-3 deleted (tags only enter through the
    stamped write paths — upsert/ingest — never by poking arrays), plus
    page 13."""
    r = Retriever(_batch(4, 9), capacity=cap)
    rows = _rows(_batch(4, 9))
    meta = [(0, ())] * 4
    r.delete([0, 1, 2, 3])
    dead = {0, 1, 2, 3}
    r.upsert(_batch(8, 0), tenant=0, tags=(1, 2))
    rows += _rows(_batch(8, 0))
    meta += [(0, (1, 2))] * 8
    r.upsert(_batch(8, 1), tenant=1, tags=(1,))
    rows += _rows(_batch(8, 1))
    meta += [(1, (1,))] * 8
    r.upsert(_batch(4, 2), tenant=1)
    rows += _rows(_batch(4, 2))
    meta += [(1, ())] * 4
    r.delete([13])
    dead.add(13)
    return r, rows, meta, dead


def _matching(meta, dead, spec):
    out = []
    for i, (t, tags) in enumerate(meta):
        if i in dead:
            continue
        if spec.tenant >= 0 and t != spec.tenant:
            continue
        if any(x not in tags for x in spec.require_tags):
            continue
        if spec.any_tags and not any(x in tags for x in spec.any_tags):
            continue
        out.append(i)
    return out


def _policy_stages(policy, k1=8, k2=4):
    base = MST.two_stage(k1, k2)
    if policy == "ref":
        return base
    if policy == "kernel":
        return MST.with_scan_policy(base, use_kernel=True, chunk=16)
    if policy == "scan_topk":
        return MST.with_scan_policy(base, use_kernel=True, chunk=16,
                                    scan_topk=True)
    return MST.with_rerank_policy(
        MST.with_scan_policy(base, use_kernel=True, chunk=16,
                             scan_topk=True), rerank_kernel=True)


@pytest.mark.parametrize("policy", ["ref", "kernel", "scan_topk",
                                    "fused_rerank"])
@pytest.mark.parametrize("spec", [
    FilterSpec(tenant=0),
    FilterSpec(tenant=1),
    FilterSpec(require_tags=(1,)),
    FilterSpec(tenant=1, require_tags=(1,)),
    FilterSpec(any_tags=(2,)),
])
def test_filtered_equals_rebuild_bitwise(policy, spec):
    """A filtered search is bitwise the unfiltered search over a corpus
    rebuilt from only the matching documents — same capacity, same
    kernel policy, both sides."""
    cap = 64
    r, rows, meta, dead = _two_tenant_retriever(cap)
    stages = _policy_stages(policy)
    s, i = r.search(QUERY, QMASK, stages=stages, filter=spec)
    match = _matching(meta, dead, spec)
    rb = Retriever(_rebuild([rows[m] for m in match]), capacity=cap)
    sr, ir = rb.search(QUERY, QMASK, stages=stages)
    mapped = np.asarray([[match[j] if j >= 0 else -1 for j in row]
                         for row in np.asarray(ir)])
    np.testing.assert_array_equal(np.asarray(i), mapped)
    np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_no_match_filter_returns_only_filler():
    """A filter matching nothing must not leak ANY live page id through
    its NEG filler entries (cross-tenant id leak regression)."""
    r, _, _, _ = _two_tenant_retriever()
    s, i = r.search(QUERY, QMASK, stages=MST.two_stage(8, 4),
                    filter=FilterSpec(require_tags=(7,)))
    assert (np.asarray(s) < NEG_CUT).all()
    assert set(np.asarray(i).ravel()) == {-1}


def test_null_filter_bitwise_equals_unfiltered():
    r, _, _, _ = _two_tenant_retriever()
    stages = MST.two_stage(8, 4)
    s0, i0 = r.search(QUERY, QMASK, stages=stages)
    for f in (None, NULL_FILTER, FilterSpec(tenant=-1)):
        s, i = r.search(QUERY, QMASK, stages=stages, filter=f)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(s0))
        np.testing.assert_array_equal(np.asarray(i), np.asarray(i0))


def test_zero_retraces_across_filter_swaps():
    """Filters are DATA: after one warm search, every tenant/tag/null
    combination re-dispatches the same executable."""
    r, _, _, _ = _two_tenant_retriever()
    stages = MST.two_stage(8, 4)
    r.search(QUERY, QMASK, stages=stages, filter=FilterSpec(tenant=0))
    before = tracing.trace_count()
    for f in (FilterSpec(tenant=1), FilterSpec(require_tags=(1, 2)),
              FilterSpec(tenant=0, any_tags=(2,)), None, NULL_FILTER,
              FilterSpec(tenant=5)):
        r.search(QUERY, QMASK, stages=stages, filter=f)
    assert tracing.trace_count() == before, "a filter swap retraced"


def test_compact_preserves_tenancy():
    """Compaction gathers the tenant/filter companions alongside the data
    rows: filtered searches stay rebuild-equivalent afterwards."""
    cap = 64
    r, rows, meta, dead = _two_tenant_retriever(cap)
    r.delete([4, 19])
    dead |= {4, 19}
    r.compact()
    stages = MST.two_stage(8, 4)
    for spec in (FilterSpec(tenant=0), FilterSpec(tenant=1),
                 FilterSpec(tenant=1, require_tags=(1,))):
        s, i = r.search(QUERY, QMASK, stages=stages, filter=spec)
        match = _matching(meta, dead, spec)
        rb = Retriever(_rebuild([rows[m] for m in match]), capacity=cap)
        sr, ir = rb.search(QUERY, QMASK, stages=stages)
        mapped = np.asarray([[match[j] if j >= 0 else -1 for j in row]
                             for row in np.asarray(ir)])
        np.testing.assert_array_equal(np.asarray(i), mapped)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


def test_ingest_pipeline_stamps_tenant_and_tags():
    """The fused ingest path writes the same companions as upsert."""
    from repro.configs.base import RetrieverConfig
    from repro.core.hygiene import SPECIAL, VISUAL
    from repro.retrieval.ingest import IngestPipeline

    cfg = RetrieverConfig(name="mini", geometry="grid", grid_h=8, grid_w=8,
                          smooth="conv1d", d_model=64, n_layers=1,
                          n_heads=1, d_ff=64, out_dim=16, n_special=3,
                          max_query_tokens=8)
    tt = jnp.asarray([SPECIAL] * cfg.n_special + [VISUAL] * cfg.n_patches)
    rng = np.random.default_rng(7)

    def pages(n):
        x = rng.normal(size=(n, cfg.seq_len, cfg.out_dim)).astype(
            np.float32)
        return jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True))

    pipe = IngestPipeline.for_config(cfg, use_kernel=False)
    r = Retriever(pipe.index(pages(4), tt), capacity=64, ingest=pipe)
    ids = r.ingest(pages(3), tt, tenant=4, tags=(6,))
    seg = r.store.segments[0]
    t = np.asarray(seg.vectors["doc_tenant"])
    f = np.asarray(seg.vectors["doc_filter"])
    np.testing.assert_array_equal(t[:4], 0)
    np.testing.assert_array_equal(t[ids], 4)
    np.testing.assert_array_equal(
        f[ids], np.broadcast_to(pack_tags((6,), 1), (len(ids), 1)))
    assert (t[7:] == 0).all() and (f[7:] == 0).all()   # padding untouched
    q = jnp.asarray(rng.normal(size=(2, 4, 16)).astype(np.float32))
    s, i = r.search(q, None, stages=MST.two_stage(6, 3),
                    filter=FilterSpec(tenant=4, require_tags=(6,)))
    live = np.asarray(i)[np.asarray(s) > NEG_CUT]
    assert set(live) == set(int(x) for x in ids)


# ----------------------------------------------------------------------
# hypothesis property: mutations + filters == rebuild
# ----------------------------------------------------------------------

try:
    from hypothesis import HealthCheck, given, settings, strategies as st
    HAVE_HYP = True
except ImportError:                                  # pragma: no cover
    HAVE_HYP = False

if HAVE_HYP:
    OPS = st.lists(
        st.tuples(st.sampled_from(["add", "delete", "compact"]),
                  st.integers(1, 5), st.integers(0, 2),
                  st.sets(st.integers(0, 3), max_size=2)),
        min_size=1, max_size=6)
    SPECS = st.builds(
        FilterSpec, tenant=st.integers(-1, 2),
        require_tags=st.sets(st.integers(0, 3), max_size=2),
        any_tags=st.sets(st.integers(0, 3), max_size=2))

    @given(OPS, SPECS, st.integers(0, 2 ** 31 - 1))
    @settings(deadline=None, max_examples=15,
              suppress_health_check=[HealthCheck.too_slow])
    def test_arbitrary_mutations_filtered_equals_rebuild(ops, spec, seed):
        """Property: after ANY tenant-stamped add/delete/compact sequence,
        a filtered search equals (bitwise, same capacity) the unfiltered
        search over a rebuild of just the matching documents."""
        rng = np.random.default_rng(seed)
        cap = 8
        r = Retriever(_batch(4, seed), capacity=cap)
        rows = _rows(_batch(4, seed))
        meta = [(0, ())] * 4
        dead: set = set()
        for step, (op, n, tenant, tags) in enumerate(ops):
            if op == "add":
                r.upsert(_batch(n, seed + step + 1), tenant=tenant,
                         tags=tuple(tags))
                rows += _rows(_batch(n, seed + step + 1))
                meta += [(tenant, tuple(tags))] * n
            elif op == "delete":
                alive = [x for x in range(len(rows)) if x not in dead]
                if not alive:
                    continue
                pick = rng.choice(alive, size=min(n, len(alive)),
                                  replace=False)
                r.delete(pick)
                dead |= {int(x) for x in pick}
            else:
                r.compact()
        match = _matching(meta, dead, spec)
        if not match:
            s, i = r.search(QUERY, QMASK, stages=MST.two_stage(4, 2),
                            filter=spec)
            assert set(np.asarray(i).ravel()) <= {-1}
            return
        k = min(3, len(match))
        stages = (MST.Stage("mean_pooling", min(6, len(match))),
                  MST.Stage("initial", k))
        s, i = r.search(QUERY, QMASK, stages=stages, filter=spec)
        rb = Retriever(_rebuild([rows[m] for m in match]),
                       capacity=max(r.store.capacities))
        sr, ir = rb.search(QUERY, QMASK, stages=stages)
        mapped = np.asarray([[match[j] if j >= 0 else -1 for j in row]
                             for row in np.asarray(ir)])
        np.testing.assert_array_equal(np.asarray(i), mapped)
        np.testing.assert_array_equal(np.asarray(s), np.asarray(sr))


# ----------------------------------------------------------------------
# frontend: cache isolation, quotas, fair flush
# ----------------------------------------------------------------------

def _frontend(**kw):
    r, _, _, _ = _two_tenant_retriever()
    kw.setdefault("max_batch", 4)
    kw.setdefault("max_q", 8)
    return ServingFrontend(r, MST.two_stage(8, 4), **kw), r


def test_cross_tenant_cache_isolation():
    """REGRESSION: identical query bytes under different tenants are
    different requests — one tenant's cached results must never serve
    another's."""
    fe, _ = _frontend(cache_size=16)
    q = np.asarray(QUERY[0])
    s0, i0 = fe.search(q, filter=FilterSpec(tenant=0))
    assert fe.stats["cache_hits"] == 0
    s1, i1 = fe.search(q, filter=FilterSpec(tenant=1))
    assert fe.stats["cache_hits"] == 0, \
        "tenant 1 was served tenant 0's cached results"
    assert not np.array_equal(i0, i1)
    live0 = i0[np.asarray(s0) > NEG_CUT]
    live1 = i1[np.asarray(s1) > NEG_CUT]
    assert set(live0) <= set(range(4, 12))       # tenant 0's pages
    assert set(live1) <= set(range(12, 24))      # tenant 1's pages
    # same tenant, same bytes: NOW it's a hit, with identical results
    s0b, i0b = fe.search(q, filter=FilterSpec(tenant=0))
    assert fe.stats["cache_hits"] == 1
    np.testing.assert_array_equal(i0b, i0)
    # the unfiltered and null-filtered request share one cache line
    fe.search(q)
    fe.search(q, filter=NULL_FILTER)
    assert fe.stats["cache_hits"] == 2


def test_tenant_quota_rejects_excess():
    fe, _ = _frontend(tenant_quota=2)
    f1 = FilterSpec(tenant=1)
    fe.submit(np.asarray(QUERY[0]), filter=f1)
    fe.submit(np.asarray(QUERY[1]), filter=f1)
    with pytest.raises(AdmissionError):
        fe.submit(np.asarray(QUERY[2]), filter=f1)
    assert fe.stats["rejected"] == 1
    # a DIFFERENT tenant still gets in: quotas are per tenant
    pr = fe.submit(np.asarray(QUERY[2]), filter=FilterSpec(tenant=0))
    assert fe.drain() == 3 and pr.done()
    # quota released after the flush
    fe.submit(np.asarray(QUERY[2]), filter=f1)
    assert fe.pending == 1


def test_round_robin_flush_is_fair():
    """A quiet tenant's single request is served on the second flush at
    the latest, however deep the bursting tenant's queue is."""
    fe, _ = _frontend()
    burst, quiet = FilterSpec(tenant=1), FilterSpec(tenant=0)
    for j in range(8):                       # 8 queued rows of burst
        fe.submit(np.asarray(QUERY[j % 3]) + j, filter=burst)
    pq = fe.submit(np.asarray(QUERY[0]), filter=quiet)
    fe.flush()                               # serves a burst micro-batch
    fe.flush()                               # round-robin: quiet's turn
    assert pq.done(), "quiet tenant starved behind the burst backlog"
    assert fe.drain() >= 0                   # drain the rest


def test_micro_batch_carries_one_filter():
    """Mixed-filter submissions never share a dispatch block — each
    micro-batch is one fspec (results must equal the direct path)."""
    fe, r = _frontend()
    prs = [fe.submit(np.asarray(QUERY[0]), filter=f)
           for f in (FilterSpec(tenant=0), FilterSpec(tenant=1), None)]
    fe.drain()
    for pr, f in zip(prs, (FilterSpec(tenant=0), FilterSpec(tenant=1),
                           None)):
        s, i = r.search(QUERY[:1], QMASK[:1], stages=fe.stages, filter=f)
        np.testing.assert_array_equal(pr.ids, np.asarray(i))
        np.testing.assert_array_equal(pr.scores, np.asarray(s))


# ----------------------------------------------------------------------
# kernel dispatch registry
# ----------------------------------------------------------------------

def test_registry_has_all_four_families():
    assert set(dispatch.op_names()) >= {
        "maxsim_scan", "maxsim_rerank", "pooling", "embed_bag"}


def test_resolve_policy_matrix():
    # use_kernel=False is ALWAYS the reference path
    for name in dispatch.op_names():
        assert dispatch.resolve(name, False) == ("ref", True)
    if jax.default_backend() != "tpu":        # this CI: CPU
        # interpret-sanctioned family serves interpreted Pallas...
        if dispatch.available("maxsim_scan"):
            assert dispatch.resolve("maxsim_scan", True) == ("pallas", True)
        # ...interpret-as-tool families serve their fallback twin
        assert dispatch.resolve("maxsim_rerank", True) == ("jnp", True)
        assert dispatch.resolve("pooling", True)[0] in ("jnp", "ref")


def _probe_error():
    raise RuntimeError("Mosaic refused the kernel")


@pytest.mark.parametrize("probe,expect", [
    (lambda: True, ("pallas", False)),
    (lambda: False, RuntimeError),
    (_probe_error, RuntimeError),
])
def test_resolve_on_tpu_never_falls_back(monkeypatch, probe, expect):
    """On a TPU backend resolve(name, True) is the native kernel or an
    error: a failed probe must never route to the family's fallback."""
    monkeypatch.setattr(dispatch, "default_interpret", lambda: False)
    monkeypatch.setitem(dispatch._REGISTRY, "tpu_op", dispatch.KernelOp(
        name="tpu_op", probe=probe, fallback="jnp"))
    monkeypatch.delitem(dispatch._AVAILABLE, "tpu_op", raising=False)
    try:
        if isinstance(expect, tuple):
            assert dispatch.resolve("tpu_op", True) == expect
        else:
            with pytest.raises(expect):
                dispatch.resolve("tpu_op", True)
        assert dispatch.resolve("tpu_op", False) == ("ref", True)
    finally:
        dispatch._AVAILABLE.pop("tpu_op", None)
        dispatch._COUNTS.pop("tpu_op", None)


def test_probe_exempt_from_dispatch_counters():
    """available() must never bump the observed-routing counters — a CI
    gate diffing kernel_dispatch_count would otherwise pass on a probe
    alone."""
    calls = []

    def probe():
        dispatch.record("fake_op", "pallas")   # probes trace wrappers
        calls.append(1)
        return True

    dispatch.register(dispatch.KernelOp(
        name="fake_op", probe=probe, fallback="jnp",
        kernel_impls=frozenset({"pallas"})))
    try:
        assert dispatch.available("fake_op")
        assert dispatch.available("fake_op")   # cached: probe ran once
        assert calls == [1]
        assert dispatch.dispatch_count("fake_op") == 0
        assert dispatch.kernel_dispatch_count("fake_op") == 0
        # real traffic IS counted, and only kernel impls gate-count
        dispatch.record("fake_op", "pallas")
        dispatch.record("fake_op", "ref")
        assert dispatch.dispatch_count("fake_op") == 2
        assert dispatch.dispatch_count("fake_op", "pallas") == 1
        assert dispatch.kernel_dispatch_count("fake_op") == 1
    finally:
        dispatch._REGISTRY.pop("fake_op", None)
        dispatch._AVAILABLE.pop("fake_op", None)
        dispatch._COUNTS.pop("fake_op", None)


def test_legacy_resolvers_are_gone():
    """Exactly ONE dispatch mechanism remains."""
    from repro.kernels.maxsim import ops as KOPS
    from repro.kernels.pooling import ops as POPS
    from repro.kernels.embed_bag import ops as EOPS
    from repro.retrieval import engine
    for mod in (KOPS, POPS, EOPS, engine):
        assert not hasattr(mod, "resolve_impl")
        assert not hasattr(mod, "resolve_rerank_impl")
        assert not hasattr(mod, "_resolve_impl")
        assert not hasattr(mod, "_resolve_rerank_impl")


# ----------------------------------------------------------------------
# sharded parity (fake 4-device CPU mesh, subprocess)
# ----------------------------------------------------------------------

FILTER_SHARD_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import numpy as np, jax.numpy as jnp
    from repro.core import multistage as MST
    from repro.launch.mesh import make_mesh
    from repro.retrieval import tracing
    from repro.retrieval.retriever import Retriever
    from repro.retrieval.store import FilterSpec, VectorStore

    D, DP, DIM = 4, 2, 8
    def batch(n, seed):
        r = np.random.default_rng(seed)
        def unit(*s):
            x = r.normal(size=s).astype(np.float32)
            return x / np.maximum(
                np.linalg.norm(x, axis=-1, keepdims=True), 1e-9)
        ini = unit(n, D, DIM)
        return VectorStore({
            "initial": jnp.asarray(ini),
            "initial_mask": jnp.ones((n, D), bool),
            "mean_pooling": jnp.asarray(ini[:, :DP]),
            "mean_pooling_mask": jnp.ones((n, DP), bool),
            "global_pooling": jnp.asarray(ini.mean(1))}, n, "float32")

    q = jnp.asarray(np.random.default_rng(9).normal(
        size=(3, 5, DIM)).astype(np.float32))
    qm = jnp.ones((3, 5), bool)
    stages = MST.two_stage(8, 4)
    mesh = make_mesh((4,), ("data",))

    # 21 docs in one 24-slot segment, ragged over 4 shards — tenant
    # boundaries cross shard boundaries (one segment so the raw vectors
    # dict below IS the whole corpus for the single-device oracle)
    r = Retriever(batch(9, 0), mesh=mesh, capacity=24)  # tenant 0
    r.upsert(batch(7, 1), tenant=1, tags=(2,))
    r.upsert(batch(5, 2), tenant=1)
    r.delete([3, 11])
    assert len(r.store.segments) == 1, "corpus must stay one segment"

    # single-device oracle: the same companions through multistage.search
    seg = r.store.segments[0]
    sv = {k: jnp.asarray(np.asarray(v)) for k, v in seg.vectors.items()}
    for spec in (FilterSpec(tenant=0), FilterSpec(tenant=1),
                 FilterSpec(tenant=1, require_tags=(2,)), None):
        s, i = r.search(q, qm, stages=stages, filter=spec,
                        translate_ids=False)
        so, io = MST.search(sv, q, stages, qm, fspec=spec)
        s, i = np.asarray(s), np.asarray(i)
        so, io = np.asarray(so), np.asarray(io)
        live = so > -1e29
        np.testing.assert_array_equal(i[live], io[live])
        np.testing.assert_allclose(s[live], so[live],
                                   rtol=1e-5, atol=1e-6)
        assert (s[~live] < -1e29).all()

    # filter swaps on the MESH are retrace-free too
    before = tracing.trace_count()
    for spec in (FilterSpec(tenant=0), FilterSpec(tenant=1,
                                                  any_tags=(2,)), None):
        r.search(q, qm, stages=stages, filter=spec)
    assert tracing.trace_count() == before, "sharded filter swap retraced"
    print("FILTER_SHARD_OK")
""")


def test_filtered_multi_shard_parity_subprocess():
    """Tenant/filter scoping on a real 4-shard mesh matches the 1-device
    oracle (fake CPU devices must exist before jax init => subprocess)."""
    env = dict(os.environ)
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", FILTER_SHARD_SCRIPT],
                         env=env, capture_output=True, text=True,
                         timeout=500)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "FILTER_SHARD_OK" in out.stdout
