"""Jitted public wrappers for the MaxSim kernels: padding, defaults, dispatch.

``maxsim_scores(q, docs, ...)`` pads N/D/Q to hardware-aligned multiples,
invokes the Pallas scan kernel (interpret=True on CPU — kernel-body
semantics validated on this host, compiled for TPU on real hardware), and
strips padding. Set ``impl="ref"`` to force the jnp oracle (used for A/B
tests and as the CPU-fast path in benchmarks).

``maxsim_rerank(q, docs, rows, ...)`` is the fused gather+MaxSim rerank
stage: per-query candidate slot ids in, [B, L] exact MaxSim scores out,
without ever materialising the [B, L, D, d] gathered candidate copy the
naive ``jnp.take``-then-score path writes to HBM. Three impls share its
semantics:

- ``"pallas"``  the scalar-prefetch gather kernel (candidate tiles DMA'd
                HBM -> VMEM by index) — the TPU path;
- ``"jnp"``     the fused jnp twin: candidate blocks of ``block_l`` are
                gathered, dequantised and scored per block inside a
                ``lax.map``, bounding the live gather working set at
                [B, block_l, D, d] (the off-TPU serving path — measurably
                faster than the vmapped reference on cache-bound hosts);
- ``"ref"``     the legacy per-query vmap(take + maxsim_scan) — the
                bitwise contract with the ``multistage._score_stage``
                oracle.

``maxsim_topk_chunked`` is the streamed scan top-k: scores the corpus
chunk-by-chunk (any scan impl) while carrying a running per-query top-k
through a ``lax.scan``, merging each chunk's local winners hierarchically —
the scan stage's HBM score write shrinks from O(B*N) to O(B*k*n_chunks)
and the full [B, N] score matrix never exists.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import dispatch as DSP
from repro.kernels.dispatch import default_interpret
from repro.kernels.maxsim.maxsim import (doc_block, maxsim_pallas,
                                         maxsim_rerank_pallas, query_block)
from repro.kernels.maxsim.ref import NEG, maxsim_ref


def _pad_to(x: jax.Array, axis: int, mult: int, value=0):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("impl", "block_n", "interpret"))
def maxsim_scores(q: jax.Array, docs: jax.Array,
                  q_mask: jax.Array | None = None,
                  doc_mask: jax.Array | None = None,
                  scales: jax.Array | None = None,
                  doc_valid: jax.Array | None = None,
                  *, impl: str = "pallas", block_n: int = 0,
                  interpret: bool = True) -> jax.Array:
    """q [B,Q,d], docs [N,D,d] -> scores [B,N] (f32).

    ``block_n`` documents stream per kernel grid step, as
    ``maxsim.doc_block`` fits it to the kernel body (0 = its default
    tile). ``doc_valid`` [N] bool
    marks live documents in a capacity-padded store; dead slots score NEG
    so they can never enter a top-k on merit. The mask is applied to the
    kernel OUTPUT — the kernel still streams the full padded corpus
    (shape stability is what makes mutation retrace-free).
    """
    B, Q, d = q.shape
    N, D, _ = docs.shape
    if q_mask is None:
        q_mask = jnp.ones((B, Q), jnp.float32)
    if doc_mask is None:
        doc_mask = jnp.ones((N, D), jnp.float32)
    q_mask = q_mask.astype(jnp.float32)
    doc_mask = doc_mask.astype(jnp.float32)
    DSP.record("maxsim_scan", impl)

    if impl == "ref":
        out = maxsim_ref(q, q_mask, docs, doc_mask, scales)
        if doc_valid is not None:
            out = jnp.where(doc_valid[None, :], out, NEG)
        return out

    # pad Q to the sublane tile, B to the query block, N to block_n
    bn = doc_block(D, d, docs.dtype.itemsize, N, block_n)
    qp = _pad_to(q, 1, 8)
    qmp = _pad_to(q_mask, 1, 8)
    bq = query_block(B, qp.shape[1])
    qp, qmp = _pad_to(qp, 0, bq), _pad_to(qmp, 0, bq)
    docs_p = _pad_to(docs, 0, bn)
    dm_p = _pad_to(doc_mask, 0, bn)
    sc_p = None if scales is None else _pad_to(scales, 0, bn)
    out = maxsim_pallas(qp, qmp, docs_p, dm_p, block_n=bn, scales=sc_p,
                        interpret=interpret)[:B, :N]
    if doc_valid is not None:
        out = jnp.where(doc_valid[None, :], out, NEG)
    return out


def _probe_scan() -> bool:
    """The ``maxsim_scan`` probe; success defines availability.

    On TPU it compiles the served scan instances (``served_instances``).
    Elsewhere it runs a tiny interpreted instance, and the serving engine
    falls back to the jnp reference when that fails (a backend without a
    working Pallas interpreter)."""
    if not default_interpret():
        return DSP.compile_served(served_instances(), "maxsim_scan")
    q = jnp.zeros((1, 8, 128), jnp.float32)
    docs = jnp.zeros((8, 8, 128), jnp.float32)
    out = maxsim_scores(q, docs, impl="pallas", block_n=8,
                        interpret=default_interpret())
    jax.block_until_ready(out)
    return True


def pallas_available() -> bool:
    """Whether the scan kernel executes here (``dispatch.available``)."""
    return DSP.available("maxsim_scan")


def maxsim_scores_chunked(q: jax.Array, docs: jax.Array,
                          q_mask: jax.Array | None = None,
                          doc_mask: jax.Array | None = None,
                          scales: jax.Array | None = None,
                          doc_valid: jax.Array | None = None,
                          *, chunk: int, impl: str = "pallas",
                          block_n: int = 0,
                          interpret: bool = True) -> jax.Array:
    """Streaming corpus scan. The reference scores ``chunk`` documents per
    ``lax.map`` step, bounding its [B, chunk, Q, D] similarity block
    regardless of corpus size N; the kernel impls ignore ``chunk`` (their
    grid already streams VMEM-sized tiles). N is padded up to a
    chunk multiple with fully-masked documents and the padding stripped
    from the returned [B, N] scores. chunk <= 0 means unchunked.
    ``doc_valid`` [N] bool NEGs dead capacity-padding slots (applied once on
    the assembled [B, N] output, not per chunk).
    """
    N, D, _ = docs.shape
    if impl != "ref" or chunk <= 0 or chunk >= N:
        # the kernel streams [block_n, D, d] tiles through VMEM itself, so
        # its intermediate is bounded whatever N is: chunking it would only
        # add launches
        return maxsim_scores(q, docs, q_mask, doc_mask, scales, doc_valid,
                             impl=impl, block_n=block_n,
                             interpret=interpret)
    if doc_mask is None:
        doc_mask = jnp.ones((N, D), jnp.float32)
    docs = _pad_to(docs, 0, chunk)
    doc_mask = _pad_to(doc_mask.astype(jnp.float32), 0, chunk)
    if scales is not None:
        scales = _pad_to(scales, 0, chunk)
    n_blocks = docs.shape[0] // chunk
    db = docs.reshape(n_blocks, chunk, *docs.shape[1:])
    mb = doc_mask.reshape(n_blocks, chunk, D)
    call = functools.partial(maxsim_scores, impl=impl, block_n=block_n,
                             interpret=interpret)
    if scales is None:
        out = jax.lax.map(lambda a: call(q, a[0], q_mask, a[1]), (db, mb))
    else:
        sb = scales.reshape(n_blocks, chunk, D)
        out = jax.lax.map(lambda a: call(q, a[0], q_mask, a[1], a[2]),
                          (db, mb, sb))
    out = jnp.moveaxis(out, 0, 1).reshape(q.shape[0],
                                          n_blocks * chunk)[:, :N]
    if doc_valid is not None:
        out = jnp.where(doc_valid[None, :], out, NEG)
    return out


# ---------------------------------------------------------------------------
# fused gather + MaxSim rerank
# ---------------------------------------------------------------------------

def _rerank_ref(q, docs, rows, q_mask, doc_mask, scales):
    """The legacy gather-then-score path: per-query ``jnp.take`` + the
    ``core.maxsim.maxsim_scan`` math — bitwise the ``multistage``
    ``_score_stage`` oracle on float stores (dequantisation of gathered
    int8 rows commutes with the gather elementwise, so quantised stores
    match the oracle's dequantise-then-gather bitwise too)."""
    def per_query(qi, qm, cl):
        dv = jnp.take(docs, cl, axis=0)                    # [L, D, d]
        if scales is not None:
            dv = dv.astype(jnp.float32) \
                * jnp.take(scales, cl, axis=0)[..., None]
        sim = jnp.einsum("qd,njd->nqj", qi, dv.astype(qi.dtype))
        if doc_mask is not None:
            sim = jnp.where(jnp.take(doc_mask, cl, axis=0)[:, None, :] > 0,
                            sim, NEG)
        best = jnp.max(sim, axis=-1)                       # [L, Q]
        best = jnp.where(qm[None, :] > 0, best, 0.0)
        return jnp.sum(best, axis=-1)

    return jax.vmap(per_query)(q, q_mask, rows)


def _rerank_fused_jnp(q, docs, rows, q_mask, doc_mask, scales,
                      block_l: int):
    """The fused twin: candidate blocks of ``block_l`` stream through a
    ``lax.map`` — gather, dequantise and score one [B, block_l] block at a
    time, so the live working set is [B, block_l, D, d] instead of the
    full [B, L, D, d] gathered copy (the same bounding the Pallas kernel
    gets from per-tile DMA, expressed in jnp)."""
    B, L = rows.shape
    block_l = max(1, min(block_l, L))
    pad = (-L) % block_l
    rows_p = jnp.pad(rows, ((0, 0), (0, pad)))             # clipped ids: safe
    n_blocks = (L + pad) // block_l
    qf = q.astype(jnp.float32)

    def block(cl):                                         # cl [B, block_l]
        dv = docs[cl]                                      # [B, bl, D, d]
        if scales is not None:
            dv = dv.astype(jnp.float32) * scales[cl][..., None]
        sim = jnp.einsum("bqd,bljd->blqj", qf, dv.astype(jnp.float32))
        if doc_mask is not None:
            sim = jnp.where(doc_mask[cl][:, :, None, :] > 0, sim, NEG)
        best = jnp.max(sim, axis=-1)                       # [B, bl, Q]
        # no NEG/2 clamp: the rerank contract is maxsim_scan's raw sum,
        # identical across all three impls even for fully-masked docs
        best = jnp.where(q_mask[:, None, :] > 0, best, 0.0)
        return jnp.sum(best, axis=-1)                      # [B, bl]

    rb = rows_p.reshape(B, n_blocks, block_l).transpose(1, 0, 2)
    out = jax.lax.map(block, rb)                           # [nb, B, bl]
    return jnp.moveaxis(out, 0, 1).reshape(B, n_blocks * block_l)[:, :L]


@functools.partial(jax.jit, static_argnames=("impl", "block_d", "block_l",
                                             "interpret"))
def maxsim_rerank(q: jax.Array, docs: jax.Array, rows: jax.Array,
                  q_mask: jax.Array | None = None,
                  doc_mask: jax.Array | None = None,
                  scales: jax.Array | None = None,
                  ok: jax.Array | None = None,
                  *, impl: str = "pallas", block_d: int = 0,
                  block_l: int = 8, interpret: bool = True) -> jax.Array:
    """Fused gather + exact MaxSim rerank: q [B,Q,d], docs [N,D,d]
    (float, or int8 codes with ``scales`` [N,D]), rows [B,L] candidate
    slot ids -> scores [B,L] f32.

    ``rows`` are clipped in-range (callers pass clipped ids anyway);
    ``ok`` [B,L] bool marks candidates the caller actually owns — the rest
    score NEG so they can never win a top-k slot on merit. Matryoshka
    stores (docs narrower than q) score against the matching query
    prefix. ``impl``: "pallas" (scalar-prefetch gather kernel), "jnp"
    (fused block-streamed twin), "ref" (legacy vmapped gather — the
    bitwise oracle contract).
    """
    B, Q, d = q.shape
    N, D, dd = docs.shape
    if dd < d:                                # Matryoshka rerank stage
        q = q[..., :dd]
    rows = jnp.clip(rows, 0, N - 1).astype(jnp.int32)
    if q_mask is None:
        q_mask = jnp.ones((B, Q), jnp.float32)
    q_mask = q_mask.astype(jnp.float32)
    if doc_mask is not None:
        doc_mask = doc_mask.astype(jnp.float32)
    # a mask-less store never materialises a corpus-sized ones array: the
    # jnp/ref impls skip the masking, the Pallas kernel streams ONE
    # broadcast all-ones row tile (see maxsim_rerank_pallas)

    DSP.record("maxsim_rerank", impl)
    if impl == "ref":
        out = _rerank_ref(q, docs, rows, q_mask, doc_mask, scales)
    elif impl == "jnp":
        out = _rerank_fused_jnp(q, docs, rows, q_mask, doc_mask, scales,
                                block_l)
    else:
        qp = _pad_to(q, 1, 8)
        qmp = _pad_to(q_mask, 1, 8)
        bd = block_d if block_d > 0 else docs.shape[1]
        docs_p = _pad_to(docs, 1, bd)
        if doc_mask is None:
            doc_mask = jnp.ones((1, D), jnp.float32)      # broadcast row
        dm_p = _pad_to(doc_mask, 1, bd)
        sc_p = None if scales is None else _pad_to(scales, 1, bd)
        out = maxsim_rerank_pallas(rows, qp, qmp, docs_p, dm_p,
                                   block_d=bd, scales=sc_p,
                                   interpret=interpret)
    if ok is not None:
        out = jnp.where(ok, out, NEG)
    return out


def _probe_rerank() -> bool:
    """The ``maxsim_rerank`` probe: the served rerank instances compiled
    on TPU, a tiny interpreted instance elsewhere (the registry snapshots
    the dispatch counters around it, so an availability check can never
    satisfy the CI gate's "the cascade really routed through the fused
    path" signal)."""
    if not default_interpret():
        return DSP.compile_served(served_instances(), "maxsim_rerank")
    q = jnp.zeros((1, 8, 128), jnp.float32)
    docs = jnp.zeros((8, 8, 128), jnp.float32)
    rows = jnp.zeros((1, 2), jnp.int32)
    out = maxsim_rerank(q, docs, rows, impl="pallas", block_d=8,
                        interpret=default_interpret())
    jax.block_until_ready(out)
    return True


def rerank_pallas_available() -> bool:
    """Whether the gather-rerank kernel executes here
    (``dispatch.available``; the engine resolves to the fused jnp twin
    when False)."""
    return DSP.available("maxsim_rerank")


# ---------------------------------------------------------------------------
# IVF centroid routing
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("impl", "interpret"))
def centroid_scores(q: jax.Array, centroids: jax.Array,
                    q_mask: jax.Array | None = None,
                    *, impl: str = "ref",
                    interpret: bool = True) -> jax.Array:
    """Query-vs-centroid routing scores: q [B,Q,d], centroids [K,dc] ->
    [B,K] f32.

    The routing score of cluster c is the summed-query dot product
    ``(sum_i mask_i * q_i) . c`` — MaxSim over a single-vector "document"
    degenerates to exactly this, so the Pallas impl reuses the scan
    kernel on a ``centroids[:, None, :]`` view (D=1 documents) while the
    reference is one masked-sum GEMM. The ref GEMM is the bitwise
    contract (it is ``core.maxsim.maxsim_single_vector`` on the centroid
    table); the kernel impl reorders the token sum and is allclose-level.
    Matryoshka centroids (narrower than q) score against the matching
    query prefix, mirroring every other stage."""
    B, Q, d = q.shape
    K, dc = centroids.shape
    if dc < d:
        q = q[..., :dc]
    if q_mask is None:
        q_mask = jnp.ones((B, Q), jnp.float32)
    q_mask = q_mask.astype(jnp.float32)
    DSP.record("ivf_route", impl)
    if impl == "ref":
        qs = jnp.sum(q.astype(jnp.float32) * q_mask[..., None], axis=-2)
        return qs @ centroids.astype(jnp.float32).T
    qp = _pad_to(q, 1, 8)
    qmp = _pad_to(q_mask, 1, 8)
    bq = query_block(B, qp.shape[1])
    qp, qmp = _pad_to(qp, 0, bq), _pad_to(qmp, 0, bq)
    bn = doc_block(1, dc, 4, K)
    docs_p = _pad_to(centroids[:, None, :].astype(jnp.float32), 0, bn)
    dm_p = jnp.ones((docs_p.shape[0], 1), jnp.float32)
    out = maxsim_pallas(qp, qmp, docs_p, dm_p, block_n=bn,
                        interpret=interpret)
    return out[:B, :K]


def _probe_route() -> bool:
    """The ``ivf_route`` probe: the served routing instance compiled on
    TPU, a tiny interpreted instance elsewhere (counter snapshot/restore
    handled by the registry)."""
    if not default_interpret():
        return DSP.compile_served(served_instances(), "ivf_route")
    q = jnp.zeros((1, 8, 128), jnp.float32)
    cents = jnp.zeros((8, 128), jnp.float32)
    out = centroid_scores(q, cents, impl="pallas",
                          interpret=default_interpret())
    jax.block_until_ready(out)
    return True


# ---------------------------------------------------------------------------
# streamed scan top-k
# ---------------------------------------------------------------------------

def _merge_topk(vals, ids, new_vals, new_ids, k: int):
    """(vals, ids) [B, k] running winners + a chunk's [B, kb] locals ->
    merged [B, k]. Local twin of ``repro.retrieval.topk.merge_topk``
    (kernels must not import retrieval — the layering is kernels < core <
    retrieval; the engine still merges SEGMENTS with the retrieval
    helper)."""
    mv = jnp.concatenate([vals, new_vals], axis=1)
    mi = jnp.concatenate([ids, new_ids], axis=1)
    v, sel = jax.lax.top_k(mv, k)
    return v, jnp.take_along_axis(mi, sel, axis=1)


def maxsim_topk_chunked(q: jax.Array, docs: jax.Array,
                        q_mask: jax.Array | None = None,
                        doc_mask: jax.Array | None = None,
                        scales: jax.Array | None = None,
                        doc_valid: jax.Array | None = None,
                        *, k: int, chunk: int, impl: str = "pallas",
                        block_n: int = 0, interpret: bool = True) -> tuple:
    """Streaming corpus scan with a RUNNING per-query top-k: returns
    (vals [B, k], local ids [B, k]) without ever assembling the [B, N]
    score matrix.

    Each ``lax.scan`` step scores one ``chunk``-document block (any scan
    impl — the Pallas kernel, or the jnp ref), NEGs dead ``doc_valid``
    slots BEFORE the block's local top-k (a dead slot must never enter a
    candidate set on merit), selects the block's top ``min(k, chunk)``
    and merges them into the carry hierarchically. The per-step HBM
    traffic is one read of the chunk plus the O(B*k) carry — the [B, N]
    write of the score-then-select path is gone. Ids are local (caller
    adds segment/shard offsets) and always < N: slots the CHUNK PADDING
    invents (N -> chunk multiple) score -inf, strictly below every real
    slot — including fully token-masked documents, whose Q*NEG sum is
    below the dead-slot NEG but still finite — and since k <= N real
    slots always exist, a padding id can never leak out and alias
    another segment's slot space. The carry seeds at -inf too: a real
    document's NEG still outranks an unfilled seed slot, keeping
    returned ids distinct.
    """
    B = q.shape[0]
    N, D, _ = docs.shape
    k = min(k, N)
    if chunk <= 0 or chunk >= N:
        s = maxsim_scores(q, docs, q_mask, doc_mask, scales, doc_valid,
                          impl=impl, block_n=block_n, interpret=interpret)
        return jax.lax.top_k(s, k)
    if doc_valid is None:
        doc_valid = jnp.ones((N,), bool)
    docs = _pad_to(docs, 0, chunk)
    doc_valid = _pad_to(doc_valid, 0, chunk)               # pads False
    n_blocks = docs.shape[0] // chunk
    kb = min(k, chunk)
    call = functools.partial(maxsim_scores, impl=impl, block_n=block_n,
                             interpret=interpret)
    # mask-less stores keep doc_mask=None per chunk (padding rows are
    # excluded via the False-padded doc_valid) — never an [N, D] ones
    xs = {"docs": docs.reshape(n_blocks, chunk, *docs.shape[1:]),
          "valid": doc_valid.reshape(n_blocks, chunk),
          "off": jnp.arange(n_blocks, dtype=jnp.int32) * chunk}
    if docs.shape[0] != N:
        # padding slots sink to -inf, not NEG: a fully token-masked live
        # document scores Q*NEG < NEG, and padding must rank below even
        # that or its out-of-range id could enter the top-k
        xs["in_range"] = (jnp.arange(docs.shape[0])
                          < N).reshape(n_blocks, chunk)
    if doc_mask is not None:
        xs["mask"] = _pad_to(doc_mask.astype(jnp.float32), 0,
                             chunk).reshape(n_blocks, chunk, D)
    if scales is not None:
        xs["scales"] = _pad_to(scales, 0, chunk).reshape(n_blocks, chunk, D)

    def step(carry, x):
        s = call(q, x["docs"], q_mask, x.get("mask"),
                 x.get("scales"))                          # [B, chunk]
        s = jnp.where(x["valid"][None, :], s, NEG)
        if "in_range" in x:
            s = jnp.where(x["in_range"][None, :], s, -jnp.inf)
        v, i = jax.lax.top_k(s, kb)
        return _merge_topk(*carry, v, i + x["off"], k), None

    init = (jnp.full((B, k), -jnp.inf, jnp.float32),
            jnp.zeros((B, k), jnp.int32))
    (vals, ids), _ = jax.lax.scan(step, init, xs)
    return vals, ids


@jax.jit
def _quantize_block(docs: jax.Array, eps) -> tuple:
    # math in f32 WITHOUT an eager full-size f32 copy: under jit the
    # upcasts fuse into the elementwise chains (abs -> reduce-max;
    # divide -> round -> clip -> int8), so the largest live buffer is the
    # int8 output, not a 4-byte shadow of the corpus
    amax = jnp.max(jnp.abs(docs).astype(jnp.float32), axis=-1)
    scales = jnp.maximum(amax, eps) / 127.0
    codes = jnp.clip(jnp.round(docs.astype(jnp.float32)
                               / scales[..., None]), -127, 127)
    return codes.astype(jnp.int8), scales


def quantize_int8(docs: jax.Array, eps: float = 1e-9, chunk: int = 0):
    """Per-vector symmetric int8 quantisation: docs [N,D,d] ->
    (int8 codes [N,D,d], scales [N,D]). Accepts any float dtype — the
    store dtype goes in directly; quantising a bf16 array is bitwise the
    old quantise-a-f32-copy behaviour (the bf16->f32 upcast is exact) but
    never materialises that copy, so ``--int8`` ingest no longer briefly
    triples HBM for the largest named vector. ``chunk`` > 0 additionally
    processes N in row slabs, bounding even the transient at
    [chunk, D, d]."""
    if chunk > 0 and chunk < docs.shape[0]:
        parts = [_quantize_block(docs[i:i + chunk], eps)
                 for i in range(0, docs.shape[0], chunk)]
        return (jnp.concatenate([c for c, _ in parts], axis=0),
                jnp.concatenate([s for _, s in parts], axis=0))
    return _quantize_block(docs, eps)


# ---------------------------------------------------------------------------
# served shapes (what the TPU probes and tests/test_chip_compile.py compile)
# ---------------------------------------------------------------------------

# colpali's served widths (configs/colpali.py): pooled rows (32 row means
# through conv1d -> 34) and full-resolution rows (the 32x32 patch grid),
# d=128; query blocks up to 64 x 32 tokens; rerank L=256; 256 IVF centroids
D_POOLED, D_FULL, DIM = 34, 1024, 128
N_POOLED, N_FULL = 65536, 8192
Q_TOKENS, RERANK_L, N_CENTROIDS = 32, 256, 256


def _doc_shapes(n: int, rows: int, dtype) -> list:
    shapes = [((n, rows, DIM), dtype), ((n, rows), jnp.float32)]
    if dtype == jnp.int8:
        shapes.append(((n, rows), jnp.float32))          # per-row scales
    return shapes


def _scan(q, qm, docs, dm, scales=None):
    return maxsim_scores(q, docs, qm, dm, scales, impl="pallas",
                         interpret=False)


def _rerank(q, qm, rows, docs, dm, scales=None):
    return maxsim_rerank(q, docs, rows, qm, dm, scales, impl="pallas",
                         interpret=False)


def _route(q, qm, cents):
    return centroid_scores(q, cents, qm, impl="pallas", interpret=False)


def served_instances() -> dict:
    """{case: (family, fn, [(shape, dtype), ...])}: the native kernel
    instances this module serves at colpali width, bf16 and int8 docs."""
    out = {}
    for n, rows, dtype, b in ((N_POOLED, D_POOLED, jnp.bfloat16, 64),
                              (N_POOLED, D_POOLED, jnp.int8, 16),
                              (N_FULL, D_FULL, jnp.bfloat16, 16),
                              (N_FULL, D_FULL, jnp.int8, 64)):
        out[f"scan-D{rows}-{jnp.dtype(dtype).name}-B{b}"] = (
            "maxsim_scan", _scan,
            [((b, Q_TOKENS, DIM), jnp.float32), ((b, Q_TOKENS), jnp.float32),
             *_doc_shapes(n, rows, dtype)])
    for dtype in (jnp.bfloat16, jnp.int8):
        out[f"rerank-L{RERANK_L}-{jnp.dtype(dtype).name}"] = (
            "maxsim_rerank", _rerank,
            [((16, Q_TOKENS, DIM), jnp.float32),
             ((16, Q_TOKENS), jnp.float32), ((16, RERANK_L), jnp.int32),
             *_doc_shapes(N_FULL, D_FULL, dtype)])
    out[f"route-K{N_CENTROIDS}"] = (
        "ivf_route", _route,
        [((64, Q_TOKENS, DIM), jnp.float32), ((64, Q_TOKENS), jnp.float32),
         ((N_CENTROIDS, DIM), jnp.float32)])
    return out


# ---------------------------------------------------------------------------
# dispatch-registry records (THE policy surface — see kernels.dispatch)
# ---------------------------------------------------------------------------

# the scan kernel's interpret mode is a sanctioned off-TPU serving path
# (kernel-body semantics validated on this host, compiled natively on TPU),
# so interpret_ok=True
DSP.register(DSP.KernelOp(
    name="maxsim_scan", probe=_probe_scan, fallback="ref",
    interpret_ok=True, kernel_impls=frozenset({"pallas"})))

# interpret-mode Pallas is a correctness tool for the gather kernel, not a
# serving path: off-TPU the fused path serves its jnp twin. Both fused
# impls count toward the candidate-path CI gate's routing signal.
DSP.register(DSP.KernelOp(
    name="maxsim_rerank", probe=_probe_rerank, fallback="jnp",
    interpret_ok=False, kernel_impls=frozenset({"pallas", "jnp"})))

# centroid routing is one small GEMM — the ref IS the fast path off-TPU
# (and the bitwise oracle contract); the kernel impl only pays on TPU
DSP.register(DSP.KernelOp(
    name="ivf_route", probe=_probe_route, fallback="ref",
    interpret_ok=False, kernel_impls=frozenset({"pallas"})))
