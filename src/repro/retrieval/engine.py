"""Mesh-sharded multi-stage MaxSim search engine over a segmented corpus.

Executes the paper's prefetch->rerank cascade (§2.4) as ONE jitted XLA
program over a corpus sharded across every chip (the "server-side single
API call", pod-scale edition). Design rules:

- documents never move: each shard scans/reranks only the documents it owns
  ("rerank where the data lives");
- the only interconnect traffic is (score, id) pairs: S*B*K*8 bytes per
  stage via all-gather — independent of D and d;
- stage-1 full-corpus scan is the largest term (N_local * D' * d bytes,
  2 * B * Q * N_local * D' * d operations); pooling shrinks it 32-64x,
  int8 storage halves its bytes again;
- later stages score only each shard's members of the global candidate set,
  compacted to a fixed per-shard cap (exact when cap >= per-shard hits;
  cap defaults to 8x the fair share);
- the corpus is a tuple of fixed-CAPACITY segments: arrays are padded to
  stable shapes and a per-doc EFFECTIVE mask NEGs dead slots (ingestion
  headroom, deleted pages, the ragged tail of an uneven shard) at every
  stage — mutation and raggedness never change compiled shapes, so
  steady-state upsert/delete/search re-dispatches cached executables;
- the effective mask is ``doc_valid`` AND the request's tenant/metadata
  filter, combined on device by ``store.effective_validity`` from the
  store companions (``doc_tenant``, ``doc_filter``) and the request's
  packed ``FilterSpec`` triple — a replicated TRACED argument of the
  compiled cascade, so tenant switches and filter changes at a fixed
  layout are pure dispatch (zero retraces), and a filtered search is
  bitwise the unfiltered search over the surviving documents;
- kernel routing (scan + fused rerank) resolves once at build time through
  the ``kernels.dispatch`` registry, the same policy table every op family
  uses;
- candidate ids live in a global SLOT space (segment offsets = cumulative
  capacities); per-segment results merge via ``merge_topk``. There is no
  divisibility constraint between corpus size and shard count: each shard
  owns ``capacity / n_shards`` slots and ``doc_valid`` masks the tail;
- the candidate path's two HBM cliffs are policy-gated away:
  ``Stage.scan_topk`` streams a RUNNING per-query top-k across corpus
  chunks (no [B, N] score write), and ``Stage.rerank_kernel`` dispatches
  rerank stages to the fused gather+MaxSim path (no materialised
  [B, L, D, d] candidate copy — scalar-prefetch Pallas kernel on TPU, the
  blockwise jnp twin elsewhere);
- in the sharded rerank merge, non-owned candidate copies DROP their slot
  id (-1 sentinel): NEG filler can then never re-enter a top-k as a
  duplicate of a live document (k > live candidates is the trigger);
- ``Stage.n_probe > 0`` replaces the stage-0 exhaustive scan with IVF
  centroid ROUTING: the query is scored against each segment's replicated
  [K, d] centroid table (``kernels.maxsim.ops.centroid_scores``), the top
  ``n_probe`` clusters' padded member-slot lists become the candidate
  rows, and those rows run through the SAME candidate-scoring machinery
  the rerank stages use (``_score_candidates`` — fused gather kernel when
  the stage asks for it). Sharded, the routing companions are replicated
  so every shard derives the identical row set, then scores only its
  owned slots via the rerank path's mine/compact/all-gather merge. The
  read bill drops from O(N*Q*d) to O((K + N*n_probe/K)*Q*d); at
  ``n_probe == K`` every live slot sits in exactly one member list so the
  routed scan recovers the exhaustive result (bitwise on multi-vector
  float stages; the routed scan ignores ``Stage.dtype``/``chunk`` — its
  working set is the probed members, not the corpus).

The single-device oracle is repro.core.multistage.search; tests assert
equality on a 1-shard mesh and overlap on multi-shard CPU meshes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map

from repro.core import maxsim as MS
from repro.core.multistage import DEFAULT_SCAN_TOPK_CHUNK, Stage
from repro.kernels import dispatch as DSP
from repro.kernels.maxsim import ops as KOPS
from repro.retrieval.store import (ROUTING_KEYS, VALIDITY_KEY,
                                   as_filter_arrays, effective_validity,
                                   filter_words, rerank_arrays,
                                   routing_arrays, scan_arrays)
from repro.retrieval.topk import (allgather_topk, gathered_merge_topk,
                                  merge_topk)
from repro.retrieval.tracing import (SCOPE_EXCHANGE, SCOPE_MASK,
                                     SCOPE_RERANK, SCOPE_SCAN, record_trace)

NEG = -1e30
INT8_REF_CHUNK = 1024      # fallback scan chunk for int8 stores in ref mode


def _flat_axes(mesh: Mesh) -> tuple:
    return tuple(mesh.axis_names)


def _mesh_shards(mesh: Mesh | None) -> int:
    if mesh is None:
        return 1
    n = 1
    for a in mesh.axis_names:
        n *= mesh.shape[a]
    return n


def _scan_arrays(store: dict, stage: Stage):
    """Resolve the scan stage's arrays: (vecs, mask, scales) — the typed
    ``VectorSchema`` accessor ``store.scan_arrays`` does the key work
    (int8 codes + scales preferred when indexed; float fallback only when
    the codes are absent — see its docstring for the roofline argument)."""
    return scan_arrays(store, stage.vector)


def _scan_prep(stage: Stage, vecs, q, scales):
    """Apply the scan stage's compute-dtype policy and the Matryoshka
    query-prefix slice (shared by the score and streamed-top-k paths)."""
    if stage.dtype is not None:
        q = q.astype(stage.dtype)
        if scales is None:                    # int8 codes must stay int8
            vecs = vecs.astype(stage.dtype)
    if vecs.shape[-1] < q.shape[-1]:          # Matryoshka stage
        q = q[..., : vecs.shape[-1]]
    return vecs, q


def _dispatch_scan(stage: Stage, vecs, mask, q, q_mask, scales,
                   impl: str, interpret: bool, doc_valid=None):
    """Score the full-corpus scan stage per the stage's dispatch policy.

    use_kernel routes to the Pallas streaming kernel (``impl`` is resolved
    once at build time: native on TPU, interpreted or the reference off
    it);
    otherwise the core.maxsim reference runs, chunked when stage.chunk > 0
    so the [B, N, Q, D] similarity intermediate is bounded at
    [B, chunk, Q, D]. [n_docs, D, d] -> [B, n_docs]. ``doc_valid`` [N] bool
    NEGs dead capacity-padding slots (threaded into the kernel wrappers, or
    applied on the ref scores).
    """
    vecs, q = _scan_prep(stage, vecs, q, scales)
    if vecs.ndim == 2:                        # single-vector stage: one GEMM
        if scales is not None:
            vecs = vecs.astype(q.dtype) * scales[..., None].astype(q.dtype)
        s = MS.maxsim_single_vector(q, vecs, q_mask)
        if doc_valid is not None:
            s = jnp.where(doc_valid[None, :], s, NEG)
        return s
    if stage.use_kernel:
        return KOPS.maxsim_scores_chunked(q, vecs, q_mask, mask, scales,
                                          doc_valid, chunk=stage.chunk,
                                          impl=impl, interpret=interpret)
    if scales is not None:
        # stream int8 through the chunked ref scorer: dequantisation happens
        # per chunk inside the scan loop, never as a full [N, D, d] float
        # copy of the corpus (that copy would undo the int8 HBM saving) —
        # hence a bounded default chunk when the stage didn't set one
        chunk = stage.chunk if stage.chunk > 0 else INT8_REF_CHUNK
        return KOPS.maxsim_scores_chunked(q, vecs, q_mask, mask, scales,
                                          doc_valid, chunk=chunk, impl="ref",
                                          interpret=True)
    s = MS.maxsim_batched(q, vecs, q_mask, mask, chunk=stage.chunk)
    if doc_valid is not None:
        s = jnp.where(doc_valid[None, :], s, NEG)
    return s


def _dispatch_scan_topk(stage: Stage, vecs, mask, q, q_mask, scales,
                        impl: str, interpret: bool, doc_valid, k: int):
    """Scan-stage select with a STREAMED running top-k: (vals, local ids)
    [B, k] without assembling the [B, N] score matrix (HBM write shrinks
    from O(B*N) to O(B*k*n_chunks) — see
    ``kernels.maxsim.ops.maxsim_topk_chunked``). Single-vector (pooled)
    scans keep score-then-select: the scan is one GEMM and the [B, N]
    scores are the GEMM output, not an avoidable intermediate."""
    vecs, q = _scan_prep(stage, vecs, q, scales)
    if vecs.ndim == 2:
        if scales is not None:
            vecs = vecs.astype(q.dtype) * scales[..., None].astype(q.dtype)
        s = MS.maxsim_single_vector(q, vecs, q_mask)
        if doc_valid is not None:
            s = jnp.where(doc_valid[None, :], s, NEG)
        return jax.lax.top_k(s, min(k, vecs.shape[0]))
    use_impl, use_interp = (impl, interpret) if stage.use_kernel \
        else ("ref", True)
    chunk = stage.chunk if stage.chunk > 0 else DEFAULT_SCAN_TOPK_CHUNK
    return KOPS.maxsim_topk_chunked(q, vecs, q_mask, mask, scales,
                                    doc_valid, k=k, chunk=chunk,
                                    impl=use_impl, interpret=use_interp)


def _score_candidates(stage_vecs, stage_mask, stage_scales, q, q_mask,
                      rows, ok, impl: str = "ref", interpret: bool = True):
    """Score per-query candidate lists against ONE segment's arrays.

    rows [B, L] in-range local slot ids; ok [B, L] marks candidates this
    caller actually owns (in-segment, on-shard, doc_valid) — the rest score
    NEG. ``stage_scales`` is set when the store's float copy was dropped
    (int8 rerank): every path dequantises the GATHERED rows, elementwise-
    commuting with the oracle's dequantise-then-gather.

    impl="ref" is the legacy gather-then-score path — same math as the
    ``multistage._score_stage`` oracle (gather, then ``maxsim_scan``) so
    the 1-segment ref path stays bitwise-comparable. Other impls route to
    the fused gather+MaxSim path (``kernels.maxsim.ops.maxsim_rerank``):
    no materialised [B, L, D, d] candidate copy. Single-vector stages are
    one small gather + GEMM either way (no memory cliff to fuse away).
    """
    if stage_vecs.shape[-1] < q.shape[-1]:    # Matryoshka rerank stage
        q = q[..., : stage_vecs.shape[-1]]
    if stage_vecs.ndim == 2:
        vecs = jnp.take(stage_vecs, rows, axis=0)              # [B, L, d]
        if stage_scales is not None:
            vecs = vecs.astype(jnp.float32) \
                * jnp.take(stage_scales, rows, axis=0)[..., None]
        if q_mask is not None:
            q = q * q_mask[..., None].astype(q.dtype)
        qs = jnp.sum(q, axis=-2)
        s = jnp.einsum("bd,bld->bl", qs, vecs.astype(qs.dtype))
        return jnp.where(ok, s, NEG)
    if impl != "ref":
        return KOPS.maxsim_rerank(q, stage_vecs, rows, q_mask, stage_mask,
                                  stage_scales, ok, impl=impl,
                                  interpret=interpret)

    def per_query(qi, qm, cl):
        dv = jnp.take(stage_vecs, cl, axis=0)                  # [L, D, d]
        if stage_scales is not None:
            dv = dv.astype(jnp.float32) \
                * jnp.take(stage_scales, cl, axis=0)[..., None]
        dm = None if stage_mask is None else jnp.take(stage_mask, cl, axis=0)
        return MS.maxsim_scan(qi, dv, qm, dm)

    qm_in = None if q_mask is None else 0
    s = jax.vmap(per_query, in_axes=(0, qm_in, 0))(q, q_mask, rows)
    return jnp.where(ok, s, NEG)


def _routed_rows(store: dict, stage: Stage, q, q_mask, impl: str,
                 interpret: bool):
    """Stage-0 candidate generation by centroid routing for ONE segment:
    score the query against the segment's [K, d] centroids, keep the top
    ``n_probe`` clusters, and emit their member-slot lists as one
    [B, n_probe * C] candidate row set (-1 marks padded member slots).
    All inputs are replicated under shard_map, so every shard derives the
    identical row set and then scores only the slots it owns."""
    routing = routing_arrays(store)
    if routing is None:
        raise ValueError(
            f"stage '{stage.vector}' sets n_probe={stage.n_probe} but the "
            "store carries no routing companions — enable routing on the "
            "SegmentedStore (Retriever(routing=...) or "
            "store.enable_routing(...)) before building the search fn")
    cents, members = routing                          # [K, d], [K, C]
    cs = KOPS.centroid_scores(q, cents, q_mask, impl=impl,
                              interpret=interpret)    # [B, K]
    _, cid = jax.lax.top_k(cs, min(stage.n_probe, cents.shape[0]))
    return jnp.take(members, cid, axis=0).reshape(q.shape[0], -1)


def _offsets(capacities: tuple) -> tuple:
    offs, off = [], 0
    for cap in capacities:
        offs.append(off)
        off += cap
    return tuple(offs)


def _segment_stage0(stage: Stage, store: dict, eff, cap: int, off, q,
                    q_mask, *, routed: bool, impl: str, interpret: bool,
                    rt_impl: str, rt_interpret: bool, r0_impl: str,
                    r0_interpret: bool):
    """Stage-0 candidate generation over ONE segment (single-host path):
    (vals [B, k0], GLOBAL slot ids [B, k0]) with
    k0 = min(stage.k, cap[, probed rows]). ``off`` shifts local slot ids
    into the global slot space; it may be a Python int (the joint cascade
    body bakes offsets in) or a traced int32 scalar (the tiered
    per-segment executable takes it as data, so ONE compiled fn serves
    every same-layout segment regardless of its position in the scope).
    The math is shared with the joint ``local_body`` — the tiered
    per-segment pipeline scores each segment bitwise-identically by
    construction."""
    if routed:
        rows = _routed_rows(store, stage, q, q_mask, rt_impl, rt_interpret)
        rclip = jnp.clip(rows, 0, cap - 1)
        ok = rows >= 0                  # -1 = padded member slot
        if eff is not None:
            ok = ok & jnp.take(eff, rclip, axis=0)
        s = _score_candidates(*_scan_arrays(store, stage), q, q_mask,
                              rclip, ok, r0_impl, r0_interpret)
        v, sel = jax.lax.top_k(s, min(stage.k, cap, rows.shape[1]))
        # dead winners (k > live probed members) drop their slot id —
        # -1 is the filler sentinel
        i = jnp.where(jnp.take_along_axis(ok, sel, axis=1),
                      jnp.take_along_axis(rclip, sel, axis=1) + off, -1)
        return v, i
    vecs, mask, scales = _scan_arrays(store, stage)
    if stage.scan_topk:
        v, i = _dispatch_scan_topk(stage, vecs, mask, q, q_mask, scales,
                                   impl, interpret, eff, min(stage.k, cap))
    else:
        s = _dispatch_scan(stage, vecs, mask, q, q_mask, scales, impl,
                           interpret, doc_valid=eff)
        v, i = jax.lax.top_k(s, min(stage.k, cap))
    return v, i + off


def _segment_rerank(stage: Stage, store: dict, eff, cap: int, off, q,
                    q_mask, cand, rr_impl: str, rr_interpret: bool):
    """One rerank stage's scores for the global candidate set against ONE
    segment: [B, L]; out-of-segment / filtered / dead candidates score
    NEG, so the cross-segment combine is an elementwise max. ``off``
    follows ``_segment_stage0`` (Python int in the joint body, traced
    scalar in the tiered per-segment executable)."""
    local = cand - off
    in_seg = (local >= 0) & (local < cap)
    rows = jnp.clip(local, 0, cap - 1)
    ok = in_seg
    if eff is not None:
        ok = ok & jnp.take(eff, rows, axis=0)
    return _score_candidates(*rerank_arrays(store, stage.vector),
                             q, q_mask, rows, ok, rr_impl, rr_interpret)


def _build_body(mesh: Mesh | None, stages: tuple, capacities: tuple,
                rerank_overcommit: int):
    """The (unjitted) cascade over a tuple of segment store dicts.

    fn(stores: tuple[dict, ...], q [B,Q,d], q_mask [B,Q],
    fspec (tenant (), require [W], any [W])) ->
    (scores [B,k], global slot ids [B,k]). ``fspec`` is the packed
    request-filter triple (``store.as_filter_arrays``) — traced data, so
    every FilterSpec at this layout dispatches one executable.
    """
    assert capacities, "search needs at least one segment"
    # kernel routing resolves ONCE at build time through the dispatch
    # registry: the scan stage's streaming kernel (interpret-mode capable
    # off-TPU) and the fused gather+rerank path (jnp twin off-TPU). Stages
    # with use_kernel/rerank_kernel False run the reference. Stage-0
    # resolution (incl. the routed stage's two extra families) is shared
    # with the tiered per-segment builders via _resolve_stage0 so the
    # joint and per-segment executables route identically.
    r0 = _resolve_stage0(stages)
    routed = r0["routed"]
    impl, interpret = r0["impl"], r0["interpret"]
    rt_impl, rt_interpret = r0["rt_impl"], r0["rt_interpret"]
    r0_impl, r0_interpret = r0["r0_impl"], r0["r0_interpret"]
    rr_impl, rr_interpret = DSP.resolve(
        "maxsim_rerank", any(s.rerank_kernel for s in stages[1:]))
    offsets = _offsets(capacities)
    total_cap = sum(capacities)

    def rerank_dispatch(stage):
        return (rr_impl, rr_interpret) if stage.rerank_kernel \
            else ("ref", True)

    if mesh is None:
        def local_body(stores, q, q_mask, fspec):
            record_trace()
            # one effective mask per segment — doc_valid AND the request's
            # tenant/filter terms — computed once and threaded through
            # every stage
            with jax.named_scope(SCOPE_MASK):
                effs = tuple(effective_validity(s, fspec) for s in stores)
            scores = cand = None
            for si, stage in enumerate(stages):
                with jax.named_scope(SCOPE_SCAN if si == 0 else SCOPE_RERANK):
                    if si == 0:
                        parts_v, parts_i = [], []
                        for store, eff, cap, off in zip(
                                stores, effs, capacities, offsets):
                            v, i = _segment_stage0(
                                stage, store, eff, cap, off, q, q_mask,
                                routed=routed, impl=impl, interpret=interpret,
                                rt_impl=rt_impl, rt_interpret=rt_interpret,
                                r0_impl=r0_impl, r0_interpret=r0_interpret)
                            parts_v.append(v)
                            parts_i.append(i)
                        scores, cand = merge_topk(
                            jnp.concatenate(parts_v, axis=1),
                            jnp.concatenate(parts_i, axis=1),
                            min(stage.k, total_cap))
                    else:
                        s_all = None
                        for store, eff, cap, off in zip(
                                stores, effs, capacities, offsets):
                            s = _segment_rerank(stage, store, eff, cap, off,
                                                q, q_mask, cand,
                                                *rerank_dispatch(stage))
                            # each candidate lives in exactly one segment; the
                            # others scored it NEG, so max == owner's score
                            s_all = s if s_all is None \
                                else jnp.maximum(s_all, s)
                        k = min(stage.k, cand.shape[1])
                        scores, sel = jax.lax.top_k(s_all, k)
                        cand = jnp.take_along_axis(cand, sel, axis=1)
            return scores, cand
        return local_body

    axes = _flat_axes(mesh)
    n_shards = _mesh_shards(mesh)
    for cap in capacities:
        # segment capacities are shard-padded at allocation; raw corpora are
        # shard-padded by make_search_fn — there is NO n_docs divisibility
        # constraint, only this internal invariant on padded capacities
        assert cap % n_shards == 0, (cap, n_shards)

    def body(stores, q, q_mask, fspec):
        record_trace()
        shard_idx = jax.lax.axis_index(axes)
        # per-segment effective mask over the LOCAL slab (the companions
        # shard along docs with everything else; fspec is replicated)
        with jax.named_scope(SCOPE_MASK):
            effs = tuple(effective_validity(s, fspec) for s in stores)
        scores = cand = None
        for si, stage in enumerate(stages):
            with jax.named_scope(SCOPE_SCAN if si == 0 else SCOPE_RERANK):
                if si == 0:
                    parts_v, parts_i = [], []
                    for store, eff, cap, off in zip(stores, effs, capacities,
                                                    offsets):
                        n_local = cap // n_shards
                        if routed:
                            # replicated routing inputs -> every shard
                            # derives the identical candidate rows, then the
                            # rerank stages' mine/compact machinery scores
                            # only the owned slots. cap_slots >= n_local
                            # whenever K*C >= capacity (the member-width
                            # invariant), so the compaction is EXACT at
                            # n_probe == K — parity mode survives sharding.
                            rows = _routed_rows(store, stage, q, q_mask,
                                                rt_impl, rt_interpret)
                            R = rows.shape[1]
                            rclip = jnp.clip(rows, 0, cap - 1)
                            cap_slots = min(R, max(1, -(-R // n_shards))
                                            * rerank_overcommit)
                            mine = (rows >= 0) \
                                & (rclip // n_local == shard_idx)
                            order = jnp.argsort(~mine, axis=1)[:, :cap_slots]
                            rsel = jnp.take_along_axis(rclip % n_local, order,
                                                       axis=1)
                            gsel = jnp.take_along_axis(rclip, order, axis=1)
                            ok = jnp.take_along_axis(mine, order, axis=1)
                            if eff is not None:
                                ok = ok & jnp.take(eff, rsel, axis=0)
                            s = _score_candidates(
                                *_scan_arrays(store, stage), q, q_mask,
                                rsel, ok, r0_impl, r0_interpret)
                            v, sel = jax.lax.top_k(
                                s, min(stage.k, cap, cap_slots))
                            gi = jnp.where(
                                jnp.take_along_axis(ok, sel, axis=1),
                                jnp.take_along_axis(gsel, sel, axis=1) + off,
                                -1)
                            v, i = gathered_merge_topk(v, gi,
                                                       min(stage.k, cap), axes)
                            parts_v.append(v)
                            parts_i.append(i)
                            continue
                        vecs, mask, scales = _scan_arrays(store, stage)
                        if stage.scan_topk:
                            # streamed per-shard running top-k; ids shift into
                            # the global slot space before the gather-merge
                            v, i = _dispatch_scan_topk(
                                stage, vecs, mask, q, q_mask, scales,
                                impl, interpret, eff, min(stage.k, cap))
                            v, i = gathered_merge_topk(
                                v, i + shard_idx * n_local + off,
                                min(stage.k, cap), axes)
                        else:
                            s_loc = _dispatch_scan(stage, vecs, mask, q,
                                                   q_mask, scales, impl,
                                                   interpret)
                            v, i = allgather_topk(s_loc, min(stage.k, cap),
                                                  axes, shard_idx, n_local,
                                                  valid_local=eff,
                                                  seg_offset=off)
                        parts_v.append(v)
                        parts_i.append(i)
                    scores, cand = merge_topk(
                        jnp.concatenate(parts_v, axis=1),
                        jnp.concatenate(parts_i, axis=1),
                        min(stage.k, total_cap))
                else:
                    L = cand.shape[1]
                    cap_slots = min(L, max(1, -(-L // n_shards))
                                    * rerank_overcommit)
                    parts_v, parts_i = [], []
                    for store, eff, cap, off in zip(stores, effs, capacities,
                                                    offsets):
                        n_local = cap // n_shards
                        local = cand - off
                        in_seg = (local >= 0) & (local < cap)
                        lclip = jnp.clip(local, 0, cap - 1)
                        mine = in_seg & (lclip // n_local == shard_idx)
                        order = jnp.argsort(~mine, axis=1)[:, :cap_slots]
                        rows = jnp.take_along_axis(lclip % n_local, order,
                                                   axis=1)
                        ok = jnp.take_along_axis(mine, order, axis=1)
                        if eff is not None:
                            ok = ok & jnp.take(eff, rows, axis=0)
                        s = _score_candidates(
                            *rerank_arrays(store, stage.vector),
                            q, q_mask, rows, ok, *rerank_dispatch(stage))
                        # merge shards/segments: each candidate scored real on
                        # exactly one (shard, segment); NEG everywhere else.
                        # Non-owned copies also DROP their slot id (-1): when
                        # k exceeds the live candidates, NEG filler wins top-k
                        # slots, and a filler copy carrying a live slot id
                        # would DUPLICATE that document in the result. -1 is
                        # the dead-filler sentinel end-to-end (Retriever
                        # translates it to page id -1; a later stage scores it
                        # NEG in every segment since it is in-segment nowhere).
                        gi = jnp.where(ok, jnp.take_along_axis(cand, order,
                                                               axis=1), -1)
                        with jax.named_scope(SCOPE_EXCHANGE):
                            parts_v.append(jax.lax.all_gather(
                                s, axes, axis=1, tiled=True))
                            parts_i.append(jax.lax.all_gather(
                                gi, axes, axis=1, tiled=True))
                    with jax.named_scope(SCOPE_EXCHANGE):
                        scores, cand = merge_topk(
                            jnp.concatenate(parts_v, axis=1),
                            jnp.concatenate(parts_i, axis=1),
                            min(stage.k, L))
        return scores, cand

    def searcher(stores, q, q_mask, fspec):
        # the [K, d]/[K, C] routing companions are replicated — their
        # member slot ids index the WHOLE segment, not a shard slab
        specs = tuple({k: (P() if k in ROUTING_KEYS else
                           (P(axes) if v.ndim >= 1 else P()))
                       for k, v in store.items()} for store in stores)
        # the filter triple is replicated: every shard applies the same
        # request predicate to its local slab
        fn = shard_map(body, mesh=mesh,
                       in_specs=(specs, P(), P(), (P(), P(), P())),
                       out_specs=(P(), P()),
                       check_vma=False)
        return fn(stores, q, q_mask, fspec)

    return searcher


def make_segmented_search_fn(mesh: Mesh | None, stages: tuple,
                             capacities: tuple,
                             rerank_overcommit: int = 8):
    """Build the jitted multi-segment search callable.

    Returns fn(stores: tuple[dict, ...], q [B,Q,d], q_mask [B,Q],
    fspec=None) -> (scores [B,k], global slot ids [B,k]). ``fspec`` is a
    ``store.FilterSpec`` (or an already-packed triple, or None for the
    match-everything filter) normalised host-side to the traced triple the
    compiled cascade takes. Compiled shapes depend only on (stages,
    capacities, mesh, filter width) — never on fill level OR filter
    values — which is what lets a ``Retriever`` upsert/delete AND swap
    tenants/filters without retracing.
    """
    jfn = jax.jit(_build_body(mesh, stages, tuple(capacities),
                              rerank_overcommit))

    def fn(stores, q, q_mask, fspec=None):
        w = filter_words(stores[0]) if stores else 0
        return jfn(stores, q, q_mask, as_filter_arrays(fspec, w))

    return fn


def _resolve_stage0(stages: tuple):
    """Build-time dispatch resolution for stage 0 — the SAME calls, in the
    same order, as ``_build_body``, so a per-segment executable and the
    joint cascade route every op family identically (a precondition for
    the tiered pipeline's bitwise-parity contract)."""
    impl, interpret = DSP.resolve(
        "maxsim_scan", bool(stages and stages[0].use_kernel))
    routed = bool(stages and stages[0].n_probe > 0)
    rt_impl, rt_interpret = DSP.resolve(
        "ivf_route", routed and stages[0].use_kernel)
    r0_impl, r0_interpret = DSP.resolve(
        "maxsim_rerank",
        routed and (stages[0].use_kernel or stages[0].rerank_kernel))
    return dict(routed=routed, impl=impl, interpret=interpret,
                rt_impl=rt_impl, rt_interpret=rt_interpret,
                r0_impl=r0_impl, r0_interpret=r0_interpret)


def make_segment_scan_fn(stages: tuple, capacity: int):
    """Jitted stage-0 over ONE segment, for the tiered per-segment
    pipeline (``repro.retrieval.tiering``, single-host meshes).

    Returns fn(store: dict, q [B,Q,d], q_mask [B,Q], fspec, offset) ->
    (vals [B,k0], GLOBAL slot ids [B,k0]). ``offset`` is passed as a
    TRACED int32 scalar — a segment's position in the scope is data, not
    shape — so one compiled executable serves every segment of this
    layout and residency churn never adds a retrace axis. The body is
    ``_segment_stage0``, the exact code the joint cascade runs per
    segment, with dispatch resolved by the same build-time policy."""
    stages = tuple(stages)
    assert stages, "search needs at least one stage"
    stage = stages[0]
    r0 = _resolve_stage0(stages)

    def seg_scan(store, q, q_mask, fspec, offset):
        record_trace()
        with jax.named_scope(SCOPE_MASK):
            eff = effective_validity(store, fspec)
        with jax.named_scope(SCOPE_SCAN):
            return _segment_stage0(stage, store, eff, capacity, offset,
                                   q, q_mask, **r0)

    jfn = jax.jit(seg_scan)

    def fn(store, q, q_mask, fspec, offset):
        return jfn(store, q, q_mask,
                   as_filter_arrays(fspec, filter_words(store)),
                   jnp.asarray(offset, jnp.int32))

    return fn


def make_segment_rerank_fn(stages: tuple, stage_index: int, capacity: int):
    """Jitted rerank-stage scorer over ONE segment (tiered pipeline twin
    of the joint body's rerank block — same ``_segment_rerank`` math,
    same build-time dispatch policy).

    Returns fn(store, q, q_mask, fspec, offset, cand [B,L]) -> [B,L]
    scores with NEG for candidates this segment doesn't own; the driver
    combines segments with an elementwise max (exact: each candidate is
    real in exactly one segment). ``offset`` is traced data, as in
    ``make_segment_scan_fn``."""
    stages = tuple(stages)
    stage = stages[stage_index]
    rr_impl, rr_interpret = DSP.resolve(
        "maxsim_rerank", any(s.rerank_kernel for s in stages[1:]))
    if not stage.rerank_kernel:
        rr_impl, rr_interpret = "ref", True

    def seg_rerank(store, q, q_mask, fspec, offset, cand):
        record_trace()
        with jax.named_scope(SCOPE_MASK):
            eff = effective_validity(store, fspec)
        with jax.named_scope(SCOPE_RERANK):
            return _segment_rerank(stage, store, eff, capacity, offset,
                                   q, q_mask, cand, rr_impl, rr_interpret)

    jfn = jax.jit(seg_rerank)

    def fn(store, q, q_mask, fspec, offset, cand):
        return jfn(store, q, q_mask,
                   as_filter_arrays(fspec, filter_words(store)),
                   jnp.asarray(offset, jnp.int32), cand)

    return fn


def make_search_fn(mesh: Mesh | None, stages: tuple, n_docs: int,
                   rerank_overcommit: int = 8):
    """Build the jitted search callable over a single raw store dict.

    Returns fn(store_vectors: dict, q [B,Q,d], q_mask [B,Q], fspec=None)
    -> (scores [B,k], ids [B,k]). ``fspec`` follows
    ``make_segmented_search_fn``: a ``FilterSpec``/packed triple/None,
    applied against whichever store companions the dict carries (a raw
    store without ``doc_tenant``/``doc_filter`` simply skips those terms).

    Matches the repro.core.multistage.search oracle bitwise when the scan
    stage runs in ref mode on a bf16/f32 store (use_kernel dispatch and
    int8 storage trade exactness for throughput; chunking and filtering do
    not). Ragged corpora are fine on any mesh: arrays are shard-padded
    inside the compiled fn and the tail masked via ``doc_valid`` (zero-copy
    when ``n_docs`` already divides evenly).
    """
    n_shards = _mesh_shards(mesh)
    cap = -(-n_docs // n_shards) * n_shards
    body = _build_body(mesh, stages, (cap,), rerank_overcommit)

    def _pad_rows(v, n, to):
        if v.ndim >= 1 and v.shape[0] == n and to != n:
            return jnp.pad(v, ((0, to - n),) + ((0, 0),) * (v.ndim - 1))
        return v

    def inner(store, q, q_mask, fspec):
        src = dict(store)
        dv = src.pop(VALIDITY_KEY, None)
        if dv is None:
            dv = jnp.ones((n_docs,), bool)
        # the tenant/filter companions (if present) pad with zeros, which
        # is irrelevant: the padded tail is doc_valid-False anyway
        padded = {k: _pad_rows(v, n_docs, cap) for k, v in src.items()}
        padded[VALIDITY_KEY] = _pad_rows(dv, n_docs, cap)  # pads False
        return body((padded,), q, q_mask, fspec)

    jfn = jax.jit(inner)

    def fn(store, q, q_mask, fspec=None):
        return jfn(store, q, q_mask,
                   as_filter_arrays(fspec, filter_words(store)))

    return fn


def store_shardings(mesh: Mesh | None, store_vectors: dict) -> dict | None:
    if mesh is None:
        return None
    axes = _flat_axes(mesh)
    return {k: NamedSharding(mesh, P() if k in ROUTING_KEYS else P(axes))
            for k in store_vectors}
