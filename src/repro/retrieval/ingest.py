"""Device-resident ingest pipeline: the write path as a serving workload.

PR 2 made corpus MUTATION retrace-free and PR 3 did the same for query
traffic; this module closes the third and final axis of the no-retrace
contract — INGESTION. At millions of pages, index construction is a
serving workload, not a preprocessing script (PLAID's index-build-cost
argument), yet the legacy write path ran as a host-driven, per-batch-shape
monolith: eager reference pooling, a second full quantisation pass that
round-tripped through float32, then a third pass writing into segment
headroom.

``IngestPipeline`` fuses the whole write path under ONE jit per
``(cfg, batch-bucket)``:

    hygiene mask -> model-aware pooling (resolved through the
    ``kernels.dispatch`` registry like the scan path: the fused operator
    with reference fallback) -> global pool -> optional int8 quantisation
    -> ``dynamic_update_slice`` directly into segment headroom — including
    the tenant-id and packed tag-bitset store companions, stamped from
    traced values (tenant churn never retraces)

On a doc-sharded store (``SegmentedStore.mesh``) the same fused program
runs under ``shard_map`` (``IngestPipeline.sharded_write``): the batch
is replicated, every device indexes it with the pooling kernel at its
own shapes, and writes only the rows its shard owns.

Batch sizes are padded into power-of-two INGEST BUCKETS (symmetric with
the bucketed segment capacities of PR 2 and the query-shape buckets of
PR 3), and ``tracing.record_trace()`` sits in the traced body, so after
one warm-up trace per bucket, steady-state ingestion of arbitrary
in-bounds batch sizes is pure dispatch. Raw encoder output goes in,
stable page ids come out — no host round-trip of the indexed arrays.

Pooling dispatch policy (``use_kernel``):
- True  -> the fused one-matmul pooling operator ``pool_pages_fused``
  (Pallas kernel on TPU, its jnp twin elsewhere; per-page dynamic
  ``h_eff`` falls back to the reference path, which is the only
  geometry the matrix formulation cannot express);
- False -> the functional ``core.pooling`` reference, bit-for-bit the
  historical ``build_store`` semantics (``build_store`` wraps this mode).

Entry points::

    pipe = IngestPipeline.for_config(cfg, quantize=("mean_pooling",),
                                     stages=stages)
    r = Retriever(seed_store, capacity=1 << 16, ingest=pipe)
    ids = r.ingest(raw_pages, token_types)     # fused, zero-retrace
    batch = pipe.index(raw_pages, token_types) # standalone VectorStore
"""
from __future__ import annotations

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import hygiene as HG
from repro.core.pooling import global_pool, pool_pages_batch
from repro.kernels import dispatch as DSP
from repro.kernels.pooling import ops as POPS
from repro.kernels.pooling.ops import pool_pages_fused
from repro.retrieval import tracing
from repro.retrieval.segments import bucket_capacity, sharded_write
from repro.retrieval.store import (FILTER_KEY, TENANT_KEY, VALIDITY_KEY,
                                   VectorStore, is_store_companion,
                                   mask_key, pack_tags, quantize_vectors)

INGEST_BUCKET_MIN = 8
INGEST_BUCKET_MAX = 256        # the paper's index step (pages_per_step)
_BULK_GRANULE = 64


def batch_bucket(n: int, min_bucket: int = INGEST_BUCKET_MIN) -> int:
    """The static ingest-batch family. Up to ``INGEST_BUCKET_MAX``
    (steady-state serving batches): smallest power-of-two >= n — literally
    ``segments.bucket_capacity``'s ladder, so ingest buckets can never
    drift out of sync with the segment capacities they're documented as
    symmetric with. Above it (one-shot BULK builds through the
    ``build_store`` wrapper), power-of-two padding would waste up to ~2x
    compute on the padded rows, so the bucket is the next 64-row granule
    instead — <25% worst-case overhead, still a bounded shape family."""
    if n < 1:
        raise ValueError(f"ingest batch must be >= 1 page, got {n}")
    if n > INGEST_BUCKET_MAX:
        return -(-n // _BULK_GRANULE) * _BULK_GRANULE
    return bucket_capacity(n, min_capacity=min_bucket)


def _pad_rows(x: jax.Array, to: int, fill=0) -> jax.Array:
    n = x.shape[0]
    if n == to:
        return x
    return jnp.pad(x, ((0, to - n),) + ((0, 0),) * (x.ndim - 1),
                   constant_values=fill)


_PIPELINES: dict = {}


class IngestPipeline:
    """Fused hygiene -> pooling -> quantise -> write, one jit per
    ``(cfg, batch bucket)`` (plus segment layout for the write path).

    ``quantize``/``stages`` follow the ``quantize_store`` policy: names to
    int8-quantise, and the cascade that decides which float copies are
    dead weight. A pipeline produces ONE fixed set of named arrays; the
    segments it writes into must have been allocated with the same set
    (``Retriever(build-matching-store, ingest=pipe)``).
    """

    def __init__(self, cfg, *, store_dtype=jnp.bfloat16,
                 experimental_smooth: str | None = None,
                 quantize: tuple = (), stages: tuple | None = None,
                 use_kernel: bool = True, impl: str | None = None,
                 interpret: bool | None = None,
                 min_bucket: int = INGEST_BUCKET_MIN):
        self.cfg = cfg
        self.store_dtype = jnp.dtype(store_dtype)
        self.experimental_smooth = experimental_smooth
        self.quantize = tuple(quantize)
        self.stages = None if stages is None else tuple(stages)
        self.use_kernel = use_kernel
        self.min_bucket = min_bucket
        # resolved ONCE at build time, like the scan path: Pallas where it
        # compiles natively, the jnp twin elsewhere (tests may force an
        # explicit impl/interpret to exercise the interpreted kernel)
        r_impl, r_interp = DSP.resolve("pooling", use_kernel)
        self.impl = r_impl if impl is None else impl
        self.interpret = r_interp if interpret is None else interpret
        self._mats = {}
        if use_kernel:
            self._mats["mean_pooling"] = self._static_operator(cfg)
            if experimental_smooth:
                self._mats["experimental"] = self._static_operator(
                    dataclasses.replace(cfg, smooth=experimental_smooth))
        for name in self.quantize:
            if name not in self._produced_names():
                raise ValueError(
                    f"quantize name {name!r} not among produced vectors "
                    f"{self._produced_names()}")
        # one jit each; the cache keys itself on (bucket, h_eff presence)
        # and, for the write path, the segment layout
        self._jit_index = jax.jit(
            lambda pages, tt, h: self._index_arrays(pages, tt, h))
        self._jit_write = jax.jit(self._write_body)
        self._sharded_writes: dict = {}       # mesh -> sharded_write(mesh)
        self.produced_keys = tuple(sorted(jax.eval_shape(
            lambda p, t: self._index_arrays(p, t, None),
            jax.ShapeDtypeStruct((self.min_bucket, cfg.seq_len,
                                  cfg.out_dim), jnp.float32),
            jax.ShapeDtypeStruct((cfg.seq_len,), jnp.int32))))

    @classmethod
    def for_config(cls, cfg, *, store_dtype=jnp.bfloat16,
                   experimental_smooth: str | None = None,
                   quantize: tuple = (), stages: tuple | None = None,
                   use_kernel: bool = True, impl: str | None = None,
                   interpret: bool | None = None,
                   min_bucket: int = INGEST_BUCKET_MIN) -> "IngestPipeline":
        """Shared pipeline per (cfg, options) — the process-wide cache that
        keeps repeated ``build_store`` calls at steady-state batch shapes
        pure dispatch instead of a fresh trace per call."""
        key = (cfg, jnp.dtype(store_dtype).name, experimental_smooth,
               tuple(quantize), None if stages is None else tuple(stages),
               use_kernel, impl, interpret, min_bucket)
        pipe = _PIPELINES.get(key)
        if pipe is None:
            pipe = _PIPELINES[key] = cls(
                cfg, store_dtype=store_dtype,
                experimental_smooth=experimental_smooth, quantize=quantize,
                stages=stages, use_kernel=use_kernel, impl=impl,
                interpret=interpret, min_bucket=min_bucket)
        return pipe

    # ------------------------------------------------------------------
    # static layout
    # ------------------------------------------------------------------

    @property
    def pool_path(self) -> str:
        """Where static-geometry pooling actually dispatches:
        ``fused-pallas`` (the kernel, compiled natively), ``fused-jnp``
        (the factored jnp twin), or ``reference`` (the functional
        ``core.pooling`` chain, i.e. ``use_kernel=False``). The ingest
        benchmark records this and CI asserts the kernel-mode pipeline
        really routes to a fused operator."""
        if not self.use_kernel:
            return "reference"
        return "fused-pallas" if self.impl == "pallas" else "fused-jnp"

    def _produced_names(self) -> tuple:
        names = ["initial", "mean_pooling", "global_pooling"]
        if self.experimental_smooth:
            names.append("experimental")
        return tuple(names)

    @staticmethod
    def _static_operator(cfg) -> dict:
        """Both evaluations of the fused pooling operator: the full
        [n_out, S] matrix (what the Pallas kernel streams on TPU) and its
        factored form (group reshape-sum + small stage-2 matrix — the
        fast jnp twin everywhere else)."""
        pm, row_valid = POPS.pooling_matrix_static(cfg)
        g, p2, _ = POPS.pooling_factors(cfg)
        return {"mat": jnp.asarray(pm), "p2": jnp.asarray(p2),
                "n_groups": g, "row_valid": jnp.asarray(row_valid)}

    # ------------------------------------------------------------------
    # traced bodies
    # ------------------------------------------------------------------

    def _pool(self, name: str, cfg, vis, vis_mask, h_eff):
        """Model-aware pooling dispatch: the fused one-matmul operator
        when enabled and expressible (static geometry), the functional
        reference otherwise."""
        if not self.use_kernel or h_eff is not None:
            return pool_pages_batch(cfg, vis, vis_mask, h_eff)
        op = self._mats[name]
        if self.impl == "pallas":
            pooled = pool_pages_fused(vis, vis_mask, op["mat"],
                                      impl="pallas",
                                      interpret=self.interpret)
        else:
            pooled = POPS.pool_pages_grouped(vis, vis_mask, op["p2"],
                                             op["n_groups"])
        pmask = jnp.broadcast_to(op["row_valid"][None], pooled.shape[:-1])
        return pooled, pmask

    def _index_arrays(self, pages, token_types, h_eff) -> dict:
        """pages [B, S, d] f32 + token_types [S]|[B, S] -> the named-vector
        dict for the batch (store dtype, quantisation applied). Rows are
        independent, so bucket padding never perturbs real pages."""
        tracing.record_trace()
        cfg = self.cfg
        N, S, _ = pages.shape
        if token_types.ndim == 1:
            token_types = jnp.broadcast_to(token_types[None], (N, S))
        emb, keep = HG.apply_hygiene(pages, token_types)

        # physically separate visual tokens (static layout: specials lead,
        # validated host-side by hygiene.require_visual_tail)
        n_vis = cfg.n_patches
        vis = emb[:, S - n_vis:]
        vis_mask = keep[:, S - n_vis:]
        sd = self.store_dtype

        pooled, pooled_mask = self._pool("mean_pooling", cfg, vis, vis_mask,
                                         h_eff)
        vectors = {
            "initial": vis.astype(sd),
            mask_key("initial"): vis_mask,
            "mean_pooling": pooled.astype(sd),
            mask_key("mean_pooling"): pooled_mask,
            "global_pooling": jax.vmap(global_pool)(vis, vis_mask).astype(sd),
        }
        if self.experimental_smooth:
            cfg2 = dataclasses.replace(cfg, smooth=self.experimental_smooth)
            exp, exp_mask = self._pool("experimental", cfg2, vis, vis_mask,
                                       h_eff)
            vectors["experimental"] = exp.astype(sd)
            vectors[mask_key("experimental")] = exp_mask
        if self.quantize:
            vectors = quantize_vectors(vectors, self.quantize, self.stages)
        return vectors

    def _blocks(self, pages, token_types, n_real, tenant,
                filter_row) -> dict:
        """Index the (bucket-padded) batch into the bucket-wide row block
        the write lays down for every segment array, store companions
        included. The rows beyond ``n_real`` are padding: their content is
        the allocation state (zeros, invalid, tenant 0, no tags)."""
        batch = self._index_arrays(pages, token_types, None)
        bucket = pages.shape[0]
        row_valid = jnp.arange(bucket) < n_real
        # zero the padding rows' derived content (pooled masks and
        # quantisation scales are nonzero even for zero pages), so a
        # never-claimed slot holds exactly its allocation state and
        # segment arrays stay bitwise-identical to the legacy
        # build_store + add_pages path
        out = {k: jnp.where(
            row_valid.reshape((bucket,) + (1,) * (v.ndim - 1)),
            v, jnp.zeros_like(v)) for k, v in batch.items()}
        out[VALIDITY_KEY] = row_valid
        # the batch's tenant id and packed tag bitset are traced VALUES
        # stamped onto the claimed rows — different tenants/tags reuse
        # the executable
        out[TENANT_KEY] = jnp.where(row_valid, tenant, jnp.int32(0))
        out[FILTER_KEY] = jnp.where(
            row_valid[:, None],
            jnp.broadcast_to(filter_row[None, :],
                             (bucket, filter_row.shape[0])),
            jnp.uint32(0))
        return out

    def _write_body(self, seg_vectors: dict, pages, token_types,
                    start, n_real, tenant, filter_row) -> dict:
        """Index the (bucket-padded) batch and write it into the segment's
        preallocated tail in the same program, as one full-bucket
        ``dynamic_update_slice`` per array (a contiguous block copy — XLA
        scatter is loop-slow on exactly these shapes). The slots beyond
        ``n_real`` receive the padding rows' content but their
        ``doc_valid`` bits stay False and the next batch starts at
        ``start + n_real``, overwriting them; ``reserve`` guarantees a
        full bucket of tail headroom so the DUS start clamp can never
        reach back into live rows."""
        out = dict(seg_vectors)
        for k, v in self._blocks(pages, token_types, n_real, tenant,
                                 filter_row).items():
            idx = (start,) + (0,) * (v.ndim - 1)
            out[k] = jax.lax.dynamic_update_slice(
                seg_vectors[k], v.astype(seg_vectors[k].dtype), idx)
        return out

    def sharded_write(self, mesh):
        """The fused write over a doc-sharded segment on ``mesh``: jitted
        ``fn(seg_vectors, start, pages, token_types, n_real, tenant,
        filter_row)`` (``segments.sharded_write``) for the arrays the
        batch writes. The whole program runs under ``shard_map``, so the
        pooling kernel runs on each device as a kernel of that device's
        shapes (the TPU compiler cannot partition a kernel itself). The
        batch comes in replicated; every shard indexes it (``_blocks``)
        and writes only the block rows that fall in its own slab, so a
        block that straddles a shard boundary is split between its owners
        and no shard gathers the segment. Made once per mesh."""
        fn = self._sharded_writes.get(mesh)
        if fn is None:
            fn = self._sharded_writes[mesh] = sharded_write(mesh,
                                                            self._blocks)
        return fn

    # ------------------------------------------------------------------
    # host entry points
    # ------------------------------------------------------------------

    def _admit(self, pages, token_types) -> tuple:
        pages = jnp.asarray(pages, jnp.float32)
        if pages.ndim != 3 or pages.shape[1] != self.cfg.seq_len:
            raise ValueError(
                f"pages must be [N, S={self.cfg.seq_len}, d] raw encoder "
                f"output, got shape {pages.shape}")
        HG.require_visual_tail(token_types, self.cfg.n_patches)
        return pages, jnp.asarray(token_types)

    def index(self, pages, token_types, h_eff=None) -> VectorStore:
        """Index a raw batch into a standalone ``VectorStore`` (the
        ``build_store`` work, bucket-padded and fused under one jit)."""
        pages, tt = self._admit(pages, token_types)
        n = int(pages.shape[0])
        bucket = batch_bucket(n, self.min_bucket)
        pages_p = _pad_rows(pages, bucket)
        if tt.ndim == 2:
            tt = _pad_rows(tt, bucket, fill=HG.PAD)
        h = None if h_eff is None else _pad_rows(
            jnp.asarray(h_eff), bucket, fill=self.cfg.grid_h)
        out = self._jit_index(pages_p, tt, h)
        return VectorStore({k: v[:n] for k, v in out.items()}, n,
                           self.store_dtype.name)

    def ingest(self, store, pages, token_types, tenant: int = 0,
               tags=()) -> np.ndarray:
        """Index a raw batch and write it straight into ``store``'s
        segment headroom (a ``SegmentedStore``) — one fused dispatch, no
        host round-trip. Returns the assigned stable page ids.

        ``tenant``/``tags`` stamp the batch's store companions exactly as
        ``SegmentedStore.add_pages`` does, as traced values inside the
        same fused write program.

        ``store.commit`` is the single landing point for both this path
        and ``add_pages``, so when the store has IVF routing enabled
        (``SegmentedStore.enable_routing``) the freshly written slots are
        assigned to their nearest cluster there — ingested pages are
        immediately reachable by routed scan stages, at the same
        zero-steady-state-retrace cost (see ``repro.retrieval.routing``)."""
        pages, tt = self._admit(pages, token_types)
        n = int(pages.shape[0])
        if store.segments:
            have = {k for k in store.segments[0].vectors
                    if not is_store_companion(k)}
            if have != set(self.produced_keys):
                raise ValueError(
                    f"pipeline produces {sorted(self.produced_keys)} but "
                    f"the store's segments hold {sorted(have)} — build the "
                    "seed store with the same quantize/stages options")
        bucket = batch_bucket(n, self.min_bucket)
        pages_p = _pad_rows(pages, bucket)
        if tt.ndim == 2:
            tt = _pad_rows(tt, bucket, fill=HG.PAD)
        # a full bucket of headroom: the write is a bucket-wide block
        seg_i, start = store.reserve(n, min_free=bucket)
        seg = store.segments[seg_i]
        words = pack_tags(tags, store.filter_words)
        s32, rest = jnp.int32(start), (jnp.int32(n), jnp.int32(int(tenant)),
                                       jnp.asarray(words))
        if store.mesh is None:
            new_vectors = self._jit_write(seg.vectors, pages_p, tt, s32,
                                          *rest)
        else:
            # the arrays the batch writes, doc-sharded; the routing
            # companions (replicated) pass through untouched
            keys = (*self.produced_keys, VALIDITY_KEY, TENANT_KEY,
                    FILTER_KEY)
            new_vectors = dict(seg.vectors)
            new_vectors.update(self.sharded_write(store.mesh)(
                {k: seg.vectors[k] for k in keys}, s32, pages_p, tt,
                *rest))
        return store.commit(seg_i, new_vectors, n)
