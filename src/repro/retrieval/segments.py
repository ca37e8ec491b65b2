"""Segmented, capacity-padded mutable corpus (the live-index store).

The static store built by ``repro.retrieval.store.build_store`` is indexed
once and frozen; production corpora are not — collections grow page-by-page
as PDFs are ingested and shrink when tenants delete documents. This module
makes the corpus MUTABLE without ever changing array shapes:

- a ``Segment`` is a fixed-``capacity`` slab of named-vector arrays padded
  with zero slots, plus a ``doc_valid`` [capacity] bool mask (stored inside
  the vectors dict so it shards/threads through the engine like any other
  per-doc array) and a host-side ``doc_ids`` map from slot to user page id;
- ``SegmentedStore.add_pages`` writes a freshly indexed batch into the
  preallocated tail of the last segment via a shape-stable jitted
  ``dynamic_update_slice`` — steady-state ingestion never retraces; when a
  batch does not fit, a NEW segment is allocated at a bucketed power-of-two
  capacity (rounded up to a shard multiple) so layouts — and therefore
  compiled search fns — come from a small reusable family;
- ``delete`` only flips ``doc_valid`` bits (validity masking, the
  Nemotron-ColEmbed-style mutable index), it never moves a byte;
- ``compact`` is the amortised reclaim: rebuilds the corpus from surviving
  rows into a single right-sized segment (this DOES change the layout and
  thus recompiles — run it off the serving path).

Search-side, the engine scans each segment per stage and merges candidates
in a global SLOT id space (segment offsets = cumulative capacities);
``slot_doc_ids`` translates slots back to stable user page ids.

Which arrays a segment holds — named vectors, their per-token masks, int8
codes + scales, the per-document store companions — is described by the
typed ``repro.retrieval.store.VectorSchema``; this module never interprets
key strings itself (the key constants and accessors are imported from the
store module, the one owner of that layout).

``doc_valid`` has two typed siblings, written by the same shape-stable
primitives and carried in the vectors dict so they shard and thread through
the engine like any other per-doc array:

- ``doc_tenant`` [capacity] int32 — the owning tenant id per slot
  (``add_pages(..., tenant=)``; 0 for legacy single-tenant corpora);
- ``doc_filter`` [capacity, filter_words] uint32 — packed metadata-tag
  bitset per slot (``add_pages(..., tags=)``; tag j lives at word j // 32,
  bit j % 32).

At query time ``store.effective_validity`` folds a request's
``FilterSpec`` into these companions on device — filters are DATA, so
tenant switches and tag changes re-dispatch cached executables (zero
retraces); only allocation/compaction changes ``layout_key``.

The device write primitives come in two flavours: ``add_pages`` copies an
already-indexed ``VectorStore`` batch into headroom (one
``dynamic_update_slice`` per array), while the device-resident
``repro.retrieval.ingest.IngestPipeline`` computes AND writes a raw batch
in one fused jit, using the shared ``reserve``/``commit`` slot
bookkeeping below.

On a mesh the store is doc-sharded: shard s owns a contiguous
``capacity / n_shards`` slots of every segment. Allocation makes each
array in its sharding, every device writing only its own slab, and every
block write runs under ``shard_map`` (``sharded_write``; the ingest
pipeline's fused write does the same), each shard writing only the block
rows that fall in its slab (``owned_rows``). A block may straddle shard
boundaries; no device ever gathers a segment.
"""
from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.retrieval import routing as RT
from repro.retrieval.store import (FILTER_KEY, ROUTING_KEYS, TENANT_KEY,
                                   VALIDITY_KEY, VectorSchema, VectorStore,
                                   is_store_companion, pack_tags)
from repro.retrieval.tracing import record_trace

SEGMENT_MIN_CAPACITY = 64
DELETE_BUCKET_MIN = 8


def bucket_capacity(n: int, n_shards: int = 1,
                    min_capacity: int = SEGMENT_MIN_CAPACITY) -> int:
    """Smallest power-of-two >= n (and >= min_capacity), rounded up to a
    multiple of ``n_shards`` so every shard owns an equal slab."""
    cap = 1 << max(0, int(n - 1).bit_length())
    cap = max(cap, min_capacity)
    return -(-cap // n_shards) * n_shards


def _round_up(n: int, mult: int) -> int:
    return -(-n // mult) * mult


# Both mutation primitives take only shape-stable arguments (the write
# offset and slot list are traced values), so the jit cache is keyed purely
# on (segment layout, batch shape): steady-state ingestion and deletion
# re-dispatch cached executables. No donation: CPU does not implement it and
# segments are modest; on TPU the update is in-place-able by XLA anyway.

@jax.jit
def _write_block(arr: jax.Array, block: jax.Array, start) -> jax.Array:
    record_trace()
    idx = (start,) + (0,) * (arr.ndim - 1)
    return jax.lax.dynamic_update_slice(arr, block, idx)


def owned_rows(slab: jax.Array, block: jax.Array, local_start) -> jax.Array:
    """Write into one shard's ``slab`` the rows of ``block`` it owns:
    block row j lands at slab row ``local_start + j`` when that row lies
    in the slab, and is dropped otherwise. ``local_start`` is the block's
    global start slot minus the shard's first slot, so it may be negative
    or past the slab's end: a block that straddles a shard boundary is
    split between the two owners, and a shard the block misses is left
    as it was. One ``dynamic_update_slice`` of at most ``len(block)``
    rows: the shard reads its rows at the clamped window, keeps those the
    block does not cover, and writes the window back."""
    n_local, bucket = slab.shape[0], block.shape[0]
    w = min(bucket, n_local)
    pos = jnp.clip(local_start, 0, n_local - w)
    # block row for each window row, through a block padded by w rows on
    # both sides so the slice is in bounds however far off the block is
    shift = pos - local_start
    pad = ((w, w),) + ((0, 0),) * (block.ndim - 1)
    rows = jax.lax.dynamic_slice_in_dim(jnp.pad(block, pad), shift + w, w)
    j = shift + jnp.arange(w)
    take = ((j >= 0) & (j < bucket)).reshape((w,) + (1,) * (block.ndim - 1))
    window = jax.lax.dynamic_slice_in_dim(slab, pos, w)
    return jax.lax.dynamic_update_slice_in_dim(
        slab, jnp.where(take, rows.astype(slab.dtype), window), pos, 0)


def sharded_write(mesh, blocks=lambda b: b):
    """The doc-sharded twin of ``_write_block`` over ``mesh``: jitted
    ``fn(arrays, start, *args) -> arrays`` for a dict of segment arrays
    (doc-sharded). Every shard computes the row blocks ``blocks(*args)``
    from the replicated ``args`` (by default ``args`` is the dict of
    blocks itself), a dict under the keys of ``arrays``, and writes only
    its own rows (``owned_rows``), so no shard gathers the segment. Build
    it once per mesh and keep it: a new function traces anew."""
    axes = tuple(mesh.axis_names)

    def body(arrays, start, *args):
        record_trace()
        shard = jax.lax.axis_index(axes)
        rows = blocks(*args)
        return {k: owned_rows(a, rows[k], start - shard * a.shape[0])
                for k, a in arrays.items()}

    @jax.jit
    def write(arrays, start, *args):
        specs = {k: P(axes) for k in arrays}
        return shard_map(body, mesh=mesh,
                         in_specs=(specs,) + (P(),) * (1 + len(args)),
                         out_specs=specs, check_vma=False)(
            arrays, start, *args)

    return write


@jax.jit
def _invalidate(valid: jax.Array, slots: jax.Array) -> jax.Array:
    record_trace()
    # slots are padded to a bucketed length with sentinel == capacity,
    # which is out of bounds and dropped — one trace serves many counts
    return valid.at[slots].set(False, mode="drop")


@dataclass
class Segment:
    """One fixed-capacity slab. ``vectors`` holds every named array padded
    to ``capacity`` rows (including ``doc_valid``); ``n_docs`` is the
    high-water mark (next free tail slot); ``doc_ids`` maps slot -> stable
    user page id, -1 for never-written or deleted slots."""
    vectors: dict
    capacity: int
    n_docs: int
    doc_ids: np.ndarray
    # host-side IVF bookkeeping (``repro.retrieval.routing.RouteState``);
    # None until the store's router is enabled. The device-side centroid /
    # member arrays live in ``vectors`` under the reserved routing keys so
    # they thread through layout_key / placement like everything else.
    routing: object = None
    # residency tier (``repro.retrieval.tiering``): "device" = arrays live
    # in accelerator memory; "host" = spilled to host RAM as numpy arrays
    # of the SAME keys/shapes/dtypes. Residency is placement, never shape:
    # layout_key() is tier-blind, so compiled search fns survive tier
    # swaps unchanged (a host-tier segment must be promoted before it is
    # scanned — the tiering layer owns that).
    tier: str = "device"

    @property
    def free(self) -> int:
        return self.capacity - self.n_docs

    @property
    def n_valid(self) -> int:
        return int((self.doc_ids >= 0).sum())

    @property
    def nbytes(self) -> int:
        """Total array bytes this segment pins in its current tier (the
        accounting unit of the tiering layer's HBM budget)."""
        return sum(int(v.nbytes) for v in self.vectors.values())


class SegmentedStore:
    """A mutable corpus as a list of capacity-padded segments."""

    def __init__(self, segments: list, store_dtype: str = "bfloat16",
                 n_shards: int = 1, next_id: int = 0, mesh=None,
                 filter_words: int = 1):
        self.segments = list(segments)
        self.store_dtype = store_dtype
        self.n_shards = n_shards
        self.next_id = next_id
        self.mesh = mesh
        # width of the packed tag bitset (32 tags per word); part of the
        # layout, so it is fixed at store construction
        self.filter_words = max(int(filter_words), 1)
        # IVF routing policy (``routing.RoutingPolicy``); None = exhaustive
        # scans only. Set via ``enable_routing`` — it changes layout_key
        # (two new companion arrays), so compiled search fns rebuild once.
        self.router = None
        self._slot_ids: np.ndarray | None = None   # slot->page-id cache
        self._writer = None      # (mesh, sharded_write(mesh)), made once
        # bumped on every content mutation (upsert/delete/compact) so
        # result caches keyed on it can never serve pre-mutation answers
        self.generation = 0

    # ------------------------------------------------------------------
    # construction
    # ------------------------------------------------------------------

    @classmethod
    def from_store(cls, store: VectorStore, n_shards: int = 1,
                   capacity: int | None = None, mesh=None,
                   filter_words: int = 1):
        """Wrap a built (immutable) store as segment 0.

        Default capacity is EXACT fit rounded up to a shard multiple — a
        frozen corpus pays zero padded-scan overhead and legacy behaviour
        is unchanged; pass ``capacity`` (e.g. ``bucket_capacity``) to
        preallocate ingestion headroom. Wrapped pages get tenant 0 and an
        empty tag set; ``filter_words`` sizes the packed bitset for pages
        upserted later."""
        cap = capacity if capacity is not None else \
            _round_up(store.n_docs, n_shards)
        if cap < store.n_docs:
            raise ValueError(f"capacity {cap} < n_docs {store.n_docs}")
        cap = _round_up(cap, n_shards)
        out = cls([], store.store_dtype, n_shards, next_id=0, mesh=mesh,
                  filter_words=filter_words)
        seg = out._alloc_segment(store.vectors, cap)
        n = store.n_docs
        blocks = {k: v.astype(seg.vectors[k].dtype)
                  for k, v in store.vectors.items()}
        blocks[VALIDITY_KEY] = jnp.ones((n,), bool)
        # stamp tenant 0 / no tags through the same write primitive an
        # ``add_pages`` of this batch shape uses — zeros over zeros, but it
        # warms those executables so a wrap-then-upsert serving loop stays
        # zero-retrace at the seed batch size (same contract as the data
        # arrays above)
        blocks[TENANT_KEY] = jnp.zeros((n,), jnp.int32)
        blocks[FILTER_KEY] = jnp.zeros((n, out.filter_words), jnp.uint32)
        out._write_rows(seg, blocks, 0)
        seg.doc_ids[:n] = np.arange(n)
        seg.n_docs = n
        out.next_id = n
        return out

    def place_on(self, mesh) -> None:
        """Lay every segment array out with ``mesh``'s doc-sharded layout
        (done once at placement, never per search call). The IVF routing
        companions replicate instead: every shard routes the same query
        through the same centroids/member lists, then scores only the
        member slots it owns."""
        self.mesh = mesh
        for seg in self.segments:
            seg.vectors = {
                k: (self._place_replicated(v) if k in ROUTING_KEYS
                    else self._place(v))
                for k, v in seg.vectors.items()}

    def _doc_sharding(self) -> NamedSharding:
        return NamedSharding(self.mesh, P(tuple(self.mesh.axis_names)))

    def _place(self, arr: jax.Array) -> jax.Array:
        if self.mesh is None:
            return arr
        return jax.device_put(arr, self._doc_sharding())

    def _zeros(self, shape: tuple, dtype) -> jax.Array:
        """A zero segment array, made in place: doc-sharded on a mesh, by
        a program whose output sharding makes each device write only its
        own slab (no device ever holds more)."""
        if self.mesh is None:
            return jnp.zeros(shape, dtype)

        def zeros():
            record_trace()
            return jnp.zeros(shape, dtype)

        return jax.jit(zeros, out_shardings=self._doc_sharding())()

    def _write_rows(self, seg: Segment, blocks: dict, start: int) -> None:
        """Write each block of ``blocks`` into ``seg``'s array of the same
        key at slot ``start`` (``_write_block`` per array, or, on a mesh,
        one ``sharded_write`` in which each shard writes only its rows)."""
        s32 = jnp.int32(start)
        if self.mesh is None:
            for k, b in blocks.items():
                seg.vectors[k] = _write_block(seg.vectors[k], b, s32)
            return
        if self._writer is None or self._writer[0] is not self.mesh:
            self._writer = (self.mesh, sharded_write(self.mesh))
        seg.vectors.update(self._writer[1](
            {k: seg.vectors[k] for k in blocks}, s32, blocks))

    def _place_replicated(self, arr: jax.Array) -> jax.Array:
        if self.mesh is None:
            return arr
        return jax.device_put(arr, NamedSharding(self.mesh, P()))

    def _alloc_segment(self, like_vectors: dict, capacity: int) -> Segment:
        vecs = {}
        for k, v in like_vectors.items():
            if is_store_companion(k):
                continue
            vecs[k] = self._zeros((capacity,) + v.shape[1:], v.dtype)
        # the store companions are always present and zero-initialised:
        # dead slots are invalid, tenant 0, no tags
        vecs[VALIDITY_KEY] = self._zeros((capacity,), bool)
        vecs[TENANT_KEY] = self._zeros((capacity,), jnp.int32)
        vecs[FILTER_KEY] = self._zeros((capacity, self.filter_words),
                                       jnp.uint32)
        seg = Segment(vecs, capacity, 0, np.full((capacity,), -1, np.int64))
        if self.router is not None:
            arrays, state = RT.alloc_arrays(self.router, like_vectors,
                                            capacity)
            for k, v in arrays.items():
                seg.vectors[k] = self._place_replicated(v)
            seg.routing = state
        self.segments.append(seg)
        return seg

    def enable_routing(self, policy) -> None:
        """Build (or rebuild) the IVF cluster index over every segment.

        ``policy`` is a ``routing.RoutingPolicy`` or a plain int K. Adds
        the centroid/member companion arrays — a one-time layout change —
        then ``add_pages``/``ingest``/``delete`` maintain them
        incrementally (assign-to-nearest on commit, drift-triggered
        re-clustering) with zero steady-state retraces. Query-side, opt a
        cascade in with ``Stage.n_probe`` (``multistage
        .with_routing_policy``)."""
        if not isinstance(policy, RT.RoutingPolicy):
            policy = RT.RoutingPolicy(n_clusters=int(policy))
        self.router = policy
        for seg in self.segments:
            RT.recluster(self, seg)

    # ------------------------------------------------------------------
    # mutation
    # ------------------------------------------------------------------

    def reserve(self, n: int, like: dict | None = None,
                min_free: int | None = None) -> tuple:
        """Find (or allocate) room for ``n`` new pages at the tail of the
        corpus. Returns ``(segment index, start slot)`` — the slots are
        NOT claimed until ``commit`` runs, so a failed device write leaves
        the store untouched. Batches are never split: when the last
        segment's free tail is too small, a NEW segment is allocated at a
        bucketed power-of-two capacity (``like`` supplies the layout when
        the store is still empty). ``min_free`` asks for extra tail
        headroom beyond ``n`` — the ingest pipeline writes full
        bucket-wide blocks, so its block must fit even though only ``n``
        slots are claimed."""
        need = max(n, min_free or 0)
        seg = self.segments[-1] if self.segments else None
        if seg is None or seg.free < need:
            if seg is None and like is None:
                raise ValueError("reserve() on an empty store needs `like` "
                                 "arrays for the segment layout")
            seg = self._alloc_segment(
                like if like is not None else self.segments[-1].vectors,
                bucket_capacity(need, self.n_shards))
        return len(self.segments) - 1, seg.n_docs

    def commit(self, seg_i: int, new_vectors: dict, n: int) -> np.ndarray:
        """Adopt device-side written arrays and do the host bookkeeping
        shared by ``add_pages`` and the ingest pipeline: assign stable
        page ids to the ``n`` reserved tail slots, advance the high-water
        mark, bump the generation. Returns the assigned ids."""
        seg = self.segments[seg_i]
        seg.vectors = new_vectors
        start = seg.n_docs
        ids = np.arange(self.next_id, self.next_id + n, dtype=np.int64)
        seg.doc_ids[start:start + n] = ids
        seg.n_docs = start + n
        self.next_id += n
        self._slot_ids = None
        self.generation += 1
        if self.router is not None:
            RT.on_commit(self, seg,
                         np.arange(start, start + n, dtype=np.int64))
        return ids

    def add_pages(self, batch: VectorStore, tenant: int = 0,
                  tags=()) -> np.ndarray:
        """Ingest an indexed batch (the output of ``build_store`` /
        ``quantize_store``). Returns the assigned stable page ids.

        Fits the WHOLE batch into the last segment's free tail when
        possible; otherwise allocates a new bucketed segment sized to the
        batch (batches are never split, so steady-state ingestion at a
        fixed batch size reuses one write executable per vector name).

        ``tenant``/``tags`` stamp the batch's store companions: every page
        in the batch belongs to ``tenant`` and carries the packed ``tags``
        bitset (queries filter on them via ``store.FilterSpec``). Both are
        traced VALUES into the same cached write executables — changing
        tenant or tags between batches never retraces."""
        n = batch.n_docs
        if self.segments:
            names = {k for k in self.segments[0].vectors
                     if not is_store_companion(k)}
            if set(batch.vectors) != names:
                raise ValueError(
                    f"batch vectors {sorted(batch.vectors)} != store "
                    f"vectors {sorted(names)}")
        seg_i, start = self.reserve(n, like=batch.vectors)
        seg = self.segments[seg_i]
        blocks = {k: jnp.asarray(v).astype(seg.vectors[k].dtype)
                  for k, v in batch.vectors.items()}
        blocks[VALIDITY_KEY] = jnp.ones((n,), bool)
        blocks[TENANT_KEY] = jnp.full((n,), int(tenant), jnp.int32)
        words = pack_tags(tags, self.filter_words)
        blocks[FILTER_KEY] = jnp.broadcast_to(jnp.asarray(words)[None, :],
                                              (n, self.filter_words))
        self._write_rows(seg, blocks, start)
        return self.commit(seg_i, seg.vectors, n)

    def delete(self, ids) -> int:
        """Invalidate pages by stable id. Only flips ``doc_valid`` bits —
        no data moves, no shapes change. Returns #pages deleted."""
        ids = np.asarray(list(ids) if not isinstance(ids, np.ndarray)
                         else ids, np.int64)
        # search results use -1 as dead-slot filler; piping them back in
        # must not match the -1 sentinel in doc_ids
        ids = ids[ids >= 0]
        deleted = 0
        for seg in self.segments:
            slots = np.flatnonzero(np.isin(seg.doc_ids, ids))
            if slots.size == 0:
                continue
            width = bucket_capacity(slots.size, min_capacity=DELETE_BUCKET_MIN)
            padded = np.full((width,), seg.capacity, np.int32)  # OOB sentinel
            padded[:slots.size] = slots
            seg.vectors[VALIDITY_KEY] = _invalidate(
                seg.vectors[VALIDITY_KEY], jnp.asarray(padded))
            seg.doc_ids[slots] = -1
            deleted += int(slots.size)
            if self.router is not None:
                RT.on_delete(self, seg, int(slots.size))
        if deleted:
            self._slot_ids = None
            self.generation += 1
        return deleted

    def compact(self):
        """Rebuild the corpus from surviving rows into one right-sized
        segment, preserving page ids and their relative order. Amortised
        maintenance: the layout changes, so compiled search fns for the old
        capacities no longer apply."""
        if not self.segments:
            return self
        # doc_tenant / doc_filter ride the gather loop like any data array
        # (each survivor keeps its tenancy and tags); doc_valid is the one
        # companion rebuilt from scratch — every survivor is live. The IVF
        # routing companions are per-CLUSTER, not per-doc: compaction
        # renumbers every slot, so they are rebuilt by a fresh clustering
        # below instead of riding the gather
        names = [k for k in self.segments[0].vectors
                 if k != VALIDITY_KEY and k not in ROUTING_KEYS]
        like = {k: self.segments[0].vectors[k] for k in names}
        rows = {k: [] for k in names}
        ids = []
        for seg in self.segments:
            slots = np.flatnonzero(seg.doc_ids >= 0)
            if slots.size == 0:
                continue
            idx = jnp.asarray(slots)
            for k in names:
                rows[k].append(jnp.take(seg.vectors[k], idx, axis=0))
            ids.append(seg.doc_ids[slots])
        total = int(sum(len(i) for i in ids))
        cap = bucket_capacity(max(total, 1), self.n_shards)
        self.segments = []
        seg = self._alloc_segment(like, cap)
        if total:
            # one array at a time, so one concatenated block is alive
            for k in names:
                block = jnp.concatenate(rows[k], axis=0)
                self._write_rows(
                    seg, {k: block.astype(seg.vectors[k].dtype)}, 0)
            self._write_rows(seg, {VALIDITY_KEY: jnp.ones((total,), bool)},
                             0)
            seg.doc_ids[:total] = np.concatenate(ids)
        seg.n_docs = total
        if self.router is not None:
            RT.recluster(self, seg)
        self._slot_ids = None
        self.generation += 1
        return self

    def tier_swap(self, seg_i: int, vectors: dict, tier: str) -> None:
        """Adopt a promotion/demotion's array swap for segment ``seg_i``:
        the SAME keys/shapes/dtypes with a different placement (device
        arrays on promote, host numpy on demote). The one mutation the
        tiering layer performs on the store — centralised here so the
        bookkeeping is uniform with ``commit``/``delete``:

        - ``generation`` bumps: placement did not change any value, but
          result caches keyed on it (the frontend's LRU) conservatively
          drop entries rather than reason about residency;
        - ``doc_ids``/``_slot_ids`` are untouched — slot->page translation
          is placement-blind, as is ``layout_key()`` (tier swaps never
          invalidate compiled search fns).
        """
        seg = self.segments[seg_i]
        if set(vectors) != set(seg.vectors):
            raise ValueError(
                f"tier swap changed the key set for segment {seg_i}: "
                f"{sorted(set(vectors) ^ set(seg.vectors))}")
        seg.vectors = vectors
        seg.tier = tier
        self.generation += 1

    # ------------------------------------------------------------------
    # views
    # ------------------------------------------------------------------

    def stores(self) -> tuple:
        """Per-segment vectors dicts, in slot order — the engine's input."""
        return tuple(seg.vectors for seg in self.segments)

    @property
    def vectors(self) -> dict:
        """Single-segment convenience view (the capacity-padded arrays,
        ``doc_valid`` included). Multi-segment stores have no flat view —
        use ``stores()``."""
        if len(self.segments) != 1:
            raise ValueError(
                f"{len(self.segments)} segments have no flat vectors view; "
                "use stores()")
        return self.segments[0].vectors

    @property
    def capacities(self) -> tuple:
        return tuple(seg.capacity for seg in self.segments)

    @property
    def n_valid(self) -> int:
        return sum(seg.n_valid for seg in self.segments)

    @property
    def total_capacity(self) -> int:
        return sum(self.capacities)

    def layout_key(self) -> tuple:
        """Everything a compiled search fn's shapes depend on — capacities
        and per-name trailing dims/dtypes, NOT the fill level. Upserts into
        existing padding and deletes leave this key unchanged (the
        no-retrace contract); only new-segment allocation or compaction
        changes it."""
        return tuple(
            (seg.capacity,
             tuple(sorted((k, v.shape[1:], str(v.dtype))
                          for k, v in seg.vectors.items())))
            for seg in self.segments)

    def slot_doc_ids(self) -> np.ndarray:
        """Global slot -> stable page id (-1 = dead slot), concatenated in
        segment order to match the engine's global slot id space. Cached:
        rebuilt only after a mutation, not per search."""
        if self._slot_ids is None:
            if not self.segments:
                self._slot_ids = np.zeros((0,), np.int64)
            else:
                self._slot_ids = np.concatenate(
                    [seg.doc_ids for seg in self.segments])
        return self._slot_ids

    def translate_slots(self, slots) -> np.ndarray:
        """Global slot ids -> stable page ids. Slot -1 is the engine's
        dead-filler sentinel (a sharded rerank merge drops the ids of
        non-owned candidate copies so NEG filler can never duplicate a
        live document); it maps to page id -1 rather than letting numpy's
        negative indexing wrap to the last slot."""
        table = self.slot_doc_ids()
        slots = np.asarray(slots)
        if len(table) == 0:      # zero segments: every slot is a sentinel
            return np.full(slots.shape, -1, np.int64)
        return np.where(
            slots >= 0, table[np.clip(slots, 0, len(table) - 1)],
            np.int64(-1))

    def schema(self) -> VectorSchema:
        """Typed layout of the live corpus (``VectorStore.schema`` twin)."""
        return VectorSchema.infer(
            self.segments[0].vectors if self.segments else {})

    def dims(self) -> dict:
        return self.schema().dims()

    def vec_dims(self) -> dict:
        """Stored embedding dim per named vector (``VectorStore.vec_dims``
        twin, so ``qps_cost_model`` works from a live corpus too)."""
        return self.schema().vec_dims()
