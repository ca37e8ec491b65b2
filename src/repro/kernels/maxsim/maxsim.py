"""Pallas TPU kernels: streaming MaxSim scan + fused gather-rerank.

score[b, n] = sum_q qmask[b,q] * max_j (dmask[n,j] ? <q[b,q], docs[n,j]> : -inf)

**Scan kernel** — TPU adaptation of the paper's hot path (§1 Eq. 1):
instead of materialising the [B, N, Q, D] similarity tensor in HBM
(GPU-einsum style), a query block stays resident in VMEM while document
tiles stream HBM -> VMEM through the Pallas grid pipeline
(double-buffered). Only the final [B, N] scores are written back, and
the corpus is read once per query batch.

Grid: (N/bn, B/bq) with the query axis innermost, so a document tile's
block index is constant across it and the tile is fetched once per call
whatever B is. bn and bq come from ``doc_block`` and ``query_block``; Q
is padded to a multiple of 8. Masks and outputs travel as blocks whose
two minor dims are whole array dims or tile multiples (the TPU block
rule). The body depends on the page length D (``scan_body``):

- ``packed`` (D < 128: the pooled pages, 34 colpali and 13 colsmol
  vectors, and D=1 centroids). The tile is token-major, [D, bn, d], read
  from a [D, N, d] view of the corpus that costs nothing on a TPU. The
  query block, transposed to [d, bq*Q], is the stationary MXU operand;
  for each token position j the [bn, d] slab of every page's j-th vector
  streams through it as rows, one matmul scoring the whole tile. The max
  over the D tokens is an elementwise VPU max of the D [bn, bq*Q]
  products, and one block-diagonal matmul sums each query's token maxima
  into the lane-dense [bq, bn] output block.
- ``per_page`` (D >= 128, full-resolution pages, which fill the lanes on
  their own): [bn, D, d] tiles, query block flattened to [bq*Q, d] rows,
  per document one [bq*Q, d] x [d, D] MXU matmul, a masked max over the
  D tokens, then the per-token maxima summed per query.

The scan was bound by MXU passes, not by HBM bytes: the per-page body
on the pooled pages spent one 512-row pass per page (with its weight
load, lane reduce and select) on a D-column product that fills 34 or 13
of the 128 lanes, and ran at 1-3% of the roofline on a v5e. The packed
body spends 4*D row pushes a page (R = 512 query rows) per bf16 pass.

Precision: products are exact to f32 and queries stay f32. f32 docs
multiply at ``HIGHEST``. Docs exact in bf16 (bf16, and int8 codes) meet
the query in the packed body as three bf16 planes that sum to it exactly
(hi + mid + lo), three MXU passes accumulated in f32 in place of the six
of an upcast ``HIGHEST`` product; this is not a precision change. An
int8 variant sends the codes to the MXU unscaled and multiplies the
per-vector scale into the similarity (<q, c*s> == <q, c>*s): HBM bytes
halve vs bf16.

**Gather-rerank kernel** — the cascade's other memory cliff (§2.4):
rerank stages score a SMALL per-query candidate set against the full
multi-vector rows. A jnp ``jnp.take`` gather first materialises a
[B, L, D, d] candidate copy in HBM (write + re-read = 3x the candidate
bytes) before any math runs. Here the candidate slot ids arrive via
SCALAR PREFETCH (``pltpu.PrefetchScalarGridSpec``): the grid is
(B, L, D/bd) and the ``docs`` BlockSpec's index map reads ``ids[b, l]``
from SMEM to pick WHICH [bd, d] document tile the next HBM->VMEM DMA
fetches — the gather IS the kernel's input stream, no gathered copy ever
exists in HBM. The resident query block, the running per-query-token max
accumulator (VMEM scratch, carried across D tiles), int8 dequantisation
(scales streamed alongside the codes through the same index map) and
Matryoshka-truncated d all work exactly as in the scan kernel; each grid
step finishes by reducing to the single score out[b, l]. HBM traffic is
one read of the candidate rows per query batch plus the [B, L] score
write — the memory-roofline floor for exact candidate reranking.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128                # a page with fewer tokens than this is packed
# per-block VMEM budget for the per-page body's streamed document tile
# (the auto-pipeline double-buffers it; the scoped-VMEM default on v5e is
# 16 MiB). The packed body streams 128-page tiles, the lane width: its
# token loop is unrolled, and on a v5e 256- and 512-page tiles scanned
# colpali's pooled corpus 1-2% faster for 2.6x and 6.4x the compile time.
DOC_TILE_BYTES = 1 << 20
Q_ROWS = 512               # query-token rows scored per MXU pass

# Trace-time record of the body each built scan call took, keyed by
# (B, Q, N, D, d, doc dtype): "packed" or "per_page". Tests read it; it
# costs nothing at run time.
SCAN_BODIES: dict = {}


def scan_body(D: int) -> str:
    """The scan body for pages of D tokens: ``packed`` while a page fills
    less than one lane tile, else ``per_page``."""
    return "packed" if D < LANES else "per_page"


def _split_bf16(x):
    """f32 [...] -> bf16 [3, ...] planes (hi, mid, lo) whose sum is x
    exactly. Each plane keeps the top 8 significant bits of what the
    planes before it left, cut by masking the f32 word (a convert could
    round, and XLA may fold an f32 -> bf16 -> f32 round trip away)."""
    def top(v):
        bits = jax.lax.bitcast_convert_type(v, jnp.uint32)
        return jax.lax.bitcast_convert_type(bits & jnp.uint32(0xFFFF0000),
                                            jnp.float32)
    hi = top(x)
    mid = top(x - hi)
    return jnp.stack([hi, mid, x - hi - mid]).astype(jnp.bfloat16)


def _packed_kernel(q_ref, qm_ref, docs_ref, dm_ref, *rest, n_q: int,
                   n_tok: int, split: bool):
    """One tile of bn pages against one query block, every page at once.

    q_ref [3, d, R] bf16 planes (``split``) or [d, R] f32, R = bq * Q
    query-token columns; qm_ref [1, R]; docs_ref [D, bn, d] token-major;
    dm_ref / sc_ref [bn, D] f32. For each token position j the [bn, d]
    slab of the tile's j-th tokens goes through the MXU against the
    stationary query block: sim [bn, R], a running max over j on the
    VPU. The per-token maxima are then summed per query by a
    block-diagonal [bq, R] x [R, bn] matmul into the lane-dense [bq, bn]
    output block."""
    sc_ref = rest[0] if len(rest) == 2 else None
    out_ref = rest[-1]
    qs = ([q_ref[i] for i in range(3)] if split
          else [q_ref[...].astype(jnp.float32)])
    R = qs[0].shape[1]
    best = jnp.full((docs_ref.shape[1], R), NEG, jnp.float32)
    for j in range(n_tok):         # static: the mask column is a lane slice
        # split: docs exact in bf16 (bf16, int8 codes) meet the query's
        # three bf16 planes, exact products accumulated in f32
        doc = docs_ref[j].astype(qs[0].dtype)                   # [bn, d]
        sim = None
        for p in qs:
            part = jnp.dot(doc, p, precision=None if split else HIGHEST,
                           preferred_element_type=jnp.float32)  # [bn, R]
            sim = part if sim is None else sim + part
        if sc_ref is not None:
            sim = sim * sc_ref[:, j:j + 1]
        best = jnp.maximum(best, jnp.where(dm_ref[:, j:j + 1] > 0, sim, NEG))
    best = jnp.where(qm_ref[...] > 0, jnp.maximum(best, NEG / 2), 0.0)
    bq = out_ref.shape[0]
    own = (jax.lax.broadcasted_iota(jnp.int32, (bq, R), 1) // n_q
           == jax.lax.broadcasted_iota(jnp.int32, (bq, R), 0))
    out_ref[...] = jax.lax.dot_general(
        own.astype(jnp.float32), best, (((1,), (1,)), ((), ())),
        precision=HIGHEST, preferred_element_type=jnp.float32)


def _score_docs(q, docs_ref, dm_ref, sc_ref, n_docs: int):
    """Per-(query-token, document) MaxSim over one resident doc tile.

    q [R, d] f32 (R = query rows x Q tokens, flattened); docs_ref
    [n_docs, D, d]; dm_ref / sc_ref [n_docs, D] f32 (sc_ref None for float
    docs). Returns [R, n_docs] f32: max_j over doc j's unmasked tokens of
    <q_r, doc_j> (NEG when every token is masked). One [R, d] x [d, D] MXU
    matmul per document; the int8 scale multiplies the similarity column
    (<q, c * s> == <q, c> * s) so codes go to the MXU unscaled."""
    R = q.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, n_docs), 1)

    def body(j, acc):
        doc = docs_ref[j].astype(jnp.float32)                   # [D, d]
        sim = jax.lax.dot_general(
            q, doc, (((1,), (1,)), ((), ())), precision=HIGHEST,
            preferred_element_type=jnp.float32)                 # [R, D]
        if sc_ref is not None:
            sim = sim * sc_ref[pl.ds(j, 1), :]
        sim = jnp.where(dm_ref[pl.ds(j, 1), :] > 0, sim, NEG)
        return jnp.where(lane == j, jnp.max(sim, axis=1, keepdims=True), acc)

    return jax.lax.fori_loop(0, n_docs, body,
                             jnp.full((R, n_docs), NEG, jnp.float32))


def _sum_tokens(best, qm, n_q: int):
    """[R, n] per-token maxima + [R, 1] query-token mask -> [R / n_q, n]
    scores. Masked tokens add 0; a fully-masked document's NEG is clamped
    to NEG/2 per token (padding docs produce garbage, masked by caller)."""
    best = jnp.where(qm > 0, jnp.maximum(best, NEG / 2), 0.0)
    return jnp.sum(best.reshape(best.shape[0] // n_q, n_q, best.shape[1]),
                   axis=1)


def _maxsim_kernel(q_ref, qm_ref, docs_ref, dm_ref, *rest, n_q: int,
                   n_docs: int):
    sc_ref = rest[0] if len(rest) == 2 else None
    out_ref = rest[-1]
    best = _score_docs(q_ref[...].astype(jnp.float32), docs_ref, dm_ref,
                       sc_ref, n_docs)
    out_ref[...] = _sum_tokens(best, qm_ref[...], n_q)


def doc_block(D: int, d: int, itemsize: int, n: int,
              block_n: int = 0) -> int:
    """Documents per streamed tile, for ``n`` documents of D tokens.

    Packed body: pages are the lane axis of the mask and output blocks,
    so ``block_n`` rounded up to a multiple of 128 (128 by default), or
    all ``n`` pages when they fit in one tile. Per-page body: ``block_n``,
    else a power of two of at least 8 (the sublane tile of the [n, D]
    mask block) whose [n, D, d] tile fits ``DOC_TILE_BYTES``. Store
    capacities are powers of two, so neither pads a corpus."""
    if scan_body(D) == "packed":
        bn = max(LANES, -(-block_n // LANES) * LANES)
        return n if n <= bn else bn
    if block_n:
        return block_n
    bn = 8
    while bn * 2 * D * d * itemsize <= DOC_TILE_BYTES and bn * 2 <= max(n, 8):
        bn *= 2
    return bn


def query_block(B: int, Q: int) -> int:
    """Query rows per grid step: all of them up to ``Q_ROWS`` token rows,
    else a multiple of 16 (a multiple of 8, the output block's sublane
    tile, and with Q % 8 == 0 of bq * Q % 128 == 0, the packed body's
    lane-dim query block)."""
    if B * Q <= Q_ROWS:
        return B
    return max(16, (Q_ROWS // Q) // 16 * 16)


def maxsim_pallas(q: jax.Array, q_mask: jax.Array, docs: jax.Array,
                  doc_mask: jax.Array, *, block_n: int,
                  scales: jax.Array | None = None,
                  interpret: bool = True) -> jax.Array:
    """q [B,Q,d] f32/bf16; q_mask [B,Q] f32; docs [N,D,d] (f32/bf16/int8);
    doc_mask [N,D] f32; scales [N,D] f32 when docs are int8. -> [B,N] f32.

    Shapes must be pre-padded: Q % 8 == 0, N % block_n == 0 with block_n
    from ``doc_block``, B % query_block(B, Q) == 0. The grid is
    (N / block_n, query blocks) with the query axis innermost: a doc
    tile's block index does not change across it, so the pipeline fetches
    each tile once and the corpus is read once per call whatever B is.

    Pages of fewer than 128 tokens (``scan_body``) take the packed body:
    the corpus is read token-major ([D, N, d], the layout XLA gives a
    [N, D, d] array with small D on a TPU, so the transpose is free),
    the query block [d, bq*Q] is the stationary MXU operand, and each of
    the D [block_n, d] token slabs of a tile streams through it as rows
    (``_packed_kernel``). Longer pages keep the per-page body: one
    [bq*Q, d] x [d, D] matmul per document, D filling the lanes.

    Precision: products are exact to f32. Float32 docs multiply at
    ``HIGHEST``. Docs exact in bf16 (bf16, int8 codes) in the packed body
    meet the f32 query as three bf16 planes summing to it exactly
    (``_split_bf16``), three MXU passes with f32 accumulation in place of
    the six of an f32 ``HIGHEST`` product; the per-page body upcasts them
    and multiplies at ``HIGHEST``. int8 scales multiply the similarity
    (<q, c*s> == <q, c>*s), so codes reach the MXU unscaled.
    """
    B, Q, d = q.shape
    N, D, dd = docs.shape
    assert d == dd and Q % 8 == 0, (q.shape, docs.shape)
    bq = query_block(B, Q)
    assert N % block_n == 0 and B % bq == 0, (N, block_n, B, bq)
    body = scan_body(D)
    SCAN_BODIES[(B, Q, N, D, d, docs.dtype.name)] = body
    nb = N // block_n
    qm = q_mask.astype(jnp.float32)
    args = [docs, doc_mask.astype(jnp.float32)]
    if scales is not None:
        args.append(scales.astype(jnp.float32))
    if body == "packed":
        split = docs.dtype in (jnp.bfloat16, jnp.int8)
        qt = q.astype(jnp.float32).reshape(B * Q, d).T          # [d, B*Q]
        if split:
            qt = _split_bf16(qt)
        lead = (0,) * (qt.ndim - 2)
        in_specs = [
            pl.BlockSpec(qt.shape[:-1] + (bq * Q,),
                         lambda n, b: lead + (0, b)),             # q planes
            pl.BlockSpec((1, bq * Q), lambda n, b: (0, b)),       # q_mask
            pl.BlockSpec((D, block_n, d), lambda n, b: (0, n, 0)),  # docs
        ] + [pl.BlockSpec((block_n, D), lambda n, b: (n, 0))
             for _ in args[1:]]                                   # dm, sc
        args[0] = jnp.transpose(docs, (1, 0, 2))                  # [D, N, d]
        return pl.pallas_call(
            functools.partial(_packed_kernel, n_q=Q, n_tok=D, split=split),
            grid=(nb, B // bq),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((bq, block_n), lambda n, b: (b, n)),
            out_shape=jax.ShapeDtypeStruct((B, N), jnp.float32),
            interpret=interpret,
        )(qt, qm.reshape(1, B * Q), *args)
    in_specs = [
        pl.BlockSpec((bq * Q, d), lambda n, b: (b, 0)),            # q rows
        pl.BlockSpec((bq * Q, 1), lambda n, b: (b, 0)),            # q_mask
        pl.BlockSpec((block_n, D, d), lambda n, b: (n, 0, 0)),     # docs
    ] + [pl.BlockSpec((block_n, D), lambda n, b: (n, 0))
         for _ in args[1:]]                                         # dm, sc
    out = pl.pallas_call(
        functools.partial(_maxsim_kernel, n_q=Q, n_docs=block_n),
        grid=(nb, B // bq),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, bq, block_n), lambda n, b: (n, b, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, B, block_n), jnp.float32),
        interpret=interpret,
    )(q.reshape(B * Q, d), qm.reshape(B * Q, 1), *args)
    return jnp.moveaxis(out, 0, 1).reshape(B, N)


def _rerank_kernel(ids_ref, q_ref, qm_ref, docs_ref, dm_ref, *rest,
                   n_d_blocks: int):
    del ids_ref            # consumed by the BlockSpec index maps, not here
    sc_ref = rest[0] if len(rest) == 3 else None
    out_ref, acc_ref = rest[-2:]
    di = pl.program_id(2)

    @pl.when(di == 0)
    def _init():
        acc_ref[...] = jnp.full_like(acc_ref, NEG)

    q = q_ref[...].astype(jnp.float32)                  # [Q, d]
    doc = docs_ref[...].astype(jnp.float32)             # [bd, d]
    # sim[q, j] = <q_q, doc_j> — contract d on the MXU
    sim = jax.lax.dot_general(
        q, doc, (((1,), (1,)), ((), ())), precision=HIGHEST,
        preferred_element_type=jnp.float32)             # [Q, bd]
    if sc_ref is not None:
        sim = sim * sc_ref[...]                         # [1, bd] scales
    sim = jnp.where(dm_ref[...] > 0, sim, NEG)
    acc_ref[...] = jnp.maximum(acc_ref[...],
                               jnp.max(sim, axis=1, keepdims=True))

    @pl.when(di == n_d_blocks - 1)
    def _finish():
        # NO NEG/2 clamp (unlike the scan kernel): the rerank contract is
        # ``core.maxsim.maxsim_scan``, which sums the raw per-token max —
        # a fully-masked candidate scores Qv*NEG on every rerank impl
        best = jnp.where(qm_ref[...] > 0, acc_ref[...], 0.0)   # [Q, 1]
        out_ref[...] = jnp.sum(best, axis=0, keepdims=True)


def maxsim_rerank_pallas(rows: jax.Array, q: jax.Array, q_mask: jax.Array,
                         docs: jax.Array, doc_mask: jax.Array, *,
                         block_d: int = 0,
                         scales: jax.Array | None = None,
                         interpret: bool = True) -> jax.Array:
    """Fused gather + exact MaxSim over per-query candidate lists.

    rows [B, L] int32 in-range slot ids (SCALAR-PREFETCHED: the BlockSpec
    index maps read them to choose which document tile each grid step
    DMAs HBM -> VMEM — no gathered candidate copy is ever materialised);
    q [B, Q, d]; q_mask [B, Q] f32; docs [N, D, d] (f32/bf16/int8);
    doc_mask [N, D] f32, or [1, D] for a BROADCAST mask (a mask-less
    store passes one all-ones row and every grid step streams tile
    (0, j) — never a corpus-sized ones array); scales [N, D] f32 when
    docs are int8. -> scores [B, L] f32.

    Shapes must be pre-padded: D % block_d == 0 (block_d a multiple of
    128, or D). Grid is (B, L, D/bd) with the D axis innermost so the
    per-query-token running max carries across a candidate's D tiles in
    VMEM scratch. Per-token rows (masks, scales) travel as [*, 1, D] and
    the query mask as [B, Q, 1], so every block's two minor dims are
    whole array dims or lane/sublane-tile multiples.
    """
    B, Q, d = q.shape
    N, D, dd = docs.shape
    assert d == dd, (d, dd)
    L = rows.shape[1]
    if block_d <= 0:
        block_d = D
    assert D % block_d == 0, (D, block_d)
    n_d_blocks = D // block_d
    if doc_mask.shape[0] == 1:               # broadcast (mask-less store)
        dm_index = lambda b, l, j, ids: (0, 0, j)         # noqa: E731
    else:
        dm_index = lambda b, l, j, ids: (ids[b, l], 0, j)  # noqa: E731

    in_specs = [
        pl.BlockSpec((None, Q, d), lambda b, l, j, ids: (b, 0, 0)),     # q
        pl.BlockSpec((None, Q, 1), lambda b, l, j, ids: (b, 0, 0)),     # qm
        pl.BlockSpec((None, block_d, d),
                     lambda b, l, j, ids: (ids[b, l], j, 0)),           # docs
        pl.BlockSpec((None, 1, block_d), dm_index),                     # dm
    ]
    args = [q, q_mask.astype(jnp.float32)[..., None], docs,
            doc_mask.astype(jnp.float32)[:, None, :]]
    if scales is not None:
        in_specs.append(pl.BlockSpec(
            (None, 1, block_d), lambda b, l, j, ids: (ids[b, l], 0, j)))
        args.append(scales.astype(jnp.float32)[:, None, :])

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, L, n_d_blocks),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((None, None, 1, 1),
                               lambda b, l, j, ids: (b, l, 0, 0)),
        scratch_shapes=[pltpu.VMEM((Q, 1), jnp.float32)],
    )
    out = pl.pallas_call(
        functools.partial(_rerank_kernel, n_d_blocks=n_d_blocks),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, L, 1, 1), jnp.float32),
        interpret=interpret,
    )(rows.astype(jnp.int32), *args)
    return out.reshape(B, L)
