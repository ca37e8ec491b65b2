"""Distributed top-k: local select + score/id merge (never moves payloads)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.retrieval.tracing import SCOPE_EXCHANGE

NEG = -1e30


def local_topk_with_ids(scores: jax.Array, k: int, id_offset) -> tuple:
    """scores [B, n_local] -> (vals [B,k], global ids [B,k])."""
    k = min(k, scores.shape[-1])
    v, i = jax.lax.top_k(scores, k)
    return v, i + id_offset


def merge_topk(vals: jax.Array, ids: jax.Array, k: int) -> tuple:
    """Merge candidate sets along the last axis: vals/ids [B, M] -> top-k."""
    k = min(k, vals.shape[-1])
    v, sel = jax.lax.top_k(vals, k)
    return v, jnp.take_along_axis(ids, sel, axis=-1)


def gathered_merge_topk(vals: jax.Array, global_ids: jax.Array, k: int,
                        axis_name) -> tuple:
    """Inside shard_map: all-gather per-shard (vals, GLOBAL ids) [B, k']
    winner lists and merge to the top-k — identical on every shard.
    Communication: S * B * k' * 8 bytes (scores + ids), never the
    documents. The merge half of ``allgather_topk``, reused directly by
    the streamed scan top-k path (whose local select already happened
    chunk-by-chunk inside the scan). Its ops carry the exchange scope."""
    with jax.named_scope(SCOPE_EXCHANGE):
        av = jax.lax.all_gather(vals, axis_name, axis=1, tiled=True)
        ai = jax.lax.all_gather(global_ids, axis_name, axis=1, tiled=True)
        return merge_topk(av, ai, k)              # over [B, S*k']


def allgather_topk(scores_local: jax.Array, k: int, axis_name,
                   shard_index, n_local: int,
                   valid_local: jax.Array | None = None,
                   seg_offset: int = 0) -> tuple:
    """Inside shard_map: per-shard top-k then all-gather + merge.

    scores_local [B, n_local]; returns identical (vals, global ids) [B, k]
    on every shard.

    ``valid_local`` [n_local] bool masks dead/padding slots to NEG before the
    local select (capacity-padded segmented stores: the tail of a ragged
    shard and deleted documents must never win a top-k slot on merit).
    ``seg_offset`` shifts the returned ids into the global slot space when
    the scored array is one segment of a larger corpus.
    """
    if valid_local is not None:
        scores_local = jnp.where(valid_local[None, :], scores_local, NEG)
    v, gi = local_topk_with_ids(scores_local, k,
                                shard_index * n_local + seg_offset)
    return gathered_merge_topk(v, gi, k, axis_name)
