"""Sharded sparse-embedding substrate (the recsys hot path).

JAX has no nn.EmbeddingBag and no CSR sparse; lookups are built from
``jnp.take`` + ``segment_sum`` (kernel taxonomy §RecSys) with a mixed
sharding layout modelled on production DLRM systems:

- fields with vocab >= ``row_shard_threshold`` are concatenated into ONE
  row-sharded table (P('model', None)); a lookup into it lowers to a masked
  local gather + all-reduce over the model axis (XLA SPMD) — only these
  8-of-26 Criteo-TB fields pay interconnect bytes;
- small fields are concatenated into one replicated table; their lookups
  are communication-free.

``lookup_shardmap`` is the explicit shard_map twin of the row-sharded path
(masked local take + psum) used for perf A/B against the XLA-partitioned
gather. Multi-hot bags use take + segment-sum (or the Pallas embed_bag
kernel on the serving path).
"""
from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map


@dataclass(frozen=True)
class EmbeddingLayout:
    vocab_sizes: tuple
    dim: int
    row_shard_threshold: int = 100_000

    @property
    def big_fields(self) -> tuple:
        return tuple(i for i, v in enumerate(self.vocab_sizes)
                     if v >= self.row_shard_threshold)

    @property
    def small_fields(self) -> tuple:
        return tuple(i for i, v in enumerate(self.vocab_sizes)
                     if v < self.row_shard_threshold)

    def offsets(self, fields) -> np.ndarray:
        offs, cum = [], 0
        for i in fields:
            offs.append(cum)
            cum += self.vocab_sizes[i]
        return np.asarray(offs, np.int64), cum

    def padded_rows(self, total: int, n_shards: int) -> int:
        return -(-total // max(n_shards, 1)) * max(n_shards, 1)


def init_embedding(layout: EmbeddingLayout, key, n_shards: int = 1,
                   scale: float | None = None) -> dict:
    kb, ks = jax.random.split(key)
    scale = scale if scale is not None else layout.dim ** -0.5
    _, big_total = layout.offsets(layout.big_fields)
    _, small_total = layout.offsets(layout.small_fields)
    big_rows = layout.padded_rows(max(big_total, 1), n_shards)
    p = {}
    if layout.big_fields:
        p["big"] = jax.random.normal(kb, (big_rows, layout.dim),
                                     jnp.float32) * scale
    if layout.small_fields:
        p["small"] = jax.random.normal(ks, (small_total, layout.dim),
                                       jnp.float32) * scale
    return p


def embedding_specs(layout: EmbeddingLayout) -> dict:
    out = {}
    if layout.big_fields:
        out["big"] = ("tp", None)
    if layout.small_fields:
        out["small"] = (None, None)
    return out


def lookup(layout: EmbeddingLayout, params: dict, idx: jax.Array,
           shard=None) -> jax.Array:
    """idx [B, n_fields] per-field local ids -> [B, n_fields, dim].

    Row-sharded table lookups are partitioned by XLA (masked local gather +
    all-reduce over the model axis).
    """
    B, nf = idx.shape
    out = jnp.zeros((B, nf, layout.dim), jnp.float32)
    if layout.big_fields:
        offs, _ = layout.offsets(layout.big_fields)
        gid = idx[:, list(layout.big_fields)] + jnp.asarray(offs)
        vecs = jnp.take(params["big"], gid, axis=0)
        out = out.at[:, list(layout.big_fields)].set(vecs)
    if layout.small_fields:
        offs, _ = layout.offsets(layout.small_fields)
        gid = idx[:, list(layout.small_fields)] + jnp.asarray(offs)
        vecs = jnp.take(params["small"], gid, axis=0)
        out = out.at[:, list(layout.small_fields)].set(vecs)
    if shard is not None:
        out = shard.constrain(out, "dp", None, None)
    return out


def lookup_shardmap(layout: EmbeddingLayout, params: dict, idx: jax.Array,
                    shard) -> jax.Array:
    """Explicit masked-local-gather + psum for the row-sharded table."""
    B, nf = idx.shape
    out = jnp.zeros((B, nf, layout.dim), jnp.float32)
    mesh = shard.mesh
    if layout.big_fields:
        offs, _ = layout.offsets(layout.big_fields)
        gid = idx[:, list(layout.big_fields)] + jnp.asarray(offs)
        tp_axes = shard.rules["tp"]
        tp_ax = tp_axes[0] if isinstance(tp_axes, tuple) else tp_axes

        def local(table_loc, gids):
            n_shards = jax.lax.axis_size(tp_ax)
            rows = table_loc.shape[0]
            my = jax.lax.axis_index(tp_ax)
            lo = my * rows
            loc = gids - lo
            ok = (loc >= 0) & (loc < rows)
            got = jnp.take(table_loc, jnp.clip(loc, 0, rows - 1), axis=0)
            got = jnp.where(ok[..., None], got, 0.0)
            return jax.lax.psum(got, tp_ax)

        vecs = shard_map(
            local, mesh=mesh,
            in_specs=(P(tp_ax, None), P()),
            out_specs=P(),
            check_vma=False,
        )(params["big"], gid)
        out = out.at[:, list(layout.big_fields)].set(vecs)
    if layout.small_fields:
        offs, _ = layout.offsets(layout.small_fields)
        gid = idx[:, list(layout.small_fields)] + jnp.asarray(offs)
        out = out.at[:, list(layout.small_fields)].set(
            jnp.take(params["small"], gid, axis=0))
    return shard.constrain(out, "dp", None, None)


def bag_lookup(table: jax.Array, indices: jax.Array,
               valid: jax.Array | None = None, mode: str = "mean"):
    """Multi-hot embedding bag via take + masked reduce (jnp path)."""
    if valid is None:
        valid = indices >= 0
    w = valid.astype(jnp.float32)
    if mode == "mean":
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1.0)
    rows = jnp.take(table, jnp.clip(indices, 0, table.shape[0] - 1), axis=0)
    return jnp.einsum("...l,...ld->...d", w, rows)
