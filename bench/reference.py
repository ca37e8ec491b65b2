"""The plain reference of the served cascade, and the comparison that
decides ``correct``.

Imports nothing of the program. From the seed it regenerates the raw pages
(``bench.corpus``), indexes them the way the paper describes (token
hygiene, then model-aware pooling with an L2 renorm, both in float32, then
the configuration's store dtype), and runs the two-stage cascade in
float32 at the highest matmul precision: a MaxSim scan over the pooled
vectors keeps ``prefetch_k`` candidates, an exact MaxSim over the
full-resolution vectors ranks them.

The comparison judges each sampled answer by what it says, not by rank
position, so a near tie at the prefetch boundary (two candidates whose
pooled scores differ by rounding) cannot fail a sound run:

- ``score_err``: the largest gap between a returned score and the
  reference's exact score of the same page, over the request's best
  reference score.
- ``prefetch_gap``: the largest amount by which a returned page's pooled
  score lies below the reference's ``prefetch_k``-th pooled score, over
  the request's best pooled score (a page the scan should never have
  passed on).
- ``missed_gap``: the largest amount by which the exact score of a page
  the reference's scan passes on, clear of the boundary by more than the
  ``prefetch_gap`` limit, lies above the worst returned page's, over the
  best reference score (a page the cascade should have returned).
- ``misordered``: adjacent returned pairs in rising score order.
- ``bad_ids``: returned ids outside the corpus or repeated in one answer.
"""
from __future__ import annotations

import contextlib
import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import corpus

HIGHEST = jax.lax.Precision.HIGHEST


def _pool_matrices(geometry: dict) -> tuple:
    """The model-aware pooling as two linear maps: ``group`` [G, P], the
    0/1 membership of patches in grid rows (colpali) or tiles (colsmol),
    and ``window`` [D', G], how each pooled vector averages groups:
    colpali's width-3 window over the row means, extended by one row at
    each end (each output the mean of the rows it covers); the identity
    for colsmol's tile means."""
    bands, n = geometry["bands"], geometry["n_patches"]
    per = n // bands
    group = np.zeros((bands, n), np.float32)
    for b in range(bands):
        group[b, b * per:(b + 1) * per] = 1.0
    if geometry["pooling"] == "tile_mean":
        return group, np.eye(bands, dtype=np.float32)
    if geometry["pooling"] != "row_mean_conv1d":
        raise ValueError(f"unknown pooling {geometry['pooling']!r}")
    window = np.zeros((bands + 2, bands), np.float32)
    for i in range(bands + 2):
        rows = [j for j in (i - 2, i - 1, i) if 0 <= j < bands]
        window[i, rows] = 1.0 / len(rows)
    return group, window


@functools.partial(jax.jit, static_argnames=("n_special", "dtype"))
def _index(raw, group, window, *, n_special: int, dtype: str):
    """Raw pages [n, S, d] -> (pooled [n, D', d], full [n, P, d]) in the
    store dtype, and the full-resolution token mask [n, P]. Hygiene keeps
    the visual tokens that are not padding; a group's mean counts only
    kept tokens."""
    vis = raw[:, n_special:]
    keep = jnp.linalg.norm(vis, axis=-1) >= 1e-6
    vis = vis * keep[..., None]
    sums = jnp.einsum("gk,nkd->ngd", group, vis, precision=HIGHEST)
    counts = jnp.einsum("gk,nk->ng", group, keep.astype(jnp.float32),
                        precision=HIGHEST)
    means = sums / jnp.maximum(counts, 1.0)[..., None]
    pooled = jnp.einsum("pg,ngd->npd", window, means, precision=HIGHEST)
    pooled = pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-9)
    return pooled.astype(dtype), vis.astype(dtype), keep


@functools.partial(jax.jit, donate_argnums=(0,))
def _put(buf, block, start):
    return jax.lax.dynamic_update_slice(
        buf, block, (start,) + (0,) * (buf.ndim - 1))


def _sum_best(sim, qm):
    """sim [B, C, Q, D] -> [B, C]: per query token the best document
    token, summed over the query's tokens."""
    return jnp.sum(jnp.where(qm[:, None, :], sim.max(-1), 0.0), axis=-1)


@functools.partial(jax.jit, static_argnames=("chunk",))
def _scan(q, qm, pooled, *, chunk: int):
    """[B, Q, d] against every pooled page [N, D', d] -> [B, N]."""
    n = pooled.shape[0]
    blocks = pooled.reshape(n // chunk, chunk, *pooled.shape[1:])

    def one(block):
        sim = jnp.einsum("bqd,cjd->bcqj", q, block.astype(jnp.float32),
                         precision=HIGHEST)
        return _sum_best(sim, qm)

    return jnp.moveaxis(jax.lax.map(one, blocks), 0, 1).reshape(q.shape[0], n)


@jax.jit
def _exact(q, qm, full, keep, ids):
    """Exact scores of pages ``ids`` [B, C] for queries [B, Q, d]."""
    sim = jnp.einsum("bqd,bcjd->bcqj", q, full[ids].astype(jnp.float32),
                     precision=HIGHEST)
    return _sum_best(jnp.where(keep[ids][:, :, None, :], sim, -jnp.inf), qm)


def _on(device):
    """Place what follows on ``device``; the default device for None."""
    return contextlib.nullcontext() if device is None \
        else jax.default_device(device)


def _merge_top(parts: list, per: int, k: int) -> tuple:
    """The best ``k`` of the shards' own top lists ``[(scores [B, k], ids
    [B, k])]``, shard ``s``'s ids offset by ``s * per``: (scores, ids) in
    falling score order, a tie to the lower id, as ``lax.top_k`` orders
    them over the whole corpus. One shard's list is already that."""
    if len(parts) == 1:
        return parts[0]
    sc = np.concatenate([p[0] for p in parts], axis=1)
    gi = np.concatenate([p[1] + s * per for s, p in enumerate(parts)],
                        axis=1)
    order = np.lexsort((gi, -sc))[:, :k]
    return (np.take_along_axis(sc, order, axis=1),
            np.take_along_axis(gi, order, axis=1))


class Reference:
    """The regenerated corpus, indexed by the reference: pooled and
    full-resolution vectors of every page, in page-id order (the order the
    program ingested them, batch by batch).

    On the default device, or doc-sharded over ``devices``: device ``s``
    holds pages ``[s * per, (s + 1) * per)`` and computes their scores, so
    that no device holds more than its share of the corpus and one query
    block's working set. The shards' top lists meet on the host. Every
    number ``search`` returns is the one-device reference's, bit for bit:
    each page is indexed and scored by the same programs at the same
    shapes (the scan's ``chunk`` pages a block permitting), only on
    another device."""

    def __init__(self, cfg: dict, seed: int, devices=None):
        geo = cfg["geometry"]
        n, batch = cfg["pages"], cfg["ingest_batch"]
        self.devices = list(devices) if devices else [None]
        per = n // len(self.devices)
        if per * len(self.devices) != n:
            raise ValueError(f"{n} pages do not split evenly over "
                             f"{len(self.devices)} devices")
        topic_vecs = corpus.topics(seed, cfg["topics"], geo["dim"])
        dt = cfg["store_dtype"]
        self.pooled, self.full, self.keep = [], [], []
        for s, dev in enumerate(self.devices):
            lo, hi = s * per, (s + 1) * per
            with _on(dev):
                group, window = (jnp.asarray(m) for m in _pool_matrices(geo))
                pooled = jnp.zeros((per, window.shape[0], geo["dim"]), dt)
                full = jnp.zeros((per, geo["n_patches"], geo["dim"]), dt)
                keep = jnp.zeros((per, geo["n_patches"]), bool)
                for b in range(lo // batch, -(-hi // batch)):
                    raw = corpus.page_batch(geo, seed, b, topic_vecs, batch)
                    idx = _index(raw, group, window,
                                 n_special=geo["n_special"], dtype=dt)
                    a, z = max(b * batch, lo), min((b + 1) * batch, hi)
                    if z - a < batch:     # a batch over two shards
                        idx = tuple(x[a - b * batch:z - b * batch]
                                    for x in idx)
                    start = jnp.int32(a - lo)
                    pooled = _put(pooled, idx[0], start)
                    full = _put(full, idx[1], start)
                    keep = _put(keep, idx[2], start)
            if dev is not None:
                # committed: a program given a shard runs on its device
                pooled, full, keep = jax.device_put((pooled, full, keep), dev)
            self.pooled.append(pooled)
            self.full.append(full)
            self.keep.append(keep)
        self.n, self.per = n, per

    def search(self, q: np.ndarray, lens: np.ndarray, prefetch_k: int,
               extra_ids: np.ndarray, block: int = 8,
               chunk: int = 2048) -> dict:
        """The cascade for queries [S, Q, d] of token counts ``lens``:
        pooled scores of the ``prefetch_k`` best pages and of the next one,
        and exact scores of the candidates and of ``extra_ids`` [S, k]
        (the program's answers), all on the host."""
        S = len(q)
        qm = np.arange(q.shape[1])[None, :] < lens[:, None]
        pad = (-S) % block
        q = np.concatenate([q, np.zeros((pad,) + q.shape[1:], q.dtype)])
        qm = np.concatenate([qm, np.zeros((pad, qm.shape[1]), bool)])
        extra = np.concatenate([extra_ids, np.zeros(
            (pad, extra_ids.shape[1]), extra_ids.dtype)])
        chunk = min(chunk, self.per)
        while self.per % chunk:
            chunk //= 2
        out = {"pooled_top": [], "cand": [], "exact": [], "pooled_extra": []}
        with jax.default_matmul_precision("highest"):
            for i in range(0, len(q), block):
                ex = np.clip(extra[i:i + block], 0, self.n - 1)
                tops, taken, blocks = [], [], []
                for s, dev in enumerate(self.devices):
                    with _on(dev):
                        qb = jnp.asarray(q[i:i + block])
                        mb = jnp.asarray(qm[i:i + block])
                        sc = _scan(qb, mb, self.pooled[s], chunk=chunk)
                        tops.append(jax.lax.top_k(
                            sc, min(prefetch_k + 1, self.per)))
                        taken.append(jnp.take_along_axis(
                            sc, jnp.asarray(np.clip(ex - s * self.per, 0,
                                                    self.per - 1)), axis=1))
                    blocks.append((qb, mb))
                top_s, top_i = _merge_top(
                    [tuple(np.array(a) for a in t) for t in tops],
                    self.per, prefetch_k + 1)
                ids = np.concatenate([top_i[:, :prefetch_k], ex], axis=1)
                exact = [_exact(qb, mb, self.full[s], self.keep[s],
                                jnp.asarray(np.clip(ids - s * self.per, 0,
                                                    self.per - 1)))
                         for s, (qb, mb) in enumerate(blocks)]
                owner = ids // self.per
                owner_ex = ex // self.per
                out["pooled_top"].append(top_s)
                out["cand"].append(top_i[:, :prefetch_k])
                out["exact"].append(_pick(exact, owner))
                out["pooled_extra"].append(_pick(taken, owner_ex))
        return {k: np.concatenate(v)[:S] for k, v in out.items()}


def _pick(per_shard: list, owner: np.ndarray) -> np.ndarray:
    """Each entry from the shard that owns its page: ``per_shard[s]`` holds
    every entry as shard ``s`` computed it, right only where ``owner`` is
    ``s``. Copied to the host, so no device buffer outlives its block."""
    got = [np.array(a) for a in per_shard]
    out = got[0]
    for s, a in enumerate(got[1:], start=1):
        out = np.where(owner == s, a, out)
    return out


def compare(ref: dict, scores: np.ndarray, ids: np.ndarray, n_docs: int,
            prefetch_k: int, pool_tol: float) -> dict:
    """The numbers ``correct`` is decided by, for sampled answers
    (``scores``/``ids`` [S, k], as served) against ``Reference.search``'s
    output for the same queries. ``pool_tol`` is the ``prefetch_gap``
    limit: candidates the reference's scan keeps by less than it are
    ambiguous and left out of ``missed_gap``."""
    k = ids.shape[1]
    cand, exact = ref["cand"], ref["exact"]
    ex_cand, ex_ret = exact[:, :prefetch_k], exact[:, prefetch_k:]
    best = np.maximum(np.max(exact, axis=1), 1e-9)
    pooled_top = ref["pooled_top"]
    pooled_best = np.maximum(pooled_top[:, 0], 1e-9)
    kth = pooled_top[:, prefetch_k - 1]

    bad = (ids < 0) | (ids >= n_docs)
    dup = np.array([len(set(row.tolist())) < len(row) for row in ids])
    score_err = np.abs(scores - ex_ret) / best[:, None]
    prefetch_gap = np.maximum(kth[:, None] - ref["pooled_extra"], 0.0) \
        / pooled_best[:, None]
    # candidate j's pooled score is the j-th of pooled_top
    clear = pooled_top[:, :prefetch_k] - kth[:, None] \
        > pool_tol * pooled_best[:, None]
    returned = np.array([np.isin(cand[r], ids[r]) for r in range(len(cand))])
    worst = np.min(np.where(bad, np.inf, ex_ret), axis=1)
    missed = np.where(clear & ~returned,
                      np.maximum(ex_cand - worst[:, None], 0.0), 0.0) \
        / best[:, None]
    return {
        "score_err": float(np.max(np.where(bad, np.inf, score_err))),
        "prefetch_gap": float(np.max(np.where(bad, np.inf, prefetch_gap))),
        "missed_gap": float(np.max(missed)),
        "misordered": int(np.sum(np.diff(scores, axis=1) > 0)),
        "bad_ids": int(bad.sum() + dup.sum()),
        "compared": int(len(ids) * k),
    }
