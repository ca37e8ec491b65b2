"""Seeded inputs of a cell: raw pages, topics and the query pool.

The recipes are copies of ``repro.data.synthetic.page_batch`` and
``ragged_queries``, kept here so that the yardstick cannot drift with the
program. Both the program's set-up and the plain reference regenerate the
corpus from these functions and the seed; nothing else carries pages from
one to the other.

A geometry is the ``geometry`` dict of a configuration file: ``n_special``
leading special tokens, ``n_patches`` visual tokens of width ``dim``, laid
out as ``bands`` groups of rows (grid rows, or tiles) in which a page's
topic is planted.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

SIGNAL, NOISE, JITTER, QUERY_NOISE = 1.0, 0.55, 0.15, 0.35
BAND_ROWS = 3             # grid rows (or tiles) that carry a page's topic


def prng_key(seed: int) -> jax.Array:
    """A JAX key from a seed of up to 64 bits (``PRNGKey`` alone keeps only
    the low 32, so seeds 2**32 apart would collide)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              (seed >> 32) & 0xFFFFFFFF)


def topics(seed: int, n_topics: int, dim: int) -> np.ndarray:
    """``n_topics`` unit topic directions [n_topics, dim] f32."""
    rng = np.random.default_rng([int(seed), 1])
    t = rng.normal(size=(n_topics, dim))
    return (t / np.linalg.norm(t, axis=1, keepdims=True)).astype(np.float32)


def _unit(x):
    return x / jnp.maximum(jnp.linalg.norm(x, axis=-1, keepdims=True), 1e-9)


@functools.partial(jax.jit, static_argnames=("n_special", "n_patches",
                                             "dim", "bands", "n_pages"))
def _page_batch(key, topic_vecs, *, n_special: int, n_patches: int,
                dim: int, bands: int, n_pages: int):
    k_t, k_n, k_r, k_j, k_s = jax.random.split(key, 5)
    t = jax.random.randint(k_t, (n_pages,), 0, topic_vecs.shape[0])
    page = NOISE * _unit(jax.random.normal(k_n, (n_pages, n_patches, dim)))
    r0 = jax.random.randint(k_r, (n_pages, 1), 0,
                            max(bands - BAND_ROWS, 1))
    row = (jnp.arange(n_patches) // (n_patches // bands))[None]
    band = ((row >= r0) & (row < r0 + BAND_ROWS)).astype(jnp.float32)
    jitter = _unit(jax.random.normal(k_j, (n_pages, n_patches, dim)))
    page = _unit(page + band[..., None] * SIGNAL
                 * (topic_vecs[t][:, None, :] + JITTER * jitter))
    spec = _unit(jax.random.normal(k_s, (n_pages, n_special, dim)))
    return jnp.concatenate([spec, page], axis=1)


def page_batch(geometry: dict, seed: int, index: int, topic_vecs,
               n_pages: int) -> jax.Array:
    """Raw pages [n_pages, n_special + n_patches, dim] f32 of batch
    ``index``, made on the device: the same seed and index give the same
    pages, whoever asks."""
    key = jax.random.fold_in(prng_key(seed), index)
    return _page_batch(key, jnp.asarray(topic_vecs, jnp.float32),
                       n_special=geometry["n_special"],
                       n_patches=geometry["n_patches"],
                       dim=geometry["dim"], bands=geometry["bands"],
                       n_pages=n_pages)


def token_types(geometry: dict) -> np.ndarray:
    """[S] token types: the special tokens (1) lead, visual patches (0)."""
    return np.concatenate([np.ones(geometry["n_special"], np.int32),
                           np.zeros(geometry["n_patches"], np.int32)])


def query_pool(seed: int, topic_vecs: np.ndarray, n: int, min_tokens: int,
               max_tokens: int) -> tuple:
    """``n`` single queries, each a noisy bundle of tokens around one
    topic: (queries [n, max_tokens, dim] f32 zero-padded, lengths [n]).

    The token counts are the same multiset for every seed (an even spread
    over [min_tokens, max_tokens]), in a seeded order: a seed changes which
    queries come when, never how much work the pool holds."""
    rng = np.random.default_rng([int(seed), 2])
    span = max_tokens - min_tokens + 1
    lens = rng.permutation(min_tokens + np.arange(n) % span)
    dim = topic_vecs.shape[1]
    q = np.zeros((n, max_tokens, dim), np.float32)
    for i, k in enumerate(lens):
        qn = rng.normal(size=(k, dim))
        qn /= np.linalg.norm(qn, axis=1, keepdims=True)
        v = topic_vecs[int(rng.integers(len(topic_vecs)))][None] \
            + QUERY_NOISE * qn
        q[i, :k] = v / np.linalg.norm(v, axis=1, keepdims=True)
    return q, lens.astype(np.int64)
