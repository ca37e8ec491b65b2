"""Per-kernel shape/dtype sweeps: Pallas (interpret=True) vs jnp oracles."""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.kernels.maxsim import (maxsim_ref, maxsim_rerank, maxsim_scores,
                                  maxsim_topk_chunked, quantize_int8)
from repro.kernels.pooling import (pool_pages_fused, pool_ref,
                                   pooling_matrix, rowmean_matrix,
                                   conv1d_matrix, smooth_matrix, tile_matrix)
from repro.kernels.embed_bag import embed_bag, embed_bag_ref
from repro.configs import get_config


# ---------------------------------------------------------------------------
# MaxSim kernel
# ---------------------------------------------------------------------------

# f32 docs at block_n=8 and odd shapes: the padding paths of both bodies
_ODD_SHAPES = [
    (1, 8, 8, 32, 128),
    (3, 10, 24, 96, 128),
    (2, 17, 40, 64, 64),      # Q not sublane-aligned -> padding path
    (4, 32, 16, 130, 128),    # D not sublane-aligned, per-page body
]
# the served pooled geometries (colpali D'=34, colsmol D'=13, D=1) in the
# served doc dtypes at the default block_n: (1,16) spans three page tiles,
# (64,32) several query blocks
_SERVED_SHAPES = [(B, Q, N, D, 128, dtype, 0)
                  for D in (34, 13, 1) for dtype in ("bfloat16", "int8")
                  for B, Q, N in ((1, 16, 1100), (16, 32, 200),
                                  (64, 32, 200))]


@pytest.mark.parametrize("B,Q,N,D,d,dtype,block_n", [
    pytest.param(*shape, "float32", 8, id="-".join(map(str, shape)))
    for shape in _ODD_SHAPES] + [
    pytest.param(*case, id="{5}-D{3}-B{0}-Q{1}-N{2}".format(*case))
    for case in _SERVED_SHAPES])
def test_maxsim_shapes(rng, B, Q, N, D, d, dtype, block_n):
    """Kernel vs the f32 oracle with masked tokens and one fully masked
    page. Docs exact in bf16 (bf16, int8 codes) hold 1e-5: the products
    stay exact to f32."""
    q = jnp.asarray(rng.normal(size=(B, Q, d)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(N, D, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Q)) > 0.2, jnp.float32)
    dm = jnp.asarray(rng.random((N, D)) > 0.1, jnp.float32).at[3].set(0.0)
    scales = None
    if dtype != "float32":    # unit vectors, as the encoders emit them
        q = q / jnp.linalg.norm(q, axis=-1, keepdims=True)
        docs = docs / jnp.linalg.norm(docs, axis=-1, keepdims=True)
    if dtype == "int8":
        docs, scales = quantize_int8(docs)
    else:
        docs = docs.astype(dtype)
    out = maxsim_scores(q, docs, qm, dm, scales, impl="pallas",
                        block_n=block_n)
    ref = maxsim_ref(q, qm, docs, dm, scales)
    tol = 1e-4 if dtype == "float32" else 1e-5
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_maxsim_dtypes(rng, dtype):
    q = jnp.asarray(rng.normal(size=(2, 8, 128)), dtype)
    docs = jnp.asarray(rng.normal(size=(16, 64, 128)), dtype)
    out = maxsim_scores(q, docs, impl="pallas", block_n=8)
    ref = maxsim_ref(q, jnp.ones((2, 8)), docs, jnp.ones((16, 64)))
    tol = 1e-4 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(out, ref, rtol=tol, atol=tol)


def test_maxsim_int8(rng):
    q = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(16, 64, 128)), jnp.float32)
    codes, scales = quantize_int8(docs)
    out = maxsim_scores(q, codes.astype(jnp.float32), None, None, scales,
                        impl="pallas", block_n=8)
    ref = maxsim_ref(q, jnp.ones((2, 8)), docs, jnp.ones((16, 64)))
    # int8 quantisation error bound, not kernel error
    np.testing.assert_allclose(out, ref, rtol=2e-2, atol=2e-1)


def test_maxsim_fully_masked_doc(rng):
    """A fully-masked document must not produce +inf/-inf leakage for
    valid query tokens of other docs."""
    q = jnp.asarray(rng.normal(size=(1, 8, 128)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(8, 16, 128)), jnp.float32)
    dm = jnp.ones((8, 16), jnp.float32).at[3].set(0.0)
    out = maxsim_scores(q, docs, None, dm, impl="pallas", block_n=8)
    assert np.isfinite(np.asarray(out))[:, :3].all()
    assert np.asarray(out)[0, 3] < -1e20        # masked doc sinks


# ---------------------------------------------------------------------------
# Fused gather-rerank kernel + streamed scan top-k
# ---------------------------------------------------------------------------

def _gathered_ref(q, qm, docs, dm, rows, ok, scales=None):
    """Expected rerank scores: full ref scan, gather the candidate
    columns, NEG the not-owned slots."""
    full = maxsim_ref(q, qm, docs, dm, scales)
    out = np.take_along_axis(np.asarray(full), np.asarray(rows), axis=1)
    return np.where(np.asarray(ok), out, -1e30)


@pytest.mark.parametrize("impl", ["ref", "jnp", "pallas"])
@pytest.mark.parametrize("B,Q,N,D,d,L", [
    (2, 8, 16, 32, 128, 6),
    (3, 11, 40, 48, 64, 9),      # Q not sublane-aligned, L not block_l mult
])
def test_rerank_impls_match_gathered_ref(rng, impl, B, Q, N, D, d, L):
    q = jnp.asarray(rng.normal(size=(B, Q, d)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(N, D, d)), jnp.float32)
    qm = jnp.asarray(rng.random((B, Q)) > 0.2, jnp.float32)
    dm = jnp.asarray(rng.random((N, D)) > 0.1, jnp.float32)
    rows = jnp.asarray(rng.integers(0, N, (B, L)), jnp.int32)
    ok = jnp.asarray(rng.random((B, L)) > 0.25)
    out = maxsim_rerank(q, docs, rows, qm, dm, None, ok, impl=impl,
                        block_d=16, block_l=4)
    exp = _gathered_ref(q, qm, docs, dm, rows, ok)
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["ref", "jnp", "pallas"])
def test_rerank_int8_dequant_in_kernel(rng, impl):
    """int8 codes + per-vector scales stream through the rerank path;
    every impl dequantises the gathered rows and matches the
    dequantise-then-gather reference."""
    q = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(24, 32, 128)), jnp.float32)
    codes, scales = quantize_int8(docs)
    rows = jnp.asarray(rng.integers(0, 24, (2, 7)), jnp.int32)
    qm = jnp.ones((2, 8), jnp.float32)
    dm = jnp.ones((24, 32), jnp.float32)
    out = maxsim_rerank(q, codes, rows, qm, dm, scales, None, impl=impl,
                        block_d=16)
    exp = _gathered_ref(q, qm, codes.astype(jnp.float32), dm, rows,
                        np.ones((2, 7), bool), scales)
    np.testing.assert_allclose(np.asarray(out), exp, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_rerank_matryoshka_truncated_docs(rng, impl):
    """Docs narrower than the query (Matryoshka rerank stage): the
    wrapper scores against the matching query prefix."""
    q = jnp.asarray(rng.normal(size=(2, 9, 128)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(16, 16, 32)), jnp.float32)
    rows = jnp.asarray(rng.integers(0, 16, (2, 5)), jnp.int32)
    out = maxsim_rerank(q, docs, rows, impl=impl)
    ref = maxsim_rerank(q, docs, rows, impl="ref")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)


def test_rerank_fully_masked_candidate(rng):
    """A fully token-masked candidate sinks without inf/nan leakage into
    other candidates' scores — and scores IDENTICALLY across impls (the
    rerank contract is maxsim_scan's raw Qv*NEG sum; no per-impl clamp
    may make degenerate candidates rank differently per dispatch
    policy)."""
    q = jnp.asarray(rng.normal(size=(1, 8, 128)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(8, 16, 128)), jnp.float32)
    dm = jnp.ones((8, 16), jnp.float32).at[3].set(0.0)
    rows = jnp.asarray([[0, 3, 5]], jnp.int32)
    ref = np.asarray(maxsim_rerank(q, docs, rows, None, dm, impl="ref"))
    for impl in ("jnp", "pallas"):
        out = np.asarray(maxsim_rerank(q, docs, rows, None, dm, impl=impl))
        assert np.isfinite(out[:, [0, 2]]).all()
        assert out[0, 1] < -1e20
        np.testing.assert_allclose(out, ref, rtol=1e-4)


@pytest.mark.parametrize("chunk", [5, 16, 48, 200])
def test_topk_chunked_matches_global_select(rng, chunk):
    """Streamed running top-k == score-everything-then-select, including
    dead doc_valid slots NEGed before each block's local select."""
    q = jnp.asarray(rng.normal(size=(3, 9, 64)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(48, 24, 64)), jnp.float32)
    qm = jnp.asarray(rng.random((3, 9)) > 0.2, jnp.float32)
    dm = jnp.asarray(rng.random((48, 24)) > 0.1, jnp.float32)
    dv = jnp.asarray(rng.random(48) > 0.3)
    s = maxsim_scores(q, docs, qm, dm, None, dv, impl="ref")
    ev, ei = jax.lax.top_k(s, 12)
    v, i = maxsim_topk_chunked(q, docs, qm, dm, None, dv, k=12,
                               chunk=chunk, impl="ref")
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
    np.testing.assert_allclose(np.asarray(v), np.asarray(ev),
                               rtol=1e-5, atol=1e-5)


def test_topk_chunked_padding_never_leaks_ids(rng):
    """Regression: chunk-padding slots must rank below EVERY real slot —
    a fully token-masked live document scores Q*NEG (below the dead-slot
    NEG), and padding scored at plain NEG used to outrank it, leaking an
    out-of-range id that aliases the next segment's slot space."""
    N, chunk, k = 5, 4, 5                   # padded to 8: 3 fake slots
    q = jnp.asarray(rng.normal(size=(1, 6, 32)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(N, 8, 32)), jnp.float32)
    dm = jnp.ones((N, 8), jnp.float32).at[0].set(0.0)   # doc 0 fully masked
    v, i = maxsim_topk_chunked(q, docs, None, dm, None, None, k=k,
                               chunk=chunk, impl="ref")
    i = np.asarray(i)
    assert (i >= 0).all() and (i < N).all(), f"padding id leaked: {i}"
    s = maxsim_scores(q, docs, None, dm, impl="ref")
    ev, ei = jax.lax.top_k(s, k)
    np.testing.assert_array_equal(i, np.asarray(ei))
    np.testing.assert_allclose(np.asarray(v), np.asarray(ev), rtol=1e-5)


def test_topk_chunked_int8_pallas(rng):
    """Streamed top-k over int8 codes through the Pallas scan kernel."""
    q = jnp.asarray(rng.normal(size=(2, 8, 128)), jnp.float32)
    docs = jnp.asarray(rng.normal(size=(32, 16, 128)), jnp.float32)
    codes, scales = quantize_int8(docs)
    s = maxsim_scores(q, codes.astype(jnp.float32), None, None, scales,
                      impl="ref")
    ev, ei = jax.lax.top_k(s, 6)
    v, i = maxsim_topk_chunked(q, codes, None, None, scales, None, k=6,
                               chunk=8, impl="pallas", block_n=8)
    np.testing.assert_array_equal(np.asarray(i), np.asarray(ei))
    np.testing.assert_allclose(np.asarray(v), np.asarray(ev),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Pooling kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["colpali", "colsmol", "colqwen"])
def test_pooling_kernel_vs_ref(rng, arch):
    cfg = get_config(arch)
    B, S, d = 3, cfg.n_patches, 128
    x = jnp.asarray(rng.normal(size=(B, S, d)), jnp.float32)
    m = jnp.asarray(rng.random((B, S)) > 0.1, jnp.float32)
    pm = jnp.asarray(pooling_matrix(cfg))
    out = pool_pages_fused(x, m, pm, impl="pallas")
    ref = pool_ref(x, m, pm)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("block_s", [64, 128, 256, 1024])
def test_pooling_kernel_blocks(rng, block_s):
    cfg = get_config("colpali")
    x = jnp.asarray(rng.normal(size=(2, 1024, 128)), jnp.float32)
    m = jnp.ones((2, 1024), jnp.float32)
    pm = jnp.asarray(pooling_matrix(cfg))
    out = pool_pages_fused(x, m, pm, impl="pallas", block_s=block_s)
    ref = pool_ref(x, m, pm)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_pooling_matrices_match_core(rng):
    """Matrix path == functional core.pooling path under full masks."""
    from repro.core import pooling as P
    x = jnp.asarray(rng.normal(size=(1024, 128)), jnp.float32)
    rows = P.row_mean_pool(x, 32, 32)
    rm = rowmean_matrix(32, 32)
    np.testing.assert_allclose(rm @ np.asarray(x) / rm.sum(1, keepdims=True),
                               rows, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(conv1d_matrix(32) @ np.asarray(rows)
                               / conv1d_matrix(32).sum(1, keepdims=True),
                               P.conv1d_extend(rows), rtol=1e-5, atol=1e-5)
    sm = smooth_matrix(32, "gaussian")
    np.testing.assert_allclose(sm @ np.asarray(rows)
                               / sm.sum(1, keepdims=True),
                               P.smooth_same_length(rows, "gaussian"),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# EmbeddingBag kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("V,d,B,L", [(100, 16, 8, 4), (1000, 32, 16, 7),
                                     (50, 128, 3, 12)])
def test_embed_bag_shapes(rng, V, d, B, L):
    table = jnp.asarray(rng.normal(size=(V, d)), jnp.float32)
    idx = jnp.asarray(rng.integers(-1, V, size=(B, L)), jnp.int32)
    for mode in ("sum", "mean"):
        out = embed_bag(table, idx, mode=mode, impl="pallas")
        ref = embed_bag(table, idx, mode=mode, impl="ref")
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)


def test_embed_bag_all_padding(rng):
    table = jnp.asarray(rng.normal(size=(10, 8)), jnp.float32)
    idx = jnp.full((2, 3), -1, jnp.int32)
    out = embed_bag(table, idx, mode="mean", impl="pallas")
    np.testing.assert_allclose(out, np.zeros((2, 8)), atol=1e-6)
