"""Pooling-matrix construction + jitted wrapper for the fused pooling kernel.

Every training-free strategy is lowered to one [n_out, S] matrix; strategy
composition (e.g. conv1d-over-row-means) is matrix composition with the
kernel's single mask-normalisation — exactly equivalent to the two-step
reference whenever the hygiene mask is uniform within a pooling group (the
common case: padding lives outside the visual-token range), and tested
against ``pool_ref`` unconditionally.

Per-page dynamic geometries (ColQwen h_eff < grid bound) take the pure-jnp
path in ``repro.core.pooling``; the kernel path covers the static-geometry
index-time bulk.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.pooling import smoothing_weights
from repro.kernels import dispatch as DSP
from repro.kernels.dispatch import default_interpret
from repro.kernels.pooling.pooling import pool_pallas
from repro.kernels.pooling.ref import pool_ref


def rowmean_matrix(grid_h: int, grid_w: int) -> np.ndarray:
    """[H, H*W] indicator: masked mean across each grid row (Eq. 3)."""
    p = np.zeros((grid_h, grid_h * grid_w), np.float32)
    for h in range(grid_h):
        p[h, h * grid_w:(h + 1) * grid_w] = 1.0
    return p


def tile_matrix(n_tiles: int, tile_patches: int) -> np.ndarray:
    """[T, T*P] indicator: masked mean within each tile group (Eq. 2)."""
    p = np.zeros((n_tiles, n_tiles * tile_patches), np.float32)
    for t in range(n_tiles):
        p[t, t * tile_patches:(t + 1) * tile_patches] = 1.0
    return p


def conv1d_matrix(n: int, k: int = 3) -> np.ndarray:
    """[N+2r, N] uniform sliding window with boundary extension (Eq. 4)."""
    r = k // 2
    p = np.zeros((n + 2 * r, n), np.float32)
    for i in range(n + 2 * r):
        for off in range(-r, r + 1):
            j = (i - r) + off
            if 0 <= j < n:
                p[i, j] = 1.0
    return p


def smooth_matrix(n: int, kind: str, k: int = 3) -> np.ndarray:
    """[N, N] same-length weighted smoothing (Eq. 5); rows renormalised."""
    r = k // 2
    w = np.asarray(smoothing_weights(kind, k))
    p = np.zeros((n, n), np.float32)
    for i in range(n):
        for di, off in enumerate(range(-r, r + 1)):
            j = i + off
            if 0 <= j < n:
                p[i, j] = w[di]
    return p


def adaptive_matrix(h: int, t_max: int) -> np.ndarray:
    """[T, H] evenly-spaced row binning for a static h (dynamic h -> jnp path)."""
    t = min(h, t_max)
    p = np.zeros((t, h), np.float32)
    for j in range(h):
        p[(j * t) // h, j] = 1.0
    return p


def pooling_matrix(cfg) -> np.ndarray:
    """Compose the model-aware pooling stack into one matrix [n_pooled, S]."""
    if cfg.geometry == "tiles":
        return tile_matrix(cfg.n_tiles, cfg.tile_patches)
    base = rowmean_matrix(cfg.grid_h, cfg.grid_w)
    if cfg.geometry == "grid":
        if cfg.smooth == "conv1d":
            return conv1d_matrix(cfg.grid_h) @ base
        if cfg.smooth in ("gaussian", "triangular"):
            return smooth_matrix(cfg.grid_h, cfg.smooth) @ base
        return base
    if cfg.geometry == "dynamic":
        if cfg.smooth in ("gaussian", "triangular"):
            base = smooth_matrix(cfg.grid_h, cfg.smooth) @ base
        return adaptive_matrix(cfg.grid_h, cfg.max_rows) @ base
    raise ValueError(cfg.geometry)


def global_matrix(s: int) -> np.ndarray:
    return np.ones((1, s), np.float32)


def pooling_matrix_static(cfg) -> tuple:
    """``pooling_matrix`` padded to the store's STATIC pooled-vector count:
    (matrix [cfg.n_pooled, n_patches], row_valid [cfg.n_pooled] bool).

    The dynamic geometry's adaptive matrix has ``min(grid_h, max_rows)``
    rows but the store holds ``max_rows`` slots with a validity mask
    (``adaptive_row_pool`` pads, it never upsamples); zero matrix rows
    reproduce those empty trailing slots (0-vectors, mask False), so the
    fused path emits exactly the reference layout."""
    p = pooling_matrix(cfg)
    n_out = cfg.n_pooled
    if p.shape[0] < n_out:
        p = np.concatenate(
            [p, np.zeros((n_out - p.shape[0], p.shape[1]), p.dtype)])
    return p, p.sum(axis=1) > 0


def pooling_factors(cfg) -> tuple:
    """Factor the composed pooling stack as ``P = P2 @ G``: a uniform
    GROUP indicator ``G`` [n_groups, S] (grid rows / tile groups — never
    materialised, it evaluates as a reshape-sum) followed by a small dense
    stage-2 matrix ``P2`` [cfg.n_pooled, n_groups] (smoothing / conv1d /
    adaptive binning; identity when the stack is a plain group mean).

    Returns (n_groups, P2, row_valid). ``P2 @ G == pooling_matrix_static``
    exactly (indicator compositions), so the factored evaluation computes
    the same single-normalisation operator while skipping the structural
    zeros a full [n_out, S] matmul would multiply through — the fast jnp
    twin of the Pallas kernel off-TPU (see ``pool_pages_grouped``)."""
    if cfg.geometry == "tiles":
        g = cfg.n_tiles
        p2 = np.eye(g, dtype=np.float32)
    else:
        g = cfg.grid_h
        if cfg.geometry == "grid":
            if cfg.smooth == "conv1d":
                p2 = conv1d_matrix(g)
            elif cfg.smooth in ("gaussian", "triangular"):
                p2 = smooth_matrix(g, cfg.smooth)
            else:
                p2 = np.eye(g, dtype=np.float32)
        else:                                  # dynamic
            p2 = adaptive_matrix(g, cfg.max_rows)
            if cfg.smooth in ("gaussian", "triangular"):
                p2 = p2 @ smooth_matrix(g, cfg.smooth)
    n_out = cfg.n_pooled
    if p2.shape[0] < n_out:
        p2 = np.concatenate(
            [p2, np.zeros((n_out - p2.shape[0], p2.shape[1]), p2.dtype)])
    return g, np.asarray(p2, np.float32), p2.sum(axis=1) > 0


def pool_pages_grouped(x: jax.Array, mask: jax.Array, p2: jax.Array,
                       n_groups: int, l2_norm: bool = True) -> jax.Array:
    """Factored evaluation of the fused pooling operator:
    x [B,S,d] + mask [B,S] + p2 [n_out, n_groups] -> pooled [B,n_out,d].

    Same masked single-normalisation semantics as
    ``pool_ref(x, mask, p2 @ G)`` — numerator and denominator both factor
    through the group sums — with the group stage evaluated as a
    reshape-sum instead of a matmul against indicator rows."""
    DSP.record("pooling", "jnp")
    B, S, d = x.shape
    w = S // n_groups
    assert S == n_groups * w, (S, n_groups)
    m = mask.astype(jnp.float32)
    xf = x.astype(jnp.float32) * m[..., None]
    gx = xf.reshape(B, n_groups, w, d).sum(axis=2)          # [B, G, d]
    gm = m.reshape(B, n_groups, w).sum(axis=2)              # [B, G]
    p2 = p2.astype(jnp.float32)
    num = jnp.einsum("og,bgd->bod", p2, gx)
    den = jnp.einsum("og,bg->bo", p2, gm)
    out = num / jnp.maximum(den, 1e-9)[..., None]
    if l2_norm:
        out = out / jnp.maximum(
            jnp.linalg.norm(out, axis=-1, keepdims=True), 1e-9)
    return out


def _pool(x, m, pm):
    return pool_pages_fused(x, m, pm, impl="pallas", interpret=False)


def served_instances() -> dict:
    """{case: (family, fn, [(shape, dtype), ...])}: the native pooling
    kernel at the page widths of the static-geometry configs (colpali
    S=1024, colsmol S=832) over a 64-page ingest batch."""
    from repro.configs import get_config
    out = {}
    for arch in ("colpali", "colsmol"):
        cfg = get_config(arch)
        mat, _ = pooling_matrix_static(cfg)
        out[f"pool-{arch}-S{cfg.n_patches}"] = (
            "pooling", _pool,
            [((64, cfg.n_patches, cfg.out_dim), jnp.float32),
             ((64, cfg.n_patches), jnp.float32), (mat.shape, jnp.float32)])
    return out


def _probe_pool() -> bool:
    """The ``pooling`` dispatch-registry probe: the served instances
    compiled on TPU, a tiny interpreted instance elsewhere (where callers
    resolve to the jnp twin when it fails)."""
    if not default_interpret():
        return DSP.compile_served(served_instances(), "pooling")
    x = jnp.zeros((1, 8, 128), jnp.float32)
    m = jnp.ones((1, 8), jnp.float32)
    pm = jnp.ones((2, 8), jnp.float32)
    out = pool_pages_fused(x, m, pm, impl="pallas", block_s=8,
                           interpret=default_interpret())
    jax.block_until_ready(out)
    return True


def pallas_available() -> bool:
    """Whether the fused pooling kernel executes here
    (``dispatch.available``)."""
    return DSP.available("pooling")


@functools.partial(jax.jit, static_argnames=("impl", "block_s", "l2_norm",
                                             "interpret"))
def pool_pages_fused(x: jax.Array, mask: jax.Array, pool_mat: jax.Array,
                     *, impl: str = "pallas", block_s: int = 0,
                     l2_norm: bool = True, interpret: bool = True):
    """x [B,S,d] + mask [B,S] + pool_mat [n_out,S] -> pooled [B,n_out,d]."""
    DSP.record("pooling", impl)
    if impl == "ref":
        return pool_ref(x, mask, pool_mat, l2_norm=l2_norm)
    return pool_pallas(x, mask, pool_mat, block_s=block_s, l2_norm=l2_norm,
                       interpret=interpret)


# interpret-mode Pallas is a correctness tool, not an ingest path: off-TPU
# the fused operator serves a jnp evaluation (the ingest pipeline maps the
# resolved fallback onto ``pool_pages_grouped``). All three impl names are
# evaluations of the SAME fused matrix formulation, so all of them count as
# kernel-routed for the ingest CI gate — the functional ``core.pooling``
# reference chain is the only non-fused path and it never records.
DSP.register(DSP.KernelOp(
    name="pooling", probe=_probe_pool, fallback="ref",
    interpret_ok=False, kernel_impls=frozenset({"pallas", "jnp", "ref"})))
