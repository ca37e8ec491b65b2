"""Pallas TPU kernel: fused training-free pooling (index-time hot path).

Every pooling strategy in the paper (tile mean Eq.2, row mean Eq.3, conv1d
Eq.4, Gaussian/Triangular smoothing Eq.5 — and their compositions) is a
fixed linear operator over the patch-token axis. We therefore fuse the whole
stack into ONE masked matmul executed in a single HBM pass per page:

    out[b] = (P @ (x[b] * mask[b])) / max(P @ mask[b], 1)

where ``P`` [n_out, S] is the host-precomputed pooling matrix (see ops.py).
The page streams HBM -> VMEM in S-tiles; numerator and denominator
accumulate in VMEM scratch; one fused normalise + L2-renorm epilogue writes
the pooled vectors. This replaces the paper's numpy post-processing with an
MXU-friendly operator whose cost is one corpus read (memory-bound,
bandwidth-roofline optimal at index time).

Grid: (B, S/bs) — S innermost so accumulators carry across page tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pool_kernel(x_ref, m_ref, p_ref, out_ref, num_ref, den_ref,
                 *, n_s_blocks: int, l2_norm: bool):
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        num_ref[...] = jnp.zeros_like(num_ref)
        den_ref[...] = jnp.zeros_like(den_ref)

    x = x_ref[...].astype(jnp.float32)            # [bs, d]
    m = m_ref[...].astype(jnp.float32)            # [1, bs] (lane row)
    # the mask folds into the operator's columns: (P * m) @ x is
    # P @ (x * m) without a per-token column broadcast
    pm = p_ref[...].astype(jnp.float32) * m       # [n_out, bs]
    num_ref[...] += jax.lax.dot(pm, x, precision=jax.lax.Precision.HIGHEST,
                                preferred_element_type=jnp.float32)
    den_ref[...] += jnp.sum(pm, axis=1, keepdims=True)    # [n_out, 1]

    @pl.when(si == n_s_blocks - 1)
    def _finish():
        out = num_ref[...] / jnp.maximum(den_ref[...], 1e-9)
        if l2_norm:
            nrm = jnp.sqrt(jnp.sum(out * out, axis=-1, keepdims=True))
            out = out / jnp.maximum(nrm, 1e-9)
        out_ref[...] = out.astype(out_ref.dtype)


def page_block(S: int) -> int:
    """Default S tile: the largest lane-tile multiple (128) up to 512 that
    divides S, else the whole page (colsmol's S=832 has no such divisor)."""
    for bs in (512, 384, 256, 128):
        if S % bs == 0:
            return bs
    return S


def pool_pallas(x: jax.Array, mask: jax.Array, pool_mat: jax.Array,
                *, block_s: int = 0, l2_norm: bool = True,
                interpret: bool = True) -> jax.Array:
    """x [B,S,d], mask [B,S] f32, pool_mat [n_out,S] -> [B, n_out, d] f32.

    The mask travels as [B, 1, S] so its block's two minor dims are
    (1, bs): a whole array dim and a lane-tile multiple (or all of S)."""
    B, S, d = x.shape
    n_out, S2 = pool_mat.shape
    assert S == S2, (S, S2)
    bs = block_s if block_s > 0 else page_block(S)
    assert S % bs == 0, (S, bs)
    n_s_blocks = S // bs

    kernel = functools.partial(_pool_kernel, n_s_blocks=n_s_blocks,
                               l2_norm=l2_norm)
    return pl.pallas_call(
        kernel,
        grid=(B, n_s_blocks),
        in_specs=[
            pl.BlockSpec((None, bs, d), lambda b, s: (b, s, 0)),
            pl.BlockSpec((None, 1, bs), lambda b, s: (b, 0, s)),
            pl.BlockSpec((n_out, bs), lambda b, s: (0, s)),
        ],
        out_specs=pl.BlockSpec((None, n_out, d), lambda b, s: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, n_out, d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((n_out, d), jnp.float32),
                        pltpu.VMEM((n_out, 1), jnp.float32)],
        interpret=interpret,
    )(x, mask.astype(jnp.float32)[:, None, :], pool_mat.astype(jnp.float32))
