"""Shared transformer layers: RMSNorm, RoPE, GQA attention (sliding window +
logit soft-capping), gated MLP, MoE (dense baseline + ragged dispatch).

All functions are pure; parameters are plain dict pytrees. Sharding is
expressed through a ShardingPolicy (no-op without a mesh).
"""
from __future__ import annotations

import dataclasses
import math
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp

NEG = -1e30


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps) * (1.0 + w.astype(jnp.float32))
    return out.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x [..., S, H, hd]; positions [..., S] (broadcastable)."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    # ang: [..., S, 1, half] (broadcasts over the head axis)
    ang = positions[..., :, None, None].astype(jnp.float32) * freq
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return cap * jnp.tanh(x / cap)


def _act(name: str):
    return {"gelu": jax.nn.gelu, "silu": jax.nn.silu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

# above this many query positions, attention scans q-chunks so the [S, S]
# score matrix never materialises (memory-efficient attention; exact).
ATTN_CHUNK_THRESHOLD = 8192
ATTN_CHUNK = 1024


def _sdpa_block(cfg, qh, k, v, q_pos, kv_pos, window, shard, attn_mode):
    """qh [B,c,KV,rep,hd]; k/v [B,S,KV,hd]; q_pos [c]; kv_pos [S]."""
    scores = jnp.einsum("bskrh,btkh->bkrst", qh, k)
    scores = softcap(scores, cfg.attn_softcap)
    i = q_pos[:, None]
    jj = kv_pos[None, :]
    mask = jj <= i
    if window:
        mask = mask & (i - jj < window)
    scores = jnp.where(mask[None, None, None, :, :], scores, NEG)
    if attn_mode == "seq":
        scores = shard.constrain(scores, "dp", None, None, "sp", None)
    w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(qh.dtype)
    return jnp.einsum("bkrst,btkh->bskrh", w, v)


def _sdpa(cfg, qh, k, v, positions, window, shard, attn_mode):
    """Exact attention; q-chunked above ATTN_CHUNK_THRESHOLD."""
    B, S = qh.shape[0], qh.shape[1]
    if S <= ATTN_CHUNK_THRESHOLD or S % ATTN_CHUNK:
        return _sdpa_block(cfg, qh, k, v, positions, positions, window,
                           shard, attn_mode)
    nc = S // ATTN_CHUNK
    qc = jnp.moveaxis(
        qh.reshape(B, nc, ATTN_CHUNK, *qh.shape[2:]), 1, 0)
    pc = positions.reshape(nc, ATTN_CHUNK)

    def body(_, xs):
        qb, pb = xs
        ob = _sdpa_block(cfg, qb, k, v, pb, positions, window, shard,
                         attn_mode)
        return None, ob

    _, oc = jax.lax.scan(body, None, (qc, pc))
    return jnp.moveaxis(oc, 0, 1).reshape(B, S, *qh.shape[2:])


def attention(cfg, p: dict, x: jax.Array, positions: jax.Array,
              window: int, shard, kv_cache: dict | None = None,
              decode_pos: jax.Array | None = None):
    """GQA attention. x [B,S,D].

    Train/prefill: ``kv_cache`` None (or a cache dict to FILL during
    prefill). Decode: S==1, ``decode_pos`` scalar position, ``kv_cache``
    holds [B,Sc,kv,hd] ring/linear caches; returns (y, new_cache).
    """
    B, S, D = x.shape
    H, KV, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    rep = H // KV
    attn_mode = "heads" if H % max(shard.axis_size("tp"), 1) == 0 else "seq"

    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    q = rope(q, positions, cfg.rope_theta) * (hd ** -0.5)
    k = rope(k, positions, cfg.rope_theta)

    new_cache = None
    if kv_cache is not None and decode_pos is not None:
        # ---- decode: write this token into the (ring) cache, attend to it
        Sc = kv_cache["k"].shape[1]
        slot = decode_pos % Sc if window else jnp.minimum(decode_pos, Sc - 1)
        ck = jax.lax.dynamic_update_slice(
            kv_cache["k"], k.astype(kv_cache["k"].dtype), (0, slot, 0, 0))
        cv = jax.lax.dynamic_update_slice(
            kv_cache["v"], v.astype(kv_cache["v"].dtype), (0, slot, 0, 0))
        new_cache = {"k": ck, "v": cv}
        ck = shard.constrain(ck, "dp" if B > 1 else None, "sp", None, None)
        cv = shard.constrain(cv, "dp" if B > 1 else None, "sp", None, None)
        j = jnp.arange(Sc)
        if window:
            valid = jnp.where(decode_pos + 1 >= Sc, True, j <= decode_pos)
        else:
            valid = j <= decode_pos
        qh = q.reshape(B, S, KV, rep, hd)
        scores = jnp.einsum("bskrh,bjkh->bkrsj", qh, ck.astype(x.dtype))
        scores = softcap(scores, cfg.attn_softcap)
        scores = jnp.where(valid[None, None, None, None, :], scores, NEG)
        w = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(x.dtype)
        o = jnp.einsum("bkrsj,bjkh->bskrh", w, cv.astype(x.dtype))
        o = o.reshape(B, S, H, hd)
    else:
        # ---- train/prefill: full (windowed-causal) self-attention
        if kv_cache is not None:
            # prefill: persist the last Sc positions (ring layout for windows)
            Sc = kv_cache["k"].shape[1]
            take = min(Sc, S)
            ks = k[:, S - take:].astype(kv_cache["k"].dtype)
            vs = v[:, S - take:].astype(kv_cache["v"].dtype)
            if window and S >= Sc:
                roll = (S % Sc)
                ks = jnp.roll(ks, roll, axis=1)
                vs = jnp.roll(vs, roll, axis=1)
            nk = jax.lax.dynamic_update_slice(kv_cache["k"], ks, (0, 0, 0, 0))
            nv = jax.lax.dynamic_update_slice(kv_cache["v"], vs, (0, 0, 0, 0))
            new_cache = {"k": nk, "v": nv}
        qh = q.reshape(B, S, KV, rep, hd)
        if attn_mode == "seq":
            qh = shard.constrain(qh, "dp", "sp", None, None, None)
        o = _sdpa(cfg, qh, k, v, positions, window, shard, attn_mode)
        o = o.reshape(B, S, H, hd)

    y = jnp.einsum("bshk,hkd->bsd", o, p["wo"].astype(x.dtype))
    y = shard.constrain(y, "dp" if B > 1 else None, None, None)
    return y, new_cache


def attention_params(cfg, key) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s = D ** -0.5
    return {
        "wq": jax.random.normal(k1, (D, H, hd), jnp.float32) * s,
        "wk": jax.random.normal(k2, (D, KV, hd), jnp.float32) * s,
        "wv": jax.random.normal(k3, (D, KV, hd), jnp.float32) * s,
        "wo": jax.random.normal(k4, (H, hd, D), jnp.float32) * ((H * hd) ** -0.5),
    }


ATTN_SPECS = {
    "wq": (None, "tp", None), "wk": (None, None, None),
    "wv": (None, None, None), "wo": ("tp", None, None),
}


# ---------------------------------------------------------------------------
# dense gated MLP
# ---------------------------------------------------------------------------

def mlp(cfg, p: dict, x: jax.Array, shard) -> jax.Array:
    act = _act(cfg.act)
    h = act(jnp.einsum("bsd,df->bsf", x, p["w1"].astype(x.dtype)))
    g = jnp.einsum("bsd,df->bsf", x, p["w3"].astype(x.dtype))
    h = shard.constrain(h * g, "dp" if x.shape[0] > 1 else None, None, "tp")
    return jnp.einsum("bsf,fd->bsd", h, p["w2"].astype(x.dtype))


def mlp_params(cfg, key) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w1": jax.random.normal(k1, (D, F), jnp.float32) * D ** -0.5,
        "w3": jax.random.normal(k2, (D, F), jnp.float32) * D ** -0.5,
        "w2": jax.random.normal(k3, (F, D), jnp.float32) * F ** -0.5,
    }


MLP_SPECS = {"w1": (None, "tp"), "w3": (None, "tp"), "w2": ("tp", None)}


# ---------------------------------------------------------------------------
# MoE: dense all-expert baseline + ragged (sorted group-GEMM) dispatch
# ---------------------------------------------------------------------------

def moe_router(p: dict, x2d: jax.Array, top_k: int):
    """Returns (gates [T,E] with zeros off the top-k, topk idx [T,k])."""
    logits = jnp.einsum("td,de->te", x2d, p["router"].astype(x2d.dtype))
    topv, topi = jax.lax.top_k(logits, top_k)
    topw = jax.nn.softmax(topv.astype(jnp.float32), axis=-1).astype(x2d.dtype)
    gates = jnp.zeros_like(logits).at[
        jnp.arange(x2d.shape[0])[:, None], topi].set(topw)
    return gates, topi, topw


def moe_dense(cfg, p: dict, x: jax.Array, shard) -> jax.Array:
    """Baseline: every token through every expert, gate-weighted combine.

    Shardable (experts on tp) and simple, but spends E/k x the active FLOPs —
    visible in the roofline MODEL_FLOPS/HLO_FLOPs ratio; the ragged variant
    below removes the waste (hillclimb #1).
    """
    moe = cfg.moe
    act = _act(cfg.act)
    B, S, D = x.shape
    x2 = x.reshape(B * S, D)
    gates, _, _ = moe_router(p, x2, moe.top_k)              # [T, E]
    h = act(jnp.einsum("td,edf->tef", x2, p["w1"].astype(x.dtype)))
    g = jnp.einsum("td,edf->tef", x2, p["w3"].astype(x.dtype))
    hg = h * g * gates[:, :, None]                          # [T, E, F]
    hg = shard.constrain(hg, "dp", "tp", None)    # tokens stay dp-sharded
    y = jnp.einsum("tef,efd->td", hg, p["w2"].astype(x.dtype))
    return y.reshape(B, S, D)


def moe_ragged(cfg, p: dict, x: jax.Array, shard) -> jax.Array:
    """Sorted dropless dispatch: tokens sorted by expert, one grouped GEMM
    per (w1/w3/w2) via jax.lax.ragged_dot, unsorted combine. Computes only
    top_k expert-passes per token (E/k x fewer FLOPs than moe_dense)."""
    moe = cfg.moe
    act = _act(cfg.act)
    B, S, D = x.shape
    T = B * S
    x2 = x.reshape(T, D)
    _, topi, topw = moe_router(p, x2, moe.top_k)            # [T,k]
    flat_e = topi.reshape(-1)                               # [T*k]
    order = jnp.argsort(flat_e)
    tok_of = order // moe.top_k
    xs = jnp.take(x2, tok_of, axis=0)                       # [T*k, D] sorted
    group_sizes = jnp.bincount(flat_e, length=moe.n_experts)
    h = act(jax.lax.ragged_dot(xs, p["w1"].astype(x.dtype), group_sizes))
    g = jax.lax.ragged_dot(xs, p["w3"].astype(x.dtype), group_sizes)
    y = jax.lax.ragged_dot(h * g, p["w2"].astype(x.dtype), group_sizes)
    w = jnp.take(topw.reshape(-1), order)[:, None].astype(x.dtype)
    out = jnp.zeros((T, D), x.dtype).at[tok_of].add(y * w)
    return out.reshape(B, S, D)


def moe_ragged_ep(cfg, p: dict, x: jax.Array, shard) -> jax.Array:
    """Expert-parallel ragged dispatch (the MoE hillclimb, §Perf).

    Inside shard_map over (dp x tp): each device routes its LOCAL tokens,
    keeps only the (token, expert) assignments owned by its tp shard
    (experts are tp-sharded), compacts them to a fixed capacity, runs ONE
    grouped GEMM per projection via jax.lax.ragged_dot over local experts,
    scatters back, and psums partial outputs over tp. Per-device FLOPs =
    ideal top-k/E fraction (vs the dense baseline's all-experts), and the
    only collective is the [T_loc, D] output psum — no token all-to-all,
    no expert-weight gather.
    Capacity = 1.25x the expected local assignment count; overflow drops
    (standard GShard-style capacity semantics).
    """
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    moe = cfg.moe
    act = _act(cfg.act)
    B, S, D = x.shape
    mesh = shard.mesh
    dp_axes = shard.rules["dp"]
    tp_axes = shard.rules["tp"]
    tp_ax = tp_axes[0] if isinstance(tp_axes, tuple) else tp_axes
    tp_size = shard.axis_size("tp")
    dp_size = shard.axis_size("dp")
    assert moe.n_experts % max(tp_size, 1) == 0
    e_loc = moe.n_experts // max(tp_size, 1)
    t_loc = (B // max(dp_size, 1)) * S
    cap = max(8, int(np.ceil(t_loc * moe.top_k * e_loc / moe.n_experts
                             * 1.25 / 8.0)) * 8)

    def body(xb, router, w1, w3, w2):
        Bb, Ss, Dd = xb.shape
        T = Bb * Ss
        x2 = xb.reshape(T, Dd)
        logits = jnp.einsum("td,de->te", x2, router.astype(x2.dtype))
        topv, topi = jax.lax.top_k(logits, moe.top_k)
        topw = jax.nn.softmax(topv.astype(jnp.float32),
                              axis=-1).astype(x2.dtype)
        my = jax.lax.axis_index(tp_ax)
        flat_e = topi.reshape(-1)
        local = (flat_e // e_loc) == my
        le = jnp.where(local, flat_e % e_loc, e_loc)     # e_loc = overflow
        order = jnp.argsort(le)[:cap]
        le_sel = jnp.take(le, order)
        valid = le_sel < e_loc
        tok = order // moe.top_k
        xs = jnp.take(x2, tok, axis=0) * valid[:, None].astype(x2.dtype)
        gs = jnp.bincount(jnp.where(valid, le_sel, 0), weights=valid.astype(
            jnp.float32), length=e_loc).astype(jnp.int32)
        # park capacity-padding rows in the last group (zeroed xs, weight 0)
        gs = gs.at[-1].add(cap - jnp.sum(gs))
        h = act(jax.lax.ragged_dot(xs, w1.astype(xs.dtype), gs))
        g = jax.lax.ragged_dot(xs, w3.astype(xs.dtype), gs)
        y = jax.lax.ragged_dot(h * g, w2.astype(xs.dtype), gs)
        w = jnp.take(topw.reshape(-1), order) * valid.astype(x2.dtype)
        out = jnp.zeros((T, Dd), x2.dtype).at[tok].add(y * w[:, None])
        out = jax.lax.psum(out, tp_ax)
        return out.reshape(Bb, Ss, Dd)

    if mesh is None:
        return moe_ragged(cfg, p, x, shard)
    return shard_map(
        body, mesh=mesh,
        in_specs=(P(dp_axes, None, None), P(None, None),
                  P(tp_ax, None, None), P(tp_ax, None, None),
                  P(tp_ax, None, None)),
        out_specs=P(dp_axes, None, None), check_vma=False,
    )(x, p["router"], p["w1"], p["w3"], p["w2"])


def moe_params(cfg, key) -> dict:
    moe = cfg.moe
    D, F, E = cfg.d_model, moe.d_ff, moe.n_experts
    k0, k1, k2, k3 = jax.random.split(key, 4)
    return {
        "router": jax.random.normal(k0, (D, E), jnp.float32) * D ** -0.5,
        "w1": jax.random.normal(k1, (E, D, F), jnp.float32) * D ** -0.5,
        "w3": jax.random.normal(k2, (E, D, F), jnp.float32) * D ** -0.5,
        "w2": jax.random.normal(k3, (E, F, D), jnp.float32) * F ** -0.5,
    }


MOE_SPECS = {"router": (None, None), "w1": ("tp", None, None),
             "w3": ("tp", None, None), "w2": ("tp", None, None)}


def ffn(cfg, p: dict, x: jax.Array, shard) -> jax.Array:
    if cfg.moe is None:
        return mlp(cfg, p, x, shard)
    if cfg.moe.impl == "ragged_ep":
        return moe_ragged_ep(cfg, p, x, shard)
    if cfg.moe.impl == "ragged":
        return moe_ragged(cfg, p, x, shard)
    return moe_dense(cfg, p, x, shard)


def ffn_params(cfg, key) -> dict:
    return moe_params(cfg, key) if cfg.moe is not None else mlp_params(cfg, key)


def ffn_specs(cfg) -> dict:
    return dict(MOE_SPECS) if cfg.moe is not None else dict(MLP_SPECS)
