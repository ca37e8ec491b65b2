"""Per-architecture smoke tests: REDUCED config of the same family, one
forward/train step on CPU, asserting output shapes + no NaNs (the FULL
configs are exercised only via the dry-run)."""
import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.distributed.sharding import ShardingPolicy

SHARD = ShardingPolicy(None)


def _finite(x):
    return bool(jnp.all(jnp.isfinite(x)))


# ---------------------------------------------------------------------------
# LM family
# ---------------------------------------------------------------------------

def reduced_lm(arch, **over):
    cfg = get_config(arch)
    kv = 2 if cfg.n_kv_heads < cfg.n_heads else 4
    kw = dict(n_layers=3, d_model=64, n_heads=4, n_kv_heads=kv, head_dim=16,
              d_ff=128, vocab_size=128, loss_chunks=2, dtype="float32",
              attn_pattern=tuple(min(w, 8) if w else 0
                                 for w in cfg.attn_pattern))
    if cfg.moe is not None:
        kw["moe"] = dataclasses.replace(cfg.moe, n_experts=4, top_k=2,
                                        d_ff=32)
    kw.update(over)
    return dataclasses.replace(cfg, **kw)


LM_ARCHS = ["gemma2-9b", "gemma3-4b", "minicpm-2b", "granite-moe-1b-a400m",
            "olmoe-1b-7b"]


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_train_step(rng, arch):
    from repro.models import transformer as T
    from repro.training import optimizer as OPT
    from repro.training.train_loop import make_train_step
    cfg = reduced_lm(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    labels = OPT.default_labels(params)
    opt = OPT.init_opt_state(params, labels)
    step = make_train_step(lambda p, b: T.loss_fn(cfg, p, b, SHARD),
                           OPT.OptConfig(warmup=2, total_steps=10),
                           labels=labels, donate=False)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    p1, o1, m1 = step(params, opt, batch)
    p2, o2, m2 = step(p1, o1, batch)
    assert _finite(m1["loss"]) and _finite(m2["loss"])
    assert float(m2["loss"]) < float(m1["loss"])     # same batch: must drop


@pytest.mark.parametrize("arch", LM_ARCHS)
def test_lm_decode_matches_forward(rng, arch):
    from repro.models import transformer as T
    cfg = reduced_lm(arch)
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    logits_p, caches = T.prefill_step(cfg, params, {"tokens": tokens}, SHARD,
                                      decode_budget=4)
    nxt = jnp.full((2, 1), 5, jnp.int32)
    logits_d, _ = T.decode_step(cfg, params, caches, nxt, jnp.int32(12),
                                SHARD)
    full = T.forward(cfg, params, jnp.concatenate([tokens, nxt], 1), SHARD)
    ref = T._logits(cfg, params, full[:, -1:])
    np.testing.assert_allclose(np.asarray(logits_d), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    assert logits_d.shape == (2, 1, T.padded_vocab(cfg))


def test_moe_ragged_matches_dense(rng):
    from repro.models import transformer as T
    cfg = reduced_lm("olmoe-1b-7b")
    cfg_r = dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, impl="ragged"))
    params = T.init_params(cfg, jax.random.PRNGKey(2))
    tokens = jnp.asarray(rng.integers(0, 128, (2, 16)), jnp.int32)
    b = {"tokens": tokens, "labels": jnp.roll(tokens, -1, 1)}
    l_dense = T.loss_fn(cfg, params, b, SHARD)
    l_ragged = T.loss_fn(cfg_r, params, b, SHARD)
    np.testing.assert_allclose(float(l_dense), float(l_ragged), rtol=1e-3)


# ---------------------------------------------------------------------------
# GNN
# ---------------------------------------------------------------------------

def reduced_gnn(**over):
    cfg = get_config("equiformer-v2")
    kw = dict(n_layers=2, d_hidden=16, l_max=3, m_max=2, n_heads=4,
              d_edge_rbf=8, remat=False)
    kw.update(over)
    return dataclasses.replace(cfg, **kw)


def test_equiformer_train_step(rng):
    from repro.models.gnn import equiformer_v2 as E
    from repro.models.gnn.graph import LocalEdges
    from repro.training import optimizer as OPT
    from repro.training.train_loop import make_train_step
    cfg = reduced_gnn()
    N, Eg, F = 24, 80, 10
    params = E.init_params(cfg, jax.random.PRNGKey(0), F, 5)
    plan = LocalEdges(jnp.asarray(rng.integers(0, N, Eg), jnp.int32),
                      jnp.asarray(rng.integers(0, N, Eg), jnp.int32),
                      jnp.ones(Eg, bool), N)
    feat = jnp.asarray(rng.normal(size=(N, F)), jnp.float32)
    pos = jnp.asarray(rng.normal(size=(N, 3)) * 2, jnp.float32)
    lab = jnp.asarray(rng.integers(0, 5, N), jnp.int32)

    def loss(p, b):
        return E.node_ce_loss(cfg, p, plan, b["feat"], b["pos"], b["labels"],
                              b["lmask"])
    labels = OPT.default_labels(params)
    opt = OPT.init_opt_state(params, labels)
    step = make_train_step(loss, OPT.OptConfig(lr=1e-3, warmup=1,
                                               total_steps=10),
                           labels=labels, donate=False)
    batch = {"feat": feat, "pos": pos, "labels": lab,
             "lmask": jnp.ones(N, bool)}
    p1, o1, m1 = step(params, opt, batch)
    p2, o2, m2 = step(p1, o1, batch)
    assert _finite(m1["loss"]) and float(m2["loss"]) < float(m1["loss"])


def test_equiformer_invariance(rng):
    """Node outputs (l=0 scalars) are invariant to global rotations."""
    from conftest import rand_rotation
    from repro.models.gnn import equiformer_v2 as E
    from repro.models.gnn.graph import LocalEdges
    cfg = reduced_gnn()
    N, Eg, F = 20, 60, 12
    params = E.init_params(cfg, jax.random.PRNGKey(0), F, 5)
    feat = jnp.asarray(rng.normal(size=(N, F)), jnp.float32)
    pos = jnp.asarray(rng.normal(size=(N, 3)) * 2, jnp.float32)
    plan = LocalEdges(jnp.asarray(rng.integers(0, N, Eg), jnp.int32),
                      jnp.asarray(rng.integers(0, N, Eg), jnp.int32),
                      jnp.ones(Eg, bool), N)
    out = E.forward(cfg, params, plan, feat, pos)
    R = jnp.asarray(rand_rotation(rng), jnp.float32)
    out_r = E.forward(cfg, params, plan, feat, pos @ R.T)
    np.testing.assert_allclose(np.asarray(out), np.asarray(out_r),
                               rtol=1e-3, atol=1e-4)


def test_sharded_edges_match_local(rng):
    """Vertex-cut bucketed plan == plain COO plan on a 1-device 'mesh'."""
    from repro.models.gnn import equiformer_v2 as E
    from repro.models.gnn.graph import (LocalEdges, ShardedEdges,
                                        partition_edges)
    cfg = reduced_gnn()
    N, Eg, F = 16, 60, 8
    src = rng.integers(0, N, Eg).astype(np.int64)
    dst = rng.integers(0, N, Eg).astype(np.int64)
    params = E.init_params(cfg, jax.random.PRNGKey(0), F, 4)
    feat = jnp.asarray(rng.normal(size=(N, F)), jnp.float32)
    pos = jnp.asarray(rng.normal(size=(N, 3)) * 2, jnp.float32)
    local = LocalEdges(jnp.asarray(src, jnp.int32), jnp.asarray(dst, jnp.int32),
                       jnp.ones(Eg, bool), N)
    out_local = E.forward(cfg, params, local, feat, pos)

    # single-shard ShardedEdges: exchange is identity over a 1-device axis
    from jax.sharding import PartitionSpec as P
    from jax import shard_map
    from repro.launch.mesh import make_mesh
    parts = partition_edges(src, dst, N, 1)
    mesh = make_mesh((1,), ("x",))

    def run(feat, pos):
        def body(feat, pos):
            plan = ShardedEdges(
                esrc=jnp.asarray(parts["esrc"][0]),
                edstg=jnp.asarray(parts["edstg"][0]),
                emask=jnp.asarray(parts["emask"][0]),
                rdst=jnp.asarray(parts["rdst"][0]),
                rsrcg=jnp.asarray(parts["rsrcg"][0]),
                rmask=jnp.asarray(parts["rmask"][0]),
                n_local=N, shard_offset=jnp.int32(0), axis_names=("x",))
            return E.forward(cfg, params, plan, feat, pos)
        return shard_map(body, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)(feat, pos)

    out_sharded = run(feat, pos)
    np.testing.assert_allclose(np.asarray(out_local),
                               np.asarray(out_sharded), rtol=2e-4, atol=2e-4)


def test_neighbor_sampler(rng):
    from repro.models.gnn.sampler import (CSRGraph, random_graph,
                                          sample_subgraph)
    src, dst = random_graph(500, 8, rng)
    g = CSRGraph.from_coo(src, dst, 500)
    seeds = rng.choice(500, 32, replace=False)
    sub = sample_subgraph(g, seeds, (5, 3), rng)
    n = int(sub["node_mask"].sum())
    e = int(sub["edge_mask"].sum())
    assert n >= 32 and e > 0
    # fanout bound: each seed <=5 edges hop1; each hop1 node <=3 hop2
    assert e <= 32 * 5 + 32 * 5 * 3
    # all edges reference in-subgraph local ids
    assert sub["src"][:e].max() < n and sub["dst"][:e].max() < n
    # seeds occupy the first positions
    np.testing.assert_array_equal(sub["nodes"][:32], seeds)
    # edges exist in the original graph (u -> v means u in N(v))
    nodes = sub["nodes"]
    for k in range(min(e, 50)):
        u, v = nodes[sub["src"][k]], nodes[sub["dst"][k]]
        assert u in g.neighbors(v)


# ---------------------------------------------------------------------------
# RecSys
# ---------------------------------------------------------------------------

RECSYS = ["dcn-v2", "autoint", "dlrm-mlperf"]


def reduced_recsys(arch):
    cfg = get_config(arch)
    over = dict(vocab_sizes=tuple([50] * len(cfg.vocab_sizes)))
    if arch == "dcn-v2":
        over["mlp"] = (64, 32)
    if arch == "dlrm-mlperf":
        over.update(bot_mlp=(32, 16, 8), top_mlp=(64, 32, 1), embed_dim=8)
    return dataclasses.replace(cfg, **over)


@pytest.mark.parametrize("arch", RECSYS)
def test_recsys_train_step(rng, arch):
    from repro.models.recsys import nets as R
    from repro.training import optimizer as OPT
    from repro.training.train_loop import make_train_step
    cfg = reduced_recsys(arch)
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    batch = {"sparse": jnp.asarray(rng.integers(0, 50, (16, cfg.n_sparse)),
                                   jnp.int32),
             "labels": jnp.asarray(rng.integers(0, 2, 16), jnp.float32)}
    if cfg.n_dense:
        batch["dense"] = jnp.asarray(rng.normal(size=(16, cfg.n_dense)),
                                     jnp.float32)
    labels = OPT.default_labels(params)
    opt = OPT.init_opt_state(params, labels)
    step = make_train_step(lambda p, b: R.loss_fn(cfg, p, b, SHARD),
                           OPT.OptConfig(lr=1e-2, warmup=1, total_steps=20),
                           labels=labels, donate=False)
    p, o, m = step(params, opt, batch)
    for _ in range(4):
        p, o, m2 = step(p, o, batch)
    assert _finite(m["loss"]) and float(m2["loss"]) < float(m["loss"])


def test_bert4rec_train_and_retrieval(rng):
    from repro.models.recsys import nets as R
    cfg = dataclasses.replace(get_config("bert4rec"), n_items=300,
                              seq_len=12, embed_dim=16)
    params = R.init_params(cfg, jax.random.PRNGKey(0))
    seq = jnp.asarray(rng.integers(0, 300, (4, 12)), jnp.int32)
    b = {"seq": seq, "seq_mask": jnp.ones((4, 12), bool),
         "mlm_positions": jnp.asarray(rng.integers(0, 12, (4, 3)), jnp.int32),
         "mlm_labels": jnp.asarray(rng.integers(0, 300, (4, 3)), jnp.int32),
         "mlm_mask": jnp.ones((4, 3), bool),
         "neg_samples": jnp.asarray(rng.integers(0, 300, 64), jnp.int32)}
    loss = R.bert4rec_mlm_loss(cfg, params, b, SHARD)
    assert _finite(loss)
    cand = jnp.arange(300, dtype=jnp.int32)
    rb = {"seq": seq[:1], "seq_mask": jnp.ones((1, 12), bool),
          "candidates": cand}
    s1, i1 = R.retrieval_step(cfg, params, rb, SHARD, stages=1, top_k=10)
    s2, i2 = R.retrieval_step(cfg, params, rb, SHARD, stages=2,
                              prefetch_k=300, top_k=10)
    # prefetch == N: 2-stage must equal exact 1-stage
    np.testing.assert_array_equal(np.asarray(i1), np.asarray(i2))


def test_sharded_embedding_lookup_matches(rng):
    """lookup (XLA-partitioned) == lookup_shardmap (explicit) == local."""
    import jax as _jax
    from repro.models.recsys import embedding as EMB
    layout = EMB.EmbeddingLayout((120_000, 50, 200_000), 8,
                                 row_shard_threshold=100_000)
    params = EMB.init_embedding(layout, jax.random.PRNGKey(0), n_shards=1)
    idx = jnp.asarray(
        np.stack([rng.integers(0, 120_000, 32), rng.integers(0, 50, 32),
                  rng.integers(0, 200_000, 32)], 1), jnp.int32)
    out = EMB.lookup(layout, params, idx)
    rows_b = np.asarray(params["big"])
    offs, _ = layout.offsets(layout.big_fields)
    exp0 = rows_b[np.asarray(idx[:, 0]) + offs[0]]
    np.testing.assert_allclose(np.asarray(out[:, 0]), exp0, rtol=1e-6)


# ---------------------------------------------------------------------------
# Retriever (paper's own encoders)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["colpali", "colsmol", "colqwen"])
def test_retriever_encode_and_contrastive(rng, arch):
    import dataclasses as dc
    from repro.models import late_interaction as LI
    cfg = dc.replace(get_config(arch), d_model=64, n_layers=2, n_heads=4,
                     d_ff=128, grid_h=8, grid_w=8, n_tiles=3, tile_patches=16,
                     max_rows=8, query_vocab=128)
    params = LI.init_params(cfg, jax.random.PRNGKey(0))
    B = 4
    n_raw = cfg.n_patches * (4 if cfg.geometry == "dynamic" else 1)
    batch = {"patches": jnp.asarray(rng.normal(size=(B, n_raw, LI.D_PATCH)),
                                    jnp.float32),
             "query_tokens": jnp.asarray(rng.integers(0, 128, (B, 8)),
                                         jnp.int32),
             "query_mask": jnp.ones((B, 8), bool)}
    vecs, types = LI.encode_pages(cfg, params, batch["patches"], SHARD)
    assert vecs.shape == (B, cfg.seq_len, cfg.out_dim)
    nrm = jnp.linalg.norm(vecs, axis=-1)
    np.testing.assert_allclose(np.asarray(nrm), 1.0, rtol=1e-4)
    loss = LI.contrastive_loss(cfg, params, batch, SHARD)
    assert _finite(loss)
    g = jax.grad(lambda p: LI.contrastive_loss(cfg, p, batch, SHARD))(params)
    assert all(_finite(x) for x in jax.tree.leaves(g))
