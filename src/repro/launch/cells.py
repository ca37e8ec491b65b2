"""Dry-run cell construction: (arch x shape x mesh) -> lowerable program.

For every assigned cell this module builds:
  - the step callable (train_step / prefill_step / decode_step / serve_step /
    retrieval_step / search_step) exactly as production would run it,
  - ShapeDtypeStruct stand-ins for every input (weak-type-correct, no
    allocation),
  - NamedShardings for every input resolved from logical axes,
  - a MODEL_FLOPS estimate (6*N*D dense / 6*N_active*D MoE; family-specific
    otherwise) for the §Roofline useful-compute ratio.

``build_cell(arch, shape_name, mesh)`` returns a Cell; launch/dryrun.py
lowers and compiles it.
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass, field
from functools import partial

import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P
from jax import shard_map

from repro.configs import get_config, get_shapes
from repro.distributed.sharding import ShardingPolicy
from repro.launch.mesh import n_devices
from repro.training import optimizer as OPT
from repro.training.train_loop import make_train_step


@dataclass
class Cell:
    arch: str
    shape: str
    fn: object                     # callable to jit
    args: tuple                    # ShapeDtypeStruct pytrees
    in_shardings: tuple            # NamedShardings (or None per-arg)
    donate: tuple = ()
    model_flops: float = 0.0       # useful FLOPs per step (fwd+bwd for train)
    note: str = ""


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(tuple(int(s) for s in shape), dtype)


def _eval_shape(fn):
    return jax.eval_shape(fn)


def _shardings_from_specs(shard: ShardingPolicy, spec_tree):
    return jax.tree.map(lambda axes: shard.named(*axes), spec_tree,
                        is_leaf=lambda x: isinstance(x, tuple))


def _replicated_like(shard: ShardingPolicy, tree):
    return jax.tree.map(lambda _: shard.named(), tree)


# ===========================================================================
# LM family
# ===========================================================================

def _lm_batch_flops(cfg, tokens: int, train: bool) -> float:
    per_tok = 6.0 * cfg.n_active_params()
    return per_tok * tokens * (1.0 if train else 1.0 / 3.0)


def _lm_opt_specs(shard, pspecs, labels):
    return OPT.opt_state_specs(pspecs, labels)


def build_lm_cell(arch: str, shape, mesh, variant: str = "base") -> Cell:
    from repro.models import transformer as T
    from repro.models import kv_cache as KV

    cfg = get_config(arch)
    micro = 1
    if variant == "opt":
        if cfg.moe is not None:
            cfg = dataclasses.replace(
                cfg, moe=dataclasses.replace(cfg.moe, impl="ragged_ep"))
        # L2/L3 (§Perf): drop the SP residual constraint (measured to cause
        # op-by-op resharding storms) and microbatch the step instead
        cfg = dataclasses.replace(cfg, sp_activations=False)
        micro = 8
    shard = ShardingPolicy(mesh)
    pol = shard
    params_sds = _eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    pspecs = T.param_specs(cfg, pol.axis_size("tp"), pol.axis_size("dp"))
    pshard = _shardings_from_specs(pol, pspecs)

    if shape.kind == "train":
        B, S = shape.global_batch, shape.seq_len
        labels = OPT.default_labels(params_sds)
        opt_sds = jax.eval_shape(lambda p: OPT.init_opt_state(p, labels),
                                 params_sds)
        ospecs = OPT.opt_state_specs(pspecs, labels)
        oshard = _shardings_from_specs(pol, ospecs)
        oc = OPT.OptConfig(schedule="wsd" if "minicpm" in arch else "cosine")

        def loss(p, b):
            if micro <= 1:
                return T.loss_fn(cfg, p, b, pol)
            # gradient accumulation: scan over microbatches; remat bounds
            # live activations to one microbatch
            tk = b["tokens"].reshape(micro, B // micro, S)
            lb = b["labels"].reshape(micro, B // micro, S)

            def body(c, tb):
                return c + T.loss_fn(cfg, p, {"tokens": tb[0],
                                              "labels": tb[1]}, pol), None
            tot, _ = jax.lax.scan(jax.checkpoint(body),
                                  jnp.zeros((), jnp.float32), (tk, lb))
            return tot / micro

        step = make_train_step(loss, oc, labels=labels, jit=False)
        batch_sds = {"tokens": _sds((B, S), jnp.int32),
                     "labels": _sds((B, S), jnp.int32)}
        bshard = {"tokens": pol.named("dp", None),
                  "labels": pol.named("dp", None)}
        return Cell(arch, shape.name, step,
                    (params_sds, opt_sds, batch_sds),
                    (pshard, oshard, bshard), donate=(0, 1),
                    model_flops=_lm_batch_flops(cfg, B * S, True))

    if shape.kind == "prefill":
        B, S = shape.global_batch, shape.seq_len
        fn = lambda p, b: T.prefill_step(cfg, p, b, pol)
        batch_sds = {"tokens": _sds((B, S), jnp.int32)}
        bshard = {"tokens": pol.named("dp", None)}
        return Cell(arch, shape.name, fn, (params_sds, batch_sds),
                    (pshard, bshard),
                    model_flops=_lm_batch_flops(cfg, B * S, False))

    # decode (decode_32k / long_500k): one token against a seq_len KV cache
    B, S = shape.global_batch, shape.seq_len
    plan = T.segment_plan(cfg)
    cache_sds = KV.cache_specs(cfg, plan, B, S, jnp.dtype(cfg.dtype))
    cspecs = KV.cache_logical_axes(cfg, plan, B)
    cshard = _shardings_from_specs(pol, cspecs)
    fn = lambda p, c, t, pos: T.decode_step(cfg, p, c, t, pos, pol)
    tok_sds = _sds((B, 1), jnp.int32)
    pos_sds = _sds((), jnp.int32)
    tshard = pol.named("dp", None) if B > 1 else pol.named(None, None)
    # decode useful FLOPs: params touched once per token (2*N_active*B)
    flops = 2.0 * cfg.n_active_params() * B
    return Cell(arch, shape.name, fn,
                (params_sds, cache_sds, tok_sds, pos_sds),
                (pshard, cshard, tshard, pol.named()), donate=(1,),
                model_flops=flops)


# ===========================================================================
# GNN family
# ===========================================================================

def _gnn_layer_flops(cfg, n_edges: float) -> float:
    """Per-edge eSCN cost: 3 SO(2) convs + 2 rotation applies."""
    C = cfg.d_hidden
    n0 = cfg.l_max + 1
    conv = (n0 * C) ** 2 * 2
    for m in range(1, cfg.m_max + 1):
        conv += 4 * ((n0 - m) * C) ** 2 * 2
    rot = sum((2 * l + 1) ** 2 for l in range(n0)) * C * 2 * 2
    return n_edges * (3 * conv + rot)


def _gnn_flops(cfg, n_edges: float, train: bool) -> float:
    f = cfg.n_layers * _gnn_layer_flops(cfg, n_edges)
    return f * (3.0 if train else 1.0)


def build_gnn_cell(arch: str, shape, mesh, variant: str = "base") -> Cell:
    from repro.models.gnn import equiformer_v2 as E
    from repro.models.gnn.graph import LocalEdges, ShardedEdges

    base = get_config(arch)
    cfg = dataclasses.replace(base, msg_dtype="bfloat16",
                              fused_rotation=(variant == "opt"))
    pol = ShardingPolicy(mesh)
    ndev = n_devices(mesh) if mesh is not None else 1
    dp = pol.axis_size("dp")
    flat_axes = tuple(mesh.axis_names) if mesh is not None else ()

    oc = OPT.OptConfig()

    if shape.kind == "batched_graphs":          # molecule
        G, NN, EE, F = shape.batch, shape.n_nodes, shape.n_edges, shape.d_feat
        params_sds = _eval_shape(
            lambda: E.init_params(cfg, jax.random.PRNGKey(0), F, 1))
        pshard = _replicated_like(pol, params_sds)

        def loss(p, b):
            def one(feat, pos, src, dst, emask, target):
                plan = LocalEdges(src, dst, emask, NN)
                return E.graph_energy_loss(cfg, p, plan, feat, pos, target)
            return jnp.mean(jax.vmap(one)(b["feat"], b["pos"], b["src"],
                                          b["dst"], b["emask"], b["target"]))

        labels = OPT.default_labels(params_sds)
        opt_sds = jax.eval_shape(lambda p: OPT.init_opt_state(p, labels),
                                 params_sds)
        step = make_train_step(loss, oc, labels=labels, jit=False)
        batch_sds = {"feat": _sds((G, NN, F), jnp.float32),
                     "pos": _sds((G, NN, 3), jnp.float32),
                     "src": _sds((G, EE), jnp.int32),
                     "dst": _sds((G, EE), jnp.int32),
                     "emask": _sds((G, EE), bool),
                     "target": _sds((G,), jnp.float32)}
        bshard = {k: pol.named("dp", *([None] * (len(v.shape) - 1)))
                  for k, v in batch_sds.items()}
        return Cell(arch, shape.name, step,
                    (params_sds, opt_sds, batch_sds),
                    (pshard, _replicated_like(pol, opt_sds), bshard),
                    donate=(0, 1),
                    model_flops=_gnn_flops(cfg, G * EE, True))

    if shape.kind == "minibatch":
        # one sampled subgraph per data shard; EACH subgraph is vertex-cut
        # sharded over the model axis (169k-node padded 2-hop neighbourhoods
        # are too large per-device otherwise). Two-level: dp x tp.
        from repro.models.gnn.sampler import max_subgraph_shape
        NN, EE = max_subgraph_shape(shape.batch_nodes, tuple(shape.fanout))
        F, G = shape.d_feat, dp
        n_cls = 41
        tp_size = max(pol.axis_size("tp"), 1)
        tp_axes = ("model",) if mesh is not None else ()
        n_local = -(-NN // max(tp_size, 1))
        N_pad = n_local * tp_size
        cap = max(8, int(np.ceil(EE / (tp_size * tp_size) * 2.0 / 8)) * 8)
        dp_axes = pol.rules["dp"]

        params_sds = _eval_shape(
            lambda: E.init_params(cfg, jax.random.PRNGKey(0), F, n_cls))
        pshard = _replicated_like(pol, params_sds)

        def loss(p, b):
            def body(feat, pos, labels_, lmask, esrc, edstg, emask, rdst,
                     rsrcg, rmask):
                # leading dims [G_loc=1, tp_loc=1] from the two shardings
                idx = jax.lax.axis_index(tp_axes)
                plan = ShardedEdges(
                    esrc=esrc[0, 0], edstg=edstg[0, 0], emask=emask[0, 0],
                    rdst=rdst[0, 0], rsrcg=rsrcg[0, 0], rmask=rmask[0, 0],
                    n_local=n_local, shard_offset=idx * n_local,
                    axis_names=tp_axes)
                # feat/labels/lmask block: [1, n_local, ...]; pos: [1, N_pad, 3]
                logits = E.forward(cfg, p, plan, feat[0], pos[0])
                logz = jax.nn.logsumexp(logits, axis=-1)
                gold = jnp.take_along_axis(
                    logits, labels_[0][:, None], axis=-1)[:, 0]
                m = lmask[0].astype(jnp.float32)
                num = jax.lax.psum(jnp.sum((logz - gold) * m),
                                   dp_axes + tp_axes)
                den = jax.lax.psum(jnp.sum(m), dp_axes + tp_axes)
                return num / jnp.maximum(den, 1.0)

            return shard_map(
                body, mesh=mesh,
                in_specs=(P(dp_axes, tp_axes), P(dp_axes),
                          P(dp_axes, tp_axes), P(dp_axes, tp_axes),
                          P(dp_axes), P(dp_axes), P(dp_axes),
                          P(dp_axes), P(dp_axes), P(dp_axes)),
                out_specs=P(), check_vma=False,
            )(b["feat"], b["pos"], b["labels"], b["lmask"], b["esrc"],
              b["edstg"], b["emask"], b["rdst"], b["rsrcg"], b["rmask"])

        labels = OPT.default_labels(params_sds)
        opt_sds = jax.eval_shape(lambda p: OPT.init_opt_state(p, labels),
                                 params_sds)
        step = make_train_step(loss, oc, labels=labels, jit=False)
        batch_sds = {"feat": _sds((G, N_pad, F), jnp.float32),
                     "pos": _sds((G, N_pad, 3), jnp.float32),
                     "labels": _sds((G, N_pad), jnp.int32),
                     "lmask": _sds((G, N_pad), bool),
                     "esrc": _sds((G, tp_size, tp_size, cap), jnp.int32),
                     "edstg": _sds((G, tp_size, tp_size, cap), jnp.int32),
                     "emask": _sds((G, tp_size, tp_size, cap), bool),
                     "rdst": _sds((G, tp_size, tp_size, cap), jnp.int32),
                     "rsrcg": _sds((G, tp_size, tp_size, cap), jnp.int32),
                     "rmask": _sds((G, tp_size, tp_size, cap), bool)}
        def bsh(k, v):
            if k in ("feat", "labels", "lmask"):
                return pol.named("dp", "tp", *([None] * (len(v.shape) - 2)))
            if k == "pos":
                return pol.named("dp", None, None)
            return pol.named("dp", "tp", *([None] * (len(v.shape) - 2)))
        bshard = {k: bsh(k, v) for k, v in batch_sds.items()}
        return Cell(arch, shape.name, step,
                    (params_sds, opt_sds, batch_sds),
                    (pshard, _replicated_like(pol, opt_sds), bshard),
                    donate=(0, 1),
                    model_flops=_gnn_flops(cfg, G * EE, True),
                    note=f"two-level dp={G} x tp={tp_size}, cap={cap}")

    # full_graph: small -> replicated-node pjit; large -> vertex-cut shard_map
    NN, EE, F = shape.n_nodes, shape.n_edges, shape.d_feat
    n_cls = 47
    params_sds = _eval_shape(
        lambda: E.init_params(cfg, jax.random.PRNGKey(0), F, n_cls))
    pshard = _replicated_like(pol, params_sds)
    labels = OPT.default_labels(params_sds)
    opt_sds = jax.eval_shape(lambda p: OPT.init_opt_state(p, labels),
                             params_sds)

    if EE <= 2_000_000:                          # Cora-scale: pjit path
        EE = -(-EE // max(ndev, 1)) * max(ndev, 1)   # pad edges to shard
        def loss(p, b):
            plan = LocalEdges(b["src"], b["dst"], b["emask"], NN)
            return E.node_ce_loss(cfg, p, plan, b["feat"], b["pos"],
                                  b["labels"], b["lmask"])
        step = make_train_step(loss, oc, labels=labels, jit=False)
        batch_sds = {"feat": _sds((NN, F), jnp.float32),
                     "pos": _sds((NN, 3), jnp.float32),
                     "src": _sds((EE,), jnp.int32),
                     "dst": _sds((EE,), jnp.int32),
                     "emask": _sds((EE,), bool),
                     "labels": _sds((NN,), jnp.int32),
                     "lmask": _sds((NN,), bool)}
        bshard = {"feat": pol.named(None, None), "pos": pol.named(None, None),
                  "src": pol.named("flat"), "dst": pol.named("flat"),
                  "emask": pol.named("flat"),
                  "labels": pol.named(None), "lmask": pol.named(None)}
        return Cell(arch, shape.name, step,
                    (params_sds, opt_sds, batch_sds),
                    (pshard, _replicated_like(pol, opt_sds), bshard),
                    donate=(0, 1), model_flops=_gnn_flops(cfg, EE, True))

    # ---- ogbn-products scale: vertex-cut + all_to_all inside shard_map
    S = ndev
    n_local = -(-NN // S)
    N_pad = n_local * S
    cap = max(8, int(np.ceil(EE / (S * S) * 1.25 / 8.0)) * 8)

    def sharded_loss(p, b):
        def body(feat, pos, labels_, lmask, esrc, edstg, emask, rdst,
                 rsrcg, rmask):
            idx = jax.lax.axis_index(flat_axes)
            plan = ShardedEdges(
                esrc=esrc[0], edstg=edstg[0], emask=emask[0],
                rdst=rdst[0], rsrcg=rsrcg[0], rmask=rmask[0],
                n_local=n_local, shard_offset=idx * n_local,
                axis_names=flat_axes)
            logits = E.forward(cfg, p, plan, feat, pos)
            logz = jax.nn.logsumexp(logits, axis=-1)
            gold = jnp.take_along_axis(logits, labels_[:, None], axis=-1)[:, 0]
            m = lmask.astype(jnp.float32)
            num = jax.lax.psum(jnp.sum((logz - gold) * m), flat_axes)
            den = jax.lax.psum(jnp.sum(m), flat_axes)
            return num / jnp.maximum(den, 1.0)

        return shard_map(
            body, mesh=mesh,
            in_specs=(P(flat_axes), P(),           # feat, pos(replicated)
                      P(flat_axes), P(flat_axes),  # labels, lmask
                      P(flat_axes), P(flat_axes), P(flat_axes),
                      P(flat_axes), P(flat_axes), P(flat_axes)),
            out_specs=P(), check_vma=False,
        )(b["feat"], b["pos"], b["labels"], b["lmask"], b["esrc"],
          b["edstg"], b["emask"], b["rdst"], b["rsrcg"], b["rmask"])

    def loss(p, b):
        return sharded_loss(p, b)

    step = make_train_step(loss, oc, labels=labels, jit=False)
    batch_sds = {
        "feat": _sds((N_pad, F), jnp.float32),
        "pos": _sds((N_pad, 3), jnp.float32),
        "labels": _sds((N_pad,), jnp.int32),
        "lmask": _sds((N_pad,), bool),
        "esrc": _sds((S, S, cap), jnp.int32),
        "edstg": _sds((S, S, cap), jnp.int32),
        "emask": _sds((S, S, cap), bool),
        "rdst": _sds((S, S, cap), jnp.int32),
        "rsrcg": _sds((S, S, cap), jnp.int32),
        "rmask": _sds((S, S, cap), bool),
    }
    bshard = {k: (pol.named(None, None) if k == "pos" else
                  pol.named("flat", *([None] * (len(v.shape) - 1))))
              for k, v in batch_sds.items()}
    return Cell(arch, shape.name, step,
                (params_sds, opt_sds, batch_sds),
                (pshard, _replicated_like(pol, opt_sds), bshard),
                donate=(0, 1), model_flops=_gnn_flops(cfg, EE, True),
                note=f"vertex-cut S={S} cap={cap}")


# ===========================================================================
# RecSys family
# ===========================================================================

def _recsys_dense_flops(cfg, batch: float) -> float:
    def mlp_f(dims):
        return sum(2.0 * a * b for a, b in zip(dims[:-1], dims[1:]))
    f = 0.0
    if cfg.name == "dcn-v2":
        d0 = cfg.n_dense + cfg.n_sparse * cfg.embed_dim
        f = cfg.n_cross_layers * 2.0 * d0 * d0 + mlp_f((d0,) + tuple(cfg.mlp))
    elif cfg.name == "autoint":
        F, d, H, da = cfg.n_sparse, cfg.embed_dim, cfg.n_heads, cfg.d_attn
        din = d
        for _ in range(cfg.n_attn_layers):
            f += 2.0 * F * din * H * da * 3 + 2.0 * F * F * H * da * 2 \
                + 2.0 * F * din * H * da
            din = H * da
        f += 2.0 * F * H * da
    elif cfg.name == "dlrm-mlperf":
        f = mlp_f((cfg.n_dense,) + tuple(cfg.bot_mlp))
        n_vec = cfg.n_sparse + 1
        f += 2.0 * n_vec * n_vec * cfg.embed_dim
        n_int = n_vec * (n_vec - 1) // 2
        f += mlp_f((n_int + cfg.embed_dim,) + tuple(cfg.top_mlp))
    elif cfg.name == "bert4rec":
        d, S_ = cfg.embed_dim, cfg.seq_len
        per_blk = 2.0 * S_ * d * d * 4 + 2.0 * S_ * S_ * d * 2 \
            + 2.0 * S_ * d * 8 * d
        f = cfg.n_blocks * per_blk
    return f * batch


def build_recsys_cell(arch: str, shape, mesh, variant: str = "base") -> Cell:
    from repro.models.recsys import nets as R

    cfg = get_config(arch)
    pol = ShardingPolicy(mesh)
    ndev = n_devices(mesh) if mesh is not None else 1
    tp = pol.axis_size("tp")
    params_sds = _eval_shape(
        lambda: R.init_params(cfg, jax.random.PRNGKey(0), n_shards=tp))

    def pshard_tree():
        def spec_of(path, leaf):
            keys = [getattr(k, "key", None) for k in path]
            if "big" in keys or (cfg.name == "bert4rec" and "items" in keys):
                return pol.named("tp", *([None] * (len(leaf.shape) - 1)))
            return pol.named()
        flat, treedef = jax.tree_util.tree_flatten_with_path(params_sds)
        return jax.tree_util.tree_unflatten(
            treedef, [spec_of(p, l) for p, l in flat])
    pshard = pshard_tree()

    def oshard_tree(opt_sds):
        def spec_of(path, leaf):
            keys = [getattr(k, "key", None) for k in path]
            if "big" in keys or (cfg.name == "bert4rec" and "items" in keys):
                return pol.named("tp", *([None] * (len(leaf.shape) - 1)))
            return pol.named()
        flat, treedef = jax.tree_util.tree_flatten_with_path(opt_sds)
        return jax.tree_util.tree_unflatten(
            treedef, [spec_of(p, l) for p, l in flat])

    def batch_for(B):
        if cfg.name == "bert4rec":
            M, K = 40, 256
            b = {"seq": _sds((B, cfg.seq_len), jnp.int32),
                 "seq_mask": _sds((B, cfg.seq_len), bool),
                 "mlm_positions": _sds((B, M), jnp.int32),
                 "mlm_labels": _sds((B, M), jnp.int32),
                 "mlm_mask": _sds((B, M), bool),
                 "neg_samples": _sds((K,), jnp.int32)}
            sh = {k: (pol.named() if k == "neg_samples" else
                      pol.named("dp", None)) for k in b}
            return b, sh
        b = {"sparse": _sds((B, cfg.n_sparse), jnp.int32),
             "labels": _sds((B,), jnp.float32)}
        sh = {"sparse": pol.named("dp", None), "labels": pol.named("dp")}
        if cfg.n_dense:
            b["dense"] = _sds((B, cfg.n_dense), jnp.float32)
            sh["dense"] = pol.named("dp", None)
        return b, sh

    if shape.kind == "train":
        B = shape.batch
        labels = OPT.default_labels(params_sds)
        opt_sds = jax.eval_shape(lambda p: OPT.init_opt_state(p, labels),
                                 params_sds)
        oc = OPT.OptConfig(lr=1e-3)
        loss = lambda p, b: R.loss_fn(cfg, p, b, pol)
        step = make_train_step(loss, oc, labels=labels, jit=False)
        batch_sds, bshard = batch_for(B)
        return Cell(arch, shape.name, step,
                    (params_sds, opt_sds, batch_sds),
                    (pshard, oshard_tree(opt_sds), bshard), donate=(0, 1),
                    model_flops=3.0 * _recsys_dense_flops(cfg, B))

    if shape.kind == "serve":
        B = shape.batch
        batch_sds, bshard = batch_for(B)
        if cfg.name == "bert4rec":
            batch_sds = {"seq": batch_sds["seq"],
                         "seq_mask": batch_sds["seq_mask"],
                         "slate": _sds((B, 64), jnp.int32)}
            bshard = {"seq": pol.named("dp", None),
                      "seq_mask": pol.named("dp", None),
                      "slate": pol.named("dp", None)}
        else:
            batch_sds.pop("labels"); bshard.pop("labels")
        fn = lambda p, b: R.serve_step(cfg, p, b, pol)
        return Cell(arch, shape.name, fn, (params_sds, batch_sds),
                    (pshard, bshard),
                    model_flops=_recsys_dense_flops(cfg, B))

    # retrieval_cand (candidate list padded to shard over every device)
    N = -(-shape.n_candidates // max(ndev, 1)) * max(ndev, 1)
    if cfg.name == "bert4rec":
        batch_sds = {"seq": _sds((1, cfg.seq_len), jnp.int32),
                     "seq_mask": _sds((1, cfg.seq_len), bool),
                     "candidates": _sds((N,), jnp.int32)}
        bshard = {"seq": pol.named(None, None),
                  "seq_mask": pol.named(None, None),
                  "candidates": pol.named("flat")}
    else:
        batch_sds = {"sparse": _sds((1, cfg.n_sparse), jnp.int32),
                     "candidates": _sds((N,), jnp.int32)}
        bshard = {"sparse": pol.named(None, None),
                  "candidates": pol.named("flat")}
        if cfg.n_dense:
            batch_sds["dense"] = _sds((1, cfg.n_dense), jnp.float32)
            bshard["dense"] = pol.named(None, None)
    n_stages = 2 if variant == "opt" else 1
    if variant == "opt":
        batch_sds["cand_proxy"] = _sds((N, 16), jnp.float32)
        bshard["cand_proxy"] = pol.named("flat", None)
    fn = lambda p, b: R.retrieval_step(cfg, p, b, pol, stages=n_stages,
                                       two_level_topk=(variant == "opt"))
    flops = _recsys_dense_flops(cfg, N if n_stages == 1 else 256)
    return Cell(arch, shape.name, fn, (params_sds, batch_sds),
                (pshard, bshard), model_flops=flops,
                note=f"stages={n_stages}")


# ===========================================================================
# Retriever family (the paper's own models; §Perf serving rows)
# ===========================================================================

def build_retriever_cell(arch: str, shape, mesh, variant: str = "base",
                         stages=None) -> Cell:
    from repro.models import late_interaction as LI
    from repro.core import multistage as MST
    from repro.retrieval.engine import make_search_fn

    cfg = get_config(arch)
    pol = ShardingPolicy(mesh)
    ndev = n_devices(mesh) if mesh is not None else 1

    if shape.kind == "train":
        B = shape.global_batch
        params_sds = _eval_shape(
            lambda: LI.init_params(cfg, jax.random.PRNGKey(0)))
        pshard = _replicated_like(pol, params_sds)
        labels = OPT.default_labels(params_sds)
        opt_sds = jax.eval_shape(lambda p: OPT.init_opt_state(p, labels),
                                 params_sds)
        oc = OPT.OptConfig()
        loss = lambda p, b: LI.contrastive_loss(cfg, p, b, pol)
        step = make_train_step(loss, oc, labels=labels, jit=False)
        n_raw = cfg.n_patches * (4 if cfg.geometry == "dynamic" else 1)
        batch_sds = {
            "patches": _sds((B, n_raw, LI.D_PATCH), jnp.float32),
            "query_tokens": _sds((B, cfg.max_query_tokens), jnp.int32),
            "query_mask": _sds((B, cfg.max_query_tokens), bool)}
        bshard = {k: pol.named("dp", *([None] * (len(v.shape) - 1)))
                  for k, v in batch_sds.items()}
        flops = 12.0 * cfg.n_layers * cfg.d_model * cfg.d_model * 3 \
            * B * cfg.seq_len
        return Cell(arch, shape.name, step,
                    (params_sds, opt_sds, batch_sds),
                    (pshard, _replicated_like(pol, opt_sds), bshard),
                    donate=(0, 1), model_flops=flops)

    if shape.kind == "index":
        B = shape.pages_per_step
        params_sds = _eval_shape(
            lambda: LI.init_params(cfg, jax.random.PRNGKey(0)))
        pshard = _replicated_like(pol, params_sds)
        n_raw = cfg.n_patches * (4 if cfg.geometry == "dynamic" else 1)

        from repro.kernels.pooling import pooling_matrix
        pm = jnp.asarray(pooling_matrix(cfg))

        def fn(p, patches):
            vecs, types = LI.encode_pages(cfg, p, patches, pol)
            vis = vecs[:, cfg.n_special:]
            mask = jnp.ones(vis.shape[:2], jnp.float32)
            from repro.kernels.pooling.ref import pool_ref
            pooled = pool_ref(vis, mask, pm)
            glob = jnp.mean(vis, axis=1)
            return vis.astype(jnp.bfloat16), pooled.astype(jnp.bfloat16), \
                glob.astype(jnp.bfloat16)

        patches_sds = _sds((B, n_raw, LI.D_PATCH), jnp.float32)
        flops = 12.0 * cfg.n_layers * cfg.d_model * cfg.d_model \
            * B * cfg.seq_len
        return Cell(arch, shape.name, fn, (params_sds, patches_sds),
                    (pshard, pol.named("dp", None, None)),
                    model_flops=flops / 3.0)

    # search over a sharded corpus
    # variants: "stage1" = pre-paper exact-scan baseline; "base" = the
    # paper's 2-stage cascade; "opt" = 2-stage + int8 scan storage.
    N = shape.corpus
    Bq = shape.query_batch
    if stages is None:
        if variant == "stage1":
            stages = MST.one_stage(shape.top_k)
        else:
            stages = MST.two_stage(shape.prefetch_k, shape.top_k)
    n_shards = ndev
    N_pad = -(-N // max(n_shards, 1)) * max(n_shards, 1)
    Dfull, Dp, d = cfg.n_patches, cfg.n_pooled, cfg.out_dim
    from repro.retrieval.store import codes_key, mask_key, scale_key
    store_sds = {
        "initial": _sds((N_pad, Dfull, d), jnp.bfloat16),
        mask_key("initial"): _sds((N_pad, Dfull), bool),
        "mean_pooling": _sds((N_pad, Dp, d), jnp.bfloat16),
        mask_key("mean_pooling"): _sds((N_pad, Dp), bool),
        "global_pooling": _sds((N_pad, d), jnp.bfloat16),
    }
    if variant == "opt":
        first = stages[0].vector
        store_sds[codes_key(first)] = _sds(store_sds[first].shape, jnp.int8)
        store_sds[scale_key(first)] = _sds(store_sds[first].shape[:2],
                                           jnp.float32)
    fn = make_search_fn(mesh, stages, N_pad)
    # underlying searcher is already jitted; unwrap for uniform handling
    fn = fn.__wrapped__ if hasattr(fn, "__wrapped__") else fn
    from repro.retrieval.engine import store_shardings
    sshard = store_shardings(mesh, store_sds)
    q_sds = _sds((Bq, 32, d), jnp.float32)
    qm_sds = _sds((Bq, 32), jnp.float32)
    # stage-1 madds + rerank madds (Eq. 1)
    flops = 2.0 * Bq * 32 * d * (N_pad * Dp + shape.prefetch_k * Dfull)
    return Cell(arch, shape.name, fn, (store_sds, q_sds, qm_sds),
                (sshard, pol.named(), pol.named()),
                model_flops=flops,
                note=f"stages={[s.vector for s in stages]}")


# ===========================================================================
# dispatch
# ===========================================================================

def build_cell(arch: str, shape_name: str, mesh, variant: str = "base",
               **kw) -> Cell:
    """variant="base": paper-faithful/straightforward sharding baseline.
    variant="opt": beyond-baseline optimisation set (§Perf hillclimbs):
      - MoE archs: ragged sorted dispatch instead of dense all-experts
      - equiformer: fused rotate+truncate / expand+rotate-back
      - recsys retrieval_cand: the paper's 2-stage prefetch->rerank
      - retriever search: int8 scan stage (+ the 2-stage cascade)
    """
    cfg = get_config(arch)
    shape = get_shapes(arch)[shape_name]
    fam = cfg.family
    if fam == "lm":
        return build_lm_cell(arch, shape, mesh, variant)
    if fam == "gnn":
        return build_gnn_cell(arch, shape, mesh, variant)
    if fam == "recsys":
        return build_recsys_cell(arch, shape, mesh, variant)
    if fam == "retriever":
        return build_retriever_cell(arch, shape, mesh, variant, **kw)
    raise ValueError(fam)
