"""End-to-end driver: train a ~100M-param late-interaction retriever for a
few hundred steps with the ColBERT-style in-batch contrastive objective,
checkpointing + resume included.

    PYTHONPATH=src python examples/train_retriever.py --steps 200

(--small trains a ~1M model in seconds for CI; default config is ~100M —
 24 layers x d_model 576, which is real work on CPU.)
"""
import sys, os
sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import argparse
import dataclasses
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.launch.runtime import device_line
from repro.distributed.sharding import ShardingPolicy
from repro.models import late_interaction as LI
from repro.training import checkpoint as CKPT
from repro.training import optimizer as OPT
from repro.training.train_loop import make_train_step


def synth_batch(rng, cfg, batch):
    """Aligned (page, query) pairs: queries point at their page's topic."""
    d = LI.D_PATCH
    n_raw = cfg.n_patches * (4 if cfg.geometry == "dynamic" else 1)
    topics = rng.normal(size=(batch, d)).astype(np.float32)
    pages = rng.normal(size=(batch, n_raw, d)).astype(np.float32) * 0.5
    pages[:, : n_raw // 4] += topics[:, None] * 1.5
    # query tokens hash the topic into the text-vocab space
    qtok = (np.abs(topics[:, :8]) * 1e4).astype(np.int64) % cfg.query_vocab
    return {"patches": jnp.asarray(pages),
            "query_tokens": jnp.asarray(qtok, jnp.int32),
            "query_mask": jnp.ones((batch, 8), bool)}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--small", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/retriever_ckpt")
    args = ap.parse_args()
    print(device_line(), flush=True)

    cfg = get_config("colpali")
    if args.small:
        cfg = dataclasses.replace(cfg, d_model=64, n_layers=2, n_heads=4,
                                  d_ff=128, grid_h=8, grid_w=8,
                                  query_vocab=1024)
    else:
        cfg = dataclasses.replace(cfg, d_model=576, n_layers=24, n_heads=8,
                                  d_ff=2304, grid_h=16, grid_w=16,
                                  query_vocab=8192)
    shard = ShardingPolicy(None)
    params = LI.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    print(f"[init] {cfg.name}-style retriever, {n_params/1e6:.1f}M params")

    labels = OPT.default_labels(params)
    oc = OPT.OptConfig(lr=3e-4, warmup=20, total_steps=args.steps)
    opt = OPT.init_opt_state(params, labels)
    step_fn = make_train_step(lambda p, b: LI.contrastive_loss(cfg, p, b,
                                                               shard),
                              oc, labels=labels, donate=False)
    start = 0
    last = CKPT.latest_step(args.ckpt_dir) if args.ckpt_dir else None
    if last is not None:
        st, meta = CKPT.restore(args.ckpt_dir, {"p": params, "o": opt})
        params, opt, start = st["p"], st["o"], meta["step"] + 1
        print(f"[resume] step {start}")

    rng = np.random.default_rng(0)
    t0 = time.time()
    for step in range(start, args.steps):
        batch = synth_batch(rng, cfg, args.batch)
        params, opt, m = step_fn(params, opt, batch)
        if step % 10 == 0:
            print(f"step {step:4d} loss={float(m['loss']):.4f} "
                  f"({(time.time()-t0):.1f}s)", flush=True)
        if args.ckpt_dir and (step + 1) % 50 == 0:
            CKPT.save(args.ckpt_dir, step, {"p": params, "o": opt})
    print(f"final loss {float(m['loss']):.4f} "
          f"(in-batch CE; ln({args.batch})={np.log(args.batch):.2f} at init)")


if __name__ == "__main__":
    main()
