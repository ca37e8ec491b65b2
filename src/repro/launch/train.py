"""Production training launcher: any arch, real data loop, fault tolerance.

  PYTHONPATH=src python -m repro.launch.train --arch minicpm-2b --reduced \
      --steps 50 --ckpt-dir /tmp/run1

Features demonstrated end-to-end on this host (and identical at pod scale):
  - config-driven arch selection (--arch), reduced configs for CPU (--reduced)
  - synthetic data pipeline with DETERMINISTIC per-(step, shard) batches
    (straggler/elastic recovery: any host can recompute any batch)
  - checkpoint/restart (atomic, keep-k): kill it mid-run and relaunch with
    the same --ckpt-dir; it resumes from LATEST
  - straggler watchdog (flags slow steps)
  - optional elastic re-mesh on restart (different device count)
"""
from __future__ import annotations

import argparse
import dataclasses
import time

import numpy as np


def reduced_lm(cfg):
    return dataclasses.replace(
        cfg, n_layers=2, d_model=128, n_heads=4,
        n_kv_heads=max(1, min(cfg.n_kv_heads, 2)), head_dim=32, d_ff=256,
        vocab_size=512,
        attn_pattern=tuple(min(w, 16) if w else 0 for w in cfg.attn_pattern),
        loss_chunks=2, dtype="float32",
        moe=None if cfg.moe is None else dataclasses.replace(
            cfg.moe, n_experts=4, top_k=2, d_ff=64))


def main():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.distributed.sharding import ShardingPolicy
    from repro.launch.runtime import device_line
    from repro.models import transformer as T
    from repro.training import checkpoint as CKPT
    from repro.training import optimizer as OPT
    from repro.training.elastic import (StragglerWatchdog,
                                        deterministic_batch_seed)
    from repro.training.train_loop import make_train_step

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config (same code path)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    print(device_line(), flush=True)

    cfg = get_config(args.arch)
    assert cfg.family == "lm", "train.py drives the LM family; see examples/"
    if args.reduced:
        cfg = reduced_lm(cfg)
    shard = ShardingPolicy(None)

    key = jax.random.PRNGKey(args.seed)
    params = T.init_params(cfg, key)
    labels = OPT.default_labels(params)
    oc = OPT.OptConfig(lr=3e-4,
                       schedule="wsd" if "minicpm" in args.arch else "cosine",
                       warmup=10, total_steps=args.steps)
    opt = OPT.init_opt_state(params, labels)
    start = 0

    if args.ckpt_dir:
        last = CKPT.latest_step(args.ckpt_dir)
        if last is not None:
            (state, meta) = CKPT.restore(args.ckpt_dir,
                                         {"params": params, "opt": opt})
            params, opt = state["params"], state["opt"]
            start = meta["step"] + 1
            print(f"[resume] from step {meta['step']}")

    loss_fn = lambda p, b: T.loss_fn(cfg, p, b, shard)
    step_fn = make_train_step(loss_fn, oc, labels=labels, donate=False)
    dog = StragglerWatchdog()

    for step in range(start, args.steps):
        rng = np.random.default_rng(
            deterministic_batch_seed(args.seed, step, 0))
        tokens = jnp.asarray(
            rng.integers(0, cfg.vocab_size, (args.batch, args.seq)),
            jnp.int32)
        batch = {"tokens": tokens, "labels": jnp.roll(tokens, -1, axis=1)}
        t0 = time.time()
        params, opt, m = step_fn(params, opt, batch)
        dt = time.time() - t0
        slow = dog.record(dt)
        if step % 5 == 0 or slow:
            print(f"step {step:4d} loss={float(m['loss']):.4f} "
                  f"lr={float(m['lr']):.2e} {dt*1e3:.0f}ms"
                  + ("  [STRAGGLER]" if slow else ""), flush=True)
        if args.ckpt_dir and (step + 1) % args.ckpt_every == 0:
            CKPT.save(args.ckpt_dir, step, {"params": params, "opt": opt},
                      meta={"arch": args.arch})
    print("done.")


if __name__ == "__main__":
    main()
