"""Doc-sharded set-up: allocation, the seed write and the ingest write on
a mesh touch only each shard's own rows, and leave the store bit for bit
what one device builds.

``owned_rows`` is checked in-process against a plain write of the whole
array, for blocks inside one shard, straddling shard boundaries, longer
than a shard and missing a shard. The mesh path runs once, in one
subprocess with four CPU devices (the device count has to be set before
JAX starts), at colpali's page geometry with the Pallas pooling kernel
(interpreted): a ``Retriever(mesh=...)`` takes a seed batch, ingest
batches of several buckets (one straddling a shard boundary, one that
opens a second segment), an ``upsert`` that straddles too, a delete and
a compaction, and is compared array by array with the same sequence on
one device, then searched through the ``shard_map`` cascade."""
import json
import os
import subprocess
import sys
import textwrap

import jax.numpy as jnp
import numpy as np
import pytest

from repro.retrieval.segments import owned_rows

SHARDS, N_LOCAL = 4, 8


@pytest.mark.parametrize("start,size", [
    (0, 8), (8, 8), (3, 4), (6, 4), (5, 16), (2, 8), (24, 8), (29, 3),
    (4, 28), (0, 32), (12, 2), (16, 16)])
def test_owned_rows_is_the_plain_write_split_over_shards(start, size):
    rng = np.random.default_rng(start * 100 + size)
    full = rng.normal(size=(SHARDS * N_LOCAL, 3)).astype(np.float32)
    block = rng.normal(size=(size, 3)).astype(np.float32)
    want = full.copy()
    want[start:start + size] = block
    got = np.concatenate([
        np.asarray(owned_rows(jnp.asarray(full[s * N_LOCAL:(s + 1)
                                               * N_LOCAL]),
                              jnp.asarray(block),
                              jnp.int32(start - s * N_LOCAL)))
        for s in range(SHARDS)])
    np.testing.assert_array_equal(got, want)


SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import gc, json
    import numpy as np, jax, jax.numpy as jnp
    from repro.configs import get_config
    from repro.core import multistage as MST
    from repro.core.hygiene import SPECIAL, VISUAL
    from repro.launch.mesh import make_mesh
    from repro.retrieval import tracing
    from repro.retrieval.ingest import IngestPipeline
    from repro.retrieval.retriever import Retriever

    cfg = get_config("colpali")
    tt = jnp.asarray([SPECIAL] * cfg.n_special + [VISUAL] * cfg.n_patches)
    pipe = IngestPipeline(cfg, use_kernel=True, impl="pallas",
                          interpret=True)
    mesh = make_mesh((4,), ("data",))
    devs = jax.devices()
    CAP = 96                 # 24 slots a device in the first segment
    # ingest batch sizes after the 8-page seed: pages [8, 32) straddle the
    # boundary at slot 24 (bucket 32, so the block reaches slot 40), 16
    # fill [32, 48), 5 land in [48, 53) (bucket 8); 40 need a bucket of
    # 64 and open a second segment; the upsert of 9 straddles slot 120
    SIZES = [24, 16, 5, 40]

    def pages(n, seed):
        r = np.random.default_rng(seed)
        x = r.normal(size=(n, cfg.seq_len, cfg.out_dim)).astype(np.float32)
        return jnp.asarray(x / np.linalg.norm(x, axis=-1, keepdims=True))

    def live_bytes():
        gc.collect()
        seen = {}
        for a in jax.live_arrays():
            for sh in a.addressable_shards:
                seen[sh.data.unsafe_buffer_pointer()] = (
                    sh.device.id, sh.data.nbytes)
        per = [0] * len(devs)
        for dev, n in seen.values():
            per[dev] += n
        return per

    def same_arrays(a, b):
        sa, sb = a.store.segments, b.store.segments
        return len(sa) == len(sb) and all(
            set(x.vectors) == set(y.vectors) and all(
                x.vectors[k].dtype == y.vectors[k].dtype
                and np.array_equal(np.asarray(x.vectors[k]),
                                   np.asarray(y.vectors[k]))
                for k in x.vectors)
            for x, y in zip(sa, sb))

    def build(mesh, held=None):
        def note(what):
            if held is not None:
                held.append((what, live_bytes(), sum(
                    int(v.nbytes) for seg in r.store.segments
                    for v in seg.vectors.values())))
        r = Retriever(pipe.index(pages(8, 0), tt), capacity=CAP,
                      ingest=pipe, mesh=mesh)
        note("seed")
        for i, n in enumerate(SIZES):
            r.ingest(pages(n, i + 1), tt)
            note(f"ingest {n}")
        r.upsert(pipe.index(pages(9, 7), tt))
        note("upsert 9")
        return r

    out = {}
    base = live_bytes()
    held = []
    r4 = build(mesh, held)
    # the steady state: once a bucket is warmed on a segment's layout,
    # another batch of that bucket traces nothing
    r4.ingest(pages(3, 11), tt)
    with tracing.no_retrace("sharded ingest"):
        r4.ingest(pages(2, 12), tt)
    r1 = build(None)
    r1.ingest(pages(3, 11), tt)
    r1.ingest(pages(2, 12), tt)
    batch = 64 * cfg.seq_len * cfg.out_dim * 4     # the largest raw batch
    out["held"] = [(w, [n - b for n, b in zip(per, base)], store // 4)
                   for w, per, store in held]
    out["batch"] = batch
    out["capacities"] = [r4.store.capacities, r1.store.capacities]
    out["sharded"] = sorted({str(v.sharding.spec) for seg in r4.store.segments
                             for v in seg.vectors.values()})
    out["same_arrays"] = same_arrays(r4, r1)
    out["same_ids"] = all(np.array_equal(a.doc_ids, b.doc_ids)
                          for a, b in zip(r4.store.segments,
                                          r1.store.segments))

    stages = MST.with_rerank_policy(
        MST.with_scan_policy(MST.two_stage(32, 10), use_kernel=True),
        rerank_kernel=True)
    q = jnp.asarray(np.random.default_rng(5).normal(
        size=(3, 7, cfg.out_dim)).astype(np.float32))

    def search(r):
        s, i = r.search(q, None, stages=stages)
        return np.asarray(s).tolist(), np.asarray(i).tolist()

    out["search"] = [search(r4), search(r1)]
    for r in (r4, r1):
        r.delete([1, 9, 30, 70])
        r.compact()
    out["compacted_same"] = same_arrays(r4, r1)
    out["compacted_search"] = [search(r4), search(r1)]
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def sharded():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env["PYTHONPATH"] = os.path.abspath(src) + os.pathsep + \
        env.get("PYTHONPATH", "")
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_sharded_store_is_the_one_device_store_bit_for_bit(sharded):
    # two segments: the 40-page batch could not fit the first one's tail
    assert sharded["capacities"] == [[96, 64], [96, 64]]
    assert sharded["sharded"] == ["PartitionSpec('data',)"]
    assert sharded["same_ids"]
    assert sharded["same_arrays"]


def test_sharded_cascade_returns_the_one_device_answers(sharded):
    four, one = sharded["search"]
    assert four == one
    four, one = sharded["compacted_search"]
    assert four == one
    assert sharded["compacted_same"]


def test_no_device_holds_more_than_its_slab_and_a_batch(sharded):
    steps = [w for w, _, _ in sharded["held"]]
    assert steps == ["seed", "ingest 24", "ingest 16", "ingest 5",
                     "ingest 40", "upsert 9"]
    for what, per_device, slab in sharded["held"]:
        for dev, n in enumerate(per_device):
            assert n <= slab + sharded["batch"], (what, dev, n, slab)
        # every device holds its share: the store is laid out, not kept
        # on one device
        assert min(per_device) >= slab, (what, per_device, slab)
