"""A cell of four chips, on four CPU devices: set-up doc-shards the store
over a one-axis mesh, the reference holds and scores the corpus
doc-sharded too, and both read what one chip reads.

The device count has to be set before JAX starts, so the four-device
work runs once, in one subprocess for the module, and prints what the
tests below judge as one JSON line."""
import json
import os
import subprocess
import sys
import textwrap
import types

import pytest

from bench import harness, manifest

PAGES = 512                  # bench/tests/tiny.py's cut of the cell

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    import dataclasses, gc, json
    import jax, numpy as np
    from bench import corpus, harness, reference
    from bench.tests import tiny

    SEED = 2**31 + 83
    base = tiny.cell("colpali-24k.batch")
    cfg, devs = base.config, jax.devices()
    out = {}

    def live_bytes():
        # per device, every live buffer once (arrays may share one); the
        # shard views made here form cycles, so collect those first
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

    # the reference, doc-sharded and on one device, on a sample of queries
    # and of answers: the one-device reference's own, and a shifted copy
    topic = corpus.topics(SEED, cfg["topics"], cfg["geometry"]["dim"])
    q, lens = corpus.query_pool(SEED, topic, cfg["check"]["sample"], 4, 32)
    k, pk, n = cfg["cascade"]["top_k"], cfg["cascade"]["prefetch_k"], \\
        cfg["pages"]
    one = reference.Reference(cfg, SEED)
    first = one.search(q, lens, pk, np.zeros((len(q), k), np.int64))
    answers = [(first["exact"][:, :k], first["cand"][:, :k]),
               (first["exact"][:, 1:k + 1], (first["cand"][:, :k] + 7) % n)]
    ones = [one.search(q, lens, pk, ids) for _, ids in answers]
    del one
    gc.collect()
    held = {"before": live_bytes()}
    four = reference.Reference(cfg, SEED, devs)
    held["built"] = live_bytes()
    pick = reference._pick

    def counted(parts, owner):
        held["searching"] = [max(a, b) for a, b in zip(
            held.get("searching", [0] * len(devs)), live_bytes())]
        return pick(parts, owner)

    reference._pick = counted
    fours = [four.search(q, lens, pk, ids) for _, ids in answers]
    reference._pick = pick
    out["ref_devices"] = [sorted(d.id for d in a.devices())
                          for a in four.pooled + four.full + four.keep]
    share = sum(a.nbytes for a in four.pooled + four.full + four.keep) \\
        // len(devs)
    del four
    gc.collect()
    out["ref"] = {
        "held": held, "share": share,
        # one query block's arrays on a device: queries, mask, scores of
        # the shard's pages, its top list, the returned pages' scan
        # scores and the exact scores
        "block": 8 * (q.shape[1] * (q.shape[2] * 4 + 1)
                      + n // len(devs) * 4 + min(pk + 1, n // len(devs)) * 8
                      + k * 4 + (pk + k) * 4),
        "same_arrays": [bool(np.array_equal(a[key], b[key]))
                        for a, b in zip(ones, fours) for key in a],
        "compare": [[reference.compare(r, s, ids, n, pk, lim)
                     for r in (a, b)]
                    for (s, ids), a, b, lim in zip(
                        answers, ones, fours,
                        [cfg["check"]["limits"]["prefetch_gap"]] * 2)]}

    # the cell through the harness on four chips, then on one
    def record(fe):
        out["store"] = {
            name: {"devices": len(v.sharding.device_set),
                   "rows": sorted({s.data.shape[0]
                                   for s in v.addressable_shards}),
                   "spec": str(getattr(v.sharding, "spec", None))}
            for seg in fe.retriever.store.segments
            for name, v in seg.vectors.items()}

    out["four"] = tiny.run(dataclasses.replace(base, chips=4), SEED,
                           seconds=2, patch=record)
    out["fullest"] = harness.fullest(devs, "peak_bytes_in_use")
    out["one"] = tiny.run(base, SEED, seconds=2)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    env["PYTHONPATH"] = os.pathsep.join(
        [str(manifest.ROOT / "src"), str(manifest.ROOT),
         env.get("PYTHONPATH", "")])
    p = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                       cwd=manifest.ROOT, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_four_chip_cell_is_correct(four):
    out = four["four"]
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["device"]["count"] == 4
    for name, c in out["checks"].items():
        assert c["value"] <= c["limit"], name


def test_served_store_is_doc_sharded_over_four_devices(four):
    assert four["store"], "no segment array recorded"
    for name, a in four["store"].items():
        assert a["devices"] == 4, name
        assert a["rows"] == [PAGES // 4], name
        assert a["spec"] == "PartitionSpec('data',)", name


def test_memory_is_the_fullest_devices(four):
    assert four["four"]["device"]["memory_peak_bytes"] == four["fullest"]


def test_fullest_reads_the_fullest_device():
    def dev(stats):
        return types.SimpleNamespace(memory_stats=lambda: stats)

    devs = [dev({"peak_bytes_in_use": 10}), dev({"peak_bytes_in_use": 30}),
            dev(None), dev({"peak_bytes_in_use": 20})]
    assert harness.fullest(devs, "peak_bytes_in_use") == 30
    assert harness.fullest(devs[:1], "peak_bytes_in_use") == 10
    assert harness.fullest([dev(None)], "bytes_in_use") == 0


def test_one_chip_has_no_mesh():
    # a one-device mesh would move a one-chip cell onto the shard_map body
    assert harness.doc_mesh(harness.jax.devices()[:1]) is None


def test_one_chip_reads_the_same_checks(four):
    c4, c1 = four["four"]["checks"], four["one"]["checks"]
    assert four["one"]["correct"], c1
    assert set(c4) == set(c1)
    for name in c4:
        lim = c4[name]["limit"]
        assert abs(c4[name]["value"] - c1[name]["value"]) <= lim, name


def test_sharded_reference_is_the_one_device_reference_bit_for_bit(four):
    ref = four["ref"]
    assert all(ref["same_arrays"])
    for one, sharded in ref["compare"]:
        assert one == sharded
    # the shifted answers are wrong, so the comparison read something
    assert ref["compare"][1][0]["bad_ids"] == 0
    assert ref["compare"][1][0]["score_err"] > 0


def test_sharded_reference_holds_a_share_a_device(four):
    ref = four["ref"]
    assert four["ref_devices"] == [[d] for d in range(4)] * 3
    before = ref["held"]["before"]
    for stage in ("built", "searching"):
        for dev, n in enumerate(ref["held"][stage]):
            assert n - before[dev] <= ref["share"] + ref["block"], \
                (stage, dev, n, ref["share"])
    assert min(ref["held"]["built"]) - max(before) >= ref["share"]
