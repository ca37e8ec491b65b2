"""The served path's Pallas kernels compile for a TPU v5e at served shapes.

Each case AOT-compiles one served kernel instance (the ``served_instances``
of the maxsim and pooling ops modules, the same ones their TPU probes
compile) for one chip of a described (not attached) v5e:2x2 topology and
checks that the kernel is in the program as a ``tpu_custom_call``.
Interpret-mode tests cannot see what the TPU compiler refuses (misaligned
blocks, layouts Mosaic cannot cast, VMEM overflow); these can, without a
chip.

The doc-sharded deployment's programs (the ingest write, the seed write
and the cascade over 98,304 colpali pages on four chips) are compiled for
the whole 2x2 mesh: each must compile, hold its kernel, and need no more
than a small share of a chip's memory for temporaries, so no program
gathers the sharded store onto one chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so a test worker that
imports this file must not load it unless it runs these tests.
"""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import dispatch as DSP
from repro.kernels.maxsim import maxsim as M
from repro.kernels.maxsim import ops as K
from repro.kernels.pooling import ops as P

SERVED = {**K.served_instances(), **P.served_instances()}
# compiled here only, not at set-up: the scan at colsmol's pooled geometry
# (13 tile means x 128-d, 30,720 resident pages, a full 16 x 32 cohort)
TEST_ONLY = {"scan-D13-bfloat16-B16": (
    "maxsim_scan", K._scan,
    [((16, K.Q_TOKENS, K.DIM), jnp.float32), ((16, K.Q_TOKENS), jnp.float32),
     *K._doc_shapes(30720, 13, jnp.bfloat16)])}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def four_chips(topo):
    """The four chips as the one-axis ``data`` mesh the doc-sharded
    deployment lays its store out on."""
    from jax.sharding import Mesh
    return Mesh(np.array(topo.devices), ("data",))


def test_every_family_has_served_instances():
    assert {fam for fam, _, _ in SERVED.values()} == {
        "maxsim_scan", "maxsim_rerank", "ivf_route", "pooling"}


@pytest.mark.parametrize("case", sorted(SERVED))
def test_served_kernel_compiles(one_chip, case):
    _, fn, shapes = SERVED[case]
    assert "tpu_custom_call" in DSP.compile_abstract(fn, shapes, one_chip)


@pytest.mark.parametrize("case,body", [
    ("scan-D34-bfloat16-B64", "packed"),       # colpali pooled
    ("scan-D34-int8-B16", "packed"),
    ("scan-D13-bfloat16-B16", "packed"),       # colsmol pooled
    ("scan-D1024-bfloat16-B16", "per_page"),   # full resolution
    ("scan-D1024-int8-B64", "per_page"),
])
def test_scan_body(one_chip, case, body):
    """The scan body each compiled instance took, by the kernel's
    trace-time record: pages shorter than a lane tile are packed."""
    _, fn, shapes = {**SERVED, **TEST_ONLY}[case]
    assert "tpu_custom_call" in DSP.compile_abstract(fn, shapes, one_chip)
    (B, Q, _), _ = shapes[0]
    (N, D, d), dtype = shapes[2]
    assert M.SCAN_BODIES[(B, Q, N, D, d, jnp.dtype(dtype).name)] == body


# the four-chip colpali cell: 4 x 24,576 pages, ingest batches of 256
SHARDED_PAGES, INGEST_BUCKET = 98304, 256
TEMP_LIMIT = 1 << 30         # bytes of temporaries a chip


def _sharded_segment(mesh):
    """The colpali ingest pipeline on its Pallas kernel, and the abstract
    arrays of a doc-sharded segment it writes (with the batch's blocks)."""
    from jax.sharding import NamedSharding, PartitionSpec as PS

    from repro.configs import get_config
    from repro.retrieval.ingest import IngestPipeline
    from repro.retrieval.store import FILTER_KEY, TENANT_KEY, VALIDITY_KEY
    cfg = get_config("colpali")
    pipe = IngestPipeline(cfg, use_kernel=True, impl="pallas",
                          interpret=False)
    blocks = jax.eval_shape(
        lambda p, t: pipe._index_arrays(p, t, None),
        jax.ShapeDtypeStruct((INGEST_BUCKET, cfg.seq_len, cfg.out_dim),
                             jnp.float32),
        jax.ShapeDtypeStruct((cfg.seq_len,), jnp.int32))
    blocks[VALIDITY_KEY] = jax.ShapeDtypeStruct((INGEST_BUCKET,), bool)
    blocks[TENANT_KEY] = jax.ShapeDtypeStruct((INGEST_BUCKET,), jnp.int32)
    blocks[FILTER_KEY] = jax.ShapeDtypeStruct((INGEST_BUCKET, 1),
                                              jnp.uint32)
    docs = NamedSharding(mesh, PS("data"))
    seg = {k: jax.ShapeDtypeStruct((SHARDED_PAGES,) + v.shape[1:], v.dtype,
                                   sharding=docs)
           for k, v in blocks.items()}
    return cfg, pipe, seg, blocks


def _replicated(mesh, shape, dtype):
    from jax.sharding import NamedSharding, PartitionSpec as PS
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, PS()))


def _check_sharded(compiled, kernel: bool) -> str:
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == kernel
    assert compiled.memory_analysis().temp_size_in_bytes < TEMP_LIMIT
    return text


def test_sharded_ingest_write_compiles(four_chips):
    """The fused write over a doc-sharded segment: the pooling kernel runs
    per chip under ``shard_map``, and each chip writes only its rows."""
    cfg, pipe, seg, _ = _sharded_segment(four_chips)
    rep = functools.partial(_replicated, four_chips)
    compiled = pipe.sharded_write(four_chips).lower(
        seg, rep((), jnp.int32),
        rep((INGEST_BUCKET, cfg.seq_len, cfg.out_dim), jnp.float32),
        rep((cfg.seq_len,), jnp.int32), rep((), jnp.int32),
        rep((), jnp.int32), rep((1,), jnp.uint32)).compile()
    assert "all-gather" not in _check_sharded(compiled, kernel=True)


def test_sharded_seed_write_compiles(four_chips):
    from repro.retrieval import segments
    _, _, seg, blocks = _sharded_segment(four_chips)
    rep = functools.partial(_replicated, four_chips)
    compiled = segments.sharded_write(four_chips).lower(
        seg, rep((), jnp.int32),
        {k: rep(v.shape, v.dtype) for k, v in blocks.items()}).compile()
    assert "all-gather" not in _check_sharded(compiled, kernel=False)


def test_sharded_cascade_compiles(four_chips, monkeypatch):
    """The two-stage cascade's ``shard_map`` body for a full (16, 32)
    cohort, with the scan and rerank on their Pallas kernels."""
    from repro.core import multistage as MST
    from repro.retrieval import engine
    resolve = DSP.resolve
    monkeypatch.setattr(DSP, "resolve", lambda name, use: (
        ("pallas", False) if use else resolve(name, use)))
    _, _, seg, _ = _sharded_segment(four_chips)
    stages = MST.with_rerank_policy(
        MST.with_scan_policy(MST.two_stage(256, 100), use_kernel=True),
        rerank_kernel=True)
    body = engine._build_body(four_chips, stages, (SHARDED_PAGES,), 8)
    rep = functools.partial(_replicated, four_chips)
    compiled = jax.jit(body).lower(
        (seg,), rep((16, 32, 128), jnp.float32), rep((16, 32), bool),
        (rep((), jnp.int32), rep((1,), jnp.uint32),
         rep((1,), jnp.uint32))).compile()
    text = _check_sharded(compiled, kernel=True)
    assert text.count("tpu_custom_call") >= 2       # scan and rerank
