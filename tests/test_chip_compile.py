"""The served path's Pallas kernels compile for a TPU v5e at served shapes.

Each case AOT-compiles one served kernel instance (the ``served_instances``
of the maxsim and pooling ops modules, the same ones their TPU probes
compile) for one chip of a described (not attached) v5e:2x2 topology and
checks that the kernel is in the program as a ``tpu_custom_call``.
Interpret-mode tests cannot see what the TPU compiler refuses (misaligned
blocks, layouts Mosaic cannot cast, VMEM overflow); these can, without a
chip.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so a test worker that
imports this file must not load it unless it runs these tests.
"""
import os

import jax.numpy as jnp
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
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:       # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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
