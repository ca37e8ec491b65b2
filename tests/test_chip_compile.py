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

import pytest

from repro.kernels import dispatch as DSP
from repro.kernels.maxsim import ops as K
from repro.kernels.pooling import ops as P

SERVED = {**K.served_instances(), **P.served_instances()}


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
