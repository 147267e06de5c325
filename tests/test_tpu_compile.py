"""Compile rehearsal: every Pallas kernel, compiled for a described TPU
v5e at published widths, with the blocks ``repro.kernels.ops`` picks.

Nothing runs; the chip's compiler refuses here what it would refuse on
the chip (unaligned blocks, VMEM overflow, unsupported lowering).  The
topology is described inside a fixture, so only the worker that runs
these tests loads the TPU compiler.
"""
import os

import jax
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.sites import sites

SITES = {s.name: s for s in sites()}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("name", sorted(SITES))
def test_kernel_compiles_for_v5e(name, one_chip):
    site = SITES[name]
    args = [jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip)
            for a in site.operands]
    # chip_smoke.py checks the kernels at the highest matmul precision,
    # which needs more VMEM than the default
    with jax.default_matmul_precision("highest"):
        compiled = jax.jit(site.kernel).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
