"""The assignment kernel compiles for a TPU v5e that is described, not attached.

The TPU compiler ships with JAX, so these tests lower and compile
``coflow_assign_fwd`` for one chip of a described ``v5e:2x2`` topology at
the deployment's real sizes: what Mosaic refuses here (an unaligned dynamic
load, a vector layout it cannot lower, more VMEM than the kernel may use)
would otherwise first show on the chip. Nothing runs, so they say nothing
about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU library, and under pytest-xdist every
worker imports this file. Keep these tests in this one file, so a single
worker loads the library. A Mosaic layout bug can abort that worker process
(a failed C++ check, SIGABRT) instead of raising: pytest-xdist then reports
the worker as crashed rather than a test as failed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.coflow_assign import MAX_CORES, MAX_PORTS, coflow_assign_fwd

#: Flows of the FB2010 deployment: every one of the 150 racks a port, the
#: hour's 526 coflows (``synth_fb_trace(526, seed=2026)``, instance seed 0).
FB_FLOWS = 443_943


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip cannot be read back from the cache
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)


def _lower(n_ports, k_cores, n_flows, sharding=None):
    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return coflow_assign_fwd.lower(
        spec((n_flows,), jnp.int32), spec((n_flows,), jnp.int32),
        spec((n_flows,), jnp.float32), spec((k_cores,), jnp.float32),
        spec((), jnp.float32), n_ports=n_ports)


@pytest.mark.parametrize("n_ports,k_cores,n_flows", [
    (150, 4, FB_FLOWS),
    (150, MAX_CORES, FB_FLOWS),
    (MAX_PORTS, MAX_CORES, 4096),
], ids=["fb2010-k4", "fb2010-k8", "max-ports-k8"])
def test_kernel_compiles_for_v5e(one_chip, n_ports, k_cores, n_flows):
    compiled = _lower(n_ports, k_cores, n_flows, one_chip).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("n_ports,k_cores", [
    (MAX_PORTS + 1, 4),
    (150, MAX_CORES + 1),
], ids=["ports", "cores"])
def test_kernel_refuses_state_beyond_vmem(n_ports, k_cores):
    with pytest.raises(ValueError, match="MAX_PORTS|MAX_CORES"):
        _lower(n_ports, k_cores, 1024)
