"""Every VectorApply round body the registry reaches compiles for a TPU
v5e at deployment widths: a 65,536-word state array and rounds of 256
announcements (the widths ``chip_smoke.py`` drives on the chip).

Nothing runs: the topology is described, not attached, so this guards
what the chip's compiler would refuse (64-bit emulation, while-loop
sifts over a large carry) at no chip time.  It says nothing about
results or times.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import vector_rounds

WIDTH = 65_536      # heap capacity
DEGREE = 256        # announcements per combining round


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)
        compilation_cache.reset_cache()


#: kernel name -> argument (shape, dtype) list, in call order
_ARGS = {
    "counter.FAA": [((), "int64"), ((DEGREE,), "int64")],
    "float.MUL": [((), "float64"), ((DEGREE,), "float64")],
    "heap.HINSERT": [((WIDTH,), "int64"), ((), "int64"),
                     ((DEGREE,), "int64")],
    "heap.HDELETEMIN": [((WIDTH,), "int64"), ((), "int64"),
                        ((DEGREE,), "int64")],
    "log.RECORD": [((DEGREE,), "int64")] * 3,
    "ckpt.CKPT": [((), "int64"), ((DEGREE,), "int64"),
                  ((DEGREE,), "int64")],
}


@pytest.mark.parametrize("name", sorted(_ARGS))
def test_kernel_compiles_for_v5e(name, one_chip, no_compile_cache):
    with jax.enable_x64(True):
        specs = [jax.ShapeDtypeStruct(shape, jnp.dtype(dt),
                                      sharding=one_chip)
                 for shape, dt in _ARGS[name]]
        compiled = vector_rounds.kernel(name).lower(*specs).compile()
    mem = compiled.memory_analysis()
    # the round's arrays fit one chip's 16 GB many times over
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 1 << 30
