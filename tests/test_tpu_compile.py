"""Ahead-of-time compiles of the device path for a described TPU v5e.

Interpret mode (the rest of the suite) cannot see what Mosaic refuses:
unsupported primitives, index maps that return 64-bit literals under x64,
tiles the layout rejects, or more VMEM than a kernel may use.  These tests
compile the two Pallas kernels and the scanned fold for a ``v5e:2x2``
topology that is described, not attached, at bucket shapes the served
ridesharing stream produces — under the suite's x64 mode, as every other
test runs.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library, and every test worker imports this
file.  Nothing here runs a kernel.
"""

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.kernels.hamlet_dense import dense_propagate_pallas
from repro.kernels.hamlet_propagate import masked_prefix_propagate_pallas


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / topology support here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    # a compile for a described chip is written to the cache but cannot be
    # read back without one; keep these compiles out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("nb,b,d", [(8, 128, 128), (2, 1152, 128)])
def test_masked_kernel_compiles_for_v5e(one_chip, nb, b, d):
    assert jax.config.jax_enable_x64   # the suite's mode (conftest.py)
    fn = jax.jit(functools.partial(masked_prefix_propagate_pallas,
                                   interpret=False))
    compiled = fn.lower(_spec((nb, b, d), jnp.float32, one_chip),
                        _spec((nb, b, b), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_dense_kernel_compiles_for_v5e(one_chip):
    fn = jax.jit(functools.partial(dense_propagate_pallas, interpret=False))
    compiled = fn.lower(_spec((4, 512, 128), jnp.float32, one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_compiled_kernels_refuse_64bit(one_chip):
    fn = jax.jit(functools.partial(masked_prefix_propagate_pallas,
                                   interpret=False))
    with pytest.raises(TypeError, match="32-bit"):
        fn.lower(_spec((1, 128, 128), jnp.float64, one_chip),
                 _spec((1, 128, 128), jnp.float64, one_chip))


@pytest.mark.parametrize("rounds,lanes", [(1, 8), (2, 32)])
def test_fold_rounds_scan_compiles_for_v5e(one_chip, rounds, lanes):
    # the 25-query ridesharing component: nu = 4 sum units, t = 4 positive
    # types, n_used = 4, C = 22 state columns, R = 1 + nu*t + nu rows
    nu, t, n_used, C = 4, 4, 4, 22
    R = 1 + nu * t + nu
    f32, i32 = jnp.float32, jnp.int32
    args = (_spec((1024, C), f32, one_chip),              # Z0
            _spec((128, 1 + nu), f32, one_chip),          # S
            _spec((rounds, lanes, t), f32, one_chip),     # PTM
            _spec((rounds, lanes, R), i32, one_chip),     # GQ
            _spec((rounds, lanes, n_used), i32, one_chip),
            _spec((rounds, lanes * n_used), i32, one_chip),
            _spec((rounds, lanes * n_used), i32, one_chip))
    compiled = ops.fold_rounds_scan.lower(*args, nu=nu, t=t,
                                          n_used=n_used).compile()
    assert compiled.memory_analysis() is not None


def test_platform_picks_interpreter_on_cpu():
    # the same kernels run in the interpreter here, chosen by the platform
    from repro.kernels.platform import device_dtype, interpret_kernels
    assert jax.default_backend() == "cpu"
    assert interpret_kernels()
    assert device_dtype(np.float64) == np.float64   # x64 on the CPU
