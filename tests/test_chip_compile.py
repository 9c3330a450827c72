"""Compile-only checks against a described TPU v5e (``v5e:2x2``).

The chip's compiler is installed here and compiles for a chip that is
described, not attached.  It refuses what the CPU backend and the
Pallas interpreter accept: Mosaic tiling and dtype rules, and the x64
rewriter that emulates float64 (which aborts the process, instead of
raising, on a 64-bit -> c128 conversion).  Nothing runs, so these say
nothing about values or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every pytest-xdist
worker imports this file.  Keep these tests in this one file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.core import distributed as Dm
from repro.core import ryser as R
from repro.core.stepspace import DEFAULT_GEOMETRY, plan_slices
from repro.kernels.ryser_pallas import (ryser_pallas_call,
                                        ryser_pallas_call_batched)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    mp = pytest.MonkeyPatch()
    mp.setenv("TPU_LOG_DIR", "disabled")      # else the compiler logs in /tmp
    # a compile for a described chip is written to the persistent cache
    # but can never be read back without the chip: keep the cache off
    cache_was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler installed
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_was)
        mp.undo()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_dense_batch_compiles(one_chip):
    stack = _sds((16, 24, 24), jnp.float64, one_chip)
    R._batched_jit.lower(stack, 4096, "dq_acc").compile()


def test_complex_split_plane_batch_compiles(one_chip):
    plane = _sds((4, 20, 20), jnp.float64, one_chip)
    live = _sds((), jnp.int32, one_chip)
    R._batched_complex_jit.lower(plane, plane, live, 4096, "dq_acc").compile()


@pytest.mark.parametrize("devices", [1, 4])
@pytest.mark.parametrize("planes", [1, 2], ids=["real", "complex"])
def test_campaign_wave_compiles(topo, devices, planes):
    n = 40
    _, cps, chunk = plan_slices(n, 64, 1, 1024)   # the planner's defaults
    mesh = Mesh(np.array(topo.devices[:devices]), ("step",))
    rep = NamedSharding(mesh, PartitionSpec())
    A = (_sds((n, n), jnp.float64, rep),) * planes
    ids = _sds((devices, 1), jnp.int32,
               NamedSharding(mesh, PartitionSpec("step")))
    wave = Dm._wave_fn(mesh, cps, chunk, "dq_acc", "jnp", None)
    wave.lower(A, ids).compile()


def test_campaign_wave_compiles_at_the_record_cells_size(topo):
    """The wave of the benchmark's four-chip campaign cell: real, n = 31
    (the smallest n the planner's threshold routes to the campaign)."""
    n = 31
    _, cps, chunk = plan_slices(n, 64, 1, 1024)
    mesh = Mesh(np.array(topo.devices[:4]), ("step",))
    A = (_sds((n, n), jnp.float64, NamedSharding(mesh, PartitionSpec())),)
    ids = _sds((4, 1), jnp.int32, NamedSharding(mesh, PartitionSpec("step")))
    wave = Dm._wave_fn(mesh, cps, chunk, "dq_acc", "jnp", None)
    wave.lower(A, ids).compile()


@pytest.mark.parametrize("entry", ["baseline", "batched", "grid"])
def test_pallas_dense_f32_compiles_with_mosaic(one_chip, entry):
    n, n_pad = 24, 24
    TB, C, Wu, blocks = DEFAULT_GEOMETRY.kernel_geometry(n)
    geom = dict(n=n, TB=TB, C=C, Wu=Wu, num_blocks=blocks, interpret=False)
    if entry == "grid":
        fn = lambda A, x: ryser_pallas_call_batched(A, x, mode="batched",
                                                    **geom)
        args = (_sds((4, n_pad, n_pad), jnp.float32, one_chip),
                _sds((4, n_pad, 1), jnp.float32, one_chip))
    else:
        fn = lambda A, x: ryser_pallas_call(A, x, 0, mode=entry, **geom)
        args = (_sds((n_pad, n_pad), jnp.float32, one_chip),
                _sds((n_pad, 1), jnp.float32, one_chip))
    hlo = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in hlo            # a Mosaic kernel, not XLA ops
