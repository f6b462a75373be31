"""Ahead-of-time compiles of the Mosaic combine kernels for a TPU v5e.

No chip is needed: the TPU compiler compiles for a described ``v5e:2x2``
topology and raises what the chip's compiler would raise (a block that
does not fit VMEM, a misaligned slice), which interpret mode cannot see.
Each case compiles one kernel at the tile the dispatch picks for its nx
(`kalman_combine.block_rows`), at the smallest batch that still gives
that tile and two grid steps.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.types import FilteringElement, SmoothingElement
from repro.kernels.kalman_combine import kalman_combine as kc

SCENARIO_NX = (1, 2, 4, 5, 8)
_MATRIX_FIELDS = ("A", "C", "J", "E", "L")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one; keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _element_shapes(cls, B, nx, sharding):
    return cls(*(jax.ShapeDtypeStruct(
        (B, nx, nx) if f in _MATRIX_FIELDS else (B, nx), jnp.float32,
        sharding=sharding) for f in cls._fields))


@pytest.mark.parametrize("nx", SCENARIO_NX)
@pytest.mark.parametrize("kind", ["filtering", "smoothing"])
def test_combine_kernel_compiles_for_v5e(kind, nx, one_chip,
                                         no_persistent_cache):
    cls, fn = {
        "filtering": (FilteringElement, kc.filtering_combine_batched),
        "smoothing": (SmoothingElement, kc.smoothing_combine_batched),
    }[kind]
    B = 2 * kc.block_rows(nx)
    ei = _element_shapes(cls, B, nx, one_chip)
    compiled = jax.jit(lambda a, b: fn(a, b, interpret=False)).lower(
        ei, ei).compile()
    assert "tpu_custom_call" in compiled.as_text()
