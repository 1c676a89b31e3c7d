"""The ingest path's Pallas kernels, compiled for a described TPU v5e.

Each case lowers one kernel at a width users run and compiles it for the
first chip of a described ``v5e:2x2`` topology, with no chip attached, then
asserts that the compiled program holds the Mosaic kernel
(``tpu_custom_call``). What the TPU compiler refuses (an unaligned slice,
more VMEM or SMEM than a kernel may use) fails here, at no chip time.

The topology is described inside a module fixture, never while a module is
imported, and every case skips where it cannot be described. The compiles
stay in this process: the TPU library, once loaded here, is held until the
process exits.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

ROWS = 2048   # the ingest path's batch


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler, or it cannot load
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def shape(topo):
    """shape((rows, cols), dtype) → an abstract argument on the first chip."""
    from jax.sharding import SingleDeviceSharding

    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


def assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def kept(p: int, gamma: float = 0.05) -> int:
    return round(gamma * p)


@pytest.mark.parametrize("p", [512, 1024, 2048, 4096, 1 << 15])
def test_fwht_single_tile_compiles(shape, p):
    from repro.kernels import fwht

    assert p <= fwht.MAX_P_SINGLE
    assert_kernel_compiles(lambda x, d: fwht.hd_precondition(x, d),
                           shape((ROWS, p)), shape((p,)))


def test_fwht_chunked_compiles(shape):
    from repro.kernels import fwht

    p = 1 << 16
    assert p > fwht.MAX_P_SINGLE
    assert_kernel_compiles(lambda x, d: fwht.hd_precondition(x, d),
                           shape((ROWS, p)), shape((p,)))


@pytest.mark.parametrize("p", [1024, 1 << 15])
def test_sketch_fused_compiles(shape, p):
    from repro.kernels import sketch_fused

    assert_kernel_compiles(lambda x, d, i: sketch_fused.sketch_fused(x, d, i),
                           shape((ROWS, p)), shape((p,)),
                           shape((ROWS, kept(p)), jnp.int32))


@pytest.mark.parametrize("op", ["spmm", "spmm_t"])
def test_spmm_compiles(shape, op):
    from repro.kernels import spmm

    p, ell, m = 1 << 16, 128, kept(1 << 16)
    vals, idx = shape((ROWS, m)), shape((ROWS, m), jnp.int32)
    if op == "spmm":
        assert_kernel_compiles(lambda v, i, d: spmm.spmm(v, i, d),
                               vals, idx, shape((p, ell)))
    else:
        assert_kernel_compiles(lambda v, i, t: spmm.spmm_t(v, i, t, p),
                               vals, idx, shape((ROWS, ell)))


def test_sparse_assign_compiles(shape):
    from repro.kernels import sparse_assign

    p, k, m = 1024, 10, kept(1024)
    assert_kernel_compiles(lambda v, i, c: sparse_assign.sparse_assign(v, i, c),
                           shape((ROWS, m)), shape((ROWS, m), jnp.int32),
                           shape((k, p)))
