"""Compile the main path's kernels for a described TPU v5e, without a chip.

The TPU compiler is installed with JAX and compiles for a topology that is
described and not attached (``topologies.get_topology_desc``).  That catches
what interpret mode cannot: block shapes Mosaic refuses, VMEM overuse, a
program larger than the chip's 16 GB.  Nothing runs here, so nothing here
says anything about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

from repro.core import scan as scan_lib
from repro.kernels.pdist.pdist import pdist_pallas
from repro.kernels.qpath.qpath import qpath_matmul_pallas
from repro.kernels.topk.ops import tile_config
from repro.kernels.topk.topk import topk_pallas, topk_quant_pallas

M, N, D, K = 64, 1_000_000, 96, 10
V5E_HBM_BYTES = 16 * 1024**3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    assert topo.devices[0].device_kind == "TPU v5 lite"
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around these
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _kernel_compiles(fn, *shapes):
    compiled = _compile(fn, *shapes)
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("masked", [False, True])
def test_topk_f32_sqeuclidean_compiles(one_chip, masked):
    cfg = tile_config(M, N, D, K, "sqeuclidean")
    shapes = [_spec(one_chip, (M, D)), _spec(one_chip, (N, D))]
    if masked:
        shapes.append(_spec(one_chip, (N,), jnp.bool_))

    def fn(x, y, valid=None):
        return topk_pallas(x, y, k=K, metric="sqeuclidean", valid=valid,
                           interpret=False, **cfg)

    _kernel_compiles(fn, *shapes)


def test_topk_int8_compiles(one_chip):
    cfg = tile_config(M, N, D, K, "euclidean", quantized=True)

    def fn(q, codes, scales, sqnorms):
        return topk_quant_pallas(q, codes, scales, sqnorms, k=K,
                                 metric="euclidean", interpret=False, **cfg)

    _kernel_compiles(fn, _spec(one_chip, (M, D)),
                     _spec(one_chip, (N, D), jnp.int8),
                     _spec(one_chip, (D,)), _spec(one_chip, (N,)))


def test_topk_manhattan_compiles(one_chip):
    cfg = tile_config(M, N, D, K, "manhattan")

    def fn(x, y):
        return topk_pallas(x, y, k=K, metric="manhattan", interpret=False, **cfg)

    _kernel_compiles(fn, _spec(one_chip, (M, D)), _spec(one_chip, (N, D)))


@pytest.mark.parametrize("metric", ["sqeuclidean", "manhattan"])
def test_pdist_compiles(one_chip, metric):
    def fn(x, y):
        return pdist_pallas(x, y, metric=metric, interpret=False)

    _kernel_compiles(fn, _spec(one_chip, (1024, D)), _spec(one_chip, (4096, D)))


def test_qpath_minmax_compiles(one_chip):
    def fn(a, b):
        return qpath_matmul_pallas(a, b, mode="minmax", interpret=False)

    _kernel_compiles(fn, _spec(one_chip, (2048, 2048)),
                     _spec(one_chip, (2048, 2048)))


def test_jnp_topk_scan_fits_one_chip(one_chip):
    def fn(q, y):
        return scan_lib.topk_scan(q, y, k=K, metric="euclidean", impl="jnp")

    compiled = _compile(fn, _spec(one_chip, (M, D)), _spec(one_chip, (N, D)))
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert 0 < total < V5E_HBM_BYTES, total
