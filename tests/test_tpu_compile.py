"""The serving kernels compile for a TPU v5e, checked without the chip.

Each test lowers a program at deployment widths (p = 4, k = 256, so the
packed width is W = 768; strips of 1024 corpus columns) and compiles it for
a described ``v5e:2x2`` topology with the TPU compiler that ships with
libtpu.  Interpret mode cannot catch what this does: Mosaic refusing a block
layout, a kernel that does not fit its fast memory, or a program that lost
its kernel.  Every compiled program must hold a ``tpu_custom_call``.

The topology is described inside a module fixture, never at import: only one
process may hold libtpu, so with several test workers the others would fail.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

from repro.core import registry
from repro.core.distributed import stacked_threshold_shards, stacked_topk_shards
from repro.core.sketch import SketchConfig
from repro.index.query import _fold_strips
from repro.kernels.pairwise_lp.kernel import pairwise_lp_call
from repro.kernels.power_project.kernel import power_project_call

W = 768          # (p - 1) k at p = 4, k = 256
COL_BLOCK = 1024  # the TPU engine's strip width
STACK_ROWS = 4 * COL_BLOCK


@pytest.fixture(scope="module")
def topo():
    # compiles for a described chip land in the persistent cache but can
    # never be read back without one: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu, or another process holds it
        jax.config.update("jax_enable_compilation_cache", was)
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def mesh_1x4(topo):
    return Mesh(np.asarray(topo.devices).reshape(1, 4), ("replica", "data"))


def _shape(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("rows", [64, 1024], ids=["q64", "strip1024"])
def test_pairwise_lp_compiles_for_v5e(one_chip, rows):
    args = (_shape((rows, W), one_chip), _shape((COL_BLOCK, W), one_chip),
            _shape((rows,), one_chip), _shape((COL_BLOCK,), one_chip))
    _assert_kernel(jax.jit(pairwise_lp_call).lower(*args).compile())


def test_segment_fold_compiles_for_v5e(one_chip):
    # one 65,536-row segment of packed factors, folded in one program with
    # the kernel inside the loop; the segment is an argument, never copied
    cfg = SketchConfig(p=4, k=256, block_d=960)
    spec = registry.resolve(registry.PLAIN, p=cfg.p,
                            projection=cfg.projection.family)
    n, q, k = 64 * COL_BLOCK, 64, 10
    args = (_shape((q, k), one_chip), _shape((q, k), one_chip, jnp.int32),
            (_shape((q, W), one_chip), _shape((q,), one_chip)),
            (_shape((n, W), one_chip), _shape((n,), one_chip)),
            _shape((n,), one_chip, jnp.bool_), _shape((), one_chip, jnp.int32))
    compiled = _fold_strips.lower(
        *args, cfg=cfg, spec=spec, backend="pallas", start=0, width=COL_BLOCK,
        n_strips=64, c=k, k=k).compile()
    _assert_kernel(compiled)
    assert compiled.memory_analysis().temp_size_in_bytes < n * W * 4 // 16


def test_power_project_compiles_for_v5e(one_chip):
    X = _shape((8192, 960), one_chip)
    R = _shape((960, 256), one_chip)
    fn = jax.jit(lambda X, R: power_project_call(X, R, (1, 2, 3)))
    _assert_kernel(fn.lower(X, R).compile())


def _stacked_args(mesh, q=64):
    rep = NamedSharding(mesh, P())
    blk = NamedSharding(mesh, P("data", None, None))
    row = NamedSharding(mesh, P("data", None))
    return (_shape((q, W), rep), _shape((q,), rep),
            _shape((4, STACK_ROWS, W), blk), _shape((4, STACK_ROWS), row),
            _shape((4, STACK_ROWS), row, jnp.bool_)), row, rep


def test_stacked_topk_compiles_for_v5e_1x4(mesh_1x4):
    args, row, _ = _stacked_args(mesh_1x4)
    pos = _shape((4, STACK_ROWS), row, jnp.int32)
    compiled = stacked_topk_shards.lower(
        *args, pos, mesh=mesh_1x4, top_k=10, col_block=COL_BLOCK,
        backend="pallas", data_axes=("data",)).compile()
    _assert_kernel(compiled)


def test_stacked_threshold_compiles_for_v5e_1x4(mesh_1x4):
    args, _, rep = _stacked_args(mesh_1x4)
    radius = _shape((), rep)
    compiled = stacked_threshold_shards.lower(
        *args, radius, mesh=mesh_1x4, relative=True, col_block=COL_BLOCK,
        backend="pallas", data_axes=("data",)).compile()
    _assert_kernel(compiled)
