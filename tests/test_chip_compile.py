"""Compile the main path's kernels for a described TPU v5e, no chip needed.

Interpret mode accepts kernels the chip's compiler refuses (unaligned
blocks, boolean loop carries, too much VMEM), so these tests hand the real
compiler the shapes ``chip_smoke.py`` produces. A passing compile is not a
chip run: nothing here executes or times anything.

The topology is described inside a module fixture: only the worker that
runs this file loads the TPU compiler, and a host that cannot describe it
skips these tests instead of failing to collect.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.lftj_jax import _count_chunked, _list_hits
from repro.kernels.intersect.kernel import intersect_count_pallas
from repro.kernels.intersect.ops import MAX_K, tile_rows
from repro.kernels.lftj_fused.kernel import (build_fused_count,
                                             build_fused_list)
from repro.kernels.lftj_fused.ops import (TILE_ROWS, VMEM_BUDGET_BYTES,
                                          _vmem_bytes)
from repro.kernels.triangle_dense.kernel import triangle_partials_pallas

TRIANGLE = ((0, 1), (0, 2), (1, 2))
FOUR_CLIQUE = ((0, 1), (0, 2), (0, 3), (1, 2), (1, 3), (2, 3))


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler on this host
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_for(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled, compiled.memory_analysis()


def assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("e,k", [(8192, 128), (8192, 1024), (8192, 4096),
                                 (1024, MAX_K)])
def test_intersect_compiles(one_chip, e, k):
    """The rotation kernel at the widths a degree-oriented Graph500 box
    reaches; the row tile shrinks with K so wide rows still fit VMEM."""
    be = tile_rows(e, k)
    compiled, mem = compile_for(
        lambda a, b: intersect_count_pallas(a, b, be=be),
        spec(one_chip, (e, k), jnp.int32), spec(one_chip, (e, k), jnp.int32))
    assert_kernel(compiled)
    assert mem.argument_size_in_bytes == 2 * e * k * 4


@pytest.mark.parametrize("nx,ny,d", [(256, 128, 512), (512, 384, 2048),
                                     (1024, 1024, 4096)])
def test_triangle_dense_compiles_multi_tile(one_chip, nx, ny, d):
    """Several (128, 128) output tiles per side, each writing an aligned
    (8, 128) int32 partial block."""
    compiled, mem = compile_for(
        lambda a, b, m: triangle_partials_pallas(a, b, m),
        spec(one_chip, (nx, d), jnp.float32),
        spec(one_chip, (ny, d), jnp.float32),
        spec(one_chip, (nx, ny), jnp.float32))
    assert_kernel(compiled)
    assert mem.output_size_in_bytes == (nx // 128) * 8 * ny * 4


def fused_args(sharding, widths, t=1024):
    args = [spec(sharding, (t, 1), jnp.int32)]
    for r, k in widths:
        args += [spec(sharding, (8, r), jnp.int32),
                 spec(sharding, (r, k), jnp.float32),
                 spec(sharding, (r, k), jnp.float32)]
    return args


def gate_tile(widths, n_vars, dims):
    """The frontier tile ``fused_count`` picks, or None if it refuses."""
    fits = [bt for bt in TILE_ROWS
            if _vmem_bytes(widths, (), n_vars, dims, bt) <= VMEM_BUDGET_BYTES]
    return fits[0] if fits else None


@pytest.mark.parametrize("n_vars,dims,r,k", [
    (3, TRIANGLE, 256, 128), (3, TRIANGLE, 1024, 256),
    (3, TRIANGLE, 2048, 128), (3, TRIANGLE, 512, 512),
    (3, TRIANGLE, 128, 1024),
    (4, FOUR_CLIQUE, 256, 128), (4, FOUR_CLIQUE, 512, 256),
    (4, FOUR_CLIQUE, 1024, 128)])
def test_fused_count_compiles_where_gate_admits(one_chip, n_vars, dims, r, k):
    """Every box width the VMEM gate admits compiles, at the tile it
    picks; the HBM arguments are exactly the slices the wrapper ships."""
    widths = tuple((r, k) for _ in dims)
    bt = gate_tile(widths, n_vars, dims)
    assert bt is not None
    call = build_fused_count(n_vars, dims, widths, (), bt, False)
    compiled, mem = compile_for(call, *fused_args(one_chip, widths))
    assert_kernel(compiled)
    shipped = 1024 * 4 + sum(8 * r * 4 + 2 * r * k * 4 for r, k in widths)
    assert mem.argument_size_in_bytes == shipped


@pytest.mark.parametrize("n_vars,dims,r,k", [
    (3, TRIANGLE, 2048, 128), (4, FOUR_CLIQUE, 1024, 256)])
def test_fused_count_gate_refusal_matches_compiler(one_chip, n_vars, dims,
                                                   r, k):
    """Where the gate refuses the full 128-row tile, the compiler refuses
    it too (scoped VMEM exhausted): the estimate is not loose there."""
    widths = tuple((r, k) for _ in dims)
    assert _vmem_bytes(widths, (), n_vars, dims, 128) > VMEM_BUDGET_BYTES
    call = build_fused_count(n_vars, dims, widths, (), 128, False)
    with pytest.raises(Exception, match="vmem"):
        compile_for(call, *fused_args(one_chip, widths))


def test_binary_lane_compiles(one_chip):
    """The jitted count of a large uniform box: a 256K-row, K = 1024
    padded slice and 2^20 edges in 2048-edge chunks. For the TPU each
    probe compares against the whole row; the (chunk, K, K) comparison
    stays inside one fused reduction and is never stored."""
    rows, k, m = (1 << 18) + 64, 1024, 1 << 20
    compiled, mem = compile_for(
        lambda npad, eu, ev: _count_chunked(npad, eu, ev, chunk=2048),
        spec(one_chip, (rows, k), jnp.int32),
        spec(one_chip, (m,), jnp.int32), spec(one_chip, (m,), jnp.int32))
    assert mem.argument_size_in_bytes == rows * k * 4 + 2 * m * 4
    assert mem.temp_size_in_bytes < 2048 * k


def test_binary_listing_compiles(one_chip):
    """``TriangleEngine.list``'s per-box program at the smoke's shape: the
    (edges × K) hit mask of a K = 512 slice in 2048-edge steps."""
    rows, k, m = 1 << 16, 512, 1 << 18
    compiled, mem = compile_for(
        lambda npad, eu, ev: _list_hits(npad, eu, ev, chunk=2048),
        spec(one_chip, (rows, k), jnp.int32),
        spec(one_chip, (m,), jnp.int32), spec(one_chip, (m,), jnp.int32))
    assert mem.output_size_in_bytes >= m * k
    assert mem.temp_size_in_bytes < 2048 * k


@pytest.mark.parametrize("n_vars,dims", [(3, TRIANGLE), (4, FOUR_CLIQUE)])
def test_fused_list_compiles(one_chip, n_vars, dims):
    """The fused listing program (one XLA program per box)."""
    r, k, t, cap = 1024, 256, 1024, 1 << 16
    call = build_fused_list(n_vars, dims, cap)
    args = [spec(one_chip, (t,), jnp.int32)]
    for _ in dims:
        args += [spec(one_chip, (r,), jnp.int32),
                 spec(one_chip, (r, k), jnp.int32)]
    compiled, mem = compile_for(call, *args)
    assert mem.output_size_in_bytes >= cap * n_vars * 4


def test_four_chip_programs_compile(topo):
    """``chip_smoke.py --chips 4``: the box-sharded count as one program
    over the four chips, and the fabric's int32-limb psum."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from repro.core.engine import sharded_count_program

    mesh = Mesh(np.asarray(topo.devices), ("boxes",))
    rows, k, m = (1 << 16) + 64, 512, 1 << 20

    def sharded(shape, spec):
        return jax.ShapeDtypeStruct(shape, jnp.int32,
                                    sharding=NamedSharding(mesh, spec))

    compiled, mem = compile_for(
        sharded_count_program(mesh, 2048),
        sharded((4, rows, k), P("boxes", None, None)),
        *[sharded((4, m), P("boxes", None)) for _ in range(3)])
    assert mem.argument_size_in_bytes == rows * k * 4 + 3 * m * 4

    fm = Mesh(np.asarray(topo.devices), ("shards",))
    psum = shard_map(lambda x: jax.lax.psum(jnp.sum(x, axis=0), "shards"),
                     mesh=fm, in_specs=P("shards"), out_specs=P())
    compiled, _ = compile_for(psum, jax.ShapeDtypeStruct(
        (4, 2), jnp.int32, sharding=NamedSharding(fm, P("shards"))))
    assert "all-reduce" in compiled.as_text()


def test_gate_grows_with_box_width():
    """A wider box never needs less VMEM (host-only check of the gate)."""
    base = _vmem_bytes(((512, 256),) * 3, (), 3, TRIANGLE, 128)
    assert _vmem_bytes(((1024, 256),) * 3, (), 3, TRIANGLE, 128) > base
    assert _vmem_bytes(((512, 512),) * 3, (), 3, TRIANGLE, 128) > base
    assert _vmem_bytes(((512, 256),) * 3, (), 3, TRIANGLE, 8) < base
