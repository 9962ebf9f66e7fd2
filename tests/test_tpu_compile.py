"""The main-path Pallas kernels compile for a TPU v5e at real widths.

Compiled ahead of time against a described ``v5e:2x2`` topology: no chip
is needed, and what Mosaic or XLA would refuse on the chip (VMEM overflow,
blocks off the (8, 128) tiling, unlowerable ops) fails here.  Interpret
mode is off explicitly, so the CPU backend's interpreter is never used.
The topology is described inside a fixture, never at import: only one
process may load the TPU library at a time.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import (axpy_op, dilate_op, dot_partials_op, gemv_op,
                           knn_op, matmul_op)
from repro.kernels.stencil_dilate.kernel import RESIDENT_BLOCKS
from repro.kernels.vmem import block_rows_for


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:        # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # A compile for a described chip cannot be read back from the
    # persistent cache without one; keep the cache out of these tests.
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


N_VEC = 1 << 26                   # axpy/dot vectors (apps.axpy.N_FULL)
LANES = 1024
M_GEMV = 1 << 13                  # apps.gemv.M_FULL
GRID = 4096                       # apps.stencil.GRID

# name -> (fn, argument shapes): each fn runs its kernel with interpret off.
KERNELS = {
    "dilate": (lambda x: dilate_op(x, iters=16, interpret=False),
               [((GRID, GRID), jnp.float32)]),
    "axpy": (lambda a, x, y: axpy_op(a, x, y, interpret=False),
             [((), jnp.float32), ((N_VEC // LANES, LANES), jnp.float32),
              ((N_VEC // LANES, LANES), jnp.float32)]),
    "matmul": (lambda a, b: matmul_op(a, b, interpret=False),
               [((4096, 4096), jnp.bfloat16), ((4096, 4096), jnp.bfloat16)]),
    "knn": (lambda q, x: knn_op(q, x, k=10, interpret=False),
            [((128, 16), jnp.float32), ((4_000_000, 16), jnp.float32)]),
    # One blue's shard of the 4M-point design: 55,556 points padded to
    # whole 512-point blocks.
    "knn_shard": (lambda q, x: knn_op(q, x, k=10, block_n=512, n_valid=55556,
                                      interpret=False),
                  [((128, 16), jnp.float32), ((55808, 16), jnp.float32)]),
    "gemv": (lambda A, x: gemv_op(A, x, interpret=False),
             [((M_GEMV, M_GEMV), jnp.float32), ((1, M_GEMV), jnp.float32)]),
    "dot_partials": (lambda x, y: dot_partials_op(x, y, interpret=False),
                     [((N_VEC // LANES, LANES), jnp.float32),
                      ((N_VEC // LANES, LANES), jnp.float32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = KERNELS[name]
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: no Pallas kernel in the compiled program"


def test_dilate_block_at_paper_grid():
    """At W = 4096 f32 the four double-buffered blocks fit at 64 rows; a
    request for 128 is refused before it reaches the compiler."""
    assert block_rows_for(GRID, GRID * 4, RESIDENT_BLOCKS) == 64
    img = jax.ShapeDtypeStruct((GRID, GRID), jnp.float32)
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(lambda x: dilate_op(x, block_rows=128,
                                           interpret=False), img)
