"""v5e compile rehearsals of the main path's Pallas kernels.

Each test lowers and compiles one kernel wrapper for a TPU v5e chip that
is described, not attached (``jax.experimental.topologies``), at the
widths ``chip_smoke.py`` runs: B = 64 fields over a 2000-sensor network
(n + 1 = 2009 padded rows, D = 25 lanes, M = 208 members per color,
2-D positions), Q = 1024 queries, a 484-cell plan 78 candidates wide,
and 36410 conn anchors.  What Mosaic refuses here (block shapes, layouts,
in-kernel gathers) it refuses on the chip, at no chip time.  The test
asserts that the compiled program holds the kernel (``tpu_custom_call``):
an interpret-mode lowering would compile too, without it.

The topology is described inside a module-scoped fixture: one process at
a time may load the TPU compiler's library, so the call must never run
while a module is imported.  The persistent compilation cache is off
around these compiles (an entry written without a chip cannot be read
back).
"""

import functools

import pytest

import jax
import jax.numpy as jnp

B, N_ROWS, D, DIM, Q = 64, 2009, 25, 2, 1024
M = 208  # widest color class
CELLS, K_MAX = 484, 78
ANCHORS = 36410  # n + n_stream of the conn expansion


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", cache_on)
    compilation_cache.reset_cache()


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_color_step_compiles_for_v5e(one_chip):
    from repro.kernels.color_step import color_solve

    f32 = jnp.float32
    _compile(
        functools.partial(color_solve, interpret=False), one_chip,
        ((B, M, D, D), f32), ((B, M, D, D), f32), ((B, M, D), f32),
    )


@pytest.mark.parametrize("anchor_dtype", ["float32", "bfloat16"])
def test_knn_fuse_compiles_for_v5e(one_chip, anchor_dtype):
    from repro.kernels.knn_fuse import knn_fuse_fused

    f32, i32, i8 = jnp.float32, jnp.int32, jnp.int8
    cdt = None if anchor_dtype == "float32" else anchor_dtype
    _compile(
        functools.partial(
            knn_fuse_fused, k=3, compute_dtype=cdt, interpret=False
        ),
        one_chip,
        ((Q, DIM), f32), ((Q,), i32), ((CELLS, K_MAX), i32),
        ((CELLS, K_MAX), bool), ((N_ROWS, DIM), f32),
        ((B, N_ROWS, D, DIM), f32), ((B, N_ROWS, D), bool),
        ((B, N_ROWS, D), f32),
    )


def test_kernel_matvec_batched_compiles_for_v5e(one_chip):
    from repro.kernels import kernel_matvec

    f32 = jnp.float32
    _compile(
        functools.partial(kernel_matvec, gamma=1.0, interpret=False),
        one_chip,
        ((Q, DIM), f32), ((B, ANCHORS, DIM), f32), ((B, ANCHORS), f32),
    )
