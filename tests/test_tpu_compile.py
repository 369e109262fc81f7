"""The state plane's Pallas kernels compile for a TPU v5e, at real widths.

Nothing runs: the TPU compiler that ships with jax compiles for a chip
that is described, not attached, so a kernel the chip would refuse fails
here.  Every program is checked for ``tpu_custom_call`` — proof that the
Pallas kernel, not an XLA fallback, is what the chip would run.

The topology is described inside a fixture (never at import time), so
every test worker collects the same tests and only the worker that runs
this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.hash_delta import ops
from repro.kernels.hash_delta.kernel import (
    block_hash_compare_kernel, block_hash_kernel,
)
from repro.kernels.quant_blockwise import ops as qops
from repro.kernels.quant_blockwise.kernel import (
    dequantize_kernel, quantize_kernel,
)

GIB_ROWS = (1 << 30) // (4 * ops.BLOCK)      # 1 GiB of uint32 block rows


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args, **static):
    return jax.jit(fn, static_argnames=tuple(static)).lower(
        *args, **static).compile()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# odd row counts exercise a partial last tile and a whole-array tile
@pytest.mark.parametrize("rows", [GIB_ROWS, 1000, 5])
def test_hash_kernels_compile_for_v5e(one_chip, rows):
    x = _spec(one_chip, (rows, ops.BLOCK), jnp.uint32)
    w = _spec(one_chip, (ops.LANES, ops.BLOCK), jnp.uint32)
    prior = _spec(one_chip, (rows, ops.LANES), jnp.uint32)
    has = _spec(one_chip, (rows, 1), jnp.uint32)
    for c in (_compile(block_hash_kernel, x, w),
              _compile(block_hash_compare_kernel, x, w, prior, has)):
        assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_quant_kernels_compile_for_v5e(one_chip, dtype):
    x = _spec(one_chip, (GIB_ROWS, ops.BLOCK), dtype)
    q = _spec(one_chip, (GIB_ROWS, ops.BLOCK), jnp.int8)
    s = _spec(one_chip, (GIB_ROWS,), jnp.float32)
    c = _compile(quantize_kernel, x)
    assert "tpu_custom_call" in c.as_text()
    c = _compile(lambda q, s: dequantize_kernel(q, s, dtype), q, s)
    assert "tpu_custom_call" in c.as_text()


def test_capture_programs_compile_for_v5e(one_chip):
    """The jitted programs the capture path launches on a TPU, at the size
    of one batch: a manifest grid, a chunk-key byte grid with its prior
    compare, and a quantized leaf."""
    rows = ops.BATCH_ROWS
    grid = _spec(one_chip, (rows, ops.BLOCK), jnp.uint32)
    idx = _spec(one_chip, (rows,), jnp.uint32)
    seg = _spec(one_chip, (rows,), jnp.int32)
    c = ops._batched_lanes.lower(grid, idx, seg, num_segments=64,
                                 mode="pallas").compile()
    assert "tpu_custom_call" in c.as_text()
    u8 = _spec(one_chip, (rows * ops.BLOCK,), jnp.uint8)
    prior = _spec(one_chip, (rows, ops.LANES), jnp.uint32)
    has = _spec(one_chip, (rows, 1), jnp.uint32)
    c = ops._packed_lanes_compare.lower(u8, prior, has,
                                        mode="pallas").compile()
    assert "tpu_custom_call" in c.as_text()
    leaf = _spec(one_chip, (48, 1024, 2048), jnp.bfloat16)
    c = qops._quantize.lower(leaf, mode="pallas").compile()
    assert "tpu_custom_call" in c.as_text()
