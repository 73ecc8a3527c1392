"""Compile the device path's kernels for a described TPU v5e.

Interpret mode (every other kernel test here) cannot see what the chip's
compiler refuses: blocks off the (8, 128) tiling, too much VMEM, or a
program that does not fit HBM. These tests compile for a v5e that is
described, not attached, so they need the TPU compiler but no chip.
The topology is described inside a fixture (never at import) so that
every pytest-xdist worker collects the same tests and only the worker
running this file loads the TPU library.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.device_direct import _carve_packed
from repro.kernels.fletcher import ops as fletcher_ops
from repro.kernels.rs_parity import kernel as rs_kernel
from repro.kernels.rs_parity import ops as rs_ops
from repro.kernels.stream_cipher import ops as cipher_ops

STRIPE = 1 << 20                     # one EC stripe (a DFS block)
SLOT = 64 << 20                      # a device-direct ring slot


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    cache_on = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            t = topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield t
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _gf_compile(one_chip, m: int, s: int, n: int):
    tile = rs_ops._effective_tile(n, rs_kernel.DEFAULT_TILE, False)
    return rs_ops._gf_matmul.lower(
        _spec((m, s), jnp.uint8, one_chip),
        _spec((s, n), jnp.uint8, one_chip),
        m=m, s=s, tile=tile, interpret=False).compile()


# (k, p) geometries the fleet runs, on 1 MiB stripes: encode is (p, k)
# over k cells, decode rebuilds p lost data cells from k survivors, delta
# multiplies one touched cell's old XOR new by its Cauchy column
_PARITY = [(k, p, leg) for k, p in [(4, 2), (8, 3)]
           for leg in ("encode", "decode", "delta")]


@pytest.mark.parametrize("k,p,leg", _PARITY,
                         ids=[f"ec{k}{p}-{leg}" for k, p, leg in _PARITY])
def test_rs_parity_compiles_for_v5e(one_chip, k, p, leg):
    cs = STRIPE // k
    m, s = {"encode": (p, k), "decode": (p, k), "delta": (p, 1)}[leg]
    compiled = _gf_compile(one_chip, m, s, cs)
    assert "tpu_custom_call" in compiled.as_text()


def test_rs_parity_delta_unaligned_span_compiles(one_chip):
    """An IO500 ior-hard write (47,008 bytes) aligns with no cell."""
    compiled = _gf_compile(one_chip, 2, 1, 47_008)
    assert "tpu_custom_call" in compiled.as_text()


_CARVE = {
    "float32": [(np.float32, (4096, 4096))],
    "bfloat16": [(jnp.bfloat16, (8192, 4096))],
    "uint8": [(np.uint8, (SLOT,))],
    "mixed": [(np.float32, (2048, 4096)), (jnp.bfloat16, (4096, 2048)),
              (np.int32, (1024, 1024)), (np.uint8, (12 << 20,))],
}


@pytest.mark.parametrize("name", sorted(_CARVE))
def test_carve_packed_hbm_temp_within_slot(one_chip, name):
    """The carve of a full slot needs at most the slot's bytes of HBM
    temp (a bitcast through (n, itemsize) needed up to 128x)."""
    groups, layout, total = [], [], 0
    for dtype, shape in _CARVE[name]:
        dt = np.dtype(dtype)
        n = int(np.prod(shape))
        layout.append((len(groups), 0, shape))
        groups.append(_spec((n,), dt, one_chip))
        total += n * dt.itemsize
    assert total == SLOT
    compiled = _carve_packed.lower(tuple(groups), tuple(layout)).compile()
    assert compiled.memory_analysis().temp_size_in_bytes <= SLOT


def test_fletcher_compiles_for_v5e(one_chip):
    compiled = fletcher_ops._checksum_words.lower(
        _spec((1 << 20,), jnp.uint32, one_chip),
        block=fletcher_ops.K.DEFAULT_BLOCK, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_stream_cipher_compiles_for_v5e(one_chip):
    compiled = cipher_ops._cipher_words.lower(
        _spec((1 << 20,), jnp.uint32, one_chip), key=0xC0FFEE, nonce=42,
        block=cipher_ops.K.DEFAULT_BLOCK, interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()
