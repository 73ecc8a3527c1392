"""Counter-mode keystream cipher Pallas TPU kernel.

TPU-side analogue of the DPU inline-encryption service (core.smartnic.
InlineCrypto): with device-direct placement, decrypt must run where the
bytes land. The DPU service and this kernel share the SAME PRF — a
murmur3-finalizer over (u32 word counter + nonce) — so the two sides are
bit-identical (tests/test_zero_copy_path.py proves `apply_into` against
`cipher_ref` at arbitrary block-absolute offsets) and bytes encrypted
inline by the DPU decrypt on-device:

    x   = (idx + nonce) * GOLDEN32 + key
    x  ^= x >> 16;  x *= 0x85EBCA6B
    x  ^= x >> 13;  x *= 0xC2B2AE35
    x  ^= x >> 16
    out = data ^ x

Fully parallel over u32 words: the grid streams (rows, 128) tiles
through VMEM with pure VPU work, so throughput is HBM-bound — the right
shape for an inline service.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
TILE_WORDS = 8 * LANES          # one (8, 128) u32 tile
DEFAULT_BLOCK = 2048            # u32 words per grid step
GOLDEN32 = 0x9E3779B9


def keystream_u32(idx: jax.Array, key: int, nonce: int) -> jax.Array:
    """The PRF, usable inside and outside the kernel. idx: u32 array."""
    x = (idx.astype(jnp.uint32) + jnp.uint32(nonce & 0xFFFFFFFF)) \
        * jnp.uint32(GOLDEN32) + jnp.uint32(key & 0xFFFFFFFF)
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    return x


def _cipher_kernel(x_ref, out_ref, *, key: int, nonce: int, rows: int):
    i = pl.program_id(0)
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    idx = ((i * rows + r) * LANES + lane).astype(jnp.uint32)
    out_ref[...] = x_ref[...] ^ keystream_u32(idx, key, nonce)


def cipher_tiles(words: jax.Array, key: int, nonce: int, *,
                 block: int = DEFAULT_BLOCK,
                 interpret: bool = False) -> jax.Array:
    """words: u32 (n_rows, 128), n_rows a multiple of block // 128 (block
    a multiple of TILE_WORDS). Returns XOR-ciphered words (same shape).
    Involution: applying twice restores the input."""
    n_rows, lanes = words.shape
    rows = block // LANES
    if lanes != LANES or block % TILE_WORDS or n_rows % rows:
        raise ValueError(f"words {words.shape} do not tile by {block}")
    kern = functools.partial(_cipher_kernel, key=key, nonce=nonce, rows=rows)
    spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    call = pl.pallas_call(
        kern, grid=(n_rows // rows,),
        in_specs=[spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n_rows, LANES), jnp.uint32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)))
    return call(words)
