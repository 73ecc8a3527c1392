"""Fletcher-style wide end-to-end checksum Pallas TPU kernel.

The DAOS-side extent checksums (media.checksum / CRC32 on the storage
server) have a TPU-resident analogue for device-direct placement: when
tensor data lands in device memory without host mediation, integrity
verification must also run on-device. CRC's bit-serial polynomial division
does not vectorize on the VPU, so we use the standard wide-word Fletcher
construction over u32 words, which admits a closed-form block decomposition:

    s1 = sum_i w_i                 (mod 2^32)
    s2 = sum_i (N - i) * w_i       (mod 2^32)

Both sums vectorize perfectly, and a block at base offset p contributes
    s1 += sum_l w_l
    s2 += sum_l (N - p - l) * w_l
so the grid streams (rows, 128) u32 blocks HBM->VMEM while two
lane-parallel accumulators stay resident in the output blocks; the
wrapper folds them to two scalars. uint32 wraparound gives the mod for
free.
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


def _fletcher_kernel(x_ref, s1_ref, s2_ref, *, n_total: int, rows: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        s1_ref[...] = jnp.zeros(s1_ref.shape, jnp.uint32)
        s2_ref[...] = jnp.zeros(s2_ref.shape, jnp.uint32)

    w = x_ref[...]                                        # (rows, 128) u32
    r = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    idx = ((i * rows + r) * LANES + lane).astype(jnp.uint32)
    # words beyond n_total are zero-padded by the caller; weight*0 = 0 so
    # padding contributes nothing regardless of its (wrapped) weight.
    weight = jnp.uint32(n_total) - idx
    # lane-parallel partial sums stay resident in the output blocks
    # across the grid; the caller folds them to two scalars
    s1_ref[...] += w
    s2_ref[...] += w * weight


def fletcher_tiles(words: jax.Array, n_total: int, *,
                   block: int = DEFAULT_BLOCK,
                   interpret: bool = False) -> jax.Array:
    """words: u32 (n_rows, 128), zero-padded, n_rows a multiple of
    block // 128 (block a multiple of TILE_WORDS). Returns (2, rows, 128)
    u32 lane partials of [s1, s2] over the first n_total words; their
    sums mod 2^32 are the checksum."""
    n_rows, lanes = words.shape
    rows = block // LANES
    if lanes != LANES or block % TILE_WORDS or n_rows % rows:
        raise ValueError(f"words {words.shape} do not tile by {block}")
    kern = functools.partial(_fletcher_kernel, n_total=n_total, rows=rows)
    part = jax.ShapeDtypeStruct((rows, LANES), jnp.uint32)
    call = pl.pallas_call(
        kern, grid=(n_rows // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (0, 0))] * 2,
        out_shape=[part, part],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)))
    return jnp.stack(call(words))
