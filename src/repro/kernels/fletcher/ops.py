"""jit'd public wrapper for the Fletcher-wide checksum kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fletcher import kernel as K


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


def _tile_block(n: int, block: int) -> int:
    """Words per grid step: `block` capped at the padded input, rounded
    up to whole (8, 128) u32 tiles."""
    t = K.TILE_WORDS
    return -(-min(block, max(n, 1)) // t) * t


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _checksum_words(words: jax.Array, block: int, interpret: bool):
    n = words.shape[0]
    blk = _tile_block(n, block)
    pad = (-n) % blk
    w = jnp.pad(words.astype(jnp.uint32), (0, pad))
    parts = K.fletcher_tiles(w.reshape(-1, K.LANES), n_total=n, block=blk,
                             interpret=interpret)
    return jnp.sum(parts.reshape(2, -1), axis=1, dtype=jnp.uint32)


def fletcher_checksum(x: jax.Array, *, block: int = K.DEFAULT_BLOCK,
                      interpret: Optional[bool] = None) -> jax.Array:
    """Checksum of any array's underlying words. Returns (2,) u32 [s1,s2].

    Non-u32 inputs are bitcast/flattened to u32 words (u8 arrays are padded
    to a 4-byte multiple)."""
    if interpret is None:
        interpret = _interpret_default()
    flat = x.reshape(-1)
    if flat.dtype == jnp.uint32:
        words = flat
    elif flat.dtype == jnp.uint8:
        pad = (-flat.shape[0]) % 4
        flat = jnp.pad(flat, (0, pad))
        words = jax.lax.bitcast_convert_type(
            flat.reshape(-1, 4), jnp.uint32).reshape(-1)
    else:
        itemsize = flat.dtype.itemsize
        if itemsize >= 4:
            words = jax.lax.bitcast_convert_type(
                flat.reshape(-1, itemsize // 4 if itemsize > 4 else 1),
                jnp.uint32).reshape(-1)
        else:
            u8 = jax.lax.bitcast_convert_type(
                flat.reshape(-1, 1), jnp.uint8).reshape(-1)
            pad = (-u8.shape[0]) % 4
            u8 = jnp.pad(u8, (0, pad))
            words = jax.lax.bitcast_convert_type(
                u8.reshape(-1, 4), jnp.uint32).reshape(-1)
    return _checksum_words(words, block, bool(interpret))


def packed(csum: jax.Array) -> int:
    """[s1, s2] u32 -> python int (s2 << 32) | s1 (matches ref.fletcher_np)."""
    import numpy as np
    a = np.asarray(csum, np.uint64)
    return (int(a[1]) << 32) | int(a[0])
