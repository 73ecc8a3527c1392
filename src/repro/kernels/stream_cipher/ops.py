"""jit'd public wrapper for the stream-cipher kernel."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.fletcher.ops import _tile_block
from repro.kernels.stream_cipher import kernel as K


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit,
                   static_argnames=("key", "nonce", "block", "interpret"))
def _cipher_words(words, key, nonce, block, interpret):
    n = words.shape[0]
    blk = _tile_block(n, block)
    pad = (-n) % blk
    w = jnp.pad(words.astype(jnp.uint32), (0, pad))
    out = K.cipher_tiles(w.reshape(-1, K.LANES), key, nonce, block=blk,
                         interpret=interpret)
    return out.reshape(-1)[:n]


def stream_cipher(x: jax.Array, key: int, nonce: int, *,
                  block: int = K.DEFAULT_BLOCK,
                  interpret: Optional[bool] = None) -> jax.Array:
    """XOR-cipher a u32 (or u8: handled by 4-byte packing) array.
    Involution: stream_cipher(stream_cipher(x)) == x."""
    if interpret is None:
        interpret = _interpret_default()
    if x.dtype == jnp.uint8:
        n = x.shape[0]
        pad = (-n) % 4
        w = jax.lax.bitcast_convert_type(
            jnp.pad(x, (0, pad)).reshape(-1, 4), jnp.uint32).reshape(-1)
        out = _cipher_words(w, int(key), int(nonce), int(block),
                            bool(interpret))
        u8 = jax.lax.bitcast_convert_type(
            out.reshape(-1, 1), jnp.uint8).reshape(-1)
        return u8[:n]
    assert x.dtype == jnp.uint32, x.dtype
    return _cipher_words(x.reshape(-1), int(key), int(nonce), int(block),
                         bool(interpret))
