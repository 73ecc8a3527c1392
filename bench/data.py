"""Seeded data in bulk: the generator's raw 64-bit words, viewed as
bytes, so that set-up pays well under a second per gigabyte."""
from __future__ import annotations

import numpy as np


def random_bytes(rng: np.random.Generator, n: int) -> np.ndarray:
    """`n` uniform bytes from `rng` (a writable uint8 array)."""
    return rng.bit_generator.random_raw(-(-n // 8)).view(np.uint8)[:n]


def bf16_weight_bytes(rng: np.random.Generator, n: int) -> np.ndarray:
    """`n` bytes of bf16 weights: uniform sign and mantissa, exponent
    uniform over 2^-15..2^0 (the span of trained weights and norms), so
    every value is a finite, normal number as in a real checkpoint. The
    exponent's top four bits are fixed to 0111 in each little-endian
    high byte."""
    words = rng.bit_generator.random_raw(-(-n // 8))
    words &= np.uint64(0x87FF87FF87FF87FF)
    words |= np.uint64(0x3800380038003800)
    return words.view(np.uint8)[:n]
