"""Plain reference for tensor-parallel loads: each chip's shard of every
tensor, sliced in numpy from the bytes written.

A sharding names, for every device it places on, the box of the tensor
that device holds (`devices_indices_map`, JAX's own description of the
layout). The placed array must hold on each such device exactly the
written tensor's bytes in that box, and nothing on a device or at a box
the sharding does not name for it.

The expected box is cut from the written bytes on the host and sent to
the shard's own device as unsigned words of the element's width; the
device counts the bytes in which the two differ, so only counts come
back to the host. Nothing here imports the program.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _words(dtype) -> np.dtype:
    """The unsigned integer type as wide as `dtype`'s elements."""
    return np.dtype(f"uint{8 * np.dtype(dtype).itemsize}")


@jax.jit
def _lane_differences(got: jax.Array, want: jax.Array) -> jax.Array:
    """For each byte lane of the element, the elements whose byte in that
    lane differs between `got` and `want` (its expected bytes as unsigned
    words of the same width)."""
    x = jax.lax.bitcast_convert_type(got, want.dtype) ^ want
    return jnp.stack([jnp.sum(((x >> (8 * k)) & 0xFF) != 0,
                              dtype=jnp.int32)
                      for k in range(want.dtype.itemsize)])


def _differing(got: jax.Array, want: np.ndarray):
    """Bytes of `want` that the device array `got` does not reproduce, as
    an int, or as per-lane counts still on the device; every byte counts
    as differing when shape or dtype differ."""
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(want.nbytes)
    expected = jax.device_put(
        np.ascontiguousarray(want).view(_words(want.dtype)),
        next(iter(got.devices())))
    return _lane_differences(got, expected)


def bytes_differing(got: jax.Array, want: np.ndarray) -> int:
    """Bytes of `want` that the device array `got` does not reproduce."""
    return int(np.asarray(_differing(got, want), np.int64).sum())


def _bounds(index: Tuple, shape: Tuple[int, ...]) -> Tuple:
    return tuple(sl.indices(n)[:2] for sl, n in zip(index, shape))


def compare(arr, want: np.ndarray, sharding) -> Tuple[int, int]:
    """(bytes differing, shards misplaced) of the placed array `arr`
    against `want`, the tensor written, under the requested `sharding`.

    Bytes differing: for each device the sharding names, the bytes of its
    box that the array's shard on that device does not reproduce (all of
    them when there is no such shard, or when the array's shape or dtype
    is not the tensor's). Shards misplaced: shards of the array on a
    device the sharding does not name, or at a box other than the one it
    names for that device."""
    boxes = sharding.devices_indices_map(want.shape)
    if tuple(arr.shape) != want.shape or arr.dtype != want.dtype:
        return sum(want[box].nbytes for box in boxes.values()), 0
    held = {s.device: s.data for s in arr.addressable_shards}
    counts = [want[box].nbytes if dev not in held
              else _differing(held[dev], want[box])
              for dev, box in boxes.items()]
    differing = sum(int(np.asarray(c, np.int64).sum())
                    for c in jax.device_get(counts))
    misplaced = sum(1 for s in arr.addressable_shards
                    if s.device not in boxes
                    or _bounds(s.index, want.shape)
                    != _bounds(boxes[s.device], want.shape))
    return differing, misplaced
