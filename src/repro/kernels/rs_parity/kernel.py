"""GF(256) Reed-Solomon matrix-multiply Pallas TPU kernel.

Both EC legs are one primitive: a small u8 coefficient matrix times a
stack of cell rows over GF(2^8) — encode multiplies the (p, k) Cauchy
rows by the k data cells, decode-from-survivors multiplies the inverted
survivor rows by any k surviving cells. Byte tables don't gather well on
the VPU (and u8 operands hit awkward (32, 128) tiling), so the kernel
keeps everything in i32 lanes and expands each coefficient multiply into
the 8-step carryless shift/xor form:

    prod = XOR_{bit in 0..7} [c>>bit & 1] * (v * x^bit mod 0x11D)

where `v * x mod poly` is `((v << 1) & 0xFF) ^ ((v >> 7) * 0x1D)` —
branch-free, fully lane-parallel, with static m x s x 8 unrolling
(m, s <= 11 for any practical ec(k,p)). The grid streams cell tiles
HBM->VMEM; each tile's stripe columns are independent so there is no
cross-step state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_TILE = 1024             # bytes of each cell per grid step


def _gf_cmul(c, v):
    """Traced scalar coefficient times i32 byte-lane vector over GF(256)."""
    prod = jnp.zeros_like(v)
    cur = v
    for bit in range(8):
        prod = prod ^ (cur * ((c >> bit) & 1))
        cur = ((cur << 1) & 0xFF) ^ (((cur >> 7) & 1) * 0x1D)
    return prod


def _rs_matmul_kernel(mat_ref, x_ref, out_ref, *, m: int, s: int):
    mat = mat_ref[...]                                    # (m, s) i32
    x = x_ref[0]                                          # (s, tile) i32
    rows = []
    for j in range(m):
        acc = jnp.zeros_like(x[0])
        for i in range(s):
            acc = acc ^ _gf_cmul(mat[j, i], x[i])
        rows.append(acc)
    out_ref[0] = jnp.stack(rows)


def rs_matmul_tiles(mat: jax.Array, x: jax.Array, *,
                    interpret: bool = False) -> jax.Array:
    """mat: i32 (m, s) GF coefficients in [0, 255]; x: i32 (nb, s, tile)
    cell bytes. Returns i32 (nb, m, tile) = mat x cells over GF(256),
    tile-by-tile."""
    nb, s, tile = x.shape
    m = mat.shape[0]
    kern = functools.partial(_rs_matmul_kernel, m=m, s=s)
    call = pl.pallas_call(
        kern, grid=(nb,),
        in_specs=[pl.BlockSpec((m, s), lambda i: (0, 0)),
                  pl.BlockSpec((1, s, tile), lambda i: (i, 0, 0))],
        out_specs=pl.BlockSpec((1, m, tile), lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, m, tile), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)))
    return call(mat, x)
