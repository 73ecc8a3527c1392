"""jit'd public wrappers for the GF(256) Reed-Solomon parity kernel.

`ec_encode` / `ec_decode` are the two legs the data path uses: the write
fan-out encodes k data cells into p parity cells, and degraded reads /
rebuild reconstruct missing data cells from any k survivors. Coefficient
matrices come from the numpy oracle (ref.py — table math is cheap at
(k, p) scale) and are passed traced, so one compilation per (m, s, tile)
shape serves every stripe and every survivor subset.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro.kernels.rs_parity import kernel as K
from repro.kernels.rs_parity import ref


def _interpret_default() -> bool:
    return jax.default_backend() != "tpu"


@functools.partial(jax.jit, static_argnames=("m", "s", "tile", "interpret"))
def _gf_matmul(mat: jax.Array, cells: jax.Array, m: int, s: int, tile: int,
               interpret: bool) -> jax.Array:
    n = cells.shape[1]
    pad = (-n) % tile
    x = jnp.pad(cells.astype(jnp.int32), ((0, 0), (0, pad)))
    nb = (n + pad) // tile
    x = x.reshape(s, nb, tile).transpose(1, 0, 2)         # (nb, s, tile)
    out = K.rs_matmul_tiles(mat.astype(jnp.int32), x, interpret=interpret)
    return out.transpose(1, 0, 2).reshape(m, nb * tile)[:, :n].astype(
        jnp.uint8)


def gf_matmul(mat, cells, *, tile: int = K.DEFAULT_TILE,
              interpret: Optional[bool] = None) -> jax.Array:
    """(m, s) u8 GF coefficient matrix times (s, L) u8 cell rows."""
    if interpret is None:
        interpret = _interpret_default()
    mat = jnp.asarray(mat, jnp.uint8)
    cells = jnp.asarray(cells, jnp.uint8)
    m, s = mat.shape
    if cells.shape[0] != s:
        raise ValueError(f"matrix is {mat.shape} but got {cells.shape[0]} "
                         "cell rows")
    if m == 0 or cells.shape[1] == 0:
        return jnp.zeros((m, cells.shape[1]), jnp.uint8)
    eff = _effective_tile(cells.shape[1], tile, bool(interpret))
    return _gf_matmul(mat, cells, m, s, eff, bool(interpret))


def _effective_tile(n: int, tile: int, interpret: bool) -> int:
    """Bytes of each cell per grid step for an n-byte cell row."""
    if interpret:
        # Interpret-mode grid steps carry heavy per-step overhead; one
        # lane-padded tile per cell keeps the XLA lowering to a single
        # fused elementwise chain (~100s of MB/s on CPU vs ~3 with 1 KiB
        # tiles). Real TPU lowering keeps the bounded VMEM tile instead.
        return min(2 << 20, -(-n // 128) * 128)
    return min(tile, max(128, n))


def ec_encode(cells, p: int, *, tile: int = K.DEFAULT_TILE,
              interpret: Optional[bool] = None) -> jax.Array:
    """(k, L) u8 data cells -> (p, L) u8 Reed-Solomon parity cells."""
    cells = jnp.asarray(cells, jnp.uint8)
    return gf_matmul(ref.cauchy_matrix(cells.shape[0], p), cells,
                     tile=tile, interpret=interpret)


def ec_parity_delta(k: int, p: int, cells_idx: Sequence[int], deltas, *,
                    tile: int = K.DEFAULT_TILE,
                    interpret: Optional[bool] = None) -> jax.Array:
    """Parity deltas for a partial-stripe overwrite (delta-parity RMW).

    GF(256) linearity: P'_j = P_j XOR sum_i C[j][i]*(old_i XOR new_i)
    over exactly the touched data cells, so a sub-stripe write updates
    parity without reading the untouched cells. `deltas` is
    (len(cells_idx), L) u8 rows of old XOR new media bytes; `cells_idx`
    the touched data-cell stripe indices (< k). Returns (p, L) u8 rows
    the parity targets XOR onto their stored cells (the engine-side
    `xor_apply` op) — bit-exact against a full re-encode (property-
    tested vs the ref.py oracle). Same Pallas tile kernel as `ec_encode`
    with the Cauchy column submatrix, interpret fallback included."""
    idx = list(cells_idx)
    if any(i < 0 or i >= k for i in idx):
        raise ValueError(f"touched cells {idx} outside data range 0..{k - 1}")
    deltas = jnp.asarray(deltas, jnp.uint8)
    if deltas.shape[0] != len(idx):
        raise ValueError(
            f"{deltas.shape[0]} delta rows for {len(idx)} touched cells")
    return gf_matmul(ref.cauchy_matrix(k, p)[:, idx], deltas,
                     tile=tile, interpret=interpret)


def ec_decode(survivors, present: Sequence[int], k: int, p: int,
              missing: Optional[Sequence[int]] = None, *,
              tile: int = K.DEFAULT_TILE,
              interpret: Optional[bool] = None) -> jax.Array:
    """Reconstruct missing data cells from any k surviving cells.

    survivors: (k, L) u8 rows ordered as `present` (stripe indices 0..k+p-1,
    parity cells are k..). Returns (len(missing), L) u8 — by default every
    data cell not among the survivors, ascending."""
    if missing is None:
        missing = [i for i in range(k) if i not in list(present)]
    survivors = jnp.asarray(survivors, jnp.uint8)
    if not missing:
        return jnp.zeros((0, survivors.shape[1]), jnp.uint8)
    return gf_matmul(ref.decode_matrix(k, p, present, missing), survivors,
                     tile=tile, interpret=interpret)
