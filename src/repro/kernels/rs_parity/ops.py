"""jit'd public wrappers for the GF(256) Reed-Solomon parity kernel.

`ec_encode` / `ec_decode` are the two legs the data path uses: the write
fan-out encodes k data cells into p parity cells, and degraded reads /
rebuild reconstruct missing data cells from any k survivors. Coefficient
matrices come from the numpy oracle (ref.py) and are passed traced, so
one compilation per (m, s, tile) shape serves every stripe and every
survivor subset.

Each call is one dispatch with one input transfer. The coefficient
matrix of a (leg, k, p, subset) key is put on the device once and kept
(`coeff_cache`); host cell rows go to the jitted program as they are,
so the jit's own argument handling makes their one H2D; and the result's
D2H is queued at dispatch (`copy_to_host_async`), so the caller's
`np.asarray` waits for a copy already in flight.
"""
from __future__ import annotations

import functools
import threading
from collections import OrderedDict
from typing import Callable, Hashable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.rs_parity import kernel as K
from repro.kernels.rs_parity import ref


class CoefficientCache:
    """Device-resident u8 coefficient matrices, built once per key and
    device. Bounded (least recently used goes first) and thread-safe;
    `hits` and `misses` count its engagement for the whole process.
    ec(4,2) needs 1 encode, 15 delta and a few dozen decode matrices of a
    few bytes each; the bound only stops an unbounded set of keys."""

    CAPACITY = 1024

    def __init__(self) -> None:
        self.hits = 0
        self.misses = 0
        self._lock = threading.Lock()
        self._mats: "OrderedDict[tuple, jax.Array]" = OrderedDict()

    def get(self, key: Hashable, device: Optional[jax.Device],
            build: Callable[[], np.ndarray]) -> jax.Array:
        full = (key, device)
        with self._lock:
            mat = self._mats.get(full)
            if mat is not None:
                self._mats.move_to_end(full)
                self.hits += 1
                return mat
            self.misses += 1
        mat = jax.device_put(np.asarray(build(), np.uint8), device)
        with self._lock:
            self._mats[full] = mat
            while len(self._mats) > self.CAPACITY:
                self._mats.popitem(last=False)
        return mat


coeff_cache = CoefficientCache()


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


def _as_u8(a):
    """`a` as the jitted call takes it: a jax.Array passes through (cast
    to u8 if it is not), anything else becomes a C-contiguous u8 ndarray,
    copied only where it is not one already."""
    if isinstance(a, jax.Array):
        return a if a.dtype == jnp.uint8 else a.astype(jnp.uint8)
    if isinstance(a, np.ndarray) and a.dtype == np.uint8 \
            and a.flags.c_contiguous:
        return a
    return np.ascontiguousarray(a, np.uint8)


def _device_of(cells) -> Optional[jax.Device]:
    """The device the call runs on: a single-device jax.Array's own, else
    the default device (where the jit puts host arguments)."""
    if isinstance(cells, jax.Array):
        devs = cells.devices()
        return next(iter(devs)) if len(devs) == 1 else None
    dflt = jax.config.jax_default_device
    return dflt if isinstance(dflt, jax.Device) else jax.devices(dflt)[0]


def _matmul(mat, cells, tile: int, interpret: Optional[bool]) -> jax.Array:
    """One dispatch of `_gf_matmul` on u8 inputs, its D2H queued."""
    if interpret is None:
        interpret = _interpret_default()
    m, s = mat.shape
    if cells.shape[0] != s:
        raise ValueError(f"matrix is {mat.shape} but got {cells.shape[0]} "
                         "cell rows")
    if m == 0 or cells.shape[1] == 0:
        return jnp.zeros((m, cells.shape[1]), jnp.uint8)
    eff = _effective_tile(cells.shape[1], tile, bool(interpret))
    out = _gf_matmul(mat, cells, m, s, eff, bool(interpret))
    out.copy_to_host_async()
    return out


def gf_matmul(mat, cells, *, tile: int = K.DEFAULT_TILE,
              interpret: Optional[bool] = None) -> jax.Array:
    """(m, s) u8 GF coefficient matrix times (s, L) u8 cell rows."""
    return _matmul(_as_u8(mat), _as_u8(cells), tile, interpret)


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
    cells = _as_u8(cells)
    k = cells.shape[0]
    mat = coeff_cache.get(("encode", k, p), _device_of(cells),
                          lambda: ref.cauchy_matrix(k, p))
    return _matmul(mat, cells, tile, interpret)


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
    idx = tuple(int(i) for i in cells_idx)
    if any(i < 0 or i >= k for i in idx):
        raise ValueError(f"touched cells {list(idx)} outside data range "
                         f"0..{k - 1}")
    deltas = _as_u8(deltas)
    if deltas.shape[0] != len(idx):
        raise ValueError(
            f"{deltas.shape[0]} delta rows for {len(idx)} touched cells")
    mat = coeff_cache.get(("delta", k, p, idx), _device_of(deltas),
                          lambda: ref.cauchy_matrix(k, p)[:, list(idx)])
    return _matmul(mat, deltas, tile, interpret)


def ec_decode(survivors, present: Sequence[int], k: int, p: int,
              missing: Optional[Sequence[int]] = None, *,
              tile: int = K.DEFAULT_TILE,
              interpret: Optional[bool] = None) -> jax.Array:
    """Reconstruct missing data cells from any k surviving cells.

    survivors: (k, L) u8 rows ordered as `present` (stripe indices 0..k+p-1,
    parity cells are k..). Returns (len(missing), L) u8 — by default every
    data cell not among the survivors, ascending."""
    present = tuple(int(i) for i in present)
    if missing is None:
        missing = [i for i in range(k) if i not in present]
    missing = tuple(int(i) for i in missing)
    survivors = _as_u8(survivors)
    if not missing:
        return jnp.zeros((0, survivors.shape[1]), jnp.uint8)
    mat = coeff_cache.get(
        ("decode", k, p, present, missing), _device_of(survivors),
        lambda: ref.decode_matrix(k, p, present, missing))
    return _matmul(mat, survivors, tile, interpret)
