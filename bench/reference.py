"""Plain references for the comparisons that decide `correct`.

The GF(256) Reed-Solomon code is a copy of the store's documented code,
kept here so that a change to the program cannot move the yardstick:
systematic Reed-Solomon over GF(2^8) with the polynomial
x^8+x^4+x^3+x^2+1 (0x11D); the p parity rows are the Cauchy matrix
C[j][i] = 1/(x_j + y_i) with x_j = k + j and y_i = i. Multiplication is a
full 256 x 256 product table built from log/antilog tables, so a stripe
encodes with one table lookup per coefficient.

Nothing here imports the program.
"""
from __future__ import annotations

from typing import Sequence, Tuple

import numpy as np

GF_POLY = 0x11D


def _log_tables() -> Tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, np.int32)
    log = np.zeros(256, np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[:255]
    return exp, log


GF_EXP, GF_LOG = _log_tables()


def _product_table() -> np.ndarray:
    a = np.arange(256)
    prod = GF_EXP[GF_LOG[a][:, None] + GF_LOG[a][None, :]].astype(np.uint8)
    prod[0, :] = 0
    prod[:, 0] = 0
    return prod


GF_MUL = _product_table()          # GF_MUL[a, b] = a * b over GF(256)


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("gf_inv(0)")
    return int(GF_EXP[255 - GF_LOG[a]])


def cauchy_matrix(k: int, p: int) -> np.ndarray:
    """The (p, k) parity rows of ec(k, p)."""
    if k < 1 or p < 0 or k + p > 256:
        raise ValueError(f"ec({k},{p}) outside GF(256)")
    return np.array([[gf_inv((k + j) ^ i) for i in range(k)]
                     for j in range(p)], np.uint8).reshape(p, k)


def gf_matmul(mat: np.ndarray, cells: np.ndarray) -> np.ndarray:
    """(m, s) u8 coefficients times (s, L) u8 cell rows over GF(256)."""
    m, s = mat.shape
    if cells.shape[0] != s:
        raise ValueError(f"matrix is {mat.shape}, cells {cells.shape}")
    out = np.zeros((m, cells.shape[1]), np.uint8)
    for j in range(m):
        for i in range(s):
            out[j] ^= GF_MUL[int(mat[j, i])][cells[i]]
    return out


def rs_encode(cells: np.ndarray, p: int) -> np.ndarray:
    """(k, L) u8 data cells -> (p, L) u8 parity cells."""
    return gf_matmul(cauchy_matrix(cells.shape[0], p), cells)


def rs_parity_delta(k: int, p: int, cells_idx: Sequence[int],
                    deltas: np.ndarray) -> np.ndarray:
    """Parity deltas of a partial-stripe overwrite: `deltas` holds one
    (old XOR new) row per touched data cell; XORing the result onto the
    stored parity gives the parity of the new stripe."""
    idx = list(cells_idx)
    if any(i < 0 or i >= k for i in idx) or deltas.shape[0] != len(idx):
        raise ValueError(f"bad delta rows {deltas.shape} for cells {idx}")
    return gf_matmul(cauchy_matrix(k, p)[:, idx], deltas)


def stripe_cells(data: np.ndarray, k: int, p: int) -> np.ndarray:
    """The k + p cells a stripe of `data` bytes must store."""
    cells = np.asarray(data, np.uint8).reshape(k, -1)
    return np.concatenate([cells, rs_encode(cells, p)])


def as_bytes(a) -> np.ndarray:
    """The raw bytes of an array, flat (NaN payloads included)."""
    a = np.ascontiguousarray(np.asarray(a))
    return a.reshape(-1).view(np.uint8)


def bytes_differing(got, want) -> int:
    """Bytes of `want` that `got` does not reproduce; every byte counts as
    differing when shape or dtype differ."""
    got, want = np.asarray(got), np.asarray(want)
    if got.shape != want.shape or got.dtype != want.dtype:
        return int(want.nbytes)
    return int(np.count_nonzero(as_bytes(got) != as_bytes(want)))
