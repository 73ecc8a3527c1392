"""Operations and bytes each device kernel's algorithm needs per call.

Counted from the call's shapes as the algorithm defines them, not as an
implementation lays them out: the GF(256) parity kernel reads and writes
u8 cells, so a share of its roofline is measured on the same work
whatever lanes a later kernel uses. GF(256) multiply-adds are table or
shift/xor work, not floating-point operations, so the bound is bytes.
"""
from __future__ import annotations

from typing import Sequence


def rs_encode_bytes(k: int, p: int, cell_len: int) -> int:
    """ec_encode: k data cells read, p parity cells written."""
    return (k + p) * cell_len


def rs_delta_bytes(touched: int, p: int, row_len: int) -> int:
    """ec_parity_delta: one delta row per touched cell read, p rows out."""
    return (touched + p) * row_len


def rs_call_bytes(fn_name: str, args: Sequence, kwargs: dict) -> int:
    """Algorithm bytes of one call of `rs_parity.ops.<fn_name>`, read
    from the call's own arguments."""
    if fn_name == "ec_encode":
        cells, p = args[0], args[1]
        k, n = _shape2(cells)
        return rs_encode_bytes(k, int(p), n)
    if fn_name == "ec_parity_delta":
        p, idx, deltas = args[1], args[2], args[3]
        return rs_delta_bytes(len(list(idx)), int(p), _shape2(deltas)[1])
    raise ValueError(f"no bytes function for rs_parity.{fn_name}")


def _shape2(a):
    shape = getattr(a, "shape", None)
    if shape is None or len(shape) != 2:
        raise ValueError(f"expected a 2-D cell array, got shape {shape}")
    return int(shape[0]), int(shape[1])
