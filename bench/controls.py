"""Controls: the plain reference put in the program's place, one step
weaker than the configuration states. Each must come out not correct;
`bench/control.py` runs them on the chip and the tests at a small size.
The benchmark's own runs never install one.

  load   weights land in HBM through the reference loader (`pread`, then
         `jax.device_put`) after a round trip through float8_e4m3fn, the
         precision below the configuration's bf16.
  ec     parity comes from the reference GF(256) code with only its first
         parity row kept: ec(4,1) protection under an ec(4,2) label, which
         breaks "readable with any 2 targets down".
"""
from __future__ import annotations

from typing import Callable, Dict

import numpy as np

from bench import reference


def _fp8_read_tensors(self, reqs, **_kw):
    import jax
    import jax.numpy as jnp

    out = []
    for fd, off, shape, dtype in reqs:
        dtype = np.dtype(dtype)
        n = int(np.prod(shape)) * dtype.itemsize
        host = np.frombuffer(self.client.pread(fd, n, off), dtype)
        with np.errstate(invalid="ignore"):        # NaN payloads stay NaN
            low = host.astype(jnp.float8_e4m3fn).astype(dtype)
        low = low.reshape(shape)
        out.append(jax.device_put(low))
    jax.block_until_ready(out)
    return out


def _one_parity_row(parity: np.ndarray) -> np.ndarray:
    parity = parity.copy()
    parity[1:] = 0
    return parity


def _ec_encode(cells, p, **_kw):
    return _one_parity_row(reference.rs_encode(np.asarray(cells, np.uint8),
                                               p))


def _ec_parity_delta(k, p, cells_idx, deltas, **_kw):
    return _one_parity_row(reference.rs_parity_delta(
        k, p, cells_idx, np.asarray(deltas, np.uint8)))


def install(name: str) -> Callable[[], None]:
    """Put control `name` in place; returns the function that undoes it."""
    if name == "load":
        from repro.core.device_direct import DeviceDirectSink
        patches: Dict = {(DeviceDirectSink, "read_tensors"):
                         _fp8_read_tensors}
    elif name == "ec":
        from repro.kernels.rs_parity import ops as rs
        patches = {(rs, "ec_encode"): _ec_encode,
                   (rs, "ec_parity_delta"): _ec_parity_delta}
    else:
        raise KeyError(f"no control named {name!r}")
    saved = {key: getattr(*key) for key in patches}
    for (owner, attr), fn in patches.items():
        setattr(owner, attr, fn)

    def undo() -> None:
        for (owner, attr), fn in saved.items():
            setattr(owner, attr, fn)
    return undo


def control_for(traffic: Dict) -> str:
    """The control of a traffic mix's driver."""
    return "load" if traffic["driver"] == "load" else "ec"
