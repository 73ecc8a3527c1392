"""RG-LRU linear-recurrence Pallas TPU kernel.

Computes h_t = a_t * h_{t-1} + b_t over the time axis — the sequence-mixing
hot spot of RecurrentGemma/Griffin recurrent blocks.

TPU adaptation: the recurrence is memory-bound (2 streamed inputs, 1
streamed output, O(R) state), so the kernel tiles the channel axis R into
VMEM-resident (block_t x block_r) panels and keeps the running hidden
state in VMEM scratch across the sequential time-block grid dimension.
Within a tile the scan runs as a fori_loop of fused multiply-adds on
(block_r,)-wide vectors — VPU work between HBM streams; a within-tile
log-step doubling scan is the recorded hillclimb alternative (trades
O(block_t) serial steps for O(log block_t) full-tile passes).

a and b arrive in f32 (they are produced by f32 gate math upstream);
output h is f32, matching the model's `_lru_scan` oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_T = 256
DEFAULT_BLOCK_R = 256


def _rglru_kernel(a_ref, b_ref, h0_ref, h_ref, carry_scr, *, block_t: int):
    it = pl.program_id(2)

    @pl.when(it == 0)
    def _init():
        carry_scr[...] = h0_ref[0][None, :]                 # (1, br)

    a = a_ref[0]                                            # (bt, br) f32
    b = b_ref[0]

    def body(i, h):
        ai = jax.lax.dynamic_slice_in_dim(a, i, 1, 0)       # (1, br)
        bi = jax.lax.dynamic_slice_in_dim(b, i, 1, 0)
        h = ai * h + bi
        h_ref[0, pl.dslice(i, 1), :] = h
        return h

    carry_scr[...] = jax.lax.fori_loop(0, block_t, body, carry_scr[...])


def rglru_scan_tiles(a: jax.Array, b: jax.Array, h0: jax.Array, *,
                     block_t: int = DEFAULT_BLOCK_T,
                     block_r: int = DEFAULT_BLOCK_R,
                     interpret: bool = False) -> jax.Array:
    """a, b (B,T,R) f32 with T % block_t == 0 and R % block_r == 0;
    h0 (B,R) f32. Returns h (B,T,R) f32."""
    B, T, R = a.shape
    assert T % block_t == 0 and R % block_r == 0, (T, R, block_t, block_r)
    grid = (B, R // block_r, T // block_t)

    kern = functools.partial(_rglru_kernel, block_t=block_t)
    in_specs = [
        pl.BlockSpec((1, block_t, block_r), lambda b_, r, t: (b_, t, r)),
        pl.BlockSpec((1, block_t, block_r), lambda b_, r, t: (b_, t, r)),
        pl.BlockSpec((1, block_r), lambda b_, r, t: (b_, r)),
    ]
    out_spec = pl.BlockSpec((1, block_t, block_r), lambda b_, r, t: (b_, t, r))
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    call = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, R), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_r), jnp.float32)],
        interpret=interpret, compiler_params=params)
    return call(a, b, h0)
