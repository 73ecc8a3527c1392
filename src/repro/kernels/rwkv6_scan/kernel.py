"""RWKV6 (Finch) chunked-WKV Pallas TPU kernel.

Per head, the recurrence over a (hd x hd) matrix state S with
data-dependent per-channel decay w_t in (0,1):

    y_t = r_t @ (diag-bonus u * k_t v_t^T + S_t)
    S_{t+1} = diag(w_t) S_t + k_t^T v_t

TPU adaptation of the chunk-parallel form: the grid walks (B, H, T/C)
with the chunk axis sequential; S persists in VMEM scratch across chunks.
Within a chunk all work is dense VMEM math that feeds the MXU:

    inter:  y += (r * exp(cumlw_prev)) @ S                   (C,hd)@(hd,hd)
    intra:  y[t] += sum_{s<t} (r_t . k_s . exp(cumlw_prev_t - cumlw_s)) v_s
            via the numerically-safe pairwise exponent (<= 0 for s < t),
            materialized as a (C,C,hd) VMEM tensor — C=32, hd<=128 keeps
            it under 2 MiB, well inside VMEM
    bonus:  y[t] += (r_t . u . k_t) v_t
    state:  S' = diag(exp(total)) S + (k * exp(total - cumlw))^T @ v

The pairwise form (exponent = cum_prev[t] - cum[s]) is what makes strong
decay safe: the factored exp(-cum) variant overflows, as noted in the
model-side wkv_chunked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 32


def _wkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                y_ref, sout_ref, s_scr, *, chunk: int):
    ic = pl.program_id(2)
    nc = pl.num_programs(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = s0_ref[0, 0].astype(jnp.float32)

    r = r_ref[0, :, 0, :].astype(jnp.float32)            # (C, hd)
    k = k_ref[0, :, 0, :].astype(jnp.float32)
    v = v_ref[0, :, 0, :].astype(jnp.float32)
    w = w_ref[0, :, 0, :].astype(jnp.float32)
    u = u_ref[0, :].astype(jnp.float32)                  # (hd,)

    lw = jnp.log(jnp.clip(w, 1e-12, 1.0))                # <= 0
    cum = jnp.cumsum(lw, axis=0)                         # inclusive
    cum_prev = cum - lw                                  # exclusive
    total = cum[-1:, :]                                  # (1, hd)

    s = s_scr[...]                                       # (hd, hd)
    # inter-chunk
    y = jax.lax.dot_general(r * jnp.exp(cum_prev), s,
                            (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    # intra-chunk, strictly causal, pairwise-stable exponent
    C = chunk
    e = cum_prev[:, None, :] - cum[None, :, :]           # (C, C, hd)
    tri = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1) \
        < jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)  # s < t
    e = jnp.where(tri[:, :, None], e, -jnp.inf)
    att = jnp.sum(r[:, None, :] * k[None, :, :] * jnp.exp(e), axis=-1)
    y = y + jax.lax.dot_general(att, v, (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
    # diagonal bonus
    coef = jnp.sum(r * u[None, :] * k, axis=-1, keepdims=True)   # (C,1)
    y = y + coef * v
    # state update
    k_dec = k * jnp.exp(total - cum)                     # (C, hd)
    s_new = jnp.exp(total)[0][:, None] * s + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    s_scr[...] = s_new

    y_ref[0, :, 0, :] = y.astype(y_ref.dtype)

    @pl.when(ic == nc - 1)
    def _final():
        sout_ref[0, 0] = s_new


def wkv_chunked_tiles(r, k, v, w, u, s0, *, chunk: int = DEFAULT_CHUNK,
                      interpret: bool = False):
    """r,k,v,w (B,T,H,hd) with T % chunk == 0; u (H,hd); s0 (B,H,hd,hd) f32.
    Returns (y (B,T,H,hd) f32, s_final (B,H,hd,hd) f32)."""
    B, T, H, hd = r.shape
    assert T % chunk == 0, (T, chunk)
    grid = (B, H, T // chunk)
    kern = functools.partial(_wkv_kernel, chunk=chunk)
    qspec = pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0))
    in_specs = [qspec, qspec, qspec, qspec,
                pl.BlockSpec((1, hd), lambda b, h, c: (h, 0)),
                pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0))]
    out_specs = [
        pl.BlockSpec((1, chunk, 1, hd), lambda b, h, c: (b, c, h, 0)),
        pl.BlockSpec((1, 1, hd, hd), lambda b, h, c: (b, h, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, T, H, hd), jnp.float32),
        jax.ShapeDtypeStruct((B, H, hd, hd), jnp.float32),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"))
    call = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        interpret=interpret, compiler_params=params)
    y, s = call(r, k, v, w, u, s0)
    return y, s
