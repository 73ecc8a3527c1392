"""Flash-attention forward Pallas TPU kernel.

Online-softmax attention with explicit VMEM tiling, adapted to the TPU
memory hierarchy: q/k/v stream HBM->VMEM in (block_q x head_dim) /
(block_k x head_dim) tiles, the (block_q x block_k) score tile lives in
VMEM/VREGs and hits the MXU twice per step (q@k^T and p@v). The running
max/denominator (m, l) and the f32 accumulator persist in VMEM scratch
across the (sequential, innermost) kv grid dimension.

Supports: causal masking, local windows (RecurrentGemma), GQA (kv-head
index_map = h // group, so kv tiles are fetched once per group), logit
softcap, kv-side zero-padding to block multiples.

Block skipping: kv blocks entirely above the causal diagonal, entirely
below the local-attention window, or entirely in the padding are skipped
with pl.when (no MXU work, no scratch update).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCK_Q = 128
DEFAULT_BLOCK_K = 128
MASK_VALUE = -1e30          # finite: online-softmax rescaling evaporates it
LANES = 128                 # TPU vector lane count (scratch minor dim)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                m_scr, l_scr, acc_scr, *,
                scale: float, causal: bool, window: Optional[int],
                softcap: Optional[float], seq_k: int,
                block_q: int, block_k: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full(m_scr.shape, MASK_VALUE, jnp.float32)
        l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q0 = iq * block_q
    k0 = ik * block_k

    # -- block-level skip decisions (scalar, cheap) -------------------------
    run = k0 < seq_k                                   # padding blocks
    if causal:
        run = jnp.logical_and(run, k0 <= q0 + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k0 + block_k - 1 > q0 - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, :, 0, :].astype(jnp.float32) * scale      # (bq, D)
        k = k_ref[0, :, 0, :].astype(jnp.float32)              # (bk, D)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        if softcap is not None:
            s = jnp.tanh(s / softcap) * softcap

        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32,
                                             (block_q, block_k), 1)
        mask = kpos < seq_k
        if causal:
            mask = jnp.logical_and(mask, kpos <= qpos)
        if window is not None:
            mask = jnp.logical_and(mask, kpos > qpos - window)
        s = jnp.where(mask, s, MASK_VALUE)

        m_prev = m_scr[:, :1]                                   # (bq, 1)
        l_prev = l_scr[:, :1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                  # (bq, bk)
        corr = jnp.exp(m_prev - m_new)                          # (bq, 1)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)

        v = v_ref[0, :, 0, :].astype(jnp.float32)               # (bk, D)
        pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        acc_scr[...] = acc_scr[...] * corr + pv
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[:, :1], 1e-30)
        o_ref[0, :, 0, :] = (acc_scr[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0, :] = m_scr[:, 0] + jnp.log(l[:, 0])


def flash_attention_fwd(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        scale: float, causal: bool = True,
                        window: Optional[int] = None,
                        softcap: Optional[float] = None,
                        seq_k: Optional[int] = None,
                        block_q: int = DEFAULT_BLOCK_Q,
                        block_k: int = DEFAULT_BLOCK_K,
                        interpret: bool = False):
    """q (B,T,H,D); k,v (B,S,KH,D) with H % KH == 0. T, S already padded to
    block multiples by the caller; seq_k is the true (unpadded) kv length
    so padding keys are masked. Returns (out (B,T,H,D), lse (B,H,T))."""
    B, T, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    assert H % KH == 0, (H, KH)
    assert T % block_q == 0 and S % block_k == 0, (T, S, block_q, block_k)
    group = H // KH
    grid = (B, H, T // block_q, S // block_k)

    kern = functools.partial(
        _fwd_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, seq_k=seq_k if seq_k is not None else S,
        block_q=block_q, block_k=block_k)

    in_specs = [
        pl.BlockSpec((1, block_q, 1, D), lambda b, h, i, j: (b, i, h, 0)),
        pl.BlockSpec((1, block_k, 1, D),
                     lambda b, h, i, j, g=group: (b, j, h // g, 0)),
        pl.BlockSpec((1, block_k, 1, D),
                     lambda b, h, i, j, g=group: (b, j, h // g, 0)),
    ]
    out_specs = [
        pl.BlockSpec((1, block_q, 1, D), lambda b, h, i, j: (b, i, h, 0)),
        pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, h, i)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((B, T, H, D), q.dtype),
        jax.ShapeDtypeStruct((B, H, T), jnp.float32),
    ]
    scratch = [
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, LANES), jnp.float32),
        pltpu.VMEM((block_q, D), jnp.float32),
    ]
    params = pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))
    call = pl.pallas_call(
        kern, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape, scratch_shapes=scratch,
        interpret=interpret, compiler_params=params)
    return tuple(call(q, k, v))
