"""Flash-attention backward Pallas TPU kernels.

Standard two-kernel scheme (recompute-from-lse, no O(T*S) residuals):

  delta = rowsum(dout * out)                       (jnp, cheap)
  p     = exp(q k^T * scale - lse)                 recomputed per tile
  dp    = dout v^T
  ds    = p * (dp - delta) * scale
  dq    = ds k          (dq kernel: kv-blocks sequential, dq in scratch)
  dk    = ds^T q        (dkv kernel: q-blocks sequential, dk/dv in scratch)
  dv    = p^T dout

Masking (causal / local window / kv padding) mirrors the forward kernel;
fully-masked tiles are skipped at block granularity. GQA: both kernels run
per q-head; the ops wrapper sums dk/dv over each kv-head's group.
Softcap is not supported here (the one softcap arch family is served by
the jnp-vjp fallback; documented in ops.py).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.flash_attention.kernel import MASK_VALUE


def _tile_p_ds(q, k, v, dout, lse_row, delta_row, *, scale, causal, window,
               seq_k, q0, k0, bq, bk):
    """Shared recompute: returns (p, ds) of shape (bq, bk), f32."""
    s = jax.lax.dot_general(q * scale, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
    qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = kpos < seq_k
    if causal:
        mask = jnp.logical_and(mask, kpos <= qpos)
    if window is not None:
        mask = jnp.logical_and(mask, kpos > qpos - window)
    s = jnp.where(mask, s, MASK_VALUE)
    p = jnp.exp(s - lse_row)                       # (bq, bk); masked -> ~0
    dp = jax.lax.dot_general(dout, v, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    ds = p * (dp - delta_row) * scale
    return p, ds


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, scale, causal, window, seq_k, block_q, block_k):
    iq, ik = pl.program_id(2), pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

    q0, k0 = iq * block_q, ik * block_k
    run = k0 < seq_k
    if causal:
        run = jnp.logical_and(run, k0 <= q0 + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k0 + block_k - 1 > q0 - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, :, 0, :].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :][:, None]            # (bq, 1)
        delta = delta_ref[0, 0, :][:, None]
        _, ds = _tile_p_ds(q, k, v, do, lse, delta, scale=scale,
                           causal=causal, window=window, seq_k=seq_k,
                           q0=q0, k0=k0, bq=block_q, bk=block_k)
        acc_scr[...] += jax.lax.dot_general(
            ds, k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ik == nk - 1)
    def _final():
        dq_ref[0, :, 0, :] = acc_scr[...].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_scr, dv_scr, *,
                scale, causal, window, seq_k, block_q, block_k):
    ik, iq = pl.program_id(2), pl.program_id(3)
    nq = pl.num_programs(3)

    @pl.when(iq == 0)
    def _init():
        dk_scr[...] = jnp.zeros(dk_scr.shape, jnp.float32)
        dv_scr[...] = jnp.zeros(dv_scr.shape, jnp.float32)

    q0, k0 = iq * block_q, ik * block_k
    run = k0 < seq_k
    if causal:
        run = jnp.logical_and(run, k0 <= q0 + block_q - 1)
    if window is not None:
        run = jnp.logical_and(run, k0 + block_k - 1 > q0 - window)

    @pl.when(run)
    def _body():
        q = q_ref[0, :, 0, :].astype(jnp.float32)
        k = k_ref[0, :, 0, :].astype(jnp.float32)
        v = v_ref[0, :, 0, :].astype(jnp.float32)
        do = do_ref[0, :, 0, :].astype(jnp.float32)
        lse = lse_ref[0, 0, :][:, None]
        delta = delta_ref[0, 0, :][:, None]
        p, ds = _tile_p_ds(q, k, v, do, lse, delta, scale=scale,
                           causal=causal, window=window, seq_k=seq_k,
                           q0=q0, k0=k0, bq=block_q, bk=block_k)
        dv_scr[...] += jax.lax.dot_general(
            p, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk_scr[...] += jax.lax.dot_general(
            ds, q, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(iq == nq - 1)
    def _final():
        dk_ref[0, :, 0, :] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0, :, 0, :] = dv_scr[...].astype(dv_ref.dtype)


def flash_attention_bwd(q, k, v, out, lse, dout, *, scale: float,
                        causal: bool, window: Optional[int],
                        seq_k: int, block_q: int, block_k: int,
                        interpret: bool = False):
    """q/out/dout (B,T,H,D) padded to block_q; k,v (B,S,KH,D) padded to
    block_k; lse (B,H,T). Returns (dq (B,T,H,D), dk, dv per *q-head*
    (B,S,H,D) — caller reduces GQA groups)."""
    B, T, H, D = q.shape
    S, KH = k.shape[1], k.shape[2]
    group = H // KH
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1).transpose(0, 2, 1)           # (B,H,T)

    common = dict(scale=scale, causal=causal, window=window, seq_k=seq_k,
                  block_q=block_q, block_k=block_k)
    pk = {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel",
                             "arbitrary"))}

    q_spec = pl.BlockSpec((1, block_q, 1, D), lambda b, h, i, j: (b, i, h, 0))
    q_spec_T = pl.BlockSpec((1, block_q, 1, D),
                            lambda b, h, j, i: (b, i, h, 0))
    kv_spec = pl.BlockSpec((1, block_k, 1, D),
                           lambda b, h, i, j, g=group: (b, j, h // g, 0))
    kv_spec_T = pl.BlockSpec((1, block_k, 1, D),
                             lambda b, h, j, i, g=group: (b, j, h // g, 0))
    row_spec = pl.BlockSpec((1, 1, block_q), lambda b, h, i, j: (b, h, i))
    row_spec_T = pl.BlockSpec((1, 1, block_q), lambda b, h, j, i: (b, h, i))

    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **common),
        grid=(B, H, T // block_q, S // block_k),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((B, T, H, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret, **pk)(q, k, v, dout, lse, delta)

    kv_out = pl.BlockSpec((1, block_k, 1, D), lambda b, h, j, i: (b, j, h, 0))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **common),
        grid=(B, H, S // block_k, T // block_q),
        in_specs=[q_spec_T, kv_spec_T, kv_spec_T, q_spec_T, row_spec_T,
                  row_spec_T],
        out_specs=[kv_out, kv_out],
        out_shape=[jax.ShapeDtypeStruct((B, S, H, D), q.dtype),
                   jax.ShapeDtypeStruct((B, S, H, D), q.dtype)],
        scratch_shapes=[pltpu.VMEM((block_k, D), jnp.float32),
                        pltpu.VMEM((block_k, D), jnp.float32)],
        interpret=interpret, **pk)(q, k, v, dout, lse, delta)
    return dq, dk, dv
