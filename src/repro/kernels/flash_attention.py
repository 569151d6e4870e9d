"""Blockwise (flash) attention for TPU — causal / sliding-window / GQA.

Layout: q [B, H, T, D]; k, v [B, KV, S, D]; output [B, H, T, D].
Grid: (B, H, T/BQ, S/BK) with the KV axis innermost (the output block is
revisited across ki — "arbitrary" dimension semantics). Running max and
softmax denominator live in VMEM scratch; the standard rescale trick keeps
a single [BQ, D] fp32 accumulator.

VMEM budget per grid step (BQ=BK=128, D<=256, fp32): q/k/v blocks
3 * 128*256*4 = 384 KiB + acc 128 KiB + scores 64 KiB ~= 0.6 MiB — well
inside a v5e core's ~16 MiB VMEM, leaving room for double buffering.

Fully-masked (causal/window) blocks are skipped with ``pl.when``: the grid
stays static, the MXU work is saved.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale, causal, window, bq, bk, t_real, s_real, nk):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    off = s_real - t_real            # queries are the last T positions of S
    q0 = qi * bq
    k0 = ki * bk
    if causal:
        relevant = (q0 + off + bq - 1) >= k0
        if window > 0:
            relevant = jnp.logical_and(
                relevant, k0 + bk - 1 > q0 + off - window)
    else:
        relevant = True

    @pl.when(relevant)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [BQ, D]
        k = k_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = (kpos < s_real) & (qpos < t_real)
        if causal:
            mask &= kpos <= qpos + off
            if window > 0:
                mask &= kpos > qpos + off - window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.where(mask, jnp.exp(s - m_new[:, None]), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=1)
        v = v_ref[0, 0].astype(jnp.float32)                  # [BK, D]
        acc = acc_scr[...] * alpha[:, None] + jax.lax.dot(
            p, v, preferred_element_type=jnp.float32)
        m_scr[...] = m_new
        l_scr[...] = l_new
        acc_scr[...] = acc

    @pl.when(ki == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)[:, None]
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, bq=128, bk=128,
                    interpret=None):
    """q: [B,H,T,D]; k,v: [B,KV,S,D] (H % KV == 0). Returns [B,H,T,D]."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    B, H, T, D = q.shape
    KV, S = k.shape[1], k.shape[2]
    G = H // KV
    scale = float(1.0 / np.sqrt(D))
    bq_ = min(bq, T)
    bk_ = min(bk, S)
    tp = (-T) % bq_
    sp = (-S) % bk_
    if tp:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, tp), (0, 0)))
    if sp:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sp), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sp), (0, 0)))
    Tp, Sp = T + tp, S + sp
    nq, nk = Tp // bq_, Sp // bk_

    out = pl.pallas_call(
        functools.partial(_kernel, scale=scale, causal=causal, window=window,
                          bq=bq_, bk=bk_, t_real=T, s_real=S, nk=nk),
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j: (b, h, i, 0)),
            pl.BlockSpec((1, 1, bk_, D), lambda b, h, i, j: (b, h // G, j, 0)),
            pl.BlockSpec((1, 1, bk_, D), lambda b, h, i, j: (b, h // G, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq_, D), lambda b, h, i, j: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Tp, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),        # running max
            pltpu.VMEM((bq_,), jnp.float32),        # running denominator
            pltpu.VMEM((bq_, D), jnp.float32),      # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(q, k, v)
    return out[:, :, :T]
