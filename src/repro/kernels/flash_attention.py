"""Pallas TPU flash-attention forward kernel.

Online-softmax attention with explicit BlockSpec VMEM tiling: the (S, T)
score matrix never leaves VMEM. Operands are head-major (B, H, S, D): the
TPU compiler tiles the last two block dims by (8, 128) or the full extent,
so the per-head block (1, 1, block_q, D) keeps the size-1 heads dim out of
the tiled pair. Grid = (batch, heads, q_blocks, kv_blocks);
TPU grids execute the trailing dim sequentially, so the running max / sum /
accumulator live in VMEM scratch across kv iterations. Causal and
sliding-window blocks that are fully masked are skipped with ``pl.when``
(compute predication) — the causal upper triangle costs nothing, unlike the
XLA fallback path.

Supports: causal, sliding window, logit softcap (gemma2), arbitrary scale.
GQA is handled by the ops.py wrapper (KV repeated to full heads — the
repeat is free on TPU: it lowers to re-reads of the same HBM tiles).

Validated in interpret mode against ref.ref_attention (tests/test_kernels).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  softcap: Optional[float], bq: int, bk: int, nk: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q_start = iq * bq
    k_start = ik * bk
    # block-level skip: fully-masked (above diagonal / outside window)
    relevant = jnp.asarray(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window:
        relevant &= k_start + bk - 1 > q_start - window

    @pl.when(relevant)
    def _compute():
        q = q_ref[0, 0]                            # (bq, d)
        k = k_ref[0, 0]                            # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        if softcap is not None:
            s = softcap * jnp.tanh(s / softcap)
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + pv
        m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _write():
        l = l_ref[...]
        l = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        softcap: Optional[float] = None,
                        scale: Optional[float] = None,
                        block_q: int = 128, block_k: int = 128,
                        interpret: bool = False):
    """q: (B, H, S, D); k, v: (B, H, T, D) (KV pre-repeated for GQA)."""
    b, h, s, d = q.shape
    t = k.shape[2]
    assert k.shape == (b, h, t, d) and v.shape == (b, h, t, d)
    assert s % block_q == 0 and t % block_k == 0, (s, t, block_q, block_k)
    scale = d ** -0.5 if scale is None else scale
    nq, nk = s // block_q, t // block_k

    kernel = functools.partial(
        _flash_kernel, scale=scale, causal=causal, window=window,
        softcap=softcap, bq=block_q, bk=block_k, nk=nk)

    return pl.pallas_call(
        kernel,
        grid=(b, h, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d), lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
            pl.BlockSpec((1, 1, block_k, d), lambda ib, ih, iq, ik: (ib, ih, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda ib, ih, iq, ik: (ib, ih, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
