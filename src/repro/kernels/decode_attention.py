"""Pallas TPU single-token decode attention over a KV cache.

Grid = (batch, kv_heads, kv_blocks): each program attends the G = H/K query
heads of one KV head against one cache block, carrying the online-softmax
state in VMEM scratch across the (sequential) kv_blocks dim. The GQA group
is processed natively — the cache is read once, NOT repeated, which is the
point of GQA at decode time (HBM-bandwidth-bound).

The cache is head-major (B, K, T, D), so a block (1, 1, block_k, D) keeps
the size-1 kv-head dim out of the two dims the TPU compiler tiles.

The current decode position arrives as a scalar-prefetch operand (SMEM) so
cache slots beyond ``pos`` are masked without host-side slicing.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _decode_kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref,
                   l_ref, *, scale: float, bk: int, nk: int, g: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0]
    k_start = ik * bk

    @pl.when(k_start <= pos)
    def _compute():
        q = q_ref[0, 0, :, :]                     # (g, d) query-head group
        k = k_ref[0, 0]                           # (bk, d)
        v = v_ref[0, 0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (g, bk)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (g, bk), 1)
        s = jnp.where(kpos <= pos, s, NEG_INF)
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
        o_ref[0, 0, :, :] = (acc_ref[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention(q, k_cache, v_cache, pos, *,
                     scale: Optional[float] = None, block_k: int = 256,
                     interpret: bool = False):
    """q: (B, H, D) one new token's queries; k/v_cache: (B, K, T, D);
    pos: scalar int32 (attend to cache[: pos+1]). Returns (B, H, D)."""
    b, h, d = q.shape
    kh, t = k_cache.shape[1], k_cache.shape[2]
    assert h % kh == 0
    g = h // kh
    assert t % block_k == 0, (t, block_k)
    scale = d ** -0.5 if scale is None else scale
    nk = t // block_k
    qg = q.reshape(b, kh, g, d)
    pos_arr = jnp.asarray(pos, jnp.int32).reshape(1)

    kernel = functools.partial(_decode_kernel, scale=scale, bk=block_k,
                               nk=nk, g=g)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, kh, nk),
            in_specs=[
                pl.BlockSpec((1, 1, g, d), lambda ib, ih, ik, pos: (ib, ih, 0, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, ik, pos: (ib, ih, ik, 0)),
                pl.BlockSpec((1, 1, block_k, d),
                             lambda ib, ih, ik, pos: (ib, ih, ik, 0)),
            ],
            out_specs=pl.BlockSpec((1, 1, g, d),
                                   lambda ib, ih, ik, pos: (ib, ih, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((g, d), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
                pltpu.VMEM((g,), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, kh, g, d), q.dtype),
        interpret=interpret,
    )(pos_arr, qg, k_cache, v_cache)
    return out.reshape(b, h, d)
