"""Wrappers around the Pallas kernels.

On TPU the kernels compile and run natively; on the CPU backend they run in
interpret mode (the kernel bodies execute as jnp for correctness tests);
any other platform is an error. Wrappers take the models' (B, S, H, D)
layout, transpose to the kernels' head-major layout, and handle padding to
block multiples and the GQA repeat for the flash path.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import decode_attention as dec
from repro.kernels import flash_attention as fa
from repro.kernels import ssd as ssd_k


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"Pallas kernels run natively on TPU or interpreted on CPU; "
            f"backend {backend!r} is neither")
    return backend == "cpu"


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    softcap: Optional[float] = None,
                    scale: Optional[float] = None,
                    block_q: int = 128, block_k: int = 128):
    """q: (B,S,H,D); k,v: (B,T,K,D) with K | H (GQA repeat done here)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    if kh != h:
        k = jnp.repeat(k, h // kh, axis=2)
        v = jnp.repeat(v, h // kh, axis=2)
    bq = min(block_q, s) if s % min(block_q, s) == 0 else block_q
    bk = min(block_k, t) if t % min(block_k, t) == 0 else block_k
    pad_q = (-s) % bq
    pad_k = (-t) % bk
    if pad_q:
        q = jnp.pad(q, [(0, 0), (0, pad_q), (0, 0), (0, 0)])
    if pad_k:
        k = jnp.pad(k, [(0, 0), (0, pad_k), (0, 0), (0, 0)])
        v = jnp.pad(v, [(0, 0), (0, pad_k), (0, 0), (0, 0)])
        # padded kv slots must be masked: window/causal handle the tail only
        # if padding stays beyond every query position, which holds since
        # pads sit at kv positions >= t > any valid causal query position.
        assert causal or pad_k == 0, "non-causal padding needs a kv mask"
    hm = lambda a: jnp.swapaxes(a, 1, 2)        # (B,S,H,D) <-> (B,H,S,D)
    out = fa.flash_attention_fwd(
        hm(q), hm(k), hm(v), causal=causal, window=window, softcap=softcap,
        scale=scale, block_q=bq, block_k=bk, interpret=_interpret())
    return hm(out)[:, :s]


def decode_attention(q, k_cache, v_cache, pos, *,
                     scale: Optional[float] = None, block_k: int = 256):
    t = k_cache.shape[1]
    bk = min(block_k, t)
    if t % bk:
        pad = (-t) % bk
        k_cache = jnp.pad(k_cache, [(0, 0), (0, pad), (0, 0), (0, 0)])
        v_cache = jnp.pad(v_cache, [(0, 0), (0, pad), (0, 0), (0, 0)])
    return dec.decode_attention(
        q, jnp.swapaxes(k_cache, 1, 2), jnp.swapaxes(v_cache, 1, 2), pos,
        scale=scale, block_k=bk, interpret=_interpret())


def ssd(x, dt, A, B, C, *, chunk: int = 256):
    """SSD chunk scan. Shapes as repro.models.mamba2.ssd_chunked with
    ngroups == 1."""
    s = x.shape[1]
    ck = min(chunk, s)
    pad = (-s) % ck
    if pad:
        x = jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)])
        B = jnp.pad(B, [(0, 0), (0, pad), (0, 0), (0, 0)])
        C = jnp.pad(C, [(0, 0), (0, pad), (0, 0), (0, 0)])
    b, sp, h, _ = x.shape
    y, state = ssd_k.ssd_chunk_scan(
        jnp.swapaxes(x, 1, 2), jnp.swapaxes(dt, 1, 2).reshape(b, h, 1, sp),
        A, B[:, :, 0], C[:, :, 0], chunk=ck, interpret=_interpret())
    return jnp.swapaxes(y, 1, 2)[:, :s], state
