"""Shared model building blocks: norms, RoPE, GQA attention (sliding-window,
softcap, qk-norm, qkv-bias, cross-attention), gated/plain MLPs, embeddings.

All functions are pure; parameters are nested dicts produced from the
``*_param_specs`` declarations in :mod:`repro.models.common`.
"""
from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models import sharding
from repro.models.common import spec


class Ctx(NamedTuple):
    """Sharding context threaded through model code (None outside jit)."""

    mesh: object
    rules: sharding.Rules


def constrain(ctx: Optional[Ctx], x, axes):
    if ctx is None:
        return x
    return sharding.constrain(x, ctx.mesh, ctx.rules, axes)


# ------------------------------------------------------------------- norms

def rms_norm(x, scale, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x, scale, bias, eps: float):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32) + bias.astype(jnp.float32)).astype(dt)


def norm_param_specs(cfg: ModelConfig, dim: Optional[int] = None):
    d = dim or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": spec((d,), ("embed",), "ones"),
                "bias": spec((d,), ("embed",), "zeros")}
    return {"scale": spec((d,), ("embed",), "zeros")}  # (1 + scale) convention


def apply_norm(p, x, cfg: ModelConfig):
    if cfg.norm == "layernorm":
        return layer_norm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rms_norm(x, p["scale"], cfg.norm_eps)


# ------------------------------------------------------------------- rope

def rope(x, positions, theta: float):
    """Rotate-half RoPE. x: (B, S, H, D); positions: (B, S) or (S,)."""
    if theta <= 0:
        return x
    d = x.shape[-1]
    half = d // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    if positions.ndim == 1:
        positions = positions[None, :]
    angle = positions[..., None].astype(jnp.float32) * freq  # (B, S, half)
    cos = jnp.cos(angle)[:, :, None, :]
    sin = jnp.sin(angle)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(positions, d_model: int):
    """Whisper-style sinusoidal embedding. positions: (B, S) -> (B, S, d)."""
    half = d_model // 2
    freq = jnp.exp(-jnp.arange(half, dtype=jnp.float32) * (jnp.log(10_000.0) / (half - 1)))
    angle = positions[..., None].astype(jnp.float32) * freq
    return jnp.concatenate([jnp.sin(angle), jnp.cos(angle)], axis=-1)


# --------------------------------------------------------------- attention

def attn_param_specs(cfg: ModelConfig, cross: bool = False):
    d, h, k, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    p = {
        "wq": spec((d, h, hd), ("embed", "heads", "head_dim")),
        "wk": spec((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wv": spec((d, k, hd), ("embed", "kv_heads", "head_dim")),
        "wo": spec((h, hd, d), ("heads", "head_dim", "embed")),
    }
    if cfg.qkv_bias:
        p["bq"] = spec((h, hd), ("heads", "head_dim"), "zeros")
        p["bk"] = spec((k, hd), ("kv_heads", "head_dim"), "zeros")
        p["bv"] = spec((k, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm:
        p["q_norm"] = spec((hd,), ("head_dim",), "zeros")
        p["k_norm"] = spec((hd,), ("head_dim",), "zeros")
    return p


def _project_qkv(p, cfg: ModelConfig, x, kv_x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("btd,dhk->bthk", kv_x, p["wk"])
    v = jnp.einsum("btd,dhk->bthk", kv_x, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k, v


def attention_core(q, k, v, *, q_positions, kv_positions, causal: bool,
                   window: int, softcap: Optional[float], scale: float,
                   kv_mask=None, impl: str = "xla", q_chunk: int = 256):
    """Grouped-query attention.

    q: (B, S, H, D); k, v: (B, T, K, D). Returns (B, S, H, D).
    ``window`` 0 disables sliding-window masking. ``kv_mask`` optionally marks
    valid cache slots (B, T) or (T,).

    For q_len > q_chunk the computation is blocked over query chunks
    (lax.map + per-chunk remat) so the (S, T) score matrix never fully
    materialises — the XLA analogue of the Pallas flash kernel's VMEM tiling.
    """
    if impl == "pallas":  # pragma: no cover - TPU path, validated in kernels tests
        from repro.kernels import ops as kops

        return kops.flash_attention(
            q, k, v, causal=causal, window=window, softcap=softcap, scale=scale
        )
    kh = k.shape[2]
    if kh != q.shape[2]:
        # Repeat KV to full heads: keeps the `heads` dim shardable over the
        # model axis (a (kh, groups) reshape would break the 16-way shard and
        # make GSPMD insert per-block all-reduces). Done BEFORE query
        # chunking so the dK/dV group-reduction happens once per layer, not
        # once per chunk.
        g = q.shape[2] // kh
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    if q_chunk and q.shape[1] > q_chunk:
        return _chunked_attention(
            q, k, v, q_positions=q_positions, kv_positions=kv_positions,
            causal=causal, window=window, softcap=softcap, scale=scale,
            kv_mask=kv_mask, q_chunk=q_chunk)
    b, s, h, d = q.shape
    t = k.shape[1]
    # bf16 inputs with f32 accumulation (MXU-style): avoids materialising
    # f32 copies of the K cache on the XLA path.
    scores = jnp.einsum("bshd,bthd->bhst", q, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    qp = q_positions if q_positions.ndim == 2 else q_positions[None, :]
    kp = kv_positions if kv_positions.ndim == 2 else kv_positions[None, :]
    mask = jnp.ones((qp.shape[0], s, t), dtype=bool)
    if causal:
        mask &= kp[:, None, :] <= qp[:, :, None]
    if window:
        mask &= kp[:, None, :] > (qp[:, :, None] - window)
    if kv_mask is not None:
        km = kv_mask if kv_mask.ndim == 2 else kv_mask[None, :]
        mask &= km[:, None, :]
    scores = jnp.where(mask[:, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhst,bthd->bshd", probs.astype(v.dtype), v)
    return out


def _hd_parallel_decode_attention(q, k, v, *, q_positions, kv_mask, window,
                                  softcap, scale, ctx=None):
    """GQA decode attention with the head_dim contraction sharded.

    Uses the grouped (kh, g) einsum form — no repeat op — so GSPMD keeps the
    hd-sharded cache local and emits only a small score all-reduce
    (the partial-sum combine) instead of all-gathering the cache.
    q: (B, S, H, D); k, v: (B, T, KH, D).
    """
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    qg = q.reshape(b, s, kh, g, d)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    qp = q_positions if q_positions.ndim == 2 else q_positions[None, :]
    kp = jnp.arange(t)
    mask = kp[None, None, :] <= qp[:, :, None]
    if window:
        mask &= kp[None, None, :] > (qp[:, :, None] - window)
    km = kv_mask if kv_mask.ndim == 2 else kv_mask[None, :]
    mask &= km[:, None, :]
    scores = jnp.where(mask[:, None, None, :, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    # pin the PV output to the cache's head_dim sharding: GSPMD must reshard
    # this tiny tensor for the output projection, not gather the V cache
    out = constrain(ctx, out, ("cache_batch", None, None, None, "cache_hd"))
    return out.reshape(b, s, h, d)


def _chunked_attention(q, k, v, *, q_positions, kv_positions, causal, window,
                       softcap, scale, kv_mask, q_chunk):
    """Query-blocked attention: sequential map over q chunks, each rematted.

    Peak live score memory drops from O(S*T) to O(q_chunk*T) per (batch,
    head); flops are unchanged (full T per chunk — the causal upper triangle
    is masked, not skipped; see kernels/flash_attention.py for the TPU
    kernel that does skip it).
    """
    b, s, h, d = q.shape
    nq = -(-s // q_chunk)
    pad = nq * q_chunk - s
    qp = q_positions if q_positions.ndim == 2 else jnp.broadcast_to(
        q_positions[None, :], (b, s))
    if pad:
        q = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
        qp = jnp.pad(qp, [(0, 0), (0, pad)], constant_values=-1)
    q_blocks = jnp.moveaxis(q.reshape(b, nq, q_chunk, h, d), 1, 0)
    p_blocks = jnp.moveaxis(qp.reshape(b, nq, q_chunk), 1, 0)

    @jax.checkpoint
    def one(args):
        q_c, p_c = args
        return attention_core(
            q_c, k, v, q_positions=p_c, kv_positions=kv_positions,
            causal=causal, window=window, softcap=softcap, scale=scale,
            kv_mask=kv_mask, q_chunk=0)

    out = jax.lax.map(one, (q_blocks, p_blocks))      # (nq, b, qc, h, d)
    out = jnp.moveaxis(out, 0, 1).reshape(b, nq * q_chunk, h, d)
    return out[:, :s]


def attn_apply(p, cfg: ModelConfig, x, *, positions, causal=True, window=0,
               kv_x=None, kv_positions=None, ctx: Optional[Ctx] = None,
               cache=None, cache_pos=None, use_rope=True):
    """Full attention block: project, (rope), (cache update), core, out-proj.

    cache: optional dict {"k": (B, T, K, D), "v": ...} updated at cache_pos.
    Returns ``(out, kv)`` where kv is the updated cache dict when a cache was
    given, else the freshly-projected (post-rope) {"k", "v"} — the prefill
    path uses the latter to build a cache.
    """
    kv_src = x if kv_x is None else kv_x
    q, k, v = _project_qkv(p, cfg, x, kv_src)
    scale = cfg.attn_scale if cfg.attn_scale is not None else cfg.resolved_head_dim ** -0.5
    if use_rope and cfg.rope_theta > 0:
        q = rope(q, positions, cfg.rope_theta)
        if kv_x is None:  # self-attention: rotate keys by their own positions
            k = rope(k, kv_positions if kv_positions is not None else positions,
                     cfg.rope_theta)
    if cache is not None:
        # single-token (or short-chunk) decode: write k/v at cache_pos
        ck = jax.lax.dynamic_update_slice_in_dim(cache["k"], k.astype(cache["k"].dtype),
                                                 cache_pos, axis=1)
        cv = jax.lax.dynamic_update_slice_in_dim(cache["v"], v.astype(cache["v"].dtype),
                                                 cache_pos, axis=1)
        kv_out = {"k": ck, "v": cv}
        # Head-dim-parallel decode attention: when kv_heads cannot shard
        # over the model axis, the cache is stored head_dim-sharded (the
        # `cache_hd` fallback). Re-shard the tiny queries to match so the
        # QK^T contraction runs as sharded partial sums (small score
        # all-reduce) instead of all-gathering the whole cache.
        hd_parallel = (
            ctx is not None and "model" in getattr(ctx.mesh, "axis_names", ())
            and cfg.num_kv_heads % ctx.mesh.shape["model"] != 0
            and ck.shape[-1] % ctx.mesh.shape["model"] == 0
        )
        kv_pos = jnp.arange(cache["k"].shape[1])
        kv_mask = kv_pos <= (cache_pos + x.shape[1] - 1)
        if hd_parallel:
            ckc = constrain(ctx, ck, ("cache_batch", "cache_seq", "kv_heads",
                                      "cache_hd"))
            cvc = constrain(ctx, cv, ("cache_batch", "cache_seq", "kv_heads",
                                      "cache_hd"))
            qc = constrain(ctx, q, ("cache_batch", None, None, "cache_hd"))
            out = _hd_parallel_decode_attention(
                qc, ckc, cvc, q_positions=positions, kv_mask=kv_mask,
                window=window, softcap=cfg.attn_logit_softcap, scale=scale,
                ctx=ctx)
        else:
            out = attention_core(
                q, ck, cv, q_positions=positions, kv_positions=kv_pos,
                causal=causal, window=window,
                softcap=cfg.attn_logit_softcap, scale=scale,
                kv_mask=kv_mask, impl="xla", q_chunk=cfg.attn_q_chunk,
            )
    else:
        kv_out = {"k": k, "v": v}
        kv_positions = positions if kv_positions is None else kv_positions
        out = attention_core(
            q, k, v, q_positions=positions, kv_positions=kv_positions,
            causal=causal, window=window, softcap=cfg.attn_logit_softcap,
            scale=scale, impl=cfg.attn_impl, q_chunk=cfg.attn_q_chunk,
        )
    out = constrain(ctx, out, ("batch", "seq", "heads", "head_dim"))
    # pin the row-parallel partial-sum point on the bf16 einsum output so
    # the TP all-reduce runs in bf16 (XLA would otherwise hoist the f32
    # convert of the downstream norm above the all-reduce, doubling bytes)
    proj = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
    proj = constrain(ctx, proj, ("batch", "seq", "embed"))
    return proj, kv_out


# --------------------------------------------------------------------- mlp

def mlp_param_specs(cfg: ModelConfig, d_ff: int):
    d = cfg.d_model
    if cfg.norm == "layernorm":  # whisper-style plain MLP with biases
        return {
            "wi": spec((d, d_ff), ("embed", "mlp")),
            "bi": spec((d_ff,), ("mlp",), "zeros"),
            "wo": spec((d_ff, d), ("mlp", "embed")),
            "bo": spec((d,), ("embed",), "zeros"),
        }
    return {
        "wi_gate": spec((d, d_ff), ("embed", "mlp")),
        "wi_up": spec((d, d_ff), ("embed", "mlp")),
        "wo": spec((d_ff, d), ("mlp", "embed")),
    }


def _act(cfg: ModelConfig, x):
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    return jax.nn.silu(x)


def mlp_apply(p, cfg: ModelConfig, x, ctx: Optional[Ctx] = None):
    if "wi" in p:  # plain MLP with biases (whisper)
        h = jnp.einsum("bsd,df->bsf", x, p["wi"]) + p["bi"]
        h = _act(cfg, h)
        return jnp.einsum("bsf,fd->bsd", h, p["wo"]) + p["bo"]
    g = _act(cfg, jnp.einsum("bsd,df->bsf", x, p["wi_gate"]))
    u = jnp.einsum("bsd,df->bsf", x, p["wi_up"])
    h = constrain(ctx, g * u, ("batch", "seq", "mlp"))
    return jnp.einsum("bsf,fd->bsd", h, p["wo"])


# -------------------------------------------------------------- embeddings

def embed_param_specs(cfg: ModelConfig):
    # A tied table is also the unembedding, so it takes the fan-in scale an
    # untied unembedding gets. At unit scale the logits' std is sqrt(d_model)
    # and each position's own token outscores the rest by ~d_model: the
    # softmax is exactly one-hot at wide d_model and every policy gradient
    # is exactly zero.
    scale = cfg.d_model ** -0.5 if cfg.tie_embeddings else 1.0
    p = {"embedding": spec((cfg.vocab_size, cfg.d_model), ("vocab", "embed"),
                           "embed", scale=scale)}
    if not cfg.tie_embeddings:
        p["unembed"] = spec((cfg.d_model, cfg.vocab_size), ("embed", "vocab"))
    return p


def embed_apply(p, cfg: ModelConfig, tokens, ctx: Optional[Ctx] = None):
    x = p["embedding"].astype(jnp.bfloat16)[tokens]
    if cfg.family == "dense" and cfg.post_norms:  # gemma scales embeddings
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return constrain(ctx, x, ("batch", "seq", "embed"))


def unembed_apply(p, cfg: ModelConfig, x, ctx: Optional[Ctx] = None):
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, p["embedding"]).astype(jnp.float32)
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, p["unembed"]).astype(jnp.float32)
    if cfg.final_logit_softcap:
        logits = cfg.final_logit_softcap * jnp.tanh(logits / cfg.final_logit_softcap)
    return constrain(ctx, logits, ("batch", "seq", "vocab"))


def remat_policy(cfg: ModelConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    return jax.checkpoint_policies.nothing_saveable
