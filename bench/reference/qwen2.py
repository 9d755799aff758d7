"""Qwen2 decoder (arXiv:2407.10671), plain float32.

Pre-norm blocks: RMSNorm, grouped-query attention with biased Q/K/V
projections and rotate-half RoPE, residual; RMSNorm, SwiGLU MLP, residual;
final RMSNorm and the tied embedding as the output head. Sizes come from
the configuration file's published keys.

Departure, noted: each RMSNorm weight is stored as its offset from 1 (the
served system's layout, initialised at 0), so the reference multiplies by
``1 + scale``; the function is the published one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.weights import fan_in_std


def arch(cfg: dict) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    if not cfg["tie_word_embeddings"]:
        raise ValueError("the qwen2 reference covers tied embeddings only")
    return dict(d=d, L=cfg["num_hidden_layers"], H=h,
                K=cfg["num_key_value_heads"], hd=d // h,
                ff=cfg["intermediate_size"], V=cfg["vocab_size"],
                theta=float(cfg["rope_theta"]), eps=float(cfg["rms_norm_eps"]))


def leaf_table(a: dict) -> dict:
    bf16, L, d, H, K, hd, ff = (jnp.bfloat16, a["L"], a["d"], a["H"], a["K"],
                                a["hd"], a["ff"])

    def normal(*shape):
        return (shape, "normal", bf16, fan_in_std(shape))

    zeros = lambda *shape: (shape, "zeros", bf16, 0.0)
    return {
        ("embed", "embedding"): ((a["V"], d), "normal", bf16, d ** -0.5),
        ("layers", "attn", "wq"): normal(L, d, H, hd),
        ("layers", "attn", "wk"): normal(L, d, K, hd),
        ("layers", "attn", "wv"): normal(L, d, K, hd),
        ("layers", "attn", "wo"): normal(L, H, hd, d),
        ("layers", "attn", "bq"): zeros(L, H, hd),
        ("layers", "attn", "bk"): zeros(L, K, hd),
        ("layers", "attn", "bv"): zeros(L, K, hd),
        ("layers", "ln1", "scale"): zeros(L, d),
        ("layers", "ln2", "scale"): zeros(L, d),
        ("layers", "mlp", "wi_gate"): normal(L, d, ff),
        ("layers", "mlp", "wi_up"): normal(L, d, ff),
        ("layers", "mlp", "wo"): normal(L, ff, d),
        ("ln_f", "scale"): zeros(d),
    }


def rms_norm(x, scale, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * (1.0 + scale.astype(jnp.float32))


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * inv[None, :]      # (S, half)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def forward(params, tokens, a: dict, num):
    """Logits (B, S, V) in float32 for tokens (B, S) at positions 0..S-1."""
    emb = params["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32)
    s = tokens.shape[1]
    pos = jnp.arange(s)
    causal = pos[:, None] >= pos[None, :]
    rep = a["H"] // a["K"]

    def layer(x, p):
        at = p["attn"]
        h = rms_norm(x, p["ln1"]["scale"], a["eps"])
        q = num.ein("bsd,dhk->bshk", h, at["wq"]) + at["bq"].astype(jnp.float32)
        k = num.ein("bsd,dhk->bshk", h, at["wk"]) + at["bk"].astype(jnp.float32)
        v = num.ein("bsd,dhk->bshk", h, at["wv"]) + at["bv"].astype(jnp.float32)
        q, k = _rope(q, pos, a["theta"]), _rope(k, pos, a["theta"])
        k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
        sc = num.ein("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(float(a["hd"]))
        pr = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = num.ein("bhqk,bkhd->bqhd", pr, v)
        x = x + num.ein("bqhd,hdm->bqm", o, at["wo"])
        m = p["mlp"]
        h = rms_norm(x, p["ln2"]["scale"], a["eps"])
        g = num.ein("bsd,df->bsf", h, m["wi_gate"])
        u = num.ein("bsd,df->bsf", h, m["wi_up"])
        return x + num.ein("bsf,fd->bsd", jax.nn.silu(g) * u, m["wo"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = rms_norm(x, params["ln_f"]["scale"], a["eps"])
    return num.ein("bsd,vd->bsv", x, emb)
