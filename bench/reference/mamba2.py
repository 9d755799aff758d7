"""Mamba-2 language model (arXiv:2405.21060), plain float32.

Pre-norm residual blocks of the Mamba-2 mixer: one input projection to
(z, x, B, C, dt); a depthwise causal convolution with SiLU over (x, B, C);
dt = softplus(dt + dt_bias); the selective state space y_t = sum_{s<=t}
C_t.B_s exp(sum_{s<k<=t} dt_k A) dt_s x_s + D x_t with A = -exp(A_log),
computed here in the paper's quadratic (dual) form over the whole
sequence; the gated RMSNorm norm(y * silu(z)); the output projection. Final
RMSNorm and the tied embedding as the output head. Sizes come from the
configuration file's published keys and the mixer defaults it lists.

Departure, noted: each RMSNorm weight is stored as its offset from 1 (the
served system's layout, initialised at 0), so the reference multiplies by
``1 + scale``; the function is the published one.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference.qwen2 import rms_norm
from bench.reference.weights import fan_in_std


def arch(cfg: dict) -> dict:
    ssm = cfg["mixer"]
    if not cfg["tie_embeddings"]:
        raise ValueError("the mamba2 reference covers tied embeddings only")
    mult = cfg["pad_vocab_size_multiple"]
    d = cfg["d_model"]
    di = ssm["expand"] * d
    return dict(d=d, L=cfg["n_layer"], V=-(-cfg["vocab_size"] // mult) * mult,
                di=di, n=ssm["d_state"], g=ssm["ngroups"], p=ssm["headdim"],
                h=di // ssm["headdim"], conv=ssm["d_conv"],
                eps=float(cfg["norm_epsilon"]))


def leaf_table(a: dict) -> dict:
    bf16, f32 = jnp.bfloat16, jnp.float32
    L, d, di, h = a["L"], a["d"], a["di"], a["h"]
    conv_dim = di + 2 * a["g"] * a["n"]
    proj = 2 * di + 2 * a["g"] * a["n"] + h

    def normal(*shape):
        return (shape, "normal", bf16, fan_in_std(shape))

    return {
        ("embed", "embedding"): ((a["V"], d), "normal", bf16, d ** -0.5),
        ("layers", "ln", "scale"): ((L, d), "zeros", bf16, 0.0),
        ("layers", "mixer", "in_proj"): normal(L, d, proj),
        ("layers", "mixer", "conv_w"): normal(L, conv_dim, a["conv"]),
        ("layers", "mixer", "conv_b"): ((L, conv_dim), "zeros", bf16, 0.0),
        ("layers", "mixer", "A_log"): ((L, h), "ssm_a", f32, 0.0),
        ("layers", "mixer", "D"): ((L, h), "ones", f32, 0.0),
        ("layers", "mixer", "dt_bias"): ((L, h), "dt_bias", f32, 0.0),
        ("layers", "mixer", "norm"): ((L, di), "zeros", bf16, 0.0),
        ("layers", "mixer", "out_proj"): normal(L, di, d),
        ("ln_f", "scale"): ((d,), "zeros", bf16, 0.0),
    }


def _mixer(p, x, a, num):
    b, s, _ = x.shape
    di, g, n, h, hp = a["di"], a["g"], a["n"], a["h"], a["p"]
    zxbcdt = num.ein("bsd,de->bse", x, p["in_proj"])
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * g * n],
                  zxbcdt[..., 2 * di + 2 * g * n:])
    w = p["conv_w"].astype(jnp.float32)                          # (C, K)
    k = w.shape[1]
    padded = jnp.pad(xbc, [(0, 0), (k - 1, 0), (0, 0)])
    conv = sum(padded[:, i:i + s] * w[:, i] for i in range(k))
    xbc = jax.nn.silu(conv + p["conv_b"].astype(jnp.float32))
    xs = xbc[..., :di].reshape(b, s, h, hp)
    rep = h // g
    bm = jnp.repeat(xbc[..., di:di + g * n].reshape(b, s, g, n), rep, axis=2)
    cm = jnp.repeat(xbc[..., di + g * n:].reshape(b, s, g, n), rep, axis=2)
    dt = jax.nn.softplus(dt + p["dt_bias"])                      # (b, s, h)
    cum = jnp.cumsum(dt * -jnp.exp(p["A_log"]), axis=1)          # (b, s, h)
    t_ge_s = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    seg = jnp.moveaxis(cum, 1, 2)                                # (b, h, s)
    decay = jnp.exp(jnp.where(t_ge_s, seg[..., :, None] - seg[..., None, :],
                              -jnp.inf))                         # (b, h, t, s)
    scores = num.ein("bthn,bshn->bhts", cm, bm) * decay \
        * jnp.moveaxis(dt, 1, 2)[:, :, None, :]
    y = num.ein("bhts,bshp->bthp", scores, xs) + p["D"][:, None] * xs
    y = y.reshape(b, s, di) * jax.nn.silu(z)
    return num.ein("bse,ed->bsd", rms_norm(y, p["norm"], a["eps"]),
                   p["out_proj"])


def forward(params, tokens, a: dict, num):
    """Logits (B, S, V) in float32 for tokens (B, S)."""
    emb = params["embed"]["embedding"]
    x = emb[tokens].astype(jnp.float32)

    def layer(x, p):
        return x + _mixer(p["mixer"], rms_norm(x, p["ln"]["scale"], a["eps"]),
                          a, num), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, params["layers"])
    x = rms_norm(x, params["ln_f"]["scale"], a["eps"])
    return num.ein("bsd,vd->bsv", x, emb)
