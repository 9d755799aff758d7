"""Mamba2 (SSD — state-space duality) mixer and attention-free LM.

Implements the chunked SSD algorithm of arXiv:2405.21060: intra-chunk
quadratic (attention-like) blocks plus an inter-chunk recurrent state scan.
Decode is O(1) in sequence length — the cache is a fixed-size
(conv window, SSM state) pair per layer, which is why the ssm/hybrid
families run the ``long_500k`` cell.

The chunk kernel has a Pallas TPU implementation in
``repro.kernels.ssd`` (this module is also its jnp oracle via
``cfg.attn_impl == "xla"``).

The causal conv, the chunked scan and the one-token update run under
``jax.named_scope`` ``ssd.conv``, ``ssd.chunk`` and ``ssd.decode``, so the
device ops they compile to carry those names in a profile's op metadata.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs import ModelConfig
from repro.models.common import spec, stack_specs
from repro.models.layers import (
    Ctx,
    apply_norm,
    constrain,
    embed_apply,
    embed_param_specs,
    norm_param_specs,
    remat_policy,
    rms_norm,
    unembed_apply,
)


# ------------------------------------------------------------------ params

def conv_dim(cfg: ModelConfig) -> int:
    return cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state


def in_proj_dim(cfg: ModelConfig) -> int:
    return 2 * cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state + cfg.ssm_nheads


def mixer_param_specs(cfg: ModelConfig):
    d, di, h = cfg.d_model, cfg.d_inner, cfg.ssm_nheads
    return {
        "in_proj": spec((d, in_proj_dim(cfg)), ("embed", "ssm_inner")),
        "conv_w": spec((conv_dim(cfg), cfg.ssm_conv), ("conv_dim", None)),
        "conv_b": spec((conv_dim(cfg),), ("conv_dim",), "zeros"),
        "A_log": spec((h,), ("ssm_heads",), "ssm_a", dtype=jnp.float32),
        "D": spec((h,), ("ssm_heads",), "ones", dtype=jnp.float32),
        "dt_bias": spec((h,), ("ssm_heads",), "dt_bias", dtype=jnp.float32),
        "norm": spec((di,), ("ssm_inner",), "zeros"),
        "out_proj": spec((di, d), ("ssm_inner", "embed")),
    }


def layer_param_specs(cfg: ModelConfig):
    return {"ln": norm_param_specs(cfg), "mixer": mixer_param_specs(cfg)}


def param_specs(cfg: ModelConfig):
    return {
        "embed": embed_param_specs(cfg),
        "layers": stack_specs(layer_param_specs(cfg), cfg.num_layers),
        "ln_f": norm_param_specs(cfg),
    }


# --------------------------------------------------------------------- SSD

def segsum(x):
    """x: (..., l) -> (..., l, l) with out[i, j] = sum_{j<k<=i} x_k (else -inf)."""
    l = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    out = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((l, l), bool))
    return jnp.where(mask, out, -jnp.inf)


def ssd_chunked(x, dt, A, B, C, chunk: int, initial_state=None):
    """Chunked SSD scan.

    x: (b, s, h, p); dt: (b, s, h) (post-softplus); A: (h,) (negative);
    B, C: (b, s, g, n). Returns (y (b, s, h, p), final_state (b, h, p, n)).
    """
    b, s, h, p = x.shape
    g, n = B.shape[2], B.shape[3]
    s_orig = s
    if s % chunk:
        # zero-pad: dt=0 at pads -> decay exp(0)=1 and zero state update, so
        # the final state is unaffected; padded outputs are sliced off.
        pad = chunk - s % chunk
        x = jnp.pad(x, [(0, 0), (0, pad), (0, 0), (0, 0)])
        dt = jnp.pad(dt, [(0, 0), (0, pad), (0, 0)])
        B = jnp.pad(B, [(0, 0), (0, pad), (0, 0), (0, 0)])
        C = jnp.pad(C, [(0, 0), (0, pad), (0, 0), (0, 0)])
        s = s + pad
    nc = s // chunk
    rep = h // g

    xc = x.reshape(b, nc, chunk, h, p)
    dtc = dt.reshape(b, nc, chunk, h)
    Bc = jnp.repeat(B.reshape(b, nc, chunk, g, n), rep, axis=3)   # (b,nc,l,h,n)
    Cc = jnp.repeat(C.reshape(b, nc, chunk, g, n), rep, axis=3)

    dA = (dtc * A[None, None, None, :]).astype(jnp.float32)       # (b,nc,l,h)
    dA_cs = jnp.cumsum(dA, axis=2)                                # (b,nc,l,h)

    # ---- intra-chunk (diagonal blocks): quadratic within a chunk
    L = jnp.exp(segsum(jnp.moveaxis(dA, -1, -2)))                 # (b,nc,h,l,l)
    scores = jnp.einsum("bclhn,bcshn->bchls", Cc.astype(jnp.float32),
                        Bc.astype(jnp.float32))
    scores = scores * L * jnp.moveaxis(dtc, -1, -2)[:, :, :, None, :]
    y_diag = jnp.einsum("bchls,bcshp->bclhp", scores.astype(x.dtype), xc)

    # ---- chunk summary states: S_c = sum_j exp(dA_j+1..L) dt_j B_j x_j^T
    decay_states = jnp.exp(dA_cs[:, :, -1:, :] - dA_cs)           # (b,nc,l,h)
    states = jnp.einsum("bclhn,bclh,bclhp->bchpn", Bc.astype(jnp.float32),
                        (decay_states * dtc).astype(jnp.float32),
                        xc.astype(jnp.float32))                   # (b,nc,h,p,n)

    # ---- inter-chunk recurrence over nc chunks
    chunk_decay = jnp.exp(dA_cs[:, :, -1, :])                     # (b,nc,h)

    def scan_fn(s_in, xs):
        st, dec = xs                                              # (b,h,p,n), (b,h)
        s_out = s_in * dec[:, :, None, None] + st
        return s_out, s_in

    init = (jnp.zeros((b, h, p, n), jnp.float32)
            if initial_state is None else initial_state.astype(jnp.float32))
    final_state, entry_states = jax.lax.scan(
        scan_fn, init,
        (jnp.moveaxis(states, 1, 0), jnp.moveaxis(chunk_decay, 1, 0)))
    entry_states = jnp.moveaxis(entry_states, 0, 1)               # (b,nc,h,p,n)

    # ---- off-diagonal contribution from the incoming state
    y_off = jnp.einsum("bclhn,bchpn,bclh->bclhp",
                       Cc.astype(jnp.float32), entry_states, jnp.exp(dA_cs))
    y = (y_diag.astype(jnp.float32) + y_off).reshape(b, s, h, p)
    return y[:, :s_orig].astype(x.dtype), final_state


def ssd_decode(state, x, dt, A, B, C):
    """Single-token SSD update.

    state: (b, h, p, n); x: (b, h, p); dt: (b, h); B, C: (b, g, n).
    Returns (y (b, h, p), new_state).
    """
    h, g = x.shape[1], B.shape[1]
    rep = h // g
    Bh = jnp.repeat(B, rep, axis=1).astype(jnp.float32)           # (b,h,n)
    Ch = jnp.repeat(C, rep, axis=1).astype(jnp.float32)
    dA = jnp.exp(dt.astype(jnp.float32) * A[None, :])             # (b,h)
    upd = jnp.einsum("bh,bhp,bhn->bhpn", dt.astype(jnp.float32),
                     x.astype(jnp.float32), Bh)
    new_state = state * dA[:, :, None, None] + upd
    y = jnp.einsum("bhn,bhpn->bhp", Ch, new_state)
    return y.astype(x.dtype), new_state


# ---------------------------------------------------------------- conv1d

def causal_conv(x, w, b):
    """Depthwise causal conv. x: (B, S, C); w: (C, K)."""
    k = w.shape[1]
    xp = jnp.pad(x, [(0, 0), (k - 1, 0), (0, 0)])
    out = jnp.zeros_like(x, dtype=jnp.float32)
    s = x.shape[1]
    for i in range(k):
        out = out + xp[:, i:i + s, :].astype(jnp.float32) * w[:, i].astype(jnp.float32)
    return jax.nn.silu(out + b.astype(jnp.float32)).astype(x.dtype)


def conv_decode(conv_state, x_new, w, b):
    """conv_state: (B, C, K-1); x_new: (B, C). Returns (out (B, C), new_state)."""
    window = jnp.concatenate([conv_state, x_new[:, :, None]], axis=2)  # (B,C,K)
    out = jnp.einsum("bck,ck->bc", window.astype(jnp.float32),
                     w.astype(jnp.float32))
    out = jax.nn.silu(out + b.astype(jnp.float32)).astype(x_new.dtype)
    return out, window[:, :, 1:]


# ------------------------------------------------------------------- mixer

def _split_in_proj(cfg: ModelConfig, zxbcdt):
    di, gn = cfg.d_inner, cfg.ssm_ngroups * cfg.ssm_state
    z = zxbcdt[..., :di]
    xBC = zxbcdt[..., di:di + di + 2 * gn]
    dt = zxbcdt[..., di + di + 2 * gn:]
    return z, xBC, dt


def _split_xbc(cfg: ModelConfig, xBC):
    di, gn = cfg.d_inner, cfg.ssm_ngroups * cfg.ssm_state
    return xBC[..., :di], xBC[..., di:di + gn], xBC[..., di + gn:]


def mixer_apply(p, cfg: ModelConfig, x, ctx: Optional[Ctx] = None,
                cache=None, return_state: bool = False):
    """Full-sequence mamba2 mixer. x: (B, S, d_model).

    Returns (out, new_cache). With ``cache`` (dict conv/ssm) the input must
    be a single step (S == 1) and the decode path is used. With
    ``return_state`` in full-seq mode, the final (conv, ssm) states are
    returned so a prefill can seed a decode cache.
    """
    b, s, _ = x.shape
    h, pdim, n, g = cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_ngroups
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    z, xBC, dt_raw = _split_in_proj(cfg, zxbcdt)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])

    if cache is None:
        xBC_raw = xBC
        with jax.named_scope("ssd.conv"):
            xBC = causal_conv(xBC, p["conv_w"], p["conv_b"])
        xs, B, C = _split_xbc(cfg, xBC)
        xs = constrain(ctx, xs, ("batch", "seq", "ssm_inner"))
        with jax.named_scope("ssd.chunk"):
            y, final_state = ssd_chunked(
                xs.reshape(b, s, h, pdim), dt, A, B.reshape(b, s, g, n),
                C.reshape(b, s, g, n), cfg.ssm_chunk)
        y = y + p["D"][None, None, :, None].astype(y.dtype) \
            * xs.reshape(b, s, h, pdim)
        new_cache = None
        if return_state:
            kc = cfg.ssm_conv - 1
            conv_state = jnp.moveaxis(xBC_raw[:, s - kc:, :], 1, 2)  # (B, C, K-1)
            new_cache = {"conv": conv_state, "ssm": final_state}
    else:
        with jax.named_scope("ssd.conv"):
            xBC_step, new_conv = conv_decode(cache["conv"], xBC[:, 0],
                                             p["conv_w"], p["conv_b"])
        xs, B, C = _split_xbc(cfg, xBC_step[:, None, :])
        with jax.named_scope("ssd.decode"):
            y1, new_ssm = ssd_decode(cache["ssm"],
                                     xs[:, 0].reshape(b, h, pdim), dt[:, 0], A,
                                     B[:, 0].reshape(b, g, n),
                                     C[:, 0].reshape(b, g, n))
        y = y1[:, None] + p["D"][None, None, :, None].astype(y1.dtype) \
            * xs.reshape(b, 1, h, pdim)
        new_cache = {"conv": new_conv, "ssm": new_ssm}

    y = y.reshape(b, s, cfg.d_inner)
    y = rms_norm(y * jax.nn.silu(z.astype(jnp.float32)).astype(y.dtype),
                 p["norm"], cfg.norm_eps)
    return jnp.einsum("bse,ed->bsd", y, p["out_proj"]), new_cache


def block_apply(p, cfg: ModelConfig, x, ctx=None, cache=None,
                return_state: bool = False):
    h = apply_norm(p["ln"], x, cfg)
    out, new_cache = mixer_apply(p["mixer"], cfg, h, ctx, cache, return_state)
    return x + out, new_cache


# ----------------------------------------------------------------- model

def forward(params, cfg: ModelConfig, tokens, ctx: Optional[Ctx] = None,
            return_cache: bool = False):
    b, s = tokens.shape
    x = embed_apply(params["embed"], cfg, tokens, ctx)
    policy = remat_policy(cfg)

    def body(x, p_layer):
        x, st = block_apply(p_layer, cfg, x, ctx, return_state=return_cache)
        return x, (st["conv"], st["ssm"]) if return_cache else None

    fn = body if policy is None else jax.checkpoint(body, policy=policy)
    x, ys = jax.lax.scan(fn, x, params["layers"])
    x = apply_norm(params["ln_f"], x, cfg)
    logits = unembed_apply(params["embed"], cfg, x, ctx)
    if return_cache:
        convs, ssms = ys
        cache = {"conv": convs, "ssm": ssms,
                 "pos": jnp.full((), s, jnp.int32)}
        return logits, jnp.zeros((), jnp.float32), cache
    return logits, jnp.zeros((), jnp.float32)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    """Decode cache: conv window + SSM state per layer. O(1) in max_len."""
    l, h, pdim, n = cfg.num_layers, cfg.ssm_nheads, cfg.ssm_head_dim, cfg.ssm_state
    return {
        "conv": spec((l, batch, conv_dim(cfg), cfg.ssm_conv - 1),
                     ("layers", "cache_batch", "conv_dim", None), "zeros"),
        "ssm": spec((l, batch, h, pdim, n),
                    ("layers", "cache_batch", "ssm_heads", None, None),
                    "zeros", dtype=jnp.float32),
        "pos": spec((), (), "zeros", dtype=jnp.int32),
    }


def init_cache_zeros(cfg: ModelConfig, batch: int):
    from repro.models.common import init_params
    import jax.random as jr
    return init_params(jr.PRNGKey(0), cache_specs(cfg, batch, 0))


def decode_step(params, cfg: ModelConfig, cache, tokens,
                ctx: Optional[Ctx] = None):
    b = tokens.shape[0]
    x = embed_apply(params["embed"], cfg, tokens, ctx)

    def body(x, xs):
        p_layer, conv_c, ssm_c = xs
        x, nc = block_apply(p_layer, cfg, x, ctx,
                            cache={"conv": conv_c, "ssm": ssm_c})
        return x, (nc["conv"], nc["ssm"])

    x, (convs, ssms) = jax.lax.scan(
        body, x, (params["layers"], cache["conv"], cache["ssm"]))
    x = apply_norm(params["ln_f"], x, cfg)
    logits = unembed_apply(params["embed"], cfg, x, ctx)
    return logits, {"conv": convs, "ssm": ssms, "pos": cache["pos"] + 1}
