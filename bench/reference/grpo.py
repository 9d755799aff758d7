"""GRPO's clipped surrogate and AdamW, written from their definitions.

The surrogate (arXiv:2402.03300) over a batch of packed rollouts: tokens
(B, S), advantages (B,), loss_mask (B, S) over the response positions and
behaviour log-probabilities. The reference is exact, so its behaviour
log-probabilities are its own: the importance ratio is 1 with the policy's
gradient. AdamW (arXiv:1711.05101) with global-norm clipping and a linear
warm-up of the learning rate; parameters are stored in their own dtype
after each step, as the configuration states.

Gradients are computed in float32 over blocks of rows, each block's
rounded once to the parameters' dtype and summed in float32, so that a
full-width model fits one chip beside its float32 optimizer state.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp


def next_token_stats(logits, tokens, probes):
    """Per position t < S-1, of the logits that predict token t+1: the
    logit of that token, the log-partition and the logit of
    ``probes[:, t+1]``. Each (B, S-1)."""
    lg = logits[:, :-1]
    at = lambda ids: jnp.take_along_axis(lg, ids[:, 1:, None], -1)[..., 0]
    return dict(logit_tok=at(tokens), lse=jax.nn.logsumexp(lg, -1),
                logit_probe=at(probes))


def surrogate_sum(logits, batch, clip_eps):
    """Sum over masked positions of the clipped surrogate (negated)."""
    lp = jax.nn.log_softmax(logits[:, :-1], -1)
    logp = jnp.take_along_axis(lp, batch["tokens"][:, 1:, None], -1)[..., 0]
    ratio = jnp.exp(logp - jax.lax.stop_gradient(logp))
    adv = batch["advantages"][:, None]
    surr = jnp.minimum(ratio * adv,
                       jnp.clip(ratio, 1 - clip_eps, 1 + clip_eps) * adv)
    return -(surr * batch["loss_mask"][:, 1:]).sum()


def blocks(batch, rows: int):
    n = batch["tokens"].shape[0]
    for i in range(0, n, rows):
        yield {k: v[i:i + rows] for k, v in batch.items()}


@partial(jax.jit, static_argnums=(0, 3))
def _block_grad(forward, params, batch, clip_eps):
    return jax.value_and_grad(
        lambda p: surrogate_sum(forward(p, batch["tokens"]), batch, clip_eps)
    )(params)


@partial(jax.jit, donate_argnums=(0,))
def _accumulate(acc, g):
    return jax.tree.map(lambda a, b: a + b.astype(jnp.float32), acc, g)


@jax.jit
def _scale(tree, by):
    return jax.tree.map(lambda x: x / by, tree)


def loss_and_grads(forward, params, batch, clip_eps: float, rows: int):
    """GRPO loss and float32 gradients of the whole batch, ``rows`` rows at
    a time. ``forward(params, tokens)`` must be hashable (jit static)."""
    denom = jnp.maximum(batch["loss_mask"][:, 1:].sum(), 1.0)
    total, acc = 0.0, None
    for blk in blocks(batch, rows):
        val, g = _block_grad(forward, params, blk, clip_eps)
        total = total + val
        acc = (jax.tree.map(lambda x: x.astype(jnp.float32), g) if acc is None
               else _accumulate(acc, g))
        del g
    return float(total / denom), _scale(acc, denom)


@jax.jit
def _norms(tree):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


def leaf_norms(tree):
    """Float32 norm of every leaf, in ``jax.tree.leaves`` order."""
    return [float(v) for v in _norms(tree)]


@jax.jit
def _diff_norms(a, b):
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)
                                                  - y.astype(jnp.float32))))
                      for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b))])


def diff_norms(a, b):
    """Norm of ``a - b`` per leaf, in float32."""
    return [float(v) for v in _diff_norms(a, b)]


@partial(jax.jit, static_argnums=(0,), donate_argnums=(1, 2, 3))
def _adamw(opt, params, mu, nu, grads, step):
    lr, b1, b2, eps, wd, clip, warmup = opt
    gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (gnorm + 1e-9)) if clip else 1.0
    t = (step + 1).astype(jnp.float32)
    rate = lr * jnp.minimum(1.0, t / max(warmup, 1))

    def one(p, m, v, g):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        upd = (m / (1 - b1 ** t)) / (jnp.sqrt(v / (1 - b2 ** t)) + eps)
        p32 = p.astype(jnp.float32)
        return (p32 - rate * (upd + wd * p32)).astype(p.dtype), m, v

    out = jax.tree.map(one, params, mu, nu, grads)
    pick = lambda i: jax.tree.map(lambda o: o[i], out,
                                  is_leaf=lambda o: isinstance(o, tuple))
    return pick(0), pick(1), pick(2)


def adamw(opt: dict, params, mu, nu, grads, step: int):
    """One AdamW step from the ``step``-th (0-based). Returns (params, mu,
    nu); the inputs are consumed."""
    cfg = (opt["lr"], opt["b1"], opt["b2"], opt["eps"], opt["weight_decay"],
           opt["grad_clip"], opt["warmup_steps"])
    return _adamw(cfg, params, mu, nu, grads, jnp.asarray(step, jnp.int32))


@jax.jit
def zeros_f32(tree):
    return jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32), tree)
