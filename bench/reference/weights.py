"""Random weights from a job's seed, drawn the way the served system draws
them, so that the reference starts from the weights the program starts from.

The scheme, as the system under test documents it: one key per parameter
leaf, ``jax.random.split(PRNGKey(seed), n_leaves)`` over the leaves in
sorted-path order; a "normal" leaf is a float32 standard normal times
``1 / sqrt(fan_in)``, where ``fan_in`` is the product of every stored
dimension but the last (the stacked layer dimension included); a tied
embedding is a normal times ``d_model ** -0.5``; ``ssm_a`` is the log of a
uniform draw in [1, 16); ``dt_bias`` is the inverse softplus of a
log-uniform draw in [1e-3, 1e-1]; each is then cast to the leaf's dtype.

A family module describes its leaves as ``{path: (shape, kind, dtype,
std)}``; :func:`make_params` turns that into the nested parameter tree.
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Leaf = Tuple[tuple, str, object, float]


def fan_in_std(shape) -> float:
    return 1.0 / math.sqrt(int(np.prod(shape[:-1])) or 1)


def _draw(key, shape, kind, dtype, std):
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "ssm_a":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0)).astype(dtype)
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                     * (math.log(1e-1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def _nest(flat: Dict[tuple, object]):
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        for name in path[:-1]:
            node = node.setdefault(name, {})
        node[path[-1]] = value
    return tree


def make_params(table: Dict[tuple, Leaf], seed):
    """The parameter tree of ``table`` drawn from ``seed`` (traceable:
    callers jit it with the table closed over)."""
    paths = sorted(table)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(paths))
    return _nest({p: _draw(k, *table[p]) for p, k in zip(paths, keys)})


def leaf_names(table: Dict[tuple, Leaf]):
    """Leaf names in the order ``jax.tree.leaves`` yields the tree."""
    return ["/".join(p) for p in sorted(table)]
