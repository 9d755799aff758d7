"""The arithmetic a reference runs its matrix products in.

``F32`` is the reference proper: float32 operands at full matmul precision
(on a TPU a float32 product otherwise runs in bfloat16 passes). ``FP8`` is
the control: every product's operands quantized per tensor to float8
(e4m3 forward, e5m2 for the cotangent in the backward pass), the step below
the bfloat16 the configurations state. Norms, softmax and the optimizer stay
in float32 under both.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def quantize(x, dtype):
    """Round ``x`` through ``dtype`` with one scale for the whole tensor,
    chosen so that its largest magnitude maps to the format's largest."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / float(jnp.finfo(dtype).max), 1.0)
    return (x / scale).astype(dtype).astype(jnp.float32) * scale


@partial(jax.custom_vjp, nondiff_argnums=(0,))
def _fp8_einsum(spec, a, b):
    return jnp.einsum(spec, quantize(a, jnp.float8_e4m3fn),
                      quantize(b, jnp.float8_e4m3fn), precision=HIGHEST)


def _fp8_fwd(spec, a, b):
    qa = quantize(a, jnp.float8_e4m3fn)
    qb = quantize(b, jnp.float8_e4m3fn)
    return jnp.einsum(spec, qa, qb, precision=HIGHEST), (qa, qb)


def _fp8_bwd(spec, res, g):
    qa, qb = res
    _, vjp = jax.vjp(lambda x, y: jnp.einsum(spec, x, y, precision=HIGHEST),
                     qa, qb)
    return vjp(quantize(g, jnp.float8_e5m2))


_fp8_einsum.defvjp(_fp8_fwd, _fp8_bwd)


@dataclasses.dataclass(frozen=True)
class Numerics:
    name: str

    def ein(self, spec: str, a, b):
        if self.name == "fp8":
            return _fp8_einsum(spec, a.astype(jnp.float32),
                               b.astype(jnp.float32))
        return jnp.einsum(spec, a.astype(jnp.float32), b.astype(jnp.float32),
                          precision=HIGHEST)


F32 = Numerics("f32")
FP8 = Numerics("fp8")
