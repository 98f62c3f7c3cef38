"""Arithmetic shared by the references: precision, and norms.

Every contraction goes through a ``Matmul``, and every activation the
program holds in its compute type passes its ``act``. The reference's
contractions are float32 at ``Precision.HIGHEST`` (on a TPU a float32
matmul otherwise runs in bfloat16 passes) and its ``act`` keeps float32.
The control computes in float8 where the configurations compute in
bfloat16, as float8 training does: it rounds the activations and both
operands of every contraction to e4m3, and the gradient that flows back
through each of them to e5m2, one scale per tensor, accumulating in
float32. That is the step below the configurations' precision, which the
comparison has to reject.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

E4M3_MAX = 448.0    # largest finite float8_e4m3fn
E5M2_MAX = 57344.0  # largest finite float8_e5m2


def _round(x, dtype, top):
    """x rounded to ``dtype`` with one scale for the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return (x / s).astype(dtype).astype(jnp.float32) * s


@jax.custom_vjp
def _f8(x):
    """x rounded to float8 e4m3; its cotangent, in the backward pass, to e5m2."""
    return _round(x, jnp.float8_e4m3fn, E4M3_MAX)


def _f8_fwd(x):
    return _f8(x), None


def _f8_bwd(_, g):
    return (_round(g, jnp.float8_e5m2, E5M2_MAX),)


_f8.defvjp(_f8_fwd, _f8_bwd)


class Matmul:
    def __init__(self, control: bool = False):
        self.control = control

    def act(self, x):
        return _f8(x) if self.control else x

    def __call__(self, spec, *ops):
        ops = [o.astype(jnp.float32) for o in ops]
        if self.control:
            ops = [_f8(o) for o in ops]
        return jnp.einsum(spec, *ops, precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)


REFERENCE = Matmul(False)
CONTROL = Matmul(True)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale
