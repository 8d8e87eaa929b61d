"""What both plain references share: the precision they compute in and
the lower precision of the control.

The reference computes in float32 with every matmul at ``highest``
precision (on a TPU a float32 matmul otherwise runs in one bfloat16
pass). The control is the same mathematics with every matmul's operands
— forward and backward — rounded to fp8 (e4m3, scaled per tensor to the
format's range), the nearest precision below the bfloat16 the
configurations state.
"""

import functools

import jax
import jax.numpy as jnp

FP8 = jnp.float8_e4m3fn
FP8_MAX = 448.0


def fp8_round(x):
    """Round to fp8 e4m3 under a per-tensor scale; returns float32."""
    x = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, FP8_MAX / amax, 1.0)
    return (x * scale).astype(FP8).astype(jnp.float32) / scale


def make_einsum(precision="float32"):
    """``einsum(spec, a, b)`` in the named precision: ``float32`` (the
    reference) or ``fp8`` (the control)."""
    if precision == "float32":
        return lambda spec, a, b: jnp.einsum(
            spec, a, b, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    if precision != "fp8":
        raise ValueError(f"unknown precision {precision!r}")

    def plain(spec, a, b):
        return jnp.einsum(
            spec, a, b, precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )

    @functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
    def mm(spec, a, b):
        return plain(spec, fp8_round(a), fp8_round(b))

    def fwd(spec, a, b):
        return mm(spec, a, b), (a, b)

    def bwd(spec, res, g):
        a, b = res
        _, vjp = jax.vjp(
            lambda x, y: plain(spec, x, y), fp8_round(a), fp8_round(b)
        )
        return vjp(fp8_round(g))

    mm.defvjp(fwd, bwd)
    return mm
