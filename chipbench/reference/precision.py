"""Precision of the plain references: matmuls at ``highest``, and the
rounding that turns a reference into the control of ``correct``."""
import functools

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def _round(x, dt):
    """``x`` rounded to the floating type ``dt`` under a per-tensor scale.
    reduce_precision, not a convert there and back, which XLA may drop;
    its exponent range tops out one binade under an "fn" type's, so the
    scale maps the largest entry to that lower top."""
    info = jnp.finfo(dt)
    top = (2.0 - 2.0 ** -info.nmant) * 2.0 ** (2 ** (info.nexp - 1) - 1)
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / top
    return lax.reduce_precision(x / scale, exponent_bits=info.nexp,
                                mantissa_bits=info.nmant) * scale


# the type a lower-precision path keeps its cotangents in
_GRAD_TYPE = {"float8_e4m3fn": "float8_e5m2"}


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def _q(x, precision):
    """An operand of a convolution or matmul in ``precision``: rounded
    on the way in, its cotangent rounded on the way back."""
    if precision == "float32":
        return x
    return _round(x, jnp.dtype(precision))


def _q_fwd(x, precision):
    return _q(x, precision), None


def _q_bwd(precision, _res, ct):
    if precision == "float32":
        return (ct,)
    return (_round(ct, jnp.dtype(_GRAD_TYPE.get(precision, precision))),)


_q.defvjp(_q_fwd, _q_bwd)
