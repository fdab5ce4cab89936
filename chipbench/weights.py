"""Seeded weights and inputs, made on the device in one jitted call.

The benchmark owns the weights: the program under test and the plain
reference are both handed the arrays made here, so the reference takes
nothing the program has produced.  A leaf the configuration serves in a
low precision is rounded to that type here and handed out as float32
holding exactly those values.
"""
import functools

import jax
import jax.numpy as jnp


def root_key(seed):
    """A PRNG key from any whole number (the driver's seeds pass 2**31)."""
    seed = int(seed)
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    return jax.random.fold_in(key, (seed >> 31) & 0x7FFFFFFF)


def round_to(x, dtype):
    """float32 values rounded to ``dtype``'s precision.  An explicit
    reduce_precision: a convert there and back is what XLA's
    allow-excess-precision drops on a TPU, leaving the values unrounded
    (PR 24 lost a chip call to that)."""
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant)


def _leaf(key, shape, kind, arg):
    if kind == "normal":            # N(0, arg**2)
        return arg * jax.random.normal(key, shape, jnp.float32)
    if kind == "uniform":           # U(arg[0], arg[1])
        return jax.random.uniform(key, shape, jnp.float32, arg[0], arg[1])
    if kind == "const":
        return jnp.full(shape, arg, jnp.float32)
    raise KeyError(f"unknown weight kind {kind!r}")


@functools.partial(jax.jit, static_argnums=(1, 2))
def _make(key, specs, low_dtype):
    out = {}
    for i, (name, shape, kind, arg, low) in enumerate(specs):
        w = _leaf(jax.random.fold_in(key, i), shape, kind, arg)
        if low:
            w = round_to(w, low_dtype)
        out[name] = w
    return out


def make_weights(specs, seed, low_dtype):
    """``specs``: a tuple of (name, shape, kind, arg, low) as a
    reference's ``param_specs`` gives them.  Returns {name: float32}."""
    key = jax.random.fold_in(root_key(seed), 1)
    return _make(key, tuple(specs), jnp.dtype(low_dtype))


@functools.partial(jax.jit, static_argnums=(1,))
def _make_inputs(key, specs):
    out = []
    for i, (shape, kind, lo, hi) in enumerate(specs):
        k = jax.random.fold_in(key, i)
        if kind == "uniform":
            out.append(jax.random.uniform(k, shape, jnp.float32, lo, hi))
        elif kind == "randint":
            out.append(jax.random.randint(k, shape, lo, hi, jnp.int32))
        elif kind == "zeros_int":
            out.append(jnp.zeros(shape, jnp.int32))
        else:
            raise KeyError(f"unknown input kind {kind!r}")
    return tuple(out)


def make_batches(input_specs, seed, pool):
    """``pool`` seeded batches, each a tuple of device arrays described by
    ``input_specs``: (shape, kind, lo, hi) per element."""
    key = jax.random.fold_in(root_key(seed), 2)
    return [_make_inputs(jax.random.fold_in(key, b), tuple(input_specs))
            for b in range(pool)]
