"""Seeded weights and inputs, made on the device in one jitted call.

What ``--seed`` draws: the traffic always (the pool's batches; where the
traffic states its pool, ``pool_seed``, the order in which the run meets
them, the same batches first: `pool_order`); the weights too, unless the configuration states ONE
draw of them (`weights_seed`), as a checkpoint is one set of routers.

The benchmark owns the weights: the program under test and the plain
reference are both handed the arrays made here, so the reference takes
nothing the program has produced.  A leaf the configuration serves in a
low precision is rounded to that type here and handed out as float32
holding exactly those values.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as onp


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


def weights_seed(cfg, seed):
    """The seed of a run's weights: ``--seed``, unless the configuration
    states ONE draw of them, ``weights_seed``, as a checkpoint is one set
    of routers."""
    return int(cfg.get("weights_seed", seed))


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


def pool_order(seed, pool, head):
    """The order in which a run meets a STATED pool's batches
    (``traffic_params.pool_seed``), drawn from ``--seed`` on the host: the
    pool's first ``head`` batches in a drawn order, then the others in a
    drawn order.  Every seed runs the same set of work in another order,
    and the same batches take the optimizer's first steps (``head`` is how
    many a run compares): which batches those are sets which way the
    routers drift, and with it how much work the later steps are."""
    seed = int(seed)
    rng = onp.random.default_rng([seed & 0x7FFFFFFF,
                                  (seed >> 31) & 0x7FFFFFFF])
    return ([int(i) for i in rng.permutation(head)]
            + [head + int(i) for i in rng.permutation(pool - head)])
