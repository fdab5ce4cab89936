"""32-bit default dtypes on the public jax.random samplers.

The 64-bit contract (docs/migration.md) is: explicit float64/int64
honored (jax_enable_x64 on), creation DEFAULTS stay 32-bit. x64 flips
jax.random's dtype-less defaults to float64/int64, and those samplers
are called from ~50 sites across the frontends (probability,
initializers, legacy random ops). Rather than threading dtype= through
every call site — and silently regressing whenever a new one lands —
wrap the public samplers once: a call WITHOUT an explicit dtype gets the
32-bit default; an explicit dtype (including 64-bit) passes through
untouched. jax's internals import from jax._src and never see these
wrappers.
"""
from __future__ import annotations

import functools
import inspect
import os

import jax
import jax.numpy as jnp

_FLOAT_SAMPLERS = [
    "normal", "uniform", "truncated_normal", "laplace", "cauchy",
    "exponential", "logistic", "gamma", "beta", "dirichlet", "gumbel",
    "pareto", "t", "chisquare", "f", "generalized_normal", "ball",
    "maxwell", "rayleigh", "wald", "weibull_min", "lognormal",
    "loggamma", "triangular",
]
_INT_SAMPLERS = ["randint", "poisson", "geometric", "binomial"]

_applied = False


def _wrap(fn, kind):
    params = inspect.signature(fn).parameters
    if "dtype" not in params:
        return fn
    dtype_pos = list(params).index("dtype")

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        if "dtype" not in kwargs and len(args) <= dtype_pos:
            # consult the mode lazily: npx.set_np(dtype=True) switches
            # the creation defaults to official-numpy 64-bit
            from .numpy_extension import default_float_dtype, \
                default_int_dtype

            kwargs["dtype"] = (default_float_dtype() if kind == "float"
                               else default_int_dtype())
        return fn(*args, **kwargs)

    wrapped.__wrapped_32bit_default__ = True
    return wrapped


def _wrap_bernoulli(fn):
    """`bernoulli` has no dtype argument: it draws uniforms in the dtype
    of `p`, and under x64 a Python-float `p` is a float64 — every
    Dropout then draws 64-bit random bits and compares in f64, which a
    TPU emulates.  A Python-float `p` takes the creation default (32-bit)
    instead; an explicit array `p` (any width) passes through."""
    @functools.wraps(fn)
    def wrapped(key, p=0.5, *args, **kwargs):
        if isinstance(p, float):
            from .numpy_extension import default_float_dtype

            p = jnp.asarray(p, default_float_dtype())
        return fn(key, p, *args, **kwargs)

    wrapped.__wrapped_32bit_default__ = True
    return wrapped


def install():
    global _applied
    if _applied:
        return
    _applied = True
    jax.random.bernoulli = _wrap_bernoulli(jax.random.bernoulli)
    for name in _FLOAT_SAMPLERS:
        fn = getattr(jax.random, name, None)
        if fn is not None and not getattr(fn, "__wrapped_32bit_default__",
                                          False):
            setattr(jax.random, name, _wrap(fn, "float"))
    for name in _INT_SAMPLERS:
        fn = getattr(jax.random, name, None)
        if fn is not None and not getattr(fn, "__wrapped_32bit_default__",
                                          False):
            setattr(jax.random, name, _wrap(fn, "int"))


# what JAX stores: (config name, the environment variable that sets it,
# what we set where the user's environment does not)
_CACHE_POLICY = (
    ("jax_persistent_cache_min_compile_time_secs",
     "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", 0.0),
    ("jax_persistent_cache_min_entry_size_bytes",
     "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES", -1),
)


def place_compile_cache():
    """Point JAX's persistent compilation cache at a fixed directory, and
    have it keep every program.

    Every chip call starts on a fresh machine and a whole-step program
    takes minutes to compile, so the cache must survive the process —
    and its path is part of the cache key, so it must not move (no
    tempfile, pid or timestamp).  Placed from outside with
    ``JAX_COMPILATION_CACHE_DIR`` (JAX reads the variable itself and no
    directory is set here); otherwise ``<checkout>/.jax_cache``, next to
    the package.

    JAX stores only what took a second to compile.  A process builds
    dozens of programs that take a tenth of one (a copy, a cast, an
    initializer's draw of each distinct shape) and, never stored, builds
    them anew at every start: seconds of set-up, every time.  So the
    threshold goes to 0 and the size limit to none, on every platform and
    wherever the directory came from — unless JAX's own variable for
    either is in the environment, which then stands
    (docs/compile_cache.md).  Returns the directory in effect."""
    for name, var, ours in _CACHE_POLICY:
        if var not in os.environ:
            jax.config.update(name, ours)
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        from jax.experimental.compilation_cache import compilation_cache

        checkout = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        jax.config.update("jax_compilation_cache_dir",
                          os.path.join(checkout, ".jax_cache"))
        # JAX decides once, at its first compile, whether the cache is in
        # use; if something compiled before this import, start it over
        compilation_cache.reset_cache()
    return jax.config.jax_compilation_cache_dir
