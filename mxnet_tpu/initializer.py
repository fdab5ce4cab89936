"""Weight initializers (reference: python/mxnet/initializer.py, 832 LoC).

Same registry + string-alias behavior: `net.initialize(init='xavier')` works.
Initializers draw from the global stateful RNG (mx._random) so mx.seed()
reproduces parameter init exactly.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp
import numpy as _np

from . import _random
from .base import registry
from .ndarray.ndarray import NDArray

_REG = registry("initializer")

__all__ = ["Initializer", "InitDesc", "Zero", "One", "Constant", "Uniform",
           "Normal", "Orthogonal", "Xavier", "MSRAPrelu", "Bilinear",
           "LSTMBias", "Mixed", "Load", "RNNFused", "register", "create"]


def register(klass):
    _REG.register(klass)
    # also register lowercase short alias (Xavier -> xavier)
    return klass


def create(init, **kwargs):
    if isinstance(init, Initializer):
        return init
    if isinstance(init, str):
        if init.startswith("["):  # serialized [name, kwargs] form
            name, kw = json.loads(init)
            return _REG.create(name, **kw)
        return _REG.create(init, **kwargs)
    raise TypeError(f"cannot create initializer from {init!r}")


class Initializer:
    """Base initializer. Subclasses implement _init_weight(name, shape, dtype)
    returning a jax array."""

    def __init__(self, **kwargs):
        self._kwargs = kwargs

    def dumps(self):
        return json.dumps([type(self).__name__.lower(), self._kwargs])

    def __call__(self, name, arr=None, explicit=False):
        """Initialize `arr` in place.

        Mirrors the reference's dispatch protocol (initializer.py:140):
        if `name` is an InitDesc carrying a declared init in
        attrs['__init__'] (the Gluon Parameter path), that declared
        initializer's _init_weight applies regardless of the name
        suffix. `explicit=True` forces THIS initializer's _init_weight
        the same way. Otherwise the legacy suffix table runs
        (bias/beta/moving stats → 0, gamma/moving var → 1, else
        _init_weight). Global initializers with a custom __call__
        (Load, Mixed) never consult the declared init — they drive,
        exactly like the reference."""
        if arr is None:
            name, arr = getattr(name, "name", str(name)), name
            name = str(name)
        declared = None
        attrs = getattr(name, "attrs", None)
        if attrs:
            declared = attrs.get("__init__")
        name = str(name)
        shape, dtype = arr.shape, arr.dtype
        lname = name.lower()
        if declared is not None:
            data = create(declared)._init_weight(name, shape, dtype)
        elif explicit:
            data = self._init_weight(name, shape, dtype)
        elif lname.endswith("bias") or lname.endswith("beta") or \
                lname.endswith("running_mean") or lname.endswith("moving_mean"):
            data = _np.zeros(shape, dtype)
        elif lname.endswith("gamma") or lname.endswith("running_var") or \
                lname.endswith("moving_var"):
            data = _np.ones(shape, dtype)
        else:
            data = self._init_weight(name, shape, dtype)
        if isinstance(arr, NDArray):
            arr._data = jnp.asarray(data, dtype)
            arr._version += 1
        return arr

    def init_array(self, name, shape, dtype, explicit=False):
        # the holder only says what shape and type to fill.  Constants
        # are made on the host throughout this file: `jnp.zeros` is an
        # XLA program per distinct (shape, type), a transfer is none
        out = NDArray(_np.zeros(shape, dtype))
        self(name, out, explicit=explicit)
        return out

    def _init_weight(self, name, shape, dtype):
        raise NotImplementedError

    def __repr__(self):
        return f"{type(self).__name__}({self._kwargs})"


@register
class Zero(Initializer):
    def _init_weight(self, name, shape, dtype):
        return _np.zeros(shape, dtype)


_REG.register(Zero, "zeros")


@register
class One(Initializer):
    def _init_weight(self, name, shape, dtype):
        return _np.ones(shape, dtype)


_REG.register(One, "ones")


@register
class Constant(Initializer):
    def __init__(self, value=0.0):
        super().__init__(value=value)
        self.value = value

    def _init_weight(self, name, shape, dtype):
        value = self.value
        if isinstance(value, NDArray):      # NumPy will not fill from one
            value = value.asnumpy()
        return _np.full(shape, value, dtype)


@register
class Uniform(Initializer):
    def __init__(self, scale=0.07):
        super().__init__(scale=scale)
        self.scale = scale

    def _init_weight(self, name, shape, dtype):
        key = _random.next_key()
        return jax.random.uniform(key, shape, jnp.float32, -self.scale,
                                  self.scale).astype(dtype)


@register
class Normal(Initializer):
    def __init__(self, sigma=0.01):
        super().__init__(sigma=sigma)
        self.sigma = sigma

    def _init_weight(self, name, shape, dtype):
        key = _random.next_key()
        return (jax.random.normal(key, shape, jnp.float32)
                * self.sigma).astype(dtype)


@register
class Orthogonal(Initializer):
    def __init__(self, scale=1.414, rand_type="uniform"):
        super().__init__(scale=scale, rand_type=rand_type)
        self.scale = scale

    def _init_weight(self, name, shape, dtype):
        key = _random.next_key()
        flat = (shape[0], int(jnp.prod(jnp.asarray(shape[1:]))))
        out = jax.nn.initializers.orthogonal(self.scale)(key, flat, jnp.float32)
        return out.reshape(shape).astype(dtype)


def _fans(shape, factor_type):
    hw = 1
    for d in shape[2:]:
        hw *= d
    fan_out = shape[0] * hw
    fan_in = (shape[1] if len(shape) > 1 else shape[0]) * hw
    if factor_type == "avg":
        return (fan_in + fan_out) / 2.0
    if factor_type == "in":
        return fan_in
    return fan_out


@register
class Xavier(Initializer):
    """Xavier/Glorot (reference initializer.py:Xavier; default for Gluon)."""

    def __init__(self, rnd_type="uniform", factor_type="avg", magnitude=3):
        super().__init__(rnd_type=rnd_type, factor_type=factor_type,
                         magnitude=magnitude)
        self.rnd_type = rnd_type
        self.factor_type = factor_type
        self.magnitude = magnitude

    def _init_weight(self, name, shape, dtype):
        factor = max(_fans(shape, self.factor_type), 1.0)
        scale = math.sqrt(self.magnitude / factor)
        key = _random.next_key()
        if self.rnd_type == "uniform":
            w = jax.random.uniform(key, shape, jnp.float32, -scale, scale)
        else:
            w = jax.random.normal(key, shape, jnp.float32) * scale
        return w.astype(dtype)


@register
class MSRAPrelu(Xavier):
    """He initialization (reference: MSRAPrelu)."""

    def __init__(self, factor_type="avg", slope=0.25):
        magnitude = 2.0 / (1 + slope ** 2)
        super().__init__("gaussian", factor_type, magnitude)
        self._kwargs = {"factor_type": factor_type, "slope": slope}


@register
class Bilinear(Initializer):
    """Bilinear upsampling kernel (reference: Bilinear, for Deconvolution)."""

    def _init_weight(self, name, shape, dtype):
        import numpy as onp

        weight = onp.zeros(shape, dtype="float32")
        f = math.ceil(shape[3] / 2.0)
        c = (2 * f - 1 - f % 2) / (2.0 * f)
        for i in range(int(onp.prod(shape))):
            x = i % shape[3]
            y = (i // shape[3]) % shape[2]
            flat = weight.reshape(-1)
            flat[i] = (1 - abs(x / f - c)) * (1 - abs(y / f - c))
        return jnp.asarray(weight, dtype)


@register
class LSTMBias(Initializer):
    """Forget-gate bias = 1 (reference: LSTMBias)."""

    def __init__(self, forget_bias=1.0):
        super().__init__(forget_bias=forget_bias)
        self.forget_bias = forget_bias

    def _init_weight(self, name, shape, dtype):
        b = jnp.zeros(shape, dtype)
        n = shape[0] // 4
        return b.at[n : 2 * n].set(self.forget_bias)


class InitDesc(str):
    """Parameter-name descriptor carrying init attrs (reference:
    initializer.py InitDesc — a str subclass so it drops into every
    name-taking API)."""

    def __new__(cls, name, attrs=None, global_init=None):
        ret = super().__new__(cls, name)
        ret.attrs = attrs or {}
        ret.global_init = global_init
        return ret


class Mixed(Initializer):
    """Route parameters to initializers by name-regex patterns
    (reference: initializer.py Mixed)."""

    def __init__(self, patterns, initializers):
        import re

        super().__init__()
        if len(patterns) != len(initializers):
            raise ValueError("patterns and initializers must pair up")
        self.map = [(re.compile(p), create(i)) for p, i in
                    zip(patterns, initializers)]

    def __call__(self, name, arr=None, explicit=False):  # noqa: ARG002
        if arr is None:
            name, arr = getattr(name, "name", str(name)), name
        name = str(name)  # the matched pattern drives, not declared inits
        for prog, init in self.map:
            if prog.match(name):
                return init(name, arr, explicit=True)
        raise ValueError(
            f"Parameter name {name} did not match any pattern; consider "
            "adding a '.*' pattern at the end with a default initializer")


class Load(Initializer):
    """Initialize from a saved name→array dict / .npz path, falling back
    to `default_init` for missing names (reference: initializer.py Load)."""

    def __init__(self, param, default_init=None, verbose=False):
        super().__init__()
        if isinstance(param, str):
            from .ndarray.utils import load as _load

            param = _load(param)
        self.param = {}
        for name, arr in dict(param).items():
            key = name[4:] if name.startswith(("arg:", "aux:")) else name
            self.param[key] = arr
        self.default_init = default_init
        self.verbose = verbose

    def __call__(self, name, arr=None, explicit=False):  # noqa: ARG002
        if arr is None:
            name, arr = getattr(name, "name", str(name)), name
        name = str(name)
        if name in self.param:
            src = self.param[name]
            src_np = src.asnumpy() if hasattr(src, "asnumpy") else src
            if tuple(arr.shape) != tuple(src_np.shape):
                raise ValueError(
                    f"Parameter {name} cannot be initialized from "
                    f"loading: shape mismatch, target {tuple(arr.shape)} "
                    f"vs loaded {tuple(src_np.shape)}")
            arr._data = jnp.asarray(src_np, arr.dtype)
            arr._version += 1
            return arr
        if self.default_init is None:
            raise ValueError(
                f"Cannot initialize {name}: not in the loaded params and "
                "no default initializer was provided")
        # the caller chose this fallback — apply it verbatim
        return create(self.default_init)(name, arr, explicit=True)


@register
class RNNFused(Initializer):
    """Initialize a fused-RNN flat parameter blob: weight segments from
    the (optional) per-segment initializers or Uniform(scale), bias
    segments zero (reference: initializer.py RNNFused; layout per
    ops/rnn.py slice_rnn_params / reference rnn-inl.h)."""

    def __init__(self, mode, num_layers, state_size, bidirectional=False,
                 projection_size=None, scale=0.07,
                 i2h_weight_initializer=None, h2h_weight_initializer=None,
                 i2h_bias_initializer=None, h2h_bias_initializer=None,
                 h2r_weight_initializer=None):
        super().__init__(mode=mode, num_layers=num_layers,
                         state_size=state_size, bidirectional=bidirectional,
                         projection_size=projection_size, scale=scale,
                         i2h_weight_initializer=i2h_weight_initializer,
                         h2h_weight_initializer=h2h_weight_initializer,
                         i2h_bias_initializer=i2h_bias_initializer,
                         h2h_bias_initializer=h2h_bias_initializer,
                         h2r_weight_initializer=h2r_weight_initializer)
        from .ops.rnn import _GATES

        self.gates = _GATES[mode]
        self.num_layers = num_layers
        self.state_size = state_size
        self.dirs = 2 if bidirectional else 1
        self.projection_size = projection_size
        self.scale = scale
        mk = lambda i, d: create(i) if i is not None else d  # noqa: E731
        default_w = Uniform(scale)
        self._i2h_w = mk(i2h_weight_initializer, default_w)
        self._h2h_w = mk(h2h_weight_initializer, default_w)
        self._i2h_b = mk(i2h_bias_initializer, Zero())
        self._h2h_b = mk(h2h_bias_initializer, Zero())
        self._h2r_w = mk(h2r_weight_initializer, default_w)

    def _input_size(self, total):
        """Invert ops/rnn.py rnn_param_size for the input width."""
        L, D, G, H = (self.num_layers, self.dirs, self.gates,
                      self.state_size)
        P = self.projection_size
        ghd = G * H * D
        if P:
            rest = (L - 1) * (P * D + P + 2) * ghd + P * H * L * D
            return (total - rest) // ghd - P - 2
        rest = (L - 1) * (H * D + H + 2) * ghd
        return (total - rest) // ghd - H - 2

    def _init_weight(self, name, shape, dtype):
        from .ops.rnn import rnn_param_size

        total = int(shape[0])
        in_size = int(self._input_size(total))
        want = rnn_param_size(self.num_layers, in_size, self.state_size,
                              self.dirs == 2, self._kwargs["mode"],
                              self.projection_size)
        if in_size <= 0 or want != total:
            raise ValueError(
                f"RNNFused: flat size {total} inconsistent with "
                f"mode={self._kwargs['mode']} layers={self.num_layers} "
                f"state={self.state_size}")
        L, D, G, H = (self.num_layers, self.dirs, self.gates,
                      self.state_size)
        P = self.projection_size or 0
        R = P or H
        segs = []

        def seg(init, n, sub):
            segs.append(jnp.ravel(jnp.asarray(
                init.init_array(f"{name}_{sub}", (n,), dtype,
                                explicit=True)._data)))

        for layer in range(L):
            in_l = in_size if layer == 0 else R * D
            for _d in range(D):
                seg(self._i2h_w, G * H * in_l, "i2h_weight")
                seg(self._h2h_w, G * H * R, "h2h_weight")
                if P:
                    seg(self._h2r_w, P * H, "h2r_weight")
        for _ in range(L * D):
            seg(self._i2h_b, G * H, "i2h_bias")
            seg(self._h2h_b, G * H, "h2h_bias")
        return jnp.concatenate(segs).astype(dtype)


# friendly aliases matching the reference registry
_REG.register(Xavier, "xavier")
_REG.register(MSRAPrelu, "msra")
_REG.register(Normal, "gaussian")
_REG.register(Uniform, "uniform")
_REG.register(Normal, "normal")
_REG.register(Zero, "zero")
_REG.register(One, "one")
