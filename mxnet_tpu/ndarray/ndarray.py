"""NDArray: an engine-tracked, mutable n-dim array over immutable jax.Arrays.

Re-design of the reference NDArray (include/mxnet/ndarray.h:81,
src/ndarray/ndarray.cc) for the XLA/PJRT world:

  * the reference's Chunk{storage, Engine::Var} pair becomes a single
    `jax.Array` handle — PJRT owns the HBM buffer, XLA tracks dependencies;
  * mutation (`a[:]=v`, `a+=b`, fused optimizer updates) is implemented by
    computing a fresh functional value and swapping the handle, bumping
    `_version` — exactly the reference's `ThreadedVar::version_` bump on a
    write dependency (src/engine/threaded_engine.h:122);
  * eager ops dispatch through `apply_op`, which (a) unwraps inputs,
    (b) runs the pure jax function (async on device), (c) wraps outputs, and
    (d) when autograd is recording, routes the call through `jax.vjp` and
    records a TapeNode — the analog of Imperative::Invoke + RecordOp
    (src/imperative/imperative.cc:105,235);
  * `wait_to_read` / `asnumpy` are the sync points, as in the reference
    (ndarray.h:394; NDArray::SyncCopyToCPU).

Sparse storage types (row_sparse/CSR) live in ndarray/sparse.py as a
storage + communication format (construction/cast/retain eager; sparse·dense
dot via XLA gather/segment_sum/scatter-add; kvstore row_sparse push/pull) —
see that module's docstring for the TPU design rationale.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as _np

from .. import autograd as ag
from .. import engine
from ..base import MXNetError, normalize_dtype
from ..device import Device, current_device, from_jax_device
from ..telemetry import instruments as _telemetry

__all__ = ["NDArray", "apply_op", "array", "from_jax", "waitall"]

_Tracer = jax.core.Tracer


def _is_concrete(data):
    return not isinstance(data, _Tracer)


class NDArray:
    """Mutable array facade over a jax.Array (or a tracer during jit tracing)."""

    __array_priority__ = 1000.0

    __slots__ = (
        "_data",
        "_device",
        "_grad",
        "_grad_req",
        "_tape_entry",
        "_version",
        "__weakref__",
    )

    def __init__(self, data, device=None):
        self._data = data
        self._device = device
        self._grad = None
        self._grad_req = "null"
        self._tape_entry = None
        self._version = 0
        if _is_concrete(data):
            engine.track(self)

    # ------------------------------------------------------------------
    # metadata
    # ------------------------------------------------------------------
    @property
    def shape(self):
        return tuple(self._data.shape)

    @property
    def dtype(self):
        return _np.dtype(self._data.dtype)

    @property
    def size(self):
        s = 1
        for d in self._data.shape:
            s *= int(d)
        return s

    @property
    def ndim(self):
        return self._data.ndim

    @property
    def device(self):
        if self._device is not None:
            return self._device
        if _is_concrete(self._data):
            devs = getattr(self._data, "devices", None)
            if devs is not None:
                return from_jax_device(next(iter(self._data.devices())))
        return current_device()

    # reference-compat aliases
    ctx = device
    context = device

    @property
    def stype(self):
        return "default"

    def tostype(self, stype):
        """Cast to a storage type ('default'/'csr'/'row_sparse');
        see ndarray/sparse.py for the TPU sparse design."""
        if stype == "default":
            return self
        from .sparse import cast_storage

        return cast_storage(self, stype)

    @property
    def grad(self):
        return self._grad

    @property
    def _requires_grad_entry(self):
        """True if ops consuming this array must be taped."""
        return self._tape_entry is not None or (
            self._grad is not None and self._grad_req != "null"
        )

    # ------------------------------------------------------------------
    # sync / host transfer
    # ------------------------------------------------------------------
    def wait_to_read(self):
        engine.wait_to_read(self)
        return self

    def wait_to_write(self):
        engine.wait_to_read(self)
        return self

    def asnumpy(self):
        """Blocking copy to host numpy (reference: NDArray::SyncCopyToCPU)."""
        if _is_concrete(self._data):
            _telemetry.record_transfer("d2h", _telemetry.nbytes_of(self._data))
        return _np.asarray(self._data)

    def item(self):
        return self.asnumpy().item()

    def asscalar(self):
        if self.size != 1:
            raise ValueError("The current array is not a scalar")
        return self.item()

    def __float__(self):
        return float(self.item())

    def __int__(self):
        return int(self.item())

    def __bool__(self):
        if self.size == 0:
            return False
        if self.size == 1:
            return bool(self.item())
        raise ValueError(
            "The truth value of an array with more than one element is ambiguous"
        )

    def __len__(self):
        if self.ndim == 0:
            raise TypeError("len() of unsized object")
        return self.shape[0]

    def __iter__(self):
        for i in range(len(self)):
            yield self[i]

    def __repr__(self):
        if _is_concrete(self._data):
            return f"{self.asnumpy()!r} <NDArray {self.shape} @{self.device}>"
        return f"<NDArray traced {self.shape} {self.dtype}>"

    # numpy protocol
    def __array__(self, dtype=None, copy=None):  # noqa: ARG002
        a = self.asnumpy()
        return a.astype(dtype) if dtype is not None else a

    # NEP-13/NEP-18 dispatch (reference:
    # python/mxnet/numpy_dispatch_protocol.py:1-334): `onp.mean(mx_arr)`
    # runs the mx.np implementation ON DEVICE and returns an NDArray
    # instead of silently copying to host through __array__.
    _NOOP_KWARGS = ("out", "where", "casting", "order", "subok",
                    "signature")

    @staticmethod
    def _np_impl(name):
        from .. import numpy as _mxnp

        fn = getattr(_mxnp, name, None)
        if fn is None and hasattr(_mxnp, "linalg"):
            fn = getattr(_mxnp.linalg, name, None)
        return fn

    @staticmethod
    def _write_out(result, out):
        """Land `result` in a caller-supplied out buffer with numpy's
        shape/dtype contract (no silent reshapes)."""
        target = out[0] if isinstance(out, tuple) else out
        rdata = result._data if isinstance(result, NDArray) else result
        if tuple(rdata.shape) != tuple(target.shape):
            raise ValueError(
                f"non-broadcastable output operand with shape "
                f"{tuple(target.shape)} doesn't match the result shape "
                f"{tuple(rdata.shape)}")
        if isinstance(target, NDArray):
            if isinstance(result, NDArray) and \
                    result.dtype != target.dtype:
                # cast THROUGH the tape so the stored data and the taped
                # vjp node agree on dtype (else backward's cotangent
                # dtype mismatches)
                result = result.astype(target.dtype)
            target._data = result._data if isinstance(result, NDArray) \
                else rdata.astype(target._data.dtype)
            target._version += 1
            # an out= write must stay on the autograd tape exactly like
            # the expression it landed (cf. _assign_from)
            target._tape_entry = result._tape_entry \
                if isinstance(result, NDArray) else None
            return target
        # plain numpy out: copy device result to host (legacy behavior)
        _np.copyto(target, _np.asarray(rdata).astype(target.dtype))
        return target

    def __array_ufunc__(self, ufunc, method, *inputs, **kwargs):
        if method != "__call__":
            return NotImplemented
        out = kwargs.pop("out", None)
        if out is not None:
            target = out[0] if isinstance(out, tuple) else out
            if not isinstance(target, (NDArray, _np.ndarray)):
                return NotImplemented
        for k in NDArray._NOOP_KWARGS:
            if kwargs.get(k) is None:
                kwargs.pop(k, None)
        dtype = kwargs.pop("dtype", None)
        if kwargs and set(kwargs) - {"axis"}:
            return NotImplemented
        fn = NDArray._np_impl(ufunc.__name__)
        if fn is None:
            return NotImplemented
        result = fn(*inputs, **kwargs)
        if dtype is not None and isinstance(result, NDArray):
            result = result.astype(dtype)   # jnp ufuncs take no dtype=
        if out is not None:
            return NDArray._write_out(result, out)
        return result

    def __array_function__(self, func, types, args, kwargs):
        if not all(issubclass(t, (NDArray, _np.ndarray)) or
                   t in (int, float, bool, list, tuple) for t in types):
            return NotImplemented
        fn = NDArray._np_impl(func.__name__)
        if fn is None:
            return NotImplemented
        kwargs = dict(kwargs)
        out = kwargs.pop("out", None)
        if out is not None and not isinstance(
                out[0] if isinstance(out, tuple) else out,
                (NDArray, _np.ndarray)):
            return NotImplemented
        for k in NDArray._NOOP_KWARGS:
            if kwargs.get(k) is None:
                kwargs.pop(k, None)
        result = fn(*args, **kwargs)
        if out is not None:
            return NDArray._write_out(result, out)
        return result

    def __dlpack__(self, **kwargs):
        return self._data.__dlpack__(**kwargs)

    def __dlpack_device__(self):
        return self._data.__dlpack_device__()

    # ------------------------------------------------------------------
    # autograd surface
    # ------------------------------------------------------------------
    def attach_grad(self, grad_req="write", stype=None):  # noqa: ARG002
        """Attach a zero-initialized gradient buffer (reference:
        python/mxnet/ndarray/ndarray.py attach_grad). On an array that is
        already part of a recorded graph this RETAINS the mid-graph
        gradient: backward lands the array's output cotangent in .grad
        while still flowing through it (reference retain-grad
        semantics)."""
        self._grad = _wrap_out(jnp.zeros_like(self._data))
        self._grad_req = grad_req
        if self._tape_entry is not None:
            import weakref

            node, idx = self._tape_entry
            if node.vjp_fn is None:
                # producer tape already consumed: nothing can flow
                # through — this array becomes a fresh leaf (the old
                # detach semantics)
                self._tape_entry = None
                return self
            if node.retained is None:
                node.retained = []
            # re-attach replaces, never duplicates (each entry lands the
            # cotangent once)
            node.retained = [(r, i) for r, i in node.retained
                             if r() is not None and r() is not self]
            node.retained.append((weakref.ref(self), idx))
        return self

    def drop_grad(self):
        self._grad = None
        self._grad_req = "null"

    def backward(self, out_grad=None, retain_graph=False, train_mode=True):
        ag.backward([self], [out_grad], retain_graph=retain_graph,
                    train_mode=train_mode)

    def detach(self):
        out = NDArray(self._data, self._device)
        return out

    # ------------------------------------------------------------------
    # device movement / copies
    # ------------------------------------------------------------------
    def as_in_context(self, device):
        return self.as_in_ctx(device)

    def as_in_ctx(self, device):
        device = Device(device) if not isinstance(device, Device) else device
        if self.device == device:
            return self
        return self.copyto(device)

    to_device = as_in_ctx

    def copyto(self, other):
        """Copy to a device or into another NDArray (reference: CopyFromTo,
        src/ndarray/ndarray.cc:1370)."""
        if isinstance(other, (Device, NDArray)) and _is_concrete(self._data):
            _telemetry.record_transfer("d2d", _telemetry.nbytes_of(self._data))
        if isinstance(other, Device):
            data = jax.device_put(self._data, other.jax_device)
            return NDArray(data, other)
        if isinstance(other, NDArray):
            other._data = jax.device_put(self._data, other.device.jax_device)
            other._version += 1
            return other
        raise TypeError(f"copyto does not support type {type(other)}")

    def copy(self):
        return _wrap_out(jnp.copy(self._data), self._device)

    def astype(self, dtype, copy=True):
        dtype = normalize_dtype(dtype)
        if not copy and self.dtype == dtype:
            return self
        return apply_op(lambda x: x.astype(dtype), self)

    def as_np_ndarray(self):
        return self

    def as_nd_ndarray(self):
        return self

    # ------------------------------------------------------------------
    # shape manipulation (differentiable, taped via apply_op)
    # ------------------------------------------------------------------
    def reshape(self, *shape, **kwargs):
        """Reshape supporting the reference's special codes on the METHOD
        (reference: ndarray/ndarray.py:1446-1501 — 0 copy-dim, -1 infer,
        -2 copy-rest, -3 merge-two, -4 split, `reverse=1` right-to-left).

        One class serves both frontends here, so dispatch is by content:
        plain dims and -1 are numpy-identical; -2/-3/-4, `reverse`, and a
        0 against a non-empty array (numpy would error) take the legacy
        path. A 0 with an empty array keeps numpy semantics."""
        reverse = bool(kwargs.pop("reverse", False))
        if not shape and "shape" in kwargs:
            shape = (kwargs.pop("shape"),)  # a.reshape(shape=(m, n))
        kwargs.pop("order", None)  # numpy-style kwarg; only 'C' layouts here
        if kwargs:
            raise TypeError(f"reshape got unexpected kwargs {sorted(kwargs)}")
        if len(shape) == 1 and isinstance(shape[0], (tuple, list)):
            shape = tuple(shape[0])
        shape = tuple(int(d) for d in shape)
        legacy = reverse or any(d in (-2, -3, -4) for d in shape) \
            or (0 in shape and self.size != 0)
        if legacy:
            from ..ops.tensor import legacy_reshape_shape

            new_shape = legacy_reshape_shape(self.shape, shape, reverse)
            return apply_op(lambda x: jnp.reshape(x, new_shape), self)
        return apply_op(lambda x: jnp.reshape(x, shape), self)

    def transpose(self, *axes, **kwargs):
        if not axes and kwargs.get("axes") is not None:
            axes = (kwargs.pop("axes"),)  # legacy kwarg spelling
        else:
            kwargs.pop("axes", None)  # axes=None == reverse all
        if kwargs:
            raise TypeError(
                f"transpose got unexpected kwargs {sorted(kwargs)}")
        if len(axes) == 1 and isinstance(axes[0], (tuple, list)):
            axes = tuple(axes[0])
        ax = axes if axes else None
        return apply_op(lambda x: jnp.transpose(x, ax), self)

    @property
    def T(self):
        return self.transpose()

    def flatten(self):
        return self.reshape((-1,))

    def squeeze(self, axis=None):
        return apply_op(lambda x: jnp.squeeze(x, axis), self)

    def expand_dims(self, axis):
        return apply_op(lambda x: jnp.expand_dims(x, axis), self)

    def swapaxes(self, a1, a2):
        return apply_op(lambda x: jnp.swapaxes(x, a1, a2), self)

    def repeat(self, repeats, axis=None):
        return apply_op(lambda x: jnp.repeat(x, repeats, axis), self)

    def broadcast_to(self, shape):
        return apply_op(lambda x: jnp.broadcast_to(x, shape), self)

    def split(self, indices_or_sections=None, axis=None, num_outputs=None,
              squeeze_axis=False):
        if num_outputs is not None:
            # legacy spelling (reference nd.split: num_outputs/squeeze_axis,
            # default axis=1 — slice_channel in matrix_op.cc)
            from .. import ndarray as _nd_ns

            return _nd_ns.split(self, num_outputs=num_outputs,
                                axis=1 if axis is None else axis,
                                squeeze_axis=squeeze_axis)
        if squeeze_axis:
            # loud: the legacy kwarg only applies with num_outputs= —
            # silently splitting on numpy's axis-0 default instead would
            # hand back wrongly-shaped sections
            raise TypeError(
                "split: squeeze_axis requires the legacy num_outputs= "
                "spelling (a.split(num_outputs=2, squeeze_axis=True)); "
                "positional arg means numpy indices_or_sections here — "
                "see docs/migration.md")
        return self._split_np(indices_or_sections,
                              0 if axis is None else axis)

    def _split_np(self, indices_or_sections, axis=0):
        return apply_op(
            lambda x: tuple(jnp.split(x, indices_or_sections, axis)), self
        )

    def take(self, indices, axis=None, mode="clip"):
        # float indices cast (both reference classes tolerate them —
        # legacy arrays default to float32, indexing_op.h casts);
        # python ints/lists pass through jnp.asarray first
        def pure(x, i):
            i = jnp.asarray(i)
            if not (jnp.issubdtype(i.dtype, jnp.integer)
                    or i.dtype == jnp.bool_):
                i = i.astype(jnp.int32)
            return jnp.take(x, i, axis=axis, mode=mode)

        return apply_op(pure, self, indices)

    def clip(self, a_min=None, a_max=None):
        return apply_op(lambda x: jnp.clip(x, a_min, a_max), self)

    def zeros_like(self):
        return _wrap_out(jnp.zeros_like(self._data), self._device)

    def ones_like(self):
        return _wrap_out(jnp.ones_like(self._data), self._device)

    def tolist(self):
        return self.asnumpy().tolist()

    # reductions / common math as methods
    def sum(self, axis=None, keepdims=False, dtype=None):
        return apply_op(
            lambda x: jnp.sum(x, axis=axis, keepdims=keepdims,
                              dtype=normalize_dtype(dtype)), self)

    def mean(self, axis=None, keepdims=False, dtype=None):
        return apply_op(
            lambda x: jnp.mean(x, axis=axis, keepdims=keepdims,
                               dtype=normalize_dtype(dtype)), self)

    def max(self, axis=None, keepdims=False):
        return apply_op(lambda x: jnp.max(x, axis=axis, keepdims=keepdims), self)

    def min(self, axis=None, keepdims=False):
        return apply_op(lambda x: jnp.min(x, axis=axis, keepdims=keepdims), self)

    def prod(self, axis=None, keepdims=False):
        return apply_op(lambda x: jnp.prod(x, axis=axis, keepdims=keepdims), self)

    def any(self, axis=None, keepdims=False):
        return apply_op(lambda x: jnp.any(x, axis=axis, keepdims=keepdims),
                        self)

    def all(self, axis=None, keepdims=False):
        return apply_op(lambda x: jnp.all(x, axis=axis, keepdims=keepdims),
                        self)

    def argmax(self, axis=None):
        return apply_op(lambda x: jnp.argmax(x, axis=axis), self)

    def argmin(self, axis=None):
        return apply_op(lambda x: jnp.argmin(x, axis=axis), self)

    def std(self, axis=None, ddof=0, keepdims=False):
        return apply_op(
            lambda x: jnp.std(x, axis=axis, ddof=ddof, keepdims=keepdims), self)

    def var(self, axis=None, ddof=0, keepdims=False):
        return apply_op(
            lambda x: jnp.var(x, axis=axis, ddof=ddof, keepdims=keepdims), self)

    def cumsum(self, axis=None, dtype=None):
        return apply_op(
            lambda x: jnp.cumsum(x, axis=axis, dtype=normalize_dtype(dtype)), self)

    def dot(self, other):
        return apply_op(jnp.dot, self, other)

    def abs(self):
        return apply_op(jnp.abs, self)

    def sqrt(self):
        return apply_op(jnp.sqrt, self)

    def exp(self):
        return apply_op(jnp.exp, self)

    def log(self):
        return apply_op(jnp.log, self)

    def round(self, decimals=0):
        return apply_op(lambda x: jnp.round(x, decimals), self)

    def sigmoid(self):
        return apply_op(jax.nn.sigmoid, self)

    def relu(self):
        return apply_op(jax.nn.relu, self)

    def tanh(self):
        return apply_op(jnp.tanh, self)

    def norm(self, ord=None, axis=None, keepdims=False):
        return apply_op(
            lambda x: jnp.linalg.norm(x, ord=ord, axis=axis, keepdims=keepdims),
            self)

    # ------------------------------------------------------------------
    # indexing
    # ------------------------------------------------------------------
    @staticmethod
    def _int_key(k):
        """Float index arrays cast to int32 here, ONCE for every indexing
        consumer (reference indexing_op.h casts; legacy index arrays
        default to float32). Bool masks pass through."""
        if hasattr(k, "dtype") and not (
                _np.issubdtype(k.dtype, _np.integer)
                or k.dtype == bool or str(k.dtype) == "bool"):
            return k.astype(jnp.int32)
        return k

    def _index(self, key):
        if isinstance(key, NDArray):
            return self._int_key(key._data)
        if isinstance(key, tuple):
            return tuple(self._int_key(k._data) if isinstance(k, NDArray)
                         else k for k in key)
        if isinstance(key, list):
            # numpy/reference semantics: a[[0, 2, 3]] is fancy indexing;
            # jnp rejects raw list indices
            return _np.asarray(key)
        return key

    def __getitem__(self, key):
        key = self._index(key)
        return apply_op(lambda x: x[key], self)

    def __setitem__(self, key, value):
        """In-place write: functional scatter + handle swap + version bump."""
        key = self._index(key)
        if isinstance(value, NDArray):
            new = apply_op(
                lambda x, v: x.at[key].set(v.astype(x.dtype)), self, value)
        else:
            new = apply_op(lambda x: x.at[key].set(value), self)
        self._assign_from(new)

    def _assign_from(self, other):
        self._data = other._data
        self._tape_entry = other._tape_entry
        self._version += 1

    # ------------------------------------------------------------------
    # arithmetic operators
    # ------------------------------------------------------------------
    def _binary(self, other, fn, reverse=False):
        if isinstance(other, NDArray):
            if reverse:
                return apply_op(fn, other, self)
            return apply_op(fn, self, other)
        if reverse:
            return apply_op(lambda x: fn(other, x), self)
        return apply_op(lambda x: fn(x, other), self)

    def __add__(self, o):
        return self._binary(o, jnp.add)

    __radd__ = __add__

    def __sub__(self, o):
        return self._binary(o, jnp.subtract)

    def __rsub__(self, o):
        return self._binary(o, jnp.subtract, reverse=True)

    def __mul__(self, o):
        return self._binary(o, jnp.multiply)

    __rmul__ = __mul__

    def __truediv__(self, o):
        return self._binary(o, jnp.divide)

    def __rtruediv__(self, o):
        return self._binary(o, jnp.divide, reverse=True)

    def __floordiv__(self, o):
        return self._binary(o, jnp.floor_divide)

    def __rfloordiv__(self, o):
        return self._binary(o, jnp.floor_divide, reverse=True)

    def __mod__(self, o):
        return self._binary(o, jnp.mod)

    def __rmod__(self, o):
        return self._binary(o, jnp.mod, reverse=True)

    def __pow__(self, o):
        return self._binary(o, jnp.power)

    def __rpow__(self, o):
        return self._binary(o, jnp.power, reverse=True)

    def __matmul__(self, o):
        return self._binary(o, jnp.matmul)

    def __rmatmul__(self, o):
        return self._binary(o, jnp.matmul, reverse=True)

    def __neg__(self):
        return apply_op(jnp.negative, self)

    def __pos__(self):
        return self

    def __abs__(self):
        return apply_op(jnp.abs, self)

    def __invert__(self):
        return apply_op(jnp.invert, self)

    # comparisons
    def __eq__(self, o):
        return self._binary(o, lambda a, b: a == b)

    def __ne__(self, o):
        return self._binary(o, lambda a, b: a != b)

    def __lt__(self, o):
        return self._binary(o, lambda a, b: a < b)

    def __le__(self, o):
        return self._binary(o, lambda a, b: a <= b)

    def __gt__(self, o):
        return self._binary(o, lambda a, b: a > b)

    def __ge__(self, o):
        return self._binary(o, lambda a, b: a >= b)

    __hash__ = object.__hash__

    # logical
    def __and__(self, o):
        return self._binary(o, jnp.bitwise_and)

    def __or__(self, o):
        return self._binary(o, jnp.bitwise_or)

    def __xor__(self, o):
        return self._binary(o, jnp.bitwise_xor)

    # in-place: compute functionally, swap handle (version bump)
    def _inplace(self, other, fn):
        new = self._binary(other, fn)
        self._assign_from(new)
        return self

    def __iadd__(self, o):
        return self._inplace(o, jnp.add)

    def __isub__(self, o):
        return self._inplace(o, jnp.subtract)

    def __imul__(self, o):
        return self._inplace(o, jnp.multiply)

    def __itruediv__(self, o):
        return self._inplace(o, jnp.divide)

    def __imod__(self, o):
        return self._inplace(o, jnp.mod)

    # fluent method surface (reference: ndarray.py hand-writes one method
    # per op — `a.topk(...)` == `mx.nd.topk(a, ...)`, test_ndarray.py:1286
    # test_ndarray_fluent). Here any registered op resolves as a method
    # through the eager nd namespace; explicit methods above keep
    # priority (normal attribute lookup wins over __getattr__).
    def __getattr__(self, name):
        if name.startswith("_"):  # never intercept protocol/dunder probes
            raise AttributeError(name)
        from .. import ndarray as _nd_ns

        fn = getattr(_nd_ns, name, None)
        if callable(fn):
            import functools

            return functools.partial(fn, self)
        raise AttributeError(
            f"'NDArray' object has no attribute {name!r}")


# ---------------------------------------------------------------------------
# op application (the Imperative::Invoke analog)
# ---------------------------------------------------------------------------

def _wrap_out(data, device=None):
    return NDArray(data, device)


def device_groups(arrays):
    """Positions of `arrays`, grouped by where each lives: its set of
    devices, and whether it is committed to them.  A jitted call takes
    operands of one set of devices only, and commits every result if any
    operand was committed; a group's results live exactly where the
    eager operation would have left each of them.

    What a phase does to every parameter (a cast, a fresh optimizer
    state) is one jitted call per group.  Eager, every distinct
    (operation, shape, type) is an XLA program of its own -- ~0.1 s each
    on a TPU, in every process; traced together they are one, however
    many parameters there are."""
    groups = {}
    for k, a in enumerate(arrays):
        # a host array goes wherever jit puts it: a group of its own kind
        where = (tuple(sorted(d.id for d in a.devices())), a.committed) \
            if isinstance(a, jax.Array) else None
        groups.setdefault(where, []).append(k)
    return list(groups.values())


def _is_sparse(a):
    return getattr(a, "stype", None) in ("csr", "row_sparse")


def densify_sparse_args(args):
    """Storage fallback (reference FComputeExFallback): sparse operands
    of ops without a sparse kernel densify at the eager boundary, so
    nd.sum(csr) / nd.where(csr, ...) value-match the reference with a
    dense result. Shared by apply_op and make_eager — keep the
    semantics in ONE place. Accepts a tuple/list of positionals or a
    dict of keywords."""
    if isinstance(args, dict):
        if any(_is_sparse(v) for v in args.values()):
            return {k: v.todense() if _is_sparse(v) else v
                    for k, v in args.items()}
        return args
    if any(_is_sparse(a) for a in args):
        return tuple(a.todense() if _is_sparse(a) else a for a in args)
    return args


def apply_op(fn, *args, name=None):
    """Run pure jax function `fn` over NDArray/raw args; tape when recording.

    `fn` receives raw jax arrays in the positions where NDArrays were passed;
    other args go through untouched. Returns NDArray or tuple of NDArrays,
    mirroring fn's output structure.
    """
    args = densify_sparse_args(args)
    nd_pos = [i for i, a in enumerate(args) if isinstance(a, NDArray)]
    datas = [args[i]._data for i in nd_pos]

    if len(nd_pos) == len(args):
        base = fn
    else:
        def base(*xs):
            call = list(args)
            for i, x in zip(nd_pos, xs):
                call[i] = x
            return fn(*call)

    def pure(*xs):
        r = base(*xs)
        # normalize list outputs (e.g. jnp.split) to tuples so the tape's
        # tuple cotangents match the vjp's recorded output pytree
        return tuple(r) if isinstance(r, list) else r

    record = ag.taping_active() and any(
        args[i]._requires_grad_entry for i in nd_pos
    )

    if record:
        out, vjp_fn = jax.vjp(pure, *datas)
    else:
        out = pure(*datas)

    multi = isinstance(out, (tuple, list))
    outs = list(out) if multi else [out]
    wrapped = [_wrap_out(o) for o in outs]

    if record:
        nd_inputs = [args[i] for i in nd_pos]
        node = ag.TapeNode(
            vjp_fn,
            nd_inputs,
            [a._tape_entry for a in nd_inputs],
            [(tuple(o.shape), o.dtype) for o in outs],
            multi_out=multi,
            name=name or getattr(fn, "__name__", "op"),
            pure_fn=pure,
            input_datas=datas,
        )
        for idx, w in enumerate(wrapped):
            w._tape_entry = (node, idx)

    return tuple(wrapped) if multi else wrapped[0]


# ---------------------------------------------------------------------------
# creation
# ---------------------------------------------------------------------------

def _creation_device(device):
    if device is None:
        return current_device()
    return device if isinstance(device, Device) else Device(device)


def from_jax(data, device=None):
    return NDArray(data, device)


def array(source, dtype=None, device=None, ctx=None):
    """Create an NDArray on `device` from array-like/NDArray."""
    device = _creation_device(device if device is not None else ctx)
    dtype = normalize_dtype(dtype)
    if isinstance(source, NDArray):
        data = source._data
        if dtype is not None and data.dtype != dtype:
            data = data.astype(dtype)
        return NDArray(jax.device_put(data, device.jax_device), device)
    from_numpy = isinstance(source, _np.ndarray)
    arr = _np.asarray(source)
    if dtype is None:
        if not from_numpy and arr.dtype.kind in "iuf":
            # python lists/scalars default to the float dtype (reference:
            # ndarray.py array — 'float32 otherwise'; f64 under
            # npx.set_np(dtype=True), test_numpy_default_dtype.py).
            # bool/complex inputs keep their kind.
            from ..numpy_extension import default_float_dtype

            dtype = _np.dtype(default_float_dtype())
        elif arr.dtype == _np.float64:
            dtype = _np.dtype(_np.float32)  # documented 32-bit default
        elif arr.dtype == _np.int64:
            dtype = _np.dtype(_np.int32)  # 32-bit creation default
    if dtype is not None:
        arr = arr.astype(dtype)
    _telemetry.record_transfer("h2d", arr.nbytes)
    return NDArray(jax.device_put(arr, device.jax_device), device)


def waitall():
    engine.waitall()
