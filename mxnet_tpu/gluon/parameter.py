"""Gluon Parameter (reference: python/mxnet/gluon/parameter.py:47).

Holds weight data (+ gradient buffer) with deferred initialization: a
Parameter created with unknown dims (0/-1/None) materializes on the first
forward once the layer infers the full shape. Supports per-device copies for
multi-device data-parallel training (the reference's `ctx` list), grad_req
write/add/null, lr_mult/wd_mult, and trace mode (during CachedOp tracing the
parameter temporarily exposes a jax tracer instead of its concrete buffer).
"""
from __future__ import annotations

import functools
import uuid

import threading as _threading

import jax
import jax.numpy as jnp
import numpy as _np

from .. import initializer as init_mod
from ..base import DeferredInitializationError, normalize_dtype
from ..device import Device, current_device
from ..ndarray.ndarray import NDArray, device_groups

__all__ = ["Parameter", "Constant", "cast_params"]


def _shape_known(shape):
    return shape is not None and all(
        d is not None and int(d) > 0 for d in shape
    )


def _device_list(device):
    devices = device if isinstance(device, (list, tuple)) else [device]
    return [d if isinstance(d, Device) else Device(d) for d in devices]


@functools.partial(jax.jit, static_argnums=1)
def _as_dtype(arrays, dtype):
    return [a.astype(dtype) for a in arrays]


# what one cast program may read: the old buffers of a call are freed
# when it returns, so old and new tree overlap by this much and no more
_CAST_BYTES = 1 << 30


def _runs(arrays, budget):
    """`arrays` cut into runs of at most `budget` bytes (or one array)."""
    run, size = [], 0
    for a in arrays:
        if run and size + a._data.nbytes > budget:
            yield run
            run, size = [], 0
        run.append(a)
        size += a._data.nbytes
    if run:
        yield run


def cast_params(params, dtype):
    """Cast the data and the gradient buffers of every parameter of
    `params` to `dtype`, in one program for the whole tree (a tree of
    one parameter is `Parameter.cast`; a tree of gigabytes takes one
    program per `_CAST_BYTES`, so that it never holds two whole copies)."""
    dtype = normalize_dtype(dtype)
    data, grads = [], []
    for p in params:
        p.dtype = dtype
        if p._data_map is not None:
            data.extend(p._data_map.values())
            grads.extend((p._grad_map or {}).values())
    todo = [a for a in data + grads if a._data.dtype != dtype]
    for ks in device_groups([a._data for a in todo]):
        for run in _runs([todo[k] for k in ks], _CAST_BYTES):
            for a, v in zip(run, _as_dtype([a._data for a in run], dtype)):
                a._data = v
    for a in data:
        a._version += 1


class Parameter:
    def __init__(self, name="weight", grad_req="write", shape=None,
                 dtype=_np.float32, lr_mult=1.0, wd_mult=1.0, init=None,
                 allow_deferred_init=False, differentiable=True,
                 stype="default", grad_stype="default"):  # noqa: ARG002
        if grad_stype not in ("default", "row_sparse"):
            raise ValueError(f"grad_stype must be 'default' or "
                             f"'row_sparse', got {grad_stype!r}")
        # row_sparse grads: the tape still accumulates densely (XLA
        # scatter-add is the efficient TPU path), but the Trainer hands the
        # optimizer a RowSparseNDArray sliced to the rows the forward
        # touched (see _as_row_sparse_grad), so lazy_update semantics match
        # the reference (optimizer/sgd.py:36-95) without a host sync.
        self.grad_stype = grad_stype
        self._sparse_row_hints = []   # index arrays recorded by Embedding
        self._name = name
        self._shape = tuple(shape) if shape is not None else None
        self.dtype = normalize_dtype(dtype)
        self.lr_mult = lr_mult
        self.wd_mult = wd_mult
        self.init = init
        self.allow_deferred_init = allow_deferred_init
        self._differentiable = bool(differentiable)
        # validate FIRST (the setter), then the setter's own coercion
        # downgrades non-differentiable params to 'null'. The ctor default
        # grad_req='write' on a differentiable=False parameter coerces
        # SILENTLY (Constant, BN running stats — nothing the caller chose);
        # the setter warns only on an explicit non-default request or a
        # post-construction reassignment.
        if not self._differentiable and grad_req == "write":
            grad_req = "null"
        self.grad_req = grad_req
        self._data_map = None  # {Device: NDArray}
        self._grad_map = None
        self._ctx_list = None
        self._deferred = None  # (init, device_list, default_init)
        # tracer visible during CachedOp tracing — THREAD-LOCAL so a trace
        # in one thread cannot leak tracers into concurrent inference
        # threads (reference: cached_op_threadsafe.cc isolation)
        self._tls = _threading.local()

    @property
    def _traced_data(self):
        return getattr(self._tls, "traced_data", None)

    @_traced_data.setter
    def _traced_data(self, value):
        self._tls.traced_data = value

    # -- identity ----------------------------------------------------------
    @property
    def name(self):
        return self._name

    @name.setter
    def name(self, value):
        self._name = value

    @property
    def shape(self):
        return self._shape

    @shape.setter
    def shape(self, new_shape):
        if self._shape is None:
            self._shape = tuple(new_shape)
            return
        # fill unknown dims; known dims must match (reference shape merge)
        merged = []
        for old, new in zip(self._shape, new_shape):
            if old in (0, -1, None):
                merged.append(new)
            else:
                if new not in (0, -1, None) and int(old) != int(new):
                    raise ValueError(
                        f"Parameter {self._name}: shape mismatch "
                        f"{self._shape} vs {tuple(new_shape)}")
                merged.append(old)
        self._shape = tuple(merged)

    def __repr__(self):
        return (f"Parameter {self._name} (shape={self._shape}, "
                f"dtype={self.dtype})")

    # -- initialization ----------------------------------------------------
    def initialize(self, init=None, device=None, default_init=None,
                   force_reinit=False, ctx=None):
        if device is None:
            device = ctx
        if device is None:
            device = current_device()
        devices = _device_list(device)
        if self._data_map is not None and not force_reinit:
            return
        default_init = default_init or init_mod.Uniform()
        if not _shape_known(self._shape):
            if not self.allow_deferred_init:
                raise ValueError(
                    f"Cannot initialize Parameter {self._name}: unknown "
                    f"shape {self._shape} and allow_deferred_init=False")
            self._deferred = (init, devices, default_init)
            return
        self._finish_init(init, devices, default_init)

    def _finish_init(self, init, devices, default_init):
        # create() resolves registry-name strings and passes Initializer
        # instances through, so one call covers every spec form
        # (net.initialize(init="normal") included)
        # Reference protocol (gluon/parameter.py:365): the GLOBAL
        # initializer's __call__ drives, with the parameter's declared
        # init riding in InitDesc.attrs['__init__']. Standard globals
        # defer to the declared init (biases stay zero because layers
        # declare 'zeros'); Load/Mixed override __call__ and so win —
        # net.initialize(init=Load(...)) warm-starts EVERY parameter.
        declared = init if init is not None else self.init
        global_init = init_mod.create(default_init)
        init_name = getattr(self, "_structured_name", None) or self._name
        desc = init_mod.InitDesc(
            init_name,
            {"__init__": declared} if declared is not None else {})
        master = global_init.init_array(desc, self._shape, self.dtype,
                                        explicit=declared is None)
        self._install(devices, master.copyto)

    def _install(self, devices, copy_on):
        """Become initialized: the value is `copy_on(device)` on each of
        `devices`, with fresh gradient buffers beside it."""
        self._ctx_list = list(devices)
        self._data_map = {d: copy_on(d) for d in devices}
        self._grad_map = {}
        if self.grad_req != "null":
            self._init_grad_buffers()
        self._deferred = None

    def _init_grad_buffers(self):
        """(Re)allocate fresh zero grad buffers on every device and wire
        them to the data arrays — the ONE copy of this logic
        (reference parameter.py _init_grad). Fresh zeros on every
        grad_req change: reused buffers would feed stale gradients into
        an 'add' accumulation."""
        self._grad_map = {}
        # host zeros, placed: `jnp.zeros` is an XLA program per distinct
        # (shape, type), a transfer is none
        zeros = _np.zeros(self._shape, self.dtype)
        for d, arr in self._data_map.items():
            g = NDArray(jax.device_put(zeros, d.jax_device), d)
            self._grad_map[d] = g
            arr._grad = g
            arr._grad_req = self._grad_req

    @property
    def grad_req(self):
        return self._grad_req

    @grad_req.setter
    def grad_req(self, req):
        """Validated + live: changing grad_req after initialize rewires
        the per-device arrays (reference parameter.py grad_req setter —
        'add' starts accumulating into FRESH zeros, 'null' drops the
        buffers, non-differentiable parameters coerce to 'null')."""
        if req not in ("write", "add", "null"):
            raise ValueError(
                f"grad_req must be 'write', 'add' or 'null', got {req!r}")
        if not getattr(self, "_differentiable", True) and req != "null":
            import warnings

            warnings.warn(
                f"parameter {getattr(self, '_name', '?')!r} is not "
                f"differentiable; ignoring grad_req={req!r}",
                stacklevel=2)
            req = "null"
        if req == getattr(self, "_grad_req", None):
            # same-value reassignment keeps accumulated gradients
            # (reference setter early-returns; Block.setattr loops every
            # parameter unconditionally)
            return
        self._grad_req = req
        data_map = getattr(self, "_data_map", None)
        if not data_map:
            return
        if req == "null":
            for arr in data_map.values():
                arr._grad = None
                arr._grad_req = req
            self._grad_map = {}
            return
        self._init_grad_buffers()

    def _defer_to_data(self, device):
        """Never initialized, and about to be handed its value on `device`
        (a checkpoint's): wait for `set_data` as a parameter of unknown
        shape does, so that no initializer draws a value to be
        overwritten."""
        self._deferred = (None, _device_list(device), None)

    def _finish_deferred_init(self, shape=None):
        """Complete deferred init once the full shape is known."""
        if shape is not None:
            self.shape = shape
        if self._deferred is None:
            raise DeferredInitializationError(
                f"Parameter {self._name} was not initialized "
                f"(call .initialize() first)")
        if not _shape_known(self._shape):
            raise DeferredInitializationError(
                f"Parameter {self._name}: shape still unknown {self._shape}")
        init, devices, default_init = self._deferred
        self._finish_init(init, devices, default_init)

    @property
    def _is_deferred(self):
        return self._data_map is None and self._deferred is not None

    def _check_initialized(self, device=None):
        if self._data_map is None:
            if self._deferred is not None:
                raise DeferredInitializationError(
                    f"Parameter {self._name} deferred-init pending")
            raise RuntimeError(
                f"Parameter {self._name} has not been initialized. "
                "Call .initialize() on the Block first")
        if device is not None and device not in self._data_map:
            raise RuntimeError(
                f"Parameter {self._name} not initialized on {device}; "
                f"it lives on {list(self._data_map)}")

    # -- data access -------------------------------------------------------
    def data(self, ctx=None, device=None):
        """The parameter value on `device` (primary device by default).

        During CachedOp tracing returns the traced stand-in (the analog of
        the reference feeding param NDArrays as CachedOp inputs).
        """
        if self._traced_data is not None:
            return self._traced_data
        device = device if device is not None else ctx
        self._check_initialized(
            device if isinstance(device, Device) else None)
        if device is None:
            return self._data_map[self._ctx_list[0]]
        if not isinstance(device, Device):
            device = Device(device)
        if device not in self._data_map:
            raise RuntimeError(
                f"Parameter {self._name} not initialized on {device}")
        return self._data_map[device]

    def data_for(self, x):
        """Copy co-located with NDArray x (layers use this in forward)."""
        if self._traced_data is not None:
            return self._traced_data
        self._check_initialized()
        if len(self._data_map) == 1:
            return self._data_map[self._ctx_list[0]]
        dev = x.device
        return self._data_map.get(dev, self._data_map[self._ctx_list[0]])

    def list_data(self):
        self._check_initialized()
        return [self._data_map[d] for d in self._ctx_list]

    def grad(self, ctx=None, device=None):
        device = device if device is not None else ctx
        self._check_initialized()
        if self.grad_req == "null":
            raise RuntimeError(
                f"Parameter {self._name} has grad_req='null'")
        if device is None:
            return self._grad_map[self._ctx_list[0]]
        if not isinstance(device, Device):
            device = Device(device)
        return self._grad_map[device]

    def list_grad(self):
        self._check_initialized()
        return [self._grad_map[d] for d in self._ctx_list]

    def list_ctx(self):
        self._check_initialized()
        return list(self._ctx_list)

    list_device = list_ctx

    def set_data(self, data):
        """Set value on all devices (reference: Parameter.set_data)."""
        if self._data_map is None and self._deferred is None:
            raise RuntimeError(
                f"Parameter {self._name} has not been initialized; call "
                ".initialize() before set_data (reference parity)")
        if not isinstance(data, NDArray):
            data = NDArray(jnp.asarray(data, self.dtype))
        deferred = self._data_map is None
        if deferred:
            # the incoming value fixes the shape
            self.shape = data.shape
            if not _shape_known(self._shape):
                raise DeferredInitializationError(
                    f"Parameter {self._name}: shape still unknown "
                    f"{self._shape}")
        src = data._data
        if deferred:
            # ... and takes the place of the initializer's draw, which
            # nobody would read (a draw is an XLA program per shape)
            value = jnp.asarray(src, self.dtype)
            self._install(self._deferred[1], lambda d: NDArray(value, d))
            return
        for d in self._ctx_list:
            arr = self._data_map[d]
            # honor the declared dtype, not the old buffer's — load with
            # dtype_source='saved' retypes the parameter before set_data
            arr._data = jnp.asarray(src, self.dtype or arr._data.dtype)
            arr._version += 1

    def zero_grad(self):
        if self._grad_map:
            for g in self._grad_map.values():
                g._data = jnp.zeros_like(g._data)
                g._version += 1
        self._sparse_row_hints = []

    def _record_sparse_rows(self, ids):
        """Called by sparse_grad layers during forward with the (concrete)
        row ids the lookup touched. Tracers are skipped — the hybridized
        path falls back to a dense update."""
        if self.grad_stype != "row_sparse" or self.grad_req == "null":
            return
        from .. import autograd as _ag

        if not _ag.is_recording():
            return   # eval/inference forwards must not skew the lazy rows
        import jax.core as _core

        if isinstance(ids, _core.Tracer):
            return
        self._sparse_row_hints.append(jnp.ravel(jnp.asarray(ids)))

    def _as_row_sparse_grad(self, g):
        """Dense grad buffer -> RowSparseNDArray over the rows touched
        since the last update. Fully on-device: fixed-size jnp.unique pads
        with the out-of-range index shape[0], which the optimizer's
        scatter drops (reference: row_sparse grad of Embedding,
        sparse.py:575). Returns the dense grad unchanged if no rows were
        recorded (e.g. hybridized forward)."""
        if not self._sparse_row_hints:
            return g
        from ..ndarray.sparse import RowSparseNDArray

        ids = (self._sparse_row_hints[0] if len(self._sparse_row_hints) == 1
               else jnp.concatenate(self._sparse_row_hints))
        self._sparse_row_hints = []
        n = g.shape[0]
        k = min(int(ids.size), int(n))
        uids = jnp.unique(ids.astype(jnp.int32), size=k, fill_value=n)
        return RowSparseNDArray(g._data[uids], uids, g.shape)

    def reset_ctx(self, ctx=None, device=None):
        device = device if device is not None else ctx
        devices = _device_list(device)
        self._check_initialized()
        master = self._data_map[self._ctx_list[0]]
        self._ctx_list = devices
        self._data_map = {d: master.copyto(d) for d in devices}
        if self.grad_req != "null":
            self._init_grad_buffers()

    reset_device = reset_ctx

    def cast(self, dtype):
        cast_params([self], dtype)

    # misc
    def var(self):
        """Symbol variable for this parameter (reference: parameter.py
        var). The variable name is namespaced per parameter object (the
        reference uses a UUID) so two blocks' 'weight' params never
        alias in one graph; known shape is attached for inference."""
        from ..symbol.symbol import var as _sym_var

        if not hasattr(self, "_var_name") or self._var_name is None:
            try:
                self._var_name = f"{self.name}_{uuid.uuid4().hex[:8]}"
            except AttributeError:  # __slots__ without the field
                return _sym_var(f"{self.name}_{id(self):x}",
                                shape=self.shape)
        return _sym_var(self._var_name, shape=self.shape)


class Constant(Parameter):
    """Non-learnable constant parameter (reference: gluon Constant)."""

    def __init__(self, value, name="const"):
        if not isinstance(value, NDArray):
            value = NDArray(jnp.asarray(value))
        super().__init__(name=name, grad_req="null", shape=value.shape,
                         dtype=value.dtype, differentiable=False,
                         init=init_mod.Constant(0.0))
        self._value = value

    def _finish_init(self, init, devices, default_init):  # noqa: ARG002
        self._install(devices, self._value.copyto)
