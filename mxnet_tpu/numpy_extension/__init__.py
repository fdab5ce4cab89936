"""mx.npx — operators beyond the NumPy standard (NN primitives etc.).

Reference: python/mxnet/numpy_extension/ (the `_npx_*` namespace: activation,
batch_norm, convolution, pooling, fully_connected, embedding, topk, pick,
one_hot, sequence ops...). Here each wraps a pure op from mxnet_tpu.ops.nn via
apply_op, so they are taped and traceable.
"""
from __future__ import annotations

import functools

from .. import _random
from ..autograd import is_training
from ..ndarray.ndarray import NDArray, apply_op
from ..ops import nn as _nn
from ..ops import pallas_kda as _kda
from ..ops import pallas_mla_heads as _mla_heads
from ..ops import pallas_qk_prep as _qk_prep
from ..ops import short_conv as _short_conv

from .control_flow import cond, foreach, while_loop  # noqa: F401
from . import image  # noqa: F401  (mx.npx.image — reference:
#                      numpy_extension/image.py op-family namespace)

__all__ = [
    "cond", "foreach", "while_loop",
    "activation", "leaky_relu", "relu", "sigmoid", "softmax", "log_softmax",
    "softmin", "fully_connected", "convolution", "deconvolution", "pooling",
    "batch_norm", "layer_norm", "group_norm", "instance_norm", "rms_norm",
    "rotary_embedding", "rms_norm_rotary", "mla_heads", "gated_short_conv",
    "short_conv", "kda_scan",
    "lrn", "dropout", "embedding", "one_hot", "pick", "topk", "sequence_mask",
    "sequence_last", "sequence_reverse", "l2_normalization", "upsampling",
    "moments", "gamma", "erf", "erfinv", "set_np", "reset_np", "is_np_array",
    "is_np_shape", "is_np_default_dtype", "use_np", "cpu", "gpu", "tpu",
    "num_gpus", "current_device", "waitall",
]


def _op(fn, n_arrays):
    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        arrs = args[:n_arrays]
        rest = args[n_arrays:]
        nd = [a for a in arrs if isinstance(a, NDArray)]

        def pure(*xs):
            it = iter(xs)
            call = [next(it) if isinstance(a, NDArray) else a for a in arrs]
            return fn(*call, *rest, **kwargs)

        return apply_op(pure, *nd, name=fn.__name__)

    return wrapped


activation = _op(_nn.activation, 1)
leaky_relu = _op(_nn.leaky_relu, 2)
softmax = _op(_nn.softmax, 1)
log_softmax = _op(_nn.log_softmax, 1)
softmin = _op(_nn.softmin, 1)
fully_connected = _op(_nn.dense, 3)
convolution = _op(_nn.conv, 3)
deconvolution = _op(_nn.conv_transpose, 3)
pooling = _op(_nn.pool, 1)
layer_norm = _op(_nn.layer_norm, 3)
group_norm = _op(_nn.group_norm, 3)
instance_norm = _op(_nn.instance_norm, 3)
rms_norm = _op(_nn.rms_norm, 2)
rotary_embedding = _op(_nn.rotary_embedding, 2)
rms_norm_rotary = _op(_qk_prep.rms_norm_rotary, 3)
mla_heads = _op(_mla_heads.mla_heads, 4)
gated_short_conv = _op(_short_conv.gated_short_conv, 2)
short_conv = _op(_short_conv.short_conv, 2)
kda_scan = _op(_kda.kda_scan, 5)
lrn = _op(_nn.lrn, 1)
embedding = _op(_nn.embedding, 2)
one_hot = _op(_nn.one_hot, 1)
pick = _op(_nn.pick, 2)
topk = _op(_nn.topk, 1)
sequence_mask = _op(_nn.sequence_mask, 2)
sequence_last = _op(_nn.sequence_last, 2)
sequence_reverse = _op(_nn.sequence_reverse, 2)
l2_normalization = _op(_nn.l2_normalization, 1)
upsampling = _op(_nn.upsample, 1)
moments = _op(_nn.moments, 1)


def relu(x):
    return activation(x, "relu")


def sigmoid(x):
    return activation(x, "sigmoid")


def batch_norm(x, gamma, beta, running_mean, running_var, eps=1e-5,
               momentum=0.9, fix_gamma=False, use_global_stats=False,
               output_mean_var=False, axis=1):
    """Eager batch_norm; updates running stats in place like the reference op
    (mutable aux inputs of nn/batch_norm.cc)."""
    training = is_training() and not use_global_stats
    if fix_gamma:
        gamma = gamma.ones_like()
    out, nm, nv = _op(_nn.batch_norm, 5)(
        x, gamma, beta, running_mean, running_var, eps=eps, momentum=momentum,
        training=training, use_global_stats=use_global_stats, axis=axis)
    if training:
        running_mean._assign_from(nm.detach())
        running_var._assign_from(nv.detach())
    if output_mean_var:
        return out, nm, nv
    return out


def dropout(x, p=0.5, axes=None, mode="training"):
    training = is_training() or mode == "always"
    if not training or p <= 0:
        return x
    key = _random.next_key()
    return _op(_nn.dropout, 1)(x, key, p=p, training=True, axes=axes)


def gamma(x):
    import jax.scipy.special as jsp

    return apply_op(lambda v: jsp.gamma(v) if hasattr(jsp, "gamma")
                    else __import__("jax.numpy", fromlist=["exp"]).exp(jsp.gammaln(v)), x)


def erf(x):
    import jax.scipy.special as jsp

    return apply_op(jsp.erf, x)


def erfinv(x):
    import jax.scipy.special as jsp

    return apply_op(jsp.erfinv, x)


# --- npx namespace/device utilities (API parity) ---------------------------
from ..device import cpu, current_device, gpu, num_gpus, tpu  # noqa: E402
from ..engine import waitall  # noqa: E402

import threading as _threading

# np-semantics state: process-wide defaults set by set_np, with
# THREAD-LOCAL overrides from the util.np_shape/np_array scopes (the
# reference's MXNET_NPX bits are per-thread; a DataLoader worker must
# not see another thread's scope)
_np_defaults = {"array": True, "shape": True}
_np_tls = _threading.local()
_np_default_dtype = False


def _np_flag(key):
    over = getattr(_np_tls, key, None)
    return _np_defaults[key] if over is None else over


def set_np(shape=True, array=True, dtype=False):
    """Set the process-wide np-semantics defaults (reference:
    util.py set_np — array semantics require shape semantics); `dtype`
    switches creation defaults to official-numpy (float64/int64)
    (numpy/multiarray.py:7004)."""
    global _np_default_dtype
    if array and not shape:
        raise ValueError("set_np: array semantics require shape "
                         "semantics (reference util.py set_np contract)")
    _np_defaults["array"] = bool(array)
    _np_defaults["shape"] = bool(shape)
    _np_default_dtype = bool(dtype)


def reset_np():
    """``set_np(shape=False, array=False, dtype=False)`` — turn every
    np-semantics flag OFF, exactly like the reference's ``reset_np()``
    (util.py).

    On this framework the ``array``/``shape`` flags are ADVISORY: every
    frontend array IS an mx.np array and zero-dim/zero-size shapes are
    always representable, so flipping them does not switch the
    underlying array implementation — it only changes what
    :func:`is_np_array` / :func:`is_np_shape` report to ported code
    paths (and the scope managers util.np_shape/np_array still override
    them thread-locally). The ``dtype`` flag is real either way: after
    ``reset_np()`` creation defaults are float32/int32 again. Code that
    wants the flags back on calls ``set_np()``; see docs/migration.md.
    """
    global _np_default_dtype
    _np_defaults["array"] = False
    _np_defaults["shape"] = False
    _np_default_dtype = False


def is_np_array():
    return _np_flag("array")


def is_np_shape():
    return _np_flag("shape")


def is_np_default_dtype():
    """True when creation defaults follow official numpy (float64/int64);
    False (default) keeps the reference's float32/int32 defaults."""
    return _np_default_dtype


def default_float_dtype():
    """THE creation-default float dtype (one definition — every creation
    path consults this): float64 under npx.set_np(dtype=True), float32
    otherwise."""
    import numpy as _np

    return _np.float64 if _np_default_dtype else _np.float32


def default_int_dtype():
    import numpy as _np

    return _np.int64 if _np_default_dtype else _np.int32


def use_np(func):
    """Decorator parity with npx.use_np — identity here."""
    return func


# --- npx op extras (reference _npx_* ops beyond the NN nucleus) ------------
import jax as _jax  # noqa: E402
import jax.numpy as _jnp  # noqa: E402
import numpy as _onp  # noqa: E402

from ..ndarray.utils import load, save, savez  # noqa: F401,E402

__all__ += [
    "arange_like", "batch_dot", "bernoulli", "broadcast_like", "from_dlpack",
    "from_numpy", "load", "save", "savez", "masked_softmax",
    "masked_log_softmax", "normal_n", "uniform_n", "rnn", "seed",
    "to_dlpack_for_read", "to_dlpack_for_write", "gelu",
]


def seed(s, ctx="all"):
    from .. import seed as _seed

    _seed(s, ctx)


def from_numpy(ndarray_, zero_copy=True):  # noqa: ARG001
    return NDArray(_jnp.asarray(_onp.asarray(ndarray_)))


def from_dlpack(x):
    return NDArray(_jnp.from_dlpack(x))


def to_dlpack_for_read(x):
    """Return the underlying array as a DLPack-protocol object (modern
    DLPack exchange passes the OBJECT, whose __dlpack__ the consumer
    calls — jnp/np.from_dlpack no longer accept bare capsules)."""
    return x._data


to_dlpack_for_write = to_dlpack_for_read


def arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    """Reference: contrib arange_like — arange shaped like `data`."""

    def pure(x):
        if axis is None:
            n = x.size
            out = start + step * (_jnp.arange(n, dtype=x.dtype) // repeat
                                  if repeat != 1 else _jnp.arange(n, dtype=x.dtype))
            return out.reshape(x.shape)
        n = x.shape[axis]
        idx = _jnp.arange(n, dtype=x.dtype)
        if repeat != 1:
            idx = idx // repeat
        return start + step * idx

    return apply_op(pure, data, name="arange_like")


def batch_dot(a, b, transpose_a=False, transpose_b=False):
    """Batched matmul over leading batch dim (reference: batch_dot op)."""

    def pure(x, y):
        if transpose_a:
            x = _jnp.swapaxes(x, -1, -2)
        if transpose_b:
            y = _jnp.swapaxes(y, -1, -2)
        return _jnp.matmul(x, y)

    return apply_op(pure, a, b, name="batch_dot")


def broadcast_like(lhs, rhs, lhs_axes=None, rhs_axes=None):
    def pure(x, y):
        if lhs_axes is None:
            return _jnp.broadcast_to(x, y.shape)
        shape = list(x.shape)
        for la, ra in zip(lhs_axes, rhs_axes):
            shape[la] = y.shape[ra]
        return _jnp.broadcast_to(x, tuple(shape))

    return apply_op(pure, lhs, rhs, name="broadcast_like")


def masked_softmax(data, mask, axis=-1, temperature=1.0):
    def pure(x, m):
        neg = _jnp.finfo(x.dtype).min
        logits = _jnp.where(m.astype(bool), x / temperature, neg)
        out = _jax.nn.softmax(logits, axis=axis)
        return _jnp.where(m.astype(bool), out, 0.0).astype(x.dtype)

    return apply_op(pure, data, mask, name="masked_softmax")


def masked_log_softmax(data, mask, axis=-1, temperature=1.0):
    def pure(x, m):
        neg = _jnp.finfo(x.dtype).min
        logits = _jnp.where(m.astype(bool), x / temperature, neg)
        out = _jax.nn.log_softmax(logits, axis=axis)
        return _jnp.where(m.astype(bool), out, neg).astype(x.dtype)

    return apply_op(pure, data, mask, name="masked_log_softmax")


def gelu(x, approximate=True):
    return apply_op(lambda v: _jax.nn.gelu(v, approximate=approximate), x,
                    name="gelu")


def bernoulli(prob=None, logit=None, size=None, dtype=None):
    if (prob is None) == (logit is None):
        raise ValueError("pass exactly one of prob/logit")
    key = _random.next_key()
    p = prob if prob is not None else None

    def pure(v):
        pv = v if p is not None else _jax.nn.sigmoid(v)
        shape = size if size is not None else pv.shape
        draw = _jax.random.bernoulli(key, pv, shape=shape)
        return draw.astype(dtype or "float32")

    x = p if p is not None else logit
    if isinstance(x, NDArray):
        return apply_op(pure, x, name="bernoulli")
    return NDArray(pure(_jnp.asarray(x)))


def _sample_n(dist):
    def fn(*params, shape=None, dtype="float32"):
        key = _random.next_key()

        def pure(*xs):
            it = iter(xs)
            ps = [next(it) if isinstance(p, NDArray) else _jnp.asarray(p)
                  for p in params]
            base = _jnp.broadcast_arrays(*ps)[0].shape
            full = tuple(shape or ()) + base
            if dist == "normal":
                loc, scale = ps
                return (loc + scale * _jax.random.normal(key, full)).astype(dtype)
            low, high = ps
            return _jax.random.uniform(
                key, full, minval=low, maxval=high).astype(dtype)

        nd = [p for p in params if isinstance(p, NDArray)]
        if nd:
            return apply_op(pure, *nd, name=f"{dist}_n")
        return NDArray(pure())

    return fn


def normal_n(loc=0.0, scale=1.0, shape=None, dtype="float32"):
    return _sample_n("normal")(loc, scale, shape=shape, dtype=dtype)


def uniform_n(low=0.0, high=1.0, shape=None, dtype="float32"):
    return _sample_n("uniform")(low, high, shape=shape, dtype=dtype)


def rnn(data=None, parameters=None, state=None, state_cell=None, mode="lstm",
        state_size=None, num_layers=1, bidirectional=False, p=0.0,
        state_outputs=False, **kwargs):  # noqa: ARG001
    """Fused multi-layer RNN on a packed parameter vector.

    Reference: src/operator/rnn.cc / rnn-inl.h — one flat `parameters` vector
    holding (all i2h/h2h weights, layer-major, direction-minor) then (all
    biases, same order). TPU re-design: the time loop is a lax.scan per
    layer/direction; the per-step gemms batch onto the MXU.
    data: (T, N, I); state: (L*D, N, H); returns out (T, N, H*D)
    (+ state outputs when state_outputs=True).
    """
    from ..gluon.rnn.rnn_layer import _rnn_step

    H = int(state_size)
    D = 2 if bidirectional else 1
    G = {"lstm": 4, "gru": 3}.get(mode, 1)
    step = _rnn_step(mode if mode != "rnn" else "rnn_tanh")
    has_cell = mode == "lstm"
    train_drop = p > 0 and is_training()
    drop_key = _random.next_key() if train_drop else None

    def pure(x, w, h0, *maybe_c):
        c0 = maybe_c[0] if maybe_c else None
        T, N, in_size = x.shape
        # slice the packed vector: weights (layer-major), then biases
        off = 0
        wi_l, wh_l, bi_l, bh_l = [], [], [], []
        for layer in range(num_layers):
            isz = in_size if layer == 0 else H * D
            for _ in range(D):
                wi_l.append(w[off:off + G * H * isz].reshape(G * H, isz))
                off += G * H * isz
                wh_l.append(w[off:off + G * H * H].reshape(G * H, H))
                off += G * H * H
        for _ in range(num_layers * D):
            bi_l.append(w[off:off + G * H])
            off += G * H
            bh_l.append(w[off:off + G * H])
            off += G * H

        def run_dir(seq, idx, reverse):
            hc = (h0[idx],) if not has_cell else (h0[idx], c0[idx])
            wi, wh, bi, bh = wi_l[idx], wh_l[idx], bi_l[idx], bh_l[idx]
            xs = seq[::-1] if reverse else seq
            carry, ys = _jax.lax.scan(
                lambda c, xt: step(c, xt, wi, wh, bi, bh), hc, xs)
            return carry, (ys[::-1] if reverse else ys)

        seq = x
        h_fin, c_fin = [], []
        for layer in range(num_layers):
            outs = []
            for d in range(D):
                idx = layer * D + d
                carry, ys = run_dir(seq, idx, reverse=(d == 1))
                outs.append(ys)
                h_fin.append(carry[0])
                if has_cell:
                    c_fin.append(carry[1])
            seq = outs[0] if D == 1 else _jnp.concatenate(outs, axis=-1)
            if train_drop and layer < num_layers - 1:
                keep = 1.0 - p
                mask = _jax.random.bernoulli(
                    _jax.random.fold_in(drop_key, layer), keep, seq.shape)
                seq = _jnp.where(mask, seq / keep, 0.0).astype(seq.dtype)
        outs = [seq, _jnp.stack(h_fin)]
        if has_cell:
            outs.append(_jnp.stack(c_fin))
        return tuple(outs)

    args = [data, parameters, state] + ([state_cell] if has_cell else [])
    res = apply_op(pure, *args, name="rnn")
    if state_outputs:
        return res
    return res[0]


# ---------------------------------------------------------------------------
# generated corpus: expose every registry op under npx as well (reference
# npx carries the full `_npx_*` surface — topk/pick/gather_nd/reshape_like/
# the linalg family/legacy vision ops...). Hand-written wrappers above win,
# so define the stateful CamelCase spellings BEFORE populate (the registry's
# pure `Dropout`/`BatchNorm` would otherwise be silent no-op traps).
# ---------------------------------------------------------------------------


def Dropout(data, p=0.5, mode="training", axes=None, **kwargs):  # noqa: ARG001, N802
    return dropout(data, p=p, axes=axes, mode=mode)


def BatchNorm(data, gamma, beta, moving_mean, moving_var, **kwargs):  # noqa: N802
    return batch_norm(data, gamma, beta, moving_mean, moving_var, **kwargs)


def npx_reshape_shape(src, target):
    """Resolve the _npx_reshape code table (reference:
    src/operator/numpy/np_matrix_op.cc NumpyXReshapeInferShape): -1 infer,
    -2 copy-dim, -3 skip size-1 dim, -4 copy-all-remaining, -5 merge-two,
    -6 split (next two entries, either may be -1)."""
    src = list(src)
    target = list(target)
    if all(t >= 0 for t in target):
        return tuple(target)
    out = []
    i = 0  # src index
    j = 0
    infer_at = -1
    known = 1
    while j < len(target):
        t = target[j]
        if t == -1:
            infer_at = len(out)
            out.append(-1)
            i += 1
        elif t == -2:
            out.append(src[i])
            known *= src[i]
            i += 1
        elif t == -3:
            if src[i] != 1:
                raise ValueError("-3 may only skip a size-1 dim")
            i += 1
        elif t == -4:
            while i < len(src):
                out.append(src[i])
                known *= src[i]
                i += 1
        elif t == -5:
            merged = src[i] * src[i + 1]
            out.append(merged)
            known *= merged
            i += 2
        elif t == -6:
            # operands are read from the (possibly reversed) target, exactly
            # like the reference's NumpyXReshapeInferShape(rev_newshape)
            if j + 2 >= len(target):
                raise ValueError(
                    "-6 needs two following entries in the (possibly "
                    f"reversed) target shape, got {target[j:]}")
            d0 = src[i]
            d1, d2 = target[j + 1], target[j + 2]
            if d1 == -1:
                d1 = d0 // d2
            elif d2 == -1:
                d2 = d0 // d1
            if d1 * d2 != d0:
                raise ValueError(
                    f"split dims ({d1}, {d2}) do not divide source dim {d0}")
            out.extend([d1, d2])
            known *= d1 * d2
            i += 1
            j += 2
        else:
            out.append(t)
            known *= t
            i += 1
        j += 1
    if infer_at >= 0:
        total = 1
        for d in src:
            total *= d
        out[infer_at] = total // known
    return tuple(out)


def reshape(a, newshape, reverse=False, order="C"):  # noqa: ARG001
    """npx.reshape with the _npx_* code table (NOT the legacy nd.reshape
    codes — those live on nd.reshape)."""
    from ..ndarray.ndarray import apply_op as _apply

    def pure(v):
        shape = list(newshape) if not isinstance(newshape, int) else [newshape]
        src = list(v.shape)
        if reverse:
            out = npx_reshape_shape(src[::-1], shape[::-1])[::-1]
        else:
            out = npx_reshape_shape(src, shape)
        return v.reshape(out)

    return _apply(pure, a, name="reshape")


def batch_flatten(x):
    """Reference: npx.batch_flatten — collapse all but the batch axis."""
    from ..ndarray.ndarray import apply_op as _apply

    return _apply(lambda v: v.reshape(v.shape[0], -1), x,
                  name="batch_flatten")


def boolean_mask(data, index, axis=0):
    """Dynamic-output row selection (reference: _npi.boolean_mask,
    contrib/boolean_mask.cc — the dynamic-shape exemplar op). Eager
    index snapshot + differentiable gather; hybridized blocks
    containing it drop to imperative mode (CachedOp dynamic-shape)."""
    from ..contrib.ops import boolean_mask as _bm

    return _bm(data, index, axis=axis)


from ..ndarray.register import populate as _populate  # noqa: E402

_populate(globals())


def index_update(data, indices, val):
    """Functional scatter-set: data with data[indices] replaced by val
    (reference: _npx_index_update, src/operator/numpy/np_indexing_op.cc).
    Indices follow npx convention: an int array (N, ndim-prefix) of
    coordinates, or a plain index array for axis 0."""
    from ..ndarray.ndarray import apply_op

    def pure(x, idx, v):
        idx = _jnp.asarray(idx)
        if not (_jnp.issubdtype(idx.dtype, _jnp.integer)
                or idx.dtype == _jnp.bool_):  # bool masks pass through
            idx = idx.astype(_jnp.int32)  # f32 default-dtype indices
        if idx.ndim == 2 and idx.dtype != _jnp.bool_:  # coordinate rows
            return x.at[tuple(idx.T)].set(v)
        return x.at[idx].set(v)

    return apply_op(pure, data, indices, val, name="index_update")


def index_add(data, indices, val):
    """Functional scatter-add (reference: _npx_index_add)."""
    from ..ndarray.ndarray import apply_op

    def pure(x, idx, v):
        idx = _jnp.asarray(idx)
        if not (_jnp.issubdtype(idx.dtype, _jnp.integer)
                or idx.dtype == _jnp.bool_):  # bool masks pass through
            idx = idx.astype(_jnp.int32)  # f32 default-dtype indices
        if idx.ndim == 2 and idx.dtype != _jnp.bool_:
            return x.at[tuple(idx.T)].add(v)
        return x.at[idx].add(v)

    return apply_op(pure, data, indices, val, name="index_add")


def nonzero(data):
    """Indices of nonzero elements as an (N, ndim) int64 array
    (reference: _npx_nonzero). Eager: the output size is data-dependent."""
    arr = data.asnumpy() if hasattr(data, "asnumpy") else _onp.asarray(data)
    idx = _onp.stack(_onp.nonzero(arr), axis=-1) if arr.ndim else \
        _onp.zeros((0, 0), _onp.int64)
    return NDArray(_jnp.asarray(idx.astype(_onp.int64)))


def constraint_check(condition, msg="Constraint violated"):
    """Raise if any element is False, else return 1.0 (reference:
    _npx_constraint_check — the probability-module validation op)."""
    arr = condition.asnumpy() if hasattr(condition, "asnumpy") else \
        _onp.asarray(condition)
    if not bool(arr.all()):
        raise ValueError(msg)
    return NDArray(_jnp.ones((1,), _jnp.float32))


__all__ += ["index_update", "index_add", "nonzero", "constraint_check"]

from . import random  # noqa: F401,E402 - mx.npx.random namespace (last: needs bernoulli et al defined)
