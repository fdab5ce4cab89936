"""Neural-net primitive ops as pure jax functions (NCHW default, NHWC fast path).

TPU re-design of src/operator/nn/ (convolution, fully_connected, pooling,
batch_norm, layer_norm, softmax, activation, dropout...): each op is a pure
function lowered by XLA — conv → MXU convolution HLO, pooling →
reduce_window, norms → fused VPU chains. cuDNN/oneDNN dispatch layers are
unnecessary.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from ..base import normalize_dtype
from .registry import register_op

# ---------------------------------------------------------------------------
# dense / linear
# ---------------------------------------------------------------------------


@register_op("fully_connected")
def dense(x, weight, bias=None, flatten=True, num_hidden=None,
          no_bias=None):  # noqa: ARG001 - reference-signature parity
    """y = x @ W^T + b (reference: src/operator/nn/fully_connected.cc).

    weight layout (out_units, in_units) matches the reference so checkpoints
    map 1:1. With flatten=True input is reshaped to (N, -1) first.
    num_hidden is accepted for reference-call-signature parity; the
    weight shape is authoritative. no_bias=True drops the bias even if
    one is passed (reference semantics).
    """
    if no_bias:
        bias = None
    if flatten and x.ndim > 2:
        x = x.reshape(x.shape[0], -1)
    y = jnp.matmul(x, weight.T)
    if bias is not None:
        y = y + bias
    return y


# ---------------------------------------------------------------------------
# convolution
# ---------------------------------------------------------------------------

def _spec(ndim):
    # NC + spatial; kernel OI + spatial
    sp = "DHW"[-ndim:] if ndim <= 3 else None
    return ("NC" + sp, "OI" + sp, "NC" + sp)


def _layout_spec(layout):
    """Map a reference layout string (NCHW/NHWC/NCW/NWC/NCDHW/NDHWC) to
    (lhs_spec, rhs_spec, ndim). Channels-last puts C in the lane dimension —
    the MXU-preferred physical layout on TPU; kernel follows the reference
    convention: O,I,*k channels-first, O,*k,I channels-last
    (src/operator/nn/convolution-inl.h layout handling)."""
    sp = layout.replace("N", "").replace("C", "")
    nd = len(sp)
    if layout[1] == "C":  # channels-first
        return "NC" + sp, "OI" + sp, nd
    return "N" + sp + "C", "O" + sp + "I", nd


@register_op("convolution")
def conv(x, weight, bias=None, stride=None, pad=None, dilate=None, groups=1,
         layout=None):
    """N-d convolution; layout NCHW (default) or NHWC family.

    weight (O, I/g, *k) channels-first, (O, *k, I/g) channels-last — matching
    the reference's per-layout weight shapes. Reference:
    src/operator/nn/convolution.cc. Lowers to a single XLA
    conv_general_dilated → MXU; channels-last keeps C in lanes.
    """
    nd = x.ndim - 2
    if layout is None:
        lhs_spec, rhs_spec = _spec(nd)[:2]
        channels_last = False
    else:
        lhs_spec, rhs_spec, lnd = _layout_spec(layout)
        assert lnd == nd, f"layout {layout} does not match input ndim {x.ndim}"
        channels_last = layout[-1] == "C"
    stride = stride or (1,) * nd
    pad = pad or (0,) * nd
    dilate = dilate or (1,) * nd
    if isinstance(stride, int):
        stride = (stride,) * nd
    if isinstance(pad, int):
        pad = (pad,) * nd
    if isinstance(dilate, int):
        dilate = (dilate,) * nd
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, (lhs_spec, rhs_spec, lhs_spec))
    y = lax.conv_general_dilated(
        x,
        weight,
        window_strides=tuple(stride),
        padding=[(p, p) for p in pad],
        rhs_dilation=tuple(dilate),
        dimension_numbers=dn,
        feature_group_count=groups,
        preferred_element_type=None,
    )
    if bias is not None:
        y = y + (bias if channels_last
                 else bias.reshape((1, -1) + (1,) * nd))
    return y


@register_op("deconvolution")
def conv_transpose(x, weight, bias=None, stride=None, pad=None, dilate=None,
                   output_padding=None, groups=1, layout=None):
    """Transposed convolution (reference: src/operator/nn/deconvolution.cc).

    weight (I, O/g, *k) channels-first / (I, *k, O/g) channels-last like the
    reference; implemented as the gradient of conv via conv_general_dilated
    with an IO spatial kernel spec and lhs dilation.
    """
    nd = x.ndim - 2
    stride = stride or (1,) * nd
    pad = pad or (0,) * nd
    dilate = dilate or (1,) * nd
    output_padding = output_padding or (0,) * nd
    if isinstance(stride, int):
        stride = (stride,) * nd
    if isinstance(pad, int):
        pad = (pad,) * nd
    if isinstance(dilate, int):
        dilate = (dilate,) * nd
    if isinstance(output_padding, int):
        output_padding = (output_padding,) * nd
    sp = "DHW"[-nd:]
    channels_last = layout is not None and layout[-1] == "C"
    if channels_last:
        lhs_spec, rhs_spec = "N" + sp + "C", "I" + sp + "O"
    else:
        lhs_spec, rhs_spec = "NC" + sp, "IO" + sp
    dn = lax.conv_dimension_numbers(
        x.shape, weight.shape, (lhs_spec, rhs_spec, lhs_spec)
    )
    k = weight.shape[1:-1] if channels_last else weight.shape[2:]
    # padding for transpose conv uses the DILATED kernel extent
    # (k-1)*dilate + 1: eff_k - 1 - p on both sides, + output_padding low
    padding = [
        ((ki - 1) * di - pi, (ki - 1) * di - pi + opi)
        for ki, pi, di, opi in zip(k, pad, dilate, output_padding)
    ]
    # the transpose of cross-correlation convolves with the ROT-180 kernel
    # (reference deconvolution.cc backward-as-forward; conv_general_dilated
    # itself computes cross-correlation, so flip the spatial dims)
    spatial_axes = tuple(range(1, 1 + nd)) if channels_last \
        else tuple(range(2, 2 + nd))
    weight = jnp.flip(weight, spatial_axes)
    y = lax.conv_general_dilated(
        x,
        weight,
        window_strides=(1,) * nd,
        padding=padding,
        lhs_dilation=tuple(stride),
        rhs_dilation=tuple(dilate),
        dimension_numbers=dn,
        feature_group_count=groups,
    )
    if bias is not None:
        y = y + (bias if channels_last
                 else bias.reshape((1, -1) + (1,) * nd))
    return y


# ---------------------------------------------------------------------------
# pooling
# ---------------------------------------------------------------------------


@register_op("pooling")
def pool(x, kernel, pool_type="max", stride=None, pad=None, global_pool=False,
         count_include_pad=True, layout=None, ceil_mode=False,
         pooling_convention=None):
    """Max/avg/lp pooling via reduce_window (reference: nn/pooling.cc).

    layout: None/channels-first ("NCHW"...) pools x[2:]; channels-last
    ("NHWC"...) pools x[1:-1]. ceil_mode (the reference's
    pooling_convention='full') rounds output sizes UP by padding extra
    rows/cols on the high side of each spatial dim."""
    same_mode = False
    if pooling_convention is not None:
        if pooling_convention == "full":
            ceil_mode = True
        elif pooling_convention == "same":
            same_mode = True
        elif pooling_convention != "valid":
            raise ValueError(
                f"unknown pooling_convention {pooling_convention!r}; "
                "expected valid/full/same")
    nd = x.ndim - 2
    channels_last = layout is not None and layout[-1] == "C"
    sp = slice(1, -1) if channels_last else slice(2, None)
    if global_pool:
        kernel = x.shape[sp]
        stride = (1,) * nd
        pad = (0,) * nd
    if isinstance(kernel, int):
        kernel = (kernel,) * nd
    stride = stride or kernel
    if isinstance(stride, int):
        stride = (stride,) * nd
    pad = pad or (0,) * nd
    if isinstance(pad, int):
        pad = (pad,) * nd
    pad_pairs = [(p, p) for p in pad]
    if same_mode and not global_pool:
        # output = ceil(n / stride); pad split low/high like the
        # reference's same convention
        spatial = x.shape[sp]
        for i, (n, k, st) in enumerate(zip(spatial, kernel, stride)):
            out_same = -(-n // st)
            total = max((out_same - 1) * st + k - n, 0)
            pad_pairs[i] = (total // 2, total - total // 2)
    if ceil_mode and not global_pool:
        spatial = x.shape[sp]
        for i, (n, k, st, p) in enumerate(
                zip(spatial, kernel, stride, pad)):
            span = n + 2 * p - k
            out_full = -(-span // st) + 1          # ceil
            extra = (out_full - 1) * st + k - (n + 2 * p)
            pad_pairs[i] = (p, p + max(0, extra))
    if channels_last:
        window = (1,) + tuple(kernel) + (1,)
        strides = (1,) + tuple(stride) + (1,)
        padding = ((0, 0),) + tuple(pad_pairs) + ((0, 0),)
    else:
        window = (1, 1) + tuple(kernel)
        strides = (1, 1) + tuple(stride)
        padding = ((0, 0), (0, 0)) + tuple(pad_pairs)
    if pool_type == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else jnp.iinfo(x.dtype).min
        return lax.reduce_window(x, init, lax.max, window, strides, padding)
    if pool_type in ("avg", "sum"):
        s = lax.reduce_window(x, 0.0 if jnp.issubdtype(x.dtype, jnp.floating)
                              else 0, lax.add, window, strides, padding)
        if pool_type == "sum":
            return s
        if count_include_pad:
            denom = 1
            for k in kernel:
                denom *= k
            return s / denom
        ones = jnp.ones(x.shape[sp], x.dtype)
        ones = ones[None, ..., None] if channels_last else ones[None, None]
        counts = lax.reduce_window(ones, 0.0, lax.add, window, strides, padding)
        return s / counts
    if pool_type == "lp":
        p = 2.0
        s = lax.reduce_window(jnp.abs(x) ** p, 0.0, lax.add, window, strides,
                              padding)
        return s ** (1.0 / p)
    raise ValueError(f"unknown pool_type {pool_type}")


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------


def _bn_shapes(x, axis):
    reduce_axes = tuple(i for i in range(x.ndim) if i != axis)
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    n = x.size // x.shape[axis]
    return reduce_axes, tuple(bshape), n


def _bn_train_impl(x, gamma, beta, shift, eps, axis):
    """One reduction pass (sum + sum-of-squares multi-output-fused by XLA,
    reading the activation once) + one fused elementwise normalize, all
    in float32 whatever the activation's dtype.

    The sums are taken over (x - shift) with shift = the moving mean — a
    per-channel constant that costs nothing (it fuses into the same pass)
    but removes the catastrophic cancellation of the textbook
    E[x²]−E[x]² form once the running mean tracks the data scale
    (var is shift-invariant mathematically)."""
    reduce_axes, bshape, n = _bn_shapes(x, axis)
    s = lax.stop_gradient(shift.astype(jnp.float32)).reshape(bshape)
    xf = x.astype(jnp.float32) - s
    s1 = jnp.sum(xf, reduce_axes)
    s2 = jnp.sum(xf * xf, reduce_axes)
    mean_c = s1 / n
    var = jnp.maximum(s2 / n - mean_c * mean_c, 0.0)
    mean = mean_c + s.reshape(s1.shape)
    inv = lax.rsqrt(var + eps)
    scale = (gamma.astype(jnp.float32) * inv).reshape(bshape)
    # xf is already centered on s, so normalize against the centered mean
    offset = (beta.astype(jnp.float32)
              - mean_c * gamma.astype(jnp.float32) * inv).reshape(bshape)
    out = (xf * scale + offset).astype(x.dtype)
    return out, mean, var, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _bn_train(x, gamma, beta, shift, eps, axis):
    out, mean, var, _ = _bn_train_impl(x, gamma, beta, shift, eps, axis)
    return out, mean, var


def _bn_train_fwd(x, gamma, beta, shift, eps, axis):
    out, mean, var, inv = _bn_train_impl(x, gamma, beta, shift, eps, axis)
    return (out, mean, var), (x, gamma, beta, shift, mean, inv)


def _bn_train_bwd(eps, axis, res, cts):
    """Closed-form BN backward: ONE pass producing both reductions
    (dbeta, dgamma multi-output-fused) + one fused elementwise pass for dx —
    instead of autodiff's per-stat reduction chains through mean/var."""
    dy, dmean_ct, dvar_ct = cts
    x, gamma, beta, shift, mean, inv = res
    reduce_axes, bshape, n = _bn_shapes(x, axis)
    dyf = dy.astype(jnp.float32)
    # centered on the saved shift like the forward: (x - shift) and
    # (mean - shift) are both on the data's centered scale
    s = lax.stop_gradient(shift.astype(jnp.float32)).reshape(bshape)
    xf = x.astype(jnp.float32) - s
    mean_c = mean.reshape(bshape) - s
    xhat = (xf - mean_c) * inv.reshape(bshape)
    dbeta = jnp.sum(dyf, reduce_axes)
    dgamma = jnp.sum(dyf * xhat, reduce_axes)
    g32 = gamma.astype(jnp.float32)
    dx = (g32 * inv).reshape(bshape) * (
        dyf - (dbeta.reshape(bshape)
               + xhat * dgamma.reshape(bshape)) / n)
    # cotangents of the batch-stat outputs (aux moving-stat path; usually
    # zero) — cheap broadcast terms that fuse into the dx pass
    dx = dx + (dmean_ct.reshape(bshape) / n
               + dvar_ct.reshape(bshape) * 2.0 * (xf - mean_c) / n)
    return (dx.astype(x.dtype), dgamma.astype(gamma.dtype),
            dbeta.astype(beta.dtype), jnp.zeros_like(shift))


_bn_train.defvjp(_bn_train_fwd, _bn_train_bwd)


@register_op("batch_norm")
def batch_norm(x, gamma, beta, moving_mean, moving_var, eps=1e-5,
               momentum=0.9, training=True, use_global_stats=False, axis=1):
    """Batch normalization (reference: nn/batch_norm.cc).

    Returns (out, new_mean, new_var). The stateful moving-stat update is done
    by the caller (BatchNorm layer / state sink), keeping this function pure.
    Training mode uses a custom_vjp so fwd reads the activation once (fused
    sum/sum² stats) and bwd is the closed-form two-pass kernel.
    """
    axis = axis % x.ndim  # normalize negative axis (-1 = channels-last)
    if training and not use_global_stats:
        out, mean, var = _bn_train(x, gamma, beta, moving_mean,
                                   float(eps), axis)
        new_mean = moving_mean * momentum + mean.astype(moving_mean.dtype) * (1 - momentum)
        new_var = moving_var * momentum + var.astype(moving_var.dtype) * (1 - momentum)
        return out, new_mean, new_var
    _, bshape, _ = _bn_shapes(x, axis)
    mean, var = moving_mean, moving_var
    inv = lax.rsqrt(var.astype(jnp.float32) + eps)
    scale = (gamma.astype(jnp.float32) * inv).reshape(bshape)
    shift = (beta.astype(jnp.float32)
             - mean.astype(jnp.float32) * gamma.astype(jnp.float32)
             * inv).reshape(bshape)
    out = (x.astype(jnp.float32) * scale + shift).astype(x.dtype)
    return out, moving_mean, moving_var


def _ln_impl(x, gamma, beta, eps, axis):
    """Single-pass stats (sum/sum² multi-output-fused): shifted
    var = E[(x−x₀)²]−E[x−x₀]² with x₀ = the row's first element, which is
    on the data's scale and so removes the cancellation of the raw
    E[x²]−E[x]² form (variance is shift-invariant mathematically)."""
    xf = x.astype(jnp.float32)
    n = x.shape[axis]
    x0 = lax.stop_gradient(
        lax.slice_in_dim(xf, 0, 1, axis=axis % x.ndim))
    xc = xf - x0
    s1 = jnp.sum(xc, axis=axis, keepdims=True)
    s2 = jnp.sum(xc * xc, axis=axis, keepdims=True)
    mean_c = s1 / n
    var = jnp.maximum(s2 / n - mean_c * mean_c, 0.0)
    mean = mean_c + x0
    inv = lax.rsqrt(var + eps)
    xhat = (xf - mean) * inv
    out = xhat
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    if gamma is not None:
        out = out * gamma.astype(jnp.float32).reshape(bshape)
    if beta is not None:
        out = out + beta.astype(jnp.float32).reshape(bshape)
    return out.astype(x.dtype), mean, inv


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _ln(x, gamma, beta, eps, axis):
    return _ln_impl(x, gamma, beta, eps, axis)[0]


def _ln_fwd(x, gamma, beta, eps, axis):
    out, mean, inv = _ln_impl(x, gamma, beta, eps, axis)
    return out, (x, gamma, beta, mean, inv)


def _ln_bwd(eps, axis, res, dy):
    """Closed-form LN backward: one fused pass per tensor instead of
    autodiff's reduction chains through mean/var."""
    x, gamma, beta, mean, inv = res
    bshape = [1] * x.ndim
    bshape[axis] = x.shape[axis]
    n = x.shape[axis]
    xf = x.astype(jnp.float32)
    dyf = dy.astype(jnp.float32)
    xhat = (xf - mean) * inv
    a = (dyf * gamma.astype(jnp.float32).reshape(bshape)
         if gamma is not None else dyf)
    m1 = jnp.sum(a, axis=axis, keepdims=True) / n
    m2 = jnp.sum(a * xhat, axis=axis, keepdims=True) / n
    dx = (inv * (a - m1 - xhat * m2)).astype(x.dtype)
    param_axes = tuple(i for i in range(x.ndim) if i != axis % x.ndim)
    dgamma = (jnp.sum(dyf * xhat, axis=param_axes).astype(gamma.dtype)
              if gamma is not None else None)
    dbeta = (jnp.sum(dyf, axis=param_axes).astype(beta.dtype)
             if beta is not None else None)
    return dx, dgamma, dbeta


_ln.defvjp(_ln_fwd, _ln_bwd)


@register_op("layer_norm")
def layer_norm(x, gamma, beta, axis=-1, eps=1e-5):
    """Layer normalization (reference: nn/layer_norm.cc).

    custom_vjp: fwd reads x once (fused sum/sum² stats); bwd is the
    closed-form kernel (dx in one fused pass, dgamma/dbeta multi-output)."""
    return _ln(x, gamma, beta, float(eps), axis)


@register_op("group_norm")
def group_norm(x, gamma, beta, num_groups, eps=1e-5):
    """Group normalization over NC+spatial (reference: nn/group_norm.cc)."""
    n, c = x.shape[:2]
    g = num_groups
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    xg = (xg - mean) * lax.rsqrt(var + eps)
    out = xg.reshape(x.shape)
    bshape = (1, c) + (1,) * (x.ndim - 2)
    if gamma is not None:
        out = out * gamma.reshape(bshape)
    if beta is not None:
        out = out + beta.reshape(bshape)
    return out


@register_op("instance_norm")
def instance_norm(x, gamma, beta, eps=1e-5):
    """Instance norm = group norm with one group per channel."""
    return group_norm(x, gamma, beta, num_groups=x.shape[1], eps=eps)


@register_op("rms_norm")
def rms_norm(x, gamma, axis=-1, eps=1e-6):
    """RMSNorm — modern-transformer extension beyond the reference set."""
    ms = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=axis, keepdims=True)
    out = (x.astype(jnp.float32) * lax.rsqrt(ms + eps)).astype(x.dtype)
    if gamma is not None:
        out = out * gamma
    return out


@register_op("rotary_embedding")
def rotary_embedding(x, positions, theta=10000.0, interleaved=False):
    """Rotary position embedding, rotate-half form: with the head's
    width d, frequencies theta ** (-2i / d) for i < d / 2 and the angle
    a = position * frequency laid out twice along the width,

        out = x * cos(a) + concat(-x[d/2:], x[:d/2]) * sin(a).

    x: (..., S, d); ``positions``: the S explicit position ids (or any
    shape that broadcasts against x's leading dimensions, e.g. (B, 1,
    S)).  Angles and the rotation are float32; the result has x's
    type.

    ``interleaved``: x pairs neighbours, (x[2i], x[2i + 1]) turning by
    frequency i.  They are first moved into the rotate-half layout,
    [x[0], x[2], ... ; x[1], x[3], ...], and the result stays in it: a
    score q . k does not depend on a permutation common to both."""
    d = x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = positions.astype(jnp.float32)[..., None] * inv_freq
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)
    x32 = x.astype(jnp.float32)
    if interleaved:
        x32 = jnp.concatenate([x32[..., 0::2], x32[..., 1::2]], axis=-1)
    rotated = jnp.concatenate([-x32[..., half:], x32[..., :half]], axis=-1)
    return (x32 * cos + rotated * sin).astype(x.dtype)


@register_op("lrn")
def lrn(x, nsize=5, alpha=1e-4, beta=0.75, knorm=2.0):
    """Local response normalization (reference: nn/lrn.cc)."""
    sq = jnp.square(x)
    half = nsize // 2
    sq_pad = jnp.pad(sq, ((0, 0), (half, half)) + ((0, 0),) * (x.ndim - 2))
    acc = sum(
        lax.dynamic_slice_in_dim(sq_pad, i, x.shape[1], axis=1)
        for i in range(nsize)
    )
    return x / (knorm + alpha / nsize * acc) ** beta


# ---------------------------------------------------------------------------
# softmax family
# ---------------------------------------------------------------------------


@register_op("softmax")
def softmax(x, axis=-1, length=None, temperature=None):
    """Softmax with optional sequence-length masking (reference: nn/softmax.cc)."""
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    if length is not None:
        mask = jnp.arange(x.shape[axis]) < jnp.expand_dims(length, -1)
        shape = [1] * x.ndim
        shape[0] = x.shape[0]
        shape[axis] = x.shape[axis]
        x = jnp.where(mask.reshape(shape), x, -jnp.inf)
    return jax.nn.softmax(x, axis=axis)


@register_op("log_softmax")
def log_softmax(x, axis=-1, temperature=None):
    if temperature is not None and temperature != 1.0:
        x = x / temperature
    return jax.nn.log_softmax(x, axis=axis)


@register_op("softmin")
def softmin(x, axis=-1):
    return jax.nn.softmax(-x, axis=axis)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------

_ACTS = {
    "relu": jax.nn.relu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
    "softrelu": jax.nn.softplus,
    "softsign": jax.nn.soft_sign,
    "softmax": jax.nn.softmax,
    "log_softmax": jax.nn.log_softmax,
    # reference gelu (mshadow_op.h) is the exact erf form; the tanh
    # approximation is opt-in under its own name
    "gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "erf_gelu": lambda x: jax.nn.gelu(x, approximate=False),
    "gelu_tanh": lambda x: jax.nn.gelu(x, approximate=True),
    "silu": jax.nn.silu,
    "swish": jax.nn.silu,
    "mish": lambda x: x * jnp.tanh(jax.nn.softplus(x)),
    "hard_sigmoid": jax.nn.hard_sigmoid,
    "hard_swish": jax.nn.hard_swish,
    "relu6": lambda x: jnp.clip(x, 0.0, 6.0),
    "identity": lambda x: x,
}


@register_op("activation")
def activation(x, act_type="relu"):
    """Activation dispatch (reference: nn/activation.cc act_type enum)."""
    try:
        return _ACTS[act_type](x)
    except KeyError:
        raise ValueError(f"unknown act_type '{act_type}'") from None


@register_op("leaky_relu")
def leaky_relu(x, gamma=None, act_type="leaky", slope=0.25):
    """LeakyReLU family (reference: leaky_relu.cc: leaky/prelu/elu/selu/gelu)."""
    if act_type == "leaky":
        return jnp.where(x >= 0, x, slope * x)
    if act_type == "prelu":
        ndim = x.ndim
        if gamma.ndim == 1 and ndim > 2:
            gamma = gamma.reshape((1, -1) + (1,) * (ndim - 2))
        return jnp.where(x >= 0, x, gamma * x)
    if act_type == "elu":
        return jnp.where(x >= 0, x, slope * jnp.expm1(x))
    if act_type == "selu":
        return jax.nn.selu(x)
    if act_type == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if act_type == "rrelu":
        return jnp.where(x >= 0, x, slope * x)  # eval-mode rrelu
    raise ValueError(f"unknown act_type '{act_type}'")


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------


@register_op("dropout")
def dropout(x, key, p=0.5, training=True, axes=None):
    """Inverted dropout (reference: nn/dropout.cc). Key is explicit — the
    stateful facade supplies it (mx._random.next_key / trace provider)."""
    if not training or p <= 0.0:
        return x
    shape = list(x.shape)
    if axes:
        # `axes` are the axes the mask is SHARED along (reference
        # nn/dropout.cc axes param): mask broadcasts over them.
        for ax in axes:
            shape[ax] = 1
    keep = 1.0 - p
    mask = jax.random.bernoulli(key, keep, tuple(shape))
    return jnp.where(mask, x / keep, jnp.zeros((), x.dtype))


# ---------------------------------------------------------------------------
# indexing / embedding / misc NN ops
# ---------------------------------------------------------------------------


@register_op("embedding")
def embedding(indices, weight, input_dim=None, output_dim=None,
              dtype=None, sparse_grad=False):  # noqa: ARG001
    """Embedding lookup (reference: tensor/indexing_op.cc Embedding;
    frontend signature numpy_extension/_op.py:976 carries
    input_dim/output_dim/dtype/sparse_grad).

    Gather on MXU-friendly layout; gradient is a dense scatter-add (the
    reference's row_sparse grad path is deliberately dense here — see
    ndarray.py module doc on sparse). input_dim/output_dim are shape
    hints validated against the weight; sparse_grad is honored at the
    gluon layer (Parameter row hints), not here.
    """
    if input_dim is not None and weight.shape[0] != input_dim:
        raise ValueError(
            f"embedding input_dim {input_dim} != weight rows "
            f"{weight.shape[0]}")
    if output_dim is not None and weight.shape[-1] != output_dim:
        raise ValueError(
            f"embedding output_dim {output_dim} != weight cols "
            f"{weight.shape[-1]}")
    out = jnp.take(weight, indices.astype(jnp.int32), axis=0)
    if dtype is not None:
        out = out.astype(normalize_dtype(dtype))
    return out


@register_op("one_hot")
def one_hot(indices, depth, on_value=1.0, off_value=0.0, dtype=jnp.float32):
    oh = jax.nn.one_hot(indices.astype(jnp.int32), depth, dtype=dtype)
    if on_value != 1.0 or off_value != 0.0:
        oh = oh * (on_value - off_value) + off_value
    return oh


@register_op("pick")
def pick(x, index, axis=-1, keepdims=False, mode="clip"):
    """Pick elements along axis by index (reference: tensor/broadcast_reduce_op_index.cc)."""
    index = index.astype(jnp.int32)
    if mode == "clip":
        index = jnp.clip(index, 0, x.shape[axis] - 1)
    else:
        index = index % x.shape[axis]
    picked = jnp.take_along_axis(x, jnp.expand_dims(index, axis), axis=axis)
    return picked if keepdims else jnp.squeeze(picked, axis=axis)


@register_op("topk")
def topk(x, k=1, axis=-1, ret_typ="indices", is_ascend=False,
         dtype="float32"):
    """Top-k (reference: tensor/ordering_op.cc; `dtype` controls the
    INDEX dtype like the reference frontend). Uses lax.top_k on last
    axis."""
    xm = jnp.moveaxis(x, axis, -1)
    if is_ascend:
        vals, idx = lax.top_k(-xm, k)
        vals = -vals
    else:
        vals, idx = lax.top_k(xm, k)
    vals = jnp.moveaxis(vals, -1, axis)
    idx = jnp.moveaxis(idx, -1, axis)

    def cast_idx(i):
        # `dtype` applies only to RETURNED indices (None = native int32);
        # mask/value paths keep exact int indices — a float32 index is
        # only exact below 2^24 and the cast is wasted work there
        return i if dtype is None else i.astype(normalize_dtype(dtype))

    if ret_typ == "indices":
        return cast_idx(idx)
    if ret_typ == "value":
        return vals
    if ret_typ == "both":
        return vals, cast_idx(idx)
    if ret_typ == "mask":
        # 0/1 mask of the selected cells in the input's shape
        # (reference ordering_op.cc ReturnType kReturnMask)
        lastax_idx = jnp.moveaxis(idx, axis, -1)  # (..., k) over xm
        mask = jax.nn.one_hot(lastax_idx, xm.shape[-1],
                              dtype=x.dtype).sum(-2)
        return jnp.moveaxis(mask, -1, axis)
    raise ValueError(f"unknown ret_typ {ret_typ}")


@register_op("sequence_mask")
def sequence_mask(x, sequence_length=None, use_sequence_length=False,
                  value=0.0, axis=0):
    """Mask sequences beyond their length (reference: sequence_mask.cc)."""
    if not use_sequence_length or sequence_length is None:
        return x
    steps = jnp.arange(x.shape[axis])
    # x: (T, N, ...) if axis==0 else (N, T, ...)
    if axis == 0:
        mask = steps[:, None] < sequence_length[None, :]
    else:
        mask = steps[None, :] < sequence_length[:, None]
    mask = mask.reshape(mask.shape + (1,) * (x.ndim - 2))
    return jnp.where(mask, x, jnp.asarray(value, x.dtype))


@register_op("sequence_last")
def sequence_last(x, sequence_length=None, use_sequence_length=False, axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.take(x, -1, axis=axis)
    idx = (sequence_length - 1).astype(jnp.int32)
    if axis == 0:
        return jnp.take_along_axis(
            x, idx.reshape((1, -1) + (1,) * (x.ndim - 2)), axis=0
        ).squeeze(0)
    return jnp.take_along_axis(
        x, idx.reshape((-1, 1) + (1,) * (x.ndim - 2)), axis=1
    ).squeeze(1)


@register_op("sequence_reverse")
def sequence_reverse(x, sequence_length=None, use_sequence_length=False,
                     axis=0):
    if not use_sequence_length or sequence_length is None:
        return jnp.flip(x, axis=axis)
    t = x.shape[axis]
    steps = jnp.arange(t)
    # reversed index within each sequence, identity beyond length
    if axis != 0:
        raise NotImplementedError("sequence_reverse supports axis=0 (T,N,...)")
    lengths = sequence_length.astype(jnp.int32)
    rev = jnp.where(steps[:, None] < lengths[None, :],
                    lengths[None, :] - 1 - steps[:, None], steps[:, None])
    return jnp.take_along_axis(x, rev.reshape(rev.shape + (1,) * (x.ndim - 2)),
                               axis=0)


@register_op("l2_normalization")
def l2_normalization(x, eps=1e-10, mode="instance"):
    if mode == "instance":
        axes = tuple(range(1, x.ndim))
    elif mode == "channel":
        axes = (1,)
    else:  # spatial
        axes = tuple(range(2, x.ndim))
    norm = jnp.sqrt(jnp.sum(jnp.square(x), axis=axes, keepdims=True) + eps)
    return x / norm


@register_op("upsampling")
def upsample(x, scale=2, sample_type="nearest"):
    """Spatial upsampling (reference: nn/upsampling.cc)."""
    n, c, h, w = x.shape
    if sample_type == "nearest":
        return jax.image.resize(x, (n, c, h * scale, w * scale), "nearest")
    return jax.image.resize(x, (n, c, h * scale, w * scale), "bilinear")


@register_op("moments")
def moments(x, axes=None, keepdims=False):
    mean = jnp.mean(x, axis=axes, keepdims=keepdims)
    var = jnp.var(x, axis=axes, keepdims=keepdims)
    return mean, var
