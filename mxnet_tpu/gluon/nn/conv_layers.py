"""Convolution, pooling and padding layers
(reference: python/mxnet/gluon/nn/conv_layers.py).

Layouts: channels-first (NCW/NCHW/NCDHW, the reference default) and
channels-last (NWC/NHWC/NDHWC — the TPU-preferred layout: C rides the lane
dimension so convs feed the MXU without transposes)."""
from __future__ import annotations

import numpy as _np

from ... import numpy_extension as npx
from ...ndarray.ndarray import apply_op
from ..block import HybridBlock
from ..parameter import Parameter

__all__ = ["Conv1D", "Conv2D", "Conv3D", "Conv1DTranspose", "Conv2DTranspose",
           "Conv3DTranspose", "MaxPool1D", "MaxPool2D", "MaxPool3D",
           "AvgPool1D", "AvgPool2D", "AvgPool3D", "GlobalMaxPool1D",
           "GlobalMaxPool2D", "GlobalMaxPool3D", "GlobalAvgPool1D",
           "GlobalAvgPool2D", "GlobalAvgPool3D", "ReflectionPad2D"]


def _tup(v, n):
    if isinstance(v, int):
        return (v,) * n
    return tuple(v)


class _Conv(HybridBlock):
    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, use_bias, in_channels, activation,
                 weight_initializer, bias_initializer, ndim, transpose=False,
                 output_padding=0, layout=None):
        super().__init__()
        self._channels = channels
        self._ndim = ndim
        self._kernel = _tup(kernel_size, ndim)
        self._strides = _tup(strides, ndim)
        self._padding = _tup(padding, ndim)
        self._dilation = _tup(dilation, ndim)
        self._groups = groups
        self._activation = activation
        self._transpose = transpose
        self._output_padding = _tup(output_padding, ndim)
        self._layout = layout
        self._channels_last = layout is not None and layout[-1] == "C"
        self.weight = Parameter("weight",
                                shape=self._weight_shape(in_channels),
                                init=weight_initializer,
                                allow_deferred_init=True)
        self.bias = (Parameter("bias", shape=(channels,),
                               init=bias_initializer or "zeros")
                     if use_bias else None)

    def _weight_shape(self, in_channels):
        c_in = in_channels // self._groups if in_channels else 0
        if self._transpose:
            # reference deconvolution weight: (I, O/g, *k) chan-first,
            # (I, *k, O/g) chan-last
            o = self._channels // self._groups
            if self._channels_last:
                return (in_channels,) + self._kernel + (o,)
            return (in_channels, o) + self._kernel
        if self._channels_last:
            return (self._channels,) + self._kernel + (c_in,)
        return (self._channels, c_in) + self._kernel

    def forward(self, x):
        c_in = x.shape[-1 if self._channels_last else 1]
        if self.weight._is_deferred:
            self.weight._finish_deferred_init(self._weight_shape(c_in))
        w = self.weight.data_for(x)
        b = self.bias.data_for(x) if self.bias is not None else None
        args = (x, w) if b is None else (x, w, b)
        if self._transpose:
            out = npx.deconvolution(
                *args, stride=self._strides, pad=self._padding,
                dilate=self._dilation, output_padding=self._output_padding,
                groups=self._groups, layout=self._layout)
        else:
            out = npx.convolution(
                *args, stride=self._strides, pad=self._padding,
                dilate=self._dilation, groups=self._groups,
                layout=self._layout)
        if self._activation:
            out = npx.activation(out, self._activation)
        return out

    def __repr__(self):
        return (f"{type(self).__name__}({self._channels}, "
                f"kernel_size={self._kernel}, stride={self._strides})")


class Conv1D(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 dilation=1, groups=1, layout="NCW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0):
        assert layout in ("NCW", "NWC"), layout
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, use_bias, in_channels, activation,
                         weight_initializer, bias_initializer, 1,
                         layout=layout)


class Conv2D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 dilation=(1, 1), groups=1, layout="NCHW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0):
        assert layout in ("NCHW", "NHWC"), layout
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, use_bias, in_channels, activation,
                         weight_initializer, bias_initializer, 2,
                         layout=layout)


class Conv3D(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), dilation=(1, 1, 1), groups=1,
                 layout="NCDHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0):
        assert layout in ("NCDHW", "NDHWC"), layout
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, use_bias, in_channels, activation,
                         weight_initializer, bias_initializer, 3,
                         layout=layout)


class Conv1DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NCW",
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0):
        assert layout in ("NCW", "NWC"), layout
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, use_bias, in_channels, activation,
                         weight_initializer, bias_initializer, 1,
                         transpose=True, output_padding=output_padding,
                         layout=layout)


class Conv2DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 output_padding=(0, 0), dilation=(1, 1), groups=1,
                 layout="NCHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0):
        assert layout in ("NCHW", "NHWC"), layout
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, use_bias, in_channels, activation,
                         weight_initializer, bias_initializer, 2,
                         transpose=True, output_padding=output_padding,
                         layout=layout)


class Conv3DTranspose(_Conv):
    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), output_padding=(0, 0, 0),
                 dilation=(1, 1, 1), groups=1, layout="NCDHW",
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0):
        assert layout in ("NCDHW", "NDHWC"), layout
        super().__init__(channels, kernel_size, strides, padding, dilation,
                         groups, use_bias, in_channels, activation,
                         weight_initializer, bias_initializer, 3,
                         transpose=True, output_padding=output_padding,
                         layout=layout)


class _Pool(HybridBlock):
    def __init__(self, pool_size, strides, padding, ndim, pool_type,
                 global_pool=False, count_include_pad=True, ceil_mode=False,
                 layout=None):
        super().__init__()
        self._kernel = _tup(pool_size, ndim)
        self._strides = _tup(strides if strides is not None else pool_size,
                             ndim)
        self._padding = _tup(padding, ndim)
        self._pool_type = pool_type
        self._global = global_pool
        self._count_include_pad = count_include_pad
        self._layout = layout
        self._ceil_mode = bool(ceil_mode)

    def forward(self, x):
        return npx.pooling(
            x, kernel=self._kernel, pool_type=self._pool_type,
            stride=self._strides, pad=self._padding,
            global_pool=self._global,
            count_include_pad=self._count_include_pad,
            layout=self._layout, ceil_mode=self._ceil_mode)

    def __repr__(self):
        return (f"{type(self).__name__}(size={self._kernel}, "
                f"stride={self._strides}, padding={self._padding})")


class MaxPool1D(_Pool):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False):
        assert layout in ("NCW", "NWC"), layout
        super().__init__(pool_size, strides, padding, 1, "max",
                         ceil_mode=ceil_mode, layout=layout)


class MaxPool2D(_Pool):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout="NCHW", ceil_mode=False):
        assert layout in ("NCHW", "NHWC"), layout
        super().__init__(pool_size, strides, padding, 2, "max",
                         ceil_mode=ceil_mode, layout=layout)


class MaxPool3D(_Pool):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout="NCDHW", ceil_mode=False):
        assert layout in ("NCDHW", "NDHWC"), layout
        super().__init__(pool_size, strides, padding, 3, "max",
                         ceil_mode=ceil_mode, layout=layout)


class AvgPool1D(_Pool):
    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False, count_include_pad=True):
        assert layout in ("NCW", "NWC"), layout
        super().__init__(pool_size, strides, padding, 1, "avg",
                         count_include_pad=count_include_pad,
                         ceil_mode=ceil_mode, layout=layout)


class AvgPool2D(_Pool):
    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout="NCHW", ceil_mode=False, count_include_pad=True):
        assert layout in ("NCHW", "NHWC"), layout
        super().__init__(pool_size, strides, padding, 2, "avg",
                         count_include_pad=count_include_pad,
                         ceil_mode=ceil_mode, layout=layout)


class AvgPool3D(_Pool):
    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 layout="NCDHW", ceil_mode=False, count_include_pad=True):
        assert layout in ("NCDHW", "NDHWC"), layout
        super().__init__(pool_size, strides, padding, 3, "avg",
                         count_include_pad=count_include_pad,
                         ceil_mode=ceil_mode, layout=layout)


class _GlobalPool(_Pool):
    def __init__(self, ndim, pool_type, layout=None):
        super().__init__(1, 1, 0, ndim, pool_type, global_pool=True,
                         layout=layout)


class GlobalMaxPool1D(_GlobalPool):
    def __init__(self, layout="NCW"):
        assert layout in ("NCW", "NWC"), layout
        super().__init__(1, "max", layout=layout)


class GlobalMaxPool2D(_GlobalPool):
    def __init__(self, layout="NCHW"):
        assert layout in ("NCHW", "NHWC"), layout
        super().__init__(2, "max", layout=layout)


class GlobalMaxPool3D(_GlobalPool):
    def __init__(self, layout="NCDHW"):
        assert layout in ("NCDHW", "NDHWC"), layout
        super().__init__(3, "max", layout=layout)


class GlobalAvgPool1D(_GlobalPool):
    def __init__(self, layout="NCW"):
        assert layout in ("NCW", "NWC"), layout
        super().__init__(1, "avg", layout=layout)


class GlobalAvgPool2D(_GlobalPool):
    def __init__(self, layout="NCHW"):
        assert layout in ("NCHW", "NHWC"), layout
        super().__init__(2, "avg", layout=layout)


class GlobalAvgPool3D(_GlobalPool):
    def __init__(self, layout="NCDHW"):
        assert layout in ("NCDHW", "NDHWC"), layout
        super().__init__(3, "avg", layout=layout)


class ReflectionPad2D(HybridBlock):
    """Reflection padding (reference: nn.ReflectionPad2D)."""

    def __init__(self, padding=0):
        super().__init__()
        self._padding = _tup(padding, 2)

    def forward(self, x):
        import jax.numpy as jnp

        ph, pw = self._padding
        return apply_op(
            lambda v: jnp.pad(
                v, ((0, 0), (0, 0), (ph, ph), (pw, pw)), mode="reflect"), x)


class _PixelShuffle(HybridBlock):
    def __init__(self, factor, ndim):
        super().__init__()
        self._factor = _tup(factor, ndim)
        self._ndim = ndim

    def __repr__(self):
        return f"{type(self).__name__}({self._factor})"


class PixelShuffle1D(_PixelShuffle):
    """(N, C*f, W) -> (N, C, W*f) sub-pixel upsample (reference:
    nn.PixelShuffle1D, conv_layers.py:1707)."""

    def __init__(self, factor):
        super().__init__(factor, 1)

    def forward(self, x):
        (f,) = self._factor

        def pure(v):
            n, cf, w = v.shape
            c = cf // f
            return v.reshape(n, c, f, w).transpose(0, 1, 3, 2) \
                .reshape(n, c, w * f)

        return apply_op(pure, x)


class PixelShuffle2D(_PixelShuffle):
    """(N, C*fh*fw, H, W) -> (N, C, H*fh, W*fw) (reference:
    nn.PixelShuffle2D, conv_layers.py:1755)."""

    def __init__(self, factor):
        super().__init__(factor, 2)

    def forward(self, x):
        fh, fw = self._factor

        def pure(v):
            n, cff, h, w = v.shape
            c = cff // (fh * fw)
            return v.reshape(n, c, fh, fw, h, w) \
                .transpose(0, 1, 4, 2, 5, 3) \
                .reshape(n, c, h * fh, w * fw)

        return apply_op(pure, x)


class PixelShuffle3D(_PixelShuffle):
    """(N, C*f1*f2*f3, D, H, W) -> (N, C, D*f1, H*f2, W*f3) (reference:
    nn.PixelShuffle3D, conv_layers.py:1818)."""

    def __init__(self, factor):
        super().__init__(factor, 3)

    def forward(self, x):
        f1, f2, f3 = self._factor

        def pure(v):
            n, cf, d, h, w = v.shape
            c = cf // (f1 * f2 * f3)
            return v.reshape(n, c, f1, f2, f3, d, h, w) \
                .transpose(0, 1, 5, 2, 6, 3, 7, 4) \
                .reshape(n, c, d * f1, h * f2, w * f3)

        return apply_op(pure, x)


class DeformableConvolution(HybridBlock):
    """DCNv1 layer: a regular conv branch producing offsets + the
    deformable conv itself (reference: nn.DeformableConvolution,
    conv_layers.py:1277; op contrib/deformable_convolution.cc)."""

    _use_mask = False

    def __init__(self, channels, kernel_size=(1, 1), strides=(1, 1),
                 padding=(0, 0), dilation=(1, 1), groups=1,
                 num_deformable_group=1, layout="NCHW", use_bias=True,
                 in_channels=0, activation=None, weight_initializer=None,
                 bias_initializer="zeros",
                 offset_weight_initializer="zeros",
                 offset_bias_initializer="zeros", offset_use_bias=True):
        super().__init__()
        assert layout == "NCHW", "deformable conv is NCHW-only"
        self._channels = channels
        self._kernel = _tup(kernel_size, 2)
        self._strides = _tup(strides, 2)
        self._padding = _tup(padding, 2)
        self._dilation = _tup(dilation, 2)
        self._groups = groups
        self._ndg = num_deformable_group
        self._activation = activation
        kh, kw = self._kernel
        mult = 3 if self._use_mask else 2
        self.offset_conv = Conv2D(
            mult * num_deformable_group * kh * kw, self._kernel,
            self._strides, self._padding, self._dilation,
            use_bias=offset_use_bias, in_channels=in_channels,
            weight_initializer=offset_weight_initializer,
            bias_initializer=offset_bias_initializer)
        self.weight = Parameter(
            "weight",
            shape=(channels, in_channels // groups if in_channels else 0,
                   kh, kw),
            init=weight_initializer, allow_deferred_init=True)
        self.bias = (Parameter("bias", shape=(channels,),
                               init=bias_initializer)
                     if use_bias else None)

    def forward(self, x):
        from ...ops import vision as _vision

        c_in = x.shape[1]
        if self.weight._is_deferred:
            kh, kw = self._kernel
            self.weight._finish_deferred_init(
                (self._channels, c_in // self._groups, kh, kw))
        offs = self.offset_conv(x)
        kh, kw = self._kernel
        if self._use_mask:
            n_off = 2 * self._ndg * kh * kw
            offset, m = offs[:, :n_off], offs[:, n_off:]
            import jax

            m = apply_op(jax.nn.sigmoid, m)
        else:
            offset, m = offs, None
        w = self.weight.data_for(x)
        b = self.bias.data_for(x) if self.bias is not None else None

        def pure(xv, ov, wv, *rest):
            i = 0
            bv = mv = None
            if b is not None:
                bv = rest[i]; i += 1
            if m is not None:
                mv = rest[i]; i += 1
            return _vision.deformable_convolution(
                xv, ov, wv, bias=bv, kernel=self._kernel,
                stride=self._strides, pad=self._padding,
                dilate=self._dilation, num_deformable_group=self._ndg,
                groups=self._groups, mask=mv)

        extra = [a for a in (b, m) if a is not None]
        out = apply_op(pure, x, offset, w, *extra)
        if self._activation:
            out = npx.activation(out, self._activation)
        return out


class ModulatedDeformableConvolution(DeformableConvolution):
    """DCNv2: offsets + sigmoid-modulated sample masks (reference:
    nn.ModulatedDeformableConvolution, conv_layers.py:1501)."""

    _use_mask = True


__all__ += ["PixelShuffle1D", "PixelShuffle2D", "PixelShuffle3D",
            "DeformableConvolution", "ModulatedDeformableConvolution"]
