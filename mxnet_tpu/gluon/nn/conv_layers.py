"""Gluon convolution and pooling layers.

Reference: python/mxnet/gluon/nn/conv_layers.py (_Conv base, Conv1D-3D,
Conv1D-3DTranspose, MaxPool/AvgPool 1-3D, GlobalMaxPool/GlobalAvgPool 1-3D,
ReflectionPad2D).

TPU notes: convs lower onto the MXU via XLA's conv_general_dilated; NCHW
layouts are kept at the API for reference parity (XLA changes layouts
internally). Pooling lowers to lax.reduce_window.
"""
from __future__ import annotations

from ..block import HybridBlock
from .activations import Activation
from ... import symbol

__all__ = ["Conv1D", "Conv2D", "Conv3D", "Conv1DTranspose",
           "Conv2DTranspose", "Conv3DTranspose", "MaxPool1D", "MaxPool2D",
           "MaxPool3D", "AvgPool1D", "AvgPool2D", "AvgPool3D",
           "GlobalMaxPool1D", "GlobalMaxPool2D", "GlobalMaxPool3D",
           "GlobalAvgPool1D", "GlobalAvgPool2D", "GlobalAvgPool3D",
           "ReflectionPad2D"]


def _to_tuple(x, n):
    if isinstance(x, (list, tuple)):
        assert len(x) == n
        return tuple(x)
    return (x,) * n


class _Conv(HybridBlock):
    """Base conv layer (reference: nn/conv_layers.py:35)."""

    def __init__(self, channels, kernel_size, strides, padding, dilation,
                 groups, layout, in_channels=0, activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", op_name="Convolution",
                 adj=None, prefix=None, params=None):
        super().__init__(prefix=prefix, params=params)
        with self.name_scope():
            self._channels = channels
            self._in_channels = in_channels
            if isinstance(strides, int):
                strides = (strides,) * len(kernel_size)
            if isinstance(padding, int):
                padding = (padding,) * len(kernel_size)
            if isinstance(dilation, int):
                dilation = (dilation,) * len(kernel_size)
            self._op_name = op_name
            self._kwargs = {
                "kernel": kernel_size, "stride": strides, "dilate": dilation,
                "pad": padding, "num_filter": channels, "num_group": groups,
                "no_bias": not use_bias, "layout": layout}
            if adj is not None:
                self._kwargs["adj"] = adj

            if op_name == "Convolution":
                dshape = [0] * (len(kernel_size) + 2)
                dshape[layout.find("N")] = 1
                dshape[layout.find("C")] = in_channels
                from ...ops.nn import is_channels_last
                cin = in_channels // groups if in_channels else 0
                if is_channels_last(layout):
                    # channels-last (NHWC family): (channels, *kernel, cin)
                    wshape = (channels,) + tuple(kernel_size) + (cin,)
                else:
                    # channels-first: (channels, in_channels/groups, *kernel)
                    wshape = (channels, cin) + tuple(kernel_size)
            else:  # Deconvolution: (in_channels, channels/groups, *kernel)
                wshape = (in_channels,
                          channels // groups if channels else 0) \
                    + tuple(kernel_size)
            self.weight = self.params.get(
                "weight", shape=wshape, init=weight_initializer,
                allow_deferred_init=True)
            if use_bias:
                self.bias = self.params.get(
                    "bias", shape=(channels,), init=bias_initializer,
                    allow_deferred_init=True)
            else:
                self.bias = None
            if activation is not None:
                self.act = Activation(activation, prefix=activation + "_")
            else:
                self.act = None

    def hybrid_forward(self, F, x, weight, bias=None):
        op = getattr(F, self._op_name)
        if bias is None:
            act = op(x, weight, name="fwd", **self._kwargs)
        else:
            act = op(x, weight, bias, name="fwd", **self._kwargs)
        if self.act is not None:
            act = self.act(act)
        return act

    def _alias(self):
        return "conv"

    def __repr__(self):
        s = "{name}({mapping}, kernel_size={kernel}, stride={stride}"
        len_kernel_size = len(self._kwargs["kernel"])
        if self._kwargs["pad"] != (0,) * len_kernel_size:
            s += ", padding={pad}"
        if self._kwargs["dilate"] != (1,) * len_kernel_size:
            s += ", dilation={dilate}"
        if self._kwargs["num_group"] != 1:
            s += ", groups={num_group}"
        if self.bias is None:
            s += ", bias=False"
        s += ")"
        shape = self.weight.shape
        return s.format(name=self.__class__.__name__,
                        mapping="{0} -> {1}".format(
                            shape[1] if shape[1] else None, shape[0]),
                        **self._kwargs)


class Conv1D(_Conv):
    """1-D convolution (reference: nn/conv_layers.py:137)."""

    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 dilation=1, groups=1, layout="NCW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        kernel_size = _to_tuple(kernel_size, 1)
        assert layout == "NCW", "Only supports 'NCW' layout for now"
        super().__init__(
            channels, kernel_size, strides, padding, dilation, groups,
            layout, in_channels, activation, use_bias, weight_initializer,
            bias_initializer, **kwargs)


class Conv2D(_Conv):
    """2-D convolution (reference: nn/conv_layers.py:220)."""

    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 dilation=(1, 1), groups=1, layout="NCHW", activation=None,
                 use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        kernel_size = _to_tuple(kernel_size, 2)
        assert layout in ("NCHW", "NHWC"), \
            "Only supports 'NCHW' and 'NHWC' layout for now"
        super().__init__(
            channels, kernel_size, strides, padding, dilation, groups,
            layout, in_channels, activation, use_bias, weight_initializer,
            bias_initializer, **kwargs)


class Conv3D(_Conv):
    """3-D convolution (reference: nn/conv_layers.py:306)."""

    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), dilation=(1, 1, 1), groups=1,
                 layout="NCDHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        kernel_size = _to_tuple(kernel_size, 3)
        assert layout in ("NCDHW", "NDHWC"), \
            "Only supports 'NCDHW' and 'NDHWC' layout for now"
        super().__init__(
            channels, kernel_size, strides, padding, dilation, groups,
            layout, in_channels, activation, use_bias, weight_initializer,
            bias_initializer, **kwargs)


class Conv1DTranspose(_Conv):
    """1-D transposed convolution (reference: nn/conv_layers.py:394)."""

    def __init__(self, channels, kernel_size, strides=1, padding=0,
                 output_padding=0, dilation=1, groups=1, layout="NCW",
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        kernel_size = _to_tuple(kernel_size, 1)
        output_padding = _to_tuple(output_padding, 1)
        assert layout == "NCW", "Only supports 'NCW' layout for now"
        super().__init__(
            channels, kernel_size, strides, padding, dilation, groups,
            layout, in_channels, activation, use_bias, weight_initializer,
            bias_initializer, op_name="Deconvolution",
            adj=output_padding, **kwargs)
        self.outpad = output_padding


class Conv2DTranspose(_Conv):
    """2-D transposed convolution (reference: nn/conv_layers.py:482)."""

    def __init__(self, channels, kernel_size, strides=(1, 1), padding=(0, 0),
                 output_padding=(0, 0), dilation=(1, 1), groups=1,
                 layout="NCHW", activation=None, use_bias=True,
                 weight_initializer=None, bias_initializer="zeros",
                 in_channels=0, **kwargs):
        kernel_size = _to_tuple(kernel_size, 2)
        output_padding = _to_tuple(output_padding, 2)
        assert layout == "NCHW", \
            "Conv2DTranspose only supports 'NCHW' layout"
        super().__init__(
            channels, kernel_size, strides, padding, dilation, groups,
            layout, in_channels, activation, use_bias, weight_initializer,
            bias_initializer, op_name="Deconvolution",
            adj=output_padding, **kwargs)
        self.outpad = output_padding


class Conv3DTranspose(_Conv):
    """3-D transposed convolution (reference: nn/conv_layers.py:575)."""

    def __init__(self, channels, kernel_size, strides=(1, 1, 1),
                 padding=(0, 0, 0), output_padding=(0, 0, 0),
                 dilation=(1, 1, 1), groups=1, layout="NCDHW",
                 activation=None, use_bias=True, weight_initializer=None,
                 bias_initializer="zeros", in_channels=0, **kwargs):
        kernel_size = _to_tuple(kernel_size, 3)
        output_padding = _to_tuple(output_padding, 3)
        assert layout == "NCDHW", \
            "Conv3DTranspose only supports 'NCDHW' layout"
        super().__init__(
            channels, kernel_size, strides, padding, dilation, groups,
            layout, in_channels, activation, use_bias, weight_initializer,
            bias_initializer, op_name="Deconvolution",
            adj=output_padding, **kwargs)
        self.outpad = output_padding


class _Pooling(HybridBlock):
    """Base pooling layer (reference: nn/conv_layers.py:669)."""

    def __init__(self, pool_size, strides, padding, ceil_mode=False,
                 global_pool=False, pool_type="max", count_include_pad=None,
                 layout=None, **kwargs):
        super().__init__(**kwargs)
        if strides is None:
            strides = pool_size
        if isinstance(strides, int):
            strides = (strides,) * len(pool_size)
        if isinstance(padding, int):
            padding = (padding,) * len(pool_size)
        self._kwargs = {
            "kernel": pool_size, "stride": strides, "pad": padding,
            "global_pool": global_pool, "pool_type": pool_type,
            "pooling_convention": "full" if ceil_mode else "valid"}
        if layout is not None:
            self._kwargs["layout"] = layout
        if count_include_pad is not None:
            self._kwargs["count_include_pad"] = count_include_pad

    def _alias(self):
        return "pool"

    def hybrid_forward(self, F, x):
        return F.Pooling(x, name="fwd", **self._kwargs)

    def __repr__(self):
        s = "{name}(size={kernel}, stride={stride}, padding={pad}, " \
            "ceil_mode={ceil_mode})"
        return s.format(name=self.__class__.__name__,
                        ceil_mode=self._kwargs["pooling_convention"]
                        == "full", **self._kwargs)


class MaxPool1D(_Pooling):
    """Max pooling 1D (reference: nn/conv_layers.py:703)."""

    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False, **kwargs):
        assert layout == "NCW", "Only supports 'NCW' layout for now"
        super().__init__(_to_tuple(pool_size, 1), strides, padding,
                         ceil_mode, False, "max", layout=layout, **kwargs)


class MaxPool2D(_Pooling):
    """Max pooling 2D (reference: nn/conv_layers.py:746)."""

    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 layout="NCHW", ceil_mode=False, **kwargs):
        assert layout in ("NCHW", "NHWC"), \
            "Only supports 'NCHW' and 'NHWC' layout for now"
        super().__init__(_to_tuple(pool_size, 2), strides, padding,
                         ceil_mode, False, "max", layout=layout, **kwargs)


class MaxPool3D(_Pooling):
    """Max pooling 3D (reference: nn/conv_layers.py:793)."""

    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 ceil_mode=False, layout="NCDHW", **kwargs):
        assert layout in ("NCDHW", "NDHWC"), \
            "Only supports 'NCDHW' and 'NDHWC' layout for now"
        super().__init__(_to_tuple(pool_size, 3), strides, padding,
                         ceil_mode, False, "max", layout=layout, **kwargs)


class AvgPool1D(_Pooling):
    """Average pooling 1D (reference: nn/conv_layers.py:842)."""

    def __init__(self, pool_size=2, strides=None, padding=0, layout="NCW",
                 ceil_mode=False, count_include_pad=True, **kwargs):
        assert layout == "NCW", "Only supports 'NCW' layout for now"
        super().__init__(_to_tuple(pool_size, 1), strides, padding,
                         ceil_mode, False, "avg", count_include_pad,
                         layout=layout, **kwargs)


class AvgPool2D(_Pooling):
    """Average pooling 2D (reference: nn/conv_layers.py:887)."""

    def __init__(self, pool_size=(2, 2), strides=None, padding=0,
                 ceil_mode=False, layout="NCHW", count_include_pad=True,
                 **kwargs):
        assert layout in ("NCHW", "NHWC"), \
            "Only supports 'NCHW' and 'NHWC' layout for now"
        super().__init__(_to_tuple(pool_size, 2), strides, padding,
                         ceil_mode, False, "avg", count_include_pad,
                         layout=layout, **kwargs)


class AvgPool3D(_Pooling):
    """Average pooling 3D (reference: nn/conv_layers.py:937)."""

    def __init__(self, pool_size=(2, 2, 2), strides=None, padding=0,
                 ceil_mode=False, layout="NCDHW", count_include_pad=True,
                 **kwargs):
        assert layout in ("NCDHW", "NDHWC"), \
            "Only supports 'NCDHW' and 'NDHWC' layout for now"
        super().__init__(_to_tuple(pool_size, 3), strides, padding,
                         ceil_mode, False, "avg", count_include_pad,
                         layout=layout, **kwargs)


class GlobalMaxPool1D(_Pooling):
    """Global max pooling 1D (reference: nn/conv_layers.py:990)."""

    def __init__(self, layout="NCW", **kwargs):
        assert layout == "NCW", "Only supports 'NCW' layout for now"
        super().__init__((1,), None, 0, True, True, "max", layout=layout,
                         **kwargs)


class GlobalMaxPool2D(_Pooling):
    """Global max pooling 2D (reference: nn/conv_layers.py:1009)."""

    def __init__(self, layout="NCHW", **kwargs):
        assert layout in ("NCHW", "NHWC"), \
            "Only supports 'NCHW' and 'NHWC' layout for now"
        super().__init__((1, 1), None, 0, True, True, "max", layout=layout,
                         **kwargs)


class GlobalMaxPool3D(_Pooling):
    """Global max pooling 3D (reference: nn/conv_layers.py:1029)."""

    def __init__(self, layout="NCDHW", **kwargs):
        assert layout in ("NCDHW", "NDHWC"), \
            "Only supports 'NCDHW' and 'NDHWC' layout for now"
        super().__init__((1, 1, 1), None, 0, True, True, "max", layout=layout,
                         **kwargs)


class GlobalAvgPool1D(_Pooling):
    """Global average pooling 1D (reference: nn/conv_layers.py:1049)."""

    def __init__(self, layout="NCW", **kwargs):
        assert layout == "NCW", "Only supports 'NCW' layout for now"
        super().__init__((1,), None, 0, True, True, "avg", layout=layout,
                         **kwargs)


class GlobalAvgPool2D(_Pooling):
    """Global average pooling 2D (reference: nn/conv_layers.py:1065)."""

    def __init__(self, layout="NCHW", **kwargs):
        assert layout in ("NCHW", "NHWC"), \
            "Only supports 'NCHW' and 'NHWC' layout for now"
        super().__init__((1, 1), None, 0, True, True, "avg", layout=layout,
                         **kwargs)


class GlobalAvgPool3D(_Pooling):
    """Global average pooling 3D (reference: nn/conv_layers.py:1082)."""

    def __init__(self, layout="NCDHW", **kwargs):
        assert layout in ("NCDHW", "NDHWC"), \
            "Only supports 'NCDHW' and 'NDHWC' layout for now"
        super().__init__((1, 1, 1), None, 0, True, True, "avg", layout=layout,
                         **kwargs)


class ReflectionPad2D(HybridBlock):
    """Pads with reflection of the input boundary
    (reference: nn/conv_layers.py:1098)."""

    def __init__(self, padding=0, **kwargs):
        super().__init__(**kwargs)
        if isinstance(padding, int):
            padding = (0, 0, 0, 0, padding, padding, padding, padding)
        assert len(padding) == 8
        self._padding = padding

    def hybrid_forward(self, F, x):
        return F.pad(x, mode="reflect", pad_width=self._padding)
