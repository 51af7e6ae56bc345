"""Convolution family — XLA conv lowering replaces the reference's two paths
(im2col+gemm: nn/layers/convolution/ConvolutionLayer.java:197-213, and the
cuDNN helper: deeplearning4j-cuda CudnnConvolutionHelper.java:54).

Native layout NHWC / kernels HWIO (TPU-preferred); the reference is NCHW /
[out,in,kh,kw].  ConvolutionMode parity (nn/conf/ConvolutionMode.java):
``same`` → SAME, ``truncate`` → VALID (floor), ``strict`` → VALID but
init-time error when sizes don't divide cleanly.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from ...ops.initializers import init_weight
from ..conf.inputs import InputType
from .base import ForwardOut, Layer, register_layer

Array = jax.Array


def _pair(v) -> Tuple[int, int]:
    if isinstance(v, (tuple, list)):
        return (int(v[0]), int(v[1]))
    return (int(v), int(v))


def _conv_out_size(size: int, k: int, s: int, mode: str, dilation: int = 1) -> int:
    eff_k = (k - 1) * dilation + 1
    if mode == "same":
        return -(-size // s)
    out = (size - eff_k) // s + 1
    if mode == "strict" and (size - eff_k) % s != 0:
        raise ValueError(
            f"ConvolutionMode.Strict: size {size} kernel {k} stride {s} leaves remainder "
            f"(reference ConvolutionMode semantics)")
    return out


def _padding(mode: str) -> str:
    return "SAME" if mode == "same" else "VALID"


@register_layer
@dataclasses.dataclass
class Convolution2D(Layer):
    """2-D convolution (reference ConvolutionLayer conf).  Kernel HWIO."""

    wants = "cnn"

    n_in: int = 0   # input channels
    n_out: int = 0  # output channels
    kernel: Tuple[int, int] = (3, 3)
    stride: Tuple[int, int] = (1, 1)
    dilation: Tuple[int, int] = (1, 1)
    convolution_mode: str = "truncate"
    has_bias: bool = True

    def __post_init__(self):
        self.kernel = _pair(self.kernel)
        self.stride = _pair(self.stride)
        self.dilation = _pair(self.dilation)

    def infer_nin(self, in_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = in_type.channels

    def output_type(self, in_type: InputType) -> InputType:
        h = _conv_out_size(in_type.height, self.kernel[0], self.stride[0], self.convolution_mode, self.dilation[0])
        w = _conv_out_size(in_type.width, self.kernel[1], self.stride[1], self.convolution_mode, self.dilation[1])
        return InputType.convolutional(h, w, self.n_out)

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        kh, kw = self.kernel
        fan_in = self.n_in * kh * kw
        fan_out = self.n_out * kh * kw
        p = {"W": init_weight(rng, (kh, kw, self.n_in, self.n_out), self._winit(), fan_in, fan_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def _conv(self, x, w):
        return lax.conv_general_dilated(
            x, w,
            window_strides=self.stride,
            padding=_padding(self.convolution_mode),
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        y = self._conv(x, params["W"].astype(x.dtype))
        # NOTE: no checkpoint_name remat tag here — measured: the name
        # primitive blocks conv-epilogue fusion (~20% on LeNet) even with
        # no checkpoint policy active, and the save-only-conv-outputs
        # policy itself lost to XLA's default.
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return ForwardOut(self._act(y), state, mask)


@register_layer
@dataclasses.dataclass
class Convolution1D(Layer):
    """1-D (temporal) convolution over [mb, t, f] (reference Convolution1DLayer)."""

    n_in: int = 0
    n_out: int = 0
    kernel: int = 3
    stride: int = 1
    dilation: int = 1
    convolution_mode: str = "same"
    has_bias: bool = True

    def infer_nin(self, in_type: InputType) -> None:
        if self.n_in == 0:
            self.n_in = in_type.size

    def output_type(self, in_type: InputType) -> InputType:
        t = in_type.timesteps
        if t is not None:
            t = _conv_out_size(t, self.kernel, self.stride, self.convolution_mode, self.dilation)
        return InputType.recurrent(self.n_out, t)

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        fan_in = self.n_in * self.kernel
        fan_out = self.n_out * self.kernel
        p = {"W": init_weight(rng, (self.kernel, self.n_in, self.n_out), self._winit(), fan_in, fan_out, dtype)}
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        y = lax.conv_general_dilated(
            x, params["W"].astype(x.dtype),
            window_strides=(self.stride,),
            padding=_padding(self.convolution_mode),
            rhs_dilation=(self.dilation,),
            dimension_numbers=("NWC", "WIO", "NWC"),
        )
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return ForwardOut(self._act(y), state, mask)


@register_layer
@dataclasses.dataclass
class Deconvolution2D(Convolution2D):
    """Transposed convolution (reference Deconvolution2D conf)."""

    def output_type(self, in_type: InputType) -> InputType:
        if self.convolution_mode == "same":
            h = in_type.height * self.stride[0]
            w = in_type.width * self.stride[1]
        else:
            h = (in_type.height - 1) * self.stride[0] + (self.kernel[0] - 1) * self.dilation[0] + 1
            w = (in_type.width - 1) * self.stride[1] + (self.kernel[1] - 1) * self.dilation[1] + 1
        return InputType.convolutional(h, w, self.n_out)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        y = lax.conv_transpose(
            x, params["W"].astype(x.dtype),
            strides=self.stride,
            padding=_padding(self.convolution_mode),
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return ForwardOut(self._act(y), state, mask)


@register_layer
@dataclasses.dataclass
class SeparableConvolution2D(Convolution2D):
    """Depthwise + pointwise conv (reference SeparableConvolution2D:
    depthWiseWeights [depthMult,in,kh,kw] + pointWiseWeights).  Here
    depthwise kernel is HWI(M) via feature_group_count=n_in."""

    depth_multiplier: int = 1

    def init_params(self, rng, in_type, dtype=jnp.float32) -> Dict[str, Array]:
        kh, kw = self.kernel
        k1, k2 = jax.random.split(rng)
        dm = self.depth_multiplier
        fan_in_d = kh * kw
        p = {
            "dW": init_weight(k1, (kh, kw, 1, self.n_in * dm), self._winit(), fan_in_d, fan_in_d * dm, dtype),
            "pW": init_weight(k2, (1, 1, self.n_in * dm, self.n_out), self._winit(), self.n_in * dm, self.n_out, dtype),
        }
        if self.has_bias:
            p["b"] = jnp.full((self.n_out,), self.bias_init, dtype)
        return p

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        x = self._maybe_dropout(x, train, rng)
        y = lax.conv_general_dilated(
            x, params["dW"].astype(x.dtype),
            window_strides=self.stride,
            padding=_padding(self.convolution_mode),
            rhs_dilation=self.dilation,
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            feature_group_count=self.n_in,
        )
        y = lax.conv_general_dilated(
            y, params["pW"].astype(x.dtype),
            window_strides=(1, 1),
            padding="VALID",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
        )
        if self.has_bias:
            y = y + params["b"].astype(x.dtype)
        return ForwardOut(self._act(y), state, mask)


@register_layer
@dataclasses.dataclass
class ZeroPadding2D(Layer):
    """Spatial zero padding (reference ZeroPaddingLayer).  padding =
    (top, bottom, left, right)."""

    padding: Tuple[int, int, int, int] = (1, 1, 1, 1)

    def has_params(self) -> bool:
        return False

    def output_type(self, in_type: InputType) -> InputType:
        t, b, l, r = self.padding
        return InputType.convolutional(in_type.height + t + b, in_type.width + l + r, in_type.channels)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        t, b, l, r = self.padding
        y = jnp.pad(x, ((0, 0), (t, b), (l, r), (0, 0)))
        return ForwardOut(y, state, mask)


@register_layer
@dataclasses.dataclass
class ZeroPadding1D(Layer):
    """Temporal zero padding (reference ZeroPadding1DLayer)."""

    padding: Tuple[int, int] = (1, 1)

    def has_params(self) -> bool:
        return False

    def output_type(self, in_type: InputType) -> InputType:
        t = in_type.timesteps
        if t is not None:
            t = t + self.padding[0] + self.padding[1]
        return InputType.recurrent(in_type.size, t)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        y = jnp.pad(x, ((0, 0), (self.padding[0], self.padding[1]), (0, 0)))
        return ForwardOut(y, state, mask)


@register_layer
@dataclasses.dataclass
class Cropping2D(Layer):
    """Spatial cropping (top, bottom, left, right)."""

    cropping: Tuple[int, int, int, int] = (0, 0, 0, 0)

    def has_params(self) -> bool:
        return False

    def output_type(self, in_type: InputType) -> InputType:
        t, b, l, r = self.cropping
        return InputType.convolutional(in_type.height - t - b, in_type.width - l - r, in_type.channels)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        t, b, l, r = self.cropping
        h, w = x.shape[1], x.shape[2]
        return ForwardOut(x[:, t:h - b, l:w - r, :], state, mask)


@register_layer
@dataclasses.dataclass
class Upsampling2D(Layer):
    """Nearest-neighbor upsampling (reference Upsampling2D)."""

    size: Tuple[int, int] = (2, 2)

    def __post_init__(self):
        self.size = _pair(self.size)

    def has_params(self) -> bool:
        return False

    def output_type(self, in_type: InputType) -> InputType:
        return InputType.convolutional(in_type.height * self.size[0], in_type.width * self.size[1], in_type.channels)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        y = jnp.repeat(jnp.repeat(x, self.size[0], axis=1), self.size[1], axis=2)
        return ForwardOut(y, state, mask)


@register_layer
@dataclasses.dataclass
class Upsampling1D(Layer):
    size: int = 2

    def has_params(self) -> bool:
        return False

    def output_type(self, in_type: InputType) -> InputType:
        t = in_type.timesteps
        return InputType.recurrent(in_type.size, None if t is None else t * self.size)

    def forward(self, params, state, x, *, train=False, rng=None, mask=None) -> ForwardOut:
        return ForwardOut(jnp.repeat(x, self.size, axis=1), state, mask)
