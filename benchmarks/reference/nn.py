"""Plain layers for the reference networks: float32, no kernels, no batching tricks.

One traversal of an architecture serves three readers, so that they cannot drift
apart: it lists the parameters with their shapes and distributions (``Net()``,
under ``jax.eval_shape``), applies given ones (``Net(params=...)``), and counts the
multiply-adds of a row as it goes (``net.flops``). Parameter names follow the order of construction
(``InceptionBlockA_0/ConvBN_1/Conv_0/kernel``); ``benchmarks/program.py`` maps them
onto the program's own tree.

``quant="int8"`` turns the same traversal into the control: every convolution and
the dense layer see their input (per tensor) and their kernel (per output channel)
rounded to 255 levels, the nearest precision below the bfloat16 the configurations
state and the one a v5e (393 TOP/s in int8) would tempt a later PR with.
"""

from __future__ import annotations

import contextlib
import math

import jax
import jax.numpy as jnp
from jax import lax

_HIGHEST = lax.Precision.HIGHEST


def _fake_int8(x, axes):
    """Round ``x`` to 255 levels spanning its largest magnitude over ``axes``."""
    amax = jnp.max(jnp.abs(x), axis=axes, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    return jnp.clip(jnp.round(x / scale), -127, 127) * scale


class Net:
    def __init__(self, params=None, quant=None):
        if quant not in (None, "int8"):
            raise ValueError(f"unknown quant {quant!r}")
        self.params = params
        # without params: {path: (shape, "normal", std) or (shape, "uniform", lo, hi)}
        self.specs: dict[str, tuple] = {}
        self.quant = quant
        self.flops = 0  # multiply-adds counted as 2, per row
        self._scope: list[str] = []
        self._counts: list[dict] = [{}]

    @contextlib.contextmanager
    def scope(self, kind: str):
        counts = self._counts[-1]
        index = counts.get(kind, 0)
        counts[kind] = index + 1
        self._scope.append(f"{kind}_{index}")
        self._counts.append({})
        try:
            yield
        finally:
            self._scope.pop()
            self._counts.pop()

    def param(self, name, shape, *dist):
        path = "/".join(self._scope + [name])
        if self.params is None:
            if path in self.specs:
                raise ValueError(f"parameter {path} listed twice")
            self.specs[path] = (tuple(shape),) + dist
            return jnp.zeros(shape, jnp.float32)
        value = self.params[path]
        if tuple(value.shape) != tuple(shape):
            raise ValueError(f"{path}: shape {value.shape}, expected {tuple(shape)}")
        return value

    # -- layers -------------------------------------------------------------

    def conv(self, x, features, kernel, strides, padding, use_bias, gain):
        kh, kw = kernel
        cin = x.shape[-1]
        std = gain * math.sqrt(2.0 / (kh * kw * cin))
        with self.scope("Conv"):
            w = self.param("kernel", (kh, kw, cin, features), "normal", std)
            b = self.param("bias", (features,), "normal", 0.05) if use_bias else None
        if self.quant == "int8":
            x = _fake_int8(x, tuple(range(x.ndim)))
            w = _fake_int8(w, (0, 1, 2))
        y = lax.conv_general_dilated(
            x, w, window_strides=strides, padding=padding,
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=_HIGHEST)
        self.flops += 2 * y.shape[1] * y.shape[2] * kh * kw * cin * features
        return y if b is None else y + b

    def batch_norm(self, x, epsilon, gain):
        c = x.shape[-1]
        with self.scope("BatchNorm"):
            scale = self.param("scale", (c,), "uniform", 0.8 * gain, 1.2 * gain)
            bias = self.param("bias", (c,), "normal", 0.1)
            mean = self.param("mean", (c,), "normal", 0.1)
            var = self.param("var", (c,), "uniform", 0.8, 1.2)
        return (x - mean) * (scale * lax.rsqrt(var + epsilon)) + bias

    def conv_bn(self, x, features, kernel=(3, 3), strides=(1, 1), padding="SAME",
                relu=True, epsilon=1e-3, use_bias=False, gain=1.0):
        """Convolution, batch norm on running statistics, optional ReLU.
        ``gain`` scales the batch norm's scale at initialisation only."""
        with self.scope("ConvBN"):
            x = self.conv(x, features, kernel, strides, padding, use_bias, 1.0)
            x = self.batch_norm(x, epsilon, gain)
        return jax.nn.relu(x) if relu else x

    def dense(self, x, features, gain=1.0):
        cin = x.shape[-1]
        with self.scope("Dense"):
            w = self.param("kernel", (cin, features), "normal", gain / math.sqrt(cin))
            b = self.param("bias", (features,), "normal", 0.1)
        if self.quant == "int8":
            x = _fake_int8(x, tuple(range(x.ndim)))
            w = _fake_int8(w, (0,))
        self.flops += 2 * cin * features
        return jnp.dot(x, w, precision=_HIGHEST) + b


def max_pool(x, window=(3, 3), strides=(2, 2), padding="VALID"):
    if not isinstance(padding, str):
        padding = ((0, 0),) + tuple(padding) + ((0, 0),)
    return lax.reduce_window(x, -jnp.inf, lax.max, (1,) + window + (1,),
                             (1,) + strides + (1,), padding)


def avg_pool_same(x, window=(3, 3)):
    """Stride-1 average over a same-padded window, divided by the number of
    elements that lie inside the image (Keras / TensorFlow semantics)."""
    dims, ones = (1,) + window + (1,), (1, 1, 1, 1)
    total = lax.reduce_window(x, 0.0, lax.add, dims, ones, "SAME")
    count = lax.reduce_window(jnp.ones((1,) + x.shape[1:3] + (1,), x.dtype), 0.0,
                              lax.add, dims, ones, "SAME")
    return total / count


def global_avg_pool(x):
    return jnp.mean(x, axis=(1, 2))
