"""Initializers + ParamAttr.

Reference parity: python/paddle/nn/initializer/ (Constant, Normal,
TruncatedNormal, Uniform, Xavier*, Kaiming*, Assign, Orthogonal, Dirac) and
paddle.ParamAttr (python/paddle/base/param_attr.py). Each initializer is a
callable (shape, dtype) -> jax.Array drawing from framework/random.py.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..framework import random as _random
from ..framework import dtype as _dtype_mod


#: from this many elements on, a normal draw runs as ONE fused program.
#: Eagerly, bits -> f32 normal -> scaled -> cast are separate dispatches
#: whose full-size f32 temporaries pile up beside the weights: building
#: the 16-layer Llama-3-8B-width serve model peaked at 15.2 of a v5e
#: chip's 15.75 GiB for 8.5 GiB of weights (chip run, PR 24).
_FUSED_DRAW_ELEMENTS = 1 << 24


@functools.partial(jax.jit, static_argnames=("shape", "dtype"))
def _fused_normal(key, mean, std, shape, dtype):
    return (mean + std * jax.random.normal(key, shape, dtype=jnp.float32)
            ).astype(dtype)


def _normal(key, mean, std, shape, dtype):
    """``mean + std * N(0, 1)`` of ``shape`` in ``dtype`` (f32 draw)."""
    if math.prod(shape) >= _FUSED_DRAW_ELEMENTS:
        return _fused_normal(key, mean, std, tuple(shape), jnp.dtype(dtype))
    return (mean + std * jax.random.normal(key, shape, dtype=jnp.float32)
            ).astype(dtype)


class Initializer:
    def __call__(self, shape, dtype):  # pragma: no cover - abstract
        raise NotImplementedError

    def _fan(self, shape):
        shape = tuple(shape)
        if len(shape) < 1:
            return 1, 1
        if len(shape) == 1:
            return shape[0], shape[0]
        receptive = int(np.prod(shape[2:])) if len(shape) > 2 else 1
        fan_in = shape[0] * receptive if len(shape) == 2 else shape[1] * receptive
        fan_out = shape[1] * receptive if len(shape) == 2 else shape[0] * receptive
        return fan_in, fan_out


class Constant(Initializer):
    def __init__(self, value=0.0):
        self.value = value

    def __call__(self, shape, dtype):
        return jnp.full(shape, self.value, dtype=dtype)


class Normal(Initializer):
    def __init__(self, mean=0.0, std=1.0):
        self.mean, self.std = mean, std

    def __call__(self, shape, dtype):
        return _normal(_random.next_key(), self.mean, self.std, shape, dtype)


class TruncatedNormal(Initializer):
    def __init__(self, mean=0.0, std=1.0, a=-2.0, b=2.0):
        self.mean, self.std, self.a, self.b = mean, std, a, b

    def __call__(self, shape, dtype):
        k = _random.next_key()
        z = jax.random.truncated_normal(k, self.a, self.b, shape, dtype=jnp.float32)
        return (self.mean + self.std * z).astype(dtype)


class Uniform(Initializer):
    def __init__(self, low=-1.0, high=1.0):
        self.low, self.high = low, high

    def __call__(self, shape, dtype):
        k = _random.next_key()
        return jax.random.uniform(k, shape, dtype=jnp.float32, minval=self.low, maxval=self.high).astype(dtype)


class XavierNormal(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = self._fan(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        std = self.gain * math.sqrt(2.0 / (fi + fo))
        return _normal(_random.next_key(), 0.0, std, shape, dtype)


class XavierUniform(Initializer):
    def __init__(self, fan_in=None, fan_out=None, gain=1.0):
        self.fan_in, self.fan_out, self.gain = fan_in, fan_out, gain

    def __call__(self, shape, dtype):
        fi, fo = self._fan(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        fo = self.fan_out if self.fan_out is not None else fo
        limit = self.gain * math.sqrt(6.0 / (fi + fo))
        k = _random.next_key()
        return jax.random.uniform(k, shape, dtype=jnp.float32, minval=-limit, maxval=limit).astype(dtype)


class KaimingNormal(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.negative_slope, self.nonlinearity = fan_in, negative_slope, nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = self._fan(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope**2)) if self.nonlinearity in ("relu", "leaky_relu") else 1.0
        std = gain / math.sqrt(fi)
        return _normal(_random.next_key(), 0.0, std, shape, dtype)


class KaimingUniform(Initializer):
    def __init__(self, fan_in=None, negative_slope=0.0, nonlinearity="relu"):
        self.fan_in, self.negative_slope, self.nonlinearity = fan_in, negative_slope, nonlinearity

    def __call__(self, shape, dtype):
        fi, _ = self._fan(shape)
        fi = self.fan_in if self.fan_in is not None else fi
        gain = math.sqrt(2.0 / (1 + self.negative_slope**2)) if self.nonlinearity in ("relu", "leaky_relu") else 1.0
        limit = gain * math.sqrt(3.0 / fi)
        k = _random.next_key()
        return jax.random.uniform(k, shape, dtype=jnp.float32, minval=-limit, maxval=limit).astype(dtype)


class Assign(Initializer):
    def __init__(self, value):
        self.value = value

    def __call__(self, shape, dtype):
        arr = jnp.asarray(np.asarray(self.value if not hasattr(self.value, "_array") else self.value._array))
        return arr.reshape(shape).astype(dtype)


class Orthogonal(Initializer):
    def __init__(self, gain=1.0):
        self.gain = gain

    def __call__(self, shape, dtype):
        k = _random.next_key()
        return (self.gain * jax.nn.initializers.orthogonal()(k, shape, jnp.float32)).astype(dtype)


class Dirac(Initializer):
    def __init__(self, groups=1):
        self.groups = groups

    def __call__(self, shape, dtype):
        arr = np.zeros(shape, dtype=np.float32)
        oc, ic = shape[0], shape[1]
        mid = tuple(s // 2 for s in shape[2:])
        per = oc // self.groups
        for g in range(self.groups):
            for i in range(min(per, ic)):
                arr[(g * per + i, i) + mid] = 1.0
        return jnp.asarray(arr).astype(dtype)


def calculate_gain(nonlinearity, param=None):
    gains = {"sigmoid": 1.0, "linear": 1.0, "conv1d": 1.0, "conv2d": 1.0, "conv3d": 1.0,
             "tanh": 5.0 / 3, "relu": math.sqrt(2.0),
             "leaky_relu": math.sqrt(2.0 / (1 + (param if param is not None else 0.01) ** 2)),
             "selu": 3.0 / 4}
    return gains[nonlinearity]


class ParamAttr:
    """paddle.ParamAttr parity (initializer/trainable/learning_rate/name)."""

    def __init__(self, name=None, initializer=None, learning_rate=1.0,
                 regularizer=None, trainable=True, do_model_average=True,
                 need_clip=True):
        self.name = name
        self.initializer = initializer
        self.learning_rate = learning_rate
        self.regularizer = regularizer
        self.trainable = trainable
        self.need_clip = need_clip

    @staticmethod
    def _to_attr(attr):
        if attr is None or attr is True:
            return ParamAttr()
        if attr is False:
            return None
        if isinstance(attr, ParamAttr):
            return attr
        if isinstance(attr, str):
            return ParamAttr(name=attr)
        if isinstance(attr, Initializer):
            return ParamAttr(initializer=attr)
        raise TypeError(f"cannot convert {attr!r} to ParamAttr")


def _resolve_initializer(attr, default_initializer, is_bias):
    if attr is not None and attr.initializer is not None:
        return attr.initializer
    # set_global_initializer overrides built-in layer defaults (reference
    # semantics: only an explicit ParamAttr initializer beats the global)
    try:
        from .initializer import _global_initializer

        g = _global_initializer(is_bias)
        if g is not None:
            return g
    except ImportError:  # pragma: no cover - during partial package init
        pass
    if default_initializer is not None:
        return default_initializer
    return Constant(0.0) if is_bias else XavierNormal()
