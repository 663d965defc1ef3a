"""Common functionals: linear/embedding/dropout/normalization/padding/etc.

Reference parity: python/paddle/nn/functional/{common,input,norm}.py.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ...ops.registry import apply
from ...framework import random as _random
from ...framework.dtype import convert_dtype
from ...tensor_class import unwrap


def linear(x, weight, bias=None, name=None):
    """y = x @ W + b. Weight layout [in, out] (reference
    python/paddle/nn/functional/common.py::linear)."""
    if bias is None:
        return apply("linear", lambda a, w: a @ w, x, weight)
    return apply("linear", lambda a, w, b: a @ w + b, x, weight, bias)


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    # eager-mode bounds check (the reference's CPU/GPU lookup kernels
    # enforce this): jnp.take's out-of-range NaN fill would otherwise
    # poison the model silently. Concrete ids only; traced ids rely on
    # the model feeding valid data (XLA clamps).
    ids_arr = x._array if hasattr(x, "_array") else x
    try:
        vocab = (weight._array if hasattr(weight, "_array") else weight).shape[0]
        lo = int(jnp.min(ids_arr))
        hi = int(jnp.max(ids_arr))
        if lo < 0 or hi >= vocab:
            raise ValueError(
                f"embedding ids out of range: [{lo}, {hi}] vs vocab {vocab}")
    except jax.errors.TracerIntegerConversionError:
        pass
    except jax.errors.ConcretizationTypeError:  # pragma: no cover
        pass

    def fn(ids, w):
        out = jnp.take(w, ids.astype(jnp.int32), axis=0)
        if padding_idx is not None:
            mask = (ids == padding_idx)[..., None]
            out = jnp.where(mask, jnp.zeros_like(out), out)
        return out

    return apply("embedding", fn, x, weight)


def one_hot(x, num_classes, name=None):
    return apply(
        "one_hot",
        lambda a: jax.nn.one_hot(a.astype(jnp.int32), num_classes, dtype=jnp.float32),
        x,
        differentiable=False,
    )


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train", name=None):
    """Reference python/paddle/nn/functional/common.py::dropout semantics:
    upscale_in_train (inverted dropout, default) or downscale_in_infer."""
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return apply("dropout", lambda a: a * (1 - p), x)
        return x
    if p == 1.0:
        return apply("dropout", lambda a: jnp.zeros_like(a), x)
    key = _random.next_key()

    def fn(a):
        shape = list(a.shape)
        if axis is not None:
            axes = axis if isinstance(axis, (list, tuple)) else [axis]
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), jnp.zeros_like(a))
        return jnp.where(keep, a, jnp.zeros_like(a))

    return apply("dropout", fn, x)


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    if not training or p == 0.0:
        return x
    key = _random.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def fn(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        a_coef = (1.0 - p + p * alpha_p**2 * (1.0 - p)) ** -0.5
        b_coef = -a_coef * p * alpha_p
        return a_coef * jnp.where(keep, a, alpha_p) + b_coef

    return apply("alpha_dropout", fn, x)


def normalize(x, p=2, axis=1, epsilon=1e-12, name=None):
    def fn(a):
        nrm = jnp.power(jnp.sum(jnp.power(jnp.abs(a), p), axis=axis, keepdims=True), 1.0 / p)
        return a / jnp.maximum(nrm, epsilon)

    return apply("normalize", fn, x)


def layer_norm(x, normalized_shape, weight=None, bias=None, epsilon=1e-5, name=None):
    if isinstance(normalized_shape, int):
        normalized_shape = [normalized_shape]
    ndims = len(normalized_shape)

    def fn(a, *wb):
        axes = tuple(range(a.ndim - ndims, a.ndim))
        mean = jnp.mean(a.astype(jnp.float32), axis=axes, keepdims=True)
        var = jnp.var(a.astype(jnp.float32), axis=axes, keepdims=True)
        out = (a.astype(jnp.float32) - mean) * jax.lax.rsqrt(var + epsilon)
        out = out.astype(a.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i]
            i += 1
        if bias is not None:
            out = out + wb[i]
        return out

    args = [t for t in (weight, bias) if t is not None]
    return apply("layer_norm", fn, x, *args)


def rms_norm(x, weight=None, epsilon=1e-6, name=None):
    """RMSNorm — reference fused kernel paddle/phi/kernels/fusion/gpu/rms_norm*;
    here a pure-XLA version (the Pallas fused variant lives in ops/pallas)."""

    def fn(a, *w):
        a32 = a.astype(jnp.float32)
        out = a32 * jax.lax.rsqrt(jnp.mean(jnp.square(a32), axis=-1, keepdims=True) + epsilon)
        out = out.astype(a.dtype)
        if w:
            out = out * w[0]
        return out

    args = [weight] if weight is not None else []
    return apply("rms_norm", fn, x, *args)


def batch_norm(
    x, running_mean, running_var, weight=None, bias=None, training=False,
    momentum=0.9, epsilon=1e-5, data_format="NCHW", use_global_stats=None, name=None,
):
    ch_axis = 1 if data_format[1] == "C" else -1

    if training and not use_global_stats:
        # Batch stats are computed INSIDE the recorded op so the full BN VJP
        # (including d mean/d x and d var/d x) flows through the tape.
        def fn(a, *wb):
            axes = tuple(i for i in range(a.ndim) if i != ch_axis % a.ndim)
            a32 = a.astype(jnp.float32)
            mean = jnp.mean(a32, axis=axes, keepdims=True)
            var = jnp.var(a32, axis=axes, keepdims=True)
            out = (a32 - mean) * jax.lax.rsqrt(var + epsilon)
            out = out.astype(a.dtype)
            shape = [1] * a.ndim
            shape[ch_axis] = a.shape[ch_axis]
            i = 0
            if weight is not None:
                out = out * wb[i].reshape(shape)
                i += 1
            if bias is not None:
                out = out + wb[i].reshape(shape)
            return out

        args = [t for t in (weight, bias) if t is not None]
        out = apply("batch_norm", fn, x, *args)

        # Running-stat update. A TRACED batch may only update a buffer that
        # is itself traced — functional state (pipeline stages, TrainStep)
        # swaps tracers in and threads the new values out. Writing a tracer
        # into a CONCRETE buffer (a plain jax.jit closing over the layer)
        # would leak it into every later eager call, so that case skips.
        if running_mean is not None:
            arr = unwrap(x)
            if (not isinstance(arr, jax.core.Tracer)
                    or isinstance(running_mean._array, jax.core.Tracer)):
                axes = tuple(i for i in range(arr.ndim) if i != ch_axis % arr.ndim)
                batch_mean = jnp.mean(arr.astype(jnp.float32), axis=axes)
                batch_var = jnp.var(arr.astype(jnp.float32), axis=axes)
                running_mean._array = (momentum * running_mean._array + (1 - momentum) * batch_mean).astype(running_mean.dtype)
                running_var._array = (momentum * running_var._array + (1 - momentum) * batch_var).astype(running_var.dtype)
        return out

    def fn(a, m, v, *wb):
        shape = [1] * a.ndim
        shape[ch_axis] = a.shape[ch_axis]
        out = (a.astype(jnp.float32) - m.reshape(shape).astype(jnp.float32)) * jax.lax.rsqrt(
            v.reshape(shape).astype(jnp.float32) + epsilon
        )
        out = out.astype(a.dtype)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [t for t in (weight, bias) if t is not None]
    return apply("batch_norm", fn, x, running_mean, running_var, *args)


def group_norm(x, num_groups, weight=None, bias=None, epsilon=1e-5, data_format="NCHW", name=None):
    def fn(a, *wb):
        if data_format[1] != "C":
            a_t = jnp.moveaxis(a, -1, 1)
        else:
            a_t = a
        n, c = a_t.shape[0], a_t.shape[1]
        spatial = a_t.shape[2:]
        g = a_t.reshape(n, num_groups, c // num_groups, *spatial).astype(jnp.float32)
        axes = tuple(range(2, g.ndim))
        mean = jnp.mean(g, axis=axes, keepdims=True)
        var = jnp.var(g, axis=axes, keepdims=True)
        out = ((g - mean) * jax.lax.rsqrt(var + epsilon)).reshape(a_t.shape).astype(a.dtype)
        shape = [1, c] + [1] * len(spatial)
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        if data_format[1] != "C":
            out = jnp.moveaxis(out, 1, -1)
        return out

    args = [t for t in (weight, bias) if t is not None]
    return apply("group_norm", fn, x, *args)


def instance_norm(x, running_mean=None, running_var=None, weight=None, bias=None,
                  use_input_stats=True, momentum=0.9, eps=1e-5, data_format="NCHW", name=None):
    def fn(a, *wb):
        ch_axis = 1 if data_format[1] == "C" else a.ndim - 1
        axes = tuple(i for i in range(a.ndim) if i not in (0, ch_axis))
        a32 = a.astype(jnp.float32)
        mean = jnp.mean(a32, axis=axes, keepdims=True)
        var = jnp.var(a32, axis=axes, keepdims=True)
        out = ((a32 - mean) * jax.lax.rsqrt(var + eps)).astype(a.dtype)
        shape = [1] * a.ndim
        shape[ch_axis] = a.shape[ch_axis]
        i = 0
        if weight is not None:
            out = out * wb[i].reshape(shape)
            i += 1
        if bias is not None:
            out = out + wb[i].reshape(shape)
        return out

    args = [t for t in (weight, bias) if t is not None]
    return apply("instance_norm", fn, x, *args)


def local_response_norm(x, size, alpha=1e-4, beta=0.75, k=1.0, data_format="NCHW", name=None):
    def fn(a):
        ch_axis = 1 if data_format[1] == "C" else a.ndim - 1
        sq = jnp.square(a)
        half = size // 2
        c = a.shape[ch_axis]
        acc = jnp.zeros_like(sq)
        for offset in range(-half, half + (size % 2)):
            shifted = jnp.roll(sq, offset, axis=ch_axis)
            idx = jnp.arange(c)
            valid = (idx - offset >= 0) & (idx - offset < c)
            shape = [1] * a.ndim
            shape[ch_axis] = c
            acc = acc + jnp.where(valid.reshape(shape), shifted, 0.0)
        return a / jnp.power(k + alpha * acc, beta)

    return apply("local_response_norm", fn, x)


def label_smooth(label, prior_dist=None, epsilon=0.1, name=None):
    def fn(l, *pd):
        k = l.shape[-1]
        if pd:
            return (1 - epsilon) * l + epsilon * pd[0]
        return (1 - epsilon) * l + epsilon / k

    args = [prior_dist] if prior_dist is not None else []
    return apply("label_smooth", fn, label, *args)


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    def fn(a, b):
        dot = jnp.sum(a * b, axis=axis)
        n1 = jnp.sqrt(jnp.sum(a * a, axis=axis))
        n2 = jnp.sqrt(jnp.sum(b * b, axis=axis))
        return dot / jnp.maximum(n1 * n2, eps)

    return apply("cosine_similarity", fn, x1, x2)


def pixel_shuffle(x, upscale_factor, data_format="NCHW", name=None):
    r = upscale_factor

    def fn(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            out = a.reshape(n, c // (r * r), r, r, h, w)
            out = out.transpose(0, 1, 4, 2, 5, 3)
            return out.reshape(n, c // (r * r), h * r, w * r)
        n, h, w, c = a.shape
        out = a.reshape(n, h, w, r, r, c // (r * r))
        out = out.transpose(0, 1, 3, 2, 4, 5)
        return out.reshape(n, h * r, w * r, c // (r * r))

    return apply("pixel_shuffle", fn, x)


def pixel_unshuffle(x, downscale_factor, data_format="NCHW", name=None):
    r = downscale_factor

    def fn(a):
        if data_format == "NCHW":
            n, c, h, w = a.shape
            out = a.reshape(n, c, h // r, r, w // r, r)
            out = out.transpose(0, 1, 3, 5, 2, 4)
            return out.reshape(n, c * r * r, h // r, w // r)
        n, h, w, c = a.shape
        out = a.reshape(n, h // r, r, w // r, r, c)
        out = out.transpose(0, 2, 4, 1, 3, 5).reshape(n, h // r, w // r, c * r * r)
        return out

    return apply("pixel_unshuffle", fn, x)


def unfold(x, kernel_sizes, strides=1, paddings=0, dilations=1, name=None):
    """im2col (NCHW). Reference phi unfold kernel."""

    def _pair(v):
        return (v, v) if isinstance(v, int) else tuple(v)

    kh, kw = _pair(kernel_sizes)
    sh, sw = _pair(strides)
    ph, pw = _pair(paddings) if not (isinstance(paddings, (list, tuple)) and len(paddings) == 4) else (None, None)
    dh, dw = _pair(dilations)

    def fn(a):
        n, c, h, w = a.shape
        if ph is not None:
            ap = jnp.pad(a, ((0, 0), (0, 0), (ph, ph), (pw, pw)))
        else:
            p = paddings
            ap = jnp.pad(a, ((0, 0), (0, 0), (p[0], p[1]), (p[2], p[3])))
        hp, wp = ap.shape[2], ap.shape[3]
        out_h = (hp - (dh * (kh - 1) + 1)) // sh + 1
        out_w = (wp - (dw * (kw - 1) + 1)) // sw + 1
        patches = []
        for i in range(kh):
            for j in range(kw):
                sl = ap[:, :, i * dh : i * dh + out_h * sh : sh, j * dw : j * dw + out_w * sw : sw]
                patches.append(sl)
        out = jnp.stack(patches, axis=2)  # n, c, kh*kw, oh, ow
        return out.reshape(n, c * kh * kw, out_h * out_w)

    return apply("unfold", fn, x)


def interpolate(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
                align_mode=0, data_format="NCHW", name=None):
    def fn(a):
        channel_last = data_format[-1] == "C"
        spatial_ndim = a.ndim - 2
        if channel_last:
            spatial = a.shape[1:-1]
        else:
            spatial = a.shape[2:]
        if size is not None:
            tgt = [int(unwrap(s)) for s in (size if isinstance(size, (list, tuple)) else [size])]
        else:
            sf = scale_factor if isinstance(scale_factor, (list, tuple)) else [scale_factor] * spatial_ndim
            tgt = [int(s * f) for s, f in zip(spatial, sf)]
        jax_mode = {"nearest": "nearest", "bilinear": "linear", "trilinear": "linear",
                    "linear": "linear", "bicubic": "cubic", "area": "linear"}[mode]
        if channel_last:
            new_shape = (a.shape[0], *tgt, a.shape[-1])
        else:
            new_shape = (a.shape[0], a.shape[1], *tgt)
        return jax.image.resize(a, new_shape, method=jax_mode)

    return apply("interpolate", fn, x)


def upsample(x, size=None, scale_factor=None, mode="nearest", align_corners=False,
             align_mode=0, data_format="NCHW", name=None):
    return interpolate(x, size, scale_factor, mode, align_corners, align_mode, data_format)


def sequence_mask(lengths, maxlen=None, dtype="int64", name=None):
    def fn(l):
        m = maxlen or int(jnp.max(l))
        return (jnp.arange(m)[None, :] < l[..., None]).astype(convert_dtype(dtype))

    return apply("sequence_mask", fn, lengths, differentiable=False)


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    def fn(a):
        if data_format == "NHWC":
            a = jnp.moveaxis(a, -1, 1)
        nt, c, h, w = a.shape
        n = nt // seg_num
        v = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([v[:, 1:, :fold], jnp.zeros_like(v[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(v[:, :1, fold : 2 * fold]), v[:, :-1, fold : 2 * fold]], axis=1)
        rest = v[:, :, 2 * fold :]
        out = jnp.concatenate([left, right, rest], axis=2).reshape(nt, c, h, w)
        if data_format == "NHWC":
            out = jnp.moveaxis(out, 1, -1)
        return out

    return apply("temporal_shift", fn, x)
