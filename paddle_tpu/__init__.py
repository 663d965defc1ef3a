"""paddle_tpu — a TPU-native deep-learning framework with the capability
surface of the reference (GerHobbelt/Paddle, PaddlePaddle ~3.0-dev), built
from scratch on JAX/XLA/Pallas/pjit.

See /root/repo/SURVEY.md for the reference structural analysis and the
architecture mapping this package implements.
"""
from __future__ import annotations

# dtypes first (no jax-heavy imports)
from .framework.dtype import (
    bool_ as bool,  # noqa: A001 - paddle exports `paddle.bool`
    uint8,
    int8,
    int16,
    int32,
    int64,
    float16,
    bfloat16,
    float32,
    float64,
    complex64,
    complex128,
    float8_e4m3fn,
    float8_e5m2,
    set_default_dtype,
    get_default_dtype,
)

from .tensor_class import Tensor, Parameter, is_tensor
from .autograd import no_grad, enable_grad, set_grad_enabled, grad
from .autograd.pylayer import PyLayer, PyLayerContext
from .framework.random import seed, get_rng_state, set_rng_state
from . import device
from .framework.device import (
    set_device,
    get_device,
    is_compiled_with_cuda,
    is_compiled_with_tpu,
    CPUPlace,
    TPUPlace,
    CUDAPlace,
    CUDAPinnedPlace,
)

from . import ops
from .ops import registry as _registry

# ---- re-export the functional surface at top level (paddle.* parity) --------
from .ops.creation import (
    to_tensor, zeros, ones, full, empty, zeros_like, ones_like, full_like,
    empty_like, arange, linspace, logspace, eye, diag, diagflat, tril, triu,
    tril_indices, triu_indices, meshgrid, clone, assign, rand, randn, randint,
    randint_like, uniform, normal, standard_normal, randperm, bernoulli,
    poisson, multinomial, complex, polar, create_parameter, create_tensor,
)
from .ops.math import (
    abs, acos, acosh, asin, asinh, atan, atanh, ceil, cos, cosh, digamma, erf,
    erfinv, exp, expm1, floor, lgamma, log, log10, log1p, log2, neg,
    reciprocal, round, rsqrt, sigmoid, sign, sin, sinh, sqrt, square, tan,
    tanh, trunc, frac, angle, conj, real, imag, deg2rad, rad2deg, isnan,
    isinf, isfinite, logical_not, bitwise_not, add, subtract, multiply,
    divide, floor_divide, remainder, mod, floor_mod, pow, maximum, minimum,
    fmax, fmin, atan2, hypot, logaddexp, nextafter, copysign, heaviside, gcd,
    lcm, ldexp, bitwise_and, bitwise_or, bitwise_xor, bitwise_left_shift,
    bitwise_right_shift, i0, i1, divide_no_nan, scale,
    cast, clip, lerp, stanh, multiplex, addmm, inner, outer, logit,
    polygamma, nan_to_num, trapezoid, diff, sum, mean, prod, max, min, amax,
    amin, any, all, nansum, nanmean, median, nanmedian, std, var, logsumexp,
    logcumsumexp, cumsum, cumprod, cummax, cummin, count_nonzero, argmax,
    argmin, argsort, sort, topk, kthvalue, mode, equal, not_equal,
    greater_than, greater_equal, less_than, less_equal, logical_and,
    logical_or, logical_xor, allclose, isclose, equal_all, where,
    masked_fill, isneginf, isposinf, isreal,
)
from .ops.manipulation import (
    reshape, flatten, squeeze, unsqueeze, transpose, moveaxis, concat, stack,
    split, chunk, unbind, unstack, tile, repeat_interleave, expand, expand_as,
    broadcast_to, broadcast_tensors, flip, rot90, roll, slice, strided_slice,
    crop, gather, gather_nd, take_along_axis, put_along_axis, scatter,
    scatter_nd_add, scatter_nd, index_select, index_sample, index_add,
    index_put, masked_select, take, unique, unique_consecutive, nonzero,
    searchsorted, bucketize, as_complex, as_real, atleast_1d, atleast_2d,
    atleast_3d, tensordot, tolist, numel, shard_index, swapaxes, pad,
    tensor_split, hsplit, vsplit, dsplit, view,
)
from .ops.linalg import (
    matmul, mm, dot, bmm, mv, t, cross, dist, norm, trace, diagonal, kron,
    einsum, histogram, bincount,
)
from . import linalg
from .autograd import backward as _backward_fn

__version__ = "0.1.0"


def flops(*args, **kwargs):  # paddle.flops parity — model profiler hook
    from .hapi.summary import flops as _flops

    return _flops(*args, **kwargs)


def in_dynamic_mode() -> bool:
    """Eager-vs-traced probe (paddle.in_dynamic_mode parity). Returns False
    inside jit-traced code."""
    from .jit import is_tracing

    return not is_tracing()


def get_flags(name=None):
    from .utils import flags as _flags

    return _flags.get_flags(name)


def set_flags(d):
    from .utils import flags as _flags

    return _flags.set_flags(d)


def save(obj, path, **kwargs):
    from .framework_io import save as _save

    return _save(obj, path, **kwargs)


def load(path, **kwargs):
    from .framework_io import load as _load

    return _load(path, **kwargs)


def summary(net, input_size=None, dtypes=None, input=None):
    from .hapi.summary import summary as _summary

    return _summary(net, input_size, dtypes, input)


def iinfo(dtype):
    import numpy as np

    from .framework.dtype import convert_dtype

    return np.iinfo(convert_dtype(dtype))


def finfo(dtype):
    import jax.numpy as jnp

    from .framework.dtype import convert_dtype

    return jnp.finfo(convert_dtype(dtype))


def is_grad_enabled():
    from .autograd.tape import grad_enabled

    return grad_enabled()


# subpackages (imported lazily in __getattr__ to keep import light and avoid
# cycles: nn imports paddle_tpu at module load)
_LAZY_SUBMODULES = (
    "nn",
    "observability",
    "optimizer",
    "amp",
    "io",
    "jit",
    "distributed",
    "vision",
    "metric",
    "hapi",
    "profiler",
    "incubate",
    "sparse",
    "static",
    "utils",
    "text",
    "audio",
    "onnx",
    "quantization",
    "autograd",
    "distribution",
    "generation",
    "inference",
    "linalg",
    "fft",
    "signal",
    "geometric",
    "strings",
    "regularizer",
    "callbacks",
    "sysconfig",
    "hub",
    "version",
    "tensorrt",
    "peft",
)



# ---- schema-generated op tail + retrofit registration -------------------------
from .ops import schema as _schema

histogramdd = _schema.generated("histogramdd")
renorm = _schema.generated("renorm")
reverse = _schema.generated("reverse")
increment = _schema.generated("increment")
as_strided = _schema.generated("as_strided")
view_as = _schema.generated("view_as")
vander = _schema.generated("vander")
quantile = _schema.generated("quantile")
nanquantile = _schema.generated("nanquantile")
index_fill = _schema.generated("index_fill")
fill_diagonal = _schema.generated("fill_diagonal")

from .tensor_array import (  # noqa: E402
    TensorArray, create_array, array_length, array_read, array_write)
gammaln = _schema.generated("gammaln")
gammainc = _schema.generated("gammainc")
gammaincc = _schema.generated("gammaincc")
i0e = _schema.generated("i0e")
i1e = _schema.generated("i1e")

# round-3 tensor-surface tail (tensor_method_func parity)
sinc = _schema.generated("sinc")
multigammaln = _schema.generated("multigammaln")
isin = _schema.generated("isin")
sgn = _schema.generated("sgn")
frexp = _schema.generated("frexp")
signbit = _schema.generated("signbit")
cumulative_trapezoid = _schema.generated("cumulative_trapezoid")
reduce_as = _schema.generated("reduce_as")
add_n = _schema.generated("add_n")
histogram_bin_edges = _schema.generated("histogram_bin_edges")
block_diag = _schema.generated("block_diag")
slice_scatter = _schema.generated("slice_scatter")
select_scatter = _schema.generated("select_scatter")
diagonal_scatter = _schema.generated("diagonal_scatter")
masked_scatter = _schema.generated("masked_scatter")
unflatten = _schema.generated("unflatten")
cdist = _schema.generated("cdist")
cholesky_inverse = _schema.generated("cholesky_inverse")
top_p_sampling = _schema.generated("top_p_sampling")
bitwise_invert = ops.math.bitwise_not
less = ops.math.less_than


def broadcast_shape(x_shape, y_shape):
    """paddle.broadcast_shape — pure shape computation (InferMeta analog)."""
    import numpy as _np

    return list(_np.broadcast_shapes(tuple(x_shape), tuple(y_shape)))


def is_empty(x):
    """paddle.is_empty: True iff the tensor has zero elements."""
    import jax.numpy as _jnp

    from .tensor_class import unwrap as _unwrap, wrap as _wrap

    return _wrap(_jnp.asarray(_unwrap(x).size == 0))


def rank(x):
    """paddle.rank: 0-D int tensor holding the rank (ndim) of x."""
    import jax.numpy as _jnp

    from .tensor_class import unwrap as _unwrap, wrap as _wrap

    return _wrap(_jnp.asarray(_unwrap(x).ndim))


def is_complex(x):
    from .framework.dtype import is_complex_dtype
    from .tensor_class import unwrap as _unwrap

    return is_complex_dtype(_unwrap(x).dtype)


def is_floating_point(x):
    from .framework.dtype import is_floating_point_dtype
    from .tensor_class import unwrap as _unwrap

    return is_floating_point_dtype(_unwrap(x).dtype)


def is_integer(x):
    from .framework.dtype import is_integer_dtype
    from .tensor_class import unwrap as _unwrap

    return is_integer_dtype(_unwrap(x).dtype)


# ---- top-level __all__ tail (reference python/paddle/__init__.py parity) -----
def enable_static():
    from . import static as _static

    return _static.enable_static()


def disable_static():
    from . import static as _static

    return _static.disable_static()


from .ops.manipulation import (  # noqa: E402
    hstack, vstack, dstack, column_stack, row_stack, cartesian_prod,
    combinations, shape)
from .ops.creation import binomial, standard_gamma, log_normal  # noqa: E402
from .nn.initializer_core import ParamAttr  # noqa: E402
from .linalg import matrix_transpose  # noqa: E402

pdist = _schema.generated("pdist")
positive = _schema.generated("positive")
unfold = _schema.generated("unfold_window")
diag_embed = linalg.diag_embed

import numpy as _np  # noqa: E402

inf = float("inf")
newaxis = None
dtype = _np.dtype          # paddle.dtype: Tensor.dtype instances are np dtypes


class _SpecialDType:
    """Non-numeric VarType sentinel (paddle.pstring / paddle.raw parity —
    XLA has no such dtypes; these exist for isinstance/label use only)."""

    __slots__ = ("name",)

    def __init__(self, name):
        self.name = name

    def __repr__(self):
        return f"paddle.{self.name}"


pstring = _SpecialDType("pstring")
raw = _SpecialDType("raw")


def set_printoptions(precision=None, threshold=None, edgeitems=None,
                     sci_mode=None, linewidth=None):
    """paddle.set_printoptions → numpy printoptions (our repr prints via
    numpy)."""
    kw = {}
    if precision is not None:
        kw["precision"] = precision
    if threshold is not None:
        kw["threshold"] = threshold
    if edgeitems is not None:
        kw["edgeitems"] = edgeitems
    if linewidth is not None:
        kw["linewidth"] = linewidth
    if sci_mode is not None:
        kw["suppress"] = not sci_mode
    _np.set_printoptions(**kw)


def batch(reader, batch_size, drop_last=False):
    """paddle.batch (python/paddle/batch.py): batch a sample generator."""
    def batched():
        buf = []
        for item in reader():
            buf.append(item)
            if len(buf) == batch_size:
                yield buf
                buf = []
        if buf and not drop_last:
            yield buf

    return batched


def check_shape(shape, op_name="", expected_shape_type=(list, tuple),
                expected_element_type=(int,), expected_tensor_dtype=("int32", "int64")):
    """paddle.check_shape (base/data_feeder.py): eager mode returns
    immediately in the reference too — shape errors surface from jnp."""
    return None


def disable_signal_handler():
    """paddle.disable_signal_handler: the reference uninstalls its C++
    fatal-signal dumpers; this runtime installs none, so there is nothing
    to disable (documented no-op)."""
    return None


class LazyGuard:
    """paddle.LazyGuard parity. Under JAX, parameter arrays are committed
    lazily by async dispatch and cost no device memory until first use, so
    eager initialization is already 'lazy' in the sense this guard provides
    in the reference (delayed allocation); the context manager is kept for
    API compatibility."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def get_cuda_rng_state():
    """CUDA-API-name parity: maps to the single framework RNG state."""
    return get_rng_state()


def set_cuda_rng_state(state):
    return set_rng_state(state)


def to_dlpack(x):
    """paddle.utils.dlpack surface: the device array as a dlpack-capable
    object (modern __dlpack__ protocol — consumers call __dlpack__
    themselves; the legacy one-shot capsule is deprecated in jax)."""
    from .tensor_class import unwrap as _unwrap

    return _unwrap(x)


def from_dlpack(ext):
    import jax.numpy as _jnp2

    from .tensor_class import wrap as _wrap

    return _wrap(_jnp2.from_dlpack(ext))


def log_normal_(x, mean=1.0, std=2.0, name=None):
    return x.log_normal_(mean, std)


def _install_inplace_functions():
    """Module-level in-place forms (paddle.log_(x) etc. — the reference
    exports every Tensor inplace method as a function too)."""
    g = globals()
    names = [
        "abs", "acos", "addmm", "asin", "atan", "bernoulli", "bitwise_and",
        "bitwise_invert", "bitwise_left_shift", "bitwise_not", "bitwise_or",
        "bitwise_right_shift", "bitwise_xor", "cast", "cauchy", "ceil",
        "clip", "copysign", "cos", "cosh", "cumprod", "cumsum", "digamma",
        "divide", "equal", "erf", "erfinv", "exp", "expm1", "flatten",
        "floor", "floor_divide", "floor_mod", "frac", "gammainc",
        "gammaincc", "gammaln", "gcd", "geometric", "greater_equal",
        "greater_than", "hypot", "i0", "index_add", "index_fill",
        "index_put", "lcm",
        "ldexp", "lerp", "less", "less_equal", "less_than", "lgamma", "log",
        "log10", "log1p", "log2", "logical_and", "logical_not", "logical_or",
        "logical_xor", "logit", "masked_fill", "masked_scatter", "mod",
        "multigammaln", "multiply", "nan_to_num", "neg", "normal",
        "not_equal", "polygamma", "pow", "put_along_axis", "reciprocal",
        "remainder", "renorm", "reshape", "round", "rsqrt", "scale",
        "scatter", "sigmoid", "sign", "sin", "sinc", "sinh", "sqrt",
        "square", "squeeze", "subtract", "t", "tan", "tanh", "transpose",
        "tril", "triu", "trunc", "uniform", "unsqueeze", "where", "add",
        "exponential",
    ]
    for name in names:
        meth = name + "_"
        if not hasattr(Tensor, meth):
            continue

        def fn(x, *a, _m=meth, **k):
            return getattr(x, _m)(*a, **k)

        fn.__name__ = meth
        fn.__doc__ = (f"In-place function form of Tensor.{meth} "
                      "(reference exports both)")
        g.setdefault(meth, fn)


_install_inplace_functions()


def _finalize_schema():
    """Register every public-op retrofit in the registry (ops.yaml parity:
    the registry enumerates the full kernel surface). Resolution of each
    public path is lazy, so nn/linalg/fft/signal stay lazily imported."""
    _schema.register_retrofits()


def __getattr__(name):
    if name in _LAZY_SUBMODULES:
        import importlib

        mod = importlib.import_module(f".{name}", __name__)
        globals()[name] = mod
        return mod
    if name == "Model":
        from .hapi.model import Model

        return Model
    if name == "DataParallel":
        from .distributed.parallel import DataParallel

        return DataParallel
    raise AttributeError(f"module 'paddle_tpu' has no attribute {name!r}")


def __dir__():
    # lazy names must be introspectable (dir()/doc tooling/surface diffs),
    # not just gettable
    return sorted(set(globals()) | set(_LAZY_SUBMODULES)
                  | {"Model", "DataParallel"})


_finalize_schema()
