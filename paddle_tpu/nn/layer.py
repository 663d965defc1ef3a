"""paddle_tpu.nn.Layer — the module system.

Reference parity: paddle.nn.Layer (python/paddle/nn/layer/layers.py): named
parameter/buffer/sublayer trees, hooks, state_dict semantics, train/eval,
to()/astype. TPU-native additions: ``functional_state`` /
``load_functional_state`` produce/consume a pure pytree of arrays so any
Layer drops into jax.jit/jax.grad/pjit (the role the dygraph→static
translators play in the reference, without AST surgery), and
``shard_fn``-style placement annotations hang off parameters for the
auto-parallel API (distributed/api.py).
"""
from __future__ import annotations

import collections
import contextlib
import threading
from typing import Any, Callable, Dict, Iterator, Optional, Tuple

import jax
import numpy as np

from ..jit import build_events as _build_events
from ..tensor_class import Tensor, Parameter, wrap, unwrap
from ..framework import dtype as _dtype_mod
from .initializer_core import _resolve_initializer, ParamAttr


class HookRemoveHelper:
    _next_id = [0]

    def __init__(self, hooks: dict):
        self._hooks = hooks
        self._id = HookRemoveHelper._next_id[0]
        HookRemoveHelper._next_id[0] += 1

    def remove(self):
        self._hooks.pop(self._id, None)


#: process-wide trace serializer: ``functional_weights`` swaps TRACER
#: arrays into the layer's parameters for the duration of a jit trace,
#: so two threads tracing against the same model concurrently would
#: read each other's tracers (the serving engine compiling a prefill
#: while the correctness sentinel's audit worker retraces the reference
#: decode path). The traced body only runs at TRACE time — compiled
#: executions never enter this context — so the decode hot path never
#: contends here. Reentrant: a traced body that traces an inner jitted
#: step on the same thread re-enters freely.
_TRACE_LOCK = threading.RLock()


@contextlib.contextmanager
def functional_weights(layer, state):
    """Temporarily install a functional parameter pytree on ``layer`` inside
    a trace, restoring the original arrays after — the shared spine of every
    jitted step (TrainStep, pipeline stage fns, serving prefill/decode).
    Yields the layer's live state_dict so callers can read in-trace buffer
    updates (BatchNorm stats) before the restore. Cross-thread traces
    serialize on :data:`_TRACE_LOCK` — the parameter swap is a mutation
    of shared model state."""
    with _TRACE_LOCK:
        own = layer.state_dict()
        snapshot = {k: t._array for k, t in own.items()}
        layer.load_functional_state(state)
        try:
            yield own
        finally:
            for k, t in own.items():
                t._array = snapshot[k]


class Layer:
    def __init__(self, name_scope: Optional[str] = None, dtype=None):
        self.training = True
        # reference semantics (python/paddle/nn/layer/layers.py): a Layer
        # with no explicit dtype uses the GLOBAL default dtype, so model
        # code under framework.dtype_guard("bfloat16") builds bf16 params
        self._dtype = (_dtype_mod.convert_dtype(dtype) if dtype is not None
                       else _dtype_mod.default_float_dtype())
        self._parameters: Dict[str, Optional[Parameter]] = collections.OrderedDict()
        self._buffers: Dict[str, Optional[Tensor]] = collections.OrderedDict()
        self._non_persistable_buffer_names = set()
        self._sub_layers: Dict[str, Optional["Layer"]] = collections.OrderedDict()
        self._forward_pre_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._forward_post_hooks: Dict[int, Callable] = collections.OrderedDict()
        self._name_scope = name_scope or type(self).__name__.lower()

    # ---- attribute routing ---------------------------------------------------
    def __setattr__(self, name, value):
        params = self.__dict__.get("_parameters")
        layers = self.__dict__.get("_sub_layers")
        buffers = self.__dict__.get("_buffers")
        if isinstance(value, Parameter):
            if params is None:
                raise RuntimeError("call super().__init__() before assigning parameters")
            params[name] = value
            self.__dict__.pop(name, None)
            buffers.pop(name, None) if buffers else None
            layers.pop(name, None) if layers else None
        elif isinstance(value, Layer):
            if layers is None:
                raise RuntimeError("call super().__init__() before assigning sublayers")
            layers[name] = value
            self.__dict__.pop(name, None)
        elif params is not None and name in params:
            if value is None:
                params[name] = None
            elif isinstance(value, Tensor):
                params[name] = Parameter.from_tensor(value)
            else:
                raise TypeError(f"cannot assign {type(value)} to parameter {name}")
        elif buffers is not None and name in buffers:
            buffers[name] = value if (value is None or isinstance(value, Tensor)) else wrap(jax.numpy.asarray(value))
        elif layers is not None and name in layers and value is None:
            layers[name] = None
        else:
            object.__setattr__(self, name, value)

    def __getattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                return d[name]
        raise AttributeError(f"'{type(self).__name__}' object has no attribute '{name}'")

    def __delattr__(self, name):
        for store in ("_parameters", "_buffers", "_sub_layers"):
            d = self.__dict__.get(store)
            if d is not None and name in d:
                del d[name]
                return
        object.__delattr__(self, name)

    def __dir__(self):
        return list(super().__dir__()) + list(self._parameters) + list(self._buffers) + list(self._sub_layers)

    # ---- construction helpers ------------------------------------------------
    def create_parameter(self, shape, attr=None, dtype=None, is_bias=False,
                         default_initializer=None) -> Parameter:
        """Reference: Layer.create_parameter (layers.py) — honors ParamAttr
        (initializer/trainable/name)."""
        if attr is False:
            return None
        dtype = _dtype_mod.convert_dtype(dtype) if dtype is not None else self._dtype
        attr = ParamAttr._to_attr(attr)
        init = _resolve_initializer(attr, default_initializer, is_bias)
        with _build_events.param_init():
            arr = init(tuple(int(s) for s in shape), dtype)
        p = Parameter(arr, trainable=attr.trainable if attr else True,
                      name=attr.name if attr else None)
        return p

    def create_tensor(self, name=None, persistable=False, dtype=None):
        t = wrap(jax.numpy.zeros((), dtype=_dtype_mod.convert_dtype(dtype) if dtype else self._dtype))
        t.persistable = persistable
        return t

    def add_parameter(self, name: str, parameter: Optional[Parameter]):
        self._parameters[name] = parameter
        return parameter

    def add_sublayer(self, name: str, sublayer: "Layer"):
        self._sub_layers[name] = sublayer
        return sublayer

    def register_buffer(self, name: str, tensor: Optional[Tensor], persistable: bool = True):
        self._buffers[name] = tensor
        if not persistable:
            self._non_persistable_buffer_names.add(name)
        return tensor

    # ---- hooks ---------------------------------------------------------------
    def register_forward_pre_hook(self, hook) -> HookRemoveHelper:
        helper = HookRemoveHelper(self._forward_pre_hooks)
        self._forward_pre_hooks[helper._id] = hook
        return helper

    def register_forward_post_hook(self, hook) -> HookRemoveHelper:
        helper = HookRemoveHelper(self._forward_post_hooks)
        self._forward_post_hooks[helper._id] = hook
        return helper

    # ---- call ----------------------------------------------------------------
    def forward(self, *inputs, **kwargs):  # pragma: no cover - abstract
        raise NotImplementedError

    def __call__(self, *inputs, **kwargs):
        for hook in list(self._forward_pre_hooks.values()):
            result = hook(self, inputs)
            if result is not None:
                inputs = result if isinstance(result, tuple) else (result,)
        outputs = self.forward(*inputs, **kwargs)
        for hook in list(self._forward_post_hooks.values()):
            result = hook(self, inputs, outputs)
            if result is not None:
                outputs = result
        return outputs

    # ---- traversal -----------------------------------------------------------
    def named_parameters(self, prefix="", include_sublayers=True) -> Iterator[Tuple[str, Parameter]]:
        seen = set()
        for name, layer, in self._traverse(prefix, include_sublayers):
            for pname, p in layer._parameters.items():
                if p is not None and id(p) not in seen:
                    seen.add(id(p))
                    yield (f"{name}.{pname}" if name else pname), p

    def parameters(self, include_sublayers=True):
        return [p for _, p in self.named_parameters(include_sublayers=include_sublayers)]

    def named_buffers(self, prefix="", include_sublayers=True):
        seen = set()
        for name, layer in self._traverse(prefix, include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None and id(b) not in seen:
                    seen.add(id(b))
                    yield (f"{name}.{bname}" if name else bname), b

    def buffers(self, include_sublayers=True):
        return [b for _, b in self.named_buffers(include_sublayers=include_sublayers)]

    def _traverse(self, prefix="", include_sublayers=True):
        yield prefix, self
        if include_sublayers:
            for name, sub in self._sub_layers.items():
                if sub is None:
                    continue
                sub_prefix = f"{prefix}.{name}" if prefix else name
                yield from sub._traverse(sub_prefix, True)

    def named_sublayers(self, prefix="", include_self=False):
        if include_self:
            yield prefix, self
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            sub_prefix = f"{prefix}.{name}" if prefix else name
            yield sub_prefix, sub
            yield from sub.named_sublayers(sub_prefix)

    def sublayers(self, include_self=False):
        return [l for _, l in self.named_sublayers(include_self=include_self)]

    def children(self):
        return (l for l in self._sub_layers.values() if l is not None)

    def named_children(self):
        return ((n, l) for n, l in self._sub_layers.items() if l is not None)

    def apply(self, fn):
        for l in self.sublayers(include_self=True):
            fn(l)
        return self

    # ---- modes ---------------------------------------------------------------
    def train(self):
        for l in self.sublayers(include_self=True):
            l.training = True
        return self

    def eval(self):
        for l in self.sublayers(include_self=True):
            l.training = False
        return self

    # ---- state dict ----------------------------------------------------------
    def state_dict(self, destination=None, include_sublayers=True, use_hook=True,
                   structured_name_prefix=""):
        dest = destination if destination is not None else collections.OrderedDict()
        for name, p in self.named_parameters(prefix=structured_name_prefix.rstrip("."),
                                             include_sublayers=include_sublayers):
            dest[name] = p
        for name, layer in self._traverse(structured_name_prefix.rstrip("."), include_sublayers):
            for bname, b in layer._buffers.items():
                if b is not None and bname not in layer._non_persistable_buffer_names:
                    dest[(f"{name}.{bname}" if name else bname)] = b
        return dest

    def set_state_dict(self, state_dict, use_structured_name=True):
        missing, unexpected = [], []
        own = self.state_dict()
        matched = set()
        for key, value in state_dict.items():
            if key in own:
                arr = value._array if isinstance(value, Tensor) else jax.numpy.asarray(np.asarray(value))
                target = own[key]
                if tuple(arr.shape) != tuple(target._array.shape):
                    raise ValueError(
                        f"shape mismatch for {key}: loading {tuple(arr.shape)} into {tuple(target._array.shape)}"
                    )
                target._array = arr.astype(target._array.dtype)
                matched.add(key)
            else:
                unexpected.append(key)
        missing = [k for k in own if k not in matched]
        return missing, unexpected

    set_dict = set_state_dict
    load_dict = set_state_dict

    # ---- dtype / device movement --------------------------------------------
    def to(self, device=None, dtype=None, blocking=None):
        from ..framework import device as _device_mod

        dev = _device_mod._resolve(device) if device is not None else None
        dt = _dtype_mod.convert_dtype(dtype) if dtype is not None else None
        for _, p in list(self.named_parameters()) + list(self.named_buffers()):
            arr = p._array
            if dt is not None and _dtype_mod.is_floating_point_dtype(arr.dtype):
                arr = arr.astype(dt)
            if dev is not None:
                arr = jax.device_put(arr, dev)
            p._array = arr
        if dt is not None:
            self._dtype = dt
        return self

    def astype(self, dtype):
        return self.to(dtype=dtype)

    def float(self):
        return self.to(dtype="float32")

    def bfloat16(self):
        return self.to(dtype="bfloat16")

    def half(self):
        return self.to(dtype="float16")

    def clear_gradients(self):
        for p in self.parameters():
            p.clear_grad()

    # ---- functional bridge (jit / pjit path) ---------------------------------
    def functional_state(self) -> Dict[str, Any]:
        """Pure pytree {name: jax.Array} of all parameters + buffers.
        Serializes on :data:`_TRACE_LOCK`: while another thread's trace
        is inside :func:`functional_weights` the parameters hold that
        trace's TRACERS, and a concurrent snapshot would capture (and
        leak) them instead of real arrays."""
        with _TRACE_LOCK:
            return {k: v._array for k, v in self.state_dict().items()}

    def load_functional_state(self, state: Dict[str, Any]):
        own = self.state_dict()
        for k, arr in state.items():
            if k in own:
                own[k]._array = arr
        return self

    def full_name(self):
        return self._name_scope

    def extra_repr(self) -> str:
        return ""

    def __repr__(self):
        extra = self.extra_repr()
        lines = []
        for name, sub in self._sub_layers.items():
            if sub is None:
                continue
            mod_str = repr(sub)
            mod_str = "\n".join("  " + l for l in mod_str.split("\n"))
            lines.append(f"({name}): {mod_str.strip()}" if "\n" not in mod_str else f"({name}): {mod_str.lstrip()}")
        main = f"{type(self).__name__}({extra}"
        if lines:
            main += "\n  " + "\n  ".join(lines) + "\n"
        return main + ")"
