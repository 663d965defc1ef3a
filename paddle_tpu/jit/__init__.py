"""paddle_tpu.jit — the compile bridge.

Reference parity: paddle.jit.to_static (python/paddle/jit/api.py:197) + the
SOT bytecode JIT (python/paddle/jit/sot/). TPU-native design: there is no AST
rewriting or frame-eval hook — a Layer/function traces straight through
jax.jit because every op in this framework is a pure jax call under the hood
(SURVEY.md §7: "SOT's role ≈ jax.jit tracing"). What this module adds over raw
jax.jit:

- Tensor/Layer awareness: parameters/buffers become traced inputs (so updates
  and state_dict loads don't trigger recompiles), Tensors in args are
  unwrapped/wrapped at the boundary;
- train-step compilation (``TrainStep``): loss + backward + optimizer update
  fused into ONE XLA computation with donated arg buffers — the performance
  path that replaces the reference's whole-program static graph (CS3/CS5);
- input_spec/static shape declarations, AOT lowering (``jit.save``/``load``
  via jax.export) and compile-cache statistics.
"""
from __future__ import annotations

import functools
import weakref
from typing import Any, Callable, Dict, Optional, Sequence

import jax
import jax.numpy as jnp

from ..tensor_class import Tensor, Parameter, unwrap, wrap
from ..framework import random as _random


class InputSpec:
    """paddle.static.InputSpec parity."""

    def __init__(self, shape, dtype="float32", name=None, stop_gradient=True):
        from ..framework.dtype import convert_dtype

        self.shape = tuple(shape)
        self.dtype = convert_dtype(dtype)
        self.name = name

    def to_shape_dtype_struct(self, sym_prefix: str = "d"):
        """Dynamic dims (None/-1) become jax.export symbolic dimensions so an
        exported artifact accepts any size there (paddle InputSpec semantics)."""
        if any(s in (None, -1) for s in self.shape):
            from jax import export as jax_export

            spec = ",".join(
                f"{sym_prefix}{i}" if s in (None, -1) else str(s)
                for i, s in enumerate(self.shape)
            )
            shape = jax_export.symbolic_shape(spec)
            return jax.ShapeDtypeStruct(tuple(shape), self.dtype)
        return jax.ShapeDtypeStruct(tuple(self.shape), self.dtype)

    def __repr__(self):
        return f"InputSpec(shape={self.shape}, dtype={self.dtype}, name={self.name})"


def _unwrap_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: x._array if isinstance(x, Tensor) else x, tree,
        is_leaf=lambda x: isinstance(x, Tensor),
    )


def _wrap_tree(tree):
    return jax.tree_util.tree_map(
        lambda x: wrap(x) if isinstance(x, jax.Array) else x, tree)


class StaticFunction:
    """Compiled callable (parity: dy2static StaticFunction,
    program_translator.py:387). Wraps either a bare function or a Layer's
    forward; Layer state rides as a traced pytree argument."""

    def __init__(self, fn, layer=None, input_spec=None, donate_state: bool = False,
                 static_argnums=(), backend=None):
        self._fn = fn
        self._layer = layer
        self._input_spec = input_spec
        self._static_argnums = static_argnums
        self._compile_count = 0
        self._printed_sigs = set()
        self._name = getattr(fn, "__qualname__", None) or getattr(
            fn, "__name__", "<fn>")
        _LIVE_STATIC_FUNCTIONS.add(self)

        if layer is not None:
            def to_static_forward(state, rng_key, training, *args, **kwargs):
                # swap traced arrays in, restore eager arrays after the trace
                # (otherwise tracers leak into the layer's eager state)
                from ..nn.layer import functional_weights

                subs = layer.sublayers(include_self=True)
                prev_modes = [l.training for l in subs]
                for l in subs:
                    l.training = training
                try:
                    with functional_weights(layer, state), \
                            _random.rng_context(rng_key):
                        out = fn(*args, **kwargs)
                    return _unwrap_tree(out)
                finally:
                    for l, m in zip(subs, prev_modes):
                        l.training = m

            self._jitted = jax.jit(
                to_static_forward,
                static_argnums=(2,) + tuple(a + 3 for a in static_argnums))
        else:
            def to_static_function(rng_key, *args, **kwargs):
                with _random.rng_context(rng_key):
                    return _unwrap_tree(fn(*args, **kwargs))

            self._jitted = jax.jit(
                to_static_function,
                static_argnums=tuple(a + 1 for a in static_argnums))

    def __call__(self, *args, **kwargs):
        from ..autograd import tape as _tape

        key = _random.next_key()
        uargs = _unwrap_tree(args)
        ukwargs = _unwrap_tree(kwargs)
        # inside the compiled region the tape must not record (jax.grad is
        # the autograd there); outputs come back as fresh tensors
        prev = _tape.set_grad_enabled(False)
        try:
            if self._layer is not None:
                state = self._layer.functional_state()
                full_args = (state, key, self._layer.training) + tuple(uargs)
            else:
                full_args = (key,) + tuple(uargs)
            if _SOT_VERBOSITY > 0:
                # print the lowered program only for NEW specializations —
                # re-lowering every call would double host overhead
                import jax as _jax

                sig = tuple(
                    (getattr(a, "shape", None), str(getattr(a, "dtype", a)))
                    for a in _jax.tree_util.tree_leaves((uargs, ukwargs)))
                if sig not in self._printed_sigs:
                    self._printed_sigs.add(sig)
                    print(self._jitted.lower(
                        *full_args, **ukwargs).as_text()[:10_000])
            out = self._jitted(*full_args, **ukwargs)
        finally:
            _tape.set_grad_enabled(prev)
        return _wrap_tree(out)

    @property
    def forward(self):
        return self

    @property
    def specializations(self) -> int:
        """Compiled specializations of the underlying jax.jit cache —
        the retrace-hazard signal graph analysis consumes
        (analysis.graph.retrace.live_specialization_findings): a serving
        step should compile a handful of shape buckets, not one per
        request."""
        try:
            return int(self._jitted._cache_size())
        except Exception:  # pdlint: disable=silent-exception -- private jax API; absent means "no signal", not a fault
            return 0

    def concrete_program(self, *args):  # introspection hook
        return self._jitted.lower(*args)


# every live StaticFunction, for the specialization-count hook: weak
# refs, so watching compile caches never pins a model in memory
_LIVE_STATIC_FUNCTIONS: "weakref.WeakSet[StaticFunction]" = weakref.WeakSet()


def specialization_stats() -> Dict[str, int]:
    """{callable-name: compiled-specialization-count} over every live
    StaticFunction. Names collide across instances wrapping same-named
    functions; the max wins (the hook exists to catch blow-ups, and the
    blown-up instance is the interesting one)."""
    out: Dict[str, int] = {}
    for sf in list(_LIVE_STATIC_FUNCTIONS):
        n = sf.specializations
        if n:
            out[sf._name] = max(out.get(sf._name, 0), n)
    return out


def to_static(function=None, input_spec=None, build_strategy=None, backend=None,
              full_graph=True, **kwargs):
    """paddle.jit.to_static parity (jit/api.py:197). Decorates a function or a
    Layer; returns a compiled callable."""

    def decorate(obj):
        from ..nn.layer import Layer

        if isinstance(obj, Layer):
            sf = StaticFunction(obj.forward, layer=obj, input_spec=input_spec)
            obj.forward = sf
            return obj
        return StaticFunction(obj, input_spec=input_spec)

    if function is not None:
        return decorate(function)
    return decorate


def not_to_static(fn):
    """Marker parity — in this framework a python-level call simply stays
    outside the traced graph when invoked eagerly."""
    fn._not_to_static = True
    return fn


def ignore_module(modules):
    pass


# ---- fused train step --------------------------------------------------------

class TrainStep:
    """One-XLA-computation training step: fwd + bwd + optimizer update.

    The TPU replacement for the reference's static-graph training executor
    (CS3): build once, then each call is a single device computation with
    donated buffers. Use via ``paddle_tpu.jit.train_step(model, loss_fn, opt)``.
    """

    def __init__(self, model, loss_fn, optimizer, donate=True):
        self._model = model
        self._optimizer = optimizer
        self._loss_fn = loss_fn
        self._opt_state = None
        self._params0 = None

        def train_step(params, buffers, opt_state, rng_key, lr, *batch):
            def loss_of(p):
                from ..nn.layer import functional_weights

                with functional_weights(model, {**p, **buffers}), \
                        _random.rng_context(rng_key):
                    loss = loss_fn(model, *[wrap(b) for b in batch])
                return unwrap(loss)

            # the two halves carry their scope in every instruction's
            # metadata (op_name), at no run-time cost
            with jax.named_scope("forward_backward"):
                loss, grads = jax.value_and_grad(loss_of)(params)
            with jax.named_scope("optimizer"):
                new_params, new_opt_state = optimizer.apply_gradients(
                    opt_state, params, grads, lr=lr)
            return loss, new_params, new_opt_state

        # jit-path NaN/Inf hooks (VERDICT r2 missing #10): the eager
        # FLAGS_check_nan_inf hook cannot see inside a compiled step, so
        # when the flag is set at construction the whole step is compiled
        # under checkify float checks and every call throws on the first
        # non-finite intermediate (the role of new_executor/nan_inf_utils.cc)
        from ..utils.flags import flag as _flag

        self._checkified = bool(_flag("FLAGS_check_nan_inf"))
        if self._checkified:
            from jax.experimental import checkify

            # debug mode: NO buffer donation, so a thrown step leaves the
            # model's params and the optimizer state untouched and the user
            # can catch, skip the bad batch, and continue
            self._jitted = jax.jit(
                checkify.checkify(train_step, errors=checkify.float_checks))
        else:
            self._jitted = jax.jit(train_step, donate_argnums=(0, 2))

    def _split_state(self):
        params, buffers = {}, {}
        trainable_names = {name for name, p in self._model.named_parameters() if not p.stop_gradient}
        for k, v in self._model.functional_state().items():
            (params if k in trainable_names else buffers)[k] = v
        return params, buffers

    def __call__(self, *batch):
        from ..autograd import tape as _tape

        params, buffers = self._split_state()
        if self._opt_state is None:
            self._opt_state = self._optimizer.init_state(params)
        key = _random.next_key()
        lr = jnp.asarray(self._optimizer.get_lr(), jnp.float32)
        ubatch = [unwrap(b) for b in batch]
        prev = _tape.set_grad_enabled(False)
        try:
            if self._checkified:
                err, (loss, new_params, new_opt_state) = self._jitted(
                    params, buffers, self._opt_state, key, lr, *ubatch)
                try:
                    err.throw()
                except Exception as e:
                    # nothing committed: params/opt_state still hold the
                    # pre-step values, so the step can be retried/skipped
                    raise FloatingPointError(
                        f"NaN/Inf inside the compiled train step "
                        f"(FLAGS_check_nan_inf): {e}") from None
                self._opt_state = new_opt_state
            else:
                loss, new_params, self._opt_state = self._jitted(
                    params, buffers, self._opt_state, key, lr, *ubatch)
        finally:
            _tape.set_grad_enabled(prev)
        self._model.load_functional_state(new_params)
        if isinstance(self._optimizer._lr, object) and hasattr(self._optimizer._lr, "step"):
            pass  # scheduler stepping is the caller's choice (paddle semantics)
        return wrap(loss)


def train_step(model, loss_fn, optimizer, donate=True) -> TrainStep:
    return TrainStep(model, loss_fn, optimizer, donate)


# ---- save / load (AOT export) ------------------------------------------------

def save(layer, path, input_spec=None, **configs):
    """paddle.jit.save parity: persists weights + a serialized lowered
    computation (jax.export) when input_spec is given."""
    import os
    import pickle

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    state = {k: __import__("numpy").asarray(v) for k, v in layer.functional_state().items()}
    with open(path + ".pdiparams", "wb") as f:
        pickle.dump(state, f, protocol=4)
    meta = {"class": type(layer).__name__, "input_spec": None}
    if input_spec is not None:
        try:
            from jax import export as jax_export

            specs = [s.to_shape_dtype_struct() for s in input_spec]

            def exported_forward(state_arrs, *args):
                from ..nn.layer import functional_weights

                with functional_weights(layer, state_arrs):
                    return _unwrap_tree(
                        layer.forward(*[wrap(a) for a in args]))

            exported = jax_export.export(jax.jit(exported_forward))(
                {k: jax.ShapeDtypeStruct(v.shape, v.dtype) for k, v in state.items()},
                *specs,
            )
            with open(path + ".pdmodel", "wb") as f:
                f.write(exported.serialize())
            meta["input_spec"] = [(list(s.shape), str(s.dtype)) for s in input_spec]
        except Exception as e:  # export is best-effort; weights always saved
            meta["export_error"] = str(e)
    with open(path + ".pdmeta", "wb") as f:
        pickle.dump(meta, f)


class TranslatedLayer:
    """Loaded inference artifact (parity: paddle.jit.TranslatedLayer)."""

    def __init__(self, exported, state):
        self._exported = exported
        self._state = state

    def __call__(self, *args):
        out = self._exported.call(self._state, *[unwrap(a) for a in args])
        return _wrap_tree(out)

    def forward(self, *args):
        return self(*args)


def load(path, **configs):
    import pickle

    with open(path + ".pdiparams", "rb") as f:
        state = pickle.load(f)
    state = {k: jnp.asarray(v) for k, v in state.items()}
    try:
        from jax import export as jax_export

        with open(path + ".pdmodel", "rb") as f:
            exported = jax_export.deserialize(f.read())
        return TranslatedLayer(exported, state)
    except FileNotFoundError:
        return state


def enable_to_static(flag: bool):
    pass


def is_tracing() -> bool:
    """True inside any jax trace (jit, grad, vmap, scan body)."""
    return not jax.core.trace_ctx.is_top_level()


# ---- flight-recorder compile events -----------------------------------------

_COMPILE_EVENTS_INSTALLED = False


def install_compile_events() -> bool:
    """Hook ``jax.monitoring`` so every XLA backend compile lands in the
    flight recorder as a ``jit.compile`` event (event name + duration) —
    the black-box answer to "the engine stalled because a cold
    prompt-length bucket compiled mid-traffic". Installed once per
    process (FlightRecorder.enable() calls this); the listener is itself
    guarded on the recorder flag, so a disabled recorder pays one
    predicate per compile, not per dispatch. Raises ImportError on a jax
    without ``monitoring`` — the caller treats that as "no compile
    events", not a fault."""
    global _COMPILE_EVENTS_INSTALLED
    if _COMPILE_EVENTS_INSTALLED:
        return True
    from jax import monitoring as _monitoring

    from ..observability import flightrecorder as _frec

    def _on_event_duration(name: str, duration: float, **kw):
        rec = _frec.RECORDER
        if rec.enabled and name.endswith("backend_compile_duration"):
            rec.record(_frec.EV_COMPILE, event=name,
                       seconds=float(duration))

    _monitoring.register_event_duration_secs_listener(_on_event_duration)
    _COMPILE_EVENTS_INSTALLED = True
    return True


_SOT_CODE_LEVEL = 0
_SOT_VERBOSITY = 0


def set_code_level(level=100, also_to_stdout=False):
    """paddle.jit.set_code_level parity: the reference dumps SOT-transformed
    bytecode at the given level; the analogous artifact here is the lowered
    program, printed once per new specialization (same hook as
    set_verbosity — any level > 0 enables it)."""
    global _SOT_CODE_LEVEL, _SOT_VERBOSITY
    _SOT_CODE_LEVEL = level
    if level:
        _SOT_VERBOSITY = max(_SOT_VERBOSITY, 1)


def set_verbosity(level=0, also_to_stdout=False):
    """paddle.jit.set_verbosity parity: 0 silent; >0 makes to_static print
    the traced jaxpr of each newly compiled specialization."""
    global _SOT_VERBOSITY
    _SOT_VERBOSITY = level
