"""Where set-up goes: every program build JAX makes in this process, by
stage, program and cause.

One listener on ``jax.monitoring``, installed when ``paddle_tpu`` is
imported, so the weights' initializers are seen too. It runs only while
JAX builds a program, never on a step path. JAX marks each stage of a
build as a span named by event, with its start and end on ``time.time()``
and the program's name (``fun_name``):

- ``trace``: ``jaxpr_trace_duration`` (Python tracing to a jaxpr);
- ``lower``: ``jaxpr_to_mlir_module_duration`` (jaxpr to StableHLO);
- ``load``: ``backend_compile_duration`` inside which the persistent
  compilation cache reported a hit (``cache_hits``, same thread);
- ``compile``: ``backend_compile_duration`` otherwise.

JAX also records each span's start as a scalar of the same event name
when the span opens, so the listener keeps a stack of the open spans of
each thread. A span nested in another of the SAME stage (a kernel
wrapper's ``jit`` traced inside ``decode_step``, a primitive traced
inside a trace) is part of the outer one and is counted once. A span
nested in another of a different stage (an eager primitive built while a
program traces) is counted under its own stage and taken out of the
outer one's seconds, so the stages never sum to more than the wall time
they cover.

Each counted build goes to three sinks: the counters
``jit_build_seconds_total`` and ``jit_builds_total`` (labels ``stage``,
``program``, ``cause``), a ``jit/<stage>`` span in the process's
``Tracer`` when it is enabled, and, for ``compile`` and ``load``, the
flight recorder's ``jit.compile`` event. ``program`` is the outermost
program's name with ``jit(...)`` stripped when it is one of
``PROGRAMS``, else ``other``; ``cause`` is what the building thread was
doing (``cause()``): ``param_init`` around a parameter's initializer, an
engine phase of ``PhaseClock`` (folded into ``CAUSES``), else ``none``.
docs/SERVING.md "Tracing", "Set-up", has the tables.
"""
from __future__ import annotations

import contextlib
import threading
import time

__all__ = ["STAGES", "PROGRAMS", "CAUSES", "install", "cause",
           "set_cause", "param_init"]

_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "compile",
}
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

STAGES = ("trace", "lower", "compile", "load")
#: the role names of docs/SERVING.md "Tracing" that a counter keeps;
#: every other program counts as ``other``
PROGRAMS = ("decode_step", "prefill", "prefill_embeds", "kv_scatter",
            "train_step")
CAUSES = ("param_init", "engine/admit", "engine/dispatch",
          "engine/retire", "none")
#: each engine phase under the cause a counter files it as: a later
#: chunk's prefill is admission, a speculative draft is the dispatch,
#: the wait for a step's tokens is its retirement
_FOLD = {"param_init": "param_init",
         "engine/admit": "engine/admit", "engine/prefill": "engine/admit",
         "engine/dispatch": "engine/dispatch",
         "engine/draft": "engine/dispatch",
         "engine/retire": "engine/retire", "engine/sync": "engine/retire"}

#: per thread: ``cause`` (what the thread is doing, or None) and
#: ``stack`` (the build spans open on it, innermost last)
_LOCAL = threading.local()
_INSTALLED = False
_SINKS = None


# ---- cause ------------------------------------------------------------------

def set_cause(name):
    """Make ``name`` (or None) the calling thread's cause; returns the
    one it replaces. ``PhaseClock.open``/``close`` call this."""
    prev = getattr(_LOCAL, "cause", None)
    _LOCAL.cause = name
    return prev


@contextlib.contextmanager
def cause(name: str):
    """``name`` is the calling thread's cause inside the block."""
    prev = set_cause(name)
    try:
        yield
    finally:
        _LOCAL.cause = prev


@contextlib.contextmanager
def param_init():
    """Around one parameter's initializer: cause ``param_init``, and the
    call's host seconds (dispatch plus any builds inside it; the device's
    work is asynchronous and not in it) added to
    ``nn_param_init_seconds_total``."""
    prev = set_cause("param_init")
    t0 = time.perf_counter()
    try:
        yield
    finally:
        _LOCAL.cause = prev
        _sinks().param_init.inc(time.perf_counter() - t0)


# ---- the listener -----------------------------------------------------------

class _Open:
    """One build span open on a thread."""

    __slots__ = ("event", "stage", "name", "cause", "inner")

    def __init__(self, event, kw):
        self.event, self.stage = event, _EVENTS[event]
        self.name = str(kw.get("fun_name", ""))
        self.cause = getattr(_LOCAL, "cause", None)
        self.inner = 0.0    # seconds of nested spans counted elsewhere


def _stack() -> list:
    st = getattr(_LOCAL, "stack", None)
    if st is None:
        st = _LOCAL.stack = []
    return st


def _on_start(event: str, value: float, **kw):
    if event in _EVENTS:
        _stack().append(_Open(event, kw))


def _on_cache_hit(event: str, **kw):
    if event != _CACHE_HIT:
        return
    for frame in reversed(_stack()):
        if frame.stage == "compile":
            frame.stage = "load"
            return


def _on_span(event: str, start: float, end: float, **kw):
    if event not in _EVENTS:
        return
    st = _stack()
    if st and st[-1].event == event:
        frame = st.pop()
    else:                       # opened before the listener was installed
        frame = _Open(event, kw)
    outer = st[-1] if st else None
    if any(f.stage == frame.stage for f in st):
        # part of an outer span of its stage, which counts it: what was
        # counted inside it is taken out of that one instead
        outer.inner += frame.inner
        return
    if outer is not None:
        outer.inner += end - start
    root = st[0] if st else frame
    _record(frame.stage, root.name, root.cause, start, end,
            max(end - start - frame.inner, 0.0))


def _program(name: str) -> str:
    if name.startswith("jit(") and name.endswith(")"):
        name = name[4:-1]
    return name if name in PROGRAMS else "other"


def _record(stage, name, raw_cause, start, end, seconds):
    sinks = _sinks()
    key = (stage, _program(name), _FOLD.get(raw_cause, "none"))
    pair = sinks.bound.get(key)
    if pair is None:
        labels = dict(zip(("stage", "program", "cause"), key))
        pair = sinks.bound[key] = (sinks.seconds.labels(**labels),
                                   sinks.builds.labels(**labels))
    pair[0].inc(seconds)
    pair[1].inc()
    tracing = sinks.tracing
    tracer = tracing.get_tracer()
    if tracer.enabled:
        # the span sits on the tracer's perf_counter clock; jax's own
        # time.time() stamps (the clock set-up laps are taken on) are kept
        # as attributes
        now_ns, now_s = time.perf_counter_ns(), time.time()
        tracer.add_span(
            {"trace": tracing.SPAN_JIT_TRACE, "lower": tracing.SPAN_JIT_LOWER,
             "compile": tracing.SPAN_JIT_COMPILE,
             "load": tracing.SPAN_JIT_LOAD}[stage],
            now_ns - int((now_s - start) * 1e9),
            now_ns - int((now_s - end) * 1e9),
            attrs={"program": name, "cause": raw_cause or "none",
                   "start_s": start, "end_s": end, "seconds": seconds})
    rec = sinks.flightrecorder.RECORDER
    if rec.enabled and stage in ("compile", "load"):
        rec.record(sinks.flightrecorder.EV_COMPILE, stage=stage,
                   program=name, seconds=end - start)


class _Sinks:
    """Where a counted build goes, bound at the first build: the
    observability package is not imported with ``paddle_tpu``."""

    def __init__(self):
        from ..observability import catalog, flightrecorder, tracing

        self.seconds = catalog.JIT_BUILD_SECONDS
        self.builds = catalog.JIT_BUILDS
        self.param_init = catalog.NN_PARAM_INIT_SECONDS.labels()
        self.bound = {}     # (stage, program, cause) -> bound counters
        self.tracing, self.flightrecorder = tracing, flightrecorder


def _sinks() -> _Sinks:
    global _SINKS
    if _SINKS is None:
        _SINKS = _Sinks()
    return _SINKS


def install() -> None:
    """Register the listener with ``jax.monitoring`` (once a process)."""
    global _INSTALLED
    if _INSTALLED:
        return
    from jax import monitoring

    monitoring.register_scalar_listener(_on_start)
    monitoring.register_event_listener(_on_cache_hit)
    monitoring.register_event_time_span_listener(_on_span)
    _INSTALLED = True
