"""Step-anatomy profiler: continuous per-phase attribution of engine
steps on the HOST's clock.

The watchtower (alerts.py) judges *whether* the tier meets its SLOs and
the flight recorder explains *what broke*; this module says *where the
engine thread spends each step's milliseconds*. It is a host stopwatch
and states no device time, rate, MFU or roofline share: those come from
the device trace the benchmark reduces (``benchmarks/``, PERF.md section
3). Since one decode step is kept in flight, ``sync`` is the wait for
step N while step N + 1 is queued, so the phases are host time BESIDE a
running program, not time the device stands still.

- ``PhaseClock`` — a lock-free, engine-thread-only stopwatch the engines
  drive through their step loop: ``begin()`` at the top, ``lap(phase)``
  at each boundary. Phases accumulate in a plain dict, so a phase that
  recurs inside one step (the trailing admission re-laps "admit") sums
  instead of overwriting, and the per-step phase total equals the step
  wall time by construction.
- ``StepProfiler`` — one per engine, registered by label. Disabled by
  default and guarded Tracer-style at every hot site (one attribute
  check — the enabled overhead bar is < 1% of a decode step, the
  flight-recorder bar). ``commit()`` publishes per-phase histograms
  (``serving_step_phase_seconds``) and keeps a bounded window of recent
  steps for exact p50/p99 and top-K-slowest reporting.
- ``profile_payload()`` — the JSON surface behind ``GET /profile``,
  router-side ``GET /profile/cluster`` federation, the PROFILE section
  of incident bundles, and ``scripts/step_anatomy.py``.

See docs/SERVING.md "Step anatomy".
"""
from __future__ import annotations

import contextlib
import math
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

import jax

from . import catalog as _cat

__all__ = ["PHASES", "PhaseClock", "StepProfiler", "get_profiler",
           "profile_payload", "NO_SPAN"]

#: the phase vocabulary, in step order. ``draft`` only appears on the
#: speculative path; the seq2seq engine folds its encoder+seed prefill
#: into ``admit`` (that IS its admission prefill) and never drafts.
PHASES = ("admit", "prefill", "draft", "dispatch", "sync", "retire")

#: names on the profiler's trace (docs/SERVING.md "Tracing"): the step,
#: and ``engine/<phase>`` for each phase and each annotation-only span
STEP_SPAN = "engine/step"
SPAN_PREFIX = "engine/"


#: what a guarded annotation site enters while the profiler is off
NO_SPAN = contextlib.nullcontext()


def _enter(annotation):
    annotation.__enter__()
    return annotation


#: recent-step window: exact quantiles + top-K come from here, while the
#: histograms carry the unbounded series for the TSDB/alerting path
_WINDOW = 512


def _quantile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank quantile of an already-sorted list."""
    if not sorted_vals:
        return 0.0
    rank = int(math.ceil(q * len(sorted_vals))) - 1
    return sorted_vals[min(max(rank, 0), len(sorted_vals) - 1)]


class PhaseClock:
    """Engine-thread-only phase stopwatch. No locks: exactly one thread
    (the engine's step loop) ever touches an instance, and the profiler
    reads it only inside that same thread's ``commit()``.

    One stopwatch, two sinks: ``begin(step_num)`` ... ``open(phase)`` ...
    ``close()`` ... ``end()`` accumulate ``phases`` exactly as
    ``begin()``/``lap(phase)`` do, and ALSO open and close
    ``jax.profiler`` annotations on the calling thread: one
    ``engine/step`` (a ``StepTraceAnnotation``) per step and one
    ``engine/<phase>`` per opened phase, so a device trace shows on its
    own clock what the host was doing in every gap. With no profiler
    session an annotation is a flag test."""

    __slots__ = ("t0", "_last", "phases", "_phase", "_span", "_step")

    def __init__(self):
        self.t0 = 0.0
        self._last = 0.0
        self.phases: Dict[str, float] = {}
        self._phase: Optional[str] = None
        self._span = None       # the open engine/<phase> annotation
        self._step = None       # the open engine/step annotation

    def begin(self, step_num: Optional[int] = None) -> None:
        """Start a step. With ``step_num`` the step is also written to
        the profiler's trace as ``engine/step``, until ``end()``."""
        if self._step is not None:      # a step that raised: close it
            self.end()
        if step_num is not None:
            self._step = _enter(jax.profiler.StepTraceAnnotation(
                STEP_SPAN, step_num=step_num))
        self.t0 = self._last = time.perf_counter()
        self.phases.clear()

    def lap(self, phase: str) -> None:
        """Attribute the time since the previous lap (or ``begin``) to
        ``phase``; repeated laps of one phase accumulate."""
        now = time.perf_counter()
        self.phases[phase] = (self.phases.get(phase, 0.0)
                              + (now - self._last))
        self._last = now

    def open(self, phase: str) -> None:
        """Open ``phase`` (closing the one that is open): the name is
        known from here on, which an annotation needs."""
        if self._phase is not None:
            self.close()
        self._phase = phase
        self._span = _enter(jax.profiler.TraceAnnotation(
            SPAN_PREFIX + phase))

    def close(self) -> None:
        """Close the open phase: its annotation ends and the time since
        the previous boundary is lapped to it."""
        phase, span = self._phase, self._span
        if phase is None:
            return
        self._phase = self._span = None
        span.__exit__(None, None, None)
        self.lap(phase)

    def span(self, name: str):
        """An annotation-only span (not a phase: nothing is timed),
        ``engine/<phase>/<name>`` inside an open phase and
        ``engine/<name>`` outside: a context manager."""
        phase = self._phase
        return jax.profiler.TraceAnnotation(
            f"{SPAN_PREFIX}{phase}/{name}" if phase
            else SPAN_PREFIX + name)

    def end(self) -> None:
        """End the step on every path out of it (early returns and
        raises included): closes a phase left open and ``engine/step``."""
        self.close()
        step, self._step = self._step, None
        if step is not None:
            step.__exit__(None, None, None)

    def total(self) -> float:
        """Wall seconds from ``begin()`` to the last lap — equals the
        sum of the phase buckets by construction."""
        return self._last - self.t0


class StepProfiler:
    """Per-engine step-anatomy profiler. Construct disabled; the HTTP
    server (or a benchmark/test harness) calls ``enable()``. Hot sites guard
    on the single ``enabled`` attribute before touching the clock."""

    def __init__(self, engine: str):
        self.engine = engine
        self.enabled = False
        self.clock = PhaseClock()
        self.steps = 0
        self.recent: deque = deque(maxlen=_WINDOW)
        self._lock = threading.Lock()   # recent-window snapshot vs append
        self._m_phase: Dict[str, Any] = {}
        _PROFILERS[engine] = self       # latest engine under a label wins

    # ---- lifecycle -----------------------------------------------------
    def enable(self) -> None:
        self.enabled = True

    def disable(self) -> None:
        self.enabled = False

    def span(self, name: str):
        """``clock.span(name)`` while enabled, else a no-op context: the
        guard of an annotation-only site (one attribute read while off)."""
        return self.clock.span(name) if self.enabled else NO_SPAN

    # ---- the per-step commit (engine thread) ---------------------------
    def commit(self, active: int = 0, kv_len: int = 0,
               fr_seq: int = 0) -> None:
        """Fold one completed step's clock into the published state.
        Called ONLY from the engine thread, after the final lap."""
        clk = self.clock
        total = clk.total()
        if total <= 0.0 or not clk.phases:
            return
        for name, secs in clk.phases.items():
            m = self._m_phase.get(name)
            if m is None:
                m = self._m_phase[name] = _cat.SERVING_STEP_PHASE.labels(
                    engine=self.engine, phase=name)
            m.observe(secs)
        self.steps += 1
        rec = {"ms": total * 1e3,
               "phases": {k: v * 1e3 for k, v in clk.phases.items()},
               "active": int(active), "kv": int(kv_len),
               "fr_seq": int(fr_seq)}
        with self._lock:
            self.recent.append(rec)

    # ---- read side (any thread) ----------------------------------------
    def federated(self) -> Dict[str, float]:
        """The scalar worth carrying over /health into the router's
        cluster_* federation (stats()-shaped; see router._FEDERATED_STATS)."""
        with self._lock:
            last = self.recent[-1] if self.recent else None
        return {"profile_step_ms": float(last["ms"]) if last else 0.0}

    def payload(self, top_k: int = 5) -> dict:
        with self._lock:
            recent = list(self.recent)
        phases: Dict[str, dict] = {}
        total_ms = sum(r["ms"] for r in recent) or 1.0
        for name in PHASES:
            vals = sorted(r["phases"][name] for r in recent
                          if name in r["phases"])
            if not vals:
                continue
            s = sum(vals)
            phases[name] = {"p50_ms": _quantile(vals, 0.5),
                            "p99_ms": _quantile(vals, 0.99),
                            "mean_ms": s / len(vals),
                            "share": s / total_ms,
                            "count": len(vals)}
        step_vals = sorted(r["ms"] for r in recent)
        top = sorted(recent, key=lambda r: -r["ms"])[:max(int(top_k), 0)]
        return {
            "engine": self.engine,
            "enabled": self.enabled,
            "steps": self.steps,
            "window": len(recent),
            "step_ms": {"p50": _quantile(step_vals, 0.5),
                        "p99": _quantile(step_vals, 0.99),
                        "mean": (sum(step_vals) / len(step_vals)
                                 if step_vals else 0.0)},
            "phases": phases,
            "top_slowest": top,
        }


#: engine label → live profiler (latest registration wins, matching the
#: flight-recorder reporter's engine registry semantics)
_PROFILERS: Dict[str, StepProfiler] = {}


def get_profiler(engine: str) -> Optional[StepProfiler]:
    return _PROFILERS.get(engine)


def profile_payload(top_k: int = 5) -> dict:
    """The ``GET /profile`` document: every registered engine's anatomy.
    Engines that never committed a step are listed (enabled flag and
    zero counters) so the surface is discoverable before traffic."""
    return {
        "schema_version": 1,
        "engines": {name: prof.payload(top_k)
                    for name, prof in sorted(_PROFILERS.items())},
    }
