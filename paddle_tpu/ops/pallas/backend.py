"""The Pallas layer's one backend probe, and the record of which
implementation each call site took.

Every kernel in this package asks the same three questions — which
platform is this lowering for, must the kernel run interpreted, may a
kernel run at all — and answers them here instead of probing
``jax.devices()`` itself:

- :func:`platform` is JAX's default backend, unless the caller chose a
  lowering target (:func:`lowering_target`). A backend that fails to
  initialise RAISES from here; it never reads as "not a TPU", so a
  broken accelerator cannot turn into interpret mode with exit code 0.
- :func:`interpret_mode` is true exactly off-TPU (the CPU tests): on a TPU
  platform no kernel is ever interpreted.
- :func:`lowering_target` lets a caller compile a kernel for a DESCRIBED
  TPU (``jax.experimental.topologies``) while the ambient backend is the
  CPU — without it such a compile would silently lower the interpreter.
- :func:`composites` switches every kernel gate off for the calling
  thread, so the same model runs on the XLA composites: the reference a
  chip run compares the kernel path with.

Call sites report the implementation they chose through :func:`took`
(at trace time, so the cost is per compile, not per step). A gate that
refuses a shape on a TPU is logged once with its reason, and
:func:`paths` hands the counts to ``chip_smoke.py``, which fails when a
main-path site ran interpreted or on a reference branch. A caller that
wants the decisions of ONE trace (a prefill step, for the engine's
``serving_prefill_attention_total``) wraps the traced call in
:func:`recording`.
"""
from __future__ import annotations

import contextlib
import threading
from collections import Counter
from typing import Dict, Iterator, List, Optional, Tuple

import jax

PALLAS = "pallas"
INTERPRET = "pallas-interpret"
XLA = "xla"

_tls = threading.local()
_lock = threading.Lock()
_paths: Counter = Counter()      # (site, impl) -> times chosen
_logged = set()                  # (site, reason) refusals already logged


def platform() -> str:
    """Platform kernels are lowered for: the caller's lowering target if
    one is active on this thread, else JAX's default backend."""
    return getattr(_tls, "target", None) or jax.default_backend()


def on_tpu() -> bool:
    return platform() == "tpu"


def interpret_mode() -> bool:
    """What every ``pallas_call`` here passes as ``interpret=``: true
    exactly on platforms Mosaic cannot compile for."""
    return not on_tpu()


def kernels_enabled() -> bool:
    return not getattr(_tls, "composites", False)


def _auto_partitioned() -> bool:
    """Is this trace headed for GSPMD partitioning over several devices?
    Mosaic kernels cannot be partitioned automatically (the lowering
    raises NotImplementedError and asks for a shard_map), so under a
    hybrid-parallel topology only code inside a shard_map — where named
    axes are bound — may run one."""
    from ...distributed.topology import get_hybrid_communicate_group

    hcg = get_hybrid_communicate_group()
    return (hcg is not None and hcg.mesh.size > 1
            and not jax.core.trace_ctx.axis_env.axis_sizes)


@contextlib.contextmanager
def lowering_target(name: str) -> Iterator[None]:
    """Lower kernels traced on this thread for platform ``name`` whatever
    the ambient backend. Traces are cached by jit per function: wrap the
    kernel call in a fresh ``jax.jit`` inside the block."""
    prev = getattr(_tls, "target", None)
    _tls.target = name
    try:
        yield
    finally:
        _tls.target = prev


@contextlib.contextmanager
def composites() -> Iterator[None]:
    """Trace on this thread with every Pallas gate refusing (reason
    ``composites``): the XLA reference path of the same model. As with
    :func:`lowering_target`, trace through a fresh ``jax.jit``."""
    prev = getattr(_tls, "composites", False)
    _tls.composites = True
    try:
        yield
    finally:
        _tls.composites = prev


@contextlib.contextmanager
def recording() -> Iterator[List[Tuple[str, str]]]:
    """The ``(site, impl)`` decisions :func:`took` records on this thread
    inside the block, in order. Decisions are made while a program is
    TRACED: the list stays empty around a call that runs a program jit
    already holds."""
    prev = getattr(_tls, "recording", None)
    _tls.recording = taken = []
    try:
        yield taken
    finally:
        _tls.recording = prev


def took(site: str, impl: str, reason: Optional[str] = None) -> None:
    """Record that ``site`` chose ``impl``. A refusal (``impl`` is
    :data:`XLA` with a ``reason``) while targeting a TPU is logged once
    per (site, reason) — visible, not silent."""
    taken = getattr(_tls, "recording", None)
    if taken is not None:
        taken.append((site, impl))
    with _lock:
        _paths[(site, impl)] += 1
        first = reason is not None and (site, reason) not in _logged
        if first:
            _logged.add((site, reason))
    if first and on_tpu() and kernels_enabled():
        from ...distributed.log_utils import get_logger

        get_logger(name="paddle_tpu.ops.pallas").warning(
            "%s: Pallas kernel refused (%s); running the XLA composite",
            site, reason)


def gate(site: str, shape_reason: Optional[str] = None,
         interpret: bool = True) -> bool:
    """The decision every ``supported()`` ends in, recorded: may ``site``
    run its Pallas kernel here? Refusals, in order: the caller asked for
    the composites; the platform cannot compile the kernel and the
    caller did not ask for interpret mode (``interpret`` is honoured
    only off-TPU — tests); the program will be partitioned by GSPMD,
    which Mosaic kernels cannot be (interpreted ones are plain XLA ops
    and can); the kernel's own ``shape_reason``."""
    if not kernels_enabled():
        reason = "composites"
    elif not on_tpu() and not interpret:
        reason = f"platform {platform()}"
    elif on_tpu() and _auto_partitioned():
        reason = "GSPMD-partitioned program outside a shard_map"
    else:
        reason = shape_reason
    if reason is None:
        took(site, INTERPRET if interpret_mode() else PALLAS)
    else:
        took(site, XLA, reason)
    return reason is None


def paths() -> Dict[str, Dict[str, int]]:
    """{site: {impl: count}} since the last :func:`reset_paths`."""
    out: Dict[str, Dict[str, int]] = {}
    with _lock:
        for (site, impl), n in sorted(_paths.items()):
            out.setdefault(site, {})[impl] = n
    return out


def refusals() -> Dict[str, list]:
    """{site: [reason, ...]} for every gate refusal recorded."""
    out: Dict[str, list] = {}
    with _lock:
        for site, reason in sorted(_logged):
            out.setdefault(site, []).append(reason)
    return out


def reset_paths() -> None:
    with _lock:
        _paths.clear()
        _logged.clear()
