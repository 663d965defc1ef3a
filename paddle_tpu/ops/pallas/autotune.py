"""Kernel block-geometry autotune: staged search with a persistent
per-device COST TABLE.

Reference parity: paddle/phi/kernels/autotune/cache.h (AutoTuneCache:
per-algorithm hashmaps keyed by shape/dtype signatures, hit-rate stats) and
switch_autotune.cc (the run-once-then-cache switch). The TPU analog tunes
Pallas block geometry instead of cuDNN algorithms — and, since PR 7, runs
a TVM-style staged search instead of measure-once pick-from-candidates:

1. **cache stage** — a persisted winner for (kernel, signature, device
   kind) is validated against the current candidate space and returned
   without touching the device (traced calls inside jit can ONLY take
   this stage — they cannot time anything).
2. **generate stage** — the caller supplies a geometry space (block
   rows/cols, pipeline-depth style knobs) as candidate tuples; wider
   than the old hand-curated lists.
3. **prune stage** — candidates recorded as failed/infeasible in the
   cost table are dropped (an OOM-ing geometry is measured at most once
   per device, ever), then a roofline cost model (HBM bytes / peak
   FLOPs per device kind + per-grid-step overhead) ranks the rest and
   only the top ``max_measure`` survivors are timed.
4. **measure stage** — every survivor's outcome (ms, or the failure
   kind + message) is recorded in the per-signature cost TABLE, not
   just the winner, so later searches start from evidence.

The cache file (``.pd_autotune.json``, or ``PD_AUTOTUNE_CACHE``) persists
winners AND tables keyed by kernel → "signature @device_kind". Writes are
batched in memory and flushed write-temp-then-rename (concurrent
processes never read a torn file; last writer wins, which is fine —
entries are measurements of the same hardware) at sweep end, atexit, and
on incident dumps (the flight-recorder reporter flushes every tracked
writer before bundling).

``FLAGS_use_autotune`` (utils/flags.py) gates measurement exactly like
the reference's switch; with the flag off the caller's heuristic default
is used untouched. Sweeps are audited: each one logs through the
rank-aware logger and records an ``autotune.sweep`` flight-recorder
event, and the ``graph-cost-table`` pdlint rule cross-checks persisted
bytes/FLOPs estimates against the live analytical models
(``register_cost_model`` / ``analytical_cost``).
"""
from __future__ import annotations

import atexit
import json
import os
import time
from typing import (Any, Callable, Dict, List, Optional, Sequence, Set,
                    Tuple)

import jax

_DEFAULT_PATH = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))),
    ".pd_autotune.json")

#: default VMEM feasibility ceiling for cost-model pruning (bytes); a
#: candidate whose modeled working set exceeds it is recorded as
#: infeasible without ever being launched
VMEM_LIMIT = 16 * 1024 * 1024

#: modeled cost of one grid step (dispatch + pipeline bubble), in ms —
#: what separates two candidates with identical HBM traffic
GRID_STEP_MS = 2e-3


def cache_path() -> str:
    return os.environ.get("PD_AUTOTUNE_CACHE", _DEFAULT_PATH)


def _logger():
    from ...distributed.log_utils import get_logger

    return get_logger(name="paddle_tpu.ops.autotune")


# ---------------------------------------------------------------------------
# roofline device model
# ---------------------------------------------------------------------------

#: device-kind substring → (HBM bytes/s, peak bf16 FLOP/s): the repo's ONE
#: table of chip peaks. Matched against
#: jax's ``device_kind`` lowercased; first hit wins. Sources: Google Cloud
#: TPU documentation, "TPU v6e" / "TPU v5p" / "TPU v5e" / "TPU v4" system
#: architecture pages. The ``cpu`` row is a nominal host figure that only
#: ranks candidates in CPU tests — never a device metric.
_ROOFLINE_CAPS: List[Tuple[str, Tuple[float, float]]] = [
    ("v6e", (1.64e12, 918e12)),
    ("v5p", (2.765e12, 459e12)),
    ("v5", (8.19e11, 197e12)),      # v5e / "TPU v5 lite"
    ("v4", (1.228e12, 275e12)),
    ("cpu", (5e10, 1e11)),
]


def roofline_caps(device: Optional[str] = None) -> Tuple[float, float]:
    """(HBM bytes/s, peak bf16 FLOP/s) of ``device`` (default: the first
    jax device). A kind the table does not list is an error, not a
    default: a utilization against somebody else's peak is not a number."""
    kind = (device or device_kind()).lower()
    for sub, caps in _ROOFLINE_CAPS:
        if sub in kind:
            return caps
    raise ValueError(
        f"no peak bandwidth/FLOP rate on file for device kind {kind!r}; "
        "add it, with its source, to autotune._ROOFLINE_CAPS")


def roofline_ms(bytes_hbm: float, flops: float,
                device: Optional[str] = None, grid: int = 0) -> float:
    """Analytical lower bound for a kernel launch: the slower of the
    bandwidth and compute ceilings, plus modeled per-grid-step overhead
    (the term that actually separates block-geometry candidates — their
    HBM traffic is usually identical)."""
    bw, peak = roofline_caps(device)
    return (max(bytes_hbm / bw, flops / peak) * 1e3
            + int(grid) * GRID_STEP_MS)


# ---- per-kernel analytical cost models --------------------------------------
# fn(params: dict, choice: tuple) -> {"bytes":, "flops":, "vmem_bytes":,
# "grid":} (any subset). ``params`` is whatever the kernel recorded with
# the signature (shape ints + dtype string). The graph-cost-table pdlint
# rule replays these against persisted entries to catch model drift.

_COST_MODELS: Dict[str, Callable[[dict, tuple], dict]] = {}


def register_cost_model(kernel: str,
                        fn: Callable[[dict, tuple], dict]) -> None:
    _COST_MODELS[kernel] = fn


def analytical_cost(kernel: str, params: dict,
                    choice: Sequence[int]) -> Optional[dict]:
    """Replay the registered cost model; None when the kernel has no
    model (entries without estimates are exempt from the cross-check)."""
    fn = _COST_MODELS.get(kernel)
    if fn is None:
        return None
    return fn(dict(params), tuple(int(c) for c in choice))


# ---------------------------------------------------------------------------
# the persisted cost table
# ---------------------------------------------------------------------------

def _choice_key(choice: Sequence[int]) -> str:
    return ",".join(str(int(c)) for c in choice)


class AutotuneCache:
    """kernel → {signature → entry} with JSON persistence.

    Entry schema (older files carry only the first three keys — every
    reader treats the rest as optional):

    - ``choice`` / ``ms`` / ``measured_at`` — the winner.
    - ``params`` — the shape/dtype ints the signature was built from
      (what the cost-table lint replays the analytical model on).
    - ``est`` — the winner's analytical ``bytes``/``flops``/
      ``roofline_ms`` at record time.
    - ``table`` — per-candidate outcomes: ``{"<c0,c1>": {"ms": ...,
      "status": "ok"}}`` or ``{"status": "fail", "error": "..."}`` or
      ``{"status": "infeasible", "reason": "..."}``. Failed/infeasible
      geometries are pruned from every later search on this device.

    Writes batch in memory (``put``/``record_result`` mark dirty) and
    ``flush()`` persists write-temp-then-rename; sweeps flush at the
    end, plus atexit and incident dumps (``snapshot.flush_all_writers``
    tracks this object) — NOT per entry, which was O(n²) file I/O
    during a wide search.
    """

    def __init__(self, path: Optional[str] = None):
        self.path = path or cache_path()
        self._data: Dict[str, Dict[str, Any]] = {}
        self._loaded = False
        self._dirty = False

    def _load(self):
        if self._loaded:
            return
        self._loaded = True
        try:
            with open(self.path) as f:
                self._data = json.load(f)
        except FileNotFoundError:
            self._data = {}  # first run: empty table is the real state
        except (OSError, ValueError) as e:
            # a torn/corrupt cache must not kill the kernel path, but it
            # is a real fault worth a line — measurements will redo
            _logger().warning("autotune cache %s unreadable (%s: %s); "
                              "starting empty", self.path,
                              type(e).__name__, e)
            self._data = {}

    # ---- reads ---------------------------------------------------------
    def entry(self, kernel: str, key: str) -> Optional[dict]:
        self._load()
        return self._data.get(kernel, {}).get(key)

    def get(self, kernel: str, key: str):
        ent = self.entry(kernel, key)
        return None if ent is None else ent.get("choice")

    def failures(self, kernel: str, key: str) -> Set[Tuple[int, ...]]:
        """Geometries this device has already proven bad (fail or
        infeasible) — pruned from later sweeps instead of re-tried."""
        ent = self.entry(kernel, key) or {}
        out: Set[Tuple[int, ...]] = set()
        for ck, rec in (ent.get("table") or {}).items():
            if rec.get("status") in ("fail", "infeasible"):
                try:
                    out.add(tuple(int(p) for p in ck.split(",")))
                except ValueError:
                    continue  # hand-edited key: unmatchable, harmless
        return out

    def stats(self):
        self._load()
        return {k: len(v) for k, v in self._data.items()}

    # ---- writes (in-memory; flush() persists) --------------------------
    def _entry_for_write(self, kernel: str, key: str) -> dict:
        self._load()
        ent = self._data.setdefault(kernel, {}).setdefault(key, {})
        self._dirty = True
        return ent

    def record_result(self, kernel: str, key: str, choice: Sequence[int],
                      ms: Optional[float] = None,
                      error: Optional[BaseException] = None,
                      infeasible: Optional[str] = None):
        """One candidate's outcome into the cost table."""
        ent = self._entry_for_write(kernel, key)
        table = ent.setdefault("table", {})
        if error is not None:
            rec = {"status": "fail",
                   "error": f"{type(error).__name__}: {error}"[:200]}
        elif infeasible is not None:
            rec = {"status": "infeasible", "reason": infeasible[:200]}
        else:
            rec = {"status": "ok", "ms": round(float(ms), 4)}
        table[_choice_key(choice)] = rec

    def put(self, kernel: str, key: str, choice: Sequence[int], ms: float,
            params: Optional[dict] = None, est: Optional[dict] = None):
        """Record the winner (and optionally the shape params + the
        analytical estimate the graph-cost-table lint cross-checks)."""
        ent = self._entry_for_write(kernel, key)
        ent.update({"choice": [int(c) for c in choice],
                    "ms": round(float(ms), 4),
                    "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S")})
        if params is not None:
            ent["params"] = dict(params)
        if est is not None:
            ent["est"] = {k: (round(v, 6) if isinstance(v, float) else v)
                          for k, v in est.items()}

    def flush(self):
        """Persist if dirty: write-temp-then-rename (concurrent readers
        never see a torn file)."""
        if not self._dirty:
            return
        tmp = f"{self.path}.{os.getpid()}.tmp"
        try:
            with open(tmp, "w") as f:
                json.dump(self._data, f, indent=1, sort_keys=True)
            os.replace(tmp, self.path)
            self._dirty = False
        except OSError as e:
            _logger().warning("autotune cache flush to %s failed "
                              "(%s: %s)", self.path, type(e).__name__, e)
            try:
                os.unlink(tmp)
            except OSError:
                pass


_cache: Optional[AutotuneCache] = None
_ATEXIT_REGISTERED = False


def flush_cache() -> None:
    """Flush the live cache if any (atexit + incident hook target)."""
    if _cache is not None:
        _cache.flush()


def get_cache() -> AutotuneCache:
    global _cache, _ATEXIT_REGISTERED
    if _cache is None or _cache.path != cache_path():
        if _cache is not None:
            _cache.flush()  # path swap (tests) must not drop batched rows
        _cache = AutotuneCache()
        if not _ATEXIT_REGISTERED:
            _ATEXIT_REGISTERED = True
            atexit.register(flush_cache)
        try:
            # incident bundles flush every tracked writer first — a
            # crash mid-search must not lose the sweep's evidence
            from ...observability.snapshot import track_flushable

            track_flushable(_cache)
        except ImportError:  # pragma: no cover — minimal builds
            pass
    return _cache


def enabled() -> bool:
    from ...utils.flags import get_flags

    return bool(get_flags("FLAGS_use_autotune")["FLAGS_use_autotune"])


def device_kind() -> str:
    """Hardware identity baked into every cache key: block winners are a
    property of the chip generation (v5e vs v6e tile timings differ), and
    the cache file travels with the repo."""
    return jax.devices()[0].device_kind.replace(" ", "_")


def full_key(key: str) -> str:
    return f"{key} @{device_kind()}"


def _measure(fn: Callable[[], Any], reps: int = 3) -> float:
    out = fn()  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) * 1000 / reps


def _record_sweep(kernel: str, key: str, choice: Tuple[int, ...],
                  ms: float, measured: int, failed: int, pruned: int,
                  log: bool):
    """Audit one sweep: rank-aware log line + autotune.sweep event."""
    if log:
        _logger().info(
            "autotune[%s] %s -> %s (%.3f ms; measured=%d failed=%d "
            "pruned=%d)", kernel, key, choice, ms, measured, failed,
            pruned)
    from ...observability import flightrecorder as _frec

    rec = _frec.get_recorder()
    if rec.enabled:
        rec.record(_frec.EV_AUTOTUNE_SWEEP, kernel=kernel, key=key,
                   choice=list(choice), ms=round(ms, 4),
                   measured=measured, failed=failed, pruned=pruned)


def search(kernel: str, key: str, default: Tuple[int, ...],
           candidates: Sequence[Tuple[int, ...]],
           runner: Callable[[Tuple[int, ...]], Callable[[], Any]],
           can_measure: bool, *, params: Optional[dict] = None,
           cost_model: Optional[Callable[[tuple], dict]] = None,
           max_measure: Optional[int] = None,
           vmem_limit: int = VMEM_LIMIT,
           log: bool = True) -> Tuple[int, ...]:
    """Staged geometry search for (kernel, key) — see the module
    docstring for the stage walk-through.

    ``candidates`` is the generated space; ``cost_model(cfg)`` (optional)
    returns ``{"bytes", "flops", "vmem_bytes", "grid"}`` estimates used
    to (a) drop VMEM-infeasible geometries unlaunched, (b) rank the rest
    by roofline and keep only the ``max_measure`` most promising, and
    (c) persist the winner's estimate for the graph-cost-table lint.
    A sweep times each survivor via ``runner(cfg)()``; every outcome
    (including failures — the kind + message) lands in the cost table so
    OOM-ing geometries are never re-tried on this device.
    """
    if not enabled():
        return default  # the reference's switch: flag off = heuristic only
    key = full_key(key)
    cache = get_cache()
    hit = cache.get(kernel, key)
    if hit is not None:
        hit = tuple(hit)
        # a stale or hand-edited entry must not silently corrupt a kernel
        # launch (e.g. a block that no longer divides the row count)
        if not candidates or hit in {tuple(c) for c in candidates}:
            return hit
    if not can_measure:
        return default
    cands = list(dict.fromkeys(tuple(c) for c in candidates))
    known_bad = cache.failures(kernel, key)
    n_known_bad = sum(1 for c in cands if c in known_bad)
    cands = [c for c in cands if c not in known_bad]
    pruned = n_known_bad
    if cost_model is not None:
        feasible = []
        for c in cands:
            est = cost_model(c) or {}
            if est.get("vmem_bytes", 0) > vmem_limit:
                cache.record_result(
                    kernel, key, c,
                    infeasible=f"vmem {est['vmem_bytes']} > {vmem_limit}")
                pruned += 1
                continue
            score = roofline_ms(est.get("bytes", 0), est.get("flops", 0),
                                grid=est.get("grid", 0))
            feasible.append((score, c))
        feasible.sort(key=lambda t: t[0])
        keep = max_measure if max_measure is not None else 8
        pruned += max(len(feasible) - keep, 0)
        cands = [c for _, c in feasible[:keep]]
    elif max_measure is not None:
        pruned += max(len(cands) - max_measure, 0)
        cands = cands[:max_measure]
    best, best_ms = default, float("inf")
    failed = 0
    for cfg in cands:
        try:
            ms = _measure(runner(cfg))
        except Exception as e:
            # a candidate that OOMs VMEM loses the sweep — but its
            # failure is EVIDENCE: recorded so no later search on this
            # device launches the same bad geometry again
            failed += 1
            cache.record_result(kernel, key, cfg, error=e)
            _logger().debug("autotune[%s] %s candidate %s failed "
                            "(%s: %s)", kernel, key, cfg,
                            type(e).__name__, e)
            continue
        cache.record_result(kernel, key, cfg, ms=ms)
        if ms < best_ms:
            best, best_ms = tuple(cfg), ms
    if best_ms == float("inf"):
        cache.flush()  # failures are worth persisting even with no winner
        return default
    est = None
    if cost_model is not None:
        e = cost_model(best)
        est = {"bytes": int(e.get("bytes", 0)),
               "flops": int(e.get("flops", 0)),
               "roofline_ms": roofline_ms(e.get("bytes", 0),
                                          e.get("flops", 0),
                                          grid=e.get("grid", 0))}
    cache.put(kernel, key, best, best_ms, params=params, est=est)
    cache.flush()
    _record_sweep(kernel, key, best, best_ms,
                  measured=len(cands) - failed, failed=failed,
                  pruned=pruned, log=log)
    return best


def pick(kernel: str, key: str, default: Tuple[int, ...],
         candidates: Sequence[Tuple[int, ...]],
         runner: Callable[[Tuple[int, ...]], Callable[[], Any]],
         can_measure: bool, log: bool = True,
         params: Optional[dict] = None) -> Tuple[int, ...]:
    """Resolve a block geometry for (kernel, key): ``search`` without a
    cost model (every candidate is measured) — the compatibility surface
    the measure-once era's callers keep using.

    Order: persisted cache hit → staged sweep (only when the flag is on
    AND ``can_measure`` — the caller passes False under tracing, off-TPU,
    or interpret mode) → ``default`` (the caller's heuristic).
    """
    return search(kernel, key, default, candidates, runner, can_measure,
                  params=params, log=log)


def is_concrete(*arrays) -> bool:
    """True when none of the arrays are tracers (a timed eager sweep is
    legal). Inside jit the kernel must consult only the persisted cache."""
    return not any(isinstance(a, jax.core.Tracer) for a in arrays)
