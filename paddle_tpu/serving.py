"""Continuous batching over the paged KV cache.

Reference parity: the serving configuration the reference builds around
``block_multi_head_attention``
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu) —
a fixed pool of sequence slots with block tables, per-row lengths, and
mid-flight admission (the vLLM pattern).

TPU-native design: everything on-device is FIXED SHAPE — a pool of
``max_batch`` slots, each owning a contiguous run of KV pages; every
``step()`` decodes ONE token for ALL slots in a single jitted dispatch
(inactive slots compute throwaway rows at length 0 — shape stability is
worth more than skipping them on a systolic machine). The host-side engine
does only bookkeeping: admit queued requests into free slots (bucketed
jitted prefill + page scatter), collect sampled tokens, retire finished
rows, immediately refill their slots. Ragged-ness is first-class because
``paged_cached_attention`` RoPEs and writes at per-row positions.
"""
from __future__ import annotations

import math
import os
import time
import zlib
from collections import deque
from typing import Deque, Dict, List, NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .chaos import inject as _chaos
from .observability import catalog as _metrics
from .observability import flightrecorder as _frec
from .observability import kvatlas as _kvatlas
from .observability import perf as _perf
from .observability import sentinel as _sentinel
from .observability import tracing as _tracing
from .tensor_class import Tensor, unwrap
from .framework import random as _random
from .jit import build_events as _build_events
from .generation import (_get_prefill_step, _get_select_decode,
                         _get_select_decode_rows, _get_spec_decode,
                         _memoized_step, traced_attention_impl)


#: default priority class — lower value is MORE important. 0 is the
#: interactive tier, 1 the default, 2+ batch/background traffic.
PRIORITY_DEFAULT = 1


#: schema version stamped on every handoff / preemption / migration
#: bundle. Bump it whenever the bundle layout changes — an engine only
#: admits bundles speaking its own version (version skew between a
#: prefill tier and a decode tier mid-deploy must fail typed, not
#: scatter mis-shaped KV).
HANDOFF_SCHEMA_VERSION = 2


class HandoffCorrupt(RuntimeError):
    """A handoff / preemption / migration bundle failed its integrity
    check: checksum mismatch (bit-rot or a corrupted transport), schema
    version skew (mixed-version tiers), or an internally inconsistent
    payload. A RuntimeError (not ValueError) on purpose: the HTTP layer
    maps it to a 5xx, which the cluster router treats as retryable — a
    fresh prefill/migration produces a fresh bundle, so the fault is
    absorbable upstream and must never be pinned on the client."""


def _bundle_digest(bundle: dict) -> int:
    """CRC32 over a bundle's leaves in deterministic (sorted-key) order.
    Numpy leaves hash dtype+shape+raw bytes; scalars hash their repr;
    the top-level ``checksum`` field is excluded (it holds the digest)."""
    crc = 0

    def upd(b: bytes):
        nonlocal crc
        crc = zlib.crc32(b, crc)

    def walk(path, o):
        if isinstance(o, np.ndarray):
            upd(f"{path}:{o.dtype.str}:{o.shape}".encode())
            upd(np.ascontiguousarray(o).tobytes())
        elif isinstance(o, dict):
            for k in sorted(o):
                if path == "" and k == "checksum":
                    continue
                walk(f"{path}/{k}", o[k])
        elif isinstance(o, (list, tuple)):
            for i, x in enumerate(o):
                walk(f"{path}[{i}]", x)
        else:
            upd(f"{path}={o!r}".encode())

    walk("", bundle)
    return crc


def seal_bundle(bundle: dict) -> dict:
    """Stamp ``version`` + ``checksum`` onto a bundle (in place). Every
    producer (export_prefill, export_slot, the preemption evictor) seals;
    every consumer verifies with :func:`verify_bundle`."""
    bundle["version"] = HANDOFF_SCHEMA_VERSION
    bundle.pop("checksum", None)
    bundle["checksum"] = _bundle_digest(bundle)
    return bundle


def verify_bundle(bundle, kind: Optional[str] = None) -> dict:
    """Integrity gate in front of every bundle admission: schema version,
    checksum, and (when given) the bundle ``kind``. Raises
    :class:`HandoffCorrupt` — typed, retryable — instead of letting a
    bit-flipped or version-skewed bundle scatter garbage into the KV
    pool."""
    if not isinstance(bundle, dict):
        raise HandoffCorrupt(
            f"bundle is a {type(bundle).__name__}, not a dict")
    v = bundle.get("version")
    if v != HANDOFF_SCHEMA_VERSION:
        raise HandoffCorrupt(
            f"bundle schema version skew: bundle says {v!r}, this engine "
            f"speaks {HANDOFF_SCHEMA_VERSION} — prefill and decode tiers "
            "must run the same bundle schema")
    if kind is not None and bundle.get("kind", "prefill") != kind:
        raise HandoffCorrupt(
            f"bundle kind {bundle.get('kind')!r} where {kind!r} was "
            "expected")
    got = bundle.get("checksum")
    if got is None:
        raise HandoffCorrupt("bundle carries no checksum")
    want = _bundle_digest(bundle)
    if int(got) != want:
        raise HandoffCorrupt(
            f"bundle checksum mismatch (stored {int(got):#010x}, "
            f"computed {want:#010x}) — corrupted in transport or host "
            "memory; discard and re-export")
    return bundle


class QueueFull(RuntimeError):
    """Typed admission rejection: the bounded queue (``max_queue``) is at
    capacity and no slot is free. The HTTP front-end maps it to
    ``429 Too Many Requests`` + ``Retry-After``; the cluster router
    treats a worker's 429 as placement feedback (skip the worker, try
    another) rather than a failover. ``retry_after_s`` is computed from
    the engine's queue depth and observed drain rate (see
    ``_retry_after_estimate``), not a constant."""

    def __init__(self, engine: str, depth: int, max_queue: int,
                 retry_after_s: float = 1.0):
        super().__init__(
            f"{engine} engine admission queue is full "
            f"({depth}/{max_queue} queued, no free slot); retry later")
        self.retry_after_s = float(retry_after_s)


class DeadlineExceeded(RuntimeError):
    """Typed end-to-end deadline rejection: the request's SLO budget is
    already spent — it was submitted with no remaining budget, or it
    expired while queued (the admission loop sheds it BEFORE it can take
    a slot, so the engine never burns a prefill on tokens nobody can
    use). The HTTP front-end maps it to ``504 Gateway Timeout`` with
    ``{"code": "deadline_exceeded"}``; the cluster router forwards a
    worker's deadline-504 verbatim (the deadline is global — another
    replica cannot un-expire it, so it must never be retried)."""

    def __init__(self, engine: str, miss_ms: Optional[float] = None,
                 rid: Optional[int] = None):
        miss = (f" (deadline missed by {miss_ms:.0f}ms)"
                if miss_ms is not None else "")
        super().__init__(
            f"{engine} engine request deadline exceeded{miss}; "
            "the SLO budget was spent before decoding could start")
        self.miss_ms = miss_ms
        self.rid = rid


def _page_tiles(buf, page_size):
    """[n_tokens, hk, D] dense rows -> [hk, n_pages, page_size, D] page
    tiles (the pool layout) — the ONE buffer-to-pages transform, shared by
    the admission scatter and the prefix-cache suffix scatter."""
    n_pages = buf.shape[0] // page_size
    hk, d = buf.shape[1], buf.shape[2]
    return jnp.moveaxis(buf.reshape(n_pages, page_size, hk, d), 2, 0)


class _Request:
    __slots__ = ("rid", "ids", "max_new_tokens", "tokens", "slot", "sampling",
                 "on_token", "on_token_arity", "pixel_values",
                 "stop_token_ids", "logprobs", "want_logprobs",
                 "encoder_input", "seed_ids", "t_enqueue", "t_admit",
                 "t_last", "span", "queue_span", "handoff",
                 "priority", "deadline", "resume", "n_preempted",
                 "on_shed", "spec_rounds", "spec_accepted", "ext_id",
                 "dispatches", "audit")

    def __init__(self, rid, ids, max_new_tokens, sampling=None,
                 on_token=None, pixel_values=None, stop_token_ids=None,
                 want_logprobs=False, priority=None, slo_ms=None,
                 request_id=None):
        self.rid = rid
        # the CALLER's request identity (the cluster router's request_id
        # header/body field) — what the deathnote names, so poison blame
        # correlates across workers and retries; engine rids are
        # process-local and reset on restart
        self.ext_id = None if request_id is None else str(request_id)
        self.ids = np.asarray(ids).reshape(-1)
        self.max_new_tokens = int(max_new_tokens)
        self.tokens: List[int] = []
        self.slot = -1
        # latency clock: submission -> admission (queue wait), submission
        # -> first token (TTFT), token -> token (inter-token)
        self.t_enqueue = time.perf_counter()
        self.t_admit = None
        self.t_last = None
        # request-scoped tracing: root span + its queue-wait child, both
        # None while tracing is disabled (the engine's guarded fast path)
        self.span = None
        self.queue_span = None
        self.sampling = sampling  # (do_sample, temperature, top_k, top_p) or None
        self.on_token = on_token  # streaming callback (rid, token, done)
        self.pixel_values = pixel_values  # multimodal prompt (LLaVA)
        # per-request stop set — ADDITIVE to the engine eos (OpenAI
        # "stop" semantics: extra stop sequences never disable
        # end-of-sequence termination)
        self.stop_token_ids = (frozenset(int(s) for s in stop_token_ids)
                               if stop_token_ids else None)
        # chosen-token logprobs accumulate ONLY when asked — a retention
        # window of full float lists nobody wants would dominate memory
        self.want_logprobs = bool(want_logprobs)
        self.logprobs: List[float] = []
        self.encoder_input = None   # Seq2SeqBatchEngine payload
        self.seed_ids = None        # Seq2SeqBatchEngine decoder prompt
        self.handoff = None         # prefilled-KV bundle (disaggregated tier)
        # SLO-aware scheduling: priority class (lower = more important)
        # and an absolute deadline derived from the per-request SLO —
        # the admission queue orders on (aged priority, deadline, rid)
        self.priority = PRIORITY_DEFAULT if priority is None else int(priority)
        self.deadline = (self.t_enqueue + float(slo_ms) / 1000.0
                         if slo_ms is not None else math.inf)
        self.resume = None          # host-side KV bundle after a preemption
        self.n_preempted = 0
        # speculative-decode observability: verify rounds this request
        # rode and draft tokens the target accepted for it (the span
        # attributes _trace_end stamps at retirement)
        self.spec_rounds = 0
        self.spec_accepted = 0
        # fused dispatches this request rode (per-request cost
        # accounting: the usage block's dispatches / tokens-per-dispatch)
        self.dispatches = 0
        # correctness-sentinel mark: None (unaudited), "shadow" (rate-
        # sampled) or "ondemand" (X-Audit forced) — set at admission,
        # carried through preemption/migration, consumed at retirement
        self.audit = None
        # shed notification: the front-end's hook for learning that a
        # QUEUED request was dropped (deadline expired / displaced by a
        # more important arrival) — without it an HTTP submission would
        # wait forever on a request the engine silently let go
        self.on_shed = None         # callback (rid, info_dict) or None
        # streaming callbacks may take (rid, tok, done) or a 4th logprob
        # arg; arity detected once at admission by counting REQUIRED
        # positional parameters only (a defaulted 4th param keeps the
        # 3-arg call — the logprob must never clobber a closure default;
        # *args opts into the 4-arg form)
        self.on_token_arity = 3
        if on_token is not None:
            import inspect

            try:
                required, varargs = 0, False
                for prm in inspect.signature(on_token).parameters.values():
                    if prm.kind in (prm.POSITIONAL_ONLY,
                                    prm.POSITIONAL_OR_KEYWORD):
                        if prm.default is prm.empty:
                            required += 1
                    elif prm.kind == prm.VAR_POSITIONAL:
                        varargs = True
                if varargs or required >= 4:
                    self.on_token_arity = 4
            except (TypeError, ValueError):
                pass


_REASON_KEEP = 4096  # finish-reason retention window (see step())


class _RequestBookkeeping:
    """Queued/active cancel scanning, bounded finish-reason retention,
    and the unified counters/metrics/stats() layer — the request-
    accounting block BOTH engines share (decoder-only and seq2seq).
    Subclasses provide _slots/_lengths/_admit and max_batch, and call
    _init_bookkeeping() from __init__."""

    # decoder-only feature, but a shared stats() key: the two hand-copied
    # stats() dicts had already drifted (the seq2seq copy lacked it)
    prefix_pages_reused = 0

    # decode-step spans are SAMPLED: the request's first token always
    # (every trace shows at least one decode child) and every Nth after —
    # a full-length request traces O(tokens / N) spans, not O(tokens)
    trace_decode_every = 16

    # starvation bound for priority admission: a queued request's
    # effective class improves by one per aging_s waited, so any request
    # is admitted within (priority * aging_s) of a continuous
    # higher-priority stream. 0 disables aging (strict classes).
    aging_s = 0.0

    # class defaults so stats() works on engines that never shed
    # (seq2seq has no deadline surface at all)
    _n_shed = 0
    _n_deadline_misses = 0
    # OOM-degrade counter (decoder-only path; class default keeps the
    # stats() key stable for seq2seq)
    _n_degraded = 0

    # SLO-outcome counters: finished requests that carried an slo_ms,
    # split by whether they retired inside it — the goodput-under-SLO
    # signal the slo_goodput_burn alert burns against (class defaults
    # so stats() works on engines that never see an SLO)
    _n_slo_good = 0
    _n_slo_late = 0

    # speculative-decode counters: class defaults so stats() works on
    # engines that never speculate (seq2seq, spec-off decoder engines)
    _n_spec_steps = 0        # multi-token verify dispatches
    _n_spec_emitted = 0      # tokens retired by spec dispatches
    _n_spec_accepted = 0     # draft tokens the target accepted
    _n_spec_slot_rounds = 0  # (active slot, spec dispatch) pairs

    # pre-dispatch blame record (supervisor.Deathnote) — None outside
    # supervised cluster workers, and the guard helpers never run then
    deathnote = None

    def _init_bookkeeping(self, engine: str):
        """One init for queue/finish state, lifetime counters, and the
        registry children (bound once here — no per-token label lookups
        on the decode hot path)."""
        self._engine_label = engine
        self._next_rid = 0
        # graceful OOM degradation: the engine's ADMISSION budget. Starts
        # at max_batch and durably SHRINKS (floor 1) every time an XLA
        # OOM is caught during admission/step — the engine sheds the
        # triggering request typed and keeps serving at the reduced
        # occupancy instead of dying (sched.degrade)
        self.max_active_slots = int(getattr(self, "max_batch", 0) or 0)
        self._queue: List[_Request] = []
        self._finished: Dict[int, np.ndarray] = {}
        # finish reasons are kept for the last _REASON_KEEP requests only
        # (the front-end reads right after the done event; an unbounded
        # dict would grow with lifetime request count)
        self._finished_reason: Dict[int, str] = {}
        self._finished_logprobs: Dict[int, list] = {}
        # per-request usage (the completion response's cost-accounting
        # block) — same retention window as the finish reasons
        self._finished_usage: Dict[int, dict] = {}
        # deque: retirement trims from the FRONT every finish/cancel —
        # list.pop(0) would be O(window) per retired request at high
        # churn once the window is full
        self._reason_order: Deque[int] = deque()
        self._n_requests = 0
        self._n_finished = 0
        self._n_cancelled = 0
        self._n_rejected = 0
        self._n_preempted = 0
        self._n_migrated_out = 0
        self._n_migrated_in = 0
        self._n_tokens = 0
        self._n_steps = 0
        self._m_queue_wait = _metrics.SERVING_QUEUE_WAIT.labels(engine=engine)
        self._m_ttft = _metrics.SERVING_TTFT.labels(engine=engine)
        self._m_inter = _metrics.SERVING_INTER_TOKEN.labels(engine=engine)
        self._m_prefill = _metrics.SERVING_PREFILL.labels(engine=engine)
        self._m_step = _metrics.SERVING_DECODE_STEP.labels(engine=engine)
        self._m_tokens = _metrics.SERVING_TOKENS.labels(engine=engine)
        self._m_req_admitted = _metrics.SERVING_REQUESTS.labels(
            engine=engine, event="admitted")
        self._m_req_finished = _metrics.SERVING_REQUESTS.labels(
            engine=engine, event="finished")
        self._m_req_cancelled = _metrics.SERVING_REQUESTS.labels(
            engine=engine, event="cancelled")
        self._m_req_rejected = _metrics.SERVING_REQUESTS.labels(
            engine=engine, event="rejected")
        self._m_req_shed = _metrics.SERVING_REQUESTS.labels(
            engine=engine, event="shed")
        self._m_slo_good = _metrics.SERVING_SLO_OUTCOMES.labels(
            engine=engine, outcome="good")
        self._m_slo_late = _metrics.SERVING_SLO_OUTCOMES.labels(
            engine=engine, outcome="late")
        self._m_deadline = _metrics.SERVING_DEADLINE_MISSES.labels(
            engine=engine)
        self._m_sched_shed = _metrics.SERVING_SCHED.labels(
            engine=engine, decision="shed")
        self._m_active = _metrics.SERVING_ACTIVE_SLOTS.labels(engine=engine)
        self._m_depth = _metrics.SERVING_QUEUE_DEPTH.labels(engine=engine)
        # step-anatomy profiler: constructed disabled (guarded fast path
        # — every hot site checks prof.enabled first); the HTTP server
        # or a bench harness enables it
        self.profiler = _perf.StepProfiler(engine)
        # KV & memory atlas: same guarded-fast-path contract. This
        # degenerate (unpaged) instance keeps every surface total;
        # engines with a paged pool replace it with a configured one
        self.kvatlas = _kvatlas.KvAtlas(
            engine, max_batch=int(getattr(self, "max_batch", 0) or 0))
        # correctness sentinel: same guarded-fast-path contract (one
        # attribute read at admission/retirement when off). Engines whose
        # decode the reference replay can reproduce mark it auditable
        self.sentinel = _sentinel.CorrectnessSentinel(engine, self)
        # overload estimators, both engine-thread-only: the FLOOR of
        # admission->first-token (best case ever observed — a request
        # whose remaining budget is below even that is PROVABLY
        # unmeetable; a mean would mis-shed behind cold-compile
        # outliers) and the gap between request finishes (the drain
        # rate behind the computed Retry-After)
        self._ttft_admit_floor: Optional[float] = None
        self._ttft_admit_n = 0   # the floor arms only past a few samples
        self._finish_interval_ewma: Optional[float] = None
        self._t_last_finish: Optional[float] = None

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slots)

    # ---- priority admission (SLO-aware scheduling) ----------------------
    def _sched_key(self, req: _Request, now: float):
        """Admission order: (aged priority class, deadline, rid). Aging
        subtracts one class per aging_s waited (starvation bound);
        within a class the earliest SLO deadline wins (EDF), and rid
        keeps same-class same-deadline traffic FIFO. With every request
        at the default priority and no SLOs this IS pop(0)."""
        eff = req.priority
        if self.aging_s > 0:
            eff -= int((now - req.t_enqueue) / self.aging_s)
        return (eff, req.deadline, req.rid)

    def _peek_next(self, now: float) -> Optional[_Request]:
        if not self._queue:
            return None
        return min(self._queue, key=lambda r: self._sched_key(r, now))

    def _pop_next(self, now: float) -> _Request:
        req = self._peek_next(now)
        self._queue.remove(req)
        return req

    def stats(self) -> dict:
        """Engine observability: lifetime counters + current occupancy
        (the serving front-end's /health payload) — ONE implementation
        for both engines, backed by the same counters the registry
        exposes, so the payloads can't drift. Reading it also refreshes
        the occupancy gauges: /health and /metrics see one snapshot."""
        active = self.num_active
        queued = len(self._queue)
        self._m_active.set(active)
        self._m_depth.set(queued)
        return {
            "requests_admitted": self._n_requests,
            "requests_finished": self._n_finished,
            "requests_cancelled": self._n_cancelled,
            "requests_rejected": self._n_rejected,
            "requests_preempted": self._n_preempted,
            "requests_shed": self._n_shed,
            "deadline_misses": self._n_deadline_misses,
            "requests_migrated_out": self._n_migrated_out,
            "requests_migrated_in": self._n_migrated_in,
            "requests_slo_good": self._n_slo_good,
            "requests_slo_late": self._n_slo_late,
            "requests_active": active,
            "requests_queued": queued,
            "requests_prefilling": len(getattr(self, "_chunking", ())),
            "decode_steps": self._n_steps,
            "tokens_generated": self._n_tokens,
            "slot_utilization": (active / self.max_batch
                                 if self.max_batch else 0.0),
            # the LIVE admission budget: == max_batch until an OOM
            # degrade shrank it (sched.degrade); /health surfaces it so
            # a balancer sees the reduced capacity, not just the symptom
            "max_active_slots": self.max_active_slots,
            "requests_degraded": self._n_degraded,
            "prefix_pages_reused": self.prefix_pages_reused,
            # speculative decode: tokens retired per slot per dispatch is
            # THE speculation health number (1.0 = no speedup; the n-gram
            # drafter earns its keep above it). All zeros when spec is
            # off — the keys stay stable for dashboards either way.
            "spec_dispatches": self._n_spec_steps,
            "spec_emitted_tokens": self._n_spec_emitted,
            "spec_accepted_tokens": self._n_spec_accepted,
            "accepted_tokens_per_dispatch": (
                self._n_spec_emitted / self._n_spec_slot_rounds
                if self._n_spec_slot_rounds else 0.0),
            # step-anatomy profiler scalars (0.0 until enabled + traffic)
            # — the router federates these as cluster_* series, so a
            # perf regression on one replica is visible tier-wide
            **self.profiler.federated(),
            # KV-atlas scalars ride the same transport: /health -> pool
            # probe cache -> router TSDB collector (cluster_kv_*)
            **self.kvatlas.federated(),
            # correctness-sentinel verdict counters (cluster_audit_*)
            **self.sentinel.federated(),
        }

    def _count_finished(self, req: "_Request", slo: bool = True):
        """Retirement accounting shared by every finish site: the
        lifetime counter plus — when the request carried an slo_ms —
        the good/late SLO outcome (``slo=False`` skips the SLO split
        for error retirements, which are neither)."""
        self._n_finished += 1
        self._m_req_finished.inc()
        self._record_usage(req)
        sn = self.sentinel
        if sn.enabled and req.audit is not None:
            # snapshot + enqueue only (budget gates are attribute
            # reads); the replay itself runs on the audit worker
            sn.on_finish(req, self._finished_reason.get(req.rid))
        if slo and req.deadline != math.inf:
            if time.perf_counter() <= req.deadline:
                self._n_slo_good += 1
                self._m_slo_good.inc()
            else:
                self._n_slo_late += 1
                self._m_slo_late.inc()

    def debug_state(self) -> dict:
        """Host-side engine state for incident bundles and /debug/dump:
        the slot table (who holds what, how far along), the queue, and
        the stats() snapshot — everything an operator needs to answer
        "what was the engine doing when it died" without a debugger."""
        slots = []
        for s, r in enumerate(self._slots):
            if r is None:
                slots.append(None)
                continue
            row = {
                "rid": r.rid,
                "prompt_tokens": int(r.ids.size),
                "generated": len(r.tokens),
                "max_new_tokens": r.max_new_tokens,
                "slot": s,
                "priority": r.priority,
            }
            # atlas ledger columns (page/byte footprint + prefix reuse
            # depth); computed from the row's lengths when disabled
            row.update(self.kvatlas.slot_info(
                s, int(r.ids.size) + len(r.tokens)))
            slots.append(row)
        return {
            "engine": self._engine_label,
            "max_batch": self.max_batch,
            "max_active_slots": self.max_active_slots,
            "slots": slots,
            "queue": [r.rid for r in self._queue],
            "prefilling": {
                s: {"rid": st.req.rid, "pos": st.pos,
                    "prompt_tokens": int(st.req.ids.size)}
                for s, st in getattr(self, "_chunking", {}).items()},
            "poisoned": bool(getattr(self, "_poisoned", False)),
            "prefix_pages_reused": self.prefix_pages_reused,
            "stats": self.stats(),
        }

    # ---- flight-recorder hooks (shared by both engines) ----------------
    # every hook guards on RECORDER.enabled FIRST — the disabled decode
    # hot path pays one attribute read, exactly like the tracer's

    def _fr_submit(self, req: _Request):
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SUBMIT, rid=req.rid,
                       engine=self._engine_label,
                       prompt_tokens=int(req.ids.size),
                       max_new_tokens=req.max_new_tokens,
                       queue_depth=len(self._queue))

    def _profiled_step(self):
        """``_step_decode`` under the step-anatomy clock: the tracer's
        guarded fast path (one attribute read while profiling is off);
        early returns and raises close their spans too."""
        prof = self.profiler
        if not prof.enabled:
            return self._step_decode(None)
        clk = prof.clock
        clk.begin(self._n_steps)
        try:
            return self._step_decode(clk)
        finally:
            clk.end()

    def _observe_admission(self, req: _Request, now: float):
        """Queue-wait accounting at the moment a request takes a slot.
        Observed with the request's root span current, so the histogram
        series picks up the trace_id as an exemplar."""
        with _tracing.get_tracer().use(req.span):
            self._m_queue_wait.observe(now - req.t_enqueue)
        req.t_admit = now

    def _observe_token(self, req: _Request, now: float):
        """Per-token latency accounting (call after tokens.append): the
        first token since submission is TTFT, later ones record the
        inter-token gap. Runs under the request's root span (when
        tracing) so TTFT / inter-token exemplars cross-link."""
        with _tracing.get_tracer().use(req.span):
            if len(req.tokens) == 1:
                self._m_ttft.observe(now - req.t_enqueue)
                if req.t_admit is not None:
                    # admission -> first token, best case ever seen:
                    # the service FLOOR the provably-unmeetable
                    # deadline shed compares remaining budgets against
                    x = now - req.t_admit
                    f = self._ttft_admit_floor
                    self._ttft_admit_floor = x if f is None \
                        else min(f, x)
                    self._ttft_admit_n += 1
            elif req.t_last is not None:
                self._m_inter.observe(now - req.t_last)
        req.t_last = now
        self._n_tokens += 1
        self._m_tokens.inc()

    # ---- request-scoped tracing (shared by both engines) ---------------
    def _trace_submit(self, req: _Request, trace_ctx=None):
        """Open the per-request root span (+ queue-wait child) at
        submission. ``trace_ctx`` is an inbound ``(trace_id,
        parent_span_id)`` pair (the HTTP layer's W3C traceparent) so
        external callers correlate. No-op while tracing is disabled —
        req.span stays None and every later hook short-circuits."""
        tracer = _tracing.get_tracer()
        if not tracer.enabled:
            return
        trace_id, parent_id = trace_ctx if trace_ctx else (None, None)
        req.span = tracer.start_span(
            _tracing.SPAN_REQUEST, trace_id=trace_id, parent_id=parent_id,
            attrs={"rid": req.rid, "engine": self._engine_label,
                   "prompt_tokens": int(req.ids.size),
                   "max_new_tokens": req.max_new_tokens})
        req.queue_span = tracer.start_span(_tracing.SPAN_QUEUE_WAIT,
                                           parent=req.span)

    def _trace_admit(self, req: _Request, slot: int):
        """Close the queue-wait child the moment the request takes a
        slot; the slot lands on the root span for the timeline view."""
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_ADMIT, rid=req.rid,
                       engine=self._engine_label, slot=slot,
                       queue_wait_s=(req.t_admit - req.t_enqueue
                                     if req.t_admit is not None else None),
                       free_slots=(self._slots.count(None)
                                   - len(getattr(self, "_chunking", ()))))
        if req.queue_span is not None:
            req.queue_span.end()
            req.queue_span = None
        if req.span is not None:
            req.span.set_attr("slot", slot)

    def _trace_decode_step(self, req: _Request, start_ns: int, end_ns: int):
        """Attach the (already timed) fused decode dispatch to this
        request as a sampled child span — see trace_decode_every."""
        n = len(req.tokens)
        if req.span is not None and (n == 1
                                     or n % self.trace_decode_every == 0):
            _tracing.get_tracer().add_span(
                _tracing.SPAN_DECODE_STEP, start_ns, end_ns,
                parent=req.span, attrs={"token_index": n})

    def _trace_end(self, req: _Request, status: str):
        """Retire the request's spans: a still-open queue-wait child
        (cancel before admission), an instant slot-free marker when it
        held a slot, then the root with its final status."""
        rec = _frec.RECORDER
        if rec.enabled and req.slot >= 0:
            rec.record(_frec.EV_SLOT_FREE, rid=req.rid,
                       engine=self._engine_label, slot=req.slot,
                       status=status, generated=len(req.tokens))
        if req.queue_span is not None:
            req.queue_span.end(status)
            req.queue_span = None
        span = req.span
        if span is None:
            return
        req.span = None
        if req.slot >= 0:
            now = time.perf_counter_ns()
            _tracing.get_tracer().add_span(
                _tracing.SPAN_SLOT_FREE, now, now, parent=span,
                attrs={"slot": req.slot})
        span.set_attr("generated_tokens", len(req.tokens))
        if req.spec_rounds:
            # speculative-decode health, per request: how many verify
            # rounds it rode and how many draft tokens landed — the
            # trace-side view of the acceptance histogram
            span.set_attr("spec_rounds", req.spec_rounds)
            span.set_attr("spec_accepted_tokens", req.spec_accepted)
        span.end(status)

    def finish_reason(self, rid: int):
        """Why a finished request retired: "stop" | "length" |
        "cancelled" (| "error" for a failed seq2seq admission). None
        while in flight or once evicted from the retention window."""
        return self._finished_reason.get(rid)

    def _record_usage(self, req: _Request):
        """Per-request cost accounting at retirement: token counts plus
        where the request's wall time went (queue vs compute) and how
        many fused dispatches it rode — the response's ``usage`` block
        and, divided out, tokens-per-dispatch (the per-request view of
        the engine-wide speculation health number)."""
        now = time.perf_counter()
        t_admit = req.t_admit if req.t_admit is not None else now
        done = req.t_last if req.t_last is not None else now
        n_disp = req.dispatches
        n_tok = len(req.tokens)
        self._finished_usage[req.rid] = {
            "prompt_tokens": int(req.ids.size),
            "completion_tokens": n_tok,
            "queue_ms": max(0.0, (t_admit - req.t_enqueue) * 1e3),
            "compute_ms": max(0.0, (done - t_admit) * 1e3),
            "dispatches": n_disp,
            "accepted_tokens_per_dispatch": (n_tok / n_disp
                                             if n_disp else 0.0),
        }

    def request_usage(self, rid: int) -> Optional[dict]:
        """The usage block of a FINISHED request; None while in flight
        or once evicted from the retention window."""
        return self._finished_usage.get(rid)

    def _release_slot(self, s: int) -> None:
        """Slot teardown, in ONE place: clear the request binding, zero
        the ragged length row, and hand the slot's KV pages back to the
        atlas. Idempotent on an already-free slot. Every retire, cancel,
        preempt, migrate-out, and degrade path routes through here —
        pdlint's engine-slot lifecycle rule anchors on this name, so an
        inlined copy that forgets the atlas half shows up as a leak."""
        self._slots[s] = None
        self._lengths = self._lengths.at[s].set(0)
        if self.kvatlas.enabled:
            self.kvatlas.free_slot(s)

    def cancel(self, rid: int) -> bool:
        """Abort a request (client disconnect): queued requests drop
        before admission; active requests free their slot immediately —
        the next step() stops decoding the row and admission can refill
        it. Partial tokens are NOT delivered. Returns True if the request
        was live (queued or active); False if unknown or finished."""
        rec = _frec.RECORDER
        for i, req in enumerate(self._queue):
            if req.rid == rid:
                del self._queue[i]
                at = self.kvatlas
                if at.enabled:
                    at.unpark(rid)  # a preempted request dies in queue
                if rec.enabled:
                    rec.record(_frec.EV_CANCEL, rid=rid,
                               engine=self._engine_label, where="queued")
                self._record_reason(rid, "cancelled")
                self._trace_end(req, "cancelled")
                return True
        for s, req in enumerate(self._slots):
            if req is not None and req.rid == rid:
                self._release_slot(s)
                if rec.enabled:
                    rec.record(_frec.EV_CANCEL, rid=rid,
                               engine=self._engine_label, where="active")
                self._record_reason(rid, "cancelled")
                self._trace_end(req, "cancelled")
                self._admit()     # the freed slot can refill immediately
                return True
        # a request mid chunked-prefill holds a RESERVED slot (not yet in
        # _slots): drop the chunk state so the slot frees immediately
        for s, st in list(getattr(self, "_chunking", {}).items()):
            if st.req.rid == rid:
                del self._chunking[s]
                self._release_slot(s)
                if st.span is not None:
                    st.span.end("cancelled")
                if rec.enabled:
                    rec.record(_frec.EV_CANCEL, rid=rid,
                               engine=self._engine_label,
                               where="prefilling")
                self._record_reason(rid, "cancelled")
                self._trace_end(st.req, "cancelled")
                self._admit()
                return True
        return False

    def _record_reason(self, rid: int, reason: str, logprobs=None):
        """Record why a request ended and trim the retention window —
        the ONE bookkeeping path for finishes AND cancels (a cancel-heavy
        workload must not grow the window unboundedly)."""
        if reason == "cancelled":
            self._n_cancelled += 1
            self._m_req_cancelled.inc()
        elif reason in ("stop", "length"):
            # drain-rate estimate: EWMA of the gap between finishes —
            # queue_depth * this gap is how long a bounced request
            # should back off (the computed Retry-After)
            now = time.perf_counter()
            if self._t_last_finish is not None:
                iv = now - self._t_last_finish
                e = self._finish_interval_ewma
                self._finish_interval_ewma = iv if e is None \
                    else 0.7 * e + 0.3 * iv
            self._t_last_finish = now
        self._finished_reason[rid] = reason
        if logprobs is not None:
            self._finished_logprobs[rid] = logprobs
        self._reason_order.append(rid)
        while len(self._reason_order) > _REASON_KEEP:
            old = self._reason_order.popleft()
            self._finished_reason.pop(old, None)
            getattr(self, "_finished_logprobs", {}).pop(old, None)
            getattr(self, "_finished_usage", {}).pop(old, None)


class _Flight(NamedTuple):
    """One enqueued one-token decode step whose tokens the host has not
    fetched yet: the device arrays to fetch, the ``(slot, request)``
    pairs the step decodes for, and when it was enqueued."""

    nxt: object
    logps: object
    rows: list
    t_dispatch: float
    # expert-layer counts, still on the device: (this step's, the
    # admission prefills' running sum as it stood when the step was
    # enqueued), or None for a model without expert layers
    moe: object = None
    # rows x linear layers whose recurrent state the step advanced, on the
    # device; None for a model without such layers
    gdn: object = None


class _ChunkState:
    """A request mid chunked-prefill: it has RESERVED a slot (invisible
    to _alloc_slot) but is not yet decoding — ``pos`` tokens of its prompt
    are already in the slot's pages, the rest lands one chunk per engine
    step with a normal decode dispatch in between."""

    __slots__ = ("req", "slot", "pos", "t_admit", "span")

    def __init__(self, req: _Request, slot: int, t_admit: float, span=None):
        self.req = req
        self.slot = slot
        self.pos = 0          # prompt tokens already prefilled (page-aligned)
        self.t_admit = t_admit
        self.span = span      # the serving.prefill span, open across chunks


def _resolve_spec_k(model, max_batch: int, max_len: int,
                    page_size: int = 16, default: int = 4,
                    acceptance: float = 0.7) -> int:
    """Pick the speculation chunk width ``k`` for THIS device from the
    autotune cost table: the verify geometry is registered with
    ``autotune.search()`` (kernel "spec_verify" — candidates are chunk
    widths, the runner times one batched verify dispatch on throwaway
    buffers, the registered analytical cost model prunes and ranks), and
    the measured table is then re-ranked by EXPECTED retired tokens per
    dispatch under a geometric acceptance model (``sum p^i`` — measured
    acceptance is what makes wider chunks pay), because raw dispatch
    latency alone always favors the narrowest chunk. Off-TPU or with
    FLAGS_use_autotune off this returns ``default`` without touching the
    device; a previously persisted table re-ranks without re-measuring."""
    from .ops.pallas import autotune

    if not autotune.enabled():
        # the reference's switch semantics: flag off = heuristic only,
        # even when a persisted table exists
        return default
    cfg = model.config
    try:
        from .models.llama import head_dim_of

        hd = head_dim_of(cfg)
        h, hk = cfg.num_attention_heads, cfg.num_key_value_heads
        params = {
            "batch": int(max_batch), "hidden": int(cfg.hidden_size),
            "layers": int(cfg.num_hidden_layers),
            "intermediate": int(cfg.intermediate_size),
            "wtot": int((h + 2 * hk) * hd),
            "vocab": int(cfg.vocab_size),
            "dtype": str(cfg.dtype),
        }
    except (AttributeError, TypeError):
        return default  # non-llama-shaped config: the heuristic default
    sig = " ".join(f"{k_}{v}" for k_, v in sorted(params.items()))
    cands = [(c,) for c in (2, 3, 4, 6, 8) if c <= max_len]
    from .ops.pallas import backend

    can = backend.on_tpu() and max_len % page_size == 0

    def runner(choice):
        (kk,) = choice
        step = _get_spec_decode(model, max_len, kk)
        dt = (jnp.dtype(cfg.dtype) if isinstance(cfg.dtype, str)
              else cfg.dtype)

        def run():
            # throwaway pool per call: the verify step DONATES its cache
            # buffers, so a timed repetition can never reuse them
            from .models.llama import head_dim_of as _hd

            d = _hd(cfg)
            pps = max_len // page_size
            n_pages = max_batch * pps
            caches = [{
                "k_pages": jnp.zeros(
                    (cfg.num_key_value_heads, n_pages, page_size, d), dt),
                "v_pages": jnp.zeros(
                    (cfg.num_key_value_heads, n_pages, page_size, d), dt),
                "page_indices": jnp.arange(
                    n_pages, dtype=jnp.int32).reshape(max_batch, pps),
                "lengths": jnp.zeros((max_batch,), jnp.int32),
                "page_size": page_size,
            } for _ in range(cfg.num_hidden_layers)]
            last = jnp.zeros((max_batch, cfg.vocab_size), jnp.float32)
            drafts = jnp.zeros((max_batch, max(kk - 1, 0)), jnp.int32)
            return step(last, drafts, caches)[0]

        return run

    choice = autotune.search(
        "spec_verify", sig, (default,), cands, runner, can,
        params=params,
        cost_model=lambda c: autotune.analytical_cost(
            "spec_verify", params, c))
    ent = autotune.get_cache().entry(
        "spec_verify", autotune.full_key(sig)) or {}
    table = ent.get("table") or {}
    best_k, best_score = int(choice[0]), None  # pdlint: disable=host-sync -- autotune.search returns a host tuple from the cost table, never a device value; engine construction is off the decode loop anyway
    for (kk,) in cands:
        row = table.get(str(kk))
        if not row or row.get("status") != "ok":
            continue
        expect = sum(acceptance ** i for i in range(kk))
        score = row["ms"] / expect
        if best_score is None or score < best_score:
            best_k, best_score = kk, score
    return best_k


class ContinuousBatchEngine(_RequestBookkeeping):
    """In-flight batching: add_request() any time, step() decodes one token
    for every active slot, finished requests free their slot immediately.

    >>> eng = ContinuousBatchEngine(model, max_batch=4, max_len=256)
    >>> rid = eng.add_request(prompt_ids, max_new_tokens=64)
    >>> done = eng.run_until_done()   # {rid: np.ndarray of generated ids}
    """

    @classmethod
    def preflight(cls, model, max_batch: int, max_len: int,
                  page_size: int = 16, mesh=None, param_specs=None,
                  budget_bytes: Optional[int] = None,
                  allow_upcast=(), raise_on_fatal: bool = True):
        """Jaxpr-level admission check BEFORE any buffer is allocated or
        step compiled: shard-spec validity (explicit ``mesh`` +
        ``param_specs`` patterns, plus placements already attached to
        parameters via dist.shard_tensor), bf16→f32 dtype promotion, and
        a param/activation/kv-cache byte bound against ``budget_bytes``.

        ``param_specs="auto"`` runs the auto-sharding solver instead of
        validating hand-written specs: the cheapest feasible plan for
        ``mesh`` + ``budget_bytes`` is adopted, returned on
        ``report.plan`` (specs, per-device bytes, reshard bytes,
        rejected-plan ledger), and announced as a
        ``preflight.autoshard`` flight-recorder event — an arbitrary
        checkpoint + mesh serves with a machine-chosen layout (apply it
        with ``analysis.graph.solver.apply_plan``).

        Returns the structured ``PreflightReport``; with
        ``raise_on_fatal`` (default) an indivisible sharding or an
        over-budget model raises ``PreflightError`` carrying that report
        — the findings-report replacement for the compile-time crash XLA
        would produce minutes later. The trace is abstract
        (jax.make_jaxpr): preflighting a 70B config costs tracing time,
        not memory.
        """
        from .analysis.graph import preflight as _preflight
        from .analysis.graph.cost import kv_cache_bytes as _kv_bytes

        report = _preflight.preflight_model(
            model, batch=1, seq_len=min(int(max_len), 128),
            mesh=mesh, param_specs=param_specs, budget_bytes=budget_bytes,
            kv_cache_bytes=_kv_bytes(model.config, max_batch, max_len,
                                     page_size),
            allow_upcast=allow_upcast)
        rec = _frec.RECORDER
        if rec.enabled and report.plan is not None:
            rec.record(_frec.EV_AUTOSHARD, model=report.model,
                       feasible=bool(report.plan.get("feasible")),
                       cost=report.plan.get("cost"),
                       per_device_bytes=report.plan.get("resident_bytes"),
                       reshard_bytes=report.plan.get("reshard_bytes"),
                       plans_considered=report.plan.get(
                           "plans_considered"),
                       assignment=dict(report.plan.get("assignment", {})))
        if raise_on_fatal and not report.ok:
            raise _preflight.PreflightError(report)
        return report

    def __init__(self, model, max_batch: int, max_len: int, page_size: int = 16,
                 eos_token_id: Optional[int] = None, do_sample: bool = False,
                 temperature: float = 1.0, top_k: int = 0, top_p: float = 1.0,
                 enable_prefix_cache: bool = False,
                 preflight: bool = False,
                 prefill_chunk_tokens: Optional[int] = None,
                 max_queue: Optional[int] = None,
                 enable_preemption: bool = False,
                 aging_s: float = 5.0,
                 speculative_k=None,
                 speculative_ngram: int = 3):
        if max_len % page_size != 0:
            raise ValueError("max_len must be a multiple of page_size")
        # ---- speculative decoding (multi-token steps) -------------------
        # speculative_k = chunk width per decode dispatch: 1 verified
        # token + up to k-1 n-gram-drafted tokens per slot per step.
        # None/0 = off (the classic one-token step, bit-identical to
        # before); "auto" = let the autotune cost table pick k for this
        # device (see _resolve_spec_k). Greedy-only: dispatches with a
        # sampling slot active fall back to the one-token step.
        if speculative_k == "auto":
            speculative_k = _resolve_spec_k(model, max_batch, max_len,
                                            page_size=page_size)
        if speculative_k is not None:
            speculative_k = int(speculative_k)
            if speculative_k < 1:
                raise ValueError(
                    f"speculative_k must be >= 1 (or 'auto'), got "
                    f"{speculative_k}")
            if speculative_k > max_len:
                raise ValueError(
                    f"speculative_k {speculative_k} exceeds max_len "
                    f"{max_len}")
            if getattr(model.llama, "empty_cache_layer", None) is not None:
                raise NotImplementedError(
                    "engine speculative decoding needs the paged KV "
                    "layout — the latent (MLA) compressed rows have no "
                    "multi-token ragged append path (use "
                    "mtp_speculative_generate for MLA self-drafting)")
        self.speculative_k = speculative_k or None
        self.speculative_ngram = int(speculative_ngram)
        if prefill_chunk_tokens is not None:
            prefill_chunk_tokens = int(prefill_chunk_tokens)
            if (prefill_chunk_tokens <= 0
                    or prefill_chunk_tokens % page_size != 0):
                raise ValueError(
                    f"prefill_chunk_tokens must be a positive multiple of "
                    f"page_size ({page_size}), got {prefill_chunk_tokens} "
                    "— later chunks continue at page-aligned positions")
        if max_queue is not None and int(max_queue) < 0:
            raise ValueError(f"max_queue must be >= 0, got {max_queue}")
        if preflight:
            # model-load gate: fail fast with a findings report (raises
            # PreflightError) instead of crashing in compile or OOMing
            # after the pools below are already allocated
            type(self).preflight(model, max_batch, max_len,
                                 page_size=page_size)
        cfg = model.config
        if max_len > cfg.max_position_embeddings:
            raise ValueError(f"max_len {max_len} exceeds "
                             f"max_position_embeddings {cfg.max_position_embeddings}")
        if temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature} "
                             "(0 decodes greedily)")
        self.model = model
        self.max_batch, self.max_len, self.page_size = max_batch, max_len, page_size
        self.eos_token_id = eos_token_id
        self._sample_cfg = (do_sample, float(temperature), int(top_k), float(top_p))

        dt = jnp.dtype(cfg.dtype) if isinstance(cfg.dtype, str) else cfg.dtype
        self._pages_per_slot = max_len // page_size
        self._lengths = jnp.zeros((max_batch,), jnp.int32)
        # models with a latent decode cache (MLA) serve through per-slot
        # rows of the compressed buffers instead of the paged K/V pool
        make = getattr(model.llama, "empty_cache_layer", None)
        self._latent_mode = make is not None
        # what a slot keeps in each layer (``slot_pool_plan``): max_len of
        # pages; a RING of them on a sliding-window layer of a model whose
        # layers differ by type (position p in page (p // page_size) mod
        # ring; None here marks a whole row); or, in a linear-attention
        # layer, a recurrent state (``state`` and ``conv``, from the
        # model's ``empty_state_cache``) and no pages. Prefix caching,
        # chunked prefill, preemption, speculation, handoff and migration
        # read a slot's pages as one run, which rings and states refuse
        # (``_refuse_on_rings``; handoff and migration are calls, not
        # options the constructor sees)
        self._ring_pages = [None] * cfg.num_hidden_layers
        self._state_layers = []
        if self._latent_mode:
            self._caches = [dict(make(max_batch, max_len, dt),
                                 lengths=self._lengths)
                            for _ in range(cfg.num_hidden_layers)]
        else:
            from .models.llama import head_dim_of, slot_pool_plan

            hk = cfg.num_key_value_heads
            d = head_dim_of(cfg)
            self._caches = []
            tables = {}
            plan = slot_pool_plan(cfg, max_len, page_size)
            for layer, (kind, pps) in enumerate(plan):
                if kind == "state":
                    self._state_layers.append(layer)
                    self._caches.append(dict(
                        model.llama.empty_state_cache(layer, max_batch, dt),
                        lengths=self._lengths))
                    continue
                if kind == "ring":
                    self._ring_pages[layer] = pps
                if pps not in tables:
                    tables[pps] = jnp.arange(
                        max_batch * pps, dtype=jnp.int32).reshape(
                            max_batch, pps)
                cache = {
                    "k_pages": jnp.zeros((hk, max_batch * pps, page_size, d),
                                         dt),
                    "v_pages": jnp.zeros((hk, max_batch * pps, page_size, d),
                                         dt),
                    "page_indices": tables[pps],
                    "lengths": self._lengths,
                    "page_size": page_size,
                }
                if self._ring_pages[layer] is not None:
                    cache["ring"] = True    # the KEY is what attention reads
                self._caches.append(cache)
        self._has_rings = any(r is not None for r in self._ring_pages)
        self._pool_keys = [("state", "conv") if i in self._state_layers
                           else ("k_pages", "v_pages")
                           for i in range(cfg.num_hidden_layers)]
        for feature, on in (("prefix caching", enable_prefix_cache),
                            ("chunked prefill", prefill_chunk_tokens),
                            ("preemption", enable_preemption),
                            ("speculative decoding", speculative_k)):
            if on:
                self._refuse_on_rings(feature)
        self._last = jnp.zeros((max_batch, cfg.vocab_size), jnp.float32)

        self._poisoned = False
        self._slots: List[Optional[_Request]] = [None] * max_batch
        # the decode step enqueued and not yet fetched (_step_decode keeps
        # one in flight), the device inputs that change only with slot
        # membership (key, arrays: _enqueue_decode), the last fetch's time
        self._in_flight: Optional[_Flight] = None
        self._step_inputs = (None, None)
        self._t_fetched = 0.0
        self._init_bookkeeping("decoder")
        # KV & memory atlas, configured with this pool's real geometry —
        # replaces the degenerate instance _init_bookkeeping registered.
        # preflight_bytes is the PREDICTED pool footprint: measured
        # occupancy is reported against it on /kvstate
        try:
            from .analysis.graph.cost import kv_cache_bytes as _kv_pre
            from .analysis.graph.cost import state_bytes_per_slot as \
                _state_bytes

            _preflight = int(_kv_pre(cfg, max_batch, max_len,
                                     page_size)) or None
        except Exception:  # pdlint: disable=silent-exception -- the preflight join is best-effort; the ledger stays exact without it
            _preflight = None
        self.kvatlas = _kvatlas.KvAtlas(
            "decoder", max_batch=max_batch, page_size=page_size,
            pages_per_slot=self._pages_per_slot,
            bytes_per_token=_kvatlas.kv_bytes_per_token(cfg),
            paged=not self._latent_mode, preflight_bytes=_preflight,
            state_bytes_per_slot=_state_bytes(cfg))
        # the reference replay reproduces exactly this engine's decode
        # semantics, so the correctness sentinel may audit it; it replays
        # through pages alone, which a recurrent layer does not keep
        self.sentinel.auditable = not self._state_layers
        # sealed-bundle size histogram children (preempt eviction,
        # migration export, prefill->decode handoff) — always-on like
        # the other engine histograms, not atlas-gated
        self._m_bundle = {
            k: _metrics.SERVING_BUNDLE_BYTES.labels(engine="decoder", kind=k)
            for k in ("preempt", "migrate", "handoff")}

        # ---- SLO-aware scheduling ---------------------------------------
        # chunked prefill: admission prefill lands prefill_chunk_tokens at
        # a time (None = whole prompt at once, the monolithic path);
        # between chunks step() runs a normal decode dispatch so a live
        # slot's worst inter-token stall is one chunk-step
        self.prefill_chunk_tokens = prefill_chunk_tokens
        self.max_queue = None if max_queue is None else int(max_queue)
        if enable_preemption and self._latent_mode:
            raise ValueError(
                "enable_preemption requires the paged KV layout — the "
                "latent (MLA) compressed rows have no host eviction path")
        self.enable_preemption = bool(enable_preemption)
        self.aging_s = float(aging_s)
        # slot -> _ChunkState: requests mid chunked-prefill (slot
        # reserved, not yet decoding); insertion order is service order
        self._chunking: Dict[int, _ChunkState] = {}
        self._m_sched = {
            d: _metrics.SERVING_SCHED.labels(engine="decoder", decision=d)
            for d in ("chunk", "preempt", "restore", "migrate_out",
                      "migrate_in", "degrade")}
        # acceptance histogram child bound once (no per-dispatch label
        # lookups on the decode hot path), like every engine metric
        self._m_spec_accept = _metrics.SERVING_SPEC_ACCEPTED.labels(
            engine="decoder")

        # ---- automatic prefix caching (vLLM-style, opt-in) --------------
        # At admission, the longest page-aligned token prefix shared with a
        # still-ACTIVE slot's prompt is COPIED from that slot's pages
        # (device page copy — cheap vs recomputing the prefill), and only
        # the suffix runs the model. Copies (not aliases) keep retirement
        # trivial: freed pages can be overwritten with no refcounts.
        self.enable_prefix_cache = bool(enable_prefix_cache)
        self.prefix_pages_reused = 0  # observability: total pages copied
        self._m_prefix_hit = _metrics.SERVING_PREFIX_LOOKUPS.labels(
            engine="decoder", result="hit")
        self._m_prefix_miss = _metrics.SERVING_PREFIX_LOOKUPS.labels(
            engine="decoder", result="miss")
        self._m_prefix_pages = _metrics.SERVING_PREFIX_PAGES.labels(
            engine="decoder")
        self._m_decode_rows = _metrics.SERVING_DECODE_ROWS.labels(
            engine="decoder")
        self._m_decode_cached = _metrics.SERVING_DECODE_CACHED_TOKENS.labels(
            engine="decoder")
        self._m_dispatch = {
            m: _metrics.SERVING_DECODE_DISPATCH.labels(engine="decoder",
                                                       mode=m)
            for m in ("ahead", "drained")}
        self._m_discarded = _metrics.SERVING_DECODE_DISCARDED_ROWS.labels(
            engine="decoder")
        self._m_prefill_prompt = _metrics.SERVING_PREFILL_TOKENS.labels(
            engine="decoder", kind="prompt")
        self._m_prefill_bucket = _metrics.SERVING_PREFILL_TOKENS.labels(
            engine="decoder", kind="bucket")
        self._m_prefill_attention = {
            impl: _metrics.SERVING_PREFILL_ATTENTION.labels(
                engine="decoder", impl=impl)
            for impl in ("flash", "append", "xla")}
        self._init_pool_and_moe_metrics()

    def _refuse_on_rings(self, feature: str):
        """THE refusal of everything that reads or writes a slot's pages
        as one run of ``max_len / page_size`` in every layer, as the
        slot's whole context: a window layer's ring holds the last window
        only, in its own order; a linear-attention layer holds no pages,
        only a recurrent state that has taken in the whole row and cannot
        be cut at a page, copied from a prefix or continued from one."""
        if self._has_rings:
            raise NotImplementedError(
                f"{feature} is not supported on a model whose sliding-window "
                f"layers keep a window-sized ring of K/V pages (layers "
                f"{[i for i, r in enumerate(self._ring_pages) if r]}): it "
                "assumes every layer holds a slot's whole row")
        if self._state_layers:
            raise NotImplementedError(
                f"{feature} is not supported on a model whose "
                f"linear-attention layers keep a recurrent state per slot "
                f"and no pages (layers {self._state_layers}): it reads a "
                "slot's pages as its whole context")

    def _init_pool_and_moe_metrics(self):
        """The pools' bytes by layer type (a gauge, set once) and the
        children of the expert layers' counters: one per held expert,
        labelled by its index in the routing width."""
        cfg = self.model.config
        pool = {"global": 0, "window": 0, "linear_state": 0}
        if not self._latent_mode:
            for c, ring in zip(self._caches, self._ring_pages):
                if "state" in c:
                    pool["linear_state"] += (c["state"].nbytes
                                             + c["conv"].nbytes)
                    continue
                pool["window" if ring else "global"] += (
                    c["k_pages"].nbytes + c["v_pages"].nbytes)
        for kind, nbytes in pool.items():
            _metrics.SERVING_KV_POOL_BYTES.labels(
                engine="decoder", layer_type=kind).set(nbytes)
        self._ring_window = (int(cfg.sliding_window) if self._has_rings
                             else None)
        self._m_rows_over_window = (
            _metrics.SERVING_DECODE_ROWS_OVER_WINDOW.labels(engine="decoder"))
        self._m_gdn_updates = _metrics.SERVING_GDN_STATE_UPDATES.labels(
            engine="decoder")
        self._m_gdn_chunks = {
            kind: _metrics.SERVING_GDN_PREFILL_CHUNKS.labels(engine="decoder",
                                                             kind=kind)
            for kind in ("run", "skipped")}
        self._m_moe_tokens = _metrics.SERVING_MOE_TOKENS.labels(
            engine="decoder")
        self._m_moe_pairs = _metrics.SERVING_MOE_HELD_PAIRS.labels(
            engine="decoder")
        lo, hi = (getattr(cfg, "held_experts", None)
                  or (0, getattr(cfg, "n_routed_experts", 0)))
        self._m_moe_expert = [
            _metrics.SERVING_MOE_EXPERT_TOKENS.labels(engine="decoder",
                                                      expert=str(e))
            for e in range(lo, hi)]
        # the admission prefills' counts, summed on the device as they are
        # enqueued; a decode step's record carries the sum as it stood, and
        # its retirement counts what came since the one before
        self._moe_prefills = None
        self._moe_prefills_seen = 0

    def _count_moe(self, counts) -> None:
        """``counts``: int array [1 + held], rows routed then pairs per
        held expert (``generation.moe_counts``), already on the host."""
        if not counts[0]:
            return          # no row routed (no prefill since the last step)
        self._m_moe_tokens.inc(int(counts[0]))
        self._m_moe_pairs.inc(int(counts[1:].sum()))
        for child, n in zip(self._m_moe_expert, counts[1:]):
            child.inc(int(n))

    def _require_fit(self, n_prompt: int, max_new: int):
        """Slot-capacity admission check. With speculation on, every
        decode dispatch writes a k-token chunk starting at the row's
        frontier, so the LAST dispatch (frontier at prompt+new-1) still
        needs k-1 slack positions for rejected-draft KV — without the
        slack the chunk scatter would clamp onto the slot's last valid
        page and corrupt it."""
        slack = (self.speculative_k - 1) if self.speculative_k else 0
        if n_prompt + max_new + slack > self.max_len:
            extra = f" + speculation slack ({slack})" if slack else ""
            raise ValueError(
                f"prompt ({n_prompt}) + max_new_tokens ({max_new})"
                f"{extra} exceeds engine max_len {self.max_len}")

    # ---- public API ---------------------------------------------------------
    def add_request(self, ids, max_new_tokens: int = 64, do_sample=None,
                    temperature=None, top_k=None, top_p=None,
                    on_token=None, pixel_values=None,
                    stop_token_ids=None, logprobs=False,
                    trace_ctx=None, priority=None, slo_ms=None,
                    on_shed=None, request_id=None, audit=None) -> int:
        """Queue one request. Sampling knobs default to the engine-level
        configuration; any per-request override routes decoding through the
        per-row sampling program (one compiled step serves the whole mix).

        ``on_token(rid, token, done)`` streams each generated token as the
        engine's step that produced it completes (token-level streaming —
        the serving front-end's SSE hook); exceptions it raises propagate
        out of step()/run_until_done(). A callback with FOUR required
        positional parameters (or ``*args``) receives the chosen-token
        logprob as the 4th argument; a defaulted 4th parameter keeps the
        3-arg call (the logprob never clobbers a closure default).

        ``stop_token_ids`` retires the request on ANY of the given ids,
        IN ADDITION to the engine-level eos (the OpenAI "stop" role:
        extra stops never disable end-of-sequence termination).

        ``pixel_values`` ([n_images, C, H, W]) serves a MULTIMODAL prompt:
        admission merges projected image features into the placeholder
        positions (model.merge_multimodal) and prefills over embeddings;
        decode is ordinary token traffic, so text and image requests batch
        in-flight together.

        ``priority`` (int, lower = more important; default
        ``PRIORITY_DEFAULT``) and ``slo_ms`` (per-request latency target)
        drive the SLO-aware admission order — see docs/SERVING.md
        "Scheduling & SLOs". With ``max_queue`` configured, a request
        that would wait behind a full queue raises :class:`QueueFull`
        (the HTTP 429 path) instead of growing the backlog unboundedly —
        unless it is strictly more important than some queued request,
        in which case that victim is SHED instead (high-priority goodput
        degrades last). ``slo_ms`` is also a hard deadline: a request
        still queued when its budget runs out is shed typed
        (``sched.shed`` -> HTTP 504 via ``on_shed(rid, info)``), and a
        request submitted with no remaining budget raises
        :class:`DeadlineExceeded` immediately.

        ``audit`` drives the correctness sentinel: ``True`` forces an
        on-demand audit (the HTTP ``X-Audit: 1`` contract — the verdict
        is waitable via ``sentinel.wait_verdict``), ``False`` opts the
        request out, ``None`` (default) leaves it to the sentinel's
        sampling rate. Only effectively-greedy text requests are
        auditable; a forced audit of an ineligible request records a
        ``skipped`` verdict rather than failing the request."""
        eff_priority = (PRIORITY_DEFAULT if priority is None
                        else int(priority))
        if slo_ms is not None and float(slo_ms) <= 0:
            self._count_deadline_reject(float(slo_ms))
            raise DeadlineExceeded(self._engine_label,
                                   miss_ms=-float(slo_ms))
        self._check_queue_bound(priority=eff_priority)
        ids = np.asarray(unwrap(ids) if isinstance(ids, Tensor) else ids).reshape(-1)
        self._require_fit(int(ids.size), int(max_new_tokens))
        if temperature is not None and temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature} "
                             "(0 decodes greedily)")
        if pixel_values is not None:
            # the multimodal model contract: merge_multimodal +
            # multimodal_token_index + features_per_image (LLaVA
            # implements it; the engine never reaches into family config)
            if not all(hasattr(self.model, a) for a in
                       ("merge_multimodal", "multimodal_token_index",
                        "features_per_image")):
                raise TypeError(
                    f"{type(self.model).__name__} is not multimodal — "
                    "pixel_values needs a model implementing "
                    "merge_multimodal / multimodal_token_index / "
                    "features_per_image (LLaVA)")
            if self._latent_mode:
                raise NotImplementedError(
                    "multimodal admission is not supported in latent "
                    "(MLA) mode")
            from .tensor_class import wrap

            if not isinstance(pixel_values, Tensor):
                pixel_values = wrap(jnp.asarray(np.asarray(pixel_values)))
            # malformed multimodal prompts must fail HERE, not out of a
            # later step() that would abort unrelated in-flight serving
            n_slots = int((np.asarray(ids)
                           == self.model.multimodal_token_index).sum())
            want = (pixel_values.shape[0]
                    * self.model.features_per_image())
            if n_slots != want:
                raise ValueError(
                    f"prompt has {n_slots} image tokens but "
                    f"{pixel_values.shape[0]} image(s) produce {want} "
                    "features")
        sampling = self._merge_sampling(do_sample, temperature, top_k, top_p)
        rid = self._next_rid
        self._next_rid += 1
        self._n_requests += 1
        self._m_req_admitted.inc()
        req = _Request(rid, ids, max_new_tokens, sampling,
                       on_token, pixel_values=pixel_values,
                       stop_token_ids=stop_token_ids,
                       want_logprobs=logprobs, priority=priority,
                       slo_ms=slo_ms, request_id=request_id)
        req.on_shed = on_shed
        self._mark_audit(req, audit)
        # trace_ctx: inbound (trace_id, parent_span_id) — the HTTP
        # layer's parsed W3C traceparent — parents this request's root
        # span so the caller's trace continues through the engine
        self._trace_submit(req, trace_ctx)
        self._queue.append(req)
        self._fr_submit(req)
        self._admit()
        return rid

    def _mark_audit(self, req: _Request, audit):
        """Admission-time correctness-sentinel decision: mark the
        request for a shadow (rate-sampled) or on-demand (forced) audit.
        Eligibility is effectively-greedy text decoding — the reference
        replay IS greedy, so a sampled request has no reference stream
        to compare against. A forced audit of an ineligible request
        records a ``skipped`` verdict (typed reason, waitable) instead
        of silently auditing nothing. Audited requests accumulate
        chosen-token logprobs so the verdict carries per-position
        drift."""
        sn = self.sentinel
        if audit is False or not sn.enabled:
            return
        forced = bool(audit)
        if not forced and not sn.should_sample():
            return
        eff = req.sampling or self._sample_cfg
        if not sn.auditable or req.pixel_values is not None \
                or req.encoder_input is not None:
            if forced:
                sn.register_forced(req.rid)
                sn.skip(req.rid, "unsupported", "ondemand", req.ext_id)
            return
        if eff[0]:
            if forced:
                sn.register_forced(req.rid)
                sn.skip(req.rid, "sampling", "ondemand", req.ext_id)
            return
        req.audit = "ondemand" if forced else "shadow"
        req.want_logprobs = True
        if forced:
            sn.register_forced(req.rid)

    def _retry_after_estimate(self) -> float:
        """Backpressure hint for a bounced request: queue depth divided
        by the observed drain rate (EWMA gap between request finishes),
        clamped to [0.5s, 30s]. Before the first finish there is no rate
        to read, so the hint falls back to 1s — never a silent constant
        once the engine has history."""
        iv = self._finish_interval_ewma
        if not iv:
            return 1.0
        est = (len(self._queue) + 1) * iv
        return min(30.0, max(0.5, est))

    def _check_queue_bound(self, priority: Optional[int] = None):
        """Bounded admission: when the queue is at max_queue AND no slot
        is free, either SHED the least-important queued request to make
        room for a strictly more important newcomer (high-priority
        goodput degrades last under sustained pressure), or reject the
        newcomer typed (QueueFull -> HTTP 429 with a computed
        Retry-After). A request that would be admitted immediately never
        bounces off the bound."""
        if (self.max_queue is None
                or len(self._queue) < self.max_queue
                or self._alloc_slot() >= 0):
            return
        if priority is not None and self._queue:
            # capacity shed: lowest class first, latest deadline within
            # a class (the request least likely to still matter)
            victim = max(self._queue,
                         key=lambda r: (r.priority, r.deadline, r.rid))
            if victim.priority > int(priority):
                self._shed_request(victim, where="capacity")
                return
        self._n_rejected += 1
        self._m_req_rejected.inc()
        raise QueueFull(self._engine_label, len(self._queue),
                        self.max_queue,
                        retry_after_s=self._retry_after_estimate())

    def _shed_request(self, req: _Request, where: str):
        """Drop ONE queued request, typed and accounted: ``where`` is
        "expired" (deadline already passed), "unmeetable" (remaining
        budget below the observed admission->first-token service floor),
        or "capacity" (displaced by a strictly more important arrival at
        a full bounded queue). Emits sched.shed + the shed counters and
        notifies the front-end through req.on_shed so an HTTP submission
        answers a typed 504/429 instead of stalling silently."""
        self._queue.remove(req)
        if self.kvatlas.enabled:
            # a preempted request shed from the queue abandons its
            # host-parked bundle
            self.kvatlas.unpark(req.rid)
        now = time.perf_counter()
        miss_ms = ((now - req.deadline) * 1000.0
                   if req.deadline != math.inf else None)
        self._n_shed += 1
        self._m_req_shed.inc()
        self._m_sched_shed.inc()
        if where == "expired":
            msg = (f"request {req.rid} deadline expired "
                   f"{miss_ms:.0f}ms before admission")
        elif where == "unmeetable":
            msg = (f"request {req.rid} shed: remaining budget "
                   f"{-miss_ms:.0f}ms is below the engine's observed "
                   "service floor")
        else:
            msg = (f"request {req.rid} displaced by a higher-priority "
                   "arrival at a full admission queue; retry later")
        info = {"where": where, "miss_ms": miss_ms, "error": msg}
        if where != "capacity":
            self._n_deadline_misses += 1
            self._m_deadline.inc()
        else:
            info["retry_after"] = self._retry_after_estimate()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_SHED, rid=req.rid,
                       engine=self._engine_label, priority=req.priority,
                       where=where, miss_ms=miss_ms,
                       queue_depth=len(self._queue))
        self._record_reason(req.rid, "shed")
        self._trace_end(req, "shed")
        if req.on_shed is not None:
            req.on_shed(req.rid, info)

    def _shed_expired(self, now: float):
        """End-to-end deadline enforcement at the admission gate: shed
        every queued request whose deadline has already passed, or whose
        remaining budget is provably below the engine's observed
        admission->first-token service floor — under overload the engine
        must spend its steps on tokens someone can still use, never on
        admitted-then-expired streams."""
        if not self._queue:
            return
        # the floor arms only once a few first tokens have been timed:
        # a single observation is usually compile-contaminated (cold
        # prompt-length buckets), and a "floor" of one sample would
        # mis-shed every tight-budget request after a cold start
        est = (self._ttft_admit_floor
               if self._ttft_admit_n >= 3 else None) or 0.0
        for req in [r for r in self._queue if r.deadline != math.inf]:
            if now >= req.deadline:
                self._shed_request(req, where="expired")
            elif est and now + est > req.deadline:
                self._shed_request(req, where="unmeetable")

    def _count_deadline_reject(self, slo_ms: float):
        """A request submitted with its budget already spent (slo_ms <=
        0, e.g. a deadline header that expired in transit): counted like
        a shed — it is one, at the door — before the typed raise."""
        self._n_shed += 1
        self._m_req_shed.inc()
        self._m_sched_shed.inc()
        self._n_deadline_misses += 1
        self._m_deadline.inc()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_SHED, rid=None,
                       engine=self._engine_label, priority=None,
                       where="expired", miss_ms=-float(slo_ms),
                       queue_depth=len(self._queue))

    def _merge_sampling(self, do_sample, temperature, top_k, top_p):
        """Per-request sampling tuple: engine defaults overlaid with the
        request's overrides, collapsed to None when the result equals the
        engine config (all-default mixes keep the static program)."""
        if all(v is None for v in (do_sample, temperature, top_k, top_p)):
            return None
        eng_s, eng_t, eng_k, eng_p = self._sample_cfg
        sampling = (
            bool(eng_s if do_sample is None else do_sample),
            float(eng_t if temperature is None else temperature),
            int(eng_k if top_k is None else top_k),
            float(eng_p if top_p is None else top_p))
        return None if sampling == self._sample_cfg else sampling

    # ---- disaggregated serving: prefill export / prefilled admission ----
    def export_prefill(self, ids, max_new_tokens: int = 64) -> dict:
        """Run the bucketed prefill for ONE prompt and return its KV as a
        host-side handoff bundle instead of admitting it — the prefill
        half of the disaggregated serving tier (serving_cluster). The
        bundle is pure numpy (prompt ids, per-layer dense K/V buffers at
        the prefill bucket, the last-logit row) so it ships over any
        byte transport (io/shm_channel for the CPU dryrun path; device
        collectives stay pluggable) and a peer engine over the SAME
        weights resumes decoding with ``admit_prefilled``.

        No slot is taken and no engine state changes — a prefill-role
        worker's pool stays empty however many prompts it prefills."""
        if self._latent_mode:
            raise NotImplementedError(
                "KV handoff is not supported in latent (MLA) mode — the "
                "compressed cache rows are engine-layout-specific")
        self._refuse_on_rings("KV handoff")
        ids = np.asarray(unwrap(ids) if isinstance(ids, Tensor)
                         else ids).reshape(-1)
        if ids.size + max_new_tokens > self.max_len:
            raise ValueError(
                f"prompt ({ids.size}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds engine max_len {self.max_len}")
        req = _Request(-1, ids, max_new_tokens)
        last, caches, S0, bucket = self._bucketed_prefill(req)
        layers = []
        for c in caches:
            pair = []
            for key in ("k", "v"):
                buf = c[key] if not isinstance(c[key], Tensor) \
                    else unwrap(c[key])
                # the handoff IS the device->host export: one deliberate
                # fetch per layer, off the decode loop entirely
                pair.append(np.asarray(buf)[0])  # handoff export is the transfer
            layers.append(tuple(pair))
        last_row = np.asarray(last)[0].astype(np.float32)  # pdlint: disable=host-sync -- handoff export is the transfer
        return seal_bundle({
            "kind": "prefill",
            "ids": np.asarray(ids, np.int64),  # pdlint: disable=host-sync -- ids is the host prompt array, never device
            "prompt_tokens": int(S0),  # pdlint: disable=host-sync -- S0 is a host int from _bucketed_prefill
            "bucket": int(bucket),  # pdlint: disable=host-sync -- bucket is a host int from _bucketed_prefill
            "page_size": int(self.page_size),
            "layers": layers,
            "last": last_row,
        })

    def admit_prefilled(self, handoff: dict, max_new_tokens: int = 64,
                        do_sample=None, temperature=None, top_k=None,
                        top_p=None, on_token=None, stop_token_ids=None,
                        logprobs=False, trace_ctx=None, priority=None,
                        slo_ms=None, on_shed=None, request_id=None) -> int:
        """Queue a request whose prefill already happened on a PEER
        engine (``export_prefill`` over the same weights): admission
        scatters the bundle's KV buffers straight into the slot's pages
        and decoding starts from the shipped last-logit row — the decode
        half of the disaggregated tier. Sampling / stop / logprobs /
        priority / SLO knobs mirror ``add_request`` (they are decode-side
        concerns)."""
        eff_priority = (PRIORITY_DEFAULT if priority is None
                        else int(priority))
        if slo_ms is not None and float(slo_ms) <= 0:
            self._count_deadline_reject(float(slo_ms))
            raise DeadlineExceeded(self._engine_label,
                                   miss_ms=-float(slo_ms))
        self._check_queue_bound(priority=eff_priority)
        if self._latent_mode:
            raise NotImplementedError(
                "KV handoff is not supported in latent (MLA) mode")
        self._refuse_on_rings("KV handoff")
        verify_bundle(handoff, kind="prefill")
        bucket = int(handoff["bucket"])
        if bucket % self.page_size != 0 or bucket > self.max_len:
            raise ValueError(
                f"handoff bucket {bucket} does not fit this engine "
                f"(page_size {self.page_size}, max_len {self.max_len}) — "
                f"prefill and decode engines must share the serving shape")
        if len(handoff["layers"]) != len(self._caches):
            raise ValueError(
                f"handoff carries {len(handoff['layers'])} layers, engine "
                f"has {len(self._caches)} — different models?")
        ids = np.asarray(handoff["ids"]).reshape(-1)
        self._require_fit(int(ids.size), int(max_new_tokens))
        if temperature is not None and temperature < 0:
            raise ValueError(f"temperature must be >= 0, got {temperature} "
                             "(0 decodes greedily)")
        sampling = self._merge_sampling(do_sample, temperature, top_k, top_p)
        rid = self._next_rid
        self._next_rid += 1
        self._n_requests += 1
        self._m_req_admitted.inc()
        req = _Request(rid, ids, max_new_tokens, sampling, on_token,
                       stop_token_ids=stop_token_ids, want_logprobs=logprobs,
                       priority=priority, slo_ms=slo_ms,
                       request_id=request_id)
        req.on_shed = on_shed
        req.handoff = handoff
        self._trace_submit(req, trace_ctx)
        self._queue.append(req)
        self._fr_submit(req)
        self._admit()
        return rid

    def _admit_handoff(self, slot: int, req: _Request):
        """Admission from a handoff bundle: rebuild the per-layer dense
        buffers on device and reuse the SAME jitted page scatter as a
        local prefill — no model forward runs here."""
        h, req.handoff = req.handoff, None  # free the host KV after use
        bucket, S0 = int(h["bucket"]), int(h["prompt_tokens"])
        self._m_bundle["handoff"].observe(float(
            sum(k.nbytes + v.nbytes for k, v in h["layers"])))
        c_new = [{"k": jnp.asarray(k)[None], "v": jnp.asarray(v)[None]}
                 for k, v in h["layers"]]
        pages = [(c["k_pages"], c["v_pages"]) for c in self._caches]
        try:
            new_pages = self._scatter_fn(bucket)(
                pages, c_new, jnp.asarray([slot, S0], jnp.int32))
        except Exception as e:
            # same donation-failure protocol as a local prefill: the page
            # pool may be gone, so poison instead of limping on
            self._poisoned = True
            raise RuntimeError(
                "ContinuousBatchEngine: handoff admission failed after "
                "the page pool was donated; rebuild the engine and "
                "resubmit in-flight requests") from e
        for c_eng, (kp, vp) in zip(self._caches, new_pages):
            c_eng["k_pages"], c_eng["v_pages"] = kp, vp
        self._last = self._last.at[slot].set(
            jnp.asarray(h["last"], jnp.float32))
        self._lengths = self._lengths.at[slot].set(S0)

    # ---- live migration: export a decoding slot / admit it elsewhere -----
    def export_slot(self, rid: int) -> dict:
        """Export a request that is ACTIVELY DECODING as a sealed
        migration bundle and release its slot — the out half of live
        request migration (serving_cluster). The bundle carries
        everything a peer engine over the same weights needs to continue
        the stream mid-decode: the KV pages densified to host numpy, the
        last-logit row, the prompt ids, the tokens generated so far
        (the delivered count), and the decode-side request state
        (sampling, stops, logprobs, priority, remaining SLO).

        :meth:`admit_migrated` on the peer restores through the SAME
        jitted page scatter as a preemption restore, so a greedy stream
        continues token-identically. Queued / mid-prefill requests raise
        ValueError — they hold no KV worth shipping; re-place them from
        scratch instead."""
        if self._latent_mode:
            raise NotImplementedError(
                "migration is not supported in latent (MLA) mode — the "
                "compressed cache rows are engine-layout-specific")
        self._refuse_on_rings("migration")
        self._drain_in_flight()  # the bundle holds every token decoded
        slot = next((s for s, r in enumerate(self._slots)
                     if r is not None and r.rid == rid), None)
        if slot is None:
            raise ValueError(
                f"request {rid} holds no decoding slot (queued, "
                "prefilling, finished or unknown) — only active slots "
                "migrate; re-place queued requests from scratch")
        req = self._slots[slot]
        kv, nbytes = self._slot_kv_bundle(slot, req)
        now = time.perf_counter()
        bundle = seal_bundle({
            "kind": "migrate",
            "ids": np.asarray(req.ids, np.int64),
            "prompt_tokens": int(req.ids.size),
            "tokens": np.asarray(req.tokens, np.int64),
            "max_new_tokens": int(req.max_new_tokens),
            "sampling": list(req.sampling or self._sample_cfg),
            "stop_token_ids": (sorted(req.stop_token_ids)
                               if req.stop_token_ids else None),
            "want_logprobs": bool(req.want_logprobs),
            "logprobs": [float(x) for x in req.logprobs],
            # additive: the correctness-sentinel mark migrates with the
            # stream, so the DESTINATION engine audits the whole stream
            # end-to-end (the migration-leg audit invariant)
            "audit": req.audit,
            "priority": int(req.priority),
            "slo_remaining_s": (None if req.deadline == math.inf
                                else float(req.deadline - now)),
            "page_size": int(self.page_size),
            "bucket": int(kv["bucket"]),
            "kv_len": int(kv["kv_len"]),
            "layers": kv["layers"],
            "last": kv["last"],
        })
        self._release_slot(slot)
        self._m_bundle["migrate"].observe(float(nbytes))
        self._n_migrated_out += 1
        self._m_sched["migrate_out"].inc()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_MIGRATE_OUT, rid=rid,
                       engine=self._engine_label, slot=slot,
                       kv_len=int(kv["kv_len"]),
                       generated=len(req.tokens), bytes=nbytes)
        self._record_reason(rid, "migrated")
        self._trace_end(req, "migrated")
        req.slot = -1
        self._admit()     # the freed slot can refill immediately
        return bundle

    def admit_migrated(self, handoff: dict, on_token=None,
                       trace_ctx=None, on_shed=None) -> int:
        """Admit a mid-stream request exported by a peer engine's
        :meth:`export_slot` (same weights): the bundle's KV scatters back
        through the preemption-restore path and decode resumes exactly
        where the source engine stopped. Decode-side knobs (sampling,
        stops, logprobs, priority, SLO) come FROM THE BUNDLE — they must
        match the source request for the continuation to be
        token-identical — and ``on_token`` fires only for NEWLY generated
        tokens, so a relay appends seamlessly after the tokens it already
        delivered."""
        if not isinstance(handoff, dict):
            raise HandoffCorrupt(
                f"bundle is a {type(handoff).__name__}, not a dict")
        self._check_queue_bound(
            priority=int(handoff.get("priority", PRIORITY_DEFAULT)))
        if self._latent_mode:
            raise NotImplementedError(
                "migration is not supported in latent (MLA) mode")
        self._refuse_on_rings("migration")
        verify_bundle(handoff, kind="migrate")
        bucket = int(handoff["bucket"])
        if bucket % self.page_size != 0 or bucket > self.max_len:
            raise ValueError(
                f"migration bucket {bucket} does not fit this engine "
                f"(page_size {self.page_size}, max_len {self.max_len}) — "
                "source and destination engines must share the serving "
                "shape")
        if len(handoff["layers"]) != len(self._caches):
            raise ValueError(
                f"migration bundle carries {len(handoff['layers'])} "
                f"layers, engine has {len(self._caches)} — different "
                "models?")
        ids = np.asarray(handoff["ids"]).reshape(-1)
        tokens = [int(t) for t in np.asarray(handoff["tokens"]).reshape(-1)]
        kv_len = int(handoff["kv_len"])
        if kv_len != ids.size + len(tokens):
            raise HandoffCorrupt(
                f"migration bundle is inconsistent: kv_len {kv_len} != "
                f"prompt {ids.size} + generated {len(tokens)}")
        max_new = int(handoff["max_new_tokens"])
        self._require_fit(int(ids.size), max_new)
        samp = handoff.get("sampling")
        sampling = self._merge_sampling(*samp) if samp else None
        slo_rem = handoff.get("slo_remaining_s")
        rid = self._next_rid
        self._next_rid += 1
        self._n_requests += 1
        self._m_req_admitted.inc()
        req = _Request(rid, ids, max_new, sampling, on_token,
                       stop_token_ids=handoff.get("stop_token_ids"),
                       want_logprobs=bool(handoff.get("want_logprobs")),
                       priority=handoff.get("priority"),
                       slo_ms=(slo_rem * 1000.0 if slo_rem is not None
                               else None))
        req.on_shed = on_shed
        req.tokens = tokens
        req.logprobs = [float(x) for x in handoff.get("logprobs") or []]
        # the sentinel mark rides the bundle (additive — absent from
        # pre-audit bundles): a migrated-in stream finishes HERE, so the
        # audit obligation lands on this engine
        aud = handoff.get("audit")
        if aud in ("shadow", "ondemand") and self.sentinel.enabled \
                and self.sentinel.auditable:
            req.audit = aud
            req.want_logprobs = True
            if aud == "ondemand":
                self.sentinel.register_forced(rid)
        # resume rides the preemption-restore path: _admit sees
        # req.resume and scatters the KV back, no model forward runs
        req.resume = seal_bundle({
            "bucket": bucket, "kv_len": kv_len,
            "layers": handoff["layers"], "last": handoff["last"]})
        if self.kvatlas.enabled:
            # the bundle parks host-side until a slot frees and the
            # restore scatters it back (unpark in _restore_into)
            self.kvatlas.park(rid, int(
                sum(k.nbytes + v.nbytes for k, v in handoff["layers"])))
        self._trace_submit(req, trace_ctx)
        self._queue.append(req)
        self._fr_submit(req)
        self._n_migrated_in += 1
        self._m_sched["migrate_in"].inc()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_MIGRATE_IN, rid=rid,
                       engine=self._engine_label, generated=len(tokens),
                       kv_len=kv_len, prompt_tokens=int(ids.size))
        self._admit()
        return rid

    def logprobs(self, rid: int):
        """Chosen-token logprobs (model's raw distribution) for a
        FINISHED request, aligned with its generated ids; None once
        evicted from the retention window or while in flight."""
        return self._finished_logprobs.get(rid)

    def cancel(self, rid: int) -> bool:
        """``_RequestBookkeeping.cancel``; a row of the step in flight
        that belonged to ``rid`` is discarded when that step retires, and
        a step left decoding for no one is retired here."""
        live = super().cancel(rid)
        if live and self.num_active == 0:
            self._drain_in_flight()
        return live

    def step(self) -> Dict[int, np.ndarray]:
        """Retire ONE decode step: one token for every slot that was
        active in it (sample + forward fused into a single device
        dispatch); returns newly finished requests {rid: generated ids}.

        The plain one-token step keeps one step IN FLIGHT: a call
        enqueues step N + 1 (the program carries the engine's lengths, so
        its inputs are all device outputs of step N), then fetches step
        N's tokens, retires N and admits while N + 1 runs. A finish by
        ``max_new_tokens`` is known before N + 1 is enqueued and that
        slot is not part of it; a finish by eos / stop token, or a
        ``cancel``, is learnt one step late and the slot's row of N + 1
        is discarded (never delivered; ``serving_decode_discarded_rows_
        total``). Every call still retires exactly one step, and once
        the last active slot's finish is known nothing stays in flight.
        The engine steps synchronously (``_drain_in_flight`` first)
        wherever the host must see step N's tokens to build step N + 1
        or reads the caches between steps: speculative steps, preemption,
        ``export_slot``, OOM degrade.

        With chunked prefill enabled, each step advances AT MOST one
        prefill chunk before the decode dispatch — a long prompt lands
        over many steps while live slots keep producing tokens, so the
        worst inter-token stall is one chunk-step instead of one full
        prefill."""
        if self._poisoned:
            raise RuntimeError(
                "ContinuousBatchEngine: a failed admission invalidated the "
                "page pool; rebuild the engine and resubmit requests")
        return self._profiled_step()

    def _step_decode(self, clk) -> Dict[int, np.ndarray]:
        """``step()``'s body; ``clk`` is the profiler's clock or None.
        Every phase is opened under its name (``PhaseClock.open``), so it
        is both timed and written to the profiler's trace.

        The plain one-token step keeps ONE step in flight: this call
        retires step N (enqueued by the call before, or here when nothing
        was in flight), and before it fetches N's tokens it enqueues
        step N + 1, whose inputs are all device outputs of N. ``dispatch``
        is that enqueue, ``sync`` the wait for N while N + 1 is queued,
        ``retire`` and the trailing ``admit`` run beside N + 1."""
        if clk is not None:
            clk.open("admit")
        self._admit()
        if clk is not None:
            clk.open("prefill")
        self._advance_chunk()
        if clk is not None:
            clk.close()
        spec = self.speculative_k is not None and self._spec_eligible()
        if spec:
            # the drafter reads the tokens of the step before
            self._drain_in_flight(clk)
        if self.num_active == 0:
            # nothing is in flight here unless a callback raised out of
            # the step that learnt the last finish
            self._drain_in_flight(clk)
            self._clear_dispatch_guard()
            return self._drain_finished()
        if clk is not None:
            clk.open("draft" if spec else "dispatch")
        # pre-dispatch blame + poison injection: arm the deathnote with
        # the rids entering this dispatch (covers the speculative branch
        # too — it is the same device dispatch boundary)
        self._dispatch_guard([r for r in self._slots if r is not None])
        if spec:
            return self._step_speculative(clk)
        try:
            with _frec.incident_scope("engine.step"):
                if self._in_flight is None:
                    self._in_flight = self._enqueue_decode()
                cur = self._in_flight
                # step N + 1 goes out before N's tokens are fetched:
                # the host's share of a step hides behind the program
                self._in_flight = self._enqueue_decode()
        except _frec.XlaOom as e:
            # graceful degradation instead of an engine-loop death: shed
            # the most recently admitted slot typed, shrink the budget
            self._degrade_on_oom(None, where="step", exc=e)
            return self._drain_finished()
        fr_seq, n_rows = self._retire_decode(cur, clk)
        if self.num_active == 0:
            # the last finish is known: what is still in flight decodes
            # for no one
            self._drain_in_flight(clk)
        if clk is not None:
            clk.open("admit")  # trailing refill accumulates into admit
        self._admit()
        if clk is not None:
            clk.close()
            self.profiler.commit(
                active=n_rows,
                kv_len=max((int(r.ids.size) + len(r.tokens)
                            for r in self._slots if r is not None),
                           default=0),
                fr_seq=fr_seq)
        return self._drain_finished()

    def _enqueue_decode(self) -> Optional[_Flight]:
        """Enqueue ONE plain one-token decode step for every row that
        still owes a token, and return its record; None, with nothing
        enqueued and no key drawn, where no row does. A row of the step
        still in flight has one token on the way that the host has not
        seen: it decodes again unless that token fills its budget (a
        finish by length is known beforehand; one by eos, a stop token
        or ``cancel`` is learnt a step late, and ``_retire_decode`` discards
        the row). The program takes ``_lengths`` and the per-slot
        ``advance`` code and returns the lengths advanced, so a steady
        step costs the host the key split and this one call; the code
        (and the per-row sampling knobs) are uploaded again only when
        they change."""
        before = self._in_flight
        owed = {id(r) for _, r in before.rows} if before is not None else ()
        rows = [(s, r) for s, r in enumerate(self._slots)
                if r is not None and (id(r) not in owed or
                                      len(r.tokens) + 1 < r.max_new_tokens)]
        if not rows:
            return None
        t_dispatch = time.perf_counter()
        code = [0] * self.max_batch
        for s in self._chunking:
            code[s] = -1  # held at its chunk frontier
        for s, _ in rows:
            code[s] = 1
        # per-row program only while a DECODING row carries an override —
        # all-default mixes keep the static program (no per-row filter
        # sorts), and the engine falls back to it as soon as the
        # overriding requests retire
        knobs = None
        if any(r.sampling is not None for _, r in rows):
            knobs = [self._sample_cfg] * self.max_batch
            for s, r in rows:
                knobs[s] = tuple(r.sampling or self._sample_cfg)
            knobs = tuple(knobs)
        key = (tuple(code), knobs)
        if key != self._step_inputs[0]:
            arrays = [jnp.asarray(np.asarray(code, np.int32))]
            if knobs is not None:
                arrays += [jnp.asarray(np.asarray([k[i] for k in knobs], t))
                           for i, t in enumerate((bool, np.float32,
                                                  np.int32, np.float32))]
            self._step_inputs = (key, arrays)
        advance, *rows_knobs = self._step_inputs[1]
        if knobs is not None:
            step = _get_select_decode_rows(self.model, self.max_len)
        else:
            step = _get_select_decode(self.model, self.max_len,
                                      *self._sample_cfg)
        nxt, logps, self._last, self._caches, self._lengths = step(
            self._last, _random.next_key(), *rows_knobs, self._caches,
            self._lengths, advance)
        self._m_dispatch["ahead" if before is not None else "drained"].inc()
        moe = (None if step.moe_counts is None
               else (step.moe_counts, self._moe_prefills))
        return _Flight(nxt, logps, rows, t_dispatch, moe, step.gdn_updates)

    def _drain_in_flight(self, clk=None) -> None:
        """Fetch and retire the step in flight, if there is one, so that
        the host's bookkeeping and the device's state agree again: called
        wherever the host must see step N's tokens before it can build
        step N + 1 (speculation's drafter) or reads the caches between
        steps (preemption, migration export, OOM degrade, the engine
        loop's exit), and when the batch empties under a step. What
        finishes here is returned by the next ``step()``."""
        flight, self._in_flight = self._in_flight, None
        if flight is not None:
            self._retire_decode(flight, clk)
            if clk is not None:
                clk.close()

    def _retire_decode(self, flight: _Flight, clk=None):
        """Fetch one enqueued step (``sync``) and deliver it (``retire``):
        per-row bookkeeping, finishes, slot release, then the callbacks.
        A row is delivered only if its slot still holds the request the
        step decoded for; the others (finished by eos or a stop token in
        the step before, cancelled since) are discarded and counted.
        Returns (flight-recorder sequence number, rows delivered)."""
        if clk is not None:
            clk.open("sync")
        # THE one deliberate device->host sync of the decode loop: every
        # other host conversion below reads these already-fetched arrays
        toks = np.asarray(flight.nxt)
        lps = np.asarray(flight.logps)
        # outputs of the same program (and of prefills enqueued before
        # it): the fetch waits for nothing the tokens did not wait for
        moe = None if flight.moe is None else tuple(
            None if a is None else np.asarray(a) for a in flight.moe)
        if clk is not None:
            clk.open("retire")
        if flight.gdn is not None:
            self._m_gdn_updates.inc(int(np.asarray(flight.gdn)))
        if moe is not None:
            self._count_moe(moe[0])
            if moe[1] is not None:
                self._count_moe(moe[1] - self._moe_prefills_seen)
                self._moe_prefills_seen = moe[1]
        if self._in_flight is None:
            self._clear_dispatch_guard()  # step success: blame record erased
        inj = _chaos.active()
        if inj is not None and "engine.logits" in inj.plan.points():
            # chaos: one emitted token flipped AFTER the device sync —
            # the silent-drift drill the correctness sentinel must
            # catch, and replay_divergence must bisect back to the plan
            fault = inj.fire("engine.logits")
            if fault is not None and fault.action == "perturb_logit":
                s0 = next((s for s, r in flight.rows
                           if self._slots[s] is r), None)
                if s0 is not None:
                    vocab = int(self.model.config.vocab_size)
                    t_new = (int(toks[s0]) + 1) % vocab
                    if self.eos_token_id is not None \
                            and t_new == int(self.eos_token_id):
                        t_new = (t_new + 1) % vocab
                    toks = toks.copy()
                    toks[s0] = t_new
        # ONE clock for every token this step produced (they came from
        # one dispatch). The step's span starts when it was enqueued, or
        # when the step before it was fetched if that came later: behind
        # a step in flight the time from the enqueue holds that step too
        now = time.perf_counter()
        t_start = max(flight.t_dispatch, self._t_fetched)
        self._t_fetched = now
        self._m_step.observe(now - t_start)
        self._n_steps += 1
        fr_seq = 0
        rec = _frec.RECORDER
        if rec.enabled:
            # ONE event per fused dispatch (not per token): the black box
            # stays O(steps) however many slots decode concurrently
            fr_seq = rec.record(_frec.EV_STEP, engine=self._engine_label,
                                active=self.num_active,
                                seconds=now - t_start)
        # perf_counter and perf_counter_ns share one clock, so the span
        # bounds come from the timestamps already taken for the metric
        trace_on = _tracing.get_tracer().enabled
        t0_ns, t1_ns = (int(t_start * 1e9), int(now * 1e9)) \
            if trace_on else (0, 0)
        retiring = []
        events = []  # (cb, rid, token, done): fired AFTER bookkeeping, so a
        # raising callback cannot leave slot state desynced from the
        # already-advanced device step
        at = self.kvatlas
        at_on = at.enabled  # hoisted: one predicate for the whole loop
        n_rows = cached = over_window = 0
        for s, req in flight.rows:
            if self._slots[s] is not req:
                continue
            n_rows += 1
            cached += int(req.ids.size) + len(req.tokens)
            if self._ring_window is not None and (
                    int(req.ids.size) + len(req.tokens) > self._ring_window):
                over_window += 1
            req.dispatches += 1
            t = int(toks[s])
            req.tokens.append(t)
            if at_on:
                at.advance(s)
            lp = float(lps[s])
            if req.want_logprobs:
                req.logprobs.append(lp)
            self._observe_token(req, now)
            if trace_on:
                self._trace_decode_step(req, t0_ns, t1_ns)
            stopped = ((self.eos_token_id is not None
                        and t == self.eos_token_id)
                       or (req.stop_token_ids is not None
                           and t in req.stop_token_ids))
            finished = len(req.tokens) >= req.max_new_tokens or stopped
            if finished:
                # recorded BEFORE the on_token callbacks fire, so a
                # front-end reading it at the done event sees the truth
                self._record_reason(
                    req.rid, "stop" if stopped else "length",
                    logprobs=(list(req.logprobs) if req.want_logprobs
                              else None))
            if req.on_token is not None:
                events.append((req.on_token, req.on_token_arity,
                               req.rid, t, lp, finished))
            if finished:
                retiring.append(s)
        # only what was delivered counts as decoded: a discarded row
        # would inflate the rows and the context a step is credited with
        self._m_decode_rows.inc(n_rows)
        self._m_decode_cached.inc(cached)
        self._m_rows_over_window.inc(over_window)
        self._m_discarded.inc(len(flight.rows) - n_rows)
        for s in retiring:
            req = self._slots[s]
            self._finished[req.rid] = np.asarray(req.tokens, np.int64)
            self._count_finished(req)
            self._release_slot(s)
            self._trace_end(req, "ok")
        # stream AFTER state is consistent: every callback fires even if an
        # earlier one raises; the first exception then propagates
        first_exc = None
        for cb, arity, rid, t, lp, done in events:
            try:
                if arity >= 4:
                    cb(rid, t, done, lp)
                else:
                    cb(rid, t, done)
            except BaseException as e:  # noqa: BLE001  # pdlint: disable=silent-exception -- collected, first one re-raised below
                if first_exc is None:
                    first_exc = e
        if first_exc is not None:
            raise first_exc
        return fr_seq, n_rows

    def _count_decode_dispatch(self):
        """One speculative dispatch left the host (synchronous: nothing
        was in flight): the rows that decode in it and the K/V rows the
        attention reads for them (prompt + tokens generated so far, per
        row), from the host's own bookkeeping. The one-token step counts
        the same in ``_retire_decode``, for the rows it delivers."""
        self._m_dispatch["drained"].inc()
        rows = cached = 0
        for r in self._slots:
            if r is not None:
                rows += 1
                cached += int(r.ids.size) + len(r.tokens)
        self._m_decode_rows.inc(rows)
        self._m_decode_cached.inc(cached)

    def _count_prefill(self, n_tokens: int, bucket: int, impl: str):
        """One prefill program enqueued: the real tokens it computes, the
        bucket it pads them to, and the implementation its attention took
        when the program was traced (``traced_attention_impl``). On a
        model with linear-attention layers, also the chunks the gated
        delta prefill kernel runs and skips in each of them."""
        self._m_prefill_prompt.inc(n_tokens)
        self._m_prefill_bucket.inc(bucket)
        self._m_prefill_attention[impl].inc()
        if self._state_layers:
            from .ops.pallas.gated_delta import CHUNK

            run = -(-n_tokens // CHUNK)
            layers = len(self._state_layers)
            self._m_gdn_chunks["run"].inc(run * layers)
            self._m_gdn_chunks["skipped"].inc(
                (-(-bucket // CHUNK) - run) * layers)

    def _dispatch_span(self):
        """Annotation-only ``engine/<phase>/prefill_dispatch`` around the
        calls that enqueue an admission's programs, so a trace tells
        enqueueing from bookkeeping."""
        return self.profiler.span("prefill_dispatch")

    # ---- speculative decoding: multi-token steps ------------------------
    def _spec_eligible(self) -> bool:
        """Speculation verifies against the GREEDY choice, so it is exact
        only while every active slot decodes greedily (engine default or
        per-request override; temperature ~ 0 counts as greedy exactly
        like sample_logits). A dispatch with any sampling slot active
        falls back to the one-token step — the engine re-enters
        speculation as soon as the sampling requests retire."""
        for r in self._slots:
            if r is None:
                continue
            do_sample, temperature, _, _ = r.sampling or self._sample_cfg
            if do_sample and temperature > 1e-6:
                return False
        return True

    def _step_speculative(self, clk=None) -> Dict[int, np.ndarray]:
        """One MULTI-token decode step: the host n-gram drafter proposes
        up to k-1 tokens per active slot from the slot's own prompt+token
        history, ONE batched verify dispatch (generation._SpecDecodeStep)
        forwards every slot's chunk [greedy, d_1..d_{k-1}] at per-row
        paged positions, and accepted runs advance each slot by a
        VARIABLE amount — rejected-draft KV parks above the new frontier
        exactly like chunked prefill's throwaway writes, where the next
        chunk's scatter overwrites it before lengths can reach it.
        Token-identity to the one-token greedy step is by construction:
        every emitted token equals the target's greedy choice at its
        position (and carries the same raw-distribution logprob)."""
        k = self.speculative_k
        t_dispatch = time.perf_counter()
        for c in self._caches:
            c["lengths"] = self._lengths  # engine-owned (masks stale +1s)
        # host drafter: pure bookkeeping-side work between dispatches —
        # padding rides the dispatch for slots with no history match and
        # can only be "accepted" when it equals the true greedy token
        from .speculative import ngram_propose

        drafts = np.zeros((self.max_batch, k - 1), np.int32)
        n_drafted = 0
        if k > 1:
            for s, r in enumerate(self._slots):
                if r is None:
                    continue
                hist = np.concatenate(
                    [r.ids, np.asarray(r.tokens, np.int64)]) \
                    if r.tokens else r.ids
                # the lookup's FIRST token predicts the same position the
                # in-dispatch argmax (g0) already decides, so the drafts
                # that ride the chunk are its CONTINUATION c_1..c_{k-1}
                # — using c_0 as d_1 would shift every cyclic proposal
                # off by one and reject whole runs the history predicted
                prop = ngram_propose(hist, k, self.speculative_ngram)
                if prop.size > 1:
                    use = prop[1:]
                    drafts[s, :use.size] = use
                    n_drafted += int(use.size)
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SPEC_PROPOSE, engine=self._engine_label,
                       active=self.num_active, k=k, drafted=n_drafted)
        if clk is not None:
            clk.open("dispatch")  # closes draft: host n-gram propose
        try:
            with _frec.incident_scope("engine.step"):
                step = _get_spec_decode(self.model, self.max_len, k)
                emitted, n_emit, logps, self._last, self._caches = step(
                    self._last, jnp.asarray(drafts), self._caches)
        except _frec.XlaOom as e:
            self._degrade_on_oom(None, where="step", exc=e)
            return self._drain_finished()
        self._count_decode_dispatch()
        if clk is not None:
            clk.open("sync")
        # THE deliberate device->host sync of the speculative decode
        # loop: one dispatch produced all three arrays, the first
        # conversion blocks, the other two read already-fetched results
        toks = np.asarray(emitted)   # pdlint: disable=host-sync -- the step's one deliberate token fetch (host retirement needs the ints)
        n_row = np.asarray(n_emit)   # pdlint: disable=host-sync -- same dispatch as toks; variable per-slot advance drives host bookkeeping
        lps = np.asarray(logps)      # pdlint: disable=host-sync -- same dispatch as toks; the OpenAI logprobs field
        if clk is not None:
            clk.open("retire")
        self._clear_dispatch_guard()  # step success: blame record erased
        now = time.perf_counter()
        self._m_step.observe(now - t_dispatch)
        self._n_steps += 1
        self._n_spec_steps += 1
        fr_seq = 0
        if rec.enabled:
            fr_seq = rec.record(_frec.EV_STEP, engine=self._engine_label,
                                active=self.num_active,
                                seconds=now - t_dispatch)
            rec.record(_frec.EV_SPEC_VERIFY, engine=self._engine_label,
                       active=self.num_active, k=k,
                       seconds=now - t_dispatch)
        trace_on = _tracing.get_tracer().enabled
        t0_ns, t1_ns = (int(t_dispatch * 1e9), int(now * 1e9)) \
            if trace_on else (0, 0)
        retiring = []
        events = []
        adv = np.zeros(self.max_batch, np.int64)
        accepted_total = emitted_total = slot_rounds = 0
        at = self.kvatlas
        at_on = at.enabled
        for s, req in enumerate(self._slots):
            if req is None:
                continue
            req.dispatches += 1
            n = int(n_row[s])
            slot_rounds += 1
            # deliver the accepted run, truncated at the request's stop
            # condition (eos / stop set / budget) — tokens past a stop
            # were never part of the greedy stream the client sees
            deliver = []
            stopped = False
            for j in range(n):
                t = int(toks[s, j])
                deliver.append(t)
                if ((self.eos_token_id is not None
                     and t == self.eos_token_id)
                        or (req.stop_token_ids is not None
                            and t in req.stop_token_ids)):
                    stopped = True
                    break
                if len(req.tokens) + len(deliver) >= req.max_new_tokens:
                    break
            for j, t in enumerate(deliver):
                req.tokens.append(t)
                if req.want_logprobs:
                    req.logprobs.append(float(lps[s, j]))
                self._observe_token(req, now)
            if at_on and deliver:
                # ledger frontier = delivered tokens only; rejected-draft
                # KV above it is garbage the next scatter overwrites, so
                # it is rightly uncounted
                at.advance(s, len(deliver))
            req.spec_rounds += 1
            req.spec_accepted += len(deliver) - 1
            accepted_total += len(deliver) - 1
            emitted_total += len(deliver)
            self._m_spec_accept.observe(len(deliver) - 1)
            if trace_on:
                self._trace_decode_step(req, t0_ns, t1_ns)
            finished = stopped or len(req.tokens) >= req.max_new_tokens
            if finished:
                self._record_reason(
                    req.rid, "stop" if stopped else "length",
                    logprobs=(list(req.logprobs) if req.want_logprobs
                              else None))
                retiring.append(s)
            else:
                adv[s] = len(deliver)   # == n: truncation always retires
            if req.on_token is not None:
                for j, t in enumerate(deliver):
                    done = finished and j == len(deliver) - 1
                    events.append((req.on_token, req.on_token_arity,
                                   req.rid, t, float(lps[s, j]), done))
        self._n_spec_emitted += emitted_total
        self._n_spec_accepted += accepted_total
        self._n_spec_slot_rounds += slot_rounds
        if rec.enabled:
            proposed = max(slot_rounds * (k - 1), 1)
            rec.record(_frec.EV_SPEC_ACCEPT, engine=self._engine_label,
                       accepted=accepted_total, emitted=emitted_total,
                       rate=accepted_total / proposed)
        # variable per-slot advance; reserved (mid-chunk) slots HOLD at
        # their frontier exactly as in the one-token step — the k
        # throwaway tokens the fixed-shape dispatch wrote for them park
        # where the next chunk's scatter lands
        active = np.array([r is not None for r in self._slots])
        adv_j = jnp.asarray(adv, jnp.int32)
        if self._chunking:
            hold = np.zeros(self.max_batch, bool)
            for s in self._chunking:
                hold[s] = True
            self._lengths = jnp.where(
                jnp.asarray(active), self._lengths + adv_j,
                jnp.where(jnp.asarray(hold), self._lengths,
                          jnp.zeros_like(self._lengths)))
        else:
            self._lengths = jnp.where(jnp.asarray(active),
                                      self._lengths + adv_j,
                                      jnp.zeros_like(self._lengths))
        for s in retiring:
            req = self._slots[s]
            self._finished[req.rid] = np.asarray(req.tokens, np.int64)
            self._count_finished(req)
            self._release_slot(s)
            self._trace_end(req, "ok")
        # stream AFTER state is consistent (same protocol as step())
        first_exc = None
        for cb, arity, rid, t, lp, done in events:
            try:
                if arity >= 4:
                    cb(rid, t, done, lp)
                else:
                    cb(rid, t, done)
            except BaseException as e:  # noqa: BLE001  # pdlint: disable=silent-exception -- collected, first one re-raised below
                if first_exc is None:
                    first_exc = e
        if first_exc is not None:
            raise first_exc
        if clk is not None:
            clk.open("admit")  # trailing refill accumulates into admit
        self._admit()
        if clk is not None:
            clk.close()
            self.profiler.commit(
                active=int(active.sum()),
                kv_len=max((int(r.ids.size) + len(r.tokens)
                            for r in self._slots if r is not None),
                           default=0),
                fr_seq=fr_seq)
        return self._drain_finished()

    def run_until_done(self, max_steps: Optional[int] = None) -> Dict[int, np.ndarray]:
        out: Dict[int, np.ndarray] = {}
        steps = 0
        while self._queue or self.num_active or self._chunking:
            out.update(self.step())
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        out.update(self._drain_finished())
        return out

    # ---- internals ----------------------------------------------------------
    def _drain_finished(self):
        done, self._finished = self._finished, {}
        return done

    def _alloc_slot(self) -> int:
        """Pick a free slot index, or -1 when none — the acquire half of
        the _alloc_slot/_release_slot pair the lifecycle rule tracks."""
        for s, r in enumerate(self._slots):
            if r is None and s not in self._chunking:
                return s
        return -1

    #: a prompt longer than this pads to half of its octave, in steps of
    #: at most this many tokens
    _HALF_OCTAVES_ABOVE = 1024

    def _bucket(self, n: int) -> int:
        """Prompt-length bucket, page-aligned and capped at ``max_len``:
        up to ``_HALF_OCTAVES_ABOVE`` (T) tokens the next power of two;
        beyond it, for the power of two ``octave < n <= 2 * octave``, the
        next multiple of ``min(octave // 2, T)``: 1536, 2048, then steps
        of 1024. A padded token costs what a real one costs, and a prompt
        over T pads under a third (the next power of two: up to half).
        Finer steps pad less but each bucket is one more prefill and
        scatter program in set-up: quarter octaves cost the docs cell 8%
        of ``setup_s`` (PERF.md, PR 38). The programs are O(log) short
        ones, 1536, 2048 and ``max_len / T`` long ones, each built when a
        prompt first needs it."""
        page = self.page_size
        limit = self._HALF_OCTAVES_ABOVE
        if n > limit:
            octave = 1 << ((n - 1).bit_length() - 1)
            step = -(-min(octave // 2, limit) // page) * page
            return min(-(-n // step) * step, self.max_len)
        b = page
        while b < n:
            b *= 2
        return min(b, self.max_len)

    # ---- crash containment: deathnote blame + graceful OOM degrade ------
    def _dispatch_guard(self, reqs: List[_Request]):
        """Pre-dispatch blame boundary, armed immediately before every
        device dispatch (admission prefill carries the one admitting
        request; a decode step carries every active slot):

        - the **deathnote** (supervisor.Deathnote, cluster workers only)
          atomically records the request ids entering the dispatch, with
          those of a decode step still in flight, and is erased when a
          step succeeds with none in flight behind it — if the process
          dies mid-dispatch the supervisor blames exactly these rids,
          not every request the router had in flight here;
        - the ``engine.dispatch`` **chaos point** hands the injector the
          same ids: a planned ``crash_on_rid`` fault kills the process
          the moment its poison rid enters a dispatch (``os._exit``,
          SIGKILL-grade — the deathnote survives to testify).

        Free when neither a deathnote nor a chaos plan is installed
        (solo engines: two attribute reads per step)."""
        dn = self.deathnote
        inj = _chaos.active()
        if dn is None and inj is None:
            return
        if self._in_flight is not None:
            # a decode step is still on the device: its rows stay blamed
            reqs = reqs + [r for _, r in self._in_flight.rows
                           if r not in reqs]
        rids = [r.ext_id if r.ext_id is not None else f"rid:{r.rid}"
                for r in reqs]
        if dn is not None:
            if rids:
                dn.arm(rids)
            else:
                dn.clear()
        if inj is not None and rids:
            fault = inj.fire("engine.dispatch", rids=tuple(rids))
            if fault is not None and fault.action == "crash_on_rid":
                os._exit(134)

    def _clear_dispatch_guard(self):
        dn = self.deathnote
        if dn is not None:
            dn.clear()

    def _degrade_on_oom(self, req: Optional[_Request], where: str, exc):
        """Graceful OOM degradation: an XLA RESOURCE_EXHAUSTED was
        caught at a dispatch boundary (``where`` = "admit" | "step").
        Instead of poisoning the engine loop, shed the TRIGGERING
        request typed (the admitting request, or the most recently
        admitted active slot — the marginal occupancy that broke the
        budget), durably shrink ``max_active_slots`` to one below the
        occupancy that OOM'd (floor 1), and emit ``sched.degrade`` so
        /health and debug_state() show the reduced budget. The incident
        bundle was already written by the dispatch's incident_scope."""
        self._drain_in_flight()  # a victim is chosen among settled slots
        occupancy = (self.num_active + len(self._chunking)
                     + (1 if req is not None else 0))
        prev = self.max_active_slots
        self.max_active_slots = max(1, min(prev, occupancy - 1))
        victim = req
        if victim is None:
            cands = [r for r in self._slots if r is not None]
            victim = max(cands, key=lambda r: (r.t_admit or 0.0, r.rid)) \
                if cands else None
        if self.kvatlas.enabled:
            self.kvatlas.set_budget(self.max_active_slots)
        if (victim is not None and victim.slot >= 0
                and self._slots[victim.slot] is victim):
            self._release_slot(victim.slot)
            victim.slot = -1
        self._n_degraded += 1
        self._m_sched["degrade"].inc()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_DEGRADE, engine=self._engine_label,
                       rid=(victim.rid if victim is not None else None),
                       where=where,
                       max_active_slots=self.max_active_slots,
                       previous=prev)
        if victim is None:
            return
        self._n_shed += 1
        self._m_req_shed.inc()
        self._m_sched_shed.inc()
        msg = (f"request {victim.rid} shed: device out of memory during "
               f"{where}; engine degraded max_active_slots "
               f"{prev} -> {self.max_active_slots} ({exc})")
        if rec.enabled:
            rec.record(_frec.EV_SCHED_SHED, rid=victim.rid,
                       engine=self._engine_label,
                       priority=victim.priority, where="oom",
                       miss_ms=None, queue_depth=len(self._queue))
        self._record_reason(victim.rid, "shed")
        self._trace_end(victim, "shed")
        if victim.on_shed is not None:
            victim.on_shed(victim.rid, {
                "where": "oom", "error": msg, "miss_ms": None,
                "retry_after": self._retry_after_estimate()})

    @_build_events.cause("engine/admit")
    def _admit(self):
        if self._poisoned and self._queue:
            raise RuntimeError(
                "ContinuousBatchEngine: a failed admission invalidated the "
                "page pool; rebuild the engine and resubmit requests")
        while self._queue:
            now = time.perf_counter()
            # deadline gate BEFORE the pop, against the same clock: a
            # request whose budget is spent sheds typed here — it can
            # never be admitted after its deadline expired
            self._shed_expired(now)
            if not self._queue:
                return
            if (self.max_active_slots < self.max_batch
                    and self.num_active + len(self._chunking)
                    >= self.max_active_slots):
                # OOM-degraded budget: the engine provably cannot serve
                # max_batch concurrent slots on this device — admission
                # respects the shrunken cap, the queue waits (and the
                # gate binds ONLY once degraded: at full budget the
                # slot-scan below owns the decision, so preemption
                # still runs at a full pool)
                return
            slot = self._alloc_slot()  # pdlint: disable=leak-path -- finder only: the slot is not reserved until _slots[slot] = req binds it, so a raise before that leaks nothing
            if slot < 0:
                # page pressure: a strictly-higher-priority queued request
                # may evict a low-priority slot's KV to host memory
                if not self._maybe_preempt(now):
                    return
                slot = self._alloc_slot()  # pdlint: disable=leak-path -- finder only, same as above
                if slot < 0:
                    return
            req = self._pop_next(now)
            t_adm = time.perf_counter()
            self._observe_admission(req, t_adm)
            self._trace_admit(req, slot)
            tracer = _tracing.get_tracer()
            if req.resume is not None:
                # a preempted request re-takes a slot: scatter the host
                # KV bundle back, no model forward runs
                with tracer.span(_tracing.SPAN_PREFILL, parent=req.span,
                                 attrs={"slot": slot, "restore": True}):
                    self._restore_into(slot, req)
                with tracer.use(req.span):
                    self._m_prefill.observe(time.perf_counter() - t_adm)
                self._slots[slot] = req
                req.slot = slot
                if self.kvatlas.enabled:
                    self.kvatlas.set_slot(
                        slot, int(req.ids.size) + len(req.tokens))
                self._fr_page_pressure()
                continue
            if self._start_chunked(slot, req, t_adm):
                # slot reserved; chunks advance one per step() so live
                # decodes keep flowing — see _advance_chunk
                continue
            self._dispatch_guard([req])
            try:
                with _frec.incident_scope("engine.admit"):
                    with tracer.span(
                            _tracing.SPAN_PREFILL, parent=req.span,
                            attrs={"slot": slot,
                                   "prompt_tokens": int(req.ids.size)}):
                        self._prefill_into(slot, req)
            except _frec.XlaOom as e:
                # graceful degradation: the admission forward OOM'd
                # BEFORE any donated scatter (scatter failures poison
                # with a plain RuntimeError) — shed the trigger typed,
                # shrink the budget, keep serving everyone else
                self._degrade_on_oom(req, where="admit", exc=e)
                continue
            with tracer.use(req.span):
                self._m_prefill.observe(time.perf_counter() - t_adm)
            self._slots[slot] = req
            req.slot = slot
            if self.kvatlas.enabled:
                self.kvatlas.set_slot(
                    slot, int(req.ids.size) + len(req.tokens))
            self._fr_page_pressure()

    # ---- preemption: KV eviction to host, restore on re-admission -------
    def _maybe_preempt(self, now: float) -> bool:
        """Under a full pool, evict the least-important active slot's KV
        pages to host memory when a STRICTLY more important request is
        queued (raw priority classes — aging never triggers a
        preemption, or same-class traffic would thrash). Returns True if
        a slot was freed."""
        if not self.enable_preemption or not self._queue:
            return False
        cand = self._peek_next(now)
        victim_slot, victim_key = -1, None
        for s, r in enumerate(self._slots):
            if r is None or r.priority <= cand.priority:
                continue
            # least important first; within a class the most recently
            # admitted loses (older work keeps its progress)
            key = (r.priority, r.t_admit if r.t_admit is not None else now)
            if victim_key is None or key > victim_key:
                victim_slot, victim_key = s, key
        if victim_slot < 0:
            return False
        victim = self._slots[victim_slot]
        self._drain_in_flight()  # the bundle holds every token decoded
        if self._slots[victim_slot] is victim:
            self._preempt_slot(victim_slot, by=cand)
        # else the victim finished in the drained step: its slot is free
        return True

    def _slot_kv_bundle(self, s: int, req: _Request):
        """Serialize slot ``s``'s device state to a sealed host bundle
        (the np.asarray reads ARE the deliberate device->host transfer):
        KV pages densified per layer, the last-logit row, the kv length.
        The one serializer behind preemption AND migration — both restore
        through the same jitted page scatter. Returns (bundle, nbytes)."""
        ps = self.page_size
        kv_len = int(req.ids.size) + len(req.tokens)
        bucket = self._bucket(kv_len)
        n_pages = bucket // ps
        base = s * self._pages_per_slot
        layers = []
        nbytes = 0
        for c in self._caches:
            pair = []
            for key in ("k_pages", "v_pages"):
                tiles = np.asarray(c[key][:, base:base + n_pages])
                hk, n, _, d = tiles.shape
                dense = np.moveaxis(tiles, 0, 2).reshape(n * ps, hk, d)
                nbytes += dense.nbytes
                pair.append(dense)
            layers.append(tuple(pair))
        last_row = np.asarray(self._last[s]).astype(np.float32)
        return seal_bundle({"bucket": bucket, "kv_len": kv_len,
                            "layers": layers, "last": last_row}), nbytes

    def _preempt_slot(self, s: int, by: Optional[_Request] = None):
        """Evict slot ``s``: serialize its KV pages + last-logit row to a
        host-side bundle, free the slot, and requeue the request with its
        generated tokens intact. A later _restore_into scatters the
        bundle back and decode resumes token-identically."""
        req = self._slots[s]
        bundle, nbytes = self._slot_kv_bundle(s, req)
        kv_len = int(bundle["kv_len"])
        req.resume = bundle
        req.n_preempted += 1
        self._n_preempted += 1
        self._release_slot(s)
        req.slot = -1
        self._queue.append(req)
        self._m_bundle["preempt"].observe(float(nbytes))
        if self.kvatlas.enabled:
            # device pages freed above; host bundle parked until restore
            self.kvatlas.park(req.rid, nbytes)
        self._m_sched["preempt"].inc()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_PREEMPT, rid=req.rid,
                       engine=self._engine_label, slot=s, kv_len=kv_len,
                       generated=len(req.tokens), bytes=nbytes,
                       priority=req.priority,
                       by_priority=(by.priority if by is not None
                                    else None))

    def _restore_into(self, slot: int, req: _Request):
        """Re-admission of a preempted request: scatter its host KV
        bundle back into the slot's pages (same jitted page scatter as a
        handoff admission) and seed sampling from the saved last-logit
        row — decode continues exactly where eviction stopped."""
        r, req.resume = req.resume, None
        verify_bundle(r)  # preemption and migration bundles are sealed
        bucket, kv_len = int(r["bucket"]), int(r["kv_len"])
        c_new = [{"k": jnp.asarray(k)[None], "v": jnp.asarray(v)[None]}
                 for k, v in r["layers"]]
        pages = [(c["k_pages"], c["v_pages"]) for c in self._caches]
        try:
            new_pages = self._scatter_fn(bucket)(
                pages, c_new, jnp.asarray([slot, kv_len], jnp.int32))
        except Exception as e:
            self._poisoned = True
            raise RuntimeError(
                "ContinuousBatchEngine: preemption restore failed after "
                "the page pool was donated; rebuild the engine and "
                "resubmit in-flight requests") from e
        for c_eng, (kp, vp) in zip(self._caches, new_pages):
            c_eng["k_pages"], c_eng["v_pages"] = kp, vp
        self._last = self._last.at[slot].set(
            jnp.asarray(r["last"], jnp.float32))
        self._lengths = self._lengths.at[slot].set(kv_len)
        if self.kvatlas.enabled:
            # the host bundle was consumed by the scatter; the slot's
            # ledger entry publishes at the _admit restore site
            self.kvatlas.unpark(req.rid)
        self._m_sched["restore"].inc()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_RESTORE, rid=req.rid,
                       engine=self._engine_label, slot=slot,
                       kv_len=kv_len, generated=len(req.tokens))

    # ---- chunked prefill: admission interleaved with decode -------------
    def _start_chunked(self, slot: int, req: _Request,
                       t_adm: float) -> bool:
        """Reserve ``slot`` for a chunked admission when the prompt is
        longer than one chunk. Handoff/restore admissions carry no local
        prefill and multimodal prompts prefill over merged embeddings
        (no token suffix to continue from) — those stay monolithic."""
        ct = self.prefill_chunk_tokens
        if (ct is None or req.handoff is not None
                or req.pixel_values is not None
                or int(req.ids.size) <= ct):
            return False
        span = None
        tracer = _tracing.get_tracer()
        if tracer.enabled:
            span = tracer.start_span(
                _tracing.SPAN_PREFILL, parent=req.span,
                attrs={"slot": slot, "chunked": True,
                       "prompt_tokens": int(req.ids.size)})
        self._chunking[slot] = _ChunkState(req, slot, t_adm, span)
        req.slot = slot
        return True

    def _advance_chunk(self) -> bool:
        """Advance ONE prefill chunk for the oldest reserved slot (FIFO —
        a single prefill in flight keeps the stall bound at one
        chunk-step). The first chunk seeds the cache via the bucketed
        prefill (or the shared-prefix path on a prefix-cache hit); later
        chunks reuse the suffix-prefill programs with src == dst. The
        final chunk publishes the slot: lengths set, request active."""
        if not self._chunking:
            return False
        slot, st = next(iter(self._chunking.items()))
        req = st.req
        ps = self.page_size
        S0 = int(req.ids.size)
        ct = self.prefill_chunk_tokens
        t0 = time.perf_counter()
        if st.pos == 0:
            src, n_pref = (-1, 0)
            if self.enable_prefix_cache:
                with _tracing.get_tracer().use(st.span):
                    src, n_pref = self._find_shared_prefix(req)
            if n_pref > 0:
                # prefix pages copy from the ACTIVE source slot and the
                # first chunk of the remaining suffix runs the model —
                # one fused dispatch, identical to a prefix admission
                pref_len = n_pref * ps
                take = min(ct, S0 - pref_len)
                self._run_suffix_chunk(slot, src, n_pref,
                                       req.ids[pref_len:pref_len + take])
                self.prefix_pages_reused += n_pref
                self._m_prefix_pages.inc(n_pref)
                if self.kvatlas.enabled:
                    self.kvatlas.note_prefix_hit(slot, req.ids, n_pref)
                st.pos = pref_len + take
            else:
                take = min(ct, S0)
                first = _Request(-1, req.ids[:take], 0)
                last, caches, _, bucket = self._bucketed_prefill(first)
                self._scatter_prefill(slot, last, caches, bucket, take)
                st.pos = take
        else:
            take = min(ct, S0 - st.pos)
            self._run_suffix_chunk(slot, slot, st.pos // ps,
                                   req.ids[st.pos:st.pos + take])
            st.pos += take
        done = st.pos >= S0
        if not done:
            # park the reserved slot's length AT the chunk frontier: the
            # interleaved decode dispatch writes a throwaway token's KV
            # at lengths[slot], and the next chunk's scatter starts
            # exactly there — the garbage never survives into a gather
            self._lengths = self._lengths.at[slot].set(st.pos)
            if self.kvatlas.enabled:
                # ledger frontier tracks landed chunks only (the
                # throwaway decode writes above it are uncounted garbage)
                self.kvatlas.set_slot(slot, st.pos, chunk=True)
        self._m_sched["chunk"].inc()
        rec = _frec.RECORDER
        if rec.enabled:
            rec.record(_frec.EV_SCHED_CHUNK, rid=req.rid,
                       engine=self._engine_label, slot=slot, pos=st.pos,
                       tokens=int(take), final=done,
                       seconds=time.perf_counter() - t0)
        if done:
            del self._chunking[slot]
            self._lengths = self._lengths.at[slot].set(S0)
            self._slots[slot] = req
            if self.kvatlas.enabled:
                self.kvatlas.set_slot(slot, S0)  # chunk flag clears here
            if st.span is not None:
                st.span.end()
            with _tracing.get_tracer().use(req.span):
                self._m_prefill.observe(time.perf_counter() - st.t_admit)
            self._fr_page_pressure()
        return True

    def _fr_page_pressure(self):
        """Sample kv page-pool pressure into the flight recorder after an
        admission — the reading that explains a later OOM or an admit
        stall. Host bookkeeping only (prompt + generated lengths); never
        touches device arrays."""
        rec = _frec.RECORDER
        if not rec.enabled:
            return
        ps = self.page_size
        used = 0
        for r in self._slots:
            if r is not None:
                used += -(-(int(r.ids.size) + len(r.tokens)) // ps)
        rec.record(_frec.EV_PAGE_PRESSURE, engine=self._engine_label,
                   pages_used=used,
                   pages_total=self.max_batch * self._pages_per_slot,
                   free_slots=self._slots.count(None))

    def _scatter_fn(self, bucket: int):
        """One jitted, page-DONATING scatter of a prefilled prompt into a
        slot's pages across all layers (admission would otherwise rebuild
        every layer's full page pool twice per request). Memoized on the
        MODEL (like the prefill/decode steps) so a fresh engine over the
        same model reuses the compiled scatter."""
        ps = self.page_size
        n_pages = bucket // ps
        rings = tuple(self._ring_pages)
        pps = self._pages_per_slot
        states = tuple(self._state_layers)

        def build():
            def kv_scatter(pages, bufs, where):
                """``where`` = int32 [slot, real tokens]. A layer that
                keeps the whole row takes the bucket's pages in order; a
                ring takes, for each of its pages, the NEWEST page of the
                prompt that maps to it (page j of the prompt lives in
                ring page j mod ring), which for a prompt shorter than
                the ring is page j."""
                slot, newest = where[0], (where[1] - 1) // ps
                out = []
                for i, ((kp, vp), c_new, ring) in enumerate(
                        zip(pages, bufs, rings)):
                    if i in states:
                        # a recurrent layer: the prompt's final state and
                        # conv window OVERWRITE the slot's
                        out.append(tuple(
                            jax.lax.dynamic_update_slice(
                                pool, c_new[key].astype(pool.dtype),
                                (slot,) + (0,) * (pool.ndim - 1))
                            for pool, key in ((kp, "state"), (vp, "conv"))))
                        continue
                    new = []
                    for pg, key in ((kp, "k"), (vp, "v")):
                        tiles = _page_tiles(c_new[key][0], ps)
                        if ring is not None and n_pages > ring:
                            at = jnp.arange(ring, dtype=jnp.int32)
                            src = newest - (newest - at) % ring
                            tiles = tiles[:, jnp.clip(src, 0, n_pages - 1)]
                        new.append(jax.lax.dynamic_update_slice(
                            pg, tiles.astype(pg.dtype),
                            (0, slot * (ring or pps), 0, 0)))
                    out.append(tuple(new))
                return out

            fn = jax.jit(kv_scatter, donate_argnums=(0,))
            fn._state = None  # _memoized_step refresh hook (stateless)
            return fn

        # pps is in the key: another engine over the same model (another
        # max_len) strides its slots differently
        return _memoized_step(self.model, "_page_scatter_fns",
                              (bucket, ps, pps, rings, states), build)

    # ---- prefix caching ------------------------------------------------------
    def _multimodal_merge_fn(self, ids_shape, px_shape):
        """Memoized jitted multimodal merge: tower + projector +
        placeholder scatter as one dispatch (keyed on prompt/image
        shapes). n_feats is static — add_request validated the count."""
        from .autograd import tape as _tape
        from .generation import _functional_weights
        from .tensor_class import wrap

        model = self.model
        n_feats = int(px_shape[0]) * model.features_per_image()

        def build():
            def multimodal_merge(state, ids, pixels):
                with _functional_weights(model, state), _tape.no_grad():
                    return unwrap(model.merge_multimodal(
                        wrap(ids), wrap(pixels), n_feats=n_feats))

            fn = jax.jit(multimodal_merge)
            step = lambda ids, pixels: fn(step._state, ids, pixels)
            step._state = dict(model.functional_state())
            return step

        return _memoized_step(model, "_mm_merge_steps",
                              (tuple(ids_shape), tuple(px_shape)), build,
                              maxsize=16)

    def _find_shared_prefix(self, req: _Request):
        """Longest page-aligned token prefix shared with an ACTIVE slot's
        prompt. Capped one token short of the whole prompt (the suffix
        prefill needs at least one token to produce the slot's logits).
        Traced as a child of the admission prefill span (which is
        current on the engine thread when tracing is on)."""
        with _tracing.get_tracer().span(_tracing.SPAN_PREFIX_LOOKUP) as sp:
            ps = self.page_size
            if req.pixel_values is not None:
                return -1, 0
            cap = (int(req.ids.size) - 1) // ps
            best_slot, best_n = -1, 0
            for s, r in enumerate(self._slots):
                if r is None or cap <= 0 or r.pixel_values is not None:
                    continue
                c = min(cap * ps, (int(r.ids.size) // ps) * ps)
                if c <= 0:
                    continue
                neq = req.ids[:c] != r.ids[:c]
                common = c if not neq.any() else int(np.argmax(neq))
                n = common // ps
                if n > best_n:
                    best_slot, best_n = s, n
            (self._m_prefix_hit if best_n > 0 else self._m_prefix_miss).inc()
            if best_n <= 0 and self.kvatlas.enabled:
                # hits index at the slot-aware admission sites instead
                self.kvatlas.note_prefix_miss()
            sp.set_attr("pages", best_n)
            return best_slot, best_n

    def _suffix_prefill_fn(self, n_pref: int, sb: int):
        """One jitted, page-DONATING admission with a cached prefix:
        gather the prefix KV from the SOURCE slot's pages, run the model
        over the suffix chunk at pos=prefix_len (append-attention fast
        path on TPU), and scatter BOTH the copied prefix tiles and the new
        suffix tiles into the destination slot's pages."""
        from .autograd import tape as _tape2
        from .nn.layer import functional_weights
        from .tensor_class import wrap as _wrap

        ps = self.page_size
        pref_len = n_pref * ps
        total = pref_len + sb
        n_suf = sb // ps
        model = self.model
        rope_len = self.max_len

        def build():
            def prefill_with_prefix(state, pages, suffix_ids, suffix_len,
                                    src_base, dst_base):
                with functional_weights(model, state), _tape2.no_grad():
                    caches = []
                    pref_tiles = []
                    for kp, vp in pages:
                        hk, _, _, d = kp.shape
                        rows = src_base + jnp.arange(n_pref)
                        tiles = (kp[:, rows], vp[:, rows])  # [hk,n_pref,ps,D]
                        pref_tiles.append(tiles)

                        def dense(t):
                            return jnp.moveaxis(
                                t.reshape(hk, pref_len, d), 0, 1)[None]

                        k_buf = jnp.zeros((1, total, hk, d), kp.dtype
                                          ).at[:, :pref_len].set(
                                              dense(tiles[0]))
                        v_buf = jnp.zeros((1, total, hk, d), vp.dtype
                                          ).at[:, :pref_len].set(
                                              dense(tiles[1]))
                        allowed = (jnp.arange(total)[None, :]
                                   < pref_len + suffix_len)
                        caches.append({
                            "k": k_buf, "v": v_buf, "allowed": allowed,
                            "pos": jnp.asarray(pref_len, jnp.int32)})
                    hidden, caches = model.llama.forward_cached(
                        _wrap(suffix_ids), caches, rope_len=rope_len)
                    h_last = jnp.take_along_axis(
                        unwrap(hidden),
                        (suffix_len - 1).reshape(1, 1, 1).astype(jnp.int32),
                        axis=1)
                    last = unwrap(model.lm_head_logits(
                        _wrap(h_last)))[:, 0, :]

                    new_pages = []
                    for (kp, vp), (tk, tv), c in zip(pages, pref_tiles,
                                                     caches):
                        hk, _, _, d = kp.shape
                        out_pair = []
                        for pg, tiles_pref, key in ((kp, tk, "k"),
                                                    (vp, tv, "v")):
                            buf = c[key] if not isinstance(c[key], Tensor) \
                                else unwrap(c[key])
                            suf_tiles = _page_tiles(
                                buf[0, pref_len:pref_len + sb], ps)
                            pg = jax.lax.dynamic_update_slice(
                                pg, tiles_pref.astype(pg.dtype),
                                (0, dst_base, 0, 0))
                            pg = jax.lax.dynamic_update_slice(
                                pg, suf_tiles.astype(pg.dtype),
                                (0, dst_base + n_pref, 0, 0))
                            out_pair.append(pg)
                        new_pages.append(tuple(out_pair))
                return last, new_pages

            fn = jax.jit(prefill_with_prefix, donate_argnums=(1,))
            fn._state = None  # _memoized_step refresh hook (state is an arg)
            return fn

        # max_len in the key is DEFENSIVE: a compiled program bakes a
        # rope_len-row cos/sin table. The pref_len + sb <= max_len compile
        # invariant already keeps any cross-engine reuse inside the baked
        # table, but keying on max_len makes reuse impossible by
        # construction rather than by invariant. maxsize sized for
        # chunked prefill: every chunk position is its own (n_pref, sb)
        # program, O(max_len / chunk) of them, LRU-kept across admissions
        return _memoized_step(self.model, "_suffix_prefill_fns",
                              (n_pref, sb, ps, self.max_len), build,
                              maxsize=64)

    def _prefill_with_prefix(self, slot: int, req: _Request, src: int,
                             n_pref: int):
        self._run_prefix_admission(slot, req, src, n_pref)

    def _latent_suffix_prefill_fn(self, n_pref: int, sb: int):
        """Jitted, buffer-DONATING prefix-cached admission for the latent
        layout: gather the prefix latent ROWS from the source slot, run
        the model over the suffix chunk at pos=prefix_len (absorbed-append
        path), and write prefix+suffix rows into the destination slot —
        token rows copy directly, no page tiling."""
        from .autograd import tape as _tape2
        from .nn.layer import functional_weights
        from .tensor_class import wrap as _wrap

        ps = self.page_size
        pref_len = n_pref * ps
        total = pref_len + sb
        model = self.model
        rope_len = self.max_len

        def build():
            def prefill_with_prefix_latent(state, bufs, suffix_ids,
                                           suffix_len, src, dst):
                with functional_weights(model, state), _tape2.no_grad():
                    caches = []
                    for ckv, kpe in bufs:
                        r, dp = ckv.shape[-1], kpe.shape[-1]
                        p_ckv = jax.lax.dynamic_slice(
                            ckv, (src, 0, 0), (1, pref_len, r))
                        p_kpe = jax.lax.dynamic_slice(
                            kpe, (src, 0, 0), (1, pref_len, dp))
                        ckv_t = jnp.zeros((1, total, r), ckv.dtype
                                          ).at[:, :pref_len].set(p_ckv)
                        kpe_t = jnp.zeros((1, total, dp), kpe.dtype
                                          ).at[:, :pref_len].set(p_kpe)
                        allowed = (jnp.arange(total)[None, :]
                                   < pref_len + suffix_len)
                        caches.append({
                            "c_kv": ckv_t, "k_pe": kpe_t,
                            "allowed": allowed,
                            "pos": jnp.asarray(pref_len, jnp.int32)})
                    hidden, caches = model.llama.forward_cached(
                        _wrap(suffix_ids), caches, rope_len=rope_len)
                    h_last = jnp.take_along_axis(
                        unwrap(hidden),
                        (suffix_len - 1).reshape(1, 1, 1).astype(jnp.int32),
                        axis=1)
                    last = unwrap(model.lm_head_logits(
                        _wrap(h_last)))[:, 0, :]
                    new_bufs = []
                    for (ckv, kpe), c in zip(bufs, caches):
                        ckv_t = (unwrap(c["c_kv"])
                                 if isinstance(c["c_kv"], Tensor)
                                 else c["c_kv"])
                        kpe_t = (unwrap(c["k_pe"])
                                 if isinstance(c["k_pe"], Tensor)
                                 else c["k_pe"])
                        new_bufs.append((
                            jax.lax.dynamic_update_slice(
                                ckv, ckv_t.astype(ckv.dtype), (dst, 0, 0)),
                            jax.lax.dynamic_update_slice(
                                kpe, kpe_t.astype(kpe.dtype), (dst, 0, 0)),
                        ))
                return last, new_bufs

            fn = jax.jit(prefill_with_prefix_latent, donate_argnums=(1,))
            fn._state = None  # _memoized_step refresh hook (state is an arg)
            return fn

        # max_len in the key: same defensive reasoning (and chunk-sized
        # maxsize) as _suffix_prefill_fn
        return _memoized_step(self.model, "_latent_suffix_prefill_fns",
                              (n_pref, sb, ps, self.max_len), build,
                              maxsize=64)

    def _run_suffix_chunk(self, slot: int, src: int, n_pref: int, suf):
        """ONE suffix-prefill dispatch: copy ``n_pref`` prefix pages/rows
        from slot ``src`` (== ``slot`` for a chunked-prefill
        continuation), run the model over ``suf`` at pos = n_pref *
        page_size, and scatter prefix + suffix into ``slot``. The shared
        core of prefix-cached admission AND chunk advancement — both
        layouts (paged and latent), the donation-failure poisoning
        protocol, and the last-logit update live HERE once. Does NOT set
        _lengths (callers publish the slot when the prompt completes)."""
        ps = self.page_size
        pref_len = n_pref * ps
        suf = np.asarray(suf).reshape(-1)
        sb = min(self._bucket(int(suf.size)), self.max_len - pref_len)
        ids = np.zeros((1, sb), np.int32)
        ids[0, :suf.size] = suf
        if self._latent_mode:
            fn = self._latent_suffix_prefill_fn(n_pref, sb)
            buf_keys, idx_scale = ("c_kv", "k_pe"), 1
            poison_what = "latent buffer pool"
        else:
            fn = self._suffix_prefill_fn(n_pref, sb)
            buf_keys, idx_scale = ("k_pages", "v_pages"), self._pages_per_slot
            poison_what = "page pool"
        bufs = [tuple(c[k] for k in buf_keys) for c in self._caches]
        try:
            with self._dispatch_span():
                last, new_bufs = traced_attention_impl(
                    fn, dict(self.model.functional_state()), bufs,
                    jnp.asarray(ids), jnp.asarray(int(suf.size), jnp.int32),
                    jnp.asarray(src * idx_scale, jnp.int32),
                    jnp.asarray(slot * idx_scale, jnp.int32))
        except Exception as e:
            self._poisoned = True
            raise RuntimeError(
                f"ContinuousBatchEngine: suffix prefill failed "
                f"after the {poison_what} was donated; rebuild the engine "
                f"and resubmit in-flight requests") from e
        self._count_prefill(int(suf.size), sb, fn.attention_impl)
        for c_eng, new in zip(self._caches, new_bufs):
            for k, v in zip(buf_keys, new):
                c_eng[k] = v
        self._last = self._last.at[slot].set(last[0].astype(jnp.float32))

    def _run_prefix_admission(self, slot, req, src, n_pref):
        """Prefix-cached MONOLITHIC admission: prefix copy + the whole
        remaining suffix in one dispatch, then publish the slot."""
        S0 = int(req.ids.size)
        self._run_suffix_chunk(slot, src, n_pref,
                               req.ids[n_pref * self.page_size:])
        self._lengths = self._lengths.at[slot].set(S0)
        self.prefix_pages_reused += n_pref
        self._m_prefix_pages.inc(n_pref)
        if self.kvatlas.enabled:
            # reuse depth rides to the slot's publish in _admit
            self.kvatlas.note_prefix_hit(slot, req.ids, n_pref)

    def _prefill_with_prefix_latent(self, slot: int, req: _Request,
                                    src: int, n_pref: int):
        self._run_prefix_admission(slot, req, src, n_pref)

    def _latent_scatter_fn(self, bucket: int):
        """Jitted, buffer-DONATING scatter of one prefilled prompt's latent
        rows into a slot's row across all layers (the latent-mode analog of
        _scatter_fn)."""
        def build():
            def kv_scatter_latent(bufs, prefill, slot):
                out = []
                for (ckv, kpe), c_new in zip(bufs, prefill):
                    out.append((
                        jax.lax.dynamic_update_slice(
                            ckv, c_new["c_kv"].astype(ckv.dtype),
                            (slot, 0, 0)),
                        jax.lax.dynamic_update_slice(
                            kpe, c_new["k_pe"].astype(kpe.dtype),
                            (slot, 0, 0)),
                    ))
                return out

            fn = jax.jit(kv_scatter_latent, donate_argnums=(0,))
            fn._state = None  # _memoized_step refresh hook (stateless)
            return fn

        return _memoized_step(self.model, "_latent_scatter_fns", (bucket,),
                              build)

    def _bucketed_prefill(self, req: _Request):
        """Shared admission prefill: one prompt, padded on the RIGHT to its
        bucket, through the bucket's one jitted prefill step. Returns
        (last_logits [1,V], per-layer caches, S0, bucket).

        No pad mask is built or passed, whatever S0: the row starts at
        cache position 0 and every family's cached prefill attention is
        causal (``cached_attention``, ``mla_cached_attention``), so a real
        token at position s < S0 sees columns 0..s and never a pad. The
        pads' own rows are never read: the last logit is gathered at
        ``lengths - 1``, and their K/V land beyond ``_lengths[slot] = S0``,
        which decode masks and then overwrites. A mask here changes no
        result and takes every bucket off the flash kernel onto O(S^2) f32
        score tensors (PERF.md, PR 34). A family whose prefill attention is
        NOT causal from position 0 would have to declare that on its
        attention layer and get its mask back here."""
        S0 = int(req.ids.size)
        bucket = self._bucket(S0)

        def run(prefill, inputs):
            with self._dispatch_span():
                out = prefill(jnp.asarray(inputs),
                              jnp.asarray([S0], jnp.int32))
            self._count_prefill(S0, bucket, prefill.attention_impl)
            if prefill.moe_counts is not None:
                self._moe_prefills = (
                    prefill.moe_counts if self._moe_prefills is None
                    else self._moe_prefills + prefill.moe_counts)
            return out

        if req.pixel_values is not None:
            # multimodal admission: ONE jitted merge (vision tower +
            # projector + placeholder scatter — eager would pay a device
            # dispatch per op per tower layer on the serving hot path),
            # then the jitted embeds-prefill
            from .generation import _get_prefill_step_embeds

            pixels = unwrap(req.pixel_values)
            merged = self._multimodal_merge_fn(
                (1, S0), pixels.shape)(
                    jnp.asarray(np.asarray(req.ids)[None], jnp.int32),
                    pixels)
            # the image array is consumed; keep only the is-multimodal
            # marker (prefix-cache exclusion) instead of pinning pixels
            # in host memory for the request's whole decode lifetime
            req.pixel_values = True
            embeds = jnp.zeros((1, bucket, merged.shape[-1]),
                               merged.dtype).at[:, :S0].set(merged)
            prefill = _get_prefill_step_embeds(self.model, bucket, False,
                                               rope_len=self.max_len)
            last, caches = run(prefill, embeds)
            return last, caches, S0, bucket
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :S0] = req.ids
        # rope provisioned at the engine's max_len so length-keyed rope
        # regimes (longrope) agree between this prefill and the decode step
        prefill = _get_prefill_step(self.model, bucket, False,
                                    rope_len=self.max_len)
        last, caches = run(prefill, ids)
        return last, caches, S0, bucket

    def _prefill_into_latent(self, slot: int, req: _Request):
        """Latent-mode admission: bucketed prefill of one prompt (latent
        caches come back [1, bucket, ...]), scattered into the slot's row
        of each layer's compressed buffers. With prefix caching on, a
        shared prefix is ROW-copied from the active source slot and only
        the suffix runs the model."""
        if self.enable_prefix_cache:
            src, n_pref = self._find_shared_prefix(req)
            if n_pref > 0:
                return self._prefill_with_prefix_latent(slot, req, src,
                                                        n_pref)
        last, caches, S0, bucket = self._bucketed_prefill(req)
        self._scatter_prefill(slot, last, caches, bucket, S0)
        self._lengths = self._lengths.at[slot].set(S0)

    def _prefill_into(self, slot: int, req: _Request):
        """Bucketed jitted prefill of one prompt, scattered into the slot's
        pages; the slot's last-logit row seeds sampling."""
        if req.handoff is not None:
            # prefill already ran on a peer engine (disaggregated tier):
            # scatter the shipped KV, run no model forward
            return self._admit_handoff(slot, req)
        if self._latent_mode:
            return self._prefill_into_latent(slot, req)
        if self.enable_prefix_cache:
            src, n_pref = self._find_shared_prefix(req)
            if n_pref > 0:
                return self._prefill_with_prefix(slot, req, src, n_pref)
        last, caches, S0, bucket = self._bucketed_prefill(req)
        self._scatter_prefill(slot, last, caches, bucket, S0)
        self._lengths = self._lengths.at[slot].set(S0)

    def _scatter_prefill(self, slot: int, last, caches, bucket: int,
                         length: int):
        """Scatter one bucketed prefill's caches into ``slot`` (pages or
        latent rows) and seed its last-logit row. Shared by monolithic
        admission and the FIRST chunk of a chunked admission — does NOT
        set _lengths (the caller publishes the slot when the whole
        prompt is in). ``length``: the real tokens among the bucket's, by
        which a ring layer's scatter places its pages."""
        if self._latent_mode:
            bufs = [(c["c_kv"], c["k_pe"]) for c in self._caches]
            try:
                with self._dispatch_span():
                    new_bufs = self._latent_scatter_fn(bucket)(
                        bufs, caches, jnp.asarray(slot, jnp.int32))
            except Exception as e:
                self._poisoned = True
                raise RuntimeError(
                    "ContinuousBatchEngine: admission failed after the "
                    "latent buffers were donated; the engine's cache state "
                    "is invalid — rebuild the engine and resubmit "
                    "in-flight requests") from e
            for c_eng, (ckv, kpe) in zip(self._caches, new_bufs):
                c_eng["c_kv"], c_eng["k_pe"] = ckv, kpe
        else:
            pages = [(c[a], c[b]) for c, (a, b)
                     in zip(self._caches, self._pool_keys)]
            try:
                with self._dispatch_span():
                    new_pages = self._scatter_fn(bucket)(
                        pages, caches, jnp.asarray([slot, length], jnp.int32))
            except Exception as e:
                # the scatter DONATES the page pool: a mid-admission
                # failure (device OOM etc.) may have invalidated it,
                # taking every in-flight request's KV with it — poison
                # the engine so later calls fail with context instead of
                # 'donated buffer deleted'
                self._poisoned = True
                raise RuntimeError(
                    "ContinuousBatchEngine: admission failed after the "
                    "page pool was donated; the engine's KV state is "
                    "invalid — rebuild the engine and resubmit in-flight "
                    "requests") from e
            for c_eng, (a, b), new in zip(self._caches, self._pool_keys,
                                          new_pages):
                c_eng[a], c_eng[b] = new
        self._last = self._last.at[slot].set(last[0].astype(jnp.float32))


class Seq2SeqBatchEngine(_RequestBookkeeping):
    """Continuous batching for ENCODER-DECODER families (Whisper ASR,
    BART and T5 seq2seq) — the enc-dec twin of ContinuousBatchEngine.

    Fixed-shape design, same philosophy: per-slot pools hold each
    request's encoder cross K/V (computed once at admission, masked to
    its true encoder length) and a ragged self-cache ([B, max_decode_len]
    rows with per-row lengths — the new BartAttention ragged branch);
    every step() decodes ONE token for every active slot in a single
    jitted dispatch. Admission runs the encoder + seed prefill for one
    request on tiny B=1 caches and SCATTERS the rows into the slot.

    All three enc-dec families serve: Whisper/BART (learned positions)
    and T5 (per-row relative-position bias via T5Stack._bias_rows).
    """

    def __init__(self, model, max_batch: int, max_decode_len: int,
                 max_encoder_len: int, eos_token_id=None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0):
        name = type(model).__name__
        # model adapter: Whisper/BART expose model.encode/decode_cached;
        # T5 exposes encoder/decoder T5Stacks with forward_cached
        if hasattr(getattr(model, "model", None), "decode_cached"):
            self._encode_fn = model.model.encode
            self._decode_fn = model.model.decode_cached
        elif hasattr(getattr(model, "decoder", None), "forward_cached"):
            self._encode_fn = model.encoder
            self._decode_fn = model.decoder.forward_cached
        else:
            raise TypeError(
                f"{name} is not an encoder-decoder with cached decode")
        self.model = model
        cfg = model.config
        table = getattr(cfg, "max_target_positions",
                        getattr(cfg, "max_position_embeddings", None))
        if table is not None and max_decode_len > table:
            raise ValueError(
                f"max_decode_len {max_decode_len} exceeds the decoder "
                f"position table ({table}) — learned positions would "
                "silently clamp")
        self.max_batch = max_batch
        self.max_decode_len = max_decode_len
        self.max_encoder_len = max_encoder_len
        self.eos_token_id = (cfg.eos_token_id if eos_token_id is None
                             else eos_token_id)
        self._sample_cfg = (bool(do_sample), float(temperature),
                            int(top_k), float(top_p))
        dt = jnp.dtype(cfg.dtype) if isinstance(cfg.dtype, str) else cfg.dtype
        h = getattr(cfg, "decoder_attention_heads", None) or cfg.num_heads
        d = getattr(cfg, "d_kv", None) or cfg.d_model // h
        L = len(getattr(getattr(model, "model", None),
                        "decoder_layers_list", None)
                or model.decoder.blocks)
        B = max_batch
        self._self_k = [jnp.zeros((B, max_decode_len, h, d), dt)
                        for _ in range(L)]
        self._self_v = [jnp.zeros((B, max_decode_len, h, d), dt)
                        for _ in range(L)]
        self._cross_k = [jnp.zeros((B, max_encoder_len, h, d), dt)
                         for _ in range(L)]
        self._cross_v = [jnp.zeros((B, max_encoder_len, h, d), dt)
                         for _ in range(L)]
        self._enc_mask = jnp.zeros((B, max_encoder_len), bool)
        self._lengths = jnp.zeros((B,), jnp.int32)
        self._last = jnp.zeros((B, cfg.vocab_size), jnp.float32)
        self._slots: List[Optional[_Request]] = [None] * B
        self._init_bookkeeping("seq2seq")

    # ---- public API ----------------------------------------------------
    def add_request(self, encoder_input, max_new_tokens: int = 64,
                    seed_ids=None, trace_ctx=None) -> int:
        """Queue one request. ``encoder_input``: mel features
        [num_mel_bins, frames] for Whisper, token ids for BART/T5.
        ``seed_ids``: decoder prompt (Whisper task tokens); defaults to
        decoder_start_token_id. ``trace_ctx``: inbound (trace_id,
        parent_span_id) for the request's root span."""
        enc = np.asarray(encoder_input)
        n_seed = 1 if seed_ids is None else int(np.asarray(seed_ids).size)
        if n_seed + max_new_tokens > self.max_decode_len:
            raise ValueError(
                f"seed ({n_seed}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds engine max_decode_len {self.max_decode_len}")
        # encoder length is knowable HERE (BART: token count; Whisper:
        # ceil(frames/2) after the stride-2 conv) — a request that cannot
        # fit must fail on ITS call, never abort the batch mid-run
        t_enc = (enc.size if enc.ndim == 1
                 else (enc.shape[-1] + 1) // 2)
        if t_enc > self.max_encoder_len:
            raise ValueError(
                f"encoder input needs {t_enc} positions > engine "
                f"max_encoder_len {self.max_encoder_len}")
        rid = self._next_rid
        self._next_rid += 1
        self._n_requests += 1
        self._m_req_admitted.inc()
        req = _Request(rid, [0], max_new_tokens)
        req.encoder_input = enc
        req.seed_ids = (None if seed_ids is None
                        else np.asarray(seed_ids, np.int32).reshape(-1))
        self._trace_submit(req, trace_ctx)
        if req.span is not None:
            req.span.set_attr("encoder_positions", int(t_enc))
        self._queue.append(req)
        self._fr_submit(req)
        self._admit()
        return rid

    def run_until_done(self):
        out: Dict[int, np.ndarray] = {}
        while self._queue or self.num_active:
            out.update(self.step())
        out.update(self._drain())
        return out

    def _drain(self):
        done, self._finished = self._finished, {}
        return done

    # ---- admission -----------------------------------------------------
    @_build_events.cause("engine/admit")
    def _admit(self):
        from .autograd import tape as _tape
        from .tensor_class import wrap

        while self._queue and None in self._slots:
            slot = self._slots.index(None)
            # same priority-queue pop as the decoder engine; with every
            # request at the default class this is FIFO by rid
            req = self._pop_next(time.perf_counter())
            t_adm = time.perf_counter()
            self._observe_admission(req, t_adm)
            self._trace_admit(req, slot)
            model = self.model
            cfg = model.config
            # the encoder + seed prefill IS this engine's admission
            # prefill — one span covers it
            with _tracing.get_tracer().span(
                    _tracing.SPAN_PREFILL, parent=req.span,
                    attrs={"slot": slot}), _tape.no_grad():
                enc_in = req.encoder_input
                if enc_in.ndim == 1:                # BART/T5 token ids
                    enc = self._encode_fn(
                        wrap(jnp.asarray(enc_in[None], jnp.int32)))
                else:                               # Whisper mel
                    enc = self._encode_fn(
                        wrap(jnp.asarray(enc_in[None], jnp.float32)))
                t_enc = enc.shape[1]
                if t_enc > self.max_encoder_len:
                    # add_request pre-validates, so this is a safety net
                    # for models whose encoder length derivation differs:
                    # fail THIS request, never the in-flight batch
                    self._finished[req.rid] = np.asarray([], np.int64)
                    self._count_finished(req, slo=False)
                    self._record_reason(req.rid, "error")
                    self._trace_end(req, "error")
                    continue
                seed = (req.seed_ids if req.seed_ids is not None
                        else np.asarray([cfg.decoder_start_token_id],
                                        np.int32))
                n_seed = int(seed.size)
                # B=1 seed prefill on the model's own scalar-pos caches
                self_c, cross_c = model._init_caches(enc, 1, n_seed)
                hidden, self_c, _ = self._decode_fn(
                    wrap(jnp.asarray(seed[None], jnp.int32)), self_c,
                    cross_c)
                last = unwrap(model.lm_head_logits(
                    wrap(unwrap(hidden)[:, -1:])))[:, 0, :]
                # scatter the request's rows into the slot pools
                for l, (sc, cc) in enumerate(zip(self_c, cross_c)):
                    self._self_k[l] = self._self_k[l].at[
                        slot, :n_seed].set(sc["k"][0].astype(
                            self._self_k[l].dtype))
                    self._self_v[l] = self._self_v[l].at[
                        slot, :n_seed].set(sc["v"][0].astype(
                            self._self_v[l].dtype))
                    self._cross_k[l] = self._cross_k[l].at[
                        slot, :t_enc].set(cc["k"][0].astype(
                            self._cross_k[l].dtype))
                    self._cross_v[l] = self._cross_v[l].at[
                        slot, :t_enc].set(cc["v"][0].astype(
                            self._cross_v[l].dtype))
                self._enc_mask = self._enc_mask.at[slot].set(False)
                self._enc_mask = self._enc_mask.at[slot, :t_enc].set(True)
                self._lengths = self._lengths.at[slot].set(n_seed)
                self._last = self._last.at[slot].set(
                    last[0].astype(jnp.float32))
            self._slots[slot] = req
            req.slot = slot
            # encoder + seed prefill IS this engine's admission prefill
            with _tracing.get_tracer().use(req.span):
                self._m_prefill.observe(time.perf_counter() - t_adm)

    # ---- decode --------------------------------------------------------
    def _step_fn(self):
        from .generation import _memoized_step

        model = self.model
        do_sample, temperature, top_k, top_p = self._sample_cfg

        def build():
            from .autograd import tape as _tape
            from .generation import _functional_weights, sample_logits
            from .tensor_class import wrap

            def seq2seq_decode_step(state, last, key, sk, sv, ck, cv,
                                    enc_mask, lengths):
                with _functional_weights(model, state), _tape.no_grad():
                    nxt = sample_logits(last, key, do_sample=do_sample,
                                        temperature=temperature,
                                        top_k=top_k, top_p=top_p)
                    token = nxt[:, None].astype(jnp.int32)
                    self_c = [{"k": k, "v": v, "lengths": lengths}
                              for k, v in zip(sk, sv)]
                    cross_c = [{"k": k, "v": v, "mask": enc_mask}
                               for k, v in zip(ck, cv)]
                    hidden, new_self, _ = self._decode_fn(
                        wrap(token), self_c, cross_c)
                    last_n = unwrap(model.lm_head_logits(
                        wrap(unwrap(hidden)[:, -1:])))[:, 0, :]
                return (nxt, last_n.astype(jnp.float32),
                        [c["k"] for c in new_self],
                        [c["v"] for c in new_self])

            fn = jax.jit(seq2seq_decode_step, donate_argnums=(3, 4))
            step = lambda *a: fn(step._state, *a)
            step._state = dict(model.functional_state())
            return step

        key = (self.max_batch, self.max_decode_len, self.max_encoder_len,
               self._sample_cfg)
        return _memoized_step(model, "_seq2seq_steps", key, build,
                              maxsize=8)

    def step(self) -> Dict[int, np.ndarray]:
        """Decode ONE token for every active slot (one fused dispatch);
        returns newly finished requests {rid: generated ids}."""
        return self._profiled_step()

    def _step_decode(self, clk) -> Dict[int, np.ndarray]:
        # the encoder+seed prefill inside _admit IS this engine's
        # admission prefill, so it attributes to "admit"
        if clk is not None:
            clk.open("admit")
        self._admit()
        if clk is not None:
            clk.close()
        if self.num_active == 0:
            return self._drain()
        if clk is not None:
            clk.open("dispatch")
        t_dispatch = time.perf_counter()
        step = self._step_fn()
        nxt, self._last, self._self_k, self._self_v = step(
            self._last, _random.next_key(), self._self_k, self._self_v,
            self._cross_k, self._cross_v, self._enc_mask, self._lengths)
        if clk is not None:
            clk.open("sync")
        # the seq2seq step's one deliberate device->host sync
        toks = np.asarray(nxt)    # pdlint: disable=host-sync
        if clk is not None:
            clk.open("retire")
        now = time.perf_counter()
        self._m_step.observe(now - t_dispatch)
        self._n_steps += 1
        fr_seq = 0
        rec = _frec.RECORDER
        if rec.enabled:
            fr_seq = rec.record(_frec.EV_STEP, engine=self._engine_label,
                                active=self.num_active,
                                seconds=now - t_dispatch)
        trace_on = _tracing.get_tracer().enabled
        t0_ns, t1_ns = (int(t_dispatch * 1e9), int(now * 1e9)) \
            if trace_on else (0, 0)
        active = np.array([r is not None for r in self._slots])
        self._lengths = jnp.where(jnp.asarray(active), self._lengths + 1,
                                  self._lengths)
        for s, req in enumerate(self._slots):
            if req is None:
                continue
            req.dispatches += 1
            t = int(toks[s])
            req.tokens.append(t)
            self._observe_token(req, now)
            if trace_on:
                self._trace_decode_step(req, t0_ns, t1_ns)
            stopped = (self.eos_token_id is not None
                       and t == self.eos_token_id)
            if len(req.tokens) >= req.max_new_tokens or stopped:
                self._finished[req.rid] = np.asarray(req.tokens, np.int64)
                self._count_finished(req)
                self._record_reason(req.rid,
                                    "stop" if stopped else "length")
                self._release_slot(s)
                self._trace_end(req, "ok")
        if clk is not None:
            clk.open("admit")  # trailing refill accumulates into admit
        self._admit()
        if clk is not None:
            clk.close()
            self.profiler.commit(active=int(active.sum()), fr_seq=fr_seq)
        return self._drain()
