"""Worker supervision: restart-with-backoff, crash-loop containment, and
poison-request quarantine — the self-healing half of the serving tier.

PR 6's launcher spawned worker subprocesses and forgot them: a SIGKILL'd
worker's capacity was gone until an operator intervened, and a request
that deterministically crashes its engine (OOM, kernel assert, poisoned
input) was failover-retried onto the next worker — serial crash-loop
amplification. This module closes both holes:

- :class:`WorkerSupervisor` OWNS the worker subprocesses. A monitor
  thread detects death by ``Popen.poll()`` (waitpid) and — optionally —
  by sustained lease silence reported by the pool, and respawns the
  worker with the SAME role/replica_id under exponential backoff with
  jitter (:class:`RestartBackoff`). The restarted worker registers a
  fresh lease and rejoins the pool warm; the router's knee capacity
  recovers without an operator.
- A per-worker :class:`CircuitBreaker` contains crash loops: more than
  ``threshold`` restarts inside ``window_s`` holds the worker OPEN (no
  further restarts; the router's ``/health`` reports the tier degraded)
  instead of burning CPU respawning a process that dies on arrival.
- :class:`QuarantineLedger` + :class:`Deathnote` contain poison
  requests: before every decode dispatch the engine arms an atomic
  tmpfile naming the request ids entering that step (erased on step
  success), so a death blames exactly the rids in the fatal dispatch —
  not every request the router had in flight on the worker. A rid
  implicated in ≥ 2 distinct worker deaths is quarantined: the router
  answers a typed 422 ``code=request_quarantined`` and never retries it.
- On every death the supervisor sweeps the workers' incident directory
  into a cluster-level index (``incidents/INDEX.jsonl``) and persists
  its own state (restart history, breaker states, quarantine ledger) as
  ``SUPERVISOR.json`` — ``scripts/read_incident.py --index`` renders
  both.

See docs/SERVING.md "Self-healing & crash containment" for the
supervision tree and the operator runbook.
"""
from __future__ import annotations

import json
import os
import random
import subprocess
import threading
import time
from collections import deque
from typing import Callable, Dict, List, Optional

from ..analysis.threads.witness import make_lock
from ..distributed.log_utils import get_logger
from ..observability import flightrecorder as _frec
from ..observability.catalog import REQUESTS_QUARANTINED, WORKER_RESTARTS

__all__ = ["RestartBackoff", "CircuitBreaker", "QuarantineLedger",
           "Deathnote", "WorkerSupervisor", "QUARANTINE_THRESHOLD",
           "EXIT_NO_DEVICE"]

#: exit code of a worker that could not acquire a JAX device (sysexits'
#: EX_UNAVAILABLE). The launcher fails the launch at once on it, and
#: the supervisor holds the worker instead of restarting it: the next
#: incarnation would meet the same busy chip.
EXIT_NO_DEVICE = 69

#: distinct worker deaths that quarantine a request id. Two is the
#: containment bound the chaos gate pins: a poison request costs the
#: tier at most two workers before it is refused typed.
QUARANTINE_THRESHOLD = 2


class RestartBackoff:
    """Exponential restart backoff with jitter, per worker.

    ``next_delay()`` returns ``min(max_s, base_s * factor**attempt)``
    spread uniformly over ``[d*(1-jitter_frac), d*(1+jitter_frac)]`` and
    bumps the attempt counter; ``reset()`` (called after the worker
    survives a sustained-health window) starts the ladder over. Jitter
    matters for the same reason the pool's busy backoff is jittered: a
    correlated mass death would otherwise respawn every worker in the
    same instant, synchronizing their compile storms."""

    def __init__(self, base_s: float = 0.5, max_s: float = 30.0,
                 factor: float = 2.0, jitter_frac: float = 0.5,
                 rng: Optional[random.Random] = None):
        self.base_s = float(base_s)
        self.max_s = float(max_s)
        self.factor = float(factor)
        self.jitter_frac = float(jitter_frac)
        self._rng = rng or random.Random()
        self.attempt = 0

    def next_delay(self) -> float:
        d = min(self.max_s, self.base_s * (self.factor ** self.attempt))
        self.attempt += 1
        lo = max(0.0, 1.0 - self.jitter_frac)
        return d * self._rng.uniform(lo, 1.0 + self.jitter_frac)

    def reset(self):
        self.attempt = 0


class CircuitBreaker:
    """Per-worker crash-loop containment: at most ``threshold`` restarts
    inside a sliding ``window_s``. ``allow()`` is asked before every
    restart — stamps older than the window age out (a worker that has
    been healthy for a while earns its full restart budget back), and
    the restart that would exceed the budget TRIPS the breaker open.
    Open holds: no further restarts until an operator ``reset()`` — a
    worker that dies ``threshold`` times in the window is broken in a
    way a fourth respawn will not fix, and the router's ``/health``
    should say "degraded", not flap. ``clock`` is injectable for the
    fake-clock tests."""

    def __init__(self, threshold: int = 5, window_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        if int(threshold) < 1:
            raise ValueError(f"threshold must be >= 1, got {threshold}")
        self.threshold = int(threshold)
        self.window_s = float(window_s)
        self._clock = clock
        self._stamps: deque = deque()
        self.open_since: Optional[float] = None

    def _prune(self, now: float):
        while self._stamps and self._stamps[0] <= now - self.window_s:
            self._stamps.popleft()

    @property
    def is_open(self) -> bool:
        return self.open_since is not None

    def allow(self) -> bool:
        """True when one more restart is within budget (and records it);
        False trips/holds the breaker open."""
        now = self._clock()
        self._prune(now)
        if self.open_since is not None:
            return False
        if len(self._stamps) >= self.threshold:
            self.open_since = now
            return False
        self._stamps.append(now)
        return True

    def reset(self):
        """Operator intervention: close the breaker and forget history."""
        self._stamps.clear()
        self.open_since = None

    def state(self) -> dict:
        now = self._clock()
        self._prune(now)
        return {"open": self.is_open,
                "restarts_in_window": len(self._stamps),
                "threshold": self.threshold,
                "window_s": self.window_s}


class Deathnote:
    """The pre-dispatch blame record: an atomic tmpfile naming the
    request ids entering the engine's CURRENT step, erased when the step
    completes. If the process dies mid-dispatch the file survives it, so
    the supervisor blames exactly the rids in the fatal dispatch instead
    of implicating every request the router had in flight on the worker
    (queued and mid-prefill rids were not in the dispatch that died).

    Write cost is one small file rename per dispatch — the engine only
    arms it when a deathnote is configured (cluster workers), solo
    engines never pay it."""

    def __init__(self, path: str):
        self.path = str(path)
        d = os.path.dirname(self.path)
        if d:
            os.makedirs(d, exist_ok=True)

    def arm(self, rids: List[str]):
        tmp = f"{self.path}.tmp.{os.getpid()}"
        with open(tmp, "w", encoding="utf-8") as f:
            json.dump({"pid": os.getpid(), "ts": time.time(),
                       "rids": [str(r) for r in rids]}, f)
        os.replace(tmp, self.path)

    def clear(self):
        try:
            os.unlink(self.path)
        except FileNotFoundError:
            pass

    @staticmethod
    def read(path: str) -> Optional[List[str]]:
        """The armed rids at ``path`` (None when the file is absent —
        the worker died between steps — or unreadable mid-write)."""
        try:
            with open(path, encoding="utf-8") as f:
                note = json.load(f)
            return [str(r) for r in note.get("rids") or ()]
        except (OSError, ValueError):
            return None


class QuarantineLedger:
    """Which request ids were implicated in which worker deaths, and
    which crossed the quarantine threshold. Thread-safe: the supervisor
    monitor thread records deaths, router handler threads query before
    every placement attempt."""

    def __init__(self, threshold: int = QUARANTINE_THRESHOLD):
        self.threshold = int(threshold)
        self._lock = make_lock("QuarantineLedger._lock")
        self._deaths: Dict[str, List[dict]] = {}   # rid -> death records
        self._quarantined: Dict[str, dict] = {}    # rid -> final record
        self._n_deaths = 0

    def record_death(self, replica_id: int, death_key, rids,
                     precise: bool = True) -> List[str]:
        """One worker death implicating ``rids`` (the deathnote's step
        batch when ``precise``, the router's in-flight journal
        otherwise). ``death_key`` identifies the death (the dead child's
        pid) so a death observed twice — by the router's broken socket
        AND the monitor's waitpid — counts once. Returns the rids this
        death pushed over the threshold."""
        newly: List[str] = []
        with self._lock:
            self._n_deaths += 1
            for rid in rids:
                rid = str(rid)
                recs = self._deaths.setdefault(rid, [])
                if any(r["death_key"] == death_key for r in recs):
                    continue
                recs.append({"death_key": death_key,
                             "replica_id": int(replica_id),
                             "precise": bool(precise),
                             "ts": time.time()})
                if (rid not in self._quarantined
                        and len(recs) >= self.threshold):
                    self._quarantined[rid] = {
                        "deaths": len(recs),
                        "replicas": sorted({r["replica_id"]
                                            for r in recs}),
                        "ts": time.time()}
                    newly.append(rid)
        rec = _frec.RECORDER
        for rid in newly:
            REQUESTS_QUARANTINED.inc()
            if rec.enabled:
                with self._lock:
                    q = dict(self._quarantined[rid])
                rec.record(_frec.EV_SCHED_QUARANTINE, rid=rid,
                           deaths=q["deaths"], replicas=q["replicas"])
            get_logger().warning(
                "quarantine: request %s implicated in %s distinct worker "
                "deaths — refused from now on (typed 422)", rid,
                self.threshold)
        return newly

    def is_quarantined(self, rid) -> bool:
        with self._lock:
            return str(rid) in self._quarantined

    def quarantined(self) -> List[str]:
        with self._lock:
            return sorted(self._quarantined)

    def snapshot(self) -> dict:
        with self._lock:
            return {
                "threshold": self.threshold,
                "deaths_recorded": self._n_deaths,
                "implicated": {rid: [dict(r) for r in recs]
                               for rid, recs in self._deaths.items()},
                "quarantined": {rid: dict(q)
                                for rid, q in self._quarantined.items()},
            }


class _Supervised:
    """One worker under supervision: its spawn closure, live process,
    restart history, backoff ladder and breaker. All mutation happens
    under the supervisor's lock."""

    __slots__ = ("replica_id", "spawn", "proc", "incarnation",
                 "backoff", "breaker", "restarts", "next_restart_at",
                 "held_open", "last_start", "blamed_pids", "last_exit")

    def __init__(self, replica_id: int, spawn, proc, backoff, breaker):
        self.replica_id = int(replica_id)
        self.spawn = spawn          # (replica_id, incarnation) -> Popen
        self.proc = proc
        self.incarnation = 0
        self.backoff = backoff
        self.breaker = breaker
        self.restarts: List[dict] = []
        self.next_restart_at: Optional[float] = None
        self.held_open = False
        self.last_start = time.monotonic()
        self.blamed_pids = set()
        self.last_exit: Optional[int] = None


class WorkerSupervisor:
    """Owns worker subprocesses: spawn, watch, blame, restart, contain.

    The launcher registers each worker with :meth:`adopt` (the spawn
    closure is re-invoked on restart with a bumped incarnation number —
    the chaos injector uses it to scope faults to one incarnation, so a
    planned kill does not re-fire in the respawned process). The monitor
    thread (``worker-supervisor``) polls ``Popen.poll()``; on death it

    1. reads the worker's deathnote (falling back to the router's
       in-flight journal via ``inflight_fn``) and records the implicated
       rids in the :class:`QuarantineLedger`;
    2. sweeps new incident bundles into ``INDEX.jsonl`` and persists
       ``SUPERVISOR.json``;
    3. asks the breaker for a restart budget — within budget the worker
       respawns after the jittered backoff delay (``sup.restart``),
       over budget it is held open (``sup.breaker_open``, the router's
       ``/health`` reports the tier degraded).

    The router calls :meth:`note_worker_death` the moment a placement
    socket breaks, so quarantine blame lands BEFORE the retry loop's
    next attempt — the monitor's slower waitpid sweep would lose that
    race. Both paths dedupe on the dead child's pid."""

    def __init__(self, *, ledger: Optional[QuarantineLedger] = None,
                 incident_dir: Optional[str] = None,
                 state_dir: Optional[str] = None,
                 backoff_base_s: float = 0.5, backoff_max_s: float = 30.0,
                 backoff_factor: float = 2.0,
                 breaker_threshold: int = 5,
                 breaker_window_s: float = 60.0,
                 healthy_reset_s: float = 30.0,
                 poll_interval_s: float = 0.2):
        self.ledger = ledger if ledger is not None else QuarantineLedger()
        self.incident_dir = incident_dir
        self.state_dir = state_dir or incident_dir
        if self.state_dir:
            os.makedirs(self.state_dir, exist_ok=True)
        self._backoff_cfg = (float(backoff_base_s), float(backoff_max_s),
                             float(backoff_factor))
        self._breaker_cfg = (int(breaker_threshold),
                             float(breaker_window_s))
        self.healthy_reset_s = float(healthy_reset_s)
        self.poll_interval_s = float(poll_interval_s)
        #: router hook: replica_id -> request ids the router has in
        #: flight there (the imprecise whole-batch fallback when a
        #: worker dies without arming a deathnote)
        self.inflight_fn: Optional[Callable[[int], List[str]]] = None
        self._lock = make_lock("WorkerSupervisor._lock")
        self._workers: Dict[int, _Supervised] = {}
        self._indexed: set = set()
        self._n_restarts = 0
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None

    # ---- registration ---------------------------------------------------
    def deathnote_path(self, replica_id: int) -> Optional[str]:
        if not self.state_dir:
            return None
        return os.path.join(self.state_dir,
                            f"deathnote-{int(replica_id)}.json")

    def adopt(self, replica_id: int, spawn, proc) -> _Supervised:
        """Put one already-spawned worker under supervision. ``spawn`` is
        re-invoked as ``spawn(replica_id, incarnation)`` on restart."""
        base_s, max_s, factor = self._backoff_cfg
        threshold, window_s = self._breaker_cfg
        sup = _Supervised(replica_id, spawn, proc,
                          RestartBackoff(base_s, max_s, factor),
                          CircuitBreaker(threshold, window_s))
        with self._lock:
            self._workers[int(replica_id)] = sup
        return sup

    def proc(self, replica_id: int) -> Optional[subprocess.Popen]:
        with self._lock:
            sup = self._workers.get(int(replica_id))
            return sup.proc if sup is not None else None

    def kill(self, replica_id: int):
        """SIGKILL the worker's CURRENT incarnation (crash simulation)."""
        p = self.proc(replica_id)
        if p is not None:
            p.kill()

    # ---- lifecycle ------------------------------------------------------
    def start(self) -> "WorkerSupervisor":
        if self._thread is not None:
            return self
        self._thread = threading.Thread(target=self._monitor, daemon=True,
                                        name="worker-supervisor")
        self._thread.start()
        return self

    def close(self, term_timeout: float = 10.0):
        """Stop supervising, SIGTERM every live child and REAP it — a
        torn-down cluster must leave no zombies (and no supervisor that
        would respawn what the teardown just killed)."""
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=5.0)
        with self._lock:
            procs = [s.proc for s in self._workers.values()
                     if s.proc is not None]
        for p in procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + term_timeout
        for p in procs:
            try:
                p.wait(timeout=max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                get_logger().warning(
                    "supervisor: worker pid %s ignored SIGTERM; killing",
                    p.pid)
                p.kill()
                try:
                    p.wait(timeout=5)
                except subprocess.TimeoutExpired:
                    get_logger().warning(
                        "supervisor: worker pid %s unreapable", p.pid)
        self.sweep_incidents()

    # ---- death handling -------------------------------------------------
    def note_worker_death(self, replica_id: int,
                          fallback_rids=()) -> bool:
        """Router-observed death (a placement socket broke): blame NOW,
        synchronously, so the ledger is current before the router's
        retry loop re-places the request. Returns True when a real
        process death was recorded (False: the process is alive — a
        connection blip, not a crash — so nothing is blamed)."""
        with self._lock:
            sup = self._workers.get(int(replica_id))
            proc = sup.proc if sup is not None else None
        if sup is None or proc is None:
            return False
        if proc.poll() is None:
            # the caller's socket broke BEFORE the exit became
            # waitpid-visible (os._exit closes fds a beat ahead of the
            # reapable state): give a real death a moment to land — a
            # genuine connection blip costs this wait once and is then
            # correctly not blamed
            try:
                proc.wait(timeout=0.5)
            except subprocess.TimeoutExpired:
                return False
        self._blame(sup, proc, fallback_rids=fallback_rids)
        return True

    def _blame(self, sup: _Supervised, proc, fallback_rids=()):
        """Record one death in the ledger, once per dead pid: the
        deathnote's step batch when armed (precise), else the router's
        in-flight journal for the replica (whole batch)."""
        with self._lock:
            if proc.pid in sup.blamed_pids:
                return
            sup.blamed_pids.add(proc.pid)
            sup.last_exit = proc.poll()
        note_path = self.deathnote_path(sup.replica_id)
        rids = Deathnote.read(note_path) if note_path else None
        precise = rids is not None
        if rids is None:
            fn = self.inflight_fn
            if fn is not None:
                try:
                    rids = [str(r) for r in fn(sup.replica_id)]
                except Exception as e:
                    get_logger().warning(
                        "supervisor: in-flight journal read failed "
                        "(%s: %s)", type(e).__name__, e)
                    rids = []
            else:
                rids = list(fallback_rids)
        if fallback_rids and not precise:
            rids = list(dict.fromkeys([*rids, *map(str, fallback_rids)]))
        if note_path:
            try:
                os.unlink(note_path)
            except FileNotFoundError:
                pass
        if rids:
            self.ledger.record_death(sup.replica_id, proc.pid, rids,
                                     precise=precise)

    def _handle_death(self, sup: _Supervised, proc):
        code = proc.poll()
        if code == EXIT_NO_DEVICE:
            # not a crash: the worker found no device it may use (the
            # chip belongs to another process). The next incarnation
            # would meet the same busy chip — hold, do not loop.
            with self._lock:
                sup.held_open = True
                sup.proc = None
                sup.last_exit = code
            get_logger().error(
                "supervisor: worker %s could not acquire a JAX device "
                "(exit %s) — holding it, not restarting (free the chip, "
                "then WorkerSupervisor.reset_breaker)", sup.replica_id,
                code)
            return
        self._blame(sup, proc)
        self.sweep_incidents()
        now = time.monotonic()
        rec = _frec.RECORDER
        with self._lock:
            allowed = sup.breaker.allow()
        if not allowed:
            with self._lock:
                already = sup.held_open
                sup.held_open = True
                sup.proc = None
            if not already:
                with self._lock:
                    n_restarts = len(sup.restarts)
                if rec.enabled:
                    rec.record(_frec.EV_SUP_BREAKER,
                               replica_id=sup.replica_id,
                               restarts=n_restarts,
                               window_s=sup.breaker.window_s)
                get_logger().error(
                    "supervisor: worker %s crash-looped (%s restarts in "
                    "%.0fs window) — breaker OPEN, holding (reset via "
                    "WorkerSupervisor.reset_breaker)", sup.replica_id,
                    sup.breaker.threshold, sup.breaker.window_s)
            return
        with self._lock:
            delay = sup.backoff.next_delay()
            sup.proc = None
            sup.next_restart_at = now + delay
            sup.restarts.append({"ts": time.time(), "exit": code,
                                 "incarnation": sup.incarnation,
                                 "delay_s": round(delay, 3)})
            self._n_restarts += 1
        WORKER_RESTARTS.inc(replica=str(sup.replica_id))
        if rec.enabled:
            rec.record(_frec.EV_SUP_RESTART, replica_id=sup.replica_id,
                       incarnation=sup.incarnation + 1, exit_code=code,
                       delay_s=round(delay, 3))
        get_logger().warning(
            "supervisor: worker %s died (exit %s) — restarting as "
            "incarnation %s in %.2fs", sup.replica_id, code,
            sup.incarnation + 1, delay)

    def _respawn(self, sup: _Supervised):
        with self._lock:
            sup.incarnation += 1
            incarnation = sup.incarnation
            sup.next_restart_at = None
        try:
            proc = sup.spawn(sup.replica_id, incarnation)
        except Exception as e:
            get_logger().error(
                "supervisor: respawn of worker %s failed (%s: %s); will "
                "retry on the backoff ladder", sup.replica_id,
                type(e).__name__, e)
            with self._lock:
                sup.next_restart_at = (time.monotonic()
                                       + sup.backoff.next_delay())
            return
        with self._lock:
            sup.proc = proc
            sup.last_start = time.monotonic()

    def _monitor(self):
        while not self._stop.wait(self.poll_interval_s):
            with self._lock:
                snapshot = list(self._workers.values())
            now = time.monotonic()
            for sup in snapshot:
                with self._lock:
                    proc = sup.proc
                    due = (sup.next_restart_at is not None
                           and now >= sup.next_restart_at
                           and not sup.held_open)
                if proc is not None:
                    if proc.poll() is not None:
                        try:
                            self._handle_death(sup, proc)
                        except Exception as e:
                            # supervision must outlive its own bugs: a
                            # failed blame/sweep still schedules the
                            # restart path next tick
                            get_logger().warning(
                                "supervisor: death handling for worker "
                                "%s failed (%s: %s)", sup.replica_id,
                                type(e).__name__, e)
                    else:
                        with self._lock:
                            if (now - sup.last_start
                                    > self.healthy_reset_s
                                    and sup.backoff.attempt):
                                # sustained health re-arms the full
                                # backoff ladder (breaker stamps age
                                # out on their own)
                                sup.backoff.reset()
                elif due:
                    try:
                        self._respawn(sup)
                    except Exception as e:
                        # a failed spawn (fork/exec pressure) must not
                        # kill supervision: next_restart_at is still in
                        # the past, so the next tick retries
                        get_logger().warning(
                            "supervisor: respawn of worker %s failed "
                            "(%s: %s); retrying next tick",
                            sup.replica_id, type(e).__name__, e)

    def reset_breaker(self, replica_id: int):
        """Operator intervention: close a held-open breaker and schedule
        an immediate restart attempt."""
        with self._lock:
            sup = self._workers.get(int(replica_id))
            if sup is None:
                return
            sup.breaker.reset()
            sup.backoff.reset()
            sup.held_open = False
            if sup.proc is None:
                sup.next_restart_at = time.monotonic()

    # ---- state / forensics ----------------------------------------------
    def state(self) -> dict:
        """Restart history + breaker state per worker + the quarantine
        ledger — the SUPERVISOR section of /health and SUPERVISOR.json."""
        with self._lock:
            workers = {}
            restarts_total = self._n_restarts
            for rid, sup in self._workers.items():
                workers[str(rid)] = {
                    "incarnation": sup.incarnation,
                    "alive": (sup.proc is not None
                              and sup.proc.poll() is None),
                    "pid": (sup.proc.pid if sup.proc is not None
                            else None),
                    "last_exit": sup.last_exit,
                    "restarts": [dict(r) for r in sup.restarts],
                    "breaker": sup.breaker.state(),
                    "held_open": sup.held_open,
                    "restart_pending": sup.next_restart_at is not None,
                }
        ledger = self.ledger.snapshot()
        return {
            "restarts_total": restarts_total,
            "breakers_open": sum(1 for w in workers.values()
                                 if w["held_open"]),
            "quarantined_total": len(ledger["quarantined"]),
            "workers": workers,
            "quarantine": ledger,
        }

    def sweep_incidents(self) -> int:
        """Index every not-yet-seen incident OR divergence bundle in
        ``incident_dir`` into ``INDEX.jsonl`` (one line per bundle:
        file, reason, context, ts, pid, rank) and refresh
        ``SUPERVISOR.json``. Divergence bundles (written by the
        correctness sentinel) index with reason ``divergence`` and a
        context naming the audit source and first diverged position, so
        the cluster index answers "has ANY worker produced wrong
        tokens" the same way it answers "has any worker crashed".
        Returns the number of newly indexed bundles."""
        if not self.state_dir:
            return 0
        new = 0
        inc_dir = self.incident_dir or self.state_dir
        try:
            names = sorted(os.listdir(inc_dir))
        except OSError:
            names = []
        index_path = os.path.join(self.state_dir, "INDEX.jsonl")
        lines = []
        for name in names:
            is_div = name.startswith("divergence-")
            if (not (name.startswith("incident-") or is_div)
                    or not name.endswith(".json")):
                continue
            with self._lock:
                if name in self._indexed:
                    continue
                self._indexed.add(name)
            path = os.path.join(inc_dir, name)
            entry = {"file": name}
            try:
                with open(path, encoding="utf-8") as f:
                    b = json.load(f)
                if is_div:
                    entry.update({
                        "reason": "divergence",
                        "context": (f"{b.get('source', '?')} "
                                    f"rid={b.get('rid')} "
                                    f"first={b.get('first_divergence')} "
                                    f"engine={b.get('engine', '?')}"),
                        "ts": os.path.getmtime(path),
                        "pid": None, "rank": None,
                    })
                else:
                    entry.update({k: b.get(k) for k in
                                  ("reason", "context", "ts",
                                   "pid", "rank")})
            except (OSError, ValueError) as e:
                entry["error"] = f"{type(e).__name__}: {e}"
            lines.append(json.dumps(entry, default=str))
            new += 1
        if lines:
            with open(index_path, "a", encoding="utf-8") as f:
                f.write("\n".join(lines) + "\n")
        # SUPERVISOR.json is rewritten every sweep (atomic): the latest
        # restart/breaker/quarantine picture next to the bundle index
        sup_path = os.path.join(self.state_dir, "SUPERVISOR.json")
        tmp = sup_path + ".tmp"
        try:
            with open(tmp, "w", encoding="utf-8") as f:
                json.dump(self.state(), f, indent=1, default=str)
            os.replace(tmp, sup_path)
        except OSError as e:
            get_logger().warning("supervisor: state persist failed "
                                 "(%s: %s)", type(e).__name__, e)
        return new
