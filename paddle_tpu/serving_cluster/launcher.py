"""Cluster launcher: one call from config to a serving tier.

Spawns N worker processes (each ``python -m
paddle_tpu.serving_cluster.worker`` with a JSON config — real processes,
so a worker death is a process death, exactly what the pool's lease
watch and the router's retry path are built for), stands up the TCPStore
the leases rendezvous on, and runs the WorkerPool + RouterServer in the
calling process. ``scripts/serve_cluster.py`` is the CLI over this; the
tier-1 multi-engine dryrun gate drives it directly.

Since the self-healing PR the launcher does not spawn-and-forget: worker
subprocesses are OWNED by a :class:`~.supervisor.WorkerSupervisor`
(``supervise=False`` opts out) that restarts dead workers with backoff +
a per-worker circuit breaker, blames crashes through the deathnote /
quarantine ledger, and sweeps incident bundles into a cluster-level
index. Teardown is total: ``close()`` is idempotent (atexit-armed),
propagates SIGTERM to every worker and REAPS it — a torn-down cluster
leaves no zombies — and SIGTERM/SIGINT on the launcher process itself
propagate to the workers before the previous handler runs.

Config shape (TOML or JSON; see docs/SERVING.md "Disaggregated
deployment")::

    [cluster]
    host = "127.0.0.1"   # router bind
    port = 0             # 0 = ephemeral
    job_id = "serve"
    ttl = 5.0            # lease ttl seconds
    max_retries = 2
    incident_dir = "incidents"   # also the supervisor's state dir

    [supervisor]         # optional overrides (see WorkerSupervisor)
    backoff_base_s = 0.5
    breaker_threshold = 5
    breaker_window_s = 60.0

    [model]
    kind = "tiny_llama"  # or factory = "pkg.module:fn"
    seed = 0

    [engine]
    max_batch = 4
    max_len = 64
    page_size = 8

    [[workers]]
    role = "unified"
    count = 2
"""
from __future__ import annotations

import atexit
import json
import os
import signal
import subprocess
import sys
import tempfile
import threading
import time
from typing import List, Optional

from ..chaos.inject import ENV_INCARNATION
from ..distributed.log_utils import get_logger
from ..distributed.store import TCPStore
from .pool import WorkerPool
from .router import RouterServer
from .supervisor import EXIT_NO_DEVICE, WorkerSupervisor

__all__ = ["Cluster", "launch_cluster", "load_config", "expand_workers"]


def load_config(path: str) -> dict:
    """TOML (via tomllib, python >= 3.11) or JSON config file."""
    if path.endswith(".toml"):
        try:
            import tomllib
        except ImportError as e:
            raise RuntimeError(
                "TOML configs need python >= 3.11 (tomllib); use a JSON "
                "config on this interpreter") from e
        with open(path, "rb") as f:
            return tomllib.load(f)
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def expand_workers(cfg: dict) -> List[dict]:
    """The ``workers`` section expanded to one role entry per process
    (``count`` multiplies); defaults to ONE unified worker — a worker
    needs an accelerator of its own, and one is what any host has."""
    specs = cfg.get("workers") or [{"role": "unified", "count": 1}]
    out = []
    for spec in specs:
        for _ in range(int(spec.get("count", 1))):
            out.append({k: v for k, v in spec.items() if k != "count"})
    return out


class Cluster:
    """A running tier: router (in-process) + supervised worker
    subprocesses."""

    def __init__(self, cfg: dict, wait: bool = True,
                 wait_timeout: float = 180.0, supervise: bool = True,
                 install_signal_handlers: bool = True):
        cluster = dict(cfg.get("cluster") or {})
        host = cluster.get("host", "127.0.0.1")
        job_id = cluster.get("job_id", "serve")
        ttl = float(cluster.get("ttl", 5.0))
        worker_specs = expand_workers(cfg)
        self.processes: List[subprocess.Popen] = []  # first incarnations
        self._replica_pids = {}
        self._closed = False
        self._prev_signals = {}
        # the lease/metadata rendezvous point: master in THIS process so
        # the router outliving every worker also owns the store
        self.store = TCPStore(host, 0, is_master=True,
                              world_size=len(worker_specs) + 1)
        endpoint = f"{host}:{self.store.port}"
        repo_root = os.path.dirname(os.path.dirname(
            os.path.dirname(os.path.abspath(__file__))))
        env = dict(os.environ)
        env["PYTHONPATH"] = (repo_root + os.pathsep
                             + env.get("PYTHONPATH", ""))
        self.supervisor: Optional[WorkerSupervisor] = None
        if supervise:
            incident_dir = cluster.get("incident_dir")
            state_dir = incident_dir or tempfile.mkdtemp(
                prefix="pdtpu-cluster-")
            self.supervisor = WorkerSupervisor(
                incident_dir=incident_dir, state_dir=state_dir,
                **dict(cfg.get("supervisor") or {}))
        for replica_id, spec in enumerate(worker_specs):
            wcfg = {
                "replica_id": replica_id,
                "role": spec.get("role", "unified"),
                "store": endpoint,
                "world_size": len(worker_specs),
                "job_id": job_id,
                "ttl": ttl,
                "host": host,
                "port": int(spec.get("port", 0)),
                "model": cfg.get("model") or {},
                "engine": cfg.get("engine") or {},
                "model_name": cluster.get("model_name", "paddle-tpu"),
                "platform": cluster.get("platform"),
                "incident_dir": cluster.get("incident_dir"),
                "handoff_wait_s": cluster.get("handoff_wait_s", 30.0),
            }
            if self.supervisor is not None:
                wcfg["deathnote"] = self.supervisor.deathnote_path(
                    replica_id)
            spawn = self._make_spawn(wcfg, env, repo_root)
            p = spawn(replica_id, 0)
            self.processes.append(p)
            self._replica_pids[replica_id] = p
            if self.supervisor is not None:
                self.supervisor.adopt(replica_id, spawn, p)
        self.pool = WorkerPool(store=self.store,
                               world_size=len(worker_specs),
                               job_id=job_id, ttl=ttl)
        self.router: Optional[RouterServer] = None
        # cluster.ts_interval_s is a SCOPED cadence override: remember
        # the process store's interval so close() restores it — a
        # gate-speed cluster (0.25s sampling) torn down inside a larger
        # process must not leave 4 Hz background sampling behind
        from ..observability.timeseries import get_store
        self._prev_ts_interval = get_store().interval_s
        # teardown must run even on an unhandled exit: atexit-armed and
        # idempotent (a second close(), from atexit after an explicit
        # close or a signal, is a no-op)
        atexit.register(self.close)
        if install_signal_handlers:
            self._install_signals()
        try:
            if wait:
                self._wait_for_launch(len(worker_specs), wait_timeout)
            self.pool.start()
            self.router = RouterServer(
                self.pool, host=host, port=int(cluster.get("port", 0)),
                model_name=cluster.get("model_name", "paddle-tpu"),
                max_retries=int(cluster.get("max_retries", 2)),
                # cluster watchtower knobs: sampler cadence and the
                # alert-window scale (the chaos dryrun runs second-scale
                # windows so fire->resolve is observable in one gate)
                ts_interval_s=cluster.get("ts_interval_s"),
                alert_time_scale=float(
                    cluster.get("alert_time_scale", 1.0)),
                supervisor=self.supervisor).start()
            if self.supervisor is not None:
                # the router's in-flight journal is the supervisor's
                # whole-batch blame fallback; wired here because the
                # router needs the pool first
                self.supervisor.inflight_fn = self.router.inflight_on
                self.supervisor.start()
        except BaseException:
            self.close()
            raise

    def _wait_for_launch(self, n: int, timeout: float):
        """Block until all ``n`` workers joined. A worker that EXITS
        before joining fails the launch at once — nothing restarts it
        yet (supervision starts after the join), so waiting out the
        timeout would only hide why."""
        deadline = time.monotonic() + timeout
        while not self.pool.wait_for_workers(n, timeout=0.5):
            for replica_id, p in self._replica_pids.items():
                code = p.poll()
                if code == EXIT_NO_DEVICE:
                    raise RuntimeError(
                        f"cluster: worker {replica_id} could not acquire "
                        f"a JAX device (exit {code}; its message is on "
                        f"stderr). An accelerator belongs to one process: "
                        f"{n} workers need {n} free chips on this host.")
                if code is not None:
                    raise RuntimeError(
                        f"cluster: worker {replica_id} exited with code "
                        f"{code} before joining the pool")
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    f"cluster: only {self.pool.alive_count()} of {n} "
                    f"workers joined within {timeout}s")

    def _make_spawn(self, wcfg: dict, env: dict, repo_root: str):
        """One worker's spawn closure — re-invoked by the supervisor on
        restart with a bumped incarnation (the chaos injector scopes
        faults by it, so a planned kill does not re-fire in the respawn
        it caused)."""

        def spawn(replica_id: int, incarnation: int) -> subprocess.Popen:
            child_env = dict(env)
            child_env[ENV_INCARNATION] = str(int(incarnation))
            # -c (not -m): runpy warns when the module is already in
            # sys.modules via the package import, and the entry is the
            # same main() either way
            return subprocess.Popen(
                [sys.executable, "-c",
                 "import sys; "
                 "from paddle_tpu.serving_cluster.worker import main; "
                 "sys.exit(main(sys.argv[1:]))",
                 json.dumps(wcfg)], env=child_env, cwd=repo_root)

        return spawn

    # ---- signals ---------------------------------------------------------
    def _install_signals(self):
        """Propagate SIGTERM/SIGINT to the worker subprocesses: the
        launcher dying must not orphan the tier. The previous handler
        (KeyboardInterrupt for SIGINT, the default death for SIGTERM)
        still runs AFTER the teardown. No-op off the main thread —
        signal wiring is impossible there, and close()/atexit still
        reap."""
        if threading.current_thread() is not threading.main_thread():
            return

        def handler(signum, frame):
            self.close()
            prev = self._prev_signals.get(signum)
            if callable(prev):
                prev(signum, frame)
            elif prev != signal.SIG_IGN:
                signal.signal(signum, signal.SIG_DFL)
                os.kill(os.getpid(), signum)

        for sig in (signal.SIGTERM, signal.SIGINT):
            try:
                self._prev_signals[sig] = signal.signal(sig, handler)
            except (ValueError, OSError) as e:
                get_logger().debug("cluster: signal %s not hooked (%s)",
                                   sig, e)

    def _restore_signals(self):
        for sig, prev in self._prev_signals.items():
            try:
                if signal.getsignal(sig) is not prev:
                    signal.signal(sig, prev)
            except (ValueError, OSError, TypeError):
                pass  # teardown off the main thread cannot rewire signals; the process is exiting anyway
        self._prev_signals = {}

    # ---- operations ------------------------------------------------------
    @property
    def address(self):
        return self.router.address

    def kill_worker(self, replica_id: int):
        """SIGKILL one worker's CURRENT incarnation (crash simulation —
        no clean deregistration, the lease must lapse / sockets must
        break for anyone to notice; under supervision the worker then
        restarts on the backoff ladder)."""
        if self.supervisor is not None:
            self.supervisor.kill(replica_id)
        else:
            self._replica_pids[replica_id].kill()

    def close(self):
        """Tear the tier down: stop routing, stop supervising, SIGTERM
        every worker and REAP it. Idempotent — the atexit hook, a signal
        handler and an explicit close can all race here safely."""
        if self._closed:
            return
        self._closed = True
        self._restore_signals()
        try:
            atexit.unregister(self.close)
        except Exception:  # pdlint: disable=silent-exception -- interpreter shutdown may have torn atexit down already; closing proceeds regardless
            pass
        if self.router is not None:
            self.router.close()
        from ..observability.timeseries import get_store

        get_store().set_interval(self._prev_ts_interval)
        self.pool.close()
        if self.supervisor is not None:
            # the supervisor owns the children now: terminate + reap
            # (and stop the monitor FIRST so nothing respawns what the
            # teardown just killed)
            self.supervisor.close()
        else:
            for p in self.processes:
                if p.poll() is None:
                    p.terminate()
            deadline = time.monotonic() + 10
            for p in self.processes:
                remain = max(0.1, deadline - time.monotonic())
                try:
                    p.wait(timeout=remain)
                except subprocess.TimeoutExpired:
                    get_logger().warning(
                        "cluster: worker pid %s ignored SIGTERM; killing",
                        p.pid)
                    p.kill()
                    p.wait(timeout=5)
        self.store.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


def launch_cluster(cfg: dict, **kw) -> Cluster:
    """Spawn workers + pool + router from a parsed config dict."""
    return Cluster(cfg, **kw)
