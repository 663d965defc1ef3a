"""RouterServer: the disaggregated tier's front door.

Admits ``POST /v1/completions`` on the same handler skeleton as the
single-process server (``serving_http.ServingHandlerBase`` — so /metrics,
/trace and /debug/* work identically on the router) and places each
request on a worker with **queue-depth-aware least-loaded scheduling**:
the pool scores every live worker by active slots + its own queue depth +
this router's not-yet-visible placements, and the emptiest one wins.

Streaming is relayed token by token (SSE in, SSE out). Fault handling is
placement-scoped: a worker that dies mid-request (socket error, EOF
before ``[DONE]``, 5xx) is marked dead in the pool and the request
REQUEUES onto another worker within a bounded retry budget — for greedy
streams the router skips the tokens it already delivered, so the client
sees one continuous, correct stream across the failover. Every placement
/ retry / loss decision is a flight-recorder event (``router.*``), and
the router's ``router.request``/``router.upstream`` spans propagate
``traceparent`` downstream, so one trace_id covers router and worker
spans across processes.

When the pool contains ``prefill``-role workers, requests run
disaggregated: a prefill worker computes the prompt KV and ships it over
the decode worker's handoff channel (``kv_handoff``), then the decode
worker streams tokens from the shipped state.
"""
from __future__ import annotations

import http.client
import json
import threading
import time
import urllib.request
import uuid
from http.server import ThreadingHTTPServer
from typing import List, Optional, Tuple

from ..analysis.threads.witness import make_lock
from ..chaos import inject as _chaos
from ..distributed.log_utils import get_logger
from ..observability import alerts as _alerts
from ..observability import flightrecorder as _frec
from ..observability import timeseries as _ts
from ..observability import tracing as _tracing
from ..observability.catalog import ROUTER_PLACEMENTS
from ..observability.metrics import PROMETHEUS_CONTENT_TYPE, get_registry
from ..serving_http import (AUDIT_HEADER, DEADLINE_HEADER,
                            ServingHandlerBase, alerts_payload,
                            kvstate_payload, profile_payload,
                            timeseries_payload)
from .pool import WorkerInfo, WorkerPool, jittered

__all__ = ["RouterServer"]


def _deadline_body(note: str = "") -> dict:
    return {"error": "request deadline exceeded" + note,
            "code": "deadline_exceeded"}


class _ClientError(Exception):
    """The worker judged the request invalid (4xx): forward verbatim,
    never retry — a bad request is bad on every replica."""

    def __init__(self, status: int, body: dict):
        super().__init__(f"client error {status}")
        self.status = status
        self.body = body


class _UpstreamError(Exception):
    """A placement attempt failed for reasons a DIFFERENT worker might
    not share: transport death, 5xx, mid-stream EOF. ``dead`` names a
    worker the router observed failing at the socket level (marked dead
    in the pool immediately — the lease would take up to ttl to lapse)."""

    def __init__(self, reason: str, dead: Optional[WorkerInfo] = None,
                 exclude: Tuple[int, ...] = ()):
        super().__init__(reason)
        self.reason = reason
        self.dead = dead
        self.exclude = exclude


class _WorkerBusy(Exception):
    """The worker answered 429 (bounded admission queue): placement
    FEEDBACK, not a failure — skip the worker for a short backoff and
    try another without marking it dead or burning the failover-retry
    budget. If every worker is busy the client gets the 429 +
    Retry-After back."""

    def __init__(self, worker: WorkerInfo, body: dict,
                 retry_after: str = "1"):
        super().__init__(f"worker {worker.replica_id} busy")
        self.worker = worker
        self.body = body
        self.retry_after = retry_after


class _ClientGone(Exception):
    """The DOWNSTREAM client disconnected mid-relay; nothing to answer."""


class _DeadlineExpired(Exception):
    """The request's end-to-end SLO budget ran out at the router —
    before a placement, or mid-hop (the upstream timeout now derives
    from the REMAINING budget, not a fixed constant). Terminal and
    typed: 504 with ``code=deadline_exceeded``, never a retry (another
    replica cannot un-expire a global deadline) and never a mark_dead
    (the worker did nothing wrong)."""


class _Migrated(Exception):
    """The upstream worker ended the stream with a migrate marker: the
    request's slot was exported to another worker (drain / rebalance).
    NOT a failure — the relay continues on the destination by claiming
    the named handoff id, without burning the failover-retry budget."""

    def __init__(self, info: dict):
        super().__init__(f"migrated to {info.get('dst')}")
        self.info = info


class RouterServer:
    """HTTP front-end placing completions across a WorkerPool."""

    #: bound on PLANNED migration hops per request (a drain chain, not a
    #: retry budget) — a pathological migrate loop must still terminate
    max_migrations = 16

    def __init__(self, pool: WorkerPool, host: str = "127.0.0.1",
                 port: int = 0, model_name: str = "paddle-tpu",
                 max_retries: int = 2, upstream_timeout: float = 120.0,
                 retry_backoff_s: float = 0.05,
                 enable_tracing: bool = True,
                 enable_flight_recorder: bool = True,
                 enable_timeseries: bool = True,
                 ts_interval_s: Optional[float] = None,
                 alert_objectives=None, alert_time_scale: float = 1.0,
                 quarantine=None, supervisor=None):
        self.pool = pool
        self.model_name = model_name
        self.max_retries = int(max_retries)
        self.upstream_timeout = float(upstream_timeout)
        # poison containment (supervisor.QuarantineLedger): a request id
        # implicated in >= 2 distinct worker deaths answers a typed 422
        # code=request_quarantined and is NEVER placed again — one
        # poisoned input must not serially crash the whole tier
        self._quarantine = quarantine
        # the worker supervisor (when this router fronts a supervised
        # launcher): notified the moment a placement socket observes a
        # death, so deathnote blame lands before the next retry; its
        # state() rides /health as the degraded-capacity report
        self._supervisor = supervisor
        if (self._quarantine is None and supervisor is not None):
            self._quarantine = supervisor.ledger
        # in-flight journal: request_id -> replica_id currently serving
        # it — the imprecise whole-batch blame fallback the supervisor
        # reads when a worker dies without arming a deathnote
        self._journal = {}
        # jittered sleep before each failover retry: after a mass event
        # (worker death under load) every relay would otherwise hammer
        # the survivors in the same instant
        self.retry_backoff_s = float(retry_backoff_s)
        if enable_tracing:
            _tracing.get_tracer().enable()
        self._tracer = _tracing.get_tracer()
        if enable_flight_recorder:
            _frec.get_recorder().enable()
        # cluster watchtower: the router's ts-sampler additionally
        # federates pool/supervisor-derived series (per-replica worker
        # counters off the probes the pool already runs, live-worker
        # count, breaker state) into the process store, and a CLUSTER
        # AlertManager judges the tier-level objectives over it — one
        # GET /alerts answers "is the tier healthy" with history
        self._alert_mgr = None
        self._ts_store = None
        if enable_timeseries:
            self._ts_store = _ts.get_store()
            self._ts_store.add_collector(self._collect_cluster)
            self._ts_store.start(interval_s=ts_interval_s)
            self._alert_mgr = _alerts.AlertManager(
                self._ts_store,
                alert_objectives
                or _alerts.cluster_objectives(alert_time_scale),
                name="cluster").attach()
        self._lock = make_lock("RouterServer._lock")
        self._placed = 0
        self._retried = 0
        self._failed = 0
        self._busy = 0
        self._deadline = 0
        self._quarantined_hits = 0
        self._httpd = ThreadingHTTPServer((host, port),
                                          self._make_handler())
        self._http_thread = threading.Thread(
            target=self._httpd.serve_forever, daemon=True,
            name="router-http-loop")

    # ---- lifecycle -----------------------------------------------------
    @property
    def address(self):
        return self._httpd.server_address

    def start(self):
        self._http_thread.start()
        return self

    def close(self):
        if self._ts_store is not None:
            # the store is a process singleton that outlives this
            # router: unhook the collector/listener so a torn-down
            # router's dead pool is never sampled again
            self._ts_store.remove_collector(self._collect_cluster)
            if self._alert_mgr is not None:
                self._alert_mgr.detach()
        self._httpd.shutdown()
        self._httpd.server_close()

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.close()

    # ---- handler hooks ---------------------------------------------------
    def _make_handler(server_self):
        class Handler(ServingHandlerBase):
            server_obj = server_self
            # the router's POST span is router.request, not http.request:
            # it parents router.upstream AND (via the forwarded
            # traceparent) the worker's http.request across the process
            # boundary
            post_span_name = _tracing.SPAN_ROUTER_REQUEST

        return Handler

    def _refresh_metrics(self):
        self.pool.refresh_gauges()

    def _health_payload(self) -> dict:
        """The POOL's health, aggregated: per-worker liveness + occupancy
        (so one scrape shows a load balancer the whole tier), the
        router's own placement counters, and — under supervision — the
        supervisor's restart/breaker/quarantine report. ``status`` says
        ``degraded`` while a breaker holds a worker down or a restart is
        pending: the tier serves, but below its provisioned capacity."""
        workers = self.pool.workers()
        alive = sum(1 for w in workers if w["alive"])
        roles: dict = {}
        for w in workers:
            if w["alive"]:
                roles[w["role"]] = roles.get(w["role"], 0) + 1
        with self._lock:
            router_stats = {"placed": self._placed,
                            "retried": self._retried,
                            "failed": self._failed,
                            "busy": self._busy,
                            "deadline": self._deadline,
                            "quarantined": self._quarantined_hits,
                            "max_retries": self.max_retries}
        status = "ok" if alive else "unavailable"
        supervisor = None
        if self._supervisor is not None:
            supervisor = self._supervisor.state()
            # the ledger's full implication lists are forensics
            # (SUPERVISOR.json / read_incident --index); /health carries
            # the operator summary
            q = supervisor.pop("quarantine", {})
            supervisor["quarantined"] = sorted(q.get("quarantined", ()))
            supervisor["deaths_recorded"] = q.get("deaths_recorded", 0)
            degraded = (supervisor["breakers_open"] > 0
                        or any(not w["alive"]
                               for w in supervisor["workers"].values()))
            if alive and degraded:
                status = "degraded"
        payload = {
            "status": status,
            "alive": alive,
            "roles": roles,
            "workers": {str(w["replica_id"]): w for w in workers},
            "router": router_stats,
        }
        if supervisor is not None:
            payload["supervisor"] = supervisor
        return payload

    def _models_payload(self) -> dict:
        return {"object": "list",
                "data": [{"id": self.model_name, "object": "model"}]}

    def _timeseries_payload(self, query: str) -> dict:
        return timeseries_payload(query)

    def _alerts_payload(self) -> dict:
        # the CLUSTER manager: tier-level objectives over the federated
        # store, not the per-process serving defaults
        return alerts_payload(self._alert_mgr)

    # ---- metrics federation ----------------------------------------------
    # the worker-stats counters the collector federates as per-replica
    # cluster_* series (keys off the engines' shared stats() schema);
    # alerts.FEDERATED_SERIES pins the resulting names for the lint
    _FEDERATED_STATS = (
        ("requests_admitted", "cluster_requests_admitted"),
        ("requests_finished", "cluster_requests_finished"),
        ("requests_shed", "cluster_requests_shed"),
        ("deadline_misses", "cluster_deadline_misses"),
        ("tokens_generated", "cluster_tokens_generated"),
    )

    # the step-anatomy profiler's scalar federated as a per-replica GAUGE
    # (the watch_cluster perf panel's sparkline feed); same /health-probe
    # transport as the counters above — a sample never does network I/O
    _FEDERATED_PERF = (
        ("profile_step_ms", "cluster_profile_step_ms"),
    )

    # KV-atlas scalars federated as per-replica GAUGES (the
    # watch_cluster MEM panel's sparkline feed + the capacity signal
    # ROADMAP item 4 consumes); same zero-I/O transport
    _FEDERATED_KV = (
        ("kv_pages_in_use", "cluster_kv_pages_in_use"),
        ("kv_bytes", "cluster_kv_bytes"),
        ("kv_headroom_slots", "cluster_kv_headroom_slots"),
        ("prefix_hit_ratio", "cluster_prefix_hit_ratio"),
    )

    # correctness-sentinel scalars federated per replica: the verdict
    # counters feed the cluster_audit_divergence objective, the drift
    # gauge feeds the watch_cluster AUDIT sparkline; same zero-I/O
    # /health-probe transport (kind rides the tuple — counters and a
    # gauge share the table)
    _FEDERATED_AUDIT = (
        ("audit_pass", "cluster_audit_pass", "counter"),
        ("audit_diverged", "cluster_audit_diverged", "counter"),
        ("audit_skipped", "cluster_audit_skipped", "counter"),
        ("audit_drift", "cluster_audit_drift", "gauge"),
    )

    def _collect_cluster(self) -> list:
        """ts-sampler collector: pool/supervisor-derived series. Reads
        ONLY state the pool's own /health probes already hold — a
        sample never does network I/O."""
        out: list = []
        alive = 0
        for rid, w_alive, stats in self.pool.worker_stats():
            if not w_alive:
                continue
            alive += 1
            labels = {"replica": str(rid)}
            for key, series in self._FEDERATED_STATS:
                if key in stats:
                    out.append((series, "counter", labels,
                                float(stats.get(key) or 0), None))
            for key, series in self._FEDERATED_PERF:
                if key in stats:
                    out.append((series, "gauge", labels,
                                float(stats.get(key) or 0), None))
            for key, series in self._FEDERATED_KV:
                if key in stats:
                    out.append((series, "gauge", labels,
                                float(stats.get(key) or 0), None))
            for key, series, kind in self._FEDERATED_AUDIT:
                if key in stats:
                    out.append((series, kind, labels,
                                float(stats.get(key) or 0), None))
        out.append(("cluster_workers_alive", "gauge", {}, float(alive),
                    None))
        breakers = 0.0
        if self._supervisor is not None:
            try:
                breakers = float(self._supervisor.state()["breakers_open"])
            except Exception as e:
                get_logger().debug("federation: supervisor state "
                                   "unavailable (%s: %s)",
                                   type(e).__name__, e)
        out.append(("cluster_breakers_open", "gauge", {}, breakers, None))
        return out

    def _scrape_worker(self, url: str) -> str:
        timeout = getattr(self.pool, "_probe_timeout", 2.0)
        with urllib.request.urlopen(url + "/metrics", timeout=timeout) as r:
            return r.read().decode("utf-8", errors="replace")

    @staticmethod
    def _merge_exposition(text: str, replica: str, seen_meta: set
                          ) -> List[str]:
        """Label-merge one process's exposition into the federated view:
        every sample line gains ``replica="N"``; # HELP/# TYPE headers
        are kept once per family; other comments (exemplars) are
        dropped — a federated surface carries samples, not per-process
        annotations."""
        lines: List[str] = []
        for line in text.splitlines():
            if not line.strip():
                continue
            if line.startswith("#"):
                parts = line.split(None, 3)
                if len(parts) >= 3 and parts[1] in ("HELP", "TYPE"):
                    meta_key = (parts[1], parts[2])
                    if meta_key not in seen_meta:
                        seen_meta.add(meta_key)
                        lines.append(line)
                continue
            name, _, rest = line.partition("{")
            if rest:                                 # name{labels} value
                lines.append(f'{name}{{replica="{replica}",{rest}')
            else:                                    # name value
                name, _, value = line.partition(" ")
                lines.append(f'{name}{{replica="{replica}"}} {value}')
        return lines

    def _cluster_metrics_text(self) -> str:
        """``GET /metrics/cluster``: one exposition for the whole tier —
        the router's own registry (``replica="router"``), every live
        worker's /metrics scraped and label-merged per replica, and the
        pool/supervisor-derived gauges. A worker that fails its scrape
        contributes a comment, never a 5xx: a half-scraped tier view
        still beats none mid-incident."""
        seen_meta: set = set()
        lines = self._merge_exposition(
            get_registry().render_prometheus(), "router", seen_meta)
        for w in self.pool.workers():
            if not w["alive"]:
                continue
            rid = str(w["replica_id"])
            try:
                text = self._scrape_worker(w["url"])
            except (OSError, ValueError) as e:
                lines.append(f'# scrape_error replica="{rid}" '
                             f'{type(e).__name__}: {e}')
                continue
            lines.extend(self._merge_exposition(text, rid, seen_meta))
        for name, kind, labels, value, _e in self._collect_cluster():
            label_s = "".join(f'{{replica="{v}"}}'
                              for k, v in labels.items() if k == "replica")
            meta_key = ("TYPE", name)
            if meta_key not in seen_meta:
                seen_meta.add(meta_key)
                lines.append(f"# TYPE {name} {kind}")
            lines.append(f"{name}{label_s} {value:g}")
        return "\n".join(lines) + "\n"

    def _cluster_profile(self, query: str) -> dict:
        """``GET /profile/cluster``: every live worker's /profile
        fetched and keyed by replica id. Same contract as the metrics
        federation — a worker that fails its fetch contributes an error
        entry, never a 5xx."""
        q = f"?{query}" if query else ""
        timeout = getattr(self.pool, "_probe_timeout", 2.0)
        out: dict = {"schema_version": 1, "replicas": {}, "errors": {}}
        for w in self.pool.workers():
            if not w["alive"]:
                continue
            rid = str(w["replica_id"])
            try:
                with urllib.request.urlopen(w["url"] + "/profile" + q,
                                            timeout=timeout) as r:
                    out["replicas"][rid] = json.loads(r.read())
            except (OSError, ValueError) as e:
                out["errors"][rid] = f"{type(e).__name__}: {e}"
        return out

    def _cluster_kvstate(self, query: str) -> dict:
        """``GET /kvstate/cluster``: every live worker's /kvstate fetched
        and keyed by replica id, plus each replica's pool-published kv
        summary (prefix hashes + headroom off store metadata — readable
        even when the worker's HTTP fetch fails). Same contract as the
        other federations: fetch failures land in ``errors``, never a
        5xx."""
        q = f"?{query}" if query else ""
        timeout = getattr(self.pool, "_probe_timeout", 2.0)
        out: dict = {"schema_version": 1, "replicas": {}, "errors": {},
                     "pool": {}}
        for w in self.pool.workers():
            if not w["alive"]:
                continue
            rid = str(w["replica_id"])
            if w.get("kv") is not None:
                out["pool"][rid] = w["kv"]
            try:
                with urllib.request.urlopen(w["url"] + "/kvstate" + q,
                                            timeout=timeout) as r:
                    out["replicas"][rid] = json.loads(r.read())
            except (OSError, ValueError) as e:
                out["errors"][rid] = f"{type(e).__name__}: {e}"
        return out

    def _cluster_audit(self, query: str) -> dict:
        """``GET /audit/cluster``: every live worker's /audit fetched and
        keyed by replica id — the tier-wide sentinel view (who audited,
        who skipped, whose canaries drifted, where the sealed divergence
        bundles live). Same contract as the other federations: fetch
        failures land in ``errors``, never a 5xx."""
        q = f"?{query}" if query else ""
        timeout = getattr(self.pool, "_probe_timeout", 2.0)
        out: dict = {"schema_version": 1, "replicas": {}, "errors": {}}
        for w in self.pool.workers():
            if not w["alive"]:
                continue
            rid = str(w["replica_id"])
            try:
                with urllib.request.urlopen(w["url"] + "/audit" + q,
                                            timeout=timeout) as r:
                    out["replicas"][rid] = json.loads(r.read())
            except (OSError, ValueError) as e:
                out["errors"][rid] = f"{type(e).__name__}: {e}"
        return out

    def _extra_get(self, handler, route, query) -> bool:
        if route == "/metrics/cluster":
            handler._count(200)
            body = self._cluster_metrics_text().encode()
            handler.send_response(200)
            handler.send_header("Content-Type", PROMETHEUS_CONTENT_TYPE)
            handler.send_header("Content-Length", str(len(body)))
            handler.end_headers()
            handler.wfile.write(body)
            return True
        if route == "/profile":
            # the router process has no engine; the payload is its own
            # (empty) profiler view — the federated one is next door
            handler._json(200, profile_payload(query))
            return True
        if route == "/profile/cluster":
            handler._json(200, self._cluster_profile(query))
            return True
        if route == "/kvstate":
            # no engine in the router process — the (empty) local atlas
            # view; the federated one is next door
            handler._json(200, kvstate_payload(query))
            return True
        if route == "/kvstate/cluster":
            handler._json(200, self._cluster_kvstate(query))
            return True
        if route == "/audit":
            # no engine in the router process — the (empty) local
            # sentinel view; the federated one is next door
            from ..observability import sentinel as _sentinel

            handler._json(200, _sentinel.audit_payload())
            return True
        if route == "/audit/cluster":
            handler._json(200, self._cluster_audit(query))
            return True
        return False

    def _post_handler(self, route):
        if route == "/v1/completions":
            return self._complete
        if route == "/drain":
            return self._drain
        return None

    # ---- graceful drain --------------------------------------------------
    def _drain(self, handler, req):
        """``POST /drain {"replica_id": N}``: gracefully drain a worker —
        stop its admission, migrate its live slots to peers (zero token
        loss), then release its pool lease. Answers the drain summary."""
        try:
            replica = int(req["replica_id"])
        except (KeyError, TypeError, ValueError):
            return handler._json(400, {
                "error": "drain needs an integer 'replica_id'"})
        try:
            summary = self.drain_worker(
                replica, timeout=float(req.get("timeout", 60.0)))
        except ValueError as e:
            return handler._json(404, {"error": str(e)})
        except _ClientError as e:
            # the worker judged a drain-path request invalid: forward
            # the verdict verbatim, as the completion path would
            return handler._json(e.status, e.body)
        except _WorkerBusy as e:
            return handler._json(429, dict(e.body,
                                           retry_after=e.retry_after))
        except _DeadlineExpired:
            return handler._json(504, _deadline_body())
        except _UpstreamError as e:
            return handler._json(502, {
                "error": f"drain failed upstream: {e.reason}"})
        except Exception as e:
            return handler._json(502, {
                "error": f"drain failed: {type(e).__name__}: {e}"})
        return handler._json(200, summary)

    def drain_worker(self, replica_id: int, timeout: float = 60.0) -> dict:
        """Drain one worker: mark it draining in the pool (no new
        placements), stop its admission (worker ``/drain``), migrate
        every active slot to a peer with a handoff channel (the relays
        follow their migrate markers), wait for the worker to empty, and
        release its lease. Slots that cannot migrate (no destination,
        n>1 sibling groups) finish locally — the drain waits them out.

        Upgrades scale-down and deploys from "kill and re-prefill" to
        zero-token-loss: a migrated stream is token-identical and its
        SSE delivery continuous."""
        w = self.pool.get(int(replica_id))
        if w is None or not w.alive:
            raise ValueError(f"no live worker {replica_id} in the pool")
        self.pool.set_draining(replica_id)
        migrated, failed = [], []
        deadline = time.monotonic() + float(timeout)
        drained = False
        while time.monotonic() < deadline:
            status, body = self._post_json(w, "/drain", {}, None)
            if status != 200:
                raise RuntimeError(
                    f"worker {replica_id} refused /drain: {status} "
                    f"{body.get('error', body)}")
            active = [int(r) for r in body.get("active") or []]
            if not (active or body.get("queued")
                    or body.get("prefilling")):
                drained = True
                break
            for rid in active:
                dst = self.pool.select(roles=("decode", "unified"),
                                       exclude=(int(replica_id),))
                if dst is None or not dst.kv_channel:
                    if dst is not None:
                        self.pool.release(dst)
                    # no migration destination: the slot finishes
                    # locally, the drain loop waits it out
                    if rid not in failed:
                        failed.append(rid)
                    continue
                hid = uuid.uuid4().hex
                try:
                    st, resp = self._post_json(
                        w, "/v1/migrate_out",
                        {"rid": rid, "channel": dst.kv_channel,
                         "dst": dst.replica_id, "handoff_id": hid}, None)
                finally:
                    self.pool.release(dst)
                if st == 200:
                    migrated.append(rid)
                    if rid in failed:
                        failed.remove(rid)
                elif rid not in failed:
                    # 409: finished / not yet decoding — next round
                    failed.append(rid)
            time.sleep(0.1)
        released = False
        if drained:
            st, _resp = self._post_json(w, "/v1/release", {}, None)
            released = (st == 200)
        get_logger().info(
            "router: drained worker %s (migrated=%s, local=%s, "
            "released=%s)", replica_id, migrated, failed, released)
        return {"replica_id": int(replica_id), "drained": drained,
                "migrated": migrated, "finished_locally": failed,
                "released": released}

    # ---- placement -------------------------------------------------------
    def _plan(self, exclude: Tuple[int, ...]):
        """(mode, prefill_worker | None, serve_worker) or None. Disagg
        when a prefill-role worker AND a handoff-capable decode target
        are both live; direct otherwise."""
        serve = self.pool.select(roles=("decode", "unified"),
                                 exclude=exclude)
        if serve is None:
            return None
        try:
            if self.pool.has_role("prefill") and serve.kv_channel:
                pre = self.pool.select(roles=("prefill",),
                                       exclude=exclude)
                if pre is not None:
                    return ("disagg", pre, serve)
            return ("direct", None, serve)
        except BaseException:
            # the lease counts pending load on the worker; an exception
            # between select() and the ownership-transferring return
            # would otherwise leave phantom load behind forever
            self.pool.release(serve)
            raise

    def _count_outcome(self, outcome: str):
        ROUTER_PLACEMENTS.inc(outcome=outcome)
        with self._lock:
            if outcome == "placed":
                self._placed += 1
            elif outcome == "retried":
                self._retried += 1
            elif outcome == "failed":
                self._failed += 1
            elif outcome == "busy":
                self._busy += 1
            elif outcome == "deadline":
                self._deadline += 1
            elif outcome == "quarantined":
                self._quarantined_hits += 1

    def _busy_blocked(self, exclude: Tuple[int, ...]):
        """When placement found no worker, distinguish FULL from DOWN:
        returns a live, non-draining, non-excluded worker that is only
        unavailable because of a 429 busy backoff (None when the pool is
        genuinely empty/dead). A full tier answers 429; only a dead one
        earns the 502."""
        candidates = [w for w in self.pool.workers()
                      if w["alive"] and not w["draining"]
                      and w["replica_id"] not in exclude]
        if not (candidates and all(w["busy"] for w in candidates)):
            return None
        return self.pool.get(candidates[0]["replica_id"])

    def _retry_after_for(self, worker: WorkerInfo) -> str:
        """Retry-After fallback when a 429 carries no header: the
        worker's last-reported backlog divided by its observed drain
        rate (both from the pool's /health polls), clamped to [1s, 30s]
        — backoff reflects actual congestion, not a constant."""
        w = self.pool.get(worker.replica_id) or worker
        depth = max(1, int(getattr(w, "queued", 0) or 0)
                    + int(getattr(w, "active", 0) or 0))
        rate = getattr(w, "drain_rate", None)
        est = depth / rate if rate else 1.0
        return str(max(1, min(30, round(est))))

    def _complete(self, handler, req):
        stream = bool(req.get("stream"))
        # the on-demand audit header survives the router hop as the
        # equivalent body field (upstream hops carry only the parsed
        # body; the worker accepts either form — serving_http
        # AUDIT_HEADER) and so also survives a failover re-placement
        hdr = (handler.headers.get(AUDIT_HEADER) or "").strip().lower()
        if hdr in ("1", "true") and "audit" not in req:
            req = dict(req, audit=True)
        # the request's cluster-wide identity: the client's request_id,
        # or one stamped here — every upstream hop carries it (the
        # engine's deathnote names it), the in-flight journal keys on
        # it, and the quarantine ledger refuses it after 2 worker
        # deaths. A router-stamped id still contains a crash loop WITHIN
        # this relay's retry budget; a client-provided id additionally
        # survives re-submissions.
        req_id = str(req.get("request_id")
                     or f"req-{uuid.uuid4().hex[:16]}")
        req = dict(req, request_id=req_id)
        # relay state survives retries: once SSE headers (or tokens) hit
        # the client socket, a failover must continue the SAME stream —
        # delivered counts the token chunks already written so the
        # replacement worker's (deterministic) stream is deduplicated
        state = {"headers_sent": False, "delivered": 0}
        exclude: Tuple[int, ...] = ()
        attempts = 0
        hops = 0      # planned migration continuations (not failures)
        cont = None   # migrate-marker info pinning the next hop
        last_reason = "no live worker available"
        busy: Optional[_WorkerBusy] = None
        root = handler._trace_span
        # end-to-end deadline: stamped at ARRIVAL, so every placement
        # attempt (and the X-Request-Deadline header each hop carries)
        # works off the remaining budget, not a fresh one
        slo_deadline = None
        try:
            slo = req.get("slo_ms")
            if slo is not None and float(slo) > 0:
                slo_deadline = time.monotonic() + float(slo) / 1000.0
        except (TypeError, ValueError):
            pass   # malformed slo_ms: the worker's 400 will name it
        while attempts <= self.max_retries and hops <= self.max_migrations:
            if (self._quarantine is not None
                    and self._quarantine.is_quarantined(req_id)):
                # poison containment: this rid has now been implicated
                # in >= 2 distinct worker deaths — typed 422, never
                # another placement (checked per attempt, so the retry
                # loop itself stops the serial crash amplification the
                # moment the second death lands)
                self._respond_quarantined(handler, state, req_id)
                return
            if (slo_deadline is not None
                    and time.monotonic() >= slo_deadline):
                # shed at the router: the budget is spent, so placing
                # the request would burn a prefill on a stream nobody
                # can use — answer typed instead
                self._respond_deadline(handler, state, slo_deadline)
                return
            rec = _frec.RECORDER
            pre = None
            if cont is not None:
                # a migrate marker pinned the destination: follow the
                # stream there by claiming its handoff id — a PLANNED
                # hop, so it spends max_migrations, not the retry budget
                info, cont = cont, None
                serve = self.pool.get(int(info.get("dst", -1)))
                if serve is None or not serve.alive:
                    # the drain's destination vanished before the
                    # continuation landed: fall back to a full replay
                    attempts += 1
                    last_reason = (f"migration destination "
                                   f"{info.get('dst')} left the pool")
                    self._count_outcome("retried")
                    continue
                self.pool.claim(serve)
                hops += 1
                mode = "migrate"
                # the destination streams only NEW tokens, numbered from
                # the bundle's generated count
                base = int(info.get("generated", state["delivered"]))
                up_req = {"handoff_id": info["handoff_id"],
                          "stream": stream}
            else:
                plan = self._plan(exclude)
                if plan is None:
                    break
                mode, pre, serve = plan
                attempts += 1
                base = 0
            try:
                self._journal_place(req_id, serve.replica_id)
                if rec.enabled:
                    rec.record(_frec.EV_ROUTER_PLACE,
                               replica_id=serve.replica_id,
                               role=serve.role, score=serve.score(),
                               attempt=attempts, mode=mode)
                sp = self._tracer.start_span(
                    _tracing.SPAN_ROUTER_UPSTREAM, parent=root,
                    attrs={"replica_id": serve.replica_id,
                           "role": serve.role, "attempt": attempts,
                           "mode": mode})
            except BaseException:
                # the attempt never started, so the attempt's finally
                # below can never run — the leases would stay counted as
                # phantom pending load on the workers. Releases first:
                # they cannot raise, the journal write could
                self.pool.release(serve)
                if pre is not None:
                    self.pool.release(pre)
                self._journal_clear(req_id)
                raise
            try:
                if mode != "migrate":
                    up_req = req
                    if mode == "disagg":
                        hid = self._prefill_hop(pre, serve, req, sp,
                                                deadline=slo_deadline)
                        up_req = {k: v for k, v in req.items()
                                  if k not in ("prompt",
                                               "prompt_token_ids",
                                               "pixel_values")}
                        up_req["handoff_id"] = hid
                if stream:
                    self._proxy_stream(handler, serve, up_req, state, sp,
                                       base=base, deadline=slo_deadline)
                else:
                    status, body = self._post_json(
                        serve, "/v1/completions", up_req, sp,
                        deadline=slo_deadline)
                    if 400 <= status < 500:
                        raise _ClientError(status, body)
                    if status != 200:
                        raise _UpstreamError(
                            f"worker {serve.replica_id} answered "
                            f"{status}: {body.get('error', body)}")
                    if isinstance(body, dict) and body.get("migrated"):
                        raise _Migrated(body["migrated"])
                    handler._json(200, body)
                sp.end()
                self._count_outcome("placed")
                return
            except _DeadlineExpired:
                # the budget ran out mid-hop: typed 504 / error chunk,
                # no retry, no mark_dead — the worker is innocent
                sp.end("error")
                self._respond_deadline(handler, state, slo_deadline)
                return
            except _Migrated as e:
                sp.end()  # the upstream hop SUCCEEDED — by migrating
                cont = e.info
                if rec.enabled:
                    rec.record(_frec.EV_ROUTER_RETRY,
                               replica_id=serve.replica_id,
                               attempt=attempts,
                               delivered=state["delivered"],
                               reason=("migrated to "
                                       f"{e.info.get('dst')}"))
            except _ClientError as e:
                sp.end("error")
                if state["headers_sent"]:
                    # the status line is long gone (a migrated stream's
                    # continuation can 4xx/deadline-504 after tokens
                    # flowed): end the SSE typed, without [DONE]
                    try:
                        handler._chunk(b"data: "
                                       + json.dumps(e.body).encode()
                                       + b"\n\n")
                        handler._chunk(b"")
                    except OSError:
                        handler.close_connection = True
                else:
                    handler._json(e.status, e.body)
                return
            except _ClientGone:
                sp.end("cancelled")
                handler.close_connection = True
                return
            except _WorkerBusy as e:
                sp.end("busy")
                # placement FEEDBACK, not a failure: short busy backoff
                # (not mark_dead), skip the worker this request, and do
                # NOT burn the failover-retry budget on backpressure
                busy = e
                attempts -= 1
                self.pool.mark_busy(e.worker.replica_id)
                exclude = exclude + (e.worker.replica_id,)
                if rec.enabled:
                    rec.record(_frec.EV_ROUTER_RETRY,
                               replica_id=e.worker.replica_id,
                               attempt=attempts + 1,
                               delivered=state["delivered"],
                               reason="busy")
                self._count_outcome("busy")
            except _UpstreamError as e:
                sp.end("error")
                last_reason = e.reason
                if e.dead is not None:
                    self.pool.mark_dead(e.dead.replica_id, "connection")
                    if self._supervisor is not None:
                        # blame NOW, before the retry places this rid
                        # again: the supervisor checks waitpid, reads
                        # the worker's deathnote (falling back to this
                        # relay's journal entry) and records the death
                        # in the quarantine ledger — the loop-top check
                        # sees a second death immediately
                        self._supervisor.note_worker_death(
                            e.dead.replica_id, fallback_rids=(req_id,))
                if e.dead is not None or mode != "disagg":
                    blame = (serve.replica_id,)
                else:
                    # a disagg decode worker answering 5xx is usually
                    # reporting a BUNDLE problem (handoff never arrived,
                    # checksum refused) — the worker is innocent, so a
                    # retry may re-plan the same pair with a freshly
                    # exported bundle instead of exhausting the pool
                    blame = ()
                exclude = exclude + blame + tuple(e.exclude)
                if rec.enabled:
                    rec.record(_frec.EV_ROUTER_RETRY,
                               replica_id=serve.replica_id,
                               attempt=attempts,
                               delivered=state["delivered"],
                               reason=e.reason)
                self._count_outcome("retried")
                get_logger().warning(
                    "router: placement attempt %s on replica %s failed "
                    "(%s); requeueing", attempts, serve.replica_id,
                    e.reason)
                if self.retry_backoff_s > 0:
                    # jittered, so a mass failure doesn't stampede every
                    # relay onto the survivors in the same instant
                    time.sleep(jittered(self.retry_backoff_s))
            finally:
                # releases first (no-raise decrements), then the span,
                # then the journal write — ordered so nothing that can
                # fail runs before a resource others account for is
                # given back. Span.end is idempotent (first end wins):
                # the typed ends in the handlers above stay
                # authoritative, this only catches exceptions no
                # handler matched, where the span would otherwise never
                # reach the trace buffer
                self.pool.release(serve)
                if pre is not None:
                    self.pool.release(pre)
                sp.end("error")
                self._journal_clear(req_id)
        # retry budget exhausted (or the pool is empty) — but if this
        # rid's LAST death is what emptied the pool, the quarantine may
        # have tripped after the loop-top check: answer the typed 422,
        # not a 502 (the tier is poisoned-by-this-request, not down)
        if (self._quarantine is not None
                and self._quarantine.is_quarantined(req_id)):
            self._respond_quarantined(handler, state, req_id)
            return
        self._count_outcome("failed")
        if not state["headers_sent"]:
            if busy is not None:
                # every placeable worker pushed back: forward the
                # backpressure (429 + Retry-After), never a 502 — the
                # tier is healthy, just full
                handler._json(429,
                              busy.body or {"error": "all workers busy"},
                              headers=(("Retry-After",
                                        busy.retry_after),))
                return
            blocked = self._busy_blocked(exclude)
            if blocked is not None:
                # this request saw no 429 itself, but every live worker
                # is sitting out a busy backoff earned from OTHER
                # requests' rejections — same situation, same typed
                # answer: the tier is at admission capacity, not down
                handler._json(
                    429, {"error": "all workers are at admission "
                                   "capacity; retry later"},
                    headers=(("Retry-After",
                              self._retry_after_for(blocked)),))
                return
        msg = (f"could not serve the request after {attempts} "
               f"placement attempt(s): {last_reason}")
        if state["headers_sent"]:
            # mid-stream: the status line is long gone — end the SSE with
            # an error and WITHOUT [DONE] (failed streams must not look
            # clean), exactly like the single-process server
            try:
                handler._chunk(b'data: {"error": '
                               + json.dumps(msg).encode() + b"}\n\n")
                handler._chunk(b"")
            except OSError:
                handler.close_connection = True
        else:
            handler._json(502, {"error": msg})

    # ---- poison quarantine ----------------------------------------------
    def _journal_place(self, req_id: str, replica_id: int):
        with self._lock:
            self._journal[req_id] = int(replica_id)

    def _journal_clear(self, req_id: str):
        with self._lock:
            self._journal.pop(req_id, None)

    def inflight_on(self, replica_id: int):
        """Request ids this router currently has placed on ``replica_id``
        — the supervisor's whole-batch blame fallback when a worker dies
        without arming a deathnote."""
        with self._lock:
            return [rid for rid, r in self._journal.items()
                    if r == int(replica_id)]

    def _respond_quarantined(self, handler, state: dict, req_id: str):
        """Answer a quarantined rid typed: 422 ``request_quarantined``
        before any bytes went out, an error chunk (no [DONE]) mid-stream
        — and NEVER another placement; the 4xx contract (a bad request
        is bad on every replica) now extends to requests proven to kill
        replicas."""
        self._count_outcome("quarantined")
        body = {"error": (f"request {req_id} quarantined: implicated in "
                          "repeated worker crashes; it will not be "
                          "retried"),
                "code": "request_quarantined"}
        if state["headers_sent"]:
            try:
                handler._chunk(b"data: " + json.dumps(body).encode()
                               + b"\n\n")
                handler._chunk(b"")
            except OSError:
                handler.close_connection = True
        else:
            handler._json(422, body)

    # ---- upstream hops ---------------------------------------------------
    def _respond_deadline(self, handler, state: dict, slo_deadline):
        """Answer a spent deadline typed: a real 504 before any bytes
        went out, an error chunk (no [DONE]) mid-stream — never a
        silent stall, never a retry."""
        self._count_outcome("deadline")
        miss_ms = (time.monotonic() - slo_deadline) * 1000.0 \
            if slo_deadline is not None else 0.0
        body = _deadline_body(f" (missed by {miss_ms:.0f}ms at the "
                              "router)")
        if state["headers_sent"]:
            try:
                handler._chunk(b"data: " + json.dumps(body).encode()
                               + b"\n\n")
                handler._chunk(b"")
            except OSError:
                handler.close_connection = True
        else:
            handler._json(504, body)

    def _headers(self, span, deadline=None) -> dict:
        h = {"Content-Type": "application/json"}
        if span:
            h[_tracing.TRACEPARENT_HEADER] = _tracing.format_traceparent(
                span.trace_id, span.span_id)
        if deadline is not None:
            # the deadline contract: each hop carries the REMAINING
            # budget in ms, so the worker's admission deadline equals
            # the router's minus elapsed time (pinned in tier-1)
            h[DEADLINE_HEADER] = (
                f"{max(0.0, (deadline - time.monotonic()) * 1000.0):.1f}")
        return h

    def _upstream_timeout(self, deadline) -> float:
        """The per-hop socket timeout derives from the remaining budget
        (plus a small grace so the worker's own typed shed wins the
        race) instead of the fixed constant — a spent deadline must
        surface in bounded time, typed."""
        if deadline is None:
            return self.upstream_timeout
        return min(self.upstream_timeout,
                   max(0.05, deadline - time.monotonic()) + 2.0)

    def _post_json(self, worker: WorkerInfo, path: str, body: dict,
                   span, deadline=None) -> Tuple[int, dict]:
        """One upstream POST, full-body; transport failures raise
        _UpstreamError naming the worker as observed-dead — unless the
        request's deadline has passed, which is the request's fault,
        not the worker's (_DeadlineExpired)."""
        self._chaos_upstream(worker, path)
        conn = http.client.HTTPConnection(
            worker.host, worker.port,
            timeout=self._upstream_timeout(deadline))
        try:
            conn.request("POST", path, json.dumps(body),
                         self._headers(span, deadline))
            resp = conn.getresponse()
            status = resp.status
            raw = resp.read()
            retry_after = ((resp.getheader("Retry-After")
                            or self._retry_after_for(worker))
                           if status == 429 else None)
        except (OSError, http.client.HTTPException) as e:
            if deadline is not None and time.monotonic() >= deadline:
                raise _DeadlineExpired() from e
            raise _UpstreamError(
                f"worker {worker.replica_id} transport failure on "
                f"{path}: {type(e).__name__}: {e}", dead=worker)
        finally:
            conn.close()
        try:
            parsed = json.loads(raw)
        except ValueError:
            parsed = {"error": raw.decode(errors="replace")}
        if (status == 504 and isinstance(parsed, dict)
                and parsed.get("code") == "deadline_exceeded"):
            # a worker's deadline shed is TERMINAL: the budget is
            # global, another replica cannot un-expire it — forward
            # verbatim through the no-retry path
            raise _ClientError(status, parsed)
        if status == 429:
            raise _WorkerBusy(worker, parsed, retry_after)
        return status, parsed

    def _prefill_hop(self, pre: WorkerInfo, serve: WorkerInfo, req: dict,
                     span, deadline=None) -> str:
        """Run the prompt through a prefill worker, shipping its KV to
        ``serve``'s handoff channel; returns the handoff id the decode
        request claims."""
        hid = uuid.uuid4().hex
        body = {"channel": serve.kv_channel, "handoff_id": hid,
                "max_tokens": req.get("max_tokens", 16)}
        for k in ("prompt", "prompt_token_ids"):
            if k in req:
                body[k] = req[k]
        try:
            status, resp = self._post_json(pre, "/v1/prefill", body, span,
                                           deadline=deadline)
        except _UpstreamError as e:
            # the SERVE worker is fine — only exclude/blame the prefill
            # worker so the retry can reuse the decode side
            raise _UpstreamError(e.reason, dead=e.dead,
                                 exclude=(pre.replica_id,)) from e
        if 400 <= status < 500:
            raise _ClientError(status, resp)
        if status != 200:
            raise _UpstreamError(
                f"prefill worker {pre.replica_id} answered {status}: "
                f"{resp.get('error', resp)}", exclude=(pre.replica_id,))
        return hid

    def _chaos_upstream(self, worker: WorkerInfo, path: str):
        """router.upstream injection point: a planned http_500 fails the
        placement attempt exactly like a worker 5xx would (retryable,
        worker NOT marked dead), a delay stalls the hop."""
        fault = _chaos.on("router.upstream",
                          replica_id=worker.replica_id, path=path)
        if fault is not None:
            if fault.action == "http_500":
                raise _UpstreamError(
                    f"chaos: injected 5xx placing on worker "
                    f"{worker.replica_id}")
            if fault.action == "delay":
                time.sleep(fault.delay_s)

    def _proxy_stream(self, handler, worker: WorkerInfo, body: dict,
                      state: dict, span, base: int = 0, deadline=None):
        """Relay one SSE stream, skipping the token chunks the client
        already has: the upstream's chunks are numbered from ``base``
        (0 for a full replay, the bundle's generated count for a
        migration continuation that emits only new tokens), and chunks
        numbered <= ``state['delivered']`` are dropped."""
        self._chaos_upstream(worker, "/v1/completions")
        conn = http.client.HTTPConnection(
            worker.host, worker.port,
            timeout=self._upstream_timeout(deadline))
        try:
            try:
                conn.request("POST", "/v1/completions", json.dumps(body),
                             self._headers(span, deadline))
                resp = conn.getresponse()
            except (OSError, http.client.HTTPException) as e:
                if deadline is not None and time.monotonic() >= deadline:
                    raise _DeadlineExpired() from e
                raise _UpstreamError(
                    f"worker {worker.replica_id} transport failure: "
                    f"{type(e).__name__}: {e}", dead=worker)
            if resp.status != 200:
                try:
                    raw = resp.read()
                except (OSError, http.client.HTTPException):
                    raw = b""
                try:
                    parsed = json.loads(raw)
                except ValueError:
                    parsed = {"error": raw.decode(errors="replace")}
                if (resp.status == 504 and isinstance(parsed, dict)
                        and parsed.get("code") == "deadline_exceeded"):
                    # terminal typed shed — forward, never retry
                    raise _ClientError(resp.status, parsed)
                if resp.status == 429:
                    raise _WorkerBusy(worker, parsed,
                                      resp.getheader("Retry-After")
                                      or self._retry_after_for(worker))
                if 400 <= resp.status < 500:
                    raise _ClientError(resp.status, parsed)
                raise _UpstreamError(
                    f"worker {worker.replica_id} answered {resp.status}: "
                    f"{parsed.get('error', parsed)}")
            if not state["headers_sent"]:
                handler._begin_sse()
                state["headers_sent"] = True
            seen = int(base)
            while True:
                try:
                    line = resp.readline()
                except (OSError, http.client.HTTPException) as e:
                    if (deadline is not None
                            and time.monotonic() >= deadline):
                        raise _DeadlineExpired() from e
                    raise _UpstreamError(
                        f"worker {worker.replica_id} stream broke: "
                        f"{type(e).__name__}: {e}", dead=worker)
                if not line:
                    # EOF without [DONE]: the worker died mid-stream
                    raise _UpstreamError(
                        f"worker {worker.replica_id} stream ended "
                        "without [DONE]", dead=worker)
                if not line.startswith(b"data: "):
                    continue
                payload = line[len(b"data: "):].strip()
                if payload == b"[DONE]":
                    try:
                        handler._chunk(b"data: [DONE]\n\n")
                        handler._chunk(b"")
                    except OSError:
                        raise _ClientGone()
                    return
                if payload.startswith(b'{"migrated"'):
                    # planned exit: the slot moved to another worker —
                    # the relay continues there (every token generated
                    # before the export was relayed ahead of the marker)
                    raise _Migrated(json.loads(payload)["migrated"])
                if payload.startswith(b'{"error"'):
                    try:
                        d = json.loads(payload)
                    except ValueError:
                        d = {}
                    if (isinstance(d, dict)
                            and d.get("code") == "deadline_exceeded"):
                        # a deadline shed after tokens flowed (preempted
                        # then requeued past its budget): terminal —
                        # forward typed, never replay on another worker
                        raise _ClientError(504, d)
                    # engine-level mid-stream failure: another worker
                    # can finish this request
                    raise _UpstreamError(
                        f"worker {worker.replica_id} streamed an error: "
                        f"{payload.decode(errors='replace')}")
                seen += 1
                if seen <= state["delivered"]:
                    continue  # already relayed before the failover
                try:
                    handler._chunk(b"data: " + payload + b"\n\n")
                except OSError:
                    # the DOWNSTREAM client went away: closing the
                    # upstream socket makes the worker see its own SSE
                    # disconnect and cancel the slot
                    raise _ClientGone()
                state["delivered"] += 1
        finally:
            conn.close()
