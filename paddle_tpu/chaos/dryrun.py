"""Chaos dryrun: the seeded end-to-end robustness gate.

Launches the REAL multi-process cluster (router + worker subprocesses
over TCPStore leases and shm handoff rings), installs a fixed-seed
:class:`~.plan.FaultPlan` in every process, drives concurrent streamed
completions through the router while the plan injects worker death,
handoff loss/corruption, a heartbeat stall and router↔worker 5xx — and
checks the claims the serving tier makes about itself:

- every stream completes **token-identical** to a solo run and ends with
  a clean ``[DONE]``;
- **zero client-visible 5xx** for absorbable faults (everything in the
  default plan is absorbable: retries, failover and handoff re-export
  must hide them);
- corrupt bundles are **detected** (checksum → ``HandoffCorrupt``) and
  retried, never admitted; dropped bundles time out and re-place;
- a stalled heartbeat reaps the worker and a fresh lease **rejoins** it;
- the cluster watchtower **judges** the kills: the
  ``worker_restart_rate`` objective (second-scale windows via
  ``alert_time_scale``) must FIRE while the supervisor restarts workers
  and RESOLVE after the heal — ``report["alerts"]`` carries the
  transition evidence off the router's ``/alerts``.

``scripts/chaos_dryrun.py`` is the CLI over :func:`run_dryrun`; the
tier-1 chaos gate (tests/test_chaos.py) drives it directly and asserts
on the returned report.
"""
from __future__ import annotations

import http.client
import json
import os
import threading
import time
import urllib.request
from typing import List, Optional

from ..distributed.log_utils import get_logger
from . import inject as _inject
from .plan import Fault, FaultPlan

__all__ = ["default_plan", "run_dryrun"]


#: the request id the default plan's poison fault triggers on — the
#: dryrun submits one request carrying it and asserts the quarantine
#: contains the blast radius at <= 2 workers + exactly one typed 422
POISON_RID = "poison-rid"


def default_plan(seed: int = 0) -> FaultPlan:
    """The gate plan: one seeded plan combining every failure domain the
    cluster claims to absorb. Counts are arrivals per point per process
    (worker:0 is the prefill worker in the default topology; worker:2 a
    decode worker); kills are incarnation-scoped so the supervisor's
    respawn is not re-killed by the fault that killed its predecessor."""
    return FaultPlan(seed=seed, faults=[
        # the 2nd KV bundle worker:0 ships is silently lost — the decode
        # side must 504 and the router re-place (fresh prefill, fresh
        # bundle)
        Fault("kv_handoff.send", "drop", nth=2, scope="worker:0"),
        # the 4th is corrupted by one flipped byte AFTER sealing — the
        # admitting engine must refuse it with HandoffCorrupt, and the
        # router absorb the 5xx
        Fault("kv_handoff.send", "corrupt", nth=4, scope="worker:0"),
        # one placement hop fails as if the worker answered 500
        Fault("router.upstream", "http_500", nth=6, scope="router"),
        # worker:0's lease heartbeat stalls past its ttl (process alive,
        # membership lapsed): the pool must reap it, traffic must flow
        # without it, and the fresh post-stall stamp must rejoin it
        Fault("worker.request", "stall_heartbeat", nth=3,
              scope="worker:0", duration_s=4.0),
        # a decode worker dies at its 20th engine step — SIGKILL-grade,
        # mid-stream; relays must fail over and continue token-identical,
        # and the SUPERVISOR must restart it (incarnation 0 only)
        Fault("worker.step", "kill", nth=20, scope="worker:2",
              incarnation=0),
        # the DOUBLE-KILL: the restarted worker:2 dies again at its 5th
        # step (incarnation 1 only) — the supervisor restarts it a
        # second time and the pool still heals to full strength
        Fault("worker.step", "kill", nth=5, scope="worker:2",
              incarnation=1),
        # the POISON: whichever worker (any incarnation) lets POISON_RID
        # into a decode dispatch dies there — quarantine must contain it
        # at <= 2 worker deaths and answer the client a typed 422
        Fault("engine.dispatch", "crash_on_rid", detail=POISON_RID,
              scope=None, incarnation=None),
    ])


def _stream_completion(host, port, body, timeout=300):
    """POST a streaming completion; returns (status, clean, tokens)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        if resp.status != 200:
            resp.read()
            return resp.status, False, []
        toks, clean = [], False
        while True:
            line = resp.readline()
            if not line:
                break
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):].strip()
            if payload == b"[DONE]":
                clean = True
                break
            d = json.loads(payload)
            if "error" in d or "migrated" in d:
                break
            toks.append(d["choices"][0]["token_ids"][0])
        return 200, clean, toks
    finally:
        conn.close()


def _get_json(url, timeout=15):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def run_dryrun(plan: Optional[FaultPlan] = None, *, streams: int = 4,
               max_tokens: int = 32, prompt_len: int = 9,
               layers: int = 2, max_batch: int = 8, max_len: int = 128,
               page_size: int = 8, ttl: float = 1.5,
               handoff_wait_s: float = 3.0, max_retries: int = 5,
               stream_timeout: float = 420.0,
               load_qps: float = 0.0,
               load_duration_s: float = 4.0,
               heal_timeout: float = 150.0,
               poison: bool = True) -> dict:
    """Run the fixed-seed chaos plan against a real 1-prefill + 2-decode
    SUPERVISED cluster and return the report dict (see module docstring
    for the claims it checks; ``report["ok"]`` is the verdict).

    With ``load_qps > 0`` the plan additionally fires UNDER GENERATED
    LOAD: a seeded open-loop Poisson stream (paddle_tpu.loadgen, with a
    priority/SLO class mix) drives the router concurrently with the
    hand-built gate streams, and ``report["load"]`` carries the harness
    summary — every load outcome must be typed (200 / 429 / 504 with
    ``code=deadline_exceeded``), zero 5xx, zero silent stalls, and the
    shed accounting must balance (requests_shed == deadline_misses when
    no bounded queue displaces work).

    Since the self-healing PR the dryrun is the full
    kill→restart→heal→quarantine story: after the classic fault window
    it (a) waits for the supervisor to restart the killed worker and the
    pool to return to full strength, (b) drives sequential streams until
    the plan's DOUBLE-KILL fires in the restarted incarnation and heals
    again, (c) submits the plan's POISON request (``POISON_RID``) and
    asserts it kills at most ``QUARANTINE_THRESHOLD`` workers before the
    router refuses it with a typed 422 ``code=request_quarantined``
    (``poison=False`` skips this leg), and (d) replays a post-heal
    loadgen burst asserting the healed tier still serves at the offered
    rate with typed-only outcomes — capacity recovered, not merely
    survived."""
    import numpy as np

    import paddle_tpu as paddle
    from ..models.llama import LlamaConfig, LlamaForCausalLM
    from ..observability import flightrecorder as frec
    from ..serving_cluster import launch_cluster

    plan = plan or default_plan()
    cfg = {
        "cluster": {"host": "127.0.0.1", "port": 0, "ttl": ttl,
                    "platform": "cpu",
                    "handoff_wait_s": handoff_wait_s,
                    "max_retries": max_retries,
                    "model_name": "tiny-llama-chaos",
                    # cluster watchtower at gate speed: sample fast and
                    # scale the alert windows from minutes to seconds so
                    # the worker-restart objective's fire->resolve cycle
                    # completes INSIDE the dryrun (window 12s, resolve
                    # hold 1s at scale 0.1)
                    "ts_interval_s": 0.25,
                    "alert_time_scale": 0.1},
        # fast healing for the gate: short backoff (the compile cache is
        # warm by restart time), generous breaker budget (the plan kills
        # worker:2 twice ON PURPOSE — the breaker must contain loops,
        # not the planned chaos), quick health-reset
        "supervisor": {"backoff_base_s": 0.25, "backoff_max_s": 2.0,
                       "breaker_threshold": 6, "breaker_window_s": 120.0,
                       "healthy_reset_s": 5.0},
        "model": {"kind": "tiny_llama", "num_hidden_layers": layers,
                  "seed": 0},
        "engine": {"max_batch": max_batch, "max_len": max_len,
                   "page_size": page_size},
        "workers": [{"role": "prefill", "count": 1},
                    {"role": "decode", "count": 2}],
    }

    # the reference run: same seed + spec as the workers build
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
    rng = np.random.RandomState(plan.seed + 3)
    prompts = [rng.randint(1, 512, (prompt_len,)).tolist()
               for _ in range(streams)]
    solos = [model.generate(paddle.to_tensor(np.asarray(p)[None]),
                            max_new_tokens=max_tokens).numpy()[0].tolist()
             for p in prompts]

    rec = frec.get_recorder()
    rec.enable()
    since = rec.stats()["recorded"]
    os.environ[_inject.ENV_PLAN] = plan.dumps()
    injector = _inject.install(plan, scope="router")
    cluster = None
    try:
        cluster = launch_cluster(cfg)
        host, port = cluster.address
        # one sequential warm request compiles the prefill/export bucket
        # before the concurrent phase, so the handoff_wait_s clock runs
        # against transport time, not first-compile time. The plan's
        # counters see it (it is arrival #1 at each point) — no default
        # fault triggers at nth=1.
        conn = http.client.HTTPConnection(host, port, timeout=300)
        try:
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt_token_ids": prompts[0],
                                     "max_tokens": 1}),
                         {"Content-Type": "application/json"})
            warm = conn.getresponse()
            warm.read()
        finally:
            conn.close()
        if warm.status != 200:
            raise RuntimeError(
                f"chaos dryrun warmup failed: {warm.status}")

        # generated load UNDER the fault plan (not idle hand-built
        # streams): an open-loop seeded mix with SLO classes runs
        # concurrently with the gate streams below, so the kill / drop
        # / corrupt / stall / 5xx faults fire while real traffic flows
        load_outcomes: List = []
        load_thread = None
        load_before = None
        if load_qps > 0:
            from ..loadgen import (WorkloadSpec, run_schedule,
                                   stack_stats, synthesize)

            load_spec = WorkloadSpec(
                qps=load_qps, duration_s=load_duration_s,
                process="poisson", prompt_tokens=(4, prompt_len),
                max_tokens=(4, 12),
                classes=((0, None, 0.4), (1, 8000.0, 0.4),
                         (2, 2500.0, 0.2)),
                vocab_size=512, seed=plan.seed + 11)
            load_schedule = synthesize(load_spec)
            load_before = stack_stats(f"http://{host}:{port}")

            def _drive_load():
                try:
                    load_outcomes.extend(run_schedule(
                        f"http://{host}:{port}", load_schedule,
                        stream_timeout=stream_timeout))
                except Exception as e:
                    # a dead load generator must show up in the report
                    # as missing outcomes, not as a hung thread the
                    # join below silently abandons
                    get_logger().warning(
                        "chaos dryrun: background load failed (%s: %s)",
                        type(e).__name__, e)

            load_thread = threading.Thread(target=_drive_load,
                                           name="chaos-loadgen",
                                           daemon=True)
            load_thread.start()
        results: List[Optional[tuple]] = [None] * streams

        def client(i):
            try:
                results[i] = _stream_completion(
                    host, port,
                    {"prompt_token_ids": prompts[i],
                     "max_tokens": max_tokens, "stream": True},
                    timeout=stream_timeout)
            except Exception as e:
                # a None result already means "stream failed" to the
                # gate checks below — record why instead of dying with
                # the verdict unexplained
                get_logger().warning(
                    "chaos dryrun: gate stream %d failed (%s: %s)",
                    i, type(e).__name__, e)

        threads = [threading.Thread(target=client, args=(i,),
                                    name=f"chaos-client-{i}")
                   for i in range(streams)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=stream_timeout)

        # kill-leg guarantee: placement races can starve the kill target
        # of decode work in a light run (its only stream was the dropped
        # bundle, say) — feed sequential streams until its per-process
        # step counter crosses the plan's nth and the kill fires. These
        # must be absorbed exactly like the planned ones: the failover
        # replays them on the survivor, token-identical.
        mopup_ok = True
        for _ in range(10):
            if cluster.processes[2].poll() is not None:
                break
            st, cl, tk = _stream_completion(
                host, port, {"prompt_token_ids": prompts[0],
                             "max_tokens": 24, "stream": True},
                timeout=stream_timeout)
            mopup_ok = (mopup_ok and st == 200 and cl
                        and tk == solos[0][:24])

        # the stalled worker must rejoin on its fresh post-pause lease
        rejoined = False
        deadline = time.monotonic() + 20.0
        while time.monotonic() < deadline and not rejoined:
            try:
                health = _get_json(f"http://{host}:{port}/health")
            except OSError:
                break
            w0 = health["workers"].get("0")
            rejoined = bool(w0 and w0["alive"])
            if not rejoined:
                time.sleep(0.5)

        # wind down the generated-load phase and read the stack's shed
        # accounting off the survivors' /health counters
        load_report = None
        if load_thread is not None:
            from ..loadgen import stack_stats, summarize

            load_thread.join(timeout=stream_timeout)
            load_after = stack_stats(f"http://{host}:{port}")
            load_report = summarize(load_outcomes, load_duration_s,
                                    offered_qps=load_qps,
                                    stack_before=load_before,
                                    stack_after=load_after)

        # ---- self-healing: kill -> restart -> heal -> quarantine -----
        def _alive_count() -> int:
            try:
                h = _get_json(f"http://{host}:{port}/health")
            except OSError:
                return 0
            return sum(1 for w in h["workers"].values() if w["alive"])

        def _wait_healed(n: int, timeout: float) -> bool:
            deadline = time.monotonic() + timeout
            while time.monotonic() < deadline:
                if _alive_count() >= n:
                    return True
                time.sleep(0.4)
            return False

        sup = cluster.supervisor
        n_workers = 3

        # heal #1: the supervisor restarts the killed decode worker
        # (same replica id, fresh lease + port) and the pool returns to
        # full strength — capacity recovered without an operator
        healed_after_kill = _wait_healed(n_workers, heal_timeout)

        # the DOUBLE-KILL: drive sequential streams until the plan's
        # incarnation-1 kill fires in the restarted worker:2 and the
        # supervisor restarts it a SECOND time; every driven stream must
        # be absorbed token-identical exactly like the planned kill
        double_kill_streams_ok = True
        restarts_w2 = 0
        dk_deadline = time.monotonic() + heal_timeout
        while time.monotonic() < dk_deadline:
            w2 = (sup.state()["workers"].get("2") or {}) if sup else {}
            restarts_w2 = len(w2.get("restarts") or ())
            if restarts_w2 >= 2:
                break
            st, cl, tk = _stream_completion(
                host, port, {"prompt_token_ids": prompts[1],
                             "max_tokens": 16, "stream": True},
                timeout=stream_timeout)
            double_kill_streams_ok = (
                double_kill_streams_ok and st == 200 and cl
                and tk == solos[1][:16])
        healed_after_double_kill = (restarts_w2 >= 2
                                    and _wait_healed(n_workers,
                                                     heal_timeout))

        # the POISON: one request that deterministically kills whichever
        # engine dispatches it. The quarantine must contain the blast
        # radius at <= 2 workers and answer the CLIENT a typed 422 —
        # exactly one, never a retry loop across the whole tier
        poison_report = None
        healed_after_poison = True
        if poison and sup is not None:
            conn = http.client.HTTPConnection(host, port,
                                              timeout=stream_timeout)
            try:
                conn.request(
                    "POST", "/v1/completions",
                    json.dumps({"prompt_token_ids": prompts[0],
                                "max_tokens": 8,
                                "request_id": POISON_RID}),
                    {"Content-Type": "application/json"})
                p_resp = conn.getresponse()
                try:
                    p_body = json.loads(p_resp.read() or b"{}")
                except ValueError:
                    p_body = {}
            finally:
                conn.close()
            ledger = sup.ledger.snapshot()
            quarantine_rec = ledger["quarantined"].get(POISON_RID) or {}
            poison_report = {
                "status": p_resp.status,
                "code": p_body.get("code"),
                "deaths": len(ledger["implicated"].get(POISON_RID, ())),
                "replicas": quarantine_rec.get("replicas"),
                "quarantined": sorted(ledger["quarantined"]),
            }
            healed_after_poison = _wait_healed(n_workers, heal_timeout)

        # post-heal capacity: replay a seeded open-loop burst at the
        # same offered rate against the HEALED tier — goodput at the
        # pre-fault knee, typed-only outcomes, zero 5xx (capacity
        # recovered, not merely survived)
        post_heal = None
        if load_qps > 0:
            from ..loadgen import (WorkloadSpec, run_schedule, summarize,
                                   synthesize)

            heal_spec = WorkloadSpec(
                qps=load_qps, duration_s=2.5, process="poisson",
                prompt_tokens=(4, prompt_len), max_tokens=(4, 10),
                vocab_size=512, seed=plan.seed + 23)
            heal_outs = run_schedule(
                f"http://{host}:{port}", synthesize(heal_spec),
                stream_timeout=stream_timeout)
            post_heal = summarize(heal_outs, 2.5, offered_qps=load_qps)
        # ---- watchtower referee: the worker-restart objective must
        # have FIRED during the kill legs (the supervisor's restarts
        # land in worker_restarts_total, the federated store samples
        # it, the cluster AlertManager judges it) and RESOLVED once the
        # scaled window drained after the heal — fire->resolve proven
        # end to end, not asserted from unit math
        from ..loadgen import alerts_state

        alerts_report = None
        restart_fired = restart_resolved = False
        alert_deadline = time.monotonic() + 30.0
        while time.monotonic() < alert_deadline:
            a = alerts_state(f"http://{host}:{port}")
            trans = a["transitions"]
            restart_fired = any(
                t["alert"] == "worker_restart_rate"
                and t["to"] == "firing" for t in trans)
            restart_resolved = restart_fired and any(
                t["alert"] == "worker_restart_rate"
                and t["to"] == "resolved" for t in trans)
            alerts_report = {
                "enabled": a["enabled"],
                "firing_final": a["firing"],
                "fired": sorted({t["alert"] for t in trans
                                 if t["to"] == "firing"}),
                "restart_fired": restart_fired,
                "restart_resolved": restart_resolved,
                "transitions": trans,
            }
            if restart_resolved or not a["enabled"]:
                break
            time.sleep(0.5)

        supervisor_state = sup.state() if sup is not None else None

        # surviving workers' chaos.inject events (the killed worker's
        # ring died with it — its evidence is the exit code below)
        fired = {"router": injector.fired()}
        try:
            health = _get_json(f"http://{host}:{port}/health")
            for rid_s, w in health["workers"].items():
                if not w["alive"]:
                    continue
                evs = _get_json(w["url"]
                                + "/debug/events?kind=chaos")["events"]
                fired[f"worker:{rid_s}"] = [
                    {k: e.get(k) for k in ("point", "action", "nth")}
                    for e in evs]
        except OSError:
            pass

        import subprocess

        killed = cluster.processes[2].poll()
        if killed is None:
            try:
                killed = cluster.processes[2].wait(timeout=10)
            except subprocess.TimeoutExpired:
                killed = None  # kill fault never fired: report says so
    finally:
        os.environ.pop(_inject.ENV_PLAN, None)
        _inject.uninstall()
        if cluster is not None:
            cluster.close()

    evs = rec.events(since=since)
    retries = [e for e in evs if e["kind"] == "router.retry"]
    lost = [e for e in evs if e["kind"] == "router.worker_lost"]
    stream_reports = []
    client_5xx = 0
    all_ok = True
    for i, r in enumerate(results):
        status, clean, toks = r if r is not None else (None, False, [])
        identical = toks == solos[i]
        if status is not None and status >= 500:
            client_5xx += 1
        ok = status == 200 and clean and identical
        all_ok = all_ok and ok
        stream_reports.append({"stream": i, "status": status,
                               "clean": clean,
                               "token_identical": identical,
                               "tokens": len(toks)})
    corrupt_detected = any("checksum mismatch" in str(e.get("reason", ""))
                           for e in retries)
    drop_detected = any("not received" in str(e.get("reason", ""))
                        for e in retries)
    drop_fired = any(f.get("action") == "drop"
                     for fs in fired.values() for f in fs)
    # a drop is ABSORBED either by its own symptom (the decode side's
    # 504 "not received" timed out and the router re-placed) or masked
    # by a concurrent failover (the waiting decode worker died inside
    # the wait window and the same re-place path took over) — both are
    # clean, and token identity above is the invariant that matters
    drop_absorbed = drop_detected or (drop_fired and all_ok)
    poison_ok = True
    if poison_report is not None:
        poison_ok = (poison_report["status"] == 422
                     and poison_report["code"] == "request_quarantined"
                     and poison_report["deaths"] <= 2
                     and poison_report["quarantined"] == [POISON_RID])
    post_heal_ok = (post_heal is None
                    or (post_heal["http_5xx"] == 0
                        and post_heal["untyped"] == 0
                        and post_heal["timed_out"] == 0
                        and post_heal["completed"] > 0))
    report = {
        "plan": plan.as_dict(),
        "streams": stream_reports,
        "client_5xx": client_5xx,
        "retries": [{k: e.get(k) for k in
                     ("replica_id", "attempt", "delivered", "reason")}
                    for e in retries],
        "worker_lost": [{"replica_id": e.get("replica_id"),
                         "reason": e.get("reason")} for e in lost],
        "faults_fired": fired,
        "corrupt_detected_and_retried": corrupt_detected,
        "drop_detected_and_retried": drop_detected,
        "drop_fired": drop_fired,
        "drop_absorbed": drop_absorbed,
        "stalled_worker_rejoined": rejoined,
        "killed_worker_exit": killed,
        "kill_mopup_ok": mopup_ok,
        "load": load_report,
        # the self-healing story
        "healed_after_kill": healed_after_kill,
        "double_kill_restarts": restarts_w2,
        "double_kill_streams_ok": double_kill_streams_ok,
        "healed_after_double_kill": healed_after_double_kill,
        "poison": poison_report,
        "healed_after_poison": healed_after_poison,
        "post_heal_load": post_heal,
        "alerts": alerts_report,
        "supervisor": supervisor_state,
        "ok": (all_ok and client_5xx == 0 and corrupt_detected
               and drop_absorbed and rejoined and bool(lost)
               and killed == 137 and mopup_ok
               and healed_after_kill and healed_after_double_kill
               and double_kill_streams_ok and poison_ok
               and healed_after_poison and post_heal_ok
               and restart_fired and restart_resolved),
    }
    return report
