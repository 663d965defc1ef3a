"""Disaggregated serving tier: pool membership over leases, KV handoff
between engines, and the multi-engine dryrun gate — router + real worker
processes serving concurrent streamed completions token-identically to a
single engine, surviving a worker kill mid-stream (bounded-retry requeue)
with the placement/retry/handoff decisions visible as flight-recorder
events and ONE trace_id spanning router and worker spans."""
import json
import http.client
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchEngine
from paddle_tpu.observability import flightrecorder as frec


def _cluster_cfg(workers, max_batch=8, max_len=128, page_size=8,
                 ttl=2.0, layers=2):
    return {
        "cluster": {"host": "127.0.0.1", "port": 0, "ttl": ttl,
                    "platform": "cpu",
                    "model_name": "tiny-llama-cluster",
                    # watchtower at test speed: fast sampling + short
                    # alert windows (restart window 6s, lost window
                    # 1.5s) so residue from EARLIER test files' clusters
                    # ages out of every window before the gate reads
                    # /alerts — the clean-run control stays deterministic
                    "ts_interval_s": 0.25,
                    "alert_time_scale": 0.05},
        "model": {"kind": "tiny_llama", "num_hidden_layers": layers,
                  "seed": 0},
        "engine": {"max_batch": max_batch, "max_len": max_len,
                   "page_size": page_size},
        "workers": workers,
    }


def _ref_model(layers=2):
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))


def _get_json(url):
    with urllib.request.urlopen(url, timeout=30) as r:
        return json.loads(r.read())


def _stream_completion(host, port, body, on_first_token=None,
                       timeout=300):
    """POST a streaming completion; returns (clean, tokens,
    traceparent)."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    assert resp.status == 200, resp.read()
    tp = resp.getheader("traceparent")
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
        if "error" in d:
            break
        toks.append(d["choices"][0]["token_ids"][0])
        if on_first_token is not None and len(toks) == 1:
            on_first_token()
    conn.close()
    return clean, toks, tp


# ---- worker 429 = placement feedback ----------------------------------------

class _FakePool:
    """The WorkerPool protocol over hand-built WorkerInfo rows — enough
    surface for RouterServer placement without a TCPStore."""

    def __init__(self, workers):
        from paddle_tpu.serving_cluster.pool import WorkerInfo

        self._ws = {}
        for rid, (host, port) in workers.items():
            self._ws[rid] = WorkerInfo(rid, {"host": host, "port": port,
                                             "role": "unified"})
        self.busy_marks = []

    def select(self, roles=None, exclude=()):
        now = time.monotonic()
        live = [w for w in self._ws.values()
                if w.alive and w.replica_id not in exclude
                and w.busy_until <= now]
        if not live:
            return None
        w = min(live, key=lambda w: (w.score(), w.replica_id))
        w.pending += 1
        return w

    def mark_busy(self, replica_id, backoff_s=0.5):
        self.busy_marks.append(replica_id)
        self._ws[replica_id].busy_until = time.monotonic() + backoff_s

    def mark_dead(self, replica_id, reason="connection"):
        self._ws[replica_id].alive = False

    def get(self, replica_id):
        return self._ws.get(replica_id)

    def claim(self, w):
        w.pending += 1

    def set_draining(self, replica_id, draining=True):
        self._ws[replica_id].draining = draining

    def release(self, w):
        if w.pending > 0:
            w.pending -= 1

    def has_role(self, role):
        return any(w.alive and w.role == role for w in self._ws.values())

    def workers(self):
        return [w.snapshot() for w in self._ws.values()]

    def worker_stats(self):
        return [(w.replica_id, w.alive, dict(w.stats))
                for w in self._ws.values()]

    def refresh_gauges(self):
        pass


def test_router_treats_worker_429_as_placement_feedback():
    """A worker answering 429 (bounded admission queue) is SKIPPED — short
    busy backoff, never marked dead, no failover-retry budget burned —
    and the request lands on another replica. When every worker pushes
    back, the client gets the 429 + Retry-After forwarded."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from paddle_tpu.serving_cluster.router import RouterServer
    from paddle_tpu.serving_http import CompletionServer

    class Busy(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            body = json.dumps({"error": "engine admission queue is "
                                        "full"}).encode()
            self.send_response(429)
            self.send_header("Retry-After", "7")
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    busy_httpd = ThreadingHTTPServer(("127.0.0.1", 0), Busy)
    threading.Thread(target=busy_httpd.serve_forever, daemon=True).start()
    model = _ref_model()
    eng = ContinuousBatchEngine(model, max_batch=4, max_len=64,
                                page_size=8)
    worker = CompletionServer(eng).start()
    try:
        # replica 0 = always-busy stub (lower replica id wins the
        # fake pool's tie-break, so it is always tried FIRST)
        pool = _FakePool({0: busy_httpd.server_address,
                          1: worker.address})
        router = RouterServer(pool, max_retries=1).start()
        try:
            host, port = router.address
            prompt = [1, 2, 3, 4, 5]
            conn = http.client.HTTPConnection(host, port, timeout=120)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt_token_ids": prompt,
                                     "max_tokens": 4}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            data = json.loads(resp.read())
            conn.close()
            assert resp.status == 200, data
            solo = model.generate(paddle.to_tensor(
                np.asarray(prompt)[None]), max_new_tokens=4).numpy()[0]
            assert data["choices"][0]["token_ids"] == list(solo)
            # feedback, not failure: busy-marked, still alive
            assert pool.busy_marks == [0]
            assert all(w["alive"] for w in pool.workers())
            assert router._busy == 1 and router._placed == 1
            assert router._retried == 0 and router._failed == 0

            # every worker busy -> the 429 + Retry-After forwards
            pool.mark_busy(1, backoff_s=30.0)
            pool.busy_marks.clear()
            time.sleep(0.6)   # stub's 0.5s backoff expires; it answers
            # 429 again, and with no other placeable worker the router
            # forwards the backpressure instead of 502ing
            conn = http.client.HTTPConnection(host, port, timeout=120)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt_token_ids": prompt,
                                     "max_tokens": 4}),
                         {"Content-Type": "application/json"})
            resp = conn.getresponse()
            body = json.loads(resp.read())
            ra = resp.getheader("Retry-After")
            conn.close()
            assert resp.status == 429 and ra == "7", (resp.status, body)
            assert "full" in body["error"]
        finally:
            router.close()
    finally:
        worker.close()
        busy_httpd.shutdown()
        busy_httpd.server_close()


# ---- in-process: engine handoff + kv channel --------------------------------

def test_export_admit_handoff_matches_solo():
    """export_prefill on one engine -> admit_prefilled on a PEER engine
    (same weights): generated tokens identical to solo generate, and the
    prefill engine's pool is untouched."""
    model = _ref_model()
    prompt = np.random.RandomState(0).randint(1, 512, (9,)).tolist()
    solo = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                          max_new_tokens=6).numpy()[0].tolist()
    pre = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    dec = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    bundle = pre.export_prefill(prompt, max_new_tokens=6)
    assert pre.num_active == 0 and not pre._queue
    assert bundle["prompt_tokens"] == len(prompt)
    rid = dec.admit_prefilled(bundle, max_new_tokens=6)
    out = dec.run_until_done()
    assert out[rid].tolist() == solo
    assert dec.finish_reason(rid) == "length"


def test_export_admit_validation():
    model = _ref_model()
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    with pytest.raises(ValueError, match="max_len"):
        eng.export_prefill([1] * 60, max_new_tokens=10)
    bundle = eng.export_prefill([1, 2, 3], max_new_tokens=4)
    # page-size mismatch between the tiers is a config error, not a crash
    other = ContinuousBatchEngine(model, max_batch=2, max_len=60,
                                  page_size=12)
    with pytest.raises(ValueError, match="page_size"):
        other.admit_prefilled(bundle, max_new_tokens=4)
    # layer-count mismatch (different model depth)
    deeper = ContinuousBatchEngine(
        LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=3)),
        max_batch=2, max_len=64, page_size=8)
    with pytest.raises(ValueError, match="layers"):
        deeper.admit_prefilled(bundle, max_new_tokens=4)


def test_kv_handoff_channel_roundtrip():
    """The shm transport end to end in one process: receiver owns the
    ring, sender opens it by name, bundles park by handoff_id and decode
    output stays token-identical; send/recv flight-recorder events land
    in the ring."""
    from paddle_tpu.serving_cluster import (KvHandoffReceiver,
                                            KvHandoffSender)

    model = _ref_model()
    prompt = np.random.RandomState(5).randint(1, 512, (7,)).tolist()
    solo = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                          max_new_tokens=5).numpy()[0].tolist()
    pre = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    dec = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)

    rec = frec.get_recorder()
    was_enabled = rec.enabled
    rec.enable()
    recv = KvHandoffReceiver(name=f"/pdtpu_kv_test_{os.getpid()}",
                             capacity_mb=16).start()
    try:
        since = rec.stats()["recorded"]
        sender = KvHandoffSender(recv.name)
        bundle = pre.export_prefill(prompt, max_new_tokens=5)
        nbytes = sender.send("h1", bundle)
        assert nbytes > 0
        got = recv.wait("h1", timeout=10)
        assert got is not None
        # unknown ids time out to None instead of blocking forever
        assert recv.wait("nope", timeout=0.1) is None
        rid = dec.admit_prefilled(got, max_new_tokens=5)
        out = dec.run_until_done()
        assert out[rid].tolist() == solo
        kinds = [e["kind"] for e in rec.events(since=since, kind="kv")]
        assert "kv.handoff_send" in kinds and "kv.handoff_recv" in kinds
        sender.close()
    finally:
        recv.close()
        if not was_enabled:
            rec.disable()


# ---- in-process: live migration (export_slot / admit_migrated) --------------

def test_export_slot_admit_migrated_token_identical():
    """Mid-decode migration between two engines over the same weights:
    tokens generated on the source + tokens generated on the destination
    equal an unmigrated run exactly; on_token on the destination fires
    only for NEW tokens; sched.migrate_out/in events land in the ring."""
    model = _ref_model()
    prompt = np.random.RandomState(11).randint(1, 512, (9,)).tolist()
    n_tok = 10
    solo = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                          max_new_tokens=n_tok).numpy()[0].tolist()
    rec = frec.get_recorder()
    was_enabled = rec.enabled
    rec.enable()
    try:
        since = rec.stats()["recorded"]
        src = ContinuousBatchEngine(model, max_batch=2, max_len=64,
                                    page_size=8)
        dst = ContinuousBatchEngine(model, max_batch=2, max_len=64,
                                    page_size=8)
        src_toks, dst_toks = [], []
        rid = src.add_request(prompt, max_new_tokens=n_tok,
                              on_token=lambda r, t, d: src_toks.append(t),
                              priority=0, slo_ms=60_000.0,
                              stop_token_ids=[99999], logprobs=True)
        for _ in range(4):
            src.step()
        bundle = src.export_slot(rid)
        assert src.num_active == 0 and not src._queue
        assert bundle["kind"] == "migrate"
        # four steps retired, and the one in flight drained by the export
        assert len(bundle["tokens"]) == 5
        assert src.finish_reason(rid) == "migrated"
        assert src.stats()["requests_migrated_out"] == 1
        rid2 = dst.admit_migrated(
            bundle, on_token=lambda r, t, d: dst_toks.append(t))
        out = dst.run_until_done()
        assert src_toks + dst_toks == solo
        assert out[rid2].tolist() == solo
        assert dst.finish_reason(rid2) == "length"
        # decode-side state survived the hop: logprobs cover ALL tokens
        assert len(dst.logprobs(rid2)) == n_tok
        assert dst.stats()["requests_migrated_in"] == 1
        kinds = [e["kind"] for e in rec.events(since=since, kind="sched")]
        assert "sched.migrate_out" in kinds
        assert "sched.migrate_in" in kinds
    finally:
        if not was_enabled:
            rec.disable()


def test_migrated_stream_audits_end_to_end_on_destination():
    """The migration-leg audit invariant: the sentinel mark rides the
    migrate bundle, the audit obligation lands on the DESTINATION (where
    the stream finishes), and the destination's reference replay covers
    the WHOLE stream — source-generated tokens included — so a
    migration that corrupted the hop would diverge, not escape."""
    model = _ref_model()
    prompt = np.random.RandomState(12).randint(1, 512, (9,)).tolist()
    src = ContinuousBatchEngine(model, max_batch=2, max_len=64,
                                page_size=8)
    dst = ContinuousBatchEngine(model, max_batch=2, max_len=64,
                                page_size=8)
    src.sentinel.enable(audit_rate=0.0)
    dst.sentinel.enable(audit_rate=0.0)
    dst.sentinel.start()
    try:
        rid = src.add_request(prompt, max_new_tokens=8, audit=True)
        for _ in range(4):
            src.step()
        bundle = src.export_slot(rid)
        assert bundle["audit"] == "ondemand"   # the mark survives the hop
        rid2 = dst.admit_migrated(bundle)
        dst.run_until_done()
        v = dst.sentinel.wait_verdict(rid2, timeout=120.0)
        assert v is not None, dst.sentinel.payload()
        assert v["verdict"] == "pass", v
        assert v["source"] == "ondemand"
        assert v["n_tokens"] == 8              # prior + new tokens audited
        assert dst.sentinel.federated()["audit_pass"] == 1.0
        assert src.sentinel.federated()["audit_pass"] == 0.0
    finally:
        dst.sentinel.stop()


def test_preempted_restored_stream_audits_end_to_end():
    """The preemption-leg audit invariant: a victim that round-tripped
    through host memory (preempt -> restore) keeps its on-demand audit
    mark and its accumulated logprobs, and the post-restore finish
    audits the WHOLE stream against the reference path — the PR-10
    token-identity invariant checked by the live sentinel, not just the
    example-based scheduler tests."""
    model = _ref_model()
    rng = np.random.RandomState(4)
    short_p = rng.randint(1, 512, (5,))
    long_p = rng.randint(1, 512, (41,))
    eng = ContinuousBatchEngine(model, max_batch=1, max_len=64,
                                page_size=8, enable_preemption=True)
    sn = eng.sentinel
    sn.enable(audit_rate=0.0)
    sn.start()
    try:
        victim = eng.add_request(short_p, max_new_tokens=12, priority=2,
                                 audit=True)
        for _ in range(3):
            eng.step()                      # victim has generated tokens
        eng.add_request(long_p, max_new_tokens=6, priority=0)
        eng.run_until_done()
        assert eng.stats()["requests_preempted"] == 1
        v = sn.wait_verdict(victim, timeout=120.0)
        assert v is not None, sn.payload()
        assert v["verdict"] == "pass", v
        assert v["source"] == "ondemand"
        assert v["n_tokens"] == 12          # pre- and post-preempt tokens
    finally:
        sn.stop()


def test_nonstream_completion_survives_drain_with_prior_tokens():
    """Non-stream drain path, in-process: worker A answers
    ``{"migrated": ...}`` for a request mid-collect; the router
    re-collects from the destination, prepending the bundle's prior
    tokens — the client sees ONE complete token-identical completion and
    both engines count the migration."""
    from paddle_tpu.serving_cluster.kv_handoff import make_receiver
    from paddle_tpu.serving_cluster.router import RouterServer
    from paddle_tpu.serving_cluster.worker import WorkerServer

    model = _ref_model()
    n_tok = 240
    prompt = np.random.RandomState(31).randint(1, 512, (9,)).tolist()
    solo = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                          max_new_tokens=n_tok).numpy()[0].tolist()
    engines = [ContinuousBatchEngine(model, max_batch=2, max_len=256,
                                     page_size=8) for _ in range(2)]
    recvs = [make_receiver(name=f"/pdtpu_kv_ns{i}_{os.getpid()}",
                           capacity_mb=32) for i in range(2)]
    workers = [WorkerServer(engines[i], role="unified", replica_id=i,
                            kv_receiver=recvs[i]).start()
               for i in range(2)]
    router = None
    try:
        pool = _FakePool({i: w.address for i, w in enumerate(workers)})
        for i in range(2):
            pool._ws[i].kv_channel = recvs[i].name
        router = RouterServer(pool, max_retries=2).start()
        host, port = router.address
        result = {}

        def post():
            conn = http.client.HTTPConnection(host, port, timeout=300)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt_token_ids": prompt,
                                     "max_tokens": n_tok}),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            result["status"] = r.status
            result["body"] = json.loads(r.read())
            conn.close()

        t = threading.Thread(target=post)
        t.start()
        # the fake pool's tie-break places on worker 0 first; drain it
        # the moment its engine is actually decoding the request
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline and engines[0].num_active == 0:
            time.sleep(0.002)
        assert engines[0].num_active == 1, "request never took a slot"
        summary = router.drain_worker(0, timeout=60)
        t.join(timeout=180)
        assert summary["drained"] and summary["released"], summary
        assert summary["migrated"], summary
        assert result["status"] == 200, result
        choice = result["body"]["choices"][0]
        assert choice["token_ids"] == solo
        assert result["body"]["usage"]["completion_tokens"] == n_tok
        assert engines[0].stats()["requests_migrated_out"] == 1
        assert engines[1].stats()["requests_migrated_in"] == 1
    finally:
        if router is not None:
            router.close()
        for w in workers:
            w.close()


def test_export_slot_only_active_slots_migrate():
    model = _ref_model()
    eng = ContinuousBatchEngine(model, max_batch=1, max_len=64,
                                page_size=8)
    r_active = eng.add_request([1, 2, 3], max_new_tokens=4)
    r_queued = eng.add_request([4, 5, 6], max_new_tokens=4)
    eng.step()
    with pytest.raises(ValueError, match="no decoding slot"):
        eng.export_slot(r_queued)
    with pytest.raises(ValueError, match="no decoding slot"):
        eng.export_slot(12345)
    bundle = eng.export_slot(r_active)
    assert bundle["kind"] == "migrate"


# ---- pool membership over real leases ---------------------------------------

def test_pool_lease_membership_and_loss():
    """Workers join the pool through ElasticManager leases + metadata;
    a lapsed heartbeat marks the worker lost (router.worker_lost event),
    and mark_dead takes a worker out of placement immediately."""
    from paddle_tpu.distributed.elastic import ElasticManager
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.serving_cluster import WorkerPool

    rec = frec.get_recorder()
    was_enabled = rec.enabled
    rec.enable()
    store = TCPStore("127.0.0.1", 0, is_master=True, world_size=3)
    workers = []
    try:
        for r in range(2):
            m = ElasticManager(store=store, rank=r, world_size=2,
                               ttl=1.0, job_id="pooltest")
            m.register()
            m.register_metadata({"host": "127.0.0.1", "port": 1000 + r,
                                 "role": "unified", "pid": 0,
                                 "kv_channel": None})
            workers.append(m)
        pool = WorkerPool(store=store, world_size=2, job_id="pooltest",
                          ttl=1.0, probe_timeout=0.2)
        since = rec.stats()["recorded"]
        pool.refresh()
        snap = {w["replica_id"]: w for w in pool.workers()}
        assert set(snap) == {0, 1}
        assert all(w["alive"] for w in snap.values())
        assert snap[0]["lease_age_s"] is not None
        kinds = [e["kind"] for e in rec.events(since=since)]
        assert kinds.count("router.worker_join") == 2

        # placement is least-loaded with pending accounting
        w_a = pool.select()
        w_b = pool.select()
        assert {w_a.replica_id, w_b.replica_id} == {0, 1}
        pool.release(w_a)
        pool.release(w_b)

        # mark_dead pulls a worker out of rotation NOW
        pool.mark_dead(0, "connection")
        w = pool.select()
        assert w.replica_id == 1
        pool.release(w)

        # a worker that KEEPS heartbeating rejoins once a stamp newer
        # than the death observation lands (a stale-but-fresh lease from
        # before the death must NOT resurrect it)
        deadline = time.monotonic() + 10
        back = False
        while time.monotonic() < deadline and not back:
            time.sleep(0.2)
            pool.refresh()
            back = {w["replica_id"] for w in pool.workers()
                    if w["alive"]} == {0, 1}
        assert back, "re-stamping worker never rejoined the pool"

        # a lapsed heartbeat is a LOST worker
        since = rec.stats()["recorded"]
        workers[1].stop_heartbeat()
        deadline = time.monotonic() + 10
        lost = False
        while time.monotonic() < deadline and not lost:
            time.sleep(0.3)
            pool.refresh()
            snap = {w["replica_id"]: w for w in pool.workers()}
            lost = not snap[1]["alive"]
        assert lost, "lease lapse never marked the worker lost"
        kinds = [e["kind"] for e in rec.events(since=since)]
        assert "router.worker_lost" in kinds
        assert snap[0]["alive"]
        pool.close()
    finally:
        for m in workers:
            m.close()
        store.close()
        if not was_enabled:
            rec.disable()


def test_pool_lease_expiry_reap_requeue_rejoin():
    """Satellite: a worker whose heartbeat STALLS past its lease (process
    alive — pause, not stop) is reaped (router.worker_lost, reason
    lease), its pending placements are requeued (pending reset so the
    retry path re-places them), it stays out of placement while stalled,
    and it rejoins ONLY on a fresh post-stall lease stamp."""
    from paddle_tpu.distributed.elastic import ElasticManager
    from paddle_tpu.distributed.store import TCPStore
    from paddle_tpu.serving_cluster import WorkerPool

    rec = frec.get_recorder()
    was_enabled = rec.enabled
    rec.enable()
    store = TCPStore("127.0.0.1", 0, is_master=True, world_size=3)
    workers = []
    try:
        for r in range(2):
            m = ElasticManager(store=store, rank=r, world_size=2,
                               ttl=1.0, job_id="leasetest")
            m.register()
            m.register_metadata({"host": "127.0.0.1", "port": 2000 + r,
                                 "role": "unified", "pid": 0,
                                 "kv_channel": None})
            workers.append(m)
        pool = WorkerPool(store=store, world_size=2, job_id="leasetest",
                          ttl=1.0, probe_timeout=0.2)
        pool.refresh()
        assert {w["replica_id"] for w in pool.workers()
                if w["alive"]} == {0, 1}

        # a placement is in flight on worker 1 when its heartbeat stalls
        w1 = pool.get(1)
        sel = pool.select(exclude=(0,))
        assert sel.replica_id == 1 and w1.pending == 1
        pause_s = 3.0
        t_pause = time.monotonic()
        workers[1].pause_heartbeat(pause_s)
        since = rec.stats()["recorded"]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and w1.alive:
            time.sleep(0.2)
            pool.refresh()
        assert not w1.alive, "stalled lease never reaped"
        evs = rec.events(since=since)
        lost = [e for e in evs if e["kind"] == "router.worker_lost"]
        assert lost and lost[0]["replica_id"] == 1
        assert lost[0]["reason"] == "lease"
        # pending placements were requeued: the reap zeroed the count so
        # the retry path re-places without phantom load on the corpse
        assert w1.pending == 0
        # while stalled, placement never offers the reaped worker
        assert pool.select(exclude=(0,)) is None

        # rejoin happens ONLY on a fresh stamp: the worker stays dead
        # for the remainder of the pause, then the first post-pause beat
        # readmits it
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline and not w1.alive:
            time.sleep(0.2)
            pool.refresh()
            if not w1.alive:
                # every refresh during the stall must keep it dead
                assert (time.monotonic() - t_pause) < pause_s + 5
        assert w1.alive, "fresh post-stall lease never rejoined"
        assert (time.monotonic() - t_pause) >= pause_s - 0.5, \
            "rejoined on a stale pre-stall stamp"
        got = pool.select(exclude=(0,))
        assert got is not None and got.replica_id == 1
        pool.release(got)
        pool.close()
    finally:
        for m in workers:
            m.close()
        store.close()
        if not was_enabled:
            rec.disable()


# ---- the multi-engine dryrun gate -------------------------------------------

@pytest.fixture(scope="module")
def unified_cluster():
    """Router + 2 worker processes, with the runtime lock-order witness
    ON everywhere: workers inherit FLAGS_lock_witness=1 through the
    launcher env, and set_flags arms the router-process locks (pool,
    router) constructed inside launch_cluster — so the dryrun validates
    the static lock graph against the real multi-process topology."""
    from paddle_tpu.serving_cluster import launch_cluster
    from paddle_tpu.utils.flags import set_flags

    os.environ["FLAGS_lock_witness"] = "1"
    set_flags({"lock_witness": True})
    try:
        # supervise=False: this module's failover gate pins the PR-6
        # semantics (a killed worker STAYS dead and the survivor carries
        # the streams) — the supervised kill→restart→heal→quarantine
        # story has its own referee in the chaos dryrun gate
        # ttl 10 s: the clean-run control below must see no lease lapse,
        # and on a starved machine a worker's heartbeat can come 2 s
        # late; the failover gate learns of its kill from the broken
        # socket (mark_dead), not from the lease
        cluster = launch_cluster(_cluster_cfg(
            [{"role": "unified", "count": 2}], ttl=10.0), supervise=False)
    except BaseException:
        os.environ.pop("FLAGS_lock_witness", None)
        set_flags({"lock_witness": False})
        raise
    yield cluster
    cluster.close()
    os.environ.pop("FLAGS_lock_witness", None)
    set_flags({"lock_witness": False})


def test_cluster_gate_federation_and_clean_alerts(unified_cluster):
    """Cluster watchtower federation + the zero-false-positive control.
    Runs FIRST against the module cluster (before the failover gate
    kills a worker): on an untouched 2-worker tier under normal
    traffic, ``GET /metrics/cluster`` merges both workers' expositions
    with ``replica`` labels plus the pool-derived series,
    ``/timeseries`` carries the federated cluster series (names pinned
    to ``alerts.FEDERATED_SERIES``), and the router's cluster
    AlertManager fires NOTHING."""
    from paddle_tpu.observability import alerts as al
    from paddle_tpu.observability import timeseries as tsm

    cluster = unified_cluster
    host, port = cluster.address
    url = f"http://{host}:{port}"
    for _ in range(2):
        clean, toks, _tp = _stream_completion(
            host, port, {"prompt_token_ids": [5, 6, 7],
                         "max_tokens": 4, "stream": True})
        assert clean and len(toks) == 4
    # a fresh probe (worker stats into the pool) then a forced sample
    # (pool stats into the federated store + one alert evaluation) —
    # the background cadences must not gate the assertions. Probe and
    # scrape give a worker 2 s to answer, and on a starved machine one
    # misses that: by design a `# scrape_error` comment, not a fault of
    # the federation. So wait (bounded) for ONE view in which every
    # worker answered, and hold that view to all of it.
    deadline = time.monotonic() + 120
    while True:
        cluster.pool.refresh()
        tsm.get_store().sample_once()
        # ---- /metrics/cluster: one exposition for the whole tier ----
        with urllib.request.urlopen(url + "/metrics/cluster",
                                    timeout=30) as r:
            assert "text/plain" in (r.headers.get("Content-Type") or "")
            text = r.read().decode()
        whole = ("# scrape_error" not in text
                 and "cluster_workers_alive 2" in text)
        if whole or time.monotonic() > deadline:
            break
        time.sleep(0.5)
    assert "# scrape_error" not in text, [
        ln for ln in text.splitlines() if "scrape_error" in ln]
    for rid in ("0", "1"):
        assert f'serving_requests_total{{replica="{rid}",' in text, rid
    assert 'replica="router"' in text
    assert "cluster_workers_alive 2" in text
    assert "# TYPE cluster_workers_alive gauge" in text
    # HELP/TYPE headers appear once per family, not once per replica
    assert text.count("# TYPE serving_requests_total counter") == 1

    # ---- the federated series are exactly the declared set ----------
    ts = _get_json(url + "/timeseries")
    cluster_series = {s["name"] for s in ts["series"]
                      if s["name"].startswith("cluster_")}
    assert "cluster_workers_alive" in cluster_series
    assert cluster_series <= set(al.FEDERATED_SERIES)
    reps = {s["labels"].get("replica") for s in ts["series"]
            if s["name"] == "cluster_requests_finished"}
    assert {"0", "1"} <= reps

    # ---- the clean-run control: ZERO false-positive alerts ----------
    a = _get_json(url + "/alerts")
    assert a["enabled"] is True and a["manager"] == "cluster"
    assert {x["name"] for x in a["alerts"]} == set(al.CLUSTER_OBJECTIVES)
    assert a["firing"] == []
    fired = [t for t in a["transitions"] if t["to"] == "firing"]
    assert fired == [], fired


def test_cluster_gate_concurrent_streams_and_failover(unified_cluster):
    """THE gate: 8 concurrent streaming requests through the router over
    2 CPU worker processes, token-identical to single-engine serving;
    killing one worker mid-stream requeues its in-flight requests onto
    the survivor (streams stay continuous and correct); the decisions
    are flight-recorder events and one trace_id spans router + worker."""
    cluster = unified_cluster
    host, port = cluster.address
    model = _ref_model()
    rng = np.random.RandomState(3)
    n_tok = 96
    # ONE prompt length: every worker compiles exactly one prefill
    # bucket, and the warmup round below pays for it — so in the real
    # phase first tokens arrive in milliseconds and the kill lands with
    # ~90 tokens still undelivered on every stream
    prompts = [rng.randint(1, 512, (9,)).tolist() for _ in range(8)]
    solos = [model.generate(paddle.to_tensor(np.asarray(p)[None]),
                            max_new_tokens=n_tok).numpy()[0].tolist()
             for p in prompts]

    def warm(i):
        conn = http.client.HTTPConnection(host, port, timeout=300)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt_token_ids": prompts[i],
                                 "max_tokens": 1}),
                     {"Content-Type": "application/json"})
        assert conn.getresponse().status == 200
        conn.close()

    warmers = [threading.Thread(target=warm, args=(i,)) for i in range(8)]
    for t in warmers:
        t.start()
    for t in warmers:
        t.join(timeout=300)

    rec = frec.get_recorder()
    since = rec.stats()["recorded"]
    results = [None] * len(prompts)
    first = [threading.Event() for _ in prompts]

    def client(i):
        results[i] = _stream_completion(
            host, port,
            {"prompt_token_ids": prompts[i], "max_tokens": n_tok,
             "stream": True},
            on_first_token=first[i].set)

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for ev in first:
        assert ev.wait(180), "a stream never produced its first token"
    # every stream is mid-flight: kill one worker process (SIGKILL — no
    # clean deregistration, exactly the failure the tier must absorb)
    cluster.kill_worker(0)
    for t in threads:
        t.join(timeout=300)
    for i, (clean, toks, _) in enumerate(results):
        assert clean, f"stream {i} did not end with [DONE]"
        assert toks == solos[i], f"stream {i} tokens diverged"

    # placement/retry/loss decisions are flight-recorder events
    evs = rec.events(since=since, kind="router")
    kinds = [e["kind"] for e in evs]
    assert kinds.count("router.place") >= len(prompts)
    assert "router.worker_lost" in kinds
    retries = [e for e in evs if e["kind"] == "router.retry"]
    assert retries, "killing a worker mid-stream must requeue requests"
    # the failover skipped already-delivered tokens (continuation, not
    # replay): at least one retry happened after first tokens flowed
    assert any(e["delivered"] >= 1 for e in retries)

    # the router's aggregate /health shows the loss and the survivor
    health = _get_json(f"http://{host}:{port}/health")
    assert health["status"] == "ok"
    workers = health["workers"]
    assert len(workers) == 2
    alive = [w for w in workers.values() if w["alive"]]
    dead = [w for w in workers.values() if not w["alive"]]
    assert len(alive) == 1 and len(dead) == 1
    assert health["router"]["retried"] >= 1

    # worker /health carries the cluster identity satellite
    wh = _get_json(alive[0]["url"] + "/health")
    assert wh["role"] == "unified"
    assert wh["replica_id"] == alive[0]["replica_id"]
    assert wh["lease_age_s"] is not None and wh["lease_age_s"] >= 0.0


def test_cluster_gate_single_trace_spans_router_and_worker(
        unified_cluster):
    """One trace_id covers the router's router.request/router.upstream
    and the worker's http.request/serving.request spans — the
    cross-process timeline the tracer was built for."""
    cluster = unified_cluster
    host, port = cluster.address
    model = _ref_model()
    prompt = np.random.RandomState(9).randint(1, 512, (6,)).tolist()
    solo = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                          max_new_tokens=4).numpy()[0].tolist()
    clean, toks, tp = _stream_completion(
        host, port, {"prompt_token_ids": prompt, "max_tokens": 4,
                     "stream": True})
    assert clean and toks == solo
    assert tp, "router must answer with a traceparent"
    trace_id = tp.split("-")[1]

    router_spans = _get_json(
        f"http://{host}:{port}/trace?trace_id={trace_id}")["spans"]
    names = {s["name"] for s in router_spans}
    assert {"router.request", "router.upstream"} <= names
    assert all(s["trace_id"] == trace_id for s in router_spans)

    health = _get_json(f"http://{host}:{port}/health")
    worker_names = set()
    for w in health["workers"].values():
        if not w["alive"]:
            continue
        spans = _get_json(
            w["url"] + f"/trace?trace_id={trace_id}")["spans"]
        worker_names |= {s["name"] for s in spans}
        assert all(s["trace_id"] == trace_id for s in spans)
    assert {"http.request", "serving.request"} <= worker_names


def test_cluster_gate_lock_witness_clean(unified_cluster):
    """The runtime lock-order witness ran through the whole gate
    (concurrent streams, a worker SIGKILL, failover) in every process —
    and observed ZERO order violations: the static lock graph
    (`pdlint --threads`) survives real multi-process execution. Runs
    after the failover test so real traffic has exercised the locks."""
    from paddle_tpu.analysis.threads import witness as twit

    cluster = unified_cluster
    host, port = cluster.address

    # router process (this process): pool/router locks are witnessed
    local = twit.report()
    assert local["enabled"]
    assert "WorkerPool._lock" in local["locks"]
    assert local["violations"] == [], local["violations"]

    # the router's /debug/dump bundle carries the same report
    bundle = _get_json(f"http://{host}:{port}/debug/dump")
    assert bundle["lock_witness"] is not None
    assert bundle["lock_witness"]["violations"] == []

    # surviving worker process: witness active there too (env-inherited),
    # its observability/kv locks witnessed, zero violations
    health = _get_json(f"http://{host}:{port}/health")
    checked = 0
    for w in health["workers"].values():
        if not w["alive"]:
            continue
        wb = _get_json(w["url"] + "/debug/dump")
        assert wb["lock_witness"] is not None, "witness off in worker"
        assert wb["lock_witness"]["enabled"]
        assert wb["lock_witness"]["locks"], "no witnessed lock ever used"
        assert wb["lock_witness"]["violations"] == [], \
            wb["lock_witness"]["violations"]
        checked += 1
    assert checked >= 1


def test_cluster_prefill_decode_disaggregation():
    """Role-split tier: a prefill worker computes the prompt KV and
    ships it over the decode worker's shm handoff channel; the decode
    worker streams token-identical output; both sides record their
    handoff events."""
    from paddle_tpu.serving_cluster import launch_cluster

    model = _ref_model()
    prompt = np.random.RandomState(7).randint(1, 512, (9,)).tolist()
    solo = model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                          max_new_tokens=8).numpy()[0].tolist()
    with launch_cluster(_cluster_cfg(
            [{"role": "prefill", "count": 1},
             {"role": "decode", "count": 1}],
            max_batch=4, max_len=64)) as cluster:
        host, port = cluster.address
        # non-stream
        conn = http.client.HTTPConnection(host, port, timeout=180)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt_token_ids": prompt,
                                 "max_tokens": 8}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 200
        out = json.loads(resp.read())
        conn.close()
        assert out["choices"][0]["token_ids"] == solo
        # stream
        clean, toks, _ = _stream_completion(
            host, port, {"prompt_token_ids": prompt, "max_tokens": 8,
                         "stream": True})
        assert clean and toks == solo
        # handoff decisions visible in BOTH processes' rings
        health = _get_json(f"http://{host}:{port}/health")
        by_role = {w["role"]: w for w in health["workers"].values()}
        pre_evs = _get_json(by_role["prefill"]["url"]
                            + "/debug/events?kind=kv")["events"]
        dec_evs = _get_json(by_role["decode"]["url"]
                            + "/debug/events?kind=kv")["events"]
        assert {"kv.handoff_send"} == {e["kind"] for e in pre_evs}
        assert {"kv.handoff_recv"} == {e["kind"] for e in dec_evs}
        assert len(pre_evs) >= 2 and len(dec_evs) >= 2
        # a prefill-role worker refuses direct completions
        conn = http.client.HTTPConnection(
            by_role["prefill"]["url"].split("//")[1].split(":")[0],
            int(by_role["prefill"]["url"].rsplit(":", 1)[1]),
            timeout=30)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt_token_ids": prompt,
                                 "max_tokens": 2}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 409
        resp.read()
        conn.close()


# ---- live migration + drain dryrun gate -------------------------------------

def test_cluster_gate_drain_migrates_live_streams():
    """THE migration gate: streams mid-decode on a 2-worker cluster,
    then POST /drain {replica_id: 0} on the router — worker 0's live
    slots migrate to worker 1 over the kv_handoff transport with zero
    token loss: every stream stays continuous (one SSE connection, clean
    [DONE]) and token-identical to an undrained run; sched.migrate_out
    fires on the source, sched.migrate_in on the destination; the
    drained worker releases its lease and leaves the pool."""
    from paddle_tpu.serving_cluster import launch_cluster

    model = _ref_model()
    rng = np.random.RandomState(21)
    n_tok = 64
    prompts = [rng.randint(1, 512, (9,)).tolist() for _ in range(4)]
    solos = [model.generate(paddle.to_tensor(np.asarray(p)[None]),
                            max_new_tokens=n_tok).numpy()[0].tolist()
             for p in prompts]
    with launch_cluster(_cluster_cfg(
            [{"role": "unified", "count": 2}])) as cluster:
        host, port = cluster.address

        # warm both workers' compile caches so the drain lands while
        # every stream has most of its tokens still undelivered
        def warm(i):
            conn = http.client.HTTPConnection(host, port, timeout=300)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt_token_ids": prompts[i],
                                     "max_tokens": 1}),
                         {"Content-Type": "application/json"})
            assert conn.getresponse().status == 200
            conn.close()

        warmers = [threading.Thread(target=warm, args=(i,))
                   for i in range(4)]
        for t in warmers:
            t.start()
        for t in warmers:
            t.join(timeout=300)

        results = [None] * len(prompts)
        first = [threading.Event() for _ in prompts]

        def client(i):
            results[i] = _stream_completion(
                host, port,
                {"prompt_token_ids": prompts[i], "max_tokens": n_tok,
                 "stream": True},
                on_first_token=first[i].set)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(len(prompts))]
        for t in threads:
            t.start()
        for ev in first:
            assert ev.wait(180), "a stream never produced a first token"

        # every stream is mid-decode: drain worker 0 through the router
        conn = http.client.HTTPConnection(host, port, timeout=180)
        conn.request("POST", "/drain",
                     json.dumps({"replica_id": 0, "timeout": 90}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        summary = json.loads(resp.read())
        conn.close()
        assert resp.status == 200, summary
        assert summary["drained"], summary
        assert summary["released"], summary
        assert summary["migrated"], \
            f"drain moved nothing (streams were live): {summary}"

        for t in threads:
            t.join(timeout=300)
        for i, (clean, toks, _) in enumerate(results):
            assert clean, f"stream {i} did not end with [DONE]"
            assert toks == solos[i], f"stream {i} tokens diverged"

        # migration decisions are flight-recorder events on both sides
        health = _get_json(f"http://{host}:{port}/health")
        w0, w1 = health["workers"]["0"], health["workers"]["1"]
        out_evs = _get_json(w0["url"]
                            + "/debug/events?kind=sched")["events"]
        assert any(e["kind"] == "sched.migrate_out" for e in out_evs), \
            [e["kind"] for e in out_evs]
        in_evs = _get_json(w1["url"]
                           + "/debug/events?kind=sched")["events"]
        assert any(e["kind"] == "sched.migrate_in" for e in in_evs), \
            [e["kind"] for e in in_evs]
        # the drained worker refuses new admissions...
        conn = http.client.HTTPConnection(
            w0["url"].split("//")[1].split(":")[0],
            int(w0["url"].rsplit(":", 1)[1]), timeout=30)
        conn.request("POST", "/v1/completions",
                     json.dumps({"prompt_token_ids": prompts[0],
                                 "max_tokens": 2}),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        assert resp.status == 503, resp.read()
        resp.read()
        conn.close()
        # ...and its released lease takes it out of the pool: placement
        # lands everything on the survivor
        clean, toks, _ = _stream_completion(
            host, port, {"prompt_token_ids": prompts[0],
                         "max_tokens": 4, "stream": True})
        assert clean and toks == solos[0][:4]


# ---- launcher config plumbing -----------------------------------------------

def test_launcher_config_loading(tmp_path):
    from paddle_tpu.serving_cluster import load_config
    from paddle_tpu.serving_cluster.launcher import expand_workers

    cfg = _cluster_cfg([{"role": "prefill", "count": 2},
                        {"role": "decode"}])
    p = tmp_path / "cluster.json"
    p.write_text(json.dumps(cfg))
    loaded = load_config(str(p))
    assert loaded["engine"]["max_batch"] == 8
    roles = [w["role"] for w in expand_workers(loaded)]
    assert roles == ["prefill", "prefill", "decode"]
    # no workers section -> ONE unified worker (a worker needs a chip of
    # its own, and one is what any host has), count stripped
    assert [w["role"] for w in expand_workers({})] == ["unified"]
    assert all("count" not in w for w in expand_workers(loaded))


# ---- end-to-end deadlines (overload resilience) -----------------------------

def test_deadline_header_roundtrip_and_router_shed():
    """The deadline contract, pinned: the router stamps each upstream
    hop with X-Request-Deadline = its own budget MINUS elapsed time
    (never a fresh budget), and a request whose budget is already spent
    is shed AT the router — typed 504 with code=deadline_exceeded,
    without ever touching a worker."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from paddle_tpu.serving_cluster.router import RouterServer

    seen = []

    class Stub(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_POST(self):
            self.rfile.read(int(self.headers.get("Content-Length", 0)))
            seen.append(self.headers.get("X-Request-Deadline"))
            body = json.dumps({"choices": [{"index": 0,
                                            "token_ids": [7]}]}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

    httpd = ThreadingHTTPServer(("127.0.0.1", 0), Stub)
    threading.Thread(target=httpd.serve_forever, daemon=True,
                     name="stub-worker-http").start()
    pool = _FakePool({0: httpd.server_address})
    router = RouterServer(pool, max_retries=1).start()
    try:
        host, port = router.address

        def post(body):
            c = http.client.HTTPConnection(host, port, timeout=60)
            t0 = time.monotonic()
            c.request("POST", "/v1/completions", json.dumps(body),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            data = json.loads(r.read())
            c.close()
            return r.status, data, time.monotonic() - t0

        st, data, elapsed = post({"prompt_token_ids": [1, 2, 3],
                                  "max_tokens": 2, "slo_ms": 900.0})
        assert st == 200, data
        assert len(seen) == 1 and seen[0] is not None
        remaining = float(seen[0])
        # the worker's effective deadline is the router's minus elapsed:
        # 0 < remaining <= 900, and the slack is bounded by the
        # measured request wall time
        assert 0 < remaining <= 900.0
        assert 900.0 - remaining <= elapsed * 1000.0 + 50.0

        # no slo: no header
        st, data, _ = post({"prompt_token_ids": [1, 2, 3],
                            "max_tokens": 2})
        assert st == 200 and seen[1] is None

        # spent budget: shed at the router, the stub never sees it
        n_before = len(seen)
        st, data, _ = post({"prompt_token_ids": [1, 2, 3],
                            "max_tokens": 2, "slo_ms": 0.001})
        assert st == 504 and data["code"] == "deadline_exceeded", data
        assert len(seen) == n_before
        health = _get_json(f"http://{host}:{port}/health")
        assert health["router"]["deadline"] == 1
    finally:
        router.close()
        httpd.shutdown()
        httpd.server_close()


def test_worker_effective_deadline_from_header():
    """The worker half of the contract: an inbound X-Request-Deadline
    header becomes the engine request's admission deadline (remaining
    budget, header wins over body slo_ms) — pinned by inspecting the
    queued request's absolute deadline."""
    from paddle_tpu.serving_http import CompletionServer

    model = _ref_model()
    eng = ContinuousBatchEngine(model, max_batch=1, max_len=256,
                                page_size=8)
    with CompletionServer(eng) as srv:
        host, port = srv.address
        holder = http.client.HTTPConnection(host, port, timeout=120)
        holder.request(
            "POST", "/v1/completions",
            json.dumps({"prompt_token_ids": [1, 2, 3, 4],
                        "max_tokens": 250, "stream": True}),
            {"Content-Type": "application/json"})
        resp = holder.getresponse()
        assert resp.status == 200
        resp.readline()               # slot definitely held
        probe = http.client.HTTPConnection(host, port, timeout=120)
        t_send = time.perf_counter()
        probe.request(
            "POST", "/v1/completions",
            json.dumps({"prompt_token_ids": [5, 6, 7], "max_tokens": 2,
                        "slo_ms": 1.0}),   # body slo would shed instantly
            {"Content-Type": "application/json",
             "X-Request-Deadline": "5000"})
        # the probe is QUEUED behind the holder: its engine deadline
        # must derive from the header (5s), not the body (1ms)
        import math

        deadline = None
        while time.perf_counter() - t_send < 10.0:
            q = list(eng._queue)
            if q and q[0].deadline != math.inf:
                deadline = q[0].deadline
                break
            time.sleep(0.01)
        assert deadline is not None, "probe never appeared in the queue"
        remaining = deadline - time.perf_counter()
        assert 3.5 <= remaining <= 5.0, remaining
        r = probe.getresponse()
        data = json.loads(r.read())
        assert r.status == 200, data  # completed inside the 5s budget
        probe.close()
        resp.read()
        holder.close()


def test_client_disconnect_mid_relay_cancels_worker(unified_cluster):
    """Satellite regression: a client dropping its SSE mid-relay (under
    concurrent load) must propagate through the router to the worker —
    the worker sees its own socket die, CANCELS the engine request
    (engine.cancel event), and the slot frees instead of decoding to a
    dead socket. Concurrent streams are unaffected."""
    import socket as _socket

    cluster = unified_cluster
    host, port = cluster.address
    model = _ref_model()
    rng = np.random.RandomState(21)
    prompts = [rng.randint(1, 512, (9,)).tolist() for _ in range(3)]
    solos = [model.generate(paddle.to_tensor(np.asarray(p)[None]),
                            max_new_tokens=64).numpy()[0].tolist()
             for p in prompts]
    # the live worker's cancel-event cursor BEFORE the drop
    health = _get_json(f"http://{host}:{port}/health")
    workers = [w for w in health["workers"].values() if w["alive"]]
    assert workers
    cursors = {w["url"]: _get_json(
        w["url"] + "/debug/events?kind=engine.cancel")["next_since"]
        for w in workers}

    results = [None] * len(prompts)

    def client(i):
        results[i] = _stream_completion(
            host, port,
            {"prompt_token_ids": prompts[i], "max_tokens": 64,
             "stream": True})

    threads = [threading.Thread(target=client, args=(i,),
                                name=f"disc-client-{i}")
               for i in range(len(prompts))]
    for t in threads:
        t.start()

    # the victim: read a couple of tokens, then drop the socket hard
    victim = http.client.HTTPConnection(host, port, timeout=120)
    victim.request("POST", "/v1/completions",
                   json.dumps({"prompt_token_ids": prompts[0],
                               "max_tokens": 100, "stream": True}),
                   {"Content-Type": "application/json"})
    vresp = victim.getresponse()
    assert vresp.status == 200
    got = 0
    while got < 2:
        line = vresp.readline()
        if line.startswith(b"data: ") and b"token_ids" in line:
            got += 1
    victim.sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_LINGER,
                           __import__("struct").pack("ii", 1, 0))
    # close EVERY reference: the response's makefile object holds the
    # fd, so sock.close() alone would leave the connection open and the
    # router would never feel the drop
    vresp.close()
    victim.close()                    # last ref + linger(0) => RST

    # the worker must emit engine.cancel and free the slot
    cancelled = False
    deadline = time.monotonic() + 60.0
    while time.monotonic() < deadline and not cancelled:
        for url, since in cursors.items():
            try:
                evs = _get_json(
                    url + f"/debug/events?kind=engine.cancel"
                          f"&since={since}")["events"]
            except OSError:
                continue
            if any(e.get("where") == "active" for e in evs):
                cancelled = True
                break
        if not cancelled:
            time.sleep(0.25)
    assert cancelled, "no worker cancelled the dropped stream's slot"

    for t in threads:
        t.join(timeout=300)
    for i, (clean, toks, _) in enumerate(results):
        assert clean and toks == solos[i], f"stream {i} was disturbed"

    # every slot drains: the cancelled request's slot was freed
    deadline = time.monotonic() + 30.0
    while time.monotonic() < deadline:
        health = _get_json(f"http://{host}:{port}/health")
        busy = sum(w.get("active", 0) for w in health["workers"].values()
                   if w["alive"])
        if busy == 0:
            break
        time.sleep(0.25)
    assert busy == 0, "the dropped stream's slot never freed"


def test_router_429_when_all_workers_busy_backed_off():
    """A request arriving while EVERY live worker sits out a busy
    backoff (earned from other requests' 429s) gets typed backpressure
    — 429 + computed Retry-After — never the 502 a dead pool earns
    (regression: found driving the load harness at a real router)."""
    from paddle_tpu.serving_cluster.router import RouterServer
    from paddle_tpu.serving_http import CompletionServer

    model = _ref_model()
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64,
                                page_size=8)
    worker = CompletionServer(eng).start()
    try:
        pool = _FakePool({0: worker.address})
        router = RouterServer(pool, max_retries=1).start()
        try:
            host, port = router.address
            pool.mark_busy(0, backoff_s=30.0)   # another request's 429
            c = http.client.HTTPConnection(host, port, timeout=60)
            c.request("POST", "/v1/completions",
                      json.dumps({"prompt_token_ids": [1, 2, 3],
                                  "max_tokens": 2}),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            body = json.loads(r.read())
            ra = r.getheader("Retry-After")
            c.close()
            assert r.status == 429, (r.status, body)
            assert "capacity" in body["error"]
            assert ra is not None and 1 <= int(ra) <= 30
            # the worker is alive and untouched: no mark_dead happened
            assert all(w["alive"] for w in pool.workers())
        finally:
            router.close()
    finally:
        worker.close()
