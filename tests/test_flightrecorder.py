"""Flight recorder & incident forensics (PR 5): the event ring, the
engine/HTTP/jit/watchdog instrumentation hooks, incident bundles (pinned
schema, atomic rank-suffixed writes, the forced-crash acceptance path),
/debug endpoints, the tracer-overflow counter, SnapshotWriter buffering
+ atexit/incident flush, the event-catalog lint, and the hot-path
overhead guarantees."""
import io
import json
import os
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import flightrecorder as fr
from paddle_tpu.observability import get_registry, tracing
from paddle_tpu.observability.snapshot import SnapshotWriter, \
    flush_all_writers
from paddle_tpu.serving import ContinuousBatchEngine
from paddle_tpu.serving_http import CompletionServer

_REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture(autouse=True)
def _isolate_recorder_and_reporter():
    """Singletons stay process-wide across the suite: restore the
    recorder's enabled flag and the reporter's arming around each test
    so a forensics test can't redirect another test's crash dumps."""
    rec = fr.get_recorder()
    rep = fr.get_reporter()
    was_enabled = rec.enabled
    was_active, was_dir = rep.active, rep.directory
    engines = dict(rep._engines)
    yield
    rec.enabled = was_enabled
    rec.clear()
    rep.active, rep.directory = was_active, was_dir
    rep._engines.clear()
    rep._engines.update(engines)


def _tiny_engine(layers=1, max_batch=2, max_len=32):
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))
    return ContinuousBatchEngine(model, max_batch=max_batch,
                                 max_len=max_len, page_size=8)


# ---------------------------------------------------------------------------
# the ring
# ---------------------------------------------------------------------------

def test_ring_record_query_and_cursor():
    rec = fr.FlightRecorder(capacity=64)
    assert rec.record(fr.EV_SUBMIT, rid=1) == 0     # disabled: no-op
    assert len(rec) == 0
    rec.enable()
    s1 = rec.record(fr.EV_SUBMIT, rid=1, engine="decoder")
    s2 = rec.record(fr.EV_ADMIT, rid=1, engine="decoder", slot=0)
    rec.record(fr.EV_HTTP_REQUEST, method="POST", path="/x")
    assert s2 == s1 + 1
    evs = rec.events()
    assert [e["seq"] for e in evs] == sorted(e["seq"] for e in evs)
    for e in evs:                      # reserved keys always present
        for k in ("seq", "ts", "mono_ns", "kind", "tid"):
            assert k in e, k
    # cursor semantics: strictly after `since`
    assert [e["kind"] for e in rec.events(since=s1)] == [
        fr.EV_ADMIT, fr.EV_HTTP_REQUEST]
    # kind exact + subsystem-prefix filters
    assert {e["kind"] for e in rec.events(kind="engine")} == {
        fr.EV_SUBMIT, fr.EV_ADMIT}
    assert [e["kind"] for e in rec.events(kind=fr.EV_HTTP_REQUEST)] == [
        fr.EV_HTTP_REQUEST]
    assert len(rec.events(limit=1)) == 1
    drained = rec.drain()
    assert len(drained) == 3 and len(rec) == 0


def test_ring_bounded_and_drop_accounting():
    rec = fr.FlightRecorder(capacity=8).enable()
    before = get_registry().get(
        "flightrecorder_events_total").value(kind=fr.EV_HEARTBEAT)
    for i in range(20):
        rec.record(fr.EV_HEARTBEAT, name="t", tag=str(i))
    assert len(rec) == 8
    st = rec.stats()
    assert st["recorded"] == 20 and st["dropped"] == 12
    # oldest evicted, newest kept
    assert [e["tag"] for e in rec.events()] == [str(i) for i in
                                                range(12, 20)]
    after = get_registry().get(
        "flightrecorder_events_total").value(kind=fr.EV_HEARTBEAT)
    assert after - before == 20


def test_ring_reserved_keys_win_over_fields():
    rec = fr.FlightRecorder(capacity=4).enable()
    rec.record(fr.EV_STALL, seq=-1, ts=0, mono_ns=0, tid=-7, name="wd")
    (ev,) = rec.events()
    assert ev["kind"] == fr.EV_STALL and ev["seq"] == 1
    assert ev["ts"] > 0 and ev["mono_ns"] > 0 and ev["tid"] != -7
    assert ev["name"] == "wd"


# ---------------------------------------------------------------------------
# satellite: tracer ring overflow is no longer silent
# ---------------------------------------------------------------------------

def test_tracer_overflow_counts_dropped_spans():
    counter = get_registry().get("tracing_spans_dropped_total")
    before = counter.value()
    tr = tracing.Tracer(capacity=4)
    tr.enabled = True            # no subscriber side effects needed
    for i in range(10):
        tr.start_span(f"t.span{i}").end()
    assert len(tr) == 4
    assert tr.dropped == 6
    assert counter.value() - before == 6
    # surfaced on the exposition and in snapshots (registry-backed)
    text = get_registry().render_prometheus()
    assert "tracing_spans_dropped_total" in text
    snap = get_registry().snapshot()
    assert snap["tracing_spans_dropped_total"]["series"][""] >= 6
    tr.clear()
    assert tr.dropped == 0


# ---------------------------------------------------------------------------
# satellite: SnapshotWriter buffering + atexit/incident flush
# ---------------------------------------------------------------------------

def test_snapshot_writer_buffers_and_flushes(tmp_path):
    w = SnapshotWriter(str(tmp_path), buffer_lines=10)
    for step in range(3):
        w.write(step=step)
    assert w.pending == 3
    assert not os.path.exists(w.path) or not open(w.path).read()
    w.flush()
    lines = open(w.path).read().splitlines()
    assert len(lines) == 3 and w.pending == 0
    assert json.loads(lines[0])["step"] == 0
    # hitting the buffer threshold flushes inline
    for step in range(10):
        w.write(step=step)
    assert w.pending == 0
    assert len(open(w.path).read().splitlines()) == 13


def test_snapshot_writer_unbuffered_default_unchanged(tmp_path):
    w = SnapshotWriter(str(tmp_path))
    w.write(step=1)
    assert len(open(w.path).read().splitlines()) == 1 and w.pending == 0


def test_flush_all_writers_and_incident_flush(tmp_path):
    w = SnapshotWriter(str(tmp_path / "a"), buffer_lines=100)
    w.write(step=1)
    assert w.pending == 1
    flush_all_writers()                       # the atexit hook's body
    assert w.pending == 0
    assert len(open(w.path).read().splitlines()) == 1
    # IncidentReporter.dump flushes buffered tails before bundling
    w.write(step=2)
    assert w.pending == 1
    fr.get_reporter().activate(str(tmp_path / "inc")).dump("manual")
    assert w.pending == 0
    assert len(open(w.path).read().splitlines()) == 2


# ---------------------------------------------------------------------------
# engine instrumentation
# ---------------------------------------------------------------------------

def test_engine_event_flow_and_cancel():
    rec = fr.get_recorder()
    rec.enable()
    rec.clear()
    eng = _tiny_engine()
    rid = eng.add_request(np.arange(1, 6), max_new_tokens=3)
    eng.run_until_done()
    kinds = [e["kind"] for e in rec.events(kind="engine")]
    assert fr.EV_SUBMIT in kinds and fr.EV_ADMIT in kinds
    assert fr.EV_STEP in kinds and fr.EV_SLOT_FREE in kinds
    assert fr.EV_PAGE_PRESSURE in kinds
    (sub,) = rec.events(kind=fr.EV_SUBMIT)
    assert sub["rid"] == rid and sub["engine"] == "decoder"
    assert sub["prompt_tokens"] == 5 and sub["max_new_tokens"] == 3
    (adm,) = rec.events(kind=fr.EV_ADMIT)
    assert adm["slot"] == 0 and adm["queue_wait_s"] >= 0
    (free,) = rec.events(kind=fr.EV_SLOT_FREE)
    assert free["status"] == "ok" and free["generated"] == 3
    (pp,) = rec.events(kind=fr.EV_PAGE_PRESSURE)
    assert pp["pages_used"] >= 1 and pp["pages_total"] == 2 * (32 // 8)
    # ONE step event per fused dispatch
    steps = rec.events(kind=fr.EV_STEP)
    assert len(steps) == 3 and all(s["active"] == 1 for s in steps)
    # cancel of a queued and of an active request
    rec.clear()
    r_active = eng.add_request(np.arange(1, 4), max_new_tokens=20)
    eng.step()
    assert eng.cancel(r_active)
    cancels = rec.events(kind=fr.EV_CANCEL)
    assert [c["where"] for c in cancels] == ["active"]
    assert rec.events(kind=fr.EV_SLOT_FREE)[-1]["status"] == "cancelled"


def test_engine_zero_cost_when_disabled():
    rec = fr.get_recorder()
    rec.disable()
    rec.clear()
    eng = _tiny_engine()
    eng.add_request(np.arange(1, 6), max_new_tokens=3)
    eng.run_until_done()
    assert len(rec) == 0                      # not one event recorded


def test_debug_state_snapshot():
    eng = _tiny_engine()
    r0 = eng.add_request(np.arange(1, 6), max_new_tokens=10)
    eng.step()
    st = eng.debug_state()
    assert st["engine"] == "decoder" and st["max_batch"] == 2
    assert st["poisoned"] is False and st["queue"] == []
    slot = st["slots"][0]
    assert slot["rid"] == r0 and slot["prompt_tokens"] == 5
    assert slot["generated"] == 1 and slot["max_new_tokens"] == 10
    assert st["slots"][1] is None
    assert st["stats"]["requests_active"] == 1
    eng.cancel(r0)


# ---------------------------------------------------------------------------
# acceptance: recorder overhead on the decode hot path
# ---------------------------------------------------------------------------

def test_recorder_overhead_under_one_percent_of_decode_step():
    """The hot path records ONE event per fused dispatch; a record()
    must cost < 1% of the cheapest measured decode step."""
    rec = fr.get_recorder()
    rec.disable()
    # eight layers: with one decode step in flight the host's part of a
    # step hides behind the program, and a one-layer step on the CPU is
    # no longer a real decode step's length
    eng = _tiny_engine(layers=8)
    eng.add_request(np.arange(1, 6), max_new_tokens=25)
    eng.step()                                # warm the compile
    times = []
    for _ in range(10):
        t0 = time.perf_counter()
        eng.step()
        times.append(time.perf_counter() - t0)
    step_s = min(times)
    rec.enable()
    rec.clear()
    n = 2000
    t0 = time.perf_counter()
    for i in range(n):
        rec.record(fr.EV_STEP, engine="decoder", active=1, seconds=0.001)
    record_s = (time.perf_counter() - t0) / n
    assert record_s < 0.01 * step_s, (
        f"record() costs {record_s * 1e6:.1f}µs against a "
        f"{step_s * 1e3:.2f}ms decode step")
    rec.disable()
    rec.clear()
    t0 = time.perf_counter()
    for i in range(n):
        rec.record(fr.EV_STEP, engine="decoder", active=1, seconds=0.001)
    disabled_s = (time.perf_counter() - t0) / n
    assert disabled_s < record_s              # guarded fast path
    assert len(rec) == 0                      # disabled records nothing


# ---------------------------------------------------------------------------
# incident bundles
# ---------------------------------------------------------------------------

def test_bundle_schema_and_dump_atomic(tmp_path, monkeypatch):
    monkeypatch.setenv("PADDLE_TRAINER_ID", "3")
    rec = fr.get_recorder()
    rec.enable()
    rec.clear()
    rep = fr.IncidentReporter(str(tmp_path))
    eng = _tiny_engine()
    rep.register_engine("decoder", eng)
    eng.add_request(np.arange(1, 6), max_new_tokens=2)
    eng.run_until_done()
    try:
        raise RuntimeError("boom")
    except RuntimeError as e:
        path = rep.activate().dump("exception", exc=e, context="unit")
    assert path is not None and os.path.exists(path)
    assert ".rank3" in os.path.basename(path)          # rank-suffixed
    assert not [f for f in os.listdir(tmp_path) if f.endswith(".tmp")]
    b = fr.validate_bundle(json.load(open(path)))
    assert b["reason"] == "exception" and b["rank"] == 3
    assert b["exception"]["type"] == "RuntimeError"
    assert any("boom" in ln for ln in b["exception"]["traceback"])
    assert {e["kind"] for e in b["events"]} >= {
        fr.EV_SUBMIT, fr.EV_ADMIT, fr.EV_STEP, fr.EV_SLOT_FREE}
    assert b["engines"]["decoder"]["max_batch"] == 2
    assert any(t["name"] == "MainThread" for t in b["threads"])
    assert "serving_requests_total" in b["metrics"]
    assert b["config"]["python"]
    # the JSONL sidecar: one event per line, same count
    (sidecar,) = [f for f in os.listdir(tmp_path)
                  if f.endswith(".events.jsonl")]
    lines = open(os.path.join(tmp_path, sidecar)).read().splitlines()
    assert len(lines) == len(b["events"])
    assert json.loads(lines[0])["kind"] == b["events"][0]["kind"]


def test_validate_bundle_rejects_malformed():
    with pytest.raises(ValueError, match="missing key"):
        fr.validate_bundle({"schema": fr.BUNDLE_SCHEMA_VERSION})
    good = fr.get_reporter().bundle("manual")
    fr.validate_bundle(good)
    bad = dict(good, events=[{"kind": "x"}])
    with pytest.raises(ValueError, match="event\\[0\\]"):
        fr.validate_bundle(bad)
    with pytest.raises(ValueError, match="unknown schema"):
        fr.validate_bundle(dict(good, schema="somebody.else/9"))


def test_incident_scope_classifies_and_enriches_oom(tmp_path):
    fr.get_reporter().activate(str(tmp_path))
    with pytest.raises(fr.XlaOom) as ei:
        with fr.incident_scope("unit.oom"):
            raise RuntimeError(
                "RESOURCE_EXHAUSTED: Out of memory allocating 16g")
    err = ei.value
    assert err.bundle_path and os.path.exists(err.bundle_path)
    assert "incident bundle" in str(err) and "unit.oom" in str(err)
    b = fr.validate_bundle(json.load(open(err.bundle_path)))
    assert b["reason"] == "xla_oom"
    assert b["exception"]["classified"] == "xla_oom"
    # non-OOM exceptions pass through unchanged (still dumped)
    with pytest.raises(ValueError, match="plain"):
        with fr.incident_scope("unit.plain"):
            raise ValueError("plain failure")
    reasons = sorted(f.split("-")[4].split(".")[0]
                     for f in os.listdir(tmp_path)
                     if f.endswith(".json"))
    assert reasons == ["exception", "xla_oom"]


def test_excepthook_install_uninstall_and_dedup(tmp_path):
    rep = fr.IncidentReporter(str(tmp_path))
    prev_hook = sys.excepthook
    rep.install(signals=False)
    try:
        assert sys.excepthook != prev_hook
        try:
            raise RuntimeError("hooked")
        except RuntimeError as e:
            sys.excepthook(type(e), e, e.__traceback__)
        bundles = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
        assert len(bundles) == 1
        # an exception already reported by incident_scope is NOT
        # re-dumped by the hook (one crash, one bundle)
        try:
            raise RuntimeError("dumped-once")
        except RuntimeError as e:
            e._pd_incident_reported = True
            sys.excepthook(type(e), e, e.__traceback__)
        assert len([f for f in os.listdir(tmp_path)
                    if f.endswith(".json")]) == 1
    finally:
        rep.uninstall()
    assert sys.excepthook is prev_hook


def test_forced_crash_subprocess_produces_complete_bundle(tmp_path):
    """THE acceptance test: a subprocess raising an XLA-OOM-classified
    error mid-request writes a complete incident bundle — event ring,
    spans, metrics snapshot, engine slot/queue state, thread stacks —
    validated against the pinned schema, and dies with the enriched
    XlaOom naming the bundle."""
    out_dir = str(tmp_path / "incidents")
    script = tmp_path / "crash.py"
    script.write_text(f"""
import sys
sys.path.insert(0, {_REPO!r})
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.serving import ContinuousBatchEngine
from paddle_tpu.observability import flightrecorder as fr
from paddle_tpu.observability import tracing

fr.install_reporter({out_dir!r})
tracing.get_tracer().enable()

paddle.seed(0)
model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
eng = ContinuousBatchEngine(model, max_batch=2, max_len=32, page_size=8)
fr.get_reporter().register_engine("decoder", eng)


def boom(rid, tok, done):
    raise RuntimeError(
        "RESOURCE_EXHAUSTED: Out of memory while trying to allocate "
        "17179869184 bytes")


eng.add_request(np.arange(1, 6), max_new_tokens=8, on_token=boom)
with fr.incident_scope("test.decode"):
    eng.run_until_done()
""")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, str(script)],
                          capture_output=True, text=True, timeout=300,
                          env=env)
    assert proc.returncode != 0
    assert "XlaOom" in proc.stderr
    assert "RESOURCE_EXHAUSTED" in proc.stderr
    assert "incident bundle" in proc.stderr
    bundles = [f for f in os.listdir(out_dir) if f.endswith(".json")]
    assert len(bundles) == 1, (bundles, proc.stderr)   # dedup held
    b = fr.validate_bundle(
        json.load(open(os.path.join(out_dir, bundles[0]))))
    assert b["reason"] == "xla_oom"
    assert b["exception"]["classified"] == "xla_oom"
    kinds = {e["kind"] for e in b["events"]}
    assert kinds >= {fr.EV_SUBMIT, fr.EV_ADMIT, fr.EV_STEP,
                     fr.EV_PAGE_PRESSURE, fr.EV_COMPILE}
    # mid-request: the slot is still held at the moment of the crash
    (slot0,) = [s for s in b["engines"]["decoder"]["slots"]
                if s is not None]
    assert slot0["generated"] < 8
    assert b["engines"]["decoder"]["stats"]["requests_active"] == 1
    assert b["spans"], "tracer was enabled; spans must be captured"
    assert any(sp["name"] == "serving.request" for sp in b["spans"])
    assert b["metrics"]["serving_requests_total"]["series"]
    assert b["threads"] and all(t["stack"] for t in b["threads"])


# ---------------------------------------------------------------------------
# HTTP: /debug endpoints + disconnect-cancel under concurrent load
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def served():
    paddle.seed(0)
    model = LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=1))
    eng = ContinuousBatchEngine(model, max_batch=4, max_len=256,
                                page_size=8)
    srv = CompletionServer(eng, model_name="tiny").start()
    yield model, eng, srv
    srv.close()


def _post(srv, body, stream=False):
    import http.client

    host, port = srv.address
    conn = http.client.HTTPConnection(host, port, timeout=120)
    conn.request("POST", "/v1/completions", json.dumps(body),
                 {"Content-Type": "application/json"})
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def _get(srv, path):
    import http.client

    host, port = srv.address
    conn = http.client.HTTPConnection(host, port, timeout=30)
    conn.request("GET", path)
    resp = conn.getresponse()
    data = resp.read()
    conn.close()
    return resp.status, data


def test_server_enables_recorder_and_debug_events(served):
    _, eng, srv = served
    assert fr.get_recorder().enabled
    status, data = _post(srv, {"prompt_token_ids": [1, 2, 3],
                               "max_tokens": 3})
    assert status == 200
    status, data = _get(srv, "/debug/events?since=0")
    assert status == 200
    doc = json.loads(data)
    kinds = {e["kind"] for e in doc["events"]}
    assert fr.EV_HTTP_REQUEST in kinds and fr.EV_SUBMIT in kinds
    assert doc["stats"]["enabled"] is True
    # cursor: a later poll from next_since only returns newer events
    cursor = doc["next_since"]
    assert cursor == doc["events"][-1]["seq"]
    status, data = _post(srv, {"prompt_token_ids": [4, 5], "max_tokens": 2})
    assert status == 200
    status, data = _get(srv, f"/debug/events?since={cursor}&kind=engine")
    doc2 = json.loads(data)
    assert doc2["events"] and all(e["seq"] > cursor
                                  for e in doc2["events"])
    assert all(e["kind"].startswith("engine.") for e in doc2["events"])
    status, _ = _get(srv, "/debug/events?since=notanint")
    assert status == 400


def test_debug_dump_serves_live_bundle(served, tmp_path):
    _, eng, srv = served
    status, _ = _post(srv, {"prompt_token_ids": [1, 2, 3],
                            "max_tokens": 2})
    assert status == 200
    status, data = _get(srv, "/debug/dump")
    assert status == 200
    b = fr.validate_bundle(json.loads(data))
    assert b["reason"] == "manual"
    assert "decoder" in b["engines"]
    assert b["engines"]["decoder"]["stats"]["requests_finished"] >= 1
    # ?write=1 persists instead
    fr.get_reporter().activate(str(tmp_path))
    status, data = _get(srv, "/debug/dump?write=1")
    assert status == 200
    path = json.loads(data)["path"]
    assert os.path.dirname(path) == str(tmp_path)
    fr.validate_bundle(json.load(open(path)))


def test_sse_disconnect_cancel_under_concurrent_load(served):
    """Satellite: several streaming clients vanish mid-decode under
    concurrent load — every slot frees, every root span ends
    `cancelled`, and `engine.cancel` events land in the flight ring."""
    import socket
    import struct

    _, eng, srv = served
    rec = fr.get_recorder()
    host, port = srv.address
    stats0 = eng.stats()
    seq0 = rec.stats()["recorded"]
    n_clients = 3

    socks = []
    for i in range(n_clients):
        prompt = np.random.RandomState(i).randint(1, 512, (5,)).tolist()
        body = json.dumps({"prompt_token_ids": prompt, "max_tokens": 200,
                           "stream": True}).encode()
        s = socket.create_connection((host, port), timeout=120)
        s.sendall((f"POST /v1/completions HTTP/1.1\r\nHost: {host}\r\n"
                   "Content-Type: application/json\r\n"
                   f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        socks.append(s)
    # plus one well-behaved non-streaming client riding the same batch
    result = {}

    def good_client():
        result["resp"] = _post(srv, {"prompt_token_ids": [7, 8, 9],
                                     "max_tokens": 5})

    t = threading.Thread(target=good_client)
    t.start()
    for s in socks:
        assert b"200" in s.recv(200)       # decoding started
    for s in socks:
        # RST on close, like a truly vanished client
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()
    t.join(timeout=300)
    assert result["resp"][0] == 200
    deadline = time.time() + 60
    while time.time() < deadline:
        stats = eng.stats()
        if (stats["requests_cancelled"] >= stats0["requests_cancelled"]
                + n_clients and stats["requests_active"] == 0):
            break
        time.sleep(0.05)
    stats = eng.stats()
    assert stats["requests_cancelled"] >= (stats0["requests_cancelled"]
                                           + n_clients)
    assert stats["requests_active"] == 0               # all slots freed
    # cancel events in the black box, slot-frees marked cancelled
    cancels = [e for e in rec.events(since=seq0, kind=fr.EV_CANCEL)]
    assert len(cancels) >= n_clients
    assert all(c["where"] in ("queued", "active") for c in cancels)
    frees = rec.events(since=seq0, kind=fr.EV_SLOT_FREE)
    assert sum(f["status"] == "cancelled" for f in frees) >= 1
    # root spans retired as cancelled
    deadline = time.time() + 10
    cancelled_spans = []
    while time.time() < deadline:
        cancelled_spans = [
            sp for sp in tracing.get_tracer().spans()
            if sp["name"] == "serving.request"
            and sp["status"] == "cancelled"]
        if len(cancelled_spans) >= n_clients:
            break
        time.sleep(0.05)
    assert len(cancelled_spans) >= n_clients
    assert all(sp["attrs"]["generated_tokens"] < 200
               for sp in cancelled_spans)


# ---------------------------------------------------------------------------
# watchdog, train, collective, compile hooks
# ---------------------------------------------------------------------------

def test_watchdog_heartbeats_and_stall_dump(tmp_path):
    from paddle_tpu.distributed.watchdog import Watchdog

    rec = fr.get_recorder()
    rec.enable()
    rec.clear()
    fr.get_reporter().activate(str(tmp_path))
    wd = Watchdog(timeout=0.2, name="unit", poll_interval=0.05,
                  stream=io.StringIO())
    wd.start()
    wd.stamp("step 1")
    deadline = time.time() + 10
    while not wd.fired and time.time() < deadline:
        time.sleep(0.05)
    wd.stop()
    assert wd.fired
    beats = rec.events(kind=fr.EV_HEARTBEAT)
    assert any(b["tag"] == "step 1" for b in beats)
    (stall,) = rec.events(kind=fr.EV_STALL)
    assert stall["name"] == "unit" and stall["age_s"] >= 0.2
    (bundle,) = [f for f in os.listdir(tmp_path)
                 if f.endswith(".json")]
    b = fr.validate_bundle(json.load(open(os.path.join(tmp_path,
                                                       bundle))))
    assert b["reason"] == "watchdog_stall" and b["context"] == "unit"
    assert any(e["kind"] == fr.EV_STALL for e in b["events"])


def test_step_timer_records_train_events():
    from paddle_tpu.observability import StepTimer

    rec = fr.get_recorder()
    rec.enable()
    rec.clear()
    StepTimer().observe(0.25, n_samples=4)
    (ev,) = rec.events(kind=fr.EV_TRAIN_STEP)
    assert ev["seconds"] == 0.25 and ev["step"] == 1


def test_collective_barrier_records_begin_end():
    from paddle_tpu.distributed import collective

    rec = fr.get_recorder()
    rec.enable()
    rec.clear()
    collective.barrier()
    (beg,) = rec.events(kind=fr.EV_COLLECTIVE_BEGIN)
    (end,) = rec.events(kind=fr.EV_COLLECTIVE_END)
    assert beg["op"] == "barrier" == end["op"]
    assert end["seconds"] >= 0 and end["seq"] > beg["seq"]


def test_jit_compile_events_recorded():
    import jax
    import jax.numpy as jnp

    rec = fr.get_recorder()
    rec.enable()                  # paddle_tpu's build listener records
    rec.clear()
    # a constant nobody else bakes, so this HLO misses every compile
    # cache (in-memory and the persistent one conftest configures) and a
    # real backend compile happens
    c = float(time.time_ns() % 1000003) + 0.5
    jax.jit(lambda x: x * c + 1)(jnp.ones((4, 3))).block_until_ready()
    compiles = rec.events(kind=fr.EV_COMPILE)
    assert compiles, "backend compile should land in the ring"
    assert all(c["seconds"] > 0 for c in compiles)
    # a load from the persistent cache is told from a compile
    assert all(c["stage"] in ("compile", "load") for c in compiles)
    assert any(c["stage"] == "compile" and c["program"].startswith("jit(")
               for c in compiles)


# ---------------------------------------------------------------------------
# event-catalog lint + read_incident
# ---------------------------------------------------------------------------

def test_event_catalog_comparison_core():
    from paddle_tpu.analysis.rules.catalogs import compare_event_catalogs

    probs = compare_event_catalogs(
        docs={"a.x", "ghost.y"},
        registered={"a.x", "b.z"},
        emitted_ok={"a.x": True, "b.z": False})
    assert any("b.z" in p and "registered but not" in p for p in probs)
    assert any("ghost.y" in p and "documented but not" in p
               for p in probs)
    assert any("never emitted" in p and "b.z" in p for p in probs)
    assert compare_event_catalogs({"a.x"}, {"a.x"},
                                  {"a.x": True}) == []


def test_documented_events_parser(tmp_path):
    from paddle_tpu.analysis.rules.catalogs import documented_events

    md = tmp_path / "SERVING.md"
    md.write_text(
        "## Incident forensics\n"
        "### Event catalog\n"
        "| kind | fields | meaning |\n"
        "|---|---|---|\n"
        "| `engine.admit` | rid | took a slot |\n"
        "| `jit.compile` | seconds | compile |\n"
        "### Debug endpoints\n"
        "| `not.an.event` | x | outside the section |\n")
    assert documented_events(str(md)) == {"engine.admit", "jit.compile"}


def test_event_catalog_rule_clean_on_live_project():
    from paddle_tpu import analysis

    findings = analysis.run(root=_REPO, paths=[],
                            selected=["event-catalog"])
    assert findings == [], [f.render() for f in findings]


def test_read_incident_renders_bundle(tmp_path, capsys):
    import importlib.util

    rec = fr.get_recorder()
    rec.enable()
    rec.clear()
    eng = _tiny_engine()
    rep = fr.IncidentReporter(str(tmp_path))
    rep.register_engine("decoder", eng)
    rid = eng.add_request(np.arange(1, 6), max_new_tokens=10)
    eng.step()
    path = rep.activate().dump("manual", context="unit")
    spec = importlib.util.spec_from_file_location(
        "_read_incident", os.path.join(_REPO, "scripts",
                                       "read_incident.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    for section in ("INCIDENT", "TIMELINE", "LAST", "ENGINE STATE",
                    "THREADS"):
        assert section in out, section
    assert "engine.submit" in out and f"rid={rid}" in out
    assert "slot 0:" in out
    # subsystem filter + timeline-only mode
    assert mod.main([path, "--subsystem", "engine"]) == 0
    assert mod.main([path, "--timeline", "--events", "5"]) == 0
    capsys.readouterr()
    # malformed input fails loudly, not with a half report
    bad = tmp_path / "bad.json"
    bad.write_text("{}")
    assert mod.main([str(bad)]) == 1
    eng.cancel(rid)


def test_hapi_steptimer_arms_incident_forensics(tmp_path):
    """The hapi StepTimer callback's incident_dir turns the recorder on
    and points the reporter at the training run's incident directory —
    a crash under fit() (wrapped in incident_scope) then dumps there."""
    from paddle_tpu.hapi.callbacks import StepTimer as HapiStepTimer

    rec = fr.get_recorder()
    rec.disable()
    HapiStepTimer(incident_dir=str(tmp_path))
    assert rec.enabled
    rep = fr.get_reporter()
    assert rep.active and rep.directory == str(tmp_path)
    with pytest.raises(RuntimeError, match="train crash"):
        with fr.incident_scope("hapi.fit"):
            raise RuntimeError("train crash")
    (bundle,) = [f for f in os.listdir(tmp_path) if f.endswith(".json")]
    b = fr.validate_bundle(json.load(open(os.path.join(tmp_path,
                                                       bundle))))
    assert b["context"] == "hapi.fit"


def test_metric_catalog_lint_still_passes():
    """The new tracing_spans_dropped_total / flightrecorder_events_total
    families are documented; the tier-1 catalog gates stay green."""
    from paddle_tpu import analysis

    findings = analysis.run(root=_REPO, paths=[],
                            selected=["metrics-catalog", "span-catalog"])
    assert findings == [], [f.render() for f in findings]
