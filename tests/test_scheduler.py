"""SLO-aware scheduler (serving.py): chunked-prefill interleaving,
priority/deadline admission with aging, KV preemption to host, and the
bounded admission queue's 429 surface.

The load-bearing guarantees pinned here:
- chunked prefill is TOKEN-IDENTICAL to the monolithic path (paged,
  latent/MLA, and prefix-cache-hit admissions);
- a preempt -> restore round trip is token-identical to an
  uninterrupted run;
- while a long prefill is in flight, a live decode's worst inter-token
  stall is bounded by ~one chunk-step (a decode dispatch runs between
  every pair of chunks) and is strictly smaller than the monolithic
  prefill stall;
- every decision is a sched.* flight-recorder event + metric.
"""
import http.client
import json
import os
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import flightrecorder as frec
from paddle_tpu.serving import (ContinuousBatchEngine, PRIORITY_DEFAULT,
                                QueueFull)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def tiny_model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))


def _solo(model, prompt, new):
    return model.generate(paddle.to_tensor(np.asarray(prompt)[None]),
                          max_new_tokens=new).numpy()[0]


@pytest.fixture()
def recorder():
    rec = frec.get_recorder()
    was = rec.enabled
    rec.enable()
    yield rec
    if not was:
        rec.disable()


# ---- chunked prefill: token identity ----------------------------------------

def test_chunked_prefill_token_identity_paged(tiny_model):
    """A long prompt admitted in 16-token chunks decodes token-identical
    to the monolithic bucketed prefill — with a live short decode
    interleaved between the chunks."""
    m = tiny_model
    rng = np.random.RandomState(4)
    long_p = rng.randint(0, m.config.vocab_size, (41,))
    short_p = rng.randint(0, m.config.vocab_size, (5,))
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8,
                                prefill_chunk_tokens=16)
    r_short = eng.add_request(short_p, max_new_tokens=12)
    eng.step()
    eng.step()
    r_long = eng.add_request(long_p, max_new_tokens=6)
    done = eng.run_until_done()
    np.testing.assert_array_equal(done[r_short], _solo(m, short_p, 12))
    np.testing.assert_array_equal(done[r_long], _solo(m, long_p, 6))


def test_chunked_prefill_token_identity_latent():
    """Latent (MLA) mode: chunk continuation goes through the latent
    suffix-prefill row copies — same token identity bar."""
    from paddle_tpu.models.deepseek import (DeepseekV2Config,
                                            DeepseekV2ForCausalLM)

    paddle.seed(3)
    m = DeepseekV2ForCausalLM(DeepseekV2Config.tiny_mla(num_hidden_layers=2))
    rng = np.random.RandomState(9)
    long_p = rng.randint(0, m.config.vocab_size, (37,))
    short_p = rng.randint(0, m.config.vocab_size, (5,))
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8,
                                prefill_chunk_tokens=16)
    assert eng._latent_mode
    r_short = eng.add_request(short_p, max_new_tokens=10)
    eng.step()
    eng.step()
    r_long = eng.add_request(long_p, max_new_tokens=6)
    done = eng.run_until_done()
    np.testing.assert_array_equal(done[r_short], _solo(m, short_p, 10))
    np.testing.assert_array_equal(done[r_long], _solo(m, long_p, 6))


def test_chunked_prefill_with_prefix_cache_hit(tiny_model):
    """Prefix-cache hit + chunking compose: the first chunk copies the
    shared prefix pages from the active source slot and runs one chunk
    of the suffix; later chunks self-continue. Token-identical, and the
    reuse counter moves."""
    m = tiny_model
    rng = np.random.RandomState(11)
    base = rng.randint(0, m.config.vocab_size, (24,))
    p_a = np.concatenate([base, rng.randint(0, m.config.vocab_size, (9,))])
    p_b = np.concatenate([base, rng.randint(0, m.config.vocab_size, (17,))])
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8,
                                prefill_chunk_tokens=16,
                                enable_prefix_cache=True)
    r_a = eng.add_request(p_a, max_new_tokens=8)
    for _ in range(4):
        eng.step()
    r_b = eng.add_request(p_b, max_new_tokens=8)
    done = eng.run_until_done()
    np.testing.assert_array_equal(done[r_a], _solo(m, p_a, 8))
    np.testing.assert_array_equal(done[r_b], _solo(m, p_b, 8))
    assert eng.prefix_pages_reused > 0


def test_short_prompts_skip_chunking(tiny_model, recorder):
    """A prompt no longer than one chunk admits monolithically — no
    sched.chunk events, no reserved-slot detour."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8,
                                prefill_chunk_tokens=16)
    since = recorder.stats()["recorded"]
    rid = eng.add_request(np.arange(1, 9), max_new_tokens=4)
    done = eng.run_until_done()
    assert rid in done
    kinds = [e["kind"] for e in recorder.events(since=since)]
    assert "sched.chunk" not in kinds


# ---- the bounded-stall guarantee --------------------------------------------

def test_mixed_load_bounded_stalls(recorder):
    """THE acceptance bar: with chunking on, a decode dispatch runs
    between every pair of prefill chunks (structural bound: no decode
    step waits longer than one chunk-step), and the live request's
    worst wall-clock inter-token gap during the long prefill is
    strictly smaller than under the monolithic prefill."""
    # wide enough that a prefill's compute, not the host's dispatch,
    # decides a gap on the CPU: at the tiny widths the two worst gaps
    # (~4 ms each) compared as a coin toss, alone and on every tree
    paddle.seed(0)
    m = LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=4, hidden_size=512, intermediate_size=2048,
        num_attention_heads=8, num_key_value_heads=4))
    rng = np.random.RandomState(7)
    long_p = rng.randint(0, m.config.vocab_size, (48,))
    short_p = rng.randint(0, m.config.vocab_size, (5,))

    def run(chunk):
        eng = ContinuousBatchEngine(m, max_batch=2, max_len=64,
                                    page_size=8,
                                    prefill_chunk_tokens=chunk)
        times = []
        r_short = eng.add_request(
            short_p, max_new_tokens=24,
            on_token=lambda rid, t, done: times.append(
                time.perf_counter()))
        while len(times) < 2:      # live decode under way
            eng.step()
        n_before = len(times)
        eng.add_request(long_p, max_new_tokens=4)
        eng.run_until_done()
        gaps = np.diff(np.asarray(times[n_before - 1:]))
        return float(gaps.max())

    # warm both variants so no measured gap pays a compile
    run(16), run(None)
    since = recorder.stats()["recorded"]
    chunked_max = run(16)
    evs = recorder.events(since=since)
    # structural interleave: between consecutive chunks of one prefill
    # a decode dispatch fired for the live slot
    seq = [e["kind"] for e in evs
           if e["kind"] in ("sched.chunk", "engine.step")]
    chunk_idx = [i for i, k in enumerate(seq) if k == "sched.chunk"]
    assert len(chunk_idx) >= 2          # 48 tokens / 16 = 3 chunks
    for a, b in zip(chunk_idx, chunk_idx[1:]):
        assert "engine.step" in seq[a + 1:b], (
            f"no decode step between chunks {a} and {b}: {seq}")
    mono_max = run(None)
    assert chunked_max < mono_max, (
        f"chunked worst gap {chunked_max * 1e3:.2f}ms not better than "
        f"monolithic {mono_max * 1e3:.2f}ms")


# ---- priority / deadline / aging --------------------------------------------

def _admit_order(recorder, since, rids):
    order = [e["rid"] for e in recorder.events(since=since,
                                               kind="engine.admit")]
    return [r for r in order if r in rids]


def test_priority_admission_order(tiny_model, recorder):
    """With the slot pool full, queued requests admit by priority class
    (lower first), not FIFO."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                aging_s=0.0)
    busy = eng.add_request(np.arange(1, 6), max_new_tokens=6)
    since = recorder.stats()["recorded"]
    r_lo = eng.add_request(np.arange(1, 6), max_new_tokens=2, priority=5)
    r_mid = eng.add_request(np.arange(1, 6), max_new_tokens=2)
    r_hi = eng.add_request(np.arange(1, 6), max_new_tokens=2, priority=0)
    assert PRIORITY_DEFAULT == 1
    done = eng.run_until_done()
    assert set(done) >= {busy, r_lo, r_mid, r_hi}
    assert _admit_order(recorder, since, {r_lo, r_mid, r_hi}) == [
        r_hi, r_mid, r_lo]


def test_deadline_tiebreak_within_class(tiny_model, recorder):
    """Same class: the earlier SLO deadline admits first (EDF), ahead of
    an earlier-submitted request with a laxer deadline."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                aging_s=0.0)
    busy = eng.add_request(np.arange(1, 6), max_new_tokens=6)
    since = recorder.stats()["recorded"]
    r_lax = eng.add_request(np.arange(1, 6), max_new_tokens=2,
                            slo_ms=60000.0)
    r_tight = eng.add_request(np.arange(1, 6), max_new_tokens=2,
                              slo_ms=50.0)
    r_none = eng.add_request(np.arange(1, 6), max_new_tokens=2)
    done = eng.run_until_done()
    assert set(done) >= {busy, r_lax, r_tight, r_none}
    assert _admit_order(recorder, since, {r_lax, r_tight, r_none}) == [
        r_tight, r_lax, r_none]


def test_aging_bounds_starvation(tiny_model, recorder):
    """A low-priority request that has waited longer than aging_s beats
    fresh higher-priority arrivals — the starvation bound."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                aging_s=0.001)
    busy = eng.add_request(np.arange(1, 6), max_new_tokens=8)
    since = recorder.stats()["recorded"]
    r_old_lo = eng.add_request(np.arange(1, 6), max_new_tokens=2,
                               priority=5)
    time.sleep(0.05)   # >> aging_s: ~50 classes of credit
    r_fresh_hi = eng.add_request(np.arange(1, 6), max_new_tokens=2,
                                 priority=0)
    done = eng.run_until_done()
    assert set(done) >= {busy, r_old_lo, r_fresh_hi}
    assert _admit_order(recorder, since, {r_old_lo, r_fresh_hi}) == [
        r_old_lo, r_fresh_hi]


# ---- preemption -------------------------------------------------------------

def test_preempt_restore_token_identity(tiny_model, recorder):
    """A high-priority arrival preempts the low-priority slot (KV to
    host), runs to completion, then the victim restores and finishes —
    BOTH outputs token-identical to uninterrupted runs, with the
    sched.preempt/sched.restore audit trail and counters."""
    m = tiny_model
    rng = np.random.RandomState(4)
    long_p = rng.randint(0, m.config.vocab_size, (41,))
    short_p = rng.randint(0, m.config.vocab_size, (5,))
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                enable_preemption=True)
    since = recorder.stats()["recorded"]
    victim = eng.add_request(short_p, max_new_tokens=12, priority=2)
    for _ in range(3):
        eng.step()                      # victim has generated tokens
    hi = eng.add_request(long_p, max_new_tokens=6, priority=0)
    done = eng.run_until_done()
    np.testing.assert_array_equal(done[hi], _solo(m, long_p, 6))
    np.testing.assert_array_equal(done[victim], _solo(m, short_p, 12))
    evs = recorder.events(since=since)
    pre = [e for e in evs if e["kind"] == "sched.preempt"]
    res = [e for e in evs if e["kind"] == "sched.restore"]
    assert len(pre) == 1 and len(res) == 1
    assert pre[0]["rid"] == victim and res[0]["rid"] == victim
    # three steps retired, and the one in flight drained before eviction
    assert pre[0]["generated"] == 4 and pre[0]["bytes"] > 0
    assert pre[0]["kv_len"] == res[0]["kv_len"] == short_p.size + 4
    assert eng.stats()["requests_preempted"] == 1


def test_equal_priority_never_preempts(tiny_model, recorder):
    """Same-class arrivals wait; only a STRICTLY more important request
    evicts (raw classes — aging credit never triggers preemption)."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                enable_preemption=True, aging_s=0.001)
    first = eng.add_request(np.arange(1, 6), max_new_tokens=6)
    eng.step()
    time.sleep(0.05)   # aging credit accrues; must NOT enable preemption
    second = eng.add_request(np.arange(2, 7), max_new_tokens=2)
    done = eng.run_until_done()
    assert set(done) == {first, second}
    assert eng.stats()["requests_preempted"] == 0


def test_preemption_rejected_in_latent_mode():
    from paddle_tpu.models.deepseek import (DeepseekV2Config,
                                            DeepseekV2ForCausalLM)

    paddle.seed(3)
    m = DeepseekV2ForCausalLM(DeepseekV2Config.tiny_mla(num_hidden_layers=2))
    with pytest.raises(ValueError, match="paged"):
        ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                              enable_preemption=True)


def test_preempted_request_streams_continuously(tiny_model):
    """on_token streaming across a preempt -> restore: no token is
    replayed and no token is lost."""
    m = tiny_model
    rng = np.random.RandomState(4)
    short_p = rng.randint(0, m.config.vocab_size, (5,))
    long_p = rng.randint(0, m.config.vocab_size, (41,))
    streamed = []
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                enable_preemption=True)
    victim = eng.add_request(
        short_p, max_new_tokens=12, priority=2,
        on_token=lambda rid, t, done: streamed.append(int(t)))
    for _ in range(3):
        eng.step()
    eng.add_request(long_p, max_new_tokens=6, priority=0)
    done = eng.run_until_done()
    assert streamed == list(done[victim])


# ---- bounded admission queue ------------------------------------------------

def test_bounded_queue_rejects_typed(tiny_model):
    m = tiny_model
    from paddle_tpu.observability import catalog as cat

    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                max_queue=1)
    n0 = cat.SERVING_REQUESTS.value(engine="decoder", event="rejected")
    eng.add_request(np.arange(1, 6), max_new_tokens=6)   # takes the slot
    eng.add_request(np.arange(1, 6), max_new_tokens=2)   # queues (1/1)
    with pytest.raises(QueueFull) as ei:
        eng.add_request(np.arange(1, 6), max_new_tokens=2)
    assert ei.value.retry_after_s > 0
    assert eng.stats()["requests_rejected"] == 1
    assert cat.SERVING_REQUESTS.value(engine="decoder",
                                      event="rejected") == n0 + 1
    # drain: the bound never wedges the engine
    done = eng.run_until_done()
    assert len(done) == 2


def test_bound_ignores_free_slots(tiny_model):
    """max_queue=0 still admits when a slot is free — the bound is on
    WAITING, not on requests."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8,
                                max_queue=0)
    rid = eng.add_request(np.arange(1, 6), max_new_tokens=2)
    assert rid in eng.run_until_done()


def test_http_429_with_retry_after(tiny_model):
    """The HTTP surface: a full bounded queue answers 429 + Retry-After
    on both the batch and the streaming path (real status line — SSE
    headers are deferred to the first token)."""
    from paddle_tpu.serving_http import CompletionServer

    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                max_queue=0)
    with CompletionServer(eng) as srv:
        host, port = srv.address
        holder = http.client.HTTPConnection(host, port, timeout=120)
        holder.request(
            "POST", "/v1/completions",
            json.dumps({"prompt_token_ids": [1, 2, 3, 4],
                        "max_tokens": 55, "stream": True}),
            {"Content-Type": "application/json"})
        resp = holder.getresponse()
        assert resp.status == 200
        resp.readline()            # first token: slot definitely held

        def post(body):
            c = http.client.HTTPConnection(host, port, timeout=120)
            c.request("POST", "/v1/completions", json.dumps(body),
                      {"Content-Type": "application/json"})
            r = c.getresponse()
            data = r.read()
            ra = r.getheader("Retry-After")
            c.close()
            return r.status, data, ra

        st, data, ra = post({"prompt_token_ids": [5, 6],
                             "max_tokens": 2})
        assert st == 429 and ra == "1" and b"queue is full" in data
        st, _, ra = post({"prompt_token_ids": [5, 6], "max_tokens": 2,
                          "stream": True})
        assert st == 429 and ra == "1"
        rest = resp.read()
        assert b"[DONE]" in rest   # the holder stream finished clean
        holder.close()


# ---- cancel / bookkeeping ---------------------------------------------------

def test_cancel_mid_chunk_frees_reserved_slot(tiny_model, recorder):
    m = tiny_model
    rng = np.random.RandomState(4)
    long_p = rng.randint(0, m.config.vocab_size, (41,))
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                prefill_chunk_tokens=16)
    since = recorder.stats()["recorded"]
    rid = eng.add_request(long_p, max_new_tokens=6)
    eng.step()                       # first chunk in, still prefilling
    assert eng.stats()["requests_prefilling"] == 1
    assert eng.cancel(rid) is True
    assert eng.stats()["requests_prefilling"] == 0
    assert eng.finish_reason(rid) == "cancelled"
    evs = recorder.events(since=since)
    cancels = [e for e in evs if e["kind"] == "engine.cancel"]
    assert cancels and cancels[-1]["where"] == "prefilling"
    # the freed slot serves the next request
    nxt = eng.add_request(np.arange(1, 6), max_new_tokens=2)
    assert nxt in eng.run_until_done()


def test_reason_retention_is_deque(tiny_model, monkeypatch):
    """The finish-reason window trims O(1) from the front (deque) and
    still evicts oldest-first."""
    import paddle_tpu.serving as serving

    m = tiny_model
    monkeypatch.setattr(serving, "_REASON_KEEP", 4)
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8)
    rids = [eng.add_request(np.arange(1, 6), max_new_tokens=1)
            for _ in range(6)]
    eng.run_until_done()
    assert eng.finish_reason(rids[0]) is None     # evicted
    assert eng.finish_reason(rids[-1]) == "length"
    from collections import deque

    assert isinstance(eng._reason_order, deque)


def test_debug_state_carries_scheduler_fields(tiny_model):
    m = tiny_model
    rng = np.random.RandomState(4)
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                prefill_chunk_tokens=16)
    rid = eng.add_request(rng.randint(0, m.config.vocab_size, (41,)),
                          max_new_tokens=4, priority=3)
    eng.step()
    st = eng.debug_state()
    assert st["prefilling"] and list(st["prefilling"].values())[0][
        "rid"] == rid
    eng.run_until_done()
    st = eng.debug_state()
    assert st["prefilling"] == {}
    assert eng.stats()["requests_preempted"] == 0


def test_read_incident_prints_scheduler_decisions(tiny_model, tmp_path,
                                                  recorder, capsys):
    """scripts/read_incident.py surfaces the sched.* trail as its own
    section."""
    import importlib.util

    m = tiny_model
    rng = np.random.RandomState(4)
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                prefill_chunk_tokens=16,
                                enable_preemption=True)
    rep = frec.IncidentReporter(str(tmp_path))
    rep.register_engine("decoder", eng)
    victim = eng.add_request(np.arange(1, 6), max_new_tokens=8,
                             priority=2)
    for _ in range(3):
        eng.step()
    eng.add_request(rng.randint(0, m.config.vocab_size, (41,)),
                    max_new_tokens=4, priority=0)
    eng.run_until_done()
    path = rep.activate().dump("manual", context="sched-test")
    spec = importlib.util.spec_from_file_location(
        "_read_incident_sched",
        os.path.join(_REPO, "scripts", "read_incident.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    assert "SCHEDULER DECISIONS" in out
    assert "sched.chunk" in out and "sched.preempt" in out
    assert "sched.restore" in out
    assert f"rid={victim}" in out


def test_memoized_step_lru_keeps_hot_entries(tiny_model):
    """_memoized_step with maxsize is LRU: a hit refreshes the key, so
    cycling through a working set the size of the cache never evicts a
    hot program (the chunked-prefill suffix-program pattern)."""
    from paddle_tpu.generation import _memoized_step

    class Dummy:
        def functional_state(self):
            return {}

    model = Dummy()
    built = []

    def factory_for(key):
        def build():
            built.append(key)
            fn = lambda: key
            fn._state = None
            return fn
        return build

    for k in ("a", "b", "c"):
        _memoized_step(model, "_t", k, factory_for(k), maxsize=3)
    # touch "a" (hit -> moves to back), then insert "d": "b" (the LRU)
    # is evicted, "a" survives
    _memoized_step(model, "_t", "a", factory_for("a"), maxsize=3)
    _memoized_step(model, "_t", "d", factory_for("d"), maxsize=3)
    _memoized_step(model, "_t", "a", factory_for("a"), maxsize=3)
    assert built.count("a") == 1          # never rebuilt
    _memoized_step(model, "_t", "b", factory_for("b"), maxsize=3)
    assert built.count("b") == 2          # "b" was the eviction victim


# ---- deadline enforcement & load shedding (overload resilience) -------------

def test_expired_deadline_sheds_before_admission(tiny_model, recorder):
    """The hard invariant behind the saturation gate: a queued request
    whose deadline passed is SHED at the admission gate — typed event,
    deadline-miss counters, on_shed notification — and is never
    admitted (no engine.admit, no tokens, no prefill burned)."""
    from paddle_tpu.observability import catalog as cat

    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8)
    hold = eng.add_request(np.arange(1, 6), max_new_tokens=24)
    eng.step()                                    # slot taken
    sheds = []
    n0 = cat.SERVING_DEADLINE_MISSES.value(engine="decoder")
    since = recorder.stats()["recorded"]
    rid = eng.add_request(np.arange(1, 8), max_new_tokens=4, priority=2,
                          slo_ms=30.0,
                          on_shed=lambda r, info: sheds.append((r, info)))
    time.sleep(0.06)                              # budget expires queued
    done = eng.run_until_done()
    assert hold in done and rid not in done
    assert eng.finish_reason(rid) == "shed"
    assert sheds and sheds[0][0] == rid
    assert sheds[0][1]["where"] == "expired"
    assert sheds[0][1]["miss_ms"] > 0
    st = eng.stats()
    assert st["requests_shed"] == 1 and st["deadline_misses"] == 1
    assert cat.SERVING_DEADLINE_MISSES.value(engine="decoder") == n0 + 1
    evs = recorder.events(since=since)
    shed_evs = [e for e in evs if e["kind"] == "sched.shed"]
    assert shed_evs and shed_evs[0]["rid"] == rid
    assert shed_evs[0]["where"] == "expired"
    # never admitted: the rid appears in no engine.admit event
    assert rid not in {e["rid"] for e in evs
                       if e["kind"] == "engine.admit"}


def test_unmeetable_budget_sheds(tiny_model, recorder):
    """A request whose REMAINING budget is below the engine's observed
    admission->first-token floor is provably unmeetable and sheds
    before burning a prefill (the floor arms only past 3 samples, so a
    single compile-contaminated observation never mis-sheds)."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8)
    # un-armed floor: a tight-but-future deadline is NOT shed
    eng._ttft_admit_floor, eng._ttft_admit_n = 10.0, 1
    hold = eng.add_request(np.arange(1, 6), max_new_tokens=6)
    eng.step()
    r_ok = eng.add_request(np.arange(1, 8), max_new_tokens=2,
                           slo_ms=5000.0)
    eng.step()
    assert eng.finish_reason(r_ok) != "shed"
    eng.cancel(r_ok)
    # armed floor above the remaining budget: provably unmeetable
    eng._ttft_admit_floor, eng._ttft_admit_n = 10.0, 3
    sheds = []
    rid = eng.add_request(np.arange(1, 8), max_new_tokens=2,
                          slo_ms=5000.0,
                          on_shed=lambda r, info: sheds.append(info))
    eng.step()
    assert eng.finish_reason(rid) == "shed"
    assert sheds and sheds[0]["where"] == "unmeetable"
    eng.run_until_done()


def test_capacity_shed_prefers_lowest_class(tiny_model, recorder):
    """At a full bounded queue, a strictly more important arrival
    displaces the least-important queued request (where=capacity, the
    429 path) instead of bouncing — and an arrival that is NOT more
    important still gets the typed QueueFull."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                max_queue=1)
    eng.add_request(np.arange(1, 6), max_new_tokens=30)
    eng.step()                                    # slot taken
    sheds = []
    victim = eng.add_request(np.arange(1, 6), max_new_tokens=2,
                             priority=2,
                             on_shed=lambda r, i: sheds.append((r, i)))
    vip = eng.add_request(np.arange(1, 6), max_new_tokens=2, priority=0)
    assert eng.finish_reason(victim) == "shed"
    assert sheds and sheds[0][0] == victim
    assert sheds[0][1]["where"] == "capacity"
    assert sheds[0][1]["retry_after"] >= 0.5
    st = eng.stats()
    assert st["requests_shed"] == 1
    assert st["deadline_misses"] == 0             # capacity != miss
    # an equal-or-lower-class arrival still bounces typed
    with pytest.raises(QueueFull):
        eng.add_request(np.arange(1, 6), max_new_tokens=2, priority=0)
    done = eng.run_until_done()
    assert vip in done


def test_deadline_exceeded_typed_at_submission(tiny_model):
    """A request submitted with its budget already spent raises the
    typed DeadlineExceeded (the front door's 504) and is counted as a
    deadline miss."""
    from paddle_tpu.serving import DeadlineExceeded

    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8)
    with pytest.raises(DeadlineExceeded):
        eng.add_request(np.arange(1, 6), max_new_tokens=2, slo_ms=-5.0)
    st = eng.stats()
    assert st["deadline_misses"] == 1 and st["requests_shed"] == 1


def test_retry_after_estimate_bounds(tiny_model):
    """The computed Retry-After (queue depth / drain rate) is pinned to
    [0.5s, 30s], falls back to 1s before any finish history exists, and
    rides QueueFull.retry_after_s."""
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8,
                                max_queue=0)
    assert eng._retry_after_estimate() == 1.0     # no history yet
    eng._finish_interval_ewma = 1000.0
    assert eng._retry_after_estimate() == 30.0    # clamped high
    eng._finish_interval_ewma = 1e-6
    assert eng._retry_after_estimate() == 0.5     # clamped low
    eng._finish_interval_ewma = 2.0
    assert eng._retry_after_estimate() == 2.0     # (depth 0 + 1) * 2s
    eng.add_request(np.arange(1, 6), max_new_tokens=20)
    eng.step()
    with pytest.raises(QueueFull) as ei:
        eng.add_request(np.arange(1, 6), max_new_tokens=2)
    assert 0.5 <= ei.value.retry_after_s <= 30.0
    assert ei.value.retry_after_s == 2.0
    eng.run_until_done()


def test_finish_interval_estimator_updates(tiny_model):
    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8)
    for _ in range(3):
        eng.add_request(np.arange(1, 6), max_new_tokens=2)
    eng.run_until_done()
    assert eng._finish_interval_ewma is not None
    assert eng._finish_interval_ewma > 0
    assert eng._ttft_admit_floor is not None and eng._ttft_admit_n >= 3


def test_http_504_on_queued_deadline_expiry(tiny_model):
    """The HTTP surface of a deadline shed: a queued request whose
    budget runs out answers a REAL 504 with code=deadline_exceeded on
    both the batch and the streaming path (SSE headers are deferred, so
    the status line is real) — never a silent stall."""
    from paddle_tpu.serving_http import CompletionServer

    m = tiny_model
    # a LONG holder stream keeps the single slot busy for the whole
    # probe sequence (the tiny model decodes ~ms/token — a short hold
    # would free the slot between probes and race the sheds away)
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=256, page_size=8)
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
        resp.readline()            # slot definitely held

        def post(body, headers=None):
            c = http.client.HTTPConnection(host, port, timeout=120)
            h = {"Content-Type": "application/json"}
            h.update(headers or {})
            c.request("POST", "/v1/completions", json.dumps(body), h)
            r = c.getresponse()
            data = json.loads(r.read())
            c.close()
            return r.status, data

        st, data = post({"prompt_token_ids": [5, 6], "max_tokens": 2,
                         "slo_ms": 40.0})
        assert st == 504 and data["code"] == "deadline_exceeded", data
        st, data = post({"prompt_token_ids": [5, 6], "max_tokens": 2,
                         "slo_ms": 40.0, "stream": True})
        assert st == 504 and data["code"] == "deadline_exceeded", data
        # deadline header: already-spent budget answers 504 at the door
        st, data = post({"prompt_token_ids": [5, 6], "max_tokens": 2},
                        headers={"X-Request-Deadline": "-100"})
        assert st == 504 and data["code"] == "deadline_exceeded", data
        # malformed header is a 400, not a stall or a 500
        st, data = post({"prompt_token_ids": [5, 6], "max_tokens": 2},
                        headers={"X-Request-Deadline": "soon"})
        assert st == 400
        rest = resp.read()
        assert b"[DONE]" in rest   # the holder stream finished clean
        holder.close()


def test_deadline_header_wins_over_body_slo(tiny_model):
    """X-Request-Deadline carries the REMAINING budget from the router
    and must override the body's original slo_ms: a request whose body
    SLO would instantly shed completes when the header grants budget."""
    from paddle_tpu.serving_http import CompletionServer

    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=2, max_len=64, page_size=8)
    with CompletionServer(eng) as srv:
        host, port = srv.address
        c = http.client.HTTPConnection(host, port, timeout=120)
        c.request("POST", "/v1/completions",
                  json.dumps({"prompt_token_ids": [1, 2, 3], 
                              "max_tokens": 2, "slo_ms": 0.001}),
                  {"Content-Type": "application/json",
                   "X-Request-Deadline": "30000"})
        r = c.getresponse()
        data = json.loads(r.read())
        c.close()
        assert r.status == 200, data


def test_read_incident_prints_admission_shed_section(
        tiny_model, tmp_path, recorder, capsys):
    """scripts/read_incident.py surfaces the shed trail as its own
    ADMISSION / SHED section."""
    import importlib.util

    m = tiny_model
    eng = ContinuousBatchEngine(m, max_batch=1, max_len=64, page_size=8)
    rep = frec.IncidentReporter(str(tmp_path))
    rep.register_engine("decoder", eng)
    eng.add_request(np.arange(1, 6), max_new_tokens=20)
    eng.step()
    rid = eng.add_request(np.arange(1, 8), max_new_tokens=2,
                          slo_ms=20.0)
    time.sleep(0.04)
    eng.run_until_done()
    assert eng.finish_reason(rid) == "shed"
    path = rep.activate().dump("manual", context="shed-test")
    spec = importlib.util.spec_from_file_location(
        "_read_incident_shed",
        os.path.join(_REPO, "scripts", "read_incident.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.main([path]) == 0
    out = capsys.readouterr().out
    assert "ADMISSION / SHED" in out
    # the module-shared ring may carry sheds from earlier tests; this
    # test's expired shed must be counted and its rid listed
    assert "expired=" in out
    assert f"rid={rid}" in out
