"""One decode step in flight (serving.py ``_step_decode``): the engine
enqueues step N + 1 before it fetches and retires step N, the program
carries the engine's lengths, and what the host learns a step late (eos,
a stop token, a cancel) is discarded, never delivered.

The oracle is the SAME engine held to the synchronous loop it ran before:
its ``_enqueue_decode`` refuses to enqueue while a step is unfetched, so
every step is enqueued ``mode="drained"`` and fetched before the next one
is built. Tokens and logprobs must agree bit for bit, and with solo
``generate()`` where the case is greedy."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving as S
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import catalog as cat
from paddle_tpu.serving import ContinuousBatchEngine


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=2))


def _prompt(n, seed=0, vocab=512):
    return np.random.RandomState(100 + seed).randint(1, vocab, (n,))


def _solo(m, p, n, **kw):
    return m.generate(paddle.to_tensor(np.asarray(p)[None]),
                      max_new_tokens=n, **kw).numpy()[0]


def _counters():
    read = lambda c, **kw: c.labels(engine="decoder", **kw).value  # noqa: E731
    return {"rows": read(cat.SERVING_DECODE_ROWS),
            "cached": read(cat.SERVING_DECODE_CACHED_TOKENS),
            "ahead": read(cat.SERVING_DECODE_DISPATCH, mode="ahead"),
            "drained": read(cat.SERVING_DECODE_DISPATCH, mode="drained"),
            "discarded": read(cat.SERVING_DECODE_DISCARDED_ROWS)}


class _Run:
    """One drive of a scenario: what every request streamed (tokens and
    logprobs in order, the ``done`` flag of the last), what ``step()``
    returned, the finish reasons, the counters' deltas."""

    def __init__(self, eng, sync):
        self.eng = eng
        self.streamed, self.done, self.rids = {}, {}, []
        self._before = _counters()
        if sync:
            enqueue = eng._enqueue_decode
            eng._enqueue_decode = lambda: (
                None if eng._in_flight is not None else enqueue())

    def add(self, ids, n, **kw):
        def on_token(rid, tok, done, lp):
            self.streamed.setdefault(rid, []).append((tok, lp, done))

        rid = self.eng.add_request(ids, max_new_tokens=n, logprobs=True,
                                   on_token=on_token, **kw)
        self.rids.append(rid)
        return rid

    def step(self, n=1):
        for _ in range(n):
            self.done.update(self.eng.step())

    def finish(self):
        eng = self.eng
        while eng._queue or eng.num_active or eng._chunking:
            self.step()
        self.done.update(eng._drain_finished())
        assert eng._in_flight is None
        self.delta = {k: v - self._before[k]
                      for k, v in _counters().items()}
        return self

    def results(self):
        return {i: (self.done.get(rid, np.zeros(0)).tolist(),
                    self.streamed.get(rid, []),
                    self.eng.finish_reason(rid),
                    self.eng.logprobs(rid))
                for i, rid in enumerate(self.rids)}


# ---- the scenarios ---------------------------------------------------------
# each: (engine kwargs, drive(run, model) -> None); greedy unless it says so

def _staggered(run, m):
    for i, n in enumerate((5, 11, 3)):
        run.add(_prompt(n, i), 6 + i)
    run.step(3)
    run.add(_prompt(7, 3), 5)          # admitted under a step in flight
    run.step(2)
    run.add(_prompt(17, 4), 9)


def _eos_in_flight(run, m):
    run.add(_prompt(4, 10), 8)          # hits the engine's eos at token 3
    run.add(_prompt(6, 11), 8)
    run.add(_prompt(5, 12), 4)          # refills the slot eos frees


def _stop_in_flight(run, m):
    p = _prompt(4, 10)
    run.add(p, 8, stop_token_ids=[int(_solo(m, p, 8)[2]), 10 ** 6])
    run.add(_prompt(6, 11), 8)


def _max_len_edge(run, m):
    # 27 + 5 == max_len 32: the last token's K/V lands in the last row of
    # the slot's last page; the free row of the step after must not write
    # at position 32 (the first page of slot 1's neighbour)
    run.add(_prompt(27, 20), 5)
    run.add(_prompt(9, 21), 12)
    run.step(6)
    run.add(_prompt(28, 22), 4)         # 28 + 4 == 32 in the freed slot


def _per_row(run, m):
    # a key is drawn per enqueued step, and both loops enqueue the same
    # steps for requests that start together: the same samples
    paddle.seed(1234)
    run.add(_prompt(5, 30), 7, do_sample=True, temperature=0.9, top_k=8)
    run.add(_prompt(9, 31), 5)          # a greedy row in the per-row program
    run.add(_prompt(4, 32), 6, do_sample=True, temperature=1.3, top_p=0.8)


def _per_row_staggered(run, m):
    # admitted under a step in flight, a request joins one step (one key)
    # later than in the synchronous loop: its samples differ from that
    # loop's, and repeat run to run
    _per_row(run, m)
    run.step(2)
    run.add(_prompt(6, 33), 5, do_sample=True, temperature=1.1)


def _chunked(run, m):
    run.add(_prompt(6, 40), 10)
    run.step(2)
    run.add(_prompt(29, 41), 6)         # 4 chunks of 8 beside the decode
    run.add(_prompt(21, 42), 4)


def _cancel_in_flight(run, m):
    keep = run.add(_prompt(7, 50), 8)
    dead = run.add(_prompt(6, 51), 8)
    run.add(_prompt(5, 52), 4)          # queued; refills the freed slot
    run.step(2)
    assert run.eng.cancel(dead) is True
    run.cancelled_after = len(run.streamed.get(dead, []))
    assert keep in run.rids


def _eos_of(m):
    return int(_solo(m, _prompt(4, 10), 8)[2])


SCENARIOS = {
    "staggered_mixed_lengths": (dict(max_batch=2, max_len=64), _staggered),
    "eos_in_flight": (dict(max_batch=2, max_len=64, eos="eos"),
                      _eos_in_flight),
    "stop_token_in_flight": (dict(max_batch=2, max_len=64), _stop_in_flight),
    "prompt_plus_new_equals_max_len": (dict(max_batch=2, max_len=32),
                                       _max_len_edge),
    "per_row_sampling": (dict(max_batch=3, max_len=64), _per_row),
    "chunked_prefill": (dict(max_batch=3, max_len=64,
                             prefill_chunk_tokens=8), _chunked),
    "cancel_in_flight": (dict(max_batch=2, max_len=64), _cancel_in_flight),
}


def _drive(m, name, sync):
    kw, scenario = SCENARIOS[name] if isinstance(name, str) else name
    kw = dict(kw, page_size=8)
    if kw.pop("eos", None):
        kw["eos_token_id"] = _eos_of(m)
    run = _Run(ContinuousBatchEngine(m, **kw), sync)
    scenario(run, m)
    return run.finish()


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_tokens_and_logprobs_equal_the_synchronous_loop(model, name):
    ahead, sync = _drive(model, name, False), _drive(model, name, True)
    assert ahead.results() == sync.results()
    # the synchronous drive never ran ahead; the other nearly always did
    assert sync.delta["ahead"] == 0 and sync.delta["discarded"] == 0
    assert ahead.delta["ahead"] > ahead.delta["drained"]
    # only delivered rows are counted: both drives decoded the same
    assert ahead.delta["rows"] == sync.delta["rows"] == sum(
        len(toks) for toks, *_ in ahead.results().values()
        if toks) + getattr(ahead, "cancelled_after", 0)
    assert ahead.delta["cached"] == sync.delta["cached"]
    for toks, streamed, reason, lps in ahead.results().values():
        if reason == "cancelled":
            continue
        assert [t for t, _, _ in streamed] == toks
        assert [lp for _, lp, _ in streamed] == lps
        assert [d for _, _, d in streamed] == [False] * (len(toks) - 1) + [
            True]


def test_sampled_tokens_repeat_run_to_run(model):
    scenario = (dict(max_batch=3, max_len=64), _per_row_staggered)
    first = _drive(model, scenario, False).results()
    again = _drive(model, scenario, False).results()
    assert first == again and len(first) == 4
    assert all(reason == "length" for _, _, reason, _ in first.values())


@pytest.mark.parametrize("name", ["staggered_mixed_lengths",
                                  "prompt_plus_new_equals_max_len",
                                  "chunked_prefill"])
def test_greedy_tokens_equal_solo_generate(model, name):
    run = _drive(model, name, False)
    # (prompt length, prompt seed, new tokens) as the scenario adds them
    seeds = {"staggered_mixed_lengths": [(5, 0, 6), (11, 1, 7), (3, 2, 8),
                                         (7, 3, 5), (17, 4, 9)],
             "prompt_plus_new_equals_max_len": [(27, 20, 5), (9, 21, 12),
                                                (28, 22, 4)],
             "chunked_prefill": [(6, 40, 10), (29, 41, 6), (21, 42, 4)]}
    for rid, (n, seed, new) in zip(run.rids, seeds[name]):
        np.testing.assert_array_equal(run.done[rid],
                                      _solo(model, _prompt(n, seed), new))


@pytest.mark.parametrize("name,discarded", [("eos_in_flight", 1),
                                            ("stop_token_in_flight", 1),
                                            ("cancel_in_flight", 1),
                                            ("staggered_mixed_lengths", 0)])
def test_a_row_learnt_late_is_discarded_and_counted(model, name, discarded):
    """eos, a stop token and a cancel are learnt while the next step is
    in flight: that row is computed, never delivered, and counted; a
    finish by length is known beforehand and computes nothing in vain."""
    run = _drive(model, name, False)
    assert run.delta["discarded"] == discarded
    if name == "eos_in_flight":
        solo = _solo(model, _prompt(4, 10), 8)
        assert run.done[run.rids[0]].tolist() == solo[:3].tolist()
        assert run.eng.finish_reason(run.rids[0]) == "stop"
    if name == "cancel_in_flight":
        dead = run.rids[1]
        assert dead not in run.done
        # nothing of the step in flight at the cancel reached the client
        assert len(run.streamed.get(dead, [])) == run.cancelled_after == 2


def test_every_call_retires_one_step_and_the_last_leaves_none(model):
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    before = _counters()
    rid = eng.add_request(_prompt(5), max_new_tokens=4)
    finished = {}
    for i in range(4):
        assert eng._n_steps == i
        finished.update(eng.step())
        assert eng._n_steps == i + 1
        # one step stays in flight until the last finish is known
        assert (eng._in_flight is not None) == (i < 3)
        assert (rid in finished) == (i == 3)
    assert eng.num_active == 0 and eng.step() == {}
    got = {k: v - before[k] for k, v in _counters().items()}
    assert (got["drained"], got["ahead"], got["discarded"]) == (1, 3, 0)


def test_a_steady_step_enqueues_only_the_key_split_and_the_program(
        model, monkeypatch):
    """No admission, no finish: the engine's own module issues no ``jnp``
    call (no upload of a mask, no eager update of the lengths); the
    lengths it holds are the decode program's output."""
    eng = ContinuousBatchEngine(model, max_batch=4, max_len=64, page_size=8)
    for i, n in enumerate((5, 9, 3)):
        eng.add_request(_prompt(n, i), max_new_tokens=12)
    eng.step()
    eng.step()
    calls, programs = [], []
    real_jnp = S.jnp

    class Recorder:
        def __getattr__(self, name):
            real = getattr(real_jnp, name)
            if not callable(real):
                return real

            def spy(*a, **kw):
                calls.append(name)
                return real(*a, **kw)
            return spy

    step = S._get_select_decode(model, 64, *eng._sample_cfg)
    jitted = step._jitted

    def counted(*a, **kw):
        programs.append("decode_step")
        return jitted(*a, **kw)

    monkeypatch.setattr(step, "_jitted", counted)
    monkeypatch.setattr(S, "jnp", Recorder())
    inputs = eng._step_inputs
    for _ in range(3):
        eng.step()
        assert eng._step_inputs is inputs       # nothing re-uploaded
        assert eng._lengths is eng._caches[0]["lengths"]
    monkeypatch.undo()
    assert calls == [] and programs == ["decode_step"] * 3
    lengths = np.asarray(eng._lengths)
    # three delivered tokens + two steps at the top + the one in flight
    assert lengths.tolist() == [5 + 6, 9 + 6, 3 + 6, 0]


def test_a_speculative_engine_stays_synchronous(model):
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8,
                                speculative_k=3)
    before = _counters()
    prompts = [_prompt(5, 60), _prompt(9, 61)]
    rids = [eng.add_request(p, max_new_tokens=7) for p in prompts]
    done = {}
    while eng.num_active:
        done.update(eng.step())
        assert eng._in_flight is None
    got = {k: v - before[k] for k, v in _counters().items()}
    assert got["ahead"] == 0 and got["drained"] == eng._n_steps > 0
    for rid, p in zip(rids, prompts):
        assert eng.finish_reason(rid) == "length"
    # a sampling request makes the one-token step what runs: it runs
    # ahead, and the switch back to speculation drains first
    paddle.seed(7)
    greedy = eng.add_request(prompts[0], max_new_tokens=9)
    eng.add_request(prompts[1], max_new_tokens=3, do_sample=True,
                    temperature=0.8)
    out = dict(done)
    while eng.num_active:
        out.update(eng.step())
    out.update(eng._drain_finished())
    got = {k: v - before[k] for k, v in _counters().items()}
    assert got["ahead"] > 0 and eng._in_flight is None
    np.testing.assert_array_equal(out[greedy], _solo(model, prompts[0], 9))
    np.testing.assert_array_equal(out[rids[0]], _solo(model, prompts[0], 7))


def test_export_slot_between_steps_drains_first(model):
    """Migration reads the caches and the tokens between two steps: the
    step in flight is retired first, so the bundle holds every token that
    was decoded, and the stream continues token-identically elsewhere."""
    p = _prompt(6, 70)
    src = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    dst = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    rid = src.add_request(p, max_new_tokens=10)
    src.add_request(_prompt(4, 71), max_new_tokens=10)
    for _ in range(3):
        src.step()
    assert src._in_flight is not None
    bundle = src.export_slot(rid)
    # three steps retired and the fourth, in flight, drained
    assert len(bundle["tokens"]) == 4
    assert int(bundle["kv_len"]) == p.size + 4
    moved = dst.admit_migrated(bundle)
    done = dst.run_until_done()
    np.testing.assert_array_equal(done[moved], _solo(model, p, 10))
    rest = src.run_until_done()
    assert len(rest) == 1 and src._in_flight is None


def test_the_blame_record_covers_the_step_in_flight(model):
    """An admission's prefill is dispatched while a decode step is still
    on the device: the deathnote names that step's requests too."""
    armed = []

    class Note:
        def arm(self, rids):
            armed.append(list(rids))

        def clear(self):
            armed.append([])

    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    eng.deathnote = Note()
    a = eng.add_request(_prompt(5, 80), max_new_tokens=8, request_id="a")
    eng.step()
    assert eng._in_flight is not None and a == 0
    del armed[:]
    eng.add_request(_prompt(7, 81), max_new_tokens=2, request_id="b")
    assert armed == [["b", "a"]]
    eng.run_until_done()
    assert armed[-1] == []      # erased once nothing is in flight
