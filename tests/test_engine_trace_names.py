"""The engine step and every program under stable names on the
profiler's clock (docs/SERVING.md "Tracing"): ``engine/<phase>``
annotations that agree with ``PhaseClock``, a role name for every jitted
program and Pallas kernel, and the counters at admission, prefill and
decode."""
import glob
import http.client
import json
import logging
import re
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import catalog as cat
from paddle_tpu.observability import perf
from paddle_tpu.serving import ContinuousBatchEngine, Seq2SeqBatchEngine

F32 = jnp.float32


def _tiny_model(layers=2):
    paddle.seed(0)
    return LlamaForCausalLM(LlamaConfig.tiny(num_hidden_layers=layers))


def _engine(model=None, **kw):
    kw = {"max_batch": 2, "max_len": 64, "page_size": 8, **kw}
    return ContinuousBatchEngine(model or _tiny_model(), **kw)


def _prompt(n, seed=0):
    return np.random.RandomState(seed).randint(1, 200, (n,))


# ---- (a) the annotations, on the profiler's clock -------------------------

def _complete(addr, n_prompt, max_tokens):
    conn = http.client.HTTPConnection(*addr, timeout=120)
    try:
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt_token_ids": _prompt(n_prompt, n_prompt).tolist(),
             "max_tokens": max_tokens}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        resp.read()
        assert resp.status == 200
    finally:
        conn.close()


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """A few steps of a tiny engine behind ``CompletionServer`` under a
    ``jax.profiler`` session: ({thread line: [(start, end, name)]} of the
    ``engine/`` annotations, the profiler's committed steps)."""
    from paddle_tpu.serving_http import CompletionServer

    eng = _engine()
    out = str(tmp_path_factory.mktemp("trace"))
    with CompletionServer(eng, model_name="tiny-trace", enable_tracing=False,
                          enable_flight_recorder=False,
                          enable_timeseries=False) as srv:
        _complete(srv.address, 6, 3)           # warm: compiles outside
        _complete(srv.address, 11, 3)
        eng.profiler.recent.clear()
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(out, profiler_options=options)
        try:
            # four requests on two slots: two are admitted inside a step
            asks = [threading.Thread(target=_complete,
                                     args=(srv.address, n, 6))
                    for n in (6, 11, 6, 11)]
            for th in asks:
                th.start()
            for th in asks:
                th.join()
            # the last token streams from inside the last step: let that
            # step end before the session does
            time.sleep(0.3)
        finally:
            jax.profiler.stop_trace()
        steps = list(eng.profiler.recent)
    (pb,) = glob.glob(f"{out}/plugins/profile/*/*.xplane.pb")
    lines = {}
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for n, line in enumerate(plane.lines):
            evs = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
                   for e in line.events if e.name.startswith("engine/")]
            if evs:
                lines[f"{plane.name}:{line.name}#{n}"] = sorted(evs)
    return lines, steps


def _inside(ev, outer):
    return any(s <= ev[0] and ev[1] <= e for s, e, _ in outer)


def test_engine_spans_sit_on_one_thread_inside_a_step(traced):
    lines, steps = traced
    assert len(lines) == 1, sorted(lines)       # the engine thread's line
    (evs,) = lines.values()
    names = {name for _, _, name in evs}
    used = {ph for rec in steps for ph in rec["phases"]}
    assert used >= {"admit", "prefill", "dispatch", "sync", "retire"}
    assert names >= {f"engine/{ph}" for ph in used} | {
        "engine/step", "engine/submissions"}
    step_spans = [ev for ev in evs if ev[2] == "engine/step"]
    for ev in evs:
        if ev[2] in {f"engine/{ph}" for ph in perf.PHASES}:
            assert _inside(ev, step_spans), ev


def test_prefill_dispatch_is_nested_in_admit(traced):
    (evs,) = traced[0].values()
    nested = [ev for ev in evs if ev[2] == "engine/admit/prefill_dispatch"]
    assert nested                      # requests admitted inside a step
    admits = [ev for ev in evs if ev[2] == "engine/admit"]
    assert all(_inside(ev, admits) for ev in nested)
    # a request that finds a slot free is admitted while the loop drains
    # its submission: the same span, outside any phase
    direct = [ev for ev in evs if ev[2] == "engine/prefill_dispatch"]
    subs = [ev for ev in evs if ev[2] == "engine/submissions"]
    assert direct and all(_inside(ev, subs) for ev in direct)


def test_annotations_agree_with_the_phase_clock(traced):
    (evs,) = traced[0].values()
    steps = traced[1]
    step_spans = [ev for ev in evs if ev[2] == "engine/step"]
    # committed steps (idle steps return before the commit) in order
    full = [sp for sp in step_spans
            if any(ev[2] == "engine/sync" and _inside(ev, [sp])
                   for ev in evs)]
    assert len(full) == len(steps) > 3
    for sp, rec in zip(full, steps):
        got = {}
        for s, e, name in evs:
            if name.count("/") == 1 and name != "engine/step" \
                    and _inside((s, e, name), [sp]):
                got[name[7:]] = got.get(name[7:], 0.0) + (e - s) * 1e-6
        assert set(got) == set(rec["phases"])
        for ph, ms in rec["phases"].items():
            assert got[ph] == pytest.approx(ms, abs=1.0), (ph, got, rec)


# ---- (b) with no session the clock accumulates as before ------------------

def test_open_close_accumulate_like_lap_with_no_session():
    clk = perf.PhaseClock()
    clk.begin(7)
    for phase in ("admit", "prefill", "dispatch", "sync", "retire", "admit"):
        clk.open(phase)
    clk.close()
    assert list(clk.phases) == ["admit", "prefill", "dispatch", "sync",
                                "retire"]
    assert clk.total() == pytest.approx(sum(clk.phases.values()), abs=1e-12)
    clk.end()
    clk.begin()                       # the old protocol is still whole
    clk.lap("admit")
    assert list(clk.phases) == ["admit"]


def test_an_early_return_or_a_raise_leaves_no_span_open():
    eng = _engine()
    eng.profiler.enable()
    clk = eng.profiler.clock
    assert eng.step() == {}                      # idle: early return
    assert clk._phase is None and clk._step is None
    eng.add_request(_prompt(5), 4, on_token=lambda *a: 1 / 0)
    with pytest.raises(ZeroDivisionError):
        eng.run_until_done()
    assert clk._phase is None and clk._step is None


# ---- (c) every jitted program runs under its role's name ------------------

class _Compiled(logging.Handler):
    """The programs JAX compiles, by name, while the handler listens."""

    def __init__(self):
        super().__init__(level=logging.WARNING)
        self.names = set()

    def emit(self, record):
        m = re.search(r"^Compiling jit\((\w+)\)", record.getMessage())
        if m:
            self.names.add(m.group(1))


def _flow_engine():
    eng = _engine()
    eng.add_request(_prompt(5), 3)               # padded to bucket 8
    eng.add_request(_prompt(8), 3)               # fills its bucket
    eng.run_until_done()


def _flow_engine_rows():
    eng = _engine()
    eng.add_request(_prompt(5), 3, do_sample=True, temperature=0.7)
    eng.run_until_done()


def _flow_engine_speculative():
    eng = _engine(speculative_k=3)
    eng.add_request(np.tile(_prompt(4), 3), 6)
    eng.run_until_done()


def _flow_engine_prefix_cache():
    eng = _engine(enable_prefix_cache=True)
    shared = _prompt(16)
    eng.add_request(np.concatenate([shared, _prompt(3, 1)]), 6)
    eng.add_request(np.concatenate([shared, _prompt(4, 2)]), 3)
    eng.run_until_done()
    assert eng.prefix_pages_reused > 0


def _flow_seq2seq():
    from paddle_tpu.models.whisper import (WhisperConfig,
                                           WhisperForConditionalGeneration)

    paddle.seed(0)
    m = WhisperForConditionalGeneration(WhisperConfig.tiny())
    eng = Seq2SeqBatchEngine(m, max_batch=2, max_decode_len=16,
                             max_encoder_len=16)
    eng.add_request(np.random.RandomState(0).randn(8, 32).astype(np.float32),
                    max_new_tokens=3)
    eng.run_until_done()


def _flow_generate():
    model = _tiny_model()
    x = paddle.to_tensor(_prompt(6)[None])
    model.generate(x, max_new_tokens=3)                       # the scan
    model.generate(x, max_new_tokens=3, eos_token_id=0)       # host loop
    model.generate(x, max_new_tokens=3, num_beams=2)
    model.generate(paddle.to_tensor(_prompt(8)[None]), max_new_tokens=2,
                   prefill_chunk_size=4)


def _flow_train_and_to_static():
    from paddle_tpu import optimizer as opt

    model = _tiny_model(1)
    step = paddle.jit.train_step(
        model, lambda m, x, y: m(x, labels=y)[0],
        opt.AdamW(1e-3, parameters=model.parameters()))
    ids = _prompt(9)[None]
    step(paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:]))
    paddle.jit.to_static(model)(paddle.to_tensor(ids))


FLOWS = {
    "engine": (_flow_engine, {"decode_step", "prefill", "kv_scatter"}),
    "engine_per_row_sampling": (_flow_engine_rows, {"decode_step_rows"}),
    "engine_speculative": (_flow_engine_speculative, {"spec_verify"}),
    "engine_prefix_cache": (_flow_engine_prefix_cache,
                            {"prefill_with_prefix"}),
    "seq2seq_engine": (_flow_seq2seq, {"seq2seq_decode_step"}),
    "generate": (_flow_generate, {"decode_scan", "decode_step_solo",
                                  "beam_step", "prefill_scan"}),
    "train_and_to_static": (_flow_train_and_to_static,
                            {"train_step", "to_static_forward"}),
}


@pytest.mark.parametrize("flow", sorted(FLOWS))
def test_every_program_is_compiled_under_its_role_name(flow):
    run, expected = FLOWS[flow]
    seen = _Compiled()
    logger = logging.getLogger("jax")
    logger.addHandler(seen)
    try:
        with jax.log_compiles():
            run()
    finally:
        logger.removeHandler(seen)
    assert expected <= seen.names, sorted(seen.names)
    assert not {"pure", "pure_step"} & seen.names
    if flow.startswith("engine"):
        # ONE prefill program a bucket: a right-padded prompt under causal
        # attention needs no pad mask, so the engine builds none
        assert not {"prefill_ragged", "prefill_embeds_ragged",
                    "prefill_mask"} & seen.names, sorted(seen.names)


def test_no_two_roles_share_a_program_name():
    names = [n for _, expected in FLOWS.values() for n in expected]
    assert len(names) == len(set(names))
    assert all(n.startswith("decode_step") for n in names
               if n in ("decode_step", "decode_step_rows",
                        "decode_step_solo"))


# ---- (d) every Pallas kernel carries its name -----------------------------

def _pallas_names(jaxpr, out):
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            out.append(eqn.params["name"])
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (list, tuple)) else [v]):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _pallas_names(inner, out)
    return out


def _kernel_calls():
    from paddle_tpu.ops.pallas import (append_attention, decode_tail,
                                       fused_norm, kv_page_write, mla_decode)

    b, hid, h, hk, d, t = 8, 256, 2, 1, 128, 128
    z = lambda *shape, dtype=F32: jnp.zeros(shape, dtype)  # noqa: E731
    return {
        "rms_norm": (lambda x, w: fused_norm.rms_norm(x, w, 1e-5),
                     (z(1, 16, hid), z(hid))),
        "add_rms_norm": (lambda x, r, w: fused_norm.add_rms_norm(
            x, r, w, 1e-5), (z(1, 16, hid), z(1, 16, hid), z(hid))),
        "fused_rope": (fused_norm.fused_rope,
                       (z(1, 16, h, d), z(16, d), z(16, d))),
        "append_attention": (
            append_attention.append_attention,
            (z(1, 16, h, d), z(1, t, hk, d), z(1, t, hk, d),
             jnp.asarray(0, jnp.int32), jnp.ones((1, t), bool))),
        "decode_tail_qkv_rope": (
            lambda x, wn, wq, wk, wv, c, s: decode_tail.fused_qkv_rope(
                x, wn, wq, wk, wv, c, s, 1e-5, h, hk, d),
            (z(b, hid), z(hid), z(hid, h * d), z(hid, hk * d),
             z(hid, hk * d), z(b, d), z(b, d))),
        "decode_tail_epilogue": (
            lambda a, wo, r, wn: decode_tail.fused_epilogue(
                a, wo, r, wn, 1e-5),
            (z(b, h * d), z(h * d, hid), z(b, hid), z(hid))),
        "mla_decode": (
            mla_decode.mla_decode_attention,
            (z(b, h, 128), z(b, h, 128), z(b, t, 128), z(b, t, 128),
             jnp.zeros((b,), jnp.int32), jnp.ones((b, t), bool))),
        "kv_page_write": (
            kv_page_write.kv_page_write,
            (z(hk, b, 16, d), jnp.arange(b, dtype=jnp.int32),
             jnp.zeros((b,), jnp.int32), z(b, hk, d))),
    }


@pytest.mark.parametrize("kernel", [
    "rms_norm", "add_rms_norm", "fused_rope", "append_attention",
    "decode_tail_qkv_rope", "decode_tail_epilogue", "mla_decode",
    "kv_page_write"])
def test_every_pallas_call_carries_its_kernel_name(kernel):
    from paddle_tpu.ops.pallas import backend

    fn, args = _kernel_calls()[kernel]
    with backend.lowering_target("tpu"):
        jaxpr = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    assert _pallas_names(jaxpr.jaxpr, []) == [kernel]


# ---- (e) the counters ------------------------------------------------------

def _counters():
    read = lambda m, **kw: m.labels(engine="decoder", **kw).value  # noqa: E731
    return {"rows": read(cat.SERVING_DECODE_ROWS),
            "cached": read(cat.SERVING_DECODE_CACHED_TOKENS),
            "prompt": read(cat.SERVING_PREFILL_TOKENS, kind="prompt"),
            "bucket": read(cat.SERVING_PREFILL_TOKENS, kind="bucket")}


def _delta(before):
    return {k: v - before[k] for k, v in _counters().items()}


def test_decode_and_prefill_counters_equal_the_hand_sum():
    eng = _engine()
    before = _counters()
    eng.add_request(_prompt(5), 4)      # bucket 8
    eng.add_request(_prompt(11), 2)     # bucket 16
    eng.run_until_done()
    got = _delta(before)
    # the first token comes from the prefill's logits in decode step 1,
    # so a request of n new tokens decodes in n steps with
    # prompt + 0 .. prompt + n - 1 rows cached
    assert got["rows"] == 4 + 2
    assert got["cached"] == sum(5 + i for i in range(4)) + sum(
        11 + i for i in range(2))
    assert (got["prompt"], got["bucket"]) == (5 + 11, 8 + 16)


def test_prefix_hit_counts_the_suffix_it_computes():
    eng = _engine(enable_prefix_cache=True)
    shared = _prompt(16)
    before = _counters()
    eng.add_request(np.concatenate([shared, _prompt(3, 1)]), 6)   # 19 -> 32
    eng.add_request(np.concatenate([shared, _prompt(4, 2)]), 2)   # 4 -> 8
    eng.run_until_done()
    got = _delta(before)
    assert eng.prefix_pages_reused == 2
    assert (got["prompt"], got["bucket"]) == (19 + 4, 32 + 8)


def test_queue_wait_and_ttft_say_where_they_start():
    for metric in (cat.SERVING_QUEUE_WAIT, cat.SERVING_TTFT):
        assert "add_request" in metric.help
