"""The build listener (``paddle_tpu.jit.build_events``): every program JAX
builds, counted by stage, program and cause, on the counters, as a
``jit/<stage>`` span and as the flight recorder's ``jit.compile`` event
(docs/SERVING.md "Set-up")."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import paddle_tpu as paddle
from paddle_tpu.jit import build_events
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
from paddle_tpu.observability import catalog as cat
from paddle_tpu.observability import flightrecorder as fr
from paddle_tpu.observability import tracing
from paddle_tpu.serving import ContinuousBatchEngine

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def counts(program=None, cause=None, family=None):
    """{stage: value} of ``jit_builds_total`` (or ``family``) summed over
    the series whose labels match."""
    family = family or cat.JIT_BUILDS
    out = dict.fromkeys(build_events.STAGES, 0.0)
    for key, child in list(family._children.items()):
        labels = dict(zip(family.label_names, key))
        if program not in (None, labels.get("program")) or \
                cause not in (None, labels.get("cause")):
            continue
        out[labels["stage"]] += child.value
    return out


def delta(before, after):
    return {k: after[k] - before[k] for k in before}


def test_a_nested_jit_is_one_build_of_the_outer_program():
    """A fresh ``jit`` that calls another: one trace (the inner one's is
    part of it), one lowering, one compile, all under the outer's name,
    and the seconds of the stages no more than the wall time."""
    c = float(time.time_ns() % 1000003) + 0.25   # misses every cache

    @jax.jit
    def inner(x):
        return x * c + 1.0

    def decode_step(x):
        return inner(x).sum() * 2.0

    x = jnp.ones((4, 3))
    tracer = tracing.get_tracer()
    rec = fr.get_recorder()
    was_on = tracer.enabled
    tracer.enable()
    rec.enable()
    rec.clear()
    b_n = counts("decode_step", "none")
    b_s = counts("decode_step", "none", cat.JIT_BUILD_SECONDS)
    try:
        with tracer.span("test.parent") as parent:
            t0 = time.time()
            jax.jit(decode_step)(x).block_until_ready()
            wall = time.time() - t0
    finally:
        if not was_on:
            tracer.disable()
    n = delta(b_n, counts("decode_step", "none"))
    secs = delta(b_s, counts("decode_step", "none", cat.JIT_BUILD_SECONDS))
    assert n == {"trace": 1, "lower": 1, "compile": 1, "load": 0}
    assert all(secs[s] > 0 for s in ("trace", "lower", "compile"))
    assert sum(secs.values()) <= wall
    spans = [s for s in tracer.spans(parent.trace_id)
             if s["name"].startswith("jit/")]
    mine = [s for s in spans if s["attrs"]["program"] in
            ("decode_step", "jit(decode_step)")]
    assert sorted(s["name"] for s in mine) == ["jit/compile", "jit/lower",
                                               "jit/trace"]
    for s in mine:
        assert s["parent_id"] == parent.span_id
        assert s["attrs"]["cause"] == "none"
        assert s["attrs"]["start_s"] <= s["attrs"]["end_s"]
        assert s["start_ns"] <= s["end_ns"]
    (ev,) = [e for e in rec.events(kind=fr.EV_COMPILE)
             if e["program"] == "jit(decode_step)"]
    assert ev["stage"] == "compile" and ev["seconds"] > 0


_SECOND_PROCESS = r"""
import json, sys
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
import jax.numpy as jnp
import paddle_tpu
from paddle_tpu.observability import catalog as cat

def prefill(x):
    return jnp.tanh(x * 3.0) @ x.T

jax.jit(prefill)(jnp.ones((8, 8))).block_until_ready()
print(json.dumps({s: cat.JIT_BUILDS.value(stage=s, program="prefill",
                                           cause="none")
                  for s in ("trace", "lower", "compile", "load")}))
"""


def test_a_second_process_on_the_cache_loads_and_does_not_compile(tmp_path):
    """Two processes on one persistent cache: the first compiles the
    program, the second reads it back, and both trace and lower it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"),
               PYTHONPATH=ROOT)

    def run():
        out = subprocess.run([sys.executable, "-c", _SECOND_PROCESS],
                             cwd=ROOT, env=env, capture_output=True,
                             text=True, timeout=300)
        assert out.returncode == 0, out.stderr[-2000:]
        return json.loads(out.stdout.strip().splitlines()[-1])

    first, second = run(), run()
    assert first == {"trace": 1, "lower": 1, "compile": 1, "load": 0}
    assert second == {"trace": 1, "lower": 1, "compile": 0, "load": 1}


def test_an_engine_names_the_admission_and_the_initializers():
    """Model construction builds under ``param_init`` and adds to
    ``nn_param_init_seconds_total``; a tiny engine's warm-up builds its
    prefill and scatter programs under ``engine/admit``, its decode step
    under ``engine/dispatch``."""
    init_s = cat.NN_PARAM_INIT_SECONDS.value()
    init = counts(cause="param_init")
    admit = counts(cause="engine/admit")
    dispatch = counts("decode_step", "engine/dispatch")
    paddle.seed(0)
    # widths no other test uses: every initializer and program is new
    model = LlamaForCausalLM(LlamaConfig.tiny(
        num_hidden_layers=1, vocab_size=263, hidden_size=48,
        intermediate_size=88, num_attention_heads=2,
        num_key_value_heads=2))
    assert cat.NN_PARAM_INIT_SECONDS.value() > init_s
    assert delta(init, counts(cause="param_init"))["trace"] > 0
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64,
                                page_size=8)
    eng.profiler.enable()
    before = {p: counts(p, "engine/admit") for p in ("prefill",
                                                     "kv_scatter")}
    for n in (5, 19):
        eng.add_request(np.random.RandomState(n).randint(1, 200, (n,)),
                        max_new_tokens=3)
    while eng.num_active or eng._queue:
        eng.step()
    for p in ("prefill", "kv_scatter"):
        d = delta(before[p], counts(p, "engine/admit"))
        assert d["compile"] + d["load"] >= 1, (p, d)
    d = delta(dispatch, counts("decode_step", "engine/dispatch"))
    assert d["compile"] + d["load"] == 1
    assert sum(delta(admit, counts(cause="engine/admit")).values()) > 0


@pytest.mark.parametrize("raw,label", [
    ("engine/admit", "engine/admit"), ("engine/prefill", "engine/admit"),
    ("engine/dispatch", "engine/dispatch"),
    ("engine/draft", "engine/dispatch"), ("engine/sync", "engine/retire"),
    ("engine/retire", "engine/retire"), ("param_init", "param_init"),
    (None, "none"), ("engine/submissions", "none")])
def test_a_cause_is_filed_under_one_of_five_labels(raw, label):
    assert build_events._FOLD.get(raw, "none") == label
    assert label in build_events.CAUSES


@pytest.mark.parametrize("name,label", [
    ("jit(decode_step)", "decode_step"), ("prefill", "prefill"),
    ("jit(prefill_ragged)", "other"), ("jit(kv_scatter)", "kv_scatter"),
    ("train_step", "train_step"), ("jit(_threefry_split)", "other")])
def test_a_program_keeps_its_role_name_or_is_other(name, label):
    assert build_events._program(name) == label


def test_a_phase_clock_sets_the_threads_cause():
    from paddle_tpu.observability.perf import PhaseClock

    clk = PhaseClock()
    clk.begin()
    clk.open("dispatch")
    assert build_events.set_cause("engine/dispatch") == "engine/dispatch"
    clk.open("retire")
    assert build_events._LOCAL.cause == "engine/retire"
    clk.end()
    assert build_events._LOCAL.cause is None
