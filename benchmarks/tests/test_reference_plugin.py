"""What the harness does with the reference module a configuration names:
the serving comparison driven with a stub module and no engine, and the
readers that divide by a reference's arithmetic, which return nothing
(and say what they lacked) where the cell's module has no such function."""
import importlib
import json
import types

import numpy as np
import pytest

from benchmarks.lib import serve
from benchmarks.reference import decoder

VOCAB = 50
PROMPT_LENGTHS = (5, 9, 12, 17, 23, 31)


def engine_and_stub(seed=0, alternatives=None):
    """Six prompts with an "engine's" answers, and a stub reference whose
    logprobs at each position are a seeded function of the token before
    it (so it really is teacher-forced on the sequence it is given).
    ``alternatives``: the stub has a router, and ``{(prompt, token): [d0,
    d1 ...]}`` are the alternate routings it offers at that answered
    token's own position: routing ``n`` moves the logprobs read there by
    ``dn``."""
    table = np.log(np.random.RandomState(seed).dirichlet(
        np.ones(VOCAB), size=VOCAB)).astype(np.float32)
    rng = np.random.RandomState(seed + 1)
    prompts = [rng.randint(1, VOCAB, n).tolist() for n in PROMPT_LENGTHS]
    answers = []
    for prompt in prompts:
        toks = rng.randint(1, VOCAB, serve.CHECK_TOKENS).tolist()
        seq = prompt + toks
        answers.append({"token_ids": toks, "logprobs": [
            float(table[seq[len(prompt) + j - 1], toks[j]])
            for j in range(len(toks))]})
    calls = []

    def forward_logprobs(spec, state, ids, last, forced=None):
        calls.append((len(ids), last) if forced is None
                     else (len(ids), last, forced))
        assert spec == "the spec" and state == {"w": 1}
        out = table[np.asarray(ids[-last:])].copy()
        for (layer, position), told in (forced or {}).items():
            moves = offered[(len(ids), position)]
            out[position - (len(ids) - last)] += moves[told[0]]
        return out

    stub = types.SimpleNamespace(forward_logprobs=forward_logprobs)
    if alternatives is not None:
        offered = {(n + serve.CHECK_TOKENS - 1, n - 1 + j): moves
                   for (n, j), moves in alternatives.items()}

        def near_tie_alternatives(spec, state, ids, position):
            return [{"forced": {(0, position): [k]},
                     "swaps": [{"layer": 0, "out": 1, "in": 2, "gap": 0.01}]}
                    for k in range(len(offered.get((len(ids), position),
                                                   ())))]

        stub.near_tie_alternatives = near_tie_alternatives
    return stub, prompts, answers, calls


def compare(stub, prompts, answers):
    """(correct, the worst error after the rule, rows, ``compared``)."""
    ok, compared, rows = serve.compare_logprobs(stub, "the spec", {"w": 1},
                                                prompts, answers)
    assert list(compared)[0] == "logprob_err_nats"
    assert list(compared)[-1] == "prompts_failed"
    assert compared["prompts_failed"] == {
        "value": sum(not r["ok"] for r in rows), "limit": 0}
    return ok, compared["logprob_err_nats"]["value"], rows, compared


def test_comparison_passes_on_the_stubs_own_logprobs():
    stub, prompts, answers, calls = engine_and_stub()
    ok, worst, rows, compared = compare(stub, prompts, answers)
    assert ok and worst < 1e-6
    # a reference without a router: the two numbers it always had
    assert set(compared) == {"logprob_err_nats", "prompts_failed"}
    assert compared["logprob_err_nats"]["limit"] == serve.LOGPROB_TOL
    # four prompts spread over the six, each with all but the last token
    assert [r["prompt_tokens"] for r in rows] == [5, 12, 17, 31]
    assert calls == [(n + serve.CHECK_TOKENS - 1, serve.CHECK_TOKENS)
                     for n in (5, 12, 17, 31)]


@pytest.mark.parametrize("fault", ["one_logprob_off_by_0.2", "short_answer",
                                   "nan"])
def test_comparison_fails(fault):
    stub, prompts, answers, _ = engine_and_stub(seed=4)
    ans = answers[3]                     # the 17-token prompt: a compared one
    if fault == "one_logprob_off_by_0.2":
        ans["logprobs"][5] += 0.2
    elif fault == "short_answer":
        ans["token_ids"], ans["logprobs"] = (ans["token_ids"][:-1],
                                             ans["logprobs"][:-1])
    else:
        ans["logprobs"][0] = float("nan")
    ok, worst, rows, _ = compare(stub, prompts, answers)
    assert not ok
    assert [r["ok"] for r in rows] == [True, True, False, True]
    if fault == "one_logprob_off_by_0.2":
        assert worst == pytest.approx(0.2, abs=1e-5)
        assert worst > serve.LOGPROB_TOL
    # an uncompared prompt's answer does not matter
    stub, prompts, answers, _ = engine_and_stub(seed=4)
    answers[1]["logprobs"][0] += 5.0
    assert compare(stub, prompts, answers)[0]


def test_fewer_than_five_prompts_are_all_compared():
    stub, prompts, answers, _ = engine_and_stub()
    ok, _, rows, _ = compare(stub, prompts[:3], answers[:3])
    assert ok and len(rows) == 3


# ---- a reference with a router: set-valued at its own near-ties ------------

#: case -> ({(prompt length, token): how far the engine is off there},
#: {(prompt length, token): the moves of the alternate routings offered},
#: correct?, tokens standing on an alternate routing, passes of the
#: reference beyond the four first ones, the worst error after the rule)
NEAR_TIES = {
    "stands_on_the_second_alternative": (
        {(17, 5): 0.25}, {(17, 5): [0.6, 0.24, 0.25]}, True, 1, 2, 0.01),
    "no_alternative_at_its_position": (
        {(17, 5): 0.25}, {(17, 4): [0.25]}, False, 0, 0, 0.25),
    "matches_none_of_them": (
        {(17, 5): 0.25}, {(17, 5): [0.6, -0.25, 0.05]}, False, 0, 3, 0.25),
    "over_the_flip_cap_is_not_read_again": (
        {(17, 5): 0.6}, {(17, 5): [0.6]}, False, 0, 0, 0.6),
    "two_tokens_may_stand_so": (
        {(5, 0): 0.2, (31, 7): -0.3}, {(5, 0): [0.2], (31, 7): [-0.3]},
        True, 2, 2, 0.0),
    "with_a_third_token_over_the_limit_none_is_read_again": (
        {(5, 0): 0.2, (12, 3): 0.2, (31, 7): -0.3},
        {(5, 0): [0.2], (12, 3): [0.2], (31, 7): [-0.3]}, False, 0, 0, 0.3),
    "nothing_is_read_again_once_the_run_has_failed": (
        {(5, 0): 0.2, (31, 7): -0.3}, {(31, 7): [-0.3]}, False, 0, 0, 0.3),
    "a_token_within_the_limit_is_never_read_again": (
        {(17, 5): 0.14}, {(17, 5): [0.0]}, True, 0, 0, 0.14),
}


@pytest.mark.parametrize("case", sorted(NEAR_TIES))
def test_routed_reference_reads_both_sides_of_its_own_near_tie(case):
    off, offered, correct, standing, passes, left = NEAR_TIES[case]
    stub, prompts, answers, calls = engine_and_stub(seed=4,
                                                    alternatives=offered)
    for (n, j), delta in off.items():
        answers[PROMPT_LENGTHS.index(n)]["logprobs"][j] += delta
    ok, worst, rows, compared = compare(stub, prompts, answers)
    assert ok is correct
    assert list(compared) == ["logprob_err_nats",
                              "logprob_err_nats_own_routing",
                              "tokens_over_limit_own_routing",
                              "tokens_on_alternate_routing",
                              "prompts_failed"]
    assert compared["tokens_over_limit_own_routing"] == {
        "value": sum(abs(d) > serve.LOGPROB_TOL for d in off.values()),
        "limit": serve.ALT_TOKENS_MAX}
    assert compared["tokens_on_alternate_routing"] == {
        "value": standing, "limit": serve.ALT_TOKENS_MAX}
    own = compared["logprob_err_nats_own_routing"]
    assert own["limit"] == serve.ROUTING_FLIP_CAP
    assert own["value"] == pytest.approx(max(abs(d) for d in off.values()),
                                         abs=1e-5)
    assert worst == pytest.approx(left, abs=1e-5)
    assert len(calls) - 4 == passes
    read_again = [t for r in rows for t in r["alternate_routing"]]
    assert sum(t["err"] is not None for t in read_again) == standing
    for t in read_again:
        if t["err"] is not None:
            assert t["tried"][-1]["swaps"][0] == {
                "layer": 0, "out": 1, "in": 2, "gap": 0.01}
            assert abs(t["tried"][-1]["logprob"]
                       - t["engine_logprob"]) == pytest.approx(t["err"])


# ---- readers and the reference module's arithmetic ------------------------

PEAKS = {"flops_bf16_per_s": 197e12, "hbm_bytes_per_s": 819e9}
MISTRAL = {"vocab_size": 32768, "hidden_size": 4096,
           "intermediate_size": 14336,
           "num_hidden_layers": 20, "num_attention_heads": 32,
           "num_key_value_heads": 8, "head_dim": 128, "rms_norm_eps": 1e-5,
           "rope_theta": 1e6, "tie_word_embeddings": False,
           "reference": {"module": "decoder", "block": "pre_norm",
                         "qk_norm": None}}


CACHED = 'serving_decode_cached_tokens_total{engine="decoder"}'
ROWS = 'serving_decode_rows_total{engine="decoder"}'


def kernel_trace(name):
    return {"kernels": {f"{name} [pallas]": {"total_s": 0.5, "count": 400}}}


def contexts(reference):
    spec = decoder.Spec.from_config(MISTRAL)
    outcome = {"ok": True, "n_prompt": 1000, "n_tokens": 21, "t_first": 10.0,
               "t_end": 14.0}
    base = {"reference": reference, "spec": spec, "peaks": PEAKS, "chips": 1,
            "config": MISTRAL, "seconds": 50.0}
    return {
        "train_mfu": dict(base, kind="train_job", tokens_per_s=29719.0,
                          seq_len=4096),
        "flash_attention_roofline": dict(
            base, kind="train_job", trace=kernel_trace("splash_mha_fwd"),
            traced_steps=10, batch=2, seq_len=4096),
        "paged_attention_roofline": dict(
            base, kind="open_loop", trace=kernel_trace("paged_attention"),
            trace_window=(12.0, 17.0), outcomes=[outcome]),
        "serve_mfu": dict(base, kind="closed_loop",
                          window_outcomes=[outcome] * 250 + [
                              dict(outcome, ok=False)]),
        "decode_step_mfu": dict(
            base, kind="open_loop", engine_args={"max_batch": 16},
            trace={"modules": {"jit_decode_step(7)": {
                "count": 200, "total_s": 3.4, "median_s": 0.0165}}},
            before={"metrics": {CACHED: 1e6, ROWS: 1e3},
                    "stats": {"decode_steps": 100}},
            after={"metrics": {CACHED: 1e6 + 1000 * 4000, ROWS: 1e3 + 8000},
                   "stats": {"decode_steps": 1100}}),
    }


NEEDS = {"train_mfu": "train_flops_per_token",
         "flash_attention_roofline": "flash_train_cost",
         "paged_attention_roofline": "paged_decode_cost",
         "serve_mfu": "serve_flops_per_token",
         "decode_step_mfu": "serve_flops_per_token"}


@pytest.mark.parametrize("metric", sorted(NEEDS))
def test_reader_reads_with_decoder_and_nothing_without_its_function(
        metric, capsys):
    read = importlib.import_module(f"benchmarks.layer_metrics.{metric}").read
    value = read(contexts(decoder)[metric])
    assert value is not None and value > 0.0
    capsys.readouterr()
    bare = types.ModuleType("bench_reference_bare")      # forward only
    bare.forward_logprobs = decoder.forward_logprobs
    assert read(contexts(bare)[metric]) is None
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert {"note": "reader_skipped", "metric": metric,
            "lacks": NEEDS[metric],
            "reference": "bench_reference_bare"} in notes


def test_serve_mfu_counts_what_a_forward_pass_requires():
    spec = decoder.Spec.from_config(MISTRAL)
    params = decoder.matmul_params(spec)
    head = 4096 * 32768
    assert params == 20 * (4096 * 128 * 80 + 3 * 4096 * 14336) + head
    # every token sampled, no context: twice the matrices
    assert decoder.serve_flops_per_token(spec, 0.0) == 2.0 * params
    # a prompt's tokens need no head; attention is 4 flops a key, head
    # width, head and layer
    assert decoder.serve_flops_per_token(spec, 500.0, 0.0) == pytest.approx(
        2.0 * (params - head) + 4 * 20 * 32 * 128 * 500.0)
    mfu = importlib.import_module("benchmarks.layer_metrics.serve_mfu")
    # prompt of 3, 3 tokens drawn: keys 1+2+3, then 4 and 5; 5 enter
    assert mfu.attended_keys(3, 3) == 6 + 4 + 5
    assert mfu.attended_keys(7, 1) == 28
    value = mfu.read(contexts(decoder)["serve_mfu"])
    entered = 250 * 1020
    keys = 250 * mfu.attended_keys(1000, 21)
    want = entered / 50.0 * decoder.serve_flops_per_token(
        spec, keys / entered, 250 * 21 / entered) / 197e12 * 100.0
    assert value == pytest.approx(want) and 20.0 < value < 30.0


def test_decode_step_mfu_is_rows_times_flops_over_the_programs_mean_time():
    spec = decoder.Spec.from_config(MISTRAL)
    read = importlib.import_module(
        "benchmarks.layer_metrics.decode_step_mfu").read
    ctx = contexts(decoder)["decode_step_mfu"]
    # 8 rows a step, 500 cached tokens a row, 17 ms a step on the device
    want = 8 * decoder.serve_flops_per_token(spec, 500.0, 1.0) / (
        0.017 * 197e12) * 100.0
    assert read(ctx) == pytest.approx(want) and 1.0 < want < 5.0
    assert read(dict(ctx, trace={"modules": {}})) is None     # a train step
    assert read(dict(ctx, after=ctx["before"])) is None       # no decode
