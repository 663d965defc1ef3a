"""``reference/cohere2_moe.py`` to the contract, as ``test_reference.py``
does for ``decoder``: on the CPU at the configuration's own rehearsal
sizes, float32 on both sides, so the program and the reference agree to
rounding; the program as it is (the comparison passes it), the CONTROL
(the reference from fp8 or int8 weights in the program's place) and
planted faults (a dropped shared expert, a window one short, a routed
expert zeroed, one expert too few a token, a wrong routing that is no
near-tie) that the harness's own comparison must catch, also with its
limits drawn down to these widths, where the near-tie rule engages
(``lib/planted.py``; ``tools/planted_faults.py`` reads the same at the
cell's own size on the chip)."""
import os

import numpy as np
import pytest

from benchmarks.lib import build, common, planted, serve

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def cell():
    cfg = common.rehearsed(common.load_json(os.path.join(
        BENCH, "configs", "command-a-plus-ep8-d4.json")), True)
    ref = common.load_module(
        os.path.join(BENCH, "reference", "cohere2_moe.py"),
        "bench_reference_cohere2_moe_test")
    import paddle_tpu as paddle

    model = build.build_model(cfg, 3)
    rng = np.random.RandomState(0)
    for name, p in model.named_parameters():
        if "norm" in name:   # all ones at init: make them matter
            p.set_value(paddle.to_tensor(
                (1.0 + 0.2 * rng.standard_normal(p.shape)).astype("float32")))
    return cfg, ref, ref.Spec.from_config(cfg), model


def answers(cfg, model, prompts):
    """What ``/v1/completions`` would answer: the engine's own tokens and
    logprobs, ``CHECK_TOKENS`` a prompt."""
    from paddle_tpu.serving import ContinuousBatchEngine

    engine = ContinuousBatchEngine(model, **cfg["recipe"]["engine"])
    rids = [engine.add_request(np.asarray(p), logprobs=True,
                               max_new_tokens=serve.CHECK_TOKENS)
            for p in prompts]
    done = {}
    while len(done) < len(rids):
        done.update(engine.step())
    return [{"token_ids": [int(t) for t in done[r]],
             "logprobs": list(engine._finished_logprobs[r])} for r in rids]


def test_contract_and_refusals(cell):
    cfg, ref, spec, _ = cell
    for name in ("Spec", "forward_logprobs", "serve_flops_per_token",
                 "paged_decode_cost", "matmul_params"):
        assert hasattr(ref, name), name
    assert hash(spec) is not None
    assert spec.held == (0, 4) and spec.routed_experts == 8
    with pytest.raises(ValueError):
        ref.Spec.from_config(dict(cfg, model_type="mistral"))
    with pytest.raises(ValueError):
        ref.Spec.from_config(dict(cfg, use_parallel_block=False))
    with pytest.raises(ValueError):
        ref.Spec.from_config(dict(cfg, held_experts=[0, 3]))


def test_reference_logprobs_equal_the_models(cell):
    import jax

    import paddle_tpu as paddle

    cfg, ref, spec, model = cell
    ids = np.random.RandomState(1).randint(1, cfg["vocab_size"], 70)
    got = np.asarray(ref.forward_logprobs(
        spec, build.plain_state(model), ids, last=70))
    logits = model(paddle.to_tensor(ids[None]))._array[0]
    want = np.asarray(jax.nn.log_softmax(logits.astype("float32"), -1))
    assert np.abs(got - want).max() < 2e-4


#: the harness's limit, drawn down to the rehearsal's widths (the head's
#: logits have a std of 0.2 here, so no fault reaches 0.15 nats and the
#: near-tie rule never engages): 20 times the sound program's rounding
SCALED_TOL = 1e-4


@pytest.fixture(scope="module")
def served(cell):
    """Four prompts, the engine's own answers, and what the program as
    it is reads: rounding (float32 on both sides here), standing on no
    alternate routing."""
    cfg, ref, spec, model = cell
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg["vocab_size"], n).tolist()
               for n in (24, 42, 75, 100)]
    replies = answers(cfg, model, prompts)
    ok, compared, rows = serve.compare_logprobs(
        ref, spec, build.plain_state(model), prompts, replies)
    sound = compared["logprob_err_nats"]["value"]
    assert ok and sound < 5e-6 and len(rows) == 4
    assert compared["tokens_on_alternate_routing"]["value"] == 0
    assert compared["logprob_err_nats_own_routing"]["value"] == sound
    return prompts, replies, sound


@pytest.mark.parametrize("limits", ["as_they_are", "drawn_down_to_the_widths"])
@pytest.mark.parametrize("what", ("none",) + planted.LOWER_PRECISIONS
                         + planted.FAULTS)
def test_control_and_planted_faults(cell, served, what, limits, monkeypatch):
    """``compare_logprobs`` on the program as it is, on the contract's
    CONTROL (the reference itself from fp8 / int8 weights, put in the
    program's place on the same prompts and tokens) and on the engine's
    own answers with each fault of ``lib/planted.py`` planted on the
    reference's side: a window of 31 for 32, top-1 for top-2, the second
    shared expert dropped, a routed held expert zeroed. The control and
    every fault read at least a thousand times what the program does. (At
    these widths none reaches the harness's bf16 limit of 0.15 nats; what
    the first set of cases pins is that the comparison SEES each, by
    orders of magnitude.) With the limit drawn down to the widths a
    faulty token IS over it and is read again on its position's
    near-ties: the rule excuses neither the control nor a fault, and the
    program still passes on the reference's own routing alone."""
    _, ref, spec, model = cell
    prompts, replies, sound = served
    if limits == "drawn_down_to_the_widths":
        monkeypatch.setattr(serve, "LOGPROB_TOL", SCALED_TOL)
    state = build.plain_state(model)
    judge = planted.plant(what, spec, state) if what in planted.FAULTS \
        else (spec, state)
    if what in planted.LOWER_PRECISIONS:
        replies = planted.control_answers(ref, spec, state, what, prompts,
                                          replies)
    ok, compared, _ = serve.compare_logprobs(ref, *judge, prompts, replies)
    worst = compared["logprob_err_nats"]["value"]
    if what == "none":
        assert ok and worst == sound
        assert compared["tokens_on_alternate_routing"]["value"] == 0
    else:
        assert worst > 1000 * sound and worst > 20 * SCALED_TOL
        assert ok is (limits == "as_they_are")
        assert compared["tokens_on_alternate_routing"]["value"] \
            <= serve.ALT_TOKENS_MAX
        if limits == "drawn_down_to_the_widths":
            # what tells the control and a fault from a flip: MANY tokens
            assert compared["tokens_over_limit_own_routing"]["value"] \
                > serve.ALT_TOKENS_MAX


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_the_controls_fp8_is_the_cast_it_stands_for(dtype):
    """``planted.fp8_e4m3`` rounds by arithmetic, because the TPU compiler
    drops a convert to a narrower float and back: it has to equal
    ``ml_dtypes``' cast to float8_e4m3fn under the same scale, ties and
    the subnormal range included, and move a weight by ~2.7% rms."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    rng = np.random.RandomState(7)
    for scale in (2e-2, 1e-3, 1.0, 37.0):
        w = jnp.asarray(rng.standard_normal((48, 96)) * scale, dtype)
        w = w.at[0, :8].set(jnp.asarray(
            [136, 42, -272, 348, 1e-5, -3e-4, 0, 0.5], dtype) * scale / 90)
        wide = np.asarray(w.astype(jnp.float32))
        s = np.float32(2.0) ** np.frexp(np.abs(wide).max()
                                        / np.float32(448))[1]
        want = (wide / s).astype(ml_dtypes.float8_e4m3fn).astype(
            np.float32) * s
        got = np.asarray(jax.jit(planted.fp8_e4m3)(w).astype(jnp.float32))
        assert got.dtype == np.float32 and np.array_equal(got, want)
        moved = np.sqrt(((got - wide) ** 2).mean() / (wide ** 2).mean())
        assert 0.02 < moved < 0.035


def test_a_held_swap_passes_at_a_near_tie_and_fails_away_from_one(
        cell, served, monkeypatch):
    """The reference itself as a stand-in engine that routed ONE position
    the other way: the last answered token of a prompt, read with one
    held swap forced at its own position. Where that swap's logit gap is
    under ``ROUTING_TIE_GAP`` the comparison finds it among the
    alternatives and passes with one token on an alternate routing; the
    same kind of swap at a gap over the constant is a wrong routing, and
    fails. (The constant is set between the two gaps found here; the
    limit is drawn down to the widths, or no flip would be over it.)"""
    _, ref, spec, model = cell
    prompts, replies, _ = served
    state = build.plain_state(model)
    monkeypatch.setattr(serve, "LOGPROB_TOL", SCALED_TOL)

    def stand_in(i, swap):
        """Prompt ``i``'s answer as an engine would give it that took
        ``swap`` at the last answered token's position."""
        toks = replies[i]["token_ids"]
        ids = prompts[i] + toks[:-1]
        _, chosen, _ = ref.forward_hidden(spec, state, ids)
        told = ref.swapped(np.asarray(chosen[swap["layer"], len(ids) - 1]),
                           swap)
        lp = np.asarray(ref.forward_logprobs(
            spec, state, ids, last=len(toks),
            forced={(swap["layer"], len(ids) - 1): told}))
        out = list(replies)
        out[i] = {"token_ids": toks, "logprobs": [
            float(v) for v in lp[np.arange(len(toks)), toks]]}
        return out

    def flip_size(i, swap):
        was = np.asarray(replies[i]["logprobs"])
        return float(np.abs(np.asarray(stand_in(i, swap)[i]["logprobs"])
                            - was).max())

    # a prompt whose last position has two held swaps that each move its
    # token by more than the limit: the nearer is the near-tie
    for i, prompt in enumerate(prompts):
        ids = prompt + replies[i]["token_ids"][:-1]
        _, chosen, logits = ref.forward_hidden(spec, state, ids)
        swaps = [s for s in ref.near_tie_swaps(
            spec, chosen[:, -1], logits[:, -1], tie_gap=np.inf)
            if flip_size(i, s) > 10 * SCALED_TOL][:2]
        if len(swaps) == 2 and swaps[0]["gap"] < swaps[1]["gap"]:
            break
    else:
        pytest.fail("no prompt ends on two held swaps that are seen")
    near, far = swaps
    monkeypatch.setattr(ref, "ROUTING_TIE_GAP",
                        (near["gap"] + far["gap"]) / 2.0)
    # what is on offer there: ONE held swap each, nearest tie first, few
    offered = ref.near_tie_alternatives(spec, state, ids, len(ids) - 1)
    assert 1 <= len(offered) <= ref.ALTERNATIVES_MAX <= 3
    assert all(len(a["swaps"]) == 1 == len(a["forced"]) for a in offered)
    gaps = [a["swaps"][0]["gap"] for a in offered]
    assert gaps == sorted(gaps) and offered[0]["swaps"] == [near]

    ok, compared, rows = serve.compare_logprobs(ref, spec, state, prompts,
                                                stand_in(i, near))
    assert ok and compared["tokens_on_alternate_routing"]["value"] == 1
    assert compared["logprob_err_nats"]["value"] < 5e-6
    assert compared["logprob_err_nats_own_routing"]["value"] \
        == pytest.approx(flip_size(i, near), abs=1e-6)
    (token,) = rows[i]["alternate_routing"]
    assert token["token"] == serve.CHECK_TOKENS - 1
    assert token["tried"][-1]["swaps"] == [near] and token["err"] < 5e-6

    ok, compared, rows = serve.compare_logprobs(ref, spec, state, prompts,
                                                stand_in(i, far))
    assert not ok and compared["tokens_on_alternate_routing"]["value"] == 0
    assert compared["logprob_err_nats"]["value"] \
        == pytest.approx(flip_size(i, far), abs=1e-6)
    assert compared["prompts_failed"]["value"] == 1
    assert all(far not in t["swaps"]
               for t in rows[i]["alternate_routing"][0]["tried"])


def test_flops_and_bytes_arithmetic():
    """At the published widths, by hand."""
    cfg = common.load_json(os.path.join(
        BENCH, "configs", "command-a-plus-ep8-d4.json"))
    ref = common.load_module(
        os.path.join(BENCH, "reference", "cohere2_moe.py"),
        "bench_reference_cohere2_moe_test2")
    spec = ref.Spec.from_config(cfg)
    attn = 4096 * 128 * (2 * 128 + 2 * 8)
    layer = attn + 3 * 4096 * 4096 * (4 + 1.0) + 4096 * 128
    assert ref.matmul_params(spec) == 4 * layer + 4096 * 32768
    # a 7680-token prompt: 3840 keys on average on the global layer, and
    # on a window layer the chord 3840 x 4096 / 8192 = 1920 (3004 in truth)
    assert ref.attended_keys(spec, 3840.0) == 3840.0 + 3 * 1920.0
    per_token = ref.serve_flops_per_token(spec, 3840.0, sampled_share=0.0)
    assert per_token == pytest.approx(
        2 * 4 * layer + 4 * 128 * 128 * (3840 + 3 * 1920), rel=1e-12)
    # never more than the true count, whatever the lengths
    for n in (2047, 4145, 7680):
        true = sum(min(i + 1, 4096) for i in range(n)) / n
        assert ref.attended_keys(spec, (n + 1) / 2.0) \
            <= (n + 1) / 2.0 + 3 * true
    peaks = {"flops_bf16_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = ref.roofline_seconds(
        ref.paged_decode_cost(spec, 32 * 5000.0, 32.0), peaks)
    assert bound == "memory"
    assert least == pytest.approx(32 * 5000 * 4096 / 819e9, rel=0.01)


def test_the_cell_names_this_reference_and_reports_the_whole_steps_share():
    """``commandaplus_rag_batch`` as ``BENCHMARK.json`` enters it: judged on
    ``tokens_per_s`` against this reference, with ``serve_mfu`` (the whole
    step's share of the peak) and the expert layer's own readers."""
    cell = common.Cell("commandaplus_rag_batch")
    assert cell.config["reference"]["module"] == "cohere2_moe"
    assert cell.reference().__name__.endswith("cohere2_moe")
    e2e = {m["name"] for m in cell.metrics("end_to_end")}
    assert e2e == {"tokens_per_s", "peak_hbm_gib", "setup_s"}
    reported = {m["name"] for m in cell.metrics("per_layer")}
    assert {"serve_mfu", "moe_held_pairs_per_token", "ragged_dot_roofline",
            "kv_pool_reserved_gib", "rows_over_window_share"} <= reported
    assert "paged_attention_roofline" not in reported   # moves itl_p95_ms
