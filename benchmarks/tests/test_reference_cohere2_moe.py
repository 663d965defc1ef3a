"""``reference/cohere2_moe.py`` to the contract, as ``test_reference.py``
does for ``decoder``: on the CPU at the configuration's own rehearsal
sizes, float32 on both sides, so the program and the reference agree to
rounding; a control (the comparison passes the program as it is) and two
planted faults (a dropped shared expert, a window one short) that the
harness's own comparison, with its own limit, must catch."""
import os

import numpy as np
import pytest

from benchmarks.lib import build, common, serve

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


def test_control_and_planted_faults(cell):
    """``compare_logprobs`` on the engine's own answers: the program as it
    is reads rounding (float32 on both sides here); a shared expert
    dropped from the REFERENCE's weights, or a window of 31 for 32, reads
    at least a thousand times that. (At these widths the head's logits
    have a std of 0.2, so no fault reaches the harness's bf16 limit of
    0.15 nats; what the test pins is that the comparison SEES each
    fault, by orders of magnitude.)"""
    cfg, ref, spec, model = cell
    rng = np.random.RandomState(2)
    prompts = [rng.randint(1, cfg["vocab_size"], n).tolist()
               for n in (24, 42, 75, 100)]
    replies = answers(cfg, model, prompts)
    state = build.plain_state(model)
    ok, control, rows = serve.compare_logprobs(ref, spec, state, prompts,
                                               replies)
    assert ok and control < 5e-6 and len(rows) == 4

    _, short_window, _ = serve.compare_logprobs(
        ref, spec._replace(sliding_window=31), state, prompts, replies)
    assert short_window > 1000 * control and short_window > 5e-3

    f = spec.intermediate_size
    dropped = dict(state)
    for layer in range(spec.num_hidden_layers):
        key = f"llama.layers.{layer}.mlp.shared_expert.down_proj.weight"
        dropped[key] = state[key].at[f:].set(0.0)     # expert 1 of 2 gone
    _, no_expert, _ = serve.compare_logprobs(ref, spec, dropped, prompts,
                                             replies)
    assert no_expert > 1000 * control and no_expert > 5e-3


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
