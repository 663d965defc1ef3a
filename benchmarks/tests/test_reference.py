"""The plain float32 reference against the program's own models at tiny
widths on the CPU: Mistral (pre-norm, GQA) and OLMo 2 (post-norm,
RMSNorm over the whole q and k width). Both in float32, so they must
agree to rounding."""
import numpy as np
import pytest

from benchmarks.reference import decoder

TINY = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
            num_hidden_layers=2, num_attention_heads=4,
            max_position_embeddings=256, rms_norm_eps=1e-5,
            tie_word_embeddings=False)


def build(family):
    import paddle_tpu as paddle

    paddle.seed(3)
    if family == "mistral":
        from paddle_tpu.models.mistral import (MistralConfig,
                                               MistralForCausalLM)

        cfg = dict(TINY, num_key_value_heads=2, rope_theta=1e6,
                   reference={"block": "pre_norm", "qk_norm": None})
        model = MistralForCausalLM(MistralConfig(
            **{k: v for k, v in cfg.items() if k != "reference"},
            sliding_window=None, dtype="float32"))
    else:
        from paddle_tpu.models.olmo2 import Olmo2Config, Olmo2ForCausalLM

        cfg = dict(TINY, num_key_value_heads=4, rope_theta=5e5,
                   rms_norm_eps=1e-6,
                   reference={"block": "post_norm", "qk_norm": "full"})
        model = Olmo2ForCausalLM(Olmo2Config(
            **{k: v for k, v in cfg.items() if k != "reference"},
            dtype="float32"))
    return cfg, model


@pytest.mark.parametrize("family", ["mistral", "olmo2"])
def test_reference_logprobs_equal_the_models(family):
    import jax

    import paddle_tpu as paddle

    cfg, model = build(family)
    # norm weights are all ones at init: make them matter
    rng = np.random.RandomState(0)
    for name, p in model.named_parameters():
        if "norm" in name:
            p.set_value(paddle.to_tensor(
                (1.0 + 0.2 * rng.standard_normal(p.shape)).astype("float32")))
    spec = decoder.Spec.from_config(cfg)
    state = {k: v._array for k, v in model.state_dict().items()}
    ids = rng.randint(1, cfg["vocab_size"], 37)
    ref = np.asarray(decoder.forward_logprobs(spec, state, ids, last=37))
    hidden = model.llama(paddle.to_tensor(ids[None, :]))
    logits = np.asarray(model.lm_head_logits(hidden).numpy(), np.float32)[0]
    want = np.asarray(jax.nn.log_softmax(logits, axis=-1))
    assert np.abs(ref - want).max() < 2e-4
    loss = decoder.next_token_loss(spec, state, ids)
    got = float(model(paddle.to_tensor(ids[None, :-1]),
                      labels=paddle.to_tensor(ids[None, 1:]))[0].numpy())
    assert abs(loss - got) < 1e-4


def test_flops_and_bytes_arithmetic():
    spec = decoder.Spec.from_config({
        "vocab_size": 100352, "hidden_size": 2048, "intermediate_size": 8192,
        "num_hidden_layers": 6, "num_attention_heads": 16,
        "num_key_value_heads": 16, "rms_norm_eps": 1e-6, "rope_theta": 5e5,
        "tie_word_embeddings": False,
        "reference": {"block": "post_norm", "qk_norm": "full"}})
    assert spec.head_dim == 128
    assert decoder.matmul_params(spec) == 6 * (4 * 2048 * 2048
                                               + 3 * 2048 * 8192) \
        + 2048 * 100352
    per_token = decoder.train_flops_per_token(spec, 4096)
    assert per_token == pytest.approx(3.95e9, rel=0.01)
    peaks = {"flops_bf16_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    least, bound = decoder.roofline_seconds(
        decoder.flash_train_cost(spec, 2, 4096), peaks)
    assert bound == "compute" and least == pytest.approx(0.0126, rel=0.05)
    least, bound = decoder.roofline_seconds(
        decoder.paged_decode_cost(spec, 8000.0, 10.0), peaks)
    assert bound == "memory"
