"""Serving: batched greedy/sampled decode with the jitted KV cache.

Run: JAX_PLATFORMS=cpu python examples/serve_generate.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def main():
    paddle.seed(0)
    cfg = LlamaConfig.tiny(num_hidden_layers=2)
    model = LlamaForCausalLM(cfg)
    prompts = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (2, 8)))
    # greedy, static-KV jitted decode
    out = model.generate(prompts, max_new_tokens=8)
    print("greedy:", out.shape, out.numpy()[0][-8:])
    # nucleus sampling
    out2 = model.generate(prompts, max_new_tokens=8, do_sample=True,
                          top_p=0.9, temperature=0.8)
    print("sampled:", out2.shape)

    # continuous batching: requests of different lengths admitted
    # mid-flight into a fixed slot pool over ONE paged KV cache
    from paddle_tpu.serving import ContinuousBatchEngine

    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64, page_size=8)
    rng = np.random.RandomState(0)
    rids = [eng.add_request(rng.randint(0, cfg.vocab_size, (n,)),
                            max_new_tokens=6) for n in (5, 9, 3)]
    done = eng.run_until_done()
    for rid in rids:
        print(f"request {rid}: {done[rid].tolist()}")

    # DeepSeek MLA serves through the SAME engine in latent mode: the
    # cache holds the compressed latent (kv_lora_rank + qk_rope_head_dim
    # floats/token) per slot row instead of paged per-head K/V
    from paddle_tpu.models import DeepseekV2Config, DeepseekV2ForCausalLM

    mla = DeepseekV2ForCausalLM(DeepseekV2Config.tiny_mla(
        num_hidden_layers=2))
    eng2 = ContinuousBatchEngine(mla, max_batch=2, max_len=64)
    assert eng2._latent_mode
    rid = eng2.add_request(rng.randint(0, 512, (7,)), max_new_tokens=6)
    print("mla request:", eng2.run_until_done()[rid].tolist())


if __name__ == "__main__":
    main()
