"""End-to-end Llama pretraining loop on paddle_tpu.

Run (CPU smoke): JAX_PLATFORMS=cpu python examples/train_llama.py
On a TPU pod the same script scales by enlarging the topology degrees —
GSPMD inserts the collectives from the sharding annotations.
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
from paddle_tpu import optimizer as opt
from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM


def main():
    tiny = os.environ.get("JAX_PLATFORMS") == "cpu"
    cfg = LlamaConfig.tiny(num_hidden_layers=2) if tiny else LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=2048, use_flash_attention=True,
        dtype="bfloat16")
    seq, batch = (32, 2) if tiny else (2048, 4)

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    optimizer = opt.AdamW(3e-4, parameters=model.parameters(),
                          weight_decay=0.1)

    # one fused XLA computation: forward + backward + AdamW, donated buffers
    step = paddle.jit.train_step(
        model, lambda m, x, y: m(x, labels=y)[0], optimizer)

    rng = np.random.RandomState(0)
    for it in range(5):
        ids = rng.randint(0, cfg.vocab_size, (batch, seq + 1))
        loss = step(paddle.to_tensor(ids[:, :-1]),
                    paddle.to_tensor(ids[:, 1:]))
        print(f"step {it}: loss {float(loss.numpy()):.4f}")

    # checkpoint + resume
    paddle.save(model.state_dict(), "/tmp/llama_example.pdparams")
    model2 = LlamaForCausalLM(cfg)
    model2.set_state_dict(paddle.load("/tmp/llama_example.pdparams"))
    print("checkpoint round-trip OK")


if __name__ == "__main__":
    main()
