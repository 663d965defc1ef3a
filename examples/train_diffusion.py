"""Latent-diffusion training + sampling (the DiT / SD3 workload family).

Two recipes in one script:
- ``--model dit``: class-conditional DiT with the DDPM eps objective and
  DDIM sampling (classifier-free guidance via the null class).
- ``--model sd3``: text-conditioned MMDiT with the rectified-flow objective
  and Euler flow sampling (text context here is random features standing in
  for a frozen text encoder).

Both train through ``paddle.jit.train_step`` — one donated XLA computation
per step — and sample with a single ``lax.scan`` dispatch. Scale-out is the
same as any model: wrap with ``fleet.distributed_model`` + ``parallelize``
under a hybrid topology (see examples/distributed_hybrid.py).

Run (CPU smoke):
  JAX_PLATFORMS=cpu python examples/train_diffusion.py --model dit --steps 20
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np

import paddle_tpu as paddle
import paddle_tpu.optimizer as opt


def train_dit(steps: int):
    from paddle_tpu.models.sd3 import (cfg_label_dropout, ddpm_eps_loss,
                                       sample_ddim)
    from paddle_tpu.vision.models import AutoencoderKL, VAEConfig
    from paddle_tpu.vision.models.dit import DiT, DiTConfig

    paddle.seed(0)
    vae = AutoencoderKL(VAEConfig.tiny())          # frozen in this recipe
    model = DiT(DiTConfig.tiny())
    optimizer = opt.AdamW(1e-4, parameters=model.parameters())

    def loss_fn(m, z, y):
        y = cfg_label_dropout(y, m.config.num_classes, prob=0.1)
        return ddpm_eps_loss(m, z, y)

    step = paddle.jit.train_step(model, loss_fn, optimizer)
    rng = np.random.RandomState(0)
    for i in range(steps):
        images = paddle.to_tensor(rng.rand(8, 3, 16, 16).astype("float32"))
        labels = paddle.to_tensor(rng.randint(0, 10, (8,)).astype("int64"))
        z = vae.scale_latents(vae.encode(images).sample())
        loss = step(z, labels)
        if i % 5 == 0 or i == steps - 1:
            print(f"dit step {i}: loss={float(loss.numpy()):.4f}")

    # CFG sampling: null class = num_classes
    y = paddle.to_tensor(np.arange(4, dtype="int64") % 10)
    null = paddle.to_tensor(np.full((4,), 10, dtype="int64"))
    lat = sample_ddim(model, (4, 4, 8, 8), y, steps=8,
                      guidance_scale=3.0, uncond=(null,))
    images = vae.decode(vae.unscale_latents(lat))
    print("dit samples:", tuple(images.shape))


def train_sd3(steps: int):
    from paddle_tpu.models.sd3 import (MMDiT, MMDiTConfig,
                                       rectified_flow_loss, sample_flow)

    paddle.seed(0)
    model = MMDiT(MMDiTConfig.tiny())
    optimizer = opt.AdamW(1e-4, parameters=model.parameters())
    step = paddle.jit.train_step(
        model, lambda m, z, c, p: rectified_flow_loss(m, z, c, p), optimizer)
    rng = np.random.RandomState(0)
    for i in range(steps):
        z = paddle.to_tensor(rng.randn(8, 4, 8, 8).astype("float32"))
        ctx = paddle.to_tensor(rng.randn(8, 6, 32).astype("float32"))
        pool = paddle.to_tensor(rng.randn(8, 16).astype("float32"))
        loss = step(z, ctx, pool)
        if i % 5 == 0 or i == steps - 1:
            print(f"sd3 step {i}: loss={float(loss.numpy()):.4f}")

    ctx = paddle.to_tensor(rng.randn(4, 6, 32).astype("float32"))
    pool = paddle.to_tensor(rng.randn(4, 16).astype("float32"))
    lat = sample_flow(model, (4, 4, 8, 8), ctx, pool, steps=8)
    print("sd3 latents:", tuple(lat.shape))


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", choices=["dit", "sd3"], default="dit")
    ap.add_argument("--steps", type=int, default=20)
    args = ap.parse_args()
    (train_dit if args.model == "dit" else train_sd3)(args.steps)
