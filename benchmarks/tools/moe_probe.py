#!/usr/bin/env python
"""Scratch probe, not the harness: the dropless expert layer's grouped
matmul at the widths of ``command-a-plus-ep8-d4`` (16 held experts of 128,
hidden 4096, expert width 4096, top-8), through ``jax.lax.ragged_dot`` and
through the bundled megablox ``gmm`` at a few tilings, at a prefill's 8192
rows and a decode step's 32. Prints one JSON line a variant (host clock
around ``block_until_ready``, best of the timed calls).

    chiprun --chips 1 -- python benchmarks/tools/moe_probe.py
"""
from __future__ import annotations

import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)


def main() -> int:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox_gmm
    from paddle_tpu.distributed import moe

    h, f, held, routed, k = 4096, 4096, 16, 128, 8
    key = jax.random.PRNGKey(0)
    w1 = (jax.random.normal(key, (held, h, 2 * f), jnp.float32) * 0.02
          ).astype(jnp.bfloat16)
    w2 = (jax.random.normal(key, (held, f, h), jnp.float32) * 0.02
          ).astype(jnp.bfloat16)
    b1 = jnp.zeros((held, 1, 2 * f), jnp.bfloat16)
    b2 = jnp.zeros((held, 1, h), jnp.bfloat16)
    # the layer calls jax.lax.ragged_dot by that name: a variant stands in
    # for it there, for this scratch probe only
    ragged = jax.lax.ragged_dot

    def with_gmm(tiling):
        def fn(x, w, gs):
            tm, tk, tn = tiling
            return megablox_gmm(
                x, w, gs.astype(jnp.int32),
                preferred_element_type=x.dtype,
                tiling=(min(tm, x.shape[0]), min(tk, w.shape[1]),
                        min(tn, w.shape[2])))
        return fn

    variants = [("ragged_dot", ragged)] + [
        (f"gmm{t}", with_gmm(t)) for t in ((128, 128, 128),
                                            (512, 1024, 1024))]
    rng = np.random.RandomState(0)
    out_path = os.path.join(ROOT, "chiprun_out", "moe_probe.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def say(**fields):
        line = json.dumps(fields)
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    for T, real in ((8192, 5900), (32, 32)):
        tokens = jnp.asarray(rng.randn(T, h) * 1.0, jnp.bfloat16)
        scores = rng.rand(T, routed)
        idx = jnp.asarray(np.argsort(-scores, -1)[:, :k], jnp.int32)
        w = jnp.full((T, k), 1.0 / k, jnp.float32)
        valid = jnp.arange(T) < real
        outs = {}
        for name, impl in variants:
            jax.lax.ragged_dot = impl
            # the weights are ARGUMENTS: closed over, 1.6 GB of them become
            # constants of the program and the compile never ends
            fn = jax.jit(lambda t, i, ww, v, a1, c1, a2, c2:
                         moe.dropless_expert_ffn(
                             t, i, ww, a1, c1, a2, c2, "swiglu",
                             held=(0, held), valid=v))
            args = (tokens, idx, w, valid, w1, b1, w2, b2)
            try:
                t0 = time.time()
                out, counts = fn(*args)
                out.block_until_ready()
                compile_s = time.time() - t0
                best = 1e9
                for _ in range(5):
                    t0 = time.time()
                    fn(*args)[0].block_until_ready()
                    best = min(best, time.time() - t0)
                outs[name] = np.asarray(out.astype(jnp.float32))
                err = float(np.abs(outs[name] - outs["ragged_dot"]).max())
                pairs = int(counts.sum())
                say(rows=T, real_rows=real, impl=name,
                    ms=round(best * 1e3, 3), pairs=pairs,
                    tflops=round(6 * pairs * h * f / best / 1e12, 2),
                    weights_gb_per_s=round(
                        held * 3 * h * f * 2 / best / 1e9, 1),
                    compile_s=round(compile_s, 1),
                    max_abs_diff_vs_ragged=err)
            except Exception as e:  # a tiling the kernel refuses
                say(rows=T, impl=name, error=repr(e)[:300])
    jax.lax.ragged_dot = ragged
    return 0


if __name__ == "__main__":
    sys.exit(main())
