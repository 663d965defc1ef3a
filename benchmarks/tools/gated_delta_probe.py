#!/usr/bin/env python
"""Scratch probe, not the harness: the chunked gated delta prefill kernel
(``ops/pallas/gated_delta.py``, trace name ``gated_delta_prefill``) alone,
at the widths of ``olmohybrid_chat_batch``'s linear layers (one row, 30
heads, dk 96, dv 192), to say where its time goes.

Cases: buckets 128, 512 and 1024, the prompt filling the bucket and at
60% of it (the rest pads, as admission pads a prompt to its bucket).

Variants, where the kernel has the knobs (a tree without them runs
``kernel`` alone): ``kernel`` as the engine calls it, then
``heads=<n>:solve=<m>``: n heads a grid step (``auto``: the kernel's own
``prefill_heads_per_block``) and the chunk's diagonal blocks of m rows
solved by substitution (1: all six levels of doubling on the MXU). The
variants ``heads=1:solve=1`` (pad chunks skipped, nothing else),
``heads=auto:solve=1`` and ``kernel`` split a gain into its three parts.

Each variant is timed on the host clock around ``block_until_ready`` of
one program that makes 20 calls in a loop, each from the state the last
left (best of three; the host's dispatch is out of the time). One JSON line a
(case, variant): ``ms`` a call, ``us_per_chunk_head`` (ms over the real
chunks times the heads), ``roofline_pct`` (the least time of one layer's
``gated_delta_prefill_cost`` for the real tokens, from the cell's
reference module, at the chip's peaks, over ``ms``), ``max_abs_err``
of o (real tokens) and the final state against the XLA composite, and
whether o is exactly 0 in the chunks past the prompt (``pads_zero``).

    python benchmarks/tools/gated_delta_probe.py            # one TPU chip
    python benchmarks/tools/gated_delta_probe.py --tree .committed_tree/p
    python benchmarks/tools/gated_delta_probe.py --rehearse  # the CPU

``--tree`` imports ``paddle_tpu`` from another checkout (the parent),
with this file's cases and cost. ``--rehearse``: tiny widths, the kernel
interpreted, on the CPU; its times mean nothing.
"""
from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "benchmarks", "configs", "olmo-hybrid-7b-d16.json")
VARIANTS = ("kernel", "heads=1:solve=1", "heads=auto:solve=1",
            "heads=auto:solve=16", "heads=6:solve=8")


def inputs(T, H, dk, dv, seed=0):
    """Unit-norm q (scaled) and k, decays ~0.2 to ~0.999, write strengths
    in [0, 2) as the configuration's negative eigenvalues allow."""
    import numpy as np

    rng = np.random.RandomState(seed)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.randn(1, T, H, dk)) * dk ** -0.5
    k = unit(rng.randn(1, T, H, dk))
    v = rng.randn(1, T, H, dv)
    g = -rng.uniform(0.001, 1.6, (1, T, H))
    beta = rng.uniform(0.0, 2.0, (1, T, H))
    h0 = 0.1 * rng.randn(1, H, dv, dk)
    return [np.asarray(x, np.float32) for x in (q, k, v, g, beta, h0)]


def repeated(gd, reps, interpret, q, k, v, g, beta, h0, lengths):
    """``reps`` calls in one program, each from the state the last one
    left, so that the host's dispatch is out of the time."""
    import jax

    return jax.lax.fori_loop(
        0, reps, lambda _, h: gd.gated_delta_prefill(
            q, k, v, g, beta, h, lengths, interpret=interpret)[1], h0)


def patched(gd, variant, heads):
    """Set the kernel's knobs for ``variant``; returns a function that puts
    them back, or None where this tree's kernel has no such knob or the
    variant's heads a block do not divide ``heads``."""
    if variant == "kernel":
        return lambda: None
    if not hasattr(gd, "prefill_heads_per_block"):
        return None
    fields = dict(part.split("=") for part in variant.split(":"))
    if fields["heads"] != "auto" and heads % int(fields["heads"]):
        return None
    import jax

    own = (gd.prefill_heads_per_block, gd._SOLVE_BLOCK)
    if fields["heads"] != "auto":
        n = int(fields["heads"])
        gd.prefill_heads_per_block = lambda *a, **kw: n
    gd._SOLVE_BLOCK = int(fields["solve"])
    jax.clear_caches()      # the kernel's call is jitted: trace it anew

    def undo():
        gd.prefill_heads_per_block, gd._SOLVE_BLOCK = own
        jax.clear_caches()

    return undo


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tree", default=None,
                    help="import paddle_tpu from this checkout")
    ap.add_argument("--label", default=None)
    ap.add_argument("--variants", nargs="*", default=list(VARIANTS))
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args()
    tree = os.path.abspath(args.tree) if args.tree else ROOT
    sys.path[:0] = [tree, ROOT]

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import common
    from benchmarks.reference._costs import roofline_seconds
    from paddle_tpu.ops.pallas import gated_delta as gd

    cfg = common.rehearsed(common.load_json(CONFIG), args.rehearse)
    ref = common.load_module(os.path.join(ROOT, "benchmarks", "reference",
                                          "olmo_hybrid.py"),
                             "probe_reference_olmo_hybrid")
    spec = ref.Spec.from_config(cfg)
    H, dk, dv = spec.linear_heads, spec.dk, spec.dv
    kind = jax.devices()[0].device_kind
    peaks = common.load_json(os.path.join(ROOT, "benchmarks", "peaks.json"))
    peaks = peaks["device_kinds"].get(kind) or \
        peaks["device_kinds"]["TPU v5 lite"]
    buckets = (64, 128) if args.rehearse else (128, 512, 1024)
    interpret = True if args.rehearse else None
    reps = 2 if args.rehearse else 20

    label = args.label or ("tree" if tree == ROOT else
                           os.path.basename(tree.rstrip("/")))
    out_path = os.path.join(ROOT, "chiprun_out", "gated_delta_probe.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def say(**fields):
        line = json.dumps(dict(label=label, **fields))
        print(line, flush=True)
        with open(out_path, "a") as f:
            f.write(line + "\n")

    say(device=kind, heads=H, dk=dk, dv=dv,
        heads_per_block=(gd.prefill_heads_per_block(H, dk, dv)
                         if hasattr(gd, "prefill_heads_per_block") else 1))
    for bucket in buckets:
        host = inputs(bucket, H, dk, dv, seed=bucket)
        dev = [jnp.asarray(x) for x in host]
        for fill in (1.0, 0.6):
            n = int(round(fill * bucket))
            lengths = jnp.asarray([n], jnp.int32)
            want_o, want_s = jax.jit(
                lambda *a: gd.gated_delta_prefill(*a, interpret=False))(
                    *dev, lengths)
            want_o = np.asarray(want_o)[0, :n]
            want_s = np.asarray(want_s)
            real_chunks = -(-n // gd.CHUNK)
            cost = ref.gated_delta_prefill_cost(spec, n)
            cost = {k: v / spec.n_linear for k, v in cost.items()}
            least, bound = roofline_seconds(cost, peaks)
            for variant in args.variants:
                undo = patched(gd, variant, H)
                if undo is None:
                    continue
                try:
                    fn = jax.jit(lambda *a: gd.gated_delta_prefill(
                        *a, interpret=interpret))
                    t0 = time.time()
                    o, s = fn(*dev, lengths)
                    s.block_until_ready()
                    compile_s = time.time() - t0
                    loop = jax.jit(functools.partial(repeated, gd, reps,
                                                     interpret))
                    loop(*dev, lengths).block_until_ready()
                    best = 1e9
                    for _ in range(3):
                        t0 = time.time()
                        loop(*dev, lengths).block_until_ready()
                        best = min(best, (time.time() - t0) / reps)
                    o = np.asarray(o)[0]
                    say(bucket=bucket, prompt=n, variant=variant,
                        ms=round(best * 1e3, 4),
                        us_per_chunk_head=round(
                            best * 1e6 / (real_chunks * H), 3),
                        roofline_pct=round(100.0 * least / best, 3),
                        bound=bound, compile_s=round(compile_s, 2),
                        max_abs_err=float(max(
                            np.abs(o[:n] - want_o).max(),
                            np.abs(np.asarray(s) - want_s).max())),
                        pads_zero=bool(
                            (o[real_chunks * gd.CHUNK:] == 0).all()),
                        finite=bool(np.isfinite(o).all()))
                except Exception as e:  # a variant the compiler refuses
                    say(bucket=bucket, prompt=n, variant=variant,
                        error=repr(e)[:400])
                finally:
                    undo()
    return 0


if __name__ == "__main__":
    sys.exit(main())
