#!/usr/bin/env python
"""Scratch tool, not the harness: where a sparse cell's logprob error
comes from. For a cell whose configuration's reference exposes
``forward_hidden`` (the experts each row chose), it builds the model as
``run.py`` does, lets the engine answer one prompt of every length on the
traffic's grid (8 tokens each, greedy, with logprobs: the harness's own
check looks at 4 of them), and for every answered token prints

- the engine's logprob against the reference's (teacher-forced),
- in how many expert layers the PROGRAM's choice of experts at that
  position (one teacher-forced pass of the program's own bf16 model over
  prompt + answer) differs from the reference's, and whether an expert
  the model HOLDS is among the differences (only then does the output
  change),

and at the end the worst error over all tokens, over the tokens with no
held difference at their own position, and the share of routing decisions
that differ.

    chiprun --chips 1 -- python benchmarks/tools/routing_diff.py \
        --workload commandaplus_rag_batch --seed 11 [--rehearse]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CHECK_TOKENS = 8


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--lengths", default=None,
                    help="prompt lengths, comma-separated (default: the "
                         "traffic's grid, as run.py's warm-up draws them)")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp
    import numpy as np

    from benchmarks.lib import build, common
    from benchmarks.lib.loadgen import schedule
    from paddle_tpu.autograd import tape
    from paddle_tpu.nn.layer import functional_weights
    from paddle_tpu.serving import ContinuousBatchEngine
    from paddle_tpu.tensor_class import wrap

    cell = common.Cell(args.workload)
    cfg = common.rehearsed(cell.config, args.rehearse)
    traffic = common.rehearsed(cell.traffic, args.rehearse)
    reference = cell.reference()
    spec = reference.Spec.from_config(cfg)
    model = build.build_model(cfg, args.seed)
    engine = ContinuousBatchEngine(model, **cfg["recipe"]["engine"])
    rng = np.random.RandomState(args.seed)
    lengths = ([int(n) for n in args.lengths.split(",")] if args.lengths
               else schedule.prompt_grid(traffic))
    prompts = [rng.randint(1, int(cfg["vocab_size"]), n).tolist()
               for n in lengths]
    rids = [engine.add_request(np.asarray(p), max_new_tokens=CHECK_TOKENS,
                               logprobs=True) for p in prompts]
    done = {}
    while len(done) < len(rids):
        done.update(engine.step())
    answers = {rid: ([int(t) for t in done[rid]],
                     np.asarray(engine._finished_logprobs[rid]))
               for rid in rids}
    # the pools make room for the reference (the engine itself stays
    # alive in the process's registries)
    engine._caches, engine._last = [], None
    del engine, done
    state = build.plain_state(model)
    lo, hi = spec.held

    def program_choices(ids):
        """[layers, S, k]: the program's own routing, one pass over ids,
        padded on the right to whole blocks of 128 (a causal pass does not
        see its pads, and splash refuses another length: the f32 composite
        would hold 128 heads x S^2 scores)."""
        def fwd(weights, ids):
            # the router's top-k is the one ``jax.lax.top_k`` a layer's
            # forward calls: this scratch tool listens there, so that the
            # program hands out nothing for it
            chosen, top_k = [], jax.lax.top_k

            def listening(x, k):
                values, idx = top_k(x, k)
                chosen.append(idx)
                return values, idx

            jax.lax.top_k = listening
            try:
                with functional_weights(model, weights), tape.no_grad():
                    model.llama(wrap(ids[None]))
            finally:
                jax.lax.top_k = top_k
            return jnp.stack(chosen)
        padded = np.zeros(-(-len(ids) // 128) * 128, np.int32)
        padded[:len(ids)] = ids
        return np.asarray(jax.jit(fwd)(dict(model.functional_state()),
                                       jnp.asarray(padded)))[:, :len(ids)]

    worst = worst_clean = 0.0
    differ = decisions = held_differ = 0
    for prompt, rid in zip(prompts, rids):
        toks, lps = answers[rid]
        ids = prompt + toks[:-1]
        x, ref_chosen = reference.forward_hidden(spec, state, ids)
        lp = np.asarray(reference.head_logprobs(
            spec, x[-len(toks):], state["llama.norm.weight"],
            state["llama.embed_tokens.weight"]))
        err = np.abs(lp[np.arange(len(toks)), toks] - lps)
        ref_chosen = np.asarray(ref_chosen)
        prog_chosen = program_choices(ids)
        rows = []
        for j in range(len(toks)):
            pos = len(ids) - len(toks) + j
            n_diff = n_held = 0
            for layer in range(ref_chosen.shape[0]):
                a = set(ref_chosen[layer, pos].tolist())
                b = set(prog_chosen[layer, pos].tolist())
                if a != b:
                    n_diff += 1
                    n_held += any(lo <= e < hi for e in a ^ b)
            rows.append({"err": round(float(err[j]), 4),
                         "layers_differ": n_diff, "held_differ": n_held})
            worst = max(worst, float(err[j]))
            if not n_held:
                worst_clean = max(worst_clean, float(err[j]))
        same = (np.sort(ref_chosen, -1) == np.sort(prog_chosen, -1)).all(-1)
        differ += int((~same).sum())
        decisions += int(same.size)
        print(json.dumps({"prompt_tokens": len(prompt),
                          "max_err": round(float(err.max()), 4),
                          "rows_that_differ_in_prompt": int((~same).sum()),
                          "tokens": rows}), flush=True)
        held_differ += sum(r["held_differ"] > 0 for r in rows)
    print(json.dumps({
        "seed": args.seed, "worst_err": worst,
        "worst_err_without_held_difference": worst_clean,
        "answered_tokens_with_held_difference": held_differ,
        "routing_decisions": decisions, "decisions_that_differ": differ,
        "share_that_differ": differ / max(decisions, 1)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
