#!/usr/bin/env python
"""Scratch tool, not the harness: where a sparse cell's logprob error
comes from, and what ``reference/<module>.ROUTING_TIE_GAP`` is set from.
For a cell whose configuration's reference exposes ``forward_hidden``
(the experts each row chose and the router's logits), it builds the model
as ``run.py`` does, lets the engine answer one prompt of every length
asked for (8 tokens each, greedy, with logprobs: the harness's own check
looks at 4 of them), and for every answered token prints

- the engine's logprob against the reference's (teacher-forced),
- in how many expert layers the PROGRAM's choice of experts at that
  position (one teacher-forced pass of the program's own bf16 model over
  prompt + answer) differs from the reference's, whether an expert the
  model HOLDS is among the differences (only then does the output
  change), the reference's logit gap that the widest such difference
  crossed, and the near-tie swaps the reference offers there
  (``near_tie_swaps``: what ``compare_logprobs`` would re-read),

and, with ``--flips``, what ONE forced held swap at the last answered
position (layer = prompt number mod layers; the swap nearest the
boundary, near-tie or not) does to that token's reference logprob: a
flip's size, which ``lib.serve.ROUTING_FLIP_CAP`` stands on.

Every decision that differs goes, with its gap, to
``chiprun_out/routing_diff/<workload>_<seed>.jsonl``; every reference
decision's k-th/(k+1)-th gap and every position's nearest HELD swap to
the ``.npz`` beside it. ``--summarize`` reads all of those files (no JAX,
no chip) and prints the percentile table of the differing decisions'
gaps and, for each, the share of ALL decisions under it and the share of
positions that would have an alternative: once for every decision that
differs, once for the FIRST held difference of a position. Only the
second is a near-tie: a held flip turns the position's residual by a
fifth, so its later layers route on another input and differ at gaps of
0.1-1.0, which the reference, told the first flip, takes by itself.

    chiprun --chips 1 -- python benchmarks/tools/routing_diff.py \
        --workload commandaplus_rag_batch --seed 11 [--rehearse]
    python benchmarks/tools/routing_diff.py --summarize
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
CHECK_TOKENS = 8
OUT = os.path.join(ROOT, "chiprun_out", "routing_diff")
PERCENTILES = (50.0, 90.0, 99.0, 99.5, 99.9, 100.0)
TIE_GAPS = (0.015, 0.02, 0.022, 0.024, 0.025, 0.027, 0.03, 0.0375)


def decision_gaps(chosen, logits, lo: int, hi: int) -> tuple:
    """Of every reference decision (``chosen`` [layers, S, k], ``logits``
    [layers, S, routed]): (the gap between its k-th and (k+1)-th logit,
    the gap of its nearest swap with a HELD expert on either side: under
    a tie gap ``g`` the position has an alternative iff that is < g in
    some layer)."""
    inside = np.zeros(logits.shape, bool)
    np.put_along_axis(inside, chosen, True, axis=-1)
    held = np.zeros(logits.shape[-1], bool)
    held[lo:hi] = True
    inf = np.float32(np.inf)
    low_in = np.where(inside, logits, inf).min(-1)
    high_out = np.where(~inside, logits, -inf).max(-1)
    low_in_held = np.where(inside & held, logits, inf).min(-1)
    high_out_held = np.where(~inside & held, logits, -inf).max(-1)
    return (low_in - high_out,
            np.minimum(low_in_held - high_out, low_in - high_out_held))


def summarize() -> int:
    """Pool every seed's files under ``chiprun_out/routing_diff``."""
    files = sorted(glob.glob(os.path.join(OUT, "*.npz")))
    if not files:
        print(f"no .npz under {OUT}", file=sys.stderr)
        return 1
    data = [np.load(f) for f in files]
    boundary = np.concatenate([d["boundary_gap"].ravel() for d in data])
    # a position has an alternative iff ANY of its layers has a held swap
    nearest_held = np.concatenate([d["held_gap"].min(0) for d in data])
    differ, first_held = [], []
    for f in files:
        by_position = {}
        with open(f[:-4] + ".jsonl", encoding="utf-8") as log:
            for row in map(json.loads, log):
                differ.append(row["gap"])
                by_position.setdefault(
                    (row["prompt_tokens"], row["position"]), []).append(row)
        for rows in by_position.values():
            held = [r for r in sorted(rows, key=lambda r: r["layer"])
                    if r["held"]]
            first_held += [r["gap"] for r in held[:1]]
    print(json.dumps({
        "files": [os.path.basename(f) for f in files],
        "routing_decisions": int(boundary.size),
        "decisions_that_differ": len(differ),
        "share_that_differ": len(differ) / boundary.size,
        "positions": int(nearest_held.size),
        "positions_with_a_held_difference": len(first_held)}))
    def shares(gap: float) -> dict:
        return {"share_of_all_decisions_under_it": float(
                    (boundary < gap).mean()),
                "share_of_positions_with_an_alternative": float(
                    (nearest_held < gap).mean())}

    for what, gaps in (("every_decision_that_differs", differ),
                       ("first_held_difference_of_a_position", first_held)):
        for pct in PERCENTILES if gaps else ():
            gap = float(np.percentile(gaps, pct))
            print(json.dumps({"of": what, "percentile": pct, "gap": gap,
                              **shares(gap)}))
    for gap in TIE_GAPS:
        print(json.dumps({
            "tie_gap": gap, "first_held_differences_under_it": float(
                (np.asarray(first_held) < gap).mean()), **shares(gap)}))
    return 0


def answer_prompts(cfg: dict, traffic: dict, seed: int, lengths):
    """The model as ``run.py`` builds it and the engine's own answers
    (``CHECK_TOKENS`` greedy tokens with logprobs, all prompts in flight
    at once) to the warm-up prompts of ``lengths`` (None: the traffic's
    whole grid), drawn as ``run.py``'s warm-up draws them, so that a
    seed's prompts here ARE its run's: (model, prompts, answers as
    ``lib.serve.complete`` gives them). The pools are freed: what follows
    is the reference's."""
    from benchmarks.lib import build
    from benchmarks.lib.loadgen import schedule
    from paddle_tpu.serving import ContinuousBatchEngine

    model = build.build_model(cfg, seed)
    engine = ContinuousBatchEngine(model, **cfg["recipe"]["engine"])
    rng = np.random.RandomState(seed)
    vocab = int(cfg["vocab_size"])
    drawn = {n: rng.randint(1, vocab, n).tolist()
             for n in schedule.prompt_grid(traffic)}
    prompts = [drawn[n] if n in drawn else rng.randint(1, vocab, n).tolist()
               for n in (lengths or sorted(drawn))]
    rids = [engine.add_request(np.asarray(p), max_new_tokens=CHECK_TOKENS,
                               logprobs=True) for p in prompts]
    done = {}
    while len(done) < len(rids):
        done.update(engine.step())
    answers = [{"token_ids": [int(t) for t in done[rid]],
                "logprobs": [float(v)
                             for v in engine._finished_logprobs[rid]]}
               for rid in rids]
    # the engine itself stays alive in the process's registries
    engine._caches, engine._last = [], None
    return model, prompts, answers


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--lengths", default=None,
                    help="prompt lengths, comma-separated (default: the "
                         "traffic's grid, as run.py's warm-up draws them)")
    ap.add_argument("--pad", type=int, default=1024,
                    help="the program's own pass runs padded to a multiple "
                         "of this (one compile a length)")
    ap.add_argument("--flips", action="store_true")
    ap.add_argument("--summarize", action="store_true")
    args = ap.parse_args()
    if args.summarize:
        return summarize()
    if not args.workload:
        ap.error("--workload is required")
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    import jax
    import jax.numpy as jnp

    from benchmarks.lib import build, common
    from paddle_tpu.autograd import tape
    from paddle_tpu.nn.layer import functional_weights
    from paddle_tpu.tensor_class import wrap

    cell = common.Cell(args.workload)
    cfg = common.rehearsed(cell.config, args.rehearse)
    traffic = common.rehearsed(cell.traffic, args.rehearse)
    reference = cell.reference()
    spec = reference.Spec.from_config(cfg)
    common.start_jax(cell, args.rehearse)      # run.py's compile cache
    model, prompts, answers = answer_prompts(
        cfg, traffic, args.seed,
        args.lengths and [int(n) for n in args.lengths.split(",")])
    state = build.plain_state(model)
    lo, hi = spec.held

    def program_choices(ids):
        """[layers, S, k]: the program's own routing, one pass over ids,
        padded on the right to whole blocks of ``--pad`` (a causal pass
        does not see its pads, and splash refuses a length that is no
        multiple of 128: the f32 composite would hold 128 heads x S^2
        scores)."""
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
        padded = np.zeros(-(-len(ids) // args.pad) * args.pad, np.int32)
        padded[:len(ids)] = ids
        return np.asarray(jax.jit(fwd)(dict(model.functional_state()),
                                       jnp.asarray(padded)))[:, :len(ids)]

    os.makedirs(OUT, exist_ok=True)
    stem = os.path.join(OUT, f"{args.workload}_{args.seed}")
    worst = worst_clean = 0.0
    held_differ = with_alternative = 0
    differ, boundary_gap, held_gap, flips = 0, [], [], []
    with open(stem + ".jsonl", "w", encoding="utf-8") as log:
        for n_prompt, (prompt, ans) in enumerate(zip(prompts, answers)):
            toks, lps = ans["token_ids"], np.asarray(ans["logprobs"])
            ids = prompt + toks[:-1]
            x, ref_chosen, ref_logits = reference.forward_hidden(spec, state,
                                                                 ids)
            lp = np.asarray(reference.head_logprobs(
                spec, x[-len(toks):], state["llama.norm.weight"],
                state["llama.embed_tokens.weight"]))
            own = lp[np.arange(len(toks)), toks]
            err = np.abs(own - lps)
            ref_chosen = np.asarray(ref_chosen)
            ref_logits = np.asarray(ref_logits)
            prog_chosen = program_choices(ids)
            boundary, nearest_held = decision_gaps(ref_chosen, ref_logits,
                                                   lo, hi)
            boundary_gap.append(boundary.ravel())
            held_gap.append(nearest_held)
            same = (np.sort(ref_chosen, -1)
                    == np.sort(prog_chosen, -1)).all(-1)
            crossed = {}
            for layer, pos in zip(*np.nonzero(~same)):
                a = set(ref_chosen[layer, pos].tolist())
                b = set(prog_chosen[layer, pos].tolist())
                # the widest gap the program's choice crossed
                gap = float(max(ref_logits[layer, pos, e] for e in a - b)
                            - min(ref_logits[layer, pos, e] for e in b - a))
                is_held = any(lo <= e < hi for e in a ^ b)
                differ += 1
                crossed[(int(layer), int(pos))] = (gap, is_held)
                log.write(json.dumps({
                    "prompt_tokens": len(prompt), "layer": int(layer),
                    "position": int(pos), "reference_not_program":
                    sorted(a - b), "program_not_reference": sorted(b - a),
                    "gap": gap, "held": is_held}) + "\n")
            rows = []
            for j in range(len(toks)):
                pos = len(ids) - len(toks) + j
                here = [crossed[(layer, pos)]
                        for layer in range(ref_chosen.shape[0])
                        if (layer, pos) in crossed]
                swaps = reference.near_tie_swaps(
                    spec, ref_chosen[:, pos], ref_logits[:, pos])
                rows.append({
                    "err": round(float(err[j]), 4),
                    "layers_differ": len(here),
                    "held_differ": sum(h for _, h in here),
                    "held_differ_gap": max(
                        (g for g, h in here if h), default=None),
                    "near_tie_swaps": [
                        [s["layer"], s["out"], s["in"], round(s["gap"], 5)]
                        for s in swaps[:4]]})
                worst = max(worst, float(err[j]))
                if not rows[-1]["held_differ"]:
                    worst_clean = max(worst_clean, float(err[j]))
                with_alternative += bool(swaps)
            held_differ += sum(r["held_differ"] > 0 for r in rows)
            line = {"prompt_tokens": len(prompt),
                    "max_err": round(float(err.max()), 4),
                    "rows_that_differ_in_prompt": int((~same).sum()),
                    "tokens": rows}
            if args.flips:
                last = len(ids) - 1
                layer = n_prompt % spec.num_hidden_layers
                swap = reference.near_tie_swaps(
                    spec, ref_chosen[:, last], ref_logits[:, last],
                    tie_gap=np.inf)
                swap = next(s for s in swap if s["layer"] == layer)
                flipped = np.asarray(reference.forward_logprobs(
                    spec, state, ids, last=1, forced={
                        (layer, last): reference.swapped(
                            ref_chosen[layer, last], swap)}))[0, toks[-1]]
                line["flip"] = dict(swap, logprob_own=float(own[-1]),
                                    logprob_flipped=float(flipped),
                                    size=abs(float(flipped - own[-1])))
                flips.append(line["flip"]["size"])
            print(json.dumps(line), flush=True)
    np.savez(stem + ".npz", boundary_gap=np.concatenate(boundary_gap),
             held_gap=np.concatenate(held_gap, axis=1))
    decisions = sum(len(b) for b in boundary_gap)
    print(json.dumps({
        "seed": args.seed, "worst_err": worst,
        "worst_err_without_held_difference": worst_clean,
        "answered_tokens": CHECK_TOKENS * len(prompts),
        "answered_tokens_with_held_difference": held_differ,
        "answered_tokens_with_an_alternative": with_alternative,
        "tie_gap": reference.ROUTING_TIE_GAP,
        "routing_decisions": decisions,
        "decisions_that_differ": differ,
        "share_that_differ": differ / max(decisions, 1),
        "flip_sizes": [round(f, 4) for f in sorted(flips)]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
