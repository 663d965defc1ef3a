#!/usr/bin/env python
"""What ``lib.serve.compare_logprobs`` reads, at the cell's own size, when
something IS wrong (``lib/planted.py`` has the control and the faults;
this is the chip's side of ``tests/test_reference_cohere2_moe.py``). It
builds the model as ``run.py`` does, lets the engine answer the warm-up
prompts (``routing_diff.answer_prompts``) and runs the harness's own
comparison on the four prompts a run compares:

- on the program as it is (what a run's set-up reads);
- on the CONTROL: the reference itself, computed from weights of the
  nearest precision below bf16 (``planted.LOWER_PRECISIONS``), put in the
  program's place on the same prompts and tokens; no decoding;
- with each fault of ``planted.FAULTS`` planted on the REFERENCE's side;
- for a reference with a router, on ONE altered logprob at a time: each
  answered token's logprob moved by ``ALTERED`` nats where it is
  produced, and whether the near-tie rule would let it stand (the token
  has an alternate routing whose reading lies within the limit of the
  altered value). The rule excuses a token here only by chance: the
  share is what the rule costs the comparison;
- and, over EVERY warm-up prompt's answer, the share of answered tokens
  that have an alternative at all, under each gap of ``TIE_GAPS``.

    chiprun --chips 1 -- python benchmarks/tools/planted_faults.py \
        --workload commandaplus_rag_batch --seed 11 [--rehearse]
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
ALTERED = (-0.4, -0.3, -0.2, 0.2, 0.3, 0.4)
TIE_GAPS = (0.015, 0.0185, 0.02, 0.024)


def own_routing(reference, spec, state, ids, n_last: int) -> tuple:
    """The reference's own routing at the last ``n_last`` positions of
    ``ids``: (the positions, chosen [n_last][layers][k], logits
    [n_last][layers][routed]), in ONE pass."""
    rows = list(range(len(ids) - n_last, len(ids)))
    chosen, logits = zip(*(
        (np.asarray(c)[rows], np.asarray(l))
        for _, c, l in reference.blocks(spec, state, ids, logit_rows=rows)))
    return rows, list(zip(*chosen)), list(zip(*logits))


def altered_tokens(reference, spec, state, prompt, ans, tol) -> dict:
    """For each move of ``ALTERED``: how many of this answer's tokens,
    their logprob moved so, the near-tie rule would let stand."""
    toks = ans["token_ids"]
    ids = prompt + toks[:-1]
    excused = dict.fromkeys(ALTERED, 0)
    for j, (row, chosen, logits) in enumerate(zip(*own_routing(
            reference, spec, state, ids, len(toks)))):
        readings = [float(np.asarray(reference.forward_logprobs(
            spec, state, ids, last=len(toks),
            forced=alt["forced"]))[j, toks[j]])
            for alt in reference.alternatives_at(spec, chosen, logits, row)]
        for move in ALTERED:
            excused[move] += any(abs(ans["logprobs"][j] + move - r) <= tol
                                 for r in readings)
    return excused


def tokens_with_an_alternative(reference, spec, state, prompt, ans) -> dict:
    """Of this answer's tokens, how many have a held swap under each gap
    of ``TIE_GAPS`` at their own position."""
    toks = ans["token_ids"]
    _, chosen, logits = own_routing(reference, spec, state,
                                    prompt + toks[:-1], len(toks))
    return {gap: sum(bool(reference.near_tie_swaps(spec, c, l, tie_gap=gap))
                     for c, l in zip(chosen, logits)) for gap in TIE_GAPS}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rehearse", action="store_true")
    ap.add_argument("--only-the-control", action="store_true",
                    help="the program as it is and the control, on the "
                         "four compared prompts; nothing else")
    args = ap.parse_args()
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from benchmarks.lib import build, common, planted, serve
    from benchmarks.lib.loadgen import schedule
    from benchmarks.tools.routing_diff import answer_prompts

    cell = common.Cell(args.workload)
    cfg = common.rehearsed(cell.config, args.rehearse)
    reference = cell.reference()
    spec = reference.Spec.from_config(cfg)
    common.start_jax(cell, args.rehearse)      # run.py's compile cache
    traffic = common.rehearsed(cell.traffic, args.rehearse)
    grid = sorted(schedule.prompt_grid(traffic))
    model, every_prompt, every_answer = answer_prompts(
        cfg, traffic, args.seed,
        [grid[i] for i in serve.compared_prompts(len(grid))]
        if args.only_the_control else None)
    state = build.plain_state(model)
    picks = serve.compared_prompts(len(every_prompt))
    prompts = [every_prompt[i] for i in picks]
    answers = [every_answer[i] for i in picks]

    def read(what: str, judge: tuple, replies: list, **more):
        ok, compared, rows = serve.compare_logprobs(reference, *judge,
                                                    prompts, replies)
        own = sorted((abs(e - r) for row in rows
                      for e, r in zip(row["engine_logprobs"],
                                      row["reference_logprobs"])),
                     reverse=True)
        print(json.dumps({
            "seed": args.seed, "read": what, "correct": ok,
            "compared": {k: v["value"] for k, v in compared.items()},
            "tokens_over_the_limit_on_the_own_routing": sum(
                e > serve.LOGPROB_TOL for e in own),
            "largest_errors_on_the_own_routing": [round(e, 4)
                                                  for e in own[:6]],
            "read_again": [dict(t, tried=len(t["tried"])) for row in rows
                           for t in row.get("alternate_routing", ())],
            **more}), flush=True)

    read("the_program_as_it_is", (spec, state), answers)
    for kind in planted.LOWER_PRECISIONS[:1 if args.only_the_control
                                         else None]:
        # that the rounding is no no-op: how far it moves one matrix
        w = np.asarray(state["llama.embed_tokens.weight"][:64], np.float32)
        moved = np.asarray(planted.in_lower_precision(
            {"w": state["llama.embed_tokens.weight"][:64]}, kind)["w"],
            np.float32) - w
        read("control_" + kind, (spec, state), planted.control_answers(
            reference, spec, state, kind, prompts, answers),
            a_matrix_moved_by_rms=float(np.sqrt((moved ** 2).mean()
                                                / (w ** 2).mean())))
    if args.only_the_control:
        return 0
    for fault in planted.FAULTS:
        read("fault_" + fault, planted.plant(fault, spec, state), answers)
    if hasattr(reference, "near_tie_alternatives"):
        excused = dict.fromkeys(ALTERED, 0)
        for prompt, ans in zip(prompts, answers):
            one = altered_tokens(reference, spec, state, prompt, ans,
                                 serve.LOGPROB_TOL)
            excused = {move: excused[move] + one[move] for move in ALTERED}
        print(json.dumps({"seed": args.seed, "altered_one_token": {
            "tokens": sum(len(a["token_ids"]) for a in answers),
            "excused": excused}}), flush=True)
        under = dict.fromkeys(TIE_GAPS, 0)
        for prompt, ans in zip(every_prompt, every_answer):
            one = tokens_with_an_alternative(reference, spec, state, prompt,
                                             ans)
            under = {gap: under[gap] + one[gap] for gap in TIE_GAPS}
        print(json.dumps({"seed": args.seed, "answered_tokens": sum(
            len(a["token_ids"]) for a in every_answer),
            "with_an_alternative_under_gap": under,
            "tie_gap": reference.ROUTING_TIE_GAP}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
