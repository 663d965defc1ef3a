"""Load imbalance over the held experts: the busiest held expert's pairs
over the mean of all held experts', from the window delta of
``serving_moe_expert_tokens_total{expert}`` (summed over layers by the
program). 1.0 is perfect balance; the grouped matmul visits every expert
whatever its load, so imbalance costs tiles, not correctness (nothing is
dropped). Nothing on a program without the counter."""
LAYER = "models/llama_moe.py dropless expert layer"
UNIT = "ratio"
MOVES = "tokens_per_s"
SOURCE = "program_counter"


def per_expert(ctx):
    """{expert label: window delta} of the per-expert counter."""
    name = "serving_moe_expert_tokens_total{"
    out = {}
    for when, sign in (("after", 1.0), ("before", -1.0)):
        for series, value in ctx[when]["metrics"].items():
            if series.startswith(name) and 'expert="' in series:
                expert = series.split('expert="', 1)[1].split('"', 1)[0]
                out[expert] = out.get(expert, 0.0) + sign * value
    return out


def read(ctx):
    from benchmarks.lib.common import note

    if "before" not in ctx:
        return None
    loads = per_expert(ctx)
    total = sum(loads.values())
    if not loads or total <= 0:
        return None
    note("moe_expert_load", pairs_by_expert=loads)
    return max(loads.values()) * len(loads) / total
