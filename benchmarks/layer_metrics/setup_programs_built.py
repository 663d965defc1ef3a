"""Programs a set-up built, compiled or loaded from the persistent cache:
``jit_builds_total``, stages ``compile`` and ``load``: the price of a
prefill bucket in programs. Equals the ``compiles`` of the harness's
``setup_split`` note but for builds made before its meter existed.
Writes the ``setup_builds`` note: builds and seconds by program, stage
and cause. Nothing on a program without the counter."""
from benchmarks.layer_metrics import setup_trace_lower_s as base

LAYER = base.LAYER
UNIT = "count"
MOVES = "setup_s"
SOURCE = "program_counter"


def read(ctx):
    builds = base.labelled(ctx, "jit_builds_total")
    if builds is None:
        return None
    seconds = {tuple(sorted(k.items())): v for k, v in
               base.labelled(ctx, "jit_build_seconds_total") or []}
    table = {}
    for labels, n in builds:
        key = "/".join((labels.get("program", ""), labels.get("stage", ""),
                        labels.get("cause", "")))
        table[key] = {"builds": n, "seconds": seconds.get(
            tuple(sorted(labels.items())), 0.0)}
    from benchmarks.lib.common import note

    note("setup_builds", by_program_stage_cause=dict(sorted(table.items())),
         param_init_s=base.total(ctx, "nn_param_init_seconds_total"))
    return float(sum(n for labels, n in builds
                     if labels.get("stage") in ("compile", "load")))
