"""Seconds of set-up that the program spent tracing its programs to
jaxprs and lowering them to modules: ``jit_build_seconds_total`` (the
program's build listener, ``paddle_tpu/jit/build_events.py``), stages
``trace`` and ``lower``, as set-up left it. A warm persistent cache saves
none of this: every program is traced and lowered again before its load.
Nothing on a program without the counter.

Also the shared reading of the set-up counters for the other ``setup_*``
readers: in a serving kind the ``/metrics`` snapshot taken at the
window's opening (exactly set-up); in ``train_job`` the registry in
process after the window, which builds nothing
(``train_compiles_in_window`` reads 0)."""
import re

LAYER = "set-up: jit program builds, nn initializers"
UNIT = "s"
MOVES = "setup_s"
SOURCE = "program_counter"

_LABEL = re.compile(r'(\w+)="([^"]*)"')


def setup_series(ctx):
    """``{series: value}`` of the program's counters at the end of
    set-up; None for a context of another kind."""
    if "before" in ctx:
        return ctx["before"]["metrics"]
    if ctx.get("kind") == "train_job":
        from benchmarks.lib.serve import parse_metrics
        from paddle_tpu.observability import get_registry

        return parse_metrics(get_registry().render_prometheus())
    return None


def labelled(ctx, name):
    """``[(labels, value)]`` of every series of the counter ``name`` at
    the end of set-up; None where the program has no such counter."""
    series = setup_series(ctx)
    if series is None:
        return None
    rows = [(dict(_LABEL.findall(rest)), value)
            for head, _, rest, value in
            ((*s.partition("{"), v) for s, v in series.items())
            if head == name]
    return rows or None


def total(ctx, name, stages=None):
    """The sum of ``name``'s series (those of ``stages`` only, where
    given); None where the program has no such counter."""
    rows = labelled(ctx, name)
    if rows is None:
        return None
    return float(sum(v for labels, v in rows
                     if stages is None or labels.get("stage") in stages))


def read(ctx):
    return total(ctx, "jit_build_seconds_total", ("trace", "lower"))
