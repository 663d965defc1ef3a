#!/usr/bin/env python
"""The device's idle time of a serving cell's traced slice, by what the
HOST was doing in each gap: run the cell once as ``run.py --trace 1``
does, and before the trace is reduced and deleted, file every interval in
which no operation ran on the device under

- the INNERMOST program span (``engine/...`` from the program's
  ``PhaseClock`` and engine loop, ``bench/...`` from the benchmark) that
  covers it on any host thread, and
- per host thread, the innermost event of ANY name that covers it (the
  runtime's own spans included), so a gap with the engine thread in
  ``engine/sync`` also shows what a runtime thread did meanwhile.

The device's events are first moved onto the host's clock
(``device_clock_lead``: the device's timestamps run 1.0-1.8 ms ahead).
A gap is cut at the spans' starts and ends first, so one that runs from
``engine/sync`` through ``engine/retire`` into the next step's
``engine/dispatch`` is shared among them by time, and each piece goes to
the span that covers its middle.

    chiprun --chips 1 -- python benchmarks/tools/gaps_by_program_span.py \
        --workload mistral7b_chat_steady --seed 11

Prints the run's notes and its ``--trace 1`` result as notes, then the
table (also in ``chiprun_out/gaps_<cell>.json``). The rows of the first
table sum to the slice's idle seconds. ``reduce_trace``'s ``idle_gaps``
names gaps by ``bench/`` spans only (``_host_spans``): taking the
program's spans there is a later ``benchmark`` change (ROADMAP).
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import bisect  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import re  # noqa: E402
import sys  # noqa: E402
from collections import defaultdict  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

PROGRAM_PREFIXES = ("engine/", "bench/")
NO_SPAN = "outside any span"


def innermost(events, times):
    """For each of the ascending ``times`` the shortest ``(start, end,
    name)`` event that covers it, or None."""
    events = sorted(events)
    out, open_, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][0] <= t:
            open_.append(events[i])
            i += 1
        open_ = [e for e in open_ if e[1] >= t]
        out.append(min(open_, key=lambda e: e[1] - e[0]) if open_ else None)
    return out


def cut(gaps, events):
    """``gaps`` (ascending, disjoint) cut at every start and end of
    ``events``: inside one piece the innermost event does not change, so
    a gap that runs across several spans is shared among them by time."""
    bounds = sorted({t for s, e, _ in events for t in (s, e)})
    out = []
    for a, b in gaps:
        lo = bisect.bisect_right(bounds, a)
        hi = bisect.bisect_left(bounds, b)
        edges = [a] + bounds[lo:hi] + [b]
        out += [(x, y) for x, y in zip(edges, edges[1:]) if y > x]
    return out


def idle_by_innermost(gaps, events, none=None, longest=None) -> dict:
    """{name of the innermost event: idle nanoseconds under it}; the
    pieces themselves go to ``longest`` as (ns, start, name) if given."""
    pieces = cut(gaps, events)
    table = defaultdict(float)
    mids = [(a + b) / 2.0 for a, b in pieces]
    for (a, b), ev in zip(pieces, innermost(events, mids)):
        name = ev[2][:80] if ev is not None else none
        if name is not None:
            table[name] += b - a
            if longest is not None:
                longest.append((b - a, a, name))
    return table


def gaps_by_span(path: str, window_span: str = "bench/trace_slice",
                 top: int = 12):
    """{"idle_s", "window_s", "device_clock_lead_s", "by_program_span":
    [[name, s], ...], "by_thread": {thread: [[event, s], ...]}, "longest",
    "step_timeline"} of device 0 of one trace; None when it holds no
    device operation."""
    from benchmarks.lib import reduce_trace as rt

    profile = rt.load(path)
    threads, ops, modules = {}, None, []
    for plane in profile.planes:
        if plane.name == rt.HOST_PLANE:
            for n, line in enumerate(plane.lines):
                evs = [(float(e.start_ns), float(e.start_ns + e.duration_ns),
                        e.name) for e in line.events]
                if evs:
                    threads[f"{line.name}#{n}"] = evs
        elif rt.DEVICE_PLANE.match(plane.name) and ops is None:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops = [(float(e.start_ns),
                            float(e.start_ns + e.duration_ns))
                           for e in line.events]
                elif line.name == "XLA Modules":
                    modules = [(float(e.start_ns),
                                float(e.start_ns + e.duration_ns), e.name)
                               for e in line.events]
    if not ops:
        return None
    lead = device_clock_lead(threads, modules)       # onto the host's clock
    ops = [(s + lead, e + lead) for s, e in ops]
    modules = [(s + lead, e + lead, name) for s, e, name in modules]
    lo, hi = min(s for s, _ in ops), max(e for _, e in ops)
    named = [ev for evs in threads.values() for ev in evs
             if ev[2] == window_span]
    if named:
        lo = max(lo, min(s for s, _, _ in named))
        hi = min(hi, max(e for _, e, _ in named))
    busy = rt.union(rt.clip(ops, lo, hi))
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    idle = sum(b - a for a, b in gaps)

    program = [ev for evs in threads.values() for ev in evs
               if ev[2].startswith(PROGRAM_PREFIXES)]
    by_thread = {}
    for thread, evs in threads.items():
        rows = _ranked(idle_by_innermost(gaps, evs))
        if rows and sum(s for _, s in rows) >= 0.01 * idle * 1e-9:
            by_thread[thread] = rows[:top]
    pieces = []
    by_span = idle_by_innermost(gaps, program, NO_SPAN, longest=pieces)
    return {"window_s": (hi - lo) * 1e-9, "idle_s": idle * 1e-9,
            "device_clock_lead_s": lead * 1e-9,
            "gaps": len(gaps), "by_program_span": _ranked(by_span),
            "by_thread": by_thread,
            # the longest single pieces: [seconds, seconds into the
            # window, span], to tell one long stall from many short ones
            "longest": [[ns * 1e-9, (a - lo) * 1e-9, name]
                        for ns, a, name in sorted(pieces, reverse=True)[:top]],
            "step_timeline": step_timeline(threads, modules, lo, hi)}


def device_clock_lead(threads, modules) -> float:
    """Nanoseconds the device's clock runs AHEAD of the host's in this
    trace. In every trace of this system looked at (PR 25's recorded one
    too) a program's device event starts 1.0-1.8 ms BEFORE the host's
    ``PjitFunction(<name>)`` call that enqueues it, so the two planes do
    not quite share a clock. A program cannot start before the host asks
    for it, so (host call start - device start) is a lower bound on the
    lead, and a tight one for a program that starts as soon as it is
    asked for: a tiny one (under 50 us) that found the device idle (no
    program in the 100 us before it), matched to the one call of its
    name within 5 ms. The estimate is the 90th percentile of those
    bounds (their launch latencies differ by tenths of a millisecond; a
    wrong match at the trace's edge may not set it)."""
    calls = defaultdict(list)
    for evs in threads.values():
        for s, _, name in evs:
            if name.startswith("PjitFunction("):
                calls[name[len("PjitFunction("):-1]].append(s)
    for starts in calls.values():
        starts.sort()
    bounds, prev_end = [], float("-inf")
    for s, e, name in sorted(modules):
        idle_before, prev_end = s - prev_end, max(prev_end, e)
        starts = calls.get(re.sub(r"\(\d+\)$", "", name)[len("jit_"):])
        if not starts or e - s > 5e4 or idle_before < 1e5:
            continue
        i = bisect.bisect_left(starts, s)
        near = [t for t in starts[max(i - 2, 0):i + 2] if abs(t - s) < 5e6]
        # the two events PjitFunction writes per call start together
        if near and max(near) - min(near) < 1e4 and min(near) > s:
            bounds.append(min(near) - s)
    if not bounds:
        return 0.0
    return sorted(bounds)[min(int(0.9 * len(bounds)), len(bounds) - 1)]


def step_timeline(threads, modules, lo, hi, steps: int = 2) -> list:
    """For looking at by hand: ``steps`` consecutive ``engine/step`` spans
    from the middle of the window, as rows [microseconds from the first
    step's start, microseconds long, where, name] of every event on the
    engine's thread and every program on the device up to the start of
    the step after them."""
    engine = next((evs for evs in threads.values()
                   if any(ev[2] == "engine/step" for ev in evs)), None)
    if engine is None:
        return []
    starts = sorted(s for s, e, name in engine
                    if name == "engine/step" and lo <= s and e <= hi)
    k = len(starts) // 2
    if len(starts) < k + steps + 1:
        return []
    t0, t1 = starts[k], starts[k + steps]
    rows = [[s, e - s, "host", name[:60]] for s, e, name in engine
            if t0 <= s < t1]
    rows += [[s, e - s, "device", name] for s, e, name in modules
             if t0 <= s < t1]
    return [[round((s - t0) * 1e-3, 1), round(d * 1e-3, 1), where, name]
            for s, d, where, name in sorted(rows)]


def _ranked(table) -> list:
    return sorted(([k, v * 1e-9] for k, v in table.items()),
                  key=lambda kv: -kv[1])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    args.trace = 1
    from benchmarks.lib import common, report, serve, tracing

    cell = common.Cell(args.workload)
    if cell.traffic["kind"] not in ("open_loop", "closed_loop"):
        raise SystemExit(f"{cell.name}: not a serving cell")
    if args.seconds is None:
        args.seconds = float(cell.manifest["run_seconds"])
    if args.rehearse:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    found = {}

    class KeptSlice(tracing.TraceSlice):
        """The cell's own traced slice, read once more before it goes."""

        def reduce(self) -> None:
            traces = glob.glob(os.path.join(
                self.dir, "plugins", "profile", "*", "*.xplane.pb"))
            if traces:
                found["table"] = gaps_by_span(traces[0], self.SPAN)
            super().reduce()

    serve.TraceSlice = KeptSlice
    out = cell.traffic_kind().run(cell, args, T_START)
    common.note("result_detail", **out["note"])
    common.note("result", **report.finish(
        cell, trace=True, rehearse=args.rehearse, device=out["device"],
        correct=out["correct"], attempted=out["attempted"],
        failed=out["failed"], end_to_end=out["end_to_end"], ctx=out["ctx"]))
    table = found.get("table")
    if table is not None:
        dst = os.path.join(ROOT, "chiprun_out")
        os.makedirs(dst, exist_ok=True)
        with open(os.path.join(dst, f"gaps_{cell.name}.json"), "w",
                  encoding="utf-8") as f:
            json.dump(table, f, indent=1)
    common.emit({"workload": cell.name, "seed": args.seed,
                 "gaps_by_program_span": table})
    return 0 if table is not None or args.rehearse else 1


if __name__ == "__main__":
    sys.exit(main())
