#!/usr/bin/env python
"""watch_cluster: live terminal dashboard over the cluster watchtower.

Polls a serving target's ``/health``, ``/alerts`` and ``/timeseries``
surfaces (docs/SERVING.md "SLOs, alerts & burn-rate runbook") and
renders, top to bottom: firing alerts (the judgments), the worker table
(the router's pool view; a single-process server renders its one
engine), and sparkline windows of recent series from the TSDB — history
at a glance, where a bare ``/metrics`` scrape is one point in time.

Usage:
    python scripts/watch_cluster.py http://127.0.0.1:8000
    python scripts/watch_cluster.py URL --interval 1 --window 120
    python scripts/watch_cluster.py URL --metric serving_queue_depth
    python scripts/watch_cluster.py URL --once            # one frame
    python scripts/watch_cluster.py URL --once --json     # scripting
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import urllib.request
from typing import List, Optional

BLOCKS = "▁▂▃▄▅▆▇█"

#: sparkline defaults: gauges render raw, counters render per-sample
#: deltas; metrics absent from the store are skipped silently (a
#: single-process server has no cluster_* series and vice versa)
DEFAULT_METRICS = (
    "cluster_workers_alive",
    "serving_active_slots",
    "serving_queue_depth",
    "serving_requests_total",
    "serving_deadline_misses_total",
    "worker_restarts_total",
)

#: perf panel series (metric, printf format for the last value): the
#: router's federated per-replica gauge of the engine step's host time
PERF_METRICS = (
    ("cluster_profile_step_ms", "%.2f ms"),
)

#: memory panel series (same shape as PERF_METRICS): the router's
#: federated per-replica KV-atlas gauges first, then the process-local
#: gauges a single server publishes
MEM_METRICS = (
    ("cluster_kv_bytes", "%.0f B"),
    ("cluster_kv_headroom_slots", "%.0f"),
    ("cluster_prefix_hit_ratio", "%.3f"),
    ("serving_kv_bytes", "%.0f B"),
    ("serving_kv_headroom_slots", "%.0f"),
    ("serving_prefix_hit_ratio", "%.3f"),
)

#: audit panel series (same shape as PERF_METRICS): the correctness
#: sentinel's federated per-replica counters/drift gauge — a non-zero
#: cluster_audit_diverged strip is the dashboard's "the model is
#: WRONG" signal, distinct from every load/latency panel above it
AUDIT_METRICS = (
    ("cluster_audit_pass", "%.0f"),
    ("cluster_audit_diverged", "%.0f"),
    ("cluster_audit_skipped", "%.0f"),
    ("cluster_audit_drift", "%.3g"),
)


def _get(url: str, timeout: float = 5.0):
    with urllib.request.urlopen(url, timeout=timeout) as r:
        return json.loads(r.read())


def sparkline(values: List[float], width: int = 40) -> str:
    """Min-max normalized block-character strip of the last ``width``
    values (constant series render as a flat low line)."""
    vals = [float(v) for v in values][-width:]
    if not vals:
        return ""
    lo, hi = min(vals), max(vals)
    if hi <= lo:
        return BLOCKS[0] * len(vals)
    span = hi - lo
    return "".join(
        BLOCKS[min(len(BLOCKS) - 1,
                   int((v - lo) / span * (len(BLOCKS) - 1)))]
        for v in vals)


def series_windows(ts_payload: dict, metric: str, limit: int = 4
                   ) -> List[dict]:
    """Matching series from a /timeseries payload, folded to what the
    sparkline needs: label string, kind, and the value list (counters
    become per-sample deltas so the strip shows activity, not a
    monotonic ramp)."""
    out = []
    for s in ts_payload.get("series") or []:
        if s.get("name") != metric:
            continue
        pts = s.get("points") or []
        if s.get("kind") == "histogram":
            vals = [p[1] for p in pts]            # observation count
            kind = "histogram"
        else:
            vals = [p[1] for p in pts]
            kind = s.get("kind")
        if kind in ("counter", "histogram") and len(vals) >= 2:
            vals = [max(0.0, b - a) for a, b in zip(vals, vals[1:])]
        label_s = ",".join(f"{k}={v}"
                           for k, v in sorted(
                               (s.get("labels") or {}).items()))
        out.append({"labels": label_s, "kind": kind, "values": vals,
                    "last": pts[-1][1] if pts else None})
        if len(out) >= limit:
            break
    return out


def snapshot(url: str, window: Optional[float] = None,
             timeout: float = 5.0) -> dict:
    """One poll of all three surfaces; failures are recorded per
    surface so a half-up tier still renders."""
    base = url.rstrip("/")
    snap = {"url": base, "ts": time.time()}
    q = f"?window={window:g}" if window else ""
    for key, path in (("health", "/health"), ("alerts", "/alerts"),
                      ("timeseries", "/timeseries" + q)):
        try:
            snap[key] = _get(base + path, timeout=timeout)
        except (OSError, ValueError) as e:
            snap[key] = {"error": f"{type(e).__name__}: {e}"}
    return snap


def render(snap: dict, metrics) -> str:
    lines: List[str] = []
    health = snap.get("health") or {}
    alerts = snap.get("alerts") or {}
    ts = snap.get("timeseries") or {}
    when = time.strftime("%H:%M:%S", time.localtime(snap.get("ts", 0)))
    status = health.get("status", health.get("error", "?"))
    lines.append(f"CLUSTER WATCH  {snap.get('url')}  {when}  "
                 f"status={status}")
    # ---- alerts on top: the judgments --------------------------------
    firing = list(alerts.get("firing") or ())
    if alerts.get("error"):
        lines.append(f"ALERTS  unavailable ({alerts['error']})")
    elif firing:
        lines.append(f"ALERTS  {len(firing)} FIRING")
        by_name = {a["name"]: a for a in alerts.get("alerts") or []}
        for name in firing:
            a = by_name.get(name, {})
            lines.append(f"  !! {name}  severity={a.get('severity')}  "
                         f"since={a.get('fired_at')}  "
                         f"detail={a.get('detail')}")
    else:
        n = alerts.get("transitions_total", 0)
        lines.append(f"ALERTS  none firing  ({n} transitions recorded)")
    for t in (alerts.get("transitions") or [])[-3:]:
        lines.append(f"    {t.get('alert')}: {t.get('from')} -> "
                     f"{t.get('to')}")
    # ---- worker table -------------------------------------------------
    workers = health.get("workers")
    if isinstance(workers, dict) and workers:
        lines.append("WORKERS")
        lines.append("  replica role     alive  active queued pending "
                     "drain")
        for rid in sorted(workers, key=lambda r: int(r)):
            w = workers[rid]
            lines.append(
                f"  {rid:>7} {str(w.get('role')):<8} "
                f"{'yes' if w.get('alive') else 'NO':<6} "
                f"{w.get('active', 0):>6} {w.get('queued', 0):>6} "
                f"{w.get('pending', 0):>7} "
                f"{'yes' if w.get('draining') else '-'}")
        sup = health.get("supervisor") or {}
        if sup:
            lines.append(f"  supervisor: {sup.get('restarts_total', 0)} "
                         f"restarts, {sup.get('breakers_open', 0)} "
                         "breakers open, "
                         f"{len(sup.get('quarantined') or ())} "
                         "quarantined")
    elif "active" in health:
        lines.append(f"ENGINE  active={health.get('active')} "
                     f"queued={health.get('queued')} "
                     f"max_active_slots={health.get('max_active_slots')}")
    # ---- perf panel: step anatomy ---------------------------------------
    # federated gauges on a router (per-replica labels); silent until
    # one has published
    perf_rows = []
    for metric, fmt in PERF_METRICS:
        for s in series_windows(ts, metric):
            if not s["values"]:
                continue
            label = f"{metric}{{{s['labels']}}}" if s["labels"] \
                else metric
            perf_rows.append(
                f"  {label:<52} {sparkline(s['values'])} "
                f"last={fmt % s['last']}")
    if perf_rows:
        lines.append("PERF  (engine step, host clock — see "
                     "GET /profile for the per-phase breakdown)")
        lines.extend(perf_rows)
    # ---- memory panel: KV atlas ---------------------------------------
    mem_rows = []
    for metric, fmt in MEM_METRICS:
        for s in series_windows(ts, metric):
            if not s["values"]:
                continue
            label = f"{metric}{{{s['labels']}}}" if s["labels"] \
                else metric
            mem_rows.append(
                f"  {label:<52} {sparkline(s['values'])} "
                f"last={fmt % s['last']}")
    if mem_rows:
        lines.append("MEM  (KV pool occupancy & prefix reuse — see "
                     "GET /kvstate for the per-slot ledger)")
        lines.extend(mem_rows)
    # ---- audit panel: correctness sentinel ----------------------------
    audit_rows = []
    for metric, fmt in AUDIT_METRICS:
        for s in series_windows(ts, metric):
            if not s["values"]:
                continue
            label = f"{metric}{{{s['labels']}}}" if s["labels"] \
                else metric
            audit_rows.append(
                f"  {label:<52} {sparkline(s['values'])} "
                f"last={fmt % s['last']}")
    if audit_rows:
        lines.append("AUDIT  (shadow audits & canary probes — see "
                     "GET /audit/cluster for verdicts and bundles)")
        lines.extend(audit_rows)
    # ---- sparklines ---------------------------------------------------
    if ts.get("error"):
        lines.append(f"TIMESERIES  unavailable ({ts['error']})")
    else:
        shown = False
        for metric in metrics:
            for s in series_windows(ts, metric):
                if not s["values"]:
                    continue
                if not shown:
                    lines.append(f"TIMESERIES  (window of "
                                 f"{len(ts.get('series') or [])} series; "
                                 "counters shown as per-sample deltas)")
                    shown = True
                label = f"{metric}{{{s['labels']}}}" if s["labels"] \
                    else metric
                lines.append(f"  {label:<52} {sparkline(s['values'])} "
                             f"last={s['last']:g}")
        if not shown:
            lines.append("TIMESERIES  (no matching series yet)")
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="watch_cluster",
                                description=__doc__)
    p.add_argument("url", help="router or server base URL "
                               "(http://host:port)")
    p.add_argument("--interval", type=float, default=2.0,
                   help="poll interval seconds (default 2)")
    p.add_argument("--window", type=float, default=120.0,
                   help="sparkline window seconds (default 120)")
    p.add_argument("--metric", action="append", default=None,
                   help="sparkline metric (repeatable; defaults to the "
                        "built-in set)")
    p.add_argument("--once", action="store_true",
                   help="render one frame and exit")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="with --once: print the raw snapshot as JSON "
                        "(scripting mode)")
    args = p.parse_args(argv)
    metrics = tuple(args.metric) if args.metric else DEFAULT_METRICS
    if args.once:
        snap = snapshot(args.url, window=args.window)
        if args.as_json:
            print(json.dumps(snap, indent=1, default=str))
        else:
            print(render(snap, metrics))
        return 0
    try:
        while True:
            snap = snapshot(args.url, window=args.window)
            # clear + home, then one frame — a dumb-terminal-friendly
            # redraw (no curses dependency)
            sys.stdout.write("\x1b[2J\x1b[H" + render(snap, metrics)
                             + "\n")
            sys.stdout.flush()
            time.sleep(args.interval)
    except KeyboardInterrupt:
        return 0


if __name__ == "__main__":
    sys.exit(main())
