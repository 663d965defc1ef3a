#!/usr/bin/env python
"""step_anatomy: pretty-print the serving step-anatomy profile.

Reads the ``GET /profile`` document (docs/SERVING.md "Step anatomy")
from a live server, a router's federated ``GET /profile/cluster``, or
the ``profile`` section of a saved incident bundle, and renders
per-engine phase tables: where each engine step's HOST time went (admit
/ prefill / draft / dispatch / sync / retire) and the slowest recent
steps with their flight-recorder sequence anchors. Device time, MFU and
rooflines come from the benchmark's device trace (PERF.md section 3).

Usage:
    python scripts/step_anatomy.py http://127.0.0.1:8000
    python scripts/step_anatomy.py http://router:8000 --cluster
    python scripts/step_anatomy.py incident_bundle.json
    python scripts/step_anatomy.py URL --top 10 --json
"""
from __future__ import annotations

import argparse
import json
import sys
import urllib.request
from typing import List

BAR = "█"
BAR_WIDTH = 24


def load(source: str, cluster: bool = False, top: int = 5,
         timeout: float = 5.0) -> dict:
    """The profile document from a URL (live server / router) or a file
    (a saved ``/profile`` payload or a full incident bundle)."""
    if source.startswith(("http://", "https://")):
        path = "/profile/cluster" if cluster else "/profile"
        url = source.rstrip("/") + path + f"?top={int(top)}"
        with urllib.request.urlopen(url, timeout=timeout) as r:
            return json.loads(r.read())
    with open(source) as f:
        doc = json.load(f)
    if isinstance(doc.get("profile"), dict):
        return doc["profile"]          # incident bundle -> PROFILE section
    if doc.get("profile", "absent") is None:
        raise SystemExit("bundle has no profile section (no serving "
                         "engine registered a profiler in that process)")
    return doc                          # already a /profile[... ] payload


def _fmt_ms(v) -> str:
    return f"{float(v):8.3f}"


def render_engine(name: str, eng: dict, lines: List[str]) -> None:
    step = eng.get("step_ms") or {}
    lines.append(f"ENGINE {name}  enabled={eng.get('enabled')}  "
                 f"steps={eng.get('steps', 0)}  "
                 f"window={eng.get('window', 0)}")
    if not eng.get("window"):
        lines.append("  (no committed steps yet)")
        return
    lines.append(f"  step_ms  p50={step.get('p50', 0):.3f}  "
                 f"p99={step.get('p99', 0):.3f}  "
                 f"mean={step.get('mean', 0):.3f}")
    phases = eng.get("phases") or {}
    if phases:
        lines.append("  phase        p50 ms   p99 ms  mean ms  share")
        for pname, info in sorted(phases.items(),
                                  key=lambda kv: -kv[1].get("share", 0)):
            share = float(info.get("share", 0.0))
            bar = BAR * max(1, round(share * BAR_WIDTH)) \
                if share > 0 else ""
            lines.append(f"  {pname:<9} {_fmt_ms(info.get('p50_ms', 0))} "
                         f"{_fmt_ms(info.get('p99_ms', 0))} "
                         f"{_fmt_ms(info.get('mean_ms', 0))}  "
                         f"{share:6.1%} {bar}")
    top = eng.get("top_slowest") or []
    if top:
        lines.append("  slowest steps (ms | dominant phase | active "
                     "slots | kv len | flight-recorder seq)")
        for r in top:
            ph = r.get("phases") or {}
            dom = max(ph, key=ph.get) if ph else "?"
            lines.append(f"    {r.get('ms', 0):9.3f}  {dom:<9} "
                         f"active={r.get('active', 0):<3} "
                         f"kv={r.get('kv', 0):<6} "
                         f"fr_seq={r.get('fr_seq', 0)}")


def render(doc: dict) -> str:
    lines: List[str] = []
    if "replicas" in doc:               # /profile/cluster federation
        for rid in sorted(doc["replicas"], key=str):
            lines.append(f"REPLICA {rid}")
            sub = doc["replicas"][rid] or {}
            for name, eng in sorted((sub.get("engines") or {}).items()):
                render_engine(name, eng, lines)
        for rid, err in sorted((doc.get("errors") or {}).items()):
            lines.append(f"REPLICA {rid}  unavailable ({err})")
        if not doc["replicas"] and not doc.get("errors"):
            lines.append("(no replicas in the pool)")
        return "\n".join(lines)
    engines = doc.get("engines") or {}
    if not engines:
        return "(no engine registered a step profiler)"
    for name, eng in sorted(engines.items()):
        render_engine(name, eng, lines)
    return "\n".join(lines)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(prog="step_anatomy", description=__doc__)
    p.add_argument("source", help="server base URL (http://host:port), "
                                  "a saved /profile payload, or an "
                                  "incident bundle JSON file")
    p.add_argument("--cluster", action="store_true",
                   help="fetch the router's federated /profile/cluster "
                        "instead of /profile")
    p.add_argument("--top", type=int, default=5,
                   help="slowest steps to list per engine (default 5)")
    p.add_argument("--json", action="store_true", dest="as_json",
                   help="print the raw document as JSON (scripting mode)")
    args = p.parse_args(argv)
    doc = load(args.source, cluster=args.cluster, top=args.top)
    if args.as_json:
        print(json.dumps(doc, indent=1, default=str))
    else:
        print(render(doc))
    return 0


if __name__ == "__main__":
    sys.exit(main())
