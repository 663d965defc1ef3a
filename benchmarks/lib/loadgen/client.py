#!/usr/bin/env python
"""The load generator's process: reads a plan, drives ``POST
/v1/completions`` (SSE) like any client, writes what every request
experienced. Never imports JAX, numpy or the program.

    python benchmarks/lib/loadgen/client.py <plan.json> <outcomes.json>

The plan: ``url``, ``kind`` (``open_loop`` | ``closed_loop``), the traffic
``params``, ``seed``, ``seconds``, ``vocab_size`` and ``t_open_epoch``,
the wall-clock time at which the window opens. Every time written out is
in seconds relative to that opening, on this process's monotonic clock.
"""
from __future__ import annotations

import http.client
import json
import os
import sys
import threading
import time
from urllib.parse import urlsplit

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import schedule as sched  # noqa: E402  (a sibling file, no package needed)


class Clock:
    """Seconds since the window opened, on the monotonic clock."""

    def __init__(self, t_open_epoch: float):
        self._pc_open = time.perf_counter() + (t_open_epoch - time.time())

    def now(self) -> float:
        return time.perf_counter() - self._pc_open

    def sleep_until(self, t: float) -> None:
        delay = t - self.now()
        if delay > 0:
            time.sleep(delay)


def one_request(host, port, req: dict, body: bytes, out: dict, clock: Clock,
                timeout: float) -> None:
    """Send one request and record its outcome into ``out``."""
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    out["t_sent"] = clock.now()
    gaps = out["gaps"]
    try:
        conn.request("POST", "/v1/completions", body,
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        out["status"] = resp.status
        if resp.status != 200:
            out["error"] = resp.read().decode(errors="replace")[:300]
            return
        t_last = None
        while True:
            line = resp.readline()
            if not line:
                out["error"] = "stream ended without [DONE]"
                break
            if not line.startswith(b"data: "):
                continue
            payload = line[len(b"data: "):].strip()
            if payload == b"[DONE]":
                out["clean"] = True
                break
            piece = json.loads(payload)
            if "choices" not in piece:
                out["error"] = json.dumps(piece)[:300]
                break
            now = clock.now()
            if t_last is None:
                out["t_first"] = now
            else:
                gaps.append(now - t_last)
            t_last = now
            out["n_tokens"] += len(piece["choices"][0]["token_ids"])
    except (OSError, http.client.HTTPException, ValueError) as e:
        out["error"] = f"{type(e).__name__}: {e}"
    finally:
        out["t_end"] = clock.now()
        conn.close()
    out["ok"] = bool(out["clean"] and out["status"] == 200
                     and out["n_tokens"] == req["max_tokens"])


def new_outcome(req: dict, t_due: float) -> dict:
    return {"index": req["index"], "client": req.get("client"),
            "t_due": t_due, "n_prompt": req["n_prompt"],
            "max_tokens": req["max_tokens"], "t_sent": None, "t_first": None,
            "t_end": None, "status": None, "clean": False, "ok": False,
            "n_tokens": 0, "error": None, "gaps": []}


def body_of(req: dict, vocab_size: int) -> bytes:
    return json.dumps({"prompt_token_ids": sched.token_ids(req, vocab_size),
                       "max_tokens": req["max_tokens"],
                       "stream": True}).encode()


def run_open_loop(plan: dict, host, port, clock: Clock) -> dict:
    params = plan["params"]
    requests = sched.open_loop(params, plan["seed"], plan["seconds"])
    bodies = [body_of(r, plan["vocab_size"]) for r in requests]
    outcomes = [new_outcome(r, r["t"]) for r in requests]
    timeout = float(params.get("request_timeout_s", 120.0))
    threads = []
    for req, body, out in zip(requests, bodies, outcomes):
        clock.sleep_until(req["t"])
        th = threading.Thread(target=one_request, daemon=True,
                              args=(host, port, req, body, out, clock,
                                    timeout))
        threads.append(th)
        th.start()
    deadline = plan["seconds"] + float(params.get("drain_s", 20.0))
    for th in threads:
        th.join(timeout=max(0.0, deadline - clock.now()))
    for th, out in zip(threads, outcomes):
        if th.is_alive():
            out["error"] = "still running when the drain ended"
            out["ok"] = False
    return {"digest": sched.digest(requests, plan["vocab_size"]),
            "outcomes": outcomes}


def run_closed_loop(plan: dict, host, port, clock: Clock) -> dict:
    params = plan["params"]
    n_clients = int(params["clients"])
    lead_in = float(params.get("lead_in_s", 0.0))
    timeout = float(params.get("request_timeout_s", 120.0))
    per_client = [[] for _ in range(n_clients)]

    def client(c: int) -> None:
        stream = sched.closed_loop_client(params, plan["seed"], c)
        # stagger the first sends over the first second so that the
        # queue does not open with one burst of N identical arrivals
        clock.sleep_until(-lead_in + c / max(n_clients, 1))
        while clock.now() < plan["seconds"]:
            req = next(stream)
            body = body_of(req, plan["vocab_size"])
            out = new_outcome(req, clock.now())
            per_client[c].append(out)
            one_request(host, port, req, body, out, clock, timeout)

    threads = [threading.Thread(target=client, args=(c,), daemon=True)
               for c in range(n_clients)]
    for th in threads:
        th.start()
    deadline = plan["seconds"] + float(params.get("drain_s", 30.0))
    for th in threads:
        th.join(timeout=max(0.0, deadline - clock.now()))
    outcomes = [o for rows in per_client for o in rows]
    for o in outcomes:
        if o["t_end"] is None:
            o["error"] = "still running when the drain ended"
    head = sched.closed_loop_head(params, plan["seed"])
    return {"digest": sched.digest(head, plan["vocab_size"]),
            "outcomes": outcomes}


def main(argv) -> int:
    plan_path, out_path = argv[1], argv[2]
    with open(plan_path, encoding="utf-8") as f:
        plan = json.load(f)
    u = urlsplit(plan["url"])
    clock = Clock(float(plan["t_open_epoch"]))
    run = {"open_loop": run_open_loop, "closed_loop": run_closed_loop}
    result = run[plan["kind"]](plan, u.hostname, int(u.port), clock)
    tmp = out_path + ".tmp"
    with open(tmp, "w", encoding="utf-8") as f:
        json.dump(result, f)
    os.replace(tmp, out_path)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
