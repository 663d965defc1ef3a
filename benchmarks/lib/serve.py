"""What the two serving kinds share: the process that holds the chip
(model, ``ContinuousBatchEngine``, ``CompletionServer`` on a loopback
port, profiler), the warm-up of the cell's own shapes, the comparison
with the plain reference, and the window around the load generator's
child process."""
from __future__ import annotations

import http.client
import json
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np

from . import build
from .loadgen import schedule
from .common import (BENCH, OUT, Cell, SetupClock, file_digest, note,
                     peaks_for, rehearsed, start_jax)
from .tracing import TraceSlice

CHECK_TOKENS = 8
#: |engine logprob - reference logprob| of a chosen token, in nats. The
#: engine computes in bf16 through the kernels, the reference in float32
#: from the same bf16 weights. Measured on the v5e at the published
#: widths, depth 20 (my chip runs, PR 25): worst 0.020-0.037 over 4
#: prompts x 8 tokens in seven runs; PR 24 measured 0.0107 of the logit
#: range between kernel path and composites. 0.15 is four times the worst
#: seen and a quarter of what an 8-bit float path (3 mantissa bits against
#: bf16's 7: errors 16 times larger) would show.
LOGPROB_TOL = 0.15
#: tokens of a run (of 32) that may read over ``LOGPROB_TOL`` on the
#: reference's OWN routing, each of which then has to stand on an alternate
#: routing. This COUNT is the number that tells a sound run of a routed
#: model from the contract's control, where the worst token cannot. Its
#: two readings (v5e, published widths, the rag cell; my chip runs, PR 37;
#: PERF.md section 2): SOUND, 0 in nine runs of ten and ONE in the tenth,
#: never two (PR 35's 50 runs, this PR's 53, and 6 seeds of
#: tools/planted_faults.py); the CONTROL (the reference from fp8 e4m3
#: weights in the program's place, seeds 3700000801-803): 10, 3 and 11.
#: With 0.1 a run expected, three arise by chance in under one run of a
#: thousand.
ALT_TOKENS_MAX = 2
#: A GUARD, not a limit that the control is held against: what ONE flipped
#: routing decision can move a token's logprob by, in nats. A token whose
#: error on the own routing is over this is no flip, whatever an
#: alternative reads. Sound runs read 0.017-0.311 on the own routing (109
#: runs; the tokens that stood on an alternate routing: 0.154-0.311); a
#: held swap FORCED at a token's own position moved it by 0.002-0.152 in
#: layers 1-3 (18 flips) and by 0.030-0.566 in layer 0, which the program
#: never flips (0 of 104,355 rows) (tools/routing_diff.py --flips). The
#: control reads 0.24-0.43, INSIDE the sound range (which is why the count
#: above judges it), a zeroed held expert 0.08-0.48, top-7 for top-8
#: 0.17-0.77, a dropped shared expert 0.99-1.79. 0.5 is 1.6 times the
#: worst a sound run has read; it has decided no run so far.
ROUTING_FLIP_CAP = 0.5


def complete(addr, ids, max_tokens: int) -> dict:
    """One non-streamed completion with the chosen tokens' logprobs."""
    conn = http.client.HTTPConnection(*addr, timeout=900)
    try:
        conn.request("POST", "/v1/completions", json.dumps(
            {"prompt_token_ids": [int(t) for t in ids],
             "max_tokens": max_tokens, "logprobs": 0}),
            {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"POST /v1/completions -> {resp.status}: "
                           f"{raw[:300]}")
    choice = json.loads(raw)["choices"][0]
    return {"token_ids": choice["token_ids"],
            "logprobs": choice["logprobs"]["token_logprobs"]}


def get(addr, route: str) -> str:
    with urllib.request.urlopen(f"http://{addr[0]}:{addr[1]}{route}",
                                timeout=60) as resp:
        return resp.read().decode()


def parse_metrics(text: str) -> dict:
    """Prometheus text -> {series with labels: value}."""
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#"):
            series, _, value = line.rpartition(" ")
            try:
                out[series] = float(value)
            except ValueError:
                pass
    return out


def read_on_alternate_routing(reference, spec, state: dict, ids: list,
                              toks: list, j: int, engine: float) -> dict:
    """Token ``j`` of an answer read again on each alternate routing that
    the reference offers at the token's own position, until one lies
    within ``LOGPROB_TOL`` of the ``engine``'s logprob: every reading made,
    with the swaps (layer, the two experts, their logit gap) behind it,
    and under ``err`` the error of the reading that matched (None: none
    did, or the position has no near-tie)."""
    position = len(ids) - len(toks) + j
    entry = {"token": j, "position": position, "engine_logprob": engine,
             "tried": [], "err": None}
    for alt in reference.near_tie_alternatives(spec, state, ids, position):
        again = float(np.asarray(reference.forward_logprobs(
            spec, state, ids, last=len(toks),
            forced=alt["forced"]))[j, toks[j]])
        entry["tried"].append({"swaps": alt["swaps"], "logprob": again})
        if abs(again - engine) <= LOGPROB_TOL:
            entry["err"] = abs(again - engine)
            break
    return entry


def compared_prompts(n: int) -> list:
    """Which of ``n`` prompts ``compare_logprobs`` reads: four, spread
    over the length grid they are sorted by (all of four or fewer)."""
    return sorted({round(i * (n - 1) / 3) for i in range(4)} if n > 4
                  else set(range(n)))


def compare_logprobs(reference, spec, state: dict, prompts: list,
                     answers: list) -> tuple:
    """The engine's logprobs for up to four of ``prompts``
    (``compared_prompts``) x ``CHECK_TOKENS`` tokens against
    the plain float32 ``reference`` module, teacher-forced on the engine's
    own tokens: logprobs, not tokens, since with random weights the top
    logit changes on rounding. ``answers[i]`` is ``complete()``'s reply
    to ``prompts[i]``.

    A reference with a top-k router (one that defines
    ``near_tie_alternatives``) is set-valued at its own near-ties, and
    only there. Where at most ``ALT_TOKENS_MAX`` tokens of the run read
    over ``LOGPROB_TOL`` on the reference's own routing, and none of them
    over ``ROUTING_FLIP_CAP``, each is read again on the alternate
    routings of its OWN position and stands on the first that brings it
    within ``LOGPROB_TOL``. A re-reading is one more pass of the
    reference, so none is made in a run that has failed already. The
    program's own routing is never read.

    Returns (correct, ``compared``: each number read beside its limit,
    one row per compared prompt)."""
    routed = hasattr(reference, "near_tie_alternatives")
    rows, refs, errs, over = [], [], [], []
    for i in compared_prompts(len(prompts)):
        prompt, ans = prompts[i], answers[i]
        toks = ans["token_ids"]
        lp = np.asarray(reference.forward_logprobs(
            spec, state, prompt + toks[:-1], last=len(toks)))
        ref = lp[np.arange(len(toks)), np.asarray(toks)]
        refs.append(ref)
        errs.append(np.abs(ref - np.asarray(ans["logprobs"])))
        over += [(len(rows), int(j))
                 for j in np.nonzero(errs[-1] > LOGPROB_TOL)[0]]
        rows.append({"prompt_tokens": len(prompt), "prompt": i,
                     "max_abs_logprob_err": float(np.max(errs[-1])),
                     "engine_logprobs": [round(float(v), 4)
                                         for v in ans["logprobs"]],
                     "reference_logprobs": [round(float(v), 4)
                                            for v in ref]})
        if routed:
            rows[-1].update(
                max_abs_logprob_err_own_routing=float(np.max(errs[-1])),
                alternate_routing=[])
    # a prompt also fails by a short answer or an error that is no number
    whole = [len(e) == CHECK_TOKENS and bool(np.all(np.isfinite(e)))
             for e in errs]
    worst_own = max(r["max_abs_logprob_err"] for r in rows)
    on_alternate = 0
    if (routed and all(whole) and len(over) <= ALT_TOKENS_MAX
            and worst_own <= ROUTING_FLIP_CAP):
        for r, j in over:
            i = rows[r]["prompt"]
            toks = answers[i]["token_ids"]
            entry = read_on_alternate_routing(
                reference, spec, state, prompts[i] + toks[:-1], toks, j,
                float(answers[i]["logprobs"][j]))
            entry["own_routing_logprob"] = float(refs[r][j])
            rows[r]["alternate_routing"].append(entry)
            if entry["err"] is None:
                break
            errs[r][j] = entry["err"]
            on_alternate += 1
            rows[r]["max_abs_logprob_err"] = float(np.max(errs[r]))
    for row, ok in zip(rows, whole):
        del row["prompt"]
        row["ok"] = bool(ok and row["max_abs_logprob_err"] <= LOGPROB_TOL)
    compared = {"logprob_err_nats": {
        "value": max(r["max_abs_logprob_err"] for r in rows),
        "limit": LOGPROB_TOL}}
    if routed:
        compared["logprob_err_nats_own_routing"] = {
            "value": worst_own, "limit": ROUTING_FLIP_CAP}
        compared["tokens_over_limit_own_routing"] = {
            "value": len(over), "limit": ALT_TOKENS_MAX}
        compared["tokens_on_alternate_routing"] = {
            "value": on_alternate, "limit": ALT_TOKENS_MAX}
    compared["prompts_failed"] = {
        "value": sum(not r["ok"] for r in rows), "limit": 0}
    return all(r["ok"] for r in rows), compared, rows


class ServeRun:
    """One serving cell's run, from the process's start to its outcomes."""

    def __init__(self, cell: Cell, args, t_start: float):
        self.cell, self.args = cell, args
        self.rehearse = bool(args.rehearse)
        self.clock = SetupClock(t_start)
        self.cfg = rehearsed(cell.config, self.rehearse)
        self.traffic = rehearsed(cell.traffic, self.rehearse)
        self.engine_args = dict(self.cfg["recipe"]["engine"])
        self.reference = cell.reference()
        self.spec = self.reference.Spec.from_config(self.cfg)
        self.compared = {}
        self.server = None

    # ---- set-up ---------------------------------------------------------
    def setup(self) -> None:
        cell = self.cell
        self.device, self.meter, cache_dir = start_jax(cell, self.rehearse)
        self.peaks = None if self.rehearse else peaks_for(
            self.device["kind"])
        self.clock.lap("backend_s")
        from paddle_tpu.ops.pallas import autotune
        from paddle_tpu.serving import ContinuousBatchEngine
        from paddle_tpu.serving_http import CompletionServer

        self.autotune_path = autotune.cache_path()
        self.model = build.build_model(self.cfg, self.args.seed)
        self.clock.lap("weights_s")
        self.engine = ContinuousBatchEngine(self.model, **self.engine_args)
        self.server = CompletionServer(self.engine,
                                       model_name=cell.entry["config"])
        self.server.start()
        self.addr = self.server.address
        self.clock.lap("engine_s")
        note("setup", cell=cell.name, compile_cache=cache_dir,
             autotune_table=self.autotune_path, device=self.device)

    def warm_up(self) -> None:
        """Every shape the window will use, and no other: one prompt of
        every length on the traffic's grid (the engine builds a prefill
        and a scatter program per bucket AND a small mask program per
        distinct prompt length), all at once as the window sends them;
        again until a pass builds no program and leaves the autotune
        table as it was."""
        rng = np.random.RandomState(self.args.seed)
        vocab = int(self.cfg["vocab_size"])
        self.warm_prompts = [rng.randint(1, vocab, n).tolist()
                             for n in schedule.prompt_grid(self.traffic)]
        self.warm_answers = [None] * len(self.warm_prompts)

        def ask(i):
            self.warm_answers[i] = complete(self.addr, self.warm_prompts[i],
                                            CHECK_TOKENS)

        passes = 0
        for _ in range(4):
            before = (self.meter.mark(), file_digest(self.autotune_path))
            threads = [threading.Thread(target=ask, args=(i,))
                       for i in range(len(self.warm_prompts))]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            passes += 1
            if (self.meter.mark(),
                    file_digest(self.autotune_path)) == before:
                break
        if any(a is None for a in self.warm_answers):
            raise RuntimeError("a warm-up request was not answered")
        self.clock.lap("warmup_s")
        note("warmup", prompt_lengths=[len(p) for p in self.warm_prompts],
             passes=passes, **self.meter.report())

    def check(self) -> bool:
        """The engine's answers to the warm-up prompts against the plain
        reference the configuration names (``compare_logprobs``)."""
        ok, self.compared, rows = compare_logprobs(
            self.reference, self.spec, build.plain_state(self.model),
            self.warm_prompts, self.warm_answers)
        self.clock.lap("reference_check_s")
        note("reference_check", reference=self.reference.__name__,
             tolerance=LOGPROB_TOL,
             worst=self.compared["logprob_err_nats"]["value"], prompts=rows)
        return ok

    # ---- the window -----------------------------------------------------
    def snapshot(self) -> dict:
        return {"metrics": parse_metrics(get(self.addr, "/metrics")),
                "stats": self.engine.stats(),
                "compiles": self.meter.mark()}

    def run_window(self) -> dict:
        """Start the load generator's process, open the window after its
        lead-in, take the program's counters at both ends, trace a slice
        if asked, wait for the generator to end. Returns the context the
        kind and the readers work from."""
        cell, args, traffic = self.cell, self.args, self.traffic
        health = json.loads(get(self.addr, "/health"))
        note("kernels", **(health.get("kernels") or {}))
        lead_in = float(traffic.get("lead_in_s", 0.0))
        seconds = float(args.seconds)
        plan_path = os.path.join(OUT, f"plan_{cell.name}.json")
        out_path = os.path.join(OUT, f"outcomes_{cell.name}.json")
        if os.path.exists(out_path):
            os.remove(out_path)
        t_open = time.time() + lead_in + 1.5
        with open(plan_path, "w", encoding="utf-8") as f:
            json.dump({"url": f"http://{self.addr[0]}:{self.addr[1]}",
                       "kind": traffic["kind"], "params": traffic,
                       "seed": args.seed, "seconds": seconds,
                       "vocab_size": int(self.cfg["vocab_size"]),
                       "t_open_epoch": t_open}, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(BENCH, "lib", "loadgen",
                                          "client.py"), plan_path, out_path],
            stdout=sys.stderr, start_new_session=True)
        tracer = TraceSlice(cell.name) if args.trace else None
        try:
            self._sleep_until(t_open)
            self.setup_s = t_open - self.clock.t_start
            self.clock.lap("lead_in_s")
            before = self.snapshot()
            if tracer is not None:
                self._sleep_until(t_open + seconds / 4.0)
                tracer.start()
                self._sleep_until(tracer.t_start + min(
                    float(traffic.get("trace_slice_s", 5.0)), seconds / 2.0))
                tracer.stop()
            self._sleep_until(t_open + seconds)
            after = self.snapshot()
            limit = float(traffic.get("drain_s", 20.0)) + 20.0
            try:
                child.wait(timeout=limit)
            except subprocess.TimeoutExpired:
                note("loadgen_killed", after_s=limit)
        finally:
            if child.poll() is None:
                child.kill()
            child.wait()
        if tracer is not None:
            tracer.reduce()
        result = {"digest": None, "outcomes": []}
        if os.path.exists(out_path):
            with open(out_path, encoding="utf-8") as f:
                result = json.load(f)
        note("setup_split", setup_s=self.setup_s, **self.clock.parts,
             **self.meter.report())
        compiles = after["compiles"][0] - before["compiles"][0] + (
            after["compiles"][1] - before["compiles"][1])
        return {
            "cell": cell.name, "kind": traffic["kind"], "seconds": seconds,
            "chips": cell.chips, "config": self.cfg, "traffic": traffic,
            "reference": self.reference, "spec": self.spec,
            "compared": self.compared,
            "engine_args": self.engine_args, "peaks": self.peaks,
            "digest": result["digest"], "outcomes": result["outcomes"],
            "before": before, "after": after, "health": health,
            "compiles_in_window": compiles,
            "trace": tracer.reduced if tracer else None,
            "trace_window": ((tracer.t_start - t_open, tracer.t_stop - t_open)
                             if tracer else None),
        }

    @staticmethod
    def _sleep_until(t_epoch: float) -> None:
        delay = t_epoch - time.time()
        if delay > 0:
            time.sleep(delay)

    def close(self) -> None:
        if self.server is not None:
            self.server.close()
            self.server = None

    def run(self) -> tuple:
        """Set-up, warm-up, check, window: (correct, context)."""
        try:
            self.setup()
            self.warm_up()
            correct = self.check()
            return correct, self.run_window()
        finally:
            self.close()


def by_status(outcomes: list) -> dict:
    counts = {}
    for o in outcomes:
        key = "ok" if o["ok"] else f"{o['status']}:{(o['error'] or '')[:60]}"
        counts[key] = counts.get(key, 0) + 1
    return counts
