"""Seeded request schedules from a traffic file's parameters. Pure
Python (``random.Random``), no numpy, no JAX: the same (parameters, seed)
always give the byte-identical schedule."""
from __future__ import annotations

import hashlib
import json
import math
import random
import statistics
from typing import Iterator, List, Optional, Sequence


def length_at(law: dict, u: float) -> int:
    """The ``u``-quantile (0 < u < 1) of a length law ``{"dist": ...,
    "min": ..., "max": ...}``: ``lognormal`` (``median``, ``sigma``),
    ``loguniform`` or ``uniform``; clipped to [min, max]."""
    lo, hi = int(law["min"]), int(law["max"])
    dist = law["dist"]
    if dist == "lognormal":
        x = float(law["median"]) * math.exp(
            float(law["sigma"]) * statistics.NormalDist().inv_cdf(u))
    elif dist == "loguniform":
        x = math.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    elif dist == "uniform":
        x = lo + u * (hi - lo)
    else:
        raise ValueError(f"unknown length law {dist!r}")
    return max(lo, min(hi, int(round(x))))


def stratified(n: int, rng: random.Random) -> List[float]:
    """The ``n`` mid-quantiles (i + 0.5) / n in a seeded order: every run
    draws the SAME multiset from a law, only the order differs."""
    us = [(i + 0.5) / n for i in range(n)]
    rng.shuffle(us)
    return us


def _is_power_of_two(n: int) -> bool:
    return n > 0 and n & (n - 1) == 0


def prompt_grid(params: dict) -> List[int]:
    """The prompt lengths the traffic can take: ``prompt_grid`` geometric
    points from the law's min to its max. The engine builds a small mask
    program for every DISTINCT prompt length, so lengths drawn freely
    would compile inside the window; on a grid every one can be warmed.
    With ``avoid_powers_of_two`` no point fills a prefill bucket exactly
    (that takes another prefill program than a padded prompt)."""
    law, n = params["prompt_tokens"], int(params["prompt_grid"])
    lo, hi = int(law["min"]), int(law["max"])
    points = {int(round(lo * (hi / lo) ** (i / max(n - 1, 1))))
              for i in range(n)}
    if params.get("avoid_powers_of_two"):
        points = {p - 1 if _is_power_of_two(p) else p for p in points}
    return sorted(points)


def snap_prompt(params: dict, n_prompt: int) -> int:
    if params.get("prompt_grid"):
        return min(prompt_grid(params),
                   key=lambda g: abs(math.log(g / n_prompt)))
    if params.get("avoid_powers_of_two") and _is_power_of_two(n_prompt):
        return n_prompt - 1
    return n_prompt


def request_at(params: dict, u_prompt: float, u_out: float,
               rng: random.Random) -> dict:
    """(prompt length, max_tokens, seed of the prompt's token ids) at the
    given quantiles of the two length laws."""
    return {"n_prompt": snap_prompt(params, length_at(
                params["prompt_tokens"], u_prompt)),
            "max_tokens": length_at(params["max_tokens"], u_out),
            "ids_seed": rng.getrandbits(48)}


def token_ids(req: dict, vocab_size: int) -> List[int]:
    """The prompt's token ids: random ids in [1, vocab), from its seed."""
    rng = random.Random(req["ids_seed"])
    return [rng.randrange(1, vocab_size) for _ in range(req["n_prompt"])]


def arrivals(params: dict, rng: random.Random, t0: float,
             t1: float) -> List[float]:
    """Arrival times in [t0, t1) at ``rate_per_s``: a Poisson process
    CONDITIONED ON ITS COUNT. Exactly round(rate x length) arrivals, each
    uniform in the interval, which is what a Poisson process looks like
    once its count is known. Every run of a cell then attempts the same
    number of requests (a fixed amount of work drawn from the seed), and
    a tail percentile always has its samples."""
    n = int(round(float(params["rate_per_s"]) * (t1 - t0)))
    return sorted(rng.uniform(t0, t1) for _ in range(n))


def open_loop(params: dict, seed: int, seconds: float) -> List[dict]:
    """Requests due in [-lead_in_s, seconds), times relative to the
    window's opening. Those with ``t < 0`` fill the batch to its steady
    occupancy and are not counted. The window's prompt and output lengths
    are the stratified quantiles of their laws in a seeded order, so every
    seed offers the same amount of work; arrival times and the pairing of
    lengths are what the seed changes."""
    rng = random.Random(f"open_loop/{seed}")
    out = []
    for t0, t1 in ((-float(params.get("lead_in_s", 0.0)), 0.0),
                   (0.0, float(seconds))):
        times = arrivals(params, rng, t0, t1)
        us = zip(stratified(len(times), rng), stratified(len(times), rng))
        out += [dict(request_at(params, up, uo, rng), t=t)
                for t, (up, uo) in zip(times, us)]
    return [dict(r, index=i) for i, r in enumerate(out)]


CLOSED_LOOP_BLOCK = 8


def closed_loop_client(params: dict, seed: int, client: int) -> Iterator[dict]:
    """The endless request stream of one client of a closed loop: blocks
    of ``CLOSED_LOOP_BLOCK`` requests whose lengths are the stratified
    quantiles of their laws in a seeded order, so that whatever the seed
    the clients together offer nearly the same mix of work."""
    rng = random.Random(f"closed_loop/{seed}/{client}")
    i = 0
    while True:
        for up, uo in zip(stratified(CLOSED_LOOP_BLOCK, rng),
                          stratified(CLOSED_LOOP_BLOCK, rng)):
            yield dict(request_at(params, up, uo, rng), client=client,
                       index=i)
            i += 1


def closed_loop_head(params: dict, seed: int, per_client: int = 16):
    """The first requests of every client (what the digest covers)."""
    out = []
    for c in range(int(params["clients"])):
        stream = closed_loop_client(params, seed, c)
        out += [next(stream) for _ in range(per_client)]
    return out


def digest(requests: Sequence[dict], vocab_size: int) -> str:
    """sha256 over the canonical JSONL of the schedule WITH its token ids:
    two runs saw the same traffic iff their digests match."""
    h = hashlib.sha256()
    for r in requests:
        row = {k: r[k] for k in sorted(r) if k != "ids_seed"}
        row["prompt_token_ids"] = token_ids(r, vocab_size)
        h.update(json.dumps(row, sort_keys=True).encode() + b"\n")
    return h.hexdigest()


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-th percentile (linear interpolation), or None where fewer
    than ten samples lie beyond it: a tail nobody sampled is not given."""
    n = len(values)
    if n == 0 or (q > 50 and n * (100.0 - q) < 1000.0 - 1e-6):
        return None
    v = sorted(values)
    pos = (n - 1) * q / 100.0
    lo = int(math.floor(pos))
    hi = min(lo + 1, n - 1)
    return v[lo] + (v[hi] - v[lo]) * (pos - lo)
