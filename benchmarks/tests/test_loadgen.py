"""The load generator's schedules: the same (mix, seed) gives the same
bytes, another seed other bytes; lengths keep to their law and grid; a
percentile is given only with ten samples beyond it."""
import json
import os
import random

from benchmarks.lib.loadgen import schedule as s

TRAFFIC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "traffic")


def mix(name):
    with open(os.path.join(TRAFFIC, f"{name}.json")) as f:
        return json.load(f)


def test_same_mix_and_seed_same_digest_other_seed_other():
    chat = mix("chat_steady")
    a = s.open_loop(chat, 7, 10.0)
    b = s.open_loop(chat, 7, 10.0)
    c = s.open_loop(chat, 8, 10.0)
    assert s.digest(a, 32768) == s.digest(b, 32768)
    assert s.digest(a, 32768) != s.digest(c, 32768)
    docs = mix("docs_batch")
    assert s.digest(s.closed_loop_head(docs, 7), 32768) == \
        s.digest(s.closed_loop_head(docs, 7), 32768)
    assert s.digest(s.closed_loop_head(docs, 7), 32768) != \
        s.digest(s.closed_loop_head(docs, 8), 32768)


def test_open_loop_has_a_lead_in_and_keeps_its_rate():
    chat = mix("chat_steady")
    reqs = s.open_loop(chat, 3, 200.0)
    assert reqs[0]["t"] < 0 <= reqs[-1]["t"] < 200.0
    assert all(a["t"] <= b["t"] for a, b in zip(reqs, reqs[1:]))
    due = [r for r in reqs if r["t"] >= 0]
    assert abs(len(due) / 200.0 - chat["rate_per_s"]) < 0.5


def test_lengths_keep_to_law_and_grid():
    for name in ("chat_steady", "docs_batch"):
        m = mix(name)
        grid = s.prompt_grid(m)
        assert len(grid) <= m["prompt_grid"]
        assert not any(g & (g - 1) == 0 for g in grid)   # no exact bucket
        rng = random.Random(0)
        for _ in range(500):
            r = s.request_at(m, rng.uniform(0.001, 0.999),
                             rng.uniform(0.001, 0.999), rng)
            assert r["n_prompt"] in grid
            assert m["max_tokens"]["min"] <= r["max_tokens"] \
                <= m["max_tokens"]["max"]
            assert r["n_prompt"] + r["max_tokens"] <= 2048   # the engine's
    rng = random.Random(1)
    law = {"dist": "lognormal", "median": 256, "sigma": 0.9, "min": 48,
           "max": 1500}
    draws = sorted(s.length_at(law, rng.uniform(1e-9, 1 - 1e-9))
                   for _ in range(4001))
    assert 230 < draws[2000] < 285


def test_every_seed_offers_the_same_work():
    chat = mix("chat_steady")
    work = {(sum(r["n_prompt"] for r in reqs if r["t"] >= 0),
             sum(r["max_tokens"] for r in reqs if r["t"] >= 0),
             sum(1 for r in reqs if r["t"] >= 0))
            for reqs in (s.open_loop(chat, seed, 50.0) for seed in range(5))}
    assert len(work) == 1
    assert next(iter(work))[2] == round(chat["rate_per_s"] * 50.0) == 220
    docs = mix("docs_batch")
    for seed in (1, 2):
        stream = s.closed_loop_client(docs, seed, 0)
        block = sorted(next(stream)["n_prompt"] for _ in range(8))
        assert block == sorted(s.snap_prompt(docs, s.length_at(
            docs["prompt_tokens"], (i + 0.5) / 8)) for i in range(8))


def test_token_ids_come_from_the_requests_seed():
    r = {"n_prompt": 50, "ids_seed": 1234}
    ids = s.token_ids(r, 1000)
    assert ids == s.token_ids(r, 1000) and len(ids) == 50
    assert all(1 <= t < 1000 for t in ids)


def test_percentile_only_with_ten_samples_beyond():
    assert s.percentile(list(range(99)), 90) is None
    assert s.percentile(list(range(100)), 90) == 89.1
    assert s.percentile(list(range(199)), 95) is None
    assert s.percentile(list(range(200)), 95) is not None
    assert s.percentile([1.0, 2.0, 3.0], 50) == 2.0
    assert s.percentile([], 50) is None
