"""The readers of what the PROGRAM now says about itself (its programs'
names in the device trace, ``PhaseClock`` phases, the counters at
admission, prefill and decode), each on a hand-built context: the value,
and nothing where the program has no such series or module (the parent
of the PR that added them)."""
import importlib
import os

import pytest

from benchmarks.lib import reduce_trace as rt
from benchmarks.tools import gaps_by_program_span as gaps

TRACE = os.path.join(os.path.dirname(__file__), "data", "train1.xplane.pb.gz")
PHASE = 'serving_step_phase_seconds_sum{engine="decoder",phase="%s"}'


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def context(before, after, **more):
    return {"kind": "open_loop", "seconds": 50.0,
            "engine_args": {"max_batch": 16, "max_len": 2048},
            "before": {"metrics": before, "stats": {"decode_steps": 100}},
            "after": {"metrics": after, "stats": {"decode_steps": 1100}},
            **more}


PHASES_BEFORE = {PHASE % p: 1.0 for p in ("admit", "dispatch", "retire",
                                          "sync", "prefill")}
PHASES_AFTER = {PHASE % "admit": 3.0, PHASE % "dispatch": 2.0,
                PHASE % "retire": 6.0, PHASE % "sync": 33.0,
                PHASE % "prefill": 1.0}


@pytest.mark.parametrize("name,value", [("engine_admit_share", 5.0),
                                        ("engine_dispatch_share", 2.5),
                                        ("engine_retire_share", 12.5)])
def test_one_phase_over_all_and_the_three_sum_to_the_host_share(name, value):
    ctx = context(PHASES_BEFORE, PHASES_AFTER)
    assert reader(name).read(ctx) == pytest.approx(value)
    parts = sum(reader(f"engine_{p}_share").read(ctx)
                for p in ("admit", "dispatch", "retire"))
    assert parts == pytest.approx(reader("engine_host_share").read(ctx))
    assert reader(name).read(context({}, {})) is None


def test_decode_program_is_found_by_name_not_by_frequency():
    trace = {"modules": {
        "jit_decode_step(11)": {"count": 100, "median_s": 0.033,
                                "total_s": 3.3},
        "jit_decode_step_rows(12)": {"count": 3, "median_s": 0.035,
                                     "total_s": 0.1},
        "jit_kv_scatter(13)": {"count": 400, "median_s": 0.002,
                               "total_s": 0.8}}}
    read = reader("decode_program_ms").read
    assert read({"trace": trace}) == pytest.approx(33.0)
    unnamed = {"modules": {"jit_pure(1)": trace["modules"][
        "jit_decode_step(11)"]}}
    assert read({"trace": unnamed}) is None       # the parent's programs
    assert read({"trace": rt.reduce_trace(TRACE)}) is None    # a train step


def histogram(name, count, total):
    return {f'{name}_count{{engine="decoder"}}': count,
            f'{name}_sum{{engine="decoder"}}': total,
            f'{name}_bucket{{engine="decoder",le="0.1"}}': count}


def test_queue_wait_is_the_windows_mean():
    wait = "serving_queue_wait_seconds"
    ctx = context(histogram(wait, 10, 1.0), histogram(wait, 100, 1.9))
    assert reader("queue_wait_mean_ms").read(ctx) == pytest.approx(10.0)
    same = context(histogram(wait, 10, 1.0), histogram(wait, 10, 1.0))
    assert reader("queue_wait_mean_ms").read(same) is None
    assert reader("queue_wait_mean_ms").read(context({}, {})) is None


def test_http_overhead_is_client_less_engine_over_the_same_requests():
    ttft = "serving_time_to_first_token_seconds"
    outcomes = [
        {"ok": True, "t_sent": 1.0, "t_first": 1.150},
        {"ok": True, "t_sent": 2.0, "t_first": 2.170},
        {"ok": True, "t_sent": -0.5, "t_first": -0.3},   # before the window
        {"ok": True, "t_sent": 49.9, "t_first": 50.2},   # after it
        {"ok": False, "t_sent": 3.0, "t_first": None}]
    ctx = context(histogram(ttft, 5, 1.0), histogram(ttft, 7, 1.28),
                  outcomes=outcomes)
    assert reader("http_overhead_ms").read(ctx) == pytest.approx(20.0)
    assert reader("http_overhead_ms").read(context({}, {},
                                                   outcomes=outcomes)) is None


def test_kv_in_use_over_reserved_from_the_engines_counters(capsys):
    cached = 'serving_decode_cached_tokens_total{engine="decoder"}'
    rows = 'serving_decode_rows_total{engine="decoder"}'
    ctx = context({cached: 1e6, rows: 1e3},
                  {cached: 1e6 + 1000 * 5150, rows: 1e3 + 1000 * 12})
    assert reader("kv_pool_in_use_share").read(ctx) == pytest.approx(
        100.0 * 5150 / 32768)
    assert '"cached_tokens_per_step": 5150.0' in capsys.readouterr().out
    assert reader("kv_pool_in_use_share").read(context({}, {})) is None


def test_padding_share_from_prompt_and_bucket_tokens():
    series = 'serving_prefill_tokens_total{engine="decoder",kind="%s"}'
    ctx = context({series % "prompt": 500.0, series % "bucket": 1000.0},
                  {series % "prompt": 500.0 + 1287, series % "bucket":
                   1000.0 + 1741})
    assert reader("prefill_padding_share").read(ctx) == pytest.approx(
        100.0 * (1 - 1287 / 1741))
    assert reader("prefill_padding_share").read(context({}, {})) is None


# ---- tools/gaps_by_program_span.py on the recorded trace ------------------

def test_gap_rows_sum_to_the_idle_time_and_name_the_innermost_span():
    table = gaps.gaps_by_span(TRACE)
    assert sum(s for _, s in table["by_program_span"]) == pytest.approx(
        table["idle_s"], rel=1e-9)
    assert {name for name, _ in table["by_program_span"]} == {
        "bench/trace_slice", "bench/train_step"}
    (main,) = [rows for thread, rows in table["by_thread"].items()
               if thread.startswith("python")]
    assert {main[0][0], main[1][0]} == {"bench/trace_slice",
                                        "bench/train_step"}
    # the device's events are moved onto the host's clock first: in this
    # trace a program starts 1.2 ms before the call that enqueues it
    assert 0.9e-3 < table["device_clock_lead_s"] < 1.4e-3
    reduced = rt.reduce_trace(TRACE)
    assert table["idle_s"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"],
        abs=table["device_clock_lead_s"])


def test_clock_lead_takes_tiny_programs_that_found_the_device_idle():
    call = lambda t, name: [(t, t + 200.0, f"PjitFunction({name})"),  # noqa: E731
                            (t + 0.2, t + 199.0, f"PjitFunction({name})")]
    host = {"python#0": [ev for k in range(10)
                         for ev in call(1e6 + k * 4e7, "_where")]
            + call(9e8, "add") + call(9e8 + 2e6, "add")
            + call(5e8, "decode_step")}
    modules = [(1e6 + k * 4e7 - 600.0 - 10.0 * k, 1e6 + k * 4e7 - 300.0,
                "jit__where(3)") for k in range(10)]     # 600..690 ns early
    modules += [(9e8 - 5e5, 9e8 - 4e5, "jit_add(5)"),    # two calls near
                (5e8 - 4e6, 5e8 + 3e7, "jit_decode_step(7)")]     # not tiny
    assert gaps.device_clock_lead(host, modules) == 690.0
    assert gaps.device_clock_lead(host, []) == 0.0


def test_innermost_picks_the_shortest_cover():
    events = [(0.0, 100.0, "engine/step"), (10.0, 30.0, "engine/admit"),
              (12.0, 20.0, "engine/admit/prefill_dispatch"),
              (40.0, 60.0, "engine/sync")]
    got = gaps.innermost(events, [5.0, 15.0, 25.0, 50.0, 150.0])
    assert [ev and ev[2] for ev in got] == [
        "engine/step", "engine/admit/prefill_dispatch", "engine/admit",
        "engine/sync", None]
