"""``decode_ahead_share`` on a RECORDED pair of ``/metrics`` texts (a tiny
CPU engine of the PR that added the series: two requests run to their
end, the snapshot, then three requests of which one is cancelled under a
step in flight, the snapshot), parsed as a run parses them; and nothing
where the program has no such series."""
import importlib
import os

import pytest

from benchmarks.lib.serve import parse_metrics

DATA = os.path.join(os.path.dirname(__file__), "data")


def recorded(when):
    with open(os.path.join(DATA, f"metrics_decode_ahead_{when}.txt"),
              encoding="utf-8") as f:
        return {"metrics": parse_metrics(f.read())}


def read(ctx):
    return importlib.import_module(
        "benchmarks.layer_metrics.decode_ahead_share").read(ctx)


def test_share_of_steps_enqueued_ahead_over_the_window(capsys):
    ctx = {"before": recorded("before"), "after": recorded("after")}
    # 16 - 5 ahead and 2 - 1 drained inside the window
    assert read(ctx) == pytest.approx(100.0 * 11 / 12)
    out = capsys.readouterr().out
    assert '"ahead": 11.0' in out and '"drained": 1.0' in out
    assert '"discarded_rows": 1.0' in out


@pytest.mark.parametrize("ctx", [
    {},                                                   # a train cell
    {"before": {"metrics": {}}, "after": {"metrics": {}}},   # the parent
    {"before": recorded("after"), "after": recorded("after")},  # no step
], ids=["no_window", "series_absent", "no_decode_step"])
def test_nothing_where_there_is_nothing_to_read(ctx):
    assert read(ctx) is None
