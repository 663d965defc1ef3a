"""From a finished run to the contract's last line: the end-to-end
metrics the kind computed (``--trace 0``), or the per-layer metrics its
readers find in the context (``--trace 1``)."""
from __future__ import annotations

import os

from .common import (Cell, memory_peak_bytes, memory_stats_note, note,
                     result_line)


def read_layer_metrics(cell: Cell, ctx: dict) -> dict:
    """Every per-layer metric of this cell through its own reader file. A
    reader that finds nothing to read returns None and is left out."""
    values = {}
    for m in cell.metrics("per_layer"):
        path = os.path.join(cell.bench_dir, "layer_metrics",
                            f"{m['name']}.py")
        if not os.path.exists(path):
            note("reader_missing", metric=m["name"])
            continue
        reader = cell.reader(m["name"])
        value = reader.read(ctx)
        if value is not None:
            values[m["name"]] = value
    return values


def finish(cell: Cell, *, trace: bool, rehearse: bool, device: dict,
           correct: bool, attempted: int, failed: int, end_to_end: dict,
           ctx: dict) -> dict:
    """Build the last line. In the rehearsal the device is not a TPU, so
    the line can only say ``correct: false`` and carries no number under
    a device metric's name."""
    memory_stats_note(cell.chips)
    peak = memory_peak_bytes(cell.chips)
    device = dict(device, memory_peak_bytes=peak)
    if rehearse:
        note("rehearsal", would_be_correct=bool(correct),
             compiles_in_window=ctx.get("compiles_in_window"),
             end_to_end_names=sorted(k for k, v in end_to_end.items()
                                     if v is not None),
             per_layer_names=sorted(read_layer_metrics(cell, ctx))
             if trace else [])
        return result_line(correct=False, attempted=attempted, failed=failed,
                           metrics={}, units={}, device=device,
                           compared=ctx.get("compared"))
    if peak is not None:
        end_to_end = dict(end_to_end, peak_hbm_gib=peak / 2.0 ** 30)
    group = "per_layer" if trace else "end_to_end"
    wanted = {m["name"]: m["unit"] for m in cell.metrics(group)}
    values = read_layer_metrics(cell, ctx) if trace else end_to_end
    metrics = {k: values.get(k) for k in wanted}
    breakdown = None
    if trace:
        reduced = ctx.get("trace") or {}
        device["busy_s"] = reduced.get("busy_s", 0.0)
        device["window_s"] = reduced.get("window_s", 0.0)
        breakdown = reduced.get("breakdown")
    return result_line(correct=correct, attempted=attempted, failed=failed,
                       metrics=metrics, units=wanted, device=device,
                       breakdown=breakdown, compared=ctx.get("compared"))
