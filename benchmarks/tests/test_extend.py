"""A later PR adds a configuration, a traffic mix, a cell and a per-layer
metric by ADDING FILES AND ENTRIES ONLY. This test does so in a temporary
copy of the tree and sees the harness find and load them; no file that
was there is edited."""
import hashlib
import json
import os
import shutil

from benchmarks.lib import common, report


def digests(root):
    out = {}
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = [d for d in dirnames if d not in ("out", "__pycache__")]
        for f in filenames:
            path = os.path.join(dirpath, f)
            with open(path, "rb") as fh:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_add_files_and_entries_only(tmp_path):
    bench = tmp_path / "benchmarks"
    shutil.copytree(common.BENCH, bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    before = digests(bench)
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))

    # 1. a configuration: its file of sizes
    cfg = common.load_json(bench / "configs" / "mistral-7b-v0.3-d20.json")
    cfg["num_hidden_layers"] = 4
    (bench / "configs" / "throwaway-cfg.json").write_text(json.dumps(cfg))
    manifest["configs"].append({
        "name": "throwaway-cfg", "source": cfg["source"],
        "file": "benchmarks/configs/throwaway-cfg.json",
        "reduced": ["num_hidden_layers"], "why": "a test"})
    # 2. a traffic mix: a data file of an existing kind
    traffic = common.load_json(bench / "traffic" / "chat_steady.json")
    traffic["rate_per_s"] = 1.0
    (bench / "traffic" / "throwaway_mix.json").write_text(json.dumps(traffic))
    # 3. a cell: its file and its entry
    (bench / "workloads" / "throwaway_cell.json").write_text(json.dumps(
        {"config": "throwaway-cfg", "traffic": "throwaway_mix", "chips": 1,
         "why": "a test"}))
    manifest["workloads"].append({
        "name": "throwaway_cell", "config": "throwaway-cfg",
        "traffic": "throwaway_mix", "chips": 1, "why": "a test"})
    for m in manifest["end_to_end"]:
        if m["name"] == "itl_p95_ms":
            m["workloads"].append("throwaway_cell")     # an entry, no edit
    # 4. a per-layer metric: a small reader of its own
    (bench / "layer_metrics" / "throwaway_metric.py").write_text(
        'LAYER = "load generator (benchmark\'s own)"\nUNIT = "count"\n'
        'MOVES = "itl_p95_ms"\nSOURCE = "host_clock"\n\n\n'
        'def read(ctx):\n    return float(len(ctx.get("outcomes", [])))\n')
    manifest["per_layer"].append({
        "name": "throwaway_metric", "unit": "count", "better": "lower",
        "source": "host_clock", "layer": "load generator (benchmark's own)",
        "moves": "itl_p95_ms", "workloads": ["throwaway_cell"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    cell = common.Cell("throwaway_cell", bench_dir=str(bench))
    assert cell.config["num_hidden_layers"] == 4
    assert cell.traffic["rate_per_s"] == 1.0
    assert cell.traffic_kind().__name__.endswith("open_loop")
    assert {m["name"] for m in cell.metrics("end_to_end")} >= {
        "itl_p95_ms", "setup_s", "peak_hbm_gib"}
    assert [m["name"] for m in cell.metrics("per_layer")] == [
        "throwaway_metric"]
    values = report.read_layer_metrics(cell, {"outcomes": [1, 2, 3]})
    assert values == {"throwaway_metric": 3.0}

    after = digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
    assert sorted(set(after) - set(before)) == [
        "configs/throwaway-cfg.json", "layer_metrics/throwaway_metric.py",
        "traffic/throwaway_mix.json", "workloads/throwaway_cell.json"]
