"""``BENCHMARK.json`` against the contract the driver checks before any
run, and against the files its names lead to."""
import json
import os
import re

import pytest

from benchmarks.lib import common

ROOT = common.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def manifest():
    return common.load_json(os.path.join(ROOT, "BENCHMARK.json"))


def line_ok(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level(manifest):
    assert set(manifest) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert isinstance(manifest["run_seconds"], int)
    assert 1 <= manifest["run_seconds"] <= 51
    assert 1 <= len(manifest["paths"]) <= 16
    assert all(line_ok(w) for w in manifest["command"])
    assert manifest["command"][1].startswith(manifest["paths"][0] + "/")


def test_configs(manifest):
    names, files = set(), set()
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] not in names
        assert c["file"] not in files and line_ok(c["source"])
        assert line_ok(c["why"]) and len(c["reduced"]) <= 16
        names.add(c["name"])
        files.add(c["file"])
        assert c["file"].startswith("benchmarks/")
        body = common.load_json(os.path.join(ROOT, c["file"]))
        assert body["source"] == c["source"]
        assert sorted(body["reduced"]) == sorted(c["reduced"])
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not re.search(r"(_dim|_rank|hidden_size|intermediate_size"
                                 r"|head_dim|per_tok)$", key), key
    used = {w["config"] for w in manifest["workloads"]}
    assert used == names


def test_workloads(manifest):
    cells = manifest["workloads"]
    assert 2 <= len(cells) <= 24
    assert len({w["name"] for w in cells}) == len(cells)
    assert len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    four = [w for w in cells if w["chips"] == 4]
    assert len(four) <= max(1, len(cells) // 4)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and line_ok(w["why"])
        cell = common.Cell(w["name"])            # every file it names loads
        assert cell.file["config"] == w["config"]
        assert cell.file["traffic"] == w["traffic"]
        assert cell.file["chips"] == w["chips"]
        assert os.path.exists(os.path.join(
            common.BENCH, "traffic_kinds", f"{cell.traffic['kind']}.py"))


def test_metrics(manifest):
    names = set()
    for group, keys in (("end_to_end", {"name", "unit", "better", "bound",
                                        "source"}),
                        ("per_layer", {"name", "unit", "better", "source",
                                       "layer", "moves"})):
        assert 1 <= len(manifest[group]) <= (16 if group == "end_to_end"
                                             else 128)
        for m in manifest[group]:
            assert set(m) - {"workloads"} == keys, m["name"]
            assert NAME.match(m["name"]) and m["name"] not in names
            names.add(m["name"])
            assert UNIT.match(m["unit"]) and m["source"] in SOURCES
            assert m["better"] in ("lower", "higher")
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    assert any(m["name"] == "setup_s" for m in manifest["end_to_end"])


def test_every_cell_reports_what_it_must(manifest):
    for w in manifest["workloads"]:
        cell = common.Cell(w["name"])
        e2e = {m["name"] for m in cell.metrics("end_to_end")}
        assert "setup_s" in e2e and len(e2e) >= 2
        layer = cell.metrics("per_layer")
        assert layer
        for m in layer:
            assert m["moves"] in e2e, (w["name"], m["name"])


def test_every_per_layer_metric_has_a_reader_that_agrees(manifest):
    for m in manifest["per_layer"]:
        mod = common.load_module(
            os.path.join(common.BENCH, "layer_metrics", f"{m['name']}.py"),
            f"check_{m['name']}")
        assert (mod.LAYER, mod.UNIT, mod.MOVES, mod.SOURCE) == (
            m["layer"], m["unit"], m["moves"], m["source"]), m["name"]
        assert line_ok(m["layer"])
        # a reader that finds nothing to read returns nothing
        assert mod.read({"kind": "none", "compiles_in_window": 0}) in (None,
                                                                       0.0)


def test_a_reference_with_a_router_offers_its_near_ties(manifest):
    """``compare_logprobs`` reads a reference's near-ties iff the module
    defines ``near_tie_alternatives`` (then ``compared`` carries
    ``logprob_err_nats_own_routing``, ``tokens_over_limit_own_routing``
    and ``tokens_on_alternate_routing`` too): the sparse configuration's does, with a gap that was measured
    and a ``forward_logprobs`` that can be told a routing; a dense one's
    has no router and stays as it was."""
    import inspect

    routed = set()
    for w in manifest["workloads"]:
        ref = common.Cell(w["name"]).reference()
        assert callable(ref.forward_logprobs), w["name"]
        if hasattr(ref, "near_tie_alternatives"):
            routed.add(w["name"])
            assert 0.0 < ref.ROUTING_TIE_GAP < 0.1
            assert "forced" in inspect.signature(
                ref.forward_logprobs).parameters
        else:
            assert not hasattr(ref, "route"), w["name"]
    assert routed == {"commandaplus_rag_batch"}


def test_file_names_use_the_characters_of_a_name():
    for dirpath, dirnames, filenames in os.walk(common.BENCH):
        dirnames[:] = [d for d in dirnames
                       if d not in ("out", "__pycache__")]
        for f in filenames:
            assert re.match(r"^[A-Za-z0-9_.\-]+$", f), os.path.join(dirpath, f)


def test_peaks_table_has_the_chip_and_refuses_others():
    assert common.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        common.peaks_for("TPU v9 imaginary")
