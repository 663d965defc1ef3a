"""A later PR adds a configuration, its plain reference, a traffic mix, a
cell and a per-layer metric by ADDING FILES AND ENTRIES ONLY. These tests
do so in a temporary copy of the tree and see the harness find and load
them (and, for a configuration of ANOTHER model class with ANOTHER
reference module, run the cell's rehearsal to its result line); no file
that was there is edited."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

from benchmarks.lib import common, report

#: a reference module as a later PR would add it, cut down to the
#: contract's names. It stands in for no architecture: every token is
#: equally likely, so a comparison against it must come out NOT correct.
STAND_IN_REFERENCE = '''"""A stand-in reference (benchmarks/tests/test_extend.py)."""
import math
from typing import NamedTuple

import numpy as np


class Spec(NamedTuple):
    vocab_size: int

    @classmethod
    def from_config(cls, cfg):
        if cfg["reference"].get("kind") != "stand_in":
            raise ValueError(f"stand-in: not mine {cfg['reference']}")
        return cls(int(cfg["vocab_size"]))


def forward_logprobs(spec, state, ids, last):
    assert all(hasattr(v, "shape") for v in state.values())
    return np.full((last, spec.vocab_size), -math.log(spec.vocab_size),
                   np.float32)
'''


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

    # 1. a configuration: its file of sizes, and the reference it names
    cfg = common.load_json(bench / "configs" / "mistral-7b-v0.3-d20.json")
    cfg["num_hidden_layers"] = 4
    cfg["reference"] = {"module": "throwaway_ref", "kind": "stand_in"}
    (bench / "reference" / "throwaway_ref.py").write_text(STAND_IN_REFERENCE)
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
    ref = cell.reference()
    assert ref.__file__ == str(bench / "reference" / "throwaway_ref.py")
    assert ref.Spec.from_config(cell.config) == (32768,)
    assert not hasattr(ref, "paged_decode_cost")     # nothing of decoder's
    with pytest.raises(ValueError, match="not mine"):
        ref.Spec.from_config(common.load_json(
            bench / "configs" / "olmo2-1b-d6.json"))
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
        "reference/throwaway_ref.py", "traffic/throwaway_mix.json",
        "workloads/throwaway_cell.json"]


@pytest.mark.parametrize("reference", [None, {"block": "pre_norm"},
                                       {"module": ""}])
def test_a_configuration_that_names_no_reference_is_refused(tmp_path,
                                                            reference):
    bench = tmp_path / "benchmarks"
    for part in ("configs", "workloads", "traffic"):
        shutil.copytree(os.path.join(common.BENCH, part), bench / part)
    cfg = common.load_json(bench / "configs" / "olmo2-1b-d6.json")
    if reference is None:
        del cfg["reference"]
    else:
        cfg["reference"] = reference
    (bench / "configs" / "olmo2-1b-d6.json").write_text(json.dumps(cfg))
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    cell = common.Cell("olmo2_1b_pretrain_1chip", bench_dir=str(bench))
    with pytest.raises(SystemExit) as refusal:
        cell.reference()
    assert "benchmarks/configs/olmo2-1b-d6.json" in str(refusal.value)
    assert "reference" in str(refusal.value)


def test_every_committed_configuration_names_a_reference_that_loads():
    configs = os.path.join(common.BENCH, "configs")
    for name in sorted(os.listdir(configs)):
        module = common.load_json(os.path.join(configs, name))[
            "reference"]["module"]
        assert os.path.exists(os.path.join(common.BENCH, "reference",
                                           f"{module}.py")), name
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    for w in manifest["workloads"]:
        cell = common.Cell(w["name"])
        assert cell.reference().Spec.from_config(cell.config)


def test_no_harness_module_imports_a_reference_by_name():
    for part in ("lib", "layer_metrics", "traffic_kinds"):
        for dirpath, _, filenames in os.walk(os.path.join(common.BENCH, part)):
            for f in filenames:
                if f.endswith(".py"):
                    with open(os.path.join(dirpath, f)) as fh:
                        text = fh.read()
                    assert "reference import decoder" not in text, f
                    assert "reference.decoder" not in text, f


def test_another_model_class_with_another_reference_rehearses(tmp_path):
    """A configuration whose ``builder.model`` is another class the program
    has (DeepSeek-V2 at its tiny preset: latent attention, routed and
    shared experts, the engine's latent cache), with an added reference
    module, an added ``closed_loop`` cell and entries: ``run.py --rehearse``
    reaches its result line, and the comparison ran against the ADDED
    module (a stand-in, so it reads not correct)."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(common.BENCH, bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    os.symlink(os.path.join(common.ROOT, "paddle_tpu"),
               tmp_path / "paddle_tpu")
    before = digests(bench)
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    sizes = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                 num_hidden_layers=3, num_attention_heads=4,
                 num_key_value_heads=4, max_position_embeddings=256,
                 n_routed_experts=4, num_experts_per_tok=2,
                 moe_intermediate_size=64, first_k_dense_replace=1,
                 kv_lora_rank=32, qk_nope_head_dim=32, qk_rope_head_dim=16,
                 v_head_dim=32, q_lora_rank=None)
    cfg = dict(sizes, source="a test", reduced={}, builder={
        "model": "paddle_tpu.models.deepseek:DeepseekV2ForCausalLM",
        "config": "paddle_tpu.models.deepseek:DeepseekV2Config",
        "config_keys": sorted(sizes), "config_args": {"dtype": "float32"}},
        reference={"module": "throwaway_ref", "kind": "stand_in"},
        recipe={"kind": "serve", "engine": {
            "max_batch": 4, "max_len": 128, "page_size": 8,
            "eos_token_id": None}})
    (bench / "configs" / "throwaway-mla.json").write_text(json.dumps(cfg))
    (bench / "reference" / "throwaway_ref.py").write_text(STAND_IN_REFERENCE)
    manifest["configs"].append({
        "name": "throwaway-mla", "source": "a test",
        "file": "benchmarks/configs/throwaway-mla.json", "reduced": [],
        "why": "a test"})
    entry = {"config": "throwaway-mla", "traffic": "docs_batch", "chips": 1,
             "why": "a test"}
    (bench / "workloads" / "throwaway_mla_cell.json").write_text(
        json.dumps(entry))
    manifest["workloads"].append(dict(entry, name="throwaway_mla_cell"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        if m["name"] in ("tokens_per_s", "serve_mfu", "compiles_in_window"):
            m["workloads"].append("throwaway_mla_cell")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))

    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "throwaway_mla_cell", "--seed", "3", "--seconds", "2", "--trace",
         "1", "--rehearse"], cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-800:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    notes = {ln["note"]: ln for ln in lines if "note" in ln}
    assert notes["setup"]["compile_cache"].startswith(str(tmp_path))
    assert "mla_decode" in notes["kernels"]["paths"]        # the other class
    check = notes["reference_check"]
    assert check["reference"].endswith("throwaway_ref")
    assert len(check["prompts"]) == 4 and check["worst"] > 0.15
    assert notes["rehearsal"]["would_be_correct"] is False
    assert notes["rehearsal"]["compiles_in_window"] == 0
    assert "tokens_per_s" in notes["rehearsal"]["end_to_end_names"]
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["attempted"] > 0 and last["failed"] == 0
    # each number compared beside its limit: last in the line, and the
    # last lines of standard error
    assert list(last)[-1] == "compared"
    assert last["compared"]["logprob_err_nats"] == {
        "value": check["worst"], "limit": 0.15}
    assert last["compared"]["prompts_failed"] == {"value": 4, "limit": 0}
    assert proc.stderr.splitlines()[-2:] == [
        f"compared logprob_err_nats: {check['worst']} (limit 0.15)",
        "compared prompts_failed: 4 (limit 0)"]

    after = digests(bench)
    assert {k: v for k, v in after.items() if k in before} == before
