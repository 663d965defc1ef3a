"""The ``setup_*`` readers: what the program's build listener and its
initializers counted in set-up (``jit_build_seconds_total``,
``jit_builds_total``, ``nn_param_init_seconds_total``), from the
``/metrics`` snapshot at the window's opening in a serving kind and from
the registry in process in ``train_job``; nothing in any other context or
on a program without the counters (the parent of the PR that added
them)."""
import importlib
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
NAMES = ("setup_trace_lower_s", "setup_compile_load_s",
         "setup_programs_built", "setup_programs_compiled",
         "setup_param_init_s")


def reader(name):
    return importlib.import_module(f"benchmarks.layer_metrics.{name}")


def series(name, stage, program, cause):
    return f'{name}{{stage="{stage}",program="{program}",cause="{cause}"}}'


BEFORE = {
    series("jit_build_seconds_total", "trace", "prefill", "engine/admit"):
        2.0,
    series("jit_build_seconds_total", "lower", "prefill", "engine/admit"):
        3.0,
    series("jit_build_seconds_total", "load", "prefill", "engine/admit"):
        0.5,
    series("jit_build_seconds_total", "compile", "other", "none"): 4.0,
    series("jit_build_seconds_total", "trace", "other", "param_init"): 0.25,
    series("jit_builds_total", "trace", "prefill", "engine/admit"): 40.0,
    series("jit_builds_total", "lower", "prefill", "engine/admit"): 3.0,
    series("jit_builds_total", "load", "prefill", "engine/admit"): 3.0,
    series("jit_builds_total", "compile", "other", "none"): 2.0,
    series("jit_builds_total", "trace", "other", "param_init"): 9.0,
    "nn_param_init_seconds_total": 1.5,
    'serving_requests_total{engine="decoder",event="admitted"}': 7.0,
}
#: what the window adds must not be read: set-up ends at its opening
AFTER = {k: v + 100.0 for k, v in BEFORE.items()}


def serving(before, after):
    return {"kind": "closed_loop", "before": {"metrics": before},
            "after": {"metrics": after}}


@pytest.mark.parametrize("name,value", [
    ("setup_trace_lower_s", 5.25), ("setup_compile_load_s", 4.5),
    ("setup_programs_built", 5.0), ("setup_programs_compiled", 2.0),
    ("setup_param_init_s", 1.5)])
def test_a_serving_reader_reads_the_snapshot_at_the_windows_opening(
        name, value, capsys):
    assert reader(name).read(serving(BEFORE, AFTER)) == pytest.approx(value)
    # a program without the counters (the parent): nothing, no raise
    other = {k: v for k, v in BEFORE.items() if k.startswith("serving_")}
    assert reader(name).read(serving(other, other)) is None
    assert reader(name).read({"kind": "none",
                              "compiles_in_window": 0}) is None


def test_the_table_by_program_stage_and_cause_is_noted(capsys):
    reader("setup_programs_built").read(serving(BEFORE, AFTER))
    notes = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("{")]
    (note,) = [n for n in notes if n.get("note") == "setup_builds"]
    table = note["by_program_stage_cause"]
    assert table["prefill/lower/engine/admit"] == {"builds": 3.0,
                                                  "seconds": 3.0}
    assert table["other/compile/none"] == {"builds": 2.0, "seconds": 4.0}
    assert note["param_init_s"] == 1.5


def test_a_stage_nobody_built_reads_zero_not_nothing():
    only_loads = {k: v for k, v in BEFORE.items() if '"compile"' not in k}
    assert reader("setup_programs_compiled").read(
        serving(only_loads, only_loads)) == 0.0


def test_a_train_job_reads_the_registry_in_process():
    import jax
    import jax.numpy as jnp

    import paddle_tpu.nn as nn

    nn.Linear(3, 5)
    jax.jit(lambda x: x * 7.0 - 1.0)(jnp.ones((3, 7))).block_until_ready()
    ctx = {"kind": "train_job", "compiles_in_window": 0}
    values = {name: reader(name).read(ctx) for name in NAMES}
    assert all(v is not None and v >= 0 for v in values.values()), values
    assert values["setup_programs_built"] >= 1
    assert values["setup_param_init_s"] > 0
    assert values["setup_trace_lower_s"] > 0


@pytest.mark.parametrize("cell", ["mistral7b_docs_batch",
                                  "olmo2_1b_pretrain_1chip"])
def test_the_rehearsal_fills_the_five(cell):
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "tools",
                                      "rehearse.py"),
         "--workload", cell, "--seconds", "2"],
        cwd=ROOT, capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    (traced,) = [json.loads(ln) for ln in out.stdout.splitlines()
                 if ln.startswith("{") and json.loads(ln)["trace"] == 1]
    assert set(NAMES) <= set(traced["fills"]), traced
