"""Without a TPU the command exits non-zero and prints no result line;
the explicit ``--rehearse`` runs the same control flow at tiny sizes and
can only end ``correct: false`` with no number under a metric's name."""
import json
import os
import subprocess
import sys

from benchmarks.lib import common

RUN = [sys.executable, os.path.join(common.BENCH, "run.py")]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def test_no_tpu_no_result():
    proc = subprocess.run(RUN + ["--workload", "olmo2_1b_pretrain_1chip",
                                 "--seed", "0", "--seconds", "1",
                                 "--trace", "0"],
                          cwd=common.ROOT, env=ENV, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0
    assert "needs 1 TPU chip" in proc.stderr
    assert not any('"correct"' in ln for ln in proc.stdout.splitlines())


def test_directory_without_the_program_no_result(tmp_path):
    import shutil

    shutil.copytree(common.BENCH, tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    shutil.copy(os.path.join(common.ROOT, "BENCHMARK.json"), tmp_path)
    proc = subprocess.run([sys.executable, "benchmarks/run.py", "--workload",
                           "olmo2_1b_pretrain_1chip", "--seed", "0",
                           "--seconds", "1", "--trace", "0"],
                          cwd=tmp_path, env=ENV, capture_output=True,
                          text=True, timeout=300)
    assert proc.returncode != 0 and not proc.stdout.strip()


def test_rehearsal_runs_end_to_end_and_cannot_pass():
    proc = subprocess.run(RUN + ["--workload", "olmo2_1b_pretrain_1chip",
                                 "--seed", "0", "--seconds", "2",
                                 "--trace", "1", "--rehearse"],
                          cwd=common.ROOT, env=ENV, capture_output=True,
                          text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-800:]
    lines = [json.loads(ln) for ln in proc.stdout.splitlines()
             if ln.startswith("{")]
    last = lines[-1]
    assert set(last) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert last["correct"] is False and last["metrics"] == {}
    assert last["device"]["platform"] == "cpu"
    note = next(ln for ln in lines if ln.get("note") == "rehearsal")
    assert note["would_be_correct"] is True
    assert note["compiles_in_window"] == 0
    assert list(last)[-1] == "compared"
    err = last["compared"]["first_loss_abs_err"]
    assert err["limit"] == 0.01 and 0.0 <= err["value"] < 1e-3
    assert proc.stderr.splitlines()[-1] == (
        f"compared first_loss_abs_err: {err['value']} (limit 0.01)")
