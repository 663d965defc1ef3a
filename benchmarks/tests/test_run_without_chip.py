"""Without a TPU the command exits non-zero and prints no result line;
the explicit ``--rehearse`` runs the same control flow at tiny sizes and
can only end ``correct: false`` with no number under a metric's name."""
import json
import os
import subprocess
import sys

import pytest

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


#: cell -> (--trace, the names under ``compared``, in the line's order:
#: a reference with a router also reports how its near-tie rule stood)
REHEARSED = {
    "olmo2_1b_pretrain_1chip": (1, ["first_loss_abs_err"]),
    "mistral7b_chat_steady": (0, ["logprob_err_nats", "prompts_failed"]),
    "mistral7b_docs_batch": (0, ["logprob_err_nats", "prompts_failed"]),
    "commandaplus_rag_batch": (0, ["logprob_err_nats",
                                   "logprob_err_nats_own_routing",
                                   "tokens_over_limit_own_routing",
                                   "tokens_on_alternate_routing",
                                   "prompts_failed"]),
}


def test_every_cell_is_rehearsed():
    manifest = common.load_json(os.path.join(common.ROOT, "BENCHMARK.json"))
    assert {w["name"] for w in manifest["workloads"]} == set(REHEARSED)


@pytest.mark.parametrize("workload", sorted(REHEARSED))
def test_rehearsal_runs_end_to_end_and_cannot_pass(workload):
    trace, names = REHEARSED[workload]
    proc = subprocess.run(RUN + ["--workload", workload,
                                 "--seed", "0", "--seconds", "2",
                                 "--trace", str(trace), "--rehearse"],
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
    assert list(last["compared"]) == names
    # each number compared beside its limit: the last lines of stderr too
    assert proc.stderr.splitlines()[-len(names):] == [
        f"compared {name}: {pair['value']} (limit {pair['limit']})"
        for name, pair in last["compared"].items()]
    if workload == "olmo2_1b_pretrain_1chip":
        err = last["compared"]["first_loss_abs_err"]
        assert err["limit"] == 0.01 and 0.0 <= err["value"] < 1e-3
    else:
        err = last["compared"]["logprob_err_nats"]
        assert err["limit"] == 0.15 and 0.0 <= err["value"] < 1e-3
        assert last["compared"]["prompts_failed"] == {"value": 0, "limit": 0}
    if "tokens_on_alternate_routing" in names:
        # float32 on both sides: no token needs the reference's near-ties
        assert last["compared"]["tokens_on_alternate_routing"]["value"] == 0
        assert last["compared"]["tokens_over_limit_own_routing"] == {
            "value": 0, "limit": 2}
        assert (last["compared"]["logprob_err_nats_own_routing"]["value"]
                == err["value"])
