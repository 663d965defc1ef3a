"""``chip_smoke.py`` off the chip, and the compile-cache helper it shares
with the tests, the cluster workers and the chaos dryrun.

On the CPU the smoke script can only FAIL: it exists to prove a run on
the TPU, and a CPU run that printed ``"ok": true`` would be the fallback
this repo just removed. The tiny rehearsal still drives every phase end
to end, so a change that breaks the script's control flow shows up here
and not as lost chip time.
"""
import json
import os
import subprocess
import sys

import pytest

from paddle_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _run(*args, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)          # one CPU device, as a chip is one
    return subprocess.run([sys.executable, SMOKE, *args], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=timeout)


def _json_lines(stdout):
    return [json.loads(ln) for ln in stdout.splitlines()
            if ln.startswith("{")]


def test_real_size_run_stops_at_once_without_a_tpu():
    r = _run(timeout=120)
    assert r.returncode != 0
    assert _json_lines(r.stdout) == []          # no result of any kind
    assert "jax found platform 'cpu'" in r.stderr


def test_tiny_rehearsal_runs_every_phase_and_fails():
    r = _run("--tiny")
    assert r.returncode != 0, r.stdout[-2000:]
    lines = _json_lines(r.stdout)
    assert [ln["phase"] for ln in lines] == ["train", "serve", "cluster"], \
        r.stderr[-3000:]
    for ln in lines:
        assert ln["ok"] is False
        assert ln["device"]["platform"] == "cpu"
        assert "platform is 'cpu', not 'tpu'" in ln["failures"]
        # off the chip the norms run interpreted and the attention gates
        # refuse: each is a counted failure, never a silent pass
        assert any("interpreted" in f for f in ln["failures"])
    train, serve, cluster = lines
    assert train["losses"][2] < train["losses"][0]
    # on the CPU the paths agree exactly (f32, same XLA ops)
    assert {v["match"] for v in serve["vs_generate"].values()} == {
        "identical"}
    assert serve["tokens"]["stream"] == serve["tokens"]["completion"]
    assert cluster["tokens"] == serve["tokens"]["stream"]
    assert not any("persistent cache" in f or "differs" in f
                   for f in cluster["failures"])
    assert '"ok": true' not in r.stdout


def test_four_chip_option_runs_only_the_mesh_phase():
    r = _run("--chips", "4", "--tiny")
    assert r.returncode != 0
    (mesh,) = _json_lines(r.stdout)
    assert mesh["phase"] == "mesh" and mesh["device"]["count"] == 4
    assert mesh["losses_hybrid"] == pytest.approx(mesh["losses_one_chip"],
                                                  abs=1e-4)
    # every parallel matrix: a quarter to a device (mp2 x sharding2)
    for cls, rec in mesh["weight_classes"].items():
        if "norm" not in cls:
            assert rec["per_device_bytes"] * 4 == rec["bytes"], cls
    assert not any("device holds" in f for f in mesh["failures"])


def test_cache_helper_leaves_the_environments_directory_alone(monkeypatch):
    import jax

    seen = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: seen.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    assert compile_cache.enable() is None
    assert seen == []                   # JAX reads the variable itself
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.enable() == os.path.join(REPO, ".jax_cache")
    assert ("jax_compilation_cache_dir",
            os.path.join(REPO, ".jax_cache")) in seen


def test_worker_without_a_device_exits_with_the_no_device_code():
    """A worker whose JAX backend cannot start says so and exits with
    EXIT_NO_DEVICE — the code the launcher fails fast on and the
    supervisor does not restart."""
    from paddle_tpu.serving_cluster.supervisor import EXIT_NO_DEVICE

    cfg = {"replica_id": 3, "store": "127.0.0.1:1", "platform": "tpu"}
    env = dict(os.environ, JAX_PLATFORMS="")
    r = subprocess.run(
        [sys.executable, "-c",
         "import sys; from paddle_tpu.serving_cluster.worker import main; "
         "sys.exit(main(sys.argv[1:]))", json.dumps(cfg)],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=300)
    assert r.returncode == EXIT_NO_DEVICE, r.stderr[-2000:]
    assert "cluster worker 3: no JAX device could be acquired" in r.stderr
