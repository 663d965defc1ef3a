#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that the system still starts,
compiles and answers on the chip.

Drives the three normal entry points once each on ONE TPU chip at the full
width of Llama-3-8B (``LlamaConfig.llama3_8b``: hidden 4096, intermediate
14336, 32 query / 8 KV heads of 128, vocab 128256, bf16), random weights
from a seed, and checks what comes out by the repo's own means:

- ``train``   ``paddle.jit.train_step`` + ``AdamW(moment_dtype="bfloat16")``,
  3 steps on one repeated batch (loss finite, near ln(vocab), falling),
  then an EAGER forward and a 4-token ``generate()`` on the same model.
  Depth cut 32 -> 2: bf16 params + f32 masters + bf16 moments of two layers
  and the tied 128256 x 4096 embedding are ~9.6 GB of a 16 GB chip.
- ``serve``   ``ContinuousBatchEngine`` behind ``CompletionServer``: /health,
  a completion, an SSE stream, two concurrent requests (one of 1024 prompt
  tokens). Greedy tokens equal solo ``generate()``; first-token logits of
  the kernel path agree with the same model on the XLA composites.
  Depth cut 32 -> 16, untied head: ~9.1 GB of weights + ~2.1 GB of paged
  K/V at max_batch 8, max_len 4096, page 16.
- ``cluster`` ``serving_cluster.launch_cluster`` with ONE unified worker built
  by :func:`serve_model`; the router lives in a process that never
  initialises a JAX backend. One streamed completion, tokens equal to the
  serve phase's, compiled programs served from the persistent cache.

Each phase runs alone in a child process (``--phase NAME``): the train and
serve states do not fit in 16 GB together, the cluster worker needs the
chip to itself, and this parent must never touch the chip. Every phase
prints ONE JSON line. A phase fails — and the script exits non-zero
without an ``"ok": true`` line — when a check fails, when the platform is
not ``tpu``, or when a main-path kernel ran interpreted or fell back to an
XLA composite. Nothing is caught and carried past.

``--chips 4`` runs ONLY the sharded path and what it is compared with: the
hybrid train step (fleet mesh, mp2 x sharding2, ZeRO-3) at the same widths
(depth 2, batch 2 x seq 2048 — the one-chip reference holds the same 4096
tokens a step as ``train``) against the one-chip step on device 0 from the
same weights and batch.

The last stdout line on success is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}``.

``--tiny`` is the CPU rehearsal (tests/test_chip_smoke.py): the same
control flow at toy sizes. It can only fail — the platform is not a TPU.
"""
from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
SEED = 0
PHASES = ("train", "serve", "cluster")
BUDGET_S = 1150          # of the contract's 1200, compilation included
NEW_TOKENS = 8

# Llama-3-8B widths are LlamaConfig's defaults; only depth, context and
# the memory recipe are stated here. ``tiny`` is the CPU rehearsal.
SIZES = {
    False: dict(
        widths={},
        train=dict(depth=2, seq=4096, batch=1),
        serve=dict(depth=16, max_batch=8, max_len=4096, page_size=16,
                   # 200 pads to its 256 bucket, 1024 IS its bucket: the
                   # engine passes no pad mask, so both prefill on splash
                   prompt_lens=(200, 1024)),
        mesh=dict(depth=2, seq=2048, batch=2)),
    True: dict(
        widths=dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                    num_attention_heads=4, num_key_value_heads=2,
                    dtype="float32"),
        train=dict(depth=2, seq=64, batch=1),
        serve=dict(depth=2, max_batch=4, max_len=128, page_size=8,
                   prompt_lens=(20, 64)),
        mesh=dict(depth=2, seq=32, batch=2)),
}

#: kernel call sites each phase's main path must have taken as compiled
#: Pallas kernels (``ops/pallas/backend.py`` keeps the record)
MAIN_PATH = {
    "train": ("flash_attention", "fused_rope", "rms_norm", "add_rms_norm"),
    "serve": ("flash_attention", "paged_attention", "kv_page_write",
              "rms_norm", "add_rms_norm"),
    "cluster": ("flash_attention", "paged_attention", "kv_page_write",
                "rms_norm", "add_rms_norm"),
    "mesh": ("flash_attention", "fused_rope", "rms_norm", "add_rms_norm"),
}   # mesh: the ONE-CHIP reference; the hybrid step is checked apart


# ---- shared by the phase children -------------------------------------------

def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def device_info(tiny: bool) -> dict:
    """The device as JAX reports it. A backend fault raises from here.
    Without a TPU the real-size run stops before building anything; the
    tiny rehearsal goes on and fails at the end."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" and not tiny:
        sys.exit(f"chip_smoke: needs a TPU; jax found platform "
                 f"{info['platform']!r} ({info['kind']})")
    return info


class CompileMeter:
    """XLA backend compile seconds and persistent-cache hits/misses of this
    process, from ``jax.monitoring``."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.seconds += float(secs)

    def _event(self, name, **kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def report(self) -> dict:
        return {"compile_s": round(self.seconds, 2),
                "cache": {"hits": self.hits, "misses": self.misses}}


def start_phase(tiny: bool):
    """Common head of a phase child: the shared compile cache, the
    compile meter, then the device."""
    from paddle_tpu.utils import compile_cache

    compile_cache.enable()
    meter = CompileMeter()
    return device_info(tiny), meter


def llama_config(tiny: bool, depth: int, max_pos: int, **kw):
    from paddle_tpu.models.llama import LlamaConfig

    return LlamaConfig.llama3_8b(
        num_hidden_layers=depth, max_position_embeddings=max_pos,
        use_flash_attention=True, **SIZES[tiny]["widths"], **kw)


def kernel_failures(phase: str, paths: dict, tiny: bool) -> list:
    """Main-path sites that did not run as compiled Pallas kernels."""
    from paddle_tpu.ops.pallas import backend

    bad = [f"{site} ran interpreted" for site, impls in paths.items()
           if backend.INTERPRET in impls]
    for site in MAIN_PATH[phase]:
        impls = paths.get(site, {})
        if backend.XLA in impls:
            bad.append(f"{site} took an XLA composite on the main path")
        elif backend.PALLAS not in impls and not tiny:
            bad.append(f"{site} never ran on the main path")
    return bad


def finish(phase: str, device: dict, report: dict, failures: list) -> int:
    if device["platform"] != "tpu":
        failures = failures + [
            f"platform is {device['platform']!r}, not 'tpu'"]
    emit(dict(report, phase=phase, ok=not failures, failures=failures,
              device=device))
    return 1 if failures else 0


def memory(label: str, log: list) -> None:
    """Append device 0's (label, bytes in use, peak so far) to ``log``."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}   # None on the CPU
    log.append([label, stats.get("bytes_in_use"),
                stats.get("peak_bytes_in_use")])


# ---- phase: train ------------------------------------------------------------

def run_train(tiny: bool) -> int:
    import numpy as np

    device, meter = start_phase(tiny)
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.ops.pallas import backend

    sz = SIZES[tiny]["train"]
    cfg = llama_config(tiny, sz["depth"], sz["seq"],
                       tie_word_embeddings=True,
                       fuse_linear_cross_entropy=True)
    paddle.seed(SEED)
    mem = []
    model = LlamaForCausalLM(cfg)
    memory("model", mem)
    step = paddle.jit.train_step(
        model, lambda m, x, y: m(x, labels=y)[0],
        opt.AdamW(3e-4, parameters=model.parameters(),
                  moment_dtype="bfloat16"))
    ids = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, (sz["batch"], sz["seq"] + 1))
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

    losses, times = [], []
    for _ in range(3):
        t0 = time.perf_counter()
        losses.append(float(step(x, y).numpy()))     # .numpy() syncs
        times.append(time.perf_counter() - t0)
    memory("3 train steps", mem)
    step_paths = backend.paths()
    failures = kernel_failures("train", step_paths, tiny)
    if not all(np.isfinite(losses)):
        failures.append(f"non-finite loss {losses}")
    if abs(losses[0] - np.log(cfg.vocab_size)) > 1.5:
        failures.append(f"first loss {losses[0]:.3f} is not near "
                        f"ln(vocab) = {np.log(cfg.vocab_size):.3f}")
    if not losses[2] < losses[0]:
        failures.append(f"loss did not fall over 3 steps: {losses}")

    # the same model, eagerly, at the length the jitted step just ran
    hidden = model.llama(x)
    last = model.lm_head_logits(hidden[:, -1:]).numpy()
    if last.shape != (sz["batch"], 1, cfg.vocab_size) or \
            not np.isfinite(last.astype(np.float32)).all():
        failures.append(f"eager forward: bad logits {last.shape}")
    toks = model.generate(x[:, :min(128, sz["seq"] // 2)],
                          max_new_tokens=4).numpy()
    if toks.shape != (sz["batch"], 4) or toks.min() < 0 \
            or toks.max() >= cfg.vocab_size:
        failures.append(f"generate(): bad tokens {toks.tolist()}")
    memory("eager forward + generate", mem)

    return finish("train", device, dict(
        meter.report(), config=dict(sz, hidden=cfg.hidden_size,
                                    vocab=cfg.vocab_size),
        losses=[round(v, 4) for v in losses],
        first_step_s=round(times[0], 2),
        step_ms=round(min(times[1:]) * 1e3, 1),
        peak_bytes_in_use=mem[-1][2], memory=mem,
        kernels=step_paths, kernels_after_eager_and_generate=backend.paths(),
        refusals=backend.refusals(), generated=toks.tolist()), failures)


# ---- phase: serve ------------------------------------------------------------

def serve_model(spec: dict):
    """The serve phase's model; also the cluster worker's
    ``model.factory`` (``chip_smoke:serve_model`` — the worker seeds from
    ``spec["seed"]`` before calling, as :func:`run_serve` does)."""
    from paddle_tpu.models.llama import LlamaForCausalLM

    tiny = bool(spec.get("tiny"))
    sz = SIZES[tiny]["serve"]
    return LlamaForCausalLM(llama_config(tiny, sz["depth"], sz["max_len"]))


def engine_kwargs(tiny: bool) -> dict:
    sz = SIZES[tiny]["serve"]
    return {k: sz[k] for k in ("max_batch", "max_len", "page_size")}


def prompts(tiny: bool, vocab: int) -> dict:
    """Seeded prompts: ``short``/``short2`` share a length (one compiled
    program serves both), ``long`` is the 1k+ one."""
    import numpy as np

    short, long_ = SIZES[tiny]["serve"]["prompt_lens"]
    rng = np.random.RandomState(SEED)
    return {"short": rng.randint(1, vocab, short).tolist(),
            "short2": rng.randint(1, vocab, short).tolist(),
            "long": rng.randint(1, vocab, long_).tolist()}


def post(addr, body: dict, stream: bool = False):
    """One /v1/completions call; returns (token ids, seconds)."""
    import http.client

    t0 = time.perf_counter()
    conn = http.client.HTTPConnection(*addr, timeout=900)
    try:
        conn.request("POST", "/v1/completions", json.dumps(body),
                     {"Content-Type": "application/json"})
        resp = conn.getresponse()
        raw = resp.read().decode()
    finally:
        conn.close()
    if resp.status != 200:
        raise RuntimeError(f"POST /v1/completions -> {resp.status}: "
                           f"{raw[:500]}")
    if not stream:
        toks = json.loads(raw)["choices"][0]["token_ids"]
    else:
        events = [ln[len("data: "):] for ln in raw.splitlines()
                  if ln.startswith("data: ")]
        if events[-1] != "[DONE]":
            raise RuntimeError(f"SSE stream did not end in [DONE]: "
                               f"{events[-1][:200]}")
        toks = [t for e in events[:-1]
                for t in json.loads(e)["choices"][0]["token_ids"]]
    return toks, time.perf_counter() - t0


def get_json(url: str):
    import urllib.request

    with urllib.request.urlopen(url, timeout=60) as resp:
        return json.loads(resp.read())


def compare_tokens(model, prompt, got, want) -> dict:
    """Engine tokens against solo greedy ``generate()``. They must be
    identical — except that two bf16 paths may order a near-tie of the
    top two logits differently. A divergence is admitted only if the
    reference logits at that position (teacher-forced eager forward)
    separate the two tokens by less than bf16's resolution at the logits'
    magnitude; everything before it must match."""
    import numpy as np

    import paddle_tpu as paddle

    if list(got) == list(want):
        return {"match": "identical"}
    if len(got) != len(want):
        return {"match": "DIFFERENT", "why": f"{len(got)} tokens for "
                                             f"{len(want)}"}
    i = next(k for k in range(len(want)) if got[k] != want[k])
    ctx = np.asarray([list(prompt) + list(want[:i])], np.int32)
    hidden = model.llama(paddle.to_tensor(ctx))
    logits = model.lm_head_logits(hidden[:, -1:]).numpy().astype(
        np.float32)[0, 0]
    gap = float(abs(logits[want[i]] - logits[got[i]]))
    tol = float(np.abs(logits).max()) * 2.0 ** -6
    return {"match": "near-tie" if gap <= tol else "DIFFERENT", "index": i,
            "logit_gap": round(gap, 5), "bf16_tol": round(tol, 5)}


def first_token_logits(model, ids, tiny: bool):
    """Last-position logits of the engine's OWN jitted prefill program on
    ``ids``, and of the same model traced with every Pallas gate refusing
    (the XLA composites)."""
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.generation import _PrefillStep, _get_prefill_step
    from paddle_tpu.ops.pallas import backend

    n, max_len = len(ids), SIZES[tiny]["serve"]["max_len"]
    args = (jnp.asarray([ids], jnp.int32), jnp.asarray([n], jnp.int32), None)
    kern, _ = _get_prefill_step(model, n, False, rope_len=max_len)(*args)
    with backend.composites():
        ref, _ = _PrefillStep(model, n, False, rope_len=max_len)(*args)
    return (np.asarray(kern, np.float32)[0], np.asarray(ref, np.float32)[0])


def run_serve(tiny: bool) -> int:
    import threading

    import numpy as np

    device, meter = start_phase(tiny)
    import paddle_tpu as paddle
    from paddle_tpu.ops.pallas import backend
    from paddle_tpu.serving import ContinuousBatchEngine
    from paddle_tpu.serving_http import CompletionServer

    paddle.seed(SEED)
    mem = []
    model = serve_model({"tiny": tiny})
    memory("model", mem)
    cfg = model.config
    p = prompts(tiny, cfg.vocab_size)
    engine = ContinuousBatchEngine(model, **engine_kwargs(tiny))
    memory("engine", mem)
    got, secs = {}, {}

    def ask(name, prompt, stream=False):
        got[name], secs[name] = post(
            addr, {"prompt_token_ids": prompt, "max_tokens": NEW_TOKENS,
                   "stream": stream}, stream=stream)

    with CompletionServer(engine, model_name="llama3-8b-width") as srv:
        addr = srv.address
        health = get_json(f"http://{addr[0]}:{addr[1]}/health")
        ask("completion", p["short"])
        ask("stream", p["short"], stream=True)
        pair = [threading.Thread(target=ask, args=("concurrent_long",
                                                   p["long"])),
                threading.Thread(target=ask, args=("concurrent_short",
                                                   p["short2"]))]
        for t in pair:
            t.start()
        for t in pair:
            t.join()
        stats = engine.stats()
    memory("traffic", mem)
    failures = []
    if health.get("status") != "ok":
        failures.append(f"/health said {health.get('status')!r}")
    missing = {"completion", "stream", "concurrent_long",
               "concurrent_short"} - set(got)
    if missing:                     # a client thread raised: see stderr
        failures.append(f"no answer for {sorted(missing)}")
    engine_paths = backend.paths()
    failures += kernel_failures("serve", engine_paths, tiny)

    # references, after the traffic: solo greedy generate() per prompt
    want = {name: model.generate(np.asarray([p[name]], np.int32),
                                 max_new_tokens=NEW_TOKENS).numpy()[0]
            .tolist() for name in ("short", "short2", "long")}
    verdicts = {name: compare_tokens(model, p[src], got[name], want[src])
                for name, src in (("completion", "short"),
                                  ("stream", "short"),
                                  ("concurrent_short", "short2"),
                                  ("concurrent_long", "long"))
                if name in got}
    failures += [f"{name}: tokens differ from solo generate() ({v})"
                 for name, v in verdicts.items() if v["match"] == "DIFFERENT"]
    if got.get("stream") != got.get("completion"):
        failures.append("the SSE stream and the completion of one prompt "
                        "disagree")
    memory("solo generate() references", mem)

    kern, ref = first_token_logits(model, p["long"], tiny)
    memory("kernel vs composites", mem)
    scale = float(np.abs(ref).max())
    err = float(np.abs(kern - ref).max()) / scale
    cos = float(kern @ ref / (np.linalg.norm(kern) * np.linalg.norm(ref)))
    if not (np.isfinite(kern).all() and err < 0.06 and cos > 0.999):
        failures.append(f"kernel-path first-token logits vs the XLA "
                        f"composites: max err {err:.4f} of the logit range, "
                        f"cosine {cos:.5f}")

    return finish("serve", device, dict(
        meter.report(),
        config=dict(SIZES[tiny]["serve"], hidden=cfg.hidden_size,
                    vocab=cfg.vocab_size),
        request_s={k: round(v, 2) for k, v in secs.items()},
        tokens=got, vs_generate=verdicts,
        kernel_vs_composites={"max_err_over_range": round(err, 5),
                              "cosine": round(cos, 6)},
        decode_steps=stats.get("decode_steps"),
        peak_bytes_in_use=mem[-1][2], memory=mem, kernels=engine_paths,
        refusals=backend.refusals()), failures)


# ---- phase: cluster ----------------------------------------------------------

def run_cluster(tiny: bool, expect: dict) -> int:
    """This process hosts the router and never initialises a JAX backend;
    the one worker it launches holds the chip."""
    from paddle_tpu.core.build import build_native
    from paddle_tpu.serving_cluster import launch_cluster
    from paddle_tpu.utils import compile_cache

    native = build_native()      # g++ on first use; raises with its stderr
    prompt = prompts(tiny, llama_config(tiny, 1, 1).vocab_size)["short"]
    cfg = {
        "cluster": {"host": "127.0.0.1", "port": 0,
                    "model_name": "llama3-8b-width"},
        "model": {"factory": "chip_smoke:serve_model", "seed": SEED,
                  "tiny": tiny},
        "engine": engine_kwargs(tiny),
        "workers": [{"role": "unified", "count": 1}],
    }
    t0 = time.perf_counter()
    with launch_cluster(cfg, wait_timeout=600.0) as cluster:
        launch_s = time.perf_counter() - t0
        toks, first_s = post(cluster.address,
                             {"prompt_token_ids": prompt,
                              "max_tokens": NEW_TOKENS, "stream": True},
                             stream=True)
        (worker,) = cluster.pool.workers()
        health = get_json(worker["url"] + "/health")
        compiles = [e["seconds"] for e in get_json(
            worker["url"] + "/debug/events?kind=jit.compile&limit=100000"
        )["events"]]
    device = health["device"]
    failures = kernel_failures("cluster", health["kernels"]["paths"], tiny)
    if expect and toks != expect.get("tokens"):
        failures.append(f"router stream {toks} differs from the serve "
                        f"phase's {expect.get('tokens')} for the same prompt")
    # a program the serve phase compiled must come out of the persistent
    # cache here: JAX caches any compile over a second under every
    # setting, and a 16-layer program at these widths takes far longer
    slowest = max(compiles, default=0.0)
    if expect and slowest > 5.0:
        failures.append(f"the worker compiled for {slowest:.1f}s: not "
                        f"served from the persistent cache")
    from jax._src import xla_bridge

    if xla_bridge.backends_are_initialized():
        failures.append("the router's process initialised a JAX backend")
    return finish("cluster", device, dict(
        compile_s=round(sum(compiles), 2), slowest_compile_s=round(slowest, 2),
        serve_compile_s=expect.get("compile_s") if expect else None,
        launch_s=round(launch_s, 2), first_request_s=round(first_s, 2),
        tokens=toks, native_core=native,
        compile_cache=(os.environ.get("JAX_COMPILATION_CACHE_DIR")
                       or compile_cache.DEFAULT_DIR),
        kernels=health["kernels"]["paths"],
        refusals=health["kernels"]["refusals"]), failures)


# ---- phase: mesh (--chips 4) -------------------------------------------------

def run_mesh(tiny: bool) -> int:
    import gc

    import jax
    import jax.numpy as jnp
    import numpy as np

    device, meter = start_phase(tiny)
    if device["count"] < 4:
        sys.exit(f"chip_smoke --chips 4: jax found {device['count']} "
                 f"device(s)")
    import paddle_tpu as paddle
    import paddle_tpu.distributed as dist
    from paddle_tpu import optimizer as opt
    from paddle_tpu.distributed.engine import parallelize
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.ops.pallas import backend

    sz = SIZES[tiny]["mesh"]
    cfg = llama_config(tiny, sz["depth"], sz["seq"],
                       tie_word_embeddings=True,
                       fuse_linear_cross_entropy=True)
    ids = np.random.RandomState(SEED).randint(
        0, cfg.vocab_size, (sz["batch"], sz["seq"] + 1))

    def loss_fn(m, x, y):
        return m(x, labels=y)[0]

    def make_optimizer(model):
        return opt.AdamW(3e-4, parameters=model.parameters(),
                         moment_dtype="bfloat16")

    def three_steps(step):
        """(losses, best warm step ms) — the step stays alive in the
        caller, with its optimizer state, until memory has been read."""
        x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
        losses, times = [], []
        for _ in range(3):
            t0 = time.perf_counter()
            losses.append(float(step(x, y).numpy()))
            times.append(time.perf_counter() - t0)
        return losses, round(min(times[1:]) * 1e3, 1)

    def bytes_in_use():
        return [(d.memory_stats() or {}).get("bytes_in_use")
                for d in jax.devices()[:4]]

    # ---- the hybrid step: mp2 x sharding2, ZeRO-3 over the sharding axis
    strategy = dist.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 2,
                               "sep_degree": 1, "sharding_degree": 2,
                               "pp_degree": 1}
    strategy.sharding_configs = {"stage": 3}
    dist.fleet.init(is_collective=True, strategy=strategy)
    paddle.seed(SEED)
    model = dist.fleet.distributed_model(LlamaForCausalLM(cfg))
    optimizer = dist.fleet.distributed_optimizer(make_optimizer(model))
    failures, classes = [], {}
    for name, prm in model.named_parameters():
        arr = prm._array
        cls = ".".join(p for p in name.split(".") if not p.isdigit())
        per_dev = max(s.data.nbytes for s in arr.addressable_shards)
        rec = classes.setdefault(cls, {
            "spec": str(arr.sharding.spec), "bytes": arr.nbytes,
            "per_device_bytes": per_dev,
            "devices": len(arr.sharding.device_set)})
        if rec["devices"] != 4:
            failures.append(f"{name} lives on {rec['devices']} device(s)")
        # the plan shards every matrix over BOTH axes (a tensor-parallel
        # layer's mp shard plus ZeRO-3's): a quarter to a device
        if arr.ndim >= 2 and per_dev * 4 > arr.nbytes:
            failures.append(f"{name}: a device holds {per_dev} of "
                            f"{arr.nbytes} B ({arr.sharding.spec})")
    snapshot = {k: np.asarray(v._array) for k, v in model.state_dict().items()}
    built = bytes_in_use()
    step = parallelize(model, loss_fn, optimizer)
    hybrid, hybrid_ms = three_steps(step)
    stepped = bytes_in_use()
    # Mosaic kernels cannot be partitioned by GSPMD: the hybrid step is
    # EXPECTED on the XLA composites, each gate saying why (D11)
    hybrid_paths, why = backend.paths(), backend.refusals()
    failures += [f"hybrid step: {site} ran {sorted(impls)}"
                 for site, impls in hybrid_paths.items()
                 if set(impls) != {backend.XLA}]
    failures += [f"hybrid step: {site} refused for {reasons}"
                 for site, reasons in why.items() if reasons != [
                     "GSPMD-partitioned program outside a shard_map"]
                 and not tiny]

    # ---- the same weights and batch, one chip (device 0)
    del model, optimizer, step
    dist.set_hybrid_communicate_group(None)
    gc.collect()
    backend.reset_paths()
    paddle.seed(SEED)
    ref = LlamaForCausalLM(cfg)
    own = ref.state_dict()
    for k, v in snapshot.items():
        own[k]._array = jnp.asarray(v)
    del snapshot
    single, single_ms = three_steps(paddle.jit.train_step(
        ref, loss_fn, make_optimizer(ref)))
    one_chip_paths = backend.paths()
    failures += kernel_failures("mesh", one_chip_paths, tiny)
    diffs = [abs(a - b) for a, b in zip(hybrid, single)]
    if not all(np.isfinite(hybrid + single)) or max(diffs) > 0.05:
        failures.append(f"hybrid losses {hybrid} vs one chip {single}")
    return finish("mesh", device, dict(
        meter.report(), config=dict(sz, hidden=cfg.hidden_size,
                                    vocab=cfg.vocab_size,
                                    layout="mp2 x sharding2, ZeRO-3"),
        losses_hybrid=[round(v, 4) for v in hybrid],
        losses_one_chip=[round(v, 4) for v in single],
        max_abs_diff=round(max(diffs), 5),
        step_ms={"hybrid": hybrid_ms, "one_chip": single_ms},
        weight_classes=classes,
        bytes_in_use_after_build=built, bytes_in_use_after_steps=stepped,
        kernels={"hybrid": hybrid_paths, "one_chip": one_chip_paths},
        refusals=why), failures)


# ---- the parent: one child per phase, never a JAX backend --------------------

def run_child(phase: str, tiny: bool, extra: list, timeout: float):
    """Run one phase as a child in its own process group; echo its lines;
    return (exit code, its last JSON line or None). The group is killed
    on the way out whatever happened — a phase leaves no process behind."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase]
    cmd += ["--tiny"] if tiny else []
    env = dict(os.environ)
    if tiny and phase == "mesh":
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                            "platform_device_count=4").strip()
    proc = subprocess.Popen(cmd + extra, cwd=HERE, env=env, text=True,
                            stdout=subprocess.PIPE, start_new_session=True)
    last = None
    try:
        out, _ = proc.communicate(timeout=timeout)
        for line in out.splitlines():
            print(line, flush=True)
            if line.startswith("{"):
                last = json.loads(line)
    except subprocess.TimeoutExpired:
        print(f"chip_smoke: phase {phase} exceeded {timeout:.0f}s",
              file=sys.stderr)
    finally:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        proc.wait()
    return proc.returncode, last


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the mp2 x sharding2 train step and its "
                         "one-chip reference")
    ap.add_argument("--phase", choices=PHASES + ("mesh",),
                    help="run ONE phase in this process (what the parent "
                         "does for each)")
    ap.add_argument("--tiny", action="store_true",
                    help="CPU rehearsal at toy sizes; cannot pass")
    ap.add_argument("--expect", default="",
                    help="(cluster phase) JSON of the serve phase's tokens "
                         "and compile_s")
    args = ap.parse_args(argv)

    sys.path.insert(0, HERE)
    if args.phase == "train":
        return run_train(args.tiny)
    if args.phase == "serve":
        return run_serve(args.tiny)
    if args.phase == "cluster":
        return run_cluster(args.tiny,
                           json.loads(args.expect) if args.expect else {})
    if args.phase == "mesh":
        return run_mesh(args.tiny)

    deadline = time.monotonic() + BUDGET_S
    results, extra = {}, []
    for phase in (("mesh",) if args.chips == 4 else PHASES):
        if phase == "cluster":
            serve = results["serve"]
            extra = ["--expect", json.dumps({
                "tokens": serve["tokens"].get("stream"),
                "compile_s": serve["compile_s"]})]
        rc, res = run_child(phase, args.tiny, extra,
                            max(1.0, deadline - time.monotonic()))
        if rc != 0 or not res or res.get("ok") is not True:
            print(f"chip_smoke: phase {phase} failed (exit {rc}): "
                  f"{(res or {}).get('failures')}", file=sys.stderr)
            if not (args.tiny and res):
                return 1         # nothing is carried past a failed phase
        results[phase] = res
    if not all(r.get("ok") is True for r in results.values()):
        return 1                 # the tiny rehearsal: every phase ran
    device = results[phase]["device"]
    if device["platform"] != "tpu" or device["count"] != args.chips:
        print(f"chip_smoke: ran on {device}, wanted {args.chips} TPU "
              f"chip(s)", file=sys.stderr)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())
