"""Benchmark legs on one real TPU chip (``BENCH_CONFIG`` picks the leg;
default: the Llama causal-LM training step).

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
The default leg's metric is tokens/sec/chip on a compiled fwd+bwd+AdamW
step (bf16 params, f32 master weights); vs_baseline is achieved MFU /
0.40 (the north-star MFU target — the reference publishes no numbers to
beat). Compile time and step time are reported separately.

The chosen leg runs in this process, on the chip, or not at all: without
a TPU the script exits non-zero and prints no record — a CPU timing is
not a device metric. The chip's peak comes from ``device_kind`` through
the one table in ``paddle_tpu/ops/pallas/autotune.py``; a device that
table does not list is an error. ROADMAP S1 replaces these legs with the
benchmark's cells.
"""
from __future__ import annotations

import json
import os
import sys
import time

import numpy as np

_REPO = os.path.dirname(os.path.abspath(__file__))


def _model_flops_per_token(cfg) -> float:
    """6*N style estimate incl. attention term (N = ACTIVE matmul params —
    for MoE, only the routed top-k + shared experts count)."""
    h, L = cfg.hidden_size, cfg.num_hidden_layers
    inter = cfg.intermediate_size
    v = cfg.vocab_size
    kv_ratio = cfg.num_key_value_heads / cfg.num_attention_heads
    attn = 2 * h * h * (1 + 2 * kv_ratio + 1)  # q,k,v,o projections
    n_exp = getattr(cfg, "n_routed_experts", 0)
    if n_exp:
        k = cfg.num_experts_per_tok + cfg.n_shared_experts
        moe_mlp = 2 * h * (k * cfg.moe_intermediate_size) * 3
        dense_layers = min(cfg.first_k_dense_replace, L)
        params_mlp = (dense_layers * 2 * h * inter * 3
                      + (L - dense_layers) * moe_mlp)
    else:
        params_mlp = L * 2 * h * inter * 3          # swiglu gate/up/down
    emb = 2 * h * v  # lm head matmul
    params_matmul = L * attn + params_mlp + emb
    return 3 * params_matmul  # fwd (1x) + bwd (2x)


def _attn_flops_per_token(cfg, seq) -> float:
    # qk + pv, fwd+bwd; the splash kernel skips fully-masked blocks, so
    # causal attention executes ~seq/2 effective length — count what runs
    return 3 * 2 * 2 * cfg.num_hidden_layers * cfg.hidden_size * (seq / 2)


def _bench_config(name):
    from paddle_tpu.models.llama import LlamaConfig

    if name == "longctx":
        # long-context leg: the 1b-class model at seq 16384 (flash/splash
        # attention streams the KV)
        cfg = LlamaConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=16384,
            use_flash_attention=True, dtype="bfloat16")
        return cfg, 16384, 1
    if name == "moe":
        # MoE train leg: a 1b-class DeepSeekMoE/Qwen2-MoE shape — measures
        # the grouped-GEMM expert path (top-2 of 8 experts + shared expert)
        # on one chip; under a pod the same model EP-shards (moe@ep4xmp2 in
        # the driver gate)
        from paddle_tpu.models.llama_moe import LlamaMoEConfig

        cfg = LlamaMoEConfig(
            vocab_size=32000, hidden_size=2048, intermediate_size=5632,
            num_hidden_layers=8, num_attention_heads=16,
            num_key_value_heads=8, max_position_embeddings=2048,
            use_flash_attention=True, dtype="bfloat16",
            n_routed_experts=8, num_experts_per_tok=2,
            moe_intermediate_size=1408, n_shared_experts=1,
            first_k_dense_replace=1)
        return cfg, 2048, int(os.environ.get("BENCH_BATCH", "4"))
    if name == "8b":
        # Llama-3-8B shape (BASELINE.json north star), depth cut to fit one
        # chip's HBM: per-layer + lm-head dims are exactly the 8B recipe so
        # per-token math speaks to the target; tokens/s scales ~1/depth.
        # Memory recipe for 16 GB v5e (first depth-4 attempt OOM'd HBM):
        # bf16 params (f32 AdamW masters), bf16 moments, tied embeddings,
        # and the chunked fused lm-head+CE so [4096, 128256] logits never
        # materialize. Persistent state ~9.6 GB at depth 2.
        depth = int(os.environ.get("BENCH_8B_DEPTH", "2"))
        cfg = LlamaConfig(
            vocab_size=128256, hidden_size=4096, intermediate_size=14336,
            num_hidden_layers=depth, num_attention_heads=32,
            num_key_value_heads=8, max_position_embeddings=4096,
            tie_word_embeddings=True, fuse_linear_cross_entropy=True,
            use_flash_attention=True, dtype="bfloat16")
        return cfg, 4096, 1
    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=2048, use_flash_attention=True,
        dtype="bfloat16")
    batch = int(os.environ.get("BENCH_BATCH", "4"))
    return cfg, 2048, batch


def _serving_config():
    """ONE serving model shape shared by the decode and serve benches so
    their tokens/s records stay comparable."""
    from paddle_tpu.models.llama import LlamaConfig

    return LlamaConfig(
        vocab_size=32000, hidden_size=2048, intermediate_size=5632,
        num_hidden_layers=8, num_attention_heads=16, num_key_value_heads=8,
        max_position_embeddings=1024, use_flash_attention=True,
        dtype="bfloat16")


def _time_generate(model, ids, new, batch, **gen_kw):
    """Shared decode-leg timing: warm-up with the SAME max_new_tokens (the
    decode step jit is keyed on max_len, so a shorter warm-up would leave
    the timed run compiling; warm wall time = compile + one full request),
    then one timed request. Returns (tokens_per_sec, ms_per_token,
    warm_run_s, step_ms) — ms_per_token is whole-request time (prefill +
    all decode steps) per generated token; step_ms is the DECODE-phase
    latency per token (the whole-request time minus a warmed
    prefill+1-token run, over the remaining tokens) — the number the
    megakernel work moves."""
    t0 = time.perf_counter()
    model.generate(ids, max_new_tokens=new, **gen_kw)
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = model.generate(ids, max_new_tokens=new, **gen_kw)
    dt = time.perf_counter() - t0
    # prefill+first-token run (own warm-up: its decode program is keyed
    # on its own, shorter max_len) isolates the decode phase
    model.generate(ids, max_new_tokens=1, **gen_kw)
    t0 = time.perf_counter()
    model.generate(ids, max_new_tokens=1, **gen_kw)
    one_s = time.perf_counter() - t0
    step_ms = max(dt - one_s, 0.0) * 1000 / max(out.shape[1] - 1, 1)
    return (batch * out.shape[1] / dt,
            dt * 1000 / max(out.shape[1], 1), warm_s, step_ms)


def _fused_decode_enabled() -> bool:
    """BENCH_FUSED_DECODE=1 turns the fused decode-tail flag on for the
    serving legs; the record carries the state either way so fused and
    discrete captures stay distinguishable."""
    from paddle_tpu.utils.flags import get_flags, set_flags

    if os.environ.get("BENCH_FUSED_DECODE"):
        set_flags({"FLAGS_use_fused_decode_tail": True})
    return bool(get_flags("FLAGS_use_fused_decode_tail")
                ["FLAGS_use_fused_decode_tail"])


def decode_bench(devs, gen):
    """BENCH_CONFIG=decode: serving throughput on the REAL serving path —
    GQA splash flash prefill + paged-KV Pallas decode kernel (the
    block_multi_head_attention serving configuration, VERDICT r3 item 3).
    Reports generated tokens/s/chip (prefill amortized over the run)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM

    cfg = _serving_config()
    fused = _fused_decode_enabled()
    batch, prompt, new = 16, 256, 128
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, prompt)))
    if fused:
        # eager autotune pass at the decode shape: the decode steps run
        # inside jit (cost-table-read-only), so search the fused-tail
        # contraction blocks here and persist the winners first
        from paddle_tpu.ops.pallas import autotune as _at
        from paddle_tpu.ops.pallas import decode_tail as _dt

        if _at.enabled():
            import jax.numpy as jnp

            from paddle_tpu.models.llama import head_dim_of

            hd = head_dim_of(cfg)
            h, hk = cfg.num_attention_heads, cfg.num_key_value_heads
            x = jnp.zeros((batch, cfg.hidden_size), jnp.bfloat16)
            w1 = jnp.ones((cfg.hidden_size,), jnp.bfloat16)
            wq = jnp.zeros((cfg.hidden_size, h * hd), jnp.bfloat16)
            wkv = jnp.zeros((cfg.hidden_size, hk * hd), jnp.bfloat16)
            cs = jnp.zeros((batch, hd), jnp.float32)
            _dt.fused_qkv_rope(x, w1, wq, wkv, wkv, cs, cs,
                               cfg.rms_norm_eps, h, hk, hd)
            _dt.fused_epilogue(jnp.zeros((batch, h * hd), jnp.bfloat16),
                               jnp.zeros((h * hd, cfg.hidden_size),
                                         jnp.bfloat16),
                               x, w1, cfg.rms_norm_eps)
    tps, ms_tok, warm_s, step_ms = _time_generate(model, ids, new, batch,
                                                  paged=True)
    rec = {
        "metric": "llama_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no reference decode number exists
        "platform": devs[0].platform,
        "ms_per_token": round(ms_tok, 2),
        "step_ms": round(step_ms, 3),
        "fused_decode_tail": fused,
        "warm_run_s": round(warm_s, 1),
        "batch": batch,
        "config": "decode",
        "phases": _phase_leg(model),
        "kv": _kv_leg(model),
        "audit": _audit_leg(model),
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    if os.environ.get("BENCH_SPEC"):
        rec.update(_spec_decode_leg(model))
    print(json.dumps(rec))


def _phase_means(eng):
    """Mean milliseconds per step-anatomy phase from the engine's step
    profiler (docs/SERVING.md "Step anatomy & roofline accounting") —
    the bench-record form of ``GET /profile``'s phases block."""
    pay = eng.profiler.payload(top_k=0)
    return {name: round(info["mean_ms"], 3)
            for name, info in pay["phases"].items()}


def _phase_leg(model):
    """Per-phase step anatomy for the decode leg: ``_time_generate``
    times ``model.generate`` (no engine), so a short profiler-enabled
    ContinuousBatchEngine run supplies the record's phase breakdown."""
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = model.config
    slots, max_len, new = 8, 512, 64
    rng = np.random.RandomState(0)
    eng = ContinuousBatchEngine(model, max_batch=slots,
                                max_len=max_len, page_size=16)

    def load():
        for i in range(slots):
            eng.add_request(rng.randint(0, cfg.vocab_size, (8 + i,)), new)
        eng.run_until_done()

    load()                  # warm-up with the profiler off: the phase
    eng.profiler.enable()   # means must not be compile-dominated
    load()
    return _phase_means(eng)


def _kv_summary(eng):
    """The ``kv`` block a bench record carries: pages peak, prefix hit
    ratio, and the measured-vs-preflight byte ratio off the engine's
    KV atlas (docs/SERVING.md "KV & memory atlas") — the capacity
    baseline the quantized-serving work lands against."""
    pay = eng.kvatlas.payload()
    pre = pay["preflight"]["kv_cache_bytes"]
    peak_bytes = pay["pages_peak"] * pay["bytes_per_page"]
    return {
        "kv_pages_peak": pay["pages_peak"],
        "kv_bytes_peak": peak_bytes,
        "prefix_hit_ratio": round(pay["prefix"]["hit_ratio"], 3),
        "capacity_bytes": pay["capacity_bytes"],
        "preflight_kv_cache_bytes": pre,
        "measured_vs_preflight": (round(peak_bytes / pre, 4)
                                  if pre else None),
    }


def _kv_leg(model):
    """KV-atlas capacity numbers for the decode leg: a short
    atlas-enabled engine run over prompts sharing a page-aligned prefix
    (so the prefix-reuse index sees traffic)."""
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = model.config
    slots, max_len, new = 8, 512, 64
    rng = np.random.RandomState(0)
    eng = ContinuousBatchEngine(model, max_batch=slots, max_len=max_len,
                                page_size=16, enable_prefix_cache=True)
    eng.kvatlas.enable()
    shared = rng.randint(0, cfg.vocab_size, (32,))
    for i in range(slots):
        ids = np.concatenate(
            [shared, rng.randint(0, cfg.vocab_size, (4 + i,))])
        eng.add_request(ids, new)
    eng.run_until_done()
    return _kv_summary(eng)


def _audit_leg(model):
    """Correctness-sentinel numbers for a bench record: a short engine
    run with shadow audits at rate 1.0 (every finished request replayed
    on the reference path by the audit worker), against an identical
    audit-off run for the hot-path overhead delta. The divergence
    count must stay 0 (docs/SERVING.md "Correctness sentinel")."""
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = model.config
    slots, max_len, new = 8, 512, 32
    rng = np.random.RandomState(0)
    prompts = [rng.randint(1, cfg.vocab_size, (8 + i,))
               for i in range(slots)]

    def run(audit_rate):
        eng = ContinuousBatchEngine(model, max_batch=slots,
                                    max_len=max_len, page_size=16)
        if audit_rate:
            eng.sentinel.enable(audit_rate=audit_rate)
            eng.sentinel.start()
        for ids in prompts:
            eng.add_request(ids, new)
        t0 = time.perf_counter()
        eng.run_until_done()
        return eng, time.perf_counter() - t0

    run(0.0)                       # warm-up: compiles are shared
    _, t_off = run(0.0)            # steady-state audit-off baseline
    eng, t_on = run(1.0)
    # drain: every finished request reaches a verdict before we count
    deadline = time.time() + 120.0
    fed = eng.sentinel.federated()
    while (fed["audit_pass"] + fed["audit_diverged"]
           + fed["audit_skipped"] < len(prompts)
           and time.time() < deadline):
        time.sleep(0.05)
        fed = eng.sentinel.federated()
    eng.sentinel.stop()
    return {
        "audit_pass": int(fed["audit_pass"]),
        "audit_diverged": int(fed["audit_diverged"]),
        "audit_skipped": int(fed["audit_skipped"]),
        "logprob_drift_last": float(fed["audit_drift"]),
        # engine-loop wall delta with audits enqueueing at rate 1.0 —
        # the replay itself runs post-finish on the audit worker
        "overhead_pct": round(100.0 * (t_on - t_off) / t_off, 2)
        if t_off else None,
    }


def _spec_decode_leg(model):
    """BENCH_SPEC=1 rider on the decode leg: engine speculative decode
    (n-gram drafter, BENCH_SPEC_K chunk width) on a REPETITIVE prompt —
    the drafter's best case, so ``accepted_tokens_per_dispatch`` records
    the acceptance ceiling of the multi-token step next to the one-token
    step_ms."""
    from paddle_tpu.serving import ContinuousBatchEngine

    spec_k = int(os.environ.get("BENCH_SPEC_K", "4"))
    cfg = model.config
    slots, max_len, new = 8, 512, 96
    pat = np.tile(np.asarray([3, 5, 7, 9]), 16)

    def run():
        eng = ContinuousBatchEngine(model, max_batch=slots,
                                    max_len=max_len, page_size=16,
                                    speculative_k=spec_k)
        for _ in range(slots):
            eng.add_request(pat % cfg.vocab_size, new)
        eng.run_until_done()
        return eng.stats()

    run()  # warm-up: compiles the prefill bucket + the spec verify step
    t0 = time.perf_counter()
    st = run()
    dt = time.perf_counter() - t0
    return {
        "accepted_tokens_per_dispatch": round(
            st["accepted_tokens_per_dispatch"], 3),
        "spec": {
            "k": spec_k,
            "dispatches": st["spec_dispatches"],
            "accepted_tokens": st["spec_accepted_tokens"],
            "emitted_tokens": st["spec_emitted_tokens"],
            "tokens_per_sec": round(st["tokens_generated"] / dt, 1),
            "spec_step_ms": round(dt * 1000 / max(st["decode_steps"], 1),
                                  3),
        },
    }


def mla_decode_bench(devs, gen):
    """BENCH_CONFIG=mla: decode throughput through the COMPRESSED latent
    cache (DeepSeek MLA, models/deepseek.py). To isolate the cache-layout
    effect from kernel differences, the SAME leg also times a GQA model of
    identical hidden/depth/FFN through the SAME dense-cache code path
    (paged=False) — `mla_vs_gqa_dense` is the clean 576-vs-2048
    cache-floats-per-token comparison; the headline value is the MLA
    tokens/s."""
    import paddle_tpu as paddle
    from paddle_tpu.models.deepseek import (DeepseekV2Config,
                                            DeepseekV2ForCausalLM)
    from paddle_tpu.models.llama import LlamaForCausalLM

    base = _serving_config()
    cfg = DeepseekV2Config(
        vocab_size=base.vocab_size, hidden_size=base.hidden_size,
        intermediate_size=base.intermediate_size,
        num_hidden_layers=base.num_hidden_layers,
        num_attention_heads=base.num_attention_heads,
        num_key_value_heads=base.num_attention_heads,
        max_position_embeddings=base.max_position_embeddings,
        use_flash_attention=True, dtype="bfloat16",
        kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64,
        v_head_dim=128, n_routed_experts=0,
        first_k_dense_replace=10 ** 9)  # dense FFN: isolate attention
    # longer context than the decode leg: the cache-layout effect is
    # proportional to cached tokens, so give the comparison a real
    # cache to stream (768+128 fits the serving config's max_pos 1024)
    batch, prompt, new = 16, 768, 128
    paddle.seed(0)
    model = DeepseekV2ForCausalLM(cfg)
    ids = paddle.to_tensor(
        np.random.randint(0, cfg.vocab_size, (batch, prompt)))
    # eager autotune pass at the decode-buffer shape: the decode steps
    # run inside jit (cache-read-only), so measure the kernel's
    # T-block candidates here and persist the winner first
    from paddle_tpu.ops.pallas import autotune as _at
    from paddle_tpu.ops.pallas import mla_decode as _pmd

    if _at.enabled():
        import jax.numpy as jnp

        T = prompt + new
        ql = jnp.zeros((batch, cfg.num_attention_heads,
                        cfg.kv_lora_rank), jnp.float32)
        qp = jnp.zeros((batch, cfg.num_attention_heads, 128),
                       jnp.float32)
        ckv = jnp.zeros((batch, T, cfg.kv_lora_rank), jnp.bfloat16)
        kpe = jnp.zeros((batch, T, 128), jnp.bfloat16)
        if _pmd.supported(ql, ckv, kpe):
            _pmd.mla_decode_attention(ql, qp, ckv, kpe, T - 1)
    tps, ms_tok, warm_s, step_ms = _time_generate(model, ids, new, batch)
    # GQA control through the IDENTICAL dense-cache decode path
    paddle.seed(0)
    gqa = LlamaForCausalLM(base)
    gqa_ids = paddle.to_tensor(
        np.random.randint(0, base.vocab_size, (batch, prompt)))
    gqa_tps, _, _, _ = _time_generate(gqa, gqa_ids, new, batch)
    rec = {
        "metric": "mla_decode_tokens_per_sec_per_chip",
        "value": round(tps, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no reference MLA number exists
        "platform": devs[0].platform,
        "ms_per_token": round(ms_tok, 2),
        "step_ms": round(step_ms, 3),
        "warm_run_s": round(warm_s, 1),
        "gqa_dense_tokens_per_sec": round(gqa_tps, 1),
        "mla_vs_gqa_dense": round(tps / gqa_tps, 3) if gqa_tps else None,
        "config": "mla",
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))


def serve_bench(devs, gen):
    """BENCH_CONFIG=serve: continuous-batching throughput — a saturated
    ContinuousBatchEngine slot pool (mixed prompt/budget mix), generated
    tokens/s/chip including admission/prefill overhead (the
    block_multi_head_attention serving configuration driven in-flight)."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = _serving_config()
    fused = _fused_decode_enabled()
    slots, max_len, n_req = 16, 512, 48
    paddle.seed(0)
    quantized = bool(os.environ.get("BENCH_SERVE_INT8"))
    int4 = bool(os.environ.get("BENCH_SERVE_INT4"))
    mla = bool(os.environ.get("BENCH_SERVE_MLA"))
    if sum(map(bool, (mla, quantized, int4))) > 1:
        raise ValueError(
            "BENCH_SERVE_MLA / BENCH_SERVE_INT8 / BENCH_SERVE_INT4 are "
            "separate legs — a mixed record would persist under the wrong "
            "key; set at most one")
    if mla:
        # latent-mode engine leg: DeepSeek MLA at the serving scale —
        # per-slot compressed-latent rows instead of the paged K/V pool
        from paddle_tpu.models.deepseek import (DeepseekV2Config,
                                                DeepseekV2ForCausalLM)

        cfg = DeepseekV2Config(
            vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
            intermediate_size=cfg.intermediate_size,
            num_hidden_layers=cfg.num_hidden_layers,
            num_attention_heads=cfg.num_attention_heads,
            num_key_value_heads=cfg.num_attention_heads,
            max_position_embeddings=cfg.max_position_embeddings,
            use_flash_attention=True, dtype="bfloat16",
            kv_lora_rank=512, qk_nope_head_dim=128,
            qk_rope_head_dim=64, v_head_dim=128, n_routed_experts=0,
            first_k_dense_replace=10 ** 9)
        model = DeepseekV2ForCausalLM(cfg)
    else:
        model = LlamaForCausalLM(cfg)
    if quantized or int4:
        # weight-only serving legs: int8 = 1 byte/element, int4 = 0.5
        # bytes/element through HBM (decode is weight-bandwidth-bound,
        # so this is the knob)
        from paddle_tpu.nn.quant import quantize_for_serving

        model, _ = quantize_for_serving(
            model, algo=("weight_only_int4" if int4
                         else "weight_only_int8"))
    rng = np.random.RandomState(0)
    # BENCH_SPEC=1: the engine runs multi-token speculative steps (n-gram
    # drafter) — the record carries accepted_tokens_per_dispatch so spec
    # and plain captures stay distinguishable
    spec_k = (int(os.environ.get("BENCH_SPEC_K", "4"))
              if os.environ.get("BENCH_SPEC") and not mla else None)
    last_stats = {}

    engines = []

    def run():
        eng = ContinuousBatchEngine(model, max_batch=slots, max_len=max_len,
                                    page_size=16, speculative_k=spec_k)
        # per-phase step anatomy + KV-atlas capacity numbers ride on
        # the record (both off by default; the timed run's engine is
        # engines[-1])
        eng.profiler.enable()
        eng.kvatlas.enable()
        engines.clear()
        engines.append(eng)
        for i in range(n_req):
            plen = [64, 128, 200, 256][i % 4]
            budget = [96, 128, 160][i % 3]
            eng.add_request(rng.randint(0, cfg.vocab_size, (plen,)), budget)
        done = eng.run_until_done()
        last_stats.clear()
        last_stats.update(eng.stats())
        return sum(v.size for v in done.values())

    run()  # warm-up: compiles the bucketed prefills + the decode step
    from paddle_tpu.observability import catalog as _cat

    label = "decoder"
    n0 = _cat.SERVING_DECODE_STEP.count(engine=label)
    s0 = _cat.SERVING_DECODE_STEP.sum(engine=label)
    t0 = time.perf_counter()
    total = run()
    dt = time.perf_counter() - t0
    # decode-step latency straight off the serving histogram the engine
    # already exports — the same series a production scrape would read
    n_steps = _cat.SERVING_DECODE_STEP.count(engine=label) - n0
    step_ms = ((_cat.SERVING_DECODE_STEP.sum(engine=label) - s0)
               * 1000 / n_steps if n_steps else 0.0)
    rec = {
        "metric": ("mla_serve_tokens_per_sec_per_chip" if mla
                   else "llama_serve_tokens_per_sec_per_chip"),
        "value": round(total / dt, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no reference serving number exists
        "platform": devs[0].platform,
        "step_ms": round(step_ms, 3),
        "fused_decode_tail": fused,
        "requests": n_req,
        "slots": slots,
        "speculative_k": spec_k,
        "accepted_tokens_per_dispatch": round(
            last_stats.get("accepted_tokens_per_dispatch", 0.0), 3),
        "config": ("serve_mla" if mla
                   else "serve_int4" if int4
                   else "serve_int8" if quantized else "serve"),
        "phases": _phase_means(engines[-1]) if engines else {},
        "kv": _kv_summary(engines[-1]) if engines else {},
        "audit": _audit_leg(model),
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))


def mixed_serve_bench(devs, gen):
    """BENCH_CONFIG=serve BENCH_SERVE_MIXED=1: the SLO-aware scheduler's
    target workload — long-prompt arrivals landing over live short
    decodes. Runs the same scenario with chunked prefill ON and OFF and
    records TTFT for the long prompts plus inter-token p50/p99 for the
    live decodes; the headline value is the chunked p99 inter-token
    latency, with the monolithic run beside it so the stall reduction is
    one record."""
    import paddle_tpu as paddle
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = _serving_config()
    slots, max_len, chunk = 8, 1024, 128
    short_len, short_budget = 32, 192
    long_len, long_budget, n_long = 704, 32, 3
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    rng = np.random.RandomState(0)
    shorts = [rng.randint(0, cfg.vocab_size, (short_len,))
              for _ in range(slots - 1)]
    longs = [rng.randint(0, cfg.vocab_size, (long_len,))
             for _ in range(n_long)]

    def run_once(chunk_tokens):
        eng = ContinuousBatchEngine(
            model, max_batch=slots, max_len=max_len, page_size=16,
            prefill_chunk_tokens=chunk_tokens)
        times = {}

        def on_token(rid, tok, done):
            times.setdefault(rid, []).append(time.perf_counter())

        live = [eng.add_request(p, short_budget, on_token=on_token)
                for p in shorts]
        # live decodes under way before the first long prompt arrives
        while not all(len(times.get(r, ())) >= 2 for r in live):
            eng.step()
        t_sub, ttfts = {}, []
        for p in longs:
            rid = eng.add_request(p, long_budget, on_token=on_token)
            t_sub[rid] = time.perf_counter()
            # let the arrival land over the live decodes before the next
            for _ in range(4):
                eng.step()
        eng.run_until_done()
        for rid, t0 in t_sub.items():
            ttfts.append(times[rid][0] - t0)
        gaps = []
        for r in live:
            ts = times[r]
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        gaps = np.asarray(gaps)
        return {
            "inter_token_p50_ms": round(float(np.percentile(gaps, 50))
                                        * 1000, 3),
            "inter_token_p99_ms": round(float(np.percentile(gaps, 99))
                                        * 1000, 3),
            "inter_token_max_ms": round(float(gaps.max()) * 1000, 3),
            "ttft_long_p50_ms": round(float(np.percentile(ttfts, 50))
                                      * 1000, 3),
        }

    # warm-up BOTH variants: the monolithic long-prompt bucket and the
    # chunk/suffix programs compile here, so neither measured run pays a
    # compile inside an inter-token gap
    run_once(chunk)
    run_once(None)
    chunked = run_once(chunk)
    mono = run_once(None)
    rec = {
        "metric": "llama_serve_mixed_inter_token_p99_ms",
        "value": chunked["inter_token_p99_ms"],
        "unit": "ms",
        "vs_baseline": 0.0,  # no reference mixed-load number exists
        "platform": devs[0].platform,
        "chunk_tokens": chunk,
        "chunked": chunked,
        "monolithic": mono,
        "stall_ratio_p99": round(
            mono["inter_token_p99_ms"]
            / max(chunked["inter_token_p99_ms"], 1e-9), 2),
        "slots": slots,
        "config": "serve_mixed",
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))


def load_bench(devs, gen):
    """BENCH_CONFIG=load: the traffic-replay & saturation harness
    (paddle_tpu.loadgen) against an in-process serving_http server —
    a QPS sweep locates the saturation knee, then a 2x-knee overload
    run with a priority/SLO class mix records goodput-under-SLO, p99
    TTFT per class, and the shed/429/504 accounting. The headline value
    is goodput tokens/s at the knee."""
    import paddle_tpu as paddle
    from paddle_tpu.loadgen import (WorkloadSpec, find_knee, run_workload,
                                    sweep)
    from paddle_tpu.models.llama import LlamaForCausalLM
    from paddle_tpu.serving import ContinuousBatchEngine
    from paddle_tpu.serving_http import CompletionServer

    cfg = _serving_config()
    slots, max_len, max_queue = 16, 512, 64
    qps_list = (8, 16, 32, 64)
    duration, prompt_rng, tok_rng = 5.0, (32, 128), (16, 64)
    slo_hi, slo_lo = 4000.0, 1500.0
    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    eng = ContinuousBatchEngine(model, max_batch=slots, max_len=max_len,
                                page_size=16, max_queue=max_queue,
                                aging_s=2.0)
    spec = WorkloadSpec(
        qps=qps_list[0], duration_s=duration, process="poisson",
        prompt_tokens=prompt_rng, max_tokens=tok_rng,
        classes=((0, slo_hi, 0.2), (1, slo_hi, 0.5), (2, slo_lo, 0.3)),
        vocab_size=cfg.vocab_size, seed=0)
    with CompletionServer(eng) as srv:
        host, port = srv.address
        url = f"http://{host}:{port}"
        # warm the prompt-length buckets so the sweep measures serving,
        # not first-compile time
        run_workload(url, spec.replace(qps=2.0, duration_s=1.0))
        curve = sweep(url, spec, qps_list)
        knee = curve["knee_qps"]
        overload = run_workload(url, spec.replace(qps=2.0 * knee))
        knee_pt = next(p for p in curve["points"]
                       if p["offered_qps"] == knee)
    rec = {
        "metric": "llama_load_goodput_tokens_per_sec",
        "value": knee_pt["goodput"]["tokens_per_s"],
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no reference load harness exists
        "platform": devs[0].platform,
        "knee_qps": knee,
        "goodput_rps_at_knee": knee_pt["goodput"]["requests_per_s"],
        "ttft_p99_ms_at_knee": knee_pt["ttft_ms"]["p99"],
        "sweep": [{
            "qps": p["offered_qps"],
            "goodput_ratio": p["goodput"]["ratio"],
            "ttft_p99_ms": p["ttft_ms"]["p99"],
            "rejected_429": p["rejected_429"],
            "shed_504": p["shed_504"],
        } for p in curve["points"]],
        "overload_2x_knee": {
            "qps": overload["offered_qps"],
            "goodput_ratio": overload["goodput"]["ratio"],
            "rejected_429": overload["rejected_429"],
            "shed_504": overload["shed_504"],
            "http_5xx": overload["http_5xx"],
            "timed_out": overload["timed_out"],
            "ttft_p99_ms_top_class":
                overload["by_priority"]["0"]["ttft_ms"]["p99"],
            "schedule_digest": overload["schedule_digest"],
        },
        "slots": slots,
        "max_queue": max_queue,
        "config": "load",
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))


def cp_bench(devs, gen):
    """BENCH_CONFIG=cp: context-parallel ring attention (splash kernel per
    hop — VERDICT r4 item 3) at long sequence, reporting ring-vs-direct-
    splash overhead. The 'sep' mesh spans all local devices: degree 1 on
    the single bench chip (wrapper + streaming-combine overhead over the
    same splash kernel), degree 8 on the CPU test mesh (real ppermute
    hops). Forward+backward is timed — the backward rides the ring's
    custom-VJP einsum recompute path."""
    import functools

    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.collective import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.context_parallel import ring_attention
    from paddle_tpu.ops.pallas import flash_attention as pf

    n = len(devs)
    b, s, h, hkv, d = 1, 16384, 16, 8, 128
    dtype = jnp.bfloat16
    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(b, s, h, d), dtype)
    k = jnp.asarray(rng.randn(b, s, hkv, d), dtype)
    v = jnp.asarray(rng.randn(b, s, hkv, d), dtype)
    mesh = Mesh(np.asarray(devs), ("sep",))
    spec = P(None, "sep", None, None)
    ring = shard_map(
        functools.partial(ring_attention, axis_name="sep", causal=True,
                          impl="splash"),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    ring_fwd = jax.jit(ring)
    ring_train = jax.jit(jax.grad(
        lambda q_, k_, v_: ring(q_, k_, v_).astype(jnp.float32).sum(),
        argnums=(0, 1, 2)))
    splash_fwd = jax.jit(functools.partial(
        pf.flash_attention_bshd, causal=True))

    def timed(fn, *args, reps=5):
        out = fn(*args)  # compile
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(*args)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / reps

    fwd_s = timed(ring_fwd, q, k, v)
    train_s = timed(ring_train, q, k, v)
    direct_s = timed(splash_fwd, q, k, v)
    # global tokens / time / chips — comparable with the other *_per_chip
    # metrics (n == 1 on the single bench chip)
    tokens_per_sec = b * s / train_s / n
    rec = {
        "metric": "cp_ring_attention_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # the reference has no CP at all (SURVEY §2.7)
        "platform": devs[0].platform,
        "sep_degree": n,
        "seq": s,
        "fwd_ms": round(fwd_s * 1000, 2),
        "fwd_bwd_ms": round(train_s * 1000, 2),
        "direct_splash_fwd_ms": round(direct_s * 1000, 2),
        "ring_fwd_overhead": round(fwd_s / direct_s, 3),
        "config": "cp",
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))


def pp_bench(devs, gen):
    """BENCH_CONFIG=pp: the host pipeline scheduler's dispatch cost —
    pp2 train_batch (1F1B by default; BENCH_PP_SCHEDULE=VPP/ZBH1/FThenB)
    vs ONE jitted train step of the same model on the same chip(s). On
    one chip both stages share the device, so the gap IS the scheduler +
    per-hop device_put overhead that micro-batch overlap must amortize
    on a pod (VERDICT r4 weak #8: previously unmeasured)."""
    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.llama import (LlamaConfig, LlamaForCausalLM,
                                         LlamaForCausalLMPipe)

    cfg = LlamaConfig(
        vocab_size=32000, hidden_size=1024, intermediate_size=2816,
        num_hidden_layers=8, num_attention_heads=8,
        num_key_value_heads=8, max_position_embeddings=1024,
        use_flash_attention=True, dtype="bfloat16")
    seq, batch, m, reps = 1024, 8, 4, 5
    sched = os.environ.get("BENCH_PP_SCHEDULE", "1F1B")
    # interleaving needs V > 1 chunks per stage (PipelineParallel validates
    # at construction); every other schedule runs plain 2-stage
    vpp = 2 if sched.upper() in ("VPP", "INTERLEAVE", "INTERLEAVED") else None
    ids = np.random.randint(0, cfg.vocab_size, (batch, seq + 1))
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])

    def loss_fn(mm, a, b):
        loss, _ = mm(a, labels=b)
        return loss

    paddle.seed(0)
    model = LlamaForCausalLM(cfg)
    step = paddle.jit.train_step(
        model, loss_fn, opt.AdamW(3e-4, parameters=model.parameters()))
    step(x, y).numpy()  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        loss = step(x, y)
    loss.numpy()
    mono_s = (time.perf_counter() - t0) / reps

    from paddle_tpu.distributed.pipeline import PipelineParallel

    paddle.seed(0)
    pipe = LlamaForCausalLMPipe(
        cfg, num_stages=2,
        **({"num_virtual_pipeline_stages": vpp} if vpp else {}))
    pp = PipelineParallel(pipe, accumulate_steps=m, schedule=sched)
    popt = opt.AdamW(3e-4, parameters=pipe.parameters())
    pp.train_batch([x, y], popt)  # compile all stage programs
    t0 = time.perf_counter()
    for _ in range(reps):
        ploss = pp.train_batch([x, y], popt)
    float(np.asarray(ploss))
    pp_s = (time.perf_counter() - t0) / reps

    tokens = batch * seq
    rec = {
        "metric": "pp_host_scheduler_tokens_per_sec_per_chip",
        "value": round(tokens / pp_s, 1),
        "unit": "tokens/s",
        "vs_baseline": 0.0,  # no reference number; the ratio is the result
        "platform": devs[0].platform,
        "schedule": sched,
        "micro_batches": m,
        "pp_step_ms": round(pp_s * 1000, 1),
        "monolithic_step_ms": round(mono_s * 1000, 1),
        "scheduler_overhead": round(pp_s / mono_s, 3),
        # per-schedule record keys: a ZBH1 capture must not mask (or block
        # re-capture of) the default 1F1B row — same pattern as serve_int8
        "config": "pp" if sched.upper() == "1F1B"
                  else f"pp_{sched.lower()}",
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    print(json.dumps(rec))


def main():
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py measures the TPU: jax found platform "
                 f"{dev.platform!r} ({dev.device_kind}); no record is "
                 f"printed for another device")
    # always-on forensics for bench runs: crashes (an OOM'd config, a
    # hung collective) leave a rank-suffixed incident bundle — event
    # ring, metrics snapshot, thread stacks — instead of a bare
    # traceback. PD_INCIDENT_DIR overrides the destination.
    from paddle_tpu.observability import flightrecorder as _frec

    _frec.get_recorder().enable()
    _frec.get_reporter().activate(
        os.environ.get("PD_INCIDENT_DIR", "incidents"))
    with _frec.incident_scope("bench"):
        return _main_inner()


def _main_inner():
    import jax

    import paddle_tpu as paddle
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.llama import LlamaForCausalLM

    from paddle_tpu.ops.pallas import autotune as _at

    devs = jax.devices()
    gen = devs[0].device_kind
    _, peak = _at.roofline_caps(gen)    # unknown device kind: raises

    cfg_name = os.environ.get("BENCH_CONFIG", "1b")
    if cfg_name == "decode":
        return decode_bench(devs, gen)
    if cfg_name == "mla":
        return mla_decode_bench(devs, gen)
    if cfg_name == "serve":
        if os.environ.get("BENCH_SERVE_MIXED"):
            return mixed_serve_bench(devs, gen)
        return serve_bench(devs, gen)
    if cfg_name == "load":
        return load_bench(devs, gen)
    if cfg_name == "cp":
        return cp_bench(devs, gen)
    if cfg_name == "pp":
        return pp_bench(devs, gen)
    cfg, seq, batch = _bench_config(cfg_name)

    paddle.seed(0)
    if getattr(cfg, "n_routed_experts", 0):
        from paddle_tpu.models.llama_moe import LlamaMoEForCausalLM

        model = LlamaMoEForCausalLM(cfg)
    else:
        model = LlamaForCausalLM(cfg)
    moment_dtype = "bfloat16" if cfg_name == "8b" else None
    optimizer = opt.AdamW(3e-4, parameters=model.parameters(),
                          moment_dtype=moment_dtype)

    def loss_fn(m, x, y):
        loss, _ = m(x, labels=y)
        return loss

    # eager autotune pass at this config's kernel shapes: measures the
    # splash / fused-norm block-geometry candidates once, persists the
    # winners (.pd_autotune.json), and logs the chosen blocks; the
    # train-step trace below then reads the cache (tracing can't time)
    from paddle_tpu.ops.pallas import autotune as _at

    if _at.enabled():
        import jax.numpy as jnp

        from paddle_tpu.ops.pallas import flash_attention as _pf
        from paddle_tpu.ops.pallas import fused_norm as _fn

        from paddle_tpu.models.llama import head_dim_of

        hd = head_dim_of(cfg)
        qa = jnp.zeros((batch, seq, cfg.num_attention_heads, hd),
                       jnp.bfloat16)
        ka = jnp.zeros((batch, seq, cfg.num_key_value_heads, hd),
                       jnp.bfloat16)
        if _pf.supported(qa, ka, ka):
            _pf.flash_attention_bshd(
                qa, ka, ka, causal=True,
                window=getattr(cfg, "sliding_window", None))
        xa = jnp.zeros((batch, seq, cfg.hidden_size), jnp.bfloat16)
        _fn.add_rms_norm(xa, xa, jnp.ones((cfg.hidden_size,),
                                          jnp.bfloat16))
        _fn.rms_norm(xa, jnp.ones((cfg.hidden_size,), jnp.bfloat16))
        print(f"# autotune cache: {_at.get_cache().stats()} "
              f"at {_at.cache_path()}", file=sys.stderr)

    step = paddle.jit.train_step(model, loss_fn, optimizer)

    ids = np.random.randint(0, cfg.vocab_size, (batch, seq + 1))
    x = paddle.to_tensor(ids[:, :-1])
    y = paddle.to_tensor(ids[:, 1:])

    t0 = time.perf_counter()
    loss = step(x, y)  # compile
    loss.numpy()
    compile_s = time.perf_counter() - t0

    n_steps = 10
    prof_dir = None
    if os.environ.get("BENCH_PROFILE"):
        # XLA-level step attribution: a tensorboard
        # trace of the timed loop under profiler_log/<config>/
        prof_dir = os.path.join(_REPO, "profiler_log", f"bench_{cfg_name}")
        jax.profiler.start_trace(prof_dir)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        loss = step(x, y)
    loss.numpy()  # sync
    dt = (time.perf_counter() - t0) / n_steps
    if prof_dir is not None:
        jax.profiler.stop_trace()
        print(f"# profile written to {prof_dir}", file=sys.stderr)

    tokens_per_step = batch * seq
    tokens_per_sec = tokens_per_step / dt
    flops_per_token = _model_flops_per_token(cfg) + _attn_flops_per_token(cfg, seq)
    mfu = tokens_per_sec * flops_per_token / peak

    # publish the measured step through the unified observability layer:
    # the same train_step_seconds / tokens-per-sec / device-memory series
    # a production train loop emits (hapi StepTimer), so bench records and
    # live telemetry read off one catalog
    from paddle_tpu.observability import StepTimer, catalog as _cat

    StepTimer().observe(dt, n_samples=batch, n_tokens=tokens_per_step)
    mem_in_use = int(_cat.DEVICE_MEM_IN_USE.value())

    rec = {
        "metric": "llama_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(mfu / 0.40, 4),
        "platform": devs[0].platform,
        "mfu": round(mfu, 4),
        "step_ms": round(dt * 1000, 1),
        "compile_s": round(compile_s, 1),
        "device_mem_bytes": mem_in_use,
        "config": cfg_name,
        "tpu_gen": gen,
        "measured_at": time.strftime("%Y-%m-%dT%H:%M:%S"),
    }
    for env in ("PD_SPLASH_BLOCK_Q", "PD_SPLASH_BLOCK_KV", "BENCH_BATCH"):
        if os.environ.get(env):
            rec[env.lower()] = os.environ[env]  # keep the best reproducible
    print(json.dumps(rec))
    print(f"# step={dt*1000:.1f}ms compile={compile_s:.1f}s mfu={mfu:.3f} gen={gen} "
          f"loss={float(loss.numpy()):.3f} params={model.num_parameters()/1e6:.0f}M "
          f"platform={devs[0].platform}", file=sys.stderr)


if __name__ == "__main__":
    main()
