"""Kernels of the main paths, compiled for a DESCRIBED TPU v5e chip at
Llama-3-8B / DeepSeek-V2-Lite widths — no chip attached.

Interpret mode cannot see what the TPU compiler refuses (a block that does
not tile, a gather Mosaic has no rule for, too much VMEM): three kernels
here had passed every interpret-mode test and were refused. The TPU
compiler is installed with jax, and ``jax.experimental.topologies``
describes a chip it can compile for, so these compiles run in tier-1 on
the CPU at ~2 s each and guard every later change to a kernel.

A compile that passes is not a chip run; ``chip_smoke.py`` is.

Also here: the regression tests for the trace probe these kernels' model
relies on (``jax.core.trace_state_clean`` is gone from jax 0.9.0 and its
four users swallowed the AttributeError).
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # compiler logs: not /tmp

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

import paddle_tpu as paddle
from paddle_tpu.ops.pallas import (append_attention, backend, decode_tail,
                                   flash_attention, fused_norm, mla_decode)

# Llama-3-8B attention/hidden widths; decode batch and cache of the
# chip_smoke serve phase
HID, H, HK, D = 4096, 32, 8, 128
B, T, PAGE = 8, 4096, 16
SEQ = 4096
# DeepSeek-V2-Lite absorbed decode: 16 heads, kv_lora 512, rope 64 (padded)
MLA_H, MLA_R, MLA_DR = 16, 512, 128
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the persistent compile cache off: an
    entry written for a described device cannot be read back without one,
    and the retry warns."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e topology here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _rms_norm(x, w):
    return fused_norm.rms_norm(x, w, 1e-5)


def _add_rms_norm(x, r, w):
    return fused_norm.add_rms_norm(x, r, w, 1e-5)


def _fused_rope(x, cos, sin):
    return fused_norm.fused_rope(x, cos, sin)


def _flash_fwd(q, k, v):
    return flash_attention.flash_attention_bshd(q, k, v, causal=True)


def _flash_grad(q, k, v):
    return jax.grad(
        lambda q_, k_, v_: _flash_fwd(q_, k_, v_).astype(F32).sum(),
        argnums=(0, 1, 2))(q, k, v)


def _paged(q, kp, vp, lengths, page_indices):
    from paddle_tpu.generation import paged_decode_attention

    return paged_decode_attention(q, kp, vp, lengths, page_indices)


def _epilogue(attn, wo, res, wn):
    return decode_tail.fused_epilogue(attn, wo, res, wn, 1e-5)


def _qkv_rope(x, wn, wq, wk, wv, cos, sin):
    return decode_tail.fused_qkv_rope(x, wn, wq, wk, wv, cos, sin, 1e-5,
                                      H, HK, D)


def _append(q, kb, vb, pos, allowed):
    return append_attention.append_attention(q, kb, vb, pos, allowed)


def _mla(ql, qp, ckv, kpe, pos, allowed):
    return mla_decode.mla_decode_attention(ql, qp, ckv, kpe, pos, allowed)


_PAGES = B * T // PAGE

# name -> (fn, [(shape, dtype), ...], pallas custom calls expected)
KERNELS = {
    "rms_norm": (_rms_norm, [((1, SEQ, HID), BF16), ((HID,), BF16)]),
    "rms_norm_decode": (_rms_norm, [((B, 1, HID), BF16), ((HID,), BF16)]),
    "add_rms_norm": (_add_rms_norm, [((1, SEQ, HID), BF16),
                                     ((1, SEQ, HID), BF16), ((HID,), BF16)]),
    "fused_rope": (_fused_rope, [((1, SEQ, H, D), BF16), ((SEQ, D), F32),
                                 ((SEQ, D), F32)]),
    "flash_attention_fwd": (_flash_fwd, [((1, SEQ, H, D), BF16),
                                         ((1, SEQ, HK, D), BF16),
                                         ((1, SEQ, HK, D), BF16)]),
    "flash_attention_grad": (_flash_grad, [((1, SEQ, H, D), BF16),
                                           ((1, SEQ, HK, D), BF16),
                                           ((1, SEQ, HK, D), BF16)]),
    "paged_attention": (_paged, [((B, H, D), BF16),
                                 ((HK, _PAGES, PAGE, D), BF16),
                                 ((HK, _PAGES, PAGE, D), BF16),
                                 ((B,), I32), ((B, T // PAGE), I32)]),
    "fused_epilogue": (_epilogue, [((B, H * D), BF16), ((H * D, HID), BF16),
                                   ((B, HID), BF16), ((HID,), BF16)]),
    "fused_qkv_rope": (_qkv_rope, [((B, HID), BF16), ((HID,), BF16),
                                   ((HID, H * D), BF16),
                                   ((HID, HK * D), BF16),
                                   ((HID, HK * D), BF16),
                                   ((B, D), F32), ((B, D), F32)]),
    # a prefix hit's suffix or a chunk of a chunked admission (one prompt,
    # pad mask on) and generate(prefill_chunk_size=) against the full
    # decode cache; the engine's admission prefill runs splash (below)
    "append_attention_prefill": (_append, [((1, 256, H, D), BF16),
                                           ((1, 256, HK, D), BF16),
                                           ((1, 256, HK, D), BF16),
                                           ((), I32), ((1, 256), jnp.bool_)]),
    "append_attention_chunk": (_append, [((B, 16, H, D), BF16),
                                         ((B, T, HK, D), BF16),
                                         ((B, T, HK, D), BF16),
                                         ((), I32), ((B, T), jnp.bool_)]),
    "mla_decode": (_mla, [((B, MLA_H, MLA_R), BF16),
                          ((B, MLA_H, MLA_DR), BF16),
                          ((B, T, MLA_R), BF16), ((B, T, MLA_DR), BF16),
                          ((B,), I32), ((B, T), jnp.bool_)]),
}


def _lower_for_chip(fn, args, donate=()):
    """Optimized HLO of ``fn`` compiled for the described chip, with the
    record of kernel paths reset first."""
    backend.reset_paths()
    # conftest asks for "highest" matmul precision (numpy-parity tests);
    # the chip runs the default, and Mosaic refuses an fp32 contraction
    # of bf16 operands
    with backend.lowering_target("tpu"), \
            jax.default_matmul_precision("default"):
        # a fresh jit per compile: traces are cached per function, and one
        # made for the CPU carries interpret=True
        return jax.jit(lambda *a: fn(*a), donate_argnums=donate).lower(
            *args).compile().as_text()


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(chip, name):
    fn, specs = KERNELS[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=chip)
            for shape, dtype in specs]
    # the Mosaic kernel is in the program — not the interpreter's XLA ops,
    # not a composite a gate fell back to
    assert "tpu_custom_call" in _lower_for_chip(fn, args), name
    took = backend.paths()
    assert not any(backend.INTERPRET in impls or backend.XLA in impls
                   for impls in took.values()), took


# the chat cell's decode step (benchmarks/configs/mistral-7b-v0.3-d20):
# 8 KV heads, 2048 pages of 16 (batch 16 x max_len 2048), 32 query heads
POOL_PAGES, CHAT_B = 2048, 16


def _pool_copies(hlo, pool_shape):
    """HLO lines whose RESULT is a ``copy`` of the pool's shape: the
    relayout XLA wraps around a scatter into a pool on a TPU."""
    dims = ",".join(map(str, pool_shape))
    return [line.strip()[:160] for line in hlo.splitlines()
            if re.search(r"= \w+\[%s\]\S* copy\(" % dims, line)]


@pytest.mark.parametrize("d,dtype", [(128, BF16), (128, F32), (256, BF16)],
                         ids=["d128-bf16", "d128-f32", "d256-bf16"])
def test_decode_write_leaves_no_copy_of_a_pool(chip, d, dtype):
    """``paged_cached_attention`` at S == 1 with both pools donated: the
    pool goes parameter -> kv_page_write -> paged_attention -> result in
    ONE layout. With the XLA scatter in its place the same program held
    four ``copy`` ops of the pool's shape (67 MB each at d128-bf16), 46%
    of the chat cell's busy time on the chip (PERF.md, PR 28)."""
    from paddle_tpu.generation import paged_cached_attention

    pool = (HK, POOL_PAGES, PAGE, d)
    pages_per_row = POOL_PAGES // CHAT_B
    specs = [((CHAT_B, 1, H, d), dtype), ((CHAT_B, 1, HK, d), dtype),
             ((CHAT_B, 1, HK, d), dtype),
             ((pages_per_row * PAGE, d), F32),
             ((pages_per_row * PAGE, d), F32),
             (pool, dtype), (pool, dtype),
             ((CHAT_B, pages_per_row), I32), ((CHAT_B,), I32)]
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=chip)
            for shape, dt in specs]
    hlo = _lower_for_chip(
        lambda *a: paged_cached_attention(*a, PAGE), args, donate=(5, 6))
    assert _pool_copies(hlo, pool) == []
    # donation reached the compiled program: results 1 and 2 ARE
    # parameters 5 and 6
    alias = re.search(r"input_output_alias=\{(.*?)\}, entry", hlo).group(1)
    assert re.findall(r"\{(\d)\}: \((\d),", alias) == [("1", "5"),
                                                         ("2", "6")]
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line and "= " in line]
    assert sum("%kv_page_write" in c.split("=")[0] for c in calls) == 2
    assert sum("%paged_attention" in c.split("=")[0] for c in calls) == 1
    assert backend.paths() == {"kv_page_write": {backend.PALLAS: 2},
                               "paged_attention": {backend.PALLAS: 1}}


@pytest.mark.parametrize("d", [64, 96, 192])
def test_decode_write_falls_back_at_a_width_that_would_copy(chip, d):
    """At a head width that is not whole lanes Mosaic compiles the page
    write, but XLA wraps it in two copies of the pool (described compile,
    PR 28): the gate refuses, the scatter stays and still compiles."""
    from paddle_tpu.generation import _write_decode_rows

    pool = (HK, POOL_PAGES, PAGE, d)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=chip)
            for shape, dt in [(pool, BF16), ((CHAT_B,), I32),
                              ((CHAT_B,), I32), ((CHAT_B, HK, d), BF16)]]
    hlo = _lower_for_chip(_write_decode_rows, args, donate=(0,))
    assert "kv_page_write" not in hlo and "tpu_custom_call" not in hlo
    assert backend.paths() == {"kv_page_write": {backend.XLA: 1}}
    (reason,) = backend.refusals()["kv_page_write"]
    assert "128 lanes" in reason


# the engine's admission prefill (benchmarks/configs/mistral-7b-v0.3-d20):
# ONE prompt padded on the right to its bucket, 32 query / 8 KV heads of 128
def _admission_attention(S, masked):
    """``cached_attention`` as ``_PrefillStep`` reaches it through the
    attention layer: fresh bucket-sized buffers, the static ``prefill``
    marker, ``use_flash``; with ``masked`` also the pad mask the engine
    built until PR 34."""
    from paddle_tpu.generation import cached_attention

    def call(q, k, v, cos, sin, k_buf, v_buf, *allowed):
        return cached_attention(q, k, v, cos, sin, k_buf, v_buf, 0,
                                *allowed, use_flash=True, prefill=True)

    specs = [((1, S, H, D), BF16), ((1, S, HK, D), BF16),
             ((1, S, HK, D), BF16), ((2048, D), F32), ((2048, D), F32),
             ((1, S, HK, D), BF16), ((1, S, HK, D), BF16)]
    if masked:
        specs.append(((1, S), jnp.bool_))
    return call, specs


def _score_tensors(hlo):
    """f32 array shapes in the HLO with two dimensions of 1024 or more:
    a dense [.., S, T] attention score tensor."""
    found = set()
    for dims in re.findall(r"f32\[([\d,]+)\]", hlo):
        if sum(int(n) >= 1024 for n in dims.split(",")) >= 2:
            found.add(dims)
    return sorted(found)


@pytest.mark.parametrize("S", [128, 512, 1024, 2048])
def test_admission_prefill_attention_is_one_splash_call(chip, S):
    """No pad mask, so every bucket the kernel tiles takes it: one splash
    call, and no O(S^2) f32 score tensor anywhere in the program."""
    call, specs = _admission_attention(S, masked=False)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=chip)
            for shape, dt in specs]
    hlo = _lower_for_chip(call, args)
    calls = [line for line in hlo.splitlines()
             if "tpu_custom_call" in line and "= " in line]
    assert len(calls) == 1 and "splash" in calls[0].split("=")[0], calls
    assert backend.paths() == {"flash_attention": {backend.PALLAS: 1}}
    assert _score_tensors(hlo) == []


def test_a_pad_mask_on_the_admission_prefill_costs_the_kernel(chip):
    """What the mask cost at the 2048 bucket until PR 34, pinned so that
    a change that hands the engine's prefill a mask again is caught here:
    splash is never asked, ``append_attention`` refuses 4 heads a group x
    2048 rows, and the f32 composite materialises [8, 4, 2048, 2048]
    scores (537 MB a layer)."""
    call, specs = _admission_attention(2048, masked=True)
    args = [jax.ShapeDtypeStruct(shape, dt, sharding=chip)
            for shape, dt in specs]
    hlo = _lower_for_chip(call, args)
    assert "tpu_custom_call" not in hlo
    assert backend.paths() == {"append_attention": {backend.XLA: 1}}
    assert backend.refusals()["append_attention"] == [
        "4 x 2048 score rows exceed 2048"]
    assert _score_tensors(hlo) != []


def test_backend_target_decides_interpret_not_the_argument():
    """On a TPU platform ``interpret`` is never true, whatever a caller
    passes; off-TPU a gate admits a kernel only on request."""
    q = jnp.zeros((1, 128, 4, 128), F32)
    assert not flash_attention.supported(q, q, q)              # CPU, no ask
    assert flash_attention.supported(q, q, q, interpret=True)  # CPU, asked
    with backend.lowering_target("tpu"):
        assert not backend.interpret_mode()
        assert flash_attention.supported(q, q, q)
        with backend.composites():
            assert not flash_attention.supported(q, q, q)
    assert backend.interpret_mode()


def test_gate_refusal_is_recorded_with_its_reason():
    backend.reset_paths()
    q = jnp.zeros((1, 100, 4, 128), F32)        # 100 is not 128-aligned
    with backend.lowering_target("tpu"):
        assert not flash_attention.supported(q, q, q)
    assert backend.paths()["flash_attention"] == {backend.XLA: 1}
    (reason,) = backend.refusals()["flash_attention"]
    assert "128" in reason


def test_unknown_device_kind_has_no_peak():
    from paddle_tpu.ops.pallas import autotune

    assert autotune.roofline_caps("TPU v5 lite") == (8.19e11, 197e12)
    with pytest.raises(ValueError, match="no peak"):
        autotune.roofline_caps("TPU v9 imaginary")


# ---- fault 1: the trace probe ------------------------------------------------

def test_is_tracing_is_true_under_jit():
    from paddle_tpu.jit import is_tracing

    seen = []

    @jax.jit
    def f(x):
        seen.append((is_tracing(), paddle.in_dynamic_mode()))
        return x + 1

    f(jnp.ones(()))
    assert seen == [(True, False)]
    assert not is_tracing() and paddle.in_dynamic_mode()


def test_jitted_step_then_eager_forward_on_one_model():
    """The rope table memoised during a jitted train step must not be a
    tracer: the same model then runs eagerly and generates."""
    from paddle_tpu import optimizer as opt
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    cfg = LlamaConfig.tiny(num_hidden_layers=1)
    model = LlamaForCausalLM(cfg)
    step = paddle.jit.train_step(
        model, lambda m, x, y: m(x, labels=y)[0],
        opt.AdamW(1e-3, parameters=model.parameters()))
    ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (2, 17))
    x, y = paddle.to_tensor(ids[:, :-1]), paddle.to_tensor(ids[:, 1:])
    assert np.isfinite(float(step(x, y).numpy()))
    assert not any(isinstance(t._array, jax.core.Tracer)
                   for pair in model.llama._rope_cache.values()
                   for t in pair)
    logits = model(x)
    assert logits.shape == [2, 16, cfg.vocab_size]
    assert np.isfinite(logits.numpy()).all()
    assert model.generate(x, max_new_tokens=3).shape == [2, 3]


def test_batch_norm_running_stats_do_not_leak_tracers():
    import paddle_tpu.nn as nn

    bn = nn.BatchNorm1D(4)
    before = bn._mean.numpy().copy()

    @jax.jit
    def f(a):
        return bn(paddle.to_tensor(a))._array

    f(jnp.ones((3, 4)))
    assert not isinstance(bn._mean._array, jax.core.Tracer)
    np.testing.assert_array_equal(bn._mean.numpy(), before)


def test_constraint_inside_a_trace_emits_a_sharding_constraint():
    from jax.sharding import PartitionSpec

    from paddle_tpu.distributed.parallel_layers import _constraint
    from paddle_tpu.distributed.process_mesh import ProcessMesh

    mesh = ProcessMesh(np.arange(4).reshape(2, 2),
                       dim_names=["sharding", "mp"])
    x = jnp.ones((4, 8, 16))
    assert _constraint(x, mesh, last="mp") is x         # eager: untouched
    jaxpr = jax.make_jaxpr(lambda a: _constraint(a, mesh, last="mp"))(x)
    (eqn,) = [e for e in jaxpr.eqns if e.primitive.name
              == "sharding_constraint"]
    spec = eqn.params["sharding"].spec
    # only the feature dim is the layer's: the batch dim stays open to
    # the dp/sharding axes instead of being gathered on every linear
    assert spec[-1] == "mp"
    assert spec[0] is PartitionSpec.UNCONSTRAINED


def test_bundled_paged_kernel_gets_a_prescaled_query(monkeypatch):
    """jax's paged_attention kernel applies no softmax scale; the
    reference path divides the scores by sqrt(D). Found on the chip: the
    first decoded token onwards disagreed with every reference."""
    import jax.experimental.pallas.ops.tpu.paged_attention as bundled

    from paddle_tpu.generation import (_paged_attention_ref,
                                       paged_decode_attention)

    rng = np.random.RandomState(0)
    q = jnp.asarray(rng.randn(2, 4, 128), F32)
    kp = jnp.asarray(rng.randn(2, 8, 16, 128), F32)
    vp = jnp.asarray(rng.randn(2, 8, 16, 128), F32)
    lengths = jnp.asarray([40, 17], I32)
    pages = jnp.arange(8, dtype=I32).reshape(2, 4)

    def unscaled_kernel(q_, k_, v_, lengths_, pages_, **kw):
        # what the bundled kernel computes: softmax(q.k) with NO scale
        return _paged_attention_ref(q_ * np.sqrt(128.0), k_, v_, lengths_,
                                    pages_)

    monkeypatch.setattr(bundled, "paged_attention", unscaled_kernel)
    with backend.lowering_target("tpu"):
        got = paged_decode_attention(q, kp, vp, lengths, pages)
    want = _paged_attention_ref(q, kp, vp, lengths, pages)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
