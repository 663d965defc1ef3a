"""Graph-rule (jaxpr-level) analysis tests — the second pdlint layer.

Three layers, mirroring tests/test_static_analysis.py:

1. **Known-bad fixtures** — each graph rule has a tiny program carrying
   exactly the hazard it exists for (indivisible spec, bf16→f32 upcast,
   data-dependent shape, baked const, dtype-lying OpDecl) and must
   produce exactly the expected finding; known-good twins produce zero.
2. **Preflight** — ``Engine.preflight()`` rejects an indivisible
   sharding / over-budget model with a structured ``PreflightReport``
   instead of a compile-time crash, and admits the clean build.
3. **The tier-1 gate** — ``scripts/pdlint.py --json --baseline
   .pdlint_baseline.json --graph`` exits 0 over the fast zoo set; the
   zoo-wide sweep (``PDLINT_GRAPH_SCOPE=full``) is ``slow``-marked.
"""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import analysis
from paddle_tpu.analysis.graph import (
    PreflightError, cost, dtype_flow, op_dtypes, preflight_model, retrace,
    shard_spec, solver, trace_fn, trace_layer, spec, zoo,
)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# tracer harness
# ---------------------------------------------------------------------------

def test_trace_fn_captures_jaxpr():
    t = trace_fn(lambda x: x * 2.0, spec((4,), jnp.float32))
    assert t.ok and t.error is None
    assert t.n_data_inputs == 1
    assert len(t.closed_jaxpr.jaxpr.eqns) >= 1


def test_trace_fn_captures_error_instead_of_raising():
    t = trace_fn(lambda x: jnp.nonzero(x)[0], spec((8,), jnp.float32))
    assert not t.ok
    assert t.error is not None


def test_trace_layer_params_are_invars_not_consts():
    """The functional state must ride as invars (so shard specs map onto
    them) — a Layer whose weights trace as baked consts would defeat
    both the shard-spec rule and the retrace const check."""
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    model = LlamaForCausalLM(LlamaConfig.tiny(dtype="bfloat16"))
    t = trace_layer(model, spec((1, 8), jnp.int32))
    assert t.ok
    assert t.param_names == sorted(t.param_avals)
    n_invars = len(t.closed_jaxpr.jaxpr.invars)
    # params + rng key + input_ids
    assert n_invars == len(t.param_names) + 1 + 1
    assert t.param_bytes() > 0
    # bf16 build: the bulk of the state is 2-byte
    emb = t.param_avals["llama.embed_tokens.weight"]
    assert str(emb.dtype) == "bfloat16"


# ---------------------------------------------------------------------------
# shard-spec: annotation validity
# ---------------------------------------------------------------------------

def test_shard_spec_indivisible_dim_one_finding():
    msgs = shard_spec.check_partition_spec(
        ("mp", None), {"dp": 2, "mp": 4}, (6, 8), what="param w")
    assert len(msgs) == 1
    assert "not divisible" in msgs[0]


def test_shard_spec_unknown_axis():
    msgs = shard_spec.check_partition_spec(
        ("tp", None), {"dp": 2}, (8, 8))
    assert len(msgs) == 1 and "unknown mesh axis" in msgs[0]


def test_shard_spec_double_sharded_axis():
    msgs = shard_spec.check_partition_spec(
        ("mp", "mp"), {"mp": 2}, (8, 8))
    assert len(msgs) == 1 and "assigned to both" in msgs[0]


def test_shard_spec_valid_spec_zero_findings():
    assert shard_spec.check_partition_spec(
        ("dp", ("mp",)), {"dp": 2, "mp": 4}, (8, 16)) == []


def test_shard_spec_over_rank():
    msgs = shard_spec.check_partition_spec(
        ("dp", "mp", None), {"dp": 2, "mp": 2}, (8,))
    assert len(msgs) == 1 and "rank" in msgs[0]


def test_check_placements_against_process_mesh():
    import paddle_tpu.distributed as dist
    from paddle_tpu.distributed.placements import Replicate, Shard

    mesh = dist.ProcessMesh([[0, 1], [2, 3]], dim_names=["dp", "mp"])
    # dim 1 of size 6 over mp=2: divisible -> clean
    assert shard_spec.check_placements(
        [Replicate(), Shard(1)], mesh, (4, 6)) == []
    # dim 1 of size 5: indivisible -> exactly one finding
    msgs = shard_spec.check_placements([Replicate(), Shard(1)], mesh, (4, 5))
    assert len(msgs) == 1 and "not divisible" in msgs[0]
    # Shard dim out of range
    msgs = shard_spec.check_placements([Shard(3)], mesh, (4, 5))
    assert len(msgs) == 1 and "invalid for rank" in msgs[0]


# ---------------------------------------------------------------------------
# shard-spec: GSPMD-lite propagation
# ---------------------------------------------------------------------------

def _propagated(fn, in_specs, axis_sizes, *arg_specs):
    t = trace_fn(fn, *arg_specs)
    assert t.ok
    return shard_spec.propagate(t, in_specs, axis_sizes)


def test_propagate_reshape_split_minor_flags_reshard():
    """Merging a sharded minor dim away forces an all-to-all: the
    known-bad propagation fixture."""
    finds = _propagated(lambda x: x.reshape(128), {0: (None, "mp")},
                        {"mp": 2}, spec((8, 16), jnp.float32))
    assert len(finds) == 1
    path, prim, msg = finds[0]
    assert prim == "reshape" and "reshard" in msg or "all-to-all" in msg


def test_propagate_reshape_major_survives():
    finds = _propagated(lambda x: x.reshape(2, 4, 16), {0: ("mp", None)},
                        {"mp": 2}, spec((8, 16), jnp.float32))
    assert finds == []


def test_propagate_elementwise_conflict():
    finds = _propagated(lambda x, y: x + y,
                        {0: ("mp", None), 1: ("dp", None)},
                        {"mp": 2, "dp": 2},
                        spec((8, 8), jnp.float32), spec((8, 8), jnp.float32))
    assert len(finds) == 1
    assert "reshard" in finds[0][2]


def test_propagate_elementwise_axis_reuse_conflict():
    """One mesh axis landing on two dims of the merged operand layout is
    equally impossible — GSPMD strips it from one dim."""
    finds = _propagated(lambda x, y: x + y,
                        {0: ("mp", None), 1: (None, "mp")}, {"mp": 2},
                        spec((8, 8), jnp.float32), spec((8, 8), jnp.float32))
    assert len(finds) == 1


def test_propagate_matched_elementwise_clean():
    finds = _propagated(lambda x, y: x * y,
                        {0: ("mp", None), 1: ("mp", None)}, {"mp": 2},
                        spec((8, 8), jnp.float32), spec((8, 8), jnp.float32))
    assert finds == []


def test_propagate_dot_contracting_mismatch():
    def f(x, y):
        return x @ y

    finds = _propagated(f, {0: (None, "mp"), 1: ("dp", None)},
                        {"mp": 2, "dp": 2},
                        spec((4, 8), jnp.float32), spec((8, 16), jnp.float32))
    assert len(finds) == 1
    assert finds[0][1] == "dot_general"
    assert "contracting" in finds[0][2]


def test_propagate_dot_matched_contracting_clean():
    """Both contracting dims on the same axis: GSPMD all-reduces the
    partial output — expected Megatron row-parallel behavior, no
    finding."""
    finds = _propagated(lambda x, y: x @ y,
                        {0: (None, "mp"), 1: ("mp", None)}, {"mp": 2},
                        spec((4, 8), jnp.float32), spec((8, 16), jnp.float32))
    assert finds == []


def test_propagate_dot_batch_dim_mismatch_flags():
    """Batch dims sharded over different axes: one operand re-tiles
    before the batched matmul — the case that used to fall through."""
    def bmm(a, b):
        return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))))

    finds = _propagated(bmm, {0: ("dp", None, None), 1: ("mp", None, None)},
                        {"dp": 2, "mp": 2},
                        spec((4, 8, 16), jnp.float32),
                        spec((4, 16, 8), jnp.float32))
    assert len(finds) == 1
    assert finds[0][1] == "dot_general" and "batch dims" in finds[0][2]


def test_propagate_dot_batch_dims_matched_clean():
    def bmm(a, b):
        return jax.lax.dot_general(a, b, (((2,), (1,)), ((0,), (0,))))

    finds = _propagated(bmm, {0: ("dp", None, None), 1: ("dp", None, None)},
                        {"dp": 2},
                        spec((4, 8, 16), jnp.float32),
                        spec((4, 16, 8), jnp.float32))
    assert finds == []


def _kv_scatter(pages, idx, new):
    # the paged-KV write shape: pages [heads, n_pages, d], update rows
    # landing at traced page indices
    return pages.at[:, idx].set(new)


def test_propagate_scatter_paged_kv_pattern_clean():
    """Scatter into unsharded page slots of a head-sharded pool — the
    engine's KV write path — keeps the operand layout, zero findings."""
    t = trace_fn(_kv_scatter, spec((4, 16, 8), jnp.float32),
                 spec((3,), jnp.int32), spec((4, 3, 8), jnp.float32))
    assert any(e.primitive.name.startswith("scatter")
               for e in t.closed_jaxpr.jaxpr.eqns)
    assert shard_spec.propagate(t, {0: ("mp", None, None)}, {"mp": 2}) == []


def test_propagate_scatter_into_sharded_dim_flags():
    def f(pages, idx, new):
        return pages.at[idx].set(new)

    t = trace_fn(f, spec((16, 8), jnp.float32), spec((3,), jnp.int32),
                 spec((3, 8), jnp.float32))
    finds = shard_spec.propagate(t, {0: ("mp", None)}, {"mp": 2})
    assert len(finds) == 1
    assert finds[0][1].startswith("scatter")
    assert "all-to-all" in finds[0][2]


def test_propagate_gather_vocab_parallel_is_expected_collective():
    """An embedding lookup into a vocab-sharded table is the PLANNED
    Megatron collective: an expected event with a byte charge for the
    solver — never a lint finding."""
    t = trace_fn(lambda w, ids: w[ids], spec((64, 16), jnp.float32),
                 spec((2, 8), jnp.int32))
    events = shard_spec.propagate_events(t, {0: ("mp", None)}, {"mp": 2})
    assert len(events) == 1
    e = events[0]
    assert e.expected and e.primitive == "gather" and e.bytes > 0
    assert shard_spec.propagate(t, {0: ("mp", None)}, {"mp": 2}) == []
    # hidden-sharded table: the lookup is local, nothing to charge
    assert shard_spec.propagate_events(
        t, {0: (None, "mp")}, {"mp": 2}) == []


def test_propagate_one_sided_contraction_charged_not_flagged():
    """x @ W with only W's contracting dim sharded: GSPMD slices the
    replicated side locally and all-reduces the partial output — an
    expected charge (the cost of 'row' plans), not a finding."""
    t = trace_fn(lambda x, w: x @ w, spec((4, 8), jnp.float32),
                 spec((8, 16), jnp.float32))
    events = shard_spec.propagate_events(t, {1: ("mp", None)}, {"mp": 2})
    assert len(events) == 1
    assert events[0].expected and "all-reduce" in events[0].message
    assert shard_spec.propagate(t, {1: ("mp", None)}, {"mp": 2}) == []


def test_zoo_sharded_llama_layout_clean():
    """The Megatron layout the zoo declares for llama must validate and
    propagate clean — this pins the mesh-divisibility choice (mp=2 over
    2 kv heads) the zoo comment documents."""
    e = zoo.entry("llama-sharded")
    t = zoo.traced("llama-sharded")
    assert t.ok
    in_specs = {}
    for name in t.param_names:
        aval = t.param_avals[name]
        sp = e.shard.spec_for(name, len(aval.shape))
        if sp is None:
            continue
        assert shard_spec.check_partition_spec(
            sp, e.shard.axis_sizes, aval.shape, what=name) == []
        in_specs[t.invar_index_of_param(name)] = \
            shard_spec.normalize_spec(sp, len(aval.shape))
    assert in_specs, "the layout matched no parameters"
    assert shard_spec.propagate(t, in_specs, e.shard.axis_sizes) == []


def test_zoo_sharded_llama_mp4_flags_attention_reshard():
    """Widening the same layout to mp=4 must flag: the per-param specs
    stay divisible (64 % 4 == 0) but splitting 2 kv heads over 4 shards
    makes the attention head reshape force an all-to-all — the hazard
    only the PROPAGATION walk can see, exactly the zoo comment's case."""
    e = zoo.entry("llama-sharded")
    t = zoo.traced("llama-sharded")
    axis_sizes = {"dp": 2, "mp": 4}
    in_specs = {}
    for name in t.param_names:
        aval = t.param_avals[name]
        sp = e.shard.spec_for(name, len(aval.shape))
        if sp is None:
            continue
        assert shard_spec.check_partition_spec(
            sp, axis_sizes, aval.shape, what=name) == []
        in_specs[t.invar_index_of_param(name)] = \
            shard_spec.normalize_spec(sp, len(aval.shape))
    finds = shard_spec.propagate(t, in_specs, axis_sizes)
    assert any(prim == "reshape" for _p, prim, _m in finds), finds


def test_check_spmd_notes_flags_lying_decl():
    class Lying:
        name = "fake_reduceish"
        spmd = "elementwise"

        @staticmethod
        def impl(x):
            return jnp.sum(x)

    class Honest:
        name = "fake_relu"
        spmd = "elementwise"

        @staticmethod
        def impl(x):
            return jnp.maximum(x, 0)

    problems = shard_spec.check_spmd_notes([Lying, Honest])
    assert len(problems) == 1
    assert problems[0][0] == "fake_reduceish"


# ---------------------------------------------------------------------------
# dtype-promotion
# ---------------------------------------------------------------------------

_F32_TABLE = jnp.ones((4,), jnp.float32)


def test_dtype_mix_with_independent_f32_table_one_finding():
    """THE bf16→f32 fixture: promotion (not the author) chooses f32
    where a bf16-derived value meets an f32 buffer."""
    def f(x):
        return x.astype(jnp.float32) * _F32_TABLE

    ups = dtype_flow.find_upcasts(trace_fn(f, spec((4,), jnp.bfloat16)))
    assert len(ups) == 1
    assert ups[0].kind == "mix" and ups[0].primitive == "mul"
    assert "promotion chose float32" in ups[0].message()


def test_dtype_direct_upcast_one_finding():
    def f(x, w):
        return jax.lax.dot_general(
            x, w, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    ups = dtype_flow.find_upcasts(trace_fn(
        f, spec((4, 8), jnp.bfloat16), spec((8, 4), jnp.bfloat16)))
    assert len(ups) == 1
    assert ups[0].kind == "direct" and ups[0].primitive == "dot_general"


def test_dtype_deliberate_island_zero_findings():
    """astype up → compute among derived values and weak scalars →
    astype down: the authored-island pattern (norms, softmax) must not
    flag."""
    def f(x):
        xf = x.astype(jnp.float32)
        v = jnp.mean(xf * xf) + 1e-6
        return (xf * jax.lax.rsqrt(v)).astype(jnp.bfloat16)

    assert dtype_flow.find_upcasts(
        trace_fn(f, spec((8,), jnp.bfloat16))) == []


def test_dtype_scalar_independent_never_flags():
    """A non-weak f32 *scalar* (np.float32 scale, -inf fill) joining a
    derived island carries no bytes and is not the reason the island is
    f32."""
    def f(x):
        return jnp.maximum(x.astype(jnp.float32) * np.float32(0.125),
                           np.float32(-np.inf))

    assert dtype_flow.find_upcasts(
        trace_fn(f, spec((8,), jnp.bfloat16))) == []


def test_dtype_bool_mask_convert_is_island_neutral():
    """int/bool→f32 converts (masks, one_hot) picked f32 to FOLLOW the
    island — not independent f32 bytes."""
    def f(x, m):
        s = x.astype(jnp.float32)
        return s + m.astype(jnp.float32)

    assert dtype_flow.find_upcasts(trace_fn(
        f, spec((8,), jnp.bfloat16), spec((8,), jnp.bool_))) == []


def test_dtype_allowlist_suppresses_primitive():
    def f(x):
        return x.astype(jnp.float32) * _F32_TABLE

    t = trace_fn(f, spec((4,), jnp.bfloat16))
    assert len(dtype_flow.find_upcasts(t)) == 1
    assert dtype_flow.find_upcasts(t, allow=("mul",)) == []


def test_dtype_mix_found_inside_pjit_sub_jaxpr():
    @jax.jit
    def inner(x):
        return x.astype(jnp.float32) * _F32_TABLE

    def f(x):
        return inner(x)

    ups = dtype_flow.find_upcasts(trace_fn(f, spec((4,), jnp.bfloat16)))
    assert len(ups) == 1
    # the jitted call's equation is "pjit" in older JAX, "jit" in newer
    assert any(seg in ("jit", "pjit")
               for seg in ups[0].eqn_path.split("."))


def test_zoo_fast_models_dtype_clean():
    """Known-good zoo builds produce zero dtype findings under their
    declared allowlists (rope's f32 tables are the documented island)."""
    for e in zoo.entries():
        if e.shard is not None:
            continue
        t = zoo.traced(e.name)
        assert t.ok, f"{e.name} does not trace: {t.error}"
        ups = dtype_flow.find_upcasts(t, allow=e.allow_upcast)
        assert ups == [], (
            f"{e.name}: {[u.message() for u in ups]}")


def test_whisper_encoder_pos_follows_model_dtype():
    """Regression for the finding this PR fixed: the sinusoidal encoder
    position table stayed float32 in a bf16 build and upcast every
    encoder activation at the stem."""
    from paddle_tpu.models.whisper import (WhisperConfig,
                                           WhisperForConditionalGeneration)

    m = WhisperForConditionalGeneration(WhisperConfig.tiny(dtype="bfloat16"))
    w = m.model.encoder_pos.weight
    assert str(w.dtype) in ("bfloat16", "paddle.bfloat16"), str(w.dtype)


# ---------------------------------------------------------------------------
# retrace-hazard
# ---------------------------------------------------------------------------

def test_retrace_data_dependent_shape_one_finding():
    t = trace_fn(lambda x: jnp.nonzero(x)[0], spec((8,), jnp.float32))
    hazards = retrace.find_hazards(t)
    assert len(hazards) == 1
    key, msg = hazards[0]
    assert key == "trace-error"
    assert "data-dependent" in msg


def test_retrace_weak_scalar_const_flagged():
    c = jnp.asarray(2.0)  # weak f32 scalar — a closed-over Python number

    def f(x):
        return x * c

    hazards = retrace.find_hazards(trace_fn(f, spec((4,), jnp.float32)))
    assert len(hazards) == 1
    assert "weak-typed scalar" in hazards[0][1]


def test_retrace_large_const_flagged():
    big = jnp.zeros((1 << 19,), jnp.float32)  # 2 MiB baked table

    def f(x):
        return x + big[:4]

    hazards = retrace.find_hazards(trace_fn(f, spec((4,), jnp.float32)))
    assert len(hazards) == 1
    assert "baked into every specialization" in hazards[0][1]


def test_retrace_clean_fn_zero_findings():
    assert retrace.find_hazards(
        trace_fn(lambda x: x * 2.0, spec((4,), jnp.float32))) == []


def test_specialization_stats_hook():
    """The jit wiring: StaticFunction counts compiled specializations
    and live_specialization_findings turns a blow-up into a finding."""
    from paddle_tpu import jit as pjit

    @pjit.to_static
    def poly(x):
        return x * 2.0

    import paddle_tpu

    for n in (4, 8, 16):  # three shape buckets -> three specializations
        poly(paddle_tpu.ones([n]))
    stats = pjit.specialization_stats()
    name = [k for k in stats if "poly" in k]
    assert name and stats[name[0]] >= 3
    found = retrace.live_specialization_findings(threshold=3)
    assert any("poly" in n for n, _c in found)
    assert retrace.live_specialization_findings(threshold=10 ** 6) == []


# ---------------------------------------------------------------------------
# preflight-cost
# ---------------------------------------------------------------------------

def test_cost_dot_flops_exact():
    def f(x, w):
        return x @ w

    rep = cost.estimate(trace_fn(f, spec((4, 8), jnp.float32),
                                 spec((8, 16), jnp.float32)))
    assert rep.flops == 2 * 4 * 16 * 8
    assert rep.output_bytes == 4 * 16 * 4
    assert rep.eqns >= 1
    assert rep.peak_activation_bytes >= rep.output_bytes


def test_cost_llama_estimates_positive():
    t = zoo.traced("llama")
    rep = cost.estimate(t)
    assert rep.param_bytes == t.param_bytes() > 0
    assert rep.flops > 0 and rep.peak_activation_bytes > 0
    assert rep.total_resident_bytes() > rep.param_bytes


def test_kv_cache_bytes_formula():
    from paddle_tpu.models.llama import LlamaConfig, head_dim_of

    cfg = LlamaConfig.tiny(dtype="bfloat16")
    got = cost.kv_cache_bytes(cfg, max_batch=4, max_len=64)
    expect = (cfg.num_hidden_layers * 2 * cfg.num_key_value_heads * 4 * 64
              * head_dim_of(cfg) * 2)
    assert got == expect > 0


def test_kv_cache_bytes_non_causal_config_is_zero():
    class NoFields:
        pass

    assert cost.kv_cache_bytes(NoFields(), 4, 64) == 0


# ---------------------------------------------------------------------------
# op-dtypes honesty
# ---------------------------------------------------------------------------

def test_op_dtypes_flags_upcasting_and_rejecting_decls():
    class Upcaster:
        name = "fake_upcaster"
        dtypes = ("float32", "bfloat16")

        @staticmethod
        def impl(x):
            return x.astype(jnp.float32) * 2

    class Rejecter:
        name = "fake_rejecter"
        dtypes = ("float32", "float16")

        @staticmethod
        def impl(x):
            if x.dtype == jnp.float16:
                raise TypeError("no f16")
            return x

    class Honest:
        name = "fake_honest"
        dtypes = ("float32", "bfloat16")

        @staticmethod
        def impl(x):
            return x * 2

    problems = dict(op_dtypes.check_decl_dtypes([Upcaster, Rejecter, Honest]))
    assert "upcasts to float32" in problems["fake_upcaster"]
    assert "rejects it" in problems["fake_rejecter"]
    assert "fake_honest" not in problems


def test_op_dtypes_registry_is_honest():
    """The satellite: every probe-able OpDecl's claimed dtype list
    survives eval_shape of its impl — the registry advertises only what
    the kernels keep."""
    from paddle_tpu.ops import schema

    assert op_dtypes.check_decl_dtypes(schema.DECLS) == []


# ---------------------------------------------------------------------------
# preflight: the serving admission gate
# ---------------------------------------------------------------------------

def _tiny_llama(dtype="bfloat16"):
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM

    return LlamaForCausalLM(LlamaConfig.tiny(dtype=dtype))


def test_preflight_clean_model_ok():
    report = preflight_model(_tiny_llama(), allow_upcast=("mul",))
    assert report.ok
    assert report.cost["param_bytes"] > 0
    assert report.cost["resident_bytes"] >= report.cost["param_bytes"]


def test_engine_preflight_rejects_indivisible_sharding():
    """THE acceptance case: an indivisible sharding config raises
    PreflightError with a structured findings report — not a compile
    crash."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.serving import ContinuousBatchEngine

    model = _tiny_llama()
    mesh = dist.ProcessMesh(
        [[0, 1, 2], [3, 4, 5]], dim_names=["dp", "mp"])  # mp=3
    with pytest.raises(PreflightError) as ei:
        ContinuousBatchEngine.preflight(
            model, max_batch=2, max_len=64, mesh=mesh,
            param_specs={"q_proj.weight": (None, "mp")})
    report = ei.value.report
    assert not report.ok
    assert any(f.rule == "graph-shard-spec" for f in report.fatal)
    doc = report.as_dict()
    assert doc["ok"] is False
    assert any(f["fatal"] and "not divisible" in f["message"]
               for f in doc["findings"])


def test_engine_preflight_rejects_over_budget_model():
    from paddle_tpu.serving import ContinuousBatchEngine

    with pytest.raises(PreflightError) as ei:
        ContinuousBatchEngine.preflight(
            _tiny_llama(), max_batch=2, max_len=64, budget_bytes=1024)
    assert any(f.rule == "graph-preflight-cost"
               for f in ei.value.report.fatal)
    assert "refuse before compile" in str(ei.value)


def test_engine_preflight_raise_on_fatal_false_returns_report():
    from paddle_tpu.serving import ContinuousBatchEngine

    report = ContinuousBatchEngine.preflight(
        _tiny_llama(), max_batch=2, max_len=64, budget_bytes=1024,
        raise_on_fatal=False)
    assert not report.ok and report.fatal


def test_engine_constructor_preflight_gate_admits_clean_model():
    from paddle_tpu.serving import ContinuousBatchEngine

    eng = ContinuousBatchEngine(_tiny_llama(), max_batch=2, max_len=64,
                                preflight=True)
    assert eng is not None


def test_preflight_untraceable_model_reports_retrace_hazard():
    # an untraceable "model": a Layer whose forward branches on a
    # concrete bool of its input (data-dependent control flow)
    import paddle_tpu.nn as nn

    class DataDep(nn.Layer):
        def forward(self, x):
            if bool(x.sum() > 0):
                return x
            return -x

    report = preflight_model(DataDep(), batch=1, seq_len=4)
    assert not report.ok
    assert any(f.rule == "graph-retrace-hazard" for f in report.findings)


# ---------------------------------------------------------------------------
# the auto-sharding solver
# ---------------------------------------------------------------------------

_MESH = {"dp": 2, "mp": 4}   # the acceptance mesh: 8 devices, dp2 x mp4


def _hand_specs(traced):
    """The Megatron-pattern hand layout applied to any family (the
    zoo's _LLAMA_SHARD rules, matched by substring) — what a human
    would write before the solver existed."""
    layout = zoo.entry("llama-sharded").shard
    return zoo.ShardLayout(axis_sizes=_MESH,
                           rules=layout.rules).specs_for(traced)


def test_solver_classifies_weight_classes():
    t = zoo.traced("llama")
    classes = solver.classify_params(t)
    assert classes["llama.embed_tokens.weight"] == "embed_in"
    assert classes["lm_head.weight"] == "lm_head"
    assert classes["llama.layers.0.self_attn.q_proj.weight"] == "attn_qkv"
    assert classes["llama.layers.0.self_attn.o_proj.weight"] == "attn_o"
    assert classes["llama.layers.0.mlp.up_proj.weight"] == "mlp_up"
    assert classes["llama.layers.0.mlp.down_proj.weight"] == "mlp_down"
    # norms and any other sub-2D state stay replicated
    assert classes["llama.norm.weight"] == "norm_scalar"


def test_solver_deterministic():
    """Two fresh solves return byte-identical plans (specs, costs,
    ledger ordering) — the search has no ambient state."""
    t = zoo.traced("llama")
    a = solver.solve(t, _MESH, budget_bytes=1 << 30)
    b = solver.solve(t, _MESH, budget_bytes=1 << 30)
    assert a.as_dict() == b.as_dict()


def test_solver_fast_zoo_feasible_and_beats_hand():
    """THE acceptance sweep: on the dp=2,mp=4 mesh every fast-zoo
    family gets a plan that (a) fits a budget tighter than the
    replicated footprint, (b) validates with zero fatal shard-spec
    problems and zero implicit reshards, and (c) matches or beats the
    hand-written Megatron pattern on the cost metric."""
    seen = set()
    for e in zoo.entries():
        t = zoo.traced(e.name)
        if t.name in seen:
            continue   # the sharded twin traces the same program
        seen.add(t.name)
        assert t.ok, f"{e.name} does not trace: {t.error}"
        replicated = cost.estimate(t).total_resident_bytes()
        plan = solver.solve(t, _MESH, budget_bytes=replicated)
        assert plan.feasible, f"{e.name}: no feasible plan"
        assert plan.specs, f"{e.name}: solver left everything replicated"
        assert plan.resident_bytes() <= replicated
        assert plan.per_device_param_bytes < t.param_bytes()
        assert plan.n_reshard_events == 0, (
            f"{e.name}: chosen plan carries implicit reshards")
        score = solver.score_specs(t, plan.specs, _MESH)
        assert score["problems"] == [], f"{e.name}: {score['problems']}"
        hand = _hand_specs(t)
        if hand:
            hand_score = solver.score_specs(t, hand, _MESH)
            if not hand_score["problems"]:   # a hand layout this mesh
                assert plan.cost <= hand_score["cost"], (
                    f"{e.name}: solver {plan.cost} worse than hand "
                    f"{hand_score['cost']}")


def test_solver_budget_infeasible_reported():
    t = zoo.traced("llama")
    plan = solver.solve(t, _MESH, budget_bytes=1024)
    assert not plan.feasible
    assert plan.budget_bytes == 1024
    assert plan.cost > 0   # the cheapest plan's numbers still ride along


def test_solver_ledger_accounts_for_the_search():
    t = zoo.traced("llama")
    plan = solver.solve(t, _MESH)
    # 6 classes x 4 candidates for llama
    assert plan.plans_considered == 4 ** 6
    statuses = {e["status"] for e in plan.ledger}
    assert "costlier" in statuses or "pruned" in statuses
    for entry in plan.ledger:
        assert entry["assignment"] and entry["reason"] is not None
    d = plan.as_dict()
    assert d["resident_bytes"] == plan.resident_bytes()
    assert json.loads(json.dumps(d)) == d   # JSON-able end to end


def test_score_specs_flags_invalid_layout():
    t = zoo.traced("llama")
    score = solver.score_specs(
        t, {"llama.embed_tokens.weight": ("nope", None)}, _MESH)
    assert any("unknown mesh axis" in p for p in score["problems"])


def test_engine_preflight_auto_returns_plan_and_event():
    """preflight(param_specs='auto'): the report carries the plan, and
    the decision is a preflight.autoshard flight-recorder event."""
    import paddle_tpu.distributed as dist
    from paddle_tpu.observability import flightrecorder as frec
    from paddle_tpu.serving import ContinuousBatchEngine

    rec = frec.get_recorder()
    rec.enable()
    try:
        mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4).tolist(),
                                dim_names=["dp", "mp"])
        report = ContinuousBatchEngine.preflight(
            _tiny_llama(), max_batch=2, max_len=64, mesh=mesh,
            param_specs="auto", budget_bytes=1 << 30)
        events = [e for e in rec.drain()
                  if e["kind"] == "preflight.autoshard"]
    finally:
        rec.disable()
    assert report.ok
    assert report.plan is not None and report.plan["feasible"]
    assert report.plan["specs"] and report.plan["assignment"]
    assert report.as_dict()["plan"]["cost"] == report.plan["cost"]
    assert len(events) == 1
    ev = events[0]
    assert ev["feasible"] is True and ev["cost"] == report.plan["cost"]
    assert ev["assignment"] == report.plan["assignment"]


def test_engine_preflight_auto_rejects_over_budget():
    from paddle_tpu.serving import ContinuousBatchEngine
    import paddle_tpu.distributed as dist

    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4).tolist(),
                            dim_names=["dp", "mp"])
    with pytest.raises(PreflightError) as ei:
        ContinuousBatchEngine.preflight(
            _tiny_llama(), max_batch=2, max_len=64, mesh=mesh,
            param_specs="auto", budget_bytes=1024)
    report = ei.value.report
    assert report.plan is not None and not report.plan["feasible"]
    assert any("no sharding plan fits" in f.message for f in report.fatal)


def test_preflight_auto_without_mesh_is_fatal():
    report = preflight_model(_tiny_llama(), param_specs="auto",
                             allow_upcast=("mul",))
    assert not report.ok
    assert any("needs a mesh" in f.message for f in report.fatal)


def test_solver_plan_token_identical_engine():
    """THE acceptance leg: decode under the solver-chosen dp2xmp4
    layout (params laid out with apply_plan over the real 8-device CPU
    mesh) is token-identical to the unsharded engine."""
    import paddle_tpu
    import paddle_tpu.distributed as dist
    from paddle_tpu.models.llama import LlamaConfig, LlamaForCausalLM
    from paddle_tpu.serving import ContinuousBatchEngine

    cfg = LlamaConfig.tiny(dtype="float32")
    prompt = np.array([3, 5, 7, 11, 13], dtype=np.int32)

    paddle_tpu.seed(7)
    model = LlamaForCausalLM(cfg)
    eng = ContinuousBatchEngine(model, max_batch=2, max_len=64)
    rid = eng.add_request(prompt, max_new_tokens=8)
    ref = eng.run_until_done()[rid]

    mesh = dist.ProcessMesh(np.arange(8).reshape(2, 4).tolist(),
                            dim_names=["dp", "mp"])
    report = ContinuousBatchEngine.preflight(
        model, max_batch=2, max_len=64, mesh=mesh, param_specs="auto")
    paddle_tpu.seed(7)
    sharded = LlamaForCausalLM(cfg)
    n = solver.apply_plan(sharded, report.plan["specs"], mesh)
    assert n == len(report.plan["specs"]) > 0
    eng2 = ContinuousBatchEngine(sharded, max_batch=2, max_len=64)
    rid2 = eng2.add_request(prompt, max_new_tokens=8)
    out = eng2.run_until_done()[rid2]
    np.testing.assert_array_equal(ref, out)


def test_shard_solver_rule_audits_bad_hand_layout(monkeypatch):
    """graph-shard-solver: a zoo layout the planner beats by >=20% is
    flagged, with the plan + rejected ledger attached as finding data;
    the shipped llama-sharded layout survives the audit."""
    from paddle_tpu.analysis.graph.rules import ShardSolverRule

    findings = list(ShardSolverRule().check_project(_REPO))
    assert findings == [], [f.message for f in findings]

    # a deliberately terrible hand layout: shard ONE mlp weight, leave
    # the rest replicated — the planner beats it easily
    bad = zoo.ZooEntry(
        "llama-sharded", zoo.entry("llama-sharded").build,
        zoo._ids_inputs,
        shard=zoo.ShardLayout(
            axis_sizes={"dp": 2, "mp": 2},
            rules=(("layers.0.mlp.up_proj.weight", (None, "mp")),)))
    monkeypatch.setattr(zoo, "entries", lambda full=False: [bad])
    findings = list(ShardSolverRule().check_project(_REPO))
    assert len(findings) == 1
    f = findings[0]
    assert f.rule == "graph-shard-solver" and "cheaper" in f.message
    assert f.data["plan"]["cost"] < f.data["hand"]["cost"]
    assert isinstance(f.data["ledger"], list) and f.data["ledger"]


def test_pdlint_solve_cli(capsys):
    mod = _load_script("pdlint.py")
    rc = mod.main(["--solve", "llama", "--mesh", "dp=2,mp=4", "--json"])
    out = capsys.readouterr().out
    doc = json.loads(out)
    assert rc == 0
    assert doc["tool"] == "pdlint-solve" and doc["mesh"] == _MESH
    plan = doc["plans"]["llama"]
    assert plan["feasible"] and plan["specs"] and plan["ledger"]
    # an impossible budget exits non-zero
    assert mod.main(["--solve", "llama", "--mesh", "dp=2,mp=4",
                     "--budget-bytes", "1024", "--json"]) == 1
    capsys.readouterr()


@pytest.mark.slow
def test_solver_full_zoo_sweep():
    """Every family the zoo enumerates solves to a feasible,
    implicit-reshard-free plan on the acceptance mesh."""
    seen = set()
    for e in zoo.entries(full=True):
        t = zoo.traced(e.name, full=True)
        if t.name in seen or not t.ok:
            continue
        seen.add(t.name)
        replicated = cost.estimate(t).total_resident_bytes()
        plan = solver.solve(t, _MESH, budget_bytes=replicated)
        assert plan.feasible, f"{e.name}: no feasible plan"
        assert plan.n_reshard_events == 0, f"{e.name}"
        assert solver.score_specs(t, plan.specs, _MESH)["problems"] == []


# ---------------------------------------------------------------------------
# registry + CLI integration
# ---------------------------------------------------------------------------

def test_cost_table_rule_flags_drifted_entry(tmp_path, monkeypatch):
    """graph-cost-table: a persisted entry whose recorded bytes/FLOPs
    disagree with the live analytical model is flagged; agreeing and
    pre-search-era (no-est) entries pass."""
    import json as _json

    from paddle_tpu.analysis.graph.rules import AutotuneCostTableRule
    from paddle_tpu.ops.pallas import autotune

    params = {"rows": 128, "d": 256, "dtype": "float32"}
    good = autotune.analytical_cost("rms_norm", params, (8,))
    assert good is not None  # fused_norm registers its model at import
    data = {"rms_norm": {
        "good @dev": {"choice": [8], "ms": 1.0, "params": params,
                      "est": {"bytes": good["bytes"],
                              "flops": good["flops"]}},
        "drifted @dev": {"choice": [8], "ms": 1.0, "params": params,
                         "est": {"bytes": good["bytes"] * 7,
                                 "flops": good["flops"]}},
        "legacy @dev": {"choice": [8], "ms": 1.0},
    }}
    path = tmp_path / "cache.json"
    path.write_text(_json.dumps(data))
    monkeypatch.setenv("PD_AUTOTUNE_CACHE", str(path))
    findings = list(AutotuneCostTableRule().check_project(_REPO))
    assert [f.symbol for f in findings] == ["rms_norm:drifted @dev"]
    assert "bytes" in findings[0].message


def test_cost_table_rule_absent_cache_is_silent(tmp_path, monkeypatch):
    from paddle_tpu.analysis.graph.rules import AutotuneCostTableRule

    monkeypatch.setenv("PD_AUTOTUNE_CACHE", str(tmp_path / "none.json"))
    assert list(AutotuneCostTableRule().check_project(_REPO)) == []


def test_cost_table_rule_orphaned_model_flagged(tmp_path, monkeypatch):
    """Estimates recorded for a kernel whose cost model is gone = stale
    evidence, flagged rather than skipped."""
    import json as _json

    from paddle_tpu.analysis.graph.rules import AutotuneCostTableRule

    data = {"gone_kernel": {"sig @dev": {
        "choice": [8], "ms": 1.0, "params": {"rows": 1},
        "est": {"bytes": 10, "flops": 10}}}}
    path = tmp_path / "cache.json"
    path.write_text(_json.dumps(data))
    monkeypatch.setenv("PD_AUTOTUNE_CACHE", str(path))
    findings = list(AutotuneCostTableRule().check_project(_REPO))
    assert len(findings) == 1
    assert "no cost model" in findings[0].message


def test_graph_rules_registered_but_excluded_by_default():
    analysis.ast_rules()  # force registration
    graph_ids = {"graph-shard-spec", "graph-shard-solver",
                 "graph-dtype-promotion", "graph-retrace-hazard",
                 "graph-preflight-cost", "graph-op-dtypes"}
    assert graph_ids <= set(analysis.RULES)
    for rid in graph_ids:
        assert analysis.RULES[rid].rationale
    default_ids = {r.id for r in analysis.core.project_rules()}
    assert not (graph_ids & default_ids), "graph rules must be opt-in"
    with_graph = {r.id for r in analysis.core.project_rules(graph=True)}
    assert graph_ids <= with_graph
    # explicit selection overrides the opt-in gate
    sel = {r.id for r in analysis.core.project_rules(
        selected=["graph-op-dtypes"])}
    assert sel == {"graph-op-dtypes"}


def _load_script(name):
    path = os.path.join(_REPO, "scripts", name)
    sp = importlib.util.spec_from_file_location(name[:-3], path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod


def test_pdlint_graph_gate_zero_new_findings(capsys):
    """THE tier-1 graph gate: ``scripts/pdlint.py --json --baseline
    .pdlint_baseline.json --graph`` exits 0 — the fast zoo set traces
    clean against the checked-in baseline."""
    mod = _load_script("pdlint.py")
    rc = mod.main(["--json", "--graph", "--baseline",
                   os.path.join(_REPO, ".pdlint_baseline.json")])
    out = capsys.readouterr().out
    doc = json.loads(out)
    assert rc == 0, f"pdlint --graph found new findings:\n{out}"
    assert doc["total"] == 0


@pytest.mark.slow
def test_pdlint_graph_full_zoo_sweep(capsys, monkeypatch):
    """The zoo-wide sweep (every family the zoo enumerates): slow-marked
    so the fast gate stays under budget."""
    monkeypatch.setenv("PDLINT_GRAPH_SCOPE", "full")
    mod = _load_script("pdlint.py")
    rc = mod.main(["--json", "--graph", "--baseline",
                   os.path.join(_REPO, ".pdlint_baseline.json")])
    out = capsys.readouterr().out
    assert rc == 0, f"full-zoo graph sweep found new findings:\n{out}"
