"""The graph rules: jaxpr-level preflight checks in the pdlint registry.

These are ``ProjectRule``s with ``graph = True`` — they trace models
(hundreds of ms each, memoized per run), so they run only under
``scripts/pdlint.py --graph`` (or when selected explicitly), keeping the
default AST lint instant. Findings key on model+eqn
(``file="<graph:llama>"``, ``symbol="dot_general@14"``) so the baseline
machinery works unchanged for graph findings.
"""
from __future__ import annotations

import os
from typing import Iterable, List

from ..core import Finding, ProjectRule, register_rule
from . import cost as _cost
from . import dtype_flow, op_dtypes, retrace, shard_spec, solver, zoo

_SCHEMA_FILE = "paddle_tpu/ops/schema.py"


def _graph_file(model_name: str) -> str:
    return f"<graph:{model_name}>"


def _full_sweep() -> bool:
    """Zoo scope: the fast 4-family set by default; PDLINT_GRAPH_SCOPE=
    full widens to the whole zoo (the slow-marked sweep)."""
    return os.environ.get("PDLINT_GRAPH_SCOPE", "") == "full"


class GraphRule(ProjectRule):
    """A project rule that traces programs; opt-in via --graph."""

    graph = True


@register_rule
class ShardSpecRule(GraphRule):
    id = "graph-shard-spec"
    rationale = ("an invalid PartitionSpec (unknown axis, indivisible "
                 "dim, double-sharded axis) or an implicit reshard on "
                 "the step path surfaces as an opaque XLA crash or a "
                 "silent all-to-all tax — both decidable before compile "
                 "(GSPMD)")

    def check_project(self, root: str) -> Iterable[Finding]:
        full = _full_sweep()
        for e in zoo.entries(full=full):
            if e.shard is None:
                continue
            t = zoo.traced(e.name, full=full)
            file = _graph_file(e.name)
            if not t.ok:
                continue  # the retrace rule owns trace failures
            in_specs = {}
            for name, sp in e.shard.specs_for(t).items():
                aval = t.param_avals[name]
                for msg in shard_spec.check_partition_spec(
                        sp, e.shard.axis_sizes, aval.shape,
                        what=f"param {name}"):
                    yield Finding(file=file, line=1, rule=self.id,
                                  message=msg, symbol=name)
                in_specs[t.invar_index_of_param(name)] = sp
            for path, prim, msg in shard_spec.propagate(
                    t, in_specs, e.shard.axis_sizes):
                yield Finding(file=file, line=1, rule=self.id,
                              message=f"implicit reshard: {msg}",
                              symbol=f"{prim}@{path}")
        # OpDecl.spmd notes vs observed eval_shape behavior — the
        # propagation walk trusts those notes, so lies here mis-shard
        from paddle_tpu.ops import schema as _schema

        for name, msg in shard_spec.check_spmd_notes(_schema.DECLS):
            yield Finding(file=_SCHEMA_FILE, line=1, rule=self.id,
                          message=msg, symbol=name)


@register_rule
class ShardSolverRule(GraphRule):
    id = "graph-shard-solver"
    rationale = ("hand-written param_specs the auto-sharding solver "
                 "beats by >=20% on the static cost metric (per-device "
                 "resident bytes + weighted reshard bytes) are leaving "
                 "HBM or interconnect on the table — the planner audits "
                 "the humans")

    #: the hand layout survives while it is within 20% of the planner
    MARGIN = 0.8

    def check_project(self, root: str) -> Iterable[Finding]:
        full = _full_sweep()
        for e in zoo.entries(full=full):
            if e.shard is None:
                continue
            t = zoo.traced(e.name, full=full)
            if not t.ok:
                continue
            hand_specs = e.shard.specs_for(t)
            if not hand_specs:
                continue
            hand = solver.score_specs(t, hand_specs, e.shard.axis_sizes)
            plan = solver.solve(t, e.shard.axis_sizes)
            if hand["cost"] <= 0 or \
                    plan.cost >= self.MARGIN * hand["cost"]:
                continue
            pct = 100 * (1 - plan.cost / hand["cost"])
            yield Finding(
                file=_graph_file(e.name), line=1, rule=self.id,
                symbol="solver",
                message=(f"hand-written specs cost {hand['cost']} but "
                         f"the solver's plan costs {plan.cost} "
                         f"({pct:.0f}% cheaper) — assignment "
                         f"{plan.assignment}"),
                data={"hand": hand, "plan": {
                    "assignment": plan.assignment,
                    "cost": plan.cost,
                    "per_device_param_bytes": plan.per_device_param_bytes,
                    "reshard_bytes": plan.reshard_bytes,
                    "specs": {k: list(v) for k, v in plan.specs.items()},
                }, "ledger": plan.ledger})


@register_rule
class DtypePromotionRule(GraphRule):
    id = "graph-dtype-promotion"
    rationale = ("a bf16-built model silently computing islands in f32 "
                 "(weak-typed constants, dtype= reductions) doubles "
                 "activation bytes with no accuracy contract — visible "
                 "only at jaxpr level")

    def check_project(self, root: str) -> Iterable[Finding]:
        full = _full_sweep()
        for e in zoo.entries(full=full):
            if e.shard is not None:
                continue  # sharded twin re-traces the same program
            t = zoo.traced(e.name, full=full)
            if not t.ok:
                continue
            for up in dtype_flow.find_upcasts(t, allow=e.allow_upcast):
                yield Finding(file=_graph_file(e.name), line=1,
                              rule=self.id, message=up.message(),
                              symbol=f"{up.primitive}@{up.eqn_path}")


@register_rule
class RetraceHazardRule(GraphRule):
    id = "graph-retrace-hazard"
    rationale = ("data-dependent shapes and baked closure constants "
                 "defeat the jit cache — every production step "
                 "recompiles (or never compiles) where the trace could "
                 "have said so upfront")

    def check_project(self, root: str) -> Iterable[Finding]:
        full = _full_sweep()
        for e in zoo.entries(full=full):
            if e.shard is not None:
                continue
            t = zoo.traced(e.name, full=full)
            for key, msg in retrace.find_hazards(t):
                yield Finding(file=_graph_file(e.name), line=1,
                              rule=self.id, message=msg, symbol=key)


@register_rule
class PreflightCostRule(GraphRule):
    id = "graph-preflight-cost"
    rationale = ("serving admission must know param/activation bytes "
                 "and FLOPs before touching the device — a family whose "
                 "cost cannot be estimated cannot be preflighted")

    def check_project(self, root: str) -> Iterable[Finding]:
        full = _full_sweep()
        for e in zoo.entries(full=full):
            if e.shard is not None:
                continue
            t = zoo.traced(e.name, full=full)
            if not t.ok:
                continue
            rep = _cost.estimate(t)
            file = _graph_file(e.name)
            if rep.param_bytes <= 0:
                yield Finding(file=file, line=1, rule=self.id,
                              message="param byte estimate is zero — "
                              "the functional state carries no avals",
                              symbol="param-bytes")
            if rep.flops <= 0:
                yield Finding(file=file, line=1, rule=self.id,
                              message="FLOP estimate is zero — the "
                              "traced program has no costed eqns",
                              symbol="flops")


@register_rule
class AutotuneCostTableRule(GraphRule):
    id = "graph-cost-table"
    rationale = ("a persisted autotune cost-table entry whose recorded "
                 "bytes/FLOPs no longer match the kernel's analytical "
                 "cost model was measured against a different kernel "
                 "than the one shipping — its winner (and its roofline "
                 "pruning evidence) is stale")

    def check_project(self, root: str) -> Iterable[Finding]:
        import json

        from ...ops.pallas import autotune
        # importing the kernel modules registers their cost models
        from ...ops.pallas import decode_tail, fused_norm  # noqa: F401

        path = autotune.cache_path()
        if not os.path.isfile(path):
            return
        rel = os.path.relpath(path, root).replace(os.sep, "/")
        try:
            with open(path) as f:
                data = json.load(f)
        except (OSError, ValueError) as e:
            yield Finding(file=rel, line=1, rule=self.id,
                          symbol="cache-file",
                          message=f"autotune cache unreadable "
                                  f"({type(e).__name__}: {e})")
            return
        for kernel, sigs in data.items():
            if not isinstance(sigs, dict):
                continue
            for key, ent in sigs.items():
                if not isinstance(ent, dict):
                    continue
                est = ent.get("est")
                params = ent.get("params")
                choice = ent.get("choice")
                if not est or not params or not choice:
                    continue  # pre-search-era entry: nothing to check
                symbol = f"{kernel}:{key}"
                try:
                    cur = autotune.analytical_cost(kernel, params, choice)
                except (KeyError, TypeError, ValueError) as e:
                    yield Finding(
                        file=rel, line=1, rule=self.id, symbol=symbol,
                        message=f"cost model replay failed on recorded "
                                f"params ({type(e).__name__}: {e})")
                    continue
                if cur is None:
                    yield Finding(
                        file=rel, line=1, rule=self.id, symbol=symbol,
                        message="entry carries analytical estimates but "
                                "no cost model is registered for this "
                                "kernel anymore — stale evidence")
                    continue
                for field in ("bytes", "flops"):
                    want = cur.get(field)
                    got = est.get(field)
                    if want is None or got is None:
                        continue
                    if abs(int(want) - int(got)) > max(1, int(want) // 100):
                        yield Finding(
                            file=rel, line=1, rule=self.id, symbol=symbol,
                            message=f"recorded {field}={got} disagrees "
                                    f"with the analytical estimate "
                                    f"{want} — re-run the sweep (or fix "
                                    f"the cost model drift)")


@register_rule
class OpDtypesRule(GraphRule):
    id = "graph-op-dtypes"
    rationale = ("an OpDecl claiming a dtype its impl upcasts or "
                 "rejects advertises support the kernel doesn't keep — "
                 "checkable by the same eval_shape path infer_meta uses")

    def check_project(self, root: str) -> Iterable[Finding]:
        import sys

        if root not in sys.path:
            sys.path.insert(0, root)
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        from paddle_tpu.ops import schema as _schema

        for name, msg in op_dtypes.check_decl_dtypes(_schema.DECLS):
            yield Finding(file=_SCHEMA_FILE, line=1, rule=self.id,
                          message=msg, symbol=name)
