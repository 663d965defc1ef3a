"""dtype-promotion analysis: find silent bf16 -> f32 upcasts in a jaxpr.

A model built in bfloat16 should compute in bfloat16; activations that
silently land in float32 double their bytes and every downstream eqn's
until something casts back. The classic sources are invisible in Python
source — a ``np.float32`` scalar constant promoting a mul, an f32
buffer added to bf16 activations, a matmul with
``preferred_element_type`` — and at jaxpr level jnp's promotion
machinery renders most of them as an inserted ``convert_element_type``,
the SAME eqn a deliberate ``.astype`` produces. So a per-eqn dtype
check cannot tell the norm's deliberate f32 island from the accident.

What can: **origin tracking**. Walk the jaxpr marking every f32 value
that descends from a bfloat16 ancestor ("derived"). A deliberate island
computes entirely among derived values (cast x up, do the math, cast
back). The accident is the MIX — an arithmetic eqn combining a derived
f32 operand with an f32 value of independent origin (a non-weak f32
literal or const, an f32 buffer, a table computed in f32): that is
precisely where jnp's promotion, not the author, chose float32.

Two finding classes:

- ``direct``: a non-convert eqn with a bf16 input and an f32 output
  (``preferred_element_type`` matmuls and friends).
- ``mix``: an arithmetic eqn mixing derived f32 with an independent
  non-weak f32 TENSOR (an f32 buffer or table whose bytes could have
  been bf16). Scalars never flag — weak ones (Python floats) because
  jax keeps bf16 for those, non-weak ones (an ``np.float32`` scale,
  ``-inf`` mask fill, eps) because with a derived operand present the
  island is already f32: a scalar contributes no bytes and cannot be
  the reason promotion chose float32.

Per-model allowlists (zoo entries / preflight callers) name allowed
PRIMITIVES for deliberate mixes (e.g. rope tables kept in f32 multiply
into converted q/k by design).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, FrozenSet, Iterable, List, Set

from .trace import TracedGraph

LOW = ("bfloat16", "float16")
HIGH = ("float32", "float64")

# arithmetic that propagates magnitude — where an f32 operand forces an
# f32 result (comparisons/bool ops don't upcast anything)
_ARITH = {"add", "sub", "mul", "div", "max", "min", "pow", "atan2",
          "rem", "nextafter", "dot_general"}

# call-like eqns whose single sub-jaxpr maps invars/outvars 1:1 (a
# jitted call is the primitive "pjit" in older JAX and "jit" in newer)
_TRANSPARENT_CALLS = {"pjit", "jit", "custom_vjp_call_jaxpr",
                      "custom_jvp_call", "custom_vjp_call", "remat",
                      "checkpoint"}


@dataclasses.dataclass
class Upcast:
    eqn_path: str
    primitive: str
    kind: str                 # "direct" | "mix"
    detail: str

    def message(self) -> str:
        if self.kind == "direct":
            return (f"eqn {self.eqn_path} {self.primitive}: bf16 input "
                    f"produces {self.detail} output directly "
                    "(preferred_element_type or accumulation dtype) — "
                    "deliberate? cast explicitly so the island is "
                    "visible in source")
        return (f"eqn {self.eqn_path} {self.primitive}: mixes "
                f"bf16-derived f32 with {self.detail} — jnp promotion "
                "chose float32 here, not the author; cast the constant/"
                "buffer to the model dtype or allowlist the primitive "
                "as a deliberate f32 island")


def _is_low(aval) -> bool:
    return hasattr(aval, "dtype") and str(aval.dtype) in LOW


def _is_high(aval) -> bool:
    return hasattr(aval, "dtype") and str(aval.dtype) in HIGH


def find_upcasts(traced: TracedGraph,
                 allow: Iterable[str] = ()) -> List[Upcast]:
    if not traced.ok:
        return []
    allowed: FrozenSet[str] = frozenset(allow)
    out: List[Upcast] = []
    jaxpr = traced.closed_jaxpr.jaxpr
    _walk(jaxpr, derived=set(), prefix="", allowed=allowed, out=out)
    return out


def _walk(jaxpr, derived: Set, prefix: str, allowed: FrozenSet[str],
          out: List[Upcast]) -> None:
    """``derived``: vars (of this jaxpr) holding f32 values with a bf16
    ancestor. Mutated as eqns are walked; sub-jaxprs get their own set
    seeded through the call boundary."""

    def is_derived(v):
        return (not hasattr(v, "val")) and v in derived

    def var_high_independent(v):
        # an f32 TENSOR operand with no bf16 lineage; scalars never
        # count (see module docstring — they carry no bytes and the
        # island is already f32 once a derived operand is present)
        aval = v.aval
        if not _is_high(aval) or aval.shape == () or \
                getattr(aval, "weak_type", False):
            return False
        if hasattr(v, "val"):  # Literal
            return True
        return v not in derived

    for i, eqn in enumerate(jaxpr.eqns):
        path = f"{prefix}{i}"
        prim = eqn.primitive.name
        in_avals = [v.aval for v in eqn.invars if hasattr(v, "aval")]
        any_low_in = any(_is_low(a) for a in in_avals)
        any_derived_in = any(is_derived(v) for v in eqn.invars
                             if not hasattr(v, "val"))

        sub = _sub_jaxpr(eqn)
        if sub is not None and prim in _TRANSPARENT_CALLS and \
                len(sub.invars) == len(eqn.invars):
            inner_derived = {iv for iv, ov in zip(sub.invars, eqn.invars)
                             if not hasattr(ov, "val") and ov in derived}
            _walk(sub, inner_derived, f"{path}.{prim}.", allowed, out)
            for ov, iv in zip(eqn.outvars, sub.outvars):
                if (not hasattr(iv, "val") and iv in inner_derived) or \
                        (hasattr(iv, "aval") and _is_low(iv.aval)):
                    if _is_high(ov.aval):
                        derived.add(ov)
            # low-dtype lineage continues through low outputs implicitly
            continue

        if prim == "convert_element_type":
            # a convert to f32 joins the island lineage unless its input
            # is an independent high float: bf16 sources are the island
            # itself, and int/bool sources (masks, one_hot) picked f32
            # only to FOLLOW the island's dtype — neither is independent
            # f32 bytes that could have been bf16
            ov = eqn.outvars[0]
            src_indep_high = any(
                _is_high(a) and not getattr(a, "weak_type", False)
                for a in in_avals) and not (any_low_in or any_derived_in)
            if _is_high(ov.aval) and not src_indep_high:
                derived.add(ov)
            continue

        # direct upcast: bf16 in, f32 out, not a convert
        if any_low_in and prim not in allowed:
            hi = [str(v.aval.dtype) for v in eqn.outvars
                  if _is_high(v.aval)]
            if hi:
                out.append(Upcast(path, prim, "direct", hi[0]))

        # the mix: derived f32 meets independent f32 in arithmetic
        if prim in _ARITH and prim not in allowed and any_derived_in:
            indep = [v for v in eqn.invars if var_high_independent(v)]
            if indep:
                what = ("an f32 literal/const"
                        if any(hasattr(v, "val") for v in indep)
                        else "an independent f32 value")
                out.append(Upcast(path, prim, "mix", what))

        # lineage propagation: any eqn with a low or derived input whose
        # output is f32 keeps the lineage
        if any_low_in or any_derived_in:
            for ov in eqn.outvars:
                if hasattr(ov, "aval") and _is_high(ov.aval):
                    derived.add(ov)


def _sub_jaxpr(eqn):
    for v in eqn.params.values():
        inner = getattr(v, "jaxpr", None)
        if inner is not None and hasattr(inner, "eqns"):
            return inner
        if hasattr(v, "eqns"):
            return v
    return None
