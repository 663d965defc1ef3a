"""metrics-catalog / span-catalog / event-catalog / alert-catalog: docs
and registries agree, both ways.

The first two rules are the grown-up form of the original tier-1 lint
scripts (scripts/check_metrics_catalog.py, check_span_catalog.py),
re-homed under the pdlint runner; the scripts remain as thin wrappers.

- **metrics-catalog**: every metric family registered at import of
  ``paddle_tpu.observability`` has a row in docs/SERVING.md's "Metric
  catalog" table (name, kind, labels) and vice versa, with schema drift
  (kind/labels mismatch) flagged per row.
- **span-catalog**: every name in ``tracing.SPAN_CATALOG`` has a row in
  the "Span catalog" table and vice versa, and every registered span's
  ``SPAN_*`` constant is actually referenced outside tracing.py (no dead
  catalog entries).
- **event-catalog**: the flight recorder's ``EVENT_CATALOG`` kinds
  (flightrecorder.py) against the docs "Event catalog" table the same
  way — documented, registered, and every ``EV_*`` constant actually
  recorded outside flightrecorder.py.
- **alert-catalog**: the default SLO objectives
  (``alerts.DEFAULT_OBJECTIVES`` ∪ ``alerts.CLUSTER_OBJECTIVES``)
  against the docs "Alert catalog" table both ways, PLUS every metric
  an objective reads must actually exist (a registered family or a
  declared federated series) — an alert burning against a renamed
  counter would silently never fire.

The comparison cores are pure functions over parsed dicts so fixture
tests can exercise drift cases without importing the live registry.
"""
from __future__ import annotations

import os
import re
from typing import Dict, Iterable, List, Set, Tuple

from ..core import Finding, ProjectRule, register_rule

_DOCS = os.path.join("docs", "SERVING.md")

# catalog rows look like: | `name` | kind | labels | meaning |
_METRIC_ROW = re.compile(r"^\|\s*`([a-z0-9_]+)`\s*\|\s*([a-z]+)\s*\|\s*([^|]*)\|")
# span rows look like: | `serving.request` | parent | meaning | (or
# `jit/trace`: a span name may hold a slash)
_SPAN_ROW = re.compile(r"^\|\s*`([a-z0-9_./]+)`\s*\|")


# ---- pure comparison cores --------------------------------------------------

def compare_metric_catalogs(docs: Dict[str, tuple],
                            registry: Dict[str, tuple]
                            ) -> List[str]:
    problems = []
    for name in sorted(set(registry) - set(docs)):
        problems.append(f"metric registered but not in docs/SERVING.md: "
                        f"{name}")
    for name in sorted(set(docs) - set(registry)):
        problems.append(f"metric documented but not registered: {name}")
    for name in sorted(set(docs) & set(registry)):
        if docs[name] != registry[name]:
            problems.append(
                f"schema drift for {name}: docs say "
                f"{docs[name][0]}{sorted(docs[name][1])}, registry has "
                f"{registry[name][0]}{sorted(registry[name][1])}")
    return problems


def compare_span_catalogs(docs: Set[str], registered: Set[str],
                          emitted_ok: Dict[str, bool]) -> List[str]:
    return compare_name_catalogs(docs, registered, emitted_ok,
                                 noun="span", home="tracing.py")


def compare_event_catalogs(docs: Set[str], registered: Set[str],
                           emitted_ok: Dict[str, bool]) -> List[str]:
    return compare_name_catalogs(docs, registered, emitted_ok,
                                 noun="event", home="flightrecorder.py")


def compare_alert_catalogs(docs: Set[str], registered: Set[str],
                           metric_refs: Dict[str, List[str]],
                           known_metrics: Set[str]) -> List[str]:
    """Docs ↔ objective registries both ways (the shared name-catalog
    core), plus the alert-specific third leg: every metric an objective
    reads must exist — in the metrics registry or the declared
    federated-series set."""
    problems = compare_name_catalogs(docs, registered, {}, noun="alert",
                                     home="alerts.py")
    for name in sorted(metric_refs):
        for metric in metric_refs[name]:
            if metric not in known_metrics:
                problems.append(
                    f"alert {name!r} reads metric {metric!r}, which is "
                    "neither a registered metric family nor a declared "
                    "federated series — the objective can never fire")
    return problems


def compare_name_catalogs(docs: Set[str], registered: Set[str],
                          emitted_ok: Dict[str, bool], noun: str,
                          home: str) -> List[str]:
    """The shared docs/registry/emit three-way check behind the span and
    event catalog rules (they differ only in nouns and home module)."""
    problems = []
    for name in sorted(registered - docs):
        problems.append(f"{noun} registered but not in docs/SERVING.md: "
                        f"{name}")
    for name in sorted(docs - registered):
        problems.append(f"{noun} documented but not registered: {name}")
    for name, ok in sorted(emitted_ok.items()):
        if not ok:
            problems.append(
                f"{noun} {name!r} is registered but never emitted outside "
                f"{home}")
    return problems


# ---- docs parsing -----------------------------------------------------------

def documented_metrics(path: str) -> Dict[str, tuple]:
    """{name: (kind, frozenset(labels))} parsed from the docs table."""
    out = {}
    with open(path, encoding="utf-8") as f:
        for line in f:
            m = _METRIC_ROW.match(line.strip())
            if not m:
                continue
            name, kind, labels_cell = m.groups()
            if kind not in ("counter", "gauge", "histogram"):
                continue  # the stats()-mapping table, not the catalog
            labels = frozenset(
                l.strip() for l in labels_cell.split(",")
                if l.strip() and l.strip() != "—")
            out[name] = (kind, labels)
    return out


def documented_spans(path: str) -> Set[str]:
    """Span names from the docs "Span catalog" section only."""
    return _documented_names(path, "Span catalog", "span")


def documented_events(path: str) -> Set[str]:
    """Event kinds from the docs "Event catalog" section only."""
    return _documented_names(path, "Event catalog", "kind")


def documented_alerts(path: str) -> Set[str]:
    """Alert names from the docs "Alert catalog" section only."""
    return _documented_names(path, "Alert catalog", "alert")


def _documented_names(path: str, section: str, header_cell: str) -> Set[str]:
    out = set()
    in_section = False
    with open(path, encoding="utf-8") as f:
        for line in f:
            line = line.strip()
            if line.startswith("#"):
                in_section = line.lstrip("#").strip() == section
                continue
            if not in_section:
                continue
            m = _SPAN_ROW.match(line)
            if m and m.group(1) != header_cell:
                out.add(m.group(1))
    return out


def _bootstrap(root: str):
    import sys

    if root not in sys.path:
        sys.path.insert(0, root)
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


# ---- rules ------------------------------------------------------------------

@register_rule
class MetricsCatalogRule(ProjectRule):
    id = "metrics-catalog"
    rationale = ("a metric must neither ship undocumented nor linger in "
                 "the docs after removal, and the documented schema must "
                 "match the registry")

    def check_project(self, root: str) -> Iterable[Finding]:
        _bootstrap(root)
        from paddle_tpu.observability import get_registry

        docs = documented_metrics(os.path.join(root, _DOCS))
        reg = {name: (d["kind"], frozenset(d["labels"]))
               for name, d in get_registry().describe().items()}
        for msg in compare_metric_catalogs(docs, reg):
            yield Finding(file=_DOCS.replace(os.sep, "/"), line=1,
                          rule=self.id, message=msg,
                          symbol="metric-catalog")


@register_rule
class SpanCatalogRule(ProjectRule):
    id = "span-catalog"
    rationale = ("a span must be documented, registered, and actually "
                 "emitted — dead catalog entries and undocumented spans "
                 "both drift")

    def check_project(self, root: str) -> Iterable[Finding]:
        _bootstrap(root)
        from paddle_tpu.observability import tracing

        docs = documented_spans(os.path.join(root, _DOCS))
        registered = set(tracing.SPAN_CATALOG)
        used = self._emitted_constants(root)
        emitted_ok = {
            value: (const in used)
            for const, value in vars(tracing).items()
            if (const.startswith("SPAN_") and isinstance(value, str)
                and const != "SPAN_CATALOG")
        }
        for msg in compare_span_catalogs(docs, registered, emitted_ok):
            yield Finding(file=_DOCS.replace(os.sep, "/"), line=1,
                          rule=self.id, message=msg, symbol="span-catalog")

    @staticmethod
    def _emitted_constants(root: str) -> Set[str]:
        """SPAN_* constants referenced OUTSIDE tracing.py (emit sites)."""
        return _referenced_constants(root, r"\bSPAN_[A-Z_]+\b",
                                     "tracing.py")


def _referenced_constants(root: str, pattern: str,
                          home_file: str) -> Set[str]:
    """Constants matching ``pattern`` referenced in paddle_tpu/ OUTSIDE
    the catalog's home module (i.e. real emit sites)."""
    used: Set[str] = set()
    pkg = os.path.join(root, "paddle_tpu")
    for dirpath, _, files in os.walk(pkg):
        for fn in files:
            if not fn.endswith(".py") or fn == home_file:
                continue
            with open(os.path.join(dirpath, fn), encoding="utf-8") as f:
                used.update(re.findall(pattern, f.read()))
    return used


@register_rule
class AlertCatalogRule(ProjectRule):
    id = "alert-catalog"
    rationale = ("a default SLO objective must be documented (operators "
                 "act on alert names), every documented alert must still "
                 "exist, and every metric an objective reads must be "
                 "real — an alert over a renamed counter silently never "
                 "fires")

    def check_project(self, root: str) -> Iterable[Finding]:
        _bootstrap(root)
        from paddle_tpu.observability import alerts, get_registry

        docs = documented_alerts(os.path.join(root, _DOCS))
        objectives = dict(alerts.DEFAULT_OBJECTIVES)
        objectives.update(alerts.CLUSTER_OBJECTIVES)
        metric_refs = {n: o.metric_names() for n, o in objectives.items()}
        known = set(get_registry().names()) | set(alerts.FEDERATED_SERIES)
        for msg in compare_alert_catalogs(docs, set(objectives),
                                          metric_refs, known):
            yield Finding(file=_DOCS.replace(os.sep, "/"), line=1,
                          rule=self.id, message=msg,
                          symbol="alert-catalog")


@register_rule
class EventCatalogRule(ProjectRule):
    id = "event-catalog"
    rationale = ("a flight-recorder event kind must be documented, "
                 "registered, and actually recorded — dead catalog "
                 "entries and undocumented kinds both drift, and an "
                 "undocumented kind makes incident bundles unreadable")

    def check_project(self, root: str) -> Iterable[Finding]:
        _bootstrap(root)
        from paddle_tpu.observability import flightrecorder

        docs = documented_events(os.path.join(root, _DOCS))
        registered = set(flightrecorder.EVENT_CATALOG)
        used = _referenced_constants(root, r"\bEV_[A-Z_]+\b",
                                     "flightrecorder.py")
        emitted_ok = {
            value: (const in used)
            for const, value in vars(flightrecorder).items()
            if (const.startswith("EV_") and isinstance(value, str)
                and const != "EVENT_CATALOG")
        }
        for msg in compare_event_catalogs(docs, registered, emitted_ok):
            yield Finding(file=_DOCS.replace(os.sep, "/"), line=1,
                          rule=self.id, message=msg,
                          symbol="event-catalog")
