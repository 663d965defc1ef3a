"""From the profiler's ``.xplane.pb`` to numbers: device busy and idle
share, time per program, per operation and per kernel, collective time
and its exposed part, and the longest idle gaps with the benchmark span
that covers each.

What a trace of this system on a TPU v5e looks like (looked at by hand,
PR 25, ``benchmarks/tests/data/train1.xplane.pb.gz`` is such a trace):

- one plane per chip, named ``/device:TPU:<n>``. Its lines: ``Steps``,
  ``XLA Modules`` (one event per executed program, named
  ``jit_<function>(<fingerprint>)``), ``XLA Ops`` (one event per executed
  HLO instruction, named by its whole HLO text ``%name = shape
  opcode(operands), attributes``) and ``Async XLA Ops`` (one event per
  asynchronous pair, named by the ``-start`` instruction and lasting from
  start to done: copies, slices and, across chips, collectives);
- ``while``/``call``/``conditional`` events CONTAIN the events of their
  bodies on the same line, so time per operation is self time;
- a Pallas kernel is a ``custom-call`` whose text holds
  ``custom_call_target="tpu_custom_call"``; its name is the kernel's
  (``splash_mha_fwd_residuals``, ``paged_attention_kernel`` ...) where
  the kernel was given one and the enclosing transform's (``jvp__``)
  where it was not. Other custom-calls (``ConcatBitcast``) are XLA's own;
- the host is the plane ``/host:CPU``, one line per thread;
  ``jax.profiler.TraceAnnotation`` spans land on the calling thread's
  line under their own name. Host and device share one clock (ns).

Only ``jax`` is needed (``jax.profiler.ProfileData``).
"""
from __future__ import annotations

import bisect
import functools
import gzip
import re
import statistics
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
HOST_PLANE = "/host:CPU"
OPCODE = re.compile(r"[\}\)\]] ([a-z][a-z0-9\-]*)\(")
COLLECTIVE = re.compile(
    r"^(all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"collective-broadcast|ragged-all-to-all)(-start|-done)?$")
WRAPPERS = frozenset({"while", "call", "conditional"})
BENCH_SPAN = "bench/"

Interval = Tuple[float, float]


def load(path: str):
    """``ProfileData`` of a ``.xplane.pb`` or a gzipped one."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return ProfileData.from_serialized_xspace(f.read())
    return ProfileData.from_file(path)


@functools.lru_cache(maxsize=None)     # one text per instruction, run often
def parse_op(text: str) -> Tuple[str, str, bool]:
    """(instruction name without its ``.N``, opcode, is a Pallas kernel)
    from an ``XLA Ops`` event's HLO text."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text, "", False
    m = OPCODE.search(rest)
    opcode = m.group(1) if m else ""
    name = re.sub(r"\.\d+$", "", head.lstrip("%"))
    return name, opcode, (opcode == "custom-call"
                          and "tpu_custom_call" in rest)


@functools.lru_cache(maxsize=None)
def result_shape(text: str) -> str:
    """``bf16[8,2048,16,128]`` of an instruction's HLO text (its layout
    dropped; ``(tuple)`` for several results): what tells one ``copy`` or
    one generic ``fusion`` from another."""
    rest = text.partition(" = ")[2]
    if rest.startswith("("):
        return "(tuple)"
    m = re.match(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return m.group(0) if m else ""


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def length(intervals: Iterable[Interval]) -> float:
    return sum(b - a for a, b in intervals)


def clip(intervals: Iterable[Interval], lo: float, hi: float):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of union ``a`` not covered by union ``b``."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def self_times(events: List[tuple]) -> List[float]:
    """Self nanoseconds of each (start, end, ...) event of ONE line, where
    an event may contain later ones: its time less its children's."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: List[int] = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack and e <= events[stack[-1]][1]:
            own[stack[-1]] -= e - s
        stack.append(i)
    return [max(0.0, v) for v in own]


def _events(line) -> List[tuple]:
    return [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
            for e in line.events]


def _host_spans(profile) -> List[tuple]:
    spans = []
    for plane in profile.planes:
        if plane.name != HOST_PLANE:
            continue
        for line in plane.lines:
            spans += [ev for ev in _events(line)
                      if ev[2].startswith(BENCH_SPAN)]
    return spans


def _covering_span(spans: List[tuple], t: float) -> str:
    """The innermost benchmark span on the host that covers time ``t``."""
    best: Optional[tuple] = None
    for s, e, name in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, name)
    return best[2] if best else "outside the benchmark's spans"


def reduce_trace(path: str, window_span: str = "bench/trace_slice",
                 top: int = 10) -> Optional[dict]:
    """Reduce one trace. The window is the host span ``window_span`` where
    the trace has one, else each device's first operation to its last.
    Returns None when no device plane holds an operation."""
    profile = load(path)
    spans = _host_spans(profile)
    named = [sp for sp in spans if sp[2] == window_span]
    devices = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        lines = {ln.name: _events(ln) for ln in plane.lines}
        ops = lines.get("XLA Ops") or []
        if not ops:
            continue
        lo = min(s for s, _, _ in ops)
        hi = max(e for _, e, _ in ops)
        if named:
            lo = max(lo, min(s for s, _, _ in named))
            hi = min(hi, max(e for _, e, _ in named))
        devices.append(_reduce_device(int(m.group(1)), lines, lo, hi, spans))
    if not devices:
        return None
    devices.sort(key=lambda d: d["device"])
    d0 = devices[0]
    n = len(devices)
    return {
        "n_devices": n,
        "window_s": sum(d["window_s"] for d in devices) / n,
        "busy_s": sum(d["busy_s"] for d in devices) / n,
        "idle_share": sum(d["idle_share"] for d in devices) / n,
        "per_device": [{k: d[k] for k in ("device", "window_s", "busy_s",
                                          "idle_share")} for d in devices],
        # everything below: device 0, the first of the cell's chips
        "modules": d0["modules"], "ops": d0["ops"], "kernels": d0["kernels"],
        "pallas_s": d0["pallas_s"],
        "collective_s": d0["collective_s"],
        "collective_exposed_s": d0["collective_exposed_s"],
        "collectives": d0["collectives"],
        "breakdown": {"device_ops": d0["top_ops"][:top],
                      "idle_gaps": d0["top_gaps"][:top]},
    }


def kernel_seconds(reduced: dict, needle: str) -> Tuple[float, int]:
    """(device seconds, calls) of the Pallas kernels whose name holds
    ``needle``, from a reduced trace."""
    hits = [v for k, v in reduced["kernels"].items() if needle in k]
    return sum(v["total_s"] for v in hits), sum(v["count"] for v in hits)


def _reduce_device(index: int, lines: Dict[str, list], lo: float, hi: float,
                   spans: List[tuple]) -> dict:
    ops = [ev for ev in lines["XLA Ops"] if ev[1] > lo and ev[0] < hi]
    window = max(hi - lo, 1.0)
    busy = union(clip([(s, e) for s, e, _ in ops], lo, hi))
    own = self_times(ops)

    per_op: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    per_kernel: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    coll: Dict[str, List[float]] = defaultdict(lambda: [0.0, 0])
    compute, collective = [], []
    for (s, e, text), mine in zip(ops, own):
        name, opcode, pallas = parse_op(text)
        is_coll = bool(COLLECTIVE.match(opcode))
        label = (f"{name} [pallas]" if pallas else
                 f"{name} {result_shape(text)} [{opcode or 'op'}]")
        per_op[label][0] += mine
        per_op[label][1] += 1
        if pallas:
            per_kernel[name][0] += e - s
            per_kernel[name][1] += 1
        if is_coll:
            collective.append((s, e))
            coll[opcode][0] += e - s
            coll[opcode][1] += 1
        elif opcode not in WRAPPERS:
            compute.append((s, e))
    for s, e, text in lines.get("Async XLA Ops") or []:
        _, opcode, _ = parse_op(text)
        if COLLECTIVE.match(opcode) and e > lo and s < hi:
            collective.append((s, e))
            coll[opcode + " (start to done)"][0] += e - s
            coll[opcode + " (start to done)"][1] += 1
    collective_u = union(clip(collective, lo, hi))
    exposed = subtract(collective_u, union(clip(compute, lo, hi)))

    modules: Dict[str, List[float]] = defaultdict(list)
    mods = sorted(ev for ev in (lines.get("XLA Modules") or [])
                  if ev[1] > lo and ev[0] < hi)
    for s, e, name in mods:
        modules[name].append(e - s)      # WITH its fingerprint: the engine's
        # prefill, scatter and decode programs are all ``jit_pure(<id>)``

    gaps: Dict[str, float] = defaultdict(float)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    mod_ends = [e for _, e, _ in mods]        # programs run one at a time
    for a, b in zip(edges[0::2], edges[1::2]):
        if b - a < 1e3:                       # under a microsecond
            continue
        k = bisect.bisect_right(mod_ends, a + 1.0)
        after = re.sub(r"\(\d+\)$", "", mods[k - 1][2]) if k else "start"
        gaps[f"{_covering_span(spans, (a + b) / 2)} | after {after}"] += b - a

    def ranked(table, scale=1e-9):
        return sorted(([k, v * scale] for k, v in table.items()),
                      key=lambda kv: -kv[1])

    return {
        "device": index, "window_s": window * 1e-9,
        "busy_s": length(busy) * 1e-9,
        "idle_share": 1.0 - length(busy) / window,
        "modules": {k: {"count": len(v), "total_s": sum(v) * 1e-9,
                        "median_s": statistics.median(v) * 1e-9}
                    for k, v in modules.items()},
        "ops": {k: {"self_s": v[0] * 1e-9, "count": v[1]}
                for k, v in per_op.items()},
        "kernels": {k: {"total_s": v[0] * 1e-9, "count": v[1]}
                    for k, v in per_kernel.items()},
        "pallas_s": sum(v[0] for v in per_kernel.values()) * 1e-9,
        "collective_s": length(collective_u) * 1e-9,
        "collective_exposed_s": length(exposed) * 1e-9,
        "collectives": {k: {"total_s": v[0] * 1e-9, "count": v[1]}
                        for k, v in coll.items()},
        "top_ops": ranked({k: v[0] for k, v in per_op.items()}),
        "top_gaps": ranked(gaps),
    }
