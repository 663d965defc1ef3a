"""What every cell shares: where the benchmark's files live, how a cell is
found by name, the device check, the compile meter and the result line.

Everything that belongs to ONE configuration, traffic mix, cell or
per-layer metric is a file of its own, found by the name in
``BENCHMARK.json`` — this module holds no list of them.
"""
from __future__ import annotations

import hashlib
import importlib
import importlib.util
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
OUT = os.path.join(BENCH, "out")


def load_json(path: str) -> dict:
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def note(_note: str, **fields) -> None:
    """An earlier line of the run: everything that is not the result."""
    emit({"note": _note, **fields})


def load_module(path: str, name: str):
    """Import one file of the benchmark by path (readers, traffic kinds,
    references)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def import_object(ref: str):
    """``module:Name`` -> the object."""
    mod, _, name = ref.partition(":")
    return getattr(importlib.import_module(mod), name)


class Cell:
    """One entry of ``workloads`` with everything its name leads to."""

    def __init__(self, name: str, bench_dir: str = BENCH):
        root = os.path.dirname(bench_dir)
        self.bench_dir = bench_dir
        self.manifest = load_json(os.path.join(root, "BENCHMARK.json"))
        entries = {w["name"]: w for w in self.manifest["workloads"]}
        if name not in entries:
            raise SystemExit(f"benchmark: no workload {name!r} in "
                             f"BENCHMARK.json (have {sorted(entries)})")
        self.name = name
        self.entry = entries[name]
        self.chips = int(self.entry["chips"])
        self.file = load_json(os.path.join(bench_dir, "workloads",
                                           f"{name}.json"))
        configs = {c["name"]: c for c in self.manifest["configs"]}
        self.config_entry = configs[self.entry["config"]]
        self.config = load_json(os.path.join(root, self.config_entry["file"]))
        self.traffic_name = self.entry["traffic"]
        self.traffic = load_json(os.path.join(bench_dir, "traffic",
                                              f"{self.traffic_name}.json"))

    def metrics(self, group: str) -> list:
        """The manifest's metrics of ``group`` that this cell reports."""
        return [m for m in self.manifest[group]
                if "workloads" not in m or self.name in m["workloads"]]

    def traffic_kind(self):
        kind = self.traffic["kind"]
        return load_module(os.path.join(self.bench_dir, "traffic_kinds",
                                        f"{kind}.py"),
                           f"bench_traffic_kind_{kind}")

    def reader(self, metric: str):
        path = os.path.join(self.bench_dir, "layer_metrics", f"{metric}.py")
        return load_module(path, f"bench_layer_metric_{metric}")

    def reference(self):
        """The plain reference this cell's configuration names: the file
        ``reference/<module>.py`` (its contract is in
        ``reference/__init__.py``). A configuration that names none is an
        error, never a default."""
        module = (self.config.get("reference") or {}).get("module")
        if not module:
            raise SystemExit(
                f"benchmark: {self.config_entry['file']} names no reference "
                f"(\"reference\": {{\"module\": <a file's stem under "
                f"benchmarks/reference/>}})")
        return load_module(os.path.join(self.bench_dir, "reference",
                                        f"{module}.py"),
                           f"bench_reference_{module}")


def reference_function(ctx: dict, metric: str, name: str):
    """The function ``name`` of the cell's own reference module, for the
    reader of ``metric``; None, with a note, where that module has none.
    A reader never borrows another configuration's arithmetic."""
    fn = getattr(ctx.get("reference"), name, None)
    if fn is None:
        note("reader_skipped", metric=metric, lacks=name,
             reference=getattr(ctx.get("reference"), "__name__", None))
    return fn


def rehearsed(section: dict, rehearse: bool) -> dict:
    """A file's parameters, with its ``rehearse`` overrides laid over them
    for the tiny CPU rehearsal (a shallow merge, one level into dicts)."""
    out = {k: v for k, v in section.items() if k != "rehearse"}
    if rehearse:
        for k, v in section.get("rehearse", {}).items():
            if isinstance(v, dict) and isinstance(out.get(k), dict):
                out[k] = {**out[k], **v}
            else:
                out[k] = v
    return out


def peaks_for(kind: str) -> dict:
    """The chip's published peaks; a kind that is not in the table is an
    error, never a default."""
    table = load_json(os.path.join(BENCH, "peaks.json"))["device_kinds"]
    if kind not in table:
        raise SystemExit(f"benchmark: no peaks for device kind {kind!r} in "
                         f"benchmarks/peaks.json; add it with its source")
    return table[kind]


def device_info(chips: int, rehearse: bool) -> dict:
    """The device as JAX reports it. Without a TPU, or with fewer chips
    than the cell asks for, a measuring run stops here with a non-zero
    exit and no result line; the rehearsal goes on and ends
    ``correct: false``."""
    import jax

    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if rehearse:
        if len(devs) < chips:
            raise SystemExit(f"benchmark --rehearse: {chips} (virtual) "
                             f"devices wanted, jax found {len(devs)}")
    elif info["platform"] != "tpu" or len(devs) < chips:
        raise SystemExit(f"benchmark: needs {chips} TPU chip(s); jax found "
                         f"{len(devs)} x {info['platform']!r} "
                         f"({info['kind']})")
    info["count"] = chips           # the chips this cell uses
    return info


def memory_stats_note(chips: int) -> None:
    """Everything the backend says about each chip's memory."""
    import jax

    note("memory_stats", per_chip=[d.memory_stats() or {}
                                   for d in jax.devices()[:chips]])


def memory_peak_bytes(chips: int):
    """Peak bytes in use on the fullest of the cell's chips (None where
    the backend keeps no such count: the CPU)."""
    import jax

    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
             for d in jax.devices()[:chips]]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


class CompileMeter:
    """Backend compile seconds and persistent-cache hits and misses of
    this process (``jax.monitoring``; the idiom of ``chip_smoke.py``)."""

    def __init__(self):
        from jax import monitoring

        self.seconds, self.hits, self.misses, self.compiles = 0.0, 0, 0, 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, name, secs, **kw):
        if name.endswith("backend_compile_duration"):
            self.seconds += float(secs)
            self.compiles += 1

    def _event(self, name, **kw):
        if name.endswith("compilation_cache/cache_hits"):
            self.hits += 1
        elif name.endswith("compilation_cache/cache_misses"):
            self.misses += 1

    def mark(self) -> tuple:
        """Programs built so far, from the cache or not."""
        return (self.compiles, self.hits, self.misses)

    def report(self) -> dict:
        return {"compile_s": round(self.seconds, 3), "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def file_digest(path: str) -> str:
    try:
        with open(path, "rb") as f:
            return hashlib.sha256(f.read()).hexdigest()
    except FileNotFoundError:
        return ""


class SetupClock:
    """Splits set-up into named parts; ``setup_s`` itself is taken from
    the process's start to the opening of the window."""

    def __init__(self, t_start: float):
        self.t_start = t_start
        self._last = time.time()
        self.parts = {"imports_s": round(self._last - t_start, 3)}

    def lap(self, name: str) -> None:
        now = time.time()
        self.parts[name] = round(self.parts.get(name, 0.0)
                                 + now - self._last, 3)
        self._last = now


def start_jax(cell: Cell, rehearse: bool):
    """Common head of every cell: the program's compile cache (a fixed
    path inside the checkout, or ``JAX_COMPILATION_CACHE_DIR``), the
    meter, the device."""
    os.makedirs(OUT, exist_ok=True)
    from paddle_tpu.utils import compile_cache

    cache_dir = compile_cache.enable() or os.environ.get(
        "JAX_COMPILATION_CACHE_DIR")
    import jax

    # where the environment names the directory the program sets no
    # thresholds either, and JAX's default keeps no program that compiled
    # in under a second: the engine's ~180 small index and mask programs
    # then compile anew in every run's set-up (24 s, my chip run, PR 25)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    meter = CompileMeter()
    device = device_info(cell.chips, rehearse)
    return device, meter, cache_dir


def result_line(*, correct: bool, attempted: int, failed: int, metrics: dict,
                units: dict, device: dict, breakdown=None,
                compared=None) -> dict:
    """The contract's last line. ``metrics`` maps a name to a number (or
    None: left out); ``compared`` maps each number the comparison with
    the reference read to ``{"value", "limit"}`` and comes last."""
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed),
            "metrics": {k: {"value": float(v), "unit": units[k]}
                        for k, v in metrics.items() if v is not None},
            "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared or {}
    return line
