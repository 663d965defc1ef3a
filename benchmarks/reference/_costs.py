"""Arithmetic every reference module's cost functions share: it knows no
architecture. A cost is ``{"flops": ..., "bytes": ...}`` for one call (or
one step) of a kernel, computed from shapes by the configuration's own
reference module; what the chip could do with it is the same for all."""
from __future__ import annotations


def roofline_seconds(cost: dict, peaks: dict) -> tuple:
    """(least seconds the chip could take, which peak bounds it)."""
    t_flops = cost["flops"] / peaks["flops_bf16_per_s"]
    t_bytes = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (max(t_flops, t_bytes),
            "compute" if t_flops >= t_bytes else "memory")
