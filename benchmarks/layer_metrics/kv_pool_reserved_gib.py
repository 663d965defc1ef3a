"""GiB the engine reserved for K/V pools at construction, summed over
layer types: the gauge ``serving_kv_pool_bytes{layer_type}`` (``global``:
``max_len`` of pages a slot; ``window``: a ring of ``ceil(window /
page_size) + 1`` pages a slot). ``command-a-plus-ep8-d4`` plans 2.69 GB =
2.51 GiB (uniform pools: 4.29 GB = 4.00 GiB). Nothing on a program without
the gauge."""
LAYER = "serving.py engine step loop"
UNIT = "GiB"
MOVES = "peak_hbm_gib"
SOURCE = "program_counter"


def read(ctx):
    from benchmarks.lib.common import note

    if "after" not in ctx:
        return None
    pools = {}
    for series, value in ctx["after"]["metrics"].items():
        if series.startswith("serving_kv_pool_bytes{") \
                and 'layer_type="' in series:
            kind = series.split('layer_type="', 1)[1].split('"', 1)[0]
            pools[kind] = pools.get(kind, 0.0) + value
    if not pools or sum(pools.values()) <= 0:
        return None
    note("kv_pools", bytes_by_layer_type=pools)
    return sum(pools.values()) / 2.0 ** 30
