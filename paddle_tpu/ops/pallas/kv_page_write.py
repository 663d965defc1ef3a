"""Pallas write of one decode step's new K (or V) rows into a page pool,
in the pool's own layout.

Role anchor: the cache-write half of the reference's
block_multi_head_attention serving kernel
(paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu),
which stores the step's K/V at ``block_tables[b, pos // block_size]``
before it attends; the attention half is ``paged_decode``.

Why a kernel: the pool ``[hk, n_pages, page_size, D]`` has to stay in
the default layout from the decode program's parameter through
``paged_attention`` to the program's result. XLA's layout assignment
gives the scatter ``pool.at[:, rows, slot].set(new)`` a layout with
pages and slots major, so on a TPU it transposes the WHOLE pool into
that layout and back around every scatter: two copies of 67 MB a pool
and layer to store 16 rows (docs/SERVING.md, "The page pool's layout").
This kernel aliases the pool in and out and moves one page a row.

Kernel shape (one grid cell per batch row, in order):
- ``rows`` (page of the pool), ``slot`` (row inside the page) and ``src``
  (which row of ``new`` to store) arrive as scalar-prefetch operands, so
  the index maps pick the page ``(hk, 1, page_size, D)`` and the new row
  ``(1, hk, 1, D)`` before the body runs;
- the body selects the new row into the page,
  ``where(iota_over_page_size == slot, new, old)``, and stores the whole
  page: no dynamic sub-tile store on packed bf16;
- ``input_output_aliases`` makes the result the pool's own buffer, so the
  pages no row touches are never moved.

Same result as the scatter, bit for bit, under the one condition a paged
cache already holds: the rows that write target DISTINCT pages (a page
being appended to belongs to one sequence). A row the scatter would drop
(its page outside the pool) must leave the pool as it was, but a grid
cell cannot skip its page's write-back, and its page may be fetched
before or after another cell's store lands. So such a row repeats the
write of the first row that does write (same page, slot and values:
storing it twice gives the same page whichever copy was read), or, when
no row writes, stores a page unchanged.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import backend

SITE = "kv_page_write"


def _shape_refusal(pages, new):
    if pages.ndim != 4 or new.ndim != 3:
        return "pool must be [hk, n_pages, page_size, D], rows [B, hk, D]"
    hk, _n, _page_size, d = pages.shape
    if new.shape[1:] != (hk, d):
        return f"rows {new.shape} do not match the pool {pages.shape}"
    # Mosaic compiles any head width, but at one that is not whole lanes
    # the custom call asks for another layout than the pool's and XLA
    # puts back the two copies of the pool this kernel exists to remove
    # (described compile, v5e: D 64, 80, 96, 192 -> 2 copies; 128, 256 -> 0)
    if d % 128 != 0:
        return f"head width {d} is not a multiple of 128 lanes"
    return None


def supported(pages, new, interpret: bool = False) -> bool:
    """Gate: a TPU (``interpret`` admits the interpreter off-TPU: tests),
    a program GSPMD will not partition, and a head width of whole lanes."""
    return backend.gate(SITE, _shape_refusal(pages, new), interpret)


def _kernel(rows_ref, slot_ref, src_ref, page_ref, new_ref, out_ref):
    del rows_ref, src_ref                      # used by the index maps
    slot = slot_ref[pl.program_id(0)]
    page = page_ref[:, 0]                      # [hk, page_size, D]
    at = jax.lax.broadcasted_iota(jnp.int32, page.shape, 1)
    out_ref[:, 0] = jnp.where(at == slot, new_ref[0], page)


def kv_page_write(pages, rows, slot, new):
    """``pages.at[:, rows, slot].set(moveaxis(new, 0, 1))`` for pages
    [hk, n_pages, page_size, D], rows/slot [B] int32 and new [B, hk, D]
    (cast to the pool's dtype), with the scatter's own reading of an
    index: a negative page counts from the end, a page still outside the
    pool drops its row. Off-TPU the kernel runs interpreted."""
    hk, n_pages, page_size, d = pages.shape
    B = new.shape[0]
    rows = jnp.asarray(rows, jnp.int32)
    rows = jnp.where(rows < 0, rows + n_pages, rows)
    writes = (rows >= 0) & (rows < n_pages)
    # a dropped row repeats the first writing row; with none, slot -1
    # matches no row of the page and every cell stores its page as read
    src = jnp.where(writes, jnp.arange(B, dtype=jnp.int32),
                    jnp.argmax(writes).astype(jnp.int32))
    rows = jnp.clip(rows, 0, n_pages - 1)[src]
    slot = jnp.where(writes.any(), jnp.asarray(slot, jnp.int32)[src], -1)
    # the page a row writes: the same block in and out
    page_block = pl.BlockSpec((hk, 1, page_size, d),
                              lambda b, rows, slot, src: (0, rows[b], 0, 0))
    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(B,),
            in_specs=[
                page_block,
                pl.BlockSpec((1, hk, 1, d),
                             lambda b, rows, slot, src: (src[b], 0, 0, 0)),
            ],
            out_specs=page_block,
        ),
        out_shape=jax.ShapeDtypeStruct(pages.shape, pages.dtype),
        # operands count the scalar-prefetch arrays: the pool is the 4th
        input_output_aliases={3: 0},
        interpret=backend.interpret_mode(),
        name=SITE,
    )(rows, slot, src, pages, new.astype(pages.dtype)[:, :, None, :])
