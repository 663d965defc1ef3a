"""Text generation: static KV-cache decode, sampling, paged attention.

Reference parity: the serving slice the reference builds from
- block_multi_head_attention (paged KV cache decode kernel,
  paddle/phi/kernels/fusion/gpu/block_multi_head_attention_kernel.cu),
- top_p_sampling (paddle/phi/kernels/gpu/top_p_sampling_kernel.cu /
  python/paddle/tensor/random.py top_p_sampling),
- PaddleNLP's GenerationMixin greedy/sampling loops.

TPU-native design: the KV cache is a STATIC-shape buffer per layer —
dense [B, max_len, kv_heads, head_dim] or paged (block tables) — updated
with dynamic_update_slice/scatter, and the whole decode step (embed →
layers → lm head → cache update) is ONE jitted computation with the cache
buffers donated, so each generated token is a single device dispatch and
the buffers are updated in place. Paged decode attention runs the Pallas
kernel of ``ops/pallas/paged_decode.py`` on TPU (jnp gather reference
elsewhere).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .tensor_class import Tensor, unwrap, wrap
from .ops.registry import apply
from .ops.pallas import backend as _pallas_backend
from .ops.pallas import kv_page_write as _kv_page_write
from .ops.pallas import paged_decode as _paged_decode
from .autograd import tape as _tape
from .framework import random as _random
from .nn.layer import functional_weights as _functional_weights


# ---------------------------------------------------------------------------
# cache attention kernels (dense + paged)
# ---------------------------------------------------------------------------

def _rope_rows(x, cos, sin, row_pos):
    """RoPE with PER-ROW positions: x [B,S,H,D], row_pos [B] — row b's
    token s sits at absolute position row_pos[b]+s (ragged decode);
    width-aware via partial_rope."""
    from .ops.pallas.fused_norm import partial_rope

    return partial_rope(_rope_rows_full, x, cos, sin, row_pos)


def _rope_rows_full(x, cos, sin, row_pos):
    S = x.shape[1]
    d = x.shape[-1]
    idx = row_pos[:, None] + jnp.arange(S)[None, :]        # [B, S]
    cos_b = cos[idx]                                       # [B, S, D]
    sin_b = sin[idx]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    c = cos_b[:, :, None, :]
    s = sin_b[:, :, None, :]
    return (x.astype(jnp.float32) * c + rotated.astype(jnp.float32) * s
            ).astype(x.dtype)


def cached_attention(q, k, v, cos, sin, k_buf, v_buf, pos, allowed=None,
                     row_pos=None, use_flash=False, interpret=False,
                     prefill=False, window=None, softcap=None,
                     rope_applied=False):
    """RoPE + cache write + masked GQA attention against a dense buffer.

    q [B,S,H,D]; k/v [B,S,hk,D]; cos/sin [>=max_len, D];
    k_buf/v_buf [B,Smax,hk,D]; pos = buffer write offset (scalar);
    allowed = optional [B,Tmax] column-validity mask (padded prompts);
    row_pos = optional [B] per-row RoPE positions (ragged batches);
    use_flash = route a pos=0 prefill without ``allowed`` (the serving
    hot path) through the GQA splash flash kernel instead of the dense
    einsum against the whole buffer — at pos=0 prefill, causal attention
    over the prompt equals causal self-attention on the S new tokens, so
    the flash kernel is exact and never touches the (mostly empty) Smax
    buffer. A prompt padded on the RIGHT needs no ``allowed`` for this:
    under the causal mask a real token at position s sees columns 0..s,
    none of them a pad, so the engine's admission passes none (the pad
    rows' outputs are never read). ``allowed`` is for pads a real token
    could see: left padding, and the decode steps of a padded batch. It
    costs the kernel: a mask sends S > 1 to ``append_attention``, whose
    gate refuses more than 2048 score rows, and from there to the f32
    einsum over the whole buffer ([B, hk, g, S, T] scores).
    ``rope_applied``: q/k arrive already rotated (the fused decode-tail
    kernel ropes in-register) — skip the rope, keep everything else.
    Returns (out [B,S,H,D], new_k_buf, new_v_buf).
    """
    from .ops.pallas.fused_norm import rope_ref

    B, S, H, D = q.shape
    hk = k_buf.shape[2]
    pos = jnp.asarray(pos, jnp.int32)
    if rope_applied:
        pass
    elif row_pos is None:
        cos_s = jax.lax.dynamic_slice_in_dim(cos, pos, S, 0)
        sin_s = jax.lax.dynamic_slice_in_dim(sin, pos, S, 0)
        q = rope_ref(q, cos_s, sin_s)
        k = rope_ref(k, cos_s, sin_s)
    else:
        q = _rope_rows(q, cos, sin, row_pos)
        k = _rope_rows(k, cos, sin, row_pos)
    k_buf = jax.lax.dynamic_update_slice(
        k_buf, k.astype(k_buf.dtype), (0, pos, 0, 0))
    v_buf = jax.lax.dynamic_update_slice(
        v_buf, v.astype(v_buf.dtype), (0, pos, 0, 0))

    if use_flash and S > 1 and allowed is None and row_pos is None:
        from .ops.pallas import flash_attention as pf

        # `prefill` is the STATIC marker _empty_caches stamps on fresh
        # (pos=0) caches — it survives jit tracing, where even jnp
        # constants are tracers and a value check would always fail
        pos_is_zero = bool(prefill)
        if not pos_is_zero:
            try:
                pos_is_zero = int(pos) == 0  # eager caller: concrete scalar
            except Exception:  # pdlint: disable=silent-exception -- int() on a traced offset raises by design (TracerError); 'unknown, stay dense' is the correct conservative branch, not a fault
                pos_is_zero = False  # traced offset: unknown, stay dense
        if pos_is_zero and pf.supported(q, k, v, interpret=interpret):
            out = pf.flash_attention_bshd(q, k, v, causal=True,
                                          interpret=interpret, window=window)
            return out.astype(q.dtype), k_buf, v_buf

    if use_flash and S > 1 and window is None:
        # multi-token append at pos >= 0 (chunked prefill, speculative
        # verify): streaming-softmax Pallas kernel over the buffer, blocks
        # beyond pos+S skipped — replaces the dense full-buffer einsum
        from .ops.pallas import append_attention as pa

        if pa.supported(q, k_buf, interpret=interpret):
            out = pa.append_attention(q, k_buf, v_buf, pos, allowed=allowed,
                                      interpret=interpret)
            return out.astype(q.dtype), k_buf, v_buf

    g = H // hk
    scale = 1.0 / math.sqrt(D)
    qg = q.reshape(B, S, hk, g, D)
    scores = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k_buf.astype(jnp.float32)) * scale
    if softcap is not None:
        # Gemma2 tanh soft cap, applied before masking (HF order)
        scores = softcap * jnp.tanh(scores / softcap)
    T = k_buf.shape[1]
    t_idx = jnp.arange(T)
    s_idx = jnp.arange(S)
    valid = t_idx[None, :] <= (pos + s_idx)[:, None]        # [S, T]
    if window is not None and allowed is None and row_pos is None:
        # sliding window, contiguous layout: column t visible from row
        # (pos+s) only while t > (pos+s) - window
        valid = valid & (t_idx[None, :] > (pos + s_idx)[:, None] - window)
    mask = valid[None, None, None]                          # [1,1,1,S,T]
    if allowed is not None:
        mask = mask & allowed[:, None, None, None, :]       # [B,1,1,S,T]
    if window is not None and (allowed is not None or row_pos is not None):
        # ragged (right-padded) layout: buffer distance != token distance —
        # a short row's prompt sits at slots 0..len-1 while decode writes at
        # the SHARED offset pos, so the window must count TRUE positions:
        # column t's position in row b is the number of allowed columns
        # before it (pads excluded), and the query at buffer slot pos+s has
        # position colpos[b, pos+s]
        base = (allowed.astype(jnp.int32) if allowed is not None
                else jnp.ones((B, T), jnp.int32))
        colpos = jnp.cumsum(base, axis=1) - 1                # [B, T]
        curpos = jax.lax.dynamic_slice_in_dim(colpos, pos, S, 1)  # [B, S]
        win_ok = colpos[:, None, :] > curpos[:, :, None] - window  # [B, S, T]
        mask = mask & win_ok[:, None, None]                  # [B,1,1,S,T]
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs,
                     v_buf.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype), k_buf, v_buf


def paged_cached_attention(q, k, v, cos, sin, k_pages, v_pages, page_indices,
                           lengths, page_size, window=None, softcap=None,
                           rope_applied=False, ring=False):
    """Multi-token decode over the PAGED cache (in-layer dispatch).

    q [B,S,H,D]; pages [hk, n_pages, page_size, D]; lengths [B] = tokens
    already present PER ROW. Fully ragged: row b's token j is RoPE'd at
    position lengths[b]+j and written at its own page/slot
    (page_indices[b, pos//ps], pos%ps) — the block_multi_head_attention
    write pattern, which is what lets a continuous-batching server mix
    requests of different lengths in one step. S == 1 is the classic
    decode step; S > 1 is the speculative-verify chunk (each chunk
    position attends the cache plus the chunk prefix before it — the
    chunk-causal mask). ``rope_applied``: q/k arrive already rotated
    (fused decode tail) — skip the per-row rope, keep the write +
    attention.

    ``ring``: this layer's pool keeps a WINDOW per row, not the row: its
    ``page_indices`` has ``ceil(window / page_size) + 1`` pages a row and
    position ``p`` lives in page ``(p // page_size) mod`` that many (the
    engine's pools by layer type: docs/SERVING.md). One token a step only.
    """
    B, S = q.shape[0], q.shape[1]
    lengths = jnp.asarray(lengths, jnp.int32)
    if not rope_applied:
        q = _rope_rows(q, cos, sin, lengths)
        k = _rope_rows(k, cos, sin, lengths)
    if ring and S != 1:
        raise NotImplementedError(
            "a window ring takes one token a step: a chunk of "
            f"{S} would overwrite keys its own first token still sees")
    if S == 1:
        page = lengths // page_size                 # [B]
        if ring:
            page = page % page_indices.shape[1]
        slot = lengths % page_size                  # [B]
        rows = page_indices[jnp.arange(B), page]    # [B]
        k_pages = _write_decode_rows(k_pages, rows, slot, k[:, 0])
        v_pages = _write_decode_rows(v_pages, rows, slot, v[:, 0])
        out = paged_decode_attention(q[:, 0], k_pages, v_pages, lengths + 1,
                                     page_indices, window=window,
                                     softcap=softcap, ring=ring)
        return out[:, None], k_pages, v_pages
    # speculative-verify chunk: scatter all S tokens at per-row positions
    # lengths[b]+j, then chunk-causal attention over the gathered pages.
    # Rejected-suffix KV lands ABOVE the row's post-accept frontier, where
    # the next chunk's scatter overwrites it before lengths can reach it —
    # the same parking invariant chunked prefill relies on.
    pos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B,S]
    page = pos // page_size
    slot = pos % page_size
    rows = jnp.take_along_axis(page_indices, page, axis=1)            # [B,S]
    k_pages = k_pages.at[:, rows, slot].set(
        jnp.moveaxis(k, 2, 0).astype(k_pages.dtype))
    v_pages = v_pages.at[:, rows, slot].set(
        jnp.moveaxis(v, 2, 0).astype(v_pages.dtype))
    out = _paged_chunk_attention(q, k_pages, v_pages, lengths, page_indices,
                                 window=window, softcap=softcap)
    return out, k_pages, v_pages


def _write_decode_rows(pages, rows, slot, new):
    """One decode step's new rows ``new`` [B,hk,D] into the pool
    [hk,n_pages,page_size,D] at (rows[b], slot[b]). On a TPU the Pallas
    page write, which leaves the pool in its own layout; the XLA scatter
    (two relayouts of the whole pool on a TPU: docs/SERVING.md) is the
    reference elsewhere and where the gate refuses."""
    if _kv_page_write.supported(pages, new):
        return _kv_page_write.kv_page_write(pages, rows, slot, new)
    return pages.at[:, rows, slot].set(
        jnp.moveaxis(new, 0, 1).astype(pages.dtype))


def _paged_chunk_attention(q, k_pages, v_pages, lengths, page_indices,
                           window=None, softcap=None):
    """Chunk attention over the paged cache: q [B,S,H,D] sits at per-row
    positions lengths[b]+j; column t is visible from chunk position j iff
    t <= lengths[b]+j (and, windowed, t > lengths[b]+j-window). XLA
    gather + MXU matmul, exact vs the dense reference — the S=1 Pallas
    decode kernel has no chunk-causal mask, so the verify chunk takes
    this path on every backend."""
    B, S = q.shape[0], q.shape[1]
    hk, _n, page_size, D = k_pages.shape
    k = jnp.moveaxis(k_pages[:, page_indices], 0, 1)  # [B,hk,pages,ps,D]
    v = jnp.moveaxis(v_pages[:, page_indices], 0, 1)
    T = k.shape[2] * page_size
    k = k.reshape(B, hk, T, D)
    v = v.reshape(B, hk, T, D)
    qpos = lengths[:, None] + jnp.arange(S, dtype=jnp.int32)[None, :]  # [B,S]
    t_idx = jnp.arange(T)
    valid = t_idx[None, None, :] <= qpos[:, :, None]                # [B,S,T]
    if window is not None:
        valid &= t_idx[None, None, :] > (qpos[:, :, None] - window)
    return _chunk_sdpa(q, k, v, valid, softcap=softcap)


def paged_decode_attention(q, k_pages, v_pages, lengths, page_indices,
                           window=None, softcap=None, ring=False):
    """Decode attention over a paged cache: the Pallas kernel of
    ``ops/pallas/paged_decode.py`` on TPU, a jnp gather reference
    (identical semantics) elsewhere.

    ``window`` (Mistral sliding-window serving): only the last ``window``
    positions attend. The kernel has no lower-bound masking, so windowed
    rows take the O(window) page-gather path (_paged_window_attention:
    only the <= ceil(window/page_size)+1 pages the band intersects are
    read — HBM cost scales with the window, not the cache capacity).

    ``ring``: the pool holds each row's last ``page_indices.shape[1]``
    pages as a ring (``_paged_ring_attention``)."""
    if ring:
        _pallas_backend.took("paged_attention", _pallas_backend.XLA,
                             "window ring")
        return _paged_ring_attention(q, k_pages, v_pages, lengths,
                                     page_indices, window, softcap=softcap)
    if window is not None:
        cache_positions = page_indices.shape[1] * k_pages.shape[2]
        if window < cache_positions:
            # gather ONLY the pages the band can touch: O(window) work
            # regardless of max_len — the win windowed serving exists for
            _pallas_backend.took("paged_attention", _pallas_backend.XLA,
                                 "sliding window below the cache length")
            return _paged_window_attention(q, k_pages, v_pages, lengths,
                                           page_indices, window,
                                           softcap=softcap)
        # else: the band can never exclude a cached position (window >=
        # cache capacity) — fall through to the fused Pallas kernel,
        # e.g. Mistral-7B's 4096 window served at max_len <= 4096
    if softcap is not None:
        # the kernel computes uncapped scores; the exact gather reference
        # (O(cache) reads) keeps softcapped models (Gemma2) servable
        # through the paged engine
        _pallas_backend.took("paged_attention", _pallas_backend.XLA,
                             "attention softcap")
        return _paged_attention_ref(q, k_pages, v_pages, lengths,
                                    page_indices, softcap=softcap)
    if _paged_decode.supported(q, k_pages):
        return _paged_decode.paged_decode_attention(
            q, k_pages, v_pages, lengths, page_indices)
    return _paged_attention_ref(q, k_pages, v_pages, lengths, page_indices)


def _paged_window_attention(q, k_pages, v_pages, lengths, page_indices,
                            window, softcap=None):
    """Sliding-window decode over the paged cache, touching only the
    pages the band intersects (≤ ceil(window/page_size)+1 per row): HBM
    reads scale with the WINDOW, not the cache capacity — the long-
    context property windowed serving exists for. Pure XLA (gather +
    MXU matmul), exact vs the full-gather reference."""
    B = q.shape[0]
    hk, _n, page_size, _ = k_pages.shape
    wp = (window + page_size - 1) // page_size + 1     # pages the band spans
    n_pages_per_row = page_indices.shape[1]
    wp = min(wp, n_pages_per_row)
    # first page the band can touch (band = [len-window, len-1])
    first = jnp.maximum(lengths - window, 0) // page_size        # [B]
    first = jnp.minimum(first, jnp.maximum(n_pages_per_row - wp, 0))
    offs = first[:, None] + jnp.arange(wp)[None, :]              # [B, wp]
    rows = jnp.take_along_axis(page_indices, offs, axis=1)       # [B, wp]
    k = jnp.moveaxis(k_pages[:, rows], 0, 1)     # [B, hk, wp, ps, D]
    v = jnp.moveaxis(v_pages[:, rows], 0, 1)
    W = wp * page_size
    k = k.reshape(B, hk, W, k_pages.shape[-1])
    v = v.reshape(B, hk, W, v_pages.shape[-1])
    # global position of each gathered column
    colpos = (offs[:, :, None] * page_size
              + jnp.arange(page_size)[None, None, :]).reshape(B, W)
    valid = (colpos < lengths[:, None]) & \
            (colpos >= (lengths[:, None] - window))
    return _banded_sdpa(q, k, v, valid, softcap=softcap)


def _paged_ring_attention(q, k_pages, v_pages, lengths, page_indices,
                          window, softcap=None):
    """Sliding-window decode over a pool that keeps only a ring of
    ``page_indices.shape[1]`` pages a row: position ``p`` lives at ring
    index ``p mod W`` (``W`` = ring pages x page size, at least ``window +
    page_size``), so index ``i`` of a row that holds ``lengths`` tokens
    holds the newest position congruent to it, and is read iff that
    position is inside the band. A ring pool is SLOT-MAJOR (row ``b`` owns
    pages ``[b x ring, (b + 1) x ring)``: the engine allocates it so and
    ``page_indices`` says the same), so the read is a reshape of the pool
    and never a gather of it: K and V cross HBM once, in their own type,
    with f32 scores and softmax. Attention does not see the order of its
    keys, so nothing is rotated back."""
    B, H, D = q.shape
    hk, n_pages, page_size, _ = k_pages.shape
    ring = page_indices.shape[1]
    if n_pages != B * ring:
        raise ValueError(
            f"a ring pool is slot-major: {n_pages} pages are not "
            f"{B} rows x {ring}")
    W = ring * page_size
    g = H // hk
    k = k_pages.reshape(hk, B, W, D)
    v = v_pages.reshape(hk, B, W, D)
    newest = lengths[:, None] - 1                                # [B, 1]
    held = newest - (newest - jnp.arange(W)[None, :]) % W       # [B, W]
    valid = (held >= 0) & (held > newest - window)
    # the batch dimensions in the POOL's order (head, row): in the other
    # order XLA transposes both pools to it, a copy of each in every step
    qk = jnp.moveaxis(q.reshape(B, hk, g, D), 0, 1)             # [hk,B,g,D]
    scores = jnp.einsum("kbgd,kbtd->kbgt", qk, k,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(D)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(valid[None, :, None, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("kbgt,kbtd->kbgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return jnp.moveaxis(out, 0, 1).reshape(B, H, D).astype(q.dtype)


def _banded_sdpa(q, k, v, valid, softcap=None):
    """Shared decode-attention tail: q [B,H,D], k/v [B,hk,T,D] gathered,
    valid [B,T] column mask — the S=1 view of :func:`_chunk_sdpa` (the
    ONE place the f32 softmax numerics of the paged decode paths live)."""
    return _chunk_sdpa(q[:, None], k, v, valid[:, None],
                       softcap=softcap)[:, 0]


def _chunk_sdpa(q, k, v, valid, softcap=None):
    """Decode/verify attention core: q [B,S,H,D] against gathered k/v
    [B,hk,T,D] with a per-position column mask valid [B,S,T]. f32 scores
    and softmax; ``softcap``: Gemma2 tanh soft cap on the scaled scores,
    applied before masking (HF order)."""
    B, S, H, D = q.shape
    hk = k.shape[1]
    g = H // hk
    qg = q.reshape(B, S, hk, g, D).astype(jnp.float32)
    scores = jnp.einsum("bskgd,bktd->bkgst", qg, k.astype(jnp.float32))
    scores = scores / math.sqrt(D)
    if softcap is not None:
        scores = softcap * jnp.tanh(scores / softcap)
    scores = jnp.where(valid[:, None, None, :, :], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,bktd->bskgd", probs, v.astype(jnp.float32))
    return out.reshape(B, S, H, D).astype(q.dtype)


def _paged_attention_ref(q, k_pages, v_pages, lengths, page_indices,
                         window=None, softcap=None):
    B, H, D = q.shape
    hk, _n, page_size, _ = k_pages.shape
    g = H // hk
    k = jnp.moveaxis(k_pages[:, page_indices], 0, 1)  # [B, hk, pages, ps, D]
    v = jnp.moveaxis(v_pages[:, page_indices], 0, 1)
    T = k.shape[2] * page_size
    k = k.reshape(B, hk, T, D)
    v = v.reshape(B, hk, T, D)
    valid = jnp.arange(T)[None, :] < lengths[:, None]
    if window is not None:
        # band lower bound: only the newest `window` positions attend
        valid &= jnp.arange(T)[None, :] >= (lengths[:, None] - window)
    return _banded_sdpa(q, k, v, valid, softcap=softcap)


# ---------------------------------------------------------------------------
# sampling
# ---------------------------------------------------------------------------

def _top_k_filter(logits, k):
    if k <= 0 or k >= logits.shape[-1]:
        return logits
    kth = jnp.sort(logits, axis=-1)[..., -k][..., None]
    return jnp.where(logits < kth, -jnp.inf, logits)


def _top_p_filter(logits, p):
    if p >= 1.0:
        return logits
    srt = jnp.sort(logits, axis=-1)[..., ::-1]
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1)
    # keep tokens until cumulative prob exceeds p (always keep the first)
    keep = jnp.concatenate(
        [jnp.ones_like(cum[..., :1], bool), cum[..., :-1] < p], -1)
    min_logit = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1, keepdims=True)
    return jnp.where(logits < min_logit, -jnp.inf, logits)


def sample_logits(logits, key, do_sample=False, temperature=1.0,
                  top_k=0, top_p=1.0):
    """Next-token selection from [B, V] logits (pure)."""
    logits = logits.astype(jnp.float32)
    # temperature ~ 0 is greedy (matches sample_logits_rows): dividing by
    # the 1e-6 cap instead would hand near-tied runner-ups real probability
    if not do_sample or (not isinstance(temperature, jnp.ndarray)
                         and temperature <= 1e-6):
        return jnp.argmax(logits, axis=-1)
    if temperature != 1.0:
        logits = logits / jnp.maximum(temperature, 1e-6)
    logits = _top_k_filter(logits, int(top_k))
    logits = _top_p_filter(logits, float(top_p))
    return jax.random.categorical(key, logits, axis=-1)


def sample_logits_rows(logits, key, do_sample, temperature, top_k, top_p):
    """Per-ROW next-token selection from [B, V] logits: every sampling knob
    is a [B] array (the continuous-batching engine's per-request sampling —
    one compiled program serves any mix of greedy/temperature/top-k/top-p
    requests). Rows with do_sample=False take the plain argmax; top_k <= 0
    means no k-filter; top_p >= 1 means no nucleus filter."""
    lg = logits.astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1)
    V = lg.shape[-1]
    x = lg / jnp.maximum(temperature, 1e-6)[:, None]
    # per-row top-k via the kth-value threshold (ties at the kth value are
    # kept, matching _top_k_filter's semantics)
    srt_desc = jnp.sort(x, axis=-1)[:, ::-1]
    idx = jnp.clip(top_k - 1, 0, V - 1)
    kth = jnp.take_along_axis(srt_desc, idx[:, None], axis=-1)  # [B, 1]
    kth = jnp.where(((top_k <= 0) | (top_k >= V))[:, None], -jnp.inf, kth)
    x = jnp.where(x < kth, -jnp.inf, x)
    # per-row top-p over the k-filtered distribution. The k-filter zeroes a
    # SUFFIX of the descending sort, so the sorted filtered logits (and
    # hence sorted probs) come from srt_desc directly — no second sort
    probs = jax.nn.softmax(x, axis=-1)
    srt = jax.nn.softmax(jnp.where(srt_desc < kth, -jnp.inf, srt_desc),
                         axis=-1)
    cum = jnp.cumsum(srt, axis=-1)
    keep = jnp.concatenate(
        [jnp.ones_like(cum[:, :1], bool), cum[:, :-1] < top_p[:, None]], -1)
    min_prob = jnp.min(jnp.where(keep, srt, jnp.inf), -1, keepdims=True)
    min_prob = jnp.where(top_p[:, None] >= 1.0, 0.0, min_prob)  # no filter
    x = jnp.where(probs < min_prob, -jnp.inf, x)
    sampled = jax.random.categorical(key, x, axis=-1)
    # temperature ~ 0 means greedy, not a 1e6x logit blow-up (ADVICE r4:
    # the division guard alone overflowed f32 to inf and degraded
    # jax.random.categorical)
    return jnp.where(do_sample & (temperature > 1e-6), sampled, greedy)


def top_p_sampling(x, ps, threshold=None, seed=None):
    """paddle.tensor.top_p_sampling parity (ops.yaml `top_p_sampling`):
    nucleus-sample one token per row of probabilities ``x`` [B, V] with
    per-row cutoffs ``ps`` [B]. Returns (scores, ids)."""
    key = (jax.random.key(seed) if seed is not None and seed >= 0
           else _random.next_key())

    def fn(probs, p):
        logits = jnp.log(jnp.maximum(probs, 1e-38))
        srt = jnp.sort(probs, axis=-1)[..., ::-1]
        cum = jnp.cumsum(srt, axis=-1)
        keep = jnp.concatenate(
            [jnp.ones_like(cum[..., :1], bool),
             cum[..., :-1] < p[..., None]], -1)
        min_prob = jnp.min(jnp.where(keep, srt, jnp.inf), -1, keepdims=True)
        filtered = jnp.where(probs < min_prob, -jnp.inf, logits)
        ids = jax.random.categorical(key, filtered, axis=-1)
        score = jnp.take_along_axis(probs, ids[..., None], -1)[..., 0]
        return score, ids

    return apply("top_p_sampling", fn, x, ps, differentiable=False)


@functools.partial(jax.jit, static_argnames=("do_sample", "temperature",
                                             "top_k", "top_p"))
def _select(logits_last, key, do_sample, temperature, top_k, top_p):
    return sample_logits(logits_last, key, do_sample=do_sample,
                         temperature=temperature, top_k=top_k, top_p=top_p)


@functools.partial(jax.jit, static_argnames=("do_sample", "temperature",
                                             "top_k", "top_p", "rp",
                                             "block_eos", "eos_id"))
def _select_penalized(logits_last, seen, key, do_sample, temperature, top_k,
                      top_p, rp, block_eos, eos_id):
    """_select with HF-semantics repetition penalty (positive logits of
    seen tokens divided by rp, negative multiplied) and an optional eos
    block (min_new_tokens phase)."""
    lg = logits_last.astype(jnp.float32)
    if rp != 1.0:
        pen = jnp.where(lg > 0, lg / rp, lg * rp)
        lg = jnp.where(seen, pen, lg)
    if block_eos:
        lg = lg.at[:, eos_id].set(-jnp.inf)
    return sample_logits(lg, key, do_sample=do_sample,
                         temperature=temperature, top_k=top_k, top_p=top_p)


class _NgramBan:
    """Incremental HF NoRepeatNGramLogitsProcessor: per row, a hash of
    (n-1)-gram prefix -> set of banned completions, updated O(1) per
    appended token. (ADVICE r4: the previous implementation rescanned the
    whole history every decode step — O(len^2) host work per token that
    serialized the loop.)"""

    def __init__(self, histories, n: int):
        self.n = n
        self.hist = [list(h) for h in histories]
        self.maps = [{} for _ in self.hist]
        for b, h in enumerate(self.hist):
            for j in range(len(h) - n + 1):
                self.maps[b].setdefault(tuple(h[j:j + n - 1]),
                                        set()).add(h[j + n - 1])

    def append(self, b: int, tok: int):
        h = self.hist[b]
        h.append(tok)
        if len(h) >= self.n:
            self.maps[b].setdefault(tuple(h[-self.n:-1]), set()).add(h[-1])

    def banned(self, vocab: int):
        """[B, V] mask of tokens that would complete an already-seen
        n-gram of each row's current suffix."""
        out = np.zeros((len(self.hist), vocab), bool)
        for b, h in enumerate(self.hist):
            if len(h) < self.n - 1 and self.n > 1:
                continue
            prefix = tuple(h[-(self.n - 1):]) if self.n > 1 else ()
            for t in self.maps[b].get(prefix, ()):
                out[b, t] = True
        return out


def _ngram_banned(histories, n, vocab):
    """[B, V] mask (one-shot form; the decode loops keep a _NgramBan)."""
    return _NgramBan(histories, n).banned(vocab)


def _select_next(last, seen, key, do_sample, temperature, top_k, top_p,
                 rp, i, min_new, eos_token_id):
    """One-call next-token selection: routes to the plain _select program
    whenever no penalty applies at step ``i`` (rp == 1 and the eos-block
    phase is over) — the marshalling shared by the cached and no-cache
    decode loops."""
    if rp == 1.0 and i >= min_new:
        return _select(last, key, do_sample, float(temperature), int(top_k),
                       float(top_p))
    return _select_penalized(
        last, seen if seen is not None else jnp.zeros((last.shape[0], 1), bool),
        key, do_sample, float(temperature), int(top_k), float(top_p), rp,
        i < min_new, int(eos_token_id) if eos_token_id is not None else -1)


def _seen_from_prompt(ids, vocab, pad_mask=None):
    """[B, V] flag of tokens present in each row's prompt (pad columns
    excluded) — the repetition-penalty working set."""
    B, S0 = ids.shape
    seen = jnp.zeros((B, vocab), bool)
    safe = ids.astype(jnp.int32)
    if pad_mask is not None:
        upd = pad_mask[:, :S0]
    else:
        upd = jnp.ones((B, S0), bool)
    return seen.at[jnp.arange(B)[:, None], safe].max(upd)


# ---------------------------------------------------------------------------
# decode step machinery
# ---------------------------------------------------------------------------

def _empty_caches(model, batch, max_len, allowed=None, row_pos=None,
                  row_lengths=None):
    from .models.llama import head_dim_of

    cfg = model.config
    hk = cfg.num_key_value_heads
    d = head_dim_of(cfg)
    dt = jnp.dtype(cfg.dtype) if isinstance(cfg.dtype, str) else cfg.dtype
    # models with a non-k/v cache layout (MLA's compressed latent) provide
    # their own per-layer buffer allocator
    make = getattr(model.llama, "empty_cache_layer", None)
    # models with recurrent layers (linear attention) give those layers a
    # fresh per-row state instead of K/V
    state_of = getattr(model.llama, "empty_state_cache", None)
    caches = []
    for layer in range(cfg.num_hidden_layers):
        # pos starts as a PYTHON int so it stays a concrete constant even
        # when the prefill traces under jit — the flash fast path's
        # `int(pos) == 0` guard (cached_attention) must see through the
        # trace; decode steps then carry it as a traced scalar
        # "prefill": static marker consumed by the first forward (the
        # attention layer's `new` dict drops it), enabling the flash fast
        # path under jit; pos stays a python 0 so the first cache write
        # compiles as a static-offset slice
        state = None if state_of is None else state_of(layer, batch, dt)
        if state is not None:
            c = dict(state, prefill=True)
        elif make is not None:
            c = dict(make(batch, max_len, dt), pos=0, prefill=True)
        else:
            c = {"k": jnp.zeros((batch, max_len, hk, d), dt),
                 "v": jnp.zeros((batch, max_len, hk, d), dt),
                 "pos": 0, "prefill": True}
        if allowed is not None:
            c["allowed"] = allowed
        if row_pos is not None:
            c["row_pos"] = row_pos
        if row_lengths is not None:
            # a right-padded prefill's real rows: an expert layer routes
            # no pad (models/llama_moe.valid_rows); attention ignores it
            c["row_lengths"] = row_lengths
        caches.append(c)
    return caches


def _unwrap_caches(caches):
    return jax.tree_util.tree_map(
        lambda x: x._array if isinstance(x, Tensor) else x, caches,
        is_leaf=lambda x: isinstance(x, Tensor))


_BUF_KEYS = ("k", "v", "k_pages", "v_pages", "c_kv", "k_pe", "state", "conv")


def _split_caches(caches):
    """Separate the big per-layer KV buffers (donatable — each layer owns
    its own) from the small shared aux values (page tables / masks /
    positions shared across layers must NOT be donated twice)."""
    bufs = [{k: c[k] for k in _BUF_KEYS if k in c} for c in caches]
    aux = [{k: v for k, v in c.items() if k not in _BUF_KEYS}
           for c in caches]
    return bufs, aux


def _cached_forward(model, max_len, state, token, bufs, aux):
    """The shared pure decode body: merge cache halves, run the cached
    forward under functional weights, split the updated caches back.
    Returns (logits, new_bufs, new_aux)."""
    caches = [{**b, **a} for b, a in zip(bufs, aux)]
    with _functional_weights(model, state), _tape.no_grad():
        hidden, new_caches = model.llama.forward_cached(
            wrap(token), caches, rope_len=max_len)
        logits = model.lm_head_logits(hidden)
    nb, na = _split_caches(_unwrap_caches(new_caches))
    return unwrap(logits), nb, na


class _DecodeStep:
    """ONE jitted computation per generated token: embed → all layers with
    in-place (donated) cache buffers → lm-head logits. The TrainStep
    pattern applied to decode (jit/__init__.py TrainStep)."""

    def __init__(self, model, max_len):
        self._model = model

        def decode_step_solo(state, token, bufs, aux):
            return _cached_forward(model, max_len, state, token, bufs, aux)

        self._jitted = jax.jit(decode_step_solo, donate_argnums=(2,))
        self._state = dict(model.functional_state())

    def __call__(self, token, caches):
        bufs, aux = _split_caches(caches)
        logits, nb, na = self._jitted(self._state, token, bufs, aux)
        return logits, [{**b, **a} for b, a in zip(nb, na)]


def _rows_match(a, n):
    """True for array leaves whose leading axis is the batch/beam rows —
    the one predicate shared by beam tiling and beam-reorder gathers."""
    return hasattr(a, "ndim") and a.ndim >= 1 and a.shape[0] == n


class _EncDecBeamStep:
    """Jitted enc-dec beam unit shared by T5/BART: gather the SELF-cache
    rows each surviving beam came from (cross caches are identical across
    a batch's K beams after tiling, so they stay untouched), run one
    cached decoder step, return next-position log-probs. ``decode`` is the
    family's cached decoder call:
    ``decode(model, token, self_caches, cross_caches) ->
    (hidden, new_self, _)``."""

    def __init__(self, model, decode):
        from .autograd import tape as _tape
        from .nn.layer import functional_weights

        def seq2seq_beam_step(state, token, row_idx, self_caches,
                              cross_caches):
            n = row_idx.shape[0]
            take = lambda a: (jnp.take(a, row_idx, axis=0)
                              if _rows_match(a, n) else a)
            self_caches = jax.tree.map(take, self_caches)
            with functional_weights(model, state), _tape.no_grad():
                hidden, new_self, _ = decode(model, wrap(token),
                                             self_caches, cross_caches)
                logits = model.lm_head_logits(hidden)
            logp = jax.nn.log_softmax(
                unwrap(logits)[:, -1, :].astype(jnp.float32), axis=-1)
            return logp, [
                {k: (unwrap(v) if isinstance(v, Tensor) else v)
                 for k, v in c.items()} for c in new_self]

        self._jitted = jax.jit(seq2seq_beam_step, donate_argnums=(3,))
        self._state = dict(model.functional_state())

    def __call__(self, token, row_idx, self_caches, cross_caches):
        return self._jitted(self._state, token, row_idx, self_caches,
                            cross_caches)


def reject_sampled_beams(family: str, num_beams: int, do_sample: bool):
    """The enc-dec families' shared guard: beam search composes with
    greedy scoring only (raised BEFORE any encoder compute, so an
    argument error is free)."""
    if num_beams > 1 and do_sample:
        raise NotImplementedError(
            f"{family}.generate: beam search composes with greedy "
            "scoring only (do_sample=False)")


def encdec_beam_generate(model, decode, step0, token0, self_c, cross_c,
                         max_new_tokens, num_beams, eos_token_id,
                         length_penalty, early_stopping, cache_attr):
    """Beam search over a cached enc-dec decoder (T5/BART ``num_beams``):
    one plain cached step on the B rows scores the first position, caches
    tile to B*K rows, and the jitted _EncDecBeamStep reorders self caches
    by beam origin each subsequent step. Returns the padded [B, width]
    token Tensor (HF generate semantics, like the decoder-only path)."""
    import numpy as np

    B, K = token0.shape[0], num_beams
    logits, self_c = step0(token0, self_c, cross_c)
    logp0 = np.asarray(jax.nn.log_softmax(
        logits[:, -1, :].astype(jnp.float32), axis=-1))
    tile = lambda t: jax.tree.map(
        lambda a: jnp.repeat(a, K, axis=0) if _rows_match(a, B) else a, t)
    self_c, cross_c = tile(self_c), tile(cross_c)
    bstep = _memoized_step(model, cache_attr, (),
                           lambda: _EncDecBeamStep(model, decode))
    holder = {"self": self_c}

    def step(token, row_idx):
        logp, holder["self"] = bstep(token.astype(jnp.int32),
                                     jnp.asarray(row_idx), holder["self"],
                                     cross_c)
        # beam scoring runs on host by design: ONE fetch per beam step
        return np.asarray(logp)  # pdlint: disable=host-sync

    arr = beam_search_loop(logp0, step, max_new_tokens, K, eos_token_id,
                           length_penalty, early_stopping)
    return wrap(jnp.asarray(arr))


class _BeamStep:
    """Beam-search decode unit, ONE jitted dispatch per step: gather the
    cache rows each surviving beam came from (beam reordering), run the
    cached forward on the chosen tokens, return next log-probs."""

    def __init__(self, model, max_len):
        self._model = model

        def beam_step(state, token, row_idx, bufs, aux):
            take = lambda a: (jnp.take(a, row_idx, axis=0)
                              if _rows_match(a, row_idx.shape[0]) else a)
            bufs = jax.tree.map(take, bufs)
            aux = jax.tree.map(take, aux)
            logits, nb, na = _cached_forward(model, max_len, state, token,
                                             bufs, aux)
            logp = jax.nn.log_softmax(
                logits[:, -1, :].astype(jnp.float32), axis=-1)
            return logp, nb, na

        self._jitted = jax.jit(beam_step, donate_argnums=(3,))
        self._state = dict(model.functional_state())

    def __call__(self, token, row_idx, caches):
        bufs, aux = _split_caches(caches)
        logp, nb, na = self._jitted(self._state, token, row_idx, bufs, aux)
        return logp, [{**b, **a} for b, a in zip(nb, na)]


def _get_beam_step(model, max_len):
    return _memoized_step(model, "_beam_steps", (max_len,),
                          lambda: _BeamStep(model, max_len))


class _BeamHyps:
    """Per-batch pool of finished hypotheses (HF BeamHypotheses semantics:
    scores are sum-logprob / len**length_penalty over GENERATED tokens)."""

    def __init__(self, k, length_penalty, early_stopping):
        self.k, self.lp, self.early = k, length_penalty, early_stopping
        self.items = []  # (score, tokens list)

    def add(self, sum_logprob, tokens):
        score = sum_logprob / (max(len(tokens), 1) ** self.lp)
        self.items.append((score, tokens))
        self.items.sort(key=lambda t: -t[0])
        del self.items[self.k:]

    def is_done(self, best_running_sum, cur_len):
        if len(self.items) < self.k:
            return False
        if self.early:
            return True
        return (best_running_sum / (max(cur_len, 1) ** self.lp)
                <= self.items[-1][0])


def _beam_search(model, last, caches, max_len, max_new_tokens,
                 num_beams, eos_token_id, length_penalty, early_stopping,
                 rp=1.0, histories0=None, min_new=0, ngram=0):
    """Host-scored beam search over the cached decode path (the LLM analog
    of nn.BeamSearchDecoder/dynamic_decode; HF generate num_beams
    semantics). ``last``/``caches`` arrive from the B-row prefill; beams
    live as B*K cache rows, reordered inside the jitted _BeamStep."""
    import numpy as np

    B = last.shape[0]
    caches = jax.tree.map(
        lambda a: jnp.repeat(a, num_beams, axis=0) if _rows_match(a, B)
        else a, caches)
    step_fn = _get_beam_step(model, max_len)
    holder = {"caches": caches}

    def step(token, row_idx):
        logp, holder["caches"] = step_fn(token, jnp.asarray(row_idx),
                                         holder["caches"])
        # beam scoring runs on host by design: ONE fetch per beam step
        return np.asarray(logp)  # pdlint: disable=host-sync

    logp0 = np.asarray(jax.nn.log_softmax(last.astype(jnp.float32), axis=-1))
    arr = beam_search_loop(logp0, step, max_new_tokens, num_beams,
                           eos_token_id, length_penalty, early_stopping,
                           rp=rp, histories0=histories0, min_new=min_new,
                           ngram=ngram)
    return wrap(jnp.asarray(arr))


def beam_search_loop(logp0, step, max_new_tokens, num_beams, eos_token_id,
                     length_penalty, early_stopping, rp=1.0, histories0=None,
                     min_new=0, ngram=0):
    """The host scoring loop of beam search, decoupled from the model: a
    caller supplies ``logp0`` (np [B, V] log-probs of the first position)
    and ``step(token [B*K, 1] jnp, row_idx [B*K] np) -> np [B*K, V]``
    log-probs of the next position, with beam-origin cache reordering the
    step's own responsibility. Serves the decoder-only path and the
    encoder-decoder families (T5/BART num_beams). Returns np [B, width]."""
    import numpy as np

    B, V = logp0.shape
    K = num_beams
    logp0 = np.repeat(logp0, K, axis=0).reshape(B, K, V)
    # beam 0 seeds the search; the copies start at -inf so step 1's top-k
    # cannot pick the same token K times
    cum = np.full((B, K), -np.inf, np.float64)
    cum[:, 0] = 0.0
    hyps = [_BeamHyps(K, length_penalty, early_stopping) for _ in range(B)]
    done = [False] * B
    beams_tokens = [[[] for _ in range(K)] for _ in range(B)]
    logp = logp0

    # prompt n-gram maps built ONCE per batch row: per-step beam work then
    # hashes only the short generated tail (+ the boundary n-grams via the
    # prompt's last n-1 tokens), not the whole prompt again — the greedy
    # path's _NgramBan amortization, adapted to beam reordering
    base_maps = ([_NgramBan([h], ngram) for h in histories0]
                 if (ngram and histories0 is not None) else None)
    prompt_sets = ([set(h) for h in histories0]
                   if (rp != 1.0 and histories0 is not None) else None)

    def _process(scores, step_i):
        """HF beam-search processor order on the [B, K, V] scores."""
        eos_active = bool(min_new and eos_token_id is not None
                          and step_i < min_new)
        if (histories0 is None and not eos_active) or all(done):
            return scores
        out = np.array(scores, np.float64)
        for b in range(B):
            if done[b] or histories0 is None:
                continue
            prompt = histories0[b]
            tail = prompt[-(ngram - 1):] if ngram > 1 else []
            for j in range(K):
                gen = beams_tokens[b][j]
                row = out[b, j]
                if rp != 1.0 and (prompt or gen):
                    idx = np.fromiter(prompt_sets[b] | set(gen), np.int64)
                    vals = row[idx]
                    row[idx] = np.where(vals < 0, vals * rp, vals / rp)
                if ngram:
                    hist = prompt + gen
                    prefix = (tuple(hist[-(ngram - 1):]) if ngram > 1
                              else ())
                    banned = set(base_maps[b].maps[0].get(prefix, ()))
                    banned |= _NgramBan([tail + gen], ngram).maps[0].get(
                        prefix, set())
                    if banned:
                        row[list(banned)] = -np.inf
        if eos_active:
            out[:, :, eos_token_id] = -np.inf
        return out

    for i in range(max_new_tokens):
        logp_p = _process(logp, i)
        total = cum[:, :, None] + logp_p        # [B, K, V] float64 scores
        flat = total.reshape(B, K * V)
        # 2K candidates per batch (eos hits may retire, HF convention);
        # O(KV) partial select, then sort only the survivors
        part = np.argpartition(-flat, 2 * K - 1, axis=1)[:, : 2 * K]
        order = np.argsort(-np.take_along_axis(flat, part, axis=1), axis=1)
        top = np.take_along_axis(part, order, axis=1)
        next_tokens = []
        next_origin = []
        next_cum = []
        for b in range(B):
            if done[b]:
                next_tokens.append([0] * K)
                next_origin.append([b * K] * K)
                next_cum.append([-np.inf] * K)
                continue
            toks, orig, cums = [], [], []
            for rank, cand in enumerate(top[b]):
                beam, tok = divmod(int(cand), V)
                score = flat[b, cand]
                if eos_token_id is not None and tok == eos_token_id:
                    if rank < K:  # only top-K eos candidates retire
                        hyps[b].add(score, beams_tokens[b][beam] + [tok])
                    continue
                toks.append(tok)
                orig.append(b * K + beam)
                cums.append(score)
                if len(toks) == K:
                    break
            next_tokens.append(toks)
            next_origin.append(orig)
            next_cum.append(cums)
            beams_tokens[b] = [beams_tokens[b][orig[j] - b * K] +
                               [toks[j]] for j in range(K)]
            # HF passes the max over ALL 2K candidates (eos hits included)
            # as the best running sum — not just the kept non-eos beams
            if hyps[b].is_done(float(flat[b, top[b][0]]), i + 1):
                done[b] = True
        if all(done) or i == max_new_tokens - 1:
            for b in range(B):
                if not done[b] or not hyps[b].items:
                    # flush running beams at the length limit
                    for j in range(K):
                        if np.isfinite(next_cum[b][j]):
                            hyps[b].add(next_cum[b][j], beams_tokens[b][j])
            break
        cum = np.asarray(next_cum, np.float64)
        row_idx = np.asarray(next_origin, np.int32).reshape(-1)
        token = jnp.asarray(np.asarray(next_tokens, np.int64).reshape(-1, 1))
        logp = step(token, row_idx).reshape(B, K, V)

    outs = []
    for b in range(B):
        if hyps[b].items:
            outs.append(hyps[b].items[0][1])
        else:  # no finished hypothesis: best running beam
            outs.append(beams_tokens[b][int(np.argmax(cum[b]))])
    width = max(1, max(len(o) for o in outs))
    fill = eos_token_id if eos_token_id is not None else 0
    arr = np.full((B, width), fill, np.int64)
    for b, o in enumerate(outs):
        arr[b, : len(o)] = o
    return arr


class _PrefillStep:
    """ONE jitted computation for the whole prefill: empty caches → all
    layers (flash kernel over the prompt — cache `pos` is a concrete 0
    inside the trace, so the fast path survives jit) → each row's last real
    logit. Eager prefill costs one device dispatch per op per layer; this is
    the serving path's second half of the TrainStep pattern.

    ``ragged`` stamps ``pad_mask`` on the fresh caches as ``allowed``, which
    takes the attention off the flash kernel (cached_attention). Only pads
    that a real token could attend need it: generate()'s batches (left
    padding; the mask also serves the decode steps that follow). The
    serving engine pads ONE prompt on the RIGHT and attends causally from
    position 0, so no real token sees a pad: it asks for ``ragged=False``
    at every prompt length and passes ``lengths`` for the last-logit gather
    alone. The pad rows' K/V and outputs are garbage nobody reads.

    ``attention_impl``: which implementation the attention site took when
    this step was last traced (``traced_attention_impl``)."""

    moe_counts = None

    def __init__(self, model, max_len, ragged, rope_len=None,
                 embeds_input=False):
        # rope_len decouples the cos/sin table length from the cache
        # length: the serving engine prefills into a BUCKET-sized cache but
        # provisions rope at its max_len, so length-keyed rope regimes
        # (Phi-3 longrope short/long factors) match its decode program.
        # embeds_input: the first call argument is pre-merged embeddings
        # (multimodal admission) instead of token ids.
        rope_len = max_len if rope_len is None else rope_len
        self._model = model

        def prefill(state, ids_or_embeds, lengths, pad_mask):
            with _functional_weights(model, state), _tape.no_grad():
                B = ids_or_embeds.shape[0]
                caches = _empty_caches(
                    model, B, max_len,
                    allowed=pad_mask if ragged else None,
                    row_lengths=None if ragged else lengths)
                if embeds_input:
                    hidden, caches = model.llama.forward_cached(
                        None, caches, rope_len=rope_len,
                        inputs_embeds=wrap(ids_or_embeds))
                else:
                    hidden, caches = model.llama.forward_cached(
                        wrap(ids_or_embeds), caches, rope_len=rope_len)
                h_last = jnp.take_along_axis(
                    unwrap(hidden),
                    (lengths - 1)[:, None, None].astype(jnp.int32), axis=1)
                last = unwrap(model.lm_head_logits(wrap(h_last)))[:, 0, :]
            caches, counts = pop_moe_counts(_unwrap_caches(caches))
            return last, caches, counts

        # the program's name says which variant ran: prefill,
        # prefill_ragged, prefill_embeds, prefill_embeds_ragged
        prefill.__name__ = ("prefill" + ("_embeds" if embeds_input else "")
                            + ("_ragged" if ragged else ""))
        self._jitted = jax.jit(prefill)
        self._state = dict(model.functional_state())

    def __call__(self, ids, lengths, pad_mask=None):
        # moe_counts: what this call's expert layers counted (on the
        # device; None for a model without one), for the engine's counters
        last, caches, self.moe_counts = traced_attention_impl(
            self._jitted, self._state, ids, lengths, pad_mask)
        return last, caches

    @property
    def attention_impl(self):
        return self._jitted.attention_impl


def traced_attention_impl(fn, *args):
    """``fn(*args)`` for a jitted prefill program; when the call traced it
    (the Pallas gates speak at trace time only; a first call always
    traces), ``fn.attention_impl`` keeps what the prefill attention took:
    ``flash`` (splash over the new tokens), ``append`` (the streaming
    kernel over the buffer) or ``xla`` (the f32 composite, also where no
    gate was asked). The engine counts admissions by it."""
    with _pallas_backend.recording() as taken:
        out = fn(*args)
    if taken or getattr(fn, "attention_impl", None) is None:
        kernels = {site for site, impl in taken
                   if impl != _pallas_backend.XLA}
        fn.attention_impl = ("flash" if "flash_attention" in kernels
                             else "append" if "append_attention" in kernels
                             else "xla")
    return out


def _trace_flags_key() -> tuple:
    """The trace-relevant flag values as seen by THIS thread (including
    any thread-local overlay). Folded into every step-memoization key:
    flags are read at trace time, so a cached executable is only valid
    for the flag values it was traced under — a flag flip (or an audit
    thread's flag_overrides) must get its own program, not silently
    reuse one traced the other way."""
    from .utils.flags import flag

    return (bool(flag("FLAGS_use_fused_decode_tail")),)


def _memoized_step(model, attr, key, factory, maxsize=None):
    """Per-model step memoization: jax.jit's compile cache keys on the
    function object, so a fresh step per generate() call would recompile
    every request (review finding). On a hit, the step re-reads the model's
    CURRENT weights. ``maxsize`` evicts the LEAST-RECENTLY-USED entry for
    caches whose key space is unbounded (per-request lengths): a hit
    re-inserts its key at the back, so a working set that cycles through
    many keys per request (the chunked-prefill suffix programs) keeps its
    hot programs instead of evicting in insertion order.

    Keys are extended with the trace-relevant flag fingerprint
    (:func:`_trace_flags_key`) so programs traced under different flag
    values never alias."""
    key = (key, _trace_flags_key())
    cache = model.__dict__.get(attr)
    if cache is None:
        cache = {}
        object.__setattr__(model, attr, cache)
    step = cache.get(key)
    if step is None:
        step = factory()
        if maxsize is not None and len(cache) >= maxsize:
            cache.pop(next(iter(cache)))
        cache[key] = step
    else:
        if maxsize is not None:
            cache.pop(key)
            cache[key] = step
        step._state = dict(model.functional_state())
    return step


def _get_prefill_step_embeds(model, max_len, ragged, rope_len=None):
    """Multimodal prefill: same jitted computation as _get_prefill_step,
    but the first argument is PRE-MERGED embeddings (LLaVA image features
    already scattered into the prompt) instead of token ids."""
    return _memoized_step(model, "_prefill_steps_embeds",
                          (max_len, ragged, rope_len),
                          lambda: _PrefillStep(model, max_len, ragged,
                                               rope_len=rope_len,
                                               embeds_input=True),
                          maxsize=16)


def _get_prefill_step(model, max_len, ragged, rope_len=None):
    # max_len varies per request: bound the cache (oldest-evicted)
    return _memoized_step(model, "_prefill_steps",
                          (max_len, ragged, rope_len),
                          lambda: _PrefillStep(model, max_len, ragged,
                                               rope_len=rope_len),
                          maxsize=16)


class _ChunkedPrefillStep:
    """Prefill as ONE jitted ``lax.scan`` over fixed-size prompt chunks
    (vLLM-style chunked prefill, TPU-shaped): compile cost scales with the
    CHUNK COUNT bucket instead of one compile per prompt-shape, and the
    per-layer MLP/projection activations are one chunk's worth. Chunk c
    writes cache entries [cC, cC+C) and attends to every earlier entry
    through the cache's pos/column masking, so the result is exactly the
    one-shot prefill. The running last-real-hidden is carried so only a
    [B, H] gather (not the full prompt's hidden) leaves the loop.

    Cost model: on TPU each chunk's attention runs the Pallas
    append-attention kernel (ops/pallas/append_attention.py — streaming
    softmax over the buffer, traced ``pos`` via scalar prefetch, KV
    blocks beyond pos+S skipped), so compute scales with the VALID
    prefix: total O(S^2/2) like a causal kernel. Where the kernel's gate
    declines (CPU, untileable dims, KV beyond its VMEM budget), the
    dense fallback materializes f32 scores [B, kv_heads, group, C,
    max_len] per layer and attends the whole buffer — pick C so
    C x max_len stays modest there."""

    def __init__(self, model, max_len, chunk, n_chunks):
        self._model = model
        C, n = int(chunk), int(n_chunks)

        def prefill_scan(state, ids_pad, lengths, allowed):
            B = ids_pad.shape[0]
            with _functional_weights(model, state), _tape.no_grad():
                caches = _empty_caches(model, B, max_len, allowed=allowed)
                for c in caches:
                    # scan-stable carry: pos as a traced scalar, no static
                    # "prefill" marker (its dict entry would be dropped by
                    # the first step and change the carry structure)
                    c.pop("prefill", None)
                    c["pos"] = jnp.asarray(0, jnp.int32)
                bufs, aux = _split_caches(caches)
                chunks = ids_pad.reshape(B, n, C).transpose(1, 0, 2)

                def body(carry, chunk_ids):
                    bufs, aux, h_last, start = carry
                    cs = [{**b, **a} for b, a in zip(bufs, aux)]
                    hidden, cs = model.llama.forward_cached(
                        wrap(chunk_ids), cs, rope_len=max_len)
                    h = unwrap(hidden)
                    idx = lengths.astype(jnp.int32) - 1 - start
                    in_chunk = (idx >= 0) & (idx < C)
                    picked = jnp.take_along_axis(
                        h, jnp.clip(idx, 0, C - 1)[:, None, None], axis=1
                    )[:, 0]
                    h_last = jnp.where(in_chunk[:, None], picked, h_last)
                    nb, na = _split_caches(_unwrap_caches(cs))
                    return (nb, na, h_last, start + C), None

                h0 = jnp.zeros((B, model.config.hidden_size),
                               jnp.dtype(model.config.dtype)
                               if isinstance(model.config.dtype, str)
                               else model.config.dtype)
                (bufs, aux, h_last, _), _ = jax.lax.scan(
                    body, (bufs, aux, h0, jnp.asarray(0, jnp.int32)), chunks)
                last = unwrap(model.lm_head_logits(
                    wrap(h_last[:, None, :])))[:, 0, :]
            return last, bufs, aux

        self._jitted = jax.jit(prefill_scan)
        self._state = dict(model.functional_state())

    def __call__(self, ids_pad, lengths, allowed):
        last, bufs, aux = self._jitted(self._state, ids_pad, lengths, allowed)
        return last, [{**b, **a} for b, a in zip(bufs, aux)]


def _get_chunked_prefill_step(model, max_len, chunk, n_chunks):
    return _memoized_step(
        model, "_chunked_prefill_steps", (max_len, chunk, n_chunks),
        lambda: _ChunkedPrefillStep(model, max_len, chunk, n_chunks),
        maxsize=8)


def _sample_and_forward(model, max_len, last, key, bufs, aux,
                        do_sample, temperature, top_k, top_p, sampler=None):
    """The fused per-token unit shared by the scan decode and the engine
    step: sample from ``last``, run one cached forward, return
    (token, chosen-token logprob, next logits, split caches). The logprob
    is under the model's RAW distribution over ``last`` (the OpenAI
    "logprobs" field — one fused log_softmax gather while the logits are
    in hand). Caller provides the weight context (functional_weights) and
    the RNG key; ``sampler`` overrides the scalar sample_logits call (the
    per-row engine path)."""
    if sampler is not None:
        nxt = sampler(last, key)
    else:
        nxt = sample_logits(last, key, do_sample=do_sample,
                            temperature=temperature, top_k=top_k, top_p=top_p)
    lp = jax.nn.log_softmax(last.astype(jnp.float32), -1)[
        jnp.arange(last.shape[0]), nxt]
    token = nxt[:, None].astype(jnp.int32)
    caches = [{**b, **a} for b, a in zip(bufs, aux)]
    with _tape.no_grad():
        hidden, new_caches = model.llama.forward_cached(
            wrap(token), caches, rope_len=max_len)
        logits = model.lm_head_logits(hidden)
    nb, na = _split_caches(_unwrap_caches(new_caches))
    return nxt, lp, unwrap(logits)[:, -1, :], nb, na


class _ScanDecodeStep:
    """The WHOLE decode loop as one jitted ``lax.scan``: each step samples
    the next token from the carried logits, runs one cached forward, and
    carries the updated (donated) KV buffers. One device dispatch for the
    entire generation instead of two per token — the python loop remains
    only for eos early-stopping (data-dependent length needs host control).
    """

    def __init__(self, model, max_len, steps, do_sample, temperature,
                 top_k, top_p):
        self._model = model

        def decode_scan(state, last, base_key, bufs, aux):
            with _functional_weights(model, state):
                def body(carry, t):
                    last_t, bufs_t, aux_t = carry
                    key = jax.random.fold_in(base_key, t)
                    nxt, _lp, last_n, nb, na = _sample_and_forward(
                        model, max_len, last_t, key, bufs_t, aux_t,
                        do_sample, temperature, top_k, top_p)
                    return (last_n, nb, na), nxt

                (last_f, bufs_f, aux_f), toks = jax.lax.scan(
                    body, (last, bufs, aux), jnp.arange(steps))
            return toks, last_f, bufs_f, aux_f

        self._jitted = jax.jit(decode_scan, donate_argnums=(3,))
        self._state = dict(model.functional_state())

    def __call__(self, last, base_key, caches):
        bufs, aux = _split_caches(caches)
        # scan carries must be type-stable across iterations: normalize the
        # python-int pos (static after prefill; absent in paged caches,
        # which track per-row lengths instead) to a traced-compatible array
        aux = [dict(a, **({"pos": jnp.asarray(a["pos"], jnp.int32)}
                          if "pos" in a else {})) for a in aux]
        toks, last_f, nb, na = self._jitted(self._state, last, base_key,
                                            bufs, aux)
        return toks, last_f, [{**b, **a} for b, a in zip(nb, na)]


def _engine_token_step(model, max_len, last, key, bufs, aux, lengths,
                       advance, sample):
    """The body of the engine's one-token programs: ``_sample_and_forward``
    with the engine's per-slot ``lengths`` carried THROUGH the step, so
    the host has nothing to compute between two steps. ``advance`` is the
    per-slot code the engine uploads when slot membership changes: > 0 an
    active row (decodes at ``lengths[b]``, leaves ``lengths[b] + 1``),
    < 0 a slot held mid chunked-prefill (its throwaway K/V lands at
    ``lengths[b]``, where the next chunk's scatter overwrites it, and the
    length keeps its place), 0 a free row (reads and leaves 0: a row that
    retired at ``lengths[b] == max_len`` must not write past its pages).
    ``lengths is None`` (dense caches: the correctness sentinel's replay)
    carries nothing."""
    new_lengths = None
    if lengths is not None:
        lengths = jnp.where(advance != 0, lengths, 0)
        live = (advance > 0).astype(lengths.dtype)
        new_lengths = lengths + live
        # row_lengths: an expert layer routes the live rows only
        aux = [dict(a, lengths=lengths, row_lengths=live) for a in aux]
    nxt, lp, last_n, nb, na = _sample_and_forward(model, max_len, last, key,
                                                  bufs, aux, **sample)
    na, counts = pop_moe_counts(na)
    na, gdn = pop_counts(na, "gdn_updates")
    if lengths is not None:
        # ONE lengths output, not one per layer (the model hands back its
        # own lengths + 1 in every layer's aux)
        na = [{k: v for k, v in a.items()
               if k not in ("lengths", "row_lengths")} for a in na]
    return (nxt, lp, last_n.astype(jnp.float32), nb, na, new_lengths,
            counts, gdn)


def pop_counts(caches, key):
    """(the layers' caches without ``key``, the values the layers handed
    back under it summed over layers), or (caches, None) where no layer
    did: a count a program returns beside its tokens."""
    counts = [unwrap(c[key]) for c in caches
              if isinstance(c, dict) and key in c]
    if not counts:
        return caches, None
    return ([{k: v for k, v in c.items() if k != key}
             if isinstance(c, dict) else c for c in caches], sum(counts))


def pop_moe_counts(caches):
    """(the layers' caches without ``moe_counts``, those counts summed over
    layers): int32 [1 + held], the rows routed then the pairs per held
    expert, as the expert layers of the forward just traced handed them
    back in their caches (``models/llama_moe.with_moe_counts``); None for
    a model without one. An output of the same program as the tokens: the
    engine fetches both at once."""
    return pop_counts(caches, "moe_counts")


def _split_step_caches(caches, lengths):
    """``_split_caches`` for the engine's one-token programs: with the
    lengths passed on their own, the per-layer copies stay at home."""
    bufs, aux = _split_caches(caches)
    if lengths is not None:
        aux = [{k: v for k, v in a.items() if k != "lengths"} for a in aux]
    return bufs, aux


def _join_step_caches(nb, na, lengths):
    extra = {} if lengths is None else {"lengths": lengths}
    return [{**b, **a, **extra} for b, a in zip(nb, na)]


class _SelectDecodeStep:
    """sample + one cached forward fused into ONE jitted dispatch: the
    continuous-batching engine's per-step unit (the scan variant without
    the scan — the host must see each token for slot retirement). Called
    with the engine's ``lengths`` and ``advance`` code it also returns the
    advanced lengths (``_engine_token_step``): step N + 1's inputs are
    all outputs of step N that never leave the device. ``moe_counts``
    keeps the last call's expert-layer counts (a device array; None for a
    model without expert layers); ``gdn_updates`` the rows times linear
    layers whose recurrent state it advanced (None without such layers)."""

    moe_counts = gdn_updates = None

    def __init__(self, model, max_len, do_sample, temperature, top_k, top_p):
        self._model = model
        sample = dict(do_sample=do_sample, temperature=temperature,
                      top_k=top_k, top_p=top_p)

        def decode_step(state, last, key, bufs, aux, lengths, advance):
            with _functional_weights(model, state):
                return _engine_token_step(model, max_len, last, key, bufs,
                                          aux, lengths, advance, sample)

        self._jitted = jax.jit(decode_step, donate_argnums=(3,))
        self._state = dict(model.functional_state())

    def __call__(self, last, key, caches, lengths=None, advance=None):
        bufs, aux = _split_step_caches(caches, lengths)
        (nxt, lp, last_f, nb, na, lengths, self.moe_counts,
         self.gdn_updates) = self._jitted(self._state, last, key, bufs, aux,
                                          lengths, advance)
        return nxt, lp, last_f, _join_step_caches(nb, na, lengths), lengths


class _SelectDecodeRowsStep:
    """_SelectDecodeStep with PER-ROW sampling parameters as traced args:
    one compiled program serves any per-request greedy/temperature/top-k/
    top-p mix in the continuous-batching engine."""

    moe_counts = gdn_updates = None

    def __init__(self, model, max_len):
        self._model = model

        def decode_step_rows(state, last, key, do_s, temp, tk, tp, bufs,
                             aux, lengths, advance):
            sample = dict(
                do_sample=None, temperature=None, top_k=None, top_p=None,
                sampler=lambda lg, k: sample_logits_rows(lg, k, do_s, temp,
                                                         tk, tp))
            with _functional_weights(model, state):
                return _engine_token_step(model, max_len, last, key, bufs,
                                          aux, lengths, advance, sample)

        self._jitted = jax.jit(decode_step_rows, donate_argnums=(7,))
        self._state = dict(model.functional_state())

    def __call__(self, last, key, do_s, temp, tk, tp, caches, lengths=None,
                 advance=None):
        bufs, aux = _split_step_caches(caches, lengths)
        (nxt, lp, last_f, nb, na, lengths, self.moe_counts,
         self.gdn_updates) = self._jitted(self._state, last, key, do_s, temp,
                                          tk, tp, bufs, aux, lengths, advance)
        return nxt, lp, last_f, _join_step_caches(nb, na, lengths), lengths


class _SpecDecodeStep:
    """Greedy speculative decode unit for the continuous-batching engine,
    ONE jitted dispatch per round: argmax the carried logits (the token a
    plain step would emit), forward a k-token chunk [g0, d_1..d_{k-1}] of
    host-proposed draft tokens through the paged cache at per-row
    positions, and compute the longest target-greedy-consistent accepted
    run on device. Returns everything the engine's host loop needs in one
    fetch: the emitted-token matrix, per-row emit counts, per-token
    logprobs (raw distribution — the OpenAI logprobs field), and the
    logits row that seeds the next round.

    Token-identity is by construction: position 0 always forwards g0
    (the verified greedy token), and draft j is emitted only when it
    EQUALS the target's greedy choice at its position — junk drafts can
    only be accepted when they happen to match the true token, so
    acceptance changes latency, never output. Rejected-suffix KV parks
    above the post-accept frontier (see paged_cached_attention)."""

    def __init__(self, model, max_len, k):
        self._model = model
        k = int(k)

        def spec_verify(state, last, drafts, bufs, aux):
            B = last.shape[0]
            with _functional_weights(model, state), _tape.no_grad():
                g0 = jnp.argmax(last, axis=-1).astype(jnp.int32)   # [B]
                chunk = (jnp.concatenate([g0[:, None], drafts], axis=1)
                         if k > 1 else g0[:, None])                # [B,k]
                caches = [{**b, **a} for b, a in zip(bufs, aux)]
                hidden, new_caches = model.llama.forward_cached(
                    wrap(chunk), caches, rope_len=max_len)
                logits = unwrap(model.lm_head_logits(hidden)
                                ).astype(jnp.float32)              # [B,k,V]
            greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)  # [B,k]
            if k > 1:
                ok = (drafts == greedy[:, :-1]).astype(jnp.int32)  # [B,k-1]
                n_acc = jnp.cumprod(ok, axis=1).sum(axis=1)        # [B]
            else:
                n_acc = jnp.zeros((B,), jnp.int32)
            # logits after the LAST emitted token (chunk position n_acc)
            # seed the next round — the bonus token is next round's g0
            new_last = jnp.take_along_axis(
                logits, n_acc[:, None, None].astype(jnp.int32), axis=1
            )[:, 0]                                                 # [B,V]
            lp0 = jax.nn.log_softmax(last.astype(jnp.float32), -1)[
                jnp.arange(B), g0]                                  # [B]
            if k > 1:
                lpd = jnp.take_along_axis(
                    jax.nn.log_softmax(logits[:, :-1], -1),
                    drafts[:, :, None].astype(jnp.int32), axis=2
                )[:, :, 0]                                          # [B,k-1]
                lps = jnp.concatenate([lp0[:, None], lpd], axis=1)
            else:
                lps = lp0[:, None]
            nb, na = _split_caches(_unwrap_caches(new_caches))
            return chunk, n_acc + 1, lps, new_last, nb, na

        self._jitted = jax.jit(spec_verify, donate_argnums=(3,))
        self._state = dict(model.functional_state())

    def __call__(self, last, drafts, caches):
        bufs, aux = _split_caches(caches)
        toks, n_emit, lps, last_f, nb, na = self._jitted(
            self._state, last, drafts, bufs, aux)
        return toks, n_emit, lps, last_f, [{**b, **a}
                                           for b, a in zip(nb, na)]


def _get_spec_decode(model, max_len, k):
    return _memoized_step(
        model, "_spec_decode_steps", (max_len, int(k)),
        lambda: _SpecDecodeStep(model, max_len, k), maxsize=8)


def _get_select_decode_rows(model, max_len):
    return _memoized_step(
        model, "_select_decode_rows_steps", (max_len,),
        lambda: _SelectDecodeRowsStep(model, max_len))


def _get_select_decode(model, max_len, do_sample, temperature, top_k, top_p):
    key = (max_len, do_sample, float(temperature), int(top_k), float(top_p))
    return _memoized_step(
        model, "_select_decode_steps", key,
        lambda: _SelectDecodeStep(model, max_len, do_sample,
                                  float(temperature), int(top_k),
                                  float(top_p)))


def _get_scan_decode(model, max_len, steps, do_sample, temperature, top_k,
                     top_p):
    # NOTE: keyed on the request's exact step count — a serving mix of many
    # distinct max_new_tokens values compiles one scan program each (the
    # fixed-length-batch assumption of this fast path). The cache is
    # LRU-bounded so varied lengths cannot accumulate executables forever.
    key = (max_len, steps, do_sample, float(temperature), int(top_k),
           float(top_p))
    return _memoized_step(
        model, "_scan_decode_steps", key,
        lambda: _ScanDecodeStep(model, max_len, steps, do_sample,
                                float(temperature), int(top_k),
                                float(top_p)),
        maxsize=16)


def _get_decode_step(model, max_len):
    return _memoized_step(model, "_decode_steps", max_len,
                          lambda: _DecodeStep(model, max_len))


# ---------------------------------------------------------------------------
# generate
# ---------------------------------------------------------------------------

#: defaults of the decoder-only generate() below — encoder-decoder
#: families (T5/BART) accept these kwargs when passed AT their default
#: (callers using the generic signature must not break on explicit
#: defaults, ADVICE r4) and raise only on a genuinely different value
GENERATE_DEFAULTS = {
    "use_cache": True, "paged": False, "page_size": 16,
    "prefill_chunk_size": None, "repetition_penalty": 1.0,
    "min_new_tokens": 0, "num_beams": 1, "length_penalty": 1.0,
    "early_stopping": False, "no_repeat_ngram_size": 0,
}


def reject_non_default_kwargs(family: str, kwargs: dict):
    """Raise for unsupported generate() kwargs UNLESS the caller passed
    the shared default value explicitly."""
    for k, v in kwargs.items():
        if k in GENERATE_DEFAULTS and v == GENERATE_DEFAULTS[k]:
            continue
        raise NotImplementedError(
            f"{family}.generate does not support {k}={v!r} (decoder-only "
            "families carry the full strategy surface)")


def generate(model, input_ids, max_new_tokens=20, do_sample=False,
             temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
             use_cache=True, attention_mask=None, paged=False,
             page_size=16, prefill_chunk_size=None,
             repetition_penalty=1.0, min_new_tokens=0,
             num_beams=1, length_penalty=1.0, early_stopping=False,
             no_repeat_ngram_size=0):
    """Batched autoregressive decode.

    ``repetition_penalty`` (HF semantics): logits of tokens already in the
    row (prompt + generated so far) are divided by the penalty when
    positive, multiplied when negative. ``min_new_tokens`` blocks
    ``eos_token_id`` for the first N generated tokens (requires eos).
    ``no_repeat_ngram_size=n`` bans tokens that would repeat an n-gram of
    the row's sequence (prompt + generated).

    ``num_beams > 1`` runs beam search (greedy scoring over K beams per
    row, HF semantics: 2K candidates per step, eos hits retire into a
    hypothesis pool scored by sum-logprob / len**length_penalty); returns
    each row's best hypothesis.

    ``attention_mask`` [B, S0] (1 = real token; right- OR
    left-padded rows — HF tokenizer output works directly, left pads
    roll to the internal right-padded layout exactly) makes
    ragged batches correct: pad columns are never attended, RoPE positions
    continue per row from each row's true length, and the first sampled
    token reads each row's last real logit.

    ``prefill_chunk_size``: process the prompt as a ``lax.scan`` over
    fixed-size chunks (chunked prefill) — compile cost buckets by chunk
    COUNT instead of exact prompt shape, and prefill activation memory is
    one chunk's worth. Output is identical to the one-shot prefill.

    Returns generated ids [B, <=max_new_tokens] (prompt excluded); stops
    early only when EVERY row has emitted eos.
    """
    ids = unwrap(input_ids) if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
    B, S0 = ids.shape
    cfg = model.config
    if max_new_tokens <= 0:
        return wrap(jnp.zeros((B, 0), ids.dtype))
    rp = float(repetition_penalty)
    if rp <= 0:
        raise ValueError("repetition_penalty must be positive")
    min_new = int(min_new_tokens)
    if min_new > 0 and eos_token_id is None:
        raise ValueError("min_new_tokens requires eos_token_id (it only "
                         "delays the eos stop)")
    ngram = int(no_repeat_ngram_size)
    penalized = rp != 1.0 or min_new > 0 or ngram > 0
    if paged and getattr(model.llama, "empty_cache_layer", None) is not None:
        # fail BEFORE the prefill: the paged layout needs per-head k/v
        # caches; MLA latent caches (c_kv/k_pe) decode dense-buffer only
        raise NotImplementedError(
            "the paged KV layout needs per-head k/v caches; MLA latent "
            "caches (c_kv/k_pe) decode through the dense buffer path "
            "(paged=False)")
    num_beams = int(num_beams)
    if num_beams > 1:
        if do_sample:
            raise NotImplementedError(
                "beam search with do_sample=True (beam sampling) is not "
                "supported; use num_beams>1 with do_sample=False")
        if paged:
            raise NotImplementedError(
                "beam search over the paged KV layout is not supported; "
                "use paged=False (beams reorder dense cache rows)")
        if not use_cache:
            raise NotImplementedError("beam search needs use_cache=True")
    chunk = int(prefill_chunk_size) if prefill_chunk_size else 0
    if chunk:
        if not use_cache:
            raise NotImplementedError(
                "prefill_chunk_size needs the cached path (use_cache=True)")
        n_chunks = -(-S0 // chunk)
        prompt_pad = n_chunks * chunk   # cache slots the padded prompt uses
    else:
        prompt_pad = S0
    max_len = prompt_pad + max_new_tokens
    if paged:
        max_len = -(-max_len // page_size) * page_size
    if max_len > cfg.max_position_embeddings:
        raise ValueError(
            f"generate: prompt+new tokens {max_len} exceeds "
            f"max_position_embeddings {cfg.max_position_embeddings}")

    pad_mask = None
    lengths = jnp.full((B,), S0, jnp.int32)
    if attention_mask is not None:
        if not use_cache:
            raise NotImplementedError(
                "generate(use_cache=False) ignores attention_mask; use the "
                "cached path for padded prompts")
        am = unwrap(attention_mask) if isinstance(attention_mask, Tensor) \
            else jnp.asarray(attention_mask)
        lengths = am.astype(jnp.int32).sum(1)
        # The internal layout is RIGHT-padded: RoPE positions, the cache
        # write layout, and the last-real-logit gather all assume each
        # row's real tokens are a CONTIGUOUS PREFIX. LEFT-padded prompts
        # (HF's generation convention) are accepted by rolling each row's
        # suffix to the front — generated tokens are pad-layout-invariant,
        # so this is exact. Interior holes still fail loudly.
        prefix = jnp.arange(S0)[None, :] < lengths[:, None]
        amb = am.astype(bool)
        if bool((amb != prefix).any()):
            suffix = jnp.arange(S0)[None, :] >= (S0 - lengths)[:, None]
            # PER-ROW gate: rows may mix right- and left-padded layouts
            # (each contiguous); only interior holes are invalid
            is_prefix = (amb == prefix).all(axis=1)
            is_suffix = (amb == suffix).all(axis=1)
            if not bool((is_prefix | is_suffix).all()):
                raise ValueError(
                    "generate(attention_mask=...) expects right- or "
                    "left-padded prompts (contiguous real tokens); got a "
                    "mask with interior holes.")
            # roll left-padded rows' suffix to the front (right-padded
            # rows shift by 0)
            shifts = jnp.where(is_prefix, 0, S0 - lengths)[:, None]
            idx = (jnp.arange(S0)[None, :] + shifts) % S0
            ids = jnp.take_along_axis(ids, idx, axis=1)
            am = jnp.take_along_axis(am, idx, axis=1)
        if bool((lengths < 1).any()):
            raise ValueError(
                "generate(attention_mask=...): every row needs at least one "
                "real token — an all-zero mask row would decode from a pad "
                "position's logits")
        pad_mask = jnp.concatenate(
            [am.astype(bool),
             jnp.ones((B, max_len - S0), bool)], axis=1)

    with _tape.no_grad():
        if not use_cache:
            return _generate_no_cache(model, ids, max_new_tokens, do_sample,
                                      temperature, top_k, top_p, eos_token_id,
                                      rp=rp, min_new=min_new, ngram=ngram)

        # ---- prefill: one jitted computation (flash kernel + cache fill +
        # last-real-logit gather; the [B,1,H] gather before the lm head
        # keeps the vocab projection S0x smaller in HBM) ----
        if chunk:
            if pad_mask is None and prompt_pad == S0:
                # evenly divisible unpadded prompt: pos masking suffices,
                # no column mask needed
                pass
            else:
                # chunked prompts are internally ragged: pad columns
                # between each row's true length and the padded prompt
                # region must never be attended, and decode RoPE continues
                # per row
                am_eff = (pad_mask[:, :S0] if pad_mask is not None
                          else jnp.ones((B, S0), bool))
                pad_mask = jnp.concatenate(
                    [am_eff, jnp.zeros((B, prompt_pad - S0), bool),
                     jnp.ones((B, max_len - prompt_pad), bool)], axis=1)
            ids_pad = jnp.concatenate(
                [ids, jnp.zeros((B, prompt_pad - S0), ids.dtype)], axis=1)
            prefill = _get_chunked_prefill_step(model, max_len, chunk,
                                                n_chunks)
            last, caches = prefill(ids_pad, lengths, pad_mask)
        else:
            prefill = _get_prefill_step(model, max_len, pad_mask is not None)
            last, caches = prefill(ids, lengths, pad_mask)

        if paged:
            caches = _caches_to_paged(caches, page_size, lengths, pad_mask)

        # per-row RoPE positions for the generated tokens (ragged batches
        # continue at each row's true length)
        if pad_mask is not None and not paged:
            for c in caches:
                c["row_pos"] = lengths

        if num_beams > 1:
            histories0 = None
            if rp != 1.0 or ngram > 0:
                ids_np = np.asarray(ids)
                lens_np = np.asarray(lengths)
                histories0 = [list(map(int, ids_np[b, : lens_np[b]]))
                              for b in range(B)]
            return _beam_search(model, last, caches, max_len,
                                max_new_tokens, num_beams, eos_token_id,
                                float(length_penalty), bool(early_stopping),
                                rp=rp, histories0=histories0,
                                min_new=min_new, ngram=ngram)

        if eos_token_id is None and max_new_tokens > 1 and not penalized:
            # fixed-length decode: the whole loop is ONE lax.scan dispatch
            # (sample_t → forward_t → logits_{t+1}); the final token needs
            # only a sample, no forward. (A repetition penalty carries a
            # [B, V] seen-set — that run takes the host loop below.)
            scan = _get_scan_decode(model, max_len, max_new_tokens - 1,
                                    do_sample, temperature, top_k, top_p)
            toks, last, caches = scan(last, _random.next_key(), caches)
            final = _select(last, _random.next_key(), do_sample,
                            float(temperature), int(top_k), float(top_p))
            return wrap(jnp.concatenate(
                [toks.T.astype(ids.dtype), final.reshape(B, 1).astype(ids.dtype)],
                axis=1))

        step = _get_decode_step(model, max_len)
        finished = jnp.zeros((B,), bool)
        seen = (_seen_from_prompt(ids, cfg.vocab_size, pad_mask)
                if rp != 1.0 else None)
        tracker = None
        if ngram > 0:
            ids_np = np.asarray(ids)
            lens_np = np.asarray(lengths)
            tracker = _NgramBan(
                [list(ids_np[b, : lens_np[b]]) for b in range(B)], ngram)
        out_tokens = []
        for i in range(max_new_tokens):
            key = _random.next_key()
            if tracker is not None:
                banned = tracker.banned(cfg.vocab_size)
                if banned.any():  # skip the transfer on no-op steps
                    last = jnp.where(jnp.asarray(banned), -jnp.inf,
                                     last.astype(jnp.float32))
            nxt = _select_next(last, seen, key, do_sample, temperature,
                               top_k, top_p, rp, i, min_new, eos_token_id)
            if eos_token_id is not None:
                nxt = jnp.where(finished, eos_token_id, nxt)
                finished = finished | (nxt == eos_token_id)
            if seen is not None:
                seen = seen.at[jnp.arange(B), nxt].set(True)
            if tracker is not None:
                for b, t in enumerate(np.asarray(nxt)):
                    tracker.append(b, int(t))
            out_tokens.append(nxt.reshape(B, 1).astype(ids.dtype))
            if i == max_new_tokens - 1 or (
                    eos_token_id is not None and bool(finished.all())):
                break
            logits, caches = step(out_tokens[-1], caches)
            last = logits[:, -1, :]
        return wrap(jnp.concatenate(out_tokens, axis=1))


def _generate_no_cache(model, ids, max_new_tokens, do_sample, temperature,
                       top_k, top_p, eos_token_id, rp=1.0, min_new=0,
                       ngram=0):
    B = ids.shape[0]
    finished = jnp.zeros((B,), bool)
    seen = (_seen_from_prompt(ids, model.config.vocab_size)
            if rp != 1.0 else None)
    tracker = (_NgramBan([list(np.asarray(ids)[b]) for b in range(B)], ngram)
               if ngram > 0 else None)
    out_tokens = []
    full = ids
    for i in range(max_new_tokens):
        hidden = model.llama(wrap(full))
        last = unwrap(model.lm_head_logits(hidden))[:, -1, :]
        key = _random.next_key()
        if tracker is not None:
            banned = tracker.banned(model.config.vocab_size)
            if banned.any():
                last = jnp.where(jnp.asarray(banned), -jnp.inf,
                                 last.astype(jnp.float32))
        nxt = _select_next(last, seen, key, do_sample, temperature, top_k,
                           top_p, rp, i, min_new, eos_token_id)
        if eos_token_id is not None:
            nxt = jnp.where(finished, eos_token_id, nxt)
            finished = finished | (nxt == eos_token_id)
        if seen is not None:
            seen = seen.at[jnp.arange(B), nxt].set(True)
        if tracker is not None:
            for b, t in enumerate(np.asarray(nxt)):
                tracker.append(b, int(t))
        out_tokens.append(nxt.reshape(B, 1).astype(ids.dtype))
        full = jnp.concatenate([full, out_tokens[-1]], axis=1)
        if eos_token_id is not None and bool(finished.all()):
            break
    return wrap(jnp.concatenate(out_tokens, axis=1))


# ---------------------------------------------------------------------------
# paged cache construction
# ---------------------------------------------------------------------------

def _caches_to_paged(caches, page_size, lengths, pad_mask):
    """Re-lay dense prefilled buffers [B, max_len, hk, D] into paged dicts
    (contiguous page tables; an allocator would virtualize page_indices)."""
    k0 = caches[0]["k"]
    B, max_len, hk, D = k0.shape
    pages_per_seq = max_len // page_size

    def to_pages(buf):
        p = buf.reshape(B, pages_per_seq, page_size, hk, D)
        return jnp.moveaxis(p, 3, 0).reshape(hk, B * pages_per_seq,
                                             page_size, D)

    page_indices = jnp.arange(B * pages_per_seq, dtype=jnp.int32).reshape(
        B, pages_per_seq)
    out = []
    for c in caches:
        out.append({
            "k_pages": to_pages(c["k"]),
            "v_pages": to_pages(c["v"]),
            "page_indices": page_indices,
            # per-row valid-token counts: paged_decode_attention masks by
            # position < lengths[b], and each decode step writes row b's
            # token at its own page/slot (lengths[b]) — right-pad garbage
            # sits at positions >= lengths[b] until overwritten, never
            # attended. Fully ragged batches are first-class.
            "lengths": lengths,
            "page_size": page_size,
        })
    return out


def generate_paged(model, input_ids, max_new_tokens=20, page_size=16,
                   **kwargs):
    """Paged-KV decode (block_multi_head_attention serving configuration):
    generate() with the paged cache layout."""
    return generate(model, input_ids, max_new_tokens=max_new_tokens,
                    paged=True, page_size=page_size, **kwargs)
