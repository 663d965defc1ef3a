"""Context parallelism for long sequences: ring attention + Ulysses all-to-all.

The reference has NO ring/context-parallel attention (SURVEY.md §2.7 "CP /
ring attention — absent"); its long-context story is Megatron-SP boundaries
(fleet/utils/sequence_parallel_utils.py) plus a bare ``sep`` topology axis
whose all-to-all redistribution lives in user model code
(python/paddle/distributed/fleet/base/topology.py:199). This module goes
beyond the reference — per the build plan (SURVEY.md §7 step 9) — with two
TPU-native mechanisms, both expressed as collectives inside ``shard_map``
so XLA schedules the ICI transfers:

- **Ring attention** (`ring_attention`): q/k/v are sharded along the
  sequence axis; k/v blocks rotate around the ring via ``lax.ppermute``
  while each device accumulates blockwise-streaming-softmax partial results
  (the flash-attention recurrence, carried as (m, l, o)). Memory per device
  is O(S_local); the full S×S score matrix never materialises.
- **Ulysses attention** (`ulysses_attention`): ``lax.all_to_all`` swaps the
  sharded axis from sequence to heads, runs ordinary (flash) attention on
  full-length sequences for a head subset, and swaps back. Cheaper than a
  ring for moderate S (two a2a's vs N-1 permutes) but caps the degree at
  num_heads.

Both are reverse-mode differentiable (the ring loop is a ``lax.scan``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax


def _neg_inf(dtype):
    return jnp.asarray(jnp.finfo(dtype).min, dtype)


def _expand_gqa(k, v, num_q_heads):
    """Repeat kv heads up to ``num_q_heads`` (standard GQA grouping: q head
    j reads kv head j // (H/H_kv))."""
    rep = num_q_heads // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return k, v


def _block_step(q, k, v, m, l, o, mask, scale):
    """One blockwise flash-attention accumulation step, GQA-grouped.

    q: [B,Hkv,G,Sq,D] local queries (G = num_q_heads / num_kv_heads);
    k/v: [B,Hkv,Sk,D] current ring block — kv heads stay UNexpanded so the
    ring carry (and every ppermute hop) moves only kv-head bytes; carry
    m (running max, [B,Hkv,G,Sq]), l (running denom), o (unnormalised
    accumulator, q-shaped); mask: [Sq,Sk] bool (True = attend).
    """
    s = jnp.einsum("bhgqd,bhkd->bhgqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    m_new = jnp.maximum(m, s.max(axis=-1))
    # rows still fully masked have m_new == -inf; exp(-inf - -inf) would be
    # NaN, so guard both the rescale factor and the block probabilities.
    dead = jnp.isneginf(m_new)
    alpha = jnp.where(dead, 0.0, jnp.exp(m - m_new))
    p = jnp.where(dead[..., None], 0.0, jnp.exp(s - m_new[..., None]))
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum(
        "bhgqk,bhkd->bhgqd", p, v.astype(jnp.float32),
        preferred_element_type=jnp.float32)
    return m_new, l_new, o_new


def _live_hops(n: int, s_k: int, causal: bool, window: Optional[int]) -> int:
    """Number of ring hops that can touch ANY live (q, kv) pair on ANY
    device. Hop t processes kv block j = (i - t) mod n; under causal +
    sliding window w the band 0 <= q_glob - k_glob <= w-1 reaches back at
    most w-1+s-1 positions, so hops with t*s_k > w-1 + s_k-1 are dead on
    EVERY device and are skipped statically — long-seq work scales with
    the window, not the ring size (VERDICT r4 item 3)."""
    if causal and window is not None:
        return min(n, (window + s_k - 2) // s_k + 1)
    return n


def _ring_stream(qt, kv0, make_kv, s_k: int, axis_name: str, causal: bool,
                 scale: float, window: Optional[int], dv: int):
    """Shared streaming-softmax ring driver.

    qt: [B,Hkv,G,Sq,Dk] grouped (UNscaled) queries. kv0: an arbitrary
    pytree that rotates around the ring via ppermute; per hop
    ``make_kv(kv0) -> (kc [B,Hkv,Sk,Dk], vc [B,Hkv,Sk,Dv])`` produces
    this hop's keys/values (identity for a plain ring; latent expansion
    for MLA). Accumulates the flash recurrence with an f32 (m, l, o)
    carry; returns the normalized output [B,Hkv,G,Sq,Dv] (f32).
    """
    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, h_kv, g, s_q, _ = qt.shape

    q_pos = idx * s_q + jnp.arange(s_q)            # global query positions
    perm = [(i, (i + 1) % n) for i in range(n)]
    t_live = _live_hops(n, s_k, causal, window)

    # derive the accumulators from qt (zeroed) so they carry the same
    # varying-manual-axes type as the inputs — both lax.cond branches (and
    # the scan carry) must agree on vma under shard_map's typing
    o0 = (jnp.zeros((b, h_kv, g, s_q, dv), jnp.float32)
          + qt[..., :1].astype(jnp.float32) * 0.0)
    l0 = o0[..., 0]
    m0 = l0 - jnp.inf

    def step(carry, t):
        kv, m, l, o = carry
        kv_idx = (idx - t) % n
        k_pos = kv_idx * s_k + jnp.arange(s_k)
        if causal:
            mask = q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= (q_pos[:, None] - k_pos[None, :]) < window
        else:
            mask = jnp.ones((s_q, s_k), bool)
        live = jnp.any(mask)

        def compute(args):
            m, l, o = args
            kc, vc = make_kv(kv)
            return _block_step(qt, kc, vc, m, l, o, mask, scale)

        m, l, o = lax.cond(live, compute, lambda args: args, (m, l, o))
        kv = jax.tree.map(lambda x: lax.ppermute(x, axis_name, perm), kv)
        return (kv, m, l, o), None

    (_, m, l, o), _ = lax.scan(step, (kv0, m0, l0, o0), jnp.arange(t_live))
    return o / jnp.where(l == 0.0, 1.0, l)[..., None]


def _ring_einsum(q, k, v, axis_name: str, causal: bool, scale: float,
                 window: Optional[int]):
    """Streaming-softmax ring over XLA einsum blocks (the differentiable
    reference path; also the fallback when splash's shape constraints
    don't hold). q: [B,S,H,D], k/v: [B,S,Hkv,D] local shards; kv heads
    stay UNexpanded so every ppermute hop moves only kv-head bytes."""
    b, s_q, h, d = q.shape
    s_k, h_kv = k.shape[1], k.shape[2]
    g = h // h_kv  # GQA group size

    # q: [B,Hkv,G,Sq,D] grouped by kv head; k/v: [B,Hkv,Sk,D]
    qt = jnp.swapaxes(q, 1, 2).reshape(b, h_kv, g, s_q, d)
    kv0 = (jnp.swapaxes(k, 1, 2), jnp.swapaxes(v, 1, 2))
    out = _ring_stream(qt, kv0, lambda kv: kv, s_k, axis_name, causal,
                       scale, window, d)
    out = out.reshape(b, h, s_q, d)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def _ring_splash_fwd_impl(q, k, v, axis_name: str, causal: bool,
                          scale: float, window: Optional[int],
                          interpret: bool):
    """Ring forward where each hop runs the GQA-native splash flash kernel
    (SURVEY §7 step 9: "Pallas flash + ppermute"). Per hop the mask
    geometry is STATIC in the hop index t (q_glob - kv_glob = q_loc -
    kv_loc + t*s for every device), so each hop gets its own compiled
    kernel: t=0 the causal diagonal, t>=1 full blocks (plain causal) or
    the t*s-offset sliding band (window). Per-device liveness (kv block in
    the future, i < t) stays dynamic via lax.cond. Hops are combined by
    streaming softmax over the per-hop (out, logsumexp) residuals with an
    f32 carry."""
    from ..ops.pallas.flash_attention import splash_hop

    n = lax.psum(1, axis_name)
    idx = lax.axis_index(axis_name)
    b, s_q, h, d = q.shape
    s_k = k.shape[1]
    perm = [(i, (i + 1) % n) for i in range(n)]
    t_live = _live_hops(n, s_k, causal, window)

    qs = jnp.swapaxes(q * jnp.asarray(scale, q.dtype), 1, 2)  # [B,H,S,D]
    kc = jnp.swapaxes(k, 1, 2)                                # [B,Hkv,S,D]
    vc = jnp.swapaxes(v, 1, 2)

    m = jnp.full((b, h, s_q), -jnp.inf, jnp.float32) + (qs[..., 0] * 0.0)
    ssum = jnp.zeros_like(m)
    acc = jnp.zeros((b, h, s_q, d), jnp.float32) + (qs * 0.0)

    for t in range(t_live):
        if causal and window is not None:
            kind, offset = "local", t * s_k
        elif causal and t == 0:
            kind, offset = "causal", 0
        else:
            # plain-causal past block (offset t*s >= s ⇒ every cell
            # attends) or non-causal: a full block either way
            kind, offset = "full", 0

        def hop(args, kc=kc, vc=vc, kind=kind, offset=offset):
            m, ssum, acc = args
            o_t, lse = splash_hop(qs, kc, vc, kind, offset=offset,
                                  window=window, interpret=interpret)
            lse = lse.astype(jnp.float32)
            m_new = jnp.maximum(m, lse)
            # m starts at -inf; splash emits a finite (hugely negative)
            # lse for fully-masked rows, so m_new is finite after hop 0
            # and neither exp() below can see (-inf) - (-inf)
            alpha = jnp.exp(m - m_new)
            w = jnp.exp(lse - m_new)
            return (m_new, ssum * alpha + w,
                    acc * alpha[..., None] + w[..., None]
                    * o_t.astype(jnp.float32))

        if causal:
            live = idx >= t  # kv block (i - t) is in this device's past
            m, ssum, acc = lax.cond(live, hop, lambda args: args,
                                    (m, ssum, acc))
        else:
            m, ssum, acc = hop((m, ssum, acc))
        if t + 1 < t_live:
            kc = lax.ppermute(kc, axis_name, perm)
            vc = lax.ppermute(vc, axis_name, perm)

    out = acc / jnp.where(ssum == 0.0, 1.0, ssum)[..., None]
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _ring_splash(q, k, v, axis_name, causal, scale, window, interpret):
    return _ring_splash_fwd_impl(q, k, v, axis_name, causal, scale, window,
                                 interpret)


def _ring_splash_vjp_fwd(q, k, v, axis_name, causal, scale, window,
                         interpret):
    out = _ring_splash_fwd_impl(q, k, v, axis_name, causal, scale, window,
                                interpret)
    return out, (q, k, v)


def _ring_splash_vjp_bwd(axis_name, causal, scale, window, interpret,
                         res, g):
    # The bundled splash kernel has no VJP through its residuals output
    # (save_residuals=True raises under AD), so the backward recomputes
    # through the einsum ring — mathematically the same function, O(S_local)
    # memory, fully collective-transposable. Fwd rides the MXU kernel;
    # bwd costs einsum-path FLOPs.
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q_, k_, v_: _ring_einsum(q_, k_, v_, axis_name, causal,
                                        scale, window), q, k, v)
    return vjp(g)


_ring_splash.defvjp(_ring_splash_vjp_fwd, _ring_splash_vjp_bwd)


def ring_attention(q, k, v, axis_name: str, causal: bool = False,
                   sm_scale: Optional[float] = None,
                   window: Optional[int] = None, impl: str = "auto",
                   interpret: bool = False):
    """Ring attention over a named mesh axis. Call INSIDE shard_map.

    q/k/v: [B, S_local, H, D] (paddle's BSHD layout), the local sequence
    shard; the global sequence is the concatenation over ``axis_name`` in
    axis-index order. Returns [B, S_local, H, D] in q.dtype.

    Causal masking uses global positions, so device i's queries attend to
    k/v blocks j<i fully, block j==i triangularly, and blocks j>i not at
    all (those steps are skipped via ``lax.cond``). K/V rotate via
    ``ppermute`` so step t processes block (i - t) mod N; each permute is a
    neighbour hop that rides ICI.

    ``window`` (requires ``causal=True``): Mistral-style sliding-window
    attention — hops whose kv block lies entirely outside the band are
    skipped statically (no compute, no permute), so cost scales with the
    window rather than the full sequence.

    ``impl``: "splash" runs the Pallas splash kernel per hop (TPU, or
    ``interpret=True`` for CPU parity tests) with an einsum-recompute
    backward; "einsum" is the all-XLA streaming path; "auto" picks splash
    when the shape qualifies (seq/head_dim multiples of 128, even GQA
    grouping) on TPU, einsum otherwise.
    """
    if window is not None:
        if not causal:
            raise ValueError("sliding window requires causal attention")
        if window <= 0:
            raise ValueError(f"sliding window must be positive, got {window}")
    if impl not in ("auto", "splash", "einsum"):
        raise ValueError(f"ring_attention impl must be auto|splash|einsum, "
                         f"got {impl!r}")
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)
    if impl != "einsum":
        from ..ops.pallas import flash_attention as pf

        ok = pf.supported(q, k, v, interpret=interpret)
        if impl == "splash" and not ok:
            raise ValueError(
                "ring_attention impl='splash' needs TPU (or interpret=True) "
                "and splash-tileable shapes: seq and head_dim multiples of "
                f"128, q heads an even multiple of kv heads; got q {q.shape} "
                f"k {k.shape}")
        if ok:
            return _ring_splash(q, k, v, axis_name, causal, scale, window,
                                interpret)
    return _ring_einsum(q, k, v, axis_name, causal, scale, window)


def mla_ring_attention(q, c_kv, k_pe, w_kv_b, axis_name: str, *,
                       nope_dim: int, v_dim: int,
                       sm_scale: Optional[float] = None):
    """Causal ring attention for Multi-head Latent Attention (DeepSeek).

    The ring rotates the COMPRESSED latent instead of expanded K/V: each
    ppermute hop moves ``kv_lora_rank + qk_rope_head_dim`` floats per
    token (576 at DeepSeek-V2 shapes) versus ``H*(d_qk + d_v)`` for an
    expanded ring (10240) — ~18x less ICI traffic. The receiving device
    re-expands the hop's K/V locally from the latent
    (``kv = c_kv · w_kv_b``, one MXU einsum that overlaps the next hop's
    permute), so the bandwidth saving is bought with FLOPs the TPU has to
    spare — the scaling-book trade in the direction the hardware wants.

    Call INSIDE shard_map. q [B, S_local, H, dn+dr] with RoPE already
    applied to its dr tail at GLOBAL positions; c_kv [B, S_local, r]
    (already kv_a_layernormed); k_pe [B, S_local, dr] roped at global
    positions; w_kv_b [r, H*(dn+dv)] (the local head shard under mp).
    Returns [B, S_local, H, dv] in q.dtype. Always causal (the MLA
    decoder family has no bidirectional/windowed variant).
    """
    b, s_q, h, dqk = q.shape
    s_k = c_kv.shape[1]
    dn, dv, dr = nope_dim, v_dim, dqk - nope_dim
    r = c_kv.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / (dqk ** 0.5)
    w3 = w_kv_b.reshape(r, h, dn + dv)

    # qt grouped for the shared driver with Hkv=H, G=1: [B, H, 1, Sq, dqk]
    qt = jnp.swapaxes(q, 1, 2).reshape(b, h, 1, s_q, dqk)

    def make_kv(kv):
        ckv_c, kpe_c = kv
        # local re-expansion of this hop's K/V from the latent
        kvx = jnp.einsum("bsr,rhd->bhsd", ckv_c.astype(w3.dtype), w3)
        kc = jnp.concatenate(
            [kvx[..., :dn],
             jnp.broadcast_to(kpe_c[:, None].astype(kvx.dtype),
                              (b, h, s_k, dr))], axis=-1)
        return kc, kvx[..., dn:]

    out = _ring_stream(qt, (c_kv, k_pe), make_kv, s_k, axis_name,
                       causal=True, scale=scale, window=None, dv=dv)
    out = out.reshape(b, h, s_q, dv)
    return jnp.swapaxes(out, 1, 2).astype(q.dtype)


def cp_mesh_axes(hcg):
    """(mesh, batch_axes, head_axis) for the model-side shard_map CP
    dispatch — the one mesh-axis naming shared by every attention class
    that shards its sequence over ``sep``."""
    mesh = hcg.jax_mesh()
    batch_ax = tuple(a for a in ("dp", "sharding")
                     if mesh.shape[a] > 1) or None
    head_ax = "mp" if mesh.shape["mp"] > 1 else None
    return mesh, batch_ax, head_ax


def _sdpa_core(q, k, v, causal, scale, window=None):
    """Plain blockless attention on BSHD, fp32 softmax. Used by Ulysses."""
    from ..nn.functional.attention import _sdpa_ref

    k, v = _expand_gqa(k, v, q.shape[2])
    mask = None
    if window is not None:
        # sliding band on GLOBAL positions (ulysses holds the full
        # sequence per head subset after the all-to-all)
        s_q, s_k = q.shape[1], k.shape[1]
        rows = jnp.arange(s_q)[:, None] + (s_k - s_q)
        cols = jnp.arange(s_k)[None, :]
        mask = (rows - cols) < window  # upper bound; causal handles >= 0
    return _sdpa_ref(q, k, v, mask=mask, causal=causal, scale=scale)


def ulysses_attention(q, k, v, axis_name: str, causal: bool = False,
                      sm_scale: Optional[float] = None,
                      window: Optional[int] = None):
    """DeepSpeed-Ulysses-style attention over a named axis. Call INSIDE
    shard_map.

    q/k/v: [B, S_local, H, D]. An ``all_to_all`` re-shards from sequence to
    heads ([B, S, H/N, D]), full-sequence attention runs per head subset,
    and a second ``all_to_all`` restores sequence sharding. Requires
    H % axis_size == 0 (and kv_heads % axis_size == 0 for GQA).
    """
    n = lax.psum(1, axis_name)
    h, h_kv = q.shape[2], k.shape[2]
    if h % n:
        raise ValueError(
            f"ulysses needs num_heads divisible by sep degree: {h} vs {n}")
    if h_kv % n:
        # GQA with fewer kv heads than the degree: minimally replicate kv
        # heads until they split evenly (h divides by n, so rep <= h/h_kv)
        rep = n // math.gcd(h_kv, n)
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
        h_kv *= rep
    scale = sm_scale if sm_scale is not None else 1.0 / (q.shape[-1] ** 0.5)

    def seq_to_heads(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    if window is not None and not causal:
        raise ValueError("sliding window requires causal attention")

    qg = seq_to_heads(q)                           # [B, S, H/N, D]
    kg = seq_to_heads(k)
    vg = seq_to_heads(v)
    out = _sdpa_core(qg, kg, vg, causal, scale, window=window)
    return lax.all_to_all(out, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def sep_attention(query, key, value, causal: bool = False,
                  sm_scale: Optional[float] = None, mode: str = "ring",
                  group=None, window: Optional[int] = None):
    """High-level eager entry: context-parallel attention on the hybrid
    topology's ``sep`` axis (parity surface for what reference users build
    by hand on the sep group — topology.py:199 + alltoall in model code).

    query/key/value: Tensors or arrays of GLOBAL shape [B, S, H, D]; the
    call shard_maps them over the sep axis (sequence dim sharded) and
    returns the global-shape result.
    """
    from jax.sharding import PartitionSpec as P
    from .collective import shard_map

    from ..tensor_class import Tensor, unwrap, wrap
    from .topology import get_hybrid_communicate_group

    if mode not in ("ring", "ulysses"):
        raise ValueError(f"sep_attention mode must be 'ring' or 'ulysses', got {mode!r}")
    if group is None:
        hcg = get_hybrid_communicate_group()
        if hcg is None:
            raise RuntimeError("sep_attention needs fleet.init or a group=")
        group = hcg.get_sep_parallel_group()
    mesh = group.mesh.jax_mesh()
    axis = group.axis_names[0]
    inner = ring_attention if mode == "ring" else ulysses_attention

    spec = P(*([None, axis] + [None] * 2))

    @functools.partial(shard_map, mesh=mesh, in_specs=(spec, spec, spec),
                       out_specs=spec,
                       # the splash-per-hop ring runs pallas_call inside
                       # shard_map, which requires the vma checker off
                       check_vma=False)
    def fn(q, k, v):
        return inner(q, k, v, axis, causal=causal, sm_scale=sm_scale,
                     window=window)

    was_tensor = isinstance(query, Tensor)
    out = fn(unwrap(query), unwrap(key), unwrap(value))
    return wrap(out) if was_tensor else out
