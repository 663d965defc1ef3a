"""DeepSeek-V2/V3 causal LM — Multi-head Latent Attention (MLA) + DeepSeekMoE.

Reference anchors: BASELINE.json names DeepSeekMoE as a target workload and
the reference serves this family through its fused MoE machinery
(paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu, the
moe_gate_dispatch SPMD rule paddle/phi/infermeta/spmd_rules/
moe_gate_dispatch.cc); the MLA block itself follows the DeepSeek-V2
technical report (arXiv:2405.04434) and the public HF
``modeling_deepseek.DeepseekV2Attention`` semantics.

MLA in one paragraph: instead of per-head K/V projections, the layer
projects the hidden state to a small shared latent ``c_kv``
(``kv_lora_rank``, e.g. 512) plus one shared RoPE key ``k_pe``
(``qk_rope_head_dim``, e.g. 64, MQA-style — one head, broadcast to all
query heads). Per-head keys/values are re-expanded from the latent with
``kv_b_proj`` (no position information — RoPE rides only the decoupled
``k_pe`` slice). Queries are optionally low-rank too (``q_lora_rank``).

TPU-native design — two execution regimes:

- **Training / prefill (expanded)**: re-expand K/V from the latent and run
  ordinary causal attention; the q/k head dim is
  ``qk_nope_head_dim + qk_rope_head_dim`` (192 at DeepSeek shapes). On TPU
  the GQA splash kernel takes the hop with q/k/v zero-padded to the next
  128 lane multiple (exact: zero columns add nothing to the dots, the true
  ``sm_scale`` is passed explicitly, and the value padding is sliced off).
  Everything is batched matmuls — MXU-shaped, GSPMD-shardable over mp.
- **Decode (absorbed)**: the KV cache stores ONLY ``c_kv`` + ``k_pe`` —
  ``kv_lora_rank + qk_rope_head_dim`` floats per token (576 at DeepSeek
  shapes vs 2048 for 8-head GQA at d=128: a 3.5x cache/bandwidth cut, the
  reason MLA exists). Scores never materialize per-head keys: q_nope is
  absorbed through the K half of ``kv_b_proj`` once per step
  (``q_lat = q_nope · W_uk``), scores = ``q_lat · c_kv + q_pe · k_pe``,
  and the context is read back through the V half
  (``out = (probs · c_kv) · W_uv``). The buffer einsums stream the latent
  once — decode is HBM-bound on 576 bytes/token/layer instead of 2 KiB.

The MoE FFN (routed + shared experts, grouped GEMM, EP-shardable) is the
shared ``MoEMLP`` from models/llama_moe.py; DeepSeek-V3 routing (sigmoid
affinities + aux-free correction bias + routed_scaling_factor) comes from
the same config knobs.
"""
from __future__ import annotations

import dataclasses
import math

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from .. import nn
from ..ops.registry import apply
from ..distributed.topology import get_hybrid_communicate_group
from .llama import (LlamaModel, LlamaRMSNorm, _make_linear, _width_norm)
from .llama_moe import (LlamaMoEConfig, LlamaMoEDecoderLayer,
                        LlamaMoEForCausalLM)


@dataclasses.dataclass
class DeepseekV2Config(LlamaMoEConfig):
    """MLA dims on top of the DeepSeekMoE base (HF DeepseekV2Config names)."""

    q_lora_rank: int | None = None         # None → full-rank q_proj (V2-Lite)
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    # DeepSeek-V3 multi-token prediction: D extra sequential modules, each
    # predicting token t+1+k from [RMSNorm(h_prev) ‖ RMSNorm(emb(t+k))]
    # through a fusion projection + one decoder block, sharing the main
    # embedding and lm head (arXiv:2412.19437 §2.2). Training-objective
    # only: forward(labels=...) adds mtp_loss_lambda x the mean MTP CE.
    num_nextn_predict_layers: int = 0
    mtp_loss_lambda: float = 0.3

    @staticmethod
    def tiny_mla(**kw):
        base = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                    num_hidden_layers=3, num_attention_heads=4,
                    num_key_value_heads=4, max_position_embeddings=256,
                    dtype="float32", n_routed_experts=4,
                    num_experts_per_tok=2, moe_intermediate_size=64,
                    first_k_dense_replace=1, kv_lora_rank=32,
                    qk_nope_head_dim=32, qk_rope_head_dim=16,
                    v_head_dim=32, q_lora_rank=None)
        base.update(kw)
        return DeepseekV2Config(**base)

    @staticmethod
    def tiny_v3(**kw):
        """V3-style routing on the tiny shape: sigmoid scores + aux-free
        correction bias + group-limited selection + routed scaling."""
        base = dict(moe_scoring_func="sigmoid", moe_correction_bias=True,
                    routed_scaling_factor=2.5, router_aux_loss_coef=0.0,
                    n_group=2, topk_group=1)
        base.update(kw)
        return DeepseekV2Config.tiny_mla(**base)


def _pad_lanes(x, to: int):
    """Zero-pad the last dim up to ``to`` (a 128 multiple for the MXU)."""
    d = x.shape[-1]
    if d == to:
        return x
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, to - d)])


def mla_softmax_scale(cfg):
    """1/sqrt(d_qk) — times the yarn mscale_all_dim factor SQUARED when the
    checkpoint scales softmax (HF DeepseekV2Attention under yarn:
    ``softmax_scale *= yarn_get_mscale(factor, mscale_all_dim)**2``)."""
    from .llama import _rope_type, _yarn_get_mscale

    scale = 1.0 / math.sqrt(cfg.qk_nope_head_dim + cfg.qk_rope_head_dim)
    rs = cfg.rope_scaling
    if _rope_type(rs) == "yarn":
        mad = float(rs.get("mscale_all_dim", 0) or 0)
        if mad:
            m = _yarn_get_mscale(float(rs["factor"]), mad)
            scale = scale * m * m
    return scale


def _mla_sdpa(q, k, v, *, causal: bool, use_flash: bool, scale: float):
    """Expanded-attention hop shared by training and prefill: q/k at
    ``qk_nope+qk_rope`` width, v at ``v_head_dim``. Takes the splash
    kernel with lane padding when the shapes tile; else the shared
    f32-softmax SDPA reference."""
    from ..nn.functional.attention import _sdpa_ref
    from ..ops.pallas import flash_attention as pf

    dv = v.shape[-1]
    if use_flash:
        dqk_p = -(-q.shape[-1] // 128) * 128
        dv_p = -(-dv // 128) * 128
        qp, kp = _pad_lanes(q, dqk_p), _pad_lanes(k, dqk_p)
        vp = _pad_lanes(v, dv_p)
        if pf.supported(qp, kp, vp):
            out = pf.flash_attention_bshd(qp, kp, vp, causal=causal,
                                          sm_scale=scale)
            return out[..., :dv].astype(q.dtype)
    return _sdpa_ref(q, k, v, causal=causal, scale=scale)


def _absorbed_tail(q_lat, q_pe, ckv_buf, kpe_buf, w_uv, scale, dr, mask,
                   kernel_pos, allowed, use_flash, interpret):
    """The absorbed-attention tail shared by the generate() cache path and
    the serving engine: optional S=1 Pallas hop (single pass over the
    latent buffer), else masked-softmax einsums. q_lat [B,S,H,r] f32
    UNscaled; q_pe [B,S,H,dr] roped; mask [B or 1, 1, S, T] bool;
    kernel_pos scalar or [B] row limits for the kernel. Returns the
    latent-absorbed output [B,S,H,dv] (f32)."""
    S = q_lat.shape[1]
    if S == 1 and use_flash:
        from ..ops.pallas import mla_decode as pmd

        ql = q_lat[:, 0] * scale
        qp = q_pe[:, 0].astype(jnp.float32) * scale
        if pmd.supported(ql, ckv_buf, kpe_buf, interpret=interpret):
            ctx = pmd.mla_decode_attention(ql, qp, ckv_buf, kpe_buf,
                                           kernel_pos, allowed=allowed,
                                           interpret=interpret)
            return jnp.einsum("bhr,rhd->bhd", ctx.astype(jnp.float32),
                              w_uv.astype(jnp.float32))[:, None]
    scores = (jnp.einsum("bshr,btr->bhst", q_lat,
                         ckv_buf.astype(jnp.float32))
              # [..., :dr]: the TPU cache is lane-padded (empty_cache_layer)
              + jnp.einsum("bshd,btd->bhst", q_pe.astype(jnp.float32),
                           kpe_buf[..., :dr].astype(jnp.float32))) * scale
    scores = jnp.where(mask, scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhst,btr->bshr", probs, ckv_buf.astype(jnp.float32))
    return jnp.einsum("bshr,rhd->bshd", ctx, w_uv.astype(jnp.float32))


def mla_cached_attention(q_nope, q_pe, c_kv, k_pe, cos, sin, ckv_buf,
                         kpe_buf, pos, w_kv_b, *, nope_dim, v_dim,
                         allowed=None, row_pos=None, prefill=False,
                         use_flash=False, interpret=False, sm_scale=None):
    """RoPE + latent-cache write + absorbed MLA attention against the
    compressed buffer (the decode analog of generation.cached_attention).

    q_nope [B,S,H,dn]; q_pe [B,S,H,dr]; c_kv [B,S,r] (already
    kv_a_layernormed); k_pe [B,S,dr] (pre-RoPE); cos/sin [>=max_len, dr];
    ckv_buf [B,Smax,r]; kpe_buf [B,Smax,dr]; pos = buffer write offset;
    w_kv_b [r, H*(dn+dv)]; allowed/row_pos as in cached_attention.
    Returns (out [B,S,H,dv], new_ckv_buf, new_kpe_buf).

    Static pos==0 prefills (the ``prefill`` marker) take the EXPANDED path
    — causal attention over just the S new tokens (flash-capable); every
    other step runs the absorbed form over the latent buffer, which is
    exact at any (pos, S) including chunked-prefill appends.
    """
    from ..generation import _rope_rows
    from ..ops.pallas.fused_norm import rope_ref

    B, S, H, dn = q_nope.shape
    dr = q_pe.shape[-1]
    r = c_kv.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(nope_dim + dr)
    pos = jnp.asarray(pos, jnp.int32)

    k_pe4 = k_pe[:, :, None, :]                            # [B,S,1,dr]
    if row_pos is None:
        cos_s = jax.lax.dynamic_slice_in_dim(cos, pos, S, 0)
        sin_s = jax.lax.dynamic_slice_in_dim(sin, pos, S, 0)
        q_pe = rope_ref(q_pe, cos_s, sin_s)
        k_pe4 = rope_ref(k_pe4, cos_s, sin_s)
    else:
        q_pe = _rope_rows(q_pe, cos, sin, row_pos)
        k_pe4 = _rope_rows(k_pe4, cos, sin, row_pos)
    k_pe = k_pe4[:, :, 0, :].astype(kpe_buf.dtype)

    ckv_buf = jax.lax.dynamic_update_slice(
        ckv_buf, c_kv.astype(ckv_buf.dtype), (0, pos, 0))
    kpe_buf = jax.lax.dynamic_update_slice(kpe_buf, k_pe, (0, pos, 0))

    w3 = w_kv_b.reshape(r, H, nope_dim + v_dim)
    if bool(prefill) and S > 1 and allowed is None and row_pos is None:
        # expanded prefill: re-inflate K/V for the S new tokens only (the
        # rest of the buffer is empty at pos==0)
        kv = jnp.einsum("bsr,rhd->bshd", c_kv.astype(w3.dtype), w3)
        k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
        q = jnp.concatenate([q_nope, q_pe.astype(q_nope.dtype)], axis=-1)
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe4.astype(k_nope.dtype),
                                      (B, S, H, dr))], axis=-1)
        out = _mla_sdpa(q, k, v, causal=True, use_flash=use_flash,
                        scale=scale)
        return out, ckv_buf, kpe_buf

    # absorbed attention over the latent buffer (shared tail; Pallas
    # single-pass hop at S=1)
    w_uk, w_uv = w3[..., :nope_dim], w3[..., nope_dim:]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    T = ckv_buf.shape[1]
    t_idx = jnp.arange(T)
    valid = t_idx[None, :] <= (pos + jnp.arange(S))[:, None]   # [S, T]
    mask = valid[None, None]                                   # [1,1,S,T]
    if allowed is not None:
        mask = mask & allowed[:, None, None, :]                # [B,1,S,T]
    out = _absorbed_tail(q_lat, q_pe, ckv_buf, kpe_buf, w_uv, scale, dr,
                         mask, kernel_pos=pos, allowed=allowed,
                         use_flash=use_flash, interpret=interpret)
    return out.astype(q_nope.dtype), ckv_buf, kpe_buf


def mla_serving_attention(q_nope, q_pe, c_kv, k_pe, cos, sin, ckv_buf,
                          kpe_buf, lengths, w_kv_b, *, nope_dim, v_dim,
                          use_flash=False, interpret=False, sm_scale=None):
    """Continuous-batching decode over the latent cache: each SLOT row sits
    at its own length (requests admit/retire independently), so writes
    scatter per row at ``lengths[b]``, RoPE rides per-row positions, and
    attention masks ``t <= lengths[b]``. S must be 1 (one token per active
    slot per engine step). Returns (out [B,1,H,dv], new_ckv, new_kpe).

    The Pallas decode kernel takes the hop with per-row ``pos`` when the
    shapes tile; else the masked einsum. Rows whose slot is empty
    (length 0) compute one masked column of garbage that the engine
    discards — identical to the paged path's dead-slot behavior."""
    from ..generation import _rope_rows

    B, S, H, dn = q_nope.shape
    if S != 1:
        raise ValueError(f"mla_serving_attention decodes one token per "
                         f"slot per step, got S={S}")
    dr = q_pe.shape[-1]
    r = c_kv.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(nope_dim + dr)
    lengths = jnp.asarray(lengths, jnp.int32)

    q_pe = _rope_rows(q_pe, cos, sin, lengths)
    k_pe4 = _rope_rows(k_pe[:, :, None, :], cos, sin, lengths)

    rows = jnp.arange(B)
    ckv_buf = ckv_buf.at[rows, lengths].set(
        c_kv[:, 0].astype(ckv_buf.dtype))
    kpe_buf = kpe_buf.at[rows, lengths, :dr].set(
        k_pe4[:, 0, 0, :].astype(kpe_buf.dtype))

    w3 = w_kv_b.reshape(r, H, nope_dim + v_dim)
    w_uk, w_uv = w3[..., :nope_dim], w3[..., nope_dim:]
    q_lat = jnp.einsum("bshd,rhd->bshr", q_nope.astype(jnp.float32),
                       w_uk.astype(jnp.float32))
    T = ckv_buf.shape[1]
    mask = (jnp.arange(T)[None, :] <= lengths[:, None])[:, None, None]
    out = _absorbed_tail(q_lat, q_pe, ckv_buf, kpe_buf, w_uv, scale, dr,
                         mask, kernel_pos=lengths, allowed=None,
                         use_flash=use_flash, interpret=interpret)
    return out.astype(q_nope.dtype), ckv_buf, kpe_buf


class DeepseekV2Attention(Layer):
    """MLA block: low-rank q (optional), shared compressed kv latent +
    decoupled MQA RoPE key, per-head re-expansion."""

    def __init__(self, config: DeepseekV2Config):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.config = config
        h = config.hidden_size
        H = config.num_attention_heads
        dn, dr = config.qk_nope_head_dim, config.qk_rope_head_dim
        dv, r = config.v_head_dim, config.kv_lora_rank
        self.num_heads, self.nope_dim, self.rope_dim, self.v_dim = H, dn, dr, dv
        bias = config.attention_bias
        if config.q_lora_rank:
            with dtype_guard(config.dtype):
                self.q_a_proj = nn.Linear(h, config.q_lora_rank,
                                          bias_attr=None if bias else False)
            self.q_a_layernorm = _width_norm(config, config.q_lora_rank)
            self.q_b_proj = _make_linear(config.q_lora_rank, H * (dn + dr),
                                         column=True, config=config)
            self.q_proj = None
        else:
            self.q_proj = _make_linear(h, H * (dn + dr), column=True,
                                       config=config, has_bias=bias)
        # latent projection stays replicated (it is the SHARED cache the
        # absorbed path streams; r+dr doesn't shard over heads)
        with dtype_guard(config.dtype):
            self.kv_a_proj_with_mqa = nn.Linear(
                h, r + dr, bias_attr=None if bias else False)
        self.kv_a_layernorm = _width_norm(config, r)
        self.kv_b_proj = _make_linear(r, H * (dn + dv), column=True,
                                      config=config)
        self.o_proj = _make_linear(H * dv, h, column=False, config=config)
        self.softmax_scale = mla_softmax_scale(config)

    def _kv_b_weight(self):
        """kv_b_proj's weight for the absorbed/expansion contractions —
        through the adapter-folded view when the layer is LoRA-wrapped
        (reading .weight directly would silently bypass the adapter)."""
        lin = self.kv_b_proj
        if hasattr(lin, "effective_weight"):
            return lin.effective_weight()
        return lin.weight

    def _project(self, hidden_states):
        """Shared q/latent projections → (q_nope, q_pe, c_kv, k_pe)."""
        b, s = hidden_states.shape[0], hidden_states.shape[1]
        H, dn, dr = self.num_heads, self.nope_dim, self.rope_dim
        if self.q_proj is not None:
            q = self.q_proj(hidden_states)
        else:
            q = self.q_b_proj(self.q_a_layernorm(self.q_a_proj(hidden_states)))
        q = q.reshape([b, s, H, dn + dr])
        kv_a = self.kv_a_proj_with_mqa(hidden_states)
        c_kv = self.kv_a_layernorm(kv_a[..., : self.config.kv_lora_rank])
        k_pe = kv_a[..., self.config.kv_lora_rank:]
        return q[..., :dn], q[..., dn:], c_kv, k_pe

    def forward(self, hidden_states, cos, sin, attention_mask=None,
                kv_cache=None, position_offset=0):
        b, s = hidden_states.shape[0], hidden_states.shape[1]
        H, dn, dr, dv = (self.num_heads, self.nope_dim, self.rope_dim,
                         self.v_dim)
        cfg = self.config
        q_nope, q_pe, c_kv, k_pe = self._project(hidden_states)

        if isinstance(kv_cache, dict) and "lengths" in kv_cache:
            # continuous-batching engine cache: per-row slot lengths
            out, ckv_buf, kpe_buf = apply(
                "mla_attention_serving", mla_serving_attention,
                q_nope, q_pe, c_kv, k_pe, cos, sin,
                kv_cache["c_kv"], kv_cache["k_pe"], kv_cache["lengths"],
                self._kv_b_weight(), nope_dim=dn, v_dim=dv,
                use_flash=cfg.use_flash_attention,
                sm_scale=self.softmax_scale)
            result = self.o_proj(out.reshape([b, s, H * dv]))
            new = {"c_kv": ckv_buf, "k_pe": kpe_buf,
                   "lengths": kv_cache["lengths"] + s}
            return result, new
        if isinstance(kv_cache, dict):
            out, ckv_buf, kpe_buf = apply(
                "mla_attention_cached", mla_cached_attention,
                q_nope, q_pe, c_kv, k_pe, cos, sin,
                kv_cache["c_kv"], kv_cache["k_pe"], kv_cache["pos"],
                self._kv_b_weight(),
                nope_dim=dn, v_dim=dv,
                allowed=kv_cache.get("allowed"),
                row_pos=kv_cache.get("row_pos"),
                prefill=bool(kv_cache.get("prefill", False)),
                use_flash=cfg.use_flash_attention,
                sm_scale=self.softmax_scale)
            result = self.o_proj(out.reshape([b, s, H * dv]))
            new = {"c_kv": ckv_buf, "k_pe": kpe_buf,
                   "pos": kv_cache["pos"] + s}
            if "allowed" in kv_cache:
                new["allowed"] = kv_cache["allowed"]
            if "row_pos" in kv_cache:
                new["row_pos"] = kv_cache["row_pos"] + s
            return result, new
        if kv_cache is not None:
            raise NotImplementedError(
                "MLA supports the dict (static-buffer) cache only — the "
                "tuple concat cache would store EXPANDED k/v and defeat "
                "the latent compression")

        def attn_fn(q_nope, q_pe, c_kv, k_pe, cos, sin, w_kv_b):
            from ..ops.pallas.fused_norm import rope_ref

            q_pe_r = rope_ref(q_pe, cos, sin).astype(q_nope.dtype)
            k_pe_r = rope_ref(k_pe[:, :, None, :], cos, sin)
            hcg = get_hybrid_communicate_group()
            sep = (hcg is not None and hcg.get_sep_parallel_world_size() > 1)
            if sep and cfg.sep_mode == "ulysses":
                raise NotImplementedError(
                    "MLA context parallelism rides the latent ring; "
                    "Ulysses needs a per-head KV axis the latent doesn't "
                    "have — use sep_mode='ring'")
            if sep and cfg.sep_mode == "ring":
                # context parallelism: the ring rotates the COMPRESSED
                # latent (r+dr floats/token) and each hop re-expands K/V
                # locally — see mla_ring_attention. ("allgather" falls
                # through: GSPMD gathers the sequence for the dense path.)
                import functools

                from ..distributed.collective import shard_map
                from jax.sharding import PartitionSpec as P

                from ..distributed.context_parallel import (
                    cp_mesh_axes, mla_ring_attention)

                mesh, batch_ax, head_ax = cp_mesh_axes(hcg)
                q = jnp.concatenate([q_nope, q_pe_r], axis=-1)
                cp = shard_map(
                    functools.partial(
                        mla_ring_attention, axis_name="sep", nope_dim=dn,
                        v_dim=dv, sm_scale=self.softmax_scale),
                    mesh=mesh,
                    in_specs=(P(batch_ax, "sep", head_ax, None),
                              P(batch_ax, "sep", None),
                              P(batch_ax, "sep", None),
                              P(None, head_ax)),
                    out_specs=P(batch_ax, "sep", head_ax, None),
                    check_vma=False)
                out = cp(q, c_kv, k_pe_r[:, :, 0, :].astype(c_kv.dtype),
                         w_kv_b)
                return out.reshape(b, s, H * dv)
            kv = jnp.einsum("bsr,rhd->bshd", c_kv,
                            w_kv_b.reshape(cfg.kv_lora_rank, H, dn + dv))
            k_nope, v = kv[..., :dn], kv[..., dn:]
            q = jnp.concatenate([q_nope, q_pe_r], axis=-1)
            k = jnp.concatenate(
                [k_nope,
                 jnp.broadcast_to(k_pe_r.astype(k_nope.dtype),
                                  (b, s, H, dr))], axis=-1)
            out = _mla_sdpa(q, k, v, causal=True,
                            use_flash=cfg.use_flash_attention,
                            scale=self.softmax_scale)
            return out.reshape(b, s, H * dv)

        out = apply("mla_attention", attn_fn, q_nope, q_pe, c_kv, k_pe,
                    cos, sin, self._kv_b_weight())
        return self.o_proj(out)


class DeepseekV2DecoderLayer(LlamaMoEDecoderLayer):
    """MLA attention + (dense | DeepSeekMoE) FFN — the shared MoE decoder
    block with the attention class swapped."""

    attn_cls = DeepseekV2Attention


class DeepseekV2Model(LlamaModel):
    """LlamaModel trunk with MLA decoder layers and qk_rope_head_dim RoPE
    tables; the decode cache is the compressed latent (see
    ``empty_cache_layer``)."""

    def __init__(self, config: DeepseekV2Config):
        base_cfg = dataclasses.replace(config, num_hidden_layers=0,
                                       layer_types=None)
        super().__init__(base_cfg)
        self.config = config
        # NOT RecomputeLayer-wrapped (matches LlamaMoEModel): the aux-loss
        # walk reads layer.is_moe / layer.mlp._aux_loss directly
        self.layers = nn.LayerList(
            [DeepseekV2DecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])

    def _rope_dim(self):
        # RoPE rides ONLY the decoupled qk_rope_head_dim slice (MLA)
        return self.config.qk_rope_head_dim

    def empty_cache_layer(self, batch, max_len, dtype):
        """Per-layer decode cache: the COMPRESSED latent + shared RoPE key
        (generation._empty_caches consumes this hook) —
        kv_lora_rank + qk_rope_head_dim floats per token.

        On TPU the k_pe buffer is allocated LANE-PADDED (width up to the
        next 128 multiple, zeros beyond qk_rope_head_dim) so the Pallas
        decode kernel consumes it zero-copy every step; writers write the
        true width at offset 0 and einsum readers slice it back."""
        cfg = self.config
        from ..ops.pallas import backend

        dr = cfg.qk_rope_head_dim
        if backend.on_tpu():
            dr = -(-dr // 128) * 128
        return {"c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), dtype),
                "k_pe": jnp.zeros((batch, max_len, dr), dtype)}


class DeepseekMTPLayer(Layer):
    """One DeepSeek-V3 multi-token-prediction depth (arXiv:2412.19437
    §2.2): fuse ``[RMSNorm(h_prev) ‖ RMSNorm(emb(t_shifted))]`` through a
    2h→h projection, then one full (MLA + MoE/dense) decoder block. The
    main model's embedding and lm head are SHARED — this module owns only
    the two input norms, the fusion projection, the block, and the
    pre-head norm. RoPE inside the block uses 0-based tables for the
    shifted window — exact, since RoPE attention is relative."""

    def __init__(self, config: DeepseekV2Config, layer_idx: int):
        super().__init__(dtype=config.dtype)
        from ..framework.dtype import dtype_guard

        self.hnorm = LlamaRMSNorm(config)
        self.enorm = LlamaRMSNorm(config)
        with dtype_guard(config.dtype):
            self.eh_proj = nn.Linear(2 * config.hidden_size,
                                     config.hidden_size, bias_attr=False)
        self.block = DeepseekV2DecoderLayer(config, layer_idx)
        self.norm = LlamaRMSNorm(config)

    def fuse(self, h_prev, emb_next):
        """[RMSNorm(h_prev) ‖ RMSNorm(emb_next)] → 2h→h projection — the
        block input, shared by training and the speculative draft path."""
        x = apply("mtp_fuse",
                  lambda a, b: jnp.concatenate([a, b], axis=-1),
                  self.hnorm(h_prev), self.enorm(emb_next))
        return self.eh_proj(x)

    def forward(self, h_prev, emb_next, cos, sin):
        return self.block(self.fuse(h_prev, emb_next), cos, sin)


class DeepseekV2ForCausalLM(LlamaMoEForCausalLM):
    """DeepSeek-V2/V3 causal LM: MLA + MoE, latent-cache generate(); the
    aux-loss plumbing (router_aux_loss_coef) comes from the MoE base.

    ``num_nextn_predict_layers = D > 0`` adds the V3 multi-token-prediction
    chain: depth k predicts token t+1+k through its own fused block over
    the previous depth's hidden, sharing the embedding/head; training loss
    becomes ``L_main + mtp_loss_lambda · mean_k(L_k)``. Inference paths
    (generate/serving/speculative) ignore the MTP modules entirely."""

    model_cls = DeepseekV2Model

    def __init__(self, config: DeepseekV2Config):
        super().__init__(config)
        D = int(config.num_nextn_predict_layers)
        self.mtp_layers = (nn.LayerList(
            [DeepseekMTPLayer(config, config.num_hidden_layers + k)
             for k in range(D)]) if D else None)

    def forward(self, input_ids, labels=None, attention_mask=None):
        D = int(self.config.num_nextn_predict_layers)
        if labels is None or not D:
            return super().forward(input_ids, labels=labels,
                                   attention_mask=attention_mask)
        if self.config.fuse_linear_cross_entropy:
            raise NotImplementedError(
                "multi-token prediction computes explicit logits per "
                "depth; unset fuse_linear_cross_entropy to train with "
                "num_nextn_predict_layers > 0")
        from .llama import causal_lm_loss

        S = input_ids.shape[1]
        if D >= S:
            raise ValueError(
                f"num_nextn_predict_layers {D} needs sequences longer "
                f"than {D} tokens, got {S}")
        normed, pre = self.llama(input_ids, attention_mask,
                                 return_prenorm=True)
        loss = causal_lm_loss(self.lm_head_logits(normed), labels)
        # MTP chain: depth k (1-based) pairs the PRE-norm h_{k-1}[:, i]
        # with emb(t_{i+k}) and targets labels[:, i+k] (= t_{i+k+1}).
        # Like the trunk's training path, the blocks are causal-only —
        # pad positions are excluded through the label ignore mask, not an
        # attention mask. Embedding and RoPE tables are computed once at
        # full length and sliced per depth.
        emb_full = self.llama.embed_tokens(input_ids).astype(
            self.config.dtype)
        cos_full, sin_full = self.llama._rope(S)
        h_prev = pre
        mtp_total = None
        for k, layer in enumerate(self.mtp_layers, start=1):
            L_k = S - k
            h_prev = layer(h_prev[:, :L_k], emb_full[:, k:],
                           cos_full[:L_k], sin_full[:L_k])
            logits_k = self.lm_head_logits(layer.norm(h_prev))
            l_k = causal_lm_loss(logits_k, labels[:, k:])
            mtp_total = l_k if mtp_total is None else mtp_total + l_k
        loss = loss + self.config.mtp_loss_lambda * (mtp_total / D)
        # router aux AFTER the chain so the MTP blocks' MoE routers get
        # load-balancing gradient too (mean over every MoE layer that ran)
        aux = self.aux_loss(
            extra_layers=[layer.block for layer in self.mtp_layers])
        if aux is not None:
            loss = loss + self.config.router_aux_loss_coef * aux
        return loss, None


# ---------------------------------------------------------------------------
# pipeline-parallel DeepSeek (MLA + MoE under pp — the way the V2/V3
# recipes actually train: pp x ep x mp)
# ---------------------------------------------------------------------------

from .llama import LlamaDecoderLayerPipe, LlamaForCausalLMPipe  # noqa: E402


class DeepseekDecoderLayerPipe(LlamaDecoderLayerPipe):
    """One MLA(+MoE) decoder layer as a pipeline item — the shared pipe
    item with the decoder class and RoPE width (the decoupled
    qk_rope_head_dim slice) swapped."""

    decoder_cls = DeepseekV2DecoderLayer

    def _rope_dim(self):
        return self.config.qk_rope_head_dim


class DeepseekForCausalLMPipe(LlamaForCausalLMPipe):
    """Stage-partitioned DeepSeek-V2/V3 causal LM — the shared pipe
    assembly with MLA+MoE decoder layers. Train with
    ``fleet.distributed_model`` under pp_degree > 1, then
    ``pp.train_batch([ids, labels], opt)``.

    The pipeline loss is the stage-local LM loss, so the router aux term
    cannot be accumulated across stages — use aux-free balancing
    (``moe_correction_bias``, the V3 recipe) or set
    ``router_aux_loss_coef=0``; a nonzero coef raises rather than being
    silently dropped."""

    decoder_pipe_cls = DeepseekDecoderLayerPipe
    shared_embed_key = "deepseek_embed"

    def _decoder_args(self, config, layer_idx):
        return (config, layer_idx)  # first_k_dense_replace needs the index

    def _check_config(self, config):
        super()._check_config(config)
        has_moe = config.first_k_dense_replace < config.num_hidden_layers
        if has_moe and config.router_aux_loss_coef:
            raise NotImplementedError(
                "the pipeline loss cannot carry the cross-stage router aux "
                "term; use aux-free balancing (moe_correction_bias) or "
                "router_aux_loss_coef=0")
        if config.num_nextn_predict_layers:
            raise NotImplementedError(
                "multi-token prediction is a monolithic-model training "
                "objective; set num_nextn_predict_layers=0 for the "
                "pipeline layout")


def deepseek_from_hf(hf_model, config=None):
    """Convert a transformers ``DeepseekV2ForCausalLM``-style state dict.

    The HF checkpoint stores the RoPE slices (q_pe rows, the k_pe tail of
    kv_a_proj_with_mqa) in INTERLEAVED pair layout; this build's rope_ref
    uses the half-split rotate_half layout, so those output rows are
    permuted even→first-half, odd→second-half (the same de-interleave the
    ernie45 loader does).
    """
    import numpy as np

    sd = {k: np.asarray(v.detach().cpu().float().numpy())
          for k, v in hf_model.state_dict().items()}
    hc = hf_model.config
    if config is None:
        moe_layers = getattr(hc, "n_routed_experts", None) is not None
        config = DeepseekV2Config(
            vocab_size=hc.vocab_size, hidden_size=hc.hidden_size,
            intermediate_size=hc.intermediate_size,
            num_hidden_layers=hc.num_hidden_layers,
            num_attention_heads=hc.num_attention_heads,
            num_key_value_heads=hc.num_attention_heads,
            max_position_embeddings=hc.max_position_embeddings,
            rms_norm_eps=hc.rms_norm_eps, rope_theta=hc.rope_theta,
            rope_scaling=(dict(hc.rope_scaling)
                          if getattr(hc, "rope_scaling", None) else None),
            dtype="float32",
            q_lora_rank=getattr(hc, "q_lora_rank", None),
            kv_lora_rank=hc.kv_lora_rank,
            qk_nope_head_dim=hc.qk_nope_head_dim,
            qk_rope_head_dim=hc.qk_rope_head_dim,
            v_head_dim=hc.v_head_dim,
            n_routed_experts=(hc.n_routed_experts if moe_layers else 0),
            n_shared_experts=(getattr(hc, "n_shared_experts", 0) or 0),
            num_experts_per_tok=(hc.num_experts_per_tok if moe_layers else 2),
            moe_intermediate_size=getattr(hc, "moe_intermediate_size", 1408),
            first_k_dense_replace=(getattr(hc, "first_k_dense_replace", 0)
                                   if moe_layers else 10 ** 9),
            norm_topk_prob=bool(getattr(hc, "norm_topk_prob", False)),
            routed_scaling_factor=float(
                getattr(hc, "routed_scaling_factor", 1.0)),
            moe_scoring_func=str(getattr(hc, "scoring_func", "softmax")),
            moe_correction_bias=(getattr(hc, "topk_method", "")
                                 == "noaux_tc"),
            # group-limited routing (V2 group_limited_greedy / V3 noaux_tc)
            n_group=int(getattr(hc, "n_group", 1) or 1),
            topk_group=int(getattr(hc, "topk_group", 1) or 1),
            # aux-free checkpoints (noaux_tc) carry aux_loss_alpha=0; the
            # HF field is the authority, NOT this build's 0.001 default
            router_aux_loss_coef=float(
                getattr(hc, "aux_loss_alpha", 0.0) or 0.0),
            tie_word_embeddings=bool(getattr(hc, "tie_word_embeddings",
                                             False)))
    # fail at CONVERT time on unsupported/malformed rope_scaling rather
    # than lazily at the first forward (yarn parameter errors included)
    from .llama import validate_rope_scaling

    validate_rope_scaling(config.rope_scaling,
                          max_position=config.max_position_embeddings)
    model = DeepseekV2ForCausalLM(config)
    H, dn, dr = (config.num_attention_heads, config.qk_nope_head_dim,
                 config.qk_rope_head_dim)
    r = config.kv_lora_rank

    def deinterleave_rows(w, dim):
        """Permute the trailing ``dim`` output rows of a [out, in] weight
        from interleaved (x0,y0,x1,y1,...) to half-split (x...,y...)."""
        head, tail = w[:-dim], w[-dim:]
        tail = tail.reshape(dim // 2, 2, -1)
        tail = np.concatenate([tail[:, 0], tail[:, 1]], axis=0)
        return np.concatenate([head, tail], axis=0)

    def deinterleave_q(w):
        """Same permutation on each head's q_pe tail rows of a q/q_b
        projection [H*(dn+dr), in]; transpose(0,2,1,3) groups
        evens-then-odds (half-split layout)."""
        w = w.reshape(H, dn + dr, -1)
        w = np.concatenate(
            [w[:, :dn],
             w[:, dn:].reshape(H, dr // 2, 2, -1).transpose(0, 2, 1, 3)
             .reshape(H, dr, -1)], axis=1)
        return w.reshape(H * (dn + dr), -1)

    def set_(layer, value, transpose=True):
        arr = value.T if transpose else value
        layer.weight._array = jnp.asarray(arr).astype(layer.weight.dtype)

    m = model.llama
    m.embed_tokens.weight._array = jnp.asarray(
        sd.pop("model.embed_tokens.weight")).astype(
            m.embed_tokens.weight.dtype)
    m.norm.weight._array = jnp.asarray(sd.pop("model.norm.weight")).astype(
        m.norm.weight.dtype)
    if model.lm_head is not None:
        set_(model.lm_head, sd.pop("lm_head.weight"))
    for i, layer in enumerate(m.layers):
        layer = getattr(layer, "inner", layer)
        p = f"model.layers.{i}"
        attn = layer.self_attn
        if attn.q_proj is not None:
            set_(attn.q_proj,
                 deinterleave_q(sd.pop(f"{p}.self_attn.q_proj.weight")))
        else:
            set_(attn.q_a_proj, sd.pop(f"{p}.self_attn.q_a_proj.weight"))
            attn.q_a_layernorm.weight._array = jnp.asarray(
                sd.pop(f"{p}.self_attn.q_a_layernorm.weight")).astype(
                    attn.q_a_layernorm.weight.dtype)
            set_(attn.q_b_proj,
                 deinterleave_q(sd.pop(f"{p}.self_attn.q_b_proj.weight")))
        w = sd.pop(f"{p}.self_attn.kv_a_proj_with_mqa.weight")
        set_(attn.kv_a_proj_with_mqa, deinterleave_rows(w, dr))
        attn.kv_a_layernorm.weight._array = jnp.asarray(
            sd.pop(f"{p}.self_attn.kv_a_layernorm.weight")).astype(
                attn.kv_a_layernorm.weight.dtype)
        set_(attn.kv_b_proj, sd.pop(f"{p}.self_attn.kv_b_proj.weight"))
        set_(attn.o_proj, sd.pop(f"{p}.self_attn.o_proj.weight"))
        layer.input_layernorm.weight._array = jnp.asarray(
            sd.pop(f"{p}.input_layernorm.weight")).astype(
                layer.input_layernorm.weight.dtype)
        layer.post_attention_layernorm.weight._array = jnp.asarray(
            sd.pop(f"{p}.post_attention_layernorm.weight")).astype(
                layer.post_attention_layernorm.weight.dtype)
        if layer.is_moe:
            from .llama_moe import pack_hf_experts

            mlp = layer.mlp
            mlp.gate_weight._array = jnp.asarray(
                sd.pop(f"{p}.mlp.gate.weight").T).astype(
                    mlp.gate_weight.dtype)
            if mlp.e_score_correction_bias is not None:
                mlp.e_score_correction_bias._array = jnp.asarray(
                    sd.pop(f"{p}.mlp.gate.e_score_correction_bias")).astype(
                        mlp.e_score_correction_bias.dtype)

            def tk(name, transpose=False):
                w = sd.pop(name)
                return w.T if transpose else w

            w1, b1, w2, b2 = pack_hf_experts(
                tk, f"{p}.mlp", config.n_routed_experts, config.hidden_size)
            mlp.experts.w1._array = jnp.asarray(w1).astype(mlp.experts.w1.dtype)
            mlp.experts.w2._array = jnp.asarray(w2).astype(mlp.experts.w2.dtype)
            if mlp.shared_expert is not None:
                sp = f"{p}.mlp.shared_experts"
                set_(mlp.shared_expert.gate_proj,
                     sd.pop(f"{sp}.gate_proj.weight"))
                set_(mlp.shared_expert.up_proj, sd.pop(f"{sp}.up_proj.weight"))
                set_(mlp.shared_expert.down_proj,
                     sd.pop(f"{sp}.down_proj.weight"))
        else:
            set_(layer.mlp.gate_proj, sd.pop(f"{p}.mlp.gate_proj.weight"))
            set_(layer.mlp.up_proj, sd.pop(f"{p}.mlp.up_proj.weight"))
            set_(layer.mlp.down_proj, sd.pop(f"{p}.mlp.down_proj.weight"))
    return model
