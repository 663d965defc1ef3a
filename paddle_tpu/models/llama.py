"""Llama-3 model family — the flagship pretraining workload.

Reference parity: the reference trains Llama via PaddleNLP on the fleet
hybrid-parallel stack (SURVEY §2.7, CS4); this is the equivalent model
implemented on paddle_tpu's layer system with TPU-first choices:

- GQA attention with a fused Pallas flash kernel (ops/pallas/flash_attention)
  and fused rotary embeddings (ops/pallas/fused_norm.fused_rope);
- RMSNorm via the fused Pallas kernel;
- tensor/sequence parallelism via the mp/sep axes of the hybrid mesh
  (parallel layers + sharding constraints), FSDP via the sharding axis;
- bf16 weights with f32 master copies in the optimizer (framework default).

Config names follow HF/PaddleNLP llama conventions so checkpoints map 1:1.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn.layer import Layer
from ..nn.initializer_core import Normal, Constant
from ..ops.registry import apply
from ..tensor_class import Tensor, unwrap, wrap
from ..distributed.topology import get_hybrid_communicate_group
from ..distributed import parallel_layers as mpu


@dataclasses.dataclass
class LlamaConfig:
    vocab_size: int = 128256
    hidden_size: int = 4096
    intermediate_size: int = 14336
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    max_position_embeddings: int = 8192
    rms_norm_eps: float = 1e-5
    rope_theta: float = 500000.0
    initializer_range: float = 0.02
    tie_word_embeddings: bool = False
    # RoPE frequency scaling for long-context checkpoints: None, or a dict
    # like HF's rope_scaling — {"rope_type": "llama3", "factor": 8.0,
    # "low_freq_factor": 1.0, "high_freq_factor": 4.0,
    # "original_max_position_embeddings": 8192} (Llama-3.1/3.2), or
    # {"rope_type": "linear", "factor": N} (position interpolation)
    rope_scaling: Optional[dict] = None
    # bias on the q/k/v projections (Qwen2-style); o_proj stays bias-free
    attention_bias: bool = False
    # attention head width decoupled from hidden_size/num_heads (Qwen3:
    # e.g. hidden 2560, 32 heads, head_dim 128); None = the quotient
    head_dim: Optional[int] = None
    # RMSNorm on q/k after projection, before RoPE: False, True or
    # "per_head" (Qwen3 — one norm per head over head_dim), or "full"
    # (OLMo2 — one norm over the WHOLE projected width)
    qk_norm: "bool | str" = False
    # fraction of head_dim that rotates (GLM/StableLM/Phi-3-small class):
    # rope tables are built at rope_dim_of(config) width and the
    # application sites rotate only that leading slice
    partial_rotary_factor: float = 1.0
    # causal sliding-window attention (Mistral/Qwen2): each token attends
    # to at most the last `sliding_window` positions. The splash kernel
    # skips blocks outside the band (O(seq*window) work); dense fallbacks
    # apply the band mask.
    sliding_window: Optional[int] = None
    use_flash_attention: bool = True
    # attention strategy when the hybrid topology has sep_degree > 1:
    # "ring" (ppermute ring attention), "ulysses" (all-to-all head redistribution),
    # or "allgather" (let GSPMD gather k/v — the reference's SP-only behaviour)
    sep_mode: str = "ring"
    sequence_parallel: bool = False
    recompute: bool = False
    # MLP gating activation: "silu" (SwiGLU — Llama/Qwen/Mistral) or
    # "gelu_pytorch_tanh" (GeGLU — Gemma)
    hidden_act: str = "silu"
    # RMSNorm weight parameterized as (1 + w), zeros-init (Gemma): the
    # checkpoint stores the DELTA from identity, and norm output is
    # x_normed * (1 + w)
    rms_norm_offset: bool = False
    # multiply embedding output by sqrt(hidden_size) (Gemma input scaling)
    scale_embeddings: bool = False
    # attention softmax scale numerator (Gemma2): scale becomes
    # query_pre_attn_scalar**-0.5 instead of head_dim**-0.5. Implemented
    # by pre-scaling q after projection (RoPE is linear, so this is exact
    # on every attention path including the Pallas kernels)
    query_pre_attn_scalar: Optional[float] = None
    # tanh soft cap on attention logits (Gemma2): cap*tanh(scores/cap).
    # Flash falls back to the dense path; paged decode uses the exact
    # gather reference; CP refuses loudly
    attn_logit_softcapping: Optional[float] = None
    # tanh soft cap on the lm-head logits (Gemma2)
    final_logit_softcapping: Optional[float] = None
    # per-layer attention kind (Gemma2 alternation): tuple of
    # "sliding_attention"/"full_attention", one per layer — sliding layers
    # use ``sliding_window``, full layers ignore it. None = uniform.
    layer_types: Optional[tuple] = None
    # chunk the lm-head matmul + CE loss over token chunks (ops.fused_loss):
    # the [tokens, vocab] logits tensor never materializes — required to fit
    # large-vocab training shapes in one chip's HBM. forward(labels=...)
    # then returns (loss, None).
    fuse_linear_cross_entropy: bool = False
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.sep_mode not in ("ring", "ulysses", "allgather"):
            raise ValueError(
                f"sep_mode must be 'ring', 'ulysses' or 'allgather', got {self.sep_mode!r}")
        if self.hidden_act not in ("silu", "gelu_pytorch_tanh"):
            raise NotImplementedError(
                f"hidden_act must be 'silu' or 'gelu_pytorch_tanh', "
                f"got {self.hidden_act!r}")
        if self.final_logit_softcapping and self.fuse_linear_cross_entropy:
            raise NotImplementedError(
                "final_logit_softcapping cannot combine with "
                "fuse_linear_cross_entropy (the chunked-CE scan computes "
                "uncapped logits)")
        if self.qk_norm not in (False, True, "per_head", "full"):
            raise ValueError(
                f"qk_norm must be False, True, 'per_head' or 'full', "
                f"got {self.qk_norm!r}")
        if not (0.0 < self.partial_rotary_factor <= 1.0):
            raise ValueError(
                f"partial_rotary_factor must be in (0, 1], got "
                f"{self.partial_rotary_factor}")
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.num_hidden_layers:
                raise ValueError(
                    f"layer_types has {len(self.layer_types)} entries for "
                    f"{self.num_hidden_layers} layers")
            bad = set(self.layer_types) - {"sliding_attention",
                                           "full_attention"}
            if bad:
                raise ValueError(f"unknown layer_types entries: {bad}")
            if ("sliding_attention" in self.layer_types
                    and self.sliding_window is None):
                raise ValueError(
                    "layer_types requests sliding_attention but "
                    "sliding_window is not set")

    @staticmethod
    def llama3_8b(**kw):
        return LlamaConfig(**kw)

    @staticmethod
    def llama3_70b(**kw):
        base = dict(hidden_size=8192, intermediate_size=28672, num_hidden_layers=80,
                    num_attention_heads=64, num_key_value_heads=8)
        base.update(kw)
        return LlamaConfig(**base)

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                    num_hidden_layers=2, num_attention_heads=4, num_key_value_heads=2,
                    max_position_embeddings=256, dtype="float32")
        base.update(kw)
        return LlamaConfig(**base)


def layer_window(config, layer_idx: int):
    """Layer ``layer_idx``'s sliding window: the uniform config value, or
    the per-layer schedule when ``layer_types`` is set (Gemma2 alternates
    sliding/full)."""
    lt = getattr(config, "layer_types", None)
    if not lt:
        return config.sliding_window
    return (config.sliding_window if lt[layer_idx] == "sliding_attention"
            else None)


def rope_dim_of(config) -> int:
    """Width of the rotary tables: head_dim scaled by
    partial_rotary_factor, floored to even (the rotate-half split)."""
    r = int(head_dim_of(config)
            * getattr(config, "partial_rotary_factor", 1.0))
    return r - (r % 2)


def head_dim_of(config) -> int:
    """Attention head width — ``config.head_dim`` when set (Qwen3 decouples
    it from hidden/heads), else the classic quotient. The ONE derivation
    shared by the attention layer, rope tables, cache allocators, and the
    serving engine."""
    hd = getattr(config, "head_dim", None)
    return int(hd) if hd else config.hidden_size // config.num_attention_heads


def _width_norm(config, width):
    """RMSNorm over an arbitrary trailing width (per-head q/k norms, the
    MLA low-rank latents) built from the family config."""
    sub = dataclasses.replace(config, hidden_size=width)
    return LlamaRMSNorm(sub)


SUPPORTED_ROPE_SCALING = ("llama3", "linear", "yarn", "longrope")


def _rope_type(scaling: Optional[dict]):
    """None/{} → "default"; a non-empty dict WITHOUT a type key returns
    None so downstream gates refuse it (silently treating a typed-less
    scaling dict as default would drop the checkpoint's scaling)."""
    if not scaling:
        return "default"
    return scaling.get("rope_type", scaling.get("type", None))


def _hf_get(hf_config):
    """Uniform accessor over a transformers config OBJECT or a raw dict —
    the one idiom every hf_config_to_* mapper needs."""
    return (hf_config.get if isinstance(hf_config, dict)
            else lambda k, d=None: getattr(hf_config, k, d))


def mapped_rope_scaling(get) -> Optional[dict]:
    """hf_config_to_* helper: read ``rope_scaling`` through the mapper's
    ``get``, validate it at CONVERT time, and return the dict (or None)
    ready for the config kwarg — the one guard shared by every family
    mapper."""
    scaling = get("rope_scaling")
    if scaling not in (None, {}):
        validate_rope_scaling(dict(scaling),
                              max_position=get("max_position_embeddings"))
    return dict(scaling) if scaling else None


def validate_rope_scaling(scaling: Optional[dict],
                          max_position: Optional[int] = None) -> None:
    """Checkpoint-loader gate: raise at CONVERT time both for rope_scaling
    TYPES this build can't reproduce (NotImplementedError) and for
    malformed configs of supported types (yarn parameter errors surface
    here instead of lazily at the first forward)."""
    rope_type = _rope_type(scaling)
    if rope_type in ("default", "none"):
        return
    if rope_type not in SUPPORTED_ROPE_SCALING:
        raise NotImplementedError(
            f"rope_scaling type {rope_type!r} is not implemented "
            f"(supported: {', '.join(sorted(SUPPORTED_ROPE_SCALING))})")
    if rope_type == "yarn":
        # dummy dims: only the parameter handling can raise
        _yarn_params(scaling, 64, 10000.0, fallback_orig=max_position)
    if rope_type == "longrope":
        n_short = len(scaling.get("short_factor") or ())
        n_long = len(scaling.get("long_factor") or ())
        if not n_short or not n_long or n_short != n_long:
            raise ValueError(
                "longrope rope_scaling needs short_factor and long_factor "
                f"lists of equal length (got {n_short}/{n_long})")
        if not (scaling.get("original_max_position_embeddings")
                or max_position):
            raise ValueError(
                "longrope rope_scaling needs "
                "original_max_position_embeddings (or a max_position "
                "fallback) to pick between the factor lists")


def _longrope_params(scaling: dict, dim: int, base: float, seq_len: int,
                     max_position: Optional[int] = None):
    """(inv_freq [dim//2], attention_factor) per transformers
    modeling_rope_utils._compute_longrope_parameters (Phi-3 LongRoPE):
    per-dim rescaled frequencies — the short_factor list within the
    pretrained window, the long_factor list beyond it — and a
    sqrt(1 + ln(f)/ln(orig)) magnitude factor on the tables.

    The factor list is chosen by the length the tables are BUILT for
    (static under jit). transformers switches on the runtime position
    instead, re-deriving frequencies mid-request when a cached generate
    crosses the pretrained window; a table built for the request's true
    maximum length applies the long factors from the start, which keeps
    every cached position self-consistent."""
    orig = int(scaling.get("original_max_position_embeddings")
               or max_position)
    factor = scaling.get("factor")
    if max_position and orig:
        factor = max_position / orig
    att = scaling.get("attention_factor")
    if att is None:
        att = (1.0 if not factor or factor <= 1.0
               else math.sqrt(1 + math.log(factor) / math.log(orig)))
    ext = (scaling["long_factor"] if seq_len > orig
           else scaling["short_factor"])
    ext = jnp.asarray(ext, jnp.float32)
    if ext.shape[0] != dim // 2:
        raise ValueError(
            f"longrope factor lists must have head_dim/2 = {dim // 2} "
            f"entries, got {ext.shape[0]}")
    inv_freq = 1.0 / (ext * base ** (
        jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    return inv_freq, float(att)


def _yarn_get_mscale(scale: float, m: float = 1.0) -> float:
    """yarn magnitude term (0.1·m·ln(s)+1) — shared by the table factor
    and the DeepSeek softmax mscale."""
    if scale <= 1:
        return 1.0
    return 0.1 * m * math.log(scale) + 1.0


def _yarn_params(scaling: dict, dim: int, base: float,
                 fallback_orig: Optional[int] = None):
    """(inv_freq [dim//2], attention_factor) per transformers
    modeling_rope_utils._compute_yarn_parameters — NTK-by-parts blended
    interpolation/extrapolation frequencies, and the magnitude factor the
    cos/sin tables are multiplied by (the DeepSeek mscale/mscale_all_dim
    variant included). ``fallback_orig``: transformers anchors the
    correction range to max_position_embeddings when the checkpoint omits
    original_max_position_embeddings."""
    factor = float(scaling["factor"])
    orig = (scaling.get("original_max_position_embeddings")
            or fallback_orig)
    if not orig:
        raise ValueError(
            "yarn rope_scaling needs original_max_position_embeddings "
            "(or a max_position fallback) to anchor the correction range")
    orig = float(orig)

    att = scaling.get("attention_factor")
    if att is None:
        mscale = scaling.get("mscale")
        mscale_all_dim = scaling.get("mscale_all_dim")
        if mscale and mscale_all_dim:
            att = float(_yarn_get_mscale(factor, float(mscale))
                        / _yarn_get_mscale(factor, float(mscale_all_dim)))
        else:
            att = _yarn_get_mscale(factor)
    beta_fast = float(scaling.get("beta_fast") or 32)
    beta_slow = float(scaling.get("beta_slow") or 1)

    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low, high = corr_dim(beta_fast), corr_dim(beta_slow)
    if scaling.get("truncate", True):
        low, high = math.floor(low), math.ceil(high)
    low, high = max(low, 0), min(high, dim - 1)
    if low == high:
        high += 0.001  # prevent singularity
    ramp = jnp.clip(
        (jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    extrap = 1.0 - ramp                     # 1 = keep base freq (short wl)
    pos_freqs = base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inv_freq = ((1.0 / (factor * pos_freqs)) * (1.0 - extrap)
                + (1.0 / pos_freqs) * extrap)
    return inv_freq, float(att)


def _scale_inv_freq(inv_freq, scaling: Optional[dict]):
    """Apply HF-style rope_scaling to the base frequencies.

    "llama3" (transformers modeling_rope_utils._compute_llama3_parameters):
    wavelengths beyond the original context are divided by ``factor``,
    short wavelengths kept, the band between smoothly interpolated.
    "linear": classic position interpolation (all frequencies / factor).
    "yarn" depends on head_dim/theta and a cos/sin magnitude factor, so it
    is computed in _rope_tables (_yarn_params), not here.
    """
    if not scaling:
        return inv_freq
    rope_type = _rope_type(scaling)
    if rope_type in ("default", "none"):
        return inv_freq
    if rope_type not in SUPPORTED_ROPE_SCALING:
        raise NotImplementedError(
            f"rope_scaling type {rope_type!r} is not implemented "
            f"(supported: {', '.join(sorted(SUPPORTED_ROPE_SCALING))})")
    if rope_type == "yarn":
        raise ValueError(
            "yarn frequencies depend on head_dim/theta — build tables "
            "through _rope_tables(scaling=...)")
    factor = float(scaling["factor"])
    if rope_type == "linear":
        return inv_freq / factor
    if rope_type == "llama3":
        low = float(scaling["low_freq_factor"])
        high = float(scaling["high_freq_factor"])
        orig = float(scaling["original_max_position_embeddings"])
        wavelen = 2.0 * math.pi / inv_freq
        low_wavelen = orig / low
        high_wavelen = orig / high
        smooth = (orig / wavelen - low) / (high - low)
        interp = (1.0 - smooth) / factor + smooth
        scaled = jnp.where(wavelen > low_wavelen, inv_freq / factor, inv_freq)
        in_band = (wavelen <= low_wavelen) & (wavelen >= high_wavelen)
        return jnp.where(in_band, interp * inv_freq, scaled)
    raise AssertionError(rope_type)  # unreachable: gated above


def _rope_tables(seq_len, head_dim, theta, dtype=jnp.float32, scaling=None,
                 max_position=None):
    att = 1.0
    if _rope_type(scaling) == "yarn":
        inv_freq, att = _yarn_params(scaling, head_dim, theta,
                                     fallback_orig=max_position)
    elif _rope_type(scaling) == "longrope":
        inv_freq, att = _longrope_params(scaling, head_dim, theta, seq_len,
                                         max_position=max_position)
    else:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
        inv_freq = _scale_inv_freq(inv_freq, scaling)
    t = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(t, inv_freq)  # [S, D/2]
    emb = jnp.concatenate([freqs, freqs], axis=-1)  # [S, D]
    if att != 1.0:
        # yarn magnitude: cos/sin scaled by the attention factor (HF
        # convention — q·k through the tables picks up att²)
        return jnp.cos(emb) * att, jnp.sin(emb) * att
    return jnp.cos(emb), jnp.sin(emb)


class LlamaRMSNorm(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.hidden_size = config.hidden_size
        self.variance_epsilon = config.rms_norm_eps
        # Gemma parameterizes the norm weight as (1 + w) with w zeros-init
        # (identity at init either way); effective_weight() is what every
        # kernel call must consume
        self.offset = (1.0 if getattr(config, "rms_norm_offset", False)
                       else 0.0)
        self.weight = self.create_parameter(
            [config.hidden_size],
            default_initializer=Constant(0.0 if self.offset else 1.0),
            dtype=config.dtype)

    def effective_weight(self):
        return self.weight + self.offset if self.offset else self.weight

    def forward(self, x):
        from ..ops.pallas import fused_norm

        eps = self.variance_epsilon
        return apply("rms_norm", lambda a, w: fused_norm.rms_norm(a, w, eps),
                     x, self.effective_weight())


def _mp_enabled():
    hcg = get_hybrid_communicate_group()
    return hcg is not None and hcg.get_model_parallel_world_size() > 1


def _make_linear(in_f, out_f, *, column: bool, config: LlamaConfig, gather_output=False,
                 input_is_parallel=True, has_bias=False):
    from ..framework.dtype import dtype_guard

    with dtype_guard(config.dtype):  # params stored in the config dtype
        if _mp_enabled():
            if column:
                cls = (mpu.ColumnSequenceParallelLinear if config.sequence_parallel
                       else mpu.ColumnParallelLinear)
                return cls(in_f, out_f, has_bias=has_bias, gather_output=gather_output)
            cls = (mpu.RowSequenceParallelLinear if config.sequence_parallel
                   else mpu.RowParallelLinear)
            return cls(in_f, out_f, has_bias=has_bias, input_is_parallel=input_is_parallel)
        return nn.Linear(in_f, out_f, bias_attr=None if has_bias else False)


def _make_embedding(config: LlamaConfig):
    """Token embedding, vocab-parallel under mp, Normal-initialized — the
    ONE construction shared by LlamaModel and the pipeline embed stage."""
    from ..framework.dtype import dtype_guard

    with dtype_guard(config.dtype):
        if _mp_enabled() and config.vocab_size % get_hybrid_communicate_group().get_model_parallel_world_size() == 0:
            emb = mpu.VocabParallelEmbedding(config.vocab_size, config.hidden_size)
        else:
            emb = nn.Embedding(config.vocab_size, config.hidden_size)
    emb.weight._array = (
        Normal(0.0, config.initializer_range)(
            (config.vocab_size, config.hidden_size), jnp.float32)
        .astype(emb.weight.dtype))
    return emb


def _scale_embed(hidden, config):
    """Gemma input scaling: hidden * sqrt(hidden_size), with the scalar
    first rounded to the compute dtype (HF casts the normalizer to the
    hidden dtype before multiplying — bf16 parity depends on it)."""
    if not getattr(config, "scale_embeddings", False):
        return hidden
    dt = jax.dtypes.canonicalize_dtype(config.dtype)
    scale = float(np.asarray(math.sqrt(config.hidden_size)).astype(dt))
    return hidden * scale


def _make_lm_head(config: LlamaConfig):
    """Column-parallel lm head, Normal-initialized — shared by
    LlamaForCausalLM and the pipeline head stage."""
    head = _make_linear(config.hidden_size, config.vocab_size,
                        column=True, config=config, gather_output=True)
    head.weight._array = (
        Normal(0.0, config.initializer_range)(
            (config.hidden_size, config.vocab_size), jnp.float32)
        .astype(head.weight.dtype))
    return head


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.hidden_size = config.hidden_size
        self.num_heads = config.num_attention_heads
        self.num_kv_heads = config.num_key_value_heads
        self.head_dim = head_dim_of(config)
        # per-INSTANCE sliding window: defaults to the config's uniform
        # value; alternating-window families (Gemma2) set it per layer
        self.window = config.sliding_window
        # Gemma2 softmax-scale override, folded into q once after
        # projection: every downstream path divides by sqrt(head_dim), so
        # multiplying q by sqrt(head_dim)/sqrt(query_pre_attn_scalar)
        # yields the target scale exactly (RoPE is linear and commutes)
        qpas = getattr(config, "query_pre_attn_scalar", None)
        self.q_premul = (math.sqrt(self.head_dim / qpas) if qpas else None)
        bias = config.attention_bias
        self.qk_norm_mode = ("per_head" if config.qk_norm is True
                             else (config.qk_norm or None))
        if self.qk_norm_mode == "per_head":
            # Qwen3: per-head RMSNorm on q/k after projection, before RoPE
            self.q_norm = _width_norm(config, self.head_dim)
            self.k_norm = _width_norm(config, self.head_dim)
        elif self.qk_norm_mode == "full":
            # OLMo2: ONE norm over the whole projected q (and k) width
            self.q_norm = _width_norm(config,
                                      self.num_heads * self.head_dim)
            self.k_norm = _width_norm(config,
                                      self.num_kv_heads * self.head_dim)
        else:
            self.q_norm = self.k_norm = None
        self.q_proj = _make_linear(self.hidden_size, self.num_heads * self.head_dim,
                                   column=True, config=config, has_bias=bias)
        self.k_proj = _make_linear(self.hidden_size, self.num_kv_heads * self.head_dim,
                                   column=True, config=config, has_bias=bias)
        self.v_proj = _make_linear(self.hidden_size, self.num_kv_heads * self.head_dim,
                                   column=True, config=config, has_bias=bias)
        self.o_proj = _make_linear(self.num_heads * self.head_dim, self.hidden_size,
                                   column=False, config=config)

    def cached_attn_core(self, q, k, v, cos, sin, kv_cache,
                         rope_applied=False):
        """Attention against the static-shape decode cache (serving
        path): jit-stable shapes at every step. Two layouts, both with
        in-place buffer updates: dense [B,Smax,hk,d], or paged (block
        tables) matching block_multi_head_attention_kernel.cu.
        ``allowed`` is an optional [B,T] column-validity mask (padded
        prompts). ``rope_applied``: q/k arrive pre-rotated (the fused
        decode-tail kernel). Returns (out [b, s, H*D] BEFORE o_proj,
        new_cache) — split from o_proj so the fused epilogue can take
        the projection into its own kernel."""
        from ..generation import cached_attention, paged_cached_attention

        b, s = q.shape[0], q.shape[1]
        h, d = self.num_heads, self.head_dim
        cfg = self.config
        softcap = getattr(cfg, "attn_logit_softcapping", None)
        if "k_pages" in kv_cache:
            out, kp, vp = apply(
                "llama_attention_paged", paged_cached_attention,
                q, k, v, cos, sin, kv_cache["k_pages"],
                kv_cache["v_pages"], kv_cache["page_indices"],
                kv_cache["lengths"], kv_cache.get("page_size"),
                window=self.window, softcap=softcap,
                rope_applied=rope_applied, ring="ring" in kv_cache)
            new = dict(kv_cache)
            new.update(k_pages=kp, v_pages=vp,
                       lengths=kv_cache["lengths"] + s)
            return out.reshape([b, s, h * d]), new
        out, k_buf, v_buf = apply(
            "llama_attention_cached", cached_attention, q, k, v, cos, sin,
            kv_cache["k"], kv_cache["v"], kv_cache["pos"],
            kv_cache.get("allowed"), kv_cache.get("row_pos"),
            use_flash=(cfg.use_flash_attention and softcap is None),
            prefill=bool(kv_cache.get("prefill", False)),
            window=self.window, softcap=softcap,
            rope_applied=rope_applied)
        new = {"k": k_buf, "v": v_buf, "pos": kv_cache["pos"] + s}
        if "allowed" in kv_cache:
            new["allowed"] = kv_cache["allowed"]
        if "row_pos" in kv_cache:
            # per-row RoPE positions ADVANCE with each decoded token —
            # frozen positions would rotate every generated token of a
            # padded row at the same angle (review r4: ragged decode
            # diverged from the solo run from the 5th token on)
            new["row_pos"] = kv_cache["row_pos"] + s
        return out.reshape([b, s, h * d]), new

    def decode_fused_qkv(self, hidden_states, norm_weight, eps, cos, sin,
                         kv_cache):
        """Fused ``rms_norm → q/k/v → rope`` through the decode-tail
        megakernel (ops/pallas/decode_tail) — the caller has verified
        the gate (fused_decode_supported). S=1 is the classic decode
        step; an S>1 speculative-verify chunk flattens to B*S independent
        rows (the kernels are row-parallel, and each row's rope position
        is gathered per row). Returns (q, k, v) shaped like the discrete
        projections, q/k already rotated at each row's cache position."""
        from ..ops.pallas import decode_tail

        b, s = hidden_states.shape[0], hidden_states.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        cos_r, sin_r = _rope_rows_for_cache(cos, sin, kv_cache, b, s)
        q2, k2, v2 = apply(
            "fused_decode_qkv",
            lambda x2, wn, wq, wk, wv, c, s_: decode_tail.fused_qkv_rope(
                x2, wn, wq, wk, wv, c, s_, eps, h, hk, d),
            hidden_states.reshape([b * s, self.hidden_size]), norm_weight,
            self.q_proj.weight, self.k_proj.weight, self.v_proj.weight,
            cos_r, sin_r)
        return (q2.reshape([b, s, h, d]), k2.reshape([b, s, hk, d]),
                v2.reshape([b, s, hk, d]))

    def forward(self, hidden_states, cos, sin, attention_mask=None, kv_cache=None, position_offset=0):
        b, s = hidden_states.shape[0], hidden_states.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        q_flat = self.q_proj(hidden_states)
        k_flat = self.k_proj(hidden_states)
        if self.qk_norm_mode == "full":   # OLMo2: norm BEFORE head split
            q_flat = self.q_norm(q_flat)
            k_flat = self.k_norm(k_flat)
        q = q_flat.reshape([b, s, h, d])
        k = k_flat.reshape([b, s, hk, d])
        v = self.v_proj(hidden_states).reshape([b, s, hk, d])
        if self.qk_norm_mode == "per_head":
            q = self.q_norm(q)
            k = self.k_norm(k)
        if self.q_premul is not None:
            q = q * self.q_premul

        cfg = self.config
        softcap = getattr(cfg, "attn_logit_softcapping", None)

        if isinstance(kv_cache, dict):
            out_flat, new = self.cached_attn_core(q, k, v, cos, sin,
                                                  kv_cache)
            return self.o_proj(out_flat), new

        def attn_fn(q, k, v, cos, sin, *cache):
            from ..ops.pallas import fused_norm, flash_attention as pf
            from ..nn.functional.attention import _sdpa_ref

            q = fused_norm.apply_rope(q, cos, sin)
            k = fused_norm.apply_rope(k, cos, sin)
            if cache:
                k = jnp.concatenate([cache[0], k], axis=1)
                v = jnp.concatenate([cache[1], v], axis=1)
            win = self.window
            if win is not None and win <= 0:
                raise ValueError("sliding_window must be positive")
            hcg = get_hybrid_communicate_group()
            cp_active = (not cache and hcg is not None
                         and hcg.get_sep_parallel_world_size() > 1
                         and cfg.sep_mode in ("ring", "ulysses"))
            if softcap is not None and cp_active:
                raise NotImplementedError(
                    "attn_logit_softcapping under context parallelism is "
                    "not supported (the ring/Ulysses kernels compute "
                    "uncapped scores)")
            if cp_active:
                # context parallelism: sequence stays sharded over sep; k/v
                # blocks ride the ring (or heads ride an all-to-all) instead
                # of GSPMD all-gathering the whole sequence per device.
                # k/v enter UNexpanded: the CP kernels handle GQA internally,
                # so the ring moves num_kv_heads worth of bytes, not num_heads.
                import functools

                from ..distributed.collective import shard_map
                from jax.sharding import PartitionSpec as P

                from ..distributed.context_parallel import (
                    cp_mesh_axes, ring_attention, ulysses_attention)

                mesh, batch_ax, head_ax = cp_mesh_axes(hcg)
                spec = P(batch_ax, "sep", head_ax, None)
                inner = (ring_attention if cfg.sep_mode == "ring"
                         else ulysses_attention)
                cp = shard_map(
                    functools.partial(inner, axis_name="sep", causal=True,
                                      window=win),
                    mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
                    # splash-per-hop ring runs pallas_call inside the
                    # shard_map; pallas outputs carry no vma, so the vma
                    # checker must be off (the jax-documented pairing)
                    check_vma=False)
                out = cp(q, k, v)
            elif (cfg.use_flash_attention and softcap is None
                  and pf.supported(q, k, v)):
                # GQA-native splash kernel: KV stays at num_kv_heads width
                # through HBM (no _expand_gqa on the hot path)
                out = pf.flash_attention_bshd(q, k, v, causal=True, window=win)
            else:
                from ..distributed.context_parallel import _expand_gqa

                ke, ve = _expand_gqa(k, v, h)
                band = None
                if win is not None:
                    sq, sk = q.shape[1], k.shape[1]
                    off = sk - sq
                    rows = jnp.arange(sq)[:, None] + off
                    cols = jnp.arange(sk)[None, :]
                    band = ((cols <= rows) & (cols > rows - win))[None, None]
                out = _sdpa_ref(q, ke, ve, causal=band is None, mask=band,
                                softcap=softcap)
            return out.reshape(b, out.shape[1], h * d), k, v

        cache_args = [kv_cache[0], kv_cache[1]] if kv_cache is not None else []
        out, k_new, v_new = apply("llama_attention", attn_fn, q, k, v, cos, sin, *cache_args)
        result = self.o_proj(out)
        if kv_cache is not None:
            return result, (k_new, v_new)
        return result


class LlamaMLP(Layer):
    """Gated MLP: SwiGLU (silu gate — Llama) or GeGLU (tanh-gelu gate —
    Gemma), selected by ``config.hidden_act``."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.hidden_act = getattr(config, "hidden_act", "silu")
        self.gate_proj = _make_linear(config.hidden_size, config.intermediate_size,
                                      column=True, config=config)
        self.up_proj = _make_linear(config.hidden_size, config.intermediate_size,
                                    column=True, config=config)
        self.down_proj = _make_linear(config.intermediate_size, config.hidden_size,
                                      column=False, config=config)

    def forward(self, x):
        gate = self.gate_proj(x)
        up = self.up_proj(x)
        if self.hidden_act == "gelu_pytorch_tanh":
            act = apply("geglu",
                        lambda g, u: jax.nn.gelu(g, approximate=True) * u,
                        gate, up)
        else:
            act = apply("swiglu", lambda g, u: jax.nn.silu(g) * u, gate, up)
        return self.down_proj(act)


def _rope_rows_for_cache(cos, sin, kv_cache, b, s=1):
    """cos/sin rows at each row's CURRENT decode position(s), [B*S, D]
    f32 — the fused decode-tail kernel ropes in-register, so the (tiny)
    table gather happens here: paged caches decode at per-row
    ``lengths`` (token j of a speculative-verify chunk sits at
    lengths[b]+j), ragged dense at ``row_pos``, plain dense batches
    share the scalar ``pos``. ``s > 1`` is paged-only (the gate keeps
    dense chunks on the discrete path)."""
    cos_a, sin_a = unwrap(cos), unwrap(sin)
    if "k_pages" in kv_cache:
        base = jnp.asarray(unwrap(kv_cache["lengths"]), jnp.int32)
        if s == 1:
            idx = base
        else:
            idx = (base[:, None]
                   + jnp.arange(s, dtype=jnp.int32)[None, :]).reshape(-1)
    elif "row_pos" in kv_cache:
        idx = jnp.asarray(unwrap(kv_cache["row_pos"]), jnp.int32)
    else:
        pos = jnp.asarray(unwrap(kv_cache["pos"]), jnp.int32)
        c = jax.lax.dynamic_slice_in_dim(cos_a, pos, 1, 0)
        s_ = jax.lax.dynamic_slice_in_dim(sin_a, pos, 1, 0)
        return (jnp.broadcast_to(c, (b, c.shape[-1])),
                jnp.broadcast_to(s_, (b, s_.shape[-1])))
    return cos_a[idx], sin_a[idx]


def fused_decode_structural(layer, dtype) -> bool:
    """The WEIGHT-STRUCTURE half of the fused decode-tail gate: does
    this decoder layer look like what the megakernels assume — llama
    attention with no qk-norm, no q pre-multiplier, no projection
    bias, no tensor parallelism (plain ``nn.Linear``), dtype-uniform
    weights and RMSNorm scales. Shape/cache/VMEM feasibility is the
    dynamic half (``fused_decode_supported``); this half is also what
    the ``fused-coverage`` pdlint rule sweeps the model zoo with — a
    family regressing off the fused path fails that gate, not a perf
    bisect three weeks later."""
    attn = getattr(layer, "self_attn", None)
    if not isinstance(attn, LlamaAttention):
        return False
    if attn.qk_norm_mode is not None or attn.q_premul is not None:
        return False
    lins = (attn.q_proj, attn.k_proj, attn.v_proj, attn.o_proj)
    if any(type(l) is not nn.Linear or l.bias is not None for l in lins):
        return False
    if any(unwrap(l.weight).dtype != dtype for l in lins):
        return False
    norms = (getattr(layer, "input_layernorm", None),
             getattr(layer, "post_attention_layernorm", None))
    if any(not isinstance(n, LlamaRMSNorm)
           or unwrap(n.weight).dtype != dtype for n in norms):
        return False
    return True


def fused_decode_supported(layer, hidden_states, kv_cache, cos) -> bool:
    """Trace-time gate for the fused decode tail
    (FLAGS_use_fused_decode_tail): the structural predicate above on a
    dict decode cache, plus decode_tail's own VMEM-feasibility gate.
    S=1 is the classic decode step; an S>1 PAGED chunk (the engine's
    speculative verify) also qualifies — it flattens to B*S independent
    rows with per-row rope positions. Anything else keeps the discrete
    reference kernels (exact parity by construction)."""
    from ..ops.pallas import decode_tail

    if not decode_tail.enabled() or not isinstance(kv_cache, dict):
        return False
    if hidden_states.shape[1] != 1 and "k_pages" not in kv_cache:
        return False
    x = unwrap(hidden_states)
    if not fused_decode_structural(layer, x.dtype):
        return False
    attn = layer.self_attn
    return decode_tail.supported(
        x.shape[0] * x.shape[1], attn.hidden_size, attn.num_heads,
        attn.num_kv_heads, attn.head_dim, unwrap(cos).shape[-1],
        jnp.dtype(x.dtype).itemsize)


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.self_attn = LlamaAttention(config)
        self.mlp = LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)

    def _forward_fused_decode(self, hidden_states, cos, sin, kv_cache):
        """The serving decode tail as two megakernel dispatches around
        the attention kernel (ops/pallas/decode_tail): norm→qkv→rope
        fused, then o_proj→residual-add→norm fused — per-token
        activations stay in VMEM instead of 4-6 HBM round trips per
        layer. An S>1 speculative-verify chunk rides the SAME kernels as
        B*S flattened rows. Token-identical to the discrete path (tier-1
        parity test)."""
        from ..ops.pallas import decode_tail

        attn = self.self_attn
        b, s = hidden_states.shape[0], hidden_states.shape[1]
        decode_tail.announce(
            "paged" if "k_pages" in kv_cache else "dense", b * s,
            attn.hidden_size, attn.num_heads, attn.num_kv_heads,
            attn.head_dim)
        q, k, v = attn.decode_fused_qkv(
            hidden_states, self.input_layernorm.effective_weight(),
            self.input_layernorm.variance_epsilon, cos, sin, kv_cache)
        out_flat, new_cache = attn.cached_attn_core(
            q, k, v, cos, sin, kv_cache, rope_applied=True)
        eps = self.post_attention_layernorm.variance_epsilon
        normed, residual = apply(
            "fused_decode_epilogue",
            lambda a, wo, r, w: decode_tail.fused_epilogue(a, wo, r, w,
                                                           eps),
            out_flat.reshape([b * s, attn.num_heads * attn.head_dim]),
            attn.o_proj.weight,
            hidden_states.reshape([b * s, attn.hidden_size]),
            self.post_attention_layernorm.effective_weight())
        hidden_states = residual.reshape([b, s, attn.hidden_size]) + \
            self.mlp(normed.reshape([b, s, attn.hidden_size]))
        return hidden_states, new_cache

    def forward(self, hidden_states, cos, sin, attention_mask=None, kv_cache=None):
        from ..ops.pallas import fused_norm

        if kv_cache is not None and fused_decode_supported(
                self, hidden_states, kv_cache, cos):
            return self._forward_fused_decode(hidden_states, cos, sin,
                                              kv_cache)
        residual = hidden_states
        hidden_states = self.input_layernorm(hidden_states)
        if kv_cache is not None:
            hidden_states, kv_cache = self.self_attn(hidden_states, cos, sin,
                                                     attention_mask, kv_cache)
        else:
            hidden_states = self.self_attn(hidden_states, cos, sin, attention_mask)
        # fused residual-add + RMSNorm (Pallas): h = residual + attn_out is
        # written once and normed in the same HBM pass; h doubles as the next
        # residual (the block's hottest bandwidth pattern — VERDICT r2 item 1)
        eps = self.post_attention_layernorm.variance_epsilon
        hidden_states, residual = apply(
            "add_rms_norm",
            lambda a, r, w: fused_norm.add_rms_norm(a, r, w, eps),
            hidden_states, residual,
            self.post_attention_layernorm.effective_weight())
        hidden_states = residual + self.mlp(hidden_states)
        if kv_cache is not None:
            return hidden_states, kv_cache
        return hidden_states


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = _make_embedding(config)
        layers = [self._make_decoder_layer(config, i)
                  for i in range(config.num_hidden_layers)]
        if config.recompute:
            from ..distributed.recompute_layer import RecomputeLayer

            layers = [RecomputeLayer(l) for l in layers]
        self.layers = nn.LayerList(layers)
        self.norm = LlamaRMSNorm(config)
        self._rope_cache = {}

    @staticmethod
    def _make_decoder_layer(config, layer_idx):
        """Per-layer construction hook — families with per-layer structure
        (Gemma2's sandwich norms) override this. The per-layer window
        schedule (``layer_types``) is applied here for every family."""
        layer = LlamaDecoderLayer(config)
        layer.self_attn.window = layer_window(config, layer_idx)
        return layer

    def _rope_dim(self):
        """Rotary table width; MLA trunks override (RoPE rides only the
        decoupled qk_rope_head_dim slice)."""
        return rope_dim_of(self.config)

    def _rope(self, seq_len):
        if seq_len in self._rope_cache:
            return self._rope_cache[seq_len]
        cos, sin = _rope_tables(seq_len, self._rope_dim(),
                                self.config.rope_theta,
                                scaling=self.config.rope_scaling,
                                max_position=self.config.max_position_embeddings)
        pair = (wrap(cos), wrap(sin))
        # memoize only outside traces (a traced constant must not escape)
        from ..jit import is_tracing

        if not is_tracing():
            self._rope_cache[seq_len] = pair
        return pair

    def forward(self, input_ids, attention_mask=None, return_prenorm=False,
                inputs_embeds=None):
        s = (input_ids if inputs_embeds is None else inputs_embeds).shape[1]
        cos, sin = self._rope(s)
        if inputs_embeds is None:
            hidden = self.embed_tokens(input_ids)
            hidden = _scale_embed(hidden.astype(self.config.dtype),
                                  self.config)
        else:
            # multimodal path (LLaVA): embeddings already merged with image
            # features — scaling (if any) was applied at merge time
            hidden = inputs_embeds
        for layer in self.layers:
            hidden = layer(hidden, cos, sin, attention_mask)
        if return_prenorm:
            # (normed, pre-norm) — the MTP chain consumes the pre-norm
            # last-layer representation (arXiv:2412.19437 §2.2)
            return self.norm(hidden), hidden
        return self.norm(hidden)

    def forward_cached(self, input_ids, kv_caches, rope_len,
                       return_prenorm=False, inputs_embeds=None):
        """Decode-path forward over static KV caches (one dict per layer,
        see generation.cached_attention). Returns (hidden, new_caches) —
        or (normed, prenorm, new_caches) with ``return_prenorm`` (the MTP
        speculative draft consumes the pre-norm stream).
        ``inputs_embeds``: pre-merged embeddings (LLaVA prefill) — skips
        the token embedding."""
        cos, sin = self._rope(rope_len)
        if inputs_embeds is None:
            hidden = self.embed_tokens(input_ids)
            hidden = _scale_embed(hidden.astype(self.config.dtype),
                                  self.config)
        else:
            hidden = inputs_embeds
        new_caches = []
        for layer, cache in zip(self.layers, kv_caches):
            inner = getattr(layer, "inner", layer)  # unwrap RecomputeLayer
            hidden, c = inner(hidden, cos, sin, kv_cache=cache)
            new_caches.append(c)
        if return_prenorm:
            return self.norm(hidden), hidden, new_caches
        return self.norm(hidden), new_caches


class LlamaForCausalLM(Layer):
    model_cls = LlamaModel  # trunk hook (Gemma2 swaps in sandwich norms)

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.llama = type(self).model_cls(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = _make_lm_head(config)

    def lm_head_logits(self, hidden):
        if self.lm_head is None:
            logits = tied_lm_head_logits(hidden,
                                         self.llama.embed_tokens.weight)
        else:
            logits = self.lm_head(hidden)
        cap = getattr(self.config, "final_logit_softcapping", None)
        if cap:
            # Gemma2 tanh soft cap — applied HERE so every consumer
            # (training loss, generate, beam, speculative, serving) and
            # every family on the trunk (MoE included) gets it
            logits = apply("final_logit_softcap",
                           lambda x: cap * jnp.tanh(x / cap), logits)
        return logits

    def generate(self, input_ids, max_new_tokens=20, do_sample=False,
                 temperature=1.0, top_k=0, top_p=1.0, eos_token_id=None,
                 use_cache=True, attention_mask=None, paged=False,
                 page_size=16, prefill_chunk_size=None,
                 repetition_penalty=1.0, min_new_tokens=0,
                 num_beams=1, length_penalty=1.0, early_stopping=False,
                 no_repeat_ngram_size=0):
        """Batched autoregressive decode (see paddle_tpu.generation)."""
        from ..generation import generate as _generate

        return _generate(self, input_ids, max_new_tokens=max_new_tokens,
                         do_sample=do_sample, temperature=temperature,
                         top_k=top_k, top_p=top_p, eos_token_id=eos_token_id,
                         use_cache=use_cache, attention_mask=attention_mask,
                         paged=paged, page_size=page_size,
                         prefill_chunk_size=prefill_chunk_size,
                         repetition_penalty=repetition_penalty,
                         min_new_tokens=min_new_tokens, num_beams=num_beams,
                         length_penalty=length_penalty,
                         early_stopping=early_stopping,
                         no_repeat_ngram_size=no_repeat_ngram_size)

    def forward(self, input_ids, labels=None, attention_mask=None):
        hidden = self.llama(input_ids, attention_mask)
        if labels is not None and self.config.fuse_linear_cross_entropy:
            # mp note: parallel weights in this build are GLOBAL jax.Arrays
            # (vocab sharding lives in the array's NamedSharding, GSPMD
            # partitions the contraction), so the fused op computes the
            # full-vocab logsumexp under mp too — mp2 training-trajectory
            # parity is tested for both the ColumnParallel head and the
            # tied VocabParallel embedding (tests/test_fused_loss.py).
            # sequence_parallel heads are NOT verified with the chunked
            # scan and fall through to the (correct) logits path, as do
            # swapped heads (WeightOnlyLinear, LoRALinear, ...) whose
            # logits come from their own forward
            head_ok = (not self.config.sequence_parallel
                       and (self.lm_head is None
                            or isinstance(self.lm_head,
                                          (nn.Linear,
                                           mpu.ColumnParallelLinear))))
            if head_ok:
                from ..ops.fused_loss import fused_linear_cross_entropy

                if self.lm_head is None:  # tied: embedding weight [vocab, hidden]
                    w, layout = self.llama.embed_tokens.weight, "vh"
                else:
                    w, layout = self.lm_head.weight, "hv"
                loss = apply(
                    "fused_linear_cross_entropy",
                    lambda h, ww, lb: fused_linear_cross_entropy(h, ww, lb,
                                                                 layout),
                    hidden, w, labels)
                return loss, None
        logits = self.lm_head_logits(hidden)
        if labels is None:
            return logits
        return causal_lm_loss(logits, labels), logits

    def num_parameters(self) -> int:
        return sum(p.size for p in self.parameters())


def tied_lm_head_logits(hidden, embed_weight):
    """Project with the shared embedding weight [vocab, hidden] — the ONE
    tied-head contraction used by every tied causal LM (Llama family,
    GPT-2, the pipeline head stage)."""
    return apply("tied_lm_head", lambda h, w: h @ w.T, hidden, embed_weight)


def causal_lm_loss(logits, labels):
    """Token-mean causal-LM cross entropy in f32; labels < 0 are ignored
    (the loss the reference's PaddleNLP criterion computes)."""
    def loss_fn(lg, lb):
        lg32 = lg.astype(jnp.float32)
        logp = jax.nn.log_softmax(lg32, axis=-1)
        idx = lb.astype(jnp.int32)
        mask = idx >= 0
        safe = jnp.where(mask, idx, 0)
        nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
        nll = jnp.where(mask, nll, 0.0)
        return jnp.sum(nll) / jnp.maximum(jnp.sum(mask.astype(jnp.float32)), 1.0)

    return apply("causal_lm_loss", loss_fn, logits, labels)


# ---------------------------------------------------------------------------
# pipeline-parallel Llama (the PaddleNLP LlamaForCausalLMPipe pattern)
# ---------------------------------------------------------------------------

from ..distributed.pipeline import LayerDesc, PipelineLayer  # noqa: E402


class LlamaEmbeddingPipe(Layer):
    """First pipeline stage: token embedding (vocab-parallel under mp).
    With tie_word_embeddings it is ALSO the head stage's shared layer
    (SharedLayerDesc) — `_tied_head_forward` projects with the same weight."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.config = config
        self.embed_tokens = _make_embedding(config)

    def forward(self, input_ids):
        return _scale_embed(self.embed_tokens(input_ids)
                            .astype(self.config.dtype), self.config)


def _tied_head_forward(layer: "LlamaEmbeddingPipe", hidden):
    """Head forward over the SHARED embedding weight (tied lm head)."""
    return tied_lm_head_logits(hidden, layer.embed_tokens.weight)


class LlamaDecoderLayerPipe(Layer):
    """One decoder layer as a pipeline item: computes its own RoPE tables
    from the activation's seq length (constant-folded by XLA inside the
    stage jit) so only [B, S, H] crosses stage boundaries.

    Subclass hooks: ``decoder_cls`` (the wrapped layer class, given
    ``(config, *extra_args)``) and ``_rope_dim`` (table width — MLA
    families rope only their decoupled slice)."""

    decoder_cls = LlamaDecoderLayer

    def __init__(self, config: LlamaConfig, *layer_args):
        super().__init__(dtype=config.dtype)
        self.config = config
        layer = type(self).decoder_cls(config, *layer_args)
        if config.recompute:
            from ..distributed.recompute_layer import RecomputeLayer

            layer = RecomputeLayer(layer)
        self.layer = layer

    def _rope_dim(self):
        return rope_dim_of(self.config)

    def forward(self, hidden):
        cfg = self.config
        cos, sin = _rope_tables(hidden.shape[1], self._rope_dim(),
                                cfg.rope_theta, scaling=cfg.rope_scaling,
                                max_position=cfg.max_position_embeddings)
        return self.layer(hidden, wrap(cos), wrap(sin))


class LlamaNormHeadPipe(Layer):
    """Last pipeline stage: final RMSNorm + (untied) lm head."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.norm = LlamaRMSNorm(config)
        self.lm_head = _make_lm_head(config)

    def forward(self, hidden):
        return self.lm_head(self.norm(hidden))


class LlamaNormPipe(Layer):
    """Final RMSNorm alone (tied-head layout: the head is the shared
    embedding layer that follows this item)."""

    def __init__(self, config: LlamaConfig):
        super().__init__(dtype=config.dtype)
        self.norm = LlamaRMSNorm(config)

    def forward(self, hidden):
        return self.norm(hidden)


class LlamaForCausalLMPipe(PipelineLayer):
    """Stage-partitioned Llama causal LM (PaddleNLP LlamaForCausalLMPipe
    pattern over this build's PipelineLayer/PipelineParallel runtime).

    Train with ``fleet.distributed_model(model)`` under an hcg with
    pp_degree > 1 — each stage's mp/sharding placements ride its submesh
    (pipeline.py hybrid mode) — then ``pp.train_batch([ids, labels], opt)``
    with ``labels`` already shifted (same contract as LlamaForCausalLM).

    Subclass hooks (the DeepSeek pipe reuses this assembly verbatim):
    ``decoder_pipe_cls``, ``shared_embed_key``, ``_decoder_args`` (extra
    per-layer ctor args) and ``_check_config`` (family guards).
    """

    decoder_pipe_cls = LlamaDecoderLayerPipe
    shared_embed_key = "llama_embed"

    def _decoder_args(self, config, layer_idx):
        return (config,)

    def _check_config(self, config):
        if config.fuse_linear_cross_entropy:
            # the pipeline head stage emits full logits into the pipeline
            # loss; honoring the flag would need a fused head+loss stage —
            # raise rather than silently skip the memory saving
            raise NotImplementedError(
                "fuse_linear_cross_entropy is not supported by the pipeline "
                f"head stage; unset the flag for {type(self).__name__}")
        if getattr(config, "layer_types", None):
            # pipe decoder items are index-free LayerDescs; honoring the
            # schedule needs per-item window plumbing — raise rather than
            # silently attend full/sliding on the wrong layers
            raise NotImplementedError(
                "the per-layer window schedule (layer_types) is not "
                f"supported under {type(self).__name__}")
        if getattr(config, "final_logit_softcapping", None):
            # the pipe head stages project with the raw weight (no
            # lm_head_logits hook)
            raise NotImplementedError(
                "final_logit_softcapping is not supported by the pipeline "
                f"head stage of {type(self).__name__}")

    def __init__(self, config: LlamaConfig, num_stages=None,
                 seg_method=None, **pipe_kwargs):
        cls = type(self)
        if seg_method is None:
            seg_method = f"layer:{cls.decoder_pipe_cls.__name__}"
        self._check_config(config)
        if num_stages is None:
            hcg = get_hybrid_communicate_group()
            num_stages = (hcg.get_pipe_parallel_world_size()
                          if hcg is not None else 1)
        decoders = [LayerDesc(cls.decoder_pipe_cls,
                              *self._decoder_args(config, i))
                    for i in range(config.num_hidden_layers)]
        if config.tie_word_embeddings:
            from ..distributed.pipeline import SharedLayerDesc

            descs = ([SharedLayerDesc(cls.shared_embed_key,
                                      LlamaEmbeddingPipe,
                                      None, "weight", config)]
                     + decoders
                     + [LayerDesc(LlamaNormPipe, config),
                        SharedLayerDesc(cls.shared_embed_key,
                                        LlamaEmbeddingPipe,
                                        _tied_head_forward, "weight",
                                        config)])
        else:
            descs = ([LayerDesc(LlamaEmbeddingPipe, config)]
                     + decoders
                     + [LayerDesc(LlamaNormHeadPipe, config)])
        super().__init__(descs, num_stages=num_stages,
                         loss_fn=causal_lm_loss, seg_method=seg_method,
                         **pipe_kwargs)
        self.config = config


# ---------------------------------------------------------------------------
# HuggingFace checkpoint interop
# ---------------------------------------------------------------------------

def _hf_to_np(v):
    try:
        import torch

        if isinstance(v, torch.Tensor):
            return v.detach().to(torch.float32).cpu().numpy()
    except ImportError:  # pragma: no cover
        pass
    return np.asarray(v)


def hf_config_to_llama(hf_config, **overrides) -> LlamaConfig:
    """Map a transformers LlamaConfig (object or dict) onto LlamaConfig."""
    get = _hf_get(hf_config)
    # a Gemma checkpoint has EXACTLY Llama's key layout, so loading it
    # through the plain-llama mapper would succeed and silently compute
    # garbage ((1+w)-delta norms read as full weights, unscaled embeddings,
    # silu instead of geglu) — refuse unless the Gemma knobs arrive via
    # overrides (gemma_from_hf sets them)
    if (str(get("model_type", "")).startswith("gemma")
            and "rms_norm_offset" not in overrides):
        raise NotImplementedError(
            "this checkpoint is a Gemma-family model — convert it with "
            "gemma_from_hf (llama_from_hf would misread its (1+w) norm "
            "deltas and unscaled embeddings)")
    # type + parameter gate at CONVERT time (yarn math errors included)
    scaling = mapped_rope_scaling(get)
    # HF Llama's attention_bias puts bias on q/k/v AND o; this build only
    # represents q/k/v bias (the Qwen2 layout) — map the Qwen2-style flag,
    # refuse a checkpoint that would carry an o_proj bias
    window = None
    if get("use_sliding_window", get("sliding_window") is not None
           and get("model_type") == "mistral"):
        window = get("sliding_window")
        # HF Qwen2 applies the window only to layers >= max_window_layers;
        # this build's window is uniform — a mixed-layer checkpoint loaded
        # uniformly would silently compute different logits than its
        # reference, so refuse it (0 = every layer windowed is exact)
        mwl = get("max_window_layers", 0) or 0
        if 0 < mwl < get("num_hidden_layers"):
            raise NotImplementedError(
                f"hf_config_to_llama: per-layer sliding window "
                f"(max_window_layers={mwl}) is not supported — this build "
                "applies sliding_window uniformly")
        if mwl >= get("num_hidden_layers"):
            window = None  # no layer is windowed in the HF semantics
    kw = dict(
        vocab_size=get("vocab_size"),
        hidden_size=get("hidden_size"),
        intermediate_size=get("intermediate_size"),
        num_hidden_layers=get("num_hidden_layers"),
        num_attention_heads=get("num_attention_heads"),
        num_key_value_heads=get("num_key_value_heads",
                                get("num_attention_heads")),
        max_position_embeddings=get("max_position_embeddings"),
        rms_norm_eps=get("rms_norm_eps", 1e-5),
        rope_theta=get("rope_theta", 10000.0),
        rope_scaling=scaling,
        tie_word_embeddings=bool(get("tie_word_embeddings", False)),
        attention_bias=bool(get("attention_bias",
                                get("model_type") == "qwen2")),
        head_dim=get("head_dim"),
        partial_rotary_factor=float(get("partial_rotary_factor") or 1.0),
        sliding_window=window,
    )
    kw.update(overrides)
    return LlamaConfig(**kw)


#: the classic per-layer norm pair of the Llama key layout (OLMo2 swaps
#: in its post-only pair, Gemma2 appends its sandwich norms)
_DEFAULT_LAYER_NORMS = ("input_layernorm", "post_attention_layernorm")


def _hf_llama_plan(model, extra_layer_norms=(), layer_norms=None):
    """{our param name: (hf key, transpose)} for the Llama key layout —
    the ONE mapping shared by the loader and the reverse exporter. The
    (untied) lm head maps to "lm_head.weight"; loaders may redirect its
    source for tied-in-HF checkpoints. ``layer_norms=None`` resolves to
    the classic pair here (the single source of that default)."""
    if layer_norms is None:
        layer_norms = _DEFAULT_LAYER_NORMS
    L = model.config.num_hidden_layers
    plan = {"llama.embed_tokens.weight": ("model.embed_tokens.weight", False),
            "llama.norm.weight": ("model.norm.weight", False)}
    for i in range(L):
        hf, ours = f"model.layers.{i}", f"llama.layers.{i}"
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            plan[f"{ours}.self_attn.{proj}.weight"] = (
                f"{hf}.self_attn.{proj}.weight", True)
        if model.config.qk_norm:
            for norm in ("q_norm", "k_norm"):  # per-head RMSNorm (Qwen3)
                plan[f"{ours}.self_attn.{norm}.weight"] = (
                    f"{hf}.self_attn.{norm}.weight", False)
        if model.config.attention_bias:
            for proj in ("q_proj", "k_proj", "v_proj"):  # o_proj stays bias-free
                plan[f"{ours}.self_attn.{proj}.bias"] = (
                    f"{hf}.self_attn.{proj}.bias", False)
        for proj in ("gate_proj", "up_proj", "down_proj"):
            plan[f"{ours}.mlp.{proj}.weight"] = (f"{hf}.mlp.{proj}.weight", True)
        for norm in tuple(layer_norms) + tuple(extra_layer_norms):
            # default: the classic input/post_attention pair; Gemma2 adds
            # its sandwich norms; OLMo2 swaps in its post-only pair
            plan[f"{ours}.{norm}.weight"] = (f"{hf}.{norm}.weight", False)
    if model.lm_head is not None:
        plan["lm_head.weight"] = ("lm_head.weight", True)
    return plan


def export_hf_llama(model: "LlamaForCausalLM", extra_layer_norms=(),
                    layer_norms=None, dtype=None):
    """The reverse of load_hf_llama: this model's weights as an
    HF-key-layout numpy state dict (torch [out, in] projection layout),
    ready for ``HFModel.load_state_dict`` via torch.from_numpy — train
    here, deploy anywhere. Tied models omit lm_head.weight (HF re-ties
    from the embedding). Round-trip parity is tested per family.

    Dtype: each tensor keeps the PARAMETER's dtype (a bf16 model exports
    a bf16 checkpoint at half the bytes of the old unconditional float32
    upcast — note bf16 arrays carry the ``ml_dtypes`` numpy dtype, which
    recent torch/safetensors understand; pass ``dtype="float32"`` for
    consumers that don't). ``dtype`` forces a uniform cast when set."""
    plan = _hf_llama_plan(model, extra_layer_norms=extra_layer_norms,
                          layer_norms=layer_norms)
    params = dict(model.named_parameters())
    out = {}
    for name, (hf_key, transpose) in plan.items():
        if name not in params:
            raise KeyError(f"export_hf_llama: model has no param {name!r}")
        v = np.asarray(unwrap(params[name]))
        if dtype is not None:
            v = v.astype(dtype)
        out[hf_key] = v.T if transpose else v
    return out


def load_hf_llama(model: "LlamaForCausalLM", hf_state_dict,
                  extra_layer_norms=(), layer_norms=None,
                  ignore_missing_prefixes=()) -> "LlamaForCausalLM":
    """Load a HuggingFace Llama checkpoint's state dict into ``model``.

    Accepts torch tensors or arrays. torch ``nn.Linear`` stores weights
    [out, in]; this build stores [in, out] (paddle convention), so every
    projection transposes. Config names follow HF conventions, so the key
    mapping is mechanical (docstring contract in the module header).
    """
    plan = _hf_llama_plan(model, extra_layer_norms=extra_layer_norms,
                          layer_norms=layer_norms)
    tied_alias = set()
    if model.lm_head is not None:
        if "lm_head.weight" not in hf_state_dict:
            # tied-in-HF checkpoint feeding an untied model
            plan["lm_head.weight"] = ("model.embed_tokens.weight", True)
    else:
        # tied model: an HF checkpoint may still carry the lm_head alias of
        # the embedding — represented here through the tie, not a drop
        tied_alias.add("lm_head.weight")

    # convert ONE tensor at a time (an 8B checkpoint converted wholesale
    # would double peak host memory) and remap; set_state_dict then reuses
    # the framework's shape-checked, dtype-cast assignment
    mapped, consumed = {}, set()
    for name, (hf_key, transpose) in plan.items():
        if hf_key not in hf_state_dict:
            raise KeyError(f"load_hf_llama: checkpoint is missing {hf_key!r}")
        v = _hf_to_np(hf_state_dict[hf_key])
        mapped[name] = v.T if transpose else v
        consumed.add(hf_key)
    leftovers = [k for k in hf_state_dict
                 if k not in consumed and k not in tied_alias
                 and not k.endswith("rotary_emb.inv_freq")]
    if leftovers:
        raise ValueError(
            f"load_hf_llama: checkpoint tensors this model cannot represent "
            f"(silently dropping them would change logits): {leftovers[:5]}"
            f"{'...' if len(leftovers) > 5 else ''}")
    missing, unexpected = model.set_state_dict(mapped)
    assert not unexpected, unexpected  # plan keys come from named_parameters
    if ignore_missing_prefixes:
        # multimodal wrappers (LLaVA) load their non-language submodules
        # through their own plan; those keys are legitimately absent here
        missing = [m for m in missing
                   if not m.startswith(tuple(ignore_missing_prefixes))]
    if missing:
        raise KeyError(f"load_hf_llama: model keys not covered: {missing[:5]}")
    return model


def _from_hf(config_cls, model_cls, hf_model_or_state, hf_config=None,
             extra_layer_norms=(), layer_norms=None, **config_overrides):
    """Shared HF-conversion protocol for the Llama-architecture families
    (Llama / Qwen2 / Mistral): unwrap model vs raw state, map the config,
    build, load."""
    import dataclasses as _dc

    if hf_config is None:
        hf_config = hf_model_or_state.config
        state = hf_model_or_state.state_dict()
    else:
        state = hf_model_or_state
    base = hf_config_to_llama(hf_config, **config_overrides)
    cfg = base if config_cls is LlamaConfig else config_cls(**_dc.asdict(base))
    return load_hf_llama(model_cls(cfg), state,
                         extra_layer_norms=extra_layer_norms,
                         layer_norms=layer_norms)


def llama_from_hf(hf_model_or_state, hf_config=None, **config_overrides):
    """Build a LlamaForCausalLM from a transformers model (or a raw state
    dict + config): ``llama_from_hf(HFLlama.from_pretrained(...))``."""
    return _from_hf(LlamaConfig, LlamaForCausalLM, hf_model_or_state,
                    hf_config, **config_overrides)


def llama_to_hf(model, dtype=None):
    """Export to the HF Llama checkpoint layout (see export_hf_llama) —
    covers every family whose checkpoint IS the plain Llama key layout
    (Llama/Qwen2/Qwen3/Mistral/Gemma; Gemma2 adds its sandwich norms).
    Families whose conversion TRANSFORMS the checkpoint (Phi-3 fuses
    projections, GLM de-interleaves rotary rows) REFUSE — exporting their
    runtime weights under HF keys without reversing the transform would
    emit a silently wrong checkpoint. Parameter dtypes are preserved
    (``dtype`` forces a uniform cast — see export_hf_llama)."""
    from .gemma2 import Gemma2ForCausalLM
    from .glm import GlmForCausalLM
    from .olmo2 import _OLMO2_NORMS, Olmo2ForCausalLM
    from .phi3 import Phi3ForCausalLM

    if isinstance(model, (GlmForCausalLM, Phi3ForCausalLM)):
        raise NotImplementedError(
            f"llama_to_hf: {type(model).__name__} checkpoints are "
            "TRANSFORMED at load (fused projections / interleaved "
            "rotary); the reverse transform is not implemented — "
            "exporting raw runtime weights would be silently wrong")
    extra, norms = (), None
    if isinstance(model, Gemma2ForCausalLM):
        extra = ("pre_feedforward_layernorm", "post_feedforward_layernorm")
    if isinstance(model, Olmo2ForCausalLM):
        norms = _OLMO2_NORMS
    return export_hf_llama(model, extra_layer_norms=extra,
                           layer_norms=norms, dtype=dtype)
