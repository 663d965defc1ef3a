"""Cohere2-MoE causal LM (``model_type: cohere2_moe``; Command A+), the
language model only, on the ``models/llama.py`` trunk.

What the published ``config.json`` states, and how each key is read:

- ``use_parallel_block``: ONE LayerNorm per block (``layer_norm_eps``,
  mean-subtracting, no bias) feeds attention and the expert layer side by
  side, and both add to the residual: ``x + Attn(u) + MoE(u)``,
  ``u = LayerNorm(x)``.
- ``layer_types`` / ``sliding_window``: a ``sliding_attention`` layer sees
  keys ``i - window < j <= i`` and rotates q and k (``rope_gptj``: ADJACENT
  pairs ``(x0, x1), (x2, x3)...``, theta ``rope_theta`` over the whole head);
  a ``full_attention`` layer is plain causal and carries NO rotation
  (Cohere2's convention; ``assumed``, the config has no key for it).
- ``num_experts`` / ``num_experts_per_tok`` / ``expert_selection_fn:
  sigmoid`` / ``norm_topk_prob``: sigmoid scores over all experts in f32,
  the k largest, weights normalised over the k. One expert is a SwiGLU of
  width ``intermediate_size`` (``assumed``: the config has no key of its
  own for it).
- ``num_shared_experts`` with ``shared_expert_combination_strategy:
  average``: that many always-on experts of the same form whose MEAN is
  added to the routed sum (``assumed`` from the strategy's name). They are
  held as one SwiGLU of ``num_shared_experts x intermediate_size`` columns,
  whose output is their sum.
- ``tie_word_embeddings`` with ``logit_scale``: the head is the embedding,
  its logits times ``logit_scale``.
- No ``initializer_range``: every matrix, embedding included, is drawn
  from Normal(0, 0.02) (``assumed``).

``first_k_dense_replace`` > 0 (prefix dense layers), q/k norms and biases
are refused: the published row has none.

**A held share.** ``num_experts`` is the number of experts THIS model
holds; ``published_num_experts`` the width the router scores (default the
same) and ``held_experts = [lo, hi)`` the range held (default the first
``num_experts``). A model of 16 of 128 is one chip's share of an 8-way
expert-parallel deployment: it routes over all 128 and adds what its 16
contribute (``models/llama_moe.MoEMLP``).

**On the chip.** The rotation in adjacent pairs is the trunk's half-split
rotation after one fixed permutation of each head's lanes, applied to q
and k alike (their product does not see it), here as a matmul with a
128 x 128 permutation matrix: exact in bf16, MXU work, no strided lane
access; the cache holds k in that order. The residual stream and the
LayerNorm stay in f32 between blocks and the router scores the f32 norm
output: which expert runs is a comparison of near-equal scores.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from .. import nn
from ..nn import initializer
from ..nn.initializer import Constant, Normal
from ..nn.layer import Layer
from ..ops.registry import apply
from .llama import (LlamaAttention, LlamaForCausalLM, LlamaModel,
                    head_dim_of, layer_window)
from .llama_moe import (LlamaMoEConfig, MoEMLP, valid_rows,
                        with_moe_counts)


@dataclasses.dataclass
class Cohere2MoEConfig(LlamaMoEConfig):
    """The published keys under their published names; ``__post_init__``
    maps them onto the trunk's."""

    layer_norm_eps: float = 1e-5
    logit_scale: float = 1.0
    num_experts: int = 128
    published_num_experts: Optional[int] = None
    num_shared_experts: int = 4
    expert_selection_fn: str = "sigmoid"
    shared_expert_combination_strategy: str = "average"
    use_parallel_block: bool = True
    position_embedding_type: str = "rope_gptj"
    use_qk_norm: bool = False
    tie_word_embeddings: bool = True
    first_k_dense_replace: int = 0

    def __post_init__(self):
        if not self.use_parallel_block:
            raise NotImplementedError("cohere2_moe: only the parallel block")
        if self.position_embedding_type != "rope_gptj":
            raise NotImplementedError(
                f"cohere2_moe: position_embedding_type "
                f"{self.position_embedding_type!r} (only rope_gptj)")
        if self.use_qk_norm or self.attention_bias:
            raise NotImplementedError("cohere2_moe: no q/k norm, no bias")
        if self.shared_expert_combination_strategy != "average":
            raise NotImplementedError(
                "cohere2_moe: shared experts are averaged")
        self.first_k_dense_replace = int(self.first_k_dense_replace or 0)
        if self.first_k_dense_replace:
            raise NotImplementedError("cohere2_moe: no prefix dense layers")
        self.n_routed_experts = int(self.published_num_experts
                                    or self.num_experts)
        if self.held_experts is None:
            self.held_experts = (0, int(self.num_experts))
        self.held_experts = tuple(int(v) for v in self.held_experts)
        if self.held_experts[1] - self.held_experts[0] != self.num_experts:
            raise ValueError(
                f"held_experts {self.held_experts} is not num_experts "
                f"({self.num_experts}) wide")
        self.n_shared_experts = int(self.num_shared_experts)
        self.moe_intermediate_size = int(self.intermediate_size)
        self.moe_scoring_func = self.expert_selection_fn
        self.rms_norm_eps = float(self.layer_norm_eps)
        super().__post_init__()

    @staticmethod
    def tiny(**kw):
        base = dict(vocab_size=256, hidden_size=128, intermediate_size=64,
                    num_hidden_layers=4, num_attention_heads=8,
                    num_key_value_heads=2, head_dim=16,
                    max_position_embeddings=256, sliding_window=32,
                    layer_types=("sliding_attention",) * 3
                    + ("full_attention",),
                    num_experts=8, num_experts_per_tok=2,
                    num_shared_experts=2, rope_theta=50000.0,
                    dtype="float32")
        base.update(kw)
        return Cohere2MoEConfig(**base)


class Cohere2LayerNorm(Layer):
    """``(x - mean) / sqrt(var + eps) * g`` in f32, no bias; the output
    stays f32 unless ``out_dtype`` says otherwise."""

    def __init__(self, config, out_dtype=None):
        super().__init__(dtype=config.dtype)
        self.eps = float(config.layer_norm_eps)
        self.out_dtype = out_dtype
        self.weight = self.create_parameter(
            [config.hidden_size], attr=Constant(1.0), dtype=config.dtype)

    def forward(self, x):
        def norm(a, g):
            a = a.astype(jnp.float32)
            mean = a.mean(-1, keepdims=True)
            var = jnp.square(a - mean).mean(-1, keepdims=True)
            out = (a - mean) * jax.lax.rsqrt(var + self.eps) * g.astype(
                jnp.float32)
            return out if self.out_dtype is None else out.astype(
                self.out_dtype)

        return apply("cohere2_layer_norm", norm, x, self.weight)


def _pairs_to_halves(d: int):
    """P with ``(x @ P)[j] = x[perm[j]]``, perm = evens then odds: adjacent
    rotary pairs become the trunk's half-split pairs."""
    perm = np.concatenate([np.arange(0, d, 2), np.arange(1, d, 2)])
    p = np.zeros((d, d), np.float32)
    p[perm, np.arange(d)] = 1.0
    return p


class Cohere2MoEAttention(LlamaAttention):
    """The trunk's GQA attention; ``rotates`` (window layers) permutes q
    and k into the half-split order the trunk's rotary code expects,
    global layers skip the rotation."""

    def __init__(self, config, layer_idx: int):
        super().__init__(config)
        self.window = layer_window(config, layer_idx)
        self.rotates = self.window is not None
        self._perm = _pairs_to_halves(self.head_dim)

    def forward(self, hidden_states, cos, sin, kv_cache):
        b, s = hidden_states.shape[0], hidden_states.shape[1]
        h, hk, d = self.num_heads, self.num_kv_heads, self.head_dim
        q = self.q_proj(hidden_states).reshape([b, s, h, d])
        k = self.k_proj(hidden_states).reshape([b, s, hk, d])
        v = self.v_proj(hidden_states).reshape([b, s, hk, d])
        if self.rotates:
            def to_halves(a):
                return jnp.einsum("...d,de->...e", a,
                                  jnp.asarray(self._perm, a.dtype))

            q = apply("rope_pairs_to_halves", to_halves, q)
            k = apply("rope_pairs_to_halves", to_halves, k)
        with jax.named_scope("attn/window" if self.rotates
                             else "attn/global"):
            out, new = self.cached_attn_core(q, k, v, cos, sin, kv_cache,
                                             rope_applied=not self.rotates)
        return self.o_proj(out), new


class Cohere2MoEDecoderLayer(Layer):
    """``x + Attn(u) + MoE(u)``, ``u = LayerNorm(x)``: the residual and
    the norm in f32, both branches fed the model's dtype, the router the
    f32 norm output."""

    is_moe = True

    def __init__(self, config, layer_idx: int):
        super().__init__(dtype=config.dtype)
        self.input_layernorm = Cohere2LayerNorm(config)
        self.self_attn = Cohere2MoEAttention(config, layer_idx)
        # the shared experts are held as one SwiGLU whose output is their
        # SUM: the published strategy is their mean
        self.mlp = MoEMLP(config,
                          shared_scale=1.0 / config.num_shared_experts)
        self._dtype_name = config.dtype

    def forward(self, hidden_states, cos, sin, attention_mask=None,
                kv_cache=None):
        if attention_mask is not None:
            raise NotImplementedError(
                "cohere2_moe: padded batches (attention_mask) are not "
                "supported; serve through ContinuousBatchEngine")
        b, s = hidden_states.shape[0], hidden_states.shape[1]
        cache = kv_cache
        if cache is None:
            # no cache given (a plain forward): attend through a throwaway
            # one of the sequence's own length
            cfg = self.self_attn.config
            shape = (b, s, cfg.num_key_value_heads, head_dim_of(cfg))
            cache = {"k": jnp.zeros(shape, self._dtype_name),
                     "v": jnp.zeros(shape, self._dtype_name),
                     "pos": 0, "prefill": True}
        x = hidden_states.astype("float32")
        u32 = self.input_layernorm(x)
        u = u32.astype(self._dtype_name)
        attn, new = self.self_attn(u, cos, sin, cache)
        valid = valid_rows(cache, s)
        moe, counts = self.mlp.forward_counted(u, router_input=u32,
                                               valid=valid)
        out = x + attn.astype("float32") + moe.astype("float32")
        if kv_cache is None:
            return out
        return out, with_moe_counts(new, valid, counts)


class Cohere2MoEModel(LlamaModel):
    def __init__(self, config: Cohere2MoEConfig):
        shell = dataclasses.replace(config, num_hidden_layers=0,
                                    layer_types=None)
        super().__init__(shell)
        self.config = config
        self.layers = nn.LayerList(
            [Cohere2MoEDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])
        self.norm = Cohere2LayerNorm(config, out_dtype=config.dtype)


class Cohere2MoEForCausalLM(LlamaForCausalLM):
    model_cls = Cohere2MoEModel

    def __init__(self, config: Cohere2MoEConfig):
        if not config.tie_word_embeddings:
            raise NotImplementedError("cohere2_moe: the head is tied")
        # the config names no initializer: every matrix (projections,
        # experts, router) is drawn like the embedding
        before = (initializer._global_initializer(False),
                  initializer._global_initializer(True))
        initializer.set_global_initializer(
            Normal(0.0, config.initializer_range), before[1])
        try:
            super().__init__(config)
        finally:
            initializer.set_global_initializer(*before)

    def lm_head_logits(self, hidden):
        logits = super().lm_head_logits(hidden)
        scale = float(self.config.logit_scale)
        return logits if scale == 1.0 else logits * scale
