"""MoE causal LM — the DeepSeekMoE / Qwen2-MoE decoder family.

Reference anchors: the fused MoE machinery the reference serves these models
with (paddle/phi/kernels/fusion/cutlass/fused_moe_kernel.cu, the
moe_gate_dispatch SPMD rule paddle/phi/infermeta/spmd_rules/moe_gate_dispatch.cc,
python/paddle/incubate/distributed/models/moe/moe_layer.py:263) and the
DeepSeekMoE/Qwen2-MoE configs named in BASELINE.json.

Architecture (DeepSeekMoE): a Llama-style decoder where every layer past
``first_k_dense_replace`` swaps the dense SwiGLU MLP for
- ``n_routed_experts`` fine-grained routed experts (top-k, softmax-normalized
  combine weights) implemented as a GroupedMLP (grouped GEMM, EP-shardable), plus
- ``n_shared_experts`` always-on shared experts (one fused SwiGLU).

TPU-native: routing/dispatch runs as one pure stage (dense GShard dispatch
einsums — MXU-friendly, GSPMD-shardable over the ep axis); the attention
block and norms are reused from models/llama.py unchanged.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from ..nn.layer import Layer
from .. import nn
from ..nn.initializer import Constant, Normal, XavierUniform
from ..ops.registry import apply
from ..tensor_class import Tensor, unwrap, wrap
from .llama import (LlamaAttention, LlamaConfig, LlamaMLP, LlamaRMSNorm,
                    LlamaModel, LlamaForCausalLM)


@dataclasses.dataclass
class LlamaMoEConfig(LlamaConfig):
    """DeepSeekMoE/Qwen2-MoE knobs on top of the Llama base."""

    n_routed_experts: int = 8
    n_shared_experts: int = 1
    shared_expert_gate: bool = False       # Qwen2-MoE sigmoid shared gate
    moe_correction_bias: bool = False      # ERNIE/DeepSeek-V3 aux-free
    # balancing: a per-expert bias added to the router probs for top-k
    # SELECTION only (combine weights stay the raw softmax probs)
    num_experts_per_tok: int = 2
    moe_intermediate_size: int = 1408      # per-expert FFN width
    first_k_dense_replace: int = 1         # leading dense layers (DeepSeek)
    norm_topk_prob: bool = True            # Qwen2-MoE renormalizes top-k
    router_aux_loss_coef: float = 0.001
    moe_capacity_factor: float = 2.0
    # DeepSeek-V3 routing: sigmoid affinity scores (softmax is V2/Qwen2),
    # and a scalar multiplier on the routed-experts output
    moe_scoring_func: str = "softmax"
    routed_scaling_factor: float = 1.0
    # group-limited (device-limited) routing: experts split into n_group
    # groups, top-k restricted to the best topk_group groups per token
    # (DeepSeek-V2 group_limited_greedy / V3 noaux_tc)
    n_group: int = 1
    topk_group: int = 1
    # the experts THIS model holds, as a contiguous range [lo, hi) of the
    # n_routed_experts the router scores (one device's share of an
    # expert-parallel deployment; docs/SERVING.md "A held share of the
    # experts"). None: all of them
    held_experts: Optional[tuple] = None

    @staticmethod
    def tiny_moe(**kw):
        base = dict(vocab_size=512, hidden_size=128, intermediate_size=256,
                    num_hidden_layers=3, num_attention_heads=4,
                    num_key_value_heads=2, max_position_embeddings=256,
                    dtype="float32", n_routed_experts=4,
                    num_experts_per_tok=2, moe_intermediate_size=64,
                    first_k_dense_replace=1)
        base.update(kw)
        return LlamaMoEConfig(**base)


def load_hf_grouped_moe(model, hf_state_dict, *, attn_biases=False,
                        qk_norms=False, shared_expert=False,
                        shared_gate=False, who="load_hf_moe",
                        mlp_key="mlp",
                        expert_keys=("gate_proj", "up_proj", "down_proj")):
    """Shared HF→grouped-layout loader for the Qwen-MoE family shapes:
    embed/norm/lm_head, per-layer attention (optionally q/k/v biases or
    per-head q/k norms), router, per-expert projections packed via
    pack_hf_experts, optional (gated) shared expert. torch [out, in]
    weights transpose to [in, out].

    ``mlp_key``/``expert_keys`` rename the MoE block for checkpoints that
    don't follow the Qwen layout (Mixtral: ``block_sparse_moe`` with
    per-expert ``w1``/``w3``/``w2`` as gate/up/down)."""
    from .llama import _hf_to_np

    cfg = model.config
    E, L = cfg.n_routed_experts, cfg.num_hidden_layers
    mapped, consumed = {}, set()

    def take(hf_key, transpose):
        if hf_key not in hf_state_dict:
            raise KeyError(f"{who}: missing {hf_key!r}")
        consumed.add(hf_key)
        v = _hf_to_np(hf_state_dict[hf_key])
        return v.T if transpose else v

    mapped["llama.embed_tokens.weight"] = take("model.embed_tokens.weight",
                                               False)
    mapped["llama.norm.weight"] = take("model.norm.weight", False)
    if model.lm_head is not None:
        src = ("lm_head.weight" if "lm_head.weight" in hf_state_dict
               else "model.embed_tokens.weight")
        mapped["lm_head.weight"] = take(src, True)
    for i in range(L):
        hf, ours = f"model.layers.{i}", f"llama.layers.{i}"
        for proj in ("q_proj", "k_proj", "v_proj", "o_proj"):
            mapped[f"{ours}.self_attn.{proj}.weight"] = take(
                f"{hf}.self_attn.{proj}.weight", True)
        if attn_biases:
            for proj in ("q_proj", "k_proj", "v_proj"):
                mapped[f"{ours}.self_attn.{proj}.bias"] = take(
                    f"{hf}.self_attn.{proj}.bias", False)
        if qk_norms:
            for norm in ("q_norm", "k_norm"):
                mapped[f"{ours}.self_attn.{norm}.weight"] = take(
                    f"{hf}.self_attn.{norm}.weight", False)
        mapped[f"{ours}.input_layernorm.weight"] = take(
            f"{hf}.input_layernorm.weight", False)
        mapped[f"{ours}.post_attention_layernorm.weight"] = take(
            f"{hf}.post_attention_layernorm.weight", False)
        # router: HF [E, h] -> gate_weight [h, E]
        mapped[f"{ours}.mlp.gate_weight"] = take(
            f"{hf}.{mlp_key}.gate.weight", True)
        (mapped[f"{ours}.mlp.experts.w1"],
         mapped[f"{ours}.mlp.experts.b1"],
         mapped[f"{ours}.mlp.experts.w2"],
         mapped[f"{ours}.mlp.experts.b2"]) = pack_hf_experts(
            take, f"{hf}.{mlp_key}", E, cfg.hidden_size,
            expert_keys=expert_keys)
        if shared_expert:
            for proj in ("gate_proj", "up_proj", "down_proj"):
                mapped[f"{ours}.mlp.shared_expert.{proj}.weight"] = take(
                    f"{hf}.{mlp_key}.shared_expert.{proj}.weight", True)
        if shared_gate:
            # shared gate: HF [1, h] -> [h, 1]
            mapped[f"{ours}.mlp.shared_gate_weight"] = take(
                f"{hf}.{mlp_key}.shared_expert_gate.weight", True)
    leftovers = [k for k in hf_state_dict
                 if k not in consumed and k != "lm_head.weight"
                 and not k.endswith("rotary_emb.inv_freq")]
    if leftovers:
        raise ValueError(
            f"{who}: checkpoint tensors this model cannot represent: "
            f"{leftovers[:5]}{'...' if len(leftovers) > 5 else ''}")
    missing, unexpected = model.set_state_dict(mapped)
    assert not unexpected, unexpected
    if missing:
        raise KeyError(f"{who}: model keys not covered: {missing[:5]}")
    return model


def pack_hf_experts(take, hf_prefix, n_experts, hidden_size,
                    expert_keys=("gate_proj", "up_proj", "down_proj")):
    """Stack a transformers checkpoint's per-expert gate/up/down weights
    into the grouped [E, ...] layout (shared by the qwen2_moe, ernie45 and
    mixtral loaders): returns (w1 fused gate||up, b1 zeros, w2, b2 zeros).
    ``expert_keys`` names the (gate, up, down) projections in the HF
    checkpoint (Mixtral: w1/w3/w2)."""
    import numpy as np

    gate_k, up_k, down_k = expert_keys
    w1 = np.stack([
        np.concatenate([take(f"{hf_prefix}.experts.{e}.{gate_k}.weight",
                             True),
                        take(f"{hf_prefix}.experts.{e}.{up_k}.weight",
                             True)], axis=-1)
        for e in range(n_experts)])
    w2 = np.stack([take(f"{hf_prefix}.experts.{e}.{down_k}.weight", True)
                   for e in range(n_experts)])
    b1 = np.zeros((n_experts, 1, w1.shape[-1]), np.float32)
    b2 = np.zeros((n_experts, 1, hidden_size), np.float32)
    return w1, b1, w2, b2


def valid_rows(kv_cache, s: int):
    """[b, s] bool: the rows of a right-padded prefill that hold a real
    token (``row_lengths`` on the fresh cache: ``generation._PrefillStep``);
    None where the cache says nothing, and every row counts."""
    n = kv_cache.get("row_lengths") if isinstance(kv_cache, dict) else None
    if n is None:
        return None
    return jnp.arange(s)[None, :] < unwrap(n)[:, None]


def with_moe_counts(new_cache, valid, counts):
    """The layer's cache, carrying what its expert layer counted
    (``moe_counts``), where the cache that came in said which rows are
    real (``valid_rows``): the serving programs pass that and take the
    counts out again (``generation.pop_moe_counts``); every other caller's
    cache keeps the keys it came with."""
    if valid is None or not isinstance(new_cache, dict):
        return new_cache
    return dict(new_cache, moe_counts=counts)


class MoEMLP(Layer):
    """Routed experts + shared experts (DeepSeekMoE block).

    The router scores all ``n_routed_experts`` in f32; the layer holds the
    experts ``held_experts = [lo, hi)`` of them (default all) and computes,
    DROPLESSLY, every (token, expert) pair whose expert it holds
    (``distributed.moe.dropless_expert_ffn``): the combine weights are
    normalised over all of a token's choices, so a share's output is what
    the whole layer's would hold of those experts, and the shares of a
    deployment sum to it. Only under an expert-parallel mesh
    (``_ep_axes``: training on the fleet topology) does the layer keep
    the dense GShard dispatch with its capacity, whose einsums GSPMD
    turns into the all_to_alls.

    ``shared_scale`` multiplies the shared experts' summed output (a
    family that AVERAGES its ``n`` shared experts passes ``1 / n``).
    """

    def __init__(self, config: LlamaMoEConfig, shared_scale: float = 1.0):
        super().__init__(dtype=config.dtype)
        from ..distributed.moe import (GroupedMLP, default_ep_axes,
                                       shard_grouped_experts)
        from ..framework.dtype import dtype_guard

        self.config = config
        self.shared_scale = float(shared_scale)
        h = config.hidden_size
        E = config.n_routed_experts
        held = getattr(config, "held_experts", None)
        self.held = (0, E) if held is None else (int(held[0]), int(held[1]))
        if not 0 <= self.held[0] < self.held[1] <= E:
            raise ValueError(
                f"held_experts {held} is no range inside the "
                f"{E} routed experts")
        self.gate_weight = self.create_parameter(
            [h, E], default_initializer=XavierUniform())
        with dtype_guard(config.dtype):  # expert weights in the config dtype
            # SwiGLU experts (reference parity: DeepSeekMoE/Qwen2-MoE/ERNIE
            # experts are gate/up/down; the fused gate‖up keeps it one
            # grouped GEMM) — r5: was a plain 2-matmul silu FFN
            self.experts = GroupedMLP(self.held[1] - self.held[0], h,
                                      config.moe_intermediate_size,
                                      activation="swiglu")
        # expert parallelism: when constructed under a hybrid topology, the
        # expert dim shards over the data axes (the reference's moe group
        # defaults to the dp communicator) and the dispatch einsums become
        # all_to_alls at the EP boundary
        self._ep_axes = shard_grouped_experts(
            self.experts, default_ep_axes(E))
        if self._ep_axes and self.held != (0, E):
            raise NotImplementedError(
                "held_experts is one device's share: it cannot be sharded "
                "again over an expert-parallel mesh")
        if config.n_shared_experts > 0:
            shared_cfg = dataclasses.replace(
                config,
                intermediate_size=config.moe_intermediate_size
                * config.n_shared_experts)
            self.shared_expert = LlamaMLP(shared_cfg)
        else:
            self.shared_expert = None
        if getattr(config, "moe_correction_bias", False):
            self.e_score_correction_bias = self.create_parameter(
                [E], default_initializer=Constant(0.0))
        else:
            self.e_score_correction_bias = None
        if getattr(config, "shared_expert_gate", False):
            # Qwen2-MoE: the shared expert's output is scaled by a learned
            # per-token sigmoid gate (modeling_qwen2_moe shared_expert_gate)
            self.shared_gate_weight = self.create_parameter(
                [h, 1], default_initializer=XavierUniform())
        else:
            self.shared_gate_weight = None
        self._aux_loss = None

    def _ep_constrain(self, arr):
        """Expert-dim sharding constraint on the [E, C, M] dispatched block
        so GSPMD forms the all_to_all at the dispatch/combine boundary."""
        from ..distributed.moe import ep_constrain

        return ep_constrain(arr, self._ep_axes)

    def forward(self, x, router_input=None, valid=None):
        return self.forward_counted(x, router_input, valid)[0]

    def forward_counted(self, x, router_input=None, valid=None):
        """x [b, s, h]. ``router_input``: what the router scores, where it
        is not ``x`` itself (a block that feeds the experts a bf16 cast
        keeps the f32 norm output for the router: a rank-k / rank-k+1
        near-tie decides which expert runs). ``valid`` [b, s] bool: the rows
        whose output is read; the others route nowhere. Returns (out,
        counts): counts int32 [1 + held], the rows routed, then the pairs
        per held expert (the serving engine's counters)."""
        from ..distributed.moe import (_grouped_ffn, compute_capacity,
                                       dropless_expert_ffn, one_hot_dispatch)

        cfg = self.config
        b, s, h = x.shape[0], x.shape[1], x.shape[2]
        k = cfg.num_experts_per_tok
        E = cfg.n_routed_experts

        def route_and_run(xf, gate_w, w1, b1, w2, b2, sel_bias, r_in, ok):
            tokens = xf.reshape(-1, h)
            S = tokens.shape[0]
            with jax.named_scope("moe/router"):
                scored = (tokens if r_in is None else r_in.reshape(-1, h))
                logits = jnp.dot(scored.astype(jnp.float32),
                                 gate_w.astype(jnp.float32),
                                 precision=jax.lax.Precision.HIGHEST)
                if cfg.moe_scoring_func == "sigmoid":
                    # DeepSeek-V3: per-expert sigmoid affinities (top-k over
                    # bias-corrected scores; combine weights renormalize
                    # below)
                    probs = jax.nn.sigmoid(logits)
                elif cfg.moe_scoring_func == "softmax":
                    probs = jax.nn.softmax(logits, axis=-1)
                else:
                    raise ValueError(
                        f"moe_scoring_func must be 'softmax' or 'sigmoid', "
                        f"got {cfg.moe_scoring_func!r}")
                # aux-free balancing (HF Ernie4_5 moe_statics /
                # DeepSeek-V3): the bias picks the experts, the raw probs
                # weight the combine
                sel = (probs + sel_bias.astype(jnp.float32)
                       if sel_bias is not None else probs)
                if cfg.n_group > 1:
                    sel = self._group_limited(sel, sel_bias is not None, S)
                _, topk_idx = jax.lax.top_k(sel, k)
                topk_p = jnp.take_along_axis(probs, topk_idx, axis=-1)
                if cfg.norm_topk_prob:
                    topk_p = topk_p / jnp.maximum(
                        topk_p.sum(-1, keepdims=True), 1e-20)
            rows_ok = None if ok is None else ok.reshape(-1)
            with jax.named_scope("moe/experts"):
                if self._ep_axes:
                    # training on an expert-parallel mesh: the dense GShard
                    # dispatch, whose capacity drops in batch order
                    weights = jnp.zeros((S, E), probs.dtype).at[
                        jnp.arange(S)[:, None], topk_idx].set(topk_p)
                    cap = compute_capacity(S, E, k, cfg.moe_capacity_factor)
                    combine, dispatch = one_hot_dispatch(weights, topk_idx,
                                                         cap)
                    # dispatch tokens: [S,E,C] x [S,M] -> [E,C,M]
                    xe = jnp.einsum("sec,sm->ecm",
                                    dispatch.astype(tokens.dtype), tokens)
                    xe = self._ep_constrain(xe)  # all_to_all boundary (EP)
                    ye = _grouped_ffn(xe, w1, b1, w2, b2, "swiglu")
                    ye = self._ep_constrain(ye)
                    out = jnp.einsum("sec,ecm->sm", combine.astype(ye.dtype),
                                     ye)
                    counts = dispatch.sum((0, 2), dtype=jnp.int32)
                else:
                    out, counts = dropless_expert_ffn(
                        tokens, topk_idx, topk_p, w1, b1, w2, b2, "swiglu",
                        held=self.held, valid=rows_ok)
            if cfg.routed_scaling_factor != 1.0:
                out = out * jnp.asarray(cfg.routed_scaling_factor, out.dtype)
            # Switch-style aux loss on the router DISTRIBUTION — sigmoid
            # affinities don't sum to 1, so the load measure always uses
            # the softmax of the logits
            dist = (probs if cfg.moe_scoring_func == "softmax"
                    else jax.nn.softmax(logits, axis=-1))
            me = dist.mean(0)
            ce = jax.nn.one_hot(topk_idx[:, 0], E,
                                dtype=dist.dtype).mean(0)
            aux = E * jnp.sum(me * ce)
            n_tok = (jnp.asarray(S, jnp.int32) if rows_ok is None
                     else rows_ok.sum(dtype=jnp.int32))
            return (out.reshape(b, s, h).astype(xf.dtype), aux,
                    jnp.concatenate([n_tok[None], counts]))

        out, aux, counts = apply(
            "moe_mlp", route_and_run, x, self.gate_weight,
            self.experts.w1, self.experts.b1, self.experts.w2,
            self.experts.b2, self.e_score_correction_bias, router_input,
            valid)
        self._aux_loss = aux
        if self.shared_expert is not None:
            with jax.named_scope("moe/shared"):
                shared = self.shared_expert(x)
                if self.shared_scale != 1.0:
                    shared = shared * self.shared_scale
            if self.shared_gate_weight is not None:
                # through apply(): the eager tape must record the gate so
                # shared_gate_weight trains outside jit too
                shared = apply(
                    "moe_shared_gate",
                    lambda xx, gw, sh: jax.nn.sigmoid(
                        xx.astype(jnp.float32) @ gw.astype(jnp.float32)
                    ).astype(sh.dtype) * sh,
                    x, self.shared_gate_weight, shared)
            out = out + shared
        return out, counts

    def _group_limited(self, sel, biased: bool, S: int):
        """Group-limited selection (DeepSeek device-limited routing): keep
        only the ``topk_group`` best expert groups per token before the
        expert top-k. Group score: sum of the group's top-2 affinities
        under the aux-free bias (V3 noaux_tc), else the group max (V2
        group_limited_greedy)."""
        cfg = self.config
        E, G, k = cfg.n_routed_experts, cfg.n_group, cfg.num_experts_per_tok
        if E % G != 0:
            raise ValueError(
                f"n_routed_experts {E} not divisible by n_group {G}")
        if k > cfg.topk_group * (E // G):
            # top_k past the surviving experts would hand real combine
            # weight to -inf-masked (out-of-group) experts
            raise ValueError(
                f"num_experts_per_tok {k} exceeds the "
                f"{cfg.topk_group} allowed group(s) x {E // G} "
                f"experts/group")
        sel_g = sel.reshape(S, G, E // G)
        if biased:
            top2, _ = jax.lax.top_k(sel_g, min(2, E // G))
            gscore = top2.sum(-1)
        else:
            gscore = sel_g.max(-1)
        _, gidx = jax.lax.top_k(gscore, cfg.topk_group)
        gmask = jnp.zeros((S, G), bool).at[
            jnp.arange(S)[:, None], gidx].set(True)
        return jnp.where(jnp.repeat(gmask, E // G, axis=1), sel, -jnp.inf)


class LlamaMoEDecoderLayer(Layer):
    """Llama attention block + (dense | MoE) FFN."""

    attn_cls = LlamaAttention  # subclasses (DeepSeek MLA) swap the block

    def __init__(self, config: LlamaMoEConfig, layer_idx: int):
        from .llama import layer_window

        super().__init__(dtype=config.dtype)
        self.self_attn = type(self).attn_cls(config)
        # per-layer window schedule (layer_types) applies to MoE trunks too;
        # attention classes without window support (MLA) must refuse rather
        # than silently attend fully
        if hasattr(self.self_attn, "window"):
            self.self_attn.window = layer_window(config, layer_idx)
        elif getattr(config, "layer_types", None):
            raise NotImplementedError(
                f"{type(self.self_attn).__name__} does not support the "
                "per-layer window schedule (layer_types)")
        self.is_moe = layer_idx >= config.first_k_dense_replace
        self.mlp = MoEMLP(config) if self.is_moe else LlamaMLP(config)
        self.input_layernorm = LlamaRMSNorm(config)
        self.post_attention_layernorm = LlamaRMSNorm(config)

    def forward(self, hidden_states, cos, sin, attention_mask=None,
                kv_cache=None):
        from ..ops.pallas import fused_norm

        residual = hidden_states
        hidden_states = self.input_layernorm(hidden_states)
        new_cache = None
        if kv_cache is not None:
            hidden_states, new_cache = self.self_attn(
                hidden_states, cos, sin, attention_mask, kv_cache)
        else:
            hidden_states = self.self_attn(hidden_states, cos, sin,
                                           attention_mask)
        eps = self.post_attention_layernorm.variance_epsilon
        hidden_states, residual = apply(
            "add_rms_norm",
            lambda a, r, w: fused_norm.add_rms_norm(a, r, w, eps),
            hidden_states, residual,
            self.post_attention_layernorm.effective_weight())
        if self.is_moe:
            valid = valid_rows(kv_cache, hidden_states.shape[1])
            mlp_out, counts = self.mlp.forward_counted(hidden_states,
                                                       valid=valid)
            new_cache = with_moe_counts(new_cache, valid, counts)
        else:
            mlp_out = self.mlp(hidden_states)
        hidden_states = residual + mlp_out
        if kv_cache is not None:
            return hidden_states, new_cache
        return hidden_states


class LlamaMoEModel(LlamaModel):
    """LlamaModel with MoE decoder layers (embed/rope/norm reused)."""

    def __init__(self, config: LlamaMoEConfig):
        # build the base with 0 layers, then install MoE layers (the
        # per-layer schedule validates against num_hidden_layers, so it is
        # cleared for the 0-layer shell and read from the REAL config)
        base_cfg = dataclasses.replace(config, num_hidden_layers=0,
                                       layer_types=None)
        super().__init__(base_cfg)
        self.config = config
        self.layers = nn.LayerList(
            [LlamaMoEDecoderLayer(config, i)
             for i in range(config.num_hidden_layers)])


class LlamaMoEForCausalLM(LlamaForCausalLM):
    """DeepSeekMoE/Qwen2-MoE-style causal LM.

    ``forward(..., labels=...)`` adds ``router_aux_loss_coef`` × the mean
    Switch aux loss over the MoE layers to the LM loss (load balancing)."""

    model_cls = LlamaMoEModel  # subclasses (DeepSeek MLA) swap the trunk

    def __init__(self, config: LlamaMoEConfig):
        Layer.__init__(self, dtype=config.dtype)
        self.config = config
        self.llama = type(self).model_cls(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            from .llama import _make_linear

            self.lm_head = _make_linear(config.hidden_size, config.vocab_size,
                                        column=True, config=config,
                                        gather_output=True)
            self.lm_head.weight._array = (
                Normal(0.0, config.initializer_range)(
                    (config.hidden_size, config.vocab_size), jnp.float32)
                .astype(self.lm_head.weight.dtype))

    def aux_loss(self, extra_layers=()):
        """Mean router aux over every MoE layer that ran — the trunk's,
        plus any ``extra_layers`` (the DeepSeek MTP depth blocks)."""
        losses = [l.mlp._aux_loss
                  for l in list(self.llama.layers) + list(extra_layers)
                  if getattr(l, "is_moe", False)
                  and l.mlp._aux_loss is not None]
        if not losses:
            return None
        total = losses[0]
        for l in losses[1:]:
            total = total + l
        return total / len(losses)

    def forward(self, input_ids, labels=None, attention_mask=None):
        out = super().forward(input_ids, labels=labels,
                              attention_mask=attention_mask)
        if labels is None:
            return out
        loss, logits = out
        aux = self.aux_loss()
        if aux is not None:
            loss = loss + self.config.router_aux_loss_coef * aux
        return loss, logits
