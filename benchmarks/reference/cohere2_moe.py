"""The plain reference of the ``cohere2_moe`` family (Command A+: the
language model of ``CohereLabs/command-a-plus-05-2026``), to the contract
in ``reference/__init__.py``: float32, ``Precision.HIGHEST`` on every
contraction, no kernels, no cache, no batching, nothing of the program but
its weights, widened one layer and ONE EXPERT at a time.

The model, from its ``config.json``. For layer ``l`` and hidden ``x``
[T, hidden]:

- ``u = LayerNorm(x) = (x - mean) / sqrt(var + layer_norm_eps) * g``, no
  bias (``rms_norm_eps`` is null).
- ``x' = x + Attn_l(u) + MoE(u)`` (``use_parallel_block``: one norm, both
  branches read ``u``, both add to ``x``).
- ``Attn_l``: ``q = u Wq`` (``num_attention_heads`` x ``head_dim``), ``k = u
  Wk``, ``v = u Wv`` (``num_key_value_heads`` x ``head_dim``), no bias, no
  q/k norm; a group of query heads shares a KV head; scores ``q.k /
  sqrt(head_dim)``, softmax in f32, ``o = heads Wo``.
  ``layer_types[l] == "sliding_attention"``: rotary embedding, theta
  ``rope_theta``, over the whole head on ADJACENT pairs ``(x0, x1), (x2,
  x3)...`` (``position_embedding_type: rope_gptj``), and key ``j`` is
  visible to query ``i`` iff ``i - sliding_window < j <= i``.
  ``"full_attention"``: no rotation at all, plain causal.
- ``MoE(u) = routed(u) + shared(u)``. ``s = sigmoid(u Wr)`` over all routed
  experts; ``I`` the ``num_experts_per_tok`` largest (plain ``argsort``);
  ``w_i = s_i / sum_{j in I} s_j`` (``norm_topk_prob``); ``routed = sum_{i in
  I} w_i E_i(u)``, ``E(u) = (silu(u Wg) * (u Wu)) Wd`` of width
  ``intermediate_size``; ``shared = (1 / n) sum_j S_j(u)``, ``n =
  num_shared_experts`` experts of the same form, always on.
- Head: ``LayerNorm_f(x) E^T x logit_scale``, ``E`` the tied embedding.

**The share.** The configuration holds the experts ``held_experts = [lo,
hi)`` of the ``published_num_experts`` the router scores: ``routed_H =
sum_{i in I, lo <= i < hi} w_i E_i(u)`` with ``w`` normalised over ALL of
``I``. What the absent experts would add is left out here as in the
program, and ``x + Attn + shared + routed_H`` goes to the next layer.

Departures, all ``assumed`` in the configuration's file: one expert's width
is ``intermediate_size``; ``shared_expert_combination_strategy: "average"``
is the MEAN of the shared experts' outputs, added to the routed sum; global
layers carry no rotation. The program holds the shared experts as one
SwiGLU of ``n x intermediate_size`` columns; expert ``j`` is columns ``[j f,
(j + 1) f)`` of its gate and up and rows of its down.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from benchmarks.reference._costs import roofline_seconds  # noqa: F401

HI = jax.lax.Precision.HIGHEST
#: query rows attended at once: 128 heads x 64 x 8192 f32 scores are 256 MiB
QUERY_BLOCK = 64


class Spec(NamedTuple):
    vocab_size: int
    hidden_size: int
    intermediate_size: int
    num_hidden_layers: int
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    layer_norm_eps: float
    rope_theta: float
    sliding_window: int
    layer_types: Tuple[str, ...]
    routed_experts: int         # the width the router scores
    held: Tuple[int, int]       # [lo, hi) of them, the experts held
    top_k: int
    shared_experts: int
    norm_topk_prob: bool
    logit_scale: float
    max_context: int            # the longest row the engine serves
    rope_pairs: str = "adjacent"    # "adjacent" (rope_gptj) | "halves"
    global_rope: bool = False       # full_attention layers rotate?

    @classmethod
    def from_config(cls, cfg: dict) -> "Spec":
        if cfg.get("model_type") != "cohere2_moe":
            raise ValueError(
                f"reference cohere2_moe: model_type {cfg.get('model_type')!r}")
        for key, want in (("use_parallel_block", True),
                          ("position_embedding_type", "rope_gptj"),
                          ("expert_selection_fn", "sigmoid"),
                          ("shared_expert_combination_strategy", "average"),
                          ("tie_word_embeddings", True),
                          ("use_qk_norm", False), ("attention_bias", False),
                          ("first_k_dense_replace", 0),
                          ("hidden_act", "silu")):
            if cfg.get(key) != want:
                raise ValueError(f"reference cohere2_moe: {key} = "
                                 f"{cfg.get(key)!r}, covered: {want!r}")
        held_n = int(cfg["num_experts"])
        lo, hi = cfg.get("held_experts") or (0, held_n)
        if hi - lo != held_n:
            raise ValueError(f"reference cohere2_moe: held_experts [{lo}, "
                             f"{hi}) is not num_experts = {held_n} wide")
        layers = int(cfg["num_hidden_layers"])
        types = tuple(cfg["layer_types"])
        if len(types) != layers:
            raise ValueError("reference cohere2_moe: layer_types and "
                             "num_hidden_layers disagree")
        return cls(
            vocab_size=int(cfg["vocab_size"]),
            hidden_size=int(cfg["hidden_size"]),
            intermediate_size=int(cfg["intermediate_size"]),
            num_hidden_layers=layers,
            num_attention_heads=int(cfg["num_attention_heads"]),
            num_key_value_heads=int(cfg["num_key_value_heads"]),
            head_dim=int(cfg["head_dim"]),
            layer_norm_eps=float(cfg["layer_norm_eps"]),
            rope_theta=float(cfg["rope_theta"]),
            sliding_window=int(cfg["sliding_window"]),
            layer_types=types,
            routed_experts=int(cfg.get("published_num_experts") or held_n),
            held=(int(lo), int(hi)),
            top_k=int(cfg["num_experts_per_tok"]),
            shared_experts=int(cfg["num_shared_experts"]),
            norm_topk_prob=bool(cfg["norm_topk_prob"]),
            logit_scale=float(cfg["logit_scale"]),
            max_context=int(cfg["recipe"]["engine"]["max_len"]))


def layer_norm(x, g, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * g


def rope(x, theta, pairs):
    """x [S, heads, D] at positions 0..S-1. ``adjacent``: pair i is lanes
    (2i, 2i + 1); ``halves``: lanes (i, i + D/2). Both at theta^(-2i/D)."""
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.outer(jnp.arange(s, dtype=jnp.float32), inv)[:, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if pairs == "adjacent":
        a, b = x[..., 0::2], x[..., 1::2]
        return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                         axis=-1).reshape(x.shape)
    a, b = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(spec: Spec, u, w, window, rotates):
    """u [S, hidden] -> [S, hidden]; ``window`` None: plain causal."""
    s = u.shape[0]
    h, hk, d = (spec.num_attention_heads, spec.num_key_value_heads,
                spec.head_dim)
    q = jnp.dot(u, w["q_proj"], precision=HI).reshape(s, h, d)
    k = jnp.dot(u, w["k_proj"], precision=HI).reshape(s, hk, d)
    v = jnp.dot(u, w["v_proj"], precision=HI).reshape(s, hk, d)
    if rotates:
        q = rope(q, spec.rope_theta, spec.rope_pairs)
        k = rope(k, spec.rope_theta, spec.rope_pairs)
    blocks = -(-s // QUERY_BLOCK)
    q = jnp.pad(q, ((0, blocks * QUERY_BLOCK - s), (0, 0), (0, 0)))
    q = q.reshape(blocks, QUERY_BLOCK, hk, h // hk, d)
    keys = jnp.arange(s)

    def block(args):
        qb, first = args
        rows = first + jnp.arange(QUERY_BLOCK)
        scores = jnp.einsum("qkgd,tkd->kgqt", qb, k, precision=HI)
        scores = scores / jnp.sqrt(jnp.float32(d))
        seen = keys[None, :] <= rows[:, None]
        if window is not None:
            seen = seen & (keys[None, :] > rows[:, None] - window)
        # a pad row past the sequence sees key 0: finite, and never read
        seen = seen | ((rows[:, None] >= s) & (keys[None, :] == 0))
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("kgqt,tkd->qkgd", probs, v, precision=HI)

    out = jax.lax.map(block, (q, jnp.arange(blocks) * QUERY_BLOCK))
    out = out.reshape(blocks * QUERY_BLOCK, h * d)[:s]
    return jnp.dot(out, w["o_proj"], precision=HI)


def swiglu(u, gate, up, down):
    return jnp.dot(jax.nn.silu(jnp.dot(u, gate, precision=HI))
                   * jnp.dot(u, up, precision=HI), down, precision=HI)


def route(spec: Spec, u, router):
    """(I [S, k] the chosen experts, w [S, k] their weights)."""
    scores = jax.nn.sigmoid(jnp.dot(u, router, precision=HI))
    chosen = jnp.argsort(-scores, axis=-1)[:, : spec.top_k]
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if spec.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w


def routed_share(spec: Spec, u, chosen, w, w1, w2):
    """sum over the HELD experts of a token's choices; ``w1`` [held,
    hidden, 2 f] is gate then up, ``w2`` [held, f, hidden], both in the
    type they are served in: one expert is widened at a time."""
    f = spec.intermediate_size
    lo = spec.held[0]

    def one(e, acc):
        weight = jnp.sum(jnp.where(chosen == lo + e, w, 0.0), axis=-1)
        wide = w1[e].astype(jnp.float32)
        y = swiglu(u, wide[:, :f], wide[:, f:], w2[e].astype(jnp.float32))
        return acc + weight[:, None] * y

    return jax.lax.fori_loop(0, w1.shape[0], one, jnp.zeros_like(u))


def shared_mean(spec: Spec, u, gate, up, down):
    n, f = spec.shared_experts, spec.intermediate_size
    total = jnp.zeros_like(u)
    for j in range(n):
        cols = slice(j * f, (j + 1) * f)
        total = total + swiglu(u, gate[:, cols].astype(jnp.float32),
                               up[:, cols].astype(jnp.float32),
                               down[cols].astype(jnp.float32))
    return total / n


@functools.partial(jax.jit, static_argnums=(0, 1))
def block(spec: Spec, sliding: bool, x, w):
    """One parallel block (``sliding``: its layer type) on [S, hidden]
    float32; ``w`` as served. Returns (x', the experts each row chose)."""
    u = layer_norm(x, w["input_layernorm"].astype(jnp.float32),
                   spec.layer_norm_eps)
    attn = attention(
        spec, u, {k: w[k].astype(jnp.float32)
                  for k in ("q_proj", "k_proj", "v_proj", "o_proj")},
        spec.sliding_window if sliding else None,
        sliding or spec.global_rope)
    chosen, weight = route(spec, u, w["gate_weight"].astype(jnp.float32))
    routed = routed_share(spec, u, chosen, weight, w["w1"], w["w2"])
    shared = shared_mean(spec, u, w["gate_proj"], w["up_proj"],
                         w["down_proj"])
    return x + attn + routed + shared, chosen


@functools.partial(jax.jit, static_argnums=0)
def head_logprobs(spec: Spec, x, norm_w, embed):
    x = layer_norm(x, norm_w.astype(jnp.float32), spec.layer_norm_eps)
    logits = jnp.dot(x, embed.astype(jnp.float32).T, precision=HI)
    return jax.nn.log_softmax(logits * spec.logit_scale, axis=-1)


def layer_weights(state: dict, i: int) -> dict:
    """Layer ``i``'s arrays by their last name (``q_proj``, ``gate_weight``,
    ``w1`` ...) from the trunk's state dict; biases (all zero in the
    program, none in the architecture) are left behind."""
    pre = f"llama.layers.{i}."
    out = {}
    for key, arr in state.items():
        if key.startswith(pre):
            name = key[len(pre):].split(".")
            last = name[-2] if name[-1] == "weight" else name[-1]
            if last not in ("b1", "b2", "bias"):
                out[last] = arr
    return out


def forward_hidden(spec: Spec, state: dict, ids):
    """(final hidden [S, hidden] f32, the experts chosen in each layer
    [layers, S, k]) of one sequence."""
    ids = jnp.asarray(ids, jnp.int32)
    x = state["llama.embed_tokens.weight"][ids].astype(jnp.float32)
    chosen = []
    for i in range(spec.num_hidden_layers):
        x, picked = block(spec, spec.layer_types[i] == "sliding_attention",
                          x, layer_weights(state, i))
        chosen.append(picked)
    return x, jnp.stack(chosen)


def forward_logprobs(spec: Spec, state: dict, ids, last: int):
    """log-softmax over the (held slice of the) vocabulary at the last
    ``last`` positions of one sequence ``ids``."""
    x, _ = forward_hidden(spec, state, ids)
    return head_logprobs(spec, x[-last:], state["llama.norm.weight"],
                         state["llama.embed_tokens.weight"])


# ---- operations and bytes, from shapes --------------------------------------

def matmul_params(spec: Spec) -> float:
    """Parameters one token's forward pass multiplies: the projections,
    the shared experts, the router, the routed experts a token reaches
    HERE (``top_k x held / routed`` of them in expectation: one), and the
    head."""
    h, d, f = spec.hidden_size, spec.head_dim, spec.intermediate_size
    attn = h * d * (2 * spec.num_attention_heads
                    + 2 * spec.num_key_value_heads)
    reached = spec.top_k * (spec.held[1] - spec.held[0]) / spec.routed_experts
    layer = (attn + 3 * h * f * (spec.shared_experts + reached)
             + h * spec.routed_experts)
    return spec.num_hidden_layers * layer + h * spec.vocab_size


def attended_keys(spec: Spec, context: float) -> float:
    """Keys one token attends to, summed over layers, from the MEAN
    ``context`` of the full-causal keys its tokens see (all the reader
    knows). A global layer: ``context``. A window layer sees ``min(keys,
    window)``, whose mean is BELOW ``min(context, window)``; what can
    never over-count is the chord of ``min(., window)`` over ``[0,
    max_context]``: ``context x window / max_context`` (an under-count,
    about a third of a window layer's attention for the longest prompts:
    PERF.md section 3)."""
    n_window = sum(t == "sliding_attention" for t in spec.layer_types)
    share = min(1.0, spec.sliding_window / spec.max_context)
    return context * (spec.num_hidden_layers - n_window + n_window * share)


def serve_flops_per_token(spec: Spec, context: float,
                          sampled_share: float = 1.0) -> float:
    """What serving REQUIRES per token that enters the model: its matrices
    (``matmul_params``), QK^T and PV over the keys it sees
    (``attended_keys``), the head for the ``sampled_share`` of tokens a
    token is drawn from."""
    head = spec.hidden_size * spec.vocab_size
    return (2.0 * (matmul_params(spec) - head * (1.0 - sampled_share))
            + 4.0 * spec.num_attention_heads * spec.head_dim
            * attended_keys(spec, context))


def paged_decode_cost(spec: Spec, context_tokens: float,
                      rows: float) -> dict:
    """ONE global layer's paged decode attention call over ``rows``
    sequences holding ``context_tokens`` cached tokens in all: every
    cached K and V row once (bf16), q and the output once. (A window
    layer's decode runs no kernel: it reads its ring through XLA.)"""
    kv_row = 2 * spec.num_key_value_heads * spec.head_dim * 2
    qo = 2 * rows * spec.num_attention_heads * spec.head_dim * 2
    return {"flops": 4.0 * context_tokens * spec.num_attention_heads
            * spec.head_dim,
            "bytes": float(context_tokens * kv_row + qo)}


def ragged_dot_cost(spec: Spec, pairs: float) -> dict:
    """The grouped expert matmuls over ``pairs`` held (token, expert)
    pairs: gate, up and down of width ``intermediate_size`` a pair; the
    bytes are the pairs' rows in and out (bf16), NOT the experts' weights,
    which depend on how the pairs fall into calls."""
    h, f = spec.hidden_size, spec.intermediate_size
    return {"flops": 6.0 * pairs * h * f,
            "bytes": float(pairs * (2 * h + 3 * f) * 2)}
