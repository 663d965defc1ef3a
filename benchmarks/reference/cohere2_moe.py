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
import numpy as np

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


def route(spec: Spec, u, router, forced_rows=None, forced_experts=None):
    """(I [S, k] the chosen experts, w [S, k] their weights, the router's
    logits [S, routed] before the sigmoid: a chosen expert's less an
    unchosen one's is the GAP that ``near_tie_alternatives`` reads). A row
    of ``forced_rows`` [S] (None: no row) takes ``forced_experts`` [S, k]
    as told; its weights are its own scores of them."""
    logits = jnp.dot(u, router, precision=HI)
    scores = jax.nn.sigmoid(logits)
    chosen = jnp.argsort(-scores, axis=-1)[:, : spec.top_k]
    if forced_rows is not None:
        chosen = jnp.where(forced_rows[:, None], forced_experts, chosen)
    w = jnp.take_along_axis(scores, chosen, axis=-1)
    if spec.norm_topk_prob:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return chosen, w, logits


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
def block(spec: Spec, sliding: bool, x, w, logit_rows, forced_rows=None,
          forced_experts=None):
    """One parallel block (``sliding``: its layer type) on [S, hidden]
    float32; ``w`` as served. Returns (x', the experts each row chose or
    was told, the router's logits of the rows ``logit_rows``: of none in
    the pass that runs beside the served model, where every array this
    returns counts in ``peak_hbm_gib``)."""
    u = layer_norm(x, w["input_layernorm"].astype(jnp.float32),
                   spec.layer_norm_eps)
    attn = attention(
        spec, u, {k: w[k].astype(jnp.float32)
                  for k in ("q_proj", "k_proj", "v_proj", "o_proj")},
        spec.sliding_window if sliding else None,
        sliding or spec.global_rope)
    chosen, weight, logits = route(
        spec, u, w["gate_weight"].astype(jnp.float32), forced_rows,
        forced_experts)
    routed = routed_share(spec, u, chosen, weight, w["w1"], w["w2"])
    shared = shared_mean(spec, u, w["gate_proj"], w["up_proj"],
                         w["down_proj"])
    return x + attn + routed + shared, chosen, logits[logit_rows]


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


def blocks(spec: Spec, state: dict, ids, forced=None, logit_rows=()):
    """The layers of one sequence in turn: (hidden after the layer [S,
    hidden] f32, the experts each row chose or was told [S, k], the
    router's logits of the positions ``logit_rows`` [len, routed]).
    ``forced``: ``{(layer, position): experts}``, the rows that are routed
    as told; every other row routes itself."""
    ids = jnp.asarray(ids, jnp.int32)
    x = state["llama.embed_tokens.weight"][ids].astype(jnp.float32)
    logit_rows = np.asarray(logit_rows, np.int32)
    told = [()] * spec.num_hidden_layers      # nothing forced: no arrays
    if forced:
        rows = np.zeros((spec.num_hidden_layers, len(ids)), bool)
        experts = np.zeros((spec.num_hidden_layers, len(ids), spec.top_k),
                           np.int32)
        for (layer, position), chosen in forced.items():
            rows[layer, position] = True
            experts[layer, position] = chosen
        told = list(zip(rows, experts))
    for i in range(spec.num_hidden_layers):
        x, chosen, logits = block(
            spec, spec.layer_types[i] == "sliding_attention", x,
            layer_weights(state, i), logit_rows, *told[i])
        yield x, chosen, logits


def forward_hidden(spec: Spec, state: dict, ids, forced=None):
    """(final hidden [S, hidden] f32, the experts chosen in each layer
    [layers, S, k], the router's logits [layers, S, routed]) of one
    sequence; ``forced`` as ``blocks`` takes it. For the tools and the
    tests: it holds every layer's logits."""
    hidden, chosen, logits = zip(*blocks(spec, state, ids, forced,
                                         np.arange(len(ids))))
    return hidden[-1], jnp.stack(chosen), jnp.stack(logits)


def forward_logprobs(spec: Spec, state: dict, ids, last: int, forced=None):
    """log-softmax over the (held slice of the) vocabulary at the last
    ``last`` positions of one sequence ``ids``; ``forced`` as ``blocks``
    takes it. No layer's routing is kept and no logit is returned: this
    pass runs beside the served model, and what it holds counts in
    ``peak_hbm_gib``."""
    for x, _, _ in blocks(spec, state, ids, forced):
        pass
    return head_logprobs(spec, x[-last:], state["llama.norm.weight"],
                         state["llama.embed_tokens.weight"])


# ---- the reference at its own near-ties -------------------------------------

#: the router LOGIT gap (a chosen expert's less an unchosen one's) under
#: which the reference takes both routings for its own. MEASURED, and set
#: from a property of the flips themselves (tools/routing_diff.py, my chip
#: runs, PR 37: seeds 3700000101-3, 8 prompts each of the committed cell,
#: 417,420 routing decisions, 104,355 positions): 4.10% of decisions
#: differ between the program's bf16 pass and this reference; the FIRST
#: held difference of a position (3,594 of them; what follows one in later
#: layers is its consequence, not a tie) lies under 0.0054 in half, 0.0148
#: in 90%, 0.01846 in 95%, 0.0261 in 99%, 0.0381 in 99.9%, 0.0609 at most
#: (rms 0.0092). The constant is their 95TH PERCENTILE, twice their rms.
#: What it costs on either side (PERF.md section 2): 20.2% of ALL
#: decisions lie under it, 19.3% of positions HAVE an alternative (ISSUE
#: 37 sets the rule aside as too loose from a quarter on; the 99.9th
#: percentile it asked for gives 36%), and one in twenty of the tokens
#: that a flip takes over the limit (one run in nine has one) meets a
#: wider flip and still fails.
ROUTING_TIE_GAP = 0.0185
#: alternate routings offered for one position, nearest tie first: every
#: one is one more pass of the reference in a run's set-up, and one more
#: chance that a faulty token is excused. Every token that has stood on an
#: alternate routing so far (6 in sound runs, 6 in the control's and the
#: planted faults' readings; my chip runs, PR 37) stood on the FIRST.
ALTERNATIVES_MAX = 3


def near_tie_swaps(spec: Spec, chosen, logits, tie_gap=None) -> list:
    """The swaps across the top-k boundary of ONE position that
    ``near_tie_alternatives`` builds on, nearest tie first: ``chosen``
    [layers, k] and ``logits`` [layers, routed] of that position ->
    ``{"layer", "out", "in", "gap"}``, ``gap`` the logit of the chosen
    expert ``out`` less that of the unchosen ``in``, under ``tie_gap``,
    and at least one of the two HELD (a swap of two absent experts changes
    nothing this chip computes)."""
    tie_gap = ROUTING_TIE_GAP if tie_gap is None else tie_gap
    lo, hi = spec.held
    swaps = []
    for layer, (picked, scored) in enumerate(zip(np.asarray(chosen),
                                                 np.asarray(logits))):
        inside = [int(e) for e in picked]
        for out in inside:
            for new in range(scored.shape[0]):
                gap = float(scored[out] - scored[new])
                if (new not in inside and gap < tie_gap
                        and (lo <= out < hi or lo <= new < hi)):
                    swaps.append({"layer": layer, "out": out, "in": new,
                                  "gap": gap})
    return sorted(swaps, key=lambda s: s["gap"])


def swapped(chosen, swap: dict) -> list:
    """One layer's ``chosen`` experts of a position with ``swap`` taken."""
    return [swap["in"] if e == swap["out"] else int(e) for e in chosen]


def alternatives_at(spec: Spec, chosen, logits, position: int) -> list:
    """``near_tie_alternatives`` from what the reference's own pass read
    at ``position``: ``chosen`` [layers, k], ``logits`` [layers, routed]."""
    chosen = np.asarray(chosen)
    swaps = near_tie_swaps(spec, chosen, logits)[:ALTERNATIVES_MAX]
    return [{"forced": {(s["layer"], int(position)):
                        swapped(chosen[s["layer"]], s)}, "swaps": [s]}
            for s in swaps]


def near_tie_alternatives(spec: Spec, state: dict, ids, position: int) -> list:
    """The reference is set-valued where its own router all but ties: a
    top-k choice is discontinuous, and where the k-th and (k+1)-th logits
    lie closer than a bf16 pipeline's error both choices are the model's
    answer. The alternate routings of ONE ``position`` of ``ids`` (one
    more pass of the reference to read its own routing there): ONE held
    swap each (``near_tie_swaps``), nearest tie first, at most
    ``ALTERNATIVES_MAX``. Each is ``{"forced": what forward_logprobs
    takes, "swaps": [the swap]}``."""
    here = [(np.asarray(chosen[position]), np.asarray(logits[0]))
            for _, chosen, logits in blocks(spec, state, ids,
                                            logit_rows=[position])]
    return alternatives_at(spec, *zip(*here), position)


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
